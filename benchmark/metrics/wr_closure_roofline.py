"""The wr check executable's share of its roofline: the least time the
chip could take for the closure work of the traced pass's histories
(harness/roofline.py: one n x n int8 product per history of n real
txns, a floor that reads low by design) over the measured device time
of `classify_matrices_device` (harness/wr.py)."""

from harness import peaks, roofline, wr


def read(r):
    measured = wr.check_seconds(r)
    if measured is None:
        return None
    pk = peaks.peaks(r["device_kind"])
    least = sum(roofline.least_seconds(*roofline.elle_closure_work(n), pk)
                for n in r["txn_counts"])
    return 100.0 * least / measured
