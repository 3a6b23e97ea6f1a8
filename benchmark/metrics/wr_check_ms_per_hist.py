"""Device time of the wr check executable (`classify_matrices_device`:
edge-matrix closure and classify) per history checked in the traced
pass, counted against the pass's bucket dispatches (harness/wr.py)."""

from harness import wr


def read(r):
    s = wr.check_seconds(r)
    return None if s is None else 1000.0 * s / r["runs"]
