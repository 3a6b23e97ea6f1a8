"""Work counts for roofline shares, kept with the benchmark.

The Elle closure decides reachability over a history's n real txns
(committed and indeterminate; not the padded T). Whatever the kernel
does, it must at least form one n x n boolean product: 2 n^3 int8
operations over two n x n int8 operands, 2 n^2 bytes. That is a floor
that does not depend on how many squaring rounds an implementation
runs, so no later kernel can read above 100% by doing fewer; it reads
low by design (a fixpoint closure runs about log2(n) such products).
"""


def elle_closure_work(n: int) -> tuple[float, float]:
    """(int8 operations, HBM bytes) floor for one history of n txns."""
    return 2.0 * n ** 3, 2.0 * n ** 2


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The larger of compute time at the int8 peak and memory time at
    the HBM peak."""
    return max(ops / peak["int8_ops"], nbytes / peak["hbm_bytes_per_s"])
