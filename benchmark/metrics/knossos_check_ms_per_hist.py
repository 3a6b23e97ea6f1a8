"""Device time of the Knossos executables (dense grid, frontier, packed
frontier) per stored run checked in the traced pass."""

PATTERNS = ("check_dense_device", "check_batch_device", "packed")


def read(r):
    s = r["trace"].module_s(PATTERNS)
    return 1000.0 * s / r["runs"] if s > 0 else None
