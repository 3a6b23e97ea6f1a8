"""Device time of the Elle check executables per history checked in the
traced pass. analyze-store loads its check executables from the
program's own AOT cache, and a deserialized executable reaches the
trace as `jit__unknown`, whatever it computes (harness/elle.py)."""

from harness import elle


def read(r):
    s = elle.check_seconds(r)
    return None if s is None else 1000.0 * s / r["runs"]
