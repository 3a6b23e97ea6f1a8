"""Which device time in an rw-register sweep's trace is the wr check's.

The wr sweep dispatches each bucket's packed edge matrices to one jitted
executable, `jit_classify_matrices_device`, once per bucket. So the
matched executions have to number the pass's `buckets_dispatched`
counter: where they do not (a program that counts no wr dispatches, or
another executable sharing the name), the wr metrics read nothing rather
than a wrong time.
"""

from __future__ import annotations

import sys

PATTERNS = ("classify_matrices_device",)


def check_seconds(r) -> float | None:
    """Device seconds of the wr check executable in the traced pass, or
    None where there are none or their count is not the pass's."""
    tr = r["trace"]
    runs = tr.module_runs(PATTERNS)
    want = r["pass"]["counters"].get("buckets_dispatched")
    if runs != want:
        print(f"wr metrics: {runs} executions match {PATTERNS}, the pass "
              f"dispatched {want} buckets: left out",
              file=sys.stderr, flush=True)
        return None
    s = tr.module_s(PATTERNS)
    return s if s > 0 else None
