"""Which device time in an append sweep's trace is the Elle check's.

analyze-store runs its check executables from the program's AOT cache,
and every deserialized executable reaches the trace as `jit__unknown`,
whatever it computes. Each bucket the pass dispatched runs its check
executable once, so the matched executions have to number the pass's
`buckets_dispatched` counter: where they do not, another executable
shares the name, and the Elle metrics read nothing rather than fold its
time into the kernels'.
"""

from __future__ import annotations

import sys

PATTERNS = ("check_batched_impl", "jit__unknown")


def check_seconds(r) -> float | None:
    """Device seconds of the Elle check executables in the traced pass,
    or None where there are none or their count is not the pass's."""
    tr = r["trace"]
    runs = tr.module_runs(PATTERNS)
    want = r["pass"]["counters"].get("buckets_dispatched")
    if runs != want:
        print(f"elle metrics: {runs} executions match {PATTERNS}, the "
              f"pass dispatched {want} buckets: left out",
              file=sys.stderr, flush=True)
        return None
    s = tr.module_s(PATTERNS)
    return s if s > 0 else None
