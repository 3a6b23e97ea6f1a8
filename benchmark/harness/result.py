"""The result line, and the numbers `correct` is decided on."""

from __future__ import annotations

import json
import sys


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks, over every value given."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Checks:
    """Numbers compared, each with its limit: a run is correct when
    every number is at or under its limit."""

    def __init__(self):
        self.items: dict = {}

    def add(self, name: str, value, limit) -> None:
        self.items[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            c["value"] <= c["limit"] for c in self.items.values())


def device_info(devices, n_used: int) -> dict:
    used = devices[:n_used]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(peak)}


def emit(*, checks: Checks, attempted: int, failed: int, metrics: dict,
         device: dict, breakdown: dict | None = None) -> None:
    """Print the compared numbers as the last lines on stderr, and the
    result as the last line on stdout, `checks` its last key."""
    for name, c in checks.items.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    line = {"correct": checks.correct, "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks.items
    print(json.dumps(line), flush=True)
