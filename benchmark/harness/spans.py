"""Reading the program's own `trace.json` (Chrome trace events)."""

from __future__ import annotations


def main_thread_phases(events: list) -> list:
    """The sweep's main-thread `phase` spans: (ts_us, dur_us, name)."""
    procs = {e["pid"] for e in events if e.get("ph") == "M"
             and e.get("name") == "process_name"
             and str(e["args"].get("name", "")).startswith("analyze-store")}
    mains = {(e["pid"], e["tid"]) for e in events if e.get("ph") == "M"
             and e.get("name") == "thread_name" and e["pid"] in procs
             and e["args"].get("name") == "MainThread"}
    return [(e["ts"], e["dur"], e["name"]) for e in events
            if e.get("ph") == "X" and e.get("cat") == "phase"
            and (e.get("pid"), e.get("tid")) in mains]


def main_thread_seconds(events: list, name: str) -> float:
    """Seconds of the sweep's main-thread `phase` spans called `name`:
    time the main thread spent blocked in that phase."""
    return sum(d for _t, d, n in main_thread_phases(events)
               if n == name) / 1e6


def on_profile_clock(spans, offset_us: float, anchor_ns: float) -> list:
    """(ts_us, dur_us, name) spans of a program tracer whose clock read
    `offset_us` at the profiler instant `anchor_ns`, as (start_ns,
    end_ns, name) on the profiler's clock (none without an anchor)."""
    if anchor_ns is None:
        return []
    return [(anchor_ns + (t - offset_us) * 1e3,
             anchor_ns + (t + d - offset_us) * 1e3, "program:" + n)
            for t, d, n in spans]
