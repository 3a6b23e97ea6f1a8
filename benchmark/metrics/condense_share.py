"""Share of a sweep pass the main thread spent condensing histories past
the dense limit (edge build, native Tarjan, SCC grouping): its
`condense` phase spans over the pass wall time. A program that records
no such span has nothing to read."""

from harness import spans


def read(r):
    p = r["pass"]
    ev = p.get("events")
    if not ev or not any(n == "condense"
                         for _t, _d, n in spans.main_thread_phases(ev)):
        return None
    return 100.0 * spans.main_thread_seconds(ev, "condense") / p["wall_s"]
