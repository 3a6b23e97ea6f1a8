"""Share of a register pass the main thread spent loading the runs'
histories (`ingest.parallel_load`): its `register_load` phase spans over
the pass wall time."""

from harness import spans


def read(r):
    p = r["pass"]
    ev = p.get("events")
    if not ev or not any(n == "register_load"
                         for _t, _d, n in spans.main_thread_phases(ev)):
        return None
    return 100.0 * spans.main_thread_seconds(ev, "register_load") \
        / p["wall_s"]
