"""Share of a sweep pass the main thread waited on ingest (parse and
encode in the pool): its `parse` phase spans over the pass wall time."""

from harness import spans


def read(r):
    p = r["pass"]
    if not p.get("events"):
        return None
    return 100.0 * spans.main_thread_seconds(p["events"], "parse") \
        / p["wall_s"]
