"""Share of a sweep pass the main thread waited on the device: its
`collect` phase spans (block on a bucket's flags) over pass wall time."""

from harness import spans


def read(r):
    p = r["pass"]
    if not p.get("events"):
        return None
    return 100.0 * spans.main_thread_seconds(p["events"], "collect") \
        / p["wall_s"]
