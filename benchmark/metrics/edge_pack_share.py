"""Share of a wr sweep pass the main thread spent packing host-built
edge lists into dense [B,T,T] bool matrices: its `edge_pack` phase spans
over the pass wall time. A program that records no such span has
nothing to read."""

from harness import spans


def read(r):
    p = r["pass"]
    ev = p.get("events")
    if not ev or not any(n == "edge_pack"
                         for _t, _d, n in spans.main_thread_phases(ev)):
        return None
    return 100.0 * spans.main_thread_seconds(ev, "edge_pack") \
        / p["wall_s"]
