"""Share of a sweep pass the main thread spent in JAX's compile steps:
its `jit_trace`, `jit_lower`, `jit_compile` and `compile_cache_load`
spans, merged so that a step nested in another counts once, over the
pass wall time. A trace.json whose process metadata lacks the tracer's
realtime origin comes from a program that records no compile spans:
there is nothing to read."""

from harness import spans

NAMES = ("jit_trace", "jit_lower", "jit_compile", "compile_cache_load")


def records_compiles(events: list) -> bool:
    return any(e.get("name") == "process_name" and "origin_realtime_ns"
               in (e.get("args") or {}) for e in events)


def read(r):
    p = r["pass"]
    ev = p.get("events")
    if not ev or not records_compiles(ev):
        return None
    total, end = 0.0, None
    for t, d, _n in sorted((t, d, n) for t, d, n
                           in spans.main_thread_phases(ev) if n in NAMES):
        start = t if end is None else max(t, end)
        if t + d > start:
            total += t + d - start
        end = t + d if end is None else max(end, t + d)
    return 100.0 * total / 1e6 / p["wall_s"]
