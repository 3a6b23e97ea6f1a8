"""The JAX side of the tracer: profiler annotations and compile spans.

`jepsen_tpu.trace` stays stdlib-only; this module, imported only by
entry points that already run JAX (`analyze-store`, the verdict daemon,
an enabled `--jax-profile` session), connects the two once per process
(`install`):

  * every span and phase of an enabled tracer also opens a
    `jax.profiler.TraceAnnotation` named `program:<name>` on its own
    thread, so a running profiler records the program's spans beside
    the device ops;
  * JAX's own compile events become spans of the current tracer, on
    the thread that compiled, category `phase` (they name the main
    thread's idle gaps; they add nothing to `phase_totals`), each with
    `fun=<jitted function>`:

    - `jit_trace` (counter `jit_traces`):
      /jax/core/compile/jaxpr_trace_duration;
    - `jit_lower` (`jit_lowerings`):
      /jax/core/compile/jaxpr_to_mlir_module_duration;
    - `jit_compile` (`jit_compiles`):
      /jax/core/compile/backend_compile_duration;
    - `compile_cache_load`:
      /jax/compilation_cache/cache_retrieval_time_sec, a duration,
      placed to end when it is reported.

Tracing a jitted function traces the jitted functions it calls inside
that trace (`jnp` ops are jitted), and each reports its own event: only
the outermost step of each kind on a thread becomes a span and counts
(JAX announces a step's start as a scalar event, which keeps the
depth). `jit_compile` covers the persistent-cache lookup, so a
`compile_cache_load` nests inside it. JAX reports the cache lookup
without the function's name, so that span carries no `fun`.
"""

from __future__ import annotations

import threading
import time

from . import trace

#: JAX time-span event -> (span name, counter).
SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit_trace", "jit_traces"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jit_lower", "jit_lowerings"),
    "/jax/core/compile/backend_compile_duration":
        ("jit_compile", "jit_compiles"),
}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_installed = False
#: per thread: event -> how many of its steps are open
_open = threading.local()


def _depths() -> dict:
    d = getattr(_open, "d", None)
    if d is None:
        d = _open.d = {}
    return d


def _on_start(event: str, value, **kw) -> None:
    if event in SPAN_EVENTS:
        d = _depths()
        d[event] = d.get(event, 0) + 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    got = SPAN_EVENTS.get(event)
    if got is None:
        return
    d = _depths()
    depth = d.get(event, 0)
    if depth > 0:
        d[event] = depth - 1
    if depth > 1:
        return      # a step nested in one of its own kind
    tr = trace.get_current()
    if not tr.enabled:
        return
    name, counter = got
    tr.add_span(name, start, end, clock="realtime", cat="phase",
                fun=str(kw.get("fun_name", "")))
    tr.counter(counter).inc()


def _on_duration(event: str, secs: float, **kw) -> None:
    if event != CACHE_LOAD_EVENT:
        return
    tr = trace.get_current()
    if not tr.enabled:
        return
    end = time.time()
    tr.add_span("compile_cache_load", end - secs, end, clock="realtime",
                cat="phase", **({"fun": str(kw["fun_name"])}
                                if "fun_name" in kw else {}))


def install() -> None:
    """Install the annotation factory and the compile listeners, once
    per process (later calls are no-ops)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax
        from jax import monitoring
        trace.set_annotation(jax.profiler.TraceAnnotation)
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
