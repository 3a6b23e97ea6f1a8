"""jepsen_tpu.obs — LIVE telemetry over the per-run tracer.

PR-2 made every run self-attributing, but only *post hoc*: counters
live inside the Tracer until the sweep exits, so a running sweep is a
black box — an operator (or the multi-host coordinator / the future
`serve` daemon) cannot ask "how far along, how healthy, how fast"
mid-flight. The online-checking literature (PAPERS.md, arxiv
2504.01477) makes the same point about checkers themselves:
infrastructure that runs continuously must be observable continuously.
This package is that layer, in four stdlib-only pieces:

  * `health` — a background sampler thread (gated by
    `JEPSEN_TPU_HEALTH_INTERVAL_S`, default off) that every N seconds
    atomically writes `<store>/health.json`: sweep progress (runs
    verdicted / total, buckets dispatched vs resolved, inflight
    depth), robustness posture (quarantine/OOM/watchdog counters),
    throughput + ETA, and a monotonic heartbeat so a wedged sweep is
    distinguishable from a slow one. Write-to-temp-then-rename: a
    reader never sees a torn file.
  * `prom` — the Prometheus text-exposition renderer plus an optional
    `http.server` endpoint (`JEPSEN_TPU_METRICS_PORT`) serving
    `/metrics` (counters/gauges/histograms; log2 magnitude buckets map
    to cumulative `_bucket` series) and `/healthz` (the same snapshot
    as health.json) — the scrape surface the future `serve` daemon and
    per-shard mesh sweeps will expose.
  * `events` — the flight recorder: an append-only
    `<store>/events.jsonl` of TYPED lifecycle events (sweep
    start/resume/end, quarantine with cause, OOM split, watchdog fire,
    journal seal, cache rebuild), each line flushed as it lands (the
    VerdictJournal discipline), so a post-mortem on a SIGKILLed sweep
    has a causal record even when trace.json was never written. Lint
    rule JT-TRACE-003 requires every event to go through
    `events.emit` with a declared kind — no ad-hoc dict writes.
  * `attribution` — the critical-path report over the MERGED sweep
    timeline (parent phases + per-worker spool tracks + device
    windows): serial bottleneck decomposition, device-gap stall
    accounting, and what-if headroom, persisted by `analyze-store
    --report` as `<store>/report.json` + `report.md` and embedded in
    the bench's north_star/cache_warm blocks.
  * `device` — the device cost observatory (JEPSEN_TPU_COSTDB,
    default off): per-executable XLA cost/memory analyses joined
    with measured dispatch windows, the HBM residency gauges, and
    the persistent `<store>/costdb.jsonl` the cost-aware planner
    consumes; `--report` grows a device roofline section from the
    same records.

The whole package imports nothing but the stdlib (plus `gates` and
`trace`, themselves stdlib-only); jax is never touched. Everything is
gated off by default — with both gates unset a sweep pays nothing but
one `gates.get` per entry point.
"""

from __future__ import annotations

from . import attribution, device, events
from .events import EVENT_KINDS, emit, install_events, load_events, reset_events
from .health import HealthSampler, health_snapshot, maybe_start_health_sampler
from .prom import MetricsServer, maybe_start_metrics_server, render_prometheus

__all__ = [
    "EVENT_KINDS", "HealthSampler", "MetricsServer", "attribution",
    "device", "emit", "events", "health_snapshot", "install_events",
    "load_events", "maybe_start_health_sampler",
    "maybe_start_metrics_server", "render_prometheus", "reset_events",
]
