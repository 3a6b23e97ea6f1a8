"""The device cost observatory: per-executable XLA cost/memory capture
joined with measured dispatch windows, feeding a persistent costdb.

Until now device time was one opaque span: the tracer records each
dispatch's enqueue→block window, but nothing ever read XLA's own
`cost_analysis()` / `memory_analysis()` even though every bucket
dispatch flows through `aot.compiled_for`'s `lower().compile()` hook —
so MFU was an analytic estimate against a hard-coded v5e peak and the
cost-aware planner (ROADMAP item 4) had no empirical
per-(kernel, geometry) cost model to train on. This module closes
that loop in three parts, all behind `JEPSEN_TPU_COSTDB` (default
off ⇒ zero new files, <1µs per dispatch):

  * **capture** — `observe()` runs once per (kernel flags + bucket
    geometry) key: the compiled executable's `cost_analysis()`
    (flops, bytes accessed, transcendentals) and
    `memory_analysis()` (argument/output/temp/generated-code bytes),
    called from `aot.compiled_for` for every bucket dispatch, and —
    with the AOT cache off — from
    `residency.ExecutableResidency.dispatch_fn` (via a one-time
    `jit.lower()`, no compile).
  * **join** — `begin_dispatch`/`close_dispatch` bracket each bucket
    dispatch's measured device window (the same enqueue→materialized
    window the tracer's device track records) and accumulate it into
    the key's record, so every record is analysis × measurement.
    The same bracket maintains the residency gauges: resident
    executables (the AOT in-memory map), modeled HBM in flight
    (argument + temp + output bytes of outstanding dispatches) and —
    throttled by `JEPSEN_TPU_RESIDENCY_INTERVAL_S` — the backend's
    own `device.memory_stats()` where the platform reports one.
  * **persist** — `flush()` appends one JSON line per (executable,
    geometry) record to `<store>/costdb.jsonl` (store.append_costdb:
    flushed per line, torn tails skipped on load like the journal);
    mesh shards flush to `costdb-shard<k>.jsonl` and the coordinator
    merges them (`merge_records`) into one deduplicated costdb.

Records carry a `provenance` field — `"measured"` only when the
windows were taken on a real accelerator backend; a CPU host's wall
windows are honest host measurements but NOT TPU numbers, so they tag
`"estimated"` instead of silently impersonating hardware. Everything
here is best-effort: any capture failure degrades to a debug log,
never to a failed sweep, and verdicts are byte-identical with the
gate on or off.

Module-level imports are stdlib-only (gates/trace); jax is touched
only inside functions, after the dispatch layer has already loaded it.
"""

from __future__ import annotations

import logging
import threading
import time

from .. import gates, trace

log = logging.getLogger(__name__)

#: Layout of the dispatch cost key — MUST match
#: `parallel.residency.ExecutableResidency.dispatch_key` (pinned by
#: tests/test_costdb.py so the two can't drift): (classify, realtime,
#: process_order, fused, donate, n_keys, max_pos, n_txns).
_KEY_FIELDS = ("classify", "realtime", "process_order", "fused",
               "donate", "n_keys", "max_pos", "n_txns")

_LOCK = threading.Lock()

#: (key_parts, B) -> mutable record dict.
_records: dict[tuple, dict] = {}

#: id(device flags array) -> (record key, modeled bytes) for
#: dispatches in flight — the join between a dispatch's enqueue and
#: its materialized flags.
_pending: dict[int, tuple] = {}

_inflight_bytes = 0
_last_mem_poll = 0.0


def enabled() -> bool:
    """The JEPSEN_TPU_COSTDB gate (default off)."""
    return gates.get("JEPSEN_TPU_COSTDB")


def residency_interval_s() -> float:
    """The JEPSEN_TPU_RESIDENCY_INTERVAL_S gate: minimum seconds
    between `device.memory_stats()` polls (<=0 disables the poll)."""
    v = gates.get("JEPSEN_TPU_RESIDENCY_INTERVAL_S")
    return float(v) if v is not None else 0.0


def reset() -> None:
    """Drop every captured record and pending window (sweep start,
    tests) — the observatory is per-sweep state like the tracer."""
    global _inflight_bytes, _last_mem_poll
    with _LOCK:
        _records.clear()
        _pending.clear()
        _inflight_bytes = 0
        _last_mem_poll = 0.0


def dispatch_cost_key(kw: dict, shape, donate: bool) -> tuple:
    """THE cost key for one bucket dispatch: it IS
    `ExecutableResidency.dispatch_key`, so the AOT cache and the
    costdb key the same executable identically, mesh-sharded or
    not."""
    from ..parallel.residency import ExecutableResidency
    return ExecutableResidency.dispatch_key(kw, shape, donate)


def _cost_dict(obj) -> dict | None:
    """Normalized `cost_analysis()` of a Compiled/Lowered, or None
    (some deserialized executables have none); keys of interest are
    `flops`, `bytes accessed` and `transcendentals`."""
    try:
        ca = obj.cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None

    def num(k):
        v = ca.get(k)
        return float(v) if isinstance(v, (int, float)) else None

    return {"flops": num("flops"),
            "bytes_accessed": num("bytes accessed"),
            "transcendentals": num("transcendentals")}


def _memory_dict(obj) -> dict | None:
    """Normalized `memory_analysis()` (CompiledMemoryStats), or None —
    Lowered objects and some deserialized executables have none."""
    try:
        ma = obj.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def b(attr):
        v = getattr(ma, attr, None)
        return int(v) if isinstance(v, int) else None

    return {"argument_bytes": b("argument_size_in_bytes"),
            "output_bytes": b("output_size_in_bytes"),
            "temp_bytes": b("temp_size_in_bytes"),
            "alias_bytes": b("alias_size_in_bytes"),
            "generated_code_bytes": b("generated_code_size_in_bytes")}


def _backend_info() -> tuple[str, str]:
    try:
        import jax
        d = jax.devices()[0]
        return d.platform, str(d.device_kind)
    except Exception:
        return "unknown", "unknown"


def observe(key_parts: tuple, args, obj, source: str) -> None:
    """Capture one executable's analyses under (key_parts, batch) —
    once per key per process; repeats are a dict probe. `obj` is a
    Compiled executable (`source="compiled"`, the aot.compiled_for
    path — memory analysis included) or a jitted fn
    (`source="lowered"`: one `lower()` trace, no compile — the
    path with the AOT cache off, where forcing a second XLA compile
    just to read costs would defeat the point). Best-effort: never
    raises."""
    if not enabled():
        return
    try:
        from ..checker.elle import kernels as K
        B = int(args[0].shape[0])
        key = (tuple(key_parts), B)
        with _LOCK:
            if key in _records:
                return
        if source == "lowered" and not hasattr(obj, "cost_analysis"):
            try:
                obj = obj.lower(*args)
            except Exception:
                log.debug("costdb: lower() for cost capture failed",
                          exc_info=True)
                obj = None
        cost = _cost_dict(obj) if obj is not None else None
        memory = _memory_dict(obj) if obj is not None else None
        platform, device_kind = _backend_info()
        geometry = {
            "B": B,
            "n_txns": int(key_parts[7]),
            "n_keys": int(key_parts[5]),
            "max_pos": int(key_parts[6]),
            "n_appends": int(args[0].shape[1]),
            "n_reads": int(args[1].shape[1]),
        }
        arg_bytes = sum(int(a.nbytes) for a in args)
        rec = {
            "key_parts": tuple(key_parts),
            "kernel": {f: key_parts[i] for i, f in
                       enumerate(_KEY_FIELDS[:4])},
            "formulation": K.CLOSURE_FORMULATION,
            "donated": bool(key_parts[4]),
            "geometry": geometry,
            "backend": platform,
            "device_kind": device_kind,
            "analysis": source,
            "cost": cost,
            "memory": memory,
            "argument_bytes_actual": arg_bytes,
            "windows": {"dispatches": 0, "device_secs": 0.0,
                        "min_secs": None, "max_secs": None,
                        "histories": 0},
        }
        with _LOCK:
            fresh = key not in _records
            if fresh:
                _records[key] = rec
        if fresh:
            trace.counter("cost_records").inc()
    except Exception:
        log.debug("costdb capture failed", exc_info=True)


def _modeled_bytes(rec: dict, args) -> int:
    """The modeled HBM footprint of one in-flight dispatch: its real
    argument bytes plus the executable's own temp/output claim when
    the memory analysis reported one."""
    n = sum(int(a.nbytes) for a in args)
    mem = rec.get("memory") or {}
    for k in ("temp_bytes", "output_bytes"):
        v = mem.get(k)
        if isinstance(v, int):
            n += v
    return n


def begin_dispatch(flags, kw: dict, shape, donate: bool, args,
                   tr=None) -> None:
    """Open one dispatch's measured window: remember which record the
    flags array (the live device result) belongs to, add its modeled
    HBM to the in-flight gauge, and publish the residency gauges.
    No-op (one gates read) when the gate is off; never raises."""
    if not enabled():
        return
    try:
        global _inflight_bytes
        key = (dispatch_cost_key(kw, shape, donate),
               int(args[0].shape[0]))
        with _LOCK:
            rec = _records.get(key)
        nbytes = _modeled_bytes(rec or {}, args)
        with _LOCK:
            _pending[id(flags)] = (key, nbytes)
            _inflight_bytes += nbytes
        _publish_gauges(tr)
    except Exception:
        log.debug("costdb begin_dispatch failed", exc_info=True)


def close_dispatch(flags, t_disp, histories: int, tr=None) -> None:
    """Close one dispatch's window (enqueue time `t_disp` →
    now, the same semantics as the tracer's device track) and fold it
    into its record's aggregate. O(1) no-op for flags that were never
    begun (gate off, bare PendingVerdicts)."""
    global _inflight_bytes
    with _LOCK:
        ent = _pending.pop(id(flags), None)
        if ent is not None:
            _inflight_bytes = max(0, _inflight_bytes - ent[1])
    if ent is None or t_disp is None:
        return
    try:
        secs = max(0.0, time.perf_counter() - t_disp)
        key = ent[0]
        with _LOCK:
            rec = _records.get(key)
            if rec is not None:
                w = rec["windows"]
                w["dispatches"] += 1
                w["device_secs"] += secs
                w["min_secs"] = secs if w["min_secs"] is None \
                    else min(w["min_secs"], secs)
                w["max_secs"] = secs if w["max_secs"] is None \
                    else max(w["max_secs"], secs)
                w["histories"] += int(histories)
        _publish_gauges(tr)
    except Exception:
        log.debug("costdb close_dispatch failed", exc_info=True)


def discard_dispatch(flags, tr=None) -> None:
    """Drop a pending window without recording it — the dispatch's
    fate was quarantine/OOM recovery, whose device time the backdown's
    own windows account for."""
    global _inflight_bytes
    with _LOCK:
        ent = _pending.pop(id(flags), None)
        if ent is not None:
            _inflight_bytes = max(0, _inflight_bytes - ent[1])
    if ent is not None:
        _publish_gauges(tr)


def _publish_gauges(tr=None) -> None:
    """Residency gauges into the metrics registry (→ /metrics,
    health.json): delegated to parallel.residency so the residency
    layer owns its own publication surface."""
    try:
        from ..parallel import residency
        residency.publish_residency_gauges(
            tr if tr is not None else trace.get_current(),
            modeled_bytes=_inflight_bytes)
    except Exception:
        log.debug("residency gauge publish failed", exc_info=True)


def maybe_poll_memory_stats(tr) -> None:
    """The backend's own memory accounting (`device.memory_stats()` —
    TPU/GPU report `bytes_in_use`; CPU reports nothing) into the
    `hbm_device_bytes` gauge, at most once per
    JEPSEN_TPU_RESIDENCY_INTERVAL_S."""
    global _last_mem_poll
    interval = residency_interval_s()
    if interval <= 0:
        return
    now = time.monotonic()
    if now - _last_mem_poll < interval and _last_mem_poll > 0:
        return
    _last_mem_poll = now
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if isinstance(stats, dict) \
                and isinstance(stats.get("bytes_in_use"), int):
            tr.gauge("hbm_device_bytes").set(stats["bytes_in_use"])
    except Exception:
        log.debug("device memory_stats poll failed", exc_info=True)


# ---------------------------------------------------------------------------
# Records out: roofline derivation, flush, cross-shard merge.
# ---------------------------------------------------------------------------

def _finalize(rec: dict) -> dict:
    """One registry record → the published costdb line: achieved
    rates from the measured windows, roofline utilization against the
    device_kind-keyed peak table, and the honesty tag — `provenance:
    measured` only for windows taken on a real accelerator."""
    from ..checker.elle import kernels as K
    out = {k: v for k, v in rec.items() if k != "key_parts"}
    out = {"v": 1, **out}
    w = rec["windows"]
    on_device = rec.get("backend") not in ("cpu", "unknown")
    # a host CPU has no peak row: its records carry no roofline
    peak = K.device_peak(rec.get("device_kind")) if on_device else None
    out["peak"] = peak
    measured = w["dispatches"] > 0 and on_device
    out["provenance"] = "measured" if measured else "estimated"
    cost = rec.get("cost") or {}
    achieved = {"flops_per_sec": None, "bytes_per_sec": None}
    roofline = {"flops_utilization": None, "bandwidth_utilization": None}
    if w["dispatches"] > 0 and w["device_secs"] > 0:
        per_sec = w["dispatches"] / w["device_secs"]
        if isinstance(cost.get("flops"), (int, float)):
            achieved["flops_per_sec"] = cost["flops"] * per_sec
            if peak is not None:
                roofline["flops_utilization"] = round(
                    achieved["flops_per_sec"]
                    / (peak["int8_tops"] * 1e12), 6)
        if isinstance(cost.get("bytes_accessed"), (int, float)):
            achieved["bytes_per_sec"] = cost["bytes_accessed"] * per_sec
            if peak is not None:
                roofline["bandwidth_utilization"] = round(
                    achieved["bytes_per_sec"]
                    / (peak["hbm_gbps"] * 1e9), 6)
    out["achieved"] = achieved
    out["roofline"] = roofline
    return out


def records() -> list[dict]:
    """Every captured record, finalized (achieved rates, roofline,
    provenance), in capture order."""
    with _LOCK:
        raw = [dict(r, windows=dict(r["windows"])) for r in
               _records.values()]
    return [_finalize(r) for r in raw]


def record_key(rec: dict) -> tuple:
    """The dedup identity of a finalized record — what two shards
    compiling the same executable over the same geometry share."""
    g = rec.get("geometry") or {}
    k = rec.get("kernel") or {}
    return (tuple(sorted(k.items())), rec.get("formulation"),
            bool(rec.get("donated")),
            tuple(sorted((n, g.get(n)) for n in
                         ("B", "n_txns", "n_keys", "max_pos",
                          "n_appends", "n_reads"))),
            rec.get("analysis"))


def merge_records(record_lists) -> list[dict]:
    """Fold finalized records from several sources (mesh shards) into
    one deduplicated set: same key → one record with the window
    aggregates summed and the achieved/roofline numbers re-derived.
    A record whose twin carries a real memory analysis adopts it."""
    merged: dict[tuple, dict] = {}
    order: list[tuple] = []
    for recs in record_lists:
        for rec in recs or []:
            if not isinstance(rec, dict):
                continue
            k = record_key(rec)
            cur = merged.get(k)
            if cur is None:
                merged[k] = dict(rec,
                                 windows=dict(rec.get("windows") or {}))
                order.append(k)
                continue
            w, wn = cur.get("windows") or {}, rec.get("windows") or {}
            w["dispatches"] = w.get("dispatches", 0) \
                + wn.get("dispatches", 0)
            w["device_secs"] = w.get("device_secs", 0.0) \
                + wn.get("device_secs", 0.0)
            w["histories"] = w.get("histories", 0) \
                + wn.get("histories", 0)
            for f, pick in (("min_secs", min), ("max_secs", max)):
                vals = [v for v in (w.get(f), wn.get(f))
                        if v is not None]
                w[f] = pick(vals) if vals else None
            cur["windows"] = w
            if cur.get("memory") is None and rec.get("memory"):
                cur["memory"] = rec["memory"]
            if "measured" in (cur.get("provenance"),
                              rec.get("provenance")):
                cur["provenance"] = "measured"
    out = []
    for k in order:
        rec = merged[k]
        # re-derive the rates over the merged windows
        raw = {kk: vv for kk, vv in rec.items()
               if kk not in ("v", "peak", "provenance", "achieved",
                             "roofline")}
        fin = _finalize(raw)
        # a merged-measured set stays measured even if re-derivation
        # (cpu coordinator finalizing tpu shards) would demote it
        if rec.get("provenance") == "measured":
            fin["provenance"] = "measured"
        out.append(fin)
    return out


def flush(path, store_base=None) -> int:
    """Append every captured record to the costdb at `path` (one
    flushed JSON line each — store.append_costdb) and emit the
    flight-recorder mark. Returns the record count; 0 (and no file)
    when the gate is off or nothing was captured."""
    if not enabled():
        return 0
    recs = records()
    if not recs:
        return 0
    from ..store import append_costdb
    n = append_costdb(path, recs)
    if n:
        from . import events
        events.emit("costdb_flush", path=str(path), records=n)
    return n


def bandwidth_share(recs: list[dict]) -> dict | None:
    """The sweep-level achieved-bandwidth share: total bytes accessed
    over total measured device seconds, against the peak HBM bandwidth
    the records resolved — the sweep's one bandwidth number.
    None when no record carries both a cost analysis and windows."""
    bytes_total = 0.0
    secs_total = 0.0
    flops_total = 0.0
    peak_bw = None
    provenance = "estimated"
    for r in recs or []:
        w = r.get("windows") or {}
        cost = r.get("cost") or {}
        if not w.get("dispatches") or not isinstance(
                cost.get("bytes_accessed"), (int, float)):
            continue
        bytes_total += cost["bytes_accessed"] * w["dispatches"]
        if isinstance(cost.get("flops"), (int, float)):
            flops_total += cost["flops"] * w["dispatches"]
        secs_total += w.get("device_secs", 0.0)
        peak_bw = (r.get("peak") or {}).get("hbm_gbps", peak_bw)
        if r.get("provenance") == "measured":
            provenance = "measured"
    if secs_total <= 0 or peak_bw is None:
        return None
    return {
        "achieved_bw_share": round(
            bytes_total / secs_total / (peak_bw * 1e9), 6),
        "achieved_gbps": round(bytes_total / secs_total / 1e9, 3),
        "achieved_tflops": round(flops_total / secs_total / 1e12, 4),
        "device_secs": round(secs_total, 6),
        "peak_hbm_gbps": peak_bw,
        "provenance": provenance,
    }
