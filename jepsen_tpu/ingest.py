"""Sharded store -> tensor ingest (SURVEY.md §5.7).

The analysis phase is device-bound only if the host can feed it:
encoding one 10k-op list-append history costs ~50ms of dict parsing,
so a single-core loop would throttle a TPU slice checking hundreds of
histories per second. This module shards the ingest the way the batch
sweep shards the checking: run directories are encoded by a process
pool, each worker reading its own history file from disk (nothing but
compact arrays crosses the process boundary — no op-dict pickling),
and the parent batches the results straight onto the mesh.

The reference's analogues are the chunked parallel history writer
(jepsen/src/jepsen/util.clj:203-225) and bounded-pmap over independent
keys (independent.clj:472-492); here the unit is a whole stored run.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import time
from pathlib import Path
from typing import Sequence

log = logging.getLogger(__name__)


def load_history_dir(run_dir: str | os.PathLike) -> list[dict]:
    """History ops from a run dir (delegates to the store's loader —
    one format rule, shared with Store.load_history)."""
    from .store import load_history_dir as _load
    return _load(run_dir)


def native_ingest_enabled() -> bool:
    """One home for the JEPSEN_TPU_NATIVE_INGEST gate (default on) so
    the sweep and the bench's reporting can't drift apart."""
    from . import gates
    return gates.get("JEPSEN_TPU_NATIVE_INGEST")


def encode_run_dir(run_dir: str | os.PathLike, checker: str = "append",
                   lean: bool = True, info: dict | None = None):
    """Load + encode one run dir. With lean=True the per-row completion
    ops are dropped so only arrays cross process boundaries (witness
    rendering then reports txn row numbers instead of full ops — the
    batch sweep's flags don't carry witnesses anyway).

    `info`, when given, gets info["cache"] set to "hit"/"miss" (None
    when the encoded sidecar cache didn't apply) so pooled callers can
    aggregate cache counters in the PARENT tracer — pool workers'
    COUNTERS are process-local and never exported (their spans spool
    to the trace fabric, but counters relay only via this dict)."""
    from . import supervisor, trace
    # self-nemesis (JEPSEN_TPU_FAULT_INJECT): deterministic encode
    # faults / worker kills land here, ahead of the cache, so every
    # retry of a selected run dir fails identically in every process
    supervisor.maybe_inject_encode_fault(run_dir)
    cacheable = lean and checker in ("append", "wr")
    if info is not None:
        info["cache"] = None
    if cacheable:
        from . import store as _store
        if _store.encode_cache_enabled():
            with trace.span("cache_probe"):
                enc = _store.load_encoded(run_dir, checker)
            if enc is not None:
                trace.counter("cache_hits").inc()
                if info is not None:
                    info["cache"] = "hit"
                    if getattr(enc, "upgraded", False):
                        info["upgraded"] = True
                return enc
            trace.counter("cache_misses").inc()
            if info is not None:
                info["cache"] = "miss"
    if cacheable and native_ingest_enabled():
        # C++ fast path: history.jsonl -> tensors/edges with no Python
        # dicts (native/hist_encode.cc). None -> fall through to the
        # Python encoder; the native side only accepts inputs it can
        # encode byte-identically. Lean only: this path's witnesses are
        # the lean int shape, which the Python branches below
        # canonicalize to as well (encode.lean_anomalies /
        # wr.lean_wr_anomalies) so persisted artifacts don't depend on
        # which encoder ran. The native encoder also writes the
        # encoded.v1 sidecar straight from its own buffers (no Python
        # round-trip) when cache writes are on.
        jl = Path(run_dir) / "history.jsonl"
        if jl.is_file():
            from . import store as _store
            from .checker.elle import native_encode as ne
            sidecar = None
            if _store.encode_cache_enabled() \
                    and _store.encode_cache_write_enabled():
                sidecar = _store.encoded_cache_path(run_dir, checker)
            with trace.span("encode_native"):
                enc = (ne.encode_history_file(jl, sidecar_path=sidecar)
                       if checker == "append"
                       else ne.encode_wr_history_file(
                           jl, sidecar_path=sidecar))
            if enc is not None:
                return enc
    with trace.span("load_history"):
        hist = load_history_dir(run_dir)
    with trace.span("encode_py"):
        if checker == "append":
            from .checker.elle.encode import (encode_history,
                                              lean_anomalies)
            enc = encode_history(hist)
            if lean:
                enc.anomalies = lean_anomalies(enc)
        elif checker == "wr":
            from .checker.elle.wr import (encode_wr_history,
                                          lean_wr_anomalies)
            enc = encode_wr_history(hist)
            if lean:
                enc.anomalies = lean_wr_anomalies(enc)
        else:
            raise ValueError(f"unknown checker {checker!r}")
    if lean:
        enc.txn_ops = []
        if cacheable:
            from . import store as _store
            with trace.span("sidecar_write"):
                _store.save_encoded(run_dir, checker, enc)
    return enc


def _worker(args):
    run_dir, checker = args
    try:
        return encode_run_dir(run_dir, checker)
    except Exception as e:
        return e


def overlap_seconds(spans_a: list, spans_b: list) -> float:
    """Total seconds where some span in `a` intersects some span in
    `b` (both lists of (start, end) wall-clock pairs). Used to report
    honest pipeline overlap: worker parse spans x caller device
    spans. Delegates to the one shared interval implementation in
    `trace` (the attribution report walks the same arithmetic)."""
    from . import trace
    return trace.overlap_seconds(spans_a, spans_b)


def _stream_worker(args):
    """Pool worker for the streaming pipeline: encode one run dir and
    move the arrays through shared memory when a segment name was
    assigned (jepsen_tpu.shm), or fall back to pickling the encoding.
    Returns (idx, payload, encode-info, t0, t1); payload is a shm
    descriptor, the encoding itself, or the per-run Exception. The
    (t0, t1) parse span uses time.monotonic: CLOCK_MONOTONIC is
    system-wide on Linux, so spans compare across processes (the
    measured-overlap contract) and an NTP step can't corrupt them.

    With worker tracing on (`tctx` non-None — parent tracing enabled,
    JEPSEN_TPU_WORKER_TRACE on, a spool dir registered), the worker
    records its own spans into a process-local Tracer, spools them to
    `<store>/trace-<pid>.jsonl` per task (torn-tail-safe), and ships
    a compact digest back in einfo["tdigest"] — the parent folds the
    digest into its metrics and merge_traces folds the spool into
    the sweep's trace.json as this worker's own pid track."""
    idx, run_dir, checker, seg_name, tctx = args
    from . import trace
    trace.ensure_worker_tracer(tctx)
    t0 = time.monotonic()
    einfo: dict = {}
    try:
        with trace.span("encode",
                        run=os.path.basename(str(run_dir).rstrip("/"))):
            enc = encode_run_dir(run_dir, checker, info=einfo)
        from . import shm
        from . import store as _store
        if _store.sidecar_version(checker) == 2 \
                and _store.encode_cache_enabled() \
                and (einfo.get("cache") == "hit"
                     or _store.encode_cache_write_enabled()) \
                and _store.encoded_cache_path(run_dir, checker,
                                              2).is_file():
            # a dispatch-shaped sidecar answers for this run (warm
            # hit, or this encode just wrote it — with cache writes
            # DISABLED a merely-existing file may be stale, so only a
            # validated hit qualifies): send a tiny reference and let
            # the PARENT mmap it — copying the padded tensors through
            # a shm segment would re-introduce the host copy the v2
            # format exists to remove, and the parent's views must be
            # its own mapping for the pack stage to stay copy-free
            payload = shm.sidecar_ref(run_dir, checker)
        elif seg_name is not None:
            with trace.span("shm_export"):
                payload = shm.export(enc, seg_name, checker)
        else:
            payload = enc
    except Exception as e:
        payload = e
    t1 = time.monotonic()
    digest = trace.flush_worker_spool()
    if digest:
        einfo["tdigest"] = digest
    return idx, payload, einfo, t0, t1


def _load_worker(run_dir):
    try:
        return load_history_dir(run_dir)
    except Exception as e:
        return e


def split_register_run(hist: list, frontier: int | None):
    """One stored run's history split for the register sweep: None when
    the run goes to its own stored checker, else its keys in first-seen
    order as (key, op count, subhistory). With `frontier` (the sweep's
    checker takes the device tiers, with that frontier arena), a key
    that fits the dense tier carries its DenseEncoded in place of its
    subhistory; the other keys stay raw for the main thread's tiered
    path."""
    from . import independent
    hist = independent.relift_history(hist)
    client_fs = {o.get("f") for o in hist
                 if o.get("process") != "nemesis"
                 and o.get("f") is not None}
    if not (client_fs and client_fs <= {"read", "write", "cas"}):
        return None
    # one pass, all keys
    by_key = independent.subhistories(hist)
    ks = list(by_key)
    # a plain cas value is [old new] (scalars); a LIFTED cas value
    # is [key [old new]] — second element a list marks it lifted
    if not ks and any(
            isinstance(o.get("value"), (list, tuple))
            and len(o["value"]) == 2
            and (o.get("f") != "cas"
                 or isinstance(o["value"][1], (list, tuple)))
            for o in hist if o.get("process") != "nemesis"):
        # looks lifted ([k v] values) but relift declined (e.g. no
        # ok read survived the faults): checking it as ONE register
        # would feed the oracle [key value] pairs — let the run's
        # own stored checker handle it instead
        return None
    out = []
    for k in (ks or [None]):
        sub = by_key[k] if ks else hist
        payload = sub
        if frontier is not None:
            from .checker.knossos import encode as kenc
            try:
                tier, enc = kenc.route_register_history(sub, frontier)
            except Exception:
                # the main thread's tiered path meets the same error
                # and isolates the key, as it would have unsplit
                tier = None
            if tier == kenc.DENSE:
                payload = enc
        out.append((k, len(sub), payload))
    return out


def _register_worker(args):
    run_dir, frontier = args
    try:
        return split_register_run(load_history_dir(run_dir), frontier)
    except Exception as e:
        return e


def _spawn_safe() -> bool:
    """Can a spawn-context worker actually boot? spawn re-imports
    __main__ by its spec or, failing that, its `__file__`; when that
    file does not exist (stdin scripts) every worker dies during
    bootstrap and the pool respawns replacements forever — the parent
    then hangs in imap instead of falling back. Detect that case up
    front. A __main__ with neither (a REPL, `python -c`, pytest-xdist
    workers) is not re-imported at all: safe."""
    import sys
    m = sys.modules.get("__main__")
    f = getattr(m, "__file__", None)
    if f is None or getattr(m, "__spec__", None) is not None:
        return True
    return os.path.exists(f)


def _pool_map(worker, items: list, processes: int | None) -> list:
    """Shared process-pool recipe: spawned workers (the parent usually
    holds live device runtimes), per-item exceptions returned not
    raised, serial fallback on pool failure. The pool is a
    ProcessPoolExecutor rather than multiprocessing.Pool because a
    SIGKILLed worker (OOM killer, the kill nemesis) must surface as
    BrokenProcessPool — which routes to the serial fallback — instead
    of hanging the parent forever on a result that will never come."""
    if processes is None:
        processes = min(len(items), os.cpu_count() or 1)
    if processes <= 1 or len(items) <= 1 or not _spawn_safe():
        return [worker(it) for it in items]
    from concurrent.futures import ProcessPoolExecutor
    ctx = mp.get_context("spawn")
    try:
        with ProcessPoolExecutor(max_workers=processes,
                                 mp_context=ctx) as ex:
            return list(ex.map(worker, items,
                               chunksize=max(1, len(items)
                                             // (4 * processes))))
    except Exception:
        log.warning("process-pool map failed; falling back to serial",
                    exc_info=True)
        return [worker(it) for it in items]


def parallel_load(run_dirs: Sequence[str | os.PathLike],
                  processes: int | None = None) -> list:
    """Load many run-dir histories via a process pool (for sweeps that
    need raw ops rather than txn encodings — e.g. the per-key register
    sweep). Returns histories or per-run Exception objects, aligned
    with run_dirs."""
    return _pool_map(_load_worker, list(run_dirs), processes)


def parallel_split_registers(run_dirs: Sequence[str | os.PathLike],
                             frontier: int | None,
                             processes: int | None = None) -> list:
    """`split_register_run` over many run dirs via a process pool, each
    worker loading its own history: only the keys, and the dense
    encodings where `frontier` asks for them, cross back to the parent.
    Returns the per-run records, None, or per-run Exception objects,
    aligned with run_dirs."""
    return _pool_map(_register_worker,
                     [(d, frontier) for d in run_dirs], processes)


def parallel_encode(run_dirs: Sequence[str | os.PathLike],
                    checker: str = "append",
                    processes: int | None = None) -> list:
    """Encode many run dirs via a process pool. Returns a list aligned
    with run_dirs: EncodedHistory / WrEncoded on success, the raised
    Exception object on per-run failure (callers route those to their
    fallback checker).

    processes=0 forces the serial path."""
    return _pool_map(_worker, [(d, checker) for d in run_dirs],
                     processes)


def iter_encode_chunks(run_dirs: Sequence[str | os.PathLike],
                       checker: str = "append", chunk: int = 64,
                       processes: int | None = None,
                       info: dict | None = None):
    """Yield (run_dir, encoding) pairs in chunks, IN ORDER, while later
    run dirs keep encoding in background workers — so a caller that
    dispatches each chunk to the accelerator overlaps device compute
    with host parsing (the analyze-store sweep's ingest/check
    pipeline). Encodings are EncodedHistory/WrEncoded or the per-run
    Exception, exactly as parallel_encode.

    On a single-core host a pool is still worth one worker when a REAL
    accelerator runs the checks (the worker parses while the parent
    blocks on the device); without one, pooling 1 core is pure
    serialization overhead, so the serial path is used unless
    JEPSEN_TPU_PIPELINE=1 forces it.

    `info`, when given, gets info["pooled"] set to whether background
    workers actually ran, and info["parse_spans"] filled with each
    worker parse's (start, end) wall-clock pair — intersect those with
    the caller's own device-dispatch spans (`overlap_seconds`) for a
    measured, not inferred, pipeline-overlap number. Spans are
    appended when their items are YIELDED (not when the pool delivers
    them), so a mid-stream pool failure can never leave spans for
    items the caller never saw — the measured overlap only ever counts
    parses whose results reached the device loop. Callers reporting
    overlap must not claim pipelining for the strictly serial path.

    Transport: pool results ride shared memory (jepsen_tpu.shm) —
    workers send only (name, offset, shape, dtype) descriptors and the
    parent wraps zero-copy views over the same pages — unless
    JEPSEN_TPU_SHM_INGEST=0 or /dev/shm is unusable, in which case the
    arrays are pickled per item exactly as before. Either way results
    arrive via imap_unordered and a reorder buffer restores run-dir
    order per chunk, so one slow run dir delays only its own chunk
    instead of head-of-line-blocking every later worker's delivery
    (`reorder_depth` gauge = the deepest the buffer got)."""
    dirs = list(run_dirs)
    if info is not None:
        info["pooled"] = False
        info["parse_spans"] = []
    if not dirs:
        return
    if checker in ("append", "wr") and native_ingest_enabled():
        # Probe the native encoder in THIS process: pooled workers'
        # fallback counters live in worker-local tracers that are never
        # exported, so a missing .so would otherwise degrade the whole
        # sweep's ingest with no signal in the sweep's metrics.json.
        # _cached_lib counts + warns on a miss as a side effect.
        from . import native_lib
        native_lib.hist_lib()
    if processes is None:
        from . import gates
        ncpu = os.cpu_count() or 1
        force = gates.get("JEPSEN_TPU_PIPELINE")
        processes = min(len(dirs), ncpu) if ncpu > 1 or force else 0
    else:
        # never spawn more workers than there are run dirs to parse
        processes = min(int(processes), len(dirs))
    done = 0   # dirs fully yielded: a mid-stream pool failure resumes
    #            serially from here instead of double-yielding
    if processes and processes > 0 and len(dirs) > 1 and _spawn_safe():
        from . import shm, trace
        from . import store as _store
        use_shm = shm.enabled() and shm.available()
        names = [shm.gen_name() if use_shm else None for _ in dirs]
        consumed = [name is None for name in names]
        from concurrent.futures import ProcessPoolExecutor, as_completed
        ctx = mp.get_context("spawn")
        ex = None
        try:
            # ProcessPoolExecutor, not multiprocessing.Pool: a worker
            # that dies without delivering (SIGKILL from the kill
            # nemesis, the OOM killer) raises BrokenProcessPool here
            # instead of hanging imap on a result that will never
            # arrive — the except below then resumes SERIALLY from
            # `done`, so a crashed worker costs re-encodes, never the
            # sweep. as_completed registers ONE waiter per future
            # (repeated wait(FIRST_COMPLETED) over the outstanding set
            # would re-register every not-done future per wake-up —
            # O(N²) churn on a big store's feed loop).
            ex = ProcessPoolExecutor(max_workers=processes,
                                     mp_context=ctx)
            if info is not None:
                info["pooled"] = True
            tr = trace.get_current()
            # worker trace fabric: one context per sweep (trace id +
            # spool dir + monotonic send stamp); None when tracing or
            # worker tracing is off — the worker then skips the whole
            # fabric for free
            tctx = trace.worker_ctx()
            futs = [ex.submit(_stream_worker,
                              (i, d, checker, names[i], tctx))
                    for i, d in enumerate(dirs)]
            pending: dict = {}   # idx -> ((dir, enc), span)
            frontier = 0         # next idx to yield
            buf, span_buf = [], []
            for fut in as_completed(futs):
                idx, payload, einfo, t0, t1 = fut.result()
                if shm.is_sidecar_ref(payload):
                    # warm v2 hit: mmap the sidecar HERE, in the
                    # consuming process — zero bytes crossed the pipe
                    payload = shm.materialize_sidecar(payload)
                elif shm.is_descriptor(payload):
                    tr.counter("shm_bytes").inc(payload["nbytes"])
                    payload = shm.materialize(payload)
                consumed[idx] = True
                if einfo.get("cache") == "hit":
                    tr.counter("cache_hits").inc()
                elif einfo.get("cache") == "miss":
                    tr.counter("cache_misses").inc()
                td = einfo.get("tdigest")
                if td:
                    # the worker's span digest, relayed through the
                    # einfo path like the cache counters: span count
                    # plus per-stage seconds per task (full spans live
                    # in the worker's spool for merge_traces)
                    tr.counter("worker_spans").inc(
                        int(td.get("spans", 0)))
                    for k, secs in (td.get("stage_secs")
                                    or {}).items():
                        tr.histogram(f"worker.{k}").observe(secs)
                if einfo.get("upgraded"):
                    # the worker's v1->v2 upgrade telemetry relayed
                    # into THIS process (worker counters/events are
                    # process-local and never exported; only spans
                    # ride the spool)
                    tr.counter("sidecar_upgrades").inc()
                    from .obs import events as obs_events
                    obs_events.emit(
                        "cache_rebuild",
                        path=str(_store.encoded_cache_path(
                            dirs[idx], checker, 2)),
                        cause="v1->v2 upgrade")
                # the worker's parse window lands on its own trace
                # track (monotonic spans; the tracer converts), so
                # trace.json shows parse/device overlap directly
                tr.add_span("parse", t0, t1, track="ingest-pool",
                            clock="monotonic")
                pending[idx] = ((dirs[idx], payload), (t0, t1))
                if len(pending) > 1:
                    g = tr.gauge("reorder_depth")
                    g.set(max(getattr(g, "value", 0) or 0,
                              len(pending)))
                while frontier in pending:
                    item, span = pending.pop(frontier)
                    buf.append(item)
                    span_buf.append(span)
                    frontier += 1
                    if len(buf) >= chunk:
                        if info is not None:
                            info["parse_spans"].extend(span_buf)
                        yield buf
                        done += len(buf)
                        buf, span_buf = [], []
            if buf:
                if info is not None:
                    info["parse_spans"].extend(span_buf)
                yield buf
                done += len(buf)
            return
        except Exception:
            log.warning("pipelined encode pool failed; falling back "
                        "to serial", exc_info=True)
        finally:
            if ex is not None:
                # cancel queued work and give running tasks a bounded
                # grace to finish: workers should not still be creating
                # segments when the stale-sweep below runs, but a
                # WEDGED worker (a hang in a huge/corrupt parse — the
                # class the supervisor exists for) must not hold
                # teardown hostage the way shutdown(wait=True) would,
                # so stragglers are killed. Their segments fall to the
                # stale-sweep below, or to shm.reclaim_stale at the
                # next sweep's start, keyed on the dead pid.
                procs = list((getattr(ex, "_processes", None)
                              or {}).values())
                ex.shutdown(wait=False, cancel_futures=True)
                deadline = time.monotonic() + 5.0
                for p in procs:
                    p.join(max(0.0, deadline - time.monotonic()))
                for p in procs:
                    if p.is_alive():
                        log.warning("killing wedged encode worker "
                                    "pid=%s", p.pid)
                        p.kill()
            # Exception-path sweep: any segment a worker created but
            # the parent never mapped must not outlive the pool. The
            # happy path unlinks at materialize time, so this only
            # fires for crashed/abandoned items.
            for name, ok in zip(names, consumed):
                if not ok:
                    shm.unlink_stale(name)
    for i in range(done, len(dirs), chunk):
        yield [(d, _worker((d, checker)))
               for d in dirs[i:i + chunk]]
