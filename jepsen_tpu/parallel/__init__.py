"""The analysis data plane: mesh construction and sharded batch checking.

The reference's only distribution mechanism is SSH fan-out on the control
plane (SURVEY.md §5.8) — analysis is single-JVM. This module is the
north-star addition: history batches are sharded over a TPU device mesh
with named axes

  dp  data parallel over histories (the primary axis, SURVEY.md §2.5)
  mp  model parallel within one history: the [T,T] adjacency/closure
      matrices are column-sharded, so each closure matmul runs as a
      distributed dense matmul with XLA inserting the collectives over
      ICI (the sequence-parallel analogue for long histories)

The batched formulation here (explicit [B,T,T] einsum instead of vmap)
exists so sharding constraints can be placed on the matrices themselves.
"""

from __future__ import annotations

import functools
import logging
import math
import os
import queue as _queue
import threading
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import planner as _planner
from .. import supervisor as sv
from .. import trace
from ..obs import device as obs_device
from ..obs import events as obs_events
from ..checker.elle import kernels as K
from ..devices import default_devices
from . import residency
from ..util import pad_to_multiple

log = logging.getLogger(__name__)


def factor2(n: int) -> tuple[int, int]:
    """Split n into (a, b), a*b == n, as square as possible, a >= b."""
    b = int(math.isqrt(n))
    while n % b:
        b -= 1
    return n // b, b


def make_mesh(devices: Sequence | None = None,
              axes: tuple[str, str] = ("dp", "mp")) -> Mesh:
    """A 2-D device mesh: data parallel over histories × model parallel
    within a history's closure matmuls."""
    devices = list(devices if devices is not None else default_devices())
    dp, mp = factor2(len(devices))
    return Mesh(np.asarray(devices).reshape(dp, mp), axes)


def host_local_mesh() -> Mesh:
    """A dp×mp mesh over THIS process's local devices only — the
    per-shard dispatch mesh of `analyze-store --mesh`. On a
    distributed job `make_mesh()`'s default devices span every host,
    but a mesh-sweep shard checks ITS OWN run dirs on ITS OWN chips:
    the cross-host axis is the deterministic shard split of the store,
    never a global dispatch (host-local batches aren't addressable on
    a cross-process mesh without collective array assembly, and the
    shard split already extracts the parallelism)."""
    import jax
    return make_mesh(jax.local_devices())


def init_distributed() -> bool:
    """Join a multi-host analysis job (SURVEY.md §5.8's DCN plane):
    when JAX_COORDINATOR_ADDRESS (or COORDINATOR_ADDRESS) is set —
    optionally with JAX_NUM_PROCESSES/JAX_PROCESS_ID — initialize
    jax.distributed so `jax.devices()` spans every host's chips and
    the dp×mp meshes built here shard across ICI within a slice and
    DCN between them. Called by analyze-store and the bench before any
    device work. Returns True when distributed mode came up; a
    single-process run (no coordinator env) returns False and
    everything behaves as before. Idempotent."""
    import os

    if not (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")):
        return False
    if jax.distributed.is_initialized():
        return True
    kw = {}
    addr = (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS"))
    if addr:
        kw["coordinator_address"] = addr
    if os.environ.get("JAX_NUM_PROCESSES"):
        kw["num_processes"] = int(os.environ["JAX_NUM_PROCESSES"])
    if os.environ.get("JAX_PROCESS_ID"):
        kw["process_id"] = int(os.environ["JAX_PROCESS_ID"])
    jax.distributed.initialize(**kw)
    return True


def sharded_check_fn(mesh: Mesh | None, shape: K.BatchShape, *,
                     classify: bool = True, realtime: bool = False,
                     process_order: bool = False,
                     fused: bool | None = None,
                     donate: bool = False,
                     with_stats: bool = False):
    """Build a jitted batched checker around kernels.check_batched_impl.
    With a mesh, inputs are expected sharded over 'dp' and the closure
    matrices are constrained to P('dp', None, 'mp'); without one, it's
    a plain single-device jit. A mesh's compiled executable carries
    the collectives XLA inserted, so bucket dispatches resolve it
    through the AOT executable map like single-device ones
    (residency.ExecutableResidency). Memoized per (mesh, shape,
    flags): a repeat call returns the same jitted wrapper, and an
    evicted one costs a rebuild, never a re-trace, on the bucket
    path."""
    if fused is None:
        fused = K.fused_classify_enabled()
    # fused only exists in classify mode; normalize so detect-mode
    # dispatches never compile twice over an irrelevant flag
    fused = bool(fused) and classify
    # donation is a dispatch-layer contract (the caller must treat its
    # input arrays as consumed) — normalize it away under a mesh so
    # the flag can't split the compile cache for sharded dispatches
    donate = bool(donate) and mesh is None
    return _sharded_check_fn_cached(mesh, shape, classify, realtime,
                                    process_order, fused, donate,
                                    bool(with_stats))


# Executable residency + donated-slot ownership live in
# parallel.residency (the split ROADMAP items 1 and 2 share: the mesh
# sweep's per-shard dispatch loops and the future serve daemon both
# hold executables and donated buffers resident without re-owning this
# bookkeeping). The dispatcher below is pure scheduling; these two
# objects are its residency/ownership seams.
_residency = residency.ExecutableResidency()
_slots = residency.DeviceSlots()


@functools.lru_cache(maxsize=64)
def _sharded_check_fn_cached(mesh: Mesh | None, shape: K.BatchShape,
                             classify: bool, realtime: bool,
                             process_order: bool,
                             fused: bool = False,
                             donate: bool = False,
                             with_stats: bool = False):
    if mesh is not None:
        spec = P("dp", None, "mp")

        def constrain(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
    else:
        def constrain(x):
            return x

    f = functools.partial(
        K.check_batched_impl, n_keys=shape.n_keys, max_pos=shape.max_pos,
        n_txns=shape.n_txns, steps=K.closure_steps(shape.n_txns),
        classify=classify, realtime=realtime, process_order=process_order,
        constrain=constrain, fused=fused, with_stats=with_stats)
    # JAX names the compiled module after the function it traces, and a
    # bare partial reads as `<unknown>` (`jit__unknown` in a device
    # trace, cached and reloaded under that name). The executable's
    # name is the kernel's; the AOT cache key is not changed by it.
    f.__name__ = K.check_batched_impl.__name__
    if mesh is None:
        if donate:
            # donated inputs: XLA reuses the six packed tensors' HBM
            # for the closure scratch instead of allocating fresh —
            # the caller's arrays are CONSUMED by the call.
            return jax.jit(f, donate_argnums=tuple(range(6)))
        return jax.jit(f)
    in_shard = NamedSharding(mesh, P("dp"))
    out_shard = NamedSharding(mesh, P("dp"))
    return jax.jit(f, in_shardings=(in_shard,) * 6, out_shardings=out_shard)


def shard_batch(mesh: Mesh | None, packed: dict) -> tuple:
    """Device-put packed batch arrays, sharded over dp when a mesh is
    given. Returns the 6 positional args for the check fn.

    A views-packed dict (kernels.pack_batch_views — the v2-sidecar
    warm path) carries per-history mmap views instead of stacked
    arrays: each view is device_put straight from the mapped pages,
    ragged views (a history padded to a smaller geometry than the
    bucket max) are padded ON DEVICE with the pack fill convention,
    and the batch axis is assembled in HBM (jnp.stack over device
    arrays) — the host copies zero bytes between the sidecar and the
    device. `h2d_bytes` counts what crossed to the device either
    way."""
    tr = trace.get_current()
    names = ("appends", "reads", "invoke_index", "complete_index",
             "process", "n_txns")
    if packed.get("views"):
        shape: K.BatchShape = packed["shape"]
        targets = {"appends": (shape.n_appends, 3),
                   "reads": (shape.n_reads, 3),
                   "invoke_index": (shape.n_txns,),
                   "complete_index": (shape.n_txns,),
                   "process": (shape.n_txns,)}
        fills = {"appends": -1, "reads": -1, "process": -1,
                 "invoke_index": 0, "complete_index": 0}
        args = []
        nbytes = 0
        for k in names[:-1]:
            tgt, fill = targets[k], fills[k]
            parts = []
            for v in packed[k]:
                nbytes += v.nbytes
                dv = jax.device_put(v)
                if v.shape != tgt:
                    dv = jnp.pad(dv,
                                 [(0, t - s)
                                  for s, t in zip(v.shape, tgt)],
                                 constant_values=fill)
                parts.append(dv)
            args.append(jnp.stack(parts))
        args.append(jnp.asarray(packed["n_txns"]))
        if tr.enabled:
            tr.counter("h2d_bytes").inc(nbytes)
        return tuple(args)
    args = [jnp.asarray(packed[k]) for k in names]
    if tr.enabled:
        tr.counter("h2d_bytes").inc(
            sum(packed[k].nbytes for k in names))
    if mesh is not None:
        s = NamedSharding(mesh, P("dp"))
        args = [jax.device_put(a, s) for a in args]
    return tuple(args)


# ---------------------------------------------------------------------------
# Long single histories: sequence-parallel checking (SURVEY.md §5.7).
# ---------------------------------------------------------------------------

def sp_mesh(devices: Sequence | None = None) -> Mesh:
    """A 1×N mesh dedicating the WHOLE slice to one history: dp is
    trivial, and the [T,T] adjacency/closure matrices are column-sharded
    over every device, so each closure matmul is a distributed dense
    matmul with XLA moving the halo over ICI — the context-parallel
    analogue for op-axis sharding."""
    devices = list(devices if devices is not None else default_devices())
    return Mesh(np.asarray(devices).reshape(1, len(devices)), ("dp", "mp"))


# Above this txn count the dense [T,T] closure no longer fits a slice's
# HBM; check_long_history switches to SCC condensation (elle.condense).
DENSE_TXN_LIMIT = 32_768


def check_long_history(enc, mesh: Mesh | None = None, *,
                       classify: bool = True, realtime: bool = False,
                       process_order: bool = False,
                       dense_limit: int = DENSE_TXN_LIMIT,
                       stats_out: list | None = None) -> dict:
    """Check ONE long encoded history; returns {anomaly: True} flags.

    Up to `dense_limit` txns: the dense closure with the op axis
    column-sharded across the mesh (the CP analogue). Beyond it: host
    SCC condensation (vectorized edge build + native Tarjan) feeding
    the device classification kernel per nontrivial SCC — the 100k-op
    path (BASELINE config #5), exact by SCC-locality of every anomaly
    query (elle/condense.py module doc).

    `stats_out` (a list) gains one stats dict for the history —
    device-computed on the dense path, host-derived (edge/SCC facts
    from the condensation's own Tarjan, no closure telemetry) past
    the dense limit."""
    if enc.n > dense_limit:
        from ..checker.elle import condense
        return condense.check_condensed(
            enc, classify=classify, realtime=realtime,
            process_order=process_order,
            devices=(list(mesh.devices.flat) if mesh is not None
                     else None), stats_out=stats_out)
    mesh = mesh if mesh is not None else sp_mesh()
    shape = K.BatchShape.plan([enc])
    packed = K.pack_batch([enc], shape)
    with_stats = stats_out is not None
    fn = sharded_check_fn(mesh, shape, classify=classify,
                          realtime=realtime, process_order=process_order,
                          with_stats=with_stats)
    args = shard_batch(mesh, packed)
    out = fn(*args)
    pending, dev_stats = out if with_stats else (out, None)
    # window opens AFTER the enqueue returns (first-call compile is
    # host time, not device time — same contract as the bucket path)
    t_disp = time.perf_counter()
    flags = np.asarray(_block_flags(pending, trace.get_current()))
    trace.get_current().device_complete("long-history", t_disp,
                                        txns=enc.n)
    if with_stats:
        stats_out.append(K.stats_row(np.asarray(dev_stats)[0],
                                     n_txns=enc.n,
                                     t_pad=shape.n_txns))
    return K.flags_to_names(int(flags[0]))


# ---------------------------------------------------------------------------
# Device-memory-aware batch scheduling (SURVEY.md §2.5): histories are
# bucketed by padded length so each dispatch's B·T² closure footprint
# stays under a budget, instead of padding everything to the longest.
# ---------------------------------------------------------------------------

def _size_of(e) -> int:
    """Txn count of an encoded history (attribute) or packed edge dict
    (key) — both bucket the same way."""
    return e["n"] if isinstance(e, dict) else e.n


def bucket_by_length(encs: Sequence, *, multiple: int = 128,
                     budget_cells: int = 1 << 27,
                     dp: int = 1) -> list[list[int]]:
    """Partition history indices into buckets of similar padded txn
    count. Each bucket satisfies B_pad * T_pad² <= budget_cells, where
    T_pad is the bucket max rounded up to `multiple` and B_pad is the
    bucket size rounded up to a multiple of `dp` (dispatchers pad
    ragged buckets to a dp multiple, so that headroom must be budgeted
    here, not discovered at dispatch). Returns buckets of indices into
    encs, longest histories first. Elements may be EncodedHistory-like
    (`.n`) or packed edge dicts (`["n"]`)."""
    order = sorted(range(len(encs)), key=lambda i: -_size_of(encs[i]))
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_tpad = 0
    for i in order:
        tpad = max(K.pad_to(max(_size_of(encs[i]), 1), multiple), 1)
        t = max(cur_tpad, tpad)
        b_pad = -(-(len(cur) + 1) // dp) * dp
        if cur and b_pad * t * t > budget_cells:
            buckets.append(cur)
            cur, cur_tpad = [], 0
            t = tpad
        cur.append(i)
        cur_tpad = t
    if cur:
        buckets.append(cur)
    return buckets


def _acc_phase(phases: dict | None, key: str, t0: float) -> None:
    """Accumulate a wall-clock span into a caller-supplied phase dict —
    the sweep-attribution hook (every host second of a bucketed sweep
    lands in exactly one named phase). Now a thin adapter over
    jepsen_tpu.trace spans: the duration is recorded ONCE (a completed
    phase span in the current tracer, feeding trace.json and
    `phase_totals`) and the same number lands in the legacy `phases`
    dict, so bench parity is exact by construction."""
    dt = trace.get_current().phase(key, t0)
    if phases is not None:
        phases[key] = phases.get(key, 0.0) + dt


def _phase(phases: dict | None, key: str, **args):
    """`with _phase(phases, "pack"):` — `_acc_phase` as a context
    manager, which also opens the phase's profiler annotation."""
    return trace.get_current().phase_span(key, phases, **args)


class PendingVerdicts:
    """Verdicts still in flight: `check_bucketed_async` queues every
    bucket's device dispatch without a host sync, so the caller can
    overlap ingest/packing of the NEXT chunk with the device's work on
    this one. `.result()` blocks, pulls the flag words D2H and returns
    per-history {anomaly: True} dicts in input order (a history the
    supervisor abandoned yields its `supervisor.Quarantined` sentinel
    instead of a flags dict — callers render it as `valid? unknown`)."""

    def __init__(self, n: int, parts: list, finish=None):
        self._n = n
        # [(bucket indices, flags, dispatch-enqueue time|None,
        #   donated, smeta)] — flags is a live device array, or
        # (already resolved) a list of per-history flag words /
        # (word, stats-dict) pairs / Quarantined aligned with indices;
        # `donated` marks a dispatch holding a device-slot ledger
        # entry the finish closure must release; `smeta` is None or
        # (device stats matrix, BatchShape) for a kernel-stats
        # dispatch (JEPSEN_TPU_KERNEL_STATS)
        self._parts = parts
        # finish(idx, device_flags) -> resolved list: the dispatcher's
        # watchdog + OOM-backdown closure; None (bare construction)
        # blocks plainly with no recovery.
        self._finish = finish
        self._result: list | None = None
        self._stats: list = [None] * n

    def is_ready(self) -> bool:
        """True when every bucket's flags have materialized (no block):
        lets callers close an honest device-in-flight window — a chunk
        whose flags are already ready before the next host stall must
        not count that stall as pipeline overlap."""
        return all(getattr(f, "is_ready", lambda: True)()
                   for _, f, _, _, _ in self._parts)

    def stats(self) -> list:
        """Per-history `kernels.stats_row` dicts aligned with the
        verdict list (None for histories whose dispatch carried no
        stats: gate off, quarantined, or resolved through the OOM/
        watchdog backdown whose retries run stats-free). Only
        populated after `.result()`."""
        return self._stats

    def result(self, phases: dict | None = None) -> list[dict]:
        # Idempotent: callers can observe readiness and collect from
        # more than one code path (the bench's is_ready fast path plus
        # its end-of-loop drain); a second call returns the SAME
        # verdict list and accumulates NO extra "collect" time,
        # instead of returning all-Nones and double-counting.
        if self._result is not None:
            return self._result
        with _phase(phases, "collect"):
            out = self._resolve()
        self._result = out
        return out  # type: ignore[return-value]

    def _resolve(self) -> list:
        tr = trace.get_current()
        out: list[dict | None] = [None] * self._n
        for idx, flags, t_disp, donated, smeta in self._parts:
            if not isinstance(flags, list):
                if self._finish is not None:
                    # the finish closure owns the device window (logged
                    # on its success path only — a recovered bucket's
                    # device time is the backdown's own windows)
                    flags = self._finish(idx, flags, t_disp, donated,
                                         smeta)
                else:
                    arr = np.asarray(jax.block_until_ready(flags))
                    # padded replicas (flags beyond the bucket's own
                    # indices) are dropped here
                    flags = [int(w) for w in arr[:len(idx)]]
                    # dispatch->materialized delta on the device track
                    # (parts already resolved by the back-pressure
                    # loop carry None)
                    tr.device_complete("bucket", t_disp,
                                       histories=len(idx))
            for i, w in zip(idx, flags):
                if isinstance(w, tuple):
                    w, self._stats[i] = w
                out[i] = (w if isinstance(w, sv.Quarantined)
                          else K.flags_to_names(int(w)))
        self._parts = []
        tr.gauge("inflight_depth").set(0)   # fully drained
        return out


def pack_thread_enabled() -> bool:
    """One home for the JEPSEN_TPU_PACK_THREAD gate (default on):
    check_bucketed_async moves bucket packing + device_put onto a
    dedicated worker thread so the parent's critical path is only the
    async kernel enqueue and h2d overlaps device compute. 0 keeps
    everything inline on the calling thread."""
    from .. import gates
    return gates.get("JEPSEN_TPU_PACK_THREAD")


def _est_cells(encs: Sequence, bucket: list[int], dp: int) -> int:
    """The padded footprint bucket_by_length budgeted for this bucket
    (B rounded to a dp multiple x T_pad²) — computable before packing,
    so the dispatcher can spot buckets that exceed the per-slot budget
    (only possible for a single history too big to subdivide)."""
    tpad = max(K.pad_to(max(_size_of(encs[i]) for i in bucket), 128), 1)
    return -(-len(bucket) // dp) * dp * tpad * tpad


def _prep_bucket(encs: Sequence, bucket: list[int], mesh: Mesh | None,
                 dp: int, budget_cells: int, tr,
                 phases: dict | None) -> tuple:
    """Host-side packing of one bucket (pack phase): group selection,
    dp-replica padding, BatchShape planning and tensor packing. Runs on
    the packer thread when pack_thread_enabled(), inline otherwise —
    the tracer span lands on whichever thread did the work (its own
    track in trace.json).

    Single-device buckets try the copy-free views path first
    (kernels.pack_batch_views): when every history carries
    dispatch-shaped v2-sidecar views matching the planned shape, no
    host tensor is built at all. Otherwise pack_batch copies as
    before, and the bytes it copied for WARM (cache-loaded) histories
    are attributed to `warm_copy_bytes` — the number the warm
    north-star bench drives to zero."""
    with _phase(phases, "pack"):
        group = [encs[i] for i in bucket]
        bucket_mesh = mesh
        if mesh is not None:
            # Pad ragged buckets to a dp multiple by replicating the
            # last history (results dropped at collect) so the dispatch
            # still shards across the mesh instead of falling to one
            # device — unless the padding itself would blow the budget
            # (a single history bigger than budget/dp), in which case
            # dispatch unsharded rather than 8x over budget.
            tpad = max(K.pad_to(max(e.n for e in group), 128), 1)
            padded = pad_to_multiple(group, dp)
            if len(padded) * tpad * tpad <= budget_cells:
                group = padded
            else:
                bucket_mesh = None
        shape = K.BatchShape.plan(group)
        packed = K.pack_batch_views(group, shape) \
            if bucket_mesh is None else None
        if packed is None:
            packed = K.pack_batch(group, shape)
            if tr.enabled:
                warm = sum(
                    e.appends.nbytes + e.reads.nbytes
                    + e.invoke_index.nbytes + e.complete_index.nbytes
                    + e.process.nbytes
                    for e in group if getattr(e, "warm", False))
                if warm:
                    tr.counter("warm_copy_bytes").inc(warm)
        if tr.enabled:
            # padding waste this dispatch pays: B_pad·T_pad² minus the
            # ORIGINAL bucket's own cells, so dp-replica padding (group
            # may hold replicated histories) counts as waste too
            cells = len(group) * shape.n_txns * shape.n_txns
            tr.counter("pad_waste_cells").inc(
                cells - sum(max(_size_of(encs[i]), 1) ** 2 for i in bucket))
            # per-dispatch device-resident footprint, in closure cells —
            # the HBM-envelope invariant (max over dispatches x
            # max_inflight <= budget_cells) is asserted against this
            tr.histogram("bucket_cells").observe(cells)
    return bucket, bucket_mesh, shape, packed


def _h2d_bucket(item: tuple, phases: dict | None) -> tuple:
    """device_put / sharding of one packed bucket (h2d phase)."""
    bucket, bucket_mesh, shape, packed = item
    with _phase(phases, "h2d"):
        args = shard_batch(bucket_mesh, packed)
    return bucket, bucket_mesh, shape, args


# ---------------------------------------------------------------------------
# Supervised dispatch: watchdog, OOM backdown, quarantine (ISSUE 4).
# The policy (gates, fault injection, the Quarantined sentinel) lives
# in jepsen_tpu.supervisor; this is the mechanism around jax calls.
# ---------------------------------------------------------------------------

def _block_flags(flags, tr):
    """`jax.block_until_ready` bounded by the dispatch watchdog
    (JEPSEN_TPU_DISPATCH_TIMEOUT_S; default off = plain block). On a
    timeout the wait is retried once — a transient host stall under a
    healthy device resolves here — then WatchdogTimeout raises and the
    caller quarantines the bucket. The device op itself cannot be
    cancelled; its waiter thread is abandoned daemonically
    (util.timeout_call), so a wedged runtime can't also wedge exit."""
    timeout = sv.dispatch_timeout_s()
    if timeout is None:
        return jax.block_until_ready(flags)
    from ..util import timeout_call
    _pending = object()
    for _attempt in range(2):
        got = timeout_call(timeout,
                           lambda: jax.block_until_ready(flags),
                           default=_pending)
        if got is not _pending:
            return got
        if _attempt == 0:
            # one wedged dispatch = one timeout, however many attempts
            # it burns — operators correlate this against `quarantined`
            tr.counter("watchdog_timeouts").inc()
        tr.instant("watchdog_timeout", track="device",
                   timeout_s=timeout, attempt=_attempt)
        obs_events.emit("watchdog_fire", timeout_s=timeout,
                        attempt=_attempt)
    raise sv.WatchdogTimeout(
        f"device dispatch exceeded {timeout}s twice")


def _quarantine_bucket(idx: list, stage: str, err, tr) -> list:
    """Per-history Quarantined sentinels for a bucket the supervisor
    abandoned, attributed as a quarantine span + counter."""
    with tr.span("quarantine", stage=stage, histories=len(idx)):
        tr.counter("quarantined").inc(len(idx))
        log.warning("quarantined %d histories (%s): %r",
                    len(idx), stage, err)
    e = repr(err)
    obs_events.emit("quarantine", stage=stage, histories=len(idx),
                    cause=e[:300])
    return [sv.Quarantined(stage, e) for _ in idx]


def _dispatch_fn(bucket_mesh, shape: K.BatchShape, kw: dict, args,
                 donate: bool):
    """The callable for one bucket dispatch, mesh-sharded or not: with
    the AOT cache on, a persistent compiled executable
    (residency.ExecutableResidency over jepsen_tpu.aot) keyed by the
    input avals and shardings + kernel flags + geometry, so a
    repeat dispatch never re-traces and a repeat sweep pays zero XLA
    compiles; else the jitted check fn."""
    fn = sharded_check_fn(bucket_mesh, shape, donate=donate, **kw)
    return _residency.dispatch_fn(fn, shape, kw, args, donate)


def _donate_active(bucket_mesh) -> bool:
    return _slots.donate_active(bucket_mesh)


def _note_donation(tr, args=None) -> None:
    _slots.note_donation(tr, args)


def _sync_check(encs, idx: list, mesh, budget_cells: int, kw: dict,
                tr, phases) -> np.ndarray:
    """One synchronous bucket check — the OOM-backdown retry path:
    pack, transfer, dispatch, block. Raises on OOM/watchdog; the
    caller owns the split/quarantine policy. Donation here is
    self-contained: the slot acquired for this retry releases in the
    finally, whatever the outcome — backdown recursion holds only its
    own halves' slots, never an ancestor's. Retries run stats-free
    (kernel-stats is observability; a re-planned bucket keeps its
    verdicts and drops its telemetry rather than re-keying the
    recovery executable)."""
    if kw.get("with_stats"):
        kw = {**kw, "with_stats": False}
    dp = mesh.devices.shape[0] if mesh is not None else 1
    bucket, bucket_mesh, shape, args = _h2d_bucket(
        _prep_bucket(encs, idx, mesh, dp, budget_cells, tr, phases),
        phases)
    donate = _donate_active(bucket_mesh)
    fn = _dispatch_fn(bucket_mesh, shape, kw, args, donate)
    sv.maybe_inject_oom()
    if donate:
        _note_donation(tr, args)
    try:
        t_disp = time.perf_counter()
        flags = fn(*args)
        obs_device.begin_dispatch(flags, kw, shape, donate, args, tr)
        try:
            arr = np.asarray(_block_flags(flags, tr))
        except BaseException:
            obs_device.discard_dispatch(flags, tr)
            raise
    finally:
        if donate:
            _slots.release()
    tr.device_complete("bucket", t_disp, histories=len(idx))
    obs_device.close_dispatch(flags, t_disp, len(idx), tr)
    return arr


def _oom_backdown(encs, idx: list, mesh, budget_cells: int, kw: dict,
                  tr, phases, err) -> list:
    """Recover from a RESOURCE_EXHAUSTED bucket: split it in half and
    retry each half synchronously at a HALVED per-slot cell budget
    (the padded footprint shrinks on both axes), recursing to
    singletons. A singleton that still OOMs is oversized for the
    device outright — it quarantines instead of crashing the sweep.
    In strict mode the original error re-raises untouched.

    Retries run WITHOUT draining the pipeline's other in-flight
    buckets first (draining from inside the threaded dispatcher would
    have to juggle its envelope semaphore — a deadlock risk not worth
    the memory it frees), so the halved budget is also what compensates
    for their residual pressure: each halving shrinks this retry's
    footprint until it fits the envelope slack or quarantines."""
    if sv.strict_enabled():
        raise err
    tr.counter("oom_retries").inc()
    if len(idx) == 1:
        return _quarantine_bucket(idx, "oom", err, tr)
    tr.counter("bucket_splits").inc()
    obs_events.emit("oom_split", histories=len(idx),
                    budget_cells=budget_cells)
    mid = (len(idx) + 1) // 2
    half_budget = max(1, budget_cells // 2)
    out: list = []
    for half in (idx[:mid], idx[mid:]):
        try:
            arr = _sync_check(encs, half, mesh, half_budget, kw, tr,
                              phases)
            out.extend(int(w) for w in arr[:len(half)])
        except BaseException as e:
            if isinstance(e, sv.WatchdogTimeout) \
                    and not sv.strict_enabled():
                out.extend(_quarantine_bucket(half, "watchdog", e, tr))
            elif sv.is_oom_error(e) and not sv.strict_enabled():
                out.extend(_oom_backdown(encs, half, mesh, half_budget,
                                         kw, tr, phases, e))
            else:
                raise
    return out


def _finish_part(encs, idx: list, flags, mesh, budget_cells: int,
                 kw: dict, tr, phases, t_disp=None,
                 donated: bool = False, smeta=None) -> list:
    """Resolve one dispatched bucket to per-history flag words (padded
    replicas dropped), recovering from OOM (backdown) and watchdog
    timeouts (quarantine) unless strict. The dispatch->materialized
    device window closes HERE, on the success path only — a recovered
    bucket's device time is the backdown's own per-half windows
    (_sync_check), never the original window stretched over the whole
    recovery (which would double-count the device track). A donated
    dispatch's ledger slot releases the moment its fate is decided —
    in particular BEFORE an OOM backdown re-plans, so a split bucket
    drops its original slot and the halves acquire their own.

    `smeta` ((device stats, BatchShape) — a kernel-stats dispatch)
    resolves to (word, stats-dict) pairs instead of bare words; the
    recovery paths resolve stats-free (a quarantined or re-planned
    history yields verdict evidence only)."""
    try:
        arr = np.asarray(_block_flags(flags, tr))
        if donated:
            _slots.release()
        tr.device_complete("bucket", t_disp, histories=len(idx))
        obs_device.close_dispatch(flags, t_disp, len(idx), tr)
        words = [int(w) for w in arr[:len(idx)]]
        if smeta is not None:
            rows = np.asarray(smeta[0])
            t_pad = smeta[1].n_txns
            return [(w, K.stats_row(rows[j], n_txns=_size_of(encs[i]),
                                    t_pad=t_pad))
                    for j, (i, w) in enumerate(zip(idx, words))]
        return words
    except BaseException as e:
        # the abandoned dispatch's cost window is discarded, never
        # recorded: a recovered bucket's device time is the backdown's
        # own windows, same as the device track
        obs_device.discard_dispatch(flags, tr)
        if donated:
            _slots.release()
        if isinstance(e, sv.WatchdogTimeout) and not sv.strict_enabled():
            return _quarantine_bucket(idx, "watchdog", e, tr)
        if sv.is_oom_error(e) and not sv.strict_enabled():
            return _oom_backdown(encs, idx, mesh, budget_cells, kw, tr,
                                 phases, e)
        raise


def check_bucketed_async(encs: Sequence, mesh: Mesh | None = None, *,
                         classify: bool = True, realtime: bool = False,
                         process_order: bool = False,
                         budget_cells: int = 1 << 27,
                         fused: bool | None = None,
                         max_inflight: int = 2,
                         phases: dict | None = None,
                         with_stats: bool = False) -> PendingVerdicts:
    """Dispatch a bucketed sweep WITHOUT blocking on the device: every
    bucket is packed, transferred and queued (JAX dispatch is async),
    and the returned PendingVerdicts resolves the flags later. This is
    the double-buffered pipeline's core — the caller dispatches chunk N,
    then collects chunk N-1 while N computes.

    `max_inflight` bounds how many buckets' packed tensors are resident
    at once: once more than that many dispatches are outstanding, the
    oldest is resolved to host flags before the next bucket transfers —
    host packing far outruns the O(T^3) closure, so an unbounded queue
    would accumulate every bucket's input tensors in device/host memory
    (exactly what budget_cells exists to prevent). Double-buffering
    only needs depth 2.

    HBM envelope: `budget_cells` bounds the TOTAL device-resident
    footprint, not one bucket's — the bucketer is therefore sized at
    budget_cells // max_inflight per bucket, so max_inflight resident
    buckets can never exceed the envelope the caller budgeted
    (ROADMAP's PR-1 open item, resolved on the halve-the-bucket side:
    the sync wrapper keeps its depth-2 pipelining and the footprint
    guarantee instead of giving up the overlap with max_inflight=1).
    A single history too long to fit the per-slot budget can't be
    subdivided; such singleton buckets are dispatched LAST and strictly
    alone (everything else resolved first, nothing pipelined next to
    them), so the envelope degrades to one such history's own
    unavoidable footprint, never that plus a pipeline's worth.

    With pack_thread_enabled() (default) a dedicated "pack-h2d" thread
    packs bucket N+1 and device_puts it while the calling thread
    dispatches/collects bucket N, so the parent's critical path is
    only the async kernel enqueue and the h2d copy overlaps device
    compute; a Semaphore caps packed-and-transferred-but-unresolved
    buckets at max_inflight so the thread can never outrun the
    envelope.

    `phases` (optional dict) accumulates per-phase host wall-clock:
    "pack" (bucket planning + host tensor packing), "h2d" (device_put /
    sharding), "dispatch" (async kernel enqueue); `.result(phases)`
    and the max_inflight back-pressure add "collect" (block + D2H +
    flag rendering)."""
    if mesh is not None and mesh.devices.size == 1:
        # a 1-device mesh (analyze-store's make_mesh() on a single-
        # device host) is single-device dispatch wearing a Mesh:
        # normalize it away so the warm path — views pack, donated
        # buffers, the AOT executable cache — applies to the REAL
        # sweep, not just bare-mesh callers. Sharding over one device
        # is an identity constraint; verdicts are unchanged.
        mesh = None
    parts: list = []
    inflight: list[int] = []    # indices into parts, oldest first
    depth = max(1, max_inflight)
    dp = mesh.devices.shape[0] if mesh is not None else 1
    tr = trace.get_current()
    kw = dict(classify=classify, realtime=realtime,
              process_order=process_order, fused=fused,
              with_stats=bool(with_stats))
    eff_budget = max(1, budget_cells // depth)
    with _phase(phases, "pack"):
        pl = _planner.get()
        if pl is not None:
            # the cost-aware planner races candidate pad multiples on
            # predicted device seconds and keeps the winner's
            # composition; it answers bucket_by_length's exact output
            # (multiple 128) whenever it has no model — and composition
            # only moves histories between dispatches, never changes a
            # verdict
            buckets = pl.plan_buckets(encs, budget_cells=eff_budget,
                                      dp=dp)
        else:
            buckets = bucket_by_length(encs, budget_cells=eff_budget,
                                       dp=dp)
        # Singleton buckets whose one history alone exceeds the
        # per-slot budget cannot honor depth-sharing: peel them off to
        # dispatch strictly alone after the pipelined buckets drain.
        oversized = [b for b in buckets
                     if _est_cells(encs, b, dp) > eff_budget]
        buckets = [b for b in buckets
                   if _est_cells(encs, b, dp) <= eff_budget]

    def finish(idx, flags, t_disp=None, donated=False, smeta=None):
        out = _finish_part(encs, idx, flags, mesh, eff_budget, kw,
                           tr, phases, t_disp, donated, smeta)
        # dispatched-vs-resolved parity for the live health snapshot:
        # exactly the buckets `buckets_dispatched` counted resolve
        # through here (sync-resolved OOM paths were never dispatched)
        tr.counter("buckets_resolved").inc()
        return out

    def resolve_oldest():
        j = inflight.pop(0)
        with _phase(phases, "collect"):
            idx, flags, t_disp, donated, smeta = parts[j]
            parts[j] = (idx, finish(idx, flags, t_disp, donated, smeta),
                        None, False, None)
            tr.gauge("inflight_depth").set(len(inflight))

    def dispatch(item) -> bool:
        """Enqueue one packed bucket async; returns False when the
        bucket was instead resolved synchronously (an OOM at enqueue
        went down the backdown path — nothing joined the pipeline)."""
        bucket, bucket_mesh, shape, args = item
        # the bucket geometry rides both sub-spans: batch x padded txns
        geo = {"B": int(args[0].shape[0]), "T": int(shape.n_txns)}
        try:
            with _phase(phases, "dispatch"):
                # dispatch.resolve / dispatch.enqueue split the phase
                # for the trace only: `phase` spans on this thread
                # (they name its idle gaps) that add to no total
                with tr.span("dispatch.resolve", cat="phase", **geo):
                    donate = _donate_active(bucket_mesh)
                    fn = _dispatch_fn(bucket_mesh, shape, kw, args,
                                      donate)
                with tr.span("dispatch.enqueue", cat="phase", **geo):
                    sv.maybe_inject_oom()
                    out = fn(*args)
                    # a kernel-stats dispatch returns (flags, stats);
                    # the flags array stays the dispatch's identity
                    # (device windows, cost observatory) and the stats
                    # ride as smeta
                    flags, dev_stats = out if isinstance(out, tuple) \
                        else (out, None)
                    if donate:
                        _note_donation(tr, args)
                    parts.append((bucket, flags, time.perf_counter(),
                                  donate,
                                  (dev_stats, shape)
                                  if dev_stats is not None else None))
                    obs_device.begin_dispatch(flags, kw, shape,
                                              donate, args, tr)
                    inflight.append(len(parts) - 1)
                    tr.counter("buckets_dispatched").inc()
                    tr.gauge("inflight_depth").set(len(inflight))
        except BaseException as e:
            if not sv.is_oom_error(e) or sv.strict_enabled():
                raise
            parts.append((bucket, _oom_backdown(
                encs, bucket, mesh, eff_budget, kw, tr, phases, e),
                None, False, None))
            return False
        return True

    def handle_failed(bucket, e):
        """A bucket whose pack/h2d failed: strict re-raises (the old
        fail-fast contract); OOM goes down the backdown path; any
        other *Exception* quarantines JUST this bucket — independent
        sub-problems fail independently, the rest of the sweep
        proceeds. Non-Exception BaseExceptions (KeyboardInterrupt,
        SystemExit) always re-raise: a Ctrl-C must stop the sweep,
        not journal a bogus permanent 'unknown'."""
        if sv.strict_enabled() or not isinstance(e, Exception):
            raise e
        if sv.is_oom_error(e):
            parts.append((bucket, _oom_backdown(
                encs, bucket, mesh, eff_budget, kw, tr, phases, e),
                None, False, None))
        else:
            parts.append((bucket,
                          _quarantine_bucket(bucket, "pack", e, tr),
                          None, False, None))

    _FAILED = object()

    if pack_thread_enabled() and len(buckets) > 1:
        # Staged pipeline: the packer thread owns pack + h2d; `sem`
        # counts device-resident buckets (transferred, not yet
        # resolved) so pack can run one bucket ahead while h2d waits
        # for an envelope slot.
        out: _queue.Queue = _queue.Queue()
        sem = threading.Semaphore(depth)
        stop = threading.Event()
        _DONE = object()

        def producer():
            try:
                for b in buckets:
                    # per-bucket isolation: a history that breaks
                    # packing must not kill the producer (and with it
                    # every later bucket's verdict) — the failure
                    # rides the queue as a marker for the caller's
                    # quarantine/backdown policy
                    try:
                        item = _prep_bucket(encs, b, mesh, dp,
                                            eff_budget, tr, phases)
                    except BaseException as e:
                        out.put((_FAILED, b, e))
                        continue
                    sem.acquire()
                    if stop.is_set():
                        return
                    try:
                        out.put(_h2d_bucket(item, phases))
                    except BaseException as e:
                        sem.release()   # no dispatch will free this slot
                        out.put((_FAILED, b, e))
                out.put(_DONE)
            except BaseException as e:   # surfaced on the caller
                out.put(e)

        th = threading.Thread(target=producer, name="pack-h2d",
                              daemon=True)
        th.start()
        try:
            while True:
                # a main-thread stall on the producer is its own phase
                # ("feed"): with pack/h2d accruing on their own thread,
                # the main thread's wall clock partitions into
                # feed/dispatch/collect instead
                with _phase(phases, "feed"):
                    item = out.get()
                if item is _DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                if isinstance(item, tuple) and item and \
                        item[0] is _FAILED:
                    handle_failed(item[1], item[2])
                    continue
                if not dispatch(item):
                    # resolved synchronously: the envelope slot the
                    # producer acquired for it frees right now, or the
                    # producer parks forever while we wait on its queue
                    sem.release()
                elif len(inflight) >= depth:
                    # release an envelope slot as soon as the pipeline
                    # is full: the producer's h2d for bucket N+depth
                    # waits on this resolve, which itself overlaps
                    # bucket N+1's compute
                    resolve_oldest()
                    sem.release()
        finally:
            stop.set()
            for _ in buckets:   # unblock a producer parked on sem
                sem.release()
            th.join(timeout=30)
    else:
        for bucket in buckets:
            while len(inflight) >= depth:
                resolve_oldest()
            try:
                item = _h2d_bucket(
                    _prep_bucket(encs, bucket, mesh, dp, eff_budget,
                                 tr, phases), phases)
            except BaseException as e:
                handle_failed(bucket, e)
                continue
            dispatch(item)
    for bucket in oversized:
        # strictly-alone dispatch: drain EVERYTHING first so this
        # history's unavoidable footprint is the only thing resident
        # (the mesh-padding check may use the full budget — nothing
        # shares the envelope with it)
        while inflight:
            resolve_oldest()
        try:
            item = _h2d_bucket(
                _prep_bucket(encs, bucket, mesh, dp, budget_cells,
                             tr, phases), phases)
        except BaseException as e:
            handle_failed(bucket, e)
            continue
        dispatch(item)
    return PendingVerdicts(len(encs), parts, finish)


def check_bucketed(encs: Sequence, mesh: Mesh | None = None, *,
                   classify: bool = True, realtime: bool = False,
                   process_order: bool = False,
                   budget_cells: int = 1 << 27,
                   two_pass: bool | None = None,
                   fused: bool | None = None,
                   phases: dict | None = None,
                   stats_out: list | None = None) -> list[dict]:
    """Check many encoded histories bucketed by length: one device
    dispatch per bucket, results returned in input order.

    With classify=True the default strategy is the FUSED detect/classify
    kernel (kernels.fused_classify_enabled): one dispatch per bucket
    runs the detect closure and only fires the classification closures
    (via lax.cond) when some history in the bucket is cyclic, reusing
    the detect pass's full closure for the cycle/G2 tests. On the
    production regime — sweeps that are mostly valid — every bucket runs
    at the detect rate with no re-dispatch, which is what lets the
    streaming pipeline stay async end to end. The cond is per BUCKET:
    one positive makes its whole bucket pay the classification
    closures (~3x detect), trading that for zero re-dispatch, no
    re-pack, and no per-subset recompiles; a sweep whose positives are
    dense enough to trip most buckets can pin two_pass=True (or
    JEPSEN_TPU_FUSED_CLASSIFY=0) to get the flagged-subset re-dispatch
    back.

    two_pass=True (the pre-fusion strategy, and the default when
    JEPSEN_TPU_FUSED_CLASSIFY=0) sweeps every bucket in detect mode and
    re-dispatches ONLY flagged histories with the chained classification
    closures. Verdicts are identical on every strategy because a
    cycle-free graph classifies to zero flags.

    `stats_out` (a list) is EXTENDED with one `kernels.stats_row` dict
    per input history — the kernel-stats telemetry path
    (JEPSEN_TPU_KERNEL_STATS); entries are None for quarantined or
    backdown-recovered histories. On the two-pass strategy the stats
    come from the DETECT pass (the from-scratch full closure — the
    uniform definition); the classify re-dispatch runs stats-free."""
    if not len(encs):
        return []
    if fused is None:
        fused = K.fused_classify_enabled()
        pl = _planner.get()
        if pl is not None and classify:
            # the planner may flip the classify strategy when the
            # costdb has measured BOTH fused and two-pass at this
            # workload's geometry (verdicts are pinned identical
            # across strategies); an explicit fused= argument or a
            # cold planner keeps the gate's choice
            t_pad = K.pad_to(max((_size_of(e) for e in encs),
                                 default=1), 128)
            fused = pl.fused_choice(fused, classify=classify,
                                    t_pad=t_pad)
    if two_pass is None:
        two_pass = classify and not fused
    if classify and two_pass:
        detect = check_bucketed(encs, mesh, classify=False,
                                realtime=realtime,
                                process_order=process_order,
                                budget_cells=budget_cells, phases=phases,
                                stats_out=stats_out)
        # quarantined sentinels pass straight through: there is
        # nothing to classify for a history the supervisor abandoned
        flagged = [i for i, f in enumerate(detect)
                   if f and not isinstance(f, sv.Quarantined)]
        if not flagged:
            return detect
        # the re-dispatch population is all-cyclic, where the chained
        # warm starts beat the fused kernel's unseeded detect closure
        full = check_bucketed([encs[i] for i in flagged], mesh,
                              classify=True, realtime=realtime,
                              process_order=process_order,
                              budget_cells=budget_cells, two_pass=False,
                              fused=False, phases=phases)
        out = list(detect)
        for i, r in zip(flagged, full):
            out[i] = r
        return out
    pv = check_bucketed_async(
        encs, mesh, classify=classify, realtime=realtime,
        process_order=process_order, budget_cells=budget_cells,
        fused=fused, phases=phases,
        with_stats=stats_out is not None)
    res = pv.result(phases)
    if stats_out is not None:
        stats_out.extend(pv.stats())
    return res
