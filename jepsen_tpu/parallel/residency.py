"""Executable residency + device-slot ownership, split out of sweep
orchestration (the unlocking refactor ROADMAP items 1 and 2 share).

The bucket dispatcher in `parallel/__init__.py` used to own three
unrelated concerns at once: bucket scheduling (its real job), WHICH
compiled executables are resident for repeat dispatches, and WHO holds
the donated device-buffer slots while a dispatch is in flight. The
multi-host mesh sweep (analyze-store --mesh) runs one long-lived
dispatch loop per shard, and the future `serve` daemon (ROADMAP item
2) runs one per process forever — both need executables and donated
buffers held resident across requests without re-owning the
bookkeeping, so the two non-scheduling concerns live here:

  * `ExecutableResidency` — resolves the callable for one dispatch,
    mesh-sharded or single-device alike: the persistent AOT-compiled
    executable (jepsen_tpu.aot), keyed by kernel flags + batch
    geometry + input shardings (a mesh's axis
    names and sizes among them), so a warm owner pays zero XLA
    compiles — and zero re-traces — however many dispatch loops it
    runs. A sharded executable carries the collectives XLA inserted
    when it compiled.
  * `DeviceSlots` — ownership of donated device-buffer slots: the
    donation policy gate (single-device only, JEPSEN_TPU_DONATE_
    BUFFERS) plus the supervisor's process-wide slot ledger. A slot
    is acquired per donated dispatch and MUST be released exactly once
    when the dispatch's fate is decided — success, watchdog
    quarantine, or OOM backdown re-plan (the split halves acquire
    their own slots; an ancestor's is never held through recovery).

Both are plain objects so a second dispatch owner (a serve daemon's
continuous batcher) can hold its own `DeviceSlots` over a different
ledger while sharing the one process-wide executable residency.
"""

from __future__ import annotations


class ExecutableResidency:
    """Which compiled executables are resident for repeat dispatches.

    jax's in-memory jit cache is keyed on the jitted function object,
    so a rebuilt wrapper (an evicted `_sharded_check_fn_cached` entry)
    traces again; the AOT executable map is keyed on what the compiled
    artifact depends on, in memory and across processes, behind one
    stable key, so callers ask for "the callable for this dispatch"
    and never learn how executables are stored."""

    def dispatch_fn(self, fn, shape, kw: dict, args, donate: bool):
        """The callable for one bucket dispatch: the persistent compiled
        executable for `fn` over `args` when the AOT cache is on, found
        by input avals and shardings before anything traces — so a
        geometry met once never re-traces, however often its jitted
        wrapper is rebuilt — else `fn` (the jitted check fn) itself.
        On the jitted path a one-time `jit.lower()` per geometry still
        feeds the device cost observatory (obs.device,
        JEPSEN_TPU_COSTDB; the compiled path captures inside
        aot.compiled_for)."""
        key = self.dispatch_key(kw, shape, donate)
        if not self._aot_enabled():
            from ..obs import device as device_obs
            device_obs.observe(key, args, fn, source="lowered")
            return fn
        from .. import aot
        return aot.compiled_for(fn, args, key)

    @staticmethod
    def _aot_enabled() -> bool:
        from .. import aot
        return aot.enabled()

    @staticmethod
    def resident_count() -> int:
        """How many compiled executables this process holds resident
        (the AOT in-memory map — jax's own jit cache is opaque)."""
        from .. import aot
        return aot.resident_count()

    @staticmethod
    def dispatch_key(kw: dict, shape, donate: bool) -> tuple:
        """The stable half of the AOT cache key for one dispatch: kernel
        flags + batch geometry (aot itself adds
        input avals and shardings — a mesh's axis names and sizes with
        them — backend topology and jax/jaxlib versions). A
        kernel-stats dispatch (JEPSEN_TPU_KERNEL_STATS) returns a
        second output and so compiles a different executable — the
        marker is APPENDED only when the flag is on, so the gate-off
        key (and every cached executable keyed under it) is
        byte-identical to before."""
        return (kw.get("classify", True), kw.get("realtime", False),
                kw.get("process_order", False), kw.get("fused"), donate,
                shape.n_keys, shape.max_pos, shape.n_txns) \
            + (("stats",) if kw.get("with_stats") else ())


class DeviceSlots:
    """Donated device-buffer slot ownership for one dispatch owner.

    Wraps the donation policy (the gate + the single-device-only rule)
    and a `supervisor.DeviceSlotLedger` so every acquire/release pair
    goes through one object — a drained owner with nonzero inflight is
    a leak, which the warm-path tests pin to zero."""

    def __init__(self, ledger=None):
        if ledger is None:
            from .. import supervisor as sv
            ledger = sv.slot_ledger
        self.ledger = ledger

    def donate_active(self, bucket_mesh) -> bool:
        """Does donation apply to this dispatch? Single-device only
        (the mesh flag is normalized away so it can't split the
        compile cache) and gated by JEPSEN_TPU_DONATE_BUFFERS; on CPU
        the spurious 'donated buffers not usable' warning is filtered
        at this dispatch site (pytest resets warning filters per test,
        so a one-time install would not survive)."""
        from .. import supervisor as sv
        active = bucket_mesh is None and sv.donate_buffers_enabled()
        if active:
            self._filter_cpu_donation_warning()
        return active

    @staticmethod
    def _filter_cpu_donation_warning() -> None:
        import jax
        if jax.default_backend() == "cpu":
            import warnings
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")

    def note_donation(self, tr, args=None) -> None:
        """One donated dispatch: six input buffers handed to XLA, one
        ledger slot held until the dispatch resolves. With `args` (and
        the cost observatory on) the donated BYTES are counted too —
        the residency surface the HBM ledger publishes."""
        self.ledger.acquire()
        tr.counter("buffers_donated").inc(6)
        if args is not None:
            from ..obs import device as device_obs
            if device_obs.enabled():
                try:
                    tr.counter("donated_bytes").inc(
                        sum(int(a.nbytes) for a in args))
                except Exception:   # observability never sinks dispatch
                    pass

    def release(self) -> None:
        self.ledger.release()

    def inflight(self) -> int:
        return self.ledger.inflight()


def publish_residency_gauges(tr, modeled_bytes: int | None = None
                             ) -> None:
    """THE residency-gauge publication point (obs.device calls it at
    each dispatch open/close): resident executables, modeled HBM in
    flight, and — throttled by JEPSEN_TPU_RESIDENCY_INTERVAL_S — the
    backend's own `memory_stats()` where the platform reports one.
    The gauges land in the metrics registry, so metrics.json,
    `/metrics` and health.json's device section all agree."""
    tr.gauge("resident_executables").set(
        ExecutableResidency.resident_count())
    if modeled_bytes is not None:
        tr.gauge("hbm_modeled_bytes").set(int(modeled_bytes))
    from ..obs import device as device_obs
    device_obs.maybe_poll_memory_stats(tr)
