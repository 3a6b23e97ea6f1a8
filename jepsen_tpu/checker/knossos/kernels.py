"""TPU kernel: batched just-in-time linearizability search.

The knossos `linear` analysis walks a history maintaining a set of
*configurations* — (model state, which currently-pending ops have
already been linearized) — pruning configurations that miss an op's
completion deadline. That search is irregular on a JVM but maps onto a
TPU as dense frontier algebra (SURVEY.md §7 stage 4):

- A configuration is two int32s: interned register state + a bitmask
  over pending-op slots. The frontier is a fixed [F] arena in
  HBM/VMEM, kept sorted and deduplicated.
- One *expansion round* applies every pending unapplied op to every
  configuration at once ([F, S] candidate grid on the VPU), merges with
  the originals, and compacts via two `lax.sort` passes (bitonic sorts
  — TPU-native) — candidate generation, dedup, and compaction are all
  branch-free.
- Expansion runs to fixpoint (a `lax.while_loop` with an
  equality-on-sorted-frontier exit) only at completion events; an op's
  completion then *filters* the frontier to configurations that
  linearized it, mirroring the just-in-time deadline rule.
- Indeterminate (:info) ops occupy a slot forever and never filter —
  they may linearize anywhere after invocation or not at all.

The whole event walk is one `lax.scan`, vmapped over histories and
sharded over the device mesh by the callers in `..` / `parallel`.
Frontier overflow (more live configurations than F) degrades the
verdict to "unknown", never to a wrong answer — the same pragmatism the
reference applies to Knossos memory blowups
(jepsen/src/jepsen/checker.clj:216-219).

Verdict parity with the CPU WGL engine (`__init__.wgl`) is the
acceptance criterion; `tests/test_knossos.py` checks it differentially.

Performance characteristics (measured, v5 lite single chip, etcd-shaped
CAS histories at concurrency 10): the CPU WGL engine wins on *valid*
histories by an order of magnitude — its depth-first greedy path rarely
backtracks, while this kernel pays the full frontier cost at every
completion, and the frontier arena genuinely needs to be large
(2^concurrency-ish) to avoid overflow. What the device path buys is
*shape-bound, predictable* cost: WGL degenerates exponentially on
highly-concurrent or invalid histories (the reference caps its output
because "writing these can take *hours*", checker.clj:216-219), while
the frontier walk costs the same whether the history is valid,
invalid, or adversarial. Hence the checker defaults to CPU and
`Linearizable(backend="tpu")` is the opt-in bounded-latency engine;
overflow degrades to "unknown" and re-routes to the CPU oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import trace
from ...devices import default_devices
from ...util import pad_to_multiple
from .encode import (CAS, COMPLETE_EV, INVOKE_EV, READ, WRITE,
                     EncodedRegisterHistory, RegisterBatchShape,
                     pack_register_batch)

_BIG = jnp.int32(2**31 - 1)


def _step_register(state, f, a1, a2, known):
    """Vectorized CAS-register transition. Returns (ok, new_state).

    read: legal iff value unknown or equal to state; write: always
    legal; cas [old new]: legal iff state == old. A linearized cas
    always succeeds — a failed cas is a no-op, represented by *not*
    linearizing it (its completion filter never fires for :info ops,
    and :ok cas implies success)."""
    is_w = f == WRITE
    is_c = f == CAS
    is_r = f == READ
    ok = jnp.where(is_r, (known == 0) | (state == a1),
                   jnp.where(is_c, state == a1, True))
    new = jnp.where(is_w, a1, jnp.where(is_c, a2, state))
    return ok, new


def _sorted_unique(states, masks, valid, F: int):
    """Sort (state, mask) pairs with invalid entries last, mark first
    occurrences, compact the unique live ones into the first F slots.
    Returns (states, masks, valid, n_unique) each [F]."""
    k1 = jnp.where(valid, states, _BIG)
    k2 = jnp.where(valid, masks, _BIG)
    k1, k2, s, m, v = jax.lax.sort(
        (k1, k2, states, masks, valid.astype(jnp.int32)), num_keys=2)
    first = jnp.ones_like(k1, dtype=bool).at[1:].set(
        (k1[1:] != k1[:-1]) | (k2[1:] != k2[:-1]))
    keep = first & (v > 0)
    n_unique = jnp.sum(keep.astype(jnp.int32))
    # Canonical compaction: kept entries to the front in (state, mask)
    # order — a deterministic arrangement of the set, so the fixpoint
    # loop's equality exit is well-defined.
    ck = (~keep).astype(jnp.int32)
    _, s, m, v = jax.lax.sort(
        (ck, s, m, keep.astype(jnp.int32)), num_keys=3)
    return s[:F], m[:F], v[:F] > 0, n_unique


def _expand_fixpoint(states, masks, valid, slot_f, slot_a1, slot_a2,
                     slot_known, enabled, F: int, S: int,
                     with_stats: bool = False):
    """Close the frontier under single-op linearization: repeatedly apply
    every occupied, unapplied slot to every configuration until the
    sorted frontier stops changing. Returns (states, masks, valid,
    overflow) — plus (peak frontier width, rounds run, candidate
    configurations generated) under `with_stats` (the kernel-stats
    telemetry path; the frontier math itself is identical — the extra
    carry only observes it)."""
    slot_bits = jnp.int32(1) << jnp.arange(S, dtype=jnp.int32)

    def round_(states, masks, valid):
        occupied = slot_f >= 0                               # [S]
        unapplied = (masks[:, None] & slot_bits[None, :]) == 0
        can = valid[:, None] & occupied[None, :] & unapplied  # [F,S]
        ok, new_state = _step_register(
            states[:, None], slot_f[None, :], slot_a1[None, :],
            slot_a2[None, :], slot_known[None, :])
        can = can & ok
        cand_states = jnp.broadcast_to(new_state, (F, S)).reshape(-1)
        cand_masks = (masks[:, None] | slot_bits[None, :]).reshape(-1)
        all_states = jnp.concatenate([states, cand_states])
        all_masks = jnp.concatenate([masks, cand_masks])
        all_valid = jnp.concatenate([valid, can.reshape(-1)])
        s, m, v, n = _sorted_unique(all_states, all_masks, all_valid,
                                    F)
        changed = ~(jnp.all((s == states) & (m == masks))
                    & jnp.all(v == valid))
        n_cand = jnp.sum(can.astype(jnp.int32))
        return s, m, v, changed, n > F, n, n_cand

    def cond(front):
        # Bounded by S+2 rounds: any forced chain applies at most S ops,
        # and the bound also guarantees termination under frontier
        # truncation (where the verdict is already "unknown").
        return front[3] & (front[5] < S + 2)

    if with_stats:
        def body(front):
            s, m, v, changed, ovf, n, nc = round_(front[0], front[1],
                                                  front[2])
            return (s, m, v, changed, front[4] | ovf, front[5] + 1,
                    jnp.maximum(front[6], n), front[7] + nc)

        init = (states, masks, valid, enabled, jnp.bool_(False),
                jnp.int32(0), jnp.int32(0), jnp.int32(0))
        (states, masks, valid, _, overflow, rounds, peak,
         explored) = jax.lax.while_loop(cond, body, init)
        return states, masks, valid, overflow, peak, rounds, explored

    def body(front):
        s, m, v, changed, ovf, _n, _nc = round_(front[0], front[1],
                                                front[2])
        return s, m, v, changed, front[4] | ovf, front[5] + 1

    # First round unconditionally sorts/dedups the incoming frontier
    # (it may be unsorted after a completion filter); the exit test
    # compares successive sorted frontiers.
    init = (states, masks, valid, enabled, jnp.bool_(False),
            jnp.int32(0))
    states, masks, valid, _, overflow, _ = jax.lax.while_loop(
        cond, body, init)
    return states, masks, valid, overflow


def _scan_history(events, F: int, S: int, with_stats: bool = False):
    """Run the event walk for one history. events: [E, 6] int32.
    Returns (valid?, overflow) — plus (peak frontier width, expansion
    rounds, configurations generated) under `with_stats`."""
    E = events.shape[0]

    init = (
        jnp.zeros((F,), jnp.int32),                       # states
        jnp.zeros((F,), jnp.int32),                       # masks
        jnp.zeros((F,), bool).at[0].set(True),            # valid
        jnp.full((S,), -1, jnp.int32),                    # slot_f
        jnp.zeros((S,), jnp.int32),                       # slot_a1
        jnp.zeros((S,), jnp.int32),                       # slot_a2
        jnp.zeros((S,), jnp.int32),                       # slot_known
        jnp.bool_(False),                                 # overflow
    )
    if with_stats:
        init = init + (jnp.int32(1),                      # peak width
                       jnp.int32(0),                      # rounds
                       jnp.int32(0))                      # explored

    def step(carry, ev):
        (states, masks, valid, slot_f, slot_a1, slot_a2, slot_known,
         overflow, *stats) = carry
        kind, slot, f, a1, a2, known = (ev[0], ev[1], ev[2], ev[3],
                                        ev[4], ev[5])
        is_inv = kind == INVOKE_EV
        is_comp = kind == COMPLETE_EV

        slot_f = slot_f.at[slot].set(
            jnp.where(is_inv, f, slot_f[slot]))
        slot_a1 = slot_a1.at[slot].set(
            jnp.where(is_inv, a1, slot_a1[slot]))
        slot_a2 = slot_a2.at[slot].set(
            jnp.where(is_inv, a2, slot_a2[slot]))
        slot_known = slot_known.at[slot].set(
            jnp.where(is_inv, known, slot_known[slot]))

        if with_stats:
            (states, masks, valid, ovf, peak, rounds,
             explored) = _expand_fixpoint(
                states, masks, valid, slot_f, slot_a1, slot_a2,
                slot_known, is_comp, F, S, with_stats=True)
            stats = (jnp.maximum(stats[0], peak), stats[1] + rounds,
                     stats[2] + explored)
        else:
            states, masks, valid, ovf = _expand_fixpoint(
                states, masks, valid, slot_f, slot_a1, slot_a2,
                slot_known, is_comp, F, S)
        overflow |= ovf

        # Completion deadline: only configurations that linearized the
        # op survive; its slot bit retires and the slot frees.
        bit = (masks >> slot) & 1
        valid = valid & jnp.where(is_comp, bit == 1, True)
        masks = jnp.where(is_comp, masks & ~(jnp.int32(1) << slot),
                          masks)
        slot_f = slot_f.at[slot].set(
            jnp.where(is_comp, -1, slot_f[slot]))

        return (states, masks, valid, slot_f, slot_a1, slot_a2,
                slot_known, overflow) + tuple(stats), None

    carry, _ = jax.lax.scan(step, init, events, length=E)
    if with_stats:
        valid, overflow = carry[2], carry[7]
        return (jnp.any(valid), overflow, carry[8], carry[9],
                carry[10])
    states, masks, valid, *_rest, overflow = carry
    return jnp.any(valid), overflow


@functools.partial(jax.jit, static_argnames=("frontier", "n_slots",
                                             "with_stats"))
def check_batch_device(events, *, frontier: int = 512,
                       n_slots: int = 16, with_stats: bool = False):
    """Jitted batched entry: events [B, E, 6] -> (valid [B] bool,
    overflow [B] bool), plus (peak, rounds, explored) [B] int32 each
    under with_stats."""
    return jax.vmap(
        functools.partial(_scan_history, F=frontier, S=n_slots,
                          with_stats=with_stats))(events)


def check_encoded_batch(encs: list[EncodedRegisterHistory],
                        frontier: int = 512,
                        devices=None, packed: bool | None = None,
                        stats_out: list | None = None) -> list[dict]:
    """Check encoded register histories on device. Returns knossos-shaped
    verdicts: {"valid?": True|False|"unknown", "analyzer": "tpu-jit"}.

    Batches shard across addressable devices on a 1-D dp mesh (the
    analysis data plane, SURVEY.md §5.8); ragged batches are padded to a
    device multiple by replicating the last history (extras dropped) so
    sharding never silently degrades to one device.

    `packed=None` (auto) routes to the packed single-int32 kernel
    (`.packed`: 2 sort operands per compaction instead of 9; measured
    ~13x wall-clock on the CPU backend at conc-10) whenever every
    history's interned values fit `state << n_slots` in an int32 —
    differential parity with this kernel and the WGL oracle is pinned
    by tests/test_knossos.py::TestPackedKernelParity. An explicit
    packed=True downgrades to the unpacked kernel if the batch doesn't
    fit: aliased packings could return confident wrong verdicts, and
    this module never trades correctness for speed."""
    if not encs:
        return []
    n = len(encs)
    devices = devices if devices is not None else default_devices()
    tr = trace.get_current()
    with tr.phase_span("knossos_pack", keys=n):
        encs = pad_to_multiple(encs, len(devices))
        batch = pack_register_batch(encs)
        shape: RegisterBatchShape = batch["shape"]
        events = jnp.asarray(batch["events"])

        if len(devices) > 1:
            mesh = jax.sharding.Mesh(np.asarray(devices), ("dp",))
            sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("dp"))
            events = jax.device_put(events, sharding)

    from .packed import packable
    fits = all(packable(e.n_values, shape.n_slots) for e in encs)
    with_stats = stats_out is not None
    # stats requested -> the unpacked kernel (the only one carrying
    # the telemetry carry); verdict parity between the two kernels is
    # pinned by tests, so the downgrade is observability-only
    packed = (fits if packed is None else (packed and fits)) \
        and not with_stats
    # enqueue and block on the device: the main thread's wait
    with tr.phase_span("knossos_wait", keys=n, S=shape.n_slots):
        peak = rounds = explored = None
        if packed:
            from .packed import check_batch_device_packed
            valid, overflow = check_batch_device_packed(
                events, frontier=frontier, n_slots=shape.n_slots)
        elif with_stats:
            valid, overflow, peak, rounds, explored = check_batch_device(
                events, frontier=frontier, n_slots=shape.n_slots,
                with_stats=True)
            peak = np.asarray(peak)
            rounds = np.asarray(rounds)
            explored = np.asarray(explored)
        else:
            valid, overflow = check_batch_device(
                events, frontier=frontier, n_slots=shape.n_slots)
        valid = np.asarray(valid)
        overflow = np.asarray(overflow)
    out = []
    for i, e in enumerate(encs[:n]):
        if overflow[i]:
            out.append({"valid?": "unknown", "analyzer": "tpu-jit",
                        "cause": ":frontier-overflow"})
        else:
            out.append({"valid?": bool(valid[i]),
                        "analyzer": "tpu-jit",
                        "op-count": int(
                            (e.events[:, 0] == INVOKE_EV).sum())})
        if with_stats:
            stats_out.append({
                "engine": "tpu-jit",
                "frontier_peak": int(peak[i]),
                "frontier": int(frontier),
                "rounds": int(rounds[i]),
                "configs": int(explored[i]),
                "overflow": bool(overflow[i]),
                "n_slots": int(shape.n_slots)})
    return out
