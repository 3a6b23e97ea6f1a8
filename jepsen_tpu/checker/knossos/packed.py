"""Packed-configuration variant of the frontier linearizability kernel.

`kernels._scan_history` carries a configuration as two int32s (interned
register state, pending-slot bitmask) and its per-round compaction
sorts five operand arrays under two keys, twice per expansion round.
Almost every real history fits a far cheaper representation: when
`(n_values << n_slots) <= 2^31 - 1`, a configuration packs into ONE
int32 — `state << S | mask` — with 2^31-1 as the "empty slot" sentinel.
Sorting then moves a single int32 array (2 sort operands per
compaction round instead of 9 — measured ~13x wall-clock on the CPU
backend at conc-10, the sort being the kernel's dominant cost), dedup
is an adjacent compare on the packed key itself, and the fixpoint-exit
equality is one array compare.

Semantics are identical to the unpacked kernel (same expansion,
completion-filter, overflow and verdict rules — see kernels.py's
module docstring for the model); `tests/test_knossos.py` pins packed
vs unpacked vs the CPU WGL oracle differentially. `check_encoded_batch`
in kernels.py routes here automatically when every history in the
batch fits the packed budget, which conc-10 CAS histories always do
(S=10 leaves 21 bits for interned values) and conc-20 ones almost
always do (S=20 leaves 11 bits: 2047 distinct values).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernels import _BIG, _step_register
from .encode import COMPLETE_EV, INVOKE_EV


def packable(n_values: int, n_slots: int) -> bool:
    """Does state << S | mask stay below the _BIG sentinel?"""
    return n_slots < 31 and (n_values << n_slots) <= 2**31 - 1


def _sorted_unique_packed(cfgs, F: int):
    """Sort packed configs (invalid == _BIG last), drop duplicates,
    return (cfgs[:F], n_unique). Two single-operand sorts replace the
    unpacked path's 5-operand + 4-operand pair."""
    cfgs = jax.lax.sort(cfgs)
    dup = jnp.zeros_like(cfgs, dtype=bool).at[1:].set(
        cfgs[1:] == cfgs[:-1])
    cfgs = jnp.where(dup, _BIG, cfgs)
    n_unique = jnp.sum((cfgs != _BIG).astype(jnp.int32))
    cfgs = jax.lax.sort(cfgs)
    return cfgs[:F], n_unique


def _expand_fixpoint_packed(cfgs, slot_f, slot_a1, slot_a2, slot_known,
                            enabled, F: int, S: int):
    """Close the packed frontier under single-op linearization (the
    packed twin of kernels._expand_fixpoint)."""
    slot_bits = jnp.int32(1) << jnp.arange(S, dtype=jnp.int32)
    low = jnp.int32((1 << S) - 1)

    def round_(front):
        cfgs, _, overflow, _r = front
        live = cfgs != _BIG
        masks = cfgs & low
        states = cfgs >> S
        occupied = slot_f >= 0                                # [S]
        unapplied = (masks[:, None] & slot_bits[None, :]) == 0
        can = live[:, None] & occupied[None, :] & unapplied   # [F,S]
        ok, new_state = _step_register(
            states[:, None], slot_f[None, :], slot_a1[None, :],
            slot_a2[None, :], slot_known[None, :])
        can = can & ok
        cand = jnp.where(
            can,
            (jnp.broadcast_to(new_state, (F, S)) << S)
            | (masks[:, None] | slot_bits[None, :]),
            _BIG).reshape(-1)
        all_cfgs = jnp.concatenate([cfgs, cand])
        c, n = _sorted_unique_packed(all_cfgs, F)
        changed = jnp.any(c != cfgs)
        return c, changed, n > F, _r

    def cond(front):
        # Bounded by S+2 rounds, as in the unpacked kernel.
        return front[1] & (front[3] < S + 2)

    def body(front):
        c, changed, ovf, r = round_(front)
        return c, changed, front[2] | ovf, r + 1

    init = (cfgs, enabled, jnp.bool_(False), jnp.int32(0))
    cfgs, _, overflow, _ = jax.lax.while_loop(cond, body, init)
    return cfgs, overflow


def _scan_history_packed(events, F: int, S: int):
    """Event walk for one history over packed configs. events: [E, 6]
    int32. Returns (valid?, overflow)."""
    E = events.shape[0]

    init = (
        jnp.full((F,), _BIG, jnp.int32).at[0].set(0),      # cfgs
        jnp.full((S,), -1, jnp.int32),                     # slot_f
        jnp.zeros((S,), jnp.int32),                        # slot_a1
        jnp.zeros((S,), jnp.int32),                        # slot_a2
        jnp.zeros((S,), jnp.int32),                        # slot_known
        jnp.bool_(False),                                  # overflow
    )

    def step(carry, ev):
        cfgs, slot_f, slot_a1, slot_a2, slot_known, overflow = carry
        kind, slot, f, a1, a2, known = (ev[0], ev[1], ev[2], ev[3],
                                        ev[4], ev[5])
        is_inv = kind == INVOKE_EV
        is_comp = kind == COMPLETE_EV

        slot_f = slot_f.at[slot].set(jnp.where(is_inv, f, slot_f[slot]))
        slot_a1 = slot_a1.at[slot].set(
            jnp.where(is_inv, a1, slot_a1[slot]))
        slot_a2 = slot_a2.at[slot].set(
            jnp.where(is_inv, a2, slot_a2[slot]))
        slot_known = slot_known.at[slot].set(
            jnp.where(is_inv, known, slot_known[slot]))

        cfgs, ovf = _expand_fixpoint_packed(
            cfgs, slot_f, slot_a1, slot_a2, slot_known, is_comp, F, S)
        overflow |= ovf

        # Completion deadline. _BIG has every low bit set, so the
        # sentinel must be exempted explicitly before the bit test.
        live = cfgs != _BIG
        bit = (cfgs >> slot) & 1
        keep = live & (bit == 1)
        filtered = jnp.where(keep, cfgs & ~(jnp.int32(1) << slot), _BIG)
        cfgs = jnp.where(is_comp, filtered, cfgs)
        slot_f = slot_f.at[slot].set(
            jnp.where(is_comp, -1, slot_f[slot]))

        return (cfgs, slot_f, slot_a1, slot_a2, slot_known,
                overflow), None

    carry, _ = jax.lax.scan(step, init, events, length=E)
    cfgs, *_rest, overflow = carry
    return jnp.any(cfgs != _BIG), overflow


@functools.partial(jax.jit, static_argnames=("frontier", "n_slots"))
def check_batch_device_packed(events, *, frontier: int = 512,
                              n_slots: int = 16):
    """Jitted packed entry: events [B, E, 6] -> (valid [B], overflow
    [B])."""
    return jax.vmap(
        functools.partial(_scan_history_packed, F=frontier,
                          S=n_slots))(events)
