"""TPU device kernels for Elle-style cycle detection.

The north-star compute path (SURVEY.md §3.3, BASELINE.json): encoded
histories live in HBM as padded int32 tensors; dependency edges are built
with dense scatters; cycle detection runs as boolean transitive closure by
repeated matrix squaring — log2(T) int8 matmuls that map straight onto
the MXU — and anomaly classes fall out of closure/edge intersections:

  G0        some ww edge (u,v) with v→u in closure(ww)
  G1c       some wr edge (u,v) with v→u in closure(ww|wr)
  G-single  some rw edge (u,v) with v→u in closure(ww|wr)
  G2-item   some rw edge (u,v) with v→u only in closure(ww|wr|rw)

There is exactly one implementation of the math, written batched over
[B,T,T] tensors with a `constrain` hook: `jepsen_tpu.parallel` passes a
sharding constraint (dp over histories × mp over closure-matmul columns)
and jit shardings; the single-device path passes identity. Realtime and
process-order edges fold into the ww class (they strengthen cycles without
adding anti-dependencies), masked to each history's live rows.

All matmuls accumulate in int32 (`preferred_element_type`) from int8
operands: entries are 0/1 so any nonzero dot-product term keeps the
closure sound; magnitudes are re-thresholded to booleans every step.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ... import trace
from ...devices import default_devices
from ...util import pad_to_multiple
from .encode import EncodedHistory, effective_complete_index

# Flag bit positions in the kernel's output word.
G0, G1C, G_SINGLE, G2_ITEM, CYCLE = 0, 1, 2, 3, 4
FLAG_NAMES = {G0: "G0", G1C: "G1c", G_SINGLE: "G-single", G2_ITEM: "G2-item"}

#: Per-history search-stat row layout ([B, N_STATS] int32) the kernels
#: return alongside the verdict flags under JEPSEN_TPU_KERNEL_STATS —
#: the structural evidence behind a verdict (ISSUE 15):
#:
#:   ww/wr/rw_edges   distinct dependency edges per class, BEFORE the
#:                    power-of-two writer-chain shortcuts (so counts
#:                    match the CPU oracle's graph exactly);
#:   rt/proc_edges    realtime / process-order edges the kernel built
#:                    from the timing tensors;
#:   closure_rounds   squaring rounds the (final) closure actually ran
#:                    to its fixpoint for THIS history (vs the static
#:                    `closure_steps` bound the caller reports);
#:   cycle_round      first round at which a cycle became visible
#:                    (0 = present in the raw edge set; -1 = acyclic);
#:   scc_count/max/min  nontrivial SCCs of the full closure, their
#:                    largest and smallest member counts (0 = none);
#:   cycle_txns       txn rows participating in any cycle;
#:   margin           the decision-boundary margin: rounds of closure
#:                    work sustained before a cycle appeared
#:                    (= cycle_round for cyclic histories — high means
#:                    the cycle needs long paths, i.e. near-miss from
#:                    inside; = closure_rounds for valid ones — high
#:                    means deep dependency chains, near-miss from
#:                    outside). Together with the cyclic bit it orders
#:                    histories by distance to the decision boundary,
#:                    the signal the adversarial mutation search
#:                    (ROADMAP item 3) seeds from.
#:
#: The JEPSEN_TPU_KERNEL_STATS gate itself has ONE reader —
#: `obs.search.enabled()` — and the kernels never self-gate: callers
#: decide by passing `with_stats`/`stats_out`, so the off path stays
#: byte-identical (executables, dispatch keys, verdicts) with zero
#: gate reads on the dispatch hot path.
STAT_FIELDS = ("ww_edges", "wr_edges", "rw_edges", "rt_edges",
               "proc_edges", "closure_rounds", "cycle_round",
               "scc_count", "scc_max", "scc_min", "cycle_txns",
               "margin")
N_STATS = len(STAT_FIELDS)


def stats_row(row, *, n_txns: int, t_pad: int) -> dict:
    """One device stats row -> the per-history dict the analytics
    journal records: the named device fields plus the host-side
    geometry facts (bucket pad, the static closure bound, per-history
    pad waste in closure cells)."""
    out = {f: int(v) for f, v in zip(STAT_FIELDS, row)}
    out["n_txns"] = int(n_txns)
    out["t_pad"] = int(t_pad)
    out["closure_bound"] = closure_steps(t_pad)
    out["pad_waste_cells"] = int(t_pad) ** 2 - int(n_txns) ** 2
    return out

#: Per-chip peak throughput, keyed by a normalized `device_kind`
#: (`jax.devices()[0].device_kind`). A kind missing from the table is
#: an error, never a default: host CPUs and unknown chips have no peak,
#: and their callers ask for none. Values are the published per-chip
#: peaks: dense bf16 TFLOPS, int8
#: TOPS (chips without an int8 fast path reuse the bf16 number; the
#: closure squares in int8, see _square), HBM bandwidth GB/s and
#: capacity GiB.
DEVICE_PEAKS: dict[str, dict] = {
    "tpu v2": {"bf16_tflops": 45.0, "int8_tops": 45.0,
               "hbm_gbps": 700.0, "hbm_gib": 16.0},
    "tpu v3": {"bf16_tflops": 123.0, "int8_tops": 123.0,
               "hbm_gbps": 900.0, "hbm_gib": 32.0},
    "tpu v4": {"bf16_tflops": 275.0, "int8_tops": 275.0,
               "hbm_gbps": 1228.0, "hbm_gib": 32.0},
    "tpu v5 lite": {"bf16_tflops": 197.0, "int8_tops": 394.0,
                    "hbm_gbps": 819.0, "hbm_gib": 16.0},
    "tpu v5p": {"bf16_tflops": 459.0, "int8_tops": 918.0,
                "hbm_gbps": 2765.0, "hbm_gib": 95.0},
    "tpu v6 lite": {"bf16_tflops": 918.0, "int8_tops": 1836.0,
                    "hbm_gbps": 1640.0, "hbm_gib": 32.0},
}

#: Spelling aliases libtpu has shipped for the same chips.
_PEAK_ALIASES = {"tpu v5e": "tpu v5 lite", "tpu v5": "tpu v5p",
                 "tpu v6e": "tpu v6 lite", "tpu v6": "tpu v6 lite"}

def device_peak(device_kind: str | None = None) -> dict:
    """The peak-throughput row for `device_kind` (default: the first
    jax device's), plus `device_kind` (as reported) and `source`.
    Raises KeyError for a kind the table does not hold."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    norm = str(device_kind).strip().lower()
    norm = _PEAK_ALIASES.get(norm, norm)
    row = DEVICE_PEAKS.get(norm)
    if row is None:
        raise KeyError(f"no peak-throughput row for device kind "
                       f"{device_kind!r}")
    return {"device_kind": str(device_kind), "source": "table", **row}


def pad_to(x: int, multiple: int) -> int:
    """Round x up to a positive multiple."""
    return max(multiple, ((x + multiple - 1) // multiple) * multiple)


@dataclass(frozen=True)
class BatchShape:
    """Static padding plan for a batch of encoded histories."""

    n_txns: int      # T: txn rows per history (padded)
    n_appends: int   # A: append triples per history
    n_reads: int     # R: read triples per history
    n_keys: int      # K: interned keys per history
    max_pos: int     # P: longest version chain

    @staticmethod
    def plan(encs: list[EncodedHistory], multiple: int = 128) -> "BatchShape":
        return BatchShape(
            n_txns=pad_to(max((e.n for e in encs), default=1), multiple),
            n_appends=pad_to(max((len(e.appends) for e in encs), default=1), 8),
            n_reads=pad_to(max((len(e.reads) for e in encs), default=1), 8),
            n_keys=pad_to(max((e.n_keys for e in encs), default=1), 8),
            max_pos=pad_to(max((e.max_pos for e in encs), default=1), 8),
        )


def pack_batch(encs: list[EncodedHistory],
               shape: BatchShape | None = None) -> dict:
    """Pack EncodedHistories into padded stacked arrays (host-side).

    Padding convention: append/read triples beyond their count have
    txn = -1; txn rows beyond a history's n are dead (no triples reference
    them, and the kernel masks them out of realtime edges via n_txns)."""
    shape = shape or BatchShape.plan(encs)
    B = len(encs)
    appends = np.full((B, shape.n_appends, 3), -1, np.int32)
    reads = np.full((B, shape.n_reads, 3), -1, np.int32)
    invoke_idx = np.zeros((B, shape.n_txns), np.int64)
    complete_idx = np.zeros((B, shape.n_txns), np.int64)
    process = np.full((B, shape.n_txns), -1, np.int32)
    n_txns = np.zeros((B,), np.int32)
    for i, e in enumerate(encs):
        a = np.asarray(e.appends, np.int32)
        r = np.asarray(e.reads, np.int32)
        if len(a) > shape.n_appends or len(r) > shape.n_reads or \
                e.n > shape.n_txns:
            raise ValueError(f"history {i} exceeds batch shape {shape}")
        appends[i, : len(a)] = a
        reads[i, : len(r)] = r
        invoke_idx[i, : e.n] = e.invoke_index
        complete_idx[i, : e.n] = effective_complete_index(
            e.status, e.complete_index)
        process[i, : e.n] = e.process
        n_txns[i] = e.n
    return {"appends": appends, "reads": reads, "n_txns": n_txns,
            "invoke_index": invoke_idx, "complete_index": complete_idx,
            "process": process, "shape": shape}


def dispatch_shape(enc) -> "BatchShape | None":
    """The BatchShape a v2 (dispatch-shaped) sidecar pre-padded this
    encoding to, or None when it carries no dispatch views. The pad
    plan lives in store.dispatch_pad_plan (jax-free, for pool
    workers); this is the one place it re-enters the kernel type."""
    p = getattr(enc, "dispatch_pad", None)
    if not p or getattr(enc, "dispatch", None) is None:
        return None
    try:
        return BatchShape(n_txns=p["n_txns"], n_appends=p["n_appends"],
                          n_reads=p["n_reads"], n_keys=p["n_keys"],
                          max_pos=p["max_pos"])
    except KeyError:
        return None


def pack_batch_views(encs: list, shape: BatchShape) -> dict | None:
    """Copy-free sibling of pack_batch: when EVERY encoding carries
    dispatch-shaped mmap views (v2 sidecar warm path), return
    per-field LISTS of those views instead of freshly-copied stacked
    arrays — the h2d stage then device_puts each view straight from
    the mapped pages, pads ragged ones ON DEVICE (a history's own pad
    geometry may be smaller than the bucket max — `pad_to` is
    monotone, so it is never larger), and stacks in HBM. The host
    copies zero bytes either way. None when any encoding carries no
    views (cold encodings, v1 cache) or claims a geometry beyond the
    bucket's: the caller falls back to pack_batch, whose copies the
    warm counters attribute."""
    for e in encs:
        ds = dispatch_shape(e)
        if ds is None or ds.n_txns > shape.n_txns \
                or ds.n_appends > shape.n_appends \
                or ds.n_reads > shape.n_reads:
            return None
    fields = ("appends", "reads", "invoke_index", "complete_index",
              "process")
    out: dict = {f: [e.dispatch[f] for e in encs] for f in fields}
    out["n_txns"] = np.asarray([e.n for e in encs], np.int32)
    out["shape"] = shape
    out["views"] = True
    return out


def fused_classify_enabled() -> bool:
    """One home for the JEPSEN_TPU_FUSED_CLASSIFY gate (default on):
    classify dispatches run the fused detect/classify kernel — one
    detect closure per history, with the classification closures behind
    a `lax.cond` that only fires when some history in the batch is
    cyclic. `=0` restores the separate detect-then-classify re-dispatch
    (the pre-fusion two-pass strategy) for A/B runs."""
    from ... import gates

    return gates.get("JEPSEN_TPU_FUSED_CLASSIFY")


def closure_steps(n_txns: int) -> int:
    """Squaring rounds needed for a T-node graph: path lengths double each
    round; (A|I)^(2^s) covers all simple paths once 2^s >= T."""
    return max(1, int(np.ceil(np.log2(max(2, n_txns)))))


def _edges_one(appends: jnp.ndarray, reads: jnp.ndarray, n_keys: int,
               max_pos: int, n_txns: int, with_counts: bool = False):
    """Build [T,T] boolean adjacency matrices for ww/wr/rw from triples.

    appends: [A,3] (txn,key,pos), pos>=1 observed, -1 unobserved/dead.
    reads:   [R,3] (txn,key,pos-of-last), 0 empty read, -1 dead.

    With `with_counts` (the kernel-stats path) a fourth output carries
    the [3] int32 distinct-edge counts — ww counted BEFORE the
    power-of-two shortcut edges below, so the number matches the CPU
    oracle's adjacent-version graph, not the shortcut-augmented one
    the closure runs on.
    """
    T = n_txns
    a_txn, a_key, a_pos = appends[:, 0], appends[:, 1], appends[:, 2]
    r_txn, r_key, r_pos = reads[:, 0], reads[:, 1], reads[:, 2]
    a_live = (a_txn >= 0) & (a_pos >= 1)
    r_live = (r_txn >= 0) & (r_pos >= 0)

    # Writer lookup table W[key, pos] -> txn row (or -1). pos axis is
    # 1-based; slot 0 unused; dead triples scatter to a trash slot that is
    # re-nulled afterwards.
    W = jnp.full((n_keys, max_pos + 2), -1, jnp.int32)
    k_idx = jnp.where(a_live, a_key, n_keys - 1)
    p_idx = jnp.where(a_live, a_pos, max_pos + 1)
    W = W.at[k_idx, p_idx].set(jnp.where(a_live, a_txn, -1), mode="drop")
    W = W.at[:, max_pos + 1].set(-1)

    def scatter_edges(src, dst, live):
        live = live & (src >= 0) & (dst >= 0) & (src != dst)
        s = jnp.where(live, src, 0)
        d = jnp.where(live, dst, 0)
        adj = jnp.zeros((T, T), bool)
        return adj.at[s, d].max(live, mode="drop")

    # ww: writer of pos-1 -> writer of pos
    prev_w = W[k_idx, jnp.maximum(p_idx - 1, 0)]
    ww = scatter_edges(prev_w, a_txn, a_live & (a_pos >= 2))
    ww_raw = ww if with_counts else None

    # Power-of-two shortcut edges along each key's writer chain: an
    # edge W[k,p] -> W[k,p+s] is implied by transitivity whenever every
    # position p..p+s is live, so every closure is unchanged — but the
    # effective graph diameter drops from the chain length to ~log of
    # it, cutting squaring rounds (measured 8 -> 4 on the 5k-txn bench
    # shape, chain length 80). Soundness needs the contiguity gate: a
    # gap in the chain means no implied path, and a shortcut across it
    # would invent reachability.
    liveW = (W >= 0).astype(jnp.int32)          # [K, P+2]
    C = jnp.cumsum(liveW, axis=1)
    P = max_pos
    s = 2
    while s <= P:
        src = W[:, 1:P + 1 - s]                 # pos p = 1..P-s
        dst = W[:, 1 + s:P + 1]                 # pos p+s
        run = (C[:, 1 + s:P + 1] - C[:, 0:P - s]) == s + 1
        ww = ww | scatter_edges(src.ravel(), dst.ravel(), run.ravel())
        s *= 2

    # wr: writer of pos -> reader (pos >= 1)
    rk = jnp.where(r_live, r_key, n_keys - 1)
    rp = jnp.where(r_live & (r_pos >= 1), r_pos, max_pos + 1)
    wr = scatter_edges(W[rk, rp], r_txn, r_live & (r_pos >= 1))

    # rw: reader -> writer of pos+1
    rp1 = jnp.where(r_live, jnp.minimum(r_pos + 1, max_pos + 1), max_pos + 1)
    rw = scatter_edges(r_txn, W[rk, rp1], r_live)
    if with_counts:
        counts = jnp.stack([jnp.sum(ww_raw), jnp.sum(wr), jnp.sum(rw)]
                           ).astype(jnp.int32)
        return ww, wr, rw, counts
    return ww, wr, rw


def _closure_batched(m: jnp.ndarray, steps: int, constrain) -> jnp.ndarray:
    """Transitive closure of [B,T,T] boolean adjacencies via repeated
    squaring; each squaring is one batched matmul on the MXU (see
    `_square`).

    Runs to the fixpoint, not a fixed count: path lengths double each
    round, so convergence takes ~log2(graph diameter) rounds — for real
    histories the diameter tracks ops-per-key, far below T, which makes
    the early exit worth ~1.5x on the 5k-txn benchmark (the any()
    reduction per round is noise next to the matmul). `steps` stays the
    adversarial upper bound.

    Returns (closure, rounds): the round counter is the ACTUAL number
    of squarings executed before the fixpoint — closure_rounds_device
    reads it back so the bench's measured MFU can never drift from
    what this kernel really does."""
    eye = jnp.eye(m.shape[-1], dtype=bool)
    m = m | eye

    def cond(carry):
        _, changed, i = carry
        return changed & (i < steps)

    def body(carry):
        m, _, i = carry
        m2 = _square(m, constrain)
        return m2, jnp.any(m2 != m), i + 1

    m, _, i = jax.lax.while_loop(
        cond, body, (m, jnp.bool_(True), jnp.int32(0)))
    return m, i


#: The closure's one formulation, as the costdb records and the
#: planner's mode key name it (an on-disk field: older files carry the
#: same string).
CLOSURE_FORMULATION = "xla-int8"


def _square(m, constrain):
    """ONE boolean matrix squaring — the loop body shared by
    `_closure_batched` and `_closure_batched_stats`, so the stats
    closure is bit-identical to the production one by construction
    (the telemetry variant only adds bookkeeping around it).

    The matmul is int8×int8→int32: exact for a boolean closure
    (non-negative 0/1 terms, and int32 accumulation cannot overflow
    below T=2^31), and the MXU's int8 path has twice the bf16 rate on
    v5e (394 TOPS against 197 TFLOPS)."""
    mb = constrain(m.astype(jnp.int8))
    m2 = jax.lax.dot_general(
        mb, mb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32) > 0
    return constrain(m2)


def _closure_batched_stats(m: jnp.ndarray, steps: int, constrain):
    """`_closure_batched` with per-HISTORY search telemetry: the same
    squaring loop (same `_square` body, same batch-level fixpoint
    exit, so the returned closure — and every flag derived from it —
    is bit-identical to the stats-off kernel), additionally tracking
    for each history the round its own matrix reached fixpoint and the
    first round at which a cycle (an off-diagonal mutual-reachability
    pair) became visible. Returns (closure, rounds [B], cycle_round
    [B]; -1 = no cycle; cycle_round 0 = a cycle already present in
    the raw edge set)."""
    T = m.shape[-1]
    eye = jnp.eye(T, dtype=bool)
    nI = ~eye
    m = m | eye
    B = m.shape[0]

    def has_cycle(mm):
        return jnp.any(mm & jnp.swapaxes(mm, 1, 2) & nI, axis=(1, 2))

    def cond(carry):
        return carry[1] & (carry[2] < steps)

    def body(carry):
        m, _, i, rounds, cyc_round = carry
        m2 = _square(m, constrain)
        changed_h = jnp.any(m2 != m, axis=(1, 2))
        rounds = jnp.where(changed_h, i + 1, rounds)
        cyc_round = jnp.where((cyc_round < 0) & has_cycle(m2), i + 1,
                              cyc_round)
        return m2, jnp.any(changed_h), i + 1, rounds, cyc_round

    cyc0 = jnp.where(has_cycle(m), jnp.int32(0), jnp.int32(-1))
    m, _, _, rounds, cyc_round = jax.lax.while_loop(
        cond, body, (m, jnp.bool_(True), jnp.int32(0),
                     jnp.zeros((B,), jnp.int32), cyc0))
    return m, rounds, cyc_round


def _graph_stats(edge_counts, rt_cnt, proc_cnt, c_full, rounds,
                 cyc_round, nI) -> jnp.ndarray:
    """Assemble the [B, N_STATS] stat rows from the full closure: SCC
    shape via mutual reachability (i and j share an SCC iff each
    reaches the other — the closure is reflexive, so the diagonal is
    excluded with nI), plus the edge counts and round telemetry
    gathered along the way. The SCC representative trick: the
    first-True index of `mutual[i, :]` is the SCC's minimum member, so
    counting rows that are their own argmax counts distinct SCCs."""
    T = c_full.shape[-1]
    mutual = c_full & jnp.swapaxes(c_full, 1, 2)       # [B,T,T]
    on_cycle = jnp.any(mutual & nI, axis=2)            # [B,T]
    scc_size = jnp.sum(mutual, axis=2).astype(jnp.int32)
    cycle_txns = jnp.sum(on_cycle, axis=1).astype(jnp.int32)
    scc_max = jnp.max(jnp.where(on_cycle, scc_size, 0), axis=1)
    scc_min = jnp.min(jnp.where(on_cycle, scc_size, T + 1), axis=1)
    scc_min = jnp.where(cycle_txns > 0, scc_min, 0)
    rep = on_cycle & (jnp.argmax(mutual, axis=2)
                      == jnp.arange(T, dtype=jnp.int32)[None, :])
    scc_count = jnp.sum(rep, axis=1).astype(jnp.int32)
    margin = jnp.where(cyc_round >= 0, cyc_round, rounds)
    return jnp.stack(
        [edge_counts[:, 0], edge_counts[:, 1], edge_counts[:, 2],
         rt_cnt, proc_cnt, rounds, cyc_round, scc_count, scc_max,
         scc_min, cycle_txns, margin], axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_keys", "max_pos",
                                             "n_txns", "steps"))
def closure_rounds_device(appends, reads, *, n_keys: int, max_pos: int,
                          n_txns: int, steps: int) -> jnp.ndarray:
    """How many squaring rounds the detect closure ACTUALLY executes on
    this batch before the fixpoint — the measured input to the bench's
    MFU number, replacing the old assumed-rounds model. Runs the SAME
    _closure_batched loop as production and reads back its round
    counter; one extra dispatch of the detect-mode work, bench-only."""
    edges = jax.vmap(functools.partial(
        _edges_one, n_keys=n_keys, max_pos=max_pos, n_txns=n_txns))
    ww, wr, rw = edges(appends, reads)
    _, i = _closure_batched(ww | wr | rw, steps, _identity)
    return i


# NOTE: an iterated-peeling cycle test (live = adj·live > 0 to fixpoint,
# O(diameter·T²) matvecs instead of O(log T) T³ matmuls) was prototyped
# for detect mode but showed no robust end-to-end win on 5k-txn
# histories: wr/rw edges chain across keys, so real dependency graphs
# have diameters in the hundreds, and peeling's linear dependence on
# diameter cancels its cheaper rounds against the closure's logarithmic
# round count. Keep the fixpoint closure for both modes.


def check_batched_impl(appends, reads, invoke_index, complete_index, process,
                       n_live, *, n_keys: int, max_pos: int, n_txns: int,
                       steps: int, classify: bool, realtime: bool,
                       process_order: bool, constrain,
                       fused: bool = True,
                       with_stats: bool = False):
    """THE cycle-check kernel: packed [B,...] tensors -> [B] int32 flag
    words. `n_live` is the per-history real txn count ([B]); rows beyond
    it are excluded from realtime/process edges. With `with_stats`
    (JEPSEN_TPU_KERNEL_STATS) the return is `(flags, stats)` where
    stats is the [B, N_STATS] int32 search-telemetry matrix — the
    flags themselves are bit-identical either way."""
    edges = jax.vmap(functools.partial(
        _edges_one, n_keys=n_keys, max_pos=max_pos, n_txns=n_txns,
        with_counts=with_stats))
    if with_stats:
        ww, wr, rw, counts = edges(appends, reads)
    else:
        ww, wr, rw = edges(appends, reads)
        counts = None
    return classify_matrices_impl(
        ww, wr, rw, invoke_index, complete_index, process, n_live,
        steps=steps, classify=classify, realtime=realtime,
        process_order=process_order, constrain=constrain, fused=fused,
        with_stats=with_stats, edge_counts=counts)


def _flags_from_closures(ww, wr, rw, c_ww, c_wwr, c_full, cycle,
                         nI) -> jnp.ndarray:
    """Anomaly flag words from the three edge classes and their three
    (nested) closures — the one classification formula, shared by the
    fused and unfused classify paths so their verdicts can't drift."""
    cT_wwr = jnp.swapaxes(c_wwr, 1, 2)
    g0 = jnp.any(ww & jnp.swapaxes(c_ww, 1, 2) & nI, axis=(1, 2))
    g1c = jnp.any(wr & cT_wwr, axis=(1, 2))
    g_single = jnp.any(rw & cT_wwr, axis=(1, 2))
    g2 = jnp.any(rw & jnp.swapaxes(c_full, 1, 2) & ~cT_wwr, axis=(1, 2))
    cycle = cycle | g0 | g1c | g_single | g2
    return (g0.astype(jnp.int32) << G0) \
        | (g1c.astype(jnp.int32) << G1C) \
        | (g_single.astype(jnp.int32) << G_SINGLE) \
        | (g2.astype(jnp.int32) << G2_ITEM) \
        | (cycle.astype(jnp.int32) << CYCLE)


def classify_matrices_impl(ww, wr, rw, invoke_index, complete_index, process,
                           n_live, *, steps: int, classify: bool,
                           realtime: bool, process_order: bool,
                           constrain, fused: bool = True,
                           with_stats: bool = False,
                           edge_counts=None):
    """Closure + anomaly classification over explicit [B,T,T] boolean edge
    matrices. Entry point for checkers (rw-register) whose edge
    construction happens host-side from inferred version graphs rather
    than from per-key position chains.

    With `with_stats`, returns `(flags, stats)` — see STAT_FIELDS. The
    WIDEST closure of whichever strategy runs (the from-scratch full
    closure in detect/fused mode; the final seeded stage of the
    unfused chain) supplies the round/margin telemetry, and
    `edge_counts` ([B,3], from `_edges_one(with_counts=True)`) the
    pre-shortcut ww/wr/rw counts; None (this host-built-matrix entry
    point) counts the RAW incoming matrices instead — host builders
    emit no shortcut edges, so the counts match their edge lists."""
    T = ww.shape[-1]
    nI = ~jnp.eye(T, dtype=bool)
    live = jnp.arange(T)[None, :] < n_live[:, None]          # [B,T]
    live2 = live[:, :, None] & live[:, None, :]              # [B,T,T]

    if with_stats and edge_counts is None:
        edge_counts = jnp.stack(
            [jnp.sum(ww, axis=(1, 2)), jnp.sum(wr, axis=(1, 2)),
             jnp.sum(rw, axis=(1, 2))], axis=-1).astype(jnp.int32)
    rt_cnt = proc_cnt = None
    if with_stats:
        B = ww.shape[0]
        rt_cnt = jnp.zeros((B,), jnp.int32)
        proc_cnt = jnp.zeros((B,), jnp.int32)

    if process_order:
        # Consecutive txns of one process in completion order: link row i
        # to the same-process row with the smallest completion index
        # greater than i's.
        same = (process[:, :, None] == process[:, None, :]) \
            & (process[:, :, None] >= 0)
        later = complete_index[:, None, :] > complete_index[:, :, None]
        cand = same & later & live2
        big = jnp.where(cand, complete_index[:, None, :],
                        jnp.iinfo(complete_index.dtype).max)
        nxt = jnp.min(big, axis=2, keepdims=True)
        proc_add = cand & (big == nxt)
        if with_stats:
            proc_cnt = jnp.sum(proc_add, axis=(1, 2)).astype(jnp.int32)
        ww = ww | proc_add
    if realtime:
        # j completed before i invoked => j precedes i in real time.
        # Indeterminate txns carry NEVER_COMPLETED and emit no rt edges.
        rt = complete_index[:, :, None] < invoke_index[:, None, :]
        rt_add = rt & live2 & nI
        if with_stats:
            rt_cnt = jnp.sum(rt_add, axis=(1, 2)).astype(jnp.int32)
        ww = ww | rt_add

    def closure(m):
        """The widest closure + its telemetry: the stats variant runs
        the SAME loop body, so the matrix (and every flag below) is
        bit-identical with the gate on or off."""
        if with_stats:
            return _closure_batched_stats(m, steps, constrain)
        c, _ = _closure_batched(m, steps, constrain)
        return c, None, None

    def result(flags, c_full, rounds, cyc_round):
        if not with_stats:
            return flags
        return flags, _graph_stats(edge_counts, rt_cnt, proc_cnt,
                                   c_full, rounds, cyc_round, nI)

    wwr = ww | wr
    full = wwr | rw
    if not classify:
        c_full, rounds, cyc_round = closure(full)
        cycle = jnp.any(full & jnp.swapaxes(c_full, 1, 2) & nI,
                        axis=(1, 2))
        return result(cycle.astype(jnp.int32) << CYCLE, c_full,
                      rounds, cyc_round)
    if fused:
        # Fused detect/classify (Elle's own design point: classification
        # falls out of the same graph detection walks): run the detect
        # closure first, and gate the classification closures behind a
        # lax.cond on "any history in this batch is cyclic". The common
        # all-valid batch pays exactly the detect cost — one closure —
        # while a batch with positives runs the per-class closures
        # REUSING the already-computed full closure for the cycle and
        # G2-item tests. Exact, because every per-class witness edge
        # implies a cycle in the full graph (each edge class is a
        # subset of `full` and each per-class closure a subset of
        # c_full), so a batch where the detect test fires nowhere can
        # only classify to zero flags.
        c_full, rounds, cyc_round = closure(full)
        cycle = jnp.any(full & jnp.swapaxes(c_full, 1, 2) & nI,
                        axis=(1, 2))

        def _classify(ops):
            ww_, wr_, rw_, c_full_, cycle_ = ops
            c_ww, _ = _closure_batched(ww_, steps, constrain)
            c_wwr, _ = _closure_batched(c_ww | wr_, steps, constrain)
            return _flags_from_closures(ww_, wr_, rw_, c_ww, c_wwr,
                                        c_full_, cycle_, nI)

        def _clean(ops):
            return ops[4].astype(jnp.int32) << CYCLE

        flags = jax.lax.cond(jnp.any(cycle), _classify, _clean,
                             (ww, wr, rw, c_full, cycle))
        return result(flags, c_full, rounds, cyc_round)
    # Unfused baseline (JEPSEN_TPU_FUSED_CLASSIFY=0): chained warm
    # starts — closure(A|B) == closure(closure(A)|B), so seeding each
    # wider closure with the previous result is exact and each seeded
    # closure converges in the few rounds its NEW edge class adds,
    # instead of re-walking the whole graph three times.
    c_ww, _ = _closure_batched(ww, steps, constrain)
    c_wwr, _ = _closure_batched(c_ww | wr, steps, constrain)
    c_full, rounds, cyc_round = closure(c_wwr | rw)
    cycle = jnp.any(full & jnp.swapaxes(c_full, 1, 2) & nI, axis=(1, 2))
    return result(
        _flags_from_closures(ww, wr, rw, c_ww, c_wwr, c_full, cycle,
                             nI), c_full, rounds, cyc_round)


def _identity(x):
    return x


@functools.partial(jax.jit, static_argnames=(
    "n_keys", "max_pos", "n_txns", "steps", "classify", "realtime",
    "process_order", "fused", "with_stats"))
def check_batch_device(appends, reads, invoke_index, complete_index, process,
                       n_live, *, n_keys: int, max_pos: int, n_txns: int,
                       steps: int, classify: bool = True,
                       realtime: bool = False,
                       process_order: bool = False,
                       fused: bool = True,
                       with_stats: bool = False):
    """Single-device jitted entry over a packed batch: [B] int32 flags
    (plus the [B, N_STATS] stats matrix under with_stats)."""
    return check_batched_impl(
        appends, reads, invoke_index, complete_index, process, n_live,
        n_keys=n_keys, max_pos=max_pos, n_txns=n_txns, steps=steps,
        classify=classify, realtime=realtime, process_order=process_order,
        constrain=_identity, fused=fused, with_stats=with_stats)


def edge_matrices(edges, n_txns: int):
    """The three [B,T,T] bool class matrices (ww, wr, rw) of packed edge
    rows: `edges` is [B,E,3] int32 (src, dst, cls) with cls in
    {graph.WW, WR, RW}. One scatter per history, vmapped over B so a
    dp-sharded batch builds each history's matrices where it lives; a
    row with any index out of range (the pack pads with T) lands
    nowhere, and a repeated edge sets its cell once."""
    def one(e):
        m = jnp.zeros((3, n_txns, n_txns), bool)
        return m.at[e[:, 2], e[:, 0], e[:, 1]].set(True, mode="drop")
    m = jax.vmap(one)(edges)
    return m[:, 0], m[:, 1], m[:, 2]


def _class_matrices(arrays: tuple):
    """(ww, wr, rw) and the timing tensors of `classify_matrices_device`'s
    arguments, told apart by their count (static at trace time): edge
    rows are built into matrices, T from the [B,T] timing tensors."""
    if len(arrays) == 5:
        edges, *timing = arrays
        return edge_matrices(edges, timing[0].shape[-1]), timing
    return arrays[:3], arrays[3:]


@functools.partial(jax.jit, static_argnames=(
    "steps", "classify", "realtime", "process_order", "fused",
    "with_stats"))
def classify_matrices_device(*arrays, steps: int,
                             classify: bool = True, realtime: bool = False,
                             process_order: bool = False,
                             fused: bool = True,
                             with_stats: bool = False):
    """Jitted single-device entry. `arrays` is (edges, invoke_index,
    complete_index, process, n_live), `edges` the [B,E,3] packed rows
    whose [B,T,T] matrices this same executable builds; or (ww, wr, rw,
    invoke_index, complete_index, process, n_live) over matrices
    already built."""
    mats, timing = _class_matrices(arrays)
    return classify_matrices_impl(
        *mats, *timing,
        steps=steps, classify=classify, realtime=realtime,
        process_order=process_order, constrain=_identity, fused=fused,
        with_stats=with_stats)


def edge_pad(n_edges: int) -> int:
    """Edge rows a bucket pads to: the next power of two, at least 1024,
    so a sweep's buckets share few edge geometries (and executables)."""
    return max(1024, 1 << (max(n_edges, 1) - 1).bit_length())


def pack_edge_lists(per_history: list[dict], multiple: int = 128) -> dict:
    """Pack host-built sparse edges into one [B,E,3] int32 array of
    (src, dst, cls) rows for `edge_matrices`, plus the timing tensors.

    per_history: dicts with keys n (txn count), edges ((src, dst, cls)
    rows with cls in {graph.WW, WR, RW}), invoke_index,
    complete_index, process (np arrays of length n). Only the three
    dependency classes are accepted: realtime/process edges are built
    in-kernel from the timing tensors (passing them here would
    double-count them against the kernel's flags). Self-loops are
    dropped; pad rows hold T in every column, so they land nowhere."""
    B = len(per_history)
    T = pad_to(max((h["n"] for h in per_history), default=1), multiple)
    rows = []
    for hist in per_history:
        # fromiter over the flattened rows: about twice as fast as
        # np.asarray on a list of tuples
        e = np.fromiter(itertools.chain.from_iterable(hist["edges"]),
                        np.int32, 3 * len(hist["edges"])).reshape(-1, 3)
        rows.append(e[e[:, 0] != e[:, 1]])
    E = edge_pad(max((len(e) for e in rows), default=0))
    edges = np.full((B, E, 3), T, np.int32)
    invoke_idx = np.zeros((B, T), np.int64)
    complete_idx = np.zeros((B, T), np.int64)
    process = np.full((B, T), -1, np.int32)
    n_live = np.zeros((B,), np.int32)
    for i, (hist, e) in enumerate(zip(per_history, rows)):
        n = hist["n"]
        n_live[i] = n
        edges[i, :len(e)] = e
        invoke_idx[i, :n] = hist["invoke_index"]
        complete_idx[i, :n] = hist["complete_index"]
        process[i, :n] = hist["process"]
    return {"edges": edges, "invoke_index": invoke_idx,
            "complete_index": complete_idx, "process": process,
            "n_txns": n_live, "T": T, "E": E}


def check_edge_batch(per_history: list[dict], realtime: bool = False,
                     process_order: bool = False,
                     classify: bool = True, devices=None,
                     fused: bool | None = None,
                     stats_out: list | None = None) -> list[dict]:
    """Device cycle check over host-built edge lists: per-history
    {anomaly-name: True} dicts (the rw-register device path, and the
    per-SCC classify stage of the condensed long-history path).

    With several devices the batch axis shards over a 1-D dp mesh,
    ragged batches padded by replicating the last entry.

    `stats_out` (a list) is EXTENDED with one `stats_row` dict per
    input history when given — the kernel then also computes the
    search-telemetry matrix (same flags either way)."""
    if not per_history:
        return []
    n = len(per_history)
    devices = devices if devices is not None else default_devices()
    per_history = pad_to_multiple(per_history, len(devices))
    tr = trace.get_current()
    edges = sum(len(h["edges"]) for h in per_history)
    with tr.phase_span("edge_pack", B=len(per_history),
                       edges=edges) as span:
        p = pack_edge_lists(per_history)
        span.note(T=p["T"])
    tr.counter("wr_edges_packed").inc(edges)
    names = ("edges", "invoke_index", "complete_index", "process",
             "n_txns")
    h2d_bytes = sum(p[k].nbytes for k in names)
    tr.counter("wr_h2d_bytes").inc(h2d_bytes)
    # device_put straight from numpy: going through jnp.asarray first
    # would commit each array whole onto device 0 before the dp
    # sharding ever applied.
    with tr.phase_span("h2d", B=len(per_history), T=p["T"], E=p["E"],
                       bytes=h2d_bytes):
        if len(devices) > 1:
            mesh = jax.sharding.Mesh(np.asarray(devices), ("dp",))
            sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("dp"))
            args = [jax.device_put(p[k], sharding) for k in names]
        else:
            args = [jax.device_put(p[k], devices[0] if devices else None)
                    for k in names]
    if fused is None:
        fused = fused_classify_enabled()
    with_stats = stats_out is not None
    with tr.phase_span("dispatch", B=len(per_history), T=p["T"]):
        out = classify_matrices_device(
            *args, steps=closure_steps(p["T"]), classify=classify,
            realtime=realtime, process_order=process_order, fused=fused,
            with_stats=with_stats)
    tr.counter("buckets_dispatched").inc()
    flags, dev_stats = out if with_stats else (out, None)
    # the np.asarray below is an implicit device wait: bound it with
    # the dispatch watchdog so a wedged device can't hang the wr sweep
    # (JEPSEN_TPU_DISPATCH_TIMEOUT_S; no-op when the gate is off)
    from ...parallel import _block_flags
    with tr.phase_span("collect"):
        words = np.asarray(_block_flags(flags, tr))[:n]
        if with_stats:
            rows = np.asarray(dev_stats)[:n]
            stats_out.extend(
                stats_row(rows[i], n_txns=per_history[i]["n"],
                          t_pad=p["T"]) for i in range(n))
    tr.counter("buckets_resolved").inc()
    return [flags_to_names(int(w)) for w in words]


def check_edge_batch_bucketed(per_history: list[dict],
                              realtime: bool = False,
                              process_order: bool = False,
                              classify: bool = True, devices=None,
                              budget_cells: int = 1 << 27,
                              fused: bool | None = None,
                              stats_out: list | None = None) -> list[dict]:
    """check_edge_batch with device-memory-aware length bucketing: the
    matrices the device builds are B·T_pad² cells × 3 edge classes, so one
    unbucketed dispatch over a big store would blow HBM. Reuses
    parallel.bucket_by_length (including its dp-padding headroom —
    check_edge_batch replicates the last entry up to a device
    multiple); results return in input order, and `stats_out` (when
    given) is extended with per-history stats dicts in the SAME
    order."""
    if not per_history:
        return []
    from ...parallel import bucket_by_length
    dp = (len(devices) if devices is not None
          else len(default_devices()))
    out: list[dict | None] = [None] * len(per_history)
    sout: list = [None] * len(per_history)
    for bucket in bucket_by_length(per_history,
                                   budget_cells=budget_cells,
                                   dp=max(1, dp)):
        bstats: list | None = [] if stats_out is not None else None
        res = check_edge_batch([per_history[j] for j in bucket],
                               realtime=realtime,
                               process_order=process_order,
                               classify=classify, devices=devices,
                               fused=fused, stats_out=bstats)
        for i, (j, r) in enumerate(zip(bucket, res)):
            out[j] = r
            if bstats is not None:
                sout[j] = bstats[i]
    if stats_out is not None:
        stats_out.extend(sout)
    return out  # type: ignore[return-value]


def flags_to_names(word: int) -> dict:
    """Anomaly names for a flag word. In detect-only mode (classify=False)
    no classify bits exist, so a set CYCLE bit reports as a generic
    "cycle" anomaly rather than vanishing."""
    out = {name: True for bit, name in FLAG_NAMES.items()
           if word & (1 << bit)}
    if not out and word & (1 << CYCLE):
        out["cycle"] = True
    return out


def check_encoded_batch(encs: list[EncodedHistory],
                        realtime: bool = False,
                        process_order: bool = False,
                        classify: bool = True,
                        devices=None,
                        stats_out: list | None = None) -> list[dict]:
    """Check a batch of encoded histories on device; returns per-history
    dicts {anomaly-name: True} for the cycle anomalies.

    With several addressable devices the batch axis is sharded across a
    1-D mesh — the analysis data plane (SURVEY.md §5.8). Ragged batches
    are padded to a device multiple by replicating the last history (the
    extra results are dropped), so sharding never silently degrades to
    one device."""
    if not encs:
        return []
    n = len(encs)
    devices = devices if devices is not None else default_devices()
    encs = pad_to_multiple(encs, len(devices))
    batch = pack_batch(encs)
    shape: BatchShape = batch["shape"]
    names = ("appends", "reads", "invoke_index", "complete_index",
             "process", "n_txns")
    args = [jnp.asarray(batch[k]) for k in names]

    if len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("dp",))
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("dp"))
        args = [jax.device_put(a, sharding) for a in args]

    with_stats = stats_out is not None
    out = check_batch_device(
        *args, n_keys=shape.n_keys, max_pos=shape.max_pos,
        n_txns=shape.n_txns, steps=closure_steps(shape.n_txns),
        classify=classify, realtime=realtime, process_order=process_order,
        fused=fused_classify_enabled(), with_stats=with_stats)
    flags, dev_stats = out if with_stats else (out, None)
    if with_stats:
        rows = np.asarray(dev_stats)[:n]
        stats_out.extend(
            stats_row(rows[i], n_txns=encs[i].n, t_pad=shape.n_txns)
            for i in range(n))
    return [flags_to_names(int(w)) for w in np.asarray(flags)[:n]]
