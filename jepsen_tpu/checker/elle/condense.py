"""Host-condensed checking for very long histories (100k+ ops).

The dense [T,T] closure kernel caps around ~32k txns per slice (HBM
holds T² cells per matrix). This module is the scale path behind it —
the reason the reference decomposes histories at all
(jepsen/src/jepsen/independent.clj:1-7; SURVEY.md §5.7) — built on one
graph fact:

    every dependency cycle lies inside one strongly-connected component
    of the FULL dependency graph, and so does every path between two
    members of an SCC (any intermediate node closes a cycle through the
    SCC and is therefore a member).

Hence each anomaly query is *exactly* answerable inside its SCC: the
offending edge plus its return path form a cycle, and the "not in
ww∪wr closure" side condition of G2-item also restricts losslessly,
because any ww∪wr return path between SCC members is SCC-internal.

Pipeline:
  1. vectorized numpy edge build (searchsorted writer lookup — no
     Python per-op loops),
  2. native C++ Tarjan over CSR arrays (realtime order sparsified to
     O(T) via a completion-rank aux chain),
  3. valid histories (no nontrivial SCC — the common case) finish here
     in milliseconds with zero device work,
  4. anomalous histories ship their (small) SCC subgraphs to the
     batched MXU classification kernel, flags OR-ed across SCCs.

This mirrors how Elle itself leans on Tarjan-over-bifurcan for the
search (SURVEY.md §2.3) while keeping classification on device.
"""

from __future__ import annotations

import numpy as np

from ... import native_lib, trace
from .encode import EncodedHistory, effective_complete_index
from . import graph as G


def _append_lookup(enc: EncodedHistory):
    """Writer lookup (key, pos) -> txn row via sorted-id binary search.

    Returns a callable look(keys, positions) -> txn rows (or -1); only
    live appends (pos >= 1) participate, matching graph.build_edges."""
    a = np.asarray(enc.appends, np.int64).reshape(-1, 3)
    P2 = enc.max_pos + 2
    live = a[:, 2] >= 1
    ids = a[live, 1] * P2 + a[live, 2]
    txns = a[live, 0]
    order = np.argsort(ids)
    sids, stx = ids[order], txns[order]

    def look(keys: np.ndarray, positions: np.ndarray) -> np.ndarray:
        if len(sids) == 0 or len(keys) == 0:
            return np.full(len(keys), -1, np.int64)
        q = keys.astype(np.int64) * P2 + positions.astype(np.int64)
        i = np.minimum(np.searchsorted(sids, q), len(sids) - 1)
        return np.where(sids[i] == q, stx[i], -1)

    return look


def build_edges_arrays(enc: EncodedHistory, process_order: bool = False
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized (src, dst, cls) edge arrays — the numpy counterpart of
    graph.build_edges (same ww/wr/rw semantics, graph.py:31-64), minus
    realtime (see rt_aux_edges)."""
    a = np.asarray(enc.appends, np.int64).reshape(-1, 3)
    r = np.asarray(enc.reads, np.int64).reshape(-1, 3)
    look = _append_lookup(enc)
    srcs, dsts, clss = [], [], []

    def emit(src, dst, cls):
        keep = (src >= 0) & (dst >= 0) & (src != dst)
        srcs.append(src[keep])
        dsts.append(dst[keep])
        clss.append(np.full(int(keep.sum()), cls, np.int32))

    m = a[:, 2] >= 2                      # ww: writer(pos-1) -> writer(pos)
    emit(look(a[m, 1], a[m, 2] - 1), a[m, 0], G.WW)
    m = r[:, 2] >= 1                      # wr: writer(pos) -> reader
    emit(look(r[m, 1], r[m, 2]), r[m, 0], G.WR)
    m = r[:, 2] >= 0                      # rw: reader -> writer(pos+1)
    emit(r[m, 0], look(r[m, 1], r[m, 2] + 1), G.RW)

    if process_order and enc.n:
        eff = effective_complete_index(enc.status, enc.complete_index)
        pr = np.asarray(enc.process, np.int64)
        rows = np.arange(enc.n, dtype=np.int64)[pr >= 0]
        order = rows[np.lexsort((eff[pr >= 0], pr[pr >= 0]))]
        if len(order) > 1:
            src, dst = order[:-1], order[1:]
            same = pr[src] == pr[dst]
            emit(src[same], dst[same], G.PROC)

    if not srcs:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0, np.int32)
    return (np.concatenate(srcs), np.concatenate(dsts),
            np.concatenate(clss))


def aux_chain(eff: np.ndarray, inv: np.ndarray
              ) -> tuple[np.ndarray, np.ndarray]:
    """Completion-rank aux chain over (complete, invoke) index arrays:
    the O(n) sparsification of the realtime order.

    Aux node n+k means "after the k-th completion (in completion
    order)". Edges: txn j -> aux rank(j); aux_k -> aux_{k+1}; and
    aux_{k_i} -> txn i where k_i is the last completion rank strictly
    before i's invocation. Reachability j -> i through aux nodes is
    then exactly complete(j) < invoke(i). Returns (src, dst); callers
    add n aux node ids on top of their own node space."""
    n = len(eff)
    order = np.argsort(eff, kind="stable")
    sorted_eff = eff[order]
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    aux = n + np.arange(n, dtype=np.int64)

    srcs = [np.arange(n, dtype=np.int64), aux[:-1]]
    dsts = [aux[rank], aux[1:]]
    k = np.searchsorted(sorted_eff, inv) - 1   # last completion < invoke
    has = k >= 0
    srcs.append(aux[k[has]])
    dsts.append(np.arange(n, dtype=np.int64)[has])
    return np.concatenate(srcs), np.concatenate(dsts)


def rt_aux_edges(enc: EncodedHistory
                 ) -> tuple[np.ndarray, np.ndarray, int]:
    """aux_chain over a whole encoded history. Returns (src, dst,
    n_aux)."""
    n = enc.n
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 0
    eff = effective_complete_index(enc.status, enc.complete_index)
    inv = np.asarray(enc.invoke_index, np.int64)
    src, dst = aux_chain(eff, inv)
    return src, dst, n


def _scc_csr(n_nodes: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """SCC ids from edge arrays: CSR build in numpy, native Tarjan, and
    a pure-Python fallback when no toolchain exists."""
    order = np.argsort(src, kind="stable")
    col = dst[order]
    row_ptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(src.astype(np.int64), minlength=n_nodes),
              out=row_ptr[1:])
    out = native_lib.tarjan_scc_csr(n_nodes, row_ptr, col)
    if out is not None:
        return out
    adj: list[list[int]] = [[] for _ in range(n_nodes)]
    for s, d in zip(src.tolist(), dst.tolist()):
        adj[s].append(d)
    return np.asarray(G._tarjan_scc_py(n_nodes, adj), np.int64)


def condense(enc: EncodedHistory, realtime: bool = False,
             process_order: bool = False) -> tuple[list[np.ndarray], tuple]:
    """Nontrivial SCCs (>= 2 txn rows each) of the full dependency
    graph. Returns (member-row arrays, cached (src, dst, cls) edges)."""
    src, dst, cls = build_edges_arrays(enc, process_order=process_order)
    if realtime:
        rs, rd, _ = rt_aux_edges(enc)
        all_src = np.concatenate([src, rs])
        all_dst = np.concatenate([dst, rd])
        n_nodes = 2 * enc.n
    else:
        all_src, all_dst = src, dst
        n_nodes = enc.n
    if len(all_src) == 0 or enc.n == 0:
        return [], (src, dst, cls)
    scc = _scc_csr(n_nodes, all_src, all_dst)[: enc.n]
    counts = np.bincount(scc)
    big = np.flatnonzero(counts >= 2)
    members = [np.flatnonzero(scc == b) for b in big]
    return members, (src, dst, cls)


# An SCC bigger than this goes to the host classifier instead of the
# dense device kernel (whose [T,T] matrices are what the condensation
# exists to avoid at full history size).
DEVICE_SCC_LIMIT = 8_192


def _classify_scc_host(enc: EncodedHistory, rows: np.ndarray,
                       src, dst, cls, keep, local,
                       realtime: bool) -> dict:
    """Host classification of one oversized SCC: graph.classify_cycles
    over the subgraph, with the realtime order carried by a
    member-local completion-rank aux chain (exact rt reachability, no
    dense [m,m] relation)."""
    m = len(rows)
    edges = list(zip(local[src[keep]].tolist(), local[dst[keep]].tolist(),
                     cls[keep].tolist()))
    n_nodes = m
    if realtime:
        eff = effective_complete_index(enc.status, enc.complete_index)[rows]
        inv = np.asarray(enc.invoke_index, np.int64)[rows]
        asrc, adst = aux_chain(eff, inv)   # member-local rt chain
        edges += [(int(s), int(d), G.RT) for s, d in zip(asrc, adst)]
        n_nodes = 2 * m
    res = G.classify_cycles(n_nodes, edges, want_witnesses=False)
    return {name: True for name in res}


def condensed_stats(enc: EncodedHistory, members, src, dst, cls,
                    realtime: bool) -> dict:
    """The host-side search-stats record for a condensed check — the
    long-history sibling of `kernels.stats_row`. Edge and SCC facts
    are exact (the condensation computed them anyway: distinct edges
    per class, nontrivial SCC count/shape from the native Tarjan, and
    the realtime-edge count via one searchsorted over the completion
    ranks rather than the O(n²) dense relation); closure-round/margin
    telemetry is -1 — no dense closure ran on this path, and an
    invented number would poison the planner's training data."""
    from . import graph as G2
    from . import kernels as K
    if len(src):
        distinct = np.unique(
            np.stack([src, dst, cls.astype(np.int64)], axis=1), axis=0)
        counts = np.bincount(distinct[:, 2], minlength=4)
    else:
        counts = np.zeros(4, np.int64)
    rt = 0
    if realtime and enc.n:
        eff = effective_complete_index(enc.status, enc.complete_index)
        inv = np.asarray(enc.invoke_index, np.int64)
        # |{(j, i): complete(j) < invoke(i)}| — a txn's own completion
        # never precedes its invocation, so self-pairs drop out free
        rt = int(np.searchsorted(np.sort(eff), inv, side="left").sum())
    sizes = np.asarray([len(m) for m in members], np.int64)
    has = len(sizes) > 0
    return {
        "ww_edges": int(counts[G2.WW]), "wr_edges": int(counts[G2.WR]),
        "rw_edges": int(counts[G2.RW]), "rt_edges": rt,
        "proc_edges": int(counts[G2.PROC]),
        "closure_rounds": -1,
        "cycle_round": 0 if has else -1,
        "scc_count": int(len(sizes)),
        "scc_max": int(sizes.max()) if has else 0,
        "scc_min": int(sizes.min()) if has else 0,
        "cycle_txns": int(sizes.sum()) if has else 0,
        "margin": -1,
        "n_txns": int(enc.n), "t_pad": int(enc.n),
        "closure_bound": K.closure_steps(max(enc.n, 1)),
        "pad_waste_cells": 0,
        "path": "condensed",
    }


def check_condensed(enc: EncodedHistory, *, classify: bool = True,
                    realtime: bool = False, process_order: bool = False,
                    devices=None,
                    device_scc_limit: int = DEVICE_SCC_LIMIT,
                    stats_out: list | None = None) -> dict:
    """Check ONE long history via SCC condensation. Returns the same
    {anomaly: True} flag dict as the dense device path.

    Valid histories (no nontrivial SCC) cost one numpy edge build plus
    one native Tarjan — no device dispatch at all. Anomalous ones ship
    each SCC subgraph to the batched classification kernel; restriction
    to the SCC is exact (module docstring). SCCs beyond
    `device_scc_limit` rows classify on the host instead (their dense
    [m,m] matrices are the very thing condensation avoids).

    `stats_out` (a list) gains one `condensed_stats` record for the
    history — the JEPSEN_TPU_KERNEL_STATS path.

    Traced: a `condense` phase (args `txns`, `edges`, `sccs`,
    `scc_max`) around the edge build and Tarjan, an `scc_host` phase
    (arg `txns`) around each host-classified SCC, and the counters
    `histories_condensed`, `scc_device` and `scc_host`; the per-SCC
    device classify records its own phases."""
    tr = trace.get_current()
    tr.counter("histories_condensed").inc()
    # made at zero, so a pass with no SCC of a kind reads 0, not nothing
    n_device, n_host = tr.counter("scc_device"), tr.counter("scc_host")
    with tr.phase_span("condense", txns=enc.n) as span:
        members, (src, dst, cls) = condense(enc, realtime=realtime,
                                            process_order=process_order)
        span.note(edges=len(src), sccs=len(members),
                  scc_max=max(map(len, members), default=0))
    if stats_out is not None:
        stats_out.append(condensed_stats(enc, members, src, dst, cls,
                                         realtime))
    if not members:
        return {}
    if not classify:
        return {"cycle": True}

    from . import kernels as K
    eff = effective_complete_index(enc.status, enc.complete_index)
    # One global local-id map + one argsort groups every (same-SCC)
    # edge by SCC id — O(E log E) total, independent of SCC count.
    local = np.full(enc.n, -1, np.int64)
    scc_of = np.full(enc.n, -1, np.int64)
    for b, rows in enumerate(members):
        local[rows] = np.arange(len(rows))
        scc_of[rows] = b
    same_idx = np.flatnonzero((scc_of[src] >= 0) &
                              (scc_of[src] == scc_of[dst]))
    grp = scc_of[src[same_idx]]
    order = np.argsort(grp, kind="stable")
    by_grp = same_idx[order]
    bounds = np.searchsorted(grp[order], np.arange(len(members) + 1))

    flags: dict = {}
    per_scc = []
    for b, rows in enumerate(members):
        keep = by_grp[bounds[b]:bounds[b + 1]]
        if len(rows) > device_scc_limit:
            n_host.inc()
            with tr.phase_span("scc_host", txns=len(rows)):
                flags.update(_classify_scc_host(
                    enc, rows, src, dst, cls, keep, local, realtime))
            continue
        # PROC edges ride along as WW-class on device (same role:
        # cycle-strengthening order edges, kernels.py module doc).
        sub_cls = np.where(cls[keep] == G.PROC, G.WW, cls[keep])
        per_scc.append({
            "n": len(rows),
            "edges": list(zip(local[src[keep]].tolist(),
                              local[dst[keep]].tolist(),
                              sub_cls.tolist())),
            "invoke_index": np.asarray(enc.invoke_index)[rows],
            "complete_index": eff[rows],
            "process": np.asarray(enc.process)[rows],
        })
    if per_scc:
        n_device.inc(len(per_scc))
        # bucketed: many small SCCs padded to the largest one's T would
        # otherwise pack into a single over-budget [B,T,T]x3 dispatch
        # fused=False: every SCC here is cyclic by construction, so the
        # fused kernel's any-cycle cond would always fire and its
        # unseeded full closure would just re-walk what the chained
        # warm starts get for free
        for res in K.check_edge_batch_bucketed(per_scc, classify=True,
                                               realtime=realtime,
                                               process_order=False,
                                               devices=devices,
                                               fused=False):
            flags.update(res)
    return flags
