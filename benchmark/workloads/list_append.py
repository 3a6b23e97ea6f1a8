"""Jepsen list-append histories from a seed, and a plain checker for them.

The generator follows Elle's list-append generator as Jepsen's
`tests/cycle/append.clj` runs it (a few active keys, 1-2 micro-ops per
txn, a key retired after a fixed number of writes, globally unique
values) under a concurrent, serializable execution: every txn applies
at one commit point inside its invoke/complete window. Some txns fail
(they never apply) and some are indeterminate (`:info`; they apply or
not, and their process is replaced by a fresh one, as Jepsen does after
a crash). Every `anomaly_every`-th history carries one G1c between two
concurrent txns that read each other's appends.

Each history's sizes are drawn at random, as Elle's generator draws
them: every txn's outcome, its length (uniform over 1-2 micro-ops), each
micro-op's kind (read or append, even odds) and key (any active key, so
a txn may touch one key twice). They come from the configuration's
`shape_seed` and the history's place in the store, not from the run's
seed, so every seed's store holds the same histories' sizes; the run's
seed draws the schedule, the commit order and so every read's contents,
which txns of unknown outcome applied, and where the G1c falls. The
program compiles one executable per padded batch geometry: sizes that
changed with the seed would recompile the whole store in every run.

`check` is an independent list-append checker (version orders from the
longest read, ww/wr/rw edges, Tarjan SCCs), written from Adya's and
Elle's definitions. It imports nothing of the program under test.
"""

from __future__ import annotations

import heapq
import json
import math
import random
from pathlib import Path

#: the verdict fields compared with the truth and the reference
FIELDS = ("valid?", "anomaly-types")



def shape(cfg: dict, index: int) -> tuple[list, list]:
    """History `index`'s sizes: each txn's outcome and its micro-ops as
    [kind, key] pairs, drawn from the configuration's `shape_seed`.
    Keys rotate as Elle's do: `key_count` active keys, each retired
    after `max_writes_per_key` appends (failed appends count too)."""
    if cfg.get("one_shape"):
        index = 0
    rng = random.Random(f"{cfg['name']}:{cfg['shape_seed']}:{index}")
    p_fail, p_info = cfg["fail_share"], cfg["info_share"]
    lo, hi = cfg["min_txn_length"], cfg["max_txn_length"]
    cap = cfg["max_writes_per_key"]
    active = list(range(cfg["key_count"]))
    writes = dict.fromkeys(active, 0)
    nxt_key = len(active)
    outcomes, txns = [], []
    for _ in range(cfg["txns_per_history"]):
        u = rng.random()
        outcomes.append("fail" if u < p_fail
                        else "info" if u < p_fail + p_info else "ok")
        txn = []
        for _ in range(rng.randint(lo, hi)):
            j = rng.randrange(len(active))
            if rng.random() < 0.5:
                txn.append(["r", active[j]])
                continue
            if writes[active[j]] == cap:
                active[j] = nxt_key
                writes[nxt_key] = 0
                nxt_key += 1
            writes[active[j]] += 1
            txn.append(["append", active[j]])
        txns.append(txn)
    return outcomes, txns


def _schedule(cfg: dict, rng: random.Random, outcomes: list[str]):
    """Invoke/commit/complete times (ns) and process ids, in invoke
    order, for `concurrency` worker threads each running txns back to
    back with a think time between them."""
    conc = cfg["concurrency"]
    lat_med = cfg["assumed"]["latency_median_ms"] * 1e6
    lat_sigma = cfg["assumed"]["latency_sigma"]
    think = cfg["assumed"]["think_mean_ms"] * 1e6
    timeout = cfg["assumed"]["info_timeout_ms"] * 1e6
    free = [(rng.random() * think, s) for s in range(conc)]
    heapq.heapify(free)
    proc = list(range(conc))
    inv, com, cmp_, pid = [], [], [], []
    for out in outcomes:
        t, s = heapq.heappop(free)
        if out == "info":
            dur = timeout
        else:
            dur = lat_med * math.exp(rng.gauss(0.0, lat_sigma))
        inv.append(int(t))
        cmp_.append(int(t + dur))
        pid.append(proc[s])
        commit = t + dur * rng.uniform(0.05, 0.95)
        if out == "fail" or (out == "info" and rng.random() < 0.5):
            commit = None
        com.append(commit)
        if out == "info":
            proc[s] += conc
        heapq.heappush(free, (t + dur + rng.expovariate(1.0 / think), s))
    return inv, com, cmp_, pid


def _mops(txns: list) -> list:
    """[f, key, value] micro-ops, appends numbered 1, 2, ... so every
    value is unique in the history."""
    out, nxt = [], 0
    for txn in txns:
        mops = []
        for f, k in txn:
            if f == "append":
                nxt += 1
                mops.append(["append", k, nxt])
            else:
                mops.append(["r", k, None])
        out.append(mops)
    return out


def _g1c_pair(order, outcomes, mops, inv, cmp_, pid):
    """Two ok txns, each an append then a read, concurrent, of other
    processes, appending to different keys, with no txn committed
    between them touching either key (so the only cycle is theirs) —
    nearest the middle of the commit order."""
    mid = len(order) // 2
    for d in range(len(order)):
        for j in (mid + d, mid - d):
            if not 0 <= j < len(order) - 1:
                continue
            a = order[j]
            if outcomes[a] != "ok" or not _ar(mops[a]):
                continue
            between = set()
            for b in order[j + 1:j + 16]:
                if (outcomes[b] == "ok" and _ar(mops[b])
                        and pid[a] != pid[b] and inv[b] < cmp_[a]
                        and inv[a] < cmp_[b]
                        and mops[a][0][1] != mops[b][0][1]
                        and not {mops[a][0][1], mops[b][0][1]} & between):
                    return a, b
                between.update(k for _f, k, _v in mops[b])
    raise RuntimeError("no concurrent pair to seed a G1c")


def _ar(mops) -> bool:
    return [f for f, _k, _v in mops] == ["append", "r"]


def history(cfg: dict, seed: str, shp: tuple, g1c: bool) -> list[str]:
    """One history of the sizes `shp` (`shape`) as JSON lines."""
    rng = random.Random(seed)
    outcomes, txns = shp
    n = len(outcomes)
    inv, com, cmp_, pid = _schedule(cfg, rng, outcomes)
    mops = _mops(txns)
    order = sorted((i for i in range(n) if com[i] is not None),
                   key=com.__getitem__)
    future = {}
    if g1c:
        a, b = _g1c_pair(order, outcomes, mops, inv, cmp_, pid)
        mops[a][1][1] = mops[b][0][1]      # a reads b's key ...
        mops[b][1][1] = mops[a][0][1]      # ... and b reads a's
        future[a] = mops[b][0][2]          # a sees b's append early
    state: dict = {}
    seen: dict = {}
    for i in order:
        got = []
        for f, k, v in mops[i]:
            lst = state.setdefault(k, [])
            if f == "append":
                lst.append(v)
                got.append(["append", k, v])
            else:
                got.append(["r", k, lst + [future[i]] if i in future
                            else list(lst)])
        seen[i] = got
    # at one instant a completion goes first: a thread's next invocation
    # can share its previous completion's nanosecond
    events = sorted([(inv[i], 1, i) for i in range(n)]
                    + [(cmp_[i], 0, i) for i in range(n)])
    lines = []
    for index, (t, kind, i) in enumerate(events):
        if kind == 1:
            typ, val = "invoke", mops[i]
        elif outcomes[i] == "ok":
            typ, val = "ok", seen[i]
        else:
            typ, val = outcomes[i], mops[i]
        lines.append(f'{{"type":"{typ}","process":{pid[i]},"f":"txn",'
                     f'"value":[{",".join(map(_mop, val))}],'
                     f'"time":{t},"index":{index}}}')
    return lines


def _mop(m) -> str:
    f, k, v = m
    if v is None:
        v = "null"
    elif isinstance(v, list):
        v = f"[{','.join(map(str, v))}]"
    return f'["{f}",{k},{v}]'


def generate(cfg: dict, root: Path, seed: int, count: int,
             first: int = 0) -> dict:
    """Write `count` run dirs `root/run-<i>/history.jsonl` and return
    the seeded truth: {run name: {"valid?", "anomaly-types"}}."""
    truth = {}
    every = cfg["anomaly_every"]
    for i in range(first, first + count):
        bad = i % every == every - 1
        d = root / f"run-{i:05d}"
        d.mkdir(parents=True)
        shp = shape(cfg, i)
        lines = history(cfg, f"{seed}:{i}", shp, bad)
        (d / "history.jsonl").write_text("\n".join(lines) + "\n")
        truth[d.name] = {"valid?": not bad,
                         "anomaly-types": ["G1c"] if bad else [],
                         "txns": sum(o != "fail" for o in shp[0])}
    return truth


# -- the plain checker ---------------------------------------------------


def _txns(path: Path) -> list[dict]:
    """Pair each completion with its process's open invocation."""
    txns, open_ = [], {}
    with open(path) as f:
        for line in f:
            op = json.loads(line)
            if op["type"] == "invoke":
                open_[op["process"]] = op
            else:
                open_.pop(op["process"], None)
                txns.append({"status": op["type"], "mops": op["value"]})
    return txns


def _sccs(n: int, adj: list[list[int]]) -> list[int]:
    """Tarjan's strongly connected components, iteratively: comp[v]."""
    index = [-1] * n
    low = [0] * n
    on = [False] * n
    comp = [-1] * n
    stack: list[int] = []
    counter = ncomp = 0
    for root in range(n):
        if index[root] != -1:
            continue
        work = [(root, 0)]
        while work:
            v, i = work.pop()
            if i == 0:
                index[v] = low[v] = counter
                counter += 1
                stack.append(v)
                on[v] = True
            recurse = False
            while i < len(adj[v]):
                w = adj[v][i]
                i += 1
                if index[w] == -1:
                    work.append((v, i))
                    work.append((w, 0))
                    recurse = True
                    break
                if on[w]:
                    low[v] = min(low[v], index[w])
            if recurse:
                continue
            if low[v] == index[v]:
                while True:
                    w = stack.pop()
                    on[w] = False
                    comp[w] = ncomp
                    if w == v:
                        break
                ncomp += 1
            if work:
                u = work[-1][0]
                low[u] = min(low[u], low[v])
    return comp


def _reaches(adj: list[list[int]], src: int, dst: int,
             allowed: set) -> bool:
    seen, todo = {src}, [src]
    while todo:
        v = todo.pop()
        if v == dst:
            return True
        for w in adj[v]:
            if w in allowed and w not in seen:
                seen.add(w)
                todo.append(w)
    return False


def check(path: Path, g1c_blind: bool = False) -> dict:
    """The verdict of Elle's list-append checker with G1 and G2
    prohibited, as {"valid?", "anomaly-types"}. `g1c_blind` is the
    control: the same checker with the G1c prohibition dropped."""
    txns = _txns(path)
    found: set = set()
    failed_vals = set()
    writer: dict = {}
    appends_of: dict = {}                  # (txn, key) -> [values]
    reads = []                             # (txn, key, list)
    live = [i for i, t in enumerate(txns) if t["status"] != "fail"]
    for i, t in enumerate(txns):
        for f, k, v in t["mops"]:
            if f == "append":
                if t["status"] == "fail":
                    failed_vals.add((k, v))
                    continue
                if (k, v) in writer:
                    found.add("duplicate-appends")
                writer[(k, v)] = i
                appends_of.setdefault((i, k), []).append(v)
        if t["status"] != "ok":
            continue
        mine: dict = {}
        for f, k, v in t["mops"]:
            if f == "append":
                mine.setdefault(k, []).append(v)
            elif v is not None:
                own = mine.get(k, [])
                if own and v[-len(own):] != own:
                    found.add("internal")
                if len(set(v)) != len(v):
                    found.add("duplicate-elements")
                if not own:
                    reads.append((i, k, list(v)))
    order: dict = {}
    for i, k, v in reads:
        if len(v) > len(order.get(k, [])):
            order[k] = v
    for i, k, v in reads:
        if order.get(k, [])[:len(v)] != v:
            found.add("incompatible-order")
        if any((k, x) in failed_vals for x in v):
            found.add("G1a")
        if v:
            w = writer.get((k, v[-1]))
            if w is not None and w != i \
                    and appends_of[(w, k)][-1] != v[-1]:
                found.add("G1b")
    ww, wr, rw = [], [], []
    for k, vs in order.items():
        ws = [writer.get((k, v)) for v in vs]
        for a, b in zip(ws, ws[1:]):
            if a is not None and b is not None and a != b:
                ww.append((a, b))
    for i, k, v in reads:
        vs = order.get(k, [])
        if v:
            w = writer.get((k, v[-1]))
            if w is not None and w != i:
                wr.append((w, i))
        if len(v) < len(vs):
            w = writer.get((k, vs[len(v)]))
            if w is not None and w != i:
                rw.append((i, w))
    n = len(txns)

    def adjacency(*rels):
        adj = [[] for _ in range(n)]
        for rel in rels:
            for a, b in rel:
                adj[a].append(b)
        return adj

    comp = _sccs(n, adjacency(ww))
    if any(comp[a] == comp[b] for a, b in ww):
        found.add("G0")
    wwr = adjacency(ww, wr)
    comp = _sccs(n, wwr)
    if not g1c_blind and any(comp[a] == comp[b] for a, b in wr):
        found.add("G1c")
    full = adjacency(ww, wr, rw)
    comp = _sccs(n, full)
    everyone = set(live)
    for a, b in rw:
        if comp[a] != comp[b]:
            continue
        members = {v for v in everyone if comp[v] == comp[a]}
        if _reaches(wwr, b, a, members):
            found.add("G-single")
        elif _reaches(full, b, a, members):
            found.add("G2-item")
    return {"valid?": not found, "anomaly-types": sorted(found)}
