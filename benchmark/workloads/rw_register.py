"""Jepsen rw-register histories from a seed, and a plain checker for them.

The generator follows Elle's rw-register generator (`elle.txn/wr-txns`)
as Jepsen's `tests/cycle/wr.clj` runs it: a few active keys, 1-2
micro-ops per txn, each a read or a write at even odds, a key retired
after a fixed number of writes, every write unique per key. The
execution is the list-append generator's (list_append.py): concurrent
and serializable, every txn applied at one commit point inside its
invoke/complete window, some txns failed and some indeterminate. So the
sizes are list-append's sizes with each append a write: they come from
the configuration's `shape_seed` and the history's place in the store,
and the run's seed draws the schedule, the commit order and so every
read, which txns of unknown outcome applied, and where the G2 falls.

Every `anomaly_every`-th history carries one G2 write skew, as Jepsen's
`tests/adya.clj` provokes it: two concurrent txns each read a key the
other writes and both see it unwritten (`[r a nil][w b 1]` and
`[r b nil][w a 1]`, on two keys nothing else touches).

`check` is an independent rw-register checker written from Adya's and
Elle's definitions: external reads and writes, the internal, G1a and G1b
checks, version orders from the initial state only (nil precedes every
written value, wr.clj's default), wr and rw edges, Tarjan SCCs. It
imports nothing of the program under test.
"""

from __future__ import annotations

import random
from pathlib import Path

from harness import spec

LA = spec.load_module(Path(__file__).with_name("list_append.py"))

#: the verdict fields compared with the truth and the reference
FIELDS = LA.FIELDS


def shape(cfg: dict, index: int) -> tuple[list, list]:
    """History `index`'s sizes: list-append's, each append a write."""
    outcomes, txns = LA.shape(cfg, index)
    return outcomes, [[["w" if f == "append" else f, k] for f, k in txn]
                      for txn in txns]


def _mops(txns: list) -> list:
    """[f, key, value] micro-ops, each key's writes numbered 1, 2, ..."""
    out, last = [], {}
    for txn in txns:
        mops = []
        for f, k in txn:
            if f == "w":
                last[k] = last.get(k, 0) + 1
                mops.append(["w", k, last[k]])
            else:
                mops.append(["r", k, None])
        out.append(mops)
    return out


def _g2_pair(order, outcomes, mops, inv, cmp_, pid):
    """Two ok two-micro-op txns, concurrent, of other processes, nearest
    the middle of the commit order."""
    mid = len(order) // 2
    for d in range(len(order)):
        for j in (mid + d, mid - d):
            if not 0 <= j < len(order) - 1:
                continue
            a = order[j]
            if outcomes[a] != "ok" or len(mops[a]) != 2:
                continue
            for b in order[j + 1:j + 16]:
                if (outcomes[b] == "ok" and len(mops[b]) == 2
                        and pid[a] != pid[b] and inv[b] < cmp_[a]
                        and inv[a] < cmp_[b]):
                    return a, b
    raise RuntimeError("no concurrent pair to seed a G2")


def history(cfg: dict, seed: str, shp: tuple, g2: bool) -> list[str]:
    """One history of the sizes `shp` (`shape`) as JSON lines."""
    rng = random.Random(seed)
    outcomes, txns = shp
    n = len(outcomes)
    inv, com, cmp_, pid = LA._schedule(cfg, rng, outcomes)
    mops = _mops(txns)
    order = sorted((i for i in range(n) if com[i] is not None),
                   key=com.__getitem__)
    if g2:
        a, b = _g2_pair(order, outcomes, mops, inv, cmp_, pid)
        ka = 1 + max(k for txn in txns for _f, k in txn)
        kb = ka + 1
        mops[a] = [["r", ka, None], ["w", kb, 1]]
        mops[b] = [["r", kb, None], ["w", ka, 1]]
    state: dict = {}
    seen: dict = {}
    for i in order:
        got = []
        for f, k, v in mops[i]:
            if f == "w":
                state[k] = v
                got.append(["w", k, v])
            else:
                got.append(["r", k, state.get(k)])
        seen[i] = got
    if g2:
        # each misses the other's write: the write skew
        seen[a][0][2] = seen[b][0][2] = None
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
                     f'"value":[{",".join(map(LA._mop, val))}],'
                     f'"time":{t},"index":{index}}}')
    return lines


def generate(cfg: dict, root: Path, seed: int, count: int,
             first: int = 0) -> dict:
    """Write `count` run dirs `root/run-<i>/history.jsonl` and return
    the seeded truth: {run name: {"valid?", "anomaly-types", "txns"}}."""
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
                         "anomaly-types": ["G2-item"] if bad else [],
                         "txns": sum(o != "fail" for o in shp[0])}
    return truth


# -- the plain checker ---------------------------------------------------


def _ext(mops: list) -> tuple[dict, dict]:
    """A txn's external reads (a key's first read before the txn writes
    it) and external writes (its last write of each key)."""
    reads, writes = {}, {}
    for f, k, v in mops:
        if f == "w":
            writes[k] = v
        elif k not in writes and k not in reads:
            reads[k] = v
    return reads, writes


def _internal(mops: list) -> bool:
    """A read that differs from the txn's own last write or read of
    that key."""
    state: dict = {}
    for f, k, v in mops:
        if f == "r" and k in state and state[k] != v:
            return True
        state[k] = v
    return False


def check(path: Path, g2_allowed: bool = False) -> dict:
    """The verdict of Elle's rw-register checker with G2, G1a, G1b and
    internal prohibited, as {"valid?", "anomaly-types"}. `g2_allowed`
    is the control: the same checker with G2-item allowed."""
    all_txns = LA._txns(path)
    found: set = set()
    failed = {(k, v) for t in all_txns if t["status"] == "fail"
              for f, k, v in t["mops"] if f == "w"}
    txns = [t for t in all_txns if t["status"] != "fail"]
    writer: dict = {}                      # (k, v) -> txn
    final: dict = {}                       # (k, v) -> is the txn's last
    for i, t in enumerate(txns):
        _r, last = _ext(t["mops"])
        for f, k, v in t["mops"]:
            if f == "w":
                writer[(k, v)] = i
                final[(k, v)] = last[k] == v
    readers: dict = {}                     # (k, v) -> [txn]
    for i, t in enumerate(txns):
        if t["status"] != "ok":
            continue
        if _internal(t["mops"]):
            found.add("internal")
        for k, v in _ext(t["mops"])[0].items():
            readers.setdefault((k, v), []).append(i)
            if v is None:
                continue
            w = writer.get((k, v))
            if w is None and (k, v) in failed:
                found.add("G1a")
            elif w is not None and w != i and not final[(k, v)]:
                found.add("G1b")
    # versions: nil, then every written value, no order among those
    ww: list = []
    wr = [(writer[kv], r) for kv, rs in readers.items()
          if kv in writer for r in rs if r != writer[kv]]
    rw = [(r, w) for (k, v), w in writer.items()
          for r in readers.get((k, None), ()) if r != w]
    found |= _cycles(len(txns), ww, wr, rw)
    # every class found is one that G2, G1a, G1b and internal prohibit
    bad = found - ({"G2-item"} if g2_allowed else set())
    return {"valid?": not bad, "anomaly-types": sorted(found)}


def _cycles(n: int, ww: list, wr: list, rw: list) -> set:
    """Adya's cycle classes over the dependency graph: G0 (ww only),
    G1c (ww and wr), G-single (exactly one rw) and G2-item (more)."""
    def adjacency(*rels):
        adj = [[] for _ in range(n)]
        for rel in rels:
            for a, b in rel:
                adj[a].append(b)
        return adj

    found = set()
    comp = LA._sccs(n, adjacency(ww))
    if any(comp[a] == comp[b] for a, b in ww):
        found.add("G0")
    wwr = adjacency(ww, wr)
    comp = LA._sccs(n, wwr)
    if any(comp[a] == comp[b] for a, b in wr):
        found.add("G1c")
    full = adjacency(ww, wr, rw)
    comp = LA._sccs(n, full)
    for a, b in rw:
        if comp[a] != comp[b]:
            continue
        members = {v for v in range(n) if comp[v] == comp[a]}
        if LA._reaches(wwr, b, a, members):
            found.add("G-single")
        elif LA._reaches(full, b, a, members):
            found.add("G2-item")
    return found
