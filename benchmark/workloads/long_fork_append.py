"""Long Jepsen list-append histories with a seeded long fork, and a plain
checker for them.

The histories are list_append.py's (Elle's list-append generator under a
concurrent, serializable execution, sizes from the configuration's
`shape_seed`), at the length of a YugabyteDB YSQL `append` run. Every
`anomaly_every`-th history carries one long fork in place of a G1c: two
writers append to two fresh keys `a` and `b`, and two concurrent readers
each read both keys, one seeing `a`'s append and not `b`'s, the other
`b`'s and not `a`'s. The cycle w_a ->wr r1 ->rw w_b ->wr r2 ->rw w_a has
two non-adjacent rw edges: Elle files it under G2-item.

The four txns are ok txns of four processes, all in flight at one
instant, at the instant nearest the middle of the commit order that has
them. Each writer is a single append, so nothing but the fork leads into
or out of it: a writer with a second micro-op would join the rest of the
history's ww and wr edges, and a path from one writer to the other would
close a cycle with one rw edge, a G-single. Each reader's two micro-ops
were reads. Only the keys (and the readers' results) change: no txn's
length, micro-op kinds or outcome.

`check` is list_append.py's plain checker, which imports nothing of the
program under test.
"""

from __future__ import annotations

import bisect
import random
from pathlib import Path

from harness import spec

LA = spec.load_module(Path(__file__).with_name("list_append.py"))

#: the verdict fields compared with the truth and the reference
FIELDS = LA.FIELDS

shape = LA.shape


def _fork(order, com, outcomes, mops, inv, cmp_, pid):
    """Two writers (one append each) and two readers (two reads each),
    ok txns of four processes, all in flight at the commit instant
    nearest the middle of the commit order that has them."""
    longest = max(c - i for i, c in zip(inv, cmp_))
    mid = len(order) // 2
    for d in range(len(order)):
        for j in (mid + d, mid - d):
            if not 0 <= j < len(order):
                continue
            t = com[order[j]]
            # inv is in invoke order: txns in flight at t started here
            lo = bisect.bisect_left(inv, t - longest)
            hi = bisect.bisect_left(inv, t)
            writers, readers, procs = [], [], set()
            for i in range(lo, hi):
                if outcomes[i] != "ok" or cmp_[i] <= t or pid[i] in procs:
                    continue
                kinds = [f for f, _k, _v in mops[i]]
                if kinds == ["append"] and len(writers) < 2:
                    writers.append(i)
                elif kinds == ["r", "r"] and len(readers) < 2:
                    readers.append(i)
                else:
                    continue
                procs.add(pid[i])
            if len(writers) == len(readers) == 2:
                return writers, readers
    raise RuntimeError("no four concurrent txns to seed a long fork")


def history(cfg: dict, seed: str, shp: tuple, fork: bool) -> list[str]:
    """One history of the sizes `shp` (`shape`) as JSON lines."""
    rng = random.Random(seed)
    outcomes, txns = shp
    n = len(outcomes)
    inv, com, cmp_, pid = LA._schedule(cfg, rng, outcomes)
    mops = LA._mops(txns)
    order = sorted((i for i in range(n) if com[i] is not None),
                   key=com.__getitem__)
    if fork:
        (wa, wb), (r1, r2) = _fork(order, com, outcomes, mops, inv, cmp_,
                                   pid)
        ka = 1 + max(k for txn in txns for _f, k in txn)
        kb = ka + 1
        mops[wa][0][1] = ka
        mops[wb][0][1] = kb
        va, vb = mops[wa][0][2], mops[wb][0][2]
        mops[r1] = [["r", ka, None], ["r", kb, None]]
        mops[r2] = [["r", ka, None], ["r", kb, None]]
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
                got.append(["r", k, list(lst)])
        seen[i] = got
    if fork:
        # each reader sees one writer's append and misses the other's
        seen[r1] = [["r", ka, [va]], ["r", kb, []]]
        seen[r2] = [["r", ka, []], ["r", kb, [vb]]]
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


def check(path: Path, g2_blind: bool = False) -> dict:
    """The verdict of Elle's list-append checker with G1 and G2
    prohibited (list_append.check), as {"valid?", "anomaly-types"}.
    `g2_blind` is the control: the same checker with G2-item dropped."""
    res = LA.check(path)
    if not g2_blind:
        return res
    found = [a for a in res["anomaly-types"] if a != "G2-item"]
    return {"valid?": not found, "anomaly-types": found}
