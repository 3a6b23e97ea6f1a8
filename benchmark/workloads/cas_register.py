"""etcd's independent CAS registers from a seed, and a plain checker.

The generator follows the etcd suite's register workload
(`etcd/src/jepsen/etcd.clj:167-179`): independent keys, each driven by
a group of threads through a mix of read, write and compare-and-set
over a few values, `stagger` between a thread's ops, a fixed number of
ops per key; groups of threads take keys one after another. Each op
applies at one instant inside its invoke/complete window, so every key
is linearizable by construction. A few ops are indeterminate (`:info`):
they apply or not, their thread waits out a timeout and comes back as
a fresh process, and their invocation stays open for the rest of the
key. Every `anomaly_every`-th run carries one key with a stale read: an
ok read returns a value the register held earlier, chosen so that no
linearization exists (checked here, by `linearizable`).

Each key's sizes are drawn at random as the suite draws them: every
op's kind (read, write or compare-and-set, even odds), its thread, its
latency, whether it times out (`:info`) and whether a compare-and-set
finds its expected value (one in `values`). They come from the
configuration's `shape_seed` and the key's place in the store, not from
the run's seed, so every seed's store holds the same keys' sizes: the
program batches each slot bucket of a store at its exact size, and
sizes that changed with the seed would recompile in every run. The run's
seed spreads a run's key shapes over its keys and draws every value,
and so every read's result, and the stale read.

`check` splits a run by key and decides each key with the Wing-Gong-Lowe
search over a nil-initial CAS register, written from the published
algorithm. It imports nothing of the program under test.
"""

from __future__ import annotations

import json
import math
import random
from pathlib import Path

#: the verdict fields compared with the truth and the reference
FIELDS = ("valid?", "failures")

FS = ("read", "write", "cas")


def key_shape(cfg: dict, index: int) -> list[dict]:
    """Key `index`'s sizes, in invoke order: each op's f, thread slot,
    invoke, completion (None: it timed out) and commit time (None: it
    never applied), times in ns from the key's start, and for a
    compare-and-set whether it finds its expected value."""
    rng = random.Random(f"{cfg['name']}:{cfg['shape_seed']}:{index}")
    a = cfg["assumed"]
    stagger = 1e9 / cfg["stagger_hz"]
    lat = a["latency_median_ms"] * 1e6
    sigma = a["latency_sigma"]
    timeout = a["info_timeout_ms"] * 1e6
    free = [(rng.random() * 2 * stagger, s)
            for s in range(cfg["threads_per_key"])]
    ops = []
    for _ in range(cfg["ops_per_key"]):
        free.sort()
        t, s = free.pop(0)
        dur = lat * math.exp(rng.gauss(0.0, sigma))
        op = {"f": rng.choice(FS), "slot": s, "inv": t, "cmp": t + dur,
              "commit": t + dur * rng.uniform(0.05, 0.95),
              "hit": rng.random() < 1.0 / cfg["values"]}
        if rng.random() < cfg["info_share"]:
            op["cmp"] = None
            if rng.random() >= a["info_applied_share"]:
                op["commit"] = None
            nxt = t + timeout
        else:
            nxt = t + dur
        ops.append(op)
        free.append((nxt + rng.random() * 2 * stagger, s))
    return ops


def _key_ops(cfg: dict, rng: random.Random, shp: list, procs: list,
             t0: float) -> list[dict]:
    """One key's ops of the sizes `shp`, from `t0`, with values drawn
    from `rng`: dicts with f, value, inv, cmp, commit, process and
    result. `procs` holds the group's thread process ids; a timed-out
    op's thread comes back as a fresh process, in place."""
    nv = cfg["values"]
    ops = []
    for o in shp:
        op = {"f": o["f"], "inv": t0 + o["inv"],
              "cmp": None if o["cmp"] is None else t0 + o["cmp"],
              "commit": None if o["commit"] is None else t0 + o["commit"],
              "process": procs[o["slot"]], "hit": o["hit"]}
        if o["cmp"] is None:
            procs[o["slot"]] += cfg["concurrency"]
        ops.append(op)
    state = None
    for op in sorted((o for o in ops if o["commit"] is not None),
                     key=lambda o: o["commit"]):
        if op["f"] == "read":
            op["value"] = None
            op["result"] = ("ok", state)
        elif op["f"] == "write":
            op["value"] = state = rng.randrange(nv)
            op["result"] = ("ok", state)
        elif op["hit"] and state is not None:
            op["value"] = [state, rng.randrange(nv)]
            state = op["value"][1]
            op["result"] = ("ok", op["value"])
        else:
            op["value"] = [rng.choice([v for v in range(nv) if v != state]),
                           rng.randrange(nv)]
            op["result"] = ("fail", op["value"])
            if op["cmp"] is None:
                op["commit"] = None
    for op in ops:
        if "value" not in op:
            op["value"] = (None if op["f"] == "read"
                           else rng.randrange(nv) if op["f"] == "write"
                           else [rng.randrange(nv), rng.randrange(nv)])
    return ops


def _stale_read(rng, ops) -> bool:
    """Make one ok read of this key stale and non-linearizable."""
    reads = [o for o in ops if o["f"] == "read" and o["cmp"] is not None]
    rng.shuffle(reads)
    for r in reads:
        held = {o["value"] if o["f"] == "write" else o["value"][1]
                for o in ops
                if o["commit"] is not None and o["commit"] < r["inv"]
                and o["f"] != "read" and o["result"][0] == "ok"}
        for v in sorted(held - {r["result"][1]}):
            old = r["result"]
            r["result"] = ("ok", v)
            if not linearizable(_entries(ops, 0)):
                return True
            r["result"] = old
    return False


def _entries(ops, key) -> list:
    """(time, kind, op) events of one key: invocations and completions."""
    ev = []
    for o in ops:
        inv = [key, o["value"]]
        ev.append((o["inv"], "invoke", o["process"], o["f"], inv))
        if o["cmp"] is None:
            ev.append((o["inv"] + 1e12, "info", o["process"], o["f"], inv))
        else:
            typ, val = o["result"]
            ev.append((o["cmp"], typ, o["process"], o["f"], [key, val]))
    return ev


def run_history(cfg: dict, seed: str, index: int, bad: bool):
    """Run `index` of a store: JSON lines and the keys with a stale
    read. Its keys take the shapes of its places in the store, in an
    order drawn from the seed."""
    rng = random.Random(seed)
    keys = cfg["keys_per_run"]
    shapes = [key_shape(cfg, index * keys + k) for k in range(keys)]
    rng.shuffle(shapes)
    groups = cfg["concurrency"] // cfg["threads_per_key"]
    group_t = [0.0] * groups
    tpk = cfg["threads_per_key"]
    group_procs = [list(range(g * tpk, (g + 1) * tpk))
                   for g in range(groups)]
    per_key = []
    for k, shp in enumerate(shapes):
        g = k % groups
        ops = _key_ops(cfg, rng, shp, group_procs[g], group_t[g])
        group_t[g] = max(o["cmp"] for o in ops if o["cmp"] is not None)
        per_key.append(ops)
    corrupt = None
    if bad:
        for k in rng.sample(range(keys), keys):
            if _stale_read(rng, per_key[k]):
                corrupt = k
                break
        else:
            raise RuntimeError(f"run {index}: no key takes a stale read")
    events = [e for k, ops in enumerate(per_key) for e in _entries(ops, k)]
    # an info completion lands when its thread's timeout fires
    timeout = cfg["assumed"]["info_timeout_ms"] * 1e6
    events = [(t - 1e12 + timeout if kind == "info" else t, kind, p, f, v)
              for t, kind, p, f, v in events]
    # at one instant a completion goes first (a thread's next invocation
    # can share its previous completion's time)
    events.sort(key=lambda e: (e[0], e[1] == "invoke"))
    lines = [json.dumps({"type": kind, "process": p, "f": f, "value": v,
                         "time": int(t), "index": i},
                        separators=(",", ":"))
             for i, (t, kind, p, f, v) in enumerate(events)]
    return lines, [] if corrupt is None else [str(corrupt)]


def generate(cfg: dict, root: Path, seed: int, count: int,
             first: int = 0) -> dict:
    """Write `count` run dirs and return the seeded truth:
    {run name: {"valid?", "failures"}}."""
    truth = {}
    every = cfg["anomaly_every"]
    for i in range(first, first + count):
        bad = i % every == every - 1
        d = root / f"run-{i:05d}"
        d.mkdir(parents=True)
        lines, failures = run_history(cfg, f"{seed}:{i}", i, bad)
        (d / "history.jsonl").write_text("\n".join(lines) + "\n")
        truth[d.name] = {"valid?": not failures, "failures": failures}
    return truth


# -- the plain checker ---------------------------------------------------


def linearizable(events, stale_ok: bool = False,
                 max_configs: int = 5_000_000) -> bool | None:
    """Wing-Gong-Lowe search over one key's (time, type, process, f,
    [key, value]) events for a nil-initial CAS register. Failed ops
    never happened; indeterminate ops may be linearized or left out.
    `stale_ok` is the control: an ok read of any value written by an
    op invoked before the read completed is accepted unchecked.
    None: the search passed `max_configs`."""
    calls = {}
    order = []                # [f, value, type] calls, ("ret", op) returns
    written_before = set()
    for t, typ, p, f, v in sorted(events,
                                  key=lambda e: (e[0], e[1] == "invoke")):
        if typ == "invoke":
            calls[p] = len(order)
            order.append(None)
            if f == "write":
                written_before.add(v[1])
            elif f == "cas":
                written_before.add(v[1][1])
            continue
        pos = calls.pop(p)
        if typ == "fail":
            continue
        val = v[1]
        if typ == "ok" and f == "read" and stale_ok \
                and (val is None or val in written_before):
            continue
        if typ == "info" and f == "read":
            continue
        op = [f, val, typ]
        order[pos] = op
        if typ == "ok":
            order.append(("ret", op))
    # the entry list: calls (op) and returns (("ret", op)) in time order
    entries = [e for e in order if e is not None]
    n = len(entries)
    idx = {}
    k = 0
    for e in entries:
        if not isinstance(e, tuple):
            idx[id(e)] = k
            k += 1
    nxt = list(range(1, n + 1))
    prv = list(range(-1, n - 1))
    _first = [0]
    ret_of = {id(e[1]): i for i, e in enumerate(entries)
              if isinstance(e, tuple)}
    pending = sum(1 for e in entries if isinstance(e, tuple))

    def unlink(i):
        p, q = prv[i], nxt[i]
        if p >= 0:
            nxt[p] = q
        else:
            _first[0] = q
        if q < n:
            prv[q] = p

    def relink(i):
        p, q = prv[i], nxt[i]
        if p >= 0:
            nxt[p] = i
        else:
            _first[0] = i
        if q < n:
            prv[q] = i

    def step(state, op):
        f, val = op[0], op[1]
        if f == "read":
            return (True, state) if val == state else (False, state)
        if f == "write":
            return True, val
        if val[0] == state:
            return True, val[1]
        return False, state

    state = None
    lin = 0
    cache = set()
    stack = []
    i = _first[0]
    while pending:
        if i >= n:
            return False      # cannot happen: a return is always ahead
        e = entries[i]
        if isinstance(e, tuple):          # a return: backtrack
            if not stack:
                return False
            i, state, lin = stack.pop()
            op = entries[i]
            if op[2] == "ok":
                relink(ret_of[id(op)])
                pending += 1
            relink(i)
            i = nxt[i]
            continue
        ok, new = step(state, e)
        bit = 1 << idx[id(e)]
        if ok and (lin | bit, new) not in cache:
            cache.add((lin | bit, new))
            if len(cache) > max_configs:
                return None
            stack.append((i, state, lin))
            state, lin = new, lin | bit
            unlink(i)
            if e[2] == "ok":
                unlink(ret_of[id(e)])
                pending -= 1
            i = _first[0]
        else:
            i = nxt[i]
    return True


def check(path: Path, stale_ok: bool = False) -> dict:
    """Per-key linearizability of one run: {"valid?", "failures"}."""
    by_key: dict = {}
    with open(path) as f:
        for line in f:
            o = json.loads(line)
            k, v = o["value"]
            by_key.setdefault(k, []).append(
                (o["index"], o["type"], o["process"], o["f"], [k, v]))
    failures = []
    unknown = False
    for k, ev in by_key.items():
        r = linearizable(ev, stale_ok=stale_ok)
        if r is None:
            unknown = True
        elif not r:
            failures.append(str(k))
    return {"valid?": "unknown" if unknown else not failures,
            "failures": sorted(failures)}
