"""The long-fork list-append deployment at small sizes on the CPU: the
generator is byte-identical per seed and its sizes follow the shape
seed; every 8th history holds exactly one long fork on two fresh keys;
the plain checker finds it as G2-item and its G2-blind control does not;
and `analyze-store --checker append`, with the dense limit lowered so
every history takes the condensation route, gives the plain checker's
verdicts while tracing one main-thread `condense` phase per history and
counting `histories_condensed` and `scc_device`, which the
`condense_share` and `condensed_share` readers read."""

import contextlib
import json
import os
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spans, spec, stores  # noqa: E402

BENCH = spec.Benchmark()
LF = BENCH.config("yugabyte-long-fork-100k")
WL = BENCH.workload_module(LF)
SEEDS = (2**40 + 29, 3_000_000_019)
LIMIT = 256


def small(n=2000):
    return {**LF, "txns_per_history": n}


def ops(path: Path) -> list:
    return [json.loads(line) for line in path.read_text().splitlines()]


def verdict(res: dict) -> tuple:
    return res["valid?"], sorted(res["anomaly-types"])


def test_same_seed_same_bytes(tmp_path):
    trees = []
    for name in ("a", "b"):
        stores.generate(WL, small(600), tmp_path / name, SEEDS[0], 8)
        trees.append({p.relative_to(tmp_path / name): p.read_bytes()
                      for p in sorted((tmp_path / name).rglob("*"))
                      if p.is_file()})
    assert trees[0] == trees[1] and len(trees[0]) == 8


def test_sizes_follow_the_shape_seed_not_the_run_seed(tmp_path):
    def sizes(seed):
        stores.generate(WL, small(600), tmp_path / str(seed), seed, 8)
        out = []
        for d in sorted((tmp_path / str(seed)).iterdir()):
            h = ops(d / "history.jsonl")
            out.append((Counter(o["type"] for o in h),
                        [len(o["value"]) for o in h
                         if o["type"] == "invoke"]))
        return out
    a, b = sizes(SEEDS[0]), sizes(SEEDS[1])
    assert a == b
    assert len({str(x) for x in a}) > 1
    assert (tmp_path / str(SEEDS[0]) / "run-00000" / "history.jsonl") \
        .read_bytes() != (tmp_path / str(SEEDS[1]) / "run-00000"
                          / "history.jsonl").read_bytes()


@pytest.fixture(scope="module", params=SEEDS, ids=["seed-a", "seed-b"])
def store(request, tmp_path_factory):
    """A 16-run store of 2000-txn histories, and its seeded truth."""
    root = tmp_path_factory.mktemp("lf-store")
    truth = stores.generate(WL, small(), root / LF["name"], request.param,
                            16)
    return root, truth


def test_every_eighth_run_holds_one_long_fork(store):
    """A seeded run adds two keys above the shape's, which four ok txns
    of four processes, all in flight at once, and nothing else, touch:
    two single appends and two pairs of reads that see them in opposite
    orders. Other runs add none."""
    root, truth = store
    for name, t in truth.items():
        i = int(name[-5:])
        top = max(k for txn in WL.shape(small(), i)[1] for _f, k in txn)
        h = ops(root / LF["name"] / name / "history.jsonl")
        new = [o for o in h if any(k > top for _f, k, _v in o["value"])]
        seeded = i % 8 == 7
        assert t == {"valid?": not seeded,
                     "anomaly-types": ["G2-item"] if seeded else [],
                     "txns": t["txns"]}
        if not seeded:
            assert new == []
            continue
        a, b = top + 1, top + 2
        inv = [o for o in new if o["type"] == "invoke"]
        done = [o for o in new if o["type"] != "invoke"]
        assert [o["type"] for o in done] == ["ok"] * 4
        assert len({o["process"] for o in done}) == 4
        # mutually concurrent: all invoked before any completed
        assert max(map(h.index, inv)) < min(map(h.index, done))
        vals = sorted(o["value"] for o in done)
        wrote = {m[0][1]: m[0][2] for m in vals if m[0][0] == "append"}
        va, vb = wrote[a], wrote[b]
        assert vals == sorted([[["append", a, va]], [["append", b, vb]],
                               [["r", a, [va]], ["r", b, []]],
                               [["r", a, []], ["r", b, [vb]]]])


def test_plain_checker_finds_the_fork_and_the_control_does_not(store):
    root, truth = store
    assert sum(not t["valid?"] for t in truth.values()) == 2
    for name, t in truth.items():
        path = root / LF["name"] / name / "history.jsonl"
        assert WL.check(path) == {"valid?": t["valid?"],
                                  "anomaly-types": t["anomaly-types"]}
        assert WL.check(path, g2_blind=True) == {"valid?": True,
                                                 "anomaly-types": []}


@pytest.fixture(scope="module")
def condensed(store, tmp_path_factory):
    """One `analyze-store --checker append` pass over the store with
    the dense limit below every history's length."""
    from jepsen_tpu import cli, parallel
    root, truth = store
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "DENSE_TXN_LIMIT", LIMIT)
    try:
        with open(os.devnull, "w") as f, contextlib.redirect_stdout(f):
            rc = cli.run_cli(lambda tmap, args: tmap, argv=[
                "analyze-store", "--store", str(root), "--checker",
                "append", "--backend", "tpu"])
    finally:
        mp.undo()
    ev = json.loads((root / "trace.json").read_text())["traceEvents"]
    counters = json.loads((root / "metrics.json").read_text())["counters"]
    return {"rc": rc, "events": ev, "counters": counters}


def test_condensed_sweep_gives_the_plain_verdicts(store, condensed):
    root, truth = store
    assert condensed["rc"] == 1
    assert min(t["txns"] for t in truth.values()) > LIMIT
    for name, t in truth.items():
        d = root / LF["name"] / name
        got = json.loads((d / "results.json").read_text())
        assert verdict(got) == verdict(WL.check(d / "history.jsonl")), name
        assert verdict(got) == verdict(t), name


def test_condensed_sweep_traces_one_condense_phase_a_history(
        store, condensed):
    root, truth = store
    ev, c = condensed["events"], condensed["counters"]
    main = spans.main_thread_phases(ev)
    cond = [(t, t + d) for t, d, n in main if n == "condense"]
    assert len(cond) == len(truth)
    args = [e["args"] for e in ev if e.get("name") == "condense"]
    assert sorted(a["txns"] for a in args) == sorted(
        t["txns"] for t in truth.values())
    for a in args:
        assert set(a) == {"txns", "edges", "sccs", "scc_max"}
        assert a["edges"] > a["txns"]
    # the two seeded histories' forks are the only nontrivial SCCs
    assert Counter((a["sccs"], a["scc_max"]) for a in args) \
        == {(0, 0): len(truth) - 2, (1, 4): 2}
    dispatches = [(t, t + d) for t, d, n in main if n == "dispatch"]
    assert len(dispatches) == c["buckets_dispatched"] >= 1
    for s, e in cond:
        assert all(de <= s or ds >= e for ds, de in dispatches)
    assert c["histories_condensed"] == len(truth)
    assert c["scc_device"] == 2 and c["scc_host"] == 0


def test_condense_readers_read_the_condensed_pass(store, condensed):
    root, truth = store
    r = {"pass": {"events": condensed["events"], "wall_s": 10.0,
                  "counters": condensed["counters"]},
         "runs": len(truth)}
    share = BENCH.metric_module("condense_share").read(r)
    assert share == pytest.approx(100 * spans.main_thread_seconds(
        condensed["events"], "condense") / 10.0)
    assert share > 0
    assert BENCH.metric_module("condensed_share").read(r) == 100.0


@pytest.mark.parametrize("metric", ["condense_share", "condensed_share"])
def test_condense_readers_read_nothing_without_the_route(metric):
    """A program that opens no `condense` phase and counts no
    `histories_condensed` (the dense route, or the parent program)."""
    meta = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "analyze-store:append"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 5,
             "args": {"name": "MainThread"}}]
    ev = meta + [{"name": n, "cat": "phase", "ph": "X", "pid": 1,
                  "tid": 5, "ts": t * 1e3, "dur": 100e3}
                 for n, t in (("parse", 0), ("collect", 100))]
    r = {"pass": {"events": ev, "wall_s": 2.0,
                  "counters": {"buckets_dispatched": 3}}, "runs": 8}
    assert BENCH.metric_module(metric).read(r) is None


def test_oversized_scc_classifies_on_the_host_in_its_own_phase(store):
    """An SCC past the device limit takes `_classify_scc_host`, in an
    `scc_host` phase, counted by `scc_host`, with the same verdict."""
    from jepsen_tpu import ingest, trace
    from jepsen_tpu.checker.elle import condense
    root, truth = store
    name = next(n for n, t in sorted(truth.items()) if not t["valid?"])
    enc = ingest.encode_run_dir(root / LF["name"] / name, "append")
    tr = trace.fresh_run("scc-host")
    flags = condense.check_condensed(enc, device_scc_limit=3)
    assert set(flags) == {"G2-item"}
    c = tr.metrics_dict()["counters"]
    assert c["histories_condensed"] == c["scc_host"] == 1
    assert c["scc_device"] == 0
    got = [e["args"] for e in tr.chrome_events()
           if e.get("cat") == "phase" and e["name"] == "scc_host"]
    assert got == [{"txns": 4}]
