"""The rw-register generator and plain checker at small sizes on the CPU:
sizes follow the shape seed and not the run seed; every 8th history
holds exactly one seeded G2 write skew; the plain checker agrees with
the seeded truth, and its G2-allowed control disagrees on exactly the
seeded runs; and the program's `analyze-store --checker wr` and
`WrChecker(backend="tpu")` (the device kernels, here on the CPU) give
the plain checker's verdict on every run."""

import contextlib
import json
import os
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec, stores  # noqa: E402

BENCH = spec.Benchmark()
WR = BENCH.config("cockroach-rw-register-g2")
RW = BENCH.workload_module(WR)
SEEDS = (2**40 + 25, 3_000_000_017)


def small(n=300):
    return {**WR, "txns_per_history": n}


def ops(path: Path) -> list:
    return [json.loads(line) for line in path.read_text().splitlines()]


def verdict(res: dict) -> tuple:
    return res["valid?"], sorted(res["anomaly-types"])


@pytest.fixture(scope="module", params=SEEDS, ids=["seed-a", "seed-b"])
def store(request, tmp_path_factory):
    """A 16-run store of 300-txn histories, and its seeded truth."""
    root = tmp_path_factory.mktemp("wr-store")
    truth = stores.generate(RW, small(), root / WR["name"], request.param,
                            16)
    return root, truth


def test_sizes_follow_the_shape_seed_not_the_run_seed(tmp_path):
    def sizes(seed):
        stores.generate(RW, small(), tmp_path / str(seed), seed, 8)
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


def test_every_eighth_run_holds_one_g2(store):
    """A seeded run adds two keys above the shape's, which two
    concurrent ok txns of other processes, and nothing else, touch: each
    reads one unwritten and writes the other. Other runs add none."""
    root, truth = store
    for name, t in truth.items():
        i = int(name[-5:])
        top = max(k for txn in RW.shape(small(), i)[1] for _f, k in txn)
        h = ops(root / WR["name"] / name / "history.jsonl")
        new = [o for o in h if any(k > top for _f, k, _v in o["value"])]
        seeded = i % 8 == 7
        assert t["valid?"] is not seeded
        if not seeded:
            assert new == []
            continue
        a, b = top + 1, top + 2
        inv = [o for o in new if o["type"] == "invoke"]
        done = [o for o in new if o["type"] != "invoke"]
        assert [o["type"] for o in done] == ["ok", "ok"]
        assert len({o["process"] for o in done}) == 2
        # concurrent: both invoked before either completed
        assert max(map(h.index, inv)) < min(map(h.index, done))
        assert sorted(o["value"] for o in done) == sorted(
            [[["r", a, None], ["w", b, 1]], [["r", b, None], ["w", a, 1]]])


def test_plain_checker_agrees_with_the_truth(store):
    root, truth = store
    assert sum(not t["valid?"] for t in truth.values()) == 2
    for name, t in truth.items():
        path = root / WR["name"] / name / "history.jsonl"
        assert RW.check(path) == {"valid?": t["valid?"],
                                  "anomaly-types": t["anomaly-types"]}
        # the control: G2-item allowed, only the seeded runs change
        ctl = RW.check(path, g2_allowed=True)
        assert ctl["valid?"] is True
        assert (ctl == RW.check(path)) is t["valid?"]


def test_plain_checker_finds_the_other_classes(tmp_path):
    """Hand-built histories: G1a, G1b, internal, G-single."""
    def run(name, txns):
        lines = []
        for i, (typ, mops, done) in enumerate(txns):
            lines.append({"type": "invoke", "process": i, "f": "txn",
                          "value": mops})
            lines.append({"type": typ, "process": i, "f": "txn",
                          "value": done})
        p = tmp_path / f"{name}.jsonl"
        p.write_text("".join(json.dumps(x) + "\n" for x in lines))
        return RW.check(p)["anomaly-types"]
    w = [["w", 1, 1]]
    assert run("g1a", [("fail", w, w),
                       ("ok", [["r", 1, None]], [["r", 1, 1]])]) == ["G1a"]
    ww = [["w", 1, 1], ["w", 1, 2]]
    assert run("g1b", [("ok", ww, ww),
                       ("ok", [["r", 1, None]], [["r", 1, 1]])]) == ["G1b"]
    assert run("internal", [("ok", [["w", 1, 1], ["r", 1, None]],
                             [["w", 1, 1], ["r", 1, 2]])]) == ["internal"]
    # T0 reads x unwritten, T1 writes x and y, T0 reads T1's y
    assert run("g-single", [
        ("ok", [["r", 1, None], ["r", 2, None]],
         [["r", 1, None], ["r", 2, 1]]),
        ("ok", [["w", 1, 1], ["w", 2, 1]],
         [["w", 1, 1], ["w", 2, 1]])]) == ["G-single"]


def test_analyze_store_wr_gives_the_plain_verdicts(store):
    from jepsen_tpu import cli
    root, truth = store
    with open(os.devnull, "w") as f, contextlib.redirect_stdout(f):
        rc = cli.run_cli(lambda tmap, args: tmap, argv=[
            "analyze-store", "--store", str(root), "--checker", "wr",
            "--backend", "tpu"])
    assert rc == 1
    for name, t in truth.items():
        d = root / WR["name"] / name
        got = json.loads((d / "results.json").read_text())
        assert verdict(got) == verdict(RW.check(d / "history.jsonl")), name
        assert verdict(got) == verdict(t), name


def test_wr_checker_on_the_device_path_gives_the_plain_verdicts(store):
    from jepsen_tpu.checker.elle.wr import WrChecker
    from jepsen_tpu.store import load_history_dir
    root, truth = store
    names = sorted(truth)[4:12]
    dirs = [root / WR["name"] / n for n in names]
    got = WrChecker(backend="tpu").check_batch(
        {}, [load_history_dir(d) for d in dirs], {})
    for d, res in zip(dirs, got):
        assert verdict(res) == verdict(RW.check(d / "history.jsonl")), d
