"""The benchmark's generators at small sizes on the CPU: the same seed
gives byte-identical stores; the seeded truth equals the repo's CPU
checkers and the benchmark's own plain checkers; the stated shares
hold within sampling error; and history sizes vary within a store but
every seed gives the program the same padded geometries."""

import json
import math
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec, stores  # noqa: E402

BENCH = spec.Benchmark()
APPEND = BENCH.config("etcd-append-10k")
REGISTER = BENCH.config("etcd-cas-register")
LA = BENCH.workload_module(APPEND)
CR = BENCH.workload_module(REGISTER)


def small_append(n=1000):
    return {**APPEND, "txns_per_history": n}


def small_register(n=90):
    return {**REGISTER, "ops_per_key": n, "keys_per_run": 6,
            "concurrency": 20}


def near(count: int, n: int, p: float) -> bool:
    """`count` of `n` draws lies within 4.5 standard deviations of a
    share `p`."""
    return abs(count - n * p) <= 4.5 * math.sqrt(n * p * (1 - p)) + 1


def tree(root: Path) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.mark.parametrize("mod,cfg", [(LA, small_append()),
                                     (CR, small_register())],
                         ids=["list_append", "cas_register"])
def test_same_seed_same_bytes(tmp_path, mod, cfg):
    a = stores.generate(mod, cfg, tmp_path / "a", 2**40 + 7, 9)
    b = stores.generate(mod, cfg, tmp_path / "b", 2**40 + 7, 9, workers=2)
    c = stores.generate(mod, cfg, tmp_path / "c", 2**40 + 8, 9)
    assert a == b
    assert tree(tmp_path / "a") == tree(tmp_path / "b")
    assert tree(tmp_path / "a") != tree(tmp_path / "c")


def ops(path: Path) -> list:
    return [json.loads(line) for line in path.read_text().splitlines()]


def assert_paired(h: list) -> None:
    """Each process alternates invoke, completion: a history whose
    process invokes twice without a completion is malformed."""
    open_ = set()
    for o in h:
        if o["type"] == "invoke":
            assert o["process"] not in open_, o
            open_.add(o["process"])
        else:
            assert o["process"] in open_, o
            open_.discard(o["process"])


def test_append_truth_matches_repo_and_plain_checkers(tmp_path):
    from jepsen_tpu import ingest
    from jepsen_tpu.checker import elle
    truth = stores.generate(LA, small_append(), tmp_path, 31337, 16)
    assert sum(not t["valid?"] for t in truth.values()) == 2
    prohibited = elle.AppendChecker().prohibited
    for name, t in truth.items():
        path = tmp_path / name / "history.jsonl"
        enc = ingest.encode_run_dir(tmp_path / name, "append")
        repo = elle.render_verdict(enc, elle.cycle_anomalies_cpu(enc),
                                   prohibited)
        want = (t["valid?"], t["anomaly-types"])
        assert (repo["valid?"], repo["anomaly-types"]) == want, name
        plain = LA.check(path)
        assert (plain["valid?"], plain["anomaly-types"]) == want, name
        # the control: G1c no longer prohibited, seeded runs read valid
        assert LA.check(path, g1c_blind=True)["valid?"] is True


def test_register_truth_matches_repo_and_plain_checkers(tmp_path):
    from jepsen_tpu import independent
    from jepsen_tpu.checker import Linearizable, models
    from jepsen_tpu.store import load_history_dir
    cfg = small_register()
    truth = stores.generate(CR, cfg, tmp_path, 2**35 + 1, 8)
    assert [n for n, t in truth.items() if not t["valid?"]] == \
        ["run-00007"]
    wgl = Linearizable(models.cas_register())
    for name, t in truth.items():
        h = independent.relift_history(load_history_dir(tmp_path / name))
        subs = independent.subhistories(h)
        assert len(subs) == cfg["keys_per_run"]
        failures = sorted(str(k) for k, s in subs.items()
                          if wgl._cpu(s)["valid?"] is False)
        assert failures == t["failures"], name
        assert CR.check(tmp_path / name / "history.jsonl") == \
            {"valid?": t["valid?"], "failures": t["failures"]}
        # the control: stale reads accepted, the corrupted run reads valid
        assert CR.check(tmp_path / name / "history.jsonl",
                        stale_ok=True)["valid?"] is True


def test_append_shares(tmp_path):
    cfg = small_append(1000)
    stores.generate(LA, cfg, tmp_path, 5, 6)
    done, lengths, same_key = Counter(), Counter(), 0
    kinds = Counter()
    for d in sorted(tmp_path.iterdir()):
        h = ops(d / "history.jsonl")
        assert_paired(h)
        open_, peak, writes = set(), 0, Counter()
        for o in h:
            if o["type"] == "invoke":
                open_.add(o["process"])
                lengths[len(o["value"])] += 1
                kinds.update(f for f, _k, _v in o["value"])
                same_key += len({k for _f, k, _v in o["value"]}) < \
                    len(o["value"])
                writes.update(k for f, k, _v in o["value"] if f == "append")
            else:
                open_.discard(o["process"])
                done[o["type"]] += 1
            peak = max(peak, len(open_))
        assert peak <= cfg["concurrency"]
        assert max(writes.values()) <= cfg["max_writes_per_key"]
        assert [o["index"] for o in h] == list(range(len(h)))
    n = sum(done.values())
    assert n == 6000
    assert near(done["info"], n, cfg["info_share"])
    assert near(done["fail"], n, cfg["fail_share"])
    assert set(lengths) == {1, 2} and near(lengths[1], n, 0.5)
    assert near(kinds["r"], sum(kinds.values()), 0.5)
    # Elle draws each micro-op's key alone: a txn may touch one key twice
    assert near(same_key, lengths[2], 1 / cfg["key_count"])


def test_register_shares(tmp_path):
    cfg = small_register()
    stores.generate(CR, cfg, tmp_path, 11, 4)
    inv, done = [], []
    for d in sorted(tmp_path.iterdir()):
        h = ops(d / "history.jsonl")
        assert_paired(h)
        mine = [o for o in h if o["type"] == "invoke"]
        per_key = Counter(o["value"][0] for o in mine)
        assert set(per_key.values()) == {cfg["ops_per_key"]}
        assert len(per_key) == cfg["keys_per_run"]
        inv += mine
        done += [o for o in h if o["type"] != "invoke"]
    n = len(inv)
    kinds = Counter(o["f"] for o in inv)
    assert all(near(kinds[f], n, 1 / 3) for f in ("read", "write", "cas"))
    assert near(sum(o["type"] == "info" for o in done), n,
                cfg["info_share"])
    cas = Counter(o["type"] for o in done if o["f"] == "cas")
    # a compare-and-set finds its expected value one time in `values`
    # (less before a key's first write)
    ok = cas["ok"] / (cas["ok"] + cas["fail"])
    assert 0.1 < ok <= 1 / cfg["values"] + 0.05


def _append_plans(root: Path) -> list:
    from jepsen_tpu import ingest
    from jepsen_tpu.store import dispatch_pad_plan
    return [tuple(sorted(dispatch_pad_plan(
        ingest.encode_run_dir(d, "append")).items()))
        for d in sorted(root.iterdir())]


def test_append_geometry_varies_by_history_not_by_seed(tmp_path):
    """At the configured size: the histories of a store pad to several
    batch geometries, and every seed's store to the same ones, history
    by history, so the warm-up pass compiles every one of them."""
    plans = []
    for seed in (1, 2**31 + 5, 99999999999):
        root = tmp_path / str(seed)
        stores.generate(LA, APPEND, root, seed, 8)
        plans.append(_append_plans(root))
    assert plans[0] == plans[1] == plans[2]
    assert len(set(plans[0])) > 1, plans[0]


def test_one_shape_gives_every_history_one_geometry(tmp_path):
    cfg = {**APPEND, **BENCH.traffic("serve-open-loop")["generator"]}
    stores.generate(LA, cfg, tmp_path, 7, 4)
    assert len(set(_append_plans(tmp_path))) == 1


def test_register_geometry_varies_by_key_not_by_seed(tmp_path):
    from jepsen_tpu import independent
    from jepsen_tpu.checker.knossos import dense
    from jepsen_tpu.store import load_history_dir
    shapes = []
    for seed in (3, 2**33 + 1):
        root = tmp_path / str(seed)
        stores.generate(CR, REGISTER, root, seed, 2)
        c = Counter()
        for d in sorted(root.iterdir()):
            h = independent.relift_history(load_history_dir(d))
            for s in independent.subhistories(h).values():
                e = dense.encode_dense_history(s)
                c[(e.n_steps, e.n_slots, e.n_values)] += 1
        shapes.append(c)
    assert shapes[0] == shapes[1]
    assert len(shapes[0]) > 1
