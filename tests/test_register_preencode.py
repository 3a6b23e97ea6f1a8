"""The register sweep's load workers split each run and dense-encode its
keys: verdicts match the in-process path, the encodings match the main
thread's encoder, and the counters say how many keys came pre-encoded."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

from jepsen_tpu import cli, independent, ingest
from jepsen_tpu.checker.knossos import encode as kenc
from jepsen_tpu.store import Store

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec  # noqa: E402

BENCH = spec.Benchmark()
CFG = BENCH.cell_config(BENCH.cells["register-sweep"])
TEST = CFG["name"]
SEED = 3141592653


@pytest.fixture(scope="module")
def store_src(tmp_path_factory):
    """Runs 4-7 of the cell's store: the last (every 8th) takes a
    stale read."""
    root = tmp_path_factory.mktemp("src")
    truth = BENCH.workload_module(CFG).generate(
        CFG, root / TEST, SEED, 4, first=4)
    assert [t["valid?"] for t in truth.values()] == [True] * 3 + [False]
    return root, truth


def _copy(src: Path, dst: Path) -> Store:
    shutil.copytree(src, dst)
    return Store(dst)


def _results(store: Store) -> dict:
    return {d.name: json.loads((d / "results.json").read_text())
            for d in sorted((store.base / TEST).iterdir())}


@pytest.fixture(scope="module")
def swept(store_src, tmp_path_factory):
    """The store swept on the device tiers with the spawned pool, and
    again with the workers run in this process."""
    src, truth = store_src
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JEPSEN_TPU_BACKEND", "tpu")
        for mode in ("pool", "serial"):
            if mode == "serial":
                mp.setattr(ingest, "_spawn_safe", lambda: False)
            store = _copy(src, tmp_path_factory.mktemp(mode) / "s")
            rc = cli.analyze_store(store, checker="register")
            out[mode] = (rc, _results(store), json.loads(
                (store.base / "metrics.json").read_text())["counters"])
    return out


def test_pool_verdicts_equal_in_process(swept, store_src):
    _src, truth = store_src
    rc, pooled, _ = swept["pool"]
    rc_serial, serial, _ = swept["serial"]
    assert rc == rc_serial == 1
    assert pooled == serial
    for name, want in truth.items():
        assert pooled[name]["valid?"] is want["valid?"], name
        assert pooled[name]["failures"] == want["failures"], name
        assert pooled[name]["key-count"] == CFG["keys_per_run"]
        assert {r["analyzer"] for r in pooled[name]["results"].values()} \
            == {"tpu-dense"}


def test_pool_verdicts_equal_main_thread_tiers(swept, store_src,
                                               monkeypatch):
    """Each key's verdict is what the checker's own tiered path gives
    its raw subhistory on the main thread."""
    from jepsen_tpu.checker import linearizable, models
    src, _truth = store_src
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
    c = linearizable(models.cas_register())
    assert c.engine() == "tpu"
    _rc, pooled, _ = swept["pool"]
    keys, subs = [], []
    for d in sorted((src / TEST).iterdir()):
        by_key = independent.subhistories(
            independent.relift_history(ingest.load_history_dir(d)))
        keys += [(d.name, str(k)) for k in by_key]
        subs += list(by_key.values())
    for (name, k), res in zip(keys, c.check_batch({}, subs, {})):
        assert pooled[name]["results"][k] == res, (name, k)


@pytest.mark.parametrize("mode", ["pool", "serial"])
def test_every_key_arrives_preencoded(swept, mode):
    _rc, _res, counters = swept[mode]
    assert counters["register_keys_preencoded"] == 4 * CFG["keys_per_run"]
    assert counters["register_keys_raw"] == 0
    assert "register_cpu_routed" not in counters


def test_worker_encodings_equal_main_thread_encoder(store_src):
    src, _truth = store_src
    dirs = sorted((src / TEST).iterdir())
    recs = ingest.parallel_split_registers(dirs, 512)
    for d, rec in zip(dirs, recs):
        hist = independent.relift_history(ingest.load_history_dir(d))
        by_key = independent.subhistories(hist)
        assert [k for k, _n, _e in rec] == list(by_key)
        for k, n, enc in rec:
            want = kenc.encode_dense_history(by_key[k])
            assert n == len(by_key[k])
            assert isinstance(enc, kenc.DenseEncoded)
            assert (enc.n_steps, enc.n_slots, enc.n_values, enc.n_ops) \
                == (want.n_steps, want.n_slots, want.n_values, want.n_ops)
            np.testing.assert_array_equal(enc.regs, want.regs)
            np.testing.assert_array_equal(enc.comp_slot, want.comp_slot)


def test_cpu_backend_workers_return_raw_subhistories(store_src):
    src, _truth = store_src
    d = sorted((src / TEST).iterdir())[0]
    rec = ingest.split_register_run(ingest.load_history_dir(d), None)
    by_key = independent.subhistories(
        independent.relift_history(ingest.load_history_dir(d)))
    assert [(k, n, s) for k, n, s in rec] == \
        [(k, len(s), s) for k, s in by_key.items()]


def _pass(counters):
    return {"pass": {"counters": counters}}


@pytest.mark.parametrize("counters,want", [
    ({"register_keys_preencoded": 1920, "register_keys_raw": 0}, 100.0),
    ({"register_keys_preencoded": 3, "register_keys_raw": 1}, 75.0),
    ({"register_keys_preencoded": 0, "register_keys_raw": 1920}, 0.0),
    ({}, None),
    ({"register_keys_preencoded": 0, "register_keys_raw": 0}, None),
])
def test_preencoded_share_reader(counters, want):
    """Nothing from a program that counts neither key kind."""
    got = BENCH.metric_module("register_preencoded_share").read(
        _pass(counters))
    assert got == want
