"""Runs of each cell at a tiny size on the CPU, past the harness's look
for a chip: a sound run comes out correct, and with the timed path
broken underneath (an answer altered where it is produced, half of the
histories left out) `correct` comes out false. The control, each
workload's plain checker with one stated guarantee broken, put in the
program's place, fails the same comparison."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

import run as bench_run  # noqa: E402
from harness import spec, stores, verify  # noqa: E402

SMALL = {
    "append-sweep": {"txns_per_history": 1000, "runs_per_store": 8},
    "register-sweep": {"ops_per_key": 90, "keys_per_run": 6,
                       "concurrency": 20, "runs_per_store": 8},
    "append-serve": {"txns_per_history": 1000},
    "append-sweep-4chip": {"txns_per_history": 600, "runs_per_store": 16},
}


def execute(tmp_path, capsys, cell, seconds=1.0):
    import jax
    b = spec.Benchmark()
    args = NS(seed=2**40 + 3, seconds=seconds, trace=0)
    ctx = bench_run.Context(b, b.cells[cell], args,
                            jax.devices()[:b.cells[cell]["chips"]])
    ctx.config.update(SMALL[cell])
    ctx.traffic.update({"rate_per_s": 4.0, "warm_requests": 4,
                        "reference_valid": 3})
    ctx.work = tmp_path / "work"
    bench_run.execute(ctx)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(out)
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_a_sound_run_is_correct(tmp_path, capsys, cell):
    import jax
    if len(jax.devices()) < spec.Benchmark().cells[cell]["chips"]:
        pytest.skip("needs as many (virtual) devices as the cell's chips")
    line = execute(tmp_path, capsys, cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def _flip(res: dict) -> dict:
    return {**res, "valid?": not res.get("valid?")}


def test_an_altered_append_verdict_is_caught(tmp_path, capsys,
                                             monkeypatch):
    from jepsen_tpu.checker import elle
    render = elle.render_verdict
    monkeypatch.setattr(elle, "render_verdict",
                        lambda *a, **k: _flip(render(*a, **k)))
    line = execute(tmp_path, capsys, "append-sweep")
    assert line["correct"] is False
    assert line["checks"]["wrong_vs_truth"]["value"] > 0


def test_half_the_store_left_out_is_caught(tmp_path, capsys, monkeypatch):
    from jepsen_tpu.store import Store
    walk = Store.iter_run_dirs

    def half(self, *a, **k):
        return (d for i, d in enumerate(walk(self, *a, **k)) if i % 2)

    monkeypatch.setattr(Store, "iter_run_dirs", half)
    line = execute(tmp_path, capsys, "append-sweep")
    assert line["correct"] is False
    assert line["checks"]["missing"]["value"] > 0


def test_the_exchange_between_chips_left_out_is_caught(tmp_path, capsys,
                                                      monkeypatch):
    """On a mesh, each bucket's flags come back sharded over the chips;
    where only the first chip's shard arrives and stands in for the
    others, verdicts go wrong."""
    import jax
    import numpy as np
    from jepsen_tpu import parallel
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    block = parallel._block_flags

    def first_chip_only(flags, tr):
        got = block(flags, tr)
        shards = getattr(got, "addressable_shards", ())
        if len(shards) < 2:
            return got
        first = np.asarray(shards[0].data)
        reps = -(-got.shape[0] // first.shape[0])
        return np.concatenate([first] * reps)[:got.shape[0]]

    monkeypatch.setattr(parallel, "_block_flags", first_chip_only)
    line = execute(tmp_path, capsys, "append-sweep-4chip")
    assert line["correct"] is False


def test_an_altered_register_verdict_is_caught(tmp_path, capsys,
                                               monkeypatch):
    from jepsen_tpu.checker import Linearizable
    batch = Linearizable.check_batch

    def altered(self, *a, **k):
        out = batch(self, *a, **k)
        return [_flip(out[0])] + out[1:]

    monkeypatch.setattr(Linearizable, "check_batch", altered)
    line = execute(tmp_path, capsys, "register-sweep")
    assert line["correct"] is False


def test_an_altered_serve_verdict_is_caught(tmp_path, capsys,
                                            monkeypatch):
    from jepsen_tpu.parallel import folding
    verdicts = folding.FoldDispatcher.verdicts
    monkeypatch.setattr(
        folding.FoldDispatcher, "verdicts",
        lambda self, *a, **k: [_flip(r) for r in verdicts(self, *a, **k)])
    line = execute(tmp_path, capsys, "append-serve")
    assert line["correct"] is False


def test_unanswered_serve_requests_are_caught(tmp_path, capsys,
                                              monkeypatch):
    from jepsen_tpu.serve.daemon import VerdictDaemon
    drivers = spec.Benchmark().driver_module({"driver": "serve"})
    monkeypatch.setattr(drivers, "GRACE_S", 2.0)
    on_check = VerdictDaemon._on_check
    seen = []

    def drop_half(self, conn, frame):
        seen.append(frame["id"])
        if frame["id"].startswith("warm") or len(seen) % 2:
            on_check(self, conn, frame)

    monkeypatch.setattr(VerdictDaemon, "_on_check", drop_half)
    line = execute(tmp_path, capsys, "append-serve")
    assert line["correct"] is False
    assert line["checks"]["missing"]["value"] > 0


@pytest.mark.parametrize("config,broken", [
    ("etcd-append-10k", {"g1c_blind": True}),
    ("etcd-cas-register", {"stale_ok": True})])
def test_the_control_fails_the_comparison(tmp_path, config, broken):
    """The plain checker with one guarantee broken, in the program's
    place, on the seeded invalid runs and a few valid ones."""
    b = spec.Benchmark()
    cfg = {**b.config(config),
           **SMALL["append-sweep" if "append" in config
                   else "register-sweep"]}
    wl = b.workload_module(cfg)
    truth = stores.generate(wl, cfg, tmp_path, 2**41 + 9, 16)
    picked = verify.sample(truth, 1, 4, 2)
    ref = {n: wl.check(tmp_path / n / "history.jsonl") for n in picked}
    control = {n: wl.check(tmp_path / n / "history.jsonl", **broken)
               for n in picked}
    c = verify.compare(wl, control, {n: truth[n] for n in picked}, ref)
    assert c["wrong_vs_reference"] >= 1 and c["wrong_vs_truth"] >= 1
    sound = verify.compare(wl, ref, {n: truth[n] for n in picked}, ref)
    assert sound == {"missing": 0, "wrong_vs_truth": 0,
                     "wrong_vs_reference": 0}
