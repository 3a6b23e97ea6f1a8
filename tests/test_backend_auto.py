"""The device kernels are the DEFAULT analysis path (the north star's
`:backend :tpu` flag, jepsen/src/jepsen/checker.clj:188-219): every
checker constructor defaults backend="auto", which resolves to the
device engine when an accelerator is reachable (or JEPSEN_TPU_BACKEND
forces it) and to the CPU oracle otherwise — and a full dummy-remote
etcd run's analyze phase actually routes through the device kernels.

Also covers the detect-then-classify two-pass in the bucketed batch
sweep (the production analyze-store path)."""

import json
import threading
from http.server import HTTPServer

import numpy as np
import pytest

from jepsen_tpu import core, devices, parallel
from jepsen_tpu.checker.elle import synth
from jepsen_tpu.store import Store
from jepsen_tpu.suites import etcd
from tests.test_suites import FakeEtcd


# --------------------------------------------------------------------------
# resolve_backend
# --------------------------------------------------------------------------

def test_resolve_backend_explicit_passthrough(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
    assert devices.resolve_backend("cpu") == "cpu"   # explicit beats env
    assert devices.resolve_backend("tpu") == "tpu"


def test_resolve_backend_auto_no_accelerator(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_BACKEND", raising=False)
    # conftest pins the cpu platform: no accelerator reachable
    assert devices.resolve_backend("auto") == "cpu"


def test_resolve_backend_auto_with_accelerator(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_BACKEND", raising=False)
    monkeypatch.setattr(devices, "accelerator_available", lambda: True)
    assert devices.resolve_backend("auto") == "tpu"


def test_resolve_backend_env_override(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
    assert devices.resolve_backend("auto") == "tpu"
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "cpu")
    assert devices.resolve_backend("auto") == "cpu"


class _FakeDevice:
    def __init__(self, platform: str, id: int = 0,
                 device_kind: str = "fake"):
        self.platform, self.id, self.device_kind = platform, id, device_kind


def test_auto_resolves_from_in_process_platform(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_BACKEND", raising=False)
    monkeypatch.setattr(devices, "default_devices",
                        lambda: [_FakeDevice("tpu")])
    assert devices.device_platform() == "tpu"
    assert devices.resolve_backend("auto") == "tpu"
    monkeypatch.setattr(devices, "default_devices",
                        lambda: [_FakeDevice("cpu")])
    assert devices.resolve_backend("auto") == "cpu"


def test_default_devices_never_substitutes_cpu(monkeypatch):
    """One accelerator where eight were wanted stays one accelerator:
    host CPU devices never stand in for a chip."""
    import jax
    monkeypatch.delenv("JEPSEN_TPU_PLATFORM", raising=False)
    tpu = [_FakeDevice("tpu")]
    cpus = [_FakeDevice("cpu", i) for i in range(8)]
    monkeypatch.setattr(
        jax, "devices",
        lambda backend=None: cpus if backend == "cpu" else tpu)
    got = devices.default_devices()
    assert got == tpu
    assert all(d.platform != "cpu" for d in got)
    assert devices.accelerator_available() is True


def test_device_peak_raises_on_unknown_kind():
    from jepsen_tpu.checker.elle import kernels as K
    with pytest.raises(KeyError):
        K.device_peak("TPU v99 ultra")
    with pytest.raises(KeyError):
        K.device_peak()       # this host's CPU has no peak row


def test_tpu_backend_never_routes_eligible_model_to_cpu(monkeypatch):
    """backend="tpu" with the nil-initial CAS register: every history
    goes down the device tiers, none to the CPU engine."""
    from jepsen_tpu.checker import Linearizable, linearizable, models
    from jepsen_tpu.checker.knossos import synth as ksynth

    def no_cpu(self, h, search_stats=None):
        raise AssertionError("CPU engine ran under backend=tpu")

    monkeypatch.setattr(Linearizable, "_cpu", no_cpu)
    hists = [ksynth.synth_register_history(n_ops=40, n_procs=4,
                                           info_prob=0.0, seed=s)
             for s in range(3)]
    res = linearizable(models.cas_register(),
                       backend="tpu").check_batch({}, hists, {})
    assert [r["valid?"] for r in res] == [True] * 3


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "script-alone"])
def test_chip_smoke_refuses_without_a_chip(tmp_path, alone):
    """chip_smoke.py under JAX_PLATFORMS=cpu, and copied alone into an
    empty directory: non-zero exit, no `ok` line."""
    import os
    import shutil
    import subprocess
    import sys
    from pathlib import Path
    script = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    p = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


def test_default_constructors_are_auto():
    from jepsen_tpu import checker as jchecker
    from jepsen_tpu.checker import elle
    from jepsen_tpu.checker.elle import wr
    assert jchecker.linearizable().backend == "auto"
    assert elle.append_checker().backend == "auto"
    assert wr.rw_register_checker().backend == "auto"


# --------------------------------------------------------------------------
# the etcd suite's analyze phase takes the device route
# --------------------------------------------------------------------------

@pytest.fixture()
def fake_etcd():
    FakeEtcd.store = {}
    srv = HTTPServer(("127.0.0.1", 0), FakeEtcd)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield srv.server_address[1]
    srv.shutdown()


def test_etcd_dummy_run_analyze_routes_device_kernels(
        tmp_path, fake_etcd, monkeypatch):
    """A dummy-remote etcd run (fake in-process etcd) with the forced
    device backend: the linearizability verdict must come out of the
    dense-bitset device kernel, not the CPU WGL engine."""
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
    monkeypatch.setattr(etcd, "client_url",
                        lambda node: f"http://127.0.0.1:{fake_etcd}")
    from jepsen_tpu.checker.knossos import dense
    batches = []
    orig = dense.check_encoded_dense_batch

    def spy(encs, *a, **kw):
        batches.append(len(encs))
        return orig(encs, *a, **kw)

    monkeypatch.setattr(dense, "check_encoded_dense_batch", spy)

    # short nemesis-interval keeps fault ops inside the window (drain
    # now interrupts in-flight sleeps, so a long interval would merely
    # be a no-op nemesis, not a hang)
    t = etcd.etcd_test({"time-limit": 2, "ops-per-key": 15,
                        "threads-per-key": 2, "nemesis-interval": 1})
    t.update(nodes=["n1", "n2", "n3"], concurrency=2,
             ssh={"dummy": True}, store=Store(tmp_path / "store"))
    t = core.run(t)
    assert t["results"]["valid?"] is True
    assert t["results"]["indep"]["valid?"] is True
    assert sum(batches) > 0, "analyze never reached the device kernel"


# --------------------------------------------------------------------------
# detect-then-classify two-pass
# --------------------------------------------------------------------------

def _encs(n_good: int, n_bad: int, T: int = 96, K: int = 8):
    out = [synth.synth_encoded_history(T, K=K) for _ in range(n_good)]
    out += [synth.synth_encoded_history(T, K=K, inject_cycle=True)
            for _ in range(n_bad)]
    return out


def test_strategies_agree():
    encs = _encs(6, 2)
    fused = parallel.check_bucketed(encs, None)   # default: fused
    two = parallel.check_bucketed(encs, None, two_pass=True)
    one = parallel.check_bucketed(encs, None, two_pass=False,
                                  fused=False)
    assert fused == two == one
    assert all(f == {} for f in fused[:6])
    assert all("G1c" in f for f in fused[6:])


def test_fused_default_is_single_dispatch(monkeypatch):
    """The fused default dispatches each bucket ONCE in classify mode
    (the classification closures stay behind the kernel's lax.cond) —
    no detect pre-pass, no re-dispatch of positives."""
    calls = []
    orig = parallel.sharded_check_fn

    def spy(mesh, shape, **kw):
        calls.append(kw.get("classify"))
        return orig(mesh, shape, **kw)

    monkeypatch.setattr(parallel, "sharded_check_fn", spy)
    out = parallel.check_bucketed(_encs(5, 1), None)
    assert all(f == {} for f in out[:5]) and "G1c" in out[5]
    assert calls == [True], calls


def test_two_pass_all_valid_skips_classify(monkeypatch):
    """With the explicit two-pass strategy an all-valid sweep never
    runs a classify dispatch: every dispatch is detect-mode."""
    calls = []
    orig = parallel.sharded_check_fn

    def spy(mesh, shape, **kw):
        calls.append(kw.get("classify"))
        return orig(mesh, shape, **kw)

    monkeypatch.setattr(parallel, "sharded_check_fn", spy)
    out = parallel.check_bucketed(_encs(5, 0), None, two_pass=True)
    assert all(f == {} for f in out)
    assert calls and not any(calls), calls


def test_analyze_store_backend_cpu_routes_host_oracle(
        tmp_path, monkeypatch, capsys):
    """An explicit --backend cpu (exported as JEPSEN_TPU_BACKEND) must
    run the batch sweep on the host oracle, not the device kernels."""
    from jepsen_tpu import cli
    from jepsen_tpu.checker.elle.synth import synth_append_history
    from jepsen_tpu.history import history_to_edn
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "cpu")

    def boom(*a, **kw):
        raise AssertionError("device sweep ran under --backend cpu")

    monkeypatch.setattr(parallel, "check_bucketed", boom)
    store = Store(tmp_path / "store")
    for ts, kw in [("20260730T000000", {}),
                   ("20260730T000001", {"g1c": True})]:
        d = store.base / "etcd" / ts
        d.mkdir(parents=True)
        (d / "history.edn").write_text(history_to_edn(
            synth_append_history(T=60, K=6, seed=4, **kw)))
    rc = cli.analyze_store(store, checker="append")
    assert rc == 1
    import json as _json
    lines = [_json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["valid?"] is True
    assert lines[1]["valid?"] is False


def test_two_pass_on_mesh():
    mesh = parallel.make_mesh()
    encs = _encs(9, 1)
    out = parallel.check_bucketed(encs, mesh)
    assert all(f == {} for f in out[:9])
    assert "G1c" in out[9]
