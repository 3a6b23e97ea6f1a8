"""Real-hardware differential tier (VERDICT r2 item 7): the CPU-vs-
device verdict-parity suites, runnable on the actual chip with

    JEPSEN_TPU_PLATFORM=tpu python -m pytest tests -m tpu -q

The main (CPU-pinned) suite proves kernel math on a virtual mesh; this
tier closes the gap to "verdict parity on TPU". Sizes are moderate —
each test is one or two device dispatches."""

import pytest

from jepsen_tpu.checker import elle, linearizable, models
from jepsen_tpu.checker.elle import kernels as elle_kernels
from jepsen_tpu.checker.elle import synth as elle_synth
from jepsen_tpu.checker.elle import wr as elle_wr
from jepsen_tpu.checker.knossos import analysis
from jepsen_tpu.checker.knossos import dense as kdense
from jepsen_tpu.checker.knossos import synth as ksynth

pytestmark = pytest.mark.tpu


def test_elle_append_parity_on_device():
    hists = [elle_synth.synth_append_history(T=300, K=16, seed=s,
                                             g1c=(s % 3 == 0))
             for s in range(6)]
    cpu = [elle.append_checker(backend="cpu").check({}, h, {})
           for h in hists]
    tpu = [elle.append_checker(backend="tpu").check({}, h, {})
           for h in hists]
    for c, t in zip(cpu, tpu):
        assert c["valid?"] == t["valid?"]
        assert sorted(c["anomaly-types"]) == sorted(t["anomaly-types"])


def test_elle_batched_sweep_parity_on_device():
    from jepsen_tpu import parallel
    encs = [elle_synth.synth_encoded_history(1000, K=32)
            for _ in range(8)]
    encs += [elle_synth.synth_encoded_history(1000, K=32,
                                              inject_cycle=True)]
    flags = parallel.check_bucketed(encs, None)
    assert all(f == {} for f in flags[:8])
    assert "G1c" in flags[8]


def test_knossos_dense_parity_on_device():
    # max_pending keeps every history inside the dense encoder's
    # 14-slot budget (crashed info ops hold slots forever, so 200 ops
    # at 5% info can exceed it otherwise); overflow ROUTING is the next
    # test's job, this one is pure dense-kernel parity
    hists = ksynth.synth_register_batch(B=12, n_ops=200, n_procs=8,
                                        info_prob=0.05, seed=3,
                                        max_pending=12)
    encs = [kdense.encode_dense_history(h) for h in hists]
    device = kdense.check_encoded_dense_batch(encs)
    for h, d in zip(hists, device):
        assert d["valid?"] == analysis(models.cas_register(), h)["valid?"]


def test_knossos_tiered_checker_parity_on_device():
    hists = ksynth.synth_register_batch(B=6, n_ops=150, n_procs=16,
                                        info_prob=0.0, seed=9)
    c = linearizable(models.cas_register(), backend="tpu")
    device = c.check_batch({}, hists, {})
    for h, d in zip(hists, device):
        assert d["valid?"] == analysis(models.cas_register(), h)["valid?"]


def test_condensed_long_history_on_device():
    from jepsen_tpu import parallel
    enc = elle_synth.synth_encoded_history(40_000, K=64)
    assert parallel.check_long_history(enc, dense_limit=10_000) == {}
    enc_bad = elle_synth.synth_encoded_history(40_000, K=64,
                                               inject_cycle=True)
    flags = parallel.check_long_history(enc_bad, dense_limit=10_000)
    assert "G1c" in flags


def test_closure_rounds_measured_on_device():
    """The bench's measured-MFU input: the fixpoint round counter must
    come off the chip within the adversarial bound and reproduce."""
    encs = [elle_synth.synth_encoded_history(1000, K=32)
            for _ in range(4)]
    packed = elle_kernels.pack_batch(encs)
    sh = packed["shape"]
    steps = elle_kernels.closure_steps(sh.n_txns)
    r1 = int(elle_kernels.closure_rounds_device(
        packed["appends"], packed["reads"], n_keys=sh.n_keys,
        max_pos=sh.max_pos, n_txns=sh.n_txns, steps=steps))
    r2 = int(elle_kernels.closure_rounds_device(
        packed["appends"], packed["reads"], n_keys=sh.n_keys,
        max_pos=sh.max_pos, n_txns=sh.n_txns, steps=steps))
    assert 1 <= r1 <= steps
    assert r1 == r2   # deterministic on the same batch


def test_int8_formulation_agrees_on_device():
    """The int8×int8→int32 squaring on the real MXU must give each
    history of a batch the CPU oracle's anomalies."""
    from jepsen_tpu import parallel
    import jax
    import numpy as np
    from jepsen_tpu.checker.elle import cycle_anomalies_cpu
    from jepsen_tpu.checker.elle import encode as elle_encode
    hists = [elle_synth.synth_append_history(T=500, K=32, seed=6 + i,
                                             g1c=(i == 2))
             for i in range(4)]
    encs = [elle_encode.encode_history(h) for h in hists]
    batch = elle_kernels.pack_batch(encs)
    args = parallel.shard_batch(None, batch)
    f = parallel.sharded_check_fn(None, batch["shape"])
    words = np.asarray(jax.block_until_ready(f(*args))).tolist()
    assert [sorted(elle_kernels.flags_to_names(w)) for w in words] \
        == [sorted(cycle_anomalies_cpu(e)) for e in encs]
    assert words[2] & (1 << elle_kernels.G1C)


def test_wr_edge_batch_parity_on_device():
    def hist(txns):
        out = []
        for p, txn in txns:
            for ty in ("invoke", "ok"):
                out.append({"type": ty, "process": p, "f": "txn",
                            "value": txn, "index": len(out),
                            "time": len(out) * 1000})
        return out

    good = hist([(0, [["w", "x", 1]]), (1, [["r", "x", 1]]),
                 (0, [["w", "x", 2]]), (1, [["r", "x", 2]])])
    for h in (good,):
        cpu = elle_wr.rw_register_checker(backend="cpu").check({}, h, {})
        tpu = elle_wr.rw_register_checker(backend="tpu").check({}, h, {})
        assert cpu["valid?"] == tpu["valid?"]
        assert sorted(cpu["anomaly-types"]) == sorted(tpu["anomaly-types"])


def test_packed_frontier_parity_on_device():
    """The packed single-int32 frontier kernel vs the unpacked one vs
    the CPU engine, on the real chip (the packed kernel's sort-traffic
    win is TPU-motivated; its parity must hold there too)."""
    import jax.numpy as jnp

    from jepsen_tpu.checker.knossos import encode as kenc
    from jepsen_tpu.checker.knossos import kernels as kker
    from jepsen_tpu.checker.knossos import packed as kpk

    hists = ksynth.synth_register_batch(B=8, n_ops=200, n_procs=8,
                                        info_prob=0.02, seed=21,
                                        max_pending=10)
    hists += [ksynth.corrupt(h, seed=i) for i, h in enumerate(hists[:4])]
    encs = [kenc.encode_register_history(h) for h in hists]
    batch = kenc.pack_register_batch(encs)
    sh = batch["shape"]
    ev = jnp.asarray(batch["events"])
    pv, po = kpk.check_batch_device_packed(ev, frontier=512,
                                           n_slots=sh.n_slots)
    uv, uo = kker.check_batch_device(ev, frontier=512,
                                     n_slots=sh.n_slots)
    assert list(po) == list(uo)
    for h, p, u, o in zip(hists, list(pv), list(uv), list(po)):
        assert bool(p) == bool(u)
        if not o:
            assert bool(p) == analysis(models.cas_register(), h)["valid?"]


def test_int8_auto_default_on_device():
    """The production bucket path on hardware runs the one (xla-int8)
    formulation and agrees with the CPU oracle verdict-for-verdict."""
    from jepsen_tpu import parallel
    from jepsen_tpu.checker.elle import cycle_anomalies_cpu
    from jepsen_tpu.checker.elle import encode as elle_encode

    assert elle_kernels.CLOSURE_FORMULATION == "xla-int8"
    hists = [elle_synth.synth_append_history(T=300, K=8, seed=i,
                                             g1c=(i % 2 == 0))
             for i in range(4)]
    encs = [elle_encode.encode_history(h) for h in hists]
    auto = parallel.check_bucketed(encs, None)
    assert [sorted(a) for a in auto] \
        == [sorted(cycle_anomalies_cpu(e)) for e in encs]
    assert sum(1 for a in auto if "G1c" in a) == 2