"""Copy-free warm path (ISSUE 7): dispatch-shaped v2 sidecars, donated
device buffers, and the persistent AOT executable cache.

The contract under test, end to end: cold, warm-v1, warm-v2 and
donated-buffer sweeps produce BYTE-IDENTICAL verdicts — including the
OOM-split, watchdog-quarantine and oversized-singleton recovery paths
over v2 sidecars — while the counters prove the warm path stopped
copying: `warm_copy_bytes == 0` on the views path, 100% executable-
cache hits on a repeat sweep, and a drained donation ledger after
every recovery. Plus the format itself: v2 roundtrips exactly, v1
upgrades in place, a torn v2 sidecar rebuilds cleanly, and the pad
plan can never drift from kernels.BatchShape.plan.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from jepsen_tpu import aot, ingest, parallel, store, supervisor, trace
from jepsen_tpu.checker.elle import kernels as K
from jepsen_tpu.checker.elle import synth
from jepsen_tpu.checker.elle.encode import (effective_complete_index,
                                            encode_history,
                                            lean_anomalies)

sys.path.insert(0, os.path.dirname(__file__))

APPEND_FIELDS = ("appends", "reads", "status", "process",
                 "invoke_index", "complete_index")


def write_run(tmp_path, name, hist):
    d = tmp_path / name
    d.mkdir()
    with open(d / "history.jsonl", "w") as f:
        for o in hist:
            f.write(json.dumps(o) + "\n")
    return d


def append_dirs(tmp_path, n=4, T=30, K_=6):
    return [write_run(tmp_path, f"r{i}",
                      synth.synth_append_history(T=T, K=K_, seed=i))
            for i in range(n)]


def lean_encode(hist):
    enc = encode_history(hist)
    enc.anomalies = lean_anomalies(enc)
    enc.txn_ops = []
    return enc


def assert_append_identical(a, b):
    assert (a.n, a.n_keys, a.max_pos) == (b.n, b.n_keys, b.max_pos)
    assert a.key_names == b.key_names
    for f in APPEND_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f
    assert a.anomalies == b.anomalies


def ctr(tr, name):
    return getattr(tr.counter(name), "value", 0) or 0


@pytest.fixture(autouse=True)
def _aot_tmp(tmp_path, monkeypatch):
    """Every test gets its own executable-cache dir and a clean
    in-memory AOT map — no cross-test (or cross-run) executables."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "aot-cache"))
    aot.clear_memory()
    yield
    aot.clear_memory()


def warm_encs(dirs, checker="append"):
    """Encode twice: once to populate sidecars, once to load warm."""
    for d in dirs:
        ingest.encode_run_dir(d, checker)
    out = [ingest.encode_run_dir(d, checker) for d in dirs]
    assert not any(isinstance(e, Exception) for e in out)
    return out


# ---------------------------------------------------------------------------
# The v2 format.
# ---------------------------------------------------------------------------

class TestSidecarV2:
    def test_pad_plan_matches_batchshape(self):
        """store.dispatch_pad_plan (jax-free, for pool workers) must
        agree with kernels.BatchShape.plan on a singleton batch — the
        anti-drift pin for the two pad implementations."""
        for T in (1, 7, 30, 128, 129, 300):
            enc = lean_encode(synth.synth_append_history(T=T, K=5,
                                                         seed=T))
            plan = K.BatchShape.plan([enc])
            pad = store.dispatch_pad_plan(enc)
            assert pad == {"n_txns": plan.n_txns,
                           "n_appends": plan.n_appends,
                           "n_reads": plan.n_reads,
                           "n_keys": plan.n_keys,
                           "max_pos": plan.max_pos}

    def test_v2_roundtrip_identical(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_NATIVE_INGEST", "0")
        (d,) = append_dirs(tmp_path, n=1)
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        cold = ingest.encode_run_dir(d, "append")
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "1")
        ingest.encode_run_dir(d, "append")
        assert (d / "encoded.v2.bin").is_file()
        warm = store.load_encoded(d, "append")
        assert warm is not None and warm.warm
        assert_append_identical(cold, warm)
        # the dispatch views: padded to the singleton plan, dead rows
        # at the pack convention (-1 triples/process, 0 indexes), and
        # the effective completion keys precomputed at device dtype
        pad = store.dispatch_pad_plan(cold)
        assert warm.dispatch_pad == pad
        dv = warm.dispatch
        assert dv["appends"].shape == (pad["n_appends"], 3)
        assert (dv["appends"][len(cold.appends):] == -1).all()
        assert dv["process"].shape == (pad["n_txns"],)
        assert (dv["process"][cold.n:] == -1).all()
        assert (dv["invoke_index"][cold.n:] == 0).all()
        eff = effective_complete_index(
            np.asarray(cold.status, np.int32),
            np.asarray(cold.complete_index, np.int64))
        assert np.array_equal(dv["complete_index"][:cold.n],
                              eff.astype(np.int32))
        assert np.array_equal(dv["invoke_index"][:cold.n],
                              np.asarray(cold.invoke_index, np.int32))

    def test_native_v2_roundtrip(self, tmp_path, monkeypatch):
        from jepsen_tpu import native_lib
        if native_lib.hist_lib() is None:
            pytest.skip("native encoder unavailable")
        (d,) = append_dirs(tmp_path, n=1)
        ingest.encode_run_dir(d, "append")   # native writes v2
        assert (d / "encoded.v2.bin").is_file()
        warm = store.load_encoded(d, "append")
        assert warm is not None and warm.dispatch is not None
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        monkeypatch.setenv("JEPSEN_TPU_NATIVE_INGEST", "0")
        py = ingest.encode_run_dir(d, "append")
        assert_append_identical(py, warm)

    def test_v1_upgrades_in_place(self, tmp_path, monkeypatch):
        (d,) = append_dirs(tmp_path, n=1)
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        v1_enc = ingest.encode_run_dir(d, "append")
        assert (d / "encoded.v1.bin").is_file()
        assert not (d / "encoded.v2.bin").exists()
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "1")
        tr = trace.fresh_run("upgrade")
        up = store.load_encoded(d, "append")
        assert up is not None and up.dispatch is not None
        assert (d / "encoded.v2.bin").is_file()
        assert not (d / "encoded.v1.bin").exists(), \
            "upgrade must retire the v1 sidecar"
        assert ctr(tr, "sidecar_upgrades") == 1
        assert_append_identical(v1_enc, up)
        # second load: plain v2 hit, no second upgrade
        again = store.load_encoded(d, "append")
        assert again is not None and ctr(tr, "sidecar_upgrades") == 1

    def test_upgrade_readonly_serves_v1(self, tmp_path, monkeypatch):
        (d,) = append_dirs(tmp_path, n=1)
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        ingest.encode_run_dir(d, "append")
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "1")
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE_WRITE", "0")
        enc = store.load_encoded(d, "append")
        assert enc is not None, "read-only mount must still hit v1"
        assert getattr(enc, "dispatch", None) is None
        assert (d / "encoded.v1.bin").is_file()

    def test_torn_v2_rebuilds_cleanly(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_NATIVE_INGEST", "0")
        (d,) = append_dirs(tmp_path, n=1)
        fresh = ingest.encode_run_dir(d, "append")
        sc = d / "encoded.v2.bin"
        raw = sc.read_bytes()
        for corrupt in (raw[: len(raw) // 3],           # truncated
                        b"JUNKJUNK" + raw[8:],          # bad magic
                        raw[:16] + b"\xff" * 32 + raw[48:]):  # torn hdr
            sc.write_bytes(corrupt)
            assert store.load_encoded(d, "append") is None
            got = ingest.encode_run_dir(d, "append")
            assert_append_identical(fresh, got)
            assert store.load_encoded(d, "append") is not None, \
                "re-encode must leave a valid sidecar behind"

    def test_gate_off_pins_v1(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        (d,) = append_dirs(tmp_path, n=1)
        ingest.encode_run_dir(d, "append")
        assert (d / "encoded.v1.bin").is_file()
        assert not (d / "encoded.v2.bin").exists()
        enc = store.load_encoded(d, "append")
        assert enc is not None and getattr(enc, "dispatch", None) is None

    def test_wr_stays_v1(self, tmp_path):
        import random

        from test_fuzz_differential import rand_wr_history
        hist = rand_wr_history(random.Random(3), T=40, K=4, conc=4)
        d = write_run(tmp_path, "wr0", hist)
        ingest.encode_run_dir(d, "wr")
        assert (d / "encoded-wr.v1.bin").is_file()
        enc = store.load_encoded(d, "wr")
        assert enc is not None and getattr(enc, "dispatch", None) is None


# ---------------------------------------------------------------------------
# The copy-free pack path.
# ---------------------------------------------------------------------------

class TestPackViews:
    def test_views_pack_matches_copy_pack(self, tmp_path):
        """The device-side tensors the views path assembles (device_put
        per view + on-device ragged padding + stack) must equal the
        host-copied pack_batch tensors element for element — including
        a bucket mixing pad geometries (ragged minor axes)."""
        dirs = append_dirs(tmp_path, n=3, T=30)
        dirs += [write_run(tmp_path, "big",
                           synth.synth_append_history(T=160, K=6,
                                                      seed=77))]
        encs = warm_encs(dirs)
        assert all(e.dispatch is not None for e in encs)
        shape = K.BatchShape.plan(encs)
        views = K.pack_batch_views(encs, shape)
        assert views is not None and views["views"]
        packed = K.pack_batch(encs, shape)
        args_v = parallel.shard_batch(None, views)
        args_c = parallel.shard_batch(None, packed)
        for a, b, name in zip(args_v, args_c,
                              ("appends", "reads", "invoke",
                               "complete", "process", "n_txns")):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name

    def test_cold_and_foreign_shapes_fall_back(self, tmp_path):
        # cold encodings never view-pack
        cold = [lean_encode(synth.synth_append_history(T=30, K=6,
                                                       seed=i))
                for i in range(2)]
        assert K.pack_batch_views(
            cold, K.BatchShape.plan(cold)) is None
        # and a view claiming a geometry BEYOND the bucket's falls back
        dirs = append_dirs(tmp_path, n=2, T=30)
        encs = warm_encs(dirs)
        shape = K.BatchShape.plan(encs)
        encs[0].dispatch_pad = dict(encs[0].dispatch_pad,
                                    n_txns=shape.n_txns * 2)
        assert K.pack_batch_views(encs, shape) is None

    def test_warm_sweep_copies_zero_bytes(self, tmp_path):
        dirs = append_dirs(tmp_path, n=4, T=30)
        base = parallel.check_bucketed(
            [lean_encode(synth.synth_append_history(T=30, K=6, seed=i))
             for i in range(4)])
        encs = warm_encs(dirs)
        tr = trace.fresh_run("warm-zero")
        got = parallel.check_bucketed(encs)
        assert got == base
        assert ctr(tr, "warm_copy_bytes") == 0
        assert ctr(tr, "h2d_bytes") > 0

    def test_v1_warm_sweep_counts_copies(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        dirs = append_dirs(tmp_path, n=4, T=30)
        encs = warm_encs(dirs)
        assert all(getattr(e, "warm", False) for e in encs)
        tr = trace.fresh_run("warm-v1")
        parallel.check_bucketed(encs)
        assert ctr(tr, "warm_copy_bytes") > 0, \
            "v1 warm packs must attribute their host copies"


# ---------------------------------------------------------------------------
# Donated buffers + the slot ledger.
# ---------------------------------------------------------------------------

class TestDonation:
    def test_donated_sweep_parity_and_ledger(self, tmp_path,
                                             monkeypatch):
        dirs = append_dirs(tmp_path, n=5, T=30)
        encs = warm_encs(dirs)
        monkeypatch.setenv("JEPSEN_TPU_DONATE_BUFFERS", "0")
        base = parallel.check_bucketed(warm_encs(dirs))
        monkeypatch.setenv("JEPSEN_TPU_DONATE_BUFFERS", "1")
        tr = trace.fresh_run("donate")
        got = parallel.check_bucketed(encs)
        assert got == base
        bd = ctr(tr, "buffers_donated")
        assert bd > 0 and bd % 6 == 0
        assert supervisor.slot_ledger.inflight() == 0

    def test_oom_split_drops_and_replans_slots(self, tmp_path,
                                               monkeypatch):
        from test_supervisor import arm
        dirs = append_dirs(tmp_path, n=6, T=30)
        base = parallel.check_bucketed(warm_encs(dirs))
        arm(monkeypatch, "oom:first")
        tr = trace.fresh_run("donate-oom")
        got = parallel.check_bucketed(warm_encs(dirs))
        assert got == base
        assert ctr(tr, "bucket_splits") >= 1
        assert supervisor.slot_ledger.inflight() == 0, \
            "a split bucket leaked its donated slot"

    def test_watchdog_quarantine_releases_slot(self, tmp_path,
                                               monkeypatch):
        dirs = append_dirs(tmp_path, n=3, T=30)
        encs = warm_encs(dirs)
        monkeypatch.setenv("JEPSEN_TPU_DISPATCH_TIMEOUT_S", "0.05")
        release = threading.Event()

        def wedged(_flags):
            release.wait(2.0)
            return np.zeros(len(encs), np.int64)

        monkeypatch.setattr(parallel.jax, "block_until_ready", wedged)
        tr = trace.fresh_run("donate-watchdog")
        got = parallel.check_bucketed(encs)
        release.set()
        assert all(isinstance(g, supervisor.Quarantined) for g in got)
        assert all(g.stage == "watchdog" for g in got)
        assert supervisor.slot_ledger.inflight() == 0, \
            "a quarantined bucket leaked its donated slot"
        assert ctr(tr, "quarantined") == len(encs)

    def test_oversized_singleton_over_v2(self, tmp_path):
        """A history too big for the per-slot budget dispatches alone
        (strictly after the pipeline drains) — over v2 sidecars, with
        donation on, verdicts identical and nothing leaks."""
        dirs = append_dirs(tmp_path, n=3, T=30)
        dirs.append(write_run(
            tmp_path, "huge",
            synth.synth_append_history(T=300, K=6, seed=99)))
        cold = [lean_encode(synth.synth_append_history(T=30, K=6,
                                                       seed=i))
                for i in range(3)]
        cold.append(lean_encode(
            synth.synth_append_history(T=300, K=6, seed=99)))
        budget = 2 * 384 * 384   # the T=300 history alone exceeds /2
        base = parallel.check_bucketed(cold, budget_cells=budget)
        got = parallel.check_bucketed(warm_encs(dirs),
                                      budget_cells=budget)
        assert got == base
        assert supervisor.slot_ledger.inflight() == 0


# ---------------------------------------------------------------------------
# The AOT executable cache.
# ---------------------------------------------------------------------------

class TestAotCache:
    def test_sharding_is_part_of_the_key(self, tmp_path):
        """One cache dir, the same avals compiled once for 8-shard
        inputs on the virtual mesh and once for one device: both run,
        and neither is ever loaded for the other (the seed's 'Expected
        args to execute_sharded_on_local_devices to have 8 shards')."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        enc = synth.synth_encoded_history(64, K=8, inject_cycle=True)
        shape = K.BatchShape.plan([enc] * 8)
        packed = K.pack_batch([enc] * 8, shape)
        fn = parallel.sharded_check_fn(None, shape)
        devs = jax.devices()
        assert len(devs) == 8
        mesh = Mesh(np.asarray(devs), ("dp",))
        single = parallel.shard_batch(None, packed)
        sharded = tuple(jax.device_put(a, NamedSharding(mesh, P("dp")))
                        for a in single)
        kp = ("sharding-key-test",)
        assert aot._fingerprint(fn, sharded, kp) \
            != aot._fingerprint(fn, single, kp)
        want = np.asarray(fn(*single))
        assert all(w & (1 << K.G1C) for w in want)
        for _ in range(2):          # cold, then from disk alone
            for args in (sharded, single):
                exe = aot.compiled_for(fn, args, kp)
                assert np.asarray(exe(*args)).tolist() == want.tolist()
            aot.clear_memory()
        assert len(list(aot.cache_dir().glob("*.jtx"))) == 2

    def test_mesh_shape_is_part_of_the_key(self):
        """A 2x2 dp x mp mesh and a 4x1 dp mesh over the same four
        devices, with the same P('dp') inputs, compile different
        executables (the closure is constrained over `mp`): their
        fingerprints differ."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        enc = synth.synth_encoded_history(64, K=8)
        shape = K.BatchShape.plan([enc] * 4)
        single = parallel.shard_batch(None, K.pack_batch([enc] * 4, shape))
        devs = np.asarray(jax.devices()[:4])
        kp = ("mesh-key-test",)
        prints = []
        for mesh in (Mesh(devs.reshape(2, 2), ("dp", "mp")),
                     Mesh(devs.reshape(4, 1), ("dp", "mp"))):
            fn = parallel.sharded_check_fn(mesh, shape)
            args = tuple(jax.device_put(a, NamedSharding(mesh, P("dp")))
                         for a in single)
            prints.append(aot._fingerprint(fn, args, kp))
            assert str(tuple(mesh.shape.items())) \
                in aot._sharding_key(args[0])
        assert prints[0] != prints[1]

    @pytest.mark.parametrize("layer", ["memory", "disk"])
    def test_mesh_pass_resolves_through_aot(self, layer):
        """Two check_bucketed passes on a 2x2 dp x mp mesh over buckets
        of several geometries, every jitted wrapper evicted between
        them (and, for `disk`, the in-memory executables too, so the
        disk layer alone answers): the second pass finds each bucket's
        executable by fingerprint — no miss, no trace — and its
        verdicts are the single-device ones."""
        import jax
        from jepsen_tpu import jaxtrace
        jaxtrace.install()
        mesh = parallel.make_mesh(jax.devices()[:4])
        assert dict(mesh.shape) == {"dp": 2, "mp": 2}
        # padded to 512, 384 and 128 txns; at this budget (two buckets
        # in flight, each at most 2 x 512^2 cells) one bucket each
        encs = [synth.synth_encoded_history(T, K=6, inject_cycle=c)
                for T in (450, 300, 40) for c in (False, True)]
        budget = 2 * 2 * 512 * 512
        want = parallel.check_bucketed(encs)
        tr = trace.fresh_run("mesh-cold")
        assert parallel.check_bucketed(encs, mesh,
                                       budget_cells=budget) == want
        assert ctr(tr, "compile_cache_misses") \
            == ctr(tr, "buckets_dispatched") == 3
        parallel._sharded_check_fn_cached.cache_clear()
        if layer == "disk":
            aot.clear_memory()
        tr = trace.fresh_run(f"mesh-warm-{layer}")
        assert parallel.check_bucketed(encs, mesh,
                                       budget_cells=budget) == want
        assert ctr(tr, "compile_cache_misses") == 0
        assert ctr(tr, "compile_cache_hits") \
            == ctr(tr, "buckets_dispatched") == 3
        assert ctr(tr, "jit_traces") == 0

    def test_repeat_sweep_all_hits(self, tmp_path):
        dirs = append_dirs(tmp_path, n=4, T=30)
        encs = warm_encs(dirs)
        tr = trace.fresh_run("aot-cold")
        base = parallel.check_bucketed(encs)
        assert ctr(tr, "compile_cache_misses") >= 1
        cache_files = list((tmp_path / "aot-cache" / "executables").glob("*.jtx"))
        assert cache_files, "misses must persist executables to disk"
        # fresh in-memory state = a fresh process; only the disk layer
        # can answer now
        aot.clear_memory()
        tr = trace.fresh_run("aot-warm")
        got = parallel.check_bucketed(warm_encs(dirs))
        assert got == base
        assert ctr(tr, "compile_cache_misses") == 0
        assert ctr(tr, "compile_cache_hits") >= 1

    def test_gate_off_compiles_plainly(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_AOT_CACHE", "0")
        dirs = append_dirs(tmp_path, n=3, T=30)
        tr = trace.fresh_run("aot-off")
        parallel.check_bucketed(warm_encs(dirs))
        assert ctr(tr, "compile_cache_hits") == 0
        assert ctr(tr, "compile_cache_misses") == 0
        assert not list((tmp_path / "aot-cache" / "executables").glob("*.jtx"))

    def test_corrupt_entry_degrades_to_compile(self, tmp_path):
        dirs = append_dirs(tmp_path, n=3, T=30)
        encs = warm_encs(dirs)
        base = parallel.check_bucketed(encs)
        for f in (tmp_path / "aot-cache" / "executables").glob("*.jtx"):
            f.write_bytes(b"not a pickled executable")
        aot.clear_memory()
        tr = trace.fresh_run("aot-corrupt")
        got = parallel.check_bucketed(warm_encs(dirs))
        assert got == base
        assert ctr(tr, "compile_cache_misses") >= 1

    def test_cache_dir_follows_jax_compilation_cache_dir(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "elsewhere"))
        assert aot.cache_root() == tmp_path / "elsewhere"
        assert aot.cache_dir() == tmp_path / "elsewhere" / "executables"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = Path(__file__).resolve().parent.parent
        assert aot.cache_root() == repo / ".jax_cache"
        assert aot.cache_dir() == repo / ".jax_cache" / "executables"


# ---------------------------------------------------------------------------
# The differential parity floor.
# ---------------------------------------------------------------------------

class TestDifferentialParity:
    def test_cold_warm_v1_v2_donated_identical(self, tmp_path,
                                               monkeypatch):
        """The acceptance matrix: every warm/donated combination's
        verdicts byte-identical to the cold sweep's."""
        dirs = append_dirs(tmp_path, n=5, T=30)
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        cold_encs = [ingest.encode_run_dir(d, "append") for d in dirs]
        cold = parallel.check_bucketed(cold_encs)
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "1")

        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        warm_v1 = parallel.check_bucketed(warm_encs(dirs))
        assert warm_v1 == cold

        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "1")
        warm_v2 = parallel.check_bucketed(warm_encs(dirs))
        assert warm_v2 == cold

        for donate in ("0", "1"):
            monkeypatch.setenv("JEPSEN_TPU_DONATE_BUFFERS", donate)
            assert parallel.check_bucketed(warm_encs(dirs)) == cold
        assert supervisor.slot_ledger.inflight() == 0

    def test_oom_split_over_v2_identical(self, tmp_path, monkeypatch):
        from test_supervisor import arm
        dirs = append_dirs(tmp_path, n=6, T=30)
        base = parallel.check_bucketed(warm_encs(dirs))
        arm(monkeypatch, "oom:first")
        tr = trace.fresh_run("v2-oom")
        got = parallel.check_bucketed(warm_encs(dirs))
        assert got == base
        assert ctr(tr, "oom_retries") >= 1

    def test_pooled_v1_upgrade_relays_telemetry(self, tmp_path,
                                                monkeypatch):
        """v1→v2 upgrades inside spawn-pool workers must still land in
        the PARENT's sidecar_upgrades counter (worker tracers are
        process-local and never exported — the einfo relay carries
        the upgrade home)."""
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        dirs = append_dirs(tmp_path, n=4, T=30)
        for d in dirs:
            ingest.encode_run_dir(d, "append")
            assert (d / "encoded.v1.bin").is_file()
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "1")
        tr = trace.fresh_run("pooled-upgrade")
        got = [e for chunk in ingest.iter_encode_chunks(
            dirs, "append", chunk=2, processes=2) for _d, e in chunk]
        assert len(got) == len(dirs)
        assert all(not (d / "encoded.v1.bin").exists() for d in dirs)
        assert ctr(tr, "sidecar_upgrades") == len(dirs)

    def test_sidecar_ref_transport_parity(self, tmp_path):
        """The pooled warm path: workers send sidecar REFERENCES, the
        parent mmaps — encodings and verdicts identical to the serial
        path, and the refs carry dispatch views."""
        dirs = append_dirs(tmp_path, n=4, T=30)
        serial = warm_encs(dirs)
        chunks = list(ingest.iter_encode_chunks(
            dirs, "append", chunk=2, processes=2))
        pooled = [e for chunk in chunks for _d, e in chunk]
        assert len(pooled) == len(serial)
        for a, b in zip(serial, pooled):
            assert_append_identical(a, b)
        assert all(getattr(e, "dispatch", None) is not None
                   for e in pooled), \
            "pooled warm hits must carry the parent's mmap views"
