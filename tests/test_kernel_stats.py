"""Kernel search telemetry (ISSUE 15, JEPSEN_TPU_KERNEL_STATS).

The core contract, pinned three ways:

  * verdicts are BYTE-identical with the gate on vs off, across the
    cold / warm-sidecar / donated / mesh / serve(fold) dispatch
    matrix (stats ride beside results, never inside them);
  * golden stats on synthetic histories with KNOWN graph shape: a
    seeded G1c cycle reports its exact SCC size and edge counts (the
    CPU oracle's graph), a serial linearizable register history
    reports zero WGL backtracks;
  * off is free: zero new files, no AOT-key churn, sub-µs per
    dispatch for the added code path — the costdb's contract.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]

from jepsen_tpu import parallel, store as store_mod  # noqa: E402
from jepsen_tpu.checker.elle import graph as g  # noqa: E402
from jepsen_tpu.checker.elle import kernels as K  # noqa: E402
from jepsen_tpu.checker.elle import synth  # noqa: E402
from jepsen_tpu.checker.elle.encode import encode_history  # noqa: E402
from jepsen_tpu.obs import search as search_obs  # noqa: E402


def _encs(n=5, T=50, cyclic=(2,)):
    return [encode_history(synth.synth_append_history(
        T=T, K=6, seed=s, g1c=(s in cyclic))) for s in range(n)]


def _oracle_graph(enc):
    """Distinct-edge counts per class + SCC shape from the CPU oracle."""
    edges = set(g.build_edges(enc))
    counts = Counter(ty for _s, _d, ty in edges)
    scc = g.tarjan_scc(enc.n, g.adjacency(enc.n, edges))
    sizes = np.bincount(np.asarray(scc))
    big = sizes[sizes >= 2]
    return counts, big


class TestGoldenStats:
    def test_seeded_g1c_matches_cpu_oracle_exactly(self):
        """The acceptance pin: SCC/edge values equal the CPU oracle's
        graph on a history with KNOWN shape (one seeded 2-txn G1c
        cycle from checker.elle.synth)."""
        encs = _encs()
        souts: list = []
        res = K.check_encoded_batch(encs, stats_out=souts)
        for enc, sd, cy in zip(encs, souts, res):
            counts, big = _oracle_graph(enc)
            assert sd["ww_edges"] == counts.get(g.WW, 0)
            assert sd["wr_edges"] == counts.get(g.WR, 0)
            assert sd["rw_edges"] == counts.get(g.RW, 0)
            assert sd["rt_edges"] == 0 and sd["proc_edges"] == 0
            assert sd["scc_count"] == len(big)
            assert sd["scc_max"] == (big.max() if len(big) else 0)
            assert sd["cycle_txns"] == (big.sum() if len(big) else 0)
            assert (sd["cycle_txns"] > 0) == bool(cy)
        # the seeded cycle is a direct 2-txn mutual observation:
        # visible in the raw edge set (margin 0), SCC of exactly 2
        bad = souts[2]
        assert (bad["scc_count"], bad["scc_max"], bad["scc_min"],
                bad["cycle_txns"]) == (1, 2, 2, 2)
        assert bad["cycle_round"] == 0 and bad["margin"] == 0
        # valid histories: no cycle ever, margin = rounds to fixpoint
        ok = souts[0]
        assert ok["cycle_round"] == -1
        assert ok["margin"] == ok["closure_rounds"] >= 1
        assert 0 < ok["closure_rounds"] <= ok["closure_bound"]
        assert ok["pad_waste_cells"] == \
            ok["t_pad"] ** 2 - ok["n_txns"] ** 2

    def test_order_edges_counted(self):
        """realtime/process edge counts match the CPU oracle's
        order_edges relation."""
        encs = _encs(n=2, cyclic=())
        souts: list = []
        K.check_encoded_batch(encs, realtime=True, process_order=True,
                              stats_out=souts)
        for enc, sd in zip(encs, souts):
            edges = g.build_edges(enc, process_order=True,
                                  realtime=True)
            counts = Counter(ty for _s, _d, ty in set(edges))
            assert sd["rt_edges"] == counts.get(g.RT, 0)
            assert sd["proc_edges"] == counts.get(g.PROC, 0)

    def test_condensed_path_stats(self):
        """Past the dense limit the condensation reports exact host
        facts and honest -1 closure telemetry."""
        enc = _encs(n=3)[2]
        souts: list = []
        res = parallel.check_long_history(enc, None, dense_limit=10,
                                          stats_out=souts)
        assert res == {"G1c": True}
        sd = souts[0]
        counts, big = _oracle_graph(enc)
        assert sd["path"] == "condensed"
        assert sd["ww_edges"] == counts.get(g.WW, 0)
        assert (sd["scc_count"], sd["scc_max"]) == (len(big), 2)
        assert sd["closure_rounds"] == -1 and sd["margin"] == -1

    def test_wgl_serial_register_zero_backtracks(self, monkeypatch):
        """A serial linearizable register history: the greedy WGL path
        linearizes outright — zero backtracks, depth == op count.
        The native engine is monkeypatched away (not just NO_NATIVE:
        an earlier test may have memoized the loaded lib) — the
        backtrack counter is the PYTHON engine's telemetry."""
        from jepsen_tpu import native_lib
        monkeypatch.setattr(native_lib, "wgl_lib", lambda: None)
        from jepsen_tpu.checker import knossos, models
        from jepsen_tpu.checker.knossos.synth import \
            synth_register_history
        hist = synth_register_history(40, n_procs=1, seed=7)
        sd: dict = {}
        res = knossos.wgl(models.cas_register(), hist, search_stats=sd)
        assert res["valid?"] is True
        assert sd["engine"] == "wgl"
        assert sd["backtracks"] == 0
        assert sd["max_depth"] == sd["op_count"] == res["op-count"]
        # verdict dict untouched by the stats seam
        assert res == knossos.wgl(models.cas_register(), hist)


class TestVerdictParityMatrix:
    def test_cold_and_two_pass_and_unfused(self):
        encs = _encs()
        base = parallel.check_bucketed(encs, None)
        for kw in ({}, {"two_pass": True}, {"fused": False}):
            souts: list = []
            assert parallel.check_bucketed(
                encs, None, stats_out=souts, **kw) == base
            assert all(s is not None for s in souts)

    def test_warm_sidecar_and_donated(self, tmp_path):
        """Warm path: encodings rebuilt from the v2 sidecar (mmap
        dispatch views; donation is the single-device default) yield
        identical verdicts and the same golden stats as cold."""
        d = tmp_path / "run"
        d.mkdir()
        hist = synth.synth_append_history(T=50, K=6, seed=2, g1c=True)
        (d / "history.jsonl").write_text(
            "\n".join(json.dumps(o) for o in hist) + "\n")
        from jepsen_tpu import ingest
        cold = ingest.encode_run_dir(d, "append")
        warm = store_mod.load_encoded(d, "append")
        assert warm is not None and getattr(warm, "warm", False)
        s_cold: list = []
        s_warm: list = []
        r_cold = parallel.check_bucketed([cold], None,
                                         stats_out=s_cold)
        r_warm = parallel.check_bucketed([warm], None,
                                         stats_out=s_warm)
        assert r_cold == r_warm == parallel.check_bucketed([warm],
                                                           None)
        for f in K.STAT_FIELDS:
            assert s_cold[0][f] == s_warm[0][f], f

    def test_mesh_sharded_dispatch_parity(self):
        """A REAL 2-device dp mesh (virtual CPU devices — the sharded
        kernel path with collectives, not the 1-device normalization):
        gate-on verdicts and stats vs gate-off verdicts, in a
        subprocess so the device count can be pinned before jax
        init."""
        code = """
import json
from jepsen_tpu import parallel
from jepsen_tpu.checker.elle import synth
from jepsen_tpu.checker.elle.encode import encode_history
encs = [encode_history(synth.synth_append_history(
    T=40, K=6, seed=s, g1c=(s == 1))) for s in range(4)]
mesh = parallel.make_mesh()
assert mesh.devices.size == 2, mesh.devices
souts = []
on = parallel.check_bucketed(encs, mesh, stats_out=souts)
off = parallel.check_bucketed(encs, mesh)
print(json.dumps({"parity": on == off,
                  "stats": [s["cycle_txns"] for s in souts]}))
"""
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "JEPSEN_TPU_PLATFORM": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["parity"] is True
        assert got["stats"] == [0, 2, 0, 0]

    def test_serve_fold_parity(self, monkeypatch):
        """The serve daemon's dispatch core (FoldDispatcher): the
        rendered verdict dicts are identical with the gate on, and the
        stats list aligns (None for a quarantined encode)."""
        from jepsen_tpu.parallel.folding import FoldDispatcher
        encs = _encs(n=3)
        fd = FoldDispatcher()
        base = fd.verdicts(encs, "append")
        souts: list = []
        monkeypatch.setenv("JEPSEN_TPU_KERNEL_STATS", "1")
        got = fd.verdicts(encs + [ValueError("poisoned")], "append",
                          stats_out=souts)
        assert got[:3] == base
        assert got[3].get("valid?") == "unknown"
        assert [s is None for s in souts] == [False, False, False,
                                              True]


class TestGateOffFree:
    def test_dispatch_key_no_churn(self):
        """The AOT-cache key with the gate off is the EXACT pre-stats
        tuple (no executable churn); with it on, one appended
        marker."""
        from jepsen_tpu.parallel.residency import ExecutableResidency
        from jepsen_tpu.obs import device as device_obs
        shape = K.BatchShape(n_txns=128, n_appends=8, n_reads=8,
                             n_keys=8, max_pos=8)
        kw = {"classify": True, "realtime": False,
              "process_order": False, "fused": True}
        off = ExecutableResidency.dispatch_key(kw, shape, donate=True)
        assert off == (True, False, False, True, True, 8, 8, 128)
        on = ExecutableResidency.dispatch_key(
            {**kw, "with_stats": True}, shape, donate=True)
        assert on == off + ("stats",)
        # the costdb mirrors the same rule on the (undonated) mesh key
        assert device_obs.dispatch_cost_key(
            {**kw, "with_stats": True}, shape, False)[-1] == "stats"

    def test_single_device_fingerprint_golden(self):
        """A single-device dispatch's AOT fingerprint is byte-identical
        to the one every cached executable was written under: a mesh's
        axis names and sizes enter only the keys of mesh-sharded
        inputs (a SingleDeviceSharding has no mesh)."""
        import jax
        from jepsen_tpu import aot, parallel
        from jepsen_tpu.parallel.residency import ExecutableResidency
        shape = K.BatchShape(n_txns=128, n_appends=8, n_reads=8,
                             n_keys=8, max_pos=8)
        kw = {"classify": True, "realtime": False,
              "process_order": False, "fused": True}
        key = ExecutableResidency.dispatch_key(kw, shape, donate=True)
        fn = parallel.sharded_check_fn(None, shape, donate=True, **kw)
        dev = jax.devices()[0]
        args = tuple(jax.device_put(np.zeros(s, np.int32), dev)
                     for s in [(2, 8, 3), (2, 8, 3), (2, 128), (2, 128),
                               (2, 128), (2,)])
        assert aot._sharding_key(args[0]) == "SingleDeviceSharding[0]"
        assert aot._fingerprint(fn, args, key) == (
            "2bd076c94f6e408e12d935fdcb6f7496"
            "bd31e000f41ac82c173fd003e8675957")

    def test_gate_off_overhead_sub_microsecond(self, monkeypatch):
        """The added per-record code path with the gate off is one
        None check (record(stats=None)) — pinned like costdb's."""
        monkeypatch.delenv("JEPSEN_TPU_KERNEL_STATS", raising=False)
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            search_obs.record("r", "append", None)
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6, f"{per * 1e6:.2f}µs per disabled record"

    def test_gate_off_no_flush_no_files(self, tmp_path,
                                        monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_KERNEL_STATS", raising=False)
        search_obs.reset()
        search_obs.record("r", "append", {"margin": 1})
        p = tmp_path / "analytics.jsonl"
        assert search_obs.flush(p) == 0
        assert not p.exists()
        search_obs.reset()


class TestAnalyticsLedger:
    def test_roundtrip_and_torn_tail(self, tmp_path):
        p = tmp_path / "analytics.jsonl"
        recs = [{"dir": f"r{i}", "checker": "append", "margin": i}
                for i in range(3)]
        assert store_mod.append_analytics(p, recs) == 3
        # a crash-torn tail is skipped on load and sealed on append
        with open(p, "a") as f:
            f.write('{"dir": "torn", "checker": "app')
        assert [r["dir"] for r in store_mod.load_analytics(p)] \
            == ["r0", "r1", "r2"]
        store_mod.append_analytics(p, [{"dir": "r3",
                                        "checker": "append"}])
        assert [r["dir"] for r in store_mod.load_analytics(p)] \
            == ["r0", "r1", "r2", "r3"]

    def test_sampling_gate(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_KERNEL_STATS", "1")
        monkeypatch.setenv("JEPSEN_TPU_KERNEL_STATS_SAMPLE", "2")
        search_obs.reset()
        for i in range(5):
            search_obs.record(f"r{i}", "append",
                              {"margin": i, "cycle_txns": 0})
        p = tmp_path / "analytics.jsonl"
        assert search_obs.flush(p) == 3   # records 0, 2, 4
        assert [r["dir"] for r in store_mod.load_analytics(p)] \
            == ["r0", "r2", "r4"]
        search_obs.reset()

    def test_near_miss_marker(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_KERNEL_STATS", "1")
        search_obs.reset()
        search_obs.record("deep", "append",
                          {"margin": 3, "cycle_txns": 4})
        search_obs.record("blatant", "append",
                          {"margin": 0, "cycle_txns": 2})
        search_obs.record("valid", "append",
                          {"margin": 5, "cycle_txns": 0})
        recs = {r["dir"]: r for r in search_obs.records()}
        assert recs["deep"].get("near_miss") is True
        assert "near_miss" not in recs["blatant"]
        assert "near_miss" not in recs["valid"]
        search_obs.reset()

    def test_mesh_merge_dedup(self, tmp_path):
        from jepsen_tpu import mesh
        for k, dirs in enumerate((("a", "b"), ("c", "b"))):
            store_mod.append_analytics(
                store_mod.analytics_path(tmp_path, k),
                [{"dir": d, "checker": "append", "margin": k}
                 for d in dirs])
        merged = mesh.merge_analytics(tmp_path, 2)
        by = {r["dir"]: r["margin"] for r in merged}
        assert by == {"a": 0, "b": 1, "c": 1}   # last shard wins
        # the merged file is the atomic store-level ledger; a repeat
        # merge replaces it byte-identically
        p = tmp_path / "analytics.jsonl"
        first = p.read_bytes()
        mesh.merge_analytics(tmp_path, 2)
        assert p.read_bytes() == first

    def test_search_section_aggregates(self):
        recs = [{"dir": f"r{i}", "checker": "append", "margin": m,
                 "cycle_txns": c, "closure_rounds": 2, "t_pad": 128,
                 "n_txns": 50, "ww_edges": 10, "wr_edges": 5,
                 "rw_edges": 5, "rt_edges": 0, "proc_edges": 0,
                 "scc_max": s}
                for i, (m, c, s) in enumerate(
                    ((0, 2, 2), (2, 0, 0), (3, 0, 0)))]
        cost = [{"geometry": {"n_txns": 128},
                 "windows": {"histories": 3, "device_secs": 0.3,
                             "dispatches": 1}}]
        sec = search_obs.search_section(recs, cost_records=cost)
        assert sec["histories"] == 3 and sec["anomalous"] == 1
        assert sec["anomaly_rate"] == round(1 / 3, 4)
        row = sec["by_geometry"][0]
        assert row["t_pad"] == 128
        assert row["device_secs_per_history"] == 0.1
        # empty ledger (gate off): no section at all
        assert search_obs.search_section([]) is None


class TestCliAcceptance:
    def test_sweep_byte_identical_and_ledger(self, tmp_path):
        """The acceptance criterion end to end through the REAL
        analyze-store CLI: gate-on produces analytics.jsonl + a report
        search section matching the seeded store; results.json/.edn
        byte-identical to gate-off; gate-off adds zero new files."""
        for side in ("off", "on"):
            (tmp_path / side / "synth").mkdir(parents=True)
            synth.write_synth_store(tmp_path / side / "synth",
                                    4, 48, 6, 2)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        outs = {}
        for side in ("off", "on"):
            e = dict(env)
            if side == "on":
                e["JEPSEN_TPU_KERNEL_STATS"] = "1"
            else:
                e.pop("JEPSEN_TPU_KERNEL_STATS", None)
            p = subprocess.run(
                [sys.executable, "-m", "jepsen_tpu.cli",
                 "analyze-store", "--store",
                 str(tmp_path / side), "--report"],
                cwd=REPO, env=e, capture_output=True, text=True,
                timeout=420)
            assert p.returncode == 1, p.stderr[-2000:]
            outs[side] = tmp_path / side
        off, on = outs["off"], outs["on"]
        for d in os.listdir(off / "synth"):
            for f in ("results.json", "results.edn"):
                assert (off / "synth" / d / f).read_bytes() \
                    == (on / "synth" / d / f).read_bytes(), (d, f)
        assert not (off / "analytics.jsonl").exists()
        recs = store_mod.load_analytics(on)
        assert len(recs) == 4
        bad = [r for r in recs if r.get("cycle_txns")]
        assert len(bad) == 2
        assert all((r["scc_count"], r["scc_max"]) == (1, 2)
                   for r in bad)
        rep = json.loads((on / "report.json").read_text())
        assert rep["search"]["histories"] == 4
        assert rep["search"]["anomaly_rate"] == 0.5
        assert "Search telemetry" in (on / "report.md").read_text()
