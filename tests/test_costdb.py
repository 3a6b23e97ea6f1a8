"""The device cost observatory (jepsen_tpu/obs/device.py) + costdb.

Pins the ISSUE-12 contract: per-executable XLA cost/memory capture
joined with measured dispatch windows, the device_kind-keyed peak
table, the costdb.jsonl persistence discipline (flushed lines, torn
tails skipped), the two-shard mesh merge deduplication, the report's
device roofline section, residency gauges in health.json, and the
gate-off invariants — zero new files and byte-identical verdicts.
All CPU-safe.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import pytest

from jepsen_tpu import store as jstore
from jepsen_tpu import trace
from jepsen_tpu.obs import attribution
from jepsen_tpu.obs import device as device_obs

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _fresh_observatory():
    device_obs.reset()
    trace.reset()
    yield
    device_obs.reset()
    trace.reset()


def _encs(n=4, T=40, K=4):
    from jepsen_tpu.checker.elle import encode as enc_mod
    from jepsen_tpu.checker.elle.synth import synth_append_history
    return [enc_mod.encode_history(synth_append_history(T=T, K=K,
                                                        seed=i))
            for i in range(n)]


def _sweep(encs, mesh=None):
    from jepsen_tpu import parallel
    return parallel.check_bucketed(encs, mesh)


# ---------------------------------------------------------------------------
# Peak table (the hard-coded-MFU-peak fix)
# ---------------------------------------------------------------------------

class TestPeakTable:
    def test_known_kinds_resolve_from_table(self):
        from jepsen_tpu.checker.elle import kernels as K
        v5e = K.device_peak("TPU v5 lite")
        assert v5e["source"] == "table"
        assert v5e["int8_tops"] == 394.0
        assert v5e["bf16_tflops"] == 197.0
        assert v5e["hbm_gbps"] == 819.0
        assert K.device_peak("TPU v4")["bf16_tflops"] == 275.0
        assert K.device_peak("TPU v5p")["int8_tops"] == 918.0

    def test_aliases_and_case(self):
        from jepsen_tpu.checker.elle import kernels as K
        assert K.device_peak("tpu v5e")["int8_tops"] == 394.0
        assert K.device_peak("TPU V6E")["bf16_tflops"] == 918.0

    @pytest.mark.parametrize("kind", ["cpu", "TPU v99"])
    def test_unknown_kind_raises(self, kind):
        # no assumed peak: a kind the table lacks is an error
        from jepsen_tpu.checker.elle import kernels as K
        with pytest.raises(KeyError, match="no peak"):
            K.device_peak(kind)

    def test_key_layout_pinned_to_residency(self):
        # the observatory parses dispatch_key positionally; this pin
        # fails loudly if residency reorders the tuple
        from jepsen_tpu.checker.elle.kernels import BatchShape
        from jepsen_tpu.parallel.residency import ExecutableResidency
        shape = BatchShape(n_txns=128, n_appends=8, n_reads=8,
                           n_keys=16, max_pos=24)
        kw = {"classify": True, "realtime": False,
              "process_order": False, "fused": True}
        key = ExecutableResidency.dispatch_key(kw, shape, donate=True)
        assert len(key) == len(device_obs._KEY_FIELDS)
        assert key[0] is True and key[4] is True          # classify, donate
        assert key[5] == 16 and key[6] == 24 and key[7] == 128
        assert key == device_obs.dispatch_cost_key(kw, shape, donate=True)
        # a mesh dispatch (donation normalized off) keys the same
        # layout (its mesh rides the AOT fingerprint's shardings), and
        # the costdb joins it the same way
        mesh_key = ExecutableResidency.dispatch_key(kw, shape, donate=False)
        assert len(mesh_key) == len(device_obs._KEY_FIELDS)
        assert mesh_key[4] is False and mesh_key[5:] == key[5:]
        assert mesh_key == device_obs.dispatch_cost_key(
            kw, shape, donate=False)


# ---------------------------------------------------------------------------
# Capture + join: the golden record shape
# ---------------------------------------------------------------------------

class TestCapture:
    def test_golden_record_shape(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        encs = _encs()
        verdicts = _sweep(encs)
        assert len(verdicts) == len(encs)
        recs = device_obs.records()
        assert recs, "no record captured from a compiled dispatch"
        r = recs[0]
        # the golden shape: every published field present
        assert r["v"] == 1
        assert set(r["kernel"]) == {"classify", "realtime",
                                    "process_order", "fused"}
        assert r["formulation"] == "xla-int8"
        g = r["geometry"]
        assert g["B"] >= len(encs) and g["n_txns"] % 128 == 0
        assert set(g) == {"B", "n_txns", "n_keys", "max_pos",
                          "n_appends", "n_reads"}
        assert r["analysis"] in ("compiled", "lowered")
        assert r["cost"]["flops"] > 0
        assert r["cost"]["bytes_accessed"] > 0
        w = r["windows"]
        assert w["dispatches"] >= 1 and w["device_secs"] > 0
        assert w["histories"] >= len(encs)
        assert w["min_secs"] <= w["max_secs"]
        assert r["peak"] is None      # a host CPU has no peak row
        # CPU windows are honest host measurements, NOT TPU numbers
        assert r["provenance"] == "estimated"
        assert r["achieved"]["flops_per_sec"] > 0
        assert r["roofline"]["bandwidth_utilization"] is None
        json.dumps(r)   # a costdb line must be plain JSON

    def test_capture_dedups_per_geometry(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        encs = _encs()
        _sweep(encs)
        n1 = len(device_obs.records())
        _sweep(encs)    # same geometry: windows accumulate, no new rec
        recs = device_obs.records()
        assert len(recs) == n1
        assert recs[0]["windows"]["dispatches"] >= 2

    def test_counter_declared_and_ticks(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        tr = trace.fresh_run("costdb-unit", scope="sweep")
        _sweep(_encs())
        assert tr.counter("cost_records").value >= 1
        assert "cost_records" in trace.DECLARED_METRICS["counters"]

    def test_gate_off_captures_nothing(self, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_COSTDB", raising=False)
        _sweep(_encs())
        assert device_obs.records() == []
        assert device_obs._pending == {}

    def test_verdicts_identical_gate_on_vs_off(self, monkeypatch):
        encs = _encs(n=6)
        monkeypatch.delenv("JEPSEN_TPU_COSTDB", raising=False)
        off = _sweep(encs)
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        on = _sweep(encs)
        assert off == on

    def test_residency_gauges_published(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        tr = trace.fresh_run("costdb-gauges", scope="sweep")
        _sweep(_encs())
        assert isinstance(tr.gauge("resident_executables").value, int)
        # all pending windows closed: modeled HBM drains to zero
        assert tr.gauge("hbm_modeled_bytes").value == 0
        for g in ("resident_executables", "hbm_modeled_bytes",
                  "hbm_device_bytes"):
            assert g in trace.DECLARED_METRICS["gauges"]

    def test_health_snapshot_carries_device_section(self, monkeypatch):
        from jepsen_tpu.obs.health import health_snapshot
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        tr = trace.fresh_run("costdb-health", scope="sweep")
        _sweep(_encs())
        snap = health_snapshot(tr, seq=1)
        dev = snap["device"]
        assert isinstance(dev["resident_executables"], int)
        assert dev["hbm_modeled_bytes"] == 0
        # null, never absent, when the platform reports no stats
        assert "hbm_device_bytes" in dev


# ---------------------------------------------------------------------------
# costdb.jsonl persistence: flushed lines, torn tails, retention
# ---------------------------------------------------------------------------

class TestCostdbFile:
    def test_append_and_load_roundtrip(self, tmp_path):
        p = tmp_path / "costdb.jsonl"
        recs = [{"v": 1, "geometry": {"B": 1}, "i": i}
                for i in range(3)]
        assert jstore.append_costdb(p, recs) == 3
        assert [r["i"] for r in jstore.load_costdb(p)] == [0, 1, 2]

    def test_torn_tail_skipped_on_load(self, tmp_path):
        p = tmp_path / "costdb.jsonl"
        jstore.append_costdb(p, [{"v": 1, "geometry": {"B": 2},
                                  "ok": True}])
        with open(p, "a") as f:     # a crash mid-append: no newline
            f.write('{"v": 1, "geometry": {"B": 3}, "torn')
        loaded = jstore.load_costdb(p)
        assert len(loaded) == 1 and loaded[0]["ok"] is True

    def test_append_seals_torn_tail_first(self, tmp_path):
        # appending after a line that lost its newline must not merge
        # two records into one unparseable line (the journal rule)
        p = tmp_path / "costdb.jsonl"
        with open(p, "w") as f:
            f.write('{"v": 1, "geometry": {}, "torn": tru')
        jstore.append_costdb(p, [{"v": 1, "geometry": {"B": 1},
                                  "fresh": True}])
        loaded = jstore.load_costdb(p)
        assert len(loaded) == 1 and loaded[0]["fresh"] is True

    def test_non_record_lines_skipped(self, tmp_path):
        p = tmp_path / "costdb.jsonl"
        p.write_text('null\n[]\n{"no_geometry": 1}\n'
                     '{"v": 1, "geometry": {"B": 1}}\n')
        assert len(jstore.load_costdb(p)) == 1

    def test_shard_paths(self, tmp_path):
        assert jstore.costdb_path(tmp_path).name == "costdb.jsonl"
        assert jstore.costdb_path(tmp_path, 3).name \
            == "costdb-shard3.jsonl"

    def test_flush_gate_off_no_file(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_COSTDB", raising=False)
        assert device_obs.flush(tmp_path / "costdb.jsonl") == 0
        assert not (tmp_path / "costdb.jsonl").exists()


# ---------------------------------------------------------------------------
# The real sweep contract: analyze-store writes (or doesn't) the file
# ---------------------------------------------------------------------------

def _synth_store(tmp_path, n=3):
    from jepsen_tpu.checker.elle.synth import synth_append_history
    from jepsen_tpu.store import Store
    store = Store(tmp_path / "store")
    for i in range(n):
        d = store.base / "costdb" / f"2020010{i + 1}T000000"
        d.mkdir(parents=True)
        hist = synth_append_history(T=40, K=4, seed=i)
        (d / "history.jsonl").write_text(
            "\n".join(json.dumps(o) for o in hist) + "\n")
    return store


class TestAnalyzeStore:
    def test_sweep_writes_provenance_tagged_costdb(self, tmp_path,
                                                   monkeypatch):
        from jepsen_tpu import cli
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        store = _synth_store(tmp_path)
        assert cli.analyze_store(store, checker="append") == 0
        recs = jstore.load_costdb(store.base)
        assert len(recs) >= 1    # >=1 record per compiled executable
        for r in recs:
            assert r["provenance"] in ("measured", "estimated")
            assert r["windows"]["dispatches"] >= 1

    def test_report_device_section_from_sweep(self, tmp_path,
                                              monkeypatch):
        from jepsen_tpu import cli
        monkeypatch.setenv("JEPSEN_TPU_COSTDB", "1")
        store = _synth_store(tmp_path)
        assert cli.analyze_store(store, checker="append",
                                 report=True) == 0
        rep = json.loads((store.base / "report.json").read_text())
        dev = rep["device"]
        assert dev["records"] and dev["provenance"] == "estimated"
        row = dev["records"][0]
        assert row["dispatches"] >= 1 and row["device_secs"] > 0
        assert row["flops"] > 0
        md = (store.base / "report.md").read_text()
        assert "Device roofline" in md

    def test_gate_off_zero_new_files(self, tmp_path, monkeypatch):
        from jepsen_tpu import cli
        monkeypatch.delenv("JEPSEN_TPU_COSTDB", raising=False)
        store = _synth_store(tmp_path)
        assert cli.analyze_store(store, checker="append",
                                 report=True) == 0
        assert not (store.base / "costdb.jsonl").exists()
        assert not list(store.base.glob("costdb*.jsonl"))
        rep = json.loads((store.base / "report.json").read_text())
        assert "device" not in rep

    def test_gate_off_overhead_is_sub_microsecond(self, monkeypatch):
        # the <1µs contract: a disabled begin/close pair is a gate
        # read + a dict probe
        monkeypatch.delenv("JEPSEN_TPU_COSTDB", raising=False)
        sentinel = object()
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            device_obs.begin_dispatch(sentinel, {}, None, False, None,
                                      None)
            device_obs.close_dispatch(sentinel, t0, 1, None)
        per_pair = (time.perf_counter() - t0) / n
        assert per_pair < 5e-6, f"{per_pair * 1e6:.2f}µs per disabled pair"


# ---------------------------------------------------------------------------
# Two-shard mesh merge: one deduplicated costdb
# ---------------------------------------------------------------------------

class TestMeshMerge:
    def _rec(self, B=8, dispatches=2, secs=0.5, provenance="estimated",
             flops=1e9, backend="cpu", device_kind="cpu"):
        return {
            "v": 1,
            "kernel": {"classify": True, "realtime": False,
                       "process_order": False, "fused": True},
            "formulation": "xla-int8", "donated": True,
            "geometry": {"B": B, "n_txns": 128, "n_keys": 8,
                         "max_pos": 8, "n_appends": 64, "n_reads": 64},
            "backend": backend, "device_kind": device_kind,
            "analysis": "compiled",
            "cost": {"flops": flops, "bytes_accessed": 2e8,
                     "transcendentals": None},
            "memory": None, "argument_bytes_actual": 1024,
            "windows": {"dispatches": dispatches,
                        "device_secs": secs, "min_secs": 0.1,
                        "max_secs": 0.4, "histories": B * dispatches},
            "peak": {"device_kind": "cpu", "source": "fallback",
                     "bf16_tflops": 197.0, "int8_tops": 394.0,
                     "hbm_gbps": 819.0, "hbm_gib": 16.0},
            "provenance": provenance,
            "achieved": {"flops_per_sec": None, "bytes_per_sec": None},
            "roofline": {"flops_utilization": None,
                         "bandwidth_utilization": None},
        }

    def test_merge_dedups_same_executable(self):
        a = self._rec(dispatches=2, secs=0.5)
        b = self._rec(dispatches=3, secs=1.0)
        other = self._rec(B=16, dispatches=1, secs=0.2)
        merged = device_obs.merge_records([[a, other], [b]])
        assert len(merged) == 2
        m = next(r for r in merged if r["geometry"]["B"] == 8)
        w = m["windows"]
        assert w["dispatches"] == 5
        assert w["device_secs"] == pytest.approx(1.5)
        assert w["histories"] == 8 * 5
        assert w["min_secs"] == 0.1 and w["max_secs"] == 0.4
        # the roofline is re-derived over the MERGED windows
        assert m["achieved"]["flops_per_sec"] == pytest.approx(
            5 * 1e9 / 1.5)

    def test_merge_keeps_measured_provenance(self):
        a = self._rec(provenance="measured")
        b = self._rec(provenance="estimated")
        merged = device_obs.merge_records([[a], [b]])
        assert len(merged) == 1
        assert merged[0]["provenance"] == "measured"

    def test_two_shard_file_merge(self, tmp_path):
        from jepsen_tpu import mesh
        base = tmp_path
        jstore.append_costdb(jstore.costdb_path(base, 0),
                             [self._rec(dispatches=1, secs=0.3)])
        jstore.append_costdb(jstore.costdb_path(base, 1),
                             [self._rec(dispatches=2, secs=0.6),
                              self._rec(B=32, dispatches=1, secs=0.1)])
        merged = mesh.merge_costdbs(base, 2)
        assert len(merged) == 2
        on_disk = jstore.load_costdb(base)
        assert len(on_disk) == 2
        m = next(r for r in on_disk if r["geometry"]["B"] == 8)
        assert m["windows"]["dispatches"] == 3
        # repeat merge replaces, never doubles (derived artifact)
        mesh.merge_costdbs(base, 2)
        assert len(jstore.load_costdb(base)) == 2

    def test_merge_no_shard_files_is_noop(self, tmp_path):
        from jepsen_tpu import mesh
        assert mesh.merge_costdbs(tmp_path, 2) == []
        assert not (tmp_path / "costdb.jsonl").exists()


# ---------------------------------------------------------------------------
# The report device section pinned on synthetic records (CPU-safe)
# ---------------------------------------------------------------------------

class TestDeviceSection:
    def test_section_and_md_pinned(self):
        # a synthetic chip record: the roofline needs a peak row
        rec = TestMeshMerge()._rec(dispatches=4, secs=2.0,
                                   backend="tpu",
                                   device_kind="TPU v5 lite")
        rec = device_obs.merge_records([[rec]])[0]   # derive rates
        dev = attribution.device_section([rec])
        assert dev["provenance"] == "measured"
        row = dev["records"][0]
        assert row["dispatches"] == 4
        assert row["achieved_tflops"] == pytest.approx(
            4 * 1e9 / 2.0 / 1e12, rel=1e-3)
        assert row["achieved_gbps"] == pytest.approx(
            4 * 2e8 / 2.0 / 1e9, rel=1e-3)
        assert row["bandwidth_utilization"] == pytest.approx(
            (4 * 2e8 / 2.0) / (819.0 * 1e9), rel=1e-3)
        md = "\n".join(attribution.render_device_md(dev))
        assert "Device roofline" in md
        assert "B8xT128" in md
        assert "measured" in md
        # the peak row's source is surfaced
        assert "TPU v5 lite [table]" in md

    def test_empty_records_no_section(self):
        assert attribution.device_section([]) is None
        rep_j, rep_m = None, None  # write_report without records
        # write_report(device_records=None) must not add the section
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            rep_j, rep_m = attribution.write_report(d, [],
                                                    device_records=None)
            rep = json.loads(Path(rep_j).read_text())
            assert "device" not in rep

    def test_bandwidth_share_aggregate(self):
        recs = device_obs.merge_records([[
            TestMeshMerge()._rec(dispatches=4, secs=2.0, backend="tpu",
                                 device_kind="TPU v5 lite")]])
        bw = device_obs.bandwidth_share(recs)
        assert bw["provenance"] == "measured"
        assert bw["achieved_bw_share"] == pytest.approx(
            (4 * 2e8 / 2.0) / (819.0 * 1e9), rel=1e-3)
        assert bw["device_secs"] == pytest.approx(2.0)
        assert device_obs.bandwidth_share([]) is None


# ---------------------------------------------------------------------------
# Crash-sim coverage (the JT-DUR dynamic counterpart, `make
# crash-smoke`): the costdb journal family driven through real
# SIGKILL-mid-write and injected short writes — torn tails must be
# sealed + skipped, and a repeat merge must stay idempotent.
# ---------------------------------------------------------------------------

class TestCostdbCrashSim:
    _rec = TestMeshMerge._rec

    def test_kill_mid_append_crash_seals_and_resumes(self, tmp_path):
        # a REAL kill: the child appends complete records, leaves a
        # torn tail on disk, and SIGKILLs itself mid-"write"
        import signal
        import subprocess
        import sys
        import textwrap
        p = tmp_path / "costdb.jsonl"
        child = textwrap.dedent(f"""
            import json, os, signal
            from jepsen_tpu import store
            p = {str(p)!r}
            store.append_costdb(
                p, [{{"v": 1, "geometry": {{"B": i}}, "i": i}}
                    for i in range(3)])
            with open(p, "a") as f:
                f.write('{{"v": 1, "geometry": {{"B": 9}}, "to')
                f.flush()
                os.kill(os.getpid(), signal.SIGKILL)
        """)
        res = subprocess.run(
            [sys.executable, "-c", child], cwd=str(Path(__file__).parents[1]),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, timeout=120)
        assert res.returncode == -signal.SIGKILL, res.stderr.decode()
        # the torn tail is skipped, the complete records survive
        loaded = jstore.load_costdb(p)
        assert [r["i"] for r in loaded] == [0, 1, 2]
        # the next append seals the torn tail before writing: the new
        # record cannot merge into the dead bytes
        assert jstore.append_costdb(
            p, [{"v": 1, "geometry": {"B": 4}, "i": 3}]) == 1
        loaded = jstore.load_costdb(p)
        assert [r["i"] for r in loaded] == [0, 1, 2, 3]

    def test_short_write_crash_mid_record(self, tmp_path, monkeypatch):
        # the faultfs local injector: the write that exhausts the
        # byte budget lands its prefix (flushed) and raises EIO —
        # the torn tail a full disk or a kill leaves behind
        from jepsen_tpu import faultfs
        p = tmp_path / "costdb.jsonl"
        recs = [{"v": 1, "geometry": {"B": i}, "i": i} for i in range(3)]
        line0 = json.dumps(recs[0]) + "\n"
        real_open = open
        monkeypatch.setattr(
            "builtins.open",
            faultfs.faulty_opener(len(line0) + 11, real_open=real_open))
        # best-effort contract: the injected fault must not raise out
        n = jstore.append_costdb(p, recs)
        monkeypatch.setattr("builtins.open", real_open)
        assert n == 1                       # one record fully landed
        raw = p.read_text()
        assert raw.startswith(line0) and not raw.endswith("\n")
        assert [r["i"] for r in jstore.load_costdb(p)] == [0]
        # recovery: seal + append, nothing merged, nothing doubled
        assert jstore.append_costdb(p, recs[1:]) == 2
        assert [r["i"] for r in jstore.load_costdb(p)] == [0, 1, 2]

    def test_merge_crash_at_publish_is_invisible(self, tmp_path,
                                                 monkeypatch):
        # crash between the merged tmp write and os.replace: the
        # previous costdb.jsonl must survive untouched, and the
        # re-merge (and a repeat merge) must converge byte-identical
        from jepsen_tpu import mesh
        base = tmp_path
        jstore.append_costdb(jstore.costdb_path(base, 0),
                             [self._rec(dispatches=1, secs=0.3)])
        jstore.append_costdb(jstore.costdb_path(base, 1),
                             [self._rec(dispatches=2, secs=0.6)])
        before = mesh.merge_costdbs(base, 2)
        assert len(before) == 1
        first_bytes = jstore.costdb_path(base).read_bytes()
        # another shard lands; the next merge dies at the publish
        jstore.append_costdb(jstore.costdb_path(base, 1),
                             [self._rec(B=32, dispatches=1, secs=0.1)])
        real_replace = os.replace

        def boom(src, dst):
            raise OSError(5, "faultfs: injected crash at publish")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            mesh.merge_costdbs(base, 2)
        monkeypatch.setattr(os, "replace", real_replace)
        # previous merged file intact, no tmp litter
        assert jstore.costdb_path(base).read_bytes() == first_bytes
        assert [f for f in os.listdir(base) if f.endswith(".tmp")] == []
        # the re-merge converges, and a repeat merge is idempotent
        merged = mesh.merge_costdbs(base, 2)
        assert len(merged) == 2
        once = jstore.costdb_path(base).read_bytes()
        mesh.merge_costdbs(base, 2)
        assert jstore.costdb_path(base).read_bytes() == once
