"""Sharded store->tensor ingest (jepsen_tpu/ingest.py)."""

from __future__ import annotations

import json

import pytest

from jepsen_tpu import ingest
from jepsen_tpu.checker.elle import encode, synth


def write_run(tmp_path, name, hist):
    d = tmp_path / name
    d.mkdir()
    with open(d / "history.jsonl", "w") as f:
        for o in hist:
            f.write(json.dumps(o) + "\n")
    return d


class TestEncodeRunDir:
    def test_jsonl_roundtrip_matches_direct_encode(self, tmp_path):
        hist = synth.synth_append_history(T=40, K=8, seed=1)
        d = write_run(tmp_path, "r0", hist)
        enc = ingest.encode_run_dir(d)
        direct = encode.encode_history(hist)
        assert enc.n == direct.n
        assert (enc.appends == direct.appends).all()
        assert (enc.reads == direct.reads).all()
        assert enc.txn_ops == []  # lean by default

    def test_missing_history_raises(self, tmp_path):
        d = tmp_path / "empty"
        d.mkdir()
        with pytest.raises(FileNotFoundError):
            ingest.encode_run_dir(d)

    def test_edn_fallback(self, tmp_path):
        d = tmp_path / "edn"
        d.mkdir()
        (d / "history.edn").write_text(
            '{:type :invoke, :process 0, :f :txn, '
            ':value [[:append 1 1]], :index 0}\n'
            '{:type :ok, :process 0, :f :txn, '
            ':value [[:append 1 1]], :index 1}\n')
        enc = ingest.encode_run_dir(d)
        assert enc.n == 1


class TestParallelEncode:
    def test_serial_and_pool_agree(self, tmp_path):
        dirs = [write_run(tmp_path, f"r{i}",
                          synth.synth_append_history(T=30, K=6, seed=i))
                for i in range(4)]
        serial = ingest.parallel_encode(dirs, processes=0)
        pooled = ingest.parallel_encode(dirs, processes=2)
        for a, b in zip(serial, pooled):
            assert a.n == b.n
            assert (a.appends == b.appends).all()

    def test_failures_come_back_as_exceptions(self, tmp_path):
        hist = synth.synth_append_history(T=20, K=4, seed=0)
        good = write_run(tmp_path, "good", hist)
        bad = tmp_path / "bad"
        bad.mkdir()
        out = ingest.parallel_encode([good, bad], processes=0)
        from jepsen_tpu.checker.elle.encode import encode_history
        assert out[0].n == encode_history(hist).n
        assert isinstance(out[1], Exception)


class TestIterEncodeChunks:
    def test_chunks_ordered_and_complete(self, tmp_path):
        dirs = [write_run(tmp_path, f"r{i}",
                          synth.synth_append_history(T=30, K=6, seed=i))
                for i in range(7)]
        got = []
        for part in ingest.iter_encode_chunks(dirs, chunk=3,
                                              processes=2):
            assert len(part) <= 3
            got.extend(part)
        assert [d for d, _e in got] == dirs        # in order, no dups
        serial = ingest.parallel_encode(dirs, processes=0)
        for (d, e), s in zip(got, serial):
            assert e.n == s.n and (e.appends == s.appends).all()

    def test_exceptions_and_serial_path(self, tmp_path):
        good = write_run(tmp_path, "good",
                         synth.synth_append_history(T=20, K=4, seed=0))
        bad = tmp_path / "bad"
        bad.mkdir()
        parts = list(ingest.iter_encode_chunks([good, bad], chunk=8,
                                               processes=0))
        assert len(parts) == 1
        (d1, e1), (d2, e2) = parts[0]
        assert d1 == good and e1.n > 0
        assert d2 == bad and isinstance(e2, Exception)


class TestPipelineOverlap:
    def test_overlap_seconds_intersection(self):
        ov = ingest.overlap_seconds
        assert ov([], [(0, 1)]) == 0.0
        assert ov([(0, 1)], [(2, 3)]) == 0.0
        assert ov([(0, 2)], [(1, 3)]) == pytest.approx(1.0)
        # overlapping input spans must not double-count
        assert ov([(0, 2), (1, 3)], [(0, 10)]) == pytest.approx(3.0)
        assert ov([(0, 1), (2, 3)], [(0.5, 2.5)]) == pytest.approx(1.0)

    def test_pipelined_sweep_measures_real_overlap(self, tmp_path):
        """The round-4 flagship claim, proven without a multicore
        host: a slow fake device sweep (sleep per chunk) over
        iter_encode_chunks with 2 spawn workers must show worker
        parse spans intersecting device windows — measured overlap,
        not inferred from end-to-end subtraction."""
        import time as _t
        dirs = [write_run(tmp_path, f"r{i}",
                          synth.synth_append_history(T=600, K=12,
                                                     seed=i))
                for i in range(6)]
        info: dict = {}
        dev_spans = []
        for part in ingest.iter_encode_chunks(dirs, chunk=1,
                                              processes=2, info=info):
            assert len(part) == 1
            t0 = _t.monotonic()     # same clock as parse_spans
            _t.sleep(0.4)           # the fake accelerator dispatch
            dev_spans.append((t0, _t.monotonic()))
        assert info["pooled"] is True
        assert len(info["parse_spans"]) == 6
        overlap = ingest.overlap_seconds(info["parse_spans"], dev_spans)
        assert overlap > 0.0, (info["parse_spans"], dev_spans)


def _encode_in_worker(run_dir):
    """Runs in a spawned pool worker: one streaming-pipeline encode,
    then what JAX state the worker holds (read here, in the test only:
    the program never reads private JAX state)."""
    import os
    import sys
    _idx, payload, _einfo, _t0, _t1 = ingest._stream_worker(
        (0, run_dir, "append", None, None))
    backends = []
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        backends = sorted(xla_bridge._backends)
    return (not isinstance(payload, Exception),
            os.environ.get("JAX_PLATFORMS"), backends)


def _register_history():
    """A lifted two-key register history as stored: [k v] values."""
    hist = []
    for k in ("a", "b"):
        for f, v in (("write", 1), ("read", 1), ("cas", [1, 2])):
            hist.append({"type": "invoke", "process": 0, "f": f,
                         "value": [k, None if f == "read" else v]})
            hist.append({"type": "ok", "process": 0, "f": f,
                         "value": [k, v]})
    return [{**o, "index": i, "time": i} for i, o in enumerate(hist)]


class TestPoolWorkersLeaveTheChip:
    def test_spawned_encode_initialises_no_backend(self, tmp_path,
                                                   monkeypatch):
        # the parent may hold the chip: a pool worker that initialised
        # a backend would fight it for the device
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor
        (tmp_path / "s").mkdir()
        dirs = synth.write_synth_store(tmp_path / "s", 1, 40, 4, 0)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with ProcessPoolExecutor(
                max_workers=1, mp_context=mp.get_context("spawn")) as ex:
            ok, platforms, backends = ex.submit(
                _encode_in_worker, str(dirs[0])).result(timeout=300)
        assert ok
        assert platforms is None
        assert backends == []

    def test_spawned_register_worker_never_imports_jax(self, tmp_path,
                                                        monkeypatch):
        # the register sweep's workers encode for the device tiers on
        # the host alone; `eval` probes the worker's own sys.modules
        # (this module imports JAX, so a function of its own would)
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor
        from jepsen_tpu.checker.knossos import encode as kenc
        d = write_run(tmp_path, "r0", _register_history())
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with ProcessPoolExecutor(
                max_workers=1, mp_context=mp.get_context("spawn")) as ex:
            rec = ex.submit(ingest._register_worker,
                            (str(d), 512)).result(timeout=300)
            jax_loaded = ex.submit(
                eval, "'jax' in __import__('sys').modules").result(
                    timeout=60)
        assert [k for k, _n, _e in rec] == ["a", "b"]
        assert all(isinstance(e, kenc.DenseEncoded) for _k, _n, e in rec)
        assert jax_loaded is False
