"""The zero-copy shared-memory ingest pipeline (ISSUE 3).

Covers the three tentpole layers end to end: the shm transport
(jepsen_tpu/shm.py — descriptor round-trips, fallback when /dev/shm is
unusable, leak-freedom on normal AND exception exits), the
imap_unordered reorder buffer and its mid-stream-failure span
accounting, the encoded.v1.bin sidecar cache (byte-identical reloads,
xxh64 parity with the native hasher, invalidation on history change),
and the HBM-envelope invariant of the pipelined bucket dispatcher
(budget_cells bounds the TOTAL resident footprint, not one bucket's).
Everything here is spawn-safe and fast (tier-1, `-m 'not slow'`).
"""

from __future__ import annotations

import json
import os
import random
import sys

import numpy as np
import pytest

from jepsen_tpu import ingest, parallel, shm, store, trace

sys.path.insert(0, os.path.dirname(__file__))
from test_fuzz_differential import rand_wr_history  # noqa: E402

from jepsen_tpu.checker.elle import synth  # noqa: E402


def write_run(tmp_path, name, hist):
    d = tmp_path / name
    d.mkdir()
    with open(d / "history.jsonl", "w") as f:
        for o in hist:
            f.write(json.dumps(o) + "\n")
    return d


def append_dirs(tmp_path, n=4, T=30, corrupt=()):
    out = []
    for i in range(n):
        hist = synth.synth_append_history(T=T, K=6, seed=i)
        out.append(write_run(tmp_path, f"r{i}", hist))
    return out


def wr_dir(tmp_path, seed=7):
    hist = rand_wr_history(random.Random(seed), T=50, K=4, conc=4)
    return write_run(tmp_path, f"wr{seed}", hist)


APPEND_FIELDS = ("appends", "reads", "status", "process",
                 "invoke_index", "complete_index")
WR_FIELDS = ("status", "process", "invoke_index", "complete_index")


def assert_append_identical(a, b):
    assert (a.n, a.n_keys, a.max_pos) == (b.n, b.n_keys, b.max_pos)
    assert a.key_names == b.key_names
    for f in APPEND_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f
    assert a.anomalies == b.anomalies
    assert a.txn_ops == [] and b.txn_ops == []


def assert_wr_identical(a, b):
    assert (a.n, a.key_count) == (b.n, b.key_count)
    assert a.edges == b.edges
    for f in WR_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype and np.array_equal(x, y), f
    assert a.anomalies == b.anomalies


def shm_leaks() -> list[str]:
    try:
        # this process's segments only: other test workers' pools
        # create and reclaim their own concurrently
        mine = f"{shm.NAME_PREFIX}_{os.getpid()}_"
        return [x for x in os.listdir("/dev/shm") if x.startswith(mine)]
    except FileNotFoundError:   # non-Linux: nothing to scan
        return []


# ---------------------------------------------------------------------------
# Differential: shm-transported and cache-loaded encodings are
# byte-identical to in-process encode_run_dir output (ISSUE 3 S3).
# ---------------------------------------------------------------------------

class TestDifferential:
    @pytest.mark.parametrize("native", [True, False])
    def test_shm_and_cache_append(self, tmp_path, monkeypatch, native):
        if not native:
            monkeypatch.setenv("JEPSEN_TPU_NATIVE_INGEST", "0")
        d = append_dirs(tmp_path, n=1, T=40)[0]
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        ref = ingest.encode_run_dir(d, "append")
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "1")
        # shm round trip
        desc = shm.export(ref, shm.gen_name(), "append")
        assert shm.is_descriptor(desc)
        assert_append_identical(shm.materialize(desc), ref)
        assert not shm_leaks()
        # cache round trip: first encode writes the sidecar (native
        # writer when the .so carries the encode, Python writer
        # otherwise), second encode must mmap-load it
        info: dict = {}
        first = ingest.encode_run_dir(d, "append", info=info)
        assert info["cache"] == "miss"
        assert store.encoded_cache_path(d, "append").is_file()
        assert_append_identical(first, ref)
        info2: dict = {}
        warm = ingest.encode_run_dir(d, "append", info=info2)
        assert info2["cache"] == "hit"
        assert_append_identical(warm, ref)

    @pytest.mark.parametrize("native", [True, False])
    def test_shm_and_cache_wr(self, tmp_path, monkeypatch, native):
        if not native:
            monkeypatch.setenv("JEPSEN_TPU_NATIVE_INGEST", "0")
        d = wr_dir(tmp_path)
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        ref = ingest.encode_run_dir(d, "wr")
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "1")
        desc = shm.export(ref, shm.gen_name(), "wr")
        assert shm.is_descriptor(desc)
        assert_wr_identical(shm.materialize(desc), ref)
        assert not shm_leaks()
        info: dict = {}
        first = ingest.encode_run_dir(d, "wr", info=info)
        assert info["cache"] == "miss"
        assert_wr_identical(first, ref)
        info2: dict = {}
        warm = ingest.encode_run_dir(d, "wr", info=info2)
        assert info2["cache"] == "hit"
        assert_wr_identical(warm, ref)

    def test_cache_invalidates_on_history_change(self, tmp_path):
        d = append_dirs(tmp_path, n=1, T=30)[0]
        info: dict = {}
        ingest.encode_run_dir(d, "append", info=info)
        assert info["cache"] == "miss"
        # append one more committed txn: size/mtime/hash all change
        hist = synth.synth_append_history(T=31, K=6, seed=0)
        with open(d / "history.jsonl", "w") as f:
            for o in hist:
                f.write(json.dumps(o) + "\n")
        info2: dict = {}
        enc = ingest.encode_run_dir(d, "append", info=info2)
        assert info2["cache"] == "miss"   # stale sidecar rejected
        assert enc.n == 31

    def test_cache_gate_off(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
        d = append_dirs(tmp_path, n=1, T=20)[0]
        info: dict = {}
        ingest.encode_run_dir(d, "append", info=info)
        assert info["cache"] is None
        assert not store.encoded_cache_path(d, "append").exists()

    def test_xxh64_native_parity(self):
        from jepsen_tpu import native_lib
        L = native_lib.hist_lib()
        if L is None:
            pytest.skip("native hist lib unavailable")
        rng = random.Random(11)
        for n in (0, 1, 3, 4, 7, 8, 31, 32, 33, 100, 4096):
            data = bytes(rng.randrange(256) for _ in range(n))
            assert L.jt_xxh64_buf(data, n, 0) == store.xxh64(data)
            assert L.jt_xxh64_buf(data, n, 7) == store.xxh64(data, 7)


# ---------------------------------------------------------------------------
# The streaming pipeline: unordered delivery + reorder buffer, shm
# fallback, leak checks, span-trim regression.
# ---------------------------------------------------------------------------

class TestPipeline:
    def test_unordered_reorder_correctness(self, tmp_path,
                                           monkeypatch):
        # v1 sidecars: with v2 on, pooled append encodes send sidecar
        # REFERENCES (the parent mmaps; zero shm bytes by design —
        # tests/test_warm_path.py covers that transport), and this
        # test is about the shm descriptor path
        monkeypatch.setenv("JEPSEN_TPU_SIDECAR_V2", "0")
        dirs = append_dirs(tmp_path, n=7)
        tr = trace.fresh_run("reorder")
        got = []
        for part in ingest.iter_encode_chunks(dirs, chunk=3,
                                              processes=2):
            assert len(part) <= 3
            got.extend(part)
        assert [d for d, _e in got] == dirs     # in order, no dups
        serial = ingest.parallel_encode(dirs, processes=0)
        for (_d, e), s in zip(got, serial):
            assert_append_identical(e, s)
        if shm.enabled() and shm.available():
            assert tr.counter("shm_bytes").value > 0
        assert not shm_leaks()

    def test_fallback_when_shm_unusable(self, tmp_path, monkeypatch):
        dirs = append_dirs(tmp_path, n=4)
        monkeypatch.setattr(shm, "available", lambda: False)
        tr = trace.fresh_run("fallback")
        info: dict = {}
        got = []
        for part in ingest.iter_encode_chunks(dirs, chunk=2,
                                              processes=2, info=info):
            got.extend(part)
        assert info["pooled"] is True            # pool still ran
        assert [d for d, _e in got] == dirs
        serial = ingest.parallel_encode(dirs, processes=0)
        for (_d, e), s in zip(got, serial):
            assert_append_identical(e, s)
        assert tr.counter("shm_bytes").value == 0  # pickle transport
        assert not shm_leaks()

    def test_gate_off_uses_pickle(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_SHM_INGEST", "0")
        dirs = append_dirs(tmp_path, n=3)
        tr = trace.fresh_run("gate-off")
        got = [p for part in ingest.iter_encode_chunks(
            dirs, chunk=2, processes=2) for p in part]
        assert [d for d, _e in got] == dirs
        assert tr.counter("shm_bytes").value == 0

    def test_worker_exception_no_leak(self, tmp_path):
        dirs = append_dirs(tmp_path, n=3)
        bad = tmp_path / "bad"
        bad.mkdir()                             # no history: raises
        got = [p for part in ingest.iter_encode_chunks(
            dirs + [bad], chunk=2, processes=2) for p in part]
        assert [d for d, _e in got] == dirs + [bad]
        assert isinstance(got[-1][1], Exception)
        assert all(not isinstance(e, Exception) for _d, e in got[:-1])
        assert not shm_leaks()

    def test_pool_failure_trims_spans_and_unlinks(self, tmp_path,
                                                 monkeypatch):
        """ISSUE 3 S2 regression: a mid-stream pool failure must (a)
        leave info["parse_spans"] covering exactly the YIELDED items —
        buffered-but-unyielded parses must not inflate measured
        overlap — (b) resume serially without dropping or duplicating
        a run dir, and (c) unlink every segment a worker created for
        an item the parent never consumed."""
        dirs = append_dirs(tmp_path, n=6)
        encs = ingest.parallel_encode(dirs, processes=0)
        delivered = 3
        stale: list[str] = []
        tasks_box: list = []

        class FakeFut:
            """Delivers like the executor pool: results in submit
            order; the item past `delivered` raises (the
            BrokenProcessPool moment of a SIGKILLed worker)."""

            def __init__(self, k, task):
                self.k = k
                self.task = task

            def result(self):
                idx, _d, checker, name, _tctx = self.task
                if self.k >= delivered:
                    raise RuntimeError("pool died mid-stream")
                if name is not None and self.k == delivered - 1:
                    # this item's segment was written but the parent
                    # raises before a later item; simulate a crash
                    # AFTER segment creation for the NEXT
                    # (undelivered) task too
                    nxt = tasks_box[self.k + 1][3]
                    if nxt is not None:
                        desc = shm.export(encs[tasks_box[self.k + 1][0]],
                                          nxt, checker)
                        assert shm.is_descriptor(desc)
                        stale.append(nxt)
                payload = (shm.export(encs[idx], name, checker)
                           if name is not None else encs[idx])
                return idx, payload, {"cache": None}, 0.0, 0.0

        class FakeExecutor:
            def __init__(self, max_workers=None, mp_context=None):
                pass

            def submit(self, fn, task):
                tasks_box.append(task)
                return FakeFut(len(tasks_box) - 1, task)

            def shutdown(self, wait=True, cancel_futures=False):
                pass

        def fake_as_completed(fs):
            return iter(sorted(fs, key=lambda f: f.k))

        import concurrent.futures as cf
        monkeypatch.setattr(cf, "ProcessPoolExecutor", FakeExecutor)
        monkeypatch.setattr(cf, "as_completed", fake_as_completed)
        info: dict = {}
        got = []
        for part in ingest.iter_encode_chunks(dirs, chunk=2,
                                              processes=2, info=info):
            got.extend(part)
        # complete, ordered, no dups (serial resume from `done`)
        assert [d for d, _e in got] == dirs
        # spans trimmed to yielded items: the fake pool delivered 3
        # before dying, so exactly one full chunk (2 items) yielded
        # from the pooled phase
        assert len(info["parse_spans"]) == 2
        assert stale, "test should have staged a stale segment"
        assert not shm_leaks()

    def test_overlap_still_measured(self, tmp_path):
        """parse_spans still intersect caller device windows on the
        shm path (the measured-overlap contract test_ingest pins for
        the pickle path)."""
        import time as _t
        dirs = append_dirs(tmp_path, n=6, T=400)
        info: dict = {}
        dev = []
        for part in ingest.iter_encode_chunks(dirs, chunk=1,
                                              processes=2, info=info):
            t0 = _t.monotonic()
            _t.sleep(0.05)
            dev.append((t0, _t.monotonic()))
        assert info["pooled"] is True
        assert len(info["parse_spans"]) == 6
        assert all(b >= a for a, b in info["parse_spans"])


# ---------------------------------------------------------------------------
# HBM envelope: pipelining must not double the device-resident
# footprint the bucketer sized for (ROADMAP PR-1 open item).
# ---------------------------------------------------------------------------

class TestHbmEnvelope:
    def _encs(self, n=5, T=40):
        return [synth.synth_encoded_history(T=T + i, K=8)
                for i in range(n)]

    def test_bucket_cells_times_inflight_within_budget(self):
        encs = self._encs()
        tr = trace.fresh_run("envelope")
        # budget sized so ONE bucket of everything would fit, but the
        # halved per-bucket budget forces a split
        cells = 128 * 128           # T=40 pads to 128
        budget = 4 * cells
        out = parallel.check_bucketed(encs, None, budget_cells=budget)
        md = tr.metrics_dict()
        h = md["histograms"]["bucket_cells"]
        assert md["counters"]["buckets_dispatched"] >= 2
        # the invariant: max per-dispatch footprint x the sync
        # wrapper's max_inflight (2) stays inside the caller's budget
        assert h["max"] * 2 <= budget, (h, budget)
        assert md["gauges"]["inflight_depth"] == 0   # fully drained
        assert md["counters"]["pad_waste_cells"] >= 0
        # verdicts unaffected by the split
        assert out == parallel.check_bucketed(encs, None)

    def test_max_inflight_one_keeps_full_budget(self):
        encs = self._encs()
        tr = trace.fresh_run("envelope-1")
        cells = 128 * 128
        budget = 8 * cells
        pv = parallel.check_bucketed_async(encs, None,
                                           budget_cells=budget,
                                           max_inflight=1)
        pv.result()
        md = tr.metrics_dict()
        # depth 1: no halving, everything fits one bucket
        assert md["counters"]["buckets_dispatched"] == 1
        assert md["histograms"]["bucket_cells"]["max"] <= budget

    def test_oversized_singleton_dispatched_alone(self):
        """A single history too big for the per-slot budget can't be
        subdivided: it must peel off, dispatch after the pipelined
        buckets drain, and share the envelope with nothing — while
        verdicts stay identical to the unconstrained sweep."""
        big = synth.synth_encoded_history(T=300, K=8)   # pads to 384²
        small = [synth.synth_encoded_history(T=40 + i, K=8)
                 for i in range(10)]
        encs = [big] + small
        ref = parallel.check_bucketed(encs, None)
        tr = trace.fresh_run("oversized")
        budget = 200_000    # eff 100k: big (147k cells) is oversized
        out = parallel.check_bucketed(encs, None, budget_cells=budget)
        assert out == ref
        md = tr.metrics_dict()
        h = md["histograms"]["bucket_cells"]
        # the oversized bucket is the only one allowed past eff budget
        assert h["max"] == 384 * 384
        over = [b for b in (int(k) for k in h["log2_buckets"])
                if 2 ** b > budget // 2]
        assert len(over) <= 1
        assert md["gauges"]["inflight_depth"] == 0

    def test_pack_thread_parity_and_gate(self, monkeypatch):
        encs = self._encs(n=6)
        budget = 2 * 128 * 128      # several buckets -> threaded path
        threaded = parallel.check_bucketed(encs, None,
                                           budget_cells=budget)
        monkeypatch.setenv("JEPSEN_TPU_PACK_THREAD", "0")
        inline = parallel.check_bucketed(encs, None,
                                         budget_cells=budget)
        assert threaded == inline
