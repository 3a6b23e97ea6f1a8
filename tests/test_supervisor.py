"""The fault-tolerant sweep supervisor (ISSUE 4).

Every recovery layer is driven through the self-nemesis hook
(JEPSEN_TPU_FAULT_INJECT) — the checker gets its own nemesis, so no
real faults are needed: deterministic encode failures quarantine
instead of killing the sweep (and the non-quarantined verdicts stay
byte-identical to a fault-free run), simulated OOMs exercise the
halve-and-retry backdown down to singleton quarantine, a SIGKILLed
pool worker surfaces as BrokenProcessPool -> serial resume rather
than a hung parent, the dispatch watchdog quarantines a wedged
device wait, interrupted sweeps resume from the verdicts.jsonl
journal alone, and JEPSEN_TPU_STRICT=1 restores fail-fast on every
path. Satellites: jittered-exponential with_retry, daemonic
timeout_call, shm.reclaim_stale, corrupted-sidecar rebuild.
Everything here is spawn-safe and fast (tier-1, `-m 'not slow'`).
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from jepsen_tpu import parallel, shm, supervisor, trace
from jepsen_tpu.checker.elle.encode import encode_history
from jepsen_tpu.checker.elle.synth import synth_append_history
from jepsen_tpu.store import Store, VerdictJournal
from jepsen_tpu.util import timeout_call, with_retry


@pytest.fixture(autouse=True)
def _clean_injection(monkeypatch):
    """Every test starts and ends with the nemesis disarmed."""
    monkeypatch.delenv("JEPSEN_TPU_FAULT_INJECT", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_STRICT", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_DISPATCH_TIMEOUT_S", raising=False)
    supervisor.reset_injection()
    yield
    supervisor.reset_injection()


def arm(monkeypatch, spec: str) -> None:
    monkeypatch.setenv("JEPSEN_TPU_FAULT_INJECT", spec)
    supervisor.reset_injection()


def write_run(base, name, hist):
    d = base / name
    d.mkdir(parents=True)
    with open(d / "history.jsonl", "w") as f:
        for o in hist:
            f.write(json.dumps(o) + "\n")
    return d


def synth_store(tmp_path, n=8, T=30, bad_every=0):
    store = Store(tmp_path / "store")
    dirs = []
    for i in range(n):
        hist = synth_append_history(T=T, K=6, seed=i,
                                    g1c=bool(bad_every
                                             and i % bad_every == 0))
        dirs.append(write_run(store.base / "etcd",
                              f"2020010{i}T000000", hist))
    return store, dirs


def encode_selected(dirs, rate) -> set:
    """The run dirs the encode:<rate> nemesis deterministically picks
    (same hash as supervisor._Injector)."""
    inj = supervisor._Injector(f"encode:{rate}")
    return {d for d in dirs
            if inj.selects("encode", os.path.basename(str(d)))}


# ---------------------------------------------------------------------------
# Utility satellites
# ---------------------------------------------------------------------------

def test_with_retry_exponential_jitter(monkeypatch):
    sleeps: list[float] = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 3:
            raise OSError("transient")
        return "ok"

    assert with_retry(flaky, retries=3, backoff=0.1,
                      exceptions=(OSError,), exponential=True) == "ok"
    assert len(sleeps) == 3
    for attempt, dt in enumerate(sleeps):
        lo = 0.1 * 2 ** attempt * 0.5
        hi = 0.1 * 2 ** attempt * 1.5
        assert lo <= dt <= hi, (attempt, dt)


def test_with_retry_fatal_never_retries(monkeypatch):
    monkeypatch.setattr(time, "sleep",
                        lambda *_: pytest.fail("slept on fatal"))
    calls = {"n": 0}

    def gone():
        calls["n"] += 1
        raise FileNotFoundError("segment is gone")

    with pytest.raises(FileNotFoundError):
        with_retry(gone, retries=5, backoff=0.1,
                   exceptions=(OSError,),
                   fatal=(FileNotFoundError,))
    assert calls["n"] == 1


def test_timeout_call_abandons_daemonic_named_thread():
    release = threading.Event()
    got = timeout_call(0.05, release.wait, default="timed-out")
    assert got == "timed-out"
    # the abandoned worker must be daemonic (interpreter exit cannot
    # hang on it) and attributable in a faulthandler dump
    stragglers = [t for t in threading.enumerate()
                  if t.name == "timeout-call" and t.is_alive()]
    assert stragglers and all(t.daemon for t in stragglers)
    release.set()


# ---------------------------------------------------------------------------
# The self-nemesis (fault-injection spec)
# ---------------------------------------------------------------------------

def test_injector_spec_parsing_and_determinism():
    inj = supervisor._Injector("encode:0.5,oom:first,kill:2")
    assert inj.modes == {"encode": ("rate", 0.5),
                        "oom": ("count", 1), "kill": ("count", 2)}
    # rate selection is a pure function of the name: identical across
    # processes and retries (the same run fails every time, so it
    # exhausts its budget and quarantines instead of flapping)
    again = supervisor._Injector("encode:0.5")
    for n in ("r0", "r1", "20200101T000000"):
        assert inj.selects("encode", n) == again.selects("encode", n)
        assert inj.selects("encode", n) == inj.selects("encode", n)
    # count modes burn per-process charges
    assert inj.selects("oom") is True
    assert inj.selects("oom") is False
    assert inj.selects("kill") and inj.selects("kill")
    assert inj.selects("kill") is False


def test_encode_fault_raises_in_parent(monkeypatch, tmp_path):
    from jepsen_tpu import ingest
    arm(monkeypatch, "encode:1.0")
    d = write_run(tmp_path, "r0", synth_append_history(T=10, K=3,
                                                       seed=0))
    with pytest.raises(supervisor.InjectedFault):
        ingest.encode_run_dir(d)
    # kill-mode in the PARENT degrades to a raise, never a dead sweep
    arm(monkeypatch, "kill:first")
    with pytest.raises(supervisor.InjectedFault):
        ingest.encode_run_dir(d)


# ---------------------------------------------------------------------------
# Tentpole: encode-fault quarantine through a full analyze-store sweep
# ---------------------------------------------------------------------------

def sweep_artifacts(store, dirs):
    for d in dirs:
        for f in ("results.json", "results.edn", ".sweep-append",
                  ".sweep-wr"):
            (d / f).unlink(missing_ok=True)
    (store.base / "verdicts.jsonl").unlink(missing_ok=True)


def serial_ingest(monkeypatch):
    """Pin the sweep's ingest to the in-process serial path: pool
    workers re-import jax per spawn (~seconds each on a small CI box)
    and add nothing to what these tests prove — the pooled path gets
    its own dedicated coverage in the SIGKILL test below."""
    monkeypatch.setattr(os, "cpu_count", lambda: 1)


def test_encode_fault_sweep_quarantines_and_matches_fault_free(
        tmp_path, capsys, monkeypatch):
    """The acceptance smoke: with encode faults injected the sweep
    COMPLETES, quarantined + verdicted runs cover the whole store, the
    journal records every history, and the non-quarantined verdicts
    are byte-identical to a fault-free sweep."""
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, dirs = synth_store(tmp_path, n=8)
    rate = 0.4
    expect_q = encode_selected(dirs, rate)
    assert expect_q and len(expect_q) < len(dirs)  # both sides present

    assert cli.analyze_store(store, checker="append") == 0
    clean = {d: (d / "results.json").read_bytes() for d in dirs}
    capsys.readouterr()
    sweep_artifacts(store, dirs)

    # both nemeses in one sweep: encode faults quarantine AND the
    # first bucket dispatch OOMs (the backdown must re-produce
    # byte-identical verdicts for everything it recovers)
    arm(monkeypatch, f"encode:{rate},oom:first")
    rc = cli.analyze_store(store, checker="append")
    capsys.readouterr()
    assert rc == 2  # worst validity: unknown (no invalid runs here)
    quarantined = set()
    for d in dirs:
        res = json.loads((d / "results.json").read_text())
        if res.get("quarantined"):
            assert res["valid?"] == "unknown"
            assert res["quarantined"] == "encode"
            assert "injected encode fault" in res["error"]
            quarantined.add(d)
        else:
            # byte-identical to the fault-free sweep
            assert (d / "results.json").read_bytes() == clean[d]
    assert quarantined == expect_q
    # the journal covers the WHOLE store: quarantined + verdicted
    entries = VerdictJournal.load(store.base / "verdicts.jsonl")
    assert len(entries) == len(dirs)
    n_q = sum(1 for e in entries.values() if e.get("quarantined"))
    assert n_q == len(expect_q)
    assert n_q + sum(1 for e in entries.values()
                     if e["valid?"] is True) == len(dirs)
    # recovery is tracer-attributed in the sweep metrics
    metrics = json.loads((store.base / "metrics.json").read_text())
    assert metrics["counters"]["quarantined"] == len(expect_q)
    assert metrics["counters"]["oom_retries"] >= 1
    assert "shm_stale_reclaimed" in metrics["counters"]


def test_strict_restores_fail_fast(tmp_path, capsys, monkeypatch):
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, dirs = synth_store(tmp_path, n=4)
    arm(monkeypatch, "encode:1.0")
    monkeypatch.setenv("JEPSEN_TPU_STRICT", "1")
    with pytest.raises(supervisor.InjectedFault):
        cli.analyze_store(store, checker="append")
    capsys.readouterr()


def test_corrupt_history_quarantines_not_raises(tmp_path, capsys,
                                                monkeypatch):
    """A genuinely unparseable run (truncated history.jsonl) degrades
    to `valid? unknown` — the stored-checker detour fails too — while
    sibling runs still verify."""
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, dirs = synth_store(tmp_path, n=3)
    (dirs[1] / "history.jsonl").write_text('{"type": "invoke", "proc')
    rc = cli.analyze_store(store, checker="append")
    capsys.readouterr()
    assert rc == 2
    res = json.loads((dirs[1] / "results.json").read_text())
    assert res["valid?"] == "unknown" and res.get("quarantined")
    for d in (dirs[0], dirs[2]):
        assert json.loads(
            (d / "results.json").read_text())["valid?"] is True


# ---------------------------------------------------------------------------
# Tentpole: OOM backdown + watchdog at the dispatcher
# ---------------------------------------------------------------------------

def encs_for(n=6, T=30):
    return [encode_history(synth_append_history(T=T, K=6, seed=i))
            for i in range(n)]


def test_oom_first_splits_and_matches(monkeypatch):
    encs = encs_for()
    tr = trace.fresh_run("oom-split")
    base = parallel.check_bucketed(encs, None)
    arm(monkeypatch, "oom:first")
    got = parallel.check_bucketed(encs, None)
    assert got == base
    ctr = tr.metrics_dict()["counters"]
    assert ctr["oom_retries"] >= 1
    assert ctr["bucket_splits"] >= 1
    assert "quarantined" not in ctr or ctr["quarantined"] == 0


def test_oom_always_quarantines_singletons(monkeypatch):
    encs = encs_for(4)
    tr = trace.fresh_run("oom-exhaust")
    arm(monkeypatch, "oom:999")
    got = parallel.check_bucketed(encs, None)
    assert all(isinstance(g, supervisor.Quarantined) for g in got)
    assert all(g.stage == "oom" for g in got)
    assert tr.metrics_dict()["counters"]["quarantined"] == len(encs)
    v = got[0].verdict("append")
    assert v["valid?"] == "unknown" and v["quarantined"] == "oom"


def test_oom_strict_reraises(monkeypatch):
    encs = encs_for(3)
    arm(monkeypatch, "oom:first")
    monkeypatch.setenv("JEPSEN_TPU_STRICT", "1")
    with pytest.raises(supervisor.InjectedOom):
        parallel.check_bucketed(encs, None)


def test_watchdog_retries_then_quarantines(monkeypatch):
    """A wedged block_until_ready burns both watchdog attempts, then
    the bucket quarantines (never hangs, never crashes); without the
    env gate the watchdog is off. One wedged dispatch counts as ONE
    watchdog_timeout however many attempts it burns, so the counter
    correlates 1:1 with distinct device stalls."""
    assert supervisor.dispatch_timeout_s() is None
    monkeypatch.setenv("JEPSEN_TPU_DISPATCH_TIMEOUT_S", "0.05")
    assert supervisor.dispatch_timeout_s() == 0.05
    release = threading.Event()

    def wedged(_flags):
        release.wait(2.0)
        return np.zeros(2, np.int64)

    monkeypatch.setattr(parallel.jax, "block_until_ready", wedged)
    tr = trace.fresh_run("watchdog")
    kw = dict(classify=True, realtime=False, process_order=False,
              fused=None)
    out = parallel._finish_part([], [0, 1], np.zeros(2, np.int64),
                                None, 1 << 20, kw, tr, None)
    release.set()
    assert all(isinstance(w, supervisor.Quarantined) for w in out)
    assert all(w.stage == "watchdog" for w in out)
    ctr = tr.metrics_dict()["counters"]
    assert ctr["watchdog_timeouts"] == 1
    assert ctr["quarantined"] == 2


def test_watchdog_strict_reraises(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_DISPATCH_TIMEOUT_S", "0.05")
    monkeypatch.setenv("JEPSEN_TPU_STRICT", "1")
    release = threading.Event()

    def wedged(_flags):
        release.wait(2.0)
        return np.zeros(1, np.int64)

    monkeypatch.setattr(parallel.jax, "block_until_ready", wedged)
    tr = trace.fresh_run("watchdog-strict")
    kw = dict(classify=True, realtime=False, process_order=False,
              fused=None)
    with pytest.raises(supervisor.WatchdogTimeout):
        parallel._finish_part([], [0], np.zeros(1, np.int64), None,
                              1 << 20, kw, tr, None)
    release.set()


def test_wr_backdown_quarantines_watchdog_timeouts():
    """The wr sweep's watchdog contract: a batch-level WatchdogTimeout
    degrades to singletons (exactly like OOM), and a history whose
    singleton re-check ALSO times out quarantines with stage
    "watchdog" — never a hung or dead sweep."""
    from jepsen_tpu import cli

    class FakeKernels:
        def __init__(self):
            self.calls = 0

        def check_edge_batch_bucketed(self, edges):
            self.calls += 1
            if self.calls == 1 or edges[0]["i"] == 0:
                raise supervisor.WatchdogTimeout("wedged dispatch")
            return [{"i": e["i"]} for e in edges]

    class FakeWr:
        @staticmethod
        def to_edge_dict(e):
            return e

    tr = trace.fresh_run("wr-watchdog")
    out = cli._wr_chunk_with_backdown(
        [("d0", {"i": 0}), ("d1", {"i": 1})], FakeKernels(), FakeWr)
    assert isinstance(out[0], supervisor.Quarantined)
    assert out[0].stage == "watchdog"
    assert out[1] == {"i": 1}
    ctr = tr.metrics_dict()["counters"]
    assert ctr["quarantined"] == 1
    # a watchdog batch failure is NOT an OOM retry: the bench's
    # robustness block tells the two causes apart
    assert "oom_retries" not in ctr


def test_wr_backdown_stops_probing_wedged_device():
    """Two consecutive singleton watchdog timeouts mean the DEVICE is
    wedged, not the data: the chunk's remainder quarantines without
    burning 2x the timeout per history on a dead runtime."""
    from jepsen_tpu import cli

    class AlwaysWedged:
        def __init__(self):
            self.calls = 0

        def check_edge_batch_bucketed(self, edges):
            self.calls += 1
            raise supervisor.WatchdogTimeout("wedged dispatch")

    class FakeWr:
        @staticmethod
        def to_edge_dict(e):
            return e

    trace.fresh_run("wr-wedged")
    kernels = AlwaysWedged()
    out = cli._wr_chunk_with_backdown(
        [(f"d{i}", {"i": i}) for i in range(6)], kernels, FakeWr)
    assert all(isinstance(w, supervisor.Quarantined)
               and w.stage == "watchdog" for w in out)
    # 1 batch probe + 2 singleton probes, then no more dispatches
    assert kernels.calls == 3


def test_pack_failure_quarantines_only_its_bucket(monkeypatch):
    """A history that breaks packing fails ALONE (per-bucket producer
    isolation): the rest of the sweep still verdicts."""
    encs = encs_for(4)
    base = parallel.check_bucketed(encs, None)
    poisoned = encs[2]
    orig = parallel.K.pack_batch

    def bad_pack(group, *a, **kw):
        if any(e is poisoned for e in group):
            raise ValueError("poisoned history")
        return orig(group, *a, **kw)

    monkeypatch.setattr(parallel.K, "pack_batch", bad_pack)
    trace.fresh_run("pack-poison")
    # budget forcing one bucket per history so the poisoned one
    # shares a bucket with nothing
    budget = 128 * 128  # one padded T=30 history exactly
    got = parallel.check_bucketed(encs, None, budget_cells=budget)
    for i, (g, b) in enumerate(zip(got, base)):
        if i == 2:
            assert isinstance(g, supervisor.Quarantined)
            assert g.stage == "pack"
        else:
            assert g == b


def test_keyboard_interrupt_is_never_quarantined(monkeypatch):
    """Ctrl-C during packing must stop the sweep, not journal a bogus
    permanent 'unknown' verdict for the bucket it landed in."""
    encs = encs_for(3)

    def interrupted(*a, **kw):
        raise KeyboardInterrupt

    monkeypatch.setattr(parallel.K, "pack_batch", interrupted)
    trace.fresh_run("ctrl-c")
    with pytest.raises(KeyboardInterrupt):
        parallel.check_bucketed(encs, None)


# ---------------------------------------------------------------------------
# Worker crash mid-stream (the kill nemesis) + corrupted sidecars
# ---------------------------------------------------------------------------

def shm_leaks() -> list[str]:
    try:
        # this process's segments only: other test workers' pools
        # create and reclaim their own concurrently
        mine = f"{shm.NAME_PREFIX}_{os.getpid()}_"
        return [x for x in os.listdir("/dev/shm") if x.startswith(mine)]
    except OSError:
        return []


def test_worker_sigkill_mid_stream_degrades_to_serial(
        tmp_path, monkeypatch):
    """SIGKILL of a pool worker during iter_encode_chunks must surface
    as BrokenProcessPool -> serial resume (one InjectedFault payload
    from the parent's re-encode, everything else encoded), never a
    hung parent or a leaked /dev/shm segment."""
    from jepsen_tpu import ingest
    dirs = [write_run(tmp_path, f"r{i}",
                      synth_append_history(T=20, K=4, seed=i))
            for i in range(6)]
    before = shm_leaks()
    arm(monkeypatch, "kill:first")
    out = []
    for chunk in ingest.iter_encode_chunks(dirs, "append", chunk=3,
                                           processes=2):
        out.extend(chunk)
    assert [d for d, _ in out] == dirs
    errs = [e for _, e in out if isinstance(e, Exception)]
    good = [e for _, e in out if not isinstance(e, Exception)]
    # the parent's serial resume burns the per-process kill charge as
    # an InjectedFault on one run; every other run encodes fine
    assert len(errs) == 1
    assert isinstance(errs[0], supervisor.InjectedFault)
    assert len(good) == len(dirs) - 1
    assert all(e.n > 0 for e in good)
    assert shm_leaks() == before


def test_corrupted_sidecar_invalidated_and_rebuilt(tmp_path):
    """A truncated/corrupted encoded.v1.bin must never raise: the
    cache degrades to a miss, the history re-encodes, and the next
    sweep leaves a VALID sidecar behind."""
    from jepsen_tpu import ingest, store as jstore
    d = write_run(tmp_path, "r0", synth_append_history(T=25, K=5,
                                                       seed=3))
    fresh = ingest.encode_run_dir(d)      # writes the sidecar
    sc = jstore.encoded_cache_path(d, "append")
    assert sc.is_file()
    assert jstore.load_encoded(d, "append") is not None
    blob = sc.read_bytes()
    for corrupt in (blob[:len(blob) // 2],        # truncated tail
                    b"garbage" + blob[7:],        # smashed magic
                    b""):                         # zero-length
        sc.write_bytes(corrupt)
        assert jstore.load_encoded(d, "append") is None  # miss, no raise
        enc = ingest.encode_run_dir(d)    # re-encodes + rebuilds
        assert enc.n == fresh.n
        assert np.array_equal(enc.appends, fresh.appends)
        rebuilt = jstore.load_encoded(d, "append")
        assert rebuilt is not None and rebuilt.n == fresh.n


# ---------------------------------------------------------------------------
# Resumable verdict journal
# ---------------------------------------------------------------------------

def test_verdict_journal_roundtrip_and_truncated_tail(tmp_path):
    j = VerdictJournal(tmp_path / "verdicts.jsonl", base=tmp_path)
    j.record(tmp_path / "etcd" / "r0", "append", {"valid?": True})
    j.record(tmp_path / "etcd" / "r1", "append",
             {"valid?": "unknown", "quarantined": "encode",
              "error": "boom"})
    j.close()
    # a crash-truncated tail line is skipped, not fatal
    with open(tmp_path / "verdicts.jsonl", "a") as f:
        f.write('{"dir": "etcd/r2", "chec')
    entries = VerdictJournal.load(tmp_path / "verdicts.jsonl")
    assert entries[("etcd/r0", "append")]["valid?"] is True
    e1 = entries[("etcd/r1", "append")]
    assert e1["valid?"] == "unknown" and e1["quarantined"] == "encode"
    assert len(entries) == 2


def test_verdict_journal_seals_torn_tail_on_append(tmp_path):
    """A journal killed mid-write ends without its newline; the next
    sweep's first append must not merge into the torn bytes (that
    corrupts the NEW record — load would drop a real verdict and
    --resume would grind over it again)."""
    path = tmp_path / "verdicts.jsonl"
    j = VerdictJournal(path, base=tmp_path)
    j.record(tmp_path / "etcd" / "r0", "append", {"valid?": True})
    j.close()
    with open(path, "a") as f:
        f.write('{"dir": "etcd/r1", "chec')   # torn: no newline
    j2 = VerdictJournal(path, base=tmp_path)
    j2.record(tmp_path / "etcd" / "r2", "append", {"valid?": False})
    j2.close()
    entries = VerdictJournal.load(path)
    assert entries[("etcd/r0", "append")]["valid?"] is True
    assert entries[("etcd/r2", "append")]["valid?"] is False
    assert ("etcd/r1", "append") not in entries
    assert len(entries) == 2


def test_resume_from_journal_alone(tmp_path, capsys, monkeypatch):
    """Kill the sweep halfway: the journal (not the per-run markers,
    which we strip to prove the point) drives --resume, and only the
    un-journaled remainder reprocesses."""
    from jepsen_tpu import cli, ingest
    store, dirs = synth_store(tmp_path, n=4)

    def two_chunks(rd, checker="append", **kw):
        rd = list(rd)
        for part in (rd[:2], rd[2:]):
            yield list(zip(part, ingest.parallel_encode(
                part, checker=checker, processes=0)))

    monkeypatch.setattr(ingest, "iter_encode_chunks", two_chunks)
    calls = {"n": 0}
    orig = parallel.check_bucketed

    def dying(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("killed mid-sweep")
        return orig(*a, **kw)

    monkeypatch.setattr(parallel, "check_bucketed", dying)
    with pytest.raises(RuntimeError):
        cli.analyze_store(store, checker="append")
    capsys.readouterr()
    entries = VerdictJournal.load(store.base / "verdicts.jsonl")
    assert {d for (d, _c) in entries} == \
        {os.path.relpath(d, store.base) for d in dirs[:2]}
    # strip chunk 1's per-run markers: the journal alone must carry
    # the resume (an interrupted sweep may die between the verdict
    # landing in the journal and any given run-dir artifact)
    for d in dirs[:2]:
        (d / "results.json").unlink()
        (d / ".sweep-append").unlink()
    monkeypatch.setattr(parallel, "check_bucketed", orig)
    rc = cli.analyze_store(store, checker="append", resume=True)
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["dir"] for ln in lines] == [str(d) for d in dirs[2:]]
    entries = VerdictJournal.load(store.base / "verdicts.jsonl")
    assert len(entries) == len(dirs)


# ---------------------------------------------------------------------------
# shm reclamation + CLI debuggability
# ---------------------------------------------------------------------------

def dead_pid() -> int:
    for pid in range(400_000, 500_000):
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return pid
        except OSError:
            continue
    pytest.skip("no dead pid found")


def test_reclaim_stale_unlinks_only_dead_pids():
    if not shm.available():
        pytest.skip("/dev/shm unusable")
    from multiprocessing import shared_memory as sm
    stale_name = f"{shm.NAME_PREFIX}_{dead_pid()}_deadbeef0000"
    live_name = f"{shm.NAME_PREFIX}_{os.getpid()}_cafebabe0000"
    stale = sm.SharedMemory(name=stale_name, create=True, size=64)
    live = sm.SharedMemory(name=live_name, create=True, size=64)
    try:
        assert shm.reclaim_stale() >= 1
        names = os.listdir("/dev/shm")
        assert stale_name not in names      # dead owner: reclaimed
        assert live_name in names           # live owner: untouched
    finally:
        for seg in (stale, live):
            try:
                seg.close()
                seg.unlink()
            except FileNotFoundError:
                pass


def test_run_cli_registers_faulthandler(tmp_path, capsys):
    import faulthandler
    import signal
    from jepsen_tpu import cli
    rc = cli.run_cli(lambda tmap, args: tmap,
                     argv=["analyze-store", "--store",
                           str(tmp_path / "empty")])
    capsys.readouterr()
    assert rc == 254            # no stored runs
    # SIGUSR1 now dumps all threads' stacks (hung-sweep debugging)
    assert faulthandler.unregister(signal.SIGUSR1)
