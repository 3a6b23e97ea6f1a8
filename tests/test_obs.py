"""The live-telemetry layer (jepsen_tpu.obs): health.json shape and
write atomicity under a concurrent reader, the Prometheus exposition
(golden-file), the `/metrics`+`/healthz` endpoint and its gates, the
typed flight-recorder event API (including a fault-injected sweep
whose every quarantine lands in events.jsonl), crash-atomic
trace/metrics export. All tier-1, CPU-only.
"""

from __future__ import annotations

import json
import os
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from jepsen_tpu import obs, supervisor, trace
from jepsen_tpu.checker.elle.synth import synth_append_history
from jepsen_tpu.obs.health import (HealthSampler, health_snapshot,
                                   maybe_start_health_sampler)
from jepsen_tpu.obs.prom import (MetricsServer,
                                 maybe_start_metrics_server,
                                 render_prometheus)
from jepsen_tpu.store import Store

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts with the obs layer uninstalled and both gates
    unset; the flight recorder is reset again at teardown so a failed
    test can't leak an installed log into the next."""
    monkeypatch.delenv("JEPSEN_TPU_HEALTH_INTERVAL_S", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_METRICS_PORT", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_FAULT_INJECT", raising=False)
    obs.reset_events()
    trace.reset()
    supervisor.reset_injection()
    yield
    obs.reset_events()
    trace.reset()
    supervisor.reset_injection()


def synth_store(tmp_path, n=3, T=40):
    store = Store(tmp_path / "store")
    dirs = []
    for i in range(n):
        d = store.base / "etcd" / f"2020010{i + 1}T000000"
        d.mkdir(parents=True)
        hist = synth_append_history(T=T, K=4, seed=i)
        (d / "history.jsonl").write_text(
            "\n".join(json.dumps(o) for o in hist) + "\n")
        dirs.append(d)
    return store, dirs


def serial_ingest(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 1)


# ---------------------------------------------------------------------------
# health.json: snapshot shape, gating, atomicity
# ---------------------------------------------------------------------------

def test_health_snapshot_shape_and_math():
    tr = trace.Tracer(run="unit")
    tr.gauge("runs_total").set(10)
    tr.counter("runs_verdicted").inc(5)
    tr.counter("buckets_dispatched").inc(4)
    tr.counter("buckets_resolved").inc(3)
    tr.counter("quarantined").inc(2)
    snap = health_snapshot(tr, seq=7,
                           started_mono=time.monotonic() - 10.0)
    assert snap["v"] == 1 and snap["run"] == "unit"
    assert snap["heartbeat"]["seq"] == 7
    assert snap["heartbeat"]["monotonic"] > 0
    p = snap["progress"]
    assert p["runs_total"] == 10 and p["runs_verdicted"] == 5
    assert p["buckets_dispatched"] == 4 and p["buckets_resolved"] == 3
    assert snap["robustness"]["quarantined"] == 2
    assert snap["robustness"]["watchdog_timeouts"] == 0
    t = snap["throughput"]
    assert t["elapsed_secs"] == pytest.approx(10.0, abs=1.0)
    assert t["runs_per_sec"] == pytest.approx(0.5, rel=0.15)
    # 5 runs left at ~0.5 runs/s
    assert t["eta_secs"] == pytest.approx(10.0, rel=0.2)


def test_health_snapshot_null_tracer_all_null_fields():
    snap = health_snapshot(trace.NullTracer(), seq=1)
    assert snap["progress"]["runs_total"] is None
    assert snap["progress"]["runs_verdicted"] == 0
    assert snap["throughput"]["eta_secs"] is None


def test_health_sampler_gate(monkeypatch, tmp_path):
    # unset / zero / negative JEPSEN_TPU_HEALTH_INTERVAL_S: off
    assert maybe_start_health_sampler(tmp_path) is None
    for off in ("0", "-1", "not-a-number"):
        monkeypatch.setenv("JEPSEN_TPU_HEALTH_INTERVAL_S", off)
        assert maybe_start_health_sampler(tmp_path) is None
    monkeypatch.setenv("JEPSEN_TPU_HEALTH_INTERVAL_S", "0.01")
    tr = trace.Tracer(run="gated")
    s = maybe_start_health_sampler(tmp_path, tracer_fn=lambda: tr)
    try:
        assert s is not None
        assert (tmp_path / "health.json").is_file()  # first write is
        # synchronous at start()
    finally:
        s.stop()
    snap = json.loads((tmp_path / "health.json").read_text())
    assert snap["run"] == "gated"


def test_health_atomic_under_concurrent_reader(tmp_path):
    """The acceptance contract: a reader polling health.json as fast
    as it can while the sampler rewrites it every few ms NEVER sees a
    torn/partial file, and the heartbeat seq is non-decreasing."""
    tr = trace.Tracer(run="atomic")
    sampler = HealthSampler(tmp_path, 0.002,
                            tracer_fn=lambda: tr).start()
    seqs = []
    deadline = time.monotonic() + 0.5
    try:
        while time.monotonic() < deadline:
            try:
                text = (tmp_path / "health.json").read_text()
            except FileNotFoundError:
                continue
            snap = json.loads(text)     # JSONDecodeError == torn file
            seqs.append(snap["heartbeat"]["seq"])
    finally:
        sampler.stop()
    assert len(seqs) > 10
    assert seqs == sorted(seqs)
    assert seqs[-1] > seqs[0]           # the sampler actually ticked
    # no temp droppings left behind
    assert not list(tmp_path.glob(".health.json.*"))


# ---------------------------------------------------------------------------
# Prometheus exposition + endpoint
# ---------------------------------------------------------------------------

def make_golden_tracer():
    tr = trace.Tracer(run="golden")
    tr.counter("quarantined").inc(3)
    tr.counter("buckets_dispatched").inc(5)
    tr.gauge("inflight_depth").set(2)
    tr.gauge("runs_total").set(None)    # unset gauge must not render
    h = tr.histogram("bucket_cells")
    for v in (1.0, 3.0, 100.0):
        h.observe(v)
    return tr


def test_prometheus_exposition_golden_file():
    """The rendering is pinned byte-for-byte: counter/gauge TYPE
    lines, log2 magnitude buckets mapped to cumulative `_bucket`
    series closed by +Inf/_sum/_count, unset gauges skipped."""
    got = render_prometheus(make_golden_tracer())
    golden = (REPO / "tests" / "golden_metrics.prom").read_text()
    assert got == golden


def test_prometheus_counters_match_metrics_dict():
    tr = make_golden_tracer()
    page = render_prometheus(tr)
    for name, v in tr.metrics_dict()["counters"].items():
        assert f"jepsen_tpu_{name} {v}" in page
    # histogram invariants: +Inf bucket equals _count
    assert 'jepsen_tpu_bucket_cells_bucket{le="+Inf"} 3' in page
    assert "jepsen_tpu_bucket_cells_count 3" in page


def test_metrics_server_scrapes(monkeypatch):
    tr = make_golden_tracer()
    srv = MetricsServer(0, host="127.0.0.1", tracer_fn=lambda: tr)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            page = r.read().decode()
        assert page == render_prometheus(tr)
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            snap = json.loads(r.read().decode())
        assert snap["v"] == 1 and snap["run"] == "golden"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_metrics_server_gate(monkeypatch):
    # JEPSEN_TPU_METRICS_PORT unset / negative: off
    assert maybe_start_metrics_server() is None
    monkeypatch.setenv("JEPSEN_TPU_METRICS_PORT", "-1")
    assert maybe_start_metrics_server() is None
    # 0: ephemeral port for tests/parallel CI
    monkeypatch.setenv("JEPSEN_TPU_METRICS_PORT", "0")
    srv = maybe_start_metrics_server()
    try:
        assert srv is not None and srv.port > 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# The typed flight-recorder event API
# ---------------------------------------------------------------------------

def test_emit_is_noop_until_installed(tmp_path):
    assert obs.emit("sweep_start", checker="append") is False
    p = obs.install_events(tmp_path)
    assert p == tmp_path / p.name
    assert obs.emit("sweep_start", checker="append") is True
    obs.reset_events()
    assert obs.emit("sweep_end", exit_code=0) is False
    evs = obs.load_events(tmp_path)
    assert [e["event"] for e in evs] == ["sweep_start"]
    assert evs[0]["checker"] == "append"
    assert evs[0]["t_mono"] > 0 and evs[0]["t_wall"] > 0


def test_emit_rejects_undeclared_kind(tmp_path):
    obs.install_events(tmp_path)
    with pytest.raises(ValueError):
        obs.emit("sweep_strat")     # typo — the stream must not fork


def test_load_events_skips_torn_tail(tmp_path):
    obs.install_events(tmp_path)
    obs.emit("sweep_start", checker="wr")
    obs.emit("sweep_end", exit_code=0)
    p = obs.events.current_path()
    with open(p, "a") as f:
        f.write('{"event": "quarant')     # SIGKILL mid-append
    evs = obs.load_events(tmp_path)
    assert [e["event"] for e in evs] == ["sweep_start", "sweep_end"]


def test_events_rotation_default_off(tmp_path, monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_EVENTS_MAX_BYTES", raising=False)
    obs.install_events(tmp_path)
    for i in range(50):
        obs.emit("sweep_start", checker="append", runs=i)
    assert not (tmp_path / "events.jsonl.1").exists()
    assert len(obs.load_events(tmp_path)) == 50


def test_events_rotate_at_cap(tmp_path, monkeypatch):
    # the registry's declared `rotated` retention class made real:
    # the over-cap log is renamed aside atomically and the fresh log
    # opens with an events_rotated record naming it
    monkeypatch.setenv("JEPSEN_TPU_EVENTS_MAX_BYTES", "400")
    obs.install_events(tmp_path)
    for i in range(40):
        obs.emit("sweep_start", checker="append", runs=i)
    rotated = tmp_path / "events.jsonl.1"
    assert rotated.exists()
    # every rotated-aside line is complete (rename is atomic — no
    # torn records created by rotation itself); at this cap the log
    # rotates repeatedly, so the kept generation may itself start
    # with the previous rotation's mark
    old = obs.load_events(rotated)
    assert old and all(e["event"] in ("sweep_start", "events_rotated")
                       for e in old)
    live = obs.load_events(tmp_path)
    assert live[0]["event"] == "events_rotated"
    assert live[0]["rotated_to"] == "events.jsonl.1"
    assert live[0]["size"] >= 400
    # nothing lost across the rotation boundary: one generation kept
    # plus the live log covers the tail of the emits
    seen = [e["runs"] for e in old + live if e["event"] == "sweep_start"]
    assert seen == sorted(seen) and seen[-1] == 39
    # the live log stays under cap + one rotation's slack
    assert (tmp_path / "events.jsonl").stat().st_size < 400 + 400


def test_events_rotation_keeps_one_generation(tmp_path, monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_EVENTS_MAX_BYTES", "300")
    obs.install_events(tmp_path)
    for i in range(120):
        obs.emit("sweep_start", checker="append", runs=i)
    names = sorted(p.name for p in tmp_path.glob("events.jsonl*"))
    assert names == ["events.jsonl", "events.jsonl.1"]


def test_events_rotation_cross_process_claim(tmp_path, monkeypatch):
    # mesh shards share one store log: a concurrent rotator's live
    # lockfile must make this emitter SKIP rotation (append only) —
    # renaming with a stale size would destroy the kept generation
    monkeypatch.setenv("JEPSEN_TPU_EVENTS_MAX_BYTES", "10")
    obs.install_events(tmp_path)
    obs.emit("sweep_start", checker="append")       # now over cap
    lock = tmp_path / "events.jsonl.rotlock"
    lock.write_text("")                             # a live claimant
    obs.emit("sweep_end", exit_code=0)
    assert not (tmp_path / "events.jsonl.1").exists()
    assert [e["event"] for e in obs.load_events(tmp_path)] \
        == ["sweep_start", "sweep_end"]
    assert lock.exists()      # a LIVE lock is never broken
    # a stale lock (its holder crashed mid-rotation) is broken so the
    # NEXT emit can rotate again
    stale = obs.events._ROTLOCK_STALE_S + 5
    os.utime(lock, (time.time() - stale, time.time() - stale))
    obs.emit("sweep_end", exit_code=0)              # breaks the lock
    assert not lock.exists()
    obs.emit("sweep_end", exit_code=0)              # rotates
    assert (tmp_path / "events.jsonl.1").exists()
    live = obs.load_events(tmp_path)
    assert live[0]["event"] == "events_rotated"


def test_events_rotation_stale_break_restores_live_claim(tmp_path,
                                                         monkeypatch):
    # the break is rename-then-verify: if ANOTHER claimant replaced
    # the stale lock between our staleness stat and our rename, we
    # renamed a LIVE claim — it must be renamed straight back, not
    # deleted (deleting it would let two rotators run at once)
    from jepsen_tpu.obs import events as ev
    monkeypatch.setenv("JEPSEN_TPU_EVENTS_MAX_BYTES", "10")
    obs.install_events(tmp_path)
    obs.emit("sweep_start", checker="append")        # over cap
    lock = tmp_path / "events.jsonl.rotlock"
    lock.write_text("")
    stale = ev._ROTLOCK_STALE_S + 5
    os.utime(lock, (time.time() - stale, time.time() - stale))
    real_rename = os.rename
    fired = {"v": False}

    def racing_rename(src, dst):
        if Path(src) == lock and not fired["v"]:
            fired["v"] = True
            # between our stat and rename, another breaker removed
            # the stale lock and a fresh claimant took the path
            os.unlink(lock)
            lock.write_text("")
        return real_rename(src, dst)

    monkeypatch.setattr(os, "rename", racing_rename)
    obs.emit("sweep_end", exit_code=0)
    monkeypatch.setattr(os, "rename", real_rename)
    assert lock.exists()                 # the live claim came back
    assert not (tmp_path / "events.jsonl.1").exists()
    assert list(tmp_path.glob("events.jsonl.rotlock.*")) == []


def test_events_rotation_restat_under_claim(tmp_path, monkeypatch):
    # the clobber race, replayed deterministically: an emitter whose
    # pre-claim stat is stale (another process already rotated and
    # the live log is small again) must NOT rotate — the re-stat
    # under the lock catches it
    from jepsen_tpu.obs import events as ev
    monkeypatch.setenv("JEPSEN_TPU_EVENTS_MAX_BYTES", "100")
    obs.install_events(tmp_path)
    p = tmp_path / "events.jsonl"
    real_stat = Path.stat
    calls = {"n": 0}

    def racing_stat(self, *a, **kw):
        res = real_stat(self, *a, **kw)
        if self == p:
            calls["n"] += 1
            if calls["n"] == 1:
                # the pre-claim probe saw the PRE-ROTATION size; the
                # "other process" rotates right after it
                os.replace(p, tmp_path / "events.jsonl.1")
                p.write_text('{"event": "events_rotated"}\n')
        return res

    p.write_text('{"event": "sweep_start"}\n' * 8)   # over cap
    kept = (tmp_path / "events.jsonl.1")
    monkeypatch.setattr(Path, "stat", racing_stat)
    assert ev._maybe_rotate(p) is None               # re-stat saved it
    monkeypatch.setattr(Path, "stat", real_stat)
    # the concurrently-kept generation survived intact
    assert kept.read_text() == '{"event": "sweep_start"}\n' * 8
    assert not (tmp_path / "events.jsonl.rotlock").exists()


def test_fault_inject_sweep_records_every_quarantine(
        tmp_path, capsys, monkeypatch):
    """The acceptance case: a `JEPSEN_TPU_FAULT_INJECT kill:` sweep
    (kill degrades to encode faults on the serial path) completes with
    quarantines, and events.jsonl holds the full causal record — one
    `quarantine` event per quarantined run plus the sweep lifecycle —
    even though the sweep also wrote trace.json normally."""
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, dirs = synth_store(tmp_path, n=6)
    inj = supervisor._Injector("kill:0.4")
    expect_q = {d for d in dirs
                if inj.selects("kill", os.path.basename(str(d)))}
    assert expect_q and len(expect_q) < len(dirs)
    monkeypatch.setenv("JEPSEN_TPU_FAULT_INJECT", "kill:0.4")
    supervisor.reset_injection()
    rc = cli.analyze_store(store, checker="append")
    capsys.readouterr()
    assert rc == 2
    evs = obs.load_events(store.base)
    kinds = [e["event"] for e in evs]
    assert kinds[0] == "sweep_start" and "sweep_end" in kinds
    assert all(k in obs.EVENT_KINDS for k in kinds)
    q_events = [e for e in evs if e["event"] == "quarantine"]
    assert len(q_events) == len(expect_q)
    assert {e["run"] for e in q_events} == {str(d) for d in expect_q}
    for e in q_events:
        assert e["stage"] == "encode" and e["cause"]
    end = [e for e in evs if e["event"] == "sweep_end"][-1]
    assert end["exit_code"] == 2
    # the recorder is uninstalled after the sweep: later emits no-op
    assert obs.emit("sweep_start") is False


def test_sweep_lifecycle_and_resume_events(tmp_path, capsys,
                                           monkeypatch):
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, dirs = synth_store(tmp_path, n=2)
    assert cli.analyze_store(store, checker="append") == 0
    assert cli.analyze_store(store, checker="append", resume=True) == 0
    capsys.readouterr()
    evs = obs.load_events(store.base)
    kinds = [e["event"] for e in evs]
    assert kinds.count("sweep_start") == 2
    assert kinds.count("sweep_end") == 2
    resumes = [e for e in evs if e["event"] == "sweep_resume"]
    assert len(resumes) == 1
    assert resumes[0]["skipped"] == 2 and resumes[0]["pending"] == 0


def test_obs_off_by_default(tmp_path, capsys, monkeypatch):
    """With both gates unset a sweep writes NO health.json and starts
    no endpoint — the <1% overhead contract is 'the code never runs',
    not 'the code is fast'. The flight recorder alone is always on."""
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    store, _dirs = synth_store(tmp_path, n=2)
    assert cli.analyze_store(store, checker="append") == 0
    capsys.readouterr()
    assert not (store.base / "health.json").exists()
    kinds = {e["event"] for e in obs.load_events(store.base)}
    assert "metrics_serve" not in kinds and "health_sample" not in kinds
    assert {"sweep_start", "sweep_end"} <= kinds


def test_sweep_with_gates_produces_live_artifacts(tmp_path, capsys,
                                                  monkeypatch):
    """JEPSEN_TPU_HEALTH_INTERVAL_S + JEPSEN_TPU_METRICS_PORT=0 on a
    real sweep: mid-sweep scrape succeeds, final health.json records
    full progress, and the scraped counter names match metrics.json."""
    from jepsen_tpu import cli
    serial_ingest(monkeypatch)
    monkeypatch.setenv("JEPSEN_TPU_HEALTH_INTERVAL_S", "0.05")
    monkeypatch.setenv("JEPSEN_TPU_METRICS_PORT", "0")
    store, dirs = synth_store(tmp_path, n=3)
    scraped = {}

    def hook(server, sampler):
        assert server is not None and sampler is not None
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            scraped["metrics"] = r.read().decode()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            scraped["healthz"] = json.loads(r.read().decode())

    assert cli.analyze_store(store, checker="append",
                             obs_hook=hook) == 0
    capsys.readouterr()
    assert scraped["healthz"]["v"] == 1
    assert "jepsen_tpu_" in scraped["metrics"]
    health = json.loads((store.base / "health.json").read_text())
    assert health["progress"]["runs_total"] == 3
    assert health["progress"]["runs_verdicted"] == 3
    assert health["progress"]["buckets_dispatched"] == \
        health["progress"]["buckets_resolved"]
    final = json.loads((store.base / "metrics.json").read_text())
    assert final["counters"]["runs_verdicted"] == 3
    assert "jepsen_tpu_shm_stale_reclaimed " in scraped["metrics"]


# ---------------------------------------------------------------------------
# Crash-atomic trace/metrics persistence (satellite)
# ---------------------------------------------------------------------------

def test_trace_export_atomic_no_tmp_droppings(tmp_path):
    tr = trace.Tracer(run="atomic")
    with tr.span("s"):
        pass
    for _ in range(2):      # overwrite path too
        p = tr.export(tmp_path / "trace.json")
        m = tr.export_metrics(tmp_path / "metrics.json")
    assert json.loads(p.read_text())["traceEvents"]
    assert "counters" in json.loads(m.read_text())
    assert not list(tmp_path.glob(".trace.json.*"))
    assert not list(tmp_path.glob(".metrics.json.*"))


def test_trace_export_failure_leaves_previous_artifact(tmp_path,
                                                       monkeypatch):
    """A crash mid-flush must leave the PREVIOUS complete file: the
    write goes to a temp name and only an intact temp is renamed in."""
    tr = trace.Tracer(run="crash")
    p = tr.export_metrics(tmp_path / "metrics.json")
    before = p.read_text()
    real_replace = os.replace

    def boom(src, dst):
        raise OSError("simulated crash at rename")

    monkeypatch.setattr(os, "replace", boom)
    tr.counter("quarantined").inc()
    with pytest.raises(OSError):
        tr.export_metrics(tmp_path / "metrics.json")
    monkeypatch.setattr(os, "replace", real_replace)
    assert p.read_text() == before      # old artifact intact
    assert not list(tmp_path.glob(".metrics.json.*"))
