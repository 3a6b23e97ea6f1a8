"""The serve fleet (`jepsen-tpu fleet`): router, failover, fencing.

Tier-1 coverage of the fleet invariant — a tenant never loses and
never double-receives a verdict across a member death:

  * store path helpers + the epoch-fence predicate (unit);
  * an in-process attach-mode fleet: affine routing, a simulated
    member death (clean stop retires the beacon), journal replay on
    the successor — byte-identical, `replays` observed by the client;
  * spill under a pinned-low JEPSEN_TPU_FLEET_SPILL_DEPTH: two
    weighted tenants stream through both members with zero
    lost/duplicated journal lines;
  * the subprocess SIGKILL-mid-stream contract: kill the affine
    member with checks in flight, the successor replays/re-checks,
    every verdict lands exactly once;
  * the zombie fence: a SIGSTOPped member is convicted on beacon
    staleness (it still accept()s, so only staleness can convict),
    fenced out of the epoch, and on SIGCONT drops its stale folds
    unjournaled — raw journal line counts prove no double-append;
  * the client's bounded-retry contract: ServeUnavailable (terminal)
    once JEPSEN_TPU_SERVE_RETRY_S passes without progress, on both
    the connect and the reconnect path.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import sys
import threading
import time
from pathlib import Path

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from jepsen_tpu import obs, trace  # noqa: E402
from jepsen_tpu.serve import protocol  # noqa: E402
from jepsen_tpu.serve.client import (ServeClient, ServeError,  # noqa: E402
                                     ServeUnavailable)
from jepsen_tpu.serve.daemon import VerdictDaemon  # noqa: E402
from jepsen_tpu.serve.fleet import FleetRouter  # noqa: E402
from jepsen_tpu.checker.elle.synth import write_synth_store  # noqa: E402
from jepsen_tpu.store import (Store, VerdictJournal,  # noqa: E402
                              fleet_daemon_socket_path,
                              fleet_epoch_path, fleet_member_path,
                              fleet_reassign_path, fleet_socket_path,
                              shard_of, tenant_journal_path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_store(root: Path, b: int = 4, t: int = 64, k: int = 8,
               bad_every: int = 2) -> tuple[Path, list[Path]]:
    store = root / "store"
    (store / "synth").mkdir(parents=True)
    write_synth_store(store / "synth", b, t, k, bad_every)
    return store, sorted(Store(store).iter_run_dirs())


@pytest.fixture
def keep_tracer():
    prev = trace.get_current()
    yield
    trace.set_current(prev)
    obs.reset_events()


@pytest.fixture
def fleet_env(monkeypatch):
    """Fast heartbeats for the in-test routers, and no port/health
    contention with whatever else the test box runs."""
    monkeypatch.setenv("JEPSEN_TPU_FLEET_HEARTBEAT_S", "0.2")
    monkeypatch.setenv("JEPSEN_TPU_FLEET_FAILOVER_S", "1.0")
    monkeypatch.setenv("JEPSEN_TPU_HEALTH_INTERVAL_S", "0")
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "60")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JEPSEN_TPU_PLATFORM", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    for var in ("JEPSEN_TPU_METRICS_PORT", "JEPSEN_TPU_MESH",
                "JEPSEN_TPU_MESH_SHARD", "JEPSEN_TPU_MESH_SHARDS",
                "JEPSEN_TPU_SERVE_SOCKET", "JEPSEN_TPU_SERVE_PORT"):
        monkeypatch.delenv(var, raising=False)


def _canon(v) -> str:
    return json.dumps(v, sort_keys=True)


def _raw_line_count(p: Path) -> int:
    if not p.exists():
        return 0
    return sum(1 for ln in p.read_text().splitlines() if ln.strip())


def _wait(pred, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# units: path helpers + the epoch fence
# ---------------------------------------------------------------------------

def test_fleet_store_helpers(tmp_path):
    assert fleet_socket_path(tmp_path).name == "fleet.sock"
    assert fleet_daemon_socket_path(tmp_path, 2).name == "fleet-d2.sock"
    assert fleet_member_path(tmp_path, 0).name == "fleet-d0.json"
    assert fleet_epoch_path(tmp_path).name == "fleet-epoch.json"
    assert fleet_reassign_path(tmp_path).name == "fleet-reassign.jsonl"


def test_epoch_fence_predicate(tmp_path):
    store, _dirs = make_store(tmp_path)
    d = VerdictDaemon(Store(store), fleet_instance=1, fleet_epoch=1)
    # no marker yet: not fenced (a lone member with a slow router)
    assert d._fenced() is False
    marker = fleet_epoch_path(store)
    marker.write_text(json.dumps(
        {"epoch": 1, "members": {"0": {"status": "live"},
                                 "1": {"status": "live"}}}))
    assert d._fenced() is False
    time.sleep(0.02)   # distinct mtime so the stat-cache re-parses
    marker.write_text(json.dumps(
        {"epoch": 2, "members": {"0": {"status": "live"},
                                 "1": {"status": "dead"}}}))
    assert d._fenced() is True
    # a standalone (non-fleet) daemon never consults the marker
    d2 = VerdictDaemon(Store(store))
    assert d2._fenced() is False


def test_epoch_fence_corrupt_and_alien_marker(tmp_path):
    """A mangled or version-skewed fleet-epoch.json must degrade to
    "not fenced" — never crash the fold loop mid-verdict — and a torn
    read must not poison the stat cache for the clean rewrite."""
    store, _dirs = make_store(tmp_path)
    d = VerdictDaemon(Store(store), fleet_instance=1, fleet_epoch=1)
    marker = fleet_epoch_path(store)

    # torn mid-replace marker: parse failure reads as unfenced, and the
    # stat key is NOT cached, so the subsequent clean rewrite (same
    # content prefix, new mtime) is re-parsed and honored
    marker.write_text('{"epoch": 2, "members": {"1": {"status": "de')
    assert d._fenced() is False
    time.sleep(0.02)
    marker.write_text(json.dumps(
        {"epoch": 2, "members": {"1": {"status": "dead"}}}))
    assert d._fenced() is True

    # alien top-level shape (a JSON list) degrades safely
    time.sleep(0.02)
    marker.write_text(json.dumps([1, 2, 3]))
    assert d._fenced() is False

    # members as a list (version-skewed writer): no crash, not fenced
    time.sleep(0.02)
    marker.write_text(json.dumps({"epoch": 3, "members": ["1"]}))
    assert d._fenced() is False

    # a member entry as a bare string: no crash, not fenced
    time.sleep(0.02)
    marker.write_text(json.dumps({"epoch": 4,
                                  "members": {"1": "dead"}}))
    assert d._fenced() is False

    # recovery: a clean marker after the alien ones still fences
    time.sleep(0.02)
    marker.write_text(json.dumps(
        {"epoch": 5, "members": {"1": {"status": "dead"}}}))
    assert d._fenced() is True


# ---------------------------------------------------------------------------
# in-process attach-mode fleet: routing, simulated death, replay, spill
# ---------------------------------------------------------------------------

def _attach_fleet(store: Path, n: int = 2):
    # stonith=False is mandatory in attach mode here: the members live
    # IN this process (their beacons carry our pid), so a STONITH on a
    # convicted member would SIGKILL the test run itself
    daemons = [VerdictDaemon(Store(store), fleet_instance=k,
                             fleet_epoch=1).start()
               for k in range(n)]
    router = FleetRouter(Store(store), daemons=n, spawn=False,
                         stonith=False)
    for k in range(n):
        router.attach_member(k, fleet_daemon_socket_path(store, k))
    router.start()
    return router, daemons


def test_attach_failover_replays_journal(tmp_path, fleet_env,
                                         keep_tracer):
    store, dirs = make_store(tmp_path)
    router, daemons = _attach_fleet(store)
    tenant = "tA"
    affine = shard_of(tenant, 2)
    try:
        c = ServeClient(socket_path=fleet_socket_path(store),
                        tenant=tenant, timeout=120)
        c.connect()
        for d in dirs:
            c.check_dir(d)
        first = dict(c.collect(timeout=240, reconnect=True))
        assert len(first) == len(dirs)
        # simulated member death: a clean stop retires the beacon,
        # which the monitor treats as gone (same path as a crash)
        daemons[affine].stop()
        _wait(lambda: router._member(affine).status == "dead",
              15.0, "router to convict the stopped member")
        assert router._epoch == 2
        # resubmit everything: the SUCCESSOR must answer from the
        # tenant's journal, byte-identical, without re-checking
        for d in dirs:
            c.check_dir(d)
        again = c.collect(timeout=240, reconnect=True)
        assert c.replays >= len(dirs)
        assert {r: _canon(v) for r, v in again.items()} \
            == {r: _canon(v) for r, v in first.items()}
        c.close()
        # exactly one journal line per id, deaths notwithstanding
        p = tenant_journal_path(store, tenant)
        assert set(VerdictJournal.load(p)) \
            == {(str(d), "append") for d in dirs}
        assert _raw_line_count(p) == len(dirs)
        # the fence marker records the conviction durably
        marker = json.loads(fleet_epoch_path(store).read_text())
        assert marker["epoch"] == 2
        assert marker["members"][str(affine)]["status"] == "dead"
    finally:
        router.stop()
        for d in daemons:
            try:
                d.stop()
            except Exception:
                pass


def test_spill_keeps_tenants_whole(tmp_path, fleet_env, keep_tracer,
                                   monkeypatch):
    # a spill-happy gate: anything queued on the affine member sends
    # the next check to the least-loaded — both members see work, and
    # the per-tenant journals still hold exactly each tenant's ids
    monkeypatch.setenv("JEPSEN_TPU_FLEET_SPILL_DEPTH", "1")
    store, dirs = make_store(tmp_path, b=6, bad_every=3)
    router, daemons = _attach_fleet(store)
    tenants = {"wA": dirs[:3], "wB": dirs[3:]}
    try:
        clients = {}
        for name, share in tenants.items():
            c = ServeClient(socket_path=fleet_socket_path(store),
                            tenant=name, timeout=120,
                            weight=2.0 if name == "wA" else 1.0)
            c.connect()
            clients[name] = c
            for d in share:
                c.check_dir(d)
        for name, share in tenants.items():
            got = clients[name].collect(timeout=240, reconnect=True)
            assert len(got) == len(share)
            clients[name].close()
        tr = trace.get_current()
        assert tr.counter("fleet_spills").value > 0
        for name, share in tenants.items():
            p = tenant_journal_path(store, name)
            assert set(VerdictJournal.load(p)) \
                == {(str(d), "append") for d in share}
            assert _raw_line_count(p) == len(share)
    finally:
        router.stop()
        for d in daemons:
            try:
                d.stop()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# subprocess fleets: SIGKILL mid-stream, the zombie fence
# ---------------------------------------------------------------------------

def test_sigkill_midstream_failover_no_loss_no_dup(tmp_path,
                                                   fleet_env,
                                                   keep_tracer):
    store, dirs = make_store(tmp_path)
    router = FleetRouter(Store(store), daemons=2,
                         start_timeout_s=180.0)
    tenant = "tK"
    try:
        router.start()
        c = ServeClient(socket_path=fleet_socket_path(store),
                        tenant=tenant, timeout=180)
        c.connect(retry=True)
        for d in dirs:
            c.check_dir(d)
        victim = router._affine(tenant, router._live_members())
        os.kill(victim.current_pid(), signal.SIGKILL)
        got = c.collect(timeout=300, reconnect=True)
        c.close()
        assert len(got) == len(dirs)
        _wait(lambda: router._member(victim.instance).status == "dead",
              15.0, "router to convict the killed member")
        assert router._epoch == 2
        p = tenant_journal_path(store, tenant)
        assert set(VerdictJournal.load(p)) \
            == {(str(d), "append") for d in dirs}
        assert _raw_line_count(p) == len(dirs)
    finally:
        router.stop()


def test_zombie_fenced_after_sigstop_resurrection(tmp_path, fleet_env,
                                                  keep_tracer):
    # stonith off: the test owns the zombie's life so it can PROVE the
    # fence (with stonith the zombie would just be killed)
    store, dirs = make_store(tmp_path)
    router = FleetRouter(Store(store), daemons=2, stonith=False,
                         start_timeout_s=180.0)
    tenant = "tZ"
    try:
        router.start()
        c = ServeClient(socket_path=fleet_socket_path(store),
                        tenant=tenant, timeout=180)
        c.connect(retry=True)
        victim = router._affine(tenant, router._live_members())
        pid = victim.current_pid()
        # stop the member BEFORE submitting: every check lands in its
        # kernel buffer unprocessed, so the resurrected zombie has a
        # full set of stale folds to (not) journal
        os.kill(pid, signal.SIGSTOP)
        for d in dirs:
            c.check_dir(d)
        got = c.collect(timeout=300, reconnect=True)
        c.close()
        assert len(got) == len(dirs)   # the successor answered
        _wait(lambda: router._member(victim.instance).status == "dead",
              15.0, "staleness conviction of the SIGSTOPped member")
        # resurrect: the zombie folds its buffered checks, hits the
        # epoch fence between compute and journal, drops and drains
        os.kill(pid, signal.SIGCONT)
        proc = router._member(victim.instance).proc
        _wait(lambda: proc.poll() is not None, 120.0,
              "the fenced zombie to drain itself")
        p = tenant_journal_path(store, tenant)
        assert set(VerdictJournal.load(p)) \
            == {(str(d), "append") for d in dirs}
        assert _raw_line_count(p) == len(dirs)   # no double-append
        kinds = {e.get("event") for e in obs.load_events(store)}
        assert "fleet_fence" in kinds
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# the client's bounded-retry contract
# ---------------------------------------------------------------------------

def test_connect_retry_is_bounded(tmp_path, monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "0.3")
    c = ServeClient(socket_path=tmp_path / "nope.sock", timeout=2)
    t0 = time.monotonic()
    with pytest.raises(ServeUnavailable):
        c.connect(retry=True)
    assert time.monotonic() - t0 < 10.0


def _one_shot_server(sock_path: Path):
    """Accept ONE connection, answer the hello, then slam everything
    shut — a daemon that dies right after the welcome."""
    ls = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    ls.bind(str(sock_path))
    ls.listen(1)

    def run():
        conn, _ = ls.accept()
        hello = protocol.recv_frame(conn)
        assert hello and hello.get("op") == "hello"
        protocol.send_frame(conn, {"op": "welcome", "v": 1})
        # give the client a beat to submit, then die hard
        time.sleep(0.2)
        conn.close()
        ls.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def test_collect_reconnect_budget_is_terminal(tmp_path, monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "0.4")
    sock = tmp_path / "one-shot.sock"
    _one_shot_server(sock)
    c = ServeClient(socket_path=sock, tenant="t", timeout=5)
    c.connect()
    c.check_history([], rid="h1")
    t0 = time.monotonic()
    with pytest.raises(ServeUnavailable):
        c.collect(timeout=30, reconnect=True)
    assert time.monotonic() - t0 < 15.0


def test_collect_without_reconnect_raises_plain_error(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "0.4")
    sock = tmp_path / "one-shot2.sock"
    _one_shot_server(sock)
    c = ServeClient(socket_path=sock, tenant="t", timeout=5)
    c.connect()
    c.check_history([], rid="h1")
    with pytest.raises(ServeError, match="closed the connection"):
        c.collect(timeout=30)


# ---------------------------------------------------------------------------
# One process per chip: device-backed members never outnumber the chips
# ---------------------------------------------------------------------------

def test_spawn_refuses_more_device_members_than_chips(tmp_path,
                                                      monkeypatch):
    from jepsen_tpu import devices
    from jepsen_tpu.serve.fleet import FleetRouter
    from jepsen_tpu.store import Store
    monkeypatch.setattr(devices, "host_chip_count", lambda: 0)
    spawned = []
    monkeypatch.setattr("subprocess.Popen",
                        lambda *a, **kw: spawned.append(a))
    router = FleetRouter(Store(tmp_path / "store"), daemons=1,
                         member_env={0: {"JAX_PLATFORMS": "tpu",
                                         "JEPSEN_TPU_PLATFORM": ""}})
    with pytest.raises(RuntimeError, match="0 chip"):
        router._spawn_member(0)
    assert not spawned


def test_device_backed_reads_the_platform_pin():
    from jepsen_tpu.serve.fleet import _device_backed
    assert _device_backed({}) is True
    assert _device_backed({"JAX_PLATFORMS": "tpu"}) is True
    assert _device_backed({"JAX_PLATFORMS": "cpu"}) is False
    assert _device_backed({"JEPSEN_TPU_PLATFORM": "cpu",
                           "JAX_PLATFORMS": "tpu"}) is False


def test_host_chip_count_reads_pci_accelerators(tmp_path):
    from jepsen_tpu import devices
    # two v5e chips as a v5litepod host lists them, a gVNIC (same
    # vendor, network class) and another vendor's accelerator
    for name, vendor, cls in (("0000:00:08.0", "0x1ae0", "0xff0000"),
                              ("0000:00:09.0", "0x1ae0", "0xff0000"),
                              ("0000:00:06.0", "0x1ae0", "0x020000"),
                              ("0000:00:07.0", "0x8086", "0x120000")):
        d = tmp_path / name
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "class").write_text(cls + "\n")
    assert devices.host_chip_count(str(tmp_path)) == 2
