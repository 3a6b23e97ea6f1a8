"""The env-gate registry (jepsen_tpu.gates).

Every `JEPSEN_TPU_*` gate is declared exactly once in the registry and
read through its typed accessors; this suite pins the parse semantics
(bool default-on vs default-off, malformed int/float fallback, choice
validation), the writer counterparts (export/unset), and the
registry↔README↔tests drift contracts the linter enforces
(JT-GATE-003/004). The literal name list below is the drift tripwire:
adding a gate without touching this file fails here AND in lint.
"""

from __future__ import annotations

import re
from pathlib import Path

import pytest

from jepsen_tpu import gates

REPO = Path(__file__).resolve().parents[1]

#: Every registered gate, by name — the literal drift list. A new gate
#: must be added here (and thereby gets test "coverage" in the
#: JT-GATE-004 sense) plus a behavior test below if it has one.
ALL_GATES = [
    "JEPSEN_TPU_TRACE",
    "JEPSEN_TPU_TRACE_MAX_EVENTS",
    "JEPSEN_TPU_WORKER_TRACE",
    "JEPSEN_TPU_REPORT",
    "JEPSEN_TPU_JAX_PROFILE",
    "JEPSEN_TPU_HEALTH_INTERVAL_S",
    "JEPSEN_TPU_METRICS_PORT",
    "JEPSEN_TPU_EVENTS_MAX_BYTES",
    "JEPSEN_TPU_COSTDB",
    "JEPSEN_TPU_RESIDENCY_INTERVAL_S",
    "JEPSEN_TPU_KERNEL_STATS",
    "JEPSEN_TPU_KERNEL_STATS_SAMPLE",
    "JEPSEN_TPU_BACKEND",
    "JEPSEN_TPU_PLATFORM",
    "JEPSEN_TPU_FUSED_CLASSIFY",
    "JEPSEN_TPU_FRONTIER",
    "JEPSEN_TPU_NATIVE_INGEST",
    "JEPSEN_TPU_NATIVE_SPLIT",
    "JEPSEN_TPU_NO_NATIVE",
    "JEPSEN_TPU_NATIVE_LIB_DIR",
    "JEPSEN_TPU_SHM_INGEST",
    "JEPSEN_TPU_PIPELINE",
    "JEPSEN_TPU_ENCODE_CACHE",
    "JEPSEN_TPU_ENCODE_CACHE_WRITE",
    "JEPSEN_TPU_PACK_THREAD",
    "JEPSEN_TPU_SIDECAR_V2",
    "JEPSEN_TPU_DONATE_BUFFERS",
    "JEPSEN_TPU_AOT_CACHE",
    "JEPSEN_TPU_MESH",
    "JEPSEN_TPU_MESH_SHARD",
    "JEPSEN_TPU_MESH_SHARDS",
    "JEPSEN_TPU_MESH_WAIT_S",
    "JEPSEN_TPU_SERVE_SOCKET",
    "JEPSEN_TPU_SERVE_PORT",
    "JEPSEN_TPU_SERVE_MAX_QUEUE",
    "JEPSEN_TPU_SERVE_WEIGHTS",
    "JEPSEN_TPU_SERVE_DRAIN_S",
    "JEPSEN_TPU_SERVE_RETRY_S",
    "JEPSEN_TPU_FLEET_HEARTBEAT_S",
    "JEPSEN_TPU_FLEET_FAILOVER_S",
    "JEPSEN_TPU_FLEET_SPILL_DEPTH",
    "JEPSEN_TPU_PLANNER",
    "JEPSEN_TPU_PLANNER_PATH",
    "JEPSEN_TPU_STRICT",
    "JEPSEN_TPU_DISPATCH_TIMEOUT_S",
    "JEPSEN_TPU_FAULT_INJECT",
    "JEPSEN_TPU_EC",
]


def test_registry_drift_list():
    assert sorted(gates.GATES) == sorted(ALL_GATES)
    assert len(ALL_GATES) == len(set(ALL_GATES))


def test_every_gate_well_formed():
    for name, g in gates.GATES.items():
        assert g.name == name and name.startswith(gates.PREFIX)
        assert g.kind in gates.KINDS
        assert g.doc.strip(), f"{name} needs a doc line"
        # the declared default must round-trip through the parser
        assert g.parse(None) == g.default


# -- parse semantics --------------------------------------------------------

def test_bool_default_on_parse():
    g = gates.gate("JEPSEN_TPU_TRACE")
    assert g.parse(None) is True
    assert g.parse("0") is False
    assert g.parse("1") is True
    # historical convention: anything but "0" is on
    assert g.parse("yes") is True
    assert g.parse("") is True


def test_bool_default_off_parse():
    g = gates.gate("JEPSEN_TPU_STRICT")
    assert g.parse(None) is False
    assert g.parse("") is False
    assert g.parse("0") is False
    assert g.parse("1") is True
    # widened vs the old `== "1"` reads: spelled-out truthy works
    assert g.parse("yes") is True


def test_int_malformed_falls_back(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_FRONTIER", "not-a-number")
    assert gates.get("JEPSEN_TPU_FRONTIER") == 512
    monkeypatch.setenv("JEPSEN_TPU_FRONTIER", "1024")
    assert gates.get("JEPSEN_TPU_FRONTIER") == 1024


def test_float_malformed_falls_back(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_MESH_WAIT_S", "soon")
    assert gates.get("JEPSEN_TPU_MESH_WAIT_S") == 600.0
    monkeypatch.setenv("JEPSEN_TPU_MESH_WAIT_S", "7.5")
    assert gates.get("JEPSEN_TPU_MESH_WAIT_S") == 7.5


def test_str_values_are_stripped(monkeypatch):
    # a trailing space from a shell export or CI YAML must not turn a
    # valid value into an unknown one (the old read .strip()ed too)
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", " cpu ")
    assert gates.get("JEPSEN_TPU_BACKEND") == "cpu"
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "   ")
    assert gates.get("JEPSEN_TPU_BACKEND") is None


def test_unregistered_name_raises():
    with pytest.raises(KeyError):
        gates.get("JEPSEN_TPU_NOT_A_GATE")
    with pytest.raises(KeyError):
        gates.get_raw("JEPSEN_TPU_NOT_A_GATE")
    with pytest.raises(KeyError):
        gates.export("JEPSEN_TPU_NOT_A_GATE", 1)
    with pytest.raises(KeyError):
        gates.unset("JEPSEN_TPU_NOT_A_GATE")


# -- writer counterparts ----------------------------------------------------

def test_export_unset_roundtrip(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_BACKEND", raising=False)
    assert not gates.is_set("JEPSEN_TPU_BACKEND")
    gates.export("JEPSEN_TPU_BACKEND", "cpu")
    assert gates.is_set("JEPSEN_TPU_BACKEND")
    assert gates.get_raw("JEPSEN_TPU_BACKEND") == "cpu"
    assert gates.get("JEPSEN_TPU_BACKEND") == "cpu"
    gates.unset("JEPSEN_TPU_BACKEND")
    assert gates.get("JEPSEN_TPU_BACKEND") is None


def test_export_bool_canonical(monkeypatch):
    monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)
    gates.export("JEPSEN_TPU_TRACE", False)
    assert gates.get_raw("JEPSEN_TPU_TRACE") == "0"
    assert gates.get("JEPSEN_TPU_TRACE") is False
    gates.export("JEPSEN_TPU_TRACE", True)
    assert gates.get_raw("JEPSEN_TPU_TRACE") == "1"
    monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)


def test_marker_is_not_an_env_var(monkeypatch):
    # JEPSEN_TPU_EC is a protocol constant sharing the namespace: the
    # env can never override it, and export() refuses to write it
    monkeypatch.setenv("JEPSEN_TPU_EC", "hijacked")
    assert gates.get("JEPSEN_TPU_EC") == "__JEPSEN_TPU_EC:"
    with pytest.raises(AssertionError):
        gates.export("JEPSEN_TPU_EC", "x")


# -- gates wired into their consumers ---------------------------------------

def test_ec_marker_is_the_ssh_marker():
    from jepsen_tpu import control
    assert control.SSHRemote._EC_MARK == gates.get("JEPSEN_TPU_EC")
    assert control.SSHRemote._EC_MARK.startswith("__JEPSEN_TPU_EC")


def test_platform_gate_selects_devices(monkeypatch):
    from jepsen_tpu import devices
    monkeypatch.setenv("JEPSEN_TPU_PLATFORM", "cpu")
    assert {d.platform for d in devices.default_devices()} == {"cpu"}
    monkeypatch.delenv("JEPSEN_TPU_PLATFORM")
    import jax
    assert devices.default_devices() == jax.devices()


def test_trace_max_events_gate(monkeypatch):
    from jepsen_tpu import trace
    monkeypatch.setenv("JEPSEN_TPU_TRACE_MAX_EVENTS", "5")
    assert trace.Tracer()._max_events == 5
    monkeypatch.setenv("JEPSEN_TPU_TRACE_MAX_EVENTS", "plenty")
    assert trace.Tracer()._max_events == 200_000   # malformed -> default


def test_jax_profile_gate(monkeypatch):
    from jepsen_tpu import trace
    monkeypatch.delenv("JEPSEN_TPU_JAX_PROFILE", raising=False)
    assert trace.jax_profile_enabled() is False
    monkeypatch.setenv("JEPSEN_TPU_JAX_PROFILE", "1")
    assert trace.jax_profile_enabled() is True
    monkeypatch.setenv("JEPSEN_TPU_JAX_PROFILE", "0")
    assert trace.jax_profile_enabled() is False


def test_no_native_gate(monkeypatch):
    from jepsen_tpu import native_lib
    monkeypatch.setenv("JEPSEN_TPU_NO_NATIVE", "1")
    assert native_lib._load_so(Path("x.cc"), Path("x.so")) is None
    # the old truthy-string parse read NO_NATIVE=0 as *disable*;
    # the registry parse fixes that (see MIGRATING.md)
    monkeypatch.setenv("JEPSEN_TPU_NO_NATIVE", "0")
    assert gates.get("JEPSEN_TPU_NO_NATIVE") is False


def test_native_lib_dir_gate(tmp_path, monkeypatch):
    # an explicit lib dir must load exactly that lib or degrade to
    # Python — never silently substitute the production build
    from jepsen_tpu import native_lib
    monkeypatch.setenv("JEPSEN_TPU_NATIVE_LIB_DIR", str(tmp_path))
    monkeypatch.setattr(native_lib, "_cached", {})
    assert native_lib._cached_lib(
        "hist_encode.cc", "libjepsen_histenc.so", lambda L: True) is None


def test_no_native_wins_over_lib_dir(tmp_path, monkeypatch):
    # the kill switch disables EVERY ctypes load, pinned lib dir
    # included: no CDLL attempt may happen at all
    from jepsen_tpu import native_lib
    monkeypatch.setenv("JEPSEN_TPU_NO_NATIVE", "1")
    monkeypatch.setenv("JEPSEN_TPU_NATIVE_LIB_DIR", str(tmp_path))
    monkeypatch.setattr(native_lib, "_cached", {})
    monkeypatch.setattr(
        native_lib.ctypes, "CDLL",
        lambda *a, **k: pytest.fail("CDLL called despite NO_NATIVE"))
    assert native_lib._cached_lib(
        "hist_encode.cc", "libjepsen_histenc.so", lambda L: True) is None


def test_serve_gates(monkeypatch):
    # the verdict daemon's knobs: socket path default (None -> the
    # store-derived serve.sock), queue-depth cap, weight-spec parse
    from jepsen_tpu.serve import scheduler
    monkeypatch.delenv("JEPSEN_TPU_SERVE_SOCKET", raising=False)
    assert gates.get("JEPSEN_TPU_SERVE_SOCKET") is None
    monkeypatch.delenv("JEPSEN_TPU_SERVE_MAX_QUEUE", raising=False)
    assert gates.get("JEPSEN_TPU_SERVE_MAX_QUEUE") == 256
    monkeypatch.setenv("JEPSEN_TPU_SERVE_MAX_QUEUE", "not-a-depth")
    assert gates.get("JEPSEN_TPU_SERVE_MAX_QUEUE") == 256
    monkeypatch.setenv("JEPSEN_TPU_SERVE_WEIGHTS",
                       "fleetA=3, fleetB=1, junk, neg=-2")
    # malformed/negative entries fall back to weight 1, never crash
    assert scheduler.parse_weights() == {"fleetA": 3.0, "fleetB": 1.0}
    monkeypatch.delenv("JEPSEN_TPU_SERVE_WEIGHTS", raising=False)
    assert scheduler.parse_weights() == {}
    monkeypatch.delenv("JEPSEN_TPU_SERVE_DRAIN_S", raising=False)
    assert gates.get("JEPSEN_TPU_SERVE_DRAIN_S") == 30.0


def test_serve_retry_gate(monkeypatch):
    # the client's no-progress budget: default 60 s, floored at 0
    # (`0` = fail on the first retryable condition, never negative)
    from jepsen_tpu.serve import client
    monkeypatch.delenv("JEPSEN_TPU_SERVE_RETRY_S", raising=False)
    assert client.retry_budget_s() == 60.0
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "2.5")
    assert client.retry_budget_s() == 2.5
    monkeypatch.setenv("JEPSEN_TPU_SERVE_RETRY_S", "-3")
    assert client.retry_budget_s() == 0.0


def test_fleet_gates(monkeypatch):
    # the fleet's knobs, each floored so a zero/negative setting can't
    # turn the heartbeat into a busy-loop or disable failover outright
    from jepsen_tpu.serve import fleet
    for var in ("JEPSEN_TPU_FLEET_HEARTBEAT_S",
                "JEPSEN_TPU_FLEET_FAILOVER_S",
                "JEPSEN_TPU_FLEET_SPILL_DEPTH"):
        monkeypatch.delenv(var, raising=False)
    assert fleet.heartbeat_s() == 1.0
    assert fleet.failover_s() == 5.0
    assert fleet.spill_depth() == 32
    monkeypatch.setenv("JEPSEN_TPU_FLEET_HEARTBEAT_S", "0.001")
    assert fleet.heartbeat_s() == 0.05
    monkeypatch.setenv("JEPSEN_TPU_FLEET_FAILOVER_S", "0")
    assert fleet.failover_s() == 0.1
    monkeypatch.setenv("JEPSEN_TPU_FLEET_SPILL_DEPTH", "0")
    assert fleet.spill_depth() == 1
    monkeypatch.setenv("JEPSEN_TPU_FLEET_SPILL_DEPTH", "7")
    assert fleet.spill_depth() == 7


def test_encode_cache_write_gate(monkeypatch):
    from jepsen_tpu import store
    monkeypatch.delenv("JEPSEN_TPU_ENCODE_CACHE_WRITE", raising=False)
    assert store.encode_cache_write_enabled() is True
    monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE_WRITE", "0")
    assert store.encode_cache_write_enabled() is False


# -- render/drift contracts -------------------------------------------------

def test_render_table_covers_every_gate():
    table = gates.render_env_table()
    for name in gates.GATES:
        assert f"`{name}`" in table


def test_render_table_escapes_pipes():
    # markdown splits cells on every unescaped pipe, code spans
    # included — a doc like `tpu`|`cpu` must render as one cell
    table = gates.render_env_table()
    assert "`tpu`\\|`cpu`\\|`race`" in table
    for row in table.splitlines()[2:]:
        cells = [c for c in re.split(r"(?<!\\)\|", row) if c.strip()]
        assert len(cells) == 3, row


def test_readme_block_matches_registry():
    # the test-suite twin of lint rule JT-GATE-003
    text = (REPO / "README.md").read_text(encoding="utf-8")
    start = text.index(gates.TABLE_BEGIN)
    end = text.index(gates.TABLE_END) + len(gates.TABLE_END)
    assert text[start:end].strip() == gates.render_env_block().strip()
