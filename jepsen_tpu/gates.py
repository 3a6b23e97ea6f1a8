"""jepsen_tpu.gates — THE registry of `JEPSEN_TPU_*` environment gates.

Every env var this package reads is declared here exactly once — name,
type, default, one doc line — and read only through the typed
accessors below. The rest of the package holds no raw
`os.environ`/`os.getenv` of a `JEPSEN_TPU_*` name: the self-hosted
linter (``python -m jepsen_tpu.cli lint``, rule JT-GATE-001) fails the
build on one, and rule JT-GATE-003/004 fail it when a registered gate
is missing from the README env-gate table (rendered from this registry
by `render_env_table`) or from test coverage. That closes the drift
loop that produced 21 ad-hoc gate reads with three different truthy
parses: a gate can no longer exist without a declaration, a doc row
and a test.

Parse semantics are normalized to two shapes (recorded per gate by
`kind` + `default`):

  * bool, default on  — unset or anything but ``"0"`` is True
    (the historical ``!= "0"`` convention of the default-on gates);
  * bool, default off — only a set, non-empty, non-``"0"`` value is
    True. This widens the old ``== "1"`` gates (STRICT, JAX_PROFILE,
    PIPELINE) to accept ``yes``/``true`` spellings, and FIXES
    ``JEPSEN_TPU_NO_NATIVE=0``, which the old truthy-string parse
    read as *disable native* (see MIGRATING.md);
  * int/float — parsed, falling back to the declared default on
    malformed values instead of crashing the run (the old
    ``int(os.environ[...])`` reads raised ValueError);
  * str — raw value, empty string treated as unset;
  * marker — not an env var at all: a protocol constant that shares
    the namespace (``JEPSEN_TPU_EC`` is the ssh exit-code marker
    string), registered so the name scanner and the README table can
    account for it.

This module is the ONE file where `os.environ` access to
`JEPSEN_TPU_*` names is sanctioned; `export`/`unset` are the writer
counterparts the CLI uses to hand a flag down to subprocesses.
Stdlib-only, import-cheap: every hot path reads gates at call time, so
tests can monkeypatch the env freely.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)

PREFIX = "JEPSEN_TPU_"

#: Parse kinds a gate may declare.
KINDS = ("bool", "int", "float", "str", "marker")


class Gate:
    """One declared gate: name, kind, default, one doc line."""

    __slots__ = ("name", "kind", "default", "doc")

    def __init__(self, name: str, kind: str, default, doc: str):
        assert kind in KINDS, kind
        assert name.startswith(PREFIX), name
        self.name = name
        self.kind = kind
        self.default = default
        self.doc = doc

    def parse(self, raw: str | None):
        """Typed value for a raw env string (None = unset)."""
        if self.kind == "marker":
            return self.default
        if raw is None or (raw == "" and self.kind != "bool"):
            return self.default
        if self.kind == "bool":
            if self.default:
                return raw != "0"
            return raw not in ("", "0")
        if self.kind == "int":
            try:
                return int(raw)
            except ValueError:
                log.debug("malformed %s=%r; using default %r",
                          self.name, raw, self.default)
                return self.default
        if self.kind == "float":
            try:
                return float(raw)
            except ValueError:
                log.debug("malformed %s=%r; using default %r",
                          self.name, raw, self.default)
                return self.default
        # str — stripped: a trailing space from a shell export or CI
        # YAML must not turn a valid value into an unknown one
        raw = raw.strip()
        if raw == "":
            return self.default
        return raw

    def default_str(self) -> str:
        """The README-table rendering of the default."""
        if self.kind == "marker":
            return "—"
        if self.kind == "bool":
            return "`1`" if self.default else "off"
        if self.default is None or self.default == "":
            return "off"
        return f"`{self.default}`"


# ---------------------------------------------------------------------------
# The registry. Ordering is the README table ordering.
# ---------------------------------------------------------------------------

GATES: dict[str, Gate] = {}


def _g(name: str, kind: str, default, doc: str) -> None:
    assert name not in GATES, f"duplicate gate {name}"
    GATES[name] = Gate(name, kind, default, doc)


# -- observability ----------------------------------------------------------
_g("JEPSEN_TPU_TRACE", "bool", True,
   "`0`: no trace/metrics files, no-op spans (<1µs each — the "
   "dp8-efficiency floor is unaffected)")
_g("JEPSEN_TPU_TRACE_MAX_EVENTS", "int", 200_000,
   "bounded tracer event buffer; overflow is counted "
   "(`dropped_events`), never silent")
_g("JEPSEN_TPU_WORKER_TRACE", "bool", True,
   "`0`: ingest pool workers record no spans and write no "
   "`trace-<pid>.jsonl` spools (the merged sweep trace then carries "
   "only parent-side tracks); moot when `JEPSEN_TPU_TRACE=0` — no "
   "tracer means no spools either way")
_g("JEPSEN_TPU_REPORT", "bool", False,
   "set: `analyze-store` writes the critical-path attribution report "
   "(`<store>/report.json` + `report.md`) at sweep end, as if "
   "`--report` were passed")
_g("JEPSEN_TPU_JAX_PROFILE", "bool", False,
   "`1`: wrap the run in a `jax.profiler` capture "
   "(`<run-dir>/jax-profile`; `--jax-profile` sets it)")
_g("JEPSEN_TPU_HEALTH_INTERVAL_S", "float", None,
   "live telemetry: write `<store>/health.json` atomically every this "
   "many seconds during a sweep (progress, robustness, throughput, "
   "heartbeat); unset/<=0 = off")
_g("JEPSEN_TPU_METRICS_PORT", "int", None,
   "serve `/metrics` (Prometheus text exposition) + `/healthz` (the "
   "health snapshot) on this port during a sweep; `0` binds an "
   "ephemeral port; unset = off")
_g("JEPSEN_TPU_EVENTS_MAX_BYTES", "int", None,
   "rotate `<store>/events.jsonl` once it exceeds this many bytes "
   "(atomic rename to `events.jsonl.1`, then an `events_rotated` "
   "event opens the fresh log); unset/<=0 = unbounded (the default)")
_g("JEPSEN_TPU_COSTDB", "bool", False,
   "set: the device cost observatory — capture each executable's XLA "
   "`cost_analysis()`/`memory_analysis()` once per compile, join it "
   "with the measured per-dispatch device windows, publish the "
   "residency gauges, append one record per (executable, geometry) "
   "to `<store>/costdb.jsonl` at sweep end, and add the device "
   "roofline section to `--report`; off (the default) writes zero "
   "new files and costs <1µs per dispatch")
_g("JEPSEN_TPU_RESIDENCY_INTERVAL_S", "float", 5.0,
   "minimum seconds between `device.memory_stats()` polls for the "
   "`hbm_device_bytes` residency gauge (the cheap gauges still "
   "publish per dispatch); `<=0` disables the poll; only read when "
   "`JEPSEN_TPU_COSTDB` is on")
_g("JEPSEN_TPU_KERNEL_STATS", "bool", False,
   "set: kernel search telemetry — checker dispatches additionally "
   "return a per-history graph/search stats vector (edge counts, "
   "closure rounds, SCC shape, decision-boundary margin; WGL "
   "frontier/backtrack counters), journaled to "
   "`<store>/analytics.jsonl` and aggregated into the report's "
   "\"search\" section; off (the default) leaves verdicts, files and "
   "executables byte-identical at <1µs per dispatch")
_g("JEPSEN_TPU_KERNEL_STATS_SAMPLE", "int", 1,
   "journal every Nth history's stats line into `analytics.jsonl` "
   "(in-memory aggregates and the report still cover every history); "
   "`1` (the default) journals all; only read when "
   "`JEPSEN_TPU_KERNEL_STATS` is on")
# -- kernels / backend ------------------------------------------------------
_g("JEPSEN_TPU_BACKEND", "str", None,
   "analysis backend override: `tpu`|`cpu`|`race` (the CLI's "
   "`--backend` exports it; `auto` resolves by hardware)")
_g("JEPSEN_TPU_PLATFORM", "str", None,
   "analysis devices come from this jax platform (`cpu`, `tpu`) "
   "instead of the default backend; also selects the real-hardware "
   "test tier")
_g("JEPSEN_TPU_FUSED_CLASSIFY", "bool", True,
   "`0`: detect-then-classify two-pass instead of the fused kernel")
_g("JEPSEN_TPU_FRONTIER", "int", 512,
   "bounded-frontier arena size for the sorted-frontier register "
   "kernel")
# -- ingest / native --------------------------------------------------------
_g("JEPSEN_TPU_NATIVE_INGEST", "bool", True,
   "`0`: Python jsonl→tensor encoder")
_g("JEPSEN_TPU_NATIVE_SPLIT", "bool", True,
   "`0`: Python per-key splitter for register sweeps")
_g("JEPSEN_TPU_NO_NATIVE", "bool", False,
   "set (non-`0`): disable every ctypes-loaded helper")
_g("JEPSEN_TPU_NATIVE_LIB_DIR", "str", None,
   "load the native `.so`s from this directory instead of "
   "building into `native/build/` — no rebuild, no silent fallback "
   "to a production lib (`make native-sanitize` points it at the "
   "ASan/UBSan instrumented builds)")
_g("JEPSEN_TPU_SHM_INGEST", "bool", True,
   "`0`: pool-encoded histories ride the classic pickle pipe instead "
   "of `multiprocessing.shared_memory` descriptors (also "
   "auto-falls-back when /dev/shm is unusable)")
_g("JEPSEN_TPU_PIPELINE", "bool", False,
   "set: force the multi-process ingest pipeline even on single-core "
   "hosts")
_g("JEPSEN_TPU_ENCODE_CACHE", "bool", True,
   "`0`: no `encoded.v1.bin` sidecar reads or writes — every sweep "
   "re-parses")
_g("JEPSEN_TPU_ENCODE_CACHE_WRITE", "bool", True,
   "`0`: read-only cache (hit existing sidecars, never write — e.g. "
   "a read-only store mount)")
_g("JEPSEN_TPU_PACK_THREAD", "bool", True,
   "`0`: bucket packing + `device_put` stay inline on the "
   "dispatching thread instead of the dedicated pack-h2d thread")
# -- warm path --------------------------------------------------------------
_g("JEPSEN_TPU_SIDECAR_V2", "bool", True,
   "`0`: write/read only v1 (unpadded) encoded sidecars — no "
   "dispatch-shaped `encoded.v2.bin`, no v1→v2 upgrade, warm sweeps "
   "pack with host copies as before")
_g("JEPSEN_TPU_DONATE_BUFFERS", "bool", True,
   "`0`: single-device bucket dispatches keep their input buffers "
   "instead of donating them to XLA (`donate_argnums`) for reuse "
   "across dispatches")
_g("JEPSEN_TPU_AOT_CACHE", "bool", True,
   "`0`: no persistent AOT executable cache — every process pays its "
   "own XLA compiles (the in-memory jit cache still applies)")
# -- multi-host mesh --------------------------------------------------------
_g("JEPSEN_TPU_MESH", "bool", False,
   "set: `analyze-store` runs as ONE SHARD of a multi-host mesh sweep "
   "(the `--mesh` flag exports it): deterministic shard of the run "
   "dirs, per-shard `verdicts-<shard>.jsonl` journal and "
   "`trace-shard<k>.json` artifacts, coordinator merge on shard 0")
_g("JEPSEN_TPU_MESH_SHARD", "int", None,
   "mesh shard index override (re-assign a dead host's shard to "
   "another host); default: `jax.process_index()` on a distributed "
   "job, else 0")
_g("JEPSEN_TPU_MESH_SHARDS", "int", None,
   "mesh shard-count override — set on every host to shard a store "
   "WITHOUT a jax.distributed coordinator; default: "
   "`jax.process_count()` on a distributed job, else 1")
_g("JEPSEN_TPU_MESH_WAIT_S", "float", 600.0,
   "seconds the mesh coordinator (shard 0) waits for the other "
   "shards' done markers before declaring them lost (re-assignable, "
   "exit code ≥2) and merging what exists; `0` merges immediately")
# -- verdict service --------------------------------------------------------
_g("JEPSEN_TPU_SERVE_SOCKET", "str", None,
   "unix-socket path the `serve` verdict daemon listens on (default "
   "`<store>/serve.sock`); tenants stream length-prefixed frames over "
   "it and get verdicts back")
_g("JEPSEN_TPU_SERVE_PORT", "int", None,
   "TCP port for the `serve` daemon instead of the unix socket "
   "(`0` binds an ephemeral port, printed in the ready line); unset = "
   "unix socket")
_g("JEPSEN_TPU_SERVE_MAX_QUEUE", "int", 256,
   "per-tenant admission-queue depth of the `serve` daemon; a CHECK "
   "past the cap gets an explicit `retry-after` frame (never a "
   "silent drop)")
_g("JEPSEN_TPU_SERVE_WEIGHTS", "str", "",
   "per-tenant fairness weights for the `serve` daemon's continuous "
   "batcher, e.g. `fleetA=3,fleetB=1` (unlisted tenants weigh 1); "
   "fold shares follow weighted deficit round-robin")
_g("JEPSEN_TPU_SERVE_DRAIN_S", "float", 30.0,
   "seconds the `serve` daemon spends draining admitted work on "
   "SIGTERM before closing; work never admitted (or past the "
   "deadline) is left for the tenant to resend — never half-acked")
_g("JEPSEN_TPU_SERVE_RETRY_S", "float", 60.0,
   "client-side retry budget: `ServeClient` stops retrying a "
   "backpressured or unreachable endpoint this many seconds after "
   "its last progress (verdict or successful send) and raises "
   "`ServeUnavailable` — the terminal error fleet failover bounds "
   "tenants to; `0` fails on the first retryable condition")
# -- serve fleet ------------------------------------------------------------
_g("JEPSEN_TPU_FLEET_HEARTBEAT_S", "float", 1.0,
   "seconds between a fleet daemon's beacon rewrites "
   "(`fleet-d<k>.json`: pid, epoch, load) — the router's liveness "
   "evidence; lower = faster death detection, more beacon churn")
_g("JEPSEN_TPU_FLEET_FAILOVER_S", "float", 5.0,
   "beacon staleness (kernel mtime age, immune to daemon clock skew) "
   "past which the fleet router declares a daemon dead, fences it "
   "out of the membership epoch, and replays its tenants' journals "
   "on a successor")
_g("JEPSEN_TPU_FLEET_SPILL_DEPTH", "int", 32,
   "queued histories on a tenant's affine daemon past which the "
   "fleet router spills new checks to the least-loaded live daemon "
   "(by beacon queue depth, tie-broken on modeled HBM bytes) "
   "instead of queueing deeper")
# -- cost-aware planner -----------------------------------------------------
_g("JEPSEN_TPU_PLANNER", "bool", False,
   "set: the cost-aware dispatch planner — route per-history tier "
   "(python/native/TPU split + dispatch), bucket geometry and "
   "fused-vs-two-pass choice, and price `serve` admission, from a "
   "cost model fit on `costdb.jsonl` × `analytics.jsonl` (persisted "
   "as `<store>/plan.json`); cold start (no costdb, unseen device "
   "kind, corrupt plan) degrades to the exact current heuristics — "
   "planner decisions never change verdicts, only placement")
_g("JEPSEN_TPU_PLANNER_PATH", "str", None,
   "explicit `plan.json` path for the planner (load AND save), e.g. "
   "one shared model across stores or a daemon fleet; default "
   "`<store>/plan.json`; only read when `JEPSEN_TPU_PLANNER` is on")
# -- robustness -------------------------------------------------------------
_g("JEPSEN_TPU_STRICT", "bool", False,
   "set: restore fail-fast — no quarantine, no OOM backdown; the "
   "first failure raises (CI bisection, debugging one corrupt store)")
_g("JEPSEN_TPU_DISPATCH_TIMEOUT_S", "float", None,
   "per-dispatch device watchdog: bound each `block_until_ready` to "
   "this many seconds, retry once, then quarantine the bucket")
_g("JEPSEN_TPU_FAULT_INJECT", "str", "",
   "self-nemesis spec, e.g. `encode:0.05,oom:first` — deterministic "
   "encode faults / worker kills / simulated OOMs (see Robustness)")
# -- protocol markers (not env vars) ----------------------------------------
_g("JEPSEN_TPU_EC", "marker", "__JEPSEN_TPU_EC:",
   "ssh exit-code marker string the control layer echoes from remote "
   "shells to disambiguate ssh's own 255 from the command's — a "
   "protocol constant, not an env var")


# ---------------------------------------------------------------------------
# Accessors — the only sanctioned JEPSEN_TPU_* env reads/writes.
# ---------------------------------------------------------------------------

def gate(name: str) -> Gate:
    """The declaration for `name` (KeyError on an unregistered gate —
    reads of undeclared names must fail loudly, not invent a gate)."""
    return GATES[name]


def get(name: str):
    """The typed value of gate `name` from the current environment."""
    g = GATES[name]
    if g.kind == "marker":
        return g.default
    return g.parse(os.environ.get(name))


def get_raw(name: str) -> str | None:
    """The raw env string of a REGISTERED gate (None = unset) — for
    the rare caller that needs the spelling, not the parse (e.g. the
    fault injector keying its state on the exact spec string)."""
    GATES[name]  # KeyError on unregistered names
    return os.environ.get(name)


def is_set(name: str) -> bool:
    """Is the gate explicitly set (non-empty) in the environment?"""
    GATES[name]
    return bool(os.environ.get(name))


def export(name: str, value) -> None:
    """Write gate `name` into the environment (the CLI flag→env
    export; subprocesses and embedded callers then see the choice).
    Booleans serialize to the canonical `1`/`0`."""
    g = GATES[name]
    assert g.kind != "marker", f"{name} is a protocol marker, not a gate"
    if isinstance(value, bool):
        value = "1" if value else "0"
    os.environ[name] = str(value)


def unset(name: str) -> None:
    """Remove gate `name` from the environment."""
    GATES[name]
    os.environ.pop(name, None)


# ---------------------------------------------------------------------------
# README rendering — the env-gate table is generated, never hand-kept.
# ---------------------------------------------------------------------------

#: Markers delimiting the generated block in README.md; lint rule
#: JT-GATE-003 fails when the committed block drifts from the registry.
TABLE_BEGIN = "<!-- env-gates:begin (generated by jepsen_tpu.gates) -->"
TABLE_END = "<!-- env-gates:end -->"


def render_env_table() -> str:
    """The README env-gate table, one row per registered gate. Literal
    `|` in a doc line is escaped: markdown splits cells on every
    unescaped pipe, code spans included."""
    lines = ["| gate | default | meaning |", "|---|---|---|"]
    for g in GATES.values():
        doc = g.doc.replace("|", "\\|")
        lines.append(f"| `{g.name}` | {g.default_str()} | {doc} |")
    return "\n".join(lines)


def render_env_block() -> str:
    """The full generated README block, markers included."""
    return f"{TABLE_BEGIN}\n{render_env_table()}\n{TABLE_END}"
