"""The program's spans on the profiler's clock: profiler annotations
around every span and phase, JAX's compile steps as spans and counters,
the dispatch phase split into resolve and enqueue, the register sweep's
stages, the serve request spans that share an id, the tracer's realtime
origin, and the device executable's name. All on the CPU."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from jepsen_tpu import jaxtrace, parallel, trace
from jepsen_tpu.checker.elle import encode as elle_encode
from jepsen_tpu.checker.elle.synth import synth_append_history

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _fresh_trace():
    trace.reset()
    yield
    trace.reset()


def _x(tr, name=None):
    return [e for e in tr.chrome_events() if e["ph"] == "X"
            and (name is None or e["name"] == name)]


def _main_tid() -> int:
    import threading
    return threading.main_thread().ident


# ---------------------------------------------------------------------------
# Annotations
# ---------------------------------------------------------------------------

class _Recorder:
    """An annotation factory that records what it was asked to open."""

    def __init__(self):
        self.opened: list[str] = []
        self.closed = 0

    def __call__(self, name):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.opened.append(name)
                return self

            def __exit__(self, *exc):
                rec.closed += 1
                return False
        return _Ann()


@pytest.fixture
def recorder():
    prev = trace._annotation
    rec = _Recorder()
    trace.set_annotation(rec)
    yield rec
    trace.set_annotation(prev)


@pytest.mark.parametrize("kind", ["span", "phase_span"])
def test_annotation_wraps_spans_and_phases(recorder, kind):
    tr = trace.fresh_run("ann")
    phases: dict = {}
    cm = tr.span("pack", cat="phase") if kind == "span" \
        else tr.phase_span("pack", phases)
    with cm:
        time.sleep(0.001)
    assert recorder.opened == ["program:pack"] and recorder.closed == 1
    assert [e["name"] for e in _x(tr)] == ["pack"]


def test_trace_off_runs_no_hook_and_imports_no_jax():
    """JEPSEN_TPU_TRACE=0: the NullTracer never calls an installed
    annotation factory, and trace.py imports no jax."""
    code = (
        "import sys\n"
        "from jepsen_tpu import trace\n"
        "calls = []\n"
        "trace.set_annotation(lambda n: calls.append(n))\n"
        "tr = trace.get_current()\n"
        "assert not tr.enabled\n"
        "phases = {}\n"
        "with trace.span('a'):\n"
        "    pass\n"
        "with tr.phase_span('parse', phases) as p:\n"
        "    p.note(runs=1)\n"
        "with tr.phase_span('parse'):\n"
        "    pass\n"
        "assert calls == [], calls\n"
        "assert 'parse' in phases\n"
        "assert 'jax' not in sys.modules, 'trace imported jax'\n"
        "print('ok')\n")
    env = {**os.environ, "JEPSEN_TPU_TRACE": "0", "PYTHONPATH": str(REPO)}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_profiler_capture_holds_program_annotations(tmp_path):
    """A running profiler records the program's spans as `program:*`
    host events, and a trace.json span placed through the realtime
    origin lands on its annotation's start (within 0.1 ms)."""
    import jax
    from jax.profiler import ProfileData
    jaxtrace.install()
    tr = trace.fresh_run("analyze-store:clock", scope="sweep")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.phase_span("parse"):
            time.sleep(0.005)
        with tr.span("dispatch.resolve", cat="phase"):
            pass
    finally:
        jax.profiler.stop_trace()
    f = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    planes = list(ProfileData.from_file(f).planes)
    start = dict(next(p for p in planes
                      if p.name == "Task Environment").stats)
    host = {ev.name: ev for p in planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events}
    assert {"program:parse", "program:dispatch.resolve"} <= set(host)
    parse = _x(tr, "parse")[0]
    placed = tr.origin_realtime_ns + parse["ts"] * 1e3
    ann = start["profile_start_time"] + host["program:parse"].start_ns
    assert abs(placed - ann) < 100_000, placed - ann


# ---------------------------------------------------------------------------
# The realtime origin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["trace.json", "metrics.json", "spool"])
def test_realtime_origin_is_recorded(tmp_path, where):
    before = time.time_ns()
    tr = trace.fresh_run("analyze-store:origin", scope="sweep")
    after = time.time_ns()
    assert before <= tr.origin_realtime_ns <= after
    assert after - before < 1_000_000
    if where == "trace.json":
        obj = json.loads(tr.export(tmp_path / "trace.json").read_text())
        meta = next(e for e in obj["traceEvents"]
                    if e.get("name") == "process_name")
        got = meta["args"]["origin_realtime_ns"]
        assert got == tr.origin_realtime_ns
    elif where == "metrics.json":
        got = json.loads(tr.export_metrics(tmp_path / "m.json")
                         .read_text())["origin_realtime_ns"]
        assert got == tr.origin_realtime_ns
    else:
        tr.spool_dir = tmp_path
        ctx = trace.worker_ctx()
        t0 = time.time_ns()
        trace.ensure_worker_tracer(ctx)
        trace.close_worker_spool()
        meta, _thr, _evs = trace.load_spool(
            trace.spool_path(tmp_path, os.getpid()))
        got = meta["origin_realtime_ns"]
        assert abs(got - t0) < 1_000_000
        assert isinstance(meta["origin_mono"], float)
        return
    assert abs(got - time.time_ns()) < 60e9   # a real wall-clock stamp


def test_realtime_spans_land_on_the_tracer_clock():
    tr = trace.fresh_run("rt")
    now = time.time()
    tr.add_span("jit_trace", now - 0.010, now, clock="realtime",
                cat="phase", fun="f")
    (e,) = _x(tr, "jit_trace")
    assert e["cat"] == "phase" and e["args"] == {"fun": "f"}
    assert e["dur"] == pytest.approx(10_000, abs=5)
    assert tr.origin_realtime_ns + (e["ts"] + e["dur"]) * 1e3 \
        == pytest.approx(now * 1e9, abs=5_000)


# ---------------------------------------------------------------------------
# JAX compile spans
# ---------------------------------------------------------------------------

def test_compile_listener_spans_and_counters():
    import jax
    import jax.numpy as jnp
    jaxtrace.install()
    x = jnp.arange(7.0)
    tr = trace.fresh_run("compile")

    def fresh_kernel(x):
        # jnp ops are jitted themselves: their nested traces fold into
        # this one
        return jnp.sum(jnp.tanh(x) * 3.0) + jnp.max(x)

    f = jax.jit(fresh_kernel)
    f(x).block_until_ready()
    spans = {n: _x(tr, n) for n in ("jit_trace", "jit_lower",
                                    "jit_compile")}
    for n, got in spans.items():
        assert len(got) == 1, (n, got)
        assert got[0]["cat"] == "phase"
        assert got[0]["tid"] == _main_tid()
        assert "fresh_kernel" in got[0]["args"]["fun"]
    c = tr.metrics_dict()["counters"]
    assert (c["jit_traces"], c["jit_lowerings"], c["jit_compiles"]) \
        == (1, 1, 1)
    # same jit, same shape: no trace, no lowering, no compile
    f(x).block_until_ready()
    assert tr.metrics_dict()["counters"] == c
    assert len(_x(tr, "jit_trace")) == 1
    assert tr.phase_totals() == {}     # compile spans add to no total


# ---------------------------------------------------------------------------
# dispatch.resolve / dispatch.enqueue
# ---------------------------------------------------------------------------

def test_dispatch_subspans_nest_and_leave_totals():
    tr = trace.fresh_run("dispatch")
    encs = [elle_encode.encode_history(
        synth_append_history(T=60 + 30 * i, K=6, seed=i))
        for i in range(3)]
    phases: dict = {}
    pv = parallel.check_bucketed_async(encs, phases=phases)
    pv.result(phases)
    disp = _x(tr, "dispatch")
    assert disp
    for sub in ("dispatch.resolve", "dispatch.enqueue"):
        got = _x(tr, sub)
        assert len(got) == len(disp)
        for s in got:
            assert s["cat"] == "phase"
            assert set(s["args"]) == {"B", "T"} and s["args"]["T"] % 128 == 0
            assert any(d["tid"] == s["tid"] and d["ts"] <= s["ts"]
                       and s["ts"] + s["dur"] <= d["ts"] + d["dur"] + 1e-3
                       for d in disp), s
    totals = tr.phase_totals()
    assert not any(k.startswith(("dispatch.", "jit_")) for k in totals)
    assert set(phases) == set(totals)
    for k, v in phases.items():
        assert totals[k] == pytest.approx(v, rel=1e-9)


# ---------------------------------------------------------------------------
# The register sweep's stages
# ---------------------------------------------------------------------------

def _reg_run(store, ts: str, keys=("a", "b", "c")):
    from jepsen_tpu import independent
    kv = independent.tuple_
    hist = []
    for k in keys:
        for f, v in (("write", 1), ("read", 1), ("cas", [1, 2]),
                     ("read", 2)):
            hist.append({"type": "invoke", "process": 0, "f": f,
                         "value": kv(k, None if f == "read" else v)})
            hist.append({"type": "ok", "process": 0, "f": f,
                         "value": kv(k, v)})
    d = store.base / "etcd" / ts
    d.mkdir(parents=True)
    (d / "history.jsonl").write_text("\n".join(
        json.dumps({**o, "index": i, "time": i * 1000})
        for i, o in enumerate(hist)) + "\n")
    return d


def test_register_sweep_stages_on_the_main_thread(tmp_path, monkeypatch):
    """Every register and Knossos stage lands as a main-thread phase of
    the sweep's trace.json; key "c" is routed to the CPU engine. The
    load workers run in this process, where the patches below reach
    them."""
    from jepsen_tpu import cli, ingest
    from jepsen_tpu.checker.knossos import encode as kenc
    from jepsen_tpu.store import Store

    def routed(real):
        def enc(hs):
            if hs and hs[0].get("_cpu"):
                raise kenc.EncodingError("routed to the CPU")
            return real(hs)
        return enc

    # histories of key "c" carry a marker the split keeps per op
    def marked(hist):
        return [{**o, "_cpu": True} if o["value"][0] == "c" else o
                for o in hist]

    real_split = __import__("jepsen_tpu.independent",
                            fromlist=["x"]).subhistories

    def split(hist):
        return real_split(marked(hist))

    monkeypatch.setattr("jepsen_tpu.independent.subhistories", split)
    monkeypatch.setattr(ingest, "_spawn_safe", lambda: False)
    monkeypatch.setattr(kenc, "encode_dense_history",
                        routed(kenc.encode_dense_history))
    monkeypatch.setattr(kenc, "encode_register_history",
                        routed(kenc.encode_register_history))
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
    store = Store(tmp_path / "store")
    for i in range(2):
        _reg_run(store, f"2020010{1 + i}T000000")
    assert cli.analyze_store(store, checker="register") == 0
    evs = json.loads((store.base / "trace.json").read_text())[
        "traceEvents"]
    mains = {(e["pid"], e["tid"]) for e in evs if e.get("ph") == "M"
             and e.get("name") == "thread_name"
             and e["args"]["name"] == "MainThread"}
    on_main = {e["name"]: e for e in evs if e.get("ph") == "X"
               and e.get("cat") == "phase"
               and (e["pid"], e["tid"]) in mains}
    want = {"register_load": "runs", "register_split": "keys",
            "knossos_pack": "keys", "knossos_wait": "keys",
            "knossos_cpu": "keys", "register_write": "runs"}
    for name, arg in want.items():
        assert name in on_main, sorted(on_main)
        assert on_main[name]["args"][arg] >= 1
    assert on_main["register_load"]["args"]["runs"] == 2
    m = json.loads((store.base / "metrics.json").read_text())
    assert set(want) <= set(m["phase_totals_secs"])
    assert m["counters"]["register_cpu_routed"] == 2


# ---------------------------------------------------------------------------
# Serve: one request's spans share its id
# ---------------------------------------------------------------------------

def test_serve_request_spans_share_id_and_fold(tmp_path):
    from jepsen_tpu.checker.elle.synth import write_synth_store
    from jepsen_tpu.serve.client import ServeClient
    from jepsen_tpu.serve.daemon import VerdictDaemon
    from jepsen_tpu.store import Store
    store = tmp_path / "store"
    (store / "synth").mkdir(parents=True)
    write_synth_store(store / "synth", 2, 96, 8, 0)
    dirs = sorted(Store(store).iter_run_dirs())
    prev = trace.get_current()
    d = VerdictDaemon(Store(store)).start()
    try:
        tr = trace.get_current()
        with ServeClient(socket_path=d.ready_info()["serve"]["socket"],
                         tenant="fleetA") as c:
            for x in dirs:
                c.check_dir(x, rid=x.name)
            c.collect(timeout=300)
    finally:
        assert d.stop() == 0
        trace.set_current(prev)
    # after the stop: a reply span closes just after its frame is sent
    evs = _x(tr)
    folds = {e["args"]["fold"]: e for e in evs if e["name"] == "serve_fold"}
    for x in dirs:
        mine = {e["name"]: e for e in evs
                if (e.get("args") or {}).get("id") == x.name}
        assert {"serve_encode", "serve_admission_wait", "serve_reply",
                "serve_request"} <= set(mine), sorted(mine)
        assert mine["serve_encode"]["args"]["tenant"] == "fleetA"
        fold = mine["serve_admission_wait"]["args"]["fold"]
        assert mine["serve_reply"]["args"]["fold"] == fold
        assert x.name in folds[fold]["args"]["ids"]
        # the wait ends where its fold starts
        w = mine["serve_admission_wait"]
        assert w["ts"] + w["dur"] <= folds[fold]["ts"] + 1.0


# ---------------------------------------------------------------------------
# The device executable's name
# ---------------------------------------------------------------------------

def test_check_executable_keeps_its_name(tmp_path, monkeypatch):
    """The Elle check executable is named after the kernel, also when
    reloaded from the AOT cache (no longer `jit__unknown`)."""
    from jepsen_tpu import aot
    from jepsen_tpu.checker.elle import kernels as K
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    encs = [elle_encode.encode_history(
        synth_append_history(T=40, K=4, seed=1))]
    shape = K.BatchShape.plan(encs)
    args = parallel.shard_batch(None, K.pack_batch(encs, shape))
    fn = parallel.sharded_check_fn(None, shape)
    compiled = aot.compiled_for(fn, args, ("name-test",))
    aot.clear_memory()
    reloaded = aot.compiled_for(fn, args, ("name-test",))
    for c in (compiled, reloaded):
        (mod,) = c.runtime_executable().hlo_modules()
        assert mod.name == "jit_check_batched_impl"
