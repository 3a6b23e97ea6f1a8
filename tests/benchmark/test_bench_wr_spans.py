"""What the wr-sweep metrics read, on the CPU: the wr dispatcher's
main-thread `edge_pack`, `h2d`, `dispatch` and `collect` phases and its
`buckets_dispatched`, `buckets_resolved` and `wr_edges_packed` counters;
the executable's module name the device readers match; and the readers
themselves on hand-built passes and traces."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spans, spec, stores, xplane  # noqa: E402

BENCH = spec.Benchmark()
WR = BENCH.config("cockroach-rw-register-g2")
RW = BENCH.workload_module(WR)
PHASES = ("edge_pack", "h2d", "dispatch", "collect")
MS = 1_000_000


@pytest.fixture(scope="module")
def edge_dicts(tmp_path_factory):
    """Edge dicts of eight 300-txn histories, one of them seeded."""
    from jepsen_tpu import ingest
    from jepsen_tpu.checker.elle.wr import to_edge_dict
    root = tmp_path_factory.mktemp("wr-edges")
    stores.generate(RW, {**WR, "txns_per_history": 300}, root, 2**36 + 9, 8)
    return [to_edge_dict(ingest.encode_run_dir(d, "wr"))
            for d in sorted(root.iterdir())]


@pytest.mark.parametrize("budget_rows", [8, 3, 1])
def test_wr_dispatch_phases_and_counters(edge_dicts, budget_rows):
    """Each bucket opens the four phases once on the calling thread and
    counts one dispatch and one resolve; the edges packed are counted."""
    import jax
    from jepsen_tpu import trace
    from jepsen_tpu.checker.elle import kernels as K
    from jepsen_tpu.parallel import bucket_by_length
    budget = budget_rows * 384 * 384
    buckets = bucket_by_length(edge_dicts, budget_cells=budget)
    tr = trace.fresh_run("wr-spans")
    got = K.check_edge_batch_bucketed(edge_dicts, budget_cells=budget,
                                      devices=jax.devices()[:1])
    assert [bool(g) for g in got] == [False] * 7 + [True]
    md = tr.metrics_dict()["counters"]
    assert md["buckets_dispatched"] == md["buckets_resolved"] \
        == len(buckets)
    assert md["wr_edges_packed"] == sum(len(e["edges"]) for e in edge_dicts)
    ev = tr.chrome_events()
    names = [e["name"] for e in ev if e.get("cat") == "phase"]
    for ph in PHASES:
        assert names.count(ph) == len(buckets), ph
    packs = [e["args"] for e in ev if e.get("name") == "edge_pack"]
    assert {tuple(sorted(a)) for a in packs} == {("B", "T", "edges")}
    assert sum(a["B"] for a in packs) == len(edge_dicts)


def test_module_name_is_pinned():
    """The device readers match the executable by this name."""
    import jax.numpy as jnp
    from jepsen_tpu.checker.elle import kernels as K
    B, T = 2, 128
    m = jnp.zeros((B, T, T), bool)
    i = jnp.zeros((B, T), jnp.int32)
    text = K.classify_matrices_device.lower(
        m, m, m, i, i, i, jnp.zeros((B,), jnp.int32),
        steps=K.closure_steps(T)).as_text()
    assert "jit_classify_matrices_device" in text


def test_a_cpu_wr_sweep_traces_main_thread_phases(tmp_path):
    """analyze-store --checker wr: the metrics' spans sit on the sweep's
    main thread, and the pass counts its one bucket."""
    import contextlib
    import json
    import os
    from jepsen_tpu import cli
    stores.generate(RW, {**WR, "txns_per_history": 200},
                    tmp_path / WR["name"], 77, 8)
    with open(os.devnull, "w") as f, contextlib.redirect_stdout(f):
        cli.run_cli(lambda tmap, args: tmap, argv=[
            "analyze-store", "--store", str(tmp_path), "--checker", "wr",
            "--backend", "tpu"])
    ev = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    main = [n for _t, _d, n in spans.main_thread_phases(ev)]
    for ph in PHASES:
        assert ph in main, ph
    c = json.loads((tmp_path / "metrics.json").read_text())["counters"]
    assert c["buckets_dispatched"] == c["buckets_resolved"] \
        == main.count("dispatch") >= 1
    r = {"pass": {"events": ev, "wall_s": 10.0, "counters": c}}
    share = BENCH.metric_module("edge_pack_share").read(r)
    assert share == pytest.approx(
        100 * spans.main_thread_seconds(ev, "edge_pack") / 10.0)
    assert share > 0


def planes(modules):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            NS(name=n, start_ns=s * MS, duration_ns=d * MS)
            for n, s, d in modules]),
        NS(name="XLA Ops", events=[])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        NS(name=xplane.WINDOW, start_ns=0, duration_ns=100 * MS)])])
    return [dev, host]


# two wr dispatches of 10 and 20 ms, and an executable of another name
MODULES = [("jit_classify_matrices_device(11)", 10, 10),
           ("jit_classify_matrices_device(11)", 40, 20),
           ("jit_check_batched_impl(3)", 70, 5)]


def readings(dispatched):
    counters = {} if dispatched is None else \
        {"buckets_dispatched": dispatched}
    return {"trace": xplane.from_planes(planes(MODULES)), "runs": 4,
            "pass": {"counters": counters}, "device_kind": "TPU v5 lite",
            "txn_counts": [4850, 4800, 4813, 4884]}


@pytest.mark.parametrize("dispatched,reads", [(2, True), (3, False),
                                              (1, False), (None, False)])
def test_wr_readers_read_only_the_pass_dispatches(dispatched, reads):
    r = readings(dispatched)
    for name in ("wr_check_ms_per_hist", "wr_closure_roofline"):
        v = BENCH.metric_module(name).read(r)
        assert (v is not None) is reads, name


def test_wr_check_time_and_roofline_by_hand():
    r = readings(2)
    # 30 ms of device time over 4 histories
    assert BENCH.metric_module("wr_check_ms_per_hist").read(r) == \
        pytest.approx(7.5)
    # compute-bound at these sizes: 2 n^3 int8 ops each at 393e12 op/s
    ns = np.array(r["txn_counts"], float)
    assert all(2 * ns ** 2 / 819e9 < 2 * ns ** 3 / 393e12)
    least = float(np.sum(2 * ns ** 3)) / 393e12
    assert BENCH.metric_module("wr_closure_roofline").read(r) == \
        pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("events,want", [
    ([("edge_pack", 0, 300), ("edge_pack", 500, 200), ("h2d", 700, 100)],
     100 * 0.5 / 2.0),
    ([("parse", 0, 10), ("collect", 10, 300)], None),
    ([], None),
])
def test_edge_pack_share_reader(events, want):
    meta = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "analyze-store:wr"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 5,
             "args": {"name": "MainThread"}}]
    ev = (meta if events else []) + [
        {"name": n, "cat": "phase", "ph": "X", "pid": 1, "tid": 5,
         "ts": t * 1e3, "dur": d * 1e3} for n, t, d in events]
    got = BENCH.metric_module("edge_pack_share").read(
        {"pass": {"events": ev, "wall_s": 2.0}})
    assert got == (None if want is None else pytest.approx(want))
