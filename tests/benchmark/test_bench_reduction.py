"""The benchmark's yardstick on hand-built inputs: trace reduction
(busy time, idle share, kernel time, breakdown), the peaks table, the
Elle closure work count, percentiles and the program-span reader. No
test here loads libtpu."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import peaks, result, roofline, spans, xplane  # noqa: E402

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def planes(window=(0, 100)):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__unknown(123)", 10, 30), ev("jit_check_dense_device(7)",
                                               60, 10)]),
        NS(name="XLA Ops", events=[
            ev("%while.3 = (pred[2,8,8]) while(...)", 10, 30),
            ev("%fusion.1 = pred[2,8,8] fusion(...)", 12, 8),
            ev("%fusion.2 = pred[2,8,8] fusion(...)", 25, 10),
            ev("%copy.1 = s32[3] copy(...)", 60, 10)]),
        NS(name="Async XLA Ops", events=[ev("%copy-start", 0, 100)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            ev(xplane.WINDOW, *window),
            ev("bench:analyze_store", 0, 100),
            ev("np.asarray(jax.Array)", 45, 10)]),
        NS(name="pjrt-tpu-tasks/3", events=[ev("Transpose", 70, 30)])])
    return [NS(name="/host:metadata", lines=[]), dev, host]


def test_busy_idle_and_kernel_time():
    t = xplane.from_planes(planes())
    assert t.window_s == pytest.approx(0.1)
    # ops [10,40] and [60,70]: nested ops count once, async copies not
    assert t.busy_s() == pytest.approx(0.04)
    assert t.module_s(("jit__unknown",)) == pytest.approx(0.03)
    assert t.module_s(("check_dense_device",)) == pytest.approx(0.01)
    assert t.module_s(("nothing",)) == 0


def test_window_clips_busy_time():
    t = xplane.from_planes(planes(window=(20, 40)))
    assert t.busy_s() == pytest.approx(0.02)
    assert t.module_s(("jit__unknown",)) == pytest.approx(0.02)


def test_breakdown_lists_innermost_ops_and_named_gaps():
    t = xplane.from_planes(planes())
    top = dict(t.top_ops())
    assert "while.3" not in top
    assert top["fusion.2"] == pytest.approx(0.01)
    assert top["copy.1"] == pytest.approx(0.01)
    gaps = t.idle_gaps()
    # gaps [70,100] (30 ms), [40,60] and [0,10]
    assert [round(g, 3) for _n, g in gaps] == [0.03, 0.02, 0.01]
    assert gaps[0][0] == "bench:analyze_store"     # python line first
    assert gaps[1][0] == "np.asarray(jax.Array)"


def test_a_trace_without_the_window_is_refused():
    pl = planes()
    pl[2].lines[0].events = pl[2].lines[0].events[1:]
    with pytest.raises(ValueError):
        xplane.from_planes(pl)


def test_merge_and_leaves():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                              [5, 9]]
    ivs = [(0, 10, "p"), (1, 3, "a"), (4, 6, "b"), (20, 30, "c")]
    assert [n for _s, _e, n in xplane.leaves(ivs)] == ["a", "b", "c"]


def test_peaks_table():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["int8_ops"] == 393e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_elle_closure_work_is_a_floor():
    ops, nbytes = roofline.elle_closure_work(4850)
    assert ops == 2 * 4850 ** 3 and nbytes == 2 * 4850 ** 2
    pk = peaks.peaks("TPU v5 lite")
    least = roofline.least_seconds(ops, nbytes, pk)
    assert least == pytest.approx(ops / 393e12)      # compute-bound
    # one padded squaring round alone already takes longer than the floor
    assert 2 * 4864 ** 3 / 393e12 > least
    assert roofline.least_seconds(1.0, 819e9, pk) == pytest.approx(1.0)


def test_percentile():
    assert result.percentile([3, 1, 2], 50) == 2
    assert result.percentile(range(101), 95) == 95
    assert result.percentile([1, 2], 50) == 1.5
    with pytest.raises(ValueError):
        result.percentile([], 50)


def test_checks_pass_only_at_or_under_every_limit():
    c = result.Checks()
    assert not c.correct
    c.add("missing", 0, 0)
    assert c.correct
    c.add("wrong", 1, 0)
    assert not c.correct


def test_gaps_are_named_by_program_spans_on_the_profiler_clock():
    t = xplane.from_planes(planes())
    # a program span [5, 15) ms of a tracer whose clock read 1 ms at the
    # profiler's 0 ms: [4, 14) ms on the profiler clock, which covers
    # the middle of the [0, 10) ms gap
    labels = spans.on_profile_clock([(5000.0, 10000.0, "parse")], 1000.0,
                                    0.0)
    assert labels == [(4e6, 14e6, "program:parse")]
    named = {round(g, 3): n for n, g in t.idle_gaps(labels=labels)}
    assert named[0.01] == "program:parse"
    assert named[0.02] == "np.asarray(jax.Array)"
    assert spans.on_profile_clock([(0.0, 1.0, "x")], 0.0, None) == []
    assert t.host_start("bench:analyze_store") == 0


def test_main_thread_phase_seconds():
    events = [
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
         "args": {"name": "analyze-store:append"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 11,
         "args": {"name": "MainThread"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 12,
         "args": {"name": "pack-h2d"}},
        {"ph": "X", "cat": "phase", "name": "parse", "pid": 1, "tid": 11,
         "ts": 0, "dur": 2e6},
        {"ph": "X", "cat": "phase", "name": "parse", "pid": 1, "tid": 11,
         "ts": 3e6, "dur": 1e6},
        {"ph": "X", "cat": "span", "name": "parse", "pid": 1, "tid": 12,
         "ts": 0, "dur": 5e6},
        {"ph": "X", "cat": "phase", "name": "collect", "pid": 1, "tid": 11,
         "ts": 5e6, "dur": 4e6}]
    assert spans.main_thread_seconds(events, "parse") == 3.0
    assert spans.main_thread_seconds(events, "collect") == 4.0


@pytest.mark.parametrize("dispatched,reads", [(1, True), (2, False),
                                              (None, False)])
def test_elle_metrics_read_only_the_pass_dispatches(dispatched, reads):
    """`jit__unknown` names any executable loaded from the AOT cache:
    the Elle readers take its time only while its executions number
    the pass's bucket dispatches."""
    from harness import spec
    b = spec.Benchmark()
    counters = {} if dispatched is None else \
        {"buckets_dispatched": dispatched}
    r = {"trace": xplane.from_planes(planes()), "runs": 2,
         "pass": {"counters": counters}, "device_kind": "TPU v5 lite",
         "txn_counts": [100, 120]}
    for name in ("elle_check_ms_per_hist", "elle_closure_roofline"):
        v = b.metric_module(name).read(r)
        assert (v is not None) is reads, name
    if reads:
        assert b.metric_module("elle_check_ms_per_hist").read(r) == \
            pytest.approx(15.0)


def test_a_split_metric_name_shares_one_reader():
    from harness import spec
    b = spec.Benchmark()
    sweep = b.metric_module("device_idle_share.sweep")
    assert sweep is b.metric_module("device_idle_share.serve")
    assert sweep.read({"trace": xplane.from_planes(planes())}) == \
        pytest.approx(60.0)
