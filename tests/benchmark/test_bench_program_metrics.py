"""The per-layer readers of the program's compile, register and serve
spans, on synthetic passes with known spans: each reads the expected
number, and reads nothing from a trace of a program that records none
of those spans."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec  # noqa: E402

PID, MAIN, OTHER = 7, 100, 200


def meta(origin=True):
    args = {"name": "analyze-store:append"}
    if origin:
        args["origin_realtime_ns"] = 1_700_000_000_000_000_000
    return [{"name": "process_name", "ph": "M", "pid": PID, "tid": 0,
             "args": args},
            {"name": "thread_name", "ph": "M", "pid": PID, "tid": MAIN,
             "args": {"name": "MainThread"}},
            {"name": "thread_name", "ph": "M", "pid": PID, "tid": OTHER,
             "args": {"name": "pack-h2d"}}]


def x(name, ts_ms, dur_ms, tid=MAIN, cat="phase"):
    return {"name": name, "cat": cat, "ph": "X", "pid": PID, "tid": tid,
            "ts": ts_ms * 1e3, "dur": dur_ms * 1e3}


def sweep(events, wall_s=2.0):
    return {"pass": {"events": events, "wall_s": wall_s, "counters": {}},
            "runs": 4}


# a 2-s pass: 100 ms of jit_trace holding a 20-ms nested trace, a 50-ms
# lowering, a 200-ms compile holding a 150-ms cache load (so 350 ms
# merged), a compile on another thread, and a span outside `phase`
COMPILES = [x("dispatch", 0, 600), x("dispatch.resolve", 0, 590),
            x("jit_trace", 10, 100), x("jit_trace", 50, 20),
            x("jit_lower", 110, 50), x("jit_compile", 160, 200),
            x("compile_cache_load", 200, 150),
            x("jit_compile", 400, 300, tid=OTHER),
            x("jit_trace", 900, 100, cat="span")]
REGISTER = [x("register_load", 0, 500), x("register_split", 500, 100),
            x("register_split", 600, 200), x("knossos_pack", 800, 100),
            x("register_load", 0, 300, tid=OTHER)]
SERVE = [("serve_encode", 0.015), ("serve_admission_wait", 0.004),
         ("serve_admission_wait", 0.010), ("serve_fold", 0.025)]


@pytest.mark.parametrize("metric,readings,want", [
    ("compile_wait_share", sweep(meta() + COMPILES), 100 * 0.35 / 2.0),
    ("compile_wait_share", sweep(meta() + REGISTER), 0.0),
    ("register_load_share", sweep(meta() + REGISTER), 100 * 0.5 / 2.0),
    ("register_split_share", sweep(meta() + REGISTER), 100 * 0.3 / 2.0),
    ("serve_admission_wait_ms", {"serve_spans": SERVE}, 7.0),
])
def test_reader_reads_known_spans(metric, readings, want):
    got = spec.Benchmark().metric_module(metric).read(readings)
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("metric,readings", [
    ("compile_wait_share", sweep(meta(origin=False) + COMPILES)),
    ("register_load_share", sweep(meta(origin=False) + COMPILES)),
    ("register_split_share", sweep(meta() + [x("parse", 0, 10)])),
    ("serve_admission_wait_ms", {"serve_spans": [("serve_fold", 0.02)]}),
    ("compile_wait_share", sweep([])),
])
def test_reader_reads_nothing_without_the_spans(metric, readings):
    """The parent program records none of these spans: the reader
    returns nothing, and does not raise."""
    assert spec.Benchmark().metric_module(metric).read(readings) is None
