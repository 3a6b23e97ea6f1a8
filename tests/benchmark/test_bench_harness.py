"""The benchmark is data: every name in BENCHMARK.json resolves to its
files, names and units keep to the contract's characters, each per-layer
metric's cells report the metric it moves, a new cell is new files
only, and the command refuses to run without a TPU or the program."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


@pytest.fixture(scope="module")
def doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark()


def test_top_level_keys_and_paths(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert (ROOT / p).is_dir() and ".." not in p.split("/")
    assert len(doc["command"]) <= 32
    for word in doc["command"][1:]:
        assert any(word == p or word.startswith(p + "/")
                   for p in doc["paths"])
    assert isinstance(doc["run_seconds"], int)
    assert 1 <= doc["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_name_resolves(doc, bench):
    for c in doc["configs"]:
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        cfg = bench.config(c["name"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        wl = bench.workload_module(cfg)
        assert callable(wl.generate) and callable(wl.check)
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    for w in doc["workloads"]:
        assert w["config"] in bench.configs
        tr = bench.traffic(w["traffic"])
        assert callable(bench.driver_module(tr).run)
        assert w["chips"] in (1, 4)
    for m in doc["per_layer"]:
        assert callable(bench.metric_module(m["name"]).read)


def test_names_units_and_lines(doc):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section, e["name"]))
    assert len(names) == len(set(names))
    metric_names = [e["name"] for e in doc["end_to_end"] + doc["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in doc["configs"]:
        assert LINE.match(c["source"]) and LINE.match(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_metric_cells_report_what_it_moves(doc, bench):
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", bench.cells):
            assert cell in bench.cells
            reported = {x["name"] for x in bench.end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)
    for cell in bench.cells:
        reported = {x["name"] for x in bench.end_to_end(cell)}
        assert "setup_s" in reported and len(reported) >= 2
        assert bench.per_layer(cell), cell


def test_a_new_cell_is_new_files_only(tmp_path, doc):
    """A mix, a metric and a cell added as files and entries resolve
    without an edit to any file the benchmark has."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    new = json.loads(json.dumps(doc))
    tr = json.loads((ROOT / "benchmark/traffic/store-sweep.json")
                    .read_text())
    tr["name"] = "store-sweep-small-sample"
    tr["reference_valid"] = 4
    (tmp_path / "benchmark/traffic/store-sweep-small-sample.json") \
        .write_text(json.dumps(tr))
    (tmp_path / "benchmark/metrics/passes_seen.py").write_text(
        "def read(r):\n    return 1.0\n")
    new["workloads"].append({
        "name": "append-sweep-small-sample", "config": "etcd-append-10k",
        "traffic": "store-sweep-small-sample", "chips": 1, "why": "test"})
    new["per_layer"].append({
        "name": "passes_seen", "unit": "pass", "better": "higher",
        "source": "host_clock", "layer": "parallel",
        "moves": "sweep_hist_per_s",
        "workloads": ["append-sweep-small-sample"]})
    for m in new["end_to_end"]:
        if "workloads" in m and "append-sweep" in m["workloads"]:
            m["workloads"].append("append-sweep-small-sample")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    b = spec.Benchmark(tmp_path)
    cell = b.cells["append-sweep-small-sample"]
    assert b.traffic(cell["traffic"])["reference_valid"] == 4
    assert b.driver_module(b.traffic(cell["traffic"])).run
    names = [m["name"] for m in b.per_layer(cell["name"])]
    assert names == ["passes_seen"]
    assert b.metric_module("passes_seen").read(None) == 1.0


def _run(cwd: Path, *extra_env) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="1")
    env.pop("JEPSEN_TPU_PLATFORM", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "append-sweep",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns(".work",
                                                      "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert not (tmp_path / "benchmark" / ".work").exists()
