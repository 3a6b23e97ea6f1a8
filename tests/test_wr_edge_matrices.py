"""The wr dispatcher's device-side edge build: `pack_edge_lists` packs
each bucket's edge lists into [B,E,3] rows, and `edge_matrices` scatters
them into the [B,T,T] ww/wr/rw matrices inside the classify executable.
Checked bit for bit against the dense host pack it replaced (kept here
as `host_pack`), for verdicts against the host oracle, for the bytes it
puts on the device, and for how many edge geometries a store yields."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jepsen_tpu import ingest, trace
from jepsen_tpu.checker.elle import graph as G
from jepsen_tpu.checker.elle import kernels as K
from jepsen_tpu.checker.elle import wr
from jepsen_tpu.parallel import bucket_by_length

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

from harness import spec, stores  # noqa: E402

BENCH = spec.Benchmark()
WR = BENCH.config("cockroach-rw-register-g2")
RW = BENCH.workload_module(WR)


def host_pack(per_history: list[dict], multiple: int = 128):
    """The dense host pack the device build replaced: three [B,T,T]
    bool matrices, one edge set at a time, self-loops skipped."""
    B = len(per_history)
    T = K.pad_to(max((h["n"] for h in per_history), default=1), multiple)
    mats = {c: np.zeros((B, T, T), bool) for c in (G.WW, G.WR, G.RW)}
    for i, hist in enumerate(per_history):
        for s, d, cls in hist["edges"]:
            if s != d:
                mats[cls][i, s, d] = True
    return mats[G.WW], mats[G.WR], mats[G.RW]


def random_history(rng, n: int, n_edges: int) -> dict:
    """n txns with n_edges (src, dst, cls) rows drawn with repeats, so
    duplicates and self-loops both occur."""
    if n == 0:
        edges = []
    else:
        edges = [tuple(int(x) for x in row) for row in np.stack(
            [rng.integers(0, n, n_edges), rng.integers(0, n, n_edges),
             rng.integers(0, 3, n_edges)], axis=1)]
        edges += [(s, s, c) for s, _d, c in edges[:3]]   # self-loops
        edges += edges[:5]                                # duplicates
    return {"n": n, "edges": edges,
            "invoke_index": rng.integers(0, 10 * n + 1, n),
            "complete_index": rng.integers(0, 10 * n + 1, n),
            "process": rng.integers(0, 5, n).astype(np.int32)}


build = jax.jit(K.edge_matrices, static_argnums=1)


@pytest.mark.parametrize("sizes", [
    [(5, 12)],
    [(130, 2000), (7, 30), (0, 0)],
    [(0, 0), (0, 0)],
    [(40, 1500), (200, 900), (3, 3), (64, 0), (90, 1100)],
], ids=["one", "ragged-with-empty", "all-empty", "five"])
def test_device_build_equals_host_pack(sizes):
    rng = np.random.default_rng(len(sizes) * 1000 + sizes[0][1])
    per = [random_history(rng, n, e) for n, e in sizes]
    p = K.pack_edge_lists(per)
    assert p["edges"].shape == (len(per), p["E"], 3)
    assert p["E"] == K.edge_pad(max(
        sum(s != d for s, d, _c in h["edges"]) for h in per))
    got = build(jnp.asarray(p["edges"]), p["T"])
    for g, want in zip(got, host_pack(per)):
        np.testing.assert_array_equal(np.asarray(g), want)
    for i, h in enumerate(per):
        n = h["n"]
        assert p["n_txns"][i] == n
        np.testing.assert_array_equal(p["invoke_index"][i, :n],
                                      h["invoke_index"])
        np.testing.assert_array_equal(p["complete_index"][i, :n],
                                      h["complete_index"])
        np.testing.assert_array_equal(p["process"][i, :n], h["process"])
        assert (p["process"][i, n:] == -1).all()


@pytest.mark.parametrize("n_edges,want", [
    (0, 1024), (1, 1024), (1024, 1024), (1025, 2048), (3700, 4096),
    (4096, 4096), (4108, 8192)])
def test_edge_pad(n_edges, want):
    assert K.edge_pad(n_edges) == want


@pytest.fixture(scope="module")
def g2_store(tmp_path_factory):
    """Encoded histories of a small rw-register store with one seeded
    G2 in every 8th history."""
    root = tmp_path_factory.mktemp("wr-g2")
    stores.generate(RW, {**WR, "txns_per_history": 250}, root,
                    2**35 + 28, 16)
    return [ingest.encode_run_dir(d, "wr") for d in sorted(root.iterdir())]


@pytest.mark.parametrize("budget_rows", [16, 3])
def test_verdicts_equal_host_oracle(g2_store, budget_rows):
    got = K.check_edge_batch_bucketed(
        [wr.to_edge_dict(e) for e in g2_store],
        budget_cells=budget_rows * 256 * 256, devices=jax.devices()[:1])
    want = [wr.cycle_anomalies_cpu(e) for e in g2_store]
    assert [sorted(g) for g in got] == [sorted(w) for w in want]
    assert [bool(g) for g in got] == [i % 8 == 7 for i in range(16)]


def test_dp_sharded_build_equals_one_device(g2_store):
    """On a dp mesh each device builds its own histories' matrices."""
    per = [wr.to_edge_dict(e) for e in g2_store[:5]]
    one = K.check_edge_batch(per, devices=jax.devices()[:1])
    assert K.check_edge_batch(per, devices=jax.devices()[:4]) == one


def test_h2d_bytes_are_the_packed_rows(g2_store):
    """Each bucket puts its edge rows and timing tensors on the device,
    counted in `wr_h2d_bytes` and on its `h2d` span; the edge geometry
    rides on the span too."""
    per = [wr.to_edge_dict(e) for e in g2_store]
    budget = 3 * 256 * 256
    tr = trace.fresh_run("wr-h2d")
    K.check_edge_batch_bucketed(per, budget_cells=budget,
                                devices=jax.devices()[:1])
    h2d = [e["args"] for e in tr.chrome_events() if e.get("name") == "h2d"]
    assert len(h2d) == len(bucket_by_length(per, budget_cells=budget))
    assert tr.metrics_dict()["counters"]["wr_h2d_bytes"] == \
        sum(a["bytes"] for a in h2d)
    for a in h2d:
        B, T, E = a["B"], a["T"], a["E"]
        assert a["bytes"] == B * (E * 3 * 4 + T * (8 + 8 + 4) + 4)


def test_executable_takes_the_edge_rows():
    """The device readers match the wr executable by this name, and it
    is the one executable a bucket runs: the build is inside it."""
    B, T = 2, 128
    e = jnp.zeros((B, K.edge_pad(0), 3), jnp.int32)
    i = jnp.zeros((B, T), jnp.int32)
    text = K.classify_matrices_device.lower(
        e, i, i, i, jnp.zeros((B,), jnp.int32),
        steps=K.closure_steps(T)).as_text()
    assert "jit_classify_matrices_device" in text
    assert "scatter" in text


def test_store_buckets_share_two_edge_geometries(tmp_path):
    """Across the cockroach-rw-register-g2 store's histories, the edge
    pad gives each bucket T at most two edge geometries, so a sweep
    compiles at most twice the executables the T geometries ask for."""
    stores.generate(RW, WR, tmp_path, 2**34 + 28, WR["runs_per_store"],
                    workers=1)
    per = [wr.to_edge_dict(ingest.encode_run_dir(d, "wr"))
           for d in sorted(tmp_path.iterdir())]
    geometries: dict[int, set] = {}
    for bucket in bucket_by_length(per):
        p = K.pack_edge_lists([per[j] for j in bucket])
        geometries.setdefault(p["T"], set()).add(p["E"])
    assert geometries and all(len(es) <= 2 for es in geometries.values())
