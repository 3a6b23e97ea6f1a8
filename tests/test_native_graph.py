"""Differential tests: C++ graph kernels (native/graph_algo.cc via
ctypes) vs the pure-Python Tarjan/BFS oracles. Skipped when no toolchain
can build the library."""

import random

import pytest

from jepsen_tpu import native_lib
from jepsen_tpu.checker.elle import graph as G

pytestmark = pytest.mark.skipif(
    not native_lib.available(), reason="native graph lib not buildable")


def partition(scc_ids):
    comps = {}
    for i, c in enumerate(scc_ids):
        comps.setdefault(c, set()).add(i)
    return sorted(sorted(c) for c in comps.values())


def py_reach(adj, s, t):
    if s == t:
        return True
    seen, q = {s}, [s]
    while q:
        v = q.pop()
        for w in adj[v]:
            if w == t:
                return True
            if w not in seen:
                seen.add(w)
                q.append(w)
    return False


@pytest.mark.parametrize("seed", range(10))
def test_scc_matches_python(seed):
    rng = random.Random(seed)
    n = rng.randint(1, 500)
    adj = [[] for _ in range(n)]
    for _ in range(int(n * rng.uniform(0.3, 3))):
        adj[rng.randrange(n)].append(rng.randrange(n))
    assert partition(native_lib.tarjan_scc(n, adj)) == \
        partition(G._tarjan_scc_py(n, adj))


def test_scc_chain_and_cycle():
    # 0->1->2->0 cycle plus 3->4 chain
    adj = [[1], [2], [0], [4], []]
    ids = native_lib.tarjan_scc(5, adj)
    assert ids[0] == ids[1] == ids[2]
    assert len({ids[0], ids[3], ids[4]}) == 3


@pytest.mark.parametrize("seed", range(5))
def test_reach_matches_python(seed):
    rng = random.Random(100 + seed)
    n = rng.randint(2, 300)
    adj = [[] for _ in range(n)]
    for _ in range(int(n * rng.uniform(0.3, 2))):
        adj[rng.randrange(n)].append(rng.randrange(n))
    queries = [(rng.randrange(n), rng.randrange(n)) for _ in range(50)]
    got = native_lib.reach(n, adj, queries)
    assert got == [py_reach(adj, s, t) for s, t in queries]


def test_reach_empty_and_self():
    assert native_lib.reach(3, [[], [], []], []) == []
    assert native_lib.reach(3, [[], [], []], [(1, 1)]) == [True]
    assert native_lib.reach(3, [[1], [], []], [(0, 2)]) == [False]


def test_dispatcher_uses_native_above_threshold():
    n = G.NATIVE_SCC_THRESHOLD + 10
    adj = [[(i + 1) % n] for i in range(n)]  # one big ring
    ids = G.tarjan_scc(n, adj)
    assert len(set(ids)) == 1  # single SCC


@pytest.mark.parametrize("seed", range(6))
def test_classify_batch_reach_parity(seed):
    """classify_cycles without witnesses (>=64 rw edges routes probes
    through the native batch-reach kernel) must flag the same anomalies
    as the witness path (pure-Python per-edge BFS)."""
    rng = random.Random(200 + seed)
    n = 160
    edges = []
    # ww backbone chain + random wr edges + >=64 rw edges
    for i in range(n - 1):
        if rng.random() < 0.5:
            edges.append((i, i + 1, G.WW))
    for _ in range(40):
        edges.append((rng.randrange(n), rng.randrange(n), G.WR))
    for _ in range(80):
        edges.append((rng.randrange(n), rng.randrange(n), G.RW))
    flags = G.classify_cycles(n, edges, want_witnesses=False)
    witnessed = G.classify_cycles(n, edges, want_witnesses=True)
    assert set(flags) == set(witnessed)


def test_out_of_range_edges_fall_back_to_python():
    # Native wrappers refuse graphs with out-of-range column indices so
    # a buggy analyzer gets Python's IndexError, not a segfault.
    adj = [[5], []]  # node 5 doesn't exist
    assert native_lib.tarjan_scc(2, adj) is None
    assert native_lib.reach(2, adj, [(0, 1)]) is None
    with pytest.raises(IndexError):
        G._tarjan_scc_py(2, adj)


def test_failed_rebuild_never_loads_stale_lib(tmp_path, monkeypatch):
    # a lib older than its source whose rebuild fails must not load:
    # the copy on disk no longer matches the committed source
    import os
    import shutil
    src = tmp_path / "graph_algo.cc"
    so = tmp_path / "libjepsen_graph.so"
    built = native_lib._NATIVE_DIR / "build" / "libjepsen_graph.so"
    if not built.exists():
        assert native_lib.lib() is not None
    shutil.copy(built, so)
    src.write_text("// newer than the lib\n")
    os.utime(so, (1, 1))
    monkeypatch.delenv("JEPSEN_TPU_NO_NATIVE", raising=False)
    monkeypatch.setattr(native_lib, "_compile_so", lambda s, o: False)
    assert native_lib._load_so(src, so) is None
    # an up-to-date lib still loads without a rebuild
    os.utime(src, (1, 1))
    os.utime(so, None)
    assert native_lib._load_so(src, so) is not None
