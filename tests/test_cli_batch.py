"""Tests for test-all, analyze-store (the batch device path), and the
linear.svg failure renderer."""

import json

import pytest

from jepsen_tpu import checker as c
from jepsen_tpu import cli
from jepsen_tpu.checker import models
from jepsen_tpu.checker.elle.synth import synth_append_history
from jepsen_tpu.history import history_to_edn
from jepsen_tpu.store import Store


def make_run(store: Store, name: str, ts: str, hist):
    d = store.base / name / ts
    d.mkdir(parents=True)
    (d / "history.edn").write_text(history_to_edn(hist))
    return d


def test_analyze_store_batch(tmp_path, capsys):
    store = Store(tmp_path / "store")
    good = synth_append_history(T=60, K=6, seed=1)
    bad = synth_append_history(T=60, K=6, seed=2, g1c=True)
    d1 = make_run(store, "etcd", "20200101T000000", good)
    d2 = make_run(store, "etcd", "20200101T000001", bad)
    rc = cli.analyze_store(store, checker="append")
    assert rc == 1  # one invalid run
    res1 = json.loads((d1 / "results.json").read_text())
    res2 = json.loads((d2 / "results.json").read_text())
    assert res1["valid?"] is True
    assert res2["valid?"] is False
    assert "G1c" in res2["anomaly-types"]
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2


def test_analyze_store_name_filter_and_empty(tmp_path):
    store = Store(tmp_path / "store")
    assert cli.analyze_store(store) == 254
    make_run(store, "a", "20200101T000000", synth_append_history(20, 4, 1))
    assert cli.analyze_store(store, name="nope") == 254
    assert cli.analyze_store(store, name="a") == 0


def test_analyze_store_stored_checker(tmp_path):
    store = Store(tmp_path / "store")
    hist = [{"type": "invoke", "process": 0, "f": "read", "value": None},
            {"type": "ok", "process": 0, "f": "read", "value": 1}]
    d = make_run(store, "x", "20200101T000000", hist)
    (d / "test.json").write_text(json.dumps({"name": "x"}))
    rc = cli.analyze_store(store, checker="stored")
    # no stored checker object -> unbridled optimism -> valid
    assert rc == 0


def test_test_all_subcommand(tmp_path, capsys):
    from jepsen_tpu import db as jdb, net as jnet, workloads
    from jepsen_tpu import generator as gen

    def one(tmap, args, valid=True):
        db, client = workloads.atom_fixtures()
        return {
            "name": "t-valid" if valid else "t-invalid",
            "nodes": ["n1"], "concurrency": 2,
            "ssh": {"dummy": True}, "net": jnet.noop(),
            "db": db, "client": client,
            "store": Store(tmp_path / "store"),
            "generator": gen.clients(gen.limit(
                20, gen.repeat_gen({"f": "read"}))),
            "checker": c.linearizable(
                models.cas_register(0 if valid else 99)),
        }

    rc = cli.run_cli(
        lambda tmap, args: one(tmap, args),
        tests_fn=lambda tmap, args: [one(tmap, args, True),
                                     one(tmap, args, False)],
        argv=["test-all", "--store", str(tmp_path / "store")])
    assert rc == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    byname = {ln["name"]: ln for ln in lines}
    assert byname["t-valid"]["valid?"] is True
    assert byname["t-invalid"]["valid?"] is False


def test_linear_svg_rendered_on_failure(tmp_path):
    store = Store(tmp_path / "store")
    test = {"name": "lin", "store": store}
    hist = [
        {"type": "invoke", "process": 0, "f": "read", "value": None,
         "time": 0},
        {"type": "ok", "process": 0, "f": "read", "value": 5, "time": 10},
    ]
    res = c.linearizable(models.cas_register(0)).check(test, hist, {})
    assert res["valid?"] is False
    svg = (store.test_dir(test) / "linear.svg").read_text()
    assert svg.startswith("<svg")
    assert "cannot linearize" in svg
    assert "read" in svg


def test_linear_svg_not_rendered_when_valid(tmp_path):
    store = Store(tmp_path / "store")
    test = {"name": "lin-ok", "store": store}
    hist = [
        {"type": "invoke", "process": 0, "f": "read", "value": None,
         "time": 0},
        {"type": "ok", "process": 0, "f": "read", "value": 0, "time": 10},
    ]
    res = c.linearizable(models.cas_register(0)).check(test, hist, {})
    assert res["valid?"] is True
    assert not (store.test_dir(test) / "linear.svg").exists()


def test_render_svg_handles_missing_fields():
    from jepsen_tpu.checker import linear_svg
    out = linear_svg.render_svg({"valid?": False}, [])
    assert out.startswith("<svg")


def test_analyze_store_wr(tmp_path):
    store = Store(tmp_path / "store")
    hist = [
        {"type": "invoke", "process": 0, "f": "txn",
         "value": [["w", 1, 1]], "time": 0},
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["w", 1, 1]], "time": 1},
        {"type": "invoke", "process": 1, "f": "txn",
         "value": [["r", 1, None]], "time": 2},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["r", 1, 1]], "time": 3},
    ]
    d = make_run(store, "wr", "20200101T000000", hist)
    rc = cli.analyze_store(store, checker="wr")
    assert rc == 0
    res = json.loads((d / "results.json").read_text())
    assert res["valid?"] is True


def test_analyze_store_wr_backend_cpu(tmp_path, monkeypatch):
    """--backend cpu routes the wr sweep through the wr module's OWN
    host analyzer (WrEncoded has edges, not append triples)."""
    monkeypatch.setenv("JEPSEN_TPU_BACKEND", "cpu")
    from jepsen_tpu.checker.elle import kernels as elle_kernels

    def boom(*a, **kw):
        raise AssertionError("device edge-batch ran under --backend cpu")

    monkeypatch.setattr(elle_kernels, "check_edge_batch", boom)
    store = Store(tmp_path / "store")
    good = [
        {"type": "invoke", "process": 0, "f": "txn",
         "value": [["w", 1, 1]], "time": 0},
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["w", 1, 1]], "time": 1},
    ]
    bad = good + [
        {"type": "invoke", "process": 1, "f": "txn",
         "value": [["r", 1, 1], ["r", 1, 2]], "time": 2},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["r", 1, 1], ["r", 1, 2]], "time": 3},
    ]
    d1 = make_run(store, "wr", "20200101T000000", good)
    d2 = make_run(store, "wr", "20200101T000001", bad)
    rc = cli.analyze_store(store, checker="wr")
    assert rc == 1
    assert json.loads((d1 / "results.json").read_text())["valid?"] is True
    res2 = json.loads((d2 / "results.json").read_text())
    assert res2["valid?"] is False
    assert "internal" in res2["anomaly-types"]


def test_analyze_store_flags_host_anomalies(tmp_path):
    """G1a (reading a failed write) has no cycle, so the device flags
    alone would miss it — the verdict must include host anomalies."""
    store = Store(tmp_path / "store")
    hist = [
        {"type": "invoke", "process": 0, "f": "txn",
         "value": [["append", 1, None]], "time": 0, "index": 0},
        {"type": "fail", "process": 0, "f": "txn",
         "value": [["append", 1, 9]], "time": 1, "index": 1},
        {"type": "invoke", "process": 1, "f": "txn",
         "value": [["r", 1, None]], "time": 2, "index": 2},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["r", 1, [9]]], "time": 3, "index": 3},
    ]
    d = make_run(store, "g1a", "20200101T000000", hist)
    rc = cli.analyze_store(store)
    res = json.loads((d / "results.json").read_text())
    assert res["valid?"] is False, res
    assert rc == 1


def test_analyze_store_unencodable_falls_back(tmp_path):
    store = Store(tmp_path / "store")
    # register-style history: not a txn workload, unencodable as append
    hist = [{"type": "invoke", "process": 0, "f": "read", "value": None},
            {"type": "ok", "process": 0, "f": "read", "value": 3}]
    d = make_run(store, "reg", "20200101T000000", hist)
    (d / "test.json").write_text(json.dumps({"name": "reg"}))
    rc = cli.analyze_store(store)
    assert rc == 0  # stored-checker fallback, not an error


def test_linear_svg_rendered_per_key_through_independent(tmp_path):
    """The per-key (independent) path must render linear.svg for failing
    keys even though Linearizable dispatches via check_batch."""
    from jepsen_tpu import independent
    store = Store(tmp_path / "store")
    test = {"name": "indep-lin", "store": store}
    kv = independent.tuple_
    h = [
        {"type": "invoke", "process": 0, "f": "read",
         "value": kv(1, None), "time": 0},
        {"type": "ok", "process": 0, "f": "read", "value": kv(1, 0),
         "time": 10},
        {"type": "invoke", "process": 1, "f": "read",
         "value": kv(2, None), "time": 20},
        {"type": "ok", "process": 1, "f": "read", "value": kv(2, 7),
         "time": 30},  # key 2 reads 7 from a 0-register: invalid
    ]
    res = independent.checker(
        c.linearizable(models.cas_register(0))).check(test, h, {})
    assert res["valid?"] is False
    d = store.test_dir(test)
    assert (d / "independent" / "2" / "linear.svg").exists()
    assert not (d / "independent" / "1" / "linear.svg").exists()
    svg = (d / "independent" / "2" / "linear.svg").read_text()
    assert "cannot linearize" in svg


def test_symlinks_only_move_forward(tmp_path):
    store = Store(tmp_path / "store")
    new = {"name": "t", "start-time": "20260101T000000"}
    old = {"name": "t", "start-time": "20200101T000000"}
    store.test_dir(new).mkdir(parents=True)
    store.test_dir(old).mkdir(parents=True)
    store.update_symlinks(new)
    store.update_symlinks(old)  # re-analysis of an old run
    assert store.latest().name == "20260101T000000"


def test_analyze_store_routes_long_histories_via_condensation(
        tmp_path, monkeypatch):
    """A run beyond the dense [T,T] limit still gets a verdict —
    through the SCC-condensation path, not a blown HBM budget."""
    import json as _json

    from jepsen_tpu import cli, parallel
    from jepsen_tpu.checker.elle import synth
    from jepsen_tpu.store import Store

    # shrink the dense limit so a small synthetic history counts as huge
    monkeypatch.setattr(parallel, "DENSE_TXN_LIMIT", 50)
    calls = []
    real = parallel.check_long_history

    def spy(enc, mesh, **kw):
        calls.append(enc.n)
        return real(enc, mesh, **kw)

    monkeypatch.setattr(parallel, "check_long_history", spy)
    store = Store(tmp_path / "store")
    hist = synth.synth_append_history(T=120, K=12, seed=3)
    d = tmp_path / "store" / "long-run" / "t0"
    d.mkdir(parents=True)
    (d / "history.jsonl").write_text(
        "\n".join(_json.dumps(o) for o in hist))

    rc = cli.analyze_store(store, checker="append")
    assert rc == 0
    # the long-history path actually ran (not the dense bucketed sweep)
    assert calls and calls[0] > 50


def test_analyze_store_register_batch(tmp_path):
    """--checker register: every key of every stored run in one tiered
    linearizability sweep, regrouped per run (BASELINE config #1's
    etcd-shaped batch)."""
    from jepsen_tpu import independent
    kv = independent.tuple_

    def reg_hist(bad_key=None):
        hist = []
        for k in ("a", "b"):
            seq = [("write", 1), ("read", 1), ("cas", [1, 2]),
                   ("read", 2)]
            if k == bad_key:
                seq[-1] = ("read", 3)  # value never written
            for f, v in seq:
                hist.append({"type": "invoke", "process": 0, "f": f,
                             "value": kv(k, None if f == "read" else v)})
                hist.append({"type": "ok", "process": 0, "f": f,
                             "value": kv(k, v)})
        return [{**o, "index": i, "time": i * 1000}
                for i, o in enumerate(hist)]

    store = Store(tmp_path / "store")
    d1 = make_run(store, "etcd", "20200101T000000", reg_hist())
    d2 = make_run(store, "etcd", "20200101T000001", reg_hist("b"))
    rc = cli.analyze_store(store, checker="register")
    assert rc == 1
    r1 = json.loads((d1 / "results.json").read_text())
    r2 = json.loads((d2 / "results.json").read_text())
    assert r1["valid?"] is True and r1["key-count"] == 2
    assert r2["valid?"] is False
    assert r2["failures"] == ["b"]
    assert r2["results"]["a"]["valid?"] is True


def test_relift_history_heuristics():
    from jepsen_tpu import independent
    kv = independent.tuple_
    # lifted history round-tripped to plain lists -> re-lifted
    lifted = [
        {"type": "invoke", "process": 0, "f": "write", "value": ["a", 1]},
        {"type": "ok", "process": 0, "f": "write", "value": ["a", 1]},
        {"type": "invoke", "process": 0, "f": "read", "value": ["a", None]},
        {"type": "ok", "process": 0, "f": "read", "value": ["a", 1]},
    ]
    out = independent.relift_history(lifted)
    assert all(independent.is_tuple(o["value"]) for o in out)
    # plain cas-register history: scalar read values -> untouched
    plain = [
        {"type": "invoke", "process": 0, "f": "cas", "value": [1, 2]},
        {"type": "ok", "process": 0, "f": "cas", "value": [1, 2]},
        {"type": "invoke", "process": 0, "f": "read", "value": None},
        {"type": "ok", "process": 0, "f": "read", "value": 2},
    ]
    assert independent.relift_history(plain) == plain
    # already-lifted histories pass through unchanged
    native = [{"type": "ok", "process": 0, "f": "read",
               "value": kv("a", 1)}]
    assert independent.relift_history(native) == native


def test_analyze_store_register_isolates_malformed_run(tmp_path):
    """A run with unhashable register values must not sink the sweep:
    its keys degrade to unknown while sibling runs still verify."""
    from jepsen_tpu import independent
    kv = independent.tuple_

    def ok_hist():
        hist = []
        for f, v in [("write", 1), ("read", 1)]:
            hist.append({"type": "invoke", "process": 0, "f": f,
                         "value": kv("a", None if f == "read" else v)})
            hist.append({"type": "ok", "process": 0, "f": f,
                         "value": kv("a", v)})
        return [{**o, "index": i, "time": i * 1000}
                for i, o in enumerate(hist)]

    bad_hist = [
        {"type": "invoke", "process": 0, "f": "write",
         "value": {"un": "hashable"}, "time": 0, "index": 0},
        {"type": "ok", "process": 0, "f": "write",
         "value": {"un": "hashable"}, "time": 1, "index": 1},
        {"type": "invoke", "process": 0, "f": "read", "value": None,
         "time": 2, "index": 2},
        {"type": "ok", "process": 0, "f": "read",
         "value": {"un": "hashable"}, "time": 3, "index": 3},
    ]
    store = Store(tmp_path / "store")
    d1 = make_run(store, "etcd", "20200101T000000", ok_hist())
    d2 = store.base / "etcd" / "20200101T000001"
    d2.mkdir(parents=True)
    import json as _json
    with open(d2 / "history.jsonl", "w") as f:
        for o in bad_hist:
            f.write(_json.dumps(o) + "\n")
    rc = cli.analyze_store(store, checker="register")
    r1 = json.loads((d1 / "results.json").read_text())
    assert r1["valid?"] is True
    r2 = json.loads((d2 / "results.json").read_text())
    assert r2["valid?"] in ("unknown", False)
    assert rc in (1, 2)


def test_analyze_store_register_declined_relift_falls_back(tmp_path):
    """A lifted register run whose reads all crashed can't be re-lifted
    (no ok read) — it must go to the stored checker, not be checked as
    ONE register full of [k v] pairs."""
    hist = [
        {"type": "invoke", "process": 0, "f": "write", "value": [1, 3],
         "time": 0, "index": 0},
        {"type": "ok", "process": 0, "f": "write", "value": [1, 3],
         "time": 1, "index": 1},
        {"type": "invoke", "process": 1, "f": "read", "value": [1, None],
         "time": 2, "index": 2},
        {"type": "info", "process": 1, "f": "read", "value": None,
         "time": 3, "index": 3},
    ]
    store = Store(tmp_path / "store")
    d = make_run(store, "etcd", "20200101T000000", hist)
    (d / "test.json").write_text(json.dumps({"name": "etcd"}))
    rc = cli.analyze_store(store, checker="register")
    # stored fallback (no stored checker object -> trivially valid);
    # the point is it did NOT produce a keyless register verdict
    assert rc == 0
    if (d / "results.json").exists():  # written by the stored analyze
        res = json.loads((d / "results.json").read_text())
        assert "key-count" not in res


def drop_journal_lines(store: Store, run_dir, checker=None):
    """Simulate an interrupted sweep for one run: a sweep killed before
    verdicting `run_dir` would never have journaled it, so tests that
    strip its results.json/.sweep-* markers must strip its
    verdicts.jsonl lines too."""
    import os
    j = store.base / "verdicts.jsonl"
    if not j.exists():
        return
    rel = os.path.relpath(run_dir, store.base)
    keep = []
    for ln in j.read_text().splitlines():
        try:
            e = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if e.get("dir") == rel and (checker is None
                                    or e.get("checker") == checker):
            continue
        keep.append(ln)
    j.write_text("".join(k + "\n" for k in keep))


def test_analyze_store_resume_skips_verdicted_runs(tmp_path, capsys):
    store = Store(tmp_path / "store")
    d1 = make_run(store, "etcd", "20200101T000000",
                  synth_append_history(T=40, K=4, seed=1))
    d2 = make_run(store, "etcd", "20200101T000001",
                  synth_append_history(T=40, K=4, seed=2))
    assert cli.analyze_store(store, checker="append") == 0
    capsys.readouterr()
    stamp1 = (d1 / "results.json").stat().st_mtime_ns
    # make d2 look un-verdicted (an interrupted run has neither the
    # results.json nor the sidecar — the sidecar lands last — nor its
    # verdict-journal lines)
    (d2 / "results.json").unlink()
    (d2 / ".sweep-append").unlink()
    drop_journal_lines(store, d2)
    assert cli.analyze_store(store, checker="append", resume=True) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["dir"] for ln in lines] == [str(d2)]
    assert (d1 / "results.json").stat().st_mtime_ns == stamp1
    assert (d2 / "results.json").exists()
    # everything verdicted for THIS checker: success, nothing to do
    assert cli.analyze_store(store, checker="append", resume=True) == 0
    # a different checker's sweep is NOT masked by append's markers
    capsys.readouterr()
    assert cli.analyze_store(store, checker="wr", resume=True) in (0, 1, 2)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2  # both runs re-checked under wr
    # ...and, once done (here via the stored fallback's sidecar), a
    # resumed wr sweep is complete
    assert (d1 / ".sweep-wr").exists()
    assert cli.analyze_store(store, checker="wr", resume=True) == 0
    # a truncated/absent marker means the run is redone, not skipped
    (d2 / "results.json").write_text("{truncated")
    (d2 / ".sweep-wr").unlink()
    drop_journal_lines(store, d2, "wr")
    capsys.readouterr()
    assert cli.analyze_store(store, checker="wr", resume=True) in (0, 1, 2)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["dir"] for ln in lines] == [str(d2)]


def test_wr_sweep_interrupted_mid_stream_resumes_from_chunk(
        tmp_path, capsys, monkeypatch):
    """Streaming wr sweep persists verdicts PER CHUNK: a crash after
    chunk 1 leaves its results on disk, and --resume re-checks only
    the unfinished remainder."""
    from jepsen_tpu import ingest
    from jepsen_tpu.checker.elle import kernels as elle_kernels

    def wr_hist(seed):
        txns = [(0, [["w", "x", seed * 10 + 1]]),
                (1, [["r", "x", seed * 10 + 1]])]
        out = []
        for p, txn in txns:
            for ty in ("invoke", "ok"):
                out.append({"type": ty, "process": p, "f": "txn",
                            "value": txn, "index": len(out),
                            "time": len(out) * 1000})
        return out

    store = Store(tmp_path / "store")
    dirs = [make_run(store, "pg", f"2026073{i}T000000", wr_hist(i))
            for i in range(4)]
    # chunks of 2; the second chunk's device dispatch dies
    def two_chunks(rd, checker="wr", **kw):
        rd = list(rd)
        for part in (rd[:2], rd[2:]):
            yield list(zip(part, ingest.parallel_encode(
                part, checker=checker)))

    monkeypatch.setattr(ingest, "iter_encode_chunks", two_chunks)
    calls = {"n": 0}
    orig = elle_kernels.check_edge_batch_bucketed

    def dying(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("interrupted mid-sweep")
        return orig(*a, **kw)

    monkeypatch.setattr(elle_kernels, "check_edge_batch_bucketed",
                        dying)
    monkeypatch.delenv("JEPSEN_TPU_BACKEND", raising=False)
    with pytest.raises(RuntimeError):
        cli.analyze_store(store, checker="wr")
    # chunk 1's verdicts survived the crash
    assert (dirs[0] / ".sweep-wr").exists()
    assert (dirs[1] / ".sweep-wr").exists()
    assert not (dirs[2] / ".sweep-wr").exists()
    capsys.readouterr()
    # resume: only the unfinished half is re-checked
    monkeypatch.setattr(elle_kernels, "check_edge_batch_bucketed", orig)
    rc = cli.analyze_store(store, checker="wr", resume=True)
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["dir"] for ln in lines] == [str(dirs[2]), str(dirs[3])]
    assert all((d / ".sweep-wr").exists() for d in dirs)


def test_stored_fallback_sidecar_records_validity(tmp_path, capsys):
    """ADVICE r3: a stored-fallback run writes no results.json, so its
    `.sweep-<checker>` sidecar must carry the verdict's validity —
    otherwise an invalid verdict from the completed part of an
    interrupted sweep reads as exit code 0 on --resume."""
    from jepsen_tpu.cli import _prior_code, _stored_fallback
    rc = _stored_fallback(tmp_path, lambda d: {"valid?": False}, "stored")
    assert rc == 1
    assert not (tmp_path / "results.json").exists()
    assert _prior_code(tmp_path, "stored") == 1
    rc = _stored_fallback(tmp_path, lambda d: {"valid?": "unknown"},
                          "stored")
    assert rc == 2
    assert _prior_code(tmp_path, "stored") == 2
    # legacy empty sidecar (pre-upgrade stores) still counts as done=ok
    (tmp_path / ".sweep-stored").write_text("")
    assert _prior_code(tmp_path, "stored") == 0
    # a later sweep by a DIFFERENT checker rewrites results.json; this
    # sweep's sidecar must still win (cross-checker masking)
    _stored_fallback(tmp_path, lambda d: {"valid?": False}, "stored")
    (tmp_path / "results.json").write_text(
        json.dumps({"valid?": True, "checker": "append"}))
    assert _prior_code(tmp_path, "stored") == 1
    capsys.readouterr()


def test_init_distributed_gating(monkeypatch):
    from jepsen_tpu import parallel
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("COORDINATOR_ADDRESS", raising=False)
    assert parallel.init_distributed() is False  # single-process: no-op
    called = {}
    import jax as _jax
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    monkeypatch.setattr(_jax.distributed, "initialize",
                        lambda **kw: called.update(kw))
    assert parallel.init_distributed() is True
    assert called == {"coordinator_address": "10.0.0.1:1234",
                      "num_processes": 4, "process_id": 2}


def test_analyze_store_stored_resume(tmp_path, capsys):
    """stored sweeps mark progress via the sidecar only — a run's
    pre-existing results.json (from its original invocation) must not
    count as 'this sweep already visited it'."""
    store = Store(tmp_path / "store")
    hist = [{"type": "invoke", "process": 0, "f": "read", "value": None},
            {"type": "ok", "process": 0, "f": "read", "value": 1}]
    d = make_run(store, "x", "20200101T000000", hist)
    (d / "test.json").write_text(json.dumps({"name": "x"}))
    # simulate the run's own analyze having written results already
    (d / "results.json").write_text(json.dumps({"valid?": True}))
    capsys.readouterr()
    rc = cli.analyze_store(store, checker="stored", resume=True)
    assert rc == 0
    out = capsys.readouterr()
    assert "nothing to resume" not in out.err  # it DID re-check
    assert (d / ".sweep-stored").exists()
    # now the sweep is recorded: resume has nothing left
    rc = cli.analyze_store(store, checker="stored", resume=True)
    assert rc == 0
    assert "nothing to resume" in capsys.readouterr().err


def test_analyze_store_leaves_environ_alone(tmp_path, monkeypatch):
    """The accelerator-probe pipelining decision flows to
    iter_encode_chunks via its `processes` parameter, NOT by mutating
    os.environ for the rest of the process."""
    import os
    from jepsen_tpu import devices as devmod, ingest

    monkeypatch.delenv("JEPSEN_TPU_PIPELINE", raising=False)
    monkeypatch.setattr(devmod, "accelerator_available", lambda: True)
    seen = {}
    real = ingest.iter_encode_chunks

    def spy(run_dirs, checker="append", chunk=64, processes=None,
            info=None):
        seen["processes"] = processes
        return real(run_dirs, checker=checker, chunk=chunk,
                    processes=0, info=info)   # serial: keep test fast

    monkeypatch.setattr(ingest, "iter_encode_chunks", spy)
    store = Store(tmp_path / "store")
    make_run(store, "e", "20200101T000000",
             synth_append_history(T=30, K=4, seed=3))
    assert cli.analyze_store(store, checker="append") == 0
    assert seen["processes"] == max(1, os.cpu_count() or 1)
    assert "JEPSEN_TPU_PIPELINE" not in os.environ
