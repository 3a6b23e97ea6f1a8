"""Second wave of per-DB suites (galera, percona, mysql-cluster, crate,
elasticsearch, raftis): dummy-remote lifecycle smoke + end-to-end runs
against the protocol fakes."""

from __future__ import annotations

import pytest

from jepsen_tpu import core, net as jnet
from jepsen_tpu.store import Store
from jepsen_tpu.suites import (crate, elasticsearch, galera,
                               mysql_cluster, percona, raftis)

from fake_misc import FakeESServer, FakeRedisServer
from fake_sql import FakeMySQLServer, FakePGServer


def hosts_for(srv):
    return {n: ("127.0.0.1", srv.port)
            for n in ("n1", "n2", "n3", "n4", "n5")}


@pytest.mark.parametrize("make_test,needle", [
    (galera.galera_test, "galera"),
    (percona.percona_test, "percona"),
    (mysql_cluster.mysql_cluster_test, "ndb"),
    (crate.crate_test, "crate"),
    (elasticsearch.elasticsearch_test, "elasticsearch"),
    (raftis.raftis_test, "raftis"),
])
def test_db_setup_against_dummy_remote(make_test, needle):
    from jepsen_tpu import control
    test = make_test({"ssh": {"dummy": True}})
    control.on_nodes(test, lambda t, n: t["db"].setup(t, n))
    cmds = "\n".join(str(p) for _n, kind, p in test["remote"].actions
                     if kind == "execute")
    assert needle in cmds


def run_suite(tmp_path, make_test, srv, opts=None):
    test = make_test({
        "ssh": {"dummy": True}, "time-limit": 1.0,
        "db-hosts": hosts_for(srv), **(opts or {}),
    })
    for k in ("db", "os", "nemesis"):
        test.pop(k, None)
    test["net"] = jnet.noop()
    test["store"] = Store(tmp_path / "store")
    return core.run(test)


def test_raftis_register_end_to_end(tmp_path):
    with FakeRedisServer() as srv:
        test = run_suite(tmp_path, raftis.raftis_test, srv)
    assert test["results"]["valid?"] is True


def test_elasticsearch_set_end_to_end(tmp_path):
    # the 1 s time limit covers the final read too: a set small enough
    # to add well inside it keeps a loaded test box from cutting the
    # read ("Set was never read")
    with FakeESServer() as srv:
        test = run_suite(tmp_path, elasticsearch.elasticsearch_test, srv,
                         {"set-size": 100})
    r = test["results"]
    assert r["valid?"] is True, r
    assert r["set"]["ok-count"] > 10


def test_crate_register_end_to_end(tmp_path):
    with FakePGServer() as srv:
        test = run_suite(tmp_path, crate.crate_test, srv,
                         {"workload": "register"})
    assert test["results"]["valid?"] is True


@pytest.mark.parametrize("make_test", [
    galera.galera_test, percona.percona_test,
    mysql_cluster.mysql_cluster_test,
])
def test_mysql_family_bank_end_to_end(tmp_path, make_test):
    with FakeMySQLServer() as srv:
        test = run_suite(tmp_path, make_test, srv, {"workload": "bank"})
    r = test["results"]
    assert r["valid?"] is True, r
    assert r["bank"]["read-count"] > 0


# ---------------------------------------------------------------------
# crate version-divergence (version_divergence.clj) + lost-updates
# (lost_updates.clj)
# ---------------------------------------------------------------------

def test_crate_version_divergence_end_to_end(tmp_path):
    with FakePGServer() as srv:
        test = run_suite(tmp_path, crate.crate_test, srv,
                         {"workload": "version-divergence",
                          "keys-concurrent": 4, "readers": 2})
    r = test["results"]
    assert r["valid?"] is True, r
    # at least one key actually observed versioned reads
    assert any(v.get("version-count", 0) > 0
               for v in r["results"].values())


def test_crate_lost_updates_end_to_end(tmp_path):
    with FakePGServer() as srv:
        # key-count bounded so every key finishes its adds+quiesce+read
        # phase inside the outer time limit (a cut-off key's set is
        # never read -> unknown, the reference's behavior too)
        test = run_suite(tmp_path, crate.crate_test, srv,
                         {"workload": "lost-updates", "time-limit": 3.0,
                          "quiesce": 0.5, "keys-concurrent": 4,
                          "key-count": 2})
    r = test["results"]
    assert r["valid?"] is True, r
    # the serializable fake must never lose an acked add
    assert any(v.get("ok-count", 0) > 0 for v in r["results"].values())


def test_multiversion_checker_detects_divergence():
    c = crate.MultiVersionChecker()
    ok = [{"type": "ok", "f": "read",
           "value": {"value": 5, "version": 1}},
          {"type": "ok", "f": "read",
           "value": {"value": 6, "version": 2}}]
    assert c.check({}, ok, {})["valid?"] is True
    # same _version serving two different values: divergence
    bad = ok + [{"type": "ok", "f": "read",
                 "value": {"value": 99, "version": 2}}]
    res = c.check({}, bad, {})
    assert res["valid?"] is False
    assert res["multis"] == {2: [6, 99]}
    # unread rows (value None) don't count
    none = [{"type": "ok", "f": "read", "value": None}]
    assert c.check({}, none, {})["valid?"] is True


def test_crate_lost_updates_client_cas(tmp_path):
    """The add path's optimistic `AND _version = ?` guard: a version
    that moved between read and update is a definite fail, and the
    final read returns every acked element (lost_updates.clj:73-98)."""
    from jepsen_tpu import independent
    with FakePGServer() as srv:
        test = {"db-hosts": hosts_for(srv)}
        a = crate.CrateClient("lost-updates").open(test, "n1")
        b = crate.CrateClient("lost-updates").open(test, "n1")
        kv = lambda v: {"type": "invoke", "f": "add", "process": 0,
                        "value": independent.tuple_(7, v)}
        assert a.invoke(test, kv(1))["type"] == "ok"     # insert
        assert b.invoke(test, kv(2))["type"] == "ok"     # rmw update
        r = a.invoke(test, {"type": "invoke", "f": "read", "process": 0,
                            "value": independent.tuple_(7, None)})
        assert r["type"] == "ok" and r["value"].value == [1, 2]

        # stale-version CAS: read current version, bump it via the
        # other client, then watch the guarded update fail
        rows = crate.sql._rows(a.conn.query(
            'SELECT elements, "_version" FROM lu_sets WHERE id = 7'))
        ver = int(rows[0][1])
        assert b.invoke(test, kv(3))["type"] == "ok"     # version moves
        res = a.conn.query(
            f"UPDATE lu_sets SET elements = '9' "
            f"WHERE id = 7 AND _version = {ver}")
        assert crate._rowcount(res) == 0                 # CAS lost
        a.close(test)
        b.close(test)


def test_crate_workload_registry_has_reference_families():
    wls = crate.workloads({})
    assert {"version-divergence", "lost-updates", "register", "set",
            "wr", "monotonic", "long-fork"} <= set(wls)


# ---------------------------------------------------------------------
# galera / percona dirty-reads (galera/dirty_reads.clj:1-120 and its
# percona twin)
# ---------------------------------------------------------------------

def test_dirty_reads_checker_verdicts():
    from jepsen_tpu.workloads import dirty_reads
    c = dirty_reads.DirtyReadsChecker()
    hist = [
        {"type": "ok", "f": "write", "value": 1},
        {"type": "fail", "f": "write", "value": 2},
        {"type": "ok", "f": "read", "value": [1, 1, 1]},
    ]
    good = c.check({}, hist, {})
    assert good["valid?"] is True and good["failed-write-count"] == 1

    # a reader observed failed txn 2's value: dirty read, must fail
    bad = hist + [{"type": "ok", "f": "read", "value": [1, 2, 1]}]
    res = c.check({}, bad, {})
    assert res["valid?"] is False
    assert res["dirty-count"] == 1
    # that read is also internally inconsistent (fractured)
    assert res["inconsistent-count"] == 1

    # info writes are indeterminate — observing them is NOT dirty
    maybe = hist + [{"type": "info", "f": "write", "value": 3},
                    {"type": "ok", "f": "read", "value": [3, 3, 3]}]
    assert c.check({}, maybe, {})["valid?"] is True


def test_dirty_reads_client_ops():
    from jepsen_tpu.suites import sql
    with FakeMySQLServer() as srv:
        test = {"db-hosts": hosts_for(srv)}
        mk = lambda p: sql.client_for(
            sql.MySQLDialect(port=3306, user="root", database="test"),
            "dirty-reads", {"sql-opts": {"abort_prob": p}}
        ).open(test, "n1")
        c = mk(0.0)
        w = c.invoke(test, {"type": "invoke", "f": "write", "value": 7})
        assert w["type"] == "ok"
        r = c.invoke(test, {"type": "invoke", "f": "read", "value": None})
        assert r["type"] == "ok" and r["value"] == [7] * 8

        # deliberate abort: the write must fail AND leave no trace
        a = mk(1.0)
        w2 = a.invoke(test, {"type": "invoke", "f": "write", "value": 9})
        assert w2["type"] == "fail" and w2["error"] == "deliberate-abort"
        r2 = c.invoke(test, {"type": "invoke", "f": "read", "value": None})
        assert r2["type"] == "ok" and r2["value"] == [7] * 8
        c.close(test)
        a.close(test)


@pytest.mark.parametrize("make_test", [
    galera.galera_test, percona.percona_test,
])
def test_dirty_reads_end_to_end(tmp_path, make_test):
    with FakeMySQLServer() as srv:
        test = run_suite(tmp_path, make_test, srv,
                         {"workload": "dirty-reads", "time-limit": 1.5,
                          "sql-opts": {"abort_prob": 1.0}})
    r = test["results"]["dirty-reads"]
    # every write deliberately aborts; the serializable fake rolls them
    # back, so readers only ever see the -1 seed — no dirty reads
    assert r["valid?"] is True, r
    assert r["failed-write-count"] > 0
    assert r["read-count"] > 0


def test_dirty_reads_in_both_registries():
    assert "dirty-reads" in galera.workloads({})
    assert "dirty-reads" in percona.workloads({})


# ---------------------------------------------------------------------
# elasticsearch dirty-read (dirty_read.clj)
# ---------------------------------------------------------------------

def test_es_dirty_read_checker_verdicts():
    c = elasticsearch.DirtyReadChecker()

    def h(writes, reads, strongs):
        out = [{"type": "ok", "f": "write", "value": v} for v in writes]
        out += [{"type": "ok", "f": "read", "value": v} for v in reads]
        out += [{"type": "ok", "f": "strong-read", "value": list(s)}
                for s in strongs]
        return out

    good = c.check({}, h([0, 1], [0], [{0, 1}, {0, 1}]), {})
    assert good["valid?"] is True and good["nodes-agree?"] is True

    # dirty: read 2 observed, but 2 is in NO strong read (uncommitted)
    dirty = c.check({}, h([0, 1], [0, 2], [{0, 1}, {0, 1}]), {})
    assert dirty["valid?"] is False and dirty["dirty"] == [2]

    # lost: write 1 acked, absent from every strong read
    lost = c.check({}, h([0, 1], [0], [{0}, {0}]), {})
    assert lost["valid?"] is False and lost["lost"] == [1]

    # divergent nodes: strong reads disagree
    div = c.check({}, h([0, 1], [0], [{0, 1}, {0}]), {})
    assert div["valid?"] is False and div["nodes-agree?"] is False
    assert div["not-on-all"] == [1] and div["some-lost"] == [1]

    unknown = c.check({}, h([0], [0], []), {})
    assert unknown["valid?"] == "unknown"


def test_es_rw_gen_shapes():
    from jepsen_tpu import generator as gen
    test = {"concurrency": 6, "nodes": ["n1", "n2", "n3"]}
    g = elasticsearch.RWGen(2)
    ctx = gen.Context.for_test(test)
    writes, reads = [], []
    busy = []
    for i in range(12):
        if len(busy) == len(test["nodes"]) * 2:   # all 6 threads busy:
            for t in busy:                        # complete them all
                ctx = ctx.free(t)
            busy = []
        res = gen.op(g, test, ctx)
        assert res is not None
        op_, g = res
        assert op_ is not gen.PENDING
        thread = ctx.process_to_thread(op_["process"])
        ctx = ctx.with_time(op_["time"]).busy(thread)
        busy.append(thread)
        g = gen.update(g, test, ctx, op_)
        (writes if op_["f"] == "write" else reads).append(op_)
    assert writes and reads
    # writers produce strictly ascending unique values
    vals = [o["value"] for o in writes]
    assert vals == sorted(set(vals))
    # readers chase their node's in-flight write
    assert all(isinstance(o["value"], int) for o in reads)


def test_es_rw_gen_tracks_node_by_thread_after_crash():
    """Crashed processes retire to p + concurrency, but clients stay
    bound to nodes by THREAD — the in-flight vector must follow the
    thread's node, not (raw process) % n_nodes."""
    from jepsen_tpu import generator as gen
    test = {"concurrency": 5, "nodes": ["n1", "n2", "n3"]}
    g = elasticsearch.RWGen(2)
    ctx = gen.Context.for_test(test)
    # thread 1 crashed once: its process is now 1 + 5 = 6
    ctx = ctx.with_worker(1, 6)
    ev = {"type": "invoke", "f": "write", "value": 42, "process": 6,
          "time": 0}
    g2 = gen.update(g, test, ctx, ev)
    # thread 1 runs on nodes[1 % 3] = n2 -> slot 1 (not 6 % 3 = 0)
    assert g2.in_flight == (0, 42, 0)
    # and a reader on thread 1's node chases that write
    assert g2._node_of(ctx, 6, 3) == 1


def test_es_dirty_read_client_ops(tmp_path):
    with FakeESServer() as srv:
        test = {"db-hosts": hosts_for(srv)}
        c = elasticsearch.DirtyReadClient().open(test, "n1")
        assert c.invoke(test, {"type": "invoke", "f": "write",
                               "value": 3})["type"] == "ok"
        assert c.invoke(test, {"type": "invoke", "f": "read",
                               "value": 3})["type"] == "ok"
        missing = c.invoke(test, {"type": "invoke", "f": "read",
                                  "value": 99})
        assert missing["type"] == "fail"
        assert c.invoke(test, {"type": "invoke", "f": "refresh"}
                        )["type"] == "ok"
        sr = c.invoke(test, {"type": "invoke", "f": "strong-read",
                             "value": None})
        assert sr["type"] == "ok" and sr["value"] == [3]


def test_es_dirty_read_end_to_end(tmp_path):
    with FakeESServer() as srv:
        test = run_suite(tmp_path, elasticsearch.elasticsearch_test, srv,
                         {"workload": "dirty-read", "time-limit": 2.0,
                          "quiesce": 0.2, "concurrency": 6})
    r = test["results"]
    assert r["dirty-read"]["valid?"] is True, r
    assert r["dirty-read"]["strong-read-count"] >= 1
