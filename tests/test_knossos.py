"""Linearizability engine tests: CPU WGL oracle golden cases, TPU kernel
parity (the acceptance criterion, SURVEY.md §4.3), and the independent
key-decomposition layer that feeds the batch path."""

from __future__ import annotations

import random

import pytest

from jepsen_tpu import independent
from jepsen_tpu.checker import linearizable, models
from jepsen_tpu.checker import knossos
from jepsen_tpu.checker.knossos import encode as kenc
from jepsen_tpu.checker.knossos import kernels as kker
from jepsen_tpu.checker.knossos import synth as ksynth


def op(type, process, f, value=None, **kw):
    return {"type": type, "process": process, "f": f, "value": value, **kw}


def pairs_history(*steps):
    """Build a history from (process, f, value, result-type[, result-value])
    sequential steps — each op completes before the next begins."""
    hist = []
    for s in steps:
        p, f, v, t = s[0], s[1], s[2], s[3]
        rv = s[4] if len(s) > 4 else v
        hist.append(op("invoke", p, f, v))
        hist.append(op(t, p, f, rv))
    return hist


CASR = models.cas_register()


# ---------------------------------------------------------------------------
# CPU WGL golden verdicts
# ---------------------------------------------------------------------------

class TestWGL:
    def test_empty_history_valid(self):
        assert knossos.wgl(CASR, [])["valid?"] is True

    def test_sequential_write_read_valid(self):
        h = pairs_history((0, "write", 1, "ok"), (0, "read", 1, "ok"))
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_read_wrong_value_invalid(self):
        h = pairs_history((0, "write", 1, "ok"), (0, "read", 2, "ok"))
        r = knossos.wgl(CASR, h)
        assert r["valid?"] is False
        assert "op" in r  # the op whose return the search died at

    def test_initial_nil_read_valid(self):
        h = pairs_history((0, "read", None, "ok"))
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_concurrent_writes_reorder_valid(self):
        # w1 and w2 overlap; a later read of 1 forces order w2, w1.
        h = [op("invoke", 0, "write", 1), op("invoke", 1, "write", 2),
             op("ok", 0, "write", 1), op("ok", 1, "write", 2),
             op("invoke", 2, "read"), op("ok", 2, "read", 1)]
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_sequential_writes_fix_order_invalid(self):
        # w1 completes before w2 begins; read of 1 afterwards is stale.
        h = pairs_history((0, "write", 1, "ok"), (1, "write", 2, "ok"),
                          (2, "read", 1, "ok"))
        assert knossos.wgl(CASR, h)["valid?"] is False

    def test_cas_chain_valid(self):
        h = pairs_history((0, "write", 1, "ok"), (0, "cas", [1, 2], "ok"),
                          (1, "read", 2, "ok"))
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_cas_from_wrong_value_invalid(self):
        h = pairs_history((0, "write", 1, "ok"), (0, "cas", [3, 4], "ok"))
        assert knossos.wgl(CASR, h)["valid?"] is False

    def test_info_write_may_happen(self):
        # Indeterminate write of 3; later read sees 3: the write happened.
        h = [op("invoke", 0, "write", 3), op("info", 0, "write", 3),
             op("invoke", 1, "read"), op("ok", 1, "read", 3)]
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_info_write_may_not_happen(self):
        h = [op("invoke", 0, "write", 3), op("info", 0, "write", 3),
             op("invoke", 1, "read"), op("ok", 1, "read", None)]
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_failed_write_dropped(self):
        h = [op("invoke", 0, "write", 9), op("fail", 0, "write", 9),
             op("invoke", 1, "read"), op("ok", 1, "read", None)]
        assert knossos.wgl(CASR, h)["valid?"] is True

    def test_failed_write_observed_invalid(self):
        h = [op("invoke", 0, "write", 9), op("fail", 0, "write", 9),
             op("invoke", 1, "read"), op("ok", 1, "read", 9)]
        assert knossos.wgl(CASR, h)["valid?"] is False

    def test_mutex_model(self):
        h = pairs_history((0, "acquire", None, "ok"),
                          (1, "acquire", None, "ok"))
        assert knossos.wgl(models.mutex(), h)["valid?"] is False
        h2 = pairs_history((0, "acquire", None, "ok"),
                           (0, "release", None, "ok"),
                           (1, "acquire", None, "ok"))
        assert knossos.wgl(models.mutex(), h2)["valid?"] is True

    def test_unknown_on_cache_exhaustion(self):
        h = [op("invoke", p, "write", p) for p in range(6)] + \
            [op("ok", p, "write", p) for p in range(6)]
        r = knossos.wgl(CASR, h, max_configs=2)
        assert r["valid?"] == "unknown"


# ---------------------------------------------------------------------------
# Random linearizable histories (simulated atomic register) + corruption
# ---------------------------------------------------------------------------

def random_register_history(rng: random.Random, n_ops=25, n_procs=4,
                            n_values=4, info_prob=0.08):
    """Thin adapter over the package simulator (knossos.synth) so test
    call sites can keep threading one rng."""
    return ksynth.synth_register_history(
        n_ops=n_ops, n_procs=n_procs, n_values=n_values,
        info_prob=info_prob, seed=rng.randrange(1 << 30))


def corrupt(rng: random.Random, hist):
    return ksynth.corrupt(hist, seed=rng.randrange(1 << 30))


class TestRandomHistories:
    def test_simulated_histories_are_linearizable(self):
        rng = random.Random(7)
        for _ in range(20):
            h = random_register_history(rng)
            assert knossos.wgl(CASR, h)["valid?"] is True

    def test_corrupted_histories_checked(self):
        rng = random.Random(8)
        seen_invalid = 0
        for _ in range(20):
            h = corrupt(rng, random_register_history(rng, info_prob=0.0))
            if knossos.wgl(CASR, h)["valid?"] is False:
                seen_invalid += 1
        assert seen_invalid > 5  # corruption usually detected


# ---------------------------------------------------------------------------
# TPU kernel parity (differential: kernel verdict == WGL verdict)
# ---------------------------------------------------------------------------

def kernel_verdict(h, frontier=256, packed=None):
    enc = kenc.encode_register_history(h)
    return kker.check_encoded_batch([enc], frontier=frontier,
                                    packed=packed)[0]


class TestKernelParity:
    GOLDENS = [
        (pairs_history((0, "write", 1, "ok"), (0, "read", 1, "ok")), True),
        (pairs_history((0, "write", 1, "ok"), (0, "read", 2, "ok")), False),
        (pairs_history((0, "read", None, "ok")), True),
        ([op("invoke", 0, "write", 1), op("invoke", 1, "write", 2),
          op("ok", 0, "write", 1), op("ok", 1, "write", 2),
          op("invoke", 2, "read"), op("ok", 2, "read", 1)], True),
        (pairs_history((0, "write", 1, "ok"), (1, "write", 2, "ok"),
                       (2, "read", 1, "ok")), False),
        (pairs_history((0, "write", 1, "ok"), (0, "cas", [1, 2], "ok"),
                       (1, "read", 2, "ok")), True),
        (pairs_history((0, "write", 1, "ok"), (0, "cas", [3, 4], "ok")),
         False),
        ([op("invoke", 0, "write", 3), op("info", 0, "write", 3),
          op("invoke", 1, "read"), op("ok", 1, "read", 3)], True),
        ([op("invoke", 0, "write", 3), op("info", 0, "write", 3),
          op("invoke", 1, "read"), op("ok", 1, "read", None)], True),
        ([op("invoke", 0, "write", 9), op("fail", 0, "write", 9),
          op("invoke", 1, "read"), op("ok", 1, "read", 9)], False),
    ]

    # packed=False keeps the unpacked kernel under the WGL oracle even
    # though auto-routing sends every packable batch to the packed one
    @pytest.mark.parametrize("packed", [False, None])
    def test_golden_verdicts_on_device(self, packed):
        encs = [kenc.encode_register_history(h) for h, _ in self.GOLDENS]
        results = kker.check_encoded_batch(encs, packed=packed)
        for (h, expect), r in zip(self.GOLDENS, results):
            assert r["valid?"] is expect, (h, r)

    @pytest.mark.parametrize("packed", [False, None])
    def test_differential_random(self, packed):
        rng = random.Random(99)
        hists = [random_register_history(rng, n_ops=15, n_procs=3)
                 for _ in range(8)]
        hists += [corrupt(rng, random_register_history(
            rng, n_ops=15, n_procs=3, info_prob=0.0)) for _ in range(8)]
        cpu = [knossos.wgl(CASR, h)["valid?"] for h in hists]
        tpu = [kernel_verdict(h, packed=packed)["valid?"] for h in hists]
        assert cpu == tpu

    @pytest.mark.parametrize("packed", [False, None])
    def test_overflow_degrades_to_unknown(self, packed):
        h = [op("invoke", p, "write", p) for p in range(8)] + \
            [op("ok", p, "write", p) for p in range(8)]
        r = kernel_verdict(h, frontier=4, packed=packed)
        assert r["valid?"] == "unknown"

    def test_unencodable_raises(self):
        with pytest.raises(kenc.EncodingError):
            kenc.encode_register_history(
                pairs_history((0, "enqueue", 1, "ok")))


# ---------------------------------------------------------------------------
# Linearizable checker + independent decomposition
# ---------------------------------------------------------------------------

class TestLinearizableChecker:
    def test_cpu_backend(self):
        h = pairs_history((0, "write", 1, "ok"), (0, "read", 1, "ok"))
        c = linearizable(CASR, backend="cpu")
        assert c.check({}, h, {})["valid?"] is True

    def test_tpu_backend_with_fallback(self):
        good = pairs_history((0, "write", 1, "ok"), (0, "read", 1, "ok"))
        bad = pairs_history((0, "write", 1, "ok"), (0, "read", 2, "ok"))
        weird = pairs_history((0, "enqueue", 1, "ok"))  # CPU fallback
        c = linearizable(CASR, backend="tpu")
        rs = c.check_batch({}, [good, bad, weird], {})
        assert rs[0]["valid?"] is True
        assert rs[1]["valid?"] is False
        assert rs[2]["valid?"] is False  # queue op vs cas-register model

    def test_slot_overflow_routes_to_frontier_kernel(self, monkeypatch):
        """Concurrency past the dense grid's 14-slot budget must route
        to the bounded frontier kernel, not straight to the CPU oracle
        (VERDICT r2 item 10)."""
        # 16 pending ops at once — past the dense grid — but a CAS
        # chain, so the legal interleavings (and the frontier) stay
        # small: cas[p, p+1] can only apply in chain order.
        h = [op("invoke", 50, "write", 0), op("ok", 50, "write", 0)]
        h += [op("invoke", p, "cas", [p, p + 1]) for p in range(16)]
        h += [op("ok", p, "cas", [p, p + 1]) for p in range(16)]
        h += [op("invoke", 50, "read", None), op("ok", 50, "read", 16)]
        from jepsen_tpu.checker.knossos import dense as kdense
        with pytest.raises(kenc.EncodingError):
            kdense.encode_dense_history(h)
        cpu_calls = []
        c = linearizable(CASR, backend="tpu")
        orig_cpu = c._cpu
        c._cpu = lambda hs: cpu_calls.append(1) or orig_cpu(hs)
        [r] = c.check_batch({}, [h], {})
        assert r["valid?"] is True
        assert r["analyzer"] == "tpu-jit"
        assert not cpu_calls, "frontier-eligible history went to CPU"
        # and an invalid one (read observes a value never written)
        h_bad = h[:-1] + [op("ok", 50, "read", 99)]
        [rb] = c.check_batch({}, [h_bad], {})
        assert rb["valid?"] is False
        # differential: CPU oracle agrees
        assert orig_cpu(h)["valid?"] is True
        assert orig_cpu(h_bad)["valid?"] is False

    def test_frontier_overflow_falls_back_to_cpu(self, monkeypatch):
        """A ":frontier-overflow" unknown from the frontier kernel must
        re-run on the CPU oracle — verdicts never degrade to unknown."""
        h = [op("invoke", p, "write", p) for p in range(16)]
        h += [op("ok", p, "write", p) for p in range(16)]
        orig = kker.check_encoded_batch
        monkeypatch.setattr(
            kker, "check_encoded_batch",
            lambda encs, **kw: [{"valid?": "unknown", "analyzer":
                                 "tpu-jit", "cause": ":frontier-overflow"}
                                for _ in encs])
        c = linearizable(CASR, backend="tpu")
        [r] = c.check_batch({}, [h], {})
        assert r["valid?"] is True  # exact, from the CPU re-run

    def test_independent_checker_batches(self):
        T = independent.tuple_
        h = []
        for k, val, expect_read in [("a", 1, 1), ("b", 2, 3)]:
            h.append(op("invoke", 0, "write", T(k, val)))
            h.append(op("ok", 0, "write", T(k, val)))
            h.append(op("invoke", 1, "read", T(k, None)))
            h.append(op("ok", 1, "read", T(k, expect_read)))
        c = independent.checker(linearizable(CASR, backend="tpu"))
        r = c.check({}, h, {})
        assert r["valid?"] is False
        assert r["results"]["a"]["valid?"] is True
        assert r["results"]["b"]["valid?"] is False
        assert r["failures"] == ["b"]


class TestIndependentGenerators:
    def test_tuple_helpers(self):
        t = independent.tuple_("k", 5)
        assert independent.is_tuple(t)
        assert independent.key_of(t) == "k"
        assert independent.value_of(t) == 5
        assert not independent.is_tuple(["k", 5])

    def test_sequential_generator(self):
        import jepsen_tpu.generator as g
        from gen_sim import perfect, simulate
        sg = independent.sequential_generator(
            ["x", "y"],
            lambda k: g.limit(3, lambda test, ctx:
                              {"type": "invoke", "f": "read", "value": None}))
        hist = simulate(g.clients(sg), perfect, concurrency=2)
        invokes = [o for o in hist if o["type"] == "invoke"]
        assert len(invokes) == 6
        keys = [o["value"].key for o in invokes]
        assert keys == ["x"] * 3 + ["y"] * 3

    def test_concurrent_generator(self):
        import jepsen_tpu.generator as g
        from gen_sim import perfect, simulate
        cg = independent.concurrent_generator(
            2, ["x", "y"],
            lambda k: g.limit(4, lambda test, ctx:
                              {"type": "invoke", "f": "read", "value": None}))
        hist = simulate(g.clients(cg), perfect, concurrency=4)
        invokes = [o for o in hist if o["type"] == "invoke"]
        assert len(invokes) == 8
        by_key: dict = {}
        for o in invokes:
            by_key.setdefault(o["value"].key, set()).add(o["process"] // 2)
        # each key served by exactly one thread-group
        assert all(len(gs) == 1 for gs in by_key.values())

    def test_register_workload_end_to_end(self):
        import jepsen_tpu.generator as g
        from gen_sim import perfect, simulate
        from jepsen_tpu.workloads import register as reg
        t = reg.test(threads_per_key=2, key_count=3, ops_per_key=6,
                     backend="tpu")
        hist = simulate(t["generator"], perfect, concurrency=6)
        # The perfect executor oks every op — including random cas ops,
        # which usually can't all have succeeded, so the verdict is
        # typically False. What must hold: TPU and CPU backends agree
        # per key, and every key got checked.
        r_tpu = t["checker"].check({}, hist, {})
        r_cpu = reg.checker(backend="cpu").check({}, hist, {})
        assert len(r_tpu["results"]) == 3
        assert {k: v["valid?"] for k, v in r_tpu["results"].items()} == \
               {k: v["valid?"] for k, v in r_cpu["results"].items()}


# ---------------------------------------------------------------------------
# Dense-bitset kernel (the default TPU engine): exact verdicts over the
# full configuration grid — differential vs the WGL oracle.
# ---------------------------------------------------------------------------

class TestDenseKernel:
    def test_golden_verdicts(self):
        from jepsen_tpu.checker.knossos import dense
        encs = [dense.encode_dense_history(h)
                for h, _ in TestKernelParity.GOLDENS]
        results = dense.check_encoded_dense_batch(encs)
        for (h, expect), r in zip(TestKernelParity.GOLDENS, results):
            assert r["valid?"] is expect, (h, r)
            assert r["analyzer"] == "tpu-dense"

    def test_differential_random_with_infos(self):
        from jepsen_tpu.checker.knossos import dense
        rng = random.Random(41)
        hists = [random_register_history(rng, n_ops=25, n_procs=4,
                                         info_prob=0.15)
                 for _ in range(10)]
        hists += [corrupt(rng, random_register_history(
            rng, n_ops=25, n_procs=4, info_prob=0.0)) for _ in range(10)]
        cpu = [knossos.wgl(CASR, h)["valid?"] for h in hists]
        encs = [dense.encode_dense_history(h) for h in hists]
        tpu = [r["valid?"] for r in dense.check_encoded_dense_batch(encs)]
        assert cpu == tpu

    def test_info_reads_are_dropped(self):
        from jepsen_tpu.checker.knossos import dense
        h = [op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
             op("invoke", 1, "read"), op("info", 1, "read"),
             op("invoke", 2, "read"), op("ok", 2, "read", 1)]
        e = dense.encode_dense_history(h)
        assert e.n_ops == 2          # the info read contributes no slot
        assert e.n_slots <= 2
        assert dense.check_encoded_dense_batch([e])[0]["valid?"] is True

    def test_slot_buckets_mixed_concurrency(self):
        from jepsen_tpu.checker.knossos import dense
        rng = random.Random(5)
        lo = [random_register_history(rng, n_ops=12, n_procs=2)
              for _ in range(3)]
        hi = [random_register_history(rng, n_ops=12, n_procs=6)
              for _ in range(3)]
        hists = [h for pair in zip(lo, hi) for h in pair]
        encs = [dense.encode_dense_history(h) for h in hists]
        assert len({e.n_slots for e in encs}) > 1
        res = dense.check_encoded_dense_batch(encs)
        assert [r["valid?"] for r in res] == \
               [knossos.wgl(CASR, h)["valid?"] for h in hists]

    def test_slot_budget_exceeded_raises(self):
        from jepsen_tpu.checker.knossos import dense
        h = [op("invoke", p, "write", p) for p in range(6)]
        h += [op("ok", p, "write", p) for p in range(6)]
        with pytest.raises(kenc.EncodingError):
            dense.encode_dense_history(h, max_slots=4)

    def test_checker_tpu_backend_uses_dense(self):
        good = pairs_history((0, "write", 1, "ok"), (0, "read", 1, "ok"))
        c = linearizable(CASR, backend="tpu")
        r = c.check_batch({}, [good], {})[0]
        assert r["valid?"] is True
        assert r["analyzer"] == "tpu-dense"


class TestFeasibilityGate:
    def test_uncond_peak_counts_writes_reads_not_cas(self):
        h = [op("invoke", p, "write", p) for p in range(3)]
        h += [op("invoke", 10 + p, "cas", [p, p + 1]) for p in range(2)]
        h += [op("ok", p, "write", p) for p in range(3)]
        h += [op("ok", 10 + p, "cas", [p, p + 1]) for p in range(2)]
        e = kenc.encode_register_history(h)
        assert e.n_slots == 5
        assert e.uncond_peak == 3     # the cas pair prunes, not doubles

    def test_crashed_unconditional_ops_count_forever(self):
        h = [op("invoke", p, "write", p) for p in range(4)]
        h += [op("info", 0, "write", 0)]          # crashed: open forever
        h += [op("ok", p, "write", p) for p in range(1, 4)]
        h += [op("invoke", 9, "write", 9), op("ok", 9, "write", 9)]
        e = kenc.encode_register_history(h)
        assert e.uncond_peak == 4

    def test_predictably_infeasible_skips_device_pass(self, monkeypatch):
        """15 open writes: past the dense grid AND past any sane arena
        (closure ~2^15) — the router must go straight to the oracle
        instead of burning a device pass to discover overflow."""
        h = [op("invoke", p, "write", p) for p in range(15)]
        h += [op("ok", p, "write", p) for p in range(15)]
        def boom(*a, **kw):
            raise AssertionError("frontier kernel dispatched for a "
                                 "predictably-infeasible history")
        monkeypatch.setattr(kker, "check_encoded_batch", boom)
        c = linearizable(CASR, backend="tpu")
        [r] = c.check_batch({}, [h], {})
        assert r["valid?"] is True and r["analyzer"] == "wgl"

    def test_structured_chain_still_takes_frontier(self):
        """A 16-slot cas chain has a tiny real frontier (uncond_peak 1)
        and must keep riding the device kernel despite its slot count."""
        h = [op("invoke", 50, "write", 0), op("ok", 50, "write", 0)]
        h += [op("invoke", p, "cas", [p, p + 1]) for p in range(16)]
        h += [op("ok", p, "cas", [p, p + 1]) for p in range(16)]
        c = linearizable(CASR, backend="tpu")
        [r] = c.check_batch({}, [h], {})
        assert r["valid?"] is True and r["analyzer"] == "tpu-jit"

    def test_frontier_budget_env_and_param(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_FRONTIER", "2048")
        assert linearizable(CASR).frontier == 2048
        assert linearizable(CASR, frontier=64).frontier == 64


    def test_known_reads_count_half_not_full(self):
        """Known-value reads prune like cas — a read-heavy batch must
        still reach the device kernel (they cost ~half a doubling, not
        a full one)."""
        # 12 concurrently-open determinate reads + 1 write
        h = [op("invoke", 99, "write", 1), op("ok", 99, "write", 1)]
        h += [op("invoke", p, "read") for p in range(12)]
        h += [op("invoke", 80, "write", 1), op("ok", 80, "write", 1)]
        h += [op("ok", p, "read", 1) for p in range(12)]
        e = kenc.encode_register_history(h)
        assert e.uncond_peak <= 2      # reads back-filled => known
        c = linearizable(CASR, backend="tpu")
        [r] = c.check_batch({}, [h], {})
        assert r["analyzer"] in ("tpu-dense", "tpu-jit")
        assert r["valid?"] is True


    def test_joint_peak_not_sum_of_phase_maxima(self):
        """Disjoint phases — a 15-op cas chain, THEN 5 open writes —
        must gate on the worst single moment (load 15), not
        n_slots + uncond_peak = 20, which would over-route feasible
        histories to the oracle."""
        h = [op("invoke", 50, "write", 0), op("ok", 50, "write", 0)]
        h += [op("invoke", p, "cas", [p, p + 1]) for p in range(15)]
        h += [op("ok", p, "cas", [p, p + 1]) for p in range(15)]
        h += [op("invoke", 20 + p, "write", 9) for p in range(5)]
        h += [op("ok", 20 + p, "write", 9) for p in range(5)]
        e = kenc.encode_register_history(h)
        assert e.n_slots == 15               # past the dense grid
        assert e.half_doublings_peak == 15   # phase A: 15 cond ops
        assert e.uncond_peak == 5            # phase B writes
        # frontier=256 -> budget 16: the joint peak (15) admits; the
        # old sum-of-maxima (15 + 5 = 20) would have gone to the oracle
        c = linearizable(CASR, backend="tpu", frontier=256)
        [r] = c.check_batch({}, [h], {})
        assert r["analyzer"] == "tpu-jit", r
        assert r["valid?"] is True


    def test_frontier_band_differential_with_crashes(self):
        """Shapes engineered toward the frontier band — enough
        COMMITTED writes from a 300-value pool to bust the dense
        grid's 64-value intern budget (cas rarely commits and failed
        ops are stripped, so this needs ~260 ops) while max_pending
        keeps the closure arena-sized — must agree with the WGL
        oracle, and the frontier kernel itself (tpu-jit) must
        actually be the tier taking them. info_prob is low enough
        that the hard max_pending cap doesn't end the walk early
        (crashed ops hold slots forever), and the self-checks below
        pin that the band shape actually materialized: a parameter
        or synth change that silently sends cases back to the dense
        tier, or strips their crashes, fails loudly."""
        from jepsen_tpu.checker.knossos import analysis, synth

        tiers = []
        for case in range(6):
            h = synth.synth_register_history(
                n_ops=260, n_procs=20, n_values=300,
                info_prob=0.01, seed=7000 + case, max_pending=8)
            assert sum(1 for o in h if o["type"] == "invoke") == 260, \
                "walk ended early: max_pending cap hit"
            assert any(o["type"] == "info" for o in h), \
                "no crashed ops: the case lost its crash coverage"
            if case % 2:
                h = synth.corrupt(h, seed=case)
            c = linearizable(CASR, backend="tpu", frontier=512)
            [dev] = c.check_batch({}, [h], {})
            cpu = analysis(CASR, h)
            assert dev["valid?"] == cpu["valid?"], (case, dev)
            tiers.append(dev.get("analyzer"))
        assert tiers.count("tpu-jit") >= 4, tiers


# ---------------------------------------------------------------------------
# Packed-kernel parity (packed int32 configs vs unpacked vs WGL)
# ---------------------------------------------------------------------------

class TestPackedKernelParity:
    def _verdicts(self, hists, frontier=256):
        import jax.numpy as jnp
        from jepsen_tpu.checker.knossos import packed as kpk
        encs = [kenc.encode_register_history(h) for h in hists]
        batch = kenc.pack_register_batch(encs)
        shape = batch["shape"]
        assert all(kpk.packable(e.n_values, shape.n_slots) for e in encs)
        valid, ovf = kpk.check_batch_device_packed(
            jnp.asarray(batch["events"]), frontier=frontier,
            n_slots=shape.n_slots)
        return [("unknown" if o else bool(v))
                for v, o in zip(list(valid), list(ovf))]

    def test_goldens_packed(self):
        hists = [h for h, _ in TestKernelParity.GOLDENS]
        got = self._verdicts(hists)
        for (h, expect), v in zip(TestKernelParity.GOLDENS, got):
            assert v is expect, (h, v)

    def test_differential_random_packed(self):
        rng = random.Random(1234)
        hists = [random_register_history(rng, n_ops=20, n_procs=4)
                 for _ in range(10)]
        hists += [corrupt(rng, random_register_history(
            rng, n_ops=20, n_procs=4, info_prob=0.0)) for _ in range(10)]
        cpu = [knossos.wgl(CASR, h)["valid?"] for h in hists]
        assert self._verdicts(hists) == cpu

    def test_packed_matches_unpacked_including_overflow(self):
        # a tiny frontier forces overflow on busy histories: both
        # kernels must degrade to "unknown" on the SAME histories
        import jax.numpy as jnp
        rng = random.Random(555)
        hists = [random_register_history(rng, n_ops=30, n_procs=6)
                 for _ in range(6)]
        encs = [kenc.encode_register_history(h) for h in hists]
        batch = kenc.pack_register_batch(encs)
        shape = batch["shape"]
        ev = jnp.asarray(batch["events"])
        from jepsen_tpu.checker.knossos import packed as kpk
        pv, po = kpk.check_batch_device_packed(
            ev, frontier=8, n_slots=shape.n_slots)
        uv, uo = kker.check_batch_device(
            ev, frontier=8, n_slots=shape.n_slots)
        assert list(po) == list(uo)
        for p, u, o in zip(list(pv), list(uv), list(po)):
            if not o:
                assert bool(p) == bool(u)

    def test_packable_gate(self):
        from jepsen_tpu.checker.knossos import packed as kpk
        assert kpk.packable(2047, 20)
        assert not kpk.packable(2**12, 20)
        assert kpk.packable(2**20, 10)
        assert not kpk.packable(2, 31)

    def test_explicit_packed_downgrades_when_unpackable(self):
        # packed=True on an unfittable batch must not alias configs:
        # the router silently takes the unpacked kernel instead
        rng = random.Random(31)
        h = random_register_history(rng, n_ops=12, n_procs=2)
        enc = kenc.encode_register_history(h)
        enc.n_values = 2**30          # force the gate shut
        [r] = kker.check_encoded_batch([enc], packed=True)
        assert r["valid?"] == knossos.wgl(CASR, h)["valid?"]


# ---------------------------------------------------------------------------
# Native WGL parity (C++ search vs the Python oracle engine)
# ---------------------------------------------------------------------------

class TestNativeWGL:
    def _native_available(self):
        from jepsen_tpu import native_lib
        return native_lib.wgl_lib() is not None

    def test_differential_fuzz(self):
        if not self._native_available():
            pytest.skip("native WGL unavailable")
        rng = random.Random(321)
        checked = 0
        for _ in range(40):
            h = random_register_history(rng, n_ops=25, n_procs=5)
            if rng.random() < 0.5:
                h = corrupt(rng, h)
            nat = knossos._wgl_native(h, 10_000_000)
            py = knossos._wgl_python(CASR, h)
            assert nat is not None
            assert nat["valid?"] == py["valid?"], h
            assert nat.get("max-depth") == py.get("max-depth"), h
            if nat["valid?"] is False:
                assert nat["op"] == py["op"]
            checked += 1
        assert checked == 40

    def test_max_configs_cutoff_identical(self):
        if not self._native_available():
            pytest.skip("native WGL unavailable")
        # the cutoff depends on cache-insertion order: both engines
        # must flip to "unknown" at the same threshold
        h = [op("invoke", p, "write", p) for p in range(7)] + \
            [op("ok", p, "write", p) for p in range(7)]
        for mc in (1, 2, 5, 50, 10_000):
            nat = knossos._wgl_native(h, mc)
            py = knossos._wgl_python(CASR, h, max_configs=mc)
            assert nat["valid?"] == py["valid?"], mc

    def test_non_cas_models_stay_python(self):
        h = pairs_history((0, "acquire", None, "ok"),
                          (1, "acquire", None, "ok"))
        r = knossos.wgl(models.mutex(), h)
        assert r["valid?"] is False   # python engine handles mutex

    def test_unencodable_histories_fall_back(self):
        # >24 pending slots exceeds the encoder's budget; wgl() must
        # still answer via the Python engine
        h = [op("invoke", p, "write", p) for p in range(30)] + \
            [op("ok", p, "write", p) for p in range(30)]
        assert knossos.wgl(CASR, h)["valid?"] is True


def test_list_tuple_values_route_to_python_oracle():
    """A tuple write observed as an equal-content list read: the intern
    map would equate what CASRegister.__eq__ distinguishes, so every
    interned engine (native WGL, dense grid, frontier kernel) must
    refuse the history and the oracle's verdict must prevail."""
    h = pairs_history((0, "write", (1, 2), "ok"),
                      (0, "read", [1, 2], "ok"))
    with pytest.raises(kenc.EncodingError):
        kenc.encode_register_history(h)
    assert knossos._wgl_native(h, 10_000_000) is None
    r = knossos.wgl(CASR, h)
    assert r["valid?"] is False       # the oracle distinguishes them
    c = linearizable(CASR, backend="tpu")
    [rt] = c.check_batch({}, [h], {})
    assert rt["valid?"] is False      # device tiers fall through too


class TestRaceBackend:
    """backend="race": device pipeline vs CPU engine, first full-batch
    finisher wins; verdicts must match the oracle either way."""

    def _hists(self):
        rng = random.Random(64)
        hists = [random_register_history(rng, n_ops=60, n_procs=6)
                 for _ in range(4)]
        hists += [corrupt(rng, random_register_history(
            rng, n_ops=60, n_procs=6, info_prob=0.0)) for _ in range(2)]
        return hists

    def test_race_verdict_parity(self, monkeypatch):
        # force the accelerator resolution so _race actually runs on
        # the virtual CPU mesh (without it, auto resolves to cpu and
        # the race is never entered)
        monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
        hists = self._hists()
        c = linearizable(CASR, backend="race")
        res = c.check_batch({}, hists, {})
        for h, r in zip(hists, res):
            assert r["valid?"] == knossos.analysis(CASR, h)["valid?"]

    def test_race_raises_on_device_failure(self, monkeypatch):
        # a device pipeline that raises fails the race: the CPU engine
        # never stands in for a broken device
        import time
        from jepsen_tpu.checker import Linearizable
        monkeypatch.setenv("JEPSEN_TPU_BACKEND", "tpu")
        calls = []
        def boom(self, hists):
            calls.append(1)
            raise RuntimeError("boom")
        orig_cpu = Linearizable._cpu
        def slow_cpu(self, h, search_stats=None):
            time.sleep(0.1)
            return orig_cpu(self, h, search_stats=search_stats)
        monkeypatch.setattr(Linearizable, "_device_batch", boom)
        monkeypatch.setattr(Linearizable, "_cpu", slow_cpu)
        c = linearizable(CASR, backend="race")
        with pytest.raises(RuntimeError, match="boom"):
            c.check_batch({}, self._hists(), {})
        assert calls, "race never entered the device side"

    def test_race_via_env_from_cli_wiring(self, monkeypatch):
        # the CLI exports --backend race as JEPSEN_TPU_BACKEND=race and
        # builds checkers with backend="auto": the race must still
        # engage (and elle-side resolve_backend must not see "race")
        from jepsen_tpu import devices
        monkeypatch.setenv("JEPSEN_TPU_BACKEND", "race")
        monkeypatch.setattr(devices, "accelerator_available", lambda: True)
        entered = []
        from jepsen_tpu.checker import Linearizable
        orig = Linearizable._race
        monkeypatch.setattr(
            Linearizable, "_race",
            lambda self, hists: entered.append(1) or orig(self, hists))
        hists = self._hists()
        c = linearizable(CASR, backend="auto")
        res = c.check_batch({}, hists, {})
        assert entered, "env-requested race never engaged"
        for h, r in zip(hists, res):
            assert r["valid?"] == knossos.analysis(CASR, h)["valid?"]
        # non-racing checkers resolve "race" like auto, never literally
        assert devices.resolve_backend("race") in ("tpu", "cpu")

    def test_race_non_register_model_goes_cpu(self):
        h = pairs_history((0, "acquire", None, "ok"),
                          (1, "acquire", None, "ok"))
        c = linearizable(models.mutex(), backend="race")
        assert c.check_batch({}, [h], {})[0]["valid?"] is False


class TestReducedSeqParity:
    """_reduced_seq (the encoder's dict-free reduction) must produce
    the SAME event stream as encoding the dict pipeline's output —
    including on malformed histories (stale invokes, stray
    completions, unknown op types), where the stages' distinct pairing
    rules interact (a stray ok can complete a stale invoke once the
    fail pair between them is deleted)."""

    def _encode_via_dicts(self, h):
        """Reference: the original dict-pipeline reduction feeding an
        equivalent encoder walk, reconstructed from reduce_history."""
        hist = knossos.reduce_history(h)
        seq = []
        for o in hist:
            ty = o.get("type")
            if ty == "invoke":
                seq.append((0, o.get("process"), o.get("f"),
                            o.get("value")))
            elif ty == "info":
                seq.append((1, o.get("process"), o.get("f"),
                            o.get("value")))
            else:
                seq.append((2, o.get("process"), o.get("f"),
                            o.get("value")))
        return seq

    def test_reviewer_repro(self):
        # fail pair between a stale invoke and its stray ok completion
        h = [op("invoke", 0, "write", 1), op("invoke", 0, "write", 2),
             op("fail", 0, "write", 2), op("ok", 0, "write", 1)]
        assert kenc._reduced_seq(h) == self._encode_via_dicts(h)
        enc = kenc.encode_register_history(h)
        # the stray ok completes the stale invoke: 1 invoke + 1 complete
        assert (enc.events[:, 0] == 1).sum() == 1

    def test_fuzz_reductions_agree(self):
        rng = random.Random(8088)
        types = ["invoke", "ok", "fail", "info", "invoke", "ok",
                 "weird", None]
        fs = ["read", "write", "cas"]
        for trial in range(400):
            h = []
            for i in range(rng.randrange(1, 30)):
                ty = rng.choice(types)
                f = rng.choice(fs)
                v = ([rng.randrange(3), rng.randrange(3)]
                     if f == "cas" else
                     rng.choice([None, rng.randrange(4)]))
                o = {"process": rng.randrange(3), "f": f, "value": v}
                if ty is not None:
                    o["type"] = ty
                h.append(o)
            assert kenc._reduced_seq(h) == self._encode_via_dicts(h), h

    def test_fuzz_well_formed_verdicts(self):
        rng = random.Random(4242)
        for trial in range(60):
            h = random_register_history(rng, n_ops=30, n_procs=4)
            if rng.random() < 0.5:
                h = corrupt(rng, h)
            nat = knossos._wgl_native(h, 10_000_000)
            py = knossos._wgl_python(CASR, h)
            assert nat is not None and nat["valid?"] == py["valid?"]


def test_subhistories_single_pass_parity():
    """independent.subhistories must match per-key subhistory() exactly
    — including un-lifted (nemesis) ops appearing in every key's list,
    even keys first seen after them."""
    t = independent.tuple_
    h = [
        {"type": "info", "process": "nemesis", "f": "start", "value": None},
        op("invoke", 0, "write", t(1, 5)),
        op("ok", 0, "write", t(1, 5)),
        {"type": "info", "process": "nemesis", "f": "stop", "value": None},
        op("invoke", 1, "read", t(2, None)),
        op("ok", 1, "read", t(2, 5)),
        op("invoke", 2, "cas", t(1, [5, 6])),
        op("ok", 2, "cas", t(1, [5, 6])),
    ]
    by_key = independent.subhistories(h)
    assert list(by_key) == independent.history_keys(h)
    for k in by_key:
        assert by_key[k] == independent.subhistory(k, h), k


class TestNativeMutexWGL:
    """The native WGL's mutex model vs the Python oracle."""

    @staticmethod
    def _mutex_history(rng, n_ops=30, n_procs=4, corrupt=False):
        """Simulated lock: acquire/release with real overlap (invoke
        and completion interleave across processes); optionally corrupt
        by flipping an op's f."""
        hist, held, pending = [], [None], {}
        for i in range(n_ops):
            p = rng.randrange(n_procs)
            if p in pending:
                f, _g = pending.pop(p)
                # info/fail completions exercise return-at-infinity and
                # the fail-pair dropping; keeping the SIMULATED state as
                # if the op took effect stays conservative for "ok"
                # parity while still generating both engines' hard paths
                ty = rng.choices(["ok", "info", "fail"],
                                 [0.8, 0.1, 0.1])[0]
                hist.append(op(ty, p, f))
                continue
            if held[0] is None and rng.random() < 0.6:
                hist.append(op("invoke", p, "acquire"))
                held[0] = p
                pending[p] = ("acquire", True)
            elif held[0] is not None and rng.random() < 0.6:
                q = held[0]
                if q in pending:
                    continue
                hist.append(op("invoke", q, "release"))
                held[0] = None
                pending[q] = ("release", True)
        for p, (f, _g) in list(pending.items()):
            hist.append(op("ok", p, f))
        if corrupt and len(hist) > 2:
            i = rng.randrange(len(hist))
            hist[i] = {**hist[i],
                       "f": "acquire" if hist[i]["f"] == "release"
                       else "release"}
        return hist

    def test_mutex_differential_fuzz(self):
        from jepsen_tpu import native_lib
        if native_lib.wgl_lib() is None:
            pytest.skip("native WGL unavailable")
        rng = random.Random(6060)
        MUT = models.mutex()
        for trial in range(120):
            h = self._mutex_history(rng, n_ops=rng.randrange(6, 40),
                                    n_procs=rng.randrange(2, 6),
                                    corrupt=rng.random() < 0.5)
            nat = knossos._wgl_native(h, 10_000_000, "mutex")
            py = knossos._wgl_python(MUT, h)
            assert nat is not None
            assert nat["valid?"] == py["valid?"], h
            assert nat.get("max-depth") == py.get("max-depth"), h

    def test_mutex_goldens_via_wgl(self):
        # the public wgl() entry now routes fresh-mutex models natively
        h = pairs_history((0, "acquire", None, "ok"),
                          (1, "acquire", None, "ok"))
        assert knossos.wgl(models.mutex(), h)["valid?"] is False
        h2 = pairs_history((0, "acquire", None, "ok"),
                           (0, "release", None, "ok"),
                           (1, "acquire", None, "ok"))
        assert knossos.wgl(models.mutex(), h2)["valid?"] is True
        # a held-lock initial state must stay on the Python engine
        assert knossos.wgl(models.Mutex(True), h2)["valid?"] is False
