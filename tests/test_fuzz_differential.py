"""Randomized differential sweeps: CPU oracle vs device kernels on
randomly corrupted histories (SURVEY.md §4.3's property-test tier).
Bounded trial counts for CI; crank FUZZ_TRIALS for a longer hunt."""

from __future__ import annotations

import os
import random

from jepsen_tpu.checker import elle, knossos as kn, linearizable, models
from jepsen_tpu.checker.elle import wr as elle_wr
from jepsen_tpu.checker.knossos import synth as ksynth

TRIALS = int(os.environ.get("FUZZ_TRIALS", 6))


def rand_append_history(rng, T, K, conc, info_p=0.05, corrupt_p=0.15):
    hist, state = [], {}
    for i in range(T):
        k = rng.randrange(K)
        if rng.random() < 0.5:
            v = len(state.setdefault(k, [])) + 1
            mops = [["append", k, v]]
            state[k].append(v)
        else:
            obs = list(state.get(k, []))
            if obs and rng.random() < corrupt_p:
                cut = rng.randrange(len(obs) + 1)
                obs = obs[:cut] + ([99999] if rng.random() < 0.2 else [])
            mops = [["r", k, obs]]
        p = i % conc
        hist.append({"type": "invoke", "process": p, "f": "txn",
                     "value": [[m[0], m[1],
                                None if m[0] == "r" else m[2]]
                               for m in mops]})
        ty = "info" if rng.random() < info_p else "ok"
        hist.append({"type": ty, "process": p, "f": "txn",
                     "value": mops if ty == "ok" else None})
    return [{**o, "index": i, "time": i * 1000}
            for i, o in enumerate(hist)]


def rand_wr_history(rng, T, K, conc, corrupt_p=0.2):
    hist, state, vc = [], {}, {}
    for i in range(T):
        k = f"k{rng.randrange(K)}"
        mops = []
        for _ in range(rng.choice([1, 1, 2])):
            if rng.random() < 0.5:
                vc[k] = vc.get(k, 0) + 1
                mops.append(["w", k, vc[k]])
                state[k] = vc[k]
            else:
                v = state.get(k)
                if v is not None and rng.random() < corrupt_p:
                    v = rng.choice([v + 1, max(1, v - 1), 777])
                mops.append(["r", k, v])
        p = i % conc
        ty = rng.choices(["ok", "info", "fail"], [0.9, 0.05, 0.05])[0]
        hist.append({"type": "invoke", "process": p, "f": "txn",
                     "value": [[m[0], m[1],
                                None if m[0] == "r" else m[2]]
                               for m in mops]})
        hist.append({"type": ty, "process": p, "f": "txn",
                     "value": mops if ty == "ok" else None})
    return [{**o, "index": i, "time": i * 1000}
            for i, o in enumerate(hist)]


def test_fuzz_append_parity():
    rng = random.Random(2026)
    for trial in range(TRIALS):
        h = rand_append_history(rng, rng.choice([30, 120]),
                                rng.choice([2, 8]), rng.choice([1, 5]))
        for rt, po in ((False, False), (True, False), (False, True)):
            c = elle.append_checker(backend="cpu", realtime=rt,
                                    process_order=po).check({}, h, {})
            t = elle.append_checker(backend="tpu", realtime=rt,
                                    process_order=po).check({}, h, {})
            assert (c["valid?"], sorted(c["anomaly-types"])) == \
                (t["valid?"], sorted(t["anomaly-types"])), (trial, rt, po)


def test_fuzz_int8_closure_parity():
    """The int8 squaring, run batched over 3 histories at a time, must
    give each history the CPU oracle's anomalies on randomly corrupted
    batches — the exactness argument (non-negative terms, int32
    accumulation) fuzz-checked end to end, in detect and classify
    mode."""
    import numpy as np

    from jepsen_tpu.checker.elle import cycle_anomalies_cpu
    from jepsen_tpu.checker.elle import encode as elle_encode
    from jepsen_tpu.checker.elle import kernels as K
    rng = random.Random(31)
    for trial in range(TRIALS):
        hists = [rand_append_history(rng, rng.choice([30, 120]),
                                     rng.choice([2, 8]),
                                     rng.choice([1, 5]))
                 for _ in range(3)]
        encs = [elle_encode.encode_history(h) for h in hists]
        want = [sorted(cycle_anomalies_cpu(e)) for e in encs]
        packed = K.pack_batch(encs)
        sh = packed["shape"]
        names = ("appends", "reads", "invoke_index", "complete_index",
                 "process", "n_txns")
        args = tuple(packed[k] for k in names)
        kw = dict(n_keys=sh.n_keys, max_pos=sh.max_pos,
                  n_txns=sh.n_txns, steps=K.closure_steps(sh.n_txns))
        classified = np.asarray(K.check_batch_device(
            *args, classify=True, **kw)).tolist()
        assert [sorted(K.flags_to_names(w)) for w in classified] \
            == want, (trial, classified, want)
        detected = np.asarray(K.check_batch_device(
            *args, classify=False, **kw)).tolist()
        assert [bool(w >> K.CYCLE & 1) for w in detected] \
            == [bool(a) for a in want], (trial, detected, want)


def test_fuzz_wr_parity():
    rng = random.Random(77)
    for trial in range(TRIALS):
        h = rand_wr_history(rng, rng.choice([30, 120]),
                            rng.choice([2, 6]), rng.choice([1, 6]))
        for flags in ({}, {"sequential_keys": True}, {"realtime": True}):
            c = elle_wr.rw_register_checker(
                backend="cpu", **flags).check({}, h, {})
            t = elle_wr.rw_register_checker(
                backend="tpu", **flags).check({}, h, {})
            assert (c["valid?"], sorted(c["anomaly-types"])) == \
                (t["valid?"], sorted(t["anomaly-types"])), (trial, flags)


def test_fuzz_knossos_parity_with_corruption():
    rng = random.Random(9)
    c = linearizable(models.cas_register(), backend="tpu")
    for trial in range(TRIALS):
        h = ksynth.synth_register_history(
            n_ops=rng.choice([60, 150]), n_procs=rng.choice([4, 10]),
            n_values=4, info_prob=rng.choice([0.0, 0.1]),
            seed=trial * 13 + 1)
        if trial % 2:
            ok_reads = [i for i, o in enumerate(h)
                        if o.get("type") == "ok" and o.get("f") == "read"
                        and o.get("value") is not None]
            if ok_reads:
                i = rng.choice(ok_reads)
                h = list(h)
                h[i] = {**h[i], "value": h[i]["value"] + 10}
        cpu = kn.analysis(models.cas_register(), h)["valid?"]
        [dev] = c.check_batch({}, [h], {})
        assert cpu == dev["valid?"], (trial, cpu, dev)
