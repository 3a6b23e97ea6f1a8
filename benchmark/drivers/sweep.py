"""Store sweeps: passes of `analyze-store` over a generated store.

Set-up generates the store from the seed and runs one full pass (every
bucket geometry the window uses compiles there), then puts the store
back. The window runs passes, each a first sweep of a fresh store as a
nightly sweep is, until their summed wall time reaches `--seconds`;
between passes the store is put back, timed apart. Every verdict of
every pass is compared with the seeded truth, and a sample drawn from
the seed with the plain reference checker, once the window has closed.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

from harness import result, spans, stores, verify, xplane


def _sweep(store: Path, checker: str) -> int:
    from jepsen_tpu import cli
    with open(os.devnull, "w") as f, contextlib.redirect_stdout(f):
        return cli.run_cli(lambda tmap, args: tmap, argv=[
            "analyze-store", "--store", str(store), "--checker", checker,
            "--backend", "tpu"])


def _answers(runs: Path, names) -> dict:
    out = {}
    for n in names:
        try:
            out[n] = json.loads((runs / n / "results.json").read_text())
        except (OSError, ValueError):
            out[n] = None
    return out


def run(ctx) -> dict:
    import jax
    cfg, traffic, wl = ctx.config, ctx.traffic, ctx.workload
    checker = cfg["checker"]
    shutil.rmtree(ctx.work, ignore_errors=True)
    store = ctx.work / "store"
    runs = store / cfg["name"]
    t = time.perf_counter()
    truth = stores.generate(wl, cfg, runs, ctx.seed, cfg["runs_per_store"])
    print(f"store generation: {time.perf_counter() - t:.3f} s for "
          f"{len(truth)} runs", file=sys.stderr, flush=True)
    from jepsen_tpu import aot
    aot.configure_jax_cache()
    t = time.perf_counter()
    rc = _sweep(store, checker)
    if rc not in (0, 1):
        raise RuntimeError(f"warm-up analyze-store exited {rc}")
    print(f"warm-up pass: {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    stores.restore(store, cfg["name"])
    setup_s = time.perf_counter() - ctx.t0

    passes, answers = [], []
    restore_s = 0.0
    while sum(p["wall_s"] for p in passes) < ctx.seconds:
        traced = ctx.trace and not passes
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            prof_dir = ctx.work / "profile"
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.WINDOW) if traced \
                else contextlib.nullcontext():
            with jax.profiler.TraceAnnotation("bench:analyze_store"):
                t = time.perf_counter()
                rc = _sweep(store, checker)
                wall = time.perf_counter() - t
        if traced:
            from jepsen_tpu import trace as program_trace
            # the sweep's tracer is still current: its clock at `t`
            offset_us = program_trace.get_current().rel_us(t)
        if traced:
            jax.profiler.stop_trace()
        got = _answers(runs, truth)
        p = {"wall_s": wall, "rc": rc, "runs": len(truth),
             "verdicted": sum(a is not None for a in got.values()),
             "counters": json.loads(
                 (store / "metrics.json").read_text())["counters"]}
        if traced:
            p["events"] = json.loads(
                (store / "trace.json").read_text())["traceEvents"]
        passes.append(p)
        answers.append(got)
        t = time.perf_counter()
        stores.restore(store, cfg["name"])
        restore_s += time.perf_counter() - t
    window_s = sum(p["wall_s"] for p in passes)
    print(f"window: {len(passes)} passes, {window_s:.3f} s "
          f"({[round(p['wall_s'], 3) for p in passes]}); restores "
          f"{restore_s:.3f} s; compile cache misses "
          f"{[p['counters'].get('compile_cache_misses', 0) for p in passes]}",
          file=sys.stderr, flush=True)
    device = result.device_info(ctx.devices, len(ctx.devices))

    # the reference, once the window has closed, over the last pass
    t = time.perf_counter()
    picked = verify.sample(truth, ctx.seed, traffic["reference_valid"],
                           traffic["reference_invalid"])
    last = answers[-1]
    # the last pass was put back: re-read its verdicts from memory only
    ref = {n: wl.check(runs / n / "history.jsonl") for n in picked}
    print(f"reference: {len(ref)} runs in {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    checks = result.Checks()
    totals = {"missing": 0, "wrong_vs_truth": 0}
    for got in answers:
        c = verify.compare(wl, got, truth, {})
        totals["missing"] += c["missing"]
        totals["wrong_vs_truth"] += c["wrong_vs_truth"]
    wrong_ref = verify.compare(wl, last, truth, ref)["wrong_vs_reference"]
    for line in verify.report(wl, last, truth, ref):
        print(f"disagrees: {line}", file=sys.stderr, flush=True)
    checks.add("missing", totals["missing"], 0)
    checks.add("wrong_vs_truth", totals["wrong_vs_truth"], 0)
    checks.add("wrong_vs_reference", wrong_ref, 0)
    checks.add("bad_exit", sum(p["rc"] not in (0, 1) for p in passes), 0)

    verdicted = sum(p["verdicted"] for p in passes)
    e2e = {"setup_s": setup_s,
           "sweep_hist_per_s": verdicted / window_s}
    readings = None
    if ctx.trace:
        first = passes[0]
        tr = xplane.load(ctx.work / "profile")
        labels = spans.on_profile_clock(
            spans.main_thread_phases(first["events"]), offset_us,
            tr.host_start("bench:analyze_store"))
        readings = {"trace": tr, "labels": labels,
                    "device_kind": device["kind"],
                    "chips": len(ctx.devices),
                    "pass": first, "runs": first["runs"],
                    "txn_counts": [t.get("txns") for t in truth.values()],
                    "config": cfg}
    shutil.rmtree(ctx.work, ignore_errors=True)
    return {"checks": checks, "attempted": sum(p["runs"] for p in passes),
            "failed": totals["missing"] + totals["wrong_vs_truth"],
            "e2e": e2e, "device": device, "readings": readings}
