"""The verdict daemon under open-loop load.

Set-up generates one run dir per request (and a few for the warm-up)
from the seed, starts an in-process `VerdictDaemon` over the store,
compiles a fold of every size the daemon can form (the program's
`FoldDispatcher`, below the socket) and sends a few requests through
the socket. The window sends `check_dir` requests over the daemon's
unix socket, one connection per tenant, at the times of a Poisson
process drawn from the seed at the rate fixed in the traffic file, and
conditioned on its count (rate x window), tenants in fixed shares. Latency runs from a request's scheduled send time to
its verdict reaching the client; requests unanswered a minute after
the window closes have failed. Every verdict is compared with the
seeded truth, and a sample drawn from the seed with the plain
reference checker, once the daemon has stopped.
"""

from __future__ import annotations

import contextlib
import random
import shutil
import sys
import threading
import time

from harness import result, spans, stores, verify, xplane

GRACE_S = 60.0


def schedule(n: int, seconds: float, seed: int) -> list[float]:
    """n send times in [0, seconds): a Poisson process of n/seconds per
    second conditioned on n arrivals, that is n uniform times, sorted."""
    rng = random.Random(f"arrivals:{seed}")
    return sorted(rng.uniform(0.0, seconds) for _ in range(n))


def tenants_of(n: int, shares: dict, seed: int) -> list[str]:
    names = sorted(shares)
    counts = {t: int(n * shares[t]) for t in names}
    for t in sorted(names, key=lambda t: -shares[t])[:n - sum(
            counts.values())]:
        counts[t] += 1
    out = [t for t in names for _ in range(counts[t])]
    random.Random(f"tenants:{seed}").shuffle(out)
    return out


def drive(clients: dict, runs, names, tenants, at) -> tuple:
    """Send each run dir from its tenant's connection at its time in
    `at` (seconds from start); returns (start, lateness of each send)."""
    import jax
    late = []
    start = time.monotonic() + 0.2
    with jax.profiler.TraceAnnotation("bench:send_requests"):
        for name, tn, due in zip(names, tenants, at):
            wait = start + due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            clients[tn].check_dir(runs / name, rid=name)
            late.append(clients[tn].sent_at[name] - (start + due))
    return start, late


def _warm_folds(dirs) -> int:
    """Compile a fold of every size the daemon can form over these
    histories: up to its fold limit, as many as its cell budget holds."""
    from jepsen_tpu import ingest
    from jepsen_tpu.parallel import folding
    from jepsen_tpu.serve import scheduler
    encs = [ingest.encode_run_dir(d, "append") for d in dirs]
    most = min(scheduler.DEFAULT_MAX_FOLD, len(encs), max(
        1, folding.DEFAULT_FOLD_CELLS // max(folding.fold_cost(e.n)
                                             for e in encs)))
    fd = folding.FoldDispatcher(budget_cells=folding.DEFAULT_FOLD_CELLS)
    for b in range(1, most + 1):
        fd.verdicts(encs[:b])
    return most


def _warm(sock, dirs, tenants, timeout: float) -> None:
    """Concurrent requests from every tenant at once, through the
    socket."""
    from jepsen_tpu.serve.client import ServeClient
    clients = [ServeClient(socket_path=sock, tenant=f"warm-{t}")
               for t in tenants]
    try:
        for c in clients:
            c.connect()
        for i, d in enumerate(dirs):
            clients[i % len(clients)].check_dir(d, rid=f"warm:{i}")
        for c in clients:
            c.collect(timeout=timeout)
    finally:
        for c in clients:
            c.close()


def run(ctx) -> dict:
    import jax
    from jepsen_tpu import aot, trace
    from jepsen_tpu.serve.client import ServeClient
    from jepsen_tpu.serve.daemon import VerdictDaemon
    from jepsen_tpu.store import Store
    cfg, traffic, wl = ctx.config, ctx.traffic, ctx.workload
    n = max(1, round(traffic["rate_per_s"] * ctx.seconds))
    n_warm = max(traffic["warm_requests"], traffic["warm_folds"])
    shutil.rmtree(ctx.work, ignore_errors=True)
    store = ctx.work / "store"
    runs = store / cfg["name"]
    t = time.perf_counter()
    truth = stores.generate(wl, cfg, runs, ctx.seed, n)
    warm = stores.generate(wl, cfg, runs, ctx.seed, n_warm, first=n)
    print(f"store generation: {time.perf_counter() - t:.3f} s for "
          f"{n + n_warm} runs", file=sys.stderr, flush=True)
    names = sorted(truth)
    ten = tenants_of(n, traffic["tenants"], ctx.seed)
    at = schedule(n, ctx.seconds, ctx.seed)
    aot.configure_jax_cache()
    prev = trace.get_current()      # the daemon replaces the tracer
    daemon = VerdictDaemon(Store(store)).start()
    clients: dict = {}
    try:
        tr = trace.get_current()
        sock = daemon.ready_info()["serve"]["socket"]
        t = time.perf_counter()
        warm_dirs = [runs / w for w in sorted(warm)]
        most = _warm_folds(warm_dirs)
        _warm(sock, warm_dirs[:traffic["warm_requests"]],
              sorted(traffic["tenants"]), timeout=600)
        print(f"warm-up: folds of 1-{most} histories and "
              f"{traffic['warm_requests']} requests in "
              f"{time.perf_counter() - t:.3f} s", file=sys.stderr,
              flush=True)
        for tn in sorted(traffic["tenants"]):
            c = ServeClient(socket_path=sock, tenant=tn,
                            timeout=ctx.seconds + GRACE_S + 60)
            c.connect()
            clients[tn] = c
        expect = {tn: ten.count(tn) for tn in clients}
        errors: list = []

        def collect(c, k):
            try:
                c.collect(timeout=ctx.seconds + GRACE_S, expect=k)
            except Exception as e:          # reported as missing
                errors.append(f"{c.tenant}: {e!r}")

        collectors = [threading.Thread(target=collect, args=(c, expect[tn]),
                                       name=f"collect-{tn}")
                      for tn, c in clients.items() if expect[tn]]
        for th in collectors:
            th.start()
        setup_s = time.perf_counter() - ctx.t0
        misses0 = tr.metrics_dict()["counters"].get("compile_cache_misses", 0)

        if ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(ctx.work / "profile"),
                                     profiler_options=opts)
        p0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(xplane.WINDOW) if ctx.trace \
                else contextlib.nullcontext():
            start, late = drive(clients, runs, names, ten, at)
            close = start + ctx.seconds
            with jax.profiler.TraceAnnotation("bench:wait_verdicts"):
                time.sleep(max(0.0, close - time.monotonic()))
        if ctx.trace:
            jax.profiler.stop_trace()
        p1 = time.perf_counter()
        for th in collectors:
            th.join(timeout=max(0.0, close + GRACE_S - time.monotonic()))
        device = result.device_info(ctx.devices, len(ctx.devices))
        folds = tr.metrics_dict()
        print(f"window: compile cache misses "
              f"{folds['counters'].get('compile_cache_misses', 0) - misses0}"
              f", folds {folds['histograms'].get('serve_fold_histories')}",
              file=sys.stderr, flush=True)
        lo, hi = tr.rel_us(p0), tr.rel_us(p1)
        daemon_spans = [(e["ts"], e["dur"], e["name"])
                        for e in tr.chrome_events()
                        if e.get("ph") == "X" and lo <= e["ts"] < hi]
    finally:
        for c in clients.values():
            c.close()
        daemon.stop()
        trace.set_current(prev)

    answers, lat = {}, []
    for name, tn, due in zip(names, ten, at):
        c = clients[tn]
        answers[name] = c.verdicts.get(name)
        done = c.done_at.get(name)
        lat.append(((done if done is not None else close + GRACE_S)
                    - (start + due)) * 1000.0)
    late_ms = sorted(x * 1000.0 for x in late)
    print(f"latency: p50 {result.percentile(lat, 50):.3f} ms, p95 "
          f"{result.percentile(lat, 95):.3f} ms, max {max(lat):.3f} ms",
          file=sys.stderr, flush=True)
    print(f"generator lateness: median {result.percentile(late_ms, 50):.3f}"
          f" ms, p95 {result.percentile(late_ms, 95):.3f} ms, max "
          f"{late_ms[-1]:.3f} ms over {n} requests"
          + (f"; collect errors {errors}" if errors else ""),
          file=sys.stderr, flush=True)

    t = time.perf_counter()
    picked = verify.sample(truth, ctx.seed, traffic["reference_valid"],
                           traffic["reference_invalid"])
    ref = {nm: wl.check(runs / nm / "history.jsonl") for nm in picked}
    print(f"reference: {len(ref)} runs in {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    c = verify.compare(wl, answers, truth, ref)
    for line in verify.report(wl, answers, truth, ref):
        print(f"disagrees: {line}", file=sys.stderr, flush=True)
    checks = result.Checks()
    for k in ("missing", "wrong_vs_truth", "wrong_vs_reference"):
        checks.add(k, c[k], 0)
    e2e = {"setup_s": setup_s, "serve_p50_ms": result.percentile(lat, 50)}
    readings = None
    if ctx.trace:
        trc = xplane.load(ctx.work / "profile")
        readings = {"trace": trc,
                    "serve_spans": [(n, d / 1e6) for _t, d, n in daemon_spans],
                    "labels": spans.on_profile_clock(daemon_spans, lo,
                                                     trc.window[0])}
    shutil.rmtree(ctx.work, ignore_errors=True)
    return {"checks": checks, "attempted": n,
            "failed": c["missing"] + c["wrong_vs_truth"], "e2e": e2e,
            "device": device, "readings": readings}
