#!/usr/bin/env python3
"""The knee of the serve cell, found once on the chip by a sweep of
offered rates: one daemon, warmed as the cell warms it, then for each
rate a window of open-loop requests over never-checked run dirs. For
each rate it prints the rate completed within the window, the backlog
at its close, and the median latency of the window's first and last
thirds. The knee is the highest rate that keeps up without a growing
backlog; the cell's traffic file takes 4/5 of it as a number.

    python3 benchmark/knee.py --workload append-serve --seed 7 \
        --seconds 10 --rates 8,12,16
"""

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee.py: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from harness import result, spec, stores
    from jepsen_tpu import aot, trace
    from jepsen_tpu.serve.client import ServeClient
    from jepsen_tpu.serve.daemon import VerdictDaemon
    from jepsen_tpu.store import Store
    b = spec.Benchmark()
    cell = b.cells[args.workload]
    cfg, traffic = b.cell_config(cell), b.traffic(cell["traffic"])
    wl = b.workload_module(cfg)
    serve = b.driver_module(traffic)
    rates = [float(r) for r in args.rates.split(",")]
    work = HERE / ".work" / f"knee-{cell['name']}"
    shutil.rmtree(work, ignore_errors=True)
    runs = work / "store" / cfg["name"]
    sizes = [round(r * args.seconds) for r in rates]
    n_warm = traffic["warm_requests"]
    stores.generate(wl, cfg, runs, args.seed, sum(sizes) + n_warm)
    names = sorted(p.name for p in runs.iterdir())
    aot.configure_jax_cache()
    prev = trace.get_current()
    daemon = VerdictDaemon(Store(work / "store")).start()
    try:
        sock = daemon.ready_info()["serve"]["socket"]
        serve._warm(sock, [runs / n for n in names[-n_warm:]],
                    sorted(traffic["tenants"]), timeout=600)
        first = 0
        for rate, n in zip(rates, sizes):
            batch = names[first:first + n]
            first += n
            ten = serve.tenants_of(n, traffic["tenants"], args.seed)
            at = serve.schedule(n, args.seconds, args.seed)
            clients = {}
            for tn in sorted(traffic["tenants"]):
                c = ServeClient(socket_path=sock, tenant=tn)
                c.connect()
                clients[tn] = c
            import threading
            ths = [threading.Thread(
                target=c.collect, kwargs={"timeout": args.seconds + 120,
                                          "expect": ten.count(tn)})
                for tn, c in clients.items() if ten.count(tn)]
            for th in ths:
                th.start()
            start, late = serve.drive(clients, runs, batch, ten, at)
            close = start + args.seconds
            time.sleep(max(0.0, close - time.monotonic()))
            for th in ths:
                th.join(timeout=args.seconds + 120)
            lat, done_in = [], 0
            for nm, tn, due in zip(batch, ten, at):
                d = clients[tn].done_at.get(nm)
                lat.append((d - start - due) * 1000 if d else float("inf"))
                done_in += d is not None and d <= close
            third = max(1, n // 3)
            print(json.dumps({
                "rate": rate, "requests": n,
                "completed_per_s": done_in / args.seconds,
                "backlog_at_close": n - done_in,
                "p50_first_third_ms": result.percentile(lat[:third], 50),
                "p50_last_third_ms": result.percentile(lat[-third:], 50),
                "p95_ms": result.percentile(lat, 95),
                "late_p95_ms": result.percentile(
                    [x * 1000 for x in late], 95)}), flush=True)
            for c in clients.values():
                c.close()
    finally:
        daemon.stop()
        trace.set_current(prev)
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
