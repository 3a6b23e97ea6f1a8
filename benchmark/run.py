#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name (harness/spec.py). The run refuses to start without a
TPU, or with fewer chips than the cell asks for; it generates the
cell's inputs from the seed, warms up every shape the window uses
(set-up), measures for `--seconds`, checks the verdicts the window
produced against the seeded truth and a plain reference checker, and
prints one JSON line: `correct`, `attempted`, `failed`, `metrics`,
`device` (and `breakdown` with `--trace 1`), then `checks`.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))


class Context:
    """What a driver needs to run one cell once."""

    def __init__(self, bench, cell: dict, args, devices):
        self.bench = bench
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.devices = devices
        self.traffic = bench.traffic(cell["traffic"])
        self.config = bench.cell_config(cell)
        self.workload = bench.workload_module(self.config)
        self.work = HERE / ".work" / cell["name"]
        self.t0 = T0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    bench = spec.Benchmark()
    cell = bench.cells.get(args.workload)
    if cell is None:
        print(f"run.py: no cell {args.workload!r}", file=sys.stderr)
        return 2
    if not (ROOT / "jepsen_tpu" / "__init__.py").is_file():
        print("run.py: the program (jepsen_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    execute(Context(bench, cell, args, devices))
    return 0


def execute(ctx: Context) -> None:
    """Drive the cell through its driver and print the result line."""
    from harness import result
    bench, name = ctx.bench, ctx.cell["name"]
    out = bench.driver_module(ctx.traffic).run(ctx)
    if ctx.trace:
        metrics = {}
        for m in bench.per_layer(name):
            v = bench.metric_module(m["name"]).read(out["readings"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = out["readings"]["trace"]
        out["device"]["busy_s"] = tr.busy_s()
        out["device"]["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(),
                     "idle_gaps": tr.idle_gaps(
                         labels=out["readings"].get("labels", ()))}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench.end_to_end(name)}
        breakdown = None
    result.emit(checks=out["checks"], attempted=out["attempted"],
                failed=out["failed"], metrics=metrics, device=out["device"],
                breakdown=breakdown)


if __name__ == "__main__":
    sys.exit(main())
