#!/usr/bin/env python3
"""The control's readings: for each seed, a cell's store at its own size
and the cell's reference sample, checked by the workload's plain
checker with one stated guarantee broken (list-append: G1c no longer
prohibited; CAS register: stale reads accepted), in the program's
place. Prints the numbers `correct` compares, per seed, as JSON lines.
The benchmark's own runs never run this; PERF.md records its output.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3
"""

import argparse
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

BROKEN = {"list_append": {"g1c_blind": True},
          "cas_register": {"stale_ok": True}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from harness import spec, stores, verify
    b = spec.Benchmark()
    cell = b.cells[args.workload]
    traffic = b.traffic(cell["traffic"])
    cfg = b.cell_config(cell)
    wl = b.workload_module(cfg)
    count = cfg.get("runs_per_store") or round(
        traffic["rate_per_s"] * b.doc["run_seconds"])
    work = HERE / ".work" / f"calibrate-{cell['name']}"
    for seed in (int(s) for s in args.seeds.split(",")):
        shutil.rmtree(work, ignore_errors=True)
        truth = stores.generate(wl, cfg, work, seed, count)
        picked = verify.sample(truth, seed, traffic["reference_valid"],
                               traffic["reference_invalid"])
        ref = {n: wl.check(work / n / "history.jsonl") for n in picked}
        control = {n: wl.check(work / n / "history.jsonl",
                               **BROKEN[cfg["workload"]]) for n in picked}
        sample_truth = {n: truth[n] for n in picked}
        print(json.dumps({
            "seed": seed, "sample": len(picked),
            "reference": verify.compare(wl, ref, sample_truth, ref),
            "control": verify.compare(wl, control, sample_truth, ref)}),
            flush=True)
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
