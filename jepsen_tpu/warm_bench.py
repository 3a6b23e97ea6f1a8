"""`make bench-warm` — the copy-free warm-path gate.

Runs the smoke-shape cold → warm → warm-again sequence, each sweep in
its OWN process over a shared store and a shared executable-cache
directory, and fails (exit 1) unless the third run proves the warm
path is actually copy-free:

  * `warm_copy_bytes == 0` — every bucket fed `device_put` straight
    from the v2 sidecar's mmap views, no host-side pack copies;
  * `compile_cache_misses == 0` — every dispatch came out of the
    persistent AOT executable cache, zero XLA compiles;
  * verdicts byte-identical across all three runs (the parity floor —
    a fast wrong answer is not a win).

Separate processes are the point: the second warm run starts with an
empty in-memory jit cache and an empty in-memory AOT map, so its 100%
hit rate can only come from the disk layer. One JSON line per run and
one summary line out, `python -m jepsen_tpu.warm_bench` to run by
hand (BENCH_WARM_B/T/K scale the shape).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path


def _write_store(root: Path, B: int, T: int, K: int) -> list[Path]:
    """B serial list-append run dirs (the bench's north-star execution
    shape via the SHARED generator, scaled to smoke size), the last
    one seeded with a G1c cycle so the classify path runs too."""
    from jepsen_tpu.checker.elle.synth import write_synth_store
    return write_synth_store(root, B, T, K, bad_every=B)


def _child(store_dir: str) -> int:
    """One sweep over the store; prints counters + a verdict digest."""
    import time

    from jepsen_tpu import ingest, parallel, trace

    tr = trace.fresh_run("warm-bench")

    def ctr(name: str) -> int:
        return getattr(tr.counter(name), "value", 0) or 0

    dirs = sorted(Path(store_dir).iterdir())
    t0 = time.perf_counter()
    encs = [ingest.encode_run_dir(d, "append") for d in dirs]
    t_ingest = time.perf_counter() - t0
    bad = [e for e in encs if isinstance(e, Exception)]
    assert not bad, bad[:1]
    t0 = time.perf_counter()
    verdicts = parallel.check_bucketed(encs)
    t_check = time.perf_counter() - t0
    digest = hashlib.sha256(
        json.dumps([sorted(v) for v in verdicts]).encode()).hexdigest()
    print(json.dumps({
        "ingest_secs": round(t_ingest, 3),
        "check_secs": round(t_check, 3),
        "verdict_digest": digest,
        "invalid": sum(1 for v in verdicts if v),
        "warm_copy_bytes": ctr("warm_copy_bytes"),
        "h2d_bytes": ctr("h2d_bytes"),
        "compile_cache_hits": ctr("compile_cache_hits"),
        "compile_cache_misses": ctr("compile_cache_misses"),
        "buffers_donated": ctr("buffers_donated"),
        "cache_hits": ctr("cache_hits"),
        "cache_misses": ctr("cache_misses"),
    }))
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--child":
        return _child(argv[1])

    B = int(os.environ.get("BENCH_WARM_B", 6))
    T = int(os.environ.get("BENCH_WARM_T", 60))
    K = int(os.environ.get("BENCH_WARM_K", 8))
    with tempfile.TemporaryDirectory(prefix="bench-warm-") as td:
        store_dir = Path(td) / "store"
        store_dir.mkdir()
        _write_store(store_dir, B, T, K)
        env = {**os.environ,
               "JAX_COMPILATION_CACHE_DIR": str(Path(td) / "cache"),
               "JEPSEN_TPU_TRACE": "1"}
        runs = []
        for name in ("cold", "warm", "warm-again"):
            p = subprocess.run(
                [sys.executable, "-m", "jepsen_tpu.warm_bench",
                 "--child", str(store_dir)],
                capture_output=True, text=True, timeout=600, env=env)
            got = None
            for line in reversed((p.stdout or "").strip().splitlines()):
                try:
                    got = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
            if got is None:
                print(f"bench-warm: {name} run produced no JSON "
                      f"(rc={p.returncode}): "
                      + (p.stderr or "")[-300:], file=sys.stderr)
                return 1
            got["run"] = name
            runs.append(got)
            print(json.dumps(got))

        failures = []
        if len({r["verdict_digest"] for r in runs}) != 1:
            failures.append("verdicts differ across cold/warm runs")
        last = runs[-1]
        if last["warm_copy_bytes"] != 0:
            failures.append(
                f"warm-again copied {last['warm_copy_bytes']} host "
                "bytes (want 0: pack must feed device_put from the "
                "v2 sidecar mmap)")
        if last["compile_cache_misses"] != 0:
            failures.append(
                f"warm-again missed the executable cache "
                f"{last['compile_cache_misses']} time(s) (want 0: a "
                "repeat sweep pays zero XLA compiles)")
        if last["cache_misses"] != 0:
            failures.append(
                f"warm-again re-encoded {last['cache_misses']} "
                "run(s) (want 0: every history hits its sidecar)")
        if failures:
            for f in failures:
                print(f"bench-warm: FAIL: {f}", file=sys.stderr)
            return 1
        print(f"bench-warm: OK — {B}x{T}-txn smoke store: warm path "
              f"copy-free (warm_copy_bytes=0), "
              f"{last['compile_cache_hits']} executable-cache hits, "
              "0 misses, verdicts byte-identical")
        return 0


if __name__ == "__main__":
    sys.exit(main())
