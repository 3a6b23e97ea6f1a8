#!/usr/bin/env python3
"""Chip smoke: the analysis data plane end to end on a TPU.

Runs in ONE process (a chip belongs to one process), through the entry
points a user calls, at the north star's shape:

  A  Elle list-append: 64 histories x 5000 txns (10k ops), K=64 keys,
     concurrency 5, every 8th history carrying a seeded G1c, swept by
     `analyze-store --checker append --backend tpu`. Verdicts must
     equal the seeded truth and the jax-free CPU oracle, with zero
     stored-checker fallbacks and zero quarantines.
  B  Knossos CAS register (BASELINE config 1, etcd-shaped): 256
     single-key histories of 1k ops at concurrency 10, some corrupted,
     swept by `analyze-store --checker register --backend tpu`.
     Verdicts must equal the CPU WGL engine, with no history routed to
     the host.
  C  `serve`: an in-process VerdictDaemon over the phase-A store
     answers 4 `check` requests; the verdicts must be byte-identical
     to phase A's results.json.

`--chips 4` runs only the mesh phase: the phase-A store swept on a
2x2 dp x mp mesh over four local chips, checked again on one device in
the same process; verdicts must be identical and every bucket's
inputs must span all four chips.

Every number printed is a smoke timing, not a benchmark number. The
last line of stdout is `{"ok": true, "device": {...}}` only when every
phase passed; any failure exits non-zero without it. Without a TPU, or
outside a checkout of the repo, it exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
#: Where the phases build their stores: inside the checkout, git-ignored.
WORK = REPO / ".smoke"
NATIVE_LIBS = ("libjepsen_graph.so", "libjepsen_histenc.so",
               "libjepsen_wgl.so")

# phase shapes
APPEND_B, APPEND_T, APPEND_K, BAD_EVERY = 64, 5000, 64, 8
REG_B, REG_OPS, REG_CONC, REG_BAD_EVERY = 256, 1000, 10, 32
SERVE_REQUESTS = 4


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def canon(v) -> str:
    return json.dumps(v, sort_keys=True)


def counters(store: Path) -> dict:
    return json.loads((store / "metrics.json").read_text())["counters"]


def analyze_store(store: Path, checker: str) -> int:
    """One `analyze-store` through the CLI's own entry, in-process; its
    per-run lines go to a file beside the store."""
    from jepsen_tpu import cli
    out = store.parent / f"{store.name}.{checker}.stdout"
    with open(out, "w") as f, contextlib.redirect_stdout(f):
        return cli.run_cli(lambda tmap, args: tmap, argv=[
            "analyze-store", "--store", str(store),
            "--checker", checker, "--backend", "tpu"])


def write_append_store(root: Path, seed: int) -> list[Path]:
    from jepsen_tpu.checker.elle.synth import write_synth_store
    (root / "smoke-append").mkdir(parents=True)
    return write_synth_store(root / "smoke-append", B=APPEND_B,
                             T=APPEND_T, K=APPEND_K,
                             bad_every=BAD_EVERY, seed=seed)


def append_truth(dirs: list[Path]) -> set[str]:
    return {d.name for i, d in enumerate(dirs)
            if i % BAD_EVERY == BAD_EVERY - 1}


def check_sweep_counters(store: Path, what: str) -> dict:
    c = counters(store)
    log(f"{what}: stored_fallbacks={c.get('stored_fallbacks', 0)} "
        f"quarantined={c.get('quarantined', 0)} "
        f"oom_retries={c.get('oom_retries', 0)} "
        f"bucket_splits={c.get('bucket_splits', 0)} "
        f"compile_cache_hits={c.get('compile_cache_hits', 0)} "
        f"compile_cache_misses={c.get('compile_cache_misses', 0)}")
    require(c.get("stored_fallbacks", 0) == 0,
            f"{what}: runs fell back to their stored checker")
    require(c.get("quarantined", 0) == 0, f"{what}: runs quarantined")
    return c


def verdict_core(res: dict) -> dict:
    """What a device verdict and the CPU oracle's must share: validity,
    anomaly classes and sizes (the oracle adds cycle witnesses)."""
    return {"valid?": res["valid?"],
            "anomaly-types": res.get("anomaly-types"),
            "anomalies": sorted(res.get("anomalies") or {}),
            "txn-count": res.get("txn-count"),
            "key-count": res.get("key-count")}


def check_append_verdicts(dirs: list[Path], what: str) -> dict:
    """results.json of every run against the seeded truth."""
    verdicts = {d.name: json.loads((d / "results.json").read_text())
                for d in dirs}
    invalid = {n for n, v in verdicts.items() if v["valid?"] is not True}
    require(invalid == append_truth(dirs),
            f"{what}: invalid runs {sorted(invalid)} != seeded "
            f"{sorted(append_truth(dirs))}")
    require(all(verdicts[n]["anomaly-types"] == ["G1c"]
                for n in invalid),
            f"{what}: seeded runs not classified exactly G1c")
    return verdicts


def phase_append(seed: int) -> tuple[Path, list[Path]]:
    from jepsen_tpu import ingest
    from jepsen_tpu.checker import elle
    store = WORK / "store-append"
    dirs = write_append_store(store, seed)
    rc = analyze_store(store, "append")
    require(rc == 1, f"analyze-store append rc={rc}, want 1 (invalid "
                     f"runs found)")
    c = check_sweep_counters(store, "phase A")
    log(f"phase A compile cache: hits={c.get('compile_cache_hits', 0)} "
        f"misses={c.get('compile_cache_misses', 0)}")
    verdicts = check_append_verdicts(dirs, "phase A")
    # the jax-free CPU oracle on 8 runs, 2 of them seeded invalid
    bad = sorted(append_truth(dirs))
    sample = bad[:2] + [d.name for d in dirs if d.name not in bad][:6]
    prohibited = elle.AppendChecker().prohibited
    for name in sample:
        d = store / "smoke-append" / name
        enc = ingest.encode_run_dir(d, "append")
        res = elle.render_verdict(enc, elle.cycle_anomalies_cpu(enc),
                                  prohibited)
        require(verdict_core(res) == verdict_core(verdicts[name]),
                f"phase A: {name} differs from the CPU oracle")
    log(f"phase A: {len(dirs)} runs, {len(bad)} invalid (G1c) as "
        f"seeded; {len(sample)} equal the CPU oracle")
    return store, dirs


def write_register_store(root: Path, seed: int) -> list[Path]:
    from jepsen_tpu.checker.knossos import synth
    base = root / "smoke-register"
    base.mkdir(parents=True)
    dirs = []
    for i in range(REG_B):
        h = synth.synth_register_history(
            n_ops=REG_OPS, n_procs=REG_CONC, info_prob=0.002,
            seed=seed * 100_000 + i, max_pending=14)
        if i % REG_BAD_EVERY == REG_BAD_EVERY - 1:
            h = synth.corrupt(h, seed=seed * 100_000 + i)
        d = base / f"run-{i:05d}"
        d.mkdir()
        d.joinpath("history.jsonl").write_text("".join(
            json.dumps({**o, "index": j, "time": j * 1000}) + "\n"
            for j, o in enumerate(h)))
        dirs.append(d)
    return dirs


def phase_register(seed: int) -> None:
    from jepsen_tpu.checker import Linearizable, models
    from jepsen_tpu.store import load_history_dir
    store = WORK / "store-register"
    dirs = write_register_store(store, seed)
    rc = analyze_store(store, "register")
    require(rc in (0, 1), f"analyze-store register rc={rc}")
    c = check_sweep_counters(store, "phase B")
    routed = c.get("register_cpu_routed", 0)
    require(routed == 0,
            f"phase B: {routed} histories routed to the CPU engine")
    wgl = Linearizable(models.cas_register())
    invalid = 0
    for d in dirs:
        got = json.loads((d / "results.json").read_text())["valid?"]
        want = wgl._cpu(load_history_dir(d))["valid?"]
        require(got == want, f"phase B: {d.name} device {got} != "
                             f"CPU WGL {want}")
        invalid += want is False
    require(invalid > 0, "phase B: no non-linearizable history")
    log(f"phase B: {len(dirs)} runs equal CPU WGL ({invalid} "
        f"non-linearizable)")


def phase_serve(store: Path, dirs: list[Path]) -> None:
    from jepsen_tpu import trace
    from jepsen_tpu.serve.client import ServeClient
    from jepsen_tpu.serve.daemon import VerdictDaemon
    from jepsen_tpu.store import Store
    picked = dirs[BAD_EVERY - 2:BAD_EVERY + SERVE_REQUESTS - 2]
    prev = trace.get_current()      # the daemon replaces the tracer
    daemon = None
    try:
        daemon = VerdictDaemon(Store(store)).start()
        sock = daemon.ready_info()["serve"]["socket"]
        with ServeClient(socket_path=sock, tenant="smoke") as c:
            for i, d in enumerate(picked):
                c.check_dir(d, rid=f"smoke:{i}")
            got = c.collect(timeout=900)
        rc = daemon.stop()
        daemon = None
        require(rc == 0, f"phase C: daemon drain rc={rc}")
    finally:
        if daemon is not None:
            daemon.stop()
        trace.set_current(prev)
    for i, d in enumerate(picked):
        want = json.loads((d / "results.json").read_text())
        require(canon(got.get(f"smoke:{i}")) == canon(want),
                f"phase C: serve verdict for {d.name} differs from "
                f"analyze-store")
    log(f"phase C: {len(picked)} serve verdicts byte-identical to "
        f"analyze-store")


def phase_mesh(seed: int) -> None:
    """analyze-store on a 2x2 dp x mp mesh over four chips, and the
    same store on one device in this process."""
    from jepsen_tpu import cli, ingest, parallel
    from jepsen_tpu.checker import elle
    import jax
    require(len(jax.devices()) == 4,
            f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    require(parallel.factor2(4) == (2, 2), "factor2(4) != (2, 2)")
    store = WORK / "store-mesh"
    dirs = write_append_store(store, seed)
    placed: list[list] = []
    shard_batch = parallel.shard_batch

    def recording_shard_batch(mesh, packed):
        args = shard_batch(mesh, packed)
        placed.append(sorted(d.id for d in args[0].sharding.device_set))
        return args

    parallel.shard_batch = recording_shard_batch
    try:
        rc = analyze_store(store, "append")
    finally:
        parallel.shard_batch = shard_batch
    require(rc == 1, f"mesh analyze-store rc={rc}, want 1")
    check_sweep_counters(store, "mesh sweep")
    verdicts = check_append_verdicts(dirs, "mesh sweep")
    spans = sorted({tuple(p) for p in placed})
    log(f"mesh sweep: {len(placed)} buckets; input device sets "
        f"{spans}")
    require(placed and all(p == [0, 1, 2, 3] for p in placed),
            f"mesh sweep: buckets not sharded over all 4 chips: {spans}")
    # the same store on one device, same process
    encs = [ingest.encode_run_dir(d, "append") for d in dirs]
    one = parallel.check_bucketed(encs, None)
    prohibited = elle.AppendChecker().prohibited
    for d, enc, cycles in zip(dirs, encs, one):
        res = elle.render_verdict(enc, cycles, prohibited)
        res["checker"] = "append"
        require(canon(cli._json_safe(res)) == canon(verdicts[d.name]),
                f"mesh vs one device: {d.name} differs")
    log(f"mesh sweep: {len(dirs)} verdicts identical to one device")


def build_native() -> None:
    """Build the ctypes helpers from the committed sources."""
    p = subprocess.run(
        ["make", "-C", str(REPO / "native"), "-B",
         *(f"build/{lib}" for lib in NATIVE_LIBS)],
        capture_output=True, text=True, timeout=600)
    require(p.returncode == 0,
            f"native build failed: {p.stderr[-500:]}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (REPO / "jepsen_tpu" / "__init__.py").is_file():
        print("chip_smoke: not inside a jepsen-tpu checkout",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devs[0].platform}", file=sys.stderr)
        return 2
    t_all = time.perf_counter()
    failures: list[str] = []
    timings: dict[str, float] = {}

    def phase(name: str, fn, *a):
        t0 = time.perf_counter()
        try:
            return fn(*a)
        except Exception as e:   # every phase runs; any failure fails
            failures.append(f"{name}: {e!r}"[:600])
            log(f"{name} FAILED: {e!r}"[:600])
            return None
        finally:
            timings[name] = time.perf_counter() - t0
            log(f"{name}: {timings[name]:.3f} s (smoke timing, not a "
                f"benchmark number)")

    from jepsen_tpu import aot, devices, native_lib
    log(f"device: {devs[0].device_kind} x{len(devs)}; host PCI TPU "
        f"chips: {devices.host_chip_count()}")
    log(f"compile cache: {aot.configure_jax_cache()}")
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    phase("native build", build_native)
    libs = {"graph": native_lib.lib(), "histenc": native_lib.hist_lib(),
            "wgl": native_lib.wgl_lib()}
    log("native libraries active: " + ", ".join(
        f"{k}={v is not None}" for k, v in libs.items()))
    if not all(libs.values()):
        failures.append("native libraries did not load")

    if args.chips == 4:
        phase("mesh", phase_mesh, args.seed)
    else:
        a = phase("A append", phase_append, args.seed)
        phase("B register", phase_register, args.seed)
        if a is not None:
            phase("C serve", phase_serve, *a)
        else:
            failures.append("C serve: skipped, phase A failed")
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"peak device memory {d.id}: "
            f"{stats.get('peak_bytes_in_use')} bytes")
    log(f"total: {time.perf_counter() - t_all:.3f} s (smoke timing)")
    log(f"serialized executables under {aot.cache_dir()}: "
        f"{len(list(aot.cache_dir().glob('*.jtx')))}")
    if failures:
        for f in failures:
            print(f"chip_smoke FAILED: {f}", file=sys.stderr)
        return 1
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
