"""L8: the command-line interface.

Counterpart of jepsen.cli (jepsen/src/jepsen/cli.clj): per-suite mains
call `run_cli(test_fn=...)` to get `test`, `analyze`, and `serve`
subcommands with the standard option set (cli.clj:55-99) and exit codes
(cli.clj:117-127):

    0    test ran and was valid
    1    test ran and was invalid
    2    validity unknown
    254  usage error
    255  crash
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from pathlib import Path
from typing import Callable

from . import core, gates, trace
from .store import Store

log = logging.getLogger(__name__)


def validity_exit_code(results: dict | None) -> int:
    v = (results or {}).get("valid?")
    if v is True:
        return 0
    if v == "unknown" or v is None:
        return 2
    return 1


def add_test_opts(p: argparse.ArgumentParser) -> None:
    p.add_argument("--node", "-n", action="append", dest="nodes",
                   metavar="HOST", help="node to test (repeatable)")
    p.add_argument("--nodes-file", help="file with one node per line")
    p.add_argument("--username", default="root")
    p.add_argument("--password")
    p.add_argument("--port", type=int, default=22)
    p.add_argument("--private-key-path")
    p.add_argument("--dummy", action="store_true",
                   help="use the no-op dummy remote")
    p.add_argument("--concurrency", default="1n",
                   help="worker count; 'Nn' means N per node")
    p.add_argument("--time-limit", type=float, default=60.0,
                   help="seconds of main workload")
    p.add_argument("--test-count", type=int, default=1)
    p.add_argument("--leave-db-running", action="store_true")
    p.add_argument("--store", default="store", help="store directory")
    p.add_argument("--faults", default=None,
                   help="comma list for the combined nemesis bundle "
                        "(partition,kill,pause,clock) — swaps the "
                        "suite's default nemesis for the composed "
                        "package (combined.clj:318-364)")
    p.add_argument("--backend", default="auto",
                   choices=["auto", "tpu", "cpu", "race"],
                   help="analysis backend: device kernels (tpu), host "
                        "oracles (cpu), or pick by hardware (auto — "
                        "the default; the north star's :backend :tpu "
                        "is the production path when a chip is up)")
    add_trace_opts(p)


def add_trace_opts(p: argparse.ArgumentParser) -> None:
    p.add_argument("--trace", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="write trace.json (Chrome trace-event / "
                        "Perfetto) + metrics.json into the run dir "
                        "(default on; --no-trace or JEPSEN_TPU_TRACE=0 "
                        "disables)")
    p.add_argument("--jax-profile",
                   action=argparse.BooleanOptionalAction, default=None,
                   help="additionally capture a jax.profiler session "
                        "of the run (sets JEPSEN_TPU_JAX_PROFILE; "
                        "lands in <run-dir>/jax-profile; "
                        "--no-jax-profile overrides an inherited env)")


def apply_trace_opts(args: argparse.Namespace) -> None:
    """Export --trace/--no-trace/--jax-profile to the env gates every
    layer reads (JEPSEN_TPU_TRACE / JEPSEN_TPU_JAX_PROFILE), so
    embedded callers and subprocesses see the same choice."""
    if getattr(args, "trace", None) is not None:
        gates.export("JEPSEN_TPU_TRACE", args.trace)
        trace.reset()
    if getattr(args, "jax_profile", None) is not None:
        gates.export("JEPSEN_TPU_JAX_PROFILE", args.jax_profile)


def _trace_path_of(test: dict) -> str | None:
    """The run's written trace.json path (None when tracing is off)."""
    try:
        p = test["store"].test_dir(test) / "trace.json"
        return str(p) if p.exists() else None
    except Exception:
        return None


def _print_result_line(test: dict, line: dict) -> None:
    """The one-line JSON result every run-style subcommand prints,
    with the run's written trace.json path attached when one exists."""
    tp = _trace_path_of(test)
    if tp:
        line["trace"] = tp
    print(json.dumps(line))


def test_map_from_args(args: argparse.Namespace) -> dict:
    nodes = list(args.nodes or [])
    if args.nodes_file:
        nodes += [ln.strip() for ln in
                  Path(args.nodes_file).read_text().splitlines()
                  if ln.strip()]
    t: dict = {
        "backend": getattr(args, "backend", "auto"),
        "concurrency": args.concurrency,
        **({"faults": [f.strip() for f in args.faults.split(",")
                       if f.strip()]}
           if getattr(args, "faults", None) else {}),
        "time_limit": args.time_limit,
        "leave_db_running": args.leave_db_running,
        "store": Store(args.store),
        "ssh": {"username": args.username, "password": args.password,
                "port": args.port, "private_key_path": args.private_key_path,
                "dummy": args.dummy},
    }
    if nodes:
        t["nodes"] = nodes
    return t


def run_cli(test_fn: Callable[[dict, argparse.Namespace], dict],
            name: str = "jepsen-tpu", opt_fn=None,
            argv: list[str] | None = None,
            tests_fn: Callable[[dict, argparse.Namespace], list] | None
            = None) -> int:
    """Build and dispatch the CLI. `test_fn(base_test, args)` returns the
    full test map; `opt_fn(parser)` may add suite-specific options;
    `tests_fn(base_test, args)` returns the list of test maps run by the
    `test-all` subcommand (defaults to the single test_fn test)."""
    parser = argparse.ArgumentParser(prog=name)
    sub = parser.add_subparsers(dest="command", required=True)

    p_test = sub.add_parser("test", help="run a test")
    add_test_opts(p_test)
    if opt_fn:
        opt_fn(p_test)

    p_an = sub.add_parser("analyze",
                          help="re-run the checker on a stored history")
    p_an.add_argument("run_dir", nargs="?",
                      help="store run dir (default: latest)")
    # The same option set as `test` (including --store), so test_fn sees
    # a complete args namespace when rebuilding checkers (cli.clj:381-411).
    add_test_opts(p_an)
    if opt_fn:
        opt_fn(p_an)

    p_all = sub.add_parser(
        "test-all",
        help="run a whole suite of tests (cli.clj:413-491's test-all)")
    add_test_opts(p_all)
    if opt_fn:
        opt_fn(p_all)

    p_batch = sub.add_parser(
        "analyze-store",
        help="batch re-check every stored run on the device mesh "
             "(the north-star batch path)")
    p_batch.add_argument("--store", default="store")
    p_batch.add_argument("--checker", default="append",
                         choices=["append", "wr", "register", "stored"],
                         help="append/wr: encode histories and batch-"
                              "check on the mesh; register: per-key "
                              "CAS linearizability, every key of every "
                              "run in one dense-kernel sweep; stored: "
                              "re-run each run's own checker")
    p_batch.add_argument("--name", default=None,
                         help="only runs of this test name")
    p_batch.add_argument("--backend", default="auto",
                         choices=["auto", "tpu", "cpu", "race"])
    p_batch.add_argument("--resume", action="store_true",
                         help="continue an interrupted sweep: skip "
                              "runs this checker already verdicted "
                              "(results.json naming the checker, or "
                              "the fallback's .sweep-* sidecar)")
    p_batch.add_argument("--report", action="store_true",
                         help="write the critical-path attribution "
                              "report (<store>/report.json + "
                              "report.md) from the merged sweep "
                              "timeline at exit (JEPSEN_TPU_REPORT=1 "
                              "is the env equivalent; needs tracing "
                              "on)")
    p_batch.add_argument("--mesh", action="store_true",
                         help="run as ONE SHARD of a multi-host mesh "
                              "sweep (JEPSEN_TPU_MESH=1 is the env "
                              "equivalent): deterministic shard of "
                              "the run dirs, per-shard "
                              "verdicts-<shard>.jsonl journal + "
                              "trace-shard<k>.json artifacts, "
                              "coordinator merge on shard 0; shard "
                              "identity from JEPSEN_TPU_MESH_SHARD/"
                              "_SHARDS or the jax.distributed job")
    add_trace_opts(p_batch)

    p_serve = sub.add_parser(
        "serve",
        help="run the multi-tenant verdict daemon: tenants stream "
             "histories over a local socket and get verdicts back "
             "while their tests run (continuous batching, per-tenant "
             "fairness, journaled verdicts; analyze-store remains the "
             "batch path). --web serves the legacy HTTP store browser "
             "instead.")
    p_serve.add_argument("--port", type=int, default=None,
                         help="TCP port for the daemon (default: unix "
                              "socket <store>/serve.sock); with --web, "
                              "the HTTP port (default 8080)")
    p_serve.add_argument("--host", default=None,
                         help="bind address (default 127.0.0.1 for "
                              "the daemon, the historical 0.0.0.0 "
                              "for --web)")
    p_serve.add_argument("--store", default="store")
    p_serve.add_argument("--socket", default=None,
                         help="unix-socket path the daemon listens on "
                              "(default <store>/serve.sock; "
                              "JEPSEN_TPU_SERVE_SOCKET is the env "
                              "equivalent)")
    p_serve.add_argument("--drain-timeout", type=float, default=None,
                         help="seconds to drain admitted work on "
                              "SIGTERM (default "
                              "JEPSEN_TPU_SERVE_DRAIN_S)")
    p_serve.add_argument("--web", action="store_true",
                         help="serve the legacy HTTP store browser "
                              "instead of the verdict daemon")
    p_serve.add_argument("--fleet-instance", type=int, default=None,
                         help="run as member <k> of a serve fleet "
                              "(the `fleet` subcommand spawns these): "
                              "bind fleet-d<k>.sock, heartbeat the "
                              "fleet-d<k>.json beacon, honor the "
                              "epoch fence")
    p_serve.add_argument("--fleet-epoch", type=int, default=None,
                         help="the membership epoch this member was "
                              "started under (the fleet router sets "
                              "it)")
    add_trace_opts(p_serve)

    p_fleet = sub.add_parser(
        "fleet",
        help="run N verdict daemons behind a fault-tolerant router: "
             "tenants connect to one fleet socket; the router "
             "hash-affines them to daemons, spills on backpressure, "
             "and on a daemon death replays its tenants' journals on "
             "a successor (zero lost or duplicated verdicts)")
    p_fleet.add_argument("--store", default="store")
    p_fleet.add_argument("--daemons", type=int, default=3,
                         help="fleet size (default 3)")
    p_fleet.add_argument("--socket", default=None,
                         help="router socket path (default "
                              "<store>/fleet.sock)")
    p_fleet.add_argument("--no-stonith", action="store_true",
                         help="skip the router's best-effort SIGKILL "
                              "of a daemon it declares dead (nemesis "
                              "harnesses that manage the process "
                              "themselves set this)")
    add_trace_opts(p_fleet)

    from . import lint as _lint   # stdlib-only, import-cheap
    p_lint = sub.add_parser(
        "lint",
        help="self-hosted static analysis (gate registry, JAX "
             "hazards, concurrency, shm lifecycle, tracer discipline)")
    _lint.add_args(p_lint)

    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        return 254 if e.code not in (0, None) else 0

    if args.command == "lint":
        # no logging/backend/trace setup: lint parses source, it never
        # imports or executes the target package
        return _lint.run_from_args(args)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s [%(name)s] %(message)s")

    # Hung sweeps must be debuggable in production: SIGUSR1 dumps
    # every thread's stack (faulthandler) without killing the process
    # — `kill -USR1 <pid>` answers "where is it stuck" on a wedged
    # device wait or a parked pool. Best-effort: unavailable off the
    # main thread and on platforms without SIGUSR1.
    try:
        import faulthandler
        import signal as _signal
        # the REAL stderr fd: sys.stderr may be a captured/fileno-less
        # wrapper (pytest, some embedders), which faulthandler rejects
        faulthandler.register(_signal.SIGUSR1, all_threads=True,
                              chain=True, file=sys.__stderr__)
    except (AttributeError, ValueError, OSError, ImportError):
        pass

    # Every auto-backend checker constructed from here on resolves per
    # this process-wide choice (devices.resolve_backend).
    if getattr(args, "backend", None) and args.backend != "auto":
        gates.export("JEPSEN_TPU_BACKEND", args.backend)
    apply_trace_opts(args)

    try:
        if args.command == "test":
            code = 0
            for i in range(args.test_count):
                test = test_fn(test_map_from_args(args), args)
                test = core.run(test)
                _print_result_line(test, {
                    "valid?": test["results"].get("valid?"),
                    "dir": str(test["store"].test_dir(test))})
                code = max(code, validity_exit_code(test.get("results")))
                if code:
                    break
            return code
        if args.command == "analyze":
            store = Store(args.store)
            run_dir = args.run_dir or store.latest()
            if run_dir is None:
                print("no stored runs", file=sys.stderr)
                return 254
            stored = store.load_test(run_dir)
            test = test_fn(stored, args)
            test.setdefault("name", stored.get("name", "analyze"))
            from . import independent
            # json/edn round trips erase the lifted-tuple type; re-lift
            # so per-key checkers split the history again
            test["history"] = independent.relift_history(
                stored["history"])
            test["store"] = store
            trace.fresh_run(test.get("name"))
            with trace.jax_profile_session(
                    Path(run_dir) / "jax-profile"):
                test = core.analyze(test)
            _print_result_line(test,
                               {"valid?": test["results"].get("valid?")})
            return validity_exit_code(test["results"])
        if args.command == "test-all":
            tests = (tests_fn(test_map_from_args(args), args)
                     if tests_fn is not None
                     else [test_fn(test_map_from_args(args), args)])
            worst = 0
            for test in tests:
                try:
                    test = core.run(test)
                    code = validity_exit_code(test.get("results"))
                    _print_result_line(test, {
                        "name": test.get("name"),
                        "valid?": test["results"].get("valid?"),
                        "dir": str(test["store"].test_dir(test))})
                except Exception as e:
                    log.exception("test %s crashed", test.get("name"))
                    print(json.dumps({"name": test.get("name"),
                                      "error": str(e)}))
                    code = 255
                worst = max(worst, code)
            return worst
        if args.command == "analyze-store":
            if args.mesh:
                # flag→env export, like --backend: embedded callers
                # and subprocesses of this sweep see the same choice
                gates.export("JEPSEN_TPU_MESH", True)
            return analyze_store(Store(args.store), checker=args.checker,
                                 name=args.name, resume=args.resume,
                                 report=args.report or None,
                                 mesh=args.mesh or None)
        if args.command == "serve":
            if args.web:
                from . import web
                web.serve(Store(args.store),
                          host=args.host or "0.0.0.0",
                          port=args.port if args.port is not None
                          else 8080)
                return 0
            from .serve import run_daemon
            return run_daemon(Store(args.store),
                              socket_path=args.socket, port=args.port,
                              host=args.host or "127.0.0.1",
                              drain_s=args.drain_timeout,
                              fleet_instance=args.fleet_instance,
                              fleet_epoch=args.fleet_epoch)
        if args.command == "fleet":
            from .serve.fleet import run_fleet
            return run_fleet(Store(args.store), daemons=args.daemons,
                             socket_path=args.socket,
                             stonith=not args.no_stonith)
        return 254
    except KeyboardInterrupt:
        return 255
    except Exception:
        log.exception("fatal error")
        return 255


def analyze_store(store: Store, checker: str = "append",
                  name: str | None = None,
                  resume: bool = False, obs_hook=None,
                  report: bool | None = None,
                  mesh: bool | None = None) -> int:
    """`_analyze_store_impl` wrapped in a fresh sweep tracer: the whole
    sweep's spans (ingest parse, pack/h2d/dispatch/collect phases,
    device windows, per-checker fallbacks) export to
    `<store>/trace.json` + `metrics.json` at exit, printing the path —
    the sweep-level analogue of the per-run artifacts save_2 writes.
    Since the trace fabric the exported trace.json is MERGED: each
    pool worker's span spool (`trace-<pid>.jsonl`, written per task)
    folds in as its own real-pid process track, so encode time is
    visible per worker, not inferred from parent stalls. With
    `report` (the `--report` flag; None defers to JEPSEN_TPU_REPORT)
    the critical-path attribution report (`report.json` +
    `report.md`) is derived from the same merged timeline.

    Sweep start also reclaims /dev/shm segments a previous crashed
    run's dead pid left behind (`shm_stale_reclaimed` counter), and
    every verdict appends to the store's `verdicts.jsonl` journal as
    it lands — `--resume` reads it back and skips the journaled
    (run, checker) pairs, so an interrupted sweep restarts where it
    died.

    Live telemetry (jepsen_tpu.obs) wraps the whole sweep: the flight
    recorder (`<store>/events.jsonl`) always records the lifecycle;
    `JEPSEN_TPU_HEALTH_INTERVAL_S` additionally starts the health
    sampler (`<store>/health.json`, atomic, every N s) and
    `JEPSEN_TPU_METRICS_PORT` the `/metrics`+`/healthz` endpoint —
    both off by default, costing nothing when unset. `obs_hook(server,
    sampler)` is a test/smoke seam called once the obs layer is up.

    With `mesh` (the `--mesh` flag; None defers to JEPSEN_TPU_MESH)
    this process sweeps ONE SHARD of a multi-host mesh sweep
    (jepsen_tpu.mesh): a deterministic hash-split of the run dirs,
    journaled to `verdicts-<shard>.jsonl` (resume stays strictly
    per-shard), dispatched on this host's LOCAL devices via the same
    warm path, traced to `trace-shard<k>.json` with the shard id in
    every track name; the coordinator (shard 0) then merges journals,
    traces, metrics and — with `report` — the per-shard attribution
    report once the fleet's done markers land. A shard that never
    reports is LOST (exit ≥2, runs unverdicted) and re-assignable
    with `JEPSEN_TPU_MESH_SHARD=<k> --mesh --resume` — the
    supervisor's degradation contract at fleet scale."""
    from . import aot
    from . import mesh as meshmod
    from . import obs
    from . import shm as _shm
    from . import supervisor as sv
    from .obs import device as device_obs
    from .obs import search as search_obs
    from . import jaxtrace
    from .store import VerdictJournal, analytics_path, costdb_path
    aot.configure_jax_cache()
    jaxtrace.install()
    if report is None:
        report = gates.get("JEPSEN_TPU_REPORT")
    if mesh is None:
        mesh = meshmod.mesh_enabled()
    shard = n_shards = None
    run_name = f"analyze-store:{checker}"
    if mesh:
        shard, n_shards = meshmod.resolve_shard()
        # the shard id rides the tracer's run name, so every process
        # track of this shard's trace carries it after the merge
        run_name = f"{run_name}@shard{shard}/{n_shards}"
    tr = trace.fresh_run(run_name, scope="sweep")
    # the device cost observatory is per-sweep state like the tracer:
    # a fresh sweep must not inherit a previous sweep's records or
    # half-open dispatch windows (no-op-cheap; gate read at capture)
    device_obs.reset()
    # so is the kernel search-telemetry ledger (JEPSEN_TPU_KERNEL_STATS)
    search_obs.reset()
    # the cost-aware planner is per-sweep state too: load the store's
    # persisted plan.json (warm start) or run cold — a no-op with
    # JEPSEN_TPU_PLANNER off
    from . import planner as planner_mod
    planner_mod.activate(store.base)
    if getattr(tr, "enabled", False) and store.base.is_dir():
        # point the worker trace fabric at the store: pool workers
        # spool spans to <spool_dir>/trace-<pid>.jsonl; stale spools
        # from a previous sweep are derived artifacts keyed by trace
        # id — cleared here so the dir holds exactly this sweep's set.
        # Mesh shards share the store CONCURRENTLY and two hosts'
        # workers can even share a pid, so each shard owns its own
        # spool subdirectory (trace.shard_spool_dir) — cleaning it
        # can't race a sibling, and spool names can't collide.
        sd = store.base if not mesh \
            else trace.shard_spool_dir(store.base, shard)
        sd.mkdir(exist_ok=True)
        trace.clean_spools(sd)
        tr.spool_dir = sd
    elif report:
        print("attribution report needs tracing on "
              "(JEPSEN_TPU_TRACE=0 set); skipping", file=sys.stderr)
    tr.counter("shm_stale_reclaimed").inc(_shm.reclaim_stale())
    journal = VerdictJournal(
        meshmod.shard_journal_path(store.base, shard) if mesh
        else store.base / "verdicts.jsonl", base=store.base)
    if mesh and store.base.is_dir():
        sv.mark_shard_start(store.base, shard)
    obs.install_events(store.base)
    obs.emit("sweep_start", checker=checker, resume=bool(resume),
             store=str(store.base),
             **({"shard": shard, "shards": n_shards} if mesh else {}))
    sampler = obs.maybe_start_health_sampler(store.base)
    server = obs.maybe_start_metrics_server(
        health_fn=(sampler.write_snapshot if sampler is not None
                   else None))
    rc: int | None = None
    try:
        if obs_hook is not None:
            obs_hook(server, sampler)
        with trace.jax_profile_session(store.base / "jax-profile"):
            rc = _analyze_store_impl(store, checker=checker,
                                     name=name, resume=resume,
                                     journal=journal, shard=shard,
                                     n_shards=n_shards)
    finally:
        journal.close()
        obs.emit("sweep_end",
                 exit_code=rc if rc is not None else "crashed")
        if sampler is not None:
            sampler.stop()
        if server is not None:
            server.stop()
        if store.base.is_dir():
            # the costdb lands whether or not tracing was on: the
            # observatory's windows are measured with perf_counter
            # directly, and the planner's training data must not
            # depend on the trace gate. flush() is a no-op (zero
            # files) with JEPSEN_TPU_COSTDB off. It runs BEFORE
            # reset_events so its costdb_flush mark reaches the
            # flight recorder.
            try:
                n_cost = device_obs.flush(
                    costdb_path(store.base, shard if mesh else None))
                if n_cost:
                    print(f"costdb: {n_cost} record(s) appended to "
                          f"{costdb_path(store.base, shard if mesh else None)}",
                          file=sys.stderr)
            except Exception:
                log.warning("costdb flush failed", exc_info=True)
            # the analytics ledger follows the same contract: journal
            # before reset_events so its flight-recorder mark lands;
            # zero files with the gate off
            try:
                n_stats = search_obs.flush(
                    analytics_path(store.base,
                                   shard if mesh else None))
                if n_stats:
                    print(f"analytics: {n_stats} record(s) appended "
                          f"to "
                          f"{analytics_path(store.base, shard if mesh else None)}",
                          file=sys.stderr)
            except Exception:
                log.warning("analytics flush failed", exc_info=True)
            # sweep-end planner refit from the full on-disk tables
            # (this sweep's fresh records included): plan.json is what
            # the NEXT sweep and the daemon warm-start from. Mesh
            # shards skip it — the coordinator refits once over the
            # merged fleet tables instead.
            if not mesh and planner_mod.enabled():
                try:
                    from .store import load_analytics, load_costdb
                    plan = planner_mod.refresh(
                        store.base,
                        load_costdb(costdb_path(store.base)),
                        load_analytics(analytics_path(store.base)))
                    if plan is not None:
                        print(f"planner: plan.json refit from "
                              f"{plan['trained_records']} record(s)",
                              file=sys.stderr)
                except Exception:
                    log.warning("planner refresh failed",
                                exc_info=True)
        obs.reset_events()
        if getattr(tr, "enabled", False) and store.base.is_dir():
            try:
                # the merged export: parent events + every worker
                # spool of THIS sweep (from this sweep's own spool
                # dir), one real-pid track per worker
                evs = trace.merge_traces(tr)
                if mesh:
                    # a resume that re-checked nothing records no
                    # timed events: keep the PREVIOUS shard trace —
                    # it is still the evidence for how this shard's
                    # journaled verdicts were produced, and the
                    # coordinator's per-shard attribution needs it
                    timed = any(e.get("ph") != "M" for e in evs)
                    sp = trace.shard_trace_path(store.base, shard)
                    if timed or not sp.exists():
                        p = trace.export_shard_trace(
                            tr, store.base, shard, n_shards, evs)
                        tr.export_metrics(
                            store.base / f"metrics-shard{shard}.json")
                        print(f"shard trace written to {p}",
                              file=sys.stderr)
                    else:
                        print(f"shard {shard}: no new events; "
                              f"keeping {sp}", file=sys.stderr)
                else:
                    p = trace.atomic_write_text(
                        store.base / "trace.json",
                        json.dumps({"traceEvents": evs,
                                    "displayTimeUnit": "ms"}))
                    tr.export_metrics(store.base / "metrics.json")
                    print(f"trace written to {p}", file=sys.stderr)
                    if report:
                        from .obs import attribution
                        rj, _rmd = attribution.write_report(
                            store.base, evs, tr.metrics_dict(),
                            device_records=(device_obs.records()
                                            if device_obs.enabled()
                                            else None),
                            search_records=(search_obs.records()
                                            if search_obs.enabled()
                                            else None))
                        print(f"report written to {rj}",
                              file=sys.stderr)
            except Exception:
                log.warning("sweep trace export failed", exc_info=True)
        if mesh and store.base.is_dir():
            # the done marker is the LAST artifact: a coordinator that
            # sees it may merge this shard's journal + trace right away
            sv.mark_shard_done(store.base, shard, {
                "shard": shard, "shards": n_shards, "checker": checker,
                "exit_code": rc if rc is not None else "crashed"})
    if mesh:
        return meshmod.coordinator_merge(store, checker, shard,
                                         n_shards, rc, report=report,
                                         tracer=tr, name=name)
    return rc


def _analyze_store_impl(store: Store, checker: str = "append",
                        name: str | None = None,
                        resume: bool = False,
                        journal=None, shard: int | None = None,
                        n_shards: int | None = None) -> int:
    """Batch re-check every stored run — the north-star batch path
    (SURVEY.md §3.4, §7 stage 8): encodable histories are packed,
    length-bucketed, and dispatched across the device mesh in one sweep;
    the rest (or --checker stored) re-run their own checker host-side.

    Writes `results.json`/`results.edn` into each run dir and prints one
    JSON summary line per run. Exit code: worst validity across runs.
    With `shard`/`n_shards` (a mesh sweep) only this shard's
    deterministic slice of the run dirs is walked — the store iterator
    applies the hash split during the (lazy) listing itself, so no
    host ever builds the other shards' run list."""
    from .store import VerdictJournal
    run_dirs = list(store.iter_run_dirs(
        name=name, shard=shard,
        n_shards=n_shards if n_shards is not None else 1))
    prior_worst = 0
    if resume:
        # resumable analysis (SURVEY.md §5.4): skip runs THIS sweep
        # already verdicted — journaled in verdicts.jsonl (appended
        # per history as results land, so it survives a SIGKILL of
        # the sweep) or carrying the per-run marker (which records
        # which checker wrote it, so an append sweep never masks a
        # pending wr sweep). Skipped runs still contribute their
        # recorded validity to the exit code — an invalid verdict
        # from the completed part of an interrupted sweep must not
        # read as success.
        # per-shard resume reads THIS shard's journal only (the
        # journal threaded in is already verdicts-<shard>.jsonl):
        # cross-host resume must never read — or race — another
        # shard's evidence
        journaled = VerdictJournal.load(
            journal.path if journal is not None
            else store.base / "verdicts.jsonl")
        rel = journal.rel if journal is not None else str
        pending = []
        for d in run_dirs:
            ent = journaled.get((rel(d), checker))
            if _verdicted(d, checker):
                prior_worst = max(prior_worst, _prior_code(d, checker))
            elif ent is not None:
                prior_worst = max(prior_worst,
                                  validity_exit_code(ent))
            else:
                pending.append(d)
        from . import obs
        obs.emit("sweep_resume", skipped=len(run_dirs) - len(pending),
                 pending=len(pending))
        if not pending and run_dirs:
            print(f"all {len(run_dirs)} runs already verdicted "
                  f"({checker}); nothing to resume", file=sys.stderr)
            return prior_worst
        run_dirs = pending
    if not run_dirs:
        if shard is not None \
                and next(store.iter_run_dirs(name=name), None) \
                is not None:
            # a legitimate mesh assignment, not a usage error: the
            # hash split left this shard nothing (tiny store, many
            # shards) — the shard completes empty so the coordinator
            # can still merge the fleet
            print(f"shard {shard}/{n_shards}: no runs assigned",
                  file=sys.stderr)
            return prior_worst
        print("no stored runs", file=sys.stderr)
        return 254
    # live-telemetry progress denominators: the health sampler reads
    # these from the sweep tracer (runs_verdicted ticks per verdict)
    trace.get_current().gauge("runs_total").set(len(run_dirs))

    # multi-host pods: join the job before any device work so meshes
    # span every host's chips (no-op without a coordinator env)
    if checker != "stored":
        from . import parallel as _parallel
        try:
            _parallel.init_distributed()
        except Exception:
            log.warning("jax.distributed init failed; continuing "
                        "single-process", exc_info=True)

    def stored_check(d) -> dict:
        stored = store.load_test(d)
        test = dict(stored)
        test["store"] = store
        return core.analyze(test)["results"]

    def emit(d, res):
        return _write_results(d, res, checker, journal=journal)

    worst = prior_worst
    if checker == "stored":
        for d in run_dirs:
            worst = max(worst,
                        _stored_fallback(d, stored_check, "stored",
                                         journal=journal))
        return worst

    if checker == "register":
        return max(prior_worst,
                   _analyze_store_register(store, run_dirs,
                                           stored_check,
                                           journal=journal))

    from . import parallel
    from .checker import elle
    from .checker.elle import kernels as elle_kernels
    from .checker.elle import wr as elle_wr
    import os as _os

    # An EXPLICIT --backend cpu (the dispatcher exports it) routes the
    # sweep through the host oracle. Auto stays on the batched kernels:
    # they run on whatever devices exist — that's the north-star sweep,
    # and on CPU-only hosts it doubles as the virtual-mesh dryrun.
    host_only = gates.get("JEPSEN_TPU_BACKEND") == "cpu"

    # Kernel search telemetry (JEPSEN_TPU_KERNEL_STATS): dispatches
    # additionally return per-history stats rows, recorded into the
    # per-sweep ledger keyed by the SAME store-relative dir string the
    # verdict journal uses. The host-oracle sweep runs no kernels, so
    # it records nothing for the elle checkers.
    from .obs import search as search_obs
    want_stats = search_obs.enabled() and not host_only
    _rel = journal.rel if journal is not None else str

    def record_stats(d, checker_name: str, sd, cycles=None) -> None:
        if sd is not None:
            search_obs.record(
                _rel(d), checker_name, sd,
                anomalies=(cycles if isinstance(cycles, dict)
                           else None))

    # Encodable histories get the batched device sweep; the rest fall
    # back to their own stored checker host-side. Ingest shards run
    # dirs across a process pool (ingest.py, SURVEY.md §5.7).
    from . import ingest

    def encodable(d, enc, fallback: list) -> bool:
        """Shared triage, with per-history isolation: a run whose
        encode raised (the pool returns the per-run exception) gets
        ONE more chance through its own stored checker — a wr sweep
        over an append-shaped store is unencodable yet perfectly
        checkable — and if that fails too, `_stored_fallback`
        quarantines it as a `valid? unknown` verdict instead of
        killing the sweep (JEPSEN_TPU_STRICT=1 restores fail-fast).
        A self-nemesis InjectedFault skips the detour: the injection
        simulates a poisoned history, whose terminal state IS
        quarantine. Txn-less histories are no failure at all and
        route to the stored checker as before."""
        nonlocal worst
        if isinstance(enc, Exception):
            from . import supervisor
            if isinstance(enc, supervisor.InjectedFault):
                worst = max(worst, _quarantine_run(d, enc, "encode",
                                                   checker,
                                                   journal=journal))
                return False
            log.info("run %s not encodable as %s (%r); using stored "
                     "checker", d, checker, enc)
            fallback.append(d)
            return False
        if enc.n == 0:   # no txn ops at all: not a txn workload
            fallback.append(d)
            return False
        return True

    # Pipelining decision passed DOWN to iter_encode_chunks, not via
    # process-global env (a later sweep or embedded caller must not
    # inherit a stale decision). None = let ingest decide.
    sweep_procs = None
    if not host_only:
        from . import devices as devmod
        if devmod.accelerator_available():
            # overlap pays even on a single-core host when a real
            # device runs the checks: the worker parses while the
            # parent blocks on the accelerator (append AND wr sweeps)
            sweep_procs = max(1, _os.cpu_count() or 1)

    if checker == "append":
        # Mesh built lazily on the FIRST dense dispatch: an
        # all-fallback store (non-txn workloads) must never pay — or
        # hang in — device init it doesn't need.
        mesh_box: list = []

        def get_mesh():
            if not mesh_box:
                # a mesh-sweep shard dispatches on ITS OWN host's
                # chips only: the cross-host axis is the shard split
                # of run dirs, never a global dispatch mesh
                mesh_box.append(parallel.host_local_mesh()
                                if shard is not None
                                else parallel.make_mesh())
            return mesh_box[0]

        # The checker class's own defaults, so batch verdicts match
        # single-run verdicts for the same history.
        prohibited = elle.AppendChecker().prohibited

        def emit_append(d, enc, cycles):
            from . import supervisor
            if isinstance(cycles, supervisor.Quarantined):
                # the dispatcher abandoned this history (OOM backdown
                # exhausted / watchdog) — already counted + span'd at
                # the quarantine site; persist the unknown verdict
                return emit(d, cycles.verdict("append"))
            res = elle.render_verdict(enc, cycles, prohibited)
            res["checker"] = "append"   # --resume marker
            return emit(d, res)

        fallback, huge, huge_map = [], [], []
        # Streaming ingest/check pipeline: each chunk's device sweep
        # overlaps the pool workers' parsing of the NEXT chunk, so
        # device time hides under ingest on stores big enough to
        # matter (SURVEY.md §5.7; the bench's north-star block uses
        # the same loop). Verdicts persist PER CHUNK: an interrupted
        # sweep --resumes from the last chunk, not from zero (huge
        # runs defer to their own host-condensation pass below).
        # Each main-thread stall on the ingest iterator lands as a
        # "parse" phase span in the sweep tracer (bench semantics).
        for chunk in _parse_timed(ingest.iter_encode_chunks(
                run_dirs, checker=checker, processes=sweep_procs)):
            dense, dense_map = [], []
            for d, enc in chunk:
                if not encodable(d, enc, fallback):
                    continue
                if enc.n > parallel.DENSE_TXN_LIMIT:
                    # too long for the dense [T,T] closure: SCC
                    # condensation (the 100k-op path), after the sweep
                    huge.append(enc)
                    huge_map.append(d)
                elif host_only:
                    worst = max(worst, emit_append(
                        d, enc, elle.cycle_anomalies_cpu(enc)))
                else:
                    dense.append(enc)
                    dense_map.append(d)
            if dense:
                souts: list | None = [] if want_stats else None
                cycles_per = parallel.check_bucketed(
                    dense, get_mesh(), stats_out=souts)
                for i, (d, enc, cycles) in enumerate(
                        zip(dense_map, dense, cycles_per)):
                    worst = max(worst, emit_append(d, enc, cycles))
                    if souts is not None:
                        record_stats(d, "append", souts[i], cycles)
        for d, enc in zip(huge_map, huge):
            shuge: list | None = [] if want_stats else None
            try:
                if host_only:
                    cycles = elle.cycle_anomalies_cpu(enc)
                else:
                    # mesh=None: these are all past the dense limit, so
                    # check_long_history goes host-condensation; None
                    # just lets the per-SCC classify stage use
                    # default_devices() (the dp batch mesh would be
                    # wrong for B=1 anyway)
                    cycles = parallel.check_long_history(
                        enc, None, dense_limit=parallel.DENSE_TXN_LIMIT,
                        stats_out=shuge)
            except Exception as e:
                # one monster history must fail alone, not take the
                # whole sweep's remaining verdicts with it
                worst = max(worst, _quarantine_run(
                    d, e, "check", checker, journal=journal))
                continue
            worst = max(worst, emit_append(d, enc, cycles))
            if shuge:
                record_stats(d, "append", shuge[0], cycles)
        for d in fallback:
            worst = max(worst, _stored_fallback(d, stored_check,
                                                checker,
                                                journal=journal))
        return worst

    # wr: edge lists host-built; bucketed device dispatches — the same
    # streaming pipeline as the append sweep (chunked device work
    # overlaps pool parsing of the next chunk).
    prohibited = elle_wr.WrChecker().prohibited
    fallback = []
    for chunk in _parse_timed(ingest.iter_encode_chunks(
            run_dirs, checker=checker, processes=sweep_procs)):
        good = [(d, enc) for d, enc in chunk
                if encodable(d, enc, fallback)]
        if not good:
            continue
        wr_stats: list | None = [] if want_stats else None
        if host_only:
            cycles_per = [elle_wr.cycle_anomalies_cpu(e)
                          for _d, e in good]
        else:
            cycles_per = _wr_chunk_with_backdown(
                good, elle_kernels, elle_wr, stats_out=wr_stats)
        # emit per chunk: verdicts persist incrementally (an
        # interrupted sweep --resumes from the last chunk, not from
        # zero) and encodings free as we go
        for i, ((d, enc), cycles) in enumerate(zip(good, cycles_per)):
            if hasattr(cycles, "verdict"):   # supervisor.Quarantined
                worst = max(worst, emit(d, cycles.verdict("wr")))
                continue
            res = elle_wr.render_wr_verdict(enc, cycles, prohibited)
            res["checker"] = "wr"       # --resume marker
            worst = max(worst, emit(d, res))
            if wr_stats is not None and i < len(wr_stats):
                record_stats(d, "wr", wr_stats[i], cycles)

    for d in fallback:
        worst = max(worst, _stored_fallback(d, stored_check, checker,
                                            journal=journal))
    return worst


def _wr_chunk_with_backdown(good, elle_kernels, elle_wr,
                            stats_out: list | None = None):
    """One wr chunk's device dispatch with the supervisor's OOM and
    watchdog degradation: the bucketed batch first; on
    RESOURCE_EXHAUSTED (or a watchdog timeout) the chunk re-checks one
    history at a time (the wr dispatcher has no incremental split, so
    singletons ARE the backdown floor), and a history that still fails
    alone quarantines. Two CONSECUTIVE singleton watchdog timeouts mean
    the device is wedged, not the data: the chunk's remainder
    quarantines without re-probing. Other errors (and strict mode)
    re-raise — fail-fast exactly as before.

    `stats_out` (a list) is extended with one kernel-stats dict per
    history in chunk order (None for quarantined histories) — only
    on completion, so a re-raised failure leaves it untouched."""
    from . import supervisor

    def recoverable(e) -> bool:
        return not supervisor.strict_enabled() and (
            supervisor.is_oom_error(e)
            or isinstance(e, supervisor.WatchdogTimeout))

    edges = [elle_wr.to_edge_dict(e) for _d, e in good]
    tr = trace.get_current()
    # the stats kwarg is passed ONLY when requested: the supervisor
    # tests drive this ladder through duck-typed fake kernels whose
    # stats-free signature must keep working
    try:
        if stats_out is not None:
            batch_stats: list = []
            res = elle_kernels.check_edge_batch_bucketed(
                edges, stats_out=batch_stats)
            stats_out.extend(batch_stats)
        else:
            res = elle_kernels.check_edge_batch_bucketed(edges)
        return res
    except Exception as e:
        if not recoverable(e):
            raise
        if supervisor.is_oom_error(e):
            # watchdog batch failures are already counted inside the
            # bounded wait; oom_retries must mean real OOMs so the
            # bench's robustness block can tell the two causes apart
            tr.counter("oom_retries").inc()
    out = []
    souts: list | None = [] if stats_out is not None else None
    wedged = 0
    for ed in edges:
        if wedged >= 2:
            # two consecutive singleton watchdog timeouts: the device
            # is wedged, not the data — quarantine the remainder
            # instead of burning 2x the timeout (and two abandoned
            # waiter threads) per history on a dead runtime
            with tr.span("quarantine", stage="watchdog", histories=1):
                tr.counter("quarantined").inc()
            from . import obs
            obs.emit("quarantine", stage="watchdog", histories=1,
                     cause="device wedged")
            out.append(supervisor.Quarantined(
                "watchdog", "device wedged: consecutive singleton "
                "watchdog timeouts"))
            if souts is not None:
                souts.append(None)
            continue
        try:
            if souts is not None:
                s1: list = []
                out.append(elle_kernels.check_edge_batch_bucketed(
                    [ed], stats_out=s1)[0])
                souts.append(s1[0] if s1 else None)
            else:
                out.append(
                    elle_kernels.check_edge_batch_bucketed([ed])[0])
            wedged = 0
        except Exception as e:
            if not recoverable(e):
                raise
            if isinstance(e, supervisor.WatchdogTimeout):
                stage = "watchdog"
                wedged += 1
            else:
                stage = "oom"
            with tr.span("quarantine", stage=stage, histories=1):
                tr.counter("quarantined").inc()
            from . import obs
            obs.emit("quarantine", stage=stage, histories=1,
                     cause=repr(e)[:300])
            out.append(supervisor.Quarantined(stage, repr(e)))
            if souts is not None:
                souts.append(None)
    if stats_out is not None:
        stats_out.extend(souts)
    return out


def _parse_timed(it):
    """Re-yield an iterator, recording each main-thread stall on it as
    a "parse" phase span in the current tracer — analyze-store sweeps
    get the same parse/pack/h2d/dispatch/collect attribution as the
    bench's north-star loop."""
    it = iter(it)
    while True:
        with trace.get_current().phase_span("parse"):
            chunk = next(it, None)
        if chunk is None:
            return
        yield chunk


def _verdicted(d, checker: str) -> bool:
    """Did a prior sweep of THIS checker fully verdict this run? Every
    completed verdict leaves an additive `.sweep-<checker>` sidecar
    (so alternating sweeps never erase each other's progress); a
    parseable results.json naming the checker counts too."""
    if (d / f".sweep-{checker}").exists():
        return True
    p = d / "results.json"
    if not p.exists() or checker == "stored":
        return False  # stored sweeps mark ONLY via the sidecar: the
        #               run's own results.json predates the sweep
    try:
        return json.loads(p.read_text()).get("checker") == checker
    except (OSError, json.JSONDecodeError):
        return False  # truncated marker: redo the run


def _prior_code(d, checker: str | None = None) -> int:
    """Exit-code contribution of an already-verdicted (skipped) run.
    THIS sweep's sidecar is consulted first: results.json is whichever
    checker wrote it last, so a later sweep by a different checker
    would mask this checker's recorded validity (and stored-fallback
    runs never write results.json at all) — an invalid verdict from
    the completed part of an interrupted sweep must not read as
    success. Legacy empty sidecars fall through to results.json."""
    if checker is not None:
        try:
            return validity_exit_code(
                json.loads((d / f".sweep-{checker}").read_text()))
        except (OSError, json.JSONDecodeError, ValueError):
            pass
    try:
        return validity_exit_code(
            json.loads((d / "results.json").read_text()))
    except (OSError, json.JSONDecodeError):
        return 0  # legacy empty sidecar: validity was reported when run


def _write_results(d, res: dict, checker: str | None = None,
                   journal=None, persist: bool = True) -> int:
    """Persist results.json/.edn into a run dir and print the one-line
    summary; returns the validity exit code. results.json lands via
    per-process temp-file + atomic rename (multi-host sweeps over a
    shared store race benignly — identical content, last writer wins),
    then the additive `.sweep-<checker>` sidecar marks the run done
    for --resume, and the sweep's verdicts.jsonl journal (when one is
    threaded through) gets its per-history append. persist=False skips
    the results.json/.edn write (sidecar/journal/summary only) so the
    stored-fallback's failure path can't clobber a run's original
    test-time results — its success path never writes them either."""
    import os as _os
    from . import edn as edn_mod
    from .store import _results_to_edn
    if persist:
        (d / "results.edn").write_text(
            edn_mod.dumps(_results_to_edn(_json_safe(res))) + "\n")
        tmp = d / f"results.json.tmp.{_os.getpid()}"
        tmp.write_text(json.dumps(_json_safe(res), indent=2))
        _os.replace(tmp, d / "results.json")
    if checker is not None:
        (d / f".sweep-{checker}").write_text(
            json.dumps({"valid?": res.get("valid?")}))
    if journal is not None and checker is not None:
        journal.record(d, checker, res)
    trace.get_current().counter("runs_verdicted").inc()
    line = {"dir": str(d), "valid?": res.get("valid?")}
    if "anomaly-types" in res:
        line["anomalies"] = res.get("anomaly-types", [])
    if "failures" in res:
        line["failures"] = res["failures"]
    if "quarantined" in res:
        line["quarantined"] = res["quarantined"]
        line["error"] = res.get("error")
    print(json.dumps(line))
    return validity_exit_code(res)


def _quarantine_run(d, err, stage: str, checker: str | None = None,
                    journal=None, persist: bool = True) -> int:
    """Record a run the sweep abandoned as a `valid? unknown` verdict —
    never a false verdict, never a dead sweep (Elle's degradation
    contract) — persisting the cause for triage and journaling it so
    --resume doesn't grind over the same broken run forever.
    JEPSEN_TPU_STRICT=1 re-raises instead (the old fail-fast)."""
    from . import supervisor
    if supervisor.strict_enabled():
        if isinstance(err, BaseException):
            raise err
        raise RuntimeError(str(err))
    tr = trace.get_current()
    with tr.span("quarantine", stage=stage):
        tr.counter("quarantined").inc()
    from . import obs
    obs.emit("quarantine", stage=stage, run=str(d),
             cause=str(err)[:300])
    log.warning("quarantining %s (%s): %s", d, stage, err)
    return _write_results(
        d, supervisor.quarantine_verdict(err, stage, checker), checker,
        journal=journal, persist=persist)


def _stored_fallback(d, stored_check, checker: str | None = None,
                     journal=None) -> int:
    """Run a dir through its own stored checker, quarantining (an
    `unknown` verdict, never an exception, never a dead sweep) on
    failure. With `checker`, a success leaves the `.sweep-<checker>`
    sidecar so --resume counts the run done for that sweep. Every run a
    device sweep routes here counts in `stored_fallbacks`."""
    if checker != "stored":
        trace.get_current().counter("stored_fallbacks").inc()
    try:
        res = stored_check(d)
    except Exception as e:
        # never clobber an existing test-time results.json — the
        # stored path's success leaves it untouched too, and a
        # transient failure must not replace a recorded verdict with
        # an unknown. A run dir without one records the quarantine so
        # triage has something to read.
        return _quarantine_run(
            d, e, "stored", checker, journal=journal,
            persist=not (d / "results.json").exists())
    print(json.dumps({"dir": str(d), "valid?": res.get("valid?")}))
    trace.get_current().counter("runs_verdicted").inc()
    if checker is not None:
        # record the validity: the fallback may not write a
        # results.json, and --resume must reproduce this run's
        # exit-code contribution from the sidecar alone
        (d / f".sweep-{checker}").write_text(
            json.dumps({"valid?": res.get("valid?")}))
    if journal is not None and checker is not None:
        journal.record(d, checker, res)
    return validity_exit_code(res)


def _analyze_store_register(store: Store, run_dirs: list,
                            stored_check, journal=None) -> int:
    """Per-key CAS-register linearizability over a whole store: every
    key's subhistory from EVERY run goes down in one tiered device
    sweep (dense grid -> bounded frontier -> CPU re-run), then verdicts
    regroup per run — the etcd-shaped batch sweep of BASELINE config
    #1. The load workers relift and split each run and, on the device
    tiers, dense-encode its keys; the main thread only regroups and
    dispatches. Runs whose client ops aren't register-shaped fall back
    to their own stored checker."""
    from . import ingest
    from .checker import linearizable, models

    # auto resolves to the device kernels when an accelerator is
    # reachable and honors the --backend env export either way
    c = linearizable(models.cas_register(), backend="auto")
    tr = trace.get_current()

    # with the device tiers, the load workers also encode each key that
    # fits the dense grid; every other key comes back raw
    frontier = c.frontier if c.engine() == "tpu" else None
    with tr.phase_span("register_load", runs=len(run_dirs)):
        recs = ingest.parallel_split_registers(run_dirs, frontier)
    subs: list = []     # per key: its subhistory, or its DenseEncoded
    owners: list[tuple[int, object, int]] = []  # (run index, key, ops)
    fallback: list[int] = []
    with tr.phase_span("register_split") as split:
        for i, rec in enumerate(recs):
            if rec is None or isinstance(rec, Exception):
                fallback.append(i)
                continue
            for k, n, sub in rec:
                subs.append(sub)
                owners.append((i, k, n))
        split.note(keys=len(subs))
    raw = sum(isinstance(s, list) for s in subs)
    tr.counter("register_keys_preencoded").inc(len(subs) - raw)
    tr.counter("register_keys_raw").inc(raw)

    from .obs import search as search_obs
    ksouts: list | None = [] if search_obs.enabled() else None
    try:
        results = c.check_batch({}, subs, {}, stats_out=ksouts) \
            if subs else []
    except Exception:
        # one malformed run must not sink the sweep: re-dispatch each
        # key in isolation, degrading only the broken ones
        log.warning("batched register sweep failed; isolating per key",
                    exc_info=True)
        results = []
        ksouts = None   # isolation retries run telemetry-free
        for s in subs:
            try:
                results.append(c.check_batch({}, [s], {})[0])
            except Exception as e:
                results.append({"valid?": "unknown",
                                "error": repr(e)[:200]})
    per_run: dict[int, dict] = {}
    per_run_stats: dict[int, list] = {}
    for j, ((i, k, n), res) in enumerate(zip(owners, results)):
        per_run.setdefault(i, {})[k] = res
        if ksouts is not None:
            per_run_stats.setdefault(i, []).append((k, n, ksouts[j]))

    with tr.phase_span("register_write", runs=len(run_dirs)):
        return _write_register_verdicts(run_dirs, fallback, per_run,
                                        per_run_stats, ksouts,
                                        stored_check, journal)


def _write_register_verdicts(run_dirs, fallback, per_run, per_run_stats,
                             ksouts, stored_check, journal) -> int:
    """The register sweep's per-run verdicts: results files, journal,
    search records; returns the worst exit code."""
    from .checker import merge_valid
    from .obs import search as search_obs
    worst = 0
    for i, d in enumerate(run_dirs):
        if i in fallback:
            worst = max(worst,
                        _stored_fallback(d, stored_check, "register",
                                         journal=journal))
            continue
        keyed = per_run.get(i, {})
        valid = merge_valid([r.get("valid?", True)
                             for r in keyed.values()] or [True])
        res = {"valid?": valid,
               "checker": "register",       # --resume marker
               "key-count": len(keyed),
               "results": {str(k): r for k, r in keyed.items()},
               "failures": sorted(str(k) for k, r in keyed.items()
                                  if r.get("valid?") is False)}
        worst = max(worst, _write_results(d, res, "register",
                                          journal=journal))
        if ksouts is not None and i in per_run_stats:
            rel = journal.rel if journal is not None else str
            search_obs.record(
                rel(d), "register",
                _register_run_stats(per_run_stats[i]),
                anomalies=res["failures"] or None)
    return worst


def _register_run_stats(keyed: list) -> dict | None:
    """One run's register-sweep search record: the per-key subhistory
    sizes the native split produced (the WGL cost driver) plus the
    engines' own counters aggregated across keys — summed where the
    quantity is additive (configs, backtracks, rounds), maxed where it
    is a peak (frontier width, depth)."""
    sizes = [n for _k, n, _s in keyed]
    stats = [s for _k, _n, s in keyed if isinstance(s, dict)]
    if not sizes:
        return None
    out: dict = {
        "keys": len(sizes),
        "subhistory_ops": {"min": min(sizes), "max": max(sizes),
                           "mean": round(sum(sizes) / len(sizes), 2)},
        "engines": sorted({s.get("engine") for s in stats
                           if s.get("engine")}),
    }
    for f in ("configs", "backtracks", "rounds"):
        vals = [s[f] for s in stats if isinstance(s.get(f), int)]
        if vals:
            out[f] = sum(vals)
    for f in ("frontier_peak", "max_depth"):
        vals = [s[f] for s in stats if isinstance(s.get(f), int)]
        if vals:
            out[f] = max(vals)
    return out


def _json_safe(v):
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


if __name__ == "__main__":
    # `python -m jepsen_tpu.cli analyze-store ...` — the suite-agnostic
    # entry (test/analyze with no suite run a noop test map).
    sys.exit(run_cli(lambda tmap, args: tmap))
