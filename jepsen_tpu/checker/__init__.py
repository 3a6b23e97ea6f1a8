"""Checkers: analysis of histories.

The `Checker` interface mirrors the reference protocol
(jepsen/src/jepsen/checker.clj:49-69): `check(test, history, opts) ->
{"valid?": True | False | "unknown", ...}`. `compose` runs a map of named
checkers in parallel and merges validity with invalid < unknown < valid
precedence (checker.clj:20-47,90-102).

The built-in checkers here are the CPU oracles: pure data-in/data-out
functions, golden-tested, that also serve as the differential references for
the TPU kernel checkers in `checker.elle` and `checker.knossos`.
"""

from __future__ import annotations

import traceback
from collections import Counter
from typing import Any, Callable

from .. import history as h
from ..util import integer_interval_set_str, real_pmap
from . import models as model

VALID_PRIORITIES = {True: 2, "unknown": 1, False: 0}


def merge_valid(valids: list) -> Any:
    """Merge validity values: false wins over unknown wins over true."""
    out: Any = True
    for v in valids:
        if v not in VALID_PRIORITIES:
            raise ValueError(f"{v!r} is not a known valid? value")
        if VALID_PRIORITIES[v] < VALID_PRIORITIES[out]:
            out = v
    return out


class Checker:
    def check(self, test: dict, history: list, opts: dict) -> dict | None:
        raise NotImplementedError


class FnChecker(Checker):
    """Wrap a function (test, history, opts) -> result as a Checker."""

    def __init__(self, f: Callable[[dict, list, dict], dict | None]):
        self.f = f

    def check(self, test, history, opts):
        return self.f(test, history, opts)


def check_safe(checker: Checker, test: dict, history: list,
               opts: dict | None = None) -> dict:
    """Like check, but returns exceptions as {"valid?": "unknown"} results
    (checker.clj:77-88). Every check runs inside a trace span named
    after the checker class, so composed checkers show up as one track
    row each in the run's trace.json."""
    from .. import trace
    try:
        with trace.span(f"check:{type(checker).__name__}",
                        ops=len(history)):
            r = checker.check(test, history, opts or {})
        return r if r is not None else {"valid?": True}
    except Exception:
        return {"valid?": "unknown", "error": traceback.format_exc()}


class Noop(Checker):
    def check(self, test, history, opts):
        return None


def noop() -> Checker:
    return Noop()


class UnbridledOptimism(Checker):
    """Everything is awesome."""

    def check(self, test, history, opts):
        return {"valid?": True}


def unbridled_optimism() -> Checker:
    return UnbridledOptimism()


class Compose(Checker):
    def __init__(self, checker_map: dict[str, Checker]):
        self.checker_map = checker_map

    def check(self, test, history, opts):
        items = list(self.checker_map.items())
        results = real_pmap(
            lambda kv: (kv[0], check_safe(kv[1], test, history, opts)), items)
        out: dict = dict(results)
        out["valid?"] = merge_valid([r.get("valid?", True) for _, r in results])
        return out


def compose(checker_map: dict[str, Checker]) -> Checker:
    return Compose(checker_map)


class ConcurrencyLimit(Checker):
    """Bound concurrent executions of a memory-hungry checker
    (checker.clj:104-119)."""

    def __init__(self, limit: int, checker: Checker):
        import threading
        self.sem = threading.Semaphore(limit)
        self.checker = checker

    def check(self, test, history, opts):
        with self.sem:
            return self.checker.check(test, history, opts)


def concurrency_limit(limit: int, checker: Checker) -> Checker:
    return ConcurrencyLimit(limit, checker)


class UnhandledExceptions(Checker):
    """Aggregate crashed (:info) ops carrying errors, by error class,
    in descending frequency (checker.clj:127-154)."""

    def check(self, test, history, opts):
        crashed = [o for o in history
                   if h.is_info(o) and (o.get("exception") or o.get("error"))]
        groups: dict[Any, list] = {}
        for o in crashed:
            exc = o.get("exception")
            cls = (exc.get("class") if isinstance(exc, dict)
                   else type(exc).__name__ if isinstance(exc, BaseException)
                   else str(o.get("error", exc)))
            groups.setdefault(cls, []).append(o)
        exes = sorted(groups.items(), key=lambda kv: len(kv[1]), reverse=True)
        if not exes:
            return {"valid?": True}
        return {"valid?": True,
                "exceptions": [{"class": cls, "count": len(ops),
                                "example": ops[0]} for cls, ops in exes]}


def unhandled_exceptions() -> Checker:
    return UnhandledExceptions()


def _stats_of(ops: list) -> dict:
    ok = sum(1 for o in ops if h.is_ok(o))
    fail = sum(1 for o in ops if h.is_fail(o))
    info = sum(1 for o in ops if h.is_info(o))
    return {"valid?": ok > 0, "count": ok + fail + info,
            "ok-count": ok, "fail-count": fail, "info-count": info}


class Stats(Checker):
    """Success/failure counts, overall and by :f. Valid only when every :f
    saw at least one :ok (checker.clj:169-186)."""

    def check(self, test, history, opts):
        hist = [o for o in history
                if not h.is_invoke(o) and o.get("process") != h.NEMESIS]
        by_f: dict = {}
        for o in hist:
            by_f.setdefault(o.get("f"), []).append(o)
        groups = {f: _stats_of(ops) for f, ops in sorted(
            by_f.items(), key=lambda kv: str(kv[0]))}
        out = _stats_of(hist)
        out["by-f"] = groups
        out["valid?"] = merge_valid([g["valid?"] for g in groups.values()])
        return out


def stats() -> Checker:
    return Stats()


class QueueChecker(Checker):
    """Every dequeue must come from somewhere: assume every non-failing
    enqueue succeeded and only ok dequeues happened, then fold through the
    model (checker.clj:221-240). O(n); use an unordered queue model."""

    def __init__(self, m: model.Model):
        self.model = m

    def check(self, test, history, opts):
        state = self.model
        for o in history:
            f = o.get("f")
            take = (h.is_invoke(o) if f == "enqueue"
                    else h.is_ok(o) if f == "dequeue" else False)
            if not take:
                continue
            state = state.step(o)
            if model.is_inconsistent(state):
                return {"valid?": False, "error": state.msg}
        return {"valid?": True, "final-queue": repr(state)}


def queue(m: model.Model | None = None) -> Checker:
    return QueueChecker(m or model.unordered_queue())


class SetChecker(Checker):
    """:add ops followed by a final :read of the whole set
    (checker.clj:243-302): every acknowledged add must be present; nothing
    unexpected may appear."""

    def check(self, test, history, opts):
        attempts = {o.get("value") for o in history
                    if h.is_invoke(o) and o.get("f") == "add"}
        adds = {o.get("value") for o in history
                if h.is_ok(o) and o.get("f") == "add"}
        final_read = None
        for o in history:
            if h.is_ok(o) and o.get("f") == "read":
                final_read = o.get("value")
        if final_read is None:
            return {"valid?": "unknown", "error": "Set was never read"}
        final = {v for v in final_read} if not isinstance(final_read, (set, frozenset)) else set(final_read)
        ok = final & attempts
        unexpected = final - attempts
        lost = adds - final
        recovered = ok - adds
        return {
            "valid?": not lost and not unexpected,
            "attempt-count": len(attempts),
            "acknowledged-count": len(adds),
            "ok-count": len(ok),
            "lost-count": len(lost),
            "recovered-count": len(recovered),
            "unexpected-count": len(unexpected),
            "ok": integer_interval_set_str(ok),
            "lost": integer_interval_set_str(lost),
            "unexpected": integer_interval_set_str(unexpected),
            "recovered": integer_interval_set_str(recovered),
        }


def set_checker() -> Checker:
    return SetChecker()


class _SetFullElement:
    """Per-element timeline state for set-full (checker.clj:305-341)."""

    __slots__ = ("element", "known", "last_present", "last_absent")

    def __init__(self, element):
        self.element = element
        self.known = None          # completion op that proved existence
        self.last_present = None   # latest read invocation that observed it
        self.last_absent = None    # latest read invocation that missed it

    def add_ok(self, op):
        if self.known is None:
            self.known = op

    def read_present(self, iop, op):
        if self.known is None:
            self.known = op
        if self.last_present is None or \
                self.last_present["index"] < iop["index"]:
            self.last_present = iop

    def read_absent(self, iop, op):
        if self.last_absent is None or \
                self.last_absent["index"] < iop["index"]:
            self.last_absent = iop


def _idx(op, default=-1):
    return op["index"] if op is not None else default


def _set_full_element_results(e: _SetFullElement) -> dict:
    known_time = e.known.get("time") if e.known else None
    stable = bool(e.last_present is not None and
                  _idx(e.last_absent) < _idx(e.last_present))
    # An absent read concurrent with the add could have linearized before it;
    # require the miss to begin after the add was known complete
    # (checker.clj:368-383).
    lost = bool(e.known is not None and e.last_absent is not None and
                _idx(e.last_present) < _idx(e.last_absent) and
                _idx(e.known) < _idx(e.last_absent))
    stable_time = ((e.last_absent["time"] + 1 if e.last_absent else 0)
                   if stable else None)
    lost_time = ((e.last_present["time"] + 1 if e.last_present else 0)
                 if lost else None)
    stable_latency = (max(0, stable_time - known_time) // 1_000_000
                      if stable else None)
    lost_latency = (max(0, lost_time - known_time) // 1_000_000
                    if lost else None)
    return {"element": e.element,
            "outcome": ("stable" if stable else
                        "lost" if lost else "never-read"),
            "stable-latency": stable_latency,
            "lost-latency": lost_latency,
            "known": e.known,
            "last-absent": e.last_absent}


def frequency_distribution(points: list[float], xs: list) -> dict | None:
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return {p: xs[min(n - 1, int(n * p))] for p in points}


class SetFull(Checker):
    """Rigorous set analysis: per-element stable/lost/never-read outcomes
    with latency distributions (checker.clj:464-595). With
    linearizable=True, stale reads (nonzero stable latency) are invalid.

    Note: the reference's duplicate filter compares multiplicity < 1
    (checker.clj:571), which can never fire; we implement the evident
    intent — elements appearing more than once in a single read."""

    def __init__(self, linearizable: bool = False):
        self.linearizable = linearizable

    def check(self, test, history, opts):
        elements: dict[Any, _SetFullElement] = {}
        reads: dict[Any, dict] = {}   # process -> read invocation
        dups: dict[Any, int] = {}     # element -> max multiplicity > 1
        for o in history:
            if not h.is_client_op(o):
                continue
            f, p = o.get("f"), o.get("process")
            if f == "add":
                v = o.get("value")
                if h.is_invoke(o):
                    elements.setdefault(v, _SetFullElement(v))
                elif h.is_ok(o) and v in elements:
                    elements[v].add_ok(o)
            elif f == "read":
                if h.is_invoke(o):
                    reads[p] = o
                elif h.is_fail(o):
                    reads.pop(p, None)
                elif h.is_ok(o):
                    iop = reads.pop(p, o)
                    vals = o.get("value") or []
                    for el, n in Counter(vals).items():
                        if n > 1:
                            dups[el] = max(dups.get(el, 0), n)
                    vset = set(vals)
                    for el, state in elements.items():
                        if el in vset:
                            state.read_present(iop, o)
                        else:
                            state.read_absent(iop, o)
        rs = [_set_full_element_results(e) for _, e in sorted(
            elements.items(), key=lambda kv: repr(kv[0]))]
        outcomes: dict[str, list] = {}
        for r in rs:
            outcomes.setdefault(r["outcome"], []).append(r)
        stable = outcomes.get("stable", [])
        lost = outcomes.get("lost", [])
        never_read = outcomes.get("never-read", [])
        stale = [r for r in stable if r["stable-latency"] > 0]
        stable_lat = [r["stable-latency"] for r in rs
                      if r["stable-latency"] is not None]
        lost_lat = [r["lost-latency"] for r in rs
                    if r["lost-latency"] is not None]
        valid: Any = (False if lost else
                      "unknown" if not stable else
                      False if self.linearizable and stale else
                      True)
        out = {
            "valid?": False if dups else valid,
            "attempt-count": len(rs),
            "stable-count": len(stable),
            "lost-count": len(lost),
            "lost": sorted((r["element"] for r in lost), key=repr),
            "never-read-count": len(never_read),
            "never-read": sorted((r["element"] for r in never_read), key=repr),
            "stale-count": len(stale),
            "stale": sorted((r["element"] for r in stale), key=repr),
            "worst-stale": sorted(stale, key=lambda r: r["stable-latency"],
                                  reverse=True)[:8],
            "duplicated-count": len(dups),
            "duplicated": dict(sorted(dups.items(), key=lambda kv: repr(kv[0]))),
        }
        points = [0, 0.5, 0.95, 0.99, 1]
        fd = frequency_distribution(points, stable_lat)
        if fd:
            out["stable-latencies"] = fd
        fd = frequency_distribution(points, lost_lat)
        if fd:
            out["lost-latencies"] = fd
        return out


def set_full(linearizable: bool = False) -> Checker:
    return SetFull(linearizable)


def expand_queue_drain_ops(history: list) -> list:
    """Expand ok :drain ops (value = list of elements) into dequeue
    invoke/ok pairs (checker.clj:598-628)."""
    out = []
    for o in history:
        if o.get("f") != "drain":
            out.append(o)
        elif h.is_invoke(o) or h.is_fail(o):
            continue
        elif h.is_ok(o):
            for el in o.get("value") or []:
                out.append({**o, "type": "invoke", "f": "dequeue", "value": None})
                out.append({**o, "type": "ok", "f": "dequeue", "value": el})
        else:
            raise ValueError(f"can't handle a crashed drain operation: {o!r}")
    return out


class TotalQueue(Checker):
    """What goes in must come out — multiset accounting over enqueues and
    dequeues, with drains expanded (checker.clj:631-690)."""

    def check(self, test, history, opts):
        hist = expand_queue_drain_ops(history)
        attempts = Counter(o.get("value") for o in hist
                           if h.is_invoke(o) and o.get("f") == "enqueue")
        enqueues = Counter(o.get("value") for o in hist
                           if h.is_ok(o) and o.get("f") == "enqueue")
        dequeues = Counter(o.get("value") for o in hist
                           if h.is_ok(o) and o.get("f") == "dequeue")
        ok = dequeues & attempts
        unexpected = Counter({v: n for v, n in dequeues.items()
                              if v not in attempts})
        duplicated = dequeues - attempts - unexpected
        lost = enqueues - dequeues
        recovered = ok - enqueues
        return {
            "valid?": not lost and not unexpected,
            "attempt-count": sum(attempts.values()),
            "acknowledged-count": sum(enqueues.values()),
            "ok-count": sum(ok.values()),
            "unexpected-count": sum(unexpected.values()),
            "duplicated-count": sum(duplicated.values()),
            "lost-count": sum(lost.values()),
            "recovered-count": sum(recovered.values()),
            "lost": dict(lost),
            "unexpected": dict(unexpected),
            "duplicated": dict(duplicated),
            "recovered": dict(recovered),
        }


def total_queue() -> Checker:
    return TotalQueue()


class UniqueIds(Checker):
    """A unique-id generator must emit distinct values
    (checker.clj:692-737)."""

    def check(self, test, history, opts):
        attempted = sum(1 for o in history
                        if h.is_invoke(o) and o.get("f") == "generate")
        acks = [o.get("value") for o in history
                if h.is_ok(o) and o.get("f") == "generate"]
        counts = Counter(acks)
        dups = {v: n for v, n in counts.items() if n > 1}
        rng = [min(acks), max(acks)] if acks else [None, None]
        worst = dict(sorted(dups.items(), key=lambda kv: kv[1],
                            reverse=True)[:48])
        return {"valid?": not dups,
                "attempted-count": attempted,
                "acknowledged-count": len(acks),
                "duplicated-count": len(dups),
                "duplicated": worst,
                "range": rng}


def unique_ids() -> Checker:
    return UniqueIds()


class CounterChecker(Checker):
    """A counter incremented by :add ops and observed by :read ops: each read
    must lie within [sum of ok increments + attempted decrements, sum of
    attempted increments + ok decrements] at its window (checker.clj:740-795).
    """

    def check(self, test, history, opts):
        # Apply completion values to invocations; drop definite failures.
        hist = h.remove_failures(h.complete(h.index(history)))
        lower = upper = 0
        pending_reads: dict = {}
        reads: list = []
        for o in hist:
            key = (o.get("type"), o.get("f"))
            p = o.get("process")
            v = o.get("value")
            if key == ("invoke", "read"):
                pending_reads[p] = [lower, v]
            elif key == ("ok", "read"):
                r = pending_reads.pop(p, [lower, v])
                reads.append([r[0], r[1], upper])
            elif key == ("invoke", "add"):
                if v >= 0:
                    upper += v
                else:
                    lower += v
            elif key == ("ok", "add"):
                if v >= 0:
                    lower += v
                else:
                    upper += v
        errors = [r for r in reads
                  if r[1] is None or not (r[0] <= r[1] <= r[2])]
        return {"valid?": not errors, "reads": reads, "errors": errors}


def counter() -> Checker:
    return CounterChecker()


class Linearizable(Checker):
    """Linearizability checker over a data-type model — the reference's
    `checker/linearizable` (jepsen/src/jepsen/checker.clj:188-219),
    rebuilt on the native knossos engine.

    `model` is a `models.Model` (immutable; step returns a successor).
    `algorithm` mirrors knossos: "wgl" | "linear" | "competition"; on
    this build all CPU routes share the WGL engine (C++ for CAS
    registers, Python otherwise) and the `linear` config-space search
    is the TPU dense-bitset kernel, selected with backend="tpu".
    backend="race" is the knossos-competition analogue across ENGINES:
    the device pipeline and the CPU engine run concurrently and the
    first full-batch finisher wins (multi-core hosts only — racing
    doubles host work while both run). The device route is taken only
    for the model it implements (a fresh CAS register) on histories
    that fit its slot/value grid; everything else falls back to the
    CPU engine, so verdicts only ever degrade to the oracle, never
    diverge from it."""

    def __init__(self, m: model.Model | None = None,
                 algorithm: str = "competition", backend: str = "auto",
                 frontier: int | None = None):
        self.model = m if m is not None else model.cas_register()
        self.algorithm = algorithm
        self.backend = backend
        # bounded-frontier arena size; None = JEPSEN_TPU_FRONTIER or 512
        if frontier is None:
            from .. import gates
            frontier = gates.get("JEPSEN_TPU_FRONTIER")
        self.frontier = frontier

    def _cpu(self, history: list, search_stats: dict | None = None
             ) -> dict:
        from . import knossos
        return knossos.analysis(self.model, history,
                                algorithm=self.algorithm,
                                search_stats=search_stats)

    def check(self, test, history, opts):
        res = self.check_batch(test, [history], opts)[0]
        if res.get("valid?") is False:
            self.render_failure(test, history, res, opts)
        return res

    def render_failure(self, test, history, res, opts) -> None:
        """Render linear.svg for an invalid analysis (checker.clj:209-213,
        knossos.linear.report). Called directly from check(), and by
        independent.checker per failing key with that key's
        subdirectory opts."""
        if test.get("store") is None:
            return
        try:
            from . import linear_svg
            linear_svg.render_analysis(test, res, history, opts)
        except Exception:  # rendering must never mask the verdict
            import logging
            logging.getLogger(__name__).warning(
                "linear.svg render failed", exc_info=True)

    def check_batch(self, test, histories: list[list], opts,
                    stats_out: list | None = None) -> list[dict]:
        """Check many histories at once — the TPU batch path used by
        `independent.checker` to shard per-key subhistories across the
        device mesh instead of pmapping JVM threads.

        `stats_out` (a list, JEPSEN_TPU_KERNEL_STATS) is extended with
        one per-history search-telemetry dict — WGL configs/backtracks
        on the CPU engine, frontier/grid occupancy on the device
        kernels; None per history on the race backend (whichever
        engine wins owns the wall clock, so neither's counters are
        authoritative).

        Device routing is tiered: (1) the dense-bitset config-grid
        kernel (`.knossos.dense`) — exact verdicts, no frontier
        overflow — for histories inside its slot/value grid budget;
        (2) histories past the grid (e.g. >14 concurrently-pending
        ops) route to the bounded sorted-frontier kernel
        (`.knossos.kernels`), whose cost scales with the frontier
        arena, not 2^slots; its rare ":frontier-overflow" unknowns
        (3) re-run on the CPU WGL oracle, as does anything not
        register-shaped at all. The kernels implement CAS-register
        semantics from a nil initial state, so any other model routes
        to CPU wholesale. Verdicts only ever degrade toward the
        oracle, never diverge from it.

        Where `engine()` is "tpu", a history may arrive already routed
        and encoded, as a `DenseEncoded` (the register sweep's ingest
        workers encode its keys): it joins the dense tier as it is."""
        engine = self.engine()
        if engine == "race":
            if stats_out is not None:
                stats_out.extend(None for _ in histories)
            return self._race(histories)
        if engine == "tpu":
            return self._device_batch(histories, stats_out=stats_out)
        out = []
        for hs in histories:
            sd: dict | None = {} if stats_out is not None else None
            out.append(self._cpu(hs, search_stats=sd))
            if stats_out is not None:
                stats_out.append(sd or None)
        return out

    def engine(self) -> str:
        """Where check_batch sends a batch: "tpu" (the tiered device
        pipeline), "race" (device against CPU) or "cpu"."""
        # Model eligibility first: only the CPU engine implements
        # models other than the nil-initial CAS register.
        if not (type(self.model) is model.CASRegister
                and self.model.value is None):
            return "cpu"
        from ..devices import resolve_backend
        backend = self.backend
        if backend == "auto":
            # the CLI communicates --backend via JEPSEN_TPU_BACKEND and
            # constructs checkers with "auto": honor an env-requested
            # race here, where the race is implemented
            from .. import gates
            backend = gates.get("JEPSEN_TPU_BACKEND") or "auto"
        if backend == "race":
            return "race" if resolve_backend("auto") == "tpu" else "cpu"
        return "tpu" if resolve_backend(self.backend) == "tpu" else "cpu"

    #: losing race dispatches still draining in background threads;
    #: joined at interpreter exit so teardown can't kill a thread
    #: mid-XLA-dispatch (pthread aborts with "exception not rethrown")
    _race_threads: set = set()
    _race_atexit = [False]

    def _race(self, histories: list[list]) -> list[dict]:
        """knossos.competition's racing rule, engine-scaled: run the
        tiered device pipeline and the CPU engine concurrently and
        return whichever finishes the WHOLE batch first (verdicts are
        identical by the parity contract, so the race only decides
        wall-clock). The reference races wgl against linear the same
        way and takes the first future (knossos competition.clj via
        jepsen checker.clj:188-219); like there, the loser can't be
        interrupted mid-flight — the CPU side stops at the next
        history boundary, a losing device dispatch runs its course in
        the background. Racing doubles host work while both run, so
        it's an explicit backend choice for multi-core hosts, not the
        auto default."""
        import threading

        n = len(histories)
        cpu_res: list = [None] * n
        stop = threading.Event()
        cpu_done = threading.Event()
        dev_out: list = []
        dev_done = threading.Event()
        turn = threading.Event()

        cpu_exc: list = []

        def cpu_side():
            try:
                for i, hs in enumerate(histories):
                    if stop.is_set():
                        return
                    cpu_res[i] = self._cpu(hs)
            except Exception as e:   # propagate via the main thread
                cpu_exc.append(e)
            finally:
                Linearizable._race_threads.discard(
                    threading.current_thread())
            cpu_done.set()
            turn.set()

        def dev_side():
            try:
                dev_out.append(self._device_batch(histories))
            except Exception as e:   # re-raised by the main thread
                dev_out.append(e)
            finally:
                Linearizable._race_threads.discard(
                    threading.current_thread())
            dev_done.set()
            turn.set()

        tc = threading.Thread(target=cpu_side, daemon=True,
                              name="linearizable-race-cpu")
        td = threading.Thread(target=dev_side, daemon=True,
                              name="linearizable-race-dev")
        if not Linearizable._race_atexit[0]:
            Linearizable._race_atexit[0] = True
            import atexit

            def _drain():
                for t in list(Linearizable._race_threads):
                    t.join(timeout=120)
            atexit.register(_drain)
        tc.start()
        Linearizable._race_threads.add(tc)
        td.start()
        Linearizable._race_threads.add(td)
        while True:
            turn.wait()
            turn.clear()
            if dev_done.is_set():
                stop.set()
                if isinstance(dev_out[0], Exception):
                    # a failed device side fails the race: the CPU
                    # engine never stands in for a broken device
                    raise dev_out[0]
                return dev_out[0]
            if cpu_done.is_set():
                if cpu_exc:
                    # CPU side failed; the device result decides, or
                    # the failure propagates as it would un-raced
                    dev_done.wait()
                    if isinstance(dev_out[0], Exception):
                        raise dev_out[0]
                    return dev_out[0]
                return list(cpu_res)

    def _device_batch(self, histories: list[list],
                      stats_out: list | None = None) -> list[dict]:
        """The tiered device pipeline (see check_batch's docstring);
        callers have already checked model eligibility. With
        `stats_out`, each tier reports its own search telemetry
        (grid/frontier occupancy, rounds; the CPU oracle's WGL
        counters for fallbacks)."""
        from .. import trace
        from .knossos import dense, kernels
        from .knossos import encode as kenc
        tr = trace.get_current()
        with_stats = stats_out is not None
        stats: list = [None] * len(histories)
        dense_encs, dense_idx = [], []
        front_encs, front_idx = [], []
        cpu_idx = []
        # the host build of the tier tensors: each history routed to
        # the device tier it fits and encoded for it
        with tr.phase_span("knossos_pack", keys=len(histories)):
            for i, hs in enumerate(histories):
                if isinstance(hs, kenc.DenseEncoded):
                    tier, enc = kenc.DENSE, hs
                else:
                    tier, enc = kenc.route_register_history(
                        hs, self.frontier)
                if tier == kenc.DENSE:
                    dense_encs.append(enc)
                    dense_idx.append(i)
                elif tier == kenc.FRONTIER:
                    front_encs.append(enc)
                    front_idx.append(i)
                else:
                    cpu_idx.append(i)
        results: list[dict | None] = [None] * len(histories)
        if dense_encs:
            ds: list | None = [] if with_stats else None
            for j, (i, r) in enumerate(zip(
                    dense_idx,
                    dense.check_encoded_dense_batch(dense_encs,
                                                    stats_out=ds))):
                results[i] = r
                if ds is not None:
                    stats[i] = ds[j]
        if front_encs:
            fs: list | None = [] if with_stats else None
            for j, (i, r) in enumerate(zip(
                    front_idx,
                    kernels.check_encoded_batch(
                        front_encs, frontier=self.frontier,
                        stats_out=fs))):
                if r.get("valid?") == "unknown":
                    cpu_idx.append(i)  # overflow: exact answer from CPU
                else:
                    results[i] = r
                    if fs is not None:
                        stats[i] = fs[j]
        if cpu_idx:
            tr.counter("register_cpu_routed").inc(len(cpu_idx))
            with tr.phase_span("knossos_cpu", keys=len(cpu_idx)):
                for i in cpu_idx:
                    sd: dict | None = {} if with_stats else None
                    results[i] = self._cpu(histories[i],
                                           search_stats=sd)
                    if with_stats:
                        stats[i] = sd or None
        if with_stats:
            stats_out.extend(stats)
        return results  # type: ignore[return-value]


def linearizable(m: model.Model | None = None,
                 algorithm: str = "competition",
                 backend: str = "auto", **kw) -> Checker:
    return Linearizable(m, algorithm=algorithm, backend=backend, **kw)


# ---------------------------------------------------------------------------
# Plot/report checkers live in submodules (perf, clock, timeline) but are
# part of the reference's jepsen.checker namespace (checker.clj:797-837) —
# re-export the constructors here. Imported lazily to keep matplotlib off
# the fast path.
# ---------------------------------------------------------------------------

def _submodule(name: str):
    import importlib
    return importlib.import_module(f"{__name__}.{name}")


def latency_graph(nemeses=None) -> Checker:
    return _submodule("perf").latency_graph(nemeses)


def rate_graph(nemeses=None) -> Checker:
    return _submodule("perf").rate_graph_checker(nemeses)


def perf_checker(opts: dict | None = None) -> Checker:
    """Composite latency+rate plots (checker.clj:822-829). Named
    perf_checker because `checker.perf` is the helper submodule, as in the
    reference's jepsen.checker.perf namespace."""
    return _submodule("perf").perf(opts)


def clock_plot() -> Checker:
    return _submodule("clock").clock_plot()


def timeline_checker() -> Checker:
    """Timeline HTML checker; the submodule `checker.timeline` mirrors
    jepsen.checker.timeline (whose constructor is `html`)."""
    return _submodule("timeline").html()
