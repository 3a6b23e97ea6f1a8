"""ctypes loader for the native graph kernels (native/graph_algo.cc).

The C++ library plays the role the JVM's Tarjan-over-bifurcan plays in
the reference's Elle (SURVEY.md §2.3-2.4): a sequential host fallback for
pathological dependency graphs that resist the vectorized/TPU closure
formulation. Compiled on first use with g++ (cached under native/build/);
everything degrades cleanly to the pure-Python implementations when no
toolchain is present.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"

_lock = threading.Lock()


def _compile_so(src: Path, so: Path) -> bool:
    """g++ -> temp file -> atomic rename, so concurrent builders can
    never leave a torn .so for another dlopen. The temp name carries
    pid AND thread id: spawn-pool ingest workers race this across
    processes, and since the build moved outside `_lock`, two threads
    of one process can race it too — a pid-only name would have both
    g++ runs interleaving onto the same file."""
    tmp = so.with_name(
        f".{so.name}.{os.getpid()}.{threading.get_ident()}.tmp")
    try:
        so.parent.mkdir(parents=True, exist_ok=True)
        subprocess.run(
            ["g++", "-O2", "-fPIC", "-std=c++17", "-shared",
             "-o", str(tmp), str(src)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.debug("native lib build failed (%s): %s", src.name, e)
        tmp.unlink(missing_ok=True)
        return False


def _load_so(src: Path, so: Path) -> ctypes.CDLL | None:
    """Shared build-or-rebuild-then-dlopen recipe: honor the
    JEPSEN_TPU_NO_NATIVE kill switch, rebuild when the source is newer
    than the lib, and degrade to None on any failure — a failed
    rebuild included: a lib older than its source is never loaded."""
    from . import gates
    if gates.get("JEPSEN_TPU_NO_NATIVE"):
        return None
    stale = (so.exists() and src.exists()
             and src.stat().st_mtime > so.stat().st_mtime)
    if (not so.exists() or stale) and not (src.exists()
                                           and _compile_so(src, so)):
        return None
    try:
        return ctypes.CDLL(str(so))
    except OSError as e:
        log.debug("native lib load failed (%s): %s", so.name, e)
        return None


_cached: dict[str, ctypes.CDLL | None] = {}

# Fallbacks already warned about (one line per degraded component per
# process; the counter still counts every degraded call).
_warned: set[str] = set()


def count_fallback(what: str) -> None:
    """Count a native→Python degrade in the current tracer's metrics
    without the rebuild-advice warning — for benign per-file declines
    (edited files, content the native pass can't replicate) where the
    library itself is healthy."""
    from . import trace
    trace.counter("native_fallback").inc()
    trace.counter(f"native_fallback.{what}").inc()


def note_fallback(what: str, detail: str = "") -> None:
    """Record a native→Python degrade: bump the `native_fallback`
    metric (plus a per-component counter) and log ONE warning per
    component per process. The native paths otherwise degrade
    silently, which makes a missing/stale .so an invisible 3-9x perf
    regression (ISSUE 2 satellite)."""
    count_fallback(what)
    if what not in _warned:
        _warned.add(what)
        log.warning(
            "native %s unavailable%s; degrading to the Python path "
            "(slower — build with `make -C native` or check g++)",
            what, f" ({detail})" if detail else "")


def _cached_lib(src_name: str, so_name: str, bind) -> ctypes.CDLL | None:
    """One home for the lazy build-load-bind-memoize dance all three
    native libraries share. `bind(L)` attaches restype/argtypes and
    returns False to reject the library (e.g. a stale .so that
    predates the current ABI — it must degrade to the Python engines,
    not crash on missing symbols)."""
    if src_name in _cached:
        L = _cached[src_name]
        if L is None:
            # the warning fired once at first probe, but tracers are
            # per-run: every degraded call still counts, so a later
            # run's metrics.json can't report native_fallback=0 while
            # running fully degraded
            count_fallback(src_name)
        return L
    from . import gates
    # the NO_NATIVE kill switch wins over an explicit lib dir — it
    # must disable EVERY ctypes load, pinned or not
    libdir = None if gates.get("JEPSEN_TPU_NO_NATIVE") \
        else gates.get("JEPSEN_TPU_NATIVE_LIB_DIR")
    # Build + dlopen OUTSIDE the lock: g++ can legitimately run for
    # minutes, and holding the module-wide lock across it would stall
    # every other native consumer (the warm-path hasher included) on
    # an unrelated lib's first build — the JT-LOCK-003 class.
    # _compile_so is temp+rename atomic precisely so concurrent
    # builders (threads here, spawn-pool workers elsewhere) can race
    # harmlessly: at worst the same lib builds twice, never torn.
    if libdir:
        # explicit lib dir (e.g. the sanitizer-instrumented builds):
        # load exactly that lib or degrade to Python — never silently
        # substitute the production build
        try:
            L = ctypes.CDLL(str(Path(libdir) / so_name))
        except OSError as e:
            log.debug("native lib load failed (%s from %s): %s",
                      so_name, libdir, e)
            L = None
    else:
        L = _load_so(_NATIVE_DIR / src_name,
                     _NATIVE_DIR / "build" / so_name)
    if L is not None:
        try:
            if not bind(L):
                L = None
        except AttributeError:
            L = None
    with _lock:
        won = src_name not in _cached
        if won:
            _cached[src_name] = L
        else:
            L = _cached[src_name]   # first finisher won the publish
    if L is None:
        if won:
            note_fallback(
                src_name,
                "JEPSEN_TPU_NO_NATIVE set"
                if gates.get("JEPSEN_TPU_NO_NATIVE")
                else "build/load/ABI-bind failed")
        else:
            count_fallback(src_name)
    return L


def _bind_graph(L: ctypes.CDLL) -> bool:
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    L.jt_tarjan_scc.restype = ctypes.c_int64
    L.jt_tarjan_scc.argtypes = [ctypes.c_int64, i64p, i64p, i64p]
    L.jt_reach.restype = None
    L.jt_reach.argtypes = [ctypes.c_int64, i64p, i64p,
                           ctypes.c_int64, i64p, i64p, u8p]
    return True


def lib() -> ctypes.CDLL | None:
    """The graph-kernel library (Tarjan/BFS), building on first call;
    None when unavailable (no source tree / no compiler)."""
    return _cached_lib("graph_algo.cc", "libjepsen_graph.so",
                       _bind_graph)


def available() -> bool:
    return lib() is not None


# -- history-ingest encoder (native/hist_encode.cc) ----------------------

def _bind_hist(L: ctypes.CDLL) -> bool:
    L.jt_ha_abi_version.restype = ctypes.c_int64
    if L.jt_ha_abi_version() != 5:
        return False
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    L.jt_ha_encode_file.restype = ctypes.c_void_p
    L.jt_ha_encode_file.argtypes = [ctypes.c_char_p]
    L.jt_wr_encode_file.restype = ctypes.c_void_p
    L.jt_wr_encode_file.argtypes = [ctypes.c_char_p]
    L.jt_ha_dims.restype = None
    L.jt_ha_dims.argtypes = [ctypes.c_void_p, i64p]
    for name in ("jt_ha_appends", "jt_ha_reads", "jt_ha_edges",
                 "jt_ha_status", "jt_ha_process", "jt_ha_kid_to_pre"):
        fn = getattr(L, name)
        fn.restype = i32p
        fn.argtypes = [ctypes.c_void_p]
    for name in ("jt_ha_invoke_index", "jt_ha_complete_index",
                 "jt_ha_anomalies"):
        fn = getattr(L, name)
        fn.restype = i64p
        fn.argtypes = [ctypes.c_void_p]
    L.jt_ha_pre_key_names_json.restype = ctypes.c_char_p
    L.jt_ha_pre_key_names_json.argtypes = [ctypes.c_void_p]
    L.jt_ha_free.restype = None
    L.jt_ha_free.argtypes = [ctypes.c_void_p]
    # ABI v5: versioned sidecar writer (1 = lean, 2 = dispatch-shaped)
    # + the bounded-hash primitive (parity-tested against store.xxh64)
    L.jt_ha_write_sidecar.restype = ctypes.c_int64
    L.jt_ha_write_sidecar.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p, ctypes.c_int64]
    L.jt_xxh64_buf.restype = ctypes.c_uint64
    L.jt_xxh64_buf.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                               ctypes.c_uint64]
    # per-key split (jt_ks_*): same library, own handle type
    L.jt_ks_split_file.restype = ctypes.c_void_p
    L.jt_ks_split_file.argtypes = [ctypes.c_char_p]
    L.jt_ks_dims.restype = None
    L.jt_ks_dims.argtypes = [ctypes.c_void_p, i64p]
    L.jt_ks_key_ids.restype = i32p
    L.jt_ks_key_ids.argtypes = [ctypes.c_void_p]
    L.jt_ks_key_names_json.restype = ctypes.c_char_p
    L.jt_ks_key_names_json.argtypes = [ctypes.c_void_p]
    L.jt_ks_free.restype = None
    L.jt_ks_free.argtypes = [ctypes.c_void_p]
    return True


def hist_lib() -> ctypes.CDLL | None:
    """The native history-ingest encoder (jt_ha_* ABI), built on first
    call; None when unavailable. Same degrade-to-Python contract as
    lib()."""
    return _cached_lib("hist_encode.cc", "libjepsen_histenc.so",
                       _bind_hist)


# -- WGL linearizability search (native/wgl.cc) --------------------------

def _bind_wgl(L: ctypes.CDLL) -> bool:
    L.jt_wgl_abi_version.restype = ctypes.c_int64
    if L.jt_wgl_abi_version() != 2:
        return False
    L.jt_wgl_run.restype = None
    L.jt_wgl_run.argtypes = [ctypes.POINTER(ctypes.c_int32),
                             ctypes.c_int64, ctypes.c_int64,
                             ctypes.c_int64,
                             ctypes.POINTER(ctypes.c_int64)]
    return True


def wgl_lib() -> ctypes.CDLL | None:
    """The native WGL search (jt_wgl_* ABI; CAS-register and mutex
    models), built on first call; None when unavailable — the Python
    engine in checker.knossos stays the oracle and fallback."""
    return _cached_lib("wgl.cc", "libjepsen_wgl.so", _bind_wgl)


def _csr(n: int, adj: list[list[int]]) -> tuple[np.ndarray, np.ndarray] | None:
    """CSR arrays, or None if any column index is out of [0, n) — the
    C++ kernel does no bounds checks, so invalid graphs must take the
    Python path (which raises a clean IndexError instead of corrupting
    memory)."""
    counts = np.fromiter((len(a) for a in adj), np.int64, count=n)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    col = np.fromiter((w for a in adj for w in a), np.int64,
                      count=int(row_ptr[-1]))
    if col.size and (col.min() < 0 or col.max() >= n):
        return None
    return row_ptr, col


def _p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def tarjan_scc(n: int, adj: list[list[int]]) -> list[int] | None:
    """SCC ids per node via the C++ kernel, or None if unavailable."""
    L = lib()
    if L is None or n == 0:
        return None if L is None else []
    csr = _csr(n, adj)
    if csr is None:
        return None
    row_ptr, col = csr
    out = np.empty(n, np.int64)
    L.jt_tarjan_scc(n, _p(row_ptr), _p(col), _p(out))
    return out.tolist()


def tarjan_scc_csr(n: int, row_ptr: np.ndarray,
                   col: np.ndarray) -> np.ndarray | None:
    """SCC ids straight from CSR arrays (the 100k-node condensation path
    — no Python adjacency lists in between). Returns int64 [n] or None
    when the kernel is unavailable or the CSR is malformed."""
    L = lib()
    if L is None:
        return None
    if n == 0:
        return np.zeros(0, np.int64)
    row_ptr = np.ascontiguousarray(row_ptr, np.int64)
    col = np.ascontiguousarray(col, np.int64)
    if len(row_ptr) != n + 1 or int(row_ptr[-1]) != len(col):
        return None
    if int(row_ptr[0]) != 0 or np.any(np.diff(row_ptr) < 0):
        return None
    if col.size and (col.min() < 0 or col.max() >= n):
        return None
    out = np.empty(n, np.int64)
    L.jt_tarjan_scc(n, _p(row_ptr), _p(col), _p(out))
    return out


def split_key_ids(path) -> tuple[list, np.ndarray] | None:
    """Per-op [key value] split ids for a history.jsonl, from the
    native splitter (hist_encode.cc's jt_ks_* ABI): returns
    (keys, key_ids) where `keys` are the lifted key values in
    first-seen order and `key_ids` is an int32 array aligned with the
    file's op lines (-1 = un-lifted op). None means "use the Python
    splitter" (lib unavailable, file absent, or content whose lift /
    key-equality semantics the native pass can't replicate)."""
    import json

    L = hist_lib()
    if L is None:
        return None
    h = L.jt_ks_split_file(os.fsencode(path))
    if not h:
        # benign: the library is healthy, this file's lift semantics
        # just aren't natively replicable — count, don't cry rebuild
        count_fallback("split_key_ids")
        log.debug("native split declined %s", path)
        return None
    try:
        dims = (ctypes.c_int64 * 4)()
        L.jt_ks_dims(h, dims)
        n_ops, n_keys, json_len, _lifted = dims
        if n_ops == 0:
            ids = np.zeros(0, np.int32)
        else:
            ids = np.ctypeslib.as_array(
                L.jt_ks_key_ids(h), shape=(int(n_ops),)).copy()
        keys = json.loads(
            L.jt_ks_key_names_json(h).decode("utf-8")) if json_len \
            else []
        if len(keys) != int(n_keys):
            note_fallback("split_key_ids", "key-name/ids ABI drift")
            return None  # ABI drift: don't guess
        return keys, ids
    finally:
        L.jt_ks_free(h)


def reach(n: int, adj: list[list[int]],
          queries: list[tuple[int, int]]) -> list[bool] | None:
    """Batch src->dst reachability via the C++ kernel, or None."""
    L = lib()
    if L is None:
        return None
    if not queries:
        return []
    csr = _csr(n, adj)
    if csr is None:
        return None
    row_ptr, col = csr
    src = np.asarray([q[0] for q in queries], np.int64)
    dst = np.asarray([q[1] for q in queries], np.int64)
    out = np.zeros(len(queries), np.uint8)
    L.jt_reach(n, _p(row_ptr), _p(col), len(queries),
               _p(src), _p(dst),
               out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return [bool(x) for x in out]
