"""Persistent AOT executable cache: zero XLA compiles on warm sweeps.

A bucketed sweep's kernels are keyed by a tiny tuple — bucket shape,
batch size, flag set, backend — yet every fresh
`analyze-store` process used to re-trace and re-compile each of them
from scratch: tens of seconds of XLA time on the north-star shape
before the first verdict, paid again on every repeat sweep over the
same store. This module front-ends `jax.jit(...).lower(...).compile()`
with two layers:

  * an in-process map (compiled executables reused across buckets of
    the same geometry — what jit's own tracing cache did, minus the
    tracing), and
  * a disk cache of serialized executables
    (`jax.experimental.serialize_executable`), keyed by a digest of
    (jax/jaxlib version, backend platform + device count, jitted
    function name, input avals and shardings — a mesh's axis names
    and sizes included — kernel flags), so a REPEAT sweep
    in a fresh process deserializes instead of compiling.

Single-device and mesh-sharded dispatches both resolve here: an
executable compiled from a jit with in/out shardings carries its
collectives, and a lookup by fingerprint never traces, so a rebuilt
jitted wrapper costs a dict probe, not a re-trace.

Every lookup lands in exactly one of the `compile_cache_hits` /
`compile_cache_misses` counters — the warm-path bench drives the miss
count to zero and `make bench-warm` gates on it. Everything here is
best-effort: a corrupt/incompatible cache entry (jax upgrade, topology
change — both keyed, but belt and braces) degrades to a fresh compile,
never to a failed sweep. Gate: `JEPSEN_TPU_AOT_CACHE` (default on).

One compile cache, placed from outside: JAX's persistent compilation
cache and the serialized executables (in its `executables/`
subdirectory) both live under `JAX_COMPILATION_CACHE_DIR` when it is
set, and under the fixed, git-ignored `<repo>/.jax_cache` otherwise.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
from pathlib import Path

log = logging.getLogger(__name__)

#: In-memory executables, bounded: a sweep sees a handful of bucket
#: geometries, so 128 is generous; insertion order evicts oldest.
_MEM_CAP = 128

_mem: dict[str, object] = {}
_lock = threading.Lock()


def enabled() -> bool:
    """One home for the JEPSEN_TPU_AOT_CACHE gate (default on)."""
    from . import gates
    return gates.get("JEPSEN_TPU_AOT_CACHE")


#: The compile cache's home when JAX_COMPILATION_CACHE_DIR is unset:
#: fixed and inside the checkout, so a later process finds it again.
DEFAULT_CACHE_ROOT = Path(__file__).resolve().parent.parent / ".jax_cache"


def cache_root() -> Path:
    """Where every compile artifact lives: JAX_COMPILATION_CACHE_DIR,
    else DEFAULT_CACHE_ROOT."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(d) if d else DEFAULT_CACHE_ROOT


def cache_dir() -> Path:
    """The on-disk serialized-executable cache directory."""
    return cache_root() / "executables"


def configure_jax_cache() -> Path:
    """Point JAX's persistent compilation cache at cache_root(). JAX
    reads JAX_COMPILATION_CACHE_DIR itself, so this only sets the
    in-checkout default; call it before the process's first compile
    (the sweep and daemon entry points do)."""
    import jax
    root = cache_root()
    if jax.config.jax_compilation_cache_dir != str(root):
        jax.config.update("jax_compilation_cache_dir", str(root))
    return root


def clear_memory() -> None:
    """Drop the in-process executable map (tests; a backend restart)."""
    with _lock:
        _mem.clear()


def resident_count() -> int:
    """How many compiled executables the in-process map holds — the
    `resident_executables` gauge the device cost observatory
    publishes (obs.device via residency.publish_residency_gauges)."""
    with _lock:
        return len(_mem)


def _placement(a) -> list:
    """The devices an argument lives on, in the executable's device
    order (a mesh's, else by id)."""
    sh = a.sharding
    mesh = getattr(sh, "mesh", None)
    if mesh is not None:
        return list(mesh.devices.flat)
    return sorted(sh.device_set, key=lambda d: d.id)


def _sharding_key(a) -> str:
    """An argument's placement: sharding type, device ids and spec, and
    for a mesh its axis names and sizes. An executable compiled for
    8-shard inputs cannot run 1-device ones, and one compiled on a 2x2
    dp x mp mesh is not the one for a 4x1 mesh over the same devices
    with the same spec (the closure's constraint names `mp`). A
    single-device sharding has no mesh: its key, and every executable
    cached under it, stays as it was."""
    sh = a.sharding
    ids = [d.id for d in _placement(a)]
    key = f"{type(sh).__name__}{ids}{getattr(sh, 'spec', '')}"
    mesh = getattr(sh, "mesh", None)
    if mesh is not None:
        key += str(tuple(mesh.shape.items()))
    return key


def _fingerprint(jitfn, args, key_parts: tuple) -> str:
    """Digest of everything that determines the compiled artifact:
    toolchain versions, backend topology, the jitted function, input
    avals and shardings, kernel flags."""
    import jax
    import jaxlib
    parts = [jax.__version__, jaxlib.__version__,
             jax.devices()[0].platform, str(jax.device_count()),
             getattr(jitfn, "__name__", type(jitfn).__name__),
             repr(key_parts)]
    for a in args:
        parts.append(f"{tuple(a.shape)}:{a.dtype}:{_sharding_key(a)}")
    return hashlib.sha256("|".join(map(str, parts)).encode()).hexdigest()


def _disk_load(path: Path, devices: list):
    """Deserialize one cached executable onto `devices` (the ones it
    was compiled for — left unnamed, JAX loads it onto every local
    device), or None (missing/corrupt/incompatible — the caller
    recompiles and overwrites)."""
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = pickle.loads(path.read_bytes())
        return se.deserialize_and_load(payload, in_tree, out_tree,
                                       execution_devices=devices)
    except FileNotFoundError:
        return None
    except Exception:
        log.debug("AOT cache load failed for %s; recompiling",
                  path, exc_info=True)
        return None


def _disk_store(path: Path, compiled) -> None:
    """Serialize one executable, atomically (temp + rename — a crash
    mid-write must never leave a torn entry for another process)."""
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
        tmp.write_bytes(pickle.dumps((payload, in_tree, out_tree)))
        os.replace(tmp, path)
    except Exception:
        log.debug("AOT cache store failed for %s", path, exc_info=True)


def compiled_for(jitfn, args, key_parts: tuple):
    """The compiled executable for `jitfn` over `args`' avals: memory,
    then disk, then `lower().compile()` (+ persist). Exactly one of
    compile_cache_hits/compile_cache_misses increments per call. Any
    failure in the AOT machinery returns the plain jitted fn — the
    sweep must never be hostage to its own compile cache."""
    from . import trace
    try:
        key = _fingerprint(jitfn, args, key_parts)
        with _lock:
            hit = _mem.get(key)
        if hit is not None:
            trace.counter("compile_cache_hits").inc()
            # re-observe on memory hits too: the observatory resets
            # per sweep, and a later sweep's costdb must still carry
            # the resident executables it dispatched (dict probe once
            # captured; nothing with JEPSEN_TPU_COSTDB off)
            from .obs import device as device_obs
            device_obs.observe(key_parts, args, hit, source="compiled")
            return hit
        path = cache_dir() / f"{key}.jtx"
        compiled = _disk_load(path, _placement(args[0]))
        if compiled is not None:
            trace.counter("compile_cache_hits").inc()
        else:
            trace.counter("compile_cache_misses").inc()
            compiled = jitfn.lower(*args).compile()
            _disk_store(path, compiled)
        with _lock:
            if len(_mem) >= _MEM_CAP:
                _mem.pop(next(iter(_mem)))
            _mem[key] = compiled
        # the device cost observatory's capture point: the compiled
        # executable's cost/memory analyses, once per (key_parts,
        # batch) — a dict probe on repeats, nothing at all with the
        # JEPSEN_TPU_COSTDB gate off
        from .obs import device as device_obs
        device_obs.observe(key_parts, args, compiled, source="compiled")
        return compiled
    except Exception:
        log.warning("AOT executable cache failed; dispatching via jit",
                    exc_info=True)
        return jitfn
