"""Stores generated from the seed, and put back between passes."""

from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os
import shutil
from pathlib import Path


def _generate(module_path: str, cfg: dict, root: str, seed: int,
              first: int, count: int) -> dict:
    from harness import spec
    mod = spec.load_module(Path(module_path))
    return mod.generate(cfg, Path(root), seed, count, first)


def generate(mod, cfg: dict, root: Path, seed: int, count: int,
             first: int = 0, workers: int | None = None) -> dict:
    """`mod.generate` over `count` run dirs, in chunks across a spawned
    pool for a large store (the workers import only the generator, never
    JAX); the seeded truth of every run, by name. Byte-identical for any
    worker count."""
    root.mkdir(parents=True, exist_ok=True)
    if workers is None:
        workers = 1 if count < 32 else min(8, os.cpu_count() or 1)
    if workers == 1:
        return mod.generate(cfg, root, seed, count, first)
    step = -(-count // workers)
    truth: dict = {}
    ctx = multiprocessing.get_context("spawn")
    with cf.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        futs = [pool.submit(_generate, mod.__file__, cfg, str(root), seed,
                            first + i, min(step, count - i))
                for i in range(0, count, step)]
        for f in futs:
            truth.update(f.result())
    # a large store's writeback belongs to set-up, not to the window
    os.sync()
    return truth


def restore(store: Path, test: str) -> None:
    """Put a swept store back to its generated state: only each run's
    history.jsonl stays, and nothing beside the test dir."""
    for p in store.iterdir():
        if p.name == test:
            continue
        if p.is_dir():
            shutil.rmtree(p)
        else:
            p.unlink()
    for d in (store / test).iterdir():
        for f in d.iterdir():
            if f.name != "history.jsonl":
                if f.is_dir():
                    shutil.rmtree(f)
                else:
                    f.unlink()
