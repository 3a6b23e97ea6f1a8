"""Pallas TPU kernel for the closure-squaring step — THE hot op.

The transitive-closure fixpoint (kernels._closure_batched) squares a
[B, T, T] boolean reachability matrix each round:

    m2 = (bf16(m) @ bf16(m)) > 0

On the XLA path that is three HBM passes per round: cast bool->bf16
(materialized), the matmul, and the f32->bool compare. This kernel
fuses all three: bool tiles are DMA'd to VMEM once, cast on the VPU,
accumulated on the MXU in an f32 VMEM scratch over the k-tiles, and
thresholded back to bool as they leave — one HBM read of m per operand
tile and one bool write, no bf16/f32 intermediates in HBM.

Grid is (B, i, j, k) with k innermost (sequential — "arbitrary"
semantics) so the accumulator scratch carries across the k loop of one
output tile; b/i/j are parallel. T must be a multiple of the tile (the
encoders pad T to 128 already).

Used by kernels._closure_batched on unsharded TPU dispatches;
mesh-sharded closures keep the XLA matmul so the compiler can insert
the dp/mp collectives. Correctness is pinned CPU-side via
interpret=True differential tests (tests/test_pallas_square.py) and on
hardware by the `-m tpu` tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tests flip this to run the kernel through the Pallas interpreter on
# CPU (full-verdict parity without hardware); production leaves it off.
INTERPRET = False


def _square_kernel(a_ref, b_ref, out_ref, acc_ref, *, dot_dtype):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0].astype(dot_dtype)
    b = b_ref[0].astype(dot_dtype)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _emit():
        out_ref[0] = acc_ref[...] > 0


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "int8"))
def closure_square(m: jnp.ndarray, *, tile: int = 256,
                   interpret: bool = False,
                   int8: bool = False) -> jnp.ndarray:
    """One closure round: (cast(m) @ cast(m)) > 0 for m [B, T, T] bool —
    bf16 dots accumulated in f32 by default, or int8 dots accumulated
    in int32 (exact for boolean operands, ~2× MXU throughput on v5e):
    the fusion (VMEM residency) and the arithmetic (int8) are
    orthogonal levers, and this kernel stacks them.

    `tile` shrinks to T when T < tile; T must divide evenly by the
    effective tile (guaranteed by the 128-padding in the encoders)."""
    B, T, T2 = m.shape
    assert T == T2, m.shape
    t = tile if T % tile == 0 else 128  # encoders pad T to 128
    t = min(t, T)
    assert T % t == 0, (T, t)
    grid = (B, T // t, T // t, T // t)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    dot_dtype = jnp.int8 if int8 else jnp.bfloat16
    acc_dtype = jnp.int32 if int8 else jnp.float32
    return pl.pallas_call(
        functools.partial(_square_kernel, dot_dtype=dot_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, t), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, t, t), lambda b, i, j, k: (b, k, j)),
        ],
        out_specs=pl.BlockSpec((1, t, t), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((t, t), acc_dtype)],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * T * T * T,
            bytes_accessed=m.size * 2 + m.size,
            transcendentals=0),
        interpret=interpret,
        **kwargs,
    )(m, m)


_works: dict[bool, bool] = {}


def pallas_available(int8: bool = False) -> bool:
    """True when the default device is a TPU and the requested kernel
    variant lowers there and squares a probe input correctly (checked
    once per process per variant); False on any other platform, where
    only interpret mode (tests) can run it. A kernel that fails to
    lower or miscomputes on a TPU raises: the caller asked for this
    kernel, and no other runs in its place."""
    cached = _works.get(int8)
    if cached is not None:
        return cached
    from ...devices import default_devices
    if default_devices()[0].platform != "tpu":
        _works[int8] = False
        return False
    import numpy as np
    # 256 is divisible by both effective tiles, so this lowers the
    # same tile=256 configuration the production shapes use
    eye = np.eye(256, dtype=bool)[None]
    out = np.asarray(closure_square(jnp.asarray(eye), int8=int8))
    if not (out == eye).all():
        raise RuntimeError(
            f"pallas closure kernel (int8={int8}) miscomputed its probe")
    _works[int8] = True
    return True
