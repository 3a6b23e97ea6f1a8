"""The main path's kernels compiled for a described v5e:2x2 topology at
the north star's widths — what the chip's compiler would refuse, and
how much HBM each program needs, without a chip. Nothing here runs."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from jepsen_tpu import parallel
from jepsen_tpu.checker.elle import kernels as K
from jepsen_tpu.checker.elle import synth
from jepsen_tpu.checker.knossos import dense
from jepsen_tpu.checker.knossos import synth as ksynth

#: One v5e chip's HBM.
HBM_BYTES = 16 * 2**30
#: The north-star bucket: 32 histories of 5000 txns, K=64.
B_NORTH, T_NORTH, K_NORTH = 32, 5000, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def north_enc():
    return synth.synth_encoded_history(T_NORTH, K=K_NORTH)


def _check_args(shape: K.BatchShape, B: int, sharding) -> tuple:
    def s(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=sharding)
    return (s(B, shape.n_appends, 3), s(B, shape.n_reads, 3),
            s(B, shape.n_txns), s(B, shape.n_txns), s(B, shape.n_txns),
            s(B))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)


@pytest.mark.parametrize("classify", [False, True],
                         ids=["detect", "fused-classify"])
def test_north_star_check_compiles(one_chip, north_enc, classify):
    shape = K.BatchShape.plan([north_enc] * B_NORTH)
    assert shape.n_txns == 5120 and shape.max_pos == 80
    fn = parallel.sharded_check_fn(None, shape, classify=classify,
                                   fused=True)
    compiled = fn.lower(*_check_args(shape, B_NORTH, one_chip)).compile()
    # one north-star bucket fits one chip's HBM; two do not
    assert _device_bytes(compiled) < HBM_BYTES


def test_wr_bucket_compiles(one_chip):
    """The rw-register sweep's bucket: 5 histories at T=4992 whose
    packed edge rows (the larger of the sweep's two edge pads) the
    device scatters into [T,T] matrices, classified with the fused
    kernel (check_edge_batch's defaults), compile for one chip and
    fit it."""
    B, T, E = 5, 4992, K.edge_pad(4097)

    def s(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    args = (s(jnp.int32, B, E, 3), s(jnp.int32, B, T),
            s(jnp.int32, B, T), s(jnp.int32, B, T), s(jnp.int32, B))
    compiled = K.classify_matrices_device.lower(
        *args, steps=K.closure_steps(T), classify=True,
        fused=True).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_analyze_store_buckets_fit_in_flight(one_chip, north_enc):
    """analyze-store's own geometry at the north-star shape: buckets
    sized by the default cell budget, `max_inflight` of them resident
    at once, must fit the chip — so a sweep never OOMs and backs down."""
    depth = 2   # check_bucketed_async's default max_inflight
    buckets = parallel.bucket_by_length(
        [north_enc] * 64, budget_cells=(1 << 27) // depth)
    B = len(buckets[0])
    shape = K.BatchShape.plan([north_enc] * B)
    fn = parallel.sharded_check_fn(None, shape, classify=True)
    compiled = fn.lower(*_check_args(shape, B, one_chip)).compile()
    assert depth * _device_bytes(compiled) < HBM_BYTES


def test_knossos_dense_compiles(one_chip):
    encs = [dense.encode_dense_history(ksynth.synth_register_history(
        n_ops=1000, n_procs=10, info_prob=0.002, seed=s,
        max_pending=14)) for s in range(4)]
    shape = dense.DenseBatchShape.plan(encs)
    B = 256
    regs = jax.ShapeDtypeStruct((B, shape.n_steps, shape.n_slots, 4),
                                jnp.int32, sharding=one_chip)
    comp = jax.ShapeDtypeStruct((B, shape.n_steps), jnp.int32,
                                sharding=one_chip)
    compiled = dense.check_dense_device.lower(
        regs, comp, n_values=shape.n_values,
        n_slots=shape.n_slots).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_mesh_check_compiles_sharded(topo, north_enc):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "mp"))
    shape = K.BatchShape.plan([north_enc] * B_NORTH)
    fn = parallel.sharded_check_fn(mesh, shape, classify=True)
    compiled = fn.lower(*_check_args(
        shape, B_NORTH, NamedSharding(mesh, P("dp")))).compile()
    text = compiled.as_text()
    # the mp-sharded closure matmuls need collectives over ICI
    assert "all-gather" in text or "all-reduce" in text
    assert _device_bytes(compiled) < HBM_BYTES
