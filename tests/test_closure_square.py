"""The closure's int8 squaring and its fixpoint loop, against plain
numpy references."""

import numpy as np
import pytest

import jax.numpy as jnp

from jepsen_tpu.checker.elle import kernels as K
from jepsen_tpu.checker.elle import synth


def np_square(m):
    """One boolean squaring, in integer numpy."""
    mi = m.astype(int)
    return (mi @ mi) > 0


def int8_square(m):
    return np.asarray(K._square(jnp.asarray(m), K._identity))


@pytest.mark.parametrize("B,T", [(1, 128), (3, 128), (2, 256), (1, 384)])
def test_square_parity_random(B, T):
    rng = np.random.default_rng(B * 1000 + T)
    m = rng.random((B, T, T)) < 0.02
    m |= np.eye(T, dtype=bool)[None]
    assert (int8_square(m) == np_square(m)).all()


def test_square_empty_and_full():
    for m in (np.zeros((1, 128, 128), bool),
              np.ones((1, 128, 128), bool)):
        assert (int8_square(m) == np_square(m)).all()


def test_int8_on_sharded_mesh():
    """The squaring is plain XLA dot_general, so it runs on the dp×mp
    mesh, and the sharded build's flag words equal the single-device
    build's on the same batch."""
    from jepsen_tpu import parallel
    batch = synth.synth_valid_batch(B=4, T=64, K=8, seed=1)
    batch = synth.inject_g1c(batch, np.asarray([2]), 8)
    shape = batch["shape"]
    mesh = parallel.make_mesh()
    sharded = np.asarray(parallel.sharded_check_fn(mesh, shape)(
        *parallel.shard_batch(mesh, batch)))
    local = np.asarray(parallel.sharded_check_fn(None, shape)(
        *parallel.shard_batch(None, batch)))
    np.testing.assert_array_equal(sharded, local)
    assert local[2] & (1 << K.G1C)
    assert local[0] == 0 and local[1] == 0 and local[3] == 0


# (path nodes, squarings at the fixpoint exit). A path of n nodes has
# diameter d = n-1 and is closed after ceil(log2 d) squarings; the loop
# runs one more to see nothing change, unless the static bound
# closure_steps(T_pad) stops it first (128 nodes: T_pad 128, bound 7;
# 300 nodes: T_pad 384, bound 9).
@pytest.mark.parametrize("n,rounds", [(2, 1), (3, 2), (17, 5), (128, 7),
                                      (300, 9)])
def test_closure_fixpoint_rounds(n, rounds):
    T = K.pad_to(n, 128)
    m = np.zeros((1, T, T), bool)
    idx = np.arange(n - 1)
    m[0, idx, idx + 1] = True
    want = np.eye(T, dtype=bool)
    want[:n, :n] |= np.triu(np.ones((n, n), bool))
    c, i = K._closure_batched(jnp.asarray(m), K.closure_steps(T),
                              K._identity)
    assert (np.asarray(c)[0] == want).all()
    assert int(i) == rounds
    # the stats closure runs the same loop body: same matrix, and its
    # per-history count is the last round that changed the matrix
    cs, hist_rounds, cyc_round = K._closure_batched_stats(
        jnp.asarray(m), K.closure_steps(T), K._identity)
    assert (np.asarray(cs) == np.asarray(c)).all()
    assert int(hist_rounds[0]) == int(np.ceil(np.log2(n - 1)))
    assert int(cyc_round[0]) == -1
