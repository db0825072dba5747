"""Zigzag (load-balanced causal) ring attention vs the dense reference.

Same pattern as test_sequence_parallel.py: 8-device CPU mesh, random
tensors, exactness against ``reference_attention``, gradients via
autograd.  The zigzag layout is the balanced-causal design — see the
module docstring of ``parallel/zigzag_attention.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import (make_mesh, reference_attention,
                                  zigzag_ring_self_attention,
                                  zigzag_shard, zigzag_unshard)


def _rand(b=1, t=128, h=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("p_size", [1, 2, 4])
def test_zigzag_shard_roundtrip(p_size):
    x = jnp.arange(2 * 16 * 3).reshape(2, 16, 3).astype(jnp.float32)
    y = zigzag_unshard(zigzag_shard(x, p_size), p_size)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_zigzag_shard_layout():
    """Rank i's contiguous slice is chunk i then chunk 2P-1-i."""
    p = 4
    x = jnp.arange(2 * p * 2)[None, :, None]          # chunks of 2
    z = np.asarray(zigzag_shard(x, p))[0, :, 0]
    # rank 0: chunk 0 (0,1) + chunk 7 (14,15)
    np.testing.assert_array_equal(z[:4], [0, 1, 14, 15])
    # rank 3: chunk 3 (6,7) + chunk 4 (8,9)
    np.testing.assert_array_equal(z[12:], [6, 7, 8, 9])


@pytest.mark.parametrize("p_size", [2, 4, 8])
def test_zigzag_matches_dense_causal(p_size):
    mesh = make_mesh({"sp": p_size}, devices=jax.devices()[:p_size])
    q, k, v = _rand(t=128, seed=1)
    expected = reference_attention(q, k, v, causal=True)
    got = zigzag_ring_self_attention(q, k, v, mesh, use_flash=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_single_rank_degenerate():
    mesh = make_mesh({"sp": 1}, devices=jax.devices()[:1])
    q, k, v = _rand(t=32, seed=2)
    expected = reference_attention(q, k, v, causal=True)
    got = zigzag_ring_self_attention(q, k, v, mesh, use_flash=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_with_flash_blocks():
    """Flash kernel (interpret mode) computing each zigzag block, under
    the framework's (unchecked) shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map
    from horovod_tpu.parallel.zigzag_attention import (
        zigzag_ring_attention)

    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    b, t, h, d = 1, 1024, 2, 16                    # C=128: packed lse
    q, k, v = _rand(b=b, t=t, h=h, d=d, seed=3)
    expected = reference_attention(q, k, v, causal=True)

    spec = P(None, "sp", None, None)
    fn = functools.partial(zigzag_ring_attention, axis_name="sp",
                           use_flash=True)
    sm = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    args = [jax.device_put(zigzag_shard(x, 4), sharding)
            for x in (q, k, v)]
    got = zigzag_unshard(sm(*args), 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_gradients_match_dense():
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(t=64, seed=4)

    def loss_z(q, k, v):
        return jnp.sum(
            zigzag_ring_self_attention(q, k, v, mesh,
                                       use_flash=False) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gz = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gz, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_zigzag_rejects_bad_length():
    with pytest.raises(ValueError, match="not divisible"):
        zigzag_shard(jnp.zeros((1, 30, 2, 4)), 4)


def test_zigzag_bf16():
    """bf16 inputs ride the same fp32 streaming-softmax accumulators."""
    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _rand(t=128, seed=6)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    expected = reference_attention(q, k, v, causal=True)
    got = zigzag_ring_self_attention(qb, kb, vb, mesh, use_flash=False)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(expected), rtol=0.1, atol=0.1)
