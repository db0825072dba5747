"""Pallas flash-attention kernel vs dense reference (interpret mode on CPU).

The kernel is the TPU hot-op (SURVEY §2.2: the reference has no compute
kernels of its own; this framework does).  Same test pattern as the rest:
random tensors, numpy-level expectation, gradients via autograd.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.parallel import reference_attention


def _rand(b=2, t=128, h=4, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _rand()
    expected = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _rand(b=1, t=64, h=2, d=16, seed=1)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _rand(t=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(qb, kb, vb, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(expected), rtol=0.1, atol=0.1)


def test_flash_non_pow2_seq():
    """Sequence length not divisible by 128: block picker shrinks blocks."""
    q, k, v = _rand(t=96, seed=2)
    expected = reference_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_shapes():
    """Tkv != Tq (cross attention, non-causal)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 4, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 4, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 4, 32).astype(np.float32))
    expected = reference_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_in_transformer():
    """flash_attention drops into TransformerConfig.attn_fn."""
    from horovod_tpu.models import Transformer, TransformerConfig

    base = TransformerConfig(vocab_size=64, n_layers=1, d_model=32,
                             n_heads=2, d_ff=64, max_len=32,
                             dtype=jnp.float32)
    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=32,
                            n_heads=2, d_ff=64, max_len=32,
                            dtype=jnp.float32, attn_fn=flash_attention)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    params = Transformer(base).init(jax.random.PRNGKey(0), tokens)["params"]
    expected = Transformer(base).apply({"params": params}, tokens)
    got = Transformer(cfg).apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_flash_in_ulysses():
    """flash_attention as the local kernel of Ulysses sequence parallelism."""
    from horovod_tpu.parallel import make_mesh, ulysses_self_attention

    mesh = make_mesh({"sp": 8})
    q, k, v = _rand(t=64, h=8, seed=4)
    expected = reference_attention(q, k, v, causal=True)
    got = ulysses_self_attention(q, k, v, mesh, causal=True,
                                 attn_fn=flash_attention)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- lse API
def test_flash_lse_matches_reference_logsumexp():
    q, k, v = _rand(b=1, t=64, h=2, d=16, seed=3)
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
    # dense logsumexp of the masked scores
    scale = 1.0 / np.sqrt(16)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) * scale
    msk = np.arange(64)[:, None] >= np.arange(64)[None, :]
    s = np.where(msk[None, None], s, -1e30)
    expect_lse = np.log(np.sum(np.exp(
        s - s.max(-1, keepdims=True)), -1)) + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), expect_lse,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


def test_flash_lse_gradient():
    """The lse cotangent folds into delta (ds = p*(dp - delta + g_lse));
    check against autodiff through the dense logsumexp."""
    q, k, v = _rand(b=1, t=32, h=2, d=16, seed=4)
    scale = 1.0 / np.sqrt(16)

    def loss_flash(q, k, v):
        out, lse = flash_attention(q, k, v, causal=False, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- flash inside ring
@pytest.mark.parametrize("causal", [False, True])
def test_flash_in_ring_attention(causal):
    """Ring attention with the Pallas kernel computing each local block
    (interpret mode on the 8-device CPU mesh) is exact attention."""
    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.ring_attention import ring_self_attention
    import functools
    from horovod_tpu.parallel._compat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
    b, t, h, d = 1, 64, 2, 16
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
               for _ in range(3))

    spec = P(None, "sp", None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp",
                          causal=causal, use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    out = fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
             jax.device_put(v, sharding))
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_mxu_transpose_helpers_exact():
    """_col_to_row/_row_to_col: identity-matmul lane<->sublane moves must
    be bit-exact for fp32 (one nonzero product per output element)."""
    from horovod_tpu.ops.pallas.flash_attention import (_col_to_row,
                                                       _row_to_col)
    rng = np.random.RandomState(7)
    col = jnp.asarray(rng.randn(128, 1).astype(np.float32))
    row = _col_to_row(col)
    assert row.shape == (1, 128)
    assert np.array_equal(np.asarray(row)[0], np.asarray(col)[:, 0])
    back = _row_to_col(row)
    assert np.array_equal(np.asarray(back), np.asarray(col))


def test_packed_lse_layout_engaged_and_dense():
    """VERDICT r2 item 6: with block_q=128 the backward's lse/delta ride
    a dense [bh, t/128, 1, 128] layout (128x less HBM than the broadcast
    fallback).  Check the forward's residual output shape directly and
    that long-T backward matches the dense reference."""
    from horovod_tpu.ops.pallas.flash_attention import _fwd
    rng = np.random.RandomState(11)
    bh, t, d = 2, 512, 32
    mk = lambda: jnp.asarray(rng.randn(bh, t, d).astype(np.float32))
    q3, k3, v3 = mk(), mk(), mk()
    out, lse = _fwd(q3, k3, v3, scale=d ** -0.5, causal=False,
                    block_q=128, block_k=128, interpret=True)
    assert lse.shape == (bh, t)

    # prove the PACKED layout is what the kernel writes to HBM: the
    # pallas_call's lse output aval must be [bh, t/128, 1, 128], not the
    # broadcast [bh, t, 128] (which would also reshape to (bh, t) after
    # the [:, :, 0] slice — shape of the public return can't catch it)
    import functools as ft
    jaxpr = jax.make_jaxpr(ft.partial(
        _fwd, scale=d ** -0.5, causal=False, block_q=128, block_k=128,
        interpret=True))(q3, k3, v3)
    pallas_out_shapes = [
        tuple(v.aval.shape)
        for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"
        for v in eqn.outvars]
    assert (bh, t // 128, 1, 128) in pallas_out_shapes, pallas_out_shapes
    assert (bh, t, 128) not in pallas_out_shapes, pallas_out_shapes

    # end-to-end gradient at t=512 (packed path active: block_q=128)
    q = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 512, 2, 16).astype(np.float32))

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_long_sequence_backward_packed():
    """T=4096 causal backward through the packed lse/delta layout — the
    long-sequence regime the round-2 broadcast layout capped (its dkv
    kernel held full-T 128-lane tiles of both operands).  Both backward
    kernels (dq; dk/dv) must produce finite, non-trivial gradients."""
    q, k, v = _rand(b=1, t=4096, h=1, seed=0)
    gq, gk, gv = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for name, g in (("dq", gq), ("dk", gk), ("dv", gv)):
        arr = np.asarray(g)
        assert np.isfinite(arr).all(), name
        assert np.abs(arr).max() > 0, name


def test_flash_block_env_overrides_validated(monkeypatch):
    """HVD_FLASH_BLOCK_Q/K override the defaults; non-positive or
    garbage values fall back instead of crashing _pick_block."""
    from horovod_tpu.ops.pallas.flash_attention import _env_block

    monkeypatch.setenv("HVD_FLASH_BLOCK_Q", "256")
    assert _env_block("HVD_FLASH_BLOCK_Q", 128) == 256
    for bad in ("0", "-128", "abc", ""):
        monkeypatch.setenv("HVD_FLASH_BLOCK_Q", bad)
        assert _env_block("HVD_FLASH_BLOCK_Q", 128) == 128

    # an explicit bad argument still fails loudly
    import pytest as _pytest

    from horovod_tpu.ops.pallas.flash_attention import _pick_block
    with _pytest.raises(ValueError, match="block size"):
        _pick_block(64, 0)
