"""Fused Pallas LayerNorm vs the XLA oracle (interpret mode on CPU;
the same kernels compile on TPU — see tests/test_chip_compile.py and
chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas import layer_norm, layer_norm_reference


def _data(shape, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    d = shape[-1]
    return (jnp.asarray(rng.randn(*shape).astype(dtype)),
            jnp.asarray(rng.randn(d).astype(np.float32)),
            jnp.asarray(rng.randn(d).astype(np.float32)))


@pytest.mark.parametrize("shape", [(4, 128), (2, 16, 256), (6, 32, 128)])
def test_forward_matches_oracle(shape):
    x, g, b = _data(shape)
    out = layer_norm(x, g, b, 1e-6, True)
    ref = layer_norm_reference(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_backward_matches_oracle():
    x, g, b = _data((8, 32, 128), seed=1)

    def loss_p(x, g, b):
        return jnp.mean(layer_norm(x, g, b, 1e-6, True) ** 2)

    def loss_r(x, g, b):
        return jnp.mean(layer_norm_reference(x, g, b) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, g, b)
    for a, c, nm in zip(gp, gr, "xgb"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-5,
            err_msg=f"d{nm} mismatch")


def test_backward_multi_grid_step_accumulation():
    """Row count forcing grid > 1 (n=24 -> block_n=8, 3 steps): the
    cross-step dgamma/dbeta accumulation (pl.when init + '+=') must
    produce the same parameter grads as the oracle."""
    x, g, b = _data((3, 8, 128), seed=5)

    def loss_p(x, g, b):
        return jnp.mean(layer_norm(x, g, b, 1e-6, True) ** 2)

    def loss_r(x, g, b):
        return jnp.mean(layer_norm_reference(x, g, b) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, g, b)
    for a, c, nm in zip(gp, gr, "xgb"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-5,
            err_msg=f"d{nm} mismatch")


def test_odd_row_count_pads_and_slices():
    # 7 rows: padded to 8 internally; fwd AND bwd must stay exact
    x, g, b = _data((7, 128), seed=2)
    out = layer_norm(x, g, b, 1e-6, True)
    ref = layer_norm_reference(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss_p(x, g, b):
        return jnp.mean(layer_norm(x, g, b, 1e-6, True) ** 2)

    def loss_r(x, g, b):
        return jnp.mean(layer_norm_reference(x, g, b) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, g, b)
    for a, c, nm in zip(gp, gr, "xgb"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-5,
            err_msg=f"d{nm} mismatch (padded rows)")


def test_bf16_activations_fp32_stats():
    x, g, b = _data((4, 64, 128), seed=3)
    xb = x.astype(jnp.bfloat16)
    out = layer_norm(xb, g, b, 1e-6, True)
    assert out.dtype == jnp.bfloat16
    ref = layer_norm_reference(xb, g, b)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32), rtol=2e-2, atol=2e-2)


def test_transformer_blocks_use_fused_layer_norm():
    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=32,
                            n_heads=2, d_ff=64, max_len=16,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # parameter tree keeps nn.LayerNorm-compatible names
    assert "scale" in params["block_0"]["ln1"]
    assert "bias" in params["block_0"]["ln2"]
    assert "scale" in params["ln_f"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (1, 8, 64)
    assert np.all(np.isfinite(np.asarray(logits)))

def test_block_n_budgeted_by_feature_dim():
    """ADVICE r2: block_n must shrink with d so the kernel's fp32 slabs
    stay under VMEM (softmax_xent's budget rule); d=8192 previously
    picked block_n=256 -> 8192*256*4*3 = 24 MB > 16 MB VMEM."""
    from horovod_tpu.ops.pallas.layer_norm import _pick_block_n
    assert _pick_block_n(1024, 128, slabs=2) == 256   # small d: unchanged
    assert _pick_block_n(1024, 8192, slabs=3) * 8192 * 4 * 3 <= 4 << 20
    assert _pick_block_n(1024, 8192, slabs=3) >= 8
    # numerics still hold at large d with the smaller block
    x, g, b = _data((16, 8192), seed=3)
    out = layer_norm(x, g, b, 1e-6, True)
    ref = layer_norm_reference(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_large_d_backward_budgeted_blocks():
    """d=8192 BACKWARD through the VMEM-budgeted block pick (3 slabs);
    round-3's budget fix covered the forward — lock the bwd too."""
    x, g, b = _data((16, 8192), seed=5)
    gp = jax.grad(lambda x, g, b: jnp.mean(
        layer_norm(x, g, b, 1e-6, True) ** 2), argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda x, g, b: jnp.mean(
        layer_norm_reference(x, g, b) ** 2), argnums=(0, 1, 2))(x, g, b)
    for a, c, nm in zip(gp, gr, "xgb"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-6,
                                   err_msg=f"d{nm} mismatch at d=8192")
