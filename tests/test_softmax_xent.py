"""Fused Pallas softmax cross-entropy vs the XLA oracle (interpret mode
on CPU; compiled Pallas on TPU — see tests/test_chip_compile.py and
chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops.pallas import softmax_xent, softmax_xent_reference


def _data(shape, v, seed=0, scale=3.0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(*shape, v).astype(np.float32) * scale)
    labels = jnp.asarray(rng.randint(0, v, shape))
    return logits, labels


@pytest.mark.parametrize("shape,v", [((4, 16), 512), ((3, 7), 1000),
                                     ((24,), 4096)])
def test_forward_matches_oracle_and_optax(shape, v):
    logits, labels = _data(shape, v)
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    ox = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ox),
                               rtol=1e-5, atol=1e-5)


def test_backward_matches_oracle_multi_grid():
    # n=24 rows -> block 8, grid 3: exercises cross-step independence
    logits, labels = _data((3, 8), 1024, seed=1)

    gp = jax.grad(lambda x: jnp.mean(softmax_xent(x, labels, True)))(logits)
    gr = jax.grad(
        lambda x: jnp.mean(softmax_xent_reference(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_extreme_logits_stable():
    """Online logsumexp must not overflow for large-magnitude logits."""
    logits, labels = _data((16,), 512, seed=2, scale=200.0)
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_bf16_logits_fp32_loss():
    logits, labels = _data((4, 8), 512, seed=3)
    lb = logits.astype(jnp.bfloat16)
    out = softmax_xent(lb, labels, True)
    assert out.dtype == jnp.float32
    ref = softmax_xent_reference(lb, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
    g = jax.grad(lambda x: jnp.mean(softmax_xent(x, labels, True)))(lb)
    assert g.dtype == jnp.bfloat16


def test_odd_row_count_pads_and_slices():
    logits, labels = _data((5,), 768, seed=4)  # 5 rows -> pad to 8
    out = softmax_xent(logits, labels, True)
    ref = softmax_xent_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    gp = jax.grad(lambda x: jnp.sum(softmax_xent(x, labels, True)))(logits)
    gr = jax.grad(
        lambda x: jnp.sum(softmax_xent_reference(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_large_vocab_fwd_bwd():
    """vocab=32768 (production LM scale) through the VMEM-chunked
    streaming path, forward + backward vs optax."""
    logits, labels = _data((16,), 32768, seed=9, scale=1.0)

    got = softmax_xent(logits, labels, True)
    ref = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda l: jnp.mean(softmax_xent(l, labels, True)))(
        logits)
    gr = jax.grad(lambda l: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(l, labels)))(
        logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-7)
