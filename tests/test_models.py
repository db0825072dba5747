"""Benchmark-model family shape/dtype checks (reference measurement
vehicles: ResNet-50/101, VGG-16, Inception V3 — ``docs/benchmarks.rst``).
Forward passes on tiny inputs; the bench drives the full-size versions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import InceptionV3, ResNet50, ResNet101, VGG16


def _forward(model, x, train=False):
    """One program for ``init`` and one for ``apply``: run eagerly, every
    layer's every operation is a compile of its own, which is where
    these tests' time went (Inception V3: 23 s against 12)."""
    variables = jax.jit(lambda key: model.init(key, x, train=train))(
        jax.random.PRNGKey(0))
    return jax.jit(lambda v: model.apply(v, x, train=train))(variables)


@pytest.mark.parametrize("cls", [ResNet50, ResNet101])
def test_resnet_forward(cls):
    model = cls(num_classes=10, dtype=jnp.float32)
    out = _forward(model, jnp.ones((2, 64, 64, 3)))
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    assert np.isfinite(np.asarray(out)).all()


def test_vgg16_forward():
    model = VGG16(num_classes=10, dtype=jnp.float32, classifier_width=64)
    out = _forward(model, jnp.ones((2, 64, 64, 3)))
    assert out.shape == (2, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_inception_v3_forward():
    model = InceptionV3(num_classes=10, dtype=jnp.float32)
    # 299x299 is the canonical input; 75 is the least that every
    # reduction stage accepts
    out = _forward(model, jnp.ones((1, 75, 75, 3)))
    assert out.shape == (1, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_models_bf16_params_stay_fp32():
    model = ResNet50(num_classes=10)  # default dtype bfloat16
    variables = jax.jit(lambda key: model.init(
        key, jnp.ones((1, 64, 64, 3)), train=False))(jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(variables["params"])
    assert all(leaf.dtype == jnp.float32 for leaf in leaves), \
        "params must remain fp32 (bf16 is compute dtype only)"


def test_transformer_remat_matches_dense():
    """cfg.remat=True must be numerically identical (same graph, just
    rematerialized in backward) and differentiable."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import Transformer, TransformerConfig, lm_loss

    base = dict(vocab_size=128, n_layers=2, d_model=64, n_heads=2,
                d_ff=128, max_len=32, dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 32)))
    m0 = Transformer(TransformerConfig(**base))
    m1 = Transformer(TransformerConfig(**base, remat=True))
    params = m0.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss(m):
        def f(p):
            return lm_loss(m.apply({"params": p}, tokens), tokens)
        return f

    l0, g0 = jax.value_and_grad(loss(m0))(params)
    l1, g1 = jax.value_and_grad(loss(m1))(params)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
