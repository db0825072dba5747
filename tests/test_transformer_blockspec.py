"""``models/transformer.py``'s block described by data: the default
``BlockSpec`` is the GPT-2 block name for name; RoPE, QK-norm, RMSNorm
and the top-k expert layer are what OLMoE's spec adds; and
``apply_with_aux`` returns the layers' auxiliary terms and counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, Transformer, TransformerConfig,
                                apply_with_aux)
from horovod_tpu.models.transformer import Attention, RMSNorm, rope

OLMOE = BlockSpec(norm="rms", positions="rope", qk_norm=True,
                  ffn="moe_topk")
SIZES = dict(vocab_size=50, n_layers=2, d_model=32, n_heads=4, d_ff=64,
             max_len=16, dtype=jnp.float32)
TOKENS = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 16)))


def shapes(cfg):
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    return {"/".join(k.key for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


def test_default_spec_builds_the_gpt2_tree_name_for_name():
    """What ``gpt2_medium``'s cells, checkpoints and the sharding rules
    read: written out here, not computed from the program."""
    block = {"ln1/scale": (32,), "ln1/bias": (32,),
             "attn/qkv/kernel": (32, 3, 4, 8), "attn/out/kernel": (32, 32),
             "ln2/scale": (32,), "ln2/bias": (32,),
             "mlp/up/kernel": (32, 64), "mlp/down/kernel": (64, 32)}
    want = {"embed/embedding": (50, 32), "pos_embed/embedding": (16, 32),
            "ln_f/scale": (32,), "ln_f/bias": (32,),
            "lm_head/kernel": (32, 50)}
    for i in range(2):
        want.update({f"block_{i}/{k}": v for k, v in block.items()})
    assert TransformerConfig().block == BlockSpec() == BlockSpec(
        "layer", "learned", False, "gelu")
    assert shapes(TransformerConfig(**SIZES)) == want


def test_olmoe_spec_tree():
    cfg = TransformerConfig(**SIZES, block=OLMOE, n_experts=8,
                            experts_per_token=2, d_expert=24)
    block = {"ln1/scale": (32,), "attn/qkv/kernel": (32, 3, 4, 8),
             "attn/q_norm/scale": (32,), "attn/k_norm/scale": (32,),
             "attn/out/kernel": (32, 32), "ln2/scale": (32,),
             "moe/router_kernel": (32, 8), "moe/wg_kernel": (8, 32, 24),
             "moe/wi_kernel": (8, 32, 24), "moe/wo_kernel": (8, 24, 32)}
    # no position table, no bias on any norm, a head of its own
    want = {"embed/embedding": (50, 32), "ln_f/scale": (32,),
            "lm_head/kernel": (32, 50)}
    for i in range(2):
        want.update({f"block_{i}/{k}": v for k, v in block.items()})
    assert shapes(cfg) == want


def test_moe_every_still_makes_every_kth_block_a_switch_layer():
    got = shapes(TransformerConfig(**SIZES, moe_every=2, n_experts=4))
    assert "block_0/mlp/up/kernel" in got and "block_1/moe/wi_kernel" in got
    assert "block_1/moe/wg_kernel" not in got


@pytest.mark.parametrize("field,value", [
    ("norm", "batch"), ("positions", "alibi"), ("ffn", "swiglu")])
def test_an_unknown_part_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=value):
        BlockSpec(**{field: value})


def test_sharding_rules_read_the_gate_like_the_other_expert_weights():
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.tensor_parallel import (
        transformer_sharding_rules)
    import re

    def spec(path):
        return next(s for pattern, s in transformer_sharding_rules()
                    if re.search(pattern, path))

    assert spec("block_0/moe/wg_kernel") == spec(
        "block_0/moe/wi_kernel") == P("ep", None, "tp")
    assert spec("block_0/moe/wo_kernel") == P("ep", "tp", None)
    assert spec("block_0/attn/q_norm/scale") == P()


# ------------------------------------------------------------------ RoPE
def complex_rotation(x, theta):
    """The same rotation written with complex numbers: the pair
    ``(x[i], x[i + D / 2])`` is one complex number turned by
    ``t * theta^(-2i / D)``."""
    t, d = x.shape[-3], x.shape[-1]
    x = np.asarray(x, np.float64)
    z = x[..., :d // 2] + 1j * x[..., d // 2:]
    angle = (np.arange(t)[:, None, None]
             * theta ** (-2 * np.arange(d // 2) / d))
    z = z * np.exp(1j * angle)
    return np.concatenate([z.real, z.imag], -1)


@pytest.mark.parametrize("theta", [10000.0, 500.0])
def test_rope_is_the_complex_rotation(theta):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 3, 16))
    np.testing.assert_allclose(rope(x, theta), complex_rotation(x, theta),
                               rtol=1e-5, atol=1e-5)
    # position 0 is not turned, and a rotation keeps the length
    np.testing.assert_allclose(rope(x, theta)[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(rope(x, theta), axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_rope_scores_depend_on_the_distance_only():
    """``<rope(q, m), rope(k, n)>`` is a function of ``m - n``: the same
    vector at every position gives a Toeplitz score matrix."""
    kq, kk = jax.random.split(jax.random.PRNGKey(2))
    t, d = 10, 16
    q = jnp.broadcast_to(jax.random.normal(kq, (d,)), (t, 1, d))
    k = jnp.broadcast_to(jax.random.normal(kk, (d,)), (t, 1, d))
    scores = np.asarray(jnp.einsum("qhd,khd->qk", rope(q), rope(k)))
    for offset in range(-t + 1, t):
        diagonal = np.diagonal(scores, offset)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=1e-4,
                                   atol=1e-5)
    assert abs(scores[0, 1] - scores[1, 0]) > 1e-3  # and on its sign


def test_rope_keeps_bfloat16_and_computes_in_float32():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 2, 8), jnp.bfloat16)
    got = rope(x)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        complex_rotation(x.astype(jnp.float32), 10000.0), atol=0.02)


# --------------------------------------------------------------- norms
def test_rms_norm_formula():
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 8)) * 3 + 1
    scale = jnp.arange(1.0, 9.0)
    got = RMSNorm(eps=1e-5).apply({"params": {"scale": scale}}, x)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) \
        * scale
    np.testing.assert_allclose(got, want, rtol=1e-5)
    half = RMSNorm(eps=1e-5).apply({"params": {"scale": scale}},
                                   x.astype(jnp.bfloat16))
    assert half.dtype == jnp.bfloat16


def test_qk_norm_is_over_the_whole_projection_not_per_head():
    """Identity projections, one head much larger than the others: a
    norm over all 32 columns leaves the heads' relative sizes alone; a
    norm per head would make them equal."""
    cfg = TransformerConfig(**SIZES, block=BlockSpec(
        norm="rms", positions="learned", qk_norm=True),
        attn_fn=lambda q, k, v, causal: q)  # hands q through
    h, d = 4, 8
    eye = jnp.eye(32).reshape(32, 1, h, d)
    params = {"qkv": {"kernel": jnp.concatenate([eye] * 3, 1)},
              "out": {"kernel": jnp.eye(32)},
              "q_norm": {"scale": jnp.ones(32)},
              "k_norm": {"scale": jnp.ones(32)}}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 32))
    x = x.at[..., :d].multiply(10.0)  # head 0
    got = Attention(cfg).apply({"params": params}, x)
    want = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    per_head = jnp.linalg.norm(got.reshape(1, 6, h, d), axis=-1)
    assert np.all(np.asarray(per_head[..., 0] > 3 * per_head[..., 1]))


# ------------------------------------------------------ apply_with_aux
def test_apply_with_aux_sums_the_layers_terms_and_stacks_the_counters():
    cfg = TransformerConfig(**SIZES, block=OLMOE, n_experts=8,
                            experts_per_token=2, d_expert=24)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(1), TOKENS)["params"]
    logits, aux = apply_with_aux(model, params, TOKENS)
    assert logits.shape == (2, 16, 50)
    np.testing.assert_allclose(
        logits, model.apply({"params": params}, TOKENS), rtol=1e-6)
    assert aux["moe_layers"] == 2
    assert aux["tokens_per_expert"].shape == (2, 8)
    np.testing.assert_array_equal(aux["tokens_per_expert"].sum(-1),
                                  [2 * 16 * 2] * 2)
    # two layers' terms summed: each load-balancing term is >= k = 2 at
    # its best, each z term is logsumexp^2 > 0
    assert float(aux["load_balancing"]) >= 4.0 - 1e-4
    assert float(aux["router_z"]) > 0
    # the router gets a gradient from each term
    for term in ("load_balancing", "router_z"):
        grads = jax.grad(lambda p: apply_with_aux(model, p, TOKENS)[1][term])(
            params)
        for i in range(2):
            assert float(jnp.abs(
                grads[f"block_{i}"]["moe"]["router_kernel"]).max()) > 0


def test_apply_with_aux_on_switch_layers_and_on_a_dense_model():
    switch = Transformer(TransformerConfig(**SIZES, moe_every=2,
                                           n_experts=4))
    params = switch.init(jax.random.PRNGKey(2), TOKENS)["params"]
    aux = apply_with_aux(switch, params, TOKENS)[1]
    assert aux["moe_layers"] == 1 and aux["tokens_per_expert"] is None
    assert float(aux["load_balancing"]) >= 1.0 - 1e-4
    assert float(aux["router_z"]) == 0.0
    dense = Transformer(TransformerConfig(**SIZES))
    params = dense.init(jax.random.PRNGKey(3), TOKENS)["params"]
    aux = apply_with_aux(dense, params, TOKENS)[1]
    assert aux["moe_layers"] == 0 and float(aux["load_balancing"]) == 0.0


def test_olmoe_spec_trains_in_bfloat16_under_remat():
    cfg = TransformerConfig(**{**SIZES, "dtype": jnp.bfloat16}, block=OLMOE,
                            n_experts=8, experts_per_token=2, d_expert=24,
                            remat=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(4), TOKENS)["params"]

    def loss(p):
        from horovod_tpu.models import lm_loss

        logits, aux = apply_with_aux(model, p, TOKENS)
        return (lm_loss(logits, TOKENS) + 0.01 * aux["load_balancing"]
                + 0.001 * aux["router_z"])

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(value))
    assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
               for g in jax.tree.leaves(grads))
