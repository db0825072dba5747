"""``models/transformer.py``'s block described by data: the default
``BlockSpec`` is the GPT-2 block name for name; RoPE, QK-norm, RMSNorm
and the top-k expert layer are what OLMoE's spec adds; and
``apply_with_aux`` returns the layers' auxiliary terms and counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, LatentAttention, NextTokenModule,
                                TopkExperts, Transformer, TransformerConfig,
                                apply_with_aux, lm_loss)
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
    ("norm", "batch"), ("positions", "alibi"), ("ffn", "relu"),
    ("attention", "linear")])
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


# ------------- latent attention, layer kinds, held and shared experts
LATENT = LatentAttention(q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4,
                         v_dim=6)
EXPERTS = TopkExperts(scoring="sigmoid", renormalize=True, scale=2.5,
                      shared=1, held=(4, 4))
SPARSE = dict(**SIZES, n_experts=16, experts_per_token=4, d_expert=12,
              leading_dense=1, rope_theta=32e6,
              block=BlockSpec(norm="rms", positions="rope_pairs",
                              attention=LATENT, ffn=EXPERTS))


def test_latent_dense_and_expert_layers_tree():
    """Written out: five projections and two inner norms of latent
    attention (heads of 8 + 4 for the scores, 6 for the values); block
    0 a dense SwiGLU of width ``d_ff``, block 1 a router over all 16
    outputs, the 4 experts held and a shared one of the experts' width."""
    attn = {"attn/q_a/kernel": (32, 24), "attn/q_a_norm/scale": (24,),
            "attn/q_b/kernel": (24, 4, 12), "attn/kv_a/kernel": (32, 20),
            "attn/kv_a_norm/scale": (16,), "attn/kv_b/kernel": (16, 4, 14),
            "attn/out/kernel": (24, 32), "ln1/scale": (32,),
            "ln2/scale": (32,)}
    dense = {"mlp/gate/kernel": (32, 64), "mlp/up/kernel": (32, 64),
             "mlp/down/kernel": (64, 32)}
    sparse = {"moe/router_kernel": (32, 16), "moe/wg_kernel": (4, 32, 12),
              "moe/wi_kernel": (4, 32, 12), "moe/wo_kernel": (4, 12, 32),
              "moe/shared/gate/kernel": (32, 12),
              "moe/shared/up/kernel": (32, 12),
              "moe/shared/down/kernel": (12, 32)}
    want = {"embed/embedding": (50, 32), "ln_f/scale": (32,),
            "lm_head/kernel": (32, 50)}
    want.update({f"block_0/{k}": v for k, v in {**attn, **dense}.items()})
    want.update({f"block_1/{k}": v for k, v in {**attn, **sparse}.items()})
    cfg = TransformerConfig(**SPARSE)
    assert [cfg.ffn_of(i) for i in range(2)] == ["swiglu", EXPERTS]
    assert shapes(cfg) == want


def test_plain_topk_spec_is_the_default_topk_experts():
    """``"moe_topk"`` and ``TopkExperts()`` are one layer."""
    sizes = dict(**SIZES, n_experts=8, experts_per_token=2, d_expert=24)
    named = TransformerConfig(**sizes, block=OLMOE)
    spelled = TransformerConfig(**sizes, block=BlockSpec(
        norm="rms", positions="rope", qk_norm=True, ffn=TopkExperts()))
    assert shapes(named) == shapes(spelled)
    params = Transformer(named).init(jax.random.PRNGKey(0), TOKENS)["params"]
    np.testing.assert_array_equal(
        Transformer(named).apply({"params": params}, TOKENS),
        Transformer(spelled).apply({"params": params}, TOKENS))


def pair_rotation(x, theta):
    """The neighbours ``(x[2i], x[2i + 1])`` as one complex number
    turned by ``t * theta^(-2i / D)``."""
    t, d = x.shape[-3], x.shape[-1]
    x = np.asarray(x, np.float64)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    z = z * np.exp(1j * np.arange(t)[:, None, None]
                   * theta ** (-2 * np.arange(d // 2) / d))
    return np.stack([z.real, z.imag], -1).reshape(x.shape)


def test_rope_pairs_is_the_complex_rotation_of_neighbours():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 3, 16))
    np.testing.assert_allclose(rope(x, 32e6, pairs=True),
                               pair_rotation(x, 32e6), rtol=1e-5, atol=1e-5)
    # the two pairings give the same scores once q and k are both
    # de-interleaved: what Hugging Face's ``rope_interleave`` does
    q, k = x[:1], x[1:]
    flat = lambda u: jnp.concatenate([u[..., 0::2], u[..., 1::2]], -1)
    np.testing.assert_allclose(
        jnp.einsum("bqhd,bkhd->bhqk", rope(q, 500.0, pairs=True),
                   rope(k, 500.0, pairs=True)),
        jnp.einsum("bqhd,bkhd->bhqk", rope(flat(q), 500.0),
                   rope(flat(k), 500.0)), rtol=1e-4, atol=1e-5)


def test_latent_attention_hands_the_kernel_two_widths_and_one_shared_key():
    seen = {}

    def attend(q, k, v, causal):
        seen.update(q=q, k=k, v=v)
        return v

    cfg = TransformerConfig(**{**SPARSE, "attn_fn": attend})
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 32))
    attn = Attention(cfg)
    params = attn.init(jax.random.PRNGKey(8), x)["params"]
    out = attn.apply({"params": params}, x)
    assert out.shape == x.shape
    assert seen["q"].shape == seen["k"].shape == (2, 16, 4, 12)
    assert seen["v"].shape == (2, 16, 4, 6)
    # the rotated key is one for all heads, and is the pair rotation of
    # the last rope_dim columns of the kv_a projection
    k_rope = seen["k"][..., 8:]
    np.testing.assert_array_equal(k_rope, jnp.broadcast_to(
        k_rope[:, :, :1], k_rope.shape))
    down = x @ params["kv_a"]["kernel"]
    np.testing.assert_allclose(
        k_rope[:, :, 0], pair_rotation(down[:, :, None, 16:], 32e6)[:, :, 0],
        rtol=1e-4, atol=1e-5)
    # q's first nope_dim columns are not turned, the rest are
    c_q = RMSNorm().apply({"params": params["q_a_norm"]},
                          x @ params["q_a"]["kernel"])
    q = jnp.einsum("btr,rhk->bthk", c_q, params["q_b"]["kernel"])
    np.testing.assert_allclose(seen["q"][..., :8], q[..., :8], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(seen["q"][..., 8:],
                               pair_rotation(q[..., 8:], 32e6), rtol=1e-4,
                               atol=1e-5)


def model_and_module(**changes):
    cfg = TransformerConfig(**{**SPARSE, "n_layers": 3, **changes})
    model, module = Transformer(cfg), NextTokenModule(cfg)
    params = model.init(jax.random.PRNGKey(9), TOKENS)["params"]
    params["next_token"] = module.init(
        jax.random.PRNGKey(10), jnp.zeros((2, 16, 32)), TOKENS,
        params["embed"]["embedding"], params["lm_head"]["kernel"])["params"]
    return model, module, params


def test_the_counter_reaches_apply_with_aux_for_every_expert_layer():
    """Two expert layers and the module's, in that order, each over
    all 16 outputs; a bias row a layer decides the choice there and
    nowhere else."""
    model, module, params = model_and_module()
    bias = jnp.zeros((3, 16))
    logits, aux = apply_with_aux(model, params, TOKENS, router_bias=bias,
                                 next_token=module)
    assert logits.shape == aux["next_token_logits"].shape == (2, 16, 50)
    assert aux["moe_layers"] == 3
    assert aux["tokens_per_expert"].shape == (3, 16)
    np.testing.assert_array_equal(aux["tokens_per_expert"].sum(-1),
                                  [2 * 16 * 4] * 3)
    np.testing.assert_allclose(
        logits, model.apply({"params": params}, TOKENS), rtol=1e-6)
    for row in range(3):
        pushed = apply_with_aux(
            model, params, TOKENS, next_token=module,
            router_bias=bias.at[row, 7].set(10.0))[1]["tokens_per_expert"]
        assert int(pushed[row, 7]) == 2 * 16
        if row == 0:  # later layers see another input; the first does not
            np.testing.assert_array_equal(
                np.delete(pushed[0], 7).sum(), 2 * 16 * 3)
        else:
            np.testing.assert_array_equal(pushed[:row],
                                          aux["tokens_per_expert"][:row])


def test_the_module_predicts_the_token_after_next_from_shared_weights():
    """``h' = W_eh [norm(Emb(t_{i+1})) ; norm(z_i)]`` through one block
    and the model's own head; the embedding and the head get gradients
    from both losses."""
    model, module, params = model_and_module()

    def losses(p):
        logits, aux = apply_with_aux(model, p, TOKENS, next_token=module)
        return (lm_loss(logits, TOKENS),
                lm_loss(aux["next_token_logits"], jnp.roll(TOKENS, -1, -1)))

    main, second = (jax.grad(lambda p, i=i: losses(p)[i])(params)
                    for i in range(2))
    assert not np.any(np.asarray(main["next_token"]["eh_proj"]["kernel"]))
    for grads in (main, second):
        for leaf in (grads["embed"]["embedding"],
                     grads["lm_head"]["kernel"],
                     grads["block_0"]["mlp"]["gate"]["kernel"]):
            assert np.any(np.asarray(leaf))
    # by hand, from the model's hidden state
    _, hidden = model.apply({"params": params}, TOKENS, return_hidden=True)
    m = params["next_token"]
    norm = lambda u, w: RMSNorm().apply({"params": w}, u)
    following = params["embed"]["embedding"][jnp.roll(TOKENS, -1, -1)]
    x = jnp.concatenate([norm(following, m["enorm"]),
                         norm(hidden, m["hnorm"])], -1) @ m["eh_proj"][
                             "kernel"]
    from horovod_tpu.models.transformer import Block

    x, _ = Block(module.cfg).apply({"params": m["block"]}, x)
    want = norm(x, m["ln_f"]) @ params["lm_head"]["kernel"]
    got = apply_with_aux(model, params, TOKENS,
                         next_token=module)[1]["next_token_logits"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sparse_spec_trains_in_bfloat16_under_remat():
    model, module, params = model_and_module(dtype=jnp.bfloat16, remat=True)

    def loss(p):
        logits, aux = apply_with_aux(
            model, p, TOKENS, router_bias=jnp.zeros((3, 16)),
            next_token=module)
        return lm_loss(logits, TOKENS) + 0.3 * lm_loss(
            aux["next_token_logits"], jnp.roll(TOKENS, -1, -1))

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(value))
    assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
               for g in jax.tree.leaves(grads))
