"""``models/transformer.py``'s weight-shared passes over the stack: a
sandwich norm as a kind on ``BlockSpec``, ``TransformerConfig.passes``
with the blocks' parameters made once and the final norm closing each
pass, the exit gate, every pass's exit through the one head, and
``looped_lm_loss`` over the exit distribution.  With one pass, no
sandwich norm and no gate the model is the one from before them, bit
for bit."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from horovod_tpu.models import (BlockSpec, GroupedAttention,
                                LatentAttention, TopkExperts,
                                Transformer, TransformerConfig,
                                apply_with_aux, lm_loss, looped_lm_loss,
                                transformer)
from horovod_tpu.models.transformer import (Block, keeping, kept_names,
                                            make_norm)
from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.parallel import reference_attention

SANDWICH = BlockSpec(norm="rms", positions="rope", ffn="swiglu",
                     norm_placement="sandwich")
SIZES = dict(vocab_size=50, n_layers=2, d_model=32, n_heads=4, d_ff=48,
             max_len=16, dtype=jnp.float32, rope_theta=1e6)
TOKENS = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 16)))
BETA = 0.1


def looped(passes, **changes):
    return TransformerConfig(**{**SIZES, **changes}, block=SANDWICH,
                             passes=passes, exit_gate=True)


def seeded(cfg, seed=0):
    """Parameters off the symmetric start (norm scales of 1, a gate
    bias of 0)."""
    params = Transformer(cfg).init(jax.random.PRNGKey(seed),
                                   TOKENS)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return tree.unflatten([leaf + 0.1 * jax.random.normal(k, leaf.shape)
                           for leaf, k in zip(leaves, keys)])


def loss_of(cfg, params, tokens=TOKENS):
    logits, aux = apply_with_aux(Transformer(cfg), params, tokens)
    return looped_lm_loss(logits, aux["exit_gate_logits"], tokens, BETA)


def names(params):
    return {"/".join(k.key for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_sandwich_gated_tree_is_the_same_whatever_the_passes(passes):
    """Four norms a block, one gate with a bias, one head; the blocks'
    parameters are made once."""
    block = {"ln1/scale": (32,), "ln1_post/scale": (32,),
             "attn/qkv/kernel": (32, 3, 4, 8), "attn/out/kernel": (32, 32),
             "ln2/scale": (32,), "ln2_post/scale": (32,),
             "mlp/gate/kernel": (32, 48), "mlp/up/kernel": (32, 48),
             "mlp/down/kernel": (48, 32)}
    want = {"embed/embedding": (50, 32), "ln_f/scale": (32,),
            "exit_gate/kernel": (32, 1), "exit_gate/bias": (1,),
            "lm_head/kernel": (32, 50)}
    for i in range(2):
        want.update({f"block_{i}/{k}": v for k, v in block.items()})
    cfg = looped(passes)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert names(params) == want
    one = Transformer(looped(1)).init(jax.random.PRNGKey(0),
                                      TOKENS)["params"]
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(one)))


class TransformerBefore(nn.Module):
    """``Transformer.__call__`` as it stood before the passes, the
    sandwich norm and the gate, written out: what the cells that have
    none of them ran (a recomputed block saves what the model's does:
    ``keeping`` what ``kept_names`` lists)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, router_bias=None, return_hidden=False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="embed")(tokens)
        if cfg.block.positions == "learned":
            x = x + nn.Embed(
                cfg.max_len, cfg.d_model, dtype=cfg.dtype,
                name="pos_embed")(jnp.arange(tokens.shape[-1]))
        block_cls = (keeping(Block, kept_names(cfg)) if cfg.remat
                     else Block)
        rows = 0
        for i in range(cfg.n_layers):
            ffn = cfg.ffn_of(i)
            bias = None
            if router_bias is not None and isinstance(ffn, TopkExperts):
                bias, rows = router_bias[rows], rows + 1
            x, _ = block_cls(cfg, ffn=ffn, name=f"block_{i}")(x, bias)
        hidden = x
        x = make_norm(cfg, "ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          name="lm_head")(x.astype(cfg.dtype))
        return (logits, hidden) if return_hidden else logits


BEFORE = {
    "gpt2": dict(),
    "olmoe": dict(block=BlockSpec(norm="rms", positions="rope",
                                  qk_norm=True, ffn="moe_topk"),
                  n_experts=8, experts_per_token=2, d_expert=24),
    "latent_sparse_remat": dict(
        block=BlockSpec(
            norm="rms", positions="rope_pairs",
            attention=LatentAttention(q_rank=24, kv_rank=16, nope_dim=8,
                                      rope_dim=4, v_dim=8),
            ffn=TopkExperts(scoring="sigmoid", renormalize=True, scale=2.5,
                            shared=1, held=(0, 4))),
        leading_dense=1, n_experts=8, experts_per_token=2, d_expert=24,
        remat=True, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("kind", sorted(BEFORE))
def test_one_pass_no_sandwich_no_gate_is_the_model_from_before(kind):
    """Parameters tree, logits, hidden state and every gradient, bit
    for bit."""
    cfg = TransformerConfig(**{**SIZES, **BEFORE[kind]})
    assert cfg.passes == 1 and not cfg.exit_gate
    assert cfg.block.norm_placement == "pre"
    now, before = Transformer(cfg), TransformerBefore(cfg)
    params = now.init(jax.random.PRNGKey(2), TOKENS)["params"]
    want = before.init(jax.random.PRNGKey(2), TOKENS)["params"]
    assert names(params) == names(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)

    def run(model):
        def loss(p):
            logits, hidden = model.apply({"params": p}, TOKENS,
                                         return_hidden=True)
            return lm_loss(logits, TOKENS), (logits, hidden)
        return jax.value_and_grad(loss, has_aux=True)(params)

    got, want = run(now), run(before)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def untied_loss(cfg, copies, tokens=TOKENS):
    """The looped model's equations with a parameter tree a pass
    (``copies[r]``: blocks and final norm of pass r; embedding, gate and
    head from ``copies[0]``), in a Python loop over plain modules."""
    one = TransformerConfig(**{**SIZES, "n_layers": cfg.n_layers},
                            block=cfg.block)
    x = copies[0]["embed"]["embedding"][tokens]
    exits = []
    for w in copies:
        for i in range(cfg.n_layers):
            x, _ = Block(one).apply({"params": w[f"block_{i}"]}, x)
        x = make_norm(one, None).apply({"params": w["ln_f"]}, x)
        exits.append(x)
    exits = jnp.stack(exits)
    gate = copies[0]["exit_gate"]
    gate_logits = (exits @ gate["kernel"])[..., 0] + gate["bias"][0]
    return looped_lm_loss(exits @ copies[0]["lm_head"]["kernel"],
                          gate_logits, tokens, BETA)[0]


@pytest.mark.parametrize("remat", [False, True], ids=["saved", "remat"])
@pytest.mark.parametrize("passes", [2, 4])
def test_passes_are_one_pass_over_tied_copies_with_the_gradients_summed(
        passes, remat):
    """R passes of N blocks equal R N blocks (each pass closed by the
    final norm) whose parameters are tied copies: the same loss, and a
    weight's gradient is the sum over its R copies'."""
    cfg = looped(passes, remat=remat)
    params = seeded(cfg)
    got, got_grads = jax.value_and_grad(
        lambda p: loss_of(cfg, p)[0])(params)
    copies = [params] * passes
    want, copy_grads = jax.value_and_grad(
        lambda c: untied_loss(cfg, c))(copies)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *copy_grads)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(summed)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=1e-6 * float(jnp.max(jnp.abs(w)) + 1),
            err_msg=str(path))
    # a copy alone is NOT the whole gradient: every pass adds its part
    block = [c["block_0"]["mlp"]["up"]["kernel"] for c in copy_grads]
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in block)


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_looped_loss_is_the_expectation_less_beta_times_the_entropy(passes):
    """Against the definition in numpy, float64: the products of the
    gate's probabilities, the last exit taking what is left."""
    rng = np.random.RandomState(passes)
    logits = rng.normal(size=(passes, 2, 16, 50)).astype(np.float32)
    gate = (2 * rng.normal(size=(passes, 2, 16))).astype(np.float32)
    got, aux = looped_lm_loss(jnp.asarray(logits), jnp.asarray(gate),
                              TOKENS, BETA)
    labels = np.roll(np.asarray(TOKENS), -1, axis=-1)
    x = logits.astype(np.float64)
    logp = x - np.log(np.sum(np.exp(x), -1, keepdims=True))
    losses = -np.take_along_axis(
        logp, np.broadcast_to(labels, gate.shape)[..., None], -1)[..., 0]
    leave = 1 / (1 + np.exp(-gate.astype(np.float64)))
    stayed, p = np.ones_like(leave[0]), []
    for r in range(passes - 1):
        p.append(leave[r] * stayed)
        stayed = stayed * (1 - leave[r])
    p = np.stack(p + [stayed])
    entropy = -np.sum(p * np.log(p), 0)
    want = np.mean(np.sum(p * losses, 0) - BETA * entropy)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the exit distribution sums to 1 at every token, and the counters
    np.testing.assert_allclose(np.sum(p, 0), 1.0, rtol=1e-12)
    assert set(aux) == {"exit_probability", "exit_losses", "exit_entropy"}
    assert aux["exit_probability"].shape == aux["exit_losses"].shape == (
        passes,)
    np.testing.assert_allclose(aux["exit_probability"], p.mean((1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(aux["exit_probability"]), 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(aux["exit_losses"], losses.mean((1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(aux["exit_entropy"], entropy.mean(),
                               rtol=1e-5, atol=1e-7)
    # the last pass's gate is not read
    grad = jax.grad(lambda g: looped_lm_loss(
        jnp.asarray(logits), g, TOKENS, BETA)[0])(jnp.asarray(gate))
    assert not np.any(np.asarray(grad[-1]))
    assert passes == 1 or np.all(np.asarray(grad[:-1]) != 0)


def test_a_saturated_gate_gives_a_finite_loss_and_gradient():
    """The probabilities go through their logarithms: a gate at +-80
    (sigmoid rounds to 0 or 1 in float32) gives 0 log 0 = 0."""
    gate = jnp.asarray([80.0, -80.0, 0.0])[:, None, None] * jnp.ones(
        (3, 2, 16))
    logits = jnp.zeros((3, 2, 16, 50))
    value, grad = jax.value_and_grad(lambda g: looped_lm_loss(
        logits, g, TOKENS, BETA)[0])(gate)
    assert np.isfinite(float(value)) and np.all(np.isfinite(grad))
    np.testing.assert_allclose(value, np.log(50), rtol=1e-6)


def test_the_model_returns_every_exit_and_the_gates_logits():
    cfg = looped(4)
    params = seeded(cfg)
    logits, aux = apply_with_aux(Transformer(cfg), params, TOKENS)
    assert logits.shape == (4, 2, 16, 50)
    assert aux["exit_gate_logits"].shape == (4, 2, 16)
    assert aux["exit_gate_logits"].dtype == jnp.float32
    # without the gate the same weights give the last exit alone, and
    # the hidden state is the last pass's before the final norm
    plain = TransformerConfig(**SIZES, block=SANDWICH, passes=4)
    rest = {k: v for k, v in params.items() if k != "exit_gate"}
    last, hidden = Transformer(plain).apply({"params": rest}, TOKENS,
                                            return_hidden=True)
    np.testing.assert_allclose(last, logits[-1], rtol=1e-6, atol=1e-6)
    normed = make_norm(plain, None).apply({"params": rest["ln_f"]}, hidden)
    np.testing.assert_allclose(normed @ rest["lm_head"]["kernel"], last,
                               rtol=1e-5, atol=1e-5)
    _, aux = apply_with_aux(Transformer(plain), rest, TOKENS)
    assert "exit_gate_logits" not in aux


def test_passes_over_expert_layers_are_refused_by_name():
    cfg = TransformerConfig(
        **SIZES, passes=2, n_experts=4, d_expert=24,
        block=BlockSpec(norm="rms", positions="rope", ffn="moe_topk"))
    with pytest.raises(ValueError, match="passes > 1 over expert layers"):
        Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)


def test_an_unknown_norm_placement_is_refused_by_name():
    with pytest.raises(ValueError, match="'post'"):
        BlockSpec(norm_placement="post")


def test_looped_spec_trains_in_bfloat16_under_remat():
    """A few AdamW steps of the looped model as the cell runs it; the
    compiled program holds ONE loop over the passes in the forward
    pass."""
    import optax

    cfg = looped(4, dtype=jnp.bfloat16, remat=True)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(params))
    opt = optax.adamw(1e-2)

    @jax.jit
    def step(params, state):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: loss_of(cfg, p), has_aux=True)(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss, aux

    state, losses = opt.init(params), []
    for _ in range(8):
        params, state, loss, aux = step(params, state)
        losses.append(float(loss))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    np.testing.assert_allclose(jnp.sum(aux["exit_probability"]), 1.0,
                               rtol=1e-5)
    forward = jax.jit(lambda p: loss_of(cfg, p)[0]).lower(params).as_text()
    assert forward.count("stablehlo.while") == 1


# ------------- what a recomputed block saves: the flash kernel's results
RECOMPUTED = {
    "plain": dict(n_layers=3),
    # score heads of 128 + 64 rotated = 192, value heads of 128
    "latent": dict(
        n_heads=2,
        block=BlockSpec(norm="rms", positions="rope_pairs", ffn="swiglu",
                        attention=LatentAttention(
                            q_rank=24, kv_rank=16, nope_dim=128,
                            rope_dim=64, v_dim=128))),
    "looped": dict(block=SANDWICH, passes=4, exit_gate=True),
    # 6 and 4 query heads over 2 key-value heads, with a window and without
    "grouped_window": dict(block=BlockSpec(
        norm="rms", positions="rope", ffn="swiglu",
        attention=GroupedAttention(heads=6, kv_heads=2, head_dim=8, window=4,
                                   gate="softplus"))),
    "grouped_full": dict(block=BlockSpec(
        norm="rms", positions="rope", ffn="swiglu",
        attention=GroupedAttention(heads=4, kv_heads=2, head_dim=8))),
}


def plain_remat(block, names):
    return nn.remat(block)


def recomputed_loss(kind, attn_fn=flash_attention):
    """``(cfg, params, loss)`` of a spec of ``RECOMPUTED`` with every
    block recomputed, its attention ``attn_fn``."""
    cfg = TransformerConfig(**{**SIZES, **RECOMPUTED[kind]}, remat=True,
                            attn_fn=attn_fn)
    params = seeded(cfg)

    def loss(p):
        if cfg.exit_gate:
            return loss_of(cfg, p)[0]
        return lm_loss(Transformer(cfg).apply({"params": p}, TOKENS), TOKENS)

    return cfg, params, loss


def kernel_calls(loss, params):
    """Call sites of the flash forward kernel's and of the backward
    kernel's ``jit`` in the lowered value-and-gradient."""
    text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
    return (len(re.findall(r"call @_fwd(_\d+)?\(", text)),
            len(re.findall(r"call @_bwd(_\d+)?\(", text)))


@pytest.mark.parametrize("kind", sorted(RECOMPUTED))
def test_a_recomputed_block_runs_each_flash_kernel_once(kind, monkeypatch):
    """N recomputed blocks (under the scan over the passes: N bodies)
    call the forward kernel N times and the backward kernel N times;
    under a plain ``nn.remat`` the forward kernel runs again in
    every recomputation, 2 N."""
    cfg, params, loss = recomputed_loss(kind)
    n = cfg.n_layers
    assert kernel_calls(loss, params) == (n, n)
    monkeypatch.setattr(transformer, "keeping", plain_remat)
    assert kernel_calls(loss, params) == (2 * n, n)


@pytest.mark.parametrize("kind", sorted(RECOMPUTED))
def test_saving_the_kernels_results_changes_no_bit(kind, monkeypatch):
    """What a block keeps is what the recomputation would have
    produced: the loss and every gradient leaf are bitwise those of a
    plain ``nn.remat``.  (Instruction by instruction, with no ``jit``
    around the gradient: compiled whole, a program that recomputes less
    is fused elsewhere and the CPU's compiler sums a product in another
    order, 1e-7 apart in float32.)"""
    _, params, loss = recomputed_loss(kind)
    got = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(transformer, "keeping", plain_remat)
    want = jax.value_and_grad(loss)(params)
    assert float(jnp.max(jnp.abs(got[1]["block_0"]["attn"]["out"]["kernel"]
                                 ))) > 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=str(path))


def saved(loss, params):
    """What the backward pass is handed that is no argument, as sorted
    ``(shape, dtype)``."""
    return sorted((aval.shape, str(aval.dtype)) for aval, why
                  in saved_residuals(loss, params)
                  if "from the argument" not in why)


@pytest.mark.parametrize("kind", sorted(RECOMPUTED))
def test_a_recomputed_block_saves_what_its_attention_names(kind,
                                                           monkeypatch):
    """Through the flash kernel a block saves ``out [B H, T, d_v]`` in
    the activation type and ``lse [B H, T]`` in float32, under the scan
    stacked over the passes and nothing else; with one pass also the
    sum after attention, q, k and v in the kernel's layout where none is
    wider than ``out`` (not at 192 over 128) and latent attention's two
    narrow products: nothing else more than a plain ``nn.remat``.  On
    the dense attention the kernel's names are not there."""
    cfg, params, flash = recomputed_loss(kind)
    _, _, dense = recomputed_loss(kind, reference_attention)
    named, unnamed = saved(flash, params), saved(dense, params)
    monkeypatch.setattr(transformer, "keeping", plain_remat)
    plain, plain_dense = saved(flash, params), saved(dense, params)
    b, t = TOKENS.shape
    spec = cfg.block.attention
    heads = groups = cfg.n_heads
    d_qk = d_v = cfg.d_model // cfg.n_heads
    if kind == "latent":
        d_qk, d_v = spec.nope_dim + spec.rope_dim, spec.v_dim
    elif kind.startswith("grouped"):
        heads, groups, d_qk, d_v = (spec.heads, spec.kv_heads,
                                    spec.head_dim, spec.head_dim)
    kind_of = str(jnp.dtype(cfg.dtype))
    stack = (cfg.passes,) if cfg.passes > 1 else ()
    kernel = [(stack + (b * heads, t, d_v), kind_of),
              (stack + (b * heads, t), "float32")]
    model = []
    if cfg.passes == 1:
        model = [((b, t, cfg.d_model), kind_of)]
        if kind == "latent":
            model += [((b, t, spec.q_rank), kind_of),
                      ((b, t, spec.kv_rank + spec.rope_dim), kind_of)]
        else:
            kernel += [((b * heads, t, d_qk), kind_of),
                       ((b * groups, t, d_qk), kind_of),
                       ((b * groups, t, d_v), kind_of)]
        if kind == "plain":
            # the reference LayerNorm's jitted ``_var`` hands the kept sum
            # on to its backward half: a second line of this account for
            # the same array
            model *= 2
    assert named == sorted(plain + (kernel + model) * cfg.n_layers)
    assert unnamed == sorted(plain_dense + model * cfg.n_layers)
