"""The mixers a decoder-hybrid-decoder model brings to
``models/transformer.py``: differential attention against its pairs
written out (with and without a window, and through the flash kernels in
interpret mode), a cross layer's gradient reaching the k and v of the
layer that published them, the state-space mixer against its recurrence
one position at a time, the memory unit, a head that is the embedding
with the gradient of both uses, a model with no positions, and what a
recomputed block of each new kind keeps."""

from collections import Counter

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, DifferentialAttention, MemoryUnit,
                                SelectiveScan, Transformer, TransformerConfig,
                                lm_loss)
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (KEPT_SUM, Block,
                                            DifferentialAttentionMixer,
                                            MemoryUnitMixer,
                                            SelectiveScanMixer, kept_bytes,
                                            kept_names)
from horovod_tpu.ops import selective_scan
from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                    SAVED_NAMES,
                                                    flash_attention)
from horovod_tpu.parallel import moe

B, T, D, HEADS, KV_HEADS, DIM = 2, 32, 32, 8, 4, 8
INNER, STATE, RANK = 64, 4, 4
SSM = dict(d_inner=INNER, dt_rank=RANK, state=STATE)


def spec(mixer):
    return BlockSpec(norm="layer", positions="none", ffn="swiglu",
                     attention=mixer)


def diff(lambda_init=0.7, **kw):
    return DifferentialAttention(HEADS, KV_HEADS, DIM, lambda_init, **kw)


def config(pattern, **kw):
    return TransformerConfig(**{**dict(
        vocab_size=97, n_layers=len(pattern), d_model=D, n_heads=HEADS,
        d_ff=48, max_len=T, dtype=jnp.float32, norm_eps=1e-5,
        pattern=tuple(pattern)), **kw})


# Mamba, sliding, Mamba that publishes, full that publishes, memory unit,
# cross: a decoder-hybrid-decoder's six kinds of layer
HYBRID = (spec(SelectiveScan(**SSM)), spec(diff(0.70, window=8)),
          spec(SelectiveScan(**SSM, publishes=True)),
          spec(diff(0.75, keys="published")), spec(MemoryUnit(INNER)),
          spec(diff(0.78, keys="read")))
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0, 97)


def noisy(params, seed=1, scale=0.1):
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([leaf + scale * jax.random.normal(k, leaf.shape)
                           for leaf, k in zip(leaves, keys)])


def written_out(x, w, mixer, keys=None):
    """``x [B, T, d]`` through differential attention, a pair of query
    heads at a time: two softmaxes, one taken off the other."""
    q = jnp.einsum("btd,dpsk->bpstk", x, w["q"]["kernel"]) + (
        w["q"]["bias"][None, :, :, None, :])
    if mixer.keys == "read":
        k, v = (jnp.moveaxis(u, 1, 3) for u in keys)   # [B, G/2, 2, T, D]
    else:
        k, v = jnp.einsum("btd,dcpsk->cbpstk", x, w["kv"]["kernel"]) + (
            w["kv"]["bias"][:, None, :, :, None, :])
    behind = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    allowed = behind >= 0
    if mixer.window is not None:
        allowed &= behind < mixer.window
    lam = (jnp.exp(w["lambda_q1"] @ w["lambda_k1"])
           - jnp.exp(w["lambda_q2"] @ w["lambda_k2"]) + mixer.lambda_init)
    group = mixer.heads // mixer.kv_heads
    outs = []
    for pair in range(mixer.heads // 2):
        kv = pair // group
        vv = jnp.concatenate([v[:, kv, 0], v[:, kv, 1]], -1)  # [B, T, 2 D]
        maps = [jax.nn.softmax(jnp.where(
            allowed, q[:, pair, s] @ jnp.swapaxes(k[:, kv, s], 1, 2)
            / np.sqrt(mixer.head_dim), -jnp.inf), -1) for s in (0, 1)]
        o = maps[0] @ vv - lam * (maps[1] @ vv)
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        outs.append(o * w["subln"]["scale"] * (1 - mixer.lambda_init))
    return (jnp.concatenate(outs, -1) @ w["out"]["kernel"]
            + w["out"]["bias"])


@pytest.mark.parametrize("attn_fn", [None, "flash"], ids=["dense", "flash"])
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_against_the_pairs_written_out(kind, attn_fn):
    """Forward and the gradient of every weight and of the input, on the
    dense attention function and through the flash kernels (interpret
    mode) at ``d_qk`` 8, ``d_v`` 16 with grouped heads; a cross layer
    reads another layer's k and v and has no projection for them."""
    mixer = {"window": diff(window=8), "full": diff(keys="published"),
             "cross": diff(keys="read")}[kind]
    cfg = config([spec(mixer)], attn_fn=attn_fn and (
        lambda *a, **kw: flash_attention(*a, block_q=16, block_k=16, **kw)))
    module = DifferentialAttentionMixer(cfg.at(0))
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (B, T, D))
    keys = tuple(jax.random.normal(k, (B, T, KV_HEADS // 2, 2, DIM))
                 for k in jax.random.split(key, 2))
    params = noisy(module.init(key, x, keys)["params"])
    assert ("kv" in params) == (kind != "cross")
    weights = jax.random.normal(jax.random.PRNGKey(4), (B, T, D))

    def program(p, x, keys):
        out, made = module.apply({"params": p}, x, keys)
        return jnp.sum(out * weights), made

    def by_hand(p, x, keys):
        return jnp.sum(written_out(x, p, mixer, keys) * weights)

    with jax.default_matmul_precision("highest"):
        (got, made), got_grads = jax.value_and_grad(
            program, (0, 1, 2), has_aux=True)(params, x, keys)
        want, want_grads = jax.value_and_grad(by_hand, (0, 1, 2))(
            params, x, keys)
    # (the kernels' online softmax sums in another order)
    np.testing.assert_allclose(got, want, rtol=1e-4 if attn_fn else 1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5)
    if kind == "cross":
        # what it read is what it hands back, and the gradient reaches it
        assert all(a is b for a, b in zip(made, keys))
        assert all(float(jnp.abs(g).sum()) > 0 for g in got_grads[2])
    else:
        assert [u.shape for u in made] == [(B, T, KV_HEADS // 2, 2, DIM)] * 2


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_cross_layers_gradient_reaches_the_publishing_layers_k_and_v(remat):
    """In the model: the cross layer (block 5) reads block 3's k and v
    and the memory unit (block 4) block 2's scan output through the
    pytree beside ``x``; with the cross layer's and the memory unit's
    outputs cut off, less gradient reaches the publishers' weights, and
    under ``keeping`` the gradients are the plain model's."""
    cfg = config(HYBRID, tie_head=True, remat=remat)
    model = Transformer(cfg)
    params = noisy(model.init(jax.random.PRNGKey(1), TOKENS)["params"], 2,
                   0.05)

    def loss(p):
        return lm_loss(model.apply({"params": p}, TOKENS), TOKENS)

    grads = jax.grad(loss)(params)
    plain = Transformer(config(HYBRID, tie_head=True))
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(jax.grad(
            lambda p: lm_loss(plain.apply({"params": p}, TOKENS),
                              TOKENS))(params))):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7)

    # the readers silenced: their output projections are zero
    silenced = jax.tree.map(lambda a: a, params)
    for block, module in (("block_5", "attn"), ("block_4", "mixer")):
        out = silenced[block][module]["out"]
        silenced[block][module]["out"] = jax.tree.map(jnp.zeros_like, out)
    without = jax.grad(loss)(silenced)
    kv = grads["block_3"]["attn"]["kv"]["kernel"]
    assert float(jnp.max(jnp.abs(kv - without["block_3"]["attn"]["kv"][
        "kernel"]))) > 1e-6
    # the memory is the scan's output: its D reaches the memory unit
    d_of = grads["block_2"]["mixer"]["D"]
    assert float(jnp.max(jnp.abs(
        d_of - without["block_2"]["mixer"]["D"]))) > 1e-7


def test_a_reader_before_any_publisher_has_nothing_to_read():
    cfg = config([spec(MemoryUnit(INNER)), spec(SelectiveScan(**SSM))])
    with pytest.raises(KeyError, match="memory"):
        Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)


def test_the_state_space_mixer_against_its_recurrence_position_by_position():
    cfg = config([spec(SelectiveScan(**SSM, publishes=True))])
    module = SelectiveScanMixer(cfg.at(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, D))
    w = noisy(module.init(jax.random.PRNGKey(6), x)["params"], 7)
    assert w["conv_kernel"].shape == (4, INNER)
    assert w["A_log"].shape == (INNER, STATE)
    with jax.default_matmul_precision("highest"):
        got, memory = module.apply({"params": w}, x)
        a, z = jnp.split(x @ w["in"]["kernel"], 2, -1)
        padded = jnp.pad(a, ((0, 0), (3, 0), (0, 0)))
        c = jax.nn.silu(sum(w["conv_kernel"][j] * padded[:, j:j + T]
                            for j in range(4)) + w["conv_bias"])
        rbc = c @ w["x"]["kernel"]
        delta = jax.nn.softplus(rbc[..., :RANK] @ w["dt_kernel"]
                                + w["dt_bias"])
        big_a = -jnp.exp(w["A_log"])
        h, ys = jnp.zeros((B, INNER, STATE)), []
        for t in range(T):
            h = (jnp.exp(delta[:, t, :, None] * big_a) * h
                 + (delta[:, t] * c[:, t])[..., None]
                 * rbc[:, t, None, RANK:RANK + STATE])
            ys.append(jnp.einsum("bdn,bn->bd", h, rbc[:, t, RANK + STATE:])
                      + w["D"] * c[:, t])
        y = jnp.stack(ys, 1)
        want = (y * jax.nn.silu(z)) @ w["out"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the memory is the scan's output BEFORE the gate
    np.testing.assert_allclose(memory, y, rtol=2e-4, atol=2e-5)


def test_the_memory_unit_gates_what_it_is_handed():
    cfg = config([spec(MemoryUnit(INNER))])
    module = MemoryUnitMixer(cfg.at(0))
    x = jax.random.normal(jax.random.PRNGKey(8), (B, T, D))
    memory = jax.random.normal(jax.random.PRNGKey(9), (B, T, INNER))
    w = module.init(jax.random.PRNGKey(10), x, memory)["params"]
    assert set(w) == {"in", "out"} and "bias" not in w["in"]
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": w}, x, memory)
        want = ((jax.nn.silu(x @ w["in"]["kernel"]) * memory)
                @ w["out"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_a_tied_heads_gradient_is_the_sum_of_its_two_uses():
    """``logits = norm_f(x) E^T``: no ``lm_head`` among the parameters,
    and the gradient of ``E`` is the gradient through the lookup plus
    the gradient through the product, each taken with the other use
    held constant."""
    cfg = config(HYBRID[:2], tie_head=True)
    model = Transformer(cfg)
    params = noisy(model.init(jax.random.PRNGKey(1), TOKENS)["params"], 3,
                   0.05)
    assert "lm_head" not in params and "pos_embed" not in params
    untied = Transformer(config(HYBRID[:2]))

    def loss(lookup, head):
        p = {**params, "embed": {"embedding": lookup},
             "lm_head": {"kernel": head.T}}
        return lm_loss(untied.apply({"params": p}, TOKENS), TOKENS)

    e = params["embed"]["embedding"]
    tied = jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, TOKENS), TOKENS))(params)
    through_lookup, through_head = jax.grad(loss, (0, 1))(e, e)
    assert float(jnp.abs(through_lookup).sum()) > 0
    assert float(jnp.abs(through_head).sum()) > 0
    np.testing.assert_allclose(tied["embed"]["embedding"],
                               through_lookup + through_head, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(
        model.apply({"params": params}, TOKENS),
        untied.apply({"params": {**params, "lm_head": {"kernel": e.T}}},
                     TOKENS), rtol=1e-6)


def test_a_model_with_no_positions_has_no_table_and_turns_no_head():
    """``positions="none"`` on the plain fused attention too: no
    ``pos_embed``, and the logits of a sequence do not know where a
    token stands beyond the causal mask (a rotated or tabled model's
    would: the same tokens shifted give shifted logits here)."""
    cfg = config([BlockSpec(positions="none")] * 2, n_heads=4)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert "pos_embed" not in params
    jaxpr = str(jax.make_jaxpr(lambda p: model.apply({"params": p},
                                                     TOKENS))(params))
    assert "cos" not in jaxpr and "sin" not in jaxpr
    with pytest.raises(ValueError, match="none of"):
        BlockSpec(positions="absolute")


def test_specs_refuse_what_they_cannot_be():
    with pytest.raises(ValueError, match="pairs"):
        DifferentialAttention(8, 3, 8, 0.5)
    with pytest.raises(ValueError, match="keys"):
        DifferentialAttention(8, 4, 8, 0.5, keys="borrowed")


# ------------------------------------- what a recomputed block keeps
def test_kept_names_and_bytes_of_the_new_kinds():
    cfg = config(HYBRID, dtype=jnp.bfloat16, remat=True)
    scanned = selective_scan.SAVED_NAMES
    routed = moe.SAVED_NAMES          # listed; no layer here sets them
    assert kept_names(cfg.at(0)) == scanned + (KEPT_SUM,) + routed
    assert kept_names(cfg.at(4)) == (KEPT_SUM,) + routed
    assert set(SAVED_NAMES + SAVED_INPUT_NAMES + scanned) <= set(
        kept_names(cfg))
    sums = B * T * D * 2
    assert kept_bytes(cfg, B, T, 4) == {KEPT_SUM: sums}
    assert kept_bytes(cfg, B, T, 0) == {
        selective_scan.SAVED_Y: B * T * INNER * 2,
        selective_scan.SAVED_STATES: B * STATE * INNER * 4, KEPT_SUM: sums}
    # two calls a layer: half the heads each, the values twice as wide
    q, k, v = SAVED_INPUT_NAMES
    for layer in (1, 3, 5):
        assert kept_bytes(cfg, B, T, layer) == {
            SAVED_NAMES[0]: 2 * B * T * (HEADS // 2) * 2 * DIM * 2,
            SAVED_NAMES[1]: 2 * B * T * (HEADS // 2) * 4,
            q: 2 * B * T * (HEADS // 2) * DIM * 2,
            k: 2 * B * T * (KV_HEADS // 2) * DIM * 2,
            v: 2 * B * T * (KV_HEADS // 2) * 2 * DIM * 2, KEPT_SUM: sums}


def test_kept_bytes_are_what_the_backward_pass_is_handed(monkeypatch):
    """``kept_bytes`` against ``jax.ad_checkpoint``'s own account of the
    residuals, through the flash kernels: the six recomputed blocks hand
    their backward passes every array the function gives by name, at
    its bytes (a differential layer's kernel names twice, a call
    each)."""
    from jax._src.ad_checkpoint import saved_residuals

    def handed():
        cfg = config(HYBRID, remat=True, tie_head=True, attn_fn=(
            lambda *a, **kw: flash_attention(*a, block_q=16, block_k=16,
                                             **kw)))
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(1), TOKENS)["params"]
        return cfg, [int(np.prod(aval.shape)) * aval.dtype.itemsize
                     for aval, _ in saved_residuals(
                         lambda p: lm_loss(model.apply({"params": p}, TOKENS),
                                           TOKENS), params)]

    cfg, named = handed()
    monkeypatch.setattr(transformer, "keeping",
                        lambda block, names: nn.remat(block))
    _, plain = handed()
    more = []
    for layer in range(cfg.n_layers):
        twice = isinstance(cfg.at(layer).block.attention,
                           DifferentialAttention)
        for name, n in kept_bytes(cfg, B, T, layer).items():
            calls = 2 if twice and name != KEPT_SUM else 1
            more += [n // calls] * calls
    # every array the function lists is there at its bytes, and beyond
    # a plain remat's and those nothing but the kept sums a second time
    # (the reference LayerNorm's jitted ``_var`` hands its input on to
    # its backward half: the same array in the program)
    assert not Counter(more) - Counter(named)
    assert set(Counter(named) - Counter(plain + more)) <= {
        kept_bytes(cfg, B, T)[KEPT_SUM]}


def test_a_block_hands_on_what_it_was_given():
    """A block that publishes nothing returns what it was handed: nothing
    (the blocks of every model from before) or what came in."""
    cfg = config([BlockSpec()], n_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, D))
    block = Block(cfg.at(0))
    params = block.init(jax.random.PRNGKey(1), x)["params"]
    out, shared = block.apply({"params": params}, x)
    assert out.shape == x.shape and not jax.tree.leaves(shared)
    out, shared = block.apply({"params": params}, x, None, {"memory": x})
    assert list(shared) == ["memory"] and shared["memory"] is x
