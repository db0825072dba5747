"""The mixer that is no attention (``models/transformer.py:ShortConv``,
``ShortConvMixer``): a doubly gated causal convolution of a few taps
against an explicit loop over taps and positions in float32; a position
reads nothing after it and the first positions read zeros; it is data
on ``BlockSpec`` beside the attention kinds, in a pattern with them;
what a recomputed block of it keeps; and the head norm of
``GroupedAttention(qk_norm=True)`` against a reference a head at a
time."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core

from horovod_tpu.models import (BlockSpec, GroupedAttention, Rotary,
                                ShortConv, Transformer, TransformerConfig)
from horovod_tpu.models.transformer import (KEPT_SUM, Attention, Block,
                                            ShortConvMixer, keeping,
                                            kept_bytes, kept_names, rotate)
from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                    SAVED_NAMES)
from horovod_tpu.utils import trace

D, T = 16, 12
FULL = GroupedAttention(heads=4, kv_heads=2, head_dim=8, qk_norm=True,
                        rotary=Rotary(theta=1e6))


def spec(mixer, ffn="swiglu"):
    return BlockSpec(norm="rms", positions="rope", ffn=ffn, attention=mixer)


def config(**changes):
    base = dict(vocab_size=31, n_layers=5, d_model=D, n_heads=4, head_dim=8,
                d_ff=24, max_len=T, dtype=jnp.float32, norm_eps=1e-5,
                leading_dense=1,
                pattern=(spec(ShortConv()), spec(FULL), spec(ShortConv()),
                         spec(ShortConv())))
    return TransformerConfig(**{**base, **changes})


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, 31)


def mixer_and_params(taps=3, dtype=jnp.float32, seed=0):
    cfg = config(pattern=(), block=spec(ShortConv(taps=taps)), dtype=dtype)
    mixer = ShortConvMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, D))
    return mixer, mixer.init(jax.random.PRNGKey(seed + 1), x)["params"], x


def by_loops(params, x):
    """The mixer's equations a position and a tap at a time, float64 on
    the host."""
    w_in, w, w_out = (np.asarray(a, np.float64) for a in (
        params["in"]["kernel"], params["kernel"], params["out"]["kernel"]))
    taps = w.shape[0]
    out = np.zeros(x.shape)
    for n, seq in enumerate(np.asarray(x, np.float64)):
        bch = seq @ w_in
        b, c, h = bch[:, :D], bch[:, D:2 * D], bch[:, 2 * D:]
        g = b * h
        s = np.zeros_like(g)
        for t in range(T):
            for j in range(taps):
                at = t - (taps - 1) + j  # the last tap is the position
                if at >= 0:
                    s[t] += w[j] * g[at]
        out[n] = (c * s) @ w_out
    return out


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_mixer_against_loops_over_positions_and_taps(taps):
    mixer, params, x = mixer_and_params(taps)
    assert set(params) == {"in", "kernel", "out"}
    assert params["in"]["kernel"].shape == (D, 3 * D)
    assert params["kernel"].shape == (taps, D)
    assert params["out"]["kernel"].shape == (D, D)
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": params}, x)
    np.testing.assert_allclose(got, by_loops(params, x), rtol=2e-5,
                               atol=2e-6)


def test_the_taps_start_inside_one_over_the_root_of_their_count():
    _, params, _ = mixer_and_params(3)
    w = np.asarray(params["kernel"])
    assert np.abs(w).max() <= 1 / np.sqrt(3) and np.abs(w).max() > 0.4


def test_a_position_reads_itself_and_the_two_before_it_and_nothing_after():
    """Output and gradient: what position t gives depends on positions
    t - 2 .. t alone, and the gradient of position t's output reaches no
    input after t nor more than two before it."""
    mixer, params, x = mixer_and_params(3)

    def at(x, t):
        return mixer.apply({"params": params}, x)[:, t]

    changed = x.at[:, 7:].add(1.0)
    np.testing.assert_array_equal(at(changed, 6), at(x, 6))
    assert float(jnp.max(jnp.abs(at(changed, 7) - at(x, 7)))) > 1e-3
    early = x.at[:, :4].add(1.0)           # 6 reads 4, 5, 6
    np.testing.assert_array_equal(at(early, 6), at(x, 6))
    assert float(jnp.max(jnp.abs(at(early, 5) - at(x, 5)))) > 1e-3
    reach = jax.grad(lambda x: jnp.sum(at(x, 6)))(x)
    touched = np.flatnonzero(np.abs(np.asarray(reach)).sum((0, 2)))
    assert list(touched) == [4, 5, 6]


def test_positions_0_and_1_read_zeros_ahead_of_the_sequence():
    """Position 0 is the last tap on itself alone; position 1 the last
    two; whatever stands ahead in the batch or in another sequence does
    not leak in."""
    mixer, params, x = mixer_and_params(3)
    got = mixer.apply({"params": params}, x)
    alone = mixer.apply({"params": params}, x[:, :1])
    np.testing.assert_allclose(got[:, :1], alone, rtol=1e-6, atol=1e-7)
    pair = mixer.apply({"params": params}, x[:, :2])
    np.testing.assert_allclose(got[:, :2], pair, rtol=1e-6, atol=1e-7)
    w = params["kernel"]
    bch = x[:, 0] @ params["in"]["kernel"]
    b, c, h = bch[:, :D], bch[:, D:2 * D], bch[:, 2 * D:]
    np.testing.assert_allclose(
        got[:, 0], (c * (w[2] * b * h)) @ params["out"]["kernel"],
        rtol=1e-5, atol=1e-6)


def test_gradients_against_the_loops_by_finite_differences_of_them():
    """``jax.grad`` of the mixer against central differences of the
    loops (float64), on the taps and on a slice of the input."""
    mixer, params, x = mixer_and_params(3)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, T, D))

    def loss(params, x):
        return jnp.sum(mixer.apply({"params": params}, x) * weight)

    with jax.default_matmul_precision("highest"):
        g_params, g_x = jax.grad(loss, (0, 1))(params, x)

    def loops(w=None, x_=None):
        p = dict(params, kernel=params["kernel"] if w is None else w)
        return float(np.sum(by_loops(p, x if x_ is None else x_)
                            * np.asarray(weight, np.float64)))

    eps = 1e-4
    w = np.asarray(params["kernel"], np.float64)
    for j, col in ((0, 3), (1, 0), (2, 11)):
        up, down = w.copy(), w.copy()
        up[j, col] += eps
        down[j, col] -= eps
        assert float(g_params["kernel"][j, col]) == pytest.approx(
            (loops(w=up) - loops(w=down)) / (2 * eps), rel=2e-3, abs=1e-4)
    x64 = np.asarray(x, np.float64)
    for n, t, col in ((0, 0, 2), (1, 5, 7), (0, T - 1, 15)):
        up, down = x64.copy(), x64.copy()
        up[n, t, col] += eps
        down[n, t, col] -= eps
        assert float(g_x[n, t, col]) == pytest.approx(
            (loops(x_=up) - loops(x_=down)) / (2 * eps), rel=2e-3, abs=1e-4)


def test_bfloat16_activations_sum_the_taps_in_float32():
    mixer, params, x = mixer_and_params(3, dtype=jnp.bfloat16)
    got = mixer.apply({"params": params}, x.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), by_loops(params, x),
                               rtol=0.1, atol=0.05)
    jaxpr = str(jax.make_jaxpr(
        lambda x: mixer.apply({"params": params}, x))(
            x.astype(jnp.bfloat16)))
    # three products of float32 against the taps, summed, cast back
    assert jaxpr.count("f32[2,12,16]") >= 6


def test_the_mixer_is_data_on_the_spec_beside_the_attention_kinds():
    cfg = config()
    kinds = [cfg.at(i).block.attention for i in range(5)]
    assert kinds == [ShortConv(), FULL, ShortConv(), ShortConv(),
                     ShortConv()]
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    for i in (0, 2, 3, 4):
        assert set(params[f"block_{i}"]) == {
            "ln1", "mixer", "ln2", "mlp"}
        assert set(params[f"block_{i}"]["mixer"]) == {"in", "kernel", "out"}
    attn = params["block_1"]["attn"]
    assert set(attn) == {"q", "kv", "q_norm", "k_norm", "out"}
    assert attn["q_norm"]["scale"].shape == (8,)
    assert attn["k_norm"]["scale"].shape == (8,)
    with pytest.raises(ValueError, match="none of"):
        BlockSpec(attention="conv")
    with pytest.raises(ValueError, match="taps"):
        ShortConv(taps=0)


def test_the_head_norm_against_a_reference_a_head_at_a_time():
    """``GroupedAttention(qk_norm=True)``: every head of q and of k
    normed over its own columns with one scale of ``head_dim``, before
    the rotation; query head h reads key-value head h // 2."""
    cfg = config().at(1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, D))
    attention = Attention(cfg)
    params = attention.init(jax.random.PRNGKey(4), x)["params"]
    params = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(5),
                                              a.shape), params)
    with jax.default_matmul_precision("highest"):
        got = attention.apply({"params": params}, x)
        q = jnp.einsum("btd,dhk->bthk", x, params["q"]["kernel"])
        k, v = jnp.einsum("btd,dcgk->cbtgk", x, params["kv"]["kernel"])

        def normed(u, scale):
            return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                                + 1e-5) * scale

        heads = []
        for h in range(4):
            q_h = rotate(normed(q[:, :, h:h + 1],
                                params["q_norm"]["scale"]), FULL.rotary)
            k_h = rotate(normed(k[:, :, h // 2:h // 2 + 1],
                                params["k_norm"]["scale"]), FULL.rotary)
            s = jnp.einsum("bqd,bkd->bqk", q_h[:, :, 0],
                           k_h[:, :, 0]) / 8 ** .5
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
            heads.append(jax.nn.softmax(s, -1) @ v[:, :, h // 2])
        want = jnp.concatenate(heads, -1) @ params["out"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # off, it is the kind from before: no norm's parameters, another result
    plain = Attention(config(pattern=(spec(
        GroupedAttention(heads=4, kv_heads=2, head_dim=8,
                         rotary=Rotary(theta=1e6))),)))
    bare = {n: params[n] for n in ("q", "kv", "out")}
    assert set(plain.init(jax.random.PRNGKey(4), x)["params"]) == set(bare)
    other = plain.apply({"params": bare}, x)
    assert float(jnp.max(jnp.abs(other - got))) > 1e-3


def test_kept_names_and_bytes_of_a_layer_with_no_kernel():
    """A conv layer names the sum after its mixer and nothing of the
    flash kernel; the attention layer of the same pattern keeps what it
    kept; one policy over the mixed pattern (a routed layer's names are
    on every one-pass list and keep nothing where no layer routes)."""
    from horovod_tpu.parallel.moe import SAVED_NAMES as routed

    cfg = config(dtype=jnp.bfloat16, remat=True)
    everything = SAVED_NAMES + SAVED_INPUT_NAMES
    assert kept_names(cfg.at(0)) == (KEPT_SUM,) + routed
    assert set(everything) <= set(kept_names(cfg.at(1)))
    assert set(everything) <= set(kept_names(cfg))
    assert kept_names(config(pattern=(), block=spec(ShortConv()))) == (
        KEPT_SUM,) + routed
    for layer in (0, 2, 3, 4):
        assert kept_bytes(cfg, 2, T, layer) == {KEPT_SUM: 2 * T * D * 2}
    full = kept_bytes(cfg, 2, T, 1)
    assert full[KEPT_SUM] == 2 * T * D * 2
    assert set(full) == set(everything) | {KEPT_SUM}
    # under passes nothing of a conv layer is kept by name
    looped = config(pattern=(), block=spec(ShortConv()), passes=2,
                    leading_dense=0, dtype=jnp.bfloat16)
    assert kept_bytes(looped, 2, T) == {}


def test_recomputed_blocks_of_a_mixed_pattern_give_the_same_gradients():
    cfg = config()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]

    def loss(model):
        return lambda p: jnp.mean(
            model.apply({"params": p}, TOKENS).astype(jnp.float32) ** 2)

    want = jax.grad(loss(model))(params)
    got = jax.grad(loss(Transformer(config(remat=True))))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
    assert issubclass(keeping(Block, kept_names(cfg)), nn.Module)
    # the recomputation of a conv block makes ``in`` again and not
    # ``out``, whose result is in the kept sum
    jaxpr = jax.make_jaxpr(jax.grad(loss(Transformer(config(remat=True)))))(
        params)

    def walk(jaxpr, prefix=""):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            yield eqn.primitive.name, stack
            for sub in core.jaxprs_in_params(eqn.params):
                yield from walk(sub, stack)

    again = sorted(re.search(r"/block_\d+/(.*)$", stack).group(1)
                   for name, stack in walk(jaxpr.jaxpr)
                   if name == "dot_general"
                   and "rematted_computation" in stack
                   and re.search(r"/block_[0234]/", stack))
    assert again == sorted(["mixer/mixer/conv/in", "mlp/gate", "mlp/up"] * 4)


def test_the_compiled_step_carries_the_mixers_scopes():
    """``mixer/conv`` around all of a conv mixer, ``in`` and ``out`` its
    two products, ``gate_conv`` the gates and the taps; ``qk_norm``
    inside ``attn/global``; forward and backward, as
    ``utils/trace.py:step_phases`` reads the compiled step."""
    cfg = config()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    compiled = jax.jit(jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, TOKENS)))).lower(params).compile()
    text = compiled.as_text()
    for scope in ("block_0/mixer/mixer/conv/in",
                  "block_2/mixer/mixer/conv/out",
                  "block_4/mixer/mixer/conv/gate_conv",
                  "block_1/attn/attn/global/qk_norm/q_norm",
                  "block_1/attn/attn/global/qk_norm/k_norm",
                  "block_1/attn/attn/global/flash"):
        assert scope in text, scope
    found = {(phase, scope) for phase, scope in
             trace.step_phases(compiled)[0].values()}
    for scope in ("block/mixer/conv/in", "block/mixer/conv/out",
                  "block/mixer/conv/gate_conv"):
        assert {("forward", scope), ("backward", scope)} <= found, scope
    assert any(scope.startswith("block/attn/global/qk_norm")
               for _, scope in found)
    assert trace.scope_of(
        "jit(per_shard)/transpose(jvp(Transformer))/block_3/mixer/mixer/"
        "conv/gate_conv/mul") == "block/mixer/conv/gate_conv"
