"""A pattern of block specs by layer and the attention kind with
grouped key-value heads, a window, its own rotary recipe and a gate
(``models/transformer.py``): what a layer is built from, that a
configuration with one spec is built as before, what reaches the
attention function, and the scopes the compiled step carries."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, GroupedAttention, Rotary,
                                TopkExperts, Transformer, TransformerConfig)
from horovod_tpu.models.transformer import Block, keeping, kept_names
from horovod_tpu.parallel.ring_attention import reference_attention
from horovod_tpu.utils import trace

FULL = GroupedAttention(heads=4, kv_heads=2, head_dim=8, gate="softplus",
                        rotary=Rotary(theta=500000.0, fraction=0.5, factor=8,
                                      original_len=8, attention_factor=1.2))
SLIDING = GroupedAttention(heads=6, kv_heads=2, head_dim=8, window=4,
                           gate="softplus", rotary=Rotary(theta=10000.0))


def spec(attention, ffn="swiglu"):
    return BlockSpec(norm="rms", positions="rope", ffn=ffn,
                     attention=attention)


def config(**changes):
    base = dict(vocab_size=31, n_layers=5, d_model=32, n_heads=4, d_ff=48,
                max_len=16, dtype=jnp.float32, leading_dense=1,
                pattern=(spec(FULL), spec(SLIDING), spec(SLIDING),
                         spec(SLIDING)))
    return TransformerConfig(**{**base, **changes})


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 31)


def test_a_layer_is_built_from_its_spec_of_the_pattern():
    cfg = config()
    assert cfg.block == cfg.pattern[0]
    kinds = [cfg.at(i).block.attention for i in range(5)]
    assert kinds == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert all(cfg.at(i).pattern == () for i in range(5))
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    for i, kind in enumerate(kinds):
        attn = params[f"block_{i}"]["attn"]
        assert set(attn) == {"q", "kv", "gate", "out"}
        assert attn["q"]["kernel"].shape == (32, kind.heads, 8)
        # k and v have the key-value heads' count, never the queries'
        assert attn["kv"]["kernel"].shape == (32, 2, 2, 8)
        assert attn["gate"]["kernel"].shape == (32, kind.heads)
        assert attn["out"]["kernel"].shape == (kind.heads * 8, 32)
    assert "pos_embed" not in params  # rotary: no table


def test_one_spec_is_built_as_before():
    """No pattern: ``at`` is the configuration itself, every block reads
    ``cfg.block``, and the parameter tree has the names it had."""
    cfg = TransformerConfig(vocab_size=31, n_layers=2, d_model=32, n_heads=4,
                            d_ff=48, max_len=16, dtype=jnp.float32)
    assert cfg.pattern == () and cfg.at(1) is cfg
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert set(params) == {"embed", "pos_embed", "block_0", "block_1",
                           "ln_f", "lm_head"}
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "mlp"}
    assert set(params["block_0"]["attn"]) == {"qkv", "out"}
    assert params["block_0"]["attn"]["qkv"]["kernel"].shape == (32, 3, 4, 8)


def test_the_feed_forward_follows_the_pattern_behind_the_dense_layers():
    experts = TopkExperts(scoring="sigmoid", renormalize=True, scale=2.5,
                          shared=1, held=(0, 2))
    cfg = config(n_experts=8, experts_per_token=2, d_expert=16, pattern=(
        spec(FULL, experts), spec(SLIDING, experts)))
    assert [cfg.ffn_of(i) for i in range(3)] == ["swiglu", experts, experts]
    params = Transformer(cfg).init(jax.random.PRNGKey(0), TOKENS)["params"]
    assert "mlp" in params["block_0"] and "moe" in params["block_1"]


def test_specs_that_differ_in_what_the_model_has_once_are_refused():
    with pytest.raises(ValueError, match="has once"):
        config(pattern=(spec(FULL), BlockSpec(
            norm="layer", positions="rope", attention=SLIDING)))
    with pytest.raises(ValueError, match="has once"):
        config(pattern=(spec(FULL), BlockSpec(
            norm="rms", positions="learned", attention=SLIDING)))
    with pytest.raises(ValueError, match="do not divide"):
        GroupedAttention(heads=6, kv_heads=4, head_dim=8)
    with pytest.raises(ValueError, match="gate"):
        GroupedAttention(heads=4, kv_heads=2, head_dim=8, gate="sigmoid")


def test_the_attention_function_gets_grouped_heads_and_the_window():
    """k and v reach it with the key-value heads' count; a window is
    asked only of a layer that has one, so a function that knows none
    (ring, zigzag) serves the layers without."""
    seen = []

    def spy(q, k, v, *, causal, **rest):
        seen.append((q.shape[2], k.shape[2], v.shape[2], causal, rest))
        return reference_attention(q, k, v, causal=causal, **rest)

    cfg = config(attn_fn=spy)
    model = Transformer(cfg)
    model.apply(model.init(jax.random.PRNGKey(0), TOKENS), TOKENS)
    assert seen[-5:] == [(4, 2, 2, True, {})] + [
        (6, 2, 2, True, {"window": 4})] * 3 + [(4, 2, 2, True, {})]


def test_the_window_and_the_gate_do_something():
    cfg = config()
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(0), TOKENS)
    out = model.apply(variables, TOKENS)

    def without(**changes):
        pattern = tuple(dataclasses.replace(
            s, attention=dataclasses.replace(s.attention, **changes))
            for s in cfg.pattern)
        return Transformer(config(pattern=pattern))

    wide = without(window=None).apply(variables, TOKENS)
    assert float(jnp.max(jnp.abs(wide - out))) > 1e-4
    # within the window's reach of the start nothing differs
    np.testing.assert_allclose(wide[:, :4], out[:, :4], rtol=1e-5,
                               atol=1e-6)
    ungated = without(gate=None)
    no_gate = jax.tree.map(lambda x: x, variables)
    for i in range(5):
        del no_gate["params"][f"block_{i}"]["attn"]["gate"]
    plain = ungated.apply(no_gate, TOKENS)
    assert float(jnp.max(jnp.abs(plain - out))) > 1e-4
    other = without(rotary=Rotary(theta=100.0)).apply(variables, TOKENS)
    assert float(jnp.max(jnp.abs(other - out))) > 1e-4


def test_recomputed_blocks_of_a_pattern_give_the_same_gradients():
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


def test_the_compiled_step_carries_the_four_scopes():
    """``attn/window`` on a sliding layer, ``attn/global`` on a full
    one, the attention function's call under ``flash`` inside each and
    the gate under ``attn/gate``, forward and backward, as
    ``utils/trace.py:step_phases`` reads the compiled step."""
    cfg = config()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    compiled = jax.jit(jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, TOKENS)))).lower(params).compile()
    text = compiled.as_text()
    for scope in ("block_1/attn/attn/window/flash",
                  "block_0/attn/attn/global/flash",
                  "block_4/attn/attn/global/q", "block_2/attn/attn/window/kv",
                  "block_3/attn/attn/window/out", "attn/attn/gate",
                  "attn/attn/window/rope"):
        assert scope in text, scope
    found = {(phase, scope) for phase, scope in
             trace.step_phases(compiled)[0].values()}
    scopes = {scope for _, scope in found}
    assert {"block/attn/window/flash", "block/attn/global/flash",
            "block/attn/gate"} <= scopes
    assert {("forward", "block/attn/window/flash"),
            ("backward", "block/attn/window/flash")} <= found
    assert trace.scope_of(
        "jit(per_shard)/transpose(jvp(Transformer))/jvp(Transformer)/"
        "checkpoint/block_3/attn/attn/window/flash/jit(_bwd)/pallas_call"
    ) == "block/attn/window/flash"
    assert trace.scope_of(
        "jit(per_shard)/jvp(Transformer)/block_0/attn/attn/global/flash/"
        "jit(_fwd)/pallas_call") == "block/attn/global/flash"
