"""What a model of :class:`ChunkSummaryAttention` blocks brings to
``models/transformer.py``: the mixer and its parameters, a residual
stream in another dtype than the products', the RMSNorm's unit offset, a
head with several outputs a position and its loss, and what a recomputed
block of the kind keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, ChunkSummaryAttention, Rotary,
                                Transformer, TransformerConfig, lm_loss,
                                multi_offset_lm_loss)
from horovod_tpu.models import transformer
from horovod_tpu.ops import chunk_attention

SPEC = ChunkSummaryAttention(heads=4, head_dim=8, window=16, chunk=4,
                             rotary=Rotary(theta=100000.0))


def config(**changes):
    return TransformerConfig(**{**dict(
        vocab_size=23, n_layers=2, d_model=32, n_heads=4, d_ff=48, max_len=64,
        dtype=jnp.float32, norm_eps=1e-5, norm_unit_offset=True,
        head_outputs=3, logits_dtype=jnp.float32,
        block=BlockSpec(norm="rms", positions="rope", ffn="swiglu",
                        attention=SPEC)), **changes})


def seeded(cfg, t=48, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (2, t), 0, cfg.vocab_size)
    params = Transformer(cfg).init(key, tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    return tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))
    ]), tokens


def test_the_mixers_parameters_and_their_start():
    cfg = config()
    tokens = jnp.zeros((1, 48), jnp.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    attn = params["block_0"]["attn"]
    assert sorted(attn) == ["k", "mu", "out", "phi", "q", "v"]
    assert attn["q"]["kernel"].shape == (32, 4, 8)
    assert attn["out"]["kernel"].shape == (32, 32)
    for name in ("phi", "mu"):
        assert attn[name].shape == (4, 8) and attn[name].dtype == jnp.float32
        # clip(normal, -1, 1) * head_dim^-1/2
        assert float(jnp.max(jnp.abs(attn[name]))) <= 8 ** -0.5
        assert float(jnp.std(attn[name])) > 0.1
    # the unit offset: every norm's scale starts at 0 and counts from 1
    for norm in (params["block_0"]["ln1"], params["block_1"]["ln2"],
                 params["ln_f"]):
        assert not np.any(norm["scale"])
    assert params["lm_head"]["kernel"].shape == (32, 3 * 23)
    logits = Transformer(cfg).apply({"params": params}, tokens)
    assert logits.shape == (1, 48, 69) and logits.dtype == jnp.float32


def test_the_kernel_path_is_the_dense_path(monkeypatch):
    """The model on the two calls of the flash kernels (interpret mode)
    against the model on the dense masked softmax: logits and every
    gradient."""
    cfg = config()
    params, tokens = seeded(cfg)

    def loss(p):
        return multi_offset_lm_loss(
            Transformer(cfg).apply({"params": p}, tokens), tokens, 3)

    want, want_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(transformer, "default_chunk_attention",
                        lambda: chunk_attention.chunk_summary_attention)
    got, got_grads = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=2e-6)


def test_rms_norm_with_a_unit_offset():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    g = jax.random.normal(jax.random.PRNGKey(2), (16,))
    got = transformer.RMSNorm(eps=1e-5, unit_offset=True).apply(
        {"params": {"scale": g}}, x)
    want = transformer.RMSNorm(eps=1e-5).apply(
        {"params": {"scale": 1 + g}}, x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(
        got, x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        * (1 + g), rtol=1e-5)
    with pytest.raises(ValueError, match="norm_unit_offset"):
        TransformerConfig(norm_unit_offset=True)      # a LayerNorm has none


def test_multi_offset_loss_at_one_output_is_lm_loss_and_shifts_by_hand():
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(key, (2, 12), 0, 7)
    logits = jax.random.normal(key, (2, 12, 3 * 7))
    np.testing.assert_allclose(
        multi_offset_lm_loss(logits[..., :7], tokens, 1),
        lm_loss(logits[..., :7], tokens), rtol=1e-6)
    # output r at position t is asked for token t + 1 + r; the last 1 + r
    # positions wrap to the first tokens; equal weights
    logp = np.asarray(jax.nn.log_softmax(logits.reshape(2, 12, 3, 7), -1))
    by_hand = np.mean([
        -logp[b, t, r, int(tokens[b, (t + 1 + r) % 12])]
        for b in range(2) for t in range(12) for r in range(3)])
    np.testing.assert_allclose(multi_offset_lm_loss(logits, tokens, 3),
                               by_hand, rtol=1e-6)
    with pytest.raises(ValueError, match="head_outputs"):
        TransformerConfig(head_outputs=2, tie_head=True)


def test_a_float32_stream_is_carried_and_differs_from_a_bfloat16_one():
    """bfloat16 products either way; with ``residual_dtype=float32`` the
    embedding, every sum and what travels between the blocks are
    float32.  On what leaves the stack (the logits) a rounded stream is
    off the carried one by more than the benchmark family's forward limit
    (on the mean loss over four layers it is not: the family's file says
    what the chip read), so this is where the two are told apart."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "hvd_benchmark_evabyte_lm_limit", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "models", "evabyte_lm.py"))
    evabyte_lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(evabyte_lm)

    carried = config(dtype=jnp.bfloat16, residual_dtype=jnp.float32)
    rounded = config(dtype=jnp.bfloat16)
    params, tokens = seeded(carried)
    seen = []
    for cfg in (carried, rounded):
        x, _ = transformer.Block(cfg).apply(
            {"params": params["block_0"]},
            jnp.ones((2, 48, 32), cfg.residual_dtype or cfg.dtype))
        seen.append(x.dtype)
    assert seen == [jnp.float32, jnp.bfloat16]
    logits = [Transformer(cfg).apply({"params": params}, tokens)
              for cfg in (carried, rounded)]
    off = float(jnp.linalg.norm(logits[0] - logits[1])
                / jnp.linalg.norm(logits[0]))
    assert off > 10 * evabyte_lm.TOLERANCE["forward"], off


def test_what_a_recomputed_block_of_the_kind_keeps():
    """The two calls' ``out`` and ``lse``, the summaries, the sum after
    the mixer in the stream's dtype; NOT q, k, v as the kernels read
    them (two layouts of q: four arrays of ``out``'s size a layer)."""
    from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                        SAVED_LSE, SAVED_OUT)

    cfg = config(dtype=jnp.bfloat16, residual_dtype=jnp.float32, remat=True)
    names = transformer.kept_names(cfg)
    assert set(chunk_attention.SAVED_NAMES) <= set(names)
    assert {SAVED_OUT, SAVED_LSE, transformer.KEPT_SUM} <= set(names)
    assert not set(SAVED_INPUT_NAMES) & set(names)
    b, t, h, d = 2, 48, 4, 8
    assert transformer.kept_bytes(cfg, b, t) == {
        SAVED_OUT: 2 * b * t * h * d * 2, SAVED_LSE: 2 * b * t * h * 4,
        chunk_attention.SAVED_KT: b * t // 4 * h * d * 2,
        chunk_attention.SAVED_VT: b * t // 4 * h * d * 2,
        transformer.KEPT_SUM: b * t * 32 * 4}
    # within one window there is one call
    assert transformer.kept_bytes(cfg, b, 16)[SAVED_OUT] == b * 16 * h * d * 2
    # the recomputed model is the model
    plain = config(dtype=jnp.bfloat16, residual_dtype=jnp.float32)
    params, tokens = seeded(plain)

    def loss(cfg):
        return jax.value_and_grad(lambda p: multi_offset_lm_loss(
            Transformer(cfg).apply({"params": p}, tokens), tokens, 3))(params)

    (got, got_grads), (want, want_grads) = loss(cfg), loss(plain)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_chunks_divide_the_window():
    with pytest.raises(ValueError, match="ChunkSummaryAttention"):
        ChunkSummaryAttention(heads=4, head_dim=8, window=16, chunk=5)
