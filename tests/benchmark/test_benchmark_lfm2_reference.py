"""What is particular to ``lfm2_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model): it takes nothing of the
path under test; each kind of mixer alone; the eight shares of the
experts add up to the uncut layer; the bias moves by the rule on both
sides; the loss after one AdamW step; and each way of getting it wrong
comes out as not correct."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "lfm2_24b_a2b-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, seed=5):
    """Parameters off the symmetric start, the bias off zero, a batch."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    extra = {"router_bias": 0.05 * jax.random.normal(
        key, extra["router_bias"].shape)}
    return params, extra, family.make_batch(config, job, key, 2)


def test_reference_uses_no_convolution_no_sort_no_top_k_no_kernel():
    with open(os.path.join(BENCH, "models", "lfm2_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("sort(", "top_k", "ragged", "horovod_tpu", "pallas",
                 "reference_attention", "conv_general", "jnp.convolve",
                 "jnp.pad"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    assert re.search(r"jax\.lax\.scan\(\s*add_expert", code)
    # the taps an explicit sum over j of shifted arrays, the mask a where
    assert re.search(r"for j in range\(taps\):", code)
    assert "jnp.where(allowed" in code


def test_the_toy_has_both_kinds_of_mixer_in_the_published_order(cell):
    config = cell.config
    assert cell.family._layers(config) == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert config["num_dense_layers"] == 1
    assert config["num_attention_heads"] // config["num_key_value_heads"] == 2
    assert config["conv_L_cache"] == 3


@pytest.mark.parametrize("layer", [0, 1], ids=["conv", "full"])
def test_each_kind_of_mixer_alone_against_the_reference(cell, layer):
    """The program's mixer with the layer's spec of the pattern on one
    block's weights against the reference's ``_conv`` / ``_attention``."""
    from horovod_tpu.models.transformer import Attention, ShortConvMixer

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 32, config["hidden_size"]))
    program = family._program_config(config).at(layer)
    with jax.default_matmul_precision("highest"):
        if family._layers(config)[layer] == "conv":
            w = params[f"block_{layer}"]["mixer"]
            got = ShortConvMixer(program).apply({"params": w}, x)
            want = jnp.stack([family._conv(s, w, config, None) for s in x])
        else:
            w = params[f"block_{layer}"]["attn"]
            got = Attention(program).apply({"params": w}, x)
            want = jnp.stack([family._attention(s, w, config, None)
                              for s in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_last_tap_weighs_the_position_itself(cell):
    """The reference's reading of the program's ``[taps, d]``: with only
    the last tap set the convolution is the identity on ``g``; with only
    the first, ``g`` two positions back."""
    family, config = cell.family, cell.config
    d = config["hidden_size"]
    eye = jnp.eye(d)
    h = jax.random.normal(jax.random.PRNGKey(2), (8, d))
    w_in = jnp.concatenate([eye, eye, eye], axis=1)    # b = c = gate = h
    for row, back in ((2, 0), (1, 1), (0, 2)):
        taps = jnp.zeros((3, d)).at[row].set(1.0)
        got = family._conv(h, {"in": {"kernel": w_in}, "kernel": taps,
                               "out": {"kernel": eye}}, config, None)
        g = h * h
        shifted = jnp.concatenate([jnp.zeros((back, d)), g[:8 - back]])
        np.testing.assert_allclose(got, h * shifted, rtol=1e-6, atol=1e-7)


def test_the_eight_shares_add_up_to_the_uncut_references_layer(cell):
    """Every device's ``topk_moe(held=(count r, count))`` over the
    router's 16 outputs (the toy's four shares; the cell's eight are the
    same code at 8 of 64) against the reference's layer given ALL the
    experts; there is no shared expert to count once."""
    from horovod_tpu.parallel.moe import init_moe_params, topk_moe

    family, config = cell.family, cell.config
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    outputs, k = config["router_outputs"], config["num_experts_per_tok"]
    for count in (config["experts_held"]["count"], outputs // 8):
        key = jax.random.PRNGKey(7)
        h = jax.random.normal(key, (64, d))
        bias = 0.1 * jax.random.normal(key, (outputs,))
        experts = init_moe_params(key, d, width, outputs, gated=True)
        whole = dict(config, experts_held={"first": 0, "count": outputs})
        w = {"router_kernel": experts["router"]["kernel"],
             **{f"{n}_kernel": experts[n]["kernel"]
                for n in ("wg", "wi", "wo")}}
        with jax.default_matmul_precision("highest"):
            want, want_counts = family._experts(h, w, bias, whole)
        got = jnp.zeros_like(h)
        shares = range(0, outputs, count)
        assert len(shares) == outputs // count
        for first in shares:
            held = {"router": experts["router"], **{
                n: {"kernel": experts[n]["kernel"][first:first + count]}
                for n in ("wg", "wi", "wo")}}
            part, aux = topk_moe(
                h, held, k=k, held=(first, count), scoring="sigmoid",
                bias=bias, renormalize=True,
                scale=float(config["routed_scaling_factor"]))
            got = got + part
            np.testing.assert_array_equal(aux["tokens_per_expert"],
                                          want_counts)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_weights_of_a_tokens_four_add_up_to_one(cell):
    """``norm_topk_prob`` with scale 1: summed over ALL outputs the
    reference's weights of a token are 1, whatever the bias chose."""
    family, config = cell.family, cell.config
    outputs = config["router_outputs"]
    d = config["hidden_size"]
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (16, d))
    whole = dict(config, experts_held={"first": 0, "count": outputs})
    # experts that return their weight in column 0: wo picks it up
    ones = jnp.ones((outputs, d, 4)) / d
    w = {"router_kernel": jax.random.normal(key, (d, outputs)),
         "wg_kernel": ones, "wi_kernel": ones,
         "wo_kernel": jnp.ones((outputs, 4, d))}
    out, counts = family._experts(h, w, jnp.zeros((outputs,)), whole)
    assert int(counts.sum()) == 16 * config["num_experts_per_tok"]
    m = jnp.mean(h, -1)
    same = 4 * jax.nn.silu(m) * m          # every expert's output
    np.testing.assert_allclose(out[:, 0], same, rtol=1e-4, atol=1e-6)


def test_the_bias_moves_by_the_rule_on_both_sides(cell):
    family, config = cell.family, cell.config
    params, extra, batch = seeded(cell)
    _, got = family.loss(config, params, extra, batch)
    _, want = family.reference_loss(config, params, extra, batch)
    np.testing.assert_allclose(got["router_bias"], want["router_bias"],
                               rtol=1e-6)
    step = np.asarray(got["router_bias"] - extra["router_bias"])
    assert step.shape == (4, config["router_outputs"])
    rate = config["job"]["bias_update_rate"]
    assert set(np.round(np.unique(step) / rate).astype(int)) <= {-1, 0, 1}
    assert np.all(np.abs(step).sum(-1) > 0)


def test_loss_after_one_adamw_step_with_the_updated_bias(cell):
    """Forward-backward, one float32 AdamW step, forward: program and
    reference each with the bias its own first step left."""
    family, config = cell.family, cell.config
    params, extra, batch = seeded(cell)
    opt = optax.adamw(**cell.job["optimizer"]["args"])

    def two_steps(loss):
        (first, moved), grads = jax.value_and_grad(
            lambda p: loss(config, p, extra, batch), has_aux=True)(params)
        updates, _ = opt.update(grads, opt.init(params), params)
        second, _ = loss(config, optax.apply_updates(params, updates),
                         moved, batch)
        return first, second

    got = jax.jit(lambda: two_steps(family.loss))()
    want = jax.jit(lambda: two_steps(family.reference_loss))()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert float(got[1]) < float(got[0])


@pytest.mark.parametrize("perturb", ["bfloat16", "tap_order", "head_norm"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """Through ``run_cell`` on the toy: the reference in bfloat16, one
    that reads the taps back to front, one without the norm on the heads
    of q and k."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
