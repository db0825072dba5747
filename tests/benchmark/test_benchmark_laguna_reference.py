"""What is particular to ``laguna_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model): it takes nothing of the
path under test; each kind of attention layer alone; YaRN's table by
hand; the shares of the experts add up to the uncut layer; the bias
moves by the rule on both sides; the loss after one AdamW step; and
each way of getting it wrong comes out as not correct."""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import benchmark_toy
from benchmark_toy import BENCH, REPO, bench, load_json, toy_root  # noqa: F401

CELL = "laguna_s_2_1-spmd-1chip"


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


def test_reference_uses_no_sort_no_top_k_no_grouped_product_no_kernel():
    with open(os.path.join(BENCH, "models", "laguna_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("sort(", "top_k", "ragged", "horovod_tpu", "pallas",
                 "reference_attention"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    assert re.search(r"jax\.lax\.scan\(\s*add_expert", code)
    # every mask an explicit where
    assert "jnp.where(allowed" in code


def test_the_toy_has_both_kinds_of_layer_and_windows_that_bite(cell):
    config, job = cell.config, cell.job
    assert cell.family._layers(config) == [
        ("full_attention", 4), ("sliding_attention", 6),
        ("sliding_attention", 6), ("sliding_attention", 6),
        ("full_attention", 4)]
    assert config["sliding_window"] < job["seq_len"]
    assert config["num_key_value_heads"] == 2


@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding"])
def test_each_kind_of_attention_alone_against_the_reference(cell, layer):
    """The program's ``Attention`` with the layer's spec of the pattern
    on one block's weights against the reference's ``_attention``."""
    from horovod_tpu.models.transformer import Attention

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params[f"block_{layer}"]["attn"]
    kind, heads = family._layers(config)[layer]
    assert w["q"]["kernel"].shape[1] == heads
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 32, config["hidden_size"]))
    program = family._program_config(config).at(layer)
    assert (program.block.attention.window is None) == (
        kind == "full_attention")
    got = Attention(program).apply({"params": w}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([family._attention(s, w, kind, config, None)
                          for s in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_yarn_table_against_the_formula_by_hand():
    """The published recipe of a full layer (dim 64 of a head's 128,
    base 500000, factor 128, original length 8192, beta 32 / 1): the
    ramp runs from frequency 9 to frequency 18; below it a frequency is
    as it is, above it divided by 128, between them mixed linearly;
    program and reference give the same 32 numbers."""
    from horovod_tpu.models.transformer import Rotary, rotary_table

    config = load_json(os.path.join(
        REPO, "benchmark", "configs", "laguna_s_2_1.json"))
    recipe = config["rope_parameters"]["full_attention"]
    family = benchmark_toy.load_by_path(
        os.path.join(BENCH, "models", "laguna_lm.py"), "hvd_laguna_yarn")
    reference, factor = family._frequencies(recipe, 64)
    program, program_factor = rotary_table(Rotary(
        theta=500000.0, fraction=0.5, factor=128, original_len=8192,
        beta_fast=32, beta_slow=1,
        attention_factor=recipe["attention_factor"]), 64)
    assert factor == program_factor == 1.4852030263919618
    np.testing.assert_allclose(program, reference, rtol=1e-6)

    def turning(beta):
        return 64 * math.log(8192 / (2 * math.pi * beta)) / (
            2 * math.log(500000))

    assert math.floor(turning(32)) == 9 and math.ceil(turning(1)) == 18
    by_hand = {
        4: 500000 ** (-8 / 64),                       # below the ramp
        12: 500000 ** (-24 / 64) * (1 - 3 / 9 + 3 / 9 / 128),  # a third up
        25: 500000 ** (-50 / 64) / 128}               # above it
    for i, want in by_hand.items():
        assert float(reference[i]) == pytest.approx(want, rel=1e-5)
    # the sliding layers' table is the plain one over the whole head
    plain, one = family._frequencies(
        config["rope_parameters"]["sliding_attention"], 128)
    assert one == 1.0
    assert float(plain[5]) == pytest.approx(10000 ** (-10 / 128), rel=1e-6)


def test_half_a_head_is_turned_and_scaled_and_half_passes():
    from horovod_tpu.models.transformer import Rotary, rotate

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    turned = rotate(x, Rotary(theta=500000.0, fraction=0.5, factor=4,
                              original_len=4, attention_factor=1.5))
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    # position 0: the angle is 0, cos times the factor
    np.testing.assert_allclose(turned[0, 0, :, :8], 1.5 * x[0, 0, :, :8],
                               rtol=1e-6)
    whole = rotate(x, Rotary(theta=10000.0))
    np.testing.assert_allclose(
        jnp.sum(whole ** 2, -1), jnp.sum(x ** 2, -1), rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_references_layer(cell):
    """Every device's ``topk_moe(held=(4 r, 4))`` over the router's 16
    outputs, with the shared expert counted once, against the
    reference's layer given ALL 16 experts."""
    from horovod_tpu.parallel.moe import init_moe_params, topk_moe

    family, config = cell.family, cell.config
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    outputs, k = config["router_outputs"], config["num_experts_per_tok"]
    count = config["experts_held"]["count"]
    key = jax.random.PRNGKey(7)
    h = jax.random.normal(key, (64, d))
    bias = 0.1 * jax.random.normal(key, (outputs,))
    experts = init_moe_params(key, d, width, outputs, gated=True)
    shared = {name: {"kernel": jax.random.normal(k2, shape) / 8}
              for name, shape, k2 in zip(
                  ("gate", "up", "down"),
                  ((d, width), (d, width), (width, d)),
                  jax.random.split(key, 3))}
    whole = dict(config, experts_held={"first": 0, "count": outputs})
    w = {"router_kernel": experts["router"]["kernel"], "shared": shared,
         **{f"{n}_kernel": experts[n]["kernel"] for n in ("wg", "wi", "wo")}}
    with jax.default_matmul_precision("highest"):
        want, want_counts = family._experts(h, w, bias, whole, None)
        got = family._swiglu(h, shared)  # once, not once a device
    for first in range(0, outputs, count):
        held = {"router": experts["router"], **{
            n: {"kernel": experts[n]["kernel"][first:first + count]}
            for n in ("wg", "wi", "wo")}}
        part, aux = topk_moe(
            h, held, k=k, held=(first, count), scoring="sigmoid",
            bias=bias, renormalize=True,
            scale=config["moe_routed_scaling_factor"])
        got = got + part
        np.testing.assert_array_equal(aux["tokens_per_expert"], want_counts)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


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


@pytest.mark.parametrize("perturb", ["bfloat16", "moe_routed_scaling_factor",
                                     "gating", "sliding_window"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """Through ``run_cell`` on the toy: the reference in bfloat16, one
    without the factor 2.5 on the weights, one without the gate, one
    whose sliding layers see every key before the query."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
