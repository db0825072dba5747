"""What is particular to ``joyai_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model): it takes nothing of the
path under test; latent attention alone; the shares of the experts add
up to the uncut layer; the bias moves by the rule on both sides; the
loss after one AdamW step; and each way of getting it wrong comes out
as not correct."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import benchmark_toy
from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "joyai_llm_flash-spmd-1chip"


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
    with open(os.path.join(BENCH, "models", "joyai_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("sort(", "top_k", "ragged", "horovod_tpu", "pallas"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    assert re.search(r"jax\.lax\.scan\(\s*add_expert", code)


def test_latent_attention_alone_against_the_references(cell):
    """(b) the program's ``Attention`` with the latent spec on one
    block's weights against the reference's ``_latent_attention``."""
    from horovod_tpu.models.transformer import Attention, RMSNorm

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 32, config["hidden_size"]))
    program = family._program_config(config)
    normed = RMSNorm(eps=config["rms_norm_eps"]).apply(
        {"params": w["ln1"]}, x)
    got = x + Attention(program).apply({"params": w["attn"]}, normed)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([family._latent_attention(s, w, config)
                          for s in x])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_references_layer(cell):
    """(c) every device's ``topk_moe(held=(4 r, 4))`` over the router's
    16 outputs, with the shared expert counted once, against the
    reference's layer given ALL 16 experts."""
    from horovod_tpu.parallel.moe import init_moe_params, topk_moe

    family, config = cell.family, cell.config
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    outputs, k = config["router_outputs"], config["num_experts_per_tok"]
    count = config["experts_held"]["count"]
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (64, d))
    bias = 0.1 * jax.random.normal(key, (outputs,))
    experts = init_moe_params(key, d, width, outputs, gated=True)
    shared = {name: {"kernel": jax.random.normal(k2, shape) / 8}
              for name, shape, k2 in zip(
                  ("gate", "up", "down"),
                  ((d, width), (d, width), (width, d)),
                  jax.random.split(key, 3))}
    whole = dict(config, experts_held={"first": 0, "count": outputs})
    w = {"ln2": {"scale": jnp.ones((d,))},
         "moe": {"router_kernel": experts["router"]["kernel"],
                 "shared": shared,
                 **{f"{n}_kernel": experts[n]["kernel"]
                    for n in ("wg", "wi", "wo")}}}
    with jax.default_matmul_precision("highest"):
        want, want_counts = family._experts(x, w, bias, whole, None)
        h = family._rms_norm(x, w["ln2"]["scale"], config["rms_norm_eps"])
        got = x + family._swiglu(h, shared)  # once, not once a device
    for first in range(0, outputs, count):
        held = {"router": experts["router"], **{
            n: {"kernel": experts[n]["kernel"][first:first + count]}
            for n in ("wg", "wi", "wo")}}
        part, aux = topk_moe(
            h, held, k=k, held=(first, count), scoring="sigmoid",
            bias=bias, renormalize=True,
            scale=config["routed_scaling_factor"])
        got = got + part
        np.testing.assert_array_equal(aux["tokens_per_expert"], want_counts)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_bias_moves_by_the_rule_on_both_sides(cell):
    """(f) program and reference return the same moved bias: up by the
    rate where an output had fewer token-slots than the mean, down
    where more, in every row (the toy's two expert layers and the
    module's)."""
    family, config = cell.family, cell.config
    params, extra, batch = seeded(cell)
    _, got = family.loss(config, params, extra, batch)
    _, want = family.reference_loss(config, params, extra, batch)
    np.testing.assert_allclose(got["router_bias"], want["router_bias"],
                               rtol=1e-6)
    step = np.asarray(got["router_bias"] - extra["router_bias"])
    assert step.shape == (3, config["router_outputs"])
    rate = config["job"]["bias_update_rate"]
    assert set(np.round(np.unique(step) / rate).astype(int)) <= {-1, 0, 1}
    assert np.all(np.abs(step).sum(-1) > 0)
    # no gradient reaches it: the loss does not depend on it smoothly
    grad = jax.grad(lambda b: family.loss(
        config, params, {"router_bias": b}, batch)[0])(extra["router_bias"])
    assert not np.any(np.asarray(grad))


def test_loss_after_one_adamw_step_with_the_updated_bias(cell):
    """(a) forward-backward, one float32 AdamW step, forward: program
    and reference each with the bias its own first step left."""
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


@pytest.mark.parametrize("perturb", ["bfloat16", "routed_scaling_factor",
                                     "scoring_func"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """(g) through ``run_cell`` on the toy: the reference in bfloat16,
    one without the factor 2.5 on the weights, one that scores by
    softmax for sigmoid."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
