"""Each family's plain reference against the program's model at a toy
size, and the check that holds a cell to it."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_toy import bench, toy_root  # noqa: F401

CELL_OF = {"transformer_lm": "gpt2_medium-spmd-1chip",
           "resnet": "resnet50_v15-spmd-1chip"}
# a term the reference gets wrong on purpose, per family
PERTURB = {"transformer_lm": "gelu", "resnet": "relu"}


@pytest.mark.parametrize("family_name", sorted(CELL_OF))
def test_reference_matches_program_model(family_name, bench, toy_root):
    """Loss and every gradient leaf, float32 on both sides."""
    cell = bench.load_cell(toy_root, CELL_OF[family_name])
    family, config, job = cell.family, cell.config, cell.job
    assert config["family"] == family_name
    key = jax.random.PRNGKey(3)
    params, extra = family.init(config, job, key)
    # off the symmetric start (zero biases, zero-scaled residuals)
    leaves, tree = jax.tree.flatten(params)
    noise = jax.random.split(key, len(leaves))
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, noise)])
    batch = family.make_batch(config, job, key, family.CHECK_GROUP)

    def program(p):
        return family.loss(config, p, extra, batch)[0]

    def reference(p):
        return family.reference_loss(config, p, extra, batch)[0]

    got, got_grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(reference)(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3,
                                   atol=2e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("family_name", sorted(CELL_OF))
def test_check_fails_on_a_perturbed_reference(family_name, bench, toy_root):
    """The same run is ``correct`` against the reference and not against
    one with a term changed: the comparison can see a term."""
    cell = bench.load_cell(toy_root, CELL_OF[family_name])
    lines = []
    result = bench.run_cell(
        cell, jax.devices()[:1], 0, 0.05, False, log=lines.append,
        perturb_reference=PERTURB[family_name])
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
