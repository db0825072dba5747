"""What is particular to ``ouro_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model at the toy's two passes): it
takes nothing of the path under test; loss and every gradient leaf for
1, 2 and 4 passes; the counters the step carries; the loss after one
AdamW step; and each way of getting it wrong comes out as not correct."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "ouro_2_6b-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, passes, seed=5):
    """The toy configuration at ``passes`` passes, parameters off the
    symmetric start (norm scales of 1, a gate bias of 0), a batch."""
    family, job = cell.family, cell.job
    config = dict(cell.config, total_ut_steps=passes)
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    return config, params, extra, family.make_batch(config, job, key, 2)


def test_reference_takes_nothing_of_the_program_and_no_kernel():
    with open(os.path.join(BENCH, "models", "ouro_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("horovod_tpu", "pallas", "flax", "softmax_xent",
                 "looped_lm_loss", "log_sigmoid"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    # ONE set of weights, run total_ut_steps times
    assert "jax.lax.scan(\n            one_pass" in code


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_loss_and_every_gradient_leaf_whatever_the_passes(passes, cell):
    family = cell.family
    config, params, extra, batch = seeded(cell, passes)
    (got, got_extra), got_grads = jax.value_and_grad(
        lambda p: family.loss(config, p, extra, batch), has_aux=True)(params)
    (want, want_extra), want_grads = jax.value_and_grad(
        lambda p: family.reference_loss(config, p, extra, batch),
        has_aux=True)(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=str(path))
    # the counters the step carries: a distribution over the exits and
    # each exit's mean cross-entropy, the same on both sides
    assert set(got_extra) == set(want_extra) == {"exit_probability",
                                                 "exit_losses"}
    for name in got_extra:
        assert got_extra[name].shape == (passes,)
        np.testing.assert_allclose(got_extra[name], want_extra[name],
                                   rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(got_extra["exit_probability"]), 1.0,
                               rtol=1e-6)
    # the last pass's gate is not read; with one pass the gate is idle
    gate = got_grads["exit_gate"]["kernel"]
    assert bool(jnp.any(gate != 0)) == (passes > 1)


def test_loss_after_one_adamw_step(cell):
    """Forward-backward, one float32 AdamW step, forward: what the
    harness's check compares on the chip."""
    family = cell.family
    config, params, extra, batch = seeded(cell, 4)
    opt = optax.adamw(**cell.job["optimizer"]["args"])

    def two_steps(loss):
        first, grads = jax.value_and_grad(
            lambda p: loss(config, p, extra, batch)[0])(params)
        updates, _ = opt.update(grads, opt.init(params), params)
        second, _ = loss(config, optax.apply_updates(params, updates),
                         extra, batch)
        return first, second

    got = jax.jit(lambda: two_steps(family.loss))()
    want = jax.jit(lambda: two_steps(family.reference_loss))()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert float(got[1]) < float(got[0])


@pytest.mark.parametrize("perturb", ["bfloat16", "beta", "total_ut_steps"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """Through ``run_cell`` on the toy: the reference in bfloat16, one
    without the entropy's term, one with a pass fewer."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]


def test_the_published_cut_in_the_configuration_file(bench):
    """Every key of the catalog's ``config`` at its value but
    ``num_hidden_layers``; the job is one sequence of 4096."""
    from benchmark_toy import REPO

    cell = bench.load_cell(REPO, CELL)
    config = cell.config
    catalog = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, layer_types=["full_attention"] * 48,
        max_position_embeddings=65536, max_window_layers=48,
        model_type="ouro", num_attention_heads=16, num_hidden_layers=48,
        num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152)
    differs = {k for k, v in catalog.items() if config[k] != v}
    assert differs == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 48
    assert cell.job["per_chip_batch"] == 1 and cell.job["seq_len"] == 4096
    assert cell.chips == 1 and cell.traffic["loop"] == "spmd"
    program = cell.family._program_config(config)
    assert program.passes == 4 and program.exit_gate and program.remat
    assert program.block.norm_placement == "sandwich"
    assert (program.n_layers, program.n_heads, program.head_dim) == (
        8, 16, 128)
