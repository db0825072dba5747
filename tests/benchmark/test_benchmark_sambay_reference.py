"""What is particular to ``sambay_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model): it takes nothing of the
path under test; each kind of mixer alone; the recurrence is a scan
over single positions whatever the block of positions; the memory is
taken before the gate and ``lambda_init`` from the published index; the
loss after one AdamW step; each way of getting it wrong comes out as not
correct; and a scan state held in bfloat16 fails the family's forward
limit where it can be seen, on a Mamba block's output (the harness's
check reads two losses, which a state's rounding moves by 2e-6 to 8e-6
at the toy: under any limit that bfloat16 activations pass)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "phi4_mini_flash-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, seed=5):
    """Parameters off the symmetric start, a batch."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    return params, extra, family.make_batch(config, job, key, 2)


def test_reference_uses_no_kernel_no_chunked_scan_no_convolution():
    with open(os.path.join(BENCH, "models", "sambay_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _layer_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("horovod_tpu", "pallas", "selective_scan", "causal_taps",
                 "reference_attention", "conv_general", "jnp.convolve",
                 "jnp.pad", "associative_scan", "cumsum"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    # the recurrence one position at a time: a scan whose step takes the
    # state and ONE position's c, delta, B, C; the taps a sum of shifted
    # arrays; the masks made from indices
    assert re.search(r"def step\(h, x\):\s+c_t, delta_t, b_t, c2_t = x", code)
    assert "jax.lax.scan(step, h, xs)" in code
    assert re.search(r"for j in range\(taps\):", code)
    assert "jnp.where(allowed" in code and "jnp.arange(t)" in code


def test_the_toy_has_every_kind_of_layer_in_the_published_order(cell):
    config = cell.config
    assert cell.family._layers(config) == [
        "mamba", "sliding_attention", "mamba", "full_attention",
        "memory_unit", "cross_attention"]
    assert config["layers_here"]["first"] == 14
    assert config["sliding_window"] < cell.job["seq_len"]
    assert config["num_attention_heads"] // config["num_key_value_heads"] == 2


@pytest.mark.parametrize("layer", range(6), ids=[
    "mamba", "sliding", "mamba_publishes", "full", "memory_unit", "cross"])
def test_each_kind_of_block_alone_against_the_reference(cell, layer):
    """The program's block with the layer's spec on one block's weights,
    handed a memory and keys, against the reference's ``_block``: the
    output and what it hands on."""
    from horovod_tpu.models.transformer import Block

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    d, dim = config["hidden_size"], family._head_dim(config)
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(keys[0], (2, 32, d))
    memory = jax.random.normal(keys[1], (2, 32, config["mamba"]["d_inner"]))
    k, v = (jax.random.normal(key, (2, 32, config["num_key_value_heads"] // 2,
                                    2, dim)) for key in keys[2:])
    w = params[f"block_{layer}"]
    program = family._program_config(config).at(layer)
    published = config["layers_here"]["first"] + layer
    with jax.default_matmul_precision("highest"):
        got, shared = Block(program).apply(
            {"params": w}, x, None, {"memory": memory, "keys": (k, v)})
        want = [family._block(
            x[i], memory[i],
            tuple(jnp.moveaxis(u[i], 0, 2) for u in (k, v)), w, published,
            config, None) for i in range(2)]
    np.testing.assert_allclose(got, jnp.stack([o[0] for o in want]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(shared["memory"],
                               jnp.stack([o[1] for o in want]), rtol=2e-4,
                               atol=2e-5)
    for a, b in zip(shared["keys"], zip(*(o[2] for o in want))):
        np.testing.assert_allclose(
            a, jnp.moveaxis(jnp.stack(b), 3, 1), rtol=2e-4, atol=2e-5)
    # only the two publishers change what travels
    assert (shared["memory"] is memory) == (layer != 2)
    assert (shared["keys"][0] is k) == (layer != 3)


def test_the_recurrence_does_not_depend_on_its_block_of_positions(cell):
    family = cell.family
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    c, delta = (jax.random.normal(k, (32, 8)) for k in keys[:2])
    b, c2 = (jax.random.normal(k, (32, 4)) for k in keys[2:4])
    a = -jnp.exp(jax.random.normal(keys[4], (8, 4)))
    args = (c, jax.nn.softplus(delta), a, b, c2, jnp.ones((8,)),
            jnp.float32)
    whole = family._recurrence(*args)
    was = family.SCAN_BLOCK
    try:
        family.SCAN_BLOCK = 8
        blocks = family._recurrence(*args)
        grads = jax.grad(lambda c: jnp.sum(family._recurrence(
            c, *args[1:]) ** 2))(c)
    finally:
        family.SCAN_BLOCK = was
    np.testing.assert_array_equal(whole, blocks)
    np.testing.assert_allclose(grads, jax.grad(lambda c: jnp.sum(
        family._recurrence(c, *args[1:]) ** 2))(c), rtol=1e-6)


def test_loss_after_one_adamw_step(cell):
    """Forward-backward, one float32 AdamW step, forward: program and
    reference, loss and two steps."""
    family, config = cell.family, cell.config
    params, extra, batch = seeded(cell)
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


def off_by(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("layer", [0, 2], ids=["mamba", "mamba_publishes"])
def test_a_scan_state_held_in_bfloat16_fails_the_forward_limit(cell, layer):
    """The program's Mamba mixer against the reference's on the seeded
    weights: within a hundredth of the family's forward limit as it is,
    and outside the limit when the reference's recurrence carries its
    state in bfloat16 and all else stays float32 (the mixer's output
    and the memory it publishes), at step sizes where the state's part
    of ``y`` outweighs ``D c`` (the step-size bias + 4; at Mamba's own
    start of 0.001 to 0.1 the state is a small part of ``y`` and its
    rounding reads 1.1e-4).  So a program whose scan held a bfloat16
    state is told from the reference by the limit the cell is held to,
    on the mixer; on the loss it is not (the file's docstring)."""
    from horovod_tpu.models.transformer import SelectiveScanMixer

    family, config = cell.family, cell.config
    limit = family.TOLERANCE["forward"]
    params, _ = family.init(config, cell.job, jax.random.PRNGKey(5))
    w = params[f"block_{layer}"]["mixer"]
    w = {**w, "dt_bias": w["dt_bias"] + 4.0}
    x = jax.random.normal(jax.random.PRNGKey(8),
                          (2, 32, config["hidden_size"]))
    program = family._program_config(config).at(layer)
    with jax.default_matmul_precision("highest"):
        got = SelectiveScanMixer(program).apply({"params": w}, x)
        for perturb, holds in ((None, True), ("scan_state_bfloat16", False)):
            want = [family._mamba(s, w, config, perturb) for s in x]
            for mine, theirs in zip(got, zip(*want)):
                err = off_by(mine, jnp.stack(theirs))
                assert (err < limit / 100) if holds else (err > limit), err


@pytest.mark.parametrize("perturb", [
    "bfloat16", "memory_after_gate", "lambda_depth"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """Through ``run_cell`` on the toy, within the family's own
    tolerances: the reference in bfloat16, one that publishes the memory
    after the gate, one that counts ``lambda_init``'s depth from the
    first layer here."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
