"""What is particular to ``evabyte_lm``'s plain reference, beyond what
``test_benchmark_references.py`` holds every family to (loss and every
gradient leaf against the program's model): it takes nothing of the path
under test; one block alone; its attention against a loop over single
queries written out here; the loss after one AdamW step; each way of
getting it wrong comes out as not correct; and a residual stream rounded
to bfloat16 is outside the family's forward limit where it can be seen, on
a block's output (the harness's check reads two mean losses, which the
stream's rounding moves by less than bfloat16 products do: on the chip
the program with a bfloat16 stream read inside the system's own range,
``evabyte_lm.py``)."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "evabyte_6_5b-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, seed=5):
    """Parameters off the symmetric start (the norms' offsets too), a
    batch."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    return params, extra, family.make_batch(config, job, key, 2)


def test_reference_uses_nothing_of_the_program_and_no_kernel():
    with open(os.path.join(BENCH, "models", "evabyte_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("horovod_tpu", "pallas", "flash", "chunk_summary",
                 "pool_chunks", "reference_attention", "logaddexp", "_lse",
                 "rotate(", "rotary"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    # one softmax over the joined keys under a mask made from indices; the
    # pooling a reshape and a softmax; eight shifted cross-entropies
    assert "jnp.concatenate([k, kt])" in code
    assert "jnp.where(allowed" in code and "jnp.arange(t)" in code
    assert "reshape(t // chunk, chunk, d)" in code
    assert "jnp.roll(tokens, -(1 + r))" in code


def test_the_toy_has_three_windows_of_four_chunks(cell):
    config, job = cell.config, cell.job
    assert job["seq_len"] == 3 * config["window_size"]
    assert config["window_size"] == 4 * config["chunk_size"]
    assert config["num_pred_heads"] == 3 and config["vocab_size"] == 23
    program = cell.family._program_config(config)
    assert program.residual_dtype == jnp.float32
    assert program.norm_unit_offset and program.head_outputs == 3
    spec = program.block.attention
    assert (spec.window, spec.chunk, spec.rotary.theta) == (16, 4, 100000.0)


def test_one_block_alone_against_the_reference(cell):
    from horovod_tpu.models.transformer import Block

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, cell.job["seq_len"], config["hidden_size"]))
    w = params["block_1"]
    with jax.default_matmul_precision("highest"):
        got, _ = Block(family._program_config(config)).apply(
            {"params": w}, x)
        want = jnp.stack([family._block(s, w, config, None) for s in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_a_head_against_a_loop_over_single_queries(cell):
    """The reference's head (blocks of query rows, one mask) against one
    query at a time with its keys gathered by hand."""
    family, config = cell.family, cell.config
    t, d, dim = cell.job["seq_len"], config["hidden_size"], 8
    window, chunk = config["window_size"], config["chunk_size"]
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    u = jax.random.normal(keys[0], (t, d))
    w_q, w_k, w_v = (jax.random.normal(k, (d, dim)) / math.sqrt(d)
                     for k in keys[1:4])
    phi, mu = (jax.random.normal(k, (dim,)) for k in keys[4:])
    was = family.SCORE_BLOCK_ROWS
    try:
        family.SCORE_BLOCK_ROWS = 16      # three blocks of rows
        got = family._head(u, w_q, w_k, w_v, phi, mu, config, None)
    finally:
        family.SCORE_BLOCK_ROWS = was
    theta = config["rope_theta"]
    q, k, v = (np.asarray(a, np.float64) for a in (
        family._rope(u @ w_q, theta), family._rope(u @ w_k, theta), u @ w_v))
    kt, vt = np.zeros((t // chunk, dim)), np.zeros((t // chunk, dim))
    for c in range(t // chunk):
        rows = slice(chunk * c, chunk * (c + 1))
        a = k[rows] @ np.asarray(phi, np.float64) / math.sqrt(dim)
        p = np.exp(a - a.max())
        p /= p.sum()
        kt[c], vt[c] = p @ k[rows] + np.asarray(mu), p @ v[rows]
    want = np.zeros((t, dim))
    for i in range(t):
        start = i // window * window
        ks = np.concatenate([k[start:i + 1], kt[:start // chunk]])
        vs = np.concatenate([v[start:i + 1], vt[:start // chunk]])
        s = ks @ q[i] / math.sqrt(dim)
        p = np.exp(s - s.max())
        want[i] = p / p.sum() @ vs
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_rope_is_the_halves_layout(cell):
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 8))
    got = np.asarray(cell.family._rope(x, 100.0))
    for t in range(5):
        for i in range(4):
            angle = t * 100.0 ** (-2 * i / 8)
            a, b = float(x[t, i]), float(x[t, i + 4])
            assert got[t, i] == pytest.approx(
                a * math.cos(angle) - b * math.sin(angle), abs=1e-5)
            assert got[t, i + 4] == pytest.approx(
                b * math.cos(angle) + a * math.sin(angle), abs=1e-5)


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


def test_a_stream_rounded_to_bfloat16_fails_the_limit_on_a_blocks_output(
        cell):
    """The program's block (float32 here, as the toy's activations are)
    against the reference's: within a hundredth of the forward limit as
    it is, outside it when the reference rounds the stream to bfloat16
    after each of the block's two sums."""
    from horovod_tpu.models.transformer import Block

    family, config = cell.family, cell.config
    limit = family.TOLERANCE["forward"]
    params, _, _ = seeded(cell)
    x = jax.random.normal(jax.random.PRNGKey(9),
                          (2, cell.job["seq_len"], config["hidden_size"]))
    w = params["block_0"]
    with jax.default_matmul_precision("highest"):
        got, _ = Block(family._program_config(config)).apply(
            {"params": w}, x)
        for perturb, holds in ((None, True), ("residual_bfloat16", False)):
            want = jnp.stack([family._block(s, w, config, perturb)
                              for s in x])
            err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            assert (err < limit / 100) if holds else (err > limit), err


@pytest.mark.parametrize("perturb", [
    "bfloat16", "visible_by_chunk", "mu_on_values", "norm_without_offset"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, bench, cell):
    """Through ``run_cell`` on the toy, within the family's own
    tolerances."""
    lines = []
    result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False,
                            log=lines.append, perturb_reference=perturb)
    assert result["correct"] is False
    assert "off the reference" in lines[-1]
