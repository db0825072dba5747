"""What is particular to ``nemotron_h_lm``'s plain reference, beyond
what ``test_benchmark_references.py`` holds every family to (loss and
every gradient leaf against the program's model): it takes nothing of
the path under test and computes the scan as the RECURRENCE; the Mamba-2
mixer and the attention layer alone; the shares of the experts with the
shared expert counted once add up to the uncut layer; the balancing bias
moves by the rule; the loss after one AdamW step; and each way of
getting it wrong comes out as not correct."""

import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "nemotron3_nano_30b_a3b-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, seed=5):
    """Parameters off the symmetric start, the bias off zero and a
    batch."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    extra = {"router_bias": 0.02 * jax.random.normal(
        key, extra["router_bias"].shape)}
    return params, extra, family.make_batch(config, job, key, 2)


def test_reference_uses_the_recurrence_and_nothing_of_the_program():
    with open(os.path.join(BENCH, "models", "nemotron_h_lm.py")) as f:
        source = f.read()
    reference = source[source.index("def _rms_norm"):]
    code = "\n".join(line.split("#")[0] for line in reference.splitlines()
                     if not line.strip().startswith(('"', "``")))
    for word in ("sort(", "top_k", "ragged", "horovod_tpu", "pallas", "ssd",
                 "cumsum", "conv_general", "reference_attention"):
        assert word not in code, word
    assert 'default_matmul_precision("highest")' in code
    # the scan is the definition: a ``lax.scan`` over single positions
    assert re.search(r"def step\(h, at\):", code)
    assert re.search(r"jax\.lax\.scan\(step, h, ats\)", code)
    assert re.search(r"jax\.lax\.scan\(\s*add_expert", code)
    assert "jnp.where(allowed" in code


def test_the_toy_has_every_kind_of_layer_and_a_state_carried_across_chunks(
        cell):
    config, job = cell.config, cell.job
    kinds = cell.family._layers(config)
    assert kinds == ["mamba2", "experts"] * 2 + [
        "mamba2", "attention", "experts", "mamba2", "experts"]
    assert job["seq_len"] == 5 * config["chunk_size"]
    program = cell.family._program_config(config)
    mixers = [program.at(i).block.attention for i in range(9)]
    ffns = [program.ffn_of(i) for i in range(9)]
    for kind, mixer, ffn in zip(kinds, mixers, ffns):
        # ONE branch a layer
        assert (mixer is None) == (kind == "experts")
        assert (ffn is None) == (kind != "experts")
    assert (mixers[0].heads, mixers[0].groups, mixers[0].chunk) == (4, 2, 8)
    assert mixers[5].rotary is None and mixers[5].window is None
    assert (mixers[5].heads, mixers[5].kv_heads) == (4, 2)
    assert (ffns[1].activation, ffns[1].gated, ffns[1].shared,
            ffns[1].shared_width) == ("relu2", False, 1, 48)
    assert (ffns[1].scoring, ffns[1].renormalize, ffns[1].scale) == (
        "sigmoid", True, 2.5)
    assert ffns[1].held == (4, 4)


def test_init_draws_the_embedding_at_unit_variance_and_shrinks_the_branches(
        cell):
    """The two things the family's ``init`` changes of the program's own
    draw: the embedding at unit variance, and the kernel that closes
    every layer's branch divided by ``sqrt(52)``, the published count of
    layers (``rescale_prenorm_residual``); with both the stream a router
    reads stays the token's own (PERF.md section 6, PR 55).  Every other
    leaf is as the program draws it, and the bias starts at zero."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(11)
    params, extra = family.init(config, job, key)
    np.testing.assert_array_equal(extra["router_bias"], np.zeros((4, 16)))
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    plain = family._model(config).init(key, tokens)["params"]
    drawn = plain["block_0"]["mixer"]["out"]["kernel"]
    d = config["hidden_size"]
    embedding = params["embed"].pop("embedding")
    np.testing.assert_allclose(
        embedding, plain["embed"].pop("embedding") * np.sqrt(d), rtol=1e-6)
    assert float(jnp.std(embedding)) == pytest.approx(1.0, rel=0.06)
    assert config["published"]["num_hidden_layers"] == 52
    shrunk = 0
    for i, kind in enumerate(family._layers(config)):
        block, was = params[f"block_{i}"], plain[f"block_{i}"]
        closing = {"mamba2": [("mixer", "out", "kernel")],
                   "attention": [("attn", "out", "kernel")],
                   "experts": [("moe", "wo_kernel"),
                               ("moe", "shared", "down", "kernel")]}[kind]
        for path in closing:
            ours, theirs = block, was
            for name in path[:-1]:
                ours, theirs = ours[name], theirs[name]
            np.testing.assert_allclose(
                ours.pop(path[-1]), theirs.pop(path[-1]) / np.sqrt(52),
                rtol=1e-6)
            shrunk += 1
    assert shrunk == 4 + 1 + 2 * 4
    jax.tree.map(np.testing.assert_array_equal, params, plain)
    mixer = params["block_0"]["mixer"]
    assert float(jnp.min(jnp.exp(mixer["A_log"]))) >= 1.0
    assert float(jnp.max(jnp.exp(mixer["A_log"]))) <= 16.0
    steps = jax.nn.softplus(mixer["dt_bias"])
    assert 0.001 <= float(jnp.min(steps)) and float(jnp.max(steps)) <= 0.1
    # a configuration that does not ask for it is left as drawn
    asked_not = dict(config, rescale_prenorm_residual=False)
    np.testing.assert_array_equal(
        family.init(asked_not, job, key)[0]["block_0"]["mixer"]["out"][
            "kernel"], drawn)


def test_the_mamba2_mixer_alone_against_the_reference(cell):
    """The program's ``Mamba2Mixer`` (the scan by chunks of 8, five
    chunks) on one block's weights against the reference's ``_mamba2``
    (the recurrence, a position at a time), values and the gradient of
    every weight."""
    from horovod_tpu.models.transformer import Mamba2Mixer

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params["block_0"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 40, config["hidden_size"]))
    ct = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    program = family._program_config(config).at(0)

    def ours(w):
        return Mamba2Mixer(program).apply({"params": w}, x)

    def theirs(w):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([family._mamba2(s, w, config, None) for s in x])

    np.testing.assert_allclose(ours(w), theirs(w), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda w: jnp.vdot(ours(w), ct))(w)
    want = jax.grad(lambda w: jnp.vdot(theirs(w), ct))(w)
    for (path, g), v in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, v, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(v))),
            err_msg=str(path))


@pytest.mark.parametrize("perturb", ["scan_state_bfloat16", "dt_bfloat16"])
def test_a_bfloat16_state_or_step_size_fails_the_mixer_alone(cell, perturb):
    """What the harness's two losses cannot see at the toy size (a
    reference whose recurrence carries its state in bfloat16, or whose
    step sizes are rounded to bfloat16, moves the toy's loss by 1e-6 and
    passes ``TOLERANCE``) the comparison of the mixer alone does: such a
    reference is off the program's float32 scan by more than three times
    the ``rtol 2e-4, atol 2e-5`` the test above holds the mixer to."""
    from horovod_tpu.models.transformer import Mamba2Mixer

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params["block_0"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 40, config["hidden_size"]))
    got = Mamba2Mixer(family._program_config(config).at(0)).apply(
        {"params": w}, x)
    with jax.default_matmul_precision("highest"):
        plain, wrong = (jnp.stack([family._mamba2(s, w, config, how)
                                   for s in x]) for how in (None, perturb))
    allowed = 2e-5 + 2e-4 * jnp.abs(plain)
    assert float(jnp.max(jnp.abs(got - plain) / allowed)) < 1
    assert float(jnp.max(jnp.abs(got - wrong) / allowed)) > 3


def test_the_attention_layer_alone_against_the_reference(cell):
    from horovod_tpu.models.transformer import Attention

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params["block_5"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 40, config["hidden_size"]))
    got = Attention(family._program_config(config).at(5)).apply(
        {"params": w}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([family._attention(s, w, config, None) for s in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        cell):
    """The model-configs guide's share test.  Every device's
    ``TopkMoeMlp`` with ``held=(4 r, 4)`` over the router's 16 outputs:
    the routed parts of the four shares, plus what every chip computes
    alike (the shared expert) counted ONCE, against the reference's
    expert layer given ALL 16 experts; and the counter is the same on
    every share."""
    import dataclasses

    from horovod_tpu.models.transformer import TopkMoeMlp
    from horovod_tpu.parallel.moe import moe_kernel_init

    family, config = cell.family, cell.config
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    outputs, count = config["router_outputs"], config["experts_held"]["count"]
    assert outputs == config["experts_held"]["of_chips"] * count
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    h = jax.random.normal(keys[0], (64, d))
    bias = 0.05 * jax.random.normal(keys[1], (outputs,))
    whole = {
        "router_kernel": moe_kernel_init(keys[2], (d, outputs)),
        "wi_kernel": moe_kernel_init(keys[3], (outputs, d, width)),
        "wo_kernel": moe_kernel_init(keys[4], (outputs, width, d)),
        "shared": {
            "up": {"kernel": moe_kernel_init(keys[5], (d, 2 * width))},
            "down": {"kernel": moe_kernel_init(keys[5], (2 * width, d))}}}
    uncut = dict(config, experts_held={"first": 0, "count": outputs})
    with jax.default_matmul_precision("highest"):
        want, counts = family._experts(h, whole, bias, uncut, None)
        shared = family._relu2(h, whole["shared"]["up"]["kernel"],
                               whole["shared"]["down"]["kernel"], None)
    program = family._program_config(config)
    spec = program.ffn_of(1)
    routed = jnp.zeros_like(h)
    for first in range(0, outputs, count):
        share = dict(whole, **{name: whole[name][first:first + count]
                               for name in ("wi_kernel", "wo_kernel")})
        out, state = TopkMoeMlp(program, dataclasses.replace(
            spec, held=(first, count))).apply(
                {"params": share}, h, bias, mutable=["intermediates"])
        np.testing.assert_array_equal(
            state["intermediates"]["moe_tokens_per_expert"][0], counts)
        # every share computes the shared expert alike: counted once
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2


def test_the_bias_moves_by_the_rule_in_program_and_reference(cell):
    family, config = cell.family, cell.config
    params, extra, batch = seeded(cell)
    _, got = family.loss(config, params, extra, batch)
    _, want = family.reference_loss(config, params, extra, batch)
    np.testing.assert_allclose(got["router_bias"], want["router_bias"],
                               rtol=1e-6)
    moved = np.asarray(got["router_bias"] - extra["router_bias"])
    rate = config["job"]["bias_update_rate"]
    assert set(np.round(np.unique(moved) / rate).astype(int)) <= {-1, 0, 1}
    assert np.any(moved > 0) and np.any(moved < 0)


def test_loss_after_one_adamw_step(cell):
    """Forward-backward, one float32 AdamW step, forward."""
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


@pytest.fixture(scope="module")
def checked(bench, cell):
    """``checked(perturb)``: the failures ``check_against_reference``
    finds on the toy cell's own compiled step (built once) against the
    reference with ``perturb``."""
    cell = types.SimpleNamespace(**vars(cell))
    run = bench.Run(cell, jax.devices()[:1], 0, 0.05)
    loop = cell.loop.build(run)

    def check(perturb):
        run.perturb_reference = perturb
        failures = bench.check_against_reference(run, loop)
        return failures, run.notes["reference_check"]["relative_error"]

    return check


@pytest.mark.parametrize("perturb", [
    None, "bfloat16", "norm_before_gate", "head_group", "kv_group", "relu",
    "routed_scaling_factor"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, checked):
    """The harness's own comparison on the toy's step, under the
    family's ``TOLERANCE``: correct against the reference as it is; not
    against the reference in bfloat16, one that norms before the gate,
    one whose heads read group ``h % 2``, one whose query heads read
    key-value head ``h % 2``, one whose experts' ReLU is not squared,
    one whose weights lack the factor 2.5.  (A bfloat16 state or step
    size in the scan alone the two losses do not see at this size: the
    mixer's own test above does.)"""
    failures, seen = checked(perturb)
    if perturb is None:
        assert failures == [], seen
    else:
        assert failures and all("off the reference" in f for f in failures), (
            perturb, seen)
