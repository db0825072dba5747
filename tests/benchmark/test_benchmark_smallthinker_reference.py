"""What is particular to ``smallthinker_lm``'s plain reference, beyond
what ``test_benchmark_references.py`` holds every family to (loss and
every gradient leaf against the program's model): it takes nothing of
the path under test; each kind of attention layer alone; the router
reads the block's input; the four shares of the experts add up to the
uncut layer; the loss after one AdamW step; and each way of getting it
wrong comes out as not correct."""

import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark_toy import BENCH, bench, toy_root  # noqa: F401

CELL = "smallthinker_21b_a3b-spmd-1chip"


@pytest.fixture(scope="module")
def cell(bench, toy_root):
    return bench.load_cell(toy_root, CELL)


def seeded(cell, seed=5):
    """Parameters off the symmetric start and a batch."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(seed)
    params, extra = family.init(config, job, key)
    leaves, tree = jax.tree.flatten(params)
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves)))])
    return params, extra, family.make_batch(config, job, key, 2)


def test_reference_uses_no_sort_no_top_k_no_grouped_product_no_kernel():
    with open(os.path.join(BENCH, "models", "smallthinker_lm.py")) as f:
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


def test_the_toy_has_two_global_layers_and_windows_that_bite(cell):
    config, job = cell.config, cell.job
    assert cell.family._layers(config) == [
        (False, False), (True, True), (True, True), (True, True)] * 2
    assert cell.family._period(config) == cell.family._layers(config)[:4]
    assert 2 * config["sliding_window_size"] < job["seq_len"]
    assert (config["num_attention_heads"], config["num_key_value_heads"]) == (
        6, 2)
    program = cell.family._program_config(config)
    assert [program.at(i).block.attention.rotary is None
            for i in range(4)] == [True, False, False, False]
    assert [program.at(i).block.attention.window for i in range(4)] == [
        None, 16, 16, 16]
    assert program.at(0).block.ffn.route_from == "input"
    assert program.at(0).block.ffn.activation == "relu"


def test_init_draws_the_embedding_at_unit_variance(cell):
    """The one thing the family's ``init`` changes of the program's own
    draw: at flax's ``1 / d`` a token's row is small beside what the
    branches hand back, the stream a router reads is then attention's
    running mean, and the rows the held experts get (and with them the
    cell's step time) are a draw of the seed (PERF.md section 6, PR 53).
    Every other leaf is as the program draws it."""
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(11)
    params, extra = family.init(config, job, key)
    assert extra == {}
    tokens = jnp.zeros((1, job["seq_len"]), jnp.int32)
    plain = family._model(config).init(key, tokens)["params"]
    d = config["hidden_size"]
    embedding = params["embed"].pop("embedding")
    np.testing.assert_allclose(
        embedding, plain["embed"].pop("embedding") * np.sqrt(d), rtol=1e-6)
    assert float(jnp.std(embedding)) == pytest.approx(1.0, rel=0.05)
    jax.tree.map(np.testing.assert_array_equal, params, plain)


@pytest.mark.parametrize("layer", [0, 1], ids=["global", "window"])
def test_each_kind_of_attention_alone_against_the_reference(cell, layer):
    """The program's ``Attention`` with the layer's spec of the pattern
    on one block's weights against the reference's ``_attention``."""
    from horovod_tpu.models.transformer import Attention

    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params[f"block_{layer}"]["attn"]
    rotated, windowed = family._layers(config)[layer]
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 40, config["hidden_size"]))
    program = family._program_config(config).at(layer)
    got = Attention(program).apply({"params": w}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([family._attention(s, w, rotated, windowed, config,
                                            None) for s in x])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_references_router_reads_the_blocks_input(cell):
    """``_block`` with the router's kernel turned gives another result
    only through ``entered @ router``: with ``perturb="router_input"``
    it reads the second norm's output and gives a third."""
    family, config = cell.family, cell.config
    params, _, _ = seeded(cell)
    w = params["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(8),
                          (1, 40, config["hidden_size"]))
    k = config["moe_num_active_primary_experts"]
    with jax.default_matmul_precision("highest"):
        got = family._block(x, w, True, True, config, None)
        behind = family._block(x, w, True, True, config, "router_input")
        weight = family._decide(x[0] @ w["moe"]["router_kernel"], k)
    assert float(jnp.max(jnp.abs(got - behind))) > 1e-3
    # k non-zero weights a token that add up to 1, the softmax over the
    # k chosen logits
    assert np.all(np.sum(np.asarray(weight) > 0, -1) == k)
    np.testing.assert_allclose(jnp.sum(weight, -1), 1.0, rtol=1e-6)
    logits = np.asarray(x[0] @ w["moe"]["router_kernel"])
    for t in (0, 17):
        chosen = np.argsort(-logits[t])[:k]
        want = np.exp(logits[t][chosen] - logits[t][chosen].max())
        np.testing.assert_allclose(np.asarray(weight)[t][chosen],
                                   want / want.sum(), rtol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_references_layer(cell):
    """Every device's ``topk_moe(held=(4 r, 4))`` over the router's 16
    outputs, each handed the decision made ahead from the block's input
    (``route_tokens`` on ``x``, not on what the experts read), against
    the reference's layer given ALL 16 experts."""
    from horovod_tpu.parallel.moe import (init_moe_params, route_tokens,
                                          topk_moe)

    family, config = cell.family, cell.config
    d, width = config["hidden_size"], config["moe_ffn_hidden_size"]
    outputs = config["router_outputs"]
    k, count = (config["moe_num_active_primary_experts"],
                config["experts_held"]["count"])
    assert outputs == config["experts_held"]["of_chips"] * count
    key = jax.random.PRNGKey(7)
    entered = jax.random.normal(key, (64, d))          # the block's input
    h = jax.random.normal(jax.random.PRNGKey(8), (64, d))  # what experts read
    experts = init_moe_params(key, d, width, outputs, gated=True)
    whole = dict(config, experts_held={"first": 0, "count": outputs})
    w = {f"{n}_kernel": experts[n]["kernel"] for n in ("wg", "wi", "wo")}
    with jax.default_matmul_precision("highest"):
        weight = family._decide(entered @ experts["router"]["kernel"], k)
        want = family._experts(h, weight, w, whole, None)
    decision = route_tokens(entered, experts["router"]["kernel"], k,
                            scoring="softmax", renormalize=True)
    got = jnp.zeros_like(h)
    for first in range(0, outputs, count):
        held = {"router": experts["router"], **{
            n: {"kernel": experts[n]["kernel"][first:first + count]}
            for n in ("wg", "wi", "wo")}}
        part, aux = topk_moe(h, held, k=k, held=(first, count),
                             activation="relu", decision=decision)
        got = got + part
        np.testing.assert_array_equal(
            aux["tokens_per_expert"], jnp.sum(weight > 0, 0))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 1e-2


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
    finds on the toy cell's own compiled step (built once, at one
    period's depth: every kind of layer, half the toy's compile)
    against the reference with ``perturb``."""
    cell = types.SimpleNamespace(**{**vars(cell), "config": {
        **cell.config, "num_hidden_layers": 4}})
    run = bench.Run(cell, jax.devices()[:1], 0, 0.05)
    loop = cell.loop.build(run)

    def check(perturb):
        run.perturb_reference = perturb
        return bench.check_against_reference(run, loop)

    return check


@pytest.mark.parametrize("perturb", [
    None, "bfloat16", "window_edge", "global_rope", "router_input", "silu",
    "kv_group"])
def test_check_fails_on_each_way_of_getting_it_wrong(perturb, checked):
    """The harness's own comparison on the toy's step: correct against
    the reference as it is; not against the reference in bfloat16, one
    whose window layers see one key more, one that turns the global
    layers' q and k, one whose router reads what the experts read
    (where every other family's does), one whose experts gate by SiLU,
    one whose query heads read key-value head ``h % 2``."""
    failures = checked(perturb)
    if perturb is None:
        assert failures == []
    else:
        assert failures and all("off the reference" in f for f in failures)
