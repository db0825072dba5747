"""Family ``smallthinker_lm``'s counts of parameters, of required
operations and of the flash kernels' operations, against counts worked
on paper from the published shapes, against the products the plain
reference itself makes at the toy size, and the shape its trace reader
looks for."""

import math
import os

import jax
import pytest
from jax._src import core

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "smallthinker_21b_a3b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "smallthinker_lm.py"),
                      "hvd_benchmark_ops_smallthinker_lm")

# SmallThinker-21BA3B, matmul parameters a token meets.
# Attention, 28 query heads of 128 over 4 key-value heads:
#   q 2560 x 3584 and the output projection 3584 x 2560   = 18,350,080
#   k and v, 2 x 2560 x 512                               =  2,621,440
#                                                          = 20,971,520
# The router 2560 x 64                                    =    163,840
# An expert 3 x 2560 x 768                                =  5,898,240
# of the token's 6 the held ones, 6 x 16 / 64 = 1.5       =  8,847,360
# The head, a slice of 37,984 rows: 2560 x 37984          = 97,239,040
ATTENTION, ROUTER, EXPERT, HEAD = 20_971_520, 163_840, 5_898_240, 97_239_040
PER_TOKEN = 4 * (ATTENTION + ROUTER + EXPERT * 3 // 2) + HEAD
# attention, a sequence of 16384: the global layer uses 16384 x 16385 / 2
# pairs, a window layer 4096 x 4097 / 2 + 12288 x 4096 (a query sees
# 4096 keys, itself included; the first 4095 see fewer); a query head
# 2 x 128 for a score + 2 x 128 for the weighted sum
CAUSAL_PAIRS, WINDOW_PAIRS = 134_225_920, 58_722_304
GLOBAL_ATTENTION = 28 * 512 * CAUSAL_PAIRS
WINDOW_ATTENTION = 3 * 28 * 512 * WINDOW_PAIRS


def test_allowed_pairs():
    assert FAMILY.allowed_pairs(16384) == CAUSAL_PAIRS
    assert FAMILY.allowed_pairs(16384, 4096) == WINDOW_PAIRS
    # at 4096 a window of 4096 is plain causal attention
    assert FAMILY.allowed_pairs(4096, 4096) == FAMILY.allowed_pairs(4096)
    # by counting, windows under, at and over the length
    for t, window in ((9, 1), (9, 4), (9, 9), (9, 20), (40, 16)):
        assert FAMILY.allowed_pairs(t, window) == sum(
            1 for i in range(t) for j in range(t) if i - window < j <= i)


def test_required_operations_at_the_sizes_the_cell_runs():
    assert FAMILY._matmul_params(CONFIG) == (
        ATTENTION, ROUTER + 1.5 * EXPERT, HEAD)
    assert PER_TOKEN == 217_169_920
    assert GLOBAL_ATTENTION == 1_924_262_789_120
    assert WINDOW_ATTENTION == 2_525_528_850_432
    want = 3 * (2 * PER_TOKEN * 16384 + GLOBAL_ATTENTION + WINDOW_ATTENTION)
    assert want == 34_698_046_734_336
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_2_12_gflop_a_token_and_its_shares():
    """The issue's count, confirmed: 705.9 MFLOP a token forward, 34.7
    TFLOP a step of 16,384 tokens; the flash kernels are 38% of it, the
    head 28%, attention's projections 24%, the held experts 10%."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    assert per_token == 2_117_800_704
    forward = per_token / 3
    assert forward == pytest.approx(705.9e6, rel=1e-4)
    shares = {
        "flash": (GLOBAL_ATTENTION + WINDOW_ATTENTION) / 16384 / forward,
        "head": 2 * HEAD / forward,
        "projections": 4 * 2 * ATTENTION / forward,
        "experts": 4 * 2 * 1.5 * EXPERT / forward,
        "router": 4 * 2 * ROUTER / forward}
    assert {k: round(v, 3) for k, v in shares.items()} == {
        "flash": 0.385, "head": 0.275, "projections": 0.238,
        "experts": 0.1, "router": 0.002}
    assert sum(shares.values()) == pytest.approx(1.0)


def test_flash_operations_a_step():
    """All four layers' kernels, and the three window layers' alone:
    13.35e12 and 7.58e12 of the step's 34.7e12."""
    job = CONFIG["job"]
    assert FAMILY.flash_flops_per_step(CONFIG, job) == 3 * (
        GLOBAL_ATTENTION + WINDOW_ATTENTION) == 13_349_374_918_656
    assert FAMILY.window_flash_flops_per_step(CONFIG, job) == (
        3 * WINDOW_ATTENTION) == 7_576_586_551_296


def test_parameters_of_the_published_configuration_cut_to_the_chip():
    """656,529,920 parameters (the issue's count: a layer 115,512,320 =
    attention + router + two norms + 16 experts, embedding and head 2 x
    97,239,040, the final norm) = 10.50 GB at 16 bytes: the program's own
    tree, by ``jax.eval_shape``."""
    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    for i in range(4):
        assert count(params[f"block_{i}"]) == (
            ATTENTION + ROUTER + 2 * 2560 + 16 * EXPERT) == 115_512_320
    assert count(params) == 4 * 115_512_320 + 2 * HEAD + 2560 == 656_529_920
    assert count(params) * 16 == pytest.approx(10.50e9, rel=1e-3)
    assert extra == {}
    moe = params["block_0"]["moe"]
    assert moe["router_kernel"].shape == (2560, 64)
    assert moe["wg_kernel"].shape == moe["wi_kernel"].shape == (16, 2560, 768)
    # no array of k or v with the query heads' count
    assert params["block_1"]["attn"]["kv"]["kernel"].shape == (
        2560, 2, 4, 128)
    assert params["block_1"]["attn"]["q"]["kernel"].shape == (2560, 28, 128)


def test_every_published_width_is_in_the_file():
    catalog = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13,
        "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False}
    assert {key: CONFIG[key] for key in catalog} == catalog
    # the three keys cut, the published values beside them
    assert (CONFIG["num_hidden_layers"], CONFIG["moe_num_primary_experts"],
            CONFIG["vocab_size"]) == (4, 16, 37984)
    assert CONFIG["published"]["num_hidden_layers"] == 52
    assert CONFIG["published"]["moe_num_primary_experts"] == 64
    assert CONFIG["published"]["vocab_size"] == 4 * 37984 == 151936
    assert CONFIG["router_outputs"] == 64
    assert CONFIG["experts_held"] == {"first": 0, "count": 16, "of_chips": 4}
    assert FAMILY._layers(CONFIG) == [(False, False)] + [(True, True)] * 3
    assert FAMILY._period(CONFIG) == FAMILY._layers(CONFIG)
    for choice in ("router_input", "router", "experts", "secondary_experts",
                   "aux_loss", "rope", "optimizer", "global_batch"):
        assert CONFIG["assumed"][choice]
    # the job as the issue wrote it
    assert (CONFIG["job"]["per_chip_batch"],
            CONFIG["job"]["seq_len"]) == (1, 16384)
    assert CONFIG["job"]["optimizer"]["args"] == {
        "learning_rate": 1e-5, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1}


def test_trace_shapes_are_the_query_shape_of_both_kinds():
    """And, for ``latent_trace.py``, the token-slots and the router's
    result of the expert layers; nothing is latent attention."""
    assert FAMILY.trace_shapes(CONFIG, CONFIG["job"]) == {
        "flash": ["[28,16384,128]"], "latent": [],
        "experts": ["[98304", "[16384,64]"]}


def product_flops(jaxpr, times=1):
    """Operations of every ``dot_general`` of ``jaxpr``, ``2 x`` the
    result's size ``x`` the contracted size, a ``scan``'s body as often
    as it runs."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            shape = eqn.invars[0].aval.shape
            total += times * 2 * math.prod(eqn.outvars[0].aval.shape) * (
                math.prod(shape[i] for i in contract))
        inner = times * eqn.params.get("length", 1) if (
            eqn.primitive.name == "scan") else times
        for sub in core.jaxprs_in_params(eqn.params):
            total += product_flops(sub, inner)
    return total


def test_required_operations_against_the_references_own_products():
    """At the toy size (8 layers, T 40, windows of 16, 4 of 16 experts
    held, 3 a token) the products the plain reference makes in a forward
    pass are the required ones and what a plain reference cannot leave
    out: the masked pairs (it scores every pair) and the held experts a
    token did not choose (it runs all 4 on every token, where 3 x 4 / 16
    are met)."""
    toy = load_json(os.path.join(HERE, "toy", "smallthinker_21b_a3b.json"))
    config = {**CONFIG, **toy["sizes"]}
    job = {**CONFIG["job"], **toy["job"], "per_chip_batch": 1}
    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(config, job, key), jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct((1, job["seq_len"]), "int32")
    made = product_flops(jax.make_jaxpr(
        lambda p, b: FAMILY.reference_loss(config, p, extra, b)[0])(
            params, batch).jaxpr)
    t, d, f = job["seq_len"], config["hidden_size"], (
        config["moe_ffn_hidden_size"])
    heads, dim = config["num_attention_heads"], config["head_dim"]
    masked = 2 * (t * t - FAMILY.allowed_pairs(t)) + 6 * (
        t * t - FAMILY.allowed_pairs(t, config["sliding_window_size"]))
    unmet = 4 - 3 * 4 / 16
    required = FAMILY.required_flops_per_sample(config, job) / 3
    assert made == required + heads * 4 * dim * masked + (
        8 * unmet * 3 * d * f * 2 * t)
    assert 0.5 < required / made < 0.6
