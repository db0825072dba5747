"""Family ``nemotron_h_lm``'s counts of parameters, of required
operations, of the flash kernel's operations and of the bytes its scans
must move, against counts worked on paper from the published shapes,
against the products the plain reference itself makes at the toy size,
and the shape its trace reader looks for."""

import math
import os

import jax
import pytest
from jax._src import core

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "nemotron3_nano_30b_a3b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "nemotron_h_lm.py"),
                      "hvd_benchmark_ops_nemotron_h_lm")

# NVIDIA-Nemotron-3-Nano-30B-A3B, matmul parameters a token meets.
# A Mamba-2 layer, 64 heads of 64, 8 groups, state 128:
#   in  2688 x (4096 + 6144 + 64) = 2688 x 10304          = 27,697,152
#   out 4096 x 2688                                       = 11,010,048
#                                                          = 38,707,200
# The attention layer, 32 query heads of 128 over 2 key-value heads:
#   q 2688 x 4096 and the output projection 4096 x 2688   = 22,020,096
#   k and v, 2 x 2688 x 256                               =  1,376,256
#                                                          = 23,396,352
# An expert layer: the router 2688 x 128                  =    344,064
#   the shared expert 2 x 2688 x 3712                     = 19,955,712
#   an expert 2 x 2688 x 1856 (no gate)                   =  9,977,856
#   of the token's 6 the held ones, 6 x 8 / 128 = 0.375   =  3,741,696
# The head, a slice of 16,384 rows: 2688 x 16384          = 44,040,192
MAMBA, ATTENTION, ROUTER, SHARED, EXPERT, HEAD = (
    38_707_200, 23_396_352, 344_064, 19_955_712, 9_977_856, 44_040_192)
MET = 3_741_696
PER_TOKEN = 4 * MAMBA + ATTENTION + 4 * (ROUTER + SHARED + MET) + HEAD
# causal pairs of a sequence of 8192, and of a chunk of 128
CAUSAL_PAIRS, CHUNK_PAIRS = 33_558_528, 8_256
FLASH = 32 * 512 * CAUSAL_PAIRS
# one layer's scan on 8192 positions by 64 chunks of 128: a causal pair
# 2 x 128 for C . B in each of 8 groups and 2 x 64 for the weighted sum
# in each of 64 heads; a position and head 2 x 64 x 128 into the chunk's
# state and as much out of the entered one; a chunk and head 2 x 64 x 128
# to carry the state on
SCAN = (64 * CHUNK_PAIRS * (2 * 128 * 8 + 2 * 64 * 64)
        + (2 * 8192 + 64) * 2 * 64 * 128 * 64)


def test_required_operations_at_the_sizes_the_cell_runs():
    layers, head = FAMILY._matmul_params(CONFIG)
    assert layers == {"mamba2": MAMBA, "attention": ATTENTION,
                      "experts": ROUTER + SHARED + MET}
    assert head == HEAD
    assert MET == 0.375 * EXPERT
    assert PER_TOKEN == 318_431_232
    assert FLASH == 549_822_922_752
    assert SCAN == 22_657_630_208 == FAMILY.scan_flops(CONFIG, 8192)
    want = 3 * (2 * PER_TOKEN * 8192 + FLASH + 4 * SCAN)
    assert want == 17_572_892_246_016
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_715_mflop_a_token_forward_and_its_shares():
    """The issue's count, confirmed: 715.0 MFLOP a token forward, 35.1
    TFLOP a step of 16,384 tokens; the Mamba-2 mixers are 45% of it (the
    scan by chunks 1.5%), the expert layers 27% (the shared expert 22%,
    the held routed experts 4%), the attention layer 16% (flash 9.4%),
    the head 12%."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    forward = per_token / 3
    assert forward == pytest.approx(715.04e6, rel=1e-5)
    assert 2 * 8192 * per_token == pytest.approx(35.15e12, rel=1e-3)
    shares = {
        "mamba_products": 4 * 2 * MAMBA / forward,
        "scan": 4 * SCAN / 8192 / forward,
        "attention_products": 2 * ATTENTION / forward,
        "flash": FLASH / 8192 / forward,
        "shared": 4 * 2 * SHARED / forward,
        "routed": 4 * 2 * MET / forward,
        "router": 4 * 2 * ROUTER / forward,
        "head": 2 * HEAD / forward}
    assert {k: round(v, 3) for k, v in shares.items()} == {
        "mamba_products": 0.433, "scan": 0.015, "attention_products": 0.065,
        "flash": 0.094, "shared": 0.223, "routed": 0.042, "router": 0.004,
        "head": 0.123}
    assert sum(shares.values()) == pytest.approx(1.0)
    # per token forward, as the issue lists them (MFLOP)
    assert 4 * (2 * MAMBA + SCAN / 8192) / 1e6 == pytest.approx(320.7, abs=0.1)
    assert 4 * SCAN / 8192 / 1e6 == pytest.approx(11.06, abs=0.01)
    assert (2 * ATTENTION + FLASH / 8192) / 1e6 == pytest.approx(113.9,
                                                                 abs=0.1)


def test_scan_operations_by_counting():
    """``scan_flops`` at a small size against a count pair by pair, a T
    the chunk divides and one it does not."""
    small = {**CONFIG, "mamba_num_heads": 4, "mamba_head_dim": 8,
             "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8}
    for t in (40, 37, 5):
        chunks = [range(c, min(c + 8, t)) for c in range(0, t, 8)]
        pairs = sum(1 for chunk in chunks for i in chunk for j in chunk
                    if j <= i)
        want = (pairs * (2 * 16 * 2 + 2 * 8 * 4)
                + t * 2 * (2 * 8 * 16 * 4) + len(chunks) * 2 * 8 * 16 * 4)
        assert FAMILY.scan_flops(small, t) == want


def test_flash_operations_and_scan_bytes_a_step():
    """3 x 4 x 128 x 33,558,528 causal pairs x 32 heads x 2 sequences;
    and the bytes the four scans must move: a token and layer the forward
    reads xs (4096 x 2), B and C (2 x 1024 x 2), dt (64 x 4) and writes
    y (4096 x 2): 20,736; the backward reads those and dy and writes
    four gradients: 33,280.  4.3 ms at 819 GB/s, against 2.8 ms for the
    scans' operations (3 x 4 x 2 x 22.66 GFLOP) at 197 TFLOP/s: bound
    by HBM."""
    job = CONFIG["job"]
    assert FAMILY.flash_flops_per_step(CONFIG, job) == (
        3 * 4 * 128 * 33_558_528 * 32 * 2) == 3_298_937_536_512
    forward = 4096 * 2 + 2 * 1024 * 2 + 64 * 4 + 4096 * 2
    backward = 2 * (4096 * 2 + 2 * 1024 * 2 + 64 * 4) + 4096 * 2
    assert (forward, backward) == (20_736, 33_280)
    assert FAMILY.scan_bytes_per_step(CONFIG, job) == (
        (forward + backward) * 4 * 16384) == 3_539_992_576
    assert 3_539_992_576 / 819e9 == pytest.approx(4.32e-3, rel=1e-2)
    assert 3 * 4 * 2 * SCAN / 197e12 == pytest.approx(2.76e-3, rel=1e-2)


def test_parameters_of_the_published_configuration_cut_to_the_chip():
    """666,962,944 parameters (the issue's count, by layer) = 10.67 GB at
    16 bytes: the program's own tree, by ``jax.eval_shape``."""
    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    # conv 6144 x 4 + 6144, A_log, D, dt_bias 3 x 64, the gated norm
    # 4096, the layer's norm 2688
    mamba = MAMBA + 6144 * 5 + 3 * 64 + 4096 + 2688
    experts = ROUTER + 8 * EXPERT + SHARED + 2688
    assert (mamba, ATTENTION + 2688, experts) == (
        38_744_896, 23_399_040, 100_125_312)
    kinds = FAMILY._layers(CONFIG)
    assert kinds == ["mamba2", "experts"] * 2 + [
        "mamba2", "attention", "experts", "mamba2", "experts"]
    for i, kind in enumerate(kinds):
        assert count(params[f"block_{i}"]) == {
            "mamba2": mamba, "attention": ATTENTION + 2688,
            "experts": experts}[kind], i
    assert count(params) == (4 * mamba + ATTENTION + 2688 + 4 * experts
                             + 2 * HEAD + 2688) == 666_962_944
    assert count(params) * 16 == pytest.approx(10.67e9, rel=1e-3)
    assert "666,962,944 parameters" in CONFIG["deployment"]
    assert extra["router_bias"].shape == (4, 128)
    moe = params["block_1"]["moe"]
    assert moe["router_kernel"].shape == (2688, 128)
    assert moe["wi_kernel"].shape == (8, 2688, 1856)
    assert moe["wo_kernel"].shape == (8, 1856, 2688)
    assert "wg_kernel" not in moe
    assert moe["shared"]["up"]["kernel"].shape == (2688, 3712)
    assert set(params["block_1"]) == {"ln2", "moe"}
    assert set(params["block_0"]) == {"ln1", "mixer"}
    assert params["block_0"]["mixer"]["in"]["kernel"].shape == (2688, 10304)
    assert params["block_0"]["mixer"]["conv_kernel"].shape == (4, 6144)
    assert params["block_5"]["attn"]["kv"]["kernel"].shape == (
        2688, 2, 2, 128)
    assert params["block_5"]["attn"]["q"]["kernel"].shape == (2688, 32, 128)


# the catalog's ``config`` of ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
# (/opt/skills/guides/model-configs/architectures.jsonl), every key
CATALOG = {'attention_bias': False,
 'chunk_size': 128,
 'conv_kernel': 4,
 'expand': 2,
 'head_dim': 128,
 'hidden_size': 2688,
 'hybrid_override_pattern': 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME',
 'intermediate_size': 1856,
 'layer_norm_epsilon': 1e-05,
 'mamba_head_dim': 64,
 'mamba_hidden_act': 'silu',
 'mamba_num_heads': 64,
 'mamba_proj_bias': False,
 'max_position_embeddings': 262144,
 'mlp_bias': False,
 'mlp_hidden_act': 'relu2',
 'model_type': 'nemotron_h',
 'moe_intermediate_size': 1856,
 'moe_shared_expert_intermediate_size': 3712,
 'n_group': 1,
 'n_groups': 8,
 'n_routed_experts': 128,
 'n_shared_experts': 1,
 'norm_eps': 1e-05,
 'norm_topk_prob': True,
 'num_attention_heads': 32,
 'num_experts_per_tok': 6,
 'num_hidden_layers': 52,
 'num_key_value_heads': 2,
 'num_logits_to_keep': 1,
 'partial_rotary_factor': 1,
 'rescale_prenorm_residual': True,
 'residual_in_fp32': False,
 'rope_theta': 10000,
 'routed_scaling_factor': 2.5,
 'sliding_window': None,
 'ssm_state_size': 128,
 'tie_word_embeddings': False,
 'time_step_floor': 0.0001,
 'time_step_max': 0.1,
 'time_step_min': 0.001,
 'topk_group': 1,
 'use_bias': False,
 'use_conv_bias': True,
 'use_mamba_kernels': True,
 'vocab_size': 131072}
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
          "/blob/main/config.json")


def test_every_published_key_is_in_the_file():
    """Every key of the catalog's ``config`` at its value but the three
    that ``reduced`` lists, the published values beside them."""
    cut = {"num_hidden_layers": (52, 9), "n_routed_experts": (128, 8),
           "vocab_size": (131072, 16384)}
    for key, value in CATALOG.items():
        if key in cut:
            assert (value, CONFIG[key]) == cut[key], key
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == SOURCE
    assert [entry.split(":")[0] for entry in CONFIG["reduced"]] == list(cut)
    assert CONFIG["router_outputs"] == 128
    assert CONFIG["experts_held"] == {"first": 0, "count": 8, "of_chips": 16}
    assert len(CONFIG["hybrid_override_pattern"]) == 52
    assert CONFIG["layers_here"]["pattern"] == (
        CONFIG["hybrid_override_pattern"][:9]) == "MEMEM*EME"
    for choice in ("layers", "positions", "mamba2", "attention", "router",
                   "bias_update_rate", "experts", "initialisation",
                   "optimizer", "global_batch", "activation_dtype", "remat",
                   "expert_load"):
        assert CONFIG["assumed"][choice], choice
    # the job as the issue wrote it
    assert (CONFIG["job"]["per_chip_batch"],
            CONFIG["job"]["seq_len"]) == (2, 8192)
    assert CONFIG["job"]["optimizer"]["args"] == {
        "learning_rate": 1e-5, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1}


def test_trace_shapes_are_the_query_shape_of_the_attention_layer():
    assert FAMILY.trace_shapes(CONFIG, CONFIG["job"]) == {
        "flash": ["[64,8192,128]"], "latent": [],
        "experts": ["[98304", "[16384,128]"]}


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
    """At the toy size (T 40 in 5 chunks of 8, 4 heads of 8 in 2 groups,
    state 8, 4 of 16 experts held, 3 a token) the products the plain
    reference makes in a forward pass are the required ones and what a
    plain reference cannot leave out or does another way: the masked
    pairs of attention (it scores every pair), the held experts a token
    did not choose (it runs all 4 on every token, where 3 x 4 / 16 are
    met), and the scan, which it computes as the RECURRENCE: a position
    and head ``2 P N`` out of the state and nothing else as a product
    (the state's update is elementwise), where the chunked scan's count
    has the pairs' ``C . B``, their weighted sum, the way into the state
    and the chunks' carry."""
    toy = load_json(os.path.join(HERE, "toy", "nemotron3_nano_30b_a3b.json"))
    config = {**CONFIG, **toy["sizes"]}
    job = {**CONFIG["job"], **toy["job"], "per_chip_batch": 1}
    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(config, job, key), jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct((1, job["seq_len"]), "int32")
    made = product_flops(jax.make_jaxpr(
        lambda p, e, b: FAMILY.reference_loss(config, p, e, b)[0])(
            params, extra, batch).jaxpr)
    t, d, f = job["seq_len"], config["hidden_size"], (
        config["moe_intermediate_size"])
    heads, dim = config["num_attention_heads"], config["head_dim"]
    masked = t * t - t * (t + 1) // 2
    unmet = 4 - 3 * 4 / 16
    recurrence = t * 2 * 8 * 8 * 4       # y[t] = h[t] C[t], 4 heads
    required = FAMILY.required_flops_per_sample(config, job) / 3
    assert made == (required + heads * 4 * dim * masked
                    + 4 * unmet * 2 * d * f * 2 * t
                    + 4 * (recurrence - FAMILY.scan_flops(config, t)))
    assert 0.6 < required / made < 0.9
