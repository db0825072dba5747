"""Family ``laguna_lm``'s counts of required operations and of the flash
kernels' operations, against counts worked on paper from the published
shapes, and the shapes its trace reader looks for."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "laguna_s_2_1.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "laguna_lm.py"),
                      "hvd_benchmark_ops_laguna_lm")

# Laguna-S-2.1, matmul parameters a token meets.
# Attention of a full layer, 48 query heads of 128 over 8 key-value heads:
#   q 3072 x 6144 and the output projection 6144 x 3072    = 37,748,736
#   k and v, 2 x 3072 x 1024                               =  6,291,456
#   the gate 3072 x 48                                     =    147,456
#                                                     full = 44,187,648
# of a sliding layer, 72 query heads:
#   q and the output projection, 2 x 3072 x 9216           = 56,623,104
#   k and v                                                =  6,291,456
#   the gate 3072 x 72                                     =    221,184
#                                                  sliding = 63,135,744
# Layer 0's dense SwiGLU: 3 x 3072 x 12288                 = 113,246,208
# An expert, routed or shared: 3 x 3072 x 1024             =   9,437,184
# An expert layer: router 3072 x 256 = 786,432, the shared expert, and
# of the token's 10 routed experts the held ones: 10 x 8 / 256 = 0.3125
# at a uniform router: 786,432 + 9,437,184 + 2,949,120     =  13,172,736
# The head, a slice of 12,544 rows: 3072 x 12544           =  38,535,168
FULL, SLIDING, DENSE = 44_187_648, 63_135_744, 113_246_208
EXPERT, EXPERT_LAYER, HEAD = 9_437_184, 13_172_736, 38_535_168
# the cell: full + dense, three sliding + experts, full + experts
PER_TOKEN = 2 * FULL + 3 * SLIDING + DENSE + 4 * EXPERT_LAYER + HEAD
# attention, a sequence of 8192: a full layer uses 8192 x 8193 / 2 =
# 33,558,528 pairs, a sliding layer 512 x 513 / 2 + 7680 x 512 =
# 4,063,488 (a query sees 512 keys, itself included; the first 511 see
# fewer); a query head 2 x 128 for a score + 2 x 128 for the weighted sum
CAUSAL_PAIRS, WINDOW_PAIRS = 33_558_528, 4_063_488
FULL_ATTENTION = 2 * 48 * 512 * CAUSAL_PAIRS
WINDOW_ATTENTION = 3 * 72 * 512 * WINDOW_PAIRS


def test_allowed_pairs():
    assert FAMILY.allowed_pairs(8192) == CAUSAL_PAIRS
    assert FAMILY.allowed_pairs(8192, 512) == WINDOW_PAIRS
    assert FAMILY.allowed_pairs(4096, 512) == 131_328 + 3584 * 512
    # by counting, windows under, at and over the length
    for t, window in ((9, 1), (9, 4), (9, 9), (9, 20)):
        assert FAMILY.allowed_pairs(t, window) == sum(
            1 for i in range(t) for j in range(t) if i - window < j <= i)


def test_required_operations_at_the_sizes_the_cell_runs():
    assert PER_TOKEN == 482_254_848
    assert FULL_ATTENTION == 1_649_468_768_256
    assert WINDOW_ATTENTION == 449_389_264_896
    want = 3 * (2 * PER_TOKEN * 8192 + FULL_ATTENTION + WINDOW_ATTENTION)
    assert want == 30_000_364_388_352
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_3_66_gflop_a_token():
    """The issue's count, confirmed: 3,662,153,856 a token, 30.0 TFLOP a
    step of 8,192 tokens; attention with its projections is 66% of it,
    the flash kernels 21%."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    assert per_token == 3_662_153_856
    kernels = 3 * (FULL_ATTENTION + WINDOW_ATTENTION) / 8192
    projections = 6 * (2 * FULL + 3 * SLIDING)
    assert (kernels + projections) / per_token == pytest.approx(
        0.665, abs=0.001)
    assert kernels / per_token == pytest.approx(0.210, abs=0.001)


def test_flash_operations_a_step():
    """All five layers' kernels, and the three sliding layers' alone:
    6.30e12 and 1.35e12 of the step's 30.0e12."""
    job = CONFIG["job"]
    assert FAMILY.flash_flops_per_step(CONFIG, job) == 3 * (
        FULL_ATTENTION + WINDOW_ATTENTION) == 6_296_574_099_456
    assert FAMILY.window_flash_flops_per_step(CONFIG, job) == (
        3 * WINDOW_ATTENTION) == 1_348_167_794_688


def test_parameters_of_the_published_configuration_cut_to_the_chip():
    """811,017,216 parameters (the issue's table: a full layer + dense
    + 2 norms 157,440,000, a sliding layer + experts 148,862,976, a full
    layer + experts 129,914,880, embedding + head + final norm
    77,073,408) = 12.98 GB at 16 bytes: the program's own tree, by
    ``jax.eval_shape``."""
    import jax

    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    held_layer = 786_432 + EXPERT + 8 * EXPERT
    assert count(params["block_0"]) == FULL + DENSE + 2 * 3072 == 157_440_000
    for i in (1, 2, 3):
        assert count(params[f"block_{i}"]) == (
            SLIDING + held_layer + 2 * 3072) == 148_862_976
    assert count(params["block_4"]) == (
        FULL + held_layer + 2 * 3072) == 129_914_880
    assert count(params) == 811_017_216
    assert count(params) * 16 == pytest.approx(12.98e9, rel=1e-3)
    assert extra["router_bias"].shape == (4, 256)
    # no array of k or v with the query heads' count
    assert params["block_1"]["attn"]["kv"]["kernel"].shape == (
        3072, 2, 8, 128)
    assert params["block_1"]["attn"]["q"]["kernel"].shape == (3072, 72, 128)
    assert params["block_4"]["attn"]["q"]["kernel"].shape == (3072, 48, 128)


def test_every_published_width_is_in_the_file():
    assert CONFIG["hidden_size"] == 3072 and CONFIG["head_dim"] == 128
    assert CONFIG["num_key_value_heads"] == 8
    assert CONFIG["sliding_window"] == 512
    assert CONFIG["moe_intermediate_size"] == 1024
    assert CONFIG["shared_expert_intermediate_size"] == 1024
    assert CONFIG["num_experts_per_tok"] == 10
    assert CONFIG["router_outputs"] == 256
    assert CONFIG["intermediate_size"] == 12288
    assert FAMILY._layers(CONFIG) == [
        ("full_attention", 48), ("sliding_attention", 72),
        ("sliding_attention", 72), ("sliding_attention", 72),
        ("full_attention", 48)]
    assert FAMILY._period(CONFIG) == FAMILY._layers(CONFIG)[:4]
    assert CONFIG["layers_here"]["layer_types"] == [
        kind for kind, _ in FAMILY._layers(CONFIG)]
    for choice in ("gate", "router", "qk_norm", "shared_expert"):
        assert CONFIG["assumed"][choice]


def test_trace_shapes_are_the_query_shapes_of_both_kinds():
    assert FAMILY.trace_shapes(CONFIG, CONFIG["job"]) == {
        "flash": ["[48,8192,128]", "[72,8192,128]"]}
