"""Family ``joyai_lm``'s counts of required operations and of the flash
kernels' operations, against counts worked on paper from the published
shapes, and the shapes its trace readers look for."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "joyai_llm_flash.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "joyai_lm.py"),
                      "hvd_benchmark_ops_joyai_lm")

# JoyAI-LLM-Flash, matmul parameters a token meets.
# Latent attention, every block:
#   q_a 2048 x 1536                                        =  3,145,728
#   q_b 1536 x (32 x 192 = 6144)                           =  9,437,184
#   kv_a 2048 x (512 + 64 = 576)                           =  1,179,648
#   kv_b 512 x (32 x (128 + 128) = 8192)                   =  4,194,304
#   out (32 x 128 = 4096) x 2048                           =  8,388,608
#                                                attention = 26,345,472
# Layer 0's dense SwiGLU: 3 x 2048 x 7168                  = 44,040,192
# An expert: 3 x 2048 x 768                                =  4,718,592
# An expert layer: router 2048 x 256 = 524,288, the shared expert, and
# of the token's 8 routed experts the held ones: 8 x 16 / 256 = 0.5 at
# a uniform router: 524,288 + 4,718,592 + 2,359,296        =  7,602,176
# The module's projection: 4096 x 2048                     =  8,388,608
# The head, a slice of 16,160 rows: 2048 x 16160           = 33,095,680
ATTENTION, DENSE, EXPERT_LAYER = 26_345_472, 44_040_192, 7_602_176
PROJECTION, HEAD = 8_388_608, 33_095_680
# the cell: 1 dense + 4 expert layers, the module (a sixth block with an
# expert layer), the head twice
PER_TOKEN = (6 * ATTENTION + DENSE + 5 * EXPERT_LAYER + PROJECTION
             + 2 * HEAD)
# causal attention, a sequence of 4096: (4096 x 4097 / 2 = 8,390,656
# pairs) x 32 heads x (2 x 192 for a score + 2 x 128 for the weighted
# sum = 640) x 6 blocks
PAIRS = 8_390_656
ATTENTION_4096 = PAIRS * 32 * 640 * 6


def test_required_operations_at_the_sizes_the_cell_runs():
    assert PER_TOKEN == 314_703_872
    assert ATTENTION_4096 == 1_031_043_809_280
    want = 3 * (2 * PER_TOKEN * 4096 + ATTENTION_4096)
    assert want == 10_827_293_786_112
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_2_64_gflop_a_token():
    """The issue's count, confirmed: 2.64 GFLOP a token, 43.3 TFLOP a
    step of 16,384 tokens; causal attention is 28.6% of it."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    assert per_token == 2_643_382_272
    assert per_token * 16384 == pytest.approx(43.3e12, rel=2e-3)
    assert 3 * ATTENTION_4096 / 4096 / per_token == pytest.approx(
        0.286, abs=0.001)


def test_flash_operations_a_step():
    """``3 x 2 x (192 + 128) x B x 32 x T (T + 1) / 2`` a block, six
    blocks, 4 sequences: the attention part of the required count."""
    want = 3 * 2 * (192 + 128) * 4 * 32 * PAIRS * 6
    assert want == 12_372_525_711_360 == 4 * 3 * ATTENTION_4096
    assert FAMILY.flash_flops_per_step(CONFIG, CONFIG["job"]) == want


def test_parameters_of_the_published_configuration_cut_to_the_chip():
    """680,439,808 parameters (the issue's 680,441,088 less the five
    bias rows of 256, which are state and no parameter) = 10.89 GB at 16
    bytes: the program's own tree, by ``jax.eval_shape``."""
    import jax

    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert count == 680_439_808
    assert extra["router_bias"].shape == (5, 256)
    assert params["block_1"]["moe"]["wg_kernel"].shape == (16, 2048, 768)
    assert params["block_1"]["moe"]["router_kernel"].shape == (2048, 256)
    assert params["block_0"]["attn"]["q_b"]["kernel"].shape == (1536, 32, 192)
    assert params["embed"]["embedding"].shape == (16160, 2048)


def test_trace_shapes_at_the_cells_sizes_and_at_a_toy_size():
    shapes = FAMILY.trace_shapes(CONFIG, CONFIG["job"])
    assert shapes["flash"] == ["[128,4096,192]"]
    assert shapes["experts"] == ["[131072", "[16384,256]"]
    assert {"[4,4096,1536]", "[4,4096,576]", "[4,4096,32,192]",
            "[4,4096,32,256]", "[4,32,4096,128]"} <= set(shapes["latent"])
    toy = load_json(os.path.join(REPO, "tests", "benchmark", "toy",
                                 "joyai_llm_flash.json"))
    small = FAMILY.trace_shapes({**CONFIG, **toy["sizes"]}, toy["job"])
    assert small["flash"] == ["[8,32,24]"]
    assert small["experts"] == ["[256", "[64,16]"]
