"""Family ``sambay_lm``'s counts of parameters, of required operations,
of the flash kernels' operations and of the bytes the scans must move,
against counts worked on paper from the published shapes, and the shape
its trace reader looks for."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "phi4_mini_flash.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "sambay_lm.py"),
                      "hvd_benchmark_ops_sambay_lm")

# Phi-4-mini-flash-reasoning, d 2560, parameters a token is multiplied
# with (biases, norms, taps, A, D and the lambda vectors are none).
# The feed-forward, every layer: 3 x 2560 x 10240            = 78,643,200
# A Mamba mixer: W_in 2560 x 10240                           = 26,214,400
#   W_x 5120 x (160 + 16 + 16)                               =    983,040
#   W_dt 160 x 5120                                          =    819,200
#   W_out 5120 x 2560                                        = 13,107,200
#                                                      mamba = 41,123,840
# Differential attention with its own k and v: q, k and v together, and
#   the output projection, 3 x 2560 x 2560                   = 19,660,800
# Cross attention: q and the output projection               = 13,107,200
# A memory unit: 2 x 2560 x 5120                             = 26,214,400
# The head, tied, a slice of 25,008 rows: 2560 x 25008       = 64,020,480
FFN, MAMBA, ATTENTION = 78_643_200, 41_123_840, 19_660_800
CROSS, MEMORY_UNIT, HEAD = 13_107_200, 26_214_400, 64_020_480
# the cell: Mamba, sliding, Mamba, full, memory unit, cross
MATMUL = 6 * FFN + 2 * MAMBA + 2 * ATTENTION + MEMORY_UNIT + CROSS + HEAD
# every parameter, as ``init`` makes them: a Mamba mixer's matrices and
# its taps 4 x 5120 + their bias 5120, the step-size bias 5120, A_log
# 5120 x 16, D 5120 = 41,241,600; attention's with three biases of 2560,
# four lambda vectors of 64 and the norm's 128 = 19,668,864; cross
# attention's with two biases = 13,112,704; two LayerNorms a block
# 4 x 2560; the embedding once; the final norm
PARAMETERS = (6 * (FFN + 10_240) + 2 * 41_241_600 + 2 * 19_668_864
              + 13_112_704 + MEMORY_UNIT + HEAD + 5_120)
# attention, a sequence of 8192: 8192 x 8193 / 2 = 33,558,528 causal
# pairs (the full and the cross layer), 512 x 513 / 2 + 7680 x 512 =
# 4,063,488 under the window; a pair and query head 2 x 64 for a score
# and 2 x 128 for the weighted sum, 40 query heads
CAUSAL_PAIRS, WINDOW_PAIRS = 33_558_528, 4_063_488
ATTENTION_FORWARD = 40 * 2 * (64 + 128) * (2 * CAUSAL_PAIRS + WINDOW_PAIRS)


def test_the_layers_here_are_published_layers_14_to_19():
    assert FAMILY._layers(CONFIG) == [
        "mamba", "sliding_attention", "mamba", "full_attention",
        "memory_unit", "cross_attention"]
    whole = [FAMILY.kind_of(layer, CONFIG) for layer in range(32)]
    assert [whole.count(kind) for kind in FAMILY.KINDS] == [9, 8, 1, 7, 7]
    assert whole[16] == "mamba" and whole[17] == "full_attention"
    assert whole[:2] == ["mamba", "sliding_attention"]
    assert whole[30:] == ["memory_unit", "cross_attention"]
    program = FAMILY._program_config(CONFIG)
    published = [program.at(i).block.attention for i in range(6)]
    assert [getattr(m, "publishes", None) for m in published] == [
        False, None, True, None, None, None]
    assert [getattr(m, "keys", None) for m in published] == [
        None, "own", None, "published", None, "read"]
    assert [getattr(m, "window", None) for m in published] == [
        None, 512, None, None, None, None]
    # lambda_init from the PUBLISHED index: layers 15, 17, 19
    assert [round(m.lambda_init, 4) for m in published[1::2]] == [
        0.7933, 0.7963, 0.798]


def test_allowed_pairs():
    assert FAMILY.allowed_pairs(8192) == CAUSAL_PAIRS
    assert FAMILY.allowed_pairs(8192, 512) == WINDOW_PAIRS
    for window in (None, 3, 20):
        assert FAMILY.allowed_pairs(9, window) == sum(
            1 for i in range(9) for j in range(9)
            if j <= i and (window is None or i - j < window))


def test_parameters_as_the_issue_reckoned_them():
    """697,094,272 = 11.15 GB at 16 bytes: what ``init`` makes (by its
    shapes; nothing is allocated)."""
    import jax

    assert PARAMETERS == 697_094_272
    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))
    assert extra == {}
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == PARAMETERS
    assert "lm_head" not in params and "pos_embed" not in params
    assert PARAMETERS * 16 == pytest.approx(11.15e9, rel=1e-3)


def test_required_operations_at_the_sizes_the_cell_runs():
    assert MATMUL == 696_770_560
    assert ATTENTION_FORWARD == 1_093_333_155_840
    want = 3 * (2 * MATMUL * 8192 + ATTENTION_FORWARD)
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_4_58_gflop_a_token_by_the_issues_parts():
    """The issue's count, confirmed: 6 x 696,770,560 + 400,390,560 =
    4,581,013,920 a token; the feed-forward is 62% of it, Mamba's
    products 11%, attention's kernels 9%, the head 8%."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    assert per_token == 4_581_013_920
    assert 3 * ATTENTION_FORWARD == 3_279_999_467_520
    parts = {"ffn": 6 * 6 * FFN, "mamba": 6 * 2 * MAMBA,
             "projections": 6 * (2 * ATTENTION + CROSS),
             "kernels": 3 * ATTENTION_FORWARD // 8192,
             "memory_unit": 6 * MEMORY_UNIT, "head": 6 * HEAD}
    assert parts == {"ffn": 2_831_155_200, "mamba": 493_486_080,
                     "projections": 314_572_800, "kernels": 400_390_560,
                     "memory_unit": 157_286_400, "head": 384_122_880}
    assert sum(parts.values()) == per_token
    for name, share in (("ffn", 0.62), ("mamba", 0.11), ("kernels", 0.09),
                        ("head", 0.08)):
        assert parts[name] / per_token == pytest.approx(share, abs=0.005)


def test_flash_operations_a_step():
    """Three attention layers' kernels (two calls each), forward and
    both gradients, on the step's sequences."""
    job = CONFIG["job"]
    assert FAMILY.flash_flops_per_step(CONFIG, job) == (
        3 * ATTENTION_FORWARD * job["per_chip_batch"])


def test_window_flash_operations_a_step():
    """The one sliding layer's two calls alone: 512 x 513 / 2 + 7,680 x
    512 = 4,063,488 allowed pairs a head and sequence, 2 x (64 + 128) =
    384 operations a pair, 40 query heads, forward and both gradients,
    two sequences: 374,491,054,080, 1.9 ms at the bf16 peak."""
    assert 512 * 513 // 2 + 7_680 * 512 == 4_063_488
    job = dict(CONFIG["job"], per_chip_batch=2)
    assert FAMILY.window_flash_flops_per_step(CONFIG, job) == (
        3 * 2 * 40 * 384 * 4_063_488)
    assert FAMILY.window_flash_flops_per_step(CONFIG, job) == 374_491_054_080
    assert 374_491_054_080 / 197e12 == pytest.approx(1.9e-3, rel=0.01)
    # the full and the cross layer are the rest of flash_flops_per_step
    assert FAMILY.flash_flops_per_step(CONFIG, job) - 374_491_054_080 == (
        2 * 3 * 2 * 40 * 384 * (8192 * 8193 // 2))


def test_scan_bytes_a_step():
    """What any implementation of the two scans must move at bfloat16: a
    token and layer the forward reads c, delta, B, C and writes y, (3 x
    5120 + 32) x 2 = 30,784 bytes, the backward reads those and dy and
    writes four gradients, (5 x 5120 + 64) x 2 = 51,328: 82,112 bytes;
    two sequences of 8192 through two layers are 2,690,646,016 bytes,
    3.3 ms at 819 GB/s."""
    assert 30_784 + 51_328 == 82_112
    two = dict(CONFIG["job"], per_chip_batch=2)
    assert FAMILY.scan_bytes_per_step(CONFIG, two) == 82_112 * 2 * 16_384
    assert FAMILY.scan_bytes_per_step(CONFIG, two) == 2_690_646_016
    one = dict(CONFIG["job"], per_chip_batch=1)
    assert FAMILY.scan_bytes_per_step(CONFIG, one) == 1_345_323_008
    assert 2_690_646_016 / 819e9 == pytest.approx(3.3e-3, rel=0.01)


def test_the_shape_the_flash_calls_are_found_by():
    """q of one of a layer's two calls: 20 pairs' first (or second)
    heads of 64 a sequence."""
    for batch in (1, 2):
        job = dict(CONFIG["job"], per_chip_batch=batch)
        assert FAMILY.trace_shapes(CONFIG, job) == {
            "flash": [f"[{20 * batch},8192,64]"]}
