"""Family ``olmoe_lm``'s counts of required operations, against counts
worked on paper from the published shapes and at one tiny size."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "olmoe_1b_7b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "olmoe_lm.py"),
                      "hvd_benchmark_ops_olmoe_lm")

# OLMoE-1B-7B, matmul parameters a token meets in one layer:
#   attention q, k, v, out: 4 x 2048^2                    =  16,777,216
#   router: 2048 x 64                                     =     131,072
#   the 8 experts it is routed to, gate + up + down each:
#     8 x 3 x 2048 x 1024                                 =  50,331,648
#                                                   layer =  67,239,936
#   head (a matrix of its own): 2048 x 50304              = 103,022,592
# Causal attention a sequence of 4096 and a layer: 2 products x 2 x 2048
# operations x (4096 x 4097 / 2 = 8,390,656 pairs)        = 68,736,253,952
LAYER, HEAD = 67_239_936, 103_022_592
ATTENTION_4096 = 68_736_253_952
# the cell: depth 1.  Forward a sequence: 2 x 170,262,528 x 4096 =
# 1,394,790,629,376, + attention = 1,463,526,883,328; backward twice that.
DEPTH_1 = 3 * (2 * (LAYER + HEAD) * 4096 + ATTENTION_4096)


def test_required_operations_at_the_sizes_the_cell_runs():
    assert CONFIG["num_hidden_layers"] == 1
    assert DEPTH_1 == 4_390_580_649_984
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == DEPTH_1


def test_the_cell_is_1_07_gflop_a_token():
    per_token = (FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"])
                 / FAMILY.sample_units(CONFIG, CONFIG["job"]))
    assert per_token == 1_071_919_104


def test_required_operations_at_the_published_depth():
    """16 layers: forward a token 16 x (2 x 67,239,936 + 16,781,312)
    + 2 x 103,022,592 = 2,626,224,128; the head is 7.8% of it, and
    57.7% of the 357,306,368 at depth 1 (the configuration's
    ``assumed.head_share``)."""
    published = dict(CONFIG, num_hidden_layers=16)
    forward = FAMILY.required_flops_per_sample(
        published, CONFIG["job"]) / 3 / 4096
    assert forward == 2_626_224_128
    assert 2 * HEAD / forward == pytest.approx(0.078, abs=0.001)
    assert 2 * HEAD / (DEPTH_1 / 3 / 4096) == pytest.approx(0.577,
                                                            abs=0.001)


TINY = dict(hidden_size=8, intermediate_size=4, num_experts=4,
            num_experts_per_tok=2, vocab_size=16)


@pytest.mark.parametrize("layers,seq,want", [
    # d 8, 4 experts of 4, 2 a token, vocab 16: attention 4 * 64 = 256,
    # router 32, experts 2 * 3 * 32 = 192: 480 a layer; head 128;
    # attention 2 * 2d * T (T + 1) / 2 = 16 T (T + 1) a layer
    (1, 1, 3 * (2 * 608 * 1 + 16 * 1 * 2)),
    (1, 4, 3 * (2 * 608 * 4 + 16 * 4 * 5)),
    (2, 4, 3 * (2 * 1088 * 4 + 2 * 16 * 4 * 5)),
])
def test_count_at_a_size_done_on_paper(layers, seq, want):
    config = dict(TINY, num_hidden_layers=layers)
    assert FAMILY.required_flops_per_sample(
        config, {"seq_len": seq}) == want


def test_experts_a_token_is_not_routed_to_are_not_required():
    """64 experts or 8: the count follows the 8 a token visits (and the
    router's width)."""
    fewer = dict(CONFIG, num_experts=8)
    per_router_column = 3 * 2 * CONFIG["hidden_size"] * 4096
    assert (FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"])
            - FAMILY.required_flops_per_sample(fewer, CONFIG["job"])
            == 56 * per_router_column)


def test_grouped_matmul_operations_of_a_step():
    """What ``moe_gmm_roofline`` divides.  The cell: 4 x 4096 tokens x 8
    = 131,072 rows; a product 2 x 131,072 x 2048 x 1024 =
    549,755,813,888; three forward and six gradients."""
    assert FAMILY.grouped_matmul_flops_per_step(
        CONFIG, CONFIG["job"]) == 9 * 549_755_813_888 == 4_947_802_324_992
    # tiny: 2 x 4 tokens x 2 = 16 rows; 9 x 2 x 16 x 8 x 4 a layer
    job = {"per_chip_batch": 2, "seq_len": 4}
    assert FAMILY.grouped_matmul_flops_per_step(
        dict(TINY, num_hidden_layers=1), job) == 9_216
    assert FAMILY.grouped_matmul_flops_per_step(
        dict(TINY, num_hidden_layers=3), job) == 27_648


def test_parameters_of_the_cell():
    """625,616,896: one layer 419,569,664 (experts 402,653,184,
    attention 16,777,216, router 131,072, four norms 8,192), embedding
    and head 206,045,184, the final norm 2,048."""
    import jax

    params, _ = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    assert count(params) == 625_616_896
    assert count(params["block_0"]) == 419_569_664
    assert count(params["block_0"]["moe"]) == 402_653_184 + 131_072
    assert "pos_embed" not in params
