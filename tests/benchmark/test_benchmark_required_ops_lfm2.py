"""Family ``lfm2_lm``'s counts of required operations and of the flash
kernels' operations, against counts worked on paper from the published
shapes, and the shape its trace reader looks for."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "lfm2_24b_a2b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "lfm2_lm.py"),
                      "hvd_benchmark_ops_lfm2_lm")

# LFM2-24B-A2B, parameters a token is multiplied with.
# A conv mixer: W_in 2048 x 6144 and W_out 2048 x 2048      = 16,777,216
#   and the taps, 3 x 2048 (a multiply-add a token each)    =      6,144
#                                                      conv = 16,783,360
# Attention, 32 query heads of 64 over 8 key-value heads:
#   q and the output projection, 2 x 2048 x 2048            =  8,388,608
#   k and v, 2 x 2048 x 512                                 =  2,097,152
#                                                 attention = 10,485,760
#   (the two head norms' scales, 2 x 64, are parameters and no product)
# The leading dense SwiGLU: 3 x 2048 x 11776                = 72,351,744
# An expert: 3 x 2048 x 1536                                =  9,437,184
# An expert layer: router 2048 x 64 = 131,072, and of the token's 4
# routed experts the held ones: 4 x 8 / 64 = 0.5 at a uniform router:
#   131,072 + 4,718,592                                     =  4,849,664
# The head, a slice of 8,192 rows: 2048 x 8192              = 16,777,216
CONV, ATTENTION, DENSE = 16_783_360, 10_485_760, 72_351_744
EXPERT, EXPERT_LAYER, HEAD = 9_437_184, 4_849_664, 16_777_216
# the cell: conv + dense, attention + experts, three conv + experts
PER_TOKEN = 4 * CONV + ATTENTION + DENSE + 4 * EXPERT_LAYER + HEAD
# attention, a sequence of 8192: 8192 x 8193 / 2 = 33,558,528 pairs, a
# query head 2 x 64 for a score + 2 x 64 for the weighted sum
CAUSAL_PAIRS = 33_558_528
ATTENTION_PAIRS = 32 * 256 * CAUSAL_PAIRS


def test_causal_pairs():
    assert FAMILY.causal_pairs(8192) == CAUSAL_PAIRS
    assert FAMILY.causal_pairs(9) == sum(
        1 for i in range(9) for j in range(9) if j <= i)


def test_required_operations_at_the_sizes_the_cell_runs():
    assert PER_TOKEN == 186_146_816
    assert ATTENTION_PAIRS == 274_911_461_376
    want = 3 * (2 * PER_TOKEN * 8192 + ATTENTION_PAIRS)
    assert want == 9_974_222_684_160
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_1_22_gflop_a_token_by_the_issues_parts():
    """The issue's count, confirmed: 1,217,556,480 a token, 1.995e13 a
    step of 16,384 tokens; the four conv mixers are 33% of it, the one
    dense SwiGLU 36% (2 of the model's 40 layers, 1 of the 5 here),
    attention with its projections 13%, the held experts 9%."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 / FAMILY.sample_units(CONFIG, job))
    assert per_token == 1_217_556_480
    parts = {"conv": 6 * CONV * 4, "dense": 6 * DENSE,
             "attention": 6 * ATTENTION + 3 * ATTENTION_PAIRS // 8192,
             "experts": 4 * 6 * EXPERT * 4 * 8 // 64,
             "routers": 4 * 6 * 2048 * 64, "head": 6 * HEAD}
    assert parts == {"conv": 402_800_640, "dense": 434_110_464,
                     "attention": 163_590_144, "experts": 113_246_208,
                     "routers": 3_145_728, "head": 100_663_296}
    assert sum(parts.values()) == per_token
    assert parts["conv"] / per_token == pytest.approx(0.331, abs=0.001)
    assert parts["dense"] / per_token == pytest.approx(0.357, abs=0.001)
    assert per_token * 16_384 == pytest.approx(1.995e13, rel=1e-3)


def test_flash_operations_a_step():
    """One full-attention layer's kernels on two sequences, forward and
    both gradients: 1.65e12 of the step's 19.9e12."""
    job = CONFIG["job"]
    assert FAMILY.flash_flops_per_step(CONFIG, job) == (
        3 * 4 * 64 * CAUSAL_PAIRS * 32 * 2) == 1_649_468_768_256


def test_parameters_of_the_published_configuration_cut_to_the_chip():
    """486,062,208 parameters (the issue's table: the dense conv layer
    89,139,200, the attention expert layer 86,118,528, a conv expert
    layer 92,416,000, embedding + head 33,554,432, the final norm 2,048)
    = 7.78 GB at 16 bytes: the program's own tree, by
    ``jax.eval_shape``."""
    import jax

    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    held_layer = 131_072 + 8 * EXPERT
    assert count(params["block_0"]) == CONV + DENSE + 2 * 2048 == 89_139_200
    assert count(params["block_1"]) == (
        ATTENTION + 128 + held_layer + 2 * 2048) == 86_118_528
    for i in (2, 3, 4):
        assert count(params[f"block_{i}"]) == (
            CONV + held_layer + 2 * 2048) == 92_416_000
    assert count(params["embed"]) + count(params["lm_head"]) == 33_554_432
    assert count(params) == 486_062_208
    assert count(params) * 16 == pytest.approx(7.78e9, rel=1e-3)
    assert extra["router_bias"].shape == (4, 64)
    mixer, attn = params["block_2"]["mixer"], params["block_1"]["attn"]
    assert mixer["in"]["kernel"].shape == (2048, 6144)
    assert mixer["kernel"].shape == (3, 2048)
    assert mixer["out"]["kernel"].shape == (2048, 2048)
    assert attn["q"]["kernel"].shape == (2048, 32, 64)
    # no array of k or v with the query heads' count
    assert attn["kv"]["kernel"].shape == (2048, 2, 8, 64)
    assert attn["q_norm"]["scale"].shape == (64,)
    assert attn["k_norm"]["scale"].shape == (64,)
    assert params["block_3"]["moe"]["wg_kernel"].shape == (8, 2048, 1536)
    assert params["block_3"]["moe"]["router_kernel"].shape == (2048, 64)
    assert "shared" not in params["block_3"]["moe"]


def test_every_published_width_is_in_the_file():
    assert CONFIG["hidden_size"] == 2048
    assert CONFIG["intermediate_size"] == 11776
    assert CONFIG["moe_intermediate_size"] == 1536
    assert CONFIG["num_attention_heads"] == 32
    assert CONFIG["num_key_value_heads"] == 8
    assert FAMILY._head_dim(CONFIG) == 64
    assert CONFIG["conv_L_cache"] == 3 and not CONFIG["conv_bias"]
    assert CONFIG["router_outputs"] == 64
    assert CONFIG["num_experts_per_tok"] == 4
    assert CONFIG["norm_eps"] == 1e-5
    assert CONFIG["rope_parameters"]["rope_theta"] == 1000000
    # the published list is whole; the layers here are its 1 to 5
    assert len(CONFIG["layer_types"]) == 40
    assert [i for i, kind in enumerate(CONFIG["layer_types"])
            if kind == "full_attention"] == list(range(2, 40, 4))
    assert FAMILY._layers(CONFIG) == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert FAMILY._period(CONFIG) == FAMILY._layers(CONFIG)[:4]
    assert CONFIG["layers_here"]["layer_types"] == FAMILY._layers(CONFIG)
    assert CONFIG["published"] == {**CONFIG["published"],
                                   "num_hidden_layers": 40,
                                   "num_dense_layers": 2,
                                   "num_experts": 64, "vocab_size": 65536}
    for choice in ("conv_mixer", "qk_norm", "router", "tie_word_embeddings",
                   "bias_update_rate", "seq_len", "remat"):
        assert CONFIG["assumed"][choice]
    readings = CONFIG["memory_analysis"]
    assert (readings["remat_false_GiB"] > 15.0) == CONFIG["remat"]


def test_the_program_is_built_from_the_files_lists():
    from horovod_tpu.models import GroupedAttention, ShortConv, TopkExperts

    cfg = FAMILY._program_config(CONFIG)
    assert cfg.leading_dense == 1 and cfg.n_layers == 5
    assert [type(cfg.at(i).block.attention) for i in range(5)] == [
        ShortConv, GroupedAttention, ShortConv, ShortConv, ShortConv]
    assert cfg.at(0).block.attention == ShortConv(taps=3)
    full = cfg.at(1).block.attention
    assert (full.heads, full.kv_heads, full.head_dim) == (32, 8, 64)
    assert full.qk_norm and full.window is None and full.gate is None
    assert full.rotary.theta == 1e6 and full.rotary.fraction == 1.0
    assert [cfg.ffn_of(i) == "swiglu" for i in range(5)] == [
        True, False, False, False, False]
    assert cfg.ffn_of(1) == TopkExperts(
        scoring="sigmoid", renormalize=True, scale=1.0, held=(0, 8))
    assert cfg.n_experts == 64 and cfg.experts_per_token == 4


def test_trace_shapes_are_the_query_shape():
    assert FAMILY.trace_shapes(CONFIG, CONFIG["job"]) == {
        "flash": ["[64,8192,64]"]}
