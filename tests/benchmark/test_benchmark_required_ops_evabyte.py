"""Family ``evabyte_lm``'s counts of parameters, of required operations,
of the attention kernels' operations and of the bytes the pooling must
move, against counts worked on paper from the published shapes and
written out by hand at toy sizes."""

import os

import jax
import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(
    REPO, "benchmark", "configs", "evabyte_6_5b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "evabyte_lm.py"),
                      "hvd_benchmark_ops_evabyte_lm")

# EvaByte 6.5B, d 4096, parameters a token is multiplied with (the norms'
# scales and the attention's two learned vectors a head are none).
# Attention: q, k, v and the output, 4 x 4096 x 4096         =  67,108,864
# The feed-forward: 3 x 4096 x 11008                         = 135,266,304
#                                                     a layer = 202,375,168
# The head: 4096 x (8 x 320)                                 =  10,485,760
LAYER, HEAD = 202_375_168, 10_485_760
# every parameter, as ``init`` makes them: a layer's matrices, two norms
# of 4096 and phi, mu 2 x 32 x 128 = 202,391,552; the embedding 320 x
# 4096; the head; the closing norm
PARAMETERS = 4 * (LAYER + 8_192 + 8_192) + 1_310_720 + HEAD + 4_096
# attention, a head, windows of 2048 in chunks of 16 (128 summaries a
# window): a window's 2048 x 2049 / 2 = 2,098,176 local pairs; window w's
# 2048 queries read 128 w summaries
LOCAL = {8192: 4 * 2_098_176, 16384: 8 * 2_098_176, 32768: 16 * 2_098_176}
REMOTE = {8192: 2048 * 128 * 6, 16384: 2048 * 128 * 28,
          32768: 2048 * 128 * 120}


def test_parameters_as_the_issue_counts_them():
    assert FAMILY._matmul_params(CONFIG) == (LAYER, HEAD)
    assert PARAMETERS == 821_366_784
    params, _ = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, {"seq_len": 2048}, key),
        jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == PARAMETERS
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(params))


@pytest.mark.parametrize("t", [8192, 16384, 32768])
def test_allowed_pairs_at_the_three_lengths(t):
    assert FAMILY.allowed_pairs(t, 2048, 16) == (LOCAL[t], REMOTE[t])
    # the issue's millions: 16.8 and 7.3 at 16384, 8.4 and 1.6 at 8192,
    # 33.6 and 31.5 at 32768
    assert [round(n / 1e6, 1) for n in FAMILY.allowed_pairs(t, 2048, 16)] == {
        8192: [8.4, 1.6], 16384: [16.8, 7.3], 32768: [33.6, 31.5]}[t]


@pytest.mark.parametrize("t,window,chunk", [
    (48, 16, 4), (16, 16, 4), (10, 16, 4), (40, 16, 4), (64, 16, 16)])
def test_allowed_pairs_against_a_count_over_indices(t, window, chunk):
    local = sum(1 for i in range(t) for j in range(t)
                if i // window * window <= j <= i)
    remote = sum(1 for i in range(t) for c in range(t // chunk)
                 if c < i // window * (window // chunk))
    assert FAMILY.allowed_pairs(t, window, chunk) == (local, remote)


def test_required_operations_of_the_cell():
    job = {**CONFIG["job"], "seq_len": 16384}
    attention = 4 * 32 * 4 * 128 * (LOCAL[16384] + REMOTE[16384])
    assert FAMILY.required_flops_per_sample(CONFIG, job) == 3 * (
        2 * (4 * LAYER + HEAD) * 16384 + attention)
    # 80.6 TFLOP of products and 4.7 of attention a step, as the issue has it
    assert 3 * 2 * (4 * LAYER + HEAD) * 16384 == pytest.approx(80.6e12,
                                                               rel=2e-3)
    assert 3 * attention == pytest.approx(4.74e12, rel=2e-3)
    assert FAMILY.eva_flash_flops_per_step(CONFIG, job) == 3 * attention
    assert FAMILY.sample_units(CONFIG, job) == 16384


def test_counts_written_out_by_hand_at_toy_sizes():
    """2 layers, d 32, 4 heads of 8, a feed-forward of 48, 3 outputs over
    23 columns, windows of 16 in chunks of 4, 2 sequences of 48."""
    toy = {**CONFIG, "num_hidden_layers": 2, "hidden_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "intermediate_size": 48, "num_pred_heads": 3, "vocab_size": 23,
           "window_size": 16, "chunk_size": 4}
    job = {"per_chip_batch": 2, "seq_len": 48}
    layer, head = 4 * 32 * 32 + 3 * 32 * 48, 32 * 3 * 23
    # 3 windows: 3 x 136 local pairs; 16 queries x 4 + 16 x 8 summaries
    pairs = 3 * 136 + 16 * 4 + 16 * 8
    attention = 2 * 4 * (4 * 8) * pairs          # layers, heads, 4 D
    assert FAMILY.required_flops_per_sample(toy, job) == 3 * (
        2 * (2 * layer + head) * 48 + attention)
    assert FAMILY.eva_flash_flops_per_step(toy, job) == 3 * 2 * attention
    # the pooling: N = 2 x 48 x 4 x 8 numbers of k; forward 2 N + 2 N / 4,
    # backward 4 N + 2 N / 4, bfloat16, two layers
    n = 2 * 48 * 4 * 8
    assert FAMILY.eva_pool_bytes_per_step(toy, job) == (
        (6 * n + n) * 2 * 2)


def test_pool_bytes_of_the_cell():
    job = {**CONFIG["job"], "seq_len": 16384}
    n = 16384 * 32 * 128                         # 67,108,864 numbers of k
    assert FAMILY.eva_pool_bytes_per_step(CONFIG, job) == (
        (6 * n + 4 * n // 16) * 2 * 4) == 3_355_443_200
