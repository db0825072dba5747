"""The functions that count required operations, against counts worked
by hand from the published shapes."""

import os

import pytest

from benchmark_toy import BENCH, REPO, load_by_path, load_json


def family_and_config(config_name):
    config = load_json(os.path.join(
        REPO, "benchmark", "configs", config_name + ".json"))
    family = load_by_path(
        os.path.join(BENCH, "models", config["family"] + ".py"),
        "hvd_benchmark_ops_" + config["family"])
    return family, config


# GPT-2 medium, one sequence of 1024 tokens.  Matmul parameters:
# 24 layers x (qkv 3 d^2 + out d^2 + up 4 d^2 + down 4 d^2 = 12 d^2)
# + the head d x V = 24 * 12,582,912 + 51,463,168 = 353,453,056.
# Forward: 2 x 353,453,056 x 1024 tokens = 723,871,858,688; causal
# attention 24 layers x 2 products x 2 d x (1024 * 1025 / 2 pairs)
# = 24 * 2 * 2048 * 524,800 = 51,589,939,200.  Backward is twice that.
GPT2_MEDIUM = 3 * (723_871_858_688 + 51_589_939_200)

# ResNet-50 v1.5 at 224 x 224, multiply-adds of the forward pass:
#   first 7x7:      112^2 * 49 * 3 * 64                  =   118,013,952
#   a bottleneck costs 218,365,952 wherever it sits (three products of
#   51,380,224 + 115,605,504 + 51,380,224); the first of a stage costs
#   more: 231,211,008 at 56^2 (its 1x1 reads 64 channels, plus the
#   projection) and 372,506,624 in the later stages (its 1x1 runs before
#   the stride, plus the projection)
#   stage 0: 231,211,008 + 2 * 218,365,952               =   667,942,912
#   stage 1: 372,506,624 + 3 * 218,365,952               = 1,027,604,480
#   stage 2: 372,506,624 + 5 * 218,365,952               = 1,464,336,384
#   stage 3: 372,506,624 + 2 * 218,365,952               =   809,238,528
#   classifier: 2048 * 1000                              =     2,048,000
# 4,089,184,256 in all, the "4.09 G" of the literature.  Required:
# 2 operations a multiply-add, three products (forward, weight gradient,
# input gradient), but two at the first convolution: nobody needs the
# image's gradient.
RESNET50_MACS = 4_089_184_256
RESNET50 = 6 * (RESNET50_MACS - 118_013_952) + 4 * 118_013_952


@pytest.mark.parametrize("config_name,want", [
    ("gpt2_medium", GPT2_MEDIUM), ("resnet50_v15", RESNET50)])
def test_required_operations_at_published_sizes(config_name, want):
    family, config = family_and_config(config_name)
    assert family.required_flops_per_sample(config, config["job"]) == want


def test_gpt2_medium_is_2_27_gflop_a_token():
    family, config = family_and_config("gpt2_medium")
    per_token = (family.required_flops_per_sample(config, config["job"])
                 / family.sample_units(config, config["job"]))
    assert per_token == 2_271_860_736


def test_resnet_forward_multiply_adds():
    family, config = family_and_config("resnet50_v15")
    convs, (c_in, classes) = family._conv_layers(config, config["job"])
    assert len(convs) == 53  # 1 + 16 * 3 + 4 projections
    macs = sum(side * side * k * k * cin * cout
               for side, k, cin, cout, _ in convs) + c_in * classes
    assert macs == RESNET50_MACS


@pytest.mark.parametrize("seq,want", [
    # d 8, 1 layer, inner 32, vocab 16: 12 d^2 + d V = 768 + 128 = 896
    # matmul parameters; attention 2 * 2d * T (T + 1) / 2 = 16 T (T + 1)
    (1, 3 * (2 * 896 * 1 + 16 * 1 * 2)),
    (4, 3 * (2 * 896 * 4 + 16 * 4 * 5)),
])
def test_transformer_count_at_a_size_done_on_paper(seq, want):
    family, _ = family_and_config("gpt2_medium")
    config = dict(n_embd=8, n_layer=1, n_inner=32, vocab_size=16)
    assert family.required_flops_per_sample(config, {"seq_len": seq}) == want


def test_resnet_count_at_a_size_done_on_paper():
    """One stage of one block, 4 filters, 8 x 8 image, 3 classes:
    first conv 4^2 * 49 * 3 * 4 = 9,408; after the pool 2 x 2: 1x1
    4->4 = 64, 3x3 = 576, 1x1 4->16 = 256, projection 4->16 = 256;
    classifier 48."""
    family, _ = family_and_config("resnet50_v15")
    config = dict(stage_sizes=[1], num_filters=4, num_classes=3)
    got = family.required_flops_per_sample(config, {"image_size": 8})
    assert got == 4 * 9_408 + 6 * (64 + 576 + 256 + 256 + 48)
