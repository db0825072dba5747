"""A block of ONE branch (``BlockSpec(attention=None)`` or
``BlockSpec(ffn=None)``; ``models/transformer.py:Block``): its
parameters, that a recomputed stack of such blocks gives the loss and
gradients of one that keeps everything, what each layer keeps by name
(``kept_names``, ``kept_bytes``, ``kept_products``) against
``jax.ad_checkpoint``'s own account of the residuals, ``parameter_bytes``
and ``kept_plan`` layer by layer, the Mamba-2 mixer's scopes, and the
cell ``nemotron3_nano_30b_a3b-spmd-1chip``'s layers by hand."""

import json
import os
from collections import Counter

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (BlockSpec, GroupedAttention, Mamba2,
                                TopkExperts, Transformer, TransformerConfig,
                                apply_with_aux, lm_loss, transformer)
from horovod_tpu.models.transformer import (
    KEPT_GATE, KEPT_IN, KEPT_SUM, KEPT_UP, kept_bytes, kept_names, kept_plan,
    kept_products, parameter_bytes)
from horovod_tpu.ops import ssd
from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                    SAVED_NAMES)
from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 31)
MAMBA = Mamba2(heads=4, head_dim=8, groups=2, state=16, chunk=8)
ATTENTION = GroupedAttention(heads=4, kv_heads=2, head_dim=8, rotary=None)
EXPERTS = TopkExperts(scoring="sigmoid", renormalize=True, scale=2.5,
                      shared=1, shared_width=48, held=(4, 4),
                      activation="relu2")
BIAS = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (2, 16))
ROOM = 2 ** 40


def spec(attention, ffn):
    return BlockSpec(norm="rms", positions="none", attention=attention,
                     ffn=ffn)


# a mixer alone, experts alone, attention alone, experts alone: M E * E
PATTERN = (spec(MAMBA, None), spec(None, EXPERTS), spec(ATTENTION, None),
           spec(None, EXPERTS))


def config(**changes):
    return TransformerConfig(**{**dict(
        vocab_size=31, n_layers=4, d_model=32, n_heads=4, d_ff=24,
        d_expert=24, n_experts=16, experts_per_token=3, norm_eps=1e-5,
        dtype=jnp.float32, pattern=PATTERN, attn_fn=flash_attention),
        **changes})


def model_and_loss(**changes):
    cfg = config(**changes)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([leaf + 0.1 * jax.random.normal(k, leaf.shape)
                             for leaf, k in zip(leaves, keys)])
    return cfg, params, lambda p: lm_loss(
        model.apply({"params": p}, TOKENS, router_bias=BIAS), TOKENS)


def with_room(monkeypatch):
    monkeypatch.setattr(transformer, "device_memory_bytes",
                        lambda: (ROOM, None))


def keep_nothing(monkeypatch):
    monkeypatch.setattr(transformer, "keeping",
                        lambda block, names: nn.remat(block))


def test_a_block_of_one_branch_has_that_branchs_parameters_and_one_norm():
    _, params, _ = model_and_loss()
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert set(shapes["block_0"]) == {"ln1", "mixer"}
    assert set(shapes["block_1"]) == set(shapes["block_3"]) == {"ln2", "moe"}
    assert set(shapes["block_2"]) == {"ln1", "attn"}
    # H P = 32, the convolution over 32 + 2 x 2 x 16 = 96 channels, the
    # first product 2 x 32 + 64 + 4 = 132 columns
    assert shapes["block_0"]["mixer"] == {
        "in": {"kernel": (32, 132)}, "conv_kernel": (4, 96),
        "conv_bias": (96,), "dt_bias": (4,), "A_log": (4,), "D": (4,),
        "norm_scale": (32,), "out": {"kernel": (32, 32)}}
    # no gate: ``wi`` and ``wo`` of the 4 held experts, a shared expert
    # of its own width with an up and a down and nothing else
    assert shapes["block_1"]["moe"] == {
        "router_kernel": (32, 16), "wi_kernel": (4, 32, 24),
        "wo_kernel": (4, 24, 32),
        "shared": {"up": {"kernel": (32, 48)}, "down": {"kernel": (48, 32)}}}


def test_a_block_is_refused_without_either_branch():
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        BlockSpec(attention=None, ffn=None)
    with pytest.raises(ValueError, match="3 groups do not divide 4 heads"):
        Mamba2(heads=4, head_dim=8, groups=3)
    with pytest.raises(ValueError, match="shared experts are SwiGLU or have"):
        TopkExperts(shared=1, activation="relu")
    assert not TopkExperts(activation="relu2").gated
    assert TopkExperts().gated and TopkExperts(activation="relu").gated


@pytest.mark.parametrize("dtype,room", [
    (jnp.float32, False), (jnp.float32, True), (jnp.bfloat16, False)],
    ids=["f32-no-limit", "f32-room", "bf16-no-limit"])
def test_recomputed_equals_not_recomputed(dtype, room, monkeypatch):
    """Against a plain ``nn.remat``, which keeps nothing, no bit of the
    loss or of a gradient differs; against ``remat=False`` they agree to
    rounding.  With ``room`` every layer keeps its products' results
    too."""
    def run(remat):
        _, params, loss = model_and_loss(remat=remat, dtype=dtype)
        return jax.value_and_grad(loss)(params)

    if room:
        with_room(monkeypatch)
        assert kept_plan(config(remat=True), *TOKENS.shape, ROOM).names == (
            (KEPT_IN,), (KEPT_UP, moe.PRODUCT_UP, moe.PRODUCT_DOWN), (),
            (KEPT_UP, moe.PRODUCT_UP, moe.PRODUCT_DOWN))
    got, want = run(True), run(False)
    keep_nothing(monkeypatch)
    plain = run(True)
    tolerance = (dict(rtol=2e-5, atol=2e-6) if dtype == jnp.float32
                 else dict(rtol=2 ** -6, atol=2 ** -8))
    for (path, g), w, p in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                               jax.tree.leaves(want), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(g, p, err_msg=str(path))
        np.testing.assert_allclose(g, w, err_msg=str(path), **tolerance)
    assert all(float(jnp.max(jnp.abs(leaf))) > 0
               for leaf in jax.tree.leaves(want[1]))


def test_what_a_layer_of_one_branch_names():
    """A layer without a mixer names nothing of a kernel or a scan and
    no sum after the mixer; a layer without a feed-forward no product of
    one; experts without a gate no ``gate``."""
    cfg = config(remat=True)
    b, t = TOKENS.shape
    row = b * t * 4
    assert kept_bytes(cfg, b, t, 0) == {ssd.SAVED_Y: row * 32}
    assert kept_bytes(cfg, b, t, 1) == kept_bytes(cfg, b, t, 3) == {
        moe.SAVED_EXPERTS: b * t * 3 * 4, moe.SAVED_ORDER: b * t * 3 * 4}
    q = row * 4 * 8
    assert kept_bytes(cfg, b, t, 2) == {
        SAVED_NAMES[0]: q, SAVED_NAMES[1]: b * t * 4 * 4,
        SAVED_INPUT_NAMES[0]: q, SAVED_INPUT_NAMES[1]: q // 2,
        SAVED_INPUT_NAMES[2]: q // 2}
    for layer in range(4):
        assert KEPT_SUM not in kept_bytes(cfg, b, t, layer)
    assert kept_products(cfg, 0) == [((KEPT_IN,), 32)]
    assert kept_products(cfg, 2) == []
    # 3 x 4 / 16 of the 3 slots a token exist at an even router
    assert kept_products(cfg, 1) == [
        ((KEPT_UP,), 32), ((moe.PRODUCT_UP,), 32 * 0.25),
        ((moe.PRODUCT_DOWN,), 24 * 0.25)]
    assert kept_bytes(cfg, b, t, 0, (KEPT_IN,)) == {KEPT_IN: row * 132}
    assert kept_bytes(cfg, b, t, 1, (KEPT_UP, moe.PRODUCT_UP,
                                     moe.PRODUCT_DOWN)) == {
        KEPT_UP: row * 48, moe.PRODUCT_UP: row * 3 * 24,
        moe.PRODUCT_DOWN: row * 3 * 32}
    for absent in ((KEPT_GATE,), (moe.PRODUCT_GATE,)):
        with pytest.raises(KeyError):
            kept_bytes(cfg, b, t, 1, absent)
    # one list for the policy: the scan's name, the kernel's, the
    # routing's
    assert set(ssd.SAVED_NAMES + SAVED_NAMES + moe.SAVED_NAMES) <= set(
        kept_names(cfg))
    assert set(kept_names(cfg.at(1))) & set(
        ssd.SAVED_NAMES + SAVED_NAMES) == set()


@pytest.mark.parametrize("room", [False, True], ids=["no-limit", "room"])
def test_kept_bytes_are_what_the_backward_pass_is_handed(room, monkeypatch):
    """``kept_bytes`` against ``jax.ad_checkpoint``'s own account of the
    residuals, as ``tests/test_transformer_kept.py`` holds the blocks of
    two branches: what the recomputed blocks hand the backward pass
    beyond a plain ``nn.remat``'s has the bytes the function gives by
    name, every layer its own branch's."""
    from jax._src.ad_checkpoint import saved_residuals

    def handed():
        cfg, params, loss = model_and_loss(remat=True)
        return cfg, [int(np.prod(aval.shape)) * aval.dtype.itemsize
                     for aval, _ in saved_residuals(loss, params)]

    if room:
        with_room(monkeypatch)
    cfg, named = handed()
    keep_nothing(monkeypatch)
    _, plain = handed()
    plan = kept_plan(cfg, *TOKENS.shape, ROOM if room else None)
    more = [n for layer in range(cfg.n_layers) for n in {
        **kept_bytes(cfg, *TOKENS.shape, layer),
        **kept_bytes(cfg, *TOKENS.shape, layer, plan.names[layer])}.values()]
    # a routed block that decides once does not hand its bias's row on
    unread = Counter({BIAS[0].nbytes: 2})
    assert Counter(plain + more) - Counter(named) == unread
    # ``one_hot`` reads the kept experts inside a jitted function, which
    # hands them on a second time in this account (the same array)
    twice = Counter({kept_bytes(cfg, *TOKENS.shape, 1)[
        moe.SAVED_EXPERTS]: 2})
    assert Counter(named) - Counter(plain + more) == twice


def test_parameter_bytes_by_block_and_the_plans_moments(monkeypatch):
    """``parameter_bytes`` counts every block's own branch; the plan's
    moments charge each layer what ITS branch keeps and its gradient
    once the backward pass has passed it."""
    cfg, params, _ = model_and_loss(remat=True)
    whole, blocks = parameter_bytes(cfg, TOKENS.shape[1])
    assert whole == sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert blocks == tuple(
        sum(leaf.nbytes for leaf in jax.tree.leaves(params[f"block_{i}"]))
        for i in range(4))
    assert blocks[1] == blocks[3] > blocks[0] > blocks[2]
    b, t = TOKENS.shape
    plan = kept_plan(cfg, b, t, ROOM)
    x = b * t * 32 * 4
    assert plan.kept == tuple(
        x + sum(kept_bytes(cfg, b, t, i).values())
        + sum(kept_bytes(cfg, b, t, i, plan.names[i]).values())
        for i in range(4))
    moments = dict(plan.moments)
    assert list(moments) == ["head", "block 3", "block 2", "block 1",
                             "block 0", "end"]
    # between two moments: the block passed gives its gradient and no
    # longer holds what it kept; a block's own moment counts what every
    # block keeps of it once more (made again) and its products' results
    # and their cotangents: of its one branch
    def own(i):
        return x + sum(kept_bytes(cfg, b, t, i).values()) + 2 * sum(
            kept_bytes(cfg, b, t, i, plan.names[i]).values())

    for later, earlier in ((3, 2), (2, 1), (1, 0)):
        assert moments[f"block {earlier}"] - moments[f"block {later}"] == (
            blocks[later] - plan.kept[later] + own(earlier) - own(later))


def test_the_scopes_of_the_mixer_and_of_a_layer_that_is_experts_alone():
    """``mixer/ssm`` with ``in``, ``conv``, ``proj``, ``scan/intra``,
    ``scan/inter`` and ``gate_out`` inside it, in the forward pass, the
    recomputation and the backward pass; ``moe/shared`` inside a block
    that has no mixer; read as ``benchmark/scope_trace.py`` reads a
    compiled step."""
    from horovod_tpu.utils.trace import step_phases

    cfg, params, loss = model_and_loss(remat=True)
    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    instructions, _, _ = step_phases(text)
    found = {}
    for phase, scope in instructions.values():
        found.setdefault(scope, set()).add(phase)
    for part in ("in", "conv", "proj", "scan/intra", "scan/inter",
                 "gate_out"):
        assert f"block/mixer/ssm/{part}" in found, part
    for scope in ("block/mixer/ssm/scan/intra", "block/mixer/ssm/scan/inter",
                  "block/moe/shared", "block/moe/experts",
                  "block/attn/global"):
        assert found[scope] >= {"backward"}, (scope, found[scope])
    assert "recompute" in found["block/mixer/ssm/scan/intra"]
    assert not [scope for scope in found if scope.startswith(
        ("intra", "inter", "scan"))]
    assert " while(" not in "\n".join(
        line for line in text.splitlines() if "/mixer/ssm/" in line)


def test_the_sown_counters_come_from_the_expert_layers_in_order():
    """``apply_with_aux`` finds the two expert layers' counters (blocks
    1 and 3 of four) and hands each its own row of the bias."""
    cfg, params, _ = model_and_loss()
    _, aux = apply_with_aux(Transformer(cfg), params, TOKENS,
                            router_bias=BIAS)
    assert aux["tokens_per_expert"].shape == (2, 16)
    assert aux["moe_layers"] == 2
    np.testing.assert_array_equal(
        jnp.sum(aux["tokens_per_expert"], -1), [2 * 24 * 3] * 2)
    _, other = apply_with_aux(Transformer(cfg), params, TOKENS,
                              router_bias=BIAS.at[1].set(-BIAS[1]))
    np.testing.assert_array_equal(other["tokens_per_expert"][0],
                                  aux["tokens_per_expert"][0])
    assert not np.array_equal(other["tokens_per_expert"][1],
                              aux["tokens_per_expert"][1])


# ------------------------------------------------- the cell's own layers
@pytest.fixture(scope="module")
def cell_config():
    import importlib.util

    path = os.path.join(REPO, "benchmark", "models", "nemotron_h_lm.py")
    spec = importlib.util.spec_from_file_location(
        "hvd_benchmark_one_branch_nemotron_h_lm", path)
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b.json")) as f:
        return family._program_config(json.load(f))


def test_the_cells_layers_by_hand(cell_config):
    """2 x 8192 tokens at width 2688 in bfloat16.  A Mamba-2 layer keeps
    its scan's output ``[2, 8192, 4096]`` and, with room, its first
    product (10,304 columns); the attention layer the kernel's output,
    lse, q, k and v; an expert layer its decision (the 6 experts a token
    and the order of the ``16384 x 6`` rows of the buffer) and, with
    room, the shared expert's ``up`` (3,712 columns) and the held
    experts' ``up`` and ``down`` over the buffer; none a sum after a
    mixer."""
    cfg = cell_config
    rows = 2 * 8192
    assert [cfg.at(i).block.attention.__class__.__name__
            for i in range(9)] == ["Mamba2", "NoneType"] * 2 + [
                "Mamba2", "GroupedAttention", "NoneType", "Mamba2",
                "NoneType"]
    assert kept_bytes(cfg, 2, 8192, 0) == {ssd.SAVED_Y: rows * 4096 * 2}
    assert kept_bytes(cfg, 2, 8192, 0, (KEPT_IN,)) == {
        KEPT_IN: rows * (4096 + 6144 + 64) * 2}
    assert kept_bytes(cfg, 2, 8192, 1) == {
        moe.SAVED_EXPERTS: rows * 6 * 4, moe.SAVED_ORDER: rows * 6 * 4}
    assert kept_bytes(cfg, 2, 8192, 1, (KEPT_UP, moe.PRODUCT_UP,
                                        moe.PRODUCT_DOWN)) == {
        KEPT_UP: rows * 3712 * 2, moe.PRODUCT_UP: rows * 6 * 1856 * 2,
        moe.PRODUCT_DOWN: rows * 6 * 2688 * 2}
    assert kept_bytes(cfg, 2, 8192, 5) == {
        SAVED_NAMES[0]: rows * 4096 * 2, SAVED_NAMES[1]: rows * 32 * 4,
        SAVED_INPUT_NAMES[0]: rows * 4096 * 2,
        SAVED_INPUT_NAMES[1]: rows * 256 * 2,
        SAVED_INPUT_NAMES[2]: rows * 256 * 2}
    # 6 x 8 / 128 of the 6 slots a token exist
    assert kept_products(cfg, 1) == [
        ((KEPT_UP,), 2688), ((moe.PRODUCT_UP,), 2688 / 16),
        ((moe.PRODUCT_DOWN,), 1856 / 16)]
    whole, blocks = parameter_bytes(cfg, 8192)
    assert whole == 4 * 666_962_944
    assert blocks == (4 * 38_744_896, 4 * 100_125_312) * 2 + (
        4 * 38_744_896, 4 * 23_399_040, 4 * 100_125_312, 4 * 38_744_896,
        4 * 100_125_312)
    plan = kept_plan(cfg, 2, 8192, 16_911_433_728)
    assert plan.peak <= plan.budget
    assert [names for names in plan.names if KEPT_IN in names] == [
        (KEPT_IN,)] * 4
    assert plan.names[5] == ()
    text = str(plan)
    assert "0: 1 (+ mixer_in)" in text and "5: 0" in text
