"""What a recomputed block keeps from its forward pass
(``models/transformer.py:recomputed``, ``kept_names``, ``kept_bytes``):
beside the flash kernel's output and lse, the sum after attention, the
kernel's q, k and v where none is larger than its output, and latent
attention's two narrow first products, and of a routed layer what its
routing decided (the experts and the sorted order of the slots);
under a loop over passes the kernel's two results alone.  The values
are the ones the recomputation would have made, so nothing changes but
what runs twice."""

import os
import re
import sys
from collections import Counter

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core

from horovod_tpu.models import (BlockSpec, GroupedAttention, LatentAttention,
                                Rotary, TopkExperts, Transformer,
                                TransformerConfig, lm_loss, transformer)
from horovod_tpu.models.transformer import (KEPT_KV_A, KEPT_NAMES, KEPT_Q_A,
                                            KEPT_SUM, kept_bytes, kept_names)
from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.ops.pallas.flash_attention import (SAVED_INPUT_NAMES,
                                                    SAVED_LSE, SAVED_NAMES,
                                                    SAVED_OUT, saved_bytes)
from horovod_tpu.parallel import moe

TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 31)
SIZES = dict(vocab_size=31, n_layers=2, d_model=32, n_heads=4, d_ff=48,
             max_len=16)


def grouped(**attention):
    return BlockSpec(norm="rms", positions="rope", ffn="swiglu",
                     attention=GroupedAttention(
                         kv_heads=2, head_dim=8, gate="softplus",
                         **attention))


KINDS = {
    "grouped_window": dict(block=grouped(heads=6, window=4)),
    "grouped_full": dict(block=grouped(heads=4, rotary=Rotary(
        theta=500000.0, fraction=0.5, factor=8, original_len=8,
        attention_factor=1.2))),
    # score heads of 24 + 8 rotated, wider than the value heads of 16
    "latent": dict(block=BlockSpec(
        norm="rms", positions="rope_pairs", ffn="swiglu",
        attention=LatentAttention(q_rank=24, kv_rank=16, nope_dim=24,
                                  rope_dim=8, v_dim=16))),
    "plain": dict(),
    "looped": dict(passes=2, block=BlockSpec(
        norm="rms", positions="rope", ffn="swiglu",
        norm_placement="sandwich")),
}
# routed under a bias: 3 of 8 experts a token, a shared one beside them
ROUTED = dict(n_experts=8, experts_per_token=3, d_expert=16)
BIAS = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (2, 8))


def routed(held):
    return dict(ROUTED, block=BlockSpec(
        norm="rms", positions="rope", ffn=TopkExperts(
            scoring="sigmoid", renormalize=True, scale=2.5, shared=1,
            held=held)))


KINDS["routed_held"] = routed((2, 4))
KINDS["routed_all"] = routed(None)


def model_and_loss(kind, **changes):
    """``(cfg, params, loss)`` of a kind through the flash kernel;
    parameters off the symmetric start."""
    cfg = TransformerConfig(**{**SIZES, "dtype": jnp.float32,
                               **KINDS[kind], **changes},
                            attn_fn=flash_attention)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([leaf + 0.1 * jax.random.normal(k, leaf.shape)
                             for leaf, k in zip(leaves, keys)])
    bias = {"router_bias": BIAS} if kind.startswith("routed") else {}
    return cfg, params, lambda p: lm_loss(
        model.apply({"params": p}, TOKENS, **bias), TOKENS)


def plain_remat(block, cfg):
    """What ``recomputed`` was before it had a policy."""
    return nn.remat(block)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["grouped_window", "grouped_full", "latent",
                                  "plain", "routed_held", "routed_all"])
def test_recomputed_blocks_give_the_loss_and_gradients_of_kept_ones(
        kind, dtype, monkeypatch):
    """Instruction by instruction (no ``jit`` around the gradient; compiled
    whole, two programs round a sum in different places) every kept array
    is the array the recomputation would have made: against a plain
    ``nn.remat``, which keeps nothing, no bit of the loss or of a gradient
    differs in either type; against ``remat=False`` they agree as
    ``test_transformer_remat_matches_dense`` has it (to the last bit in
    all but the latent kind's loss, with or without this policy), in
    bfloat16 to that type's step."""
    def run(remat):
        _, params, loss = model_and_loss(kind, remat=remat, dtype=dtype)
        return jax.value_and_grad(loss)(params)

    got, want = run(True), run(False)
    monkeypatch.setattr(transformer, "recomputed", plain_remat)
    plain = run(True)
    assert float(jnp.max(jnp.abs(
        want[1]["block_0"]["attn"]["out"]["kernel"]))) > 0
    tolerance = (dict(rtol=2e-5, atol=2e-6) if dtype == jnp.float32
                 else dict(rtol=2 ** -7, atol=2 ** -9))
    for (path, g), w, p in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                               jax.tree.leaves(want), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(g, p, err_msg=str(path))
        np.testing.assert_allclose(g, w, err_msg=str(path), **tolerance)


def recomputation(loss, params):
    """``(primitive, name stack, result shapes)`` of every equation of
    the gradient's jaxpr that the checkpoint makes again (its name stack
    holds ``rematted_computation``, as the compiled step's ``op_name``
    does)."""
    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            yield (eqn.primitive.name, stack,
                   [v.aval.shape for v in eqn.outvars])
            for sub in core.jaxprs_in_params(eqn.params):
                yield from walk(sub, stack)

    return [eqn for eqn in walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr,
                                "")
            if "rematted_computation" in eqn[1]]


def products(again):
    """Where every ``dot_general`` made again stands in its block
    (modules and scopes: ``attn/attn/window/q``), sorted."""
    return sorted(re.search(r"/block_\d+/(.*)$", stack).group(1)
                  for name, stack, _ in again if name == "dot_general")


@pytest.mark.parametrize("kind", ["grouped_window", "grouped_full"])
def test_a_grouped_block_makes_nothing_ahead_of_its_kernel_again(kind):
    """Of a grouped block's products the recomputation holds the gate's
    (its own small one) and the feed-forward's gate and up: no ``q``,
    ``kv`` or ``out`` projection.  The only transpose left turns the
    kept output OUT of the kernel's layout (``[B, H, T, d]`` to ``[B, T,
    H, d]``); none goes into it; and of the rotation the tables alone."""
    cfg, params, loss = model_and_loss(kind, remat=True)
    again = recomputation(loss, params)
    assert products(again) == sorted(
        ["attn/attn/gate/gate", "mlp/gate", "mlp/up"] * cfg.n_layers)
    heads = cfg.block.attention.heads
    assert [shapes for name, _, shapes in again if name == "transpose"] == [
        [(2, 16, heads, 8)]] * cfg.n_layers
    rotated = [(name, shapes) for name, stack, shapes in again
               if "/rope" in stack]
    assert rotated and all(shape[1] == 1 for _, shapes in rotated
                           for shape in shapes if len(shape) == 3), rotated
    assert not [eqn for eqn in again if eqn[0] == "pallas_call"]
    # without the remat the same walk finds nothing
    _, params, loss = model_and_loss(kind)
    assert not recomputation(loss, params)


def test_a_latent_block_makes_its_wide_products_again_and_not_the_narrow():
    """192 over 128 (here 32 over 16): q and k are wider than the
    kernel's output, so the call names none of its inputs and ``q_b``,
    ``kv_b`` run again; ``q_a``, ``kv_a`` and ``out`` do not."""
    cfg, params, loss = model_and_loss("latent", remat=True)
    again = recomputation(loss, params)
    # (the scope's own two are the rotary turns of q and of the shared
    # key, ``x @ P``: made again with ``q_b`` and ``kv_a``'s slice)
    assert products(again) == sorted(
        ["attn/attn/latent/q_b", "attn/attn/latent/kv_b", "mlp/gate",
         "mlp/up"] * cfg.n_layers + ["attn/attn/latent/"] * 2 * cfg.n_layers)
    assert not [eqn for eqn in again if eqn[0] == "pallas_call"]


def test_a_plain_block_makes_its_feed_forward_again_and_nothing_else():
    cfg, params, loss = model_and_loss("plain", remat=True)
    assert products(recomputation(loss, params)) == ["mlp/up"] * cfg.n_layers
    # the kept output comes out of the kernel's layout; nothing goes in
    assert [shapes for name, _, shapes in recomputation(loss, params)
            if name in ("transpose", "pallas_call")] == [
                [(2, 16, 4, 8)]] * cfg.n_layers


def test_a_block_under_passes_keeps_the_kernels_two_results_alone():
    """Under the scan over the passes the policy is ``SAVED_NAMES``: the
    recomputation still holds ``qkv`` and ``out``, as before (and
    ``down``, whose result the sandwich norm after it reads)."""
    cfg, params, loss = model_and_loss("looped", remat=True)
    assert kept_names(cfg) == SAVED_NAMES
    # (``attn/attn/rope/``: the rotary turns of q and k, ``x @ P``)
    assert products(recomputation(loss, params)) == sorted(
        ["attn/qkv", "attn/out", "mlp/gate", "mlp/up", "mlp/down"]
        * cfg.n_layers + ["attn/attn/rope/"] * 2 * cfg.n_layers)
    assert set(kept_bytes(cfg, 2, 16)) == set(SAVED_NAMES)
    one = TransformerConfig(**{**SIZES, **KINDS["looped"], "passes": 1})
    assert kept_names(one) == (SAVED_NAMES + SAVED_INPUT_NAMES + KEPT_NAMES
                               + moe.SAVED_NAMES)


def routing(loss, params):
    """``(top_k, argsorts, other sorts)`` in the gradient's jaxpr.  An
    ``argsort`` is the routing's (the ``N k`` slots by expert, and the
    order for its inverse); the other sorts are by token, inside
    ``_tokens_of_rows``: the combine's and the dispatch's backward
    pass, counted apart."""
    def walk(jaxpr, inside=""):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name, inside
            for sub in core.jaxprs_in_params(eqn.params):
                yield from walk(sub, eqn.params.get("name", inside))

    eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    sorts = [inside for name, inside in eqns if name == "sort"]
    return (len([name for name, _ in eqns if name == "top_k"]),
            sorts.count("argsort"), len(sorts) - sorts.count("argsort"))


@pytest.mark.parametrize("kind,argsorts", [("routed_held", 1),
                                           ("routed_all", 2)])
def test_a_routed_block_decides_once(kind, argsorts):
    """The gradient of a recomputed routed model holds ``top_k`` once a
    layer and the ``argsort`` of the ``N k`` slots once (and that of the
    order, for its inverse, on the path without ``held``): as many as
    with ``remat=False``, and every other sort as often too.  The
    recomputation holds no ``top_k``, no ``sort``, no gather and no
    scatter of scalars: of ``moe/route`` the router's product, the
    score, the comparison and the count."""
    cfg, params, loss = model_and_loss(kind, remat=True)
    _, _, kept = model_and_loss(kind, remat=False)
    got, want = routing(loss, params), routing(kept, params)
    assert got == want
    assert got[:2] == (cfg.n_layers, argsorts * cfg.n_layers)
    assert got[2] == (2 * cfg.n_layers if kind == "routed_held" else 0)
    again = recomputation(loss, params)
    assert not [eqn for eqn in again if eqn[0] in ("top_k", "sort")]
    assert products(again).count("moe/moe/route") == cfg.n_layers
    routes = {name for name, stack, _ in again if "/moe/route" in stack}
    assert {"dot_general", "logistic", "select_n", "eq"} <= routes
    assert not routes & {"gather", "scatter-add"}


def test_an_unnamed_routed_block_decides_twice(monkeypatch):
    """The walk of ``test_a_routed_block_decides_once`` on a block under
    a plain ``nn.remat``: ``top_k`` and the ``argsort`` run again."""
    _, params, loss = model_and_loss("routed_held", remat=True)
    named = routing(loss, params)
    monkeypatch.setattr(transformer, "recomputed", plain_remat)
    _, params, loss = model_and_loss("routed_held", remat=True)
    plain = routing(loss, params)
    assert plain[:2] == (2 * named[0], 2 * named[1])
    assert plain[2] == named[2]


@pytest.mark.parametrize("kind", ["routed_held", "routed_all"])
def test_kept_bytes_of_a_routed_layer_are_what_the_shapes_give(kind):
    cfg, _, _ = model_and_loss(kind, remat=True)
    n, k = TOKENS.size, 3
    want = {moe.SAVED_EXPERTS: n * k * 4}
    if kind == "routed_held":
        want[moe.SAVED_ORDER] = n * min(k, 4) * 4
    else:
        want.update({moe.SAVED_ORDER: n * k * 4, moe.SAVED_INVERSE: n * k * 4})
    got = kept_bytes(cfg, *TOKENS.shape)
    assert {name: got[name] for name in moe.SAVED_NAMES if name in got} == want
    assert list(got)[-len(want):] == [name for name in moe.SAVED_NAMES
                                      if name in want]
    # a buffer narrower than k slots a token: ``min(k, count)``
    narrow = TransformerConfig(**{**SIZES, **routed((0, 2))})
    assert kept_bytes(narrow, 2, 16)[moe.SAVED_ORDER] == n * 2 * 4
    # ``"moe_topk"`` is a routed layer too; a dense layer ahead of the
    # routed ones and a layer under ``passes > 1`` name none of it
    olmoe = TransformerConfig(**{**SIZES, **ROUTED, "block": BlockSpec(
        norm="rms", positions="rope", ffn="moe_topk")})
    assert set(moe.SAVED_NAMES) <= set(kept_bytes(olmoe, 2, 16))
    dense = TransformerConfig(**{**SIZES, **KINDS[kind], "leading_dense": 1})
    assert not set(moe.SAVED_NAMES) & set(kept_bytes(dense, 2, 16, 0))
    assert set(want) <= set(kept_bytes(dense, 2, 16, 1))
    assert not set(moe.SAVED_NAMES) & set(kept_bytes(
        TransformerConfig(**{**SIZES, **KINDS["plain"]}), 2, 16))
    looped = TransformerConfig(**{**SIZES, **KINDS["looped"]})
    assert not set(moe.SAVED_NAMES) & set(kept_bytes(looped, 2, 16))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_kept_bytes_are_what_the_backward_pass_is_handed(kind, monkeypatch):
    """``kept_bytes`` against ``jax.ad_checkpoint``'s own account of the
    residuals: what a recomputed block hands its backward pass beyond a
    plain ``nn.remat``'s has the bytes the function gives by name
    (stacked over the passes under the scan)."""
    from jax._src.ad_checkpoint import saved_residuals

    def handed():
        cfg, params, loss = model_and_loss(kind, remat=True)
        return cfg, [int(np.prod(aval.shape)) * aval.dtype.itemsize
                     for aval, _ in saved_residuals(loss, params)]

    cfg, named = handed()
    monkeypatch.setattr(transformer, "recomputed", plain_remat)
    _, plain = handed()
    more = [cfg.passes * n for layer in range(cfg.n_layers)
            for n in kept_bytes(cfg, *TOKENS.shape, layer).values()]
    beyond = Counter(named) - Counter(plain + more)
    # a routed block that decides once has no use for the bias in its
    # backward pass: the row a plain ``nn.remat`` hands on is not handed
    unread = ({BIAS[0].nbytes: cfg.n_layers} if kind.startswith("routed")
              else {})
    assert Counter(plain + more) - Counter(named) == Counter(unread)
    # a jitted function that reads a kept array hands it on to the
    # backward half: a second time in this account, the same array in the
    # program (the reference LayerNorm's ``_var`` the kept sum,
    # ``one_hot`` the experts, ``floor_divide`` the order)
    twice = {"plain": (KEPT_SUM,), "routed_held": (moe.SAVED_EXPERTS,),
             "routed_all": (moe.SAVED_EXPERTS, moe.SAVED_ORDER)}
    sizes = kept_bytes(cfg, *TOKENS.shape)
    assert beyond == sum((Counter({sizes[name]: cfg.n_layers})
                          for name in twice.get(kind, ())), Counter())


def test_the_kernels_inputs_are_named_as_a_set_by_their_bytes():
    """q, k and v carry names where none is larger than ``out``."""
    def shapes(h, g, d_qk, d_v, t_kv=16):
        return (jax.ShapeDtypeStruct((2, 16, h, d_qk), jnp.bfloat16),
                jax.ShapeDtypeStruct((2, t_kv, g, d_qk), jnp.bfloat16),
                jax.ShapeDtypeStruct((2, t_kv, g, d_v), jnp.bfloat16))

    everything = set(SAVED_NAMES + SAVED_INPUT_NAMES)
    assert set(saved_bytes(*shapes(4, 4, 8, 8))) == everything
    assert set(saved_bytes(*shapes(6, 2, 8, 8))) == everything
    assert set(saved_bytes(*shapes(4, 4, 8, 16))) == everything
    # q and k wider than out; k and v longer than q
    assert set(saved_bytes(*shapes(4, 4, 12, 8))) == set(SAVED_NAMES)
    assert set(saved_bytes(*shapes(4, 4, 8, 8, t_kv=32))) == set(SAVED_NAMES)
    got = saved_bytes(*shapes(6, 2, 8, 8))
    assert got[SAVED_OUT] == got[SAVED_INPUT_NAMES[0]] == 2 * 16 * 6 * 8 * 2
    assert got[SAVED_LSE] == 2 * 16 * 6 * 4
    assert got[SAVED_INPUT_NAMES[1]] == got[SAVED_INPUT_NAMES[2]] == (
        2 * 16 * 2 * 8 * 2)


@pytest.fixture(scope="module")
def cell_config():
    """``cell_config(workload) -> (TransformerConfig, batch, seq)`` of a
    benchmark cell, as its family builds the program's model."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests", "benchmark"))
    try:
        from benchmark_toy import load_by_path
    finally:
        sys.path.pop(0)
    bench = load_by_path(os.path.join(repo, "benchmark", "run.py"),
                         "hvd_benchmark_run_kept")

    def load(workload):
        cell = bench.load_cell(repo, workload)
        return (cell.family._program_config(cell.config),
                cell.job["per_chip_batch"], cell.job["seq_len"])

    return load


Q, K, V = SAVED_INPUT_NAMES
EXPERTS, ORDER = moe.SAVED_NAMES[:2]


@pytest.mark.parametrize("workload,layer,want", [
    # a sliding layer: q [72, 8192, 128] + k, v [8, 8192, 128] = 184.5 MB;
    # routed, 10 of 256 a token with 8 held: experts [8192, 10] and the
    # order of 8192 x 8 rows, int32 = 0.6 MB
    ("laguna_s_2_1-spmd-1chip", 1, {
        SAVED_OUT: 150_994_944, SAVED_LSE: 2_359_296, Q: 150_994_944,
        K: 16_777_216, V: 16_777_216, KEPT_SUM: 50_331_648,
        EXPERTS: 327_680, ORDER: 262_144}),
    # a full layer: q [48, 8192, 128] + k, v = 134.2 MB; layer 0's
    # feed-forward is dense
    ("laguna_s_2_1-spmd-1chip", 0, {
        SAVED_OUT: 100_663_296, SAVED_LSE: 1_572_864, Q: 100_663_296,
        K: 16_777_216, V: 16_777_216, KEPT_SUM: 50_331_648}),
    # 192 over 128: none of the kernel's inputs; 67.1 + 50.3 + 18.9 MB;
    # routed, 8 of 256 a token with 16 held: experts and order [16384,
    # 8] = 1.0 MB
    ("joyai_llm_flash-spmd-1chip", 1, {
        SAVED_OUT: 134_217_728, SAVED_LSE: 2_097_152, KEPT_SUM: 67_108_864,
        KEPT_Q_A: 50_331_648, KEPT_KV_A: 18_874_368,
        EXPERTS: 524_288, ORDER: 524_288}),
    # its leading dense layer
    ("joyai_llm_flash-spmd-1chip", 0, {
        SAVED_OUT: 134_217_728, SAVED_LSE: 2_097_152, KEPT_SUM: 67_108_864,
        KEPT_Q_A: 50_331_648, KEPT_KV_A: 18_874_368}),
    # under the passes: what PR 34 kept, an application
    ("ouro_2_6b-spmd-1chip", 0, {
        SAVED_OUT: 16_777_216, SAVED_LSE: 262_144}),
    # a conv layer has no kernel: the sum after its mixer, 67.1 MB
    ("lfm2_24b_a2b-spmd-1chip", 0, {KEPT_SUM: 67_108_864}),
    # routed, 4 of 64 a token with 8 held: experts and order [16384, 4]
    # = 0.5 MB
    ("lfm2_24b_a2b-spmd-1chip", 4, {
        KEPT_SUM: 67_108_864, EXPERTS: 262_144, ORDER: 262_144}),
    # its attention layer: q [64, 8192, 64] + k, v [16, 8192, 64]
    ("lfm2_24b_a2b-spmd-1chip", 1, {
        SAVED_OUT: 67_108_864, SAVED_LSE: 2_097_152, Q: 67_108_864,
        K: 16_777_216, V: 16_777_216, KEPT_SUM: 67_108_864,
        EXPERTS: 262_144, ORDER: 262_144}),
], ids=["laguna-sliding", "laguna-full", "joyai", "joyai-dense", "ouro",
        "lfm2-conv-dense", "lfm2-conv", "lfm2-full"])
def test_kept_bytes_of_the_cells_blocks_by_hand(cell_config, workload, layer,
                                                want):
    cfg, batch, seq = cell_config(workload)
    assert cfg.remat
    assert kept_bytes(cfg, batch, seq, layer) == want
    if workload.startswith("laguna"):
        # the five blocks: F S S S F, 1.00 GiB more than out and lse, and
        # the four routed ones' decisions, 2.4 MB
        total = sum(sum(n for name, n in kept_bytes(cfg, batch, seq,
                                                    i).items()
                        if name not in SAVED_NAMES) for i in range(5))
        assert total == (2 * 134_217_728 + 3 * 184_549_376 + 5 * 50_331_648
                         + 4 * 589_824)
        assert round(total / 2 ** 30, 2) == 1.0
    if workload.startswith("joyai"):
        # four routed layers of the five; the next-token module's block is
        # a fifth: 5.2 MB a step
        routed = [sum(n for name, n in kept_bytes(cfg, batch, seq, i).items()
                      if name in moe.SAVED_NAMES) for i in range(5)]
        assert routed == [0] + [1_048_576] * 4
