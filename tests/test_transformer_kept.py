"""What a recomputed block keeps from its forward pass
(``models/transformer.py:keeping``, ``kept_names``, ``kept_bytes``):
beside the flash kernel's output and lse, the sum after attention, the
kernel's q, k and v where none is larger than its output, and latent
attention's two narrow first products, and of a routed layer what its
routing decided (the experts and the sorted order of the slots);
under a loop over passes the kernel's two results alone.  And what a
layer keeps beyond that where the device has room (``kept_plan``,
``kept_products``): the results of its products, planned from bytes.
The values are the ones the recomputation would have made, so nothing
changes but what runs twice."""

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
from horovod_tpu.models.transformer import (
    KEPT_GATE, KEPT_IN, KEPT_KV_A, KEPT_KV_B, KEPT_NAMES, KEPT_Q_A, KEPT_Q_B,
    KEPT_QKV, KEPT_SUM, KEPT_UP, ChunkSummaryAttention, MemoryUnit,
    SelectiveScan, ShortConv, kept_bytes, kept_names, kept_plan,
    kept_products)
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
# mixers that are no attention, and attention by chunk summaries (two
# windows of 8 in the 16 positions): what the plan's names are for
KINDS["conv"] = dict(block=BlockSpec(norm="rms", positions="rope",
                                     ffn="swiglu", attention=ShortConv()))
KINDS["hybrid"] = dict(pattern=tuple(
    BlockSpec(positions="none", ffn="swiglu", attention=mixer) for mixer in (
        SelectiveScan(d_inner=48, dt_rank=4, state=4, publishes=True),
        MemoryUnit(48))))
KINDS["chunk"] = dict(block=BlockSpec(
    norm="rms", positions="rope", ffn="swiglu",
    attention=ChunkSummaryAttention(heads=4, head_dim=8, window=8, chunk=4)))
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


def keep_nothing(monkeypatch):
    """Every block under a plain ``nn.remat``, whatever its plan."""
    monkeypatch.setattr(transformer, "keeping",
                        lambda block, names: nn.remat(block))


ROOM = 2 ** 40  # a device on which every product's result fits
# what a layer of each kind keeps beyond ``kept_names`` where all fits, in
# the order the plan adds it (by worth, then as the block makes it)
PRODUCTS = {
    "grouped_full": (KEPT_GATE, KEPT_UP), "plain": (KEPT_UP,),
    "latent": (KEPT_GATE, KEPT_UP, KEPT_Q_B, KEPT_KV_B),
    "conv": (KEPT_IN, KEPT_GATE, KEPT_UP),
    "chunk": KEPT_QKV + (KEPT_GATE, KEPT_UP),
    # the shared expert's pair; all rows of the buffer exist without
    # ``held``, 3 * 4 / 8 of 3 a token with 4 of 8 held
    "routed_all": (KEPT_GATE, KEPT_UP) + moe.PRODUCT_NAMES,
    "routed_held": (KEPT_GATE, KEPT_UP) + moe.PRODUCT_NAMES}


def with_room(monkeypatch, room=ROOM, in_use=None):
    monkeypatch.setattr(transformer, "device_memory_bytes",
                        lambda: (room, in_use))


@pytest.mark.parametrize("kind,dtype,room", [
    (kind, dtype, False)
    for kind in ("grouped_window", "grouped_full", "latent", "plain",
                 "routed_held", "routed_all")
    for dtype in (jnp.float32, jnp.bfloat16)] + [
    (kind, jnp.float32, True) for kind in sorted(PRODUCTS) + ["hybrid"]],
    ids=lambda v: {jnp.float32: "f32", jnp.bfloat16: "bf16", True: "room",
                   False: "no-limit"}.get(v, v))
def test_recomputed_blocks_give_the_loss_and_gradients_of_kept_ones(
        kind, dtype, room, monkeypatch):
    """Instruction by instruction (no ``jit`` around the gradient; compiled
    whole, two programs round a sum in different places) every kept array
    is the array the recomputation would have made: against a plain
    ``nn.remat``, which keeps nothing, no bit of the loss or of a gradient
    differs in either type; against ``remat=False`` they agree as
    ``test_transformer_remat_matches_dense`` has it (to the last bit in
    all but the latent kind's loss, with or without this policy), in
    bfloat16 to that type's step.  With ``room`` the device tells a
    limit under which everything fits, and every layer keeps the results
    of its products too."""
    def run(remat):
        _, params, loss = model_and_loss(kind, remat=remat, dtype=dtype)
        return jax.value_and_grad(loss)(params)

    if room:
        with_room(monkeypatch)
        cfg, _, _ = model_and_loss(kind, remat=True, dtype=dtype)
        assert all(kept_plan(cfg, *TOKENS.shape, ROOM).names)
    got, want = run(True), run(False)
    keep_nothing(monkeypatch)
    plain = run(True)
    assert max(float(jnp.max(jnp.abs(leaf)))
               for leaf in jax.tree.leaves(want[1]["block_0"])) > 0
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
    keep_nothing(monkeypatch)
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


@pytest.mark.parametrize("kind,room", [(kind, False) for kind in sorted(
    set(KINDS) - {"conv", "hybrid", "chunk"})] + [
        (kind, True) for kind in sorted(PRODUCTS)],
    ids=lambda v: {True: "room", False: "no-limit"}.get(v, v))
def test_kept_bytes_are_what_the_backward_pass_is_handed(kind, room,
                                                         monkeypatch):
    """``kept_bytes`` against ``jax.ad_checkpoint``'s own account of the
    residuals: what a recomputed block hands its backward pass beyond a
    plain ``nn.remat``'s has the bytes the function gives by name
    (stacked over the passes under the scan); with ``room`` also the
    results of the layer's products, each once a layer."""
    from jax._src.ad_checkpoint import saved_residuals

    def handed():
        cfg, params, loss = model_and_loss(kind, remat=True)
        return cfg, [int(np.prod(aval.shape)) * aval.dtype.itemsize
                     for aval, _ in saved_residuals(loss, params)]

    if room:
        with_room(monkeypatch)
    cfg, named = handed()
    keep_nothing(monkeypatch)
    _, plain = handed()
    plan = kept_plan(cfg, *TOKENS.shape, ROOM if room else None)
    assert plan.names == ((PRODUCTS[kind] if room else ()),) * cfg.n_layers
    # (the dense reference the CPU takes for chunk-summary attention is no
    # kernel and names no ``out`` and ``lse``)
    more = [cfg.passes * n for layer in range(cfg.n_layers)
            for name, n in {
                **kept_bytes(cfg, *TOKENS.shape, layer),
                **kept_bytes(cfg, *TOKENS.shape, layer,
                             plan.names[layer])}.items()
            if kind != "chunk" or name not in SAVED_NAMES]
    beyond = Counter(named) - Counter(plain + more)
    # a routed block that decides once has no use for the bias in its
    # backward pass: the row a plain ``nn.remat`` hands on is not handed;
    # nor is ``mu [4, 8]``, which made the value summaries that are kept
    unread = ({BIAS[0].nbytes: cfg.n_layers} if kind.startswith("routed")
              else {4 * 8 * 4: cfg.n_layers} if kind == "chunk" else {})
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


V5E = 16_911_433_728  # what a v5e's ``memory_stats()["bytes_limit"]`` says
GATE_UP = (KEPT_GATE, KEPT_UP)
EXPERT_GATE, EXPERT_UP, EXPERT_DOWN = moe.PRODUCT_NAMES


def beside(rows, x):
    """What stands at the head beside the logits and their gradient: the
    stack's output and its norm, and four statistics of a row (the
    norm's two, the loss's log-sum and its cotangent), a lane tile of
    float32 each."""
    return 2 * x + 4 * rows * 128 * 4


def phi4_moments():
    """Phi-4's plan, each moment's sum written out: the mixers' first
    products in blocks 0, 2 and 4 and block 0's SwiGLU pair."""
    state = 3 * 2_788_377_088           # a parameter and Adam's two moments
    grads = (479_580_160, 393_289_216, 479_580_160, 393_289_216,
             419_471_360, 367_064_576)  # the blocks' own parameters
    rest = 256_102_400                  # the tied embedding, the final norm
    logits, x = 16384 * 25008 * 2, 16384 * 2560 * 2
    wide, half = 335_544_320, 167_772_160  # [2, 8192, 10240], [.., 5120]
    ssm, attn, gmu = 419_430_400, 547_880_960, 167_772_160  # rung 0
    kept = (ssm + wide + 2 * wide, attn, ssm + wide, attn, gmu + half, attn)
    # the logits' gradient and the head's input, which wait for the head's
    # weight gradient, and the stream's two cotangents
    waits = logits + 3 * x
    return {
        "block 2": state + rest + sum(grads[3:]) + sum(kept[:3])
        + ssm + 2 * (wide + 2 * wide) + waits,
        "head": state + sum(kept) + 2 * logits + beside(16384, x),
        "block 5": state + rest + sum(kept) + attn + 2 * 2 * wide + waits,
        "block 4": state + rest + grads[5] + sum(kept[:5])
        + gmu + 2 * (half + 2 * wide) + waits,
        "block 3": state + rest + sum(grads[4:]) + sum(kept[:4])
        + attn + 2 * 2 * wide + waits,
        "block 1": state + rest + sum(grads[2:]) + sum(kept[:2])
        + attn + 2 * 2 * wide + waits,
        "block 0": state + rest + sum(grads[1:]) + kept[0]
        + ssm + 2 * (wide + 2 * wide) + waits,
        "end": state + 2_788_377_088}


def laguna_moments():
    """Laguna's: every SwiGLU pair there is (the dense layer's, the
    shared experts') and the held experts' ``gate`` and ``up``."""
    state = 3 * 3_244_068_864
    grads = (629_760_000, 595_451_904, 595_451_904, 595_451_904,
             519_659_520)
    rest = 308_293_632      # embedding, head, final norm
    logits, x = 8192 * 12544 * 2, 8192 * 3072 * 2
    dense, shared, held, down = (402_653_184, 33_554_432, 268_435_456,
                                 402_653_184)
    full, sliding, last = 337_117_184, 439_156_736, 337_707_008  # rung 0
    kept = (full + dense,) + (sliding + shared + held,) * 3 + (
        last + shared + held,)
    routed = 2 * (shared + held + down)
    waits = logits + 3 * x
    return {
        "block 4": state + rest + sum(kept) + last + routed + waits,
        "head": state + sum(kept) + 2 * logits + beside(8192, x),
        "block 3": state + rest + grads[4] + sum(kept[:4])
        + sliding + routed + waits,
        "block 2": state + rest + sum(grads[3:]) + sum(kept[:3])
        + sliding + routed + waits,
        "block 1": state + rest + sum(grads[2:]) + sum(kept[:2])
        + sliding + routed + waits,
        "block 0": state + rest + sum(grads[1:]) + kept[0]
        + full + 2 * dense + waits,
        "end": state + 3_244_068_864}


def joyai_moments():
    """JoyAI's: the dense layer's pair, the five shared experts' and ONE
    ``q_b`` of six; the next-token module's block is the sixth, and the
    backward pass enters it first, while the model's logits wait."""
    state = 3 * 2_721_759_232
    grads = (281_567_232,) + (428_367_872,) * 5
    rest = 298_352_640      # embedding, head, two final norms, the
    #                         module's own norms and projection
    logits, x = 16384 * 16160 * 2, 16384 * 2048 * 2
    q_b, kv_b, dense, shared, held, down = (
        201_326_592, 268_435_456, 469_762_048, 50_331_648, 402_653_184,
        536_870_912)
    first, routed = 339_738_624, 340_787_200  # rung 0
    kept = (first + q_b + dense,) + (routed + shared,) * 5
    made = 2 * (q_b + kv_b + shared + held + down)
    waits = logits + 3 * x
    return {
        "block 5": state + rest + sum(kept) + routed + made + waits + logits,
        "head": state + sum(kept) + 2 * logits + 2 * logits + beside(16384, x),
        **{f"block {i}": state + rest + sum(grads[i + 1:])
           + sum(kept[:i + 1]) + routed + made + waits for i in (4, 3, 2, 1)},
        "block 0": state + rest + sum(grads[1:]) + kept[0]
        + first + 2 * (q_b + kv_b + dense) + waits,
        "end": state + 2_721_759_232}


def smallthinker_moments():
    """SmallThinker's: ``gate`` and ``up`` of the held experts in all four
    blocks, as under four trees, and block 0's ``down`` beside them."""
    state = 3 * 2_626_119_680
    grads = (462_049_280,) * 4
    rest = 777_922_560      # embedding, head, final norm
    logits, x = 16384 * 37984 * 2, 16384 * 2560 * 2
    held, down, rung0 = 301_989_888, 503_316_480, 438_829_056
    kept = (rung0 + held + down,) + (rung0 + held,) * 3
    own = rung0 + 2 * (held + down) + logits + 3 * x
    return {
        "block 3": state + rest + sum(kept) + own,
        "head": state + sum(kept) + 2 * logits + beside(16384, x),
        "block 2": state + rest + grads[3] + sum(kept[:3]) + own,
        "block 1": state + rest + sum(grads[2:]) + sum(kept[:2]) + own,
        "block 0": state + rest + sum(grads[1:]) + kept[0] + own,
        "end": state + 2_626_119_680}


@pytest.mark.parametrize("workload,names,bytes_of,kept,params,moments", [
    # room for every product there is: ``in`` [2, 8192, 6144] of the four
    # conv mixers, the dense layer's pair [2, 8192, 11776] and the experts'
    # three results over the buffer of 65,536 rows; 3.97 GiB more than the
    # 0.785 of rung 0.  The peak, as the backward pass enters the LAST
    # block: 12 bytes a parameter, the gradients of what is no block (the
    # embedding, the head, the final norm), everything kept, and block 4's
    # moment (its input and sum, 2 x 67.1 MB, ``in`` and the experts'
    # three made again and their cotangents, 2 x 872.4 MB, the logits'
    # gradient, the head's input and the stream's two cotangents)
    ("lfm2_24b_a2b-spmd-1chip",
     [(KEPT_IN,) + GATE_UP, moe.PRODUCT_NAMES]
     + [(KEPT_IN,) + moe.PRODUCT_NAMES] * 3,
     {(0, KEPT_IN): 201_326_592, (0, KEPT_GATE): 385_875_968,
      (1, EXPERT_UP): 201_326_592, (2, EXPERT_DOWN): 268_435_456},
     5_104_467_968, 486_062_208,
     {"block 4": 12 * 486_062_208 + 134_225_920 + 5_104_467_968
      + 134_742_016 + 2 * 872_415_232 + 268_435_456 + 3 * 67_108_864}),
    # the three cells below kept nothing while a whole gradient tree was
    # charged at the backward's first block (16 bytes a parameter put rung
    # 0 alone over the line of 14.96 GiB); moment by moment they keep what
    # PR 50's first round kept with three trees, 1.41 / 1.50 / 0.86 GiB,
    # and compile to 14.628 / 14.082 / 14.690 GiB
    ("phi4_mini_flash-spmd-1chip",
     [(KEPT_IN,) + GATE_UP, (), (KEPT_IN,), (), (KEPT_IN,), ()],
     {(0, KEPT_IN): 335_544_320, (0, KEPT_UP): 335_544_320,
      (4, KEPT_IN): 167_772_160, (1, KEPT_GATE): 335_544_320},
     4_160_225_280, 697_094_272, phi4_moments()),
    ("laguna_s_2_1-spmd-1chip",
     [GATE_UP] + [GATE_UP + (EXPERT_GATE, EXPERT_UP)] * 4,
     {(0, KEPT_GATE): 201_326_592, (1, KEPT_GATE): 16_777_216,
      (1, EXPERT_GATE): 134_217_728, (1, EXPERT_DOWN): 402_653_184},
     3_602_907_136, 811_017_216, laguna_moments()),
    # the next-token module's block is the sixth
    ("joyai_llm_flash-spmd-1chip",
     [GATE_UP + (KEPT_Q_B,)] + [GATE_UP] * 5,
     {(0, KEPT_GATE): 234_881_024, (0, KEPT_Q_B): 201_326_592,
      (0, KEPT_KV_B): 268_435_456, (1, KEPT_UP): 25_165_824,
      (1, EXPERT_GATE): 201_326_592},
     2_966_421_504, 680_439_808, joyai_moments()),
    # rung 0 is over the line at every block (its float32 stream's
    # cotangents and the head's input are 3 x 268.4 MB of each): nothing
    # is kept
    ("evabyte_6_5b-spmd-1chip", [()] * 4,
     {(0, KEPT_QKV[0]): 134_217_728, (3, KEPT_UP): 360_710_144},
     3_305_111_552, 821_366_784,
     {"block 3": 12 * 821_366_784 + 47_202_304 + 3_305_111_552
      + 826_277_888 + 2 * (3 * 134_217_728 + 721_420_288)
      + 16384 * 320 * 32 + 3 * 268_435_456}),
    ("smallthinker_21b_a3b-spmd-1chip",
     [moe.PRODUCT_NAMES] + [(EXPERT_GATE, EXPERT_UP)] * 3,
     {(0, EXPERT_GATE): 150_994_944, (0, EXPERT_DOWN): 503_316_480},
     3_466_592_256, 656_529_920, smallthinker_moments()),
    # under the passes the plan is ``kept_names`` whatever the room
    ("ouro_2_6b-spmd-1chip", [()] * 8, {}, 8 * 33_816_576, None, None),
], ids=["lfm2", "phi4", "laguna", "joyai", "evabyte", "smallthinker", "ouro"])
def test_kept_plan_of_the_cells_by_hand(cell_config, monkeypatch, workload,
                                        names, bytes_of, kept, params,
                                        moments):
    """What the plan keeps in each recomputing cell on a v5e, the bytes
    of the new names in closed form, the parameters as ``PERF.md`` section
    4 counts them, the moments of the step by hand (``moments``: the
    first is the one the peak stands at; the whole list where the plan
    is new with the moments) and the plan's shape: rung 0 everywhere
    where the backend tells no limit, never less kept on a larger
    device, the predicted peak within 95% of the device wherever a name
    is kept."""
    cfg, batch, seq = cell_config(workload)
    module = workload.startswith("joyai")
    plan = kept_plan(cfg, batch, seq, V5E, next_token=module)
    assert list(plan.names) == [tuple(n) for n in names]
    assert plan.rungs == tuple(int(bool(n)) for n in names)
    assert sum(plan.kept) == kept
    for (layer, name), n in bytes_of.items():
        assert kept_bytes(cfg, batch, seq, layer, (name,)) == {name: n}
    nothing = kept_plan(cfg, batch, seq, None, next_token=module)
    assert not any(nothing.names) and nothing.peak is None
    assert [k - sum(kept_bytes(cfg, batch, seq, i).values())
            for i, k in enumerate(nothing.kept[:cfg.n_layers])] == [
                batch * seq * cfg.d_model * jnp.dtype(
                    cfg.residual_dtype or cfg.dtype).itemsize] * cfg.n_layers
    if moments is None:
        assert (plan.peak, plan.moment, plan.moments) == (None, None, ())
        return
    found = dict(plan.moments)
    assert {name: found[name] for name in moments} == moments
    assert (plan.moment, plan.peak) == next(iter(moments.items()))
    assert list(found) == ["head"] + [
        f"block {i}" for i in reversed(range(len(names)))] + ["end"]
    assert len(moments) in (1, len(found))
    assert plan.params == 4 * params and plan.resident == 12 * params
    assert found["end"] == 16 * params
    assert plan.budget == int(0.95 * V5E)
    assert plan.peak <= plan.budget or not any(plan.names)
    # the chips of PR 50's runs said 2 MiB less: the same plan
    assert kept_plan(cfg, batch, seq, 16_909_336_064, module).names == (
        plan.names)
    last = None
    for gib in (8, 13, 14.5, 15, 15.5, 15.75, 16, 18, 32):
        at = kept_plan(cfg, batch, seq, int(gib * 2 ** 30), module)
        assert at.peak <= at.budget or not any(at.names)
        assert last is None or sum(at.kept) >= sum(last.kept)
        last = at
    assert not any(kept_plan(cfg, batch, seq, 8 << 30, module).names)
    assert any(kept_plan(cfg, batch, seq, 24 << 30, module).names)
    # what stays on the device beside the parameters and Adam's moments
    # (an accumulator: a fourth tree) is room the plan does not fill
    # (every moment stands a tree higher: where the plan's peak then
    # passes the budget it keeps less, down to nothing where rung 0 is
    # over the line by then; LFM2 has the room and EvaByte nothing to give
    # up)
    held = kept_plan(cfg, batch, seq, V5E, module, resident=16 * params)
    assert held.resident == 16 * params
    if any(plan.names) and plan.peak + 4 * params > plan.budget:
        assert workload.split("_")[0] in ("phi4", "laguna", "joyai",
                                          "smallthinker")
        assert sum(held.kept) < kept
        assert held.peak <= held.budget or not any(held.names)
    else:
        assert workload.split("_")[0] in ("lfm2", "evabyte")
        assert held.names == plan.names
        assert held.peak == plan.peak + 4 * params
    assert kept_plan(cfg, batch, seq, V5E, module,
                     resident=params).resident == 12 * params


def test_a_budget_for_one_layer_of_two_keeps_the_first_alone(monkeypatch):
    """Two equal layers and a device that has room for one ``up``: the
    first layer stands on rung 1, the second on rung 0, the model's
    blocks are built so, and the gradient is the gradient.  What the
    first layer keeps stands at the head and in both blocks' moments,
    what the second would keep at the head and in its own block's alone
    (it is released before the backward pass enters the first), so the
    device is one byte short of the larger of those two with both kept
    (at these sizes the head's: a row's four statistics outweigh its 32
    columns)."""
    cfg, params, loss = model_and_loss("plain", remat=True)
    up = kept_bytes(cfg, *TOKENS.shape, 0, (KEPT_UP,))[KEPT_UP]
    assert up == TOKENS.size * cfg.d_ff * 4
    assert kept_products(cfg, 0) == kept_products(cfg, 1) == [
        ((KEPT_UP,), cfg.d_model)]
    floor = kept_plan(cfg, *TOKENS.shape, 1)
    assert floor.rungs == (0, 0) and floor.peak > floor.budget == 0
    moments = dict(floor.moments)
    assert floor.moment == "head" and floor.peak == moments["head"]
    assert moments["block 1"] < moments["block 0"] < moments["head"]
    both = moments["head"] + 2 * up
    assert floor.peak + up < both
    room = -(-(both - 1) * 20 // 19)  # / 0.95, rounded up
    plan = kept_plan(cfg, *TOKENS.shape, room)
    assert plan.names == ((KEPT_UP,), ()) and plan.rungs == (1, 0)
    assert plan.kept == (floor.kept[0] + up, floor.kept[1])
    assert plan.peak == floor.peak + up <= plan.budget < both
    assert dict(plan.moments) == {
        "head": moments["head"] + up, "block 1": moments["block 1"] + up,
        "block 0": moments["block 0"] + up, "end": moments["end"]}
    assert "0: 1 (+ mlp_up), 1: 0" in str(plan)
    assert "at head of a budget" in str(plan)
    with_room(monkeypatch, room)
    again = products(recomputation(loss, params))
    assert again == ["mlp/up"]
    assert "block_1" in [stack for name, stack, _ in recomputation(
        loss, params) if name == "dot_general"][0]
    want = jax.grad(model_and_loss("plain")[2])(params)
    for g, w in zip(jax.tree.leaves(jax.grad(loss)(params)),
                    jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


def toy_moments(**sizes):
    """``(cfg, floor, up)`` of a plain recomputed toy on ``TOKENS``: its
    plan with nothing kept (a device of one byte) and a layer's ``up``."""
    cfg = TransformerConfig(**{**SIZES, "dtype": jnp.float32, **sizes},
                            remat=True)
    return (cfg, kept_plan(cfg, *TOKENS.shape, 1),
            kept_bytes(cfg, *TOKENS.shape, 0, (KEPT_UP,))[KEPT_UP])


def test_wide_blocks_on_a_short_sequence_are_bound_by_the_end():
    """Where the parameters outweigh the activations the step's largest
    moment is its END, the whole gradient tree beside the state: a device
    one byte short of it keeps nothing, although the head's moment and
    every block's would admit a product (they would with a gradient tree
    charged nowhere), and the line says which moment that was."""
    cfg, floor, up = toy_moments(d_model=128, d_ff=512)
    moments = dict(floor.moments)
    assert floor.moment == "end" and floor.peak == 4 * floor.params
    assert floor.resident == 3 * floor.params
    assert max(n for name, n in moments.items() if name != "end") + 2 * up < (
        moments["end"])
    short = kept_plan(cfg, *TOKENS.shape, (moments["end"] - 1) * 20 // 19)
    assert moments["end"] - 8 < short.budget < moments["end"]
    assert short.peak == moments["end"] and not any(short.names)
    assert "at end of a budget" in str(short)
    room = kept_plan(cfg, *TOKENS.shape, -(-(moments["end"] + 1) * 20 // 19))
    assert room.names == ((KEPT_UP,),) * 2 and room.moment == "end"
    assert room.peak == moments["end"] <= room.budget < moments["end"] + 8


def test_a_large_vocabulary_is_bound_by_the_head():
    """Where the logits outweigh a block the peak stands at the head, the
    logits and their gradient beside everything kept: a product is kept
    while THAT moment has room, and no longer."""
    cfg, floor, up = toy_moments(vocab_size=4096, d_model=16, d_ff=32,
                                 tie_head=True)
    logits = TOKENS.size * 4096 * 4
    assert floor.moment == "head"
    assert floor.peak == 3 * floor.params + sum(floor.kept) + 2 * logits + (
        beside(TOKENS.size, TOKENS.size * 16 * 4))
    one = kept_plan(cfg, *TOKENS.shape, -(-(floor.peak + up) * 20 // 19))
    assert one.names == ((KEPT_UP,), ()) and one.moment == "head"
    assert one.peak == floor.peak + up <= one.budget < floor.peak + 2 * up
    assert "at head of a budget" in str(one)


def test_what_is_placed_on_the_device_is_resident(monkeypatch):
    """``resident`` is what the caller read off the device, and no less
    than the parameters and Adam's two moments: an accumulator beside
    them (a fourth tree in ``bytes_in_use``) takes room a product had,
    ``None`` and a device that holds less than the state plan for the
    state, and the model hands the plan what ``device_memory_bytes``
    says."""
    cfg, floor, up = toy_moments()
    room = -(-(floor.peak + 2 * up) * 20 // 19)
    plain = kept_plan(cfg, *TOKENS.shape, room)
    assert plain.names == ((KEPT_UP,),) * 2
    assert plain.resident == floor.resident == 3 * plain.params
    assert kept_plan(cfg, *TOKENS.shape, room, resident=17) == plain
    accumulating = kept_plan(cfg, *TOKENS.shape, room,
                             resident=4 * plain.params)
    assert accumulating.resident == 4 * plain.params
    assert not any(accumulating.names)
    assert accumulating.peak == floor.peak + plain.params
    assert f"resident {4 * plain.params / 2 ** 30:.3f} GiB" in str(
        accumulating)
    planned, said = [], []
    monkeypatch.setattr(transformer, "kept_plan", lambda *args: planned.append(
        kept_plan(*args)) or planned[-1])
    monkeypatch.setattr(transformer.get_logger(), "warning",
                        lambda line, *values: said.append(line % values))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    # the plan is made with what was read and, to say what that cost,
    # without it: a warning where the layers keep less for it
    for in_use, want, warned in ((None, plain, False),
                                 (plain.params, plain, False),
                                 (4 * plain.params, accumulating, True)):
        with_room(monkeypatch, room, in_use)
        del planned[:], said[:]
        model.apply({"params": params}, TOKENS)
        assert planned[0].names == want.names
        assert planned[1:] == [plain] * (in_use is not None)
        assert planned[0].resident == max(in_use or 0, 3 * plain.params)
        assert len(said) == warned
    assert "the layers keep" in said[0] and "device at rest" in said[0]


@pytest.mark.parametrize("kind,name", [("plain", KEPT_GATE),
                                       ("conv", KEPT_Q_B),
                                       ("latent", KEPT_IN)])
def test_a_name_a_layer_has_no_bytes_for_is_an_error(kind, name):
    """A product listed for a layer (``kept_products``) whose bytes
    ``kept_bytes`` does not know is no free product: asked for by name
    it is a ``KeyError``.  Of ``kept_names(cfg)``, one list for every
    layer of a pattern, a layer gives what it sets."""
    cfg, _, _ = model_and_loss(kind, remat=True)
    with pytest.raises(KeyError, match=name):
        kept_bytes(cfg, *TOKENS.shape, 0, (name,))
    assert set(kept_bytes(cfg, *TOKENS.shape)) < set(kept_names(cfg))
    for group, _ in kept_products(cfg, 0):
        assert set(kept_bytes(cfg, *TOKENS.shape, 0, group)) == set(group)


def test_a_batch_sharded_under_jit_is_planned_whole(monkeypatch):
    """Under a plain ``jit`` whose batch is sharded over four devices the
    trace sees the GLOBAL batch, so the plan reckons all four devices'
    activations (and the parameters whole) on one: it keeps no more than
    the plan of a device's own quarter would, each device of the compiled
    step holds less than one device would of the whole batch, and less
    than the plan predicts.  (This backend's buffers do not follow what
    is kept, so its bytes are no yardstick for one device's plan:
    ``tests/test_chip_compile.py`` holds that against a described
    chip's compiler.)"""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    batch, seq = 64, 16
    cfg = TransformerConfig(vocab_size=64, n_layers=2, d_model=64, n_heads=4,
                            d_ff=512, max_len=seq, remat=True,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3))
    up = kept_bytes(cfg, batch, seq, 0, (KEPT_UP,))[KEPT_UP]
    # room for one layer's ``up`` of the whole batch: for both of a quarter
    room = -(-(kept_plan(cfg, batch, seq, 1).peak + up + up // 2) * 20 // 19)
    with_room(monkeypatch, room)
    planned = []
    monkeypatch.setattr(transformer, "kept_plan", lambda *args: planned.append(
        (args[1:4], kept_plan(*args))) or planned[-1][1])

    def compiled(devices):
        def step(params, state, tokens):  # a trace of its own a call
            grads = jax.grad(lambda p: lm_loss(
                model.apply({"params": p}, tokens), tokens))(params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))

        def over(tree, spec):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                tokens)["params"]
        mem = jax.jit(step, donate_argnums=(0, 1)).lower(
            over(params, P()), over(jax.eval_shape(opt.init, params), P()),
            over(tokens, P("dp"))).compile().memory_analysis()
        return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)

    one, four = compiled(1), compiled(4)
    # (an initialization differentiates nothing and is told no limit)
    assert [args for args, _ in planned if args[2]] == [
        (batch, seq, room)] * 2
    plan = planned[-1][1]
    assert plan.names == ((KEPT_UP,), ())
    assert kept_plan(cfg, batch // 4, seq, room).names == ((KEPT_UP,),) * 2
    assert four < one and four < plan.peak
