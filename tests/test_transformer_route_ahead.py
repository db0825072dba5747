"""A router that decides from the block's input, before the mixer runs
(``TopkExperts(route_from="input")``, the scope ``route_ahead``), experts
that gate by ReLU (``activation="relu"``) and a grouped-attention layer
that rotates nothing (``GroupedAttention(rotary=None)``): what they
compute, what a recomputed block keeps of them, and that the specs of
before build the program of before."""

import os
import sys
from collections import Counter

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core

from horovod_tpu.models import (BlockSpec, GroupedAttention, Rotary,
                                TopkExperts, Transformer, TransformerConfig,
                                lm_loss, transformer)
from horovod_tpu.models.transformer import (Block, kept_bytes, kept_names,
                                            kept_plan, kept_products)
from horovod_tpu.ops.pallas import flash_attention
from horovod_tpu.parallel import moe

TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 31)
HELD = (2, 4)


def spec(route_from="input", activation="relu", rotary=Rotary(), window=4,
         held=HELD):
    return BlockSpec(
        norm="rms", positions="rope" if rotary else "none",
        ffn=TopkExperts(scoring="softmax", renormalize=True, held=held,
                        route_from=route_from, activation=activation),
        attention=GroupedAttention(heads=6, kv_heads=2, head_dim=8,
                                   window=window, rotary=rotary))


def config(*pattern, **changes):
    return TransformerConfig(**{**dict(
        vocab_size=31, n_layers=2, d_model=32, n_heads=6, head_dim=8, d_ff=48,
        d_expert=16, n_experts=8, experts_per_token=3, max_len=16,
        dtype=jnp.float32, attn_fn=flash_attention,
        pattern=pattern or (spec(rotary=None, window=None), spec())),
        **changes})


def model_and_loss(cfg):
    """``(params, loss)``; parameters off the symmetric start."""
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), TOKENS)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([leaf + 0.1 * jax.random.normal(k, leaf.shape)
                             for leaf, k in zip(leaves, keys)])
    return params, lambda p: lm_loss(model.apply({"params": p}, TOKENS),
                                     TOKENS)


def one_block(route_from):
    """A block alone on a seeded input: ``(x, params, counts)`` with
    ``counts`` the token-slots per expert it sowed."""
    cfg = config(spec(route_from)).at(0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    block = Block(cfg)
    params = block.init(jax.random.PRNGKey(3), x)["params"]
    # off the symmetric start: ln1 and ln2 scale what they read
    params = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), a.shape), params)
    _, state = block.apply({"params": params}, x, mutable=["intermediates"])
    return x, params, state["intermediates"]["moe"][
        "moe_tokens_per_expert"][0]


def test_the_router_reads_the_blocks_input_and_not_what_the_experts_read():
    x, params, counts = one_block("input")
    _, _, aux = moe.topk_route(
        jnp.dot(x.reshape(-1, 32), params["moe"]["router_kernel"],
                precision="highest"), 3, scoring="softmax", renormalize=True)
    np.testing.assert_array_equal(counts, aux["tokens_per_expert"])
    # the same weights, the router fed the second norm's output: today's
    _, _, behind = one_block("ffn_input")
    assert int(jnp.sum(jnp.abs(behind - counts))) > 0
    assert int(jnp.sum(behind)) == int(jnp.sum(counts)) == 2 * 16 * 3


def walk(jaxpr, prefix=""):
    """``(primitive, name stack)`` of every equation."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, stack
        for sub in core.jaxprs_in_params(eqn.params):
            yield from walk(sub, stack)


def test_a_recomputed_block_gives_the_same_and_decides_once():
    """Loss and every gradient, the router's included, with ``remat`` and
    without; the gradient's jaxpr holds ``top_k`` and the sort of the
    slots once a layer either way, none of them in the recomputation,
    where ``route_ahead`` is the router's product and the softmax
    alone."""
    params, kept = model_and_loss(config())
    _, again = model_and_loss(config(remat=True))
    want, got = jax.value_and_grad(kept)(params), jax.value_and_grad(
        again)(params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6,
                                   err_msg=str(path))
    for layer in ("block_0", "block_1"):
        assert float(jnp.max(jnp.abs(
            got[1][layer]["moe"]["router_kernel"]))) > 0

    def decisions(loss):
        eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
        return eqns, Counter(name for name, _ in eqns
                             if name in ("top_k", "sort"))

    eqns, made = decisions(again)
    assert made == decisions(kept)[1]
    assert made["top_k"] == 2
    recomputed = [(name, stack) for name, stack in eqns
                  if "rematted_computation" in stack]
    assert recomputed
    assert not [name for name, _ in recomputed if name in ("top_k", "sort")]
    ahead = {name for name, stack in recomputed if "/route_ahead/" in stack}
    assert "dot_general" in ahead and "exp" in ahead
    assert not ahead & {"gather", "scatter-add"}
    # the decision stands ahead of the mixer in the block as it is traced
    forward = [stack for _, stack in walk(
        jax.make_jaxpr(kept)(params).jaxpr) if "/block_1/" in stack]
    first = next(i for i, s in enumerate(forward) if "/route_ahead" in s)
    assert first < next(i for i, s in enumerate(forward) if "/attn/" in s)
    assert not [s for s in forward if "/moe/route" in s]


def test_kept_bytes_are_what_the_backward_pass_is_handed(monkeypatch):
    """``kept_bytes`` of a layer that decides ahead against
    ``jax.ad_checkpoint``'s own account: what the recomputed block hands
    its backward pass beyond a plain ``nn.remat``'s has the bytes the
    function gives by name, the decision (experts, order) among them
    where it now lies, and with room the products' results too."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = config(remat=True)

    def handed():
        params, loss = model_and_loss(cfg)
        return [int(np.prod(aval.shape)) * aval.dtype.itemsize
                for aval, _ in saved_residuals(loss, params)]

    n, k = TOKENS.size, 3
    for layer in range(2):
        got = kept_bytes(cfg, *TOKENS.shape, layer)
        assert got[moe.SAVED_EXPERTS] == n * k * 4
        assert got[moe.SAVED_ORDER] == n * min(k, HELD[1]) * 4
        assert kept_products(cfg, layer) == [
            ((moe.PRODUCT_GATE, moe.PRODUCT_UP), 32 * 3 * 4 / 8 / 3),
            ((moe.PRODUCT_DOWN,), 16 * 3 * 4 / 8 / 3)]
    room = 2 ** 40
    for device_bytes in (None, room):
        monkeypatch.setattr(transformer, "device_memory_bytes",
                            lambda: (device_bytes, None))
        named = handed()
        plan = kept_plan(cfg, *TOKENS.shape, device_bytes)
        assert plan.names == ((moe.PRODUCT_NAMES if device_bytes else ()),) * 2
        with monkeypatch.context() as patch:
            patch.setattr(transformer, "keeping",
                          lambda block, names: nn.remat(block))
            plain = handed()
        more = [size for layer in range(2) for size in {
            **kept_bytes(cfg, *TOKENS.shape, layer),
            **kept_bytes(cfg, *TOKENS.shape, layer,
                         plan.names[layer])}.values()]
        assert not Counter(plain + more) - Counter(named)
        # ``one_hot`` reads the kept experts inside a jitted function,
        # which hands them on: twice in this account, one array
        assert Counter(named) - Counter(plain + more) == Counter(
            {n * k * 4: 2})
    assert set(moe.SAVED_NAMES) & set(kept_names(cfg))


def test_experts_gated_by_relu_against_a_loop_over_experts():
    d, f, e, k = 16, 12, 8, 3
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (40, d))
    params = moe.init_moe_params(key, d, f, e, gated=True)
    params = jax.tree.map(lambda a: 4 * a, params)
    decision = moe.route_tokens(x, params["router"]["kernel"], k,
                                renormalize=True)
    weights, experts, _ = decision
    for activation, act in (("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        want = jnp.zeros_like(x)
        for i in range(e):
            y = (act(x @ params["wg"]["kernel"][i])
                 * (x @ params["wi"]["kernel"][i])) @ params["wo"]["kernel"][i]
            want += y * jnp.sum(jnp.where(experts == i, weights, 0),
                                -1)[:, None]
        got, _ = moe.topk_moe(x, params, k=k, activation=activation,
                              renormalize=True)
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(want))))
        handed, _ = moe.topk_moe(x, params, k=k, activation=activation,
                                 decision=decision)
        np.testing.assert_array_equal(handed, got)
    relu, _ = moe.topk_moe(x, params, k=k, activation="relu")
    silu, _ = moe.topk_moe(x, params, k=k)
    assert float(jnp.max(jnp.abs(relu - silu))) > 1e-3


def test_a_layer_without_positions_reads_q_and_k_as_projected():
    """In a pattern that mixes it with a rotating kind: the attention
    function is handed ``x W_q`` and ``x W_k`` themselves on the layer
    whose ``rotary`` is ``None``, turned ones on the other, and the
    lowered text has a ``rope`` scope under ``attn/window`` alone."""
    seen = []

    def recording(q, k, v, causal=True, **kwargs):
        seen.append((q, k, kwargs))
        return flash_attention(q, k, v, causal=causal, **kwargs)

    cfg = config(attn_fn=recording)
    assert cfg.at(0).block.attention.rotary is None
    assert cfg.at(1).block.attention.rotary == Rotary()
    params, _ = model_and_loss(cfg)
    model = Transformer(cfg)
    _, state = model.apply({"params": params}, TOKENS,
                           capture_intermediates=lambda m, _: isinstance(
                               m, transformer.RMSNorm))
    # (the model's initialisation called the function too)
    for layer, ((q, k, kwargs), window) in enumerate(zip(seen[-2:],
                                                         (None, 4))):
        normed = state["intermediates"][f"block_{layer}"]["ln1"][
            "__call__"][0]
        attn = params[f"block_{layer}"]["attn"]
        projected = (jnp.einsum("btd,dhk->bthk", normed, attn["q"]["kernel"]),
                     jnp.einsum("btd,dhk->bthk", normed,
                                attn["kv"]["kernel"][:, 0]))
        assert kwargs == ({} if window is None else {"window": window})
        for got, want in zip((q, k), projected):
            if layer == 0:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
            else:
                assert float(jnp.max(jnp.abs(got - want))) > 1e-2
    text = jax.jit(lambda p: model.apply({"params": p}, TOKENS)).lower(
        params).as_text(debug_info=True)
    assert "attn/window/rope" in text and "attn/global/rope" not in text
    assert "attn/global/flash" in text


@pytest.mark.parametrize("field,value", [
    ("route_from", "norm"), ("activation", "gelu")])
def test_an_expert_layer_raises_on_a_value_it_does_not_know(field, value):
    with pytest.raises(ValueError, match=field):
        TopkExperts(**{field: value})


def test_what_else_raises():
    with pytest.raises(ValueError, match="shared"):
        TopkExperts(shared=1, activation="relu")
    with pytest.raises(ValueError, match="rotary"):
        GroupedAttention(heads=4, kv_heads=2, head_dim=8, rotary="rope")
    x = jnp.ones((4, 8))
    params = moe.init_moe_params(jax.random.PRNGKey(0), 8, 4, 4, gated=True)
    with pytest.raises(ValueError, match="activation"):
        moe.topk_moe(x, params, k=2, activation="gelu")
    decision = moe.route_tokens(x, params["router"]["kernel"], 2)
    with pytest.raises(ValueError, match="decide again"):
        moe.topk_moe(x, params, k=2, decision=decision, renormalize=True)


# ---------------------------------------------- the specs of before
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    sys.path.insert(0, os.path.join(HERE, "benchmark"))
    try:
        import benchmark_toy
    finally:
        sys.path.pop(0)
    bench = benchmark_toy.load_by_path(
        os.path.join(benchmark_toy.BENCH, "run.py"), "hvd_benchmark_run_ahead")
    root = benchmark_toy.make_toy_root(tmp_path_factory.mktemp("toy_ahead"))
    return lambda workload: bench.load_cell(root, workload)


@pytest.mark.parametrize("workload,ahead", [
    ("olmoe_1b_7b-spmd-1chip", False), ("joyai_llm_flash-spmd-1chip", False),
    ("laguna_s_2_1-spmd-1chip", False), ("lfm2_24b_a2b-spmd-1chip", False),
    ("smallthinker_21b_a3b-spmd-1chip", True)])
def test_the_routed_families_build_the_program_they_built(toy, workload,
                                                          ahead):
    """A routed family of the benchmark at its toy size: no
    ``route_ahead`` scope, the router's product inside ``moe/route`` on
    the second norm's output, the experts' gate a SiLU; and the family
    that decides ahead the other way round on all three."""
    cell = toy(workload)
    family, config, job = cell.family, cell.config, cell.job
    params, extra = family.init(config, job, jax.random.PRNGKey(0))
    batch = family.make_batch(config, job, jax.random.PRNGKey(1), 1)
    jaxpr = jax.make_jaxpr(
        lambda p: family.loss(config, p, extra, batch)[0])(params)
    made = {}  # variable -> (primitive, name stack) of what made it

    def record(jaxpr, prefix=""):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            for out in eqn.outvars:
                made[out] = (eqn, stack)
            for sub in core.jaxprs_in_params(eqn.params):
                record(sub, stack)

    record(jaxpr.jaxpr)
    stacks = [stack for _, stack in made.values()]
    scope = "/route_ahead/" if ahead else "/moe/route/"
    other = "/moe/route/" if ahead else "/route_ahead/"
    assert not [s for s in stacks if other in s + "/"]
    routers = [eqn for eqn, stack in made.values()
               if scope in stack + "/" and eqn.primitive.name == "dot_general"]
    assert routers
    for eqn in routers:
        read = eqn.invars[0]
        while read in made and made[read][0].primitive.name in (
                "convert_element_type", "reshape"):
            read = made[read][0].invars[0]
        if ahead:  # what the (recomputed) block was handed, no norm's
            assert read not in made or "/ln" not in made[read][1]
        else:
            assert made[read][1].rstrip("/").endswith("/ln2"), made[read][1]
    gates = {eqn.primitive.name for eqn, stack in made.values()
             if "/moe/experts" in stack}
    assert ("max" in gates and "logistic" not in gates) if ahead else (
        "logistic" in gates and "max" not in gates)
