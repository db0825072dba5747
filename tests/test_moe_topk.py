"""``parallel/moe.py:topk_moe``, the dropless top-k expert layer,
against a per-token loop in float32: output, every gradient leaf, both
auxiliary terms and the counter; dropless under skew; the weights not
renormalised; nothing recompiled when the load shifts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel.moe import (init_moe_params, moe_param_shapes,
                                      topk_moe, topk_route)

N, D, F, E = 24, 16, 8, 6


def per_token_loop(x, params, k):
    """One token at a time, one expert at a time: ``(out, load-balancing
    term, z term, tokens per expert)``."""
    router, wg, wi, wo = (params[n]["kernel"]
                          for n in ("router", "wg", "wi", "wo"))
    outs, probs, lse, counts = [], [], [], jnp.zeros((E,), jnp.int32)
    for t in range(x.shape[0]):
        logits = x[t] @ router
        p = jax.nn.softmax(logits)
        chosen = jnp.argsort(-p)[:k]
        out = jnp.zeros_like(x[t])
        for j in range(k):
            e = chosen[j]
            out += p[e] * ((jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wi[e]))
                           @ wo[e])
            counts = counts.at[e].add(1)
        outs.append(out)
        probs.append(p)
        lse.append(jax.nn.logsumexp(logits))
    f = counts / x.shape[0]
    load_balancing = E * jnp.sum(f * jnp.mean(jnp.stack(probs), 0))
    return (jnp.stack(outs), load_balancing,
            jnp.mean(jnp.square(jnp.stack(lse))), counts)


def inputs(seed=0):
    kx, kp, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (N, D)),
            init_moe_params(kp, D, F, E, gated=True),
            jax.random.normal(kc, (N, D)))


def weighed(fn, ct):
    """A scalar that every output has a say in."""
    def loss(x, params):
        out, load_balancing, z = fn(x, params)
        return jnp.vdot(out, ct) + 0.3 * load_balancing + 0.7 * z
    return loss


def system(k):
    def fn(x, params):
        out, aux = topk_moe(x, params, k=k)
        return out, aux["load_balancing"], aux["router_z"]
    return fn


def loop(k):
    return lambda x, params: per_token_loop(x, params, k)[:3]


@pytest.mark.parametrize("k", [1, 2, 3])
def test_output_and_auxiliary_terms_match_the_per_token_loop(k):
    x, params, _ = inputs()
    out, aux = jax.jit(lambda x, p: topk_moe(x, p, k=k))(x, params)
    want, load_balancing, z, counts = per_token_loop(x, params, k)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux["load_balancing"], load_balancing,
                               rtol=1e-6)
    np.testing.assert_allclose(aux["router_z"], z, rtol=1e-6)
    np.testing.assert_array_equal(aux["tokens_per_expert"], counts)


@pytest.mark.parametrize("k", [1, 2])
def test_every_gradient_leaf_matches_the_per_token_loop(k):
    x, params, ct = inputs(1)
    got = jax.jit(jax.grad(weighed(system(k), ct), (0, 1)))(x, params)
    want = jax.grad(weighed(loop(k), ct), (0, 1))(x, params)
    assert set(got[1]) == set(moe_param_shapes(D, F, E, gated=True))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def skewed(params, favourites):
    """A router that sends every token with positive features to
    ``favourites``, in that order, whatever else it holds."""
    kernel = jnp.zeros((D, E))
    for rank, e in enumerate(favourites):
        kernel = kernel.at[:, e].set(1.0 - 0.1 * rank)
    return {**params, "router": {"kernel": kernel}}


@pytest.mark.parametrize("favourites", [(3,), (4, 1)])
def test_dropless_under_skew(favourites):
    """Every token to the same expert(s), the others with no token at
    all: no capacity, so nothing is dropped and the output, the counter
    and the gradients are still the loop's."""
    k = len(favourites)
    x, params, ct = inputs(2)
    x, params = jnp.abs(x), skewed(params, favourites)
    out, aux = topk_moe(x, params, k=k)
    want, _, _, counts = per_token_loop(x, params, k)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    assert [int(c) for c in aux["tokens_per_expert"]] == [
        N if e in favourites else 0 for e in range(E)]
    np.testing.assert_array_equal(aux["tokens_per_expert"], counts)
    # the worst a router can do: E * (1 * P_e) summed over the k
    assert float(aux["load_balancing"]) > k
    got = jax.grad(weighed(system(k), ct), (0, 1))(x, params)
    want = jax.grad(weighed(loop(k), ct), (0, 1))(x, params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)
    # an expert no token visits gets no gradient
    idle = [e for e in range(E) if e not in favourites]
    for name in ("wg", "wi", "wo"):
        assert not np.any(np.asarray(got[1][name]["kernel"])[idle])


@pytest.mark.parametrize("k", [1, 2, 4])
def test_tokens_per_expert_sums_to_the_token_slots(k):
    x, params, _ = inputs(3)
    aux = topk_moe(x.reshape(2, N // 2, D), params, k=k)[1]
    assert aux["tokens_per_expert"].shape == (E,)
    assert aux["tokens_per_expert"].dtype == jnp.int32
    assert int(aux["tokens_per_expert"].sum()) == N * k


def test_weights_are_the_probabilities_as_they_are():
    """``norm_topk_prob`` false: the k weights are not renormalised, so
    they sum to less than 1, and scaling them to 1 changes the result."""
    logits = jax.random.normal(jax.random.PRNGKey(4), (N, E))
    weights, experts, _ = topk_route(logits, 2)
    probs = jax.nn.softmax(logits)
    np.testing.assert_allclose(
        weights, jnp.take_along_axis(probs, experts, -1), rtol=1e-6)
    assert np.all(np.asarray(weights.sum(-1)) < 1 - 1e-3)
    # the largest first, as top-k gives them
    assert np.all(np.asarray(weights[:, 0] >= weights[:, 1]))


def test_uniform_router_gives_the_terms_their_known_values():
    """All logits 0: P_e = 1 / E, so load-balancing = E * sum_e f_e / E
    = k whatever the (tied) choice, and logsumexp = log E."""
    _, _, aux = topk_route(jnp.zeros((N, E)), 2)
    assert float(aux["load_balancing"]) == pytest.approx(2.0)
    assert float(aux["router_z"]) == pytest.approx(np.log(E) ** 2, rel=1e-6)


def test_leading_dimensions_are_folded_and_dtype_is_kept():
    x, params, _ = inputs(5)
    flat, _ = topk_moe(x, params, k=2)
    folded, _ = topk_moe(x.reshape(2, 3, 4, D), params, k=2)
    np.testing.assert_allclose(folded.reshape(N, D), flat, rtol=1e-6)
    half, aux = topk_moe(x.astype(jnp.bfloat16), params, k=2)
    assert half.dtype == jnp.bfloat16
    assert aux["load_balancing"].dtype == jnp.float32
    np.testing.assert_allclose(half.astype(jnp.float32), flat, rtol=0.1,
                               atol=0.05)


def test_router_runs_in_float32_on_a_bfloat16_input():
    """The choice follows the float32 product of the (rounded) input:
    the same experts as float32 routing of that input."""
    x, params, _ = inputs(6)
    x16 = x.astype(jnp.bfloat16)
    logits = x16.astype(jnp.float32) @ params["router"]["kernel"]
    want = topk_route(logits, 2)[2]["tokens_per_expert"]
    got = topk_moe(x16, params, k=2)[1]["tokens_per_expert"]
    np.testing.assert_array_equal(got, want)


def test_a_shifted_load_compiles_nothing_new():
    """Static shapes, group sizes as data."""
    x, params, _ = inputs(7)
    fn = jax.jit(lambda x, p: topk_moe(x, p, k=2))
    fn(x, params)
    fn(jnp.abs(x), skewed(params, (0, 5)))
    assert fn._cache_size() == 1
