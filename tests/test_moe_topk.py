"""``parallel/moe.py:topk_moe``, the dropless top-k expert layer,
against a per-token loop in float32: output, every gradient leaf, both
auxiliary terms and the counter; dropless under skew; the weights not
renormalised; nothing recompiled when the load shifts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel.moe import (SCORINGS, init_moe_params,
                                      moe_param_shapes, topk_moe, topk_route)

N, D, F, E = 24, 16, 8, 6


def per_token_loop(x, params, k, held=(0, E)):
    """One token at a time, one expert at a time: ``(out, load-balancing
    term, z term, tokens per expert)``; of the chosen experts only the
    ``held = (first, count)`` add to ``out``."""
    router, wg, wi, wo = (params[n]["kernel"]
                          for n in ("router", "wg", "wi", "wo"))
    first, count = held
    outs, probs, lse, counts = [], [], [], jnp.zeros((E,), jnp.int32)
    for t in range(x.shape[0]):
        logits = x[t] @ router
        p = jax.nn.softmax(logits)
        chosen = jnp.argsort(-p)[:k]
        out = jnp.zeros_like(x[t])
        for j in range(k):
            e = chosen[j]
            mine = (e >= first) & (e < first + count)
            out += mine * p[e] * (
                (jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wi[e])) @ wo[e])
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


@pytest.mark.parametrize("held", [None, (0, 3)], ids=["all", "held"])
def test_a_shifted_load_compiles_nothing_new(held):
    """Static shapes; group sizes, and with ``held`` the rows that
    exist (0 of them after the shift, the whole buffer before), as
    data."""
    x, params, _ = inputs(7)
    fn = jax.jit(jax.grad(lambda x, p: jnp.sum(
        topk_moe(x, p, k=2, held=held)[0]), (0, 1)))
    if held is not None:
        params = share(params, *held)
    fn(jnp.abs(x), {**params, "router": skewed(params, (0, 1))["router"]})
    fn(x, params)
    fn(jnp.abs(x), {**params, "router": skewed(params, (4, 5))["router"]})
    assert fn._cache_size() == 1


# ------------------------------- scoring, bias, renormalisation, scale
def test_default_route_is_olmoes_bit_for_bit():
    """(e) softmax, the probabilities as they are, no bias, no scale:
    what ``topk_route`` was before it took arguments."""
    logits = jax.random.normal(jax.random.PRNGKey(8), (N, E))
    weights, experts, aux = jax.jit(lambda lg: topk_route(lg, 3))(logits)
    want_w, want_e = jax.jit(
        lambda lg: jax.lax.top_k(jax.nn.softmax(lg, axis=-1), 3))(logits)
    np.testing.assert_array_equal(weights, want_w)
    np.testing.assert_array_equal(experts, want_e)
    explicit = jax.jit(lambda lg: topk_route(
        lg, 3, scoring="softmax", bias=None, renormalize=False,
        scale=1.0))(logits)
    np.testing.assert_array_equal(explicit[0], weights)
    np.testing.assert_array_equal(explicit[2]["load_balancing"],
                                  aux["load_balancing"])


def route_loop(logits, k, bias, scale):
    """One token at a time: sigmoid scores, the k largest of score +
    bias, weights = scale * score / sum of the chosen scores."""
    weights, experts = [], []
    for row in np.asarray(logits, np.float64):
        s = 1 / (1 + np.exp(-row))
        chosen = np.argsort(-(s + np.asarray(bias)), kind="stable")[:k]
        experts.append(chosen)
        weights.append(scale * s[chosen] / s[chosen].sum())
    return np.stack(weights), np.stack(experts)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_sigmoid_biased_renormalised_scaled_route_matches_the_loop(k):
    logits = jax.random.normal(jax.random.PRNGKey(9), (N, E))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(10), (E,))
    weights, experts, aux = topk_route(
        logits, k, scoring="sigmoid", bias=bias, renormalize=True,
        scale=2.5)
    want_w, want_e = route_loop(logits, k, bias, 2.5)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    assert int(aux["tokens_per_expert"].sum()) == N * k


def test_the_bias_decides_the_choice_and_enters_no_weight():
    """(f) a large bias on one expert sends every token to it; its
    weight is still its score, renormalised with the others', and the
    same experts chosen without the bias weigh the same."""
    logits = jax.random.normal(jax.random.PRNGKey(11), (N, E))
    s = jax.nn.sigmoid(logits)
    bias = jnp.zeros((E,)).at[4].set(10.0)
    weights, experts, aux = topk_route(logits, 2, scoring="sigmoid",
                                       bias=bias, renormalize=True)
    assert np.all(np.asarray(experts[:, 0]) == 4)
    assert int(aux["tokens_per_expert"][4]) == N
    chosen = jnp.take_along_axis(s, experts, -1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1,
                                                            keepdims=True),
                               rtol=1e-6)
    # a bias that changes no choice changes nothing
    flat = topk_route(logits, 2, scoring="sigmoid", renormalize=True)
    same = topk_route(logits, 2, scoring="sigmoid", renormalize=True,
                      bias=jnp.full((E,), 0.25))
    np.testing.assert_array_equal(same[1], flat[1])
    np.testing.assert_array_equal(same[0], flat[0])
    # and it gets no gradient through the weights
    grad = jax.grad(lambda b: jnp.sum(topk_route(
        logits, 2, scoring="sigmoid", bias=b, renormalize=True)[0] ** 2))(
            bias)
    assert not np.any(np.asarray(grad))


def gathered_route(logits, k, scoring, bias, renormalize):
    """The weights as ``topk_route`` read them before it compared: the
    scores of the chosen experts by ``take_along_axis``."""
    probs = SCORINGS[scoring](logits)
    _, experts = jax.lax.top_k(probs + bias, k)
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


@pytest.mark.parametrize("renormalize", [False, True],
                         ids=["as_is", "renormalized"])
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("k", [4, 8, 10])
@pytest.mark.parametrize("e", [64, 256])
def test_weights_read_by_comparison_are_the_gathered_ones_bit_for_bit(
        e, k, scoring, renormalize):
    """With a bias the chosen scores are read off ``probs`` by comparison
    over the E lanes (one term of each sum non-zero) and not by a gather
    of scalars: weights and the gradient with respect to the logits equal
    ``take_along_axis``'s and its scatter-add's to the last bit in
    float32, under a bias that changes the choice."""
    logits = jax.random.normal(jax.random.PRNGKey(e + k), (40, e))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(12), (e,))
    ct = jax.random.normal(jax.random.PRNGKey(13), (40, k))

    def compared(lg):
        weights, experts, _ = topk_route(
            lg, k, scoring=scoring, bias=bias, renormalize=renormalize)
        return jnp.sum(weights * ct), (weights, experts)

    def gathered(lg):
        weights, experts = gathered_route(lg, k, scoring, bias, renormalize)
        return jnp.sum(weights * ct), (weights, experts)

    (_, (got_w, got_e)), got_g = jax.value_and_grad(
        compared, has_aux=True)(logits)
    (_, (want_w, want_e)), want_g = jax.value_and_grad(
        gathered, has_aux=True)(logits)
    assert got_w.dtype == jnp.float32 and got_e.dtype == want_e.dtype
    np.testing.assert_array_equal(got_e, want_e)
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_g, want_g)
    assert np.any(np.asarray(got_g))
    unbiased = topk_route(logits, k, scoring=scoring)[1]
    assert np.any(np.sort(got_e, -1) != np.sort(unbiased, -1))


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_without_a_bias_the_weights_are_top_ks_own_values(scoring):
    """The branch without a bias is as it was: the weights are the
    values ``top_k`` returns and their gradient is ``top_k``'s."""
    logits = jax.random.normal(jax.random.PRNGKey(14), (N, 64))
    weights, experts, _ = topk_route(logits, 8, scoring=scoring)
    want_w, want_e = jax.lax.top_k(SCORINGS[scoring](logits), 8)
    np.testing.assert_array_equal(weights, want_w)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_array_equal(
        jax.grad(lambda lg: jnp.sum(
            topk_route(lg, 8, scoring=scoring)[0] ** 2))(logits),
        jax.grad(lambda lg: jnp.sum(
            jax.lax.top_k(SCORINGS[scoring](lg), 8)[0] ** 2))(logits))


def test_balance_bias_moves_by_the_rule():
    """(f) ``b_e += rate * sign(mean(c) - c_e)``: down where loaded
    above the mean, up below it, still at it; a row a layer."""
    from horovod_tpu.parallel.moe import balance_bias

    counts = jnp.asarray([[8, 0, 4, 4], [1, 1, 1, 13]], jnp.int32)
    bias = jnp.asarray([[0.0, 0.0, 0.5, -0.5], [0.1, 0.1, 0.1, 0.1]])
    moved = balance_bias(bias, counts, 0.001)
    np.testing.assert_allclose(
        moved, [[-0.001, 0.001, 0.5, -0.5], [0.101, 0.101, 0.101, 0.099]],
        rtol=1e-6)


# --------------------------------------------------- the experts held
def share(params, first, count):
    """The weights one device holds: all of the router, ``count``
    experts from ``first``."""
    return {"router": params["router"], **{
        name: {"kernel": params[name]["kernel"][first:first + count]}
        for name in ("wg", "wi", "wo")}}


ROUTES = {"olmoe": {}, "sigmoid": dict(scoring="sigmoid", renormalize=True,
                                       scale=2.5)}


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("k,count", [(2, 3), (2, 2), (3, 2), (4, 1), (1, 6)])
def test_the_shares_add_up_to_the_whole_layer(k, count, route):
    """(c) ``E / count`` devices, each routing over all E and computing
    its own experts' part: the parts sum to the layer that holds every
    expert, output and every gradient leaf, also where a device holds
    fewer experts than a token has slots (``count < k``: the buffer is
    ``N * count`` rows)."""
    x, params, ct = inputs(12)
    kwargs = ROUTES[route]

    def whole(x, params):
        return jnp.vdot(topk_moe(x, params, k=k, **kwargs)[0], ct)

    def shares(x, params):
        return sum(jnp.vdot(topk_moe(
            x, share(params, first, count), k=k, held=(first, count),
            **kwargs)[0], ct) for first in range(0, E, count))

    full, aux = topk_moe(x, params, k=k, **kwargs)
    parts = [topk_moe(x, share(params, first, count), k=k,
                      held=(first, count), **kwargs)
             for first in range(0, E, count)]
    np.testing.assert_allclose(sum(out for out, _ in parts), full,
                               rtol=1e-5, atol=1e-6)
    for _, part_aux in parts:
        # routing is over all E on every device
        assert part_aux["tokens_per_expert"].shape == (E,)
        np.testing.assert_array_equal(part_aux["tokens_per_expert"],
                                      aux["tokens_per_expert"])
    got = jax.grad(shares, (0, 1))(x, params)
    want = jax.grad(whole, (0, 1))(x, params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("held,k", [((2, 2), 2), ((0, 3), 2), ((3, 1), 1)])
def test_every_slot_on_held_experts_and_nothing_is_dropped(held, k):
    """(d) the router rigged so that every token's k slots land on
    experts this device holds: the buffer's bound ``N * min(k, count)``
    is met exactly, and the result is the per-token loop's over all
    experts (the others get no token)."""
    first, count = held
    favourites = tuple(range(first, first + k))
    x, params, ct = inputs(13)
    x, params = jnp.abs(x), skewed(params, favourites)
    out, aux = topk_moe(x, share(params, first, count), k=k, held=held)
    want, _, _, counts = per_token_loop(x, params, k)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(aux["tokens_per_expert"], counts)
    assert int(aux["tokens_per_expert"][first:first + count].sum()) == N * k

    def system_held(x, params):
        return jnp.vdot(topk_moe(x, share(params, first, count), k=k,
                                 held=held)[0], ct)

    def loop_all(x, params):
        return jnp.vdot(per_token_loop(x, params, k)[0], ct)

    got = jax.grad(system_held, (0, 1))(x, params)
    want = jax.grad(loop_all, (0, 1))(x, params)
    for name in ("wg", "wi", "wo"):
        np.testing.assert_allclose(
            got[1][name]["kernel"], want[1][name]["kernel"], rtol=1e-4,
            atol=1e-5)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)


def test_no_slot_on_a_held_expert_gives_zeros_and_no_nan():
    """The other extreme: nothing routed here.  The grouped products
    have no row, and what they leave undefined is zeroed."""
    x, params, ct = inputs(14)
    x, params = jnp.abs(x), skewed(params, (0, 1))
    fn = lambda x, p: topk_moe(x, share(p, 3, 3), k=2, held=(3, 3))[0]
    assert not np.any(np.asarray(fn(x, params)))
    grads = jax.grad(lambda x, p: jnp.vdot(fn(x, p), ct), (0, 1))(x, params)
    for leaf in jax.tree.leaves(grads):
        assert not np.any(np.asarray(leaf))


CHUNK = 10  # rows a chunk in the test below; 48 rows in the buffer


def flagged(x, params, held, h):
    """``x`` and a router changed so that exactly the first ``h`` tokens
    have one slot on a held expert (the first held one) and no other
    token has any: the features are positive, the other held experts'
    logits negative, the first one's large where the token is flagged
    and 0 elsewhere, everyone else's positive."""
    first, count = held
    x = jnp.abs(x) + 0.1
    x = x.at[:, 0].set(jnp.where(jnp.arange(N) < h, 5.0, 0.0))
    kernel = jnp.abs(params["router"]["kernel"]).at[0].set(0.0)
    kernel = kernel.at[:, first:first + count].set(-1.0)
    kernel = kernel.at[:, first].set(0.0).at[0, first].set(4.0)
    return x, {**params, "router": {"kernel": kernel}}


@pytest.mark.parametrize("unwritten", [0.0, float("nan")],
                         ids=["zeros", "nan"])
@pytest.mark.parametrize("h", [0, 1, CHUNK, CHUNK + 1, 2 * N],
                         ids=["none", "one", "a-chunk", "a-chunk-and-one",
                              "the-bound"])
def test_held_rows_of_any_extent_match_the_per_token_loop(h, unwritten,
                                                          monkeypatch):
    """The passes over the buffer run over ``H`` rows, a value of the
    step: output, ``aux`` and every gradient leaf are the per-token
    loop's at no row, one, exactly a chunk, a chunk and one, and the
    bound (every slot on a held expert; the last chunk overlaps the one
    before it), recomputed as the cell runs it.  What the rows that
    nobody writes hold (anything, on the chip) reaches no result."""
    from horovod_tpu.parallel import moe

    monkeypatch.setattr(moe, "_CHUNK_BYTES", CHUNK * D * 4)
    monkeypatch.setattr(moe, "_unwritten", lambda after, shape, dtype:
                        jnp.full(shape, unwritten, dtype))
    held, k = (4, 2), 2
    first, count = held
    x, params, ct = inputs(15)
    if h == 2 * N:
        x, params = jnp.abs(x), skewed(params, (4, 5))
    else:
        x, params = flagged(x, params, held, h)

    @jax.checkpoint
    def layer(x, params):
        out, aux = topk_moe(x, share(params, *held), k=k, held=held)
        return (out, aux["load_balancing"], aux["router_z"]), aux

    out, aux = jax.jit(layer)(x, params)
    want = per_token_loop(x, params, k, held)
    np.testing.assert_allclose(out[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[1], want[1], rtol=1e-6)
    np.testing.assert_allclose(out[2], want[2], rtol=1e-6)
    np.testing.assert_array_equal(aux["tokens_per_expert"], want[3])
    assert aux["held_rows"].dtype == jnp.int32
    assert int(aux["held_rows"]) == h == int(
        aux["tokens_per_expert"][first:first + count].sum())

    got = jax.jit(jax.grad(weighed(lambda x, p: layer(x, p)[0], ct),
                           (0, 1)))(x, params)
    want = jax.grad(weighed(
        lambda x, p: per_token_loop(x, p, k, held)[:3], ct), (0, 1))(
            x, params)
    assert set(got[1]) == {"router", "wg", "wi", "wo"}
    for name in got[1]:
        np.testing.assert_allclose(
            got[1][name]["kernel"], want[1][name]["kernel"], rtol=1e-4,
            atol=1e-5, err_msg=name)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)


# ------------------------------------------------ experts with no gate
def ungated_loop(x, params, k, held=(0, E), **route):
    """A dense loop over the experts, every expert on every token:
    ``sum_e w_e relu(x wi_e)^2 wo_e`` over the chosen AND held experts,
    the weights :func:`topk_route`'s."""
    weights, experts, _ = topk_route(x @ params["router"]["kernel"], k,
                                     **route)
    first, count = held
    out = jnp.zeros_like(x)
    for e in range(first, first + count):
        w = jnp.sum(jnp.where(experts == e, weights, 0), -1)
        hidden = jnp.square(jax.nn.relu(
            x @ params["wi"]["kernel"][e - first]))
        out += w[:, None] * (hidden @ params["wo"]["kernel"][e - first])
    return out


def ungated_inputs(held=None):
    x, params, ct = inputs(seed=4)
    params = dict(params)
    del params["wg"]  # no gate: ``moe_param_shapes(gated=False)``
    if held is not None:
        first, count = held
        params = {"router": params["router"], **{
            n: {"kernel": params[n]["kernel"][first:first + count]}
            for n in ("wi", "wo")}}
    return x, params, ct


@pytest.mark.parametrize("held", [None, (2, 3)], ids=["all", "held"])
@pytest.mark.parametrize("k", [1, 3])
def test_experts_without_a_gate_match_a_dense_loop(k, held):
    """``activation="relu2"``: ``relu(x W_up)^2 W_down``, two grouped
    products and no ``wg``; output and the gradient of every leaf, with
    all the experts and with a share of them, under a sigmoid router
    that renormalises and scales."""
    route = dict(scoring="sigmoid", renormalize=True, scale=2.5)
    x, params, ct = ungated_inputs(held)
    assert set(params) == set(moe_param_shapes(D, F, E)) == {
        "router", "wi", "wo"}

    def ours(x, params):
        return jnp.vdot(topk_moe(x, params, k=k, held=held,
                                 activation="relu2", **route)[0], ct)

    def theirs(x, params):
        return jnp.vdot(ungated_loop(x, params, k, held or (0, E), **route),
                        ct)

    np.testing.assert_allclose(
        topk_moe(x, params, k=k, held=held, activation="relu2", **route)[0],
        ungated_loop(x, params, k, held or (0, E), **route),
        rtol=1e-5, atol=1e-6)
    got = jax.grad(ours, (0, 1))(x, params)
    want = jax.grad(theirs, (0, 1))(x, params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                   err_msg=str(path))
    assert float(jnp.max(jnp.abs(want[1]["wi"]["kernel"]))) > 0


def grouped_products(activation, params, x):
    """``(forward ragged_dot calls, the names the products carry)`` of
    one :func:`topk_moe`'s jaxpr."""
    from horovod_tpu.parallel import moe

    text = str(jax.make_jaxpr(lambda x, p: topk_moe(
        x, p, k=2, activation=activation)[0])(x, params))
    return text.count("= ragged_dot_general["), [
        name for name in moe.PRODUCT_NAMES if f"name={name}" in text]


def test_two_grouped_products_without_a_gate_and_three_with():
    """The gated path is the program it was: three grouped products
    named gate, up and down; without a gate two, and no ``moe_gate``
    (``product_bytes(gated=False)`` has no bytes for one)."""
    from horovod_tpu.parallel import moe

    x, params, _ = inputs()
    assert grouped_products("silu", params, x) == (
        3, list(moe.PRODUCT_NAMES))
    assert grouped_products("relu", params, x) == (
        3, list(moe.PRODUCT_NAMES))
    x, params, _ = ungated_inputs()
    assert grouped_products("relu2", params, x) == (
        2, [moe.PRODUCT_UP, moe.PRODUCT_DOWN])
    with_gate, share = moe.product_bytes(64, 6, 32, 24, 2, (0, 8, 128))
    without, same = moe.product_bytes(64, 6, 32, 24, 2, (0, 8, 128),
                                      gated=False)
    assert share == same == 6 * 8 / 128 / 6
    assert set(with_gate) - set(without) == {moe.PRODUCT_GATE}
    assert all(without[name] == with_gate[name] for name in without)
    with pytest.raises(ValueError, match="activation 'gelu' is none of"):
        topk_moe(x, params, k=2, activation="gelu")
