"""Expert parallelism: GShard-style switch Mixture-of-Experts.

Absent from the reference (SURVEY §2.7).  TPU-native design follows the
original GShard/Switch recipe, which was *built* for XLA SPMD: routing is
expressed as dense one-hot einsums with static capacity (no gather/scatter,
no dynamic shapes — everything tiles onto the MXU), the expert dimension of
the dispatched activations and of the expert weights is sharded over the
``ep`` mesh axis with sharding constraints, and XLA lowers the dispatch /
combine einsums into ``all_to_all`` collectives over ICI.

Top-1 (switch) routing with capacity factor + auxiliary load-balancing
loss, per Switch Transformer; tokens overflowing an expert's capacity are
passed through the residual (combine weight 0).

:func:`topk_moe` is the second routing path, the one today's open sparse
models use (OLMoE, Moonlight, Trinity): token-choice top-k over a softmax
of all experts, **dropless** (every token-slot is computed whatever the
imbalance: no capacity, no padding), gated SwiGLU experts, and a grouped
matrix product over rows sorted by expert in place of the one-hot
einsums.  Shapes are static and the group sizes are data, so nothing
recompiles when the load shifts.  The router may score by softmax or by
sigmoid, choose through a balancing bias that never enters a weight
(:func:`balance_bias` moves it), renormalise the k weights and scale
them.  With ``held=(first, count)`` the layer routes over all experts
and computes the part of the result that the ``count`` experts it holds
give: one chip's share under expert parallelism, still dropless: the
buffer has the rows of the proven bound and its passes run over the
rows that exist.  The exchange of rows between chips is not here yet:
every token the layer is given is one of this chip's.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def switch_route(router_logits, n_experts, capacity, valid=None):
    """Top-1 routing tensors from ``[T, E]`` logits.

    ``valid`` (optional ``[T]`` mask) excludes padding tokens: they take no
    expert-queue positions, no capacity, and do not enter the balancing
    loss.  Returns (dispatch ``[T, E, C]`` float, combine ``[T, E, C]``
    float, aux_loss scalar).
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                 # [T]
    expert_gate = jnp.max(probs, axis=-1)                   # [T]
    routed_1h = jax.nn.one_hot(expert_idx, n_experts)       # [T, E] pre-drop
    if valid is not None:
        routed_1h = routed_1h * valid[:, None].astype(routed_1h.dtype)

    # position of each token within its expert's queue
    pos_in_expert = (jnp.cumsum(routed_1h, axis=0) - 1.0) * routed_1h  # [T,E]
    keep = pos_in_expert < capacity
    kept_1h = routed_1h * keep                              # drop overflow
    pos = jnp.sum(pos_in_expert * kept_1h, axis=-1)         # [T]

    pos_1h = jax.nn.one_hot(pos.astype(jnp.int32), capacity)            # [T,C]
    dispatch = kept_1h[:, :, None] * pos_1h[:, None, :]     # [T, E, C]
    combine = dispatch * expert_gate[:, None, None]

    # Switch-Transformer load-balance loss: E * sum_e f_e * p_e, with f
    # from the PRE-drop routing decisions — capacity clamping must not
    # hide imbalance from the balancing gradient.
    if valid is not None:
        denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        f = jnp.sum(routed_1h, axis=0) / denom
        p = jnp.sum(probs * valid[:, None].astype(probs.dtype),
                    axis=0) / denom
    else:
        f = jnp.mean(routed_1h, axis=0)    # fraction argmax-routed to e
        p = jnp.mean(probs, axis=0)        # mean router prob for e
    aux_loss = n_experts * jnp.sum(f * p)
    return dispatch, combine, aux_loss


def _constrain_ep(y, mesh):
    """Shard the expert dim (axis 1 of [G, E, C, D]) over ``ep``.

    With an explicit mesh, uses it; otherwise applies a bare-axis-name
    constraint against the mesh ambient at trace time (jit with sharded
    inputs), detected explicitly — a no-op only when there is no ambient
    mesh or it has no ``ep`` axis, so real constraint errors still raise.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        from horovod_tpu.parallel.tensor_parallel import constrain
        return constrain(y, mesh, None, "ep", None, None)
    if "ep" not in jax.sharding.get_abstract_mesh().axis_names:
        return y
    return jax.lax.with_sharding_constraint(y, P(None, "ep", None, None))


def switch_moe(x, params, *, capacity_factor=1.25, group_size=4096,
               mesh=None):
    """Apply a switch-MoE FFN to ``x [..., T, D]`` (leading dims folded).

    params: dict with ``router/kernel [D, E]``, ``wi/kernel [E, D, F]``,
    ``wo/kernel [E, F, D]`` (create with :func:`init_moe_params`).

    Tokens are routed in fixed-size **groups** (GShard recipe): the
    dispatch/combine one-hots are ``[G, S, E, C]`` with per-group capacity
    ``C = ceil(cf*S/E)``, so their footprint is linear in total tokens
    (``T*cf*S``) rather than quadratic, and routing never couples tokens
    across groups.  Expert-dim sharding constraints make XLA partition
    experts over ``ep`` and insert the all_to_alls (explicit ``mesh``, or
    the ambient jit mesh when ``mesh`` is None).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)                                   # [T, D]
    t = xt.shape[0]
    wi = params["wi"]["kernel"]
    wo = params["wo"]["kernel"]
    e = wi.shape[0]

    # Pad T up to a multiple of the group size rather than shrinking the
    # groups (a T with no divisor near group_size would otherwise degrade
    # to 1-2-token groups, making capacity and the balancing loss
    # meaningless).  Pad tokens carry zero router weight: their rows of
    # dispatch/combine are zeroed, so they never consume expert capacity.
    s = min(group_size, t)
    pad = (-t) % s
    if pad:
        xt = jnp.concatenate(
            [xt, jnp.zeros((pad, d), xt.dtype)], axis=0)
    g = (t + pad) // s
    xg = xt.reshape(g, s, d)
    capacity = int(math.ceil(capacity_factor * s / e))

    logits = jnp.einsum("gsd,de->gse", xg,
                        params["router"]["kernel"])         # [G, S, E]
    valid = (jnp.arange(g * s) < t).reshape(g, s)           # pad mask
    dispatch, combine, aux = jax.vmap(
        lambda lg, vg: switch_route(lg, e, capacity, valid=vg))(logits,
                                                                valid)
    aux = jnp.mean(aux)

    expert_in = jnp.einsum("gsd,gsec->gecd", xg.astype(jnp.float32),
                           dispatch)                        # [G, E, C, D]
    expert_in = _constrain_ep(expert_in, mesh)
    h = jnp.einsum("gecd,edf->gecf", expert_in, wi.astype(jnp.float32))
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("gecf,efd->gecd", h, wo.astype(jnp.float32))
    expert_out = _constrain_ep(expert_out, mesh)
    out = jnp.einsum("gecd,gsec->gsd", expert_out, combine)  # [G, S, D]
    out = out.reshape(-1, d)[:t]                            # drop padding
    return out.astype(x.dtype).reshape(orig_shape), aux


def moe_param_shapes(d_model, d_ff, n_experts, gated=False):
    """The parameter contract of :func:`switch_moe` and, with ``gated``
    (one more ``[E, D, F]`` entry, the gate ``wg``), of :func:`topk_moe`
    — single source of truth shared by :func:`init_moe_params` and the
    flax MoE modules."""
    shapes = {
        "router": (d_model, n_experts),
        "wi": (n_experts, d_model, d_ff),
        "wo": (n_experts, d_ff, d_model),
    }
    if gated:
        shapes["wg"] = (n_experts, d_model, d_ff)
    return shapes


def moe_kernel_init(rng, shape, dtype=jnp.float32):
    """Normal(0, 1/fan_in) where fan_in is the contracted (second-to-last)
    dimension."""
    scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


def init_moe_params(rng, d_model, d_ff, n_experts, dtype=jnp.float32,
                    gated=False):
    shapes = moe_param_shapes(d_model, d_ff, n_experts, gated)
    keys = jax.random.split(rng, len(shapes))
    return {name: {"kernel": moe_kernel_init(k, shape, dtype)}
            for k, (name, shape) in zip(keys, shapes.items())}


# ------------------------------------------------ dropless top-k routing
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, k):
    """Rows of ``x [N, D]`` in slot order: row ``i`` is token
    ``order[i] // k``.  ``order`` is a permutation of the ``N * k``
    token-slots and ``inverse`` says where a slot's row is, so the
    backward pass is a gather by ``inverse`` and a sum over a token's
    ``k`` slots, not the scatter-add that transposing the gather would
    give."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    return g[inverse].reshape(-1, k, g.shape[-1]).sum(1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """Slot-ordered rows back in token order, ``[N * k, D]``; backward
    is the gather by ``order``."""
    return y[inverse]


_unsort.defvjp(lambda y, order, inverse: (y[inverse], order),
               lambda order, g: (g[order], None, None))


# The experts held (``topk_moe(held=)``): the buffer has the rows of the
# proven bound, ``H`` of them exist, and ``H`` is a value of the step.
# Every pass over the buffer is a loop over chunks of rows with the trip
# count ``ceil(H / chunk)``, so it costs what the rows that exist cost.
# The loops sit inside hand-written ``custom_vjp`` rules: nothing
# differentiates through them.  A chunk is this many bytes of rows (swept
# on the v5e, PERF.md section 6, PR 32).
_CHUNK_BYTES = 4 * 2 ** 20


def _chunks(m, d, dtype, h):
    """``(rows a chunk, chunks that hold the first h of m rows)``."""
    chunk = max(1, min(m, _CHUNK_BYTES // (d * jnp.dtype(dtype).itemsize)))
    return chunk, (h + chunk - 1) // chunk


def _chunk_at(c, chunk, m, h):
    """Where chunk ``c`` starts, held inside the buffer (the last one
    of a buffer that ``chunk`` does not divide overlaps the one before
    it), and which of its rows are its own and exist."""
    start = jnp.minimum(c * chunk, m - chunk)
    at = start + jnp.arange(chunk)
    return start, (at >= c * chunk) & (at < h)


def _unwritten(after, shape, dtype):
    """A buffer that nobody has written, there once ``after`` is: on
    the TPU the result of a kernel that writes nothing (zeros cost a
    pass over the whole bound, 0.82 ms for 131,072 rows of 2,048).
    ``jax.lax.empty`` takes no operand, and the TPU compiler then puts
    every such buffer of a step at the step's start: 20 x 512 MiB in the
    JoyAI cell, which no longer fits the chip."""
    if jax.default_backend() != "tpu":
        return jnp.zeros(shape, dtype)
    from jax.experimental import pallas as pl

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda after_ref, out_ref: None,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[anywhere], out_specs=anywhere, name="unwritten")(after)


def _rows_of_tokens(x, order, k, h):
    """``[M, D]`` whose row ``i < h`` is ``x[order[i] // k]``; the rows
    from ``h`` on (no grouped product reads them) are what the buffer
    was made with."""
    m, d = order.shape[0], x.shape[-1]
    chunk, trips = _chunks(m, d, x.dtype, h)

    def body(c, rows):
        start, _ = _chunk_at(c, chunk, m, h)
        slots = jax.lax.dynamic_slice(order, (start,), (chunk,))
        return jax.lax.dynamic_update_slice(rows, x[slots // k], (start, 0))

    return jax.lax.fori_loop(
        0, trips, body, _unwritten(order, (m, d), x.dtype))


def _tokens_of_rows(rows, order, k, h, n, weights=None):
    """``[n, D]``: token ``t`` is the sum of the rows ``i < h`` with
    ``order[i] // k == t``, each times ``weights.reshape(-1)[order[i]]``
    where weights ``[n, k]`` are given; summed in float32, cast once.

    Gathers alone (a scatter-add of rows costs the v5e eight times a
    gather's time a row, PERF.md section 6, PR 32).  The held slots are
    sorted once more, by token, so that a token's rows (at most ``M /
    n``) lie side by side; a chunk at a time they are gathered in that
    order and each is given the sum of the rows behind it that are its
    token's, so the first row of a token holds the token's sum, and a
    token takes its first row's."""
    m, d = rows.shape
    most = m // n
    chunk, trips = _chunks(m, d, rows.dtype, h)
    at = jnp.arange(m)
    slots, src = jax.lax.sort(
        (jnp.where(at < h, order, n * k), at), num_keys=1)
    # a chunk reads the token before its first row and ``most - 1`` rows
    # behind its last: no token there
    tokens = jnp.pad(slots // k, (1, most - 1), constant_values=n)
    slots, src = (jnp.pad(a, (0, most - 1)) for a in (slots, src))
    span = chunk + most - 1

    def body(c, carry):
        sums, first_of = carry
        start, live = _chunk_at(c, chunk, m, h)
        token = jax.lax.dynamic_slice(tokens, (start,), (span + 1,))
        before, token = token[:chunk], token[1:]
        part = rows[jax.lax.dynamic_slice(src, (start,), (span,))].astype(
            jnp.float32)
        if weights is not None:
            part = part * weights.reshape(-1)[
                jax.lax.dynamic_slice(slots, (start,), (span,))][:, None]
        total = part[:chunk]
        for j in range(1, most):
            total += jnp.where(
                (token[j:j + chunk] == token[:chunk])[:, None],
                part[j:j + chunk], 0)
        sums = jax.lax.dynamic_update_slice(
            sums, total.astype(rows.dtype), (start, 0))
        first = live & (token[:chunk] != before)
        first_of = first_of.at[jnp.where(first, token[:chunk], n)].set(
            start + jnp.arange(chunk), mode="drop")
        return sums, first_of

    sums, first_of = jax.lax.fori_loop(0, trips, body, (
        _unwritten(rows, (m, d), rows.dtype), jnp.full((n,), m, jnp.int32)))
    return jnp.where((first_of < m)[:, None],
                     sums[jnp.minimum(first_of, m - 1)], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_held(x, order, h, k):
    """:func:`_dispatch` where ``order`` holds the held slots first,
    ``h`` of them: the rows that exist, forward and backward."""
    return _rows_of_tokens(x, order, k, h)


def _dispatch_held_fwd(x, order, h, k):
    # of x the backward pass needs the number of tokens alone
    return _dispatch_held(x, order, h, k), (order, h, x[:, :0])


def _dispatch_held_bwd(k, res, g):
    order, h, x = res
    return _tokens_of_rows(g, order, k, h, x.shape[0]), None, None


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@jax.custom_vjp
def _combine_held(y, weights, order, h):
    """``out[t] = sum_j weights[t, j] * y[row of slot (t, j)]`` over the
    held slots, accumulated in float32 and cast once: the un-sort and
    the weighted sum in one pass over the ``h`` rows that exist, so
    ``[N k, D]`` is never made."""
    n, k = weights.shape
    return _tokens_of_rows(y, order, k, h, n, weights)


def _combine_held_fwd(y, weights, order, h):
    return _combine_held(y, weights, order, h), (y, weights, order, h)


def _combine_held_bwd(res, g):
    """``g_y[i] = w_i * g[token of i]`` and ``g_w`` of slot ``order[i]``
    ``= <g[token of i], y[i]>`` for ``i < h``, from one gather of
    ``g``'s rows a chunk.  ``g_y`` is written over ``y``, a chunk's
    rows once they are read: no second buffer of the bound."""
    y, weights, order, h = res
    (n, k), (m, d) = weights.shape, y.shape
    chunk, trips = _chunks(m, d, y.dtype, h)

    def body(c, carry):
        g_y, g_w = carry
        start, live = _chunk_at(c, chunk, m, h)
        slots = jax.lax.dynamic_slice(order, (start,), (chunk,))
        g_rows = g[slots // k].astype(jnp.float32)
        y_rows = jax.lax.dynamic_slice(
            g_y, (start, 0), (chunk, d)).astype(jnp.float32)
        w_rows = weights.reshape(-1)[slots][:, None]
        g_w = g_w.at[jnp.where(live, slots, n * k)].set(
            jnp.sum(g_rows * y_rows, axis=-1), mode="drop")
        g_y = jax.lax.dynamic_update_slice(
            g_y, (w_rows * g_rows).astype(y.dtype), (start, 0))
        return g_y, g_w

    g_y, g_w = jax.lax.fori_loop(
        0, trips, body, (y, jnp.zeros((n * k,), jnp.float32)))
    return g_y, g_w.reshape(n, k).astype(weights.dtype), None, None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [M, K]`` rows sorted by group, ``rhs [G, K, N]``,
    ``group_sizes [G]`` summing to ``M`` -> ``[M, N]``: row ``i`` times
    the matrix of the group it lies in.  ``jax.lax.ragged_dot``: the
    TPU compiler lowers it, and both its gradients, to a Mosaic kernel
    of its own (instructions ``%ragged-dot-*``) that multiplies only
    the rows that exist; measured against megablox's ``gmm`` on the
    v5e in PERF.md section 6 (PR 27)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


SCORINGS = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
            "sigmoid": jax.nn.sigmoid}

# What :func:`topk_moe` decides carries these names: the experts chosen,
# the sorted order of the slots (and its inverse on the path without
# ``held``); integers, 4 (k + min(k, held)) bytes a token.  Outside
# ``jax.checkpoint`` a name is the identity; a checkpoint whose policy
# keeps them (``save_only_these_names(*SAVED_NAMES)``) has no use for
# ``top_k`` or the sorts in its recomputation.  Each stands where its
# value is made and everything after reads the NAMED value: named later,
# the backward pass reads the un-named twin and what made it runs again.
# The router's product and score ARE made again (0.22-0.28 ms a layer on
# the v5e): their float32 logits ``[N, E]`` kept as well cost the Laguna
# cell 4.1 ms a step where they saved 0.9 (PERF.md section 6, PR 48).
SAVED_EXPERTS = "moe_experts"
SAVED_ORDER = "moe_order"
SAVED_INVERSE = "moe_inverse"
SAVED_NAMES = (SAVED_EXPERTS, SAVED_ORDER, SAVED_INVERSE)


def saved_bytes(tokens, k, held=None):
    """``{name: bytes}`` of what one :func:`topk_moe` over ``tokens``
    tokens keeps under ``SAVED_NAMES``, all int32: the experts ``[N,
    k]`` and the order of the ``N min(k, count)`` rows of the buffer
    with ``held=(first, count)``, of all ``N k`` slots and its inverse
    without."""
    if held is None:
        return dict.fromkeys(SAVED_NAMES, tokens * k * 4)
    return {SAVED_EXPERTS: tokens * k * 4,
            SAVED_ORDER: tokens * min(k, held[1]) * 4}


# The results of :func:`topk_moe`'s grouped products (three with gated
# experts, two without: no ``moe_gate`` where there is no gate), over the
# whole buffer of rows, carry these: a recomputed block keeps them only
# where its plan found room (``models/transformer.py:kept_plan``).  The
# names stand outside the passes of dynamic extent and change none.
PRODUCT_GATE = "moe_gate"
PRODUCT_UP = "moe_up"
PRODUCT_DOWN = "moe_down"
PRODUCT_NAMES = (PRODUCT_GATE, PRODUCT_UP, PRODUCT_DOWN)


def product_bytes(tokens, k, d_model, d_expert, itemsize, held=None,
                  gated=True):
    """``({name: bytes}, share)`` of one :func:`topk_moe` over ``tokens``
    tokens: what its grouped products' results take under
    ``PRODUCT_NAMES`` (the buffer's ``N k`` rows, ``N min(k, count)``
    with ``held=(first, count, of)`` where ``of`` is the router's count
    of experts; ``d_expert`` columns for gate and up, ``d_model`` for
    down; no gate's where the experts are not ``gated``), and the share
    of those rows expected to exist: all of them without ``held``, ``k
    count / of`` of ``min(k, count)`` a token under a router that
    spreads its tokens evenly."""
    slots, share = k, 1.0
    if held is not None:
        _, count, of = held
        slots = min(k, count)
        share = k * count / of / slots
    rows = tokens * slots * itemsize
    found = {PRODUCT_GATE: rows * d_expert, PRODUCT_UP: rows * d_expert,
             PRODUCT_DOWN: rows * d_model}
    if not gated:
        del found[PRODUCT_GATE]
    return found, share


def topk_route(router_logits, k, *, scoring="softmax", bias=None,
               renormalize=False, scale=1.0):
    """Token-choice top-k routing from ``[N, E]`` float32 logits.

    Returns ``(weights [N, k], experts [N, k], aux)``.  An expert's
    score is the ``scoring`` (``"softmax"`` over the experts or
    ``"sigmoid"`` of its own logit) of its logit.  Chosen are the k
    largest of ``score + bias``: ``bias [E]`` balances the load
    (:func:`balance_bias`), decides the choice alone and never enters a
    weight.  The weights are the scores of the chosen experts, divided
    by their sum over the k where ``renormalize``, times ``scale``.
    The defaults are OLMoE's: the softmax probabilities AS THEY ARE
    (``norm_topk_prob: false``).  ``aux`` holds, over the N tokens,

    - ``load_balancing``: ``E * sum_e f_e P_e`` with ``f_e`` the
      token-slots routed to ``e`` over N and ``P_e`` the mean score of
      ``e`` (Hugging Face's ``load_balancing_loss_func``; 1 * k at a
      uniform softmax router);
    - ``router_z``: mean of ``logsumexp(logits)^2`` (ST-MoE);
    - ``tokens_per_expert [E]`` int32, summing to ``N * k``.
    """
    return _topk_route(router_logits, k, scoring, bias, renormalize, scale,
                       reread=bias is not None)


def _topk_route(router_logits, k, scoring, bias, renormalize, scale, reread):
    """:func:`topk_route`.  ``reread``: the weights are read off the
    scores by comparison with the chosen experts and not taken from
    ``top_k``'s values, the same numbers; a ``bias`` leaves no other
    way."""
    n, e = router_logits.shape
    probs = SCORINGS[scoring](router_logits)
    if not reread:
        weights, experts = jax.lax.top_k(probs, k)
        experts = checkpoint_name(experts, SAVED_EXPERTS)
    else:
        experts = checkpoint_name(jax.lax.top_k(
            probs if bias is None else probs + bias, k)[1], SAVED_EXPERTS)
        # ``probs[n, experts[n, j]]`` read by comparison over the E
        # lanes, one term of each sum non-zero: a select and a sum where
        # ``take_along_axis`` moves scalars one by one (and its
        # transpose scatters them), the same to the bit both ways.  As
        # ``[k, N]``, the tokens along the lanes: ``[N, 8]`` the compiler
        # lays out 8 lanes of 128 wide, a pass of 0.42 ms where this
        # takes 0.08 (PERF.md section 6, PR 48)
        chosen = experts.T[..., None] == jax.lax.broadcasted_iota(
            experts.dtype, (k, n, e), 2)
        weights = jnp.sum(jnp.where(chosen, probs, 0), axis=-1).T
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    tokens_per_expert = jnp.sum(
        jax.nn.one_hot(experts, e, dtype=jnp.int32), axis=(0, 1))
    f = tokens_per_expert.astype(jnp.float32) / n
    aux = {
        "load_balancing": e * jnp.sum(f * jnp.mean(probs, axis=0)),
        "router_z": jnp.mean(jnp.square(
            jax.nn.logsumexp(router_logits, axis=-1))),
        "tokens_per_expert": tokens_per_expert,
    }
    return weights, experts, aux


def balance_bias(bias, tokens_per_expert, rate):
    """The selection bias after a step (DeepSeek-V3, arXiv:2412.19437
    section 2.1.2): ``b_e += rate * sign(mean(c) - c_e)`` with ``c`` the
    step's token-slots per expert, over the last axis: an overloaded
    expert's bias falls, an underloaded one's rises.  No gradient: the
    bias is state beside the parameters."""
    c = tokens_per_expert.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)


# an expert's non-linearity by name: the gate function of a gated expert,
# ``(act(x wg) * (x wi)) wo``, or the whole of one that has no gate,
# ``act(x wi) wo``
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
UNGATED = {"relu2": lambda u: jnp.square(jax.nn.relu(u))}
EXPERT_ACTIVATIONS = (*ACTIVATIONS, *UNGATED)


def _router_logits(x, router):
    """``x [..., D]`` (leading dims folded into N tokens) through
    ``router [D, E]``: the product in float32 at ``HIGHEST`` whatever
    ``x`` is, so that rounding does not flip a near-tie between the
    k-th and the next expert."""
    return jnp.dot(
        x.reshape(-1, x.shape[-1]).astype(jnp.float32),
        router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


def route_tokens(x, router, k, *, scoring="softmax", bias=None,
                 renormalize=False, scale=1.0):
    """:func:`topk_route` of ``x [..., D]`` through ``router [D, E]``,
    for a caller that decides from another array or at another place
    than the experts read and hands :func:`topk_moe` the ``decision``.
    Such a decision is carried past whatever stands between (a block's
    mixer), and a checkpoint keeps it by its experts' name alone: the
    weights are read off the scores by comparison with them, the same
    numbers as ``top_k``'s values, so that the recomputation has no use
    for ``top_k``."""
    return _topk_route(_router_logits(x, router), k, scoring, bias,
                       renormalize, scale, reread=True)


def topk_moe(x, params, *, k, held=None, activation="silu", decision=None,
             **route):
    """Dropless top-``k`` MoE FFN on ``x [..., D]`` (leading dims folded
    into N tokens); returns ``(out, aux)``.

    params: ``router/kernel [D, E]``, ``wg/kernel`` and ``wi/kernel``
    ``[E, D, F]`` (gate and up), ``wo/kernel [E, F, D]`` (create with
    ``init_moe_params(..., gated=True)``).  Per token, with ``S`` the
    ``k`` experts :func:`topk_route` chooses and ``w`` their weights
    (``route``: its keyword arguments; by default the softmax
    probabilities as they are) and ``act`` the ``activation``
    (``"silu"``, a SwiGLU expert, or ``"relu"``, a ReGLU one):

        out = sum_{e in S} w_e * (act(x wg_e) * (x wi_e)) wo_e

    or, with an ``activation`` of ``UNGATED`` (``"relu2"``, the square of
    ReLU), experts that have no gate and no ``wg``, two grouped products
    where the gated ones run three:

        out = sum_{e in S} w_e * act(x wi_e) wo_e

    ``S`` and ``w`` are decided from ``x`` (the router's product in
    float32 at ``HIGHEST``, under the scope ``moe/route``) unless the
    caller hands a ``decision``: what :func:`route_tokens` returned for
    the same N tokens, read from whatever array and at whatever place
    the model decides from (the router's kernel is then not read here
    and ``route`` is empty).  The ``N * k`` token-slots are sorted by
    expert (stable), their rows gathered, the grouped products run with
    group sizes = tokens per expert, and the result is un-sorted by the
    inverse permutation and summed over a token's slots: no capacity,
    no token dropped, no scatter of rows.  ``aux`` is
    :func:`topk_route`'s and ``held_rows`` (int32): the rows of the
    buffer that exist.  What the routing decided carries
    ``SAVED_NAMES``, for the policy of a recomputed block.

    ``held=(first, count)``: this device holds the experts ``first ...
    first + count - 1`` of the router's ``E`` (``wg``, ``wi``, ``wo``
    are ``[count, ...]``).  Routing, weights and ``tokens_per_expert
    [E]`` are over all ``E``; the sum above runs over the chosen AND
    held experts only: what the other devices' experts would add is
    theirs to add.  Dropless whatever the router does: a token has at
    most ``min(k, count)`` held slots, so the held slots, sorted to the
    front, always fit the ``N * min(k, count)`` rows the buffer has.
    Of those ``held_rows`` exist, a value of the step, and the passes
    over the buffer (rows from tokens, tokens from rows, forward and
    backward) run over them alone; the rows behind them are never
    written and nothing reads them.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    dtype = x.dtype
    if activation not in EXPERT_ACTIVATIONS:
        raise ValueError(f"topk_moe: activation {activation!r} is none of "
                         f"{sorted(EXPERT_ACTIVATIONS)}")
    if decision is None:
        with jax.named_scope("moe/route"):
            decision = topk_route(
                _router_logits(xt, params["router"]["kernel"]), k, **route)
    elif route:
        raise ValueError(f"topk_moe: a decision is handed in, and "
                         f"{sorted(route)} would decide again")
    weights, experts, aux = decision
    with jax.named_scope("moe/dispatch"):
        group_sizes = aux["tokens_per_expert"]
        keys = experts.reshape(-1)
        if held is not None:
            first, count = held
            # held slots first, by expert; the others behind them
            keys = jnp.where((keys >= first) & (keys < first + count),
                             keys - first, count)
            group_sizes = group_sizes[first:first + count]
        aux = {**aux, "held_rows": jnp.sum(group_sizes)}
        order = jnp.argsort(keys, stable=True)
        if held is None:
            order = checkpoint_name(order, SAVED_ORDER)
            inverse = checkpoint_name(jnp.argsort(order), SAVED_INVERSE)
            rows = _dispatch(xt, order, inverse, k)
        else:
            order = checkpoint_name(order[:n * min(k, count)], SAVED_ORDER)
            rows = _dispatch_held(xt, order, aux["held_rows"], k)
    with jax.named_scope("moe/experts"):
        if activation in UNGATED:
            wi, wo = (params[name]["kernel"].astype(dtype)
                      for name in ("wi", "wo"))
            up = checkpoint_name(grouped_matmul(rows, wi, group_sizes),
                                 PRODUCT_UP)
            hidden = UNGATED[activation](up)
        else:
            wg, wi, wo = (params[name]["kernel"].astype(dtype)
                          for name in ("wg", "wi", "wo"))
            gate = checkpoint_name(grouped_matmul(rows, wg, group_sizes),
                                   PRODUCT_GATE)
            up = checkpoint_name(grouped_matmul(rows, wi, group_sizes),
                                 PRODUCT_UP)
            hidden = ACTIVATIONS[activation](gate) * up
        y = checkpoint_name(grouped_matmul(hidden, wo, group_sizes),
                            PRODUCT_DOWN)
    with jax.named_scope("moe/combine"):
        if held is None:
            y = _unsort(y, order, inverse).reshape(n, k, d)
            out = jnp.sum(y.astype(jnp.float32) * weights[..., None],
                          axis=1).astype(dtype)
        else:
            out = _combine_held(y, weights, order, aux["held_rows"])
    return out.reshape(orig_shape), aux
