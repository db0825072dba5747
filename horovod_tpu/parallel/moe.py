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
give: one chip's share under expert parallelism, still dropless.  The
exchange of rows between chips is not here yet: every token the layer
is given is one of this chip's.
"""

import functools
import math

import jax
import jax.numpy as jnp


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
def _rows_of_slots(y, inverse, partial):
    """Row ``inverse[s]`` of ``y`` for every token-slot ``s``.  Where
    ``y`` holds every slot's row ``inverse`` is a permutation and this
    is a plain gather.  Where it holds a device's share (``partial``) a
    slot whose row is not among the computed ones points past ``y`` and
    reads zeros."""
    if partial:
        return y.at[inverse].get(mode="fill", fill_value=0)
    return y[inverse]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(x, order, inverse, k, partial=False):
    """Rows of ``x [N, D]`` in slot order: row ``i`` is token
    ``order[i] // k``.  ``order`` holds the first M token-slots of a
    permutation of all ``N * k`` and ``inverse`` says where a slot's
    row is, so the backward pass is a gather by ``inverse`` and a sum
    over a token's ``k`` slots, not the scatter-add that transposing
    the gather would give."""
    return x[order // k]


def _dispatch_fwd(x, order, inverse, k, partial):
    return x[order // k], inverse


def _dispatch_bwd(k, partial, inverse, g):
    rows = _rows_of_slots(g, inverse, partial)
    return rows.reshape(-1, k, g.shape[-1]).sum(1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _unsort(y, order, inverse, partial=False):
    """Slot-ordered rows back in token order, ``[N * k, D]``; backward
    is the gather by ``order``."""
    return _rows_of_slots(y, inverse, partial)


_unsort.defvjp(
    lambda y, order, inverse, partial: (
        _rows_of_slots(y, inverse, partial), order),
    lambda partial, order, g: (g[order], None, None))


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
    n, e = router_logits.shape
    probs = SCORINGS[scoring](router_logits)
    if bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + bias, k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
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


def topk_moe(x, params, *, k, held=None, **route):
    """Dropless top-``k`` MoE FFN with gated experts on ``x [..., D]``
    (leading dims folded into N tokens); returns ``(out, aux)``.

    params: ``router/kernel [D, E]``, ``wg/kernel`` and ``wi/kernel``
    ``[E, D, F]`` (gate and up), ``wo/kernel [E, F, D]`` (create with
    ``init_moe_params(..., gated=True)``).  Per token, with ``S`` the
    ``k`` experts :func:`topk_route` chooses and ``w`` their weights
    (``route``: its keyword arguments; by default the softmax
    probabilities as they are):

        out = sum_{e in S} w_e * (silu(x wg_e) * (x wi_e)) wo_e

    The router's product and scores run in float32 whatever ``x`` is,
    so that rounding does not flip a near-tie between the k-th and the
    next expert.  The ``N * k`` token-slots are sorted by expert
    (stable), their rows gathered, three grouped products run with
    group sizes = tokens per expert, and the result is un-sorted by the
    inverse permutation and summed over a token's slots: no capacity,
    no token dropped, no scatter.  ``aux`` is :func:`topk_route`'s.

    ``held=(first, count)``: this device holds the experts ``first ...
    first + count - 1`` of the router's ``E`` (``wg``, ``wi``, ``wo``
    are ``[count, ...]``).  Routing, weights and ``tokens_per_expert
    [E]`` are over all ``E``; the sum above runs over the chosen AND
    held experts only: what the other devices' experts would add is
    theirs to add.  Dropless whatever the router does: a token has at
    most ``min(k, count)`` held slots, so the held slots, sorted to the
    front, always fit the ``N * min(k, count)`` rows the buffer has.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    dtype = x.dtype
    with jax.named_scope("moe/route"):
        logits = jnp.dot(
            xt.astype(jnp.float32),
            params["router"]["kernel"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        weights, experts, aux = topk_route(logits, k, **route)
    with jax.named_scope("moe/dispatch"):
        group_sizes = aux["tokens_per_expert"]
        keys = experts.reshape(-1)
        if held is not None:
            first, count = held
            # held slots first, by expert; the others behind them
            keys = jnp.where((keys >= first) & (keys < first + count),
                             keys - first, count)
            group_sizes = group_sizes[first:first + count]
        order = jnp.argsort(keys, stable=True)
        inverse = jnp.argsort(order)
        if held is not None:
            order = order[:n * min(k, count)]
            # a grouped product leaves the rows outside its groups
            # undefined, forward and backward: a slot that is not held
            # reads zeros wherever its row would be read
            inverse = jnp.where(inverse < jnp.sum(group_sizes), inverse,
                                order.shape[0])
        rows = _dispatch(xt, order, inverse, k, held is not None)
    with jax.named_scope("moe/experts"):
        wg, wi, wo = (params[name]["kernel"].astype(dtype)
                      for name in ("wg", "wi", "wo"))
        hidden = (jax.nn.silu(grouped_matmul(rows, wg, group_sizes))
                  * grouped_matmul(rows, wi, group_sizes))
        y = grouped_matmul(hidden, wo, group_sizes)
    with jax.named_scope("moe/combine"):
        y = _unsort(y, order, inverse, held is not None).reshape(n, k, d)
        out = jnp.sum(y.astype(jnp.float32) * weights[..., None], axis=1)
    return out.astype(dtype).reshape(orig_shape), aux
