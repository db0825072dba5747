"""Mamba-2's scan by chunks (state-space duality, arXiv:2405.21060): a
MATRIX state a head carried along the sequence, computed as matrix
products over chunks of positions, on the MXU.

With ``xs [B, T, H, P]`` the mixer's channels as ``H`` heads of ``P``,
``dt [B, T, H]`` their step sizes (float32), ``A [H]`` (negative), ``Bm``,
``Cm [B, T, G, N]`` shared by the ``H / G`` heads of a group and ``D
[H]``, for every sequence and head a state ``h [P, N]`` runs along T:

    h[t] = exp(dt[t] A) h[t-1] + (dt[t] xs[t]) (outer) Bm[t]       h[-1] = 0
    y[t] = h[t] Cm[t] + D xs[t]

(head ``h`` reads group ``h // (H / G)``).  T is cut into chunks of
``chunk`` positions.  With ``cum[i]`` the sum of ``dt A`` from a chunk's
first position to ``i`` (inclusive), ``x~ = dt xs`` and ``h_c`` the state
chunk ``c`` is entered with:

    intra:  y[i] += sum_{j <= i} exp(cum[i] - cum[j]) (Cm[i] . Bm[j]) x~[j]
    states: S_c = sum_j exp(cum[last] - cum[j]) x~[j] (outer) Bm[j]
    pass:   h_0 = 0;  h_{c+1} = exp(cum[last]) h_c + S_c
    inter:  y[i] += exp(cum[i]) h_c Cm[i]

``intra`` is ``(L o (Cm Bm^T)) x~`` with ``L`` the chunk's decay mask, two
products a chunk; ``states`` and ``inter`` one product each a chunk and
head; the pass across the ``T / chunk`` chunks is ONE product of the
chunks' decay matrix ``[T / chunk, T / chunk]`` with the stacked ``S``.
There is no loop over positions and none over chunks, forward or
backward.  The products take operands in ``xs``'s dtype and sum in
float32; ``dt``, ``cum``, every decay and the carried states ``S`` and
``h_c`` are float32 (the pass across chunks is a float32 product at
``HIGHEST``); ``y`` comes back in ``xs``'s dtype.  Every exponent is a
difference taken under its mask first, so it is never positive.

A T that ``chunk`` does not divide is PADDED with positions of ``dt`` 0,
which pass the state on as it is and add nothing; a T shorter than
``chunk`` is one chunk of T.

The backward pass is JAX's own of these products, run from the six
operands (the call is a ``jax.checkpoint`` of its own that keeps nothing
else: the masks, ``S`` and ``h_c`` are made again in the backward pass
and never held between the passes; no ``custom_vjp``, so every
instruction keeps the caller's scopes).  Under a caller's
``jax.checkpoint`` the result carries the name ``SAVED_NAMES[0]``: a
policy that keeps it (``save_only_these_names``) does not make the
result again in its recomputation; outside a checkpoint a name is the
identity.  No Pallas kernel: the masks ``[B, T / chunk, H, chunk,
chunk]`` go through HBM (``docs/kernels.md``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

SAVED_Y = "ssd_y"
SAVED_NAMES = (SAVED_Y,)
_HIGHEST = jax.lax.Precision.HIGHEST


def saved_bytes(batch, seq, heads, head_dim, dtype):
    """``{name: bytes}`` of what one call keeps under ``SAVED_NAMES``:
    ``y`` in the activation dtype."""
    return {SAVED_Y: batch * seq * heads * head_dim
            * jnp.dtype(dtype).itemsize}


def _decay(upper, lower, allowed):
    """``exp(upper - lower)`` where ``allowed``, 0 elsewhere; the
    difference is masked BEFORE the exponential, so nothing overflows
    and the masked entries have no gradient."""
    return jnp.exp(jnp.where(allowed, upper - lower, -jnp.inf))


def _cumulative(dt, A, chunk):
    """``cum [B, C, H, Q]`` (float32): ``dt A`` summed from a chunk's
    first position to each position, inclusive."""
    b, t, h = dt.shape
    a = (dt * A).reshape(b, t // chunk, chunk, h)
    return jnp.cumsum(a, axis=2).transpose(0, 1, 3, 2)


def entry_states(xs, dt, A, Bm, chunk):
    """``(h [B, C, H, P, N], cum)``: the float32 state every chunk is
    ENTERED with (``h[:, 0]`` is zero) and :func:`_cumulative`."""
    b, t, heads, p = xs.shape
    groups, n = Bm.shape[2:]
    c, per = t // chunk, heads // groups
    cum = _cumulative(dt, A, chunk)                      # [B, C, H, Q]
    total = cum[..., -1]                                 # [B, C, H]
    # x~ weighed by what is left of it at the chunk's end
    left = jnp.exp(total[..., None] - cum).transpose(0, 1, 3, 2)
    weighed = (xs.astype(jnp.float32).reshape(b, c, chunk, heads, p)
               * (dt.reshape(b, c, chunk, heads) * left)[..., None])
    states = jnp.einsum(
        "bcjgkp,bcjgn->bcgkpn",
        weighed.astype(xs.dtype).reshape(b, c, chunk, groups, per, p),
        Bm.reshape(b, c, chunk, groups, n),
        preferred_element_type=jnp.float32).reshape(b, c, heads, p * n)
    # h_c = sum_{k < c} exp(sum of the totals of chunks k+1 .. c-1) S_k
    through = jnp.cumsum(total, axis=1).transpose(0, 2, 1)     # [B, H, C]
    before = through - total.transpose(0, 2, 1)
    at = jnp.arange(c)
    across = _decay(before[..., :, None], through[..., None, :],
                    at[:, None] > at[None, :])                # [B, H, C, C]
    entered = jnp.einsum("bhck,bkhs->bchs", across, states,
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
    return entered.reshape(b, c, heads, p, n), cum


def _forward(xs, dt, A, Bm, Cm, D, chunk):
    """``y [B, T, H, P]`` in ``xs``'s dtype of a T that ``chunk``
    divides."""
    b, t, heads, p = xs.shape
    groups, n = Bm.shape[2:]
    c, per = t // chunk, heads // groups
    dtype = xs.dtype
    with jax.named_scope("inter"):
        entered, cum = entry_states(xs, dt, A, Bm, chunk)
    x32 = xs.astype(jnp.float32).reshape(b, c, chunk, heads, p)
    b_c, c_c = (u.reshape(b, c, chunk, groups, n) for u in (Bm, Cm))
    with jax.named_scope("intra"):
        at = jnp.arange(chunk)
        mask = _decay(cum[..., :, None], cum[..., None, :],
                      at[:, None] >= at[None, :])          # [B, C, H, Q, Q]
        pairs = jnp.einsum("bcign,bcjgn->bcgij", c_c, b_c,
                           preferred_element_type=jnp.float32)
        weights = (mask.reshape(b, c, groups, per, chunk, chunk)
                   * pairs[:, :, :, None]).astype(dtype)
        fed = (x32 * dt.reshape(b, c, chunk, heads)[..., None]).astype(dtype)
        y = jnp.einsum(
            "bcgkij,bcjgkp->bcigkp", weights,
            fed.reshape(b, c, chunk, groups, per, p),
            preferred_element_type=jnp.float32)
    with jax.named_scope("inter"):
        # what the state a chunk is entered with gives each position
        carried = jnp.einsum(
            "bcign,bcgkpn->bcigkp", c_c,
            entered.astype(dtype).reshape(b, c, groups, per, p, n),
            preferred_element_type=jnp.float32)
        y = y + carried * jnp.exp(cum).transpose(0, 1, 3, 2).reshape(
            b, c, chunk, groups, per, 1)
    y = y.reshape(b, c, chunk, heads, p) + D[:, None] * x32
    return y.reshape(b, t, heads, p).astype(dtype)


def ssd(xs, dt, A, Bm, Cm, D, *, chunk=128):
    """``y [B, T, H, P]`` (in ``xs``'s dtype) of the recurrence above;
    ``xs [B, T, H, P]``, ``dt [B, T, H]``, ``A [H]``, ``Bm``, ``Cm [B,
    T, G, N]`` with ``G`` dividing ``H``, ``D [H]``.  Differentiable in
    all six.  ``chunk``: the positions of a chunk (cut to T where T is
    shorter)."""
    t, heads, groups = xs.shape[1], xs.shape[2], Bm.shape[2]
    if heads % groups:
        raise ValueError(f"ssd: {groups} groups do not divide {heads} heads")
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        # dt 0: exp(0) = 1 and dt xs = 0, the state passes
        xs, dt, Bm, Cm = (jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (
            u.ndim - 2)) for u in (xs, dt, Bm, Cm))
    # what the products' gradients read is made again in the backward pass
    y = checkpoint_name(jax.checkpoint(
        functools.partial(_forward, chunk=chunk))(
            xs, dt.astype(jnp.float32), A.astype(jnp.float32), Bm, Cm,
            D.astype(jnp.float32)), SAVED_Y)
    return y[:, :t] if pad else y
