"""The selective scan of a state-space mixer (Mamba, arXiv:2312.00752):
a state carried along the sequence, in plain ``jax.lax``.

With ``c [B, T, d]`` the mixer's channels, ``delta [B, T, d]`` their
step sizes, ``A [d, N]`` (negative), ``B``, ``C [B, T, N]`` and ``D
[d]``, for every sequence and channel a state ``h [N]`` runs along T:

    h[t] = exp(delta[t] A) * h[t-1] + (delta[t] * c[t]) B[t]     h[-1] = 0
    y[t] = h[t] . C[t] + D * c[t]

The state, ``delta``, ``A`` and every sum are float32 whatever the
activations are; ``y`` comes back in ``c``'s dtype.

``[B, T, d, N]`` is never made.  T is cut into chunks: a loop over the
chunks carries ``h [B, N, d]`` (the channels along the lanes, the N
along the sublanes) and inside a chunk a loop over its positions updates
it and reads ``y[t]`` off it, so the forward pass holds one state and
writes ``y`` alone.  The backward pass is written out (a
``custom_vjp``): the forward pass keeps the state each chunk was ENTERED
with (``[T / chunk, B, N, d]``: 1/chunk of the states) and ``y``; the
backward walks the chunks last to first, makes a chunk's states again
from its entry state (``[chunk, B, N, d]``: the state BEFORE each
position, the one array of that shape alive at a time, with the
cotangents of the same shape), runs the
adjoint recurrence ``g[t] = C[t] (x) dy[t] + exp(delta[t+1] A) * g[t+1]``
back through the chunk and reads all six gradients off the two as sums
over the chunk.  A T no chunk length divides is padded with positions of
``delta`` 0, which pass the state on as it is.

Under ``jax.checkpoint`` the two results the backward pass is handed
carry the names ``SAVED_NAMES``: a policy that keeps them
(``save_only_these_names``) has no use for the forward recurrence in its
recomputation, which is then dead code; outside a checkpoint a name is
the identity.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# positions a chunk holds and positions a loop iteration runs: swept on
# the v5e at [2, 8192, 5120] x 16, forward + backward ms a call (PERF.md
# section 6, PR 46): 256 x 8 99.1, 128 x 8 66.7, 64 x 8 48.7, 64 x 4
# 47.2, 32 x 8 48.3, **32 x 4 42.0**, 32 x 2 45.4, 16 x 4 43.9, 8 x 8
# 61.1 (the forward alone 12.8-14.7 at every one).  A chunk's states and
# cotangents are 21 MB each at 32, and the compiler keeps them near the
# core; the states kept between the passes are 1/32 of all
CHUNK = 32
UNROLL = 4
SAVED_Y = "selective_scan_y"
SAVED_STATES = "selective_scan_states"
SAVED_NAMES = (SAVED_Y, SAVED_STATES)


def saved_bytes(batch, seq, d_inner, state, dtype, chunk=CHUNK):
    """``{name: bytes}`` of what one call keeps under ``SAVED_NAMES``:
    ``y`` in the activation dtype and a float32 state a chunk."""
    chunks = -(-seq // min(chunk, seq))
    return {SAVED_Y: batch * seq * d_inner * jnp.dtype(dtype).itemsize,
            SAVED_STATES: chunks * batch * state * d_inner * 4}


def _decay(delta_t, a_t):
    """``exp(delta[t] A)`` ``[..., B, N, d]`` of ``delta_t [..., B, d]``
    and ``a_t [N, d]``."""
    return jnp.exp(delta_t[..., None, :] * a_t)


def _step(a_t, h, x):
    """One position: the state moved, in float32."""
    u_t, delta_t, b_t = x
    return _decay(delta_t, a_t) * h + b_t[..., None] * u_t[..., None, :]


def _forward_chunk(a_t, h, xs):
    """A chunk's positions from the state ``h [B, N, d]`` it is entered
    with: ``(the state it leaves, (h, y [L, B, d]))``; ``xs`` holds ``u =
    delta * c``, ``delta [L, B, d]`` and ``B``, ``C [L, B, N]``."""
    def step(h, x):
        h = _step(a_t, h, x[:3])
        return h, jnp.sum(h * x[3][..., None], axis=-2)

    left, y = jax.lax.scan(step, h, xs, unroll=UNROLL)
    return left, (h, y)


def _chunked(x, chunk):
    """``[B, T, f] -> [T / chunk, chunk, B, f]`` in float32."""
    b, t, f = x.shape
    return (x.astype(jnp.float32).reshape(b, t // chunk, chunk, f)
            .transpose(1, 2, 0, 3))


def _unchunked(x):
    """``[T / chunk, chunk, B, f] -> [B, T, f]``."""
    n, chunk, b, f = x.shape
    return x.transpose(2, 0, 1, 3).reshape(b, n * chunk, f)


def _operands(c, delta, b, c2, chunk):
    c, delta, b, c2 = (_chunked(x, chunk) for x in (c, delta, b, c2))
    return c, (delta * c, delta, b, c2)


def _forward(c, delta, a, b, c2, d, chunk):
    """``(y [B, T, d] in c's dtype, entry states [T / chunk, B, N, d])``
    of a T that ``chunk`` divides."""
    a_t = a.astype(jnp.float32).T
    _, xs = _operands(c, delta, b, c2, chunk)
    start = jnp.zeros((c.shape[0],) + a_t.shape, jnp.float32)
    _, (states, y) = jax.lax.scan(
        functools.partial(_forward_chunk, a_t), start, xs)
    y = _unchunked(y) + d.astype(jnp.float32) * c.astype(jnp.float32)
    return y.astype(c.dtype), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(c, delta, a, b, c2, d, chunk):
    return _forward(c, delta, a, b, c2, d, chunk)[0]


def _scan_fwd(c, delta, a, b, c2, d, chunk):
    y, states = _forward(c, delta, a, b, c2, d, chunk)
    y = checkpoint_name(y, SAVED_Y)
    return y, (c, delta, a, b, c2, d, checkpoint_name(states, SAVED_STATES))


def _backward_chunk(a_t, carry, chunk_of):
    """One chunk of the backward pass, entered from the chunk after it
    with ``flow = exp(delta[t+1] A) * g[t+1]`` of its first position and
    the sum ``da [N, d]`` so far."""
    flow, da = carry
    entry, c, (u, delta, b, c2), dy = chunk_of

    def again(h, x):
        return _step(a_t, h, x), h         # the state BEFORE the position

    def adjoint(flow, x):
        delta_t, c2_t, dy_t = x
        g = c2_t[..., None] * dy_t[..., None, :] + flow
        return _decay(delta_t, a_t) * g, g

    _, before = jax.lax.scan(again, entry, (u, delta, b), unroll=UNROLL)
    flow, g = jax.lax.scan(adjoint, flow, (delta, c2, dy), reverse=True,
                           unroll=UNROLL)
    decay = _decay(delta, a_t)
    h = decay * before + b[..., None] * u[..., None, :]
    # d loss / d exp(delta[t] A), times that factor
    w = g * decay * before
    du = jnp.sum(g * b[..., None], axis=-2)
    grads = (du * delta,                                   # dc, less D dy
             jnp.sum(w * a_t, axis=-2) + du * c,           # ddelta
             jnp.sum(g * u[..., None, :], axis=-1),        # dB
             jnp.sum(h * dy[..., None, :], axis=-1))       # dC
    da = da + jnp.sum(w * delta[..., None, :], axis=(0, 1))
    return (flow, da), grads


def _scan_bwd(chunk, res, dy):
    c, delta, a, b, c2, d, states = res
    a_t = a.astype(jnp.float32).T
    c_chunks, xs = _operands(c, delta, b, c2, chunk)
    dy_chunks = _chunked(dy, chunk)
    (_, da), grads = jax.lax.scan(
        functools.partial(_backward_chunk, a_t),
        (jnp.zeros_like(states[0]), jnp.zeros_like(a_t)),
        (states, c_chunks, xs, dy_chunks), reverse=True)
    dc, ddelta, db, dc2 = map(_unchunked, grads)
    dy32 = dy.astype(jnp.float32)
    dc = dc + d.astype(jnp.float32) * dy32
    dd = jnp.sum(dy32 * c.astype(jnp.float32), axis=(0, 1))
    return (dc.astype(c.dtype), ddelta.astype(delta.dtype),
            da.T.astype(a.dtype), db.astype(b.dtype), dc2.astype(c2.dtype),
            dd.astype(d.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(c, delta, A, B, C, D, *, chunk=CHUNK):
    """``y [B, T, d]`` (in ``c``'s dtype) of the recurrence above; ``c``,
    ``delta [B, T, d]``, ``A [d, N]``, ``B``, ``C [B, T, N]``, ``D [d]``.
    Differentiable in all six.  ``chunk``: the positions between two kept
    states (cut to T where T is shorter)."""
    t = c.shape[1]
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        # delta 0: exp(0) = 1 and delta * c = 0, the state passes
        c, delta, B, C = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                          for x in (c, delta, B, C))
    y = _scan(c, delta, A, B, C, D, chunk)
    return y[:, :t] if pad else y
