"""Softmax attention linearised by chunk (EVA, "Efficient Attention via
Control Variates", arXiv:2302.04542): the sequence is cut into windows
of ``window`` positions and every window into chunks of ``chunk``.  A
query reads its OWN window exactly, causally, and every EARLIER window
through one pooled key and value a chunk, under one softmax:

    o_i = sum_j alpha_ij v_j + sum_c beta_ic vt_c
    (alpha_i, beta_i) = softmax([s q_i . k_j,  s q_i . kt_c])
    j: window * (i // window) <= j <= i      c: c < (window / chunk) * (i // window)

so the key set is no band: a block-aligned window and a staircase over a
second key array ``1 / chunk`` as long.  A summary becomes visible when
its WINDOW is complete, not its chunk; the window does not slide; a query
of the first window sees no summary and the layer is plain causal
attention there.

:func:`pool_chunks` makes the summaries (plain ``jax.numpy``: sixteen
positions reduce to one, XLA fuses it and JAX differentiates it).
:func:`chunk_summary_attention` is the attention as TWO calls of the
flash kernels joined by their ``lse``: the local part as causal attention
over the windows as batch rows, the remote part over the summaries with
the staircase in the kernels' loop bounds (``stairs``), and the join a
streaming-softmax merge in XLA.  :func:`reference_chunk_summary_attention`
is the dense masked softmax over the joined keys, for the CPU and the
tests.

Under ``jax.checkpoint`` the summaries carry the names ``SAVED_NAMES``
(``1 / chunk`` of k and v); the kernels' results carry the flash
kernel's own.
"""

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops.pallas.flash_attention import (_NEG_INF,
                                                    flash_attention)

SAVED_KT = "chunk_summary_k"
SAVED_VT = "chunk_summary_v"
SAVED_NAMES = (SAVED_KT, SAVED_VT)


def pool_chunks(k, v, phi, mu, chunk, scale):
    """The summaries ``(kt, vt) [B, T / chunk, H, D]`` of k, v ``[B, T,
    H, D]``: a head ``h``, a chunk's positions ``j``:

        p = softmax_j(scale * k_j . phi_h)
        kt = sum_j p_j k_j + mu_h        vt = sum_j p_j v_j

    with ``phi``, ``mu [H, D]``.  Scores, softmax and sums in float32,
    the results in k's and v's dtype and named ``SAVED_NAMES``."""
    b, t, h, d = k.shape
    if t % chunk:
        raise ValueError(f"pool_chunks: chunks of {chunk} do not divide a "
                         f"sequence of {t}")
    k32, v32 = (u.astype(jnp.float32).reshape(b, t // chunk, chunk, h, d)
                for u in (k, v))
    p = jax.nn.softmax(
        scale * jnp.sum(k32 * phi.astype(jnp.float32), axis=-1), axis=2)
    kt = jnp.sum(p[..., None] * k32, axis=2) + mu.astype(jnp.float32)
    vt = jnp.sum(p[..., None] * v32, axis=2)
    return (checkpoint_name(kt.astype(k.dtype), SAVED_KT),
            checkpoint_name(vt.astype(v.dtype), SAVED_VT))


def _checked(q, kt, window, chunk):
    """``(windows, summaries a window)`` of a call, or a refusal by
    name: a sequence longer than one window is whole windows of whole
    chunks."""
    t, per_window = q.shape[1], window // chunk
    if window % chunk or window < chunk:
        raise ValueError(f"chunk_summary_attention: chunks of {chunk} do "
                         f"not divide a window of {window}")
    if t <= window:
        return 1, per_window
    if t % window or kt.shape[1] * chunk != t:
        raise ValueError(
            f"chunk_summary_attention: a sequence of {t} is neither within "
            f"one window of {window} nor whole windows with a summary "
            f"every {chunk} positions ({kt.shape[1]} summaries)")
    return t // window, per_window


def chunk_summary_attention(q, k, v, kt, vt, *, window, chunk, scale=None):
    """q, k, v ``[B, T, H, D]``, the summaries kt, vt ``[B, T / chunk,
    H, D]`` -> ``[B, T, H, D]`` (the module's docstring has the
    equations).  T is at most one window (no summary is visible: plain
    causal attention) or whole windows; anything else is refused.

    The flash function (``ops/pallas/flash_attention.py``) is called
    twice with ``return_lse``: under the scope
    ``local`` causally over ``[B T / window, window, H, D]``, under
    ``remote`` over kt, vt with ``stairs=(window, window / chunk)``; the
    two are joined under ``join`` by their ``lse`` in float32.  A query
    of the first window has an ``lse`` of -1e30 in the remote part,
    which the join gives weight 0."""
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    windows, per_window = _checked(q, kt, window, chunk)
    if windows == 1:
        with jax.named_scope("local"):
            return flash_attention(q, k, v, causal=True, scale=scale)
    with jax.named_scope("local"):
        rows = (u.reshape(b * windows, window, h, d) for u in (q, k, v))
        near, near_lse = flash_attention(*rows, causal=True, scale=scale,
                                         return_lse=True)
        near = near.reshape(b, t, h, d)
        near_lse = near_lse.reshape(b, windows, h, window).transpose(
            0, 2, 1, 3).reshape(b, h, t)
    with jax.named_scope("remote"):
        far, far_lse = flash_attention(q, kt, vt, scale=scale,
                                       return_lse=True,
                                       stairs=(window, per_window))
    with jax.named_scope("join"):
        lse = jnp.logaddexp(near_lse, far_lse)                # [B, H, T]
        w_near, w_far = (jnp.exp(part - lse).transpose(0, 2, 1)[..., None]
                         for part in (near_lse, far_lse))
        return (near.astype(jnp.float32) * w_near
                + far.astype(jnp.float32) * w_far).astype(q.dtype)


def allowed_keys(t, window, chunk):
    """``[T, T + T / chunk]`` booleans: which of the joined keys (the
    positions, then the summaries) query ``i`` reads."""
    i = jnp.arange(t)[:, None]
    j, c = jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]
    start = i // window * window
    return jnp.concatenate(
        [(j >= start) & (j <= i), c < start // chunk], axis=1)


def reference_chunk_summary_attention(q, k, v, kt, vt, *, window, chunk,
                                      scale=None):
    """The same function as a dense softmax over the joined keys under
    :func:`allowed_keys`, in float32: for the CPU and the tests."""
    t, d = q.shape[1], q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    _checked(q, kt, window, chunk)
    keys, values = (jnp.concatenate([u, ut], axis=1).astype(jnp.float32)
                    for u, ut in ((k, kt), (v, vt)))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), keys) * scale
    s = jnp.where(allowed_keys(t, window, chunk), s, _NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                      values).astype(q.dtype)
