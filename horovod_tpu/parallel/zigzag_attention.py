"""Zigzag ring attention: load-BALANCED causal sequence parallelism.

The plain causal ring (`ring_attention.py`) skips future K/V blocks,
but the ring is lockstep — every hop costs the *maximum* compute over
ranks, and rank P-1 attends every block while rank 0 attends one, so
causality saves almost no wall-clock.  The zigzag layout fixes the
balance (the technique behind the public zigzag/striped ring-attention
kernels; no reference-framework analog — SURVEY §5 lists long-context
as design-fresh):

- the global sequence is cut into ``2P`` chunks and rank ``i`` holds
  the PAIR (chunk ``i``, chunk ``2P-1-i``) — one early, one late;
- when rank ``i`` meets K/V from rank ``j != i``, exactly TWO of the
  four chunk interactions are causally live, and both are FULLY
  unmasked:

  * ``q_hi x kv_lo`` — always (chunk ``2P-1-i`` is later than any low
    chunk ``j``);
  * ``q_hi x kv_hi`` if ``j > i``, else ``q_lo x kv_lo`` — one XOR the
    other, same shape, so it lowers to a select over which operands
    feed ONE block attend;

  (``q_lo x kv_hi`` is never live: ``i + j <= 2P - 2 < 2P - 1``.)

Every rank therefore computes exactly 2 unmasked ``C x C`` block
attends per hop (plus a fixed resident step) — perfect balance, no
masking waste on the MXU, and ~2x the causal throughput of the naive
ring at large P.

Each block attend runs through the Pallas flash kernel on TPU (same
``return_lse`` streaming-softmax combine as ``ring_attention``), the
dense einsum elsewhere.  Results are EXACT attention in the original
token order: :func:`zigzag_shard` / :func:`zigzag_unshard` reorder
between the natural layout and the zigzag layout.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel._compat import shard_map
from horovod_tpu.parallel.ring_attention import (_NEG_INF, _block_attend,
                                                 _combine)


def zigzag_chunk_order(p_size):
    """Chunk ids in shard order: rank ``i`` gets ``[i, 2P-1-i]``."""
    order = []
    for i in range(p_size):
        order.extend([i, 2 * p_size - 1 - i])
    return order


def zigzag_shard(x, p_size, axis=1):
    """Reorder a global ``[..., T, ...]`` array so a contiguous split
    over ``p_size`` shards hands rank ``i`` chunks ``(i, 2P-1-i)``."""
    t = x.shape[axis]
    if t % (2 * p_size):
        raise ValueError(
            f"sequence length {t} not divisible by 2*{p_size}")
    c = t // (2 * p_size)
    parts = [lax.slice_in_dim(x, k * c, (k + 1) * c, axis=axis)
             for k in zigzag_chunk_order(p_size)]
    return jnp.concatenate(parts, axis=axis)


def zigzag_unshard(x, p_size, axis=1):
    """Inverse of :func:`zigzag_shard`."""
    t = x.shape[axis]
    if t % (2 * p_size):
        raise ValueError(
            f"sequence length {t} not divisible by 2*{p_size}")
    c = t // (2 * p_size)
    order = zigzag_chunk_order(p_size)
    inverse = [0] * len(order)
    for pos, chunk in enumerate(order):
        inverse[chunk] = pos
    parts = [lax.slice_in_dim(x, pos * c, (pos + 1) * c, axis=axis)
             for pos in inverse]
    return jnp.concatenate(parts, axis=axis)


def _attend(q, k, v, *, scale, causal, use_flash, axis_name):
    """One block attend -> (numerator, denom, max) in the streaming-
    softmax representation ``_combine`` merges."""
    b, tq, h, d = q.shape
    if use_flash:
        from horovod_tpu.ops.pallas.flash_attention import flash_attention

        out, lse = flash_attention(q, k.astype(q.dtype),
                                   v.astype(q.dtype), causal=causal,
                                   scale=scale, return_lse=True)
        ones = lax.pcast(jnp.ones((b, h, tq), jnp.float32),
                         (axis_name,), to="varying")
        return out.astype(jnp.float32), ones, lse
    if causal:
        msk = (jnp.arange(tq)[:, None]
               >= jnp.arange(k.shape[1])[None, :])[None, None]
    else:
        msk = None
    return _block_attend(q.astype(jnp.float32), k, v, scale=scale,
                         mask=msk)


def zigzag_ring_attention(q, k, v, *, axis_name, scale=None,
                          use_flash=None):
    """Balanced causal ring attention over ``axis_name``.

    Must run inside ``shard_map`` with the ZIGZAG shard layout: this
    rank's ``[B, 2C, H, D]`` slice is chunk ``i`` then chunk
    ``2P-1-i`` of the global sequence (:func:`zigzag_shard`).  Always
    causal — for the non-causal case the plain ring is already
    balanced; use :func:`ring_attention`.
    """
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t2, h, d = q.shape
    if t2 % 2:
        raise ValueError(f"zigzag shard holds 2 chunks; got T={t2}")
    c = t2 // 2
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    att = functools.partial(_attend, scale=scale, use_flash=use_flash,
                            axis_name=axis_name)

    q_lo, q_hi = q[:, :c], q[:, c:]
    k_lo, k_hi = k[:, :c], k[:, c:]
    v_lo, v_hi = v[:, :c], v[:, c:]

    def init(tq):
        o = jnp.zeros((b, tq, h, d), jnp.float32)
        l = jnp.zeros((b, h, tq), jnp.float32)
        m = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
        return tuple(lax.pcast(x, (axis_name,), to="varying")
                     for x in (o, l, m))

    # Resident step (kv from this rank): q_lo/q_hi diagonal-causal on
    # their own chunks + q_hi attends kv_lo fully (chunk 2P-1-i is
    # always later than chunk i).
    acc_lo = _combine(*init(c), *att(q_lo, k_lo, v_lo, causal=True))
    acc_hi = _combine(*init(c), *att(q_hi, k_hi, v_hi, causal=True))
    acc_hi = _combine(*acc_hi, *att(q_hi, k_lo, v_lo, causal=False))

    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    def step(carry, s):
        acc_lo, acc_hi, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        j = (my_idx - s) % p_size          # origin rank of current K/V
        kc_lo, kc_hi = kc[:, :c], kc[:, c:]
        vc_lo, vc_hi = vc[:, :c], vc[:, c:]

        # always live: q_hi x kv_lo, fully unmasked
        acc_hi = _combine(*acc_hi, *att(q_hi, kc_lo, vc_lo,
                                        causal=False))

        # exactly one of (q_hi x kv_hi | j > i) / (q_lo x kv_lo | j < i)
        # is live, both unmasked and same-shaped: select the operands,
        # run ONE attend, then merge into the matching accumulator.
        hi_live = j > my_idx
        q_sel = jnp.where(hi_live, q_hi, q_lo)
        k_sel = jnp.where(hi_live, kc_hi, kc_lo)
        v_sel = jnp.where(hi_live, vc_hi, vc_lo)
        bo, bl, bm = att(q_sel, k_sel, v_sel, causal=False)
        lo_new = _combine(*acc_lo, bo, bl, bm)
        hi_new = _combine(*acc_hi, bo, bl, bm)
        acc_lo = tuple(jnp.where(hi_live, a, n)
                       for a, n in zip(acc_lo, lo_new))
        acc_hi = tuple(jnp.where(hi_live, n, a)
                       for a, n in zip(acc_hi, hi_new))
        return (acc_lo, acc_hi, kc, vc), None

    (acc_lo, acc_hi, _, _), _ = lax.scan(
        step, (acc_lo, acc_hi, k, v), jnp.arange(1, p_size))

    def finish(o, l, m):
        denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
        return o / denom

    out = jnp.concatenate([finish(*acc_lo), finish(*acc_hi)], axis=1)
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=32)
def _jitted_zigzag(mesh, axis_name, scale, use_flash):
    spec = P(None, axis_name, None, None)
    return jax.jit(shard_map(
        functools.partial(zigzag_ring_attention, axis_name=axis_name,
                          scale=scale, use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))


def zigzag_ring_self_attention(q, k, v, mesh, *, axis_name="sp",
                               scale=None, use_flash=None):
    """Convenience wrapper: zigzag-reorder global ``[B, T, H, D]``
    arrays, run :func:`zigzag_ring_attention` under ``shard_map``
    (jitted, cached per (mesh, axis, scale, flash)), and restore the
    natural token order."""
    p_size = mesh.shape[axis_name]
    sharding = NamedSharding(mesh, P(None, axis_name, None, None))

    fn = _jitted_zigzag(mesh, axis_name, scale, use_flash)
    args = (jax.device_put(zigzag_shard(x, p_size), sharding)
            for x in (q, k, v))
    return zigzag_unshard(fn(*args), p_size)
