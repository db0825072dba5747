"""Ring attention: sequence/context parallelism over the ICI ring.

The reference framework has no long-context support (SURVEY §5: no ring
attention / sequence parallelism anywhere in d3v3l0/horovod); this module is
the TPU-native design for it.  Queries stay resident on their shard while
key/value blocks rotate around the mesh axis with ``jax.lax.ppermute`` —
each hop rides one ICI link, so communication overlaps with the local
blockwise attention compute (XLA schedules the collective-permute
asynchronously against the einsums).

Numerical scheme: streaming (online) softmax in float32 — the same
log-sum-exp accumulation flash attention uses — so the result is exact
attention, independent of how many ring steps the K/V visit takes.

Usage: call :func:`ring_attention` *inside* a ``shard_map`` whose mesh has
the sequence axis, or use :func:`ring_self_attention` which wraps the
shard_map for you.

Shapes (per shard): q ``[B, Tq, H, D]``, k/v ``[B, Tkv, H, D]`` with the
global sequence dimension split over ``axis_name``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel._compat import shard_map


_NEG_INF = -1e30


def _block_attend(q, k, v, *, scale, mask=None):
    """One blockwise attention step; returns (numerator, denom, running max)
    contributions in float32.

    q: [B, Tq, H, D]; k, v: [B, Tkv, H, D].
    mask: broadcastable to [B, H, Tq, Tkv] (True = attend) or None.
    """
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                      # [B, H, Tq]
    p = jnp.exp(s - m[..., None])
    # Fully-masked rows have m == _NEG_INF; exp(s - m) would be 1 there.
    p = jnp.where(m[..., None] > _NEG_INF / 2, p, 0.0)
    l = jnp.sum(p, axis=-1)                      # [B, H, Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, l, m


def _combine(o1, l1, m1, o2, l2, m2):
    """Merge two streaming-softmax partial results (flash-attention rule)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    a1 = jnp.where(m1 > _NEG_INF / 2, a1, 0.0)
    a2 = jnp.where(m2 > _NEG_INF / 2, a2, 0.0)
    o = o1 * a1.transpose(0, 2, 1)[..., None] + \
        o2 * a2.transpose(0, 2, 1)[..., None]
    l = l1 * a1 + l2 * a2
    return o, l, m


def ring_attention(q, k, v, *, axis_name, causal=False, scale=None,
                   query_chunk_idx=None, use_flash=None):
    """Exact multi-head attention with K/V blocks rotating over ``axis_name``.

    Must run inside ``shard_map``/``pmap`` with ``axis_name`` bound; the
    global sequence dimension of q/k/v is split across that axis.

    causal: positions are global — shard ``i`` holds queries
    ``[i*Tq, (i+1)*Tq)`` and keys ``[i*Tkv, (i+1)*Tkv)``.  Off-diagonal
    blocks fully behind the queries are computed unmasked; blocks fully
    ahead are skipped via ``lax.cond`` (no FLOPs on the MXU for them).

    use_flash: compute each local block with the Pallas flash kernel
    (``ops/pallas/flash_attention.py``) instead of the dense einsum —
    O(block) VMEM instead of the O(Tq*Tkv) score matrix.  Default: on
    when running on TPU.  The kernel's logsumexp output feeds the same
    streaming-softmax combine as the dense path, so results are exact
    either way.
    """
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name) if query_chunk_idx is None \
        else query_chunk_idx
    b, tq, h, d = q.shape
    tkv = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"

    q32 = q.astype(jnp.float32)
    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    # Under a caller's checked shard_map the accumulators become
    # device-varying inside the loop, so the initial carry must be too.
    o0, l0, m0 = (lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, l0, m0))

    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    def _flash_block(kc, vc, kv_idx):
        """Local block via the Pallas kernel.  On the diagonal block the
        global causal mask reduces to the local one (tq == tkv and equal
        offsets), behind-blocks are unmasked, ahead-blocks were already
        skipped — so the kernel's static `causal` flag suffices."""
        from horovod_tpu.ops.pallas.flash_attention import flash_attention

        def run(is_causal):
            out, lse = flash_attention(
                q, kc.astype(q.dtype), vc.astype(q.dtype),
                causal=is_causal, scale=scale, return_lse=True)
            # represent as (numerator, denom, max): normalized out with
            # denom=1 in lse units plugs into the same _combine rule
            ones = lax.pcast(jnp.ones((b, h, tq), jnp.float32),
                             (axis_name,), to="varying")
            return (out.astype(jnp.float32), ones, lse)

        if causal:
            return lax.cond(kv_idx == my_idx,
                            lambda _: run(True),
                            lambda _: run(False), operand=None)
        return run(False)

    def block(o, l, m, kc, vc, kv_idx):
        def attend(_):
            # the kernel's local causal mask only matches the global one
            # on equal-length shards; fall back to the dense path else
            if use_flash and (not causal or tq == tkv):
                return _flash_block(kc, vc, kv_idx)
            if causal:
                q_pos = my_idx * tq + jnp.arange(tq)
                k_pos = kv_idx * tkv + jnp.arange(tkv)
                msk = q_pos[:, None] >= k_pos[None, :]
                msk = msk[None, None, :, :]
            else:
                msk = None
            return _block_attend(q32, kc, vc, scale=scale, mask=msk)

        def skip(_):
            return (jnp.zeros_like(o), jnp.zeros_like(l),
                    jnp.full_like(m, _NEG_INF))

        if causal:
            # Skip blocks strictly in the future of every query on this shard
            # (assumes tq == tkv sharding of one global sequence).
            need = (kv_idx * tkv) <= (my_idx * tq + tq - 1)
            bo, bl, bm = lax.cond(need, attend, skip, operand=None)
        else:
            bo, bl, bm = attend(None)
        return _combine(o, l, m, bo, bl, bm)

    # Peel the resident (local) K/V block so the scan does exactly
    # p_size - 1 permutes — no discarded final rotation on the ICI.
    # K/V rotate in their ORIGINAL dtype: upcasting first would double
    # the ICI bytes per hop for bf16 activations, and both local paths
    # cast per block anyway (_block_attend to f32, flash to q.dtype).
    o0, l0, m0 = block(o0, l0, m0, k, v, my_idx)

    def step(carry, s):
        o, l, m, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        kv_idx = (my_idx - s) % p_size      # origin shard of current K/V
        o, l, m = block(o, l, m, kc, vc, kv_idx)
        return (o, l, m, kc, vc), None

    (o, l, m, _, _), _ = lax.scan(
        step, (o0, l0, m0, k, v), jnp.arange(1, p_size))

    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_self_attention(q, k, v, mesh, *, axis_name="sp", causal=False,
                        scale=None):
    """Convenience wrapper: shard q/k/v on their sequence dim over
    ``axis_name`` and run :func:`ring_attention` under ``shard_map``.

    q, k, v: global arrays ``[B, T, H, D]`` (T divisible by the axis size).
    """
    spec = P(None, axis_name, None, None)

    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

    sharding = NamedSharding(mesh, spec)
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal=False, scale=None, window=None):
    """Dense single-device reference (for tests and small sequences).
    k and v may have fewer heads than q (grouped key-value heads: q's
    head ``h`` of ``H`` reads head ``h // (H / G)`` of their ``G``);
    with ``window`` (and ``causal``) query i sees the keys ``i - window
    < j <= i``."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if window is not None and not causal:
        raise ValueError("reference_attention: a window needs causal=True")
    group = q.shape[2] // k.shape[2]
    if group > 1:  # dense: a group's query heads each get a copy
        k, v = (jnp.repeat(u, group, axis=2) for u in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        msk = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            msk &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] < window
        s = jnp.where(msk[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
