"""Pallas TPU flash attention: the hot-op kernel for the transformer family.

Forward and backward are hand-written Pallas kernels (the reference
framework has no kernels of its own — SURVEY §2.2 "No CUDA kernels... GPU
work is cudaMemcpyAsync + NCCL"; on TPU the hot op IS the kernel, so this
framework ships one).  Design per the TPU architecture:

- the q/k score and p/v matmuls run on the MXU in fp32 accumulation
  (``preferred_element_type``), activations may be bf16;
- online-softmax streaming over K blocks keeps the working set in VMEM —
  O(T) memory instead of the O(T²) score matrix;
- grid = (batch*heads, q-blocks); the K-block loop is a ``fori_loop``
  inside the kernel over K/V resident in VMEM (for sequences too long for
  VMEM, the ring-attention layer shards the sequence first — each shard's
  local block then fits);
- causal masking skips *whole* K blocks past the diagonal (``@pl.when``),
  so the MXU never sees fully-masked tiles;
- backward recomputes the forward blockwise from the saved logsumexp
  (flash-attention-2 style): one kernel accumulates dq over K blocks, one
  accumulates dk/dv over Q blocks.

Layout: public API takes ``[B, T, H, D]`` (framework convention);
kernels run on ``[B*H, T, D]``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu is importable on CPU too, but keep a guard for odd builds
    from jax.experimental.pallas import tpu as pltpu
    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    pltpu = None
    _VMEM = None

_NEG_INF = -1e30


def _vmem_spec(*args):
    if _VMEM is None:  # pragma: no cover
        return pl.BlockSpec(*args)
    return pl.BlockSpec(*args, memory_space=_VMEM)


def _default_interpret():
    return jax.default_backend() != "tpu"


def _flatten_rows(x, fill=0.0, pad_multiple=8):
    """``[..., d] -> ([n_padded, d], n)``: flatten the leading axes and
    pad the row count up to a sublane multiple with ``fill`` rows (the
    padded rows are kernel garbage the caller slices off).  Shared by
    the row-blocked kernels (layer_norm, softmax_xent)."""
    d = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    x2 = x.reshape(n, d)
    pad = (-n) % pad_multiple
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.full((pad, d), fill, x2.dtype)], axis=0)
    return x2, n


def _pick_block_n(n, d, slabs=1):
    """Row-block size for the row-blocked kernels (layer_norm,
    softmax_xent): keep the kernel's [block_n, d] fp32 slabs well under
    VMEM; ``slabs`` counts how many the kernel holds at once."""
    budget = max((4 << 20) // (d * 4 * slabs), 8)
    for cand in (256, 128, 64, 32, 16, 8):
        if cand <= budget and n % cand == 0:
            return cand
    return 8  # callers pad the row count to a multiple of 8 first


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes of ``like``, so a
    forward call also composes with a caller's checked ``jax.shard_map``
    (empty under the framework's own, unchecked one)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ----------------------------------------------------- row-scalar packing
#
# Per-row scalars (logsumexp, delta) are natural [rows, 1] columns inside
# the kernels (rows = sublanes) but must not be stored to HBM broadcast
# across a 128-lane tile — that costs 128x the necessary bandwidth and
# capped long-sequence backward (the bundled jax.experimental kernel
# pays exactly this).  When block_q == 128 the scalars are packed dense:
# HBM shape [bh, t/128, 1, 128], one q-block's column per lane row (the
# singleton sublane axis satisfies the TPU block-shape rule — the last
# two block dims must divide (8, 128) or equal the array dims).  The
# lane<->sublane conversion uses an MXU identity contraction — bit-exact
# for fp32 (one nonzero term per output) and guaranteed to lower on any
# Mosaic version, unlike a reshape across the minor-two dims.

def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
            ).astype(jnp.float32)


def _col_to_row(c):
    """[n, 1] fp32 column -> [1, n] lane row (MXU transpose)."""
    return jax.lax.dot_general(c, _eye(c.shape[0]), (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _row_to_col(r):
    """[1, n] lane row -> [n, 1] fp32 column (MXU transpose)."""
    return jax.lax.dot_general(_eye(r.shape[1]), r, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


_PACK = 128  # lane width: one q-block of row scalars per packed lane row


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, packed):
    # q_ref: [block_q, d]; k_ref/v_ref: [t_kv, d]; o_ref: [block_q, d]
    # lse_ref: packed [1, 128] (one lane per row) or broadcast
    # [block_q, 128] for odd block sizes
    iq = pl.program_id(1)
    t_kv = k_ref.shape[1]
    d = q_ref.shape[2]
    nk = t_kv // block_k

    q = q_ref[0].astype(jnp.float32) * scale

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o0 = jnp.zeros((block_q, d), jnp.float32)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(ik, carry):
        m, l, o = carry
        k_blk = k_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        if causal:
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(m > _NEG_INF / 2, alpha, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o = o * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, o

    if causal:
        # K blocks fully past this q block contribute nothing; the loop
        # bound itself is static-per-program via the grid index.
        nk_eff = jnp.minimum(
            (iq + 1) * block_q + block_k - 1, t_kv) // block_k
    else:
        nk_eff = nk
    m, l, o = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, o0))

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)
    if packed:
        lse_ref[0, 0] = _col_to_row(lse)
    else:
        lse_ref[0] = jnp.broadcast_to(lse, (block_q, 128))


def _fwd(q3, k3, v3, *, scale, causal, block_q, block_k, interpret):
    """Returns ``(out [bh, t, d], lse [bh, t])``."""
    bh, t, d = q3.shape
    t_kv = k3.shape[1]
    nq = t // block_q
    packed = block_q == _PACK

    if packed:
        lse_spec = _vmem_spec((1, 1, 1, _PACK), lambda b, i: (b, i, 0, 0))
        lse_shape = _sds((bh, nq, 1, _PACK), jnp.float32, q3)
    else:
        lse_spec = _vmem_spec((1, block_q, 128), lambda b, i: (b, i, 0))
        lse_shape = _sds((bh, t, 128), jnp.float32, q3)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, packed=packed),
        grid=(bh, nq),
        in_specs=[
            _vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            _vmem_spec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((1, t_kv, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            lse_spec,
        ],
        out_shape=[
            _sds((bh, t, d), q3.dtype, q3),
            lse_shape,
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out, lse.reshape(bh, t) if packed else lse[:, :, 0]


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q, block_k, packed):
    iq = pl.program_id(1)
    t_kv = k_ref.shape[1]
    d = q_ref.shape[2]
    nk = t_kv // block_k

    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    if packed:
        lse = _row_to_col(lse_ref[0, 0])                    # [bq, 1]
        delta = _row_to_col(delta_ref[0, 0])
    else:
        lse = lse_ref[0, :, 0:1]                            # [bq, 1]
        delta = delta_ref[0, :, 0:1]

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(ik, dq):
        k_blk = k_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # fully-masked rows carry lse = m = NEG_INF; exp(s - lse)
        # there would be exp(0) = 1 per entry — mirror the
        # forward's guard so such rows contribute zero gradient
        p = jnp.where(lse > _NEG_INF / 2,
                      jnp.exp(s - lse), 0.0)              # [bq, bk]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    nk_eff = (jnp.minimum((iq + 1) * block_q + block_k - 1, t_kv)
              // block_k) if causal else nk
    dq = jax.lax.fori_loop(0, nk_eff, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    packed):
    ik = pl.program_id(1)
    t_q = q_ref.shape[1]
    d = k_ref.shape[2]
    nq = t_q // block_q

    k_blk = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v_blk = v_ref[0].astype(jnp.float32)

    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(iq, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        if packed:
            lse = _row_to_col(lse_ref[0, pl.ds(iq, 1), 0, :])
            delta = _row_to_col(delta_ref[0, pl.ds(iq, 1), 0, :])
        else:
            lse = lse_ref[0, pl.ds(iq * block_q, block_q), 0:1]
            delta = delta_ref[0, pl.ds(iq * block_q, block_q), 0:1]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        # fully-masked rows carry lse = m = NEG_INF; exp(s - lse)
        # there would be exp(0) = 1 per entry — mirror the
        # forward's guard so such rows contribute zero gradient
        p = jnp.where(lse > _NEG_INF / 2,
                      jnp.exp(s - lse), 0.0)              # [bq, bk]
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        return dk, dv

    if causal:
        # q blocks strictly before this k block see none of it
        iq_start = (ik * block_k) // block_q
    else:
        iq_start = 0
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(iq_start, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(res, g, *, scale, causal, block_q, block_k, interpret,
         g_lse=None):
    q3, k3, v3, out, lse = res
    bh, t, d = q3.shape
    t_kv = k3.shape[1]
    nq = t // block_q
    nk = t_kv // block_k

    # delta_i = rowsum(dO * O) — cheap elementwise, leave it to XLA.
    # A cotangent on lse folds in exactly here: d s = p*(dp - delta)*scale
    # gains p*g_lse*scale (since dlse/ds = p), i.e. delta -= g_lse.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                # [bh, t]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    packed = block_q == _PACK
    if packed:
        # dense: one q-block's 128 row scalars per lane row (a reshape,
        # i.e. free) — 128x less HBM than the broadcast fallback below
        lse_b = lse.reshape(bh, nq, 1, _PACK)
        delta_b = delta.reshape(bh, nq, 1, _PACK)
        dq_lse_spec = _vmem_spec((1, 1, 1, _PACK),
                                 lambda b, i: (b, i, 0, 0))
        dkv_lse_spec = _vmem_spec((1, nq, 1, _PACK),
                                  lambda b, i: (b, 0, 0, 0))
    else:
        lse_b = jnp.broadcast_to(lse[:, :, None], (bh, t, 128))
        delta_b = jnp.broadcast_to(delta[:, :, None], (bh, t, 128))
        dq_lse_spec = _vmem_spec((1, block_q, 128), lambda b, i: (b, i, 0))
        dkv_lse_spec = _vmem_spec((1, t, 128), lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, packed=packed),
        grid=(bh, nq),
        in_specs=[
            _vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            _vmem_spec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
            dq_lse_spec,
            dq_lse_spec,
        ],
        out_specs=_vmem_spec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=_sds((bh, t, d), q3.dtype, q3),
        interpret=interpret,
    )(q3, k3, v3, g, lse_b, delta_b)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, packed=packed),
        grid=(bh, nk),
        in_specs=[
            _vmem_spec((1, t, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((1, block_k, d), lambda b, i: (b, i, 0)),
            _vmem_spec((1, block_k, d), lambda b, i: (b, i, 0)),
            _vmem_spec((1, t, d), lambda b, i: (b, 0, 0)),
            dkv_lse_spec,
            dkv_lse_spec,
        ],
        out_specs=[
            _vmem_spec((1, block_k, d), lambda b, i: (b, i, 0)),
            _vmem_spec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, t_kv, d), k3.dtype, k3),
            _sds((bh, t_kv, d), v3.dtype, v3),
        ],
        interpret=interpret,
    )(q3, k3, v3, g, lse_b, delta_b)
    return dq, dk, dv


# ------------------------------------------------------------- public API

def _pick_block(t, want):
    """Largest divisor of t that is <= want (kernel blocks must tile T)."""
    if want < 1:
        raise ValueError(f"block size must be >= 1, got {want}")
    b = min(want, t)
    while t % b != 0:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q3, k3, v3, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q3, k3, v3, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd(res, g, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    """Like ``_flash`` but also returns the logsumexp — the streaming-
    softmax state ring attention needs to combine per-block results."""
    return _fwd(q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret)


def _flash_lse_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q3, k3, v3, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return (out, lse), (q3, k3, v3, out, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, res, g):
    g_out, g_lse = g
    return _bwd(res, g_out, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _env_block(name, default):
    from horovod_tpu.utils.env import get_int

    value = get_int(name, default)
    return value if value >= 1 else default


def flash_attention(q, k, v, *, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, return_lse=False):
    """Flash multi-head attention, ``[B, T, H, D] -> [B, T, H, D]``.

    Differentiable (custom VJP with Pallas backward kernels).  On
    non-TPU backends runs in Pallas interpret mode (tests);
    drop-in for ``TransformerConfig.attn_fn`` and as the local-block
    kernel of ring/Ulysses attention.

    ``return_lse=True`` additionally returns the logsumexp ``[B, H, T]``
    (differentiable), which lets callers combine partial attention
    results streaming-softmax style (ring attention's per-block use).
    """
    b, t, h, d = q.shape
    t_kv = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _default_interpret()
    # HVD_FLASH_BLOCK_Q/K override the 128 x 128 default, which no
    # sweep on a chip has confirmed yet (ROADMAP D4).  block_q=128 keeps
    # the packed lse/delta layout; other values fall back to the
    # broadcast layout.
    if block_q is None:
        block_q = _env_block("HVD_FLASH_BLOCK_Q", 128)
    if block_k is None:
        block_k = _env_block("HVD_FLASH_BLOCK_K", 128)
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(t_kv, block_k)

    def to3(x):
        tt = x.shape[1]
        return x.transpose(0, 2, 1, 3).reshape(b * h, tt, x.shape[3])

    if return_lse:
        out3, lse3 = _flash_lse(to3(q), to3(k), to3(v), scale, causal,
                                block_q, block_k, interpret)
        out = out3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        return out, lse3.reshape(b, h, t)

    out3 = _flash(to3(q), to3(k), to3(v), scale, causal, block_q, block_k,
                  interpret)
    return out3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
