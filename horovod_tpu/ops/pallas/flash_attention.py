"""Pallas TPU flash attention: the hot-op kernel for the transformer family.

Forward and backward are hand-written Pallas kernels (the reference
framework has no kernels of its own — SURVEY §2.2 "No CUDA kernels... GPU
work is cudaMemcpyAsync + NCCL"; on TPU the hot op IS the kernel, so this
framework ships one).  Design per the TPU architecture:

- every product runs on the MXU with **operands in the input dtype** and
  float32 accumulation (``preferred_element_type``): bfloat16 q, k, v, dO
  go in as they arrive, and ``p`` / ``ds`` are rounded to that dtype at
  their product only; float32 inputs keep float32 operands.  The running
  max and sum, ``lse``, ``delta``, the softmax and every accumulator are
  float32 for any input;
- online-softmax streaming over K blocks keeps the working set in VMEM —
  O(T) memory instead of the O(T²) score matrix;
- grid = (batch*heads, q-blocks); the K-block loop is a ``fori_loop``
  inside the kernel over K/V resident in VMEM (for sequences too long for
  VMEM, the ring-attention layer shards the sequence first — each shard's
  local block then fits);
- causal masking is in the **loop bounds**: K blocks past the diagonal are
  never visited, blocks every row sees whole run with no mask work at all
  (no iota, compare or select), and only the blocks the diagonal crosses
  run the masked body;
- both kernels hold their scores **transposed**, ``k @ q.T -> [block_k,
  block_q]``: a q block's row scalars (the forward's running max, sum and
  rescale; the backward's ``lse`` and ``delta``) are lane rows that
  broadcast down the sublanes, and a reduction over the keys runs down
  the sublanes.  The forward accumulates its output transposed too
  (``o.T [d_v, block_q] += v.T @ p``: the small operand is the one that
  is turned) and turns it once a q block; a masked tile pays one select
  (the scores against ``-inf``; the max never falls under a finite
  floor, so a row that sees nothing in a tile needs no guard);
- a sliding ``window`` (query i sees the keys ``i - window < j <= i``) is
  in the **loop bounds** too, forward and backward: K blocks that lie
  wholly before a q block's window are not visited either, so a q block
  visits the blocks that hold an allowed pair and no other (at 512 x 512
  and a window of 512: two, whatever T), only the blocks an edge (the
  diagonal or the window's far edge) crosses run the masked body, and
  the backward's k block visits only the q blocks inside its window;
- a third shape of key set, **stairs** (``stairs=(step_q, step_k)``: query
  i sees the keys ``j < (i // step_q) * step_k`` of a k and v of another
  length, what attention linearised by chunk reads of its summaries,
  ``ops/chunk_attention.py``), is loop bounds alone: the blocks divide the
  steps, so a q block visits the k blocks under its stair whole and no
  tile is crossed by an edge; the backward's k block visits the q blocks
  of every later stair;
- **grouped key-value heads** are read through the index map: q has
  ``H`` heads, k and v ``G`` (``H = G x group``), head ``h`` of q reads
  head ``h // group`` of k and v where they lie, so k and v are never
  repeated to ``H`` heads in HBM.  The heads of a group are neighbours
  on the grid, so a group's k and v are fetched once; dk and dv of a
  key-value head are summed over its group's query heads in float32 in
  VMEM and leave once, with the group's last head;
- backward recomputes the forward blockwise from the saved logsumexp
  (flash-attention-2 style) in ONE kernel: a block pair's scores,
  exponentials and ``dO v.T`` are computed once and dq, dk and dv all
  come from them (five products a pair, the mathematics' own).  Its grid
  is (batch*heads, k-blocks) with a loop over q blocks: dk and dv are
  sums over q blocks and leave with the grid step; dq is a sum over k
  blocks, so a head's whole dq is carried in float32 in VMEM across the
  head's grid steps and written once.  Only dq's product (``ds.T @ k``)
  has a transposed left operand.  The call states the scoped VMEM its
  blocks need (``_bwd_vmem_bytes``).

Layout: public API takes ``[B, T, H, D]`` (framework convention);
kernels run on ``[B*H, T, D]``.  q and k share one width (``d_qk``, the
contraction of the scores), v and the output another (``d_v``): latent
attention has heads of 192 for the scores and of 128 for the values.
Either may be any width the array itself has (a block's last dimension
is the array's), so 192 goes in as it is and is not padded to 256.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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
    """Row-block size for ``layer_norm``, whose blocks are whole rows:
    keep the kernel's [block_n, d] fp32 slabs well under VMEM; ``slabs``
    counts how many the kernel holds at once.  (``softmax_xent`` tiles
    the columns too and asks in its own file.)"""
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
# Per-row scalars (the running max and sum, logsumexp, delta) must not be
# stored to HBM broadcast across a 128-lane tile — that costs 128x the
# necessary bandwidth and capped long-sequence backward (the bundled
# jax.experimental kernel pays exactly this).  They are stored dense, one
# q-block's scalars per lane row: HBM shape [bh, t/block_q, 1, block_q],
# the bytes of [bh, t] (the singleton sublane axis satisfies the TPU
# block-shape rule — the last two block dims must divide (8, 128) or equal
# the array dims).  Both kernels hold their scores transposed, [block_k,
# block_q], so a q block's scalars ARE such a lane row inside them: it
# broadcasts down the sublanes as it is, a reduction over the keys runs
# down the sublanes, and the forward kernel stores its ``lse`` row as it
# stands.

_PACK = 128  # lane width
_BLOCK = 512  # default block_q and block_k: see flash_attention()
_VMEM_DEFAULT = 16 << 20  # the scope a call gets that asks for none


def _is_pow2(scale):
    """A power-of-two ``scale`` (1/8 at heads of 64) multiplies exactly
    in any dtype, so it can move onto an operand or an accumulator and
    off the ``[block_q, block_k]`` tile, bit for bit."""
    return math.frexp(scale)[0] == 0.5


def _dot(a, b, contract):
    """MXU product with float32 accumulation; ``contract`` names the
    contracted dimension of ``a`` and of ``b``."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_loops(body, carry, bounds):
    """Run ``body(i, carry, masked=...)`` over the index ranges
    ``bounds = [(lo, hi, masked), ...]`` in turn."""
    for lo, hi, masked in bounds:
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(body, masked=masked), carry)
    return carry


def _k_bounds(iq, *, causal, block_q, block_k, t_kv, window=None,
              stairs=None):
    """K-block ranges for q block ``iq``: whole blocks, then the blocks
    the diagonal crosses; blocks past it are not visited.  With a
    ``window`` the blocks wholly before it are not visited either, and
    the blocks its far edge crosses come first, masked.  With ``stairs
    = (step_q, step_k)`` (block_q divides the one, block_k the other) a
    q block lies on one stair and sees the k blocks under it whole: no
    block is crossed by an edge."""
    if stairs is not None:
        step_q, step_k = stairs
        return [(0, jnp.minimum((iq * block_q // step_q)
                                * (step_k // block_k), t_kv // block_k),
                 False)]
    if not causal:
        return [(0, t_kv // block_k, False)]
    seen = jnp.minimum((iq + 1) * block_q + block_k - 1, t_kv) // block_k
    whole = jnp.minimum((iq * block_q + 1) // block_k, seen)
    if window is None:
        return [(0, whole, False), (whole, seen, True)]
    # the first block with a key the block's first row sees, and the
    # first whose every key the block's last row still sees
    first = jnp.maximum(iq * block_q - window + 1, 0) // block_k
    inside = jnp.clip(
        jnp.maximum((iq + 1) * block_q - window + block_k - 1, 0) // block_k,
        first, seen)
    whole = jnp.clip(whole, inside, seen)
    return [(first, inside, True), (inside, whole, False),
            (whole, seen, True)]


def _q_bounds(ik, *, causal, block_q, block_k, nq, window=None,
              stairs=None):
    """Q-block ranges for k block ``ik`` (the backward's loop): q blocks
    before this k block see none of it, the blocks the diagonal crosses
    run masked, the rest see all of it; with a ``window`` the q blocks
    wholly past it are not visited and the blocks its far edge crosses
    come last, masked.  With ``stairs`` the mirror of ``_k_bounds``: the
    q blocks of every LATER stair, whole."""
    if stairs is not None:
        step_q, step_k = stairs
        return [(jnp.minimum((ik * block_k // step_k + 1)
                             * (step_q // block_q), nq), nq, False)]
    if not causal:
        return [(0, nq, False)]
    first = (ik * block_k) // block_q
    whole = jnp.clip(((ik + 1) * block_k + block_q - 2) // block_q,
                     first, nq)
    if window is None:
        return [(first, whole, True), (whole, nq, False)]
    last = jnp.minimum(((ik + 1) * block_k + window - 2) // block_q + 1, nq)
    whole = jnp.minimum(whole, last)
    inside = jnp.clip((ik * block_k + window) // block_q, whole, last)
    return [(first, whole, True), (whole, inside, False),
            (inside, last, True)]


def _allowed(q_pos, k_pos, window):
    """The pairs a masked block keeps: the key at or before the query
    and, with a ``window``, fewer than ``window`` positions before it."""
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, window=None, stairs=None):
    # q_ref: [block_q, d_qk]; k_ref: [t_kv, d_qk]; v_ref: [t_kv, d_v];
    # o_ref: [block_q, d_v]; lse_ref: [1, block_q], one lane per row.
    # Scores are held transposed, [block_k, block_q], as in the backward:
    # the running max, sum and rescale of a q block are lane rows (four
    # registers at 512, where a column takes 64 with one lane in use),
    # the max and the sum over the keys run down the sublanes, and the
    # output is accumulated transposed, [d_v, block_q], and turned once.
    iq = pl.program_id(1)
    t_kv = k_ref.shape[1]
    d_v = v_ref.shape[2]

    q = q_ref[0]
    # scaling q loses nothing for a power of two; float32 q was always
    # scaled so and stays bit for bit.  Otherwise (bfloat16 at, say,
    # 1/sqrt(80)) the float32 scores are scaled, as in the backward.
    fold_scale = _is_pow2(scale) or q.dtype == jnp.float32
    if fold_scale:
        q = q * scale

    def body(ik, carry, *, masked):
        m, l, o_t = carry
        k_blk = k_ref[0, pl.ds(ik * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ik * block_k, block_k), :]
        s = _dot(k_blk, q, (1, 1))                       # [bk, bq]
        if not fold_scale:
            s = s * scale
        if masked:
            # q_pos >= k_pos, and with a window q_pos - k_pos < window,
            # as a row of query offsets (along the lanes) against a
            # column of key offsets (down the sublanes) plus one or two
            # scalars: one select over the tile.  (The two iotas are
            # made here: carried into the loops from outside they cost
            # 1-3% of the kernel on the chip.)  A masked score is -inf
            # and m is never under _NEG_INF, so p is 0 there whatever
            # the row has seen: a row that sees nothing in this tile
            # keeps its m, l and o_t (alpha = 1) with no guard on p.
            q_off = jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
            k_pos = (jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                     + (ik * block_k - iq * block_q))
            keep = q_off >= k_pos
            if window is not None:
                keep = keep & (q_off < k_pos + window)
            s = jnp.where(keep, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)                       # [1, bq]
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        # v.T @ p: the small operand is the one that is turned
        o_t = o_t * alpha + _dot(v_blk, p.astype(v_blk.dtype), (0, 0))
        return m_new, l, o_t

    m, l, o_t = _causal_loops(
        body,
        (jnp.full((1, block_q), _NEG_INF, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32),
         jnp.zeros((d_v, block_q), jnp.float32)),
        _k_bounds(iq, causal=causal, block_q=block_q, block_k=block_k,
                  t_kv=t_kv, window=window, stairs=stairs))

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (o_t * (1.0 / l_safe)).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _kv_head(group):
    """The index map of k's and v's whole head for q's head ``b`` of
    ``[B H, ...]``: with ``H = G x group`` heads in q's order, head
    ``b // group`` of ``[B G, ...]``."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


def _fwd(q3, k3, v3, *, scale, causal, block_q, block_k, interpret,
         window=None, stairs=None):
    """Returns ``(out [bh, t, d_v], lse [bh, t])``; k3 and v3 may have
    fewer heads than q3 (``bh`` a multiple of theirs)."""
    bh, t, d_qk = q3.shape
    t_kv, d_v = v3.shape[1:]
    nq = t // block_q
    kv_head = _kv_head(bh // k3.shape[0])

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window,
                          stairs=stairs),
        grid=(bh, nq),
        in_specs=[
            _vmem_spec((1, block_q, d_qk), lambda b, i: (b, i, 0)),
            _vmem_spec((1, t_kv, d_qk), kv_head),
            _vmem_spec((1, t_kv, d_v), kv_head),
        ],
        out_specs=[
            _vmem_spec((1, block_q, d_v), lambda b, i: (b, i, 0)),
            _vmem_spec((1, 1, 1, block_q), lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            _sds((bh, t, d_v), q3.dtype, q3),
            _sds((bh, nq, 1, block_q), jnp.float32, q3),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out, lse.reshape(bh, t)


# --------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc, scale, causal,
                block_q, block_k, window=None, group=1, stairs=None):
    # One k block of one head a grid step.  Scores are held transposed,
    # [block_k, block_q]: a q block's lse and delta broadcast down the
    # sublanes from the lane rows they are stored as, and p.T @ dO,
    # ds.T @ q are plain products.  dq is summed over k blocks, which the
    # grid walks: its float32 sum for the whole head lives in ``dq_acc``
    # across the head's grid steps and is written once, at the last.
    # With grouped key-value heads (``group`` > 1) the grid's heads are
    # q's and k_ref, v_ref are the group's: dk and dv are sums over the
    # group's heads too, which the grid also walks, so the float32 sums
    # of the whole key-value head live in ``kv_acc`` across the group's
    # grid steps and leave block by block with the group's last head.
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    t_q = q_ref.shape[1]
    nq = t_q // block_q

    v_blk = v_ref[0]                                        # [bk, d_v]
    # a power-of-two scale rides on k (for the scores, and from there
    # on dq) and on the finished dk instead of on two [bk, bq] tiles a
    # block pair
    fold_scale = _is_pow2(scale)
    k_blk = k_ref[0] * scale if fold_scale else k_ref[0]

    @pl.when(ik == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)

    def body(iq, carry, *, masked):
        dk, dv = carry
        rows = pl.ds(iq * block_q, block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, pl.ds(iq, 1), 0, :]                # [1, bq]
        delta = delta_ref[0, pl.ds(iq, 1), 0, :]
        s = _dot(k_blk, q, (1, 1))                          # [bk, bq]
        if not fold_scale:
            s = s * scale
        p = jnp.exp(s - lse)
        if masked:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            # a fully-masked row carries lse = NEG_INF: mirror the
            # forward's guard so it contributes zero gradient
            p = jnp.where(_allowed(q_pos, k_pos, window)
                          & (lse > _NEG_INF / 2), p, 0.0)
        dv = dv + _dot(p.astype(do.dtype), do, (1, 0))      # [bk, d_v]
        ds = p * (_dot(v_blk, do, (1, 1)) - delta)          # [bk, bq]
        if not fold_scale:
            ds = ds * scale
        ds = ds.astype(q.dtype)
        dk = dk + _dot(ds, q, (1, 0))                       # [bk, d_qk]
        # the one product whose left operand is transposed
        dq_acc[rows, :] += _dot(ds, k_blk, (0, 0))          # [bq, d_qk]
        return dk, dv

    bounds = _q_bounds(ik, causal=causal, block_q=block_q, block_k=block_k,
                       nq=nq, window=window, stairs=stairs)
    dk, dv = _causal_loops(
        body, (jnp.zeros(k_blk.shape, jnp.float32),
               jnp.zeros(v_blk.shape, jnp.float32)), bounds)
    if fold_scale:
        dk = dk * scale
    if group == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
    else:
        dk_acc, dv_acc = kv_acc
        member = pl.program_id(0) % group
        k_rows = pl.ds(ik * block_k, block_k)

        @pl.when(member == 0)
        def _():
            dk_acc[k_rows, :] = dk
            dv_acc[k_rows, :] = dv

        @pl.when(member > 0)
        def _():
            dk_acc[k_rows, :] += dk
            dv_acc[k_rows, :] += dv

        @pl.when(member == group - 1)
        def _():
            dk_ref[0] = dk_acc[k_rows, :].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[k_rows, :].astype(dv_ref.dtype)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _padded_bytes(rows, cols, itemsize):
    """What a ``[rows, cols]`` array takes in VMEM: the lanes padded to
    128 and the sublanes to a tile of 32 bytes a lane."""
    sublanes = 8 * 4 // itemsize
    return (-(-rows // sublanes) * sublanes * -(-cols // _PACK) * _PACK
            * itemsize)


def _bwd_vmem_bytes(t, d_qk, d_v, block_q, block_k, itemsize, group=1,
                    t_kv=None):
    """The scoped VMEM the backward call asks for, from its own blocks:
    what the pipeline double-buffers (q, dO and dq of the whole head, k,
    v, dk and dv a block, the packed row scalars), the head's float32 dq
    and the float32 tiles and carries of one block pair (with grouped
    key-value heads also the float32 dk and dv of the whole key-value
    head), with a quarter more; never under the scope a call gets that
    asks for none."""
    whole = (2 * _padded_bytes(t, d_qk, itemsize)
             + _padded_bytes(t, d_v, itemsize))
    k_side = 2 * (_padded_bytes(block_k, d_qk, itemsize)
                  + _padded_bytes(block_k, d_v, itemsize))
    scalars = 2 * (t // block_q) * _padded_bytes(1, block_q, 4)
    pair = (6 * _padded_bytes(block_k, block_q, 4)
            + 2 * _padded_bytes(block_k, d_qk, 4)
            + 2 * _padded_bytes(block_k, d_v, 4)
            + 2 * _padded_bytes(block_q, d_qk, 4))
    need = (2 * (whole + k_side + scalars)
            + _padded_bytes(t, d_qk, 4) + pair)
    if group > 1:
        need += (_padded_bytes(t_kv or t, d_qk, 4)
                 + _padded_bytes(t_kv or t, d_v, 4))
    return max(need + need // 4, _VMEM_DEFAULT)


def _bwd(res, g, *, scale, causal, block_q, block_k, interpret,
         g_lse=None, window=None, stairs=None):
    q3, k3, v3, out, lse = res
    bh, t, d_qk = q3.shape
    t_kv, d_v = v3.shape[1:]
    nq = t // block_q
    nk = t_kv // block_k
    group = bh // k3.shape[0]

    # delta_i = rowsum(dO * O) — cheap elementwise, leave it to XLA.
    # A cotangent on lse folds in exactly here: d s = p*(dp - delta)*scale
    # gains p*g_lse*scale (since dlse/ds = p), i.e. delta -= g_lse.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                # [bh, t]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    # one q block's row scalars per lane row (a reshape, i.e. free)
    lse_b = lse.reshape(bh, nq, 1, block_q)
    delta_b = delta.reshape(bh, nq, 1, block_q)
    lse_spec = _vmem_spec((1, nq, 1, block_q), lambda b, i: (b, 0, 0, 0))
    whole_qk = _vmem_spec((1, t, d_qk), lambda b, i: (b, 0, 0))
    scratch = [pltpu.VMEM((t, d_qk), jnp.float32)]
    if group == 1:
        def k_block(b, i):
            return b, i, 0

        dk_block = k_block
    else:
        scratch += [pltpu.VMEM((t_kv, d_qk), jnp.float32),
                    pltpu.VMEM((t_kv, d_v), jnp.float32)]

        def k_block(b, i):
            return b // group, i, 0

        def dk_block(b, i):
            # the group's earlier heads write nothing: their block stays
            # the group's first, which the last head writes first, so
            # nothing goes to HBM before it holds the group's sum
            return b // group, jnp.where(b % group == group - 1, i, 0), 0

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window,
                          group=group, stairs=stairs),
        grid=(bh, nk),
        in_specs=[
            whole_qk,
            _vmem_spec((1, block_k, d_qk), k_block),
            _vmem_spec((1, block_k, d_v), k_block),
            _vmem_spec((1, t, d_v), lambda b, i: (b, 0, 0)),
            lse_spec,
            lse_spec,
        ],
        # dq's block is the head's: it stays in VMEM over the head's k
        # blocks and goes to HBM once
        out_specs=[whole_qk, _vmem_spec((1, block_k, d_qk), dk_block),
                   _vmem_spec((1, block_k, d_v), dk_block)],
        out_shape=[
            _sds((bh, t, d_qk), q3.dtype, q3),
            _sds(k3.shape, k3.dtype, k3),
            _sds(v3.shape, v3.dtype, v3),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # a group's heads add into one dk and dv: in order
            dimension_semantics=("parallel" if group == 1 else "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_bytes(
                t, d_qk, d_v, block_q, block_k, q3.dtype.itemsize, group,
                t_kv)),
        interpret=interpret,
    )(q3, k3, v3, g, lse_b, delta_b)
    return dq, dk, dv


# ------------------------------------------------------------- public API

def _pick_block(t, want):
    """Largest block <= want that tiles t (kernel blocks must tile T): a
    multiple of the 128 lanes where one divides t, so that blocks stay
    aligned to the tiling, else the largest divisor of t."""
    if want < 1:
        raise ValueError(f"block size must be >= 1, got {want}")
    b = min(want, t)
    for aligned in range(b - b % _PACK, 0, -_PACK):
        if t % aligned == 0:
            return aligned
    while t % b != 0:
        b -= 1
    return b


# A transformer calls this once a layer with the same shapes: under
# ``jit`` the kernels are traced once and lowered once for all of them,
# not once a call (GPT-2 medium's step: 72 kernel bodies down to 3).
_STATIC = ("scale", "causal", "block_q", "block_k", "interpret", "window",
           "stairs")
_fwd_once = jax.jit(_fwd, static_argnames=_STATIC)
_bwd_once = jax.jit(_bwd, static_argnames=_STATIC)

# What the forward kernel hands the backward kernels carries these
# names.  Outside ``jax.checkpoint`` a name is the identity; a checkpoint
# whose policy is ``save_only_these_names(*SAVED_NAMES)`` keeps the two
# arrays, and the recomputation then has no use for the forward kernel:
# it is dead code and the compiler drops it.
SAVED_OUT = "flash_attention_out"
SAVED_LSE = "flash_attention_lse"
SAVED_NAMES = (SAVED_OUT, SAVED_LSE)
# The kernel's inputs as it reads them (``[B H, T, d]``, after the
# transposes) carry these, AS A SET and only where each of the three is
# no larger than ``out`` (``saved_bytes``): the backward kernel reads all
# three, so two of three buy nothing.  A policy that keeps them too makes
# the projections, the rotation and the transposes ahead of the kernel
# dead code in the recomputation.
SAVED_INPUT_NAMES = ("flash_attention_q", "flash_attention_k",
                     "flash_attention_v")


def saved_bytes(q, k, v):
    """``{name: bytes}`` of what one call on q ``[B, T, H, d_qk]``, k
    ``[B, T_kv, G, d_qk]``, v ``[B, T_kv, G, d_v]`` (arrays or anything
    with their ``shape`` and ``dtype``) gives names to: ``out`` and
    ``lse`` always (``SAVED_NAMES``), and q, k, v in the kernel's layout
    (``SAVED_INPUT_NAMES``) where none of the three is larger than
    ``out`` in bytes.  That is ``d_qk <= d_v`` with k and v no longer
    and of no more heads than q: grouped or plain heads of one width; not
    latent attention's 192 over 128, whose q and k are 1.5 x ``out``."""
    itemsize = jnp.dtype(q.dtype).itemsize
    rows = math.prod(q.shape[:-1])                     # B T H
    kept = {SAVED_OUT: rows * v.shape[-1] * itemsize, SAVED_LSE: rows * 4}
    inputs = {name: math.prod(x.shape) * itemsize
              for name, x in zip(SAVED_INPUT_NAMES, (q, k, v))}
    if max(inputs.values()) <= kept[SAVED_OUT]:
        kept.update(inputs)
    return kept


def _fwd_named(q3, k3, v3, **static):
    out, lse = _fwd_once(q3, k3, v3, **static)
    return checkpoint_name(out, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q3, k3, v3, scale, causal, block_q, block_k, interpret, window,
           stairs):
    out, _ = _fwd_once(q3, k3, v3, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k, interpret=interpret,
                       window=window, stairs=stairs)
    return out


def _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
               window, stairs):
    out, lse = _fwd_named(q3, k3, v3, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, window=window, stairs=stairs)
    return out, (q3, k3, v3, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, stairs,
               res, g):
    return _bwd_once(res, g, scale=scale, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret, window=window,
                     stairs=stairs)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q3, k3, v3, scale, causal, block_q, block_k, interpret,
               window, stairs):
    """Like ``_flash`` but also returns the logsumexp — the streaming-
    softmax state ring attention needs to combine per-block results."""
    return _fwd_once(q3, k3, v3, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, interpret=interpret,
                     window=window, stairs=stairs)


def _flash_lse_fwd(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                   window, stairs):
    out, lse = _fwd_named(q3, k3, v3, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, window=window, stairs=stairs)
    return (out, lse), (q3, k3, v3, out, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, window,
                   stairs, res, g):
    g_out, g_lse = g
    return _bwd_once(res, g_out, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, interpret=interpret,
                     g_lse=g_lse, window=window, stairs=stairs)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _env_block(name, default):
    from horovod_tpu.utils.env import get_int

    value = get_int(name, default)
    return value if value >= 1 else default


def flash_attention(q, k, v, *, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, return_lse=False,
                    window=None, stairs=None):
    """Flash multi-head attention: q ``[B, T, H, d_qk]``, k ``[B, T_kv,
    G, d_qk]``, v ``[B, T_kv, G, d_v]`` -> ``[B, T, H, d_v]``; ``scale``
    defaults to ``1 / sqrt(d_qk)``.

    **Grouped key-value heads**: ``G`` divides ``H``, and q's head ``h``
    reads head ``h // (H / G)`` of k and v where they lie; dk and dv
    come back with ``G`` heads, each the sum over its query heads.
    ``window``: with ``causal``, query i sees the keys ``i - window < j
    <= i`` (``window`` of them, itself included) of a k and v as long as
    q; the kernels visit the blocks that hold such a pair and no other.
    ``stairs = (step_q, step_k)``, without ``causal``: a third shape of
    key set, over a k and v of any length: query i sees the keys ``j <
    (i // step_q) * step_k``, none on the first stair (its rows of the
    output are 0 and its ``lse`` a finite floor of -1e30, so a caller
    that joins this call with another by ``lse`` gives it weight 0 with
    no ``inf - inf``).  The blocks are taken to divide the steps, so no
    tile is crossed by an edge and the stairs are loop bounds alone.

    Differentiable (custom VJP with Pallas backward kernels).  On
    non-TPU backends runs in Pallas interpret mode (tests);
    drop-in for ``TransformerConfig.attn_fn`` and as the local-block
    kernel of ring/Ulysses attention.

    ``return_lse=True`` additionally returns the logsumexp ``[B, H, T]``
    (differentiable), which lets callers combine partial attention
    results streaming-softmax style (ring attention's per-block use).

    Under ``jax.checkpoint`` the forward kernel runs again in the
    recomputation unless the checkpoint's policy saves its two results,
    which carry the names ``SAVED_NAMES``
    (``save_only_these_names(*SAVED_NAMES)``, as a recomputed block of
    ``models/transformer.py`` has it): ``out [B H, T, d_v]`` in the input
    dtype and ``lse [B H, T]`` in float32, ``B H T (d_v x itemsize + 4)``
    bytes a call, are then kept from the forward pass and the backward
    kernel reads them.  Where none of them is larger than ``out``
    (:func:`saved_bytes`), q, k and v as the kernel reads them carry
    ``SAVED_INPUT_NAMES``: a policy that keeps those too has nothing
    ahead of the kernel left to recompute for it.  Outside a checkpoint
    the names do nothing.
    """
    b, t, h, d_qk = q.shape
    t_kv, d_v = k.shape[1], v.shape[3]
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"flash_attention: {h} query heads over {k.shape[2]} key and "
            f"{v.shape[2]} value heads: the key-value heads must be as "
            f"many and divide the query heads")
    if window is not None and (not causal or t_kv != t or window < 1):
        raise ValueError(
            f"flash_attention: a window ({window}) is at least 1 and "
            f"needs causal=True and keys as long as the queries "
            f"({t_kv} for {t})")
    if stairs is not None and (causal or window is not None
                               or min(stairs) < 1):
        raise ValueError(
            f"flash_attention: stairs {stairs} are two steps of at least 1 "
            f"and a key set of their own, without causal or a window")
    if scale is None:
        scale = 1.0 / math.sqrt(d_qk)
    if interpret is None:
        interpret = _default_interpret()
    # Blocks as large as the sequence allows, up to 512: a loop
    # iteration is a chain (scores -> max -> exp -> sum -> product) whose
    # latency the next iteration cannot hide, so a kernel pays per
    # iteration, not per element, until its tiles are large.  Swept on a
    # v5e through HVD_FLASH_BLOCK_Q/K (PERF.md, PR 25): forward + backward
    # at [128,1024,64] bfloat16 causal take 5.97 ms at 128 x 128, 3.39 at
    # 256 x 256, 2.74 at 512 x 512 and 2.78 at 1024 x 1024 (whose
    # diagonal blocks are half masked work); 512 also compiles wherever
    # 128 did (K and V whole in VMEM set that limit, not the tiles).
    if block_q is None:
        block_q = _env_block("HVD_FLASH_BLOCK_Q", _BLOCK)
    if block_k is None:
        block_k = _env_block("HVD_FLASH_BLOCK_K", _BLOCK)
    # under stairs a block divides its step too, so that none straddles two
    block_q = _pick_block(t if stairs is None else math.gcd(t, stairs[0]),
                          block_q)
    block_k = _pick_block(
        t_kv if stairs is None else math.gcd(t_kv, stairs[1]), block_k)

    def to3(x):
        tt, heads = x.shape[1:3]
        return x.transpose(0, 2, 1, 3).reshape(b * heads, tt, x.shape[3])

    qkv3 = to3(q), to3(k), to3(v)
    if SAVED_INPUT_NAMES[0] in saved_bytes(q, k, v):
        qkv3 = map(checkpoint_name, qkv3, SAVED_INPUT_NAMES)
    if return_lse:
        out3, lse3 = _flash_lse(*qkv3, scale, causal, block_q, block_k,
                                interpret, window, stairs)
        out = out3.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)
        return out, lse3.reshape(b, h, t)

    out3 = _flash(*qkv3, scale, causal, block_q, block_k, interpret, window,
                  stairs)
    return out3.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)
