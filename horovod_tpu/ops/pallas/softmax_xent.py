"""Pallas TPU fused softmax cross-entropy (forward + custom-VJP backward).

The LM-loss hot op.  The stock lowering materializes ``log_softmax``
over the full ``[rows, vocab]`` logits twice (forward + backward); at
vocab 32k that array dominates HBM traffic of the loss.  The fused
kernels walk the logits in ``[block_n, cols]`` tiles on a grid of (row
blocks, column tiles), so the pipeline copies the next tile in under
this tile's arithmetic:

- forward: one pass per row block, the column tiles in order.  Running
  max / sum-exp (online logsumexp, same trick as flash attention's
  softmax) and the label logit picked up via an iota==label mask live
  in VMEM scratch across a row block's tiles; the last tile writes the
  loss and saves ``lse`` ([rows, 1] broadcast to the 128-lane tile) for
  the backward;
- backward: ``dlogits = (exp(x - lse) - onehot(label)) * dloss`` -- one
  read of the logits, no recomputed reduction, no carry between tiles;
- labels ride as int32 ``[rows, 1]`` blocks; rows pad to the sublane
  multiple exactly like ``layer_norm.py`` (padded rows get label 0 and
  zero cotangent, then slice off).

How the tile is chosen (``_pick_tile``; the sweep on the v5e is in
PERF.md, PR 28): from rows, V, the logits' item size and
``_VMEM_BUDGET`` alone -- the largest row block that divides the rows,
then the fewest equal column tiles, multiples of 128, that fit the
budget beside it.  The tile does NOT have to divide V: the last tile's
columns past V are masked out of max, sum and pick in the forward (only
in that tile; the others pay for no mask), what the backward computes
there is never written back, and nothing past the logits' last column
is read or written in HBM.  (A tile that had to divide V gave 50304 =
128 x 393 chunks of 128 columns and 40 times the time of 50257.)

API: ``softmax_xent(logits, labels)`` -> per-row loss ``[...,]`` in
fp32; logits may be bf16 (accumulation is fp32).  Interpret mode
off-TPU; `softmax_xent_reference` is the XLA oracle.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu.ops.pallas.flash_attention import (_NEG_INF,
                                                    _default_interpret,
                                                    _flatten_rows, _sds,
                                                    _vmem_spec, pltpu)

_LANES = 128
# VMEM the blocks of one grid step may take (``_live_bytes``), and the
# limit the compiler is given for the kernel: twice that, for what
# Mosaic keeps besides (1.5 MiB at the smallest tile).  The chip's
# default limit of 16 MiB holds half the tile the sweep asks for.
_VMEM_BUDGET = 16 << 20
_VMEM_LIMIT = 32 << 20
_ROW_BLOCKS = (256, 128, 64, 32, 16, 8)


def _live_bytes(block_n, cols, itemsize):
    """VMEM of one backward grid step at tile ``[block_n, cols]``: the
    logits in and ``dlogits`` out, each double-buffered by the pipeline,
    and two float32 temporaries of the tile.  The v5e's compiler wants
    16.0-16.2 bytes an element at bfloat16 and 20.0-22.0 at float32
    (smallest limit it accepts, PR 28); the forward 9.1-13.3."""
    return block_n * cols * (4 * itemsize + 2 * 4)


def _pick_tile(n, v, itemsize):
    """``(block_n, cols)``: the tile both kernels walk ``[n, v]`` logits
    in, from what fits ``_VMEM_BUDGET``.  Rows first (the sweep: at equal
    size a tall tile is as fast as a wide one or faster, and 256 rows of
    2048 columns already reach the backward's best): the largest row
    block that divides ``n``.  Then the fewest EQUAL column tiles that
    fit beside it, each a multiple of 128 lanes: what a last tile holds
    past column ``v`` is computed and thrown away, and a V of 50304
    walked in 8192s (7 tiles, the last 14% full) took the forward 12%
    longer than in thirteen 3968s.  A row that fits whole is one tile
    of exactly ``v`` columns."""
    block_n = next(b for b in _ROW_BLOCKS if n % b == 0)  # n is padded to 8
    widest = (_VMEM_BUDGET // _live_bytes(block_n, 1, itemsize)
              // _LANES * _LANES)
    tiles = pl.cdiv(v, widest)
    if tiles == 1:
        return block_n, v
    return block_n, pl.cdiv(pl.cdiv(v, tiles), _LANES) * _LANES


def _tile_columns(x_ref, lab_ref):
    """Column numbers of this tile, local to it, and the label's: the
    tile's offset is taken off the ``[block_n, 1]`` labels, not added to
    the ``[block_n, cols]`` iota."""
    cols = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)
    offset = pl.program_id(1) * x_ref.shape[1]
    return cols, lab_ref[...] - offset, offset


def _fwd_kernel(x_ref, lab_ref, *refs, v):
    # x_ref: [block_n, cols] of [n, v]; lab_ref: [block_n, 1] int32;
    # refs: loss [, lse] outputs, then the m, s, picked carries
    *out_refs, m_ref, s_ref, picked_ref = refs
    j, last = pl.program_id(1), pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        picked_ref[...] = jnp.zeros_like(picked_ref)

    cols, lab, offset = _tile_columns(x_ref, lab_ref)

    def update(tail):
        x = x_ref[...].astype(jnp.float32)
        if tail:  # what the block holds past column v is not logits
            x = jnp.where(cols < v - offset, x, _NEG_INF)
        picked_ref[...] += jnp.sum(
            jnp.where(cols == lab, x, 0.0), axis=1, keepdims=True)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
        s_ref[...] = s_ref[...] * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(x - m_new), axis=1, keepdims=True)
        m_ref[...] = m_new

    if v % x_ref.shape[1]:
        pl.when(j < last)(lambda: update(False))
        pl.when(j == last)(lambda: update(True))
    else:
        update(False)

    @pl.when(j == last)
    def _finish():
        lse = m_ref[...] + jnp.log(s_ref[...])
        out_refs[0][...] = jnp.broadcast_to(lse - picked_ref[...],
                                            out_refs[0].shape)
        if len(out_refs) > 1:
            out_refs[1][...] = jnp.broadcast_to(lse, out_refs[1].shape)


def _bwd_kernel(x_ref, lab_ref, lse_ref, dy_ref, dx_ref):
    # no carry and no mask: lse is known, and what a tail tile computes
    # past column v is never written back
    x = x_ref[...].astype(jnp.float32)
    cols, lab, _ = _tile_columns(x_ref, lab_ref)
    p = jnp.exp(x - lse_ref[...][:, :1])
    dx = (p - jnp.where(cols == lab, 1.0, 0.0)) * dy_ref[...][:, :1]
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _rows(logits, labels):
    x2, n = _flatten_rows(logits)
    l2, _ = _flatten_rows(labels[..., None].astype(jnp.int32))
    return x2, l2, n


def _grid_and_specs(x2):
    """The grid ``(row blocks, column tiles)`` and the two kinds of
    block: a tile of the logits, and a row block's per-row values."""
    np_, v = x2.shape
    block_n, cols = _pick_tile(np_, v, x2.dtype.itemsize)
    return ((np_ // block_n, pl.cdiv(v, cols)),
            _vmem_spec((block_n, cols), lambda i, j: (i, j)),
            lambda width: _vmem_spec((block_n, width), lambda i, j: (i, 0)))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent(logits, labels, interpret=None):
    """Per-row softmax cross-entropy over the last axis (fp32).

    The primal (non-differentiated) call skips the lse residual
    output; differentiation swaps in the residual-saving forward."""
    if interpret is None:
        interpret = _default_interpret()
    x2, l2, n = _rows(logits, labels)
    loss = _call_fwd(x2, l2, interpret, with_lse=False)[0]
    return loss[:n, 0].reshape(logits.shape[:-1])


def _call_fwd(x2, l2, interpret, with_lse):
    np_, v = x2.shape
    grid, tile, per_row = _grid_and_specs(x2)
    block_n = tile.block_shape[0]
    outs = 2 if with_lse else 1
    return pl.pallas_call(
        functools.partial(_fwd_kernel, v=v),
        grid=grid,
        in_specs=[tile, per_row(1)],
        out_specs=[per_row(_LANES)] * outs,
        out_shape=[_sds((np_, _LANES), jnp.float32, x2)] * outs,
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32)] * 3,
        compiler_params=_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(x2, l2)


def _sx_fwd(logits, labels, interpret):
    if interpret is None:
        interpret = _default_interpret()
    x2, l2, n = _rows(logits, labels)
    out = _call_fwd(x2, l2, interpret, with_lse=True)
    loss, lse = out
    return (loss[:n, 0].reshape(logits.shape[:-1]),
            (x2, l2, lse, logits.shape))


def _sx_bwd(interpret, residuals, dloss):
    if interpret is None:
        interpret = _default_interpret()
    x2, l2, lse, logits_shape = residuals
    np_, v = x2.shape
    n = 1
    for s in logits_shape[:-1]:
        n *= s
    dy = dloss.reshape(n, 1).astype(jnp.float32)
    if np_ != n:
        dy = jnp.concatenate(
            [dy, jnp.zeros((np_ - n, 1), jnp.float32)], axis=0)
    dy = jnp.broadcast_to(dy, (np_, _LANES))

    grid, tile, per_row = _grid_and_specs(x2)
    dx = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[tile, per_row(1), per_row(_LANES), per_row(_LANES)],
        out_specs=[tile],
        out_shape=[_sds((np_, v), x2.dtype, x2)],
        compiler_params=_params(("parallel", "parallel")),
        interpret=interpret,
    )(x2, l2, lse, dy)[0]
    return dx[:n].reshape(logits_shape), None


softmax_xent.defvjp(_sx_fwd, _sx_bwd)


def softmax_xent_reference(logits, labels):
    """XLA oracle (optax-equivalent) for tests and non-Pallas paths."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
