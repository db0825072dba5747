"""Pallas TPU kernels of the selective scan (``ops/selective_scan.py``
has the recurrence and the public call): the state runs along T inside
the kernel and never leaves VMEM.

Layout.  A block of ``FOLD`` = 1024 channels is ONE ``(8, 128)`` float32
vreg (channel ``128 s + l`` at sublane ``s``, lane ``l``) and the state of
the block is N such vregs, so a position is N ``exp2``s and a few
multiply-adds a state vreg with no reduce over sublanes and no relayout.
``c``, ``delta``, ``dy`` and the results of their shape are read and
written where they lie, ``[B, T, d]`` in blocks ``(1, block_t, FOLD)``
whose tiles hold 8 (float32) or 16 (bfloat16) POSITIONS of 128 channels:
a block is turned into position vregs in VMEM once, ``[block_t, 8, 128]``
float32 (strided stores, ``_fold``), and a result's block back
(``_unfold``).  ``B[t, n]`` and ``C[t, n]`` are one number for all
channels: a T block's ``block_t x N`` of each arrive as lane rows and are
spread once a T block into ``[block_t N, 128]``, a number on every lane of
its row (``_spread``), which a position reads with a load that broadcasts
over the sublanes: no scalar load, no splat.  ``A`` comes as ``[N, d /
128, 128]`` times ``log2(e)``, so the decay is ``exp2(delta[t] A')``.

Grid ``(B, T / block_t, d / FOLD)``: the T blocks in order, the channel
blocks innermost, so what does not depend on the channels (the spread
rows) is made once a T block.  Forward: the states of all channel blocks
are a VMEM scratch ``[d / FOLD, N, 8, 128]`` carried from one T block to
the next and zeroed at a sequence's first; the state each T block is
ENTERED with leaves once a block (``[T / block_t, B, N, d]``: what the
backward pass starts a block from).  Backward, ONE kernel: the T blocks
are walked last to first through the index maps.  Inside a block a first
loop runs the recurrence again from the entry state and keeps, for every
position, ``exp2(delta[t] A') h[t-1]`` (``[block_t, N, 8, 128]`` in
VMEM: all the adjoint needs of the states) and the products ``h[t] dy[t]``
of ``dC``; a second loop runs the adjoint ``g[t] = C[t] (x) dy[t] +
exp2(delta[t+1] A') g[t+1]`` back through the block (carried across
blocks in a scratch like the state) and writes ``dc`` (with ``D dy``) and
``ddelta``.  ``dA`` and ``dD`` are sums over B and T: output blocks that
stay resident for the whole call.  ``dB[t, n]`` and ``dC[t, n]`` are sums
over ALL channels: a position's N vregs of products are folded over their
sublanes by a tree of sublane rolls and selects into N / 8 vregs (row
``n``: 128 lane sums still to add, ``_Fold``), added up over the channel
blocks in a scratch ``[block_t N, 128]``, and with the last channel block
turned and added down the sublanes into the lane rows they leave as
(``_lane_sums``).

``exp2(delta[t] A')``, the state, the adjoint and every sum are float32
whatever the activations are; ``c``, ``dy``, ``B``, ``C`` are read and
``y``, ``dc`` written in the activation dtype, converted in VMEM.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.pallas.flash_attention import _sds, _vmem_spec

LANES = 128
SUBLANES = 8
FOLD = SUBLANES * LANES     # channels a block holds: one float32 vreg
LN2 = 0.6931471805599453
# positions a loop iteration runs: swept on the v5e at [2, 8192, 5120] x
# 16 (docs/kernels.md)
UNROLL = 2


def tile(dtype):
    """The positions a tile of ``[T, d]`` holds at ``dtype`` (8 in
    float32, 16 in bfloat16): what a T block is a multiple of."""
    return SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _loop(steps, body, carry):
    """``fori_loop`` over ``steps`` positions (whole tiles' worth, so
    ``UNROLL`` divides them), ``UNROLL`` an iteration: the kernels'
    compiler unrolls a loop whole or not at all, so the iteration is a
    loop of its own that it unrolls whole (and that is traced once)."""
    def group(i, carry):
        return jax.lax.fori_loop(
            0, UNROLL, lambda j, carry: body(i * UNROLL + j, carry), carry,
            unroll=True)

    return jax.lax.fori_loop(0, steps // UNROLL, group, carry)


def _fold(ref, out_ref, block_t):
    """``out_ref [block_t, 8, 128]`` float32, a position one vreg, of a
    block ``ref [1, block_t, FOLD]`` as it lies in HBM: a relayout in
    VMEM, a tile's positions at a time."""
    positions = tile(ref.dtype)

    def tiles(i, _):
        at = pl.multiple_of(i * positions, positions)
        rows = ref[0, pl.ds(at, positions), :].astype(jnp.float32)
        out_ref[pl.ds(at, positions)] = rows.reshape(
            positions, SUBLANES, LANES)
        return 0

    jax.lax.fori_loop(0, block_t // positions, tiles, 0)


def _unfold(ref, out_ref, block_t):
    """:func:`_fold` undone: ``ref [block_t, 8, 128]`` float32 into the
    block ``out_ref [1, block_t, FOLD]`` in its dtype."""
    positions = tile(out_ref.dtype)

    def tiles(i, _):
        at = pl.multiple_of(i * positions, positions)
        rows = ref[pl.ds(at, positions)].reshape(positions, FOLD)
        out_ref[0, pl.ds(at, positions), :] = rows.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block_t // positions, tiles, 0)


def _spread(row_ref, out_ref):
    """``out_ref [K, 128]``: row ``i`` the ``i``-th of the ``K`` numbers
    of ``row_ref [1, 1, K / 128, 128]`` on every lane, in float32.  128
    numbers at a time: their lane row down all the sublanes, turned."""
    for j in range(out_ref.shape[0] // LANES):
        row = row_ref[0, 0, pl.ds(j, 1), :].astype(jnp.float32)
        out_ref[pl.ds(j * LANES, LANES)] = jnp.broadcast_to(
            row, (LANES, LANES)).T


def _lane_sums(acc_ref, out_ref):
    """``out_ref [1, 1, rows / 128, 128]``: the sums over the lanes of
    ``acc_ref [rows, 128]`` as lane rows, 128 rows at a time: turned, and
    added down the sublanes."""
    for j in range(acc_ref.shape[0] // LANES):
        out_ref[0, 0, pl.ds(j, 1), :] = jnp.sum(
            acc_ref[pl.ds(j * LANES, LANES)].T, axis=0, keepdims=True)


def _at(row_ref, i):
    """Row ``i`` of a spread ``[K, 128]`` down the sublanes of a vreg."""
    return row_ref[pl.ds(i, 1), :]


def _state(ref, n):
    return tuple(ref[k] for k in range(n))


def _fwd_kernel(b_ref, c2_ref, c_hbm_ref, delta_hbm_ref, a_ref, d_ref,
                y_hbm_ref, states_ref, h_ref, c_ref, delta_ref, y_ref, b_row,
                c2_row, *, block_t, n):
    j = pl.program_id(2)
    h_ref = h_ref.at[j]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(j == 0)
    def _():
        _spread(b_ref, b_row)
        _spread(c2_ref, c2_row)

    states_ref[0, 0] = h_ref[...]
    _fold(c_hbm_ref, c_ref, block_t)
    _fold(delta_hbm_ref, delta_ref, block_t)

    def position(t, h):
        delta, c = delta_ref[t], c_ref[t]
        u, y, new = delta * c, d_ref[...] * c, []
        for k in range(n):
            new.append(jnp.exp2(delta * a_ref[k]) * h[k]
                       + _at(b_row, t * n + k) * u)
            y = y + _at(c2_row, t * n + k) * new[k]
        y_ref[t] = y
        return tuple(new)

    h = _loop(block_t, position, _state(h_ref, n))
    for k in range(n):
        h_ref[k] = h[k]
    _unfold(y_ref, y_hbm_ref, block_t)


def _rows(width):
    """What a T block's ``width`` numbers take as lane rows."""
    return -(-width // LANES)


def _specs(block_t, n, t_of):
    """The block specs both kernels share, on the grid ``(b, i, j)``: a T
    block's lane rows of ``B`` or ``C``, a block of ``[B, T, d]``, a
    channel block of ``A'`` and of ``D``, and the entry states."""
    return dict(
        row=_vmem_spec((1, 1, _rows(block_t * n), LANES),
                       lambda b, i, j: (b, t_of(i), 0, 0)),
        act=_vmem_spec((1, block_t, FOLD), lambda b, i, j: (b, t_of(i), j)),
        a=_vmem_spec((n, SUBLANES, LANES), lambda b, i, j: (0, j, 0)),
        d=_vmem_spec((SUBLANES, LANES), lambda b, i, j: (j, 0)),
        states=_vmem_spec((1, 1, n, SUBLANES, LANES),
                          lambda b, i, j: (t_of(i), b, 0, j, 0)))


def _scratch(*shape):
    return pltpu.VMEM(shape + (SUBLANES, LANES), jnp.float32)


def _row_scratch(width):
    """``[K, 128]`` float32 for ``width`` numbers, a row each, up to whole
    lane rows of them."""
    return pltpu.VMEM((_rows(width) * LANES, LANES), jnp.float32)


def forward(c, delta, a, b, c2, d, *, block_t, interpret):
    """``(y [B, T, d] in c's dtype, entry states [T / block_t, B, N, d /
    128, 128] float32)`` of ``c``, ``delta [B, T, d]`` (d a multiple of
    ``FOLD``, T of ``block_t``, ``block_t`` of the tiles of both),
    ``a [N, d / 128, 128]`` (``A.T log2(e)``), ``d [d / 128, 128]``
    float32 and ``b``, ``c2 [B, T / block_t, K / 128, 128]`` (a T block's
    ``block_t N`` numbers as lane rows)."""
    batch, t, channels = c.shape
    n, rows = a.shape[:2]
    nt, nd = t // block_t, channels // FOLD
    specs = _specs(block_t, n, lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_t=block_t, n=n),
        grid=(batch, nt, nd),
        in_specs=[specs[name] for name in ("row", "row", "act", "act", "a",
                                           "d")],
        out_specs=[specs["act"], specs["states"]],
        out_shape=[_sds(c.shape, c.dtype, c),
                   _sds((nt, batch, n, rows, LANES), jnp.float32, c)],
        scratch_shapes=[_scratch(nd, n)] + [_scratch(block_t)] * 3
        + [_row_scratch(block_t * n)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(b, c2, c, delta, a, d)


def _bit_reversed(k):
    return (k & 1) << 2 | k & 2 | k >> 2


class _Fold:
    """Folds vregs over their sublanes as they come: after ``8 m`` calls
    of ``add``, ``out`` holds ``m`` vregs whose sublane ``s`` of vreg ``i``
    holds, lane by lane, the sum over the sublanes of the ``8 i +
    bit_reversed(s)``-th vreg added.  A tree of three levels, each halving
    the sublanes a vreg's sum still lies in and putting two vregs into
    one; a pair is folded as soon as it is whole, so few are alive."""

    def __init__(self):
        self.sublane = jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, LANES), 0)
        self.waiting = {}       # level -> the vreg that waits for its pair
        self.out = []

    def add(self, x, k=SUBLANES // 2):
        if k == 0:
            self.out.append(x)
            return
        if k not in self.waiting:
            self.waiting[k] = x
            return
        first = self.waiting.pop(k)
        low = (self.sublane & k) == 0
        if 2 * k == SUBLANES:       # a roll by half is its own inverse
            both = (jnp.where(low, first, x)
                    + pltpu.roll(jnp.where(low, x, first), k, 0))
        else:
            both = jnp.where(low, first + pltpu.roll(first, SUBLANES - k, 0),
                             x + pltpu.roll(x, k, 0))
        self.add(both, k // 2)


def _bwd_kernel(b_ref, c2_ref, c_hbm_ref, delta_hbm_ref, a_ref, d_ref,
                dy_hbm_ref, states_ref, dc_hbm_ref, ddelta_hbm_ref, db_ref,
                dc2_ref, da_ref, dd_ref, flow_ref, kept_ref, acc_b_ref,
                acc_c_ref, c_ref, delta_ref, dy_ref, dc_ref, ddelta_ref,
                b_row, c2_row, *, block_t, n, folded):
    b, i, j = (pl.program_id(axis) for axis in range(3))
    channels = pl.ds(pl.multiple_of(j * SUBLANES, SUBLANES), SUBLANES)
    flow_ref, da_ref, dd_ref = (flow_ref.at[j], da_ref.at[:, channels],
                                dd_ref.at[channels])

    @pl.when(i == 0)
    def _():
        flow_ref[...] = jnp.zeros_like(flow_ref)

    @pl.when((b == 0) & (i == 0))
    def _():
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(j == 0)
    def _():
        _spread(b_ref, b_row)
        _spread(c2_ref, c2_row)
        acc_b_ref[...] = jnp.zeros_like(acc_b_ref)
        acc_c_ref[...] = jnp.zeros_like(acc_c_ref)

    for hbm_ref, ref in ((c_hbm_ref, c_ref), (delta_hbm_ref, delta_ref),
                         (dy_hbm_ref, dy_ref)):
        _fold(hbm_ref, ref, block_t)
    zero = jnp.zeros((SUBLANES, LANES), jnp.float32)
    # the order the tree wants its vregs in: row n of the folded is n
    order = [m - m % SUBLANES + _bit_reversed(m % SUBLANES)
             for m in range(folded * SUBLANES)]

    def add(acc_ref, t, fold):
        for m, rows in enumerate(fold.out):
            acc_ref[pl.ds((t * folded + m) * SUBLANES, SUBLANES)] += rows

    def again(t, h):
        delta, dy = delta_ref[t], dy_ref[t]
        u = delta * c_ref[t]
        fold, new = _Fold(), [None] * n
        for k in order:
            if k >= n:
                fold.add(zero)
                continue
            # what the position keeps of the state before it: all the
            # adjoint needs of that state
            kept = jnp.exp2(delta * a_ref[k]) * h[k]
            kept_ref[t, k] = kept
            new[k] = kept + _at(b_row, t * n + k) * u
            fold.add(new[k] * dy)
        add(acc_c_ref, t, fold)
        return tuple(new)

    _loop(block_t, again, _state(states_ref.at[0, 0], n))

    def adjoint(step, flow):
        t = block_t - 1 - step
        delta, c, dy = delta_ref[t], c_ref[t], dy_ref[t]
        u = delta * c
        du, ddelta, fold, new = zero, zero, _Fold(), [None] * n
        for k in order:
            if k >= n:
                fold.add(zero)
                continue
            g = _at(c2_row, t * n + k) * dy + flow[k]
            # d loss / d exp2(delta[t] A'), times that factor
            w = g * kept_ref[t, k]
            du = du + _at(b_row, t * n + k) * g
            ddelta = ddelta + w * a_ref[k]
            da_ref[k] += w * delta
            fold.add(g * u)
            new[k] = jnp.exp2(delta * a_ref[k]) * g
        dd_ref[...] += dy * c
        dc_ref[t] = du * delta + d_ref[...] * dy
        ddelta_ref[t] = LN2 * ddelta + du * c
        add(acc_b_ref, t, fold)
        return tuple(new)

    flow = _loop(block_t, adjoint, _state(flow_ref, n))
    for k in range(n):
        flow_ref[k] = flow[k]
    _unfold(dc_ref, dc_hbm_ref, block_t)
    _unfold(ddelta_ref, ddelta_hbm_ref, block_t)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        _lane_sums(acc_b_ref, db_ref)
        _lane_sums(acc_c_ref, dc2_ref)


def backward(c, delta, a, b, c2, d, dy, states, *, block_t, interpret):
    """The gradients of :func:`forward`'s operands: ``(dc [B, T, d] in c's
    dtype, ddelta float32, db, dc2 [B, T / block_t, K' / 128, 128] float32
    (a T block's ``block_t N'`` numbers as lane rows, N' = N up to a
    multiple of 8), da [N, d / 128, 128] (with respect to ``A.T`` itself,
    not to the ``a`` it is handed), dd [d / 128, 128])``."""
    batch, t, channels = c.shape
    n = a.shape[0]
    nt, nd = t // block_t, channels // FOLD
    folded = -(-n // SUBLANES)
    wide = _rows(block_t * folded * SUBLANES)
    specs = _specs(block_t, n, lambda i: nt - 1 - i)
    partial = _vmem_spec((1, 1, wide, LANES),
                         lambda b, i, j: (b, nt - 1 - i, 0, 0))
    partials = _sds((batch, nt, wide, LANES), jnp.float32, c)
    whole_a = _vmem_spec(a.shape, lambda b, i, j: (0, 0, 0))
    whole_d = _vmem_spec(d.shape, lambda b, i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_t=block_t, n=n, folded=folded),
        grid=(batch, nt, nd),
        in_specs=[specs[name] for name in ("row", "row", "act", "act", "a",
                                           "d", "act", "states")],
        out_specs=[specs["act"], specs["act"], partial, partial, whole_a,
                   whole_d],
        out_shape=[_sds(c.shape, c.dtype, c),
                   _sds(delta.shape, jnp.float32, c), partials, partials,
                   _sds(a.shape, jnp.float32, c),
                   _sds(d.shape, jnp.float32, c)],
        scratch_shapes=[_scratch(nd, n), _scratch(block_t, n)]
        + [_row_scratch(block_t * folded * SUBLANES)] * 2
        + [_scratch(block_t)] * 5 + [_row_scratch(block_t * n)] * 2,
        compiler_params=pltpu.CompilerParams(
            # dA and dD add up over everything
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(b, c2, c, delta, a, d, dy, states)
