"""The selective scan of a state-space mixer (Mamba, arXiv:2312.00752):
a state carried along the sequence, in a Pallas kernel that keeps it in
VMEM (``ops/pallas/selective_scan.py``).

With ``c [B, T, d]`` the mixer's channels, ``delta [B, T, d]`` their
step sizes, ``A [d, N]`` (negative), ``B``, ``C [B, T, N]`` and ``D
[d]``, for every sequence and channel a state ``h [N]`` runs along T:

    h[t] = exp(delta[t] A) * h[t-1] + (delta[t] * c[t]) B[t]     h[-1] = 0
    y[t] = h[t] . C[t] + D * c[t]

The state, ``delta``, ``A`` and every sum are float32 whatever the
activations are; ``y`` comes back in ``c``'s dtype.

``[B, T, d, N]`` is never made, in HBM or anywhere.  T is cut into blocks
of ``chunk`` positions and the channels into blocks of 1,024: the forward
kernel walks the T blocks in order with the states ``[N, d]`` in VMEM,
writes ``y`` and, once a T block, the state the block was ENTERED with
(``[T / chunk, B, N, d]``: 1/chunk of the states).  The backward pass is
written out (a ``custom_vjp``) and is one kernel too: it is handed those
entry states, walks the T blocks last to first, makes a block's states
again from its entry state in VMEM, runs the adjoint recurrence ``g[t] =
C[t] (x) dy[t] + exp(delta[t+1] A) * g[t+1]`` back through the block and
reads all six gradients off the two.  Every operand goes in as it arrives
(``c``, ``dy``, ``B``, ``C`` in the activation dtype, ``delta`` in
float32, ``[B, T, d]`` where it lies) and is converted in VMEM; around the
calls there are only reshapes of ``B``, ``C`` and ``A``.  A T no block
divides is padded with positions of ``delta`` 0, which pass the state on
as it is; a ``d`` no channel block divides with channels of zeros.

Under ``jax.checkpoint`` the two results the backward pass is handed
carry the names ``SAVED_NAMES``: a policy that keeps them
(``save_only_these_names``) has no use for the forward kernel in its
recomputation, which is then dead code; outside a checkpoint a name is
the identity.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops.pallas import selective_scan as kernels
from horovod_tpu.ops.pallas.flash_attention import _default_interpret

# traced once and lowered once for all the layers of a step, not once a
# call (as the flash kernels)
_forward_once = jax.jit(kernels.forward,
                        static_argnames=("block_t", "interpret"))
_backward_once = jax.jit(kernels.backward,
                         static_argnames=("block_t", "interpret"))
SAVED_Y = "selective_scan_y"
SAVED_STATES = "selective_scan_states"
SAVED_NAMES = (SAVED_Y, SAVED_STATES)
# what the backward kernel may keep of a T block's states in VMEM (block_t
# x N x 4 KiB): with its blocks within the scope a call gets that asks for
# none (64 positions at N = 16; 128 are not)
KEPT_BYTES = 4 << 20


def _block_t(seq, state, dtype, chunk=None):
    """The positions between two kept states: ``chunk`` (what
    ``KEPT_BYTES`` hold where it is None) cut to ``seq``, up to whole
    tiles of ``[T, d]`` at ``dtype`` (8 positions in float32, 16 in
    bfloat16)."""
    tile = kernels.tile(dtype)
    chunk = chunk or KEPT_BYTES // (state * kernels.FOLD * 4)
    return -(-min(chunk, seq) // tile) * tile


def saved_bytes(batch, seq, d_inner, state, dtype, chunk=None):
    """``{name: bytes}`` of what one call keeps under ``SAVED_NAMES``:
    ``y`` in the activation dtype and a float32 state a T block."""
    blocks = -(-seq // _block_t(seq, state, dtype, chunk))
    return {SAVED_Y: batch * seq * d_inner * jnp.dtype(dtype).itemsize,
            SAVED_STATES: blocks * batch * state * d_inner * 4}


def _padded(x):
    """``x [..., d]`` with its channels padded with zeros to whole blocks
    of ``kernels.FOLD``."""
    pad = -x.shape[-1] % kernels.FOLD
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _folded(x):
    """``x [..., d]`` (float32, padded) as ``[..., d / 128, 128]``."""
    x = _padded(x.astype(jnp.float32))
    return x.reshape(x.shape[:-1] + (-1, kernels.LANES))


def _unfolded(x, d):
    """:func:`_folded` undone: ``d`` channels."""
    return x.reshape(x.shape[:-2] + (-1,))[..., :d]


def _rows(x, block_t):
    """``B`` or ``C [B, T, N]`` as lane rows ``[B, T / block_t, K / 128,
    128]``, a T block's ``K = block_t N`` numbers each (up to whole rows
    with zeros)."""
    batch, t, n = x.shape
    x = x.reshape(batch, t // block_t, block_t * n)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, -x.shape[-1] % kernels.LANES)))
    return x.reshape(batch, t // block_t, -1, kernels.LANES)


def _operands(c, delta, a, b, c2, d, block_t):
    # exp(delta A) is 2 ** (delta A log2(e)): the kernels' exponential
    return (_padded(c), _padded(delta),
            _folded(a.T.astype(jnp.float32) / kernels.LN2), _rows(b, block_t),
            _rows(c2, block_t), _folded(d))


def _forward(c, delta, a, b, c2, d, block_t):
    """``(y [B, T, d] in c's dtype, entry states [T / block_t, B, N, d])``
    of a T that ``block_t`` divides."""
    y, states = _forward_once(
        *_operands(c, delta, a, b, c2, d, block_t), block_t=block_t,
        interpret=_default_interpret())
    channels = c.shape[-1]
    return y[..., :channels], _unfolded(states, channels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(c, delta, a, b, c2, d, block_t):
    return _forward(c, delta, a, b, c2, d, block_t)[0]


def _scan_fwd(c, delta, a, b, c2, d, block_t):
    y, states = _forward(c, delta, a, b, c2, d, block_t)
    y = checkpoint_name(y, SAVED_Y)
    return y, (c, delta, a, b, c2, d, checkpoint_name(states, SAVED_STATES))


def _scan_bwd(block_t, res, dy):
    c, delta, a, b, c2, d, states = res
    channels, (batch, t, n) = c.shape[-1], b.shape
    dc, ddelta, db, dc2, da, dd = _backward_once(
        *_operands(c, delta, a, b, c2, d, block_t), _padded(dy),
        _folded(states), block_t=block_t, interpret=_default_interpret())
    # a T block's lane rows: its positions' N up to a multiple of 8
    per_block = block_t * -(-n // kernels.SUBLANES) * kernels.SUBLANES
    db, dc2 = (x.reshape(batch, t // block_t, -1)[..., :per_block]
               .reshape(batch, t, -1)[..., :n] for x in (db, dc2))
    return (dc[..., :channels], ddelta[..., :channels].astype(delta.dtype),
            _unfolded(da, channels).T.astype(a.dtype), db.astype(b.dtype),
            dc2.astype(c2.dtype), _unfolded(dd, channels).astype(d.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(c, delta, A, B, C, D, *, chunk=None):
    """``y [B, T, d]`` (in ``c``'s dtype) of the recurrence above; ``c``,
    ``delta [B, T, d]``, ``A [d, N]``, ``B``, ``C [B, T, N]``, ``D [d]``.
    Differentiable in all six.  ``chunk``: the positions between two kept
    states (cut to T where T is shorter; from the shape where None)."""
    t = c.shape[1]
    # whole tiles of both: the narrower dtype's hold more positions
    block_t = _block_t(t, A.shape[1], min(
        c.dtype, delta.dtype, key=lambda dtype: dtype.itemsize), chunk)
    pad = -t % block_t
    if pad:
        # delta 0: exp(0) = 1 and delta * c = 0, the state passes
        c, delta, B, C = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                          for x in (c, delta, B, C))
    y = _scan(c, delta, A, B, C, D, block_t)
    return y[:, :t] if pad else y
