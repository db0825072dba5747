"""Adasum: scale-invariant gradient combination.

The reference implements Adasum as a recursive vector-halving
distance-doubling (VHDD) allreduce in C++ (``horovod/common/ops/adasum/
adasum.h:194-330``): at each level, ranks exchange half-buffers with
``rank ^ level``, compute dot products and squared norms (allreduced over
per-level reduction communicators) and combine

    a' = (1 - a.b / (2 |a|^2)) * a  +  (1 - a.b / (2 |b|^2)) * b

The TPU-native formulation keeps the same pairing tree (rank r pairs with
r ^ 2^level) but expresses it as XLA ops inside the compiled step:
``all_gather`` the per-rank contributions over the mesh axis, then reduce the
leading axis pairwise.  XLA schedules the gather on ICI; the combine is pure
VPU work.  :func:`adasum_vhdd` is the large-tensor path: a true
ppermute-based VHDD (exchange halves, psum the dot/norm scalars over
per-level ``axis_index_groups``), and :func:`adasum_reduce_hierarchical`
composes it with an intra-group reduce-scatter/allgather, mirroring the
reference's NCCL+MPI hierarchical Adasum.

``adasum_reference`` is the numpy oracle used by the tests, mirroring the
reference's pure-Python reference implementation in
``test_adasum_pytorch.py``.
"""

import numpy as np
import jax
import jax.numpy as jnp

from horovod_tpu.common.compression import Compression


def _pair_coefficients(dot, norm_a, norm_b):
    """Safe Adasum pair coefficients; zero-norm operand contributes plain
    addition (reference: adasum.h DispatchComputeDotAndNormSqrds guards)."""
    a_coeff = jnp.where(norm_a > 0, 1.0 - dot / (2.0 * norm_a), 1.0)
    b_coeff = jnp.where(norm_b > 0, 1.0 - dot / (2.0 * norm_b), 1.0)
    return a_coeff, b_coeff


def adasum_pair(a, b):
    """Combine two same-shaped tensors with the Adasum formula."""
    af = a.astype(jnp.float32).reshape(-1)
    bf = b.astype(jnp.float32).reshape(-1)
    dot = jnp.dot(af, bf)
    norm_a = jnp.dot(af, af)
    norm_b = jnp.dot(bf, bf)
    a_coeff, b_coeff = _pair_coefficients(dot, norm_a, norm_b)
    return (a_coeff * af + b_coeff * bf).reshape(a.shape).astype(a.dtype)


def adasum_reduce_stacked(stacked):
    """Reduce a [N, ...] stacked tensor along axis 0 with VHDD pairing
    (rank r pairs with r ^ 2^level).  N must be a power of two."""
    n = stacked.shape[0]
    if n & (n - 1):
        raise ValueError(f"Adasum requires a power-of-two rank count, got {n}")
    level = stacked
    while level.shape[0] > 1:
        half = level.shape[0] // 2
        pairs = level.reshape((half, 2) + level.shape[1:])
        combined = jax.vmap(adasum_pair)(pairs[:, 0], pairs[:, 1])
        level = combined
    return level[0]


def adasum_reduce_pytree(grads, named_axes=("hvd",), compression=None):
    """SPMD Adasum: inside shard_map, gather contributions over the mesh
    axes and tree-combine them.  Every rank computes the identical result."""
    compression = compression or Compression.none
    axis = named_axes if isinstance(named_axes, str) else tuple(named_axes)

    def reduce_leaf(g):
        compressed, ctx = compression.compress(g)
        gathered = jax.lax.all_gather(compressed, axis)
        reduced = adasum_reduce_stacked(gathered)
        return compression.decompress(reduced, ctx)

    return jax.tree.map(reduce_leaf, grads)


def adasum_vhdd(x, axis_name, scalar_axes=()):
    """True vector-halving distance-doubling Adasum inside ``shard_map``
    (reference: ``Adasum<Communicator_type>::FusedAllreduce``,
    ``adasum/adasum.h:194-330``), expressed TPU-natively:

    at level ``k`` (distance ``2^k``) each rank exchanges half of its
    current piece with rank ``r ^ 2^k`` via ``ppermute``, and the
    dot/norm scalars of the two logical vectors being combined — which are
    at that point distributed over ``2^(k+1)`` ranks — are reduced with
    ``psum`` over ``axis_index_groups`` (the reference's per-level
    ``reduction_comms``).  After ``log2(n)`` levels every rank holds
    ``1/n`` of the combined vector; a tiled ``all_gather`` restores it.

    Communication volume per rank is ``~2|x|`` (halving) versus
    ``(n-1)|x|`` for the gather-based tree — this is the large-tensor path.
    ``n`` must be a power of two.  ``x`` is the rank's flat vector.

    ``scalar_axes``: extra mesh axes over which the logical vectors are
    chunk-distributed (hierarchical mode: the local axis after a
    reduce-scatter).  The dot/norm scalars are additionally psum'd over
    them so the coefficients see the FULL vectors — the reference's
    reduction communicators likewise span the intra-node ranks holding the
    other chunks (adasum_gpu_operations.cc start_level=local_size).
    """
    n = jax.lax.axis_size(axis_name)
    if n & (n - 1):
        raise ValueError(f"Adasum VHDD requires power-of-two ranks, got {n}")
    if n == 1:
        return x

    size = x.size
    padded = -(-size // n) * n
    piece = jnp.pad(x.astype(jnp.float32).reshape(-1),
                    (0, padded - size))
    idx = jax.lax.axis_index(axis_name)

    dist = 1
    while dist < n:
        half = piece.size // 2
        low, high = piece[:half], piece[half:]
        bit = (idx // dist) % 2  # which half this rank keeps
        send = jnp.where(bit == 0, high, low)
        mine = jnp.where(bit == 0, low, high)
        perm = [(r, r ^ dist) for r in range(n)]
        recv = jax.lax.ppermute(send, axis_name, perm)

        # a = piece of the lower group's vector, b = the upper's; roles are
        # fixed by the rank's bit so every group member reduces the same
        # (a, b) scalars (reference: DispatchComputeDotAndNormSqrds +
        # allreduce over reduction_comms[level]).
        a = jnp.where(bit == 0, mine, recv)
        b = jnp.where(bit == 0, recv, mine)
        groups = [[g * 2 * dist + i for i in range(2 * dist)]
                  for g in range(n // (2 * dist))]
        partial = jnp.stack([jnp.dot(a, b), jnp.dot(a, a), jnp.dot(b, b)])
        for extra in scalar_axes:
            partial = jax.lax.psum(partial, extra)
        dot, na, nb = jax.lax.psum(partial, axis_name,
                                   axis_index_groups=groups)
        ca, cb = _pair_coefficients(dot, na, nb)
        piece = ca * a + cb * b
        dist *= 2

    # After halving, rank r holds the chunk at bit-reversed index: level k's
    # keep-high decision (bit k of r) selects the 2^(levels-1-k)-sized
    # stride.  The reference undoes this with its backward
    # distance-halving allgather (adasum.h:308-); one gather plus a static
    # row permutation is the XLA equivalent.
    levels = n.bit_length() - 1
    gathered = jax.lax.all_gather(piece, axis_name)  # [n, chunk]
    order = [int(format(i, f"0{levels}b")[::-1], 2) for i in range(n)]
    full = gathered[jnp.asarray(order)].reshape(-1)
    return full[:size].reshape(x.shape).astype(x.dtype)


def adasum_reduce_hierarchical(x, local_axis="local", cross_axis="cross"):
    """Hierarchical Adasum inside ``shard_map`` over a (cross, local) mesh
    (reference: ``AdasumGpuAllreduceOp``, ``adasum_gpu_operations.cc``):
    reduce-scatter (sum) within the fast local group, Adasum VHDD across
    the cross axis, allgather back, with the reference's ``local_size``
    divisor folded in (``torch/mpi_ops.py:110``)."""
    local_size = jax.lax.axis_size(local_axis)
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % local_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunk = jax.lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                                 tiled=True)
    combined = adasum_vhdd(chunk, cross_axis, scalar_axes=(local_axis,))
    full = jax.lax.all_gather(combined, local_axis, tiled=True)
    if pad:
        full = full[:-pad]
    return (full / local_size).reshape(x.shape).astype(x.dtype)


def adasum_reference(tensors):
    """Numpy oracle for tests: VHDD pairing over a list of per-rank numpy
    arrays."""
    level = [np.asarray(t, dtype=np.float64) for t in tensors]
    if len(level) & (len(level) - 1):
        raise ValueError("power-of-two rank count required")
    while len(level) > 1:
        combined = []
        for i in range(0, len(level), 2):
            a, b = level[i].reshape(-1), level[i + 1].reshape(-1)
            dot = float(a @ b)
            norm_a = float(a @ a)
            norm_b = float(b @ b)
            a_coeff = 1.0 - dot / (2.0 * norm_a) if norm_a > 0 else 1.0
            b_coeff = 1.0 - dot / (2.0 * norm_b) if norm_b > 0 else 1.0
            combined.append(
                (a_coeff * a + b_coeff * b).reshape(level[i].shape))
        level = combined
    return level[0]
