"""ZeRO-1 sharded weight update on both data planes.

Cross-replica sharding of the weight update (arXiv:2004.13336 — the
technique is TPU-native in origin; the reference framework has no
analog): instead of every replica reducing the FULL gradient and holding
the FULL optimizer state,

1. gradients are **reduce-scattered** — each rank receives its 1/N block
   already reduced (half the wire traffic of a full allreduce),
2. the inner optimizer runs on that block only — optimizer state is 1/N
   per rank (Adam on a P-param model stores 2P/N here),
3. the updated parameter block is **allgathered** back.

Two bindings of the same decomposition:

- :func:`ShardedDistributedOptimizer` — in-graph: ``psum_scatter`` /
  ``all_gather`` inside ``shard_map``, compiled into the step program
  (the XLA executor's native plane).
- :func:`ZeroDistributedOptimizer` — eager: the named
  ``hvd.reduce_scatter`` / ``hvd.allgather`` collectives, so the same
  update runs over the TCP ring and the coordinator star, participates
  in negotiation/fusion, and survives elastic reconfiguration
  (:func:`gather_zero_state` / :func:`reshard_zero_state`).

See docs/sharding.md for the decomposition diagram and knob table.
"""

import jax
import optax

from horovod_tpu.common.compression import (Compression,
                                            quantized_reduce_scatter)
from horovod_tpu.common.ops_enum import (Adasum, Average, ReduceOp,
                                         reduce_scatter_split_sizes)


# --------------------------------------------------------------- shard layout
def shard_chunk_size(n_params, axis_size):
    """Per-replica flat-shard length the in-graph sharded optimizer uses
    (ceil-divided so the last shard is zero-padded)."""
    return -(-n_params // axis_size)


def zero_shard_layout(n_params, world_size, rank):
    """``(counts, offset, count)`` for the EAGER ZeRO layout: the
    np.array_split row partition shared with ``hvd.reduce_scatter``
    (``reduce_scatter_split_sizes``) — no padding, the first
    ``n_params % world_size`` ranks take one extra element."""
    counts = reduce_scatter_split_sizes(n_params, world_size)
    offset = sum(counts[:rank])
    return counts, offset, counts[rank]


def _resolve_min_size(min_size):
    """Threshold below which the update stays replicated.  Resolution:
    explicit arg > runtime config (``HVD_TPU_ZERO_MIN_SIZE`` /
    ``--zero-min-size`` / YAML ``sharding.zero_min_size``) > default.
    Deterministic across ranks — every rank sees the same flat size and
    the same config, so all take the same branch."""
    if min_size is not None:
        return int(min_size)
    from horovod_tpu.common import basics
    from horovod_tpu.utils import env as env_util

    state = basics._state
    if state is not None:
        return state.config.zero_min_size
    return env_util.DEFAULT_ZERO_MIN_SIZE


# ----------------------------------------------------- in-graph (XLA) binding
def ShardedDistributedOptimizer(optimizer, axis_name="hvd", op=Average,
                                compression=Compression.none):
    """In-graph ZeRO-1 on the data-parallel axis.

    Both ``init`` and ``update`` must run INSIDE ``shard_map`` over
    ``axis_name`` (init the state in a jitted sharded step — see
    ``tests/test_spmd.py``).  Use
    ``horovod_tpu.parallel._compat.shard_map``: the gathered
    updates ARE replicated, but jax's varying-manual-axes checker cannot
    infer replication through ``all_gather`` (no public un-vary
    annotation exists), so the check must be off for the step.  Average
    divides by the axis size; Adasum is not supported (its combination
    needs full vectors).
    """
    from jax.flatten_util import ravel_pytree

    import jax.numpy as jnp

    op_ = ReduceOp(op)
    if op_ == Adasum:
        raise ValueError(
            "ShardedDistributedOptimizer does not support Adasum; use "
            "DistributedOptimizer(op=Adasum)")
    quantized = getattr(compression, "block_quantized", False)

    def _layout(flat):
        n = jax.lax.psum(1, axis_name)  # concrete inside shard_map
        chunk = shard_chunk_size(flat.size, n)
        if quantized:
            # block-align the shard so the quantized reduce-scatter's
            # per-destination chunks land on scale-block boundaries;
            # init and update share this layout, so the optimizer-state
            # shape is stable either way
            chunk = -(-chunk // compression.block) * compression.block
        return n, chunk

    def _my_shard(flat):
        n, chunk = _layout(flat)
        padded = jnp.pad(flat, (0, n * chunk - flat.size))
        return jax.lax.dynamic_slice(
            padded, (jax.lax.axis_index(axis_name) * chunk,), (chunk,))

    def init_fn(params):
        flat, _ = ravel_pytree(params)
        return optimizer.init(_my_shard(flat))

    def update_fn(grads, state, params=None):
        flat_g, unravel = ravel_pytree(grads)
        n, chunk = _layout(flat_g)

        if quantized and jnp.issubdtype(flat_g.dtype, jnp.floating):
            # quantized reduce-scatter: each rank's contribution to every
            # shard travels as int8 + block scales, the owned shard
            # accumulates in fp32 — half of the quantized allreduce (the
            # allgather of UPDATES below stays full precision)
            padded = jnp.pad(flat_g.astype(jnp.float32),
                             (0, n * chunk - flat_g.size))
            g_shard = quantized_reduce_scatter(
                padded.reshape(n, chunk), axis_name,
                compression.block).astype(flat_g.dtype)
        else:
            compressed, ctx = compression.compress(flat_g)
            padded = jnp.pad(compressed, (0, n * chunk - flat_g.size))
            g_shard = jax.lax.psum_scatter(
                padded.reshape(n, chunk), axis_name, scatter_dimension=0)
            g_shard = compression.decompress(g_shard, ctx)
        if op_ == Average:
            g_shard = g_shard / n

        p_shard = None
        if params is not None:
            flat_p, _ = ravel_pytree(params)
            p_shard = _my_shard(flat_p)
        upd_shard, new_state = optimizer.update(g_shard, state, p_shard)

        full = jax.lax.all_gather(upd_shard, axis_name,
                                  tiled=True)[:flat_g.size]
        return unravel(full), new_state

    return optax.GradientTransformation(init_fn, update_fn)


def sharded_state_wrap(state):
    """Prepare a ShardedDistributedOptimizer state to LEAVE a
    ``shard_map`` region: every leaf (including scalar counters) gains a
    leading length-1 per-rank axis so ``out_specs=P(axis)`` can
    concatenate the per-replica shards."""
    import jax.numpy as jnp

    return jax.tree.map(lambda a: jnp.asarray(a)[None], state)


def sharded_state_unwrap(state):
    """Inverse of :func:`sharded_state_wrap` on ENTRY to the region
    (``in_specs=P(axis)`` hands each replica its own length-1 slice)."""
    return jax.tree.map(lambda a: a[0], state)


# --------------------------------------------------------------- eager binding
def ZeroDistributedOptimizer(optimizer, op=Average, compression=None,
                             min_size=None, group=None):
    """Eager ZeRO-1: the named-collective binding of the sharded update.

    Wraps an optax optimizer so that ``update`` reduce-scatters the
    flattened gradient (``hvd.reduce_scatter`` — TCP ring, coordinator
    star, or XLA plane, whichever the runtime negotiated), runs
    ``optimizer`` on this rank's block (state is allocated for that
    block only — ``init`` never materializes full-size state), and
    allgathers the updated block.  Models whose flat parameter count is
    below ``min_size`` (default: config ``zero_min_size``) fall back to
    a replicated allreduce-then-update — the branch is deterministic
    across ranks, so no negotiation mismatch is possible.

    ``op`` may be Average or Sum (Adasum needs full vectors);
    ``compression`` is a wire-compression name (``"bf16"`` / ``"fp16"``
    / ``"int8"``) applied to the gradient reduce-scatter — parameter
    allgather always travels at full precision, matching the in-graph
    binding.

    The returned transformation's state is the inner optimizer's state
    on the block; :func:`gather_zero_state` / :func:`reshard_zero_state`
    convert it to/from the full-size form for checkpointing and elastic
    reconfiguration.

    ``group`` scopes the whole decomposition to a
    :class:`~horovod_tpu.groups.ProcessGroup` — the DATA-PARALLEL group
    of a DP x TP x PP grid (docs/groups.md): the shard layout, the
    gradient reduce-scatter and the parameter allgather all run over
    the group's members, concurrently with other groups' collectives.
    """
    op_ = ReduceOp(op)
    if op_ == Adasum:
        raise ValueError(
            "ZeroDistributedOptimizer does not support Adasum; use "
            "DistributedOptimizer(op=Adasum)")
    comp = compression  # eager resolves names/classes/None uniformly

    def _topology():
        from horovod_tpu.common import basics

        if group is not None:
            # group-local view: the shard partition lives over the DP
            # group's members, re-read per call so an elastic re-form
            # is picked up (or fails typed) at the next step
            return group.rank(), group.size
        return basics.rank(), basics.size()

    def _sharded(n_params, world):
        return world > 1 and n_params >= _resolve_min_size(min_size)

    def init_fn(params):
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(params)
        rank, world = _topology()
        if not _sharded(flat.size, world):
            return optimizer.init(flat)
        _, off, cnt = zero_shard_layout(flat.size, world, rank)
        return optimizer.init(jax.lax.slice(flat, (off,), (off + cnt,)))

    def update_fn(grads, state, params=None):
        from jax.flatten_util import ravel_pytree

        from horovod_tpu.ops import eager

        flat_g, unravel = ravel_pytree(grads)
        rank, world = _topology()

        if not _sharded(flat_g.size, world):
            reduced = flat_g
            if world > 1:
                reduced = eager.allreduce(
                    flat_g, op=op_, name="zero.allreduce",
                    compression=comp, group=group)
            flat_p = None
            if params is not None:
                flat_p, _ = ravel_pytree(params)
            upd, new_state = optimizer.update(reduced, state, flat_p)
            return unravel(upd), new_state

        _, off, cnt = zero_shard_layout(flat_g.size, world, rank)
        g_block = eager.reduce_scatter(
            flat_g, op=op_, name="zero.reduce_scatter", compression=comp,
            group=group)
        p_block = None
        if params is not None:
            flat_p, _ = ravel_pytree(params)
            p_block = jax.lax.slice(flat_p, (off,), (off + cnt,))
        upd_block, new_state = optimizer.update(g_block, state, p_block)
        # variable-dim0 allgather: blocks differ by one row when
        # world_size does not divide the parameter count
        full = eager.allgather(upd_block, name="zero.allgather",
                               group=group)
        return unravel(full), new_state

    return optax.GradientTransformation(init_fn, update_fn)


# ------------------------------------------------- elastic / checkpoint glue
def flat_shard(flat, world_size, rank):
    """``rank``'s block of a flat vector under the eager ZeRO row
    partition (:func:`zero_shard_layout`).  The durable checkpoint
    writer (docs/checkpoint.md) shards every rank's param/optimizer
    vector with THIS partition so a checkpoint written at world N and a
    live ZeRO shard at world N agree bit-for-bit — and a resume at a
    different world size only re-slices, never re-pads."""
    import numpy as np

    _, off, cnt = zero_shard_layout(len(flat), world_size, rank)
    return np.asarray(flat)[off:off + cnt]


def gather_zero_state(state, n_params, name_prefix="zero.state_gather",
                      group=None):
    """Assemble the FULL optimizer state from every rank's block.

    Tree-maps the eager-ZeRO state: a 1-D leaf whose length equals this
    rank's block size is a sharded moment vector — allgather it
    (deterministic leaf-index names, so every rank pairs leaf-for-leaf
    even during elastic replay); anything else (step counters, already
    full-size leaves from a replicated fallback) is left alone.  The
    result is rank-independent: safe to checkpoint, broadcast, or
    re-shard at a different world size with :func:`reshard_zero_state`.
    """
    from horovod_tpu.common import basics
    from horovod_tpu.ops import eager

    rank, world = _topology_of(basics, group)
    if world <= 1:
        return state
    _, _, cnt = zero_shard_layout(int(n_params), world, rank)

    leaves, treedef = jax.tree.flatten(state)
    out = []
    for i, leaf in enumerate(leaves):
        arr = jax.numpy.asarray(leaf)
        if arr.ndim == 1 and arr.shape[0] == cnt and cnt != int(n_params):
            out.append(eager.allgather(arr, name=f"{name_prefix}.{i}",
                                       group=group))
        else:
            out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def reshard_zero_state(full_state, n_params, group=None):
    """Inverse of :func:`gather_zero_state` at the CURRENT topology:
    slice every full-size 1-D leaf down to this rank's block.  Called
    after elastic reconfiguration (possibly at a different world size
    than the state was gathered at) and after checkpoint restore."""
    from horovod_tpu.common import basics

    rank, world = _topology_of(basics, group)
    if world <= 1:
        return full_state
    n_params = int(n_params)
    _, off, cnt = zero_shard_layout(n_params, world, rank)

    def reshard(leaf):
        arr = jax.numpy.asarray(leaf)
        if arr.ndim == 1 and arr.shape[0] == n_params:
            return jax.lax.slice(arr, (off,), (off + cnt,))
        return leaf

    return jax.tree.map(reshard, full_state)


def _topology_of(basics, group=None):
    if group is not None:
        return group.rank(), group.size
    return basics.rank(), basics.size()
