"""Public eager collective API: named asynchronous tensor operations.

Mirrors the reference's op surface (``horovod/torch/mpi_ops.py``: sync/async
pairs, auto-generated names, Average/Sum/Adasum ops, prescale/postscale,
``synchronize``/``poll``, ``join``), executed through the controller +
XLA data plane instead of MPI/NCCL.
"""

import threading

from horovod_tpu.common import basics
from horovod_tpu.common.handles import Handle
from horovod_tpu.common.ops_enum import Adasum, Average, ReduceOp, RequestType, Sum
from horovod_tpu.ops.python_controller import EagerRequest
from horovod_tpu.utils import trace

_tls = threading.local()


def _auto_name(kind: str) -> str:
    """Per-rank sequence-numbered names, matching across ranks when call
    order matches (reference: handle-derived names in mpi_ops.py)."""
    counters = getattr(_tls, "counters", None)
    if counters is None:
        counters = _tls.counters = {}
    n = counters.get(kind, 0)
    counters[kind] = n + 1
    return f"{kind}.noname.{n}"


def _resolve_op(op, average):
    """Reference semantics (torch/mpi_ops.py:94-129): exactly one of op /
    average may be set; default is Average."""
    if op is not None and average is not None:
        raise ValueError("cannot specify both op and average")
    if op is None:
        op = Average if average in (None, True) else Sum
    return ReduceOp(op)


def _require_rank_context(state, name):
    """Device-rank mode runs every logical rank inside this process; an
    eager collective from the plain main thread would wait forever for the
    other ranks' submissions.  Fail fast with directions instead
    (reference analog: hanging negotiation is what the StallInspector
    exists to flag)."""
    if (state.config.controller != "tcp" and state.topology.local_size > 1
            and getattr(basics._tls, "local_rank", None) is None):
        raise RuntimeError(
            f"eager collective '{name}' called from the main thread in "
            f"device-rank mode (local_size="
            f"{state.topology.local_size}): each logical rank needs its "
            f"own context. Use horovod_tpu.common.basics.run_parallel(fn), "
            f"launch one process per rank with hvdrun, or use the SPMD "
            f"API (DistributedOptimizer inside shard_map)")


def _submit(req_type, tensor, name, *, op=Sum, root_rank=-1,
            prescale_factor=1.0, postscale_factor=1.0, splits=None,
            compression=None, group=None) -> Handle:
    with trace.span("hvd.submit"):
        t_submit = trace.now()
        state = basics._get_state()
        _require_rank_context(state, name)
        from horovod_tpu import groups as groups_mod
        from horovod_tpu.common.compression import resolve_compression

        # group scoping (docs/groups.md): resolve the handle to its CURRENT
        # incarnation — unsatisfiable groups fail typed here, before
        # anything reaches a controller — and require membership (a
        # collective from a non-member can never complete)
        gid, granks = groups_mod.resolve(group)
        if gid:
            me = basics.rank()
            if me not in granks:
                raise ValueError(
                    f"collective '{name}': rank {me} is not a member of "
                    f"process group {group.name!r} (ranks {list(granks)})")

        # None -> the configured default (HVD_TPU_COMPRESSION / autotune);
        # accepts a canonical name or a Compression class.  Adasum combines
        # full-precision vectors by construction, so it never compresses.
        compression = resolve_compression(
            compression, default=getattr(state.config, "compression", "none"))
        if req_type == RequestType.ADASUM:
            compression = "none"
        # rank indexes the executor's device list (global in gmesh mode, local
        # otherwise).  The tcp plane keeps tensors as numpy: a device commit
        # there would let jax narrow 64-bit dtypes before the exact numpy
        # transport ever sees them.
        if tensor is None:
            committed = None
        elif state.config.controller == "tcp":
            import numpy as _np

            # copy, not a view: capture-at-call semantics — the caller may
            # legally reuse its buffer before the coordinator cycle runs,
            # and different ranks racing that mutation would reduce
            # inconsistent snapshots.  NOTE the device path's contract is
            # weaker for MUTABLE framework tensors: jax.Array inputs are
            # immutable (capture-at-call for free), but a torch tensor
            # staged zero-copy via DLPack is aliased until the cycle reads
            # it — do not mutate between an async submit and synchronize
            # (the reference's adapters have the same rule,
            # torch/adapter_v2.h:42).
            committed = _np.array(tensor, copy=True)
        elif gid:
            # group-local commit: the entry executes on the group's
            # sub-executor, whose device list is indexed by group rank
            committed = state.executor.subset(granks).commit(
                tensor, granks.index(basics.rank()))
        else:
            committed = state.executor.commit(tensor, basics.rank())
        handle = Handle(name)
        trace.submitted(handle, t_submit)
        state.controller.enqueue(EagerRequest(
            rank=basics.rank(), req_type=req_type, name=name, tensor=committed,
            handle=handle, op=op, root_rank=root_rank,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
            splits=splits, compression=compression,
            schedule=getattr(state.config, "schedule", "auto"),
            group=gid, group_ranks=granks))
        handle.t_enqueued = trace.now()
    return handle


# ------------------------------------------------------------- allreduce ----
def allreduce_async(tensor, average=None, name=None, op=None,
                    prescale_factor=1.0, postscale_factor=1.0,
                    compression=None, group=None) -> Handle:
    """``compression``: ``None`` (use the configured default), a name
    ("none" / "bf16" / "fp16" / "int8") or a
    :class:`horovod_tpu.Compression` member — selects the on-the-wire
    representation of this allreduce (reference: the ``compression``
    argument of ``hvd.DistributedOptimizer``, fp16 in the paper)."""
    op = _resolve_op(op, average)
    req_type = RequestType.ADASUM if op == Adasum else RequestType.ALLREDUCE
    return _submit(req_type, tensor, name or _auto_name("allreduce"),
                   op=op, prescale_factor=prescale_factor,
                   postscale_factor=postscale_factor,
                   compression=compression, group=group)


def allreduce(tensor, average=None, name=None, op=None,
              prescale_factor=1.0, postscale_factor=1.0, compression=None,
              group=None):
    return synchronize(allreduce_async(
        tensor, average=average, name=name, op=op,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        compression=compression, group=group))


def grouped_allreduce(tensors, average=None, name=None, op=None,
                      compression=None, group=None):
    """Allreduce a list of tensors as one negotiation group; fusion batches
    them into single XLA programs."""
    base = name or _auto_name("grouped_allreduce")
    handles = [
        allreduce_async(t, average=average, name=f"{base}.{i}", op=op,
                        compression=compression, group=group)
        for i, t in enumerate(tensors)
    ]
    return [synchronize(h) for h in handles]


# -------------------------------------------------------- reduce_scatter ----
def reduce_scatter_async(tensor, op=None, average=None, name=None,
                         prescale_factor=1.0, postscale_factor=1.0,
                         compression=None, group=None) -> Handle:
    """Reduce across ranks, then scatter row blocks of the first
    dimension: rank ``r`` receives rows ``split_sizes[r]`` of the reduced
    tensor (np.array_split partition — the first ``dim0 % size`` ranks
    get one extra row).  The ZeRO decomposition's first half (PAPERS.md
    arXiv:2004.13336); paired with :func:`allgather` it replaces an
    allreduce with the optimizer update in between."""
    op = _resolve_op(op, average)
    if op == Adasum:
        raise ValueError("reduce_scatter does not support the Adasum op")
    return _submit(RequestType.REDUCE_SCATTER, tensor,
                   name or _auto_name("reduce_scatter"), op=op,
                   prescale_factor=prescale_factor,
                   postscale_factor=postscale_factor,
                   compression=compression, group=group)


def reduce_scatter(tensor, op=None, average=None, name=None,
                   prescale_factor=1.0, postscale_factor=1.0,
                   compression=None, group=None):
    return synchronize(reduce_scatter_async(
        tensor, op=op, average=average, name=name,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        compression=compression, group=group))


# ------------------------------------------------------------- allgather ----
def allgather_async(tensor, name=None, group=None) -> Handle:
    return _submit(RequestType.ALLGATHER, tensor,
                   name or _auto_name("allgather"), group=group)


def allgather(tensor, name=None, group=None):
    return synchronize(allgather_async(tensor, name=name, group=group))


def grouped_allgather(tensors, name=None, group=None):
    """Allgather a list of tensors as one negotiation group, mirroring
    :func:`grouped_allreduce`'s naming contract (``base.{i}``)."""
    base = name or _auto_name("grouped_allgather")
    handles = [allgather_async(t, name=f"{base}.{i}", group=group)
               for i, t in enumerate(tensors)]
    return [synchronize(h) for h in handles]


# ------------------------------------------------------------- broadcast ----
def broadcast_async(tensor, root_rank, name=None, group=None) -> Handle:
    """``root_rank`` is always a GLOBAL rank, with or without a group
    (the group path translates it internally)."""
    return _submit(RequestType.BROADCAST, tensor,
                   name or _auto_name("broadcast"), root_rank=root_rank,
                   group=group)


def broadcast(tensor, root_rank, name=None, group=None):
    return synchronize(broadcast_async(tensor, root_rank, name=name,
                                       group=group))


# -------------------------------------------------------------- alltoall ----
def alltoall_async(tensor, splits=None, name=None, group=None) -> Handle:
    if splits is None:
        if group is not None:
            from horovod_tpu import groups as groups_mod
            n = len(groups_mod.resolve(group)[1])
        else:
            n = basics.size()
        dim0 = int(tensor.shape[0])
        if dim0 % n != 0:
            raise ValueError(
                f"alltoall without explicit splits requires the first "
                f"dimension ({dim0}) to be divisible by size ({n})")
        splits = [dim0 // n] * n
    return _submit(RequestType.ALLTOALL, tensor,
                   name or _auto_name("alltoall"), splits=list(splits),
                   group=group)


def alltoall(tensor, splits=None, name=None, group=None):
    result, _ = synchronize(alltoall_async(tensor, splits=splits, name=name,
                                           group=group))
    return result


# -------------------------------------------------------------- barrier ----
def barrier(group=None, name=None):
    """Block until every rank of ``group`` (default: the world) has
    entered the barrier.  Implemented as a 1-element allreduce under a
    reserved auto-name: it rides the ordinary negotiation machinery, so
    it composes with groups, aborts and elastic epochs for free."""
    import numpy as _np

    allreduce(_np.zeros(1, dtype=_np.int32), op=Sum,
              name=name or _auto_name("barrier"), group=group)
    return None


# ------------------------------------------------------------ completion ----
def synchronize(handle: Handle, timeout=None):
    """Block until the async op completes and return its result
    (reference: mpi_ops.synchronize / HandleManager.WaitForCompletion)."""
    with trace.span("hvd.wait"):
        return handle.wait(timeout)


def poll(handle: Handle) -> bool:
    return handle.poll()


def join() -> int:
    """Signal that this rank has no more data; outstanding allreduces from
    other ranks proceed with zero stand-ins from this rank.  Blocks until
    every rank has joined and returns the last rank to join (reference:
    torch/mpi_ops_v2.cc:240 DoJoin, controller.cc joined handling)."""
    state = basics._get_state()
    _require_rank_context(state, "join")
    handle = Handle("join")
    state.controller.join(basics.rank(), handle)
    return handle.wait()
