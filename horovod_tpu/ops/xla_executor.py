"""XLA collective executor — the TPU data plane.

This is the TPU-native replacement for the reference's collective backends
(``horovod/common/ops/{nccl,mpi,gloo}_operations.cc``): a response the
controller built is executed by ONE compiled XLA program per steady-state
signature — ``lax.psum`` / ``lax.all_gather`` over the ``hvd`` mesh axis rides
ICI within a slice and DCN across slices.

Design notes (vs the reference):

- The reference caches NCCL communicators and reuses a persistent 64 MB fusion
  buffer (``fusion_buffer_manager.cc``).  Here the analogous steady-state
  object is the **compiled executable**: programs are memoized by fused-group
  signature (op, dtype, shapes, scale factors), so a training loop's recurring
  gradient buckets hit the XLA executable cache after the first step — the
  ResponseCache idea (``response_cache.cc``) mapped onto the compilation model.
- **An allreduce response is one program launch.**  The reference copies
  every tensor into the fusion buffer before the collective and out of it
  after (``MemcpyInFusionBuffer`` / ``MemcpyOutFusionBuffer``,
  ``collective_operations.cc:44``).  Here no buffer is built outside the
  program: each entry's per-rank tensors become one mesh-sharded
  ``jax.Array`` whose shard on a rank's device IS that rank's tensor (an
  assembly of handles, no device work), and the cached program takes those
  arrays as its arguments and ravels, concatenates, scales, casts, reduces
  and splits inside itself.  The flat buffer exists only as a value of the
  program, where XLA fuses the copies into the collective's neighbours; a
  launch costs the host far more than the bytes cost the chip, and a
  program per copy made a 100 us collective wait for 765 us of host work
  (PERF.md, PR 30).  The other collectives still stage a per-rank buffer
  with a small jitted program first (``_fuse_in``, the pads of allgather
  and alltoall) and assemble it with ``_stack``.
- GPU ready-events + finalizer threads (``gpu_operations.h:92``) are
  unnecessary: JAX's async dispatch returns immediately and consumers block
  only when they touch the result.
"""

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common.compression import (INT8_BLOCK,
                                            quantized_all_gather,
                                            quantized_reduce_scatter,
                                            resolve_compression)
from horovod_tpu.common.ops_enum import ReduceOp, is_float_dtype
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils import trace
from horovod_tpu.utils.logging import get_logger

AXIS = "hvd"

# The hierarchical data plane pads fused buffers so the reduce-scatter
# chunks are equal; the reference rounds its fusion buffer to be divisible
# by local_size * 64 elements the same way (controller.cc:358-376).
FUSION_ALIGN_ELEMS = 64


def _shard_map_gathered(body, mesh, in_specs, out_specs):
    """shard_map whose body returns an all-gathered (hence device-invariant,
    but not statically-inferrable-as-replicated) value."""
    return _shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)


def _prod(shape):
    return int(math.prod(shape)) if shape else 1


class XlaExecutor:
    """Executes fused collective groups as compiled XLA programs over a 1-D
    device mesh whose axis enumerates logical ranks."""

    def __init__(self, devices, hier_local_size=None):
        self.devices = list(devices)
        self.num_ranks = len(self.devices)
        # The mesh and the rank-enumerating axis name are a subclass hook:
        # MeshExecutor (horovod_tpu/sharding/mesh_executor.py) swaps in a
        # parallel.mesh-vocabulary mesh so model-parallel axes can later
        # share the topology.
        self.mesh, self.axis = self._build_mesh(self.devices)
        self._sharded = NamedSharding(self.mesh, P(self.axis))
        self._replicated = NamedSharding(self.mesh, P())
        # Multi-process (global-mesh) support: this process only produces
        # and consumes the shards that live on its own devices; the
        # compiled program spans the full mesh (reference analog: each
        # worker contributes its ranks' buffers, NCCL moves the bytes).
        my_pid = jax.process_index()
        self.local_ranks = [
            i for i, d in enumerate(self.devices)
            if getattr(d, "process_index", my_pid) == my_pid]
        self.multiprocess = len(self.local_ranks) != self.num_ranks
        # caches are touched only from the coordinator thread
        self._fuse_in_cache = {}
        self._zeros_cache = {}
        self._allreduce_cache = {}
        self._allgather_cache = {}
        self._alltoall_cache = {}
        self._reduce_scatter_cache = {}
        # process-group sub-executors, memoized per rank tuple
        # (docs/groups.md): each carries its own caches, so per-signature
        # programs are effectively keyed (group, signature)
        self._subsets = {}

        # Two-level (cross, local) mesh for hierarchical collectives
        # (reference: NCCLHierarchicalAllreduce intra-node/inter-node split,
        # nccl_operations.cc:162-289).  "local" = ranks sharing fast
        # interconnect (one host's chips / one ICI slice); "cross" rides
        # DCN.  Grouping source: explicit arg > HVD_HIER_LOCAL_SIZE env >
        # device process_index.
        explicit = hier_local_size is not None
        if hier_local_size is None:
            hier_local_size = env_util.get_int(
                env_util.HVD_HIER_LOCAL_SIZE, 0) or None
            explicit = hier_local_size is not None
        if hier_local_size is None:
            per_proc = {}
            for d in self.devices:
                per_proc.setdefault(getattr(d, "process_index", 0),
                                    []).append(d)
            sizes = {len(v) for v in per_proc.values()}
            if len(sizes) == 1:
                hier_local_size = sizes.pop()
        self.hier_mesh = None
        if hier_local_size and 1 < hier_local_size < self.num_ranks:
            try:
                from horovod_tpu.parallel.mesh import hierarchical_mesh
                self.hier_mesh = hierarchical_mesh(hier_local_size,
                                                   self.devices)
            except ValueError as exc:
                if explicit:
                    get_logger().warning(
                        "ignoring HVD_HIER_LOCAL_SIZE=%s: %s — hierarchical "
                        "collectives will run the flat path",
                        hier_local_size, exc)
        elif explicit:
            get_logger().warning(
                "HVD_HIER_LOCAL_SIZE=%s does not define a two-level "
                "hierarchy over %d ranks; hierarchical collectives will "
                "run the flat path", hier_local_size, self.num_ranks)
        # Allreduce/allgather schedules are flipped by config at init and by
        # the autotuner at runtime (pure communication-schedule choices —
        # same numbers either way).  Adasum's hierarchical mode CHANGES THE
        # REDUCTION SEMANTICS (adasum of per-group averages, reference
        # AdasumGpuAllreduceOp), so it is pinned at init and never touched
        # by the tuner.
        self.hierarchical_allreduce = False
        self.hierarchical_allgather = False
        self.adasum_hierarchical = False

    # ------------------------------------------------------------------ utils
    def _build_mesh(self, devices):
        """Return ``(mesh, axis_name)`` — the 1-D rank mesh and the name of
        its rank-enumerating axis.  Subclass hook."""
        return Mesh(np.array(devices), (AXIS,)), AXIS

    def subset(self, ranks):
        """The sub-executor over ``ranks``'s devices (memoized).  Ranks are
        GLOBAL; inside the returned executor they renumber to 0..k-1 in
        the given order, which is how grouped entries are re-keyed before
        execution (python_controller._build_group)."""
        key = tuple(int(r) for r in ranks)
        sub = self._subsets.get(key)
        if sub is None:
            sub = type(self)([self.devices[r] for r in key])
            self._subsets[key] = sub
        return sub

    def commit(self, tensor, rank):
        """Pin a rank's tensor to its device (no-op if already there)."""
        dev = self.devices[rank % self.num_ranks]
        if isinstance(tensor, jax.Array):
            try:
                if tensor.devices() == {dev}:
                    return tensor
            except Exception:  # noqa: BLE001 — fall through to device_put
                pass
        return jax.device_put(tensor, dev)

    def _shard_for(self, replicated, rank):
        """Zero-copy view of a replicated array's shard on rank's device."""
        dev = self.devices[rank]
        for shard in replicated.addressable_shards:
            if shard.device == dev:
                return shard.data
        raise RuntimeError(f"no addressable shard on {dev}")

    def _stack(self, per_rank_bufs, shard_shape, dtype):
        """Assemble the mesh-sharded fusion buffer from this process's
        per-rank shards (``per_rank_bufs``: list in local-rank order).

        Each buffer is pinned to its rank's device first: XLA constant-
        folds programs over empty/trivial shards, and folded outputs land
        on the DEFAULT device regardless of input placement (no-op when
        already resident)."""
        with trace.span("hvd.exec.stack"):
            per_rank_bufs = [
                jax.device_put(buf, self.devices[rank])
                for buf, rank in zip(per_rank_bufs, self.local_ranks)]
            global_shape = (self.num_ranks,) + tuple(shard_shape[1:])
            return jax.make_array_from_single_device_arrays(
                global_shape, self._sharded, per_rank_bufs)

    # ------------------------------------------------------- fusion buffer in
    def _fuse_in(self, tensors, sizes, dtype):
        """Concat one rank's tensors into a flat [1, total] buffer on its
        device (reference: MemcpyInFusionBuffer).  A device program of its
        own: only ``reduce_scatter``, ``broadcast`` and ``adasum`` still
        stage their one tensor this way; an allreduce flattens inside its
        collective program (``allreduce_fused``)."""
        with trace.span("hvd.exec.fuse_in"):
            key = (tuple(sizes), np.dtype(dtype).name)
            fn = self._fuse_in_cache.get(key)
            if fn is None:
                def fuse(*ts):
                    return jnp.concatenate(
                        [t.reshape(-1) for t in ts]).reshape(1, -1)
                fn = jax.jit(fuse)
                self._fuse_in_cache[key] = fn
            return fn(*tensors)

    def _zeros_buf(self, total, dtype, rank):
        """Zero stand-in buffer for a joined rank (reference:
        tensor_queue.cc GetTensorEntriesFromResponse joined path).  Made
        anew each time: the programs that take it donate it."""
        return jax.device_put(np.zeros((1, total), dtype=dtype),
                              self.devices[rank])

    def _zeros(self, shape, dtype, rank):
        """A joined rank's stand-in for one absent allreduce entry: zeros
        of the entry's shape on that rank's device, made once (nothing
        donates an allreduce's arguments, so the array is shared)."""
        key = (shape, np.dtype(dtype).name, rank)
        zeros = self._zeros_cache.get(key)
        if zeros is None:
            zeros = self._zeros_cache[key] = jax.device_put(
                np.zeros(shape, dtype=dtype), self.devices[rank])
        return zeros

    def _rank_sharded(self, shape, dtype, tensors):
        """One mesh-sharded array whose shard on a local rank's device IS
        that rank's tensor: global shape ``(N * s0, *rest)`` split on axis
        0 over the rank axis.  An assembly of handles: no device program,
        no copy.  ``tensors`` maps rank -> committed array (None or absent
        for a joined rank: zeros).

        A zero-dimensional tensor has no axis to carry the ranks, and
        declaring N different scalars one "replicated" array would license
        XLA to turn the all-reduce into a multiplication.  So each rank's
        scalar is given shape ``(1,)`` first, by one small program a rank:
        the only allreduce response with more than one launch."""
        shard_shape = shape or (1,)
        shards = []
        for rank in self.local_ranks:
            t = tensors.get(rank)
            if t is None:
                t = self._zeros(shard_shape, dtype, rank)
            else:
                # a no-op for what ops/eager.py committed; XLA would place
                # a stray tensor's results with the stray tensor
                t = self.commit(t, rank)
                if not shape:
                    t = t.reshape(1)
            shards.append(t)
        return jax.make_array_from_single_device_arrays(
            (self.num_ranks * shard_shape[0],) + shard_shape[1:],
            self._sharded, shards)

    # -------------------------------------------------------------- allreduce
    def _effective_compression(self, compression, dtype, total):
        """Resolve the on-the-wire compression for a fused group: exact
        passthrough for non-float dtypes, for tensors too small to pay
        the scale overhead, for single-rank meshes, and for casts that
        would be no-ops (bf16 of bf16, fp16 of fp16).  Deterministic in
        (dtype, total), so every process of a multi-process job resolves
        the coordinator's bucket identically."""
        comp = resolve_compression(compression) if compression else "none"
        if comp == "none":
            return comp
        npdt = np.dtype(dtype)
        if not is_float_dtype(npdt) or self.num_ranks == 1:
            return "none"
        if comp == "bf16" and npdt.name == "bfloat16":
            return "none"
        if comp == "fp16" and npdt == np.float16:
            return "none"
        if comp == "int8" and total < INT8_BLOCK:
            return "none"
        return comp

    def allreduce_fused(self, entries, op, prescale_factor, postscale_factor,
                        compression="none"):
        """Execute a fused allreduce group as ONE launch of ONE cached
        program, for one tensor and for a bucket, on one rank and on many.

        ``entries`` is a list of group entries with ``.shape``, ``.dtype``,
        ``.tensors`` (rank -> committed array, or None for joined ranks) and
        ``.handles`` (rank -> Handle).  All entries share one dtype (and
        one ``compression`` — the bucket key separates them).

        The program's arguments are the entries' own tensors, one
        mesh-sharded array an entry (``_rank_sharded``); flattening,
        concatenation, scaling, casts, the collective and the split back
        into shapes happen inside it (``_build_allreduce``).  Nothing is
        copied or reshaped outside the program, so the host pays for one
        launch a response, and nothing is donated, because the arguments
        are the caller's arrays.  A joined rank contributes cached zeros
        for each entry it is absent from, and only for those.  An empty
        tensor is no argument at all: the program makes its empty result
        itself, so a response of nothing but empty tensors is still one
        launch.  With one rank the response still launches its program.
        """
        shapes = tuple(tuple(e.shape) for e in entries)
        total = sum(_prod(s) for s in shapes)
        dtype = entries[0].dtype
        comp = self._effective_compression(compression, dtype, total)

        with trace.span("hvd.exec.assemble"):
            garrs = [self._rank_sharded(shape, dtype, entry.tensors)
                     for shape, entry in zip(shapes, entries)
                     if _prod(shape)]

        with trace.span("hvd.exec.lookup"):
            hierarchical = bool(self.hierarchical_allreduce
                                and self.hier_mesh is not None)
            key = (shapes, np.dtype(dtype).name, int(op),
                   float(prescale_factor), float(postscale_factor),
                   hierarchical, comp)
            fn = self._allreduce_cache.get(key)
            if fn is None:  # a miss: the build lands in this span
                fn = self._build_allreduce(
                    shapes, dtype, op, prescale_factor, postscale_factor,
                    hierarchical, comp)
                self._allreduce_cache[key] = fn

        with trace.span("hvd.exec.launch"):
            outs = fn(*garrs)
        with trace.span("hvd.exec.complete"):
            for entry, out in zip(entries, outs):
                for rank, handle in entry.handles.items():
                    handle.set_result(self._shard_for(out, rank))

    def _build_allreduce(self, shapes, dtype, op, prescale_factor,
                         postscale_factor, hierarchical, comp):
        """Compile the fused allreduce of one signature: exact, with the
        collective run in a narrower dtype (``comp`` bf16 / fp16), or
        block-scaled int8 (``_int8_body``).

        The program takes one argument for every entry that has elements
        (``[N * s0, *rest]`` sharded over the ranks, so a shard is the
        rank's tensor as it was handed in) and returns one replicated
        result an entry.  Per shard it ravels and concatenates the tensors
        into the flat buffer, prescales, casts to the wire dtype and
        reduces; the reduced buffer is then cast back, averaged,
        postscaled, sliced and reshaped.  The buffer is a value inside the
        program and never an array of its own."""
        num_ranks = self.num_ranks
        sizes = [_prod(s) for s in shapes]
        live = sum(1 for size in sizes if size)  # the arguments
        total = sum(sizes)
        mesh = self.hier_mesh if hierarchical else self.mesh
        in_spec = P(("cross", "local")) if hierarchical else P(self.axis)
        # Integer tensors: the reduction stays exact in the integer
        # dtype and ALL scaling (pre x post x 1/n, which commutes
        # with the sum) happens once in float32 with a cast back —
        # casting a fractional factor to an int dtype would truncate
        # it to 0 and silently zero every result, and int/int true
        # division would silently change the output dtype.  (NumPy does
        # not count bfloat16 among its floating types, so it is scaled
        # this way too: once, in float32.)
        int_dtype = not np.issubdtype(np.dtype(dtype), np.floating)

        if comp == "int8":
            reduce_flat = self._int8_body(total, prescale_factor,
                                          hierarchical)
        else:
            reduce_flat = self._cast_body(
                total, 1.0 if int_dtype else prescale_factor,
                {"bf16": jnp.bfloat16, "fp16": jnp.float16}.get(comp),
                hierarchical)

        def body(*shards):  # a rank's own tensors, as handed in
            return reduce_flat(
                jnp.concatenate([s.reshape(-1) for s in shards]))

        def scaled(flat):  # the reduced buffer -> the entries' dtype
            if comp == "int8":  # fp32 accumulate
                if op == ReduceOp.AVERAGE:
                    flat = flat / num_ranks
                if postscale_factor != 1.0:
                    flat = flat * postscale_factor
                return flat.astype(dtype)
            flat = flat.astype(dtype)  # back from the wire dtype
            if not int_dtype:
                if op == ReduceOp.AVERAGE:
                    flat = flat / jnp.asarray(num_ranks, dtype=flat.dtype)
                if postscale_factor != 1.0:
                    flat = flat * jnp.asarray(postscale_factor,
                                              dtype=flat.dtype)
                return flat
            factor = prescale_factor * postscale_factor
            if op == ReduceOp.AVERAGE:
                factor /= num_ranks
            if factor != 1.0:
                # float64 when x64 is on; otherwise f32 caps exactness at
                # 2**24 — large int sums can lose low bits (the tcp plane
                # scales in f64)
                sdt = (jnp.float64 if jax.config.jax_enable_x64
                       else jnp.float32)
                flat = (flat.astype(sdt) * factor).astype(flat.dtype)
            return flat

        def fused(*gs):
            if not live:  # nothing but empty tensors
                return tuple(jnp.zeros(shape, dtype) for shape in shapes)
            flat = scaled(_shard_map_gathered(
                body, mesh, (in_spec,) * live, P())(*gs))
            outs = []
            offset = 0
            for size, shape in zip(sizes, shapes):
                outs.append(
                    jax.lax.slice(flat, (offset,),
                                  (offset + size,)).reshape(shape))
                offset += size
            return tuple(outs)

        # replicated over the rank mesh whatever the body was: a program
        # of constants alone would leave its results on the default device
        return jax.jit(fused, out_shardings=self._replicated)

    def _cast_body(self, total, prescale_factor, wire_dt, hierarchical):
        """Per-shard reduction of the flat ``[total]`` buffer, exact or
        with the collective in a narrower dtype."""
        axis = self.axis

        def on_the_wire(x):
            if prescale_factor != 1.0:
                x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
            # Cast compression (bf16/fp16): the collective itself runs in
            # the narrow dtype — XLA fuses the casts into the program and
            # every leg (ICI and DCN) moves half the bytes (reference:
            # fp16 compression, horovod/torch/compression.py:45).
            return x if wire_dt is None else x.astype(wire_dt)

        def flat_body(x):
            return jax.lax.psum(on_the_wire(x), axis)

        def hier_body(x):
            # reduce-scatter on ICI -> cross allreduce on DCN ->
            # allgather on ICI (reference: nccl_operations.cc:162-289:
            # ncclReduceScatter -> MPI allreduce -> ncclAllgather).
            x = on_the_wire(x)
            local = self.hier_mesh.shape["local"]
            align = local * FUSION_ALIGN_ELEMS
            padded = -(-total // align) * align
            if padded != total:
                x = jnp.pad(x, (0, padded - total))
            chunk = jax.lax.psum_scatter(x, "local", scatter_dimension=0,
                                         tiled=True)
            chunk = jax.lax.psum(chunk, "cross")
            full = jax.lax.all_gather(chunk, "local", tiled=True)
            return full[:total]

        return hier_body if hierarchical else flat_body

    def _int8_body(self, total, prescale_factor, hierarchical):
        """Per-shard block-scaled int8 reduction of the flat buffer
        (EQuARX, arXiv:2506.17615): quantize inside the jitted program,
        exchange int8 + fp32 block scales via ``all_to_all`` (the
        reduce-scatter leg), accumulate in fp32, requantize the reduced
        chunk before the allgather leg, dequantize on unpack.  Each element
        passes through exactly two quantizations regardless of rank count.
        On the hierarchical mesh the quantized legs run over the fast
        "local" axis and the owned chunk crosses DCN once in fp32 (already
        1/local_size of the payload)."""
        axis = "local" if hierarchical else self.axis
        n_split = (self.hier_mesh.shape["local"] if hierarchical
                   else self.num_ranks)
        chunk = -(-total // (n_split * INT8_BLOCK)) * INT8_BLOCK
        padded = chunk * n_split

        def body(x):
            x = x.astype(jnp.float32)
            if prescale_factor != 1.0:
                x = x * prescale_factor
            x = jnp.pad(x, (0, padded - total))
            red = quantized_reduce_scatter(x.reshape(n_split, chunk), axis)
            if hierarchical:
                red = jax.lax.psum(red, "cross")
            full = quantized_all_gather(red, axis)
            return full[:total]

        return body

    # -------------------------------------------------------------- allgather
    def allgather(self, entry):
        """Allgather with per-rank variable first dimension (reference:
        controller.cc:453-518 computes recvcounts/displacements; here the
        compiled program pads to max(dim0), all-gathers over the mesh and
        concatenates the valid rows)."""
        dtype = entry.dtype
        if getattr(entry, "all_dims0", None) is not None:
            # multi-process: per-rank first dims were negotiated globally
            dims0 = [int(d) for d in entry.all_dims0]
            some_local = entry.tensors[self.local_ranks[0]]
            rest = tuple(some_local.shape[1:])
        else:
            shapes_all = tuple(tuple(entry.tensors[r].shape)
                               for r in range(self.num_ranks))
            dims0 = [s[0] if s else 1 for s in shapes_all]
            rest = shapes_all[0][1:]
        max0 = max(dims0)

        hierarchical = bool(self.hierarchical_allgather
                            and self.hier_mesh is not None)
        key = (tuple(dims0), rest, np.dtype(dtype).name, hierarchical)
        fn = self._allgather_cache.get(key)
        if fn is None:
            axis = self.axis

            def pad(t, n0=max0):
                padded = jnp.zeros((1, n0) + t.shape[1:], dtype=t.dtype)
                return jax.lax.dynamic_update_slice(
                    padded, t[None], (0,) * (t.ndim + 1))

            def body(shard):  # [1, max0, *rest]
                return jax.lax.all_gather(shard[0], axis)  # [N, max0, *rest]

            def hier_body(shard):
                # gather within the fast local group first, then move the
                # assembled block once across the slow axis (reference:
                # MPIHierarchicalAllgather's node-leader + shared-memory
                # two-phase gather, mpi_operations.cc).  Rank order is
                # (cross major, local minor), matching host:slots rank
                # numbering, so the reshape restores flat rank order.
                g_local = jax.lax.all_gather(shard[0], "local")
                g = jax.lax.all_gather(g_local, "cross")  # [C, L, max0, ...]
                return g.reshape((self.num_ranks,) + g.shape[2:])

            def gather(g):
                if hierarchical:
                    full = _shard_map_gathered(
                        hier_body, self.hier_mesh,
                        P(("cross", "local")), P())(g)
                else:
                    full = _shard_map_gathered(body, self.mesh,
                                               P(axis), P())(g)
                parts = [jax.lax.slice_in_dim(full[i], 0, dims0[i], axis=0)
                         for i in range(self.num_ranks)]
                return jnp.concatenate(parts, axis=0)

            fn = (jax.jit(pad), jax.jit(gather, donate_argnums=0))
            self._allgather_cache[key] = fn

        pad_fn, gather_fn = fn
        bufs = [pad_fn(entry.tensors[r]) for r in self.local_ranks]
        garr = self._stack(bufs, (1, max0) + rest, dtype)
        with trace.span("hvd.exec.launch"):
            out = gather_fn(garr)
        with trace.span("hvd.exec.complete"):
            for rank, handle in entry.handles.items():
                handle.set_result(self._shard_for(out, rank))

    # --------------------------------------------------------- reduce_scatter
    def reduce_scatter(self, entry):
        """Reduce + scatter row blocks of the first dimension: rank ``r``
        receives ``reduce_scatter_split_sizes(dim0, N)[r]`` rows of the
        reduced tensor (np.array_split partition, shared with the TCP
        planes).  The first half of the ZeRO decomposition (PAPERS.md
        arXiv:2004.13336) as an eager collective; int8 compression reuses
        the quantized reduce-scatter wire format from the fused allreduce.
        """
        from horovod_tpu.common.ops_enum import reduce_scatter_split_sizes

        shape = tuple(entry.shape)
        rest = shape[1:]
        total = _prod(shape)
        dtype = entry.dtype
        num_ranks = self.num_ranks
        counts = reduce_scatter_split_sizes(shape[0], num_ranks)
        offsets = [sum(counts[:r]) for r in range(num_ranks)]
        op = entry.op
        prescale_factor = entry.prescale_factor
        postscale_factor = entry.postscale_factor
        comp = self._effective_compression(entry.compression, dtype, total)

        bufs = [self._fuse_in([entry.tensors[r]], [total], dtype)
                for r in self.local_ranks]
        garr = self._stack(bufs, (1, total), dtype)

        key = ("reduce_scatter", shape, np.dtype(dtype).name, int(op),
               float(prescale_factor), float(postscale_factor), comp)
        fn = self._reduce_scatter_cache.get(key)
        if fn is None:
            axis = self.axis
            wire_dt = {"bf16": jnp.bfloat16,
                       "fp16": jnp.float16}.get(comp)
            int_dtype = not np.issubdtype(np.dtype(dtype), np.floating)

            if comp == "int8":
                chunk = -(-total // (num_ranks * INT8_BLOCK)) * INT8_BLOCK
                padded = chunk * num_ranks

                def body(shard):  # [1, total] on one rank
                    x = shard.reshape(-1).astype(jnp.float32)
                    if prescale_factor != 1.0:
                        x = x * prescale_factor
                    x = jnp.pad(x, (0, padded - total))
                    red = quantized_reduce_scatter(
                        x.reshape(num_ranks, chunk), axis)
                    full = quantized_all_gather(red, axis)
                    return full[:total][None]
            else:
                def body(shard):
                    x = shard
                    if prescale_factor != 1.0 and not int_dtype:
                        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
                    if wire_dt is not None:
                        x = x.astype(wire_dt)
                    return jax.lax.psum(x, axis)

            def fused(g):
                if comp == "int8":
                    red = _shard_map_gathered(body, self.mesh,
                                              P(axis), P())(g)
                else:
                    red = _shard_map(body, mesh=self.mesh,
                                     in_specs=P(axis), out_specs=P())(g)
                flat = red.reshape(-1)
                if wire_dt is not None:
                    flat = flat.astype(dtype)
                if comp == "int8":
                    if op == ReduceOp.AVERAGE:
                        flat = flat / num_ranks
                    if postscale_factor != 1.0:
                        flat = flat * postscale_factor
                    flat = flat.astype(dtype)
                elif int_dtype:
                    factor = prescale_factor * postscale_factor
                    if op == ReduceOp.AVERAGE:
                        factor /= num_ranks
                    if factor != 1.0:
                        sdt = (jnp.float64 if jax.config.jax_enable_x64
                               else jnp.float32)
                        flat = (flat.astype(sdt)
                                * factor).astype(flat.dtype)
                else:
                    if op == ReduceOp.AVERAGE:
                        flat = flat / jnp.asarray(num_ranks,
                                                  dtype=flat.dtype)
                    if postscale_factor != 1.0:
                        flat = flat * jnp.asarray(postscale_factor,
                                                  dtype=flat.dtype)
                full = flat.reshape(shape)
                return tuple(
                    jax.lax.slice_in_dim(full, offsets[r],
                                         offsets[r] + counts[r], axis=0)
                    for r in range(num_ranks))

            fn = jax.jit(fused, donate_argnums=0)
            self._reduce_scatter_cache[key] = fn

        with trace.span("hvd.exec.launch"):
            outs = fn(garr)
        with trace.span("hvd.exec.complete"):
            for rank, handle in entry.handles.items():
                handle.set_result(self._shard_for(outs[rank], rank))

    # -------------------------------------------------------------- broadcast
    def broadcast(self, entry):
        """Replicate the root rank's tensor to every rank's device
        (reference: MPIBroadcast / NCCLBroadcast).

        Single-process: direct XLA replication transfer.  Multi-process:
        one compiled program — non-root ranks contribute zero rows to the
        mesh-stacked buffer and a ``psum`` over the rank axis materializes
        the root's data everywhere (data rides ICI/DCN collectives, never
        the host control plane)."""
        if not self.multiprocess:
            src = entry.tensors[entry.root_rank]
            with trace.span("hvd.exec.launch"):
                replicated = jax.device_put(src,
                                            NamedSharding(self.mesh, P()))
            with trace.span("hvd.exec.complete"):
                for rank, handle in entry.handles.items():
                    handle.set_result(self._shard_for(replicated, rank))
            return

        shape = tuple(entry.shape)
        total = _prod(shape)
        dtype = entry.dtype
        bufs = []
        for rank in self.local_ranks:
            if rank == entry.root_rank:
                bufs.append(self._fuse_in([entry.tensors[rank]], [total],
                                          dtype))
            else:
                bufs.append(self._zeros_buf(total, dtype, rank))
        garr = self._stack(bufs, (1, total), dtype)

        key = ("broadcast", shape, np.dtype(dtype).name)
        fn = self._allreduce_cache.get(key)
        if fn is None:
            axis = self.axis

            def fused(g):
                def body(shard):
                    x = shard
                    # pred/int psum: sum of one real row + zeros is exact
                    if x.dtype == jnp.bool_:
                        x = x.astype(jnp.uint8)
                    out = jax.lax.psum(x, axis)
                    return out.astype(shard.dtype)
                red = _shard_map(body, mesh=self.mesh,
                                 in_specs=P(axis), out_specs=P())(g)
                return red.reshape(shape)

            fn = jax.jit(fused, donate_argnums=0)
            self._allreduce_cache[key] = fn

        with trace.span("hvd.exec.launch"):
            out = fn(garr)
        with trace.span("hvd.exec.complete"):
            for rank, handle in entry.handles.items():
                handle.set_result(self._shard_for(out, rank))

    # ----------------------------------------------------------------- adasum
    def adasum(self, entry):
        """Adasum reduction of one named tensor (reference:
        AdasumMPIAllreduceOp / AdasumGpuAllreduceOp).  Zero stand-ins from
        joined ranks fall out naturally: a zero-norm operand contributes
        plain addition."""
        from horovod_tpu.ops.adasum import (adasum_reduce_hierarchical,
                                            adasum_reduce_stacked)

        shape = tuple(entry.shape)
        total = _prod(shape)
        dtype = entry.dtype
        bufs = []
        for rank in self.local_ranks:
            t = entry.tensors.get(rank)
            if t is None:
                bufs.append(self._zeros_buf(total, dtype, rank))
            else:
                bufs.append(self._fuse_in([t], [total], dtype))
        garr = self._stack(bufs, (1, total), dtype)

        # Hierarchical Adasum (reference: AdasumGpuAllreduceOp — NCCL
        # reduce-scatter intra-node, VHDD across nodes, allgather back)
        # needs a power-of-two cross size for the VHDD pairing tree.  Pinned
        # at init (adasum_hierarchical), NOT autotuned: the two modes
        # combine gradients differently by design.
        hierarchical = bool(
            self.adasum_hierarchical and self.hier_mesh is not None
            and (self.hier_mesh.shape["cross"]
                 & (self.hier_mesh.shape["cross"] - 1)) == 0)
        key = ("adasum", shape, np.dtype(dtype).name, hierarchical)
        fn = self._allreduce_cache.get(key)
        if fn is None:
            if hierarchical:
                def fused(g):
                    def body(shard):
                        return adasum_reduce_hierarchical(
                            shard[0], local_axis="local",
                            cross_axis="cross")[None]
                    return _shard_map_gathered(
                        body, self.hier_mesh,
                        P(("cross", "local")), P())(g).reshape(shape)
            else:
                axis = self.axis

                def fused(g):
                    def body(shard):
                        gathered = jax.lax.all_gather(shard[0], axis)
                        return adasum_reduce_stacked(gathered)
                    return _shard_map_gathered(
                        body, self.mesh, P(axis), P())(g).reshape(shape)

            fn = jax.jit(fused, donate_argnums=0)
            self._allreduce_cache[key] = fn

        with trace.span("hvd.exec.launch"):
            out = fn(garr)
        with trace.span("hvd.exec.complete"):
            for rank, handle in entry.handles.items():
                handle.set_result(self._shard_for(out, rank))

    # --------------------------------------------------------------- alltoall
    def alltoall(self, entry):
        """Variable-split all-to-all as ONE compiled XLA program (API
        parity with later reference versions; also the Ulysses
        sequence-parallel primitive).

        Each rank pads its per-destination segments to the global max
        split, the compiled program runs ``lax.all_to_all`` over the mesh
        axis, and a second compiled program (keyed by the negotiated
        receive splits) slices out the valid rows — the same pad/slice
        trick the variable-dim allgather uses.  Replaces the round-1
        host-orchestrated per-destination ``device_put`` loop.  Sizing
        logic mirrors ``controller.cc:453-518`` recvcounts/displacements.
        """
        num_ranks = self.num_ranks
        splits_matrix = tuple(tuple(int(s) for s in entry.splits[r])
                              for r in range(num_ranks))
        some_local = entry.tensors[self.local_ranks[0]]
        rest = tuple(some_local.shape[1:])
        dtype = entry.dtype
        max_split = max((max(row) if row else 0)
                        for row in splits_matrix) or 1

        key = (splits_matrix, rest, np.dtype(dtype).name)
        fns = self._alltoall_cache.get(key)
        if fns is None:
            axis = self.axis

            def make_pad(row):
                # [sum(row), *rest] -> [1, N, max_split, *rest]
                def pad(t):
                    out = jnp.zeros((num_ranks, max_split) + rest,
                                    dtype=t.dtype)
                    off = 0
                    for dst, n in enumerate(row):
                        if n:
                            seg = jax.lax.slice_in_dim(t, off, off + n,
                                                       axis=0)
                            out = jax.lax.dynamic_update_slice(
                                out, seg[None],
                                (dst, 0) + (0,) * len(rest))
                        off += n
                    return out[None]
                return jax.jit(pad)

            def exchange(g):  # [N, N, max_split, *rest] sharded on axis 0
                def body(shard):
                    return jax.lax.all_to_all(
                        shard[0], axis, split_axis=0, concat_axis=0)[None]
                return _shard_map(body, mesh=self.mesh,
                                  in_specs=P(axis), out_specs=P(axis))(g)

            def make_unpack(recv_row):
                # [N, max_split, *rest] -> [sum(recv_row), *rest]
                def unpack(x):
                    parts = [jax.lax.slice_in_dim(x[src], 0, n, axis=0)
                             for src, n in enumerate(recv_row) if n]
                    if not parts:
                        return jnp.zeros((0,) + rest, dtype=x.dtype)
                    return jnp.concatenate(parts, axis=0)
                return jax.jit(unpack)

            pad_fns = {r: make_pad(splits_matrix[r])
                       for r in self.local_ranks}
            unpack_fns = {
                r: make_unpack(tuple(splits_matrix[src][r]
                                     for src in range(num_ranks)))
                for r in self.local_ranks}
            fns = (pad_fns, jax.jit(exchange, donate_argnums=0),
                   unpack_fns)
            self._alltoall_cache[key] = fns

        pad_fns, exchange_fn, unpack_fns = fns
        bufs = [pad_fns[r](entry.tensors[r]) for r in self.local_ranks]
        garr = self._stack(bufs, (1, num_ranks, max_split) + rest, dtype)
        with trace.span("hvd.exec.launch"):
            out = exchange_fn(garr)
        with trace.span("hvd.exec.complete"):
            for rank, handle in entry.handles.items():
                recv_splits = [splits_matrix[src][rank]
                               for src in range(num_ranks)]
                # [N, max_split, *rest]
                shard = self._shard_for(out, rank)[0]
                handle.set_result((unpack_fns[rank](shard), recv_splits))
