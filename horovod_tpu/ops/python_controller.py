"""Pure-Python coordination loop (fallback / reference controller).

Implements the reference's coordinator protocol (``horovod/common/
controller.cc:62`` ComputeResponseList) for the single-process device-rank
mode: per-rank threads enqueue named requests; a background coordination
thread counts readiness across ranks, validates agreement, fuses compatible
allreduces into buckets and dispatches them to the XLA executor.  The
negotiation that costs the reference 1-2 network round-trips per cycle
(MPI_Gatherv + MPI_Bcast) is process-local here; in multi-process mode the
native TCP controller plays that role.

Also hosts the reference's auxiliary semantics:

- **Join** (``controller.cc:219-221,263-273``): joined ranks stop
  contributing; allreduces proceed with zero stand-ins; the join handle
  completes when every rank has joined.
- **StallInspector** (``stall_inspector.cc``): warn when some ranks submitted
  a tensor and others didn't for longer than the stall window; optionally
  shut down.
- **ResponseCache** (``response_cache.cc``): steady-state tensors whose
  signature (type/dtype/shape/op/root/scales) is unchanged since the last
  cycle skip cross-rank validation entirely; stalled names are evicted
  (reference: ``stall_inspector.cc`` InvalidateStalledCachedTensors).
- **Timeline** phases NEGOTIATE_* / op activities.
"""

import dataclasses
import threading
import time

import numpy as np

from horovod_tpu.common.fusion import plan_buckets
from horovod_tpu.common.handles import HvdAbortedError
from horovod_tpu.common.ops_enum import ReduceOp, RequestType
from horovod_tpu.common.response_cache import SignatureCache
from horovod_tpu.utils import trace
from horovod_tpu.utils.logging import get_logger


@dataclasses.dataclass
class EagerRequest:
    rank: int
    req_type: RequestType
    name: str
    tensor: object  # committed jax.Array (None for join)
    handle: object
    op: ReduceOp = ReduceOp.SUM
    root_rank: int = -1
    prescale_factor: float = 1.0
    postscale_factor: float = 1.0
    splits: list | None = None
    compression: str = "none"
    schedule: str = "auto"
    # process-group scoping (docs/groups.md): "" is the world; a group
    # id makes the group part of the negotiation identity — entries,
    # signatures and fusion buckets are all group-qualified, so
    # cross-group requests can never meet, fuse, or cache-collide
    group: str = ""
    group_ranks: tuple | None = None

    def signature(self):
        """Everything validation checks, flattened into a hashable key
        (reference: ``response_cache.h:45`` — cache key is tensor name +
        params)."""
        # sig-exempt: ring — the ring flag is tcp-transport-local wire
        # negotiation; the in-process plane executes through XLA and
        # has no ring path to disagree about

        tensor = self.tensor
        shape = tuple(tensor.shape) if tensor is not None else None
        dtype = np.dtype(tensor.dtype).name if tensor is not None else None
        return (self.req_type, dtype, shape, self.op, self.root_rank,
                self.prescale_factor, self.postscale_factor,
                tuple(self.splits) if self.splits is not None else None,
                self.compression, self.schedule, self.group,
                self.group_ranks)


class _NameEntry:
    __slots__ = ("first_ts", "req_type", "requests", "stall_warned",
                 "group", "group_ranks")

    def __init__(self, req_type, group="", group_ranks=None):
        self.first_ts = time.monotonic()
        self.req_type = req_type
        self.requests = {}
        self.stall_warned = False
        self.group = group
        self.group_ranks = group_ranks


class GroupEntry:
    """One named tensor inside a fused response — the executor's unit of
    work (reference: TensorTableEntry, common.h:233-250)."""

    __slots__ = ("name", "shape", "dtype", "tensors", "handles", "root_rank",
                 "splits", "op", "prescale_factor", "postscale_factor",
                 "all_dims0", "compression", "schedule", "group",
                 "group_ranks")

    def __init__(self, name, shape, dtype, tensors, handles, root_rank=-1,
                 splits=None, op=ReduceOp.SUM, prescale_factor=1.0,
                 postscale_factor=1.0, all_dims0=None, compression="none",
                 schedule="auto", group="", group_ranks=None):
        self.name = name
        self.shape = shape
        self.dtype = dtype
        self.tensors = tensors
        self.handles = handles
        self.root_rank = root_rank
        self.splits = splits
        self.op = op
        self.prescale_factor = prescale_factor
        self.postscale_factor = postscale_factor
        self.all_dims0 = all_dims0
        self.compression = compression
        self.schedule = schedule
        self.group = group
        self.group_ranks = group_ranks


class PythonController:
    def __init__(self, topology, executor, timeline, config):
        self._topo = topology
        self._executor = executor
        self._timeline = timeline
        self._config = config
        self._size = topology.size
        self._lock = threading.Lock()
        self._wakeup = threading.Event()
        self._queue = []
        self._table = {}  # name -> _NameEntry, insertion-ordered
        self._joined = set()
        self._joined_view = set()  # per-cycle snapshot, coordinator-only
        self._join_handles = {}
        self._running = False
        self._shutdown_error = None
        self._abort_request = None  # (origin_rank, reason), loop-applied
        self._thread = None
        self._log = get_logger()
        self._sig_cache = SignatureCache(
            getattr(config, "cache_capacity", 1024))
        self._autotune = None
        self._tuned = None   # last applied tuned-parameter dict

    @property
    def cache_hits(self):
        return self._sig_cache.hits

    # ----------------------------------------------------------- producer API
    def start(self):
        if self._owns_autotune():
            from horovod_tpu.ops.autotune import AutotuneManager
            self._autotune = AutotuneManager.create(self._config,
                                                    self._log)
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="hvd-coordinator")
        self._thread.start()

    def _owns_autotune(self):
        """The in-process cycle loop both tunes and applies; the gmesh
        subclass tunes at its metadata coordinator instead."""
        return True

    def tuned_params(self):
        """Current (possibly autotuned) runtime knob values — same
        surface as the native controller (reference: ParameterManager
        values after SynchronizeParameters)."""
        if self._autotune is not None:
            return self._autotune.params()
        if self._tuned is not None:
            return dict(self._tuned)
        from horovod_tpu.ops.autotune import default_params
        return default_params(self._config)

    def _apply_tuned(self, params):
        """Apply a tuned-parameter set to this process's knobs (the
        reference applies SynchronizeParameters results the same way:
        config values swap at a cycle boundary) — including the
        categorical choices, which the tuner is actively scoring: the
        executor must really run hierarchically when the candidate says
        so, or every hierarchical sample would measure the flat path."""
        self._tuned = dict(params)
        self._config.fusion_threshold_bytes = \
            params["fusion_threshold_bytes"]
        self._config.cycle_time_ms = params["cycle_time_ms"]
        self._executor.hierarchical_allreduce = \
            params["hierarchical_allreduce"]
        self._executor.hierarchical_allgather = \
            params["hierarchical_allgather"]
        self._sig_cache.enabled = params["cache_enabled"]
        if "compression" in params:
            # the DEFAULT wire compression for allreduces that didn't
            # pass one explicitly; requests already in flight keep the
            # compression they were submitted with
            self._config.compression = params["compression"]
        # ring transfer-engine knobs: inert on the in-process planes,
        # but kept in config so tuned_params() reports one consistent
        # surface across controllers
        if "ring_segment_bytes" in params:
            self._config.ring_segment_bytes = \
                int(params["ring_segment_bytes"])
        if "ring_stripes" in params:
            self._config.ring_stripes = int(params["ring_stripes"])
        if "schedule" in params:
            # the DEFAULT collective schedule stamped on subsequent
            # requests (tcp plane: ring-vs-star choice + coordinator
            # negotiation input); in-flight requests keep theirs
            self._config.schedule = str(params["schedule"])

    def enqueue(self, request: EagerRequest):
        with self._lock:
            if not self._running:
                request.handle.set_error("horovod_tpu has been shut down")
                return
            if self._shutdown_error is not None:
                request.handle.set_error(self._shutdown_error)
                return
            self._queue.append(request)
        self._wakeup.set()

    # req-exempt: JOIN — joins never travel through the collective
    # dispatch; they arrive via this dedicated entry point and fold
    # into negotiation as the joined-rank set (docs/elastic.md)
    def join(self, rank, handle):
        with self._lock:
            self._joined.add(rank)
            self._join_handles[rank] = handle
        self._wakeup.set()

    def shutdown(self):
        with self._lock:
            self._running = False
        self._wakeup.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._autotune is not None:
            self._autotune.close()
            self._autotune = None
        with self._lock:
            for request in self._queue:
                request.handle.set_error("horovod_tpu has been shut down")
            self._queue.clear()
            for entry in self._table.values():
                for request in entry.requests.values():
                    request.handle.set_error(
                        "horovod_tpu has been shut down")
            self._table.clear()

    def request_drain(self) -> bool:
        """Graceful-drain announcement (docs/checkpoint.md): the
        in-process controllers coordinate device ranks inside ONE
        process, so there is no coordinator to notify and no survivor
        set to re-form — a preemption notice here simply ends the
        process.  Always False (drain impossible)."""
        return False

    # ----------------------------------------------------------------- abort
    def abort(self, origin_rank, reason):
        """Coordinated abort (``hvd.abort()`` / a rank detecting an
        unrecoverable failure): every in-flight and future collective
        fails with one typed ``HvdAbortedError``.  The table is owned by
        the coordination thread, so the abort is recorded here and
        applied at the next cycle boundary — bounded by cycle_time."""
        with self._lock:
            if self._abort_request is None:
                self._abort_request = (origin_rank, reason)
        self._wakeup.set()

    def _apply_abort(self, exc):
        """Fail everything in flight with the typed error and poison the
        controller so later enqueues fail fast (coordination-thread
        context — the only legal place to touch the table)."""
        self._log.error(str(exc))
        with self._lock:
            self._shutdown_error = exc
            queued, self._queue = self._queue, []
            join_handles = dict(self._join_handles)
            self._join_handles.clear()
            self._joined.clear()
            # a signature validated before the abort must not satisfy a
            # post-abort (or post-reconfiguration) round of the same name
            self._sig_cache.clear()
        for request in queued:
            request.handle.set_error(exc)
        for handle in join_handles.values():
            handle.set_error(exc)
        self._fail_all(exc)

    # ------------------------------------------------------- coordinator loop
    def _loop(self):
        while True:
            # re-read each cycle: autotune retunes cycle_time_ms live
            cycle_s = self._config.cycle_time_ms / 1000.0
            self._wakeup.wait(timeout=cycle_s)
            self._wakeup.clear()
            with self._lock:
                if not self._running:
                    return
                pending, self._queue = self._queue, []
                abort_req, self._abort_request = self._abort_request, None
            if abort_req is not None:
                for request in pending:
                    request.handle.set_error(HvdAbortedError(*abort_req))
                self._apply_abort(HvdAbortedError(*abort_req))
                continue
            self._timeline.mark_cycle()
            try:
                self._run_cycle(pending)
            except Exception as exc:  # noqa: BLE001 — never kill the loop
                self._log.error("coordinator cycle failed: %s", exc)
                self._fail_all(str(exc))

    def _fail_all(self, message):
        for entry in self._table.values():
            for request in entry.requests.values():
                request.handle.set_error(message)
        self._table.clear()

    def _absorb(self, pending):
        """Absorb new requests into the message table (reference:
        TensorQueue pop + table insert).  The table key is
        (group, name): same-named tensors from different groups are
        DIFFERENT negotiations and must never meet in one entry."""
        for request in pending:
            key = (getattr(request, "group", ""), request.name)
            entry = self._table.get(key)
            if entry is None:
                entry = _NameEntry(request.req_type,
                                   group=key[0],
                                   group_ranks=getattr(
                                       request, "group_ranks", None))
                self._table[key] = entry
                self._timeline.begin(
                    request.name, f"NEGOTIATE_{request.req_type.name}")
            if request.rank in entry.requests:
                request.handle.set_error(
                    f"duplicate request for tensor '{request.name}' from "
                    f"rank {request.rank} before previous one completed")
                continue
            entry.requests[request.rank] = request
            self._timeline.instant(request.name, f"{request.rank}")

    def _run_cycle(self, pending):
        # snapshot joined state once per cycle (rank threads mutate it under
        # the lock; iterating the live set would race)
        with self._lock:
            self._joined_view = set(self._joined)

        # 1. absorb new requests into the message table
        self._absorb(pending)

        # 2. stall inspection
        if not self._config.stall_check_disable:
            self._check_stalls()

        # 2b. cross-group concurrency gauge (docs/groups.md): distinct
        # groups with entries open right now — read by the acceptance
        # tests to assert concurrency rather than assume it
        if self._table:
            from horovod_tpu import groups as groups_mod
            groups_mod.note_inflight(g for (g, _) in self._table)

        # 3. collect ready responses in deterministic (arrival) order.
        # Readiness is per entry: a group entry needs exactly its
        # member ranks (no join stand-ins — joins are a world-level
        # protocol), the world needs every non-joined rank.
        ready_keys = []
        world_needed = set(range(self._size)) - self._joined_view
        for key, entry in self._table.items():
            needed = (set(entry.group_ranks) if entry.group
                      else world_needed)
            if needed.issubset(entry.requests.keys()):
                ready_keys.append(key)

        responses = []
        for key in ready_keys:
            entry = self._table.pop(key)
            _, name = key
            self._timeline.end(name)
            if self._cache_check(key, entry):
                group = self._build_group(name, entry)
            else:
                group = self._construct_response(name, entry)
                if group is not None:
                    self._cache_store(key, entry)
            if group is not None:
                responses.append((entry.req_type, group))

        # 4. fuse + dispatch
        self._dispatch(responses)

        # 4b. feed the tuner (rank-0-analog: this process IS the
        # coordinator) and apply any retuned knobs at this cycle
        # boundary
        if self._autotune is not None:
            for _, group in responses:
                self._autotune.record(
                    np.dtype(group.dtype).itemsize
                    * int(np.prod(group.shape or (1,))))
            upd = self._autotune.maybe_update()
            if upd is not None:
                _, params = upd
                self._apply_tuned(params)

        # 5. join barrier: everyone joined -> complete join handles with the
        # last rank to join (dict preserves join-call order)
        with self._lock:
            if self._joined and len(self._joined) == self._size \
                    and not self._table and not self._queue:
                last = next(reversed(self._join_handles))
                for handle in self._join_handles.values():
                    handle.set_result(last)
                self._join_handles.clear()
                self._joined.clear()

    # ---------------------------------------------------------- response cache
    @staticmethod
    def _cache_key(key):
        """Group-qualified response-cache name: a group's validated
        signature must never satisfy the world's (or another group's)
        entry of the same tensor name."""
        group, name = key
        return f"g:{group}:{name}" if group else name

    def _cache_check(self, key, entry) -> bool:
        """Fast path (reference: ``response_cache.cc`` HIT): every rank's
        request carries the same signature as the last validated cycle for
        this name — skip validation.  Never taken while ranks have joined
        (zero stand-ins change response construction)."""
        if self._joined_view:
            return False
        return self._sig_cache.check(
            self._cache_key(key),
            (r.signature() for r in entry.requests.values()))

    def _cache_store(self, key, entry):
        self._sig_cache.store(
            self._cache_key(key),
            (r.signature() for r in entry.requests.values()))

    @staticmethod
    def resolve_group_compression(compressions):
        """Cross-rank compression resolution: unanimous choice wins,
        disagreement resolves to "none" (exact) rather than erroring —
        an autotune publication applying at slightly different times on
        different ranks must not kill in-flight collectives (same spirit
        as the tcp coordinator resolving ring-vs-payload)."""
        comps = set(compressions)
        return comps.pop() if len(comps) == 1 else "none"

    @staticmethod
    def resolve_group_schedule(schedules):
        """Cross-rank collective-schedule resolution, same contract as
        the compression resolver: unanimous choice wins, disagreement —
        e.g. a tuned schedule applying at slightly different times on
        different ranks — resolves to "auto" (the coordinator then
        picks) rather than erroring."""
        scheds = set(schedules)
        return scheds.pop() if len(scheds) == 1 else "auto"

    def _build_group(self, name, entry):
        """Build the executor GroupEntry from an already-validated (or
        cache-hit) table entry."""
        requests = entry.requests
        any_req = next(iter(requests.values()))
        gid = getattr(entry, "group", "")
        granks = getattr(entry, "group_ranks", None)
        if gid:
            # group entries are re-keyed to GROUP-LOCAL ranks: the
            # executor that runs them is the group's sub-executor
            # (devices[granks]), whose world is 0..len(granks)-1
            order = list(granks)
            tensors = {order.index(rank): r.tensor
                       for rank, r in requests.items()}
            handles = {order.index(rank): r.handle
                       for rank, r in requests.items()}
            root = (order.index(any_req.root_rank)
                    if any_req.root_rank in order else any_req.root_rank)
            splits = {order.index(rank): r.splits
                      for rank, r in requests.items()}
        else:
            tensors = {rank: r.tensor for rank, r in requests.items()}
            for joined_rank in self._joined_view:
                tensors.setdefault(joined_rank, None)
            handles = {rank: r.handle for rank, r in requests.items()}
            root = any_req.root_rank
            splits = {rank: r.splits for rank, r in requests.items()}
        return GroupEntry(
            name=name, shape=tuple(any_req.tensor.shape),
            dtype=any_req.tensor.dtype, tensors=tensors,
            handles=handles,
            root_rank=root,
            splits=splits,
            op=any_req.op, prescale_factor=any_req.prescale_factor,
            postscale_factor=any_req.postscale_factor,
            compression=self.resolve_group_compression(
                r.compression for r in requests.values()),
            schedule=self.resolve_group_schedule(
                getattr(r, "schedule", "auto")
                for r in requests.values()),
            group=gid, group_ranks=granks)

    # ------------------------------------------------------------- validation
    @staticmethod
    def validate_requests(name, requests, *, size, joined):
        """Cross-rank agreement rules (reference: controller.cc:378
        ConstructResponse), shared by the in-process controllers and the
        gmesh controller's local (intra-process) pre-check.  Returns an
        error string or None."""
        types = {r.req_type for r in requests.values()}
        if len(types) > 1:
            return (f"mismatched collective types for tensor '{name}': "
                    f"{sorted(t.name for t in types)}")
        req_type = next(iter(types))

        if joined and req_type in (RequestType.ALLGATHER,
                                   RequestType.BROADCAST,
                                   RequestType.ALLTOALL,
                                   RequestType.REDUCE_SCATTER):
            return (f"{req_type.name} is not supported while ranks have "
                    f"joined")

        dtypes = {np.dtype(r.tensor.dtype).name for r in requests.values()
                  if r.tensor is not None}
        if len(dtypes) > 1:
            return f"mismatched dtypes for tensor '{name}': {sorted(dtypes)}"

        if req_type in (RequestType.ALLREDUCE, RequestType.ADASUM):
            ops = {r.op for r in requests.values()}
            if len(ops) > 1:
                return f"mismatched reduce ops for tensor '{name}'"
            pre = {r.prescale_factor for r in requests.values()}
            post = {r.postscale_factor for r in requests.values()}
            if len(pre) > 1 or len(post) > 1:
                return f"mismatched scale factors for tensor '{name}'"
            shapes = {tuple(r.tensor.shape) for r in requests.values()}
            if len(shapes) > 1:
                return (f"mismatched shapes for allreduce '{name}': "
                        f"{sorted(shapes)}")
        elif req_type == RequestType.ALLGATHER:
            ndims = {r.tensor.ndim for r in requests.values()}
            if len(ndims) > 1:
                return f"mismatched tensor ranks for allgather '{name}'"
            if 0 in ndims:
                return (f"allgather '{name}': 0-d tensors are not "
                        f"supported; reshape to (1,) first")
            trailing = {tuple(r.tensor.shape[1:])
                        for r in requests.values()}
            if len(trailing) > 1:
                return (f"mismatched trailing dimensions for allgather "
                        f"'{name}'")
        elif req_type == RequestType.BROADCAST:
            roots = {r.root_rank for r in requests.values()}
            if len(roots) > 1:
                return f"mismatched root ranks for broadcast '{name}'"
            shapes = {tuple(r.tensor.shape) for r in requests.values()}
            if len(shapes) > 1:
                return f"mismatched shapes for broadcast '{name}'"
        elif req_type == RequestType.REDUCE_SCATTER:
            ops = {r.op for r in requests.values()}
            if len(ops) > 1:
                return f"mismatched reduce ops for tensor '{name}'"
            pre = {r.prescale_factor for r in requests.values()}
            post = {r.postscale_factor for r in requests.values()}
            if len(pre) > 1 or len(post) > 1:
                return f"mismatched scale factors for tensor '{name}'"
            ndims = {r.tensor.ndim for r in requests.values()}
            if 0 in ndims:
                return (f"reduce_scatter '{name}': 0-d tensors are not "
                        f"supported; reshape to (1,) first")
            shapes = {tuple(r.tensor.shape) for r in requests.values()}
            if len(shapes) > 1:
                return (f"mismatched shapes for reduce_scatter '{name}': "
                        f"{sorted(shapes)}")
        elif req_type == RequestType.ALLTOALL:
            for r in requests.values():
                if len(r.splits) != size:
                    return (f"alltoall '{name}': splits must have one "
                            f"entry per rank ({size}), got "
                            f"{len(r.splits)}")
                if sum(r.splits) != r.tensor.shape[0]:
                    return (f"alltoall '{name}': splits sum "
                            f"{sum(r.splits)} != first dimension "
                            f"{r.tensor.shape[0]}")
        return None

    def _construct_response(self, name, entry):
        """Validate cross-rank agreement and build a GroupEntry, or
        error every handle."""
        requests = entry.requests
        granks = getattr(entry, "group_ranks", None)
        message = self.validate_requests(
            name, requests,
            size=(len(granks) if getattr(entry, "group", "") else
                  self._size),
            joined=bool(self._joined_view)
            and not getattr(entry, "group", ""))
        if message is not None:
            for request in requests.values():
                request.handle.set_error(message)
            return None
        return self._build_group(name, entry)

    # ----------------------------------------------------------------- fusion
    @staticmethod
    def allreduce_bucket_key(dtype, op, prescale, postscale,
                             compression="none", schedule="auto",
                             group=""):
        """Bucket-compatibility key shared with the gmesh coordinator
        (reference: FuseResponses fuses dtype/op/scale-homogeneous runs).
        Compression is part of the key: a compressed and an uncompressed
        request must never fuse into one program — they have different
        wire formats and different numerics.  The collective schedule
        likewise: requests negotiated for different schedules must never
        fuse into one bucket (a hierarchical and a flat-ring tensor take
        different data paths with different round structures).  The
        process-group id completes the never-fuse rules: requests from
        different groups reduce over different rank sets and must never
        share a program (docs/groups.md)."""
        return (np.dtype(dtype).name, int(op), prescale, postscale,
                compression, schedule, group)

    def _dispatch(self, responses):
        """Fuse compatible allreduces into <= fusion_threshold buckets
        (reference: controller.cc:640 FuseResponses) and execute."""
        def safe(execute, groups):
            try:
                execute()
            except Exception as exc:  # noqa: BLE001 — surface on handles
                self._log.error("collective execution failed: %s", exc)
                for g in groups:
                    for handle in g.handles.values():
                        handle.set_error(f"collective execution failed: {exc}")

        def key(item):
            req_type, group = item
            if req_type != RequestType.ALLREDUCE:
                return ("single", id(group))  # never fuses
            return self.allreduce_bucket_key(
                group.dtype, group.op, group.prescale_factor,
                group.postscale_factor, group.compression,
                getattr(group, "schedule", "auto"),
                getattr(group, "group", ""))

        def nbytes(item):
            _, group = item
            return (np.dtype(group.dtype).itemsize
                    * int(np.prod(group.shape or (1,))))

        for bucket in plan_buckets(
                responses, key_fn=key, nbytes_fn=nbytes,
                threshold=self._config.fusion_threshold_bytes):
            req_type = bucket[0][0]
            groups = [g for _, g in bucket]
            if req_type == RequestType.ALLREDUCE:
                safe(lambda groups=groups:
                     self._execute_allreduce_bucket(groups), groups)
            else:
                safe(lambda req_type=req_type, g=groups[0]:
                     self._execute_single(req_type, g), groups)

    def _exec_for(self, group_entry):
        """Executor for one response: the shared world executor, or —
        for a process-group entry — the memoized sub-executor over the
        group's device subset (XLA plane: per-(group, signature)
        program caches come for free from the sub-executor's own
        per-signature caches, docs/groups.md)."""
        granks = getattr(group_entry, "group_ranks", None)
        if getattr(group_entry, "group", "") and granks:
            return self._executor.subset(tuple(granks))
        return self._executor

    def _execute_allreduce_bucket(self, groups):
        first = groups[0]
        with trace.span("hvd.execute"):
            trace.executing(groups, trace.now())
            self._timeline_begin_groups(groups, "ALLREDUCE")
            self._exec_for(first).allreduce_fused(
                groups, op=first.op,
                prescale_factor=first.prescale_factor,
                postscale_factor=first.postscale_factor,
                compression=first.compression)
            self._timeline_end_groups(groups)

    def _execute_single(self, req_type, group):
        with trace.span("hvd.execute"):
            trace.executing([group], trace.now())
            self._timeline_begin_groups([group], req_type.name)
            executor = self._exec_for(group)
            if req_type == RequestType.ALLGATHER:
                executor.allgather(group)
            elif req_type == RequestType.BROADCAST:
                executor.broadcast(group)
            elif req_type == RequestType.ALLTOALL:
                executor.alltoall(group)
            elif req_type == RequestType.ADASUM:
                executor.adasum(group)
            elif req_type == RequestType.REDUCE_SCATTER:
                executor.reduce_scatter(group)
            self._timeline_end_groups([group])

    def _timeline_begin_groups(self, groups, phase):
        for g in groups:
            self._timeline.begin(g.name, phase)

    def _timeline_end_groups(self, groups):
        for g in groups:
            self._timeline.end(g.name)

    # ------------------------------------------------------------------ stall
    def _check_stalls(self):
        now = time.monotonic()
        warn_after = self._config.stall_warning_seconds
        shutdown_after = self._config.stall_shutdown_seconds
        for key, entry in list(self._table.items()):
            _, name = key
            expected = (set(entry.group_ranks) if entry.group
                        else set(range(self._size)))
            age = now - entry.first_ts
            if age > warn_after and not entry.stall_warned:
                ready = sorted(entry.requests.keys())
                missing = sorted(expected - set(ready)
                                 - self._joined_view)
                self._log.warning(
                    "One or more tensors were submitted to be reduced, "
                    "gathered or broadcasted by subset of ranks and are "
                    "waiting for remainder of ranks for more than %ds. "
                    "Stalled tensor: %s ready ranks: %s, waiting on: %s",
                    int(warn_after), name, ready, missing)
                entry.stall_warned = True
                # reference: stall_inspector.cc InvalidateStalledCachedTensors
                self._sig_cache.evict(self._cache_key(key))
            if shutdown_after > 0 and age > shutdown_after:
                # promoted from a log line into a coordinated abort: one
                # typed error on every rank, naming the first lagging
                # rank as the origin — group-scoped entries stamp the
                # lagging GROUP member, and the abort still fails the
                # whole job (docs/groups.md: no half-dead jobs)
                missing = sorted(expected
                                 - set(entry.requests.keys())
                                 - self._joined_view)
                origin = missing[0] if missing else -1
                self._apply_abort(HvdAbortedError(
                    origin,
                    f"stalled tensor '{name}' exceeded shutdown "
                    f"threshold of {shutdown_after}s (waiting on ranks "
                    f"{missing})"))
                return
