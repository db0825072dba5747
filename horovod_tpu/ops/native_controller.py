"""Native controller: ctypes binding over the C++ coordination core.

The background cycle loop, tensor queue, negotiation, response cache, fusion
planning, stall inspection and timeline live in ``csrc/hvd`` (the reference
keeps the same responsibilities in C++: ``horovod/common/operations.cc``,
``controller.cc``).  This module is the thin producer/dispatcher glue:

- rank threads encode metadata requests and hand them to the core
  (``hvd_core_enqueue``); tensors and completion handles stay Python-side,
  keyed by request id;
- one dispatcher thread blocks in ``hvd_core_next_batch`` (GIL released by
  ctypes) and executes each fused ResponseBatch as compiled XLA programs via
  the shared :class:`XlaExecutor`, then reports ``hvd_core_mark_done`` so the
  core can close timeline spans and maintain its cache.
"""

import ctypes
import itertools
import os
import threading

from horovod_tpu.common import wire
from horovod_tpu.common.ops_enum import ReduceOp, ResponseType
from horovod_tpu.ops.python_controller import GroupEntry, PythonController
from horovod_tpu.utils import trace
from horovod_tpu.utils.logging import get_logger

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                         "lib", "libhvdcore.so")


def _build_lib():
    """Build libhvdcore.so in-tree when absent (fresh checkouts don't ship
    binaries; the reference likewise compiles its core at install time,
    reference: setup.py:47-52).

    Multiple ranks on one host may race here on first launch, so the
    existence check and the build run under an exclusive flock; everyone
    re-checks after acquiring it.
    """
    import fcntl
    import subprocess

    csrc = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "csrc")
    if not os.path.isdir(csrc):
        raise OSError(
            f"{_LIB_PATH} is missing and cannot be built automatically "
            f"(no csrc/ tree next to the package); build libhvdcore.so "
            f"with `make -C csrc` from a source checkout")
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    lock_path = os.path.join(os.path.dirname(_LIB_PATH), ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not _lib_stale():
            return
        try:
            proc = subprocess.run(["make", "-C", csrc],
                                  capture_output=True, text=True)
        except FileNotFoundError:
            raise OSError(
                f"{_LIB_PATH} is missing and `make` is not on PATH; "
                f"build it with `make -C {csrc}`")
        if proc.returncode != 0:
            raise OSError(
                f"building libhvdcore.so failed (make -C {csrc}):\n"
                f"{proc.stdout}\n{proc.stderr}")


def _lib_stale():
    """True when any csrc source is newer than the built library."""
    if not os.path.exists(_LIB_PATH):
        return True
    csrc = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "csrc")
    if not os.path.isdir(csrc):
        return False
    lib_mtime = os.path.getmtime(_LIB_PATH)
    for root, _, files in os.walk(csrc):
        for f in files:
            if f.endswith((".cc", ".h")) or f == "Makefile":
                if os.path.getmtime(os.path.join(root, f)) > lib_mtime:
                    return True
    return False


def _load_lib():
    if _lib_stale():
        _build_lib()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.hvd_core_create.restype = ctypes.c_void_p
    lib.hvd_core_create.argtypes = [ctypes.c_int]
    lib.hvd_core_start.argtypes = [ctypes.c_void_p]
    lib.hvd_core_shutdown.argtypes = [ctypes.c_void_p]
    lib.hvd_core_finalize.argtypes = [ctypes.c_void_p]
    lib.hvd_core_destroy.argtypes = [ctypes.c_void_p]
    lib.hvd_core_enqueue.restype = ctypes.c_int
    lib.hvd_core_enqueue.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t]
    lib.hvd_core_join.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                  ctypes.c_uint64]
    lib.hvd_core_next_batch.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.hvd_core_next_batch.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_size_t)]
    lib.hvd_core_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    lib.hvd_core_mark_done.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                       ctypes.c_char_p]
    for fn in ("hvd_core_cache_hits", "hvd_core_cache_misses",
               "hvd_core_cache_size"):
        getattr(lib, fn).restype = ctypes.c_uint64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]

    # Autotuned parameter getters (reference: tuned values synchronized by
    # Controller::SynchronizeParameters; here the dispatcher polls).
    lib.hvd_core_param_fusion_bytes.restype = ctypes.c_int64
    lib.hvd_core_param_fusion_bytes.argtypes = [ctypes.c_void_p]
    lib.hvd_core_param_cycle_ms.restype = ctypes.c_double
    lib.hvd_core_param_cycle_ms.argtypes = [ctypes.c_void_p]
    for fn in ("hvd_core_param_hierarchical_allreduce",
               "hvd_core_param_hierarchical_allgather",
               "hvd_core_param_cache_enabled", "hvd_core_autotune_tuning"):
        getattr(lib, fn).restype = ctypes.c_int
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.hvd_core_autotune_best_score.restype = ctypes.c_double
    lib.hvd_core_autotune_best_score.argtypes = [ctypes.c_void_p]

    # Standalone autotune math (GP / BO / ParameterManager), unit-tested
    # against numpy oracles in tests/test_autotune.py.
    dbl_p = ctypes.POINTER(ctypes.c_double)
    lib.hvd_gp_create.restype = ctypes.c_void_p
    lib.hvd_gp_create.argtypes = [ctypes.c_double] * 3
    lib.hvd_gp_destroy.argtypes = [ctypes.c_void_p]
    lib.hvd_gp_fit.restype = ctypes.c_int
    lib.hvd_gp_fit.argtypes = [ctypes.c_void_p, dbl_p, dbl_p, ctypes.c_int,
                               ctypes.c_int]
    lib.hvd_gp_predict.argtypes = [ctypes.c_void_p, dbl_p, ctypes.c_int,
                                   dbl_p, dbl_p]
    lib.hvd_expected_improvement.restype = ctypes.c_double
    lib.hvd_expected_improvement.argtypes = [ctypes.c_double] * 4
    lib.hvd_bo_create.restype = ctypes.c_void_p
    lib.hvd_bo_create.argtypes = [dbl_p, dbl_p, ctypes.c_int, ctypes.c_double,
                                  ctypes.c_int]
    lib.hvd_bo_destroy.argtypes = [ctypes.c_void_p]
    lib.hvd_bo_add_sample.argtypes = [ctypes.c_void_p, dbl_p, ctypes.c_int,
                                      ctypes.c_double]
    lib.hvd_bo_suggest.argtypes = [ctypes.c_void_p, dbl_p, ctypes.c_int]
    lib.hvd_bo_best_y.restype = ctypes.c_double
    lib.hvd_bo_best_y.argtypes = [ctypes.c_void_p]
    lib.hvd_pm_create.restype = ctypes.c_void_p
    lib.hvd_pm_create.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_double, ctypes.c_char_p,
                                  ctypes.c_int64, ctypes.c_double,
                                  ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int64, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.hvd_pm_destroy.argtypes = [ctypes.c_void_p]
    lib.hvd_pm_record.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.hvd_pm_update.restype = ctypes.c_int
    lib.hvd_pm_update.argtypes = [ctypes.c_void_p, ctypes.c_double]
    lib.hvd_pm_fusion_bytes.restype = ctypes.c_int64
    lib.hvd_pm_fusion_bytes.argtypes = [ctypes.c_void_p]
    lib.hvd_pm_cycle_ms.restype = ctypes.c_double
    lib.hvd_pm_cycle_ms.argtypes = [ctypes.c_void_p]
    for fn in ("hvd_pm_hierarchical_allreduce",
               "hvd_pm_hierarchical_allgather", "hvd_pm_cache_enabled",
               "hvd_pm_compression_enabled", "hvd_pm_tuning",
               "hvd_pm_ring_stripes", "hvd_pm_schedule"):
        getattr(lib, fn).restype = ctypes.c_int
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.hvd_pm_ring_segment_bytes.restype = ctypes.c_int64
    lib.hvd_pm_ring_segment_bytes.argtypes = [ctypes.c_void_p]
    lib.hvd_pm_best_score.restype = ctypes.c_double
    lib.hvd_pm_best_score.argtypes = [ctypes.c_void_p]
    return lib


class NativeController:
    def __init__(self, topology, executor, timeline, config):
        # the core writes the timeline itself; the reference is kept
        # only for the grouped-collective companion controller below
        self._timeline = timeline
        self._topo = topology
        self._executor = executor
        self._config = config
        # Grouped collectives (group= on the eager API) carry fields the
        # embedded C++ core's wire format predates; they are routed to a
        # lazily-created in-process PythonController that shares this
        # controller's executor, so group isolation (sub-executors,
        # (group, name) negotiation keys, never-fuse bucket keys) holds
        # without a binary-format change (docs/groups.md).
        self._companion = None
        self._lib = _load_lib()
        self._core = self._lib.hvd_core_create(topology.size)
        self._pending = {}   # req_id -> (EagerRequest-ish record)
        self._joins = {}     # req_id -> handle
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._thread = None
        self._running = False
        self._log = get_logger()

    # ----------------------------------------------------------- producer API
    def start(self):
        self._running = True
        self._lib.hvd_core_start(self._core)
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        daemon=True, name="hvd-dispatcher")
        self._thread.start()

    def _companion_controller(self):
        with self._lock:
            if not self._running:
                return None
            if self._companion is None:
                timeline = self._timeline
                if timeline is None:
                    # the native path passes timeline=None (the core
                    # writes its own trace); the companion needs a real
                    # (no-op) Timeline object
                    from horovod_tpu.utils.timeline import Timeline
                    timeline = Timeline(None)
                companion = PythonController(self._topo, self._executor,
                                             timeline, self._config)
                companion.start()
                self._companion = companion
            return self._companion

    def enqueue(self, request):
        if getattr(request, "group", ""):
            companion = self._companion_controller()
            if companion is None:
                request.handle.set_error("horovod_tpu has been shut down")
                return
            companion.enqueue(request)
            return
        req_id = next(self._ids)
        tensor = request.tensor
        shape = [] if tensor is None else [int(d) for d in tensor.shape]
        payload = wire.encode_request(
            req_id=req_id, rank=request.rank, req_type=int(request.req_type),
            op=int(request.op),
            dtype=None if tensor is None else tensor.dtype,
            root_rank=request.root_rank, prescale=request.prescale_factor,
            postscale=request.postscale_factor, name=request.name,
            shape=shape, splits=request.splits or [])
        err = ctypes.create_string_buffer(1024)
        with self._lock:
            # the core pointer must not be destroyed (shutdown) between
            # the check and the C call — both sides hold this lock
            if not self._running or self._core is None:
                request.handle.set_error("horovod_tpu has been shut down")
                return
            self._pending[req_id] = request
            rc = self._lib.hvd_core_enqueue(self._core, payload,
                                            len(payload), err, len(err))
        if rc != 0:
            with self._lock:
                self._pending.pop(req_id, None)
            request.handle.set_error(err.value.decode() or "enqueue failed")

    def join(self, rank, handle):
        req_id = next(self._ids)
        with self._lock:
            if not self._running or self._core is None:
                handle.set_error("horovod_tpu has been shut down")
                return
            self._joins[req_id] = handle
            self._lib.hvd_core_join(self._core, rank, req_id)

    def shutdown(self):
        if not self._running:
            return
        self._running = False
        with self._lock:
            companion, self._companion = self._companion, None
        if companion is not None:
            companion.shutdown()
        self._lib.hvd_core_shutdown(self._core)
        drained = True
        if self._thread is not None:
            self._thread.join(timeout=10)
            drained = not self._thread.is_alive()
        with self._lock:
            pending = list(self._pending.values())
            self._pending.clear()
            joins = list(self._joins.values())
            self._joins.clear()
        for request in pending:
            request.handle.set_error("horovod_tpu has been shut down")
        for handle in joins:
            handle.set_error("horovod_tpu has been shut down")
        if drained:
            # close the timeline only after the dispatcher drained its
            # last MarkDone (op End events) — closing inside Shutdown
            # raced it; destroy under the lock so no producer thread is
            # mid-C-call on the pointer
            with self._lock:
                self._lib.hvd_core_finalize(self._core)
                self._lib.hvd_core_destroy(self._core)
                self._core = None
        else:
            # a stuck dispatcher may still touch the core; leak it (the
            # pointer stays VALID — nulling it would turn the stuck
            # dispatcher's next C call into a null-pointer crash)
            self._log.warning(
                "dispatcher did not drain within 10s; leaking the core "
                "and leaving the timeline file unfinalized")

    # ------------------------------------------------------------- statistics
    def _require_core(self):
        if self._core is None:
            raise RuntimeError("horovod_tpu has been shut down")
        return self._core

    def cache_stats(self):
        with self._lock:  # core must not be destroyed mid-call
            core = self._require_core()
            return {
                "hits": int(self._lib.hvd_core_cache_hits(core)),
                "misses": int(self._lib.hvd_core_cache_misses(core)),
                "size": int(self._lib.hvd_core_cache_size(core)),
            }

    def tuned_params(self):
        """Current (possibly autotuned) runtime knob values (reference:
        ParameterManager values after SynchronizeParameters)."""
        lib = self._lib
        with self._lock:  # core must not be destroyed mid-call
            core = self._require_core()
            return {
                "fusion_threshold_bytes": int(
                    lib.hvd_core_param_fusion_bytes(core)),
                "cycle_time_ms": float(lib.hvd_core_param_cycle_ms(core)),
                "hierarchical_allreduce": bool(
                    lib.hvd_core_param_hierarchical_allreduce(core)),
                "hierarchical_allgather": bool(
                    lib.hvd_core_param_hierarchical_allgather(core)),
                "cache_enabled": bool(
                    lib.hvd_core_param_cache_enabled(core)),
                # the embedded core's tuner predates the compression
                # knob; the configured value is reported so the params
                # surface stays uniform across controllers
                "compression": getattr(self._config, "compression",
                                       "none"),
                "tuning": bool(lib.hvd_core_autotune_tuning(core)),
                "best_score_bytes_per_sec": float(
                    lib.hvd_core_autotune_best_score(core)),
            }

    # ------------------------------------------------------------- dispatcher
    def _next_batch(self):
        length = ctypes.c_size_t(0)
        # the dispatcher blocked in the core: queue, cycle sleep,
        # negotiation, fusion plan, publish
        with trace.span("hvd.wait_batch"):
            ptr = self._lib.hvd_core_next_batch(self._core,
                                                ctypes.byref(length))
        try:
            return bytes(ctypes.cast(
                ptr, ctypes.POINTER(ctypes.c_uint8 * length.value)).contents)
        finally:
            self._lib.hvd_core_free(ptr)

    def _dispatch_loop(self):
        autotune = bool(self._config.autotune)
        while True:
            batch = self._next_batch()
            with trace.span("hvd.decode"):
                batch_id, is_shutdown, responses = wire.decode_batch(batch)
            if is_shutdown:
                return
            if autotune:
                # Keep the data plane in step with the tuner's categorical
                # choices (reference: tuned values take effect through
                # SynchronizeParameters).
                params = self.tuned_params()
                self._executor.hierarchical_allreduce = \
                    params["hierarchical_allreduce"]
                self._executor.hierarchical_allgather = \
                    params["hierarchical_allgather"]
                autotune = params["tuning"]  # stop polling once pinned
            error = None
            for resp in responses:
                try:
                    with trace.span("hvd.execute"):
                        self._execute_response(resp)
                except Exception as exc:  # noqa: BLE001 — surface on handles
                    self._log.error("collective execution failed: %s", exc)
                    error = str(exc)
                    self._fail_response(resp,
                                        f"collective execution failed: {exc}")
            with trace.span("hvd.mark_done"):
                self._lib.hvd_core_mark_done(
                    self._core, batch_id,
                    error.encode() if error is not None else None)

    def _take(self, req_id):
        with self._lock:
            return self._pending.pop(req_id, None)

    def _fail_response(self, resp, message):
        for _, parts, _, _ in resp["entries"]:
            for _, req_id in parts:
                request = self._take(req_id)
                if request is not None:
                    request.handle.set_error(message)

    def _execute_response(self, resp):
        start = trace.now()
        rtype = ResponseType(resp["type"])

        if rtype == ResponseType.ERROR:
            self._fail_response(resp, resp["error"])
            return

        if rtype == ResponseType.JOIN:
            _, parts, _, last_rank = resp["entries"][0]
            with self._lock:
                handles = [self._joins.pop(req_id, None)
                           for _, req_id in parts]
            for handle in handles:
                if handle is not None:
                    handle.set_result(last_rank)
            return

        groups = []
        for name, parts, joined, root_rank in resp["entries"]:
            requests = {}
            for rank, req_id in parts:
                request = self._take(req_id)
                if request is None:
                    raise RuntimeError(
                        f"lost request {req_id} for tensor '{name}'")
                requests[rank] = request
            any_req = next(iter(requests.values()))
            tensors = {self._local(rank): r.tensor
                       for rank, r in requests.items()}
            for rank in joined:
                tensors[self._local(rank)] = None
            groups.append(GroupEntry(
                name=name, shape=tuple(any_req.tensor.shape),
                dtype=any_req.tensor.dtype, tensors=tensors,
                handles={self._local(rank): r.handle
                         for rank, r in requests.items()},
                root_rank=self._local(root_rank) if root_rank >= 0 else -1,
                splits={self._local(rank): r.splits
                        for rank, r in requests.items()},
                op=ReduceOp(resp["op"]),
                prescale_factor=resp["prescale"],
                postscale_factor=resp["postscale"],
                compression=PythonController.resolve_group_compression(
                    getattr(r, "compression", "none")
                    for r in requests.values())))
        trace.executing(groups, start)

        try:
            if rtype in (ResponseType.ALLREDUCE,):
                # The C++ core's fusion key predates the compression
                # knob, so a fused response can mix wire formats —
                # partition here so compressed and uncompressed entries
                # never execute as one program (each partition is still
                # one compiled XLA program).
                by_comp = {}
                for g in groups:
                    by_comp.setdefault(g.compression, []).append(g)
                for comp, subset in by_comp.items():
                    self._executor.allreduce_fused(
                        subset, op=ReduceOp(resp["op"]),
                        prescale_factor=resp["prescale"],
                        postscale_factor=resp["postscale"],
                        compression=comp)
            elif rtype == ResponseType.ADASUM:
                for g in groups:
                    self._executor.adasum(g)
            elif rtype == ResponseType.ALLGATHER:
                for g in groups:
                    self._executor.allgather(g)
            elif rtype == ResponseType.BROADCAST:
                for g in groups:
                    self._executor.broadcast(g)
            elif rtype == ResponseType.ALLTOALL:
                for g in groups:
                    self._executor.alltoall(g)
            elif rtype == ResponseType.REDUCE_SCATTER:
                # never fused by the core (FuseAndPublish only buckets
                # ALLREDUCE), so each group is its own compiled program
                for g in groups:
                    self._executor.reduce_scatter(g)
            else:
                raise RuntimeError(f"unknown response type {rtype}")
        except Exception as exc:
            # the requests were already popped from _pending, so the
            # caller's _fail_response cannot reach these handles — fail
            # them HERE or every waiting rank thread hangs forever
            for g in groups:
                for handle in g.handles.values():
                    handle.set_error(
                        f"collective execution failed: {exc}")
            raise

    def _local(self, global_rank):
        """Global rank -> executor device index (identical in single-process
        device mode; process mode uses the TCP data plane instead)."""
        return global_rank % self._topo.local_size
