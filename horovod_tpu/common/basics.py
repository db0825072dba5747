"""Process model and global state: ``init`` / ``rank`` / ``size`` / ...

The reference implements this as ctypes calls into the C core
(``horovod/common/basics.py:22`` HorovodBasics over ``operations.cc:663-797``).
Here the state is Python-owned; the native core (when built) plugs in as the
controller implementation underneath.

Two operating modes (see ``horovod_tpu/common/topology.py``):

- **device-rank** (default): every addressable JAX device is a logical rank.
  Per-rank user code runs on threads — ``run_parallel(fn)`` mirrors the
  reference's test pattern of executing the same rank-parameterized function
  on every rank.
- **process-rank**: ``hvdrun`` wired the ``HVD_RANK``/... env contract; one
  process per worker.
"""

import contextlib
import threading

from horovod_tpu.common import topology as topology_mod
from horovod_tpu.common.config import Config
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils import trace
from horovod_tpu.utils.logging import get_logger
from horovod_tpu.utils.timeline import Timeline

_state = None
_state_lock = threading.Lock()
_tls = threading.local()


class _GlobalState:
    def __init__(self, topology, devices, config, executor, controller,
                 timeline):
        self.topology = topology
        self.devices = devices
        self.config = config
        self.executor = executor
        self.controller = controller
        self.timeline = timeline
        # Elastic membership (docs/elastic.md): ``worker_id`` is this
        # process's STABLE identity — the launcher-assigned initial rank,
        # never rewritten by reconfiguration (fault-injection determinism
        # and log attribution key off it).  ``rank`` is merely this
        # worker id's current position in the membership list.
        self.worker_id = topology.rank if topology.mode == "process" else 0
        self.epoch = 0


def _make_executor(config, devices):
    """Build the XLA data plane ``config.executor`` selects: ``"psum"``
    is the flat hvd-axis :class:`XlaExecutor`; ``"mesh"`` the
    NamedSharding :class:`MeshExecutor` over the ``parallel.mesh``
    dp-axis vocabulary (docs/sharding.md)."""
    if config.executor == "mesh":
        from horovod_tpu.sharding.mesh_executor import MeshExecutor
        executor = MeshExecutor(devices)
    else:
        from horovod_tpu.ops.xla_executor import XlaExecutor
        executor = XlaExecutor(devices)
    executor.hierarchical_allreduce = config.hierarchical_allreduce
    executor.hierarchical_allgather = config.hierarchical_allgather
    executor.adasum_hierarchical = config.adasum_hierarchical
    return executor


def init(comm=None, controller=None):
    """Initialize horovod_tpu.

    ``comm`` is accepted for API parity with the reference (an MPI
    communicator there); passing a list of jax devices restricts the rank set
    to those devices.
    """
    global _state
    with _state_lock:
        if _state is not None:
            return
        import jax  # deferred so env vars set before init still apply

        config = Config.from_env()
        if controller:
            config.controller = controller

        # deterministic fault injection (docs/fault_tolerance.md): arm
        # the process-wide injector before any controller/transport code
        # runs, keyed by this process's launcher rank
        from horovod_tpu.common import faults
        faults.configure(config.fault_spec,
                         rank=env_util.get_int(env_util.HVD_RANK, 0))

        env_topology = topology_mod.from_env()
        explicit = (controller or
                    env_util.get_str(env_util.HVD_CONTROLLER))
        use_global_mesh = (
            env_topology is not None and env_topology.size > 1
            and (env_util.get_bool(env_util.HVD_GLOBAL_MESH)
                 or explicit == "gmesh"))
        if use_global_mesh:
            # pod mode (hvdrun --tpu / --global-mesh): every process joins
            # one jax.distributed runtime; each chip is a logical rank;
            # the data plane is compiled XLA collectives over the GLOBAL
            # mesh (reference: gloo_context.cc:56-73 full-mesh rendezvous,
            # replaced by the jax coordinator + GSPMD).
            from horovod_tpu.common import distributed as dist_mod
            dist_mod.initialize_jax_distributed(
                env_topology.rank, env_topology.size)
            local = list(jax.local_devices())
            devices = sorted(
                jax.devices(),
                key=lambda d: (getattr(d, "process_index", 0), d.id))
            if len(devices) != len(local) * env_topology.size:
                raise RuntimeError(
                    f"heterogeneous device counts: {len(devices)} global "
                    f"devices across {env_topology.size} processes with "
                    f"{len(local)} local — global-mesh mode requires the "
                    f"same chip count on every host")
            topology = topology_mod.from_devices(
                local, env_topology.rank, env_topology.size)
            config.controller = "gmesh"
        elif env_topology is not None and env_topology.size > 1:
            # process-rank mode: collectives go through the TCP controller
            # (the reference's Gloo configuration).  The native/python
            # controllers coordinate a single process's device ranks and
            # cannot span processes — an explicit request for them here is
            # a configuration error, not something to override silently.
            if explicit and explicit != "tcp":
                raise RuntimeError(
                    f"HVD_CONTROLLER={explicit} cannot coordinate "
                    f"{env_topology.size} processes; multi-process jobs "
                    f"use the tcp controller (the in-process controllers "
                    f"only coordinate device ranks within one process)")
            topology = env_topology
            devices = jax.local_devices()
            config.controller = "tcp"
        elif isinstance(comm, (list, tuple)) and comm:
            devices = list(comm)
            topology = topology_mod.from_devices(devices, 0, 1)
        else:
            devices = jax.local_devices()
            topology = topology_mod.from_devices(
                devices, jax.process_index(), jax.process_count())

        executor = _make_executor(config, devices)

        timeline = None
        impl = None
        if config.controller == "gmesh":
            from horovod_tpu.ops.global_controller import \
                GlobalMeshController
            # per-process timeline file; rank-0 aggregation via the
            # launcher-side merge (utils/timeline.py)
            path = config.timeline_path
            if path:
                path = f"{path}.rank{topology.cross_rank}"
            timeline = Timeline(path, config.timeline_mark_cycles)
            impl = GlobalMeshController(topology, executor, timeline,
                                        config)
        elif config.controller == "tcp":
            from horovod_tpu.ops.tcp_controller import TcpController
            # per-rank trace file; rank 0 merges all into the base path
            # at shutdown (reference: timeline.cc rank-0 aggregation)
            path = config.timeline_path
            if path:
                path = f"{path}.rank{topology.rank}"
            timeline = Timeline(path, config.timeline_mark_cycles)
            impl = TcpController(topology, executor, timeline, config)
        elif config.controller == "native":
            try:
                from horovod_tpu.ops.native_controller import NativeController
                impl = NativeController(topology, executor, None, config)
                # the native core writes the timeline itself
                timeline = Timeline(None)
            except (ImportError, OSError) as exc:
                # loud: whoever measures the eager plane must know which
                # controller served it
                get_logger().warning(
                    "native core unavailable (%s); the python controller "
                    "serves this process instead", exc)
        if impl is None:
            timeline = Timeline(config.timeline_path,
                                config.timeline_mark_cycles)
            if topology.size > len(devices):
                raise RuntimeError(
                    f"topology spans {topology.size} ranks but only "
                    f"{len(devices)} devices are addressable in this "
                    f"process; multi-process collectives require the tcp "
                    f"controller (launch with hvdrun)")
            from horovod_tpu.ops.python_controller import PythonController
            impl = PythonController(topology, executor, timeline, config)
        impl.start()

        _state = _GlobalState(topology, devices, config, executor, impl,
                              timeline)
        # a fresh world must not inherit the previous job's process
        # groups (docs/groups.md): the registry belongs to ONE init
        from horovod_tpu import groups as groups_mod
        groups_mod.reset()
        # and hvd.eager_stats() counts from here
        trace.reset()
        _maybe_install_drain(config)


def _maybe_install_drain(config):
    """Arm the SIGTERM→graceful-drain handler (docs/checkpoint.md) when
    the runtime can actually honor it: multi-process tcp jobs with
    ``HVD_TPU_DRAIN`` on.  Elsewhere SIGTERM keeps its default (kill)
    disposition — a single process has nobody to announce departure to,
    and the in-process controllers have no coordinator."""
    if not (config.drain and config.controller == "tcp"
            and _state is not None and _state.topology.mode == "process"
            and _state.topology.size > 1):
        return
    from horovod_tpu.common import drain as drain_mod
    # resolved at signal time: reconfiguration replaces the controller
    drain_mod.install(
        lambda: _state.controller if _state is not None else None)


def _drained_teardown():
    """Quietly dismantle this process's runtime after a granted drain:
    the rank has already left the membership, so there are no job-end
    barriers to run — close transports, flush the timeline, drop the
    global state so atexit paths see an uninitialized runtime."""
    global _state
    with _state_lock:
        if _state is None:
            return
        try:
            _state.controller.close_for_reconfig()
        except Exception:  # noqa: BLE001 — leaving a world that has
            # already reconfigured past us
            get_logger().debug("drain teardown error", exc_info=True)
        try:
            _state.timeline.close()
        except Exception:  # noqa: BLE001 — best-effort flush
            get_logger().debug("drain timeline close error",
                               exc_info=True)
        _state = None


def shutdown():
    global _state
    with _state_lock:
        if _state is None:
            return
        _state.controller.shutdown()
        _state.timeline.close()
        _state = None
    from horovod_tpu import groups as groups_mod
    groups_mod.reset()


def worker_id() -> int:
    """This process's stable elastic identity (the launcher-assigned
    initial rank; unchanged by reconfiguration)."""
    return _get_state().worker_id


def members() -> list:
    """Current worker-id list in rank order: position r holds the
    stable worker id serving rank r at this membership epoch (identity
    before any elastic reconfiguration).  Process groups record THESE
    ids, so their rank-specs survive renumbering (docs/groups.md)."""
    state = _get_state()
    m = getattr(state.controller, "_members", None)
    return list(m) if m is not None else list(range(state.topology.size))


def _elastic_reinit(epoch, members):
    """Move this surviving process to a new membership epoch
    (docs/elastic.md): tear down the current-generation controller (no
    job-end barriers — the job is not ending), re-key rank/size from
    this worker's position in the new membership, and gang-start a
    fresh TcpController under the epoch's rendezvous scopes — which
    rebuilds the ring topology and stripe connections from scratch."""
    global _state
    import dataclasses

    with _state_lock:
        state = _get_state()
        wid = state.worker_id
        if wid not in members:
            raise ValueError(
                f"worker {wid} is not part of membership {members}")
        if epoch <= state.epoch:
            return  # stale directive: this process already moved on
        try:
            state.controller.close_for_reconfig()
        except Exception:  # noqa: BLE001 — tearing down a dead world
            get_logger().debug("reconfig teardown error", exc_info=True)
        new_rank = members.index(wid)
        new_size = len(members)
        # the global and local axes are re-keyed densely; the cross axis
        # keeps its launch value (single-host elastic — see docs)
        topology = dataclasses.replace(
            state.topology, rank=new_rank, size=new_size,
            local_rank=new_rank, local_size=new_size)
        from horovod_tpu.ops.tcp_controller import TcpController
        impl = TcpController(topology, state.executor, state.timeline,
                             state.config, epoch=epoch,
                             members=list(members))
        impl.start()
        state.topology = topology
        state.controller = impl
        state.epoch = epoch
        # re-form EVERY process group for the new membership
        # (docs/groups.md): a group is a pure function of (spec,
        # members) — grids re-plan over the survivors, explicit rank
        # lists with a dead worker turn typed-unsatisfiable
        from horovod_tpu import groups as groups_mod
        groups_mod.reform(list(members))
        get_logger().warning(
            "elastic: worker %d re-formed at epoch %d as rank %d/%d",
            wid, epoch, new_rank, new_size)


def _elastic_join_init(epoch, members):
    """Initialize a late-joining worker directly at an admitted
    membership epoch (it never belonged to epoch 0; a plain ``init()``
    would gang-start against the dead world's rendezvous scope)."""
    global _state
    with _state_lock:
        if _state is not None:
            raise RuntimeError(
                "horovod_tpu is already initialized; joiners call "
                "hvd.elastic.wait_for_membership() INSTEAD of hvd.init()")
        import jax

        config = Config.from_env()
        config.controller = "tcp"
        from horovod_tpu.common import faults
        wid = env_util.get_int(env_util.HVD_RANK, 0)
        faults.configure(config.fault_spec, rank=wid)
        new_rank = members.index(wid)
        topology = topology_mod.Topology(
            rank=new_rank, size=len(members),
            local_rank=new_rank, local_size=len(members),
            cross_rank=0, cross_size=1, mode="process")
        devices = jax.local_devices()
        executor = _make_executor(config, devices)
        path = config.timeline_path
        if path:
            path = f"{path}.rank{wid}"
        timeline = Timeline(path, config.timeline_mark_cycles)
        from horovod_tpu.ops.tcp_controller import TcpController
        impl = TcpController(topology, executor, timeline, config,
                             epoch=epoch, members=list(members))
        impl.start()
        _state = _GlobalState(topology, devices, config, executor, impl,
                              timeline)
        _state.worker_id = wid
        _state.epoch = epoch
        # same init-boundary rule as init(): a joiner's fresh world must
        # not inherit a previous job's process groups (docs/groups.md)
        from horovod_tpu import groups as groups_mod
        groups_mod.reset()
        _maybe_install_drain(config)
        get_logger().warning(
            "elastic: worker %d joined at epoch %d as rank %d/%d",
            wid, epoch, new_rank, len(members))


def is_initialized() -> bool:
    return _state is not None


def abort(reason="aborted by user"):
    """Broadcast a coordinated abort for the in-flight collective round
    (docs/fault_tolerance.md).

    Every rank — including ranks currently blocked inside a collective —
    purges its in-flight ring state and raises
    :class:`horovod_tpu.HvdAbortedError` (naming this rank as the
    origin) within ``HVD_TPU_ABORT_TIMEOUT``.  Use it when this rank
    detects an unrecoverable condition (corrupt batch, failed health
    check) and the whole job must unwind symmetrically instead of
    leaving peers hanging in a half-finished round.
    """
    state = _get_state()
    do_abort = getattr(state.controller, "abort", None)
    if do_abort is None:
        raise NotImplementedError(
            f"controller {state.config.controller!r} does not support "
            f"coordinated abort")
    do_abort(rank(), reason)


def _get_state() -> _GlobalState:
    if _state is None:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call hvd.init() first")
    return _state


# ----------------------------------------------------------- rank model -----
@contextlib.contextmanager
def rank_context(local_rank: int):
    """Bind the calling thread to a logical rank (device-rank mode)."""
    previous = getattr(_tls, "local_rank", None)
    _tls.local_rank = local_rank
    try:
        yield
    finally:
        _tls.local_rank = previous


def _current_local_rank() -> int:
    return getattr(_tls, "local_rank", None) or 0


def rank() -> int:
    state = _get_state()
    topo = state.topology
    if topo.mode == "process":
        return topo.rank
    return topo.cross_rank * topo.local_size + _current_local_rank()


def size() -> int:
    return _get_state().topology.size


def local_rank() -> int:
    state = _get_state()
    if state.topology.mode == "process":
        return state.topology.local_rank
    return _current_local_rank()


def local_size() -> int:
    return _get_state().topology.local_size


def cross_rank() -> int:
    return _get_state().topology.cross_rank


def cross_size() -> int:
    return _get_state().topology.cross_size


def mesh():
    """The 1-D jax Mesh over all logical ranks (axis name ``"hvd"``)."""
    return _get_state().executor.mesh


def local_device():
    """The jax device backing this logical rank's compute.

    Process-rank (tcp) jobs use this to run jitted steps on their own
    accelerator while gradients ride the eager collectives — the
    reference's one-GPU-per-process pattern (VERDICT r1 #7: process mode
    must use the chips)."""
    state = _get_state()
    devices = state.executor.devices
    # the within-host index, NOT the global rank: with non-block rank
    # placement rank() % len(devices) can double-book one chip and
    # leave another idle
    return devices[local_rank() % len(devices)]


def run_parallel(fn, num_ranks=None):
    """Run ``fn`` once per logical rank on separate threads and return the
    per-rank results.  ``fn`` may take zero args or the rank as one arg.

    This is the device-rank analog of the reference's "same script on every
    rank" execution model (SURVEY §4): inside ``fn``, ``hvd.rank()`` etc.
    reflect the calling thread's rank.
    """
    import inspect

    state = _get_state()
    n = num_ranks or state.topology.local_size
    results = [None] * n
    errors = [None] * n
    wants_rank = len(inspect.signature(fn).parameters) >= 1

    def worker(r):
        with rank_context(r):
            try:
                results[r] = fn(r) if wants_rank else fn()
            except BaseException as exc:  # noqa: BLE001 — reraised below
                errors[r] = exc

    threads = [threading.Thread(target=worker, args=(r,), daemon=True,
                                name=f"hvd-rank-{r}")
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for exc in errors:
        if exc is not None:
            raise exc
    return results


# ------------------------------------------------------ capability probes ---
def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def xla_enabled() -> bool:
    return True


def ccl_built() -> bool:
    """oneCCL backend probe (reference: ``basics.py`` ``ccl_built``) —
    always False: the five comm backends collapse into the XLA plane."""
    return False


def ddl_built() -> bool:
    """IBM DDL backend probe (reference parity) — always False."""
    return False


def mpi_threads_supported() -> bool:
    """Reference: whether MPI was initialized with THREAD_MULTIPLE.
    There is no MPI data plane here (mpirun only launches workers), so
    this is always False; raises if called before ``init`` like the
    reference does."""
    _get_state()  # raises when not initialized (reference contract)
    return False


def is_homogeneous() -> bool:
    """True when every host runs the same number of ranks (reference:
    ``controller.cc`` ``is_homogeneous_``, exposed on the basics
    surface)."""
    return _get_state().topology.is_homogeneous
