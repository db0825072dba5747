"""``hvdrun`` — the launcher CLI.

Reference: ``horovod/run/runner.py`` — every core tunable is exposed as a
CLI flag mapped onto the worker env contract; hosts come from ``-H`` or a
hostfile; the config file fills in whatever the CLI left unset.  Usage:

    hvdrun -np 4 python train.py
    hvdrun -np 8 -H host1:4,host2:4 python train.py
    hvdrun -np 4 --tpu python train.py      # one process per TPU host
"""

import argparse
import os
import sys

from horovod_tpu.run import allocate as allocate_mod
from horovod_tpu.run import config_parser
from horovod_tpu.run.http_server import RendezvousServer
from horovod_tpu.run.launch import launch_job
from horovod_tpu.utils import env as env_util


def make_parser():
    parser = argparse.ArgumentParser(
        # derive from argv[0]: the launcher answers to both its own
        # name (hvdrun) and the reference's (horovodrun alias)
        prog=os.path.basename(sys.argv[0]) or "hvdrun",
        description="Launch a horovod_tpu distributed job.")
    parser.add_argument("-np", "--num-proc", type=int, default=None,
                        help="Total number of training processes.")
    parser.add_argument("-H", "--hosts", default=None,
                        help="host:slots[,host:slots,...]; default "
                             "localhost with np slots.")
    parser.add_argument("--hostfile", default=None,
                        help="File with one 'hostname slots=N' per line.")
    parser.add_argument("--ssh-port", type=int, default=None)
    parser.add_argument("--mpi-args", default=None,
                        help="Extra arguments appended to the delegated "
                             "mpirun command (--launcher mpirun), e.g. "
                             "--mpi-args='--mca btl_tcp_if_include eth0'")
    parser.add_argument("--launcher", choices=["ssh", "mpirun", "jsrun"],
                        default="ssh",
                        help="Process placement: built-in ssh fan-out "
                             "(default), one mpirun invocation, or jsrun "
                             "on LSF (workers derive ranks from the MPI "
                             "runtime env).")
    parser.add_argument("--tpu", action="store_true",
                        help="TPU pod mode: one process per host; ranks map "
                             "onto pod-slice coordinates and in-process "
                             "chips become the local axis.  Implies "
                             "--global-mesh.")
    parser.add_argument("--global-mesh", action="store_true",
                        help="Join all processes into one jax.distributed "
                             "runtime: every chip is a logical rank and "
                             "collectives run as compiled XLA programs "
                             "over the global mesh (metadata-only control "
                             "plane).")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--version", action="store_true",
                        help="Print the framework version and exit.")
    parser.add_argument("--start-timeout", type=float, default=None,
                        help="Gang-start deadline in seconds: workers "
                             "that cannot reach the rendezvous/"
                             "controller within this window fail with "
                             "a clear message (default 120).")
    parser.add_argument("--output-filename", default=None,
                        help="Directory for per-rank logs: each rank's "
                             "stdout/stderr are captured to "
                             "<dir>/rank.<NN>/stdout|stderr (rank "
                             "zero-padded to the width of np-1) while "
                             "still teeing to the console (reference: "
                             "horovodrun --output-filename).")
    parser.add_argument("--network-interface", default=None,
                        help="NIC name override for the data/control "
                             "plane (maps to HVD_IFACE; default: "
                             "auto-discovered + intersected across "
                             "hosts).")
    parser.add_argument("--config-file", default=None,
                        help="YAML config file (CLI flags take precedence).")

    group = parser.add_argument_group("tunable parameters")
    group.add_argument("--fusion-threshold-mb", type=float, default=None)
    group.add_argument("--cycle-time-ms", type=float, default=None)
    group.add_argument("--cache-capacity", type=int, default=None)
    group.add_argument("--disable-cache", action="store_true",
                       default=None,
                       help="Disable the response cache entirely "
                            "(HVD_CACHE_CAPACITY=0).")
    group.add_argument("--no-hierarchical-allreduce",
                       action="store_true", default=None,
                       help="Force flat allreduce, overriding "
                            "env/config.")
    group.add_argument("--no-hierarchical-allgather",
                       action="store_true", default=None,
                       help="Force flat allgather, overriding "
                            "env/config.")
    group.add_argument("--hierarchical-allreduce", action="store_true",
                       default=None)
    group.add_argument("--hierarchical-allgather", action="store_true",
                       default=None)
    group.add_argument("--hier-local-size", type=int, default=None,
                       help="Ranks per fast (ICI) group for "
                            "hierarchical collectives "
                            "(HVD_HIER_LOCAL_SIZE; default: the "
                            "topology's local size).")
    group.add_argument("--adasum-hierarchical", action="store_true",
                       default=None,
                       help="Opt into the reference's NCCL+MPI-style "
                            "hierarchical Adasum (adasum of per-group "
                            "averages — numerically different from flat "
                            "Adasum)")
    group.add_argument("--compression",
                       choices=["none", "bf16", "fp16", "int8"],
                       default=None,
                       help="Default on-the-wire allreduce compression "
                            "(HVD_TPU_COMPRESSION); int8 is block-scaled "
                            "quantization — see docs/compression.md.")
    group.add_argument("--ring-segment-bytes", type=int, default=None,
                       help="TCP-ring pipeline segment size in bytes "
                            "(HVD_TPU_RING_SEGMENT_BYTES; 0 disables "
                            "segment pipelining — see docs/tuning.md).")
    group.add_argument("--ring-stripes", type=int, default=None,
                       help="Dedicated bulk-data connections per ring "
                            "peer (HVD_TPU_RING_STRIPES); control "
                            "traffic always rides its own connection.")
    group.add_argument("--tcp-ring-threshold", type=int, default=None,
                       help="Payload bytes at/above which tcp-mode "
                            "collectives ride the p2p ring instead of "
                            "the coordinator star "
                            "(HVD_TCP_RING_THRESHOLD, default 1 MB).")
    group.add_argument("--schedule",
                       choices=["auto", "flat_ring", "hierarchical",
                                "rhd", "star"],
                       default=None,
                       help="Collective schedule for the tcp data plane "
                            "(HVD_TPU_SCHEDULE): 'auto' picks per tensor "
                            "size/topology; 'hierarchical' is the "
                            "two-level intra-group + delegate-ring plan; "
                            "'rhd' is recursive halving/doubling for the "
                            "latency-bound regime — see docs/tuning.md.")
    group.add_argument("--controller", choices=["native", "python", "tcp"],
                       default=None)

    shard = parser.add_argument_group("sharding")
    shard.add_argument("--zero", action="store_true", default=None,
                       help="Enable the ZeRO-sharded weight update "
                            "(HVD_TPU_ZERO): gradients are reduce-scattered, "
                            "each rank updates its 1/N parameter shard with "
                            "optimizer state allocated for that shard only, "
                            "and updated shards are allgathered back — see "
                            "docs/sharding.md.")
    shard.add_argument("--zero-min-size", type=int, default=None,
                       help="Parameter-count threshold below which the "
                            "sharded update falls back to the replicated "
                            "path (HVD_TPU_ZERO_MIN_SIZE, default 1024).")
    shard.add_argument("--executor", choices=["psum", "mesh"], default=None,
                       help="XLA executor flavour (HVD_TPU_EXECUTOR): "
                            "'psum' is the shard_map ring executor; 'mesh' "
                            "builds the program over a NamedSharding mesh "
                            "(parallel.mesh axis vocabulary) so tensor/"
                            "pipeline parallel layers can compose on the "
                            "same mesh.")
    shard.add_argument("--group-max", type=int, default=None,
                       help="Cap on live process groups per job "
                            "(HVD_TPU_GROUP_MAX, default 64): each "
                            "hvd.new_group()/hvd.grid() group owns "
                            "negotiation state, signature caches and a "
                            "tcp ring plane, so an unbounded registry "
                            "is a leak — see docs/groups.md.")

    auto = parser.add_argument_group("autotune")
    auto.add_argument("--autotune", action="store_true", default=None)
    auto.add_argument("--no-autotune", action="store_true", default=None,
                      help="Force autotune off, overriding env/config.")
    auto.add_argument("--autotune-log-file", default=None)
    auto.add_argument("--autotune-warmup-samples", type=int, default=None)
    auto.add_argument("--autotune-steady-state-samples", type=int,
                      default=None)
    auto.add_argument("--autotune-bayes-opt-max-samples", type=int,
                      default=None)
    auto.add_argument("--autotune-gaussian-process-noise", type=float,
                      default=None)

    timeline = parser.add_argument_group("timeline")
    timeline.add_argument("--timeline-filename", default=None)
    timeline.add_argument("--timeline-mark-cycles", action="store_true",
                          default=None)

    fault = parser.add_argument_group("fault tolerance")
    fault.add_argument("--abort-timeout", type=float, default=None,
                       help="Bound (seconds) on 'abort initiated -> "
                            "every rank raises HvdAbortedError' "
                            "(HVD_TPU_ABORT_TIMEOUT; see "
                            "docs/fault_tolerance.md).")
    fault.add_argument("--heartbeat-interval", type=float, default=None,
                       help="Peer/coordinator heartbeat period in "
                            "seconds (HVD_TPU_HEARTBEAT_INTERVAL).")
    fault.add_argument("--liveness-timeout", type=float, default=None,
                       help="Missed-heartbeat window in seconds before a "
                            "silent rank is declared dead and the round "
                            "is aborted (HVD_TPU_LIVENESS_TIMEOUT; 0 "
                            "disables).")
    fault.add_argument("--connect-retry-seconds", type=float,
                       default=None,
                       help="Deadline budget in seconds for "
                            "connection-establishment retries with "
                            "backoff + jitter "
                            "(HVD_TPU_CONNECT_RETRY_SECONDS).")
    fault.add_argument("--fault-spec", default=None,
                       help="Deterministic fault injection spec "
                            "(HVD_TPU_FAULT_SPEC), e.g. "
                            "'rank1:allreduce:2:crash'; see "
                            "docs/fault_tolerance.md for the grammar. "
                            "bin/hvd-chaos generates seeded random "
                            "specs for soak runs.")
    fault.add_argument("--term-grace", type=float, default=None,
                       help="Grace window in seconds between the "
                            "SIGTERM the launcher forwards to a worker "
                            "process group and the SIGKILL escalation "
                            "(HVD_TPU_TERM_GRACE, default 5; see "
                            "docs/checkpoint.md).")
    fault.add_argument("--drain", action="store_true", default=None,
                       help="Workers convert SIGTERM (the preemption "
                            "notice) into a graceful drain: announce "
                            "departure to the coordinator, reconfigure "
                            "at the next collective boundary, exit 0 "
                            "(HVD_TPU_DRAIN, default on; see "
                            "docs/checkpoint.md).")
    fault.add_argument("--no-drain", action="store_true", default=None,
                       help="Force the drain handler off: SIGTERM "
                            "keeps its default kill disposition.")
    fault.add_argument("--reconnect-budget", type=float, default=None,
                       help="Reconnect window in seconds: a mid-stream "
                            "connection break is healed in place "
                            "(reconnect + session handshake + replay "
                            "of unacked frames) for up to this long "
                            "before the break escalates to the abort/"
                            "elastic path (HVD_TPU_RECONNECT_BUDGET, "
                            "default 0 = off; see "
                            "docs/fault_tolerance.md 'connection "
                            "blips vs dead peers').")
    fault.add_argument("--replay-buffer-bytes", type=int, default=None,
                       help="Bound on the sender-side replay buffer "
                            "of unacknowledged session frames "
                            "(HVD_TPU_REPLAY_BUFFER_BYTES, default "
                            "64 MiB); a heal needing an evicted frame "
                            "escalates instead of resuming with a "
                            "gap.")
    fault.add_argument("--rtt-alpha", type=float, default=None,
                       help="EWMA smoothing factor for the per-peer "
                            "RTT estimates behind the adaptive "
                            "liveness deadlines (HVD_TPU_RTT_ALPHA, "
                            "default 0.25; see docs/fault_tolerance.md "
                            "'degraded networks').")
    fault.add_argument("--straggler-factor", type=float, default=None,
                       help="A rank is a straggler when its reported "
                            "RTT exceeds this multiple of the median "
                            "across reporting ranks "
                            "(HVD_TPU_STRAGGLER_FACTOR, default 4). "
                            "The same factor caps the extra deadline "
                            "slack a slow rank may earn.")
    fault.add_argument("--straggler-windows", type=int, default=None,
                       help="Consecutive liveness-scan windows a rank "
                            "must exceed the straggler threshold "
                            "before the verdict is recorded "
                            "(HVD_TPU_STRAGGLER_WINDOWS, default 3).")
    fault.add_argument("--straggler-exclude", action="store_true",
                       default=None,
                       help="Under --elastic, propose a confirmed "
                            "straggler for drain-style exclusion at "
                            "the next collective boundary "
                            "(HVD_TPU_STRAGGLER_EXCLUDE, default "
                            "off: verdicts are log-only).")
    fault.add_argument("--no-straggler-exclude", action="store_true",
                       default=None,
                       help="Force straggler exclusion off (verdicts "
                            "stay log-only).")

    soak = parser.add_argument_group("soak rig")
    soak.add_argument("--soak-ranks", type=int, default=None,
                      help="World size for bin/hvd-soak "
                           "(HVD_TPU_SOAK_RANKS, default 16; see "
                           "docs/soak.md).")
    soak.add_argument("--soak-steps", type=int, default=None,
                      help="Training steps per soak leg "
                           "(HVD_TPU_SOAK_STEPS, default 20).")
    soak.add_argument("--soak-seed", type=int, default=None,
                      help="Chaos-schedule seed for the soak rig "
                           "(HVD_TPU_SOAK_SEED, default 11).")
    soak.add_argument("--soak-report", default=None,
                      help="Path prefix for the per-run SOAK_r*.json "
                           "gate artifacts (HVD_TPU_SOAK_REPORT).")
    soak.add_argument("--soak-reconfig-bound", type=float, default=None,
                      help="Regression gate: every elastic "
                           "reconfiguration observed during the soak "
                           "must complete within this many seconds "
                           "(HVD_TPU_SOAK_RECONFIG_BOUND, default "
                           "45).")

    ckpt = parser.add_argument_group("checkpointing")
    ckpt.add_argument("--ckpt-dir", default=None,
                      help="Durable checkpoint directory "
                           "(HVD_TPU_CKPT_DIR): each rank writes its "
                           "parameter/optimizer shard from the elastic "
                           "commit snapshot on a background thread; "
                           "elastic.run auto-resumes from the newest "
                           "complete manifest, re-sharding to the "
                           "current world size (docs/checkpoint.md). "
                           "Unset: checkpointing off.")
    ckpt.add_argument("--ckpt-interval", type=int, default=None,
                      help="Checkpoint every N committed steps "
                           "(HVD_TPU_CKPT_INTERVAL, default 10).")
    ckpt.add_argument("--ckpt-keep", type=int, default=None,
                      help="Retain the newest N checkpoints, pruning "
                           "older shards/manifests after each write "
                           "(HVD_TPU_CKPT_KEEP, default 2; 0 keeps "
                           "everything).")

    elastic = parser.add_argument_group("elastic membership")
    elastic.add_argument("--elastic", action="store_true", default=None,
                         help="Survive rank loss: re-form the ring "
                              "around the survivors at a new "
                              "membership epoch instead of killing "
                              "the job (HVD_TPU_ELASTIC; see "
                              "docs/elastic.md).")
    elastic.add_argument("--min-ranks", type=int, default=None,
                         help="Smallest membership the job may shrink "
                              "to; below this a rank loss is fatal "
                              "(HVD_TPU_MIN_RANKS, default 1).")
    elastic.add_argument("--max-ranks", type=int, default=None,
                         help="Cap on membership size when admitting "
                              "late joiners (HVD_TPU_MAX_RANKS; 0 = "
                              "unlimited).")
    elastic.add_argument("--reconfig-timeout", type=float, default=None,
                         help="Deadline in seconds for survivors to "
                              "re-form the world at the new epoch "
                              "(HVD_TPU_RECONFIG_TIMEOUT, default "
                              "60).")
    elastic.add_argument("--coord-failover", action="store_true",
                         default=None,
                         help="Survive rank-0 (coordinator) loss too: "
                              "survivors race a CAS election at the "
                              "rendezvous server and re-form under a "
                              "new coordinator instead of dying "
                              "(HVD_TPU_COORD_FAILOVER; requires "
                              "--elastic; see docs/elastic.md).")
    elastic.add_argument("--election-timeout", type=float, default=None,
                         help="Budget in seconds for one fail-over "
                              "election round — the CAS race plus "
                              "directive adoption "
                              "(HVD_TPU_ELECTION_TIMEOUT, default "
                              "10).")

    race = parser.add_argument_group("race detection")
    race.add_argument("--race", action="store_true", default=None,
                      help="Run every rank under the hvd-race shim "
                           "(HVD_TPU_RACE): traced threading/queue "
                           "primitives + instrumented attribute access "
                           "on the concurrency-scoped modules; see "
                           "docs/race_detection.md.")
    race.add_argument("--race-seed", type=int, default=None,
                      help="Schedule-fuzz seed (HVD_TPU_RACE_SEED): "
                           "deterministic preemptions at "
                           "instrumentation points — same seed, same "
                           "interleaving perturbation, same report.")
    race.add_argument("--race-scope", default=None,
                      help="Comma-separated module relpath suffixes to "
                           "instrument (HVD_TPU_RACE_SCOPE; 'all' = "
                           "every horovod_tpu module).")
    race.add_argument("--race-report", default=None,
                      help="Report-file prefix (HVD_TPU_RACE_REPORT): "
                           "each rank writes its race findings to "
                           "<prefix>.<pid>.json at exit.")

    proto = parser.add_argument_group("protocol checking")
    proto.add_argument("--proto-depth", type=int, default=None,
                       help="bin/hvd-proto model-checker exploration "
                            "bound in steps (HVD_TPU_PROTO_DEPTH, "
                            "default 10); see "
                            "docs/protocol_checking.md.")
    proto.add_argument("--proto-seed", type=int, default=None,
                       help="bin/hvd-proto exploration tie-break seed "
                            "(HVD_TPU_PROTO_SEED, default 0): same "
                            "seed + depth give a byte-identical "
                            "report.")

    fuzz = parser.add_argument_group("fuzzing")
    fuzz.add_argument("--fuzz-seed", type=int, default=None,
                      help="bin/hvd-fuzz mutation seed "
                           "(HVD_TPU_FUZZ_SEED, default 0): same seed "
                           "+ iters give a byte-identical run "
                           "summary; see docs/fuzzing.md.")
    fuzz.add_argument("--fuzz-iters", type=int, default=None,
                      help="bin/hvd-fuzz mutation iterations per "
                           "target (HVD_TPU_FUZZ_ITERS, default "
                           "300).")

    stall = parser.add_argument_group("stall check")
    stall.add_argument("--no-stall-check", action="store_true", default=None)
    stall.add_argument("--stall-check", action="store_true", default=None,
                       help="Force the stall check on, overriding "
                            "env/config.")
    stall.add_argument("--stall-check-warning-time-seconds", type=float,
                       default=None)
    stall.add_argument("--stall-check-shutdown-time-seconds", type=float,
                       default=None)

    logg = parser.add_argument_group("logging")
    logg.add_argument("--log-level", default=None,
                      choices=["trace", "debug", "info", "warning", "error",
                               "fatal"])
    logg.add_argument("--log-hide-timestamp", action="store_true",
                      default=None)

    parser.add_argument("-cb", "--check-build", action="store_true",
                        help="Print available frameworks, controllers "
                             "and data planes, then exit (reference: "
                             "horovodrun --check-build).")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="Training command to run on each rank.")
    return parser


def check_build(verbose=False):
    """The reference's ``horovodrun --check-build`` diagnostic
    (``runner.py:118``), in this framework's idiom: frameworks are
    import-probed, controllers/data planes are what the build ships."""
    import importlib.util
    import textwrap

    import horovod_tpu

    def have(mod):
        try:
            return importlib.util.find_spec(mod) is not None
        except (ImportError, ValueError):
            return False

    def native_core():
        try:
            from horovod_tpu.ops.native_controller import _load_lib
            return _load_lib() is not None
        except Exception:  # noqa: BLE001 — diagnostic must not crash
            return False

    x = lambda v: "X" if v else " "
    out = f"""\
    horovod_tpu v{horovod_tpu.__version__}:

    Available Frameworks:
        [{x(have('jax'))}] JAX (native)
        [{x(have('tensorflow'))}] TensorFlow / Keras
        [{x(have('torch'))}] PyTorch
        [{x(have('mxnet'))}] MXNet

    Available Controllers:
        [{x(native_core())}] native (C++ core)
        [X] python (in-process)
        [X] tcp (process coordinator)
        [X] gmesh (pod global mesh)

    Available Data Planes:
        [X] XLA (fused compiled collectives; ICI on TPU)
        [X] tcp ring (numpy p2p, process mode)
    """
    print(textwrap.dedent(out))
    if verbose:
        import os

        from horovod_tpu.ops import native_controller as nc

        print(f"package: {os.path.dirname(horovod_tpu.__file__)}")
        print(f"native core: {nc._LIB_PATH} "
              f"({'present' if os.path.exists(nc._LIB_PATH) else 'absent'})")
        try:
            import jax

            # version only — default_backend() would initialize the
            # backend and claim the chip for this diagnostic
            print(f"jax version: {jax.__version__}")
        except Exception as exc:  # noqa: BLE001
            print(f"jax: unavailable ({exc!r})")
    return 0


def build_slots(args):
    if args.hostfile:
        hosts = allocate_mod.parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = allocate_mod.parse_hosts(args.hosts)
    else:
        from horovod_tpu.run import lsf
        spec = lsf.host_spec() if lsf.using_lsf() else None
        if spec:
            # inside an LSF job the allocation is the host list
            # (reference: runner.py LSF auto-discovery via util/lsf.py)
            hosts = allocate_mod.parse_hosts(spec)
            if args.num_proc is None:
                args.num_proc = lsf.get_num_processes()
        else:
            hosts = [allocate_mod.HostInfo("localhost", args.num_proc)]
    if args.tpu:
        # one process per host; each process drives that host's chips as its
        # local ranks (device-rank mode under the hood)
        hosts = [allocate_mod.HostInfo(h.hostname, 1) for h in hosts]
        np_total = len(hosts)
    else:
        np_total = args.num_proc
    return allocate_mod.allocate(hosts, np_total)


def run_commandline(argv=None) -> int:
    parser = make_parser()
    args = parser.parse_args(argv)

    if args.version:
        import horovod_tpu

        print(horovod_tpu.__version__)
        return 0
    if args.check_build:
        return check_build(verbose=args.verbose)
    if not args.command:
        parser.error("no training command given")
    if args.num_proc is None and not args.tpu:
        from horovod_tpu.run import lsf
        if lsf.using_lsf():
            args.num_proc = lsf.get_num_processes()
        if args.num_proc is None:
            parser.error("-np is required (or use --tpu, or run inside "
                         "an LSF allocation)")

    if args.config_file:
        config_parser.apply_config_to_args(
            args, config_parser.load_config_file(args.config_file))

    extra_env = config_parser.env_from_args(args)
    if env_util.HVD_TPU_TERM_GRACE in extra_env:
        # the grace window is read by THIS process (the launcher's
        # SIGTERM forwarding, run/launch.py), not by the workers —
        # flag/YAML values must land in the launcher's own environment
        os.environ[env_util.HVD_TPU_TERM_GRACE] = \
            extra_env[env_util.HVD_TPU_TERM_GRACE]
    slots = build_slots(args)
    global_mesh = args.tpu or args.global_mesh
    if global_mesh:
        extra_env[env_util.HVD_GLOBAL_MESH] = "1"
    if len(slots) > 1 and not global_mesh \
            and env_util.HVD_CONTROLLER not in extra_env:
        extra_env[env_util.HVD_CONTROLLER] = "tcp"
    if env_util.HVD_SECRET_KEY not in extra_env:
        import base64
        from horovod_tpu.run.service import secret
        extra_env[env_util.HVD_SECRET_KEY] = base64.b64encode(
            secret.make_secret_key()).decode()

    if args.launcher != "ssh":
        return _delegate_launch(args, slots, extra_env)

    # fail fast with the full unreachable-host list before launching
    # anything (reference: runner.py:568-643 parallel cached ssh check)
    remote_hosts = sorted({s.hostname for s in slots})
    from horovod_tpu.run.ssh_check import check_all_hosts_ssh_successful
    check_all_hosts_ssh_successful(remote_hosts, ssh_port=args.ssh_port)

    rendezvous = RendezvousServer()
    port = rendezvous.start()
    addr = env_util.get_str(env_util.HVD_RENDEZVOUS_HOST_ADDR)
    if addr is None:
        from horovod_tpu.run.driver_discovery import maybe_discover
        discovered = maybe_discover(slots, ssh_port=args.ssh_port)
        if discovered is not None:
            ifaces, addr = discovered
            extra_env.setdefault(env_util.HVD_IFACE, sorted(ifaces)[0])
        else:
            addr = _routable_addr(slots)
    # Quote each token so arguments with spaces/quotes survive the shell
    # (reference: runner.py quotes the unknown args the same way).
    import shlex
    command = " ".join(shlex.quote(c) for c in args.command)
    try:
        code = launch_job(slots, command, addr, port, extra_env=extra_env,
                          ssh_port=args.ssh_port, verbose=args.verbose,
                          output_filename=args.output_filename,
                          elastic=bool(args.elastic),
                          min_ranks=args.min_ranks or 1,
                          coord_failover=bool(args.coord_failover))
    finally:
        rendezvous.stop()
    # a signal death surfaces as Popen's negative code; exit statuses
    # are unsigned, so report it in the shell's 128+signum convention
    # instead of the truncated-to-255 garbage sys.exit(-15) produces
    return 128 - code if code < 0 else code


def _delegate_launch(args, slots, extra_env):
    """mpirun / jsrun placement: start the rendezvous here, export the
    constant env contract (per-rank values come from the MPI runtime —
    ``common/topology._mpi_placed``), run ONE placement command."""
    rendezvous = RendezvousServer()
    port = rendezvous.start()
    addr = env_util.get_str(env_util.HVD_RENDEZVOUS_HOST_ADDR) \
        or _routable_addr(slots)
    env = dict(os.environ)
    env.update(extra_env)
    env[env_util.HVD_SIZE] = str(len(slots))
    env[env_util.HVD_RENDEZVOUS_ADDR] = addr
    env[env_util.HVD_RENDEZVOUS_PORT] = str(port)
    hosts_spec = ",".join(
        f"{h}:{n}" for h, n in
        _slots_by_host(slots).items())
    try:
        if args.launcher == "mpirun":
            import shlex

            from horovod_tpu.run import mpi_run
            extra = shlex.split(args.mpi_args) if args.mpi_args else None
            return mpi_run.mpi_run(len(slots), hosts_spec, args.command,
                                   env=env, extra_args=extra)
        from horovod_tpu.run import js_run
        return js_run.js_run(len(slots), args.command, env=env)
    finally:
        rendezvous.stop()


def _slots_by_host(slots):
    out = {}
    for s in slots:
        out[s.hostname] = out.get(s.hostname, 0) + 1
    return out


def _routable_addr(slots):
    """Pick the address remote workers use to reach the rendezvous server
    (reference: driver NIC discovery, simplified: hostname resolution; for
    all-local jobs, loopback)."""
    import socket

    if all(s.hostname in ("localhost", "127.0.0.1") for s in slots):
        return "127.0.0.1"
    return socket.gethostbyname(socket.gethostname())


def main():
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
