"""Elastic membership tests (docs/elastic.md).

Unit layer: the reconfiguration directive encoding, membership
planning, epoch stamping at the framing layer (a chunk from a
torn-down epoch must be refused by the data plane) and at the
coordinator (stale negotiation refused), and the
cache-hit-cannot-cross-abort regression on every controller.

Integration layer, against real worker processes on the tcp plane:

- the acceptance scenario — a 4-rank job loses rank 2 mid-training
  under ``HVD_TPU_ELASTIC=1``, reconfigures to 3 ranks, and trains to
  BITWISE-identical parameters vs an uninterrupted 3-rank run
  (integer-valued, rank-identical gradients make the ring
  allreduce-average exact for any world size, so the comparison is
  exact, not approximate);
- elastic OFF (the default): the same fault spec still raises the
  typed ``HvdAbortedError`` naming rank 2 on every surviving rank —
  the PR-2 contract is byte-identical when elastic is not enabled;
- a late joiner registered via the rendezvous is admitted at the
  reconfiguration window and observes the same parameters.
"""

import threading

import pytest

from conftest import spawn_tcp_ranks
from horovod_tpu.common.handles import (HvdAbortedError,
                                        HvdReconfigureError,
                                        encode_reconfig_reason,
                                        make_abort_error)


# ------------------------------------------------------ directive encoding --
def test_reconfig_reason_roundtrip():
    reason = encode_reconfig_reason(3, [0, 1, 3], [2], "rank 2 died")
    exc = make_abort_error(2, reason)
    assert isinstance(exc, HvdReconfigureError)
    assert isinstance(exc, HvdAbortedError)  # elastic-off except clauses
    assert (exc.epoch, exc.members, exc.dead) == (3, [0, 1, 3], [2])
    assert exc.origin_rank == 2
    assert "rank 2 died" in exc.cause


def test_malformed_directive_degrades_to_plain_abort():
    from horovod_tpu.common.handles import RECONFIG_MARKER

    exc = make_abort_error(1, RECONFIG_MARKER + "not json {")
    assert type(exc) is HvdAbortedError
    exc = make_abort_error(1, RECONFIG_MARKER + '{"epoch": 2}')
    assert type(exc) is HvdAbortedError  # missing fields
    exc = make_abort_error(1, "ordinary reason")
    assert type(exc) is HvdAbortedError


# ------------------------------------------------------ membership planning --
def _ctx(**kw):
    from horovod_tpu.elastic.membership import ElasticContext

    kw.setdefault("members", [0, 1, 2, 3])
    kw.setdefault("epoch", 0)
    return ElasticContext(**kw)


def test_plan_survivable_loss_keeps_survivor_order():
    ctx = _ctx()
    exc = make_abort_error(2, ctx.plan(2, "presumed dead"))
    assert isinstance(exc, HvdReconfigureError)
    assert exc.epoch == 1
    assert exc.members == [0, 1, 3]   # rank 0 survivor stays rank 0
    assert exc.dead == [2]


def test_plan_is_sticky_across_racing_aborts():
    ctx = _ctx()
    first = ctx.plan(2, "presumed dead")
    assert ctx.plan(3, "also reported") is first


def test_plan_refuses_rank0_user_abort_and_min_ranks():
    assert _ctx().plan(0, "rank 0 died") is None        # coordinator host
    assert _ctx().plan(1, "aborted by user") is None    # kill switch
    assert _ctx(min_ranks=4).plan(2, "died") is None    # would shrink below
    assert _ctx().plan(7, "died") is None               # not a member


def test_plan_caps_joiners_at_max_ranks():
    ctx = _ctx(max_ranks=3)
    ctx._registered_joiners = lambda exclude: [7, 8]
    exc = make_abort_error(2, ctx.plan(2, "died"))
    assert exc.members == [0, 1, 3]   # 3 survivors fill the cap


def test_plan_admits_registered_joiners():
    ctx = _ctx()
    ctx._registered_joiners = lambda exclude: [7]
    exc = make_abort_error(2, ctx.plan(2, "died"))
    assert exc.members == [0, 1, 3, 7]


# ------------------------------------------------- epoch @ framing layer ----
def test_stale_epoch_chunk_refused_by_data_plane():
    """A chunk stamped with epoch N must be dropped by a PeerService at
    epoch N+1 — the straggler traffic of a torn-down membership cannot
    land in the re-formed ring's mailbox."""
    from horovod_tpu.ops.tcp_dataplane import ChunkMsg, PeerService
    from horovod_tpu.run.service import secret

    svc = PeerService(secret.make_secret_key(), epoch=1)
    try:
        svc._handle(ChunkMsg((7, "rs", 0), 1, b"stale", epoch=0), None)
        assert svc._mailbox == {}
        assert svc.stale_epoch_drops == 1
        # current-epoch traffic still lands
        svc._handle(ChunkMsg((7, "rs", 0), 1, b"fresh", epoch=1), None)
        assert len(svc._mailbox) == 1
    finally:
        svc.shutdown()


def test_stale_epoch_negotiation_refused_by_coordinator():
    from horovod_tpu.ops.tcp_controller import (CollectiveMsg,
                                                CoordinatorService)
    from horovod_tpu.run.service import secret

    svc = CoordinatorService(1, secret.make_secret_key(), epoch=2)
    try:
        from horovod_tpu.common.ops_enum import RequestType, Sum

        req = CollectiveMsg("t", 0, RequestType.ALLREDUCE, Sum, b"",
                            (1,), "float32", epoch=1)
        resp = svc._handle_collective(req)
        assert resp.error and "stale membership epoch" in resp.error
        assert svc._forming == {}
    finally:
        svc.shutdown()


# ------------------------------------ cache cannot cross an abort boundary --
def test_tcp_coordinator_purges_sig_cache_on_abort():
    from horovod_tpu.ops.tcp_controller import CoordinatorService
    from horovod_tpu.run.service import secret

    svc = CoordinatorService(2, secret.make_secret_key())
    try:
        svc._sig_cache.store("t", ["sig-pre-abort"])
        assert svc._sig_cache.check("t", ["sig-pre-abort"])
        svc._initiate_abort(1, "rank 1 died")
        # the pre-abort signature must NOT satisfy a post-abort (or
        # post-reconfiguration) negotiation of the same tensor name
        assert not svc._sig_cache.check("t", ["sig-pre-abort"])
    finally:
        svc.shutdown()


def test_python_controller_purges_sig_cache_on_abort():
    from horovod_tpu.ops.python_controller import PythonController

    ctrl = object.__new__(PythonController)
    from horovod_tpu.common.response_cache import SignatureCache
    from horovod_tpu.utils.logging import get_logger

    ctrl._log = get_logger()
    ctrl._lock = threading.Lock()
    ctrl._shutdown_error = None
    ctrl._queue = []
    ctrl._join_handles = {}
    ctrl._joined = set()
    ctrl._sig_cache = SignatureCache(16)
    ctrl._fail_all = lambda exc: None
    ctrl._sig_cache.store("t", ["sig"])
    ctrl._apply_abort(HvdAbortedError(0, "boom"))
    assert not ctrl._sig_cache.check("t", ["sig"])
    assert isinstance(ctrl._shutdown_error, HvdAbortedError)


def test_gmesh_controller_shares_the_purging_abort_path():
    """GlobalMeshController inherits PythonController's _apply_abort —
    the purge above covers it; this pins the inheritance so a future
    override cannot silently drop the cache purge."""
    from horovod_tpu.ops.global_controller import GlobalMeshController
    from horovod_tpu.ops.python_controller import PythonController

    assert (GlobalMeshController._apply_abort
            is PythonController._apply_abort)


# --------------------------------------------------------- state object -----
def test_state_commit_restore_roundtrip():
    import numpy as np

    from horovod_tpu.elastic.state import State

    s = State(params={"w": np.arange(4.0)}, step=3, epoch=1)
    s.params["w"] += 100.0       # uncommitted in-place mutation
    s.step = 9
    s.restore()
    assert s.step == 3 and s.epoch == 1
    assert np.array_equal(s.params["w"], np.arange(4.0))
    s.params["w"] += 1.0
    s.commit()
    s.restore()
    assert np.array_equal(s.params["w"], np.arange(4.0) + 1.0)


# ------------------------------------------------------------ integration ---
ELASTIC_WORKER = r"""
import hashlib, os, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

wid = int(os.environ["HVD_RANK"])
steps = int(os.environ.get("EL_STEPS", "6"))

if wid >= int(os.environ["HVD_SIZE"]):
    # spawned OUTSIDE the initial gang: a late joiner, which enters
    # via the rendezvous instead of the epoch-0 gang start
    hvd.elastic.wait_for_membership(timeout=60)
else:
    hvd.init()

state = hvd.elastic.State(
    params={"w": jnp.zeros((1000,), dtype=jnp.float32)}, step=0)

def train(state):
    while state.step < steps:
        # integer-valued and identical on every rank: the ring
        # allreduce-average is EXACT for any world size, so the final
        # params are bitwise-independent of membership history
        grad = jnp.full((1000,), float(state.step + 1),
                        dtype=jnp.float32)
        avg = hvd.allreduce(grad, op=hvd.Average,
                            name=f"elastic.grad.{state.step}")
        state.params = {"w": state.params["w"] - avg}
        state.step += 1
        state.commit()

try:
    hvd.elastic.run(train, state)
except hvd.HvdAbortedError as exc:
    print(f"rank {hvd.rank()} wid {wid} ABORTED "
          f"origin={exc.origin_rank} why={exc}", flush=True)
    print(f"rank {hvd.rank()} wid {wid} DONE", flush=True)
    raise SystemExit(0)
digest = hashlib.sha1(
    np.asarray(state.params["w"]).tobytes()).hexdigest()
final_rank, final_size = hvd.rank(), hvd.size()
print(f"rank {final_rank} wid {wid} DIGEST={digest} "
      f"size={final_size} steps={state.step}", flush=True)
hvd.shutdown()
print(f"rank {final_rank} wid {wid} DONE", flush=True)
"""

_EL_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
    "HVD_TPU_ABORT_TIMEOUT": "10",
    "HVD_TPU_LIVENESS_TIMEOUT": "2",
    "HVD_TPU_RECONFIG_TIMEOUT": "60",
    "HVD_STALL_CHECK_TIME_SECONDS": "1",
    "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
    # 1000-float tensors (4000 B) ride the p2p ring, so the test
    # exercises the ring/stripe rebuild, not just the coordinator star
    "HVD_TCP_RING_THRESHOLD": "1024",
}


def _digests(results, ranks):
    out = {}
    for r in ranks:
        code, stdout, stderr = results[r]
        assert code == 0, f"rank {r}: {stdout}\n{stderr}"
        line = next(l for l in stdout.splitlines() if "DIGEST=" in l)
        fields = dict(kv.split("=") for kv in line.split()
                      if "=" in kv)
        out[r] = (fields["DIGEST"], int(fields["size"]),
                  int(fields["steps"]))
    return out


def test_elastic_survives_rank_loss_and_converges_bitwise():
    """The acceptance scenario: rank 2 of 4 crashes at its third
    allreduce (training step index 2); under HVD_TPU_ELASTIC=1 the
    survivors reconfigure to 3 ranks, roll back to the last commit,
    and finish — with parameters BITWISE-identical to an uninterrupted
    3-rank run of the same schedule."""
    elastic = spawn_tcp_ranks(4, ELASTIC_WORKER, timeout=150, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:3:crash",
    })
    assert elastic[2][0] == 1, f"injected crash: {elastic[2][1]}"
    got = _digests(elastic, ranks=[0, 1, 3])
    for r, (digest, size, steps) in got.items():
        assert size == 3, f"rank {r} finished at world size {size}"
        assert steps == 6
    assert len({d for d, _, _ in got.values()}) == 1, got

    uninterrupted = spawn_tcp_ranks(3, ELASTIC_WORKER, timeout=150,
                                    extra_env=_EL_ENV)
    want = _digests(uninterrupted, ranks=[0, 1, 2])
    assert got[0][0] == want[0][0], (got, want)


ZERO_ELASTIC_WORKER = r"""
import hashlib, os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import optax
import horovod_tpu as hvd

wid = int(os.environ["HVD_RANK"])
steps = int(os.environ.get("EL_STEPS", "6"))

if wid >= int(os.environ["HVD_SIZE"]):
    hvd.elastic.wait_for_membership(timeout=60)
else:
    hvd.init()

N = 1000
params = {"w": jnp.zeros((N,), dtype=jnp.float32)}
opt = hvd.ZeroDistributedOptimizer(optax.adam(0.1), min_size=1)
state = hvd.elastic.State(
    params=params, optimizer_state=opt.init(params), step=0,
    zero_n_params=N)

def train(state):
    while state.step < steps:
        # integer-valued, rank-identical gradients: the reduce-scatter
        # average is exact at any world size, and the adam update is
        # elementwise, so the allgathered params are bitwise-independent
        # of which rank owned which shard — and of membership history
        grad = {"w": jnp.full((N,), float(state.step + 1),
                              dtype=jnp.float32)}
        upd, state.optimizer_state = opt.update(
            grad, state.optimizer_state, state.params)
        state.params = optax.apply_updates(state.params, upd)
        state.step += 1
        state.commit()

try:
    hvd.elastic.run(train, state)
except hvd.HvdAbortedError as exc:
    print(f"rank {hvd.rank()} wid {wid} ABORTED "
          f"origin={exc.origin_rank} why={exc}", flush=True)
    print(f"rank {hvd.rank()} wid {wid} DONE", flush=True)
    raise SystemExit(0)
digest = hashlib.sha1(
    np.asarray(state.params["w"]).tobytes()).hexdigest()
shard = max((l.shape[0] for l in jax.tree.leaves(state.optimizer_state)
             if getattr(l, "ndim", 0) == 1), default=0)
final_rank, final_size = hvd.rank(), hvd.size()
print(f"rank {final_rank} wid {wid} DIGEST={digest} "
      f"size={final_size} steps={state.step} shard={shard}", flush=True)
hvd.shutdown()
print(f"rank {final_rank} wid {wid} DONE", flush=True)
"""


def test_elastic_zero_reshards_optimizer_state_and_converges_bitwise():
    """ZeRO x elastic acceptance (docs/sharding.md): a 4-rank sharded
    adam run loses rank 2 mid-step; survivors re-shard the committed
    (full) optimizer state at world size 3 and finish with params
    BITWISE-identical to an uninterrupted 3-rank sharded run.  Each
    survivor's final state shard must be the world-3 split of the
    1000-element flat param vector (334/333/333)."""
    elastic = spawn_tcp_ranks(4, ZERO_ELASTIC_WORKER, timeout=180,
                              extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_FAULT_SPEC": "rank2:reduce_scatter:3:crash",
    })
    assert elastic[2][0] == 1, f"injected crash: {elastic[2][1]}"
    got = _digests(elastic, ranks=[0, 1, 3])
    shards = {}
    for r, (digest, size, steps) in got.items():
        assert size == 3, f"rank {r} finished at world size {size}"
        assert steps == 6
        line = next(l for l in elastic[r][1].splitlines()
                    if "DIGEST=" in l)
        fields = dict(kv.split("=") for kv in line.split() if "=" in kv)
        shards[r] = int(fields["shard"])
    assert len({d for d, _, _ in got.values()}) == 1, got
    # survivor order 0,1,3 -> new ranks 0,1,2: np.array_split(1000, 3)
    assert [shards[0], shards[1], shards[3]] == [334, 333, 333], shards

    uninterrupted = spawn_tcp_ranks(3, ZERO_ELASTIC_WORKER, timeout=180,
                                    extra_env=_EL_ENV)
    want = _digests(uninterrupted, ranks=[0, 1, 2])
    assert got[0][0] == want[0][0], (got, want)


def test_elastic_off_same_spec_raises_typed_abort_everywhere():
    """Elastic OFF (the default): the identical fault spec must keep
    the PR-2 contract — every surviving rank raises HvdAbortedError
    naming rank 2, nobody reconfigures, nobody hangs."""
    results = spawn_tcp_ranks(4, ELASTIC_WORKER, timeout=120, extra_env={
        **_EL_ENV,
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:3:crash",
    })
    assert results[2][0] == 1
    for r in (0, 1, 3):
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err}"
        assert f"ABORTED origin=2" in out, f"rank {r}: {out}\n{err}"
        assert "DIGEST=" not in out


@pytest.mark.parametrize("action,origin", [
    ("crash", "2"), ("drop", "2")])
def test_elastic_off_matrix_cells_keep_culprit(action, origin):
    """Elastic-off regression across failure modes: crash (liveness
    detection) and drop (stall promotion) both still abort with the
    correct culprit at 4 ranks.  (Connect-refusals are retried to
    success and are covered by the fault-injection matrix.)"""
    env = {
        **_EL_ENV,
        "HVD_TPU_FAULT_SPEC": f"rank2:allreduce:3:{action}",
    }
    if action == "drop":
        # the dropper stays alive: liveness must NOT fire; the stall
        # inspector names the missing contributor
        env["HVD_TPU_LIVENESS_TIMEOUT"] = "30"
        env["HVD_STALL_SHUTDOWN_TIME_SECONDS"] = "2"
    results = spawn_tcp_ranks(4, ELASTIC_WORKER, timeout=120,
                              extra_env=env)
    survivors = [0, 1, 3] if action == "crash" else [0, 1, 2, 3]
    if action == "crash":
        assert results[2][0] == 1
    for r in survivors:
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err}"
        assert f"ABORTED origin={origin}" in out, \
            f"rank {r}: {out}\n{err}"


GROUP_ELASTIC_WORKER = r"""
import hashlib, os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

wid = int(os.environ["HVD_RANK"])
steps = int(os.environ.get("EL_STEPS", "6"))
hvd.init()

# groups are created ONCE, before any failure: the registry records
# worker ids, so the reconfiguration re-forms them — or fails them
# typed — without any re-creation by the user
g01 = hvd.new_group([0, 1], name="el.g01")
g_dead = hvd.new_group([2, 3], name="el.gdead") if hvd.size() >= 4 \
    else None
checked = {"reform": False}

state = hvd.elastic.State(
    params={"w": jnp.zeros((1000,), dtype=jnp.float32),
            "v": jnp.zeros((500,), dtype=jnp.float32)}, step=0)

def train(state):
    while state.step < steps:
        if g_dead is not None and hvd.size() == 3 \
                and not checked["reform"]:
            # epoch N+1: every group was re-formed from worker ids —
            # the survivors' group lives on the SAME workers at their
            # new ranks, the dead worker's group is typed-unsatisfiable
            assert g01.ranks == [0, 1], g01.ranks
            try:
                g_dead.ranks
                raise SystemExit("g_dead must be unsatisfiable")
            except hvd.GroupUnsatisfiableError:
                pass
            checked["reform"] = True
            print(f"wid {wid} GROUPS_REFORMED_OK", flush=True)
        grad = jnp.full((1000,), float(state.step + 1),
                        dtype=jnp.float32)
        avg = hvd.allreduce(grad, op=hvd.Average,
                            name=f"elastic.grad.{state.step}")
        # the sub-group computes, the world consumes: members reduce
        # inside g01, then rank 0 (a member in every epoch) broadcasts
        # the group's result so v stays replicated — the state resync
        # at a reconfiguration requires rank-identical state
        if hvd.rank() in g01:
            gavg = hvd.allreduce(
                jnp.full((500,), float(state.step + 2),
                         dtype=jnp.float32),
                op=hvd.Average, name=f"elastic.g.{state.step}",
                group=g01)
        else:
            gavg = jnp.zeros((500,), dtype=jnp.float32)
        gavg = hvd.broadcast(gavg, root_rank=0,
                             name=f"elastic.gb.{state.step}")
        state.params = {"w": state.params["w"] - avg,
                        "v": state.params["v"] - gavg}
        state.step += 1
        state.commit()

try:
    hvd.elastic.run(train, state)
except hvd.HvdAbortedError as exc:
    print(f"rank {hvd.rank()} wid {wid} ABORTED "
          f"origin={exc.origin_rank} why={exc}", flush=True)
    print(f"rank {hvd.rank()} wid {wid} DONE", flush=True)
    raise SystemExit(0)
digest = hashlib.sha1(
    np.asarray(state.params["w"]).tobytes()
    + np.asarray(state.params["v"]).tobytes()).hexdigest()
final_rank, final_size = hvd.rank(), hvd.size()
print(f"rank {final_rank} wid {wid} DIGEST={digest} "
      f"size={final_size} steps={state.step}", flush=True)
hvd.shutdown()
print(f"rank {final_rank} wid {wid} DONE", flush=True)
"""


def test_elastic_rank_loss_reforms_groups_and_converges_digest_identical():
    """Sub-group x elastic acceptance (docs/groups.md): a 4-rank job
    with a live sub-group [0,1] and a doomed sub-group [2,3] loses
    rank 2 mid-training.  At epoch N+1 every group is re-formed as a
    pure function of (spec, survivors): [0,1] carries on across the
    reconfiguration on the same workers, [2,3] raises the typed
    GroupUnsatisfiableError, and training finishes with a digest
    IDENTICAL to an uninterrupted 3-rank run making the same world +
    group updates."""
    elastic = spawn_tcp_ranks(4, GROUP_ELASTIC_WORKER, timeout=150,
                              extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:3:crash",
    })
    assert elastic[2][0] == 1, f"injected crash: {elastic[2][1]}"
    got = _digests(elastic, ranks=[0, 1, 3])
    for r, (digest, size, steps) in got.items():
        assert size == 3, f"rank {r} finished at world size {size}"
        assert steps == 6
        assert "GROUPS_REFORMED_OK" in elastic[r][1], elastic[r][1]
    assert len({d for d, _, _ in got.values()}) == 1, got

    uninterrupted = spawn_tcp_ranks(3, GROUP_ELASTIC_WORKER, timeout=150,
                                    extra_env=_EL_ENV)
    want = _digests(uninterrupted, ranks=[0, 1, 2])
    assert got[0][0] == want[0][0], (got, want)


def test_late_joiner_admitted_at_reconfiguration_window():
    """A 5th process registers via the rendezvous while a 4-rank job
    trains; when rank 2 is lost the reconfiguration admits it, and the
    joiner converges to the SAME parameters as the incumbents (its
    first act inside elastic.run is the state sync from rank 0)."""
    results = spawn_tcp_ranks(5, ELASTIC_WORKER, timeout=180,
                              world_size=4, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:3:crash",
    })
    assert results[2][0] == 1, f"injected crash: {results[2][1]}"
    got = _digests(results, ranks=[0, 1, 3, 4])
    for r, (digest, size, steps) in got.items():
        assert size == 4, f"rank {r} finished at world size {size}"
        assert steps == 6
    assert len({d for d, _, _ in got.values()}) == 1, got
