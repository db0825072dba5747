"""Graceful drain + durable sharded checkpointing (docs/checkpoint.md).

Unit layer: the shard/manifest store (digest verification, atomicity
contract, newest-first listing), the CheckpointManager (interval
gating, retention pruning, fallback past corrupt or incomplete
manifests, cross-world shard re-assembly), the drain protocol pieces
(preempt fault action, drain-marked directives, coordinator busy/
draining liveness interplay, culprit attribution, the launcher grace
window), and the dead-epoch rendezvous scope purge primitive.

Integration layer, against real worker processes on the tcp plane:

- the preempt matrix cell — rank 2 of 4 is SIGTERM'd mid-training,
  drains with ZERO ``HvdAbortedError`` anywhere, exits 0, and the
  survivors converge bitwise to an uninterrupted 3-rank run;
- the acceptance scenario — the drained job checkpoints durably, the
  whole job is then killed mid-step, and a fresh 3-rank job
  auto-resumes from the newest complete manifest to finish
  digest-identical to an uninterrupted run;
- cross-world resume — a checkpoint written at world 4 resumes on 3;
- the throttled-writer liveness regression (busy-flagged heartbeats);
- the checkpoint writer thread is clean under the hvd-race shim.
"""

import glob
import importlib.machinery
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import spawn_tcp_ranks
from horovod_tpu.checkpoint import CheckpointManager, store
from horovod_tpu.common.handles import (HvdAbortedError, HvdDrainedError,
                                        HvdError, HvdReconfigureError,
                                        is_drain_reason, make_abort_error)
from horovod_tpu.elastic.state import State

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _standalone_runtime(monkeypatch):
    """In-process suites that ran earlier may leave the threaded runtime
    initialized (size N) in this interpreter; these units model a
    standalone pre-init process, where ``CheckpointManager`` falls back
    to the (rank 0, world 1) topology.  Subprocess tests are unaffected."""
    from horovod_tpu.common import basics
    monkeypatch.setattr(basics, "is_initialized", lambda: False)


# ------------------------------------------------------------ store ---------
def test_shard_roundtrip_and_digest_verification(tmp_path):
    payload = {"params": np.arange(16, dtype=np.float32),
               "opt_sharded": {"0": np.ones(4, np.float32)},
               "opt_rest": {}}
    store.write_shard(str(tmp_path), 7, 1, 2, 0, payload)
    got = store.read_shard(str(tmp_path), 7, 1, 2, 0)
    assert np.array_equal(np.asarray(got["params"]), payload["params"])
    assert np.array_equal(np.asarray(got["opt_sharded"]["0"]),
                          payload["opt_sharded"]["0"])
    # no torn .tmp files survive the atomic rename
    assert not glob.glob(str(tmp_path / "*.tmp.*"))


def test_corrupt_or_missing_shard_raises_typed_error(tmp_path):
    store.write_shard(str(tmp_path), 3, 0, 1, 0,
                      {"params": np.arange(8, dtype=np.float32)})
    path = tmp_path / store.shard_name(3, 0, 1, 0)

    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF          # flip one payload byte
    path.write_bytes(bytes(blob))
    with pytest.raises(store.CorruptShardError):
        store.read_shard(str(tmp_path), 3, 0, 1, 0)

    # truncation trips the byte-count check before the digest
    path.write_bytes(bytes(blob[:-4]))
    with pytest.raises(store.CorruptShardError):
        store.read_shard(str(tmp_path), 3, 0, 1, 0)

    os.remove(f"{path}.meta.json")        # missing sidecar
    with pytest.raises(store.CorruptShardError):
        store.read_shard(str(tmp_path), 3, 0, 1, 0)
    with pytest.raises(store.CorruptShardError):
        store.read_shard(str(tmp_path), 99, 0, 1, 0)   # never written


def test_list_manifests_newest_first(tmp_path):
    for step, epoch, world in [(5, 0, 4), (10, 0, 3), (10, 1, 3)]:
        store.write_manifest(str(tmp_path), step, epoch, world)
    assert store.list_manifests(str(tmp_path)) == [
        (10, 1, 3), (10, 0, 3), (5, 0, 4)]
    assert store.list_manifests(str(tmp_path / "nonexistent")) == []


# ---------------------------------------------------------- manager ---------
def _commit_steps(state, manager, steps):
    """Drive commits one at a time, draining the writer between them so
    the latest-wins slot cannot coalesce snapshots under test."""
    for _ in range(steps):
        state.params["w"] = state.params["w"] + 1.0
        state.step += 1
        state.commit()
        assert manager.wait(timeout=30)


def test_interval_gates_and_keep_prunes(tmp_path):
    state = State(params={"w": np.zeros(8, np.float32)})
    m = CheckpointManager(str(tmp_path), interval_steps=3, keep=0)
    state.attach_checkpoint(m)
    try:
        _commit_steps(state, m, 7)
    finally:
        m.close()
    assert store.list_manifests(str(tmp_path)) == [(6, 0, 1), (3, 0, 1)]

    pruned = tmp_path / "pruned"
    state2 = State(params={"w": np.zeros(8, np.float32)})
    m2 = CheckpointManager(str(pruned), interval_steps=1, keep=1)
    state2.attach_checkpoint(m2)
    try:
        _commit_steps(state2, m2, 3)
    finally:
        m2.close()
    assert store.list_manifests(str(pruned)) == [(3, 0, 1)]
    assert store.list_own_shards(str(pruned), 0) == [(3, 0, 1)]


def test_restore_round_trips_params_and_optimizer(tmp_path):
    state = State(params={"w": np.zeros(8, np.float32)},
                  optimizer_state={"m": np.full(8, 2.0, np.float32),
                                   "count": np.float32(5)})
    m = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    state.attach_checkpoint(m)
    try:
        _commit_steps(state, m, 4)
    finally:
        m.close()

    fresh = State(params={"w": np.zeros(8, np.float32)},
                  optimizer_state={"m": np.zeros(8, np.float32),
                                   "count": np.float32(0)})
    m2 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        assert m2.restore_latest(fresh) == (4, 0)
    finally:
        m2.close()
    assert fresh.step == 4
    assert np.array_equal(fresh.params["w"], np.full(8, 4.0))
    assert np.array_equal(fresh.optimizer_state["m"], np.full(8, 2.0))
    assert float(fresh.optimizer_state["count"]) == 5.0
    # restore installed the snapshot as the committed rollback point
    fresh.params["w"] += 99.0
    fresh.restore()
    assert np.array_equal(fresh.params["w"], np.full(8, 4.0))


def test_corrupt_newest_falls_back_to_previous_complete(tmp_path):
    state = State(params={"w": np.zeros(8, np.float32)})
    m = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    state.attach_checkpoint(m)
    try:
        _commit_steps(state, m, 2)
    finally:
        m.close()

    shard = tmp_path / store.shard_name(2, 0, 1, 0)
    blob = bytearray(shard.read_bytes())
    blob[0] ^= 0xFF
    shard.write_bytes(bytes(blob))

    fresh = State(params={"w": np.zeros(8, np.float32)})
    m2 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        assert m2.restore_latest(fresh) == (1, 0)
    finally:
        m2.close()
    assert np.array_equal(fresh.params["w"], np.full(8, 1.0))


def test_incomplete_manifest_missing_world_shard_is_skipped(tmp_path):
    # a complete world-1 checkpoint at step 3 ...
    state = State(params={"w": np.zeros(8, np.float32)})
    m = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    state.attach_checkpoint(m)
    try:
        _commit_steps(state, m, 3)
    finally:
        m.close()
    # ... then a NEWER world-2 checkpoint with only rank 0's shard on
    # disk (rank 1 died pre-write): manifest present, validation fails
    m2 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        m2._write({"params": {"w": np.full(8, 9.0, np.float32)},
                   "opt": None, "opt_full": False,
                   "step": 5, "epoch": 0, "rank": 0, "world": 2})
    finally:
        m2.close()
    assert store.list_manifests(str(tmp_path))[0] == (5, 0, 2)

    fresh = State(params={"w": np.zeros(8, np.float32)})
    m3 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        assert m3.restore_latest(fresh) == (3, 0)
    finally:
        m3.close()
    assert np.array_equal(fresh.params["w"], np.full(8, 3.0))


def test_shape_mismatched_checkpoint_is_not_resumed(tmp_path):
    state = State(params={"w": np.zeros(8, np.float32)})
    m = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    state.attach_checkpoint(m)
    try:
        _commit_steps(state, m, 1)
    finally:
        m.close()
    grown = State(params={"w": np.zeros(12, np.float32)})
    m2 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        assert m2.restore_latest(grown) is None
    finally:
        m2.close()
    assert np.array_equal(grown.params["w"], np.zeros(12))


def test_cross_world_restore_reassembles_four_shards(tmp_path,
                                                    monkeypatch):
    """Shards written by 4 ranks (params + FULL-form optimizer) must
    re-assemble into the exact original vectors on restore — the
    byte-level contract behind resuming a w4 checkpoint at any world."""
    n = 10
    params = {"w": np.arange(n, dtype=np.float32)}
    opt = {"count": np.float32(7.0),
           "m": np.arange(n, dtype=np.float32) * 2.0}
    m = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        for rank in range(4):
            m._write({"params": params, "opt": opt, "opt_full": True,
                      "step": 40, "epoch": 1, "rank": rank, "world": 4})
    finally:
        m.close()
    manifest = store.read_manifest(str(tmp_path), 40, 1, 4)
    assert manifest["n_params"] == n
    assert manifest["opt_kind"] == "full"
    # each rank's shard holds only ITS block of the row partition
    assert len(store.read_shard(str(tmp_path), 40, 1, 4, 0)["params"]) == 3
    assert len(store.read_shard(str(tmp_path), 40, 1, 4, 3)["params"]) == 2

    # restore at world 1 (reshard is a passthrough there): the restored
    # live state must equal the original full vectors bit-for-bit
    from horovod_tpu.sharding import zero as zero_mod
    monkeypatch.setattr(zero_mod, "_topology_of",
                        lambda basics, group=None: (0, 1))
    fresh = State(params={"w": np.zeros(n, np.float32)},
                  optimizer_state={"count": np.float32(0),
                                   "m": np.zeros(n, np.float32)},
                  zero_n_params=n)
    m2 = CheckpointManager(str(tmp_path), interval_steps=1, keep=0)
    try:
        assert m2.restore_latest(fresh) == (40, 1)
    finally:
        m2.close()
    assert fresh.step == 40 and fresh.epoch == 1
    assert np.array_equal(fresh.params["w"], params["w"])
    assert np.array_equal(np.asarray(fresh.optimizer_state["m"]),
                          opt["m"])
    assert float(fresh.optimizer_state["count"]) == 7.0
    assert fresh._opt_full is True


def test_manager_from_env_reads_env_contract(tmp_path, monkeypatch):
    import horovod_tpu.checkpoint as ckpt
    from horovod_tpu.common import basics

    # force the env path even when another test initialized the runtime
    monkeypatch.setattr(basics, "is_initialized", lambda: False)
    monkeypatch.delenv("HVD_TPU_CKPT_DIR", raising=False)
    assert ckpt.manager_from_env() is None
    monkeypatch.setenv("HVD_TPU_CKPT_DIR", str(tmp_path / "ck"))
    monkeypatch.setenv("HVD_TPU_CKPT_INTERVAL", "7")
    monkeypatch.setenv("HVD_TPU_CKPT_KEEP", "3")
    m = ckpt.manager_from_env()
    try:
        assert (m._dir, m._interval, m._keep) == (
            str(tmp_path / "ck"), 7, 3)
    finally:
        m.close()


# ------------------------------------------------------ drain protocol ------
def test_fault_grammar_accepts_preempt():
    from horovod_tpu.common.faults import parse_fault_spec

    (spec,) = parse_fault_spec("rank2:allreduce:3:preempt")
    assert (spec.rank, spec.point, spec.step, spec.action) == (
        2, "allreduce", 3, "preempt")
    with pytest.raises(ValueError):
        parse_fault_spec("rank2:allreduce:3:sigterm")


def test_preempt_action_delivers_sigterm_to_self():
    from horovod_tpu.common import faults

    got = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: got.append(s))
    try:
        faults.configure("rank0:unit_point:1:preempt", rank=0)
        # the operation itself proceeds (not a drop) ...
        assert faults.check("unit_point") is False
        # ... and the preemption notice lands on this process
        for _ in range(200):
            if got:
                break
            time.sleep(0.005)
        assert got == [signal.SIGTERM]
        assert faults.check("unit_point") is False   # fires exactly once
        assert got == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)
        faults.configure(None)


def _load_chaos():
    loader = importlib.machinery.SourceFileLoader(
        "hvd_chaos_under_test", os.path.join(REPO, "bin", "hvd-chaos"))
    spec = importlib.util.spec_from_loader(loader.name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def test_chaos_preempt_cells_are_elastic_only_and_deterministic():
    from horovod_tpu.common.faults import parse_fault_spec

    chaos = _load_chaos()
    for seed in range(40):
        plain = chaos.generate_spec(seed, 4, 3)
        assert plain == chaos.generate_spec(seed, 4, 3)  # reproducible
        assert "preempt" not in plain    # non-elastic pool unchanged
        parse_fault_spec(plain)
        elastic = chaos.generate_spec(seed, 4, 3, elastic=True)
        assert elastic == chaos.generate_spec(seed, 4, 3, elastic=True)
        parse_fault_spec(elastic)
    assert any("preempt" in chaos.generate_spec(s, 4, 3, elastic=True)
               for s in range(40))


def test_pick_culprit_never_blames_a_clean_exit():
    from horovod_tpu.run.launch import pick_culprit

    # the drained rank exited 0 FIRST; the real failure exited 9 later
    failures = [(2, 0, False, 1.0), (1, 9, False, 2.0)]
    assert pick_culprit(failures) == (1, 9)
    # even when the fault spec armed the drained rank with the preempt
    assert pick_culprit(failures, crash_ranks=frozenset({2})) == (1, 9)


def test_termination_grace_window_env(monkeypatch):
    from horovod_tpu.run import safe_shell_exec

    monkeypatch.delenv("HVD_TPU_TERM_GRACE", raising=False)
    assert safe_shell_exec.termination_grace_seconds() == 5.0
    monkeypatch.setenv("HVD_TPU_TERM_GRACE", "9.5")
    assert safe_shell_exec.termination_grace_seconds() == 9.5


def test_drained_sentinel_and_error_classes():
    import horovod_tpu as hvd

    assert not hvd.elastic.DRAINED             # falsy ...
    assert hvd.elastic.DRAINED is not None     # ... but not None
    assert repr(hvd.elastic.DRAINED) == "hvd.elastic.DRAINED"
    exc = HvdDrainedError(3)
    assert isinstance(exc, HvdError)
    assert not isinstance(exc, HvdAbortedError)   # a drain is a success
    assert exc.worker_id == 3 and hvd.HvdDrainedError is HvdDrainedError


def test_drain_marked_directive_roundtrip():
    from horovod_tpu.common.handles import encode_reconfig_reason

    reason = encode_reconfig_reason(2, [0, 1, 3], [2], "drained",
                                    drain=True)
    assert is_drain_reason(reason)
    exc = make_abort_error(2, reason)
    assert isinstance(exc, HvdReconfigureError) and exc.drain
    plain = encode_reconfig_reason(2, [0, 1, 3], [2], "died")
    assert not is_drain_reason(plain)
    assert not make_abort_error(2, plain).drain
    assert not is_drain_reason("rank 2 died")


def test_plan_drain_marks_directive_and_respects_refusals():
    from horovod_tpu.elastic.membership import ElasticContext

    ctx = ElasticContext(members=[0, 1, 2, 3], epoch=0)
    exc = make_abort_error(2, ctx.plan_drain(2))
    assert exc.drain and exc.epoch == 1
    assert exc.members == [0, 1, 3] and exc.dead == [2]
    # a drain racing an already-decided plan is refused
    assert ctx.plan_drain(3) is None
    # coordinator rank and min-ranks refusals
    assert ElasticContext(members=[0, 1], epoch=0).plan_drain(0) is None
    assert ElasticContext(members=[0, 1], epoch=0,
                          min_ranks=2).plan_drain(1) is None


def test_coordinator_grants_drain_and_publishes_pull_only_directive():
    from horovod_tpu.elastic.membership import ElasticContext
    from horovod_tpu.ops.tcp_controller import (CoordinatorService,
                                                DrainAck, DrainMsg)
    from horovod_tpu.run.service import secret

    ctx = ElasticContext(members=[0, 1, 2, 3], epoch=0)
    svc = CoordinatorService(4, secret.make_secret_key(), elastic=ctx)
    try:
        ack = svc._handle(DrainMsg(2), None)
        assert isinstance(ack, DrainAck) and ack.ok
        with svc._cv:
            assert 2 in svc._draining
        origin, reason = svc._abort
        assert origin == 2 and is_drain_reason(reason)
        exc = make_abort_error(origin, reason)
        assert exc.members == [0, 1, 3] and exc.drain
    finally:
        svc.shutdown()


def test_coordinator_refuses_drain_without_elastic_context():
    from horovod_tpu.ops.tcp_controller import (CoordinatorService,
                                                DrainAck, DrainMsg)
    from horovod_tpu.run.service import secret

    svc = CoordinatorService(4, secret.make_secret_key())
    try:
        ack = svc._handle(DrainMsg(2), None)
        assert isinstance(ack, DrainAck) and not ack.ok
        assert svc._abort is None         # nothing aborted
        with svc._cv:                     # liveness blame restored
            assert 2 not in svc._draining
    finally:
        svc.shutdown()


def test_inprocess_controllers_refuse_drain():
    from horovod_tpu.ops.global_controller import GlobalMeshController
    from horovod_tpu.ops.python_controller import PythonController

    assert PythonController.request_drain(
        object.__new__(PythonController)) is False
    assert GlobalMeshController.request_drain(
        object.__new__(GlobalMeshController)) is False


# ------------------------------------------- busy / liveness interplay ------
def test_busy_window_nests_and_reports():
    from horovod_tpu.common import busy

    assert not busy.active()
    with busy.window():
        assert busy.active()
        with busy.window():
            assert busy.active()
        assert busy.active()
    assert not busy.active()


def _liveness_svc():
    from horovod_tpu.ops.tcp_controller import CoordinatorService
    from horovod_tpu.run.service import secret

    return CoordinatorService(2, secret.make_secret_key(),
                              liveness_timeout_sec=10.0)


def test_busy_rank_gets_doubled_liveness_window():
    from horovod_tpu.run.service import network

    svc = _liveness_svc()
    try:
        svc._handle(network.HeartbeatMsg(1, busy=True), None)
        with svc._cv:
            svc._last_seen[0] = time.monotonic()
            svc._last_seen[1] = time.monotonic() - 15.0   # 1.5x window
            svc._last_liveness_scan = 0.0   # open the scan time-gate
        svc._check_liveness()
        assert svc._abort is None        # busy: the deadline doubled
        with svc._cv:
            svc._last_seen[1] = time.monotonic() - 25.0   # past 2x
            svc._last_liveness_scan = 0.0
        svc._check_liveness()
        assert svc._abort is not None and svc._abort[0] == 1
    finally:
        svc.shutdown()


def test_non_busy_rank_keeps_plain_window():
    from horovod_tpu.run.service import network

    svc = _liveness_svc()
    try:
        svc._handle(network.HeartbeatMsg(1, busy=False), None)
        with svc._cv:
            svc._last_seen[0] = time.monotonic()
            svc._last_seen[1] = time.monotonic() - 15.0
            svc._last_liveness_scan = 0.0   # open the scan time-gate
        svc._check_liveness()
        assert svc._abort is not None and svc._abort[0] == 1
    finally:
        svc.shutdown()


def test_draining_rank_is_exempt_from_liveness_blame():
    svc = _liveness_svc()
    try:
        with svc._cv:
            svc._draining.add(1)
            svc._last_seen[0] = time.monotonic()
            svc._last_seen[1] = time.monotonic() - 100.0
        svc._check_liveness()
        assert svc._abort is None        # its silence is the departure
    finally:
        svc.shutdown()


# ------------------------------------------------- rendezvous scope purge ---
def test_delete_scope_purges_dead_epoch_keys_only():
    from horovod_tpu.run import http_client
    from horovod_tpu.run.http_server import RendezvousServer

    server = RendezvousServer()
    port = server.start()
    try:
        http_client.put("127.0.0.1", port, "controller.e1", "addr", b"x")
        http_client.put("127.0.0.1", port, "peers.e1", "r0", b"y")
        http_client.put("127.0.0.1", port, "controller.e2", "addr", b"z")
        for scope in ("controller.e1", "peers.e1"):
            http_client.delete_scope("127.0.0.1", port, scope)
            assert http_client.list_keys("127.0.0.1", port, scope) == []
        with pytest.raises(KeyError):
            http_client.get("127.0.0.1", port, "controller.e1", "addr",
                            timeout=0.2)
        # the live epoch's scope is untouched
        assert http_client.get("127.0.0.1", port, "controller.e2",
                               "addr", timeout=2) == b"z"
    finally:
        server.stop()


# ------------------------------------------------------------ integration ---
CKPT_WORKER = r"""
import hashlib, os, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

wid = int(os.environ["HVD_RANK"])
steps = int(os.environ.get("EL_STEPS", "6"))
die_at = int(os.environ.get("EL_DIE_AT", "-1"))

hvd.init()

state = hvd.elastic.State(
    params={"w": jnp.zeros((1000,), dtype=jnp.float32)}, step=0)

def train(state):
    while state.step < steps:
        if state.step == die_at:
            # deterministic whole-job kill: give the background writer
            # time to drain the committed snapshot, then die hard
            time.sleep(1.0)
            os._exit(1)
        # integer-valued and identical on every rank: the allreduce
        # average is EXACT for any world size, so the final params are
        # bitwise-independent of membership (and resume) history
        grad = jnp.full((1000,), float(state.step + 1),
                        dtype=jnp.float32)
        avg = hvd.allreduce(grad, op=hvd.Average,
                            name=f"elastic.grad.{state.step}")
        state.params = {"w": state.params["w"] - avg}
        state.step += 1
        state.commit()

try:
    result = hvd.elastic.run(train, state)
except hvd.HvdAbortedError as exc:
    print(f"wid {wid} ABORTED origin={exc.origin_rank}", flush=True)
    raise SystemExit(0)
if result is hvd.elastic.DRAINED:
    print(f"wid {wid} DRAINED", flush=True)
    raise SystemExit(0)
digest = hashlib.sha1(
    np.asarray(state.params["w"]).tobytes()).hexdigest()
print(f"rank {hvd.rank()} wid {wid} DIGEST={digest} "
      f"size={hvd.size()} steps={state.step}", flush=True)
hvd.shutdown()
print(f"wid {wid} DONE", flush=True)
"""

_EL_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
    "HVD_TPU_ABORT_TIMEOUT": "10",
    "HVD_TPU_LIVENESS_TIMEOUT": "2",
    "HVD_TPU_RECONFIG_TIMEOUT": "60",
    "HVD_STALL_CHECK_TIME_SECONDS": "1",
    "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
    "HVD_TCP_RING_THRESHOLD": "1024",
}


def _digests(results, ranks):
    out = {}
    for r in ranks:
        code, stdout, stderr = results[r]
        assert code == 0, f"rank {r}: {stdout}\n{stderr}"
        line = next(l for l in stdout.splitlines() if "DIGEST=" in l)
        fields = dict(kv.split("=") for kv in line.split() if "=" in kv)
        out[r] = (fields["DIGEST"], int(fields["size"]),
                  int(fields["steps"]))
    return out


def _assert_zero_aborts(results, ranks):
    for r in ranks:
        assert "ABORTED" not in results[r][1], \
            f"rank {r}: {results[r][1]}\n{results[r][2]}"
        assert "HvdAbortedError" not in results[r][2], \
            f"rank {r} stderr: {results[r][2]}"


_REFERENCE_DIGESTS = {}


def _reference_digest(world, steps):
    """Rank-0 digest of an uninterrupted ``world``-rank, ``steps``-step
    run — memoized, several tests compare against the same baseline."""
    key = (world, steps)
    if key not in _REFERENCE_DIGESTS:
        results = spawn_tcp_ranks(world, CKPT_WORKER, timeout=150,
                                  extra_env={**_EL_ENV,
                                             "EL_STEPS": str(steps)})
        _REFERENCE_DIGESTS[key] = _digests(
            results, ranks=list(range(world)))[0][0]
    return _REFERENCE_DIGESTS[key]


# The five scenario tests below spawn real multi-rank TCP jobs (tens of
# seconds each).  They carry the `slow` marker to stay out of the
# wall-clock-capped tier-1 sweep — the dedicated `checkpoint` CI job
# (bin/gen-ci) runs this file unfiltered, so they remain enforced.
@pytest.mark.slow
def test_preempt_drains_rank_and_survivors_converge_bitwise():
    """The preempt matrix cell: rank 2 of 4 receives SIGTERM at its
    third allreduce.  It must drain (exit 0, DRAINED marker), every
    survivor must reconfigure with ZERO ``HvdAbortedError``, and the
    survivors' final params must be bitwise-identical to an
    uninterrupted 3-rank run."""
    results = spawn_tcp_ranks(4, CKPT_WORKER, timeout=150, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:3:preempt",
    })
    code2, out2, err2 = results[2]
    assert code2 == 0, f"drained rank exited {code2}: {out2}\n{err2}"
    assert "wid 2 DRAINED" in out2, out2
    _assert_zero_aborts(results, ranks=[0, 1, 2, 3])
    got = _digests(results, ranks=[0, 1, 3])
    for r, (digest, size, steps) in got.items():
        assert size == 3, f"rank {r} finished at world size {size}"
        assert steps == 6
    assert len({d for d, _, _ in got.values()}) == 1, got

    assert got[0][0] == _reference_digest(3, 6), got


@pytest.mark.slow
def test_drain_then_whole_job_kill_auto_resumes_digest_identical(
        tmp_path):
    """The acceptance scenario (ISSUE: preemption-aware drain + durable
    checkpointing).  Phase 1: a 4-rank job checkpointing every commit
    loses rank 2 to a preemption drain at step 3, reconfigures to 3
    ranks, then the WHOLE job is killed at step 9.  Phase 2: a fresh
    3-rank job pointed at the same directory auto-resumes from the
    newest complete manifest and finishes digest-identical to an
    uninterrupted 3-rank run."""
    ckpt_dir = str(tmp_path / "ckpt")
    phase1 = spawn_tcp_ranks(4, CKPT_WORKER, timeout=180, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "EL_STEPS": "10",
        "HVD_TPU_CKPT_DIR": ckpt_dir,
        "HVD_TPU_CKPT_INTERVAL": "1",
        "HVD_TPU_FAULT_SPEC": (
            "rank2:allreduce:3:preempt,rank0:allreduce:9:crash,"
            "rank1:allreduce:9:crash,rank3:allreduce:9:crash"),
    })
    assert phase1[2][0] == 0, f"drained rank: {phase1[2][1]}"
    assert "wid 2 DRAINED" in phase1[2][1]
    for r in (0, 1, 3):
        # the whole-job kill landed: each survivor either died by its
        # own crash fault or caught the abort from a ring neighbor that
        # crashed mid-overlap — but nobody finished training
        assert phase1[r][0] != 0 or "ABORTED" in phase1[r][1], \
            f"rank {r}: {phase1[r][1]}\n{phase1[r][2]}"
        assert "DIGEST=" not in phase1[r][1], phase1[r][1]
    # durable evidence survived the kill: at least one manifest at w3
    assert any(w == 3 for _s, _e, w in store.list_manifests(ckpt_dir))

    phase2 = spawn_tcp_ranks(3, CKPT_WORKER, timeout=180, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "EL_STEPS": "10",
        "HVD_TPU_CKPT_DIR": ckpt_dir,
        "HVD_TPU_CKPT_INTERVAL": "1",
    })
    got = _digests(phase2, ranks=[0, 1, 2])
    assert "resumed from step" in phase2[0][2], phase2[0][2]
    for r, (digest, size, steps) in got.items():
        assert size == 3 and steps == 10
    assert len({d for d, _, _ in got.values()}) == 1, got

    assert got[0][0] == _reference_digest(3, 10), got


@pytest.mark.slow
def test_checkpoint_written_at_world4_resumes_on_3_ranks(tmp_path):
    """Cross-world resume: every rank of a 4-rank job dies at step 3
    (after the writer drained), so the ONLY checkpoints on disk are
    world-4 shards.  A 3-rank job must re-assemble them, re-shard to
    its own world, and finish digest-identical to an uninterrupted
    3-rank run."""
    ckpt_dir = str(tmp_path / "ckpt")
    phase1 = spawn_tcp_ranks(4, CKPT_WORKER, timeout=150, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "EL_STEPS": "6",
        "EL_DIE_AT": "3",
        "HVD_TPU_CKPT_DIR": ckpt_dir,
        "HVD_TPU_CKPT_INTERVAL": "1",
    })
    for r in range(4):
        assert phase1[r][0] == 1, f"rank {r}: {phase1[r][1]}"
    manifests = store.list_manifests(ckpt_dir)
    assert manifests and all(w == 4 for _s, _e, w in manifests)

    phase2 = spawn_tcp_ranks(3, CKPT_WORKER, timeout=150, extra_env={
        **_EL_ENV,
        "HVD_TPU_ELASTIC": "1",
        "EL_STEPS": "6",
        "HVD_TPU_CKPT_DIR": ckpt_dir,
        "HVD_TPU_CKPT_INTERVAL": "1",
    })
    assert "resumed from step 3" in phase2[0][2], phase2[0][2]
    got = _digests(phase2, ranks=[0, 1, 2])
    for r, (digest, size, steps) in got.items():
        assert size == 3 and steps == 6
    assert got[0][0] == _reference_digest(3, 6), got


BUSY_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.checkpoint import CheckpointManager

hvd.init()
state = hvd.elastic.State(
    params={"w": jnp.zeros((1000,), dtype=jnp.float32)}, step=0)
m = CheckpointManager(os.environ["CKPT_DIR"], interval_steps=1, keep=0,
                      io_delay=float(os.environ["CKPT_IO_DELAY"]))
state.attach_checkpoint(m)
try:
    for _ in range(2):
        g = jnp.ones((1000,), dtype=jnp.float32)
        avg = hvd.allreduce(g, op=hvd.Average,
                            name=f"busy.{state.step}")
        state.params = {"w": state.params["w"] - avg}
        state.step += 1
        state.commit()
        assert m.wait(timeout=60)   # sit inside the throttled write
    # a collective AFTER the slow writes: the job must still be alive
    hvd.allreduce(jnp.ones((1000,), dtype=jnp.float32),
                  name="busy.final")
    assert m._errors == 0
    print(f"rank {hvd.rank()} BUSY_OK", flush=True)
finally:
    state.attach_checkpoint(None)
    m.close()
hvd.shutdown()
"""


@pytest.mark.slow
def test_throttled_writer_does_not_trip_liveness(tmp_path):
    """Liveness-interplay regression: each write sleeps 3 s inside the
    busy window while the liveness window is 2 s.  The busy-flagged
    heartbeats must keep every rank alive — no abort, no drain, both
    ranks finish clean."""
    results = spawn_tcp_ranks(2, BUSY_WORKER, timeout=120, extra_env={
        **_EL_ENV,
        "CKPT_DIR": str(tmp_path / "ckpt"),
        "CKPT_IO_DELAY": "3.0",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
    })
    for r in (0, 1):
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err}"
        assert "BUSY_OK" in out, f"rank {r}: {out}"
        assert "ABORTED" not in out


# ----------------------------------------------------------- race shim ------
RACE_CKPT_BODY = r"""
import os
import numpy as np
import horovod_tpu  # installs the race shim under HVD_TPU_RACE=1
from horovod_tpu.checkpoint import CheckpointManager
from horovod_tpu.elastic.state import State

state = State(params={"w": np.zeros((256,), np.float32)},
              optimizer_state={"m": np.zeros((256,), np.float32)})
m = CheckpointManager(os.environ["CKPT_DIR"], interval_steps=1, keep=2)
state.attach_checkpoint(m)
for _ in range(5):
    state.params["w"] = state.params["w"] + 1.0
    state.step += 1
    state.commit()       # racing the writer thread on purpose
assert m.wait(timeout=60)
fresh = State(params={"w": np.zeros((256,), np.float32)},
              optimizer_state={"m": np.zeros((256,), np.float32)})
assert m.restore_latest(fresh) is not None
m.close()
assert m._errors == 0
print("RACE_CKPT_OK", flush=True)
"""


@pytest.mark.slow
def test_checkpoint_writer_clean_under_race_shim(tmp_path):
    """The commit-path/writer-thread handoff (latest-wins slot, busy
    window, close/flush join) under the hvd-race shim with a fixed
    seed: zero non-baselined race reports."""
    from horovod_tpu.tools.lint import findings as findings_mod

    script = tmp_path / "race_ckpt_worker.py"
    script.write_text(RACE_CKPT_BODY)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_RACE": "1",
        "HVD_TPU_RACE_SEED": "3",
        "HVD_TPU_RACE_REPORT": str(tmp_path / "ckpt"),
        "CKPT_DIR": str(tmp_path / "store"),
    })
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=180,
                         cwd=REPO)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "RACE_CKPT_OK" in out.stdout

    baseline = findings_mod.load_baseline(
        os.path.join(REPO, ".hvd-race-baseline.json"))
    active = []
    for path in sorted(glob.glob(str(tmp_path / "ckpt.*.json"))):
        with open(path) as f:
            data = json.load(f)
        active.extend(f for f in data["findings"]
                      if f["key"] not in baseline)
    assert not active, "\n".join(f["message"] for f in active)
