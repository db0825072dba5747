"""Fault-tolerant collective runtime tests (docs/fault_tolerance.md).

Unit layer: fault-spec grammar, the purge LRU bound, abort waking a
blocked mailbox recv, connect retry with backoff.

Integration layer: the crash / drop / refuse x allreduce / broadcast /
allgather matrix against real worker processes on the tcp plane — each
cell is driven by a deterministic ``HVD_TPU_FAULT_SPEC`` so the failure
fires at an exact step, and the assertion is the acceptance criterion:
every surviving rank raises ``HvdAbortedError`` naming the origin rank
within the abort deadline, no hangs, no leaked mailbox chunks.
"""

import threading
import time

import pytest

import ring_rig
from conftest import spawn_tcp_ranks
from horovod_tpu.common import faults
from horovod_tpu.common.handles import HvdAbortedError


# ------------------------------------------------------------ spec grammar --
def test_fault_spec_grammar():
    specs = faults.parse_fault_spec(
        "rank1:allreduce:2:crash, rank0:send:5:drop ,*:connect:1:refuse")
    assert [(s.rank, s.point, s.step, s.action) for s in specs] == [
        (1, "allreduce", 2, "crash"),
        (0, "send", 5, "drop"),
        (None, "connect", 1, "refuse"),
    ]
    assert faults.parse_fault_spec("") == []
    assert faults.parse_fault_spec(None) == []


@pytest.mark.parametrize("bad", [
    "rank1:allreduce:crash",          # missing field
    "node1:allreduce:1:crash",        # bad target
    "rank1:allreduce:0:crash",        # step is 1-based
    "rank1:allreduce:x:crash",        # non-integer step
    "rank1:allreduce:1:explode",      # unknown action
    "rank1::1:crash",                 # empty point
])
def test_fault_spec_rejects_bad_grammar(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_spec(bad)


def test_injector_fires_at_exact_step_for_matching_rank():
    inj = faults.FaultInjector(
        faults.parse_fault_spec("rank1:send:3:drop,*:recv:2:refuse"),
        rank=1)
    assert [inj.fire("send") for _ in range(4)] == [
        None, None, "drop", None]
    assert [inj.fire("recv") for _ in range(3)] == [None, "refuse", None]
    # rank mismatch: counter still advances, fault never fires
    other = faults.FaultInjector(
        faults.parse_fault_spec("rank1:send:1:drop"), rank=0)
    assert [other.fire("send") for _ in range(3)] == [None, None, None]


def test_config_validates_fault_spec_at_init(monkeypatch):
    from horovod_tpu.common.config import Config

    monkeypatch.setenv("HVD_TPU_FAULT_SPEC", "rank1:allreduce:1:explode")
    with pytest.raises(ValueError, match="action"):
        Config.from_env()


# --------------------------------------------------------- peer mailbox -----
def _peer_service():
    from horovod_tpu.ops.tcp_dataplane import PeerService
    from horovod_tpu.run.service import secret

    return PeerService(secret.make_secret_key())


def _push_chunk(svc, ring_id, src=1, payload=b"x"):
    from horovod_tpu.ops.tcp_dataplane import ChunkMsg

    svc._handle(ChunkMsg(((ring_id, "rs", 0)), src, payload), None)


def test_purged_ring_ids_are_a_bounded_lru():
    svc = _peer_service()
    try:
        for ring_id in range(1000):
            svc.purge(ring_id)
        assert len(svc._purged) == svc._PURGED_KEEP
        # late chunk of a recently purged round is dropped
        _push_chunk(svc, 999)
        assert svc._mailbox == {}
        # re-purging a hot id refreshes its LRU slot instead of letting
        # a newer purge evict it
        svc.purge(1000 - svc._PURGED_KEEP)  # oldest retained id
        svc.purge(2000)  # evicts the NEXT-oldest, not the refreshed one
        assert (1000 - svc._PURGED_KEEP) in svc._purged
        assert (1001 - svc._PURGED_KEEP) not in svc._purged
        # an id evicted from the LRU is forgotten: its chunks land again
        _push_chunk(svc, 0)
        assert len(svc._mailbox) == 1
    finally:
        svc.shutdown()


def test_abort_wakes_blocked_recv_and_purges_mailbox():
    svc = _peer_service()
    try:
        _push_chunk(svc, 7, src=2)
        assert len(svc._mailbox) == 1
        caught = []

        def blocked_recv():
            try:
                svc.recv(((99, "rs", 0)), 3, timeout=30)
            except BaseException as exc:  # noqa: BLE001
                caught.append(exc)

        t = threading.Thread(target=blocked_recv, daemon=True)
        t.start()
        time.sleep(0.2)
        start = time.monotonic()
        svc.abort(5, "injected test abort")
        t.join(timeout=5)
        assert not t.is_alive(), "abort did not wake the blocked recv"
        assert time.monotonic() - start < 2.0
        assert isinstance(caught[0], HvdAbortedError)
        assert caught[0].origin_rank == 5
        # no leaked chunks: buffer purged, late arrivals refused
        assert svc._mailbox == {}
        _push_chunk(svc, 8)
        assert svc._mailbox == {}
        # sticky: the next recv fails immediately too
        with pytest.raises(HvdAbortedError):
            svc.recv(((100, "rs", 0)), 1, timeout=5)
    finally:
        svc.shutdown()


# ------------------------------------------------------- transport retry ----
def test_basic_client_retries_refused_connects_with_backoff():
    from horovod_tpu.run.service import network, secret

    key = secret.make_secret_key()
    svc = network.BasicService("retry target", key)
    try:
        faults.configure("*:connect:1:refuse,*:connect:2:refuse", rank=0)
        client = network.BasicClient([("127.0.0.1", svc.port)], key,
                                     retry_for=20)
        resp = client.send(network.PingRequest())
        assert isinstance(resp, network.PingResponse)
    finally:
        faults.configure(None)
        svc.shutdown()


def test_basic_client_retry_budget_zero_fails_fast():
    from horovod_tpu.run.service import network, secret

    client = network.BasicClient([("127.0.0.1", 1)],
                                 secret.make_secret_key(),
                                 timeout=1, retry_for=0)
    start = time.monotonic()
    with pytest.raises(ConnectionError):
        client.send(network.PingRequest())
    assert time.monotonic() - start < 5.0


def test_mux_client_retries_refused_connects():
    from horovod_tpu.run.service import network, secret

    key = secret.make_secret_key()
    svc = network.MuxService("mux retry target", key)
    try:
        faults.configure("*:connect:1:refuse", rank=0)
        client = network.MuxClient([("127.0.0.1", svc.port)], key,
                                   retry_for=20)
        resp = client.send((network.PingRequest()), timeout=10)
        assert isinstance(resp, network.PingResponse)
        client.close()
    finally:
        faults.configure(None)
        svc.shutdown()


def test_http_client_all_verbs_with_retry():
    from horovod_tpu.run import http_client
    from horovod_tpu.run.http_server import RendezvousServer

    server = RendezvousServer()
    port = server.start()
    try:
        http_client.put("127.0.0.1", port, "s", "k", b"v")
        assert http_client.get("127.0.0.1", port, "s", "k") == b"v"
        http_client.delete("127.0.0.1", port, "s", "k")
        with pytest.raises(KeyError):
            http_client.get("127.0.0.1", port, "s", "k", timeout=0.2)
    finally:
        server.stop()
    # dead endpoint: the bounded retry gives up within its budget
    start = time.monotonic()
    with pytest.raises(OSError):
        http_client.get("127.0.0.1", port, "s", "k", retry_for=0.5)
    assert time.monotonic() - start < 10.0


# ----------------------------------------------- launcher culprit naming ----
def test_safe_shell_exec_reports_event_termination():
    import sys

    from horovod_tpu.run import safe_shell_exec

    # natural failure: no event involvement recorded; the exit
    # timestamp is recorded for the launcher's death-order attribution
    info = {}
    code = safe_shell_exec.execute([sys.executable, "-c", "exit(3)"],
                                   info=info)
    assert code == 3
    assert not info.get("terminated_by_event")
    assert info.get("exit_ts") is not None

    # event-driven kill: the victim is marked so the launcher does not
    # blame it for the job failure
    event = threading.Event()
    info = {}
    threading.Timer(0.3, event.set).start()
    code = safe_shell_exec.execute(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        events=[event], info=info)
    assert code != 0
    assert info.get("terminated_by_event") is True


def test_culprit_attribution_survives_reap_order_skew():
    """Deflake regression (the load-sensitive culprit flake): reap
    order is NOT death order — stream-forwarder drains and thread
    scheduling sit between a child dying and its failure being
    recorded, so under machine load a survivor that exits nonzero
    because of the coordinated abort can be reaped BEFORE the rank
    whose death caused it.  Attribution must rank by exit timestamp
    and by the fault spec's own crash ranks, never by arrival."""
    from horovod_tpu.run.launch import fault_crash_ranks, pick_culprit
    from horovod_tpu.utils import env as env_util

    # induced reap-order skew: the survivor (abort exit, ts 105) was
    # recorded first; the true culprit (died at ts 100) second
    failures = [(0, 1, False, 105.0), (1, 7, False, 100.0)]
    assert pick_culprit(failures) == (1, 7)

    # a victim of the kill fan-out never steals the blame, even with
    # the earliest timestamp
    failures = [(2, -15, True, 99.0), (1, 7, False, 100.0)]
    assert pick_culprit(failures) == (1, 7)

    # all-victims (launcher interrupt edge case): fall back to the
    # earliest observed death
    failures = [(2, -15, True, 99.0), (0, -15, True, 98.0)]
    assert pick_culprit(failures) == (0, -15)

    # an injected-crash rank is the culprit by construction — timing
    # evidence cannot outvote the fault spec
    failures = [(0, 1, False, 100.0), (1, 1, False, 101.0)]
    assert pick_culprit(failures, frozenset({1})) == (1, 1)

    # a missing timestamp (launch-phase failure) sorts last
    failures = [(0, 1, False, None), (1, 7, False, 100.0)]
    assert pick_culprit(failures) == (1, 7)

    # crash-rank extraction from the worker env contract
    assert fault_crash_ranks(
        {env_util.HVD_TPU_FAULT_SPEC:
         "rank1:ring:1:crash,rank0:send:2:drop,*:connect:1:refuse"}) \
        == frozenset({1})
    assert fault_crash_ranks({}) == frozenset()
    assert fault_crash_ranks(
        {env_util.HVD_TPU_FAULT_SPEC: "garbage"}) == frozenset()


# ------------------------------------------------------ injected matrix -----
# Worker for the crash/drop x op matrix: runs one collective; on a
# coordinated abort it reports the origin rank, the elapsed time and
# the mailbox residue so the test can assert the acceptance criterion.
MATRIX_WORKER = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
op = os.environ["FT_OP"]
n_elems = int(os.environ.get("FT_SIZE", "70000"))
t = jnp.ones((n_elems,)) * (r + 1)
start = time.monotonic()
try:
    if op == "allreduce":
        hvd.allreduce(t, op=hvd.Sum, name="ft.tensor")
    elif op == "broadcast":
        hvd.broadcast(t, root_rank=0, name="ft.tensor")
    else:
        hvd.allgather(t, name="ft.tensor")
    print(f"rank {r} COMPLETED", flush=True)
except hvd.HvdAbortedError as exc:
    elapsed = time.monotonic() - start
    from horovod_tpu.common import basics
    svc = basics._get_state().controller._peer_service
    leaked = len(svc._mailbox) if svc is not None else 0
    print(f"rank {r} ABORTED origin={exc.origin_rank} "
          f"elapsed={elapsed:.1f} leaked={leaked}", flush=True)
print(f"rank {r} DONE", flush=True)
"""

# tight failure-detection windows so each cell stays tier-1 fast; the
# abort deadline stays well above them so elapsed < deadline is a real
# bound, not a tautology
_FT_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
    "HVD_TPU_ABORT_TIMEOUT": "10",
    "HVD_STALL_CHECK_TIME_SECONDS": "1",
    "HVD_TCP_RING_THRESHOLD": "1024",
}


def _assert_aborted(out, rank, origin, deadline=10.0):
    line = next(l for l in out.splitlines()
                if l.startswith(f"rank {rank} ABORTED"))
    fields = dict(kv.split("=") for kv in line.split()[3:])
    allowed = origin if isinstance(origin, tuple) else (origin,)
    assert fields["origin"] in {str(o) for o in allowed}, line
    assert float(fields["elapsed"]) < deadline, line
    assert fields["leaked"] == "0", line


@pytest.mark.parametrize("op", ["allreduce", "broadcast", "allgather"])
def test_injected_crash_aborts_survivor(op):
    """Rank 1 hard-exits at its first <op> submit (pre-negotiation, so
    this exercises the coordinator-star side): the liveness monitor
    notices the silence and rank 0 raises HvdAbortedError(origin=1)."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": op,
        "FT_SIZE": "8",  # below the ring threshold: star path
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "20",
        "HVD_TPU_FAULT_SPEC": f"rank1:{op}:1:crash",
    })
    code0, out0, err0 = results[0]
    code1, out1, err1 = results[1]
    assert code1 == 1, f"crashed rank: {out1}\n{err1}"
    assert code0 == 0, f"survivor: {out0}\n{err0}"
    _assert_aborted(out0, rank=0, origin=1)


def test_injected_crash_mid_ring_allreduce():
    """The acceptance scenario: rank 1 dies AFTER the coordinator's
    ring go-ahead, with rank 0 already blocked on its chunks — the ring
    path's worst case.  Liveness converts the silence into an abort and
    the blocked recv wakes with the typed error, mailbox clean.

    origin=1 is deterministic whichever detector fires first under
    machine load: liveness names the silent rank, and the survivor's
    own hard failure evidence (RingSendError — the transport write to
    the dead peer broke) now carries the peer rank into the abort
    origin instead of blaming the rank that noticed.  (A recv timeout
    deliberately still names the noticing rank: in a 3+-rank ring the
    silent predecessor is usually an innocent rank blocked behind the
    real casualty — and its 30s bound can never beat the 2s liveness
    window here anyway.)"""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": "allreduce",
        "FT_SIZE": "70000",  # above the ring threshold: ring path
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        # keep the ring recv timeout far beyond liveness so the typed
        # abort (origin=the dead rank), not a local TimeoutError, is
        # what wakes the survivor
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TPU_FAULT_SPEC": "rank1:ring:1:crash",
    })
    code0, out0, err0 = results[0]
    code1, out1, _ = results[1]
    assert code1 == 1, f"crashed rank: {out1}"
    assert code0 == 0, f"survivor: {out0}\n{err0}"
    _assert_aborted(out0, rank=0, origin=1)


@pytest.mark.parametrize("op", ["allreduce", "broadcast", "allgather"])
def test_injected_drop_promotes_stall_into_abort(op):
    """Rank 1 silently drops its contribution (the rank is alive and
    heartbeating — liveness can't see it): the stall inspector promotes
    the stalled tensor into a coordinated abort naming rank 1, and BOTH
    ranks — including the dropper, whose handle would otherwise wait
    forever — raise the same typed error."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": op,
        "FT_SIZE": "8",
        "HVD_TPU_LIVENESS_TIMEOUT": "30",  # must NOT fire: rank 1 lives
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "2",
        "HVD_TPU_FAULT_SPEC": f"rank1:{op}:1:drop",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        _assert_aborted(out, rank=rank, origin=1)


def test_injected_send_drop_bounded_without_stall_shutdown():
    """A chunk silently dropped on the wire AFTER negotiation is the
    failure neither liveness (the sender is alive and heartbeating) nor
    the stall inspector (negotiation completed) can see: the ring-recv
    backstop (4x the abort deadline) must convert it into a coordinated
    abort even with the stall shutdown off — the default config."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": "allreduce",
        "FT_SIZE": "70000",  # ring path
        "HVD_TPU_ABORT_TIMEOUT": "1",  # recv backstop = 4s
        "HVD_TPU_LIVENESS_TIMEOUT": "30",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "0",
        "HVD_TPU_FAULT_SPEC": "rank0:send:1:drop",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        # whichever blocked rank's backstop fires first names itself
        _assert_aborted(out, rank=rank, origin=(0, 1))


@pytest.mark.parametrize("op", ["allreduce", "broadcast", "allgather"])
def test_injected_connect_refusals_are_retried(op):
    """Both ranks' first two connection attempts are refused: the
    backoff retry carries rendezvous/negotiation through and the
    collective completes exactly — a transport blip is not a failure."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": op,
        "FT_SIZE": "70000",  # ring path: peer connects retried too
        "HVD_TPU_LIVENESS_TIMEOUT": "30",
        "HVD_TPU_FAULT_SPEC": "*:connect:1:refuse,*:connect:2:refuse",
        "HVD_TPU_CONNECT_RETRY_SECONDS": "20",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        assert f"rank {rank} COMPLETED" in out, f"{out}\n{err}"


def test_user_abort_reaches_blocked_peer():
    """hvd.abort() from one rank fails a peer blocked in negotiation
    with the typed error naming the aborting rank."""
    script = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
start = time.monotonic()
try:
    if r == 1:
        time.sleep(1.0)  # let rank 0 block in negotiation first
        hvd.abort("operator says no")
        # the sticky abort fails this rank's own next submit too
        try:
            hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="after")
            print(f"rank {r} UNEXPECTED-OK", flush=True)
        except hvd.HvdAbortedError:
            print(f"rank {r} STICKY-OK", flush=True)
    else:
        hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="ua.tensor")
        print(f"rank {r} COMPLETED", flush=True)
except hvd.HvdAbortedError as exc:
    print(f"rank {r} ABORTED origin={exc.origin_rank} "
          f"elapsed={time.monotonic() - start:.1f} leaked=0", flush=True)
print(f"rank {r} DONE", flush=True)
"""
    results = spawn_tcp_ranks(2, script, extra_env={
        **_FT_ENV,
        "HVD_TPU_LIVENESS_TIMEOUT": "30",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
    })
    code0, out0, err0 = results[0]
    code1, out1, err1 = results[1]
    assert code0 == 0, f"{out0}\n{err0}"
    assert code1 == 0, f"{out1}\n{err1}"
    _assert_aborted(out0, rank=0, origin=1)
    assert "rank 1 STICKY-OK" in out1, out1


def test_launcher_names_culprit_rank():
    """End-to-end through hvdrun: a rank that dies on its own is named
    as the culprit — the SIGTERMed victims can no longer steal the
    blame with their -15 (satellite: exit-code/rank propagation)."""
    import os
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_culprit_worker.py")
    with open(path, "w") as f:
        f.write(r"""
import os, sys, time
rank = int(os.environ["HVD_RANK"])
if rank == 1:
    time.sleep(0.5)
    sys.exit(7)
time.sleep(30)
""")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "bin", "hvdrun"), "-np", "2",
         sys.executable, path],
        env=env, capture_output=True, text=True, timeout=120)
    assert result.returncode == 7, result.stderr
    assert "rank 1 failed first (exit code 7)" in result.stderr, \
        result.stderr


def test_hvd_chaos_prints_reproducible_spec():
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    chaos = os.path.join(repo, "bin", "hvd-chaos")

    def spec_for(seed):
        out = subprocess.run(
            [sys.executable, chaos, "--seed", str(seed), "--faults", "2",
             "--", "-np", "2", "--version"],
            env=env, capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        line = next(l for l in out.stdout.splitlines()
                    if "HVD_TPU_FAULT_SPEC=" in l)
        spec = line.split("HVD_TPU_FAULT_SPEC=")[1].strip("'\"")
        faults.parse_fault_spec(spec)  # valid grammar
        return spec

    assert spec_for(7) == spec_for(7)       # same seed -> same spec
    assert spec_for(7) != spec_for(8)       # different seed -> different


# ------------------------------------ sub-group collectives (groups.md) -----
# Two 2-rank process groups; the failure is injected INSIDE one group's
# collective.  Group-scoped abort semantics: the whole job dies typed
# with the true origin — including the OTHER group's members, who were
# busy with their own healthy collective — and no per-group ring state
# leaks (PeerService purge is group-aware).
GROUP_MATRIX_WORKER = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
lo = hvd.new_group([0, 1], name="ft.lo")
hi = hvd.new_group([2, 3], name="ft.hi")
mine = lo if r < 2 else hi
n_elems = int(os.environ.get("FT_SIZE", "8"))
t = jnp.ones((n_elems,)) * (r + 1)
# every rank leaves from one line (the faulted rank's allreduce 1),
# whatever the machine's load did to the ranks' start-up
hvd.barrier(name="ft.start")
start = time.monotonic()
try:
    hvd.allreduce(t, op=hvd.Sum, name="ft.group", group=mine)
    # the healthy group reaches the world barrier and must ALSO die; it
    # gets there well after rank 0's request for "ft.group" reached the
    # coordinator, so that of two stalled entries the GROUP's is the
    # older and its missing rank, not the barrier's, the one named
    time.sleep(1.5)
    hvd.barrier(name="ft.join")
    print(f"rank {r} COMPLETED", flush=True)
except hvd.HvdAbortedError as exc:
    elapsed = time.monotonic() - start
    from horovod_tpu.common import basics
    svc = basics._get_state().controller._peer_service
    leaked = len(svc._mailbox) if svc is not None else 0
    print(f"rank {r} ABORTED origin={exc.origin_rank} "
          f"elapsed={elapsed:.1f} leaked={leaked}", flush=True)
print(f"rank {r} DONE", flush=True)
"""


def test_injected_crash_inside_subgroup_aborts_whole_job():
    """Rank 1 hard-exits at its group's allreduce submit: every
    survivor — group peer AND both members of the other, healthy group
    — raises HvdAbortedError naming rank 1."""
    results = spawn_tcp_ranks(4, GROUP_MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_SIZE": "8",  # star path
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "20",
        "HVD_TPU_FAULT_SPEC": "rank1:allreduce:2:crash",
    })
    assert results[1][0] == 1, f"crashed rank: {results[1][1]}"
    for rank in (0, 2, 3):
        code, out, err = results[rank]
        assert code == 0, f"rank {rank}: {out}\n{err}"
        _assert_aborted(out, rank=rank, origin=1)


def test_injected_crash_mid_subgroup_ring_no_leaked_state():
    """Rank 1 dies after its GROUP ring's go-ahead with rank 0 blocked
    on chunks in the group-qualified ring namespace: the abort wakes
    the blocked recv typed and the group-aware purge leaves zero
    mailbox residue on every survivor."""
    results = spawn_tcp_ranks(4, GROUP_MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_SIZE": "70000",  # above the ring threshold: group rings
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TPU_FAULT_SPEC": "rank1:ring:1:crash",
    })
    assert results[1][0] == 1, f"crashed rank: {results[1][1]}"
    for rank in (0, 2, 3):
        code, out, err = results[rank]
        assert code == 0, f"rank {rank}: {out}\n{err}"
        _assert_aborted(out, rank=rank, origin=1)


def test_injected_drop_inside_subgroup_promotes_stall():
    """Rank 1 silently skips its group contribution while heartbeating:
    the stall inspector sees the half-reported GROUP entry, promotes it
    into a coordinated abort naming rank 1, and all four ranks — the
    dropper included — fail typed."""
    results = spawn_tcp_ranks(4, GROUP_MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_SIZE": "8",
        "HVD_TPU_LIVENESS_TIMEOUT": "30",  # must NOT fire: rank 1 lives
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "2",
        "HVD_TPU_FAULT_SPEC": "rank1:allreduce:2:drop",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        _assert_aborted(out, rank=rank, origin=1)


# ----------------------------------------- pipelined stripe data plane ------
def test_abort_wakes_blocked_stripe_recv_mid_pipeline():
    """A recv blocked on the MISSING segments of a partially-delivered
    chunk (some stripes delivered, one wedged) must wake with the typed
    error when the abort lands — stripe sockets are covered by the same
    mailbox condition the abort signals."""
    services, planes = ring_rig.ring_harness(2, 1024, 2)
    try:
        # rank 0 delivers only the FIRST segment of a 3-segment chunk
        # (simulating a wedged stripe): enqueue segment 0 directly
        planes[0]._enqueue_segment(1, 0, (42, "rs", 0, 0), b"x" * 1024)
        planes[0]._flush_sends(5)
        caught = []

        def blocked():
            try:
                planes[1].recv_chunk((42, "rs", 0), 0, 3 * 1024,
                                     timeout=30)
            except BaseException as exc:  # noqa: BLE001
                caught.append(exc)

        t = threading.Thread(target=blocked, daemon=True)
        t.start()
        time.sleep(0.3)
        assert t.is_alive(), "recv should be blocked on segment 1"
        start = time.monotonic()
        services[1].abort(0, "injected stripe abort")
        t.join(timeout=5)
        assert not t.is_alive(), "abort did not wake the stripe recv"
        assert time.monotonic() - start < 2.0
        assert isinstance(caught[0], HvdAbortedError)
        # the already-delivered segment did not leak
        assert services[1]._mailbox == {}
        assert services[1]._by_ring == {}
    finally:
        for plane in planes:
            plane.close()
        for svc in services:
            svc.shutdown()


def test_purge_drops_stale_segments_mid_pipeline_and_is_ring_indexed():
    """Purging an aborted round drops exactly that ring's buffered
    segments (O(chunks of the ring) via the ring-id index), refuses its
    late-arriving stripe segments, and leaves other rounds' chunks
    untouched."""
    services, planes = ring_rig.ring_harness(2, 1024, 2)
    try:
        svc = services[1]
        # segments of two interleaved rounds, delivered over stripes
        for seg in range(3):
            planes[0]._enqueue_segment(1, seg, (7, "rs", 0, seg),
                                       b"a" * 100)
        planes[0]._enqueue_segment(1, 0, (8, "ag", 0, 0), b"b" * 100)
        planes[0]._flush_sends(5)
        deadline = time.monotonic() + 5
        while len(svc._mailbox) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(svc._mailbox) == 4
        assert set(svc._by_ring) == {7, 8}

        svc.purge(7)
        assert len(svc._mailbox) == 1, svc._mailbox
        assert set(svc._by_ring) == {8}
        # a straggler segment of the purged round is refused...
        planes[0]._enqueue_segment(1, 1, (7, "rs", 0, 3), b"late")
        planes[0]._flush_sends(5)
        time.sleep(0.2)
        assert len(svc._mailbox) == 1
        # ...while the live round's chunk is still collectable
        got = planes[1].recv_chunk((8, "ag", 0), 0, 100, timeout=5)
        assert bytes(got) == b"b" * 100
        assert svc._by_ring == {}
    finally:
        for plane in planes:
            plane.close()
        for svc in services:
            svc.shutdown()


def test_sender_thread_failure_fails_the_round_fast():
    """A bulk send that fails (dead stripe peer) surfaces on the
    compute thread as a ConnectionError instead of a silent stall."""
    from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
    from horovod_tpu.run.service import network, secret

    key = secret.make_secret_key()
    svc = PeerService(key)
    try:
        def resolver(rank):
            return network.MuxClient([("127.0.0.1", svc.port)], key,
                                     timeout=10)

        def resolve_bulk(rank):
            # dead endpoint, no retry budget: post_bulk fails fast
            return network.StripeClient([("127.0.0.1", 1)], key,
                                        timeout=1, retry_for=0)

        plane = RingPlane(0, svc, resolver, resolve_bulk,
                          segment_bytes=64, stripes=1)
        plane.send_chunk(1, (9, "rs", 0), b"x" * 256)
        with pytest.raises((ConnectionError, TimeoutError)):
            plane._flush_sends(10)
        plane.close()
    finally:
        svc.shutdown()


def test_send_failure_wakes_blocked_recv():
    """A recv already blocked on the mailbox must wake with the send
    failure as soon as the sender thread records it — not after the
    full recv timeout (the peer can never send the segments this rank's
    broken sends were the prerequisite for)."""
    from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
    from horovod_tpu.run.service import network, secret

    key = secret.make_secret_key()
    svc = PeerService(key)
    try:
        def resolver(rank):
            return network.MuxClient([("127.0.0.1", svc.port)], key,
                                     timeout=10)

        def resolve_bulk(rank):
            return network.StripeClient([("127.0.0.1", 1)], key,
                                        timeout=1, retry_for=0)

        plane = RingPlane(0, svc, resolver, resolve_bulk,
                          segment_bytes=64, stripes=1)
        caught = []

        def blocked():
            try:
                plane.recv_chunk((9, "rs", 0), 1, 64, timeout=30)
            except BaseException as exc:  # noqa: BLE001
                caught.append(exc)

        t = threading.Thread(target=blocked, daemon=True)
        t.start()
        time.sleep(0.2)
        assert t.is_alive(), "recv should be blocked"
        start = time.monotonic()
        plane.send_chunk(1, (9, "x", 0), b"y" * 64)  # sender will fail
        t.join(timeout=10)
        assert not t.is_alive(), "send failure did not wake the recv"
        assert time.monotonic() - start < 5.0
        assert isinstance(caught[0], ConnectionError), caught
        plane.close()
    finally:
        svc.shutdown()


# ------------------------------------------- degraded-network tolerance -----
# docs/fault_tolerance.md "degraded networks": duration-scoped link
# degradations (delay/jitter/throttle/flaky/partition), the adaptive
# liveness deadline that tells slow from dead, and the k x median
# straggler verdict.
def test_fault_spec_degrade_grammar_round_trip():
    specs = faults.parse_fault_spec(
        "rank1:link:2:delay:40:6, rank0:link:1:flaky:0.2 ,"
        "*:link:3:throttle:16:2,rank2:link:1:jitter:5:1,"
        "rank0:link:1:partition:2-5:4")
    got = [(s.rank, s.point, s.step, s.action, s.param, s.duration)
           for s in specs]
    assert got == [
        (1, "link", 2, "delay", 40.0, 6.0),
        (0, "link", 1, "flaky", 0.2, None),   # no duration: forever
        (None, "link", 3, "throttle", 16.0, 2.0),
        (2, "link", 1, "jitter", 5.0, 1.0),
        (0, "link", 1, "partition", (2, 5), 4.0),
    ]


@pytest.mark.parametrize("bad", [
    "rank1:allreduce:1:crash:5",       # binary actions take no param
    "rank1:allreduce:1:crash:5:2",     # ... nor a duration
    "rank1:link:1:delay",              # degrade action needs a param
    "rank1:link:1:delay:-1",           # negative delay
    "rank1:link:1:flaky:2",            # probability > 1
    "rank1:link:1:throttle:0",         # zero rate
    "rank1:link:1:partition:5",        # not a range
    "rank1:link:1:partition:5-2",      # inverted range
    "rank1:link:1:delay:10:0",         # zero duration
    "rank1:link:1:degrade:1",          # unknown degrade action
])
def test_fault_spec_rejects_bad_degrade_grammar(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_spec(bad)


def test_link_state_aggregation_and_partition_cut_rule():
    # two delay cells: the worst one wins; partition cuts a link iff
    # exactly one endpoint is inside the range
    inj = faults.FaultInjector(faults.parse_fault_spec(
        "rank0:link:1:delay:10,rank0:link:1:delay:30,"
        "rank0:link:1:partition:2-5"), rank=0)
    state = inj.link(peer=3)
    assert state is not None
    assert state.delay_s == pytest.approx(0.030)
    assert state.partitioned        # rank 0 outside, peer 3 inside
    assert not inj.link(peer=1).partitioned  # both outside: no cut
    # rendezvous-style traffic has no peer identity: never partitioned
    assert not inj.link(peer=None).partitioned


def test_link_faults_are_deterministic_under_the_seed_contract():
    spec = "rank0:link:1:flaky:0.5,rank0:link:1:jitter:50"
    def rolls(rank):
        inj = faults.FaultInjector(faults.parse_fault_spec(spec),
                                   rank=rank, seed_text=spec)
        out = []
        for _ in range(32):
            s = inj.link(peer=1)
            out.append((s.drop, round(s.delay_s, 6)))
        return out
    assert rolls(0) == rolls(0)          # same rank: same stream
    # per-rank decorrelation: rank 1's cells target rank 0 only, so
    # build a rank-1 injector with its own cell to compare streams
    spec1 = spec.replace("rank0", "rank1")
    inj1 = faults.FaultInjector(faults.parse_fault_spec(spec1),
                                rank=1, seed_text=spec1)
    rolls1 = [(s.drop, round(s.delay_s, 6))
              for s in (inj1.link(peer=0) for _ in range(32))]
    assert rolls1 != rolls(0)


def test_degrade_cells_arm_at_step_and_expire_after_duration():
    inj = faults.FaultInjector(faults.parse_fault_spec(
        "rank0:link:3:delay:20:0.15"), rank=0)
    assert inj.link(peer=1) is None      # hit 1: not armed yet
    assert inj.link(peer=1) is None      # hit 2
    state = inj.link(peer=1)             # hit 3: armed
    assert state is not None and state.delay_s == pytest.approx(0.020)
    time.sleep(0.2)                      # past the 0.15s duration
    assert inj.link(peer=1) is None      # expired


# ------------------------- slow vs dead: the adaptive liveness deadline -----
def _coordinator(**kwargs):
    from horovod_tpu.ops.tcp_controller import CoordinatorService
    from horovod_tpu.run.service import secret

    return CoordinatorService(3, secret.make_secret_key(), **kwargs)


def test_adaptive_deadline_composes_busy_and_rtt_without_double_double():
    svc = _coordinator(liveness_timeout_sec=10.0, straggler_factor=4.0)
    try:
        with svc._cv:
            base = svc._deadline_for_locked(1)
            svc._busy_ranks.add(1)
            busy = svc._deadline_for_locked(1)
            svc._peer_rtt[1] = 0.5
            both = svc._deadline_for_locked(1)
            svc._busy_ranks.discard(1)
            rtt_only = svc._deadline_for_locked(1)
        assert base == pytest.approx(10.0)
        assert busy == pytest.approx(20.0)       # busy MULTIPLIES
        assert rtt_only == pytest.approx(12.0)   # rtt ADDS (0.5 * 4)
        # composed: busy doubles the base, rtt adds on top — the rtt
        # slack itself is NOT doubled by the busy flag
        assert both == pytest.approx(22.0)
        # pathological report: slack capped at factor x base window
        with svc._cv:
            svc._peer_rtt[1] = 1e9
            capped = svc._deadline_for_locked(1)
        assert capped == pytest.approx(10.0 + 40.0)
    finally:
        svc.shutdown()


def test_slow_rank_outlives_fixed_window_dead_rank_does_not():
    """The discrimination the whole feature exists for: with identical
    silence, the rank that REPORTED a slow link survives a scan that
    declares the non-reporting rank dead."""
    svc = _coordinator(liveness_timeout_sec=0.4, straggler_factor=4.0)
    try:
        now = time.monotonic()
        with svc._cv:
            # both silent for ~2 base windows; rank 1 reported a 0.5s
            # RTT beforehand (slack 2.0s), rank 2 reported nothing
            svc._last_seen[1] = now - 0.8
            svc._last_seen[2] = now - 0.8
            svc._peer_rtt[1] = 0.5
            svc._last_liveness_scan = 0.0
        svc._check_liveness()
        assert svc._abort is not None
        origin, reason = svc._abort
        assert origin == 2 and "presumed dead" in reason
    finally:
        svc.shutdown()


def test_liveness_scan_is_time_gated_not_per_heartbeat():
    svc = _coordinator(liveness_timeout_sec=30.0)
    try:
        with svc._cv:
            svc._last_seen[1] = time.monotonic() - 1e6  # long dead
            svc._last_liveness_scan = time.monotonic()  # just scanned
        svc._check_liveness()   # gated: no scan, no abort
        assert svc._abort is None
        with svc._cv:
            svc._last_liveness_scan = 0.0
        svc._check_liveness()   # gate open: the dead rank is found
        assert svc._abort is not None
    finally:
        svc.shutdown()


def test_straggler_verdict_needs_consecutive_windows_and_is_sticky():
    svc = _coordinator(liveness_timeout_sec=30.0, straggler_factor=4.0,
                       straggler_windows=2)
    try:
        with svc._cv:
            svc._peer_rtt.update({0: 0.01, 1: 0.01, 2: 0.5})
            assert svc._straggler_scan_locked() is None  # 1st window
            assert svc._straggler_scan_locked() is None  # exclusion off
        verdicts = svc.straggler_verdicts()
        assert list(verdicts) == [2]
        assert verdicts[2]["factor"] == 4.0
        with svc._cv:
            # a recovered rank resets its streak before a verdict
            svc._straggler_hits[1] = 1
            svc._peer_rtt[1] = 0.01
            svc._straggler_scan_locked()
            assert 1 not in svc._straggler_hits
        # verdict is sticky: recorded once, not re-logged every scan
        assert list(svc.straggler_verdicts()) == [2]
    finally:
        svc.shutdown()


def test_straggler_scan_requires_three_reporters():
    svc = _coordinator(liveness_timeout_sec=30.0, straggler_factor=2.0)
    try:
        with svc._cv:
            svc._peer_rtt.update({1: 0.01, 2: 5.0})
            for _ in range(10):
                assert svc._straggler_scan_locked() is None
        assert svc.straggler_verdicts() == {}
    finally:
        svc.shutdown()


def test_rtt_tracker_ewma_and_worst():
    from horovod_tpu.common import rtt

    t = rtt.RttTracker(alpha=0.5)
    assert t.worst() == 0.0
    t.sample(rtt.COORD_KEY, 0.1)
    t.sample(("peer", 3), 0.4)
    t.sample(("peer", 3), 0.2)          # ewma: 0.3
    assert t.get(("peer", 3)) == pytest.approx(0.3)
    assert t.worst() == pytest.approx(0.3)
    t.clear()
    assert t.worst() == 0.0 and t.snapshot() == {}
    assert rtt.median([3.0, 1.0, 2.0]) == 2.0
    assert rtt.median([4.0, 1.0, 2.0, 3.0]) == 2.5


# ------------------------ degradation x collective integration matrix -------
@pytest.mark.parametrize("op", ["allreduce", "broadcast", "allgather"])
def test_delayed_link_completes_without_abort(op):
    """A 60ms injected delay on every frame rank 1 writes makes it
    measurably slow — but slow is not dead: the collective completes
    exactly and nobody aborts (the no-false-positive criterion)."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": op,
        "FT_SIZE": "70000",  # ring path: bulk stripes feel it too
        "HVD_TPU_LIVENESS_TIMEOUT": "15",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TPU_FAULT_SPEC": "rank1:link:1:delay:60",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        assert f"rank {rank} COMPLETED" in out, f"{out}\n{err}"
        assert "ABORTED" not in out, out


def test_flaky_link_is_transparent_to_the_collective():
    """30% frame loss toward rank 1's peers: the link layer re-rolls
    the lost writes in place (the TCP-retransmit analog), the
    collective completes exactly, and the once-per-peer marker proves
    the chaos actually engaged."""
    results = spawn_tcp_ranks(2, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": "allreduce",
        "FT_SIZE": "70000",
        "HVD_TPU_LIVENESS_TIMEOUT": "15",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TPU_FAULT_SPEC": "rank1:link:1:flaky:0.3",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
        assert f"rank {rank} COMPLETED" in out, f"{out}\n{err}"
    assert "[hvd-fault] flaky link" in (results[1][1] + results[1][2])


def test_partitioned_link_is_a_real_failure_with_the_right_origin():
    """The discrimination's other half: a permanent partition isolating
    rank 2 is NOT a slow link — its control-plane writes fail outright,
    the loss is converted into a coordinated abort, and the typed error
    every survivor sees names rank 2 as the origin (so an operator
    replaces the right host)."""
    results = spawn_tcp_ranks(3, MATRIX_WORKER, extra_env={
        **_FT_ENV,
        "FT_OP": "allreduce",
        "FT_SIZE": "8",  # star path: the cut hits rank 2's
        "HVD_TPU_LIVENESS_TIMEOUT": "3",  # control-plane heartbeats
        "HVD_TPU_CONNECT_RETRY_SECONDS": "5",  # fail the cut link fast
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "12",
        "HVD_TPU_FAULT_SPEC": "rank2:link:1:partition:2-2",
    })
    code2, out2, err2 = results[2]
    assert code2 != 0 or "ABORTED" in out2, \
        f"partitioned rank survived: {out2}\n{err2}"
    for rank in (0, 1):
        code, out, err = results[rank]
        assert code == 0, f"rank {rank}: {out}\n{err}"
        _assert_aborted(out, rank, origin=2, deadline=45.0)


# ------------------- mid-stream break grammar + the self-healing matrix -----
def test_fault_spec_midstream_grammar_round_trip():
    specs = faults.parse_fault_spec(
        "rank2:link:*:reset:0.3, rank1:link:2:reset:0.2:6 ,"
        "rank1:link:5:blip:30000")
    got = [(s.rank, s.point, s.step, s.action, s.param, s.duration)
           for s in specs]
    assert got == [
        # '*' step: armed from the first write; no duration: permanent
        (2, "link", None, "reset", 0.3, None),
        (1, "link", 2, "reset", 0.2, 6.0),
        (1, "link", 5, "blip", 30000.0, None),
    ]


@pytest.mark.parametrize("bad", [
    "rank1:allreduce:*:crash",        # '*' step is midstream-only
    "rank1:link:*:delay:40",          # ... degrade cells too
    "rank1:link:1:reset",             # reset wants a probability
    "rank1:link:1:reset:1.5",         # probability > 1
    "rank1:link:1:reset:often",       # non-numeric probability
    "rank1:link:1:blip:3000:5",       # blip takes no duration
    "rank1:link:1:blip:-5",           # negative window
    "rank1:link:1:blip",              # blip wants a window
])
def test_fault_spec_rejects_bad_midstream_grammar(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_spec(bad)


# Worker for the self-healing matrix (docs/fault_tolerance.md
# "connection blips vs dead peers"): several steps of allreduce +
# broadcast folded into one digest, so "completed" also means
# "bit-identical to the fault-free run" — a heal that corrupted or
# double-delivered a frame would change the bytes.
SESSION_WORKER = r"""
import hashlib, os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
steps = int(os.environ.get("FT_STEPS", "4"))
n_elems = int(os.environ.get("FT_SIZE", "20000"))
digest = hashlib.sha256()
try:
    for step in range(steps):
        t = jnp.arange(n_elems, dtype=jnp.float32) * (r + 1) + step
        out = hvd.allreduce(t, op=hvd.Sum, name=f"sess.ar.{step}")
        digest.update(np.asarray(out).tobytes())
        b = hvd.broadcast(t, root_rank=0, name=f"sess.bc.{step}")
        digest.update(np.asarray(b).tobytes())
    # the job's end, in order: rank 0 hosts the coordinator, and a rank 0
    # that left from its last collective without it would take along the
    # threads that still owe slower ranks that collective's go-ahead
    hvd.shutdown()
    print(f"rank {r} COMPLETED digest={digest.hexdigest()}", flush=True)
except hvd.HvdAbortedError as exc:
    print(f"rank {r} ABORTED origin={exc.origin_rank} why={exc}",
          flush=True)
print(f"rank {r} DONE", flush=True)
"""

# wide liveness/stall windows: these cells assert the HEAL path, so no
# detector may convert the engineered blip into a verdict first
_SESSION_ENV = {
    **_FT_ENV,
    "FT_STEPS": "4",
    "FT_SIZE": "20000",   # above the ring threshold: bulk stripes too
    "HVD_TPU_LIVENESS_TIMEOUT": "15",
    "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
}


def _session_digests(results):
    out_digests = []
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err[-2000:]}"
        assert "ABORTED" not in out, f"rank {rank}: {out}"
        line = next(l for l in out.splitlines()
                    if l.startswith(f"rank {rank} COMPLETED"))
        out_digests.append(line.split("digest=")[1])
    return out_digests


def _healed_count(results):
    return sum(err.count("[hvd-session] reconnect healed")
               for _code, _out, err in results)


def test_midstream_reset_heals_and_completes_bitwise_identical():
    """THE acceptance scenario (ISSUE 17): every frame rank 2 writes
    has a 30% chance of tearing the connection mid-ring — and the job
    completes with digests bitwise-identical to a fault-free run, zero
    aborts, the breaks healed by session resume + replay instead of
    costing a reconfiguration."""
    clean = spawn_tcp_ranks(4, SESSION_WORKER, extra_env=_SESSION_ENV,
                            timeout=180)
    chaos = spawn_tcp_ranks(4, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "HVD_TPU_RECONNECT_BUDGET": "30",
        "HVD_TPU_FAULT_SPEC": "rank2:link:*:reset:0.3",
    }, timeout=180)
    want = _session_digests(clean)
    assert len(set(want)) == 1, want     # all ranks agree with each other
    got = _session_digests(chaos)
    assert got == want, (got, want)      # ... and chaos run is bit-equal
    assert _healed_count(chaos) >= 1, \
        "no [hvd-session] heal marker: the chaos never engaged"
    assert any("[hvd-fault] mid-stream reset" in err
               for _c, _o, err in chaos), "reset fault never armed"


def test_midstream_reset_with_zero_budget_reproduces_typed_abort():
    """The feature-off pin, both ways: with the default budget (0) a
    mid-stream reset is exactly today's failure — the typed abort, no
    heal attempts — and the SAME spec with a budget completes with a
    heal.  One knob flips between the two worlds."""
    spec = "rank1:link:1:reset:1.0:4"
    broken = spawn_tcp_ranks(2, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "HVD_TPU_LIVENESS_TIMEOUT": "3",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "12",
        "HVD_TPU_FAULT_SPEC": spec,
    }, timeout=180)
    assert _healed_count(broken) == 0, "budget 0 must never heal"
    outs = "\n".join(out for _c, out, _e in broken)
    assert "COMPLETED" not in outs, outs
    assert ("ABORTED" in outs
            or any(code != 0 for code, _o, _e in broken)), broken
    healed = spawn_tcp_ranks(2, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "HVD_TPU_RECONNECT_BUDGET": "30",
        "HVD_TPU_FAULT_SPEC": spec,
    }, timeout=180)
    _session_digests(healed)
    assert _healed_count(healed) >= 1


def test_blip_outlasting_the_budget_escalates():
    """A 30s link flap against a 2s budget is a dead peer as far as
    the job can tell: the heal loop exhausts its window (connects are
    refused while the flap is down), the ORIGINAL error escalates, and
    the typed abort fires — no infinite retry, no hang."""
    results = spawn_tcp_ranks(2, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "HVD_TPU_LIVENESS_TIMEOUT": "5",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "12",
        "HVD_TPU_RECONNECT_BUDGET": "2",
        "HVD_TPU_FAULT_SPEC": "rank1:link:5:blip:30000",
    }, timeout=180)
    outs = "\n".join(out for _c, out, _e in results)
    assert "COMPLETED" not in outs, outs
    assert ("ABORTED" in outs
            or any(code != 0 for code, _o, _e in results)), results
    assert _healed_count(results) == 0, \
        "a connect during an open blip window must be refused"


def test_healing_rank_is_exempt_from_straggler_verdicts():
    """The reconnect/liveness interplay: a rank mid-heal heartbeats as
    busy + reconnecting, so a tight liveness window and the straggler
    detector both stand down while the session resumes — the blip never
    becomes an exclusion."""
    results = spawn_tcp_ranks(2, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "FT_STEPS": "6",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_TPU_RECONNECT_BUDGET": "30",
        "HVD_TPU_FAULT_SPEC": "rank1:link:1:reset:0.3:5",
    }, timeout=180)
    _session_digests(results)
    assert _healed_count(results) >= 1
    assert not any("straggler verdict" in err for _c, _o, err in results)


def test_midstream_reset_heals_on_the_hierarchical_schedule():
    """The session layer sits below the collective schedule: the
    two-level hierarchical plan's intra/inter-group streams heal the
    same way the flat ring's do."""
    results = spawn_tcp_ranks(4, SESSION_WORKER, extra_env={
        **_SESSION_ENV,
        "HVD_TPU_SCHEDULE": "hierarchical",
        "HVD_HIER_LOCAL_SIZE": "2",
        "HVD_TPU_RECONNECT_BUDGET": "30",
        "HVD_TPU_FAULT_SPEC": "rank2:link:*:reset:0.3",
    }, timeout=180)
    digests = _session_digests(results)
    assert len(set(digests)) == 1, digests
    assert _healed_count(results) >= 1
