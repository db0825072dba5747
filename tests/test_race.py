"""Tier-1 gate for hvd-race (docs/race_detection.md).

Four halves:

1. every known-bad fixture under ``tests/race_fixtures/`` is caught
   DETERMINISTICALLY under a fixed seed (the same seed twice yields the
   byte-identical report) and every good twin stays silent;
2. the concurrency-heavy suite paths — the loopback ring data plane
   (the tcp-matrix harness), the fault-injection worker harness
   (including the mid-ring crash), and the python-controller
   stall-inspector path — run under the shim with ZERO non-baselined
   reports;
3. shim neutrality: with ``HVD_TPU_RACE`` unset the shim is provably
   not installed (stock identities, module absent, stock lock
   throughput); with it set the shim is provably installed;
4. the baseline stays small (<= 10) and justified.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

from conftest import spawn_tcp_ranks
from horovod_tpu.tools.lint import findings as findings_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "race_fixtures")
HVD_RACE = os.path.join(REPO, "bin", "hvd-race")
BASELINE = os.path.join(REPO, ".hvd-race-baseline.json")


def _run_hvd_race(fixture, seed=7, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, HVD_RACE, "--seed", str(seed), "--no-baseline",
         "--format", "json", *extra, os.path.join(FIXTURES, fixture)],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    payload = json.loads(out.stdout) if out.stdout.strip() else {}
    return out.returncode, payload, out.stderr


BAD_CASES = [
    ("bad_unlocked_counter.py", "Counter", "count"),
    ("bad_notify_without_lock.py", "Box", "ready"),
    ("bad_publish_after_close.py", "Sink", "out"),
]


@pytest.mark.parametrize("fixture,cls,attr", BAD_CASES,
                         ids=[c[0] for c in BAD_CASES])
def test_bad_fixture_is_caught(fixture, cls, attr):
    code, payload, err = _run_hvd_race(fixture)
    assert code == 1, f"{fixture}: expected findings, got rc={code}\n{err}"
    found = payload["findings"]
    assert any(f["context"] == cls and f["detail"].startswith(attr + ":")
               for f in found), found


@pytest.mark.parametrize("fixture", [
    "good_unlocked_counter.py",
    "good_notify_under_lock.py",
    "good_publish_join_before_close.py",
])
def test_good_twin_is_silent(fixture):
    code, payload, err = _run_hvd_race(fixture)
    assert code == 0, (f"{fixture}: false positive(s): "
                       f"{payload.get('findings')}\n{err}")
    assert payload["findings"] == []


def test_same_seed_reproduces_identical_report():
    """The HVD_TPU_RACE_SEED determinism contract: the fuzzer's
    preemption decisions — and therefore the report, down to the racing
    sites, thread names and message text — are a pure function of the
    seed."""
    _, first, _ = _run_hvd_race("bad_unlocked_counter.py", seed=7)
    _, second, _ = _run_hvd_race("bad_unlocked_counter.py", seed=7)
    assert first["findings"], "fixture produced no findings"
    assert first == second


def test_report_attributes_both_stacks_and_annotation():
    """A report names both racing sites with thread names, the
    ownership history, and the '# guarded by' declaration it
    contradicts."""
    _, payload, _ = _run_hvd_race("bad_notify_without_lock.py")
    (finding,) = [f for f in payload["findings"]
                  if f["detail"].startswith("ready:")]
    msg = finding["message"]
    assert "consume" in msg and "publish" in msg      # both sites
    assert "MainThread" in msg                        # thread names
    assert "first write by" in msg                    # ownership history
    assert "contradicts declared '# guarded by self._cv'" in msg


# ------------------------------------------------------- shim neutrality --
NEUTRALITY_PROBE = r"""
import sys, time
import horovod_tpu  # the install gate runs (or not) here
import threading, queue, _thread

race_on = __RACE_ON__
if race_on:
    assert "horovod_tpu.tools.race.shim" in sys.modules, \
        "HVD_TPU_RACE=1 did not install the shim"
    from horovod_tpu.tools.race import shim
    assert shim.is_installed()
    assert threading.Lock is shim.TracedLock
    assert threading.Event is shim.TracedEvent
else:
    assert "horovod_tpu.tools.race.shim" not in sys.modules, \
        "shim module imported with HVD_TPU_RACE unset"
    assert threading.Lock is _thread.allocate_lock, threading.Lock
    assert threading.Thread.start.__module__ == "threading"
    assert threading.Thread.join.__module__ == "threading"
    assert queue.Queue.put.__module__ == "queue"
    assert queue.Queue.get.__module__ == "queue"
    # micro-benchmark: stock lock throughput (instrumentation would
    # cost an order of magnitude; the floor is generous so machine
    # load cannot flake it)
    lock = threading.Lock()
    n = 200_000
    start = time.perf_counter()
    for _ in range(n):
        lock.acquire()
        lock.release()
    elapsed = time.perf_counter() - start
    assert elapsed < 2.0, f"{n} stock lock cycles took {elapsed:.2f}s"
print("NEUTRAL-OK")
"""


def _run_probe(race_on):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("HVD_TPU_RACE", None)
    if race_on:
        env["HVD_TPU_RACE"] = "1"
    script = NEUTRALITY_PROBE.replace("__RACE_ON__", str(race_on))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    assert "NEUTRAL-OK" in out.stdout


def test_shim_absent_when_off():
    _run_probe(race_on=False)


def test_shim_installed_when_on():
    _run_probe(race_on=True)


# ------------------------------------------- suites under the shim --------
def _nonbaselined(report_glob):
    baseline = findings_mod.load_baseline(BASELINE)
    active = []
    for path in sorted(glob.glob(report_glob)):
        with open(path) as f:
            data = json.load(f)
        for finding in data["findings"]:
            if finding["key"] not in baseline:
                active.append(finding)
    return active


def _run_inline_under_shim(body, report_prefix, tmp_path):
    env = dict(os.environ)
    # the fixture scripts import tests/ring_rig.py
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.join(REPO, "tests"), env.get("PYTHONPATH", "")])
    env.update({
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_RACE": "1",
        "HVD_TPU_RACE_SEED": "3",
        "HVD_TPU_RACE_REPORT": str(tmp_path / report_prefix),
    })
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=180,
                         cwd=REPO)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    return _nonbaselined(str(tmp_path / (report_prefix + ".*.json")))


RING_HARNESS = r"""
import numpy as np
import threading
import horovod_tpu  # installs the shim
import ring_rig

services, planes = ring_rig.ring_harness(2, 1024, 2)

arrs = [np.arange(4000, dtype=np.float32) * (r + 1) for r in range(2)]
out = [None, None]
def ar(r):
    out[r] = planes[r].allreduce(1, arrs[r], [0, 1],
                                 op_average=False, world_size=2)
ring_rig.run_all(planes, ar)
assert np.array_equal(out[0], out[1])
def ar8(r):
    out[r] = planes[r].allreduce(2, arrs[r], [0, 1], op_average=False,
                                 world_size=2, compression="int8")
ring_rig.run_all(planes, ar8)
def bc(r):
    out[r] = planes[r].broadcast(3, arrs[0] if r == 0 else None,
                                 [0, 1], 0, shape=arrs[0].shape,
                                 dtype="float32")
ring_rig.run_all(planes, bc)
# abort waking a blocked stripe recv, then teardown
caught = []
def blocked():
    try:
        planes[1].recv_chunk((99, "rs", 0), 0, 3 * 1024, timeout=30)
    except BaseException as e:
        caught.append(e)
t = threading.Thread(target=blocked); t.start()
import time; time.sleep(0.3)
services[1].abort(0, "race-gate abort")
t.join(5)
assert caught
for p in planes: p.close()
for s in services: s.shutdown()
print("RING-OK")
"""


def test_ring_dataplane_clean_under_shim(tmp_path):
    """The tcp-matrix harness path: exact + int8 + broadcast rounds and
    an abort wakeup over the real loopback transport, shim on — every
    report is baselined or nonexistent."""
    active = _run_inline_under_shim(RING_HARNESS, "ring", tmp_path)
    assert not active, "\n".join(f["message"] for f in active)


STALL_HARNESS = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
def per_rank():
    hvd.allreduce(jnp.ones((64,)), op=hvd.Sum, name="race.stall")
    hvd.allgather(jnp.ones((8,)), name="race.gather")
basics.run_parallel(per_rank)
import time; time.sleep(1.5)   # let the stall inspector run cycles
basics.run_parallel(per_rank)
hvd.shutdown()
print("STALL-OK")
"""


def test_stall_path_clean_under_shim(tmp_path):
    """The test_stall harness path: the python controller's cycle loop
    + stall inspector under the shim."""
    env_body = (
        "import os\n"
        "os.environ['HVD_CONTROLLER'] = 'python'\n"
        "os.environ['HVD_STALL_CHECK_TIME_SECONDS'] = '1'\n"
        + STALL_HARNESS)
    active = _run_inline_under_shim(env_body, "stall", tmp_path)
    assert not active, "\n".join(f["message"] for f in active)


GROUPS_HARNESS = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu import groups as groups_mod
from horovod_tpu.common import basics

hvd.init()
n = hvd.size()
g0 = hvd.new_group(list(range(n // 2)), name="race.g0")
g1 = hvd.new_group(list(range(n // 2, n)), name="race.g1")

def per_rank(r):
    grp = g0 if r in g0 else g1
    for i in range(3):
        hvd.allreduce(jnp.ones((64,)) * (r + 1), op=hvd.Sum,
                      name=f"race.grp.{i}", group=grp)
        hvd.allgather(jnp.ones((4,)) * r, name=f"race.gath.{i}",
                      group=grp)
    hvd.barrier(name="race.join")

basics.run_parallel(per_rank)
assert groups_mod.stats()["max_concurrent_groups"] >= 2, \
    groups_mod.stats()
basics.run_parallel(per_rank)
hvd.shutdown()
print("GROUPS-OK")
"""


def test_concurrent_groups_clean_under_shim(tmp_path):
    """ISSUE 14: two process groups' collectives concurrently in
    flight from worker threads — per-group negotiation tables, caches
    and ring namespaces racing each other and the world barrier, shim
    on: zero non-baselined reports (and the in-flight gauge proves the
    two groups really did overlap under the shim's preemption)."""
    env_body = (
        "import os\n"
        "os.environ['HVD_CONTROLLER'] = 'python'\n"
        + GROUPS_HARNESS)
    active = _run_inline_under_shim(env_body, "groups", tmp_path)
    assert not active, "\n".join(f["message"] for f in active)


FT_WORKER = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
t = jnp.ones((70000,)) * (r + 1)
start = time.monotonic()
try:
    hvd.allreduce(t, op=hvd.Sum, name="race.ft")
    print(f"rank {r} COMPLETED", flush=True)
except hvd.HvdAbortedError as exc:
    print(f"rank {r} ABORTED origin={exc.origin_rank}", flush=True)
"""


def test_fault_harness_clean_under_shim_and_origin_deterministic(
        tmp_path):
    """The fault-injection harness path under the shim: a mid-ring
    crash at rank 1.  Two assertions ride one spawn: (1) zero
    non-baselined race reports from the surviving rank (the crashed
    rank os._exit()s, so it writes none, by design); (2) the abort
    origin is ALWAYS the dead rank — liveness detection and the
    survivor's own failed sends now name the same origin
    (RingSendError carries the proven-dead peer), so culprit naming
    no longer depends on which detector fires first under load."""
    results = spawn_tcp_ranks(2, FT_WORKER, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_RACE": "1",
        "HVD_TPU_RACE_SEED": "3",
        "HVD_TPU_RACE_REPORT": str(tmp_path / "ft"),
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_ABORT_TIMEOUT": "10",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TCP_RING_THRESHOLD": "1024",
        "HVD_TPU_FAULT_SPEC": "rank1:ring:1:crash",
    }, timeout=180)
    code0, out0, err0 = results[0]
    code1, out1, _ = results[1]
    assert code1 == 1, f"crashed rank: {out1}"
    assert code0 == 0, f"survivor: {out0}\n{err0}"
    assert "rank 0 ABORTED origin=1" in out0, out0
    active = _nonbaselined(str(tmp_path / "ft.*.json"))
    assert not active, "\n".join(f["message"] for f in active)


ELASTIC_WORKER = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
state = hvd.elastic.State(
    params={"w": jnp.zeros((2000,), dtype=jnp.float32)}, step=0)

def train(state):
    while state.step < 3:
        g = jnp.full((2000,), float(state.step + 1), dtype=jnp.float32)
        avg = hvd.allreduce(g, op=hvd.Average,
                            name=f"race.el.{state.step}")
        state.params = {"w": state.params["w"] - avg}
        state.step += 1
        state.commit()

hvd.elastic.run(train, state)
print(f"rank {hvd.rank()} RECONFIGURED size={hvd.size()} "
      f"steps={state.step}", flush=True)
hvd.shutdown()
"""


def test_elastic_reconfig_path_clean_under_shim(tmp_path):
    """The elastic reconfiguration path under the shim: membership
    planning racing the abort fan-out, the controller-generation
    teardown racing in-flight ring traffic, and the epoch-scoped
    gang restart — zero non-baselined race reports on any survivor."""
    results = spawn_tcp_ranks(3, ELASTIC_WORKER, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_RACE": "1",
        "HVD_TPU_RACE_SEED": "3",
        "HVD_TPU_RACE_REPORT": str(tmp_path / "el"),
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_ABORT_TIMEOUT": "10",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_TPU_RECONFIG_TIMEOUT": "60",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TCP_RING_THRESHOLD": "1024",
        "HVD_TPU_FAULT_SPEC": "rank2:allreduce:2:crash",
    }, timeout=180)
    assert results[2][0] == 1, f"crashed rank: {results[2][1]}"
    for r in (0, 1):
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err}"
        assert "RECONFIGURED size=2 steps=3" in out, f"rank {r}: {out}"
    active = _nonbaselined(str(tmp_path / "el.*.json"))
    assert not active, "\n".join(f["message"] for f in active)


def test_coord_failover_path_clean_under_shim(tmp_path):
    """The coordinator fail-over path under the shim: rank 0 dies
    mid-collective, the survivors' CAS election races their heartbeat
    monitors and the abort fan-out, the new rank 0 starts a fresh
    CoordinatorService while the old epoch's teardown is still in
    flight — zero non-baselined race reports on any survivor."""
    results = spawn_tcp_ranks(4, ELASTIC_WORKER, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_RACE": "1",
        "HVD_TPU_RACE_SEED": "3",
        "HVD_TPU_RACE_REPORT": str(tmp_path / "cf"),
        "HVD_TPU_ELASTIC": "1",
        "HVD_TPU_COORD_FAILOVER": "1",
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_ABORT_TIMEOUT": "10",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_TPU_RECONFIG_TIMEOUT": "60",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TCP_RING_THRESHOLD": "1024",
        "HVD_TPU_FAULT_SPEC": "rank0:allreduce:2:crash",
    }, timeout=180)
    assert results[0][0] == 1, f"crashed rank 0: {results[0][1]}"
    for r in (1, 2, 3):
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err}"
        assert "RECONFIGURED size=3 steps=3" in out, f"rank {r}: {out}"
    active = _nonbaselined(str(tmp_path / "cf.*.json"))
    assert not active, "\n".join(f["message"] for f in active)


# ------------------------------------------------------------- baseline --
def test_baseline_is_small_and_justified():
    with open(BASELINE) as f:
        data = json.load(f)
    entries = data.get("suppressions", [])
    assert len(entries) <= 10, (
        f"{len(entries)} baselined race suppressions — the budget is "
        f"10; fix races (or annotate deliberate lock-free reads at the "
        f"site) instead of baselining them")
    for entry in entries:
        just = entry.get("justification", "")
        assert just and "TODO" not in just, (
            f"baseline entry {entry.get('key')!r} lacks a real "
            f"justification")


def test_write_baseline_roundtrip(tmp_path):
    """hvd-race shares hvd-lint's baseline machinery: --write-baseline
    captures this run's findings and preserves prior justifications."""
    base = tmp_path / "race-base.json"
    base.write_text(json.dumps({"suppressions": [
        {"key": "race:tests/race_fixtures/bad_unlocked_counter.py:"
                "Counter:count:write-write",
         "justification": "fixture"}]}))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, HVD_RACE, "--seed", "7", "--baseline",
         str(base), "--write-baseline",
         os.path.join(FIXTURES, "bad_unlocked_counter.py")],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert out.returncode == 0, out.stderr
    reloaded = findings_mod.load_baseline(str(base))
    key = ("race:tests/race_fixtures/bad_unlocked_counter.py:"
           "Counter:count:write-write")
    assert reloaded[key] == "fixture"           # justification survives
    assert any(k != key for k in reloaded)      # new finding captured


def test_write_baseline_refuses_partial_run(tmp_path):
    """A target that crashes observed only a prefix of the findings:
    regenerating the baseline from it would silently prune every
    justified suppression the crash prevented re-observing — the CLI
    must refuse (exit 3) and leave the baseline untouched."""
    target = tmp_path / "crasher.py"
    target.write_text("def main():\n    raise RuntimeError('boom')\n")
    base = tmp_path / "race-base.json"
    original = json.dumps({"suppressions": [
        {"key": "race:x.py:C:attr:write-write",
         "justification": "justified elsewhere"}]})
    base.write_text(original)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, HVD_RACE, "--baseline", str(base),
         "--write-baseline", str(target)],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert out.returncode == 3, out.stdout + out.stderr
    assert "baseline NOT rewritten" in out.stderr
    assert base.read_text() == original


ADAPTIVE_COORD_HARNESS = r"""
import threading
import time
import horovod_tpu  # installs the shim
from horovod_tpu.common import rtt
from horovod_tpu.ops.tcp_controller import CoordinatorService
from horovod_tpu.run.service import network, secret

svc = CoordinatorService(4, secret.make_secret_key(),
                         liveness_timeout_sec=30.0,
                         straggler_factor=4.0, straggler_windows=2)
errs = []
def worker(rank):
    # the production shape: per-connection handler threads feed
    # heartbeats (busy flags + RTT reports) while the liveness scan,
    # the straggler scan and verdict reads run concurrently
    try:
        tr = rtt.RttTracker(alpha=0.5)
        for i in range(40):
            tr.sample(rtt.COORD_KEY, 0.01 * rank + 0.001 * i)
            svc._handle(network.HeartbeatMsg(
                rank, busy=(i % 3 == 0), rtt=tr.worst() or None), None)
            svc.straggler_verdicts()
    except BaseException as e:
        errs.append(e)
ts = [threading.Thread(target=worker, args=(r,)) for r in range(1, 4)]
for t in ts: t.start()
for t in ts: t.join()
assert not errs, errs
svc.shutdown()
print("ADAPTIVE-OK")
"""


def test_adaptive_coordinator_path_clean_under_shim(tmp_path):
    """The soak rig's coordinator hot path (docs/soak.md): concurrent
    heartbeats carrying busy flags + RTT reports through the adaptive
    liveness deadline, the straggler scan and verdict reads, with
    RttTracker EWMAs updating alongside — shim on, zero non-baselined
    findings."""
    active = _run_inline_under_shim(ADAPTIVE_COORD_HARNESS, "adaptive",
                                    tmp_path)
    assert not active, "\n".join(f["message"] for f in active)


HIER_HARNESS = r"""
import numpy as np
import horovod_tpu  # installs the shim
import ring_rig

services, planes = ring_rig.ring_harness(4, 4096, 2)

arrs = [np.arange(5000, dtype=np.float32) * (r + 1) for r in range(4)]
groups = [[0, 1], [2, 3]]
out = [None] * 4
def hier(r):
    out[r] = planes[r].allreduce_hierarchical(
        1, arrs[r], [0, 1, 2, 3], groups, op_average=False,
        world_size=4)
ring_rig.run_all(planes, hier)
assert all(np.array_equal(o, out[0]) for o in out[1:])
def hier8(r):
    out[r] = planes[r].allreduce_hierarchical(
        2, arrs[r], [0, 1, 2, 3], groups, op_average=False,
        world_size=4, compression="int8")
ring_rig.run_all(planes, hier8)
assert all(np.array_equal(o, out[0]) for o in out[1:])
def rhd(r):
    out[r] = planes[r].allreduce_rhd(3, arrs[r], [0, 1, 2, 3],
                                     op_average=False, world_size=4)
ring_rig.run_all(planes, rhd)
assert all(np.array_equal(o, out[0]) for o in out[1:])
for p in planes: p.close()
for s in services: s.shutdown()
print("HIER-OK")
"""


def test_hierarchical_schedule_clean_under_shim(tmp_path):
    """ISSUE 12: the hierarchical and rhd data-plane phases — owner-
    targeted intra-group scatter, delegate gather/ring/broadcast (exact
    and int8 wire), pairwise recursive doubling — across 4 rank threads
    on the real loopback transport, shim on: every report is baselined
    or nonexistent."""
    active = _run_inline_under_shim(HIER_HARNESS, "hier", tmp_path)
    assert not active, "\n".join(f["message"] for f in active)


SESSION_HARNESS = r"""
import socket
import threading
import horovod_tpu  # installs the shim
from horovod_tpu.run.service import network, secret

key = secret.make_secret_key()


class Echo(network.MuxService):
    def _handle(self, req, client_address):
        return ("echo", req)


class Hdr:
    def __init__(self, tag):
        self.tag = tag
        self.payload = None


svc = Echo("race session", key)
client = network.MuxClient([("127.0.0.1", svc.port)], key, timeout=10,
                           peer=1, reconnect_budget=30, retry_for=10)
stripe = network.StripeClient([("127.0.0.1", svc.port)], key,
                              timeout=10, peer=1, reconnect_budget=30,
                              retry_for=10)
# concurrent senders racing the heal: the reader thread, the send
# retry loops and the sever all contend for the session state
errs = []
def pump(i):
    try:
        for j in range(12):
            client.post(("post", i, j))
            assert client.send(("ask", i, j)) == ("echo", ("ask", i, j))
            stripe.post_bulk(Hdr((i, j)), b"\x5a" * 2048)
    except BaseException as e:  # noqa: BLE001
        errs.append(e)
ts = [threading.Thread(target=pump, args=(i,)) for i in range(3)]
for t in ts: t.start()
import time
time.sleep(0.1)
for _ in range(2):           # sever both transports mid-traffic
    with client._state_lock:
        if client._sock is not None:
            client._sock.shutdown(socket.SHUT_RDWR)
    with stripe._lock:
        if stripe._sock is not None:
            stripe._sock.shutdown(socket.SHUT_RDWR)
    time.sleep(0.2)
for t in ts: t.join()
assert not errs, errs
stripe.close()
client.close()
svc.shutdown()
print("SESSION-OK")
"""


def test_session_heal_clean_under_shim(tmp_path):
    """ISSUE 17 gate: the self-healing session layer — concurrent
    send/post/bulk pumps racing two mid-stream severs and the heals
    they trigger — produces zero non-baselined findings under the
    interleaving shim."""
    active = _run_inline_under_shim(SESSION_HARNESS, "session", tmp_path)
    assert not active, "\n".join(f["message"] for f in active)
