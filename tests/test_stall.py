"""Stall-inspector tests (reference: test/test_stall.py — one rank lags,
expect a warning, then shutdown when HVD_STALL_SHUTDOWN is exceeded)."""

import os
import subprocess
import sys
import tempfile

WARN_SCRIPT = r"""
import time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
def fn(r):
    if r == 0:
        time.sleep(3.0)
    hvd.allreduce(jnp.ones((2,)), name="stall.tensor", op=hvd.Sum)
basics.run_parallel(fn)
hvd.shutdown()
print("COMPLETED")
"""

SHUTDOWN_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.common.handles import HvdAbortedError

import os
hvd.init()
typed = os.environ.get("HVD_CONTROLLER") == "python"
def fn(r):
    if r == 0:
        return "skipped"
    try:
        hvd.allreduce(jnp.ones((2,)), name="stall.tensor", op=hvd.Sum)
        return "no-error"
    except HvdAbortedError as exc:
        # the stall shutdown is a coordinated abort: one typed error
        # naming the lagging rank as origin on EVERY waiting rank
        return f"aborted-by-{exc.origin_rank}"
    except hvd.HvdError:
        # the native C++ core's stall shutdown predates the typed abort
        return "error"
results = basics.run_parallel(fn)
assert results[0] == "skipped"
expect = "aborted-by-0" if typed else ("aborted-by-0", "error")
assert all(r == expect or r in expect for r in results[1:]), results
hvd.shutdown()
print("SHUTDOWN-OK")
"""

USER_ABORT_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
n = hvd.size()
def fn(r):
    if r == n - 1:
        import time
        time.sleep(1.0)  # let the others block in negotiation first
        hvd.abort("bad shard detected")
        return "initiated"
    try:
        hvd.allreduce(jnp.ones((2,)), name="ua.tensor", op=hvd.Sum)
        return "no-error"
    except hvd.HvdAbortedError as exc:
        return f"aborted-by-{exc.origin_rank}"
results = basics.run_parallel(fn)
assert results[-1] == "initiated"
assert all(r == f"aborted-by-{n - 1}" for r in results[:-1]), results
hvd.shutdown()
print("USER-ABORT-OK")
"""


def _run(script, extra_env):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    env.update(extra_env)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=180,
                          cwd=os.path.dirname(os.path.dirname(__file__)))


def test_stall_warning():
    result = _run(WARN_SCRIPT, {"HVD_STALL_CHECK_TIME_SECONDS": "1"})
    assert result.returncode == 0, result.stderr
    assert "COMPLETED" in result.stdout
    assert "Stalled tensor: stall.tensor" in result.stderr
    assert "waiting on: [0]" in result.stderr


def test_stall_shutdown():
    result = _run(SHUTDOWN_SCRIPT, {
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "2",
    })
    assert result.returncode == 0, result.stderr + result.stdout
    assert "SHUTDOWN-OK" in result.stdout


def test_stall_shutdown_python_controller_typed_abort():
    """On the python controller the stall shutdown is a coordinated
    abort: HvdAbortedError naming the lagging rank, on every waiter."""
    result = _run(SHUTDOWN_SCRIPT, {
        "HVD_CONTROLLER": "python",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "2",
    })
    assert result.returncode == 0, result.stderr + result.stdout
    assert "SHUTDOWN-OK" in result.stdout


def test_user_abort_device_rank_mode():
    """hvd.abort() on the in-process (python) controller: every blocked
    rank raises HvdAbortedError naming the aborting rank."""
    result = _run(USER_ABORT_SCRIPT, {"HVD_CONTROLLER": "python"})
    assert result.returncode == 0, result.stderr + result.stdout
    assert "USER-ABORT-OK" in result.stdout


# ----------------------------------------------------- tcp + gmesh planes --
def test_stall_shutdown_tcp_controller():
    """Stall shutdown on the tcp coordinator is a coordinated abort:
    the waiting rank raises the typed error naming the lagging rank,
    bounded in time — not an indefinite negotiation wait."""
    from conftest import spawn_tcp_ranks

    script = r"""
import time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
if r == 0:
    # never submits; stays alive (heartbeats keep going) past the 2s
    # stall shutdown + abort fan-out
    time.sleep(4.5)
    print("rank 0 SKIPPED", flush=True)
else:
    try:
        hvd.allreduce(jnp.ones((2,)), name="stall.tensor", op=hvd.Sum)
        print("rank 1 NO-ERROR", flush=True)
    except hvd.HvdAbortedError as exc:
        print(f"rank 1 ABORTED origin={exc.origin_rank}", flush=True)
"""
    results = spawn_tcp_ranks(2, script, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "2",
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_LIVENESS_TIMEOUT": "30",
    })
    for rank, (code, out, err) in enumerate(results):
        assert code == 0, f"rank {rank}: {out}\n{err}"
    assert "rank 1 ABORTED origin=0" in results[1][1], results[1][1]


def test_stall_shutdown_gmesh_controller():
    """Stall shutdown on the global-mesh metadata coordinator emits a
    globally-ordered abort entry: every process's ranks fail with the
    typed error naming the silent process's first rank."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_gmesh_stall_worker.py")
    with open(path, "w") as f:
        f.write(r"""
import os, time
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
pid = int(os.environ["HVD_RANK"])
if pid == 1:
    # this process's ranks never submit; its controller keeps
    # heartbeat-polling and picks the abort entry up
    state = basics._get_state()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if state.controller._shutdown_error is not None:
            print(f"pid 1 SAW-ABORT", flush=True)
            break
        time.sleep(0.2)
else:
    # pid 1's first global rank (conftest-inherited XLA flags decide the
    # per-process device count, so compute it)
    origin = hvd.local_size()
    def fn(lr):
        try:
            hvd.allreduce(jnp.ones((2,)), name="gstall.t", op=hvd.Sum)
            return "no-error"
        except hvd.HvdAbortedError as exc:
            return f"aborted-by-{exc.origin_rank}"
    results = basics.run_parallel(fn)
    assert all(r == f"aborted-by-{origin}" for r in results), results
    print("pid 0 ABORT-OK", flush=True)
    time.sleep(2)  # let pid 1's next poll fetch the abort entry
""")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env.update({
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "3",
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_LIVENESS_TIMEOUT": "30",
    })
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "bin", "hvdrun"), "-np", "2",
         "--global-mesh", sys.executable, path],
        env=env, capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert "pid 0 ABORT-OK" in result.stdout, result.stdout
    assert "pid 1 SAW-ABORT" in result.stdout, result.stdout
