"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference CI pattern (SURVEY §4): "multi-node" is simulated
locally — there as N processes under the launcher, here as 8 XLA host
devices so sharding/collective code paths are exercised for real.
Env vars MUST be set before jax is imported anywhere.
"""

import contextlib
import os
import signal
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent compile cache, shared with every subprocess the launcher
# tests spawn (env-inherited): subprocess hvdrun jobs dominated suite
# wall-time by each paying full XLA compiles — warm runs skip them.
# (The multichip dryrun proved the same trick at 44.7s -> 19.0s.)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO, ".jax_cache"))
# default threshold (1s) skips exactly the small per-test programs that
# dominate here; cache everything
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

# for harnesses that build a filtered env (stripping JAX_*): re-add
# exactly these so subprocesses keep the shared cache
JAX_CACHE_KEYS = ("JAX_COMPILATION_CACHE_DIR",
                  "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES")


def readd_jax_cache(env):
    for key in JAX_CACHE_KEYS:
        if key in os.environ:
            env[key] = os.environ[key]
    return env

import jax  # noqa: E402

# The suite is a CPU suite even where the caller's JAX_PLATFORMS names
# an accelerator (a host with a chip exports "tpu,cpu"): a test run must
# never claim the chip.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running benchmarks and multi-rank scenario jobs "
        "excluded from tier-1 (-m 'not slow'); dedicated CI jobs run "
        "them unfiltered")


# The one limit on a test, in seconds.  Whatever a test waits for in a
# child (``spawn_tcp_ranks``, ``subprocess.run``) it waits for less long
# than this, so that the child is reaped by its own call with what it
# printed in the message; the alarm is what is left when that fails.
LIMIT = 200


@contextlib.contextmanager
def time_limit(name):
    """Fail ``name``'s test where it stands once it has run ``LIMIT``
    seconds: an alarm in the main thread, where ``communicate``, ``join``
    and ``sleep`` can be interrupted (no plugin: none is installed)."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def over(signum, frame):
        pytest.fail(f"{name} ran past the suite's limit of {LIMIT} s "
                    f"(tests/conftest.py LIMIT)", pytrace=False)

    was = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, LIMIT)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


@pytest.fixture(autouse=True)
def _time_limit(request):
    with time_limit(request.node.nodeid):
        yield


@pytest.fixture(scope="session")
def hvd():
    """Session-wide initialized horovod_tpu (device-rank mode, 8 ranks)."""
    import horovod_tpu as hvd_module

    hvd_module.init()
    yield hvd_module
    hvd_module.shutdown()


@pytest.fixture(autouse=True)
def _hvd_up_again(request):
    """A test that shut the session's runtime down (``hvd.shutdown()``
    in its own process) would fail every later test of its worker:
    ``init`` after it, which returns at once where the runtime is up."""
    yield
    if "hvd" in request.fixturenames:
        request.getfixturevalue("hvd").init()


@pytest.fixture(scope="session")
def hvd_init(hvd):
    """Alias fixture for tests that import horovod_tpu directly."""
    return hvd


def spawn_tcp_ranks(n, script, extra_env=None, timeout=90,
                    world_size=None):
    """Launch ``n`` worker processes under the tcp-controller env
    contract WITHOUT the hvdrun kill-on-first-failure fan-out — the
    fault-tolerance tests need surviving ranks to keep running (and
    observe the coordinated abort) after a sibling dies, which the
    launcher would otherwise preempt with SIGTERM.

    ``world_size`` (default ``n``) is what HVD_SIZE advertises; ranks
    at/above it are spawned OUTSIDE the initial gang — late joiners for
    the elastic tests, which enter via ``hvd.elastic.wait_for_membership``
    instead of ``hvd.init``.

    Returns [(returncode, stdout, stderr)] per rank.  Each rank writes
    to files of its own, never to a pipe: a pipe holds 64 KiB, the ranks
    are waited for one after the other, and a rank that had more to say
    than that before an earlier one ended would block in ``write`` and
    read to its peers as stalled or dead.  (XLA's note on loading a
    cached CPU executable, some KiB a line and one for each program a
    rank loads, is left out of ``stderr``.)  Every child is reaped on
    ANY exit path: a spawn failure or a timeout kills and joins the
    remaining workers instead of leaking them past the test (they would
    hold the rendezvous port and skew later timings), and a timeout
    fails the test with what every rank had printed.
    """
    import base64
    import subprocess
    import sys
    import tempfile
    import time

    from horovod_tpu.run.http_server import RendezvousServer
    from horovod_tpu.run.service import secret

    server = RendezvousServer()
    port = server.start()
    key = base64.b64encode(secret.make_secret_key()).decode()
    size = n if world_size is None else world_size
    procs = []
    with tempfile.TemporaryDirectory(prefix="hvd_ft_") as work:
        path = os.path.join(work, "worker.py")
        with open(path, "w") as f:
            f.write(script)

        def said(r):
            out, err = (open(os.path.join(work, f"{stream}.{r}"),
                             errors="replace").read()
                        for stream in ("out", "err"))
            return out, "\n".join(line for line in err.splitlines()
                                  if "cpu_aot_loader.cc" not in line)

        def reap():
            # kill, then join: a killed child left un-waited would
            # linger as a zombie
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()

        try:
            for r in range(n):
                env = dict(os.environ)
                env["PYTHONPATH"] = _REPO + os.pathsep + env.get(
                    "PYTHONPATH", "")
                env.update({
                    "HVD_RANK": str(r), "HVD_SIZE": str(size),
                    "HVD_LOCAL_RANK": str(r), "HVD_LOCAL_SIZE": str(size),
                    "HVD_CROSS_RANK": "0", "HVD_CROSS_SIZE": "1",
                    "HVD_RENDEZVOUS_ADDR": "127.0.0.1",
                    "HVD_RENDEZVOUS_PORT": str(port),
                    "HVD_SECRET_KEY": key,
                })
                if extra_env:
                    env.update(extra_env)
                with open(os.path.join(work, f"out.{r}"), "w") as out, \
                        open(os.path.join(work, f"err.{r}"), "w") as err:
                    procs.append(subprocess.Popen(
                        [sys.executable, path], env=env,
                        stdout=out, stderr=err))
            deadline = time.monotonic() + timeout
            try:
                for p in procs:
                    p.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                reap()
                # say what every rank had printed, not only that one hung
                raise AssertionError(
                    f"ranks still running after {timeout}s:\n" + "\n".join(
                        f"--- rank {r} exit {p.returncode}\n"
                        f"{out[-1500:]}\n{err[-3000:]}"
                        for r, p in enumerate(procs)
                        for out, err in [said(r)])) from None
            return [(p.returncode, *said(r)) for r, p in enumerate(procs)]
        finally:
            reap()    # a spawn failure or any other exception above
            server.stop()


PYSPARK_SHIM = os.path.join(_REPO, "tests", "_pyspark_shim")


def pyspark_shim_env(extra_env=None):
    """Env contract for running a Spark driver against the local-mode
    pyspark shim (shared by test_spark.py and test_examples.py)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (PYSPARK_SHIM + os.pathsep + _REPO + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("JAX_PLATFORMS", None)
    env.setdefault("SPARK_SHIM_PARALLELISM", "2")
    if extra_env:
        env.update(extra_env)
    return env
