"""Horovod-on-Spark: run a distributed training fn inside Spark tasks
(reference: ``horovod/spark/runner.py:131`` — one Spark task per rank,
tasks register with a driver service, the training fn ships to the
tasks, results return per rank).

The port keeps the reference's topology — a barrier-stage RDD with one
partition per rank — and replaces the mpirun/gloo orchestration with
this framework's env contract + rendezvous KV: the driver hosts the
RendezvousServer, each Spark task assumes its rank, connects back, and
runs the fn through the tcp controller exactly like an ``hvdrun``
worker.  Requires PySpark (import-guarded).  Executed for real by
``tests/test_spark.py`` against a local-mode stand-in
(``tests/_pyspark_shim``) that reproduces the API surface, cloudpickle
serialization, separate-process executors, and barrier gang-failure
semantics this module depends on — genuine PySpark cannot be installed
in the CI image (no network egress to PyPI)."""

import os
import socket

try:
    import pyspark  # noqa: F401
    _PYSPARK_ERROR = None
except ImportError as _exc:  # pragma: no cover — pyspark absent in image
    pyspark = None
    _PYSPARK_ERROR = _exc


def _require_pyspark():
    if pyspark is None:  # pragma: no cover
        raise ImportError(
            "horovod_tpu.spark requires PySpark, which is not installed "
            "in this environment. The estimator framework (Store / "
            "Backend / estimators) is available Spark-free in "
            "horovod_tpu.cluster.") from _PYSPARK_ERROR


def _driver_ip():
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:  # pragma: no cover
        return "127.0.0.1"


def _task_fn(index, num_proc, fn, args, kwargs, rendezvous_addr,
             rendezvous_port, secret_b64, extra_env):
    """Runs inside one Spark task (= one rank)."""
    from horovod_tpu.utils import env as env_util

    for key, value in (extra_env or {}).items():
        os.environ[key] = value
    if "JAX_PLATFORMS" in os.environ:
        # must land before hvd.init touches jax.local_devices(); some
        # TPU plugins ignore the env var, so pin programmatically too
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    # register this task's start with the driver (start_timeout watches
    # for the full gang; reference: task-to-driver registration,
    # spark/driver_service.py).  A rank that is ALREADY registered is a
    # Spark task retry — a retried rank cannot rejoin a gang whose
    # peers are mid-collective (or torn down), so fail the stage fast
    # instead of hanging on a half-dead rendezvous.
    import time as time_mod

    from horovod_tpu.run import http_client

    probe_deadline = time_mod.monotonic() + 15.0
    while True:
        try:
            # retry_for=0: this loop owns its own 15s fail-open budget;
            # the verb's built-in transport retry would overrun it
            http_client.get(rendezvous_addr, int(rendezvous_port),
                            "spark-start", str(index), retry_for=0)
            raise RuntimeError(
                f"task for rank {index} appears to be a Spark retry; "
                f"horovod jobs cannot retry individual ranks — fail "
                f"the whole job and resubmit")
        except KeyError:
            break  # key absent: first attempt, expected
        except OSError:
            # transient transport blip must not kill a healthy first
            # attempt (same rationale as http_client.put's retry);
            # fail OPEN after the budget — if the rendezvous is truly
            # dead the job fails at the next contact anyway
            if time_mod.monotonic() > probe_deadline:
                break
            time_mod.sleep(0.25)
    http_client.put(rendezvous_addr, int(rendezvous_port),
                    "spark-start", str(index), b"1")
    os.environ[env_util.HVD_RANK] = str(index)
    os.environ[env_util.HVD_SIZE] = str(num_proc)
    os.environ[env_util.HVD_LOCAL_RANK] = "0"
    os.environ[env_util.HVD_LOCAL_SIZE] = "1"
    os.environ[env_util.HVD_CROSS_RANK] = str(index)
    os.environ[env_util.HVD_CROSS_SIZE] = str(num_proc)
    os.environ[env_util.HVD_RENDEZVOUS_ADDR] = rendezvous_addr
    os.environ[env_util.HVD_RENDEZVOUS_PORT] = str(rendezvous_port)
    os.environ[env_util.HVD_SECRET_KEY] = secret_b64
    os.environ[env_util.HVD_CONTROLLER] = "tcp"

    import horovod_tpu as hvd

    hvd.init()
    try:
        return fn(*args, **kwargs)
    finally:
        hvd.shutdown()


def run(fn, args=(), kwargs=None, num_proc=None, start_timeout=None,
        use_barrier=True, verbose=False, env=None):
    """Run ``fn(*args, **kwargs)`` as a Horovod job inside Spark tasks;
    returns the list of per-rank results (reference signature:
    ``spark/runner.py:131``; ``env`` merges into each task's
    environment, as there)."""
    _require_pyspark()
    del verbose
    from pyspark.sql import SparkSession

    from horovod_tpu.run.http_server import RendezvousServer
    from horovod_tpu.run.service import secret as secret_mod
    import base64

    kwargs = kwargs or {}
    spark = SparkSession.builder.getOrCreate()
    sc = spark.sparkContext
    if num_proc is None:
        num_proc = max(int(sc.defaultParallelism), 1)

    rendezvous = RendezvousServer()
    port = rendezvous.start()
    addr = _driver_ip()
    secret_b64 = base64.b64encode(secret_mod.make_secret_key()).decode()

    def mapper(index, _iterator):
        yield _task_fn(index, num_proc, fn, args, kwargs, addr, port,
                       secret_b64, env)

    try:
        rdd = sc.parallelize(range(num_proc), num_proc)
        if use_barrier and hasattr(rdd, "barrier"):
            # barrier mode guarantees all ranks are scheduled together
            # (a partial gang would deadlock the collectives)
            mapped = rdd.barrier().mapPartitionsWithIndex(mapper)
        else:
            mapped = rdd.mapPartitionsWithIndex(mapper)
        if not start_timeout:
            return mapped.collect()
        # start_timeout semantics (reference: spark/runner.py — fail
        # when the cluster cannot schedule the full gang in time, e.g.
        # fewer slots than num_proc): collect in a thread, watch the
        # tasks' start registrations in the rendezvous KV.
        import threading
        import time as time_mod
        import uuid

        # the collect runs under a job group of its own, so that a gang
        # that never fills can be cancelled (reference:
        # spark/runner.py setJobGroup / cancelJobGroup around the wait
        # for the tasks' registration)
        job_group = f"horovod.spark.run.{uuid.uuid4().hex}"
        box = {}

        def _collect():
            try:
                sc.setJobGroup(job_group, "Horovod Spark Run",
                               interruptOnCancel=True)
                box["results"] = mapped.collect()
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                box["error"] = exc

        thread = threading.Thread(target=_collect, daemon=True)
        thread.start()
        deadline = time_mod.monotonic() + start_timeout
        started = set()
        while thread.is_alive() and len(started) < num_proc:
            for i in range(num_proc):
                if i not in started and rendezvous.get(
                        "spark-start", str(i)) is not None:
                    started.add(i)
            if (len(started) < num_proc
                    and time_mod.monotonic() > deadline):
                # the tasks that did start sit in their first collective
                # waiting for ranks that will never come: a slot each,
                # held for ever, unless the job is cancelled here
                sc.cancelJobGroup(job_group)
                thread.join(timeout=10)
                raise RuntimeError(
                    f"Spark could not start all {num_proc} training "
                    f"tasks within start_timeout={start_timeout}s "
                    f"({len(started)} started); does the cluster have "
                    f"enough task slots?")
            thread.join(timeout=0.5)
        thread.join()
        if "error" in box:
            raise box["error"]
        return box["results"]
    finally:
        rendezvous.stop()
