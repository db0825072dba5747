"""Multi-process (hvdrun) end-to-end tests — the reference CI's primary
mode (SURVEY §4: every test file runs under `horovodrun -np 2 --gloo`;
"multi-node" is N processes on one box).  Each scenario is a worker script
executed under ``bin/hvdrun -np N``; rank-aware asserts run inside the
workers and any failure propagates as a nonzero exit."""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HVDRUN = os.path.join(REPO, "bin", "hvdrun")

WORKER = r"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()
assert n == 2

# -- allreduce (sum + average + prescale) --------------------------------
out = np.asarray(hvd.allreduce(jnp.ones((4, 3)) * (r + 1), op=hvd.Sum,
                               name="ar"))
np.testing.assert_allclose(out, np.full((4, 3), 3.0))

out = np.asarray(hvd.allreduce(jnp.ones((5,)) * (r + 1), name="avg"))
np.testing.assert_allclose(out, np.full((5,), 1.5))

out = np.asarray(hvd.allreduce(jnp.ones((2,)), op=hvd.Sum, name="pre",
                               prescale_factor=0.5, postscale_factor=10.0))
np.testing.assert_allclose(out, np.full((2,), 10.0))

# -- out-of-order async submission (negotiation pairs by name; sync calls
# in different orders would deadlock, exactly as in the reference) -------
if r == 0:
    ha = hvd.allreduce_async(jnp.ones((2,)), op=hvd.Sum, name="x")
    hb = hvd.allreduce_async(jnp.ones((3,)), op=hvd.Sum, name="y")
else:
    hb = hvd.allreduce_async(jnp.ones((3,)), op=hvd.Sum, name="y")
    ha = hvd.allreduce_async(jnp.ones((2,)), op=hvd.Sum, name="x")
np.testing.assert_allclose(np.asarray(hvd.synchronize(ha)),
                           np.full((2,), 2.0))
np.testing.assert_allclose(np.asarray(hvd.synchronize(hb)),
                           np.full((3,), 2.0))

# -- allgather with variable first dim -----------------------------------
g = np.asarray(hvd.allgather(jnp.full((r + 1, 2), float(r)), name="ag"))
np.testing.assert_allclose(
    g, np.concatenate([np.full((1, 2), 0.0), np.full((2, 2), 1.0)]))

# -- broadcast ------------------------------------------------------------
b = np.asarray(hvd.broadcast(jnp.full((3,), float(r) + 5.0), root_rank=1,
                             name="bc"))
np.testing.assert_allclose(b, np.full((3,), 6.0))

# -- alltoall -------------------------------------------------------------
t = jnp.arange(4, dtype=jnp.float32) + 10 * r
out = np.asarray(hvd.alltoall(t, name="a2a"))
expect = (np.array([0., 1., 10., 11.]) if r == 0
          else np.array([2., 3., 12., 13.]))
np.testing.assert_allclose(out, expect)

# -- adasum ---------------------------------------------------------------
from horovod_tpu.ops.adasum import adasum_reference
data = [np.arange(1, 5, dtype=np.float32) * (i + 1) for i in range(2)]
out = np.asarray(hvd.allreduce(jnp.asarray(data[r]), op=hvd.Adasum,
                               name="ads"))
np.testing.assert_allclose(out, adasum_reference(data), rtol=1e-5)

# -- error: mismatched shapes surface on every rank ----------------------
from horovod_tpu.common.handles import HvdError
try:
    hvd.allreduce(jnp.ones((2 + r,)), op=hvd.Sum, name="bad")
    raise SystemExit("expected HvdError for mismatched shapes")
except HvdError:
    pass

# -- join: uneven work ----------------------------------------------------
if r == 0:
    extra = np.asarray(hvd.allreduce(jnp.ones((2,)) * 7, op=hvd.Sum,
                                     name="uneven"))
    # rank 1 joined: its stand-in is zeros
    np.testing.assert_allclose(extra, np.full((2,), 7.0))
last = hvd.join()
assert last in (0, 1)

print(f"rank {r} PROCESS_MODE_OK", flush=True)
hvd.shutdown()
"""


def _run_hvdrun(np_, script, extra_args=(), timeout=180):
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_process_mode_worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)  # worker sets cpu itself
    cmd = [sys.executable, HVDRUN, "-np", str(np_), *extra_args,
           sys.executable, path]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_process_mode_collectives():
    result = _run_hvdrun(2, WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("PROCESS_MODE_OK") == 2


def test_many_outstanding_out_of_order_collectives():
    """32 async allreduces submitted in opposite orders per rank: more
    outstanding blocking round-trips than any fixed-size pool — a bounded
    dispatch would deadlock (regression: per-request threads)."""
    script = (
        "import os\n"
        "os.environ.setdefault('XLA_FLAGS',"
        " '--xla_force_host_platform_device_count=2')\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np, jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "r = hvd.rank()\n"
        "names = [f'n{i}' for i in range(32)]\n"
        "order = names if r == 0 else names[::-1]\n"
        "handles = {n: hvd.allreduce_async(jnp.ones((4,)), op=hvd.Sum,"
        " name=n) for n in order}\n"
        "for n in names:\n"
        "    out = np.asarray(hvd.synchronize(handles[n]))\n"
        "    np.testing.assert_allclose(out, np.full((4,), 2.0))\n"
        "print('OOO_OK', flush=True)\n"
        "hvd.shutdown()\n"
    )
    result = _run_hvdrun(2, script, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("OOO_OK") == 2


def test_process_mode_worker_failure_kills_job():
    script = (
        "import os, sys\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "if hvd.rank() == 1:\n"
        "    sys.exit(3)\n"
        "import time; time.sleep(60)\n"
    )
    result = _run_hvdrun(2, script, timeout=180)
    assert result.returncode != 0


RING_ADASUM_WORKER = r"""
import os
os.environ["HVD_TCP_RING_THRESHOLD"] = "2048"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.ops.adasum import adasum_reference

hvd.init()
r, n = hvd.rank(), hvd.size()
assert n == 4

# large tensor above the (tiny) ring threshold -> distributed VHDD with
# NO rank-0 payload; verify exactly against the numpy oracle
rng = [np.random.RandomState(seed) for seed in range(n)]
data = [g.randn(4096).astype(np.float32) for g in rng]
out = np.asarray(hvd.allreduce(jnp.asarray(data[r]), op=hvd.Adasum,
                               name="vhdd.big"))
np.testing.assert_allclose(out, adasum_reference(data), rtol=1e-5,
                           atol=1e-6)

# odd (non-chunk-aligned) length exercises the padding path
data3 = [g.randn(1003).astype(np.float32) for g in rng]
out = np.asarray(hvd.allreduce(jnp.asarray(data3[r]), op=hvd.Adasum,
                               name="vhdd.odd"))
np.testing.assert_allclose(out, adasum_reference(data3), rtol=1e-5,
                           atol=1e-6)

# below threshold: coordinator payload path, same oracle
small = [g.randn(16).astype(np.float32) for g in rng]
out = np.asarray(hvd.allreduce(jnp.asarray(small[r]), op=hvd.Adasum,
                               name="vhdd.small"))
np.testing.assert_allclose(out, adasum_reference(small), rtol=1e-5,
                           atol=1e-6)

# joined rank: ring infeasible -> uniform resend onto the payload path,
# which zero-fills the joined rank's world tree position
if r == 3:
    last = hvd.join()
else:
    big2 = [g.randn(4096).astype(np.float32) for g in rng]
    expected = adasum_reference(big2[:3] + [np.zeros(4096, np.float32)])
    out = np.asarray(hvd.allreduce(jnp.asarray(big2[r]), op=hvd.Adasum,
                                   name="vhdd.joined"))
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)
    last = hvd.join()
print(f"rank {r} RING_ADASUM_OK", flush=True)
hvd.shutdown()
"""


def test_ring_adasum_distributed_vhdd():
    """VERDICT r2 item 7: 4-proc tcp Adasum runs the VHDD over the ring
    plane's p2p primitives (reference: adasum.h:194-330) and matches the
    numpy oracle; joined ranks fall back to the payload path with world
    tree semantics."""
    result = _run_hvdrun(4, RING_ADASUM_WORKER,
                         extra_args=(), timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("RING_ADASUM_OK") == 4
