"""Multi-host global-mesh end-to-end tests.

Two hvdrun processes, each with 4 virtual CPU devices, form ONE
8-device ``jax.distributed`` global mesh (reference analog:
``gloo_context.cc:56-73`` full-mesh rendezvous from launcher env).  The
data plane is compiled XLA collectives over the global mesh; the TCP
wire carries metadata only (``ops/global_controller.py``).

These are the pod-mode (``hvdrun --tpu``) tests the driver's real-TPU
runs can't cover on one chip.
"""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HVDRUN = os.path.join(REPO, "bin", "hvdrun")

EAGER_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel

hvd.init()
pid = int(os.environ["HVD_RANK"])
assert hvd.size() == 8, hvd.size()
assert hvd.local_size() == 4, hvd.local_size()
assert hvd.cross_size() == 2
assert hvd.mesh().shape["hvd"] == 8

def per_rank(lr):
    r = hvd.rank()
    out = np.asarray(hvd.allreduce(jnp.full((4,), float(r)), op=hvd.Sum,
                                   name="ar"))
    np.testing.assert_allclose(out, np.full((4,), 28.0))

    out = np.asarray(hvd.allreduce(jnp.full((3,), float(r)), name="avg"))
    np.testing.assert_allclose(out, np.full((3,), 3.5))

    b = np.asarray(hvd.broadcast(jnp.full((3,), float(r)), root_rank=5,
                                 name="bc"))
    np.testing.assert_allclose(b, np.full((3,), 5.0))

    g = np.asarray(hvd.allgather(jnp.full((r % 2 + 1, 2), float(r)),
                                 name="ag"))
    expect = np.concatenate(
        [np.full((i % 2 + 1, 2), float(i)) for i in range(8)])
    np.testing.assert_allclose(g, expect)

    t = jnp.arange(8, dtype=jnp.float32) + 100 * r
    out = np.asarray(hvd.alltoall(t, name="a2a"))
    expect = np.array([float(src * 100 + r) for src in range(8)])
    np.testing.assert_allclose(out, expect)

    # variable splits alltoall: rank r sends (dst+1) rows to each dst
    rows = sum(d + 1 for d in range(8))
    t = jnp.full((rows, 2), float(r))
    splits = [d + 1 for d in range(8)]
    out = np.asarray(hvd.alltoall(t, splits=splits, name="a2av"))
    expect = np.concatenate(
        [np.full((r + 1, 2), float(src)) for src in range(8)])
    np.testing.assert_allclose(out, expect)
    return r

ranks = run_parallel(per_rank)
assert ranks == [pid * 4 + l for l in range(4)], ranks

# cross-process validation errors surface everywhere
from horovod_tpu.common.handles import HvdError
def bad(lr):
    r = hvd.rank()
    try:
        hvd.allreduce(jnp.ones((2 + r,)), op=hvd.Sum, name="bad")
        raise SystemExit("expected HvdError for mismatched shapes")
    except HvdError:
        return True
assert all(run_parallel(bad))

print(f"proc {pid} GMESH_EAGER_OK", flush=True)
hvd.shutdown()
"""

TRAIN_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import optax
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel
from horovod_tpu.parallel import shard_global_batch
from horovod_tpu.parallel._compat import shard_map
from jax.sharding import PartitionSpec as P

hvd.init()
pid = int(os.environ["HVD_RANK"])
mesh = hvd.mesh()

from horovod_tpu.models import MLP
model = MLP(features=(16, 4))
params = model.init(jax.random.PRNGKey(0), np.ones((1, 8), np.float32))
opt = hvd.DistributedOptimizer(optax.sgd(0.05), named_axes=("hvd",))
opt_state = opt.init(params)

def per_shard(params, opt_state, x, y):
    def loss_fn(p):
        return ((model.apply(p, x) - y) ** 2).mean()
    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    return (optax.apply_updates(params, updates), opt_state,
            jax.lax.pmean(loss, "hvd"))

step = jax.jit(shard_map(per_shard, mesh=mesh,
    in_specs=(P(), P(), P("hvd"), P("hvd")), out_specs=(P(), P(), P())))

# per-host data loading: each process contributes its 8 local rows
rng = np.random.RandomState(pid)
xd = shard_global_batch(rng.randn(8, 8).astype(np.float32))
yd = shard_global_batch(rng.randn(8, 4).astype(np.float32))
losses = []
for _ in range(15):
    params, opt_state, loss = step(params, opt_state, xd, yd)
    losses.append(float(np.asarray(jax.device_get(loss))))
assert losses[-1] < losses[0] * 0.9, losses
print(f"proc {pid} SPMD_TRAIN_OK", flush=True)

def per_rank(lr):
    r = hvd.rank()
    # out-of-order async across the pod
    names = [f"n{i}" for i in range(8)]
    order = names if r % 2 == 0 else names[::-1]
    hs = {n: hvd.allreduce_async(jnp.ones((4,)) * (r + 1), op=hvd.Sum,
                                 name=n) for n in order}
    for n in names:
        np.testing.assert_allclose(np.asarray(hvd.synchronize(hs[n])),
                                   np.full((4,), 36.0))
    # Adasum across processes vs the numpy oracle
    from horovod_tpu.ops.adasum import adasum_reference
    data = [np.arange(1, 5, dtype=np.float32) * (i + 1) for i in range(8)]
    out = np.asarray(hvd.allreduce(jnp.asarray(data[r]), op=hvd.Adasum,
                                   name="ads"))
    np.testing.assert_allclose(out, adasum_reference(data), rtol=1e-4)
    # join with uneven work spanning both processes
    if r <= 2:
        extra = np.asarray(hvd.allreduce(jnp.ones((2,)) * 5, op=hvd.Sum,
                                         name="uneven"))
        np.testing.assert_allclose(extra, np.full((2,), 15.0))
    last = hvd.join()
    # ranks 0-2 joined only after their extra allreduce completed, so the
    # coordinator-serialized last joiner must be one of them
    assert last in (0, 1, 2), last
    return True

assert all(run_parallel(per_rank))
print(f"proc {pid} GMESH_TRAIN_OK", flush=True)
hvd.shutdown()
"""


def _run_gmesh(script, np_=2, devices_per_proc=4, timeout=180,
               extra_env=None):
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_multihost_worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TPU_", "JAX_"))}
    env.update(extra_env or {})
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    from tests.conftest import readd_jax_cache
    readd_jax_cache(env)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    cmd = [sys.executable, HVDRUN, "-np", str(np_), "--global-mesh",
           sys.executable, path]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_global_mesh_eager_collectives():
    result = _run_gmesh(EAGER_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GMESH_EAGER_OK") == 2


def test_global_mesh_spmd_training_and_join():
    result = _run_gmesh(TRAIN_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("SPMD_TRAIN_OK") == 2
    assert result.stdout.count("GMESH_TRAIN_OK") == 2


MATRIX_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel

hvd.init()
pid = int(os.environ["HVD_RANK"])
n = hvd.size()

def per_rank(lr):
    r = hvd.rank()
    # dtype sweep over the compiled global-mesh plane
    for dtype in ("float32", "bfloat16", "int32", "uint8"):
        data = ((np.arange(6) % 3) + 1).astype(dtype)
        out = np.asarray(hvd.allreduce(jnp.asarray(data), op=hvd.Sum,
                                       name=f"gm.{dtype}"))
        expect = (((np.arange(6) % 3) + 1) * n).astype(np.float64)
        np.testing.assert_allclose(out.astype(np.float64), expect)

    # grouped fusion burst across processes
    handles = [hvd.allreduce_async(jnp.full((5,), float(r + 1)),
                                   op=hvd.Sum, name=f"gfuse.{i}")
               for i in range(12)]
    for h in handles:
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   np.full((5,), 36.0))

    # 0-d scalar over the compiled plane
    out = hvd.allreduce(jnp.float32(r), op=hvd.Sum, name="gm0d")
    assert np.asarray(out).ndim == 0
    assert float(np.asarray(out)) == sum(range(8))
    return True

assert all(run_parallel(per_rank))

# hierarchical allreduce over the (cross, local) = (process, chip) mesh
os.environ_backup = None
from horovod_tpu.common import basics
state = basics._get_state()
assert state.executor.hier_mesh is not None, "expected 2-proc hier mesh"
state.executor.hierarchical_allreduce = True

def per_rank_hier(lr):
    r = hvd.rank()
    out = np.asarray(hvd.allreduce(jnp.full((33,), float(r + 1)),
                                   op=hvd.Sum, name="gmhier"))
    np.testing.assert_allclose(out, np.full((33,), 36.0))
    return True

assert all(run_parallel(per_rank_hier))
print(f"proc {pid} GMESH_MATRIX_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_dtype_matrix_and_hierarchical():
    result = _run_gmesh(MATRIX_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GMESH_MATRIX_OK") == 2


STALL_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel
from horovod_tpu.common.handles import HvdError

hvd.init()
pid = int(os.environ["HVD_RANK"])

def per_rank(lr):
    r = hvd.rank()
    # a healthy collective first: the stall must poison only the
    # stalled name, and only after the shutdown threshold
    out = np.asarray(hvd.allreduce(jnp.full((3,), float(r)), op=hvd.Sum,
                                   name="healthy"))
    np.testing.assert_allclose(out, np.full((3,), 28.0))

    if pid == 1:
        # process 1 never submits the stalled tensor
        return "skipped"
    try:
        hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="stalled")
        return "no-error"
    except HvdError as exc:
        assert "stall" in str(exc).lower(), exc
        return "raised"

results = run_parallel(per_rank)
expected = "raised" if pid == 0 else "skipped"
assert all(x == expected for x in results), (pid, results)
print(f"proc {pid} GMESH_STALL_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_stall_shutdown():
    """A process that never submits a tensor trips the coordinator's
    stall shutdown; the waiting process gets a per-name HvdError while
    healthy collectives complete (reference: StallInspector +
    Response::ERROR semantics, on the pod control plane)."""
    result = _run_gmesh(STALL_WORKER, timeout=180, extra_env={
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "4",
    })
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GMESH_STALL_OK") == 2


FOURPROC_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel

hvd.init()
pid = int(os.environ["HVD_RANK"])
assert hvd.size() == 8 and hvd.local_size() == 2 and hvd.cross_size() == 4

def per_rank(lr):
    r = hvd.rank()
    out = np.asarray(hvd.allreduce(jnp.full((5,), float(r + 1)),
                                   op=hvd.Sum, name="f.ar"))
    np.testing.assert_allclose(out, np.full((5,), 36.0))
    g = np.asarray(hvd.allgather(jnp.full((1, 2), float(r)), name="f.ag"))
    np.testing.assert_allclose(
        g, np.arange(8, dtype=np.float32)[:, None] * np.ones((1, 2)))
    return r

ranks = run_parallel(per_rank)
assert ranks == [pid * 2, pid * 2 + 1], ranks
print(f"proc {pid} GMESH_4P_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_four_processes():
    """A different pod shape: 4 processes x 2 devices forming the same
    8-rank global mesh (the coordinator's per-process bookkeeping must
    not assume 2 hosts)."""
    result = _run_gmesh(FOURPROC_WORKER, np_=4, devices_per_proc=2,
                        timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GMESH_4P_OK") == 4


LOCAL_MISMATCH_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel
from horovod_tpu.common.handles import HvdError

hvd.init()
pid = int(os.environ["HVD_RANK"])

def per_rank(lr):
    r = hvd.rank()
    # ranks 0 and 1 live in process 0 and disagree on shape: the
    # coordinator only compares across processes, so the process must
    # catch this locally and the error must reach EVERY rank globally
    shape = (2, 3) if r != 1 else (3, 2)
    try:
        hvd.allreduce(jnp.ones(shape), op=hvd.Sum, name="local.bad")
        return "no-error"
    except HvdError as exc:
        assert "mismatched shapes" in str(exc), exc
        return "raised"

results = run_parallel(per_rank)
assert all(x == "raised" for x in results), (pid, results)

# and the job keeps working afterwards
def ok(lr):
    out = np.asarray(hvd.allreduce(jnp.ones((3,)), op=hvd.Sum,
                                   name="after.ok"))
    np.testing.assert_allclose(out, np.full((3,), 8.0))
    return True
assert all(run_parallel(ok))
print(f"proc {pid} GMESH_LOCAL_MISMATCH_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_intra_process_mismatch_errors_globally():
    """Two ranks INSIDE one process disagreeing on a tensor's shape must
    error every rank in the job (regression: the coordinator only
    validated across processes, so the misalignment executed silently)."""
    result = _run_gmesh(LOCAL_MISMATCH_WORKER, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GMESH_LOCAL_MISMATCH_OK") == 2


GROUPED_WORKER = r"""
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel

hvd.init()
pid = hvd.cross_rank()
n = hvd.size()

def per_rank(_local):
    r = hvd.rank()  # run_parallel passes the LOCAL thread index
    # mixed dtypes in one grouped submission: separate fusion buckets
    # on the coordinator (allreduce_bucket_key), all complete
    outs = hvd.grouped_allreduce(
        [jnp.ones(4, jnp.float32) * (r + 1),
         jnp.ones(4, jnp.bfloat16) * (r + 1),
         jnp.ones(4, jnp.float32) * 2 * (r + 1)],
        op=hvd.Sum, name="gg.mixed")
    total = float(sum(range(1, n + 1)))
    np.testing.assert_allclose(np.asarray(outs[0]), np.full(4, total))
    assert outs[1].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(outs[2]),
                               np.full(4, 2 * total))

    # scalar (0-d reshaped) + vector in one group
    outs = hvd.grouped_allreduce(
        [jnp.asarray([float(r)]), jnp.ones(3)],
        op=hvd.Sum, name="gg.scalar")
    assert float(outs[0][0]) == float(sum(range(n)))

    # a burst of small same-dtype tensors: fused into ordered buckets
    outs = hvd.grouped_allreduce(
        [jnp.full((8,), float(i + r)) for i in range(12)],
        op=hvd.Average, name="gg.burst")
    for i, out in enumerate(outs):
        expect = sum(i + rr for rr in range(n)) / n
        np.testing.assert_allclose(np.asarray(out), np.full(8, expect),
                                   rtol=1e-6)
    return True

assert all(run_parallel(per_rank))
print(f"proc {pid} GMESH_GROUPED_OK", flush=True)
"""


def test_global_mesh_grouped_fused_edges():
    """Grouped/fused edge cases under the gmesh controller (VERDICT r2
    item 8): mixed-dtype bucket splits, scalars, and a 12-tensor burst
    through the global sequence log."""
    result = _run_gmesh(GROUPED_WORKER, extra_env={
        "HVD_FUSION_THRESHOLD": "128",  # force multi-bucket fusion
    })
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    for p in range(2):
        assert f"proc {p} GMESH_GROUPED_OK" in result.stdout


ERROR_SWEEP_GMESH = r"""
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common.basics import run_parallel
from horovod_tpu.common.handles import HvdError

hvd.init()
pid = hvd.cross_rank()
n = hvd.size()

def per_rank(_local):
    r = hvd.rank()
    cases = [
        (lambda: hvd.allreduce(np.ones(2 + r % 2, np.float32),
                               op=hvd.Sum, name="ge.shape"), "shape"),
        (lambda: hvd.allreduce(
            np.ones(3, np.float32 if r % 2 == 0 else np.int32),
            op=hvd.Sum, name="ge.dtype"), "dtype"),
        (lambda: hvd.allreduce(
            np.ones(3, np.float32),
            op=hvd.Sum if r % 2 == 0 else hvd.Average,
            name="ge.op"), "op"),
        (lambda: hvd.broadcast(np.ones(3, np.float32), root_rank=r % 2,
                               name="ge.root"), "root"),
        (lambda: hvd.allgather(
            np.ones((2, 3 + r % 2), np.float32), name="ge.trail"),
         "trailing"),
    ]
    for submit, frag in cases:
        try:
            submit()
            raise AssertionError(f"expected HvdError for {frag}")
        except HvdError as exc:
            assert frag in str(exc).lower(), (frag, str(exc))
    # recovery: the names work again after the error rounds
    out = np.asarray(hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum,
                                   name="ge.shape"))
    np.testing.assert_allclose(out, np.full(3, float(n)))
    return True

assert all(run_parallel(per_rank))
print(f"proc {pid} GMESH_ERRORS_OK", flush=True)
"""


def test_global_mesh_error_sweep():
    """Per-op cross-rank mismatch sweep + recovery through the global
    sequence log (errors must surface on EVERY process identically)."""
    result = _run_gmesh(ERROR_SWEEP_GMESH)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    for p in range(2):
        assert f"proc {p} GMESH_ERRORS_OK" in result.stdout


POD81_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
pid = int(os.environ["HVD_RANK"])
r = hvd.rank()
assert hvd.size() == 8, hvd.size()
assert hvd.local_size() == 1, hvd.local_size()
assert hvd.cross_size() == 8, hvd.cross_size()
assert r == pid

# flat eager pass first
out = np.asarray(hvd.allreduce(jnp.full((5,), float(r)), op=hvd.Sum,
                               name="pod.ar"))
np.testing.assert_allclose(out, np.full((5,), 28.0))

# hierarchical allreduce over the (cross=2, local=4) split: SAME numbers
# as flat (communication-schedule choice only), exercised over a payload
# that needs padding to the local*64 alignment
from horovod_tpu.common import basics
st = basics._get_state()
assert st.executor.hier_mesh is not None, "hier mesh missing"
assert st.executor.hierarchical_allreduce, "hier allreduce not enabled"
x = jnp.arange(130, dtype=jnp.float32) + 1000.0 * r
out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="pod.har"))
expect = np.arange(130, dtype=np.float32) * 8 + 1000.0 * sum(range(8))
np.testing.assert_allclose(out, expect, rtol=1e-6)

# hierarchical average with prescale
out = np.asarray(hvd.allreduce(jnp.full((66,), float(r)),
                               prescale_factor=2.0, name="pod.havg"))
np.testing.assert_allclose(out, np.full((66,), 7.0))

# hierarchical allgather
assert st.executor.hierarchical_allgather
g = np.asarray(hvd.allgather(jnp.full((2, 3), float(r)), name="pod.hag"))
expect = np.concatenate([np.full((2, 3), float(i)) for i in range(8)])
np.testing.assert_allclose(g, expect)

# broadcast + alltoall ride the same 8x1 gang
b = np.asarray(hvd.broadcast(jnp.full((4,), float(r)), root_rank=6,
                             name="pod.bc"))
np.testing.assert_allclose(b, np.full((4,), 6.0))
t = jnp.arange(8, dtype=jnp.float32) + 100 * r
out = np.asarray(hvd.alltoall(t, name="pod.a2a"))
np.testing.assert_allclose(
    out, np.array([float(src * 100 + r) for src in range(8)]))

print(f"proc {pid} POD81_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_8x1_hierarchical_gang():
    """VERDICT r3 item 7: the pod-realistic 8-process x 1-device shape
    with hierarchical allreduce/allgather over an explicit
    (cross=2, local=4) split, so the first real pod run has zero new
    code paths (reference: nccl_operations.cc:162-289 topology split)."""
    result = _run_gmesh(POD81_WORKER, np_=8, devices_per_proc=1,
                        extra_env={
                            "HVD_HIERARCHICAL_ALLREDUCE": "1",
                            "HVD_HIERARCHICAL_ALLGATHER": "1",
                            "HVD_HIER_LOCAL_SIZE": "4",
                        })
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("POD81_OK") == 8


ZIGZAG_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.parallel import (make_mesh, reference_attention,
                                  zigzag_ring_self_attention)

hvd.init()
mesh = make_mesh({"sp": len(jax.devices())})   # 8 devices over 2 procs

rng = np.random.RandomState(0)                 # same data on both hosts
b, t, h, d = 1, 128, 2, 16
q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
           for _ in range(3))
got = zigzag_ring_self_attention(q, k, v, mesh, use_flash=False)
exp = reference_attention(q, k, v, causal=True)
from jax.experimental import multihost_utils
got_np = np.asarray(multihost_utils.process_allgather(got, tiled=True))
np.testing.assert_allclose(got_np, np.asarray(exp),
                           rtol=2e-4, atol=2e-4)
print("GMESH_ZIGZAG_OK", flush=True)
hvd.shutdown()
"""


def test_global_mesh_zigzag_attention():
    """Zigzag (balanced causal) ring over the REAL 2-process x 4-device
    global mesh gang — the pod wiring — must be exact attention."""
    result = _run_gmesh(ZIGZAG_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    assert result.stdout.count("GMESH_ZIGZAG_OK") == 2
