"""TCP controller v2 tests: worker-ring data plane, response cache,
persistent mux transport, per-rank timeline with rank-0 merge, and the
jitted-local-step + eager-gradient-allreduce pattern (each process uses
its own accelerator; reference: one-GPU-per-process).

Reference analogs: ``gloo_operations.cc:30-100`` (ring allreduce),
``response_cache.cc`` (steady-state fast path), ``timeline.cc`` (rank 0
writes one file for all ranks).
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HVDRUN = os.path.join(REPO, "bin", "hvdrun")


def _run_hvdrun(np_, script, extra_env=None, timeout=180):
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_tcp_v2_worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, HVDRUN, "-np", str(np_), sys.executable, path]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


RING_WORKER = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()
assert n == 4

# ring allreduce (threshold forced to 1KB by the test) — 1MB payload,
# odd length so ring chunks are unequal (array_split path)
big = np.full((262147,), float(r + 1), np.float32)
out = np.asarray(hvd.allreduce(jnp.asarray(big), op=hvd.Sum, name="big"))
np.testing.assert_allclose(out, np.full_like(big, 10.0))

# ring average + prescale/postscale
out = np.asarray(hvd.allreduce(jnp.asarray(big), name="bigavg",
                               prescale_factor=2.0))
np.testing.assert_allclose(out, np.full_like(big, 5.0))

# ring broadcast: ~4MB from rank 2, multiple pipeline chunks
data = np.arange(1 << 20, dtype=np.float32) * (r + 1)
out = np.asarray(hvd.broadcast(jnp.asarray(data), root_rank=2,
                               name="bigbc"))
np.testing.assert_allclose(out, np.arange(1 << 20, dtype=np.float32) * 3)

# ring allgather with variable first dims
blk = np.full((1024 * (r + 1), 2), float(r), np.float32)
out = np.asarray(hvd.allgather(jnp.asarray(blk), name="bigag"))
expect = np.concatenate(
    [np.full((1024 * (i + 1), 2), float(i), np.float32) for i in range(4)])
np.testing.assert_allclose(out, expect)

# small tensors still ride the coordinator star
s = np.asarray(hvd.allreduce(jnp.ones((8,)) * (r + 1), op=hvd.Sum,
                             name="small"))
np.testing.assert_allclose(s, np.full((8,), 10.0))

# fusion-adjacent: many concurrent outstanding ring + star ops
handles = {}
for i in range(8):
    nm = f"mix{i}"
    t = jnp.ones((70000 if i % 2 == 0 else 4,)) * (r + 1)
    handles[nm] = hvd.allreduce_async(t, op=hvd.Sum, name=nm)
for nm, h in handles.items():
    out = np.asarray(hvd.synchronize(h))
    np.testing.assert_allclose(out, np.full_like(out, 10.0))

# join with ring-size uneven work
if r != 3:
    extra = np.asarray(hvd.allreduce(jnp.full((70000,), float(r + 1)),
                                     op=hvd.Sum, name="uneven"))
    np.testing.assert_allclose(extra, np.full((70000,), 6.0))
last = hvd.join()
assert last in range(4)

print(f"rank {r} RING_OK", flush=True)
hvd.shutdown()
"""


def test_ring_data_plane_4proc():
    result = _run_hvdrun(4, RING_WORKER,
                         extra_env={"HVD_TCP_RING_THRESHOLD": "1024"})
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("RING_OK") == 4


CACHE_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
# steady-state: same name, same signature, 20 rounds
for i in range(20):
    out = np.asarray(hvd.allreduce(jnp.ones((16,)) * (r + 1), op=hvd.Sum,
                                   name="steady"))
    np.testing.assert_allclose(out, np.full((16,), 3.0))
# signature change (different shape) must still validate correctly
from horovod_tpu.common.handles import HvdError
try:
    hvd.allreduce(jnp.ones((4 + r,)), op=hvd.Sum, name="steady")
    raise SystemExit("expected shape mismatch")
except HvdError:
    pass
if r == 0:
    from horovod_tpu.common import basics
    hits = basics._get_state().controller._coordinator.cache_hits
    assert hits >= 19, f"expected cache fast path, hits={hits}"
    print(f"CACHE_HITS={hits}", flush=True)
print(f"rank {r} CACHE_OK", flush=True)
hvd.shutdown()
"""


def test_response_cache_fast_path():
    result = _run_hvdrun(2, CACHE_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("CACHE_OK") == 2
    assert "CACHE_HITS=" in result.stdout


TIMELINE_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
np.asarray(hvd.allreduce(jnp.ones((70000,)), op=hvd.Sum, name="tl_ring"))
np.asarray(hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="tl_star"))
print(f"rank {r} TL_OK", flush=True)
hvd.shutdown()
"""


def test_timeline_tcp_mode_with_rank0_merge(tmp_path):
    tl = str(tmp_path / "trace.json")
    result = _run_hvdrun(2, TIMELINE_WORKER, extra_env={
        "HVD_TIMELINE": tl, "HVD_TCP_RING_THRESHOLD": "1024"})
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    # merged file exists and contains both ranks' rows + both phases
    with open(tl) as f:
        events = json.load(f)
    names = {e.get("args", {}).get("name", "") for e in events
             if e.get("name") == "process_name"}
    assert any(n.startswith("rank 0:") for n in names), names
    assert any(n.startswith("rank 1:") for n in names), names
    phases = {e.get("name") for e in events}
    assert "NEGOTIATE_ALLREDUCE" in phases, phases
    assert "RING_ALLREDUCE" in phases, phases
    assert "ALLREDUCE" in phases, phases  # star-path op phase


LOCAL_STEP_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import optax
import horovod_tpu as hvd
from horovod_tpu.models import MLP

hvd.init()
r, n = hvd.rank(), hvd.size()

# the reference's one-accelerator-per-process pattern: the jitted step
# runs on THIS rank's device; only gradients ride the eager collectives
dev = hvd.local_device()
model = MLP(features=(16, 4))
params = model.init(jax.random.PRNGKey(0), np.ones((1, 8), np.float32))
params = jax.device_put(params, dev)
opt = optax.sgd(0.05)
opt_state = jax.device_put(opt.init(params), dev)

@jax.jit
def grads_fn(params, x, y):
    def loss_fn(p):
        return ((model.apply(p, x) - y) ** 2).mean()
    return jax.value_and_grad(loss_fn)(params)

rng = np.random.RandomState(r)
x = jax.device_put(rng.randn(8, 8).astype(np.float32), dev)
y = jax.device_put(rng.randn(8, 4).astype(np.float32), dev)

losses = []
for step in range(10):
    loss, grads = grads_fn(params, x, y)
    flat, tree = jax.tree_util.tree_flatten(grads)
    reduced = [hvd.allreduce(g, name=f"g{i}.{step}")
               for i, g in enumerate(flat)]
    grads = jax.tree_util.tree_unflatten(tree, reduced)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    red = np.asarray(hvd.allreduce(loss.reshape(1),
                                   name=f"loss.{step}"))
    losses.append(float(red[0]))
assert losses[-1] < losses[0], losses
assert all(d.platform == "cpu" for d in jax.tree_util.tree_leaves(
    jax.tree.map(lambda a: list(a.devices())[0], params)))
print(f"rank {r} LOCAL_STEP_OK loss {losses[0]:.4f}->{losses[-1]:.4f}",
      flush=True)
hvd.shutdown()
"""


def test_local_jitted_step_with_eager_grad_allreduce():
    result = _run_hvdrun(2, LOCAL_STEP_WORKER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("LOCAL_STEP_OK") == 2
