"""Hierarchical allreduce/allgather (reference:
``NCCLHierarchicalAllreduce`` — reduce-scatter within the fast group,
allreduce across groups, allgather back, ``nccl_operations.cc:162-289``;
``MPIHierarchicalAllgather`` two-phase gather, ``mpi_operations.cc``).

Driven purely via env vars in a subprocess (reference test model: stall /
timeline tests), on a 2x4 (cross, local) hierarchy over the 8-device CPU
mesh; results must be bit-identical to the flat path's numpy expectation.
"""

import os
import subprocess
import sys

SCRIPT = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
state = basics._get_state()
assert state.executor.hier_mesh is not None, "hierarchy not constructed"
assert dict(zip(state.executor.hier_mesh.axis_names,
                state.executor.hier_mesh.devices.shape)) == \
    {"cross": 2, "local": 4}
assert state.executor.hierarchical_allreduce
assert state.executor.hierarchical_allgather

N = 8

# allreduce: aligned size and an awkward 13-element size (pads to the
# local*64 alignment inside the program)
for shape in [(4, 16), (13,)]:
    data = [np.random.RandomState(r).randn(*shape).astype(np.float32)
            for r in range(N)]
    expected = np.sum(np.stack(data), axis=0)

    def fn(r, data=data, shape=shape):
        return np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name=f"h.{shape}"))

    for out in basics.run_parallel(fn):
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)

# grouped allreduce exercises the fused (concatenated) buffer
datas = [[np.random.RandomState(100 + r).randn(5).astype(np.float32),
          np.random.RandomState(200 + r).randn(3, 3).astype(np.float32)]
         for r in range(N)]
exp0 = np.sum(np.stack([d[0] for d in datas]), axis=0)
exp1 = np.sum(np.stack([d[1] for d in datas]), axis=0)

def grouped(r):
    outs = hvd.grouped_allreduce(
        [jnp.asarray(t) for t in datas[r]], op=hvd.Sum, name="h.grouped")
    return [np.asarray(o) for o in outs]

for o0, o1 in basics.run_parallel(grouped):
    np.testing.assert_allclose(o0, exp0, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(o1, exp1, rtol=1e-4, atol=1e-5)

# hierarchical Adasum (opt-in knob): adasum of per-group averages
from horovod_tpu.ops.adasum import adasum_reference
adata = [np.random.RandomState(50 + r).randn(21).astype(np.float32)
         for r in range(N)]
ga = np.sum(adata[:4], axis=0) / 4.0
gb = np.sum(adata[4:], axis=0) / 4.0
aexpected = adasum_reference([ga, gb])

def afn(r):
    return np.asarray(hvd.allreduce(jnp.asarray(adata[r]), op=hvd.Adasum,
                                    name="h.adasum"))

for out in basics.run_parallel(afn):
    np.testing.assert_allclose(out, aexpected, rtol=1e-4, atol=1e-5)

# allgather with per-rank variable first dimension
gdata = [np.full((r + 1, 2), float(r), np.float32) for r in range(N)]
gexpected = np.concatenate(gdata, axis=0)

def gfn(r):
    return np.asarray(hvd.allgather(jnp.asarray(gdata[r]), name="h.gather"))

for out in basics.run_parallel(gfn):
    np.testing.assert_allclose(out, gexpected)

hvd.shutdown()
print("HIERARCHICAL_OK")
"""


def _run(extra_env):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    env.update(extra_env)
    return subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=180)


def test_hierarchical_collectives_match_flat_expectation():
    result = _run({
        "HVD_HIER_LOCAL_SIZE": "4",
        "HVD_HIERARCHICAL_ALLREDUCE": "1",
        "HVD_HIERARCHICAL_ALLGATHER": "1",
        "HVD_ADASUM_HIERARCHICAL": "1",
    })
    assert result.returncode == 0, result.stderr
    assert "HIERARCHICAL_OK" in result.stdout


def test_hierarchy_degenerate_without_grouping():
    """Without a local-size hint all 8 CPU devices share one process — the
    hierarchy must degrade to None and the flags stay harmless."""
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np, jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.common import basics\n"
        "hvd.init()\n"
        "state = basics._get_state()\n"
        "assert state.executor.hier_mesh is None\n"
        "outs = basics.run_parallel(lambda r: np.asarray(\n"
        "    hvd.allreduce(jnp.ones((4,)) * r, op=hvd.Sum, name='d')))\n"
        "for o in outs:\n"
        "    np.testing.assert_allclose(o, np.full((4,), 28.0))\n"
        "hvd.shutdown()\n"
        "print('DEGENERATE_OK')\n"
    )
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_HIERARCHICAL_ALLREDUCE": "1",
    })
    result = subprocess.run([sys.executable, "-c", code], env=env,
                            capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, result.stderr
    assert "DEGENERATE_OK" in result.stdout
