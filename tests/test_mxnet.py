"""MXNet binding executed for real (reference: ``test/test_mxnet.py``
run under horovodrun).  MXNet is EOL upstream and uninstallable here
(no egress to PyPI), so the driver runs against ``tests/_mxnet_shim`` —
a stand-in reproducing exactly the NDArray / optimizer / gluon surface
the binding touches (see its module docstring).  Exercised for real:
the collective surface (in- and out-of-place) over the eager plane,
the DistributedOptimizer sum+1/size-rescale semantics incl. the
tuple-index aggregated path, the gluon DistributedTrainer hook with
the forced kvstore=None, both double-wrap guards, and
broadcast_parameters incl. the deferred-init post-hook broadcast."""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHIM = os.path.join(REPO, "tests", "_mxnet_shim")


def _run_driver(script, timeout=180):
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_mxnet_driver.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = (SHIM + os.pathsep + REPO + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    return subprocess.run([sys.executable, path], env=env,
                          capture_output=True, text=True, timeout=timeout)


DRIVER = r"""
import numpy as np

import jax
jax.config.update("jax_platforms", "cpu")

import mxnet as mx
import horovod_tpu.mxnet as hvd
from horovod_tpu.common import basics

hvd.init()
N = hvd.size()
assert N == 8


def per_rank(r):
    # -- collectives ------------------------------------------------------
    t = mx.nd.array(np.arange(4, dtype=np.float32) * (r + 1))
    out = hvd.allreduce(t, average=True, name="mx.avg")
    np.testing.assert_allclose(
        out.asnumpy(), np.arange(4) * (N + 1) / 2.0, rtol=1e-6)
    assert out.dtype == np.float32

    t = mx.nd.array(np.full(3, float(r + 1), np.float32))
    hvd.allreduce_(t, average=False, name="mx.sum")  # in place
    np.testing.assert_allclose(t.asnumpy(),
                               np.full(3, float(sum(range(1, N + 1)))))

    g = hvd.allgather(mx.nd.array(np.full((r + 1, 2), float(r),
                                          np.float32)), name="mx.ag")
    assert g.shape == (sum(range(1, N + 1)), 2)

    b = mx.nd.array(np.full(3, float(r) + 5.0, np.float32))
    hvd.broadcast_(b, root_rank=2, name="mx.bc")
    np.testing.assert_allclose(b.asnumpy(), np.full(3, 7.0))

    a2a = hvd.alltoall(
        mx.nd.array((np.arange(N) + 100 * r).astype(np.float32)),
        name="mx.a2a")
    np.testing.assert_allclose(
        a2a.asnumpy(), np.array([r + 100.0 * s for s in range(N)]))

    # out-of-place broadcast keeps the source untouched
    src = mx.nd.array(np.full(2, float(r), np.float32))
    bout = hvd.broadcast(src, root_rank=1, name="mx.bc2")
    np.testing.assert_allclose(bout.asnumpy(), np.full(2, 1.0))
    np.testing.assert_allclose(src.asnumpy(), np.full(2, float(r)))

    # -- DistributedOptimizer: sum + 1/size rescale == averaged SGD ------
    opt = hvd.DistributedOptimizer(
        mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0))
    w = mx.nd.array(np.zeros(4, np.float32))
    grad = mx.nd.array(np.full(4, float(r + 1), np.float32))
    opt.update(0, w, grad, None)
    # averaged gradient = (N+1)/2; step = -0.1 * that
    np.testing.assert_allclose(w.asnumpy(),
                               np.full(4, -0.1 * (N + 1) / 2.0),
                               rtol=1e-6)

    # tuple-index multi-tensor update path (update_multi_precision)
    ws = [mx.nd.array(np.zeros(2, np.float32)) for _ in range(2)]
    gs = [mx.nd.array(np.full(2, float(r + 1) * (i + 1), np.float32))
          for i in range(2)]
    opt.update_multi_precision((10, 11), ws, gs, [None, None])
    for i, w_i in enumerate(ws):
        np.testing.assert_allclose(
            w_i.asnumpy(),
            np.full(2, -0.1 * (i + 1) * (N + 1) / 2.0), rtol=1e-6)

    # delegate surface + state creation
    opt.set_learning_rate(0.2)
    assert opt.lr == 0.2
    opt.set_lr_mult({}), opt.set_wd_mult({})
    assert opt.create_state_multi_precision(0, w) is None

    # double-wrap guard on the optimizer side too
    try:
        hvd.DistributedOptimizer(opt)
        raise AssertionError("expected ValueError for double wrap")
    except ValueError:
        pass

    # -- gluon DistributedTrainer ----------------------------------------
    p = mx.gluon.Parameter(
        "w", data=mx.nd.array(np.zeros(3, np.float32)))
    p.grad[:] = np.full(3, float(2 * (r + 1)), np.float32)
    trainer = hvd.DistributedTrainer(
        [p], mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0))
    assert trainer._kvstore is None  # gluon's 'device' default is fatal
    trainer.step(batch_size=1)
    # grads summed then rescaled by 1/size: avg = N+1; step -0.5*(N+1)
    np.testing.assert_allclose(p.data().asnumpy(),
                               np.full(3, -0.5 * (N + 1)), rtol=1e-6)

    # double-wrap is a hard error, not silent double rescale
    try:
        hvd.DistributedTrainer([p], hvd.DistributedOptimizer(
            mx.optimizer.SGD(learning_rate=0.1)))
        raise AssertionError("expected ValueError for double wrap")
    except ValueError:
        pass

    # -- broadcast_parameters: dict + deferred-init post-hook -------------
    params = {
        "a": mx.nd.array(np.full(2, float(r), np.float32)),
        "b": mx.gluon.Parameter("b", data=mx.nd.array(
            np.full(2, 10.0 * r, np.float32))),
        "deferred": mx.gluon.Parameter("deferred"),
    }
    hvd.broadcast_parameters(params, root_rank=3)
    np.testing.assert_allclose(params["a"].asnumpy(), np.full(2, 3.0))
    np.testing.assert_allclose(params["b"].data().asnumpy(),
                               np.full(2, 30.0))
    # the deferred parameter broadcasts the moment gluon initializes it
    # (reference: the _init_impl wrapper) — each rank initializes with
    # its OWN value; after init all must hold root 3's
    params["deferred"].initialize(
        mx.nd.array(np.full(2, 100.0 * r, np.float32)))
    np.testing.assert_allclose(params["deferred"].data().asnumpy(),
                               np.full(2, 300.0))
    return True


assert all(basics.run_parallel(per_rank))
print("MXNET_BINDING_OK", flush=True)
"""


def test_mxnet_binding_executes():
    result = _run_driver(DRIVER)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    assert "MXNET_BINDING_OK" in result.stdout


def test_import_guard_without_mxnet():
    """Without mxnet on the path the binding raises the documented
    ImportError on first use but imports cleanly."""
    script = (
        "import numpy as np\n"
        "import horovod_tpu.mxnet as hvd\n"
        "try:\n"
        "    hvd.allreduce(None)\n"
        "    raise SystemExit('expected ImportError')\n"
        "except ImportError as exc:\n"
        "    assert 'MXNet' in str(exc), exc\n"
        "print('MX_GUARD_OK')\n")
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_mxnet_guard.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO   # note: no shim
    result = subprocess.run([sys.executable, path], env=env,
                            capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "MX_GUARD_OK" in result.stdout
