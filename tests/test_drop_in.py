"""Drop-in import parity: existing Horovod scripts run with their
imports UNCHANGED (`import horovod.torch as hvd`, ...).  The `horovod`
package aliases every public reference import path to the
`horovod_tpu` implementation (reference namespace: `horovod/` tree)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reference_import_paths_resolve():
    script = (
        "import horovod.torch as t\n"
        "import horovod_tpu.torch as t_impl\n"
        "assert t is t_impl, (t, t_impl)\n"
        "import horovod.keras as k\n"
        "import horovod_tpu.keras as k_impl\n"
        "assert k is k_impl\n"
        "import horovod.mxnet as m\n"
        "import horovod_tpu.mxnet as m_impl\n"
        "assert m is m_impl\n"
        "import horovod.spark as s\n"
        "import horovod.spark.keras, horovod.spark.torch\n"
        "import horovod_tpu.spark as s_impl\n"
        "assert s is s_impl\n"
        "import horovod.run as r\n"
        "import horovod_tpu.run as r_impl\n"
        "assert r is r_impl\n"
        "import horovod.torch.compression as c\n"
        "import horovod_tpu.torch.compression as c_impl\n"
        "assert c is c_impl and "
        "c.Compression.fp16 is c_impl.Compression.fp16\n"
        "import horovod.run.runner as rr\n"
        "import horovod_tpu.run.runner as rr_impl\n"
        "assert rr is rr_impl\n"
        "import horovod as h\n"
        "assert callable(h.init) and callable(h.allreduce)\n"
        "print('DROP_IN_IMPORTS_OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    result = subprocess.run([sys.executable, "-c", script], env=env,
                            capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "DROP_IN_IMPORTS_OK" in result.stdout


def test_reference_tensorflow_keras_path():
    """`import horovod.tensorflow.keras as hvd` — the reference's
    tf-keras binding path — lands on horovod_tpu.keras."""
    script = (
        "import horovod.tensorflow.keras as hk\n"
        "import horovod_tpu.keras as k_impl\n"
        "assert hk is k_impl, (hk, k_impl)\n"
        "import horovod.tensorflow as tf_mod\n"
        "import horovod_tpu.tensorflow as tf_impl\n"
        "assert tf_mod is tf_impl\n"
        "assert tf_mod.keras is k_impl\n"
        "print('TF_KERAS_PATH_OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    result = subprocess.run([sys.executable, "-c", script], env=env,
                            capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "TF_KERAS_PATH_OK" in result.stdout


def _clean_worker_env():
    """Env for worker-spawning drop-in tests, simulating a clean user
    shell on a host without an accelerator: strip the harness's TPU
    vars and pin cpu."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for k in list(env):
        if k.startswith("TPU_") or k == "PJRT_LIBRARY_PATH":
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_unmodified_reference_style_script_trains(tmp_path):
    """A training script written against the REFERENCE API (imports and
    all) runs under hvdrun with zero changes."""
    script = tmp_path / "train.py"
    script.write_text(
        "import numpy as np\n"
        "import torch\n"
        "import torch.nn.functional as F\n"
        "import horovod.torch as hvd\n"          # reference import
        "\n"
        "hvd.init()\n"
        "torch.manual_seed(1 + hvd.rank())\n"
        "model = torch.nn.Linear(4, 2)\n"
        "optimizer = torch.optim.SGD(model.parameters(), "
        "lr=0.05 * hvd.size())\n"
        "hvd.broadcast_parameters(model.state_dict(), root_rank=0)\n"
        "hvd.broadcast_optimizer_state(optimizer, root_rank=0)\n"
        "optimizer = hvd.DistributedOptimizer(optimizer, "
        "named_parameters=model.named_parameters())\n"
        "rng = np.random.RandomState(hvd.rank())\n"
        "x = torch.tensor(rng.randn(32, 4), dtype=torch.float32)\n"
        "w = torch.tensor([[1., 0.], [0., 1.], [1., 1.], [0., 0.]])\n"
        "y = x @ w\n"
        "first = last = None\n"
        "for step in range(30):\n"
        "    optimizer.zero_grad()\n"
        "    loss = F.mse_loss(model(x), y)\n"
        "    loss.backward()\n"
        "    optimizer.step()\n"
        "    last = float(loss)\n"
        "    first = first if first is not None else last\n"
        "assert last < first * 0.5, (first, last)\n"
        "if hvd.rank() == 0:\n"
        "    print('REFERENCE_STYLE_TRAIN_OK')\n")
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hvdrun"),
         "-np", "2", sys.executable, str(script)],
        env=_clean_worker_env(), capture_output=True, text=True,
        timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout[-2000:]}\nstderr:\n{result.stderr[-2000:]}"
    assert "REFERENCE_STYLE_TRAIN_OK" in result.stdout


def test_unmodified_reference_style_tf_script_under_horovodrun(tmp_path):
    """The TF flavor, launched with the reference's own CLI name
    (``horovodrun``): DistributedGradientTape + broadcast_variables,
    imports unchanged."""
    import pytest

    pytest.importorskip("tensorflow")
    script = tmp_path / "train_tf.py"
    script.write_text(
        "import numpy as np\n"
        "import tensorflow as tf\n"
        "import horovod.tensorflow as hvd\n"     # reference import
        "\n"
        "hvd.init()\n"
        "tf.random.set_seed(1 + hvd.rank())\n"
        "model = tf.keras.Sequential("
        "[tf.keras.layers.Dense(2, input_shape=(4,))])\n"
        "opt = tf.keras.optimizers.SGD(0.05 * hvd.size())\n"
        "rng = np.random.RandomState(hvd.rank())\n"
        "x = tf.constant(rng.randn(32, 4), dtype=tf.float32)\n"
        "w = tf.constant([[1., 0.], [0., 1.], [1., 1.], [0., 0.]])\n"
        "y = x @ w\n"
        "first = last = None\n"
        "for step in range(25):\n"
        "    with tf.GradientTape() as tape:\n"
        "        loss = tf.reduce_mean((model(x) - y) ** 2)\n"
        "    tape = hvd.DistributedGradientTape(tape)\n"
        "    grads = tape.gradient(loss, model.trainable_variables)\n"
        "    opt.apply_gradients(zip(grads, model.trainable_variables))\n"
        "    if step == 0:\n"
        "        hvd.broadcast_variables(model.variables, root_rank=0)\n"
        "        hvd.broadcast_variables(opt.variables, root_rank=0)\n"
        "    last = float(loss)\n"
        "    first = first if first is not None else last\n"
        "assert last < first * 0.5, (first, last)\n"
        "if hvd.rank() == 0:\n"
        "    print('TF_REFERENCE_STYLE_OK')\n")
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "horovodrun"),
         "-np", "2", sys.executable, str(script)],
        env=_clean_worker_env(), capture_output=True, text=True,
        timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout[-2000:]}\nstderr:\n{result.stderr[-2000:]}"
    assert "TF_REFERENCE_STYLE_OK" in result.stdout


def test_alias_modules_keep_own_spec_and_support_reload():
    """The alias loader must restore the implementation module's own
    __spec__ (ADVICE round 5): with the alias spec left in place,
    importlib.reload() dispatched to the no-op alias loader and was a
    silent no-op, and find_spec disagreed with __name__."""
    script = (
        "import importlib, importlib.util\n"
        "import horovod.torch as t\n"
        "assert t.__name__ == 'horovod_tpu.torch', t.__name__\n"
        "assert t.__spec__ is not None\n"
        "assert t.__spec__.name == 'horovod_tpu.torch', t.__spec__.name\n"
        "# reload must actually re-execute the implementation module:\n"
        "# delete a module-level binding and check re-execution"
        " restores it\n"
        "del t.DistributedOptimizer\n"
        "t2 = importlib.reload(t)\n"
        "assert t2 is t\n"
        "assert hasattr(t, 'DistributedOptimizer'), 'reload was a no-op'\n"
        "assert t.__spec__.name == 'horovod_tpu.torch'\n"
        "print('ALIAS_SPEC_OK')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    result = subprocess.run([sys.executable, "-c", script], env=env,
                            capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "ALIAS_SPEC_OK" in result.stdout
