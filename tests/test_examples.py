"""Examples run end-to-end as smoke tests (reference CI runs its examples
the same way, ``gen-pipeline.sh:145-264``)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run_example(name, *args, timeout=180):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    # Some TPU plugins ignore JAX_PLATFORMS; pin the CPU backend
    # programmatically before the example module runs.
    bootstrap = (
        "import jax, runpy, sys; "
        "jax.config.update('jax_platforms', 'cpu'); "
        f"sys.argv = [sys.argv[0]] + {list(args)!r}; "
        f"runpy.run_path({os.path.join(EXAMPLES, name)!r}, "
        "run_name='__main__')"
    )
    return subprocess.run(
        [sys.executable, "-c", bootstrap],
        env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("name,args", [
    ("adasum_small_model.py", ("--steps", "10")),
    ("join_uneven_data.py", ()),
    ("interactive_run.py", ()),
    ("ring_attention_long_context.py", ("--seq-len", "512")),
    ("ring_attention_long_context.py",
     ("--strategy", "zigzag", "--seq-len", "512")),
    ("long_context_training.py", ("--steps", "4", "--seq-len", "128")),
    ("transformer_lm.py", ("--steps", "2", "--d-model", "64",
                           "--n-layers", "2", "--seq-len", "32")),
    ("jax_mnist.py", ("--epochs", "1", "--batch-size", "256",
                      "--num-samples", "512")),
    # (its time is the compile of ResNet-50's step at 224 x 224, which
    # no flag of the example changes)
    ("jax_imagenet_resnet50.py", ("--epochs", "1", "--steps", "1",
                                  "--batch-size", "1")),
    ("moe_expert_parallel.py", ("--steps", "4", "--d-model", "64",
                                "--seq-len", "32")),
    ("ulysses_long_context.py", ("--seq-len", "256", "--head-dim", "16")),
    ("cluster_estimator.py", ("--epochs", "3",)),
    ("tensor_parallel_transformer.py", ("--steps", "4", "--d-model",
                                        "64", "--seq-len", "32")),
    ("pipeline_parallel.py", ("--steps", "5",)),
    ("timeline_profiling.py", ()),
    ("jax_word2vec.py", ("--corpus-len", "4000", "--epochs", "1",
                         "--batch-size", "512", "--vocab-size", "500")),
    ("adasum_bench.py", ("--steps", "10", "--lrs", "0.05", "0.2",
                         "--tp-bytes", "65536")),
    ("mxnet_mnist.py", ()),  # prints a clean notice when mxnet absent
    ("zero1_sharded_optimizer.py", ("--steps", "12", "--batch-size",
                                    "64", "--hidden", "32")),
    ("data_pipeline.py", ("--epochs", "1", "--rows", "1024",
                          "--batch-size", "128")),
])
def test_example_runs(name, args):
    result = _run_example(name, *args)
    assert result.returncode == 0, \
        f"{name} failed\nstdout:\n{result.stdout}\nstderr:\n{result.stderr}"


def test_torch_mnist_under_hvdrun():
    """The torch binding's documented mode: one process per rank."""
    result = _run_example_hvdrun("torch_mnist.py", "--epochs", "1",
                                 "--num-samples", "256")
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"


def test_checkpoint_resume_example(tmp_path):
    d = str(tmp_path / "ckpts")
    first = _run_example("checkpoint_resume.py", "--dir", d, "--steps", "6")
    assert first.returncode == 0, first.stderr
    second = _run_example("checkpoint_resume.py", "--dir", d, "--steps", "6")
    assert second.returncode == 0, second.stderr
    assert "resumed from step" in second.stdout


def test_synthetic_benchmark_tiny():
    result = _run_example(
        "jax_synthetic_benchmark.py", "--model", "resnet50",
        "--batch-size", "1", "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1", "--num-iters", "1", timeout=180)
    assert result.returncode == 0, result.stderr
    assert "Img/sec per device" in result.stdout


def test_synthetic_benchmark_transformer_tiny():
    result = _run_example(
        "jax_synthetic_benchmark.py", "--model", "transformer",
        "--seq-len", "64", "--d-model", "128", "--n-layers", "2",
        "--vocab-size", "512", "--batch-size", "8",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1", timeout=180)
    assert result.returncode == 0, result.stderr
    assert "Tokens/sec per device" in result.stdout



def _run_example_hvdrun(name, *args, np_=2, timeout=180):
    """Per-process bindings (torch/TF/keras) run one process per rank."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    worker = (
        "import jax, runpy, sys; "
        "jax.config.update('jax_platforms', 'cpu'); "
        f"sys.argv = [{name!r}] + {list(args)!r}; "
        f"runpy.run_path({os.path.join(EXAMPLES, name)!r}, "
        "run_name='__main__')"
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hvdrun"),
         "-np", str(np_), sys.executable, "-c", worker],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_torch_synthetic_benchmark_under_hvdrun():
    result = _run_example_hvdrun(
        "torch_synthetic_benchmark.py", "--batch-size", "4", "--img",
        "32", "--num-iters", "1", "--num-batches-per-iter", "2")
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert "Img/sec per rank" in result.stdout


def test_torch_imagenet_resnet50_under_hvdrun():
    # one step a rank, of the least batch a batch norm in training takes
    result = _run_example_hvdrun(
        "torch_imagenet_resnet50.py", "--epochs", "1", "--batch-size",
        "2", "--num-samples", "2", "--img", "32", "--num-classes", "10")
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("RESNET50 DONE") == 2


@pytest.mark.parametrize("name,args", [
    # (a rank's half of the samples must hold a whole batch)
    ("tensorflow2_mnist.py", ("--epochs", "1", "--batch-size", "64",
                              "--num-samples", "128")),
    ("tensorflow2_keras_mnist.py", ("--epochs", "1", "--batch-size", "64",
                                    "--num-samples", "64")),
    ("keras_mnist_advanced.py", ("--epochs", "2", "--batch-size", "64",
                                 "--num-samples", "64",
                                 "--warmup-epochs", "1")),
    ("tensorflow2_synthetic_benchmark.py",
     ("--model", "small", "--batch-size", "4", "--img", "32",
      "--num-iters", "1", "--num-batches-per-iter", "1")),
])
def test_tf2_examples_under_hvdrun(name, args):
    pytest.importorskip("tensorflow")
    result = _run_example_hvdrun(name, *args)
    assert result.returncode == 0, \
        f"{name} failed\nstdout:\n{result.stdout}\n" \
        f"stderr:\n{result.stderr}"


def test_keras_imagenet_resnet50_train_and_resume(tmp_path):
    pytest.importorskip("tensorflow")
    ckpt_dir = str(tmp_path / "krn50")
    args = ("--epochs", "1", "--batch-size", "2", "--num-samples", "2",
            "--img", "32", "--num-classes", "4",
            "--checkpoint-dir", ckpt_dir)
    first = _run_example_hvdrun("keras_imagenet_resnet50.py", *args)
    assert first.returncode == 0, \
        f"stdout:\n{first.stdout}\nstderr:\n{first.stderr[-3000:]}"
    assert first.stdout.count("KERAS RESNET50 DONE") == 2
    assert os.path.exists(os.path.join(ckpt_dir, "checkpoint-1.keras"))

    # second run resumes from the rank-0 checkpoint (0 epochs left)
    second = _run_example_hvdrun("keras_imagenet_resnet50.py", *args)
    assert second.returncode == 0, \
        f"stdout:\n{second.stdout}\nstderr:\n{second.stderr[-3000:]}"
    assert second.stdout.count("KERAS RESNET50 DONE") == 2


def test_spark_mnist_example():
    """Spark example (reference: keras_spark_mnist.py family) through
    the pyspark shim: run(fn) + estimator-over-SparkBackend."""
    from tests.conftest import pyspark_shim_env as shim_env
    result = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "spark_mnist.py"),
         "--num-proc", "2", "--epochs", "3"],
        env=shim_env(), capture_output=True, text=True, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    assert "SPARK_MNIST_OK" in result.stdout
