"""Helpers and fixtures of the benchmark's own tests: the harness
loaded by location (it is a script, not a package) and a scratch copy
of the benchmark at toy sizes.  Imported by name, and not a
``conftest.py``: test files of ``tests/`` import the one above as
``conftest``.  CPU only (``tests/conftest.py`` pins it); nothing here
times anything."""

import importlib.util
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))

TOY_SIZES = {
    "gpt2_medium": (
        dict(vocab_size=97, n_positions=32, n_embd=32, n_layer=2, n_head=4,
             n_inner=128, activation_dtype="float32"),
        dict(per_chip_batch=2, seq_len=32)),
    "resnet50_v15": (
        dict(stage_sizes=[1, 1], num_filters=8, num_classes=10,
             activation_dtype="float32"),
        dict(per_chip_batch=8, image_size=32)),
}
TOY_TRAFFIC = dict(log_every=2, pool_batches=2, warmup_steps=1,
                   trace_steps=2)


def load_by_path(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def dump_json(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def make_toy_root(path):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` under ``path``
    whose configurations and traffic are cut to sizes the CPU runs in
    seconds.  Same files, same names, same code."""
    shutil.copytree(BENCH, os.path.join(path, "benchmark"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    for name, (sizes, job) in TOY_SIZES.items():
        file = os.path.join(path, "benchmark", "configs", name + ".json")
        config = load_json(file)
        config.update(sizes)
        config["job"].update(job)
        dump_json(config, file)
    traffic_dir = os.path.join(path, "benchmark", "traffic")
    for name in os.listdir(traffic_dir):
        traffic = load_json(os.path.join(traffic_dir, name))
        traffic.update(TOY_TRAFFIC)
        dump_json(traffic, os.path.join(traffic_dir, name))
    return str(path)


@pytest.fixture(scope="session")
def bench():
    """``benchmark/run.py`` as a module."""
    return load_by_path(os.path.join(BENCH, "run.py"), "hvd_benchmark_run")


@pytest.fixture(scope="session")
def manifest():
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    return make_toy_root(tmp_path_factory.mktemp("toy_benchmark"))
