"""Helpers and fixtures of the benchmark's own tests: the harness
loaded by location (it is a script, not a package), a scratch copy of
the benchmark at toy sizes, and the bodies of the tests that hold a
family, callable on any such copy.  Imported by name, and not a
``conftest.py``: test files of ``tests/`` import the one above as
``conftest``.  CPU only (``tests/conftest.py`` pins it); nothing here
times anything.

A PR that adds a configuration adds its file, ``toy/<config>.json``
here, the family's file and hand count if the family is new, and
entries in ``BENCHMARK.json``.  The tests then find configuration,
family and toy sizes by name: its reference is held to the program on
every gradient leaf, the check must fail on its ``perturb``, and every
``spmd`` cell of it runs end to end, with no test written or edited.
"""

import hashlib
import importlib.util
import json
import os
import shutil
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_contract import load_json

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
# a root: the manifest, the benchmark, and the toy sizes by configuration,
#   {"sizes": {...}, "job": {...}, "perturb": "<term>"}
# laid over the configuration's file and its job; ``perturb`` is the
# term the family's reference gets wrong on purpose
ROOT_PARTS = ("BENCHMARK.json", "benchmark", os.path.join(
    "tests", "benchmark", "toy"))
TOY_TRAFFIC = dict(log_every=2, pool_batches=2, warmup_steps=1,
                   trace_steps=2)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load_by_path(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dump_json(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def copy_root(path, source=REPO):
    """``source``'s manifest, benchmark and toy sizes under ``path``, as
    they are checked in: what a later PR adds files and entries to."""
    os.makedirs(path, exist_ok=True)
    for part in ROOT_PARTS:
        whole = os.path.join(source, part)
        if os.path.isdir(whole):
            shutil.copytree(whole, os.path.join(path, part),
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(whole, path)
    return str(path)


def toy_of(root, config_name):
    """The toy sizes of a configuration, by name.  Every configuration
    the manifest names has them: no test builds a model at its
    published widths on the CPU."""
    file = os.path.join(root, ROOT_PARTS[2], config_name + ".json")
    if not os.path.exists(file):
        raise FileNotFoundError(
            f"configuration {config_name!r} has no toy sizes: "
            f"{os.path.relpath(file, root)} is missing")
    return load_json(file)


def make_toy_root(path, source=REPO):
    """A copy of ``source`` whose configurations and traffic are cut to
    sizes the CPU runs in seconds.  Same files, same names, same code."""
    copy_root(path, source)
    for entry in load_json(os.path.join(path, "BENCHMARK.json"))["configs"]:
        toy = toy_of(path, entry["name"])
        file = os.path.join(path, entry["file"])
        config = load_json(file)
        config.update(toy["sizes"])
        config["job"].update(toy["job"])
        dump_json(config, file)
    traffic_dir = os.path.join(path, "benchmark", "traffic")
    for name in os.listdir(traffic_dir):
        traffic = load_json(os.path.join(traffic_dir, name))
        traffic.update(TOY_TRAFFIC)
        dump_json(traffic, os.path.join(traffic_dir, name))
    return str(path)


def digest_tree(root):
    out = {}
    for folder, _, files in os.walk(root):
        for name in files:
            if "__pycache__" not in folder:
                path = os.path.join(folder, name)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def spmd_cells(root):
    """``(cell, family)`` of every cell of the manifest under ``root``
    whose traffic's loop is ``spmd``, in the manifest's order."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    family = {c["name"]: load_json(os.path.join(root, c["file"]))["family"]
              for c in manifest["configs"]}
    return [(w["name"], family[w["config"]]) for w in manifest["workloads"]
            if load_json(os.path.join(
                root, "benchmark", "traffic",
                w["traffic"] + ".json"))["loop"] == "spmd"]


def family_cells(root):
    """The first ``spmd`` cell of every family the manifest names."""
    first = {}
    for cell, family in spmd_cells(root):
        first.setdefault(family, cell)
    return first


# ------------------------------ bodies of the tests that hold a family
def reference_matches_program_model(bench, root, workload):
    """Loss and every gradient leaf, float32 on both sides."""
    cell = bench.load_cell(root, workload)
    family, config, job = cell.family, cell.config, cell.job
    key = jax.random.PRNGKey(3)
    params, extra = family.init(config, job, key)
    # off the symmetric start (zero biases, zero-scaled residuals)
    leaves, tree = jax.tree.flatten(params)
    noise = jax.random.split(key, len(leaves))
    params = tree.unflatten([
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, noise)])
    batch = family.make_batch(config, job, key, family.CHECK_GROUP)

    def program(p):
        return family.loss(config, p, extra, batch)[0]

    def reference(p):
        return family.reference_loss(config, p, extra, batch)[0]

    got, got_grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(reference)(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3,
                                   atol=2e-5 * float(jnp.max(jnp.abs(w))))


def check_fails_on_a_perturbed_reference(bench, root, workload):
    """The same run is ``correct`` against the reference and not against
    one with a term changed: the comparison can see a term."""
    cell = bench.load_cell(root, workload)
    lines = []
    result = bench.run_cell(
        cell, jax.devices()[:1], 0, 0.05, False, log=lines.append,
        perturb_reference=toy_of(root, cell.config["name"])["perturb"])
    assert result["correct"] is False
    assert "off the reference" in lines[-1]


def check_result(result, cell, chips):
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == chips


def spmd_loop_runs_end_to_end(bench, root, workload):
    """On as many CPU devices as the cell asks chips."""
    cell = bench.load_cell(root, workload)
    chips = cell.chips
    lines = []
    result = bench.run_cell(cell, jax.devices()[:chips], 0, 0.05, False,
                            log=lines.append)
    check_result(json.loads(json.dumps(result)), cell, chips)
    earlier = json.loads(lines[-1])
    assert earlier["failures"] == []
    assert {"import_and_devices", "init", "trace_lower", "compile",
            "reference_check", "warmup"} <= set(earlier["setup_split_s"])
    # throughput is read from the median block of log_every steps
    blocks = earlier["window"]["block_s"]
    log_every = cell.traffic["log_every"]
    assert len(blocks) == result["attempted"] // log_every
    rate = next(v["value"] for k, v in result["metrics"].items()
                if k != "setup_s")
    per_block = (log_every * cell.job["per_chip_batch"]
                 * cell.family.sample_units(cell.config, cell.job))
    assert rate == pytest.approx(per_block / statistics.median(blocks))
    if chips > 1:
        assert earlier["notes"]["all_reduces"] > 0
        assert earlier["notes"]["replica_spread"] == 0.0


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
