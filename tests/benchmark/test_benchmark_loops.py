"""Each loop end to end at a toy size on the CPU mesh (the ``spmd`` loop
on every cell of the manifest whose traffic names it), the last line's
keys, the refusals of ``run.py``, and a fifth cell added with files
alone."""

import json
import os
import subprocess
import sys

import jax
import pytest

import benchmark_toy
from benchmark_toy import (BENCH, REPO, RESULT_KEYS, bench,  # noqa: F401
                           digest_tree, dump_json, load_json, make_toy_root,
                           toy_root)


@pytest.mark.parametrize(
    "workload", [cell for cell, _ in benchmark_toy.spmd_cells(REPO)])
def test_spmd_loop_runs_end_to_end(workload, bench, toy_root):
    benchmark_toy.spmd_loop_runs_end_to_end(bench, toy_root, workload)


EAGER_DRIVER = """
import json, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
from benchmark_toy import BENCH, load_by_path
import jax
bench = load_by_path(BENCH + "/run.py", "hvd_benchmark_run")
cell = bench.load_cell({root!r}, "resnet50_v15-eager-1chip")
result = bench.run_cell(cell, jax.devices()[:1], 0, 0.05, False)
print(json.dumps(result))
"""


def test_eager_loop_runs_end_to_end(bench, toy_root):
    """In a process of its own: the loop owns ``hvd.init`` on one rank,
    and the session's 8-rank ``hvd`` fixture may live in this one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "-c", EAGER_DRIVER.format(
            repo=REPO, tests=os.path.dirname(__file__), root=toy_root)],
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(result) == RESULT_KEYS
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "images_per_s_per_chip" in result["metrics"]
    served = earlier["notes"]["controller"]
    assert (served["configured"] != "native"
            or served["served_by"] == "NativeController")
    # what the eager plane compiles in steady state is the program's to
    # repair (PERF.md, PR 22); here only the reference and the identity
    # of one rank's Average must hold
    assert all("compiled inside the window" in f
               for f in earlier["failures"])


@pytest.mark.parametrize("argv,why", [
    (["--workload", "gpt2_medium-spmd-1chip"], "not a TPU"),
    (["--workload", "no_such_cell"], "no workload"),
])
def test_run_py_refuses_and_prints_no_result(argv, why):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv, "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert why in done.stderr


def test_unknown_device_kind_has_no_peak(bench):
    """``main`` looks the kind up before anything runs; the CPU's is
    not in the table and nothing stands in for it."""
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    assert jax.devices()[0].device_kind not in peaks["device_kinds"]


def test_traced_run_without_a_device_plane_is_refused(bench, toy_root):
    cell = bench.load_cell(toy_root, "gpt2_medium-spmd-1chip")
    with pytest.raises(bench.BenchmarkError, match="no operation"):
        bench.run_cell(cell, jax.devices()[:1], 0, 0.05, True,
                       log=lambda line: None)


def test_a_fifth_cell_is_files_and_one_entry(bench, tmp_path):
    """A new configuration file, traffic file and layer-metric file and
    their entries in the manifest: no file that was there is edited, and
    the harness runs the cell and reports the metric."""
    root = make_toy_root(tmp_path / "five")
    before = digest_tree(os.path.join(root, "benchmark"))
    config = load_json(os.path.join(
        root, "benchmark", "configs", "gpt2_medium.json"))
    config.update(name="gpt2_wide", n_embd=64, n_head=8, n_inner=256)
    dump_json(config, os.path.join(
        root, "benchmark", "configs", "gpt2_wide.json"))
    traffic = load_json(os.path.join(
        root, "benchmark", "traffic", "spmd_train.json"))
    traffic.update(log_every=3, job={"per_chip_batch": 3})
    dump_json(traffic, os.path.join(
        root, "benchmark", "traffic", "spmd_train_b3.json"))
    with open(os.path.join(root, "benchmark", "end_to_end_metrics",
                           "steps_per_s.py"), "w") as f:
        f.write("def read(run):\n    return run.measured['steps'] / "
                "run.measured['elapsed_s']\n")
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    manifest["configs"].append(dict(
        name="gpt2_wide", source="a test", reduced=[], why="a test",
        file="benchmark/configs/gpt2_wide.json"))
    manifest["workloads"].append(dict(
        name="gpt2_wide-b3", config="gpt2_wide", traffic="spmd_train_b3",
        chips=1, why="a test"))
    manifest["end_to_end"].append(dict(
        name="steps_per_s", unit="steps/s", better="higher", bound=0.01,
        source="host_clock", workloads=["gpt2_wide-b3"]))
    for metric in manifest["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("gpt2_wide-b3")
    dump_json(manifest, os.path.join(root, "BENCHMARK.json"))

    cell = bench.load_cell(root, "gpt2_wide-b3")
    result = bench.run_cell(cell, jax.devices()[:1], 1, 0.05, False,
                            log=lambda line: None)
    assert result["correct"] is True
    assert result["attempted"] % 3 == 0
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "setup_s",
                                      "steps_per_s"}
    after = digest_tree(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 3
