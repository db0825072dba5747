"""Each loop end to end at a toy size on the CPU mesh, the last line's
keys, the refusals of ``run.py``, and a fifth cell added with files
alone."""

import hashlib
import json
import os
import statistics
import subprocess
import sys

import jax
import pytest

from benchmark_toy import (BENCH, REPO, bench, dump_json,  # noqa: F401
                           load_json, make_toy_root, toy_root)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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


@pytest.mark.parametrize("workload,chips", [
    ("gpt2_medium-spmd-1chip", 1), ("gpt2_medium-spmd-dp4", 4),
    ("resnet50_v15-spmd-1chip", 1)])
def test_spmd_loop_runs_end_to_end(workload, chips, bench, toy_root):
    cell = bench.load_cell(toy_root, workload)
    lines = []
    result = bench.run_cell(cell, jax.devices()[:chips], 0, 0.05, False,
                            log=lines.append)
    check_result(json.loads(json.dumps(result)), cell, chips)
    earlier = json.loads(lines[-1])
    assert earlier["failures"] == []
    assert {"import_and_devices", "init", "trace_lower", "compile", "reference_check",
            "warmup"} <= set(earlier["setup_split_s"])
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
