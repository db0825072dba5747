"""BENCHMARK.json holds to the contract, and every name in it resolves
to a file of its own."""

import os
import re

import pytest

from benchmark_toy import BENCH, REPO, load_json, manifest  # noqa: F401

MANIFEST = load_json(os.path.join(REPO, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = ([("end_to_end_metrics", m) for m in MANIFEST["end_to_end"]]
           + [("layer_metrics", m) for m in MANIFEST["per_layer"]])


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_manifest_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 2 <= len(manifest["workloads"]) <= 24
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    # a pair of configuration and traffic appears once
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for _, m in METRICS]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("directory,metric", METRICS,
                         ids=[m["name"] for _, m in METRICS])
def test_metric_entry_and_reader(directory, metric):
    end_to_end = directory == "end_to_end_metrics"
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        # a per-layer metric is reported only where the metric it moves is
        for cell in CELLS:
            assert not applies(metric, cell) or applies(moved, cell)
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    reader = os.path.join(BENCH, directory, metric["name"] + ".py")
    with open(reader) as f:
        assert "def read(run):" in f.read()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell, manifest):
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    sizes = load_json(os.path.join(REPO, config["file"]))
    traffic = load_json(os.path.join(
        BENCH, "traffic", entry["traffic"] + ".json"))
    assert traffic["chips"] == entry["chips"]
    assert os.path.exists(os.path.join(
        BENCH, "models", sizes["family"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH, "loops", traffic["loop"] + ".py"))
    # set-up, one more end-to-end metric, at least one per-layer metric
    end_to_end = [m["name"] for m in manifest["end_to_end"]
                  if applies(m, cell)]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert any(applies(m, cell) for m in manifest["per_layer"])


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=[c["name"] for c in MANIFEST["configs"]])
def test_configuration_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("benchmark/")
    assert any(w["config"] == config["name"]
               for w in MANIFEST["workloads"])
    sizes = load_json(os.path.join(REPO, config["file"]))
    assert sizes["source"] == config["source"]
    assert 1 <= len(config["source"]) <= 200
    assert sizes["reduced"] == config["reduced"] == []
    assert sizes["assumed"] and sizes["deployment"] and sizes["job"]


def test_benchmark_reads_nothing_of_the_old_benchmarks():
    """``bench.py`` and ``chip_smoke.py`` may change; the yardstick
    imports neither."""
    for folder, _, files in os.walk(BENCH):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert not re.search(
                    r"^\s*(import|from)\s+(bench|chip_smoke)\b", text,
                    re.MULTILINE), name


def test_peaks_table_is_the_v5e_alone():
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    assert list(peaks["device_kinds"]) == ["TPU v5 lite"]
    v5e = peaks["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and peaks["source"]
