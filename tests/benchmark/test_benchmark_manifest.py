"""BENCHMARK.json holds to the contract (``benchmark_contract.py``), and
every name in it resolves to a file of its own.

A PR that adds a configuration adds its file, its toy file, the family's
file and hand count if the family is new, and entries in the manifest;
every case below then runs on its entries too, and
``test_benchmark_references.py`` and ``test_benchmark_loops.py`` find
its family and its cells by name."""

import os
import re

import pytest

import benchmark_contract as contract
from benchmark_toy import BENCH, REPO, load_json, manifest  # noqa: F401

MANIFEST = load_json(os.path.join(REPO, "BENCHMARK.json"))
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def named(entries):
    return dict(argvalues=entries, ids=[e["name"] for e in entries])


def test_manifest_keys_and_limits(manifest):
    contract.check_manifest(REPO, manifest)


@pytest.mark.parametrize("metric", **named(METRICS))
def test_metric_entry_and_reader(metric, manifest):
    contract.check_metric(REPO, manifest, metric)


@pytest.mark.parametrize("cell", **named(MANIFEST["workloads"]))
def test_cell_resolves_to_files(cell, manifest):
    contract.check_cell(REPO, manifest, cell)


@pytest.mark.parametrize("config", **named(MANIFEST["configs"]))
def test_configuration_file(config, manifest):
    contract.check_configuration(REPO, manifest, config)


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
