"""The benchmark's contract as plain functions of ``(root, manifest,
entry)``: what a manifest, a metric, a cell and a configuration have to
hold.  ``test_benchmark_manifest.py`` asks it of the repo's manifest;
``test_benchmark_additions.py`` asks it of scratch roots that a later PR
could have made.  ``root`` holds ``BENCHMARK.json`` and ``benchmark/``
as they are checked in (a toy root's copy of a configuration is not
held to the grammar of a cut: a toy file may shrink a key that
``reduced`` names).
"""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def check_manifest(root, manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 2 <= len(manifest["workloads"]) <= 24
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    # a pair of configuration and traffic appears once
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for entries in (manifest["configs"], manifest["workloads"],
                    manifest["end_to_end"] + manifest["per_layer"]):
        names = [e["name"] for e in entries]
        assert len(set(names)) == len(names), names


def check_metric(root, manifest, metric):
    end_to_end = metric in manifest["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = [w["name"] for w in manifest["workloads"]]
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = next(m for m in manifest["end_to_end"]
                     if m["name"] == metric["moves"])
        # a per-layer metric is reported only where the metric it moves is
        for cell in cells:
            assert not applies(metric, cell) or applies(moved, cell)
    for cell in metric.get("workloads", []):
        assert cell in cells
    reader = os.path.join(
        root, manifest["paths"][0],
        "end_to_end_metrics" if end_to_end else "layer_metrics",
        metric["name"] + ".py")
    with open(reader) as f:
        assert "def read(run):" in f.read()


def check_cell(root, manifest, entry):
    cell = entry["name"]
    bench = os.path.join(root, manifest["paths"][0])
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    sizes = load_json(os.path.join(root, config["file"]))
    traffic = load_json(os.path.join(
        bench, "traffic", entry["traffic"] + ".json"))
    assert traffic["chips"] == entry["chips"]
    assert os.path.exists(os.path.join(
        bench, "models", sizes["family"] + ".py"))
    assert os.path.exists(os.path.join(
        bench, "loops", traffic["loop"] + ".py"))
    # set-up, one more end-to-end metric, at least one per-layer metric
    end_to_end = [m["name"] for m in manifest["end_to_end"]
                  if applies(m, cell)]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert any(applies(m, cell) for m in manifest["per_layer"])


def check_configuration(root, manifest, config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith("benchmark/")
    assert any(w["config"] == config["name"]
               for w in manifest["workloads"])
    sizes = load_json(os.path.join(root, config["file"]))
    assert sizes["source"] == config["source"]
    assert 1 <= len(config["source"]) <= 200
    check_reduced(config, sizes)
    # a cut or none: what it stands for, and what is not a cut
    # (departures of the program's block, sizes the source does not give)
    assert sizes["assumed"] and sizes["deployment"] and sizes["job"]


def check_reduced(config, sizes):
    """A configuration's cut.  The manifest's ``reduced`` lists the KEYS
    changed from the source, bare names as the driver reads them (it
    holds a catalog model's file to the catalog key by key and lets
    only these differ).  The file's ``reduced`` says for each of them,
    in the same order,

        <key>: <published> -> <here>[; <why>]

    where ``<key>`` is a top-level key of the file, ``<published>`` and
    ``<here>`` are JSON, ``<here>`` is the file's value of that key and
    ``<published>`` another.  So the list cannot drift from the sizes
    the cell runs.  ``[]`` in both means nothing was cut.

    Which keys may be cut is the model-configs guide's business (depth,
    never a width): nothing here can know which keys are widths."""
    who = f"reduced of {config['name']!r}"
    listed, said = config["reduced"], sizes["reduced"]
    assert isinstance(listed, list) and isinstance(said, list), who
    assert len(listed) <= 16, f"{who}: more than 16 keys"
    keys = []
    for entry in said:
        assert isinstance(entry, str), f"{who}: {entry!r} is not a string"
        assert 1 <= len(entry) <= 200, (
            f"{who}: {entry[:40]!r}... has {len(entry)} characters, "
            f"not 1 to 200")
        key, colon, rest = entry.partition(": ")
        published, arrow, here = rest.partition("; ")[0].partition(" -> ")
        assert colon and arrow, (
            f"{who}: {entry!r} does not read '<key>: <published> -> "
            f"<here>[; <why>]'")
        assert NAME.match(key) and key in sizes, (
            f"{who}: {entry!r} names {key!r}, which is no key of "
            f"{config['file']}")
        assert key not in keys, f"{who}: {entry!r} names {key!r} twice"
        keys.append(key)
        try:
            published, here = json.loads(published), json.loads(here)
        except ValueError:
            raise AssertionError(
                f"{who}: {entry!r}: both sizes have to be JSON") from None
        assert here == sizes[key], (
            f"{who}: {entry!r} says {here!r} is run, {config['file']} "
            f"has {sizes[key]!r}")
        assert published != here, (
            f"{who}: {entry!r} changes nothing")
    assert listed == keys, (
        f"{who}: BENCHMARK.json lists {listed}, {config['file']} "
        f"explains {keys}")
