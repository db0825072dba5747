"""What a later PR may add with files and entries alone, proven on
scratch roots: a configuration with a cut, the refusals of a malformed
cut, a new family held by the tests that hold the two here, and the
refusal of a configuration without toy sizes.  No file that was there is
edited: the tree's digest before and after says so."""

import os
import re
import shutil

import jax
import pytest

import benchmark_contract as contract
import benchmark_toy
from benchmark_toy import (bench, copy_root, digest_tree,  # noqa: F401
                           dump_json, load_json, make_toy_root)

CUT = "n_layer: 24 -> 1; a test"


def add_configuration(root, name, like="gpt2_medium", toy=True, **changes):
    """A configuration ``name`` made from ``like``'s file and toy file,
    one ``spmd`` cell on one chip, and their entries in the manifest."""
    config = load_json(os.path.join(
        root, "benchmark", "configs", like + ".json"))
    config.update(name=name, **changes)
    cut = [entry.partition(":")[0] for entry in config["reduced"]]
    dump_json(config, os.path.join(
        root, "benchmark", "configs", name + ".json"))
    if toy:
        sizes = load_json(os.path.join(
            root, "tests", "benchmark", "toy", like + ".json"))
        for key in cut:
            # the toy file leaves alone what the cut names
            del sizes["sizes"][key]
        dump_json(sizes, os.path.join(
            root, "tests", "benchmark", "toy", name + ".json"))
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = name + "-spmd-1chip"
    manifest["configs"].append(dict(
        name=name, source=config["source"], why="a test", reduced=cut,
        file=f"benchmark/configs/{name}.json"))
    manifest["workloads"].append(dict(
        name=cell, config=name, traffic="spmd_train", chips=1, why="a test"))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in ("tokens_per_s_per_chip", "step_ms_p50"):
            metric["workloads"].append(cell)
    dump_json(manifest, os.path.join(root, "BENCHMARK.json"))
    return cell


def hold_to_contract(root):
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    contract.check_manifest(root, manifest)
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        contract.check_metric(root, manifest, metric)
    for cell in manifest["workloads"]:
        contract.check_cell(root, manifest, cell)
    for config in manifest["configs"]:
        contract.check_configuration(root, manifest, config)


def unedited(before, root):
    """Every file that was there is as it was, the manifest aside;
    returns how many files were added."""
    after = digest_tree(root)
    kept = {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert {k: after[k] for k in kept} == kept
    return len(after) - len(before)


def test_a_configuration_with_a_cut_passes_and_runs(bench, tmp_path):
    """``reduced`` may hold a cut: the contract passes on the files as
    they would be checked in, and the cell runs ``correct`` at the toy
    sizes with the cut left as the file has it."""
    source = copy_root(tmp_path / "source")
    before = digest_tree(source)
    cell = add_configuration(source, "gpt2_cut", n_layer=1, reduced=[CUT])
    hold_to_contract(source)
    assert unedited(before, source) == 2

    toy = make_toy_root(tmp_path / "toy", source)
    loaded = bench.load_cell(toy, cell)
    assert loaded.config["n_layer"] == 1 and loaded.config["n_embd"] == 32
    assert loaded.config["reduced"] == [CUT]
    result = bench.run_cell(loaded, jax.devices()[:1], 1, 0.05, False,
                            log=lambda line: None)
    assert result["correct"] is True


@pytest.mark.parametrize("listed,said,refusal", [
    (["n_head"], [CUT], "lists ['n_head']"),
    (["n_layers"], ["n_layers: 24 -> 1"], "'n_layers: 24 -> 1' names"),
    (["n_layer"], ["n_layer: 24 -> 2"], "'n_layer: 24 -> 2' says 2 is run"),
    (["n_layer"], ["n_layer: 1 -> 1"], "'n_layer: 1 -> 1' changes nothing"),
    (["n_layer"], [["n_layer", 24, 1]], "['n_layer', 24, 1] is not a string"),
    (["n_layer"], [CUT + " " * 200], "has 224 characters"),
    (["n_layer", "n_layer"], [CUT, "n_layer: 12 -> 1"],
     "'n_layer: 12 -> 1' names 'n_layer' twice"),
    (["n_layer"], ["n_layer 24 to 1"], "'n_layer 24 to 1' does not read"),
    (["n_layer"], ["n_layer: many -> 1"], "have to be JSON"),
], ids=["lists_differ", "no_such_key", "not_the_files_value",
        "published_is_here", "not_a_string", "too_long", "key_twice",
        "no_arrow", "not_json"])
def test_a_malformed_cut_is_refused(listed, said, refusal, tmp_path):
    source = copy_root(tmp_path / "source")
    add_configuration(source, "gpt2_cut", n_layer=1, reduced=[CUT])
    manifest = load_json(os.path.join(source, "BENCHMARK.json"))
    entry = manifest["configs"][-1]
    entry["reduced"] = listed
    file = os.path.join(source, entry["file"])
    dump_json(dict(load_json(file), reduced=said), file)
    with pytest.raises(AssertionError, match=re.escape(refusal)) as refused:
        contract.check_configuration(source, manifest, entry)
    assert "reduced of 'gpt2_cut'" in str(refused.value)


def test_a_new_family_is_files_and_entries(bench, tmp_path):
    """A family file under another name, a configuration of it, its toy
    file, one cell: the tests that hold ``transformer_lm`` and ``resnet``
    find it in the manifest and hold it as they hold those."""
    source = copy_root(tmp_path / "source")
    before = digest_tree(source)
    models = os.path.join(source, "benchmark", "models")
    shutil.copy(os.path.join(models, "transformer_lm.py"),
                os.path.join(models, "decoder_lm.py"))
    cell = add_configuration(source, "gpt2_other", family="decoder_lm")
    hold_to_contract(source)
    assert unedited(before, source) == 3

    toy = make_toy_root(tmp_path / "toy", source)
    assert benchmark_toy.family_cells(toy)["decoder_lm"] == cell
    assert (cell, "decoder_lm") in benchmark_toy.spmd_cells(toy)
    loaded = bench.load_cell(toy, cell)
    assert loaded.family.__file__.endswith("decoder_lm.py")
    benchmark_toy.reference_matches_program_model(bench, toy, cell)
    benchmark_toy.check_fails_on_a_perturbed_reference(bench, toy, cell)
    benchmark_toy.spmd_loop_runs_end_to_end(bench, toy, cell)


def test_a_configuration_without_toy_sizes_is_refused(tmp_path):
    """Or a test would build it at its published widths on the CPU."""
    source = copy_root(tmp_path / "source")
    add_configuration(source, "gpt2_whole", toy=False)
    with pytest.raises(FileNotFoundError, match=re.escape(
            os.path.join("tests", "benchmark", "toy", "gpt2_whole.json"))):
        make_toy_root(tmp_path / "toy", source)
