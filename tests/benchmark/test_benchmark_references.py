"""Each family's plain reference against the program's model at a toy
size, and the check that holds a cell to it.  The families are those
the manifest's configurations name, each on its first ``spmd`` cell;
the bodies are in ``benchmark_toy.py``, callable on any root."""

import pytest

import benchmark_toy
from benchmark_toy import REPO, bench, toy_root  # noqa: F401

FAMILY_CELLS = benchmark_toy.family_cells(REPO)


@pytest.mark.parametrize("family_name", sorted(FAMILY_CELLS))
def test_reference_matches_program_model(family_name, bench, toy_root):
    benchmark_toy.reference_matches_program_model(
        bench, toy_root, FAMILY_CELLS[family_name])


@pytest.mark.parametrize("family_name", sorted(FAMILY_CELLS))
def test_check_fails_on_a_perturbed_reference(family_name, bench, toy_root):
    benchmark_toy.check_fails_on_a_perturbed_reference(
        bench, toy_root, FAMILY_CELLS[family_name])
