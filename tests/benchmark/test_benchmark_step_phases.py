"""``horovod_tpu/utils/trace.py:step_phases`` over the compiled step of
every ``spmd`` cell of the manifest, at the toy sizes, as the loop file
builds it on the CPU mesh: every instruction has a phase, recomputation
is found exactly where the configuration has a checkpoint, the gradient
reduction is the exchange and the optimizer's arithmetic the update,
and what has no name of its own is what the compiler made itself and
is read as its neighbours.  A configuration a later PR adds is held
unasked."""

import collections
import re

import jax
import pytest

import benchmark_toy
from benchmark_toy import REPO, bench, toy_root  # noqa: F401

from horovod_tpu.parallel import make_mesh
from horovod_tpu.utils import trace

CELLS = [cell for cell, _ in benchmark_toy.spmd_cells(REPO)]
# what the CPU's compiler makes itself and gives no ``op_name``:
# arguments and constants, tuples and their elements, layout and copies
COMPILER_MADE = re.compile(
    r"^(param|params_|opt_state_|extra_|batch|constant|tuple|"
    r"get-tuple-element|bitcast|copy|wrapped_|transpose_copy_fusion|"
    r"concatenate_bitcast_fusion|convolution)")


@pytest.fixture(scope="module")
def steps(bench, toy_root):
    """``steps(workload)``: the cell, its compiled step's text and what
    ``step_phases`` makes of it; each step compiled once."""
    made = {}

    def step(workload):
        if workload not in made:
            cell = bench.load_cell(toy_root, workload)
            run = bench.Run(cell, jax.devices()[:cell.chips], 0, 0.05)
            mesh = make_mesh({"hvd": cell.chips}, devices=run.devices)
            opt, jitted = cell.loop.make_step(cell, run.optimizer(), mesh)
            params, extra = cell.family.init(cell.config, cell.job,
                                             jax.random.PRNGKey(0))
            batch = cell.family.make_batch(
                cell.config, cell.job, jax.random.PRNGKey(1),
                cell.job["per_chip_batch"] * cell.chips)
            text = jitted.lower(params, extra, opt.init(params),
                                batch).compile().as_text()
            made[workload] = (cell, text, *trace.step_phases(text))
        return made[workload]

    return step


def op_names(text):
    """``{instruction: op_name}`` of the instructions that have one."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r'^\s+(?:ROOT )?%?([\w.\-]+) = .*metadata=\{op_name="([^"]*)"',
        text, re.MULTILINE)}


@pytest.mark.parametrize("workload", CELLS)
def test_every_instruction_of_entry_and_loops_has_a_phase(workload, steps):
    _, text, instructions, fused, _ = steps(workload)
    assert set(trace.PHASES) >= {p for p, _ in instructions.values()}
    # entry and loop bodies: every ``while`` and all it runs is there,
    # and every fusion with what it holds
    entry = re.search(r"^ENTRY .*?^\}", text, re.MULTILINE | re.DOTALL)
    for line in entry.group(0).splitlines()[1:-1]:
        name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
        assert name in instructions, line[:120]
        if " fusion(" in line:
            assert name in fused
        for body in re.findall(r"body=%?([\w.\-]+)", line):
            inside = re.search(
                rf"^%?{re.escape(body)} .*?^\}}", text,
                re.MULTILINE | re.DOTALL).group(0).splitlines()[1:-1]
            assert inside and all(
                re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", row).group(1)
                in instructions for row in inside)
    for phases in fused.values():
        assert phases <= set(trace.PHASES)


@pytest.mark.parametrize("workload", CELLS)
def test_recompute_is_found_exactly_where_a_checkpoint_is(workload, steps):
    cell, _, instructions, _, _ = steps(workload)
    recomputed = [n for n, (p, _) in instructions.items() if p == "recompute"]
    assert bool(recomputed) == bool(cell.config.get("remat"))


@pytest.mark.parametrize("workload", CELLS)
def test_exchange_holds_the_reduction_and_update_the_optimizer(workload,
                                                               steps):
    cell, text, instructions, _, borrowed = steps(workload)
    names = op_names(text)
    by_phase = collections.defaultdict(set)
    for name, (phase, _) in instructions.items():
        by_phase[phase].add(name)
    reductions = {n for n in instructions
                  if n.startswith("all-reduce")
                  and "hvd/exchange" in names.get(n, "")}
    # the gradients' psum; the loss's pmean is the caller's, so update's
    assert reductions and reductions <= by_phase["exchange"]
    assert all("hvd/exchange" in names[n] for n in by_phase["exchange"]
               if n in names and n not in borrowed)
    optimizer = {n for n in instructions if "hvd/update" in names.get(n, "")}
    assert len(optimizer) > 10 and optimizer <= by_phase["update"]
    # forward and backward are the model's and the loss's
    for phase, marker in (("forward", "jvp("), ("backward", "transpose(")):
        assert by_phase[phase]
        assert all(marker in names[n] for n in by_phase[phase]
                   if n in names and n not in borrowed
                   and "while" not in names[n])
    assert not by_phase["update"] & by_phase["exchange"]


@pytest.mark.parametrize("workload", CELLS)
def test_what_has_no_name_is_the_compilers_own_and_is_read_as_its_neighbours(
        workload, steps):
    _, text, instructions, _, borrowed = steps(workload)
    names = op_names(text)
    # with a path of its own nothing borrows
    assert not [n for n in borrowed if "/" in names.get(n, "")]
    strangers = [n for n in borrowed if not COMPILER_MADE.match(n)]
    assert not strangers, strangers[:20]
    # an argument is read as the first instruction that takes it
    assert [n for n in borrowed if n.startswith("param")]
    unnamed = [n for n, (p, _) in instructions.items() if p == "unnamed"]
    assert all(COMPILER_MADE.match(n) for n in unnamed), unnamed[:20]
    assert len(unnamed) < 0.02 * len(instructions), unnamed[:20]


@pytest.mark.parametrize("workload", CELLS)
def test_the_copies_of_a_block_are_one_scope(workload, steps):
    _, _, instructions, _, _ = steps(workload)
    scopes = {s for _, s in instructions.values()}
    assert not [s for s in scopes if re.search(r"\d", s)]
    assert {"hvd/update", "hvd/exchange"} <= scopes
    assert any(s.split("/")[-1] in ("loss", "log_softmax", "exit_loss")
               or s.startswith("loss") for s in scopes)
