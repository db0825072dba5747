"""The two readers that go by the scope of a router that decides ahead
of its block's mixer (``layer_metrics/route_ahead_time_share.py``,
``routed_ffn_time_share.py``): on hand-built planes and a hand-written
program text whose answers are known, on one step of
``smallthinker_21b_a3b-spmd-1chip`` recorded on the v5e in PR 53 with the
text of the program that ran it, and that a program which sets no such
scope (every other configuration's, and the parent's) and an untraced
run leave each metric out."""

import gzip
import os

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json
from test_benchmark_window_metrics import (AGAIN, BACK, HEAD, fake_run, read,
                                           reduce, run_of)

# (instruction, op_name, a kernel?, ms a step): the router's product on
# the block's input, its ``top_k``, the product again in the
# recomputation, the router's gradient; attention between decision and
# use; the sort, a grouped product, its recomputation and its gradient
# under ``moe``; the optimizer
STEP = [
    ("fusion.1", HEAD + "block_1/route_ahead/moe.route/dot_general", False,
     2),
    ("sort.1", HEAD + "block_1/route_ahead/moe.route/top_k", False, 1),
    ("_fwd.6", HEAD + "block_1/attn/attn/window/flash/jit(_fwd)/pallas_call",
     True, 10),
    ("sort.2", HEAD + "block_1/moe/moe/dispatch/jit(argsort)/sort", False,
     3),
    ("fusion.2", HEAD + "block_1/moe/moe/experts/ragged_dot", False, 6),
    ("fusion.3", AGAIN + "block_1/route_ahead/moe.route/dot_general", False,
     2),
    ("fusion.4", AGAIN + "block_1/moe/moe/experts/ragged_dot", False, 4),
    ("fusion.5", BACK + "block_1/moe/moe/experts/ragged_dot", False, 12),
    ("fusion.6", BACK + "block_1/route_ahead/moe.route/dot_general", False,
     4),
    ("fusion.7", "jit(per_shard)/hvd/update/mul", False, 6),
]


def test_shares_by_scope_forward_recomputation_and_backward(tmp_path):
    run = fake_run(STEP, tmp_path)
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(50.0)
    # directly under the block, whatever method of the module made it
    assert {scope for scope, _ in chip.both_ms} >= {
        "block/route_ahead", "block/moe/dispatch", "block/moe/experts"}
    assert read("route_ahead_time_share", run) == pytest.approx(
        100 * (2 + 1 + 2 + 4) / 50)
    assert read("routed_ffn_time_share", run) == pytest.approx(
        100 * (2 + 1 + 2 + 4 + 3 + 6 + 4 + 12) / 50)


def behind(step):
    """The same step of a router that reads what its experts read: the
    decision under ``moe/route``, inside the expert layer."""
    return [(name, op.replace("route_ahead/moe.route", "moe/moe/route"),
             kernel, ms) for name, op, kernel, ms in step]


@pytest.mark.parametrize("metric, program, found", [
    # no ``route_ahead`` scope: no decision ahead to report, and the
    # expert path is read by its scope all the same
    ("route_ahead_time_share", behind(STEP), None),
    ("routed_ffn_time_share", behind(STEP),
     100 * (2 + 1 + 2 + 4 + 3 + 6 + 4 + 12) / 50),
    # no expert layer at all
    ("route_ahead_time_share", [STEP[2], STEP[-1]], None),
    ("routed_ffn_time_share", [STEP[2], STEP[-1]], None),
])
def test_a_program_without_the_scope_leaves_the_metric_out(
        metric, program, found, tmp_path):
    """A router that reads what its experts read decides under
    ``moe/route``: ``route_ahead_time_share`` is not reported, and the
    whole expert path is the same sum by scope; a program with no expert
    layer reports neither; nor does an untraced run."""
    assert read(metric, fake_run(program, tmp_path)) == (
        found if found is None else pytest.approx(found))
    untraced = fake_run(STEP, tmp_path)
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None


# ------------------------------------------------- the recorded trace
# One step of smallthinker_21b_a3b-spmd-1chip on the v5e (PR 53, seed
# 2153002011, the embedding drawn at unit variance; the first of the
# five traced after the window), cut by
# cut_trace.py, and the text of the step that ran it, its kernels'
# serialized bodies (``backend_config=...``) cut off.
RECORDED = os.path.join(HERE, "fixtures",
                        "smallthinker_21b_a3b-spmd-1chip.pr53.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    family = load_by_path(os.path.join(BENCH, "models", "smallthinker_lm.py"),
                          "hvd_benchmark_ra_smallthinker_lm")
    config = load_json(os.path.join(REPO, "benchmark", "configs",
                                    "smallthinker_21b_a3b.json"))
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"),
        flops=family.window_flash_flops_per_step(config, config["job"]))
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the readers that go by shape ask the family and the cell's files
    run.cell.family, run.cell.config, run.cell.job = (
        family, config, config["job"])
    return run


def test_recorded_step_by_the_decision_and_the_expert_path(recorded):
    """431.0 ms busy, the step after the window's 50 at the job's rate:
    the decision 4.5 ms (0.88 forward, 0.50 again, 3.14 backward), the
    expert path behind the mixer 109.0 (``experts`` 58.0, ``combine``
    28.7, ``dispatch`` 22.2); attention's two kinds 120.3 and 61.8
    beside them."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(431.0, abs=0.5)
    assert read("route_ahead_time_share", recorded) == pytest.approx(
        1.05, abs=0.02)
    assert read("routed_ffn_time_share", recorded) == pytest.approx(
        26.3, abs=0.2)
    assert read("window_attn_time_share", recorded) == pytest.approx(
        27.9, abs=0.2)
    assert read("global_attn_time_share", recorded) == pytest.approx(
        14.3, abs=0.2)
    assert read("window_flash_roofline", recorded) == pytest.approx(
        53.2, abs=0.3)
    ahead = {phase: ms for (scope, phase), ms in chip.both_ms.items()
             if scope == "block/route_ahead"}
    # made forward, again in the recomputation (the product and the
    # softmax: half the forward's, ``top_k`` is not made again) and its
    # gradient
    assert set(ahead) == {"forward", "recompute", "backward"}
    assert ahead["recompute"] < ahead["forward"] < ahead["backward"]
    assert read("route_ahead_time_share", recorded) == pytest.approx(
        100 * sum(ahead.values()) / chip.busy_ms)
    moe = sum(ms for (scope, _), ms in chip.both_ms.items()
              if scope.startswith("block/moe"))
    assert read("routed_ffn_time_share", recorded) == pytest.approx(
        100 * (sum(ahead.values()) + moe) / chip.busy_ms)
    assert not [scope for scope, _ in chip.both_ms if "moe/route" in scope]
    # the global layer rotates nothing
    scopes = {scope for scope, _ in chip.both_ms}
    assert "block/attn/window/rope" in scopes
    assert "block/attn/global/rope" not in scopes


def test_recorded_flash_calls_take_k_and_v_with_four_heads(recorded):
    """The eight flash custom calls of the step that ran: q
    ``[28,16384,128]``, k and v ``[4,16384,128]`` in every one, none in
    the recomputation."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/flash/" in line]
    assert len(calls) == 8
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert operands.startswith(
            "bf16[28,16384,128]{2,1,0}, bf16[4,16384,128]{2,1,0}, "
            "bf16[4,16384,128]{2,1,0}")
        assert "rematted_computation" not in line


def test_recorded_step_by_the_shapes_the_family_names(recorded):
    """The two accepted readers that go by shape find the cell's
    instructions through ``trace_shapes`` and the cell's files, unedited:
    the expert layers by the token-slots ``[98304``, the router's
    ``[16384,64]`` and the grouped products' name (a little under the
    sum by scope: the layout copies around the sort carry neither), the
    loss's two kernels by ``[16384,37984]``."""
    held = read("moe_held_time_share", recorded)
    assert held == pytest.approx(25.0, abs=0.2)
    assert held < read("routed_ffn_time_share", recorded)
    assert read("softmax_xent_roofline", recorded) == pytest.approx(
        85.1, abs=0.3)
