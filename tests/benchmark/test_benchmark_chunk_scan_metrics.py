"""The two readers that came with the cell
``nemotron3_nano_30b_a3b-spmd-1chip``
(``layer_metrics/ssm_scan_intra_share.py``,
``shared_expert_time_share.py``): on hand-built planes and a
hand-written program text whose answers are known, on one step of the
cell recorded on the v5e in PR 55 with the text of the program that ran
it, where the accepted readers whose lists the cell was appended to find
it unedited, and that a program which sets no such scope (the Mamba-1
cell's, whose scan is one kernel, and a layer without a shared expert)
and an untraced run leave each metric out."""

import gzip
import os

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json
from test_benchmark_window_metrics import (AGAIN, BACK, HEAD, fake_run, read,
                                           reduce, run_of)

CELL = "nemotron3_nano_30b_a3b-spmd-1chip"
SCAN = "block_0/mixer/mixer/ssm/scan/checkpoint/"
# (instruction, op_name, a kernel?, ms a step): a Mamba-2 layer's first
# product, the masks and the product within a chunk, the chunk states and
# the pass across chunks, the same again in the recomputation (the scan's
# own checkpoint inside the block's), their gradients; an expert layer
# with no mixer: a grouped product and the shared expert; the optimizer
STEP = [
    ("fusion.1", HEAD + "block_0/mixer/mixer/ssm/in/dot_general", False, 5),
    ("fusion.2", HEAD + SCAN + "intra/bcgkij,bcjgkp->bcigkp/dot_general",
     False, 2),
    ("fusion.3", HEAD + SCAN + "inter/bhck,bkhs->bchs/dot_general", False,
     4),
    ("fusion.4", BACK + SCAN + "rematted_computation/intra/exp", False, 1),
    ("fusion.5", BACK + SCAN + "rematted_computation/inter/dot_general",
     False, 3),
    ("fusion.6", BACK + SCAN + "intra/bcgkij,bcjgkp->bcigkp/dot_general",
     False, 6),
    ("fusion.7", BACK + SCAN + "inter/bcign,bcgkpn->bcigkp/dot_general",
     False, 7),
    ("fusion.8", HEAD + "block_1/moe/moe/experts/ragged_dot", False, 8),
    ("fusion.9", HEAD + "block_1/moe/moe/shared/shared/up/dot_general",
     False, 3),
    ("fusion.10", AGAIN + "block_1/moe/moe/experts/ragged_dot", False, 2),
    ("fusion.11", BACK + "block_1/moe/moe/shared/shared/down/dot_general",
     False, 5),
    ("fusion.12", "jit(per_shard)/hvd/update/mul", False, 4),
]


def test_shares_by_scope_forward_recomputation_and_backward(tmp_path):
    run = fake_run(STEP, tmp_path)
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(50.0)
    assert {scope for scope, _ in chip.both_ms} >= {
        "block/mixer/ssm/scan/intra", "block/mixer/ssm/scan/inter",
        "block/moe/shared/up", "block/moe/shared/down"}
    # the scan's own recomputation inside the backward pass reads as
    # recompute, under the same scope
    assert chip.both_ms["block/mixer/ssm/scan/intra", "recompute"] == (
        pytest.approx(1.0))
    assert read("ssm_scan_intra_share", run) == pytest.approx(
        100 * (2 + 1 + 6) / 50)
    assert read("ssm_scan_time_share", run) == pytest.approx(
        100 * (2 + 4 + 1 + 3 + 6 + 7) / 50)
    assert read("ssm_mixer_time_share", run) == pytest.approx(
        100 * (5 + 2 + 4 + 1 + 3 + 6 + 7) / 50)
    assert read("shared_expert_time_share", run) == pytest.approx(
        100 * (3 + 5) / 50)
    assert read("routed_ffn_time_share", run) == pytest.approx(
        100 * (8 + 3 + 2 + 5) / 50)


def one_kernel(step):
    """The same step with the scan as one kernel under ``scan`` (the
    Mamba-1 mixer's) and no shared expert."""
    return [(name, op.replace("scan/checkpoint/intra", "scan").replace(
        "scan/checkpoint/rematted_computation/intra", "scan"), kernel, ms)
        for name, op, kernel, ms in step if "/shared/" not in op]


@pytest.mark.parametrize("metric", ["ssm_scan_intra_share",
                                    "shared_expert_time_share"])
def test_a_program_without_the_scope_leaves_the_metric_out(metric, tmp_path):
    run = fake_run(one_kernel(STEP), tmp_path)
    assert read(metric, run) is None
    # the scan as a whole is read all the same
    assert read("ssm_scan_time_share", run) == pytest.approx(
        100 * (2 + 4 + 1 + 3 + 6 + 7) / 42)
    untraced = fake_run(STEP, tmp_path)
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None


# ------------------------------------------------- the recorded trace
# One step of nemotron3_nano_30b_a3b-spmd-1chip on the v5e (PR 55, seed
# 2555000317, from the committed files alone; the first of the five
# traced after the window), cut by
# cut_trace.py, and the text of the step that ran it: every
# ``backend_config=...`` (the kernels' serialized bodies, the fusions'
# window configurations), the tilings and the bodies of the fused
# computations cut off; the entry's and the loops' instructions with
# their metadata, which the readers go by, are whole.
RECORDED = os.path.join(HERE, "fixtures", CELL + ".pr55.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    family = load_by_path(os.path.join(BENCH, "models", "nemotron_h_lm.py"),
                          "hvd_benchmark_cs_nemotron_h_lm")
    config = load_json(os.path.join(REPO, "benchmark", "configs",
                                    "nemotron3_nano_30b_a3b.json"))
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"))
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run.cell.family, run.cell.config, run.cell.job = (
        family, config, config["job"])
    return run


def test_the_fixtures_are_small():
    assert sum(os.path.getsize(RECORDED + name) for name in (
        "1step.xplane.pb.gz", "step.hlo.txt.gz")) < 300 * 1024


def test_recorded_step_by_the_scan_and_the_shared_expert(recorded):
    """614.2 ms busy.  The four Mamba-2 mixers 302.0 ms, of it the scan
    125.0: within a chunk 26.6 (5.5 forward, 3.9 again, 17.1 backward),
    across chunks 61.5, and 36.9 directly under ``scan``; the expert
    path 199.3, of it the shared expert 56.6 (forward and backward, none
    again: its ``up`` is kept)."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(614.2, abs=0.5)
    assert read("ssm_scan_intra_share", recorded) == pytest.approx(
        4.33, abs=0.03)
    assert read("shared_expert_time_share", recorded) == pytest.approx(
        9.22, abs=0.05)
    intra = {phase: ms for (scope, phase), ms in chip.both_ms.items()
             if scope == "block/mixer/ssm/scan/intra"}
    assert set(intra) == {"forward", "recompute", "backward"}
    assert intra["recompute"] < intra["forward"] < intra["backward"]
    assert read("ssm_scan_intra_share", recorded) == pytest.approx(
        100 * sum(intra.values()) / chip.busy_ms)
    shared = {phase for (scope, phase), ms in chip.both_ms.items()
              if scope.startswith("block/moe/shared") and ms > 0}
    assert shared == {"forward", "backward"}
    # every instruction of the scan under the mixer's scope, the
    # backward pass too: none lost to a scope of its own
    scopes = {scope for scope, _ in chip.both_ms}
    assert not [scope for scope in scopes
                if scope.startswith(("intra", "inter", "scan"))]
    assert {"block/mixer/ssm/in", "block/mixer/ssm/conv",
            "block/mixer/ssm/proj", "block/mixer/ssm/gate_out",
            "block/mixer/ssm/scan/intra",
            "block/mixer/ssm/scan/inter"} <= scopes | {
                scope.rsplit("/", 1)[0] for scope in scopes}


def test_recorded_step_by_the_readers_the_cell_was_appended_to(recorded):
    """The accepted readers, unedited, on the new cell's step: the
    Mamba-1 cell's three by the same scope names, the attention layer's
    by ``attn/global``, the expert path by ``block/moe``, the phases."""
    assert read("ssm_mixer_time_share", recorded) == pytest.approx(
        49.18, abs=0.1)
    assert read("ssm_scan_time_share", recorded) == pytest.approx(
        20.35, abs=0.1)
    # 3,539,992,576 required bytes over 125.0 ms at 819 GB/s
    assert read("ssm_scan_roofline", recorded) == pytest.approx(
        100 * 3_539_992_576 / (0.12497 * 819e9), rel=2e-3)
    assert read("ssm_scan_roofline", recorded) == pytest.approx(3.46,
                                                                abs=0.02)
    assert read("global_attn_time_share", recorded) == pytest.approx(
        7.28, abs=0.05)
    assert read("routed_ffn_time_share", recorded) == pytest.approx(
        32.44, abs=0.1)
    assert read("window_attn_time_share", recorded) is None
    assert read("route_ahead_time_share", recorded) is None
    assert read("step_forward_ms", recorded) == pytest.approx(177.5, abs=0.3)
    assert read("step_recompute_ms", recorded) == pytest.approx(65.4,
                                                                abs=0.3)
    assert read("step_backward_ms", recorded) == pytest.approx(345.1,
                                                               abs=0.3)
    assert read("step_update_ms", recorded) == pytest.approx(26.2, abs=0.2)
    assert read("step_unnamed_share", recorded) == 0.0


def test_recorded_step_by_the_shapes_the_family_names(recorded):
    """The readers that go by shape find the cell's instructions through
    ``trace_shapes`` and the cell's files, unedited: the flash calls by q
    ``[64,8192,128]`` (58.3% of what 3.30e12 operations a step take at
    the peak), the loss's two kernels by ``[16384,16384]``; every share
    of a roofline under 100%."""
    assert read("flash_roofline", recorded) == pytest.approx(58.3, abs=0.3)
    assert read("softmax_xent_roofline", recorded) == pytest.approx(
        82.4, abs=0.3)
    for metric in ("flash_roofline", "softmax_xent_roofline",
                   "ssm_scan_roofline"):
        assert 0 < read(metric, recorded) < 100


def test_recorded_flash_calls_take_k_and_v_with_two_heads_a_sequence(
        recorded):
    """The two flash custom calls of the step that ran: q
    ``[64,8192,128]``, k and v ``[4,8192,128]``, none in the
    recomputation; and no loop under the mixers."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/flash/" in line]
    assert len(calls) == 2
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert operands.startswith(
            "bf16[64,8192,128]{2,1,0}, bf16[4,8192,128]{2,1,0}, "
            "bf16[4,8192,128]{2,1,0}")
        assert "rematted_computation" not in line
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 20 and not [
        line for line in loops if "/mixer/" in line]


def test_the_manifest_lists_the_cell_where_its_readers_find_something():
    """Seventeen accepted readers gained the cell and two came with it;
    every one of them reads a number off the recorded step or off the
    run (the four that need the run itself are the harness's)."""
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = [m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())]
    assert listed == [
        "device_idle_share", "peak_mem_GiB", "step_ms_p50", "mfu_required",
        "pallas_time_share", "softmax_xent_roofline", "flash_roofline",
        "step_forward_ms", "step_backward_ms", "step_update_ms",
        "step_recompute_ms", "step_unnamed_share", "global_attn_time_share",
        "ssm_mixer_time_share", "ssm_scan_time_share", "ssm_scan_roofline",
        "routed_ffn_time_share", "ssm_scan_intra_share",
        "shared_expert_time_share"]
    new = [m for m in manifest["per_layer"]
           if m["name"] in listed[-2:]]
    assert [m["workloads"] for m in new] == [[CELL]] * 2
    assert all(m["layer"] == "models" and m["moves"] ==
               "tokens_per_s_per_chip" for m in new)
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] ==
                        "tokens_per_s_per_chip")["workloads"]
