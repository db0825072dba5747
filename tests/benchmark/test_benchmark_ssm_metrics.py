"""The five readers that go by the scopes a decoder-hybrid-decoder model
sets (``layer_metrics/ssm_mixer_time_share.py``,
``ssm_scan_time_share.py``, ``gmu_time_share.py``,
``cross_attn_time_share.py``, ``ssm_scan_roofline.py``): on hand-built
planes and a hand-written program text whose answers are known, on one
step of ``phi4_mini_flash-spmd-1chip`` recorded on the v5e in PR 46 with
the text of the program that ran it, and that a program which sets no
such scope (the parent of the PR that brought them) leaves each metric
out."""

import gzip
import os

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json
from test_benchmark_window_metrics import (AGAIN, BACK, HEAD, fake_run, read,
                                           reduce, run_of)

METRICS = ["ssm_mixer_time_share", "ssm_scan_time_share", "gmu_time_share",
           "cross_attn_time_share", "ssm_scan_roofline"]
# (instruction, op_name, a kernel?, ms a step): a Mamba mixer's products
# and its scan, forward, recomputed and backward (the scan's loops read
# with their ``while``'s name ahead); a memory unit; a cross layer's
# kernel and its subtraction; a window layer's kernel; the feed-forward;
# the optimizer
STEP = [
    ("fusion.1", HEAD + "block_0/mixer/mixer/ssm/in/dot_general", False, 6),
    ("fusion.2", HEAD + "block_0/mixer/mixer/ssm/conv/mul", False, 1),
    ("fusion.3", HEAD + "block_0/mixer/mixer/ssm/proj/x/dot_general", False,
     1),
    ("fusion.4", HEAD + "block_0/mixer/mixer/ssm/scan/while/body/mul", False,
     4),
    ("fusion.5", HEAD + "block_0/mixer/mixer/ssm/gate_out/out/dot_general",
     False, 3),
    ("fusion.6", AGAIN + "block_0/mixer/mixer/ssm/in/dot_general", False, 6),
    ("fusion.7", BACK + "block_0/mixer/mixer/ssm/scan/while/body/mul", False,
     12),
    ("fusion.8", BACK + "block_0/mixer/mixer/ssm/scan/reduce_sum", False, 4),
    ("fusion.9", HEAD + "block_4/mixer/mixer/gmu/in/dot_general", False, 2),
    ("fusion.10", BACK + "block_4/mixer/mixer/gmu/out/dot_general", False, 3),
    ("_fwd.1", HEAD + "block_5/attn/attn/cross/flash/jit(_fwd)/pallas_call",
     True, 7),
    ("fusion.11", HEAD + "block_5/attn/attn/cross/diff/sub", False, 1),
    ("_fwd.2", HEAD + "block_1/attn/attn/window/flash/jit(_fwd)/pallas_call",
     True, 2),
    ("fusion.12", HEAD + "block_0/mlp/up/dot_general", False, 20),
    ("fusion.13", "jit(per_shard)/hvd/update/mul", False, 8),
]
BUSY = sum(entry[3] for entry in STEP)
# what the family says the scans must move a step, and the chip's peak
BYTES, PEAK = 4_000_000, 1e9


def with_bytes(run):
    run.cell.family.scan_bytes_per_step = lambda config, job: BYTES
    run.peaks = {"hbm_bytes_per_s": PEAK}
    return run


def test_shares_by_scope_forward_recomputation_and_backward(tmp_path):
    run = with_bytes(fake_run(STEP, tmp_path))
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(BUSY) and BUSY == 80
    # everything under mixer/ssm, in the three phases
    assert read("ssm_mixer_time_share", run) == pytest.approx(
        100 * (6 + 1 + 1 + 4 + 3 + 6 + 12 + 4) / BUSY)
    # the scan alone: the loops and the sums after them
    assert read("ssm_scan_time_share", run) == pytest.approx(
        100 * (4 + 12 + 4) / BUSY)
    assert read("gmu_time_share", run) == pytest.approx(100 * (2 + 3) / BUSY)
    assert read("cross_attn_time_share", run) == pytest.approx(
        100 * (7 + 1) / BUSY)
    # the window layer is its own metric's, not the cross layer's
    assert read("window_attn_time_share", run) == pytest.approx(
        100 * 2 / BUSY)
    # 4 MB a step in 20 ms of scan at 1 GB/s: 20% of what HBM allows
    assert read("ssm_scan_roofline", run) == pytest.approx(
        100 * BYTES / (20e-3 * PEAK))


def test_the_roofline_needs_the_familys_bytes_and_the_peaks(tmp_path):
    run = fake_run(STEP, tmp_path)        # a family with no such count
    assert read("ssm_scan_roofline", run) is None
    run = with_bytes(fake_run(STEP, tmp_path))
    run.peaks = None
    assert read("ssm_scan_roofline", run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_scopes_leaves_the_metric_out(metric,
                                                            tmp_path):
    """The parent's model has none of the scopes: every mixer of every
    older cell is attention or a short convolution; an untraced run
    too."""
    parent = [(name, op.replace("mixer/mixer/ssm", "mixer/mixer/conv")
               .replace("mixer/mixer/gmu", "mixer/mixer/conv")
               .replace("attn/attn/cross", "attn/attn/global"), kernel, ms)
              for name, op, kernel, ms in STEP]
    assert read(metric, with_bytes(fake_run(parent, tmp_path))) is None
    untraced = with_bytes(fake_run(STEP, tmp_path))
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None


# ------------------------------------------------- the recorded trace
# One step of phi4_mini_flash-spmd-1chip on the v5e (PR 46), cut by
# cut_trace.py, and the text of the step that ran it, its kernels'
# serialized bodies (``backend_config=...``) cut off.
RECORDED = os.path.join(HERE, "fixtures", "phi4_mini_flash-spmd-1chip.pr46.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"))
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run.cell.family = load_by_path(
        os.path.join(BENCH, "models", "sambay_lm.py"),
        "hvd_benchmark_c_sambay_lm")
    run.cell.config = load_json(os.path.join(
        REPO, "benchmark", "configs", "phi4_mini_flash.json"))
    run.cell.job = run.cell.config["job"]
    return run


def test_recorded_step_by_the_kinds_of_mixer(recorded):
    """796.6 ms busy: the two Mamba mixers 185.6 ms (the scans 102.9: 32.6
    forward, 70.4 backward, none recomputed; ``in`` 42.8 with its
    recomputation), the memory unit 20.4, cross attention 50.7, full
    attention 55.2, the sliding layer 30.2; the scans move their 2.69 GB
    at 3.2% of what HBM allows."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(796.6, abs=1.0)
    assert read("ssm_mixer_time_share", recorded) == pytest.approx(
        23.3, abs=0.2)
    assert read("ssm_scan_time_share", recorded) == pytest.approx(
        12.9, abs=0.2)
    assert read("gmu_time_share", recorded) == pytest.approx(2.56, abs=0.1)
    assert read("cross_attn_time_share", recorded) == pytest.approx(
        6.36, abs=0.1)
    assert read("global_attn_time_share", recorded) == pytest.approx(
        6.93, abs=0.1)
    assert read("window_attn_time_share", recorded) == pytest.approx(
        3.79, abs=0.1)
    assert read("ssm_scan_roofline", recorded) == pytest.approx(3.19, abs=0.1)
    assert 0 < read("ssm_scan_roofline", recorded) < 100

    def under(path):
        return {phase: sum(ms for (scope, p), ms in chip.both_ms.items()
                           if p == phase and f"/{path}/" in f"/{scope}/")
                for phase in ("forward", "recompute", "backward")}

    # the scan's output and entry states are kept: nothing of it again
    assert under("mixer/ssm/scan") == pytest.approx(
        {"forward": 32.6, "recompute": 0, "backward": 70.4}, abs=0.5)
    # a recomputed Mamba block makes ``in`` and ``proj`` again, not ``out``
    assert under("mixer/ssm/in")["recompute"] == pytest.approx(9.5, abs=0.3)
    assert under("mixer/ssm/gate_out")["recompute"] == 0
    # no kernel in the recomputation: the flash outputs and lse are saved
    for kind in ("window", "global", "cross"):
        assert under(f"attn/{kind}/flash")["recompute"] == 0
        assert 0 < sum(under(f"attn/{kind}/diff").values()) < 6
    assert chip.phase_ms["unnamed"] == 0


def test_recorded_flash_calls_at_64_and_128(recorded):
    """The twelve flash custom calls of the step that ran: q ``[40,8192,
    64]`` over k ``[20,8192,64]`` and values ``[20,8192,128]``, two
    forward and two backward a layer; 79.9 ms for 6.56e12 operations is
    41.05% of the bf16 peak, what the cell's five traced steps read."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/flash/" in line]
    assert len(calls) == 12
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert operands.startswith(
            "bf16[40,8192,64]{2,1,0}, bf16[20,8192,64]{2,1,0}, "
            "bf16[20,8192,128]{2,1,0}")
    assert sorted(kind for line in calls for kind in ("window", "global",
                                                      "cross")
                  if f"/attn/{kind}/flash/" in line) == (
        ["cross"] * 4 + ["global"] * 4 + ["window"] * 4)
    assert read("flash_roofline", recorded) == pytest.approx(41.05, abs=0.3)


def test_recorded_window_layers_kernels_against_their_operations(recorded):
    """The sliding layer's four calls (two forward at 1.86 ms, two
    backward at 3.4) take 10.53 ms of the step for 374,491,054,080
    operations, 1.90 ms at the bf16 peak: ``window_flash_roofline``
    18.0%, found by the accepted reader through the scope
    ``attn/window`` with ``flash`` inside it.  The scope holds 13.3 ms:
    the rest is what stands around the kernels, and is not theirs."""
    (chip,) = recorded.scope_trace
    assert sum(ms for (scope, _), ms in chip.both_ms.items()
               if scope.endswith("attn/window/flash")) == pytest.approx(
        13.3, abs=0.2)
    share = read("window_flash_roofline", recorded)
    assert share == pytest.approx(18.04, abs=0.2)
    kernels_ms = 374_491_054_080 / 197e12 / (share / 100) * 1e3
    assert kernels_ms == pytest.approx(10.53, abs=0.1)
