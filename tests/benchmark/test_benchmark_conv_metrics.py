"""The two readers that go by the scopes of the mixer that is no
attention (``layer_metrics/conv_mixer_time_share.py``,
``conv_gate_time_share.py``): on hand-built planes and a hand-written
program text whose answers are known, on one step of
``lfm2_24b_a2b-spmd-1chip`` recorded on the v5e in PR 41 with the text of
the program that ran it, and that a program which sets no such scope
(the parent of the PR that brought them) leaves each metric out."""

import gzip
import os

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json
from test_benchmark_window_metrics import (AGAIN, BACK, HEAD, fake_run, read,
                                           reduce, run_of)

# (instruction, op_name, a kernel?, ms a step): a conv mixer's two
# products, its gates and taps, forward, recomputed and backward; the
# attention layer's kernel and its head norm; the feed-forward; the
# optimizer
STEP = [
    ("fusion.1", HEAD + "block_0/mixer/mixer/conv/in/dot_general", False, 6),
    ("fusion.2", HEAD + "block_0/mixer/mixer/conv/gate_conv/mul", False, 1),
    ("fusion.3", HEAD + "block_0/mixer/mixer/conv/out/dot_general", False, 2),
    ("fusion.4", AGAIN + "block_2/mixer/mixer/conv/in/dot_general", False, 6),
    ("fusion.5", AGAIN + "block_2/mixer/mixer/conv/gate_conv/add", False, 1),
    ("fusion.6", BACK + "block_2/mixer/mixer/conv/gate_conv/mul", False, 3),
    ("fusion.7", BACK + "block_2/mixer/mixer/conv/out/dot_general", False, 4),
    ("_fwd.1", HEAD + "block_1/attn/attn/global/flash/jit(_fwd)/pallas_call",
     True, 9),
    ("fusion.8", HEAD + "block_1/attn/attn/global/qk_norm/q_norm/mul", False,
     1),
    ("fusion.9", HEAD + "block_0/mlp/up/dot_general", False, 12),
    ("fusion.10", "jit(per_shard)/hvd/update/mul", False, 5),
]


def test_shares_by_scope_forward_recomputation_and_backward(tmp_path):
    run = fake_run(STEP, tmp_path)
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(50.0)
    # everything under mixer/conv, in the three phases; not the attention
    # layer, not the feed-forward
    assert read("conv_mixer_time_share", run) == pytest.approx(
        100 * (6 + 1 + 2 + 6 + 1 + 3 + 4) / 50)
    # the gates and the taps alone
    assert read("conv_gate_time_share", run) == pytest.approx(
        100 * (1 + 1 + 3) / 50)
    # the head norm is the attention layer's
    assert read("global_attn_time_share", run) == pytest.approx(
        100 * (9 + 1) / 50)


@pytest.mark.parametrize("metric", ["conv_mixer_time_share",
                                    "conv_gate_time_share"])
def test_a_program_without_the_scopes_leaves_the_metric_out(metric,
                                                            tmp_path):
    """The parent's model has no ``mixer/conv``: every layer of every
    older cell is attention; an untraced run too."""
    parent = [(name, op.replace("mixer/mixer/conv", "attn/attn/global"),
               kernel, ms) for name, op, kernel, ms in STEP]
    assert read(metric, fake_run(parent, tmp_path)) is None
    untraced = fake_run(STEP, tmp_path)
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None
    # a mixer whose gates were all fused into its products: the mixer's
    # share is read, the gates' left out
    fused = [entry for entry in STEP if "gate_conv" not in entry[1]]
    assert read("conv_mixer_time_share", fake_run(fused, tmp_path)) > 0
    assert read("conv_gate_time_share", fake_run(fused, tmp_path)) is None


# ------------------------------------------------- the recorded trace
# One step of lfm2_24b_a2b-spmd-1chip on the v5e (PR 41), cut by
# cut_trace.py, and the text of the step that ran it, its kernels'
# serialized bodies (``backend_config=...``) cut off.
RECORDED = os.path.join(HERE, "fixtures", "lfm2_24b_a2b-spmd-1chip.pr41.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"))
    run.peaks = {"bf16_flops_per_s": 197e12}
    run.cell.family = load_by_path(
        os.path.join(BENCH, "models", "lfm2_lm.py"),
        "hvd_benchmark_c_lfm2_lm")
    run.cell.config = load_json(os.path.join(
        REPO, "benchmark", "configs", "lfm2_24b_a2b.json"))
    run.cell.job = run.cell.config["job"]
    return run


def test_recorded_step_by_the_kinds_of_mixer(recorded):
    """272.3 ms busy: the four conv mixers 60.6 ms (``in`` 40.9 with its
    recomputation, ``out`` 10.1, the gates and taps 9.5), the one
    attention layer 41.0 (the two flash calls 30.5 of it, the head norm
    0.3)."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(272.3, abs=0.5)
    assert read("conv_mixer_time_share", recorded) == pytest.approx(
        22.3, abs=0.2)
    assert read("conv_gate_time_share", recorded) == pytest.approx(
        3.5, abs=0.1)
    assert read("global_attn_time_share", recorded) == pytest.approx(
        15.1, abs=0.2)
    assert read("window_attn_time_share", recorded) is None

    def under(path):
        return {phase: sum(ms for (scope, p), ms in chip.both_ms.items()
                           if p == phase and f"/{path}/" in f"/{scope}/")
                for phase in ("forward", "recompute", "backward")}

    # a recomputed conv block makes ``in`` and the gates again, not ``out``
    assert under("mixer/conv/in")["recompute"] == pytest.approx(8.7, abs=0.3)
    assert under("mixer/conv/gate_conv") == pytest.approx(
        {"forward": 1.2, "recompute": 1.2, "backward": 7.1}, abs=0.2)
    assert under("mixer/conv/out")["recompute"] == 0
    assert 0 < sum(under("attn/global/qk_norm").values()) < 1
    # no kernel in the recomputation: the flash output and lse are saved
    assert under("attn/global/flash")["recompute"] < 1
    assert chip.phase_ms["unnamed"] == 0


def test_recorded_flash_calls_at_grouped_heads_of_64(recorded):
    """The two flash custom calls of the step that ran: q ``[64,8192,
    64]`` over k and v ``[16,8192,64]``; 28.4 ms for 1.649e12 operations
    is 29.46% of the bf16 peak, what the cell's five traced steps
    read."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/flash/" in line]
    assert len(calls) == 2
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert operands.startswith(
            "bf16[64,8192,64]{2,1,0}, bf16[16,8192,64]{2,1,0}, "
            "bf16[16,8192,64]{2,1,0}")
    assert read("flash_roofline", recorded) == pytest.approx(29.5, abs=0.3)
