"""The four readers that go by the scopes a chunk-summary attention layer
sets (``layer_metrics/eva_attn_time_share.py``, ``eva_pool_time_share.py``,
``eva_flash_roofline.py``, ``eva_pool_roofline.py``): on hand-built
planes and a hand-written program text whose answers are known, on one
step of ``evabyte_6_5b-spmd-1chip`` recorded on the v5e in PR 49 with the
text of the program that ran it, and that a program which sets no such
scope (the parent of the PR that brought them) leaves each metric out."""

import gzip
import os

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json
from test_benchmark_window_metrics import (AGAIN, BACK, HEAD, fake_run, read,
                                           reduce, run_of)

METRICS = ["eva_attn_time_share", "eva_pool_time_share",
           "eva_flash_roofline", "eva_pool_roofline"]
EVA = "block_0/attn/attn/eva/"
# (instruction, op_name, a kernel?, ms a step): the projections, the
# rotation, the pooling forward and backward, the two kernel calls forward
# and backward with the layout copy beside one and the join after them,
# a recomputed projection, the output projection; the feed-forward; the
# loss kernel; the optimizer
STEP = [
    ("fusion.1", HEAD + EVA + "qkv/q/dot_general", False, 6),
    ("fusion.2", HEAD + EVA + "rope/mul", False, 1),
    ("fusion.3", HEAD + EVA + "pool/reduce_sum", False, 2),
    ("_fwd.1", HEAD + EVA + "flash/local/jit(_fwd)/pallas_call", True, 5),
    ("copy.1", HEAD + EVA + "flash/local/transpose", False, 1),
    ("_fwd.2", HEAD + EVA + "flash/remote/jit(_fwd)/pallas_call", True, 3),
    ("fusion.4", HEAD + EVA + "flash/join/mul", False, 2),
    ("fusion.5", HEAD + EVA + "out/out/dot_general", False, 3),
    ("fusion.6", AGAIN + EVA + "qkv/k/dot_general", False, 6),
    ("_bwd.1", BACK + EVA + "flash/local/jit(_bwd)/pallas_call", True, 8),
    ("_bwd.2", BACK + EVA + "flash/remote/jit(_bwd)/pallas_call", True, 4),
    ("fusion.7", BACK + EVA + "pool/mul", False, 6),
    ("fusion.8", HEAD + "block_0/mlp/up/dot_general", False, 20),
    ("jvp_loss_.1", "jit(per_shard)/jvp(loss)/pallas_call", True, 1),
    ("fusion.9", "jit(per_shard)/hvd/update/mul", False, 12),
]
BUSY = sum(entry[3] for entry in STEP)
FLOPS, BYTES = 6e9, 4_000_000


def with_counts(run):
    run.cell.family.eva_flash_flops_per_step = lambda config, job: FLOPS
    run.cell.family.eva_pool_bytes_per_step = lambda config, job: BYTES
    run.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    return run


def test_shares_and_rooflines_by_scope(tmp_path):
    run = with_counts(fake_run(STEP, tmp_path))
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(BUSY) and BUSY == 80
    # everything under attn/eva in the three phases, not the feed-forward,
    # the loss kernel or the optimizer
    assert read("eva_attn_time_share", run) == pytest.approx(
        100 * (6 + 1 + 2 + 5 + 1 + 3 + 2 + 3 + 6 + 8 + 4 + 6) / BUSY)
    assert read("eva_pool_time_share", run) == pytest.approx(
        100 * (2 + 6) / BUSY)
    # 20 ms of kernels a step under attn/eva/flash: not the layout copy
    # nor the join in the same scope, not the loss kernel; at 1e12 a
    # second, 6e9 operations a step are 30% of what 20 ms could do
    assert read("eva_flash_roofline", run) == pytest.approx(
        100 * FLOPS / (20e-3 * 1e12))
    # 4 MB a step in 8 ms of pooling at 1 GB/s: half of what HBM allows
    assert read("eva_pool_roofline", run) == pytest.approx(
        100 * BYTES / (8e-3 * 1e9))


def test_the_rooflines_need_the_familys_counts_and_the_peaks(tmp_path):
    run = fake_run(STEP, tmp_path)        # a family with no such counts
    assert read("eva_flash_roofline", run) is None
    assert read("eva_pool_roofline", run) is None
    assert read("eva_attn_time_share", run) is not None
    run = with_counts(fake_run(STEP, tmp_path))
    run.peaks = None
    assert read("eva_flash_roofline", run) is None
    assert read("eva_pool_roofline", run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_scopes_leaves_the_metric_out(metric,
                                                            tmp_path):
    """The parent's model has no such scope: every attention of every
    older cell runs under ``attn/window``, ``attn/global``, ``attn/cross``
    or ``attn/latent``; an untraced run too."""
    parent = [(name, op.replace("attn/attn/eva", "attn/attn/global"), kernel,
               ms) for name, op, kernel, ms in STEP]
    assert read(metric, with_counts(fake_run(parent, tmp_path))) is None
    untraced = with_counts(fake_run(STEP, tmp_path))
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None


def test_the_manifest_lists_the_four_for_the_cell_alone():
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    for name in METRICS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == ["evabyte_6_5b-spmd-1chip"]
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert entry["unit"] == "%" and entry["source"] == "device_trace"


# ------------------------------------------------- the recorded trace
# One step of evabyte_6_5b-spmd-1chip on the v5e (PR 49, seed 4900000001),
# cut by cut_trace.py, and the text of the step that ran it, its kernels'
# serialized bodies (``backend_config=...``) cut off.
RECORDED = os.path.join(HERE, "fixtures", "evabyte_6_5b-spmd-1chip.pr49.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"))
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run.cell.family = load_by_path(
        os.path.join(BENCH, "models", "evabyte_lm.py"),
        "hvd_benchmark_c_evabyte_lm")
    run.cell.config = load_json(os.path.join(
        REPO, "benchmark", "configs", "evabyte_6_5b.json"))
    run.cell.job = {**run.cell.config["job"], "seq_len": 16384}
    return run


def test_recorded_step_by_scope(recorded):
    """997.6 ms busy: the feed-forward 533.5, the attention layers 415.7
    (the three projections 198 with their recomputation and their weight
    gradients, the kernels' scope 123.5: local 70.1, remote 36.1, the join
    17.3; the output projection 51.7, the rotation 28.0, the pooling
    14.6); nothing unnamed; no kernel in the recomputation."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(997.6, abs=1.0)
    assert read("eva_attn_time_share", recorded) == pytest.approx(
        41.66, abs=0.2)
    assert read("eva_pool_time_share", recorded) == pytest.approx(
        1.46, abs=0.1)
    assert chip.phase_ms["unnamed"] == 0

    def under(path):
        return {phase: sum(ms for (scope, p), ms in chip.both_ms.items()
                           if p == phase and f"/{path}/" in f"/{scope}/")
                for phase in ("forward", "recompute", "backward")}

    assert sum(under("mlp").values()) == pytest.approx(533.5, abs=1.0)
    assert sum(under("attn/eva/flash/local").values()) == pytest.approx(
        70.1, abs=0.5)
    assert sum(under("attn/eva/flash/remote").values()) == pytest.approx(
        36.1, abs=0.5)
    assert sum(under("attn/eva/flash/join").values()) == pytest.approx(
        17.3, abs=0.5)
    assert under("attn/eva/pool") == pytest.approx(
        {"forward": 4.8, "recompute": 0.2, "backward": 9.6}, abs=0.3)
    # the recomputation makes q, k, v again (they are not kept) and not
    # the output projection (the sum after attention is)
    assert under("attn/eva/qkv")["recompute"] == pytest.approx(44.6, abs=0.5)
    assert under("attn/eva/out")["recompute"] < 1.0


def test_recorded_kernels_against_their_operations(recorded):
    """The sixteen flash custom calls of the step that ran, four a layer:
    the local part on ``[256, 2048, 128]``, the remote on q ``[32, 16384,
    128]`` over ``[32, 1024, 128]``; 4.74e12 operations in their self
    time are 39% of the bf16 peak, and the pooling moves its 3.36 GB at
    28% of what HBM allows: both under 100."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/attn/eva/flash/" in line]
    assert len(calls) == 16
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert operands.startswith(
            "bf16[256,2048,128]{2,1,0}, bf16[256,2048,128]{2,1,0}"
            if "/flash/local/" in line else
            "bf16[32,16384,128]{2,1,0}, bf16[32,1024,128]{2,1,0}")
        assert "rematted_computation" not in line
    flash = read("eva_flash_roofline", recorded)
    pool = read("eva_pool_roofline", recorded)
    assert flash == pytest.approx(38.96, abs=0.5) and 0 < flash < 100
    assert pool == pytest.approx(28.1, abs=0.5) and 0 < pool < 100
    kernels_ms = 4.742e12 / 197e12 / (flash / 100) * 1e3
    assert kernels_ms == pytest.approx(61.8, abs=1.0)
