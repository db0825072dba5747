"""The three readers that go by the scopes of the two kinds of
attention layer (``layer_metrics/window_attn_time_share.py``,
``global_attn_time_share.py``, ``window_flash_roofline.py``): on
hand-built planes and a hand-written program text whose answers are
known, on one step of ``laguna_s_2_1-spmd-1chip`` recorded on the v5e in
PR 38 with the text of the program that ran it, and that a program
which sets no such scope (the parent of the PR that brought them) leaves
each metric out."""

import gzip
import os
import types

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

from horovod_tpu.utils import trace

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce_w")
scope_trace = load_by_path(os.path.join(BENCH, "scope_trace.py"),
                           "hvd_benchmark_scope_trace_w")
MS = 1_000_000  # ns
KERNEL = ', custom_call_target="tpu_custom_call"'
HEAD = "jit(per_shard)/jvp(Transformer)/"
BACK = ("jit(per_shard)/transpose(jvp(Transformer))/jvp(Transformer)/"
        "checkpoint/")
AGAIN = BACK + "rematted_computation/"

# (instruction, op_name, a kernel?, ms a step): a sliding layer's q
# projection, its forward kernel, the layout copy around it, its
# backward kernel and its recomputed projection; a full layer's forward
# and backward kernels and its output projection; the gate; the
# optimizer
STEP = [
    ("fusion.1", HEAD + "block_1/attn/attn/window/q/dot_general", False, 3),
    ("_fwd.6", HEAD + "block_1/attn/attn/window/flash/jit(_fwd)/pallas_call",
     True, 4),
    ("copy.9", HEAD + "block_1/attn/attn/window/flash/transpose", False, 1),
    ("fusion.2", AGAIN + "block_1/attn/attn/window/kv/dot_general", False, 2),
    ("_bwd.6", BACK + "block_1/attn/attn/window/flash/jit(_bwd)/pallas_call",
     True, 8),
    ("_fwd.5", HEAD + "block_0/attn/attn/global/flash/jit(_fwd)/pallas_call",
     True, 10),
    ("_bwd.5", BACK + "block_0/attn/attn/global/flash/jit(_bwd)/pallas_call",
     True, 20),
    ("fusion.3", HEAD + "block_0/attn/attn/global/out/dot_general", False, 5),
    ("fusion.4", HEAD + "block_1/attn/attn/gate/gate/dot_general", False, 1),
    ("fusion.5", "jit(per_shard)/hvd/update/mul", False, 6),
]


def program(step):
    lines = "\n".join(
        f'  %{name} = f32[8]{{0}} {"custom-call" if kernel else "fusion"}'
        f'(%x){KERNEL if kernel else ""}, metadata={{op_name="{op}"}}'
        for name, op, kernel, _ in step)
    return ("HloModule jit_per_shard\n\nENTRY %main (x: f32[8]) -> f32[8] {\n"
            "  %x = f32[8]{0} parameter(0)\n" + lines + "\n}\n")


def planes(step, steps=2):
    events, t = [], 0
    for _ in range(steps):
        for name, _, kernel, ms in step:
            text = (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %x)"
                    + KERNEL if kernel else
                    f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)")
            events.append((text, t * MS, (t + ms) * MS))
            t += ms
        t += 5  # idle
    return {"/device:TPU:0": {"XLA Ops": events,
                              "XLA Modules": [("jit_per_shard(1)", 0, MS)]},
            "/host:CPU": {"python3": [("dispatch", 0, MS)]}}


def fake_run(step, tmp_path, flops=None):
    """What a reader is handed after a traced run of ``step``."""
    return run_of(program(step), planes(step), 2, tmp_path, flops)


def run_of(text, made, steps, tmp_path, flops=None):
    """What a reader is handed after ``steps`` traced steps whose
    profile reads as the planes ``made`` and whose program is ``text``."""
    family = types.SimpleNamespace()
    if flops is not None:
        family.window_flash_flops_per_step = lambda config, job: flops
    run = types.SimpleNamespace(
        reduced_trace=True, measured={"traced_steps": steps}, notes={},
        devices=[0], peaks={"bf16_flops_per_s": 1e12},
        programs={"step": types.SimpleNamespace(as_text=lambda: text)},
        cell=types.SimpleNamespace(root=str(tmp_path), name="toy",
                                   bench=BENCH, family=family, config={},
                                   job={}))
    reducer = types.SimpleNamespace(**{
        name: getattr(reduce, name) for name in dir(reduce)
        if not name.startswith("__")})
    reducer.load = lambda path: path
    reducer.planes_of = lambda profile: made

    def reader(directory, name):
        if name == "trace_reduce":
            return reducer
        return load_by_path(os.path.join(BENCH, directory, name + ".py"),
                            "hvd_benchmark_w_" + name)

    run.reader = reader
    run.scope_trace = scope_trace.reduce_planes(
        reduce, made, *trace.step_phases(text), steps)
    # the profiler's file the roofline's reader opens again
    folder = tmp_path / ".bench_trace" / "toy" / "plugins" / "profile" / "1"
    folder.mkdir(parents=True, exist_ok=True)
    (folder / "toy.xplane.pb").write_bytes(b"")
    return run


def read(metric, run):
    return run.reader("layer_metrics", metric).read(run)


def test_shares_by_scope_forward_recomputation_and_backward(tmp_path):
    run = fake_run(STEP, tmp_path)
    (chip,) = run.scope_trace
    assert chip.busy_ms == pytest.approx(60.0)
    # the projections, both kernels, the copy and the recomputed
    # projection; not the gate, not the full layer
    assert read("window_attn_time_share", run) == pytest.approx(
        100 * (3 + 4 + 1 + 2 + 8) / 60)
    assert read("global_attn_time_share", run) == pytest.approx(
        100 * (10 + 20 + 5) / 60)


def test_the_roofline_divides_by_the_kernels_under_the_window_alone(
        tmp_path):
    """12 ms of kernels a step under ``attn/window/flash``: not the
    layout copy in the same scope, not the full layer's kernels.  At a
    peak of 1e12 a second, 6e9 operations a step are half of what 12 ms
    could do."""
    run = fake_run(STEP, tmp_path, flops=6e9)
    assert read("window_flash_roofline", run) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", ["window_attn_time_share",
                                    "global_attn_time_share",
                                    "window_flash_roofline"])
def test_a_program_without_the_scopes_leaves_the_metric_out(metric,
                                                            tmp_path):
    """The parent's model has ``attn/latent`` and ``attn/rope`` and none
    of these; an untraced run and a family without the count too."""
    parent = [(name, op.replace("attn/window", "attn/latent")
               .replace("attn/global", "attn/rope"), kernel, ms)
              for name, op, kernel, ms in STEP]
    assert read(metric, fake_run(parent, tmp_path, flops=6e9)) is None
    untraced = fake_run(STEP, tmp_path, flops=6e9)
    untraced.reduced_trace = None
    untraced.scope_trace = None
    assert read(metric, untraced) is None
    if metric == "window_flash_roofline":
        assert read(metric, fake_run(STEP, tmp_path)) is None


def test_a_scope_is_matched_by_whole_components():
    under = load_by_path(
        os.path.join(BENCH, "layer_metrics", "window_attn_time_share.py"),
        "hvd_benchmark_w_under").under
    assert under("block/attn/window/flash", "attn/window")
    assert under("attn/window", "attn/window")
    assert not under("block/attn/windowed/flash", "attn/window")
    assert not under("block/attn/global", "attn/window")


# ------------------------------------------------- the recorded trace
# One step of laguna_s_2_1-spmd-1chip on the v5e (PR 38, seed
# 2147483999), cut by cut_trace.py, and the text of the step that ran
# it, its kernels' serialized bodies (``backend_config=...``) cut off.
RECORDED = os.path.join(HERE, "fixtures", "laguna_s_2_1-spmd-1chip.pr38.")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        text = f.read()
    family = load_by_path(os.path.join(BENCH, "models", "laguna_lm.py"),
                          "hvd_benchmark_w_laguna_lm")
    config = load_json(os.path.join(REPO, "benchmark", "configs",
                                    "laguna_s_2_1.json"))
    run = run_of(text, reduce.planes_of(reduce.load(
        RECORDED + "1step.xplane.pb.gz")), 1,
        tmp_path_factory.mktemp("recorded"),
        flops=family.window_flash_flops_per_step(config, config["job"]))
    run.peaks = {"bf16_flops_per_s": 197e12}
    return run


def test_recorded_step_by_the_two_kinds_of_layer(recorded):
    """409.2 ms busy: the sliding layers' attention 138.3 ms (three
    layers), the full layers' 96.2 (two), the gate 8.8 beside them; the
    sliding layers' six kernel calls 32.1 ms for 1.348e12 operations."""
    (chip,) = recorded.scope_trace
    assert chip.busy_ms == pytest.approx(409.2, abs=0.5)
    assert read("window_attn_time_share", recorded) == pytest.approx(
        33.8, abs=0.2)
    assert read("global_attn_time_share", recorded) == pytest.approx(
        23.5, abs=0.2)
    assert read("window_flash_roofline", recorded) == pytest.approx(
        21.3, abs=0.3)
    gate = sum(ms for (scope, _), ms in chip.both_ms.items()
               if scope.endswith("attn/gate"))
    assert gate == pytest.approx(8.8, abs=0.2)
    # by kind and phase: a flash forward and ONE backward a layer, and
    # no kernel in the recomputation (its output and lse are saved)
    flash = {(scope, phase): ms for (scope, phase), ms
             in chip.both_ms.items() if scope.endswith("/flash")}
    assert flash["block/attn/window/flash", "backward"] > (
        flash["block/attn/window/flash", "forward"]) > 10
    assert flash["block/attn/window/flash", "recompute"] < 3
    assert flash["block/attn/global/flash", "recompute"] < 3


def test_recorded_flash_calls_take_k_and_v_with_eight_heads(recorded):
    """The ten flash custom calls of the step that ran: q of a sliding
    layer ``[72,8192,128]``, of a full layer ``[48,8192,128]``, k and v
    ``[8,8192,128]`` in every one."""
    text = recorded.programs["step"].as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "/flash/" in line]
    assert len(calls) == 10
    for line in calls:
        operands = line.split("operand_layout_constraints={", 1)[1]
        assert ", bf16[8,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}" in (
            operands)
        assert operands.startswith(("bf16[72,8192,128]",
                                    "bf16[48,8192,128]"))
