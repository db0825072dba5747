"""The compiled step by phase and scope in the device trace
(``benchmark/scope_trace.py`` and the nine ``layer_metrics/step_*.py``):
on hand-built planes and a hand-written program text whose answers are
known, and on one step of ``joyai_llm_flash-spmd-1chip`` recorded on the
v5e in PR 35 with the text of the program that ran it."""

import gzip
import os
import shutil
import sys
import types

import pytest

from benchmark_toy import BENCH, HERE, load_by_path

from horovod_tpu.utils import trace

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
scope_trace = load_by_path(os.path.join(BENCH, "scope_trace.py"),
                           "hvd_benchmark_scope_trace")
MS = 1_000_000  # ns
TOKEN_METRICS = ["step_forward_ms", "step_recompute_ms", "step_backward_ms",
                 "step_update_ms", "step_unnamed_share"]
IMAGE_METRICS = ["step_forward_ms_images", "step_backward_ms_images",
                 "step_update_ms_images", "step_unnamed_share_images"]


def read(metric, run):
    return load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric).read(run)


# A step of seven instructions that run: a forward loop of two
# iterations whose body holds a product and a copy the compiler made, a
# recomputed product, a weight gradient fused with its Adam update
# (named after the product), the gradients' all-reduce, the optimizer's
# pass over what did not fuse with the prefetch of its operand, which
# has no name, and an instruction with no name and no neighbour.
PROGRAM = """HloModule jit_per_shard

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %dot.4 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(per_shard)/transpose(jvp(Transformer))/block_1/mlp/up/dot_general"}
  ROOT %add.4 = f32[8]{0} add(%dot.4, %p), metadata={op_name="jit(per_shard)/hvd/update/add"}
}

%fused_computation.2 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %mul.5 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(per_shard)/hvd/update/mul"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %dot.1 = f32[8]{0} dot(%t, %t), metadata={op_name="jit(per_shard)/jvp(Transformer)/loop/while/body/closed_call/Transformer.one_pass/block_0/mlp/up/dot_general"}
  %copy.1 = f32[8]{0} copy(%dot.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%t, %copy.1)
}

%cond (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%t.1, %t.1), direction=LT
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(per_shard)/jvp(Transformer)/loop/while"}
  %dot.2 = f32[8]{0} dot(%x, %x), metadata={op_name="jit(per_shard)/transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/rematted_computation/block_1/mlp/up/dot_general"}
  %fusion.1 = f32[8]{0} fusion(%dot.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(per_shard)/transpose(jvp(Transformer))/block_1/mlp/up/dot_general"}
  %all-reduce.1 = f32[8]{0} all-reduce(%fusion.1), to_apply=%cond, metadata={op_name="jit(per_shard)/shard_map/hvd/exchange/psum"}
  ROOT %fusion.2 = f32[8]{0} fusion(%all-reduce.1, %copy-done.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(per_shard)/hvd/update/mul"}
}
"""


def text_of(name):
    """An event's name: the instruction's text, as the v5e writes it."""
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x), kind=kLoop"


def a_step(at, stretch=1):
    """One step's events from ``at`` ms: the loop 0-10 with two
    iterations of a product (3 ms) and a copy (1 ms) nested in it, so 2
    of the loop's own; then the recomputed product 4, the weight
    gradient with its update 6, the all-reduce 5, the optimizer's
    prefetch 1, the optimizer 3, the lone instruction 1 and an event of
    another program 1; idle for 2 ms."""
    events, t = [], at
    loop_end = at + 10 * stretch
    events.append((text_of("while.1"), t * MS, loop_end * MS))
    t += 1 * stretch
    for _ in range(2):
        events.append((text_of("dot.1"), t * MS, (t + 3 * stretch) * MS))
        t += 3 * stretch
        events.append((text_of("copy.1"), t * MS, (t + 1 * stretch) * MS))
        t += 1 * stretch
    t = loop_end
    for name, ms in (("dot.2", 4), ("fusion.1", 6), ("all-reduce.1", 5),
                     ("copy-done.1", 1), ("fusion.2", 3), ("iota.1", 1),
                     ("convert.77", 1)):
        events.append((text_of(name), t * MS, (t + ms * stretch) * MS))
        t += ms * stretch
    return events, t + 2


def hand(chips=1, stretch=(1,)):
    planes = {}
    for chip in range(chips):
        first, at = a_step(0, stretch[chip])
        second, _ = a_step(at, stretch[chip])
        planes[f"/device:TPU:{chip}"] = {
            "XLA Ops": first + second,
            "XLA Modules": [("jit_per_shard(1)", 0, at * MS)] * 2}
    planes["/host:CPU"] = {"python3": [("dispatch", 0, MS)]}
    return scope_trace.reduce_planes(reduce, planes,
                                     *trace.step_phases(PROGRAM), 2)


def test_hand_built_phases_partition_busy_time():
    (chip,) = hand()
    assert chip.busy_ms == pytest.approx(31.0)
    assert chip.phase_ms == pytest.approx({
        "forward": 10.0,     # the loop: its products, its copies, itself
        "recompute": 4.0,
        "backward": 6.0,     # the fusion goes where its own name says
        "exchange": 5.0,
        "update": 4.0,       # the optimizer and its operand's prefetch
        "unnamed": 2.0})     # the lone one and the other program's event
    assert sum(chip.phase_ms.values()) == pytest.approx(chip.busy_ms)
    assert chip.borrowed_ms == pytest.approx(1.0)
    assert chip.strangers_ms == pytest.approx(1.0)


def test_hand_built_scopes_and_the_loop_body():
    (chip,) = hand()
    assert {k: v for k, v in chip.both_ms.items() if v} == pytest.approx({
        ("loop/block/mlp/up", "forward"): 6.0,
        # a copy the compiler made in the body, and the loop's own time
        ("loop", "forward"): 4.0,
        ("block/mlp/up", "recompute"): 4.0,
        ("block/mlp/up", "backward"): 6.0,
        ("hvd/exchange", "exchange"): 5.0,
        ("hvd/update", "update"): 4.0,
        ("", "unnamed"): 2.0})


def test_hand_built_mixed_fusion_is_shown_beside_its_phase():
    (chip,) = hand()
    assert chip.mixed_ms == pytest.approx({("backward", "update"): 6.0})


def test_the_table_has_a_row_for_every_prefix():
    (chip,) = hand()
    lines = scope_trace.table(chip)
    rows = {line[:48].strip(): [float(v) for v in line[48:].split()]
            for line in lines[1:]
            if not line.startswith(("busy", "fusions"))}
    assert lines[0].split() == ["ms", "a", "step", *trace.PHASES, "total"]
    assert rows["step"] == [10.0, 4.0, 6.0, 5.0, 4.0, 2.0, 31.0]
    assert rows["block"] == [0.0, 4.0, 6.0, 0.0, 0.0, 0.0, 10.0]
    assert rows["loop"] == [10.0, 0.0, 0.0, 0.0, 0.0, 0.0, 10.0]
    assert rows["loop/block/mlp/up"] == [6.0, 0.0, 0.0, 0.0, 0.0, 0.0, 6.0]
    assert rows["block/mlp/up"] == rows["block"]
    assert rows["hvd/exchange"][-1] == 5.0 and rows["hvd/update"][-1] == 4.0
    # what a scope holds itself, beside its children
    assert rows["loop/(itself)"] == [4.0, 0.0, 0.0, 0.0, 0.0, 0.0, 4.0]
    assert rows["(itself)"] == [0.0, 0.0, 0.0, 0.0, 0.0, 2.0, 2.0]
    assert any(line.startswith("fusions that hold backward + update: 6.00")
               for line in lines)


def fake_run(chips=None, reduced_trace=True, **measured):
    """What a reader is handed; ``chips`` as though the profiler's file
    and the step's text had been joined already."""
    run = types.SimpleNamespace(
        reduced_trace=reduced_trace, measured=measured, notes={},
        programs={"step": types.SimpleNamespace(as_text=lambda: PROGRAM)},
        cell=types.SimpleNamespace(root=None, name="toy", bench=BENCH),
        reader=lambda directory, name: load_by_path(
            os.path.join(BENCH, directory, name + ".py"),
            "hvd_benchmark_" + name))
    if chips is not None:
        run.scope_trace = chips
    return run


@pytest.mark.parametrize("metric,want", [
    ("step_forward_ms", 20.0), ("step_recompute_ms", 8.0),
    ("step_backward_ms", 12.0), ("step_update_ms", 8.0),
    # the same share on both chips: 2 of 31 and 4 of 62
    ("step_unnamed_share", 100 * 2 / 31),
    ("step_forward_ms_images", 20.0), ("step_backward_ms_images", 12.0),
    ("step_update_ms_images", 8.0),
    ("step_unnamed_share_images", 100 * 2 / 31),
])
def test_readers_take_the_worst_chip(metric, want):
    """Two chips, the second twice as slow in every instruction."""
    chips = hand(chips=2, stretch=(1, 2))
    assert [c.busy_ms for c in chips] == pytest.approx([31.0, 62.0])
    assert read(metric, fake_run(chips)) == pytest.approx(want)


@pytest.mark.parametrize("metric", TOKEN_METRICS + IMAGE_METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(metric, monkeypatch):
    # an untraced run; a loop that keeps no compiled step
    assert read(metric, fake_run(reduced_trace=None)) is None
    eager = fake_run()
    eager.programs = {}
    assert read(metric, eager) is None
    # the parent of the PR that brought step_phases: the package's
    # module is there, the function is not
    monkeypatch.delattr(trace, "step_phases")
    parent = fake_run(traced_steps=2)
    assert read(metric, parent) is None
    assert parent.scope_trace is None and parent.notes == {}


# ------------------------------------------------- the recorded trace
# One step of joyai_llm_flash-spmd-1chip on the v5e (PR 35), cut by
# cut_trace.py, and the text of the step that ran it, its kernels'
# serialized bodies (``backend_config=...``) cut off: names, metadata
# and times are the chip's and its compiler's.
RECORDED = os.path.join(HERE, "fixtures",
                        "joyai_llm_flash-spmd-1chip.pr35.")


@pytest.fixture(scope="module")
def recorded_text():
    with gzip.open(RECORDED + "step.hlo.txt.gz", "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def recorded(recorded_text):
    planes = reduce.planes_of(reduce.load(RECORDED + "1step.xplane.pb.gz"))
    (chip,) = scope_trace.reduce_planes(
        reduce, planes, *trace.step_phases(recorded_text), 1)
    return chip


def test_recorded_program_by_phase(recorded_text):
    instructions, fused, borrowed = trace.step_phases(recorded_text)
    assert (len(instructions), len(fused), len(borrowed)) == (
        10320, 938, 4799)
    phases = [p for p, _ in instructions.values()]
    assert {p: phases.count(p) for p in trace.PHASES} == {
        "forward": 2626, "recompute": 1929, "backward": 3012,
        "exchange": 0, "update": 777, "unnamed": 1976}
    # the grouped products keep ``op_name="ragged-dot-none"`` alone and
    # are read as their operands: the five expert layers (the module's
    # among them), each three forward, three recomputed, six backward
    kernels = [instructions[n][0] for n in instructions
               if n.startswith("ragged-dot-none")]
    assert {p: kernels.count(p) for p in set(kernels)} == {
        "forward": 15, "recompute": 15, "backward": 30}
    assert all(n in borrowed for n in instructions
               if n.startswith("ragged-dot-none"))
    # a recomputed block does not run its flash forward kernel again
    flash = [instructions[n] for n in instructions if n.startswith("_fwd.")]
    assert flash and {p for p, _ in flash} == {"forward"}


def test_recorded_phases_partition_busy_time(recorded):
    assert recorded.busy_ms == pytest.approx(669.087884, rel=1e-9)
    assert recorded.phase_ms == pytest.approx({
        "forward": 176.835718, "recompute": 102.342359,
        "backward": 374.076949, "exchange": 0.0, "update": 15.832858,
        "unnamed": 0.0}, rel=1e-6, abs=1e-9)
    assert sum(recorded.phase_ms.values()) == pytest.approx(
        recorded.busy_ms, rel=1e-9)
    # every event is an instruction of the step
    assert recorded.strangers_ms == 0.0
    assert recorded.borrowed_ms == pytest.approx(40.819318, rel=1e-6)


def test_recorded_scopes_and_mixed_fusions(recorded):
    def under(prefix):
        return sum(ms for (scope, _), ms in recorded.both_ms.items()
                   if scope == prefix or scope.startswith(prefix + "/"))

    # latent attention with its output projection, in the five blocks
    # and in the next-token module: 62% of the step
    assert under("block/attn") == pytest.approx(350.896, abs=1e-3)
    assert under("mtp/NextTokenModule/block/attn") == pytest.approx(
        69.81, abs=5e-2)
    assert under("block/moe") == pytest.approx(96.651, abs=1e-3)
    assert under("loss") == pytest.approx(4.688, abs=1e-3)
    assert recorded.mixed_ms[("backward", "update")] == pytest.approx(
        28.889208, rel=1e-6)
    assert recorded.mixed_ms[("backward", "recompute", "update")] == \
        pytest.approx(40.829951, rel=1e-6)
    assert "block/attn/latent" in "\n".join(scope_trace.table(recorded))


def test_readers_through_the_files_as_a_run_finds_them(tmp_path,
                                                       recorded_text):
    """``scope_trace.read`` globs the profiler's directory of the cell
    under ``<root>/.bench_trace``, takes the text from the run's
    compiled step, parses once for the readers, leaves the text beside
    the trace and the split on the run's earlier line."""
    cell_dir = tmp_path / ".bench_trace" / "cell"
    folder = cell_dir / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    with gzip.open(RECORDED + "1step.xplane.pb.gz", "rb") as src, open(
            folder / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    run = fake_run(traced_steps=1)
    run.cell.root, run.cell.name = str(tmp_path), "cell"
    run.programs = {"step": types.SimpleNamespace(
        as_text=lambda: recorded_text)}
    recompute = read("step_recompute_ms", run)
    parsed = run.scope_trace
    assert recompute == pytest.approx(102.342359, rel=1e-6)
    assert read("step_forward_ms", run) == pytest.approx(176.835718,
                                                         rel=1e-6)
    assert read("step_backward_ms", run) == pytest.approx(374.076949,
                                                          rel=1e-6)
    assert read("step_update_ms", run) == pytest.approx(15.832858, rel=1e-6)
    assert read("step_unnamed_share", run) == 0.0
    assert run.scope_trace is parsed
    assert (cell_dir / "step.hlo.txt").read_text() == recorded_text
    (split,) = run.notes["step_ms_by_phase"]
    assert split["busy"] == pytest.approx(669.087884)
    assert split["read_as_their_neighbours"] == pytest.approx(40.819318)
    assert split["fusions_that_hold"]["backward+update"] == pytest.approx(
        28.889208)
    assert run.notes["scope_trace_s"] > 0


def test_the_table_by_hand_from_the_two_files(tmp_path, recorded_text):
    """``python benchmark/scope_trace.py <xplane.pb> <step.hlo.txt>``."""
    import subprocess

    text = tmp_path / "step.hlo.txt"
    text.write_text(recorded_text)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "scope_trace.py"),
         RECORDED + "1step.xplane.pb.gz", str(text)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    assert lines[0] == "chip 0, 1 launches"
    step = next(line for line in lines if line.startswith("step "))
    assert [float(v) for v in step.split()[1:]] == [
        176.84, 102.34, 374.08, 0.0, 15.83, 0.0, 669.09]
