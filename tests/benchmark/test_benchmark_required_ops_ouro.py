"""Family ``ouro_lm``'s counts of required operations and of the flash
kernels' operations, against counts worked on paper from the published
shapes; the shapes its trace readers look for; and the readers of
``flash_roofline`` and ``loop_exits_time_share`` (``benchmark/
loop_trace.py``) on a hand-built case whose answers are known and on one
step cut from the ``ouro_2_6b-spmd-1chip`` trace recorded on the v5e in
PR 33."""

import os
import types

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

CONFIG = load_json(os.path.join(REPO, "benchmark", "configs",
                                "ouro_2_6b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "ouro_lm.py"),
                      "hvd_benchmark_ops_ouro_lm")
reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce_ouro")
loop_trace = load_by_path(os.path.join(BENCH, "loop_trace.py"),
                          "hvd_benchmark_loop_trace")
CELL = types.SimpleNamespace(config=CONFIG, job=CONFIG["job"],
                             family=FAMILY)
SHAPES = FAMILY.trace_shapes(CONFIG, CONFIG["job"])
METRICS = ["flash_roofline", "loop_exits_time_share"]
MS = 1_000_000  # ns
PEAK = 197e12

# Ouro-2.6B, matmul parameters a token meets.
# A block, each of its four applications:
#   q, k, v and the output projection: 4 x 2048 x (16 x 128 = 2048)
#                                                          = 16,777,216
#   gate, up, down: 3 x 2048 x 5632                        = 34,603,008
#                                                  a block = 51,380,224
# The head, once an exit: 2048 x 49152                     = 100,663,296
# The gate, once an exit: 2048 x 1                         =       2,048
BLOCK, HEAD, GATE = 51_380_224, 100_663_296, 2_048
# the cell: 8 blocks, 4 passes: 32 applications, 4 exits
PER_TOKEN = 32 * BLOCK + 4 * (HEAD + GATE)
# causal attention, a sequence of 4096: (4096 x 4097 / 2 = 8,390,656
# pairs) x 16 heads x (2 x 128 for a score + 2 x 128 for the weighted
# sum = 512) x 32 applications
PAIRS = 8_390_656
ATTENTION_4096 = PAIRS * 16 * 512 * 32
FLASH_FLOPS = 6_598_680_379_392


def test_required_operations_at_the_sizes_the_cell_runs():
    assert PER_TOKEN == 2_046_828_544
    assert ATTENTION_4096 == 2_199_560_126_464
    want = 3 * (2 * PER_TOKEN * 4096 + ATTENTION_4096)
    assert want == 56_901_538_676_736
    assert FAMILY.required_flops_per_sample(CONFIG, CONFIG["job"]) == want


def test_the_cell_is_13_89_gflop_a_token():
    """The issue's count, confirmed: 13,891,977,216 a token = 32 x (6 x
    51,380,224 + 50,343,936 of causal attention) + 4 x 603,979,776 + the
    gate's 49,152; 56.9 TFLOP a step of 4,096 tokens; the four exits'
    heads are 17.4% of it (3.4% at the published depth of 48)."""
    job = CONFIG["job"]
    per_token = (FAMILY.required_flops_per_sample(CONFIG, job)
                 // FAMILY.sample_units(CONFIG, job))
    attention = 3 * 16 * 512 * 4097 // 2
    assert attention == 50_343_936
    assert per_token == 13_891_977_216 == (
        32 * (6 * BLOCK + attention) + 4 * 6 * HEAD + 4 * 6 * GATE)
    assert 6 * HEAD == 603_979_776 and 4 * 6 * GATE == 49_152
    assert per_token * 4096 == pytest.approx(56.9e12, rel=1e-3)
    assert 4 * 6 * HEAD / per_token == pytest.approx(0.174, abs=0.0005)
    deep = 192 * (6 * BLOCK + attention) + 4 * 6 * (HEAD + GATE)
    assert 4 * 6 * HEAD / deep == pytest.approx(0.034, abs=0.0005)


def test_flash_operations_a_step():
    """``3 x 2 x (128 + 128) x B x 16 x T (T + 1) / 2`` an application,
    32 applications, 1 sequence: the attention part of the required
    count."""
    want = 3 * 2 * 256 * 16 * PAIRS * 32
    assert want == FLASH_FLOPS == 3 * ATTENTION_4096
    assert FAMILY.flash_flops_per_step(CONFIG, CONFIG["job"]) == want


def test_parameters_of_the_published_configuration_cut_in_depth():
    """8 x (51,380,224 + four norm scales of 2048) + embedding and head
    2 x 49,152 x 2048 + the final norm + the gate's 2048 + 1 =
    612,438,017 parameters = 9.80 GB at 16 bytes: the program's own
    tree, by ``jax.eval_shape``; made ONCE, whatever the passes."""
    import jax

    params, extra = jax.eval_shape(
        lambda key: FAMILY.init(CONFIG, CONFIG["job"], key),
        jax.random.PRNGKey(0))
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert count == 612_438_017 == (
        8 * (BLOCK + 4 * 2048) + 2 * HEAD + 2048 + 2049)
    assert 16 * count == pytest.approx(9.80e9, rel=1e-3)
    assert sorted(params) == sorted(
        [f"block_{i}" for i in range(8)]
        + ["embed", "exit_gate", "lm_head", "ln_f"])
    assert params["block_0"]["attn"]["qkv"]["kernel"].shape == (
        2048, 3, 16, 128)
    assert sorted(params["block_7"]) == ["attn", "ln1", "ln1_post", "ln2",
                                         "ln2_post", "mlp"]
    assert params["lm_head"]["kernel"].shape == (2048, 49152)
    assert extra["exit_probability"].shape == (4,)


def test_trace_shapes_at_the_cells_sizes_and_at_a_toy_size():
    assert SHAPES == {"flash": ["[16,4096,128]"],
                      "exits": ["[16384,49152]", "[4,1,4096,49152]",
                                "[4,4096,49152]"]}
    toy = load_json(os.path.join(REPO, "tests", "benchmark", "toy",
                                 "ouro_2_6b.json"))
    small = FAMILY.trace_shapes({**CONFIG, **toy["sizes"]}, toy["job"])
    assert small == {"flash": ["[8,16,8]"],
                     "exits": ["[128,97]", "[4,2,16,97]"]}


# ------------------------------------------------------ the two readers
def reader(name):
    return load_by_path(os.path.join(BENCH, "layer_metrics", name + ".py"),
                        "hvd_benchmark_reader_" + name)


def fake_run(trace, steps=1, cell=CELL):
    """A run whose trace is parsed already (``loop_trace.read`` keeps
    what it parsed on ``run``)."""
    trace.steps = steps
    return types.SimpleNamespace(
        cell=cell, reduced_trace={}, loop_trace=trace, devices=[0],
        measured={"traced_steps": steps},
        peaks={"bf16_flops_per_s": PEAK},
        reader=lambda directory, name: loop_trace)


# One chip, one step, times in ms.  The flash kernels (the forward by
# its OPERANDS too: its second result is the row statistics): 40 + 15 +
# 17 = 72.  The exits: the head's product over the 16,384 rows of the
# four exits, its gradient to the exits, its weight gradient fused with
# the Adam update (by its operand), the two loss kernels: 5 + 5 + 6 + 2
# + 3 = 21.  Neither: a block's feed-forward, the gate (``[16384,1]``),
# a norm's kernel-free fusion, Adam over the head's weights alone
# (``[2048,49152]`` is no activation's shape).
FWD = ('%_fwd.3 = (bf16[16,4096,128]{2,1,0}, f32[16,8,1,512]{3,2,1,0}) '
       'custom-call(bf16[16,4096,128]{2,1,0} %q, bf16[16,4096,128]{2,1,0} '
       '%k, bf16[16,4096,128]{2,1,0} %v), '
       'custom_call_target="tpu_custom_call"')
DQ = ('%_bwd.5 = bf16[16,4096,128]{2,1,0} custom-call('
      'bf16[16,4096,128]{2,1,0} %q, bf16[16,4096,128]{2,1,0} %do), '
      'custom_call_target="tpu_custom_call"')
DKV = ('%_bwd.6 = (bf16[16,4096,128]{2,1,0}, bf16[16,4096,128]{2,1,0}) '
       'custom-call(bf16[16,4096,128]{2,1,0} %q), '
       'custom_call_target="tpu_custom_call"')
HEAD_FWD = ("%fusion.11 = bf16[16384,49152]{1,0} fusion(bf16[16384,2048]"
            "{1,0} %exits, bf16[2048,49152]{1,0} %w), kind=kOutput")
HEAD_DX = ("%fusion.12 = bf16[4,4096,2048]{2,1,0} fusion("
           "bf16[4,4096,49152]{2,1,0} %dlogits, bf16[2048,49152]{1,0} %w), "
           "kind=kOutput")
HEAD_DW = ("%fusion.13 = (f32[2048,49152]{1,0}, f32[2048,49152]{1,0}) "
           "fusion(bf16[16384,2048]{1,0} %exits, bf16[16384,49152]{1,0} "
           "%dlogits, f32[2048,49152]{1,0} %mu), kind=kOutput")
XENT = ('%jvp_exit_loss_.2 = (f32[16384,128]{1,0}, f32[16384,128]{1,0}) '
        'custom-call(bf16[16384,49152]{1,0} %logits, s32[16384,128]{1,0} '
        '%labels), custom_call_target="tpu_custom_call"')
XENT_BWD = ('%transpose_jvp_exit_loss__.1 = bf16[16384,49152]{1,0} '
            'custom-call(bf16[16384,49152]{1,0} %logits), '
            'custom_call_target="tpu_custom_call"')
MLP = ("%fusion.20 = bf16[1,4096,5632]{2,1,0} fusion(bf16[1,4096,2048]"
       "{2,1,0} %h, bf16[2048,5632]{1,0} %w), kind=kOutput")
GATE_OP = ("%fusion.21 = f32[16384,1]{1,0} fusion(bf16[16384,2048]{1,0} "
           "%exits, f32[2048,1]{1,0} %w), kind=kOutput")
NORM = ("%fusion.22 = bf16[1,4096,2048]{2,1,0} fusion(bf16[1,4096,2048]"
        "{2,1,0} %x, f32[2048]{0} %scale), kind=kLoop")
ADAM = ("%fusion.23 = (f32[2048,49152]{1,0}, f32[2048,49152]{1,0}) fusion("
        "f32[2048,49152]{1,0} %w, f32[2048,49152]{1,0} %g), kind=kLoop")


def hand_planes():
    events, at = [], 0
    for text, ms in ((FWD, 40), (DQ, 15), (DKV, 17), (HEAD_FWD, 5),
                     (HEAD_DX, 5), (HEAD_DW, 6), (XENT, 2), (XENT_BWD, 3),
                     (MLP, 60), (GATE_OP, 1), (NORM, 16), (ADAM, 30)):
        events.append((text, at * MS, (at + ms) * MS))
        at += ms
    return {"/device:TPU:0": {"XLA Ops": events,
                              "XLA Modules": [("jit_step(1)", 0, at * MS)]},
            "/host:CPU": {"python3": [("dispatch", 0, MS)]}}


def test_hand_built_times_and_shares():
    trace = loop_trace.reduce_planes(reduce, hand_planes(), SHAPES)
    assert trace.busy_s == pytest.approx(0.200)
    assert trace.flash_s == pytest.approx(0.072)
    assert trace.exits_s == pytest.approx(0.021)
    assert reader("loop_exits_time_share").read(
        fake_run(trace)) == pytest.approx(10.5)
    # 6,598,680,379,392 operations in 72 ms of kernels
    want = 100 * FLASH_FLOPS / (0.072 * PEAK)
    assert reader("flash_roofline").read(
        fake_run(trace)) == pytest.approx(want)
    # four traced steps: four times the operations for the same time,
    # and an impossible reading is reported, not clipped
    fourfold = reader("flash_roofline").read(fake_run(trace, steps=4))
    assert fourfold == pytest.approx(4 * want) and fourfold > 100


def test_nested_instructions_are_counted_once():
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 10 * MS),
        (FWD, 2 * MS, 6 * MS)]}}
    trace = loop_trace.reduce_planes(reduce, planes, SHAPES)
    assert trace.flash_s == pytest.approx(0.004)
    assert trace.busy_s == pytest.approx(0.010)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_these_leaves_the_metric_out(metric):
    """A program with neither (the parent's, or any other cell's), an
    untraced run, a family without ``trace_shapes`` or with other lists
    in it: no reading, no error."""
    planes = {"/device:TPU:0": {"XLA Ops": [(MLP, 0, MS), (ADAM, MS,
                                                        2 * MS)]}}
    dense = loop_trace.reduce_planes(reduce, planes, SHAPES)
    assert dense.flash_s == dense.exits_s == 0.0
    assert reader(metric).read(fake_run(dense)) is None
    untraced = types.SimpleNamespace(
        cell=CELL, reduced_trace=None, peaks=None,
        reader=lambda directory, name: loop_trace)
    assert reader(metric).read(untraced) is None
    other = types.SimpleNamespace(
        cell=types.SimpleNamespace(config={"n_embd": 1024},
                                   family=types.SimpleNamespace()),
        reduced_trace={}, peaks=None,
        reader=lambda directory, name: loop_trace)
    assert reader(metric).read(other) is None
    # JoyAI's family gives ``flash`` and no ``exits``, and no such kernel
    # runs here
    latent = {"flash": ["[128,4096,192]"], "latent": [], "experts": []}
    assert loop_trace.reduce_planes(
        reduce, hand_planes(), latent).flash_s == 0.0


# ------------------------------------------------- the recorded trace
# One step of ouro_2_6b-spmd-1chip on the v5e (PR 33, seed 1100000033),
# cut by cut_trace.py: names and times are the chip's.
RECORDED = os.path.join(
    HERE, "fixtures", "ouro_2_6b-spmd-1chip.1step.xplane.pb.gz")
# by hand on that step, ms: the logits of the four exits 17.060766, the
# gradient to the exits 17.603667, the head's weight gradient fused with
# its Adam update 25.246642, the loss kernels 2.130202 + 4.767254
EXITS_MS = 17.060766 + 17.603667 + 25.246642 + 2.130202 + 4.767254


@pytest.fixture(scope="module")
def recorded_planes():
    return reduce.planes_of(reduce.load(RECORDED))


@pytest.fixture(scope="module")
def recorded(recorded_planes):
    return loop_trace.reduce_planes(reduce, recorded_planes, SHAPES)


def test_recorded_planes_and_the_kernels_of_32_recomputed_applications(
        recorded_planes):
    assert {p: {line: len(events) for line, events in lines.items()}
            for p, lines in recorded_planes.items()} == {
        "/device:TPU:0": {"XLA Modules": 1, "XLA Ops": 12962,
                          "Async XLA Ops": 4893},
        "/host:CPU": {"python3": 34}}
    ops = [t for t, _, _ in recorded_planes["/device:TPU:0"]["XLA Ops"]]
    flash = [t for t in ops if reduce.PALLAS_TARGET in t
             and "[16,4096,128]" in t]
    # 8 blocks x 4 passes: the forward kernel twice an application
    # (recomputation), dq and dk/dv once
    results = sorted(reduce.parse(t)[2].split("{")[0] for t in flash)
    assert results == (["(bf16[16,4096,128]"] * (64 + 32)
                       + ["bf16[16,4096,128]"] * 32)
    # the passes are two loops a step, forward and backward
    assert len([t for t in ops if reduce.parse(t)[1] == "while"]) == 2
    # ONE head product over the four exits, its two gradients, the two
    # loss kernels: five instructions, whatever the passes
    exits = [t for t in ops if any(s in t for s in SHAPES["exits"])]
    assert len(exits) == 5
    assert sorted(reduce.PALLAS_TARGET in t for t in exits) == [
        False, False, False, True, True]


def test_recorded_times(recorded):
    assert recorded.busy_s == pytest.approx(0.547436601, rel=1e-9)
    assert recorded.flash_s == pytest.approx(0.104596481, rel=1e-6)
    assert recorded.exits_s == pytest.approx(EXITS_MS / 1e3, rel=1e-6)
    assert EXITS_MS == pytest.approx(66.808531)


@pytest.mark.parametrize("metric,want", [
    ("loop_exits_time_share", 100 * 0.066808531 / 0.547436601),
    ("flash_roofline", 100 * FLASH_FLOPS / (0.104596481 * PEAK)),
])
def test_readers_on_the_recorded_trace(metric, want, recorded):
    """The exits are 12.2% of the step; the flash kernels run at 32.0%
    of the peak (6.60 TFLOP in 104.6 ms, the forward kernel twice)."""
    got = reader(metric).read(fake_run(recorded))
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got < 100


def test_readers_through_the_file_as_a_run_finds_it(tmp_path):
    """``loop_trace.read`` globs the profiler's directory of the cell
    under ``<root>/.bench_trace`` and parses once for both readers."""
    import gzip
    import shutil

    folder = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as src, open(
            folder / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(
            config=CONFIG, job=CONFIG["job"], family=FAMILY, name="cell",
            root=str(tmp_path), bench=BENCH),
        reduced_trace={}, devices=[0], measured={"traced_steps": 1},
        peaks={"bf16_flops_per_s": PEAK},
        reader=lambda directory, name: load_by_path(
            os.path.join(BENCH, directory, name + ".py"),
            "hvd_benchmark_" + name))
    share = reader("loop_exits_time_share").read(run)
    parsed = run.loop_trace
    roofline = reader("flash_roofline").read(run)
    assert run.loop_trace is parsed
    assert share == pytest.approx(12.204, rel=1e-4)
    assert roofline == pytest.approx(32.024, rel=1e-4)
