"""The readers of ``mla_time_share``, ``mla_flash_roofline`` and
``moe_held_time_share``: the rule that finds the instructions of latent
attention and of the expert layers that hold a share
(``benchmark/latent_trace.py``) on a hand-built case whose answers are
known, and on one step cut from the ``joyai_llm_flash-spmd-1chip`` trace
recorded on the v5e in PR 31."""

import os
import types

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
latent_trace = load_by_path(os.path.join(BENCH, "latent_trace.py"),
                            "hvd_benchmark_latent_trace")
CONFIG = load_json(os.path.join(REPO, "benchmark", "configs",
                                "joyai_llm_flash.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "joyai_lm.py"),
                      "hvd_benchmark_latent_trace_joyai_lm")
CELL = types.SimpleNamespace(config=CONFIG, job=CONFIG["job"],
                             family=FAMILY)
SHAPES = FAMILY.trace_shapes(CONFIG, CONFIG["job"])
METRICS = ["mla_time_share", "mla_flash_roofline", "moe_held_time_share"]
MS = 1_000_000  # ns
PEAK = 197e12
FLASH_FLOPS = 12_372_525_711_360  # hand-checked in ..._required_ops_joyai


def reader(name):
    return load_by_path(os.path.join(BENCH, "layer_metrics", name + ".py"),
                        "hvd_benchmark_reader_" + name)


def fake_run(trace, steps=1):
    """A run whose trace is parsed already (``latent_trace.read`` keeps
    what it parsed on ``run``)."""
    trace.steps = steps
    return types.SimpleNamespace(
        cell=CELL, reduced_trace={}, latent_trace=trace, devices=[0],
        measured={"traced_steps": steps},
        peaks={"bf16_flops_per_s": PEAK},
        reader=lambda directory, name: latent_trace)


# One chip, one step, times in ms.  Latent attention: the three flash
# kernels (the forward by its OPERANDS: its results are 128 wide), the
# projection up from the query's latent, the rotation of the one key,
# the concatenation into heads of 192: 30 + 20 + 25 + 6 + 1 + 2 = 84.
# The expert layer that holds a share: a grouped product by name, the
# gather into the buffer of 131,072 rows, the router's sigmoid over 256
# outputs: 7 + 5 + 3 = 15.  Neither: the shared expert, the head, the
# softmax-xent kernel (a Pallas call that takes rows of tokens), Adam
# over q_b's weights (``[1536,32,192]`` is no activation's shape).
FWD = ('%_fwd.3 = (bf16[128,4096,128]{2,1,0}, f32[128,8,1,512]{3,2,1,0}) '
       'custom-call(bf16[128,4096,192]{2,1,0} %q, bf16[128,4096,192]{2,1,0} '
       '%k, bf16[128,4096,128]{2,1,0} %v), '
       'custom_call_target="tpu_custom_call"')
DQ = ('%_bwd.5 = bf16[128,4096,192]{2,1,0} custom-call('
      'bf16[128,4096,192]{2,1,0} %q, bf16[128,4096,128]{2,1,0} %do), '
      'custom_call_target="tpu_custom_call"')
DKV = ('%_bwd.6 = (bf16[128,4096,192]{2,1,0}, bf16[128,4096,128]{2,1,0}) '
       'custom-call(bf16[128,4096,192]{2,1,0} %q), '
       'custom_call_target="tpu_custom_call"')
Q_UP = ("%fusion.4 = bf16[4,4096,32,192]{3,2,1,0} fusion("
        "bf16[4,4096,1536]{2,1,0} %c_q, bf16[1536,32,192]{2,1,0} %w), "
        "kind=kOutput")
K_ROPE = ("%fusion.5 = bf16[4,4096,64]{2,1,0} fusion(bf16[4,4096,576]{2,1,0}"
          " %kv_a), kind=kLoop")
CONCAT = ("%fusion.6 = bf16[4,32,4096,192]{3,2,1,0} fusion("
          "bf16[4,4096,32,256]{3,2,1,0} %kv, bf16[4,4096,64]{2,1,0} %k_rope),"
          " kind=kLoop")
GMM = ('%ragged-dot-none.3 = bf16[131072,768]{1,0} custom-call('
       'bf16[131072,2048]{1,0} %rows, bf16[16,2048,768]{2,1,0} %w), '
       'custom_call_target="tpu_custom_call"')
GATHER = ("%fusion.7 = bf16[131072,2048]{1,0} fusion(bf16[16384,2048]{1,0} "
          "%x, s32[131072]{0} %order), kind=kLoop")
ROUTER = ("%fusion.9 = f32[16384,256]{0,1} fusion(f32[16384,256]{0,1} "
          "%logits), kind=kLoop")
SHARED = ("%fusion.10 = bf16[4,4096,768]{2,1,0} fusion(bf16[4,4096,2048]"
          "{2,1,0} %h, bf16[2048,768]{1,0} %w), kind=kOutput")
HEAD = ("%fusion.11 = bf16[16384,16160]{1,0} fusion(bf16[16384,2048]{1,0} "
        "%x, bf16[2048,16160]{1,0} %w), kind=kOutput")
XENT = ('%jvp__.2 = (f32[16384,128]{1,0}, f32[16384,128]{1,0}) custom-call('
        'bf16[16384,16160]{1,0} %logits, s32[16384,128]{1,0} %labels), '
        'custom_call_target="tpu_custom_call"')
ADAM = ("%fusion.20 = (f32[1536,32,192]{2,1,0}, f32[1536,32,192]{2,1,0}) "
        "fusion(f32[1536,32,192]{2,1,0} %w, bf16[1536,32,192]{2,1,0} %g), "
        "kind=kLoop")


def hand_planes():
    events, at = [], 0
    for text, ms in ((FWD, 30), (DQ, 20), (DKV, 25), (Q_UP, 6), (K_ROPE, 1),
                     (CONCAT, 2), (GMM, 7), (GATHER, 5), (ROUTER, 3),
                     (SHARED, 4), (HEAD, 40), (XENT, 7), (ADAM, 50)):
        events.append((text, at * MS, (at + ms) * MS))
        at += ms
    return {"/device:TPU:0": {"XLA Ops": events,
                              "XLA Modules": [("jit_step(1)", 0, at * MS)]},
            "/host:CPU": {"python3": [("dispatch", 0, MS)]}}


def reduced_hand():
    return latent_trace.reduce_planes(reduce, hand_planes(), SHAPES)


def test_hand_built_times_by_layer():
    trace = reduced_hand()
    assert trace.busy_s == pytest.approx(0.200)
    assert trace.flash_s == pytest.approx(0.075)
    assert trace.latent_s == pytest.approx(0.009)
    assert trace.experts_s == pytest.approx(0.015)
    assert reader("mla_time_share").read(
        fake_run(trace)) == pytest.approx(42.0)
    assert reader("moe_held_time_share").read(
        fake_run(trace)) == pytest.approx(7.5)


def test_hand_built_roofline_share_counts_the_causal_pairs_alone():
    """12,372,525,711,360 operations in 75 ms of kernels."""
    trace = reduced_hand()
    want = 100 * FLASH_FLOPS / (0.075 * PEAK)
    assert reader("mla_flash_roofline").read(
        fake_run(trace)) == pytest.approx(want)
    # two traced steps: twice the operations for the same time, and an
    # impossible reading is reported, not clipped
    doubled = reader("mla_flash_roofline").read(fake_run(trace, steps=2))
    assert doubled == pytest.approx(2 * want) and doubled > 100


def test_nested_instructions_are_counted_once():
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 10 * MS),
        (ROUTER, 2 * MS, 6 * MS)]}}
    trace = latent_trace.reduce_planes(reduce, planes, SHAPES)
    assert trace.experts_s == pytest.approx(0.004)
    assert trace.busy_s == pytest.approx(0.010)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_these_layers_leaves_the_metric_out(metric):
    """A program with neither layer, an untraced run, a family without
    ``trace_shapes`` (any other cell): no reading, no error."""
    planes = {"/device:TPU:0": {"XLA Ops": [(HEAD, 0, MS), (XENT, MS,
                                                         2 * MS)]}}
    dense = latent_trace.reduce_planes(reduce, planes, SHAPES)
    assert dense.flash_s == dense.latent_s == dense.experts_s == 0.0
    assert reader(metric).read(fake_run(dense)) is None
    untraced = types.SimpleNamespace(
        cell=CELL, reduced_trace=None, peaks=None,
        reader=lambda directory, name: latent_trace)
    assert reader(metric).read(untraced) is None
    other = types.SimpleNamespace(
        cell=types.SimpleNamespace(config={"n_embd": 1024},
                                   family=types.SimpleNamespace()),
        reduced_trace={}, peaks=None,
        reader=lambda directory, name: latent_trace)
    assert reader(metric).read(other) is None


# ------------------------------------------------- the recorded trace
# One step of joyai_llm_flash-spmd-1chip on the v5e (PR 31), cut by
# cut_trace.py: names and times are the chip's.
RECORDED = os.path.join(
    HERE, "fixtures", "joyai_llm_flash-spmd-1chip.1step.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded_planes():
    return reduce.planes_of(reduce.load(RECORDED))


@pytest.fixture(scope="module")
def recorded(recorded_planes):
    return latent_trace.reduce_planes(reduce, recorded_planes, SHAPES)


def test_recorded_planes_and_the_kernels_of_six_recomputed_blocks(
        recorded_planes):
    assert {p: {line: len(events) for line, events in lines.items()}
            for p, lines in recorded_planes.items()} == {
        "/device:TPU:0": {"XLA Modules": 1, "XLA Ops": 5322,
                          "Async XLA Ops": 1828},
        "/host:CPU": {"python3": 34}}
    ops = [t for t, _, _ in recorded_planes["/device:TPU:0"]["XLA Ops"]]
    flash = [t for t in ops if reduce.PALLAS_TARGET in t
             and "[128,4096,192]" in t]
    # six blocks: the forward kernel twice each (recomputation), dq and
    # dk/dv once; the forward is found by its operands alone
    results = sorted(reduce.parse(t)[2].split("{")[0] for t in flash)
    assert results == (["(bf16[128,4096,128]"] * 12
                       + ["(bf16[128,4096,192]"] * 6
                       + ["bf16[128,4096,192]"] * 6)
    # five expert layers: three grouped products forward, three
    # recomputed, six gradients
    assert len([t for t in ops if t.startswith("%ragged-dot-none")]) == 60
    assert all("bf16[16,2048,768]" in t or "bf16[16,768,2048]" in t
               for t in ops if t.startswith("%ragged-dot-none"))


def test_recorded_times_by_layer(recorded):
    assert recorded.busy_s == pytest.approx(0.850599613, rel=1e-9)
    assert recorded.flash_s == pytest.approx(0.22898173, rel=1e-6)
    assert recorded.latent_s == pytest.approx(0.182086666, rel=1e-6)
    assert recorded.experts_s == pytest.approx(0.239326616, rel=1e-6)


@pytest.mark.parametrize("metric,want", [
    ("mla_time_share", 100 * (0.22898173 + 0.182086666) / 0.850599613),
    ("mla_flash_roofline", 100 * FLASH_FLOPS / (0.22898173 * PEAK)),
    ("moe_held_time_share", 100 * 0.239326616 / 0.850599613),
])
def test_readers_on_the_recorded_trace(metric, want, recorded):
    """Latent attention 48.3% of the step, its kernels at 27.4% of the
    peak, the expert layers that hold a sixteenth of the experts 28.1%."""
    got = reader(metric).read(fake_run(recorded))
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got < 100


def test_the_optimizer_pass_over_a_latent_weight_alone_is_not_the_layers(
        recorded_planes):
    """An update fused with its weight-gradient product takes an
    activation of latent attention as an operand and counts; the pass
    over ``f32[1536,32,192]`` with no such operand is the optimizer's."""
    ops = [t for t, _, _ in recorded_planes["/device:TPU:0"]["XLA Ops"]]
    weight = [t for t in ops if "f32[1536,32,192]" in t
              and not any(s in t for s in SHAPES["latent"])]
    assert weight
    counted = latent_trace.reduce_planes(
        reduce, {"/device:TPU:0": {"XLA Ops": [
            (t, i * MS, (i + 1) * MS) for i, t in enumerate(weight)]}},
        SHAPES)
    assert counted.latent_s == counted.flash_s == counted.experts_s == 0.0


def test_readers_through_the_file_as_a_run_finds_it(tmp_path):
    """``latent_trace.read`` globs the profiler's directory of the cell
    under ``<root>/.bench_trace`` and parses once for the three readers."""
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
    share = reader("mla_time_share").read(run)
    parsed = run.latent_trace
    roofline = reader("mla_flash_roofline").read(run)
    held = reader("moe_held_time_share").read(run)
    assert run.latent_trace is parsed
    assert share == pytest.approx(48.327, rel=1e-4)
    assert roofline == pytest.approx(27.428, rel=1e-4)
    assert held == pytest.approx(28.136, rel=1e-4)
