"""The readers of ``moe_time_share`` and ``moe_gmm_roofline``: the rule
that finds the expert layer's instructions (``benchmark/moe_trace.py``)
on a hand-built case whose answers are known, and on one step cut from
the ``olmoe_1b_7b-spmd-1chip`` trace recorded on the v5e in PR 27."""

import os
import types

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
moe_trace = load_by_path(os.path.join(BENCH, "moe_trace.py"),
                         "hvd_benchmark_moe_trace")
CONFIG = load_json(os.path.join(REPO, "benchmark", "configs",
                                "olmoe_1b_7b.json"))
FAMILY = load_by_path(os.path.join(BENCH, "models", "olmoe_lm.py"),
                      "hvd_benchmark_moe_trace_olmoe_lm")
CELL = types.SimpleNamespace(config=CONFIG, job=CONFIG["job"],
                             family=FAMILY)
MS = 1_000_000  # ns
PEAK = 197e12


def reader(name):
    return load_by_path(os.path.join(BENCH, "layer_metrics", name + ".py"),
                        "hvd_benchmark_reader_" + name)


def fake_run(trace, steps=1):
    """A run whose trace is parsed already (``moe_trace.read`` keeps
    what it parsed on ``run``)."""
    trace.steps = steps
    return types.SimpleNamespace(
        cell=CELL, reduced_trace={}, moe_trace=trace, devices=[0],
        measured={"traced_steps": steps},
        peaks={"bf16_flops_per_s": PEAK},
        reader=lambda directory, name: moe_trace)


def test_the_two_shapes_come_from_the_cell():
    assert moe_trace.shapes_of(CELL) == ("[131072", "[16384,64]")
    small = types.SimpleNamespace(
        config=dict(num_experts_per_tok=2, num_experts=8),
        job=dict(per_chip_batch=2, seq_len=32))
    assert moe_trace.shapes_of(small) == ("[128", "[64,8]")


# One chip, one step, times in ms.  The expert layer: a grouped product
# and its metadata kernel (by name), a sort of the token-slots, the
# dispatch gather (a fusion with the slots' shape as an OPERAND), the
# router's softmax (by the router's shape): 20 + 1 + 2 + 3 + 4 = 30.
# Not the expert layer: the head's fusion, a flash kernel, and the
# optimizer's pass over the experts' weights.
GMM = ('%ragged-dot-none.3 = bf16[131072,1024]{1,0:T(8,128)(2,1)} '
       'custom-call(s32[1]{0} %a, bf16[131072,2048]{1,0} %rows, '
       'bf16[64,2048,1024]{2,1,0} %w), custom_call_target="tpu_custom_call"')
GMM_GRAD = ('%ragged-dot-none = bf16[64,2048,1024]{2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[131072,2048]{1,0} %rows, bf16[131072,1024]{1,0}'
            ' %g), custom_call_target="tpu_custom_call"')
METADATA = ('%ragged-dot-metadata = (s32[65]{0}, s32[319]{0}, s32[319]{0}, '
            's32[1]{0}) custom-call(s32[64]{0} %sizes), '
            'custom_call_target="tpu_custom_call"')
SORT = ("%sort.1 = (s32[131072]{0:T(1024)}, s32[131072]{0:T(1024)S(1)}) "
        "sort(s32[131072]{0} %ids, s32[131072]{0} %iota), dimensions={0}")
GATHER = ("%fusion.7 = bf16[16384,2048]{1,0:T(8,128)(2,1)} fusion("
          "bf16[131072,2048]{1,0} %y, s32[131072]{0} %inverse), kind=kLoop")
ROUTER = ("%fusion.9 = f32[16384,64]{0,1:T(8,128)} fusion(f32[16384,64]{0,1}"
          " %logits), kind=kLoop")
HEAD = ("%fusion.11 = bf16[16384,50304]{1,0:T(8,128)(2,1)} fusion("
        "bf16[16384,2048]{1,0} %x, bf16[2048,50304]{1,0} %w), kind=kOutput")
FLASH = ('%attn.2 = (bf16[64,4096,128]{2,1,0}, f32[64,4096,128]{2,1,0}) '
         'custom-call(bf16[64,4096,128]{2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')
ADAM = ("%fusion.20 = (f32[64,2048,1024]{2,1,0}, f32[64,2048,1024]{2,1,0}) "
        "fusion(f32[64,2048,1024]{2,1,0} %w, bf16[64,2048,1024]{2,1,0} %g),"
        " kind=kLoop")


def hand_planes():
    events, at = [], 0
    for text, ms in ((HEAD, 40), (GMM, 12), (GMM_GRAD, 8), (METADATA, 1),
                     (SORT, 2), (GATHER, 3), (ROUTER, 4), (FLASH, 10),
                     (ADAM, 20)):
        events.append((text, at * MS, (at + ms) * MS))
        at += ms
    return {"/device:TPU:0": {"XLA Ops": events,
                              "XLA Modules": [("jit_step(1)", 0, at * MS)]},
            "/host:CPU": {"python3": [("dispatch", 0, MS)]}}


def reduced_hand():
    return moe_trace.reduce_planes(reduce, hand_planes(),
                                   moe_trace.shapes_of(CELL))


def test_hand_built_expert_layer_time():
    trace = reduced_hand()
    assert trace.busy_s == pytest.approx(0.100)
    assert trace.gmm_s == pytest.approx(0.021)   # 12 + 8 + 1
    assert trace.moe_s == pytest.approx(0.030)   # + 2 + 3 + 4
    assert reader("moe_time_share").read(
        fake_run(trace)) == pytest.approx(30.0)


def test_hand_built_roofline_share_counts_required_rows_alone():
    """4,947,802,324,992 operations in 21 ms of kernels."""
    trace = reduced_hand()
    want = 100 * 4_947_802_324_992 / (0.021 * PEAK)
    assert want > 100  # an impossible reading is reported, not clipped
    assert reader("moe_gmm_roofline").read(
        fake_run(trace)) == pytest.approx(want)
    # two traced steps: twice the operations for the same time
    assert reader("moe_gmm_roofline").read(
        fake_run(trace, steps=2)) == pytest.approx(2 * want)


def test_nested_instructions_are_counted_once():
    """Self time: a while that holds a sort keeps what the sort does
    not cover, and only the sort is the expert layer's."""
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 10 * MS),
        (SORT, 2 * MS, 6 * MS)]}}
    trace = moe_trace.reduce_planes(reduce, planes,
                                    moe_trace.shapes_of(CELL))
    assert trace.moe_s == pytest.approx(0.004)
    assert trace.busy_s == pytest.approx(0.010)


@pytest.mark.parametrize("metric", ["moe_time_share", "moe_gmm_roofline"])
def test_a_program_without_an_expert_layer_leaves_the_metric_out(metric):
    """The parent of the PR that brought the layer, a dense cell, an
    untraced run: no reading, no error."""
    planes = {"/device:TPU:0": {"XLA Ops": [(HEAD, 0, MS), (FLASH, MS,
                                                          2 * MS)]}}
    dense = moe_trace.reduce_planes(reduce, planes,
                                    moe_trace.shapes_of(CELL))
    assert dense.moe_s == dense.gmm_s == 0.0
    assert reader(metric).read(fake_run(dense)) is None
    untraced = types.SimpleNamespace(
        cell=CELL, reduced_trace=None, peaks=None,
        reader=lambda directory, name: moe_trace)
    assert reader(metric).read(untraced) is None
    gpt2 = types.SimpleNamespace(
        cell=types.SimpleNamespace(config={"n_embd": 1024}),
        reduced_trace={}, peaks=None,
        reader=lambda directory, name: moe_trace)
    assert reader(metric).read(gpt2) is None


# ------------------------------------------------- the recorded trace
# One step of olmoe_1b_7b-spmd-1chip on the v5e (PR 27), cut by
# cut_trace.py: names and times are the chip's.
RECORDED = os.path.join(
    HERE, "fixtures", "olmoe_1b_7b-spmd-1chip.1step.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded_planes():
    return reduce.planes_of(reduce.load(RECORDED))


@pytest.fixture(scope="module")
def recorded(recorded_planes):
    return moe_trace.reduce_planes(reduce, recorded_planes,
                                   moe_trace.shapes_of(CELL))


def test_recorded_planes_and_the_grouped_products_names(recorded_planes):
    assert {p: {line: len(events) for line, events in lines.items()}
            for p, lines in recorded_planes.items()} == {
        "/device:TPU:0": {"XLA Modules": 1, "XLA Ops": 507,
                          "Async XLA Ops": 172},
        "/host:CPU": {"python3": 34}}
    ops = [t for t, _, _ in recorded_planes["/device:TPU:0"]["XLA Ops"]]
    products = [t for t in ops if t.startswith("%ragged-dot-none")]
    # three forward, six gradients: the compiler's own Mosaic kernels
    assert len(products) == 9
    assert all('custom_call_target="tpu_custom_call"' in t
               and 'ragged_dot_tiling="512,512,512"' in t for t in products)
    assert sorted(reduce.parse(t)[2].split("{")[0] for t in products) == [
        "bf16[131072,1024]"] * 3 + ["bf16[131072,2048]"] * 3 + [
        "bf16[64,1024,2048]"] + ["bf16[64,2048,1024]"] * 2
    assert len([t for t in ops if t.startswith("%ragged-dot-metadata")]) == 2
    # the two sorts of the 131,072 token-slots and top-k's sort
    assert len([t for t in ops if " sort(" in t and "[131072]" in t]) == 2
    assert len([t for t in ops if " sort(" in t and "[16384,64]" in t]) == 1


def test_recorded_expert_layer_time(recorded):
    assert recorded.busy_s == pytest.approx(0.348327733, rel=1e-9)
    assert recorded.gmm_s == pytest.approx(0.045796519, rel=1e-6)
    assert recorded.moe_s == pytest.approx(0.070942012, rel=1e-6)


@pytest.mark.parametrize("metric,want", [
    ("moe_time_share", 100 * 0.070942012 / 0.348327733),
    ("moe_gmm_roofline", 100 * 4_947_802_324_992 / (0.045796519 * PEAK)),
])
def test_readers_on_the_recorded_trace(metric, want, recorded):
    """20.4% of the step; the grouped products at 54.8% of the peak."""
    got = reader(metric).read(fake_run(recorded))
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got < 100


def test_the_optimizer_pass_over_the_experts_is_not_the_layers(
        recorded_planes):
    """15.8 ms of Adam over ``f32[64,2048,1024]`` and its twin: found by
    no rule here, as in the dense cells."""
    ops = recorded_planes["/device:TPU:0"]["XLA Ops"]
    adam = [(t, e - s) for t, s, e in ops
            if t.startswith("%fusion") and reduce.parse(t)[2].startswith(
                "(f32[64,") and "params__block_0____moe" in t]
    assert len(adam) == 3
    assert sum(ns for _, ns in adam) / 1e9 == pytest.approx(0.0158, rel=0.01)
    shapes = moe_trace.shapes_of(CELL)
    assert not any(moe_trace.GROUPED_PRODUCT.match(t)
                   or any(shape in t for shape in shapes) for t, _ in adam)


def test_readers_through_the_file_as_a_run_finds_it(tmp_path):
    """``moe_trace.read`` globs the profiler's directory of the cell
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
    share = reader("moe_time_share").read(run)
    parsed = run.moe_trace
    roofline = reader("moe_gmm_roofline").read(run)
    assert run.moe_trace is parsed
    assert share == pytest.approx(20.3665, rel=1e-4)
    assert roofline == pytest.approx(54.842, rel=1e-4)
