"""The reduction from a trace to busy / idle, categories, exposed
collective time, launches and attributed gaps: on a hand-built case
whose answers are known, through the profiler's own file format, and
on a trace recorded on the v5e in PR 22."""

import os
import types

import pytest

from benchmark_toy import BENCH, HERE, load_by_path

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
MS = 1_000_000  # ns

# One chip, two steps.  Times in ms:
#   0-10   fusion.1            10-14  flash_fwd kernel (a custom call)
#   14-20  all-reduce.1        16-18  fusion.2 (runs under the all-reduce)
#   20-30  idle (host: fetch_loss 19-31)
#   30-40  fusion.1            40-44  flash_fwd   44-50 all-reduce.1
#   46-48  fusion.2
# and a while loop 50-60 that holds two bodies of 4 ms each.  Events are
# named as the v5e's trace names them: by the instruction's whole text.
FUSION_1 = "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p), kind=kOutput"
FUSION_2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %pallas_call.3), kind=kLoop"
FLASH = ('%attn.7 = (bf16[8,64]{1,0:T(8,128)(2,1)}, f32[8,128]{1,0}) '
         'custom-call(bf16[8,64]{1,0} %q), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
ALL_REDUCE = ("%all-reduce.1 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0} "
              "%fusion.1), replica_groups={{0,1,2,3}}, to_apply=%add")
WHILE = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
BODY = "%body_fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
HAND = {
    "/device:TPU:0": {
        "XLA Ops": [
            (FUSION_1, 0, 10 * MS), (FLASH, 10 * MS, 14 * MS),
            (ALL_REDUCE, 14 * MS, 20 * MS),
            (FUSION_2, 16 * MS, 18 * MS),
            (FUSION_1, 30 * MS, 40 * MS), (FLASH, 40 * MS, 44 * MS),
            (ALL_REDUCE, 44 * MS, 50 * MS),
            (FUSION_2, 46 * MS, 48 * MS),
            (WHILE, 50 * MS, 60 * MS),
            (BODY, 51 * MS, 55 * MS),
            (BODY, 55 * MS, 59 * MS),
        ],
        "XLA Modules": [("jit_step(1)", 0, 20 * MS),
                        ("jit_step(1)", 30 * MS, 60 * MS)],
        "Steps": [("0", 0, 60 * MS)],
    },
    "/host:CPU": {
        "python": [("dispatch", 0, 1 * MS), ("dispatch", 1 * MS, 2 * MS),
                   ("fetch_loss", 19 * MS, 31 * MS),
                   ("unrelated", 0, 60 * MS)],
    },
}


def reduced_hand():
    return reduce.reduce_planes(
        HAND, span_names={"dispatch", "fetch_loss"})


def test_instruction_text_is_parsed():
    assert reduce.parse(FLASH) == (
        "attn.7", "custom-call",
        "(bf16[8,64]{1,0:T(8,128)(2,1)}, f32[8,128]{1,0})")
    assert reduce.parse(FUSION_1)[:2] == ("fusion.1", "fusion")
    assert reduce.parse("jit_step(1)") == ("jit_step(1)", "jit_step(1)", "")
    # a kernel's output among a fusion's operands does not make it one
    assert [reduce.category(t) for t in (
        FLASH, FUSION_2, ALL_REDUCE, WHILE)] == [
        "tpu_custom_call", "other", "collective", "other"]
    start = ALL_REDUCE.replace("all-reduce", "all-reduce-start")
    assert reduce.category(start) == "collective"


def test_interval_arithmetic():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert reduce.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert reduce.subtract([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]
    assert reduce.measure([[0, 3], [5, 8]]) == 6
    assert reduce.base_name("fusion.12.3") == "fusion"


@pytest.mark.parametrize("key,want", [
    ("window_s", 0.060), ("busy_s", 0.050)])
def test_hand_built_busy_and_window(key, want):
    assert reduced_hand()[key] == pytest.approx(want)


def test_hand_built_categories_and_self_time():
    device = reduced_hand()["devices"][0]
    assert device["category_s"]["tpu_custom_call"] == pytest.approx(0.008)
    # self time: the all-reduce keeps what the fusion under it does not
    # cover, so the categories add up to the busy time
    assert device["category_s"]["collective"] == pytest.approx(0.008)
    # the while keeps only what its bodies do not cover
    assert device["name_s"]["while"] == pytest.approx(0.002)
    assert device["name_s"]["body_fusion"] == pytest.approx(0.008)
    assert device["name_s"]["fusion"] == pytest.approx(0.024)
    assert sum(device["category_s"].values()) == pytest.approx(0.050)


def test_hand_built_exposed_collective_time():
    """6 ms of all-reduce a step, 2 of them under a fusion."""
    device = reduced_hand()["devices"][0]
    assert device["collective_s"] == pytest.approx(0.012)
    assert device["collective_exposed_s"] == pytest.approx(0.008)


def test_hand_built_gap_goes_to_the_span_that_covers_it():
    out = reduced_hand()
    assert out["idle_gaps"] == [["fetch_loss", pytest.approx(0.010)]]
    assert out["top_ops"][0] == [
        "fusion bf16[8,128]{1,0:T(8,128)(2,1)}", pytest.approx(0.020)]
    assert ["attn custom-call (bf16[8,64]{1,0:T(8,128)(2,1)}, f32[8,128]{1,0})",
            pytest.approx(0.008)] in out["top_ops"]
    assert [n for _, _, n in out["devices"][0]["launches"]] == [
        "jit_step(1)"] * 2


def test_a_trace_without_device_planes_reduces_to_nothing():
    out = reduce.reduce_planes({"/host:CPU": HAND["/host:CPU"]},
                               span_names={"dispatch"})
    assert out["devices"] == [] and out["busy_s"] == 0.0


TEXT_PROTO = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2
    name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(7)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 4000000 } }
  lines { id: 2 name: "main" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fetch_loss" } }
  event_metadata { key: 2 value { id: 2 name: "dispatch" } } }
"""


def test_through_the_profilers_own_format(tmp_path):
    """4 us of fusion, a 2 us gap under ``fetch_loss``, 2 us of
    all-reduce with nothing beside it."""
    from jax.profiler import ProfileData

    path = str(tmp_path / "hand.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT_PROTO))
    out = reduce.reduce_file(path, span_names={"fetch_loss", "dispatch"})
    # two host threads of one name: both lines' spans are read
    assert sorted(n for _, _, n in out["host_spans"]) == [
        "dispatch", "fetch_loss"]
    assert out["window_s"] == pytest.approx(8e-6)
    assert out["busy_s"] == pytest.approx(6e-6)
    assert out["devices"][0]["collective_exposed_s"] == pytest.approx(2e-6)
    assert out["idle_gaps"] == [["fetch_loss", pytest.approx(2e-6)]]
    assert len(out["devices"][0]["launches"]) == 1


def fake_run(reduced, **measured):
    return types.SimpleNamespace(
        reduced_trace=reduced, measured=dict(traced_steps=2, **measured),
        notes={}, programs={}, peaks=None)


@pytest.mark.parametrize("metric,want", [
    ("device_idle_share", 100 * 10 / 60),
    ("pallas_time_share", 100 * 8 / 50),
    ("collective_exposed_ms", 4.0),
])
def test_trace_readers_on_the_hand_built_case(metric, want):
    reader = load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric)
    assert reader.read(fake_run(reduced_hand())) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "device_idle_share", "pallas_time_share", "collective_exposed_ms",
    "eager_launches_per_step", "eager_exchange_ms", "step_ms_p50",
    "peak_mem_GiB", "mfu_required"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    reader = load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric)
    assert reader.read(fake_run(None)) is None


# ------------------------------------------------- the recorded trace
# Two steps of gpt2_medium-spmd-1chip on the v5e (PR 22), cut by
# cut_trace.py: names and times are the chip's.
RECORDED = os.path.join(
    HERE, "fixtures", "gpt2_medium-spmd-1chip.2steps.xplane.pb.gz")
SPANS = {"dispatch", "fetch_loss", "traced_window"}


@pytest.fixture(scope="module")
def recorded():
    return reduce.reduce_file(RECORDED, span_names=SPANS)


def test_recorded_planes_and_lines():
    planes = reduce.planes_of(reduce.load(RECORDED))
    assert {p: {line: len(events) for line, events in lines.items()}
            for p, lines in planes.items()} == {
        "/device:TPU:0": {"XLA Modules": 2, "XLA Ops": 14000,
                          "Async XLA Ops": 5540},
        "/host:CPU": {"python3": 44}}
    # an operation is named by its instruction's whole text
    assert all(name.startswith("%") and " = " in name
               for name, _, _ in planes["/device:TPU:0"]["XLA Ops"])


def test_recorded_busy_idle_and_launches(recorded):
    assert recorded["busy_s"] == pytest.approx(0.538000567, rel=1e-9)
    assert recorded["window_s"] == pytest.approx(0.538046959, rel=1e-9)
    launches = recorded["devices"][0]["launches"]
    assert [n for _, _, n in launches] == [
        "jit_per_shard(10343462853030252830)"] * 2
    # one program a step, dispatched ahead: 46 us idle in 538 ms, most
    # of it while the host sat in fetch_loss
    assert recorded["idle_gaps"][0] == [
        "fetch_loss", pytest.approx(4.508e-05, rel=1e-3)]


def test_recorded_categories(recorded):
    device = recorded["devices"][0]
    assert device["category_s"]["tpu_custom_call"] == pytest.approx(
        0.29202345, rel=1e-6)
    assert device["category_s"]["collective"] == 0.0
    assert device["collective_exposed_s"] == 0.0
    assert sum(device["category_s"].values()) == pytest.approx(
        recorded["busy_s"])


def test_recorded_kernels_are_named_after_their_functions(recorded):
    names = recorded["devices"][0]["name_s"]
    assert names["attn"] == pytest.approx(0.277121443, rel=1e-6)
    assert {"ln1", "ln2", "ln_f", "jvp__", "transpose_jvp___"} <= set(names)
    assert recorded["top_ops"][0][0].startswith("attn custom-call (bf16[")


@pytest.mark.parametrize("metric,want", [
    ("device_idle_share", 100 * (1 - 0.538000567 / 0.538046959)),
    ("pallas_time_share", 100 * 0.29202345 / 0.538000567),
    ("collective_exposed_ms", 0.0),
    ("eager_launches_per_step", None),  # no enqueue span in this cell
])
def test_readers_on_the_recorded_trace(metric, want, recorded):
    reader = load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric)
    got = reader.read(fake_run(recorded))
    assert got == (want if want is None else pytest.approx(want, rel=1e-6))


def test_cutting_to_one_launch_keeps_one_step(tmp_path):
    cutter = load_by_path(os.path.join(HERE, "cut_trace.py"),
                          "hvd_benchmark_cut_trace")
    import gzip

    with gzip.open(RECORDED, "rb") as f:
        small = cutter.cut(f.read(), 1)
    path = str(tmp_path / "one.xplane.pb")
    with open(path, "wb") as f:
        f.write(small)
    out = reduce.reduce_file(path, span_names=SPANS)
    assert len(out["devices"][0]["launches"]) == 1
    assert out["busy_s"] == pytest.approx(0.538000567 / 2, rel=1e-3)
