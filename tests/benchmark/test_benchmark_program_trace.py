"""The six readers of the eager plane's own record
(``benchmark/program_trace.py`` and ``layer_metrics/eager_*.py``): on a
hand-built case whose answers are known, on a trace the CPU backend
writes here, and on one step of ``resnet50_v15-eager-1chip`` recorded
on the v5e in PR 24."""

import os
import time
import types

import pytest

from benchmark_toy import BENCH, HERE, load_by_path

from horovod_tpu.utils import trace

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
program = load_by_path(os.path.join(BENCH, "program_trace.py"),
                       "hvd_benchmark_program_trace")
MS = 1_000_000  # ns
SPAN_METRICS = ["eager_jax_dispatch_us", "eager_core_stall_ms",
                "eager_idle_in_execute_ms"]
COUNTER_METRICS = ["eager_submit_us", "eager_queue_wait_ms",
                   "eager_execute_us"]


def read(metric, run):
    return load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric).read(run)


def fake_run(program_trace=None, reduced_trace=True, root=None, **measured):
    """What a reader is handed; ``program_trace`` as though the
    profiler's file had been parsed already."""
    return types.SimpleNamespace(
        reduced_trace=reduced_trace, measured=measured,
        program_trace=program_trace,
        cell=types.SimpleNamespace(root=root, name="toy", bench=BENCH),
        reader=lambda directory, name: load_by_path(
            os.path.join(BENCH, directory, name + ".py"),
            "hvd_benchmark_" + name))


# One chip, two steps.  Times in ms.  The chip works 0-10, 14-16, 30-40
# and 44-50, so it idles 10-14, 16-30 and 40-44.  The loop's exchanges
# run 5-20 and 35-48.  The dispatcher sits in the core 0-6, 12-13,
# 18-36 and 47-60, of which 5-6, 12-13, 18-20, 35-36 and 47-48 lie
# inside an exchange: 6 ms, 3 a step.  It executes 6-12, 13-18 and
# 36-47, over the idle 10-12, 13-14, 16-18 and 40-44: 9 ms, 4.5 a step.
# Its launches take 1, 1.5 and 2 ms.
OP = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
HAND = {
    "/device:TPU:0": {
        "XLA Ops": [(OP, 0, 10 * MS), (OP, 14 * MS, 16 * MS),
                    (OP, 30 * MS, 40 * MS), (OP, 44 * MS, 50 * MS)],
        "XLA Modules": [("jit_fused(1)", 14 * MS, 16 * MS)],
    },
    "/host:CPU": {
        "python3": [
            ("enqueue", 5 * MS, 8 * MS), ("synchronize", 8 * MS, 20 * MS),
            ("enqueue", 35 * MS, 37 * MS),
            ("synchronize", 37 * MS, 48 * MS),
            ("hvd.submit", 5 * MS, 6 * MS), ("hvd.wait", 8 * MS, 20 * MS),
            ("hvd.wait_batch", 0, 6 * MS),
            ("hvd.wait_batch", 12 * MS, 13 * MS),
            ("hvd.wait_batch", 18 * MS, 36 * MS),
            ("hvd.wait_batch", 47 * MS, 60 * MS),
            ("hvd.execute", 6 * MS, 12 * MS),
            ("hvd.execute", 13 * MS, 18 * MS),
            ("hvd.execute", 36 * MS, 47 * MS),
            ("hvd.exec.launch", 7 * MS, 8 * MS),
            ("hvd.exec.launch", 14 * MS, 15.5 * MS),
            ("hvd.exec.launch", 38 * MS, 40 * MS),
            ("unrelated", 0, 60 * MS)],
    },
}


def hand():
    return program.reduce_planes(reduce, HAND)


def test_hand_built_spans_exchanges_and_idle():
    out = hand()
    assert sorted(out.spans) == ["hvd.exec.launch", "hvd.execute",
                                 "hvd.submit", "hvd.wait", "hvd.wait_batch"]
    assert out.exchanges == [(5 * MS, 20 * MS), (35 * MS, 48 * MS)]
    assert out.idle == [[[10 * MS, 14 * MS], [16 * MS, 30 * MS],
                         [40 * MS, 44 * MS]]]
    assert out.overlap([(0, 4), (2, 6), (10, 12)], [(3, 11)]) == 4


@pytest.mark.parametrize("metric,want", [
    ("eager_jax_dispatch_us", 1500.0),
    # only the part of a wait that lies inside an exchange
    ("eager_core_stall_ms", 3.0),
    # only the idle time that lies under an execute span
    ("eager_idle_in_execute_ms", 4.5),
])
def test_span_readers_on_the_hand_built_case(metric, want):
    assert read(metric, fake_run(hand())) == pytest.approx(want)


def test_the_chip_that_idles_most_under_execute_counts():
    planes = dict(HAND)
    planes["/device:TPU:1"] = {"XLA Ops": [(OP, 0, 50 * MS)]}
    out = program.reduce_planes(reduce, planes)
    assert out.idle[1] == []
    assert read("eager_idle_in_execute_ms",
                fake_run(out)) == pytest.approx(4.5)


def test_a_trailing_enqueue_without_its_synchronize_is_no_exchange():
    """A trace cut inside a step, as ``cut_trace.py`` leaves one."""
    planes = {"/host:CPU": {"python3": HAND["/host:CPU"]["python3"] + [
        ("enqueue", 70 * MS, 72 * MS)]}}
    out = program.reduce_planes(reduce, planes)
    assert len(out.exchanges) == 2 and out.idle == []


# A window from 1,000 to 2,000 us.  Response 1 carries two requests,
# response 2 one; request 4 was handed in before the window and
# request 5 finished after it.
WINDOW = [("enqueue", 1_000_000, 1_100_000),
          ("synchronize", 1_100_000, 2_000_000)]
LOG = [(1, 1, 1_000_000, 1_010_000, 1_100_000, 1_200_000),
       (2, 1, 1_005_000, 1_025_000, 1_100_000, 1_300_000),
       (3, 2, 1_500_000, 1_530_000, 1_700_000, 1_800_000),
       (4, 3, 900_000, 950_000, 1_000_000, 1_100_000),
       (5, 4, 1_900_000, 1_910_000, 1_950_000, 2_100_000)]


@pytest.fixture
def log():
    trace.reset()
    trace.LOG.extend(LOG)
    yield
    trace.reset()


def test_the_log_is_cut_to_the_measured_window(log):
    run = fake_run(window_spans=WINDOW)
    assert [r[0] for r in program.request_log(run)] == [1, 2, 3]
    assert program.request_log(fake_run()) == []


@pytest.mark.parametrize("metric,want", [
    ("eager_submit_us", (10 + 20 + 30) / 3),
    ("eager_queue_wait_ms", 0.090),          # of 90, 75 and 170 us
    ("eager_execute_us", (200 + 100) / 2),   # by response, to its last
])
def test_counter_readers_on_a_known_log(metric, want, log):
    assert read(metric, fake_run(window_spans=WINDOW)) == pytest.approx(want)


@pytest.mark.parametrize("metric", COUNTER_METRICS + SPAN_METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    trace.reset()
    # an untraced run, and a trace of a program without the spans
    assert read(metric, fake_run(reduced_trace=None)) is None
    bare = program.reduce_planes(reduce, {
        "/device:TPU:0": HAND["/device:TPU:0"],
        "/host:CPU": {"python3": HAND["/host:CPU"]["python3"][:4]}})
    assert read(metric, fake_run(bare, window_spans=WINDOW)) is None


# --------------------------------------------- a trace written here
@pytest.fixture(scope="module")
def cpu_run(hvd, tmp_path_factory):
    """Eight ranks, two tensors each, inside spans named as the eager
    loop names its own, under ``jax.profiler`` on the CPU backend."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.common import basics

    def exchange(rank):
        handles = [hvd.allreduce_async(
            jnp.full((4,), float(rank)), op=hvd.Sum,
            name=f"program_trace.{i}") for i in range(2)]
        return [hvd.synchronize(h) for h in handles]

    def step(_):
        with jax.profiler.TraceAnnotation("enqueue"):
            pass
        with jax.profiler.TraceAnnotation("synchronize"):
            basics.run_parallel(exchange)

    step(0)  # compiles
    root = tmp_path_factory.mktemp("program_trace")
    start = time.perf_counter_ns()
    jax.profiler.start_trace(str(root / ".bench_trace" / "toy"))
    for i in range(2):
        step(i)
    time.sleep(0.3)  # the dispatcher leaves its last hvd.execute
    jax.profiler.stop_trace()
    return fake_run(root=str(root), window_spans=[
        ("enqueue", start, time.perf_counter_ns())])


def test_the_profilers_file_is_found_and_parsed_once(cpu_run):
    out = program.read(cpu_run)
    assert program.read(cpu_run) is out
    assert len(out.exchanges) == 2
    assert len(out.spans["hvd.submit"]) == len(out.spans["hvd.wait"]) == 32
    assert 2 <= len(out.spans["hvd.execute"]) <= 4
    # no device plane on this backend
    assert out.idle == []


def test_readers_on_the_cpu_trace_and_the_live_log(cpu_run):
    from horovod_tpu.common import basics

    assert len(program.request_log(cpu_run)) == 32
    for metric in COUNTER_METRICS + ["eager_jax_dispatch_us"]:
        assert read(metric, cpu_run) > 0
    assert read("eager_idle_in_execute_ms", cpu_run) is None
    stall = read("eager_core_stall_ms", cpu_run)
    if type(basics._get_state().controller).__name__ == "NativeController":
        assert stall >= 0
    else:  # the Python controller has no core to wait in
        assert stall is None


# ------------------------------------------------- the recorded trace
# The traced steps of resnet50_v15-eager-1chip on the v5e (PR 24), cut
# by cut_trace.py to the first step's 323 launches (forward + backward,
# then fuse + fused for each of 161 gradients).  cut_trace.py cuts the
# device's lines; the host's ``python3`` lines, the caller's and the
# dispatcher's, are whole: all five steps.  Names and times are the
# chip's.
RECORDED = os.path.join(
    HERE, "fixtures", "resnet50_v15-eager-1chip.1step.xplane.pb.gz")
TABLE = {"hvd.submit": 805, "hvd.wait": 805, "hvd.wait_batch": 635,
         "hvd.decode": 636, "hvd.execute": 805, "hvd.exec.fuse_in": 805,
         "hvd.exec.stack": 805, "hvd.exec.lookup": 805,
         "hvd.exec.launch": 805, "hvd.exec.complete": 805,
         "hvd.mark_done": 636}


@pytest.fixture(scope="module")
def recorded_planes():
    return reduce.planes_of(reduce.load(RECORDED))


@pytest.fixture(scope="module")
def recorded(recorded_planes):
    return program.reduce_planes(reduce, recorded_planes)


def test_recorded_fixture_is_smaller_than_the_first():
    first = os.path.join(HERE, "fixtures",
                         "gpt2_medium-spmd-1chip.2steps.xplane.pb.gz")
    assert os.path.getsize(RECORDED) < os.path.getsize(first)


def test_recorded_spans_are_the_table(recorded_planes, recorded):
    assert {plane: sorted(lines) for plane, lines
            in recorded_planes.items()} == {
        "/device:TPU:0": ["Async XLA Ops", "XLA Modules", "XLA Ops"],
        "/host:CPU": ["python3"]}
    assert len(recorded_planes["/device:TPU:0"]["XLA Modules"]) == 323
    # five steps of 161 requests, each its own response (the cell runs
    # with fusion off); a batch of the core holds one or more
    assert {name: len(spans) for name, spans
            in recorded.spans.items()} == TABLE
    assert len(recorded.exchanges) == 5


def test_recorded_spans_are_on_the_device_traces_clock(recorded_planes,
                                                       recorded):
    (window,) = [(s, e) for name, s, e
                 in recorded_planes["/host:CPU"]["python3"]
                 if name == "traced_window"]
    ops = recorded_planes["/device:TPU:0"]["XLA Ops"]
    assert window[0] < min(s for _, s, _ in ops) < window[1]
    for spans in recorded.spans.values():
        assert all(window[0] <= start <= window[1] for start, _ in spans)


def test_recorded_exec_spans_lie_inside_an_execute_span(recorded):
    executes = sorted(recorded.spans["hvd.execute"])
    for name, spans in recorded.spans.items():
        if name.startswith("hvd.exec."):
            assert recorded.overlap(spans, executes) == sum(
                e - s for s, e in spans), name


def test_recorded_first_launch_of_a_step_waits_for_the_backward_pass(
        recorded):
    """What the chip's trace showed (PERF.md, PR 24): a launch costs
    141 us, but one in every step, the 32nd program launched behind the
    running forward + backward, blocks for 77-79 ms until the device is
    through with it."""
    import statistics

    launches = sorted(e - s for s, e in recorded.spans["hvd.exec.launch"])
    assert statistics.median(launches) == pytest.approx(140_933, rel=1e-3)
    assert all(t < 2 * MS for t in launches[:-5])
    assert all(70 * MS < t < 100 * MS for t in launches[-5:])


@pytest.mark.parametrize("metric,want", [
    ("eager_jax_dispatch_us", 652.3128732919254),
    ("eager_core_stall_ms", 1.6403424),
    # the device's lines hold one step of five: 103.66 ms of its idle
    # time lie under an execute span, over the five exchanges
    ("eager_idle_in_execute_ms", 20.7321122),
])
def test_span_readers_on_the_recorded_trace(metric, want, recorded):
    assert read(metric, fake_run(recorded)) == pytest.approx(want, rel=1e-9)


def test_the_cell_still_launches_322_programs_an_exchange(recorded_planes):
    """The older reader on the new fixture: one exchange's launches
    over the host's five."""
    reduced = reduce.reduce_planes(
        recorded_planes, span_names={"enqueue", "synchronize"})
    run = fake_run(reduced_trace=reduced)
    assert read("eager_launches_per_step", run) == pytest.approx(322 / 5)
