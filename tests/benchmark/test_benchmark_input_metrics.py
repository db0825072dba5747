"""The five readers of the input path's own record
(``benchmark/input_trace.py`` and ``layer_metrics/input_*.py``) on a
hand-built log and hand-built spans whose answers are known, and the
``spmd_hostfed`` loop end to end at the toy size on the CPU mesh."""

import json
import os
import types

import jax
import pytest

from benchmark_toy import (BENCH, RESULT_KEYS, bench, load_by_path,  # noqa: F401
                           toy_root)

from horovod_tpu.utils import trace

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
program = load_by_path(os.path.join(BENCH, "program_trace.py"),
                       "hvd_benchmark_program_trace")
input_trace = load_by_path(os.path.join(BENCH, "input_trace.py"),
                           "hvd_benchmark_input_trace")
MS = 1_000_000  # ns
COUNTER_METRICS = ["input_wait_ms", "input_starved_share",
                   "input_source_ms", "input_put_ms"]
CELL = "resnet50_v15-spmd-hostfed"


def read(metric, run):
    return load_by_path(
        os.path.join(BENCH, "layer_metrics", metric + ".py"),
        "hvd_benchmark_reader_" + metric).read(run)


def fake_run(program_trace=None, reduced_trace=True, **measured):
    return types.SimpleNamespace(
        reduced_trace=reduced_trace, measured=measured,
        program_trace=program_trace,
        cell=types.SimpleNamespace(root=None, name="toy", bench=BENCH),
        reader=lambda directory, name: load_by_path(
            os.path.join(BENCH, directory, name + ".py"),
            "hvd_benchmark_" + name))


# A window from 1,000 to 2,000 ms, four steps.  Batch 1 was taken in
# warm-up, before it; batch 6 is asked for inside it and taken after.
# Of the four between, the loop found nothing staged for batch 2, and
# batch 4's copy was still under way when it was handed over.
WINDOW = [("input_wait", 1_000 * MS, 1_030 * MS),
          ("fetch_loss", 1_900 * MS, 2_000 * MS)]
#       id  bytes  next  host  put   asked  taken  depth  ready
LOG = [(1, 100, 700, 760, 790, 900, 901, 2, True),
       (2, 100, 800, 960, 1_028, 1_000, 1_030, 0, True),
       (3, 100, 1_028, 1_088, 1_128, 1_200, 1_202, 1, True),
       (4, 100, 1_128, 1_198, 1_398, 1_400, 1_404, 1, False),
       (5, 100, 1_398, 1_448, 1_478, 1_600, 1_604, 2, True),
       (6, 100, 1_478, 1_538, 1_568, 1_990, 2_010, 2, True)]


@pytest.fixture
def log():
    trace.reset()
    trace.BATCHES.extend(
        (*r[:2], *(t * MS for t in r[2:7]), *r[7:]) for r in LOG)
    yield
    trace.reset()


def test_the_batch_log_is_cut_to_the_measured_window(log):
    run = fake_run(window_spans=WINDOW, steps=4)
    assert [r[0] for r in input_trace.batch_log(run)] == [2, 3, 4, 5]
    assert input_trace.batch_log(fake_run()) == []


@pytest.mark.parametrize("metric,want", [
    ("input_wait_ms", (30 + 2 + 4 + 4) / 4),    # over the window's steps
    ("input_starved_share", 50.0),              # batches 2 and 4
    ("input_source_ms", (60 + 70) / 2),         # of 160, 60, 70 and 50
    ("input_put_ms", (40 + 68) / 2),            # of 68, 40, 200 and 30
])
def test_counter_readers_on_a_known_log(metric, want, log):
    run = fake_run(window_spans=WINDOW, steps=4)
    assert read(metric, run) == pytest.approx(want)


# One chip, three traced steps: it runs them 5-15, 35-45 and 49-55.
# The loop waits for its batches 2-4, 8-26 and 41-43.  The chip waits
# 3 ms for the first (from the wait's start to its step's), 20 for the
# second (11 under the wait and 9 more before the step that took the
# batch starts: the copy was still under way) and 4 for the third (the
# loop left that wait while the chip still worked, and the chip then
# ran dry until the batch's step): 27, 9 a step.
OP = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
STEPS = [(5 * MS, 15 * MS), (35 * MS, 45 * MS), (49 * MS, 55 * MS)]
HAND = {
    "/device:TPU:0": {
        "XLA Ops": [(OP, start, end) for start, end in STEPS],
        "XLA Modules": [("jit_step(1)", start, end)
                        for start, end in STEPS]},
    "/host:CPU": {
        "python3": [("input_wait", 8 * MS, 26 * MS),
                    ("hvd.data.wait", 2 * MS, 4 * MS),
                    ("hvd.data.wait", 8 * MS, 26 * MS),
                    ("hvd.data.wait", 41 * MS, 43 * MS),
                    ("dispatch", 26 * MS, 27 * MS)],
        "python3 ": [("hvd.data.next", 0, 12 * MS),
                     ("hvd.data.put", 12 * MS, 25 * MS)]},
}


def traced(planes, **measured):
    return fake_run(program.reduce_planes(reduce, planes),
                    reduce.reduce_planes(planes), **measured)


def test_idle_from_a_wait_to_its_step_on_the_hand_built_case():
    run = traced(HAND, traced_steps=3)
    assert sorted(run.program_trace.spans) == [
        "hvd.data.next", "hvd.data.put", "hvd.data.wait"]
    assert read("input_idle_ms", run) == pytest.approx(9.0)


def test_waits_that_pair_with_no_launch_count_what_lies_under_them():
    """A launch more than waits (another program ran in the traced
    window): no wait can be given its step, and the reading is the
    time under the waits alone in which nothing ran."""
    planes = dict(HAND)
    planes["/device:TPU:0"] = dict(
        HAND["/device:TPU:0"], **{"XLA Modules": [
            ("jit_step(1)", start, end) for start, end in STEPS + STEPS[:1]]})
    assert read("input_idle_ms",
                traced(planes, traced_steps=3)) == pytest.approx(13 / 3)


def test_the_chip_that_waits_most_for_its_batch_counts():
    """A second chip whose steps run back to back from the first one's
    start waits 3 ms, for the first batch alone."""
    planes = dict(HAND)
    planes["/device:TPU:1"] = {
        "XLA Ops": [(OP, 5 * MS, 55 * MS)],
        "XLA Modules": [("jit_step(1)", 5 * MS, 20 * MS),
                        ("jit_step(1)", 20 * MS, 45 * MS),
                        ("jit_step(1)", 45 * MS, 55 * MS)]}
    assert read("input_idle_ms",
                traced(planes, traced_steps=3)) == pytest.approx(9.0)
    del planes["/device:TPU:0"]
    assert read("input_idle_ms",
                traced(planes, traced_steps=3)) == pytest.approx(1.0)


@pytest.mark.parametrize("metric", COUNTER_METRICS + ["input_idle_ms"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric, monkeypatch):
    """An empty log, a loop that never entered the input path, an
    untraced run, a trace without the spans, and a program from before
    the log: the metric is left out of the line."""
    trace.reset()
    assert read(metric, fake_run(program.NOTHING, window_spans=WINDOW,
                                 steps=4)) is None
    assert read(metric, fake_run(reduced_trace=None)) is None
    bare = {"/device:TPU:0": HAND["/device:TPU:0"],
            "/host:CPU": {"python3": HAND["/host:CPU"]["python3"][:1]}}
    assert read(metric, traced(bare, window_spans=WINDOW, steps=4,
                               traced_steps=3)) is None
    monkeypatch.delattr(trace, "BATCHES")
    assert read(metric, traced(bare, window_spans=WINDOW, steps=4,
                               traced_steps=3)) is None


# ----------------------------------------- the loop, at the toy size
@pytest.fixture(scope="module")
def hostfed(bench, toy_root):
    """One run of the cell on one CPU device, with the ``Run`` the
    readers were handed and the batch log as the run left it."""
    trace.reset()
    cell = bench.load_cell(toy_root, CELL)
    runs, lines = [], []
    read_metrics = bench.read_metrics

    def keeping_the_run(run, entries, directory):
        runs.append(run)
        return read_metrics(run, entries, directory)

    bench.read_metrics = keeping_the_run
    try:
        result = bench.run_cell(cell, jax.devices()[:1], 3000000001, 0.05,
                                False, log=lines.append)
    finally:
        bench.read_metrics = read_metrics
    yield types.SimpleNamespace(
        cell=cell, result=json.loads(json.dumps(result)), run=runs[0],
        earlier=json.loads(lines[-1]), batches=list(trace.BATCHES))
    trace.reset()


def test_hostfed_loop_runs_end_to_end(hostfed):
    result = hostfed.result
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    assert result["metrics"]["images_per_s_per_chip"]["value"] > 0
    assert hostfed.earlier["failures"] == []
    assert "host_pool" in hostfed.earlier["setup_split_s"]


def test_hostfed_loop_takes_every_step_from_the_prefetcher(hostfed):
    cell, steps = hostfed.cell, hostfed.result["attempted"]
    # warm-up's batches and the window's, and nothing the loop did not take
    assert len(hostfed.batches) == cell.traffic["warmup_steps"] + steps
    window = input_trace.batch_log(hostfed.run)
    assert len(window) == steps
    rows = cell.job["per_chip_batch"]
    size = cell.job["image_size"]
    # what the step takes: float32 images and int32 labels
    assert {r[1] for r in window} == {rows * size * size * 3 * 4 + rows * 4}
    # the device's copy of the pool is dropped
    assert hostfed.run.measured["window_spans"][0][0] == "input_wait"


def test_hostfed_counter_readers_agree_with_the_host_clock(hostfed):
    """The log's waits lie inside the loop's own ``input_wait`` spans."""
    steps = hostfed.result["attempted"]
    for metric in COUNTER_METRICS:
        assert read(metric, hostfed.run) >= 0
    logged = read("input_wait_ms", hostfed.run) * steps
    spans = sum(end - start for name, start, end
                in hostfed.run.measured["window_spans"]
                if name == "input_wait") / 1e6
    assert 0 < logged <= spans
    assert read("input_idle_ms", hostfed.run) is None  # untraced


def test_hostfed_reference_check_is_the_resident_loops(hostfed, bench,
                                                        toy_root):
    """Until ``check()`` has run the loop feeds what it is handed: the
    same numbers as the ``spmd`` loop at the same seed."""
    lines = []
    bench.run_cell(bench.load_cell(toy_root, "resnet50_v15-spmd-1chip"),
                   jax.devices()[:1], 3000000001, 0.05, False,
                   log=lines.append)
    resident = json.loads(lines[-1])["notes"]["reference_check"]
    assert hostfed.earlier["notes"]["reference_check"] == resident


def test_the_cell_reports_the_resident_cells_metrics_and_its_own(hostfed,
                                                                 bench):
    """All but the device's idle share of the traced window: under the
    profiler the runtime lays a host batch out for the chip many times
    slower (an event a tile), the traced steps queue up behind the
    first copy and run back to back, and the share reads the resident
    cell's (PERF.md section 3)."""
    from benchmark_toy import REPO

    names = {m["name"] for m in bench.load_cell(REPO, CELL).per_layer}
    resident = {m["name"] for m in bench.load_cell(
        REPO, "resnet50_v15-spmd-1chip").per_layer}
    assert names == (resident - {"device_idle_share_images"}
                     | set(COUNTER_METRICS) | {"input_idle_ms"})
