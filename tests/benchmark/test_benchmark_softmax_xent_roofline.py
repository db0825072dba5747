"""The reader of ``softmax_xent_roofline``: the rule that finds the
loss's kernels by the logits' shape, on a hand-built case whose answer
is known and on the two LM traces recorded on the v5e (one step of
``olmoe_1b_7b-spmd-1chip``, PR 27: the "before" of PR 28; two steps of
``gpt2_medium-spmd-1chip``, PR 22)."""

import gzip
import os
import shutil
import types

import pytest

from benchmark_toy import BENCH, HERE, REPO, load_by_path, load_json

reduce = load_by_path(os.path.join(BENCH, "trace_reduce.py"),
                      "hvd_benchmark_trace_reduce")
roofline = load_by_path(
    os.path.join(BENCH, "layer_metrics", "softmax_xent_roofline.py"),
    "hvd_benchmark_reader_softmax_xent_roofline")
MS = 1_000_000  # ns
HBM = 819e9


def cell_of(config, **more):
    sizes = load_json(os.path.join(REPO, "benchmark", "configs",
                                   config + ".json"))
    return types.SimpleNamespace(config=sizes, job=sizes["job"], **more)


OLMOE, GPT2, RESNET = (cell_of(c) for c in (
    "olmoe_1b_7b", "gpt2_medium", "resnet50_v15"))


def test_the_shape_comes_from_the_cells_files():
    assert roofline.logits_of(OLMOE) == (16384, 50304)
    assert roofline.logits_of(GPT2) == (8192, 50257)
    assert roofline.logits_of(RESNET) is None
    small = types.SimpleNamespace(config=dict(vocab_size=96),
                                  job=dict(per_chip_batch=2, seq_len=32))
    assert roofline.logits_of(small) == (64, 96)


# One chip, one step, times in ms.  The loss: a forward that takes the
# logits as an OPERAND (3 ms) and a backward that gives their shape as
# its RESULT (5 ms).  Not the loss: the head's fusion of the same shape
# (no kernel), a flash kernel (another shape), a kernel on other rows.
FORWARD = ('%jvp__.1 = (f32[16384,128]{1,0}, f32[16384,128]{1,0}) '
           'custom-call(bf16[16384,50304]{1,0:T(8,128)(2,1)} %logits, '
           's32[16384,1]{1,0} %labels), custom_call_target="tpu_custom_call"')
BACKWARD = ('%transpose_jvp___.1 = bf16[16384,50304]{1,0:T(8,128)(2,1)} '
            'custom-call(bf16[16384,50304]{1,0} %logits, s32[16384,1]{1,0} '
            '%labels, f32[16384,128]{1,0} %lse, f32[16384,128]{1,0} %dy), '
            'custom_call_target="tpu_custom_call"')
HEAD = ("%fusion.11 = bf16[16384,50304]{1,0:T(8,128)(2,1)} fusion("
        "bf16[16384,2048]{1,0} %x, bf16[2048,50304]{1,0} %w), kind=kOutput")
FLASH = ('%_fwd.2 = (bf16[64,4096,128]{2,1,0}, f32[64,4096,128]{2,1,0}) '
         'custom-call(bf16[64,4096,128]{2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')
OTHER_ROWS = FORWARD.replace("16384", "116384")


def planes_of(*timed):
    events, at = [], 0
    for text, ms in timed:
        events.append((text, at * MS, (at + ms) * MS))
        at += ms
    return {"/device:TPU:0": {"XLA Ops": events},
            "/host:CPU": {"python3": [("dispatch", 0, MS)]}}


def test_hand_built_kernels_are_found_by_shape_and_kind():
    planes = planes_of((HEAD, 40), (FORWARD, 3), (FLASH, 10), (BACKWARD, 5),
                       (OTHER_ROWS, 7))
    seconds, itemsize = roofline.kernels_of(reduce, planes, 16384, 50304)
    assert seconds == pytest.approx(0.008) and itemsize == 2
    f32 = planes_of((FORWARD.replace("bf16[16384,50304]",
                                     "f32[16384,50304]"), 3))
    assert roofline.kernels_of(reduce, f32, 16384, 50304) == (
        pytest.approx(0.003), 4)
    assert roofline.kernels_of(
        reduce, planes_of((HEAD, 40), (FLASH, 10)), 16384,
        50304) == (0.0, None)


def run_on(tmp_path, cell, source=None, planes=None, steps=1, chips=1):
    """A run as ``run.py`` hands it to a reader, its profile under
    ``<root>/.bench_trace/<cell>`` (``source``: a recorded ``.gz``)."""
    folder = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    if source:
        with gzip.open(source, "rb") as src, open(
                folder / "host.xplane.pb", "wb") as dst:
            shutil.copyfileobj(src, dst)
    else:
        (folder / "host.xplane.pb").write_bytes(b"")
    reducer = reduce
    if planes is not None:
        reducer = types.SimpleNamespace(**vars(reduce))
        reducer.load = lambda path: path
        reducer.planes_of = lambda profile: planes
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=cell.config, job=cell.job,
                                   name="cell", root=str(tmp_path)),
        reduced_trace={}, devices=list(range(chips)),
        peaks={"hbm_bytes_per_s": HBM},
        measured={"traced_steps": steps},
        reader=lambda directory, name: reducer)


def test_hand_built_share_of_the_roofline(tmp_path):
    """4,945,084,416 bytes in 8 ms: 6.04 ms at 819 GB/s."""
    planes = planes_of((HEAD, 40), (FORWARD, 3), (BACKWARD, 5))
    want = 100 * 3 * 16384 * 50304 * 2 / (0.008 * HBM)
    assert want == pytest.approx(75.47, rel=1e-3)
    assert roofline.read(run_on(tmp_path, OLMOE, planes=planes)
                         ) == pytest.approx(want)


def test_steps_and_chips_scale_the_bytes(tmp_path):
    """Two traced steps on four chips: eight times the bytes, and the
    kernels' time is summed over the planes."""
    one = planes_of((FORWARD, 3), (BACKWARD, 5))["/device:TPU:0"]
    planes = {f"/device:TPU:{i}": one for i in range(4)}
    got = roofline.read(run_on(tmp_path, OLMOE, planes=planes, steps=2,
                               chips=4))
    assert got == pytest.approx(2 * 100 * 3 * 16384 * 50304 * 2
                                / (0.008 * HBM))


@pytest.mark.parametrize("case", ["no_kernel", "no_vocabulary", "untraced",
                                  "no_peaks"])
def test_nothing_to_read_leaves_the_metric_out(tmp_path, case):
    planes = planes_of((HEAD, 40), (FLASH, 10), (FORWARD, 3))
    run = run_on(tmp_path, RESNET if case == "no_vocabulary" else OLMOE,
                 planes=planes)
    if case == "no_kernel":
        run = run_on(tmp_path / "n", OLMOE,
                     planes=planes_of((HEAD, 40), (FLASH, 10)))
    elif case == "untraced":
        run.reduced_trace = None
    elif case == "no_peaks":
        run.peaks = None
    assert roofline.read(run) is None


# ------------------------------------------------ the recorded traces
@pytest.mark.parametrize("cell,fixture,steps,ms,want", [
    # PR 27's trace, the "before" of PR 28: 80.24 + 72.46 ms a step
    (OLMOE, "olmoe_1b_7b-spmd-1chip.1step.xplane.pb.gz", 1, 152.700, 3.954),
    # PR 22's trace: (1.234 + 2.513) ms a step, twice
    (GPT2, "gpt2_medium-spmd-1chip.2steps.xplane.pb.gz", 2, 7.495, 80.47),
], ids=["olmoe_1b_7b", "gpt2_medium"])
def test_reader_on_the_recorded_traces(tmp_path, cell, fixture, steps, ms,
                                       want):
    source = os.path.join(HERE, "fixtures", fixture)
    seconds, itemsize = roofline.kernels_of(
        reduce, reduce.planes_of(reduce.load(source)),
        *roofline.logits_of(cell))
    assert seconds * 1e3 == pytest.approx(ms, rel=1e-3) and itemsize == 2
    got = roofline.read(run_on(tmp_path, cell, source=source, steps=steps))
    assert got == pytest.approx(want, rel=1e-3)
    assert 0 < got < 100
    if cell is OLMOE:
        assert 3.9 <= got <= 4.0
