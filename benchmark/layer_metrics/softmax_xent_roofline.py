"""Layer: kernels.  The bytes the loss must move a step over what the
chip's HBM could move in the device self time of the softmax-xent
kernels, in percent.  Bound by memory: the forward reads the
``[rows, V]`` logits once, the backward reads them and writes
``dlogits`` of the same shape and type once, so a step needs ``3 x rows
x V x itemsize`` bytes, with rows = ``per_chip_batch x seq_len`` and V =
``vocab_size`` of the cell's own files, and the item size read off the
instruction (``bf16[rows,V]``).  It cannot pass 100% while the logits
live in HBM.

The kernels are found by shape, not by name (``%jvp__`` and
``%transpose_jvp___`` are whatever scope called them): the
``tpu_custom_call`` instructions whose text holds ``[rows,V]`` as an
operand or as a result.  ``run.reduced_trace`` keeps time by name, so
the profiler's file is opened again here through ``trace_reduce.py``.
A program with no such kernel, a cell with no vocabulary and an
untraced run leave the metric out.
"""

import glob
import os
import re


def logits_of(cell):
    """``(rows, V)`` of the logits a chip's step makes, or ``None`` where
    the cell's files have no vocabulary or no sequence."""
    config, job = cell.config, cell.job
    if "vocab_size" not in config or "seq_len" not in job:
        return None
    return job["per_chip_batch"] * job["seq_len"], config["vocab_size"]


def kernels_of(reducer, planes, rows, vocab):
    """``(seconds, itemsize)``: device self time of the kernels that
    take or give ``<type>[rows,vocab]``, summed over the chips, and the
    bytes of one element of it."""
    typed = re.compile(rf"[a-z]+(\d+)\[{rows},{vocab}\]")
    seconds, itemsize = 0.0, None
    for plane, lines in planes.items():
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        for text, self_ns in reducer.self_times(ops):
            found = reducer.PALLAS_TARGET in text and typed.search(text)
            if found:
                seconds += self_ns / 1e9
                itemsize = int(found.group(1)) // 8
    return seconds, itemsize


def read(run):
    logits = logits_of(run.cell)
    if run.reduced_trace is None or not run.peaks or logits is None:
        return None
    # the glob of run.py's traced_steps
    files = sorted(glob.glob(os.path.join(
        run.cell.root, ".bench_trace", run.cell.name, "plugins", "profile",
        "*", "*.xplane.pb")))
    reducer = run.reader(".", "trace_reduce")
    seconds, itemsize = kernels_of(
        reducer, reducer.planes_of(reducer.load(files[-1])), *logits)
    if not seconds:
        return None
    rows, vocab = logits
    required = (3 * rows * vocab * itemsize
                * run.measured["traced_steps"] * len(run.devices))
    return 100 * required / (seconds * run.peaks["hbm_bytes_per_s"])
