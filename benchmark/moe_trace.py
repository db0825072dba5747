"""The expert layer's instructions in the device trace of the traced
steps, for the readers of ``moe_time_share`` and ``moe_gmm_roofline``.

``run.reduced_trace`` keeps device time by instruction NAME, and most
of the expert layer's instructions are fusions that share their names
with everything else, so the profiler's file is opened here a second
time, through ``trace_reduce.py``, and what was parsed is kept on
``run``: two readers, one parse.

How an instruction is found (PERF.md section 3; seen by hand on the
v5e's trace in PR 27).  The scope names of ``jax.named_scope`` are not
on the ``XLA Ops`` events: an event carries its instruction's text and
nothing of the ``op_name`` metadata.  So an instruction belongs to the
expert layer where its text holds

- the name of a grouped product: ``%ragged-dot-*`` (the Mosaic kernels
  the compiler lowers ``jax.lax.ragged_dot`` and both its gradients to,
  and the ``ragged-dot-metadata`` kernel beside them); or
- a shape only this layer has, as a result or as an operand: the
  token-slots ``[<N * k>`` (rows sorted by expert: the sorts, the
  gathers of dispatch and combine, SwiGLU, their gradients) or the
  router's ``[<N>,<E>]`` (logits, softmax, top-k, the auxiliary terms).

N, k and E come from the cell's configuration and job, so a toy size
is found the same way.  The optimizer's pass over the experts'
weights (``f32[64,2048,1024]``) and the casts of those weights to
bfloat16 are NOT the expert layer's: they are the optimizer's and the
parameters', as in the dense cells.  A program with no expert layer
(the parent of the PR that brought it) has no such instruction, and
both readers leave their metric out.
"""

import glob
import os
import re
import types

GROUPED_PRODUCT = re.compile(r"^%ragged-dot")
NOTHING = types.SimpleNamespace(busy_s=0.0, moe_s=0.0, gmm_s=0.0, steps=0)


def shapes_of(cell):
    """The two shapes only the expert layer has, as they stand in an
    instruction's text."""
    config, job = cell.config, cell.job
    tokens = job["per_chip_batch"] * job["seq_len"]
    return (f"[{tokens * config['num_experts_per_tok']}",
            f"[{tokens},{config['num_experts']}]")


def reduce_planes(reducer, planes, shapes):
    """``planes`` as ``trace_reduce.planes_of`` gives them.  Device
    seconds, summed over the chips: ``busy_s`` (the union of the
    operations), ``moe_s`` (self time of the expert layer's
    instructions, the grouped products among them), ``gmm_s`` (self
    time of the grouped products alone)."""
    busy = gmm = rest = 0.0
    for plane, lines in planes.items():
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        busy += reducer.measure(
            reducer.union((s, e) for _, s, e in ops)) / 1e9
        for text, self_ns in reducer.self_times(ops):
            if GROUPED_PRODUCT.match(text):
                gmm += self_ns / 1e9
            elif any(shape in text for shape in shapes):
                rest += self_ns / 1e9
    return types.SimpleNamespace(busy_s=busy, moe_s=gmm + rest, gmm_s=gmm)


def read(run):
    """The traced steps of this run, parsed once."""
    if run.reduced_trace is None or "num_experts" not in run.cell.config:
        return NOTHING
    if getattr(run, "moe_trace", None) is None:
        # the glob of run.py's traced_steps
        files = sorted(glob.glob(os.path.join(
            run.cell.root, ".bench_trace", run.cell.name, "plugins",
            "profile", "*", "*.xplane.pb")))
        reducer = run.reader(".", "trace_reduce")
        run.moe_trace = reduce_planes(
            reducer, reducer.planes_of(reducer.load(files[-1])),
            shapes_of(run.cell))
        run.moe_trace.steps = run.measured["traced_steps"]
    return run.moe_trace
