"""The flash kernels and the exits of a looped model in the device
trace of the traced steps: for the readers of ``flash_roofline`` and
``loop_exits_time_share``.

As in ``latent_trace.py`` (PERF.md section 3): an ``XLA Ops`` event
carries its instruction's text and nothing of a ``jax.named_scope``, so
an instruction is found by a shape only its layer has, and the shapes
are the family's to give (``trace_shapes(config, job)``), from the
cell's own files, so that a toy size is found the same way:

- ``flash``: the Pallas custom calls that take q and k of ``[batch x
  heads, T, head_dim]``: the forward, dq and dk/dv kernels of flash
  attention (every other kernel of the step takes rows of tokens);
- ``exits``: any other instruction with the exits' logits ``[rows, V]``
  as a result or an operand: the head's product over the exits and its
  two gradients (the weight's fused with its Adam update or not), the
  softmax-xent kernels, casts and copies of the logits.

A family whose ``trace_shapes`` lacks either list gives no time for it;
a family without ``trace_shapes`` and an untraced run give ``NOTHING``,
and the readers leave their metrics out.
"""

import glob
import os
import types

NOTHING = types.SimpleNamespace(busy_s=0.0, flash_s=0.0, exits_s=0.0,
                                steps=0)


def reduce_planes(reducer, planes, shapes):
    """``planes`` as ``trace_reduce.planes_of`` gives them.  Device
    seconds of self time, summed over the chips: ``busy_s`` (the union
    of the operations), ``flash_s`` and ``exits_s``."""
    flash, exits = shapes.get("flash", ()), shapes.get("exits", ())
    busy_s = flash_s = exits_s = 0.0
    for plane, lines in planes.items():
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        busy_s += reducer.measure(
            reducer.union((s, e) for _, s, e in ops)) / 1e9
        for text, self_ns in reducer.self_times(ops):
            if (reducer.PALLAS_TARGET in text
                    and any(s in text for s in flash)):
                flash_s += self_ns / 1e9
            elif any(s in text for s in exits):
                exits_s += self_ns / 1e9
    return types.SimpleNamespace(busy_s=busy_s, flash_s=flash_s,
                                 exits_s=exits_s)


def read(run):
    """The traced steps of this run, parsed once."""
    family = run.cell.family
    if run.reduced_trace is None or not hasattr(family, "trace_shapes"):
        return NOTHING
    if getattr(run, "loop_trace", None) is None:
        # the glob of run.py's traced_steps
        files = sorted(glob.glob(os.path.join(
            run.cell.root, ".bench_trace", run.cell.name, "plugins",
            "profile", "*", "*.xplane.pb")))
        reducer = run.reader(".", "trace_reduce")
        run.loop_trace = reduce_planes(
            reducer, reducer.planes_of(reducer.load(files[-1])),
            family.trace_shapes(run.cell.config, run.cell.job))
        run.loop_trace.steps = run.measured["traced_steps"]
    return run.loop_trace
