"""The instructions of latent attention and of the expert layer that
holds a share of its experts, in the device trace of the traced steps:
for the readers of ``mla_time_share``, ``mla_flash_roofline`` and
``moe_held_time_share``.  (``moe_trace.py`` reads ``num_experts``, a key
a DeepSeek-V3-style configuration does not have.)

As there (PERF.md section 3): an ``XLA Ops`` event carries its
instruction's text and nothing of a ``jax.named_scope``, so an
instruction is found by a name or by a shape only its layer has.  The
shapes are the family's to give (``trace_shapes(config, job)``), from
the cell's own files, so that a toy size is found the same way:

- ``flash``: the Pallas custom calls that take q and k of ``[batch x
  heads, T, d_qk]``: the forward, dq and dk/dv kernels of flash
  attention, and nothing else (every other kernel of the step takes
  rows of tokens);
- ``latent``: any other instruction with one of the latent
  projections' shapes as a result or an operand: the projections down
  to and up from the latents, the inner norms, the rotation, the
  concatenations, their gradients, what the recomputation runs again;
- ``experts``: the token-slots ``[N k``, the router's ``[N, outputs]``
  and the grouped products ``%ragged-dot-*``.

The optimizer's pass over any of their weights is the optimizer's.  A
program without these layers, a family without ``trace_shapes`` and an
untraced run give ``NOTHING``, and the readers leave their metrics out.
"""

import glob
import os
import re
import types

GROUPED_PRODUCT = re.compile(r"^%ragged-dot")
NOTHING = types.SimpleNamespace(busy_s=0.0, flash_s=0.0, latent_s=0.0,
                                experts_s=0.0, steps=0)


def reduce_planes(reducer, planes, shapes):
    """``planes`` as ``trace_reduce.planes_of`` gives them.  Device
    seconds of self time, summed over the chips: ``busy_s`` (the union
    of the operations), ``flash_s``, ``latent_s`` (the flash kernels
    not among them) and ``experts_s``."""
    seconds = dict.fromkeys(("busy", "flash", "latent", "experts"), 0.0)
    for plane, lines in planes.items():
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        seconds["busy"] += reducer.measure(
            reducer.union((s, e) for _, s, e in ops)) / 1e9
        for text, self_ns in reducer.self_times(ops):
            if (reducer.PALLAS_TARGET in text
                    and any(s in text for s in shapes["flash"])):
                seconds["flash"] += self_ns / 1e9
            elif (GROUPED_PRODUCT.match(text)
                  or any(s in text for s in shapes["experts"])):
                seconds["experts"] += self_ns / 1e9
            elif any(s in text for s in shapes["latent"]):
                seconds["latent"] += self_ns / 1e9
    return types.SimpleNamespace(
        **{name + "_s": value for name, value in seconds.items()})


def read(run):
    """The traced steps of this run, parsed once."""
    family = run.cell.family
    if run.reduced_trace is None or not hasattr(family, "trace_shapes"):
        return NOTHING
    if getattr(run, "latent_trace", None) is None:
        # the glob of run.py's traced_steps
        files = sorted(glob.glob(os.path.join(
            run.cell.root, ".bench_trace", run.cell.name, "plugins",
            "profile", "*", "*.xplane.pb")))
        reducer = run.reader(".", "trace_reduce")
        run.latent_trace = reduce_planes(
            reducer, reducer.planes_of(reducer.load(files[-1])),
            family.trace_shapes(run.cell.config, run.cell.job))
        run.latent_trace.steps = run.measured["traced_steps"]
    return run.latent_trace
