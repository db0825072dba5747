"""Layer: kernels.  The operations the flash kernels of the
sliding-window layers require a step (the family's
``window_flash_flops_per_step``: the allowed query-key pairs alone,
``i - window < j <= i``, ``2 head_dim`` for a score and ``2 head_dim``
for the weighted sum, forward and both gradients, nothing recomputed)
over what the chip could do at its published bf16 peak in the device
self time of the Pallas custom calls whose scope lies under
``attn/window`` and holds ``flash``, in percent.  Bound by compute.  A
block an edge of the window crosses computes its masked pairs too: time
here and no operation, so it shows as a lower share, and the share
cannot pass 100%.

The calls are found by scope (``scope_trace.py``'s join of an event's
instruction name with the step's text), not by shape; ``scope_trace``
keeps time by scope and phase alone, so the profiler's file is opened
again here for the custom calls among them.  A program that sets no
such scope, a family without ``window_flash_flops_per_step`` and an
untraced run leave the metric out.
"""

import glob
import os


def kernel_seconds(reducer, planes, instructions, wanted):
    """Device self time, summed over the chips, of the Pallas custom
    calls whose scope ``wanted`` takes."""
    seconds = 0.0
    for plane, lines in planes.items():
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        for text, self_ns in reducer.self_times(ops):
            if reducer.PALLAS_TARGET not in text:
                continue
            scope = instructions.get(reducer.parse(text)[0], ("", ""))[1]
            if wanted(scope):
                seconds += self_ns / 1e9
    return seconds


def read(run):
    family = run.cell.family
    if (run.reduced_trace is None or not run.peaks
            or "step" not in run.programs
            or not hasattr(family, "window_flash_flops_per_step")):
        return None
    try:
        from horovod_tpu.utils.trace import step_phases
    except ImportError:
        return None
    # the glob of run.py's traced_steps
    files = sorted(glob.glob(os.path.join(
        run.cell.root, ".bench_trace", run.cell.name, "plugins", "profile",
        "*", "*.xplane.pb")))
    reducer = run.reader(".", "trace_reduce")
    under = run.reader("layer_metrics", "window_attn_time_share").under
    seconds = kernel_seconds(
        reducer, reducer.planes_of(reducer.load(files[-1])),
        step_phases(run.programs["step"].as_text())[0],
        lambda scope: under(scope, "attn/window") and under(scope, "flash"))
    if not seconds:
        return None
    required = (family.window_flash_flops_per_step(
        run.cell.config, run.cell.job)
        * run.measured["traced_steps"] * len(run.devices))
    return 100 * required / (seconds * run.peaks["bf16_flops_per_s"])
