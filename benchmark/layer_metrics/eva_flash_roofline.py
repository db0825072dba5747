"""Layer: kernels.  The operations the attention kernels of the
chunk-summary layers require a step (the family's
``eva_flash_flops_per_step``: the allowed pairs alone, a query and the
positions of its own window up to itself and a query and the summaries
of every earlier window, ``2 head_dim`` for a score and ``2 head_dim``
for the weighted sum, forward and both gradients, nothing recomputed)
over what the chip could do at its published bf16 peak in the device
self time of the Pallas custom calls whose scope lies under
``attn/eva/flash``, in percent.  Bound by compute.  The count is the
same whether one kernel or two implement the layer; a diagonal block
computes its masked pairs too: time here and no operation, so it shows
as a lower share, and the share cannot pass 100%.

The calls are found by scope, as ``window_flash_roofline`` finds its own
(that file's ``kernel_seconds``), not by shape.  A program that sets no
such scope, a family without ``eva_flash_flops_per_step`` and an
untraced run leave the metric out.
"""

import glob
import os


def read(run):
    family = run.cell.family
    if (run.reduced_trace is None or not run.peaks
            or "step" not in run.programs
            or not hasattr(family, "eva_flash_flops_per_step")):
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
    window = run.reader("layer_metrics", "window_flash_roofline")
    under = run.reader("layer_metrics", "window_attn_time_share").under
    seconds = window.kernel_seconds(
        reducer, reducer.planes_of(reducer.load(files[-1])),
        step_phases(run.programs["step"].as_text())[0],
        lambda scope: under(scope, "attn/eva/flash"))
    if not seconds:
        return None
    required = (family.eva_flash_flops_per_step(run.cell.config, run.cell.job)
                * run.measured["traced_steps"] * len(run.devices))
    return 100 * required / (seconds * run.peaks["bf16_flops_per_s"])
