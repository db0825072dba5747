"""Layer: kernels.  The operations the flash kernels of a step require
(the family's ``flash_flops_per_step``: the causal query-key pairs
alone, ``2 head_dim`` for a score and ``2 head_dim`` for the weighted
sum, forward and both gradients, nothing recomputed) over what the chip
could do at its published bf16 peak in the device self time of the
flash custom calls (``loop_trace.py`` says how they are found), in
percent.  Bound by compute.  Where the cell recomputes its blocks the
forward kernel runs twice an application: that is time here and no
operation, and shows as a lower share."""


def read(run):
    trace = run.reader(".", "loop_trace").read(run)
    family = run.cell.family
    if (not trace.flash_s or not run.peaks
            or not hasattr(family, "flash_flops_per_step")):
        return None
    required = family.flash_flops_per_step(
        run.cell.config, run.cell.job) * trace.steps * len(run.devices)
    return 100 * required / (trace.flash_s * run.peaks["bf16_flops_per_s"])
