"""Layer: kernels.  The operations the flash kernels of a step require
(the family's ``flash_flops_per_step``: the causal query-key pairs
alone, ``2 d_qk`` for a score and ``2 d_v`` for the weighted sum,
forward and both gradients, nothing recomputed) over what the chip
could do at its published bf16 peak in the device self time of the
flash custom calls, in percent.  Bound by compute.  The cell recomputes
every block, so the forward kernel runs twice a layer: that is time
here and no operation, and shows as a lower share."""


def read(run):
    trace = run.reader(".", "latent_trace").read(run)
    if not trace.flash_s or not run.peaks:
        return None
    cell = run.cell
    required = cell.family.flash_flops_per_step(
        cell.config, cell.job) * trace.steps * len(run.devices)
    return 100 * required / (trace.flash_s * run.peaks["bf16_flops_per_s"])
