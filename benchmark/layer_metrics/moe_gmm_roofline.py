"""Layer: kernels.  The operations the grouped products of a step
require (the family's ``grouped_matmul_flops_per_step``: routed rows
only, none of a kernel's padding) over what the chip could do at its
published bf16 peak in the device self time of EVERY instruction the
grouped products lower to, in percent.  Bound by compute: a row of
2048 is multiplied with 2048 x 1024 weights that 2,048 rows share."""


def read(run):
    trace = run.reader(".", "moe_trace").read(run)
    if not trace.gmm_s or not run.peaks:
        return None
    cell = run.cell
    required = cell.family.grouped_matmul_flops_per_step(
        cell.config, cell.job) * trace.steps * len(run.devices)
    return 100 * required / (trace.gmm_s * run.peaks["bf16_flops_per_s"])
