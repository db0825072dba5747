"""Layer: models.  Operations the forward and backward passes require
(from shapes, by the family's own function; nothing recomputed) times
samples a second of the measured window, over chips times the chip's
published bf16 peak, in percent."""


def read(run):
    if not run.peaks:
        return None
    cell = run.cell
    required = cell.family.required_flops_per_sample(cell.config, cell.job)
    return 100 * required * run.measured["samples_per_s"] / (
        len(run.devices) * run.peaks["bf16_flops_per_s"])
