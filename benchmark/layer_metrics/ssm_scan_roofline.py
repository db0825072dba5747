"""Layer: kernels.  The bytes the scans of the state-space mixers must
move a step (the family's ``scan_bytes_per_step``: what ANY
implementation moves at the activation dtype, nothing recomputed: the
forward reads ``c``, ``delta``, ``B``, ``C`` and writes ``y``, the
backward reads those and ``dy`` and writes four gradients) over what the
chip's HBM could move in the device self time of every instruction
whose scope lies under ``mixer/ssm/scan``, forward, recomputation and
backward, in percent.  Bound by memory.  A scan that keeps its state in
HBM, makes its states again or runs a loop iteration a position spends
time here and moves no required byte, so it shows as a lower share; the
share cannot pass 100% while ``c``, ``delta`` and ``y`` live in HBM.
The count stays the same under any implementation, a kernel included,
as long as its instructions carry the scope.

The time is ``scope_trace.py``'s (by scope, no shape is looked for).  A
program that sets no such scope, a family without
``scan_bytes_per_step`` and an untraced run leave the metric out."""


def read(run):
    family = run.cell.family
    if not run.peaks or not hasattr(family, "scan_bytes_per_step"):
        return None
    under = run.reader("layer_metrics", "window_attn_time_share").under

    def seconds(chip):
        return sum(ms for (scope, phase), ms in chip.both_ms.items()
                   if under(scope, "mixer/ssm/scan")
                   and phase in ("forward", "recompute", "backward")) / 1e3

    # a step's time on the chip where it is longest, a step's bytes
    worst = run.reader(".", "scope_trace").worst(run, seconds)
    if not worst:
        return None
    required = family.scan_bytes_per_step(run.cell.config, run.cell.job)
    return 100 * required / (worst * run.peaks["hbm_bytes_per_s"])
