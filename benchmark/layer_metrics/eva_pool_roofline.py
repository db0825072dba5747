"""Layer: kernels.  The bytes the pooling of the chunk-summary attention
must move a step (the family's ``eva_pool_bytes_per_step``: what ANY
implementation moves at the activation dtype, nothing recomputed: the
forward reads k and v and writes kt and vt, the backward reads k, v and
two cotangents and writes two gradients) over what the chip's HBM could
move in the device self time of every instruction whose scope lies under
``attn/eva/pool``, forward, recomputation and backward, in percent.
Bound by memory.  A pooling that is made again in the recomputation, or
that reads its operands in float32, spends time here and moves no
required byte, so it shows as a lower share; the share cannot pass 100%
while k and v live in HBM.  The count stays the same under any
implementation, a kernel included, as long as its instructions carry the
scope.

The time is ``scope_trace.py``'s (by scope, no shape is looked for).  A
program that sets no such scope, a family without
``eva_pool_bytes_per_step`` and an untraced run leave the metric out."""


def read(run):
    family = run.cell.family
    if not run.peaks or not hasattr(family, "eva_pool_bytes_per_step"):
        return None
    under = run.reader("layer_metrics", "window_attn_time_share").under

    def seconds(chip):
        return sum(ms for (scope, phase), ms in chip.both_ms.items()
                   if under(scope, "attn/eva/pool")
                   and phase in ("forward", "recompute", "backward")) / 1e3

    # a step's time on the chip where it is longest, a step's bytes
    worst = run.reader(".", "scope_trace").worst(run, seconds)
    if not worst:
        return None
    required = family.eva_pool_bytes_per_step(run.cell.config, run.cell.job)
    return 100 * required / (worst * run.peaks["hbm_bytes_per_s"])
