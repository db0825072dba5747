"""Layer: models.  ``ssm_scan_time_share`` of the products within a
chunk alone: device self time of every instruction whose scope lies
under ``mixer/ssm/scan/intra`` (a scan by chunks: the chunks' decay
masks ``[B, T / chunk, H, chunk, chunk]``, ``C B^T``, their product
with the fed channels and all their gradients), forward, recomputation
and backward, over device busy time, on the chip where it is largest,
in percent: where a kernel that keeps the masks out of HBM would win.
A program that sets no such scope (a scan that is one kernel, and a
parent from before the scope), and an untraced run, leave the metric
out."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "mixer/ssm/scan/intra")
