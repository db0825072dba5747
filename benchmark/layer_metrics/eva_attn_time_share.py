"""Layer: models.  ``window_attn_time_share`` of the attention that
reads its own window exactly and the earlier ones through summaries:
device self time of every instruction whose scope lies under
``attn/eva`` (the three projections, the rotation, the pooling, the
kernel calls with the layout copies and the join around them, the output
projection and their gradients), forward, recomputation and backward,
over device busy time, on the chip where it is largest, in percent.  A
program that sets no such scope, and an untraced run, leave the metric
out."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "attn/eva")
