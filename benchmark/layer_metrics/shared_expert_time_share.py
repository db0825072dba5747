"""Layer: models.  ``routed_ffn_time_share`` of the shared expert
alone: device self time of every instruction whose scope lies under
``moe/shared`` (the dense expert every token goes through, beside the
routed ones: its products, its non-linearity and their gradients),
forward, recomputation and backward, over device busy time, on the chip
where it is largest, in percent: how much of the expert path is dense.
A program that sets no such scope (no shared expert, and a parent from
before the scope), and an untraced run, leave the metric out."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "moe/shared")
