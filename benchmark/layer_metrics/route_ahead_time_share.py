"""Layer: models.  Device self time of a router that decides from the
block's input before the mixer runs, forward, recomputation and
backward: every instruction whose scope lies under ``route_ahead`` (the
router's float32 product on the block's input, the softmax, ``top_k``,
the weights and the counts, and the router's gradient; ``scope_trace.py``
says how an instruction gets its scope, no shape is looked for), over
device busy time, on the chip where it is largest, in percent: what a
decision made ahead of attention costs, and whether recomputation makes
it twice.  A program that sets no such scope (every configuration whose
router reads what its experts read, and a parent from before the
scope), and an untraced run, leave the metric out."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "route_ahead")
