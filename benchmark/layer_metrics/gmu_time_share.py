"""Layer: models.  ``window_attn_time_share`` of the gated memory units:
device self time of every instruction whose scope lies under
``mixer/gmu`` (the two products, the gate on the memory an earlier block
published, and their gradients), over device busy time, in percent."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "mixer/gmu")
