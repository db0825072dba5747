"""Layer: models.  ``window_attn_time_share`` of the full-attention
layers: device self time of every instruction whose scope lies under
``attn/global``, forward, recomputation and backward, over device busy
time, in percent."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "attn/global")
