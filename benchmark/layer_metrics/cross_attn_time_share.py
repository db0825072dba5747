"""Layer: models.  ``window_attn_time_share`` of the attention layers
that read another layer's keys and values: device self time of every
instruction whose scope lies under ``attn/cross`` (the q and output
projections, the two flash calls with the layout copies around them,
the subtraction and norm under ``diff``, and their gradients; the
gradient into the published k and v is this layer's work and is here),
over device busy time, in percent."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "attn/cross")
