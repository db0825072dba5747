"""Layer: models.  ``eva_attn_time_share`` of the pooling alone: device
self time of every instruction whose scope lies under ``attn/eva/pool``
(a chunk's positions weighed into one key and one value, and the
gradients; no matrix product, so none of it is in ``mfu_required``),
over device busy time, in percent."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "attn/eva/pool")
