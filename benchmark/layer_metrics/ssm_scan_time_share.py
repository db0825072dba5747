"""Layer: models.  ``ssm_mixer_time_share`` of the scan alone: device
self time of every instruction whose scope lies under ``mixer/ssm/scan``
(the loops over the positions, forward and backward, and the sums the
backward reads its gradients off: no matrix product, so none of it is in
``mfu_required``), over device busy time, in percent."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "mixer/ssm/scan")
