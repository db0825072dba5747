"""Layer: models.  ``window_attn_time_share`` of the mixers that carry
a state along the sequence: device self time of every instruction whose
scope lies under ``mixer/ssm`` (the products ``in``, ``proj`` and
``gate_out``, the taps under ``conv``, the scan and their gradients),
forward, recomputation and backward, over device busy time, on the chip
where it is largest, in percent.  A program that sets no such scope,
and an untraced run, leave the metric out."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "mixer/ssm")
