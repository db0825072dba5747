"""Layer: models.  ``conv_mixer_time_share`` of the elementwise part
alone: device self time of every instruction whose scope lies under
``mixer/conv/gate_conv`` (``b * h``, the three shifted multiply-adds
along the sequence, ``c * s`` and their gradients: passes bound by HBM,
what a fused kernel could win), over device busy time, in percent.  The
compiler may fuse a gate into the product beside it; the fusion then
goes where its own name says (``scope_trace.py``), so this share is a
floor of the elementwise work, not a count of its bytes."""


def read(run):
    return run.reader("layer_metrics", "window_attn_time_share").share(
        run, "mixer/conv/gate_conv")
