"""``step_backward_ms`` in the cells that count images: a per-layer
metric names the one end-to-end metric it moves, so the cells whose
throughput is ``images_per_s_per_chip`` report it under this name."""


def read(run):
    return run.reader("layer_metrics", "step_backward_ms").read(run)
