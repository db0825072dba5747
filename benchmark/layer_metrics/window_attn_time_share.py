"""Layer: models.  Device self time of the attention of the
sliding-window layers, forward, recomputation and backward: every
instruction whose scope lies under ``attn/window`` (the q and k/v
projections, the rotation, the flash kernels with the layout copies
around them, the output projection and their gradients;
``scope_trace.py`` says how an instruction gets its scope, no shape is
looked for), over device busy time, on the chip where it is largest, in
percent.  The gate (``attn/gate``) is not among them.  A program that
sets no such scope, and an untraced run, leave the metric out."""


def under(scope, path):
    """Whether ``path`` (``"attn/window"``) is a run of whole
    components of ``scope`` (``"block/attn/window/flash"``)."""
    return f"/{path}/" in f"/{scope}/"


def share(run, path):
    """Percent of busy time under ``path``, forward, recomputation and
    backward, or ``None`` where nothing ran under it."""
    def of(chip):
        return 100 * sum(
            ms for (scope, phase), ms in chip.both_ms.items()
            if under(scope, path)
            and phase in ("forward", "recompute", "backward")) / chip.busy_ms

    return run.reader(".", "scope_trace").worst(run, of) or None


def read(run):
    return share(run, "attn/window")
