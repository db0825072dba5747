"""Layer: models.  Device self time of the whole expert path of a
block, forward, recomputation and backward: every instruction whose
scope lies under ``block/moe`` (the router where it reads what the
experts read, the sort, the gathers over the token-slots, the grouped
products with the experts' gate between them, the combine, a shared
expert) or under ``route_ahead`` (the decision of a router that reads
the block's input, made ahead of the mixer), by scope and not by shape
(``scope_trace.py``), over device busy time, on the chip where it is
largest, in percent.  A program with no expert layer, and an untraced
run, leave the metric out."""

PATHS = ("route_ahead", "block/moe")


def read(run):
    under = run.reader("layer_metrics", "window_attn_time_share").under

    def of(chip):
        return 100 * sum(
            ms for (scope, phase), ms in chip.both_ms.items()
            if any(under(scope, path) for path in PATHS)
            and phase in ("forward", "recompute", "backward")) / chip.busy_ms

    return run.reader(".", "scope_trace").worst(run, of) or None
