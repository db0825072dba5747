"""Layer: models.  Device self time of the expert layer's instructions,
forward and backward (route, sort, gather, grouped products, combine;
``moe_trace.py`` says how each is found), over device busy time, all
chips, in percent."""


def read(run):
    trace = run.reader(".", "moe_trace").read(run)
    if not trace.moe_s:
        return None
    return 100 * trace.moe_s / trace.busy_s
