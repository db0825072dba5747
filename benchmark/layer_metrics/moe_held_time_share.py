"""Layer: models.  Device self time of the expert layers that hold a
share of their experts, forward, recomputation and backward (router
over all outputs, sort, gathers over the token-slots, grouped products
over the held rows, combine; ``latent_trace.py`` says how each is
found; the shared expert is not among them), over device busy time,
all chips, in percent."""


def read(run):
    trace = run.reader(".", "latent_trace").read(run)
    if not trace.experts_s:
        return None
    return 100 * trace.experts_s / trace.busy_s
