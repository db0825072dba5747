"""Layer: models.  Device self time of latent attention, forward,
recomputation and backward (the three flash kernels at the heads'
two widths and every instruction with a latent projection's shape;
``latent_trace.py`` says how each is found), over device busy time, all
chips, in percent."""


def read(run):
    trace = run.reader(".", "latent_trace").read(run)
    if not trace.flash_s + trace.latent_s:
        return None
    return 100 * (trace.flash_s + trace.latent_s) / trace.busy_s
