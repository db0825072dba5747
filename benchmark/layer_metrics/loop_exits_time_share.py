"""Layer: models.  Device self time of the exits of a looped model: the
one head's product over every pass's exit and its two gradients, the
softmax-xent kernels over the exits' logits, and whatever else takes or
gives the logits' ``[rows, V]`` shape (``loop_trace.py`` says how each
is found), over device busy time, all chips, in percent.  The passes
over the blocks and the gate are not among them."""


def read(run):
    trace = run.reader(".", "loop_trace").read(run)
    if not trace.exits_s:
        return None
    return 100 * trace.exits_s / trace.busy_s
