"""Layer: SPMD step.  The share of device busy time that the names
leave with no phase: events whose instruction has no ``op_name`` (or an
argument's label or a bare primitive for one) and no named neighbour to
be read as, or is no instruction of the step (``scope_trace.py``); the
instrument's own coverage, on the chip where it is largest, in
percent."""


def read(run):
    return run.reader(".", "scope_trace").worst(
        run, lambda chip: 100 * chip.phase_ms["unnamed"] / chip.busy_ms)
