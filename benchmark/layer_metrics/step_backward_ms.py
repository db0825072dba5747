"""Layer: SPMD step.  Device self time a step of the compiled step's
backward pass: the instructions whose ``op_name`` has ``transpose(``
and no ``rematted_computation`` (``scope_trace.py``), on the chip where
it is longest, in milliseconds.  A weight gradient that the compiler
fused with its optimizer update is here when the fusion carries the
product's name (the run's earlier line has the time of such fusions)."""


def read(run):
    return run.reader(".", "scope_trace").worst(
        run, lambda chip: chip.phase_ms["backward"])
