"""Layer: SPMD step.  Device self time a step of forward passes run
again inside the backward pass (``jax.checkpoint``): the instructions
whose ``op_name`` has ``rematted_computation`` (``scope_trace.py``), on
the chip where it is longest, in milliseconds.  Listed for the cells
whose steps hold a checkpoint; elsewhere it would read 0."""


def read(run):
    return run.reader(".", "scope_trace").worst(
        run, lambda chip: chip.phase_ms["recompute"])
