"""Layer: SPMD step.  Device self time a step of the compiled step's
forward pass: the instructions whose ``op_name`` has ``jvp(`` and
neither ``transpose(`` nor ``rematted_computation`` (the model, the
loss; ``scope_trace.py`` says how an event finds its name), on the
chip where it is longest, in milliseconds."""


def read(run):
    return run.reader(".", "scope_trace").worst(
        run, lambda chip: chip.phase_ms["forward"])
