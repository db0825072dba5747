"""Layer: SPMD step.  Device self time a step of what the step holds
outside its gradient and its exchange: the optimizer under
``hvd/update`` (``DistributedOptimizer``), the caller's
``apply_updates``, the ``pmean`` of the loss and of statistics
(``scope_trace.py``), on the chip where it is longest, in milliseconds.
An update that the compiler fused into a weight gradient's product is
NOT here (``step_backward_ms``)."""


def read(run):
    return run.reader(".", "scope_trace").worst(
        run, lambda chip: chip.phase_ms["update"])
