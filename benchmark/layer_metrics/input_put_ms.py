"""Layer: input path.  One batch onto the device under its sharding
(the producer's ``jax.tree.map(put, batch)``; on a runtime whose
``device_put`` returns before the copy ends, the part the producer's
thread is held for): median ``t_put_end - t_host_ready`` of the
program's batch log over the measured window's batches."""


def read(run):
    trace = run.reader(".", "input_trace")
    return trace.median_ms(run, trace.T_HOST_READY, trace.T_PUT_END)
