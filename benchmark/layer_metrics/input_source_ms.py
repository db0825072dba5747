"""Layer: input path.  The source iterator's work for one host batch
(``BatchIterator``'s gather): median ``t_host_ready - t_next_start`` of
the program's batch log over the measured window's batches."""


def read(run):
    trace = run.reader(".", "input_trace")
    return trace.median_ms(run, trace.T_NEXT_START, trace.T_HOST_READY)
