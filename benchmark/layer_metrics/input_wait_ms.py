"""Layer: input path.  The time a step waits for input: what the
``next()`` calls on ``prefetch_to_device`` cost the step loop
(``t_taken - t_asked`` of the program's batch log, summed over the
batches taken in the measured window) over the window's steps."""


def read(run):
    trace = run.reader(".", "input_trace")
    log = trace.batch_log(run)
    if not log:
        return None
    return (sum(r[trace.T_TAKEN] - r[trace.T_ASKED] for r in log)
            / run.measured["steps"] / 1e6)
