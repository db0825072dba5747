"""Layer: input path.  The share of the measured window's batches the
step loop had to wait for: nothing was staged when it asked
(``depth_at_ask == 0``) or the batch's copy onto the device was still
under way when it was handed over (``ready_at_take`` false), in
percent of the batches taken."""


def read(run):
    trace = run.reader(".", "input_trace")
    log = trace.batch_log(run)
    if not log:
        return None
    return 100 * sum(r[trace.DEPTH_AT_ASK] == 0 or not r[trace.READY_AT_TAKE]
                     for r in log) / len(log)
