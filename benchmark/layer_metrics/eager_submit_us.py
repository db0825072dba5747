"""Layer: eager plane.  What one ``allreduce_async`` costs its caller:
mean time from the entry of ``ops/eager.py:_submit`` to the return of
``controller.enqueue`` (``t_enqueued - t_submit`` of the program's
request log), over the requests of the measured window, in
microseconds."""


def read(run):
    log = run.reader(".", "program_trace").request_log(run)
    if not log:
        return None
    return sum(r[3] - r[2] for r in log) / len(log) / 1e3
