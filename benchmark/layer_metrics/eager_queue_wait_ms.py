"""Layer: eager plane.  How long a request waited for the dispatcher
to reach it: median time from the return of ``controller.enqueue`` to
the start of the response that carries it (``t_execute_start -
t_enqueued`` of the program's request log), over the requests of the
measured window."""

import statistics


def read(run):
    log = run.reader(".", "program_trace").request_log(run)
    if not log:
        return None
    return statistics.median(r[4] - r[3] for r in log) / 1e6
