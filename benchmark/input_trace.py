"""What the input path recorded about itself, for the readers of the
``input_*`` metrics: ``horovod_tpu/utils/trace.py``'s batch log, cut to
the measured window as ``program_trace.request_log`` cuts the request
log.  (The path's ``hvd.data.*`` spans are in ``program_trace.read``'s
``spans`` with every other ``hvd.*`` name.)

One record per batch the loop took from ``prefetch_to_device``:

    (batch id, bytes, t_next_start, t_host_ready, t_put_end,
     t_asked, t_taken, depth_at_ask, ready_at_take)

A program from before the log (the parent of the PR that brought it)
and a loop that never enters the input path give an empty list, and
every reader leaves its metric out.
"""

import statistics

T_NEXT_START, T_HOST_READY, T_PUT_END, T_ASKED, T_TAKEN = 2, 3, 4, 5, 6
DEPTH_AT_ASK, READY_AT_TAKE = 7, 8


def batch_log(run):
    """The log's records whose wait (``t_asked`` to ``t_taken``) lies
    inside the measured window, on the clock of ``run.spans``."""
    try:
        from horovod_tpu.utils import trace
    except ImportError:
        return []
    window = run.measured.get("window_spans")
    if not window:
        return []
    start = min(s for _, s, _ in window)
    end = max(e for _, _, e in window)
    return [r for r in getattr(trace, "BATCHES", ())
            if start <= r[T_ASKED] and r[T_TAKEN] <= end]


def median_ms(run, start, end):
    """Median milliseconds from stamp ``start`` to stamp ``end`` over
    the window's batches, or ``None`` where the log has none."""
    log = batch_log(run)
    if not log:
        return None
    return statistics.median(r[end] - r[start] for r in log) / 1e6
