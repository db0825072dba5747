"""What the program recorded about itself, for the readers of the
eager plane's metrics: ``horovod_tpu/utils/trace.py``'s request log,
cut to the measured window, and its ``hvd.*`` spans beside the
device's idle time, from the profiler's file of the traced steps.

``run.reduced_trace["host_spans"]`` holds only the names the loop files
gave their own spans, so the file is opened here a second time, through
``trace_reduce.py``, and what was parsed is kept on ``run``: six
readers, one parse.  A program from before the spans and the log (the
parent of the PR that brought them) gives empty lists, and every
reader leaves its metric out.
"""

import glob
import os
import types

SPAN_PREFIX = "hvd."
# loops/eager.py: a step's exchange runs from the start of its
# ``enqueue`` span to the end of its ``synchronize`` span
EXCHANGE_START, EXCHANGE_END = "enqueue", "synchronize"
NOTHING = types.SimpleNamespace(spans={}, exchanges=[], idle=[],
                                overlap=None)


def request_log(run):
    """The log's records that lie inside the measured window, as
    ``(request id, response id, t_submit, t_enqueued, t_execute_start,
    t_done)`` on the clock of ``run.spans``."""
    try:
        from horovod_tpu.utils import trace
    except ImportError:
        return []
    window = run.measured.get("window_spans")
    if not window:
        return []
    start = min(s for _, s, _ in window)
    end = max(e for _, _, e in window)
    return [r for r in trace.LOG if start <= r[2] and r[5] <= end]


def reduce_planes(reducer, planes):
    """``planes`` as ``trace_reduce.planes_of`` gives them.  Returns

    - ``spans``: ``{name: [(start_ns, end_ns)]}`` of the host's
      ``hvd.*`` spans;
    - ``exchanges``: ``[(start_ns, end_ns)]``, one per traced step;
    - ``idle``: per chip, the intervals of the traced window (first
      operation's start to the last one's end over all chips, as
      ``device_idle_share`` reckons it) in which no operation ran;
    - ``overlap(intervals, cover)``: nanoseconds of ``intervals`` that
      lie inside ``cover``.
    """
    host = [event for plane, lines in planes.items()
            if plane.startswith("/host:")
            for events in lines.values() for event in events]
    spans = {}
    for name, start, end in host:
        if name.startswith(SPAN_PREFIX):
            spans.setdefault(name, []).append((start, end))
    exchanges = list(zip(
        sorted(s for name, s, _ in host if name == EXCHANGE_START),
        sorted(e for name, _, e in host if name == EXCHANGE_END)))
    busy = [reducer.union((s, e) for _, s, e in lines[reducer.OP_LINE])
            for plane, lines in sorted(planes.items())
            if reducer.DEVICE_PLANE.match(plane)
            and lines.get(reducer.OP_LINE)]
    window = busy and [[min(b[0][0] for b in busy),
                        max(b[-1][1] for b in busy)]]

    def overlap(intervals, cover):
        intervals = reducer.union(intervals)
        return reducer.measure(intervals) - reducer.measure(
            reducer.subtract(intervals, reducer.union(cover)))

    return types.SimpleNamespace(
        spans=spans, exchanges=exchanges, overlap=overlap,
        idle=[reducer.subtract(window, b) for b in busy])


def read(run):
    """The traced steps of this run, parsed once."""
    if run.reduced_trace is None:
        return NOTHING
    if getattr(run, "program_trace", None) is None:
        # the glob of run.py's traced_steps
        files = sorted(glob.glob(os.path.join(
            run.cell.root, ".bench_trace", run.cell.name, "plugins",
            "profile", "*", "*.xplane.pb")))
        reducer = run.reader(".", "trace_reduce")
        run.program_trace = reduce_planes(
            reducer, reducer.planes_of(reducer.load(files[-1])))
    return run.program_trace
