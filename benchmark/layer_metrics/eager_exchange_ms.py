"""Layer: eager plane.  Median host time of a step's gradient
exchange, from the first ``allreduce_async`` to the return of the last
``synchronize`` (the loop's ``enqueue`` and ``synchronize`` spans),
over the steps of the measured window."""

import statistics


def read(run):
    spans = run.measured.get("window_spans", [])
    starts = [s for name, s, _ in spans if name == "enqueue"]
    ends = [e for name, _, e in spans if name == "synchronize"]
    if not starts or len(starts) != len(ends):
        return None
    return statistics.median(
        (e - s) / 1e6 for s, e in zip(starts, ends))
