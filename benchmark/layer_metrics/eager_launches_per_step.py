"""Layer: eager plane.  Device programs launched between a step's
first ``allreduce_async`` and the return of its last ``synchronize``,
counted in the trace (the host spans and the device's ``XLA Modules``
line share a clock), averaged over the traced steps: an exact count."""


def read(run):
    trace = run.reduced_trace
    if not trace or not trace["devices"]:
        return None
    starts = [s for s, _, name in trace["host_spans"] if name == "enqueue"]
    ends = [e for _, e, name in trace["host_spans"]
            if name == "synchronize"]
    if not starts or len(starts) != len(ends):
        return None
    launches = [s for d in trace["devices"] for s, _, _ in d["launches"]]
    inside = sum(1 for t in launches
                 if any(a <= t <= b for a, b in zip(starts, ends)))
    return inside / len(starts)
