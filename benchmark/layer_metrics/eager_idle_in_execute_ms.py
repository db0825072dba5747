"""Layer: eager plane.  Device idle time (the complement of the union
of ``XLA Ops``, as ``device_idle_share`` reckons it) that lies under an
``hvd.execute`` span, on the chip that idles most: the chip waited
while the dispatcher was busy launching.  Per traced step."""


def read(run):
    trace = run.reader(".", "program_trace").read(run)
    executes = trace.spans.get("hvd.execute")
    if not executes or not trace.idle or not trace.exchanges:
        return None
    return (max(trace.overlap(idle, executes) for idle in trace.idle)
            / len(trace.exchanges) / 1e6)
