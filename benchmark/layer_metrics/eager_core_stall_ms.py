"""Layer: eager plane.  Time the dispatcher sat in the native core
(``hvd.wait_batch``: queue, cycle sleep, negotiation, fusion plan) while
a step's exchange was under way, from the loop's ``enqueue`` start to
its ``synchronize`` end: requests were outstanding and the dispatcher
had nothing to do, so the core set the pace.  Per traced step."""


def read(run):
    trace = run.reader(".", "program_trace").read(run)
    waits = trace.spans.get("hvd.wait_batch")
    if not waits or not trace.exchanges:
        return None
    return (trace.overlap(waits, trace.exchanges)
            / len(trace.exchanges) / 1e6)
