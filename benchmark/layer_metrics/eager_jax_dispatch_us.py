"""Layer: eager plane.  What of a response's execution is JAX's
dispatch and not the plane's own Python: mean duration of the
``hvd.exec.launch`` spans (the call of the cached collective program)
in the trace, in microseconds."""


def read(run):
    launches = run.reader(".", "program_trace").read(run).spans.get(
        "hvd.exec.launch")
    if not launches:
        return None
    return sum(e - s for s, e in launches) / len(launches) / 1e3
