"""Layer: input path.  The time the chip ran no program because it
waited for its batch: under an ``hvd.data.wait`` span (the step loop
waited, so nothing was dispatched) and from a wait's end to the start
of the step that took its batch (``device_put`` returns before the copy
ends, and a loop that runs ahead of the chip has left its wait long
before the chip runs dry).  The traced window's waits and its launches
(``XLA Modules``) pair in order, one of each a step; the chip is busy
inside a launch.  Reckoned from the first wait's start, not from the
first operation's as ``device_idle_share`` is: the chip may wait
longest for the first batch.  On the chip that waited most, per traced
step."""


def read(run):
    waits = sorted(run.reader(".", "program_trace").read(run).spans.get(
        "hvd.data.wait", ()))
    if not waits:
        return None
    reducer = run.reader(".", "trace_reduce")
    worst = 0
    for device in run.reduced_trace["devices"]:
        launches = [(start, end) for start, end, _ in device["launches"]]
        cover = list(waits)
        if len(launches) == len(waits):
            cover += [(wait[1], launch[0])
                      for wait, launch in zip(waits, launches)
                      if launch[0] > wait[1]]
        worst = max(worst, reducer.measure(reducer.subtract(
            reducer.union(cover), reducer.union(launches))))
    return worst / run.measured["traced_steps"] / 1e6
