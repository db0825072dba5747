"""Layer: device.  1 - (union of the device-operation intervals over
the traced window), on the chip that idles most, in percent."""


def read(run):
    trace = run.reduced_trace
    if not trace or not trace["devices"]:
        return None
    return 100 * max(1 - d["busy_s"] / trace["window_s"]
                     for d in trace["devices"])
