"""Layer: kernels.  Device time of ``tpu_custom_call`` operations (the
Pallas kernels) over device busy time, all chips, in percent."""


def read(run):
    trace = run.reduced_trace
    if not trace or not trace["devices"]:
        return None
    busy = sum(d["busy_s"] for d in trace["devices"])
    kernels = sum(d["category_s"]["tpu_custom_call"]
                  for d in trace["devices"])
    return 100 * kernels / busy
