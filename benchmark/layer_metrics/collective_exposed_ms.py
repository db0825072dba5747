"""Layer: SPMD step.  Per step, the device time of collective
operations during which no other operation runs on that chip, on the
chip where it is longest.  (All collective time, hidden or not, is on
the run's earlier line.)"""


def read(run):
    trace = run.reduced_trace
    if not trace or not trace["devices"]:
        return None
    run.notes["collective_ms_per_step"] = [
        1e3 * d["collective_s"] / run.measured["traced_steps"]
        for d in trace["devices"]]
    return 1e3 * max(d["collective_exposed_s"] for d in trace["devices"]) \
        / run.measured["traced_steps"]
