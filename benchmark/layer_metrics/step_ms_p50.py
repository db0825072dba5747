"""Layer: SPMD step.  Median host-clock time of a step ended by
``block_until_ready``, over the blocked steps of a traced run."""

import statistics


def read(run):
    blocked = run.measured.get("blocked_step_ms")
    return statistics.median(blocked) if blocked else None
