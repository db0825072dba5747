"""Async-op handles.

The reference exposes integer handles managed by a poll/wait map
(``horovod/torch/handle_manager.{h,cc}``).  Core operations here return
:class:`Handle` objects; the torch binding wraps them in integers for drop-in
API fidelity.
"""

import json
import threading

from horovod_tpu.utils import trace


class HvdError(RuntimeError):
    """Raised when a collective fails (reference: Response::ERROR path)."""


class HvdAbortedError(HvdError):
    """Raised on EVERY rank when the collective runtime performs a
    coordinated abort — a rank crashed, went silent past the liveness
    window, hit an unrecoverable transport error, or the stall inspector
    promoted a stalled tensor into a shutdown.  Symmetric by design: all
    survivors raise this one typed error (naming the origin rank) within
    ``HVD_TPU_ABORT_TIMEOUT`` instead of hanging or failing each with a
    different exception and leaked ring state."""

    def __init__(self, origin_rank, reason):
        super().__init__(
            f"collective runtime aborted (origin rank {origin_rank}): "
            f"{reason}")
        self.origin_rank = origin_rank
        self.reason = reason


class HvdReconfigureError(HvdAbortedError):
    """An abort carrying an elastic membership directive (the coordinator
    decided the job can survive the failure).  Subclasses
    :class:`HvdAbortedError` so every existing ``except HvdAbortedError``
    site — and the non-elastic contract — is untouched; ``hvd.elastic.run``
    catches this subtype, reconfigures, and retries the step instead of
    letting the job die."""

    def __init__(self, origin_rank, reason, *, epoch, members, dead,
                 cause="", drain=False):
        super().__init__(origin_rank, reason)
        self.epoch = epoch          # new membership epoch to move to
        self.members = list(members)  # stable worker ids, new-rank order
        self.dead = list(dead)      # worker ids removed this epoch
        self.cause = cause          # the original (pre-rewrite) reason
        self.drain = drain          # planned departure, not a failure


class HvdDrainedError(HvdError):
    """Raised on the DRAINING rank only, after it helped the survivors
    reconfigure past it: this worker received a preemption notice
    (SIGTERM), announced departure, and left at a collective boundary.
    Deliberately NOT a subclass of :class:`HvdAbortedError` — a drain is
    a success path, and the zero-``HvdAbortedError`` guarantee of the
    drain protocol (docs/checkpoint.md) would be meaningless if the
    drained rank itself raised one.  ``hvd.elastic.run`` catches it and
    returns; bare workers can treat it as "stop training, exit 0"."""

    def __init__(self, worker_id):
        super().__init__(
            f"worker {worker_id} drained after preemption notice")
        self.worker_id = worker_id


# Elastic reconfiguration directives ride the existing abort fan-out
# (peer pushes, heartbeat replies, negotiation responses) as a marked
# reason string, so no wire message gains a new field for delivery.
RECONFIG_MARKER = "__hvd_elastic_reconfig__:"


def encode_reconfig_reason(epoch, members, dead, cause, drain=False):
    """Serialize a membership directive into an abort ``reason``.

    ``drain=True`` marks a PLANNED departure: delivery skips the rank-0
    peer fan-out (the directive reaches every rank at its next
    collective / heartbeat anyway) and the departing worker leaves with
    :class:`HvdDrainedError` instead of an abort."""
    payload = {"epoch": epoch, "members": list(members),
               "dead": list(dead), "cause": str(cause)}
    if drain:
        payload["drain"] = True
    return RECONFIG_MARKER + json.dumps(payload)


def is_drain_reason(reason) -> bool:
    """True when ``reason`` is a drain-marked membership directive."""
    if not (isinstance(reason, str)
            and reason.startswith(RECONFIG_MARKER)):
        return False
    try:
        return bool(json.loads(
            reason[len(RECONFIG_MARKER):]).get("drain"))
    except (ValueError, AttributeError):
        return False


def make_abort_error(origin_rank, reason):
    """Build the right typed error for a learned ``(origin, reason)``
    abort: a plain :class:`HvdAbortedError`, or the
    :class:`HvdReconfigureError` subtype when the reason carries an
    elastic membership directive."""
    if isinstance(reason, str) and reason.startswith(RECONFIG_MARKER):
        try:
            d = json.loads(reason[len(RECONFIG_MARKER):])
            return HvdReconfigureError(
                origin_rank, reason, epoch=d["epoch"],
                members=d["members"], dead=d.get("dead", ()),
                cause=d.get("cause", ""),
                drain=bool(d.get("drain", False)))
        except (ValueError, KeyError, TypeError):
            pass  # malformed directive degrades to a plain abort
    return HvdAbortedError(origin_rank, reason)


class Handle:
    """Completion handle for one rank's view of one collective."""

    # the last five: the request's stamps for utils/trace.py's log,
    # 0 until the eager plane sets them (a join's handle never has any)
    __slots__ = ("_event", "_result", "_error", "name", "request_id",
                 "response_id", "t_submit", "t_enqueued",
                 "t_execute_start")

    def __init__(self, name=""):
        self._event = threading.Event()
        self._result = None
        self._error = None
        self.name = name
        self.request_id = self.response_id = 0
        self.t_submit = self.t_enqueued = self.t_execute_start = 0

    def set_result(self, result):
        # first completion wins: an abort broadcast and the op's own
        # failure path may both reach the same handle
        if self._event.is_set():
            return
        self._result = result
        if self.t_execute_start:
            # logged before the waiter is released: it may read the log
            trace.finished(self)
        self._event.set()

    def set_error(self, message):
        if self._event.is_set():
            return
        self._error = (message if isinstance(message, HvdError)
                       else HvdError(message))
        self._event.set()

    def poll(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"collective '{self.name}' did not complete within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result


class HandleManager:
    """Integer-handle indirection used by the torch binding.

    Mirrors ``horovod/torch/handle_manager.cc:47`` (AllocateHandle /
    MarkDone via the underlying Handle / PollHandle / WaitForCompletion).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._next = 0
        self._handles = {}

    def allocate(self, handle: Handle) -> int:
        with self._lock:
            idx = self._next
            self._next += 1
            self._handles[idx] = handle
        return idx

    def get(self, idx: int) -> Handle:
        with self._lock:
            if idx not in self._handles:
                raise ValueError(f"unknown handle {idx}")
            return self._handles[idx]

    def poll(self, idx: int) -> bool:
        return self.get(idx).poll()

    def wait(self, idx: int, timeout=None):
        handle = self.get(idx)
        try:
            result = handle.wait(timeout)
        except TimeoutError:
            raise  # handle stays registered: the collective may still
            # complete, and a retry must be able to collect the result
        except Exception:
            with self._lock:  # terminal (HvdError): drop the entry
                self._handles.pop(idx, None)
            raise
        with self._lock:
            self._handles.pop(idx, None)
        return result
