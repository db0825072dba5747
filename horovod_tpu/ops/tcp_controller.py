"""TCP controller: multi-process coordination + data plane.

The process-rank analog of the reference's Gloo configuration
(``horovod/common/gloo/gloo_controller.cc`` + ``gloo_operations.cc``): a
job launched as N OS processes (``hvdrun -np N``) coordinates named
collectives through a rank-0 service.

v2 design (round 2 — replaces the round-1 star):

- **Control plane**: ONE persistent multiplexed connection per worker to
  the rank-0 coordinator (``network.MuxClient``); each named collective
  is a signed request that blocks until all ranks contributed
  (negotiation-order freedom, cross-rank validation, Join stand-ins and
  stall handling per the reference's protocol).
- **Response cache**: the coordinator keeps an LRU of validated
  signatures per name (reference: ``response_cache.cc``); steady-state
  resubmissions with a matching signature skip re-validation.
- **Data plane**: small tensors ride the coordinator round-trip (one
  RTT, latency-optimal).  Tensors >= ``HVD_TCP_RING_THRESHOLD``
  (default 1 MB) move rank-to-rank on the worker ring instead
  (``ops/tcp_dataplane.py``): ring allreduce / pipelined broadcast /
  block-rotation allgather — the coordinator only referees metadata, so
  no O(N·bytes) hot spot (reference: ``gloo_operations.cc:30-100`` ring
  allreduce).
- **Timeline**: enabled per rank (``HVD_TIMELINE=<path>`` writes
  ``<path>.rank<r>``); rank 0 merges every rank's trace into ``<path>``
  at shutdown (reference: rank 0 writes one file for all ranks,
  ``timeline.cc``).

THE PERF PATH ON TPU PODS IS NOT THIS: under ``hvdrun --tpu`` the
global-mesh controller compiles collectives over ICI/DCN
(``ops/global_controller.py``); the tcp plane is the no-accelerator
configuration.
"""

import base64
import hashlib
import os
import threading
import time

import numpy as np

from horovod_tpu.common import busy, faults
from horovod_tpu.common import rtt as rtt_mod
from horovod_tpu.common.handles import (RECONFIG_MARKER, HvdAbortedError,
                                        HvdError, is_drain_reason,
                                        make_abort_error)
from horovod_tpu.common.ops_enum import (ReduceOp, RequestType,
                                         is_float_dtype,
                                         reduce_scatter_split_sizes)
from horovod_tpu.common.response_cache import SignatureCache
from horovod_tpu.ops.tcp_dataplane import (DEFAULT_RHD_MAX_BYTES,
                                           DEFAULT_RHD_MIN_BYTES,
                                           DEFAULT_RING_THRESHOLD,
                                           PeerService, RingPlane,
                                           RingSendError)
from horovod_tpu.run.service import network
from horovod_tpu.utils import env as env_util
from horovod_tpu.utils.logging import get_logger

CONTROLLER_SCOPE = "controller"
CONTROLLER_KEY = "addr"
PEERS_SCOPE = "peers"
TIMELINE_SCOPE = "timeline"
# dead-epoch GC watermark (rendezvous): highest epoch whose suffixed
# scopes have already been torn down, so a reconfiguration at epoch k
# purges only the epochs since the last purge instead of rescanning
# 0..k-1 every time (O(k^2) cumulative rendezvous calls at soak scale)
GC_SCOPE = "gc"
GC_PURGED_KEY = "purged-epoch"


# ------------------------------------------------------------------ messages
class CollectiveMsg:
    def __init__(self, name, rank, req_type, op, payload, shape, dtype,
                 root_rank=-1, splits=None, prescale=1.0, postscale=1.0,
                 ring=False, sig=None, compression="none", epoch=0,
                 schedule="auto", group="", group_ranks=None):
        self.name = name
        # process-group scoping (docs/groups.md): "" = the world.  The
        # member list rides the message so the coordinator never needs
        # this worker's group registry — negotiation state is keyed
        # (group, name) and readiness counts exactly these ranks.
        self.group = group
        self.group_ranks = tuple(group_ranks) if group_ranks else None
        self.epoch = epoch              # sender's membership epoch
        self.rank = rank
        self.req_type = int(req_type)
        self.op = int(op)
        self.payload = payload          # raw little-endian bytes (None=ring)
        self.shape = tuple(shape)
        self.dtype = dtype              # numpy dtype string
        self.root_rank = root_rank
        self.splits = splits
        self.prescale = prescale
        self.postscale = postscale
        self.ring = ring
        self.sig = sig                  # signature digest (response cache)
        self.compression = compression  # requested wire compression
        self.schedule = schedule        # requested collective schedule


# epoch-exempt: responses ride the fenced request's connection — the
# coordinator only writes a ResultMsg back on the socket that carried a
# CollectiveMsg already admitted past the epoch fence in
# _handle_collective, so a stale-epoch result cannot reach a re-formed
# world's rank
class ResultMsg:
    def __init__(self, payload=None, shape=None, dtype=None, error=None,
                 recv_splits=None, ring_go=False, participants=None,
                 dims0=None, ring_id=None, params_seq=0, params=None,
                 resend=False, compression="none", aborted=None,
                 ring_segment_bytes=None, schedule=None, groups=None):
        self.payload = payload
        self.shape = shape
        self.dtype = dtype
        self.error = error
        self.recv_splits = recv_splits
        self.ring_go = ring_go
        self.participants = participants
        self.dims0 = dims0              # per-rank first dims (ring allgather)
        self.ring_id = ring_id          # coordinator-assigned round id
        self.params_seq = params_seq    # autotune publication counter
        self.params = params            # tuned knob dict (rank 0 -> all)
        self.resend = resend    # ring infeasible: resubmit with payload
        self.compression = compression  # coordinator-resolved wire format
        self.aborted = aborted  # (origin_rank, reason) coordinated abort
        # coordinator-resolved pipeline segment size for THIS round
        # (None: every rank uses its identical launch-env value) — both
        # ring endpoints must derive the same segment plan even while a
        # tuned value propagates
        self.ring_segment_bytes = ring_segment_bytes
        # coordinator-resolved collective schedule for THIS round,
        # stamped like the segment size so endpoints can't desync:
        # "flat_ring" | "hierarchical" | "rhd" (None: flat ring, the
        # pre-schedule wire default)
        self.schedule = schedule
        # hierarchical only: the group plan (list of sorted rank lists)
        # every participant executes — stamped so re-grouping after an
        # elastic reconfiguration is digest-identical by construction
        self.groups = groups


# epoch-exempt: join barriers run inside one epoch by construction —
# the coordinator address is published under an epoch-suffixed
# rendezvous scope (run/rendezvous.py) and the session hello fences
# resumed connections, so a JoinMsg can only reach the coordinator of
# the epoch it was minted in
class JoinMsg:
    def __init__(self, rank):
        self.rank = rank


# epoch-exempt: reply half of the JoinMsg barrier above — rides the
# fenced join connection
class JoinDoneMsg:
    def __init__(self, last_rank, abort=None):
        self.last_rank = last_rank
        self.abort = abort              # (origin_rank, reason) | None


# epoch-exempt: teardown is epoch-agnostic by design — a shutdown must
# deregister the rank whichever epoch the frame was minted in, and
# acting on a straggler shutdown is idempotent (the rank is gone either
# way)
class ShutdownMsg:
    def __init__(self, rank=None):
        self.rank = rank  # deregisters the rank from liveness tracking


# epoch-exempt: drain intent is epoch-agnostic by design — the rank is
# leaving whichever world it lands in; the reconfiguration it triggers
# mints the next epoch itself, and a duplicate/straggler drain for an
# already-departed rank is a no-op
class DrainMsg:
    """A rank announces planned departure: it received the preemption
    notice (SIGTERM) and asks the coordinator to reconfigure the job
    without it at the next collective boundary (docs/checkpoint.md)."""

    def __init__(self, rank):
        self.rank = rank


class DrainAck:
    def __init__(self, ok, reason=""):
        self.ok = ok          # False: drain not survivable, die as preempted
        self.reason = reason


def _wire_dtype(arr):
    """(native-endian array, wire dtype string).  Extension dtypes
    (bfloat16) have opaque ``.str`` so they travel by name; fixed-width
    bytes/str keep ``.str`` (their ``.name`` doesn't round-trip); any
    non-native byte order is normalized before the bytes hit the wire."""
    dt = arr.dtype
    if dt.kind in "SU":
        return arr, dt.str
    if dt.byteorder == ">":
        arr = arr.astype(dt.newbyteorder("="))
    return arr, arr.dtype.name


def _decode(msg):
    return np.frombuffer(msg.payload, dtype=np.dtype(msg.dtype)).reshape(
        msg.shape)


def _encode(arr):
    arr = np.asarray(arr)
    # ascontiguousarray promotes 0-d to 1-d; keep the true shape
    shape = arr.shape
    arr, dtype = _wire_dtype(arr)
    return ResultMsg(payload=np.ascontiguousarray(arr).tobytes(),
                     shape=shape, dtype=dtype)


def _signature(msg) -> bytes:
    """Validation-relevant fields of a request (reference: the response
    cache key is tensor name + params, ``response_cache.h:45``)."""
    parts = (msg.req_type, msg.op, msg.dtype, tuple(msg.shape),
             msg.root_rank, tuple(msg.splits or ()), msg.prescale,
             msg.postscale, bool(msg.ring),
             getattr(msg, "compression", "none"),
             getattr(msg, "schedule", "auto"),
             # group id + membership join the signature (docs/groups.md:
             # the same tensor name in two groups must never validate —
             # or cache — against the other's round)
             getattr(msg, "group", ""),
             tuple(getattr(msg, "group_ranks", None) or ()))
    return hashlib.sha1(repr(parts).encode()).digest()


# ---------------------------------------------------------------- entry
class _Entry:
    """One named collective being negotiated (reference: the coordinator's
    message table, controller.cc:62)."""

    def __init__(self, req_type, group="", group_ranks=None):
        self.req_type = req_type
        self.group = group              # "" = world (docs/groups.md)
        self.group_ranks = group_ranks  # tuple | None
        self.requests = {}   # rank -> CollectiveMsg
        self.results = {}    # rank -> ResultMsg
        self.done = threading.Event()
        self.first_ts = time.monotonic()
        self.stall_warned = False

    def expected_ranks(self, size):
        """The ranks whose contribution completes this entry: the
        group's members, or the full world."""
        return (self.group_ranks if self.group else range(size))


class CoordinatorService(network.MuxService):
    """Rank 0's collective coordinator (persistent mux connections)."""

    NAME = "horovod_tpu coordinator"

    def __init__(self, size, key, stall_warning_sec=60.0,
                 stall_shutdown_sec=0.0, cache_capacity=1024,
                 autotune=None, liveness_timeout_sec=0.0, epoch=0,
                 elastic=None, straggler_factor=None,
                 straggler_windows=None, straggler_exclude=False):
        self._size = size
        # membership epoch this coordinator serves; a CollectiveMsg
        # stamped with a different epoch is refused (stale negotiation
        # from a torn-down membership must not form entries here)
        self._epoch = epoch
        # ElasticContext (rank 0, HVD_TPU_ELASTIC=1) or None: consulted
        # by _initiate_abort to rewrite a survivable failure into a
        # reconfiguration directive instead of a fatal abort
        self._elastic = elastic
        self._stall_warning = stall_warning_sec
        self._stall_shutdown = stall_shutdown_sec
        self._liveness = liveness_timeout_sec
        self._cv = threading.Condition()
        self._forming = {}          # name -> _Entry; guarded by self._cv
        self._joined = set()        # guarded by self._cv
        # (rank, Event, [last_rank]); guarded by self._cv
        self._join_waiters = []
        # rank -> monotonic ts of last message; guarded by self._cv
        self._last_seen = {}
        # ranks whose LAST heartbeat carried the busy flag (checkpoint
        # write / drain teardown in progress): liveness doubles their
        # deadline so slow disk I/O can't read as death; guarded by
        # self._cv
        self._busy_ranks = set()
        # ranks whose last heartbeat reported a session heal in flight
        # (docs/fault_tolerance.md "connection blips vs dead peers"):
        # treated as busy for liveness AND exempt from straggler
        # verdicts — a recovering link is never converted into an
        # exclusion or an abort; guarded by self._cv
        self._reconnecting_ranks = set()
        # ranks that announced a graceful drain: excluded from liveness
        # blame entirely — silence is their planned departure, not a
        # death to abort over; guarded by self._cv
        self._draining = set()
        # degraded-network tolerance (docs/fault_tolerance.md): each
        # rank's self-reported worst link RTT EWMA widens its liveness
        # window by an ADDITIVE slack (composing with — never
        # double-doubling — the multiplicative busy factor), and a rank
        # whose RTT stays over factor x median for ``windows``
        # consecutive scans earns a straggler verdict
        self._straggler_factor = (
            env_util.get_float(env_util.HVD_TPU_STRAGGLER_FACTOR,
                               env_util.DEFAULT_STRAGGLER_FACTOR)
            if straggler_factor is None else straggler_factor)
        self._straggler_windows = (
            env_util.get_int(env_util.HVD_TPU_STRAGGLER_WINDOWS,
                             env_util.DEFAULT_STRAGGLER_WINDOWS)
            if straggler_windows is None else straggler_windows)
        self._straggler_exclude = straggler_exclude
        self._peer_rtt = {}        # rank -> seconds; guarded by self._cv
        # rank -> launcher host hash carried on heartbeats: the raw
        # material for hierarchical group planning; guarded by self._cv
        self._host_of = {}
        # rank -> consecutive over-threshold scans; guarded by self._cv
        self._straggler_hits = {}
        # rank -> verdict dict, sticky; guarded by self._cv
        self._straggler_verdicts = {}
        # monotonic ts of the last O(N) liveness scan (the scan is
        # time-gated, not per-heartbeat); guarded by self._cv
        self._last_liveness_scan = 0.0
        # (origin_rank, reason), sticky: written once under self._cv;
        # guarded by self._cv (the lock-free reads below are annotated —
        # a stale None is at worst one poll late, never wrong)
        self._abort = None
        self._sig_cache = SignatureCache(cache_capacity)
        self._ring_seq = 0     # unique id per ring round; guarded by self._cv
        self._autotune = autotune        # rank-0-owned manager | None
        # (seq, tuned knob dict); guarded by self._publish_lock
        self._published = None
        self._publish_lock = threading.Lock()
        self._log = get_logger()
        super().__init__(self.NAME, key)

    # ----------------------------------------------------------- negotiation
    def _handle(self, req, client_address):
        rank = getattr(req, "rank", None)
        if rank is not None:
            with self._cv:
                self._last_seen[rank] = time.monotonic()
                if isinstance(req, network.HeartbeatMsg):
                    # getattr: a pre-busy-field peer's heartbeat simply
                    # never widens its window
                    rec = getattr(req, "reconnecting", None)
                    if getattr(req, "busy", False) or rec:
                        self._busy_ranks.add(rank)
                    else:
                        self._busy_ranks.discard(rank)
                    if rec:
                        self._reconnecting_ranks.add(rank)
                    else:
                        self._reconnecting_ranks.discard(rank)
                    rtt = getattr(req, "rtt", None)
                    if rtt is not None:
                        self._peer_rtt[rank] = float(rtt)
                    host = getattr(req, "host", None)
                    if host is not None:
                        self._host_of[rank] = host
        if isinstance(req, CollectiveMsg):
            return self._handle_collective(req)
        if isinstance(req, JoinMsg):
            return self._handle_join(req)
        if isinstance(req, DrainMsg):
            return self._handle_drain(req)
        if isinstance(req, network.HeartbeatMsg):
            self._check_liveness()
            # sticky set-once flag: a stale None here is one heartbeat
            # late, never wrong
            return network.HeartbeatReply(abort=self._abort)  # hvd-lint: ignore[lock-discipline]
        if isinstance(req, network.AbortMsg):
            self._initiate_abort(req.origin_rank, req.reason)
            return network.AckResponse()
        if isinstance(req, ShutdownMsg):
            # a cleanly-departing rank stops heartbeating BY DESIGN: it
            # must leave the liveness table, or a survivor doing slow
            # post-training work would trip a spurious "presumed dead"
            # abort on its stale last-seen entry
            if req.rank is not None:
                with self._cv:
                    self._last_seen.pop(req.rank, None)
                    self._busy_ranks.discard(req.rank)
                    self._reconnecting_ranks.discard(req.rank)
                    self._draining.discard(req.rank)
                    self._peer_rtt.pop(req.rank, None)
                    self._straggler_hits.pop(req.rank, None)
                    self._host_of.pop(req.rank, None)
            return network.AckResponse()
        return super()._handle(req, client_address)

    def wait_for_departures(self, own_rank):
        """Job end on the coordinator's host, which leaves last: return
        once every other rank has deregistered (its ``ShutdownMsg``), has
        been silent for its liveness window (it left without saying so),
        or an abort stands.  A rank still inside the last collective
        (its go-ahead not yet written, its session mid-resume) needs
        this service after rank 0 itself is through with it; closing at
        once, the rank fails its last collective naming the coordinator
        (reference: the background loop ends when EVERY rank has asked
        to shut down)."""
        while True:
            now = time.monotonic()
            with self._cv:
                if self._abort is not None:
                    return
                # (with liveness off a silent rank is given the window
                # it would have by default)
                windows = {
                    r: (self._deadline_for_locked(r) if self._liveness > 0
                        else env_util.DEFAULT_LIVENESS_TIMEOUT_SECONDS)
                    for r in self._last_seen
                    if r != own_rank and r not in self._draining}
                if not any(now - self._last_seen[r] <= window
                           for r, window in windows.items()):
                    return
            time.sleep(0.02)

    # -------------------------------------------------- abort + liveness
    def _abort_result(self):
        # sticky flag, set-once before the waiter events fire: callers
        # only reach here after observing it non-None
        origin, reason = self._abort  # hvd-lint: ignore[lock-discipline]
        return ResultMsg(
            error=f"collective runtime aborted (origin rank {origin}): "
                  f"{reason}",
            aborted=(origin, reason))

    def _initiate_abort(self, origin_rank, reason):
        """Coordinated abort (reference analog: the stall inspector's
        shutdown path, promoted from a log line into action): fail every
        negotiating rank NOW with one typed, symmetric error; ranks not
        currently negotiating learn the abort from their next heartbeat
        reply.  Sticky — the surviving ranks are expected to unwind.

        With an ElasticContext attached, a survivable failure is
        rewritten into a membership-reconfiguration directive BEFORE the
        sticky flag is set: the same fan-out then delivers "re-form at
        epoch N+1" instead of "die" (docs/elastic.md)."""
        # plan() runs outside the lock (it talks to the rendezvous
        # server); idempotence is re-checked under the lock, and the
        # plan itself is sticky, so a racing second abort just reads
        # the cached directive.  A reason that already IS a directive
        # (the drain path planned before calling here) passes through
        # unchanged.
        if (self._elastic is not None and self._abort is None  # hvd-lint: ignore[lock-discipline]
                and not (isinstance(reason, str)
                         and reason.startswith(RECONFIG_MARKER))):
            planned = self._elastic.plan(origin_rank, reason)
            if planned is not None:
                reason = planned
        with self._cv:
            if self._abort is not None:
                return
            self._abort = (origin_rank, reason)
            # satellite bugfix: a signature validated pre-abort must not
            # short-circuit validation after a reconfiguration reuses
            # the same tensor names with a different membership
            self._sig_cache.clear()
            forming, self._forming = self._forming, {}
            waiters, self._join_waiters = self._join_waiters, []
            self._joined.clear()
        self._log.error("coordinated abort (origin rank %s): %s",
                        origin_rank, reason)
        for entry in forming.values():
            entry.results = {r: self._abort_result()
                             for r in entry.requests}
            entry.done.set()
        for _, event, slot in waiters:
            slot[0] = None  # join handler converts to a typed error
            event.set()

    def _handle_drain(self, req):
        """Graceful drain (docs/checkpoint.md): exempt the announcing
        rank from liveness blame, plan a reconfiguration WITHOUT it,
        wait for the next collective boundary, then publish the
        directive through the ordinary abort delivery (minus the peer
        fan-out — ``is_drain_reason`` delivery is pull-only).  Runs on
        this request's own mux thread, so blocking here blocks nobody
        else."""
        rank = req.rank
        with self._cv:
            if self._abort is not None:
                # a failure (or another drain) beat this announcement;
                # the rank leaves through whatever is already in flight
                return DrainAck(False, "abort already in flight")
            self._draining.add(rank)
        directive = (self._elastic.plan_drain(rank)
                     if self._elastic is not None else None)
        if directive is None:
            with self._cv:
                self._draining.discard(rank)
            return DrainAck(
                False, "drain not survivable: elastic disabled, "
                       "coordinator rank, or too few survivors")
        # collective boundary: no entry mid-negotiation.  Polled OUTSIDE
        # _cv (the wait must not starve negotiations, and
        # _initiate_abort below re-acquires it).  Bounded: a steady
        # stream of collectives may never leave _forming observably
        # empty, and a late directive is still correct — it just fails
        # one in-flight round into the reconfiguration.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._cv:
                if self._abort is not None or not self._forming:
                    break
            time.sleep(0.005)
        self._initiate_abort(rank, directive)
        return DrainAck(True)

    def _deadline_for_locked(self, r):  # holds: self._cv
        """Effective liveness window for rank ``r``: the busy factor
        MULTIPLIES the base window (slow local I/O scales everything),
        the RTT slack ADDS to it (a slow link delays delivery by a
        bounded absolute amount) — composed, never double-doubled."""
        base = self._liveness * (2.0 if r in self._busy_ranks else 1.0)
        return base + self._rtt_slack_locked(r)

    def _rtt_slack_locked(self, r):  # holds: self._cv
        """Additive deadline slack from the rank's self-reported RTT
        EWMA, capped at factor x the base window so a pathological
        report cannot make the rank effectively unkillable."""
        return min(self._peer_rtt.get(r, 0.0) * self._straggler_factor,
                   self._liveness * self._straggler_factor)

    def _check_liveness(self):
        """Convert a silently-dead peer (no message within its adaptive
        liveness window) into a coordinated abort instead of an
        indefinite wait.

        A rank whose last heartbeat was busy-flagged (checkpoint write /
        drain teardown) gets a doubled window; a rank reporting a high
        link RTT gets an additive slack (slow is not dead,
        docs/fault_tolerance.md "degraded networks"); a rank that
        announced a drain is never blamed at all — its silence is the
        planned departure."""
        # sticky-flag fast path; _initiate_abort re-checks under the lock
        if self._liveness <= 0 or self._abort is not None:  # hvd-lint: ignore[lock-discipline]
            return
        now = time.monotonic()
        with self._cv:
            # the O(N) table scan runs at most ~10x per window — on
            # every heartbeat it would be O(N^2) per window at 64
            # ranks, a measured rank-0 hot spot in the soak rig
            if now - self._last_liveness_scan < self._liveness / 10.0:
                return
            self._last_liveness_scan = now
            dead = sorted(
                r for r, ts in self._last_seen.items()
                if now - ts > self._deadline_for_locked(r)
                and r not in self._joined and r not in self._draining)
            window = self._deadline_for_locked(dead[0]) if dead else 0.0
            straggler = None if dead else self._straggler_scan_locked()
        if dead:
            self._initiate_abort(
                dead[0],
                f"rank {dead[0]} sent no heartbeat for more than "
                f"{window:g}s (presumed dead)")
        elif straggler is not None:
            # boundary-wait + plan_drain can block; never on a
            # heartbeat handler thread.  lifecycle: daemon, one-shot
            threading.Thread(
                target=self._propose_straggler_exclusion,
                args=(straggler,), daemon=True,
                name="hvd-straggler-drain").start()

    def _straggler_scan_locked(self):  # holds: self._cv
        """k x median straggler verdict: a rank whose reported RTT EWMA
        exceeds ``straggler_factor`` x the median of all reports for
        ``straggler_windows`` consecutive scans is recorded (and
        logged) as a straggler.  Returns a rank to propose for
        drain-style exclusion, or None (exclusion is opt-in and
        elastic-only — the default verdict is a report, not an
        eviction)."""
        if len(self._peer_rtt) < 3:
            return None  # no meaningful median from fewer peers
        med = rtt_mod.median(self._peer_rtt.values())
        exclude = None
        for r, value in self._peer_rtt.items():
            if r in self._reconnecting_ranks:
                # a healing link inflates RTT by construction; a
                # reconnect in progress must never ripen into a
                # straggler verdict (docs/fault_tolerance.md
                # "connection blips vs dead peers")
                self._straggler_hits.pop(r, None)
                continue
            if not (med > 0 and value > self._straggler_factor * med):
                self._straggler_hits.pop(r, None)
                continue
            self._straggler_hits[r] = self._straggler_hits.get(r, 0) + 1
            if (self._straggler_hits[r] >= self._straggler_windows
                    and r not in self._straggler_verdicts):
                self._straggler_verdicts[r] = {
                    "rank": r, "rtt": value, "median": med,
                    "factor": self._straggler_factor}
                self._log.warning(
                    "straggler verdict: rank %d RTT %.3fs > %g x "
                    "median %.3fs for %d consecutive windows", r,
                    value, self._straggler_factor, med,
                    self._straggler_hits[r])
                if exclude is None:
                    exclude = r
        if (exclude is not None and self._straggler_exclude
                and self._elastic is not None):
            return exclude
        return None

    def straggler_verdicts(self):
        """Recorded straggler verdicts (rank -> verdict dict) — the
        soak rig's regression artifact reads these off the logs; tests
        read them here."""
        with self._cv:
            return {r: dict(v)
                    for r, v in self._straggler_verdicts.items()}

    def _propose_straggler_exclusion(self, rank):
        """Drain-style exclusion of a confirmed straggler
        (HVD_TPU_STRAGGLER_EXCLUDE, elastic only): same protocol as a
        granted drain — plan a membership without the rank, wait for a
        collective boundary, deliver the drain-marked directive
        pull-only.  Nothing crashed, so nothing aborts: survivors
        reconfigure, the straggler exits cleanly."""
        with self._cv:
            if self._abort is not None or rank in self._draining:
                return
            self._draining.add(rank)
        directive = self._elastic.plan_drain(
            rank, cause=f"rank {rank} excluded as confirmed straggler")
        if directive is None:
            with self._cv:
                self._draining.discard(rank)
            return
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with self._cv:
                if self._abort is not None or not self._forming:
                    break
            time.sleep(0.005)
        self._initiate_abort(rank, directive)

    def _ready(self, entry):  # holds: self._cv
        """Ready once every live (non-joined) rank has contributed — a
        raw count would let a since-joined rank's own request stand in
        for a live rank's missing one (silent wrong result).  A grouped
        entry waits for exactly its members: joins are a world-level
        protocol, so they never stand in for a group rank."""
        if entry.group:
            return set(entry.group_ranks) <= entry.requests.keys()
        live = set(range(self._size)) - self._joined
        return live <= entry.requests.keys()

    def _handle_collective(self, req):
        if getattr(req, "epoch", 0) != self._epoch:
            # stale membership epoch: a straggler negotiation from a
            # torn-down world must not form entries at this coordinator
            return ResultMsg(error=(
                f"stale membership epoch {getattr(req, 'epoch', 0)} for "
                f"tensor '{req.name}' (coordinator is at epoch "
                f"{self._epoch})"))
        # (group, name) is THE negotiation key: the same tensor name in
        # two groups forms two independent entries that can be in
        # flight concurrently (docs/groups.md)
        key = (getattr(req, "group", ""), req.name)
        with self._cv:
            if self._abort is not None:
                return self._abort_result()
            entry = self._forming.get(key)
            if entry is None:
                entry = _Entry(req.req_type, group=key[0],
                               group_ranks=getattr(req, "group_ranks",
                                                   None))
                self._forming[key] = entry
            if req.rank in entry.requests:
                return ResultMsg(error=(
                    f"duplicate request for tensor '{req.name}' from rank "
                    f"{req.rank} before previous one completed"))
            entry.requests[req.rank] = req
            gids = {g for (g, _) in self._forming}
            if self._ready(entry):
                self._complete(key, entry)
                self._check_join_barrier()
        # concurrency gauge (read by the acceptance tests): distinct
        # groups simultaneously negotiating at this coordinator
        from horovod_tpu import groups as groups_mod
        groups_mod.note_inflight(gids)
        # Wait outside negotiation state; requests run on their own mux
        # threads, so blocking here is the reference's "wait for the
        # response list" on this rank.
        deadline = (time.monotonic() + self._stall_shutdown
                    if self._stall_shutdown > 0 else None)
        while not entry.done.wait(timeout=1.0):
            # sticky-flag poll; the typed result is taken under the lock
            if self._abort is not None:  # hvd-lint: ignore[lock-discipline]
                # abort raced entry creation: take the typed result (and
                # drop the orphaned entry so it can't pin the join
                # barrier)
                with self._cv:
                    if self._forming.get(key) is entry:
                        del self._forming[key]
                return self._abort_result()
            age = time.monotonic() - entry.first_ts
            # hvd-race: ok[racy fast-path check only; warn-once is
            # decided by the re-check under the lock below]
            if age > self._stall_warning and not entry.stall_warned:
                with self._cv:
                    already, entry.stall_warned = entry.stall_warned, \
                        True
                    missing = [r for r in entry.expected_ranks(self._size)
                               if r not in entry.requests
                               and r not in self._joined]
                    ready = sorted(entry.requests)
                    if not already:
                        # reference: InvalidateStalledCachedTensors
                        self._sig_cache.evict(self._cache_name(key))
                if not already:
                    self._log.warning(
                        "Stalled tensor: %s ready ranks: %s, waiting "
                        "on: %s for more than %ds", req.name, ready,
                        missing, int(self._stall_warning))
            if deadline is not None and time.monotonic() > deadline:
                # stall shutdown, promoted into a coordinated abort: the
                # first missing rank is the culprit, EVERY rank (not just
                # this entry's waiters) raises the same typed error, and
                # ring state everywhere is purged via the abort broadcast
                with self._cv:
                    missing = [r for r in entry.expected_ranks(self._size)
                               if r not in entry.requests
                               and r not in self._joined]
                origin = missing[0] if missing else req.rank
                self._initiate_abort(
                    origin,
                    f"stalled tensor '{req.name}' exceeded shutdown "
                    f"threshold of {self._stall_shutdown}s (waiting on "
                    f"ranks {missing})")
                break
        # sticky-flag read: once done fired, results are immutable
        if self._abort is not None and req.rank not in entry.results:  # hvd-lint: ignore[lock-discipline]
            return self._abort_result()
        # hvd-race: ok[results written before done.set(); immutable and
        # deliberately lock-free once the done event ordered this read]
        return entry.results.get(req.rank,
                                 ResultMsg(error="internal: no result"))

    def _handle_join(self, req):
        event = threading.Event()
        slot = [None]
        with self._cv:
            if self._abort is not None:
                return JoinDoneMsg(None, abort=self._abort)
            self._joined.add(req.rank)
            self._join_waiters.append((req.rank, event, slot))
            # a rank joining may complete entries now only missing it
            for key, entry in list(self._forming.items()):
                if entry.requests and self._ready(entry):
                    self._complete(key, entry)
            self._check_join_barrier()
        # wakeable: _initiate_abort and _check_join_barrier both set
        # every registered join-waiter event (tested by test_stall's
        # join-barrier abort coverage)
        event.wait()
        # sticky flag: the abort path set slot[0]=None before event.set
        if slot[0] is None and self._abort is not None:  # hvd-lint: ignore[lock-discipline]
            return JoinDoneMsg(None, abort=self._abort)  # hvd-lint: ignore[lock-discipline]
        return JoinDoneMsg(slot[0])

    def _check_join_barrier(self):  # holds: self._cv
        # all ranks joined and nothing pending -> release joins (reference:
        # controller joined handling: the join barrier completes only when
        # the tensor table is empty)
        if (len(self._joined) == self._size and not self._forming
                and self._join_waiters):
            last_rank = self._join_waiters[-1][0]
            for _, event, slot in self._join_waiters:
                slot[0] = last_rank
                event.set()
            self._join_waiters.clear()
            self._joined.clear()

    # ------------------------------------------------------------- execution
    def _complete(self, key, entry):  # holds: self._cv
        """Validate cross-rank agreement and compute every rank's result
        (reference: ConstructResponse validation + the backend op).
        ``key`` is the (group, name) negotiation key."""
        del self._forming[key]
        reqs = entry.requests
        try:
            results = self._execute(key, entry)
        except Exception as exc:  # noqa: BLE001 — done MUST be set: the
            # entry left _forming already, so an unset event would spin
            # every waiting rank forever with no stall escape
            results = {r: ResultMsg(error=str(exc)) for r in reqs}
        if self._autotune is not None:
            # only SUCCESSFUL entries score the tuner: a failed
            # collective transferred nothing, and counting its bytes
            # would inflate bytes/sec for whatever knob values were
            # active (the gmesh coordinator records validated-only for
            # the same reason)
            if not any(r.error or r.resend for r in results.values()):
                first = next(iter(reqs.values()))
                self._autotune.record(
                    np.dtype(first.dtype).itemsize
                    * int(np.prod(first.shape or (1,))))
            upd = self._autotune.maybe_update()
            if upd is not None:
                # publish: result messages carry the new values
                # (reference: SynchronizeParameters — rank 0 tunes,
                # winners ride the coordinator's responses).  Today both
                # _complete call sites already hold self._cv, so stores
                # are serialized; the lock + newer-seq guard are
                # DEFENSIVE, so a future call site outside _cv cannot
                # roll a later stamp back and leave ranks on stale
                # knobs until the next value change.
                with self._publish_lock:
                    if (self._published is None
                            or upd[0] > self._published[0]):
                        self._published = upd
                        self._sig_cache.enabled = upd[1]["cache_enabled"]
        # latest-wins advisory read: a racing publish just means the
        # stamp rides the next entry
        stamped = self._published  # hvd-lint: ignore[lock-discipline]
        if stamped is not None:
            # stamp HERE (one point per entry), not at each rank's
            # return: every rank of the same collective must see the
            # same (seq, params) — the "same cycle boundary" contract
            seq, params = stamped
            for resp in results.values():
                resp.params_seq, resp.params = seq, params
        entry.results = results
        entry.done.set()

    @property
    def cache_hits(self):
        return self._sig_cache.hits

    @staticmethod
    def _cache_name(key):
        """Signature-cache key for a (group, name) entry: group-
        qualified so the same tensor name in two groups can never hit
        the other's cached validation (docs/groups.md)."""
        group, name = key
        return f"g:{group}:{name}" if group else name

    def _cache_check(self, key, entry) -> bool:
        """Response-cache fast path (reference: response_cache.cc) — a
        steady-state name whose every rank resubmits the exact signature
        of the last validated round skips re-validation."""
        return self._sig_cache.check(
            self._cache_name(key), (r.sig for r in entry.requests.values()))

    def _cache_store(self, key, entry):
        self._sig_cache.store(
            self._cache_name(key), (r.sig for r in entry.requests.values()))

    def _ring_seg(self):
        """Coordinator-resolved pipeline segment size for a ring round:
        the latest published tuned value, or None before any
        publication (all ranks then share the identical launch-env
        value).  Stamped onto every ring_go so both endpoints of every
        hop derive the same segment plan even while a tuned value is
        still propagating rank by rank."""
        # latest-wins advisory read (see _complete)
        published = self._published  # hvd-lint: ignore[lock-discipline]
        if published is not None \
                and "ring_segment_bytes" in published[1]:
            return int(published[1]["ring_segment_bytes"])
        return None

    def _sched(self):
        """Latest published tuned schedule (autotune walk probing the
        schedule knob), or None when unpublished / left on auto."""
        # latest-wins advisory read (see _complete)
        published = self._published  # hvd-lint: ignore[lock-discipline]
        if published is not None and "schedule" in published[1]:
            val = published[1]["schedule"]
            if val and val != "auto":
                return str(val)
        return None

    def _plan_groups(self, participants):  # holds: self._cv
        """Partition ``participants`` into co-located groups for the
        hierarchical schedule.  Precedence: an explicit
        ``HVD_HIER_LOCAL_SIZE`` (> 0) chunks the sorted membership (the
        deterministic override, and the only grouping available on a
        single host); otherwise the launcher host hashes carried on
        heartbeats.  Returns None when no two-level plan exists (every
        rank on one host, or one rank per host, or unknown topology).
        Planned per collective from live membership, so an elastic
        reconfiguration that breaks a host group re-plans automatically
        — and because the plan is stamped on the ring_go, every
        survivor executes the identical (digest-identical) grouping."""
        ranks = sorted(participants)
        local = env_util.get_int(env_util.HVD_HIER_LOCAL_SIZE, 0)
        if local > 0:
            groups = [ranks[i:i + local]
                      for i in range(0, len(ranks), local)]
        else:
            by_host = {}
            for r in ranks:
                host = self._host_of.get(r)
                if host is None:
                    return None     # unknown topology: stay flat
                by_host.setdefault(host, []).append(r)
            groups = sorted(by_host.values(), key=lambda g: g[0])
        if len(groups) < 2 or all(len(g) == 1 for g in groups):
            return None
        return groups

    def _resolve_schedule(self, reqs, participants, nbytes):
        """Resolve the collective schedule for one ring round (same
        role as the compression resolution: unanimous request wins,
        disagreement falls back to auto).  Auto picks rhd in the
        latency-bound small-tensor regime, hierarchical when the
        topology offers co-located groups, flat ring otherwise.  A
        forced-but-infeasible hierarchical degrades to the flat ring;
        "star" reaching a ring round (possible mid-propagation of a
        tuned value) likewise runs flat — the star IS the payload
        path, decided worker-side before the ring_go."""
        from horovod_tpu.ops.python_controller import PythonController

        sched = PythonController.resolve_group_schedule(
            getattr(r, "schedule", "auto") for r in reqs.values())
        if sched == "auto":
            sched = self._sched() or "auto"
        groups = None
        if sched in ("auto", "hierarchical"):
            groups = self._plan_groups(participants)
        if sched == "auto":
            if (DEFAULT_RHD_MIN_BYTES <= nbytes <= DEFAULT_RHD_MAX_BYTES
                    and len(participants) > 1):
                sched = "rhd"
            elif groups is not None:
                sched = "hierarchical"
            else:
                sched = "flat_ring"
        if sched == "hierarchical" and groups is None:
            sched = "flat_ring"
        if sched != "hierarchical":
            groups = None
        if sched == "star":
            sched = "flat_ring"
        return sched, groups

    def _next_ring_id(self, group):  # holds: self._cv
        """Coordinator-assigned id for one ring round.  Grouped rounds
        live in a per-group namespace ("g<gid>:<seq>") so purge/straggler
        drops at the peer mailbox stay group-scoped (docs/groups.md);
        world rounds keep the bare integer for wire compatibility."""
        self._ring_seq += 1
        return f"g{group}:{self._ring_seq}" if group else self._ring_seq

    def _execute(self, key, entry):  # holds: self._cv
        _, name = key
        reqs = entry.requests
        first = next(iter(reqs.values()))
        rtype = RequestType(first.req_type)
        cached = self._cache_check(key, entry)
        # a grouped collective's "world" is its member list
        gsize = len(entry.group_ranks) if entry.group else self._size

        if not cached:
            for r in reqs.values():
                if r.req_type != first.req_type:
                    raise ValueError(
                        f"mismatched collective types for tensor "
                        f"'{first.name}'")
                if r.dtype != first.dtype:
                    raise ValueError(
                        f"mismatched dtypes for tensor '{first.name}'")

        # The coordinator RESOLVES the data plane: any rank asking for
        # the ring wins (thresholds can transiently disagree while
        # autotuned values propagate; every rank holds its array locally
        # so ring_go is always executable).  When the ring is infeasible
        # but payload-less requests exist, everyone resends with payload.
        ring = any(r.ring for r in reqs.values())

        if self._joined and rtype in (RequestType.ALLGATHER,
                                      RequestType.BROADCAST,
                                      RequestType.ALLTOALL,
                                      RequestType.REDUCE_SCATTER):
            raise ValueError(f"{rtype.name} is not supported while ranks "
                             f"have joined")

        if rtype in (RequestType.ALLREDUCE, RequestType.ADASUM):
            if not cached:
                for r in reqs.values():
                    if r.shape != first.shape:
                        raise ValueError(
                            f"mismatched shapes for allreduce "
                            f"'{first.name}'")
                    if r.op != first.op or r.prescale != first.prescale \
                            or r.postscale != first.postscale:
                        raise ValueError(
                            f"mismatched reduce ops or scale factors for "
                            f"tensor '{first.name}'")
                self._cache_store(key, entry)
            if ring and rtype == RequestType.ALLREDUCE:
                participants = sorted(reqs.keys())
                rid = self._next_ring_id(entry.group)
                # coordinator-resolved wire format (same role as the
                # ring-vs-payload resolution): unanimous choice wins,
                # disagreement — e.g. tuned params applied at slightly
                # different times on different ranks — resolves to the
                # exact path instead of erroring
                from horovod_tpu.ops.python_controller import \
                    PythonController

                comp = PythonController.resolve_group_compression(
                    getattr(r, "compression", "none")
                    for r in reqs.values())
                count = 1
                for d in first.shape:
                    count *= int(d)
                try:
                    nbytes = count * np.dtype(first.dtype).itemsize
                except TypeError:
                    nbytes = count * 2      # extension dtype (bf16)
                sched, groups = self._resolve_schedule(
                    reqs, participants, nbytes)
                return {r: ResultMsg(ring_go=True,
                                     participants=participants,
                                     ring_id=rid,
                                     compression=comp,
                                     ring_segment_bytes=self._ring_seg(),
                                     schedule=sched, groups=groups)
                        for r in reqs}
            if ring and rtype == RequestType.ADASUM:
                participants = sorted(reqs.keys())
                p = len(participants)
                # grouped adasum always rides the payload path: the
                # distributed VHDD tree is laid out over world positions
                if (not entry.group and p == self._size
                        and p & (p - 1) == 0):
                    rid = self._next_ring_id(entry.group)
                    return {r: ResultMsg(
                        ring_go=True, participants=participants,
                        ring_id=rid,
                        ring_segment_bytes=self._ring_seg())
                        for r in reqs}
                # joined ranks (zero stand-ins at world tree positions)
                # or non-power-of-two world: only the payload path keeps
                # the reference tree semantics — uniform resend
                return {r: ResultMsg(resend=True) for r in reqs}
            # reaching here means ring resolved False: every rank
            # submitted a payload (ring=True implies payload=None and
            # takes the branches above)
            arrs = {r: _decode(m) for r, m in reqs.items()}
            if rtype == RequestType.ADASUM:
                out = self._adasum(arrs, first,
                                   ranks=entry.group_ranks
                                   if entry.group else None)
            else:
                out = self._allreduce(arrs, first, divisor=gsize)
            return {r: _encode(out) for r in reqs}

        if rtype == RequestType.REDUCE_SCATTER:
            if not cached:
                if not first.shape:
                    raise ValueError(
                        f"reduce_scatter '{first.name}': 0-d tensors are "
                        f"not supported; reshape to (1,) first")
                for r in reqs.values():
                    if r.shape != first.shape:
                        raise ValueError(
                            f"mismatched shapes for reduce_scatter "
                            f"'{first.name}'")
                    if r.op != first.op or r.prescale != first.prescale \
                            or r.postscale != first.postscale:
                        raise ValueError(
                            f"mismatched reduce ops or scale factors for "
                            f"tensor '{first.name}'")
                self._cache_store(key, entry)
            if ring:
                participants = sorted(reqs.keys())
                rid = self._next_ring_id(entry.group)
                from horovod_tpu.ops.python_controller import \
                    PythonController

                comp = PythonController.resolve_group_compression(
                    getattr(r, "compression", "none")
                    for r in reqs.values())
                return {r: ResultMsg(ring_go=True,
                                     participants=participants,
                                     ring_id=rid,
                                     compression=comp,
                                     ring_segment_bytes=self._ring_seg())
                        for r in reqs}
            # star path: reduce exactly like the allreduce (ascending-
            # rank float64/int64 sum), then hand each rank its row block
            # of the np.array_split partition
            arrs = {r: _decode(m) for r, m in reqs.items()}
            out = self._allreduce(arrs, first, divisor=gsize)
            participants = sorted(reqs.keys())
            counts = reduce_scatter_split_sizes(first.shape[0],
                                                len(participants))
            results = {}
            off = 0
            for i, r in enumerate(participants):
                results[r] = _encode(out[off:off + counts[i]])
                off += counts[i]
            return results

        if rtype == RequestType.ALLGATHER:
            shapes = {r: m.shape for r, m in reqs.items()}
            trailing = {s[1:] for s in shapes.values()}
            if any(not s for s in shapes.values()):
                raise ValueError(
                    f"allgather '{first.name}': 0-d tensors are not "
                    f"supported; reshape to (1,) first")
            if len(trailing) > 1:
                raise ValueError(
                    f"mismatched trailing dimensions for allgather "
                    f"'{first.name}'")
            if ring:
                participants = sorted(reqs.keys())
                dims0 = [shapes[r][0] for r in participants]
                rid = self._next_ring_id(entry.group)
                return {r: ResultMsg(ring_go=True,
                                     participants=participants,
                                     dims0=dims0, ring_id=rid,
                                     ring_segment_bytes=self._ring_seg())
                        for r in reqs}
            out = np.concatenate(
                [_decode(reqs[r]) for r in sorted(reqs)], axis=0)
            return {r: _encode(out) for r in reqs}

        if rtype == RequestType.BROADCAST:
            if not cached:
                for r in reqs.values():
                    if r.root_rank != first.root_rank:
                        raise ValueError(
                            f"mismatched root ranks for broadcast "
                            f"'{first.name}'")
                    if r.shape != first.shape:
                        raise ValueError(
                            f"mismatched shapes for broadcast "
                            f"'{first.name}'")
                self._cache_store(key, entry)
            if first.root_rank not in reqs:
                raise ValueError(
                    f"broadcast '{first.name}': root rank "
                    f"{first.root_rank} did not participate")
            if ring:
                participants = sorted(reqs.keys())
                rid = self._next_ring_id(entry.group)
                return {r: ResultMsg(ring_go=True,
                                     participants=participants,
                                     ring_id=rid,
                                     ring_segment_bytes=self._ring_seg())
                        for r in reqs}
            out = _decode(reqs[first.root_rank])
            return {r: _encode(out) for r in reqs}

        if rtype == RequestType.ALLTOALL:
            pieces = {}
            offsets = {}
            for r, m in reqs.items():
                if m.splits is None or len(m.splits) != gsize:
                    raise ValueError(
                        f"alltoall '{first.name}': splits must have one "
                        f"entry per rank ({gsize})")
                if sum(m.splits) != (m.shape[0] if m.shape else 0):
                    raise ValueError(
                        f"alltoall '{first.name}': splits sum "
                        f"{sum(m.splits)} != first dimension "
                        f"{m.shape[0] if m.shape else 0}")
                arr = _decode(m)
                off = 0
                offsets[r] = []
                for n in m.splits:
                    pieces[(r, len(offsets[r]))] = arr[off:off + n]
                    offsets[r].append(n)
                    off += n
            # splits rows are indexed by GROUP-LOCAL position for grouped
            # entries (the member order the group was declared with); for
            # the world the global rank is the index
            order = list(entry.group_ranks) if entry.group else sorted(reqs)
            out = {}
            for dst in reqs:
                di = order.index(dst) if entry.group else dst
                parts = [pieces[(src, di)] for src in order]
                recv_splits = [offsets[src][di] for src in order]
                res = _encode(np.concatenate(parts, axis=0))
                res.recv_splits = recv_splits
                out[dst] = res
            return out

        raise ValueError(f"unknown request type {rtype}")

    def _allreduce(self, arrs, first, divisor=None):
        acc = None
        for r in sorted(arrs):
            a = arrs[r].astype(np.float64) if is_float_dtype(
                arrs[r].dtype) else arrs[r].astype(np.int64)
            if first.prescale != 1.0:
                a = a * first.prescale
            acc = a if acc is None else acc + a
        if ReduceOp(first.op) == ReduceOp.AVERAGE:
            # the divisor is the collective's world: the process group's
            # size for grouped entries, the full size otherwise (joined
            # ranks still count — they contribute zeros by contract)
            acc = acc / (divisor or self._size)
        if first.postscale != 1.0:
            acc = acc * first.postscale
        return acc.astype(np.dtype(first.dtype))

    def _adasum(self, arrs, first, ranks=None):
        from horovod_tpu.ops.adasum import adasum_reference

        # joined ranks contribute zero stand-ins, like the device-mode
        # executor (zero norm -> plain addition); a grouped entry's tree
        # spans exactly its member list
        tensors = []
        for r in (ranks if ranks is not None else range(self._size)):
            if r in arrs:
                tensors.append(arrs[r])
            else:
                tensors.append(np.zeros(first.shape,
                                        dtype=np.dtype(first.dtype)))
        return adasum_reference(tensors).astype(np.dtype(first.dtype))


# ----------------------------------------------------------------- controller
class TcpController:
    """Per-process controller facade (same interface as the in-process
    controllers: enqueue / join / start / shutdown)."""

    def __init__(self, topology, executor, timeline, config, epoch=0,
                 members=None):
        self._topo = topology
        self._executor = executor
        self._timeline = timeline
        self._config = config
        self._rank = topology.rank
        self._size = topology.size
        # elastic membership (docs/elastic.md): the epoch names this
        # controller's generation of the world; rendezvous scopes are
        # suffixed with it so a re-formed job can never read the old
        # world's addresses.  ``members`` lists the stable worker ids in
        # new-rank order (None: pre-elastic identity mapping).
        self._epoch = epoch
        self._members = (list(members) if members is not None
                         else list(range(self._size)))
        self._coordinator = None
        self._client_addrs = None
        self._mux = None            # guarded by self._mux_lock
        self._mux_lock = threading.Lock()
        self._key = None
        # the peer mailbox: its own handler threads reach back for it
        # (a pushed abort), request threads purge through it, the main
        # thread's ``start`` and teardown set it
        self._peer_service = None   # guarded by self._abort_lock
        # the world's ring plane: request threads read it while the main
        # thread's teardown (shutdown, reconfiguration) takes it away
        self._ring = None           # guarded by self._rings_lock
        # per-group ring planes (docs/groups.md): each live group gets
        # its own RingPlane (own send queue, sender thread and stripe
        # connections) lazily on first grouped ring round, sharing the
        # one PeerService mailbox — the concurrency lever that lets two
        # groups' rounds be in flight at once
        self._rings = {}            # guarded by self._rings_lock
        self._rings_lock = threading.Lock()
        self._ring_threshold = env_util.get_int(
            env_util.HVD_TCP_RING_THRESHOLD, DEFAULT_RING_THRESHOLD)
        self._autotune = None       # rank 0 only
        # last applied (seq, params); guarded by self._tuned_lock
        self._tuned = None
        self._tuned_lock = threading.Lock()
        # (origin_rank, reason), sticky; guarded by self._abort_lock
        self._abort_state = None
        self._abort_lock = threading.Lock()
        # id(handle) -> handle (abort fan-out); guarded by self._abort_lock
        self._inflight = {}
        self._hb_stop = threading.Event()
        self._hb_thread = None
        self._host_hash_val = None      # cached launcher host identity
        self._log = get_logger()

    def _scope(self, base):
        """Rendezvous scope for this membership epoch.  Epoch 0 keeps
        the bare names (wire/rendezvous compatibility with every
        pre-elastic artifact); later epochs get a fresh namespace so
        survivors re-forming the job can never read the dead world's
        addresses."""
        return base if self._epoch == 0 else f"{base}.e{self._epoch}"

    def _start_timeout(self):
        # initial gang start keeps its own deadline; a reconfiguration
        # window is bounded by the (usually tighter) reconfig budget
        if self._epoch == 0:
            return env_util.get_float(env_util.HVD_START_TIMEOUT, 120.0)
        return self._config.reconfig_timeout_seconds

    # -------------------------------------------------------------- lifecycle
    def start(self):
        key_b64 = env_util.get_str(env_util.HVD_SECRET_KEY)
        if key_b64:
            self._key = base64.b64decode(key_b64)
        else:
            # standalone/testing: derive a per-job key from the rendezvous
            # location so all ranks agree
            seed = (env_util.get_str(env_util.HVD_RENDEZVOUS_ADDR,
                                     "local") +
                    env_util.get_str(env_util.HVD_RENDEZVOUS_PORT, "0"))
            self._key = hashlib.sha256(seed.encode()).digest()

        addr = env_util.get_str(env_util.HVD_RENDEZVOUS_ADDR)
        port = env_util.get_str(env_util.HVD_RENDEZVOUS_PORT)
        if self._rank == 0:
            from horovod_tpu.ops.autotune import AutotuneManager
            self._autotune = AutotuneManager.create(self._config,
                                                    self._log)
            elastic_ctx = None
            if self._config.elastic and addr is not None:
                from horovod_tpu.elastic.membership import ElasticContext
                elastic_ctx = ElasticContext(
                    members=self._members, epoch=self._epoch,
                    min_ranks=self._config.min_ranks,
                    max_ranks=self._config.max_ranks,
                    rendezvous=(addr, int(port)),
                    coord_failover=self._config.coord_failover)
            self._coordinator = CoordinatorService(
                self._size, self._key,
                stall_warning_sec=self._config.stall_warning_seconds,
                stall_shutdown_sec=self._config.stall_shutdown_seconds,
                cache_capacity=self._config.cache_capacity,
                autotune=self._autotune,
                liveness_timeout_sec=self._config.liveness_timeout_seconds,
                epoch=self._epoch, elastic=elastic_ctx,
                straggler_factor=self._config.straggler_factor,
                straggler_windows=self._config.straggler_windows,
                straggler_exclude=self._config.straggler_exclude)
            tagged = [(iface, ip, self._coordinator.port)
                      for iface, ip in network.local_interfaces().items()]
            tagged.append(("lo", "127.0.0.1", self._coordinator.port))
            if addr is not None:
                from horovod_tpu.run import http_client
                http_client.put(
                    addr, int(port), self._scope(CONTROLLER_SCOPE),
                    CONTROLLER_KEY,
                    ";".join(f"{i}={ip}:{p}"
                             for i, ip, p in tagged).encode())
                if self._epoch > 0:
                    # dead-epoch cleanup: the previous memberships'
                    # suffixed scopes would otherwise accumulate on the
                    # rendezvous server for the life of the job.  Every
                    # epoch < ours is torn down by construction (we are
                    # the reconfigured successor); best-effort — a
                    # leaked scope is garbage, not a correctness hazard.
                    # A GC watermark bounds the sweep to the epochs
                    # since the LAST purge: rescanning 0..k-1 on every
                    # reconfiguration is O(k^2) cumulative rendezvous
                    # calls — a rank-0 hot spot under elastic churn at
                    # soak scale.
                    purge_from = 0
                    try:
                        purge_from = int(http_client.get(
                            addr, int(port), GC_SCOPE, GC_PURGED_KEY,
                            timeout=2.0, retry_for=0).decode()) + 1
                    except Exception:  # noqa: BLE001 — first purge
                        pass
                    for e in range(purge_from, self._epoch):
                        suffix = "" if e == 0 else f".e{e}"
                        for base in (CONTROLLER_SCOPE, PEERS_SCOPE,
                                     TIMELINE_SCOPE):
                            try:
                                http_client.delete_scope(
                                    addr, int(port), f"{base}{suffix}")
                            except Exception:  # noqa: BLE001
                                pass
                    try:
                        http_client.put(
                            addr, int(port), GC_SCOPE, GC_PURGED_KEY,
                            str(self._epoch - 1).encode(),
                            retry_for=2.0)
                    except Exception:  # noqa: BLE001 — next purge
                        # just rescans from the stale watermark
                        pass
            self._client_addrs = self._filter_ifaces(tagged)
        else:
            if addr is None:
                raise RuntimeError(
                    "multi-process mode requires the rendezvous env "
                    "contract (launch with hvdrun)")
            from horovod_tpu.run import http_client
            blob = http_client.get(
                addr, int(port), self._scope(CONTROLLER_SCOPE),
                CONTROLLER_KEY, timeout=self._start_timeout()).decode()
            tagged = []
            for part in blob.split(";"):
                iface, rest = part.split("=", 1)
                ip, p = rest.rsplit(":", 1)
                tagged.append((iface, ip, int(p)))
            self._client_addrs = self._filter_ifaces(tagged)

        # peer mailbox for the ring data plane (epoch-stamped: stale
        # chunks from a pre-reconfiguration ring are refused at framing)
        peer_service = PeerService(self._key, epoch=self._epoch)
        # a peer-pushed abort must fail negotiation-blocked handles too,
        # not only blocked ring recvs (no re-fan-out: the pusher
        # already reached every peer)
        peer_service.abort_callback = self._on_peer_abort
        with self._abort_lock:
            self._peer_service = peer_service
        if addr is not None:
            from horovod_tpu.run import http_client
            tagged = [(iface, ip, peer_service.port)
                      for iface, ip in network.local_interfaces().items()]
            tagged.append(("lo", "127.0.0.1", peer_service.port))
            http_client.put(addr, int(port), self._scope(PEERS_SCOPE),
                            str(self._rank),
                            ";".join(f"{i}={ip}:{p}"
                                     for i, ip, p in tagged).encode())
            ring = RingPlane(
                self._rank, peer_service, self._resolve_peer,
                resolve_bulk=self._resolve_stripe,
                segment_bytes=self._config.ring_segment_bytes,
                stripes=self._config.ring_stripes,
                epoch=self._epoch)
            with self._rings_lock:
                self._ring = ring

        # peer liveness: a background heartbeat per worker keeps the
        # coordinator's last-seen table fresh AND carries the abort
        # state back, so a rank blocked on ring chunks (never touching
        # the control plane) still observes a coordinated abort within
        # one heartbeat interval
        from horovod_tpu.common.config import effective_heartbeat_interval
        interval = effective_heartbeat_interval(self._config)
        if self._size > 1 and interval > 0:
            # one synchronous beat before init returns: the coordinator
            # knows this rank exists BEFORE any user collective can run,
            # so a crash at ANY later point falls inside the liveness
            # window.  Failing this beat is fatal — a silently-skipped
            # registration would leave this rank invisible to liveness
            # (the monitor only watches ranks it has seen), reopening
            # the unbounded-hang window for the peers.  The mux client's
            # own connect retry already absorbed transient blips.
            try:
                t0 = time.monotonic()
                # the registration beat carries this rank's launcher
                # host hash: the coordinator needs the full topology
                # BEFORE the first collective so the hierarchical
                # schedule is plannable from round one
                self._client().send(
                    network.HeartbeatMsg(self._rank,
                                         host=self._host_hash()),
                    timeout=30.0)
                # seed the control-plane RTT EWMA with the very first
                # round-trip so the adaptive deadline starts from a
                # measured baseline, not from zero slack
                rtt_mod.tracker().sample(rtt_mod.COORD_KEY,
                                         time.monotonic() - t0)
            except Exception as exc:
                raise RuntimeError(
                    f"rank {self._rank} could not register with the "
                    f"coordinator at startup: {exc}") from exc
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, args=(interval,),
                daemon=True, name="hvd-heartbeat")
            self._hb_thread.start()

    def _peer_addrs(self, rank, resolve_timeout, retry_for=None):
        from horovod_tpu.run import http_client

        addr = env_util.get_str(env_util.HVD_RENDEZVOUS_ADDR)
        port = env_util.get_str(env_util.HVD_RENDEZVOUS_PORT)
        kwargs = {} if retry_for is None else {"retry_for": retry_for}
        blob = http_client.get(addr, int(port), self._scope(PEERS_SCOPE),
                               str(rank), timeout=resolve_timeout,
                               **kwargs).decode()
        tagged = []
        for part in blob.split(";"):
            iface, rest = part.split("=", 1)
            ip, p = rest.rsplit(":", 1)
            tagged.append((iface, ip, int(p)))
        return self._filter_ifaces(tagged)

    def _resolve_peer(self, rank):
        # epoch rides along so a session healing across a
        # reconfiguration is fenced by the peer's PeerService instead
        # of replaying a torn-down ring's frames into the new epoch
        return network.MuxClient(
            self._peer_addrs(rank, env_util.get_float(
                env_util.HVD_START_TIMEOUT, 120.0)),
            self._key, timeout=30, peer=rank, epoch=self._epoch)

    def _resolve_stripe(self, rank):
        """One dedicated bulk-data connection to ``rank``'s mailbox —
        the ring opens up to HVD_TPU_RING_STRIPES of these per peer,
        so chunk segments never share a socket (or a write lock) with
        control traffic."""
        return network.StripeClient(
            self._peer_addrs(rank, env_util.get_float(
                env_util.HVD_START_TIMEOUT, 120.0)),
            self._key, timeout=30, peer=rank, epoch=self._epoch)

    @staticmethod
    def _filter_ifaces(tagged):
        """Pin to the launcher-discovered interface when HVD_IFACE is set
        and the coordinator advertises it; otherwise keep every address
        (reference: NIC discovery exporting the common interface)."""
        iface = env_util.get_str(env_util.HVD_IFACE)
        pinned = [(ip, p) for i, ip, p in tagged if i == iface]
        return pinned or [(ip, p) for _, ip, p in tagged]

    def _client(self):
        # ONE persistent multiplexed connection (v2); concurrent
        # blocking requests ride separate mux frames.  Guarded: many
        # request threads hit first-use together (one burst per backward
        # pass) and unsynchronized construction leaks every loser's
        # socket + reader thread
        with self._mux_lock:
            if self._mux is None:
                self._mux = network.MuxClient(self._client_addrs,
                                              self._key, timeout=30,
                                              peer=0)
            return self._mux

    def _spawn(self, target, *args):
        # one daemon thread per in-flight request (a bounded pool of
        # blocking round-trips can deadlock: with >pool outstanding
        # collectives submitted in different per-rank orders, no name ever
        # has all contributions.  The reference's request inserts are
        # non-blocking for the same reason.)
        threading.Thread(target=target, args=args, daemon=True,
                         name="hvd-tcp-req").start()

    # ------------------------------------------------------- fault tolerance
    def _host_hash(self):
        """This rank's launcher host identity (run/host_hash.py),
        computed once: heartbeats carry it so the coordinator can group
        co-located ranks when planning the hierarchical schedule."""
        if self._host_hash_val is None:
            from horovod_tpu.run.host_hash import host_hash
            self._host_hash_val = host_hash()
        return self._host_hash_val

    def _heartbeat_loop(self, interval):
        # a DEDICATED no-retry client: the shared mux's connect retry
        # (HVD_TPU_CONNECT_RETRY_SECONDS per attempt) would stretch the
        # dead-coordinator budget below to a multiple of itself, and a
        # failed heartbeat must be cheap to observe
        hb_client = network.MuxClient(self._client_addrs, self._key,
                                      timeout=max(interval, 2.0),
                                      retry_for=0, peer=0)
        tracker = rtt_mod.tracker()
        fail_since = None
        try:
            while True:
                try:
                    t0 = time.monotonic()
                    # each beat carries the worst smoothed RTT this rank
                    # observes (control plane or ring acks): the
                    # coordinator widens this rank's liveness deadline
                    # by that slack, telling slow-but-alive from dead
                    reply = hb_client.send(
                        network.HeartbeatMsg(
                            self._rank,
                            busy=busy.active(),
                            rtt=tracker.worst() or None,
                            host=self._host_hash(),
                            # peers this rank is healing a session
                            # toward RIGHT NOW: the coordinator widens
                            # the liveness window and skips straggler
                            # verdicts instead of reading the recovery
                            # pause as death
                            reconnecting=network.healing_peers() or None),
                        timeout=max(interval * 2, 5.0))
                    tracker.sample(rtt_mod.COORD_KEY,
                                   time.monotonic() - t0)
                except Exception as exc:  # noqa: BLE001 — outage
                    now = time.monotonic()
                    fail_since = (fail_since if fail_since is not None
                                  else now)
                    # the abort deadline, not the liveness window,
                    # bounds how long this rank may spin against a dead
                    # coordinator; a measured-slow network widens the
                    # budget by the same capped slack the coordinator
                    # grants us, so both sides give up symmetrically
                    budget = (self._config.abort_timeout_seconds
                              or self._config.liveness_timeout_seconds)
                    if budget > 0:
                        budget += min(
                            tracker.worst()
                            * self._config.straggler_factor,
                            budget)
                    if budget > 0 and now - fail_since > budget:
                        # a dead coordinator must fail the job, not
                        # hang it: fail-over election when armed,
                        # else self-abort naming the coordinator
                        self._coordinator_lost(
                            f"coordinator unreachable for "
                            f"{int(now - fail_since)}s: {exc}")
                        return
                else:
                    fail_since = None
                    ab = getattr(reply, "abort", None)
                    if ab is not None:
                        self._learned_abort(*ab)
                        return
                # first beat went out BEFORE the first wait: the
                # coordinator learns this rank exists the moment init
                # completes, so a rank that dies at any later point is
                # inside the liveness window from its very first
                # collective
                if self._hb_stop.wait(timeout=interval):
                    return
        finally:
            hb_client.close()

    def _coordinator_lost(self, reason):
        """Every path that decides the coordinator is unreachable funnels
        here: with fail-over armed the survivors race the rendezvous CAS
        election and the winning reconfiguration directive replaces the
        fatal abort — the same typed delivery, a different verdict.  Not
        armed (or the election is not winnable): today's exact behavior,
        a fatal self-abort naming the coordinator rank."""
        directive = self._try_failover(reason)
        self._local_abort(0, directive if directive is not None
                          else reason)

    def _try_failover(self, reason):
        """Attempt the coordinator fail-over election
        (docs/elastic.md#coordinator-fail-over).  Returns the winning
        reconfiguration directive, or None when fail-over is off, not
        survivable (below --min-ranks), or the election cannot be won
        within HVD_TPU_ELECTION_TIMEOUT — every None falls back to the
        fatal path, byte-identical to fail-over-off behavior."""
        if not (self._config.coord_failover and self._config.elastic):
            return None
        if self._rank == 0 or self._size <= 1:
            # rank 0 IS the coordinator host: its own unreachability
            # verdict means this process is the casualty, not a survivor
            return None
        with self._abort_lock:
            if self._abort_state is not None:
                return None   # a verdict (or directive) already landed
        addr = env_util.get_str(env_util.HVD_RENDEZVOUS_ADDR)
        port = env_util.get_str(env_util.HVD_RENDEZVOUS_PORT)
        if addr is None or port is None:
            return None   # no rendezvous server, no election ground
        if len(self._members) - 1 < self._config.min_ranks:
            self._log.error(
                "fail-over: %d survivors < --min-ranks %d; coordinator "
                "loss is fatal", len(self._members) - 1,
                self._config.min_ranks)
            return None
        from horovod_tpu.elastic import election
        return election.elect(
            addr, int(port), self._epoch, self._members, reason,
            proposer_wid=self._members[self._rank],
            timeout=self._config.election_timeout_seconds)

    def _local_abort(self, origin_rank, reason, fan_out=True):
        """Apply a coordinated abort on this worker: purge the ring
        mailbox (waking every blocked ``recv`` with the typed error) and
        fail all in-flight handles symmetrically.  ``fan_out=False``
        when the abort ARRIVED as a peer push — the pushing rank already
        reached everyone, and N ranks each re-pushing to N-1 peers would
        be an O(N^2) storm of fresh rendezvous lookups mid-failure."""
        with self._abort_lock:
            if self._abort_state is not None:
                return
            self._abort_state = (origin_rank, reason)
            inflight = list(self._inflight.values())
            self._inflight.clear()
            peer_service = self._peer_service
        self._log.error("aborting collectives (origin rank %s): %s",
                        origin_rank, reason)
        # push to every peer mailbox BEFORE waking local waiters: a
        # waiter's thread may exit the process (taking the coordinator
        # with it on rank 0) the moment it observes the error, and the
        # peers must have heard by then — heartbeats remain the backstop
        # for peers the push cannot reach
        if fan_out:
            self._push_abort_to_peers(origin_rank, reason)
        if peer_service is not None:
            peer_service.abort(origin_rank, reason)
        exc = make_abort_error(origin_rank, reason)
        for handle in inflight:
            handle.set_error(exc)

    def _peer_mailbox(self):
        """The peer service as it stands (None before ``start`` and
        after a teardown): the caller keeps the reference it was
        given."""
        with self._abort_lock:
            return self._peer_service

    def _on_peer_abort(self, origin_rank, reason):
        """PeerService push receipt: apply locally, no re-fan-out."""
        self._local_abort(origin_rank, reason, fan_out=False)

    def _learned_abort(self, origin_rank, reason):
        """Abort learned from a live coordinator (heartbeat reply,
        negotiation/join response).  Only rank 0 re-pushes to peers: its
        process HOSTS the coordinator, so its exit would cut the relay
        before slower ranks hear — every other rank can rely on its own
        heartbeat, keeping the fan-out O(N) instead of O(N^2).

        A drain-marked directive skips even that push: nothing crashed,
        every rank is alive and heartbeating, so pull delivery reaches
        everyone within one interval without the abort storm the drain
        protocol exists to avoid."""
        self._local_abort(origin_rank, reason,
                          fan_out=(self._rank == 0
                                   and not is_drain_reason(reason)))

    def _push_abort_to_peers(self, origin_rank, reason, budget=2.0):
        """Best-effort direct abort fan-out to every peer's mailbox
        service (bounded: dead peers refuse the connect instantly,
        unreachable ones are cut off by the join budget).  Reuses the
        ring's live peer connections where they exist; otherwise one
        short-budget resolve + connect per peer.

        Pushes ride a BOUNDED worker pool, not a thread per peer: at
        soak scale (64 ranks) a per-peer burst is 63 simultaneous
        thread spawns + rendezvous resolves on the failing rank — an
        O(N) hot spot exactly when the process is dying.  Each pool
        worker walks a strided slice of the peer list, so a stuck peer
        delays only its own slice and the deadline still bounds the
        whole fan-out; heartbeats remain the backstop for peers the
        pool never reached."""
        ring = self._ring_for(None)
        if ring is None:
            return

        deadline = time.monotonic() + budget

        def push_one(rank):
            # ``send``, not ``post``: the peer's answer says that it HAS
            # applied the abort.  A frame only written can lose the race
            # against this process's exit on the peer's other
            # connections, and the peer then names the coordinator, not
            # the culprit
            msg = network.AbortMsg(origin_rank, reason)
            try:
                wait = max(0.1, deadline - time.monotonic())
                cached = ring.cached_peer(rank)
                if cached is not None:
                    cached.send(msg, timeout=wait)
                    return
                client = network.MuxClient(
                    self._peer_addrs(rank, resolve_timeout=2.0,
                                     retry_for=0),
                    self._key, timeout=2, retry_for=0, peer=rank)
                try:
                    client.send(msg, timeout=wait)
                finally:
                    client.close()
            except Exception:  # noqa: BLE001 — heartbeat backstop
                pass

        def push_slice(ranks):
            for rank in ranks:
                if time.monotonic() >= deadline:
                    return
                push_one(rank)

        peers = [r for r in range(self._size) if r != self._rank]
        if not peers:
            return
        width = min(8, len(peers))
        threads = [threading.Thread(target=push_slice,
                                    args=(peers[i::width],),
                                    daemon=True, name="hvd-abort-push")
                   for i in range(width)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def _report_abort(self, origin_rank, reason):
        """Broadcast an abort: best-effort notify the coordinator (which
        relays it to every rank via heartbeat replies and negotiation
        responses), then apply it locally.  When the notify fails AND
        the evidence already names rank 0 dead (a ring send to the
        coordinator's own process broke — RingSendError(peer=0)), the
        two signals corroborate: the coordinator is gone, so this is a
        fail-over trigger, not merely an undeliverable report."""
        from horovod_tpu.elastic.membership import USER_ABORT_PREFIX
        try:
            self._client().send(network.AbortMsg(origin_rank, reason),
                                timeout=5.0)
        except Exception:  # noqa: BLE001 — local abort still proceeds
            if (origin_rank == 0
                    and not (isinstance(reason, str)
                             and reason.startswith(USER_ABORT_PREFIX))):
                self._coordinator_lost(reason)
                return
        self._local_abort(origin_rank, reason)

    def abort(self, origin_rank, reason):
        """Any rank may broadcast an abort for the in-flight round
        (``hvd.abort()``); all ranks raise ``HvdAbortedError`` within
        the abort deadline."""
        self._report_abort(origin_rank, reason)

    def request_drain(self) -> bool:
        """Announce this rank's planned departure (preemption notice)
        to the coordinator and wait for its verdict.  True: a boundary
        reconfiguration without this rank is in flight — keep running
        until the directive arrives.  False: the drain is not
        survivable (single process, elastic off, coordinator rank, too
        few survivors) and the caller should treat the preemption as
        death."""
        if self._size <= 1 or self._client_addrs is None:
            return False
        try:
            # 30s cap: the coordinator's boundary wait is bounded at 5s,
            # the rest is headroom for a loaded control plane
            reply = self._client().send(DrainMsg(self._rank),
                                        timeout=30.0)
        except Exception as exc:  # noqa: BLE001 — a dead coordinator
            # while this rank is being preempted: nothing to drain into
            self._log.warning("drain announce failed: %s", exc)
            return False
        return bool(getattr(reply, "ok", False))

    # ------------------------------------------------------------ producer API
    def enqueue(self, request):
        with self._abort_lock:
            ab = self._abort_state
            if ab is None:
                self._inflight[id(request.handle)] = request.handle
        if ab is not None:
            request.handle.set_error(make_abort_error(*ab))
            return
        self._spawn(self._run_one, request)

    def _ring_for(self, group):
        """The ring plane a round runs on: the world plane, or the
        group's own lazily-built plane (same resolver + PeerService,
        independent sender/stripes so concurrent groups never share a
        send queue).  The world plane is None before ``start`` built it
        and after a teardown took it: the caller keeps the reference it
        was given."""
        with self._rings_lock:
            if not group:
                return self._ring
            plane = self._rings.get(group)
            if plane is None:
                plane = RingPlane(
                    self._rank, self._peer_mailbox(), self._resolve_peer,
                    resolve_bulk=self._resolve_stripe,
                    segment_bytes=self._config.ring_segment_bytes,
                    stripes=self._config.ring_stripes,
                    epoch=self._epoch)
                self._rings[group] = plane
            return plane

    def _use_ring(self, req_type, nbytes):
        if self._size <= 1 or self._ring_for(None) is None:
            return False
        rtype = RequestType(req_type)
        if rtype == RequestType.ALLGATHER:
            # first dims legitimately differ per rank, so a local
            # nbytes-vs-threshold choice would disagree across ranks;
            # the ring is the uniform choice
            return True
        if rtype == RequestType.ADASUM:
            # distributed VHDD only over the full power-of-two world;
            # the coordinator still referees (joined ranks force the
            # payload path via resend)
            return (nbytes >= self._ring_threshold
                    and self._size & (self._size - 1) == 0)
        if rtype == RequestType.ALLREDUCE:
            # the schedule knob owns the ring-vs-star choice for
            # allreduce: a forced ring schedule always negotiates
            # ring_go, "star" always rides coordinator payloads, and
            # auto keeps the threshold split — sub-threshold tensors
            # stay on the star (its single fused round-trip plus the
            # fusion/caching machinery beat per-tensor ring
            # negotiation there); WHICH peer pattern a ring-bound
            # tensor runs is the coordinator's pick (_resolve_schedule:
            # rhd in the latency band, hierarchical over groups)
            sched = getattr(self._config, "schedule", "auto")
            if sched == "star":
                return False
            if sched in ("flat_ring", "hierarchical", "rhd"):
                return True
            return nbytes >= self._ring_threshold
        return (nbytes >= self._ring_threshold
                and rtype in (RequestType.BROADCAST,
                              RequestType.REDUCE_SCATTER))

    def _run_one(self, request, force_payload=False):
        dropped = False
        try:
            arr = np.asarray(request.tensor)
            arr, wire_dtype = _wire_dtype(arr)
            rtype = RequestType(request.req_type)
            if not force_payload and faults.check(rtype.name.lower()):
                # injected drop: this rank silently never contributes —
                # the handle is failed by the eventual stall/liveness
                # abort (it stays registered in _inflight)
                dropped = True
                return
            ring = (not force_payload
                    and self._use_ring(request.req_type, arr.nbytes))
            msg = CollectiveMsg(
                name=request.name, rank=self._rank,
                req_type=request.req_type, op=request.op,
                payload=(None if ring
                         else np.ascontiguousarray(arr).tobytes()),
                shape=arr.shape, dtype=wire_dtype,
                root_rank=request.root_rank, splits=request.splits,
                prescale=request.prescale_factor,
                postscale=request.postscale_factor, ring=ring,
                compression=getattr(request, "compression", "none"),
                epoch=self._epoch,
                schedule=getattr(self._config, "schedule", "auto"),
                group=getattr(request, "group", ""),
                group_ranks=getattr(request, "group_ranks", None))
            msg.sig = _signature(msg)
            self._timeline.begin(request.name,
                                 f"NEGOTIATE_{rtype.name}")
            try:
                resp = self._client().send(msg)
            except (ConnectionError, TimeoutError, OSError) as exc:
                # the control plane is gone (mux retry budget spent):
                # surface the SAME typed, symmetric error as the
                # heartbeat self-abort, not a one-off transport string
                # — or, fail-over armed, the SAME election verdict
                self._coordinator_lost(
                    f"coordinator unreachable during negotiation of "
                    f"'{request.name}': {exc}")
                # sticky: _local_abort just set it (or an earlier abort
                # did); set-once means this read cannot tear
                request.handle.set_error(
                    make_abort_error(*self._abort_state))  # hvd-lint: ignore[lock-discipline]
                return
            self._timeline.end(request.name)
            self._maybe_apply_params(resp)
            ab = getattr(resp, "aborted", None)
            if ab is not None:
                # coordinated abort: fail EVERY in-flight handle (this
                # one included) with the one typed error + purge rings
                self._learned_abort(*ab)
                request.handle.set_error(make_abort_error(*ab))
                return
            if resp.error is not None:
                request.handle.set_error(resp.error)
                return
            if getattr(resp, "resend", False):
                # coordinator resolved to the payload path but this
                # round had payload-less submissions — one uniform retry
                self._run_one(request, force_payload=True)
                return
            if resp.ring_go:
                # "ring" fires AFTER negotiation: crash models a rank
                # dying mid-collective with peers already committed;
                # drop models a rank silently abandoning the round (its
                # handle stays registered for the eventual abort, and
                # the peers' recv backstop converts the silence)
                if faults.check("ring"):
                    dropped = True
                    return
                out = self._run_ring(rtype, request, arr, resp)
            else:
                self._timeline.begin(request.name, rtype.name)
                out = np.frombuffer(
                    resp.payload,
                    dtype=np.dtype(resp.dtype)).reshape(resp.shape)
                self._timeline.end(request.name,
                                   {"bytes": out.nbytes})
            if out.dtype.itemsize >= 8 or out.dtype.kind == "u":
                # jax without x64 narrows 64-bit dtypes (and flips some
                # unsigned ints); the tcp plane promises exact transport,
                # so hand back numpy without paying a device copy
                result = out
            else:
                import jax.numpy as jnp

                result = jnp.asarray(out)
            if rtype == RequestType.ALLTOALL:
                request.handle.set_result((result, resp.recv_splits))
            else:
                request.handle.set_result(result)
        except HvdError as exc:  # typed (e.g. HvdAbortedError): keep it
            request.handle.set_error(exc)
        except Exception as exc:  # noqa: BLE001 — surface on the handle
            request.handle.set_error(str(exc))
        finally:
            if not dropped:
                with self._abort_lock:
                    self._inflight.pop(id(request.handle), None)

    def _run_ring(self, rtype, request, arr, resp):
        """Execute the worker-ring data plane after the coordinator's
        metadata go-ahead."""
        self._timeline.begin(request.name, f"RING_{rtype.name}")
        # every ring recv is time-bounded even with the stall shutdown
        # off: post-negotiation all participants are committed, so a
        # chunk that never arrives (silently dropped on the wire, sender
        # wedged but still heartbeating) is a failure to detect — the
        # timeout converts it into a coordinated abort below instead of
        # an indefinite wait.  4x the abort deadline leaves generous
        # room for a slow multi-hundred-MB ring step.
        timeout = (self._config.stall_shutdown_seconds
                   or (self._config.abort_timeout_seconds * 4
                       if self._config.abort_timeout_seconds > 0
                       else None))
        # coordinator-resolved segment size for this round (None until
        # a tuned value is published): both endpoints of every ring hop
        # must slice identically, whatever this rank last applied
        seg = getattr(resp, "ring_segment_bytes", None)
        # coordinator-resolved schedule for this round, stamped like the
        # segment size so every participant runs the identical plan
        sched = getattr(resp, "schedule", None)
        groups = getattr(resp, "groups", None)
        # grouped rounds run on the group's own plane; the effective
        # world of an AVERAGE (and of split planning) is the group size
        gid = getattr(request, "group", "")
        plane = self._ring_for(gid)
        wsize = (len(request.group_ranks)
                 if gid and request.group_ranks else self._size)
        try:
            if rtype == RequestType.ALLREDUCE:
                kwargs = dict(
                    op_average=(ReduceOp(request.op) == ReduceOp.AVERAGE),
                    world_size=wsize,
                    prescale=request.prescale_factor,
                    postscale=request.postscale_factor, timeout=timeout,
                    compression=getattr(resp, "compression", "none"),
                    segment_bytes=seg)
                if sched == "hierarchical" and groups:
                    out = plane.allreduce_hierarchical(
                        resp.ring_id, arr, resp.participants, groups,
                        **kwargs)
                elif sched == "rhd":
                    out = plane.allreduce_rhd(
                        resp.ring_id, arr, resp.participants, **kwargs)
                else:
                    out = plane.allreduce(
                        resp.ring_id, arr, resp.participants, **kwargs)
            elif rtype == RequestType.REDUCE_SCATTER:
                out = plane.reduce_scatter(
                    resp.ring_id, arr, resp.participants,
                    op_average=(ReduceOp(request.op) == ReduceOp.AVERAGE),
                    world_size=wsize,
                    prescale=request.prescale_factor,
                    postscale=request.postscale_factor, timeout=timeout,
                    compression=getattr(resp, "compression", "none"),
                    segment_bytes=seg)
            elif rtype == RequestType.ADASUM:
                out = plane.adasum(
                    resp.ring_id, arr, resp.participants, timeout=timeout,
                    segment_bytes=seg)
            elif rtype == RequestType.BROADCAST:
                out = plane.broadcast(
                    resp.ring_id,
                    arr if self._rank == request.root_rank else None,
                    resp.participants, request.root_rank,
                    shape=tuple(arr.shape), dtype=arr.dtype.name,
                    timeout=timeout, segment_bytes=seg)
            else:  # ALLGATHER
                trailing = arr.shape[1:]
                per_row = int(np.prod(trailing or (1,))) \
                    * arr.dtype.itemsize
                blocks = plane.allgather(
                    resp.ring_id, arr, resp.participants,
                    block_nbytes=[d * per_row for d in resp.dims0],
                    timeout=timeout, segment_bytes=seg)
                parts = [np.frombuffer(
                    b, dtype=arr.dtype).reshape((d,) + trailing)
                    for b, d in zip(blocks, resp.dims0)]
                out = np.concatenate(parts, axis=0)
        except HvdAbortedError:
            # already a coordinated abort (the peer mailbox was purged
            # wholesale when it was applied) — just propagate the type
            raise
        except BaseException as exc:
            # drop any chunks of the aborted round so nothing lingers
            # (a retry gets a fresh ring_id and can never match them) …
            peer_service = self._peer_mailbox()
            if peer_service is not None:
                peer_service.purge(resp.ring_id)
            # … then turn the local failure (recv timeout, codec error,
            # dead neighbor) into a coordinated abort: the OTHER ranks of
            # this round are blocked on chunks this rank will never send,
            # and without the broadcast they would hang or time out
            # asymmetrically with leaked mailbox state.  When the
            # failure PROVES a peer dead (RingSendError: the transport
            # write to that rank broke), the abort origin is THAT rank
            # — the same origin the liveness monitor would name — so
            # culprit attribution doesn't depend on which detector
            # fires first under machine load (the mid-ring crash
            # flake).  A recv timeout is NOT such proof: in a 3+-rank
            # ring the silent predecessor is usually blocked behind
            # the real casualty, so it names this rank as before.
            origin = exc.peer_rank if isinstance(
                exc, RingSendError) else self._rank
            reason = (f"ring {rtype.name.lower()} '{request.name}' failed "
                      f"on rank {self._rank}: {exc}")
            self._report_abort(origin, reason)
            raise HvdAbortedError(origin, reason) from exc
        finally:
            self._timeline.end(request.name, {"bytes": arr.nbytes})
        return out

    # req-exempt: JOIN — joins never travel through the collective
    # dispatch; they cross the wire as the dedicated JoinMsg barrier
    # below (docs/elastic.md)
    def join(self, rank, handle):
        def run():
            try:
                resp = self._client().send(JoinMsg(rank))
                ab = getattr(resp, "abort", None)
                if ab is not None:
                    self._learned_abort(*ab)
                    handle.set_error(make_abort_error(*ab))
                    return
                handle.set_result(resp.last_rank)
            except Exception as exc:  # noqa: BLE001
                handle.set_error(str(exc))
            finally:
                with self._abort_lock:
                    self._inflight.pop(id(handle), None)

        with self._abort_lock:
            ab = self._abort_state
            if ab is None:
                self._inflight[id(handle)] = handle
        if ab is not None:
            handle.set_error(make_abort_error(*ab))
            return
        self._spawn(run)

    # -------------------------------------------------------------- autotune
    def _maybe_apply_params(self, resp):
        """Apply tuned knob values published by the coordinator
        (reference: SynchronizeParameters applies rank-0's winners on
        every rank).  The knob this data plane owns is its byte-size
        cutover: the tuned fusion threshold IS the ring threshold — the
        size above which tensors take the bulk p2p path instead of
        riding coordinator payloads (same role the fusion threshold
        plays for the in-process planners).  A transiently-stale
        threshold on some rank is safe: the coordinator resolves the
        ring-vs-payload choice per tensor and all participants follow
        its ring_go."""
        seq = getattr(resp, "params_seq", 0)
        params = getattr(resp, "params", None)
        if not params:
            return
        # in-flight request threads race here: without the lock a
        # thread holding an OLDER stamp could overwrite a newer one
        with self._tuned_lock:
            if self._tuned is not None and seq <= self._tuned[0]:
                return
            self._tuned = (seq, dict(params))
            self._ring_threshold = params["fusion_threshold_bytes"]
            self._config.fusion_threshold_bytes = \
                params["fusion_threshold_bytes"]
            self._config.cycle_time_ms = params["cycle_time_ms"]
            if "compression" in params:
                self._config.compression = params["compression"]
            # ring transfer-engine knobs: every rank of a collective
            # receives the same (seq, params) stamp with its ring_go
            # and applies it BEFORE running the ring, so the segment
            # plan both endpoints derive stays identical within a round
            if "ring_segment_bytes" in params:
                self._config.ring_segment_bytes = \
                    int(params["ring_segment_bytes"])
                for plane in self._all_ring_planes():
                    plane.segment_bytes = \
                        int(params["ring_segment_bytes"])
            if "ring_stripes" in params:
                self._config.ring_stripes = int(params["ring_stripes"])
                for plane in self._all_ring_planes():
                    plane.stripes = int(params["ring_stripes"])
            if "schedule" in params:
                # worker-side effect is the ring-vs-star choice in
                # _use_ring; the per-round plan itself always comes
                # stamped on the ring_go, so a transiently-stale value
                # here can never desync a round
                self._config.schedule = str(params["schedule"])

    def _all_ring_planes(self):
        """World plane + every live group plane (tuned-knob fan-out and
        teardown walk the same list)."""
        with self._rings_lock:
            planes = list(self._rings.values())
            if self._ring is not None:
                planes.append(self._ring)
        return planes

    def _close_ring_planes(self):
        with self._rings_lock:
            planes = list(self._rings.values())
            if self._ring is not None:
                planes.append(self._ring)
            self._rings, self._ring = {}, None
        for plane in planes:
            plane.close()

    def tuned_params(self):
        """Same surface as the native controller (reference:
        ParameterManager values after SynchronizeParameters)."""
        if self._autotune is not None:    # rank 0: live tuner view
            return self._autotune.params()
        with self._tuned_lock:
            if self._tuned is not None:
                return dict(self._tuned[1])
        from horovod_tpu.ops.autotune import default_params
        return default_params(self._config)

    def close_for_reconfig(self):
        """Tear down this controller's generation of the world so a
        successor at the next membership epoch can be built: no
        ShutdownMsg (the coordinator we would deregister from is part of
        the dead world), no timeline merge (that is a job-end barrier —
        the job is NOT ending).  Closing the ring plane and peer
        service here is what "rebuild ring topology + stripe
        connections" means: the successor's RingPlane re-resolves every
        peer through the new epoch's rendezvous scope from scratch."""
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        with self._mux_lock:
            mux, self._mux = self._mux, None
        if mux is not None:
            mux.close()
        self._close_ring_planes()
        with self._abort_lock:
            peer_service, self._peer_service = self._peer_service, None
        if peer_service is not None:
            peer_service.shutdown()
        if self._coordinator is not None:
            self._coordinator.shutdown()
            self._coordinator = None
        if self._autotune is not None:
            self._autotune.close()
            self._autotune = None

    def shutdown(self):
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        with self._abort_lock:
            aborted = self._abort_state is not None
        with self._mux_lock:
            mux = self._mux
        if self._size > 1 and mux is not None and not aborted:
            try:  # deregister from liveness (best-effort)
                mux.send(ShutdownMsg(self._rank), timeout=5.0)
            except Exception:  # noqa: BLE001 — coordinator may be gone
                pass
        self._merge_timelines()
        if self._coordinator is not None and not aborted:
            self._coordinator.wait_for_departures(self._rank)
        with self._mux_lock:
            mux, self._mux = self._mux, None
        if mux is not None:
            mux.close()
        self._close_ring_planes()
        with self._abort_lock:
            peer_service, self._peer_service = self._peer_service, None
        if peer_service is not None:
            peer_service.shutdown()
        if self._coordinator is not None:
            self._coordinator.shutdown()
            self._coordinator = None
        if self._autotune is not None:
            self._autotune.close()
            self._autotune = None

    # -------------------------------------------------------------- timeline
    def _merge_timelines(self):
        from horovod_tpu.utils.timeline import publish_and_merge

        publish_and_merge(self._rank, self._size,
                          self._config.timeline_path, self._timeline,
                          scope=self._scope(TIMELINE_SCOPE))
