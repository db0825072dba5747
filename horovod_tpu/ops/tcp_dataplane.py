"""Peer-to-peer ring data plane for process-rank (tcp) mode.

Round 1's tcp mode shipped every payload through the rank-0 coordinator
(an O(N·bytes) star on one host).  The reference's no-dependency config
does better: Gloo runs ring allreduce between workers
(``gloo_operations.cc:30-100``).  This module is that ring, built on the
HMAC mux transport: every worker runs a :class:`PeerService` (a chunk
mailbox) and keeps persistent connections to each neighbor it talks
to.  Large collectives negotiate metadata through the coordinator as
usual, then move bytes rank-to-rank:

- **allreduce**: ring reduce-scatter + ring allgather — each rank moves
  ~2·bytes·(P−1)/P regardless of P, no hot spot (the classic
  Baidu/Horovod ring the reference popularized).
- **broadcast**: segmented pipeline around the ring from the root — the
  root uploads each byte once instead of N−1 times.
- **allgather**: ring block rotation (N−1 forwarding steps).

Transfer engine (round 3):

- **Native wire dtypes** — chunk bytes ship in the tensor's input dtype
  (fp32/bf16/int32/...); float64/int64 accumulation is strictly local
  to each rank.  A fp32 allreduce moves half the bytes the
  f64-on-the-wire seed moved (bf16: a quarter).  Rank-consistency is
  preserved the same way the compressed path always did it: the owner
  of each reduced chunk encodes it ONCE and the allgather leg rotates
  the encoded blob verbatim, so every rank decodes identical bytes.
  Integer dtypes stay exact: partial sums wrap modulo 2^width on the
  wire, and modular addition is associative, so the final
  cast-to-input-dtype result equals the wide-accumulator sum.
- **Segment pipelining** — each ring step's chunk is split into
  ``HVD_TPU_RING_SEGMENT_BYTES`` segments driven through a dedicated
  sender thread, so the send of segment k+1 overlaps the recv +
  accumulate of segment k (double-buffered; both the exact and the
  compressed legs).
- **Socket striping** — bulk segments ride a pool of
  ``HVD_TPU_RING_STRIPES`` dedicated raw-frame connections per peer
  (:class:`network.StripeClient`), separate from the control
  ``MuxClient``: heartbeats, negotiation and abort fan-out never queue
  behind a multi-MB chunk write, and high-BDP links get multi-stream
  throughput.  Abort/purge wake and drain every stripe — blocked recvs
  all wait on the one mailbox condition the abort signals.

The two planes are rank-consistent but not bitwise-identical to each
other for floats: the ring reduces each chunk in ring-rotation order
at wire precision while the star sums in ascending rank order in
float64 — a tensor crossing HVD_TCP_RING_THRESHOLD can change in the
last ulps.
"""

import collections
import queue
import threading
import time

import numpy as np

from horovod_tpu.common import faults
from horovod_tpu.common import rtt as rtt_mod
from horovod_tpu.common.handles import make_abort_error
from horovod_tpu.common.ops_enum import (INT8_BLOCK, is_float_dtype,
                                         reduce_scatter_split_sizes)
from horovod_tpu.run.service import network
from horovod_tpu.tools.race import hooks as race_hooks
from horovod_tpu.utils import env as env_util

# payloads at or above this ride the ring; below it the coordinator star
# round-trip is latency-optimal (one RTT, no rendezvous fan-out)
DEFAULT_RING_THRESHOLD = 1 << 20
# the collective schedules the coordinator can stamp on a ring_go
# (docs/tuning.md "Choosing a collective schedule"); order is the wire
# encoding the C++ ParameterManager autotune walk uses (index = int id)
SCHEDULES = ("auto", "flat_ring", "hierarchical", "rhd", "star")
# the latency-bound regime: among RING-BOUND tensors (past
# HVD_TCP_RING_THRESHOLD, or schedule-forced onto the ring), the
# coordinator resolves auto to recursive halving/doubling (O(log N)
# serialized rounds vs the flat ring's O(N)) inside [MIN, MAX].
# Below MIN the coordinator star's single fused round-trip wins —
# log2(P) serialized peer hops cost more than one coordinator
# exchange for control-plane-sized tensors — and forcing tiny tensors
# onto the ring would also bypass the star's fusion/caching machinery,
# so the band never widens ring ENTRY: sub-threshold traffic keeps
# the star unless a ring schedule is forced
DEFAULT_RHD_MAX_BYTES = 1 << 18
DEFAULT_RHD_MIN_BYTES = 1 << 13
# broadcast pipeline chunk when segmenting is disabled
BCAST_CHUNK = 1 << 22
# pipeline segment size / bulk connections per peer (tunable:
# HVD_TPU_RING_SEGMENT_BYTES / HVD_TPU_RING_STRIPES, docs/tuning.md)
DEFAULT_SEGMENT_BYTES = env_util.DEFAULT_RING_SEGMENT_BYTES
DEFAULT_STRIPES = env_util.DEFAULT_RING_STRIPES


# ------------------------------------------------------- compressed codecs
# enc(float64 1-D chunk) -> wire bytes; dec(blob, n) -> float32-ish [n];
# nbytes(n) -> deterministic blob size (sender and receiver derive the
# segment count from it independently, so no size header travels).
# int8 blobs are [ceil(n/256) fp32 scales][ceil(n/256)*256 int8 values]
# (~27% of fp32 bytes); cast codecs are plain dtype reinterpretations.
def _enc_int8(chunk):
    # all math in float32 with in-place rint/clip: the encoder sits on
    # the ring's critical path and f64 temporaries double its memory
    # traffic (the quantization error bound doesn't need f64 — the
    # scale only has to be within an ulp of max|x|/127)
    n = chunk.size
    nb = -(-n // INT8_BLOCK)
    x = np.ascontiguousarray(chunk, dtype=np.float32)
    if nb * INT8_BLOCK != n:
        x = np.concatenate(
            [x, np.zeros(nb * INT8_BLOCK - n, np.float32)])
    blocks = x.reshape(nb, INT8_BLOCK)
    maxabs = np.maximum(blocks.max(axis=1), -blocks.min(axis=1))
    scale = np.where(maxabs > 0, maxabs / np.float32(127.0),
                     np.float32(1.0)).astype(np.float32)
    # divide like the jnp quantizer — a reciprocal multiply overflows to
    # inf for denormal scales and would send the block's zeros through
    # 0 * inf = NaN into an undefined NaN->int8 cast
    q = blocks / scale[:, None]
    np.rint(q, out=q)
    np.clip(q, -127, 127, out=q)
    return scale.tobytes() + q.astype(np.int8).tobytes()


def _dec_int8(blob, n):
    nb = -(-n // INT8_BLOCK)
    scale = np.frombuffer(blob, np.float32, count=nb)
    q = np.frombuffer(blob, np.int8, offset=nb * 4).reshape(
        nb, INT8_BLOCK).astype(np.float32)
    # float32 out: these ARE the wire values (int8 x fp32 scale); the
    # caller's float64 accumulator upcasts on +=
    q *= scale[:, None]
    return q.reshape(-1)[:n]


def _int8_nbytes(n):
    nb = -(-n // INT8_BLOCK)
    return nb * 4 + nb * INT8_BLOCK


def _cast_codec(wire_dtype):
    dt = np.dtype(wire_dtype)

    def enc(chunk):
        return np.ascontiguousarray(chunk.astype(dt)).tobytes()

    def dec(blob, n):
        # float32 is exact for bf16/fp16 wire values; the caller's
        # float64 accumulator upcasts on +=
        return np.frombuffer(blob, dtype=dt, count=n).astype(np.float32)

    def nbytes(n):
        return n * dt.itemsize

    return enc, dec, nbytes


def _codecs():
    # bfloat16 comes from ml_dtypes (a jax dependency) — resolved lazily
    # so importing this module never pulls it in on the no-accelerator
    # path until a compressed collective actually runs
    import ml_dtypes

    return {
        "int8": (_enc_int8, _dec_int8, _int8_nbytes),
        "bf16": _cast_codec(ml_dtypes.bfloat16),
        "fp16": _cast_codec(np.float16),
    }


def _as_bytes_view(arr):
    """Zero-copy raw-bytes view of a contiguous array — via a uint8
    reinterpretation, because numpy refuses direct buffer export for
    ml_dtypes extension dtypes (bfloat16)."""
    return arr.view(np.uint8).data


def _wire_spec(dtype, prescale, widen):
    """(wire dtype, accumulator dtype) for the exact ring path.

    Floats wire natively and accumulate in f64.  Integers wire natively
    and accumulate in int64 — modular wrap on the wire is exact for the
    final input-dtype result, but ONLY for a pure sum: ``widen`` (an
    average or postscale, which read the true wide total before the
    cast back) keeps int64 on the wire like the seed did, and a
    prescale promotes the math to float entirely, so f64 wires (exact
    for every integer the cast back can represent)."""
    dt = np.dtype(dtype)
    if is_float_dtype(dt):
        return dt, np.float64
    if prescale != 1.0:
        return np.dtype(np.float64), np.float64
    if widen:
        return np.dtype(np.int64), np.int64
    return dt, np.int64


class ChunkMsg:
    # ``epoch`` is the membership epoch the sender's plane belongs to
    # (docs/elastic.md): the header rides pickled on BOTH frame kinds
    # (control-connection chunks and raw bulk stripes share the pickled
    # header in write_bulk_message), so a straggler chunk from a
    # pre-reconfiguration ring is droppable at the framing layer.
    # __weakref__ keeps instances weakref-able despite __slots__: the
    # race shim's address-recycling check needs a liveness weakref, and
    # chunk headers churn through recycled addresses constantly.
    # (pickle skips the __weakref__ slot, so the wire format is
    # unchanged.)
    __slots__ = ("tag", "src", "payload", "epoch", "__weakref__")

    def __init__(self, tag, src, payload, epoch=0):
        self.tag = tag
        self.src = src
        self.payload = payload
        self.epoch = epoch


class RingSendError(ConnectionError):
    """A bulk segment write to a SPECIFIC peer failed.  Carrying the
    peer rank lets the abort that follows name the rank the transport
    proved unreachable — not the rank that happened to notice first —
    so culprit attribution stays deterministic under machine-load skew
    (the mid-ring crash scenario races liveness detection against the
    survivor's own failed sends; both now name the same origin).

    Only the SEND side carries this evidence: a failed connection to a
    peer proves THAT peer is gone, while a recv timeout only proves the
    ring stalled somewhere upstream — in a 3+-rank ring the silent
    predecessor is usually an innocent rank blocked behind the real
    casualty, so recv timeouts keep naming the noticing rank and leave
    precise attribution to the liveness monitor."""

    def __init__(self, peer_rank, cause):
        super().__init__(
            f"ring bulk send to rank {peer_rank} failed: {cause}")
        self.peer_rank = peer_rank


class _PlaneClosedError(ConnectionError):
    """This plane's own close() refused the operation — a local
    teardown artifact, never evidence about a peer (the sender loop
    must not convert it into a RingSendError that blames one)."""


class PeerService(network.MuxService):
    """Per-worker chunk mailbox: peers push ``ChunkMsg`` frames (pickled
    small ones on the control connection, raw bulk frames on the
    stripes); the local compute thread collects them by tag."""

    NAME = "horovod_tpu peer"

    # purged ring ids remembered so late-arriving chunks of aborted
    # rounds are dropped instead of leaking in the mailbox forever.
    # Bounded LRU: re-purging a hot id refreshes its slot instead of
    # evicting a different recent id, and total memory is O(KEEP)
    # however long the job runs.
    _PURGED_KEEP = 256

    def __init__(self, key, epoch=0):
        # membership epoch this plane accepts; stale-epoch frames are
        # dropped in _handle so a straggler chunk from a torn-down ring
        # can never corrupt a post-reconfiguration collective
        self._epoch = epoch
        self.stale_epoch_drops = 0   # guarded by self._cv
        self._cv = threading.Condition()
        self._mailbox = {}   # (tag, src) -> payload; guarded by self._cv
        # ring-id index over the mailbox: purge and the late-chunk drop
        # check are O(chunks of that ring), not O(total mailbox)
        self._by_ring = {}   # ring_id -> mailbox keys; guarded by self._cv
        # ring_id -> None (LRU); guarded by self._cv
        self._purged = collections.OrderedDict()
        # (origin_rank, reason) once observed; guarded by self._cv
        self._aborted = None
        # set by the controller: called (origin, reason) when a PEER
        # pushes an abort here, so in-flight negotiation handles fail
        # too, not just blocked ring recvs
        self.abort_callback = None
        super().__init__(self.NAME, key)

    def session_epoch(self):
        """Session hellos must carry the plane's membership epoch: a
        client healing across a reconfiguration is fenced (refused
        welcome) and escalates instead of replaying a torn-down ring's
        frames into the new epoch."""
        return self._epoch

    def _handle(self, req, client_address):
        if isinstance(req, ChunkMsg):
            with self._cv:
                if getattr(req, "epoch", 0) != self._epoch:
                    # stale-epoch frame (or one from the future — a
                    # peer that reconfigured ahead of us): refuse it at
                    # the framing layer, before it can touch the mailbox
                    self.stale_epoch_drops += 1
                    return network.AckResponse()
                if self._aborted is not None \
                        or req.tag[0] in self._purged:
                    return network.AckResponse()  # aborted round, drop
                key = (req.tag, req.src)
                self._mailbox[key] = req.payload
                self._by_ring.setdefault(req.tag[0], set()).add(key)
                if race_hooks.active:
                    # deliver→recv happens-before edge: even a recv
                    # that never waits (chunk already buffered) is
                    # ordered after this insert (docs/race_detection.md)
                    race_hooks.publish(("mailbox", id(self)) + key)
                self._cv.notify_all()
            return network.AckResponse()
        if isinstance(req, network.AbortMsg):
            # direct peer-to-peer abort fan-out: delivery does not
            # depend on the coordinator (or its host process) surviving
            self.abort(req.origin_rank, req.reason)
            callback = self.abort_callback
            if callback is not None:
                callback(req.origin_rank, req.reason)
            return network.AckResponse()
        return super()._handle(req, client_address)

    def recv(self, tag, src, timeout=None, error_check=None):
        """``error_check`` (optional, called with the condition held on
        every wakeup) raises to fail this recv on a local error — the
        ring plane uses it so a blocked recv dies as soon as its own
        sender thread reports a broken stripe, instead of waiting out
        the timeout for segments the peer will never get to send."""
        import time as _time

        deadline = (_time.monotonic() + timeout) if timeout else None
        key = (tag, src)
        with self._cv:
            while key not in self._mailbox:
                if self._aborted is not None:
                    raise make_abort_error(*self._aborted)
                if error_check is not None:
                    error_check()
                remaining = None
                if deadline is not None:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"no chunk {tag!r} from rank {src} within "
                            f"{timeout}s")
                self._cv.wait(timeout=remaining)
            ring_keys = self._by_ring.get(tag[0])
            if ring_keys is not None:
                ring_keys.discard(key)
                if not ring_keys:
                    del self._by_ring[tag[0]]
            if race_hooks.active:
                race_hooks.observe(("mailbox", id(self)) + key)
            return self._mailbox.pop(key)

    def purge(self, ring_id):
        """Drop chunks of an aborted collective round (its tags lead with
        the coordinator-assigned ring id, so a retry — which gets a NEW
        id — can never consume stale data).  O(chunks of this ring) via
        the ring-id index, not a scan of every mailbox key."""
        with self._cv:
            self._purged[ring_id] = None
            self._purged.move_to_end(ring_id)
            while len(self._purged) > self._PURGED_KEEP:
                self._purged.popitem(last=False)
            for key in self._by_ring.pop(ring_id, ()):
                self._mailbox.pop(key, None)

    def purge_group(self, group):
        """Group-aware purge (docs/groups.md): drop every buffered round
        of process group ``group``.  Grouped ring ids live in a per-group
        namespace ("g<gid>:<seq>"), so the group's rounds — and only the
        group's rounds — are identifiable here without consulting any
        registry.  Used when a group's rounds must all die together
        (e.g. a reform at a new membership epoch) while other groups'
        in-flight rounds keep their mailbox state."""
        prefix = f"g{group}:"
        with self._cv:
            for ring_id in [rid for rid in self._by_ring
                            if isinstance(rid, str)
                            and rid.startswith(prefix)]:
                self._purged[ring_id] = None
                self._purged.move_to_end(ring_id)
                for key in self._by_ring.pop(ring_id, ()):
                    self._mailbox.pop(key, None)
            while len(self._purged) > self._PURGED_KEEP:
                self._purged.popitem(last=False)

    def abort(self, origin_rank, reason):
        """Coordinated abort observed: fail every blocked ``recv`` with
        the typed error, drop all buffered chunks and refuse new ones —
        no mailbox state survives the abort (sticky; the job is over).
        Recvs blocked on stripe-delivered segments wake too: every recv
        waits on this one condition regardless of which connection the
        bytes would have arrived on."""
        with self._cv:
            if self._aborted is not None:
                return
            self._aborted = (origin_rank, reason)
            self._mailbox.clear()
            self._by_ring.clear()
            self._cv.notify_all()


class RingPlane:
    """This process's endpoint of the worker ring."""

    def __init__(self, rank, service, resolve_peer, resolve_bulk=None, *,
                 segment_bytes=None, stripes=None, epoch=0):
        """``resolve_peer(rank) -> MuxClient`` (control; lazy, cached).
        ``resolve_bulk(rank) -> StripeClient`` builds one bulk-data
        stripe (called up to ``stripes`` times per peer; None routes
        bulk frames through the control client's bulk companion —
        still a dedicated socket, just a single one)."""
        self.rank = rank
        self.epoch = epoch        # stamped on every outgoing ChunkMsg
        self._service = service
        self._resolve = resolve_peer
        self._resolve_bulk = resolve_bulk
        self._clients = {}        # rank -> MuxClient; guarded by self._lock
        # rank -> [StripeClient | None]; guarded by self._lock
        self._stripe_pools = {}
        self._lock = threading.Lock()
        self.segment_bytes = (env_util.get_int(
            env_util.HVD_TPU_RING_SEGMENT_BYTES, DEFAULT_SEGMENT_BYTES)
            if segment_bytes is None else int(segment_bytes))
        self.stripes = (env_util.get_int(
            env_util.HVD_TPU_RING_STRIPES, DEFAULT_STRIPES)
            if stripes is None else int(stripes))
        self._sendq = queue.Queue()
        self._sender = None       # sender thread; guarded by self._lock
        # latest async send failure (sticky, written by the sender
        # thread, read by the compute thread); guarded by self._pending_cv
        self._send_error = None
        # peer the failed write was addressed to (None: not
        # peer-specific, e.g. close()); guarded by self._pending_cv
        self._send_error_peer = None
        # enqueued-but-unwritten segments; guarded by self._pending_cv
        self._pending_sends = 0
        self._pending_cv = threading.Condition()
        self._closed = False      # guarded by self._lock

    # ------------------------------------------------------------ transport
    def _peer(self, rank):
        with self._lock:
            if self._closed:
                # the sender thread may still be draining queued
                # segments when close() empties the pools — refusing
                # here stops it from repopulating them with fresh
                # connections nobody would ever close
                raise _PlaneClosedError("ring plane closed")
            client = self._clients.get(rank)
            if client is None:
                client = self._clients[rank] = self._resolve(rank)
            return client

    def cached_peer(self, rank):
        """The already-connected client for ``rank``, or None — the
        abort fan-out prefers live connections over re-resolving peers
        through the rendezvous mid-failure."""
        with self._lock:
            return self._clients.get(rank)

    def bytes_sent(self):
        """Wire bytes this plane has written (control posts + bulk
        stripes, framing included) — the byte-accounting surface the
        wire-efficiency tests measure."""
        with self._lock:
            total = sum(c.bytes_sent for c in self._clients.values())
            total += sum(s.bytes_sent for pool in
                         self._stripe_pools.values()
                         for s in pool if s is not None)
        return total

    def _stripe(self, dst, index):
        with self._lock:
            if self._closed:
                raise _PlaneClosedError("ring plane closed")
            n = max(1, int(self.stripes))
            pool = self._stripe_pools.setdefault(dst, [])
            i = index % n
            while len(pool) <= i:
                pool.append(self._resolve_bulk(dst)
                            if self._resolve_bulk is not None else None)
            return pool[i]

    def send(self, dst, tag, payload):
        # fire-and-forget: the mailbox is tag-keyed, so ordering doesn't
        # need acks, and ring steps stay bandwidth-bound (no ack RTT on
        # the critical path).  This is the seed-era unsegmented path —
        # kept for the reference (seed-parity) collectives and small
        # control-sized chunks.
        if faults.check("send"):
            return  # injected drop: the chunk vanishes on the wire
        self._peer(dst).post(
            ChunkMsg(tag, self.rank, payload, epoch=self.epoch))

    def recv(self, tag, src, timeout=None) -> bytes:
        if faults.check("recv"):
            raise TimeoutError(
                f"no chunk {tag!r} from rank {src} (injected recv fault)")
        return self._service.recv(tag, src, timeout=timeout)

    # --------------------------------------------------- segment pipeline
    @staticmethod
    def _segment_plan(nbytes, seg_bytes, align):
        """(segment size, segment count) — derived identically on the
        send and recv side from the chunk's wire size, the segment knob
        and the wire itemsize (every segment but the last is a multiple
        of ``align`` so per-segment decode never splits an element)."""
        nbytes = int(nbytes)
        if seg_bytes <= 0 or nbytes <= seg_bytes:
            return max(nbytes, 1), 1
        size = max(align, (int(seg_bytes) // align) * align)
        return size, -(-nbytes // size)

    def _sender_loop(self):
        while True:
            # wakeable: close() enqueues the None sentinel; the abort
            # path never needs to wake this thread (it only ever blocks
            # when there is nothing left to write)
            item = self._sendq.get()
            if item is None:
                return
            dst, stripe_i, msg, payload = item
            try:
                t0 = time.monotonic()
                stripe = self._stripe(dst, stripe_i)
                if stripe is not None:
                    stripe.post_bulk(msg, payload)
                else:
                    self._peer(dst).post_bulk(msg, payload)
                # per-peer write latency feeds the adaptive-deadline
                # EWMA: a bulk write blocking on socket backpressure (or
                # an injected delay/throttle) is exactly the slow-link
                # evidence the next heartbeat should carry upstream
                rtt_mod.tracker().sample(("peer", dst),
                                         time.monotonic() - t0)
            except Exception as exc:  # noqa: BLE001 — surface on the
                # compute thread: its next send/recv of any round fails
                # fast instead of waiting out the recv timeout
                with self._pending_cv:
                    self._send_error = exc
                    # peer evidence ONLY for genuine transport failures
                    # addressed at dst: a local error (framing bug,
                    # MemoryError, this plane's own close()) must not
                    # make the abort origin blame a healthy rank
                    if isinstance(exc, (OSError, TimeoutError)) \
                            and not isinstance(exc, _PlaneClosedError):
                        self._send_error_peer = dst
                # a recv already blocked on the mailbox must wake NOW:
                # its error_check re-raises this under the condition
                # (never nested with _pending_cv — no ordering edge)
                with self._service._cv:
                    self._service._cv.notify_all()
            finally:
                with self._pending_cv:
                    self._pending_sends -= 1
                    self._pending_cv.notify_all()

    def _raise_if_send_failed(self):
        with self._pending_cv:
            self._raise_if_send_failed_locked()

    def _raise_if_send_failed_locked(self):  # holds: self._pending_cv
        if self._send_error is not None:
            if self._send_error_peer is not None:
                raise RingSendError(self._send_error_peer,
                                    self._send_error)
            raise ConnectionError(
                f"ring bulk send failed: {self._send_error}")

    def _enqueue_segment(self, dst, stripe_i, tag, payload):
        # spawn-check and pending-count both under _lock: close() sets
        # _closed under the same lock, so a segment can never be
        # counted after close() decided nobody will ever drain it —
        # that would strand a timeout-less _flush_sends forever
        with self._lock:
            if self._closed:
                raise ConnectionError("ring plane closed")
            if self._sender is None:
                self._sender = threading.Thread(
                    target=self._sender_loop, daemon=True,
                    name="hvd-ring-sender")
                self._sender.start()
            with self._pending_cv:
                self._pending_sends += 1
        self._sendq.put(
            (dst, stripe_i,
             ChunkMsg(tag, self.rank, None, epoch=self.epoch), payload))

    def _flush_sends(self, timeout=None):
        """Block until every enqueued segment has been WRITTEN to its
        socket.  Every collective ends with this: fire-and-forget must
        not outlive the collective call — a rank whose process exits
        right after a broadcast/allreduce returns would otherwise race
        its own sender thread and strand peers waiting on segments that
        were never written."""
        import time as _time

        deadline = (_time.monotonic() + timeout) if timeout else None
        with self._pending_cv:
            while self._pending_sends > 0:
                self._raise_if_send_failed_locked()
                remaining = None
                if deadline is not None:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"{self._pending_sends} ring segments still "
                            f"unsent after {timeout}s")
                # wakeable: every enqueued segment decrements
                # _pending_sends under this condition, a sender failure
                # notifies it, and close() fails any segments the
                # sender exited without writing — timeout-less callers
                # always wake
                self._pending_cv.wait(timeout=remaining)
            self._raise_if_send_failed_locked()

    def send_chunk(self, dst, base_tag, payload, seg_bytes=None,
                   align=1):
        """Split ``payload`` into pipeline segments, round-robined over
        the stripe pool via the dedicated sender thread — returns
        immediately so the caller's recv+accumulate of the incoming
        chunk overlaps the outgoing writes."""
        if faults.check("send"):
            return  # injected drop: the whole chunk vanishes
        self._raise_if_send_failed()
        seg = self.segment_bytes if seg_bytes is None else seg_bytes
        mv = memoryview(payload).cast("B")
        size, n_seg = self._segment_plan(mv.nbytes, seg, align)
        for k in range(n_seg):
            self._enqueue_segment(dst, k, base_tag + (k,),
                                  mv[k * size:(k + 1) * size])

    def recv_chunk(self, base_tag, src, nbytes, timeout=None,
                   consume=None, seg_bytes=None, align=1):
        """Receive the pipeline segments of one chunk.  ``nbytes`` is
        the chunk's deterministic wire size (both sides derive the
        segment count from it — no size header travels).  With
        ``consume(offset, segment)`` each segment is handed over as it
        arrives (overlapping the peer's remaining sends) and None is
        returned; otherwise the reassembled bytes are returned."""
        if faults.check("recv"):
            raise TimeoutError(
                f"no chunk {base_tag!r} from rank {src} "
                f"(injected recv fault)")
        seg = self.segment_bytes if seg_bytes is None else seg_bytes
        _, n_seg = self._segment_plan(nbytes, seg, align)
        parts = [] if consume is None else None
        offset = 0
        for k in range(n_seg):
            segment = self._service.recv(
                base_tag + (k,), src, timeout=timeout,
                error_check=self._raise_if_send_failed)
            if consume is None:
                parts.append(segment)
            else:
                consume(offset, segment)
            offset += len(segment)
        if consume is None:
            return parts[0] if len(parts) == 1 else b"".join(parts)
        return None

    def close(self):
        with self._lock:
            self._closed = True
            clients = list(self._clients.values())
            self._clients.clear()
            stripes = [s for pool in self._stripe_pools.values()
                       for s in pool if s is not None]
            self._stripe_pools.clear()
            sender = self._sender
            self._sender = None
        if sender is not None:
            self._sendq.put(None)
            sender.join(timeout=5)
        # a racing _enqueue_segment may have counted a segment the
        # (now-exiting) sender never wrote: fail it loudly so a blocked
        # _flush_sends raises instead of waiting forever
        with self._pending_cv:
            if self._pending_sends > 0:
                if self._send_error is None:
                    self._send_error = ConnectionError(
                        f"ring plane closed with {self._pending_sends} "
                        f"segment(s) unsent")
                self._pending_sends = 0
                self._pending_cv.notify_all()
        for client in clients:
            client.close()
        for stripe in stripes:
            stripe.close()

    # ------------------------------------------------------------- allreduce
    def allreduce(self, ring_id, arr, participants, *, op_average,
                  world_size, prescale=1.0, postscale=1.0, timeout=None,
                  compression="none", segment_bytes=None):
        """Pipelined ring allreduce over ``participants`` (sorted rank
        ids; must include ``self.rank``).  Joined ranks simply aren't in
        the ring — their zero stand-ins are additive identities.

        Bulk bytes travel in the tensor's native dtype (or the
        ``compression`` wire format: "int8" / "bf16" / "fp16", floats
        only); accumulation stays float64/int64 LOCAL to each rank, and
        every rank decodes the same reduced blobs, so the result is
        identical on all ranks."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)

        out_dtype = arr.dtype
        float_in = is_float_dtype(arr.dtype)
        wire_dt, acc_dtype = _wire_spec(
            arr.dtype, prescale, widen=op_average or postscale != 1.0)
        flat = arr.reshape(-1).astype(acc_dtype)
        if prescale != 1.0:
            flat = flat * prescale
        codec = (_codecs().get(compression)
                 if float_in and compression not in (None, "none") else None)
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes))
        if p == 1:
            total = flat
        elif codec is not None:
            total = self._allreduce_compressed(ring_id, flat, participants,
                                               idx, codec, timeout, seg)
        else:
            total = self._allreduce_exact(ring_id, flat, participants, idx,
                                          wire_dt, acc_dtype, timeout, seg)
        if op_average:
            total = total / world_size
        if postscale != 1.0:
            total = total * postscale
        return total.astype(out_dtype).reshape(arr.shape)

    def _allreduce_exact(self, ring_id, flat, participants, idx, wire_dt,
                         acc_dtype, timeout, seg):
        """Native-wire-dtype pipelined ring.  Reduce-scatter leg: the
        running partial sum of each chunk hops the ring at wire
        precision (each hop decodes, adds its own contribution in the
        wide local accumulator, re-encodes).  Allgather leg: the chunk's
        owner encodes the reduced chunk ONCE and the rotation forwards
        the blob verbatim — every rank (owner included) decodes the
        same bytes, so the result is rank-consistent."""
        p = len(participants)
        right = participants[(idx + 1) % p]
        left = participants[(idx - 1) % p]
        chunks = np.array_split(flat, p)
        sizes = [c.size for c in chunks]
        item = wire_dt.itemsize

        # reduce-scatter: after p-1 steps this rank owns the fully
        # reduced chunk (idx+1) % p
        for s in range(p - 1):
            send_i = (idx - s) % p
            recv_i = (idx - 1 - s) % p
            out = chunks[send_i].astype(wire_dt)
            self.send_chunk(right, (ring_id, "rs", s), _as_bytes_view(out),
                            seg_bytes=seg, align=item)
            target = chunks[recv_i]

            def accumulate(offset, segment, target=target):
                lo = offset // item
                decoded = np.frombuffer(segment, dtype=wire_dt)
                target[lo:lo + decoded.size] += decoded.astype(
                    target.dtype, copy=False)

            self.recv_chunk((ring_id, "rs", s), left,
                            sizes[recv_i] * item, timeout=timeout,
                            consume=accumulate, seg_bytes=seg, align=item)

        # allgather: rotate the owner-encoded chunks p-1 times; blobs
        # forward verbatim
        owner = (idx + 1) % p
        own_wire = chunks[owner].astype(wire_dt)
        blobs = {owner: _as_bytes_view(own_wire)}
        carry = owner
        for s in range(p - 1):
            self.send_chunk(right, (ring_id, "ag", s), blobs[carry],
                            seg_bytes=seg, align=item)
            recv_owner = (idx - s) % p
            blobs[recv_owner] = self.recv_chunk(
                (ring_id, "ag", s), left, sizes[recv_owner] * item,
                timeout=timeout, seg_bytes=seg, align=item)
            carry = recv_owner
        self._flush_sends(timeout)
        return np.concatenate([
            np.frombuffer(blobs[i], dtype=wire_dt,
                          count=sizes[i]).astype(acc_dtype)
            for i in range(p)])

    def _allreduce_compressed(self, ring_id, flat, participants, idx,
                              codec, timeout, seg):
        """Compressed bulk exchange (EQuARX-style block scaling mapped
        onto the p2p transport).  Reduce-scatter leg: each rank encodes
        its contribution to every destination chunk ONCE at the source
        and ships it straight to the chunk's owner — same (p-1)/p bytes
        per rank as the classic ring's reduce-scatter, but one
        quantization per contribution instead of a requantize at every
        hop.  The owner accumulates all contributions in float64,
        encodes its reduced chunk once, and the allgather leg rotates
        the compressed blobs around the ring verbatim.  Every rank
        decodes the SAME blobs (the owner included), so the result stays
        rank-consistent like the exact ring."""
        enc, dec, enc_nbytes = codec
        p = len(participants)
        chunks = np.array_split(flat, p)
        sizes = [c.size for c in chunks]
        for d in range(p):
            if d != idx:
                self.send_chunk(participants[d], (ring_id, "qrs", d),
                                enc(np.ascontiguousarray(chunks[d])),
                                seg_bytes=seg)
        acc = chunks[idx].astype(np.float64, copy=True)
        for src_i, src in enumerate(participants):
            if src_i == idx:
                continue
            blob = self.recv_chunk((ring_id, "qrs", idx), src,
                                   enc_nbytes(sizes[idx]),
                                   timeout=timeout, seg_bytes=seg)
            acc += dec(blob, sizes[idx])
        # allgather: rotate the compressed reduced chunks p-1 times
        right = participants[(idx + 1) % p]
        left = participants[(idx - 1) % p]
        blobs = {idx: enc(np.ascontiguousarray(acc))}
        carry = idx
        for s in range(p - 1):
            self.send_chunk(right, (ring_id, "qag", s), blobs[carry],
                            seg_bytes=seg)
            recv_owner = (idx - 1 - s) % p
            blobs[recv_owner] = self.recv_chunk(
                (ring_id, "qag", s), left, enc_nbytes(sizes[recv_owner]),
                timeout=timeout, seg_bytes=seg)
            carry = recv_owner
        self._flush_sends(timeout)
        return np.concatenate([dec(blobs[i], sizes[i]) for i in range(p)])

    # -------------------------------------------- hierarchical allreduce
    def allreduce_hierarchical(self, ring_id, arr, participants, groups,
                               *, op_average, world_size, prescale=1.0,
                               postscale=1.0, timeout=None,
                               compression="none", segment_bytes=None):
        """Two-level (topology-aware) allreduce (the MLPerf TPU-pod
        schedule, arXiv:1909.09756, mapped onto the TCP plane).

        ``groups`` partitions ``participants`` into co-located sets (the
        coordinator stamps them from launcher host hashes or
        ``HVD_HIER_LOCAL_SIZE``).  Four phases:

        1. intra-group reduce-scatter — every member ships its
           contribution to each group slice straight to the slice's
           owner (one serialized round, same (g-1)/g bytes as a ring
           reduce-scatter);
        2. slice gather — members hand their reduced slice to the
           group's delegate (its min rank), which assembles the full
           group sum;
        3. delegates run the existing striped/pipelined ring
           (:meth:`_allreduce_exact` / :meth:`_allreduce_compressed`)
           across groups — rank-consistency and compression compose
           unchanged;
        4. each delegate encodes the global result ONCE and every group
           member (the delegate included) decodes the same blob, so the
           result is bitwise identical on all ranks.

        The flat ring serializes 2·(P−1) rounds; this schedule runs
        3 + 2·(G−1) rounds for G groups — the latency term the scaling
        curve collapses under (docs/tuning.md)."""
        participants = sorted(participants)
        out_dtype = arr.dtype
        float_in = is_float_dtype(arr.dtype)
        wire_dt, acc_dtype = _wire_spec(
            arr.dtype, prescale, widen=op_average or postscale != 1.0)
        flat = arr.reshape(-1).astype(acc_dtype)
        if prescale != 1.0:
            flat = flat * prescale
        codec = (_codecs().get(compression)
                 if float_in and compression not in (None, "none") else None)
        enc, dec, enc_nbytes = codec if codec else (None, None, None)
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes))
        item = wire_dt.itemsize

        groups = [sorted(g) for g in groups]
        groups.sort(key=lambda g: g[0])
        group = next(g for g in groups if self.rank in g)
        g = len(group)
        gidx = group.index(self.rank)
        delegate = group[0]
        delegates = [gr[0] for gr in groups]

        # phase 1: intra-group owner-targeted reduce-scatter.  Slice d
        # of the flat vector belongs to group member d; contributions
        # are wire-encoded once at the source and accumulated wide at
        # the owner in group order (deterministic, like the star).
        chunks = np.array_split(flat, g)
        sizes = [c.size for c in chunks]
        if g > 1:
            own = chunks[gidx].astype(
                np.float64 if codec else acc_dtype, copy=True)
            for d in range(g):
                if d == gidx:
                    continue
                if codec is None:
                    out = chunks[d].astype(wire_dt)
                    self.send_chunk(group[d], (ring_id, "h1", d),
                                    _as_bytes_view(out), seg_bytes=seg,
                                    align=item)
                else:
                    self.send_chunk(group[d], (ring_id, "h1", d),
                                    enc(np.ascontiguousarray(chunks[d])),
                                    seg_bytes=seg)
            for src_i, src in enumerate(group):
                if src_i == gidx:
                    continue
                if codec is None:
                    blob = self.recv_chunk(
                        (ring_id, "h1", gidx), src, sizes[gidx] * item,
                        timeout=timeout, seg_bytes=seg, align=item)
                    own += np.frombuffer(blob, wire_dt).astype(
                        acc_dtype, copy=False)
                else:
                    blob = self.recv_chunk(
                        (ring_id, "h1", gidx), src,
                        enc_nbytes(sizes[gidx]), timeout=timeout,
                        seg_bytes=seg)
                    own += dec(blob, sizes[gidx])

            # phase 2: gather the reduced slices at the delegate
            if gidx != 0:
                if codec is None:
                    out = own.astype(wire_dt)
                    self.send_chunk(delegate, (ring_id, "h2", gidx),
                                    _as_bytes_view(out), seg_bytes=seg,
                                    align=item)
                else:
                    self.send_chunk(delegate, (ring_id, "h2", gidx),
                                    enc(np.ascontiguousarray(own)),
                                    seg_bytes=seg)
                total = None
            else:
                parts = [own]
                for i in range(1, g):
                    if codec is None:
                        blob = self.recv_chunk(
                            (ring_id, "h2", i), group[i],
                            sizes[i] * item, timeout=timeout,
                            seg_bytes=seg, align=item)
                        parts.append(np.frombuffer(blob, wire_dt).astype(
                            acc_dtype, copy=False))
                    else:
                        blob = self.recv_chunk(
                            (ring_id, "h2", i), group[i],
                            enc_nbytes(sizes[i]), timeout=timeout,
                            seg_bytes=seg)
                        parts.append(dec(blob, sizes[i]))
                total = np.concatenate(parts)
        else:
            total = flat if gidx == 0 else None

        if gidx == 0:
            # phase 3: the existing cross-group ring among delegates
            # ("rs"/"ag"/"qrs"/"qag" tags — disjoint from the "h*"
            # intra-group tags, all under this ring_id so purge() still
            # clears everything)
            if len(delegates) > 1:
                didx = delegates.index(self.rank)
                if codec is None:
                    total = self._allreduce_exact(
                        ring_id, total.astype(acc_dtype, copy=False),
                        delegates, didx, wire_dt, acc_dtype, timeout, seg)
                else:
                    total = self._allreduce_compressed(
                        ring_id, total, delegates, didx, codec, timeout,
                        seg)
            # phase 4: encode the global result ONCE; every rank in the
            # group (this delegate included) decodes the same blob, so
            # the result is bitwise identical everywhere
            if codec is None:
                wire = np.ascontiguousarray(total.astype(wire_dt))
                blob = _as_bytes_view(wire)
                for peer in group[1:]:
                    self.send_chunk(peer, (ring_id, "h3"), blob,
                                    seg_bytes=seg, align=item)
                total = wire.astype(acc_dtype)
            else:
                blob = enc(np.ascontiguousarray(total))
                for peer in group[1:]:
                    self.send_chunk(peer, (ring_id, "h3"), blob,
                                    seg_bytes=seg)
                total = dec(blob, flat.size).astype(np.float64)
        else:
            if codec is None:
                blob = self.recv_chunk(
                    (ring_id, "h3"), delegate, flat.size * item,
                    timeout=timeout, seg_bytes=seg, align=item)
                total = np.frombuffer(blob, wire_dt).astype(acc_dtype)
            else:
                blob = self.recv_chunk(
                    (ring_id, "h3"), delegate, enc_nbytes(flat.size),
                    timeout=timeout, seg_bytes=seg)
                total = dec(blob, flat.size).astype(np.float64)
        self._flush_sends(timeout)
        if op_average:
            total = total / world_size
        if postscale != 1.0:
            total = total * postscale
        return total.astype(out_dtype).reshape(arr.shape)

    # --------------------------------- recursive halving/doubling (rhd)
    def allreduce_rhd(self, ring_id, arr, participants, *, op_average,
                      world_size, prescale=1.0, postscale=1.0,
                      timeout=None, compression="none",
                      segment_bytes=None):
        """Latency-optimal small-tensor allreduce: recursive doubling
        with a fold-in step for non-power-of-two rings — O(log P)
        serialized rounds against the flat ring's 2·(P−1) and the
        coordinator star's O(P·bytes) hot spot.

        Extras (the P − 2^m highest positions) fold their vector into a
        power-of-two partner, the 2^m survivors run log2 rounds of
        pairwise full-vector exchange, and partners hand the finished
        vector back verbatim.  Every level re-encodes the local partial
        to the wire dtype and accumulates ``decode(mine) +
        decode(theirs)`` — both partners add the SAME two wire values
        (IEEE addition is commutative and deterministic), so by
        induction every rank finishes with bitwise-identical bytes.

        ``compression`` is accepted for signature parity but the wire
        stays in the native dtype: this schedule serves the
        latency-bound ≤``DEFAULT_RHD_MAX_BYTES`` regime where a
        quantization pass costs more than the bytes it saves."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)
        out_dtype = arr.dtype
        wire_dt, acc_dtype = _wire_spec(
            arr.dtype, prescale, widen=op_average or postscale != 1.0)
        flat = arr.reshape(-1).astype(acc_dtype)
        if prescale != 1.0:
            flat = flat * prescale
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes))
        item = wire_dt.itemsize
        nbytes = flat.size * item

        if p > 1:
            m = p.bit_length() - 1        # floor(log2(p))
            pow2 = 1 << m
            if idx >= pow2:
                # extra: fold into the partner, receive the result back
                partner = participants[idx - pow2]
                out = np.ascontiguousarray(flat.astype(wire_dt))
                self.send_chunk(partner, (ring_id, "rdf"),
                                _as_bytes_view(out), seg_bytes=seg,
                                align=item)
                blob = self.recv_chunk((ring_id, "rdb"), partner, nbytes,
                                       timeout=timeout, seg_bytes=seg,
                                       align=item)
                flat = np.frombuffer(blob, wire_dt).astype(acc_dtype)
            else:
                if idx + pow2 < p:
                    blob = self.recv_chunk(
                        (ring_id, "rdf"), participants[idx + pow2],
                        nbytes, timeout=timeout, seg_bytes=seg,
                        align=item)
                    flat = flat + np.frombuffer(blob, wire_dt).astype(
                        acc_dtype, copy=False)
                for k in range(m):
                    partner = participants[idx ^ (1 << k)]
                    mine = np.ascontiguousarray(flat.astype(wire_dt))
                    self.send_chunk(partner, (ring_id, "rd", k),
                                    _as_bytes_view(mine), seg_bytes=seg,
                                    align=item)
                    blob = self.recv_chunk(
                        (ring_id, "rd", k), partner, nbytes,
                        timeout=timeout, seg_bytes=seg, align=item)
                    # decode(mine) + decode(theirs): both partners sum
                    # the same wire values -> bitwise-equal partials
                    flat = (mine.astype(acc_dtype) +
                            np.frombuffer(blob, wire_dt).astype(
                                acc_dtype, copy=False))
                # adopt the final wire encoding on EVERY survivor (not
                # just partners of extras) so extras' decoded copies and
                # survivors' accumulators agree bitwise before any
                # average/postscale math
                wfin = np.ascontiguousarray(flat.astype(wire_dt))
                if idx + pow2 < p:
                    self.send_chunk(participants[idx + pow2],
                                    (ring_id, "rdb"),
                                    _as_bytes_view(wfin), seg_bytes=seg,
                                    align=item)
                flat = wfin.astype(acc_dtype)
            self._flush_sends(timeout)
        if op_average:
            flat = flat / world_size
        if postscale != 1.0:
            flat = flat * postscale
        return flat.astype(out_dtype).reshape(arr.shape)

    # -------------------------------------------------------- reduce_scatter
    def reduce_scatter(self, ring_id, arr, participants, *, op_average,
                       world_size, prescale=1.0, postscale=1.0,
                       timeout=None, compression="none",
                       segment_bytes=None):
        """First-class reduce-scatter: the ring allreduce's reduce-scatter
        half, exposed on its own (the ZeRO decomposition's first stage).
        Chunk boundaries sit at FIRST-DIMENSION rows, partitioned
        np.array_split style (``reduce_scatter_split_sizes``), and the
        rank at position ``idx`` of the sorted participants receives
        chunk ``idx`` — unlike the fused allreduce's internal leg, whose
        element-granular chunks land one position rotated.  Returns this
        rank's reduced row block in the input dtype."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)

        out_dtype = arr.dtype
        rest = arr.shape[1:]
        counts = reduce_scatter_split_sizes(arr.shape[0], p)
        row = int(np.prod(rest or (1,)))
        sizes = [c * row for c in counts]
        bounds = np.cumsum([0] + sizes)

        float_in = is_float_dtype(arr.dtype)
        wire_dt, acc_dtype = _wire_spec(
            arr.dtype, prescale, widen=op_average or postscale != 1.0)
        flat = arr.reshape(-1).astype(acc_dtype)
        if prescale != 1.0:
            flat = flat * prescale
        codec = (_codecs().get(compression)
                 if float_in and compression not in (None, "none") else None)
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes))
        chunks = [flat[bounds[i]:bounds[i + 1]] for i in range(p)]
        if p == 1:
            own = chunks[0]
        elif codec is not None:
            own = self._reduce_scatter_compressed(
                ring_id, chunks, sizes, participants, idx, codec, timeout,
                seg)
        else:
            own = self._reduce_scatter_exact(
                ring_id, chunks, sizes, participants, idx, wire_dt, timeout,
                seg)
        if op_average:
            own = own / world_size
        if postscale != 1.0:
            own = own * postscale
        return own.astype(out_dtype).reshape((counts[idx],) + rest)

    def _reduce_scatter_exact(self, ring_id, chunks, sizes, participants,
                              idx, wire_dt, timeout, seg):
        """The pipelined ring's reduce-scatter leg, shifted one chunk so
        rank ``idx`` ends up owning chunk ``idx`` (the fused allreduce
        leaves rank ``idx`` holding chunk ``(idx+1) % p``): at step ``s``
        send the running partial of chunk ``(idx-1-s) % p`` rightward and
        accumulate chunk ``(idx-2-s) % p`` from the left."""
        p = len(participants)
        right = participants[(idx + 1) % p]
        left = participants[(idx - 1) % p]
        item = wire_dt.itemsize
        for s in range(p - 1):
            send_i = (idx - 1 - s) % p
            recv_i = (idx - 2 - s) % p
            out = chunks[send_i].astype(wire_dt)
            self.send_chunk(right, (ring_id, "rs", s), _as_bytes_view(out),
                            seg_bytes=seg, align=item)
            target = chunks[recv_i]

            def accumulate(offset, segment, target=target):
                lo = offset // item
                decoded = np.frombuffer(segment, dtype=wire_dt)
                target[lo:lo + decoded.size] += decoded.astype(
                    target.dtype, copy=False)

            self.recv_chunk((ring_id, "rs", s), left,
                            sizes[recv_i] * item, timeout=timeout,
                            consume=accumulate, seg_bytes=seg, align=item)
        self._flush_sends(timeout)
        return chunks[idx]

    def _reduce_scatter_compressed(self, ring_id, chunks, sizes,
                                   participants, idx, codec, timeout, seg):
        """The compressed allreduce's owner-targeted reduce-scatter half
        without the allgather rotation: each rank encodes its
        contribution to every destination chunk ONCE and ships it
        straight to the chunk's owner, who accumulates in float64 — one
        quantization per contribution, same wire format as the fused
        path."""
        enc, dec, enc_nbytes = codec
        p = len(participants)
        for d in range(p):
            if d != idx:
                self.send_chunk(participants[d], (ring_id, "qrs", d),
                                enc(np.ascontiguousarray(chunks[d])),
                                seg_bytes=seg)
        acc = chunks[idx].astype(np.float64, copy=True)
        for src_i, src in enumerate(participants):
            if src_i == idx:
                continue
            blob = self.recv_chunk((ring_id, "qrs", idx), src,
                                   enc_nbytes(sizes[idx]),
                                   timeout=timeout, seg_bytes=seg)
            acc += dec(blob, sizes[idx])
        self._flush_sends(timeout)
        return acc

    # ----------------------------------------------------- seed reference
    def allreduce_seed(self, ring_id, arr, participants, *, op_average,
                       world_size, prescale=1.0, postscale=1.0,
                       timeout=None):
        """The seed-era exact ring, verbatim: float64/int64 accumulator
        bytes on the wire, strictly serial whole-chunk blocking steps on
        the control connection.  Kept as the oracle for the pipelined
        plane's parity matrix (``tests/test_tcp_matrix.py``) — NOT used
        in production."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)

        out_dtype = arr.dtype
        float_in = is_float_dtype(arr.dtype)
        acc_dtype = np.float64 if float_in else np.int64
        flat = arr.reshape(-1).astype(acc_dtype)
        if prescale != 1.0:
            flat = flat * prescale
        if p == 1:
            total = flat
        else:
            right = participants[(idx + 1) % p]
            left = participants[(idx - 1) % p]
            chunks = np.array_split(flat, p)
            for s in range(p - 1):
                send_i = (idx - s) % p
                recv_i = (idx - 1 - s) % p
                self.send(right, ((ring_id, "rs", s)),
                          np.ascontiguousarray(chunks[send_i]).tobytes())
                data = self.recv(((ring_id, "rs", s)), left, timeout=timeout)
                chunks[recv_i] = chunks[recv_i] + np.frombuffer(
                    data, dtype=acc_dtype)
            for s in range(p - 1):
                send_i = (idx + 1 - s) % p
                recv_i = (idx - s) % p
                self.send(right, ((ring_id, "ag", s)),
                          np.ascontiguousarray(chunks[send_i]).tobytes())
                data = self.recv(((ring_id, "ag", s)), left, timeout=timeout)
                chunks[recv_i] = np.frombuffer(data, dtype=acc_dtype)
            total = np.concatenate(chunks)
        if op_average:
            total = total / world_size
        if postscale != 1.0:
            total = total * postscale
        return total.astype(out_dtype).reshape(arr.shape)

    # --------------------------------------------------------------- adasum
    def adasum(self, ring_id, arr, participants, *, timeout=None,
               segment_bytes=None):
        """Distributed Adasum vector-halving distance-doubling
        (reference: ``Adasum<Communicator_type>::FusedAllreduce``,
        ``adasum/adasum.h:194-330``) over the p2p plane — no rank-0
        payload hotspot: per-rank traffic is ~2|x| halves plus 24-byte
        scalar rounds.

        At level ``k`` this rank exchanges half of its current piece
        with ``participants[idx ^ 2^k]``; the dot/norm scalars of the
        two logical vectors (distributed over the ``2^(k+1)``-rank
        group) are star-reduced through the group's lowest rank (the
        reference's per-level ``reduction_comms``); coefficients
        combine the halves.  After ``log2(p)`` levels each rank holds
        ``1/p`` of the result at bit-reversed chunk order; a block
        gather + static permutation rebuilds the full vector — same
        algebra as :func:`horovod_tpu.ops.adasum.adasum_vhdd`, which the
        numpy oracle validates.

        Accumulation and the scalar reductions stay float64 locally;
        the exchanged halves and gathered blocks wire the array's
        NATIVE dtype (floats) — the gather rotates each rank's
        once-encoded piece verbatim, so the rebuilt vector is
        rank-consistent.

        ``participants`` must be ALL world ranks (the coordinator
        falls back to the payload path when ranks have joined) and a
        power of two.
        """
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)
        if p & (p - 1):
            raise ValueError(
                f"ring Adasum requires power-of-two ranks, got {p}")
        out_dtype = arr.dtype
        shape = arr.shape
        size = arr.size
        if p == 1:
            return arr
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes))
        wire_dt = (np.dtype(arr.dtype) if is_float_dtype(arr.dtype)
                   else np.dtype(np.float64))
        item = wire_dt.itemsize
        padded = -(-size // p) * p
        piece = np.zeros(padded, np.float64)
        piece[:size] = arr.reshape(-1).astype(np.float64)

        dist = 1
        level = 0
        while dist < p:
            half = piece.size // 2
            low, high = piece[:half], piece[half:]
            bit = (idx // dist) % 2
            send_half, mine = (high, low) if bit == 0 else (low, high)
            peer = participants[idx ^ dist]
            self.send_chunk(peer, (ring_id, "ad", level),
                            _as_bytes_view(send_half.astype(wire_dt)),
                            seg_bytes=seg)
            recv = np.frombuffer(
                self.recv_chunk((ring_id, "ad", level), peer,
                                half * item, timeout=timeout,
                                seg_bytes=seg),
                dtype=wire_dt).astype(np.float64)
            # a = the lower sub-group's vector piece, b = the upper's —
            # fixed roles so every group member reduces the same scalars
            a, b = (mine, recv) if bit == 0 else (recv, mine)
            partial = np.array([a @ b, a @ a, b @ b])

            group = [r for r in range(p)
                     if r // (2 * dist) == idx // (2 * dist)]
            leader = group[0]
            if idx == leader:
                total = partial.copy()
                for member in group[1:]:
                    total += np.frombuffer(self.recv(
                        ((ring_id, "adp", level)),
                        participants[member], timeout=timeout), np.float64)
                blob = np.ascontiguousarray(total).tobytes()
                for member in group[1:]:
                    self.send(participants[member],
                              ((ring_id, "ads", level)), blob)
            else:
                self.send(participants[leader], ((ring_id, "adp", level)),
                          np.ascontiguousarray(partial).tobytes())
                total = np.frombuffer(self.recv(
                    ((ring_id, "ads", level)), participants[leader],
                    timeout=timeout), np.float64)
            dot, na, nb = total
            a_coeff = 1.0 - dot / (2.0 * na) if na > 0 else 1.0
            b_coeff = 1.0 - dot / (2.0 * nb) if nb > 0 else 1.0
            piece = a_coeff * a + b_coeff * b
            dist *= 2
            level += 1

        # block gather (ring rotation) of the NATIVE-dtype pieces, then
        # undo the bit-reversed chunk order the halving walk leaves
        # behind (adasum.py:150-153).  Every rank decodes each piece
        # from the same once-encoded blob — its own included.
        own_wire = piece.astype(wire_dt)
        blocks = {idx: _as_bytes_view(own_wire)}
        block_nbytes = piece.size * item
        right = participants[(idx + 1) % p]
        left = participants[(idx - 1) % p]
        carry = idx
        for s in range(p - 1):
            self.send_chunk(right, (ring_id, "adg", s), blocks[carry],
                            seg_bytes=seg)
            recv_owner = (idx - 1 - s) % p
            blocks[recv_owner] = self.recv_chunk(
                (ring_id, "adg", s), left, block_nbytes, timeout=timeout,
                seg_bytes=seg)
            carry = recv_owner
        self._flush_sends(timeout)
        levels = p.bit_length() - 1
        order = [int(format(i, f"0{levels}b")[::-1], 2) for i in range(p)]
        full = np.concatenate([
            np.frombuffer(blocks[order[i]], dtype=wire_dt).astype(
                np.float64)
            for i in range(p)])
        return full[:size].reshape(shape).astype(out_dtype)

    # ------------------------------------------------------------- broadcast
    def broadcast(self, ring_id, arr_or_none, participants, root, *,
                  shape, dtype, timeout=None, segment_bytes=None):
        """Segmented pipeline around the ring rooted at ``root``: every
        rank receives each segment once from its left neighbor and
        forwards it once to its right AS IT ARRIVES — the root uploads
        the tensor exactly once, in its native dtype, and hop latency
        overlaps across segments."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)
        root_idx = participants.index(root)
        right = participants[(idx + 1) % p]
        nbytes = int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
        seg = (self.segment_bytes if segment_bytes is None
               else int(segment_bytes)) or BCAST_CHUNK

        if self.rank == root:
            data = np.ascontiguousarray(arr_or_none)
            if p > 1:
                self.send_chunk(right, (ring_id, "bc"),
                                _as_bytes_view(data), seg_bytes=seg)
            data = data.tobytes()
        else:
            left = participants[(idx - 1) % p]
            last = (idx + 1) % p == root_idx  # my right neighbor is root
            forward = not last and not faults.check("send")
            pieces = []

            def relay(offset, segment, seg_i=[0]):
                if forward:
                    self._enqueue_segment(right, seg_i[0],
                                          (ring_id, "bc", seg_i[0]),
                                          segment)
                seg_i[0] += 1
                pieces.append(segment)

            self.recv_chunk((ring_id, "bc"), left, nbytes,
                            timeout=timeout, consume=relay, seg_bytes=seg)
            data = (bytes(pieces[0]) if len(pieces) == 1
                    else b"".join(pieces))
        if p > 1:
            self._flush_sends(timeout)
        return np.frombuffer(data, dtype=np.dtype(dtype)).reshape(shape)

    # ------------------------------------------------------------- allgather
    def allgather(self, ring_id, arr, participants, *, block_nbytes=None,
                  timeout=None, segment_bytes=None):
        """Ring block rotation: each step forwards the block received the
        previous step; after p-1 steps every rank holds every block.
        Returns the blocks (bytes) in participant rank order — blocks
        travel as raw native-dtype bytes, segmented across the stripes;
        ``block_nbytes`` gives each participant's block size (negotiated
        out-of-band by the coordinator; None falls back to unsegmented
        single-frame rotation for callers that don't know the sizes)."""
        participants = sorted(participants)
        p = len(participants)
        idx = participants.index(self.rank)
        blocks = {self.rank: np.ascontiguousarray(arr).tobytes()}
        if p > 1:
            right = participants[(idx + 1) % p]
            left = participants[(idx - 1) % p]
            seg = ((self.segment_bytes if segment_bytes is None
                    else int(segment_bytes))
                   if block_nbytes is not None else 0)
            sizes = (dict(zip(participants, block_nbytes))
                     if block_nbytes is not None else None)
            carry_owner = self.rank
            for s in range(p - 1):
                self.send_chunk(right, (ring_id, "ag", s),
                                blocks[carry_owner], seg_bytes=seg)
                recv_owner = participants[(idx - 1 - s) % p]
                nbytes = (sizes[recv_owner] if sizes is not None
                          else len(blocks[carry_owner]))
                blocks[recv_owner] = self.recv_chunk(
                    (ring_id, "ag", s), left, nbytes, timeout=timeout,
                    seg_bytes=seg)
                carry_owner = recv_owner
            self._flush_sends(timeout)
        return [blocks[r] for r in participants]
