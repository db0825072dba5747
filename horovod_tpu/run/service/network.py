"""Secret-keyed pickled-message TCP services (reference:
``horovod/run/common/service/__init__.py`` + ``horovod/run/common/util/
network.py`` — a threaded socket server exchanging HMAC-signed pickled
request/response objects, plus interface enumeration helpers used for
routable-NIC discovery).

Wire format per message: ``[4-byte big-endian length][32-byte HMAC-SHA256
digest][pickled (direction, object)]``.  The digest is verified BEFORE
unpickling — an unauthenticated peer cannot reach the unpickler — and the
claimed length is capped before any buffering, so an unauthenticated peer
cannot make the service hold gigabytes either.  The signed envelope
carries a direction tag ("q" request / "r" response) so a reflected
frame cannot answer a request, and mux request ids start at a random
64-bit offset so a frame recorded from an earlier connection cannot pair
with a live request.  (An on-path adversary that can splice into the TCP
stream in real time is outside this threat model — that requires TLS.)
"""

import pickle
import random
import secrets as _secrets
import socket
import socketserver
import struct
import sys
import threading
import time

from horovod_tpu.run.service import secret
from horovod_tpu.utils import env as env_util

# Largest frame accepted before authentication.  Generous: the tcp star
# data plane ships whole tensors (hundreds of MB for a fused bucket).
MAX_FRAME_BYTES = 1 << 30

# Bulk (raw-bytes) frame: the high bit of the length word flags a frame
# whose payload travels as raw bytes AFTER a small pickled header —
#   [4B RAW_FRAME_FLAG|header_len][32B HMAC][4B payload_len]
#   [pickled (direction, obj)][payload bytes]
# The HMAC covers [header_len][payload_len][header][payload] — the
# length words are bound in so an on-path attacker can't shift the
# header/payload boundary into a silently truncated payload — and is
# verified before unpickling; the payload is never pickled (no
# serialize copy on the send side, a single recv_into buffer on the
# receive side).  MAX_FRAME_BYTES < 2^30 keeps the flag bit
# unambiguous.
RAW_FRAME_FLAG = 0x80000000
# the pickled header of a bulk frame is a tag + rank, never big
MAX_RAW_HEADER_BYTES = 1 << 16


# ------------------------------------------------------------- base messages
class PingRequest:
    pass


class PingResponse:
    def __init__(self, service_name):
        self.service_name = service_name


class AckResponse:
    pass


# Fault-tolerance control messages, shared by the tcp and global-mesh
# coordinators (docs/fault_tolerance.md): any rank can broadcast an
# abort for the in-flight round; heartbeats keep the coordinator's
# last-seen table fresh and carry the abort state back.
# epoch-exempt: the abort channel is epoch-agnostic by design — a
# world dying at epoch N must be able to kill collectives on ranks that
# already adopted N+1; fencing it would strand exactly the straggler
# ranks an abort exists to release (docs/fault_tolerance.md)
class AbortMsg:
    def __init__(self, origin_rank, reason):
        self.origin_rank = origin_rank
        self.reason = reason


# epoch-exempt: liveness must keep flowing across reconfiguration
# boundaries — the coordinator's last-seen table is how a rank that
# died MID-reconfiguration gets detected, so heartbeats deliberately
# cross epochs (docs/fault_tolerance.md)
class HeartbeatMsg:
    def __init__(self, rank, busy=False, rtt=None, host=None,
                 reconnecting=None):
        self.rank = rank
        # peers this rank is currently healing a session toward
        # (docs/fault_tolerance.md "connection blips vs dead peers"):
        # the coordinator treats a healing rank like a busy one — wider
        # liveness deadline, no straggler verdicts — so a link blip is
        # never converted into an exclusion or an abort
        self.reconnecting = reconnecting
        # sender's launcher host hash (run/host_hash.py): the
        # coordinator groups co-located ranks from these when planning
        # the hierarchical collective schedule (docs/tuning.md)
        self.host = host
        # rank is inside a known-slow-but-alive window (checkpoint
        # write, drain teardown): the coordinator widens its liveness
        # deadline so disk I/O can't read as death (docs/checkpoint.md)
        self.busy = busy
        # sender's worst observed link RTT EWMA in seconds (heartbeat
        # round trips + ring chunk sends): the coordinator adds an
        # RTT-proportional slack to this rank's liveness window so a
        # slow-but-alive link never reads as death
        # (docs/fault_tolerance.md "degraded networks")
        self.rtt = rtt


class HeartbeatReply:
    def __init__(self, abort=None):
        self.abort = abort  # (origin_rank, reason) | None


# -------------------------------------------------------- session messages
# Reliable session layer (docs/fault_tolerance.md "connection blips vs
# dead peers"): every long-lived peer connection opens with a hello /
# welcome exchange that names a stable session id, and every frame the
# client writes carries a monotonic sequence number inside its request
# id.  On a mid-stream break the client reconnects inside the
# HVD_TPU_RECONNECT_BUDGET window, re-offers the same session, learns
# from the welcome which frames the service already delivered, and
# retransmits only the tail — the service dedups by seq, so a collective
# in flight completes without any rank observing an error.  The layer is
# entirely inert (zero extra frames, request ids unchanged) when the
# budget is 0.
class SessionHello:
    def __init__(self, session_id, epoch, rx_seen):
        self.session_id = session_id
        # the sender's view of the controller epoch: a hello from before
        # a reconfiguration must NOT resume into the new epoch's service
        # (the welcome comes back refused and the client escalates)
        self.epoch = epoch
        self.rx_seen = rx_seen  # reserved: client->service direction only


class SessionWelcome:
    def __init__(self, rx_seen, refused=False):
        # highest contiguous client seq this service delivered — the
        # client prunes its replay buffer to here and retransmits the
        # rest
        self.rx_seen = rx_seen
        self.refused = refused  # epoch fence: do not resume, escalate


class SessionAck:
    def __init__(self, seen):
        self.seen = seen  # cumulative: every seq <= seen is delivered


# session knobs resolve from the env contract at client construction
# (tests pass explicit ctor kwargs instead to avoid env mutation)
def default_reconnect_budget():
    return env_util.get_float(env_util.HVD_TPU_RECONNECT_BUDGET,
                              env_util.DEFAULT_RECONNECT_BUDGET_SECONDS)


def default_replay_bytes():
    return env_util.get_int(env_util.HVD_TPU_REPLAY_BUFFER_BYTES,
                            env_util.DEFAULT_REPLAY_BUFFER_BYTES)


# service acks every Nth delivered frame (piggybacked on the existing
# connection, never a new one); the sender prunes its replay buffer on
# each — so steady-state overhead is one tiny frame per N, not per write
_SESSION_ACK_EVERY = 16
# responses the service retains per session for redelivery after a heal
# (a response can vanish in the kernel buffer of a dying socket without
# the write erroring — the resume flush covers that window)
_SESSION_RESP_KEEP = 256
# replay-buffer byte estimate for a control frame (the exact pickled
# size isn't known until write time; control messages are tiny and the
# bound only needs the right order of magnitude)
_CTRL_FRAME_EST = 1024

# session-id sanity bound: ours are 16 hex chars (token_hex(8)); a
# verified-but-hostile hello must not intern megabyte strings as dict
# keys
_MAX_SESSION_ID_LEN = 64
# sessions retained per service: sessions outlive sockets by design, so
# without a cap a peer re-helloing with fresh ids would grow the table
# forever.  At the cap, admission first evicts sessions with no live
# socket (oldest first), then refuses.
_MAX_SESSIONS = 1024


def _valid_seq(value):
    """True for a trustworthy sequence/ack number: a real int (bool is
    an int subclass but never a seq) in the non-negative range a
    well-behaved peer can produce.  Everything in a session record —
    seq, ack ``seen``, welcome ``rx_seen`` — arrives inside a VERIFIED
    envelope, but verified only means the peer holds the key, not that
    the field is sane: these values reach dict keys, comparisons and
    replay-buffer arithmetic, so they are bounds-checked like any other
    wire input."""
    return (isinstance(value, int) and not isinstance(value, bool)
            and 0 <= value < (1 << 62))


# process-wide session telemetry (the soak gates read these)
_session_stats_lock = threading.Lock()
_session_stats = {"reconnects_healed": 0, "reconnects_failed": 0,
                  "frames_replayed": 0}


def _session_note(kind, n=1):
    with _session_stats_lock:
        _session_stats[kind] = _session_stats.get(kind, 0) + n


def session_stats():
    """Snapshot of the process-wide session-layer counters."""
    with _session_stats_lock:
        return dict(_session_stats)


# peers with a heal in flight RIGHT NOW: the worker's heartbeat reports
# these so the coordinator widens the liveness deadline instead of
# reading the recovery pause as death
_healing_lock = threading.Lock()
_healing = {}  # peer -> nesting depth


def _healing_enter(peer):
    with _healing_lock:
        _healing[peer] = _healing.get(peer, 0) + 1


def _healing_exit(peer):
    with _healing_lock:
        depth = _healing.get(peer, 0) - 1
        if depth <= 0:
            _healing.pop(peer, None)
        else:
            _healing[peer] = depth


def healing_peers():
    """Sorted ranks this process is currently healing a session toward."""
    with _healing_lock:
        return sorted(p for p in _healing if p is not None)


class _SessionResumeRefused(ConnectionError):
    """The service fenced the resume (stale epoch) or the replay buffer
    no longer holds a frame the service needs — healing would leave a
    silent gap, so the ORIGINAL transport error must escalate."""


class _SessionSender:
    """Client half of a transport session: assigns the per-direction
    sequence numbers, retains every unacknowledged frame in a
    byte-bounded replay buffer (drop-oldest), prunes on cumulative
    acks.  Callers serialize access under their own write lock so
    replay order always equals wire order."""

    def __init__(self, epoch, replay_bytes):
        self.session_id = _secrets.token_hex(8)
        self.epoch = epoch
        self._limit = max(0, int(replay_bytes))
        self._frames = {}      # seq -> (record, nbytes); insertion-ordered
        self._bytes = 0
        self._next = 1
        self._oldest = 1       # oldest seq still retained
        self.acked = 0

    def append(self, make_record, nbytes):
        """Assign the next seq, build the frame record via
        ``make_record(seq)`` and retain it for replay.  Returns
        ``(seq, record)``."""
        seq = self._next
        self._next += 1
        record = make_record(seq)
        self._frames[seq] = (record, nbytes)
        self._bytes += nbytes
        while self._bytes > self._limit and self._frames:
            old = next(iter(self._frames))
            _, nb = self._frames.pop(old)
            self._bytes -= nb
            self._oldest = old + 1
        return seq, record

    def ack(self, seen):
        """Cumulative ack: drop every retained frame with seq <= seen."""
        while self._frames:
            seq = next(iter(self._frames))
            if seq > seen:
                break
            _, nb = self._frames.pop(seq)
            self._bytes -= nb
        if seen + 1 > self._oldest:
            self._oldest = seen + 1
        if seen > self.acked:
            self.acked = seen

    def replayable_from(self, rx_seen):
        """Frame records to retransmit after a heal — everything newer
        than what the service delivered.  None when the service needs a
        frame the byte bound already evicted (resuming would skip it
        silently, so the caller must escalate instead)."""
        self.ack(rx_seen)
        if rx_seen + 1 < self._oldest:
            return None
        return [rec for rec, _ in self._frames.values()]


class _SessionState:
    """Service half of a transport session.  Outlives any one socket:
    ``sock``/``write_lock`` always point at the session's CURRENT
    connection, so in-flight handler threads route their responses to
    wherever the client is now, not to the socket their request arrived
    on."""

    __slots__ = ("session_id", "epoch", "seen", "dup_drops", "lock",
                 "sock", "write_lock", "responses",
                 "delivered_since_ack")

    def __init__(self, session_id, epoch):
        self.session_id = session_id
        self.epoch = epoch
        self.seen = 0            # highest contiguous seq delivered
        self.dup_drops = 0
        self.lock = threading.Lock()
        self.sock = None         # live socket; guarded by self.lock
        self.write_lock = None   # its write lock; guarded by self.lock
        # req_id -> (req_id, resp) wire tuples retained for redelivery
        # after a resume; bounded at _SESSION_RESP_KEEP
        self.responses = {}
        self.delivered_since_ack = 0


def _session_handshake_client(sock, key, session, timeout):
    """Open or resume ``session`` on a freshly connected socket: write
    the hello, synchronously await the welcome (no reader thread exists
    yet, so this read races nothing)."""
    write_message(sock, key, (None, SessionHello(
        session.session_id, session.epoch, 0)), "q")
    old_timeout = sock.gettimeout()
    sock.settimeout(timeout)
    try:
        frame = read_message(sock, key, "r")
    finally:
        sock.settimeout(old_timeout)
    if not (isinstance(frame, tuple) and len(frame) == 2
            and isinstance(frame[1], SessionWelcome)):
        raise ConnectionError(
            "session handshake expected SessionWelcome, got "
            f"{type(frame).__name__}")
    welcome = frame[1]
    # rx_seen flows into replay-buffer arithmetic; a verified welcome
    # carrying garbage there must fail the handshake typed, not raise
    # TypeError inside the sender's ack bookkeeping
    if not welcome.refused and not _valid_seq(welcome.rx_seen):
        raise ConnectionError(
            f"session welcome carried invalid rx_seen "
            f"({type(welcome.rx_seen).__name__})")
    return welcome


# ------------------------------------------------------- retry / backoff
def backoff_delay(attempt, base=0.05, cap=2.0):
    """Exponential backoff with jitter (50-100% of the exponential
    step): simultaneous rank retries after a shared blip decorrelate
    instead of synchronizing into a thundering herd."""
    return min(cap, base * (1 << min(attempt, 16))) * \
        (0.5 + random.random() * 0.5)


def default_connect_retry():
    return env_util.get_float(env_util.HVD_TPU_CONNECT_RETRY_SECONDS,
                              env_util.DEFAULT_CONNECT_RETRY_SECONDS)


def connect(addr, timeout, peer=None):
    """All control/data-plane TCP connects funnel through here: one
    fault-injection point ("connect") covers rendezvous, negotiation and
    the ring transport.  A "drop" at this point is a dropped SYN, which
    the caller can only observe as a failed connect — same surface as
    "refuse".  ``peer`` scopes per-link faults: a reconnect toward a
    peer whose blip window is still open is refused (the flap is still
    down), so the session layer's backoff loop rides it out."""
    from horovod_tpu.common import faults

    if faults.check("connect", peer=peer):
        raise ConnectionRefusedError(
            "injected connection drop at connect (HVD_TPU_FAULT_SPEC)")
    return socket.create_connection(addr, timeout=timeout)


class _RetryableSendError(ConnectionError):
    """Internal marker: the request may be safely retried in full
    (nothing reached the service, or the request is idempotent)."""


# ------------------------------------------------- degraded-link injection
# bound on one injected sleep: a chaos cell must slow the job, never
# wedge it past its own deadlines' ability to tell slow from dead
_MAX_DEGRADE_SLEEP = 5.0
_flaky_noted = set()    # peers already logged; guarded by _flaky_note_lock
_reset_noted = set()    # peers already logged; guarded by _flaky_note_lock
_flaky_note_lock = threading.Lock()


def _note_flaky(peer):
    with _flaky_note_lock:
        if peer in _flaky_noted:
            return
        _flaky_noted.add(peer)
    print(f"[hvd-fault] flaky link toward peer {peer}: dropping writes, "
          f"transport resends (injected)", file=sys.stderr, flush=True)


def _note_reset(peer):
    with _flaky_note_lock:
        if peer in _reset_noted:
            return
        _reset_noted.add(peer)
    print(f"[hvd-fault] mid-stream reset toward peer {peer}: cutting "
          f"the connection, session layer heals (injected)",
          file=sys.stderr, flush=True)


def _apply_link_faults(peer, nbytes=None, sock=None):
    """Client-side framing-layer chaos (docs/fault_tolerance.md
    "degraded networks"): every client frame write — control mux,
    bulk-stripe, mailbox — funnels through here, so an armed
    degradation is felt by all three paths.  ``peer`` is the remote's
    rank (None: unknown, e.g. rendezvous); ``nbytes`` sizes the
    throttle pacing for bulk payloads.

    A flaky drop loses the write BEFORE any byte leaves the socket, so
    the resend here is always safe — the peer never saw a partial
    frame (the TCP-retransmit analog, surfaced once per peer for the
    chaos log).  A partition fails the write outright, exactly like an
    unreachable host.  A mid-stream ``reset``/``blip`` verdict puts a
    PARTIAL frame prefix on the wire first (when ``sock`` is given),
    hard-closes the socket and raises ConnectionResetError — the one
    failure mode the session layer's reconnect + replay path exists
    for."""
    from horovod_tpu.common import faults

    state = faults.link(peer)
    if state is None:
        return
    attempts = 0
    while state is not None and state.drop and not state.reset:
        _note_flaky(peer)
        attempts += 1
        if attempts >= 1000:
            raise ConnectionResetError(
                f"injected flaky link toward peer {peer} dropped "
                f"{attempts} consecutive writes (HVD_TPU_FAULT_SPEC)")
        time.sleep(0.002)
        state = faults.link(peer)
    if state is None:
        return
    if state.partitioned:
        raise ConnectionResetError(
            f"injected network partition toward peer {peer} "
            f"(HVD_TPU_FAULT_SPEC)")
    if state.reset:
        if sock is not None:
            # two bytes of a frame header, then a hard close: the peer's
            # reader blocks mid-header and sees the cut exactly the way
            # a real RST lands — genuinely mid-stream, never a cleanly
            # framed boundary
            try:
                # wire-safe: deliberately UNSIGNED garbage — this IS the
                # injected fault (a torn frame), not a protocol message
                sock.sendall(b"\x15\x03")
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        _note_reset(peer)
        raise ConnectionResetError(
            f"injected mid-stream connection reset toward peer {peer} "
            f"(HVD_TPU_FAULT_SPEC)")
    sleep_s = state.delay_s
    if state.throttle_bps > 0 and nbytes:
        sleep_s += nbytes / state.throttle_bps
    if sleep_s > 0:
        time.sleep(min(sleep_s, _MAX_DEGRADE_SLEEP))


# ---------------------------------------------------------------- wire codec
def write_message(sock, key, obj, direction):
    payload = pickle.dumps((direction, obj))
    if len(payload) > MAX_FRAME_BYTES:
        # fail HERE with a clear error — the receiver would just drop
        # the connection and the sender would see a mute timeout
        raise ValueError(
            f"frame of {len(payload)} bytes exceeds the "
            f"{MAX_FRAME_BYTES}-byte transport limit")
    digest = secret.sign(key, payload)
    frame = struct.pack(">I", len(payload)) + digest + payload
    sock.sendall(frame)
    return len(frame)


def write_bulk_message(sock, key, obj, payload, direction):
    """Raw-bytes bulk frame: ``obj`` is a small header object (pickled;
    its ``payload`` attribute must be None — the receiver injects the
    raw bytes there), ``payload`` is bytes-like and goes on the wire
    verbatim via scatter-gather, never through pickle.  Returns the
    frame size in bytes."""
    hdr = pickle.dumps((direction, obj))
    payload = memoryview(payload).cast("B")
    if len(hdr) > MAX_RAW_HEADER_BYTES:
        raise ValueError(
            f"bulk frame header of {len(hdr)} bytes exceeds the "
            f"{MAX_RAW_HEADER_BYTES}-byte limit")
    if payload.nbytes > MAX_FRAME_BYTES:
        raise ValueError(
            f"bulk payload of {payload.nbytes} bytes exceeds the "
            f"{MAX_FRAME_BYTES}-byte transport limit")
    lengths = struct.pack(">II", len(hdr), payload.nbytes)
    digest = secret.sign_parts(key, lengths, hdr, payload)
    prefix = (struct.pack(">I", RAW_FRAME_FLAG | len(hdr)) + digest +
              struct.pack(">I", payload.nbytes) + hdr)
    _sendall_vec(sock, [prefix, payload])
    return len(prefix) + payload.nbytes


def _sendall_vec(sock, buffers):
    """sendall over a list of buffers without concatenating them (one
    sendmsg syscall per iteration; falls back to per-buffer sendall).
    Only ever called with complete pre-signed frames built by
    :func:`write_bulk_message`."""
    bufs = [memoryview(b).cast("B") for b in buffers if len(b)]
    if not hasattr(sock, "sendmsg"):
        for b in bufs:
            sock.sendall(b)  # wire-safe: frame signed by the caller
        return
    while bufs:
        sent = sock.sendmsg(bufs)  # wire-safe: frame signed by caller
        while sent:
            if sent >= bufs[0].nbytes:
                sent -= bufs[0].nbytes
                bufs.pop(0)
            else:
                bufs[0] = bufs[0][sent:]
                sent = 0


def read_message(sock, key, expected_direction):
    header = _read_exact(sock, 4 + secret.DIGEST_LEN)
    (length,) = struct.unpack(">I", header[:4])
    digest = header[4:]
    if length & RAW_FRAME_FLAG:
        return _read_bulk(sock, key, expected_direction,
                          length & (RAW_FRAME_FLAG - 1), digest)
    if length > MAX_FRAME_BYTES:
        raise ConnectionError(
            f"frame length {length} exceeds limit {MAX_FRAME_BYTES}")
    payload = _read_exact(sock, length)
    if not secret.check(key, payload, digest):
        raise PermissionError("message failed HMAC verification")
    envelope = _loads_checked(payload)
    if not (isinstance(envelope, tuple) and len(envelope) == 2
            and envelope[0] == expected_direction):
        raise PermissionError(
            "message direction mismatch (reflected frame?)")
    return envelope[1]


def _loads_checked(payload):
    """Unpickle an HMAC-verified envelope, converting any decode failure
    into the transport's typed rejection.  A signed-but-undecodable
    frame (a peer running different code, or stream corruption that
    survived by chance) must surface exactly like any other malformed
    frame — a connection-scoped error the read loops already sever on —
    never an arbitrary exception type escaping into handler threads."""
    try:
        return pickle.loads(payload)
    except Exception as exc:  # noqa: BLE001 — unpickler raises freely
        raise PermissionError(
            f"verified frame failed to decode: "
            f"{type(exc).__name__}") from exc


def _read_bulk(sock, key, expected_direction, hdr_len, digest):
    """Read the remainder of a raw bulk frame (both length caps are
    checked before any buffering; the HMAC — covering the length words
    plus header plus payload — is verified before the header reaches
    the unpickler)."""
    if hdr_len > MAX_RAW_HEADER_BYTES:
        raise ConnectionError(
            f"bulk header length {hdr_len} exceeds limit "
            f"{MAX_RAW_HEADER_BYTES}")
    (payload_len,) = struct.unpack(">I", _read_exact(sock, 4))
    if payload_len > MAX_FRAME_BYTES:
        raise ConnectionError(
            f"bulk payload length {payload_len} exceeds limit "
            f"{MAX_FRAME_BYTES}")
    hdr = _read_exact(sock, hdr_len)
    payload = _read_exact_into(sock, payload_len)
    lengths = struct.pack(">II", hdr_len, payload_len)
    if not secret.check_parts(key, digest, lengths, hdr, payload):
        raise PermissionError("bulk message failed HMAC verification")
    envelope = _loads_checked(hdr)
    if not (isinstance(envelope, tuple) and len(envelope) == 2
            and envelope[0] == expected_direction):
        raise PermissionError(
            "message direction mismatch (reflected frame?)")
    obj = envelope[1]
    # payload injection: the carrier (the mux (req_id, obj) pair's
    # second element, or the bare object) declared a ``payload`` slot
    carrier = obj[1] if isinstance(obj, tuple) and len(obj) == 2 else obj
    try:
        carrier.payload = payload
    except (AttributeError, TypeError) as exc:
        # a verified header whose carrier can't accept the payload
        # (wrong type, slots without a payload slot) is still a
        # malformed frame — typed rejection, not an AttributeError
        # escaping into the reader loop
        raise PermissionError(
            f"bulk frame carrier {type(carrier).__name__} cannot "
            f"accept a payload") from exc
    return obj


def _read_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        # wakeable: closing the socket (peer abort/purge teardown, or
        # the owner's close()) breaks the blocked recv with an OSError;
        # callers set read timeouts where the protocol demands one
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed connection")
        buf += chunk
    return bytes(buf)


def _read_exact_into(sock, n):
    """One preallocated buffer filled by recv_into — the bulk payload is
    copied exactly once off the socket."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        # wakeable: socket close breaks the blocked recv (see
        # _read_exact)
        r = sock.recv_into(view[got:], n - got)
        if not r:
            raise ConnectionError("peer closed connection")
        got += r
    return buf


# ------------------------------------------------------------------- service
class BasicService:
    """Threaded TCP service answering one signed request per connection
    (reference: ``network.BasicService``)."""

    def __init__(self, name, key):
        self._name = name
        self._key = key
        self._start_server(self._make_handler())

    def _make_handler(self):
        service = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    # wakeable: server shutdown closes the listener and
                    # every accepted socket, breaking this read
                    req = read_message(self.request, service._key, "q")
                except (PermissionError, ConnectionError, EOFError):
                    return  # drop unauthenticated/broken peers silently
                try:
                    resp = service._handle(req, self.client_address)
                except Exception as exc:  # noqa: BLE001 — ship to client
                    resp = exc
                try:
                    write_message(self.request, service._key, resp, "r")
                except OSError:
                    pass  # client went away
                except Exception as exc:  # noqa: BLE001 — unpicklable resp
                    try:
                        write_message(
                            self.request, service._key,
                            RuntimeError(
                                f"response serialization failed: {exc}"),
                            "r")
                    except Exception:  # noqa: BLE001
                        pass

        return Handler

    def _start_server(self, handler):
        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server(("0.0.0.0", 0), handler)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True,
                                        name=f"{self._name}-service")
        self._thread.start()

    @property
    def port(self):
        return self._server.server_address[1]

    def addresses(self):
        """{interface: [(ip, port)]} for every non-loopback interface
        (reference: ``network.get_local_host_addresses``)."""
        out = {}
        for iface, ip in local_interfaces().items():
            out[iface] = [(ip, self.port)]
        return out

    def _handle(self, req, client_address):
        if isinstance(req, PingRequest):
            return PingResponse(self._name)
        raise ValueError(f"unknown request type {type(req).__name__}")

    def shutdown(self):
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5)


class BasicClient:
    """One-connection-per-request client (reference:
    ``network.BasicClient``): tries each known (ip, port) until one
    answers, remembers the winner."""

    def __init__(self, addresses, key, timeout=10, read_timeout="same",
                 retry_for=None, peer=None):
        # addresses: {iface: [(ip, port)]} or flat [(ip, port)].
        # ``timeout`` bounds connection establishment; ``read_timeout``
        # bounds the response wait (None = wait forever — collectives
        # legitimately block until every rank contributes, and the
        # coordinator owns stall detection).  ``retry_for`` is the
        # deadline budget for connect-phase retries with backoff+jitter
        # (None = HVD_TPU_CONNECT_RETRY_SECONDS; 0 = a single sweep) —
        # one RST during rendezvous must not kill the job.  ``peer`` is
        # the remote's rank when known, for link-level fault targeting.
        if isinstance(addresses, dict):
            flat = [a for addrs in addresses.values() for a in addrs]
        else:
            flat = list(addresses)
        if not flat:
            raise ValueError("no addresses to connect to")
        self._addresses = flat
        self._good = None
        self._key = key
        self._timeout = timeout
        self._peer = peer
        self._read_timeout = timeout if read_timeout == "same" \
            else read_timeout
        self._retry_for = (default_connect_retry() if retry_for is None
                           else retry_for)

    def _send_one(self, addr, req):
        with connect(addr, self._timeout, peer=self._peer) as sock:
            sock.settimeout(self._read_timeout)
            _apply_link_faults(self._peer, sock=sock)
            write_message(sock, self._key, req, "q")
            resp = read_message(sock, self._key, "r")
        if isinstance(resp, Exception):
            raise resp
        return resp

    def send(self, req, idempotent=False):
        """Address failover happens ONLY at the connect phase.  Once a
        request has been written, any error propagates — retransmitting a
        non-idempotent message (e.g. a collective contribution that is
        merely slow to complete) would hit the coordinator's
        duplicate-request detection and fail the job.  ``idempotent=True``
        (registrations, probes, polls) lifts that rule: the whole request
        is retried under the deadline budget even after a post-write
        failure.  A cached winner whose CONNECT fails is safe to fail
        over from (nothing was sent), so the other addresses are retried
        then; when every address refuses, the sweep repeats with
        exponential backoff + jitter until the ``retry_for`` budget is
        spent."""
        deadline = time.monotonic() + self._retry_for
        attempt = 0
        while True:
            try:
                return self._send_sweep(req, idempotent)
            except _RetryableSendError as exc:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ConnectionError(str(exc)) from exc
                time.sleep(min(backoff_delay(attempt), max(remaining, 0.0)))
                attempt += 1

    def _send_sweep(self, req, idempotent):
        """One pass over the candidate addresses."""
        candidates = list(self._addresses)
        if self._good is not None and self._good in candidates:
            candidates.remove(self._good)
            candidates.insert(0, self._good)
        last_error = None
        for addr in candidates:
            try:
                sock = connect(addr, self._timeout, peer=self._peer)
            except OSError as exc:
                last_error = exc
                if addr == self._good:
                    self._good = None
                continue
            try:
                with sock:
                    sock.settimeout(self._read_timeout)
                    _apply_link_faults(self._peer, sock=sock)
                    write_message(sock, self._key, req, "q")
                    resp = read_message(sock, self._key, "r")
            except OSError as exc:
                if idempotent:
                    # safe to resend in full: surface as retryable
                    raise _RetryableSendError(
                        f"idempotent request to {addr} failed after "
                        f"write: {exc}") from exc
                raise  # sent — do NOT failover to another address
            self._good = addr
            if isinstance(resp, Exception):
                raise resp
            return resp
        raise _RetryableSendError(
            f"could not reach service at any of {self._addresses}: "
            f"{last_error}")

    def probe(self):
        """Which of the candidate addresses actually answer a Ping
        (reference: the task-to-task address check,
        ``driver_service.py:156``)."""
        good = []
        for addr in self._addresses:
            try:
                resp = self._send_one(addr, PingRequest())
                if isinstance(resp, PingResponse):
                    good.append(addr)
            except (OSError, ConnectionError, PermissionError):
                continue
        return good


def _connect_any(addresses, timeout, retry_for, peer=None):
    """Connect sweep over the address list with exponential backoff +
    jitter under the ``retry_for`` deadline budget; returns a connected
    TCP_NODELAY socket (shared by the mux control connection, its bulk
    companion, and the ring stripe pool)."""
    deadline = time.monotonic() + retry_for
    attempt = 0
    last_error = None
    while True:
        for addr in addresses:
            try:
                sock = connect(addr, timeout, peer=peer)
                sock.settimeout(None)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                return sock
            except OSError as exc:
                last_error = exc
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise ConnectionError(
                f"could not reach service at any of {addresses}: "
                f"{last_error}")
        time.sleep(min(backoff_delay(attempt), max(remaining, 0.0)))
        attempt += 1


# ------------------------------------------------- persistent mux transport
class MuxService(BasicService):
    """Persistent-connection variant: each connection carries a stream of
    ``(req_id, request)`` frames; every request is handled on its own
    thread and the ``(req_id, response)`` frame is written back whenever
    it completes — so slow (blocking) requests don't head-of-line-block
    the connection.  Fire-and-forget posts (``req_id`` None) are handled
    inline on the reader loop instead: their handlers are quick and a
    thread spawn per bulk segment would dominate the striped data path.
    The reference keeps persistent Gloo pairs the same way; round 1's
    one-connection-per-request client was the analog of re-running
    rendezvous per collective.

    When a connection's FIRST frame is a :class:`SessionHello` the
    connection becomes a session (docs/fault_tolerance.md "connection
    blips vs dead peers"): frames carry seq numbers inside their
    request ids, the service dedups and acks cumulatively, and a later
    connection offering the same session id resumes exactly where the
    broken one stopped."""

    def __init__(self, name, key):
        self._inflight = 0   # guarded by self._inflight_cv
        self._inflight_cv = threading.Condition()
        # session_id -> _SessionState; sessions survive their sockets —
        # that's the whole point
        self._sessions = {}
        self._sessions_lock = threading.Lock()
        self.sessions_resumed = 0     # guarded by self._sessions_lock
        self.session_dup_drops = 0    # guarded by self._sessions_lock
        super().__init__(name, key)

    def session_epoch(self):
        """Controller epoch a hello must match to be admitted; services
        without reconfiguration epochs (the coordinator control plane)
        stay at 0.  PeerService overrides with its live epoch."""
        return 0

    def _make_handler(self):
        service = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                write_lock = threading.Lock()
                sock = self.request
                first = True
                while True:
                    try:
                        # wakeable: shutdown() and a session resume both
                        # close this socket, breaking the blocked read
                        frame = read_message(sock, service._key, "q")
                    except (PermissionError, ConnectionError, EOFError,
                            OSError):
                        return
                    if not (isinstance(frame, tuple) and len(frame) == 2):
                        return
                    req_id, req = frame
                    if first:
                        first = False
                        if isinstance(req, SessionHello):
                            service._session_serve(sock, write_lock, req,
                                                   self.client_address)
                            return
                    with service._inflight_cv:
                        service._inflight += 1
                    if req_id is None:
                        # fire-and-forget: no response is ever written
                        # and the handlers behind these posts (mailbox
                        # insert, abort flag) are quick — dispatch
                        # inline rather than paying a thread spawn per
                        # bulk segment on the striped data path
                        try:
                            service._handle(req, self.client_address)
                        except Exception:  # noqa: BLE001 — nowhere to
                            pass           # report without a req_id
                        finally:
                            with service._inflight_cv:
                                service._inflight -= 1
                                service._inflight_cv.notify_all()
                        continue

                    def run(req_id=req_id, req=req):
                        try:
                            try:
                                resp = service._handle(
                                    req, self.client_address)
                            except Exception as exc:  # noqa: BLE001
                                resp = exc
                            service._write_response(sock, write_lock,
                                                    req_id, resp)
                        finally:
                            with service._inflight_cv:
                                service._inflight -= 1
                                service._inflight_cv.notify_all()

                    # lifecycle: ends with its single _handle call;
                    # shutdown() drains in-flight handlers through the
                    # _inflight_cv barrier before the socket closes
                    threading.Thread(target=run, daemon=True,
                                     name=f"{service._name}-req").start()

        return Handler

    # ------------------------------------------------------ session side
    def _session_serve(self, sock, write_lock, hello, client_address):
        """Admit (or resume) a session offered by a fresh connection:
        fence stale epochs, install this socket as the session's live
        one, tell the client how far delivery got (it retransmits the
        rest), redeliver retained responses the dying socket may have
        swallowed, then serve frames until the connection breaks."""
        # the hello is HMAC-verified, but its FIELDS are still wire
        # input: the id becomes a dict key (unhashable -> handler
        # crash; unbounded -> memory held per session), so reject
        # anything but a short string before touching the table
        if not (isinstance(hello.session_id, str)
                and 0 < len(hello.session_id) <= _MAX_SESSION_ID_LEN) \
                or hello.epoch != self.session_epoch():
            try:
                with write_lock:
                    write_message(sock, self._key,
                                  (None, SessionWelcome(0, refused=True)),
                                  "r")
            except OSError:
                pass
            return
        with self._sessions_lock:
            state = self._sessions.get(hello.session_id)
            resumed = state is not None
            if not resumed:
                if len(self._sessions) >= _MAX_SESSIONS:
                    self._evict_dead_session_locked()
                if len(self._sessions) >= _MAX_SESSIONS:
                    # table full of LIVE sessions: refuse rather than
                    # grow without bound (a keyed-but-misbehaving peer
                    # minting a fresh id per connect lands here)
                    state = None
                else:
                    state = _SessionState(hello.session_id, hello.epoch)
                    self._sessions[hello.session_id] = state
            else:
                self.sessions_resumed += 1
        if state is None:
            try:
                with write_lock:
                    write_message(sock, self._key,
                                  (None, SessionWelcome(0, refused=True)),
                                  "r")
            except OSError:
                pass
            return
        with state.lock:
            old_sock = state.sock
            state.sock = sock
            state.write_lock = write_lock
            seen = state.seen
            stash = list(state.responses.values()) if resumed else []
        if old_sock is not None and old_sock is not sock:
            # break the dead connection's blocked reader, if it hasn't
            # noticed yet
            try:
                old_sock.close()
            except OSError:
                pass
        try:
            with write_lock:
                write_message(sock, self._key,
                              (None, SessionWelcome(seen)), "r")
            for wire in stash:
                with write_lock:
                    write_message(sock, self._key, wire, "r")
        except OSError:
            return  # this socket died too; the client will be back
        self._session_loop(sock, write_lock, state, client_address)

    def _evict_dead_session_locked(self):  # holds: self._sessions_lock
        """Drop one session with no live socket (insertion order, so
        oldest first).  Returns True when something was evicted."""
        for sid, st in list(self._sessions.items()):
            with st.lock:
                # the server closes each handler's socket when its
                # handle() returns, so a session whose connection died
                # (and hasn't resumed) holds a closed socket
                dead = st.sock is None or st.sock.fileno() == -1
            if dead:
                del self._sessions[sid]
                return True
        return False

    def _session_loop(self, sock, write_lock, state, client_address):
        """Frame pump for one live session connection: deliver exactly
        the next-in-sequence frames, drop duplicates a replay sent
        again, ack cumulatively every few deliveries."""
        while True:
            try:
                # wakeable: the next resume for this session (and
                # shutdown) closes this socket, breaking the read
                frame = read_message(sock, self._key, "q")
            except (PermissionError, ConnectionError, EOFError, OSError):
                return
            if not (isinstance(frame, tuple) and len(frame) == 2):
                return
            rid, req = frame
            if not (isinstance(rid, tuple) and len(rid) in (2, 3)
                    and rid[0] == "sq" and _valid_seq(rid[1])):
                return  # not session-framed: protocol violation, sever
            seq = rid[1]
            need_ack = False
            with state.lock:
                if seq <= state.seen:
                    state.dup_drops += 1
                    verdict = "dup"
                elif seq == state.seen + 1:
                    state.seen = seq
                    state.delivered_since_ack += 1
                    if state.delivered_since_ack >= _SESSION_ACK_EVERY:
                        state.delivered_since_ack = 0
                        need_ack = True
                    verdict = "deliver"
                else:
                    # a gap means the sender replayed past a frame we
                    # never got — resuming would corrupt; sever and let
                    # the sender's next heal (or escalation) decide
                    verdict = "gap"
                seen = state.seen
            if verdict == "gap":
                try:
                    sock.close()
                except OSError:
                    pass
                return
            if verdict == "dup":
                with self._sessions_lock:
                    self.session_dup_drops += 1
                continue
            with self._inflight_cv:
                self._inflight += 1
            if len(rid) == 2:
                # fire-and-forget (the bulk/mailbox path): inline, like
                # the legacy req_id-None dispatch
                try:
                    self._handle(req, client_address)
                except Exception:  # noqa: BLE001 — nowhere to report
                    pass
                finally:
                    with self._inflight_cv:
                        self._inflight -= 1
                        self._inflight_cv.notify_all()
            else:
                base_id = rid[2]

                def run(base_id=base_id, req=req):
                    try:
                        try:
                            resp = self._handle(req, client_address)
                        except Exception as exc:  # noqa: BLE001
                            resp = exc
                        self._write_session_response(state, base_id, resp)
                    finally:
                        with self._inflight_cv:
                            self._inflight -= 1
                            self._inflight_cv.notify_all()

                # lifecycle: ends with its single _handle call;
                # shutdown() drains in-flight handlers through the
                # _inflight_cv barrier before the socket closes
                threading.Thread(target=run, daemon=True,
                                 name=f"{self._name}-req").start()
            if need_ack:
                try:
                    with write_lock:
                        write_message(sock, self._key,
                                      (None, SessionAck(seen)), "r")
                except OSError:
                    pass  # connection dying; the reader will notice

    def _write_session_response(self, state, req_id, resp):
        """Route a response to the session's CURRENT socket (the one the
        request arrived on may be long dead by completion time) and
        retain it for redelivery at the next resume — a write into a
        dying socket's kernel buffer can vanish without erroring."""
        wire = (req_id, resp)
        with state.lock:
            state.responses[req_id] = wire
            while len(state.responses) > _SESSION_RESP_KEEP:
                state.responses.pop(next(iter(state.responses)))
            sock, wlock = state.sock, state.write_lock
        if sock is None:
            return
        try:
            with wlock:
                write_message(sock, self._key, wire, "r")
        except OSError:
            pass  # retained; the resume flush redelivers
        except Exception as exc:  # noqa: BLE001 — e.g. unpicklable resp
            wire = (req_id,
                    RuntimeError(f"response serialization failed: {exc}"))
            with state.lock:
                state.responses[req_id] = wire
            try:
                with wlock:
                    write_message(sock, self._key, wire, "r")
            except Exception:  # noqa: BLE001
                try:
                    sock.close()
                except OSError:
                    pass

    def _write_response(self, sock, write_lock, req_id, resp):
        try:
            with write_lock:
                write_message(sock, self._key, (req_id, resp), "r")
        except OSError:
            pass  # client went away
        except Exception as exc:  # noqa: BLE001 — e.g. unpicklable resp
            # a silently-dropped frame would hang the client's send()
            # forever; ship an error, or kill the connection so the
            # client fails fast
            try:
                with write_lock:
                    write_message(
                        sock, self._key,
                        (req_id,
                         RuntimeError(
                             f"response serialization failed: {exc}")),
                        "r")
            except Exception:  # noqa: BLE001
                try:
                    sock.close()
                except OSError:
                    pass

    def shutdown(self):
        """Drain in-flight requests before closing: a coordinator whose
        own rank finishes first must not tear down the socket while
        response frames to other ranks are still being written."""
        import time as _time

        deadline = _time.monotonic() + 10
        with self._inflight_cv:
            while self._inflight > 0:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                self._inflight_cv.wait(timeout=remaining)
        super().shutdown()


class MuxClient:
    """Client for :class:`MuxService`: ONE persistent socket, concurrent
    in-flight requests demultiplexed by id.  Thread-safe."""

    def __init__(self, addresses, key, timeout=10, retry_for=None,
                 peer=None, epoch=0, reconnect_budget=None,
                 replay_bytes=None):
        if isinstance(addresses, dict):
            flat = [a for addrs in addresses.values() for a in addrs]
        else:
            flat = list(addresses)
        if not flat:
            raise ValueError("no addresses to connect to")
        self._addresses = flat
        self._key = key
        self._timeout = timeout
        # remote's rank when known (coordinator: 0, ring mailboxes:
        # the peer rank) — link-level fault targeting needs the
        # identity, the transport itself never does
        self._peer = peer
        self._retry_for = (default_connect_retry() if retry_for is None
                           else retry_for)
        # self-healing session (docs/fault_tolerance.md "connection
        # blips vs dead peers"): active iff the reconnect budget is
        # positive; at 0 (the default) this client is frame-for-frame
        # identical to the pre-session transport
        budget = (default_reconnect_budget() if reconnect_budget is None
                  else reconnect_budget)
        self._budget = max(0.0, float(budget))
        self._epoch = epoch
        self._replay_bytes = (default_replay_bytes() if replay_bytes
                              is None else replay_bytes)
        # replay buffer + seq assignment; guarded by self._send_lock
        self._session = (_SessionSender(epoch, self._replay_bytes)
                         if self._budget > 0 else None)
        self._sock = None     # guarded by self._state_lock
        self._send_lock = threading.Lock()
        self._state_lock = threading.Lock()
        # req_id -> [event, response]; guarded by self._state_lock
        self._pending = {}
        # random start: a (req_id, resp) frame recorded from an earlier
        # connection/run cannot collide with a live request id
        self._next_id = _secrets.randbits(48)  # guarded by self._state_lock
        self._reader = None   # guarded by self._state_lock
        self._broken = None   # guarded by self._state_lock
        self._closed = False  # guarded by self._state_lock
        # bulk companion: a StripeClient to the same service that
        # carries ONLY fire-and-forget raw frames, under its own lock —
        # a pending control request (heartbeat, negotiation, abort)
        # never waits behind an in-progress multi-MB bulk write
        self._bulk = None     # guarded by self._bulk_lock
        self._bytes_sent = 0  # control bytes; guarded by self._send_lock
        self._bulk_lock = threading.Lock()

    def _connect_locked(self, retry_for=None):  # holds: self._state_lock
        """Establish the socket + reader (caller holds _state_lock).
        Sweeps the address list with exponential backoff + jitter under
        the ``retry_for`` deadline budget: a refused/reset connection
        during rendezvous or negotiation is retried, not fatal.  With a
        session active, the handshake + replay of unacked frames happen
        here, BEFORE the reader thread exists — so the welcome read
        races nothing and the retransmits precede any new frame."""
        sock = _connect_any(self._addresses, self._timeout,
                            self._retry_for if retry_for is None
                            else retry_for, peer=self._peer)
        # the _session REFERENCE is set once at construction and never
        # reassigned — only its contents need _send_lock; the handshake
        # reads the immutable id/epoch fields
        if self._session is not None:  # hvd-lint: ignore[lock-discipline]
            try:
                welcome = _session_handshake_client(
                    sock, self._key, self._session, self._timeout)  # hvd-lint: ignore[lock-discipline]
            except Exception:
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            if welcome.refused:
                try:
                    sock.close()
                except OSError:
                    pass
                raise _SessionResumeRefused(
                    f"service fenced session resume toward peer "
                    f"{self._peer} (stale epoch {self._epoch})")
            with self._send_lock:
                frames = self._session.replayable_from(welcome.rx_seen)
                if frames is None:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    raise _SessionResumeRefused(
                        f"replay buffer no longer holds frames the "
                        f"service needs (peer {self._peer}; raise "
                        f"{env_util.HVD_TPU_REPLAY_BUFFER_BYTES})")
                try:
                    for wire in frames:
                        _apply_link_faults(self._peer, sock=sock)
                        self._bytes_sent += write_message(
                            sock, self._key, wire, "q")
                except OSError:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    raise
                if frames:
                    _session_note("frames_replayed", len(frames))
        self._sock = sock
        self._broken = None
        # lifecycle: exits when its socket dies — close() closes the
        # socket, which breaks the blocked read_message and returns
        self._reader = threading.Thread(
            target=self._read_loop, args=(sock,), daemon=True,
            name="mux-client-reader")
        self._reader.start()

    def _try_heal(self, dead_sock, exc):
        """Transparent in-place session heal after a mid-stream break.
        Returns True when the session is live again (this call healed
        it, or another thread already did) — the caller's frame is in
        the replay buffer, so it was (or will be) retransmitted; the
        caller may also just rewrite it, the service dedups by seq.
        Returns False when healing is off, fenced, or out of budget —
        the caller escalates the ORIGINAL error, exactly the
        pre-session abort path."""
        # reference set once at construction, never reassigned
        if self._session is None or self._budget <= 0:  # hvd-lint: ignore[lock-discipline]
            return False
        deadline = time.monotonic() + self._budget
        with self._state_lock:
            if self._closed:
                return False
            if self._sock is not None and self._sock is not dead_sock:
                return True  # someone else already healed
            if self._sock is None and self._broken is not None:
                return False  # an earlier heal already gave up
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None
            from horovod_tpu.common import busy

            _healing_enter(self._peer)
            try:
                # busy window: the coordinator widens this rank's
                # liveness deadline while the heal is in flight — a
                # recovering link must never read as a dead rank
                with busy.window():
                    while True:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            self._broken = exc
                            _session_note("reconnects_failed")
                            return False
                        try:
                            self._connect_locked(retry_for=remaining)
                        except _SessionResumeRefused:
                            self._broken = exc
                            _session_note("reconnects_failed")
                            return False
                        except (OSError, ConnectionError,
                                PermissionError):
                            self._sock = None
                            continue
                        _session_note("reconnects_healed")
                        with self._send_lock:  # acks land under it
                            acked = self._session.acked
                        print(f"[hvd-session] reconnect healed toward "
                              f"peer {self._peer} (control session, "
                              f"acked {acked})",
                              file=sys.stderr, flush=True)
                        return True
            finally:
                _healing_exit(self._peer)

    def _ensure_connected_locked(self):  # holds: self._state_lock
        """Returns the live socket (caller holds _state_lock).  The
        returned reference — not a re-read of self._sock — must be used
        for the write, so a concurrent reconnect can never route this
        request onto a connection its pending entry isn't tied to."""
        if self._sock is None or self._broken is not None:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None
            self._connect_locked()
        return self._sock

    def _read_loop(self, sock):
        while True:
            try:
                # wakeable: close() severs this socket, which breaks
                # the blocked read; a heal hands the loop a new socket
                frame = read_message(sock, self._key, "r")
                if not (isinstance(frame, tuple) and len(frame) == 2):
                    raise ConnectionError(
                        f"malformed mux frame {type(frame).__name__}")
                req_id, resp = frame
            except Exception as exc:  # noqa: BLE001 — reader must never
                # die silently: heal the session in place if one is
                # active (pending waiters survive — their responses are
                # redelivered after the resume); otherwise fail every
                # waiter and mark broken, the pre-session behavior
                if isinstance(exc, (OSError, ConnectionError)) \
                        and self._try_heal(sock, exc):
                    return  # a new reader owns the healed socket
                with self._state_lock:
                    if self._broken is None:
                        self._broken = exc
                    pending, self._pending = self._pending, {}
                for event, slot in pending.values():
                    slot[0] = ConnectionError(
                        f"connection to service lost: {exc}")
                    event.set()
                return
            if req_id is None:
                # piggybacked session ack: prune the replay buffer
                # (the seen field is wire input even inside a verified
                # frame — a non-int would TypeError the ack arithmetic
                # and kill this reader)
                if isinstance(resp, SessionAck) and _valid_seq(resp.seen) \
                        and self._session is not None:  # hvd-lint: ignore[lock-discipline] — set-once reference
                    with self._send_lock:
                        self._session.ack(resp.seen)
                continue
            with self._state_lock:
                entry = self._pending.pop(req_id, None)
            if entry is not None:
                entry[1][0] = resp
                entry[0].set()

    def send(self, req, timeout=None):
        with self._state_lock:
            base_id = self._next_id
            self._next_id += 1
            event, slot = threading.Event(), [None]
            self._pending[base_id] = (event, slot)
        wire = None
        sock = None
        while True:
            try:
                with self._state_lock:
                    sock = self._ensure_connected_locked()
                with self._send_lock:
                    if wire is None:
                        if self._session is not None:
                            # seq inside the request id; the response
                            # still answers to base_id, and the replay
                            # buffer retains the frame until acked
                            _, wire = self._session.append(
                                lambda s: (("sq", s, base_id), req),
                                _CTRL_FRAME_EST)
                        else:
                            wire = (base_id, req)
                    _apply_link_faults(self._peer, sock=sock)
                    self._bytes_sent += write_message(
                        sock, self._key, wire, "q")
                break
            except OSError as exc:
                if self._try_heal(sock, exc):
                    # healed: rewrite this frame on the new socket (the
                    # replay may have carried it already — the service
                    # dedups by seq, so the rewrite is harmless)
                    continue
                with self._state_lock:
                    self._pending.pop(base_id, None)
                raise
            except Exception:  # PicklingError, oversize ValueError…
                with self._state_lock:
                    self._pending.pop(base_id, None)
                raise
        if not event.wait(timeout):
            with self._state_lock:
                self._pending.pop(base_id, None)
            raise TimeoutError("no response from service")
        resp = slot[0]
        if isinstance(resp, Exception):
            raise resp
        return resp

    def post(self, req):
        """Fire-and-forget: write the frame without expecting a response
        (req_id None).  TCP ordering + HMAC still apply; used by the ring
        data plane so chunk streams aren't serialized on ack round-trips."""
        wire = None
        sock = None
        while True:
            try:
                with self._state_lock:
                    sock = self._ensure_connected_locked()
                with self._send_lock:
                    if wire is None:
                        if self._session is not None:
                            _, wire = self._session.append(
                                lambda s: (("sq", s), req),
                                _CTRL_FRAME_EST)
                        else:
                            wire = (None, req)
                    _apply_link_faults(self._peer, sock=sock)
                    self._bytes_sent += write_message(sock, self._key,
                                                      wire, "q")
                return
            except OSError as exc:
                if self._try_heal(sock, exc):
                    continue  # rewrite; the service dedups by seq
                raise

    @property
    def bytes_sent(self):
        """Wire bytes written (control + bulk companion, framing
        included) — the own counter and the bulk reference are read
        under their guarding locks; the companion's monotonic counter
        is read staleness-tolerantly (it may lag an in-flight
        post_bulk by one frame, which the quiesced-transfer
        byte-accounting tests never observe)."""
        with self._send_lock:
            total = self._bytes_sent
        with self._bulk_lock:
            bulk = self._bulk
        return total + (bulk.bytes_sent if bulk else 0)

    def post_bulk(self, obj, payload):
        """Fire-and-forget raw bulk frame on the dedicated bulk
        companion connection (a lazily-built :class:`StripeClient` to
        the same service): ``obj`` is the small header carrier (its
        ``payload`` attribute must be None), ``payload`` the raw bytes.
        Control ``send``s keep round-tripping on the main socket while
        this write is in flight."""
        with self._bulk_lock:
            if self._bulk is None:
                self._bulk = StripeClient(
                    self._addresses, self._key, timeout=self._timeout,
                    retry_for=self._retry_for, peer=self._peer,
                    epoch=self._epoch, reconnect_budget=self._budget,
                    replay_bytes=self._replay_bytes)
            bulk = self._bulk
        bulk.post_bulk(obj, payload)

    def close(self):
        with self._state_lock:
            self._closed = True
            sock, self._sock = self._sock, None
        with self._bulk_lock:
            bulk = self._bulk
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        if bulk is not None:
            bulk.close()


class StripeClient:
    """One dedicated bulk-data connection to a :class:`MuxService`:
    fire-and-forget raw frames only (req_id None, so the service never
    writes back — no reader thread).  The ring data plane keeps a pool
    of these per peer (``HVD_TPU_RING_STRIPES``), separate from the
    control :class:`MuxClient`, so heartbeats and negotiation never
    queue behind multi-MB chunk writes and high-BDP links get
    multi-stream throughput.  Thread-safe."""

    def __init__(self, addresses, key, timeout=10, retry_for=None,
                 peer=None, epoch=0, reconnect_budget=None,
                 replay_bytes=None):
        if isinstance(addresses, dict):
            flat = [a for addrs in addresses.values() for a in addrs]
        else:
            flat = list(addresses)
        if not flat:
            raise ValueError("no addresses to connect to")
        self._addresses = flat
        self._key = key
        self._timeout = timeout
        self._peer = peer    # remote's rank when known (fault targeting)
        self._retry_for = (default_connect_retry() if retry_for is None
                           else retry_for)
        budget = (default_reconnect_budget() if reconnect_budget is None
                  else reconnect_budget)
        self._budget = max(0.0, float(budget))
        self._epoch = epoch
        replay = (default_replay_bytes() if replay_bytes is None
                  else replay_bytes)
        # session seq/replay state; guarded by self._lock (the payload
        # references are retained zero-copy — the data plane never
        # mutates a posted chunk)
        self._session = (_SessionSender(epoch, replay)
                         if self._budget > 0 else None)
        self._lock = threading.Lock()
        self._sock = None    # guarded by self._lock
        # cumulative frame bytes written by post_bulk; external
        # monotonic reads tolerate staleness; guarded by self._lock
        self.bytes_sent = 0

    def _open_locked(self, retry_for):  # holds: self._lock
        """Connect and, with a session active, handshake + start the
        ack reader before any bulk frame goes out."""
        sock = _connect_any(self._addresses, self._timeout, retry_for,
                            peer=self._peer)
        replayed = 0
        if self._session is not None:
            try:
                welcome = _session_handshake_client(
                    sock, self._key, self._session, self._timeout)
            except Exception:
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            if welcome.refused:
                try:
                    sock.close()
                except OSError:
                    pass
                raise _SessionResumeRefused(
                    f"service fenced stripe session resume toward peer "
                    f"{self._peer} (stale epoch {self._epoch})")
            frames = self._session.replayable_from(welcome.rx_seen)
            if frames is None:
                try:
                    sock.close()
                except OSError:
                    pass
                raise _SessionResumeRefused(
                    f"stripe replay buffer no longer holds frames the "
                    f"service needs (peer {self._peer}; raise "
                    f"{env_util.HVD_TPU_REPLAY_BUFFER_BYTES})")
            try:
                for hdr, payload in frames:
                    _apply_link_faults(self._peer,
                                       memoryview(payload).nbytes,
                                       sock=sock)
                    self.bytes_sent += write_bulk_message(
                        sock, self._key, hdr, payload, "q")
            except OSError:
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            if frames:
                _session_note("frames_replayed", len(frames))
                replayed = len(frames)
            # lifecycle: exits when its socket dies (read raises); a
            # heal replaces the socket, so each reader is per-socket
            # and the dead one unwinds on its own
            threading.Thread(target=self._ack_loop, args=(sock,),
                             daemon=True,
                             name="stripe-ack-reader").start()
        self._sock = sock
        return replayed

    def _ack_loop(self, sock):
        """Per-socket daemon draining piggybacked session acks; exits
        quietly when its socket dies (the writer path owns healing)."""
        while True:
            try:
                # wakeable: per-socket daemon; the writer path closes
                # this socket on heal/teardown, breaking the read
                frame = read_message(sock, self._key, "r")
            except Exception:  # noqa: BLE001 — socket gone
                return
            if (isinstance(frame, tuple) and len(frame) == 2
                    and isinstance(frame[1], SessionAck)
                    and _valid_seq(frame[1].seen)):
                with self._lock:
                    if self._session is not None:
                        self._session.ack(frame[1].seen)

    def _heal_locked(self, exc):  # holds: self._lock
        """Reconnect + resume the stripe session inside the budget
        window; every retained unacked frame (including the one whose
        write just failed) is retransmitted by :meth:`_open_locked`.
        Escalates the ORIGINAL error on fence, replay gap, or budget
        exhaustion — exactly the pre-session abort surface."""
        deadline = time.monotonic() + self._budget
        from horovod_tpu.common import busy

        _healing_enter(self._peer)
        try:
            with busy.window():
                while True:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        _session_note("reconnects_failed")
                        raise exc
                    try:
                        replayed = self._open_locked(remaining)
                    except _SessionResumeRefused:
                        _session_note("reconnects_failed")
                        raise exc
                    except (OSError, ConnectionError, PermissionError):
                        self._sock = None
                        continue
                    _session_note("reconnects_healed")
                    print(f"[hvd-session] reconnect healed toward peer "
                          f"{self._peer} (replayed {replayed} bulk "
                          f"frames)", file=sys.stderr, flush=True)
                    return
        finally:
            _healing_exit(self._peer)

    def post_bulk(self, obj, payload):
        """Write one raw bulk frame (``obj`` the small header carrier
        with a None ``payload`` attribute, ``payload`` the raw bytes).
        With a session active the frame is retained in the replay
        buffer BEFORE the write, so a mid-stream break heals in place —
        reconnect, resume, retransmit the unacked tail — and this call
        still returns success."""
        nbytes = memoryview(payload).nbytes
        with self._lock:
            rec = None
            if self._session is not None:
                _, rec = self._session.append(
                    lambda s: ((("sq", s), obj), payload), nbytes)
            try:
                if self._sock is None:
                    self._open_locked(self._retry_for)
                    if self._session is not None:
                        return  # _open_locked replayed it already
                _apply_link_faults(self._peer, nbytes, sock=self._sock)
                if rec is None:
                    self.bytes_sent += write_bulk_message(
                        self._sock, self._key, (None, obj), payload, "q")
                else:
                    self.bytes_sent += write_bulk_message(
                        self._sock, self._key, rec[0], payload, "q")
            except OSError as exc:
                if self._sock is not None:
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    self._sock = None
                if self._session is None:
                    raise
                self._heal_locked(exc)

    def close(self):
        with self._lock:
            sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass


# ----------------------------------------------------------- NIC enumeration
def local_interfaces():
    """{interface_name: ipv4} for every UP non-loopback interface.

    Stdlib-only Linux implementation (ioctl SIOCGIFADDR per interface from
    ``socket.if_nameindex``); falls back to a hostname lookup pinned to a
    pseudo-interface when the ioctl path is unavailable.
    """
    import fcntl

    out = {}
    try:
        ifaces = socket.if_nameindex()
    except OSError:
        ifaces = []
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for _, name in ifaces:
            if name == "lo":
                continue
            try:
                packed = fcntl.ioctl(
                    s.fileno(), 0x8915,  # SIOCGIFADDR
                    struct.pack("256s", name.encode()[:15]))
                out[name] = socket.inet_ntoa(packed[20:24])
            except OSError:
                continue  # interface without an IPv4 address
    finally:
        s.close()
    if not out:
        try:
            out["_default"] = socket.gethostbyname(socket.gethostname())
        except OSError:
            out["_default"] = "127.0.0.1"
    return out
