"""Transport substrate: framed sockets, pipe workers, and an event-loop hub.

Round-2 redesign of the communication layer. The wire format keeps the
reference-compatible 4-byte big-endian length framing (reference
connection.py:45-69 uses the same header), but everything else is built
differently:

* **Data-only codec.** Socket payloads are msgpack with an ndarray
  extension type instead of pickle. A crafted frame from a network peer can
  only ever decode to plain data — never to a code object — which closes the
  remote-code-execution hole pickle leaves open on the public worker/eval
  ports (9999/9998/9876). Same-host ``mp.Pipe`` endpoints keep mp's native
  transport (kernel-mediated, same-user only).

* **One event loop, not thread pairs.** ``Hub`` multiplexes any number of
  heterogeneous endpoints (sockets and pipes) on a single ``selectors`` loop
  with a self-wake pipe, per-endpoint outboxes, and command-queue attach /
  detach — replacing the reference's two-threads-plus-0.3s-poll
  QueueCommunicator design. Dead peers are detached on read/write errors;
  peers are elastic by design.

* **Demand-driven job dispatch.** ``JobPool`` primes each spawned worker
  with one job and hands out the next the moment a result returns — a single
  dispatcher thread with backpressure from the bounded result queue, instead
  of separate sender/receiver threads with a free-connection queue.
"""

from __future__ import annotations

import os
import queue
import select
import selectors
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import msgpack
import numpy as np

from . import telemetry

_HEADER = struct.Struct('!i')
_EXT_NDARRAY = 1

# transport-level flow counters (no-ops when telemetry is disabled): every
# framed socket send/recv in the process adds here, so a gather's heartbeat
# snapshot carries its true wire traffic and the learner sees fleet totals
_NET_TX = telemetry.counter('net_bytes_sent_total')
_NET_RX = telemetry.counter('net_bytes_recv_total')
_NET_FRAMES_TX = telemetry.counter('net_frames_sent_total')
_LOG = telemetry.get_logger('connection')


# ---------------------------------------------------------------------------
# codec


def _encode_ext(obj):
    if isinstance(obj, np.ndarray):
        header = msgpack.packb([obj.dtype.str, list(obj.shape)],
                               use_bin_type=True)
        return msgpack.ExtType(
            _EXT_NDARRAY, header + np.ascontiguousarray(obj).tobytes())
    if isinstance(obj, np.generic):      # numpy scalar -> python scalar
        return obj.item()
    raise TypeError('refusing to serialize %r (data-only codec)' % type(obj))


def _decode_ext(code, data):
    if code == _EXT_NDARRAY:
        unpacker = msgpack.Unpacker(use_list=True, raw=False)
        unpacker.feed(data)
        dtype_str, shape = unpacker.unpack()
        arr = np.frombuffer(data[unpacker.tell():], dtype=np.dtype(dtype_str))
        return arr.reshape(shape).copy()
    return msgpack.ExtType(code, data)


def pack(msg) -> bytes:
    """Serialize a message for the wire (msgpack + an ndarray extension).

    Tuples normalize to lists across a socket hop — every protocol message
    is a ``(kind, payload)`` pair and all receive sites sequence-unpack, so
    the normalization is observable but harmless by design.
    """
    return msgpack.packb(msg, default=_encode_ext, use_bin_type=True)


def unpack(payload: bytes):
    """Inverse of :func:`pack`. Decodes only data — never code objects."""
    return msgpack.unpackb(payload, ext_hook=_decode_ext, raw=False,
                           strict_map_key=False, use_list=True)


# ---------------------------------------------------------------------------
# endpoints


MAX_FRAME_BYTES = 256 * (1 << 20)   # largest legal payload (256 MiB)


class FrameParser:
    """Incremental splitter of a byte stream into length-framed payloads.

    Frame lengths are attacker-controlled on the public ports, so they are
    validated before any buffering commitment: a negative or oversized
    header is a protocol violation and poisons the connection (the caller's
    error handling detaches the peer) instead of letting a crafted header
    pin gigabytes per connection or desync the stream."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, data: bytes) -> List[bytes]:
        self._buf += data
        frames = []
        while True:
            if len(self._buf) < _HEADER.size:
                break
            (n,) = _HEADER.unpack_from(self._buf)
            if n < 0 or n > MAX_FRAME_BYTES:
                raise ConnectionResetError(
                    'protocol violation: frame length %d' % n)
            if len(self._buf) < _HEADER.size + n:
                break
            frames.append(bytes(self._buf[_HEADER.size:_HEADER.size + n]))
            del self._buf[:_HEADER.size + n]
        return frames


class FramedConnection:
    """Duplex message endpoint over a stream socket.

    Blocking ``send``/``recv`` serve call-response clients; ``drain`` serves
    the Hub's non-blocking read path via the incremental FrameParser.
    """

    def __init__(self, sock: socket.socket):
        self.sock: Optional[socket.socket] = sock
        self._parser = FrameParser()
        self._ready: deque = deque()
        # serialize concurrent senders (e.g. a gather's main RPC loop and
        # its heartbeat thread): interleaved sendall calls would splice two
        # frames together and desync the stream
        self._send_lock = threading.Lock()

    def fileno(self) -> int:
        return self.sock.fileno()

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            finally:
                self.sock = None

    __del__ = close

    def send(self, msg):
        payload = pack(msg)
        if len(payload) > MAX_FRAME_BYTES:
            raise ValueError('message of %d bytes exceeds the frame limit'
                             % len(payload))
        with self._send_lock:
            self.sock.sendall(_HEADER.pack(len(payload)) + payload)
        _NET_TX.inc(_HEADER.size + len(payload))
        _NET_FRAMES_TX.inc()

    @staticmethod
    def _decode(payload: bytes):
        """A frame that passed the length check can still carry garbage; any
        decode failure poisons the connection (callers detach/close) rather
        than leaking arbitrary exceptions into multiplexer threads."""
        try:
            return unpack(payload)
        except Exception as exc:
            raise ConnectionResetError('undecodable frame (%s: %s)'
                                       % (type(exc).__name__,
                                          str(exc)[:80])) from exc

    def recv(self):
        if self._ready:
            return self._decode(self._ready.popleft())
        while not self._ready:
            chunk = self.sock.recv(1 << 16)
            if not chunk:
                raise ConnectionResetError('peer closed')
            _NET_RX.inc(len(chunk))
            self._ready.extend(self._parser.feed(chunk))
        return self._decode(self._ready.popleft())

    def poll(self, timeout: float = 0.0) -> bool:
        """True when a recv() would find data (a complete frame already
        buffered, or socket bytes ready within ``timeout`` seconds) — the
        deadline primitive the timeout-bounded clients (EngineClient's
        remote-service path, ServiceClient) build on, matching the
        ``mp.Connection.poll`` surface PipeEndpoint exposes."""
        if self._ready:
            return True
        if self.sock is None:
            return False
        readable, _, _ = select.select([self.sock], [], [],
                                       max(0.0, float(timeout)))
        return bool(readable)

    def drain(self) -> List[Any]:
        """Non-blocking read of everything currently available."""
        try:
            chunk = self.sock.recv(1 << 16, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            return []
        if not chunk:
            raise ConnectionResetError('peer closed')
        _NET_RX.inc(len(chunk))
        self._ready.extend(self._parser.feed(chunk))
        out = [self._decode(p) for p in self._ready]
        self._ready.clear()
        return out


class PipeEndpoint:
    """Adapter giving an ``mp.Connection`` the same endpoint surface."""

    def __init__(self, conn):
        self.conn = conn

    def fileno(self) -> int:
        return self.conn.fileno()

    def close(self):
        self.conn.close()

    def send(self, msg):
        self.conn.send(msg)

    def recv(self):
        return self.conn.recv()

    def drain(self) -> List[Any]:
        out = []
        while self.conn.poll(0):
            out.append(self.conn.recv())
        return out


def send_recv(conn, msg):
    conn.send(msg)
    return conn.recv()


# ---------------------------------------------------------------------------
# sockets


def open_socket_connection(port: int) -> socket.socket:
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind(('', int(port)))
    return sock


def connect_socket_connection(host: str, port: int) -> FramedConnection:
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.connect((host, int(port)))
    return FramedConnection(sock)


def accept_socket_connections(port: int, timeout: Optional[float] = None,
                              maxsize: int = 1024
                              ) -> Iterator[Optional[FramedConnection]]:
    """Yield one FramedConnection per accepted client; None on idle timeout."""
    sock = open_socket_connection(port)
    sock.listen(maxsize)
    sock.settimeout(timeout)
    accepted = 0
    while accepted < maxsize:
        try:
            conn, _ = sock.accept()
        except socket.timeout:
            yield None
            continue
        accepted += 1
        yield FramedConnection(conn)


# ---------------------------------------------------------------------------
# event-loop hub


_WRITER_EXIT = object()   # per-endpoint writer shutdown sentinel

# Heartbeat frames are a one-way liveness beacon from blocking RPC clients
# (gathers) to the Hub: the Hub refreshes the sender's liveness deadline,
# records the payload (client-side fleet stats), and never replies — a
# reply would land in the middle of the client's call-response stream.
HEARTBEAT_KIND = '__hb__'

# Inference-service frames (inference.py): an engine-mode worker's
# ``(INFER_KIND, request)`` rides its existing pipe to the host relay,
# multiplexed by the relay's Hub event loop alongside the task RPCs; the
# engine's reply is posted back through the same per-endpoint outbox AS A
# ``(INFER_KIND, reply)`` frame. Tagging replies matters for self-healing:
# a worker that timed out on a request and failed over to local inference
# may receive the engine's late answer at ANY later point — including in
# the middle of an args/episode/model call-response — and must be able to
# recognize and absorb it instead of mistaking it for the RPC's reply
# (inference.EngineClient.rpc does exactly that, via ``is_infer``).
INFER_KIND = '__infer__'

# Resume-token handshake (docs/large_scale_training.md "Zero-loss training
# plane"): a reconnecting gather's FIRST frame after a redial is a
# ``(RESUME_KIND, {gather, run_id, generation})`` RPC. A restarted learner
# that recognizes the run_id replies ``{'ok': True, 'run_id', 'generation'}``
# and the gather reattaches in place — resend buffer replayed, nothing
# respawned. A learner that predates the handshake (or a different run)
# answers with something else, which the gather treats as "cold respawn"
# — today's behavior, so mixed-version fleets keep working.
RESUME_KIND = '__resume__'

# Serving-path trace context rides INSIDE the INFER/admin body dict under
# this key (docs/observability.md, "Serving-path tracing"): extra dict keys
# are ignored by peers that predate it, so absent context simply means
# "unsampled" — no wire-format break, old and new peers interoperate.
TRACE_KEY = 'trace'


def is_heartbeat(msg) -> bool:
    return (isinstance(msg, (list, tuple)) and len(msg) == 2
            and msg[0] == HEARTBEAT_KIND)


def is_infer(msg) -> bool:
    """True for an inference-service frame (request or reply)."""
    return (isinstance(msg, (list, tuple)) and len(msg) == 2
            and msg[0] == INFER_KIND)


def _describe(endpoint) -> str:
    """Human identity of an endpoint for disconnect logs."""
    sock = getattr(endpoint, 'sock', None)
    if sock is not None:
        try:
            peer = sock.getpeername()
        except OSError:
            return 'socket peer (already closed)'
        if isinstance(peer, tuple) and len(peer) >= 2:   # AF_INET[6]
            return 'socket peer %s:%s' % peer[:2]
        return 'socket peer %r' % (peer,)                # AF_UNIX et al.
    try:
        return 'pipe fd %d' % endpoint.fileno()
    except Exception:
        return 'endpoint'


class Hub:
    """Message multiplexer: one selector read loop + one writer per endpoint.

    Incoming messages land in one inbox as ``(endpoint, message)``; outgoing
    messages are posted to a PER-ENDPOINT outbox drained by that endpoint's
    own writer thread, so a peer that stops consuming delays only its own
    sends — never another peer's RPC round trip. A stalled peer is detached
    when its socket send exceeds ``SEND_TIMEOUT`` (deadline set on attach)
    or its outbox backs up past ``OUTBOX_MAX`` queued messages. Endpoints
    may be attached / detached from any thread at any time (workers are
    elastic); a failed read or write detaches the endpoint.

    Liveness: socket endpoints additionally carry a per-endpoint deadline —
    a peer that sends NOTHING (not even a ``HEARTBEAT_KIND`` beacon) for
    ``LIVENESS_TIMEOUT`` seconds is presumed silently dead (half-open TCP:
    the remote host vanished without a FIN) and detached, instead of
    holding its slot until some future write happens to fail. Any received
    frame refreshes the deadline; heartbeat frames are filtered out of the
    inbox and their payloads retained per endpoint (``peer_info_snapshot``).
    Pipe endpoints are exempt — a dead pipe peer is always observable as an
    immediate EOF. Every disconnect is counted by reason in ``stats`` and
    journaled for ``drain_detach_events`` (the learner's task ledger feeds
    on it).
    """

    SEND_TIMEOUT = 30.0
    OUTBOX_MAX = 512
    LIVENESS_TIMEOUT = 60.0   # silent-socket-peer deadline; 0 disables

    def __init__(self, endpoints: Optional[List] = None, inbox_max: int = 256):
        self._inbox: queue.Queue = queue.Queue(maxsize=inbox_max)
        # every mutable map below is shared by the read loop, the per-
        # endpoint writers and arbitrary caller threads; one lock guards
        # them all (lexical discipline checked by graftlint GL004)
        self._lock = threading.Lock()
        self._outboxes: Dict[Any, queue.Queue] = {}        # guarded-by: _lock
        self._commands: deque = deque()                    # guarded-by: _lock
        self._liveness: Dict[Any, float] = {}              # guarded-by: _lock
        self._last_recv: Dict[Any, float] = {}             # guarded-by: _lock
        self._peer_info: Dict[Any, Any] = {}               # guarded-by: _lock
        self._detach_events: deque = deque(maxlen=4096)    # guarded-by: _lock
        self.stats: Dict[str, int] = {}                    # guarded-by: _lock
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._selector = selectors.DefaultSelector()
        self._selector.register(self._wake_r, selectors.EVENT_READ, None)
        for ep in endpoints or []:
            self.attach(ep)
        threading.Thread(target=self._read_loop, name='hub-read',
                         daemon=True).start()

    # -- public api (any thread) --

    def count(self) -> int:
        with self._lock:
            return len(self._outboxes)

    # QueueCommunicator-compatible alias used by the learner's server loop
    connection_count = count

    def _bump(self, key: str, n: int = 1):
        """Increment a stats counter (caller holds no lock)."""
        with self._lock:
            self.stats[key] = self.stats.get(key, 0) + n

    def stats_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.stats)

    def peer_info_snapshot(self) -> Dict[Any, Any]:
        """Latest heartbeat payload per live endpoint."""
        with self._lock:
            return dict(self._peer_info)

    def drain_detach_events(self) -> List[Tuple[Any, str, float]]:
        """Consume the (endpoint, reason, time) disconnect journal."""
        with self._lock:
            events = list(self._detach_events)
            self._detach_events.clear()
        return events

    def recv(self, timeout: Optional[float] = None) -> Tuple[Any, Any]:
        return self._inbox.get(timeout=timeout)

    def send(self, endpoint, msg):
        with self._lock:
            outbox = self._outboxes.get(endpoint)
        if outbox is None:      # already detached: drop, like a dead socket
            return
        try:
            outbox.put_nowait(msg)
        except queue.Full:      # peer hopelessly behind — treat as stalled
            self.detach(endpoint, reason='outbox_overflow')

    def attach(self, endpoint, liveness: Optional[float] = None):
        """Register ``endpoint``. ``liveness`` overrides the silent-peer
        deadline in seconds (0 disables); default: ``LIVENESS_TIMEOUT`` for
        socket endpoints, disabled for pipes."""
        sock = getattr(endpoint, 'sock', None)
        if sock is not None:
            sock.settimeout(self.SEND_TIMEOUT)   # bound writer stalls
        if liveness is None:
            liveness = self.LIVENESS_TIMEOUT if sock is not None else 0.0
        outbox: queue.Queue = queue.Queue(maxsize=self.OUTBOX_MAX)
        with self._lock:
            if endpoint in self._outboxes:
                return
            self._outboxes[endpoint] = outbox
            self._liveness[endpoint] = float(liveness or 0.0)
            self._last_recv[endpoint] = time.monotonic()
            self._commands.append(('+', endpoint))
            self.stats['attached'] = self.stats.get('attached', 0) + 1
            telemetry.gauge('hub_peers').set(len(self._outboxes))
        threading.Thread(target=self._write_loop, args=(endpoint, outbox),
                         name='hub-write', daemon=True).start()
        self._wake()

    # API name kept for operator familiarity with the reference logs
    add_connection = attach

    def detach(self, endpoint, reason: str = 'requested'):
        with self._lock:
            outbox = self._outboxes.pop(endpoint, None)
            if outbox is not None:
                self._liveness.pop(endpoint, None)
                self._last_recv.pop(endpoint, None)
                self._peer_info.pop(endpoint, None)
                self._commands.append(('-', endpoint))
                self.stats['detached'] = self.stats.get('detached', 0) + 1
                key = 'disconnect_' + reason
                self.stats[key] = self.stats.get(key, 0) + 1
                self._detach_events.append((endpoint, reason, time.time()))
                telemetry.gauge('hub_peers').set(len(self._outboxes))
        if outbox is None:
            return                        # already gone: count/log only once
        telemetry.counter('hub_disconnects_total', reason=reason).inc()
        _LOG.info('disconnected %s (%s)', _describe(endpoint), reason)
        try:                              # fast writer wake; the writer also
            outbox.put_nowait(_WRITER_EXIT)   # polls attachment, so a
        except queue.Full:                # full outbox can't wedge detach
            pass
        self._wake()

    # -- loop internals --

    def _wake(self):
        try:
            self._wake_w.send(b'.')
        except OSError:
            pass

    def _apply_commands(self):
        while True:
            with self._lock:
                if not self._commands:
                    return
                op, ep = self._commands.popleft()
            try:
                if op == '+':
                    self._selector.register(ep, selectors.EVENT_READ, ep)
                else:
                    self._selector.unregister(ep)
                    ep.close()
            except (KeyError, ValueError, OSError):
                pass

    def _write_loop(self, ep, outbox: queue.Queue):
        """Drain ONE endpoint's outbox; exit when it is detached."""
        while True:
            try:
                msg = outbox.get(timeout=1.0)
            except queue.Empty:
                with self._lock:
                    if self._outboxes.get(ep) is not outbox:
                        return        # detached while idle
                continue
            if msg is _WRITER_EXIT:
                return
            try:
                ep.send(msg)
            except (OSError, ValueError, TimeoutError, AttributeError) as exc:
                # AttributeError: closed while queued
                reason = ('send_timeout'
                          if isinstance(exc, (socket.timeout, TimeoutError))
                          else 'send_error')
                self.detach(ep, reason=reason)
                return

    def _check_liveness(self):
        now = time.monotonic()
        with self._lock:
            stale = [ep for ep, limit in self._liveness.items()
                     if limit > 0 and now - self._last_recv.get(ep, now) > limit]
        for ep in stale:
            self.detach(ep, reason='heartbeat_miss')

    def _read_loop(self):
        while True:
            events = self._selector.select(timeout=0.5)
            for key, _mask in events:
                if key.data is None:        # wake pipe
                    try:
                        self._wake_r.recv(4096)
                    except OSError:
                        pass
                    continue
                ep = key.data
                try:
                    msgs = ep.drain()
                except (ConnectionResetError, EOFError, OSError):
                    self.detach(ep, reason='read_error')
                    continue
                if msgs:
                    with self._lock:
                        if ep in self._last_recv:
                            self._last_recv[ep] = time.monotonic()
                for msg in msgs:
                    if is_heartbeat(msg):
                        with self._lock:
                            self._peer_info[ep] = msg[1]
                            self.stats['heartbeats'] = (
                                self.stats.get('heartbeats', 0) + 1)
                        telemetry.counter('hub_heartbeats_total').inc()
                        continue
                    self._inbox.put((ep, msg))
            self._apply_commands()
            self._check_liveness()


# ---------------------------------------------------------------------------
# process fan-out


def force_cpu_backend():
    """Pin this (sub)process's JAX to the CPU backend.

    Worker/eval/batcher processes must never claim the accelerator: a chip
    belongs to one process at a time, the learner (or the device gather)
    holds it, and a second claimant fails or hangs. Called at the top of
    every child-process entry point, before anything creates a jax array.
    The env var covers processes spawned from here; the config update
    covers this one, whose ``import jax`` already read the inherited
    variable. A pin that did not take — some import initialized another
    backend first — raises instead of running on whatever jax picked.
    """
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    jax.config.update('jax_platforms', 'cpu')
    backend = jax.default_backend()
    if backend != 'cpu':
        raise RuntimeError(
            'force_cpu_backend: this process already initialized the %r '
            'backend; the CPU pin must run before any jax array is created'
            % backend)
    # spawned children start with a fresh interpreter: re-enable the shared
    # compile cache so their (CPU) compiles are one-time across the fleet
    from . import setup_compile_cache
    setup_compile_cache()


def spawn_pipe_workers(count: int, target: Callable,
                       make_args: Callable[[int, Any], tuple],
                       daemon: bool = False) -> List[PipeEndpoint]:
    """Spawn ``count`` processes, each holding one end of a duplex pipe.

    Uses the 'spawn' context: a forked child would inherit the parent's
    initialized JAX backend (possibly the exclusive TPU client); a spawned
    child starts clean and pins itself to CPU via force_cpu_backend().
    Returns the parent-side pipe endpoints.
    """
    import multiprocessing as mp
    ctx = mp.get_context('spawn')
    parents = []
    for i in range(count):
        ours, theirs = ctx.Pipe(duplex=True)
        ctx.Process(target=target, args=make_args(i, theirs),
                    daemon=daemon).start()
        theirs.close()
        parents.append(PipeEndpoint(ours))
    return parents


class JobPool:
    """Fan jobs out to spawned worker processes, demand-driven.

    ``job_source`` is an iterator of job payloads; ``worker_fn(conn, idx)``
    is the child entry point (recv job -> send result, forever). One
    dispatcher thread keeps every child busy: each result immediately buys
    its sender the next job, then lands (optionally transformed) in
    ``results`` — whose bound provides the backpressure.
    """

    def __init__(self, worker_fn: Callable, job_source, num_workers: int,
                 transform: Optional[Callable] = None, results_max: int = 8):
        self._jobs = job_source
        self._transform = transform
        self.results: queue.Queue = queue.Queue(maxsize=results_max)
        self._endpoints = spawn_pipe_workers(
            num_workers, worker_fn, lambda i, c: (c, i), daemon=True)
        # mp.Connection.send is not thread-safe: the dispatcher thread and
        # out-of-band senders (send_to, e.g. shared-memory slot releases
        # from the trainer thread) serialize per endpoint
        self._send_locks = [threading.Lock() for _ in self._endpoints]

    # Batcher compatibility: the learner reads .output_queue
    @property
    def output_queue(self) -> queue.Queue:
        return self.results

    def start(self):
        threading.Thread(target=self._dispatch, name='jobpool-dispatch',
                         daemon=True).start()

    def recv(self):
        return self.results.get()

    def send_to(self, idx: int, msg):
        """Out-of-band message to worker ``idx`` (any thread); best-effort —
        a dead worker's pipe error is swallowed like a dead socket's."""
        try:
            with self._send_locks[idx]:
                self._endpoints[idx].send(msg)
        except (OSError, ValueError, BrokenPipeError):
            pass

    def _dispatch(self):
        import multiprocessing.connection as mpc
        for i, ep in enumerate(self._endpoints):
            with self._send_locks[i]:
                ep.send(next(self._jobs))
        live = {ep.conn: (i, ep) for i, ep in enumerate(self._endpoints)}
        while live:
            for conn in mpc.wait(list(live)):
                i, ep = live[conn]
                try:
                    result = ep.recv()
                except (EOFError, OSError):
                    del live[conn]
                    continue
                with self._send_locks[i]:     # refill before the maybe-block
                    ep.send(next(self._jobs))
                if self._transform is not None:
                    result = self._transform(result)
                self.results.put(result)
