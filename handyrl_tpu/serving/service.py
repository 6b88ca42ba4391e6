"""The InferenceService: a long-lived model-serving process.

One process hosts one or more supervised :class:`~.inference.InferenceEngine`
fleets behind a TCP listener speaking the framed ``INFER_KIND`` protocol
(the exact frames engine-mode workers already emit), so eval servers,
league matches, worker fleets with ``serving.endpoint`` configured, and
external match traffic all hit one engine tier instead of each run growing
its own. Requests name models by ``line@selector`` against the
:class:`~.registry.ModelRegistry`; a promote flips what ``@champion``
resolves to between one tick and the next with zero failed requests.

Pieces:

* **Continuous batching** — requests from every connected client coalesce
  in the engine's intake queue (quiescence early-dispatch +
  ``inference.batch_wait_ms`` deadline + ``inference.max_batch`` cap,
  power-of-two row padding), one ``batch_inference`` per tick. Multiple
  engines (``serving.engines``) partition the model space so two lines
  never serialize behind each other's forwards.

* **Admission control, shed on overload** — a connection past
  ``serving.max_clients`` is refused with an error frame
  (``serve_shed_total``); a request past the engine's bounded intake queue
  is shed with an immediate error reply (``engine_shed_total``). Nothing
  queues without bound, nothing is dropped silently.

* **SLO telemetry** — per-client/per-model request-latency histograms
  (``serve_request_seconds{client=,model=}`` → p50/p95/p99), request and
  error counters, live in-flight/clients gauges, all in the process
  registry and on ``GET /metrics`` (``serving.metrics_port``).

* **Graceful drain** — SIGTERM (the PR 4 :class:`~.guard.PreemptionGuard`
  contract) stops admission, answers every request already accepted (new
  arrivals get an immediate ``draining`` error reply — answered, never
  dropped), waits out the engines up to ``serving.drain_timeout``, then
  exits 75 (EX_TEMPFAIL: supervisor, restart me). A service restart
  re-reads the registry manifest and recovers the exact serving set.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

from .. import telemetry
from ..connection import (FramedConnection, Hub, open_socket_connection,
                          connect_socket_connection, is_infer)
from ..connection import INFER_KIND, TRACE_KEY
from ..fault import Backoff
from ..guard import PREEMPT_EXIT_CODE, PreemptionGuard
from .client import SERVE_KIND, is_serve, parse_endpoint
from .registry import ModelRegistry, RegistryError, parse_spec

_LOG = telemetry.get_logger('serving')


def ring_percentile_ms(lats, q: float) -> float:
    """Nearest-rank percentile of a latency ring (seconds), in ms — the
    one SLO-snapshot definition shared by the service heartbeat and the
    gateway's per-ply latency gauge, so 'p99_ms' means the same thing on
    every surface."""
    if not lats:
        return 0.0
    lats = sorted(lats)
    return 1e3 * lats[int(round((len(lats) - 1) * float(q)))]


class _WarmSink:
    """Reply endpoint for synthetic warm-up requests (the rolling-promote
    walk): the engine's reply lands here instead of a client socket, so a
    replica can materialize + compile a model version end-to-end before the
    champion flips to it."""

    def __init__(self):
        self.done = threading.Event()
        self.reply: Dict[str, Any] = {}

    def deliver(self, msg: Dict[str, Any]):
        self.reply = msg or {}
        self.done.set()


class InferenceService:
    """One serving process: listener + Hub + registry-backed engine fleet.

    ``args`` is a train_args-style dict carrying an ``env`` block (the
    Gather convention): the env builds the example observation the engines
    materialize snapshots against; the ``serving`` and ``inference`` blocks
    carry the knobs. ``start()`` binds and spins the accept/dispatch
    threads; ``stop()`` drains and tears down. The service holds no
    per-episode state — clients may connect, crash, and reconnect at any
    ply (recurrent hidden state rides the requests, as in the worker tier).
    """

    def __init__(self, args: Dict[str, Any],
                 registry: Optional[ModelRegistry] = None):
        srv = dict(args.get('serving') or {})
        self._args = args
        self.host = str(srv.get('host') or '')
        self.port = int(srv.get('port', 9997))
        self.default_line = str(srv.get('line', 'default'))
        self.max_clients = max(1, int(srv.get('max_clients', 64)))
        self.drain_timeout = max(0.1, float(srv.get('drain_timeout', 30.0)))
        self.engines_n = max(1, int(srv.get('engines', 1)))
        self.metrics_port = int(srv.get('metrics_port') or 0)
        root = srv.get('registry_dir') or args.get('model_dir', 'models')
        self.registry = registry if registry is not None \
            else ModelRegistry(root,
                               lock_timeout=float(srv.get('lock_timeout',
                                                          10.0)))
        flt = dict(srv.get('fleet') or {})
        self.resolver_endpoint = str(flt.get('resolver') or '')
        self.replica_name = str(flt.get('replica') or '')
        self.advertise_host = str(flt.get('advertise') or '')
        self.heartbeat_interval = max(0.05,
                                      float(flt.get('heartbeat_interval',
                                                    2.0)))

        env = None
        self._example_obs = None
        if args.get('env'):
            from ..environment import make_env
            env = make_env(dict(args['env']))
            env.reset()
            self._example_obs = env.observation(env.players()[0])

        self._lock = threading.Lock()
        # (line, version) <-> engine-facing integer model handle; appended
        # by the dispatch thread, read by engine threads' snapshot fetches
        self._handles: Dict[Tuple[str, str], int] = {}   # guarded-by: _lock
        self._handle_meta: Dict[int, Tuple[str, str]] = {}  # guarded-by: _lock
        # (endpoint id, rid) -> (t0, model label, client label); written at
        # submit (dispatch thread), popped at reply (engine threads)
        self._pending: Dict[Tuple[int, Any], tuple] = {}  # guarded-by: _lock
        # recent request latencies (s) feeding the heartbeat SLO snapshot
        self._lat_ring: deque = deque(maxlen=512)         # guarded-by: _lock
        self._draining = False
        self._fleet_drain = False   # resolver told us to drain (autoscaler)
        self._stop = False
        self._sock: Optional[socket.socket] = None
        self.hub: Optional[Hub] = None
        self.engines: list = []
        self._exporter = None
        self._threads: list = []
        self.received = 0
        self.answered = 0
        self.refused = 0      # connections shed by the admission gate

        self._m_requests = lambda model, client: telemetry.counter(
            'serve_requests_total', model=model, client=client)
        self._m_latency = lambda model, client: telemetry.REGISTRY.histogram(
            'serve_request_seconds', model=model, client=client)
        self._m_errors = lambda reason: telemetry.counter(
            'serve_errors_total', reason=reason)
        self._m_shed = telemetry.counter('serve_shed_total')
        self._m_clients = telemetry.gauge('serve_clients')
        self._m_inflight = telemetry.gauge('serve_inflight')
        self._m_draining = telemetry.gauge('serve_draining')
        # SLO alert engine over this replica's own registry (shed burn
        # rate, heartbeat misses); evaluated on /statusz scrapes and the
        # heartbeat loop through one cadence-gated stream
        self._alerts = telemetry.AlertEngine.from_config(args)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> 'InferenceService':
        from ..inference import EngineSupervisor
        self._sock = open_socket_connection(self.port)
        self._sock.listen(self.max_clients + 8)
        self._sock.settimeout(0.5)
        self.port = self._sock.getsockname()[1]   # resolve port 0
        self.hub = Hub()
        self.engines = [
            EngineSupervisor(self._args, fetch_snapshot=self._fetch,
                             reply_fn=self._reply, clients=None,
                             example_obs=self._example_obs)
            for _ in range(self.engines_n)]
        if self.metrics_port and telemetry.enabled():
            self._exporter = telemetry.TelemetryExporter(
                lambda: [telemetry.snapshot()], port=self.metrics_port,
                status=self._status_info
            ).start()
            self.metrics_port = self._exporter.port
        loops = [(self._accept_loop, 'serve-accept'),
                 (self._dispatch_loop, 'serve-dispatch')]
        if self.resolver_endpoint:
            loops.append((self._fleet_loop, 'serve-heartbeat'))
        for target, name in loops:
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        _LOG.info('inference service listening on port %d (%d engine(s), '
                  'registry %s)', self.port, self.engines_n,
                  self.registry.root)
        return self

    def request_drain(self):
        """Begin graceful drain: no new work is admitted; everything
        already accepted is answered."""
        if not self._draining:
            self._draining = True
            self._m_draining.set(1.0)
            _LOG.warning('serving: drain requested — answering %d in-flight '
                         'request(s), refusing new work', self.inflight())

    def drained(self) -> bool:
        with self._lock:
            pending = bool(self._pending)
        return not pending

    def stop(self, drain: bool = True):
        """Drain (bounded by ``serving.drain_timeout``), then tear down the
        listener, engines, and exporter."""
        if drain:
            self.request_drain()
            deadline = time.monotonic() + self.drain_timeout
            while not self.drained() and time.monotonic() < deadline:
                time.sleep(0.02)
            if not self.drained():
                _LOG.error('serving: drain timeout (%.1fs) with %d '
                           'request(s) still unanswered',
                           self.drain_timeout, self.inflight())
        self._stop = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        for engine in self.engines:
            engine.stop()
        # give the Hub's per-endpoint writers a beat to flush the final
        # replies out of their outboxes before the process goes away
        time.sleep(0.25)
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None

    # -- accept / admission ------------------------------------------------

    def _accept_loop(self):
        while not self._stop:
            try:
                conn, _addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return            # listener closed: shutting down
            ep = FramedConnection(conn)
            if self.hub.count() >= self.max_clients:
                # admission control: refuse loudly instead of queueing a
                # client the engines cannot keep up with
                self.refused += 1
                self._m_shed.inc()
                try:
                    ep.send((SERVE_KIND,
                             {'error': 'service full (%d clients)'
                                       % self.max_clients}))
                finally:
                    ep.close()
                continue
            # clients may idle between matches: disable the silent-peer
            # deadline (dead sockets still detach on read/write errors)
            self.hub.attach(ep, liveness=0)
            self._m_clients.set(self.hub.count())

    # -- dispatch ----------------------------------------------------------

    def _dispatch_loop(self):
        import queue as _q
        while not self._stop:
            try:
                ep, msg = self.hub.recv(timeout=0.3)
            except _q.Empty:
                self._m_clients.set(self.hub.count())
                continue
            try:
                if is_infer(msg):
                    body = msg[1] if isinstance(msg[1], dict) else {}
                    self._submit(ep, body)
                elif is_serve(msg):
                    body = msg[1] if isinstance(msg[1], dict) else {}
                    self._admin(ep, body)
                else:
                    self.hub.send(ep, (SERVE_KIND,
                                       {'error': 'unknown frame kind'}))
            except Exception as exc:   # noqa: BLE001 — the loop must live
                _LOG.error('serving: dispatch error (%s: %s)',
                           type(exc).__name__, str(exc)[:200])

    def _client_label(self, ep, body: Dict[str, Any]) -> str:
        name = body.get('client')
        if name:
            return str(name)[:64]
        sock = getattr(ep, 'sock', None)
        try:
            peer = sock.getpeername()
            return '%s:%s' % peer[:2]
        except (OSError, AttributeError, TypeError):
            return 'unknown'

    def _error_reply(self, ep, body: Dict[str, Any], reason: str,
                     error: str):
        """Answer a request the service itself rejects (resolve failure,
        drain, missing fields): counted, tagged as an engine fault so
        worker clients fail over, and always SENT — a rejected request is
        still an answered request."""
        self._m_errors(reason).inc()
        self.answered += 1
        self.hub.send(ep, (INFER_KIND, {'rid': body.get('rid'),
                                        'engine_fault': True,
                                        'error': error}))

    def _submit(self, ep, body: Dict[str, Any]):
        self.received += 1
        if self._draining:
            self._error_reply(ep, body, 'draining',
                              'service draining (restart imminent)')
            return
        spec = body.get('model')
        try:
            if spec is not None:
                line, selector = parse_spec(str(spec))
            elif body.get('mid') is not None:
                # bare integer ids resolve as versions of the default line
                # (the worker EngineClient convention: version == epoch)
                line, selector = self.default_line, str(int(body['mid']))
            else:
                raise RegistryError('request names no model (neither '
                                    "'model' nor 'mid')")
            version, _meta = self.registry.resolve(line, selector)
        except (RegistryError, ValueError) as exc:
            self._error_reply(ep, body, 'resolve', str(exc))
            return
        handle = self._intern(line, version)
        model_label = '%s@%s' % (line, version)
        with self._lock:
            # the trace context (and its wall-clock arrival) rides in the
            # pending entry so _reply can close the serve_request span
            self._pending[(id(ep), body.get('rid'))] = (
                time.monotonic(), model_label,
                self._client_label(ep, body),
                body.get(TRACE_KEY), time.time())  # graftlint: allow[GL001] wall-clock span timestamp for the Chrome trace only — never enters the reply or any episode record
            self._m_inflight.set(len(self._pending))
        self.engines[handle % len(self.engines)].submit(
            ep, dict(body, mid=handle))

    def _intern(self, line: str, version: str) -> int:
        with self._lock:
            key = (line, version)
            handle = self._handles.get(key)
            if handle is None:
                handle = len(self._handles) + 1
                self._handles[key] = handle
                self._handle_meta[handle] = key
            return handle

    def _fetch(self, handle: int) -> Dict[str, Any]:
        """Engine-side snapshot fetch: handle -> registry bytes (CRC
        re-verified on every load)."""
        with self._lock:
            line, version = self._handle_meta[handle]
        return self.registry.load_snapshot(line, version)

    def _reply(self, ep, msg: Dict[str, Any]):
        """Engine reply fan-in: close the latency span, count, forward."""
        if isinstance(ep, _WarmSink):
            ep.deliver(msg)           # synthetic warm-up: no client socket
            return
        with self._lock:
            entry = self._pending.pop((id(ep), (msg or {}).get('rid')), None)
            self._m_inflight.set(len(self._pending))
            if entry is not None:
                self._lat_ring.append(time.monotonic() - entry[0])
        if entry is not None:
            t0, model_label, client_label, trace, t_wall = entry
            dt = time.monotonic() - t0
            self._m_latency(model_label, client_label).observe(dt)
            self._m_requests(model_label, client_label).inc()
            if msg.get('error'):
                self._m_errors('engine').inc()
            if trace:
                telemetry.trace_event('serve_request', ts=t_wall, dur=dt,
                                      trace_id=trace, model=model_label,
                                      client=client_label,
                                      replica=self.replica_name or '')
        self.answered += 1
        self.hub.send(ep, (INFER_KIND, msg))

    # -- admin frames ------------------------------------------------------

    def _admin(self, ep, body: Dict[str, Any]):
        op = body.get('op')
        if op == 'status':
            self.hub.send(ep, (SERVE_KIND, self.stats()))
        elif op == 'resolve':
            try:
                line, selector = parse_spec(str(body.get('model')))
                version, meta = self.registry.resolve(line, selector)
                self.hub.send(ep, (SERVE_KIND,
                                   {'line': line, 'version': version,
                                    'steps': meta.get('steps'),
                                    'architecture': meta.get('architecture')}))
            except (RegistryError, ValueError) as exc:
                self.hub.send(ep, (SERVE_KIND, {'error': str(exc)}))
        elif op == 'warm':
            self._warm(ep, str(body.get('model')))
        elif op == 'trace':
            # runtime tracing toggle: turn a warmed service's tracing on
            # and off without a restart (docs/observability.md)
            telemetry.configure_tracing(str(body.get('dir') or ''),
                                        body.get('rate'), force=True)
            self.hub.send(ep, (SERVE_KIND,
                               {'ok': True,
                                'dir': telemetry.trace_dir(),
                                'rate': telemetry.trace_sample_rate()}))
        else:
            self.hub.send(ep, (SERVE_KIND,
                               {'error': 'unknown admin op %r' % (op,)}))

    def _warm(self, ep, spec: str):
        """Rolling-promote walk: materialize + compile ``line@selector``
        end-to-end by pushing one synthetic request (the example
        observation) through the engine, replying asynchronously — engine
        compiles must not wedge the dispatch loop."""
        if self._draining:
            self.hub.send(ep, (SERVE_KIND, {'error': 'service draining'}))
            return
        try:
            line, selector = parse_spec(spec)
            version, _meta = self.registry.resolve(line, selector)
        except (RegistryError, ValueError) as exc:
            self.hub.send(ep, (SERVE_KIND, {'error': str(exc)}))
            return
        if self._example_obs is None:
            # no env block: nothing to push through the engine; resolving
            # (and the CRC-verified load on first real request) is all we
            # can pre-pay
            self.hub.send(ep, (SERVE_KIND, {'ok': True, 'line': line,
                                            'version': version,
                                            'warmed': False}))
            return
        handle = self._intern(line, version)

        def run():
            sink = _WarmSink()
            self.engines[handle % len(self.engines)].submit(
                sink, {'rid': -1, 'mid': handle, 'obs': self._example_obs})
            ok = sink.done.wait(timeout=60.0)
            err = (sink.reply.get('error') if ok
                   else 'warm-up request timed out')
            reply = ({'ok': True, 'line': line, 'version': version,
                      'warmed': True} if ok and not err
                     else {'error': str(err)})
            self.hub.send(ep, (SERVE_KIND, reply))

        t = threading.Thread(target=run, name='serve-warm', daemon=True)
        t.start()

    # -- fleet membership --------------------------------------------------

    def fleet_drain_requested(self) -> bool:
        """True once the resolver directed this replica to drain (the
        autoscaler's scale-down path); ``serve_main`` then exits 75, the
        same supervisor contract as a SIGTERM drain."""
        return self._fleet_drain

    def poll_alerts(self):
        """Drive the alert engine from the owner's idle loop so rules
        fire/clear even when nothing scrapes /statusz."""
        if self._alerts is not None:
            self._alerts.maybe_evaluate(lambda: [telemetry.snapshot()])

    def _status_info(self) -> Dict[str, Any]:
        """/statusz payload for the serving metrics port: live SLO
        numbers, request progress, and the replica's alert state."""
        info: Dict[str, Any] = {
            'slo': self.slo_snapshot(),
            'progress': {'received': self.received,
                         'answered': self.answered,
                         'refused': self.refused,
                         'draining': bool(self._draining)},
        }
        if self._alerts is not None:
            info['alerts'] = self._alerts.maybe_evaluate(
                lambda: [telemetry.snapshot()])
        return info

    def slo_snapshot(self) -> Dict[str, Any]:
        """The live SLO numbers a heartbeat carries: recent p50/p99
        latency, shed + request counters, in-flight depth."""
        with self._lock:
            lats = sorted(self._lat_ring)
            inflight = len(self._pending)

        def pct(q: float) -> float:
            return ring_percentile_ms(lats, q)

        return {'p50_ms': pct(0.50), 'p99_ms': pct(0.99),
                'inflight': inflight,
                'shed': self.refused + sum(e.sheds for e in self.engines),
                'received': self.received, 'answered': self.answered,
                'draining': self._draining}

    def _fleet_reply(self, conn, timeout: float = 5.0) -> Dict[str, Any]:
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not conn.poll(remaining):
                raise TimeoutError('no resolver reply within %.1fs'
                                   % timeout)
            msg = conn.recv()
            if is_serve(msg) and isinstance(msg[1], dict):
                return msg[1]

    def _fleet_loop(self):
        """Register with the resolver, then heartbeat liveness + the SLO
        snapshot every ``heartbeat_interval``; a lost resolver is redialed
        with jittered backoff (re-registration under the same replica name
        is how a respawned replica is re-admitted). The heartbeat reply may
        carry a drain directive."""
        host, port = parse_endpoint(self.resolver_endpoint)
        advertise = self.advertise_host or self.host or '127.0.0.1'
        backoff = Backoff(initial=0.5, maximum=10.0)
        conn = None
        while not self._stop:
            try:
                if conn is None:
                    conn = connect_socket_connection(host, port)
                    body = {'op': 'register',
                            'endpoint': '%s:%d' % (advertise, self.port),
                            'pid': os.getpid()}
                    if self.replica_name:
                        body['replica'] = self.replica_name
                    conn.send((SERVE_KIND, body))
                    rep = self._fleet_reply(conn)
                    if rep.get('error'):
                        raise RuntimeError(str(rep['error']))
                    self.replica_name = str(rep.get('replica')
                                            or self.replica_name)
                    backoff.reset()
                    _LOG.info('serving: registered with resolver %s as '
                              'replica %r', self.resolver_endpoint,
                              self.replica_name)
                conn.send((SERVE_KIND, {'op': 'heartbeat',
                                        'replica': self.replica_name,
                                        'slo': self.slo_snapshot()}))
                rep = self._fleet_reply(conn)
                if rep.get('drain') and not self._draining:
                    _LOG.warning('serving: resolver directed replica %r to '
                                 'drain', self.replica_name)
                    self._fleet_drain = True
                    self.request_drain()
            except (OSError, ConnectionError, EOFError, ValueError,
                    TimeoutError, RuntimeError) as exc:
                if conn is not None:
                    try:
                        conn.close()
                    except Exception:
                        pass
                    conn = None
                if not self._stop:
                    _LOG.warning('serving: resolver connection lost (%s: '
                                 '%s); redialing', type(exc).__name__,
                                 str(exc)[:200])
                self._sleep(backoff.next_delay())
                continue
            self._sleep(self.heartbeat_interval)
        if conn is not None:
            try:
                conn.close()
            except Exception:
                pass

    def _sleep(self, seconds: float):
        deadline = time.monotonic() + seconds
        while not self._stop and time.monotonic() < deadline:
            time.sleep(min(0.1, max(0.0, deadline - time.monotonic())))

    # -- introspection -----------------------------------------------------

    def inflight(self) -> int:
        with self._lock:
            return len(self._pending)

    def stats(self) -> Dict[str, Any]:
        # local tallies, NOT the process-global telemetry registry: stats
        # must describe THIS service instance even when other engines share
        # the process (tests) or telemetry is disabled
        shed = self.refused + sum(e.sheds for e in self.engines)
        return {
            'port': self.port,
            'clients': self.hub.count() if self.hub is not None else 0,
            'received': self.received,
            'answered': self.answered,
            'inflight': self.inflight(),
            'shed': shed,
            'draining': self._draining,
            'engines': len(self.engines),
            'replica': self.replica_name,
            'resolver': self.resolver_endpoint,
            'engine_requests': sum(e.requests_served for e in self.engines),
            'engine_batches': sum(e.batches_run for e in self.engines),
            'lines': {line: {'champion': entry['champion'],
                             'previous': entry['previous'],
                             'versions': sorted(entry['versions'])}
                      for line, entry in self.registry.describe().items()},
        }


def serve_main(args, argv=None):
    """``main.py --serve``: run the service until SIGTERM/SIGINT, then
    drain and exit 75 (the PreemptionGuard supervisor contract). Prints one
    JSON ready-line on stdout so harnesses can discover the bound ports."""
    sargs = dict(args['train_args'])
    sargs['env'] = dict(args['env_args'])
    inf = dict(sargs.get('inference') or {})
    if str(inf.get('engine_backend', 'cpu')) == 'device':
        from .. import claim_devices, setup_compile_cache
        setup_compile_cache()
        claim_devices('serve')
    else:
        from ..connection import force_cpu_backend
        force_cpu_backend()
    from ..environment import prepare_env
    prepare_env(sargs['env'])

    telemetry.adopt_config(sargs)
    telemetry.set_process_label('serve')
    telemetry.install_crash_dump()
    if telemetry.enabled():
        # XLA compile-event counters (cache hits, compile durations)
        telemetry.install_jax_monitoring()
    guard = PreemptionGuard().install()
    service = InferenceService(sargs).start()
    print(json.dumps({'serving_ready': {
        'port': service.port, 'metrics_port': service.metrics_port,
        'pid': os.getpid(), 'registry': service.registry.root}}), flush=True)
    try:
        while not guard.requested() and not service.fleet_drain_requested():
            time.sleep(0.2)
            service.poll_alerts()
        if guard.requested():
            _LOG.warning('serving: preemption signal received; draining')
    finally:
        service.stop(drain=True)
        guard.uninstall()
    if guard.fired or service.fleet_drain_requested():
        # a resolver-directed drain exits through the same supervisor
        # contract as a SIGTERM: 75 = done cleanly, restartable
        raise SystemExit(PREEMPT_EXIT_CODE)
