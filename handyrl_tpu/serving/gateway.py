"""Match gateway: sessionful gameplay over the stateless replica fleet.

The fleet (fleet.py) serves pure per-ply inference; a real product runs
*matches*. The :class:`MatchGateway` is the session tier on top of
:class:`~.fleet.RoutedClient`: a client opens a session naming an
environment and a ``line@selector``, the gateway instantiates the env
host-side (any :class:`~..environment.BaseEnvironment`), steps every
opponent seat through the fleet, and caches recurrent hidden state
keyed by session — so each client ply is one round trip and consecutive
plies of a session coalesce into the same engine batch (session
affinity via the :class:`~..fault.SessionLedger`).

Robustness model — the PR 12 zero-loss story extended from requests to
sessions. Every session keeps a compact **journal**: env name + the
audited seed that built it, the model spec *pinned* to a concrete
``line@version`` at open (so a champion flip mid-match never forks the
opponent), the full action history, and a digest of the cached hidden
state. Because fleet inference is pure in ``(model@version, obs,
hidden, legal, seed)``, the journal is a complete reconstruction
recipe:

* **drain → handoff.** A draining replica's sessions are re-pinned to a
  survivor with ZERO replayed plies — the hidden cache lives in the
  gateway and rides the next request (``gateway_handoffs_total``).
* **SIGKILL → reconstruct.** The monitor rebuilds each stranded
  session from its journal: a fresh env from ``(env, seed)``, every
  journaled opponent ply replayed through a survivor with its original
  audited seed. Replayed actions must equal the journaled ones and the
  rebuilt hidden digest must equal the journal's — byte-identical, and
  the rebuilt state is *adopted*, so play continues on proven state
  (``gateway_reconstructs_total`` / ``gateway_replayed_plies_total``;
  a divergence books ``gateway_reconstruct_mismatch_total`` and drops
  the session — loudly, never silently).

Match outcomes feed the league :class:`~..league.RatingBook`: external
players are provisional members (seeded at the learner's rating, high
sigma, never promotion-eligible), the served model is its rated
``line@version`` entry. Admission control sheds *opens*, never plies.
Opponent inference seeds ride the audited
:func:`~..generation.sample_seed` machinery under namespace
``GATEWAY_SEED_NAMESPACE`` so replay is a pure function of the journal.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import socket
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from .. import telemetry
from ..connection import FramedConnection, Hub
from ..connection import TRACE_KEY, open_socket_connection
from ..environment import make_env
from ..fault import HOST_DEGRADED, HOST_HEALTHY, SessionLedger
from ..generation import sample_seed
from ..guard import PREEMPT_EXIT_CODE, PreemptionGuard
from ..league import journal_path, make_rating_book
from .client import (SERVE_KIND, ServiceClient, ServiceError,
                     ServiceUnavailable, is_serve, parse_endpoint)
from .fleet import RoutedClient
from .service import ring_percentile_ms

_LOG = telemetry.get_logger('serving')

# Episode-key namespace for gateway opponent-inference draws (0 =
# generation, 1 = worker-local, 2 = evaluator, 3 = league — see
# generation.py / league.py). Draw 0 derives the per-session env seed;
# opponent plies consume draws 1, 2, ... in strict session order, so a
# journal replay re-consumes the identical sequence.
GATEWAY_SEED_NAMESPACE = 4

_ROUTABLE = (HOST_HEALTHY, HOST_DEGRADED)


def _feed(h, node) -> None:
    if node is None:
        h.update(b'N')
    elif isinstance(node, dict):
        h.update(b'D')
        for k in sorted(node, key=str):
            h.update(str(k).encode('utf-8'))
            _feed(h, node[k])
    elif isinstance(node, (list, tuple)):
        h.update(b'L%d' % len(node))
        for v in node:
            _feed(h, v)
    elif isinstance(node, np.ndarray):
        h.update(b'A')
        h.update(str(node.dtype).encode('ascii'))
        h.update(str(node.shape).encode('ascii'))
        h.update(np.ascontiguousarray(node).tobytes())
    elif isinstance(node, (bytes, bytearray)):
        h.update(b'B')
        h.update(bytes(node))
    else:
        h.update(b'S')
        h.update(repr(node).encode('utf-8'))


def state_digest(state) -> str:
    """Deterministic digest of a (possibly nested) hidden-state pytree —
    the byte-identity witness the session journal carries."""
    h = hashlib.sha1()
    _feed(h, state)
    return h.hexdigest()


def session_env_seed(base_seed: int, counter: int) -> int:
    """Per-session env construction seed: draw 0 of the session's audited
    sequence, folded to one int (HungryGeese-style envs seed their own
    ``random.Random(args['id'])`` from it)."""
    seq = sample_seed(int(base_seed),
                      (GATEWAY_SEED_NAMESPACE, int(counter)), 0)
    return int(np.random.default_rng(seq).integers(0, 2 ** 31 - 1))


class MatchSession:
    """One open match: the host-side env, the per-seat hidden cache, and
    the journal that makes both reconstructible."""

    def __init__(self, sid: str, counter: int, env_name: str,
                 env_args: Dict[str, Any], env, model: str, seat: int,
                 base_seed: int, client: str, clock=time.time, trace=None):
        self.sid = sid
        self.counter = int(counter)
        self.env = env
        self.model = str(model)          # pinned line@version (or raw spec)
        self.seat = int(seat)
        self.client = str(client)
        self.base_seed = int(base_seed)
        self.opened_at = clock()
        self.last_active = self.opened_at
        self.lock = threading.Lock()
        self.hiddens: Dict[int, Any] = {}   # opponent seat -> cached hidden
        self.draws = 1                       # draw 0 built the env seed
        # the session's trace context: the id minted (or adopted) at open;
        # reconstruct/handoff link spans carry it so a failover reads as
        # one causal chain from the original open
        self.trace = trace
        self.lat_ring: deque = deque(maxlen=64)   # per-session ply seconds
        self.done = False
        self.outcome: Optional[Dict[int, float]] = None
        self.journal: Dict[str, Any] = {
            'sid': sid, 'counter': self.counter, 'env': str(env_name),
            'env_args': dict(env_args), 'model': self.model,
            'seat': self.seat, 'client': self.client,
            'base_seed': self.base_seed,
            'actions': [],                   # one {player: action} per step
            'hidden_digest': state_digest({}),
        }

    def plies(self) -> int:
        return len(self.journal['actions'])

    def summary(self, replica=None, clock=time.time) -> Dict[str, Any]:
        return {'sid': self.sid, 'env': self.journal['env'],
                'model': self.model, 'seat': self.seat,
                'client': self.client, 'plies': self.plies(),
                'version': self.model.rpartition('@')[2] or None,
                'ply_p99_ms': (ring_percentile_ms(list(self.lat_ring), 0.99)
                               if self.lat_ring else None),
                'age_s': round(clock() - self.opened_at, 3),
                'replica': replica, 'done': self.done}


class MatchGateway:
    """The session tier: listener + Hub + worker pool over the fleet.

    ``args`` is a train_args-style dict; knobs ride
    ``serving.gateway.*`` (see config.py). Fast admin ops (``status`` /
    ``sessions``) answer inline on the dispatch thread; ``open`` /
    ``play`` / ``close`` run on the worker pool, each worker owning its
    own :class:`RoutedClient` (the one-submitter-per-instance
    contract). A monitor thread watches the fleet table: draining
    replicas hand their sessions off, vanished replicas trigger
    journal reconstruction.
    """

    def __init__(self, args: Dict[str, Any]):
        srv = dict(args.get('serving') or {})
        gw = dict(srv.get('gateway') or {})
        flt = dict(srv.get('fleet') or {})
        self.port = int(gw.get('port', 0) or 0)
        self.workers_n = max(1, int(gw.get('workers', 4)))
        self.max_sessions = max(1, int(gw.get('max_sessions', 64)))
        self.ply_timeout = max(0.1, float(gw.get('ply_timeout', 15.0)))
        self.monitor_interval = max(0.05, float(gw.get('monitor_interval',
                                                       0.5)))
        self.session_timeout = max(1.0, float(gw.get('session_timeout',
                                                     600.0)))
        self.default_model = str(gw.get('model') or 'default@champion')
        self.resolver_endpoint = str(gw.get('resolver')
                                     or flt.get('resolver') or '')
        if not self.resolver_endpoint:
            raise ValueError('the match gateway needs a fleet resolver '
                             '(serving.gateway.resolver)')
        self.base_seed = int(args.get('seed', 0) or 0)
        root = srv.get('registry_dir') or args.get('model_dir', 'models')
        self.ratings = make_rating_book(args.get('league') or {})
        self._ratings_path = journal_path(str(root))
        self.ratings.load(self._ratings_path)
        self._ratings_lock = threading.Lock()

        self.ledger = SessionLedger()
        self._sessions: Dict[str, MatchSession] = {}
        self._lock = threading.Lock()
        self._counter = 0
        self._queue: "queue.Queue" = queue.Queue()
        self._tl = threading.local()
        self._lat_ring: deque = deque(maxlen=512)   # guarded-by: _lock
        self._stop = False
        self._sock: Optional[socket.socket] = None
        self.hub: Optional[Hub] = None
        self._threads: List[threading.Thread] = []
        self.metrics_port = int(gw.get('metrics_port') or 0)
        self._exporter = None

        self._m_opened = telemetry.counter('gateway_sessions_opened_total')
        self._m_closed = telemetry.counter('gateway_sessions_closed_total')
        self._m_drops = telemetry.counter('gateway_session_drops_total')
        self._m_shed = telemetry.counter('gateway_shed_total')
        self._m_plies = telemetry.counter('gateway_plies_total')
        self._m_outcomes = telemetry.counter('gateway_outcomes_total')
        self._m_handoffs = telemetry.counter('gateway_handoffs_total')
        self._m_reconstructs = telemetry.counter(
            'gateway_reconstructs_total')
        self._m_replayed = telemetry.counter('gateway_replayed_plies_total')
        self._m_mismatch = telemetry.counter(
            'gateway_reconstruct_mismatch_total')
        self._m_open_g = telemetry.gauge('gateway_sessions_open')
        self._m_age_g = telemetry.gauge('gateway_session_age_seconds')
        self._m_p99_g = telemetry.gauge('gateway_ply_p99_ms')
        self._m_ply_h = telemetry.REGISTRY.histogram('gateway_ply_seconds')
        self._alerts = telemetry.AlertEngine.from_config(args)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> 'MatchGateway':
        self._sock = open_socket_connection(self.port)
        self._sock.listen(self.max_sessions + 8)
        self._sock.settimeout(0.5)
        self.port = self._sock.getsockname()[1]
        self.hub = Hub()
        if self.metrics_port and telemetry.enabled():
            self._exporter = telemetry.TelemetryExporter(
                lambda: [telemetry.snapshot()], port=self.metrics_port,
                status=self._status_info,
            ).start()
            self.metrics_port = self._exporter.port
        loops = [(self._accept_loop, 'gateway-accept'),
                 (self._dispatch_loop, 'gateway-dispatch'),
                 (self._monitor_loop, 'gateway-monitor')]
        loops += [(self._worker_loop, 'gateway-worker-%d' % i)
                  for i in range(self.workers_n)]
        for target, name in loops:
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        _LOG.info('match gateway listening on port %d (resolver %s, '
                  '%d worker(s), max %d sessions)', self.port,
                  self.resolver_endpoint, self.workers_n,
                  self.max_sessions)
        return self

    def stop(self, drain: bool = True):
        if drain:
            # sessions are reconstructible from their journals by design;
            # a gateway drain just stops admitting and lets in-flight ops
            # finish (they complete in worker time, bounded by ply_timeout)
            deadline = time.monotonic() + min(self.ply_timeout, 30.0)
            while not self._queue.empty() and time.monotonic() < deadline:
                time.sleep(0.02)
        self._stop = True
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        time.sleep(0.25)     # let Hub writers flush final replies
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None

    # -- the per-thread fleet router ---------------------------------------

    def _router(self) -> RoutedClient:
        r = getattr(self._tl, 'router', None)
        if r is None:
            host, port = parse_endpoint(self.resolver_endpoint)
            r = RoutedClient(host, port, timeout=self.ply_timeout,
                             name='gateway',
                             refresh_interval=self.monitor_interval)
            self._tl.router = r
        return r

    # -- accept / dispatch -------------------------------------------------

    def _accept_loop(self):
        while not self._stop:
            try:
                conn, _addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            self.hub.attach(FramedConnection(conn), liveness=0)

    def _dispatch_loop(self):
        while not self._stop:
            try:
                ep, msg = self.hub.recv(timeout=0.3)
            except queue.Empty:
                continue
            try:
                if not is_serve(msg) or not isinstance(msg[1], dict):
                    self.hub.send(ep, (SERVE_KIND,
                                       {'error': 'unknown frame kind'}))
                    continue
                body = msg[1]
                op = body.get('op')
                if op == 'status':
                    self.hub.send(ep, (SERVE_KIND, self.stats()))
                elif op == 'sessions':
                    self.hub.send(ep, (SERVE_KIND,
                                       {'sessions': self.session_table()}))
                elif op == 'trace':
                    # runtime tracing toggle, as on the service
                    # (docs/observability.md)
                    telemetry.configure_tracing(
                        str(body.get('dir') or ''), body.get('rate'),
                        force=True)
                    self.hub.send(ep, (SERVE_KIND,
                                       {'ok': True,
                                        'dir': telemetry.trace_dir(),
                                        'rate':
                                            telemetry.trace_sample_rate()}))
                elif op in ('open', 'play', 'close'):
                    self._queue.put((ep, body))
                else:
                    self.hub.send(ep, (SERVE_KIND,
                                       {'error': 'unknown gateway op %r'
                                                 % (op,)}))
            except Exception as exc:   # noqa: BLE001 — the loop must live
                _LOG.error('gateway: dispatch error (%s: %s)',
                           type(exc).__name__, str(exc)[:200])

    def _worker_loop(self):
        while not self._stop:
            try:
                ep, body = self._queue.get(timeout=0.3)
            except queue.Empty:
                continue
            op = body.get('op')
            try:
                if op == 'open':
                    reply = self._op_open(body)
                elif op == 'play':
                    reply = self._op_play(body)
                else:
                    reply = self._op_close(body)
            except (ServiceError, ServiceUnavailable, TimeoutError) as exc:
                reply = {'error': '%s: %s' % (type(exc).__name__, exc)}
            except Exception as exc:   # noqa: BLE001 — answer, never drop
                _LOG.error('gateway: %s failed (%s: %s)', op,
                           type(exc).__name__, str(exc)[:200])
                reply = {'error': '%s: %s' % (type(exc).__name__, exc)}
            try:
                self.hub.send(ep, (SERVE_KIND, reply))
            except Exception:   # noqa: BLE001 — client gone mid-reply
                pass

    # -- session ops -------------------------------------------------------

    def _op_open(self, body: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            if len(self._sessions) >= self.max_sessions:
                self._m_shed.inc()
                return {'error': 'gateway full (%d sessions)'
                                 % self.max_sessions, 'shed': True}
            self._counter += 1
            counter = self._counter
        env_name = str(body.get('env') or '')
        model = str(body.get('model') or self.default_model)
        seat = int(body.get('seat', 0))
        client = str(body.get('client') or 'anon')[:64]
        base_seed = int(body['seed']) if body.get('seed') is not None \
            else self.base_seed
        env_args = {'env': env_name,
                    'id': session_env_seed(base_seed, counter)}
        try:
            env = make_env(dict(env_args))
            env.reset()
        except Exception as exc:   # noqa: BLE001 — bad env name/args
            return {'error': 'cannot build env %r: %s' % (env_name, exc)}
        if seat not in env.players():
            return {'error': 'seat %d not in players %s'
                             % (seat, env.players())}
        router = self._router()
        pinned = router._pin_spec(model)
        sid = 's%06d' % counter
        # session trace context: adopt the client's id, else mint at this
        # edge; every ply/seat/reconstruct span of the session links to it
        tid = body.get(TRACE_KEY) or (telemetry.mint_trace_id()
                                      if telemetry.trace_enabled() else None)
        t0 = time.time()
        session = MatchSession(sid, counter, env_name, env_args, env,
                               pinned, seat, base_seed, client, trace=tid)
        with self._lock:
            self._sessions[sid] = session
        with session.lock:
            self._advance(session, None, router, trace=tid)
            if router.last_replica is not None:
                self.ledger.book(sid, router.last_replica)
            reply = self._state_reply(session)
        if tid:
            telemetry.trace_event('gateway_open', ts=t0,
                                  dur=time.time() - t0, trace_id=tid,
                                  sid=sid, model=pinned, client=client)
        self._m_opened.inc()
        self._set_gauges()
        reply.update({'sid': sid, 'seat': seat, 'model': pinned})
        if session.done:
            self._finish(session)
        return reply

    def _op_play(self, body: Dict[str, Any]) -> Dict[str, Any]:
        sid = str(body.get('sid') or '')
        with self._lock:
            session = self._sessions.get(sid)
        if session is None:
            return {'error': 'unknown session %r' % sid}
        router = self._router()
        # per-ply trace context: the client's id if it sent one, else a
        # fresh mint; args carry the session's open-time id as the link
        tid = body.get(TRACE_KEY) or (telemetry.mint_trace_id()
                                      if telemetry.trace_enabled() else None)
        t0 = time.monotonic()
        t0_wall = time.time()
        with session.lock:
            if session.done:
                return dict(self._state_reply(session), sid=sid)
            action: Optional[int] = None
            if session.seat in (int(p) for p in session.env.turns()):
                if body.get('action') is None:
                    return {'error': 'it is your turn in session %s — '
                                     'an action is required' % sid}
                action = int(body['action'])
                if action not in [int(a)
                                  for a in session.env.legal_actions(
                                      session.seat)]:
                    return {'error': 'illegal action %d in session %s'
                                     % (action, sid)}
            elif body.get('action') is not None:
                return {'error': 'not your turn in session %s' % sid}
            # action None here = a spectate poll (the client's seat is out
            # of the match but the game runs on): advance to terminal
            before = session.plies()
            self._advance(session, action, router, trace=tid)
            played = session.journal['actions'][before:]
            if router.last_replica is not None:
                self.ledger.move(sid, router.last_replica)
            session.last_active = time.time()
            session.lat_ring.append(time.monotonic() - t0)
            reply = self._state_reply(session)
        dt = time.monotonic() - t0
        with self._lock:
            self._lat_ring.append(dt)
        self._m_plies.inc()
        self._m_ply_h.observe(dt)
        if tid:
            telemetry.trace_event('gateway_ply', ts=t0_wall, dur=dt,
                                  trace_id=tid, sid=sid,
                                  session_trace=session.trace)
        reply.update({'sid': sid,
                      'actions': [{int(p): int(a) for p, a in step.items()}
                                  for step in played]})
        if session.done:
            self._finish(session)
        self._set_gauges()
        return reply

    def _op_close(self, body: Dict[str, Any]) -> Dict[str, Any]:
        sid = str(body.get('sid') or '')
        with self._lock:
            session = self._sessions.pop(sid, None)
        if session is None:
            return {'error': 'unknown session %r' % sid}
        self.ledger.release(sid)
        self._m_closed.inc()
        self._set_gauges()
        return {'sid': sid, 'closed': True, 'done': session.done}

    def _state_reply(self, session: MatchSession) -> Dict[str, Any]:
        env = session.env
        out: Dict[str, Any] = {'done': bool(env.terminal())}
        if out['done']:
            session.done = True
            session.outcome = {int(p): float(s)
                               for p, s in env.outcome().items()}
            out['outcome'] = session.outcome
        else:
            out['obs'] = env.observation(session.seat)
            out['legal'] = [int(a)
                            for a in env.legal_actions(session.seat)] \
                if session.seat in env.turns() else []
            out['to_move'] = session.seat in env.turns()
        return out

    # -- the opponent-stepping core ----------------------------------------

    def _advance(self, session: MatchSession, action: Optional[int],
                 router: RoutedClient,
                 replica: Optional[str] = None, trace=None) -> None:
        """Step the env until it is the client's turn with no pending
        action, or terminal. Every step's action dict lands in the
        journal; opponent seats act (and observers watch) through the
        fleet in sorted-seat order, so a journal replay consumes the
        identical audited-seed sequence."""
        env = session.env
        while not env.terminal():
            acting = sorted(int(p) for p in env.turns())
            watching = sorted(int(p) for p in env.observers())
            if session.seat in acting and action is None:
                break
            moves: Dict[int, int] = {}
            for p in acting:
                if p == session.seat:
                    moves[p] = int(action)
                    action = None
                else:
                    moves[p] = self._opponent_act(session, p, router,
                                                  replica, trace)
            for p in watching:
                if p != session.seat:
                    self._opponent_watch(session, p, router, replica,
                                         trace)
            env.step(moves)
            session.journal['actions'].append(
                {int(p): int(a) for p, a in moves.items()})
        session.journal['hidden_digest'] = state_digest(session.hiddens)

    def _seed_seq(self, session: MatchSession) -> List[int]:
        seq = sample_seed(session.base_seed,
                          (GATEWAY_SEED_NAMESPACE, session.counter),
                          session.draws)
        session.draws += 1
        return seq

    def _opponent_act(self, session: MatchSession, p: int,
                      router: RoutedClient,
                      replica: Optional[str] = None, trace=None) -> int:
        env = session.env
        t0 = time.time()
        reply = router.request(
            session.model, env.observation(p),
            hidden=session.hiddens.get(p),
            legal=[int(a) for a in env.legal_actions(p)],
            seed=self._seed_seq(session),
            timeout=self.ply_timeout,
            replica=replica if replica is not None
            else self.ledger.replica_of(session.sid),
            trace=trace)
        if trace:
            telemetry.trace_event('gateway_seat', ts=t0,
                                  dur=time.time() - t0, trace_id=trace,
                                  sid=session.sid, seat=p)
        session.hiddens[p] = reply.get('hidden')
        return int(reply['action'])

    def _opponent_watch(self, session: MatchSession, p: int,
                        router: RoutedClient,
                        replica: Optional[str] = None, trace=None) -> None:
        env = session.env
        t0 = time.time()
        reply = router.request(
            session.model, env.observation(p),
            hidden=session.hiddens.get(p),
            timeout=self.ply_timeout,
            replica=replica if replica is not None
            else self.ledger.replica_of(session.sid),
            trace=trace)
        if trace:
            telemetry.trace_event('gateway_seat', ts=t0,
                                  dur=time.time() - t0, trace_id=trace,
                                  sid=session.sid, seat=p, watch=True)
        session.hiddens[p] = (reply.get('outputs') or {}).get('hidden')

    # -- outcome booking ---------------------------------------------------

    def _finish(self, session: MatchSession):
        """Book the finished match into the RatingBook (the external
        player is a provisional member; the served model is its rated
        ``line@version`` entry) and retire the session."""
        with self._lock:
            live = self._sessions.pop(session.sid, None)
        self.ledger.release(session.sid)
        if live is None:      # already closed/dropped concurrently
            return
        score = (session.outcome or {}).get(session.seat, 0.0)
        score = min(max(0.5 * (1.0 + float(score)), 0.0), 1.0)
        player = 'gateway:%s' % session.client
        with self._ratings_lock:
            self.ratings.seed_provisional(player)
            self.ratings.record_between(player, session.model, score)
            try:
                self.ratings.save(self._ratings_path)
            except OSError as exc:
                _LOG.warning('gateway: rating journal write failed: %s',
                             exc)
        self._m_outcomes.inc()
        self._m_closed.inc()
        self._set_gauges()

    def _drop(self, session: MatchSession, reason: str):
        with self._lock:
            self._sessions.pop(session.sid, None)
        self.ledger.release(session.sid)
        self._m_drops.inc()
        telemetry.record_event('session_drop', session.sid, reason=reason)
        _LOG.error('gateway: dropped session %s (%s)', session.sid, reason)
        self._set_gauges()

    # -- journal reconstruction --------------------------------------------

    def _reconstruct(self, session: MatchSession,
                     router: RoutedClient) -> bool:
        """Rebuild a stranded session from its journal through a
        survivor: fresh env from ``(env, seed)``, every opponent ply
        replayed with its original audited seed. The replayed actions
        and the rebuilt hidden digest must match the journal — then the
        rebuilt state is adopted, proving the journal alone carries the
        match. False (and a drop) on divergence."""
        j = session.journal
        # link span: the replay-through-a-survivor carries the session's
        # ORIGINAL open-time trace id, so the SIGKILL reads as one chain
        tid = session.trace
        t0 = time.time()
        env = make_env(dict(j['env_args']))
        env.reset()
        hiddens: Dict[int, Any] = {}
        draws = 1
        replayed = 0
        for step in list(j['actions']):
            step = {int(p): int(a) for p, a in step.items()}
            acting = sorted(int(p) for p in env.turns())
            watching = sorted(int(p) for p in env.observers())
            for p in acting:
                if p == j['seat']:
                    continue
                seq = sample_seed(j['base_seed'],
                                  (GATEWAY_SEED_NAMESPACE, j['counter']),
                                  draws)
                draws += 1
                reply = router.request(
                    j['model'], env.observation(p),
                    hidden=hiddens.get(p),
                    legal=[int(a) for a in env.legal_actions(p)],
                    seed=seq, timeout=self.ply_timeout, trace=tid)
                hiddens[p] = reply.get('hidden')
                replayed += 1
                if int(reply['action']) != step.get(p):
                    self._m_mismatch.inc()
                    if tid:
                        telemetry.trace_event(
                            'gateway_reconstruct', ts=t0,
                            dur=time.time() - t0, trace_id=tid,
                            link='reconstruct', sid=session.sid,
                            replayed=replayed, ok=False)
                    self._drop(session, 'reconstruct action mismatch at '
                                        'ply %d seat %d' % (replayed, p))
                    return False
            for p in watching:
                if p != j['seat']:
                    reply = router.request(j['model'], env.observation(p),
                                           hidden=hiddens.get(p),
                                           timeout=self.ply_timeout,
                                           trace=tid)
                    hiddens[p] = (reply.get('outputs') or {}).get('hidden')
            env.step(step)
        if state_digest(hiddens) != j['hidden_digest']:
            self._m_mismatch.inc()
            if tid:
                telemetry.trace_event('gateway_reconstruct', ts=t0,
                                      dur=time.time() - t0, trace_id=tid,
                                      link='reconstruct', sid=session.sid,
                                      replayed=replayed, ok=False)
            self._drop(session, 'reconstruct hidden-digest mismatch')
            return False
        session.env = env
        session.hiddens = hiddens
        session.draws = draws
        self._m_reconstructs.inc()
        self._m_replayed.inc(replayed)
        if tid:
            telemetry.trace_event('gateway_reconstruct', ts=t0,
                                  dur=time.time() - t0, trace_id=tid,
                                  link='reconstruct', sid=session.sid,
                                  replayed=replayed, ok=True)
        if router.last_replica is not None:
            self.ledger.move(session.sid, router.last_replica)
        _LOG.warning('gateway: reconstructed session %s (%d plies '
                     'replayed, digest verified)', session.sid, replayed)
        return True

    # -- fleet monitoring: handoff and reconstruction ----------------------

    def _monitor_loop(self):
        router: Optional[RoutedClient] = None
        known: Dict[str, Dict[str, Any]] = {}
        while not self._stop:
            time.sleep(self.monitor_interval)
            try:
                if router is None:
                    host, port = parse_endpoint(self.resolver_endpoint)
                    router = RoutedClient(host, port,
                                          timeout=self.ply_timeout,
                                          name='gateway-monitor',
                                          refresh_interval=
                                          self.monitor_interval)
                table = {str(r['replica']): r for r in router.replicas()}
            except (ServiceUnavailable, TimeoutError, ServiceError):
                continue
            survivors = [n for n, r in sorted(table.items())
                         if r.get('state') in _ROUTABLE
                         and not r.get('draining')]
            # drain → handoff: zero replayed plies, the hidden cache is
            # ours and simply rides the next request to the survivor
            for name, rec in table.items():
                if rec.get('draining') and rec.get('state') in _ROUTABLE:
                    self._handoff(name, survivors, reason='drain')
            # SIGKILL → reconstruct: the replica vanished from the table
            # (externally managed) or was stranded out of the routable
            # states (a managed corpse walks healthy → quarantined and is
            # respawned under its old name — its in-flight plies died)
            dead = list(set(known) - set(table))
            dead += [name for name, rec in table.items()
                     if rec.get('state') not in _ROUTABLE]
            for name in dead:
                sids = self.ledger.fail_replica(name, reason='killed')
                for sid in sids:
                    with self._lock:
                        session = self._sessions.get(sid)
                    if session is None:
                        continue
                    with session.lock:
                        if not session.done:
                            self._reconstruct(session, router)
            known = table
            self._reap()
            self._set_gauges()
            if self._alerts is not None:
                self._alerts.maybe_evaluate(
                    lambda: [telemetry.snapshot()])

    def _handoff(self, replica: str, survivors: List[str], reason: str):
        sids = self.ledger.sessions_on(replica)
        if not sids:
            return
        pool = [s for s in survivors if s != replica]
        if not pool:
            return      # nowhere to go yet; next tick retries
        for i, sid in enumerate(sids):
            target = pool[i % len(pool)]
            self.ledger.move(sid, target)
            self._m_handoffs.inc()
            with self._lock:
                session = self._sessions.get(sid)
            if session is not None and session.trace:
                # link span under the session's original open-time id
                telemetry.trace_event('gateway_handoff',
                                      trace_id=session.trace,
                                      link='handoff', sid=sid,
                                      from_replica=replica,
                                      to_replica=target, reason=reason)
        _LOG.warning('gateway: handed %d session(s) off %s (%s)',
                     len(sids), replica, reason)

    def _reap(self):
        now = time.time()
        with self._lock:
            idle = [s for s in self._sessions.values()
                    if now - s.last_active > self.session_timeout]
        for session in idle:
            self._drop(session, 'session_timeout')

    # -- observability -----------------------------------------------------

    def _set_gauges(self):
        now = time.time()
        with self._lock:
            n = len(self._sessions)
            oldest = max((now - s.opened_at
                          for s in self._sessions.values()), default=0.0)
            lats = list(self._lat_ring)
        self._m_open_g.set(float(n))
        self._m_age_g.set(float(oldest))
        self._m_p99_g.set(ring_percentile_ms(lats, 0.99))

    def session_table(self) -> List[Dict[str, Any]]:
        with self._lock:
            sessions = list(self._sessions.values())
        return [s.summary(replica=self.ledger.replica_of(s.sid))
                for s in sessions]

    def _status_info(self) -> Dict[str, Any]:
        """/statusz payload for the gateway metrics port: the live
        session table (main.py --status renders it), session/ply
        progress, and the gateway's alert state."""
        with self._lock:
            lats = list(self._lat_ring)
        info: Dict[str, Any] = {
            'sessions': self.session_table(),
            'progress': {'opened': int(self._m_opened.value),
                         'plies': int(self._m_plies.value),
                         'outcomes': int(self._m_outcomes.value),
                         'handoffs': int(self._m_handoffs.value),
                         'reconstructs': int(self._m_reconstructs.value),
                         'dropped': int(self._m_drops.value)},
            'slo': {'ply_p50_ms': ring_percentile_ms(lats, 0.50),
                    'ply_p99_ms': ring_percentile_ms(lats, 0.99)},
        }
        if self._alerts is not None:
            info['alerts'] = self._alerts.maybe_evaluate(
                lambda: [telemetry.snapshot()])
        return info

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            n = len(self._sessions)
            lats = list(self._lat_ring)
        return {'gateway': True, 'port': self.port,
                'resolver': self.resolver_endpoint,
                'sessions': n, 'max_sessions': self.max_sessions,
                'opened': int(self._m_opened.value),
                'closed': int(self._m_closed.value),
                'dropped': int(self._m_drops.value),
                'shed': int(self._m_shed.value),
                'plies': int(self._m_plies.value),
                'outcomes': int(self._m_outcomes.value),
                'handoffs': int(self._m_handoffs.value),
                'reconstructs': int(self._m_reconstructs.value),
                'replayed_plies': int(self._m_replayed.value),
                'mismatches': int(self._m_mismatch.value),
                'ply_p50_ms': ring_percentile_ms(lats, 0.50),
                'ply_p99_ms': ring_percentile_ms(lats, 0.99),
                'ledger': dict(self.ledger.stats),
                'ratings': self.ratings.names()}


class GatewayClient:
    """Client for the match gateway: the whole session protocol over one
    :class:`ServiceClient` admin channel (``open``/``play``/``close``
    round trips; one in flight at a time per client, matching the
    one-submitter contract)."""

    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 name: str = ''):
        self.name = str(name)
        self._client = ServiceClient(host, int(port), timeout=timeout,
                                     name=name)

    def _call(self, body: Dict[str, Any],
              timeout: Optional[float] = None) -> Dict[str, Any]:
        if (body.get('op') in ('open', 'play') and TRACE_KEY not in body
                and telemetry.trace_enabled()):
            # mint at the true request edge so the chain starts with the
            # client; the gateway adopts the id instead of minting its own
            body = dict(body, **{TRACE_KEY: telemetry.mint_trace_id()})
        reply = self._client.call_admin(body, timeout)
        if reply.get('error'):
            raise ServiceError(str(reply['error']))
        return reply

    def open(self, env: str, model: Optional[str] = None, seat: int = 0,
             seed: Optional[int] = None,
             timeout: Optional[float] = None) -> Dict[str, Any]:
        body: Dict[str, Any] = {'op': 'open', 'env': str(env),
                                'seat': int(seat), 'client': self.name}
        if model is not None:
            body['model'] = str(model)
        if seed is not None:
            body['seed'] = int(seed)
        return self._call(body, timeout)

    def play(self, sid: str, action: Optional[int] = None,
             timeout: Optional[float] = None) -> Dict[str, Any]:
        """Submit a ply (``action=None`` is a spectate poll: the seat is
        out of the match but the game runs on)."""
        body: Dict[str, Any] = {'op': 'play', 'sid': str(sid),
                                'client': self.name}
        if action is not None:
            body['action'] = int(action)
        return self._call(body, timeout)

    def close_session(self, sid: str) -> Dict[str, Any]:
        return self._call({'op': 'close', 'sid': str(sid)})

    def sessions(self) -> List[Dict[str, Any]]:
        return self._call({'op': 'sessions'}).get('sessions', [])

    def status(self) -> Dict[str, Any]:
        return self._call({'op': 'status'})

    def close(self):
        self._client.close()


def gateway_main(args, argv=None):
    """``main.py --gateway``: one MatchGateway over a running fleet
    resolver until SIGTERM/SIGINT, then drain and exit 75 (the
    supervisor restart contract). Prints one JSON ``gateway_ready``
    line once the listener is bound."""
    sargs = dict(args['train_args'])
    sargs['env'] = dict(args.get('env_args') or {})
    telemetry.adopt_config(sargs)
    telemetry.set_process_label('gateway')
    telemetry.install_crash_dump()
    guard = PreemptionGuard().install()
    gateway = MatchGateway(sargs).start()
    print(json.dumps({'gateway_ready': {
        'port': gateway.port, 'pid': os.getpid(),
        'resolver': gateway.resolver_endpoint,
        'max_sessions': gateway.max_sessions}}), flush=True)
    try:
        while not guard.requested():
            time.sleep(0.2)
        _LOG.warning('gateway: preemption signal received; draining')
    finally:
        gateway.stop(drain=True)
        guard.uninstall()
    if guard.fired:
        raise SystemExit(PREEMPT_EXIT_CODE)
