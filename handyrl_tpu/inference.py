"""Per-host batched inference service for the distributed actor fleet.

The reference design runs B=1 CPU inference inside every episode worker
(reference model.py:50-60): each worker process holds a full model snapshot
and pays one jitted dispatch per ply. The Podracer/Sebulba architecture
(https://arxiv.org/pdf/2104.06272) restructures that: env-steppers submit
observations to one accelerator-adjacent inference server that coalesces
them into large batched forward passes. This module is that restructuring
for the 4-RPC worker fleet — and, since PR 6, the *self-healing* version of
it: every worker on a host depends on one engine thread, so that thread is
supervised, requests carry deadlines, and the worker can degrade to the
per-worker inference path and come back, all without losing a single
episode byte.

* :class:`InferenceEngine` — the coalescing batched-forward server. It
  groups outstanding ``(model_id, obs, hidden, legal_actions)`` requests
  across all workers on the host — per model id, under a ``batch_wait_ms``
  deadline and a ``max_batch`` cap, padding ragged rows exactly like the
  learner-local batched generator — runs ONE ``batch_inference`` per tick,
  performs masked sampling engine-side (the same audited routine the B=1
  path uses, so episode records stay bit-identical), and fans the
  ``(action, prob, value, hidden')`` replies back over the Hub. Its intake
  queue is bounded (``inference.queue_max``): an overloaded engine sheds
  requests with an error reply instead of growing without bound, and a
  fatal engine error fans an error reply to every in-flight request — no
  reply is ever silently dropped by a crash.

* :class:`EngineSupervisor` — the watchdog the Gather actually owns. It
  health-checks the engine's tick progress, restarts a crashed or stalled
  engine with :class:`~.fault.Backoff`, drains + error-answers whatever the
  dead engine was holding, and suppresses replies from an abandoned
  (zombie) engine thread via a generation tag so a restart can never
  double-answer a request. It also hosts the ``enginekill=`` /
  ``enginestall=`` chaos injectors.

* :class:`EngineClient` / :class:`RemoteModel` / :class:`RemoteModelCache`
  — the worker side. A worker in engine mode never touches params by
  default: its "models" are handles that turn ``act``/``inference`` calls
  into request frames on the existing worker<->gather pipe. Every round
  trip carries a deadline (``inference.request_timeout``) with bounded
  resends (``request_retries``); when the engine stays unreachable the
  client opens a circuit breaker and **degrades to the per-worker
  inference path** — materializing snapshots locally through the same
  'model' RPC — and, because the PR 5 seeded sampler makes an episode a
  pure function of ``(seed, sample_key, params)`` on either path, the
  failover is lossless: records stay byte-identical. A half-open probe
  (``reprobe_initial_delay`` backoff) re-promotes the worker to the engine
  path once the engine answers again.

* :class:`ModelVault` — the snapshot-materialization LRU (the per-worker
  B=1 path and the degraded failover path use it directly; the engine uses
  it engine-side). Capacity is the ``inference.vault_size`` knob. Two ids
  of the same architecture never alias one set of live params.

Recurrent state rides the requests: a request with ``hidden=None`` against
a recurrent model gets a fresh ``init_hidden()`` engine-side (episode
start), and every reply carries the advanced per-row hidden for the worker
to send back on its next ply — the engine itself holds no per-episode
state, so workers may crash/join/degrade/re-promote at any ply without
poisoning the service or the episode.
"""

from __future__ import annotations

import random
import threading
import time
import traceback
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import telemetry
from .connection import INFER_KIND, is_infer
from .fault import Backoff, parse_chaos
from .generation import (bucketed_inference, masked_sample_batch, model_act,
                         pad_to_bucket)
from .model import ModelWrapper, RandomModel
from .utils.tree import map_structure

_LOG = telemetry.get_logger('inference')

_UNSET = object()   # per-wrapper init_hidden cache sentinel


def _canon(x):
    """Rebind an unpickled ndarray's dtype to the interned descriptor.

    Arrays that crossed the engine pipe carry a fresh ``dtype`` instance;
    value-equal but not identical to numpy's interned singleton. Pickle
    memoizes dtype objects by IDENTITY, so a moment dict mixing local and
    wire arrays would serialize to different bytes than an all-local one —
    breaking the bit-identical episode record contract. Rebinding is O(1)
    (descriptor swap, no data copy)."""
    if isinstance(x, np.ndarray):
        x.dtype = np.dtype(x.dtype.str)
    return x


class ModelVault:
    """Small LRU of materialized models keyed by model id.

    ``fetch(model_id)`` pulls a snapshot over the RPC connection on miss.
    Each cached id owns its wrapper (sharing only the per-architecture jit
    cache inside ModelWrapper), so distinct ids never share live params.
    Id 0 denotes the untrained epoch-0 net and is served as a RandomModel —
    a deliberate, documented divergence (see PARITY.md): its uniform play
    matches the sampler's selected_prob, keeping training math identical.
    """

    def __init__(self, fetch, example_obs, capacity: int = 3):
        self._fetch = fetch
        self._example_obs = example_obs
        self._capacity = max(1, int(capacity))
        self._slots: OrderedDict = OrderedDict()
        self._templates: Dict[str, Any] = {}   # arch -> params pytree
        self.fetches = 0                       # snapshot pulls (cache misses)

    def obtain(self, wanted: Dict[Any, Optional[int]]) -> Dict[Any, Any]:
        """Return player -> model for every requested id (None/negative ->
        no model: the server assigns those seats to built-in opponents)."""
        out = {}
        for player, mid in wanted.items():
            if mid is None or mid < 0:
                out[player] = None
                continue
            out[player] = self.model(mid)
        return out

    def model(self, mid: int):
        """The materialized model for one id (admitting it on miss)."""
        if mid not in self._slots:
            self._admit(mid)
        self._slots.move_to_end(mid)
        return self._slots[mid]

    def params(self, mid: int):
        """The raw params pytree for one id, or None for ids served as
        RandomModel (id 0) — the device actor backend stacks these as slot
        leaves; paramless seats run zero-policy modes instead."""
        return getattr(self.model(int(mid)), 'params', None)

    def _admit(self, mid: int):
        snap = self._fetch(mid)
        self.fetches += 1
        # template key includes the wire config: the same architecture with
        # a different param-tree-shaping knob (e.g. GeisterNet norm_kind)
        # must not reuse a structurally different template
        key = (snap['architecture'], tuple(sorted(snap.get('config', {}).items())))
        wrapper = ModelWrapper.from_snapshot(
            snap, self._example_obs,
            params_template=self._templates.get(key))
        self._templates.setdefault(key, wrapper.params)
        model = RandomModel(wrapper, self._example_obs) if mid == 0 else wrapper
        while len(self._slots) >= self._capacity:
            self._slots.popitem(last=False)
        self._slots[mid] = model


# ---------------------------------------------------------------------------
# worker side: deadline-bounded transport + circuit-breaker failover


class EngineClient:
    """Worker-side engine transport: deadlines, bounded retry, and a
    circuit breaker that degrades to the per-worker inference path.

    One client per worker process owns the worker's half of the engine
    protocol on the shared gather pipe: request ids, the pending-request
    book (kept so a timed-out request can be REPLAYED locally from its own
    inputs — lossless, since the two paths are bit-identical), early/stale
    reply routing, and the circuit-breaker state machine:

    * **closed** (``engine_ok``): requests go to the engine, each with a
      ``request_timeout`` deadline and up to ``request_retries`` resends.
    * **open**: after a request exhausts its deadline budget or gets an
      engine-fault error reply, the client logs the degradation, computes
      every in-flight and subsequent request locally (ModelVault over the
      same 'model' RPC), and schedules a half-open probe.
    * **half-open**: once the :class:`~.fault.Backoff` delay elapses, ONE
      request is routed to the engine as a probe; success re-promotes the
      worker to the engine path (circuit closes, backoff resets), failure
      re-opens with a longer delay.

    ``rpc`` is the client's filtered call-response for the worker's
    non-inference RPCs (args/episode/model): a late reply from an abandoned
    inference request may arrive at any time after a failover, and must be
    absorbed instead of being mistaken for the RPC's reply.
    """

    def __init__(self, conn, args: Dict[str, Any], namespace: int = 0):
        inf = dict(args.get('inference') or {})
        srv = dict(args.get('serving') or {})
        self.conn = conn
        self._args = args
        self.namespace = int(namespace)
        # remote-service mode (serving.endpoint, docs/serving.md): engine
        # frames dial a standalone InferenceService over TCP instead of
        # riding the gather pipe; requests name the model '<line>@<mid>'
        # against the service registry. Everything else — deadlines,
        # retries, the circuit breaker, the byte-identical local fallback —
        # is the same machinery, so a dead service degrades exactly like a
        # dead in-Gather engine. The endpoint may be a comma-separated
        # replica list (and/or a serving.fleet.resolver to fetch the live
        # table from): a dead replica rotates to the next one, and only an
        # all-replicas-down fleet degrades to per-worker inference.
        flt = dict(srv.get('fleet') or {})
        self.endpoint = str(srv.get('endpoint') or '')
        self._endpoints = [e.strip() for e in self.endpoint.split(',')
                           if e.strip()]
        self._resolver = str(flt.get('resolver') or '')
        self._resolver_refresh = max(0.5, float(flt.get('refresh_interval',
                                                        2.0)))
        self._resolver_next = 0.0      # next fleet-table fetch
        self._remote_mode = bool(self._endpoints or self._resolver)
        self._line = str(srv.get('line', 'default'))
        self._remote = None            # lazy FramedConnection to the service
        self._remote_ep = ''           # endpoint self._remote targets
        self._ep_idx = 0               # rotation cursor over _endpoints
        self._ep_down: Dict[str, float] = {}     # endpoint -> retry-at
        self._ep_backoff: Dict[str, Backoff] = {}
        self._m_dials = telemetry.counter('worker_engine_remote_dials_total')
        self._m_rotations = telemetry.counter(
            'worker_engine_endpoint_rotations_total')
        self.timeout = max(0.05, float(inf.get('request_timeout', 10.0)))
        self.retries = max(0, int(inf.get('request_retries', 1)))
        self.failover = bool(inf.get('failover', True))
        self.vault_size = int(inf.get('vault_size', 3))
        self._backoff = Backoff(
            float(inf.get('reprobe_initial_delay', 2.0)),
            float(inf.get('reprobe_max_delay', 30.0)))
        self.engine_ok = True          # circuit closed: engine path active
        self._probe_at = 0.0           # open circuit: next half-open probe
        self._probing_rid: Optional[int] = None
        self._rid = 0
        self._pending: Dict[int, Dict[str, Any]] = {}   # rid -> request
        self._box: Dict[int, Dict[str, Any]] = {}       # rid -> early reply
        self._local_box: Dict[int, Dict[str, Any]] = {}  # rid -> local reply
        self._vault: Optional[ModelVault] = None
        self._m_timeouts = telemetry.counter('worker_engine_timeouts_total')
        self._m_errors = telemetry.counter('worker_engine_errors_total')
        self._m_failovers = telemetry.counter('worker_engine_failovers_total')
        self._m_repromote = telemetry.counter(
            'worker_engine_repromotions_total')
        self._m_local = telemetry.counter('worker_local_inference_total')
        self._m_stale = telemetry.counter('worker_stale_replies_total')
        self._m_path = telemetry.gauge('worker_inference_path')
        self._m_path.set(1.0)

    # -- non-inference RPCs (args / episode / result / model) --------------

    def rpc(self, msg):
        """send_recv with inference-frame filtering: a stale engine reply
        (late answer to a request the client already failed over) must not
        be mistaken for this RPC's reply."""
        self.conn.send(msg)
        while True:
            reply = self.conn.recv()
            if is_infer(reply):
                self._absorb(reply[1] if isinstance(reply[1], dict) else {})
                continue
            return reply

    # -- request submission ------------------------------------------------

    def send(self, mid: int, body: Dict[str, Any]) -> int:
        """Submit one inference request; returns its request id. Routed to
        the engine when the circuit is closed (or as the half-open probe),
        computed locally otherwise."""
        self._rid += 1
        rid = self._rid
        rec = dict(body)
        rec['mid'] = int(mid)
        engine_path = self.engine_ok
        if (not engine_path and self.failover and self._probing_rid is None
                and time.monotonic() >= self._probe_at):
            engine_path = True          # half-open: one probe in flight
            self._probing_rid = rid
            _LOG.info('worker %d: probing inference engine (rid %d)',
                      self.namespace, rid)
        if engine_path:
            self._pending[rid] = rec
            if not self._send_engine(rid, rec):
                # every service replica is down: fail over NOW instead of
                # burning the request deadline on sockets that never opened
                self._local_box[rid] = self._fail(
                    rid, rec,
                    'service endpoint(s) %s unreachable'
                    % (self.endpoint or self._resolver))
        else:
            self._local_box[rid] = self._local_reply(rec)
        return rid

    def recv(self, rid: int) -> Dict[str, Any]:
        """Collect the reply for ``rid``: deadline-bounded with bounded
        resends on the engine path, instant on the degraded local path."""
        if rid in self._local_box:
            return self._local_box.pop(rid)
        rec = self._pending.get(rid)
        if rec is None:
            raise RuntimeError('unknown inference request id %r' % rid)
        err = 'no reply within %.1fs' % self.timeout
        # a probe gets ONE deadline (no resends): the point is to test the
        # engine cheaply, not to wait retries*timeout on a dead one
        attempts = 1 + (0 if self._probing_rid == rid else self.retries)
        for attempt in range(attempts):
            reply = self._box.pop(rid, None)
            if reply is None:
                reply = self._await(rid, self.timeout)
            if reply is None:                     # deadline expired
                self._m_timeouts.inc()
                if attempt + 1 < attempts:
                    # a silent service endpoint is down-marked before the
                    # resend so the redial rotates to another replica (the
                    # blackholed-replica case; no-op on the gather pipe)
                    self._drop_remote()
                    # resend under the same rid: if BOTH replies eventually
                    # arrive, the second is absorbed as stale
                    if not self._send_engine(rid, rec):
                        break                     # dead service: fail now
                continue
            if reply.get('error'):
                self._m_errors.inc()
                err = str(reply['error'])
                break
            self._settle_ok(rid)
            out = map_structure(_canon, reply)
            if isinstance(out.get('prob'), float):
                # the remote-service hop (msgpack) degrades np.float32
                # scalars to python floats; records must keep the dtype or
                # they pickle to different bytes than the local path's
                out['prob'] = np.float32(out['prob'])
            return out
        return self._fail(rid, rec, err)

    # -- internals ---------------------------------------------------------

    def _refresh_endpoints(self):
        """Fetch the routable replica table from the fleet resolver (when
        one is configured), replacing the endpoint rotation; a resolver
        failure keeps the stale list — the data plane outlives it."""
        now = time.monotonic()
        if not self._resolver or now < self._resolver_next:
            return
        self._resolver_next = now + self._resolver_refresh
        try:
            from .serving.client import (ServiceClient, ServiceUnavailable,
                                         parse_endpoint)
            host, port = parse_endpoint(self._resolver)
            probe = ServiceClient(host, port, timeout=2.0, dial_retries=0)
            try:
                table = probe.fleet(timeout=2.0).get('replicas') or []
            finally:
                probe.close()
        except (OSError, ConnectionError, EOFError, ValueError,
                TimeoutError, RuntimeError):
            return
        fresh = [str(r.get('endpoint')) for r in table
                 if r.get('state') in ('healthy', 'degraded')
                 and not r.get('draining') and r.get('endpoint')]
        if fresh and sorted(fresh) != sorted(self._endpoints):
            _LOG.info('worker %d: fleet resolver lists %d routable '
                      'replica(s): %s', self.namespace, len(fresh),
                      ', '.join(fresh))
            self._endpoints = fresh

    def _pick_endpoint(self) -> str:
        """Next admissible endpoint in rotation; an endpoint stays skipped
        until its down-mark expires. All down -> the soonest-retryable one
        (so a fleet-wide blip still probes instead of deadlocking)."""
        self._refresh_endpoints()
        if not self._endpoints:
            raise OSError('no service endpoints known (resolver %s has no '
                          'routable replicas)' % (self._resolver or '-'))
        now = time.monotonic()
        n = len(self._endpoints)
        for off in range(n):
            ep = self._endpoints[(self._ep_idx + off) % n]
            if self._ep_down.get(ep, 0.0) <= now:
                self._ep_idx = (self._ep_idx + off) % n
                return ep
        return min(self._endpoints, key=lambda e: self._ep_down.get(e, 0.0))

    def _infer_conn(self):
        """The connection engine frames ride: the gather pipe, or — with a
        ``serving.endpoint``/fleet resolver configured — a lazily-dialed
        TCP link to one of the InferenceService replicas."""
        if not self._remote_mode:
            return self.conn
        if self._remote is None:
            from .connection import connect_socket_connection
            ep = self._pick_endpoint()
            host, _, port = ep.rpartition(':')
            self._remote = connect_socket_connection(host or 'localhost',
                                                     int(port))
            self._remote_ep = ep
            self._m_dials.inc()
            _LOG.info('worker %d: dialed inference service %s',
                      self.namespace, ep)
        return self._remote

    def _drop_remote(self):
        if self._remote is not None:
            try:
                self._remote.close()
            except Exception:
                pass
            self._remote = None
        ep = self._remote_ep
        if ep:
            # down-mark the endpoint so the next dial rotates to another
            # replica; the mark expires on a per-endpoint backoff
            self._remote_ep = ''
            backoff = self._ep_backoff.setdefault(
                ep, Backoff(initial=0.5, maximum=15.0))
            self._ep_down[ep] = time.monotonic() + backoff.next_delay()
            if len(self._endpoints) > 1:
                self._m_rotations.inc()
                _LOG.warning('worker %d: service replica %s dropped; '
                             'rotating to the next endpoint',
                             self.namespace, ep)

    def _send_engine(self, rid: int, rec: Dict[str, Any]) -> bool:
        """Post one request on the engine path. False means the remote
        service could not be reached (dial or send failure) — the caller
        fails the request over; the gather-pipe path never fails here (a
        dead pipe is fatal to the worker, as before)."""
        body = {'rid': rid, **rec}
        if not self._remote_mode:
            self.conn.send((INFER_KIND, body))
            return True
        # the service resolves models by name against its registry; the
        # learner's publish hook registers epoch E as '<line>@<E>'
        body['model'] = '%s@%d' % (self._line, int(rec['mid']))
        # one attempt per known replica: a dead endpoint down-marks and
        # rotates; False only when the WHOLE fleet refused the frame
        attempts = max(1, len(self._endpoints))
        for _attempt in range(attempts):
            try:
                self._infer_conn().send((INFER_KIND, body))
                return True
            except (OSError, ConnectionError, EOFError, ValueError):
                self._drop_remote()
        return False

    def _poll(self, conn, timeout: float) -> bool:
        poll = getattr(conn, 'poll', None)
        return True if poll is None else poll(timeout)

    def _await(self, rid: int, timeout: float) -> Optional[Dict[str, Any]]:
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                conn = self._infer_conn()
                if not self._poll(conn, remaining):
                    return None
                msg = conn.recv()
            except (OSError, ConnectionError, EOFError):
                if not self._remote_mode:
                    raise          # a dead gather pipe is fatal (unchanged)
                self._drop_remote()
                return None        # treated as a timeout: retry/fail over
            if not is_infer(msg):
                raise ConnectionError(
                    'unexpected %s frame while awaiting an inference reply'
                    % type(msg).__name__)
            body = msg[1] if isinstance(msg[1], dict) else {}
            if body.get('rid') == rid:
                return body
            self._absorb(body)

    def _absorb(self, body: Dict[str, Any]):
        rid = body.get('rid')
        if rid in self._pending:
            self._box[rid] = body      # early reply for a later recv()
        else:
            self._m_stale.inc()        # late reply to an abandoned request

    def _settle_ok(self, rid: int):
        self._pending.pop(rid, None)
        if self._remote_ep:
            # the replica answered: clear its down-mark and backoff
            self._ep_down.pop(self._remote_ep, None)
            self._ep_backoff.pop(self._remote_ep, None)
        if self._probing_rid == rid:
            self._probing_rid = None
        if not self.engine_ok:
            self.engine_ok = True      # re-promotion: circuit closes
            self._backoff.reset()
            self._m_repromote.inc()
            self._m_path.set(1.0)
            _LOG.warning('worker %d: engine answered the probe; re-promoted '
                         'to engine inference', self.namespace)

    def _fail(self, rid: int, rec: Dict[str, Any], err: str
              ) -> Dict[str, Any]:
        self._pending.pop(rid, None)
        probing = self._probing_rid == rid
        if probing:
            self._probing_rid = None
        if not self.failover:
            raise RuntimeError('inference engine: %s' % err)
        now = time.monotonic()
        self._probe_at = now + self._backoff.next_delay()
        if self.engine_ok:
            self.engine_ok = False     # circuit opens
            self._m_failovers.inc()
            self._m_path.set(0.0)
            _LOG.warning('worker %d: engine unreachable (%s); degrading to '
                         'per-worker inference', self.namespace, err)
            # resolve the rest of the in-flight burst locally too — waiting
            # out each one's deadline serially would stall the episode for
            # pending * timeout seconds (their late replies are absorbed
            # as stale; the local results are bit-identical anyway)
            for orid in [r for r in self._pending if r not in self._box]:
                self._local_box[orid] = self._local_reply(
                    self._pending.pop(orid))
        elif probing:
            _LOG.info('worker %d: engine probe failed (%s); next probe in '
                      '%.1fs', self.namespace, err, self._probe_at - now)
        return self._local_reply(rec)

    # -- degraded path: per-worker inference, replayed from the request ----

    def _local_model(self, mid: int):
        if self._vault is None:
            from .environment import make_env
            env = make_env(dict(self._args['env']))
            env.reset()
            example_obs = env.observation(env.players()[0])
            self._vault = ModelVault(
                lambda m: self.rpc(('model', m)), example_obs,
                capacity=self.vault_size)
            _LOG.info('worker %d: materialized local model vault for the '
                      'degraded inference path', self.namespace)
        return self._vault.model(mid)

    def _local_reply(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        """Serve one request on the per-worker path, replaying exactly the
        inputs the engine would have seen — the reply is bit-identical to
        the engine's (PR 5 parity contract), so records do not fork."""
        self._m_local.inc()
        model = self._local_model(rec['mid'])
        hidden = rec.get('hidden')
        if hidden is None:
            hidden = model.init_hidden()   # same substitution as _serve
        if rec.get('legal') is None:
            return {'outputs': bucketed_inference(model, rec['obs'], hidden)}
        return model_act(model, rec['obs'], hidden, rec['legal'], rec['seed'])


class RemoteModel:
    """Worker-side model handle: calls become engine request frames.

    Presents the model surface the generators/agents dispatch on
    (``inference`` / ``init_hidden`` plus the engine-native ``act``), but
    holds no params — calls delegate to the shared :class:`EngineClient`,
    which owns deadlines, failover and the degraded local path.
    ``init_hidden`` returns None by design: both serving paths substitute a
    fresh initial state for a None hidden, so the worker needs no knowledge
    of the recurrent state's structure.
    """

    def __init__(self, client: EngineClient, model_id: int):
        self.client = client
        self.model_id = int(model_id)

    def init_hidden(self, batch_shape=None):
        return None

    def inference(self, obs, hidden=None) -> Dict[str, Any]:
        """Full-output forward (observer plies, evaluation agents)."""
        rid = self.client.send(self.model_id, {'obs': obs, 'hidden': hidden})
        return self.client.recv(rid)['outputs']

    def act(self, obs, hidden, legal_actions, seed_seq) -> Dict[str, Any]:
        """Masked sampling in one round trip: returns the sampled action,
        its probability, the action mask, value and hidden'."""
        return self.act_recv(self.act_send(obs, hidden, legal_actions,
                                           seed_seq))

    # split act: generators submit every simultaneous-turn request before
    # collecting any reply, so one worker's plies coalesce into the same
    # engine batch
    def act_send(self, obs, hidden, legal_actions, seed_seq) -> int:
        return self.client.send(self.model_id, {
            'obs': obs, 'hidden': hidden,
            'legal': [int(a) for a in legal_actions],
            'seed': [int(s) for s in seed_seq]})

    def act_recv(self, rid: int) -> Dict[str, Any]:
        return self.client.recv(rid)


class RemoteModelCache:
    """Engine-mode stand-in for the worker's ModelVault: same ``obtain``
    surface, but entries are weightless wire proxies (sharing one
    :class:`EngineClient`) instead of materialized snapshots."""

    def __init__(self, client, capacity: int = 8):
        self.client = client
        self._capacity = max(1, int(capacity))
        self._slots: OrderedDict = OrderedDict()

    def obtain(self, wanted: Dict[Any, Optional[int]]) -> Dict[Any, Any]:
        out = {}
        for player, mid in wanted.items():
            if mid is None or mid < 0:
                out[player] = None
                continue
            if mid not in self._slots:
                while len(self._slots) >= self._capacity:
                    self._slots.popitem(last=False)
                self._slots[mid] = RemoteModel(self.client, mid)
            self._slots.move_to_end(mid)
            out[player] = self._slots[mid]
        return out


# ---------------------------------------------------------------------------
# host side: the engine and its supervisor


class _ChaosEngineKill(RuntimeError):
    """Injected engine crash (HANDYRL_TPU_CHAOS enginekill=)."""


class InferenceEngine:
    """Coalescing batched-inference server for one host's episode workers.

    ``submit(endpoint, request)`` may be called from any thread (the
    gather's Hub loop); a single engine thread drains the queue in ticks:
    it waits until ``max_batch`` requests are pending, ``batch_wait_ms``
    has passed since the oldest arrival, or the queue has gone quiescent
    with at least ``clients`` requests waiting (see ``_collect``); then it
    groups the tick's requests per model id, pads each group to a
    power-of-two row bucket, runs ONE ``batch_inference`` per group, samples
    actions engine-side for the rows that carry legal actions, and replies
    through ``reply_fn(endpoint, message)``.

    Robustness contract (PR 6): the intake queue is bounded — a submit past
    ``queue_max`` is shed with an immediate error reply instead of growing
    the backlog without bound; a failure while serving a group (snapshot
    fetch error, model crash) answers the affected requests with an
    ``error`` reply; a FATAL engine error (anything escaping the tick loop)
    error-answers every in-flight and queued request before the thread
    exits, so no reply is ever silently dropped. Tick progress is exported
    (``progress_age`` / ``busy``) for the :class:`EngineSupervisor`
    watchdog, which restarts crashed/stalled engines.
    """

    def __init__(self, args: Dict[str, Any], fetch_snapshot: Callable,
                 reply_fn: Callable, clients: Optional[int] = None,
                 example_obs=None):
        inf = dict(args.get('inference') or {})
        self.batch_wait = max(0.0, float(inf.get('batch_wait_ms', 2.0))) / 1e3
        self.max_batch = max(1, int(inf.get('max_batch', 64)))
        self.vault_size = int(inf.get('vault_size', 3))
        self.queue_max = max(0, int(inf.get('queue_max', 1024)))
        self.clients = clients
        self._args = args
        self._fetch = fetch_snapshot
        self._reply = reply_fn
        self._example_obs = example_obs
        self.vault: Optional[ModelVault] = None   # built lazily (engine thread)
        self._cv = threading.Condition()
        # intake queue entries are (endpoint, request, t_arrival); shared by
        # submitters (hub loop), the engine thread, and the supervisor's
        # drain (lexical discipline checked by graftlint GL004)
        self._queue: deque = deque()              # guarded-by: _cv
        self._stop = False                        # guarded-by: _cv
        self._thread: Optional[threading.Thread] = None
        # watchdog surface: last tick progress + the tick's in-flight items
        self.started_at = time.monotonic()
        self.last_progress = time.monotonic()
        self._current: List[tuple] = []
        self.crashed: Optional[BaseException] = None
        self._fault: Optional[tuple] = None       # (kind, due_at, stall_s)
        # local tallies mirror the registry so the fill ratio (and the
        # serving tier's per-service shed accounting) is computable even
        # with telemetry disabled (the service stats and tests read them)
        self.requests_served = 0
        self.batches_run = 0
        self.sheds = 0
        self._m_requests = telemetry.counter('engine_requests_total')
        self._m_batches = telemetry.counter('engine_batches_total')
        self._m_rows = telemetry.REGISTRY.histogram(
            'engine_batch_rows', buckets=telemetry.BATCH_ROW_BUCKETS)
        self._m_wait = telemetry.REGISTRY.histogram('engine_coalesce_seconds')
        self._m_depth = telemetry.gauge('engine_queue_depth')
        self._m_fill = telemetry.gauge('engine_batch_fill_ratio')
        self._m_shed = telemetry.counter('engine_shed_total')
        self._m_errors = telemetry.counter('engine_error_replies_total')
        self._m_leaked = telemetry.counter('engine_stop_leaked_total')

    # -- lifecycle --------------------------------------------------------

    def start(self) -> 'InferenceEngine':
        self.started_at = self.last_progress = time.monotonic()
        self._thread = threading.Thread(target=self._run,
                                        name='inference-engine', daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        with self._cv:
            self._stop = True
            queued = len(self._queue)
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # a wedged loop thread (stuck forward pass, hung snapshot
                # fetch) survives the join: make the leak VISIBLE instead
                # of silently returning over it
                self._m_leaked.inc()
                _LOG.warning(
                    'engine: loop thread still running %.0fs after stop() '
                    '(last progress %.1fs ago, %d queued) — leaking it',
                    timeout, self.progress_age(), queued)

    def abandon(self):
        """Mark the engine stopped without joining (supervisor restart of a
        wedged engine: the zombie thread exits at its next loop boundary —
        if any — and its replies are suppressed by the generation tag)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def thread_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- watchdog surface --------------------------------------------------

    def progress_age(self) -> float:
        """Seconds since the engine thread last demonstrated progress."""
        return time.monotonic() - self.last_progress

    def busy(self) -> bool:
        """True when the engine holds work a stalled thread would strand."""
        with self._cv:
            queued = bool(self._queue)
        return queued or bool(self._current)

    def batch_fill_ratio(self) -> float:
        """Mean requests per dispatched forward batch (1.0 = no coalescing
        benefit over per-worker B=1)."""
        return self.requests_served / max(1, self.batches_run)

    def drain_pending(self) -> List[tuple]:
        """Remove and return every queued + in-flight item (supervisor
        restart path: the caller owns answering them)."""
        with self._cv:
            items = list(self._queue)
            self._queue.clear()
            self._m_depth.set(0)
            self._cv.notify_all()
        current, self._current = list(self._current), []
        return current + items

    # -- chaos (HANDYRL_TPU_CHAOS enginekill= / enginestall=) --------------

    def arm_fault(self, kind: str, delay: float, stall_secs: float = 3600.0):
        """Schedule one injected fault: 'kill' raises out of the tick loop
        (a crashed engine), 'stall' sleeps inside it while holding the
        tick's requests (a wedged forward pass / hung snapshot fetch)."""
        self._fault = (kind, time.monotonic() + max(0.0, delay),
                       float(stall_secs))

    def _maybe_chaos(self):
        if self._fault is None or time.monotonic() < self._fault[1]:
            return
        kind, _due, stall_secs = self._fault
        self._fault = None
        if kind == 'kill':
            raise _ChaosEngineKill('chaos: engine kill injected')
        _LOG.warning('chaos: engine stall injected (%.0fs)', stall_secs)
        time.sleep(stall_secs)

    # -- request intake (any thread) --------------------------------------

    def submit(self, endpoint, request: Dict[str, Any]):
        shed = False
        with self._cv:
            if self.queue_max and len(self._queue) >= self.queue_max:
                shed = True    # backpressure: bounded queue, visible drop
                self.sheds += 1
            else:
                self._queue.append((endpoint, request, time.monotonic()))
                self._m_depth.set(len(self._queue))
                self._cv.notify()
        if shed:
            self._m_shed.inc()
            self._safe_reply(endpoint, {
                'rid': (request or {}).get('rid'), 'engine_fault': True,
                'error': 'engine overloaded: request shed '
                         '(queue >= %d)' % self.queue_max})

    # -- engine thread ----------------------------------------------------

    def _safe_reply(self, endpoint, msg):
        try:
            self._reply(endpoint, msg)
        except Exception:
            pass   # a dead endpoint's reply is a no-op, like a dead socket

    def fail_pending(self, reason: str) -> int:
        """Error-answer every queued + in-flight request (crash fan-out /
        supervisor drain): no submitter is left waiting on a reply the
        engine will never send."""
        items = self.drain_pending()
        for ep, req, _t in items:
            self._m_errors.inc()
            self._safe_reply(ep, {'rid': (req or {}).get('rid'),
                                  'error': reason, 'engine_fault': True})
        return len(items)

    def _ensure_vault(self):
        if self.vault is not None:
            return
        example_obs = self._example_obs
        if example_obs is None:
            from .environment import make_env
            env = make_env(dict(self._args['env']))
            env.reset()
            example_obs = env.observation(env.players()[0])
        self.vault = ModelVault(self._fetch, example_obs,
                                capacity=self.vault_size)

    def _collect(self) -> Optional[List[tuple]]:
        """Block until a tick's worth of requests is due; None on stop.

        A tick dispatches when ``max_batch`` requests are pending, when
        ``batch_wait_ms`` has elapsed since the oldest arrival (the hard
        latency cap), or when the queue has gone QUIESCENT — no new arrival
        for a fraction of the deadline while at least ``clients`` requests
        wait. Quiescence is the early-dispatch workhorse: submitters push
        their whole turn burst back-to-back, so a silent queue means
        everyone who was going to join this batch already has, and holding
        the deadline out would only add latency, not fill."""
        gap = max(2e-4, self.batch_wait / 8)
        floor = min(self.max_batch, max(1, self.clients or 1))
        with self._cv:
            while not self._queue:
                if self._stop:
                    return None
                self.last_progress = time.monotonic()   # idle, not stalled
                self._cv.wait(1.0)
            deadline = self._queue[0][2] + self.batch_wait
            while len(self._queue) < self.max_batch and not self._stop:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                before = len(self._queue)
                self._cv.wait(min(remaining, gap))
                if len(self._queue) == before and before >= floor:
                    break
            n = min(len(self._queue), self.max_batch)
            items = [self._queue.popleft() for _ in range(n)]
            self._m_depth.set(len(self._queue))
        self._m_wait.observe(time.monotonic() - items[0][2])
        self.last_progress = time.monotonic()
        return items

    def _run(self):
        """Thread body: the tick loop plus the fatal-error fan-out. A
        per-group failure is answered inline and the service keeps running;
        anything escaping the loop itself error-answers EVERYTHING still in
        flight, marks the engine crashed, and lets the supervisor restart."""
        try:
            self._loop()
        except BaseException as exc:   # noqa: BLE001 — crash containment
            self.crashed = exc
            _LOG.error('engine: fatal %s: %s', type(exc).__name__,
                       str(exc)[:200])
            if not isinstance(exc, _ChaosEngineKill):
                _LOG.debug('%s', traceback.format_exc())
            failed = self.fail_pending(
                'inference engine crashed (%s: %s)'
                % (type(exc).__name__, str(exc)[:200]))
            if failed:
                _LOG.warning('engine: error-answered %d in-flight '
                             'request(s) after the crash', failed)

    def _loop(self):
        while True:
            items = self._collect()
            if items is None:
                return
            self._current = items
            self._maybe_chaos()
            groups: Dict[int, List[tuple]] = {}
            for item in items:
                groups.setdefault(int(item[1]['mid']), []).append(item)
            for mid, group in groups.items():
                try:
                    self._serve(mid, group)
                except Exception as exc:
                    _LOG.warning('engine: serving model %d failed (%s: %s)',
                                 mid, type(exc).__name__, str(exc)[:200])
                    _LOG.debug('%s', traceback.format_exc())
                    for ep, req, _t in group:
                        self._m_errors.inc()
                        self._safe_reply(ep, {'rid': req.get('rid'),
                                              'error': '%s: %s'
                                              % (type(exc).__name__,
                                                 str(exc)[:200])})
                self.last_progress = time.monotonic()
            self._current = []

    def _serve(self, mid: int, group: List[tuple]):
        # rate-sampled batch-level span in the episode trace (plus the
        # stage_seconds{stage=engine_batch} histogram): one span per
        # coalesced forward batch, sized for the critical-path report
        extra: Dict[str, Any] = {}
        if telemetry.trace_enabled():
            # serving-path context: per-request queue_wait spans (intake ->
            # batch start) for every sampled trace id, and the batch span
            # carries args.trace_ids so --serve chains link through it
            # (same linkage shape as train_step's episode list)
            now_mono, now_wall = time.monotonic(), time.time()
            tids = []
            for _ep, req, t_arr in group:
                tid = req.get('trace')
                if not (tid and telemetry.trace_sampled(tid)):
                    continue
                tids.append(tid)
                wait = max(0.0, now_mono - t_arr)
                telemetry.trace_event('queue_wait', ts=now_wall - wait,
                                      dur=wait, trace_id=tid, mid=mid)
            if tids:
                extra = {'trace_ids': tids, 'always': True}
        with telemetry.trace_span('engine_batch', rows=len(group), mid=mid,
                                  **extra):
            replies = self._serve_group(mid, group)
        # fan out only after the span has closed: a submitter that reads
        # the trace as soon as it holds its reply must find the span there
        for ep, reply in replies:
            self._safe_reply(ep, reply)

    def _serve_group(self, mid: int, group: List[tuple]) -> List[tuple]:
        """One coalesced forward batch; returns ``(endpoint, reply)`` for
        every request of the group, in order."""
        self._ensure_vault()
        model = self.vault.model(mid)
        reqs = [req for _ep, req, _t in group]
        rows = len(reqs)
        self.requests_served += rows
        self.batches_run += 1
        self._m_requests.inc(rows)
        self._m_batches.inc()
        self._m_rows.observe(rows)
        self._m_fill.set(self.batch_fill_ratio())

        if isinstance(model, RandomModel):
            # id 0: zero outputs, no forward pass — masked sampling over a
            # zero policy is exactly the uniform play RandomModel encodes
            out = model.inference(None)
            policies = np.broadcast_to(out['policy'],
                                       (rows,) + out['policy'].shape)
            values = (np.broadcast_to(out['value'],
                                      (rows,) + out['value'].shape)
                      if 'value' in out else None)
            next_hidden = None
        else:
            obs_batch, _ = pad_to_bucket([r['obs'] for r in reqs])
            init = getattr(model, '_engine_h0', _UNSET)
            if init is _UNSET:
                init = model.init_hidden()
                model._engine_h0 = init
            hidden_batch = None
            if init is not None:
                hidden_batch, _ = pad_to_bucket(
                    [r.get('hidden') if r.get('hidden') is not None else init
                     for r in reqs])
            outputs = model.batch_inference(obs_batch, hidden_batch)
            policies = np.asarray(outputs['policy'])
            values = (np.asarray(outputs['value'])
                      if outputs.get('value') is not None else None)
            next_hidden = outputs.get('hidden')

        act_rows = [n for n, r in enumerate(reqs) if r.get('legal') is not None]
        if act_rows:
            actions, probs, masks = masked_sample_batch(
                policies[act_rows],
                [reqs[n]['legal'] for n in act_rows],
                [reqs[n].get('seed') or [0] for n in act_rows])
        act_index = {n: k for k, n in enumerate(act_rows)}

        replies = []
        for n, (ep, req, _t) in enumerate(group):
            hidden_row = None
            if next_hidden is not None:
                hidden_row = map_structure(
                    lambda a: np.asarray(a)[n], next_hidden)
            if n in act_index:
                k = act_index[n]
                reply = {'rid': req.get('rid'),
                         'action': int(actions[k]), 'prob': probs[k],
                         'action_mask': masks[k],
                         'value': values[n] if values is not None else None,
                         'hidden': hidden_row}
            else:
                row_out = {'policy': policies[n]}
                if values is not None:
                    row_out['value'] = values[n]
                if hidden_row is not None:
                    row_out['hidden'] = hidden_row
                reply = {'rid': req.get('rid'), 'outputs': row_out}
            replies.append((ep, reply))
        return replies


class EngineSupervisor:
    """Watchdog + restart policy around :class:`InferenceEngine`.

    The Gather owns one of these instead of a bare engine. A monitor thread
    health-checks the engine's tick progress on a short cadence:

    * **crash** — the engine thread died (its own fan-out already answered
      what it could); the supervisor drains any later arrivals with error
      replies and restarts the engine after a :class:`~.fault.Backoff`
      delay (reset once an engine survives ``RESET_AFTER`` seconds).
    * **stall** — the engine is ``busy()`` but has made no tick progress
      for ``inference.stall_timeout`` seconds (wedged forward pass, hung
      snapshot fetch). The thread cannot be killed, so it is ABANDONED: the
      generation counter advances (suppressing any reply the zombie might
      eventually produce — a request must never be answered twice), every
      queued + in-flight request is error-answered, and a fresh engine
      starts. Requests the zombie physically holds get their error reply
      from this fan-out; workers that raced it are covered by their own
      request deadlines.

    While the engine is down (the backoff window), ``submit`` answers
    immediately with an error so workers fail fast into their degraded
    path instead of burning a full request deadline.

    Chaos: ``HANDYRL_TPU_CHAOS=enginekill=<mean s>`` / ``enginestall=<mean
    s>`` arm one injected fault per engine incarnation (alternating kinds
    when both are set) on an exponential clock, bounded by
    ``engine_max_faults=<n>``; ``enginestall_secs=<s>`` sets the injected
    stall's length (default 3600 — "forever" at test scale).
    """

    RESET_AFTER = 60.0   # engine alive this long => restart backoff resets

    def __init__(self, args: Dict[str, Any], fetch_snapshot: Callable,
                 reply_fn: Callable, clients: Optional[int] = None,
                 example_obs=None, chaos: Optional[Dict[str, float]] = None):
        inf = dict(args.get('inference') or {})
        self.stall_timeout = max(0.2, float(inf.get('stall_timeout', 30.0)))
        self._args = args
        self._fetch = fetch_snapshot
        self._reply_raw = reply_fn
        self._clients = clients
        self._example_obs = example_obs
        self._chaos = parse_chaos() if chaos is None else dict(chaos)
        self._faults_left = int(self._chaos.get('engine_max_faults', 1 << 30))
        self._fault_cycle = 0
        self._chaos_rng = random.Random(
            int(self._chaos.get('seed', 0)) * 104729 + 13)
        self._backoff = Backoff(0.5, float(inf.get('restart_max_delay', 10.0)))
        self._lock = threading.RLock()
        self._gen = 0
        self._stopping = False
        self._served_total = 0
        self._batches_total = 0
        self._sheds_total = 0
        self.restarts = 0
        self._m_restarts = {
            reason: telemetry.counter('engine_restarts_total', reason=reason)
            for reason in ('crash', 'stall')}
        self._m_stale = telemetry.counter('engine_stale_replies_total')
        self._spawned_at = time.monotonic()
        self.engine: Optional[InferenceEngine] = self._spawn()
        self._thread = threading.Thread(target=self._watch,
                                        name='engine-supervisor', daemon=True)
        self._thread.start()

    # -- tallies across engine restarts (service stats, tests) ------------

    @property
    def requests_served(self) -> int:
        engine = self.engine
        return self._served_total + (engine.requests_served if engine else 0)

    @property
    def batches_run(self) -> int:
        engine = self.engine
        return self._batches_total + (engine.batches_run if engine else 0)

    @property
    def sheds(self) -> int:
        engine = self.engine
        return self._sheds_total + (engine.sheds if engine else 0)

    def batch_fill_ratio(self) -> float:
        return self.requests_served / max(1, self.batches_run)

    # -- lifecycle ---------------------------------------------------------

    def _tagged(self, gen: int) -> Callable:
        """Reply function bound to one engine incarnation: replies from an
        abandoned engine (older generation) are dropped — an answered
        request was already error-answered by the restart fan-out, and a
        second reply would desync the worker's reply stream."""
        def reply(ep, msg):
            if gen == self._gen:
                self._reply_raw(ep, msg)
            else:
                self._m_stale.inc()
        return reply

    def _spawn(self) -> InferenceEngine:
        self._gen += 1
        engine = InferenceEngine(
            self._args, fetch_snapshot=self._fetch,
            reply_fn=self._tagged(self._gen), clients=self._clients,
            example_obs=self._example_obs)
        self._arm_chaos(engine)
        self._spawned_at = time.monotonic()
        return engine.start()

    def _arm_chaos(self, engine: InferenceEngine):
        kinds = [k for k in ('enginekill', 'enginestall')
                 if self._chaos.get(k)]
        if not kinds or self._faults_left <= 0:
            return
        kind = kinds[self._fault_cycle % len(kinds)]
        self._fault_cycle += 1
        self._faults_left -= 1
        delay = self._chaos_rng.expovariate(1.0 / float(self._chaos[kind]))
        engine.arm_fault('kill' if kind == 'enginekill' else 'stall', delay,
                         stall_secs=float(self._chaos.get('enginestall_secs',
                                                          3600.0)))
        _LOG.info('chaos: armed engine %s in ~%.1fs (%d fault(s) left)',
                  kind, delay, self._faults_left)

    def submit(self, endpoint, request: Dict[str, Any]):
        with self._lock:
            engine = self.engine
        if engine is None:    # restart backoff window: fail fast
            self._reply_raw(endpoint, {
                'rid': (request or {}).get('rid'), 'engine_fault': True,
                'error': 'inference engine restarting'})
            return
        engine.submit(endpoint, request)

    def stop(self):
        self._stopping = True
        with self._lock:
            engine = self.engine
        if engine is not None:
            engine.stop()

    # -- the watchdog ------------------------------------------------------

    def _watch(self):
        interval = max(0.1, min(1.0, self.stall_timeout / 4))
        while not self._stopping:
            time.sleep(interval)
            with self._lock:
                engine = self.engine
            if engine is None or self._stopping:
                continue
            reason = None
            if engine.crashed is not None or not engine.thread_alive():
                reason = 'crash'
            elif (engine.busy()
                    and engine.progress_age() > self.stall_timeout):
                reason = 'stall'
            if reason is None:
                if time.monotonic() - self._spawned_at > self.RESET_AFTER:
                    self._backoff.reset()
                continue
            self._restart(engine, reason)

    def _restart(self, engine: InferenceEngine, reason: str):
        with self._lock:
            if self.engine is not engine:
                return
            self.engine = None
            self._gen += 1            # zombie replies suppressed from here
        engine.abandon()
        self._served_total += engine.requests_served
        self._batches_total += engine.batches_run
        self._sheds_total += engine.sheds
        # fan-out THROUGH THE RAW reply path: the engine's own (tagged)
        # reply function is already suppressed by the generation bump
        stranded = engine.drain_pending()
        for ep, req, _t in stranded:
            try:
                self._reply_raw(ep, {'rid': (req or {}).get('rid'),
                                     'engine_fault': True,
                                     'error': 'inference engine %s; '
                                              'restarting' % reason})
            except Exception:
                pass
        self.restarts += 1
        self._m_restarts[reason].inc()
        telemetry.record_event('supervisor', 'engine %s declared' % reason,
                               restarts=self.restarts,
                               stranded=len(stranded))
        telemetry.dump_blackbox('engine-' + reason, restarts=self.restarts,
                                stranded=len(stranded))
        delay = self._backoff.next_delay()
        _LOG.warning('engine %s detected (progress %.1fs ago, %d request(s) '
                     'error-answered); restarting in %.1fs',
                     reason, engine.progress_age(), len(stranded), delay)
        time.sleep(delay)
        with self._lock:
            if not self._stopping:
                self.engine = self._spawn()
