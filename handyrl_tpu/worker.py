"""Actor-side process tree: episode workers, relay proxies, cluster fronts.

Round-2 redesign of the actor plumbing. The wire protocol is unchanged —
the four RPCs (``args`` / ``episode`` / ``result`` / ``model``), the entry
handshake on port 9999 (base_worker_id assignment + merged config), and the
data connections on port 9998 all match the reference topology
(reference worker.py:26-254) — but the machinery is built differently:

* every multiplexing component composes a :class:`~.connection.Hub`
  (single selector event loop) instead of subclassing a thread-pair
  communicator;
* workers cache model *snapshots per model id* in a small LRU vault and
  materialize wrappers per id — two ids of the same architecture can never
  alias one set of live params (a league/past-epoch opponent setup works);
* with the ``inference`` config block enabled, workers become pure
  env-steppers: the host relay (Gather) spawns one
  :class:`~.inference.InferenceEngine` that alone materializes snapshots
  and serves coalesced batched forward passes for every worker on the
  host — the 'model' RPC then flows learner -> gather -> engine only, so
  model broadcast cost is O(hosts), not O(workers). The engine is owned
  through an :class:`~.inference.EngineSupervisor` (restart on crash or
  stall, error fan-out) and workers degrade to the per-worker inference
  path — losslessly, records stay byte-identical — when it is
  unreachable, re-promoting once a probe succeeds;
* the 'model' RPC ships an architecture-name + msgpack-params snapshot
  (model.ModelWrapper.snapshot), never pickled code, and socket frames are
  msgpack data — nothing on the public ports can execute on decode.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue
import random
import threading
import time
import traceback
from collections import OrderedDict, defaultdict, deque
from socket import gethostname
from typing import Any, Dict, Optional

from . import telemetry
from .connection import (HEARTBEAT_KIND, INFER_KIND, RESUME_KIND, Hub,
                         accept_socket_connections,
                         connect_socket_connection, force_cpu_backend,
                         send_recv, spawn_pipe_workers)
from .environment import make_env, prepare_env
from .evaluation import Evaluator
from .fault import Backoff, parse_chaos
from .generation import Generator
# ModelVault moved to inference.py (the engine shares it); re-exported here
# for compatibility with existing imports
from .inference import (EngineClient, EngineSupervisor, InferenceEngine,
                        ModelVault, RemoteModelCache)

_LOG = telemetry.get_logger('worker')

# Overridable so several learner/worker fleets (or parallel test runs) can
# share one host without colliding on the well-known ports.
ENTRY_PORT = int(os.environ.get('HANDYRL_TPU_ENTRY_PORT', 9999))
DATA_PORT = int(os.environ.get('HANDYRL_TPU_DATA_PORT', 9998))

# connection-death signatures on the blocking RPC paths (sockets AND pipes);
# socket.timeout / Broken/ResetError are OSError subclasses
_CONN_ERRORS = (OSError, EOFError, ConnectionError)


class Worker:
    """One actor process: loops task requests over the 4-RPC protocol and
    plays out generation ('g') or evaluation ('e') assignments."""

    def __init__(self, args: Dict[str, Any], conn, wid: int):
        _LOG.info('opened worker %d', wid)
        telemetry.adopt_config(args)
        telemetry.set_process_label('worker-%d' % wid)
        telemetry.install_crash_dump()
        self.worker_id = wid
        self.conn = conn
        self.env = make_env({**args['env'], 'id': wid})
        random.seed(args['seed'] + wid)
        # one-way liveness/telemetry beacon cadence toward the gather: the
        # snapshot rides the same HEARTBEAT frame the Hub already filters
        ft = args.get('fault_tolerance') or {}
        self._hb_interval = float(ft.get('heartbeat_interval', 10.0))
        self._hb_next = time.time() + self._hb_interval

        inf = args.get('inference') or {}
        self.client: Optional[EngineClient] = None
        if inf.get('enabled'):
            # engine mode: this process materializes no params up front —
            # models are wire proxies onto the host relay's InferenceEngine.
            # The shared EngineClient owns request deadlines and the
            # circuit-breaker failover to the per-worker path (at which
            # point snapshots ARE materialized locally, via the same
            # 'model' RPC — graceful degradation costs memory, not bytes).
            self.client = EngineClient(conn, args, namespace=wid)
            self.vault = RemoteModelCache(self.client)
        else:
            self.env.reset()
            example_obs = self.env.observation(self.env.players()[0])
            self.vault = ModelVault(
                lambda mid: send_recv(conn, ('model', mid)), example_obs,
                capacity=int(inf.get('vault_size', 3)))

        generator = Generator(self.env, args, namespace=wid)
        evaluator = Evaluator(self.env, args)
        # role -> (episode producer, upload RPC name)
        self.playbook = {'g': (generator.execute, 'episode'),
                         'e': (evaluator.execute, 'result')}
        if (args.get('streaming') or {}).get('enabled'):
            # streaming ingest: the generator flushes fixed-T chunks
            # through the same RPC pipe mid-episode ('chunk' uploads ride
            # the gather's stash/resend machinery like any other kind);
            # the whole-episode upload collapses into a streamed sentinel
            # the run loop skips — the learner's assembler completes the
            # task once every window lands
            self.playbook['g'] = (
                lambda models, task: generator.execute(
                    models, task, emit=lambda c: self._rpc(('chunk', c))),
                'episode')

    def __del__(self):
        _LOG.info('closed worker %d', self.worker_id)

    def _maybe_heartbeat(self):
        """Piggyback this worker's registry snapshot on a heartbeat frame
        toward the gather's hub (filtered there into peer_info, merged into
        the gather's own beacon toward the learner)."""
        now = time.time()
        if self._hb_interval <= 0 or now < self._hb_next:
            return
        self._hb_next = now + self._hb_interval
        # refresh the device-memory gauges so every heartbeat snapshot
        # carries this process's current footprint up the merge tree
        telemetry.sample_device_memory()
        self.conn.send((HEARTBEAT_KIND,
                        {'worker': self.worker_id,
                         'telemetry': telemetry.snapshot()}))
        # keep the shared trace file current even while this worker lives:
        # a gather-killed (chaos) worker must not strand its episode spans
        telemetry.trace_flush()

    def _rpc(self, msg):
        """One blocking call-response on the gather pipe. In engine mode
        the EngineClient filters out any late inference reply that would
        otherwise be mistaken for this RPC's answer."""
        if self.client is not None:
            return self.client.rpc(msg)
        return send_recv(self.conn, msg)

    def run(self):
        """Supervised task loop: a broken pipe to the gather ends the
        process (the gather's supervisor respawns the whole subtree), but a
        crashing episode only costs that one episode — the payload becomes
        None (skipped server-side; the task ledger re-issues it on
        deadline) and the loop keeps serving."""
        chaos = parse_chaos()
        doom = None
        if chaos.get('kill_worker'):
            rng = random.Random(int(chaos.get('seed', 0)) * 7919
                                + self.worker_id)
            doom = time.time() + rng.expovariate(1.0 / chaos['kill_worker'])
        while True:
            if doom is not None and time.time() >= doom:
                print('chaos: worker %d self-destructing' % self.worker_id,
                      flush=True)
                os._exit(17)
            try:
                self._maybe_heartbeat()
                task = self._rpc(('args', None))
            except _CONN_ERRORS:
                self._gather_lost()
                return
            if task is None:
                return
            if task.get('role') == 'idle':
                # elastic fleet control: the learner is withholding fresh
                # tasks from this host (quarantined/draining) — nap and
                # re-ask instead of exiting, so the host stays warm for
                # re-admission
                telemetry.counter('worker_idle_tasks_total').inc()
                time.sleep(min(5.0, float(task.get('wait', 1.0))))
                continue
            produce, upload_as = self.playbook[task['role']]
            t0 = time.perf_counter()
            try:
                models = self.vault.obtain(dict(task.get('model_id', {})))
                payload = produce(models, task)
            except _CONN_ERRORS:       # model fetch rode the dead pipe
                self._gather_lost()
                return
            except Exception:
                traceback.print_exc()
                payload = None
                telemetry.counter('worker_task_failures_total').inc()
            telemetry.counter('worker_tasks_total',
                              role=task['role']).inc()
            telemetry.REGISTRY.histogram(
                'worker_task_seconds', role=task['role']).observe(
                    time.perf_counter() - t0)
            if isinstance(payload, dict) and payload.get('streamed'):
                # every window (final chunk included) already rode the
                # pipe mid-episode; there is no whole-episode upload
                continue
            try:
                self._rpc((upload_as, payload))
            except _CONN_ERRORS:
                self._gather_lost()
                return

    def _gather_lost(self):
        """The pipe to the gather died under us: leave a blackbox dump
        behind (the postmortem's evidence of WHICH side died first) and
        let the process exit — the gather supervisor owns respawns."""
        _LOG.warning('worker %d: lost its gather; exiting', self.worker_id)
        telemetry.record_event('guard', 'gather connection lost',
                               worker=self.worker_id)
        telemetry.dump_blackbox('gather-lost', worker=self.worker_id)


def open_worker(args, conn, wid):
    force_cpu_backend()
    Worker(args, conn, wid).run()


def _shard(total: int, parts: int, index: int) -> int:
    """Size of shard ``index`` when ``total`` items split across ``parts``."""
    return total // parts + (1 if index < total % parts else 0)


class UploadTrace:
    """Per-episode ``upload`` spans for the gather relay: payload stash
    time -> server ack. Only deterministically-sampled trace ids are
    tracked (the same keep/drop every other hop computes), bounded so a
    long outage cannot grow the book past the resend buffer's order."""

    MAX_PER_KIND = 512

    def __init__(self, gather_id: int):
        self.gather_id = int(gather_id)
        self._box: Dict[str, list] = defaultdict(list)

    def stash(self, kind: str, payload):
        if not telemetry.trace_enabled():
            return
        tid = telemetry.episode_trace_id((payload or {}).get('args') or {})
        if tid and telemetry.trace_sampled(tid):
            box = self._box[kind]
            if len(box) < self.MAX_PER_KIND:
                box.append((tid, time.time()))

    def shipped(self, kind: str):
        """The server acked this kind's batch: emit one span per tracked
        payload covering its whole stash->ack residence in the relay."""
        entries = self._box.pop(kind, None)
        if not entries:
            return
        now = time.time()
        for tid, t0 in entries:
            telemetry.trace_event('upload', ts=t0, dur=now - t0,
                                  trace_id=tid, kind=kind,
                                  gather=self.gather_id)
        telemetry.trace_flush()


class Gather:
    """Fan-in relay between ~16 workers and the learner.

    Amortizes server round-trips three ways: task assignments are prefetched
    in blocks, model snapshots are served from a per-id cache, and episode /
    result uploads are batched before shipping. State lives in three small
    stores; routing is a dispatch over the RPC kind.

    Fault tolerance (remote mode, i.e. ``reconnect`` given): every server
    RPC is supervised — a socket failure closes the connection, redials the
    data port with exponential backoff + jitter, and retries the same RPC,
    so batched ``_upload_box`` contents survive a severed link instead of
    dying with it (an RPC whose ack was lost is resent; the server's task
    ledger drops the duplicate). A daemon thread additionally sends one-way
    heartbeat frames carrying this relay's fleet stats, so the server's Hub
    can detach silently-dead peers and the learner can aggregate
    reconnect/drop counts per epoch.
    """

    def __init__(self, args: Dict[str, Any], server_conn, gather_id: int,
                 reconnect=None):
        _LOG.info('started gather %d', gather_id)
        telemetry.adopt_config(args)
        telemetry.set_process_label('gather-%d' % gather_id)
        telemetry.install_crash_dump()
        self.gather_id = gather_id
        self._upload_trace = UploadTrace(gather_id)
        gid = str(gather_id)
        self._m_uploads = {
            'episode': telemetry.counter('gather_uploads_total',
                                         gather=gid, kind='episode'),
            'result': telemetry.counter('gather_uploads_total',
                                        gather=gid, kind='result'),
            'chunk': telemetry.counter('gather_uploads_total',
                                       gather=gid, kind='chunk')}
        self._m_retries = telemetry.counter('gather_rpc_retries_total',
                                            gather=gid)
        self._m_reconnects = telemetry.counter('gather_reconnects_total',
                                               gather=gid)
        self._m_dropped = telemetry.counter('gather_dropped_uploads_total',
                                            gather=gid)
        self._m_box_depth = telemetry.gauge('gather_upload_box_depth',
                                            gather=gid)
        self._m_eps_rate = telemetry.gauge('gather_episodes_per_sec',
                                           gather=gid)
        ft = args.get('fault_tolerance') or {}
        self._reconnect_fn = reconnect
        self._rpc_timeout = float(ft.get('rpc_timeout', 120.0))
        self._hb_interval = float(ft.get('heartbeat_interval', 10.0))
        self._backoff_initial = float(ft.get('reconnect_initial_delay', 1.0))
        self._backoff_max = float(ft.get('reconnect_max_delay', 30.0))
        self._max_tries = int(ft.get('reconnect_max_tries', 30))
        self._resend_max = int(ft.get('resend_buffer', 256))
        # resume token stamped by a durable learner (train.py publishes it in
        # the merged entry config): presented on every redial so a RESTARTED
        # learner recognizes this gather and it rides through without a
        # respawn — an unrecognized run_id forces the cold path instead
        self._resume_token = dict(args.get('resume_token') or {})
        self.stats = {'reconnects': 0, 'dropped_uploads': 0, 'reattaches': 0}
        self._m_resend_dropped = telemetry.counter(
            'gather_resend_dropped_total', gather=gid)
        self._m_reattach = telemetry.counter('gather_reattach_total',
                                             gather=gid)
        if server_conn is None and reconnect is not None:
            server_conn = self._dial()   # child-side dial (respawn-friendly)
        self.server = server_conn
        if getattr(server_conn, 'sock', None) is not None:
            # a silently-dead server must fail the blocking recv, not hang it
            server_conn.sock.settimeout(self._rpc_timeout)
            if self._hb_interval > 0:
                threading.Thread(target=self._heartbeat_loop,
                                 name='gather-%d-heartbeat' % gather_id,
                                 daemon=True).start()

        n_total = args['worker']['num_parallel']
        n_relays = args['worker']['num_gathers']
        n_here = _shard(n_total, n_relays, gather_id)
        first_wid = args['worker'].get('base_worker_id', 0)

        def worker_args(i, child_conn):
            wid = first_wid + i * n_relays + gather_id
            return (args, child_conn, wid)

        self.hub = Hub(spawn_pipe_workers(n_here, open_worker, worker_args))

        self.block = 1 + n_here // 4          # round-trip amortization factor
        self.SNAP_SLOTS = 4                   # snapshots cached per relay
        self._task_stock: deque = deque()
        # shared with the engine thread's snapshot fetches (graftlint GL004)
        self._snap_cache: OrderedDict = OrderedDict()   # guarded-by: _rpc_lock
        self._upload_box: Dict[str, list] = defaultdict(list)
        self._upload_count = 0
        # the engine thread fetches snapshots through the same server link
        # as the main task loop: RPCs must not interleave on the wire
        self._rpc_lock = threading.RLock()

        self.engine: Optional[EngineSupervisor] = None
        srv = args.get('serving') or {}
        # remote mode engages on an explicit endpoint list OR a fleet
        # resolver (the EngineClient fetches the replica table itself)
        remote_endpoint = srv.get('endpoint') \
            or (srv.get('fleet') or {}).get('resolver')
        if (args.get('inference') or {}).get('enabled') and remote_endpoint:
            # remote-service mode (docs/serving.md): workers dial the
            # standalone InferenceService (or fleet) directly (EngineClient
            # owns the links + replica failover), so this relay spawns no
            # engine of its own — the 'model' RPC path stays available for
            # degraded workers
            _LOG.info('gather %d: inference routed to remote service %s; '
                      'no local engine', gather_id, remote_endpoint)
        elif (args.get('inference') or {}).get('enabled'):
            # per-host batched inference service: this relay alone pulls
            # model snapshots; its workers submit (mid, obs, hidden, legal)
            # frames and receive sampled actions back over the same pipes.
            # The supervisor watchdogs the engine thread (restart on
            # crash/stall, error fan-out so no reply is silently dropped);
            # replies ride the pipe as (INFER_KIND, reply) frames so the
            # worker's client can tell them from task-RPC answers.
            self.engine = EngineSupervisor(
                args, fetch_snapshot=self._snapshot,
                reply_fn=lambda ep, msg: self.hub.send(ep, (INFER_KIND, msg)),
                clients=n_here)

    def __del__(self):
        _LOG.info('finished gather %d', self.gather_id)

    # -- supervised server link --

    def _dial(self):
        return self._reconnect_fn()

    def _heartbeat_loop(self):
        """One-way liveness beacons, sent even while the main loop blocks
        inside a long RPC (e.g. the server is busy at an epoch boundary).
        FramedConnection.send serializes with the RPC path internally.

        Each beacon piggybacks this relay's telemetry: its own registry
        merged with the latest snapshot every worker child sent up its
        pipe, plus a freshly computed episodes/sec gauge — the learner
        aggregates these per-peer payloads into the fleet view."""
        last_n, last_t = 0, time.time()
        while True:
            time.sleep(self._hb_interval)
            now = time.time()
            n = self._m_uploads['episode'].value
            self._m_eps_rate.set((n - last_n) / max(now - last_t, 1e-9))
            last_n, last_t = n, now
            # the beacon thread starts before the worker hub is built; the
            # first beats may carry only the gather's own registry
            hub = getattr(self, 'hub', None)
            worker_snaps = [info.get('telemetry')
                            for info in (hub.peer_info_snapshot().values()
                                         if hub is not None else ())
                            if isinstance(info, dict)]
            # gather processes sample their own memory footprint too: a
            # leaking relay shows up in the fleet merge, not just workers
            telemetry.sample_device_memory()
            snap = telemetry.merge_snapshots(
                [telemetry.snapshot()] + worker_snaps)
            conn = self.server
            try:
                conn.send((HEARTBEAT_KIND,
                           {'gather': self.gather_id, **self.stats,
                            'telemetry': snap}))
            except Exception:
                pass   # the RPC path owns failure handling and reconnect
            telemetry.trace_flush()   # keep the shared trace file current

    def _recover(self, exc: Exception):
        """Redial the data port with exponential backoff + jitter (the
        ``entry()`` retry pattern, hardened)."""
        _LOG.warning('gather %d: server link lost (%s: %s); reconnecting',
                     self.gather_id, type(exc).__name__, str(exc)[:120])
        try:
            self.server.close()
        except Exception:
            pass
        backoff = Backoff(self._backoff_initial, self._backoff_max)
        last_err: Optional[Exception] = exc
        for _ in range(self._max_tries):
            time.sleep(backoff.next_delay())
            try:
                conn = self._dial()
            except OSError as e:
                last_err = e
                continue
            conn.sock.settimeout(self._rpc_timeout)
            if self._resume_token:
                # resume-token handshake (durable learner): prove membership
                # before committing the link. A RESTARTED learner with the
                # same run_id answers ok + its new generation — this gather
                # reattaches in place and its resend buffer replays as
                # ordinary duplicate-screened uploads. A different run_id
                # (or a reply this build cannot read) means the fleet we
                # belonged to is gone: fail hard so the supervisor
                # cold-respawns against the new run.
                try:
                    reply = send_recv(conn, (RESUME_KIND, dict(
                        self._resume_token, gather=self.gather_id)))
                except _CONN_ERRORS as e:
                    last_err = e
                    try:
                        conn.close()
                    except Exception:
                        pass
                    continue
                if not (isinstance(reply, dict) and reply.get('ok')):
                    try:
                        conn.close()
                    except Exception:
                        pass
                    raise ConnectionError(
                        'gather %d: learner rejected the resume token '
                        '(run over or replaced); cold respawn required'
                        % self.gather_id)
                gen = int(reply.get('generation',
                                    self._resume_token.get('generation', 0)))
                if gen != int(self._resume_token.get('generation', 0)):
                    # the learner restarted while we were severed: this
                    # redial is a zero-respawn reattach, not a mere blip
                    self._resume_token['generation'] = gen
                    self.stats['reattaches'] += 1
                    self._m_reattach.inc()
                    _LOG.warning(
                        'gather %d: reattached across a learner restart '
                        '(generation %d)', self.gather_id, gen)
            self.server = conn
            self.stats['reconnects'] += 1
            self._m_reconnects.inc()
            _LOG.warning('gather %d: reconnected to the server',
                         self.gather_id)
            return
        raise ConnectionError(
            'gather %d: could not re-reach the server after %d tries (%s)'
            % (self.gather_id, self._max_tries, last_err))

    def _server_rpc(self, msg):
        """send_recv with supervised reconnect; the in-flight request is
        resent on the fresh link (the server dedupes by task_id, so a
        request whose ack was lost cannot double-count). Serialized: the
        engine thread's snapshot fetches share this link with the main
        task loop, and two interleaved call-response pairs would cross
        their replies."""
        with self._rpc_lock:
            while True:
                try:
                    return send_recv(self.server, msg)
                except _CONN_ERRORS as exc:
                    if self._reconnect_fn is None:  # pipe mode: unrecoverable
                        raise
                    self._m_retries.inc()
                    self._recover(exc)

    # -- per-RPC handling --

    def _next_task(self):
        if not self._task_stock:
            self._task_stock.extend(
                self._server_rpc(('args', [None] * self.block)))
        return self._task_stock.popleft()

    def _snapshot(self, mid):
        """Per-id snapshot LRU: one epoch's params per entry, bounded — the
        epoch counter increments for the life of the run, so an unbounded
        map would leak a params-sized blob per update. Thread-safe: serves
        both worker 'model' RPCs (per-worker mode) and the inference
        engine's fetches (engine mode)."""
        with self._rpc_lock:
            if mid not in self._snap_cache:
                while len(self._snap_cache) >= self.SNAP_SLOTS:
                    self._snap_cache.popitem(last=False)
                self._snap_cache[mid] = self._server_rpc(('model', mid))
            self._snap_cache.move_to_end(mid)
            return self._snap_cache[mid]

    def _stash_upload(self, kind: str, payload):
        self._upload_box[kind].append(payload)
        self._upload_trace.stash(kind, payload)
        self._upload_count += 1
        if kind in self._m_uploads:
            self._m_uploads[kind].inc()
        while self._upload_count > self._resend_max:
            # bounded resend buffer: under a long outage, keep the newest
            # uploads and count the sacrifice instead of growing forever
            biggest = max(self._upload_box, key=lambda k: len(self._upload_box[k]))
            self._upload_box[biggest].pop(0)
            self._upload_count -= 1
            self.stats['dropped_uploads'] += 1
            self._m_dropped.inc()
            self._m_resend_dropped.inc()
            if self.stats['dropped_uploads'] == 1 \
                    or self.stats['dropped_uploads'] % 50 == 0:
                # loud, throttled: evicted uploads are PERMANENT episode
                # loss — the alert catalog watches the counter, this line
                # lands in the FlightRecorder ring for the post-mortem
                _LOG.warning(
                    'gather %d: resend buffer full (%d); dropped a %r '
                    'upload (%d dropped so far) — raise '
                    'fault_tolerance.resend_buffer or shorten outages',
                    self.gather_id, self._resend_max, biggest,
                    self.stats['dropped_uploads'])
        if self._upload_count >= self.block:
            for kind in list(self._upload_box):
                self._server_rpc((kind, self._upload_box[kind]))
                # acked: this kind's batch is safely booked server-side
                del self._upload_box[kind]
                self._upload_trace.shipped(kind)
            self._upload_count = sum(len(v) for v in self._upload_box.values())
        self._m_box_depth.set(self._upload_count)

    def run(self):
        while self.hub.count() > 0:
            try:
                ep, (kind, body) = self.hub.recv(timeout=0.3)
            except queue.Empty:
                continue
            if kind == 'args':
                self.hub.send(ep, self._next_task())
            elif kind == 'model':
                self.hub.send(ep, self._snapshot(body))
            elif kind == INFER_KIND:
                if self.engine is None:
                    self.hub.send(ep, (INFER_KIND,
                                       {'rid': (body or {}).get('rid'),
                                        'engine_fault': True,
                                        'error': 'inference engine disabled '
                                                 'on this host'}))
                else:
                    self.engine.submit(ep, body)
            else:
                self.hub.send(ep, None)       # ack now, ship in bulk later
                self._stash_upload(kind, body)
        self._flush_and_beacon()

    def _flush_and_beacon(self):
        """End of the relay's life (training over): ship the final partial
        upload block — it would otherwise die in the box — and beacon a
        last telemetry snapshot so the learner's fleet view includes
        this relay's complete engine/upload counters. The loop covers
        every stashed kind, streamed ``'chunk'`` windows included, so a
        budgeted run's tail chunks land instead of stranding mid-episode
        assemblies server-side."""
        for kind in list(self._upload_box):
            if self._upload_box[kind]:
                self._server_rpc((kind, self._upload_box[kind]))
                self._upload_trace.shipped(kind)
            del self._upload_box[kind]
        if self.engine is not None:
            self.engine.stop()
        try:
            self.server.send((HEARTBEAT_KIND,
                              {'gather': self.gather_id, **self.stats,
                               'telemetry': telemetry.snapshot()}))
        except Exception:
            pass   # the run is over; a dead link changes nothing


def resolve_generation_backend(args: Dict[str, Any]) -> str:
    """Which actor engine a gather host runs: 'worker' (per-worker
    inference), 'engine' (per-host InferenceEngine), or 'device' (fused
    on-device rollouts, DeviceActorGather). A per-host override
    (``worker_args.backend``, riding the entry handshake) wins over the
    training config's ``generation.backend``; with neither set, the
    presence of the inference block picks engine vs worker — exactly the
    pre-backend-knob behavior."""
    backend = str((args.get('worker') or {}).get('backend') or ''
                  ) or str((args.get('generation') or {}).get('backend')
                           or '')
    if not backend:
        backend = ('engine' if (args.get('inference') or {}).get('enabled')
                   else 'worker')
    return backend


def gather_claims_device(args: Dict[str, Any]) -> bool:
    """True when a gather host's rollout or inference engine runs on the
    host's accelerator instead of pinning itself to the CPU."""
    inf = args.get('inference') or {}
    return (resolve_generation_backend(args) == 'device'
            or bool(inf.get('enabled')
                    and str(inf.get('engine_backend', 'cpu')) == 'device'))


class DeviceActorGather(Gather):
    """A gather whose 'workers' are lanes of one fused device rollout.

    Reuses ALL of Gather's learner-side plumbing — the supervised server
    RPC with reconnect, the task-block prefetch, the snapshot LRU, the
    batched upload box with resend bounds, heartbeats — by initializing the
    parent with zero worker children and no inference engine. The run loop
    then pulls task blocks through ``_next_task`` and serves them with a
    :class:`~.device_generation.DeviceActorEngine`; tasks the compiled
    program cannot express fall back to a host Generator/Evaluator pair in
    this same process, so every assigned task is answered either way."""

    def __init__(self, args: Dict[str, Any], server_conn, gather_id: int,
                 reconnect=None):
        from .device_generation import DeviceActorEngine
        from .environment import make_jax_env
        doctored = dict(args)
        doctored['worker'] = dict(args['worker'], num_parallel=0)
        doctored['inference'] = dict(args.get('inference') or {},
                                     enabled=False)
        super().__init__(doctored, server_conn, gather_id,
                         reconnect=reconnect)
        gen = dict(args.get('generation') or {})
        n_envs = int(gen.get('device_actor_envs', 64))
        slots = int(gen.get('device_actor_slots', 2))
        self.block = max(1, n_envs // 4)      # task-prefetch granularity
        self.host_env = make_env(args['env'])
        self.host_env.reset()
        example_obs = self.host_env.observation(self.host_env.players()[0])
        self.vault = ModelVault(self._snapshot, example_obs,
                                capacity=slots + 2)
        self.device_engine = DeviceActorEngine(
            make_jax_env(args['env']), self.vault, self.host_env, args,
            n_envs=n_envs,
            chunk_steps=int(gen.get('device_actor_chunk_steps', 16)),
            slots=slots,
            record_mode=str(gen.get('device_actor_record', '') or ''),
            seed=int(args.get('seed', 0)) * 1009 + gather_id)
        if (args.get('streaming') or {}).get('enabled'):
            # streamed windows ride the same upload box as whole episodes
            # (resend buffer, reconnect replay and the clean-exit flush
            # all cover the 'chunk' kind)
            self.device_engine.emit = \
                lambda c: self._stash_upload('chunk', c)
        self._fallback_gen = Generator(self.host_env, args,
                                       namespace=gather_id)
        self._fallback_eval = Evaluator(self.host_env, args)
        self._m_deferred = telemetry.counter('device_actor_deferred_total')
        _LOG.info('gather %d: device actor backend (%d lanes, %d slots, '
                  '%s records)', gather_id, n_envs, slots,
                  self.device_engine.record_mode)

    def _collect_block(self):
        """Pull up to one lane-count of tasks; returns (tasks, stop)."""
        tasks = []
        while len(tasks) < self.device_engine.n_envs:
            task = self._next_task()
            if task is None:
                return tasks, True
            if task.get('role') == 'idle':
                if tasks:
                    return tasks, False   # serve the partial block now
                telemetry.counter('worker_idle_tasks_total').inc()
                time.sleep(min(5.0, float(task.get('wait', 1.0))))
                continue
            tasks.append(task)
        return tasks, False

    def _run_host(self, task):
        """Host fallback for a task the device program cannot express
        (unknown opponent, slot overflow, missing sample key). Same
        payload contract as a worker process; a crash costs one task."""
        self._m_deferred.inc()
        kind = 'result' if task.get('role') == 'e' else 'episode'
        try:
            models = self.vault.obtain(dict(task.get('model_id', {})))
            with telemetry.expected_compile('device-actor host fallback'):
                if task.get('role') == 'e':
                    payload = self._fallback_eval.execute(models, task)
                else:
                    payload = self._fallback_gen.execute(models, task)
        except Exception:
            traceback.print_exc()
            payload = None
            telemetry.counter('worker_task_failures_total').inc()
        self._stash_upload(kind, payload)

    def run(self):
        while True:
            tasks, stop = self._collect_block()
            if tasks:
                uploads, deferred = self.device_engine.run_block(tasks)
                for kind, payload in uploads:
                    self._stash_upload(kind, payload)
                for task in deferred:
                    self._run_host(task)
            if stop:
                break
        self._flush_and_beacon()


def gather_loop(args, conn, gather_id, server_address=None):
    from .environment import make_jax_env
    backend = resolve_generation_backend(args)
    inf = args.get('inference') or {}
    if gather_claims_device(args):
        # the rollout/inference engine is the ONE process on this host
        # allowed to claim a local accelerator; it says which backend jax
        # gave it (the CPU, on a host without one). Workers stay CPU-pinned
        # either way.
        from . import claim_devices, setup_compile_cache
        setup_compile_cache()
        claim_devices('gather-%d' % gather_id)
    else:
        force_cpu_backend()
    reconnect = None
    if server_address:
        def reconnect():
            return connect_socket_connection(server_address,
                                             WorkerServer.WORKER_PORT)
    if backend == 'device':
        if make_jax_env(args['env']) is not None:
            DeviceActorGather(args, conn, gather_id,
                              reconnect=reconnect).run()
            return
        raise ValueError(
            'gather %d: generation backend "device" needs an env with a '
            'pure-JAX twin; %r has none'
            % (gather_id, (args.get('env') or {}).get('env')))
    if backend == 'worker' and inf.get('enabled'):
        # per-host override demoted this gather to plain workers: they
        # must materialize their own params instead of dialing an engine
        args = dict(args, inference=dict(inf, enabled=False))
    elif backend == 'engine' and not inf.get('enabled'):
        args = dict(args, inference=dict(inf, enabled=True))
    Gather(args, conn, gather_id, reconnect=reconnect).run()


def default_num_gathers(num_parallel: int) -> int:
    return 1 + max(0, num_parallel - 1) // 16


class WorkerCluster:
    """Local mode: gather processes over spawned pipes, one hub in the
    learner. ``recv``/``send``/``connection_count`` delegate to the hub —
    the learner's server loop is transport-agnostic."""

    def __init__(self, args: Dict[str, Any]):
        self.args = args
        self.hub = Hub()
        ft = args.get('fault_tolerance') or {}
        self.hub.LIVENESS_TIMEOUT = float(
            ft.get('liveness_timeout', Hub.LIVENESS_TIMEOUT))

    def connection_count(self) -> int:
        return self.hub.count()

    def recv(self, timeout: Optional[float] = None):
        return self.hub.recv(timeout=timeout)

    def send(self, conn, data):
        self.hub.send(conn, data)

    # fleet observability, consumed by the learner's ledger + epoch stats
    def hub_stats(self) -> Dict[str, int]:
        return self.hub.stats_snapshot()

    def peer_info(self) -> Dict[Any, Any]:
        return self.hub.peer_info_snapshot()

    def drain_detach_events(self):
        return self.hub.drain_detach_events()

    def run(self):
        wargs = self.args['worker']
        wargs.setdefault('num_gathers',
                         default_num_gathers(wargs['num_parallel']))
        for ep in spawn_pipe_workers(
                wargs['num_gathers'], gather_loop,
                lambda i, c: (self.args, c, i)):
            self.hub.attach(ep)


class WorkerServer(WorkerCluster):
    """Remote mode, learner side. Two listener threads: the entry port
    hands each arriving host its base_worker_id plus the merged config;
    the data port feeds accepted sockets straight into the hub. Hosts may
    join or leave at any time, mid-training."""

    ENTRY_PORT = ENTRY_PORT
    WORKER_PORT = DATA_PORT

    def __init__(self, args: Dict[str, Any]):
        super().__init__(args)
        self._next_base_wid = 0

    def _entry_loop(self):
        _LOG.info('started entry server %d', self.ENTRY_PORT)
        for conn in accept_socket_connections(port=self.ENTRY_PORT):
            host_args = conn.recv()
            _LOG.info('accepted connection from %s!', host_args['address'])
            host_args['base_worker_id'] = self._next_base_wid
            self._next_base_wid += host_args['num_parallel']
            merged = dict(self.args)
            merged['worker'] = host_args
            conn.send(merged)
            conn.close()

    def _data_loop(self):
        _LOG.info('started worker server %d', self.WORKER_PORT)
        for conn in accept_socket_connections(port=self.WORKER_PORT):
            self.hub.attach(conn)

    def run(self):
        for loop in (self._entry_loop, self._data_loop):
            threading.Thread(target=loop, name=loop.__name__.strip('_'),
                             daemon=True).start()


def entry(worker_args, retries: int = 30, delay: float = 2.0):
    """Entry handshake with retry: the learner may still be starting (jax
    import + bind) when a worker host comes up. Retries back off with
    jitter so a whole fleet booting at once does not hammer in lockstep."""
    last_err: Optional[Exception] = None
    port = WorkerServer.ENTRY_PORT
    backoff = Backoff(delay, maximum=4 * delay)
    for _ in range(retries):
        try:
            conn = connect_socket_connection(
                worker_args['server_address'], port)
            try:
                conn.send(worker_args)
                return conn.recv()
            finally:
                conn.close()
        except (OSError, ConnectionResetError) as e:
            last_err = e
            time.sleep(backoff.next_delay())
    raise ConnectionError('could not reach training server at %s:%d (%s)'
                          % (worker_args['server_address'], port, last_err))


class RemoteWorkerCluster:
    """Remote mode, worker-host side: entry handshake, then one data socket
    per gather, each driven by its own spawned process — plus a supervisor
    that respawns crashed gathers (with per-slot backoff) instead of
    sleeping forever next to a shrinking fleet. A gather that exits cleanly
    (exit code 0: the server handed out a None task, training is over) is
    retired, so the host process itself terminates when the run ends.

    ``HANDYRL_TPU_CHAOS=kill_gather=<mean s>[,max_kills=N][,seed=S]`` arms
    a fault injector that SIGKILLs random gather children on an exponential
    clock — the chaos tests (and soak runs) prove the supervisor + task
    ledger recover."""

    RESPAWN_RESET_AFTER = 60.0   # gather alive this long => backoff resets

    def __init__(self, args: Dict[str, Any]):
        args['address'] = gethostname()
        args.setdefault('num_gathers',
                        default_num_gathers(args['num_parallel']))
        self.args = args

    def run(self):
        merged = entry(self.args)
        telemetry.adopt_config(merged)
        telemetry.set_process_label('worker-host')
        telemetry.install_crash_dump()
        _LOG.info('joined run %s as %s (base_worker_id %s, %s gathers)',
                  merged.get('run_id', '?'), self.args['address'],
                  merged['worker'].get('base_worker_id'),
                  self.args['num_gathers'])
        _LOG.debug('merged config: %r', merged)
        prepare_env(merged['env'])

        ctx = mp.get_context('spawn')
        address = self.args['server_address']
        ft = merged.get('fault_tolerance') or {}
        max_fails = int(ft.get('reconnect_max_tries', 30))

        chaos = parse_chaos()
        rng = random.Random(int(chaos.get('seed', 0)))
        kills_left = int(chaos.get('max_kills', 1 << 30))
        next_kill = None
        if chaos.get('kill_gather'):
            next_kill = time.time() + rng.expovariate(
                1.0 / chaos['kill_gather'])

        def spawn(i):
            # the gather dials the data port itself: respawns need no
            # parent-held socket, and a half-dead link is its own problem
            proc = ctx.Process(target=gather_loop,
                               args=(merged, None, i, address))
            proc.start()
            return proc

        n = self.args['num_gathers']
        children = {i: spawn(i) for i in range(n)}
        started_at = {i: time.time() for i in children}
        backoffs = {i: Backoff(float(ft.get('reconnect_initial_delay', 1.0)),
                               float(ft.get('reconnect_max_delay', 30.0)))
                    for i in children}
        fails = {i: 0 for i in children}
        try:
            while children:
                time.sleep(0.5)
                now = time.time()
                if next_kill is not None and now >= next_kill:
                    if kills_left > 0:
                        live = [i for i, p in children.items() if p.is_alive()]
                        if live:
                            victim = rng.choice(live)
                            print('chaos: killing gather %d' % victim,
                                  flush=True)
                            children[victim].kill()
                            kills_left -= 1
                    next_kill = now + rng.expovariate(
                        1.0 / chaos['kill_gather'])
                for i, proc in list(children.items()):
                    if proc.is_alive():
                        if (fails[i] and
                                now - started_at[i] > self.RESPAWN_RESET_AFTER):
                            fails[i] = 0
                            backoffs[i].reset()
                        continue
                    if proc.exitcode == 0:
                        del children[i]   # clean exit: training ended
                        continue
                    fails[i] += 1
                    if fails[i] > max_fails:
                        # likely the server is gone for good — stop churning
                        _LOG.error('gather %d: giving up after %d failed '
                                   'respawns', i, fails[i] - 1)
                        del children[i]
                        continue
                    delay = backoffs[i].next_delay()
                    _LOG.warning('gather %d died (exit %s); respawning '
                                 'in %.1fs', i, proc.exitcode, delay)
                    # supervisor death declaration: the gather itself had
                    # no chance to dump (SIGKILL), so the host supervisor
                    # records the evidence for the postmortem
                    telemetry.record_event(
                        'supervisor', 'gather %d died' % i,
                        exitcode=proc.exitcode, respawn_in=round(delay, 2))
                    telemetry.dump_blackbox('gather-death', gather=i,
                                            exitcode=proc.exitcode)
                    time.sleep(delay)
                    children[i] = spawn(i)
                    started_at[i] = time.time()
        finally:
            for proc in children.values():
                if proc.is_alive():
                    proc.terminate()


def worker_main(args, argv):
    # This host process only supervises: it creates no jax array, and it
    # leaves JAX_PLATFORMS as the operator set it. Every gather pins itself
    # to the CPU (gather_loop) except a 'device' gather, which must inherit
    # the operator's platform to reach this host's accelerator.
    worker_args = args['worker_args']
    if len(argv) >= 1:
        worker_args['num_parallel'] = int(argv[0])
    RemoteWorkerCluster(args=worker_args).run()
