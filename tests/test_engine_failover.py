"""Self-healing inference tier: engine supervision (crash/stall restart,
error fan-out, bounded queue shedding), worker-side request deadlines with
circuit-breaker failover to the per-worker path (byte-identical records),
the learner's elastic fleet controller, and the chaos end-to-end proving a
real TCP fleet survives injected engine kills and stalls.

The coalescing/parity behavior of a HEALTHY engine is pinned in
tests/test_inference_engine.py; this module is about what happens when the
engine is anything but.
"""

import json
import os
import pickle
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from collections import deque

import numpy as np
import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.connection import (FramedConnection, INFER_KIND,
                                    connect_socket_connection, is_infer)
from handyrl_tpu.environment import make_env
from handyrl_tpu.fault import (FleetController, TaskLedger, parse_chaos,
                               HOST_DEGRADED, HOST_DRAINING, HOST_HEALTHY,
                               HOST_QUARANTINED)
from handyrl_tpu.generation import Generator, model_act, sample_seed
from handyrl_tpu.inference import (EngineClient, EngineSupervisor,
                                   InferenceEngine, RemoteModel,
                                   RemoteModelCache)
from handyrl_tpu.model import ModelWrapper

GEN_ARGS = {'observation': False, 'gamma': 0.8, 'compress_steps': 4,
            'seed': 11}


def _ttt_wrapper(seed=7):
    env = make_env({'env': 'TicTacToe'})
    env.reset()
    w = ModelWrapper(env.net(), seed=seed)
    w.ensure_params(env.observation(0))
    return env, w


def _counter_value(name, **labels):
    return telemetry.REGISTRY.counter(name, **labels).value


def _wait_for(predicate, timeout, poll=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


# ---------------------------------------------------------------------------
# ChaosProxy stall mode (satellite): accept frames, never reply


def test_chaos_proxy_stall_mode_is_one_way():
    from tests.proxy import ChaosProxy
    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(('127.0.0.1', 0))
    lsock.listen(4)
    received, replies_sent = [], []

    def echo_server():
        while True:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return
            try:
                data = conn.recv(1 << 12)
                received.append(data)
                conn.sendall(b'reply:' + data)
                replies_sent.append(data)
            except OSError:
                pass

    threading.Thread(target=echo_server, daemon=True).start()
    proxy = ChaosProxy(target_port=lsock.getsockname()[1])
    try:
        proxy.stall = True
        client = socket.create_connection(('127.0.0.1', proxy.port),
                                          timeout=5)
        client.sendall(b'ping')
        # the REQUEST gets through (unlike blackhole) ...
        assert _wait_for(lambda: received == [b'ping'], 5)
        assert _wait_for(lambda: replies_sent == [b'ping'], 5)
        # ... but the reply never comes back
        client.settimeout(0.5)
        with pytest.raises(socket.timeout):
            client.recv(1 << 12)
        client.close()

        proxy.stall = False            # healthy again: full round trip
        client2 = socket.create_connection(('127.0.0.1', proxy.port),
                                           timeout=5)
        client2.sendall(b'pong')
        client2.settimeout(5)
        assert client2.recv(1 << 12) == b'reply:pong'
        client2.close()
    finally:
        proxy.close()
        lsock.close()


def test_parse_chaos_engine_knobs():
    spec = 'enginekill=4,enginestall=6,enginestall_secs=600,engine_max_faults=2'
    assert parse_chaos(spec) == {'enginekill': 4.0, 'enginestall': 6.0,
                                 'enginestall_secs': 600.0,
                                 'engine_max_faults': 2.0}


# ---------------------------------------------------------------------------
# engine hardening: bounded queue, crash fan-out, stall watchdog, stop leak


class _Endpoint:
    """Bare reply sink used when driving engines/supervisors directly."""

    def __init__(self):
        self.replies: queue.Queue = queue.Queue()


def _act_request(rid, obs, mid=1):
    return {'rid': rid, 'mid': mid, 'obs': obs, 'hidden': None,
            'legal': [0, 1, 2], 'seed': sample_seed(11, (0, rid), 0)}


def test_engine_bounded_queue_sheds_with_error_reply():
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    args = {'inference': {'enabled': True, 'queue_max': 2},
            'env': {'env': 'TicTacToe'}}
    engine = InferenceEngine(args, fetch_snapshot=lambda mid: w.snapshot(),
                             reply_fn=lambda ep, msg: ep.replies.put(msg),
                             clients=1, example_obs=obs)
    # NOT started: the queue cannot drain, so the bound is deterministic
    shed_before = _counter_value('engine_shed_total')
    ep = _Endpoint()
    for rid in range(3):
        engine.submit(ep, _act_request(rid, obs))
    assert len(engine._queue) == 2            # bound held
    reply = ep.replies.get(timeout=5)          # the third was shed, loudly
    assert reply['rid'] == 2 and reply.get('engine_fault')
    assert 'shed' in reply['error']
    assert _counter_value('engine_shed_total') == shed_before + 1


def _supervisor_for(w, obs, chaos, stall_timeout=0.5, queue_max=64):
    args = {'inference': {'enabled': True, 'batch_wait_ms': 1.0,
                          'stall_timeout': stall_timeout,
                          'restart_max_delay': 1.0, 'queue_max': queue_max},
            'env': {'env': 'TicTacToe'}}
    return EngineSupervisor(
        args, fetch_snapshot=lambda mid: w.snapshot(),
        reply_fn=lambda ep, msg: ep.replies.put(msg),
        clients=1, example_obs=obs, chaos=chaos)


@pytest.mark.timeout(120)
def test_supervisor_restarts_crashed_engine_with_error_fanout():
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    crashes_before = _counter_value('engine_restarts_total', reason='crash')
    sup = _supervisor_for(w, obs,
                          chaos={'enginekill': 1e-4, 'engine_max_faults': 1})
    try:
        ep = _Endpoint()
        sup.submit(ep, _act_request(1, obs))
        # the injected kill fires on the first tick: the in-flight request
        # is error-answered by the crash fan-out, not silently dropped
        # (the waits are generous: the test failed once under the whole
        # suite's six workers on a shared machine and passes alone in
        # seconds; its own limit is 120)
        reply = ep.replies.get(timeout=30)
        assert reply['rid'] == 1 and 'crashed' in reply['error']
        # wait for the DECLARED restart, not just a live engine thread —
        # the crashed engine's thread lingers in its crash handler for a
        # beat, so thread_alive() alone passes before the watchdog's first
        # tick and reads restarts too early
        assert _wait_for(
            lambda: (sup.restarts >= 1 and sup.engine is not None
                     and sup.engine.thread_alive()), 40)
        assert sup.restarts == 1
        assert (_counter_value('engine_restarts_total', reason='crash')
                == crashes_before + 1)
        sup.submit(ep, _act_request(2, obs))   # restarted engine serves
        reply = ep.replies.get(timeout=30)
        assert reply['rid'] == 2 and reply['action'] in (0, 1, 2)
    finally:
        sup.stop()


@pytest.mark.timeout(120)
def test_supervisor_detects_stall_and_restarts():
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    stalls_before = _counter_value('engine_restarts_total', reason='stall')
    sup = _supervisor_for(w, obs,
                          chaos={'enginestall': 1e-4, 'engine_max_faults': 1,
                                 'enginestall_secs': 120})
    try:
        ep = _Endpoint()
        sup.submit(ep, _act_request(1, obs))
        # the engine wedges holding the request; the watchdog declares the
        # stall, error-answers what the zombie holds, and restarts
        reply = ep.replies.get(timeout=15)
        assert reply['rid'] == 1 and 'stall' in reply['error']
        assert _wait_for(
            lambda: sup.engine is not None and sup.engine.thread_alive(), 15)
        assert (_counter_value('engine_restarts_total', reason='stall')
                == stalls_before + 1)
        sup.submit(ep, _act_request(2, obs))
        reply = ep.replies.get(timeout=10)
        assert reply['rid'] == 2 and reply['action'] in (0, 1, 2)
    finally:
        sup.stop()


@pytest.mark.timeout(120)
def test_stalled_snapshot_fetch_detected_via_chaos_proxy():
    """Deterministic stall via the ChaosProxy stall mode: the engine's
    snapshot fetch crosses a stalled TCP link (request accepted, reply
    never comes) — the engine wedges inside _serve, the watchdog restarts
    it, and once the link heals the restarted engine serves."""
    from tests.proxy import ChaosProxy
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    snap = w.snapshot()

    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(('127.0.0.1', 0))
    lsock.listen(8)

    def snapshot_server():
        while True:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return

            def serve_one(fc):
                try:
                    while True:            # hold the connection open: a
                        fc.recv()          # stalled service looks alive
                        fc.send(snap)
                except Exception:
                    pass
            threading.Thread(target=serve_one,
                             args=(FramedConnection(conn),),
                             daemon=True).start()

    threading.Thread(target=snapshot_server, daemon=True).start()
    proxy = ChaosProxy(target_port=lsock.getsockname()[1])
    proxy.stall = True

    def fetch(mid):
        conn = connect_socket_connection('127.0.0.1', proxy.port)
        try:
            conn.send(int(mid))
            return conn.recv()
        finally:
            conn.close()

    args = {'inference': {'enabled': True, 'batch_wait_ms': 1.0,
                          'stall_timeout': 0.5, 'restart_max_delay': 1.0},
            'env': {'env': 'TicTacToe'}}
    sup = EngineSupervisor(args, fetch_snapshot=fetch,
                           reply_fn=lambda ep, msg: ep.replies.put(msg),
                           clients=1, example_obs=obs, chaos={})
    try:
        ep = _Endpoint()
        sup.submit(ep, _act_request(1, obs))
        reply = ep.replies.get(timeout=20)     # stall detected + fanned out
        assert reply['rid'] == 1 and 'stall' in reply['error']
        assert _wait_for(lambda: sup.restarts >= 1, 15)
        proxy.stall = False                    # link heals
        assert _wait_for(
            lambda: sup.engine is not None and sup.engine.thread_alive(), 15)
        sup.submit(ep, _act_request(2, obs))
        reply = ep.replies.get(timeout=20)
        assert reply['rid'] == 2 and reply['action'] in (0, 1, 2)
    finally:
        sup.stop()
        proxy.close()
        lsock.close()


@pytest.mark.timeout(60)
def test_engine_stop_leak_is_visible():
    """stop() on a wedged engine cannot join the thread — that must be a
    logged warning plus an engine_stop_leaked_total increment, not a silent
    return (satellite)."""
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    args = {'inference': {'enabled': True, 'batch_wait_ms': 1.0},
            'env': {'env': 'TicTacToe'}}
    engine = InferenceEngine(args, fetch_snapshot=lambda mid: w.snapshot(),
                             reply_fn=lambda ep, msg: ep.replies.put(msg),
                             clients=1, example_obs=obs)
    engine.arm_fault('stall', 0.0, stall_secs=60)
    engine.start()
    ep = _Endpoint()
    engine.submit(ep, _act_request(1, obs))
    assert _wait_for(lambda: engine.busy() and engine.progress_age() > 0.3,
                     10)
    leaked_before = _counter_value('engine_stop_leaked_total')
    engine.stop(timeout=0.3)
    assert engine.thread_alive()               # really is wedged
    assert _counter_value('engine_stop_leaked_total') == leaked_before + 1


# ---------------------------------------------------------------------------
# worker-side client: deadline -> degrade -> probe -> re-promote, byte-exact


class _FakeGatherPipe:
    """Worker-side view of a gather pipe: INFER frames route into a real
    engine when healthy (or vanish when ``drop_infer`` — a dead/stalled
    engine whose replies never come), and the 'model' RPC serves snapshots
    like the real relay does — which is exactly what the degraded local
    path fetches through."""

    def __init__(self, engine, snapshots):
        self.engine = engine
        self.snapshots = snapshots
        self.drop_infer = False
        self.drop_after = None          # drop infer frames after N submits
        self.drop_until = None          # ... up to frame N (None = forever)
        self.infer_sent = 0
        self.model_fetches = 0
        self.replies: queue.Queue = queue.Queue()
        self._peeked: deque = deque()
        self._rpc_replies: deque = deque()

    def send(self, msg):
        if is_infer(msg):
            self.infer_sent += 1
            dropped = self.drop_infer or (
                self.drop_after is not None
                and self.infer_sent > self.drop_after
                and (self.drop_until is None
                     or self.infer_sent <= self.drop_until))
            if not dropped and self.engine is not None:
                self.engine.submit(self, pickle.loads(pickle.dumps(msg[1])))
            return
        kind, body = msg
        assert kind == 'model', 'unexpected worker RPC %r' % (kind,)
        self.model_fetches += 1
        self._rpc_replies.append(pickle.loads(pickle.dumps(
            self.snapshots[body])))

    def poll(self, timeout=0.0):
        if self._peeked:
            return True
        try:
            self._peeked.append(self.replies.get(timeout=max(timeout, 1e-4)))
        except queue.Empty:
            return False
        return True

    def recv(self):
        if self._peeked:
            return (INFER_KIND,
                    pickle.loads(pickle.dumps(self._peeked.popleft())))
        if not self.replies.empty():
            return (INFER_KIND,
                    pickle.loads(pickle.dumps(self.replies.get())))
        if self._rpc_replies:
            return self._rpc_replies.popleft()
        return (INFER_KIND, pickle.loads(pickle.dumps(
            self.replies.get(timeout=30))))


def _engine_and_pipe(snap, obs, **inf):
    args = {'inference': {'enabled': True, 'batch_wait_ms': 1.0, **inf},
            'env': {'env': 'TicTacToe'}}
    engine = InferenceEngine(
        args, fetch_snapshot=lambda mid: snap,
        reply_fn=lambda ep, msg: ep.replies.put(msg),
        clients=1, example_obs=obs).start()
    pipe = _FakeGatherPipe(engine, {1: snap})
    client = EngineClient(pipe, args)
    return engine, pipe, client


@pytest.mark.timeout(120)
def test_client_deadline_failover_is_bitwise_identical():
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    snap = w.snapshot()
    failovers_before = _counter_value('worker_engine_failovers_total')
    engine, pipe, client = _engine_and_pipe(
        snap, obs, request_timeout=0.2, request_retries=1,
        reprobe_initial_delay=30.0)
    try:
        remote = RemoteModel(client, 1)
        legal = env.legal_actions(0)
        seed_seq = sample_seed(11, (0, 3), 0)
        res_engine = remote.act(obs, None, legal, seed_seq)   # healthy
        assert client.engine_ok

        pipe.drop_infer = True        # engine "dies": replies never arrive
        t0 = time.monotonic()
        res_degraded = remote.act(obs, None, legal, seed_seq)
        waited = time.monotonic() - t0
        assert waited >= 0.4          # deadline + one bounded retry
        assert not client.engine_ok   # circuit opened
        assert pipe.model_fetches >= 1   # snapshot came over the model RPC
        assert (_counter_value('worker_engine_failovers_total')
                == failovers_before + 1)
        # lossless: the degraded reply is bit-identical to the engine's AND
        # to the plain per-worker path on the same inputs
        local = model_act(ModelWrapper.from_snapshot(snap, obs), obs,
                          None, legal, seed_seq)
        for res in (res_engine, res_degraded):
            assert res['action'] == local['action']
            assert res['prob'] == local['prob']
            np.testing.assert_array_equal(res['action_mask'],
                                          local['action_mask'])
            np.testing.assert_array_equal(res['value'], local['value'])
        # while degraded, requests are served locally, instantly
        t0 = time.monotonic()
        remote.act(obs, None, legal, sample_seed(11, (0, 3), 1))
        assert time.monotonic() - t0 < 0.2
    finally:
        engine.stop()


@pytest.mark.timeout(120)
def test_client_reprobes_and_repromotes():
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    snap = w.snapshot()
    repromotes_before = _counter_value('worker_engine_repromotions_total')
    engine, pipe, client = _engine_and_pipe(
        snap, obs, request_timeout=0.2, request_retries=0,
        reprobe_initial_delay=0.2, reprobe_max_delay=0.5)
    try:
        remote = RemoteModel(client, 1)
        legal = env.legal_actions(0)
        pipe.drop_infer = True
        remote.act(obs, None, legal, sample_seed(11, (0, 1), 0))
        assert not client.engine_ok
        # still down at probe time: the probe fails and backs off again
        time.sleep(0.3)
        remote.act(obs, None, legal, sample_seed(11, (0, 1), 1))
        assert not client.engine_ok
        pipe.drop_infer = False       # engine healed
        assert _wait_for(
            lambda: (remote.act(obs, None, legal,
                                sample_seed(11, (0, 1), 2)) or True)
            and client.engine_ok, 10, poll=0.2)
        assert (_counter_value('worker_engine_repromotions_total')
                == repromotes_before + 1)
    finally:
        engine.stop()


@pytest.mark.timeout(300)
def test_engine_killed_mid_episode_record_byte_identical():
    """Satellite: kill the engine mid-episode on a fixed seed — the worker
    degrades to the per-worker path, FINISHES the episode, and the record
    is byte-identical to an uninterrupted engine run (and to the plain
    local path)."""
    from handyrl_tpu.connection import pack
    env, w = _ttt_wrapper()
    obs = env.observation(0)
    snap = w.snapshot()
    task = {'role': 'g', 'player': [0, 1], 'model_id': {0: 1, 1: 1},
            'sample_key': 5}

    def reference_episode(sample_key):
        e = make_env({'env': 'TicTacToe'})
        g = Generator(e, GEN_ARGS, namespace=0)
        m = ModelWrapper.from_snapshot(snap, obs)
        return g.generate({0: m, 1: m}, dict(task, sample_key=sample_key))

    def engine_episode(sample_key, drop_after=None, drop_until=None,
                       reprobe=30.0):
        engine, pipe, client = _engine_and_pipe(
            snap, obs, request_timeout=0.2, request_retries=0,
            reprobe_initial_delay=reprobe, reprobe_max_delay=reprobe)
        try:
            pipe.drop_after = drop_after
            pipe.drop_until = drop_until
            e = make_env({'env': 'TicTacToe'})
            g = Generator(e, GEN_ARGS, namespace=9)
            models = RemoteModelCache(client).obtain({0: 1, 1: 1})
            episode = g.generate(models, dict(task, sample_key=sample_key))
            return episode, client
        finally:
            engine.stop()

    ref = reference_episode(5)
    uninterrupted, _ = engine_episode(5)
    assert pack(ref) == pack(uninterrupted)

    # kill after the 3rd inference request: mid-episode degradation
    degraded, client = engine_episode(5, drop_after=3)
    assert not client.engine_ok, 'the mid-episode failover never happened'
    assert pack(ref) == pack(degraded)

    # and a degrade -> re-promote cycle WITHIN one episode is lossless too:
    # exactly frame 4 is lost, the probe (due immediately) heals on the
    # next ply, and the rest of the episode runs back on the engine
    cycled, client = engine_episode(5, drop_after=3, drop_until=4,
                                    reprobe=1e-6)
    assert client.engine_ok, 'the mid-episode re-promotion never happened'
    assert pack(ref) == pack(cycled)


# ---------------------------------------------------------------------------
# ledger stranding attribution + fleet controller


class _Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


def test_ledger_stranding_events_attribute_endpoints():
    clock = _Clock()
    ledger = TaskLedger(deadline=10.0, clock=clock)
    ledger.assign('ep-a', {'role': 'g', 'model_id': {}})
    ledger.assign('ep-a', {'role': 'g', 'model_id': {}})
    ledger.assign('ep-b', {'role': 'e', 'model_id': {}})
    assert ledger.outstanding_by_endpoint() == {'ep-a': 2, 'ep-b': 1}
    ledger.fail_endpoint('ep-a')
    clock.now += 11.0
    ledger.reap()
    events = ledger.drain_stranding_events()
    assert [(ep, reason) for ep, reason, _t in events] == [
        ('ep-a', 'detach'), ('ep-a', 'detach'), ('ep-b', 'deadline')]
    assert ledger.drain_stranding_events() == []   # journal is consumed
    assert ledger.outstanding_by_endpoint() == {}


def test_fleet_controller_degrade_and_recover():
    clock = _Clock()
    fleet = FleetController(degrade_after=2, quarantine_after=5,
                            health_window=60.0, quarantine_period=30.0,
                            clock=clock)
    fleet.observe('host-a')
    assert fleet.state('host-a') == HOST_HEALTHY and fleet.admits('host-a')
    fleet.record_soft_fault('host-a')
    assert fleet.state('host-a') == HOST_HEALTHY    # below degrade_after
    fleet.record_soft_fault('host-a')
    assert fleet.state('host-a') == HOST_DEGRADED
    assert fleet.admits('host-a')                   # degraded still works
    clock.now += 61.0                               # quiet window passes
    fleet.tick({})
    assert fleet.state('host-a') == HOST_HEALTHY
    trans = [(h, a, b) for h, a, b, _t in fleet.drain_transitions()]
    assert trans == [('host-a', HOST_HEALTHY, HOST_DEGRADED),
                     ('host-a', HOST_DEGRADED, HOST_HEALTHY)]


def test_fleet_controller_drain_quarantine_readmit_cycle():
    clock = _Clock()
    fleet = FleetController(degrade_after=1, quarantine_after=3,
                            health_window=60.0, quarantine_period=30.0,
                            clock=clock)
    for _ in range(3):                 # flapping: repeated strandings
        fleet.record_stranding('host-a')
    assert fleet.state('host-a') == HOST_DRAINING
    assert not fleet.admits('host-a')  # no fresh tasks while draining
    fleet.tick({'host-a': 2})          # booked work still outstanding
    assert fleet.state('host-a') == HOST_DRAINING
    fleet.tick({'host-a': 0})          # drained -> quarantine clock starts
    assert fleet.state('host-a') == HOST_QUARANTINED
    assert not fleet.admits('host-a')
    clock.now += 29.0
    fleet.tick({})
    assert fleet.state('host-a') == HOST_QUARANTINED   # not yet
    clock.now += 2.0
    fleet.tick({})
    assert fleet.state('host-a') == HOST_HEALTHY       # re-admitted
    assert fleet.admits('host-a')
    assert fleet.stats['quarantined'] == 1
    assert fleet.stats['readmitted'] == 1
    # history cleared on re-admission: one more stranding only degrades
    fleet.record_stranding('host-a')
    assert fleet.state('host-a') == HOST_DEGRADED
    counts = fleet.counts()
    assert counts['degraded'] == 1 and counts['healthy'] == 0


def test_fleet_controller_state_codes_cover_all_states():
    assert set(telemetry.HOST_STATE_CODES) == {
        HOST_HEALTHY, HOST_DEGRADED, HOST_DRAINING, HOST_QUARANTINED}
    # severity-monotone: alerting on >= 2 means "not receiving work"
    assert (telemetry.HOST_STATE_CODES[HOST_HEALTHY]
            < telemetry.HOST_STATE_CODES[HOST_DEGRADED]
            < telemetry.HOST_STATE_CODES[HOST_DRAINING]
            < telemetry.HOST_STATE_CODES[HOST_QUARANTINED])


def test_worker_idle_task_naps_and_reasks():
    from handyrl_tpu.worker import Worker
    from handyrl_tpu.config import apply_defaults
    args = apply_defaults({'env_args': {'env': 'TicTacToe'}})['train_args']
    args['env'] = {'env': 'TicTacToe'}

    class _ScriptedConn:
        """Replies: one idle placeholder, then the shutdown None."""

        def __init__(self):
            self.sent = []
            self._replies = deque([{'role': 'idle', 'wait': 0.01}, None])

        def send(self, msg):
            self.sent.append(msg)

        def recv(self):
            return self._replies.popleft()

    conn = _ScriptedConn()
    idle_before = _counter_value('worker_idle_tasks_total')
    Worker(args, conn, wid=0).run()
    args_requests = [m for m in conn.sent if m[0] == 'args']
    assert len(args_requests) == 2     # re-asked after the idle nap
    assert _counter_value('worker_idle_tasks_total') == idle_before + 1


# ---------------------------------------------------------------------------
# chaos end-to-end: engine kills + stalls in a real TCP fleet


LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    import json
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 2,
                          'forward_steps': 8, 'num_batchers': 1,
                          'model_dir': %(model_dir)r,
                          'metrics_jsonl': %(metrics)r,
                          'telemetry_port': %(tport)d,
                          'inference': {
                              'enabled': True,
                              'request_timeout': 3.0,
                              'request_retries': 0,
                              'stall_timeout': 4.0,
                              'restart_max_delay': 2.0,
                              'reprobe_initial_delay': 2.0,
                              'reprobe_max_delay': 4.0},
                          'fault_tolerance': {
                              'heartbeat_interval': 1.0,
                              'liveness_timeout': 8.0,
                              'rpc_timeout': 30.0,
                              'task_deadline': 30.0,
                              'reconnect_initial_delay': 0.25,
                              'reconnect_max_delay': 2.0,
                              'reconnect_max_tries': 60,
                              'host_health_window': 30.0,
                              'host_quarantine_period': 5.0}}}
    args = apply_defaults(raw)
    learner = Learner(args=args, remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, learner.num_episodes,
          learner.num_returned_episodes, flush=True)
    print('LEDGER', json.dumps(learner.ledger.stats), flush=True)
    print('FLEETSTATES', json.dumps(learner.fleet.snapshot()), flush=True)

if __name__ == '__main__':
    main()
'''

WORKER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.worker import worker_main
    args = {'worker_args': {'server_address': 'localhost', 'num_parallel': 2}}
    worker_main(args, [])

if __name__ == '__main__':
    main()
'''


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_engine_chaos_cluster_self_heals(tmp_path):
    """The acceptance e2e: a real learner + worker host over TCP with
    ``enginekill`` AND ``enginestall`` injected into the host inference
    engines must complete its full 2-epoch budget with zero permanently
    hung workers, at least one observed degrade -> re-promote cycle,
    converged episode accounting, and fleet_host_state visible in both
    metrics_jsonl and the Prometheus exposition during the run."""
    entry_port, data_port, tport = 21920, 21921, 21922
    model_dir = str(tmp_path / 'models')
    metrics = str(tmp_path / 'metrics.jsonl')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {
        'model_dir': model_dir, 'metrics': metrics, 'tport': tport})
    worker_py.write_text(WORKER_SCRIPT)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                'HANDYRL_TPU_DATA_PORT': str(data_port),
                'PYTHONPATH': repo + os.pathsep
                + os.environ.get('PYTHONPATH', '')}
    worker_env = {**base_env,
                  'HANDYRL_TPU_CHAOS': ('enginekill=5,enginestall=7,'
                                        'enginestall_secs=600,'
                                        'engine_max_faults=4,seed=5')}

    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)],
                               env=base_env, stdout=learner_log,
                               stderr=subprocess.STDOUT)
    worker = None
    scraped_states = False
    try:
        time.sleep(3)    # let the entry/data servers bind
        worker = subprocess.Popen([sys.executable, str(worker_py)],
                                  env=worker_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        def done():
            return (os.path.exists(os.path.join(model_dir, '2.ckpt'))
                    or learner.poll() is not None)

        deadline = time.time() + 420
        while not done() and time.time() < deadline:
            # scrape the live exporter mid-run: host states must be
            # visible in the Prometheus exposition DURING the chaos
            try:
                with urllib.request.urlopen(
                        'http://127.0.0.1:%d/metrics' % tport,
                        timeout=2) as resp:
                    text = resp.read().decode()
                if 'fleet_host_state{' in text:
                    scraped_states = True
            except OSError:
                pass
            time.sleep(2)

        assert os.path.exists(os.path.join(model_dir, '2.ckpt')), \
            'run did not reach its epoch budget under engine chaos'
        # zero permanently hung workers: the whole tree winds down on its
        # own once training ends (a wedged worker would hang these waits)
        learner.wait(timeout=120)
        worker.wait(timeout=120)
    finally:
        for proc in (worker, learner):
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
        learner_log.close()
        worker_log.close()

    learner_out = (tmp_path / 'learner.log').read_text()
    worker_out = (tmp_path / 'worker.log').read_text()

    # chaos actually fired, and the self-healing machinery engaged
    assert 'chaos: armed engine' in worker_out
    # at least one degrade -> re-promote cycle was observed worker-side
    assert 'degrading to per-worker inference' in worker_out
    assert 're-promoted to engine inference' in worker_out

    # accounting converged (no double-counted re-issues, budget met)
    done_line = [l for l in learner_out.splitlines()
                 if l.startswith('LEARNER DONE')][0]
    _, _, epoch, _num_episodes, num_returned = done_line.split()
    assert int(epoch) == 2
    assert int(num_returned) >= 36
    ledger = json.loads(learner_out.split('LEDGER', 1)[1].splitlines()[0])
    assert ledger['completed'] <= ledger['assigned']

    # fleet host states reached metrics_jsonl ...
    host_state_records = []
    with open(metrics) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get('fleet_host_states'):
                host_state_records.append(rec['fleet_host_states'])
    assert host_state_records, 'fleet_host_states never hit metrics_jsonl'
    # ... and the engine faults were visible learner-side as a host-state
    # signal (healthy -> degraded at minimum) plus the live exposition
    assert 'fleet: host' in learner_out, 'no host state transition observed'
    assert scraped_states, \
        'fleet_host_state never appeared in the live Prometheus exposition'
