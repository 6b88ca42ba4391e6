"""CI chaos smoke for the match gateway (docs/serving.md, "Match
gateway").

Runs a REAL fleet resolver + 2 managed replica subprocesses and a REAL
gateway subprocess (``python -m handyrl_tpu.serving --gateway``), opens
concurrent HungryGeese sessions against a published **recurrent**
GeeseNetLSTM (so the server-side hidden cache and the journal's hidden
digest are live, not trivially empty), SIGKILLs one replica while every
session is held mid-match, and asserts the session tier's zero-loss
contract:

  * ZERO dropped sessions and zero client-visible errors — every match
    plays to a terminal outcome through the kill;
  * >= 1 session is reconstructed from its journal through a survivor,
    with ZERO mismatches — the gateway replays every journaled opponent
    ply with its original audited seed and verifies both the replayed
    actions and the rebuilt hidden digest byte-identically before
    adopting the rebuilt state;
  * every outcome is booked into the RatingBook: one provisional
    ``gateway:<client>`` entry per client (never promotion-eligible)
    plus the rated model entry, round-tripped through the on-disk
    rating journal;
  * gateway and fleet SIGTERM drains both exit 75 (EX_TEMPFAIL — the
    PreemptionGuard supervisor contract);
  * the collated trace holds >= 1 complete client->router->engine->reply
    chain and >= 1 journal-reconstruction chain linked to its session's
    ORIGINAL open-time trace_id, and ``trace_report.py --serve --json``
    exits 0 on it.

Runs under ``HANDYRL_TPU_SANITIZE=1`` in CI like the other chaos legs.
Exits 0 on success, 1 with a reason on any failure. Stdlib + repo only.
"""

import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_SESSIONS = 8
ENV = 'HungryGeese'


def main() -> int:
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    # serving-path tracing at rate 1.0, inherited by the fleet, every
    # replica, and the gateway (telemetry reads the env at import)
    trace_dir = tempfile.mkdtemp(prefix='gateway_smoke_trace.')
    os.environ['HANDYRL_TPU_TRACE'] = trace_dir
    os.environ['HANDYRL_TPU_TRACE_RATE'] = '1'
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.league import journal_path, make_rating_book
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.serving.fleet import RoutedClient
    from handyrl_tpu.serving.gateway import GatewayClient
    from handyrl_tpu.serving.registry import ModelRegistry

    env = make_env({'env': ENV, 'net_kind': 'lstm'})
    env.reset()
    obs = env.observation(env.players()[0])
    wrapper = ModelWrapper(env.net(), seed=7)
    wrapper.ensure_params(obs)

    root = tempfile.mkdtemp(prefix='gateway_smoke_registry.')
    fleet = gw = rc = None
    try:
        ModelRegistry(root).publish('default', snapshot=wrapper.snapshot(),
                                    version=1, promote=True)
        fleet = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--fleet',
             '--replicas', '2', '--env', ENV, '--registry', root,
             '--port', '0', '--line', 'default',
             '--heartbeat', '0.2', '--heartbeat-timeout', '2.0'],
            cwd=REPO, stdout=subprocess.PIPE, text=True)
        fleet_port = int(json.loads(
            fleet.stdout.readline())['fleet_ready']['port'])
        gw = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--gateway',
             '--resolver', 'localhost:%d' % fleet_port,
             '--registry', root, '--env', ENV,
             '--gateway-workers', '8', '--max-sessions', '16',
             '--seed', '17'],
            cwd=REPO, stdout=subprocess.PIPE, text=True)
        gport = int(json.loads(
            gw.stdout.readline())['gateway_ready']['port'])

        # every session plays 2 plies, then holds mid-match until the
        # SIGKILL (and the journal reconstructions) have happened — so
        # the kill is guaranteed to land on live, stateful sessions
        hold = threading.Event()
        ready = threading.Semaphore(0)
        results = [None] * N_SESSIONS

        def session(ci):
            rng = random.Random(100 + ci)
            marked = False
            cl = GatewayClient('localhost', gport, timeout=120.0,
                               name='smoke%d' % ci)
            try:
                r = cl.open(ENV, seat=0)
                sid = r['sid']
                plies = 0
                while not r.get('done'):
                    if plies >= 2 and not marked:
                        marked = True
                        ready.release()
                        hold.wait(timeout=300)
                    action = (rng.choice(r['legal'])
                              if r.get('to_move') and r.get('legal')
                              else None)
                    r = cl.play(sid, action)
                    plies += 1
                results[ci] = r.get('outcome')
            except Exception as exc:  # noqa: BLE001 — asserted below
                results[ci] = 'ERROR: %s' % exc
            finally:
                if not marked:
                    ready.release()
                cl.close()

        threads = [threading.Thread(target=session, args=(ci,),
                                    name='smoke-session-%d' % ci)
                   for ci in range(N_SESSIONS)]
        for t in threads:
            t.start()
        for _ in range(N_SESSIONS):
            assert ready.acquire(timeout=300), 'sessions never got rolling'

        status_cl = GatewayClient('localhost', gport, timeout=60.0,
                                  name='smoke-status')
        by_replica = {}
        for s in status_cl.sessions():
            if not s.get('done'):
                by_replica.setdefault(s.get('replica'), []).append(s['sid'])
        by_replica.pop(None, None)
        assert by_replica, 'no session is pinned to any replica'
        victim = max(by_replica, key=lambda n: len(by_replica[n]))
        rc = RoutedClient('localhost', fleet_port, timeout=30.0,
                          refresh_interval=0.2)
        table = {r['replica']: r for r in rc.replicas()}
        os.kill(int(table[victim]['pid']), signal.SIGKILL)

        # the monitor must notice the corpse and reconstruct its
        # sessions from their journals before we let play resume
        deadline = time.monotonic() + 60
        status = {}
        while time.monotonic() < deadline:
            status = status_cl.status()
            if status.get('reconstructs', 0) >= len(by_replica[victim]):
                break
            time.sleep(0.25)
        hold.set()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), \
            'session thread(s) wedged'

        errors = [r for r in results if not isinstance(r, dict)]
        assert not errors, 'client-visible failure(s): %s' % errors[:3]
        status = status_cl.status()
        assert status['dropped'] == 0, \
            '%d session(s) dropped' % status['dropped']
        assert status['mismatches'] == 0, \
            '%d reconstruction(s) diverged from the journal' \
            % status['mismatches']
        assert status['reconstructs'] >= len(by_replica[victim]), \
            'only %d of %d stranded session(s) reconstructed' \
            % (status['reconstructs'], len(by_replica[victim]))
        assert status['replayed_plies'] >= 2 * status['reconstructs'], \
            'reconstructions replayed suspiciously few plies: %s' % status
        assert status['outcomes'] >= N_SESSIONS, \
            'only %d of %d outcomes booked' % (status['outcomes'],
                                               N_SESSIONS)
        assert status['shed'] == 0, '%d open(s) shed' % status['shed']
        for ci in range(N_SESSIONS):
            assert 'gateway:smoke%d' % ci in status['ratings'], \
                'client smoke%d missing from the RatingBook' % ci
        status_cl.close()

        # outcomes round-trip through the on-disk rating journal: the
        # external players are provisional (never promotion-eligible),
        # the served model is a rated entry
        book = make_rating_book({})
        assert book.load(journal_path(root)), 'rating journal missing'
        for ci in range(N_SESSIONS):
            name = 'gateway:smoke%d' % ci
            assert book.is_provisional(name), \
                '%s is not a provisional member' % name
        rated = [n for n in book.names() if n.startswith('default@')]
        assert rated, 'served model missing from the rating journal'

        # graceful drains: gateway first, then the whole fleet — both 75
        gw.send_signal(signal.SIGTERM)
        code = gw.wait(timeout=60)
        assert code == 75, 'gateway exited %s, not 75' % code
        fleet.send_signal(signal.SIGTERM)
        code = fleet.wait(timeout=120)
        assert code == 75, 'fleet exited %s, not 75' % code

        # the collated trace reads as one causal chain per session:
        # >= 1 complete client->router->engine->reply chain, and >= 1
        # journal reconstruction linked to its session's ORIGINAL
        # open-time trace_id
        from handyrl_tpu import telemetry
        telemetry.trace_flush()
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, 'scripts', 'trace_report.py'),
             trace_dir, '--serve', '--json'],
            capture_output=True, text=True)
        assert out.returncode == 0, \
            'trace_report --serve exited %d: %s' % (out.returncode,
                                                    out.stderr[:500])
        serve = json.loads(out.stdout)['serve']
        assert serve['complete_chains'] >= 1, serve
        assert serve['reconstruct_chains'] >= 1, serve

        print('gateway smoke OK: %d/%d matches finished through a replica '
              'SIGKILL (%s), %d session(s) journal-reconstructed '
              '(%d plies replayed, 0 mismatches), 0 drops, %d outcomes '
              'in the RatingBook, both drains exited 75; trace holds %d '
              'complete serve chain(s) and %d reconstruct chain(s)'
              % (len(results), N_SESSIONS, victim,
                 status['reconstructs'], status['replayed_plies'],
                 status['outcomes'], serve['complete_chains'],
                 serve['reconstruct_chains']))
        return 0
    finally:
        if rc is not None:
            rc.close()
        for proc in (gw, fleet):
            if proc is not None and proc.poll() is None:
                proc.kill()
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == '__main__':
    raise SystemExit(main())
