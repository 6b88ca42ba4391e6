"""CI chaos smoke for the replicated serving fleet (docs/serving.md,
"Serving fleet").

Runs a REAL resolver + 2 managed replica subprocesses (``python -m
handyrl_tpu.serving --fleet``) and proves the fleet's headline contract
under chaos, asserting invariants rather than throughput (CI machines are
too noisy — and often too small — for scaling thresholds):

  * routed requests answer byte-identically to a pre-kill reference
    (inference is a pure function of model version + request, so replicas
    are interchangeable);
  * a replica SIGKILLed with a burst in flight costs ZERO client-visible
    failures — stranded requests are replayed on the survivor and the
    replies stay byte-identical;
  * the resolver strands the corpse, respawns it under its old name, and
    the re-registration walks the quarantine round trip back to healthy
    (the controller's ``readmitted`` counter moves);
  * the respawned replica serves byte-identical replies again;
  * SIGTERM drains the whole fleet to exit 75 (EX_TEMPFAIL — the
    PreemptionGuard supervisor contract);
  * the collated trace holds >= 1 complete client->router->engine->reply
    chain — including >= 1 chain that crosses the SIGKILL replay under
    its ORIGINAL trace_id — and ``trace_report.py --serve --json``
    exits 0 on it.

Runs under ``HANDYRL_TPU_SANITIZE=1`` in CI like the other chaos legs:
the lock-order-inversion detector and thread accountant instrument the
resolver and every replica, and the leg must stay green.

Exits 0 on success, 1 with a reason on any failure. Stdlib + repo only.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    # serving-path tracing at rate 1.0, inherited by the resolver and
    # every replica subprocess (telemetry reads the env at import)
    trace_dir = tempfile.mkdtemp(prefix='fleet_smoke_trace.')
    os.environ['HANDYRL_TPU_TRACE'] = trace_dir
    os.environ['HANDYRL_TPU_TRACE_RATE'] = '1'
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import sample_seed
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.serving.fleet import RoutedClient
    from handyrl_tpu.serving.registry import ModelRegistry

    env = make_env({'env': 'TicTacToe'})
    env.reset()
    obs = env.observation(env.players()[0])
    legal = env.legal_actions(env.players()[0])
    wrapper = ModelWrapper(env.net(), seed=7)
    wrapper.ensure_params(obs)

    root = tempfile.mkdtemp(prefix='fleet_smoke_registry.')
    proc = rc = None
    try:
        ModelRegistry(root).publish('default', snapshot=wrapper.snapshot(),
                                    version=1, promote=True)
        proc = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--fleet',
             '--replicas', '2', '--env', 'TicTacToe', '--registry', root,
             '--port', '0', '--line', 'default',
             '--heartbeat', '0.2', '--heartbeat-timeout', '2.0'],
            cwd=REPO, stdout=subprocess.PIPE, text=True)
        ready = json.loads(proc.stdout.readline())['fleet_ready']
        assert ready['replicas'] == 2, ready
        rc = RoutedClient('127.0.0.1', int(ready['port']), timeout=20.0,
                          refresh_interval=0.2)
        table = {r['replica']: r for r in rc.replicas()}
        assert len(table) == 2, table

        seeds = [sample_seed(11, (0, k), 0) for k in range(8)]
        refs = [rc.request('default@champion', obs, legal=legal, seed=s)
                for s in seeds]

        # SIGKILL one replica with a burst in flight (the whole burst is
        # steered onto the victim so the replay path is exercised for
        # certain, not left to round-robin timing)
        victim = sorted(table)[0]
        rids = [rc.submit('default@champion', obs, legal=legal, seed=s,
                          replica=victim)
                for s in seeds]
        os.kill(table[victim]['pid'], signal.SIGKILL)
        failures = 0
        for rid, ref in zip(rids, refs):
            rep = rc.collect(rid)
            if rep['action'] != ref['action'] or rep['prob'] != ref['prob']:
                failures += 1
        assert failures == 0, \
            '%d client-visible failure(s) through the SIGKILL' % failures

        # corpse -> quarantine -> respawn -> re-admission round trip
        round_trip = False
        states = {}
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            states = {r['replica']: r['state'] for r in rc.replicas()}
            readmitted = rc.status()['controller'].get('readmitted', 0)
            if readmitted >= 1 and states.get(victim) == 'healthy':
                round_trip = True
                break
            time.sleep(0.25)
        assert round_trip, \
            'kill never walked the quarantine round trip: %s' % states

        # the respawned replica serves byte-identical replies again
        for s, ref in zip(seeds, refs):
            rep = rc.request('default@champion', obs, legal=legal, seed=s)
            assert rep['prob'] == ref['prob'], 'post-respawn reply diverged'

        # fleet-wide graceful drain: exit 75 (EX_TEMPFAIL, restart me)
        proc.send_signal(signal.SIGTERM)
        code = proc.wait(timeout=120)
        assert code == 75, 'fleet exited %s, not 75' % code

        # the collated trace carries the whole causal story: >= 1
        # complete client->router->engine->reply chain, and >= 1 chain
        # crossing the SIGKILL replay under its ORIGINAL trace_id
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
        assert serve['routed_chains'] >= 1, serve
        assert serve['replay_chains'] >= 1, serve
        assert serve['complete_replay_chains'] >= 1, serve

        print('fleet smoke OK: %d/%d burst replies byte-identical through '
              'a replica SIGKILL, %s respawned and re-admitted, fleet '
              'drained to exit 75; trace holds %d complete serve chain(s) '
              'incl. %d crossing the replay'
              % (len(rids), len(rids), victim, serve['complete_chains'],
                 serve['complete_replay_chains']))
        return 0
    finally:
        if rc is not None:
            rc.close()
        if proc is not None and proc.poll() is None:
            proc.kill()
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == '__main__':
    raise SystemExit(main())
