"""CI smoke for the device actor backend (docs/large_scale_training.md).

Runs a REAL learner + one worker host over TCP where the host selects
``backend: device`` (worker_args.backend riding the entry handshake): the
gather serves its whole task block through the fused on-device rollout
engine (DeviceActorEngine) instead of worker processes. League training is
on, so PFSP pairings are served by the SAME compiled program via stacked
opponent params. Proves, without throughput thresholds:

  * episodes and eval results generated on device land through the task
    ledger and finish the learner's epochs (exit 0);
  * the retrace sentinel stays clean on the device host under
    ``HANDYRL_TPU_RETRACE=abort`` (one warmup compile, then steady state —
    a league pairing change must NOT retrace);
  * ``device_actor_*`` counters ride the gather heartbeat into the
    learner's merged fleet telemetry (metrics_jsonl);
  * PFSP sampled >= 2 distinct registry opponent versions while the only
    generation host in the fleet was the device gather.

``--chaos`` (the slow leg) arms ``HANDYRL_TPU_CHAOS=kill_gather`` on the
worker host: the device gather is SIGKILLed mid-run, the supervisor
respawns it (as a device gather — same merged args), the ledger re-issues
its in-flight tasks, and the run still completes.

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

LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 10,
                          'minimum_episodes': 10, 'epochs': 5,
                          'forward_steps': 8, 'num_batchers': 1,
                          'eval_rate': 0.3, 'seed': 11,
                          'keep_checkpoints': 3,
                          'metrics_jsonl': %(metrics)r,
                          'model_dir': %(model_dir)r,
                          'generation': {'device_actor_envs': 8,
                                         'device_actor_chunk_steps': 8,
                                         'device_actor_slots': 2},
                          # the tiny run is over in seconds; beat fast so
                          # device_actor_* counters ride the fleet merge
                          # before the last epoch record is written
                          'fault_tolerance': {'heartbeat_interval': 1.0},
                          'serving': {'publish': True, 'line': 'default'},
                          'league': {'enabled': True, 'self_play_rate': 0.0,
                                     'rating_match_rate': 0.3,
                                     'curve': 'uniform', 'min_games': 1,
                                     'promote_margin': 0.0}}}
    learner = Learner(args=apply_defaults(raw), remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, flush=True)

if __name__ == '__main__':
    main()
'''

# the host asks for the device backend itself: worker_args.backend rides
# the entry handshake and WINS over the training config's generation block
WORKER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.worker import worker_main
    args = {'worker_args': {'server_address': 'localhost',
                            'num_parallel': 2, 'backend': 'device'}}
    worker_main(args, [])

if __name__ == '__main__':
    main()
'''


def main() -> int:
    chaos = '--chaos' in sys.argv[1:]
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    work = tempfile.mkdtemp(prefix='device_actor_smoke.')
    model_dir = os.path.join(work, 'models')
    metrics = os.path.join(work, 'metrics.jsonl')
    learner_py = os.path.join(work, 'learner.py')
    worker_py = os.path.join(work, 'worker.py')
    with open(learner_py, 'w') as f:
        f.write(LEARNER_SCRIPT % {'model_dir': model_dir, 'metrics': metrics})
    with open(worker_py, 'w') as f:
        f.write(WORKER_SCRIPT)
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'PYTHONPATH': REPO + os.pathsep
                + os.environ.get('PYTHONPATH', '')}
    worker_env = dict(base_env, HANDYRL_TPU_RETRACE='abort')
    if chaos:
        # mean 8s between SIGKILLs: at least one hit lands mid-run on the
        # tiny geometry, the supervisor respawn + ledger re-issue recover
        worker_env['HANDYRL_TPU_CHAOS'] = 'kill_gather=8,max_kills=2,seed=3'
        worker_env.pop('HANDYRL_TPU_RETRACE')  # respawns recompile by design

    learner = worker = None
    learner_log = open(os.path.join(work, 'learner.log'), 'w')
    worker_log = open(os.path.join(work, 'worker.log'), 'w')
    try:
        learner = subprocess.Popen([sys.executable, learner_py],
                                   env=base_env, stdout=learner_log,
                                   stderr=subprocess.STDOUT)
        time.sleep(3)   # let the entry/worker servers bind
        worker = subprocess.Popen([sys.executable, worker_py],
                                  env=worker_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)
        deadline = time.time() + 240
        while time.time() < deadline and learner.poll() is None:
            time.sleep(2)
        assert learner.poll() is not None, 'learner never finished its epochs'
        assert learner.returncode == 0, \
            'learner exited %s' % learner.returncode

        # the worker log proves the backend actually engaged (and, in the
        # chaos leg, that the respawned gather came back as a device gather)
        wlog = open(os.path.join(work, 'worker.log')).read()
        engaged = wlog.count('device actor backend')
        assert engaged >= 1, 'device backend never engaged:\n%s' % wlog[-2000:]
        if chaos:
            assert engaged >= 2, \
                'chaos leg: expected a respawned device gather ' \
                '(saw %d backend banner(s))' % engaged
        assert 'retrace' not in wlog.lower() or chaos, \
            'retrace sentinel tripped on the device host:\n%s' % wlog[-2000:]

        # metrics: device_actor_* counters rode the heartbeat merge, and
        # PFSP drew >= 2 distinct registry versions through the device host
        sampled = set()
        dev_eps = dev_results = 0
        recs = 0
        with open(metrics) as f:
            for line in f:
                rec = json.loads(line)
                recs += 1
                lg = rec.get('league')
                if lg:
                    sampled.update(lg.get('opponents_sampled') or {})
                fleet = ((rec.get('fleet_telemetry') or {})
                         .get('counters') or {})
                dev_eps = max(dev_eps,
                              fleet.get('device_actor_episodes_total', 0))
                dev_results = max(
                    dev_results, fleet.get('device_actor_results_total', 0))
        assert recs > 0, 'no metrics records written'
        assert dev_eps > 0, \
            'no device_actor_episodes_total in fleet telemetry ' \
            '(device engine produced nothing?)'
        versions = {m for m in sampled if '@' in m}
        assert len(versions) >= 2, \
            'PFSP sampled %r: wanted >= 2 registry versions served by the ' \
            'device host' % (sampled,)

        print('device actor smoke OK%s: %d device episodes, %d device '
              'results, league versions sampled %s'
              % (' (chaos)' if chaos else '', dev_eps, dev_results,
                 sorted(versions)))
        return 0
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
        shutil.rmtree(work, ignore_errors=True)


if __name__ == '__main__':
    raise SystemExit(main())
