"""CI smoke for league training (docs/league.md).

Runs a REAL learner + worker-host fleet over TCP with ``league.enabled``
(tiny CPU geometry, a few epochs) and proves the headline contract
without throughput thresholds:

  * PFSP sampling draws >= 2 DISTINCT registry opponent versions into
    'g' episodes (the pool is a population, not just the newest ckpt);
  * the RatingBook journal lands on disk, is non-empty, and round-trips
    through the book bit-identically (the restart-survival contract);
  * every metrics_jsonl record carries the league block, and
    ``scripts/league_report.py`` renders the stream (exit 0).

Runs under ``HANDYRL_TPU_SANITIZE=1`` in CI like the other fleet legs:
the lock-order-inversion detector and thread accountant instrument the
learner and the worker host, and the leg must stay green.

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
                          'serving': {'publish': True, 'line': 'default'},
                          'league': {'enabled': True, 'self_play_rate': 0.0,
                                     'rating_match_rate': 1.0,
                                     'curve': 'uniform', 'min_games': 1,
                                     'promote_margin': 0.0}}}
    learner = Learner(args=apply_defaults(raw), remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, flush=True)

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


def main() -> int:
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    work = tempfile.mkdtemp(prefix='league_smoke.')
    model_dir = os.path.join(work, 'models')
    metrics = os.path.join(work, 'metrics.jsonl')
    journal = os.path.join(model_dir, 'league_ratings.json')
    learner_py = os.path.join(work, 'learner.py')
    worker_py = os.path.join(work, 'worker.py')
    with open(learner_py, 'w') as f:
        f.write(LEARNER_SCRIPT % {'model_dir': model_dir, 'metrics': metrics})
    with open(worker_py, 'w') as f:
        f.write(WORKER_SCRIPT)
    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}

    learner = worker = None
    learner_log = open(os.path.join(work, 'learner.log'), 'w')
    worker_log = open(os.path.join(work, 'worker.log'), 'w')
    try:
        learner = subprocess.Popen([sys.executable, learner_py], env=env,
                                   stdout=learner_log,
                                   stderr=subprocess.STDOUT)
        time.sleep(3)   # let the entry/worker servers bind
        worker = subprocess.Popen([sys.executable, worker_py], env=env,
                                  stdout=worker_log,
                                  stderr=subprocess.STDOUT)
        deadline = time.time() + 240
        while time.time() < deadline and learner.poll() is None:
            time.sleep(2)
        assert learner.poll() is not None, 'learner never finished its epochs'
        assert learner.returncode == 0, \
            'learner exited %s' % learner.returncode

        # ratings journal: on disk, non-empty, bit-identical round trip
        assert os.path.exists(journal), 'no ratings journal at %s' % journal
        raw = open(journal, 'rb').read()
        state = json.loads(raw)
        assert state['entries'], 'ratings journal booked no games'
        from handyrl_tpu.league import RatingBook
        book = RatingBook()
        assert book.load(journal), 'journal did not reload'
        again = os.path.join(work, 'roundtrip.json')
        book.save(again)
        assert open(again, 'rb').read() == raw, \
            'journal round trip is not bit-identical'

        # metrics: league block on every record, >= 2 distinct versions
        sampled = set()
        league_recs = total_recs = 0
        with open(metrics) as f:
            for line in f:
                rec = json.loads(line)
                total_recs += 1
                lg = rec.get('league')
                if lg:
                    league_recs += 1
                    sampled.update(lg.get('opponents_sampled') or {})
        assert league_recs == total_recs > 0, \
            'league block on %d/%d records' % (league_recs, total_recs)
        versions = {m for m in sampled if '@' in m}
        assert len(versions) >= 2, \
            'PFSP sampled %r: wanted >= 2 registry versions' % (sampled,)

        # the report renders the stream
        rep = subprocess.run(
            [sys.executable, os.path.join(REPO, 'scripts/league_report.py'),
             metrics, '--journal', journal],
            capture_output=True, text=True, timeout=60)
        assert rep.returncode == 0, 'league_report failed: %s' % rep.stderr
        assert 'champion' in rep.stdout and 'learner' in rep.stdout

        print('league smoke OK: %d league records, versions sampled %s, '
              'journal %d entries round-tripped bit-identically'
              % (league_recs, sorted(versions), len(state['entries'])))
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
