"""CI chaos smoke for the durable training plane
(docs/large_scale_training.md, "Zero-loss training plane").

Runs a REAL learner + worker-host fleet over TCP, SIGKILLs the learner
mid-epoch (after its first model update, with in-flight tasks booked and
admitted episodes sitting past the last ledger snapshot), restarts it with
``restart_epoch: -1``, and proves the headline contract:

  * the restarted learner adopts the run token and restores the persisted
    ledger book (``durable plane: restored ledger book``);
  * >= 1 admitted episode is replayed from the spool — episodes the dead
    process had counted but never checkpointed
    (``durable plane: recovered N spooled episode(s)``);
  * the ORIGINAL worker-host gathers ride through: resume-token handshake
    (``reattached across a learner restart``), ZERO gather respawns;
  * the exact epoch budget completes with converged accounting — nothing
    double-counts, nothing is lost;
  * restart MTTR (SIGKILL -> first post-restart train step) is measured
    and printed in the OK line.

Runs under ``HANDYRL_TPU_SANITIZE=1`` in CI like the other chaos legs.
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

ENTRY_PORT = int(os.environ.get('HANDYRL_TPU_ENTRY_PORT', 21940))
DATA_PORT = int(os.environ.get('HANDYRL_TPU_DATA_PORT', 21941))

LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    import json
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 3,
                          'forward_steps': 8, 'num_batchers': 1,
                          'restart_epoch': -1,
                          'model_dir': %(model_dir)r,
                          'fault_tolerance': {
                              'heartbeat_interval': 1.0,
                              'liveness_timeout': 8.0,
                              'rpc_timeout': 30.0,
                              'task_deadline': 30.0,
                              'reconnect_initial_delay': 0.25,
                              'reconnect_max_delay': 1.0,
                              'reconnect_max_tries': 240}}}
    args = apply_defaults(raw)
    learner = Learner(args=args, remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, learner.num_episodes,
          learner.num_returned_episodes, flush=True)
    print('LEDGER', json.dumps(learner.ledger.stats), flush=True)

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


def _wait_for(predicate, deadline, poll=0.25):
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


def main() -> int:
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    work = tempfile.mkdtemp(prefix='learner_chaos_smoke.')
    model_dir = os.path.join(work, 'models')
    learner_py = os.path.join(work, 'learner.py')
    worker_py = os.path.join(work, 'worker.py')
    with open(learner_py, 'w') as f:
        f.write(LEARNER_SCRIPT % {'model_dir': model_dir})
    with open(worker_py, 'w') as f:
        f.write(WORKER_SCRIPT)

    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'HANDYRL_TPU_ENTRY_PORT': str(ENTRY_PORT),
           'HANDYRL_TPU_DATA_PORT': str(DATA_PORT),
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}
    log1_path = os.path.join(work, 'learner1.log')
    log2_path = os.path.join(work, 'learner2.log')
    worker_path = os.path.join(work, 'worker.log')

    def read(path):
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ''

    learner2 = worker = None
    log1 = open(log1_path, 'w')
    log2 = open(log2_path, 'w')
    worker_log = open(worker_path, 'w')
    learner1 = subprocess.Popen([sys.executable, learner_py], env=env,
                                stdout=log1, stderr=subprocess.STDOUT)
    try:
        time.sleep(3)
        worker = subprocess.Popen([sys.executable, worker_py], env=env,
                                  stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        # wait for one full epoch (checkpoint + ledger snapshot exist),
        # then a little mid-epoch churn so admitted episodes sit past the
        # snapshot horizon and in-flight tasks are booked — then murder it
        assert _wait_for(lambda: 'updated model' in read(log1_path)
                         or learner1.poll() is not None, time.time() + 300), \
            'fleet never completed its first model update'
        assert learner1.poll() is None, 'learner died before the kill'
        time.sleep(2.0)
        kill_at = time.monotonic()
        learner1.send_signal(signal.SIGKILL)
        learner1.wait(timeout=30)

        learner2 = subprocess.Popen([sys.executable, learner_py], env=env,
                                    stdout=log2, stderr=subprocess.STDOUT)
        # restart MTTR: SIGKILL -> the restarted learner's first train step
        assert _wait_for(lambda: 'updated model' in read(log2_path)
                         or learner2.poll() is not None, time.time() + 300), \
            'restarted learner never reached a train step'
        mttr = time.monotonic() - kill_at

        assert _wait_for(lambda: 'LEARNER DONE' in read(log2_path)
                         or learner2.poll() is not None, time.time() + 300), \
            'restarted learner hung before finishing its budget'
        learner2.wait(timeout=120)
        worker.wait(timeout=120)

        out2 = read(log2_path)
        worker_out = read(worker_path)
        assert 'durable plane: restored ledger book' in out2, \
            'restart never restored the persisted ledger book'
        assert 'durable plane: recovered' in out2, \
            'restart recovered zero spooled episodes'
        recovered = int(out2.split('durable plane: recovered', 1)[1]
                        .strip().split()[0])
        assert recovered >= 1, 'spool recovery replayed no episodes'
        assert 'reattached across a learner restart' in worker_out, \
            'no gather went through the resume-token reattach'
        assert 'respawning' not in worker_out, \
            'a gather respawned — the fleet did not ride through'
        done_line = [l for l in out2.splitlines()
                     if l.startswith('LEARNER DONE')][0]
        _, _, epoch, _n_eps, num_returned = done_line.split()
        assert int(epoch) == 3, 'budget incomplete: epoch %s' % epoch
        assert int(num_returned) >= 36, \
            'accounting did not converge: %s returned' % num_returned
        ledger = json.loads(
            read(log2_path).split('LEDGER', 1)[1].strip().splitlines()[0])

        print('learner chaos smoke OK: SIGKILL mid-epoch -> restart '
              'recovered %d spooled episode(s), restored book re-issued %d, '
              'gathers reattached with 0 respawns, budget completed at '
              'epoch %s (%s episodes); restart MTTR %.1fs'
              % (recovered, ledger.get('reissued', 0), epoch,
                 num_returned, mttr), flush=True)
        return 0
    finally:
        for proc in (worker, learner2, learner1):
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
        log1.close()
        log2.close()
        worker_log.close()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == '__main__':
    raise SystemExit(main())
