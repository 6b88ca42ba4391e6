"""CI chaos smoke for streaming partial-episode ingest
(docs/large_scale_training.md, "Streaming ingest").

Runs a REAL learner + worker-host fleet over TCP with the ``streaming:``
block enabled (chunked uploads, staleness-aware selection), SIGKILLs the
host's only gather mid-run via the chaos harness, and proves the chunked
pipeline survives exactly like the whole-episode one:

  * workers flush fixed-T window chunks through the upload path — the
    learner ingests a meaningful number of them
    (``chunks_ingested_total``) and reassembles whole episodes
    (``streaming_reassembled_episodes_total``);
  * the killed gather strands in-flight chunk streams; the supervisor
    respawns it, the stranded tasks re-issue with their ORIGINAL
    sample_keys, and the regenerated chunks MERGE into the stranded
    assemblies instead of double-counting (accounting converges to the
    exact budget);
  * the run completes its epoch budget — partially-delivered episodes
    never wedge the learner.

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

ENTRY_PORT = int(os.environ.get('HANDYRL_TPU_ENTRY_PORT', 21950))
DATA_PORT = int(os.environ.get('HANDYRL_TPU_DATA_PORT', 21951))

LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    import json
    from handyrl_tpu import telemetry
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 3,
                          'forward_steps': 8, 'num_batchers': 1,
                          'compress_steps': 2,
                          'model_dir': %(model_dir)r,
                          # chunk_steps 2 on TicTacToe's 5-9 ply games
                          # makes EVERY episode multi-chunk, so the kill
                          # is guaranteed to strand partial streams
                          'streaming': {'enabled': True, 'chunk_steps': 2,
                                        'staleness_half_life': 30.0},
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
    print('CHUNKS',
          telemetry.counter('chunks_ingested_total').value,
          telemetry.counter('streaming_reassembled_episodes_total').value,
          telemetry.counter('chunk_duplicates_total').value, flush=True)

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
    work = tempfile.mkdtemp(prefix='streaming_chaos_smoke.')
    model_dir = os.path.join(work, 'models')
    learner_py = os.path.join(work, 'learner.py')
    worker_py = os.path.join(work, 'worker.py')
    with open(learner_py, 'w') as f:
        f.write(LEARNER_SCRIPT % {'model_dir': model_dir})
    with open(worker_py, 'w') as f:
        f.write(WORKER_SCRIPT)

    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'HANDYRL_TPU_ENTRY_PORT': str(ENTRY_PORT),
                'HANDYRL_TPU_DATA_PORT': str(DATA_PORT),
                'PYTHONPATH': REPO + os.pathsep
                + os.environ.get('PYTHONPATH', '')}
    # chaos: SIGKILL the host's single gather once, early in the run —
    # after generation is underway, so in-flight chunk streams strand
    worker_env = {**base_env,
                  'HANDYRL_TPU_CHAOS': 'kill_gather=8,max_kills=1,seed=5'}
    learner_path = os.path.join(work, 'learner.log')
    worker_path = os.path.join(work, 'worker.log')

    def read(path):
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ''

    worker = None
    learner_log = open(learner_path, 'w')
    worker_log = open(worker_path, 'w')
    learner = subprocess.Popen([sys.executable, learner_py], env=base_env,
                               stdout=learner_log,
                               stderr=subprocess.STDOUT)
    try:
        time.sleep(3)   # let the entry/data servers bind
        worker = subprocess.Popen([sys.executable, worker_py],
                                  env=worker_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        assert _wait_for(lambda: 'LEARNER DONE' in read(learner_path)
                         or learner.poll() is not None, time.time() + 420), \
            'fleet hung before finishing its epoch budget'
        learner.wait(timeout=120)
        worker.wait(timeout=120)

        learner_out = read(learner_path)
        worker_out = read(worker_path)

        # the chaos kill actually happened and the supervisor recovered it
        assert 'chaos: killing gather' in worker_out, \
            'chaos harness never killed the gather'
        assert 'respawning' in worker_out, \
            'the killed gather was never respawned'

        # the budget completed with converged accounting despite the
        # stranded chunk streams
        done_line = [l for l in learner_out.splitlines()
                     if l.startswith('LEARNER DONE')][0]
        _, _, epoch, _n_eps, num_returned = done_line.split()
        assert int(epoch) == 3, 'budget incomplete: epoch %s' % epoch
        assert int(num_returned) >= 36, \
            'accounting did not converge: %s returned' % num_returned

        # streaming was genuinely exercised: multi-chunk episodes flowed
        # and reassembled (chunk_steps 2 means >= 2 chunks per episode)
        chunks_line = [l for l in learner_out.splitlines()
                       if l.startswith('CHUNKS')][0]
        _, ingested, reassembled, dupes = chunks_line.split()
        assert int(ingested) >= 2 * int(num_returned) // 2, \
            'too few chunks ingested (%s) for %s episodes' % (
                ingested, num_returned)
        assert int(reassembled) >= 36, \
            'assembler reassembled only %s episodes' % reassembled

        ledger = json.loads(
            learner_out.split('LEDGER', 1)[1].strip().splitlines()[0])
        assert ledger['completed'] <= ledger['assigned']

        print('streaming chaos smoke OK: gather SIGKILL mid-stream -> '
              'respawned, budget completed at epoch %s; %s chunks '
              'ingested, %s episodes reassembled, %s duplicate chunk(s) '
              'screened, %d task(s) re-issued'
              % (epoch, ingested, reassembled, dupes,
                 ledger.get('reissued', 0)), flush=True)
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
