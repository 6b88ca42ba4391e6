"""CI trace smoke: a short CPU learner+worker fleet with tracing on.

Proves the episode-lifecycle tracing loop end to end:

  1. launches a tiny TCP fleet (server-mode learner + one worker host)
     with ``HANDYRL_TPU_TRACE`` set;
  2. after the run, validates the collated Chrome-trace JSON parses, spans
     from >= 3 distinct processes share trace ids, and per-chain stage
     ordering holds (spans nest causally);
  3. runs ``scripts/trace_report.py`` on the trace dir and asserts it
     reports a non-empty generation->gradient critical path (exit 0).

Exits 0 on success, 1 with a reason on any failure. Stdlib + repo only.
"""

import json
import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ENTRY_PORT = int(os.environ.get('TRACE_SMOKE_ENTRY_PORT', '23110'))
DATA_PORT = int(os.environ.get('TRACE_SMOKE_DATA_PORT', '23111'))

LEARNER = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 2,
                          'forward_steps': 8, 'num_batchers': 1,
                          'model_dir': %(model_dir)r,
                          'metrics_jsonl': %(metrics)r,
                          'fault_tolerance': {'heartbeat_interval': 1.0,
                                              'liveness_timeout': 15.0}}}
    learner = Learner(args=apply_defaults(raw), remote=True)
    learner.run()
    print('TRACE SMOKE LEARNER DONE', learner.model_epoch, flush=True)

if __name__ == '__main__':
    main()
'''

WORKER = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.worker import worker_main
    args = {'worker_args': {'server_address': 'localhost', 'num_parallel': 2}}
    worker_main(args, [])

if __name__ == '__main__':
    main()
'''


def fail(msg):
    print('TRACE SMOKE FAILED: %s' % msg, flush=True)
    sys.exit(1)


def main():
    import tempfile
    workdir = tempfile.mkdtemp(prefix='trace_smoke.')
    trace_dir = os.path.join(workdir, 'traces')
    learner_py = os.path.join(workdir, 'learner.py')
    worker_py = os.path.join(workdir, 'worker.py')
    with open(learner_py, 'w') as f:
        f.write(LEARNER % {'model_dir': os.path.join(workdir, 'models'),
                           'metrics': os.path.join(workdir, 'metrics.jsonl')})
    with open(worker_py, 'w') as f:
        f.write(WORKER)

    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'HANDYRL_TPU_TRACE': trace_dir,
           'HANDYRL_TPU_TRACE_RATE': '1.0',
           'HANDYRL_TPU_ENTRY_PORT': str(ENTRY_PORT),
           'HANDYRL_TPU_DATA_PORT': str(DATA_PORT),
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}
    learner = subprocess.Popen([sys.executable, learner_py], env=env)
    worker = None
    try:
        time.sleep(3)
        worker = subprocess.Popen([sys.executable, worker_py], env=env)
        rc = learner.wait(timeout=420)
        worker.wait(timeout=120)
    except subprocess.TimeoutExpired:
        fail('fleet did not finish in time')
    finally:
        for proc in (worker, learner):
            if proc is not None and proc.poll() is None:
                proc.kill()
    if rc != 0:
        fail('learner exited rc=%d' % rc)

    # -- the collated Chrome trace parses and links >= 3 processes --------
    finalized = glob.glob(os.path.join(trace_dir, 'trace-*.json'))
    if not finalized:
        fail('no finalized trace-<run_id>.json in %s' % trace_dir)
    events = json.load(open(finalized[0])).get('traceEvents')
    if not events:
        fail('finalized trace has no events')

    sys.path.insert(0, os.path.join(REPO, 'scripts'))
    import trace_report
    chains = trace_report.build_chains(events)
    linked_pids = set()
    full = 0
    for tid, stages in chains.items():
        if trace_report.chain_errors(stages):
            fail('chain %s violates stage ordering: %s'
                 % (tid, trace_report.chain_errors(stages)))
        for stage, (_ts, _dur, pid) in stages.items():
            linked_pids.add(pid)
        if {'task_assign', 'generate', 'upload', 'ingest'} <= set(stages):
            full += 1
    if len(linked_pids) < 3:
        fail('trace-linked spans from only %d process(es); want >= 3 '
             '(learner, gather, worker)' % len(linked_pids))
    if full < 1:
        fail('no chain covers task_assign+generate+upload+ingest')
    print('trace OK: %d events, %d chains (%d full), %d linked processes'
          % (len(events), len(chains), full, len(linked_pids)))

    # -- trace_report emits a non-empty critical path ---------------------
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts', 'trace_report.py'),
         trace_dir], capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        fail('trace_report exited rc=%d: %s'
             % (proc.returncode, proc.stderr[-400:]))
    if 'generation->gradient' not in proc.stdout:
        fail('trace_report emitted no generation->gradient line')
    print(proc.stdout)
    print('TRACE SMOKE PASSED')


if __name__ == '__main__':
    main()
