"""Remote-mode integration: a --train-server learner and a --worker host as
separate OS processes speaking the real TCP protocol (entry handshake on
:9999, gather data connections on :9998) on localhost."""

import os
import signal
import subprocess
import sys
import time

import pytest

LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 15,
                          'minimum_episodes': 15, 'epochs': 1,
                          'forward_steps': 8, 'num_batchers': 1,
                          'inference': {'enabled': %(engine)r},
                          'model_dir': %(model_dir)r}}
    args = apply_defaults(raw)
    learner = Learner(args=args, remote=True)
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


@pytest.mark.slow
@pytest.mark.timeout(600)
@pytest.mark.parametrize('engine', [False, True],
                         ids=['per-worker', 'inference-engine'])
def test_remote_train_server_and_worker(tmp_path, engine):
    model_dir = str(tmp_path / 'models')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {'model_dir': model_dir,
                                            'engine': engine})
    worker_py.write_text(WORKER_SCRIPT)

    env = {**os.environ, 'JAX_PLATFORMS': 'cpu'}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env['PYTHONPATH'] = repo + os.pathsep + env.get('PYTHONPATH', '')

    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)], env=env,
                               stdout=learner_log, stderr=subprocess.STDOUT)
    try:
        time.sleep(3)   # let the entry/worker servers bind
        worker = subprocess.Popen([sys.executable, str(worker_py)], env=env,
                                  stdout=worker_log, stderr=subprocess.STDOUT)
        try:
            deadline = time.time() + 240
            done_path = os.path.join(model_dir, '1.ckpt')
            while time.time() < deadline:
                if os.path.exists(done_path):
                    break
                if learner.poll() is not None:
                    break
                time.sleep(2)
            assert os.path.exists(done_path), 'no checkpoint from remote training'
        finally:
            worker.send_signal(signal.SIGTERM)
            worker.wait(timeout=20)
    finally:
        if learner.poll() is None:
            learner.send_signal(signal.SIGTERM)
        try:
            learner.wait(timeout=20)
        except subprocess.TimeoutExpired:
            learner.kill()
