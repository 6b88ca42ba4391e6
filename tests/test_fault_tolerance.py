"""Fault tolerance: task ledger accounting, atomic checkpoints, shm
cleanup, and the full chaos end-to-end (learner + worker host over real TCP
with an injected gather kill and a severed data socket).

Hub-level liveness/heartbeat behavior is pinned in tests/test_hub.py.
"""

import json
import os
import signal
import subprocess
import sys
import time
from multiprocessing import shared_memory

import numpy as np
import pytest

from handyrl_tpu.fault import Backoff, TaskLedger, parse_chaos
from handyrl_tpu.utils.fs import atomic_write_bytes


# ---------------------------------------------------------------------------
# task ledger


class _Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


def test_ledger_assign_complete_roundtrip():
    clock = _Clock()
    ledger = TaskLedger(deadline=30.0, clock=clock)
    task = {'role': 'g', 'model_id': {0: 1, 1: 1}, 'player': [0, 1]}
    tid = ledger.assign('ep-a', task)
    assert task['task_id'] == tid
    assert ledger.outstanding() == 1
    admitted = ledger.admit([{'args': {'task_id': tid}, 'outcome': {}}])
    assert len(admitted) == 1
    assert ledger.outstanding() == 0
    assert ledger.stats['completed'] == 1


def test_ledger_drops_duplicate_uploads():
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    tid = ledger.assign('ep-a', {'role': 'g', 'model_id': {}})
    first = ledger.admit([{'args': {'task_id': tid}}])
    dup = ledger.admit([{'args': {'task_id': tid}}])
    assert len(first) == 1 and len(dup) == 0
    assert ledger.stats['duplicates'] == 1
    # items with no task_id (pre-ledger peers) and Nones pass untouched
    passthrough = ledger.admit([None, {'args': {}}])
    assert len(passthrough) == 2


def test_ledger_reissues_on_endpoint_failure_without_recounting():
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    orig = {'role': 'g', 'model_id': {0: 5}, 'player': [0, 1]}
    ledger.assign('ep-dead', orig)
    ledger.assign('ep-live', {'role': 'e', 'model_id': {}})
    assert ledger.fail_endpoint('ep-dead') == 1
    assert ledger.pending_reissue() == 1
    again = ledger.next_reissue()
    # the re-issued payload is the original task, sans the stale task_id
    assert again['role'] == 'g' and again['model_id'] == {0: 5}
    assert 'task_id' not in again
    new_tid = ledger.assign('ep-live', again)
    assert new_tid != orig['task_id']
    assert ledger.outstanding() == 2
    assert ledger.fail_endpoint('ep-dead') == 0   # nothing left booked there


def test_ledger_deadline_reap():
    clock = _Clock()
    ledger = TaskLedger(deadline=10.0, clock=clock)
    ledger.assign('ep', {'role': 'g', 'model_id': {}})
    assert ledger.reap() == 0
    clock.now += 11.0
    assert ledger.reap() == 1
    assert ledger.outstanding() == 0
    assert ledger.pending_reissue() == 1
    assert ledger.stats['expired'] == 1
    # a straggler completing AFTER expiry is treated as a duplicate
    assert ledger.admit([{'args': {'task_id': 0}}]) == []


def test_backoff_is_bounded_and_jittered():
    backoff = Backoff(initial=1.0, maximum=8.0, jitter=0.5)
    delays = [backoff.next_delay() for _ in range(8)]
    assert all(0.5 <= d <= 8.0 for d in delays)
    assert delays[-1] > 2.0          # grew toward the ceiling
    backoff.reset()
    assert backoff.next_delay() <= 1.0


def test_parse_chaos():
    assert parse_chaos('') == {}
    assert parse_chaos('kill_gather=8,max_kills=2') == {
        'kill_gather': 8.0, 'max_kills': 2.0}
    assert parse_chaos('garbage') == {}   # malformed entries are ignored


# ---------------------------------------------------------------------------
# atomic checkpoint writes


def test_atomic_write_publishes_complete_bytes(tmp_path):
    target = tmp_path / 'latest.ckpt'
    atomic_write_bytes(str(target), b'v1')
    assert target.read_bytes() == b'v1'
    atomic_write_bytes(str(target), b'v2-longer')
    assert target.read_bytes() == b'v2-longer'
    assert os.listdir(tmp_path) == ['latest.ckpt']   # no temp litter


def test_interrupted_save_never_corrupts_target(tmp_path, monkeypatch):
    """A crash anywhere before the final rename leaves the old checkpoint
    bytes fully intact and no stray temp files."""
    target = tmp_path / 'latest.ckpt'
    target.write_bytes(b'GOOD-CHECKPOINT')

    # crash at the publish step (after the temp write)
    def boom(src, dst):
        raise OSError('simulated crash mid-save')
    monkeypatch.setattr(os, 'replace', boom)
    with pytest.raises(OSError):
        atomic_write_bytes(str(target), b'half-written-new-bytes')
    assert target.read_bytes() == b'GOOD-CHECKPOINT'
    assert os.listdir(tmp_path) == ['latest.ckpt']

    # crash during the temp write itself (e.g. ENOSPC / power loss window)
    monkeypatch.undo()

    class _ExplodingBytes(bytes):
        pass
    real_fdopen = os.fdopen

    def exploding_fdopen(fd, *a, **k):
        f = real_fdopen(fd, *a, **k)
        orig_write = f.write

        def write(data):
            orig_write(data[: len(data) // 2])
            raise OSError('simulated torn write')
        f.write = write
        return f
    monkeypatch.setattr(os, 'fdopen', exploding_fdopen)
    with pytest.raises(OSError):
        atomic_write_bytes(str(target), b'another-new-version')
    assert target.read_bytes() == b'GOOD-CHECKPOINT'
    assert os.listdir(tmp_path) == ['latest.ckpt']


# ---------------------------------------------------------------------------
# shared-memory arena cleanup


def test_arena_ring_close_is_idempotent_and_unlinks():
    from handyrl_tpu.ops.shm_batch import ArenaRing, batch_spec
    spec = batch_spec({'a': np.zeros((4, 4), np.float32)})
    ring = ArenaRing(spec, slots=2)
    names = list(ring.names)
    assert len(names) == 2
    ring.close()
    ring.close()   # double close/unlink must be a no-op, not an error
    for name in names:
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)


# ---------------------------------------------------------------------------
# chaos end-to-end: gather kill + severed data socket over real TCP


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
                          'fault_tolerance': {
                              'heartbeat_interval': 1.0,
                              'liveness_timeout': 8.0,
                              'rpc_timeout': 30.0,
                              'task_deadline': 30.0,
                              'reconnect_initial_delay': 0.25,
                              'reconnect_max_delay': 2.0,
                              'reconnect_max_tries': 60}}}
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


def _wait_for(predicate, deadline, poll=1.0):
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_chaos_remote_cluster_survives_faults(tmp_path):
    """A remote-cluster run with (a) the only gather SIGKILLed mid-run and
    (b) the data socket severed between epochs must still complete its
    2-epoch budget with converged accounting: the stranded tasks are
    re-issued, the respawned/reconnected gather resumes, and the learner
    finishes instead of hanging on episodes that will never arrive."""
    from tests.proxy import ChaosProxy

    entry_port, data_port = 21910, 21911
    model_dir = str(tmp_path / 'models')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {'model_dir': model_dir})
    worker_py.write_text(WORKER_SCRIPT)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'PYTHONPATH': repo + os.pathsep + os.environ.get('PYTHONPATH', '')}
    learner_env = {**base_env, 'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                   'HANDYRL_TPU_DATA_PORT': str(data_port)}

    proxy = ChaosProxy(target_port=data_port)
    # the worker host dials the data port THROUGH the proxy (reconnects
    # included); chaos kills its single gather once, early in the run
    worker_env = {**base_env, 'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                  'HANDYRL_TPU_DATA_PORT': str(proxy.port),
                  'HANDYRL_TPU_CHAOS': 'kill_gather=6,max_kills=1,seed=3'}

    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)],
                               env=learner_env, stdout=learner_log,
                               stderr=subprocess.STDOUT)
    worker = None
    try:
        time.sleep(3)   # let the entry/data servers bind
        worker = subprocess.Popen([sys.executable, str(worker_py)],
                                  env=worker_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        def learner_says(needle):
            return needle in (tmp_path / 'learner.log').read_text()

        # generation is underway (minimum episodes reached), so the gather
        # holds prefetched/in-flight booked tasks more or less continuously
        assert _wait_for(
            lambda: learner_says('started training')
            or learner.poll() is not None, time.time() + 240), \
            'fleet never produced the minimum episodes'

        # fault 2: hard-sever every data connection, repeatedly, until the
        # gather demonstrably went through its supervised reconnect AND the
        # server stranded + re-issued booked tasks (the kill above may have
        # already produced the re-issue); after each cut the gather must
        # back off, redial (through the proxy) and resume — the run cannot
        # finish short of episodes, so severed outstanding work forces the
        # re-issue path
        def both_faults_observed():
            return ('reconnecting' in (tmp_path / 'worker.log').read_text()
                    and learner_says('re-issuing'))

        deadline = time.time() + 240
        while (not both_faults_observed()
               and learner.poll() is None and time.time() < deadline):
            proxy.sever()
            time.sleep(1.5)

        def done():
            return (os.path.exists(os.path.join(model_dir, '2.ckpt'))
                    or learner.poll() is not None)
        assert _wait_for(done, time.time() + 240), \
            'learner hung after injected faults'
        assert os.path.exists(os.path.join(model_dir, '2.ckpt')), \
            'run did not reach its epoch budget'

        # with training over, the whole actor tree must wind down on its
        # own: None tasks -> workers exit -> gathers exit 0 -> host exits
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
        proxy.close()
        learner_log.close()
        worker_log.close()

    learner_out = (tmp_path / 'learner.log').read_text()
    worker_out = (tmp_path / 'worker.log').read_text()

    # the chaos kill actually happened and the supervisor recovered it
    assert 'chaos: killing gather' in worker_out
    assert 'respawning' in worker_out
    # the severed gather went through the supervised-reconnect path
    assert 'reconnecting' in worker_out
    # the learner noticed the dead peer and re-issued its booked tasks
    assert 'disconnected' in learner_out
    # only the LEDGER line itself: trailing diagnostics (e.g. the
    # graftlint-sanitizer exit report) may follow it in the stream
    ledger = json.loads(
        learner_out.split('LEDGER', 1)[1].strip().splitlines()[0])
    assert ledger['reissued'] >= 1, 'stranded tasks were never re-issued'
    assert ledger['completed'] <= ledger['assigned']

    # accounting converged: 2 epochs at minimum=12/update=12 means at least
    # 36 returned episodes actually fed training
    done_line = [l for l in learner_out.splitlines()
                 if l.startswith('LEARNER DONE')][0]
    _, _, epoch, num_episodes, num_returned = done_line.split()
    assert int(epoch) == 2
    assert int(num_returned) >= 36
