"""Durable training plane: framed WAL records, the episode spool, the
task-ledger journal, and the full learner-restart end-to-end (SIGKILL the
learner mid-run; the restarted process recovers spooled episodes, re-issues
the persisted book, and the surviving gathers reattach without respawning).

The in-memory ledger semantics (assign/admit/reap) are pinned in
tests/test_fault_tolerance.py; this file covers what survives a dead
process.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from handyrl_tpu.fault import RESTORED_ENDPOINT, LedgerJournal, TaskLedger
from handyrl_tpu.utils.fs import (append_framed_record, frame_record,
                                  open_append, read_framed_records)


# ---------------------------------------------------------------------------
# framed records (utils/fs.py)


def _write_frames(path, payloads):
    fd = open_append(str(path))
    try:
        for payload in payloads:
            append_framed_record(fd, payload)
    finally:
        os.close(fd)


def test_framed_record_roundtrip(tmp_path):
    path = tmp_path / 'frames.wal'
    payloads = [b'alpha', b'', b'x' * 4096]
    _write_frames(path, payloads)
    records, valid_bytes, torn = read_framed_records(str(path))
    assert records == payloads
    assert valid_bytes == path.stat().st_size
    assert not torn


def test_framed_record_torn_tail_is_detected_and_truncatable(tmp_path):
    path = tmp_path / 'frames.wal'
    _write_frames(path, [b'good-1', b'good-2'])
    keep = path.stat().st_size
    # a torn final record: header + only half the payload made it to disk
    with open(path, 'ab') as f:
        f.write(frame_record(b'torn-record-payload')[:-7])
    records, valid_bytes, torn = read_framed_records(str(path))
    assert records == [b'good-1', b'good-2']
    assert valid_bytes == keep
    assert torn
    os.truncate(str(path), valid_bytes)
    assert read_framed_records(str(path)) == ([b'good-1', b'good-2'],
                                              keep, False)


def test_framed_record_crc_mismatch_stops_the_scan(tmp_path):
    path = tmp_path / 'frames.wal'
    _write_frames(path, [b'aaaa', b'bbbb', b'cccc'])
    data = bytearray(path.read_bytes())
    # flip a payload byte of the SECOND record: everything from there on
    # is untrusted (WAL semantics: no resynchronization past corruption)
    frame_len = len(frame_record(b'aaaa'))
    data[2 * frame_len - 1] ^= 0xFF   # last payload byte of record 2
    path.write_bytes(bytes(data))
    records, valid_bytes, torn = read_framed_records(str(path))
    assert records == [b'aaaa']
    assert valid_bytes == len(frame_record(b'aaaa'))
    assert torn


# ---------------------------------------------------------------------------
# episode spool


def _make_spool(tmp_path, **kw):
    from handyrl_tpu.spool import EpisodeSpool
    kw.setdefault('segment_mb', 64.0)
    kw.setdefault('keep_segments', 2)
    return EpisodeSpool(str(tmp_path), **kw)


def test_spool_append_recover_roundtrip(tmp_path):
    from handyrl_tpu.connection import pack, unpack
    spool = _make_spool(tmp_path)
    for idx in range(5):
        spool.append(idx, pack({'idx': idx, 'episode': {'n': idx}}))
    spool.close()

    fresh = _make_spool(tmp_path)
    recovered = fresh.recover(2, unpack)
    assert [rec['idx'] for rec in recovered] == [2, 3, 4]
    assert [rec['episode']['n'] for rec in recovered] == [2, 3, 4]
    # horizon past everything -> nothing to replay
    assert _make_spool(tmp_path).recover(5, unpack) == []


def test_spool_truncates_torn_tail_on_recover(tmp_path):
    from handyrl_tpu.connection import pack, unpack
    spool = _make_spool(tmp_path)
    for idx in range(3):
        spool.append(idx, pack({'idx': idx, 'episode': idx}))
    spool.close()
    (segment,) = [os.path.join(spool.root, n)
                  for n in os.listdir(spool.root)]
    good_size = os.path.getsize(segment)
    with open(segment, 'ab') as f:
        f.write(frame_record(pack({'idx': 3, 'episode': 3}))[:-3])

    recovered = _make_spool(tmp_path).recover(0, unpack)
    assert [rec['idx'] for rec in recovered] == [0, 1, 2]
    assert os.path.getsize(segment) == good_size   # tail truncated in place


def test_spool_rotation_gc_and_restart_sequencing(tmp_path):
    from handyrl_tpu.connection import pack, unpack
    # ~1KB segments: every append rotates, so each record is its own file
    spool = _make_spool(tmp_path, segment_mb=0.0001, keep_segments=1)
    for idx in range(6):
        spool.append(idx, pack({'idx': idx, 'episode': 'x' * 256}))
    segments = sorted(os.listdir(spool.root))
    assert len(segments) == 6

    # horizon 4: segments holding idx 0..3 are eligible, the newest ONE of
    # them is kept as cushion (keep_segments=1) -> 3 removed
    assert spool.gc(4) == 3
    assert len(sorted(os.listdir(spool.root))) == 3
    # the survivors still replay everything past the horizon
    recovered = _make_spool(tmp_path, keep_segments=1).recover(4, unpack)
    assert [rec['idx'] for rec in recovered] == [4, 5]
    spool.close()

    # a restarted spool appends into a FRESH segment numbered past every
    # survivor — two generations never interleave within one file
    fresh = _make_spool(tmp_path, segment_mb=0.0001, keep_segments=1)
    fresh.recover(6, unpack)
    fresh.append(6, pack({'idx': 6, 'episode': 'y'}))
    fresh.close()
    newest = sorted(os.listdir(fresh.root))[-1]
    assert newest > sorted(os.listdir(fresh.root))[-2]


# ---------------------------------------------------------------------------
# ledger journal: snapshot + delta persistence


class _Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


def test_ledger_journal_roundtrip_preserves_payloads(tmp_path):
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    ledger.journal = LedgerJournal(str(tmp_path))
    # int-keyed model_id is the regression trap: a JSON journal would
    # stringify the keys and break the byte-identical re-issue contract
    t0 = {'role': 'g', 'model_id': {0: 5, 1: 3}, 'sample_key': 17}
    t1 = {'role': 'e', 'model_id': {0: 5}, 'sample_key': 4}
    t2 = {'role': 'g', 'model_id': {0: 5}, 'sample_key': 18}
    tid0 = ledger.assign('ep-a', t0)
    ledger.assign('ep-a', t1)
    ledger.assign('ep-b', t2)
    ledger.admit([{'args': {'task_id': tid0}}])
    ledger.flush_journal()
    ledger.journal.close()

    state = LedgerJournal(str(tmp_path)).load()
    assert state['next_tid'] == 3
    assert sorted(state['tasks']) == [1, 2]
    assert state['tasks'][1] == {'role': 'e', 'model_id': {0: 5},
                                 'sample_key': 4}
    assert state['tasks'][2]['model_id'] == {0: 5}

    # restore into a fresh book: the outstanding tasks re-issue with their
    # ORIGINAL payloads, ahead of fresh work, exactly once
    restored = TaskLedger(deadline=30.0, clock=_Clock())
    restored.restore_state(state)
    assert restored.outstanding() == 2
    assert restored.outstanding_by_endpoint() == {RESTORED_ENDPOINT: 2}
    first, second = restored.next_reissue(), restored.next_reissue()
    assert {first['sample_key'], second['sample_key']} == {4, 18}
    assert restored.next_reissue() is None
    # a fresh assignment must not collide with a restored task_id
    assert restored.assign('ep-new', {'role': 'g', 'model_id': {}}) == 3


def test_ledger_journal_snapshot_folds_deltas_and_replays_idempotently(
        tmp_path):
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    ledger.journal = LedgerJournal(str(tmp_path))
    ledger.assign('ep', {'role': 'g', 'sample_key': 0})
    tid1 = ledger.assign('ep', {'role': 'g', 'sample_key': 1})
    ledger.admit([{'args': {'task_id': tid1}}])
    ledger.flush_journal()
    # epoch sync: snap the book, truncate the delta journal
    ledger.journal.snapshot(ledger.snapshot_state())
    assert os.path.getsize(os.path.join(str(tmp_path),
                                        LedgerJournal.DELTA)) == 0
    # post-snapshot churn journals as fresh deltas
    ledger.assign('ep', {'role': 'g', 'sample_key': 2})
    ledger.journal.close()

    state = LedgerJournal(str(tmp_path)).load()
    assert sorted(state['tasks']) == [0, 2]
    assert state['next_tid'] == 3
    # replay tolerates ops against tids the snapshot already folded in:
    # 'c'/'x'/'s' on an unknown tid are no-ops, not corruption
    journal = LedgerJournal(str(tmp_path))
    journal.record('c', tid1)
    journal.record('s', 99)
    journal.close()
    again = LedgerJournal(str(tmp_path)).load()
    assert sorted(again['tasks']) == [0, 2]
    assert again['reissue'] == state['reissue']


def test_ledger_journal_torn_delta_tail_truncates_on_load(tmp_path):
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    ledger.journal = LedgerJournal(str(tmp_path))
    ledger.assign('ep', {'role': 'g', 'sample_key': 7})
    ledger.journal.close()
    delta = os.path.join(str(tmp_path), LedgerJournal.DELTA)
    good_size = os.path.getsize(delta)
    with open(delta, 'ab') as f:
        f.write(b'HRLW\x00\x00\xff\xff')   # header promising absent bytes

    state = LedgerJournal(str(tmp_path)).load()
    assert sorted(state['tasks']) == [0]
    assert state['tasks'][0]['sample_key'] == 7
    assert os.path.getsize(delta) == good_size


def test_restored_task_cancel_closes_the_unflushed_completion_window(
        tmp_path):
    """The one crash window: an episode was admitted (it reached the spool)
    but its 'c' record never flushed. On restart the spool recovery cancels
    the task straight out of the restored state, so it neither re-issues
    nor double-counts — and a reattached gather's replayed upload for a
    cancelled tid drops as an ordinary duplicate."""
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    ledger.journal = LedgerJournal(str(tmp_path))
    spooled = {'role': 'g', 'sample_key': 5}
    lost = {'role': 'g', 'sample_key': 6}
    tid_spooled = ledger.assign('ep', spooled)
    ledger.assign('ep', lost)
    ledger.admit([{'args': {'task_id': tid_spooled}}])
    # crash here: the completion was never flushed to the journal
    ledger.journal.close()

    state = LedgerJournal(str(tmp_path)).load()
    assert sorted(state['tasks']) == [0, 1]
    # spool recovery: the recovered episode's task_id cancels its book entry
    state['tasks'].pop(tid_spooled, None)
    restored = TaskLedger(deadline=30.0, clock=_Clock())
    restored.restore_state(state)
    reissued = restored.next_reissue()
    assert reissued == {'role': 'g', 'sample_key': 6}   # lost, sans task_id
    assert restored.next_reissue() is None
    # the replayed upload for the spooled episode is a duplicate, not a count
    assert restored.admit([{'args': {'task_id': tid_spooled}}]) == []
    assert restored.stats['duplicates'] == 1


def test_restored_reissue_skips_tasks_a_reattached_gather_completed():
    ledger = TaskLedger(deadline=30.0, clock=_Clock())
    state = {'tasks': {0: {'role': 'g', 'sample_key': 0},
                       1: {'role': 'g', 'sample_key': 1}},
             'reissue': [], 'next_tid': 2}
    ledger.restore_state(state)
    # a surviving gather replays its resend buffer BEFORE the next 'args'
    # request drains the restored queue: task 0 completes normally
    assert len(ledger.admit([{'args': {'task_id': 0}}])) == 1
    assert ledger.next_reissue() == {'role': 'g', 'sample_key': 1}
    assert ledger.next_reissue() is None   # 0 must not re-issue


# ---------------------------------------------------------------------------
# config validation


def test_durability_config_validation():
    from handyrl_tpu.config import apply_defaults
    args = apply_defaults({})
    dur = args['train_args']['durability']
    assert dur['spool'] is True and dur['ledger_snapshot'] is True
    with pytest.raises(AssertionError):
        apply_defaults({'train_args': {'durability': {'segment_mb': 0}}})
    with pytest.raises(AssertionError):
        apply_defaults({'train_args': {'durability': {'keep_segments': -1}}})
    with pytest.raises(AssertionError):
        apply_defaults({'train_args': {'league': {
            'rating_flush_seconds': -1}}})


# ---------------------------------------------------------------------------
# learner-restart end-to-end: SIGKILL the learner, restart it, and require
# the durable plane to hand back every admitted episode + in-flight task
# while the surviving gathers reattach in place


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


def _wait_for(predicate, deadline, poll=0.5):
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_learner_restart_zero_loss(tmp_path):
    """SIGKILL the learner mid-run, restart it with ``restart_epoch: -1``:
    the restarted process must adopt the run token, restore the ledger
    book, and finish the full epoch budget while the ORIGINAL worker-host
    gathers reattach through the resume handshake — zero gather respawns."""
    entry_port, data_port = 21930, 21931
    model_dir = str(tmp_path / 'models')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {'model_dir': model_dir})
    worker_py.write_text(WORKER_SCRIPT)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                'HANDYRL_TPU_DATA_PORT': str(data_port),
                'PYTHONPATH': repo + os.pathsep
                + os.environ.get('PYTHONPATH', '')}

    log1 = open(tmp_path / 'learner1.log', 'w')
    log2 = open(tmp_path / 'learner2.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner2 = worker = None
    learner1 = subprocess.Popen([sys.executable, str(learner_py)],
                                env=base_env, stdout=log1,
                                stderr=subprocess.STDOUT)
    try:
        time.sleep(3)
        worker = subprocess.Popen([sys.executable, str(worker_py)],
                                  env=base_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        def says(path, needle):
            return needle in (tmp_path / path).read_text()

        # let the run get past warmup (the fleet is generating and the
        # ledger book is live), then murder the learner outright
        assert _wait_for(lambda: says('learner1.log', 'started training')
                         or learner1.poll() is not None,
                         time.time() + 240), 'fleet never reached warmup'
        assert learner1.poll() is None, 'learner died before the kill'
        time.sleep(2)   # a little mid-epoch churn: in-flight tasks + spool
        learner1.send_signal(signal.SIGKILL)
        learner1.wait(timeout=30)

        learner2 = subprocess.Popen([sys.executable, str(learner_py)],
                                    env=base_env, stdout=log2,
                                    stderr=subprocess.STDOUT)

        def done():
            return (says('learner2.log', 'LEARNER DONE')
                    or learner2.poll() is not None)
        assert _wait_for(done, time.time() + 300), \
            'restarted learner hung'
        learner2.wait(timeout=120)
        worker.wait(timeout=120)
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

    out2 = (tmp_path / 'learner2.log').read_text()
    worker_out = (tmp_path / 'worker.log').read_text()

    # the durable plane actually engaged on restart
    assert 'durable plane: restored ledger book' in out2
    # the surviving gathers rode through: resume handshake, no respawn
    assert 'reattached across a learner restart' in worker_out
    assert 'respawning' not in worker_out, \
        'a gather respawned — the fleet did not survive the restart'
    # the full budget completed with converged accounting
    done_line = [l for l in out2.splitlines()
                 if l.startswith('LEARNER DONE')][0]
    _, _, epoch, _num_episodes, num_returned = done_line.split()
    assert int(epoch) == 3
    assert int(num_returned) >= 36
    ledger = json.loads(out2.split('LEDGER', 1)[1].strip().splitlines()[0])
    assert ledger['completed'] <= ledger['assigned'] + ledger['reissued']
