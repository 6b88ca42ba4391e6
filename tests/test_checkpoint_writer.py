"""The checkpoint writer beside the fused loop (train.py ``_CheckpointWriter``,
``Learner._hand_over_checkpoint`` / ``_write_checkpoint`` /
``_announce_checkpoint`` / ``_collect_checkpoint``): the loop hands an epoch's
checkpoint over as plain host values and enqueues the next dispatch while the
fsynced writes run, and every guarantee of the synchronous code holds: every
epoch's files, in order, one write in flight at most, announcements only
after the fsync, a wait before anything that reads ``model_dir``."""

import json
import os
import threading
import time
import zlib

import jax
import numpy as np
import pytest
from flax import serialization

from handyrl_tpu import telemetry
from handyrl_tpu import train as train_mod
from handyrl_tpu.config import apply_defaults
from handyrl_tpu.train import Learner
from handyrl_tpu.utils.fetch import fetch_tree
from handyrl_tpu.utils.fs import verify_checkpoint


def _raw(tmp_path, **over):
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            # batch 12 is not divisible by the 8-device test mesh: the
            # trainer stays single-device and the run takes the fused loop
            'batch_size': 12, 'forward_steps': 4, 'compress_steps': 2,
            'update_episodes': 40, 'minimum_episodes': 40, 'epochs': 3,
            'generation_envs': 16, 'num_batchers': 1,
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 4, 'seed': 7,
            'model_dir': str(tmp_path / 'models'),
            'metrics_jsonl': str(tmp_path / 'metrics.jsonl'),
        },
    }
    raw['train_args'].update(over)
    return raw


class _Writes:
    """``train.checksummed_write_bytes`` patched: records every call, holds
    each one until ``gate`` is set and for ``delay`` seconds, raises
    ``error`` if one is given, then writes for real."""

    def __init__(self, monkeypatch, delay=0.0, gated=False, error=None):
        self.calls = []                 # the files, in the order asked for
        self.delay, self.error = delay, error
        self.gate = threading.Event()
        if not gated:
            self.gate.set()
        real = train_mod.checksummed_write_bytes

        def write(path, data):
            self.calls.append(os.path.basename(path))
            assert self.gate.wait(30), 'the test never opened the gate'
            time.sleep(self.delay)
            if self.error is not None:
                raise self.error
            real(path, data)
        monkeypatch.setattr(train_mod, 'checksummed_write_bytes', write)


@pytest.fixture
def learner(tmp_path):
    ln = Learner(args=apply_defaults(_raw(tmp_path)))
    yield ln
    ln.shutdown()


def _host_state(learner, offset):
    """The learner's train state on the host, its params shifted by
    ``offset`` so that every epoch's bytes are its own."""
    state = fetch_tree(learner.trainer.state)
    return state._replace(params=jax.tree_util.tree_map(
        lambda x: np.asarray(x) + np.asarray(offset, x.dtype), state.params))


def _hand_over(learner, epoch):
    """One boundary's checkpoint as the fused loop hands it over; returns
    the bytes ``<epoch>.ckpt`` must hold."""
    learner.trainer.steps = 10 * epoch
    state = _host_state(learner, epoch)
    job, _waited = learner._hand_over_checkpoint(state)
    assert learner.model_epoch == job.epoch == epoch
    learner._ckpt_writer.submit(job)    # the boundary's last act
    return serialization.to_bytes(state.params)


def _read(path):
    with open(path, 'rb') as f:
        return f.read()


def _trainer_state(learner):
    return serialization.msgpack_restore(_read(learner.trainer_state_path()))


def _counter(name):
    return telemetry.REGISTRY.counter(name).value


def test_every_boundary_writes_its_own_files_one_job_in_flight(
        learner, monkeypatch):
    """(a) N boundaries leave N numbered checkpoints, each CRC-verified and
    holding ITS epoch's bytes, ``latest.ckpt`` equal to the last; the second
    hand-over waits for the first."""
    _Writes(monkeypatch, delay=0.05)
    t_start = time.perf_counter()
    writes0 = _counter('checkpoint_writes_total')
    waited0 = _counter('checkpoint_wait_seconds_total')
    expect = {epoch: _hand_over(learner, epoch) for epoch in (1, 2, 3)}
    assert learner._ckpt_writer.busy()
    with pytest.raises(RuntimeError, match='write outstanding'):
        # depth one, never two
        learner._ckpt_writer.submit(learner._ckpt_writer._pending[0])
    learner._collect_checkpoint()
    assert not learner._ckpt_writer.busy()

    for epoch, raw in expect.items():
        path = learner.model_path(epoch)
        assert verify_checkpoint(path) == (True, 'ok')
        assert _read(path) == raw
        with open(path + '.layout') as f:
            assert json.load(f)['steps'] == 10 * epoch
    assert sorted(n for n in os.listdir(os.path.dirname(path))
                  if n.endswith('.ckpt')) == [
        '1.ckpt', '2.ckpt', '3.ckpt', 'latest.ckpt', 'trainer_state.ckpt']
    assert _read(learner.latest_model_path()) == expect[3]
    assert verify_checkpoint(learner.latest_model_path()) == (True, 'ok')
    assert verify_checkpoint(learner.trainer_state_path()) == (True, 'ok')
    assert _trainer_state(learner)['steps'] == 30

    waits = telemetry.spans('checkpoint_wait', since=t_start)
    assert len(waits) == 3                       # one a boundary, even at 0
    seconds = [w['t1'] - w['t0'] for w in waits]
    assert seconds[0] < 0.01                     # nothing was outstanding
    assert min(seconds[1:]) > 0.1                # three files x 50 ms, less
    assert _counter('checkpoint_writes_total') - writes0 == 3
    # the counter holds the boundaries' waits and the last collection's
    waited = _counter('checkpoint_wait_seconds_total') - waited0
    assert sum(seconds) <= waited + 1e-3
    assert waited < sum(seconds) + 0.5
    # the writer's spans are the same stages the synchronous code records
    writes = telemetry.spans('checkpoint_write', since=t_start)
    assert [w['attrs']['files'] for w in writes] == [3, 3, 3]
    assert len(telemetry.spans('checkpoint_serialize', since=t_start)) == 6


@pytest.mark.timeout(600)
def test_next_dispatch_opens_before_the_boundarys_write_closes(
        tmp_path, monkeypatch):
    """(b) In a fused run the iteration after a boundary enqueues its
    dispatch while that boundary's files are still being written."""
    _Writes(monkeypatch, delay=0.1)
    t_start = time.perf_counter()
    ln = Learner(args=apply_defaults(_raw(tmp_path)))
    ln.run()
    assert ln.model_epoch == 3
    recs = telemetry.spans(since=t_start)
    boundaries = [r for r in recs if r['name'] == 'epoch_boundary']
    writes = [r for r in recs if r['name'] == 'checkpoint_write']
    dispatches = [r for r in recs if r['name'] == 'dispatch']
    assert len(boundaries) == len(writes) == 3
    overlapped = 0
    for boundary, write in zip(boundaries, writes):
        assert boundary['t0'] < write['t0']
        later = [d for d in dispatches if d['t0'] > boundary['t1']]
        if later:     # the last boundary ends the run: no dispatch follows
            assert later[0]['t0'] < write['t1']
            overlapped += 1
    assert overlapped == 2
    # every epoch's record says what its boundary waited for the writer
    rows = [json.loads(line) for line in
            (tmp_path / 'metrics.jsonl').read_text().splitlines()]
    assert all(row['fused']['ckpt_wait_s'] >= 0 for row in rows)
    for epoch in (1, 2, 3):
        assert verify_checkpoint(ln.model_path(epoch)) == (True, 'ok')


def test_announcements_follow_the_write_with_the_boundarys_marks(
        learner, monkeypatch):
    """(c) write(E) < publish(E) < GC < durable sync, with the episode counts
    and the spool horizon captured at E's boundary although later ones exist
    by the time the write ends."""
    writes = _Writes(monkeypatch, gated=True)
    order = []
    monkeypatch.setattr(
        learner, '_publish_checkpoint', lambda steps, epoch: order.append(
            ('publish', epoch, steps, list(writes.calls))))
    monkeypatch.setattr(learner, '_gc_checkpoints',
                        lambda: order.append(('gc',)))
    monkeypatch.setattr(learner, '_sync_durable_state',
                        lambda marks: order.append(('sync', marks)))
    learner.num_episodes, learner.num_results = 120, 7
    learner.num_returned_episodes = 100
    monkeypatch.setattr(learner._assembler, 'min_open_mark', lambda: 80)
    _hand_over(learner, 1)
    # the loop goes on while the write is held: more games come back
    learner.num_episodes, learner.num_results = 300, 9
    learner.num_returned_episodes = 250
    monkeypatch.setattr(learner._assembler, 'min_open_mark', lambda: 200)
    learner._collect_checkpoint(block=False)
    assert order == []                  # nothing announced before the fsync
    writes.gate.set()
    learner._collect_checkpoint()
    files = ['1.ckpt', 'latest.ckpt', 'trainer_state.ckpt']
    assert order == [
        ('publish', 1, 10, files), ('gc',),
        ('sync', {'num_episodes': 120, 'num_results': 7,
                  'num_returned_episodes': 100, 'spool_horizon': 80})]


@pytest.mark.parametrize('site', ['_collect_checkpoint', 'next_hand_over'])
def test_a_failed_write_is_raised_on_the_loop_thread(learner, monkeypatch,
                                                     site):
    """(d) An ``OSError`` in the writer reaches the loop thread at the next
    wait, as the write raised it; nothing is announced for that epoch."""
    _Writes(monkeypatch, error=OSError(28, 'No space left on device'))
    announced = []
    monkeypatch.setattr(learner, '_announce_checkpoint', announced.append)
    _hand_over(learner, 1)
    with pytest.raises(OSError, match='No space left'):
        if site == 'next_hand_over':
            _hand_over(learner, 2)
        else:
            learner._collect_checkpoint()
    assert announced == [] and not learner._ckpt_writer.busy()
    learner._collect_checkpoint()       # raised once, not kept


@pytest.mark.parametrize('site', ['final_flush', '_rollback_source',
                                  '_apply_rollback'])
def test_readers_of_model_dir_wait_for_the_write_in_flight(
        learner, monkeypatch, site):
    """(e) ``final_flush`` and the guard's rollback wait for (and announce)
    a job in flight first. (The ``model`` RPC and resume never run beside
    the fused loop: ``run()`` takes ``server()`` or ``_run_fused``, and
    resume loads in ``__init__``.)"""
    writes = _Writes(monkeypatch, gated=True)
    announced = []
    announce = learner._announce_checkpoint
    monkeypatch.setattr(learner, '_announce_checkpoint', lambda job: (
        announced.append((job.epoch, list(writes.calls))), announce(job)))
    raw = _hand_over(learner, 1)
    assert learner._ckpt_writer.busy()
    opener = threading.Timer(0.2, writes.gate.set)
    opener.start()
    try:
        if site == 'final_flush':
            learner._fused_active = True
            learner.trainer.steps = 15
            learner.final_flush()
            # drained, then written inline: the flush is the LAST trainer
            # state on disk and its files follow the job's
            assert _trainer_state(learner)['steps'] == 15
            assert writes.calls == 2 * ['1.ckpt', 'latest.ckpt',
                                          'trainer_state.ckpt']
        elif site == '_rollback_source':
            epoch, blob = learner._rollback_source()
            assert epoch == 1
            assert serialization.msgpack_restore(blob)['steps'] == 10
        else:
            learner.wrapper.params = jax.tree_util.tree_map(
                np.zeros_like, learner.wrapper.params)
            learner._apply_rollback(1)
            assert serialization.to_bytes(learner.wrapper.params) == raw
    finally:
        opener.join(5)
    assert announced[0] == (1, ['1.ckpt', 'latest.ckpt', 'trainer_state.ckpt'])
    assert not learner._ckpt_writer.busy()


def _published(root):
    """The registry's versions of line ``ttt`` and its champion."""
    from handyrl_tpu.serving.registry import ModelRegistry
    reg = ModelRegistry(root)
    line = reg.describe()['ttt']
    return ({int(v): meta for v, meta in line['versions'].items()},
            int(line['champion']))


_PUBLISH = {'publish': True, 'line': 'ttt', 'auto_promote': True}


def test_a_skip_epoch_during_the_write_announces_the_written_epoch(
        tmp_path, monkeypatch):
    """``checkpoint_interval`` 2: epoch 2 is handed over, epoch 3 skips (and
    bumps the epoch) while 2's files are still being written. What is then
    announced is epoch 2 and ``2.ckpt``, not the live epoch."""
    writes = _Writes(monkeypatch, gated=True)
    ln = Learner(args=apply_defaults(_raw(
        tmp_path, checkpoint_interval=2, serving=_PUBLISH)))
    try:
        ln.model_epoch = 1
        _hand_over(ln, 2)
        ln._collect_checkpoint(block=False)      # the next iteration's poll
        ln.update_model(None, 25, write_files=False)   # epoch 3: no files
        assert ln.model_epoch == 3 and ln._ckpt_writer.busy()
        writes.gate.set()
        ln._collect_checkpoint()
        versions, champion = _published(str(tmp_path / 'models'))
        assert sorted(versions) == [2] and champion == 2
        assert versions[2]['steps'] == 20
        assert os.path.samefile(versions[2]['path'], ln.model_path(2))
        assert not os.path.exists(ln.model_path(3))
    finally:
        ln.shutdown()


@pytest.mark.timeout(600)
def test_interval_run_publishes_only_epochs_whose_files_exist(
        tmp_path, monkeypatch):
    """A fused run with ``checkpoint_interval`` 2 and writes slower than a
    boundary's distance: skip epochs bump the epoch with a write in flight,
    and the registry still holds exactly the epochs that wrote, each
    pointing at a file that passes verification."""
    _Writes(monkeypatch, delay=0.15)
    t_start = time.perf_counter()
    ln = Learner(args=apply_defaults(_raw(
        tmp_path, epochs=5, checkpoint_interval=2, serving=_PUBLISH)))
    ln.run()
    assert ln.model_epoch == 5
    recs = telemetry.spans(since=t_start)
    writes = [r for r in recs if r['name'] == 'checkpoint_write']
    bumps = [r['t0'] for r in recs if r['name'] == 'epoch_boundary']
    # the case this test is for did occur: a boundary opened inside a write
    assert any(w['t0'] < t < w['t1'] for w in writes for t in bumps)
    versions, champion = _published(str(tmp_path / 'models'))
    assert sorted(versions) == [2, 4, 5] and champion == 5   # 5 is final
    for epoch, entry in versions.items():
        assert os.path.samefile(entry['path'], ln.model_path(epoch))
        assert verify_checkpoint(entry['path']) == (True, 'ok')
    assert sorted(n for n in os.listdir(tmp_path / 'models')
                  if n[0].isdigit() and n.endswith('.ckpt')) == [
        '2.ckpt', '4.ckpt', '5.ckpt']


@pytest.mark.parametrize('site', ['final_flush', 'run_exit'])
def test_a_failed_write_is_not_swallowed_by_the_preemption_exit(
        learner, monkeypatch, tmp_path, site):
    """A write fails and the preemption exit comes before the loop's next
    wait: the error is raised out of ``final_flush`` (which stays to be
    made) and out of ``run()``, after the flush was still made."""
    writes = _Writes(monkeypatch, error=OSError(28, 'No space left on device'))
    _hand_over(learner, 1)
    while learner._ckpt_writer.busy():
        time.sleep(0.01)
    writes.error = None                 # the disk has room again
    learner._fused_active = True
    learner.trainer.steps = 15
    learner.preempt.signum = 15
    learner.preempt._event.set()
    with pytest.raises(OSError, match='No space left'):
        if site == 'final_flush':
            learner.final_flush()
        else:
            monkeypatch.setattr(learner, '_run_batched', lambda: None)
            learner.run()
    if site == 'final_flush':
        assert not learner._final_flushed
        assert not os.path.exists(learner.trainer_state_path())
        learner.final_flush()           # run()'s exit makes it
    assert learner._final_flushed
    assert _trainer_state(learner)['steps'] == 15
    assert verify_checkpoint(learner.model_path(1)) == (True, 'ok')
    if site == 'run_exit':
        rows = [json.loads(line) for line in
                (tmp_path / 'metrics.jsonl').read_text().splitlines()]
        assert rows[-1]['preempted'] is True and rows[-1]['steps'] == 15


class _InlineExecutor:
    """``ThreadPoolExecutor`` for the reference run: the job is written
    before ``submit`` returns, as the synchronous code wrote it."""

    def __init__(self, *args, **kwargs):
        pass

    def submit(self, fn, *args):
        from concurrent.futures import Future
        future = Future()
        future.set_result(fn(*args))
        return future

    def shutdown(self, wait=True):
        pass


def _preempted_run(tmp_path, monkeypatch, name):
    """A fused run that takes the preemption exit on the first training
    dispatch after its second boundary; returns what it left on disk."""
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    folder = tmp_path / name
    folder.mkdir()
    # a boundary every three chunks or so: the exit lands between two
    ln = Learner(args=apply_defaults(_raw(folder, epochs=-1,
                                          update_episodes=120)))
    step = FusedPipeline.train_step

    def train_step(self, *args, **kwargs):
        out = step(self, *args, **kwargs)
        if ln.model_epoch >= 2:
            ln.preempt.signum = 15
            ln.preempt._event.set()
        return out
    monkeypatch.setattr(FusedPipeline, 'train_step', train_step)
    ln.run()
    monkeypatch.setattr(FusedPipeline, 'train_step', step)
    left = {}
    for fname in sorted(os.listdir(folder / 'models')):
        data = _read(str(folder / 'models' / fname))
        if fname.endswith('.crc'):
            side = json.loads(data)
            left[fname] = (side['size'], side['crc32'])
        elif fname.endswith('.layout'):
            left[fname] = json.loads(data)
        else:
            left[fname] = (len(data), zlib.crc32(data) & 0xffffffff)
    state = serialization.msgpack_restore(
        _read(str(folder / 'models' / 'trainer_state.ckpt')))
    return ln, left, state


@pytest.mark.timeout(600)
def test_preempted_run_leaves_what_inline_writes_leave(tmp_path, monkeypatch):
    """(f) File for file, the names, sizes and CRCs of a fused run ended by
    the preemption exit are those of the same seed with every job written
    inline, and ``trainer_state.ckpt`` holds the flushed step count."""
    _Writes(monkeypatch, delay=0.05)     # jobs really are in flight
    t_start = time.perf_counter()
    ln, left, state = _preempted_run(tmp_path, monkeypatch, 'writer')
    # two boundaries and the flush
    assert len(telemetry.spans('checkpoint_write', since=t_start)) == 3
    assert ln.preempt.fired and ln.model_epoch == 2
    assert state['steps'] == ln.trainer.steps == ln._last_ckpt_steps > 0
    assert {'1.ckpt', '2.ckpt', 'latest.ckpt', 'trainer_state.ckpt'} \
        <= set(left)
    # the flush wrote epoch 2 again, later than the boundary's own write
    assert left['2.ckpt.layout']['steps'] == ln.trainer.steps
    assert left['1.ckpt.layout']['steps'] < ln.trainer.steps

    monkeypatch.setattr(train_mod, 'ThreadPoolExecutor', _InlineExecutor)
    ln2, inline, state2 = _preempted_run(tmp_path, monkeypatch, 'inline')
    assert ln2.trainer.steps == ln.trainer.steps
    assert inline == left
    assert state2['steps'] == state['steps']


def test_span_ring_holds_a_window_and_the_record_before_it():
    """(g) 40,000 spans in a row keep the ``host_block`` from before the
    window readable by ``program_counter_ratio``'s rule (the last record
    that ended at or before the window's opening)."""
    from benchmark.manifest import Manifest
    from benchmark.readers import program_counter_ratio
    from benchmark.record import Run
    assert telemetry.SPAN_RING_SIZE >= 65536
    with telemetry.trace_span('host_block') as first:
        first.set(plies=2048, builder_plies=10)
    for _ in range(40000):
        with telemetry.trace_span('unit_filler'):
            pass
    with telemetry.trace_span('host_block') as last:
        last.set(plies=2048 * 51, builder_plies=10 + 40 * 20)
    run = Run(cell={'name': 'c'}, config={}, traffic={},
              train_args={'generation_envs': 64}, spans={},
              window=(first.t1, last.t1))
    args = Manifest().load_metric('ingest_builder_ply_share')['args']
    assert program_counter_ratio.read(run, **args) == pytest.approx(
        100 * (40 * 20 * 64) / (2048 * 50))
