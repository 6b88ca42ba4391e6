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
    """(b) In a fused run the dispatch that follows a boundary's opening
    (the boundary's own since PR 35, which enqueues it before it fetches the
    train state) is enqueued before that boundary's files are written."""
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
        later = [d for d in dispatches if d['t0'] > boundary['t0']]
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


def _files_left(model_dir):
    """Every file of ``model_dir`` by name: size and CRC of a checkpoint,
    what a sidecar or a layout manifest says."""
    left = {}
    for fname in sorted(os.listdir(model_dir)):
        data = _read(str(model_dir / fname))
        if fname.endswith('.crc'):
            side = json.loads(data)
            left[fname] = (side['size'], side['crc32'])
        elif fname.endswith('.layout'):
            left[fname] = json.loads(data)
        else:
            left[fname] = (len(data), zlib.crc32(data) & 0xffffffff)
    return left


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
    state = serialization.msgpack_restore(
        _read(str(folder / 'models' / 'trainer_state.ckpt')))
    return ln, _files_left(folder / 'models'), state


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


# ---------------------------------------------------------------------------
# the boundary's order (PR 35): pack the train state ON the device, enqueue
# the next dispatch, and only then fetch the pack and write the record. A
# state with a leaf over ``LARGE_LEAF_BYTES`` keeps fetch-then-enqueue, which
# is also these tests' control: with the threshold at 0 every leaf is one.

_ROW_KEYS = ('epoch', 'steps', 'episodes', 'dispatches_gen', 'entropy',
             'grad_norm', 'rho_clip_fraction', 'importance_ratio_mean',
             'guard_nonfinite', 'guard_rollbacks')


def _order_run(tmp_path, monkeypatch, capsys, name, large_leaf_bytes=None,
               arrange=None, **over):
    """A seeded fused run under the boundary's new order, or, with
    ``large_leaf_bytes`` (a number, or a function of the learner), under the
    kept one. Online evaluation's share is held to the episode counts: its
    budget follows the wall clock. Returns the learner, the files it left
    and what it printed and recorded of the trajectory."""
    from handyrl_tpu.utils import fetch
    folder = tmp_path / name
    folder.mkdir()
    share = Learner._run_eval_share
    with monkeypatch.context() as patch:
        patch.setattr(Learner, '_run_eval_share',
                      lambda self, evaluator, tracker, budget_s=None:
                      share(self, evaluator, tracker))
        ln = Learner(args=apply_defaults(_raw(folder, **over)))
        if large_leaf_bytes is not None:
            patch.setattr(fetch, 'LARGE_LEAF_BYTES',
                          large_leaf_bytes(ln) if callable(large_leaf_bytes)
                          else large_leaf_bytes)
        if arrange is not None:
            arrange(ln, patch)
        capsys.readouterr()
        ln.run()
    printed = capsys.readouterr().out.splitlines()
    rows = [json.loads(line) for line in
            (folder / 'metrics.jsonl').read_text().splitlines()]
    return ln, _files_left(folder / 'models'), {
        'printed': [line for line in printed
                    if line.startswith(('epoch ', 'loss =', 'updated model'))],
        'rows': [{k: row.get(k) for k in _ROW_KEYS} for row in rows],
        'steps': ln.trainer.steps,
    }, [row['fused']['enqueued_first'] for row in rows if 'fused' in row]


def _one_leaf_over(ln):
    """A threshold that exactly the train state's largest leaves pass."""
    return max(leaf.nbytes for leaf in
               jax.tree_util.tree_leaves(ln.trainer.state)) - 1


_ROLLBACK = {'guard': {'nonfinite_policy': 'rollback', 'rollback_after': 4}}


@pytest.mark.timeout(900)
@pytest.mark.parametrize('case, over, flags', [
    ('plain', {'epochs': 4}, [True, True, True, False]),
    # skip epochs fetch nothing and keep their order; 5 is final
    ('interval_2', {'epochs': 5, 'checkpoint_interval': 2},
     [False, True, False, True, False]),
    # a NaN burst after epoch 1's checkpoint: the guard restores it in place
    ('rollback', dict(_ROLLBACK, epochs=4), None),
])
def test_both_orders_leave_the_same_bits(tmp_path, monkeypatch, capsys,
                                         case, over, flags):
    """(b) One seed, several epochs: every checkpoint file, sidecar and
    layout manifest, the printed losses and the records' dynamics are the
    same under enqueue-then-fetch and under fetch-then-enqueue."""
    if case == 'rollback':
        monkeypatch.setenv('HANDYRL_TPU_CHAOS', 'nanepoch=1,nanburst=64')
    ln, left, told, first = _order_run(tmp_path, monkeypatch, capsys, 'new',
                                       **over)
    controls = [0] + ([_one_leaf_over] if case == 'plain' else [])
    for n, threshold in enumerate(controls):
        _ln, left_kept, told_kept, first_kept = _order_run(
            tmp_path, monkeypatch, capsys, 'kept%d' % n,
            large_leaf_bytes=threshold, **over)
        assert not any(first_kept)
        assert left_kept == left
        assert told_kept == told
    assert ln.model_epoch == over['epochs'] and told['steps'] > 0
    assert 'trainer_state.ckpt' in left and 'latest.ckpt' in left
    if flags is not None:
        assert first == flags
    else:
        assert told['rows'][-1]['guard_rollbacks'] >= 1 and any(first)
    snap = telemetry.summarize(telemetry.snapshot())['counters']
    assert snap['epoch_boundaries_enqueued_first_total'] \
        <= snap['epoch_boundaries_total']


@pytest.mark.timeout(600)
def test_the_snapshot_outlives_the_donation_of_the_train_state(
        tmp_path, monkeypatch, capsys):
    """(a, c, e) At a boundary the next program is on the device before the
    blocking fetch starts, and exactly one is in flight through it; the
    pack holds the state after chunk *i* although chunk *i+1* has donated
    and overwritten ``tr.state``. A state with one leaf over the threshold
    takes no detached pack at all."""
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    from handyrl_tpu.utils import fetch
    built, packs, seen = [], [], []
    init, pack, unpack = (FusedPipeline.__init__, fetch.pack_tree,
                          fetch.fetch_packed)

    def remember(self, *args, **kwargs):
        built.append(self)
        init(self, *args, **kwargs)

    def pack_tree(tree, detach=False):
        packs.append(detach)
        if detach:      # what the state holds now, after chunk i
            fp, = built
            seen.append({'leaves': jax.tree_util.tree_leaves(tree),
                         'want': jax.tree_util.tree_map(np.array, tree),
                         'packed_at': fp.dispatches})
        return pack(tree, detach)

    def fetch_packed(packed):
        fp, = built
        now = seen[-1] if packs[-1] else None
        if now is None or fp.dispatches == now['packed_at']:
            return unpack(packed)
        # the next iteration's step is made: chunk i+1 is enqueued and has
        # taken the train state with it, chunk i's result is collected, so
        # exactly one program is in flight
        assert fp.dispatches == now['packed_at'] + 1 == fp.chunks_host + 1
        assert fp._pending is not None
        assert all(leaf.is_deleted() for leaf in now['leaves'])
        got = unpack(packed)
        now['got'], now['dispatches'] = got, fp.dispatches
        return got

    def arrange(ln, patch):
        patch.setattr(FusedPipeline, '__init__', remember)
        patch.setattr(fetch, 'pack_tree', pack_tree)
        patch.setattr(fetch, 'fetch_packed', fetch_packed)

    t_start = time.perf_counter()
    ln, _left, told, first = _order_run(tmp_path, monkeypatch, capsys, 'new',
                                        arrange=arrange, epochs=3)
    fp, = built
    assert first == [True, True, False] and len(seen) == 3
    for now, row in zip(seen[:2], told['rows']):
        for want, got in zip(jax.tree_util.tree_leaves(now['want']),
                             jax.tree_util.tree_leaves(now['got'])):
            np.testing.assert_array_equal(want, got)
        # the record and the checkpoint speak of chunk i, not of i+1
        assert int(now['got'].steps) == row['steps']
        assert row['dispatches_gen'] == now['dispatches'] - 1
    boundaries = telemetry.spans('epoch_boundary', since=t_start)
    assert [b['attrs']['enqueued_first'] for b in boundaries] == [1, 1, 0]

    # one leaf over the threshold: no snapshot is taken, the order is kept
    packs.clear(), built.clear()
    t_start = time.perf_counter()
    _ln, _left, _told, first = _order_run(
        tmp_path, monkeypatch, capsys, 'large', arrange=arrange, epochs=3,
        large_leaf_bytes=_one_leaf_over)
    assert first == [False] * 3 and len(packs) >= 3 and not any(packs)
    recs = telemetry.spans(since=t_start)
    fetches = [r for r in recs if r['name'] == 'state_fetch']
    dispatches = [r for r in recs if r['name'] == 'dispatch']
    for fetch_span in fetches[:2]:
        assert [d for d in dispatches if d['t0'] > fetch_span['t0']][0][
            't0'] > fetch_span['t1']


def _exit_at_second_boundary(site):
    """Patches that bring the exit at the OPENING of the second boundary."""
    def arrange(ln, patch):
        epoch_close = Learner._fused_epoch

        def _fused_epoch(self, *args, **kwargs):
            if self.model_epoch == 1:
                if site == 'preemption':
                    self.preempt.signum = 15
                    self.preempt._event.set()
                elif site == 'deadline':
                    self._deadline = time.time() - 1.0
            return epoch_close(self, *args, **kwargs)
        patch.setattr(Learner, '_fused_epoch', _fused_epoch)
    return arrange


@pytest.mark.timeout(600)
@pytest.mark.parametrize('site', ['preemption', 'deadline', 'epoch_budget'])
def test_an_exit_at_a_boundary_leaves_what_the_kept_order_leaves(
        tmp_path, monkeypatch, capsys, site):
    """(d) Preemption, the deadline and the epoch budget arriving at a
    boundary: that boundary enqueues nothing, no chunk runs past the last
    checkpoint, and file for file the run leaves what fetch-then-enqueue
    leaves (``test_preempted_run_leaves_what_inline_writes_leave``'s
    method)."""
    over = {'epochs': 2 if site == 'epoch_budget' else -1}
    arrange = None if site == 'epoch_budget' else \
        _exit_at_second_boundary(site)
    ln, left, told, first = _order_run(tmp_path, monkeypatch, capsys, 'new',
                                       arrange=arrange, **over)
    ln2, left_kept, told_kept, first_kept = _order_run(
        tmp_path, monkeypatch, capsys, 'kept', large_leaf_bytes=0,
        arrange=arrange, **over)
    assert first == [True, False] and first_kept == [False, False]
    assert ln.model_epoch == ln2.model_epoch == 2
    assert ln.trainer.steps == ln._last_ckpt_steps == ln2.trainer.steps > 0
    assert left == left_kept
    assert told['rows'][:2] == told_kept['rows'][:2]
    assert told['printed'] == told_kept['printed']
    assert {'1.ckpt', '2.ckpt', 'latest.ckpt', 'trainer_state.ckpt'} \
        <= set(left)
    assert left['2.ckpt.layout']['steps'] == ln.trainer.steps
    assert ln.preempt.fired == (site == 'preemption')


@pytest.mark.timeout(600)
def test_a_preemption_after_the_enqueue_books_both_chunks(
        tmp_path, monkeypatch, capsys):
    """The signal lands while the boundary waits for its fetch, with the
    next chunk on the device already: the boundary completes, the exit
    books the result that the boundary's step collected and collects the
    chunk in flight, and the flush writes the state after that chunk with
    its own step count."""
    from handyrl_tpu.utils import fetch
    unpack = fetch.fetch_packed

    def arrange(ln, patch):
        def fetch_packed(packed):
            if ln.model_epoch == 2:     # bumped: the second boundary's
                ln.preempt.signum = 15
                ln.preempt._event.set()
            return unpack(packed)
        patch.setattr(fetch, 'fetch_packed', fetch_packed)

    t_start = time.perf_counter()
    ln, left, told, first = _order_run(tmp_path, monkeypatch, capsys, 'new',
                                       arrange=arrange, epochs=-1)
    assert first == [True, True] and ln.preempt.fired and ln.model_epoch == 2
    steps_at_boundary = told['rows'][1]['steps']
    sgd = ln.args['sgd_steps_per_chunk']
    assert ln.trainer.steps == ln._last_ckpt_steps == steps_at_boundary + sgd
    state = serialization.msgpack_restore(
        _read(ln.trainer_state_path()))
    assert state['steps'] == ln.trainer.steps
    assert left['2.ckpt.layout']['steps'] == ln.trainer.steps
    for name in ('1.ckpt', '2.ckpt', 'latest.ckpt', 'trainer_state.ckpt'):
        assert verify_checkpoint(
            os.path.join(os.path.dirname(ln.trainer_state_path()), name)) \
            == (True, 'ok')
    # every chunk enqueued was fetched and booked, the last two booked at
    # the exit
    blocks = telemetry.spans('host_block', since=t_start)
    dispatches = telemetry.spans('dispatch', since=t_start)
    assert len(blocks) == len(dispatches)
    assert ln.num_returned_episodes == blocks[-1]['attrs']['episodes']
    # two boundaries' writes and the flush
    assert len(telemetry.spans('checkpoint_write', since=t_start)) == 3
