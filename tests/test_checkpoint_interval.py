"""checkpoint_interval: file cadence, final-epoch flush, and resume.

The fused device loop may skip the per-epoch host state fetch + ckpt write
(train.py _fused_epoch); these pin the knob's contract: numbered ckpts at
multiples of N plus the final epoch, trainer state resumable from them.

The learner itself runs in a SPAWNED subprocess: this exact fused program
has segfaulted XLA CPU on some hosts, and an in-process crash kills the
whole pytest run (hiding every later test file) instead of failing one
test. The subprocess boundary turns a backend crash into a plain failure
with an exit code; on healthy hosts the contract is tested unchanged.
"""

import glob
import json
import multiprocessing as mp
import os

import pytest

from handyrl_tpu.config import apply_defaults


def _args(tmp, **over):
    # batch 12 is not divisible by the 8-device test mesh, so the trainer
    # stays single-device and the run takes the fused device loop (the
    # path checkpoint_interval applies to)
    train = {'batch_size': 12, 'forward_steps': 8, 'update_episodes': 30,
             'minimum_episodes': 15, 'generation_envs': 8, 'epochs': 7,
             'device_generation': True, 'device_replay': True,
             'sgd_steps_per_chunk': 2, 'device_chunk_steps': 8,
             'model_dir': os.path.join(tmp, 'models'),
             'checkpoint_interval': 3}
    train.update(over)
    return apply_defaults({'env_args': {'env': 'TicTacToe'},
                           'train_args': train})


def _ckpt_numbers(model_dir):
    return sorted(int(os.path.basename(p).split('.')[0])
                  for p in glob.glob(os.path.join(model_dir, '*.ckpt'))
                  if os.path.basename(p).split('.')[0].isdigit())


def _learner_child(args, report_path):
    from handyrl_tpu.train import Learner
    ln = Learner(args=args)
    steps_at_start = ln.trainer.steps
    ln.run()
    with open(report_path, 'w') as f:
        json.dump({'model_epoch': ln.model_epoch,
                   'steps_at_start': steps_at_start}, f)


def _run_learner(args, tmp, tag, timeout=480):
    """Run the learner in a spawned child; return its exit report."""
    report = os.path.join(tmp, 'report_%s.json' % tag)
    ctx = mp.get_context('spawn')
    proc = ctx.Process(target=_learner_child, args=(args, report))
    proc.start()
    proc.join(timeout=timeout)
    if proc.is_alive():
        proc.terminate()
        proc.join(10)
        pytest.fail('learner subprocess timed out (%s)' % tag)
    # The report is written AFTER ln.run() returns, so its existence means
    # the training contract completed; a nonzero exit with a report present
    # is the known XLA daemon-thread abort at interpreter teardown
    # (train.py Learner.shutdown docstring), not a training failure.
    if not os.path.exists(report):
        pytest.fail('learner subprocess died with exit code %s (%s) — '
                    'backend crash, see stderr above' % (proc.exitcode, tag))
    if proc.exitcode != 0:
        print('note: learner child (%s) exited %s AFTER completing its run '
              '(teardown abort)' % (tag, proc.exitcode))
    with open(report) as f:
        return json.load(f)


@pytest.mark.timeout(560)
def test_interval_cadence_and_final_flush(tmp_path):
    args = _args(str(tmp_path))
    rep = _run_learner(args, str(tmp_path), 'first')
    model_dir = args['train_args']['model_dir']
    # multiples of 3 from the interval, 7 from the final-epoch force-write
    assert _ckpt_numbers(model_dir) == [3, 6, 7]
    assert os.path.exists(os.path.join(model_dir, 'trainer_state.ckpt'))
    assert rep['model_epoch'] == 7

    # resume from the final flush: params + optimizer state round-trip
    args2 = _args(str(tmp_path), restart_epoch=7, epochs=8)
    rep2 = _run_learner(args2, str(tmp_path), 'resume')
    assert rep2['steps_at_start'] > 0     # trainer state actually loaded
    assert rep2['model_epoch'] == 8
    assert 8 in _ckpt_numbers(model_dir)


@pytest.mark.timeout(560)
def test_skip_epochs_keep_their_order_and_fetching_ones_enqueue_first(
        tmp_path):
    """A skip epoch fetches nothing and enqueues nothing ahead; an epoch
    that writes packs the train state on the device, enqueues the next
    dispatch and fetches afterwards; the final one ends the run and leaves
    no chunk past its checkpoint. The record's ``fused`` block and the two
    counters say which."""
    metrics = os.path.join(str(tmp_path), 'metrics.jsonl')
    args = _args(str(tmp_path), epochs=5, checkpoint_interval=2,
                 metrics_jsonl=metrics)
    rep = _run_learner(args, str(tmp_path), 'order')
    assert rep['model_epoch'] == 5
    assert _ckpt_numbers(args['train_args']['model_dir']) == [2, 4, 5]
    with open(metrics) as f:
        rows = [json.loads(line) for line in f]
    assert [row['epoch'] for row in rows] == [1, 2, 3, 4, 5]
    assert [row['fused']['enqueued_first'] for row in rows] == [
        False, True, False, True, False]
    assert ['ckpt_wait_s' in row['fused'] for row in rows] == [
        False, True, False, True, True]
    counters = rows[-1]['telemetry']['counters']
    assert counters['epoch_boundaries_total'] == 5
    assert counters['epoch_boundaries_enqueued_first_total'] == 2
    # a record's steps are its epoch's end, whatever was enqueued ahead
    steps = [row['steps'] for row in rows]
    assert steps == sorted(steps) and len(set(steps)) == 5
    from flax import serialization
    with open(os.path.join(args['train_args']['model_dir'],
                           'trainer_state.ckpt'), 'rb') as f:
        assert serialization.msgpack_restore(f.read())['steps'] == steps[-1]
