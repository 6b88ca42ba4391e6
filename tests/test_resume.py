"""Checkpoint/resume test: a restarted learner continues the optimization
trajectory (params AND optimizer state/steps), not just the weights.

Each learner runs in a SPAWNED subprocess (same containment as
test_checkpoint_interval): the resume path has triggered heap corruption
inside XLA CPU on some hosts, and an in-process crash would kill the whole
pytest run — hiding every later test file — instead of failing one test.
"""

import json
import multiprocessing as mp
import os

import pytest

from handyrl_tpu.config import apply_defaults


def _args(model_dir, epochs, restart=0):
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 16, 'update_episodes': 25, 'minimum_episodes': 30,
            'epochs': epochs, 'generation_envs': 8, 'forward_steps': 8,
            'num_batchers': 1, 'model_dir': model_dir,
            'restart_epoch': restart,
        },
    }
    return apply_defaults(raw)


def _learner_child(args, report_path):
    import numpy as np
    import jax
    from handyrl_tpu.train import Learner
    ln = Learner(args=args)
    rep = {'steps_at_start': ln.trainer.steps,
           'model_epoch_at_start': ln.model_epoch}
    if ln.trainer.state is not None:
        rep['opt_mu_norm'] = sum(
            float(np.abs(np.asarray(l)).sum())
            for l in jax.tree_util.tree_leaves(ln.trainer.state.opt_state))
    ln.run()
    rep['model_epoch'] = ln.model_epoch
    rep['steps'] = ln.trainer.steps
    with open(report_path, 'w') as f:
        json.dump(rep, f)


def _run_learner(args, tmp, tag, timeout=480):
    report = os.path.join(tmp, 'resume_report_%s.json' % tag)
    ctx = mp.get_context('spawn')
    proc = ctx.Process(target=_learner_child, args=(args, report))
    proc.start()
    proc.join(timeout=timeout)
    if proc.is_alive():
        proc.terminate()
        proc.join(10)
        pytest.fail('learner subprocess timed out (%s)' % tag)
    # report written after ln.run() => contract completed even if the
    # interpreter aborted at teardown (known XLA daemon-thread issue)
    if not os.path.exists(report):
        pytest.fail('learner subprocess died with exit code %s (%s) — '
                    'backend crash, see stderr above' % (proc.exitcode, tag))
    with open(report) as f:
        return json.load(f)


@pytest.mark.timeout(560)
def test_resume_continues_trainer_state(tmp_path):
    model_dir = str(tmp_path / 'models')

    rep1 = _run_learner(_args(model_dir, epochs=2), str(tmp_path), 'first')
    steps_before = rep1['steps']
    assert steps_before > 0

    rep2 = _run_learner(_args(model_dir, epochs=3, restart=2),
                        str(tmp_path), 'resume')
    # optimizer state and step counter restored before any new training
    # (saved at the last epoch boundary; the live counter may have ticked
    # a little further before shutdown)
    assert 0 < rep2['steps_at_start'] <= steps_before
    assert rep2['model_epoch_at_start'] == 2
    assert rep2['opt_mu_norm'] > 0, \
        'adam moments must be restored, not zero-initialized'
    assert rep2['model_epoch'] == 3
    assert rep2['steps'] > steps_before
