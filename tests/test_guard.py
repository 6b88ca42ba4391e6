"""Learner-side resilience (handyrl_tpu/guard.py): preemption snapshot-and-
exit, non-finite guards with rollback escalation, and checkpoint integrity.

Units cover the signal flag, the CRC sidecar round trip, corrupt-checkpoint
fallback selection, skip→rollback escalation, and the episode ingest
screen. The slow e2e legs drive real learners: SIGTERM mid-epoch →
exit 75 → restart completes the budget with monotonic step counts; an
injected NaN step is skipped (counted) and an injected NaN burst rolls the
TrainState back to the last good checkpoint.
"""

import bz2
import json
import os
import pickle
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from handyrl_tpu import guard
from handyrl_tpu.config import apply_defaults
from handyrl_tpu.utils.fs import (atomic_write_bytes, checksummed_write_bytes,
                                  read_verified_bytes, sidecar_path,
                                  verify_checkpoint)


# ---------------------------------------------------------------------------
# preemption guard


def test_preempt_exit_code_is_the_supervisor_contract():
    assert guard.PREEMPT_EXIT_CODE == 75   # EX_TEMPFAIL: restart me


def test_preempt_guard_sets_flag_on_sigterm():
    pg = guard.PreemptionGuard().install()
    try:
        assert not pg.requested()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not pg.requested() and time.time() < deadline:
            time.sleep(0.01)
        assert pg.requested() and pg.fired
        assert pg.signum == signal.SIGTERM
    finally:
        pg.uninstall()
    # handlers restored: a fresh guard can install again
    assert signal.getsignal(signal.SIGTERM) is not pg._handle


def test_preempt_guard_disabled_never_installs():
    pg = guard.PreemptionGuard(enabled=False).install()
    assert not pg._previous
    pg.uninstall()


# ---------------------------------------------------------------------------
# CRC sidecar round trip


def test_checksummed_write_roundtrip(tmp_path):
    target = str(tmp_path / '3.ckpt')
    checksummed_write_bytes(target, b'checkpoint-bytes')
    ok, reason = verify_checkpoint(target)
    assert ok and reason == 'ok'
    assert read_verified_bytes(target) == b'checkpoint-bytes'
    manifest = json.loads(open(sidecar_path(target)).read())
    assert manifest['algo'] == 'crc32'
    assert manifest['size'] == len(b'checkpoint-bytes')


def test_bitflip_and_truncation_fail_verification(tmp_path):
    target = str(tmp_path / '3.ckpt')
    checksummed_write_bytes(target, b'checkpoint-bytes')

    raw = bytearray(open(target, 'rb').read())
    raw[4] ^= 0x40                      # single bit flip
    atomic_write_bytes(target, bytes(raw))
    ok, reason = verify_checkpoint(target)
    assert not ok and 'crc32' in reason
    assert read_verified_bytes(target) is None

    checksummed_write_bytes(target, b'checkpoint-bytes')
    atomic_write_bytes(target, b'check')   # truncated write
    ok, reason = verify_checkpoint(target)
    assert not ok and 'mismatch' in reason


def test_legacy_checkpoint_without_sidecar_passes(tmp_path):
    target = str(tmp_path / '1.ckpt')
    atomic_write_bytes(target, b'pre-manifest era')
    ok, reason = verify_checkpoint(target)
    assert ok and reason == 'unverified'
    assert read_verified_bytes(target) == b'pre-manifest era'


def test_missing_file_fails_verification(tmp_path):
    ok, reason = verify_checkpoint(str(tmp_path / 'nope.ckpt'))
    assert not ok
    assert read_verified_bytes(str(tmp_path / 'nope.ckpt')) is None


# ---------------------------------------------------------------------------
# fallback selection: newest VALID epoch wins


def test_newest_valid_epoch_skips_corrupt_checkpoints(tmp_path):
    d = str(tmp_path)
    checksummed_write_bytes(os.path.join(d, '1.ckpt'), b'one')
    checksummed_write_bytes(os.path.join(d, '3.ckpt'), b'three')
    assert guard.numbered_checkpoints(d) == [1, 3]
    assert guard.newest_valid_epoch(d) == (3, [])

    # bit-flip the newest: resume must fall back to epoch 1, reporting 3
    atomic_write_bytes(os.path.join(d, '3.ckpt'), b'thrEe')
    epoch, discarded = guard.newest_valid_epoch(d)
    assert epoch == 1 and discarded == [3]

    # corrupt everything: fresh start (epoch 0)
    atomic_write_bytes(os.path.join(d, '1.ckpt'), b'0ne')
    epoch, discarded = guard.newest_valid_epoch(d)
    assert epoch == 0 and discarded == [3, 1]   # newest discarded first


def test_newest_valid_epoch_empty_dir(tmp_path):
    assert guard.newest_valid_epoch(str(tmp_path)) == (0, [])
    assert guard.newest_valid_epoch(str(tmp_path / 'missing')) == (0, [])


# ---------------------------------------------------------------------------
# non-finite escalation policy


def test_guard_skip_policy_counts_but_never_rolls_back():
    g = guard.NonFiniteGuard({'nonfinite_policy': 'skip',
                              'rollback_after': 2})
    assert g.observe(1, 0) == 'skip'
    assert g.observe(1, 0) == 'skip'
    assert g.observe(1, 0) == 'skip'
    assert g.total_bad == 3 and g.consecutive == 3


def test_guard_skip_then_rollback_escalation():
    g = guard.NonFiniteGuard({'nonfinite_policy': 'rollback',
                              'rollback_after': 4})
    assert g.observe(1, 0) == 'skip'
    assert g.observe(2, 0) == 'skip'
    assert g.observe(0, 8) is None      # clean drain resets the streak
    assert g.consecutive == 0
    assert g.observe(3, 0) == 'skip'
    assert g.observe(2, 0) == 'rollback'   # 5 consecutive >= 4
    g.reset_streak()
    assert g.consecutive == 0 and g.total_bad == 8


def test_guard_abort_policy():
    g = guard.NonFiniteGuard({'nonfinite_policy': 'abort'})
    assert g.observe(1, 0) == 'abort'


def test_guard_loss_spike_zscore_trips_rollback():
    g = guard.NonFiniteGuard({'nonfinite_policy': 'rollback',
                              'rollback_after': 99,
                              'loss_spike_zscore': 6.0})
    rng = np.random.RandomState(0)
    for _ in range(50):
        assert g.observe(0, 8, 1.0 + 0.01 * rng.randn()) is None
    assert g.observe(0, 8, 50.0) == 'rollback'


def test_chaos_nan_consumes_its_burst():
    c = guard.ChaosNaN({'nanstep': 10, 'nanburst': 3})
    assert not c.due(8)            # before the window
    assert c.due(10)
    assert c.due(11) and c.due(12)
    assert not c.due(13)           # budget spent
    assert not c.due(10)           # a rollback rewind must NOT re-trigger


def test_chaos_nan_covers_fused_dispatch_ranges():
    c = guard.ChaosNaN({'nanstep': 10, 'nanburst': 2})
    assert not c.due(0, count=8)   # steps 0..7
    assert c.due(8, count=8)       # steps 8..15 overlap the window
    assert not c.due(16, count=8)  # budget consumed by the dispatch


# ---------------------------------------------------------------------------
# episode ingest screen


def _episode(obs_value=0.5, reward=0.25, outcome=1.0):
    moments = [{'observation': {0: np.full((3, 3), obs_value, np.float32),
                                1: None},
                'selected_prob': {0: 0.5, 1: None},
                'action_mask': {0: np.zeros(9, np.float32), 1: None},
                'action': {0: 4, 1: None},
                'value': {0: 0.1, 1: None},
                'reward': {0: reward, 1: None},
                'return': {0: 0.3, 1: None}}]
    block = bz2.compress(pickle.dumps(moments))
    return {'args': {'player': [0, 1], 'model_id': {0: 0, 1: 0}},
            'outcome': {0: outcome, 1: -outcome},
            'moment': [block], 'steps': 1}


def test_episode_screen_accepts_finite_and_none_entries():
    assert guard.episode_is_finite(_episode())


def test_episode_screen_rejects_nonfinite_payloads():
    assert not guard.episode_is_finite(_episode(obs_value=np.nan))
    assert not guard.episode_is_finite(_episode(reward=np.inf))
    assert not guard.episode_is_finite(_episode(outcome=np.nan))
    corrupt = _episode()
    corrupt['moment'] = [b'not a bz2 block']
    assert not guard.episode_is_finite(corrupt)


def test_feed_episodes_drops_and_counts_poisoned_episodes(tmp_path):
    from handyrl_tpu.train import Learner
    args = apply_defaults({
        'env_args': {'env': 'TicTacToe'},
        'train_args': {'batch_size': 8, 'update_episodes': 16,
                       'minimum_episodes': 16, 'epochs': 1,
                       'forward_steps': 8, 'num_batchers': 1,
                       'model_dir': str(tmp_path / 'models')}})
    learner = Learner(args=args)
    good, bad = _episode(), _episode(obs_value=np.nan)
    learner.feed_episodes([good, bad, None])
    assert learner._bad_episodes == 1
    assert list(learner.trainer.episodes) == [good]
    assert learner.num_returned_episodes == 1


# ---------------------------------------------------------------------------
# slow e2e: preempt-and-resume, NaN skip, NaN burst rollback


LEARNER_SCRIPT = r'''
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import train_main
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': dict(
               batch_size=8, update_episodes=12, minimum_episodes=12,
               epochs=%(epochs)d, forward_steps=8, num_batchers=1,
               generation_envs=8,
               model_dir=%(model_dir)r, metrics_jsonl=%(metrics)r,
               restart_epoch=%(restart)d,
               guard=%(guard)r)}
    train_main(apply_defaults(raw))
    print('LEARNER DONE', flush=True)

if __name__ == '__main__':
    main()
'''


def _spawn_learner(tmp_path, tag, epochs=3, restart=0, guard_cfg=None,
                   chaos=''):
    script = tmp_path / ('learner_%s.py' % tag)
    script.write_text(LEARNER_SCRIPT % {
        'epochs': epochs, 'model_dir': str(tmp_path / 'models'),
        'metrics': str(tmp_path / 'metrics.jsonl'), 'restart': restart,
        'guard': guard_cfg or {}})
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'PYTHONPATH': repo + os.pathsep + os.environ.get('PYTHONPATH', '')}
    if chaos:
        env['HANDYRL_TPU_CHAOS'] = chaos
    else:
        env.pop('HANDYRL_TPU_CHAOS', None)
    log = open(tmp_path / ('learner_%s.log' % tag), 'w')
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=log, stderr=subprocess.STDOUT)
    return proc, log


def _wait_for(predicate, deadline, poll=0.5):
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


def _read_metrics(tmp_path):
    from handyrl_tpu.telemetry import validate_metrics_line
    path = tmp_path / 'metrics.jsonl'
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    return [validate_metrics_line(l) for l in lines]


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_preempt_snapshot_and_resume(tmp_path):
    """SIGTERM mid-run → full checkpoint flush + 'preempted' metrics record
    + exit 75; the supervisor restart (restart_epoch: -1) completes the
    epoch budget with monotonic step counts and no duplicate epoch rows."""
    model_dir = tmp_path / 'models'
    proc, log = _spawn_learner(tmp_path, 'first', epochs=3)
    try:
        # preempt once the first epoch checkpoint exists (mid epoch 2)
        assert _wait_for(
            lambda: (model_dir / '1.ckpt').exists()
            or proc.poll() is not None,
            time.time() + 420), 'first epoch never completed'
        assert proc.poll() is None, 'learner died before the preempt'
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()
    assert rc == guard.PREEMPT_EXIT_CODE, \
        'expected the supervisor-contract exit code, got %s' % rc
    out = (tmp_path / 'learner_first.log').read_text()
    assert 'preempted: checkpoint flushed' in out
    # the flushed pair is on disk, checksummed and valid
    assert (model_dir / 'trainer_state.ckpt').exists()
    assert verify_checkpoint(str(model_dir / 'trainer_state.ckpt'))[0]
    flushed_epoch, _ = guard.newest_valid_epoch(str(model_dir))
    assert flushed_epoch >= 1
    recs = _read_metrics(tmp_path)
    assert any(r.get('preempted') for r in recs)

    # supervisor restart: auto-resume, finish the budget
    proc, log = _spawn_learner(tmp_path, 'resume', epochs=3, restart=-1)
    try:
        rc = proc.wait(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()
    out2 = (tmp_path / 'learner_resume.log').read_text()
    assert rc == 0, 'resumed learner failed (rc %s):\n%s' % (rc, out2[-2000:])
    assert 'LEARNER DONE' in out2
    assert ('auto-resume: newest valid checkpoint is epoch %d'
            % flushed_epoch) in out2
    assert (model_dir / '3.ckpt').exists(), 'budget not reached after resume'

    recs = _read_metrics(tmp_path)
    # resumed step counts are monotonic across the whole file
    steps = [r['steps'] for r in recs]
    assert steps == sorted(steps), 'step counts regressed across restart'
    # epoch rows are unique once the tagged preemption record is set aside
    epochs = [r['epoch'] for r in recs if not r.get('preempted')]
    assert len(epochs) == len(set(epochs)), \
        'duplicate epoch rows in metrics_jsonl: %s' % epochs
    assert max(epochs) == 3


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_bitflipped_checkpoint_resumes_from_previous_epoch(tmp_path):
    """Corrupting the newest numbered checkpoint after a finished run must
    make auto-resume fall back to the previous valid epoch, not crash."""
    model_dir = tmp_path / 'models'
    proc, log = _spawn_learner(tmp_path, 'first', epochs=2)
    try:
        rc = proc.wait(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()
    assert rc == 0
    assert (model_dir / '2.ckpt').exists()

    raw = bytearray((model_dir / '2.ckpt').read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    atomic_write_bytes(str(model_dir / '2.ckpt'), bytes(raw))

    proc, log = _spawn_learner(tmp_path, 'resume', epochs=3, restart=-1)
    try:
        rc = proc.wait(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
        log.close()
    out = (tmp_path / 'learner_resume.log').read_text()
    assert rc == 0, 'resume crashed on the corrupt checkpoint:\n%s' % out[-2000:]
    assert 'auto-resume: newest valid checkpoint is epoch 1' in out
    assert 'discarding checkpoint' in out
    assert (model_dir / '3.ckpt').exists()


def _nan_learner_child(args, chaos, report_path):
    # spawned subprocess: an XLA-CPU crash fails one test instead of
    # killing the whole pytest run (same containment as test_resume)
    os.environ['HANDYRL_TPU_CHAOS'] = chaos
    import jax
    import numpy as _np
    from handyrl_tpu.train import Learner
    learner = Learner(args=args)
    learner.run()
    finite = all(_np.isfinite(_np.asarray(l)).all()
                 for l in jax.tree_util.tree_leaves(learner.wrapper.params))
    with open(report_path, 'w') as f:
        json.dump({'total_bad': learner.trainer.guard.total_bad,
                   'rollbacks': learner.trainer.guard.rollbacks,
                   'model_epoch': learner.model_epoch,
                   'params_finite': finite}, f)


def _run_nan_learner(tmp_path, tag, chaos, guard_cfg, epochs=2):
    import multiprocessing as mp
    args = apply_defaults({
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 8, 'update_episodes': 12,
            'minimum_episodes': 12, 'epochs': epochs,
            'forward_steps': 8, 'num_batchers': 1,
            'generation_envs': 8,
            'model_dir': str(tmp_path / ('models_%s' % tag)),
            'metrics_jsonl': str(tmp_path / ('m_%s.jsonl' % tag)),
            'guard': guard_cfg}})
    report = str(tmp_path / ('report_%s.json' % tag))
    ctx = mp.get_context('spawn')
    proc = ctx.Process(target=_nan_learner_child, args=(args, chaos, report))
    proc.start()
    proc.join(timeout=600)
    if proc.is_alive():
        proc.terminate()
        proc.join(10)
        pytest.fail('learner subprocess timed out (%s)' % tag)
    if not os.path.exists(report):
        pytest.fail('learner subprocess died with exit code %s (%s)'
                    % (proc.exitcode, tag))
    with open(report) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_nan_injection_is_skipped_and_counted(tmp_path):
    """An injected non-finite update under policy 'skip' is dropped on
    device (params stay finite) and counted in telemetry."""
    rep = _run_nan_learner(tmp_path, 'skip', 'nanepoch=1,nanburst=2',
                           {'nonfinite_policy': 'skip'})
    assert rep['total_bad'] >= 2, 'injected NaNs were not counted'
    assert rep['rollbacks'] == 0
    assert rep['params_finite'], 'params were poisoned despite the skip guard'
    recs = [json.loads(l) for l in
            (tmp_path / 'm_skip.jsonl').read_text().splitlines()]
    assert recs[-1]['guard_nonfinite'] >= 2


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_nan_burst_triggers_rollback(tmp_path):
    """A NaN burst under policy 'rollback' restores the last good
    checkpoint in place and the run still completes its budget with
    finite training."""
    rep = _run_nan_learner(tmp_path, 'rb', 'nanepoch=1,nanburst=64',
                           {'nonfinite_policy': 'rollback',
                            'rollback_after': 4}, epochs=3)
    assert rep['rollbacks'] >= 1, 'NaN burst never rolled back'
    assert rep['params_finite']
    assert rep['model_epoch'] == 3, 'run did not complete its budget'
    recs = [json.loads(l) for l in
            (tmp_path / 'm_rb.jsonl').read_text().splitlines()]
    assert recs[-1]['guard_rollbacks'] >= 1
