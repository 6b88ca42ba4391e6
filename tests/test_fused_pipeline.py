"""The fully-fused device loop: ONE dispatch = rollout chunk + on-device
window ingest + K SGD steps (ops/fused_pipeline.py). End-to-end learner runs
for both ingest layouts, plus resume."""

import json

import pytest

from handyrl_tpu.config import apply_defaults
from handyrl_tpu.models import build
from handyrl_tpu.train import Learner


def _ttt_raw(tmp_path, **over):
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            # batch 12 is not divisible by the 8-device test mesh, so the
            # trainer stays single-device — the device-ingest requirement
            'batch_size': 12, 'forward_steps': 4, 'compress_steps': 2,
            'update_episodes': 40, 'minimum_episodes': 40, 'epochs': 2,
            'generation_envs': 16, 'num_batchers': 1,
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 4,
            'model_dir': str(tmp_path / 'models'),
            'metrics_jsonl': str(tmp_path / 'metrics.jsonl'),
        },
    }
    raw['train_args'].update(over)
    return raw


@pytest.mark.timeout(600)
def test_tictactoe_fused_pipeline_learner(tmp_path, capsys):
    args = apply_defaults(_ttt_raw(tmp_path))
    learner = Learner(args=args)
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(turn mode)' in out
    # the single-device downgrade (batch 12 on 8 devices) is kept, and the
    # start-up line is where it shows
    assert '"found": 8, "used": 1, "mesh": null' in out
    assert 'loss =' in out          # metric futures drained and printed
    assert learner.model_epoch == 2
    assert learner.num_returned_episodes >= 80
    assert learner.trainer.steps > 0
    assert (tmp_path / 'models' / '2.ckpt').exists()
    assert (tmp_path / 'models' / 'trainer_state.ckpt').exists()
    # metrics JSONL carries the dispatch count (host round trips per epoch)
    rows = [json.loads(line)
            for line in (tmp_path / 'metrics.jsonl').read_text().splitlines()]
    assert rows and rows[-1]['dispatches_gen'] > 0
    assert rows[-1]['steps'] == learner.trainer.steps


@pytest.mark.timeout(600)
def test_fused_pipeline_ingest_accounting(tmp_path):
    """windows_ingested must be the CUMULATIVE ingest count, not the ring
    size (which saturates at capacity once the ring wraps)."""
    args = apply_defaults(_ttt_raw(
        tmp_path, maximum_episodes=2, replay_windows_per_episode=2))
    learner = Learner(args=args)
    learner.run()
    capacity = learner.trainer.replay.capacity
    assert capacity == 4
    stats = learner.trainer.replay_stats
    # ~80 episodes x >=1 window each went through a 4-row ring
    assert stats['windows_ingested'] > capacity * 4
    assert stats['samples_drawn'] > 0


@pytest.mark.timeout(600)
def test_geese_fused_pipeline_learner(tmp_path, capsys):
    raw = {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {
            'turn_based_training': False, 'observation': True,
            'gamma': 0.99, 'forward_steps': 8, 'compress_steps': 4,
            'batch_size': 12, 'update_episodes': 10, 'minimum_episodes': 10,
            'epochs': 1, 'generation_envs': 8, 'num_batchers': 1,
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 4,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE',
            'model_dir': str(tmp_path / 'models'),
        },
    }
    args = apply_defaults(raw)
    learner = Learner(args=args, net=build('GeeseNet', layers=2, filters=16))
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(solo mode)' in out
    assert learner.model_epoch == 1
    assert learner.trainer.steps > 0
    assert (tmp_path / 'models' / '1.ckpt').exists()


@pytest.mark.timeout(600)
def test_geister_fused_pipeline_learner(tmp_path, capsys):
    """Geister (turn-based, observation=True, recurrent DRC, dict
    observations) now runs the FUSED pipeline: the ingest gate admits
    observation=True via the compact 'turn' layout (equivalence proven by
    tests/test_turn_layout_parity.py), and the windower handles the
    pytree observation. This pins geister's sample reuse to
    sgd_steps_per_chunk instead of the threaded trainer's free spin."""
    from handyrl_tpu.models.geister import GeisterNet

    raw = {
        'env_args': {'env': 'Geister'},
        'train_args': {
            'turn_based_training': True, 'observation': True,
            'gamma': 0.9, 'forward_steps': 4, 'burn_in_steps': 2,
            'compress_steps': 2, 'batch_size': 8, 'update_episodes': 8,
            'minimum_episodes': 8, 'epochs': 2, 'generation_envs': 8,
            'num_batchers': 1, 'device_generation': True,
            'device_replay': True, 'sgd_steps_per_chunk': 2,
            'model_dir': str(tmp_path / 'models'),
        },
    }
    args = apply_defaults(raw)
    learner = Learner(args=args,
                      net=GeisterNet(filters=8, drc_layers=1))
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(turn mode' in out
    assert learner.model_epoch == 2
    assert learner.trainer.steps > 0
    assert learner.trainer.device_cfg.observation is False
    assert learner.trainer.cfg.observation is True
    assert (tmp_path / 'models' / '2.ckpt').exists()


@pytest.mark.timeout(600)
def test_geister_threaded_turn_ingest(tmp_path, capsys):
    """fused_pipeline: False with an observation=True turn-based env:
    the THREADED device-ingest path must train with the rebuilt
    (observation=False) replay program against the compact windower rows
    — the Trainer.build_replay_update relayering, not the fused path."""
    from handyrl_tpu.models.geister import GeisterNet

    raw = {
        'env_args': {'env': 'Geister'},
        'train_args': {
            'turn_based_training': True, 'observation': True,
            'gamma': 0.9, 'forward_steps': 2, 'burn_in_steps': 0,
            'compress_steps': 2, 'batch_size': 4, 'update_episodes': 4,
            'minimum_episodes': 4, 'epochs': 1, 'generation_envs': 4,
            'num_batchers': 1, 'device_generation': True,
            'device_replay': True, 'fused_pipeline': False,
            'replay_fused_steps': 2, 'device_chunk_steps': 8,
            'model_dir': str(tmp_path / 'models'),
        },
    }
    args = apply_defaults(raw)
    learner = Learner(args=args,
                      net=GeisterNet(filters=4, drc_layers=1,
                                     drc_repeats=1))
    learner.run()
    out = capsys.readouterr().out
    assert 'device ingest: windows assembled on device' in out
    assert learner.model_epoch == 1
    assert learner.trainer.steps > 0
    assert learner.trainer.device_cfg.observation is False
    assert (tmp_path / 'models' / '1.ckpt').exists()


@pytest.mark.timeout(600)
def test_fused_pipeline_resume(tmp_path, capsys):
    args = apply_defaults(_ttt_raw(tmp_path))
    learner = Learner(args=args)
    learner.run()
    steps_before = learner.trainer.steps
    assert learner.model_epoch == 2

    args2 = apply_defaults(_ttt_raw(tmp_path, restart_epoch=2, epochs=3))
    learner2 = Learner(args=args2)
    assert learner2.trainer.steps == steps_before   # optimizer state resumed
    learner2.run()
    assert learner2.model_epoch == 3
    assert learner2.trainer.steps > steps_before
    assert (tmp_path / 'models' / '3.ckpt').exists()
