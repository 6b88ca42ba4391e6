"""Mesh-portable checkpoints: the layout manifest that rides next to every
checkpoint (utils/fs.py layout_path, parallel/partition.py
checkpoint_layout), restore across DIFFERENT device counts with
bit-identical params and monotonic step counts, and the corrupt-manifest
fallback through the PR 4 newest-valid path.

The e2e legs spawn learners with different XLA virtual-device counts (the
flag must precede jax import, hence subprocesses): a run checkpointed under
a 4-device mesh resumes under a 2-device mesh and keeps training.
"""

import hashlib
import io
import json
import multiprocessing as mp
import os

import pytest

from handyrl_tpu.config import apply_defaults

pytestmark = []


def _args(model_dir, epochs, restart=0, metrics=''):
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 8, 'update_episodes': 16, 'minimum_episodes': 16,
            'epochs': epochs, 'generation_envs': 8, 'forward_steps': 4,
            'num_batchers': 1, 'model_dir': model_dir,
            'restart_epoch': restart, 'metrics_jsonl': metrics,
        },
    }
    return apply_defaults(raw)


def _value_sha1(params):
    """Order-independent hash of the raw param VALUES (leaf bytes in
    sorted-path order) — serialization byte order differs between a fresh
    template and a trained tree, the values are the contract."""
    import jax
    import numpy as np
    h = hashlib.sha1()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(params)[0],
                             key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def _learner_child(args, device_count, report_path):
    # the virtual-device count must be pinned BEFORE jax imports
    os.environ['XLA_FLAGS'] = \
        '--xla_force_host_platform_device_count=%d' % device_count
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import contextlib

    import jax
    from handyrl_tpu.train import Learner

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        ln = Learner(args=args)
        rep = {
            'devices': jax.device_count(),
            'mesh': dict(ln.trainer.mesh.shape) if ln.trainer.mesh else None,
            'steps_at_start': ln.trainer.steps,
            'epoch_at_start': ln.model_epoch,
            'params_sha1_at_start': _value_sha1(ln.wrapper.params),
        }
        ln.run()
    rep['epoch'] = ln.model_epoch
    rep['steps'] = ln.trainer.steps
    rep['params_sha1_at_end'] = _value_sha1(ln.wrapper.params)
    rep['stdout'] = buf.getvalue()
    with open(report_path, 'w') as f:
        json.dump(rep, f)


def _run_learner(args, device_count, tmp, tag, timeout=420):
    report = os.path.join(tmp, 'mesh_ckpt_%s.json' % tag)
    ctx = mp.get_context('spawn')
    proc = ctx.Process(target=_learner_child,
                       args=(args, device_count, report))
    proc.start()
    proc.join(timeout=timeout)
    if proc.is_alive():
        proc.terminate()
        pytest.fail('learner child %r timed out' % tag)
    assert proc.exitcode == 0, 'child %r exited %s' % (tag, proc.exitcode)
    with open(report) as f:
        return json.load(f)


@pytest.mark.timeout(900)
def test_checkpoint_restores_across_mesh_shapes(tmp_path):
    """Save under a 4-device mesh; resume under a 2-device mesh: the resumed
    params are bit-identical to the written checkpoint, the step counter
    continues monotonically, and the mesh change is logged, not silent."""
    from handyrl_tpu.utils.fs import read_layout_manifest

    model_dir = str(tmp_path / 'models')
    metrics = str(tmp_path / 'metrics.jsonl')

    a = _run_learner(_args(model_dir, epochs=2, metrics=metrics), 4,
                     str(tmp_path), 'save4')
    assert a['mesh'] == {'data': 4, 'model': 1}
    assert a['epoch'] == 2 and a['steps'] > 0

    # the manifest describes the writing mesh, next to the CRC sidecar
    state_path = os.path.join(model_dir, 'trainer_state.ckpt')
    layout, reason = read_layout_manifest(state_path)
    assert reason == 'ok'
    assert layout['mesh'] == {'data': 4, 'model': 1}
    assert layout['devices'] == 4
    assert layout['partition_rules'] == [['.*', []]]

    b = _run_learner(_args(model_dir, epochs=4, restart=-1,
                           metrics=metrics), 2, str(tmp_path), 'resume2')
    assert b['mesh'] == {'data': 2, 'model': 1}
    assert b['epoch_at_start'] == 2
    # bit-identical resumed params: what the 4-device run ended with is
    # exactly what the 2-device run starts from
    assert b['params_sha1_at_start'] == a['params_sha1_at_end']
    # the trainer state resumed too (not a params-only fallback): the
    # resumed step counter equals what epoch 2's checkpoint recorded (the
    # trainer thread's post-handover steps are uncheckpointed by design)
    with open(metrics) as f:
        rows = [json.loads(line) for line in f]
    a_rows = rows[:2]
    assert b['steps_at_start'] == a_rows[-1]['steps'] > 0
    assert 'resumed trainer state' in b['stdout']
    assert 'mesh-portable restore' in b['stdout']
    assert b['epoch'] == 4 and b['steps'] > b['steps_at_start']

    # the rewritten manifest now describes the NEW mesh
    layout, reason = read_layout_manifest(state_path)
    assert reason == 'ok' and layout['mesh'] == {'data': 2, 'model': 1}

    # metrics_jsonl: epoch/step counts monotonic across the mesh change
    steps_seq = [int(r['steps']) for r in rows]
    epochs_seq = [int(r['epoch']) for r in rows]
    assert steps_seq == sorted(steps_seq) and len(rows) >= 4
    assert epochs_seq == [1, 2, 3, 4]


def _write_fake_checkpoints(model_dir, layouts):
    """Numbered TicTacToe checkpoints with CRC sidecars and the given
    per-epoch layout bytes (None = no manifest)."""
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.utils.fs import checksummed_write_bytes, layout_path

    env = make_env({'env': 'TicTacToe'})
    env.reset()
    wrapper = ModelWrapper(env.net(), seed=3)
    wrapper.ensure_params(env.observation(env.players()[0]))
    raw = wrapper.params_bytes()
    os.makedirs(model_dir, exist_ok=True)
    for epoch, layout_bytes in layouts.items():
        path = os.path.join(model_dir, '%d.ckpt' % epoch)
        checksummed_write_bytes(path, raw)
        if layout_bytes is not None:
            with open(layout_path(path), 'wb') as f:   # deliberately raw
                f.write(layout_bytes)
    return raw


def test_corrupt_manifest_falls_back_to_newest_valid(tmp_path):
    """A PRESENT but unparsable layout manifest disqualifies its checkpoint
    exactly like a CRC failure: resume falls back to the previous valid
    epoch (the PR 4 path); a corrupt trainer_state manifest degrades to a
    params-only resume instead of trusting the pair."""
    from handyrl_tpu import telemetry
    from handyrl_tpu.train import Learner
    from handyrl_tpu.utils.fs import (checksummed_write_bytes,
                                      layout_path, read_layout_manifest)

    model_dir = str(tmp_path / 'models')
    good = json.dumps({'format': 1, 'mesh': None, 'devices': 1,
                       'processes': 1, 'partition_rules': [['.*', []]]}
                      ).encode()
    _write_fake_checkpoints(model_dir, {1: good, 2: b'{not json'})

    # a corrupt trainer_state manifest must force the params-only path
    state_path = os.path.join(model_dir, 'trainer_state.ckpt')
    checksummed_write_bytes(state_path, b'\x00' * 64)
    with open(layout_path(state_path), 'wb') as f:
        f.write(b'\xff\xfe garbage')
    assert read_layout_manifest(state_path) == (None, 'unparsable')

    fallbacks = telemetry.REGISTRY.counter('guard_ckpt_fallbacks_total')
    mark = fallbacks.value
    args = _args(model_dir, epochs=0, restart=-1)
    ln = Learner(args=args)
    # epoch 2's corrupt manifest was skipped; epoch 1 resumed
    assert ln.model_epoch == 1
    # trainer_state pair untrusted: optimizer restarted fresh
    assert ln.trainer.steps == 0
    assert fallbacks.value >= mark + 2
    ln.shutdown()


def test_missing_manifest_is_legacy_ok(tmp_path):
    """Checkpoints from before the manifest era (no .layout file) stay
    loadable — reason 'missing', resume proceeds."""
    from handyrl_tpu.train import Learner
    from handyrl_tpu.utils.fs import read_layout_manifest

    model_dir = str(tmp_path / 'models')
    _write_fake_checkpoints(model_dir, {3: None})
    assert read_layout_manifest(
        os.path.join(model_dir, '3.ckpt')) == (None, 'missing')
    ln = Learner(args=_args(model_dir, epochs=0, restart=-1))
    assert ln.model_epoch == 3
    ln.shutdown()
