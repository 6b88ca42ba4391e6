"""The fused device pipeline sharded over the 8-virtual-device CPU mesh:
device generation + device window ingest + device replay + SGD, end to end.

This is the multi-chip layout of the flagship loop (round-2 review, item 3):
shard_map over 'data' with per-shard env slices and ring shards, replicated
train state, and gradient psum — the only cross-chip traffic in steady
state. The reference scales actors with worker processes
(reference worker.py:169-254); this scales them with chips.
"""

import glob
import os

import jax
import numpy as np
import pytest

from handyrl_tpu.config import apply_defaults
from handyrl_tpu.train import Learner


@pytest.mark.timeout(560)
def test_ttt_fused_pipeline_sharded_e2e(tmp_path, capsys):
    assert len(jax.devices()) == 8       # conftest's virtual CPU mesh
    args = apply_defaults({
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 16, 'forward_steps': 8, 'update_episodes': 30,
            'minimum_episodes': 16, 'generation_envs': 16, 'eval_envs': 8,
            'epochs': 3, 'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 2, 'device_chunk_steps': 8,
            'model_dir': os.path.join(str(tmp_path), 'models')}})
    ln = Learner(args=args)
    ln.run()
    out = capsys.readouterr().out
    assert 'sharded over 8 devices' in out
    assert '"found": 8, "used": 8, "mesh": {"data": 8, "model": 1}' in out
    assert ln.model_epoch == 3
    assert ln.trainer.steps > 0
    assert ln.num_returned_episodes >= 30 * 3
    ckpts = glob.glob(os.path.join(str(tmp_path), 'models', '*.ckpt'))
    assert any(os.path.basename(p) == 'latest.ckpt' for p in ckpts)


def test_fused_pipeline_state_is_sharded(tmp_path):
    """The loop state really lives on the mesh: env axis and ring rows are
    split over 'data', train params replicated."""
    from handyrl_tpu.device_generation import DeviceEvaluator  # noqa: F401
    from handyrl_tpu.environment import make_jax_env
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.ops.device_windows import DeviceWindower
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    env_args = {'env': 'TicTacToe'}
    env = make_env(env_args)
    env.reset()
    wrapper = ModelWrapper(env.net())
    wrapper.ensure_params(env.observation(0))
    env_mod = make_jax_env(env_args)
    args = apply_defaults({'env_args': env_args, 'train_args': {
        'batch_size': 16, 'forward_steps': 8}})['train_args']
    wd = DeviceWindower(mode='turn', fs=8, bi=0, max_steps=9,
                        windows_cap=1, capacity=64,   # per-shard rows
                        num_players=2, gamma=0.8, has_reward=False)
    fp = FusedPipeline(env_mod, wrapper, LossConfig.from_args(args), wd,
                       args, n_envs=16, chunk_steps=8, sgd_steps=2,
                       batch_size=16, mesh=mesh)

    def names(arr):
        spec = arr.sharding.spec
        return tuple(spec) if spec else ()

    first_env_leaf = jax.tree_util.tree_leaves(fp.state)[0]
    assert names(first_env_leaf)[:1] == ('data',)
    ring_leaf = next(iter(fp.ring.values()))
    assert ring_leaf.shape[0] == 64 * 8          # global rows = shards x 8
    assert names(ring_leaf)[:1] == ('data',)
    assert np.asarray(fp.cursor).shape == (8,)   # one cursor per shard

    # one warmup dispatch executes across the mesh and returns a global
    # done/outcome pack of the full env count
    parsed = fp.warm_step(jax.device_put(
        wrapper.params,
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())))
    assert parsed is None                        # pipelined one deep
    parsed = fp.warm_step(jax.device_put(
        wrapper.params,
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())))
    assert parsed['done'].shape == (8, 16)
    assert parsed['outcome'].shape == (8, 16, 2)
