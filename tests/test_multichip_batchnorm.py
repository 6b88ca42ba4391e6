"""norm_kind='batch' through the shard_map'd update on the 8-virtual-
device CPU mesh: each shard normalizes by its LOCAL slice (torch
DataParallel semantics, train_step._update_core) and the pmean'd running
averages must leave the replicated train state IDENTICAL on every shard
— the invariant that keeps params from silently diverging across chips."""

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.models.geister import GeisterNet
from handyrl_tpu.environment import make_env
from handyrl_tpu.ops.losses import LossConfig
from handyrl_tpu.ops.train_step import (_update_core, init_train_state,
                                        make_optimizer)
from tests.test_batchnorm_parity import geister_batch_and_wrapper  # noqa: F401


def test_shard_map_batchnorm_stats_replicated(geister_batch_and_wrapper):
    _, batch, args = geister_batch_and_wrapper
    devices = jax.devices()
    assert len(devices) >= 8, 'conftest forces an 8-virtual-device mesh'
    mesh = jax.sharding.Mesh(np.array(devices[:8]), ('data',))

    wrapper = ModelWrapper(GeisterNet(filters=8, drc_layers=2,
                                      drc_repeats=1, norm_kind='batch'))
    env = make_env({'env': 'Geister'})
    env.reset()
    wrapper.ensure_params(env.observation(0))
    state = init_train_state(jax.tree_util.tree_map(jnp.array,
                                                    wrapper.params))
    cfg = LossConfig.from_args(args)
    core = _update_core(wrapper.module, cfg, make_optimizer(),
                        axis_name='data')

    # 8 identical batch slices -> every shard sees the same local batch,
    # so the pmean'd stats must equal the single-shard stats and the
    # post-step state must be bit-identical across shards
    rep8 = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a] * 8, axis=0), batch)

    shard_map = partial(jax.shard_map, mesh=mesh, check_vma=False)

    P = jax.sharding.PartitionSpec

    def spec_like(tree, s):
        return jax.tree_util.tree_map(lambda _: s, tree)

    lr = jnp.asarray(1e-3, jnp.float32)
    # shape inference with the axis-free core (same output structure;
    # the psum'd core can only be traced under shard_map)
    out_shapes = jax.eval_shape(
        _update_core(wrapper.module, cfg, make_optimizer()),
        state, batch, lr)
    sharded = shard_map(
        lambda st, b, l: core(st, b, l),
        in_specs=(spec_like(state, P()), spec_like(rep8, P('data')), P()),
        out_specs=spec_like(out_shapes, P()),
    )

    state8, metrics8 = jax.jit(sharded)(state, rep8, lr)
    single_state, _ = jax.jit(
        _update_core(wrapper.module, cfg, make_optimizer()))(
        state, batch, lr)

    assert np.isfinite(float(metrics8['total']))
    # running averages advanced
    before = jax.tree_util.tree_leaves(state.params['batch_stats'])
    after = jax.tree_util.tree_leaves(state8.params['batch_stats'])
    assert max(float(jnp.abs(a - b).max())
               for a, b in zip(after, before)) > 1e-7
    # identical-slices construction: stats equal the single-device run's
    for a, b in zip(after, jax.tree_util.tree_leaves(
            single_state.params['batch_stats'])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
