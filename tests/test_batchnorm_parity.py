"""Full BatchNorm parity for norm_kind='batch' (round-4 review, item 1).

The reference trains GeisterNet with nn.BatchNorm2d in the stem and both
heads (reference geister.py:107,122) and serves actors/evaluators in eval
mode with running averages (reference model.py:54 — ``self.eval()`` before
inference). These tests pin the three ingredients on this side:

  1. the norm block itself matches torch BatchNorm2d train-mode outputs
     exactly and eval-mode outputs through the running-average EMA;
  2. the compiled update step advances the ``batch_stats`` collection by
     EMA only — Adam never touches it (zero-grad moments + weight decay
     would shrink the averages toward 0);
  3. every inference path reads the running averages, so B=1 sequential
     host inference computes the SAME network function as the batched
     paths — the documented BatchStatsNorm trap (ADVICE r4) is gone for
     'batch' — and snapshots/checkpoints carry the averages.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.models.blocks import make_norm
from handyrl_tpu.models.geister import GeisterNet
from handyrl_tpu.ops.losses import split_batch_stats

torch = pytest.importorskip('torch')


def _np(x):
    return np.asarray(x, dtype=np.float32)


def test_make_norm_batch_matches_torch_bn2d():
    """Same data stream through flax make_norm('batch') and torch
    BatchNorm2d: train-mode outputs agree exactly (both normalize by the
    biased current-batch variance); after several EMA updates the
    running mean agrees exactly and the running variance to the
    unbiased-vs-biased estimator factor n/(n-1)."""
    rng = np.random.RandomState(0)
    B, H, W, C = 4, 6, 6, 5
    n = B * H * W

    tnorm = torch.nn.BatchNorm2d(C, eps=1e-5, momentum=0.1)
    tnorm.train()

    norm = make_norm('batch', C, jnp.float32, train=True)
    x0 = rng.randn(B, H, W, C).astype(np.float32)
    variables = norm.init(jax.random.PRNGKey(0), jnp.asarray(x0))

    for step in range(3):
        x = (rng.randn(B, H, W, C) * (1 + step) + 0.3 * step).astype(np.float32)
        y, mut = norm.apply(variables, jnp.asarray(x),
                            mutable=['batch_stats'])
        variables = {**variables, 'batch_stats': mut['batch_stats']}
        with torch.no_grad():
            ty = tnorm(torch.from_numpy(x.transpose(0, 3, 1, 2)))
        np.testing.assert_allclose(
            _np(y), ty.numpy().transpose(0, 2, 3, 1), atol=2e-5,
            err_msg='train-mode output step %d' % step)

    bs = variables['batch_stats']
    np.testing.assert_allclose(_np(bs['mean']),
                               tnorm.running_mean.numpy(), atol=1e-5)
    # torch's running update uses the unbiased batch variance; flax the
    # biased one — each EMA term differs by n/(n-1), so the averages agree
    # to that factor (1.7% at n=144); the init-value term is shared
    np.testing.assert_allclose(_np(bs['var']), tnorm.running_var.numpy(),
                               rtol=(1.0 / (n - 1)) * 1.5)

    # eval mode: both serve their running averages per-sample
    tnorm.eval()
    xe = rng.randn(1, H, W, C).astype(np.float32)
    enorm = make_norm('batch', C, jnp.float32, train=False)
    ye = enorm.apply(variables, jnp.asarray(xe))
    with torch.no_grad():
        tye = tnorm(torch.from_numpy(xe.transpose(0, 3, 1, 2)))
    np.testing.assert_allclose(_np(ye), tye.numpy().transpose(0, 2, 3, 1),
                               rtol=2e-2, atol=2e-3)


@pytest.fixture(scope='module')
def geister_batch_and_wrapper():
    """A small real Geister training batch + a norm_kind='batch' model."""
    import random
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import BatchedGenerator
    from handyrl_tpu.ops.batch import make_batch, select_episode

    random.seed(7)
    args = {
        'turn_based_training': True, 'observation': False,
        'gamma': 0.9, 'forward_steps': 8, 'burn_in_steps': 2,
        'compress_steps': 4, 'maximum_episodes': 100,
        'lambda': 0.7, 'policy_target': 'TD', 'value_target': 'TD',
        'entropy_regularization': 0.1, 'entropy_regularization_decay': 0.1,
    }
    env = make_env({'env': 'Geister'})
    env.reset()
    wrapper = ModelWrapper(GeisterNet(filters=8, drc_layers=2,
                                      drc_repeats=1, norm_kind='batch'))
    wrapper.ensure_params(env.observation(0))
    gen = BatchedGenerator(lambda i: make_env({'env': 'Geister'}), wrapper,
                           args, n_envs=4)
    episodes = []
    for _ in range(400):
        episodes += gen.step()
        if len(episodes) >= 4:
            break
    assert len(episodes) >= 4
    windows = [select_episode(episodes, args) for _ in range(4)]
    return wrapper, make_batch(windows, args), args


def test_update_step_advances_batch_stats_ema_only(geister_batch_and_wrapper):
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import build_update_step, init_train_state

    wrapper, batch, args = geister_batch_and_wrapper
    assert 'batch_stats' in wrapper.params, 'init must create running stats'

    state = init_train_state(jax.tree_util.tree_map(jnp.array, wrapper.params))
    # Adam state covers ONLY the trainable collections
    trainable, _ = split_batch_stats(state.params)
    opt_leaves = len(jax.tree_util.tree_leaves(state.opt_state))
    train_leaves = len(jax.tree_util.tree_leaves(trainable))
    all_leaves = len(jax.tree_util.tree_leaves(state.params))
    assert all_leaves > train_leaves, 'batch_stats leaves exist'
    # clip + weight-decay carry no state; adam = (mu, nu) per TRAINABLE
    # leaf + 1 count scalar. Equality pins Adam to exactly the trainable
    # set — covering batch_stats too would give 2*all_leaves + 1
    assert opt_leaves == 2 * train_leaves + 1, \
        'optimizer must cover exactly the trainable collections'

    update = build_update_step(wrapper.module, LossConfig.from_args(args),
                               mesh=None, donate=False)
    before = jax.tree_util.tree_map(np.array, state.params['batch_stats'])
    state2, metrics = update(state, batch, jnp.float32(1e-3))
    after = state2.params['batch_stats']

    moved = [float(np.max(np.abs(_np(a) - b)))
             for a, b in zip(jax.tree_util.tree_leaves(after),
                             jax.tree_util.tree_leaves(before))]
    assert max(moved) > 1e-6, 'running averages must advance during training'
    assert np.isfinite(float(metrics['total']))

    # second application must keep advancing (scan carry, not a one-shot)
    state3, _ = update(state2, batch, jnp.float32(1e-3))
    moved2 = [float(np.max(np.abs(_np(a) - _np(b))))
              for a, b in zip(jax.tree_util.tree_leaves(state3.params['batch_stats']),
                              jax.tree_util.tree_leaves(after))]
    assert max(moved2) > 1e-7


def test_b1_inference_matches_batched_rows(geister_batch_and_wrapper):
    """Running-average inference is per-sample: the sequential B=1 host
    paths (worker Evaluator, NetworkAgent) now compute the same network
    function as the batched actors (the BatchStatsNorm trap, ADVICE r4)."""
    from handyrl_tpu.environment import make_env

    wrapper, _, _ = geister_batch_and_wrapper
    env = make_env({'env': 'Geister'})
    env.reset()
    obs0 = env.observation(0)
    obs1 = env.observation(1)

    h1 = wrapper.init_hidden()
    single = wrapper.inference(obs0, h1)

    obs_b = jax.tree_util.tree_map(
        lambda a, b: np.stack([a, b]), obs0, obs1)
    hb = wrapper.init_hidden((2,))
    batched = wrapper.batch_inference(obs_b, hb)
    np.testing.assert_allclose(single['policy'],
                               _np(batched['policy'][0]), atol=1e-5)
    np.testing.assert_allclose(single['value'],
                               _np(batched['value'][0]), atol=1e-5)


def test_snapshot_roundtrip_carries_batch_stats(geister_batch_and_wrapper):
    from handyrl_tpu.environment import make_env

    wrapper, _, _ = geister_batch_and_wrapper
    env = make_env({'env': 'Geister'})
    env.reset()

    # perturb the running stats so the roundtrip can't pass by init values
    params = dict(wrapper.params)
    params['batch_stats'] = jax.tree_util.tree_map(
        lambda v: v + 0.25, params['batch_stats'])
    src = ModelWrapper(wrapper.module, params)
    snap = src.snapshot()
    dst = ModelWrapper.from_snapshot(snap, env.observation(0))
    for a, b in zip(jax.tree_util.tree_leaves(src.params['batch_stats']),
                    jax.tree_util.tree_leaves(dst.params['batch_stats'])):
        np.testing.assert_allclose(_np(a), _np(b))
    # and the served function reflects them
    out_src = src.inference(env.observation(0), src.init_hidden())
    out_dst = dst.inference(env.observation(0), dst.init_hidden())
    np.testing.assert_allclose(out_src['policy'], out_dst['policy'], atol=1e-6)


def test_norm_kind_env_args_plumbing_geese():
    """env_args {'norm_kind': 'batch'} reaches GeeseNet (caught live:
    the geese env didn't store self.args)."""
    from handyrl_tpu.environment import make_env
    env = make_env({'env': 'HungryGeese', 'norm_kind': 'batch'})
    assert env.net().norm_kind == 'batch'
    assert make_env({'env': 'HungryGeese'}).net().norm_kind == 'group'


def test_spatial_policy_head_layout_and_plumbing():
    """SpatialPolicyHead flattens channel-major: logit index =
    direction*36 + x*6 + y, the env's move encoding
    (envs/geister.py:114-118). Pinned by forcing the final 1x1 conv to
    emit direction-constant maps and checking where they land."""
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.blocks import SpatialPolicyHead

    head = SpatialPolicyHead(4, 4)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 6, 8),
                    jnp.float32)
    variables = head.init(jax.random.PRNGKey(0), x)
    out = head.apply(variables, x)
    assert out.shape == (2, 144)

    # zero the final conv kernel, set bias[f] = f: every cell of
    # direction-plane f must read f after flattening
    params = jax.tree_util.tree_map(np.array, variables['params'])
    last = sorted(k for k in params if k.startswith('Conv'))[-1]
    params[last]['kernel'] = np.zeros_like(params[last]['kernel'])
    params[last]['bias'] = np.arange(4, dtype=np.float32)
    out = np.asarray(head.apply({'params': params}, x))
    for d in range(4):
        for cell in (0, 7, 35):
            assert out[0, d * 36 + cell] == d

    # env_args plumbing + the A/B config (spatial head + full BatchNorm)
    # constructs, serves B=1 inference, and takes a training step
    env = make_env({'env': 'Geister', 'policy_head': 'spatial',
                    'norm_kind': 'batch'})
    assert env.net().policy_head == 'spatial'
    assert make_env({'env': 'Geister'}).net().policy_head == 'dense'
    from handyrl_tpu.model import ModelWrapper
    env.reset()
    w = ModelWrapper(env.net())
    out = w.inference(env.observation(0), w.init_hidden())
    assert out['policy'].shape == (214,)
    assert np.all(np.isfinite(out['policy']))
    assert 'batch_stats' in w.params


def test_spatial_batch_head_trains(geister_batch_and_wrapper):
    """One compiled update step on the exact round-5 A/B model config
    (policy_head='spatial', norm_kind='batch'): finite loss, advancing
    running stats — so the combination cannot first fail mid-benchmark."""
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import build_update_step, init_train_state

    _, batch, args = geister_batch_and_wrapper
    wrapper = ModelWrapper(GeisterNet(filters=8, drc_layers=2,
                                      drc_repeats=1, norm_kind='batch',
                                      policy_head='spatial'))
    from handyrl_tpu.environment import make_env
    env = make_env({'env': 'Geister'})
    env.reset()
    wrapper.ensure_params(env.observation(0))
    state = init_train_state(jax.tree_util.tree_map(jnp.array, wrapper.params))
    update = build_update_step(wrapper.module, LossConfig.from_args(args),
                               mesh=None, donate=False)
    before = jax.tree_util.tree_map(np.array, state.params['batch_stats'])
    state2, metrics = update(state, batch, jnp.float32(1e-3))
    assert np.isfinite(float(metrics['total']))
    moved = [float(np.max(np.abs(_np(a) - b)))
             for a, b in zip(jax.tree_util.tree_leaves(
                 state2.params['batch_stats']),
                 jax.tree_util.tree_leaves(before))]
    assert max(moved) > 1e-7
