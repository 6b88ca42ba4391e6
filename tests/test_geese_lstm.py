"""Recurrent Hungry Geese model: hidden carry and training through the
observation-mode RNN path (the LSTM-era baseline configuration)."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.models import build
from handyrl_tpu.ops import losses
from handyrl_tpu.ops.losses import (LossConfig, compute_loss,
                                    forward_prediction, split_batch_stats)

tmap = jax.tree_util.tree_map


def _obs(rng):
    obs = (rng.rand(17, 7, 11) < 0.1).astype(np.float32)
    obs[0] = 0
    obs[0, 3, 5] = 1.0
    return obs


def test_hidden_carry_and_shapes():
    rng = np.random.RandomState(0)
    wrapper = ModelWrapper(build('GeeseNetLSTM', filters=8, stem_layers=1))
    obs = _obs(rng)
    h0 = wrapper.init_hidden()
    out = wrapper.inference(obs, h0)
    assert out['policy'].shape == (4,)
    assert out['hidden'][0].shape == (7, 11, 8)
    out2 = wrapper.inference(obs, out['hidden'])
    assert not np.allclose(out['hidden'][0], out2['hidden'][0])


def test_trains_through_rnn_path():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        raw = {
            'env_args': {'env': 'HungryGeese'},
            'train_args': {
                'turn_based_training': False, 'observation': True,
                'gamma': 0.99, 'forward_steps': 6, 'burn_in_steps': 2,
                'batch_size': 8, 'update_episodes': 6, 'minimum_episodes': 6,
                'epochs': 1, 'generation_envs': 4, 'num_batchers': 1,
                'policy_target': 'VTRACE', 'value_target': 'VTRACE',
                'model_dir': td + '/models',
            },
        }
        learner = Learner(args=apply_defaults(raw),
                          net=build('GeeseNetLSTM', filters=8, stem_layers=1))
        learner.run()
        assert learner.model_epoch == 1


# ---------------------------------------------------------------------------
# the learner's scan over plies, whose backward pass recomputes each ply


# what ``GeeseNetLSTM().init`` gives and every checkpoint of it holds
PARENT_TREE = {
    'ConvLSTMCell_0': {'Conv_0': {'bias': (128,),
                                  'kernel': (3, 3, 64, 128)}},
    'Dense_0': {'kernel': (32, 4)},
    'Dense_1': {'kernel': (64, 1)},
    'TorusConv_0': {'Conv_0': {'kernel': (3, 3, 17, 32)},
                    'GroupNorm_0': {'bias': (32,), 'scale': (32,)}},
    **{'TorusConv_%d' % i: {'Conv_0': {'kernel': (3, 3, 32, 32)},
                            'GroupNorm_0': {'bias': (32,), 'scale': (32,)}}
       for i in range(1, 5)},
}


def _board(rng, lead):
    obs = (rng.rand(*lead, 17, 7, 11) < 0.1).astype(np.float32)
    obs[..., 0, :, :] = 0
    obs[..., 0, 3, 5] = 1.0
    return jnp.asarray(obs)


def test_parameter_tree_is_written_out_and_the_checkpoint_loads():
    from flax import serialization
    net = build('GeeseNetLSTM')
    obs = _board(np.random.RandomState(0), (3,))
    params = net.init(jax.random.PRNGKey(0), obs, None)
    assert tmap(lambda x: x.shape, params) == {'params': PARENT_TREE}
    path = os.path.join(os.path.dirname(__file__), '..', 'benchmark',
                        'checkpoints', 'geese_lstm.ckpt')
    with open(path, 'rb') as f:
        stored = serialization.msgpack_restore(f.read())
    stored = stored.get('params', stored)
    stored = stored if 'params' in stored else {'params': stored}
    assert tmap(lambda x: x.shape, stored) == {'params': PARENT_TREE}
    loaded = serialization.from_state_dict(params, stored)
    out = net.apply(loaded, obs, None)
    assert np.isfinite(np.asarray(out['policy'])).all()
    assert not np.array_equal(np.asarray(out['policy']),
                              np.asarray(net.apply(params, obs, None)['policy']))


LSTM_CFG = LossConfig(turn_based_training=False, observation=True,
                      burn_in_steps=4, policy_target='VTRACE',
                      value_target='VTRACE', gamma=0.99)
B, T, P = 3, 20, 4


def _window_batch(kind, seed=0):
    """(B, T, P) windows of 4 burn-in + 16 trained plies. ``fills``: every
    ply lies in its game; ``ends_inside``: the games end at plies 19, 11 and
    6 (one inside the burn-in's reach of nothing, one mid-window, one two
    plies after the burn-in) and seats leave earlier; ``holes``: seats stop
    and START observing again, which the carry's gate and merge answer."""
    rng = np.random.RandomState(seed)
    alive = np.ones((B, T, P, 1), np.float32)
    if kind != 'fills':
        for b, end in enumerate((19, 11, 6)):
            alive[b, end:] = 0
            alive[b, end - 3:, b] = 0          # a goose out before the end
    omask = alive.copy()
    if kind == 'holes':
        omask *= (rng.rand(B, T, P, 1) < 0.7)
        omask[0, 2, 1] = omask[1, 5, 0] = 0    # one in the burn-in, one after
        omask[0, 3, 1] = omask[1, 6, 0] = 1
    emask = alive.max(axis=2, keepdims=True)
    return {
        'observation': _board(rng, (B, T, P)),
        'action': jnp.asarray(rng.randint(4, size=(B, T, P, 1))),
        'selected_prob': jnp.asarray(rng.uniform(0.1, 0.9, (B, T, P, 1)),
                                     jnp.float32),
        'action_mask': jnp.zeros((B, T, P, 4), jnp.float32),
        'turn_mask': jnp.asarray(omask), 'observation_mask': jnp.asarray(omask),
        'episode_mask': jnp.asarray(emask),
        'value': jnp.zeros((B, T, P, 1), jnp.float32),
        'reward': jnp.zeros((B, T, P, 1), jnp.float32),
        'return': jnp.zeros((B, T, P, 1), jnp.float32),
        'outcome': jnp.asarray(np.sign(rng.randn(B, 1, P, 1)), jnp.float32),
        'progress': jnp.asarray(rng.rand(B, T, 1), jnp.float32),
    }


def _whole_net_a_ply(apply_fn, params, hidden, batch, cfg, batch_stats=None,
                     sequence_fn=None):
    """``forward_prediction``'s recurrent branch written out as a loop, for
    a net that observes on every seat: the whole net a ply at a time on the
    B*P rows, the carry gated and merged by ``observation_mask``, cut after
    the burn-in, whose outputs read zero. Plain autodiff keeps every ply's
    residuals: nothing here is recomputed."""
    obs, omask = batch['observation'], batch['observation_mask']
    outs = []
    for t in range(T):
        m = lambda h: omask[:, t].reshape((B, P) + (1,) * (h.ndim - 2))
        h_in = tmap(lambda h: (h * m(h)).reshape((-1,) + h.shape[2:]), hidden)
        obs_in = obs[:, t].reshape((-1,) + obs.shape[3:])
        if batch_stats is None:
            out = dict(apply_fn(params, obs_in, h_in))
        else:
            out, mut = apply_fn({**params, 'batch_stats': batch_stats},
                                obs_in, h_in, train=True,
                                mutable=['batch_stats'])
            out, batch_stats = dict(out), lax.stop_gradient(
                mut['batch_stats'])
        nxt = tmap(lambda h: h.reshape((B, P) + h.shape[1:]),
                   out.pop('hidden'))
        hidden = tmap(lambda h, n: h * (1 - m(h)) + n * m(h), hidden, nxt)
        if t == cfg.burn_in_steps - 1:
            hidden = lax.stop_gradient(hidden)
        outs.append({k: (v * (t >= cfg.burn_in_steps)).reshape(
            (B, P) + v.shape[1:]) for k, v in out.items()})
    policy = jnp.stack([o['policy'] for o in outs], axis=1)
    value = jnp.stack([o['value'] for o in outs], axis=1)
    masked = {'policy': policy * batch['turn_mask'] - batch['action_mask'],
              'value': value * omask}
    return masked if batch_stats is None else (masked, batch_stats)


def _lstm(seed=3, **kwargs):
    net = build('GeeseNetLSTM', filters=8, stem_layers=2, **kwargs)
    variables = net.init(jax.random.PRNGKey(seed),
                         _board(np.random.RandomState(seed), (2,)), None)
    # heads and norms away from their initial symmetric values
    variables = tmap(lambda x: x + 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), x.shape), variables)
    return net, variables, net.init_hidden((B, P))


@pytest.mark.parametrize('norm_kind', ['group', 'batch'])
@pytest.mark.parametrize('kind', ['fills', 'ends_inside', 'holes'])
def test_scan_is_the_written_out_loop(kind, norm_kind, monkeypatch):
    """The scan whose backward pass recomputes each ply, against the loop
    that keeps every residual: outputs to 1e-5, ``compute_loss``'s total and
    every gradient leaf to 1e-4 of its size. A ``norm_kind='batch'`` net's
    statistics are per ply over the B x P rows, the recomputed ply's too,
    and its running averages advance as the loop's."""
    net, variables, hidden = _lstm(norm_kind=norm_kind)
    params, stats = split_batch_stats(variables)
    assert (stats is not None) == (norm_kind == 'batch')
    batch = _window_batch(kind)

    got = forward_prediction(net.apply, params, hidden, batch, LSTM_CFG,
                             batch_stats=stats)
    want = _whole_net_a_ply(net.apply, params, hidden, batch, LSTM_CFG,
                            batch_stats=stats)
    if stats is not None:
        (got, new_stats), (want, want_stats) = got, want
        moved = False
        for a, b, c in zip(*map(jax.tree_util.tree_leaves,
                                (new_stats, want_stats, stats))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
            moved |= not np.allclose(np.asarray(a), np.asarray(c))
        assert moved
    assert sorted(got) == ['policy', 'value']
    for k in want:
        assert got[k].shape == want[k].shape
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=0, atol=1e-5, err_msg=k)
    assert not np.asarray(got['value'][:, :4]).any()      # the burn-in

    def total_and_grads():
        return jax.value_and_grad(lambda p: compute_loss(
            net.apply, p, hidden, batch, LSTM_CFG,
            batch_stats=stats)[0])(params)
    loss, grads = total_and_grads()
    monkeypatch.setattr(losses, 'forward_prediction', _whole_net_a_ply)
    loss_ref, grads_ref = total_and_grads()
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-4)
    flat, flat_ref = (jax.tree_util.tree_leaves_with_path(g)
                      for g in (grads, grads_ref))
    assert len(flat) == len(flat_ref) == 13
    for (path, g), (_, g_ref) in zip(flat, flat_ref):
        scale = float(jnp.abs(g_ref).max())
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), rtol=0, atol=1e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


def _scans(jaxpr, found=None):
    """Every ``scan`` of a jaxpr, those inside calls and other scans too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'scan':
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, found)
    return found


def test_scan_stacks_its_carry_and_no_other_feature_map():
    """The backward pass recomputes a ply from what entered it, so of all
    a ply's feature maps the trained plies' forward scan stacks two: the
    carry's ``h`` and ``c``. (Plain autodiff stacks a dozen more, and the
    chip relays each one for the backward pass.)"""
    net, params, hidden = _lstm()
    batch = _window_batch('fills')
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: compute_loss(
        net.apply, p, hidden, batch, LSTM_CFG)[0]))(params).jaxpr
    trained = [e for e in _scans(jaxpr) if e.params['length'] == 16]
    assert len(trained) == 2                   # the forward and its backward
    forward = next(e for e in trained if not e.params['reverse'])
    stacked = forward.outvars[forward.params['num_carry']:]
    maps = [v.aval.shape for v in stacked if len(v.aval.shape) >= 4
            and v.aval.shape[-3:-1] == (7, 11)]
    assert maps == [(16, B, P, 7, 11, 8)] * 2, maps


def test_burn_in_is_one_forward_scan_of_the_ply_traced_once():
    """The burn-in's carry is cut, so its parameters enter as constants:
    the gradient's program holds its forward scan and no backward one, it
    stacks no feature map, and the net's Python runs once for both scans
    (the burn-in reuses the ply traced for ``jax.checkpoint``)."""
    net, params, hidden = _lstm()
    batch = _window_batch('fills')
    applies = []

    def apply_fn(*args, **kwargs):
        applies.append(1)
        return net.apply(*args, **kwargs)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: compute_loss(
        apply_fn, p, hidden, batch, LSTM_CFG)[0]))(params).jaxpr
    assert len(applies) == 1
    burn_in, = [e for e in _scans(jaxpr) if e.params['length'] == 4]
    assert not burn_in.params['reverse']
    stacked = burn_in.outvars[burn_in.params['num_carry']:]
    assert all(len(v.aval.shape) < 5 for v in stacked)


def test_burn_in_plies_take_no_gradient():
    net, params, hidden = _lstm()
    batch = _window_batch('fills')
    d_obs = jax.grad(lambda obs: compute_loss(
        net.apply, params, hidden, {**batch, 'observation': obs},
        LSTM_CFG)[0])(batch['observation'])
    assert not np.asarray(d_obs[:, :4]).any()
    assert np.asarray(d_obs[:, 4:]).any()
