"""Loss pipeline tests: forward masking/turn-gather, RNN hidden gating,
burn-in stop-gradient, and the compiled update step (single device + 8-device
mesh)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn
from jax.flatten_util import ravel_pytree

from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.models.geese import GeeseNet
from handyrl_tpu.models.tictactoe import SimpleConv2dModel
from handyrl_tpu.ops.batch import make_batch
from handyrl_tpu.ops.losses import (LossConfig, _fold_bt, _unfold_bt,
                                    compute_loss, forward_prediction,
                                    split_batch_stats)
from handyrl_tpu.ops.train_step import build_update_step, init_train_state
from handyrl_tpu.parallel.mesh import make_mesh, shard_batch

from helpers import turn_based_episode, train_args, window

tmap = jax.tree_util.tree_map


def _ttt_batch(B=4, steps=5, fs=4):
    eps = [window(turn_based_episode(steps, seed=i), 0, min(fs, steps))
           for i in range(B)]
    return make_batch(eps, train_args(forward_steps=fs))


def _params(module, batch):
    obs = jax.tree_util.tree_map(lambda o: o[:, 0, 0], batch['observation'])
    return module.init(jax.random.PRNGKey(0), obs, None)


def test_forward_prediction_turn_gather_and_masks():
    """Stub net with known outputs: verify turn-gather and mask algebra."""
    batch = _ttt_batch(B=2)

    def stub_apply(params, obs, hidden):
        s = obs.reshape(obs.shape[0], -1).sum(-1, keepdims=True)
        return {'policy': jnp.tile(s, (1, 9)), 'value': jnp.tanh(s)}

    cfg = LossConfig()
    out = forward_prediction(stub_apply, None, None, batch, cfg)
    B, T = batch['action'].shape[:2]
    # policy: (B,T,1,9) after turn-gather, minus action mask
    assert out['policy'].shape == (B, T, 1, 9)
    obs_sum = np.asarray(batch['observation']).reshape(B, T, -1).sum(-1)
    want = obs_sum[..., None, None] * np.asarray(batch['turn_mask']).sum(2, keepdims=True) \
        - np.asarray(batch['action_mask'])
    np.testing.assert_allclose(np.asarray(out['policy']), want, rtol=1e-4)
    # value: broadcast over P then masked by omask -> zero where not observed
    assert out['value'].shape == (B, T, 2, 1)
    omask = np.asarray(batch['observation_mask'])
    assert np.all(np.asarray(out['value'])[omask == 0] == 0)


def test_compute_loss_finite_and_grads_flow():
    batch = _ttt_batch()
    module = SimpleConv2dModel()
    params = _params(module, batch)
    cfg = LossConfig()

    def loss_fn(p):
        total, aux = compute_loss(module.apply, p, None, batch, cfg)
        return total, aux

    (total, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert np.isfinite(float(total))
    for k in ('p', 'v', 'ent', 'total'):
        assert np.isfinite(float(aux['losses'][k])), k
    gnorm = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.abs(l).sum()), grads, 0.0)
    assert gnorm > 0
    assert float(aux['data_count']) == float(np.asarray(batch['turn_mask']).sum())


@pytest.mark.parametrize('pt,vt', [('TD', 'TD'), ('UPGO', 'VTRACE'), ('MC', 'MC')])
def test_loss_all_target_algorithms(pt, vt):
    batch = _ttt_batch(B=2)
    module = SimpleConv2dModel()
    params = _params(module, batch)
    cfg = LossConfig(policy_target=pt, value_target=vt)
    total, _ = compute_loss(module.apply, params, None, batch, cfg)
    assert np.isfinite(float(total))


class TinyRNN(nn.Module):
    """Minimal recurrent net over (3,3,3) obs for RNN-path tests."""
    features: int = 4

    def init_hidden(self, batch_shape=()):
        return (jnp.zeros(tuple(batch_shape) + (self.features,)),)

    @nn.compact
    def __call__(self, obs, hidden):
        x = obs.reshape(obs.shape[:-3] + (-1,))
        if hidden is None:
            hidden = self.init_hidden(x.shape[:-1])
        h_prev = hidden[0]
        h = jnp.tanh(nn.Dense(self.features)(x) + nn.Dense(self.features)(h_prev))
        policy = nn.Dense(9)(h)
        value = jnp.tanh(nn.Dense(1)(h))
        return {'policy': policy, 'value': value, 'hidden': (h,)}


def _rnn_setup(burn_in=0, fs=4, steps=6):
    eps = [window(turn_based_episode(steps, seed=i), 0, min(fs + burn_in, steps),
                  train_start=burn_in)
           for i in range(2)]
    args = train_args(forward_steps=fs, burn_in=burn_in)
    batch = make_batch(eps, args)
    module = TinyRNN()
    obs = jax.tree_util.tree_map(lambda o: o[:, 0, 0], batch['observation'])
    params = module.init(jax.random.PRNGKey(1), obs, None)
    B, P = batch['value'].shape[0], batch['value'].shape[2]
    hidden = module.init_hidden((B, P))
    return module, params, hidden, batch, args


def test_rnn_forward_and_loss():
    module, params, hidden, batch, args = _rnn_setup()
    cfg = LossConfig.from_args(args)
    total, aux = compute_loss(module.apply, params, hidden, batch, cfg)
    assert np.isfinite(float(total))
    grads = jax.grad(lambda p: compute_loss(module.apply, p, hidden, batch, cfg)[0])(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    assert any(np.abs(np.asarray(g)).sum() > 0 for g in flat)


def test_rnn_burn_in_matches_T_slicing():
    """With burn-in, loss terms only cover the main window; output time length
    must equal forward_steps after slicing."""
    module, params, hidden, batch, args = _rnn_setup(burn_in=2, fs=3, steps=6)
    cfg = LossConfig.from_args(args)
    assert batch['observation'].shape[1] == 5   # burn_in + forward
    out = forward_prediction(module.apply, params, hidden, batch, cfg)
    assert out['policy'].shape[1] == 5          # full window, burn-in rows zeroed
    total, aux = compute_loss(module.apply, params, hidden, batch, cfg)
    assert np.isfinite(float(total))


def test_compute_loss_off_policy_diagnostics():
    """The learning-dynamics aux: rho/c clip counts and importance-ratio
    moments, summed over acting pairs so data_count normalizes them."""
    batch = _ttt_batch()
    module = SimpleConv2dModel()
    params = _params(module, batch)
    _total, aux = compute_loss(module.apply, params, None, batch,
                               LossConfig())
    diag = aux['diag']
    dcnt = float(aux['data_count'])
    for key in ('rho_clip', 'c_clip', 'rho_sum', 'rho_sq_sum'):
        assert np.isfinite(float(diag[key])), key
    # clip fractions are counts of acting pairs: within [0, data_count]
    assert 0.0 <= float(diag['rho_clip']) <= dcnt
    assert 0.0 <= float(diag['c_clip']) <= dcnt
    # the ratio's second moment dominates its first (Jensen)
    assert float(diag['rho_sq_sum']) >= 0.0
    assert float(diag['rho_sum']) > 0.0


def test_update_step_emits_diag_metrics():
    """diag_* metrics (incl. the global grad norm) ride the compiled step's
    metric dict — and stay off the loss-line keys (no 'diag_' prefix there
    would leak into the printed reference format)."""
    batch = _ttt_batch(B=4)
    module = SimpleConv2dModel()
    state = init_train_state(_params(module, batch))
    step = build_update_step(module, LossConfig(), donate=False)
    _state2, metrics = step(state, batch, jnp.asarray(1e-3, jnp.float32))
    for key in ('diag_grad_norm', 'diag_rho_clip', 'diag_rho_sum'):
        assert key in metrics, sorted(metrics)
        assert np.isfinite(float(metrics[key])), key
    assert float(metrics['diag_grad_norm']) > 0


def test_update_step_single_device():
    batch = _ttt_batch(B=4)
    module = SimpleConv2dModel()
    params = _params(module, batch)
    state = init_train_state(params)
    step = build_update_step(module, LossConfig(), donate=False)
    lr = jnp.asarray(1e-3, jnp.float32)
    state2, metrics = step(state, batch, lr)
    assert int(state2.steps) == 1
    assert np.isfinite(float(metrics['total']))
    # params changed
    diff = jax.tree_util.tree_reduce(
        lambda a, pq: a + float(jnp.abs(pq).sum()),
        jax.tree_util.tree_map(lambda a, b: a - b, state.params, state2.params), 0.0)
    assert diff > 0


def test_update_step_8_device_mesh():
    """The full data-parallel path on the virtual 8-device CPU mesh."""
    assert len(jax.devices()) == 8, 'conftest must force 8 virtual devices'
    mesh = make_mesh()
    batch = _ttt_batch(B=8)
    module = SimpleConv2dModel()
    params = _params(module, batch)
    state = init_train_state(params)
    step = build_update_step(module, LossConfig(), mesh=mesh, donate=False)
    sbatch = shard_batch(mesh, batch)
    state2, metrics = step(state, sbatch, jnp.asarray(1e-3, jnp.float32))
    assert np.isfinite(float(metrics['total']))
    # sharded-batch result must match the single-device program
    step1 = build_update_step(module, LossConfig(), donate=False)
    _, metrics1 = step1(state, batch, jnp.asarray(1e-3, jnp.float32))
    np.testing.assert_allclose(float(metrics['total']), float(metrics1['total']),
                               rtol=2e-3)

def test_update_step_with_target_network():
    """IMPACT clipped target network (streaming.target_clip): the 4-arg
    compiled step runs, emits diag_target_* metrics, and — with the target
    an exact copy of the live params and target_clip == clip_rho — computes
    the same loss as the 3-arg step (rhos_tgt == rhos)."""
    batch = _ttt_batch(B=4)
    module = SimpleConv2dModel()
    params = _params(module, batch)
    state = init_train_state(params)
    cfg = LossConfig(target_clip=1.0)
    step = build_update_step(module, cfg, donate=False, use_target=True)
    lr = jnp.asarray(1e-3, jnp.float32)
    target = jax.tree_util.tree_map(jnp.copy, params)
    state2, metrics = step(state, batch, lr, target)
    for key in ('diag_target_clip', 'diag_target_ratio_sum',
                'diag_target_gap_sum'):
        assert key in metrics, sorted(metrics)
        assert np.isfinite(float(metrics[key])), key
    # fresh sync: the live policy IS the target -> zero log-prob gap
    np.testing.assert_allclose(float(metrics['diag_target_gap_sum']), 0.0,
                               atol=1e-5)
    base = build_update_step(module, LossConfig(), donate=False)
    _, metrics0 = base(state, batch, lr)
    np.testing.assert_allclose(float(metrics['total']),
                               float(metrics0['total']), rtol=1e-5)

    # a LAGGED target (one update old) changes the targets but stays finite,
    # and the policy gradient still flows through the live params
    state3, metrics_lag = step(state2, batch, lr, target)
    assert np.isfinite(float(metrics_lag['total']))
    assert int(state3.steps) == 2
    assert abs(float(metrics_lag['diag_target_gap_sum'])) > 0


def test_update_step_target_network_on_mesh():
    """The 4-arg program's mesh shardings: target params replicate like
    the state and the sharded result matches the single-device program."""
    assert len(jax.devices()) == 8
    mesh = make_mesh()
    batch = _ttt_batch(B=8)
    module = SimpleConv2dModel()
    state = init_train_state(_params(module, batch))
    cfg = LossConfig(target_clip=1.0)
    target = jax.tree_util.tree_map(jnp.copy, state.params)
    lr = jnp.asarray(1e-3, jnp.float32)
    step = build_update_step(module, cfg, mesh=mesh, donate=False,
                             use_target=True)
    _, metrics = step(state, shard_batch(mesh, batch), lr, target)
    step1 = build_update_step(module, cfg, donate=False, use_target=True)
    _, metrics1 = step1(state, batch, lr, target)
    np.testing.assert_allclose(float(metrics['total']),
                               float(metrics1['total']), rtol=2e-3)


# --- the feed-forward fold is time-major (PR 39) -------------------------
#
# `_fold_bt` hands the net (T*B*P, ...) rows and `_unfold_bt` brings its
# outputs back to (B, T, P, ...): every feed-forward net is a function of one
# row at a time, so the order of the fold must not show in any output.

class TinyDictNet(nn.Module):
    """Feed-forward net over Geister's observation pytree: a (7, 6, 6)
    board and 18 scalars, each leaf folded on its own by ``tmap``."""

    @nn.compact
    def __call__(self, obs, hidden=None):
        x = jnp.concatenate(
            [obs['board'].reshape(obs['board'].shape[:-3] + (-1,)),
             obs['scalar']], axis=-1)
        h = jnp.tanh(nn.Dense(16)(x))
        return {'policy': nn.Dense(5)(h), 'value': jnp.tanh(nn.Dense(1)(h))}


def _random_ff_batch(obs_shapes, B, T, P, n_actions, dtype, seed=0):
    """A random (B, T, P, ...) batch of 0/1 planes (what the games' nets
    read) with every mask open, so the masked outputs of
    ``forward_prediction`` ARE the net's outputs."""
    rng = np.random.RandomState(seed)
    draw = lambda shape: jnp.asarray(
        rng.random_sample((B, T, P) + shape) < 0.2, dtype)
    if isinstance(obs_shapes, dict):
        obs = {k: draw(s) for k, s in obs_shapes.items()}
    else:
        obs = draw(obs_shapes)
    return {'observation': obs,
            'action': jnp.zeros((B, T, P, 1), jnp.int32),
            'turn_mask': jnp.ones((B, T, P, 1), dtype),
            'observation_mask': jnp.ones((B, T, P, 1), dtype),
            'action_mask': jnp.zeros((B, T, P, n_actions), dtype)}


# name -> (module, observation shapes, actions, dtype). BatchNorm's variance
# is E[x^2] - E[x]^2 and the net divides by its root, which carries the
# float32 rounding of a sum taken in another order to 1e-5 of an output:
# that case runs in float64, where "the same but for rounding" reads 1e-13
_FF_NETS = {
    'geese_group': (lambda dt: GeeseNet(filters=8, layers=2, dtype=dt),
                    (17, 7, 11), 4, 'float32'),
    'geese_batch': (lambda dt: GeeseNet(filters=8, layers=2, dtype=dt,
                                        norm_kind='batch'),
                    (17, 7, 11), 4, 'float64'),
    'dict_obs': (lambda dt: TinyDictNet(),
                 {'board': (7, 6, 6), 'scalar': (18,)}, 5, 'float32'),
}


@pytest.mark.parametrize('p_obs', [1, 4])
@pytest.mark.parametrize('net', sorted(_FF_NETS))
def test_forward_prediction_is_the_net_window_by_window(net, p_obs):
    """Folded time-major or not, row (b, t, p) of every output is the net on
    observation (b, t, p). Nets without statistics across rows are applied
    one window at a time; with ``norm_kind='batch'`` the statistics span the
    whole fold (the reference's flattened forward), so the comparison is the
    net on the window-major fold: same rows, same statistics, and the same
    new ``batch_stats``, up to the rounding of a sum taken in another
    order."""
    make, obs_shapes, n_actions, dtype = _FF_NETS[net]
    B, T = 3, 5
    with jax.enable_x64(dtype == 'float64'):
        dtype = jnp.dtype(dtype)
        batch = _random_ff_batch(obs_shapes, B, T, p_obs, n_actions, dtype)
        obs = batch['observation']
        module = make(dtype)
        variables = tmap(lambda v: v.astype(dtype), module.init(
            jax.random.PRNGKey(2), tmap(lambda o: o[0, 0], obs), None))
        params, stats = split_batch_stats(variables)
        got = forward_prediction(module.apply, params, None, batch,
                                 LossConfig(), batch_stats=stats)
        if stats is None:
            per_window = [module.apply(params, tmap(
                lambda o: o[b].reshape((T * p_obs,) + o.shape[3:]), obs),
                None) for b in range(B)]
            want = {k: jnp.stack([w[k] for w in per_window])
                    for k in ('policy', 'value')}
        else:
            got, new_stats = got
            want, mutated = module.apply(
                variables, tmap(lambda o: o.reshape((-1,) + o.shape[3:]), obs),
                None, train=True, mutable=['batch_stats'])
            got['batch_stats'] = ravel_pytree(new_stats)[0]
            want['batch_stats'] = ravel_pytree(mutated['batch_stats'])[0]
        for k, w in want.items():
            assert got[k].dtype == dtype, (k, got[k].dtype)
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(w).reshape(got[k].shape),
                rtol=1e-6, atol=1e-6, err_msg=k)
        assert got['policy'].shape == (B, T, p_obs, n_actions)
        assert got['value'].shape == (B, T, p_obs, 1)


@pytest.mark.parametrize('shape', [(3, 5, 1), (2, 4, 4, 17, 7, 11),
                                   (4, 2, 2, 6)])
def test_fold_then_unfold_is_the_identity(shape):
    B, T, P = shape[:3]
    x = jnp.arange(int(np.prod(shape))).reshape(shape)
    folded = _fold_bt(x)
    assert folded.shape == (T * B * P,) + shape[3:]
    # time-major: the first B*P rows are ply 0 of every window
    np.testing.assert_array_equal(
        np.asarray(folded[:B * P]),
        np.asarray(x[:, 0].reshape((B * P,) + shape[3:])))
    np.testing.assert_array_equal(np.asarray(_unfold_bt(folded, B, T, P)),
                                  np.asarray(x))


@pytest.mark.parametrize('burn_in', [0, 2])
def test_rnn_scan_recomputes_plies_without_changing_a_gradient(burn_in,
                                                               monkeypatch):
    """The recurrent scan's body runs under ``jax.checkpoint``; with it
    taken away (plain autodiff, every residual stacked) the turn-based
    window's loss and gradients are the same numbers."""
    module, params, hidden, batch, args = _rnn_setup(burn_in=burn_in, fs=3)
    cfg = LossConfig.from_args(args)

    def total_and_grads():
        return jax.value_and_grad(lambda p: compute_loss(
            module.apply, p, hidden, batch, cfg)[0])(params)
    loss, grads = total_and_grads()
    monkeypatch.setattr(jax, 'checkpoint', lambda f, **kw: f)
    loss_ref, grads_ref = total_and_grads()
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-6)
    g, g_ref = ravel_pytree(grads)[0], ravel_pytree(grads_ref)[0]
    assert float(jnp.abs(g_ref).max()) > 0
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(g_ref).max()))
