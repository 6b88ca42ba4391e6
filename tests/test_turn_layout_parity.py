"""The compact 'turn' window layout is TRAINING-EQUIVALENT to the wide
observation=True layout for turn-based envs that record only the acting
seat (every env: ``observers()`` defaults empty, as in the reference —
reference environment.py:84).

This is the proof obligation behind train.py's ingest gate admitting
observation=True configs to the device 'turn' windower: the same window,
expressed in both layouts, must produce the SAME loss and the SAME
gradients when the loss runs with the matching LossConfig.observation
flag. The wide layout runs the net on zero observations for non-acting
seats and masks the outputs; the compact layout skips them; per-player
recurrent hidden advances identically in both (omask-gated carry).

Scope: the identity holds for PER-SAMPLE models (GroupNorm/LayerNorm —
each row's output depends only on that row). With batch-statistics
normalization (models/blocks.py BatchStatsNorm, GeisterNet's
norm_kind='batch' investigation setting) the layouts intentionally
differ: the wide layout's statistics include the zeroed non-acting-seat
rows (as the torch reference's train-mode BatchNorm did) while the
compact layout's do not (window-tail pad rows still enter both). The
last test pins that difference so it stays a documented choice, not an
accident."""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from handyrl_tpu.environment import make_env
from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.models.geister import GeisterNet
from handyrl_tpu.generation import BatchedGenerator
from handyrl_tpu.ops.batch import make_batch, select_episode
from handyrl_tpu.ops.losses import LossConfig, compute_loss

ENV_ARGS = {'env': 'Geister'}


def _args(observation, burn_in=2):
    return {
        'turn_based_training': True, 'observation': observation,
        'gamma': 0.9, 'forward_steps': 8, 'burn_in_steps': burn_in,
        'compress_steps': 4, 'maximum_episodes': 100,
        'lambda': 0.7, 'policy_target': 'TD', 'value_target': 'TD',
        'entropy_regularization': 0.1, 'entropy_regularization_decay': 0.1,
    }


def _wide_to_compact(batch):
    """Project an observation=True (B, T, P, ...) batch onto the compact
    turn layout (data leaves P axis 1, masks/values still span P) by
    selecting the acting seat's lane — the inverse of what the wide
    layout's zero-padding adds."""
    seat = jnp.argmax(batch['turn_mask'][..., 0], axis=-1)       # (B, T)

    def take(x, pad):
        # (B, T, P, ...) -> (B, T, 1, ...): acting seat's entry where one
        # exists, the layout's pad value on tail plies (no seat acted)
        sel = seat.reshape(seat.shape + (1,) * (x.ndim - 2))
        idx = jnp.broadcast_to(sel, x.shape[:2] + (1,) + x.shape[3:])
        got = jnp.take_along_axis(x, idx, axis=2)
        any_turn = jnp.any(batch['turn_mask'][..., 0] > 0, axis=-1)
        m = any_turn.reshape(any_turn.shape + (1,) * (x.ndim - 2))
        return jnp.where(m, got, pad)

    out = dict(batch)
    out['observation'] = jax.tree_util.tree_map(
        lambda x: take(x, 0.0), batch['observation'])
    out['selected_prob'] = take(batch['selected_prob'], 1.0)
    out['action'] = take(batch['action'], 0)
    out['action_mask'] = take(batch['action_mask'], 1e32)
    return out


@pytest.fixture(scope='module')
def wide_batch_and_params():
    random.seed(11)
    env = make_env(ENV_ARGS)
    env.reset()
    # norm_kind='group': the layout identity is a per-sample-model theorem
    # (see module docstring); batch-stats norm is covered separately below
    wrapper = ModelWrapper(GeisterNet(filters=8, drc_layers=2,
                                      drc_repeats=1, norm_kind='group'))
    wrapper.ensure_params(env.observation(0))
    gen = BatchedGenerator(lambda i: make_env(ENV_ARGS), wrapper,
                           _args(True), n_envs=4)
    episodes = []
    for _ in range(400):
        episodes += gen.step()
        if len(episodes) >= 4:
            break
    assert len(episodes) >= 4
    args = _args(True)
    windows = [select_episode(episodes, args) for _ in range(4)]
    return wrapper, make_batch(windows, args)


def _loss_and_grads(wrapper, batch, cfg):
    def init_hidden():
        B = batch['value'].shape[0]
        P = batch['value'].shape[2]
        return wrapper.module.init_hidden((B, P))

    def f(params):
        loss, aux = compute_loss(wrapper.module.apply, params,
                                 init_hidden(), batch, cfg)
        return loss, aux
    (loss, aux), grads = jax.value_and_grad(f, has_aux=True)(wrapper.params)
    return loss, aux, grads


def test_wide_and_compact_layouts_train_identically(wide_batch_and_params):
    wrapper, wide = wide_batch_and_params
    compact = _wide_to_compact(wide)
    # the compact layout really is compact: data leaves have P axis 1
    assert compact['action'].shape[2] == 1
    assert wide['action'].shape[2] == 2

    loss_w, aux_w, grads_w = _loss_and_grads(
        wrapper, wide, LossConfig.from_args(_args(True)))
    loss_c, aux_c, grads_c = _loss_and_grads(
        wrapper, compact, LossConfig.from_args(_args(False)))

    np.testing.assert_allclose(float(loss_w), float(loss_c),
                               rtol=1e-5, atol=1e-6)
    for k in aux_w['losses']:
        np.testing.assert_allclose(
            float(aux_w['losses'][k]), float(aux_c['losses'][k]),
            rtol=1e-5, atol=1e-6, err_msg=k)
    # gradient criterion is RELATIVE to each leaf's own scale: a fixed
    # absolute band is wrong in both directions — float32 grads of scale ~5
    # legitimately differ by a few e-6 between the two scan splits, while a
    # tiny-scale leaf could hide a real bug under the same band
    flat_w = jax.tree_util.tree_leaves(grads_w)
    flat_c = jax.tree_util.tree_leaves(grads_c)
    for gw, gc in zip(flat_w, flat_c):
        gw, gc = np.asarray(gw), np.asarray(gc)
        err = float(np.abs(gw - gc).max())
        scale = float(np.abs(gw).max())
        rel = err / max(scale, 1e-6)
        assert rel < 1e-4, \
            'gradient leaf mismatch: max|dw|=%.3g at scale %.3g (rel %.3g)' \
            % (err, scale, rel)


def test_wide_and_compact_no_burn_in(wide_batch_and_params):
    """Same equivalence with burn_in 0 (different scan split)."""
    wrapper, wide = wide_batch_and_params
    compact = _wide_to_compact(wide)
    cfg_w = LossConfig.from_args(_args(True, burn_in=0))
    cfg_c = LossConfig.from_args(_args(False, burn_in=0))
    loss_w, _, _ = _loss_and_grads(wrapper, wide, cfg_w)
    loss_c, _, _ = _loss_and_grads(wrapper, compact, cfg_c)
    np.testing.assert_allclose(float(loss_w), float(loss_c),
                               rtol=1e-5, atol=1e-6)


def test_batch_stats_norm_layouts_differ_by_design(wide_batch_and_params):
    """With BatchStatsNorm (norm_kind='batch') the compact layout's
    statistics exclude the wide layout's zero rows — the losses MUST
    differ; if this ever starts passing with equality, the norm silently
    stopped using batch statistics."""
    _, wide = wide_batch_and_params
    env = make_env(ENV_ARGS)
    env.reset()
    wrapper = ModelWrapper(GeisterNet(filters=8, drc_layers=2,
                                      drc_repeats=1, norm_kind='batch'))
    wrapper.ensure_params(env.observation(0))
    compact = _wide_to_compact(wide)
    loss_w, _, _ = _loss_and_grads(
        wrapper, wide, LossConfig.from_args(_args(True)))
    loss_c, _, _ = _loss_and_grads(
        wrapper, compact, LossConfig.from_args(_args(False)))
    assert np.isfinite(float(loss_w)) and np.isfinite(float(loss_c))
    assert abs(float(loss_w) - float(loss_c)) > 1e-6


def test_norm_kind_env_args_plumbing():
    """env_args {'norm_kind': 'batch'} reaches GeisterNet without a source
    edit (the BENCHMARKS round-5 A/B path)."""
    env = make_env({'env': 'Geister', 'norm_kind': 'batch'})
    assert env.net().norm_kind == 'batch'
    assert make_env(ENV_ARGS).net().norm_kind == 'group'
