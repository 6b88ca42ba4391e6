"""The plain references against the program's nets at a toy width, and the
numpy V-trace against ops/targets.py. Float32 on both sides here, so the
tolerance is float32 rounding through a few layers."""

import os

import jax
import numpy as np
import pytest

from benchmark import checks
from benchmark.manifest import ROOT, Manifest
from benchmark.reference import geese_net
from benchmark.reference.vtrace import vtrace
from benchmark.session import merged_args
from handyrl_tpu.models import build


def _boards(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, 17, 7, 11)) < 0.1).astype(np.float32)


def test_geesenet_matches_the_programs_net_at_toy_width():
    module = build('GeeseNet', layers=3, filters=16)
    obs = _boards(6)
    variables = module.init(jax.random.PRNGKey(1), obs, None)
    with jax.default_matmul_precision('highest'):
        got = module.apply(variables, obs, None)
        want = geese_net.forward(variables, obs)
    np.testing.assert_allclose(got['policy'], want['policy'], atol=2e-5)
    np.testing.assert_allclose(got['value'], want['value'], atol=2e-5)


def test_geesenet_lstm_matches_through_several_plies():
    module = build('GeeseNetLSTM', filters=16, stem_layers=2)
    boards = [_boards(4, seed) for seed in range(4)]
    hidden = module.init_hidden((4,))
    variables = module.init(jax.random.PRNGKey(2), boards[0], hidden)
    h_ref = None
    with jax.default_matmul_precision('highest'):
        for obs in boards:
            got = module.apply(variables, obs, hidden)
            want = geese_net.forward_lstm(variables, obs, h_ref)
            hidden, h_ref = got['hidden'], want['hidden']
            np.testing.assert_allclose(got['policy'], want['policy'],
                                       atol=2e-5)
            np.testing.assert_allclose(got['value'], want['value'], atol=2e-5)
    for a, b in zip(hidden, h_ref):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_wrong_reference_would_be_caught():
    """Zero padding where the torus wraps changes the edge cells: the
    comparison is not blind to the convolution's shape."""
    module = build('GeeseNet', layers=1, filters=16)
    obs = _boards(4)
    variables = module.init(jax.random.PRNGKey(3), obs, None)
    kernel = variables['params']['TorusConv_0']['Conv_0']['kernel']
    x = np.moveaxis(obs, -3, -1)
    wrapped = geese_net._conv3x3(x, kernel, wrap=True)
    zeroed = geese_net._conv3x3(x, kernel, wrap=False)
    assert float(np.abs(np.asarray(wrapped) - np.asarray(zeroed)).max()) > 1e-2


@pytest.mark.parametrize('with_rewards', [True, False])
def test_numpy_vtrace_matches_ops_targets(with_rewards):
    import jax.numpy as jnp
    from handyrl_tpu.ops.targets import compute_target
    rng = np.random.default_rng(5)
    shape = (6, 9, 4, 1)
    values, returns = (rng.normal(size=shape).astype(np.float32)
                       for _ in range(2))
    rewards = (0.1 * rng.normal(size=shape)).astype(np.float32) \
        if with_rewards else None
    rhos, cs = (np.minimum(np.exp(rng.normal(size=shape)), 1).astype(
        np.float32) for _ in range(2))
    masks = (rng.random(shape) < 0.7).astype(np.float32)
    got = compute_target('VTRACE', jnp.asarray(values), jnp.asarray(returns),
                         None if rewards is None else jnp.asarray(rewards),
                         0.7, 0.99, jnp.asarray(rhos), jnp.asarray(cs),
                         jnp.asarray(masks))
    want = vtrace(values, returns, rewards, 0.7, 0.99, rhos, cs, masks)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-5)


def test_vtrace_reduces_to_the_bootstrap_when_rho_is_zero():
    shape = (2, 5, 1, 1)
    values = np.arange(10, dtype=np.float32).reshape(shape)
    returns = np.ones(shape, np.float32)
    zeros, ones = np.zeros(shape, np.float32), np.ones(shape, np.float32)
    vs, adv = vtrace(values, returns, None, 0.7, 0.9, zeros, zeros, ones)
    np.testing.assert_allclose(vs, values)       # no correction at rho = 0
    np.testing.assert_allclose(adv[:, -1], 0.9 * returns[:, -1] - values[:, -1])


def test_the_chip_side_checks_run_here_at_a_small_size():
    manifest = Manifest()
    config = dict(manifest.load_config('geese_lstm'),
                  reference_envs=2, reference_plies=2)
    seed = 2 ** 31 - 200
    train_args = merged_args(config, manifest.load_traffic('sgd_heavy'),
                             seed)['train_args']
    variables = checks.starting_variables(config, train_args)
    got = checks.forward_check(config, variables, seed, train_args)
    assert got['ok'] and got['parameters'] == 116128 and got['plies'] == 2
    assert got['observations'] == 8
    # each number compared stands beside its limit
    assert [row[0] for row in got['compared']] == [
        'parameters', 'policy_rms_rel_to_logit_rms', 'value_rms']
    assert checks.vtrace_check(config, variables, seed, train_args)['ok']


@pytest.mark.parametrize('name, control, fails', [
    ('geese_lstm', 'bf16_activations', ['value_rms']),
    ('geese', 'params_int8', ['policy_rms_rel_to_logit_rms', 'value_rms']),
    ('geese', 'params_fp8', ['policy_rms_rel_to_logit_rms', 'value_rms']),
    ('geese', 'skip_block', ['policy_rms_rel_to_logit_rms', 'value_rms']),
])
def test_a_lower_precision_fails_the_forward_limits(name, control, fails):
    """The negative controls of ``tolerance.why`` at the check's own size, on
    the CPU (where the stated precision reads far under its limits, the
    float32 products being exact): the program's side in the lower
    precision, the reference on the true weights, three seeds."""
    from benchmark import forward_tail
    manifest = Manifest()
    config = manifest.load_config(name)
    train_args = merged_args(config, manifest.load_traffic('sgd_heavy'),
                             0)['train_args']
    variables = checks.starting_variables(config, train_args)
    module = checks.build_module(config, train_args)
    side, program_variables = forward_tail.control(control, module, variables)
    stated = checks.ForwardComparison(config, module)
    lowered = checks.ForwardComparison(config, side)
    tol = config['tolerance']
    for seed in (11, 2 ** 30 + 7, 2 ** 31 - 300):
        sound = checks.forward_statistics(*stated.errors(variables, seed))
        broken = checks.forward_statistics(
            *lowered.errors(variables, seed, program_variables))
        for key in ('policy_rms_rel_to_logit_rms', 'value_rms'):
            assert sound[key] <= tol[key]
        for key in fails:
            assert broken[key] > tol[key], (key, broken[key])
