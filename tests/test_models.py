"""Model zoo tests: output shapes, hidden-state carry, snapshot round-trip,
and the protocol the learner reads off a trunk net (``models/__init__.py``)."""

import functools
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.model import ModelWrapper, RandomModel
from handyrl_tpu.models import build, trunk
from handyrl_tpu.envs.tictactoe import Environment as TicTacToe


def test_simple_conv2d_shapes():
    env = TicTacToe()
    wrapper = ModelWrapper(env.net())
    obs = env.observation(0)
    out = wrapper.inference(obs)
    assert out['policy'].shape == (9,)
    assert out['value'].shape == (1,)
    assert -1.0 <= float(out['value'][0]) <= 1.0
    assert 'hidden' not in out


def test_batch_inference_matches_single():
    env = TicTacToe()
    wrapper = ModelWrapper(env.net())
    obs = env.observation(0)
    single = wrapper.inference(obs)
    batched = wrapper.batch_inference(np.stack([obs, obs]))
    # B=1 and B=2 are different XLA programs; allow cross-compile numeric drift
    np.testing.assert_allclose(np.asarray(batched['policy'])[0], single['policy'], atol=1e-2)
    np.testing.assert_allclose(np.asarray(batched['policy'])[0],
                               np.asarray(batched['policy'])[1], atol=1e-6)


def test_geister_net_hidden_carry():
    net = build('GeisterNet')
    wrapper = ModelWrapper(net)
    rng = np.random.RandomState(0)
    obs = {'scalar': rng.rand(18).astype(np.float32),
           'board': rng.rand(7, 6, 6).astype(np.float32)}
    hidden = wrapper.init_hidden()
    out = wrapper.inference(obs, hidden)
    assert out['policy'].shape == (4 * 36 + 70,)
    assert out['value'].shape == (1,)
    assert out['return'].shape == (1,)
    hs, cs = out['hidden']
    assert len(hs) == 3 and hs[0].shape == (6, 6, 32)
    # state must evolve under repeated observation
    out2 = wrapper.inference(obs, out['hidden'])
    assert not np.allclose(hs[0], out2['hidden'][0][0])


def test_geese_net_shapes():
    net = build('GeeseNet')
    wrapper = ModelWrapper(net)
    obs = np.zeros((17, 7, 11), np.float32)
    obs[0, 3, 5] = 1.0  # own head
    out = wrapper.inference(obs)
    assert out['policy'].shape == (4,)
    assert out['value'].shape == (1,)


def test_snapshot_roundtrip():
    env = TicTacToe()
    obs = env.observation(0)
    w1 = ModelWrapper(env.net(), seed=7)
    p1 = w1.inference(obs)['policy']
    snap = w1.snapshot()
    assert snap['architecture'] == 'SimpleConv2dModel'
    w2 = ModelWrapper.from_snapshot(snap, obs)
    np.testing.assert_allclose(w2.inference(obs)['policy'], p1, atol=1e-6)


def test_random_model_zero_outputs():
    env = TicTacToe()
    wrapper = ModelWrapper(env.net())
    rm = RandomModel(wrapper, env.observation(0))
    out = rm.inference()
    assert np.all(out['policy'] == 0) and out['policy'].shape == (9,)
    assert np.all(out['value'] == 0)


# -- the four trunks hold to the protocol models/__init__.py states -------------
# each net at a tiny size through its own fields (its own test file's):
# windows and circles of 16 rows, so that 40 plies go round them twice; a
# decode ply's 3 rows take every held expert, a window's 120 the grouped path
TRUNKS = {
    'EvaByteNet': dict(hidden_size=64, layers=2, heads_held=2, head_dim=16,
                       mlp_size=96, vocab=72, chunk_size=4, window_size=16,
                       max_positions=64, query_block=8),
    'TrinityNet': dict(hidden_size=64,
                       layer_types=('sliding', 'sliding', 'full'),
                       dense_layers=1, heads_held=4, kv_heads_held=1,
                       head_dim=16, mlp_size=96, expert_size=32,
                       experts_published=16, experts_held=(0, 1, 2, 3),
                       experts_per_token=4, vocab=72, window_size=16,
                       max_positions=64, query_block=8, dense_rows=4,
                       param_scale=4.0),
    'SmallThinkerNet': dict(hidden_size=64,
                            layer_types=('global', 'window', 'window'),
                            heads_held=7, kv_heads_held=1, head_dim=16,
                            expert_size=32, experts_published=16,
                            experts_held=(0, 1, 2, 3), experts_per_token=3,
                            vocab=72, window_size=16, max_positions=64,
                            query_block=8, dense_rows=4, param_scale=4.0),
    'OuroNet': dict(hidden_size=64, layers=2, heads_held=2, kv_heads_held=2,
                    head_dim=16, mlp_size=96, vocab=72, passes=4,
                    max_positions=48, query_block=8, param_scale=4.0),
}
# name -> (parameters, the trunks that have it, as models/__init__.py lists
# them)
EVERY = frozenset(TRUNKS)
EXPERT = frozenset({'TrinityNet', 'SmallThinkerNet'})
PROTOCOL = {
    'init_hidden': (['batch_shape'], EVERY),
    'reset_hidden': (['hidden', 'done'], EVERY),
    'sequence': (['ids', 'first_position', 'valid', 'no_grad_prefix'], EVERY),
    'policy_logits': (['features'], EXPERT | {'OuroNet'}),
    'post_update': (['before', 'after', 'aux'], EXPERT),
    # a net whose blocks of queries take a span of the keys: the expert
    # trunks' window layers, every layer of ``evabyte``; ``ouro``'s layers
    # see everything
    'attention_key_share': (['T'], EXPERT | {'EvaByteNet'}),
    'epoch_dynamics': (['sums'], EXPERT | {'OuroNet'}),
}
PLIES = 40


@functools.lru_cache(maxsize=None)
def _trunk(name):
    net = build(name, dtype=jnp.float32, **TRUNKS[name])
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    # weights large enough that every term matters, the vectors (norms,
    # biases, mu and phi) away from their initial 0 or 1
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    return net, jax.tree_util.tree_map(
        lambda x: x * 4 if x.ndim >= 2
        else x + 0.3 * jax.random.normal(next(keys), x.shape), variables)


def _trunk_ids(seed, n=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, PLIES), 0, 72)


@pytest.mark.parametrize('name', sorted(TRUNKS))
def test_a_trunk_defines_the_protocol_as_documented(name):
    net, _variables = _trunk(name)
    for method, (parameters, trunks) in PROTOCOL.items():
        assert hasattr(net, method) == (name in trunks), method
        if hasattr(net, method):
            got = list(inspect.signature(getattr(net, method)).parameters)
            assert got == parameters, (method, got)
    assert list(inspect.signature(net.__call__).parameters) == [
        'obs', 'hidden', 'train']
    assert net.actor_param_dtype == net.dtype
    assert inspect.signature(net.sequence).parameters[
        'no_grad_prefix'].default == 0
    if hasattr(net, 'attention_key_share'):
        assert 0 < net.attention_key_share(PLIES) <= 1
    if hasattr(net, 'epoch_dynamics'):
        assert net.epoch_dynamics({}) == {}


@pytest.mark.parametrize('name', sorted(TRUNKS))
def test_a_trunks_cache_goes_round_init_call_and_reset(name):
    """``init_hidden`` makes what ``__call__`` takes and hands back, one
    position on; ``reset_hidden`` sets a finished sequence's counter to 0
    and touches nothing else, so the buffers are the arrays they were."""
    net, variables = _trunk(name)
    ids = _trunk_ids(2, n=2)
    hidden = net.init_hidden((2,))
    assert sorted(hidden) == ['k', 'pos', 'v']
    assert hidden['pos'].shape == (2,) and hidden['pos'].dtype == jnp.int32
    assert all(k.dtype == net.dtype and k.shape[0] == 2 and k.ndim == 3
               for k in hidden['k'] + hidden['v'])
    shapes = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), hidden)
    step = jax.jit(net.apply)
    for t in range(5):
        out = step(variables, ids[:, t], hidden)
        assert {'policy', 'value', 'hidden'} <= set(out)
        hidden = out['hidden']
        assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                      hidden) == shapes
    assert out['policy'].shape == (2, 72) and out['value'].shape == (2, 1)
    assert hidden['pos'].tolist() == [5, 5]
    reset = net.reset_hidden(hidden, jnp.asarray([True, False]))
    assert reset['pos'].tolist() == [0, 5]
    for key in ('k', 'v'):
        assert all(a is b for a, b in zip(reset[key], hidden[key]))
    # with no cache handed over the net starts from an empty one
    np.testing.assert_array_equal(
        net.apply(variables, ids[:, 0], None)['policy'],
        net.apply(variables, ids[:, 0], net.init_hidden((2,)))['policy'])


@pytest.mark.parametrize('name', sorted(TRUNKS))
def test_a_trunks_sequence_and_its_steps_agree(name):
    """``sequence`` over 40 positions (2.5 windows or circles of 16) and 40
    calls of ``__call__`` through the cache give the same policy and value
    at every position, read as ``ops/losses.py`` reads them: the head over
    ``policy_features`` where the net returns those, the LAST pass where it
    returns a pass axis (the pass the actor plays from)."""
    net, variables = _trunk(name)
    ids = _trunk_ids(3)

    def window(v, i):
        out = net.apply(v, i, jnp.zeros((3,), jnp.int32),
                        jnp.ones((3, PLIES), bool), method=net.sequence)
        if 'policy' not in out:
            out['policy'] = net.apply(v, out['policy_features'],
                                      method=net.policy_logits)
        if 'exit_gate' in out:
            out = {k: x[-1] for k, x in out.items() if k != 'aux'}
        return out['policy'], out['value']
    policy, value = jax.jit(window)(variables, ids)
    assert policy.shape == (3, PLIES, 72) and value.shape == (3, PLIES, 1)
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    for t in range(PLIES):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        np.testing.assert_allclose(out['policy'], policy[:, t], atol=3e-4)
        np.testing.assert_allclose(out['value'], value[:, t], atol=3e-4)
    assert hidden['pos'].tolist() == [PLIES] * 3


@pytest.mark.parametrize('unit_offset', [False, True],
                         ids=['weight', 'unit_offset'])
def test_the_norm_has_the_two_published_forms(unit_offset):
    """``x / rms(x)`` times the weight, or times one plus the weight; the
    form is chosen in Python, so the first adds nothing in the program."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 16)).astype(np.float32) * 3
    g = rng.normal(size=(16,)).astype(np.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * (
        1 + g if unit_offset else g)
    norm = functools.partial(trunk.rms_norm, eps=1e-5, dtype=jnp.float32,
                             unit_offset=unit_offset)
    np.testing.assert_allclose(norm(x, g), want, rtol=1e-5, atol=1e-6)
    text = jax.jit(norm).lower(x, g).as_text()
    adds = re.findall(r'stablehlo\.add [^\n]*: tensor<16xf32>', text)
    assert len(adds) == int(unit_offset)       # an add over the weight
