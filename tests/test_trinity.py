"""Trinity-Mini as a policy trunk (models/trinity.py) and what it forced in the
normal path, at small widths on the CPU with seeded weights: each kind of
layer and the whole ``sequence`` against the plain reference, ``__call__``
through the two-length cache against ``sequence`` over a game longer than
the window with lanes reset at different counters, the eight expert shares
and the four head shares against the uncut layer, all rows on one held
expert, the router's zero gradient, ``b`` under Adam and under
``post_update``, and the step check's planted faults at the rehearsal's
size."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import trinity_mini as reference           # noqa: E402
from benchmark.reference import trinity_mini_loss as reference_loss  # noqa: E402
from handyrl_tpu.models.trinity import TrinityNet                    # noqa: E402

WIDTHS = dict(hidden_size=64, layer_types=('sliding', 'sliding', 'full'),
              dense_layers=1, heads_held=4, kv_heads_held=1, head_dim=16,
              mlp_size=96, expert_size=32, experts_published=16,
              experts_held=(0, 1, 2, 3), experts_per_token=4, vocab=72,
              window_size=16, max_positions=64, query_block=8, dense_rows=4,
              param_scale=4.0)
T = 40     # 2.5 attention windows


def _cfg(net):
    return dict(head_dim=net.head_dim, window_size=net.window_size,
                rope_theta=net.rope_theta, norm_eps=net.norm_eps,
                route_scale=net.route_scale,
                experts_per_token=net.experts_per_token,
                layer_types=net.layer_types, experts_held=net.held,
                param_scale=net.param_scale)


@functools.lru_cache(maxsize=None)
def _net_and_variables(dtype='float32', **over):
    net = TrinityNet(dtype=jnp.dtype(dtype), **dict(WIDTHS, **dict(over)))
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    # seeded weights large enough that every term matters: the norms'
    # weights away from 1 and b away from 0
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))

    def seeded(path, x):
        name = path[-1].key
        if name == 'router_bias':
            return 0.05 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1:
            return 1.0 + 0.3 * jax.random.normal(next(keys), x.shape)
        return x * 4
    return net, jax.tree_util.tree_map_with_path(seeded, variables)


def _ids(n, seed=0, length=T):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, WIDTHS['vocab'], (n, length)), jnp.int32)


def _sequence(net, variables, ids, first, valid):
    """(logits (B, T, A), value (B, T), aux)."""
    def run(v, i, f, m):
        out = net.apply(v, i, f, m, method=net.sequence)
        logits = net.apply(v, out['policy_features'],
                           method=net.policy_logits)
        return logits, out['value'][..., 0], out.get('aux')
    return jax.jit(run)(variables, ids, first, valid)


def _plain(net, variables, ids, first, valid, **controls):
    with jax.default_matmul_precision('highest'):
        return reference.forward(variables, ids, first, valid, _cfg(net),
                                 **controls)


# -- the net the configuration states -----------------------------------------
def test_the_cut_has_the_parameter_count_the_configuration_states():
    def count(net):
        shapes = jax.eval_shape(lambda: net.init(
            jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
            net.init_hidden((1,))))
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(shapes))
    cut = TrinityNet(layer_types=('sliding',) * 4 + ('full',), dense_layers=1,
                     heads_held=8, kv_heads_held=1,
                     experts_held=tuple(range(16)), vocab=25024)
    assert count(cut) == 603240192
    # the defaults are the published counts: 26.1B parameters
    assert 26.0e9 < count(TrinityNet()) < 26.2e9


def test_the_key_share_is_by_hand_at_the_cells_shapes():
    """What the learner's gauge ``attention_key_share`` reads for the cell's
    cut at its 4,096-position windows: four sliding layers at 2,560 of 4,096
    keys a block of 512, the full layer every key."""
    cut = TrinityNet(layer_types=('sliding',) * 4 + ('full',), heads_held=8,
                     kv_heads_held=1, experts_held=tuple(range(16)),
                     vocab=25024)
    assert cut.attention_key_share(4096) == (4 * 2560 / 4096 + 1) / 5 == 0.7
    # windows of 2,048 positions: nothing to leave out
    assert cut.attention_key_share(2048) == 1.0
    # five blocks of 8 under a window of 16: 24 of 40 keys a block
    assert TrinityNet(**WIDTHS).attention_key_share(T) == pytest.approx(
        (2 * 24 / 40 + 1) / 3)


# -- each kind of layer, and the whole net, against the reference ---------------
@pytest.mark.parametrize('kinds,dense', [
    (('sliding',), 1), (('sliding',), 0), (('full',), 0)],
    ids=['dense', 'window_expert', 'full_expert'])
def test_one_layer_matches_the_plain_reference(kinds, dense):
    net, variables = _net_and_variables(layer_types=kinds, dense_layers=dense)
    ids = _ids(2, 3)
    first = jnp.asarray([0, 7], jnp.int32)
    valid = jnp.arange(T)[None, :] < jnp.asarray([T, 29])[:, None]
    logits, value, aux = _sequence(net, variables, ids, first, valid)
    for b in range(2):
        want = _plain(net, variables, ids[b], first[b], valid[b])
        keep = np.asarray(valid[b])
        np.testing.assert_allclose(np.asarray(logits[b])[keep],
                                   np.asarray(want['logits'])[keep],
                                   atol=3e-4)
        np.testing.assert_allclose(np.asarray(value[b])[keep],
                                   np.asarray(want['value'])[keep], atol=1e-4)
    assert (aux is None) == bool(dense)


@pytest.mark.parametrize('first,length', [(0, T), (5, T), (13, 33), (24, T)])
def test_sequence_matches_the_plain_reference(first, length):
    net, variables = _net_and_variables()
    ids = _ids(1, first + length)
    valid = (jnp.arange(T) < length)[None]
    logits, value, aux = _sequence(net, variables, ids,
                                   jnp.asarray([first], jnp.int32), valid)
    want = _plain(net, variables, ids[0], jnp.int32(first), valid[0])
    np.testing.assert_allclose(logits[0, :length], want['logits'][:length],
                               atol=5e-4)
    np.testing.assert_allclose(value[0, :length], want['value'][:length],
                               atol=2e-4)
    # the sums of the forward pass are the reference's choices, counted
    counts = reference_loss.expert_counts(want['routes'], 16)
    np.testing.assert_array_equal(aux['moe_counts'], counts)
    held = counts[:, :4]
    assert float(aux['moe_rows_held']) == held.sum()
    assert float(aux['moe_rows_routed']) == T * 4 * 2
    assert float(aux['moe_rows_fullest']) == held.max()
    assert float(aux['moe_rows_dropped']) == 0


@pytest.mark.parametrize('control', ['skip_layer', 'use_experts',
                                     'use_window', 'rotary_on_full'])
def test_the_reference_controls_differ_from_the_model(control):
    net, variables = _net_and_variables()
    ids = _ids(1, 4)
    valid = jnp.ones((1, T), bool)
    logits, _value, _aux = _sequence(net, variables, ids,
                                     jnp.zeros((1,), jnp.int32), valid)
    args = {'skip_layer': {'skip_layer': 1},
            'use_experts': {'use_experts': False},
            'use_window': {'use_window': False},
            'rotary_on_full': {'rotary_on_full': True}}[control]
    want = _plain(net, variables, ids[0], jnp.int32(0), valid[0], **args)
    assert float(jnp.abs(logits[0] - want['logits']).max()) > 1e-2


# -- one position through the two-length cache ------------------------------------
def test_decode_through_the_cache_matches_sequence():
    """A game of 40 plies over a circle of 16 rows: the circle goes round
    twice and the full layer outgrows it; in the cell's compute dtype (in
    float32, with the other trunks: tests/test_models.py
    ``test_a_trunks_sequence_and_its_steps_agree``)."""
    dtype, atol = 'bfloat16', 0.15
    net, variables = _net_and_variables(dtype)
    ids = _ids(3, 5)
    logits, value, _aux = _sequence(
        net, variables, ids, jnp.zeros((3,), jnp.int32), jnp.ones((3, T), bool))
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    assert [k.shape[-2:] for k in hidden['k']] == [(16, 16), (16, 16),
                                                   (64, 16)]
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        np.testing.assert_allclose(out['policy'], logits[:, t], atol=atol)
        np.testing.assert_allclose(out['value'][:, 0], value[:, t], atol=atol)


def test_the_kernels_form_decodes_what_sequence_computes(kernel_form):
    """The same game through the cache as ``cache_attention`` chooses on a
    TPU (the block kernel interpreted here, a row of 128 lanes: the one KV
    head at the cell's head size, its four query heads the rows of the
    kernel's matrix): a circle is ONE block, the full layer four, and the
    counters pass every block boundary and go round the circles twice."""
    net, variables = _net_and_variables(head_dim=128)
    ids = _ids(3, 5)
    logits, value, _aux = _sequence(
        net, variables, ids, jnp.zeros((3,), jnp.int32), jnp.ones((3, T), bool))
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        np.testing.assert_allclose(out['policy'], logits[:, t], atol=3e-4)
        np.testing.assert_allclose(out['value'][:, 0], value[:, t], atol=3e-4)
    # a call a layer each time the ply is traced
    assert kernel_form and len(kernel_form) % len(net.layer_types) == 0


@pytest.mark.parametrize('case,pos,read', [
    ('low_counters', [0, 15, 16], 3 * (16 + 16) + 16 + 16 + 32),
    ('a_full_circle_beside_a_half_read_full_layer', [40, 63],
     2 * (16 + 16) + 48 + 64),
    ('the_full_layers_last_row', [63], 16 + 16 + 64)])
def test_the_net_counts_the_rows_its_plies_read(kernel_form, case, pos, read):
    """``decode_rows``: each layer hands ONE span, so where the kernel runs a
    ply reads whole blocks up to each counter's own: a circle of one block
    whole whatever the counter (read == held there) and, of the full layer,
    less than it holds until the counter reaches its last block; every row
    where the products run (the narrow nets' head size of 16 is no whole
    lane; the CPU)."""
    net, _ = _net_and_variables(head_dim=128)
    held = len(pos) * (16 + 16 + 64)
    assert net.decode_rows(np.asarray(pos)) == (read, held)
    assert read % 16 == 0 and (read < held) == (case != 'the_full_layers_'
                                                        'last_row')
    narrow, _ = _net_and_variables()
    assert narrow.decode_rows(np.asarray(pos)) == (held, held)


def test_lanes_reset_at_different_counters_keep_their_buffers():
    """Three lanes, reset after 0, 9 and 21 plies: each plays its new game
    over the rows of the last one, at its own counter."""
    net, variables = _net_and_variables()
    step = jax.jit(net.apply)
    old, new = _ids(3, 6), _ids(3, 7)
    resets = [0, 9, 21]
    hidden = net.init_hidden((3,))
    got = np.zeros((3, T, WIDTHS['vocab']), np.float32)
    for t in range(21 + T):
        ids = jnp.stack([(new[n, t - r] if r <= t < r + T else old[n, t % T])
                         for n, r in enumerate(resets)])
        done = jnp.asarray([t == r and r > 0 for r in resets])
        before = hidden
        hidden = net.reset_hidden(hidden, done)
        assert all((a == b).all() for a, b in zip(before['k'], hidden['k']))
        out = step(variables, ids, hidden)
        hidden = out['hidden']
        for n, r in enumerate(resets):
            if r <= t < r + T:
                got[n, t - r] = out['policy'][n]
    want, _value, _aux = _sequence(net, variables, new,
                                   jnp.zeros((3,), jnp.int32),
                                   jnp.ones((3, T), bool))
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_rollout_chunk_across_a_games_end_matches_sequence():
    """The program's own rollout scan over games that end inside the chunk,
    at an id space wide enough that the record's mask is bits."""
    from handyrl_tpu.device_generation import make_gen_body
    from handyrl_tpu.environment import make_jax_env
    from handyrl_tpu.ops import maskbits
    net, variables = _net_and_variables(vocab=4608)
    twin = make_jax_env({'env': 'ByteGame', 'min_steps': 18, 'max_steps': 30,
                         'ids': 4608, 'first_ply_ids': 64})
    assert twin.MASK_AS_BITS
    rollout = make_gen_body(twin, net.apply, True, True)
    _state, hidden, _rng, rec = jax.jit(
        lambda p, s, h, r: rollout(p, s, h, r, 60))(
        variables, twin.init_state(2, 3), net.init_hidden((2, 2)),
        jax.random.PRNGKey(0))
    done = np.asarray(rec['done'])
    assert done.sum() >= 3
    assert rec['amask'].dtype == jnp.uint8 and rec['amask'].shape[-1] == 576
    legal = np.asarray(maskbits.as_float(rec['amask'], 4608)) == 0
    assert legal[0].sum(-1).tolist() == [[4608] * 2] * 2
    assert legal[1].sum(-1).tolist() == [[4544] * 2] * 2
    for lane in range(2):
        ends = [0] + list(np.flatnonzero(done[:, lane]) + 1) + [60]
        for a, b in zip(ends, ends[1:]):
            for seat in range(2):
                ids = jnp.zeros((1, T), jnp.int32).at[0, :b - a].set(
                    rec['obs'][a:b, lane, seat])
                _logits, value, _aux = _sequence(
                    net, variables, ids, jnp.zeros((1,), jnp.int32),
                    jnp.ones((1, T), bool))
                np.testing.assert_allclose(
                    rec['value'][a:b, lane, seat, 0], value[0, :b - a],
                    atol=3e-4)


# -- the shares add up to the uncut layer -----------------------------------------
def test_the_eight_expert_shares_sum_to_the_uncut_layer():
    """Each share routes over all 16 experts, keeps the 4 a token and their
    normalisation, and adds what ITS two experts give; the shared expert,
    which every chip computes alike, is counted once."""
    whole, variables = _net_and_variables(experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 64))
    layer = variables['params']['layer_1']
    uncut = whole.apply(variables, 1, x, method=whole.mlp_branch)
    total = 0.0
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        net = TrinityNet(dtype=jnp.float32, **dict(WIDTHS, experts_held=held))
        part = dict(layer, **{
            key: layer[key][2 * share:2 * share + 2]
            for key in ('experts_gate', 'experts_up', 'experts_down')})
        params = {'params': dict(variables['params'], layer_1=part)}
        routed = net.apply(params, 1, x, False, method=net.mlp_branch)
        total = total + routed
        if share == 0:
            total = total + net.apply(params, 1, x, method=net.mlp_branch) \
                - routed
    np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_the_four_head_shares_sum_to_the_uncut_attention():
    whole, variables = _net_and_variables(heads_held=16, kv_heads_held=4,
                                          head_dim=8)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, 64))
    positions = jnp.arange(T)[None, :] + jnp.asarray([[0], [5]])
    valid = jnp.ones((2, T), bool)
    for index in (0, 2):      # a sliding layer and the full one
        layer = variables['params']['layer_%d' % index]
        uncut = whole.apply(variables, index, x, positions, valid,
                            method=whole.attention_part)
        total = 0.0
        for share in range(4):
            net = TrinityNet(dtype=jnp.float32, **dict(
                WIDTHS, heads_held=4, kv_heads_held=1, head_dim=8))
            q, kv = slice(32 * share, 32 * share + 32), \
                slice(8 * share, 8 * share + 8)
            part = dict(layer, wq=layer['wq'][:, q], wg=layer['wg'][:, q],
                        wo=layer['wo'][q], wk=layer['wk'][:, kv],
                        wv=layer['wv'][:, kv])
            params = {'params': dict(variables['params'],
                                     **{'layer_%d' % index: part})}
            total = total + net.apply(params, index, x, positions, valid,
                                      method=net.attention_part)
        np.testing.assert_allclose(total, uncut, atol=2e-5)


# -- dropless under imbalance -------------------------------------------------------
def test_all_rows_on_one_held_expert_are_computed():
    """``b`` sends every row to held expert 0 and to three experts of other
    chips: one group holds all rows, three none, nothing is dropped."""
    net, variables = _net_and_variables()
    params = dict(variables['params'])
    for name in ('layer_1', 'layer_2'):
        bias = jnp.zeros((16,)).at[jnp.asarray([0, 9, 12, 15])].set(10.0)
        params[name] = dict(params[name], router_bias=bias)
    variables = {'params': params}
    ids = _ids(1, 8)
    valid = jnp.ones((1, T), bool)
    logits, _value, aux = _sequence(net, variables, ids,
                                    jnp.zeros((1,), jnp.int32), valid)
    assert np.asarray(aux['moe_counts'])[:, :4].tolist() == [[T, 0, 0, 0]] * 2
    assert float(aux['moe_rows_dropped']) == 0
    assert float(aux['moe_rows_fullest']) == T
    want = _plain(net, variables, ids[0], jnp.int32(0), valid[0])
    np.testing.assert_allclose(logits[0], want['logits'], atol=5e-4)


# -- what trains and what does not ----------------------------------------------------
def _batch(net, seed=5):
    from benchmark import checks_trinity_mini
    config = {'model': dict(vocab=net.vocab, max_positions=64),
              'env_args': {'first_ply_ids': 8}}
    batch, windows = checks_trinity_mini.seeded_batch(
        config, seed, {'forward_steps': 32, 'batch_size': 2})
    return jax.tree_util.tree_map(jnp.asarray, batch), windows


def test_the_router_takes_no_gradient_and_the_experts_do():
    from handyrl_tpu.ops.losses import LossConfig, compute_loss
    net, variables = _net_and_variables()
    batch, _windows = _batch(net)
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='VTRACE', value_target='VTRACE')
    sequence = lambda p, *a: net.apply(p, *a, method=net.sequence)
    policy = lambda p, f: net.apply(p, f, method=net.policy_logits)
    grads = jax.grad(lambda p: compute_loss(
        net.apply, p, None, batch, cfg, sequence_fn=sequence,
        policy_fn=policy)[0])(variables)['params']
    for name in ('layer_1', 'layer_2'):
        assert float(jnp.abs(grads[name]['router']).max()) == 0
        assert float(jnp.abs(grads[name]['router_bias']).max()) == 0
        for key in ('experts_gate', 'experts_down', 'shared_up', 'wq', 'wg'):
            assert float(jnp.abs(grads[name][key]).max()) > 0, key


def test_adam_leaves_the_bias_alone_and_post_update_moves_it_by_the_rule():
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import (_update_core, init_train_state,
                                            make_optimizer)
    net, variables = _net_and_variables()
    batch, _windows = _batch(net)
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='VTRACE', value_target='VTRACE')
    counts = np.asarray(net.apply(
        variables, batch['observation'][..., 0],
        batch['first_position'].reshape(2),
        batch['episode_mask'][..., 0, 0] > 0,
        method=net.sequence)['aux']['moe_counts'])
    state, metrics = jax.jit(_update_core(net, cfg, make_optimizer()))(
        init_train_state(variables), batch, jnp.float32(1e-2))
    assert float(metrics['diag_moe_rows_routed']) == 2 * 32 * 4 * 2
    assert float(metrics['diag_moe_rows_held']) == counts[:, :4].sum()
    for n, name in enumerate(('layer_1', 'layer_2')):
        before, after = (tree['params'][name]
                         for tree in (variables, state.params))
        # Adam's step of 1e-2 would show; the rule moves b by 1e-3 at most
        want = reference_loss.bias_after_step(before['router_bias'],
                                              counts[n], 0.001)
        np.testing.assert_allclose(after['router_bias'], want, atol=1e-7)
        assert float(jnp.abs(after['router_bias']
                             - before['router_bias']).max()) < 2.1e-3
        np.testing.assert_array_equal(after['router'], before['router'])
        assert float(jnp.abs(after['experts_up']
                             - before['experts_up']).max()) > 5e-3
    # the rule on its own, on the parameter trees
    moved = net.post_update(variables, variables,
                            {'moe_counts': jnp.asarray(counts)})
    np.testing.assert_allclose(
        moved['params']['layer_2']['router_bias'],
        reference_loss.bias_after_step(
            variables['params']['layer_2']['router_bias'], counts[1], 0.001),
        atol=1e-7)


# -- the step check's planted faults, at the rehearsal's size -------------------------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    from benchmark import checks, rehearse
    from benchmark.manifest import Manifest
    dest = str(tmp_path_factory.mktemp('trinity_tiny'))
    rehearse.build_root(Manifest(), dest, 'trinity_mini.moe_selfplay_4k')
    laid = Manifest(dest)
    config = laid.load_config('trinity_mini')
    traffic = laid.load_traffic('moe_selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'], seed=5)
    return (config, train_args, checks.build_module(config, train_args),
            checks.starting_variables(config, train_args))


@pytest.mark.parametrize('fault', ['none', 'half_the_batch',
                                   'experts_left_out'])
def test_the_step_check_catches_a_step_that_trains_less(tiny, fault,
                                                        monkeypatch):
    from benchmark import checks_trinity_mini as ct
    from handyrl_tpu.models import trinity
    from handyrl_tpu.ops import train_step
    config, train_args, module, variables = tiny
    if fault == 'half_the_batch':
        real = train_step._update_core

        def half(*args, **kw):
            update = real(*args, **kw)
            return lambda state, batch, lr: update(
                state, jax.tree_util.tree_map(lambda x: x[:1], batch), lr)
        monkeypatch.setattr(train_step, '_update_core', half)
    elif fault == 'experts_left_out':
        monkeypatch.setattr(
            trinity.TrinityBlock, '_experts_grouped',
            lambda self, m, slot, w: (jnp.zeros(m.shape, jnp.float32),
                                      jnp.int32(0)))
    stats = ct.step_errors(config, module, variables, 11, train_args)
    over = [name for name in ct.STEP_LIMITS
            if stats[name] > config['tolerance']['step_' + name]]
    if fault == 'none':
        assert not over, stats
    else:
        assert 'grad_err_rel_to_grad' in over, stats


# -- what the other nets' programs keep -------------------------------------------
@pytest.mark.parametrize('net_name', ['GeeseNet', 'EvaByteNet'])
def test_a_net_without_the_hooks_compiles_to_the_program_it_had(net_name,
                                                                monkeypatch):
    """A net that defines no ``post_update``, no ``policy_logits`` and
    returns no ``aux`` takes none of the new paths, and the ``optimizer``
    scope that now wraps Adam is operation metadata only: the update step's
    lowered text is the same with the scope taken away again. (That the five
    shipped cells' fused programs lower to the parent commit's text is held
    by hash in CHANGES.md, PR 38.)"""
    import contextlib
    from handyrl_tpu import models
    from handyrl_tpu.ops import train_step
    from handyrl_tpu.ops.losses import LossConfig
    f = jnp.float32
    if net_name == 'GeeseNet':
        net = models.build('GeeseNet')
        obs = jnp.zeros((2, 4, 1, 17, 7, 11), f)
        variables = net.init(jax.random.PRNGKey(0), obs[0, :, 0])
        cfg = LossConfig(turn_based_training=False, observation=False)
        extra, A = {}, 4
    else:
        net = models.build('EvaByteNet', hidden_size=32, layers=1,
                           heads_held=2, heads_published=2, head_dim=8,
                           mlp_size=48, chunk_size=4, window_size=8,
                           max_positions=32, query_block=4, pred_heads=2)
        obs = jnp.zeros((2, 4, 1), jnp.int32)
        variables = net.init(jax.random.PRNGKey(0), obs[0, :, 0], None)
        cfg = LossConfig(turn_based_training=False, observation=True)
        extra, A = {'first_position': jnp.zeros((2, 1, 1, 1), jnp.int32)}, 320
    for hook in ('post_update', 'policy_logits', 'epoch_dynamics'):
        assert not hasattr(net, hook)
    one = jnp.ones((2, 4, 1, 1), f)
    batch = dict(extra, observation=obs, selected_prob=one, action_mask=jnp.zeros(
        (2, 4, 1, A), f), action=jnp.zeros((2, 4, 1, 1), jnp.int32),
        value=one, reward=one, outcome=jnp.ones((2, 1, 1, 1), f),
        episode_mask=one, turn_mask=one, observation_mask=one,
        progress=jnp.ones((2, 4, 1), f))
    batch['return'] = one

    def lowered():
        update = train_step._update_core(net, cfg, train_step.make_optimizer())
        return jax.jit(update).lower(train_step.init_train_state(variables),
                                     batch, jnp.float32(1e-3))
    with_scope = lowered()
    assert 'optimizer' in with_scope.as_text(debug_info=True)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, 'named_scope',
        lambda name: contextlib.nullcontext() if name == 'optimizer'
        else real(name))
    without = lowered()
    assert 'optimizer/' not in without.as_text(debug_info=True)
    assert with_scope.as_text() == without.as_text()
