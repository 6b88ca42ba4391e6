"""SmallThinker as a policy trunk (models/smallthinker.py) and the layers it
shares with Trinity (models/experts.py, models/attention.py), at small widths
on the CPU with seeded weights: each kind of layer and the whole ``sequence``
against the plain reference, ``__call__`` through the two-length cache
against ``sequence`` over a game of 2.5 windows with a group of SEVEN query
heads on a KV head, lanes reset at different counters, the four expert
shares and the four head shares against the uncut layer, the routing's
dependence on ``N_in(h)`` alone (and Trinity's on attention), the router's
zero gradient and ``post_update``, all rows on one held expert, the shared
functions on their own, and the step check's two controls of this
architecture at the rehearsal's size."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import smallthinker as reference           # noqa: E402
from benchmark.reference import smallthinker_loss as reference_loss  # noqa: E402
from handyrl_tpu.models import experts                               # noqa: E402
from handyrl_tpu.models.smallthinker import (PUBLISHED_LAYERS,       # noqa: E402
                                             SmallThinkerNet)

WIDTHS = dict(hidden_size=64, layer_types=('global', 'window', 'window'),
              heads_held=7, kv_heads_held=1, head_dim=16, expert_size=32,
              experts_published=16, experts_held=(0, 1, 2, 3),
              experts_per_token=3, vocab=72, window_size=16, max_positions=64,
              query_block=8, dense_rows=4, param_scale=4.0)
T = 40     # 2.5 attention windows


def _cfg(net):
    return dict(head_dim=net.head_dim, window_size=net.window_size,
                rope_theta=net.rope_theta, norm_eps=net.norm_eps,
                experts_per_token=net.experts_per_token,
                layer_types=net.layer_types, experts_held=net.held,
                param_scale=net.param_scale)


@functools.lru_cache(maxsize=None)
def _net_and_variables(dtype='float32', **over):
    net = SmallThinkerNet(dtype=jnp.dtype(dtype),
                          **dict(WIDTHS, **dict(over)))
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    # seeded weights large enough that every term matters, the norms'
    # weights away from 1
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    return net, jax.tree_util.tree_map(
        lambda x: 1.0 + 0.3 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x * 4, variables)


def _ids(n, seed=0, length=T):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, WIDTHS['vocab'], (n, length)), jnp.int32)


def _sequence(net, variables, ids, first, valid):
    """(logits (B, T, A), value (B, T), aux, the router's choices)."""
    def run(v, i, f, m):
        out, state = net.apply(v, i, f, m, method=net.sequence,
                               mutable=['intermediates'])
        logits = net.apply(v, out['policy_features'],
                           method=net.policy_logits)
        routes = jnp.stack([
            state['intermediates']['layer_%d' % n]['route_ids'][0]
            for n in net.expert_layers])
        return logits, out['value'][..., 0], out['aux'], routes
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
    cut = SmallThinkerNet(layer_types=PUBLISHED_LAYERS[:4], heads_held=7,
                          kv_heads_held=1, experts_held=tuple(range(16)),
                          vocab=37984)
    assert count(cut) == 593617920
    # the defaults are the published counts: 21.5B parameters
    assert len(PUBLISHED_LAYERS) == 52
    assert 21.4e9 < count(SmallThinkerNet()) < 21.6e9


def test_the_key_share_is_by_hand_at_the_cells_shapes():
    """What the learner's gauge ``attention_key_share`` reads for the cell's
    cut at its 8,192-position windows: three window layers at 4,224 of 8,192
    keys a block of 128, the global layer every key."""
    cut = SmallThinkerNet(layer_types=PUBLISHED_LAYERS[:4], heads_held=7,
                          kv_heads_held=1, experts_held=tuple(range(16)),
                          vocab=37984)
    assert PUBLISHED_LAYERS[:4] == ('global', 'window', 'window', 'window')
    assert cut.attention_key_share(8192) == (
        3 * 4224 / 8192 + 1) / 4 == 0.63671875
    # a window no longer than the attention's window and a block: every key
    assert cut.attention_key_share(4096) == 1.0
    # five blocks of 8 under a window of 16: 24 of 40 keys a block
    assert SmallThinkerNet(**WIDTHS).attention_key_share(T) == pytest.approx(
        (2 * 24 / 40 + 1) / 3)


# -- each kind of layer, and the whole net, against the reference ---------------
@pytest.mark.parametrize('kind', ['global', 'window'])
def test_one_layer_matches_the_plain_reference(kind):
    net, variables = _net_and_variables(layer_types=(kind,))
    ids = _ids(2, 3)
    first = jnp.asarray([0, 7], jnp.int32)
    valid = jnp.arange(T)[None, :] < jnp.asarray([T, 29])[:, None]
    logits, value, _aux, _routes = _sequence(net, variables, ids, first,
                                             valid)
    for b in range(2):
        want = _plain(net, variables, ids[b], first[b], valid[b])
        keep = np.asarray(valid[b])
        np.testing.assert_allclose(np.asarray(logits[b])[keep],
                                   np.asarray(want['logits'])[keep],
                                   atol=3e-4)
        np.testing.assert_allclose(np.asarray(value[b])[keep],
                                   np.asarray(want['value'])[keep], atol=1e-4)


@pytest.mark.parametrize('first,length', [(0, T), (5, T), (13, 33), (24, T)])
def test_sequence_matches_the_plain_reference(first, length):
    net, variables = _net_and_variables()
    ids = _ids(1, first + length)
    valid = (jnp.arange(T) < length)[None]
    logits, value, aux, routes = _sequence(
        net, variables, ids, jnp.asarray([first], jnp.int32), valid)
    want = _plain(net, variables, ids[0], jnp.int32(first), valid[0])
    np.testing.assert_allclose(logits[0, :length], want['logits'][:length],
                               atol=5e-4)
    np.testing.assert_allclose(value[0, :length], want['value'][:length],
                               atol=2e-4)
    # the sums of the forward pass are the reference's choices, counted
    np.testing.assert_array_equal(np.sort(routes[:, :length], axis=-1),
                                  np.sort(want['routes'][:, :length], axis=-1))
    counts = reference_loss.expert_counts(want['routes'], 16)
    np.testing.assert_array_equal(aux['moe_counts'], counts)
    held = counts[:, :4]
    assert float(aux['moe_rows_held']) == held.sum()
    assert float(aux['moe_rows_routed']) == T * 3 * 3
    assert float(aux['moe_rows_fullest']) == held.max()
    assert float(aux['moe_rows_dropped']) == 0
    # the positions a window layer hides a key at: from the 16th of the
    # window on, of the valid ones
    assert float(aux['window_positions_valid']) == length
    assert float(aux['window_positions_hidden']) == length - 16


@pytest.mark.parametrize('control', [
    'skip_layer', 'use_experts', 'use_window', 'rotary_on_global',
    'route_after_attention', 'silu_experts'])
def test_the_reference_controls_differ_from_the_model(control):
    net, variables = _net_and_variables()
    ids = _ids(1, 4)
    valid = jnp.ones((1, T), bool)
    logits, _value, _aux, routes = _sequence(
        net, variables, ids, jnp.zeros((1,), jnp.int32), valid)
    args = {'skip_layer': {'skip_layer': 1},
            'use_experts': {'use_experts': False},
            'use_window': {'use_window': False}}.get(control,
                                                     {control: True})
    want = _plain(net, variables, ids[0], jnp.int32(0), valid[0], **args)
    assert float(jnp.abs(logits[0] - want['logits']).max()) > 1e-2
    if control == 'route_after_attention':
        # another architecture's order chooses other experts
        same = (np.sort(routes, axis=-1)
                == np.sort(want['routes'], axis=-1)).all(axis=-1)
        assert same.mean() < 0.8


# -- one position through the two-length cache ------------------------------------
def test_decode_through_the_cache_matches_sequence():
    """A game of 40 plies over circles of 16 rows, seven query heads on the
    one KV head: the circles go round twice and the global layer's buffer
    outgrows them; in the cell's compute dtype (in float32, with the other
    trunks: tests/test_models.py
    ``test_a_trunks_sequence_and_its_steps_agree``)."""
    dtype, atol = 'bfloat16', 0.15
    net, variables = _net_and_variables(dtype)
    assert net.heads_held // net.kv_heads_held == 7
    ids = _ids(3, 5)
    logits, value, _aux, _routes = _sequence(
        net, variables, ids, jnp.zeros((3,), jnp.int32), jnp.ones((3, T), bool))
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    assert [k.shape[-2:] for k in hidden['k']] == [(64, 16), (16, 16),
                                                   (16, 16)]
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        np.testing.assert_allclose(out['policy'], logits[:, t], atol=atol)
        np.testing.assert_allclose(out['value'][:, 0], value[:, t], atol=atol)


def test_the_kernels_form_decodes_what_sequence_computes(kernel_form):
    """The same game through the cache as ``cache_attention`` chooses on a
    TPU (the block kernel interpreted here, a row of 128 lanes: the one KV
    head at the cell's head size, its SEVEN query heads the rows of the
    kernel's matrix of eight): a circle is ONE block, the global layer's
    buffer four, and the counters pass every block boundary and go round
    the circles twice."""
    net, variables = _net_and_variables(head_dim=128)
    ids = _ids(3, 5)
    logits, value, _aux, _routes = _sequence(
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
    ('a_full_circle_beside_a_half_read_global_layer', [40, 63],
     2 * (16 + 16) + 48 + 64),
    ('the_global_layers_last_row', [63], 16 + 16 + 64)])
def test_the_net_counts_the_rows_its_plies_read(kernel_form, case, pos, read):
    """``decode_rows``: each layer hands ONE span, so where the kernel runs a
    ply reads whole blocks up to each counter's own: a circle of one block
    whole whatever the counter (read == held there) and, of the global
    layer's buffer, less than it holds until the counter reaches its last
    block; every row where the products run (the narrow nets' head size of
    16 is no whole lane; the CPU)."""
    net, _ = _net_and_variables(head_dim=128)
    held = len(pos) * (64 + 16 + 16)
    assert net.decode_rows(np.asarray(pos)) == (read, held)
    assert read % 16 == 0 and (read < held) == (case != 'the_global_layers_'
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
    want, _value, _aux, _routes = _sequence(
        net, variables, new, jnp.zeros((3,), jnp.int32),
        jnp.ones((3, T), bool))
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_the_byte_game_builds_the_net_and_its_rollout_matches_sequence():
    """``env_args.net_name`` / ``env_args.net`` through the game's own
    ``net()``, and the program's rollout scan over games that end inside the
    chunk, the record's mask as bits."""
    from handyrl_tpu.device_generation import make_gen_body
    from handyrl_tpu.environment import make_env, make_jax_env
    env_args = {'env': 'ByteGame', 'min_steps': 18, 'max_steps': 30,
                'ids': 4608, 'first_ply_ids': 64,
                'net_name': 'SmallThinkerNet',
                'net': dict(WIDTHS, vocab=4608,
                            layer_types=list(WIDTHS['layer_types']),
                            experts_held=list(WIDTHS['experts_held']))}
    built = make_env(env_args).net()
    assert isinstance(built, SmallThinkerNet)
    assert built.held == (0, 1, 2, 3) and built.vocab == 4608
    net, variables = _net_and_variables(vocab=4608)
    twin = make_jax_env(env_args)
    assert twin.MASK_AS_BITS
    rollout = make_gen_body(twin, net.apply, True, True)
    _state, _hidden, _rng, rec = jax.jit(
        lambda p, s, h, r: rollout(p, s, h, r, 60))(
        variables, twin.init_state(2, 3), net.init_hidden((2, 2)),
        jax.random.PRNGKey(0))
    done = np.asarray(rec['done'])
    assert done.sum() >= 3
    for lane in range(2):
        ends = [0] + list(np.flatnonzero(done[:, lane]) + 1) + [60]
        for a, b in zip(ends, ends[1:]):
            for seat in range(2):
                ids = jnp.zeros((1, T), jnp.int32).at[0, :b - a].set(
                    rec['obs'][a:b, lane, seat])
                _logits, value, _aux, _routes = _sequence(
                    net, variables, ids, jnp.zeros((1,), jnp.int32),
                    jnp.ones((1, T), bool))
                np.testing.assert_allclose(
                    rec['value'][a:b, lane, seat, 0], value[0, :b - a],
                    atol=3e-4)


# -- the shares add up to the uncut layer -----------------------------------------
def _uncut_layer(params, x, positions, valid, cfg, kind):
    with jax.default_matmul_precision('highest'):
        return reference.layer(params, x, positions, valid, cfg, kind)[0]


def test_the_four_expert_shares_sum_to_the_uncut_layer():
    """Each share routes over all 16 experts from the same ``N_in(h)``,
    keeps the 3 a token and their soft-max, and adds what ITS four experts
    give; nothing is counted twice (there is no shared expert). The sum is
    the reference's experts' sum with every expert held."""
    whole, variables = _net_and_variables(experts_held=None)
    a32 = jax.random.normal(jax.random.PRNGKey(2), (24, 64))
    m = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    layer = variables['params']['layer_1']
    cfg = dict(_cfg(whole), experts_held=tuple(range(16)))
    with jax.default_matmul_precision('highest'):
        p = reference.values(layer, cfg)
        chosen, w = reference.route(p, a32, cfg)
        uncut = reference.experts_part(p, m, chosen, w, cfg)
    np.testing.assert_allclose(
        whole.apply(variables, 1, a32, m, method=whole.experts_part), uncut,
        atol=2e-5)
    total = 0.0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        net = SmallThinkerNet(dtype=jnp.float32,
                              **dict(WIDTHS, experts_held=held))
        part = dict(layer, **{
            key: layer[key][4 * share:4 * share + 4]
            for key in ('experts_gate', 'experts_up', 'experts_down')})
        params = {'params': dict(variables['params'], layer_1=part)}
        total = total + net.apply(params, 1, a32, m,
                                  method=net.experts_part)
    np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_the_four_head_shares_sum_to_the_uncut_attention():
    """28 query heads on 4 KV heads, a group of seven each: a share is one
    KV head with its seven query heads' columns of W_q and rows of W_o."""
    whole, variables = _net_and_variables(heads_held=28, kv_heads_held=4,
                                          head_dim=8)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, 64))
    positions = jnp.arange(T)[None, :] + jnp.asarray([[0], [5]])
    valid = jnp.ones((2, T), bool)
    for index in (0, 1):      # the global layer and a window layer
        layer = variables['params']['layer_%d' % index]
        uncut = whole.apply(variables, index, x, positions, valid,
                            method=whole.attention_part)
        cfg = dict(_cfg(whole), head_dim=8)
        for b in range(2):    # the uncut layer is the reference's
            with jax.default_matmul_precision('highest'):
                p = reference.values(layer, cfg)
                want = reference.attention_part(
                    p, reference.rms_norm(x[b], p['norm_in'], 1e-6),
                    positions[b], valid[b], cfg, whole.layer_types[index],
                    block=8)
            np.testing.assert_allclose(uncut[b], want, atol=2e-5)
        total = 0.0
        for share in range(4):
            net = SmallThinkerNet(dtype=jnp.float32, **dict(
                WIDTHS, heads_held=7, kv_heads_held=1, head_dim=8))
            q, kv = slice(56 * share, 56 * share + 56), \
                slice(8 * share, 8 * share + 8)
            part = dict(layer, wq=layer['wq'][:, q], wo=layer['wo'][q],
                        wk=layer['wk'][:, kv], wv=layer['wv'][:, kv])
            params = {'params': dict(variables['params'],
                                     **{'layer_%d' % index: part})}
            total = total + net.apply(params, index, x, positions, valid,
                                      method=net.attention_part)
        np.testing.assert_allclose(total, uncut, atol=2e-5)


# -- routed BEFORE attention ---------------------------------------------------------
def _routes_with_attention_perturbed(net, variables, layer):
    """The router's choices of ``layer`` as they are, and with that layer's
    attention weights replaced by others."""
    ids = _ids(2, 9)
    args = (ids, jnp.zeros((2,), jnp.int32), jnp.ones((2, T), bool))
    name = 'layer_%d' % layer
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    moved = dict(variables['params'][name])
    for key, leaf in zip(keys, ('wq', 'wk', 'wv', 'wo')):
        moved[leaf] = moved[leaf] + 0.3 * jax.random.normal(
            key, moved[leaf].shape)
    other = {'params': dict(variables['params'], **{name: moved})}

    def routes(v):
        _out, state = net.apply(v, *args, method=net.sequence,
                                mutable=['intermediates'])
        return np.asarray(state['intermediates'][name]['route_ids'][0])
    return routes(variables), routes(other)


def test_the_routing_of_a_block_depends_on_the_normed_input_alone():
    """Perturbing a layer's attention weights leaves THAT layer's choices
    as they were: they are taken from ``N_in(h)`` before attention is
    called. The same done to Trinity's block changes its choices: its
    router reads the stream after attention."""
    net, variables = _net_and_variables()
    for layer in (0, 1):
        before, after = _routes_with_attention_perturbed(net, variables,
                                                         layer)
        np.testing.assert_array_equal(before, after)
    import test_trinity
    trinity, its_variables = test_trinity._net_and_variables()
    before, after = _routes_with_attention_perturbed(trinity, its_variables,
                                                     1)
    assert (before != after).any()


def test_the_sort_plan_is_traced_before_attention():
    """In the block's jaxpr every operation of the routing and of the sort
    plan (the top-k, the plan's scatter: the only plain ``scatter``, the
    short buffer's way back is a ``scatter-add``) stands before the first
    operation that only attention has (the cosine of a window layer's
    phases)."""
    net, variables = _net_and_variables(layer_types=('window',))
    ids = _ids(1, 2)
    text = str(jax.make_jaxpr(lambda v: net.apply(
        v, ids, jnp.zeros((1,), jnp.int32), jnp.ones((1, T), bool),
        method=net.sequence)['policy_features'])(variables))
    assert text.count('top_k') == 1
    top_k, scatter = text.index('top_k'), text.rindex(' scatter[')
    assert top_k < scatter < text.index(' cos ')


# -- dropless under imbalance -------------------------------------------------------
def test_all_rows_on_one_held_expert_are_computed():
    """Every id's embedding leads with a large first coordinate and the
    router's first row sends it to held expert 0 and to two experts of other
    chips: in every layer one group holds all rows, three none, and nothing
    is dropped."""
    net, variables = _net_and_variables()
    params = dict(variables['params'])
    params['embed'] = params['embed'].at[:, 0].set(40.0)
    for name in ('layer_0', 'layer_1', 'layer_2'):
        router = params[name]['router'].at[0].set(0.0)
        router = router.at[0, jnp.asarray([0, 9, 12])].set(40.0)
        params[name] = dict(params[name], router=router,
                            norm_in=params[name]['norm_in'].at[0].set(1.0))
    variables = {'params': params}
    ids = _ids(1, 8)
    valid = jnp.ones((1, T), bool)
    logits, _value, aux, _routes = _sequence(
        net, variables, ids, jnp.zeros((1,), jnp.int32), valid)
    assert np.asarray(aux['moe_counts'])[:, :4].tolist() == [[T, 0, 0, 0]] * 3
    assert float(aux['moe_rows_dropped']) == 0
    assert float(aux['moe_rows_fullest']) == T
    want = _plain(net, variables, ids[0], jnp.int32(0), valid[0])
    np.testing.assert_allclose(logits[0], want['logits'], atol=5e-4)


def test_one_group_takes_every_row_and_three_none():
    """The shared functions under the worst imbalance: every pair on slot
    0, the other three groups empty, pairs of absent experts behind them."""
    n, K, held, D, F = 12, 3, 4, 8, 6
    slot = jnp.tile(jnp.asarray([[0, held, held]], jnp.int32), (n, 1))
    plan = experts.sort_plan(slot, held, 16)
    assert plan.groups.tolist() == [n, 0, 0, 0]
    assert int(plan.dropped) == 0 and int(plan.in_group.sum()) == n
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    m = jax.random.normal(keys[0], (n, D))
    w = jax.nn.softmax(jax.random.normal(keys[1], (n, K)))
    gate, up = (jax.random.normal(k, (held, D, F)) for k in keys[2:4])
    down = jax.random.normal(keys[4], (held, F, D))
    y = experts.grouped_products(experts.to_expert_order(m, plan),
                                 plan.groups, gate, up, down, jax.nn.relu,
                                 jnp.float32, 1.0)
    got = experts.weighted_sum_back(y, plan, slot, w, held)
    want = w[:, :1] * ((jax.nn.relu(m @ gate[0]) * (m @ up[0])) @ down[0])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the shared expert layer on its own ---------------------------------------------
def _old_one_call_path(m, slot, w, gate, up, down, activation, dtype, inv):
    """The grouped path as ``TrinityBlock._experts_grouped`` had it before
    it was shared: sort, gather, products and the sum back in one call."""
    f32 = jnp.float32
    n, K = slot.shape
    held, M = gate.shape[0], n * K
    flat = slot.reshape(M)
    onehot = flat[:, None] == jnp.arange(held + 1)
    rank = (jnp.cumsum(onehot, axis=0, dtype=jnp.int32)
            * onehot).sum(axis=1) - 1
    sizes = onehot.sum(axis=0, dtype=jnp.int32)
    dest = (jnp.cumsum(sizes) - sizes)[flat] + rank
    source = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32))
    rows = jnp.repeat(m, K, axis=0).at[source].get(unique_indices=True)
    groups = sizes[:held]
    in_group = (jnp.arange(M) < groups.sum())[:, None]
    rows = jnp.where(in_group, rows, 0)
    grouped = lambda x, p: jax.lax.ragged_dot(
        x, p.astype(dtype), groups, preferred_element_type=dtype) * inv
    y = grouped(activation(grouped(rows, gate)) * grouped(rows, up), down)
    y = jnp.where(in_group, y, 0)
    y = y.at[dest].get(unique_indices=True).reshape(n, K, -1)
    return jnp.einsum('nkd,nk->nd', y, (w * (slot < held)).astype(y.dtype),
                      preferred_element_type=f32)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('activation', [jax.nn.silu, jax.nn.relu],
                         ids=['silu', 'relu'])
def test_plan_then_dispatch_is_the_old_one_call_path_bit_for_bit(activation,
                                                                 dtype):
    dtype = jnp.dtype(dtype)
    n, K, held, E, D, F = 40, 3, 4, 16, 32, 24
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    m = jax.random.normal(keys[0], (n, D)).astype(dtype)
    ids = jnp.argsort(jax.random.uniform(keys[1], (n, E)), axis=1)[:, :K]
    w = jax.nn.softmax(jax.random.normal(keys[2], (n, K)))
    slot = experts.held_slot(ids, (2, 5, 7, 11), E)
    assert set(np.unique(slot)) <= set(range(held + 1))
    gate, up = (jax.random.normal(k, (held, D, F)) for k in keys[3:5])
    down = jax.random.normal(keys[5], (held, F, D))
    args = (gate, up, down, activation, dtype, 0.25)

    @jax.jit
    def in_parts(m, slot, w):
        plan = experts.sort_plan(slot, held, E)   # from the routing alone
        y = experts.grouped_products(experts.to_expert_order(m, plan),
                                     plan.groups, *args)
        return experts.weighted_sum_back(y, plan, slot, w, held), plan
    got, plan = in_parts(m, slot, w)
    want = jax.jit(lambda m, slot, w: _old_one_call_path(m, slot, w,
                                                         *args))(m, slot, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(plan.dropped) == 0
    assert int(plan.groups.sum()) == int((slot < held).sum())
    assert sorted(plan.dest.tolist()) == list(range(n * K))
    # and the every-row path gives the same sum from the same routing
    dense = experts.every_row_products(
        m, experts.every_row_gate(slot, w, held), *args)
    np.testing.assert_allclose(dense, got, rtol=3e-2 if dtype != jnp.float32
                               else 1e-4, atol=0.1 if dtype != jnp.float32
                               else 1e-4)


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
    for name in ('layer_0', 'layer_1', 'layer_2'):
        assert float(jnp.abs(grads[name]['router']).max()) == 0
        for key in ('experts_gate', 'experts_down', 'wq', 'wo', 'norm_in',
                    'norm_post'):
            assert float(jnp.abs(grads[name][key]).max()) > 0, key


def test_post_update_restores_the_router_after_adams_weight_decay():
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import (_update_core, init_train_state,
                                            make_optimizer)
    net, variables = _net_and_variables()
    batch, windows = _batch(net)
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='VTRACE', value_target='VTRACE')
    state, metrics = jax.jit(_update_core(net, cfg, make_optimizer()))(
        init_train_state(variables), batch, jnp.float32(1e-2))
    positions = [int(w['valid'].sum()) for w in windows]
    assert float(metrics['diag_moe_rows_routed']) == 2 * 32 * 3 * 3
    assert float(metrics['diag_moe_rows_dropped']) == 0
    assert float(metrics['diag_window_positions_valid']) == sum(positions)
    assert float(metrics['diag_window_positions_hidden']) == sum(
        n - 16 for n in positions)
    for name in ('layer_0', 'layer_1', 'layer_2'):
        before, after = (tree['params'][name]
                         for tree in (variables, state.params))
        np.testing.assert_array_equal(after['router'], before['router'])
        assert float(jnp.abs(after['experts_up']
                             - before['experts_up']).max()) > 5e-3
    # without the hook the zero gradient would not keep it: weight decay
    # reaches Adam as a gradient of its own
    moved = jax.tree_util.tree_map(lambda x: x + 1.0, variables)
    kept = net.post_update(variables, moved, None)['params']
    np.testing.assert_array_equal(kept['layer_1']['router'],
                                  variables['params']['layer_1']['router'])
    np.testing.assert_array_equal(kept['layer_1']['wq'],
                                  moved['params']['layer_1']['wq'])
    dynamics = net.epoch_dynamics({k: float(v) for k, v in metrics.items()})
    assert dynamics['moe_rows_dropped'] == 0
    assert 0 < dynamics['moe_rows_held_share'] < 100
    assert dynamics['window_hidden_position_share'] == pytest.approx(
        100.0 * sum(n - 16 for n in positions) / sum(positions))
    assert net.epoch_dynamics({}) == {}


# -- the step check's controls of this architecture, at the rehearsal's size ----------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    from benchmark import checks, rehearse
    from benchmark.manifest import Manifest
    dest = str(tmp_path_factory.mktemp('smallthinker_tiny'))
    rehearse.build_root(Manifest(), dest, 'smallthinker.moe_selfplay_8k')
    laid = Manifest(dest)
    config = laid.load_config('smallthinker')
    traffic = laid.load_traffic('moe_selfplay_8k')
    train_args = dict(traffic['train_args'], **config['train_args'], seed=5)
    return (config, train_args, checks.build_module(config, train_args),
            checks.starting_variables(config, train_args))


@pytest.mark.parametrize('control', ['stated', 'router_after_attention',
                                     'silu_for_relu'])
def test_the_step_check_catches_another_architectures_block(tiny, control):
    """A reference that routes from ``N_post(h)`` after attention, or gates
    its experts by SiLU, is another model: the program's gradient is not
    its gradient."""
    from benchmark import checks_smallthinker as cs
    config, train_args, module, variables = tiny
    stats = cs.step_errors(config, module, variables, 11, train_args,
                           **cs.CONTROLS.get(control, {}))
    over = [name for name in cs.STEP_LIMITS
            if stats[name] > config['tolerance']['step_' + name]]
    assert stats['router_moved_max_abs'] == 0 and stats['rows_dropped'] == 0
    if control == 'stated':
        assert not over, stats
    else:
        assert 'grad_err_rel_to_grad' in over, stats
