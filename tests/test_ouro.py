"""The looped trunk (models/ouro.py), its (pass, layer) cache
(models/attention.py) and the exit-weighted loss seam (ops/losses.py), at
small widths on the CPU with seeded weights: ``sequence`` against the plain
reference, every pass; ``__call__`` through the cache against the full
forward at every position, across a reset and with sequences at different
counters; the loss and ``jax.grad`` of it against the reference's; each
weight's gradient against the SUM over four untied copies; the four head
shares and the four vocabulary slices against the uncut layer and head; the
exit distribution; and the lowered programs, which hold the stack once."""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import checks_ouro                                # noqa: E402
from benchmark.reference import ouro as reference                # noqa: E402
from benchmark.reference import ouro_loss as reference_loss      # noqa: E402
from handyrl_tpu.models import attention                         # noqa: E402
from handyrl_tpu.models.ouro import OuroNet                      # noqa: E402
from handyrl_tpu.ops import losses                               # noqa: E402

WIDTHS = dict(hidden_size=64, layers=2, heads_held=2, kv_heads_held=2,
              head_dim=16, mlp_size=96, vocab=72, passes=4, max_positions=48,
              query_block=8, param_scale=4.0)
T = 40


def _cfg(net):
    return dict(layers=net.layers, passes=net.passes, head_dim=net.head_dim,
                rope_theta=net.rope_theta, norm_eps=net.norm_eps,
                param_scale=net.param_scale)


@functools.lru_cache(maxsize=None)
def _net_and_variables(dtype='float32', **over):
    net = OuroNet(dtype=jnp.dtype(dtype), **dict(WIDTHS, **dict(over)))
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    # seeded weights large enough that every term matters, the norms'
    # weights away from 1, the gate's bias away from 0
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    return net, jax.tree_util.tree_map(
        lambda x: 1.0 + 0.3 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x * 4, variables)


def _ids(seed, shape, vocab=72):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _program(net, variables, ids, first, valid):
    out = net.apply(variables, ids, first, valid, method=net.sequence)
    logits = net.apply(variables, out['policy_features'],
                       method=net.policy_logits)
    return logits, out['value'][..., 0], out['exit_gate'][..., 0]


def _reference(net, variables, ids, first, valid, **controls):
    with jax.default_matmul_precision('highest'):
        return reference.forward(variables, ids, first, valid, _cfg(net),
                                 **controls)


# -- the cut ---------------------------------------------------------------------
def test_the_cut_has_the_parameter_count_the_configuration_states():
    net = OuroNet(layers=4, heads_held=4, kv_heads_held=4, vocab=12288,
                  max_positions=4096, param_scale=256.0)
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    layer = 4 * 2048 * 512 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 38805504
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) \
        == 4 * layer + 2 * 12288 * 2048 + 3 * 2048 + 1 == 205559809
    # K and V of every (pass, layer): 32 KB a position, 4.0 GiB for 32 x 4,096
    hidden = jax.eval_shape(lambda: net.init_hidden((32,)))
    assert [k.shape for k in hidden['k']] == [(32, 4 * 4096, 512)] * 4
    a_position = sum(2 * k.shape[1] // 4096 * k.shape[2] * 2
                     for k in hidden['k'])
    assert a_position == 32 * 1024
    assert 32 * 4096 * a_position == 4 * 2 ** 30


def test_the_defaults_are_the_published_counts():
    net = OuroNet()
    assert (net.hidden_size, net.layers, net.heads_held, net.kv_heads_held,
            net.head_dim, net.mlp_size, net.vocab, net.passes,
            net.max_positions, net.rope_theta, net.norm_eps,
            net.param_scale) \
        == (2048, 48, 16, 16, 128, 5632, 49152, 4, 65536, 1e6, 1e-6, 1.0)


# -- the window's forward --------------------------------------------------------
@pytest.mark.parametrize('first,length', [(0, T), (5, T), (3, 29)])
def test_sequence_matches_the_plain_reference_in_every_pass(first, length):
    net, variables = _net_and_variables()
    ids = _ids(2, (2, T))
    valid = jnp.arange(T)[None, :] < jnp.asarray([length, T])[:, None]
    firsts = jnp.asarray([first, 0])
    logits, value, gate = _program(net, variables, ids, firsts, valid)
    assert logits.shape == (4, 2, T, 72)
    for b in range(2):
        want = _reference(net, variables, ids[b], firsts[b], valid[b])
        keep = np.asarray(valid[b])
        for t in range(4):      # every pass, not the last alone
            np.testing.assert_allclose(logits[t, b][keep],
                                       want['logits'][t][keep], atol=2e-4)
            np.testing.assert_allclose(value[t, b][keep],
                                       want['value'][t][keep], atol=2e-4)
            np.testing.assert_allclose(gate[t, b][keep],
                                       want['gate'][t][keep], atol=2e-4)
    # the passes differ: the loop is no fixed point at these weights
    assert float(jnp.abs(logits[0] - logits[3]).max()) > 0.1


@pytest.mark.parametrize('control', [
    name for name in checks_ouro.CONTROLS
    if name not in checks_ouro.STEP_ONLY])
def test_the_reference_controls_differ_from_the_model(control):
    net, variables = _net_and_variables()
    ids = _ids(3, (T,))
    valid = jnp.ones((T,), bool)
    want = _reference(net, variables, ids, 0, valid)
    got = _reference(net, variables, ids, 0, valid,
                     **checks_ouro.CONTROLS[control])
    n = got['logits'].shape[0]
    assert float(jnp.abs(got['logits'][-1] - want['logits'][-1]).max()) > 0.05
    assert n == (3 if control == 'one_pass_left_out' else 4)


# -- one position through the (pass, layer) cache ----------------------------------
def test_decode_through_the_cache_matches_sequence():
    """In the cell's compute dtype (in float32, with the other trunks:
    tests/test_models.py ``test_a_trunks_sequence_and_its_steps_agree``)."""
    dtype, atol = 'bfloat16', 0.12
    net, variables = _net_and_variables(dtype)
    ids = _ids(4, (3, T))
    logits, value, _gate = _program(
        net, variables, ids, jnp.zeros((3,), jnp.int32),
        jnp.ones((3, T), bool))
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    assert [k.shape for k in hidden['k']] == [(3, 4 * 48, 32)] * 2
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        # the actor plays from the LAST pass
        np.testing.assert_allclose(out['policy'], logits[-1][:, t],
                                   atol=atol)
        np.testing.assert_allclose(out['value'][:, 0], value[-1][:, t],
                                   atol=atol)
    assert hidden['pos'].tolist() == [T] * 3


RESETS = {1: 9, 2: 23}      # sequence -> the ply its second game begins


def _played_across_resets(net, variables, ids):
    """Three sequences' logits (3, T, A) ply by ply through the cache, two
    of them reset at ``RESETS``."""
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    got = []
    for t in range(T):
        done = jnp.asarray([RESETS.get(b) == t for b in range(3)])
        hidden = net.reset_hidden(hidden, done)
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        got.append(out['policy'])
    assert hidden['pos'].tolist() == [T, T - 9, T - 23]
    return jnp.stack(got, axis=1)


def test_lanes_reset_at_different_counters_keep_their_buffers():
    """Three sequences, two of them reset at different plies: every ply of
    every game against the full forward over that game's ids; the buffers
    are never cleared, the counter alone masks what an earlier game left."""
    net, variables = _net_and_variables()
    ids = _ids(5, (3, T))
    got = _played_across_resets(net, variables, ids)
    for b in range(3):
        a = RESETS.get(b, 0)
        for lo, hi in ((0, a), (a, T)):
            if lo == hi:
                continue
            game = jnp.zeros((T,), jnp.int32).at[:hi - lo].set(ids[b, lo:hi])
            want = _reference(net, variables, game, 0,
                              jnp.arange(T) < hi - lo)
            np.testing.assert_allclose(got[b, lo:hi],
                                       want['logits'][-1][:hi - lo],
                                       atol=3e-4)


@pytest.mark.parametrize('dtype,atol', [('float32', 3e-4),
                                        ('bfloat16', 0.12)])
def test_the_kernels_form_decodes_what_sequence_computes_across_a_games_end(
        kernel_form, dtype, atol):
    """``OuroNet`` played ply by ply through the cache with the block kernel
    (a row of 128 lanes: 2 heads of 64) against ``sequence`` over the same
    ids, to the tolerances of the two cases above: three sequences, two of
    them reset at different plies, so a counter goes back to 0 over buffers
    that still hold the old game's rows, and the counters pass every block
    boundary of a 48-row pass."""
    net, variables = _net_and_variables(dtype, head_dim=64)
    ids = _ids(7, (3, T))
    got = _played_across_resets(net, variables, ids)
    # a call a layer each time the pass scan's body is traced
    assert kernel_form and len(kernel_form) % net.layers == 0
    for b in range(3):
        a = RESETS.get(b, 0)
        for lo, hi in ((0, a), (a, T)):
            if lo == hi:
                continue
            game = jnp.zeros((1, T), jnp.int32).at[0, :hi - lo].set(
                ids[b, lo:hi])
            logits, _value, _gate = _program(
                net, variables, game, jnp.zeros((1,), jnp.int32),
                (jnp.arange(T) < hi - lo)[None])
            np.testing.assert_allclose(got[b, lo:hi],
                                       logits[-1][0, :hi - lo], atol=atol)


def test_the_net_counts_the_rows_its_plies_read(kernel_form):
    """``decode_rows``: over every (pass, layer), whole blocks up to each
    counter's own where the kernel runs, with groups or without, every row
    elsewhere (rows of no whole lanes, the CPU), beside the rows the buffers
    hold."""
    pos = np.asarray([[0, 15], [16, 47]])
    net, _ = _net_and_variables(head_dim=64)
    each = net.passes * net.layers
    assert net.decode_rows(pos) == (each * (16 + 16 + 32 + 48),
                                    each * 48 * 4)
    grouped, _ = _net_and_variables(head_dim=64, heads_held=4)
    assert grouped.decode_rows(pos) == net.decode_rows(pos)
    narrow, _ = _net_and_variables()         # 32 lanes: not the kernel's
    assert narrow.decode_rows(pos)[0] == each * 48 * 4


def test_a_pass_reads_its_own_rows_and_no_other_passes():
    """The helpers of ``models/attention.py``: pass t's rows of a layer's
    buffer are rows ``t * rows ..``, written at ``t * rows + pos``."""
    ck, cv = (jnp.zeros((2, 4 * 6, 8)) for _ in range(2))
    k = jnp.ones((2, 2, 4))
    pos = jnp.asarray([1, 5])
    for t in range(4):
        ck, cv = attention.pass_write(ck, cv, (t + 1) * k, -(t + 1) * k,
                                      pos, t, 6)
    for t in range(4):
        rows = attention.pass_rows(ck, jnp.int32(t), 6)
        assert rows.shape == (2, 6, 8)
        assert rows[0, 1].tolist() == [t + 1] * 8 == rows[1, 5].tolist()
        assert float(jnp.abs(rows).sum()) == 2 * 8 * (t + 1)
        assert attention.pass_rows(cv, t, 6)[0, 1].tolist() == [-t - 1] * 8
    cache = attention.init_pass_cache((3,), 4, [6, 6], 8, jnp.bfloat16)
    assert [c.shape for c in cache['k']] == [(3, 24, 8)] * 2
    assert cache['pos'].shape == (3,)


def test_a_net_with_grouped_heads_decodes_what_its_sequence_computes():
    """The decode attention is chosen from the shapes, so the net asserts
    nothing about its heads: two query heads a KV head take the grouped
    product (the heads' comparison with a group of one:
    tests/test_attention.py)."""
    net, variables = _net_and_variables(heads_held=4)
    ids = _ids(6, (2, T))
    logits, _value, _gate = _program(
        net, variables, ids, jnp.zeros((2,), jnp.int32),
        jnp.ones((2, T), bool))
    step = jax.jit(net.apply)
    hidden = net.init_hidden((2,))
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        np.testing.assert_allclose(out['policy'], logits[-1][:, t],
                                   atol=3e-4)


def test_the_byte_game_builds_the_net_and_its_rollout_runs_every_pass():
    from handyrl_tpu.environment import make_env
    env = make_env({'env': 'ByteGame', 'min_steps': 12, 'max_steps': 16,
                    'ids': 72, 'first_ply_ids': 8, 'net_name': 'OuroNet',
                    'net': dict(WIDTHS, max_positions=16)})
    net = env.net()
    assert isinstance(net, OuroNet) and net.passes == 4
    assert hasattr(net, 'sequence') and hasattr(net, 'policy_logits')
    assert net.actor_param_dtype == net.dtype


# -- the shares -----------------------------------------------------------------
def test_the_four_head_shares_sum_to_the_uncut_attention():
    """W_q, W_k, W_v by columns and W_o by rows, four ways: the shares'
    parts of W_o's sum add up to the uncut layer's attention output."""
    whole, variables = _net_and_variables(heads_held=8, kv_heads_held=8)
    share = OuroNet(dtype=jnp.float32, **WIDTHS)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, 64))
    positions = jnp.arange(T)[None, :] + jnp.asarray([[0], [7]])
    valid = jnp.ones((2, T), bool)
    want = whole.apply(variables, 0, x, positions, valid,
                       method=whole.attention_part)
    total = 0.0
    p = variables['params']
    for c in range(4):
        cols = slice(c * 32, (c + 1) * 32)      # 2 heads of 16
        layer = dict(p['layer_0'])
        for name in ('wq', 'wk', 'wv'):
            layer[name] = p['layer_0'][name][:, cols]
        layer['wo'] = p['layer_0']['wo'][cols]
        cut = {'params': dict(p, layer_0=layer, layer_1=layer)}
        total = total + share.apply(cut, 0, x, positions, valid,
                                    method=share.attention_part)
    np.testing.assert_allclose(total, want, atol=2e-4)


def test_the_four_vocabulary_slices_side_by_side_are_the_whole_head():
    whole, variables = _net_and_variables()
    share = OuroNet(dtype=jnp.float32, **dict(WIDTHS, vocab=18))
    features = jax.random.normal(jax.random.PRNGKey(7), (4, 2, T, 64))
    want = whole.apply(variables, features, method=whole.policy_logits)
    p = variables['params']
    parts = [share.apply(
        {'params': dict(p, head=p['head'][:, c * 18:(c + 1) * 18],
                        embed=p['embed'][c * 18:(c + 1) * 18])},
        features, method=share.policy_logits) for c in range(4)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), want,
                               atol=1e-5)


# -- the exit distribution -------------------------------------------------------
def test_the_exit_distribution_sums_to_one_and_is_the_written_out_one():
    gate = 2.0 * jax.random.normal(jax.random.PRNGKey(8), (4, 3, 5))
    p, entropy = losses.exit_distribution(gate)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    want, want_entropy = reference_loss.exit_distribution(
        gate.reshape(4, 15))
    np.testing.assert_allclose(p.reshape(4, 15), want, atol=1e-6)
    np.testing.assert_allclose(entropy.reshape(15), want_entropy, atol=1e-5)
    assert float(entropy.max()) <= np.log(4) + 1e-6
    # the last pass's own logit is not read
    moved = gate.at[-1].add(3.0)
    np.testing.assert_array_equal(losses.exit_distribution(moved)[0], p)
    # an undecided gate of four passes: (1/2, 1/4, 1/8, 1/8)
    even, _ = losses.exit_distribution(jnp.zeros((4, 1)))
    assert even[:, 0].tolist() == [0.5, 0.25, 0.125, 0.125]


def test_the_sums_of_the_loss_count_the_acting_positions():
    """What ``_exit_weighted_losses`` hands to ``diag`` beside the losses."""
    gate = jax.random.normal(jax.random.PRNGKey(9), (4, 2, 6, 1, 1))
    acting = (jnp.arange(6)[None, :] < jnp.asarray([[6], [3]])).astype(
        jnp.float32)[..., None, None]
    zeros = jnp.zeros((4, 2, 6, 1, 1))
    batch = {'turn_mask': acting, 'observation_mask': acting,
             'progress': jnp.zeros((2, 1, 1))}
    terms, count, sums = losses._exit_weighted_losses(
        zeros, zeros[..., 0], zeros, gate, zeros[0], {'value': zeros[0]},
        batch, LOSS)
    assert float(count) == float(sums['window_positions_valid']) == 9.0
    p, entropy = losses.exit_distribution(gate[:, :, :, 0, 0])
    np.testing.assert_allclose(sums['exit_entropy_nats'],
                               (entropy * acting[..., 0, 0]).sum(), rtol=1e-6)
    assert sums['exit_entropy_nats'] is terms['exit_ent']
    np.testing.assert_allclose(sums['exit_mass_pass_2'],
                               (p[1] * acting[..., 0, 0]).sum(), rtol=1e-6)
    np.testing.assert_allclose(sums['exit_entropy_max_nats'], 9 * np.log(4),
                               rtol=1e-6)
    np.testing.assert_allclose(
        sum(sums['exit_mass_pass_%d' % t] for t in range(1, 5)), 9.0,
        rtol=1e-6)
    assert 0 < float(sums['exit_entropy_nats']) \
        < float(sums['exit_entropy_max_nats'])
    net, _variables = _net_and_variables()
    dynamics = net.epoch_dynamics({'diag_' + k: float(v)
                                   for k, v in sums.items()})
    assert 0 < dynamics['exit_entropy_share'] < 100
    np.testing.assert_allclose(
        sum(dynamics['exit_mass_pass_%d' % t] for t in range(1, 5)), 1.0,
        rtol=1e-6)
    assert net.epoch_dynamics({}) == {}


# -- the loss and its gradient ---------------------------------------------------
CONFIG = {'model': {'vocab': 72, 'max_positions': 48},
          'env_args': {'first_ply_ids': 8}}
TRAIN_ARGS = {'forward_steps': T, 'batch_size': 2}
LOSS = losses.LossConfig(
    turn_based_training=False, observation=True, policy_target='VTRACE',
    value_target='VTRACE', gamma=0.99)


def _loss_fns(net):
    apply = net.apply
    sequence = lambda params, *a: net.apply(params, *a, method=net.sequence)
    policy = lambda params, f: net.apply(params, f, method=net.policy_logits)
    return apply, sequence, policy


def _program_loss(net, variables, batch):
    apply, sequence, policy = _loss_fns(net)
    return losses.compute_loss(apply, variables, None, batch, LOSS,
                               sequence_fn=sequence, policy_fn=policy)


def _reference_loss(net, variables, windows, **controls):
    cfg = _cfg(net)
    total, terms = 0.0, {}
    forward = {k: v for k, v in controls.items() if k != 'last_pass_only'}
    for window in windows:
        win = {k: jnp.asarray(v) for k, v in window.items()}
        with jax.default_matmul_precision('highest'):
            out = reference.forward(variables, win['ids'],
                                    win['first_position'], win['valid'] > 0,
                                    cfg, **forward)
            value_target, advantage = reference_loss.targets(
                jax.lax.stop_gradient(out), window, LOSS.lmb)
            one, its = reference_loss.loss(
                variables, win, jnp.asarray(value_target, jnp.float32),
                jnp.asarray(advantage, jnp.float32), cfg,
                LOSS.entropy_regularization,
                LOSS.entropy_regularization_decay, **controls)
        total = total + one
        for k, v in its.items():
            terms[k] = terms.get(k, 0.0) + v
    return total, terms


@pytest.fixture(scope='module')
def batch_and_windows():
    batch, windows = checks_ouro.seeded_batch(CONFIG, 3, TRAIN_ARGS)
    return jax.tree_util.tree_map(jnp.asarray, batch), windows


def test_the_loss_is_the_references_exit_weighted_sum(batch_and_windows):
    net, variables = _net_and_variables()
    batch, windows = batch_and_windows
    total, aux = _program_loss(net, variables, batch)
    want, terms = _reference_loss(net, variables, windows)
    np.testing.assert_allclose(total, want, rtol=2e-4)
    for key in ('p', 'v', 'ent', 'exit_ent', 'total'):
        np.testing.assert_allclose(aux['losses'][key], terms[key], rtol=2e-4,
                                   atol=1e-4)
    valid = sum(int(w['valid'].sum()) for w in windows)
    assert float(aux['data_count']) == valid
    diag = aux['diag']
    assert float(diag['window_positions_valid']) == valid
    np.testing.assert_allclose(diag['exit_entropy_nats'], terms['exit_ent'],
                               rtol=1e-4)
    # V-trace's ratios come from the LAST pass: another loss where they do not
    alone, _ = _reference_loss(net, variables, windows, last_pass_only=True)
    assert abs(float(alone) - float(want)) > 1e-2 * abs(float(want))


def test_the_gradient_is_the_references_leaf_by_leaf(batch_and_windows):
    net, variables = _net_and_variables()
    batch, windows = batch_and_windows
    got = jax.grad(lambda v: _program_loss(net, v, batch)[0])(variables)

    def plain(v):
        # the targets carry no gradient: taken at the point, held constant
        return _reference_loss(net, v, windows)[0]
    want = jax.grad(plain)(variables)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, w, atol=2e-3 * scale, err_msg=str(path))


def test_beta_times_the_entropy_of_p_reaches_the_gates_gradient(
        batch_and_windows, monkeypatch):
    net, variables = _net_and_variables()
    batch, _windows = batch_and_windows
    gate = lambda v: jax.grad(lambda x: _program_loss(net, x, batch)[0])(
        v)['params']
    with_entropy = gate(variables)
    monkeypatch.setattr(losses, 'EXIT_ENTROPY_COEF', 0.0)
    without = gate(variables)
    for name in ('gate', 'gate_bias'):
        assert float(jnp.abs(with_entropy[name] - without[name]).max()) > 1e-3
        assert float(jnp.abs(without[name]).max()) > 0    # sum_t p_t l_t too


def test_each_weights_gradient_is_the_sum_over_four_untied_copies(
        batch_and_windows):
    """What ties the loop to the model: the reference run with FOUR sets of
    layer weights, one a pass (the same values), differentiated set by set;
    the program's gradient of a layer's leaf is the four sets' SUM, and no
    single use's."""
    net, variables = _net_and_variables()
    batch, windows = batch_and_windows
    cfg = _cfg(net)
    p = variables['params']
    layers = {name: leaf for name, leaf in p.items()
              if name.startswith('layer_')}
    window = {k: jnp.asarray(v) for k, v in windows[1].items()}
    with jax.default_matmul_precision('highest'):
        out = reference.forward(variables, window['ids'],
                                window['first_position'],
                                window['valid'] > 0, cfg)
    value_target, advantage = (
        jnp.asarray(x, jnp.float32)
        for x in reference_loss.targets(out, windows[1], LOSS.lmb))

    def untied(copies):
        positions = window['first_position'] + jnp.arange(T)
        x = reference.embed(p, window['ids'], cfg)
        xs = []
        for t in range(4):
            for i in range(net.layers):
                x, _own = reference.layer(copies[t]['layer_%d' % i], x,
                                          positions, window['valid'] > 0, cfg)
            x = reference.between(p['norm_out'], x, cfg)
            xs.append(x)
        return reference_loss.loss_of_outputs(
            reference.readout(p, jnp.stack(xs), cfg), window, value_target,
            advantage, LOSS.entropy_regularization,
            LOSS.entropy_regularization_decay)[0]
    with jax.default_matmul_precision('highest'):
        by_copy = jax.grad(untied)([layers] * 4)
    one_window = jax.tree_util.tree_map(lambda x: x[1:], batch)
    got = jax.grad(lambda v: _program_loss(net, v, one_window)[0])(
        variables)['params']
    for name in layers:
        for leaf in layers[name]:
            uses = [by_copy[t][name][leaf] for t in range(4)]
            total = sum(uses)
            scale = float(jnp.abs(total).max())
            np.testing.assert_allclose(got[name][leaf], total,
                                       atol=2e-3 * scale, err_msg=leaf)
            for use in uses:        # no single use is the gradient
                assert float(jnp.abs(got[name][leaf] - use).max()) \
                    > 0.02 * scale, (name, leaf)


# -- the lowered programs hold the stack once -------------------------------------
def _products(text, shape):
    """dot_generals of the lowered text whose right operand has ``shape``."""
    return len(re.findall(
        r'stablehlo\.dot_general[^\n]*tensor<%s>\) ->' % shape, text))


def test_the_lowered_programs_hold_one_copy_of_the_stack(batch_and_windows):
    net, variables = _net_and_variables('float32', mlp_size=80)
    ids = _ids(2, (2, T))
    args = (variables, ids, jnp.zeros((2,), jnp.int32),
            jnp.ones((2, T), bool))
    text = jax.jit(lambda *a: net.apply(*a, method=net.sequence)).lower(
        *args).as_text()
    # W_gate and W_up (64 x 80) of 2 layers: 4 products, not 4 passes' 16
    assert _products(text, '64x80xf32') == 2 * net.layers
    decode = jax.jit(net.apply).lower(
        variables, ids[:, 0], net.init_hidden((2,))).as_text()
    assert _products(decode, '64x80xf32') == 2 * net.layers
    # the update's gradient: W_gate and W_up in the forward, the recomputed
    # forward and the transpose to the layer's input, once a LAYER (6) and
    # not once a use (24)
    batch, _windows = batch_and_windows
    grad = jax.jit(jax.grad(lambda v: _program_loss(net, v, batch)[0])).lower(
        variables).as_text()
    assert 0 < _products(grad, '64x80xf32') <= 6 * net.layers
    # the head (64 x 72): the passes' four readouts are ONE block program
    assert _products(grad, '64x72xf32') <= 3     # four unrolled: 12


def test_the_scopes_stand_as_they_are_in_the_backward_pass_too(
        batch_and_windows):
    """A scope reader looks for the scope's name among the steps of an
    operation's path, and jax writes its transforms around the first scope
    named under them: ``jvp(pass_readout)`` would be counted by no metric
    (``_pass_readout_scope``)."""
    net, variables = _net_and_variables()
    batch, _windows = batch_and_windows
    text = jax.jit(jax.grad(lambda v: _program_loss(net, v, batch)[0])).lower(
        variables).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ('loop_attention', 'trunk_mlp', 'pass_readout'):
        ours = [path for path in paths if scope in path]
        assert any('transpose(' in path for path in ours), scope
        assert all(scope in path.split('/') for path in ours), [
            path for path in ours if scope not in path.split('/')][:3]
    # the head's products, forward and backward, are under the scope
    assert any('pass_readout' in path and path.endswith('dot_general')
               and 'transpose(' in path for path in paths)


def test_a_net_without_a_pass_axis_takes_the_old_path():
    """The seam is one dictionary key: a sequence net that hands over no
    ``exit_gate`` reaches ``compose_losses`` as before."""
    assert losses.PASS_GATE == 'exit_gate'
    from handyrl_tpu.models.smallthinker import SmallThinkerNet
    net = SmallThinkerNet(
        hidden_size=32, layer_types=('global',), heads_held=2,
        kv_heads_held=1, head_dim=8, expert_size=16, experts_published=4,
        experts_held=(0, 1), experts_per_token=2, vocab=72, window_size=8,
        max_positions=48, query_block=8, dense_rows=4, dtype=jnp.float32)
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    batch, _ = checks_ouro.seeded_batch(CONFIG, 4, TRAIN_ARGS)
    total, aux = _program_loss(net, variables,
                               jax.tree_util.tree_map(jnp.asarray, batch))
    assert np.isfinite(float(total))
    assert set(aux['losses']) == {'p', 'v', 'ent', 'total'}
