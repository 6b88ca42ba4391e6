"""The ``evabyte`` configuration's own files: what its file states against
the source and against the program, its FLOP and byte counts against a
count by brute force, the numbers a derived metric reads against the
function that gives them, the reader of a scope inside a loop, and its
checks' negative controls at the rehearsal's size. (That the cell rehearses
with ``correct`` true is test_bench_rehearsal's, which runs every cell of
the manifest.)

Every test that takes ``cell`` runs twice: on the checkout and on the root
later PRs will have grown it into (``conftest.py``'s ``either_root``), so a
pin that indexes a list from its end (``raw['per_layer'][-13:]`` stood here
from PR 34 to PR 41) fails in the PR that writes it. On the checkout alone:
the tests that take ``tiny`` (they lay out a rehearsal root of the checkout
and run the checks at its size) and those that take neither fixture."""

import json
import os

import numpy as np
import pytest

from benchmark import checks_evabyte, flops_evabyte, rehearse
from benchmark.manifest import Manifest, ROOT
from benchmark.readers import trace_inner_scope_time

from tests.benchmark import contracts, traced_fill

CELL = 'evabyte.selfplay_4k'
# EvaByte/EvaByte config.json, the numbers of it: what may not differ
PUBLISHED = {'hidden_size': 4096, 'intermediate_size': 11008,
             'vocab_size': 320, 'chunk_size': 16, 'window_size': 2048,
             'num_pred_heads': 8, 'rope_theta': 100000, 'rms_norm_eps': 1e-05,
             'max_position_embeddings': 32768, 'init_std': 0.01275}
CUT = {'num_hidden_layers': (32, 4), 'num_attention_heads': (32, 8),
       'num_key_value_heads': (32, 8)}


@pytest.fixture(scope='module')
def cell(either_root):
    manifest = either_root
    config = manifest.load_config('evabyte')
    traffic = manifest.load_traffic('selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'])
    return manifest, config, traffic, train_args


def test_the_file_keeps_every_published_width_and_lists_each_cut(cell):
    manifest, config, _traffic, _args = cell
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = manifest.configs['evabyte']
    assert sorted(entry['reduced']) == sorted(CUT) == sorted(config['reduced'])
    for key, (published, held) in CUT.items():
        assert config[key] == held < published
    assert (config['heads_held'], config['heads_published']) == (8, 32)
    assert 'four chips share each layer by heads' in config['deployment']
    assert config['weights'] == {'seeded': True, 'why': config['weights']['why']}
    assert len(config['assumed']) >= 3 and config['departures_from_source']
    model, net = config['model'], config['env_args']['net']
    for key in ('hidden_size', 'layers', 'heads_held', 'head_dim', 'mlp_size',
                'vocab', 'chunk_size', 'window_size', 'pred_heads'):
        assert model[key] == net[key], key
    assert (model['hidden_size'], model['mlp_size'], model['head_dim']) \
        == (config['hidden_size'], config['intermediate_size'],
            config['hidden_size'] // 32)


@pytest.mark.parametrize('key', ['parameters', 'defaults'])
def test_the_program_builds_the_net_the_file_states(cell, key):
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.evabyte import EvaByteNet
    _manifest, config, _traffic, _args = cell
    net = make_env(config['env_args']).net()
    if key == 'defaults':    # the module's defaults ARE the published widths
        assert net == EvaByteNet()
        return
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) \
        == config['model']['parameters'] == 620019712
    assert sum(flops_evabyte.matmul_parameters(config['model'])) \
        == 620019712 - 320 * 4096 - 4 * (2 * 4096 + 2 * 8 * 128) - 4096


def test_the_cell_is_the_issues_table(cell):
    """ISSUE 34's table C, value for value, but for the first two of the
    cuts it permits where the seconds run over, which they did with the
    final flush at 98 s (the traffic file's ``fallbacks``): ``trace_seconds``
    6 -> 3 and ``update_episodes`` 8 -> 4."""
    _manifest, _config, traffic, args = cell
    want = {'generation_envs': 16, 'eval_envs': 4, 'device_chunk_steps': 256,
            'forward_steps': 4096, 'burn_in_steps': 0, 'batch_size': 2,
            'sgd_steps_per_chunk': 2, 'maximum_episodes': 192,
            'replay_windows_per_episode': 1, 'minimum_episodes': 8,
            'update_episodes': 4, 'checkpoint_interval': 64,
            'compute_dtype': 'bfloat16', 'gamma': 0.99}
    assert {k: args[k] for k in want} == want
    # "2 steps, batch 2, 4 windows and 4,096 plies a chunk"
    assert traffic['replay'] == {
        'sgd_steps_per_chunk': 2, 'batch_size': 2,
        'trained_windows_per_chunk': 4, 'plies_per_chunk': 256 * 16}
    # 16,384 positions trained a chunk of 4,096 lane-plies
    assert 4 * args['forward_steps'] == 16384
    assert traffic['window']['trace_seconds'] == 3


def _pairs_by_brute_force(window, chunk, positions, first):
    total = 0
    for n in range(first, first + positions):
        local = [m for m in range(first, n + 1) if m // window == n // window]
        chunks = {m // chunk for m in range(first, n + 1)
                  if m // window < n // window
                  and m // chunk * chunk >= first}
        total += len(local) + len(chunks)
    return total


@pytest.mark.parametrize('positions,first', [(40, 0), (64, 0), (37, 16)])
def test_attention_pairs_are_the_sets_the_equations_name(positions, first):
    model = {'window_size': 16, 'chunk_size': 4}
    assert flops_evabyte.attention_pairs(model, positions, first) \
        == _pairs_by_brute_force(16, 4, positions, first)


def test_the_counts_a_metric_reads_are_the_functions(cell):
    _manifest, config, _traffic, args = cell
    model = config['model']
    scope = flops_evabyte.eva_attention_scope(model, args)
    assert model['eva_attention_sgd_flops'] == scope['sgd_flops']
    # the cache's bytes are held SPLIT (PR 52): a ply's weights, and what
    # one more row (a K and V of the window, or a summary pair) in every
    # sequence costs a ply; with the WHOLE cache read, 2,560 rows, it is the
    # number held until then
    assert model['eva_attention_rollout'] == scope['rollout']
    assert scope['rollout'] == {
        'plies': 256, 'ply_bytes': 4 * 4 * 4096 * 1024 * 2,
        'row_bytes': {'eva': 32 * 4 * 2 * 8 * 128 * 2},
        'analytic_rows': {'eva': 2048 + 512}}
    assert scope['rollout_bytes'] == 377957122048 \
        == int(flops_evabyte.chunk_bytes(scope['rollout']))
    # |L_n| + |R_n| of the equations: the window's rows up to the query and
    # one summary a chunk of the windows before
    at = [0, 9, 2047, 2048, 4100, 8191]
    assert list(flops_evabyte.rows_seen_at(model, 'eva', at)) \
        == [1, 10, 2048, 1 + 128, 5 + 256, 2048 + 384]
    assert sum(flops_evabyte.rows_seen_at(model, 'eva', np.arange(5000))) \
        == flops_evabyte.attention_pairs(model, 5000)
    window = flops_evabyte.train_window_flops(model, args)
    # 3 x (2 x positions x parameters) and a few percent of attention
    floor = 6 * 4096 * sum(flops_evabyte.matmul_parameters(model))
    assert floor < window < 1.05 * floor
    # the scope's SGD part is a share of the dispatch's trained windows'
    # FLOPs (batch_size x sgd_steps_per_chunk of them)
    windows = args['batch_size'] * args['sgd_steps_per_chunk']
    assert 0.05 < scope['sgd_flops'] / (windows * window) < 0.2
    # a ply reads the attention weights once and 32 caches of 42 MB
    assert scope['rollout_bytes'] == 256 * (
        4 * 4 * 4096 * 1024 * 2 + 32 * 4 * 2 * (2048 + 512) * 8 * 128 * 2)
    burn = dict(args, burn_in_steps=64)
    assert flops_evabyte.train_window_flops(model, burn) \
        > flops_evabyte.train_window_flops(model, args)


@pytest.mark.parametrize('case, fill', traced_fill.CASES)
def test_a_roofline_counts_the_rows_its_traced_chunk_had_reached(
        cell, tmp_path, case, fill):
    """As ``test_bench_ouro``'s, through ``eva_attention_roofline``'s own
    file: 100% at 0.7, 1.0 and 1.3 times the games' mean ply index (2,559)
    for a dispatch whose time is the chip's least for the cache rows its
    queries must see (their window up to themselves and the summaries
    reached), where the count of before PR 52, the WHOLE cache a ply, reads
    far over 100%; the share of what is required for one that reads every
    row; nothing for one whose chunk was never recorded."""
    manifest, config, traffic, args = cell
    spec = manifest.load_metric('eva_attention_roofline')
    assert spec['reader'] == 'traced_fill_roofline'
    assert spec['args'] == {
        'module': 'jit_fused_pipeline_train', 'span': 'chunk_plies',
        'scope': 'eva_attention',
        'rows': 'benchmark.flops_evabyte:rows_seen_at',
        'rollout': 'config.model.eva_attention_rollout',
        'sgd_flops': 'config.model.eva_attention_sgd_flops'}
    got, share, old = traced_fill.roofline_case(
        tmp_path, manifest, manifest.cell(CELL), config, traffic, args,
        'eva_attention_roofline', case, fill, mean_index=2559)
    if case == 'unpaired':
        assert got is None
        return
    assert got['value'] == pytest.approx(share, rel=1e-6)
    assert got['analytic_mean_value'] == pytest.approx(old, rel=1e-6)
    assert got['executions'][0]['fill_rows']['eva'] < 2560
    if case == 'time_follows_fill':
        assert share == pytest.approx(100.0) and old > 150
    else:
        assert 30 < got['value'] < 45


def test_the_cells_own_metrics_are_pinned_by_name(cell):
    """What the cell brought that no entry read before, found by NAME (the
    entries that list this cell alone), wherever in ``per_layer`` they
    stand: a later PR appends behind them and fails nothing here. The
    shared path's readings (``sgd_ms``, ``rollout_ms``, ``fetch_wait_ms``,
    ``train_mfu``, ...) list no cells and are this cell's under their one
    name; the eight ``trunk_*`` twins of PR 34 are gone."""
    manifest, _config, _traffic, _args = cell
    contracts.a_cells_own_metrics_are_its_entries(manifest, CELL)
    reported = manifest.metrics_of(CELL, 'per_layer')
    # the seven shipped metrics that never listed cells are the cell's too
    for name in ('fused_program_ms', 'env_steps_per_s', 'episodes_per_s',
                 'plies_per_episode', 'chunk_max_ms', 'device_idle',
                 'hbm_peak_gib'):
        assert name in reported
    for twin in ('sgd_ms', 'rollout_ms', 'ingest_ms', 'unscoped_ms',
                 'fetch_wait_ms', 'host_busy_ms', 'dispatch_enqueue_ms',
                 'train_mfu'):
        assert 'trunk_' + twin not in manifest.metrics
        assert twin in reported


@pytest.mark.parametrize('name', contracts.OWN[CELL])
def test_each_own_metric_names_a_reader_and_the_cell(cell, name):
    contracts.a_cells_own_metric(cell[0], CELL, name)


def test_self_times_take_the_children_out():
    # a while of 100 ns holding two bodies of 30 ns, one with a 10 ns child
    ops = [(0, 100, 1), (10, 40, 2), (15, 25, 3), (50, 80, 2), (120, 130, 4)]
    got = sorted(trace_inner_scope_time.self_times(ops))
    assert got == [(1, 40), (2, 20), (2, 30), (3, 10), (4, 10)]


def test_the_inner_scope_reader_finds_nothing_without_a_trace():
    class Run:
        trace = None
    assert trace_inner_scope_time.read(Run(), 'm', 'eva_attention') is None
    Run.trace = {'path': '/nonexistent.xplane.pb', 'window': (0, 1)}
    assert trace_inner_scope_time.read(Run(), 'm', 'eva_attention') is None


# -- the checks and their negative controls, at the rehearsal's size ------------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    """The configuration under its rehearsal overlay, its module and the
    learner's own starting variables."""
    import jax.numpy as jnp
    from benchmark import checks
    dest = str(tmp_path_factory.mktemp('evabyte_tiny'))
    rehearse.build_root(Manifest(), dest, CELL)
    laid = Manifest(dest)
    config = laid.load_config('evabyte')
    traffic = laid.load_traffic('selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'], seed=5)
    variables = checks.starting_variables(config, train_args)
    module = checks.build_module(config, train_args)
    assert module.dtype == jnp.bfloat16
    return config, train_args, module, variables


def test_the_seeded_windows_differ_in_length(tiny):
    """The step check's batch is the cell's ``batch_size`` windows: one ends
    inside its game (padding, the value's tail), the next fills it."""
    config, train_args, _module, _variables = tiny
    batch, windows = checks_evabyte.seeded_batch(config, 3, train_args)
    assert len(windows) == train_args['batch_size'] == 2
    short, whole = (w['valid'] for w in windows)
    assert 0 < short.sum() < len(short) == train_args['forward_steps']
    assert whole.all()
    assert batch['observation'].shape == (2, len(short), 1)
    assert batch['first_position'].shape == (2, 1, 1, 1)
    assert (batch['action_mask'][0, 1:, 0, 256:] == 1e32).all()
    assert (batch['value'][0, short == 0, 0, 0]
            == windows[0]['outcome']).all()
    assert (batch['observation'][1, :, 0] == windows[1]['ids']).all()
    ids, first, ok = checks_evabyte.seeded_windows(config, 3, 4, 32)
    assert ok[0].all() and not ok[1].all() and first.max() <= 64 - 32


@pytest.mark.parametrize('seed', [0, 1, 2934100001 % 2**31])
def test_the_rollout_checks_games_end_where_it_puts_them(tiny, seed):
    """One band a lane: the whole run, an early end, a late end, and one
    past an attention window that reads summaries before it ends."""
    config, _train_args, _module, _variables = tiny
    model = config['model']
    plies = int(config['rollout_plies'])
    whole, early, late, past = checks_evabyte.first_lengths(
        config, seed, plies)
    assert whole > plies
    assert 0 < early < late < plies and early < plies // 4 <= late
    assert model['window_size'] + model['chunk_size'] <= past < plies
    assert len({plies - early, plies - late, plies - past, plies}) == 4


@pytest.mark.parametrize('control', ['stated', 'summaries_left_out',
                                     'one_layer_left_out',
                                     'int8_parameters'])
def test_the_rollout_check_tells_the_controls_apart(tiny, control):
    """Lanes whose counters differ, three resets inside the run, and the
    plies that read summaries held to a limit of their own: each control
    fails, the stated precision passes every limit."""
    import jax
    import jax.numpy as jnp
    config, train_args, module, variables = tiny
    args, actor, reference = {}, variables, None
    if control == 'summaries_left_out':
        args = {'use_remote': False}
    elif control == 'one_layer_left_out':
        args = {'skip_layer': 1}
    elif control == 'int8_parameters':
        def rounded(x):
            scale = jnp.abs(x).max() / 127 + 1e-12
            return jnp.round(x / scale) * scale
        actor, reference = jax.tree_util.tree_map(rounded, variables), variables
    stats = checks_evabyte.rollout_errors(
        config, module, actor, 11, train_args, reference_variables=reference,
        **args)
    assert stats['resets'] >= 3 and stats['distinct_counters'] == 4
    assert stats['remote_plies'] > 0 and stats['after_reset_plies'] > 0
    over = [name for name in checks_evabyte.ROLLOUT_LIMITS
            if stats[name] > config['tolerance']['rollout_' + name]]
    if control == 'stated':
        assert not over, stats
    else:
        assert 'remote_logits_rms_rel_to_logit_rms' in over, stats
        assert 'after_reset_logits_rms_rel_to_logit_rms' in over, stats


@pytest.mark.parametrize('control', ['stated', 'half_the_batch',
                                     'summaries_left_out',
                                     'int8_parameters'])
def test_the_step_check_tells_the_controls_apart(tiny, control, monkeypatch):
    """At the cell's batch size, leaf by leaf. ``half_the_batch`` plants
    what REVIEW of PR 34 named: a step that trains the first window only."""
    import jax
    import jax.numpy as jnp
    config, train_args, module, variables = tiny
    kwargs = {}
    if control == 'summaries_left_out':
        kwargs = {'use_remote': False}
    elif control == 'int8_parameters':
        def rounded(x):
            scale = jnp.abs(x).max() / 127 + 1e-12
            return jnp.round(x / scale) * scale
        kwargs = {'program_variables':
                  jax.tree_util.tree_map(rounded, variables)}
    elif control == 'half_the_batch':
        from handyrl_tpu.ops import train_step
        real = train_step._update_core

        def half(*args, **kw):
            update = real(*args, **kw)
            return lambda state, batch, lr: update(
                state, jax.tree_util.tree_map(lambda x: x[:1], batch), lr)
        monkeypatch.setattr(train_step, '_update_core', half)
    stats = checks_evabyte.step_errors(config, module, variables, 11,
                                       train_args, **kwargs)
    over = [name for name in checks_evabyte.STEP_LIMITS
            if stats[name] > config['tolerance']['step_' + name]]
    if control == 'stated':
        assert not over, stats
    else:
        assert 'grad_err_rel_to_grad' in over, stats
        assert 'grad_err_worst_leaf' in over, stats
    if control == 'half_the_batch':
        assert 'loss_rel_err' in over, stats


@pytest.mark.parametrize('control', ['stated', 'skip_layer', 'no_remote',
                                     'int8_parameters'])
def test_the_forward_check_tells_the_controls_apart(tiny, control):
    import jax
    import jax.numpy as jnp
    config, _train_args, module, variables = tiny
    args, program = {}, None
    if control == 'skip_layer':
        args = {'skip_layer': 1}
    elif control == 'no_remote':
        args = {'use_remote': False}
    elif control == 'int8_parameters':
        def rounded(x):
            scale = jnp.abs(x).max() / 127 + 1e-12
            return jnp.round(x / scale) * scale
        program = jax.tree_util.tree_map(rounded, variables)
    stats = checks_evabyte.forward_errors(config, module, variables, 11,
                                          program_variables=program, **args)
    limit = config['tolerance']['forward_logits_rms_rel_to_logit_rms']
    if control == 'stated':
        assert stats['logits_rms_rel_to_logit_rms'] < limit
    else:
        assert stats['logits_rms_rel_to_logit_rms'] > limit, stats
