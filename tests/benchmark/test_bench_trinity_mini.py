"""The ``trinity_mini`` configuration's own files: what its file states
against the source and against the program, the manifest's contracts on the
checkout with the sixth cell, its FLOP and byte counts against a count by
brute force, the numbers a derived metric reads against the functions that
give them, each new metric's reader and cell, and a planted fault for each
new check at the rehearsal's size. (That the cell rehearses with ``correct``
true is test_bench_rehearsal's, which runs every cell of the manifest.)

The cell's per-layer metrics are entries of the checkout's
``BENCHMARK.json`` since PR 42 (they waited in a root of their own from PR
38 on): five read what only this net's program names (``contracts.OWN``),
four read what both expert cells' programs give and list both under one
name since PR 45 (``contracts.EXPERT_SHARED``; ``trinity_optimizer_ms`` is
``optimizer_ms`` now); what every cell's program gives (``sgd_ms``,
``rollout_ms``, ``train_mfu``, ...) it reports under the shared names, and
the three twins PR 38 had for them are gone.

Every test that takes ``cell`` runs twice: on the checkout and on the root
later PRs will have grown it into (``conftest.py``'s ``either_root``), so a
pin that indexes a list from its end fails here, in the PR that writes it.
On the checkout alone: the tests that take ``tiny`` (they lay out a
rehearsal root of the checkout and run the checks at its size) and those
that take neither fixture."""

import numpy as np
import pytest

from benchmark import checks_trinity_mini as ct
from benchmark import flops_trinity_mini, rehearse
from benchmark.manifest import Manifest

from tests.benchmark import contracts, traced_fill

CELL = 'trinity_mini.moe_selfplay_4k'
# arcee-ai/Trinity-Mini config.json, the numbers of it: what may not differ
PUBLISHED = {'hidden_size': 2048, 'intermediate_size': 6144,
             'moe_intermediate_size': 1024, 'head_dim': 128,
             'num_experts_per_tok': 8, 'num_shared_experts': 1,
             'route_scale': 2.826, 'load_balance_coeff': 0.001,
             'sliding_window': 2048, 'rope_theta': 10000,
             'rms_norm_eps': 1e-05, 'global_attn_every_n_layers': 4,
             'n_group': 1, 'topk_group': 1, 'num_expert_groups': 1,
             'num_limited_groups': 1, 'max_position_embeddings': 131072}
CUT = {'num_hidden_layers': (32, 5), 'num_dense_layers': (2, 1),
       'num_experts': (128, 16), 'num_attention_heads': (32, 8),
       'num_key_value_heads': (4, 1), 'vocab_size': (200192, 25024)}
NEW = contracts.OWN[CELL]
SHARED_BY_THE_EXPERT_CELLS = list(contracts.EXPERT_SHARED)


@pytest.fixture(scope='module')
def cell(either_root):
    manifest = either_root
    config = manifest.load_config('trinity_mini')
    traffic = manifest.load_traffic('moe_selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'])
    return manifest, config, traffic, train_args


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_on_the_checkout_with_the_sixth_cell(contract, cell):
    manifest = cell[0]
    assert CELL in manifest.cells and len(manifest.cells) >= 6
    contract(manifest)


def test_the_pins_hold_with_the_sixth_cell(cell):
    contracts.pins(cell[0])


def test_the_file_keeps_every_published_width_and_lists_each_cut(cell):
    manifest, config, _traffic, _args = cell
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config['layer_types'] == (['sliding_attention'] * 3
                                     + ['full_attention']) * 8
    entry = manifest.configs['trinity_mini']
    assert sorted(entry['reduced']) == sorted(CUT) == sorted(config['reduced'])
    for key, (published, held) in CUT.items():
        assert config[key] == held < published == config['published'][key]
    assert 'eight chips (two v5e-4 hosts) share each layer' \
        in config['deployment']
    assert config['weights'] == {'seeded': True,
                                 'why': config['weights']['why']}
    assert len(config['assumed']) >= 9
    departures = ' '.join(config['departures_from_source'])
    for word in ('router takes no gradient', 'value row', 'V-trace',
                 'partial sums', 'param_scale'):
        assert word in departures, word
    model, net = config['model'], config['env_args']['net']
    for key in net:
        assert model[key] == net[key], key
    assert (model['hidden_size'], model['mlp_size'], model['expert_size'],
            model['head_dim'], model['experts_per_token'],
            model['window_size'], model['route_scale']) \
        == (2048, 6144, 1024, 128, 8, 2048, 2.826)
    assert model['layer_types'] == ['sliding'] * 4 + ['full']
    assert model['experts_held'] == list(range(16))
    assert (model['heads_held'], model['kv_heads_held'], model['vocab']) \
        == (8, 1, 25024) == (config['num_attention_heads'],
                             config['num_key_value_heads'],
                             config['vocab_size'])
    assert model['held_expert_slots'] == 16 * 4
    # a power of two: dividing a product's result by it changes no bit
    assert model['param_scale'] == 2 ** round(np.log2(model['param_scale']))
    env = config['env_args']
    assert (env['ids'], env['first_ply_ids'], env['net_name']) \
        == (25024, 64, 'TrinityNet')


@pytest.mark.parametrize('key', ['parameters', 'defaults'])
def test_the_program_builds_the_net_the_file_states(cell, key):
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.trinity import PUBLISHED_LAYERS, TrinityNet
    _manifest, config, _traffic, _args = cell
    net = make_env(config['env_args']).net()
    assert isinstance(net, TrinityNet)
    if key == 'defaults':   # the module's defaults ARE the published counts
        plain = TrinityNet()
        assert (plain.heads_held, plain.kv_heads_held, plain.vocab,
                plain.dense_layers, len(plain.held), plain.param_scale) \
            == (32, 4, 200192, 2, 128, 1.0)
        assert plain.layer_types == PUBLISHED_LAYERS and len(
            PUBLISHED_LAYERS) == 32
        return
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) \
        == config['model']['parameters'] == 603240192
    # ISSUE 38's table: 4 expert layers of 114.04M, a dense one of 44.57M,
    # 102.50M of embedding and head, the last norm and the value row
    expert = 6816000 + 8192 + 16 * 3 * 2048 * 1024 + 2048 * 128 + 128 \
        + 3 * 2048 * 1024
    dense = 6816000 + 8192 + 3 * 2048 * 6144
    assert 4 * expert + dense + 2 * 25024 * 2048 + 2 * 2048 == 603240192
    # the cache: a circle of 2,048 rows on four layers, 8,192 on the full
    hidden = jax.eval_shape(lambda: net.init_hidden((1,)))
    assert [k.shape for k in hidden['k']] \
        == [(1, 2048, 128)] * 4 + [(1, 8192, 128)]


def test_the_cell_is_the_issues_traffic(cell):
    _manifest, config, traffic, args = cell
    want = {'generation_envs': 16, 'eval_envs': 4, 'device_chunk_steps': 256,
            'forward_steps': 4096, 'burn_in_steps': 0, 'batch_size': 2,
            'sgd_steps_per_chunk': 2,
            'replay_windows_per_episode': 1, 'minimum_episodes': 8,
            'update_episodes': 4, 'checkpoint_interval': 64,
            'compute_dtype': 'bfloat16', 'gamma': 0.99,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE'}
    assert {k: args[k] for k in want} == want
    # 96 windows, or 48 by the first permitted cut (``fallbacks``)
    assert args['maximum_episodes'] in (96, 48)
    assert args['guard'] == {'nonfinite_policy': 'abort'}
    assert traffic['replay'] == {
        'sgd_steps_per_chunk': 2, 'batch_size': 2,
        'trained_windows_per_chunk': 4, 'plies_per_chunk': 256 * 16}
    assert (config['env_args']['min_steps'],
            config['env_args']['max_steps']) == (2048, 8192)
    # 512 rows a held expert a layer a step at the even share
    assert 2 * 4096 * 8 // 128 == 512


def _pairs_by_brute_force(kind, window, positions, first):
    total = 0
    for n in range(first, first + positions):
        seen = [m for m in range(first, n + 1)
                if kind == 'full' or n - window < m]
        total += len(seen)
    return total


@pytest.mark.parametrize('kind', ['sliding', 'full'])
@pytest.mark.parametrize('positions,first', [(40, 0), (16, 0), (37, 9)])
def test_attention_pairs_are_the_sets_the_equations_name(kind, positions,
                                                         first):
    assert flops_trinity_mini.attention_pairs(
        {'window_size': 16}, kind, positions, first) \
        == _pairs_by_brute_force(kind, 16, positions, first)


def test_the_counts_a_metric_reads_are_the_functions(cell):
    _manifest, config, _traffic, args = cell
    model = config['model']
    experts = flops_trinity_mini.moe_experts_scope(model, args)
    attention = flops_trinity_mini.gqa_attention_scope(model, args)
    assert model['moe_experts_sgd_flops'] == experts['sgd_flops']
    assert model['moe_experts_rollout_bytes'] == experts['rollout_bytes']
    assert model['gqa_attention_sgd_flops'] == attention['sgd_flops']
    # the attention's bytes are held SPLIT (PR 52): a ply's weights, and
    # what one more row in every sequence costs over the layers of a kind;
    # at the rows the count took until then it is the number held until then
    assert model['gqa_attention_rollout'] == attention['rollout']
    assert attention['rollout'] == {
        'plies': 256, 'ply_bytes': 5 * 6815744 * 2,
        'row_bytes': {'sliding': 4 * 32 * 512, 'full': 32 * 512},
        'analytic_rows': {'sliding': 2048.0,
                          'full': pytest.approx(2560.0, rel=1e-12)}}
    assert attention['rollout_bytes'] == 62545461248 \
        == int(flops_trinity_mini.chunk_bytes(attention['rollout']))
    at = [0, 9, 2047, 2048, 8191, 9000]
    assert list(flops_trinity_mini.rows_seen_at(model, 'sliding', at)) \
        == [1, 10, 2048, 2048, 2048, 2048]
    assert list(flops_trinity_mini.rows_seen_at(model, 'full', at)) \
        == [1, 10, 2048, 2049, 8192, 8192]
    # the even share: one held expert a position and layer
    assert flops_trinity_mini.held_per_position(model) == 1.0
    assert experts['sgd_flops'] == 3 * 4 * 2 * 4096 * 4 * 3 * 2048 * 1024
    # a ply reads the 64 held experts' bfloat16 weights once: 805 MB
    assert experts['rollout_bytes'] == 256 * 64 * 3 * 2048 * 1024 * 2
    assert 64 * 3 * 2048 * 1024 * 2 == 805306368
    parts = flops_trinity_mini.matmul_parameters(model)
    assert sum(parts) + flops_trinity_mini.router_parameters(model) \
        == pytest.approx(174.5e6, rel=2e-3)
    window = flops_trinity_mini.train_window_flops(model, args)
    floor = 6 * 4096 * sum(parts)
    assert floor < window < 1.12 * floor
    windows = args['batch_size'] * args['sgd_steps_per_chunk']
    assert 0.10 < experts['sgd_flops'] / (windows * window) < 0.16
    assert 0.2 < attention['sgd_flops'] / (windows * window) < 0.32
    # a ply reads the attention weights of five layers and, a sequence, a
    # circle of 2,048 rows of K and of V on four layers and the counter's
    # rows on the full one
    weights = 5 * 6815744 * 2
    circles = 4 * 2048 * 128 * 2 * 2
    a_ply = attention['rollout_bytes'] / 256
    assert weights + 32 * circles < a_ply < weights + 32 * (
        circles + 8192 * 128 * 2 * 2)
    burn = dict(args, burn_in_steps=64)
    assert flops_trinity_mini.train_window_flops(model, burn) > window


@pytest.mark.parametrize('case, fill', traced_fill.CASES)
def test_a_roofline_counts_the_rows_its_traced_chunk_had_reached(
        cell, tmp_path, case, fill):
    """As ``test_bench_ouro``'s, through ``gqa_attention_roofline``'s own
    file: 100% at 0.7, 1.0 and 1.3 times the games' mean ply index (2,559)
    for a dispatch whose time is the chip's least for the rows its own
    counters reached, the sliding layers' rows ``min(p + 1, 2,048)`` and the
    full layer's ``p + 1``; the share of what is required for one that reads
    every row of its buffers; nothing for one whose chunk was never
    recorded."""
    manifest, config, traffic, args = cell
    spec = manifest.load_metric('gqa_attention_roofline')
    assert spec['reader'] == 'traced_fill_roofline'
    assert spec['args'] == {
        'module': 'jit_fused_pipeline_train', 'span': 'chunk_plies',
        'scope': 'gqa_attention',
        'rows': 'benchmark.flops_trinity_mini:rows_seen_at',
        'rollout': 'config.model.gqa_attention_rollout',
        'sgd_flops': 'config.model.gqa_attention_sgd_flops'}
    got, share, old = traced_fill.roofline_case(
        tmp_path, manifest, manifest.cell(CELL), config, traffic, args,
        'gqa_attention_roofline', case, fill, mean_index=2559)
    if case == 'unpaired':
        assert got is None
        return
    assert got['value'] == pytest.approx(share, rel=1e-6)
    assert got['analytic_mean_value'] == pytest.approx(old, rel=1e-6)
    rows = got['executions'][0]['fill_rows']
    assert rows['full'] == pytest.approx(fill * 2559 + 1, abs=0.51)
    assert rows['sliding'] <= 2048
    if case == 'time_follows_fill':
        assert share == pytest.approx(100.0)
        assert (old > 105) == (fill == 0.7)
    else:
        assert 60 < got['value'] < 80


def test_each_new_metric_names_a_reader_and_the_cell(cell):
    manifest = cell[0]
    contracts.a_cells_own_metrics_are_its_entries(manifest, CELL)
    assert len(NEW) == 5 and len(SHARED_BY_THE_EXPERT_CELLS) == 4
    for name in NEW:
        contracts.a_cells_own_metric(manifest, CELL, name)
    for name in SHARED_BY_THE_EXPERT_CELLS:
        contracts.an_expert_cells_shared_metric(manifest, name)
    reported = manifest.metrics_of(CELL, 'per_layer')
    assert 'trinity_optimizer_ms' not in manifest.metrics
    for name in ('fused_program_ms', 'env_steps_per_s', 'episodes_per_s',
                 'plies_per_episode', 'chunk_max_ms', 'device_idle',
                 'hbm_peak_gib'):
        assert name in reported
    assert not [name for name in reported if name.startswith('trunk_')]
    # one name a shared reading: no twin of the cell's own is left
    for twin in ('sgd_ms', 'rollout_ms', 'train_mfu'):
        assert 'trinity_' + twin not in manifest.metrics
        assert twin in reported
    for name, scope in (('moe_experts_ms', 'moe_experts'),
                        ('moe_route_ms', 'moe_route'),
                        ('gqa_attention_ms', 'gqa_attention'),
                        ('optimizer_ms', 'optimizer')):
        spec = manifest.load_metric(name)
        # the grouped products reach the trace under the compiler's own
        # names and without a scope path: the experts' reader counts both
        assert spec['reader'] == (
            'trace_inner_scope_kernels_time' if name == 'moe_experts_ms'
            else 'trace_inner_scope_time')
        assert spec['args']['scope'] == scope
    assert manifest.load_metric('moe_experts_ms')['args']['kernels'] \
        == ['ragged-dot']
    for name in ('moe_rows_held_share', 'moe_load_max_over_mean',
                 'expert_short_buffer_share'):
        assert manifest.load_metric(name)['reader'] == 'program_counter_ratio'
    # named for no net, and no parameter count in its text: both cells' is it
    what = manifest.load_metric('optimizer_ms')['what']
    assert 'optimizer' in what and '603' not in what and '594' not in what


# one execution of the module, 0..100 us: a while that holds a fusion under
# the scope (10 us), a grouped product under the compiler's own name and
# path (30 us) with its offsets' kernel (2 us), and a fusion of another
# scope (20 us); a second module holds a grouped product that is not ours
KERNEL_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "jit_fused_pipeline_train(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = (s32[]) while(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/moe_experts/mul" } } }
  event_metadata { key: 4 value { id: 4 name: "%ragged-dot-none.5 = bf16[64,8] custom-call(...)" stats { metadata_id: 1 str_value: "ragged-dot-none" } } }
  event_metadata { key: 5 value { id: 5 name: "%ragged-dot-metadata = (s32[17]) custom-call(...)" stats { metadata_id: 1 str_value: "ragged-dot-metadata" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[64] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/moe_route/gather" } } }
  event_metadata { key: 7 value { id: 7 name: "jit_other(3)" } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 7 offset_ps: 110000000 duration_ps: 40000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 80000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 17000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 30000000 }
    events { metadata_id: 6 offset_ps: 55000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 115000000 duration_ps: 30000000 } } }
"""


def test_the_experts_time_holds_the_grouped_products_the_scope_lost(
        tmp_path):
    """Microseconds by hand: the scope alone reads 10; with the kernels the
    compiler named itself, 42; another module's kernel and another scope's
    fusion are left out; no kernel and no scope, nothing to read."""
    from jax.profiler import ProfileData
    from benchmark.readers import (trace_inner_scope_kernels_time,
                                   trace_inner_scope_time)
    path = str(tmp_path / 'host.xplane.pb')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(KERNEL_TRACE))

    class Run:
        trace = {'path': path, 'window': (0, 10 ** 9)}
    module = 'jit_fused_pipeline_train'
    alone = trace_inner_scope_time.read(Run, module, 'moe_experts')
    assert alone['value'] == pytest.approx(10e-3)
    both = trace_inner_scope_kernels_time.read(Run, module, 'moe_experts',
                                               ['ragged-dot'])
    assert both['value'] == pytest.approx(42e-3) and both['samples'] == 1
    assert both['kernels_ms'] == pytest.approx(32e-3)
    assert trace_inner_scope_kernels_time.read(
        Run, module, 'moe_experts', [])['value'] == pytest.approx(10e-3)
    assert trace_inner_scope_kernels_time.read(
        Run, module, 'absent', ['no-such-kernel']) is None
    Run.trace = None
    assert trace_inner_scope_kernels_time.read(
        Run, module, 'moe_experts', ['ragged-dot']) is None


def test_the_counter_ratios_read_the_pipelines_sums(cell, monkeypatch):
    """``program_counter_ratio`` with the new metrics' arguments over two
    records of the ``host_block`` span as ``FusedPipeline._parse`` sets it."""
    from benchmark.readers import program_counter_ratio
    from benchmark.record import Run
    manifest, config, traffic, args = cell
    attrs = lambda k: {'moe_rows_held': 1000.0 * k,
                       'moe_rows_routed': 8000.0 * k,
                       'moe_rows_fullest': 40.0 * k,
                       'moe_dispatches': 8.0 * k,
                       'moe_dispatches_short': 8.0 * k - (k > 1)}
    ring = [{'name': 'host_block', 't1': 1.0, 'attrs': attrs(1)},
            {'name': 'host_block', 't1': 2.0, 'attrs': attrs(3)}]
    monkeypatch.setattr(program_counter_ratio, 'ring', lambda: ring)
    run = Run(manifest.cell(CELL), config, traffic, args, {}, (1.0, 2.5))
    read = lambda name: program_counter_ratio.read(
        run, **manifest.load_metric(name)['args'])
    assert read('moe_rows_held_share') == 12.5
    assert read('moe_load_max_over_mean') == 80.0 * 64 / 2000.0
    # 16 layer-steps in the window, one through the every-pair buffer
    assert read('expert_short_buffer_share') == 100 * 15.0 / 16.0
    # a program without the sums (the parent's): nothing to read, no error
    for record in ring:
        record['attrs'] = {'plies': 1}
    assert read('moe_rows_held_share') is None
    assert read('expert_short_buffer_share') is None


# -- the checks and a planted fault for each, at the rehearsal's size ----------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    import jax.numpy as jnp
    from benchmark import checks
    dest = str(tmp_path_factory.mktemp('trinity_mini_tiny'))
    rehearse.build_root(Manifest(), dest, CELL)
    laid = Manifest(dest)
    config = laid.load_config('trinity_mini')
    traffic = laid.load_traffic('moe_selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'], seed=5)
    variables = checks.starting_variables(config, train_args)
    module = checks.build_module(config, train_args)
    assert module.dtype == jnp.bfloat16
    return config, train_args, module, variables


def _over(config, stats, check, limits):
    tolerance = config['tolerance']
    out = []
    for name in limits:
        name, op = name if isinstance(name, tuple) else (name, '<=')
        limit = tolerance['%s_%s' % (check, name)]
        if (stats[name] > limit) if op == '<=' else (stats[name] < limit):
            out.append(name)
    return out


def test_the_seeded_batch_holds_its_legal_set_as_bits(tiny):
    config, train_args, _module, _variables = tiny
    batch, windows = ct.seeded_batch(config, 3, train_args)
    assert len(windows) == train_args['batch_size'] == 2
    short, whole = (w['valid'] for w in windows)
    assert 0 < short.sum() < len(short) == train_args['forward_steps']
    assert whole.all()
    ids = config['model']['vocab']
    assert batch['action_mask'].dtype == np.uint8
    assert batch['action_mask'].shape == (2, len(short), 1, ids // 8)
    unpacked = np.unpackbits(batch['action_mask'], axis=-1,
                             bitorder='little')[..., :ids]
    for b, window in enumerate(windows):
        assert ((window['action_mask'] > 0) == unpacked[b, :, 0]).all()
    assert (unpacked[0, short == 0] == 1).all()       # padding: all illegal
    assert unpacked[1, 1, 0, -64:].all() and not unpacked[1, 0, 0].any()


@pytest.mark.parametrize('seed', [0, 1, 2938100001 % 2**31])
def test_the_rollout_checks_games_end_where_it_puts_them(tiny, seed):
    config, _train_args, _module, _variables = tiny
    plies = int(config['rollout_plies'])
    whole, early, late, past = ct.first_lengths(config, seed, plies)
    assert whole > plies
    assert 0 < early < late < plies and early < plies // 4 <= late
    assert config['model']['window_size'] < past < plies
    assert len({plies - early, plies - late, plies - past, plies}) == 4


@pytest.mark.parametrize('fault', ['none', 'experts_sum_dropped',
                                   'window_ignored'])
def test_the_forward_check_tells_the_faults_apart(tiny, fault, monkeypatch):
    import jax.numpy as jnp
    from handyrl_tpu.models import trinity
    config, _train_args, module, variables = tiny
    if fault == 'experts_sum_dropped':
        monkeypatch.setattr(
            trinity.TrinityBlock, '_experts_grouped',
            lambda self, m, slot, w: (jnp.zeros(m.shape, jnp.float32),
                                      jnp.int32(0)))
    elif fault == 'window_ignored':
        module = module.clone(window_size=10 ** 6)
    stats = ct.forward_errors(config, module, variables, 11)
    over = _over(config, stats, 'forward', ct.FORWARD_LIMITS)
    if fault == 'none':
        assert not over, stats
    else:
        assert 'logits_rms_rel_to_logit_rms' in over, stats


@pytest.mark.parametrize('control', ['stated'] + list(ct.CONTROLS))
def test_the_rollout_check_tells_the_controls_apart(tiny, control):
    config, train_args, module, variables = tiny
    stats = ct.rollout_errors(config, module, variables, 11, train_args,
                              **ct.CONTROLS.get(control, {}))
    assert stats['resets'] >= 3 and stats['distinct_counters'] == 4
    assert stats['wrapped_plies'] > 0 and stats['after_reset_plies'] > 0
    over = _over(config, stats, 'rollout', ct.ROLLOUT_LIMITS)
    if control == 'stated':
        assert not over, stats
    else:
        assert 'wrapped_logits_rms_rel_to_logit_rms' in over, stats


@pytest.mark.parametrize('fault', ['none', 'small_leaf_unmoved',
                                   'router_moved', 'bias_unmoved'])
def test_the_step_check_catches_what_adam_and_the_rule_must_do(
        tiny, fault, monkeypatch):
    """Planted in the program's own step: a small leaf the optimizer left
    where it was, a router that Adam's weight decay moved, and a ``b`` that
    no rule moved. Each trips the number that is there for it."""
    from handyrl_tpu.ops import train_step
    config, train_args, module, variables = tiny
    real = train_step._update_core

    def planted(*args, **kw):
        update = real(*args, **kw)

        def step(state, batch, lr):
            new, metrics = update(state, batch, lr)
            params = dict(new.params['params'])
            if fault == 'small_leaf_unmoved':
                old = state.params['params']
                params['value'] = old['value']
                for name, leaf in params.items():
                    if name.startswith('layer_'):
                        params[name] = dict(leaf, **{
                            k: old[name][k] for k in leaf if 'norm' in k})
            elif fault == 'router_moved':
                params['layer_1'] = dict(
                    params['layer_1'],
                    router=params['layer_1']['router'] + lr)
            elif fault == 'bias_unmoved':
                params['layer_2'] = dict(
                    params['layer_2'], router_bias=state.params['params'][
                        'layer_2']['router_bias'])
            return new._replace(params={'params': params}), metrics
        return step
    if fault != 'none':
        monkeypatch.setattr(train_step, '_update_core', planted)
    # (the module is frozen: what post_update does is the program's)
    stats = ct.step_errors(config, module, variables, 11, train_args)
    over = _over(config, stats, 'step', ct.STEP_LIMITS)
    if fault == 'none':
        assert not over, stats
        assert stats['router_moved_max_abs'] == 0
        assert stats['bias_err_max_abs'] < 1e-6
        assert stats['bias_signs_against_reference'] == 0
        assert stats['rows_dropped'] == 0
    elif fault == 'small_leaf_unmoved':
        # the norms' weights and the value row are 3% of the elements: the
        # limit over every leaf does not see them, the small leaves' does,
        # and the worst leaf reads 1
        assert 'small_change_err_rel_to_change' in over, stats
        assert 'change_err_worst_leaf' in over, stats
        assert 'change_err_rel_to_change' not in over, stats
    elif fault == 'router_moved':
        assert stats['router_moved_max_abs'] > 0
    else:
        # an unmoved b has the rule's form (every sign 0), not its signs
        assert stats['bias_signs_against_reference'] > 0
