"""The ``smallthinker`` configuration's own files: what its file states
against the catalog's ``config`` and against the program, the manifest's
contracts and pins with the seventh cell, its FLOP and byte counts against a
count by brute force and against the numbers the metrics read, each new
metric's reader on a synthetic trace or span ring, and a planted fault or
control for each new check at the rehearsal's size. (That the cell rehearses
with ``correct`` true is test_bench_rehearsal's, which runs every cell of
the manifest.)

Every test that takes ``cell`` runs twice: on the checkout and on the root
later PRs will have grown it into (``conftest.py``'s ``either_root``). What
this configuration brought is pinned by NAME or as a PREFIX, never from the
end of a list: ``test_the_entries_are_appended_and_nothing_before_them_is_
edited`` as PR 43 wrote it (``raw['per_layer'][-9:]``) passes on the first
root and fails on the second, which is the point. On the checkout alone:
the tests that take ``tiny`` (they lay out a rehearsal root of the checkout
and run the checks at its size) and those that take neither fixture."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import checks_smallthinker as cs
from benchmark import flops_smallthinker, rehearse
from benchmark.manifest import Manifest

from tests.benchmark import contracts, traced_fill

CELL = 'smallthinker.moe_selfplay_8k'
SOURCE = ('https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/'
          'blob/main/config.json')
# PowerInfer/SmallThinker-21BA3B-Instruct config.json as the catalog beside
# the model-configs guide holds it: every key of its ``config``
LAYOUT = [0, 1, 1, 1] * 13
CATALOG = {'head_dim': 128, 'hidden_size': 2560,
           'max_position_embeddings': 16384,
           'model_name': 'smallthinker_21b_instruct',
           'moe_ffn_hidden_size': 768, 'moe_num_active_primary_experts': 6,
           'moe_num_primary_experts': 64,
           'moe_primary_router_apply_softmax': True, 'norm_topk_prob': True,
           'num_attention_heads': 28, 'num_hidden_layers': 52,
           'num_key_value_heads': 4, 'rms_norm_eps': 1e-06,
           'rope_layout': LAYOUT, 'rope_scaling': None,
           'rope_theta': 1500000, 'sliding_window_layout': LAYOUT,
           'sliding_window_size': 4096, 'tie_word_embeddings': False,
           'vocab_size': 151936}
CUT = {'num_hidden_layers': (52, 4), 'moe_num_primary_experts': (64, 16),
       'num_attention_heads': (28, 7), 'num_key_value_heads': (4, 1),
       'vocab_size': (151936, 37984)}
NEW = contracts.OWN[CELL]


@pytest.fixture(scope='module')
def cell(either_root):
    manifest = either_root
    config = manifest.load_config('smallthinker')
    traffic = manifest.load_traffic('moe_selfplay_8k')
    train_args = dict(traffic['train_args'], **config['train_args'])
    return manifest, config, traffic, train_args


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_on_the_checkout_with_the_seventh_cell(contract, cell):
    manifest = cell[0]
    assert list(manifest.cells)[6] == CELL and len(manifest.cells) >= 7
    assert manifest.cells[CELL]['chips'] == 1
    contract(manifest)


@pytest.mark.parametrize('pin', contracts.PINS, ids=contracts.pin_id)
def test_pin_holds_with_the_seventh_cell(pin, cell):
    fn, args = pin
    fn(cell[0], *args)


def test_the_entries_are_appended_and_nothing_before_them_is_edited(cell):
    """By name and by prefix: what PR 43 appended is found wherever it
    stands, and what stood before it stands there still."""
    manifest = cell[0]
    raw = manifest.raw
    assert manifest.configs['smallthinker']['source'] == SOURCE
    contracts.the_first_seven_cells_and_five_configurations(manifest)
    assert manifest.cells[CELL] == dict(
        manifest.cells[CELL], name=CELL, config='smallthinker',
        traffic='moe_selfplay_8k', chips=1)
    # the nine stand in ``per_layer`` in this order (a subsequence, not a
    # tail), each listing this cell alone
    names = [entry['name'] for entry in raw['per_layer']]
    assert [name for name in names if name in NEW] == NEW
    for name in NEW:
        assert manifest.metrics[name]['workloads'] == [CELL]
    # the entries that list the other trunk cells alone list them still, and
    # what the two expert cells share lists both
    for other, theirs in contracts.OWN.items():
        for name in theirs:
            assert manifest.metrics[name]['workloads'] == [other]
    for name in contracts.EXPERT_SHARED:
        assert CELL in manifest.metrics[name]['workloads']


def test_the_file_holds_the_catalogs_config_and_lists_each_cut(cell):
    manifest, config, _traffic, _args = cell
    for key, value in CATALOG.items():
        if key in CUT:
            published, held = CUT[key]
            assert value == published == config['published'][key]
            assert config[key] == held < published
        else:
            assert config[key] == value, key
    entry = manifest.configs['smallthinker']
    assert sorted(entry['reduced']) == sorted(CUT) == sorted(config['reduced'])
    # no width is among the cuts
    for key in config['reduced']:
        assert not key.endswith(('_dim', '_rank', '_size')) \
            or key == 'vocab_size'
    assert 'four chips (one v5e-4 host) share each layer' \
        in config['deployment']
    assert config['weights'] == {'seeded': True,
                                 'why': config['weights']['why']}
    assumed = ' '.join(config['assumed'])
    for word in ('NORMED layer input', '4,096 keys', 'rotate-half',
                 'weights start at 1', 'primary experts are the only'):
        assert word in assumed, word
    departures = ' '.join(config['departures_from_source'])
    for word in ('value row', 'V-trace', 'router takes no gradient',
                 'no balancing rule', 'partial sums', 'param_scale 256',
                 'burn_in_steps 0', 'max_positions 8192'):
        assert word in departures, word
    model, net = config['model'], config['env_args']['net']
    for key in net:
        assert model[key] == net[key], key
    assert (model['hidden_size'], model['expert_size'], model['head_dim'],
            model['experts_per_token'], model['experts_published'],
            model['window_size'], model['rope_theta'], model['norm_eps']) \
        == (2560, 768, 128, 6, 64, 4096, 1.5e6, 1e-6)
    assert model['layer_types'] == ['global', 'window', 'window', 'window']
    assert model['layer_types'] == [
        ('global', 'window')[kind] for kind in LAYOUT[:4]]
    assert model['experts_held'] == list(range(16))
    assert (model['heads_held'], model['kv_heads_held'], model['vocab']) \
        == (7, 1, 37984) == (config['num_attention_heads'],
                             config['num_key_value_heads'],
                             config['vocab_size'])
    assert model['held_expert_slots'] == 16 * 4
    assert model['param_scale'] == 2 ** round(np.log2(model['param_scale']))
    env = config['env_args']
    assert (env['env'], env['min_steps'], env['max_steps'], env['ids'],
            env['first_ply_ids'], env['net_name']) \
        == ('ByteGame', 4096, 8192, 37984, 64, 'SmallThinkerNet')
    assert [c['name'] for c in config['checks']] == [
        'forward_matches_reference', 'rollout_matches_reference',
        'step_matches_reference', 'vtrace_matches_reference']
    assert (config['forward_windows'], config['forward_positions'],
            config['rollout_envs']) == (1, 8192, 4)
    assert config['rollout_plies'] >= 4608


@pytest.mark.parametrize('key', ['parameters', 'defaults'])
def test_the_program_builds_the_net_the_file_states(cell, key):
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.smallthinker import (PUBLISHED_LAYERS,
                                                 SmallThinkerNet)
    _manifest, config, _traffic, _args = cell
    net = make_env(config['env_args']).net()
    assert isinstance(net, SmallThinkerNet)
    if key == 'defaults':   # the module's defaults ARE the published counts
        plain = SmallThinkerNet()
        assert (plain.hidden_size, plain.heads_held, plain.kv_heads_held,
                plain.head_dim, plain.expert_size, plain.experts_per_token,
                plain.vocab, len(plain.held), plain.window_size,
                plain.rope_theta, plain.norm_eps, plain.param_scale) \
            == (2560, 28, 4, 128, 768, 6, 151936, 64, 4096, 1.5e6, 1e-6, 1.0)
        assert plain.layer_types == PUBLISHED_LAYERS == tuple(
            ('global', 'window')[kind] for kind in LAYOUT)
        return
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) \
        == config['model']['parameters'] == 593617920
    # ISSUE 43's table: four layers of 99.78M, 2 x 97.24M of embedding and
    # head, the last norm and the value row
    layer = 2 * 2560 * 896 + 2 * 2560 * 128 + 2 * 2560 + 2560 * 64 \
        + 16 * 3 * 2560 * 768
    assert layer == 99783680
    assert 4 * layer + 2 * 37984 * 2560 + 2 * 2560 == 593617920
    # the cache: 8,192 rows on the global layer, circles of 4,096 on three
    hidden = jax.eval_shape(lambda: net.init_hidden((1,)))
    assert [k.shape for k in hidden['k']] \
        == [(1, 8192, 128)] + [(1, 4096, 128)] * 3


def test_the_cell_is_the_issues_traffic(cell):
    manifest, config, traffic, args = cell
    want = {'generation_envs': 16, 'eval_envs': 4, 'device_chunk_steps': 256,
            'forward_steps': 8192, 'burn_in_steps': 0, 'batch_size': 1,
            'sgd_steps_per_chunk': 2, 'replay_windows_per_episode': 1,
            'update_episodes': 4, 'checkpoint_interval': 64,
            'compute_dtype': 'bfloat16', 'gamma': 0.99,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE'}
    assert {k: args[k] for k in want} == want
    # the issue's numbers, or its permitted fallbacks (``fallbacks``)
    assert args['maximum_episodes'] in (32, 24)
    assert args['minimum_episodes'] in (8, 6)
    assert args['guard'] == {'nonfinite_policy': 'abort'}
    assert args['telemetry'] == {'retrace': 'abort'}
    assert traffic['replay'] == {
        'sgd_steps_per_chunk': 2, 'batch_size': 1,
        'trained_windows_per_chunk': 2, 'plies_per_chunk': 256 * 16}
    window = traffic['window']
    assert (window['skip_dispatches'], window['open_after'],
            window['trace_seconds']) == (3, {'epoch_boundary': 1}, 1)
    assert traffic['fallbacks']
    # everything else is moe_selfplay_4k's
    other = manifest.load_traffic('moe_selfplay_4k')
    moved = {'forward_steps', 'batch_size', 'maximum_episodes',
             'minimum_episodes'}
    for key, value in other['train_args'].items():
        if key not in moved:
            assert traffic['train_args'][key] == value, key
    # 768 rows a held expert a layer a step at the even share
    assert 8192 * 6 // 64 == 768
    assert flops_smallthinker.held_per_position(config['model']) == 1.5


# -- FLOPs and bytes --------------------------------------------------------------
def _pairs_by_brute_force(kind, window, positions, first):
    total = 0
    for n in range(first, first + positions):
        seen = [m for m in range(first, n + 1)
                if kind == 'global' or n - window < m]
        total += len(seen)
    return total


@pytest.mark.parametrize('kind', ['window', 'global'])
@pytest.mark.parametrize('positions,first', [(40, 0), (16, 0), (37, 9)])
def test_attention_pairs_are_the_sets_the_equations_name(kind, positions,
                                                         first):
    assert flops_smallthinker.attention_pairs(
        {'window_size': 16}, kind, positions, first) \
        == _pairs_by_brute_force(kind, 16, positions, first)


@pytest.mark.parametrize('kind', ['window', 'global'])
def test_the_rows_a_decode_query_sees_are_counted_ply_by_ply(kind):
    """``mean_rows_seen`` against every ply of every length, weighted as
    the env draws them (log-uniform lengths: weight 1 / L)."""
    model = {'window_size': 16, 'min_steps': 12, 'max_steps': 40}
    rows = plies = 0.0
    for length in range(12, 41):
        for p in range(length):
            seen = p + 1 if kind == 'global' else min(p + 1, 16)
            rows += seen / length
            plies += 1 / length
    assert flops_smallthinker.mean_rows_seen(model, kind) \
        == pytest.approx(rows / plies)


def test_the_counts_a_metric_reads_are_the_functions(cell):
    _manifest, config, _traffic, args = cell
    model = config['model']
    experts = flops_smallthinker.reglu_experts_scope(model, args)
    window = flops_smallthinker.window_attention_scope(model, args)
    whole = flops_smallthinker.global_attention_scope(model, args)
    for scope, counts in (('reglu_experts', experts),
                          ('window_attention', window),
                          ('global_attention', whole)):
        assert model[scope + '_sgd_flops'] == counts['sgd_flops']
        if scope == 'reglu_experts':    # the experts' bytes follow no fill
            assert model[scope + '_rollout_bytes'] == counts['rollout_bytes']
        else:                           # held SPLIT (PR 52)
            assert model[scope + '_rollout'] == counts['rollout']
    # 2 windows of 8,192 positions, 1.5 held experts a position and layer
    assert experts['sgd_flops'] == int(
        3 * 2 * 2 * 8192 * 4 * 1.5 * 3 * 2560 * 768)
    # a ply reads the 64 held experts' bfloat16 weights once: 755 MB
    assert experts['rollout_bytes'] == 256 * 64 * 3 * 2560 * 768 * 2
    assert 64 * 3 * 2560 * 768 * 2 == 754974720
    parts = flops_smallthinker.matmul_parameters(model)
    assert parts[0] == 4 * 5242880 and parts[2] == 2560 * 37984 + 2560
    # ISSUE 43: 356 MFLOP a position forward, of it the head 194, the
    # experts 71, the scores 48, the projections 42
    forward = flops_smallthinker.forward_flops(model, 8192) / 8192
    assert forward == pytest.approx(356.2e6, rel=1e-3)
    assert 2 * parts[2] == pytest.approx(194.5e6, rel=1e-3)
    assert 2 * parts[1] == pytest.approx(70.8e6, rel=1e-3)
    assert 2 * parts[0] == pytest.approx(41.9e6, rel=2e-3)
    assert flops_smallthinker.attention_flops(model, 8192) / 8192 \
        == pytest.approx(47.7e6, rel=1e-3)
    step = flops_smallthinker.train_window_flops(model, args)
    assert step == pytest.approx(8.73e12, rel=1e-3)
    floor = 6 * 8192 * sum(parts)
    assert floor < step < 1.2 * floor
    # the two attention scopes split the layers and the pairs between them
    pairs = lambda kind: flops_smallthinker.attention_flops(model, 8192, kind)
    assert pairs('window') + pairs('global') == pairs(None)
    assert window['sgd_flops'] == 3 * 2 * (2 * 8192 * 3 * 5242880
                                           + pairs('window'))
    assert whole['sgd_flops'] == 3 * 2 * (2 * 8192 * 5242880
                                          + pairs('global'))
    # a ply reads a layer's attention weights and, a sequence, the rows a
    # query sees: min(position + 1, 4,096) on a window layer, the counter's
    # rows on the global one, each at its mean over the games' plies
    weights, row = 5242880 * 2, 128 * 2 * 2
    seen = flops_smallthinker.mean_rows_seen(model, 'window')
    assert 2048 < seen < 3072      # (L / 2 at L = 4,096; 3 L / 8 at 8,192)
    assert window['rollout_bytes'] == int(
        256 * 3 * (weights + 32 * seen * row))
    deep = flops_smallthinker.mean_rows_seen(model, 'global')
    mean_len = 4096 / math.log(2)
    assert mean_len / 2 < deep < 8192 / 2 + 1
    assert whole['rollout_bytes'] == int(256 * (weights + 32 * deep * row))
    # the split: a ply's weights, a row of every sequence over the kind's
    # layers; at the analytic mean's rows it is the number held until PR 52
    assert window['rollout'] == {
        'plies': 256, 'ply_bytes': 3 * weights,
        'row_bytes': {'window': 3 * 32 * row},
        'analytic_rows': {'window': seen}}
    assert whole['rollout'] == {
        'plies': 256, 'ply_bytes': weights, 'row_bytes': {'global': 32 * row},
        'analytic_rows': {'global': deep}}
    assert window['rollout_bytes'] == 41734407697 \
        == int(flops_smallthinker.chunk_bytes(window['rollout']))
    assert whole['rollout_bytes'] == 15571353600 \
        == int(flops_smallthinker.chunk_bytes(whole['rollout']))
    at = [0, 9, 4095, 4096, 8191, 9000]
    assert list(flops_smallthinker.rows_seen_at(model, 'window', at)) \
        == [1, 10, 4096, 4096, 4096, 4096]
    assert list(flops_smallthinker.rows_seen_at(model, 'global', at)) \
        == [1, 10, 4096, 4097, 8192, 8192]
    burn = dict(args, burn_in_steps=64)
    assert flops_smallthinker.train_window_flops(model, burn) > step


# -- the new metrics -----------------------------------------------------------------
def test_each_new_metric_names_a_reader_and_the_cell(cell):
    manifest = cell[0]
    reported = manifest.metrics_of(CELL, 'per_layer')
    assert manifest.metrics_of(CELL, 'end_to_end') \
        == ['train_windows_per_s', 'setup_s']
    for name in NEW:
        contracts.a_cells_own_metric(manifest, CELL, name)
        assert name in reported
    # every shared reading under its one name, and no other trunk cell's own
    for name in contracts.SHARED + ['fused_program_ms', 'device_idle',
                                    'hbm_peak_gib', 'env_steps_per_s']:
        assert name in reported
    for name in contracts.EXPERT_SHARED:
        assert name in reported
    for other, names in contracts.OWN.items():
        assert other == CELL or not set(names) & set(reported)
    for name, scope in (('pre_route_ms', 'pre_route'),
                        ('expert_dispatch_ms', 'expert_dispatch'),
                        ('window_attention_ms', 'window_attention'),
                        ('global_attention_ms', 'global_attention')):
        spec = manifest.load_metric(name)
        assert spec['reader'] == 'trace_inner_scope_time'
        assert spec['args'] == {'module': 'jit_fused_pipeline_train',
                                'scope': scope, 'stat': 'median'}
    spec = manifest.load_metric('reglu_experts_ms')
    assert spec['reader'] == 'trace_inner_scope_kernels_time'
    assert (spec['args']['scope'], spec['args']['kernels']) \
        == ('reglu_experts', ['ragged-dot'])
    spec = manifest.load_metric('reglu_experts_roofline')
    assert spec['reader'] == 'derived'
    for word in ('config.model.reglu_experts_sgd_flops',
                 'config.model.reglu_experts_rollout_bytes',
                 'reglu_experts_ms'):
        assert word in spec['args']['expr']
    for scope in ('window_attention', 'global_attention'):
        spec = manifest.load_metric(scope + '_roofline')
        assert spec['reader'] == 'traced_fill_roofline'
        assert spec['args'] == {
            'module': 'jit_fused_pipeline_train', 'span': 'chunk_plies',
            'scope': scope,
            'rows': 'benchmark.flops_smallthinker:rows_seen_at',
            'rollout': 'config.model.%s_rollout' % scope,
            'sgd_flops': 'config.model.%s_sgd_flops' % scope}
        assert 'TRACED plies' in spec['what']
    for scope in ('reglu_experts', 'window_attention', 'global_attention'):
        spec = manifest.load_metric(scope + '_roofline')
        assert 'UPPER bound' in spec['what']
        assert manifest.metrics[scope + '_roofline']['unit'] == '%'
    spec = manifest.load_metric('window_hidden_position_share')
    assert spec['reader'] == 'program_counter_ratio'
    assert spec['args'] == {'stage': 'host_block',
                            'numerator': 'window_positions_hidden',
                            'denominator': 'window_positions_valid',
                            'scale': 100}
    assert manifest.load_config('smallthinker')['flops'] \
        == 'benchmark.flops_smallthinker:train_window_flops'


# one execution of the module, 0..100 us: a while that holds a fusion under
# each of the net's five scopes (one of them with ``state_update`` nested),
# a grouped product under the compiler's own name, and a fusion of Trinity's
# scope, which is none of ours
SCOPE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "jit_fused_pipeline_train(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = (s32[]) while(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[64,64] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/pre_route/top_k" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/window_attention/dot_general" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/rollout/while/body/window_attention/state_update/scatter" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/global_attention/dot_general" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/expert_dispatch/gather" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.8 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/reglu_experts/max" } } }
  event_metadata { key: 9 value { id: 9 name: "%ragged-dot-none.5 = bf16[64,8] custom-call(...)" stats { metadata_id: 1 str_value: "ragged-dot-none" } } }
  event_metadata { key: 10 value { id: 10 name: "%fusion.10 = f32[64] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/moe_route/gather" } } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 95000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 12000000 }
    events { metadata_id: 5 offset_ps: 20000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 24000000 duration_ps: 9000000 }
    events { metadata_id: 7 offset_ps: 34000000 duration_ps: 6000000 }
    events { metadata_id: 8 offset_ps: 41000000 duration_ps: 5000000 }
    events { metadata_id: 9 offset_ps: 47000000 duration_ps: 21000000 }
    events { metadata_id: 10 offset_ps: 70000000 duration_ps: 20000000 } } }
"""


def test_the_scope_readers_read_each_of_the_five_scopes(cell, tmp_path):
    """Microseconds by hand, through each new metric's own file: the
    projections and the cache write nested under ``state_update`` are both
    ``window_attention``'s; the grouped products join ``reglu_experts``;
    Trinity's scope is nobody's here; a program without the scopes (the
    parent's) gives nothing to read and no error."""
    import importlib
    from jax.profiler import ProfileData
    manifest = cell[0]
    path = str(tmp_path / 'host.xplane.pb')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(SCOPE_TRACE))

    class Run:
        trace = {'path': path, 'window': (0, 10 ** 9)}

    def read(name):
        spec = manifest.load_metric(name)
        reader = importlib.import_module('benchmark.readers.'
                                         + spec['reader'])
        got = reader.read(Run, **spec['args'])
        return got['value'] if isinstance(got, dict) else got
    assert read('pre_route_ms') == pytest.approx(4e-3)
    assert read('window_attention_ms') == pytest.approx(15e-3)
    assert read('global_attention_ms') == pytest.approx(9e-3)
    assert read('expert_dispatch_ms') == pytest.approx(6e-3)
    assert read('reglu_experts_ms') == pytest.approx(26e-3)
    text = SCOPE_TRACE
    for scope in ('pre_route', 'window_attention', 'global_attention',
                  'expert_dispatch', 'reglu_experts'):
        text = text.replace('/' + scope + '/', '/other/')
    text = text.replace('ragged-dot', 'plain-dot')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    for name in NEW[:3] + NEW[4:5] + NEW[6:7]:
        assert read(name) is None, name
    Run.trace = None
    assert read('reglu_experts_ms') is None


def test_the_rooflines_and_the_share_read_their_numbers(cell, monkeypatch):
    """``derived`` over the experts' counts and their scope's time,
    ``program_counter_ratio`` over two records of the ``host_block`` span as
    ``FusedPipeline._parse`` sets it; the two attention rooflines read
    nothing without a trace (with one:
    ``test_a_roofline_counts_the_rows_its_traced_chunk_had_reached``)."""
    from benchmark.readers import (derived, program_counter_ratio,
                                   traced_fill_roofline)
    from benchmark.record import Run
    manifest, config, traffic, args = cell
    attrs = lambda k: {'window_positions_valid': 6000.0 * k,
                       'window_positions_hidden': 1800.0 * k,
                       # the host's own count, padding and all: not ours
                       'window_positions': 16384 * k,
                       # what both expert cells' programs count
                       'moe_rows_held': 3000.0 * k,
                       'moe_rows_routed': 12000.0 * k,
                       'moe_rows_fullest': 300.0 * k,
                       'moe_dispatches': 8.0 * k,
                       'moe_dispatches_short': 8.0 * k}
    ring = [{'name': 'host_block', 't1': 1.0, 'attrs': attrs(1)},
            {'name': 'host_block', 't1': 2.0, 'attrs': attrs(3)}]
    monkeypatch.setattr(program_counter_ratio, 'ring', lambda: ring)
    peaks = manifest.load_peaks()['TPU v5 lite']
    run = Run(manifest.cell(CELL), config, traffic, args, {}, (1.0, 2.5),
              names={'peak.' + k: v for k, v in peaks.items()
                     if k != 'source'})
    share = manifest.load_metric('window_hidden_position_share')
    assert program_counter_ratio.read(run, **share['args']) == 30.0
    # the three counters' metrics this cell lists beside ``trinity_mini``'s
    shared = lambda name: program_counter_ratio.read(
        run, **manifest.load_metric(name)['args'])
    assert shared('moe_rows_held_share') == 25.0       # the even share
    assert shared('moe_load_max_over_mean') == 600.0 * 64 / 6000.0
    assert shared('expert_short_buffer_share') == 100.0
    model = config['model']
    spec = manifest.load_metric('reglu_experts_roofline')
    assert derived.read(run, **spec['args']) is None    # no time yet
    least_ms = 1000 * (model['reglu_experts_sgd_flops'] / 197e12
                       + model['reglu_experts_rollout_bytes'] / 819e9)
    run.values['reglu_experts_ms'] = 4 * least_ms
    assert derived.read(run, **spec['args']) == pytest.approx(25.0)
    for scope in ('window_attention', 'global_attention'):
        spec = manifest.load_metric(scope + '_roofline')
        assert traced_fill_roofline.read(run, **spec['args']) is None
    # a program without the sums (the parent's): nothing to read, no error
    for record in ring:
        record['attrs'] = {'plies': 1}
    assert program_counter_ratio.read(run, **share['args']) is None
    assert shared('expert_short_buffer_share') is None


@pytest.mark.parametrize('case, fill', traced_fill.CASES)
@pytest.mark.parametrize('metric', ['window_attention_roofline',
                                    'global_attention_roofline'])
def test_a_roofline_counts_the_rows_its_traced_chunk_had_reached(
        cell, tmp_path, metric, case, fill):
    """As ``test_bench_ouro``'s: a dispatch whose time is the chip's least
    for the rows its own counters reached reads 100% at 0.7, 1.0 and 1.3
    times the games' mean ply index (3,071.5), whatever the expression of
    before PR 52 reads there; one that reads every row of its buffers reads
    what is required over that; one whose chunk was never recorded, nothing.
    A window layer's rows stop at 4,096."""
    manifest, config, traffic, args = cell
    got, share, old = traced_fill.roofline_case(
        tmp_path, manifest, manifest.cell(CELL), config, traffic, args,
        metric, case, fill, mean_index=3071.5)
    if case == 'unpaired':
        assert got is None
        return
    assert got['value'] == pytest.approx(share, rel=1e-6)
    assert got['analytic_mean_value'] == pytest.approx(old, rel=1e-6)
    (kind, rows), = got['executions'][0]['fill_rows'].items()
    assert kind == metric.split('_')[0]
    if kind == 'global':
        assert rows == pytest.approx(fill * 3071.5 + 1, abs=0.51)
    else:
        assert rows <= 4096 and (fill < 1.3 or rows > 3900)
    if case == 'time_follows_fill':
        assert share == pytest.approx(100.0)
        assert (old > 105) == (fill == 0.7)
    else:
        assert 40 < got['value'] < 90


# -- the checks and a planted fault for each, at the rehearsal's size ----------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    import jax.numpy as jnp
    from benchmark import checks
    dest = str(tmp_path_factory.mktemp('smallthinker_tiny'))
    rehearse.build_root(Manifest(), dest, CELL)
    laid = Manifest(dest)
    config = laid.load_config('smallthinker')
    traffic = laid.load_traffic('moe_selfplay_8k')
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


def test_the_rehearsal_is_the_issues_small_net(tiny):
    config, train_args, module, _variables = tiny
    assert module.layer_types == ('global', 'window', 'window')
    assert (module.hidden_size, module.heads_held, module.kv_heads_held,
            module.head_dim, len(module.held), module.experts_published,
            module.experts_per_token, module.vocab, module.window_size) \
        == (64, 7, 1, 16, 4, 16, 3, 4608, 16)
    # a training window crosses the attention's window; one window a step
    assert (train_args['forward_steps'], train_args['batch_size']) == (32, 1)
    assert config['rollout_plies'] > 2 * module.window_size
    assert config['model']['parameters'] == 716288
    with open(os.path.join(os.path.dirname(rehearse.__file__), 'rehearsal',
                           'smallthinker.json')) as f:
        assert json.load(f)['model']['parameters'] == 716288


def test_the_seeded_batch_is_one_window_that_ends_inside_its_game(tiny):
    config, train_args, _module, _variables = tiny
    batch, windows = cs.seeded_batch(config, 3, train_args)
    assert len(windows) == train_args['batch_size'] == 1
    valid = windows[0]['valid']
    assert 16 <= valid.sum() < len(valid) == train_args['forward_steps']
    ids = config['model']['vocab']
    assert batch['action_mask'].dtype == np.uint8
    assert batch['action_mask'].shape == (1, len(valid), 1, ids // 8)


@pytest.mark.parametrize('fault', ['none', 'experts_sum_dropped',
                                   'window_ignored', 'routed_late'])
def test_the_forward_check_tells_the_faults_apart(tiny, fault, monkeypatch):
    """Planted in the PROGRAM: the experts' sum dropped, the window ignored,
    and a block that takes its routing from ``N_post(h)`` after attention
    (Trinity's order under this net's name)."""
    import jax.numpy as jnp
    from handyrl_tpu.models import smallthinker
    config, _train_args, module, variables = tiny
    block = smallthinker.SmallThinkerBlock
    if fault == 'experts_sum_dropped':
        monkeypatch.setattr(
            block, 'experts_part',
            lambda self, m, routing: (jnp.zeros(m.shape, jnp.float32),
                                      jnp.int32(0)))
    elif fault == 'window_ignored':
        module = module.clone(window_size=10 ** 6)
    elif fault == 'routed_late':
        real = block._after_attention

        def late(self, x, routing):
            m32 = smallthinker._rms_norm(x, self.norm_post, self.norm_eps,
                                         jnp.float32)
            return real(self, x, self.pre_route(m32))
        monkeypatch.setattr(block, '_after_attention', late)
    stats = cs.forward_errors(config, module, variables, 11)
    over = _over(config, stats, 'forward', cs.FORWARD_LIMITS)
    if fault == 'none':
        assert not over, stats
    elif fault == 'routed_late':
        assert 'routing_agreement_share' in over, stats
    else:
        assert 'logits_rms_rel_to_logit_rms' in over, stats


# phases on the global layer: at the toy's width the logits do not separate
# them from the stated precision (the rehearsal's ``tolerance.why``); the
# step check's worst leaf does, below
@pytest.mark.parametrize('control', ['stated'] + [
    name for name in cs.CONTROLS if name != 'rotary_on_global_layer'])
def test_the_rollout_check_tells_the_controls_apart(tiny, control):
    config, train_args, module, variables = tiny
    stats = cs.rollout_errors(config, module, variables, 11, train_args,
                              **cs.CONTROLS.get(control, {}))
    assert stats['resets'] >= 3 and stats['distinct_counters'] == 4
    assert stats['wrapped_plies'] > 0 and stats['after_reset_plies'] > 0
    over = _over(config, stats, 'rollout', cs.ROLLOUT_LIMITS)
    if control == 'stated':
        assert not over, stats
    else:
        assert over, stats


def test_the_step_check_catches_phases_on_the_global_layer(tiny):
    config, train_args, module, variables = tiny
    stats = cs.step_errors(config, module, variables, 11, train_args,
                           **cs.CONTROLS['rotary_on_global_layer'])
    over = _over(config, stats, 'step', cs.STEP_LIMITS)
    assert {'grad_err_worst_leaf', 'change_err_worst_leaf'} & set(over), stats


@pytest.mark.parametrize('fault', ['none', 'small_leaf_unmoved',
                                   'router_moved', 'silu_in_the_program'])
def test_the_step_check_catches_what_the_step_must_do(tiny, fault,
                                                      monkeypatch):
    """Planted in the program's own step: a small leaf the optimizer left
    where it was, a router that Adam's weight decay moved, and SiLU where
    the source gates by ReLU."""
    import jax
    from handyrl_tpu.models import smallthinker
    from handyrl_tpu.ops import train_step
    config, train_args, module, variables = tiny
    real = train_step._update_core

    def planted(*args, **kw):
        update = real(*args, **kw)

        def step(state, batch, lr):
            new, metrics = update(state, batch, lr)
            params = dict(new.params['params'])
            old = state.params['params']
            if fault == 'small_leaf_unmoved':
                params['value'] = old['value']
                for name, leaf in params.items():
                    if name.startswith('layer_'):
                        params[name] = dict(leaf, **{
                            k: old[name][k] for k in leaf if 'norm' in k})
            else:
                params['layer_1'] = dict(
                    params['layer_1'],
                    router=params['layer_1']['router'] + lr)
            return new._replace(params={'params': params}), metrics
        return step
    if fault in ('small_leaf_unmoved', 'router_moved'):
        monkeypatch.setattr(train_step, '_update_core', planted)
    elif fault == 'silu_in_the_program':
        monkeypatch.setattr(smallthinker.SmallThinkerBlock, 'activation',
                            staticmethod(jax.nn.silu))
    stats = cs.step_errors(config, module, variables, 11, train_args)
    over = _over(config, stats, 'step', cs.STEP_LIMITS)
    if fault == 'none':
        assert not over, stats
        assert stats['router_moved_max_abs'] == 0
        assert stats['rows_dropped'] == 0
        assert stats['windows'] == 1
        assert 0 < stats['positions_hidden_share'] < 0.5
    elif fault == 'small_leaf_unmoved':
        assert 'small_change_err_rel_to_change' in over, stats
        assert 'change_err_worst_leaf' in over, stats
    elif fault == 'router_moved':
        assert stats['router_moved_max_abs'] > 0
    else:
        assert 'grad_err_rel_to_grad' in over, stats
