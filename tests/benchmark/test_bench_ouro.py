"""The ``ouro`` configuration's own files: what its file states against the
catalog's ``config`` and against the program, the manifest's contracts and
pins with the eighth cell, its FLOP and byte counts against a count by brute
force and against the numbers the metrics read, each new metric's reader on
a synthetic trace or span ring, and a planted fault or control for each check
at the rehearsal's size. (That the cell rehearses with ``correct`` true is
test_bench_rehearsal's, which runs every cell of the manifest.)

Every test that takes ``cell`` runs twice: on the checkout and on the root
later PRs will have grown it into (``conftest.py``'s ``either_root``). What
this configuration brought is pinned by NAME, never from the end of a list:
``OWN`` below is this file's own list in the form
``contracts.a_cells_own_metrics_are_its_entries`` checks (a program PR may
not edit ``contracts.py``; a ``benchmark`` PR moves it there). On the
checkout alone: the tests that take ``tiny`` (they lay out a rehearsal root
of the checkout and run the checks at its size) and those that take neither
fixture."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import checks_ouro as co
from benchmark import flops_ouro, rehearse
from benchmark.manifest import Manifest

from tests.benchmark import contracts, traced_fill

CELL = 'ouro.loop_selfplay_4k'
SOURCE = 'https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json'
# ByteDance/Ouro-2.6B config.json as the catalog beside the model-configs
# guide holds it: every key of its ``config``
CATALOG = {'head_dim': 128, 'hidden_act': 'silu', 'hidden_size': 2048,
           'intermediate_size': 5632,
           'layer_types': ['full_attention'] * 48,
           'max_position_embeddings': 65536, 'max_window_layers': 48,
           'model_type': 'ouro', 'num_attention_heads': 16,
           'num_hidden_layers': 48, 'num_key_value_heads': 16,
           'rms_norm_eps': 1e-06, 'rope_scaling': None,
           'rope_theta': 1000000, 'sliding_window': None,
           'tie_word_embeddings': False, 'total_ut_steps': 4,
           'early_exit_threshold': 1, 'use_sliding_window': False,
           'vocab_size': 49152}
CUT = {'num_hidden_layers': (48, 4), 'num_attention_heads': (16, 4),
       'num_key_value_heads': (16, 4), 'vocab_size': (49152, 12288)}
# the entries that list this cell alone, in the file's order
OWN = ['loop_attention_ms', 'loop_attention_roofline', 'trunk_mlp_ms',
       'pass_readout_ms', 'loop_decode_roofline', 'exit_entropy_share']
SCOPES = {'loop_attention_ms': 'loop_attention',
          'trunk_mlp_ms': 'trunk_mlp', 'pass_readout_ms': 'pass_readout'}


@pytest.fixture(scope='module')
def cell(either_root):
    manifest = either_root
    config = manifest.load_config('ouro')
    traffic = manifest.load_traffic('loop_selfplay_4k')
    train_args = dict(traffic['train_args'], **config['train_args'])
    return manifest, config, traffic, train_args


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_with_the_eighth_cell(contract, cell):
    manifest = cell[0]
    assert list(manifest.cells)[7] == CELL and len(manifest.cells) >= 8
    assert manifest.cells[CELL]['chips'] == 1
    contract(manifest)


@pytest.mark.parametrize('pin', contracts.PINS, ids=contracts.pin_id)
def test_pin_holds_with_the_eighth_cell(pin, cell):
    fn, args = pin
    fn(cell[0], *args)


def test_the_entries_are_appended_and_nothing_before_them_is_edited(cell):
    """By name and by prefix: what PR 46 appended is found wherever it
    stands, and what stood before it stands there still."""
    manifest = cell[0]
    raw = manifest.raw
    assert manifest.configs['ouro']['source'] == SOURCE
    contracts.the_first_seven_cells_and_five_configurations(manifest)
    assert list(manifest.configs)[5] == 'ouro'
    assert manifest.cells[CELL] == dict(
        manifest.cells[CELL], name=CELL, config='ouro',
        traffic='loop_selfplay_4k', chips=1)
    # the six stand in ``per_layer`` in this order (a subsequence, not a
    # tail), each listing this cell alone
    names = [entry['name'] for entry in raw['per_layer']]
    assert [name for name in names if name in OWN] == OWN
    for name in OWN:
        assert manifest.metrics[name]['workloads'] == [CELL]
    # the cell lists itself under no entry that was there
    for other, theirs in contracts.OWN.items():
        for name in theirs:
            assert manifest.metrics[name]['workloads'] == [other]
    for name in contracts.EXPERT_SHARED:
        assert CELL not in manifest.metrics[name]['workloads']


def test_the_cells_own_metrics_are_its_entries(cell):
    """``contracts.a_cells_own_metrics_are_its_entries`` with this file's
    list."""
    manifest = cell[0]
    alone = [entry['name'] for entry in manifest.raw['per_layer']
             if entry.get('workloads') == [CELL]]
    assert alone[:len(OWN)] == OWN
    assert manifest.metrics_of(CELL, 'end_to_end') \
        == ['train_windows_per_s', 'setup_s']
    reported = manifest.metrics_of(CELL, 'per_layer')
    for name in contracts.SHARED + OWN + ['fused_program_ms', 'device_idle',
                                          'hbm_peak_gib', 'env_steps_per_s']:
        assert name in reported, name
    for names in contracts.OWN.values():
        assert not set(names) & set(reported)
    for name in OWN:
        contracts.a_cells_own_metric(manifest, CELL, name)


def test_the_file_holds_the_catalogs_config_and_lists_each_cut(cell):
    manifest, config, _traffic, _args = cell
    for key, value in CATALOG.items():
        if key in CUT:
            published, held = CUT[key]
            assert value == published == config['published'][key]
            assert config[key] == held < published
        else:
            assert config[key] == value, key
    entry = manifest.configs['ouro']
    assert sorted(entry['reduced']) == sorted(CUT) == sorted(config['reduced'])
    for key in config['reduced']:      # no width is among the cuts
        assert not key.endswith(('_dim', '_rank', '_size')) \
            or key == 'vocab_size'
    assert 'four chips (one v5e-4 host) share each layer by heads' \
        in config['deployment']
    assert 'stages of a pipeline' in config['deployment']
    assert config['weights'] == {'seeded': True,
                                 'why': config['weights']['why']}
    assumed = ' '.join(config['assumed'])
    for word in ('four norms a layer', 'N_out', 'rotate-half', 'ONE row',
                 'beta', '0.1', 'weights start at 1'):
        assert word in assumed, word
    departures = ' '.join(config['departures_from_source'])
    for word in ('value row', 'V-trace', 'NOT the source\'s cross-entropy',
                 'LAST pass', 'never leaves the loop early', 'partial sum',
                 'param_scale 256', 'burn_in_steps 0', 'max_positions 4096'):
        assert word in departures, word
    model, net = config['model'], config['env_args']['net']
    for key in net:
        assert model[key] == net[key], key
    assert (model['hidden_size'], model['mlp_size'], model['head_dim'],
            model['passes'], model['rope_theta'], model['norm_eps']) \
        == (2048, 5632, 128, 4, 1e6, 1e-6) == (
            config['hidden_size'], config['intermediate_size'],
            config['head_dim'], config['total_ut_steps'],
            config['rope_theta'], config['rms_norm_eps'])
    assert (model['layers'], model['heads_held'], model['kv_heads_held'],
            model['vocab']) == (4, 4, 4, 12288) == (
                config['num_hidden_layers'], config['num_attention_heads'],
                config['num_key_value_heads'], config['vocab_size'])
    assert model['param_scale'] == 2 ** round(np.log2(model['param_scale']))
    env = config['env_args']
    assert (env['env'], env['min_steps'], env['max_steps'], env['ids'],
            env['first_ply_ids'], env['net_name']) \
        == ('ByteGame', 2048, 4096, 12288, 64, 'OuroNet')
    assert [c['name'] for c in config['checks']] == [
        'forward_matches_reference', 'rollout_matches_reference',
        'step_matches_reference', 'vtrace_matches_reference']
    assert (config['forward_windows'], config['forward_positions'],
            config['rollout_envs'], config['rollout_plies']) \
        == (1, 4096, 4, 4096)
    assert config['reference'] == 'benchmark.reference.ouro:forward'
    # every limit the checks hold stands in the file with its readings
    tolerance = config['tolerance']
    for check, limits in (('forward', [n for n, _op in co.FORWARD_LIMITS]),
                          ('rollout', co.ROLLOUT_LIMITS),
                          ('step', co.STEP_LIMITS)):
        for name in limits:
            assert 0 < tolerance['%s_%s' % (check, name)] < 10, name
    for control in list(co.CONTROLS) + ['8 bits']:
        assert control.replace('_', ' ') in tolerance['why'], control


@pytest.mark.parametrize('key', ['parameters', 'cache'])
def test_the_program_builds_the_net_the_file_states(cell, key):
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.ouro import OuroNet
    _manifest, config, _traffic, _args = cell
    net = make_env(config['env_args']).net()
    assert isinstance(net, OuroNet)
    if key == 'cache':
        # K and V of every (pass, layer): 4 x 4 x 2 x 512 bfloat16 = 32 KB a
        # position, 4.0 GiB for the cell's 32 sequences of 4,096 plies
        hidden = jax.eval_shape(lambda: net.init_hidden((16, 2)))
        assert [k.shape for k in hidden['k']] == [(16, 2, 16384, 512)] * 4
        assert all(k.dtype == jnp.bfloat16 for k in hidden['k'])
        size = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(hidden))
        assert size == 4 * 2 ** 30 + 32 * 4
        return
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) \
        == config['model']['parameters'] == 205559809
    assert 4 * 38805504 + 2 * 25165824 + 3 * 2048 + 1 == 205559809


def test_the_cell_is_the_issues_traffic(cell):
    manifest, _config, traffic, args = cell
    want = {'eval_envs': 4, 'device_chunk_steps': 256, 'forward_steps': 4096,
            'burn_in_steps': 0, 'sgd_steps_per_chunk': 2,
            'replay_windows_per_episode': 1, 'update_episodes': 4,
            'minimum_episodes': 8, 'maximum_episodes': 96,
            'checkpoint_interval': 64, 'compute_dtype': 'bfloat16',
            'gamma': 0.99, 'policy_target': 'VTRACE',
            'value_target': 'VTRACE'}
    assert {k: args[k] for k in want} == want
    # the issue's numbers, or its permitted fallbacks in their order
    assert (args['batch_size'], args['generation_envs']) in (
        (2, 16), (1, 16), (1, 8))
    assert args['guard'] == {'nonfinite_policy': 'abort'}
    assert args['telemetry'] == {'retrace': 'abort'}
    assert traffic['replay'] == {
        'sgd_steps_per_chunk': 2, 'batch_size': args['batch_size'],
        'trained_windows_per_chunk': 2 * args['batch_size'],
        'plies_per_chunk': 256 * args['generation_envs']}
    window = traffic['window']
    assert (window['skip_dispatches'], window['open_after'],
            window['trace_seconds']) == (3, {'epoch_boundary': 1}, 1)
    assert traffic['fallbacks'] and traffic['who']
    # everything else is selfplay_4k's
    other = manifest.load_traffic('selfplay_4k')
    moved = {'batch_size', 'generation_envs', 'maximum_episodes'}
    for key, value in other['train_args'].items():
        if key not in moved:
            assert traffic['train_args'][key] == value, key


# -- FLOPs and bytes --------------------------------------------------------------
@pytest.mark.parametrize('positions', [1, 7, 40])
def test_attention_pairs_are_the_sets_the_equations_name(positions):
    by_hand = sum(len([m for m in range(n + 1)]) for n in range(positions))
    assert flops_ouro.attention_pairs(positions) == by_hand


def test_a_position_costs_the_stack_once_a_pass_by_brute_force():
    """Every product of every (pass, layer) application counted one by one
    at a toy size against the closed forms."""
    model = {'hidden_size': 6, 'layers': 3, 'heads_held': 2,
             'kv_heads_held': 1, 'head_dim': 4, 'mlp_size': 10, 'vocab': 11,
             'passes': 4}
    positions = 9
    flops = 0
    for _t in range(model['passes']):
        for _layer in range(model['layers']):
            for n in range(positions):
                flops += 2 * 6 * (2 * 4) * 2          # W_q, W_o
                flops += 2 * 6 * (1 * 4) * 2          # W_k, W_v
                flops += 2 * 6 * 10 * 3               # the MLP's three
                flops += 2 * 2 * 4 * 2 * (n + 1)      # scores and values
        flops += positions * 2 * 6 * (11 + 2)         # head, value, gate
    assert flops_ouro.forward_flops(model, positions) == flops
    args = {'forward_steps': positions, 'burn_in_steps': 0}
    assert flops_ouro.train_window_flops(model, args) == 3 * flops
    burn = {'forward_steps': 5, 'burn_in_steps': 4}
    assert flops_ouro.train_window_flops(model, burn) \
        == 3 * flops - 2 * flops_ouro.forward_flops(model, 4)


def test_the_rows_a_decode_ply_reads_are_counted_ply_by_ply():
    model = {'max_positions': 40, 'min_steps': 12, 'max_steps': 40}
    rows = plies = 0.0
    for length in range(12, 41):        # log-uniform lengths: weight 1 / L
        for p in range(length):
            rows += (p + 1) / length
            plies += 1 / length
    assert flops_ouro.rows_written(model) == pytest.approx(rows / plies)


def test_the_counts_a_metric_reads_are_the_functions(cell):
    _manifest, config, _traffic, args = cell
    model = config['model']
    scope = flops_ouro.loop_attention_scope(model, args)
    assert model['loop_attention_sgd_flops'] == scope['sgd_flops']
    # the rollout's bytes are held SPLIT (PR 52): what a ply reads whatever
    # the caches hold, and what one more row in every sequence costs a ply
    assert model['loop_attention_rollout'] == scope['rollout']
    decode = flops_ouro.decode_rollout(model, args)
    assert model['decode_rollout'] == decode
    assert decode == {'plies': 256, 'ply_bytes': 1291849728,
                      'row_bytes': {'global': 32 * 32768},
                      'analytic_rows': {'global': 1536.5}}
    assert scope['rollout'] == dict(decode, ply_bytes=16 * 4 * 2048 * 512 * 2)
    # at the analytic mean's rows the split is the number held until PR 52
    assert scope['rollout_bytes'] == 446810816512 \
        == int(flops_ouro.chunk_bytes(scope['rollout']))
    assert flops_ouro.decode_ply_bytes(model, args) == 2902986752 \
        == int(flops_ouro.chunk_bytes(decode) / 256)
    at_mean = {'global': flops_ouro.rows_seen_at(
        model, 'global', np.full((256, 16), 1535.5))}
    assert flops_ouro.chunk_bytes(decode, at_mean) \
        == flops_ouro.chunk_bytes(decode)
    assert list(flops_ouro.rows_seen_at(model, 'global', [0, 7, 4095, 5000])) \
        == [1, 8, 4096, 4096]
    attention, mlp, readout = flops_ouro.matmul_parameters(model)
    assert attention == 16 * 4 * 2048 * 512 and mlp == 16 * 3 * 2048 * 5632
    assert readout == 4 * 2048 * (12288 + 2)
    # ISSUE 46: 1.24 GFLOP of layer products a position over four passes,
    # 0.2 of four heads
    assert 2 * (attention + mlp) == pytest.approx(1.24e9, rel=2e-3)
    assert 2 * readout == pytest.approx(0.2e9, rel=1e-2)
    step = flops_ouro.train_window_flops(model, args)
    floor = 6 * 4096 * (attention + mlp + readout)
    assert floor < step < 1.2 * floor
    # a ply reads the layers' bfloat16 weights once a PASS: 1.24 GB, the head
    # once, and the rows written so far of all 16 (pass, layer)s
    weights = 16 * 38797312 * 2
    assert weights == pytest.approx(1.24e9, rel=2e-3)
    seen = flops_ouro.rows_written(model)
    assert 2048 / 2 < seen < 4096 / 2 + 1
    sequences = 2 * args['generation_envs']
    assert flops_ouro.decode_ply_bytes(model, args) == int(
        weights + 2048 * 12289 * 2 + 16 * sequences * seen * 2048)
    assert scope['rollout_bytes'] == int(256 * 16 * (
        4 * 2048 * 512 * 2 + sequences * seen * 2048))
    windows = 2 * args['batch_size']
    assert scope['sgd_flops'] == 3 * windows * (
        2 * 4096 * attention // 1
        + flops_ouro.attention_flops(model, 4096))


# -- the new metrics -----------------------------------------------------------------
def test_each_new_metric_names_a_reader_and_the_cell(cell):
    manifest = cell[0]
    for name, scope in SCOPES.items():
        spec = manifest.load_metric(name)
        assert spec['reader'] == 'trace_inner_scope_time'
        assert spec['args'] == {'module': 'jit_fused_pipeline_train',
                                'scope': scope, 'stat': 'median'}
    common = {'module': 'jit_fused_pipeline_train', 'span': 'chunk_plies',
              'rows': 'benchmark.flops_ouro:rows_seen_at'}
    spec = manifest.load_metric('loop_attention_roofline')
    assert spec['reader'] == 'traced_fill_roofline'
    assert spec['args'] == dict(
        common, scope='loop_attention',
        rollout='config.model.loop_attention_rollout',
        sgd_flops='config.model.loop_attention_sgd_flops')
    assert 'UPPER bound' in spec['what']
    spec = manifest.load_metric('loop_decode_roofline')
    assert spec['reader'] == 'traced_fill_roofline'
    assert spec['args'] == dict(
        common, scope='rollout', scopes=['rollout', 'ingest', 'sgd', 'pack'],
        rollout='config.model.decode_rollout')
    for name in ('loop_attention_roofline', 'loop_decode_roofline'):
        assert 'TRACED plies' in manifest.load_metric(name)['what']
        assert manifest.metrics[name]['unit'] == '%'
        assert manifest.metrics[name]['layer'] == 'rollout'
    spec = manifest.load_metric('exit_entropy_share')
    assert spec['reader'] == 'program_counter_ratio'
    assert spec['args'] == {'stage': 'host_block',
                            'numerator': 'exit_entropy_nats',
                            'denominator': 'exit_entropy_max_nats',
                            'scale': 100}
    assert manifest.load_config('ouro')['flops'] \
        == 'benchmark.flops_ouro:train_window_flops'


# one execution of the module, 0..100 us: a while that holds a fusion under
# each of the net's three scopes (one of them with ``state_update`` nested)
# and a fusion of another net's scope, which is none of ours
SCOPE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "jit_fused_pipeline_train(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = (s32[]) while(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/while/body/checkpoint/loop_attention/dot_general" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/rollout/while/body/closed_call/OuroNet/while/body/loop_attention/state_update/scatter" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/while/body/checkpoint/trunk_mlp/dot_general" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/transpose(jvp(exit_weighted))/pass_readout/while/body/dot_general" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[64] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/global_attention/dot_general" } } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 95000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 12000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 24000000 duration_ps: 9000000 }
    events { metadata_id: 6 offset_ps: 34000000 duration_ps: 6000000 }
    events { metadata_id: 7 offset_ps: 70000000 duration_ps: 20000000 } } }
"""


def test_the_scope_readers_read_each_of_the_three_scopes(cell, tmp_path):
    """Microseconds by hand, through each new metric's own file: the
    projections and the cache write nested under ``state_update`` are both
    ``loop_attention``'s; another net's scope is nobody's here; a program
    without the scopes (the parent's) gives nothing to read and no error."""
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
    assert read('loop_attention_ms') == pytest.approx(15e-3)
    assert read('trunk_mlp_ms') == pytest.approx(9e-3)
    assert read('pass_readout_ms') == pytest.approx(6e-3)
    text = SCOPE_TRACE
    for scope in SCOPES.values():
        text = text.replace('/' + scope + '/', '/other/')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    for name in SCOPES:
        assert read(name) is None, name
    Run.trace = None
    assert read('loop_attention_ms') is None


def test_the_rooflines_and_the_share_read_their_numbers(cell, monkeypatch):
    """``program_counter_ratio`` over two records of the ``host_block`` span
    as ``FusedPipeline._parse`` sets it; the two rooflines read nothing
    without a trace (with one:
    ``test_a_roofline_counts_the_rows_its_traced_chunk_had_reached``)."""
    from benchmark.readers import program_counter_ratio, traced_fill_roofline
    from benchmark.record import Run
    manifest, config, traffic, args = cell
    attrs = lambda k: {'window_positions_valid': 6000.0 * k,
                       'exit_entropy_nats': 6000.0 * k * 0.9 * np.log(4),
                       'exit_entropy_max_nats': 6000.0 * k * np.log(4),
                       'exit_mass_pass_1': 3000.0 * k}
    ring = [{'name': 'host_block', 't1': 1.0, 'attrs': attrs(1)},
            {'name': 'host_block', 't1': 2.0, 'attrs': attrs(3)}]
    monkeypatch.setattr(program_counter_ratio, 'ring', lambda: ring)
    peaks = manifest.load_peaks()['TPU v5 lite']
    run = Run(manifest.cell(CELL), config, traffic, args, {}, (1.0, 2.5),
              names={'peak.' + k: v for k, v in peaks.items()
                     if k != 'source'})
    share = manifest.load_metric('exit_entropy_share')
    assert program_counter_ratio.read(run, **share['args']) \
        == pytest.approx(90.0)
    for name in ('loop_attention_roofline', 'loop_decode_roofline'):
        spec = manifest.load_metric(name)
        assert traced_fill_roofline.read(run, **spec['args']) is None
    # a program without the sums (the parent's): nothing to read, no error
    for record in ring:
        record['attrs'] = {'plies': 1}
    assert program_counter_ratio.read(run, **share['args']) is None


@pytest.mark.parametrize('case, fill', traced_fill.CASES)
@pytest.mark.parametrize('metric', ['loop_decode_roofline',
                                    'loop_attention_roofline'])
def test_a_roofline_counts_the_rows_its_traced_chunk_had_reached(
        cell, tmp_path, metric, case, fill):
    """A synthetic traced run (``traced_fill.py``) through the metric's own
    file. A dispatch that takes exactly the HBM's time for the rows its own
    counters reached reads 100% at 0.7, 1.0 and 1.3 times the games' mean
    fill, where the expression of before PR 52 (the analytic mean's bytes
    over whatever the traced dispatch took) reads 120%, 100% and 86%: the
    105.25% that refused PR 49. A dispatch that reads all 4,096 rows of every
    buffer at the 711 GB/s the tree's ply reaches reads the share the tree
    reads (ledger, PR 51: 45.10-45.11%). A program whose chunk was never
    recorded reads nothing: the analytic mean does not stand in."""
    manifest, config, traffic, args = cell
    got, share, old = traced_fill.roofline_case(
        tmp_path, manifest, manifest.cell(CELL), config, traffic, args,
        metric, case, fill, mean_index=1535.5, bandwidth=711e9)
    if case == 'unpaired':
        assert got is None
        return
    assert got['value'] == pytest.approx(share, rel=1e-6)
    assert got['analytic_mean_value'] == pytest.approx(old, rel=1e-6)
    assert got['samples'] == 1
    assert got['executions'][0]['fill_rows']['global'] \
        == pytest.approx(fill * 1535.5 + 1, abs=0.51)
    if case == 'time_follows_fill':
        assert share == pytest.approx(100.0)
        if metric == 'loop_decode_roofline':
            assert old == pytest.approx({0.7: 120.0, 1.0: 100.0,
                                         1.3: 85.7}[fill], abs=0.05)
    elif metric == 'loop_decode_roofline':
        assert got['value'] == pytest.approx(45.1, abs=0.05)
        # by hand, as the acceptance asks: the printed fill and milliseconds
        (one,) = got['executions']
        assert got['value'] == pytest.approx(
            100 * (1.291849728e9 + 32 * 32768 * one['fill_rows']['global'])
            / 819e9 / (one['ms'] / 1e3 / 256), rel=1e-9)


# -- the checks and a planted fault for each, at the rehearsal's size ----------
@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    import jax.numpy as jnp
    from benchmark import checks
    dest = str(tmp_path_factory.mktemp('ouro_tiny'))
    rehearse.build_root(Manifest(), dest, CELL)
    laid = Manifest(dest)
    config = laid.load_config('ouro')
    traffic = laid.load_traffic('loop_selfplay_4k')
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
    assert (module.hidden_size, module.layers, module.heads_held,
            module.kv_heads_held, module.head_dim, module.passes,
            module.vocab, module.max_positions) \
        == (64, 2, 2, 2, 16, 4, 4608, 64)
    assert (train_args['forward_steps'], train_args['batch_size']) == (32, 2)
    env = config['env_args']
    assert (env['min_steps'], env['max_steps']) == (44, 64)
    assert config['model']['parameters'] == 643777
    with open(os.path.join(os.path.dirname(rehearse.__file__), 'rehearsal',
                           'ouro.json')) as f:
        assert json.load(f)['model']['parameters'] == 643777


def test_the_seeded_batch_is_the_cells_with_the_legal_set_as_bits(tiny):
    config, train_args, _module, _variables = tiny
    batch, windows = co.seeded_batch(config, 3, train_args)
    assert len(windows) == train_args['batch_size'] == 2
    valid = windows[0]['valid']
    assert 16 <= valid.sum() < len(valid) == train_args['forward_steps']
    assert windows[1]['valid'].sum() == train_args['forward_steps']
    assert batch['action_mask'].dtype == np.uint8
    assert batch['action_mask'].shape \
        == (2, len(valid), 1, config['model']['vocab'] // 8)


@pytest.mark.parametrize('fault', ['none', 'a_pass_left_out',
                                   'one_cache_for_all_passes'])
def test_the_forward_and_rollout_checks_tell_the_faults_apart(tiny, fault,
                                                             monkeypatch):
    """Planted in the PROGRAM: three passes for four, and every pass writing
    and reading pass 0's rows (one cache for all passes: the window's
    forward keeps no cache, so the rollout check alone can see that one)."""
    from handyrl_tpu.models import attention
    config, train_args, module, variables = tiny
    if fault == 'a_pass_left_out':
        module = module.clone(passes=3)
    elif fault == 'one_cache_for_all_passes':
        monkeypatch.setattr(
            attention, 'pass_write',
            lambda ck, cv, k, v, pos, t, rows: attention.cache_write(
                ck, cv, k, v, pos))
        monkeypatch.setattr(attention, 'pass_rows',
                            lambda c, t, rows: c[:, :rows])
    stats = co.forward_errors(config, module, variables, 11)
    over = _over(config, stats, 'forward', co.FORWARD_LIMITS)
    if fault == 'a_pass_left_out':
        assert over and stats['passes'] == 3, stats
    else:
        assert not over and stats['passes'] == 4, stats
    stats = co.rollout_errors(config, module, variables, 11, train_args)
    assert stats['resets'] >= 3 and stats['distinct_counters'] == 4
    assert stats['late_plies'] > 0 and stats['after_reset_plies'] > 0
    over = _over(config, stats, 'rollout', co.ROLLOUT_LIMITS)
    assert (not over) if fault == 'none' else over, stats


@pytest.mark.parametrize('control', ['stated'] + [
    name for name in co.CONTROLS if name not in co.STEP_ONLY])
def test_the_rollout_check_tells_the_controls_apart(tiny, control):
    config, train_args, module, variables = tiny
    stats = co.rollout_errors(config, module, variables, 12, train_args,
                              **co.CONTROLS.get(control, {}))
    over = _over(config, stats, 'rollout', co.ROLLOUT_LIMITS)
    if control == 'stated':
        assert not over, stats
    elif control != 'phases_left_out':   # the step's worst leaf catches those
        assert over, stats


@pytest.mark.parametrize('control', ['stated'] + [
    name for name in co.CONTROLS if name not in co.FORWARD_ONLY])
def test_the_step_check_tells_the_controls_apart(tiny, control):
    config, train_args, module, variables = tiny
    stats = co.step_errors(config, module, variables, 11, train_args,
                           **co.CONTROLS.get(control, {}))
    over = _over(config, stats, 'step', co.STEP_LIMITS)
    if control == 'stated':
        assert not over, stats
        assert abs(stats['exit_mass'] - 1) < 1e-4
        assert stats['positions_valid'] == sum(stats['positions'])
        assert stats['windows'] == 2
    else:
        assert 'grad_err_worst_leaf' in over, stats
    if control == 'first_passes_under_stop_gradient':
        # one use of each weight instead of four: the gradient's norm too
        assert 'grad_norm_rel_err' in over, stats
    if control == 'loss_of_the_last_pass_alone':
        assert 'loss_rel_err' in over, stats


@pytest.mark.parametrize('fault', ['small_leaf_unmoved',
                                   'loss_of_the_last_pass_alone'])
def test_the_step_check_catches_what_the_step_must_do(tiny, fault,
                                                      monkeypatch):
    """Planted in the program's own step: small leaves the optimizer left
    where they were, and the seam taking the last pass's loss alone."""
    import jax.numpy as jnp
    from handyrl_tpu.ops import losses, train_step
    config, train_args, module, variables = tiny
    if fault == 'small_leaf_unmoved':
        real = train_step._update_core

        def planted(*args, **kw):
            update = real(*args, **kw)

            def step(state, batch, lr):
                new, metrics = update(state, batch, lr)
                params, old = dict(new.params['params']), \
                    state.params['params']
                params['value'] = old['value']
                for name, leaf in params.items():
                    if name.startswith('layer_'):
                        params[name] = dict(leaf, **{
                            k: old[name][k] for k in leaf if 'norm' in k})
                return new._replace(params={'params': params}), metrics
            return step
        monkeypatch.setattr(train_step, '_update_core', planted)
    else:
        def last_alone(gate_logits):
            p = jnp.zeros_like(gate_logits).at[-1].set(1.0)
            return p, jnp.zeros_like(gate_logits[0])
        monkeypatch.setattr(losses, 'exit_distribution', last_alone)
    stats = co.step_errors(config, module, variables, 11, train_args)
    over = _over(config, stats, 'step', co.STEP_LIMITS)
    if fault == 'small_leaf_unmoved':
        assert 'small_change_err_rel_to_change' in over, stats
        assert 'change_err_worst_leaf' in over, stats
    else:
        assert 'loss_rel_err' in over and 'grad_err_rel_to_grad' in over, stats
