"""What every manifest root has to hold, as functions of a ``Manifest``, and
the pins on what the checkout ships, as functions of a ``Manifest`` too.

The contracts hold for any number of configurations and cells. The pins name
what they pin (four cells, two configurations, nine metrics, what each trunk
cell brought of its own, what the two expert cells share, the turnaround
four) BY NAME and reach no further: none indexes ``configs``, ``workloads``
or ``per_layer`` by position past a prefix (a prefix never moves under
appending), and none from the END, so they hold on a root that has MORE than
the checkout and a later PR that appends entries and adds files edits none
of them (``test_bench_open_for_additions.py`` is the guard; ``conftest.py``'s
``either_root`` takes every configuration's own tests there too).
tests/benchmark runs both on the checkout and on ``fixture/make_root``'s
roots, which have one more cell of a seeded configuration with checks, a
FLOP count, a traffic mix, a hook, a reader, a metric and a rehearsal overlay
of its own.

An entry that a later PR may extend to a further cell is pinned as "lists
these cells, in this order" (a prefix of its ``workloads``), not as "lists
them alone": ``EXPERT_SHARED``. An entry that reads what ONE net's program
names (a scope of its own layers, a counter of its own) lists that cell
alone: ``OWN``.

A reading that every cell's program gives (a scope of the module every cell
runs, a span every fused iteration opens, the whole step's share of the peak)
has ONE entry that lists no cells: ``Manifest.cells_of`` gives it to every
cell, those a later PR adds too. An entry lists cells only where the reading
exists or always reads in those alone."""

import importlib
import json
import os

from benchmark import hooks
from benchmark.manifest import ROOT

FOUR = ['geese.sgd_heavy', 'geese.rollout_heavy',
        'geese_lstm.sgd_heavy', 'geese_lstm.rollout_heavy']
PAIR = ['geese', 'geese_lstm']
HARNESS_VERDICTS = ['all_updates_finite', 'replay_ratio_as_configured',
                    'learner_took_its_preemption_exit',
                    'no_compilation_in_window']
SIX = HARNESS_VERDICTS + ['forward_matches_reference',
                          'vtrace_matches_reference']
# (source, layer, reader) of the nine metrics that read the program's own
# measurement. The seven that read what every cell's program gives list no
# cells (``SHARED`` less ``train_mfu``); the other two list those four, by
# name: a trunk cell's window may hold no epoch boundary to read them from
NINE = {
    'rollout_ms': ('device_trace', 'rollout', 'trace_scope_time'),
    'ingest_ms': ('device_trace', 'ingest', 'trace_scope_time'),
    'sgd_ms': ('device_trace', 'update step', 'trace_scope_time'),
    'unscoped_ms': ('device_trace', 'fused dispatch', 'trace_scope_time'),
    'dispatch_enqueue_ms': ('program_span', 'fused dispatch', 'program_span'),
    'fetch_wait_ms': ('program_span', 'fused dispatch', 'program_span'),
    'host_busy_ms': ('program_span', 'entry, orchestration', 'program_span'),
    'checkpoint_write_ms': ('program_span', 'param publish, checkpoint',
                            'program_span'),
    'ingest_builder_ply_share': ('program_counter', 'ingest',
                                 'program_counter_ratio'),
}
# one name a shared reading: no ``workloads`` key, so every cell reports them
SHARED = ['rollout_ms', 'ingest_ms', 'sgd_ms', 'unscoped_ms',
          'dispatch_enqueue_ms', 'fetch_wait_ms', 'host_busy_ms', 'train_mfu']
# what each trunk cell brought that no entry read before: the entries that
# list that cell alone, in the file's order
OWN = {
    'evabyte.selfplay_4k': [
        'eva_attention_ms', 'eva_attention_roofline', 'window_padding_share',
        'state_cache_gib', 'trunk_eval_share_ms'],
    'trinity_mini.moe_selfplay_4k': [
        'moe_experts_ms', 'moe_experts_roofline', 'moe_route_ms',
        'gqa_attention_ms', 'gqa_attention_roofline'],
    'smallthinker.moe_selfplay_8k': [
        'pre_route_ms', 'expert_dispatch_ms', 'reglu_experts_ms',
        'reglu_experts_roofline', 'window_attention_ms',
        'window_attention_roofline', 'global_attention_ms',
        'global_attention_roofline', 'window_hidden_position_share'],
}
# the first seven cells and five configurations, in the file's order: a
# prefix, which appending never moves
SEVEN = FOUR + list(OWN)
FIVE = PAIR + ['evabyte', 'trinity_mini', 'smallthinker']
# what both expert cells' programs give under one name (``models/experts.py``'
# counters on ``host_block``, the scope ``optimizer`` of ``ops/train_step.py``):
# (reader, the argument that says what it reads). Each lists both cells, in
# this order; a third expert cell may be appended behind them
EXPERT_CELLS = ['trinity_mini.moe_selfplay_4k', 'smallthinker.moe_selfplay_8k']
EXPERT_SHARED = {
    'moe_rows_held_share': ('program_counter_ratio',
                            ('numerator', 'moe_rows_held')),
    'moe_load_max_over_mean': ('program_counter_ratio',
                               ('denominator', 'moe_rows_held')),
    'optimizer_ms': ('trace_inner_scope_time', ('scope', 'optimizer')),
    'expert_short_buffer_share': ('program_counter_ratio',
                                  ('numerator', 'moe_dispatches_short')),
}
# the loop's turnaround (PR 40), counters on ``fused_iter``: (unit, layer,
# numerator, denominator, scale, the cells it lists). The two shares divide
# by ``hb_turnaround_s``, which is zero where no chunk of the window was
# host-bound: they list the cells whose windows hold such chunks by the
# hundred (2-33% of ~3,000), not the ``sgd_heavy`` cells' 0-3 of ~600, where
# one traced run in three of ``geese.sgd_heavy`` read none (PERF.md section
# 6, PR 42)
HOST_BOUND = ['geese.rollout_heavy', 'geese_lstm.rollout_heavy']
TURNAROUND = {
    'host_bound_chunk_share': ('%', 'entry, orchestration',
                               'host_bound_chunks', 'chunks', 100, FOUR),
    'turnaround_ms': ('ms', 'entry, orchestration',
                      'turnaround_s', 'chunks', 1000, FOUR),
    'host_bound_ckpt_wait_share': ('%', 'param publish, checkpoint',
                                   'hb_ckpt_wait_s', 'hb_turnaround_s', 100,
                                   HOST_BOUND),
    'host_bound_boundary_share': ('%', 'entry, orchestration',
                                  'hb_boundary_s', 'hb_turnaround_s', 100,
                                  HOST_BOUND),
}


# ---------------------------------------------------------------------------
# contracts: any root


def keys_and_limits(manifest):
    raw = manifest.raw
    assert set(raw) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
    assert raw['command'] == ['python3', 'benchmark/run.py']
    assert raw['paths'] == ['benchmark', 'tests/benchmark']
    assert 1 <= raw['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(manifest.root,
                                        'BENCHMARK.json')) < 64 * 1024
    assert 1 <= len(raw['configs']) <= 24
    assert 1 <= len(raw['workloads']) <= 24
    assert 1 <= len(raw['end_to_end']) <= 16
    assert 1 <= len(raw['per_layer']) <= 128
    # a full check of 24 cells must fit: (2 + 14 * 24) runs
    runs = 2 + 14 * 24
    assert (runs * (raw['run_seconds'] + 60) + 24 * 2 * 90 + 1200) <= 43200


def entries_have_just_the_contracts_keys(manifest):
    raw = manifest.raw
    for entry in raw['configs']:
        assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
        assert entry['file'].startswith('benchmark/')
        assert 1 <= len(entry['source']) <= 200
        assert 1 <= len(entry['why']) <= 200
        assert len(entry['reduced']) <= 16
    files = [entry['file'] for entry in raw['configs']]
    assert len(set(files)) == len(files)
    for cell in raw['workloads']:
        assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert 1 <= len(cell['why']) <= 200 and '\n' not in cell['why']
    pairs = [(c['config'], c['traffic']) for c in raw['workloads']]
    assert len(set(pairs)) == len(pairs)
    used = {c['config'] for c in raw['workloads']}
    assert used == set(manifest.configs)   # each used by some cell
    for entry in raw['end_to_end']:
        assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                              'bound', 'source'}
        assert entry['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= entry['bound'] <= 0.1
    for entry in raw['per_layer']:
        assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                              'source', 'layer', 'moves'}
        assert 1 <= len(entry['layer']) <= 200 and '\n' not in entry['layer']
    assert 'setup_s' in manifest.metrics


def a_layer_metric_moves_what_its_cells_report(manifest):
    for name, entry in manifest.metrics.items():
        if entry['group'] != 'per_layer':
            continue
        moved = manifest.metrics[entry['moves']]
        assert moved['group'] == 'end_to_end'
        assert set(manifest.cells_of(name)) <= \
            set(manifest.cells_of(entry['moves'])), name


def every_cell_reports_setup_one_more_and_a_layer_metric(manifest):
    for cell in manifest.cells:
        e2e = manifest.metrics_of(cell, 'end_to_end')
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert manifest.metrics_of(cell, 'per_layer')


def four_chip_cells_are_a_quarter_at_most(manifest):
    chips = [cell['chips'] for cell in manifest.cells.values()]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 4)


def any_configuration(manifest, name):
    """What holds for ANY configuration's file: it agrees with its entry;
    ``weights`` is one of the two kinds and says why (a checkpoint also where
    it came from, and is a file of about 4 bytes a parameter); ``checks`` is
    a non-empty list of unique names that resolve; ``flops`` resolves."""
    config = manifest.load_config(name)   # raises on an unresolved name
    entry = manifest.configs[name]
    assert config['name'] == name
    assert config['reduced'] == entry['reduced']
    assert config['source'] == entry['source']
    weights = config['weights']
    assert weights['why']
    if weights.get('seeded') is True:     # ask the kind before the key
        assert 'checkpoint' not in weights
    else:
        assert weights['provenance']
        size = os.path.getsize(os.path.join(ROOT, weights['checkpoint']))
        assert abs(size / (4 * config['model']['parameters']) - 1) < 0.1
    names = [check['name'] for check in config['checks']]
    assert names and len(set(names)) == len(names)
    assert not set(names) & set(HARNESS_VERDICTS)
    for check in config['checks']:
        assert callable(hooks.resolve(check['check'])[2])
    assert callable(hooks.resolve(config['flops'])[2])
    assert 'checkpoint' not in config   # one place, one path: `weights`


def every_named_file_exists_and_agrees(manifest):
    for name in manifest.configs:
        any_configuration(manifest, name)
    for cell in manifest.cells.values():
        traffic = manifest.load_traffic(cell['traffic'])
        for key in ('sgd_steps_per_chunk', 'batch_size'):
            assert traffic['train_args'][key] == traffic['replay'][key]
        window = traffic['window']
        spans = [window[k] for k in ('dispatch_span', 'account_span',
                                     'fetch_span')]
        manifest.load_hooks(spans + list(window.get('open_after', {}))
                            + list(window.get('spans', ())))
    for name in manifest.metrics:
        spec = manifest.load_metric(name)   # raises where it disagrees
        module = importlib.import_module('benchmark.readers.'
                                         + spec['reader'])
        assert callable(module.read)
        args = spec.get('args', {})
        manifest.load_hooks([args[k] for k in ('span', 'inner') if k in args])
    peaks = manifest.load_peaks()
    assert peaks['TPU v5 lite']['bf16_flops_per_s'] == 197e12
    assert all('source' in row for row in peaks.values())


def every_metric_file_has_an_entry_and_every_entry_a_file(manifest):
    """A file without an entry is a metric nobody reads (a pending one: the
    ledger never sees it); an entry without a file fails every run."""
    folder = os.path.join(manifest.root, 'benchmark', 'metrics')
    files = {name[:-5] for name in os.listdir(folder)
             if name.endswith('.json')}
    assert files == set(manifest.metrics), sorted(
        files ^ set(manifest.metrics))


def no_two_entries_are_twins(manifest):
    """Two entries with the same ``reader`` and ``args`` are one reading
    under two names: a twin where their cells are disjoint (drop the list of
    the first and the second entry), a duplicate where they are not."""
    seen = {}
    for name in manifest.metrics:
        spec = manifest.load_metric(name)
        key = json.dumps([spec['reader'], spec.get('args', {})],
                         sort_keys=True)
        assert key not in seen, '%s reads what %s reads: %s' % (
            name, seen[key], key)
        seen[key] = name


CONTRACTS = [keys_and_limits, entries_have_just_the_contracts_keys,
             a_layer_metric_moves_what_its_cells_report,
             every_cell_reports_setup_one_more_and_a_layer_metric,
             four_chip_cells_are_a_quarter_at_most,
             every_named_file_exists_and_agrees,
             every_metric_file_has_an_entry_and_every_entry_a_file,
             no_two_entries_are_twins]


# ---------------------------------------------------------------------------
# pins: what the checkout ships, by name, and no further


def the_first_four_cells(manifest):
    assert list(manifest.cells)[:4] == FOUR
    for name in FOUR:
        assert manifest.cells[name]['chips'] == 1


def the_first_seven_cells_and_five_configurations(manifest):
    """Prefixes of ``workloads`` and ``configs``: what stands behind them is
    a later PR's and no concern of this pin."""
    assert list(manifest.cells)[:7] == SEVEN
    assert list(manifest.configs)[:5] == FIVE
    for name in SEVEN:
        assert manifest.cells[name]['chips'] == 1
        assert manifest.cells[name]['config'] == name.split('.')[0]


def the_pair_states_all_three_explicitly(manifest, name):
    assert name in PAIR
    config = manifest.load_config(name)
    assert set(config['weights']) >= {'checkpoint', 'why', 'provenance'}
    assert config['weights']['checkpoint'] == \
        'benchmark/checkpoints/%s.ckpt' % name
    with open(os.path.join(ROOT, config['weights']['checkpoint']),
              'rb') as f:
        assert len(f.read()) > 400_000   # ~116k float32 parameters
    assert [c['name'] for c in config['checks']] == SIX[4:]
    assert [c['check'] for c in config['checks']] == [
        'benchmark.checks:forward_check', 'benchmark.checks:vtrace_check']
    assert config['flops'] == 'benchmark.flops:train_window_flops'


def one_of_the_nine_agrees_with_its_entry(manifest, name):
    source, layer, reader = NINE[name]
    entry = manifest.metrics[name]
    spec = manifest.load_metric(name)           # raises where they disagree
    assert (entry['source'], entry['layer'], spec['reader']) == \
        (source, layer, reader)
    assert entry['moves'] == 'train_windows_per_s'
    if name in SHARED:
        assert 'workloads' not in entry
        assert manifest.cells_of(name) == tuple(manifest.cells)
    else:
        assert entry['workloads'] == FOUR
    with open(os.path.join(manifest.root, 'benchmark', 'metrics',
                           name + '.json')) as f:
        raw = json.load(f)
    for key in ('name', 'unit', 'better', 'source', 'layer', 'moves'):
        assert raw[key] == entry[key]
    # the harness takes a metric's `span` / `inner` argument for a hook to
    # install: the program's own spans go by `stage`
    assert not {'span', 'inner'} & set(spec['args'])
    assert spec['args'].get('stat', 'median') == 'median'
    module = importlib.import_module('benchmark.readers.' + reader)
    assert callable(module.read)


def a_rehearsal_line(manifest, workload, line):
    """The verdict keys of a rehearsed cell: the harness's four, letter for
    letter and in order, then the configuration's ``checks`` as its file
    lists them; for the four shipped cells that makes the six, and the
    forward check ran ``tiny.json``'s three plies."""
    assert line['cpu_rehearsal'] is True and line['platform'] == 'cpu'
    config = manifest.load_config(manifest.cell(workload)['config'])
    own = [check['name'] for check in config['checks']]
    assert list(line['checks']) == HARNESS_VERDICTS + own
    assert list(line['reference']) == own
    if workload in FOUR:
        assert list(line['checks']) == SIX
        assert line['reference']['forward_matches_reference']['plies'] == 3
    assert line['flops']['train_window'] > 0
    # counts only: nothing under a device metric's name
    assert not set(line) & set(manifest.metrics)
    assert 'metrics' not in line and 'device' not in line


def the_whole_steps_share_is_every_cells(manifest):
    """``train_mfu``: one ``derived`` expression on ``flops.train_window``,
    which every configuration's file must state, and no list of cells: each
    cell has a share of the whole step under a name that holds ``mfu``."""
    entry = manifest.metrics['train_mfu']
    assert 'workloads' not in entry
    spec = manifest.load_metric('train_mfu')
    assert spec['reader'] == 'derived'
    assert 'flops.train_window' in spec['args']['expr']
    assert 'lower bound' in spec['what'].lower()   # float32 activations
    for cell in manifest.cells:
        assert 'train_mfu' in manifest.metrics_of(cell, 'per_layer')


def a_cells_own_metrics_are_its_entries(manifest, cell):
    """By NAME: the entries that list this cell alone, in the file's order,
    wherever in ``per_layer`` they stand; a later PR may append more of
    them behind these."""
    alone = [entry['name'] for entry in manifest.raw['per_layer']
             if entry.get('workloads') == [cell]]
    assert alone[:len(OWN[cell])] == OWN[cell]
    assert manifest.metrics_of(cell, 'end_to_end') \
        == ['train_windows_per_s', 'setup_s']
    reported = manifest.metrics_of(cell, 'per_layer')
    for name in SHARED + OWN[cell]:
        assert name in reported, name
    for other, names in OWN.items():
        if other != cell:
            assert not set(names) & set(reported)


def a_cells_own_metric(manifest, cell, name):
    entry = manifest.metrics[name]
    assert entry['workloads'] == [cell]
    assert entry['moves'] == 'train_windows_per_s'
    spec = manifest.load_metric(name)           # raises where they disagree
    module = importlib.import_module('benchmark.readers.' + spec['reader'])
    assert callable(module.read)
    assert spec['what']


def an_expert_cells_shared_metric(manifest, name):
    """Lists both expert cells, in this order, and maybe more behind them;
    named for no net, and read where both programs put it."""
    reader, (key, value) = EXPERT_SHARED[name]
    entry = manifest.metrics[name]
    assert entry['workloads'][:len(EXPERT_CELLS)] == EXPERT_CELLS
    assert (entry['layer'], entry['moves']) == ('update step',
                                                'train_windows_per_s')
    spec = manifest.load_metric(name)           # raises where they disagree
    assert spec['reader'] == reader and spec['args'][key] == value
    assert spec['what']
    assert not name.startswith(tuple(
        cell.split('.')[0] for cell in EXPERT_CELLS))
    for cell in EXPERT_CELLS:
        assert name in manifest.metrics_of(cell, 'per_layer')
    for cell in FOUR + ['evabyte.selfplay_4k']:
        assert name not in manifest.metrics_of(cell)


def a_turnaround_metric(manifest, name):
    unit, layer, top, bottom, scale, cells = TURNAROUND[name]
    spec = manifest.load_metric(name)     # raises where file and entry differ
    entry = manifest.metrics[name]
    assert (entry['unit'], entry['better'], entry['source'], entry['layer'],
            entry['moves']) == (unit, 'lower', 'program_counter', layer,
                                'train_windows_per_s')
    assert entry['workloads'] == cells
    assert spec['reader'] == 'program_counter_ratio'
    assert spec['args'] == {'stage': 'fused_iter', 'numerator': top,
                            'denominator': bottom, 'scale': scale}
    # the text names the span and the counters it reads
    for word in ('fused_iter', top, bottom, 'ChunkMonitor'):
        assert word in spec['what'], word


# every pin as (function, further arguments): one case each wherever a test
# is parametrised by pin
PINS = ([(the_first_four_cells, ()),
         (the_first_seven_cells_and_five_configurations, ()),
         (the_whole_steps_share_is_every_cells, ())]
        + [(the_pair_states_all_three_explicitly, (name,)) for name in PAIR]
        + [(one_of_the_nine_agrees_with_its_entry, (name,)) for name in NINE]
        + [(a_cells_own_metrics_are_its_entries, (cell,)) for cell in OWN]
        + [(a_cells_own_metric, (cell, name))
           for cell, names in OWN.items() for name in names]
        + [(an_expert_cells_shared_metric, (name,)) for name in EXPERT_SHARED]
        + [(a_turnaround_metric, (name,)) for name in TURNAROUND])


def pin_id(pin):
    fn, args = pin
    return '-'.join((fn.__name__,) + args)


def pins(manifest):
    """Every pin, on one root."""
    for fn, args in PINS:
        fn(manifest, *args)
