"""Each metric reader on canned input."""

import importlib
import os

import pytest

from benchmark.manifest import ROOT, Manifest
from benchmark.record import Run, intervals, quantile
from benchmark.session import fold_seed, merged_args, read_metrics


def _reader(name):
    return importlib.import_module('benchmark.readers.' + name)


def _run(trace=None, memory=None, names=None):
    """Warm-up dispatches ending at 1, 2, 3, 4.5, 5.5 s; training dispatches
    ending every second from 10 to 20 s, the one that ends at 16 s delayed to
    16.5 by an epoch boundary; the window is 12 .. 20 s."""
    warm = [(t - 0.1, t, {'dispatches': i + 1})
            for i, t in enumerate([1, 2, 3, 4.5, 5.5])]
    ends = [10, 11, 12, 13, 14, 15, 16.5, 17.5, 18.5, 19.5, 20.5]
    train = [(t - 0.05, t, {'dispatches': 5 + i + 1, 'sgd_steps': 32})
             for i, t in enumerate(ends)]
    account = [(t, t + 0.01, {'steps': 32 * (i + 1), 'episodes': 200 + 17 * i,
                              'batch_size': 128})
               for i, t in enumerate(ends)]
    spans = {'warm_dispatch': warm, 'train_dispatch': train,
             'chunk_account': account,
             'epoch_boundary': [(15.2, 15.7, {})]}
    return Run(cell={'name': 'c', 'chips': 1}, config={}, traffic={},
               train_args={'batch_size': 128, 'sgd_steps_per_chunk': 32,
                           'device_chunk_steps': 32, 'generation_envs': 64},
               spans=spans, window=(12, 20.5), trace=trace, memory=memory,
               names=names)


def test_quantile_and_intervals():
    assert quantile([1, 2, 3, 4, 5], 0.5) == 3
    assert quantile([0, 10], 0.95) == pytest.approx(9.5)
    assert quantile([], 0.5) is None
    assert intervals([(0, 1, {}), (0, 3, {}), (0, 6, {})]) == [2, 3]


def test_count_rate_reads_the_counter_at_the_window_edges():
    run = _run()
    # the account records that follow the dispatch ends at 12 and 20.5 s:
    # 8 chunks of 32 steps in 8.5 s
    got = _reader('count_rate').read(run, 'chunk_account', 'steps',
                                     ['train_args.batch_size'])
    assert got == pytest.approx(8 * 32 * 128 / 8.5)
    assert _reader('count_rate').read(run, 'chunk_account', 'episodes') == \
        pytest.approx(8 * 17 / 8.5)
    plies = _reader('count_rate').read(
        run, 'train_dispatch', 'dispatches',
        ['train_args.device_chunk_steps', 'train_args.generation_envs'])
    assert plies == pytest.approx(8 * 32 * 64 / 8.5)
    assert _reader('count_rate').read(run, 'no_such_span', 'x') is None


def test_hook_interval_in_and_before_the_window():
    run = _run()
    p95 = _reader('hook_interval').read(run, 'train_dispatch', 'window', 'p95')
    assert p95['samples'] == 8
    assert p95['value'] == pytest.approx(
        quantile([1, 1, 1, 1.5, 1, 1, 1, 1], 0.95) * 1e3)
    warm = _reader('hook_interval').read(run, 'warm_dispatch',
                                         'before_window', 'median', 1)
    assert warm == {'value': pytest.approx(1000.0), 'samples': 3}
    assert _reader('hook_interval').read(run, 'eval_share') is None


def test_span_tail_is_the_time_after_the_inner_span():
    run = _run()
    # the boundary 15.2 .. 15.7 s holds a state fetch that ends at 15.6 s
    run.spans['state_fetch'] = [(15.25, 15.6, {}), (30.0, 30.1, {})]
    got = _reader('span_tail').read(run, 'epoch_boundary', 'state_fetch')
    assert got == {'value': pytest.approx(100.0), 'samples': 1}
    assert _reader('span_tail').read(_run(), 'epoch_boundary',
                                     'state_fetch') is None


def test_trace_readers_and_memory():
    trace = {'window_s': 2.0, 'busy_s': 1.5,
             'modules': {'jit_fused_pipeline_train': [0.5, 0.7, 0.6]}}
    run = _run(trace=trace, memory={'peak_bytes_in_use': 5 * 2 ** 30})
    assert _reader('trace_idle').read(run) == pytest.approx(25.0)
    assert _reader('trace_module_duration').read(
        run, 'jit_fused_pipeline_train') == {'value': pytest.approx(600.0),
                                             'samples': 3}
    assert _reader('trace_module_duration').read(run, 'jit_absent') is None
    assert _reader('memory_stat').read(run, 'peak_bytes_in_use',
                                       2 ** 30) == 5.0
    bare = _run()
    assert _reader('trace_idle').read(bare) is None
    assert _reader('memory_stat').read(bare, 'peak_bytes_in_use') is None


def test_derived_arithmetic_names_and_refusals():
    run = _run(names={'flops.train_window': 2e9, 'peak.bf16_flops_per_s': 1e12,
                      'chips': 1, 'setup_s': 42.0})
    run.values.update(fused_program_ms=700.0, rollout_chunk_ms=60.0,
                      train_windows_per_s=100.0)
    derived = _reader('derived')
    assert derived.read(run, '(fused_program_ms - rollout_chunk_ms) '
                        '/ train_args.sgd_steps_per_chunk') == 20.0
    assert derived.read(run, '100 * train_windows_per_s * flops.train_window'
                        ' / (peak.bf16_flops_per_s * chips)') == \
        pytest.approx(20.0)
    assert derived.read(run, 'no_such_metric / 2') is None
    with pytest.raises(ValueError):
        derived.read(run, '__import__("os").getcwd()')
    assert _reader('run_number').read(run, 'setup_s') == 42.0


def test_read_metrics_leaves_out_what_no_reader_found():
    manifest = Manifest()
    run = _run(names={'setup_s': 42.0, 'chips': 1, 'flops.train_window': 8.5e8,
                      'peak.bf16_flops_per_s': 197e12})
    got = read_metrics(manifest, run, manifest.metrics_of('geese.sgd_heavy'))
    assert set(got) == {'train_windows_per_s', 'episodes_per_s', 'setup_s',
                        'rollout_chunk_ms', 'env_steps_per_s', 'train_mfu',
                        'plies_per_episode', 'chunk_max_ms'}
    assert got['plies_per_episode']['value'] == pytest.approx(
        got['env_steps_per_s']['value'] / got['episodes_per_s']['value'])
    assert got['train_windows_per_s']['unit'] == 'windows/s'
    assert 0 < got['train_mfu']['value'] < 100


def test_merged_args_lay_config_over_traffic_with_the_runs_seed():
    manifest = Manifest()
    args = merged_args(manifest.load_config('geese_lstm'),
                       manifest.load_traffic('rollout_heavy'), 2 ** 31 + 5)
    train = args['train_args']
    assert args['env_args']['net_kind'] == 'lstm'
    assert train['burn_in_steps'] == 4 and train['sgd_steps_per_chunk'] == 2
    assert train['seed'] == fold_seed(2 ** 31 + 5)   # --seed is the learner's
    assert train['guard'] == {'nonfinite_policy': 'abort'}
    assert train['telemetry']['retrace'] == 'abort'
    assert train['init_params'] == os.path.join(
        ROOT, 'benchmark', 'checkpoints', 'geese_lstm.ckpt')


@pytest.mark.parametrize('seed', [0, 7, 2 ** 31 - 1, 2 ** 31 + 12345,
                                  2 ** 32 + 3])
def test_any_seed_folds_into_what_the_programs_int32_seeds_hold(seed):
    from benchmark.session import fold_seed
    folded = fold_seed(seed)
    assert 0 <= folded < 2 ** 31 - 100   # the program adds small offsets
    assert fold_seed(seed) == folded
