"""BENCHMARK.json against its contract, and the loader's refusals."""

import json
import os
import shutil

import pytest

from benchmark import manifest
from benchmark.manifest import Manifest, ManifestError, ROOT


@pytest.fixture(scope='module')
def shipped():
    return Manifest()


def test_keys_and_limits(shipped):
    raw = shipped.raw
    assert set(raw) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
    assert raw['command'] == ['python3', 'benchmark/run.py']
    assert raw['paths'] == ['benchmark', 'tests/benchmark']
    assert 1 <= raw['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024
    # a full check of 24 cells must fit: (2 + 14 * 24) runs
    runs = 2 + 14 * 24
    assert (runs * (raw['run_seconds'] + 60) + 24 * 2 * 90 + 1200) <= 43200


def test_cells_are_the_issues_four_in_order(shipped):
    assert list(shipped.cells) == [
        'geese.sgd_heavy', 'geese.rollout_heavy',
        'geese_lstm.sgd_heavy', 'geese_lstm.rollout_heavy'][:len(shipped.cells)]
    for cell in shipped.cells.values():
        assert cell['chips'] == 1
        assert set(cell) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert 1 <= len(cell['why']) <= 200 and '\n' not in cell['why']


def test_entries_have_just_the_contracts_keys(shipped):
    for entry in shipped.raw['configs']:
        assert set(entry) == {'name', 'source', 'file', 'reduced', 'why'}
        assert entry['file'].startswith('benchmark/')
        assert len(entry['source']) <= 200 and len(entry['why']) <= 200
    for entry in shipped.raw['end_to_end']:
        assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                              'bound', 'source'}
        assert entry['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= entry['bound'] <= 0.1
    for entry in shipped.raw['per_layer']:
        assert set(entry) - {'workloads'} == {'name', 'unit', 'better',
                                              'source', 'layer', 'moves'}
        assert len(entry['layer']) <= 200
    assert 'setup_s' in shipped.metrics


def test_every_cell_reports_setup_one_more_and_a_layer_metric(shipped):
    for cell in shipped.cells:
        e2e = shipped.metrics_of(cell, 'end_to_end')
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert shipped.metrics_of(cell, 'per_layer')


def test_every_named_file_exists_and_agrees(shipped):
    for name in shipped.configs:
        config = shipped.load_config(name)
        assert config['name'] == name
        assert config['reduced'] == shipped.configs[name]['reduced']
        assert config['source'] == shipped.configs[name]['source']
        with open(os.path.join(ROOT, config['checkpoint']), 'rb') as f:
            assert len(f.read()) > 400_000   # ~116k float32 parameters
    for cell in shipped.cells.values():
        traffic = shipped.load_traffic(cell['traffic'])
        assert traffic['train_args']['sgd_steps_per_chunk'] == \
            traffic['replay']['sgd_steps_per_chunk']
        assert traffic['train_args']['batch_size'] == \
            traffic['replay']['batch_size']
    for name in shipped.metrics:
        spec = shipped.load_metric(name)
        assert os.path.exists(os.path.join(
            ROOT, 'benchmark', 'readers', spec['reader'] + '.py'))
    peaks = shipped.load_peaks()
    assert peaks['TPU v5 lite']['bf16_flops_per_s'] == 197e12
    assert all('source' in row for row in peaks.values())


def test_traffic_mixes_differ_only_in_the_replay_dial(shipped):
    heavy = shipped.load_traffic('sgd_heavy')['train_args']
    light = shipped.load_traffic('rollout_heavy')['train_args']
    assert {k for k in heavy if heavy[k] != light[k]} == \
        {'sgd_steps_per_chunk'}
    assert (heavy['sgd_steps_per_chunk'], light['sgd_steps_per_chunk']) == \
        (32, 2)
    assert heavy['batch_size'] == 128 and heavy['generation_envs'] == 64


@pytest.fixture
def copy(tmp_path):
    """A private copy of the manifest and its data files, to be broken."""
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(os.path.join(ROOT, 'benchmark'), tmp_path / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__', 'testdata'))
    return tmp_path


def _rewrite(root, edit):
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        raw = json.load(f)
    edit(raw)
    with open(path, 'w') as f:
        json.dump(raw, f)


@pytest.mark.parametrize('bad', ['tokens per s', 'a,b', 'a/b', '-lead', '',
                                 'x' * 65, 'grμk'])
def test_refuses_a_name_outside_the_allowed_set(copy, bad):
    _rewrite(copy, lambda raw: raw['per_layer'][0].update(name=bad))
    with pytest.raises(ManifestError):
        Manifest(str(copy))
    with pytest.raises(ManifestError):
        manifest.check_name(bad)


@pytest.mark.parametrize('bad', ['tokens per second', 'μs', 'a,b', '',
                                 'u' * 17])
def test_refuses_a_unit_outside_the_allowed_set(copy, bad):
    _rewrite(copy, lambda raw: raw['end_to_end'][0].update(unit=bad))
    with pytest.raises(ManifestError):
        Manifest(str(copy))


@pytest.mark.parametrize('good', ['tokens/s', 'ms', '%', 'GiB', 'plies/s'])
def test_accepts_the_units_in_use(good):
    assert manifest.check_unit(good) == good


def test_refuses_a_layer_metric_that_moves_nothing_reported(copy):
    # an end-to-end metric that exists only in one cell: a per-layer
    # metric of every cell cannot move it
    def narrow(raw):
        raw['end_to_end'].append(dict(
            raw['end_to_end'][0], name='only_here',
            workloads=['geese.rollout_heavy']))
        raw['per_layer'][0].update(moves='only_here')
    _rewrite(copy, narrow)
    with pytest.raises(ManifestError):
        Manifest(str(copy))


def test_refuses_a_metric_file_that_disagrees(copy):
    path = copy / 'benchmark' / 'metrics' / 'device_idle.json'
    spec = json.loads(path.read_text())
    spec['unit'] = 'ms'
    path.write_text(json.dumps(spec))
    with pytest.raises(ManifestError):
        Manifest(str(copy)).load_metric('device_idle')


def test_unknown_workload_is_an_error(shipped):
    with pytest.raises(ManifestError):
        shipped.cell('geese.no_such_traffic')
