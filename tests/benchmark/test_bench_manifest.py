"""BENCHMARK.json against its contract, on the checkout and on a root with a
fifth cell, and the loader's refusals. The contracts and the pins are
functions of a ``Manifest`` (``contracts.py``)."""

import filecmp
import json
import os
import shutil

import pytest

from benchmark import manifest
from benchmark.manifest import Manifest, ManifestError, ROOT

from tests.benchmark import contracts
from tests.benchmark.fixture import make_root


def test_keys_and_limits(shipped):
    contracts.keys_and_limits(shipped)


def test_cells_are_the_issues_four_in_order(shipped):
    contracts.the_first_four_cells(shipped)


def test_entries_have_just_the_contracts_keys(shipped):
    contracts.entries_have_just_the_contracts_keys(shipped)


def test_every_cell_reports_setup_one_more_and_a_layer_metric(shipped):
    contracts.every_cell_reports_setup_one_more_and_a_layer_metric(shipped)
    contracts.a_layer_metric_moves_what_its_cells_report(shipped)


def test_every_named_file_exists_and_agrees(shipped):
    contracts.every_named_file_exists_and_agrees(shipped)


def test_at_most_a_quarter_of_the_cells_and_never_fewer_than_one_on_4_chips(
        shipped):
    contracts.four_chip_cells_are_a_quarter_at_most(shipped)


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_on_a_root_with_a_fifth_cell(contract, fifth, shipped):
    assert len(fifth.cells) == len(shipped.cells) + 1 >= 5
    assert len(fifth.configs) == len(shipped.configs) + 1 >= 3
    contract(fifth)


def test_the_pins_hold_on_a_root_with_a_fifth_cell(fifth):
    """They keep their values and lose their reach: a cell list compared
    whole, a ``weights.checkpoint`` read without asking the kind,
    ``workloads == list(cells)`` or the six check names held against a
    configuration that is not of the pair would each fail here."""
    contracts.pins(fifth)
    contracts.any_configuration(fifth, make_root.CONFIG)
    assert fifth.metrics[make_root.METRIC]['workloads'] == [make_root.CELL]
    for cell in contracts.FOUR:
        assert make_root.METRIC not in fifth.metrics_of(cell)


def test_appending_entries_and_adding_files_changes_no_shipped_entry_list_or_file_and_leaves_every_contract_green(  # noqa: E501
        shipped, fifth):
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        ours, theirs = shipped.raw[group], fifth.raw[group]
        assert theirs[:len(ours)] == ours          # entries and their lists
    assert len(fifth.raw['workloads']) == len(shipped.raw['workloads']) + 1
    for key in ('command', 'paths', 'run_seconds'):
        assert fifth.raw[key] == shipped.raw[key]
    added = []
    for folder in ('configs', 'traffic', 'metrics', 'hooks', 'readers',
                   'rehearsal'):
        ours = os.path.join(shipped.root, 'benchmark', folder)
        theirs = os.path.join(fifth.root, 'benchmark', folder)
        for name in os.listdir(ours):
            if name != '__pycache__':     # every shipped file, byte for byte
                assert filecmp.cmp(os.path.join(ours, name),
                                   os.path.join(theirs, name), shallow=False)
        added += [folder + '/' + name for name in os.listdir(theirs)
                  if not os.path.exists(os.path.join(ours, name))]
    assert sorted(added) == sorted([
        'configs/%s.json' % make_root.CONFIG,
        'traffic/%s.json' % make_root.TRAFFIC,
        'metrics/%s.json' % make_root.METRIC,
        'hooks/%s.json' % make_root.HOOK,
        'readers/%s.py' % make_root.READER,
        'rehearsal/%s.json' % make_root.CONFIG])
    for contract in contracts.CONTRACTS:
        contract(fifth)
        contract(shipped)
    contracts.pins(fifth)
    contracts.pins(shipped)


def test_traffic_mixes_differ_only_in_the_replay_dial(shipped):
    heavy = shipped.load_traffic('sgd_heavy')['train_args']
    light = shipped.load_traffic('rollout_heavy')['train_args']
    assert {k for k in heavy if heavy[k] != light[k]} == \
        {'sgd_steps_per_chunk'}
    assert (heavy['sgd_steps_per_chunk'], light['sgd_steps_per_chunk']) == \
        (32, 2)
    assert heavy['batch_size'] == 128 and heavy['generation_envs'] == 64


@pytest.mark.parametrize('chips, refused', [
    ([1, 1, 1, 4], False), ([4], False), ([1, 4], False),
    ([1, 1, 4, 4], True), ([1] * 6 + [4, 4], False),
    ([1] * 5 + [4, 4], True)],
    ids=['1_of_4', 'the_only_cell', '1_of_2', '2_of_4', '2_of_8', '2_of_7'])
def test_the_four_chip_share_is_counted_over_any_number_of_cells(chips,
                                                                 refused):
    class Cells:
        cells = {str(i): {'chips': n} for i, n in enumerate(chips)}
    if refused:
        with pytest.raises(AssertionError):
            contracts.four_chip_cells_are_a_quarter_at_most(Cells)
    else:
        contracts.four_chip_cells_are_a_quarter_at_most(Cells)


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
