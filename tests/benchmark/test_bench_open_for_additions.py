"""The guard that keeps ``per_layer`` (and ``configs``, ``workloads``) open
for additions: a root made from the CHECKOUT's own ``BENCHMARK.json`` and
files with one more configuration, cell, traffic file, metric file and
``per_layer`` entry APPENDED, the way ``benchmark/README.md``'s "whole list"
tells a later PR to (``fixture/make_root.build`` does exactly that, and adds
a hook, a reader and a rehearsal overlay besides), one more entry for a cell
the checkout already has behind that, and on it every contract,
every pin of ``contracts.PINS`` (the first four cells, the pair, the nine,
what each trunk cell brought of its own, the turnaround four) one case each.

A pin that indexes a list by position past the first four cells fails here:
``manifest.raw['per_layer'][-13:]`` in ``test_bench_evabyte.py`` did, from PR
34 to PR 41, and no program PR could add a per-layer metric (CHANGES.md, PR
42, shows this guard failing with that line put back)."""

import json
import os

import pytest

from benchmark.manifest import Manifest

from tests.benchmark import contracts
from tests.benchmark.fixture import make_root

# one more entry for a cell the checkout HAS, as a program PR that adds a
# counter to a shipped cell would append it
SECOND, ITS_CELL = 'toy_boundaries_in_a_shipped_cell', 'evabyte.selfplay_4k'


def append_entry(root, entry):
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        raw = json.load(f)
    raw['per_layer'].append(entry)
    with open(path, 'w') as f:
        json.dump(raw, f, indent=1)


def grow(dest):
    """``make_root.build``'s root (a configuration, its cell, a traffic
    file, a hook, a reader, a metric file and its entry, all appended) and,
    behind that, one more metric file and entry that lists a shipped cell
    alone."""
    root = make_root.build(dest)
    entry = {'name': SECOND, 'unit': 'calls', 'better': 'higher',
             'source': 'program_counter',
             'layer': 'param publish, checkpoint',
             'moves': 'train_windows_per_s', 'workloads': [ITS_CELL]}
    with open(os.path.join(root, 'benchmark', 'metrics',
                           SECOND + '.json'), 'w') as f:
        json.dump(dict(entry, reader=make_root.READER,
                       args={'span': 'epoch_boundary'},
                       what='guard: the boundaries that ended in the window'),
                  f)
    append_entry(root, entry)
    return root


@pytest.fixture(scope='module')
def grown(tmp_path_factory):
    """The checkout as later PRs would leave it: everything it has, and one
    of each kind of addition behind it."""
    return Manifest(grow(
        str(tmp_path_factory.mktemp('open_for_additions') / 'root')))


def test_the_root_is_the_checkout_with_one_of_each_appended(grown, shipped):
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        ours, theirs = shipped.raw[group], grown.raw[group]
        assert theirs[:len(ours)] == ours
    for group, more in (('configs', 1), ('workloads', 1), ('per_layer', 2)):
        assert len(grown.raw[group]) == len(shipped.raw[group]) + more
    assert grown.raw['end_to_end'] == shipped.raw['end_to_end']
    assert [entry['name'] for entry in grown.raw['per_layer'][-2:]] \
        == [make_root.METRIC, SECOND]
    assert list(grown.cells)[-1] == make_root.CELL
    assert grown.metrics_of(ITS_CELL, 'per_layer')[-1] == SECOND


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_with_one_of_each_appended(contract, grown):
    contract(grown)


@pytest.mark.parametrize('pin', contracts.PINS, ids=contracts.pin_id)
def test_pin_holds_with_one_of_each_appended(pin, grown):
    fn, args = pin
    fn(grown, *args)


def test_the_new_cell_reports_every_shared_reading_and_names_none(grown):
    """One name a shared reading: the appended cell brought ONE entry, its
    own, and reports the shared path's metrics because they list no cells."""
    reported = grown.metrics_of(make_root.CELL, 'per_layer')
    for name in contracts.SHARED:
        assert name in reported, name
        assert 'workloads' not in grown.metrics[name]
    own = [entry['name'] for entry in grown.raw['per_layer']
           if make_root.CELL in entry.get('workloads', ())]
    assert own == [make_root.METRIC]
    # and nothing that lists cells by name reaches it
    for name in reported:
        listed = grown.metrics[name].get('workloads')
        assert listed is None or listed == [make_root.CELL], name


def test_a_twin_and_an_orphan_file_are_refused(tmp_path):
    """The two contracts PR 42 added, each on a root that breaks it."""
    root = make_root.build(str(tmp_path / 'root'))
    metrics = os.path.join(root, 'benchmark', 'metrics')
    with open(os.path.join(metrics, 'sgd_ms.json')) as f:
        twin = dict(json.load(f), name='toy_sgd_ms')
    with open(os.path.join(metrics, 'toy_sgd_ms.json'), 'w') as f:
        json.dump(twin, f)
    with pytest.raises(AssertionError):     # a file and no entry
        contracts.every_metric_file_has_an_entry_and_every_entry_a_file(
            Manifest(root))
    append_entry(root, dict(
        {key: twin[key] for key in ('name', 'unit', 'better', 'source',
                                    'layer', 'moves')},
        workloads=[make_root.CELL]))
    grown = Manifest(root)
    contracts.every_metric_file_has_an_entry_and_every_entry_a_file(grown)
    with pytest.raises(AssertionError) as err:
        contracts.no_two_entries_are_twins(grown)
    assert 'toy_sgd_ms' in str(err.value) and 'sgd_ms' in str(err.value)
