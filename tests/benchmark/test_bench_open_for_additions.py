"""The guard that keeps ``per_layer`` (and ``configs``, ``workloads``) open
for additions: a root made from the CHECKOUT's own ``BENCHMARK.json`` and
files with one more configuration, cell, traffic file, metric file and
``per_layer`` entry APPENDED, the way ``benchmark/README.md``'s "whole list"
tells a later PR to (``fixture/make_root.build`` does exactly that, and adds
a hook, a reader and a rehearsal overlay besides), one more entry for a cell
the checkout already has behind that (``fixture/make_root.grow``; the root
is ``conftest.py``'s ``grown``), and on it every contract and every pin of
``contracts.PINS`` (the first four cells, the pair, the nine, what each
trunk cell brought of its own, what the two expert cells share, the
turnaround four) one case each.

A pin that indexes a list by position past a prefix fails here:
``manifest.raw['per_layer'][-13:]`` in ``test_bench_evabyte.py`` did, from PR
34 to PR 41, and no program PR could add a per-layer metric (CHANGES.md, PR
42, shows this guard failing with that line put back). It reached only the
pins of ``contracts.py``, though: PR 43 wrote ``raw['per_layer'][-9:]`` into
``test_bench_smallthinker.py``, which ran on the checkout alone, saw it pass,
and PR 44 could not append its entry. Since PR 45 every configuration's own
manifest tests take their manifest from ``conftest.py``'s ``either_root``,
the checkout and then THIS root, so such a pin fails in the PR that writes
it (CHANGES.md, PR 45, shows that run). The tests of this file check what
``grow`` itself appended, so they alone may index from the end."""

import json
import os

import pytest

from benchmark.manifest import Manifest

from tests.benchmark import contracts
from tests.benchmark.fixture import make_root
from tests.benchmark.fixture.make_root import ITS_CELL, SECOND, append_entry


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
