"""The four metrics of the loop's turnaround (PR 40): data files on the
reader the benchmark already has, ``program_counter_ratio`` over the sums
that ``telemetry.ChunkMonitor`` puts on every ``fused_iter`` span.

Their entries AWAIT a ``benchmark`` PR (``test_bench_evabyte.py`` pins the
last thirteen entries of ``per_layer`` by position; PERF.md section 7 (9)),
so what is said of them here is said on a root built in ``tmp_path``: the
checkout's ``BENCHMARK.json`` with the four entries appended, for the four
conv cells, and everything else a link to the checkout's file."""

import json
import os

import pytest

from benchmark.manifest import ROOT, Manifest
from benchmark.readers import program_counter_ratio
from benchmark.record import Run

from tests.benchmark import contracts
from tests.benchmark.fixture import make_root

NEW = {
    'host_bound_chunk_share': ('%', 'entry, orchestration',
                               'host_bound_chunks', 'chunks', 100),
    'turnaround_ms': ('ms', 'entry, orchestration',
                      'turnaround_s', 'chunks', 1000),
    'host_bound_ckpt_wait_share': ('%', 'param publish, checkpoint',
                                   'hb_ckpt_wait_s', 'hb_turnaround_s', 100),
    'host_bound_boundary_share': ('%', 'entry, orchestration',
                                  'hb_boundary_s', 'hb_turnaround_s', 100),
}
ENTRY_KEYS = ('name', 'unit', 'better', 'source', 'layer', 'moves')


def entries():
    """The ``per_layer`` entries the ``benchmark`` PR is to append."""
    out = []
    for name in NEW:
        with open(os.path.join(ROOT, 'benchmark', 'metrics',
                               name + '.json')) as f:
            spec = json.load(f)
        out.append(dict({key: spec[key] for key in ENTRY_KEYS},
                        workloads=list(contracts.FOUR)))
    return out


@pytest.fixture(scope='module')
def appended(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp('turnaround') / 'root')
    make_root._link_shipped(dest)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        raw = json.load(f)
    raw['per_layer'] += entries()
    with open(os.path.join(dest, 'BENCHMARK.json'), 'w') as f:
        json.dump(raw, f, indent=1)
    return Manifest(dest)


@pytest.mark.parametrize('contract', contracts.CONTRACTS,
                         ids=lambda fn: fn.__name__)
def test_contract_holds_with_the_four_entries_appended(contract, appended):
    contract(appended)


def test_the_pins_hold_and_nothing_shipped_changes(appended, shipped):
    contracts.pins(appended)
    for key, value in shipped.raw.items():
        if key != 'per_layer':
            assert appended.raw[key] == value
    held = len(shipped.raw['per_layer'])
    assert appended.raw['per_layer'][:held] == shipped.raw['per_layer']
    assert [e['name'] for e in appended.raw['per_layer'][held:]] == list(NEW)
    # the checkout names none of them: the accepted list stands as it was
    assert not set(NEW) & set(shipped.metrics)
    for cell in contracts.FOUR:
        assert appended.metrics_of(cell, 'per_layer')[-4:] == list(NEW)
    for cell in set(appended.cells) - set(contracts.FOUR):
        assert not set(NEW) & set(appended.metrics_of(cell))


@pytest.mark.parametrize('name', NEW)
def test_a_metric_file_agrees_with_its_entry(name, appended):
    unit, layer, top, bottom, scale = NEW[name]
    spec = appended.load_metric(name)     # raises where file and entry differ
    entry = appended.metrics[name]
    assert (entry['unit'], entry['better'], entry['source'], entry['layer'],
            entry['moves']) == (unit, 'lower', 'program_counter', layer,
                                'train_windows_per_s')
    assert entry['layer'] in {e['layer'] for e in
                              Manifest().raw['per_layer']}
    assert spec['reader'] == 'program_counter_ratio'
    assert spec['args'] == {'stage': 'fused_iter', 'numerator': top,
                            'denominator': bottom, 'scale': scale}
    # the text names the span and the counters it reads
    for word in ('fused_iter', top, bottom, 'ChunkMonitor'):
        assert word in spec['what'], word


def _ring(host_bound):
    """``fused_iter`` records one second apart that carry the sums as the
    monitor keeps them. Every chunk: 2 ms enqueue, 1 ms accounting, 1 ms
    eval. ``host_bound`` chunks (by index) besides hold 5 ms of writer wait
    and 9 ms of the boundary's own work, and wait 0.1 ms; the others wait
    12 ms."""
    sums = dict.fromkeys(
        ['chunks', 'host_bound_chunks', 'interval_s', 'turnaround_s',
         'hb_turnaround_s', 'hb_boundary_s', 'hb_ckpt_wait_s',
         'hb_enqueue_s', 'hb_account_s', 'hb_eval_s'], 0)
    records = []
    for n in range(10, 20):
        bound = n in host_bound
        turnaround = 0.004 + (0.014 if bound else 0.0)
        sums['chunks'] += 1
        sums['interval_s'] += turnaround + (0.0001 if bound else 0.012)
        sums['turnaround_s'] += turnaround
        if bound:
            sums['host_bound_chunks'] += 1
            sums['hb_turnaround_s'] += turnaround
            sums['hb_ckpt_wait_s'] += 0.005
            sums['hb_boundary_s'] += 0.009
            sums['hb_enqueue_s'] += 0.002
            sums['hb_account_s'] += 0.001
            sums['hb_eval_s'] += 0.001
        records.append({'name': 'fused_iter', 't0': n - 0.9, 't1': float(n),
                        'span_id': n, 'parent_id': None,
                        'attrs': dict(sums, dispatch=n)})
    return records


def _read(monkeypatch, manifest, records, window):
    from handyrl_tpu import telemetry
    monkeypatch.setattr(telemetry, 'spans',
                        lambda name=None, since=None: list(records))
    run = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
              spans={}, window=window)
    return {name: program_counter_ratio.read(
        run, **manifest.load_metric(name)['args']) for name in NEW}


def test_the_shares_of_the_host_bound_turnaround_on_a_hand_made_ring(
        monkeypatch, appended):
    # the window's chunks are those of records 12 .. 17; 13, 14 and 16 were
    # host-bound (record 11's, host-bound too, lies before the window)
    got = _read(monkeypatch, appended, _ring({11, 13, 14, 16}), (11.5, 17.5))
    assert got['host_bound_chunk_share'] == pytest.approx(100 * 3 / 6)
    assert got['turnaround_ms'] == pytest.approx(
        1e3 * (6 * 0.004 + 3 * 0.014) / 6)
    assert got['host_bound_ckpt_wait_share'] == pytest.approx(
        100 * 0.005 / 0.018)
    assert got['host_bound_boundary_share'] == pytest.approx(
        100 * 0.009 / 0.018)
    # with the loop's three pieces the two add up to the whole
    assert (got['host_bound_ckpt_wait_share']
            + got['host_bound_boundary_share']
            + 100 * 0.004 / 0.018) == pytest.approx(100)


def test_no_host_bound_chunk_leaves_the_two_shares_out(monkeypatch, appended):
    got = _read(monkeypatch, appended, _ring({11}), (11.5, 17.5))
    assert got['host_bound_chunk_share'] == 0.0
    assert got['turnaround_ms'] == pytest.approx(4.0)
    assert got['host_bound_ckpt_wait_share'] is None
    assert got['host_bound_boundary_share'] is None


def test_a_program_without_the_counters_leaves_all_four_out(monkeypatch,
                                                           appended):
    """The parent's ``fused_iter`` carries ``dispatch`` and ``warm`` alone."""
    records = [dict(r, attrs={'dispatch': r['span_id'], 'warm': 0})
               for r in _ring(set())]
    got = _read(monkeypatch, appended, records, (11.5, 17.5))
    assert got == dict.fromkeys(NEW)
