"""The four metrics of the loop's turnaround (PR 40): data files on the
reader the benchmark already has, ``program_counter_ratio`` over the sums
that ``telemetry.ChunkMonitor`` puts on every ``fused_iter`` span.

Entries of the checkout's ``BENCHMARK.json`` since PR 42 (they waited on a
root of their own from PR 40 on). An entry lists a cell only where the
metric read a number in every traced run of that cell: the two shares of the
host-bound turnaround divide by ``hb_turnaround_s``, which is zero in a
window with no host-bound chunk (``contracts.TURNAROUND`` has the lists)."""

import pytest

from benchmark.readers import program_counter_ratio
from benchmark.record import Run

from tests.benchmark import contracts

NEW = contracts.TURNAROUND


def test_the_four_are_entries_of_the_checkout_for_the_cells_that_read_them(
        shipped):
    names = [e['name'] for e in shipped.raw['per_layer']]
    assert [name for name in names if name in NEW] == list(NEW)
    for name, (*_rest, cells) in NEW.items():
        assert set(cells) <= set(contracts.FOUR)
        for cell in shipped.cells:
            assert (name in shipped.metrics_of(cell, 'per_layer')) \
                == (cell in cells)


@pytest.mark.parametrize('name', NEW)
def test_a_metric_file_agrees_with_its_entry(name, shipped):
    contracts.a_turnaround_metric(shipped, name)
    assert shipped.metrics[name]['layer'] in {
        e['layer'] for e in shipped.raw['per_layer'] if e['name'] not in NEW}


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
        monkeypatch, shipped):
    # the window's chunks are those of records 12 .. 17; 13, 14 and 16 were
    # host-bound (record 11's, host-bound too, lies before the window)
    got = _read(monkeypatch, shipped, _ring({11, 13, 14, 16}), (11.5, 17.5))
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


def test_no_host_bound_chunk_leaves_the_two_shares_out(monkeypatch, shipped):
    got = _read(monkeypatch, shipped, _ring({11}), (11.5, 17.5))
    assert got['host_bound_chunk_share'] == 0.0
    assert got['turnaround_ms'] == pytest.approx(4.0)
    assert got['host_bound_ckpt_wait_share'] is None
    assert got['host_bound_boundary_share'] is None


def test_a_program_without_the_counters_leaves_all_four_out(monkeypatch,
                                                           shipped):
    """The parent's ``fused_iter`` carries ``dispatch`` and ``warm`` alone."""
    records = [dict(r, attrs={'dispatch': r['span_id'], 'warm': 0})
               for r in _ring(set())]
    got = _read(monkeypatch, shipped, records, (11.5, 17.5))
    assert got == dict.fromkeys(NEW)
