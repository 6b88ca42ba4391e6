"""The readers of what the program measures itself: ``program_span`` and
``program_counter_ratio`` on a hand-written span ring, ``trace_scope_time`` on
a hand-written trace with named phases (exact answers) and on the trace
recorded on a v5e, and the nine metrics that use them against their entries."""

import os

import pytest

from benchmark import reduce_trace
from benchmark.manifest import ROOT, Manifest
from benchmark.readers import (program_counter_ratio, program_span,
                               trace_scope_time)
from benchmark.record import Run

from tests.benchmark import contracts

TESTDATA = os.path.join(ROOT, 'benchmark', 'testdata')
SCOPES = ['rollout', 'ingest', 'sgd', 'pack']
MODULE = 'jit_fused_pipeline_train'
NEW_METRICS = contracts.NINE


def _run(trace=None, name='c'):
    return Run(cell={'name': name, 'chips': 1}, config={}, traffic={},
               train_args={'generation_envs': 64, 'device_chunk_steps': 32},
               spans={}, window=(12.0, 16.0), trace=trace)


def _ring():
    """Loop iterations one second apart, the first ending at 11.02 s. Each
    ``fused_iter`` (id 10 * n) holds a ``dispatch`` of 4 ms, a ``host_block``
    of 0.9 s (0.7 s in iteration 14) that ends 10 ms before the iteration
    does; iteration 13 also holds an ``epoch_boundary`` of 50 ms with a
    ``checkpoint_write`` of 20 ms inside. The window is 12 .. 16 s: the
    ``host_block`` records that bound it end at 11.01 and 15.01 s."""
    records, counters = [], {'plies': 0, 'builder_plies': 0,
                             'windows_built': 0, 'windows_ingested': 0}
    for n in range(11, 18):
        t0, root = n - 0.98, 10 * n
        block_s = 0.7 if n == 14 else 0.9
        counters = {'plies': counters['plies'] + 2048,
                    'builder_plies': counters['builder_plies'] + 24 + n % 2,
                    'windows_built': (counters['windows_built']
                                      + (24 + n % 2) * 64 * 12),
                    'windows_ingested': counters['windows_ingested'] + 60}
        records.append(_rec('dispatch', t0, t0 + 0.004, root + 1, root))
        records.append(_rec('host_block', n + 0.01 - block_s, n + 0.01,
                            root + 2, root, **counters))
        end = n + 0.02
        if n == 13:
            records.append(_rec('checkpoint_write', n + 0.03, n + 0.05,
                                root + 4, root + 3, files=3))
            records.append(_rec('epoch_boundary', n + 0.02, n + 0.07,
                                root + 3, root, epoch=2))
            end = n + 0.08
        records.append(_rec('fused_iter', t0, end, root, None, dispatch=n))
    return records


def _rec(name, t0, t1, span_id, parent_id, **attrs):
    return {'name': name, 't0': t0, 't1': t1, 'span_id': span_id,
            'parent_id': parent_id, 'attrs': attrs}


@pytest.fixture
def ring(monkeypatch):
    from handyrl_tpu import telemetry
    records = _ring()
    monkeypatch.setattr(
        telemetry, 'spans',
        lambda name=None, since=None: [r for r in records
                                       if name in (None, r['name'])])
    return records


def test_program_span_keeps_the_records_that_end_in_the_window(ring):
    run = _run()
    # dispatch spans ending at 12.024 .. 15.024: four of them, 4 ms each
    assert program_span.read(run, 'dispatch') == {
        'value': pytest.approx(4.0), 'samples': 4}
    # host_block ends at 12.01, 13.01, 14.01 (0.7 s), 15.01
    got = program_span.read(run, 'host_block')
    assert got == {'value': pytest.approx(900.0), 'samples': 4}
    with pytest.raises(KeyError):          # no metric reads another statistic
        program_span.read(run, 'host_block', stat='max')
    assert program_span.read(run, 'checkpoint_write') == {
        'value': pytest.approx(20.0), 'samples': 1}
    assert program_span.read(run, 'no_such_span') is None


def test_program_span_minus_a_child_and_without_boundary_records(ring):
    run = _run()
    # iterations ending at 12.02, 13.08, 14.02, 15.02: 1.0 s long (1.06 with
    # the boundary); less the wait: 0.1, 0.16, 0.3 (the short wait), 0.1
    whole = program_span.read(run, 'fused_iter')
    assert whole == {'value': pytest.approx(1000.0), 'samples': 4}
    busy = program_span.read(run, 'fused_iter', minus='host_block')
    assert busy == {'value': pytest.approx(130.0), 'samples': 4}
    quiet = program_span.read(run, 'fused_iter', minus='host_block',
                              without='epoch_boundary')
    assert quiet == {'value': pytest.approx(100.0), 'samples': 3}


def test_program_span_also_gives_each_chunk_its_whole_wait(monkeypatch):
    """Where boundaries are frequent the wait for a chunk is mostly taken
    by the boundary's ``state_fetch`` and the next ``host_block`` returns at
    once: ``host_block`` alone is bimodal and its median reads 0.5 ms on a
    device that is busy all the time; with the state fetches that ended
    since the previous ``host_block`` every chunk reads its whole wait."""
    from handyrl_tpu import telemetry
    records, t, ident = [], 10.0, 0
    #  (wait in host_block, the iteration's boundary state fetch)
    series = [(0.95, 0.0), (0.95, 0.94), (0.0005, 0.94), (0.0005, 0.0),
              (0.95, 0.94), (0.0005, 0.94), (0.0005, 0.94), (0.0005, 0.0)]
    for wait, fetch in series:
        ident += 10
        t0 = t
        records.append(_rec('host_block', t + 0.002, t + 0.002 + wait,
                            ident + 1, ident))
        t += 0.002 + wait + 0.003
        if fetch:
            records.append(_rec('state_fetch', t, t + fetch, ident + 3,
                                ident + 2))
            records.append(_rec('epoch_boundary', t, t + fetch + 0.015,
                                ident + 2, ident))
            t += fetch + 0.015
        records.append(_rec('fused_iter', t0, t, ident, None))
    monkeypatch.setattr(telemetry, 'spans',
                        lambda name=None, since=None: list(records))
    run = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
              spans={}, window=(10.0, 99.0))
    alone = program_span.read(run, 'host_block')
    assert alone == {'value': pytest.approx(0.5), 'samples': 8}
    whole = program_span.read(run, 'host_block', also=['state_fetch'])
    # 0.95 of the fetch itself (three chunks) or 0.94 + 0.0005 (five)
    assert whole == {'value': pytest.approx(940.5), 'samples': 8}
    # the first record of the window takes the state fetch before it, also
    # where that ended outside the window; the last state fetch (no
    # host_block after it) belongs to a chunk that completed in no record
    second = [r for r in records if r['name'] == 'state_fetch'][1]
    late = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
               spans={}, window=(second['t1'] + 0.001, 99.0))
    assert program_span.read(late, 'host_block', also=['state_fetch']) == {
        'value': pytest.approx(940.5), 'samples': 5}
    # host work of the iterations without a boundary is what it was
    assert program_span.read(run, 'fused_iter', minus='host_block',
                             without='epoch_boundary')['value'] == \
        pytest.approx(5.0)


def test_checkpoint_write_ms_reads_what_the_boundary_tail_did(ring):
    """``epoch_stall_ms`` (the hooks' reading of a boundary from the state
    fetch's end to its own, retired with its reader ``span_tail``) was the
    outside-timed twin of the program's ``checkpoint_write`` span: the
    metric that stays, read as its own file asks."""
    args = Manifest().load_metric('checkpoint_write_ms')['args']
    assert program_span.read(_run(), **args) == \
        {'value': pytest.approx(20.0), 'samples': 1}
    late = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
               spans={}, window=(14.0, 16.0))   # no boundary inside
    assert program_span.read(late, **args) is None
    assert not os.path.exists(os.path.join(ROOT, 'benchmark', 'readers',
                                           'span_tail.py'))


def test_program_counter_ratio_is_growth_between_the_bounding_records(ring):
    run = _run()
    # host_block at 11.01 s opens (n = 11), at 15.01 s closes (n = 15):
    # four chunks: 8192 plies, builder plies 24 + 25 + 24 + 25 = 98
    share = program_counter_ratio.read(
        run, 'host_block', ['builder_plies', 'train_args.generation_envs'],
        'plies', scale=100)
    assert share == pytest.approx(100.0 * 98 * 64 / 8192)
    useful = program_counter_ratio.read(
        run, 'host_block', 'windows_ingested', 'windows_built', scale=100)
    assert useful == pytest.approx(100.0 * 240 / (98 * 64 * 12))
    assert program_counter_ratio.read(run, 'host_block', 'absent',
                                      'plies') is None
    assert program_counter_ratio.read(run, 'dispatch', 'plies',
                                      'plies') is None
    early = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
                spans={}, window=(5.0, 9.0))
    assert program_counter_ratio.read(early, 'host_block', 'builder_plies',
                                      'plies') is None


def test_a_program_without_the_ring_gives_nothing_to_read(monkeypatch):
    """The parent commit of the PR that brought the ring: the readers
    return None and do not raise."""
    from handyrl_tpu import telemetry
    monkeypatch.delattr(telemetry, 'spans')
    run = _run()
    assert program_span.read(run, 'dispatch') is None
    assert program_counter_ratio.read(run, 'host_block', 'builder_plies',
                                      'plies') is None


# ---------------------------------------------------------------------------
# trace_scope_time


def _write_trace(root, cell, text):
    from jax.profiler import ProfileData
    folder = os.path.join(str(root), '.bench_runs', cell, 'trace',
                          'plugins', 'profile', 'x')
    os.makedirs(folder)
    path = os.path.join(folder, 'host.xplane.pb')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def _scopes_text():
    with open(os.path.join(TESTDATA, 'scopes.xplane.txt')) as f:
        return ''.join(line for line in f if not line.startswith('#'))


@pytest.fixture
def traced(tmp_path):
    path = _write_trace(tmp_path, 'c', _scopes_text())
    reduced = reduce_trace.reduce(path, window_span='train_dispatch')
    assert reduced['window'] == [1000, 36000]
    assert reduced['modules'][MODULE] == pytest.approx([10e-6, 12e-6])
    return _run(trace=reduced)


@pytest.mark.parametrize('scope, first, second', [
    ('rollout', 1.0, 1.0), ('ingest', 6.0, 8.0), ('sgd', 2.0, 2.0),
    ('pack', 0.3, 0.3), ('unscoped', 0.7, 0.7)])
def test_scope_time_is_assigned_by_nesting(traced, scope, first, second):
    """Microseconds by hand (testdata/scopes.xplane.txt): the unlabelled
    ``while`` goes, whole, to the scope that holds most of its labelled self
    time, its unlabelled copy included; the unlabelled top-level copy and
    the gap are unscoped; the execution outside the window is left out."""
    got = trace_scope_time.read(traced, MODULE, scope, SCOPES)
    assert got['samples'] == 2
    assert got['value'] == pytest.approx((first + second) / 2 * 1e-3)


def test_scope_times_add_up_to_the_module(traced):
    """True by construction (``unscoped`` is the remainder); what is
    evidence is its two parts, each summed on its own: the unlabelled
    top-level copy (0.5 us) and the gap at the end (0.2 us)."""
    parts = [trace_scope_time.read(traced, MODULE, scope, SCOPES)['value']
             for scope in SCOPES + ['unscoped']]
    assert sum(parts) == pytest.approx(11e-3)     # (10 + 12) / 2 us
    rest = trace_scope_time.read(traced, MODULE, 'unscoped', SCOPES)
    assert rest['unlabelled_ops_ms'] == pytest.approx(0.5e-3)
    assert rest['gaps_ms'] == pytest.approx(0.2e-3)
    assert 'gaps_ms' not in trace_scope_time.read(traced, MODULE, 'ingest',
                                                  SCOPES)


def test_scope_time_finds_nothing_without_scopes_or_trace(tmp_path):
    assert trace_scope_time.read(_run(), MODULE, 'ingest', SCOPES) is None
    # a trace from before the phases were named: no tf_op holds a scope
    bare = _scopes_text().replace('/rollout/', '/').replace(
        '/ingest/', '/').replace('/sgd/', '/').replace('/pack/', '/')
    path = _write_trace(tmp_path, 'parent', bare)
    run = _run(trace=reduce_trace.reduce(path), name='parent')
    for scope in SCOPES + ['unscoped']:
        assert trace_scope_time.read(run, MODULE, scope, SCOPES) is None
    assert trace_scope_time.read(run, 'jit_absent', 'ingest',
                                 SCOPES) is None
    # the trace file gone
    lost = _run(trace=dict(run.trace, path=str(tmp_path / 'nowhere.pb')))
    assert trace_scope_time.read(lost, MODULE, 'ingest', SCOPES) is None


def test_scope_decoder_reads_the_trace_recorded_on_a_v5e():
    """The real format: on ``toy.xplane.pb`` the scope path is the metadata
    stat ``tf_op`` of ``%fusion.8`` and of nothing else; the ``while`` that
    holds it and the copies inside carry none, and nesting gives the
    ``while`` event to the labelled scope."""
    path = os.path.join(TESTDATA, 'toy.xplane.pb')
    modules, ops, names, paths = trace_scope_time.load(path)
    assert len(modules) == 5 and len(ops) == 65
    labelled = {names[key].split(' = ')[0]: path_
                for key, path_ in paths.items()}
    assert labelled == {
        '%fusion.8': 'jit(bench_toy_step)/while/body/closed_call/dot_general:'}
    runs = trace_scope_time.executions(path, 'jit_bench_toy_step',
                                       ('while',), 0, 1e18)
    assert len(runs) == 5
    for run in runs:
        # ProfileData reads the while at 1,295 ns and the module at ~2,300
        assert run['while'] == pytest.approx(1.295e-6, rel=2e-3)
        # the unlabelled top-level operations; the module also holds gaps
        assert run['while'] + run['unscoped'] <= run['module']
        assert run['unscoped'] > 0.8 * (run['module'] - run['while'])


# ---------------------------------------------------------------------------
# the nine metrics


@pytest.mark.parametrize('name', sorted(NEW_METRICS))
def test_metric_file_agrees_with_its_entry(name):
    """The seven that read what every cell's program gives list no cells
    (a new cell reports them under their one name); the two that need an
    epoch boundary in the window list the four conv cells, by name."""
    contracts.one_of_the_nine_agrees_with_its_entry(Manifest(), name)


def test_checkpoint_wait_ms_reads_the_loops_wait_for_its_writer(ring):
    """The tenth of the kind (PR 33): ``checkpoint_wait``, the loop's wait
    before it hands the next checkpoint to the writer thread."""
    shipped = Manifest()
    entry, spec = (shipped.metrics['checkpoint_wait_ms'],
                   shipped.load_metric('checkpoint_wait_ms'))
    assert (entry['source'], entry['layer'], spec['reader']) == (
        'program_span', 'param publish, checkpoint', 'program_span')
    assert entry['moves'] == 'train_windows_per_s'
    assert entry['workloads'] == contracts.FOUR
    assert spec['args'] == {'stage': 'checkpoint_wait', 'stat': 'median'}
    assert program_span.read(_run(), **spec['args']) is None   # no such span
    ring.append(_rec('checkpoint_wait', 13.025, 13.025004, 135, 133))
    ring.append(_rec('checkpoint_wait', 14.1, 14.13, 145, 143))
    assert program_span.read(_run(), **spec['args']) == {
        'value': pytest.approx(15.002), 'samples': 2}
