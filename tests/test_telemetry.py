"""Unified telemetry: registry math, merge rules, heartbeat piggyback over a
real Hub pair, Prometheus exposition, the append-safe JSONL sink, and (slow)
the distributed learner+worker run whose metrics_jsonl carries the merged
fleet aggregates the exporter also serves.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.telemetry import (MetricRegistry, TelemetryExporter,
                                   hist_quantile, merge_snapshots,
                                   metric_key, relabel, render_prometheus,
                                   split_key, summarize,
                                   validate_metrics_line)


# ---------------------------------------------------------------------------
# registry


def test_counter_concurrent_increments():
    reg = MetricRegistry()
    c = reg.counter('requests_total', role='g')

    def spin():
        for _ in range(5000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40000
    assert reg.snapshot()['counters']['requests_total{role="g"}'] == 40000


def test_metric_handles_are_cached_and_labeled():
    reg = MetricRegistry()
    assert reg.counter('a_total', x=1) is reg.counter('a_total', x=1)
    assert reg.counter('a_total', x=1) is not reg.counter('a_total', x=2)
    assert metric_key('a_total', {'b': 2, 'a': 1}) == 'a_total{a="1",b="2"}'
    assert split_key('a_total{a="1"}') == ('a_total', 'a="1"')
    assert split_key('plain') == ('plain', '')


def test_gauge_set_and_add():
    reg = MetricRegistry()
    g = reg.gauge('depth')
    g.set(3)
    g.add(2)
    assert reg.snapshot()['gauges']['depth'] == 5.0


def test_histogram_buckets_and_percentiles():
    reg = MetricRegistry()
    h = reg.histogram('lat_seconds', buckets=(0.01, 0.1, 1.0), stage='x')
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()['hists']['lat_seconds{stage="x"}']
    assert snap['buckets'] == [2, 1, 1, 1]     # one overflow bucket
    assert snap['count'] == 5
    assert abs(snap['sum'] - 5.56) < 1e-9
    # p50: rank 2.5 inside the first bucket (2 events, bounds 0..0.01)
    assert 0.0 < h.quantile(0.5) <= 0.1
    # p99 lands in the overflow bucket -> clamped to the last bound
    assert h.quantile(0.99) == 1.0
    # empty histogram quantile is defined
    assert hist_quantile((1.0,), [0, 0], 0, 0.5) == 0.0


def test_histogram_observe_agg_matches_sums():
    reg = MetricRegistry()
    h = reg.histogram('stage_seconds', stage='decode')
    h.observe_agg(0.5, 10)                      # 10 events, 50ms mean
    assert h.count == 10
    assert abs(h.sum - 0.5) < 1e-12


def test_snapshot_reset_semantics():
    reg = MetricRegistry()
    reg.counter('c_total').inc(7)
    reg.gauge('g').set(4)
    reg.histogram('h_seconds').observe(0.2)
    first = reg.snapshot(reset=True)
    assert first['counters']['c_total'] == 7
    second = reg.snapshot()
    assert second['counters']['c_total'] == 0   # counters restart
    assert second['hists']['h_seconds']['count'] == 0
    assert second['gauges']['g'] == 4.0         # gauges are levels, kept


def test_disabled_registry_is_inert(monkeypatch):
    monkeypatch.setattr(telemetry, '_ENABLED', False)
    reg = MetricRegistry()
    reg.counter('c_total').inc(5)
    reg.gauge('g').set(1)
    reg.histogram('h').observe(1.0)
    snap = reg.snapshot()
    assert snap['counters']['c_total'] == 0
    assert snap['gauges']['g'] == 0.0
    assert snap['hists']['h']['count'] == 0


def test_span_records_stage_histogram():
    hist = telemetry.REGISTRY.histogram('stage_seconds', stage='unit_select')
    before = hist.count, hist.sum
    with telemetry.trace_span('unit_select'):
        time.sleep(0.01)
    assert hist.count == before[0] + 1 and hist.sum >= before[1] + 0.01
    assert 'stage_seconds{stage="unit_select"}' in \
        telemetry.snapshot()['hists']


def test_span_records_its_parent():
    with telemetry.trace_span('unit_select'):
        with telemetry.trace_span('unit_decode'):
            pass
    decode = telemetry.spans(name='unit_decode')[-1]
    select = telemetry.spans(name='unit_select')[-1]
    assert decode['parent_id'] == select['span_id']
    assert select['parent_id'] is None


def test_stage_timer_mirrors_into_registry():
    from handyrl_tpu.utils.timing import StageTimer
    reg = MetricRegistry()
    timer = StageTimer(registry=reg)
    timer.add('assemble', 0.25, count=5)
    assert timer.snapshot()['assemble'] == {'s': 0.25, 'n': 5}
    h = reg.snapshot()['hists']['stage_seconds{stage="assemble"}']
    assert h['count'] == 5 and abs(h['sum'] - 0.25) < 1e-9


# ---------------------------------------------------------------------------
# merge rules


def _snap(counters=None, gauges=None, hists=None):
    return {'run_id': 'x', 'time': 0.0, 'counters': counters or {},
            'gauges': gauges or {}, 'hists': hists or {}}


def test_merge_counters_sum_gauges_sum_hists_add():
    h = {'bounds': [0.1, 1.0], 'buckets': [1, 2, 0], 'sum': 1.5, 'count': 3}
    a = _snap({'c_total': 2}, {'depth{gather="0"}': 3.0}, {'lat': dict(h)})
    b = _snap({'c_total': 5}, {'depth{gather="1"}': 4.0}, {'lat': dict(h)})
    merged = merge_snapshots([a, b, None, 'garbage'])
    assert merged['peers'] == 2                 # non-dicts skipped
    assert merged['counters']['c_total'] == 7
    # distinct label sets stay distinct (per-gather resolution survives)
    assert merged['gauges'] == {'depth{gather="0"}': 3.0,
                                'depth{gather="1"}': 4.0}
    assert merged['hists']['lat']['buckets'] == [2, 4, 0]
    assert merged['hists']['lat']['count'] == 6


def test_merge_skips_mismatched_bucket_bounds():
    a = _snap(hists={'lat': {'bounds': [0.1], 'buckets': [1, 0],
                             'sum': 0.05, 'count': 1}})
    b = _snap(hists={'lat': {'bounds': [0.2], 'buckets': [3, 0],
                             'sum': 0.3, 'count': 3}})
    merged = merge_snapshots([a, b])
    assert merged['hists']['lat']['count'] == 1   # peer with other bounds skipped


def test_merge_counts_mismatched_bucket_bounds():
    """The disagree path must drop-with-counter, never mis-add: the first
    peer's histogram survives untouched, every later disagreeing peer is
    counted — as a merged COUNTER (so the signal survives re-merging up
    the fleet tree and reaches the exposition) and as a top-level field."""
    a = _snap(hists={'lat': {'bounds': [0.1, 1.0], 'buckets': [1, 0, 0],
                             'sum': 0.05, 'count': 1}})
    b = _snap(hists={'lat': {'bounds': [0.2, 1.0], 'buckets': [3, 0, 0],
                             'sum': 0.3, 'count': 3}})
    c = _snap(hists={'lat': {'bounds': [0.1], 'buckets': [5, 0],
                             'sum': 0.5, 'count': 5}})
    merged = merge_snapshots([a, b, c])
    # first peer wins the geometry; neither disagreeing peer was mis-added
    assert merged['hists']['lat']['bounds'] == [0.1, 1.0]
    assert merged['hists']['lat']['buckets'] == [1, 0, 0]
    assert merged['hists']['lat']['count'] == 1
    assert abs(merged['hists']['lat']['sum'] - 0.05) < 1e-12
    assert merged['hist_bound_conflicts'] == 2
    assert merged['counters']['telemetry_hist_bound_conflicts_total'] == 2
    # the conflict counter itself re-merges like any flow
    again = merge_snapshots([merged, merged])
    assert again['counters']['telemetry_hist_bound_conflicts_total'] == 4
    # agreeing peers still add and report no conflict
    clean = merge_snapshots([a, a])
    assert clean['hists']['lat']['count'] == 2
    assert 'hist_bound_conflicts' not in clean
    assert 'telemetry_hist_bound_conflicts_total' not in clean['counters']


def test_summarize_reduces_histograms():
    h = {'bounds': [0.1, 1.0], 'buckets': [8, 1, 1], 'sum': 2.0, 'count': 10}
    out = summarize(_snap({'c_total': 1}, {'g': 2.0}, {'lat': h}))
    assert out['counters'] == {'c_total': 1}
    assert set(out['hists']['lat']) == {'count', 'sum', 'p50', 'p95', 'p99'}
    assert out['hists']['lat']['count'] == 10


# ---------------------------------------------------------------------------
# heartbeat piggyback through a real Hub pair


def test_heartbeat_piggyback_roundtrip_through_hub():
    """A worker/gather registry snapshot must survive the msgpack wire codec
    inside a heartbeat frame and come back out of peer_info ready to merge —
    exactly the path worker -> gather -> learner telemetry rides."""
    import socket
    from handyrl_tpu.connection import FramedConnection, HEARTBEAT_KIND, Hub

    reg = MetricRegistry()
    reg.counter('gather_uploads_total', gather='3', kind='episode').inc(12)
    reg.gauge('gather_episodes_per_sec', gather='3').set(2.5)
    reg.histogram('worker_task_seconds', role='g').observe(0.05)
    snap = reg.snapshot()

    hub = Hub()
    a, b = socket.socketpair()
    server_side, client_side = FramedConnection(a), FramedConnection(b)
    hub.attach(server_side)
    client_side.send((HEARTBEAT_KIND,
                      {'gather': 3, 'reconnects': 0, 'telemetry': snap}))
    deadline = time.time() + 10
    info = {}
    while time.time() < deadline:
        info = hub.peer_info_snapshot().get(server_side) or {}
        if info:
            break
        time.sleep(0.05)
    assert info.get('gather') == 3
    merged = merge_snapshots([info.get('telemetry')])
    key = 'gather_uploads_total{gather="3",kind="episode"}'
    assert merged['counters'][key] == 12
    assert merged['gauges']['gather_episodes_per_sec{gather="3"}'] == 2.5
    assert merged['hists']['worker_task_seconds{role="g"}']['count'] == 1
    hub.detach(server_side)
    client_side.close()


# ---------------------------------------------------------------------------
# Prometheus exposition + HTTP exporter


_PROM_LINE = re.compile(
    r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(_bucket|_sum|_count)?'
    r'(\{[^{}]*\})? [0-9eE.+-]+)$')


def assert_valid_exposition(body: str):
    lines = [l for l in body.splitlines() if l.strip()]
    assert lines, 'empty exposition'
    for line in lines:
        assert _PROM_LINE.match(line), 'bad exposition line: %r' % line


def test_render_prometheus_format():
    reg = MetricRegistry()
    reg.counter('requests_total', role='g').inc(3)
    reg.gauge('depth').set(1.5)
    reg.histogram('lat_seconds', buckets=(0.1, 1.0)).observe(0.05)
    body = render_prometheus([reg.snapshot()])
    assert_valid_exposition(body)
    assert '# TYPE requests_total counter' in body
    assert 'requests_total{role="g"} 3' in body
    assert 'depth 1.5' in body
    # histogram: cumulative buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.1"} 1' in body
    assert 'lat_seconds_bucket{le="+Inf"} 1' in body
    assert 'lat_seconds_count 1' in body


def test_exporter_falls_back_to_ephemeral_port():
    """A busy telemetry_port must not crash the learner: the exporter
    retries, falls back to an ephemeral port, logs the real one (kept on
    .port) and counts the fallback."""
    reg = MetricRegistry()
    reg.counter('pings_total').inc(1)
    blocker = TelemetryExporter(lambda: [reg.snapshot()], port=0).start()
    try:
        busy_port = blocker.port
        before = telemetry.counter('telemetry_port_fallbacks_total').value
        exporter = TelemetryExporter(lambda: [reg.snapshot()],
                                     port=busy_port).start()
        try:
            assert exporter.port != busy_port and exporter.port > 0
            assert telemetry.counter(
                'telemetry_port_fallbacks_total').value == before + 1
            body = urllib.request.urlopen(
                'http://127.0.0.1:%d/metrics' % exporter.port,
                timeout=10).read().decode()
            assert 'pings_total 1' in body
        finally:
            exporter.stop()
    finally:
        blocker.stop()


def test_exporter_serves_metrics_over_http():
    reg = MetricRegistry()
    reg.counter('pings_total').inc(2)
    fleet = relabel(reg.snapshot(), source='fleet')
    exporter = TelemetryExporter(
        lambda: [reg.snapshot(), fleet], port=0).start()
    try:
        url = 'http://127.0.0.1:%d/metrics' % exporter.port
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert_valid_exposition(body)
        assert 'pings_total 2' in body
        assert 'pings_total{source="fleet"} 2' in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                'http://127.0.0.1:%d/nope' % exporter.port, timeout=10)
    finally:
        exporter.stop()


# ---------------------------------------------------------------------------
# flight recorder + blackbox dumps


def test_flight_recorder_ring_bounds_and_stats():
    rec = telemetry.FlightRecorder(capacity=16)
    for i in range(40):
        rec.record('test', 'event %d' % i, i=i)
    st = rec.stats()
    assert st['events'] == 16 and st['total'] == 40 and st['dropped'] == 24
    assert rec.capacity == 16
    assert [e['i'] for e in rec.events()] == list(range(24, 40))


def test_flight_recorder_set_capacity_keeps_newest():
    rec = telemetry.FlightRecorder(capacity=64)
    for i in range(40):
        rec.record('test', 'e', i=i)
    rec.set_capacity(16)
    assert [e['i'] for e in rec.events()] == list(range(24, 40))


def test_flight_recorder_dump_schema(tmp_path):
    rec = telemetry.FlightRecorder(capacity=16)
    rec.record('guard', 'something tripped', detail=7)
    path = rec.dump('unit-test', directory=str(tmp_path),
                    context={'k': 1})
    assert path and os.path.exists(path)
    payload = json.load(open(path))
    assert payload['schema'] == 'handyrl_tpu.blackbox/1'
    assert payload['reason'] == 'unit-test'
    assert payload['context'] == {'k': 1}
    assert payload['pid'] == os.getpid()
    assert payload['events'][-1]['msg'] == 'something tripped'
    assert path in rec.stats()['dumps']
    # an empty directory disables dumping entirely
    assert rec.dump('unit-test', directory='') is None


def test_flight_recorder_disabled_is_inert(monkeypatch):
    rec = telemetry.FlightRecorder(capacity=16)
    monkeypatch.setattr(telemetry, '_ENABLED', False)
    rec.record('test', 'dropped')
    assert rec.stats()['total'] == 0


def test_recorder_only_toggle_leaves_metrics_live():
    rec = telemetry.FlightRecorder(capacity=16)
    telemetry.set_recorder_enabled(False)
    try:
        rec.record('test', 'dropped')
        telemetry.counter('recorder_toggle_probe_total').inc()
    finally:
        telemetry.set_recorder_enabled(True)
    assert rec.stats()['total'] == 0
    assert telemetry.counter('recorder_toggle_probe_total').value == 1
    rec.record('test', 'kept')
    assert rec.stats()['total'] == 1


def test_log_warnings_land_in_recorder():
    # compare the monotonic total, not a kind-filtered length: once the
    # ring reaches capacity (easy in a long suite run) every append
    # evicts an old event and the filtered count stays flat
    before = telemetry.recorder_stats()['total']
    telemetry.get_logger('recorder-test').warning('recorder mirror check')
    assert telemetry.recorder_stats()['total'] > before
    logged = [e for e in telemetry.recorder().events()
              if e.get('kind') == 'log']
    assert any('recorder mirror check' in e['msg'] for e in logged)


# ---------------------------------------------------------------------------
# SLO alert engine


def _gauge_snap(**gauges):
    return [{'counters': {}, 'gauges': dict(gauges), 'hists': {}}]


def _counter_snap(**counters):
    return [{'counters': dict(counters), 'gauges': {}, 'hists': {}}]


def test_alert_value_rule_sustain_and_clear_debounce():
    eng = telemetry.AlertEngine([
        {'name': 'deep_queue', 'metric': 'q_depth', 'kind': 'value',
         'op': '>', 'threshold': 5.0, 'for': 10.0, 'clear_for': 5.0}])
    blk = eng.evaluate(_gauge_snap(q_depth=9.0), now=100.0)
    assert blk['active'] == []                 # must sustain 10 s first
    blk = eng.evaluate(_gauge_snap(q_depth=9.0), now=111.0)
    assert blk['active'] == ['deep_queue']
    assert blk['fired'] == {'deep_queue': 1}
    assert telemetry.gauge('alerts_active', alert='deep_queue').value == 1
    blk = eng.evaluate(_gauge_snap(q_depth=1.0), now=112.0)
    assert blk['active'] == ['deep_queue']     # clear_for debounce holds
    blk = eng.evaluate(_gauge_snap(q_depth=1.0), now=120.0)
    assert blk['active'] == []
    assert telemetry.gauge('alerts_active', alert='deep_queue').value == 0


def test_alert_rate_rule_needs_two_samples():
    eng = telemetry.AlertEngine([
        {'name': 'err_burst', 'metric': 'errs_total', 'kind': 'rate',
         'op': '>', 'threshold': 1.0}])
    assert eng.evaluate(_counter_snap(errs_total=0),
                        now=10.0)['active'] == []
    blk = eng.evaluate(_counter_snap(errs_total=30), now=20.0)   # 3/s
    assert blk['active'] == ['err_burst']
    assert blk['values']['err_burst'] == 3.0


def test_alert_ratio_rule_burn_rate():
    eng = telemetry.AlertEngine([
        {'name': 'shed_burn', 'metric': 'shed_total', 'kind': 'ratio',
         'denominator': 'reqs_total', 'op': '>', 'threshold': 0.05}])
    eng.evaluate(_counter_snap(shed_total=0, reqs_total=0), now=0.0)
    blk = eng.evaluate(_counter_snap(shed_total=10, reqs_total=100),
                       now=10.0)
    assert blk['active'] == ['shed_burn']      # 10% of requests shed


def test_alert_arm_metric_gates_until_first_signal():
    eng = telemetry.AlertEngine([
        {'name': 'stall', 'metric': 'eps_total', 'kind': 'rate',
         'op': '<=', 'threshold': 0.0, 'arm_metric': 'eps_total'}])
    empty = _counter_snap()
    assert eng.evaluate(empty, now=1.0)['active'] == []
    assert eng.evaluate(empty, now=2.0)['active'] == []    # still unarmed
    live = _counter_snap(eps_total=5)
    eng.evaluate(live, now=3.0)
    blk = eng.evaluate(live, now=4.0)          # armed; zero rate breaches
    assert blk['active'] == ['stall']


def test_alert_engine_from_config_merge_and_disable():
    eng = telemetry.AlertEngine.from_config({'telemetry': {'alerts': {
        'rules': [
            {'name': 'ingest_stall', 'threshold': 1.0},
            {'name': 'custom_rule', 'metric': 'q_depth', 'kind': 'value',
             'op': '>', 'threshold': 2.0}]}}})
    names = eng.rule_names()
    assert 'custom_rule' in names
    assert names.count('ingest_stall') == 1    # override, not duplicate
    builtin = {str(s['name']) for s in telemetry.BUILTIN_ALERTS}
    assert builtin <= set(names)
    assert telemetry.AlertEngine.from_config(
        {'telemetry': {'alerts': False}}) is None
    assert telemetry.AlertEngine.from_config({'telemetry': False}) is None


def test_alert_maybe_evaluate_is_cadence_gated():
    eng = telemetry.AlertEngine([
        {'name': 'deep_queue', 'metric': 'q_depth', 'kind': 'value',
         'op': '>', 'threshold': 5.0}], interval=5.0)
    calls = []

    def collect():
        calls.append(1)
        return _gauge_snap(q_depth=9.0)

    eng.maybe_evaluate(collect, now=100.0)
    eng.maybe_evaluate(collect, now=101.0)     # inside the cadence window
    assert len(calls) == 1
    blk = eng.maybe_evaluate(collect, now=106.0)
    assert len(calls) == 2
    assert blk['active'] == ['deep_queue']


# ---------------------------------------------------------------------------
# status surface (/healthz, /statusz, main.py --status)


def test_exporter_serves_healthz_and_statusz():
    reg = MetricRegistry()
    exporter = TelemetryExporter(
        lambda: [reg.snapshot()], port=0,
        status=lambda: {'progress': {'epoch': 3},
                        'alerts': {'active': ['ingest_stall']}}).start()
    try:
        base = 'http://127.0.0.1:%d' % exporter.port
        assert urllib.request.urlopen(
            base + '/healthz', timeout=10).read() == b'ok\n'
        payload = json.loads(urllib.request.urlopen(
            base + '/statusz', timeout=10).read().decode())
        assert payload['progress'] == {'epoch': 3}
        assert payload['alerts']['active'] == ['ingest_stall']
        assert payload['pid'] == os.getpid()
        assert 'run_id' in payload and 'recorder' in payload
        rendered = telemetry.render_status(payload)
        assert 'ingest_stall' in rendered
        fetched = telemetry.fetch_statusz('127.0.0.1:%d' % exporter.port)
        assert fetched['pid'] == os.getpid()
    finally:
        exporter.stop()


# ---------------------------------------------------------------------------
# append-safe JSONL + schema checker


def test_append_jsonl_writes_complete_lines(tmp_path):
    from handyrl_tpu.utils.fs import append_jsonl
    path = str(tmp_path / 'metrics.jsonl')
    for i in range(3):
        append_jsonl(path, {'epoch': i, 'v': 'x' * 100})
    lines = open(path).read().splitlines()
    assert [json.loads(l)['epoch'] for l in lines] == [0, 1, 2]


def test_rotate_file_caps_metrics_jsonl(tmp_path):
    from handyrl_tpu.utils.fs import rotate_file
    path = str(tmp_path / 'metrics.jsonl')
    with open(path, 'w') as f:
        f.write('x' * 2048)
    assert not rotate_file(path, 1.0)          # under the cap: untouched
    assert not rotate_file(path, 0)            # 0 = rotation off
    assert rotate_file(path, 0.001)            # ~1 KB cap: rotate
    assert not os.path.exists(path)
    assert os.path.getsize(path + '.1') == 2048
    assert not rotate_file(path, 0.001)        # gone now: nothing to do


def test_validate_metrics_line_schema():
    good = json.dumps({'epoch': 1, 'steps': 10, 'episodes': 100,
                       'time': 1.0, 'run_id': 'abc',
                       'telemetry': {'counters': {}, 'gauges': {},
                                     'hists': {}}})
    rec = validate_metrics_line(good)
    assert rec['epoch'] == 1
    with pytest.raises(ValueError):
        validate_metrics_line(json.dumps({'epoch': 1}))
    with pytest.raises(ValueError):
        validate_metrics_line(good, fleet=True)   # no fleet_telemetry key


# ---------------------------------------------------------------------------
# distributed e2e: fleet aggregation lands in metrics_jsonl + the exporter


LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 2,
                          'forward_steps': 8, 'num_batchers': 1,
                          'model_dir': %(model_dir)r,
                          'metrics_jsonl': %(metrics)r,
                          'telemetry_port': %(port)d,
                          'fault_tolerance': {'heartbeat_interval': 1.0,
                                              'liveness_timeout': 15.0}}}
    args = apply_defaults(raw)
    learner = Learner(args=args, remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, flush=True)

if __name__ == '__main__':
    main()
'''

WORKER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.worker import worker_main
    args = {'worker_args': {'server_address': 'localhost', 'num_parallel': 2}}
    worker_main(args, [])

if __name__ == '__main__':
    main()
'''


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_distributed_run_exports_fleet_telemetry(tmp_path):
    """Learner + worker host over real TCP: per-epoch metrics_jsonl records
    must carry merged fleet telemetry (per-gather episodes/sec, upload
    counters, queue depths) consistent with the per-process snapshots, and
    the Prometheus endpoint must serve valid exposition text while the run
    is live."""
    entry_port, data_port, prom_port = 22910, 22911, 22912
    model_dir = str(tmp_path / 'models')
    metrics = str(tmp_path / 'metrics.jsonl')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {
        'model_dir': model_dir, 'metrics': metrics, 'port': prom_port})
    worker_py.write_text(WORKER_SCRIPT)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'PYTHONPATH': repo + os.pathsep
                + os.environ.get('PYTHONPATH', ''),
                'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                'HANDYRL_TPU_DATA_PORT': str(data_port)}

    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)],
                               env=base_env, stdout=learner_log,
                               stderr=subprocess.STDOUT)
    worker = None
    exposition = ''
    try:
        time.sleep(3)
        worker = subprocess.Popen([sys.executable, str(worker_py)],
                                  env=base_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)
        # scrape the exporter while the run is alive (retry until up)
        deadline = time.time() + 240
        url = 'http://127.0.0.1:%d/metrics' % prom_port
        while time.time() < deadline and learner.poll() is None:
            try:
                exposition = urllib.request.urlopen(
                    url, timeout=5).read().decode()
                if 'source="fleet"' in exposition:
                    break
            except OSError:
                pass
            time.sleep(2)
        learner.wait(timeout=300)
        worker.wait(timeout=120)
    finally:
        for proc in (worker, learner):
            if proc is not None and proc.poll() is None:
                proc.kill()
        learner_log.close()
        worker_log.close()

    assert_valid_exposition(exposition)
    assert 'source="fleet"' in exposition, \
        'exporter never served merged fleet metrics'

    lines = [l for l in open(metrics).read().splitlines() if l.strip()]
    assert lines, 'no metrics_jsonl records written'
    last = None
    for line in lines:
        last = validate_metrics_line(line, fleet=True)
    fleet = last['fleet_telemetry']
    # the acceptance trio: episodes/sec per gather (gauge), RPC retry
    # counters, and upload/queue depth gauges, all merged from heartbeats
    assert any(k.startswith('gather_episodes_per_sec')
               for k in fleet['gauges']), fleet['gauges']
    assert any(k.startswith('gather_upload_box_depth')
               for k in fleet['gauges'])
    assert any(k.startswith('gather_rpc_retries_total')
               for k in fleet['counters'])
    uploads = sum(v for k, v in fleet['counters'].items()
                  if k.startswith('gather_uploads_total'))
    assert uploads > 0
    # fleet episode counters are plausible against the learner's own view
    assert last['episodes'] >= 24
