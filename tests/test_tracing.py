"""Episode-lifecycle distributed tracing: sampling determinism, the
trace-context propagation chain (task_assign -> generate -> upload ->
ingest -> train_step) through the real ledger/gather/batcher components,
policy-lag accounting at window selection, and (slow) the full TCP fleet
whose one trace file links spans from >= 3 processes by shared trace_ids
while policy_lag / rho_clip_fraction land in metrics_jsonl and /metrics.
"""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.request
from collections import deque

import numpy as np
import pytest

from handyrl_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def trace_dir(tmp_path):
    """Route tracing into a tmp dir for the duration of one test, then
    restore the off state (other tests must see tracing disabled)."""
    d = str(tmp_path / 'traces')
    telemetry.configure_tracing(d, 1.0, force=True)
    try:
        yield d
    finally:
        telemetry.trace_flush()
        telemetry.configure_tracing('', 1.0, force=True)
        os.environ.pop('HANDYRL_TPU_TRACE', None)
        os.environ.pop('HANDYRL_TPU_TRACE_RATE', None)


def read_events(d):
    telemetry.trace_flush()
    events = []
    for path in glob.glob(os.path.join(d, 'trace-*.jsonl')):
        for line in open(path):
            if line.strip():
                events.append(json.loads(line))
    return events


# ---------------------------------------------------------------------------
# trace id + sampling


def test_episode_trace_id_derivation():
    assert telemetry.episode_trace_id({'role': 'g', 'sample_key': 7}) == 'g7'
    assert telemetry.episode_trace_id({'role': 'e', 'sample_key': 0}) == 'e0'
    # no server-stamped sample_key -> no trace context
    assert telemetry.episode_trace_id({'role': 'g'}) is None
    assert telemetry.episode_trace_id(None) is None
    assert telemetry.episode_trace_id('not-a-dict') is None


def test_sampling_is_deterministic_and_rate_shaped(trace_dir):
    # rate 1: everything kept; rate 0: nothing; fractional: deterministic
    assert telemetry.trace_sampled('g1')
    telemetry.configure_tracing(trace_dir, 0.0, force=True)
    assert not telemetry.trace_sampled('g1')
    telemetry.configure_tracing(trace_dir, 0.25, force=True)
    ids = ['g%d' % i for i in range(400)]
    kept = [i for i in ids if telemetry.trace_sampled(i)]
    # deterministic: the same decision on every call (every process)
    assert kept == [i for i in ids if telemetry.trace_sampled(i)]
    assert 40 < len(kept) < 160          # ~25% of 400
    # unsampled ids produce no events
    telemetry.trace_event('generate', trace_id=(set(ids) - set(kept)).pop())
    telemetry.trace_event('generate', trace_id=kept[0])
    events = [e for e in read_events(trace_dir) if e['name'] == 'generate']
    assert len(events) == 1
    assert events[0]['args']['trace_id'] == kept[0]


def test_tracing_off_is_inert(tmp_path):
    telemetry.configure_tracing('', 1.0, force=True)
    assert not telemetry.trace_enabled()
    assert not telemetry.trace_sampled('g1')
    telemetry.trace_event('generate', trace_id='g1')   # must be a no-op
    with telemetry.trace_span('generate', trace_id='g1'):
        pass
    telemetry.trace_flush()
    telemetry.finalize_trace()


def test_trace_span_records_stage_histogram_and_event(trace_dir):
    before = telemetry.REGISTRY.histogram('stage_seconds',
                                          stage='unit_span').count
    with telemetry.trace_span('unit_span', trace_id='g3'):
        time.sleep(0.01)
    hist = telemetry.REGISTRY.histogram('stage_seconds', stage='unit_span')
    assert hist.count == before + 1
    ev = [e for e in read_events(trace_dir) if e['name'] == 'unit_span']
    assert len(ev) == 1
    assert ev[0]['dur'] >= 10000          # microseconds
    assert ev[0]['args']['trace_id'] == 'g3'
    assert ev[0]['args']['run_id'] == telemetry.run_id()
    # the trace file carries the ring's ids
    rec = telemetry.spans(name='unit_span')[-1]
    assert ev[0]['args']['span_id'] == rec['span_id']
    assert ev[0]['args']['parent_id'] is None


# ---------------------------------------------------------------------------
# the span primitive: ring, parents, attributes, profiler annotations


def _named(records, name):
    return [r for r in records if r['name'] == name]


def test_span_parent_ids_nest_and_unwind_on_exceptions():
    t_start = time.perf_counter()
    with telemetry.trace_span('unit_outer') as outer:
        with telemetry.trace_span('unit_inner'):
            pass
        with pytest.raises(RuntimeError):
            with telemetry.trace_span('unit_raises'):
                with telemetry.trace_span('unit_deep'):
                    raise RuntimeError('boom')
        # the stack unwound: the next child hangs off the outer span again
        with telemetry.trace_span('unit_after'):
            pass
    with telemetry.trace_span('unit_root'):
        pass
    recs = telemetry.spans(since=t_start)
    by_name = {r['name']: r for r in recs}
    outer_id = by_name['unit_outer']['span_id']
    assert by_name['unit_outer']['parent_id'] is None
    assert by_name['unit_root']['parent_id'] is None
    for child in ('unit_inner', 'unit_raises', 'unit_after'):
        assert by_name[child]['parent_id'] == outer_id
    assert by_name['unit_deep']['parent_id'] == \
        by_name['unit_raises']['span_id']
    # finished spans, oldest first, with ends inside their parent
    assert [r['name'] for r in recs] == [
        'unit_inner', 'unit_deep', 'unit_raises', 'unit_after',
        'unit_outer', 'unit_root']
    assert by_name['unit_outer']['t0'] <= by_name['unit_inner']['t0'] \
        <= by_name['unit_inner']['t1'] <= by_name['unit_outer']['t1']
    # a span holds its finished direct children (the fused loop's per-chunk
    # split reads them)
    assert [c.name for c in outer.children] == [
        'unit_inner', 'unit_raises', 'unit_after']
    assert 0 < outer.child_seconds('unit_raises') <= outer.seconds
    assert outer.child_seconds('unit_deep') == 0.0       # a grandchild


def test_span_attributes_given_at_open_or_set_before_close_are_kept():
    with telemetry.trace_span('unit_attrs', rows=3) as span:
        span.set(plies=2048, note='after the work')
    rec = _named(telemetry.spans(), 'unit_attrs')[-1]
    assert rec['attrs'] == {'rows': 3, 'plies': 2048,
                            'note': 'after the work'}


def test_span_ring_is_bounded_and_since_filters():
    for _ in range(3):
        with telemetry.trace_span('unit_ring'):
            pass
    t_mid = time.perf_counter()
    with telemetry.trace_span('unit_ring'):
        pass
    assert len(_named(telemetry.spans(since=t_mid), 'unit_ring')) == 1
    assert len(telemetry.spans(name='unit_ring')) >= 4
    assert telemetry.spans(name='unit_ring', since=time.perf_counter()) == []
    for _ in range(telemetry.SPAN_RING_SIZE + 10):
        with telemetry.trace_span('unit_flood'):
            pass
    held = telemetry.spans()
    assert len(held) == telemetry.SPAN_RING_SIZE
    assert {r['name'] for r in held} == {'unit_flood'}   # oldest fell out


def test_span_with_telemetry_off_records_nothing(monkeypatch):
    monkeypatch.setattr(telemetry, '_ENABLED', False)
    before = len(telemetry.spans())
    hist = telemetry.REGISTRY.histogram('stage_seconds', stage='unit_off')
    with telemetry.trace_span('unit_off', rows=1) as span:
        span.set(more=2)
        with telemetry.trace_span('unit_off'):
            pass
    assert span.t1 is None and span.children == ()
    assert len(telemetry.spans()) == before
    assert telemetry.spans(name='unit_off') == []
    assert hist.count == 0


def test_span_is_a_profiler_annotation_on_the_host_plane(tmp_path):
    """Inside any jax profiler session the program's spans are host events
    named ``handyrl:<name>`` on the trace's own timeline."""
    import jax
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.trace_span('unit_profiled', step_num=7):
            with telemetry.trace_span('unit_profiled_child'):
                time.sleep(0.002)
    found = glob.glob(os.path.join(str(tmp_path), '**', '*.xplane.pb'),
                      recursive=True)
    assert found
    events = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != '/host:CPU':
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(telemetry.SPAN_ANNOTATION_PREFIX):
                    events[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(events) == {'handyrl:unit_profiled',
                           'handyrl:unit_profiled_child'}
    start, dur = events['handyrl:unit_profiled']
    c_start, c_dur = events['handyrl:unit_profiled_child']
    assert c_dur >= 2e6 and start <= c_start and c_start + c_dur <= start + dur


# ---------------------------------------------------------------------------
# propagation: one synthetic episode through ledger -> gather -> batcher


def _synthetic_task_episode(sample_key=7, model_epoch=1):
    """One geese-geometry episode stamped like a served generation task."""
    from helpers import _synthetic_geese_episodes
    rng = np.random.RandomState(3)
    ep = _synthetic_geese_episodes(1, rng, min_steps=24, max_steps=24)[0]
    players = ep['args']['player']
    ep['args'] = {'role': 'g', 'player': players,
                  'model_id': {p: model_epoch for p in players},
                  'sample_key': sample_key}
    return ep


def test_trace_context_propagates_gather_ledger_batcher(trace_dir):
    """The unit half of the propagation satellite: one synthetic episode
    rides the REAL components — TaskLedger.assign/admit (learner),
    UploadTrace (gather), Batcher/TracedBatch (trainer) — and every span
    shares the derived trace_id with causally ordered stages."""
    from handyrl_tpu.fault import TaskLedger
    from handyrl_tpu.train import Batcher, TracedBatch
    from handyrl_tpu.worker import UploadTrace

    ep = _synthetic_task_episode(sample_key=7)
    tid = telemetry.episode_trace_id(ep['args'])
    assert tid == 'g7'

    # learner: assignment books the task and births the trace context
    ledger = TaskLedger()
    endpoint = object()
    ledger.assign(endpoint, ep['args'])
    assert 'task_id' in ep['args']

    # worker: the generate span (the real Generator.execute wraps exactly
    # this call around env stepping)
    with telemetry.trace_span('generate', trace_id=tid):
        time.sleep(0.002)

    # gather: stash -> server-ack upload span
    upload = UploadTrace(gather_id=0)
    upload.stash('episode', ep)
    upload.shipped('episode')

    # learner: ledger delivery (the ingest event) + consumption stamp
    admitted = ledger.admit([ep])
    assert admitted == [ep]
    ep['recv_time'] = time.time()

    # trainer: the batcher selects/builds and wraps the trace ids; the
    # train_step event carries them (what Trainer.train emits at dispatch)
    args = {'turn_based_training': False, 'observation': True,
            'forward_steps': 8, 'burn_in_steps': 0, 'compress_steps': 4,
            'maximum_episodes': 1000, 'batch_size': 2, 'num_batchers': 1}
    batcher = Batcher(args, deque([ep]))
    batcher.run()
    try:
        wrapped = batcher.batch(timeout=60)
    finally:
        batcher.stop()
    assert isinstance(wrapped, TracedBatch)
    assert wrapped.trace_ids == [tid]
    telemetry.trace_event('train_step', dur=0.001, always=True,
                          trace_ids=wrapped.trace_ids, steps=1)

    # duplicate admission must NOT re-emit the ingest hop
    assert ledger.admit([dict(ep)]) == []

    events = read_events(trace_dir)
    by_stage = {}
    for e in events:
        a = e.get('args') or {}
        if a.get('trace_id') == tid or tid in (a.get('trace_ids') or ()):
            by_stage.setdefault(e['name'], []).append(e)
    for stage in ('task_assign', 'generate', 'upload', 'ingest',
                  'train_step'):
        assert stage in by_stage, 'missing %s span for %s' % (stage, tid)
        assert len(by_stage[stage]) == 1
    # causal nesting: each hop starts no earlier than the previous one
    order = [by_stage[s][0]['ts'] for s in
             ('task_assign', 'generate', 'upload', 'ingest', 'train_step')]
    assert order == sorted(order), order
    # the upload span COVERS its stash->ack residence (ingest falls after)
    up = by_stage['upload'][0]
    assert by_stage['ingest'][0]['ts'] >= up['ts']

    # trace_report sees one complete chain over these events
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts', 'trace_report.py'),
         trace_dir, '--json'], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout)
    assert report['complete_chains'] == 1
    assert report['order_violations'] == 0
    assert report['generation_to_gradient_seconds']['n'] == 1


def test_shm_descriptor_carries_trace_ids(trace_dir):
    from handyrl_tpu.ops.shm_batch import SharedBatch
    sb = SharedBatch({'x': 1}, lambda: None, trace_ids=['g7'])
    assert sb.trace_ids == ['g7']
    assert SharedBatch({'x': 1}, lambda: None).trace_ids is None


# ---------------------------------------------------------------------------
# policy-lag accounting at window selection


def test_batcher_observes_policy_lag_and_sample_age():
    from handyrl_tpu.train import Batcher

    ep = _synthetic_task_episode(sample_key=9, model_epoch=2)
    ep['recv_time'] = time.time() - 5.0
    args = {'turn_based_training': False, 'observation': True,
            'forward_steps': 8, 'burn_in_steps': 0, 'compress_steps': 4,
            'maximum_episodes': 1000, 'batch_size': 2, 'num_batchers': 1}
    batcher = Batcher(args, deque([ep]))
    batcher.epoch_fn = lambda: 6
    lag0, lag_sum0 = batcher._m_lag.count, batcher._m_lag.sum
    age0, age_sum0 = batcher._m_age.count, batcher._m_age.sum
    batcher.run()
    try:
        batcher.batch(timeout=60)
    finally:
        batcher.stop()
    # batch_size=2 windows from the one episode: 2 selections, 4 players
    # each -> 8 lag observations of (6 - 2) = 4 epochs, 2 age observations
    assert batcher._m_lag.count >= lag0 + 8
    lag_mean = ((batcher._m_lag.sum - lag_sum0)
                / (batcher._m_lag.count - lag0))
    assert abs(lag_mean - 4.0) < 1e-6
    assert batcher._m_age.count >= age0 + 2
    age_mean = ((batcher._m_age.sum - age_sum0)
                / (batcher._m_age.count - age0))
    assert 4.0 < age_mean < 30.0


# ---------------------------------------------------------------------------
# slow: the real TCP fleet writes one linked multi-process trace


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
    learner = Learner(args=apply_defaults(raw), remote=True)
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
def test_fleet_trace_links_three_processes(tmp_path):
    """Learner + worker host over real TCP with HANDYRL_TPU_TRACE set: one
    trace file must hold spans from >= 3 distinct processes (learner,
    gather, worker) linked by shared trace_ids covering
    task_assign -> generate -> upload -> ingest (-> train_step), the
    collated Chrome JSON must parse, trace_report must find a non-empty
    generation->gradient critical path, and policy_lag /
    rho_clip_fraction must appear per epoch in metrics_jsonl AND in the
    live Prometheus exposition."""
    entry_port, data_port, prom_port = 23210, 23211, 23212
    trace_d = str(tmp_path / 'traces')
    metrics = str(tmp_path / 'metrics.jsonl')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {
        'model_dir': str(tmp_path / 'models'), 'metrics': metrics,
        'port': prom_port})
    worker_py.write_text(WORKER_SCRIPT)

    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'HANDYRL_TPU_TRACE': trace_d, 'HANDYRL_TPU_TRACE_RATE': '1.0',
           'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
           'HANDYRL_TPU_DATA_PORT': str(data_port),
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}
    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)], env=env,
                               stdout=learner_log, stderr=subprocess.STDOUT)
    worker = None
    exposition = ''
    try:
        time.sleep(3)
        worker = subprocess.Popen([sys.executable, str(worker_py)], env=env,
                                  stdout=worker_log,
                                  stderr=subprocess.STDOUT)
        deadline = time.time() + 240
        url = 'http://127.0.0.1:%d/metrics' % prom_port
        while time.time() < deadline and learner.poll() is None:
            try:
                body = urllib.request.urlopen(url, timeout=5).read().decode()
                if 'rho_clip_fraction' in body and 'policy_lag' in body:
                    exposition = body
                    break
                exposition = exposition or body
            except OSError:
                pass
            time.sleep(2)
        assert learner.wait(timeout=300) == 0
        worker.wait(timeout=120)
    finally:
        for proc in (worker, learner):
            if proc is not None and proc.poll() is None:
                proc.kill()
        learner_log.close()
        worker_log.close()

    # learning-dynamics + policy-lag metrics per epoch in metrics_jsonl
    lines = [json.loads(l) for l in open(metrics) if l.strip()]
    assert lines
    last = lines[-1]
    for key in ('policy_lag', 'rho_clip_fraction', 'entropy', 'grad_norm'):
        assert key in last, 'metrics_jsonl missing %s: %s' % (key, last)
    assert 0.0 <= last['rho_clip_fraction'] <= 1.0
    # ... and live on the exporter while the run was up
    assert 'rho_clip_fraction' in exposition
    assert 'policy_lag' in exposition

    # the collated Chrome trace parses and links >= 3 processes by id
    finalized = glob.glob(os.path.join(trace_d, 'trace-*.json'))
    assert finalized, 'learner did not collate the Chrome trace'
    events = json.load(open(finalized[0]))['traceEvents']
    assert events
    sys.path.insert(0, os.path.join(REPO, 'scripts'))
    import trace_report
    chains = trace_report.build_chains(events)
    full = 0
    linked_pids = set()
    for tid, stages in chains.items():
        assert not trace_report.chain_errors(stages), (tid, stages)
        linked_pids.update(pid for _ts, _dur, pid in stages.values())
        if {'task_assign', 'generate', 'upload', 'ingest'} <= set(stages):
            full += 1
    assert len(linked_pids) >= 3, \
        'want spans from learner+gather+worker, got %d pids' % len(linked_pids)
    assert full >= 1

    # trace_report: non-empty generation->gradient critical path
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts', 'trace_report.py'),
         trace_d, '--json'], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    report = json.loads(out.stdout)
    assert report['complete_chains'] >= 1
    assert report['processes'] >= 3
    assert report['generation_to_gradient_seconds']['n'] >= 1
