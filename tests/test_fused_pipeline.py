"""The fully-fused device loop: ONE dispatch = rollout chunk + on-device
window ingest + K SGD steps (ops/fused_pipeline.py). End-to-end learner runs
for both ingest layouts, plus resume."""

import json

import pytest

from handyrl_tpu.config import apply_defaults
from handyrl_tpu.models import build
from handyrl_tpu.train import Learner


def _ttt_raw(tmp_path, **over):
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            # batch 12 is not divisible by the 8-device test mesh, so the
            # trainer stays single-device — the device-ingest requirement
            'batch_size': 12, 'forward_steps': 4, 'compress_steps': 2,
            'update_episodes': 40, 'minimum_episodes': 40, 'epochs': 2,
            'generation_envs': 16, 'num_batchers': 1,
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 4,
            'model_dir': str(tmp_path / 'models'),
            'metrics_jsonl': str(tmp_path / 'metrics.jsonl'),
        },
    }
    raw['train_args'].update(over)
    return raw


@pytest.mark.timeout(600)
def test_tictactoe_fused_pipeline_learner(tmp_path, capsys):
    args = apply_defaults(_ttt_raw(tmp_path))
    learner = Learner(args=args)
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(turn mode)' in out
    # the single-device downgrade (batch 12 on 8 devices) is kept, and the
    # start-up line is where it shows
    assert '"found": 8, "used": 1, "mesh": null' in out
    assert 'loss =' in out          # metric futures drained and printed
    assert learner.model_epoch == 2
    assert learner.num_returned_episodes >= 80
    assert learner.trainer.steps > 0
    assert (tmp_path / 'models' / '2.ckpt').exists()
    assert (tmp_path / 'models' / 'trainer_state.ckpt').exists()
    # metrics JSONL carries the dispatch count (host round trips per epoch)
    rows = [json.loads(line)
            for line in (tmp_path / 'metrics.jsonl').read_text().splitlines()]
    assert rows and rows[-1]['dispatches_gen'] > 0
    assert rows[-1]['steps'] == learner.trainer.steps


@pytest.mark.timeout(600)
def test_fused_pipeline_ingest_accounting(tmp_path):
    """windows_ingested must be the CUMULATIVE ingest count, not the ring
    size (which saturates at capacity once the ring wraps)."""
    args = apply_defaults(_ttt_raw(
        tmp_path, maximum_episodes=2, replay_windows_per_episode=2))
    learner = Learner(args=args)
    learner.run()
    capacity = learner.trainer.replay.capacity
    assert capacity == 4
    stats = learner.trainer.replay_stats
    # ~80 episodes x >=1 window each went through a 4-row ring
    assert stats['windows_ingested'] > capacity * 4
    assert stats['samples_drawn'] > 0


@pytest.mark.timeout(600)
def test_geese_fused_pipeline_learner(tmp_path, capsys):
    raw = {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {
            'turn_based_training': False, 'observation': True,
            'gamma': 0.99, 'forward_steps': 8, 'compress_steps': 4,
            'batch_size': 12, 'update_episodes': 10, 'minimum_episodes': 10,
            'epochs': 1, 'generation_envs': 8, 'num_batchers': 1,
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 4,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE',
            'model_dir': str(tmp_path / 'models'),
        },
    }
    args = apply_defaults(raw)
    learner = Learner(args=args, net=build('GeeseNet', layers=2, filters=16))
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(solo mode)' in out
    assert learner.model_epoch == 1
    assert learner.trainer.steps > 0
    assert (tmp_path / 'models' / '1.ckpt').exists()


@pytest.mark.timeout(600)
def test_geister_fused_pipeline_learner(tmp_path, capsys):
    """Geister (turn-based, observation=True, recurrent DRC, dict
    observations) now runs the FUSED pipeline: the ingest gate admits
    observation=True via the compact 'turn' layout (equivalence proven by
    tests/test_turn_layout_parity.py), and the windower handles the
    pytree observation. This pins geister's sample reuse to
    sgd_steps_per_chunk instead of the threaded trainer's free spin."""
    from handyrl_tpu.models.geister import GeisterNet

    raw = {
        'env_args': {'env': 'Geister'},
        'train_args': {
            'turn_based_training': True, 'observation': True,
            'gamma': 0.9, 'forward_steps': 4, 'burn_in_steps': 2,
            'compress_steps': 2, 'batch_size': 8, 'update_episodes': 8,
            'minimum_episodes': 8, 'epochs': 2, 'generation_envs': 8,
            'num_batchers': 1, 'device_generation': True,
            'device_replay': True, 'sgd_steps_per_chunk': 2,
            'model_dir': str(tmp_path / 'models'),
        },
    }
    args = apply_defaults(raw)
    learner = Learner(args=args,
                      net=GeisterNet(filters=8, drc_layers=1))
    learner.run()
    out = capsys.readouterr().out
    assert 'fused device pipeline' in out and '(turn mode' in out
    assert learner.model_epoch == 2
    assert learner.trainer.steps > 0
    assert learner.trainer.device_cfg.observation is False
    assert learner.trainer.cfg.observation is True
    assert (tmp_path / 'models' / '2.ckpt').exists()


@pytest.mark.parametrize('value', [False, True])
def test_a_fused_pipeline_key_is_refused(tmp_path, value):
    """The switch went with the split learner it selected: a config that
    still sets it must not silently run something else."""
    with pytest.raises(ValueError, match='only device-ingest learner'):
        apply_defaults(_ttt_raw(tmp_path, fused_pipeline=value))


@pytest.mark.timeout(600)
def test_fused_pipeline_resume(tmp_path, capsys):
    args = apply_defaults(_ttt_raw(tmp_path))
    learner = Learner(args=args)
    learner.run()
    steps_before = learner.trainer.steps
    assert learner.model_epoch == 2

    args2 = apply_defaults(_ttt_raw(tmp_path, restart_epoch=2, epochs=3))
    learner2 = Learner(args=args2)
    assert learner2.trainer.steps == steps_before   # optimizer state resumed
    learner2.run()
    assert learner2.model_epoch == 3
    assert learner2.trainer.steps > steps_before
    assert (tmp_path / 'models' / '3.ckpt').exists()


# ---------------------------------------------------------------------------
# the loop measures itself: spans, counters, named phases, stalls


def telemetry_metric_args(name):
    """The arguments the benchmark's metric file gives its reader."""
    import os

    from benchmark.manifest import ROOT
    with open(os.path.join(ROOT, 'benchmark', 'metrics',
                           name + '.json')) as f:
        return json.load(f)['args']


@pytest.mark.timeout(600)
def test_fused_loop_spans_counters_and_named_phases(tmp_path, monkeypatch):
    """A short fused run leaves, per iteration, one ``fused_iter`` span with
    ``dispatch`` and ``host_block`` children; the ``host_block`` counters
    agree with the pipeline's own; the epoch records carry the ``fused``
    block; and the fused program's phases carry their names."""
    import time

    import jax
    import jax.numpy as jnp

    from handyrl_tpu import telemetry
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    built = []
    init = FusedPipeline.__init__

    def remember(self, *args, **kwargs):
        built.append(self)
        init(self, *args, **kwargs)
    monkeypatch.setattr(FusedPipeline, '__init__', remember)
    booked = []
    observe = telemetry.ChunkMonitor.observe

    def remember_chunk(self, dispatch, interval_s, split):
        booked.append(dict(split, interval=interval_s))
        return observe(self, dispatch, interval_s, split)
    monkeypatch.setattr(telemetry.ChunkMonitor, 'observe', remember_chunk)

    t_start = time.perf_counter()
    learner = Learner(args=apply_defaults(_ttt_raw(tmp_path)))
    learner.run()
    fp, = built
    recs = telemetry.spans(since=t_start)
    iters = [r for r in recs if r['name'] == 'fused_iter']
    assert len(iters) == fp.dispatches
    assert [r['attrs']['dispatch'] for r in iters] == \
        list(range(1, fp.dispatches + 1))
    assert iters[0]['attrs']['warm'] == 1 and iters[-1]['attrs']['warm'] == 0
    assert all(r['parent_id'] is None for r in iters)
    children = {}
    for rec in recs:
        children.setdefault(rec['parent_id'], []).append(rec['name'])
    boundaries = [r for r in recs if r['name'] == 'epoch_boundary']
    # the first boundary made the next iteration's step itself, before it
    # fetched the train state; the second ends the run and makes none
    assert [r['attrs']['enqueued_first'] for r in boundaries] == [1, 0]
    ahead = False
    for n, it in enumerate(iters):
        names = children[it['span_id']]
        # one step a chunk (the enqueue, and the fetch of the chunk before,
        # which the first iteration has not): the iteration's own, or the
        # one that the boundary which closed the last iteration made for it
        assert names.count('dispatch') == (0 if ahead else 1)
        assert names.count('host_block') == (0 if ahead or n == 0 else 1)
        assert names.count('chunk_account') == names.count('eval_share') == 1
        held = [b for b in boundaries if b['parent_id'] == it['span_id']]
        ahead = bool(held and held[0]['attrs']['enqueued_first'])
        if ahead:
            inside = children[held[0]['span_id']]
            assert inside.count('dispatch') == inside.count('host_block') == 1
            assert inside.index('actor_refresh') < inside.index('dispatch') \
                < inside.index('host_block') < inside.index('state_fetch') \
                < inside.index('metrics_write')
    assert not ahead
    assert sum(r['name'] == 'dispatch' for r in recs) == fp.dispatches
    # every eval share is told how long it may hold the loop: a share of
    # its own iteration's training stretch
    shares = [r for r in recs if r['name'] == 'eval_share']
    assert all(r['attrs']['budget_ms'] >= 0 for r in shares)
    assert any(r['attrs']['budget_ms'] > 0 for r in shares)
    assert [r['attrs']['epoch'] for r in boundaries] == [1, 2]
    for boundary in boundaries:
        assert set(children[boundary['span_id']]) >= {
            'state_fetch', 'checkpoint_wait', 'metrics_write'}
    # serialisation and the writes are the writer thread's (root spans
    # there), one set a boundary; the run ends on a boundary, so the final
    # flush has nothing new to write
    writes = [r for r in recs if r['name'] == 'checkpoint_write']
    assert len(writes) == len(boundaries)
    assert all(r['attrs']['files'] == 3 and r['attrs']['bytes'] > 0
               and r['parent_id'] is None for r in writes)
    names = [r['name'] for r in recs]
    assert names.count('checkpoint_serialize') == 2 * len(writes)
    assert names.count('checkpoint_publish_gc') == len(writes)

    # counters: cumulative, one host_block per fetched chunk (the last one
    # is the loop's drain), in step with the pipeline's own
    blocks = [r for r in recs if r['name'] == 'host_block']
    assert len(blocks) == fp.dispatches
    last = blocks[-1]['attrs']
    chunk_plies = fp.chunk_steps * fp.n_envs
    assert [b['attrs']['plies'] for b in blocks] == \
        [chunk_plies * (i + 1) for i in range(len(blocks))]
    assert 0 < last['builder_plies'] <= fp.chunk_steps * len(blocks)
    assert last['builder_plies'] * fp.n_envs <= last['plies']
    assert last['windows_ingested'] == fp.windows_ingested_host > 0
    # the builder makes the windows of the games that ended, one each loop
    # iteration, and the ring gets every one: 1 to W a game
    assert last['windows_built'] == last['windows_ingested']
    # (TicTacToe's 5-9 plies are one or two windows of forward_steps 4)
    assert last['episodes'] <= last['windows_ingested'] \
        <= last['episodes'] * 2
    assert last['episodes'] == fp.episodes_host \
        == learner.num_returned_episodes
    assert last['builder_plies'] <= last['episodes']
    assert blocks[0]['attrs']['sgd_steps'] == 0          # a warm-up chunk
    assert last['sgd_steps'] == fp.sgd_steps == 4

    # the epoch records carry the per-chunk block
    lines = (tmp_path / 'metrics.jsonl').read_text().splitlines()
    rows = [telemetry.validate_metrics_line(line) for line in lines]
    assert sum(row['fused']['chunks'] for row in rows) <= fp.dispatches
    block = rows[-1]['fused']
    assert block['chunks'] > 0 and block['stalls'] == []
    assert 0 < block['interval_median_s'] <= block['interval_max_s']
    assert block['wait_median_s'] >= 0 and 0 <= block['utilization'] <= 1

    # the benchmark's fetch_wait_ms and the loop's own per-chunk record are
    # one quantity: a chunk's wait is its host_block plus the boundary state
    # fetch before it, and it lies inside the chunk's interval
    from benchmark.readers import program_span
    from benchmark.record import Run, quantile
    assert len(booked) == fp.dispatches - 2
    assert all(0 <= chunk['wait'] <= chunk['interval'] for chunk in booked)
    window = (blocks[0]['t1'], iters[-1]['t1'])   # the chunks the loop booked
    run = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
              spans={}, window=window)
    args = telemetry_metric_args('fetch_wait_ms')
    assert program_span.read(run, **args) == {
        'samples': len(booked), 'value': pytest.approx(
            1e3 * quantile([chunk['wait'] for chunk in booked], 0.5))}

    # named phases in the lowered program
    tr = learner.trainer

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    text = fp._fused.lower(
        spec(tr.state.params), spec(tr.state), spec(fp.state),
        spec(fp.hidden), spec(fp.wstate), spec(fp.ring), spec(fp.cursor),
        spec(fp.size), spec(fp.rng),
        jax.ShapeDtypeStruct((), jnp.float32)).as_text(debug_info=True)
    for scope in ('rollout', 'ingest', 'sgd', 'pack'):
        assert 'jit(fused_pipeline_train)/%s/' % scope in text, scope
    # the SGD scan's body is a function of its own: its scopes are relative
    assert 'loc("sample/gather"' in text and 'loc("update/' in text


def test_fused_block_must_be_well_formed():
    import json as _json

    from handyrl_tpu import telemetry
    base = {'epoch': 1, 'steps': 1, 'episodes': 1, 'time': 0.0,
            'run_id': 'r', 'telemetry': {'counters': {}}}
    telemetry.validate_metrics_line(_json.dumps(
        dict(base, fused={'chunks': 0, 'stalls': []})))
    for bad in ({'chunks': 3, 'stalls': []},        # no interval statistics
                {'chunks': 0}, ['chunks']):
        with pytest.raises(ValueError, match='fused block'):
            telemetry.validate_metrics_line(_json.dumps(
                dict(base, fused=bad)))


def test_stall_detector_on_a_synthetic_interval_series():
    """One 5x outlier in a steady series: one event, complete one
    completion later, with the next chunk's wait as the diagnosis."""
    from handyrl_tpu import telemetry
    fired = telemetry.counter('fused_stalls_total')
    before = fired.value
    monitor = telemetry.ChunkMonitor()
    steady = {'enqueue': 0.004, 'wait': 0.98, 'account': 0.003,
              'eval': 0.002, 'epoch': 0.0}
    for n in range(1, 13):
        assert monitor.observe(n, 1.0 + 0.01 * (n % 3), steady) is None
    # the outlier itself is only booked; nothing is emitted yet
    assert monitor.observe(13, 5.0, dict(steady, wait=4.98)) is None
    assert fired.value == before
    # ... one completion later it goes out, with that chunk's wait
    stall = monitor.observe(14, 1.0, dict(steady, wait=0.015))
    assert fired.value == before + 1
    assert stall['dispatch'] == 13
    assert stall['interval_s'] == 5.0
    assert stall['median_s'] == pytest.approx(1.01)
    assert stall['split'] == dict(steady, wait=4.98)
    assert stall['next_host_block_s'] == 0.015
    # two clocks read one after the other over a few microseconds: the
    # process's may trail the thread's by a tick
    assert stall['process_cpu_s'] + 1e-4 >= stall['thread_cpu_s'] >= 0
    assert stall['involuntary_switches'] >= 0 and len(stall['loadavg']) == 3
    event = [e for e in telemetry.recorder().events()
             if e['kind'] == 'stall'][-1]
    assert event['dispatch'] == 13 and event['next_host_block_s'] == 0.015
    # a boundary-sized bump (under twice the median, or under 0.5 s over
    # it) is no stall; and nothing is emitted twice
    assert monitor.observe(15, 1.4, steady) is None
    assert monitor.observe(16, 1.0, steady) is None
    block = monitor.epoch_block()
    assert block['chunks'] == 16 and block['stalls'] == [stall]
    assert block['interval_max_s'] == 5.0
    assert block['wait_median_s'] == 0.98
    assert monitor.epoch_block() == {'chunks': 0, 'stalls': []}
    # a stall at the loop's last completion still goes out, undiagnosed
    for n in range(8):
        monitor.observe(n, 1.0, steady)
    monitor.observe(9, 9.0, steady)
    assert monitor.flush()['next_host_block_s'] is None
    assert monitor.flush() is None


class _FakeSpan:
    """What ChunkMonitor reads of a span."""

    def __init__(self, name, t0, t1, children=()):
        self.name, self.t0, self.t1 = name, t0, t1
        self.children = list(children)

    def child_seconds(self, name):
        return sum(c.t1 - c.t0 for c in self.children if c.name == name)


def _fake_iteration(t, wait, boundary=0.0):
    """An iteration that starts at ``t``: enqueue 2 ms, the wait, 3 ms of
    accounting, then perhaps a boundary whose state fetch is all but 15 ms
    of it. Returns (span as the step returns, span at its close), end."""
    head = [_FakeSpan('dispatch', t, t + 0.002),
            _FakeSpan('host_block', t + 0.002, t + 0.002 + wait)]
    end = t + 0.002 + wait + 0.003
    tail = [_FakeSpan('chunk_account', end - 0.003, end)]
    if boundary:
        fetch = _FakeSpan('state_fetch', end, end + boundary - 0.015)
        tail.append(_FakeSpan('epoch_boundary', end, end + boundary, [fetch]))
        end += boundary
    return (_FakeSpan('fused_iter', t, None, head),
            _FakeSpan('fused_iter', t, end, head + tail)), end


def test_a_step_made_inside_a_boundary_is_the_next_chunks_step():
    """A boundary that makes the next iteration's step before it fetches the
    train state (1 ms of host work, the 2 ms enqueue, the wait for the chunk
    in flight; then a 5 ms state fetch and 15 ms of host work): the
    iteration after it has no step of its own, and every chunk's record still
    reads one enqueue, its waits and the boundary's host work, each once and
    all inside the interval from completion to completion."""
    from handyrl_tpu import telemetry
    monitor = telemetry.ChunkMonitor()
    t, ahead, intervals = 10.0, False, []
    #   the step's wait, whether a boundary closes the iteration and steps
    for n, (wait, steps) in enumerate(
            [(0.9, None), (0.9, True), (0.8, None), (0.7, True),
             (0.6, True), (0.5, False), (0.4, None)], 1):
        head = []
        if not ahead:
            head = [_FakeSpan('dispatch', t, t + 0.002),
                    _FakeSpan('host_block', t + 0.002, t + 0.002 + wait)]
            t += 0.002 + wait
            monitor.fetched(n, _FakeSpan('fused_iter', t, None, head))
        tail = [_FakeSpan('chunk_account', t, t + 0.003)]
        t += 0.003
        if steps is not None:
            t0, inside = t, []
            if steps:
                inside = [_FakeSpan('dispatch', t + 0.001, t + 0.003),
                          _FakeSpan('host_block', t + 0.003, t + 0.003 + wait)]
                t += 0.003 + wait
                monitor.fetched(n + 1, _FakeSpan('fused_iter', t, None,
                                                 head + tail),
                                _FakeSpan('epoch_boundary', t0, None, inside))
            inside.append(_FakeSpan('state_fetch', t, t + 0.005))
            t += 0.020
            tail.append(_FakeSpan('epoch_boundary', t0, t, inside))
        monitor.closed(_FakeSpan('fused_iter', t, t, head + tail))
        ahead = bool(steps)
    for interval, split in monitor._chunks:
        assert sum(split.values()) == pytest.approx(interval)
    splits = [split for _interval, split in monitor._chunks]
    assert len(splits) == 6          # seven completions
    assert [round(s['enqueue'], 4) for s in splits] == [0.002] * 6
    # a state fetch made after a boundary's own step joins the NEXT wait
    assert [round(s['wait'], 4) for s in splits] == [
        0.9, 0.9, 0.705, 0.7, 0.605, 0.41]
    assert [round(s['account'], 4) for s in splits] == [
        0.003, 0.003, 0.003, 0.003, 0.003, 0.003]
    # 1 ms before a boundary's step; the 15 ms after its fetch are the next
    # interval's, as is the whole of a boundary that keeps the order (the
    # last interval holds one of each)
    assert [round(s.get('epoch', 0.0), 4) for s in splits] == [
        0.0, 0.001, 0.015, 0.001, 0.016, 0.03]


def test_chunk_intervals_run_from_completion_to_completion():
    """The interval ends where a chunk's fetch returns (``host_block``),
    whatever the iterations around it held: a boundary iteration (whose
    state fetch takes over the wait for the device) followed by a short one
    still reads as two whole chunks, and each chunk's ``wait`` is all the
    time blocked on the device in its interval."""
    from handyrl_tpu import telemetry
    monitor = telemetry.ChunkMonitor()
    t, seen = 100.0, []
    #           wait   boundary: the chunk in flight is waited for THERE,
    #                            so the next iteration's own wait is ~0
    for wait, boundary in [(0.9, 0.0), (0.9, 1.0), (0.0005, 0.0),
                           (0.9, 1.0), (0.0005, 1.0), (0.0005, 0.0)]:
        (open_span, closed_span), t = _fake_iteration(t, wait, boundary)
        monitor.fetched(len(seen) + 1, open_span)
        monitor.closed(closed_span)
        seen.append(closed_span.t1 - closed_span.t0)
    # the iterations themselves are bimodal: ~0.9, ~1.9, ~0.006 s ...
    assert max(seen) > 1.9 and min(seen) < 0.01
    splits = [split for _interval, split in monitor._chunks]
    block = monitor.epoch_block()
    assert block['chunks'] == 5 and block['stalls'] == []
    # ... the intervals between completions are not: 0.905 and 1.0055 s
    assert block['interval_max_s'] == pytest.approx(1.0055)
    assert block['interval_median_s'] == pytest.approx(1.0055)
    assert block['enqueue_median_s'] == pytest.approx(0.002)
    # nor is the wait: the fetch's own 0.9 s, or 0.5 ms of it after the
    # boundary's state fetch took 0.985 s; the boundary keeps its host work
    assert [round(s['wait'], 4) for s in splits] == [
        0.9, 0.9855, 0.9, 0.9855, 0.9855]
    assert [round(s['epoch'], 4) for s in splits] == [
        0.0, 0.015, 0.0, 0.015, 0.015]
    assert block['wait_median_s'] == pytest.approx(0.9855)
    assert block['utilization'] == pytest.approx(
        (2 * 0.9 + 3 * 0.9855) / (2 * 0.905 + 3 * 1.0055), rel=1e-4)
    # with telemetry off the loop hands over spans that hold nothing
    assert monitor.fetched(7, telemetry._NULL_SPAN) is None
    monitor.closed(telemetry._NULL_SPAN)
    assert monitor.epoch_block() == {'chunks': 0, 'stalls': []}


@pytest.mark.parametrize('series', [
    # every iteration a boundary (an epoch a chunk)
    [(0.0005, 1.0)] * 6,
    # a boundary first and last: their state fetches lie in no booked
    # interval of this record and must not be counted against it
    [(0.9, 1.0), (0.0005, 0.0), (0.9, 0.0), (0.9, 1.0)],
    # the record is cut (epoch_block) inside every boundary, as the loop does
    [(0.9, 0.0), (0.9, 1.0), (0.0005, 1.0), (0.0005, 0.0), (0.9, 1.0)],
], ids=['all_boundaries', 'boundary_at_the_ends', 'cut_in_boundaries'])
def test_time_blocked_on_the_device_never_exceeds_the_intervals(series):
    """``fused.utilization`` is not clipped, so the booking itself has to
    keep every chunk's wait inside its interval: over synthetic series with
    boundaries the blocked time is at most the sum of the intervals, per
    chunk and per record."""
    from handyrl_tpu import telemetry
    monitor = telemetry.ChunkMonitor()
    t, blocks = 50.0, []
    for n, (wait, boundary) in enumerate(series * 3, 1):
        (open_span, closed_span), t = _fake_iteration(t, wait, boundary)
        monitor.fetched(n, open_span)
        for interval, split in monitor._chunks[-1:]:
            assert 0 <= split['wait'] <= interval
            assert sum(split.values()) <= interval + 1e-9
        if boundary:       # the loop cuts the record inside the boundary
            blocks.append(monitor.epoch_block())
        monitor.closed(closed_span)
    blocks.append(monitor.epoch_block())
    shares = [b['utilization'] for b in blocks if b['chunks']]
    assert shares and all(0.9 < share <= 1.0 for share in shares)
    assert sum(b['chunks'] for b in blocks) == 3 * len(series) - 1


@pytest.mark.timeout(600)
def test_retrace_sentinel_waits_for_the_first_train_dispatch(tmp_path,
                                                             monkeypatch):
    """One chunk can return ``minimum_episodes + update_episodes`` games, so
    the first epoch boundary falls in an iteration whose dispatch was still
    a warm-up one: the training program has not compiled yet, and a sentinel
    armed there (``model_epoch >= retrace_warmup_epochs``) would count, or
    under ``abort`` refuse, that first compile."""
    import time

    from handyrl_tpu import telemetry
    armed = []
    mark = telemetry.mark_steady_state

    def remember(note=''):
        armed.append(learner._fused_trained)
        return mark(note)
    monkeypatch.setattr(telemetry, 'mark_steady_state', remember)
    monkeypatch.delenv('HANDYRL_TPU_RETRACE', raising=False)
    t_start = time.perf_counter()
    learner = Learner(args=apply_defaults(_ttt_raw(
        tmp_path, minimum_episodes=12, update_episodes=8, epochs=3,
        telemetry={'retrace': 'abort'})))
    try:
        learner.run()
    finally:
        telemetry.configure_perf_plane(True, 'warn')
    recs = telemetry.spans(since=t_start)
    iters = {r['span_id']: r for r in recs if r['name'] == 'fused_iter'}
    first = [r for r in recs if r['name'] == 'epoch_boundary'][0]
    assert iters[first['parent_id']]['attrs']['warm'] == 1
    assert armed == [True]            # armed once, after a train dispatch
    assert not learner.trainer.failed and learner.model_epoch == 3
