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
    block; and the fused program's phases carry their names. A net that
    says what share of a window's (query, key) pairs its attention
    multiplies (the hook ``attention_key_share``, called at the trained
    length) has it on the span and in every record."""
    import time

    import jax
    import jax.numpy as jnp

    from handyrl_tpu import telemetry
    from handyrl_tpu.models.tictactoe import SimpleConv2dModel
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    monkeypatch.setattr(SimpleConv2dModel, 'attention_key_share',
                        lambda self, T: T / 16, raising=False)
    built = []
    init = FusedPipeline.__init__

    def remember(self, *args, **kwargs):
        built.append(self)
        init(self, *args, **kwargs)
    monkeypatch.setattr(FusedPipeline, '__init__', remember)
    booked = []
    observe = telemetry.ChunkMonitor.observe

    def remember_chunk(self, dispatch, interval_s, split, fetch_s=None):
        booked.append(dict(split, interval=interval_s, fetch=fetch_s))
        return observe(self, dispatch, interval_s, split, fetch_s)
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
    assert [b['attrs']['attention_key_share'] for b in blocks] == \
        [0.25] * len(blocks)                              # forward_steps 4

    # the epoch records carry the per-chunk block
    lines = (tmp_path / 'metrics.jsonl').read_text().splitlines()
    rows = [telemetry.validate_metrics_line(line) for line in lines]
    assert sum(row['fused']['chunks'] for row in rows) <= fp.dispatches
    assert [row['attention_key_share'] for row in rows] == [0.25] * len(rows)
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
    assert all(0 <= chunk['fetch'] <= chunk['wait'] <= chunk['interval']
               for chunk in booked)
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


@pytest.mark.timeout(600)
def test_a_boundary_is_covered_by_its_children_and_the_sums_ride_every_iteration(  # noqa: E501
        tmp_path):
    """A few epochs of the real loop: every checkpointing boundary holds each
    of ``epoch_report``, ``state_pack``, ``epoch_advance``,
    ``checkpoint_submit`` and ``snapshot_release`` once; what a boundary
    holds under no child span is glue (under a fifth of the boundaries' seconds here, where a boundary is
    a few milliseconds; PERF.md has the chip's number); and every
    ``fused_iter`` carries the monitor's sums, which only grow and end at
    what the monitor booked."""
    import time

    from handyrl_tpu import telemetry
    t_start = time.perf_counter()
    before = {name: telemetry.counter(name).value for name in (
        'fused_chunks_total', 'fused_chunks_host_bound_total')}
    learner = Learner(args=apply_defaults(_ttt_raw(tmp_path, epochs=4)))
    learner.run()
    recs = telemetry.spans(since=t_start)
    children = {}
    for rec in recs:
        children.setdefault(rec['parent_id'], []).append(rec)
    boundaries = [r for r in recs if r['name'] == 'epoch_boundary']
    assert len(boundaries) == 4
    inside = outside = 0.0
    for boundary in boundaries:
        held = children[boundary['span_id']]
        names = [r['name'] for r in held]
        for name in ('epoch_report', 'state_pack', 'epoch_advance',
                     'checkpoint_submit', 'snapshot_release', 'state_fetch',
                     'checkpoint_wait', 'metrics_write'):
            assert names.count(name) == 1, name
        assert names.index('epoch_report') < names.index('state_pack') \
            < names.index('state_fetch') < names.index('checkpoint_wait') \
            < names.index('epoch_advance') < names.index('metrics_write') \
            < names.index('checkpoint_submit') \
            < names.index('snapshot_release')
        # every child is a piece the monitor books
        assert set(names) <= set(telemetry.CHUNK_PIECES)
        inside += sum(r['t1'] - r['t0'] for r in held)
        outside += boundary['t1'] - boundary['t0']
    assert 0 <= outside - inside < 0.2 * outside
    report = [r for r in recs if r['name'] == 'epoch_report']
    # (the first boundary closes the warm-up: no training chunk to sum)
    assert [r['attrs']['chunks'] >= 1 for r in report] == [False] + [True] * 3

    iters = [r['attrs'] for r in recs if r['name'] == 'fused_iter']
    keys = [key for key in iters[-1] if key not in ('dispatch', 'warm')]
    assert set(keys) == {'chunks', 'host_bound_chunks', 'interval_s',
                         'turnaround_s', 'hb_turnaround_s', 'hb_boundary_s'
                         } | {'hb_%s_s' % k for k in telemetry.CHUNK_KEYS}
    for key in keys:
        series = [attrs[key] for attrs in iters]
        assert series == sorted(series), key
    last = iters[-1]
    # one interval a completion after the first; the last chunk is the
    # loop's drain, which the monitor does not see
    blocks = sum(r['name'] == 'host_block' for r in recs)
    assert last['chunks'] == blocks - 2
    assert 0 <= last['host_bound_chunks'] <= last['chunks']
    assert 0 < last['turnaround_s'] < last['interval_s']
    assert last['hb_turnaround_s'] <= last['turnaround_s']
    assert (last['hb_ckpt_wait_s'] + last['hb_boundary_s'] + last['hb_eval_s']
            + last['hb_account_s'] + last['hb_enqueue_s']) == pytest.approx(
                last['hb_turnaround_s'])
    assert telemetry.counter('fused_chunks_total').value \
        - before['fused_chunks_total'] == last['chunks']
    assert telemetry.counter('fused_chunks_host_bound_total').value \
        - before['fused_chunks_host_bound_total'] == last['host_bound_chunks']
    rows = [telemetry.validate_metrics_line(line) for line in
            (tmp_path / 'metrics.jsonl').read_text().splitlines()]
    blocks = [row['fused'] for row in rows if row['fused']['chunks']]
    assert blocks
    for block in blocks:
        assert 0 <= block['host_bound_chunks'] <= block['chunks']
        assert 0 < block['turnaround_median_s'] <= block['interval_max_s']
        assert set(block['host_bound_split']) <= set(telemetry.CHUNK_KEYS)
        assert bool(block['host_bound_split']) == bool(
            block['host_bound_chunks'])
    # the record is cut inside the boundary: its chunks are those booked
    # before it, so the records together hold all but the last boundary's
    assert sum(b['host_bound_chunks'] for b in blocks) \
        <= last['host_bound_chunks']


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


def _laid_out(name, t, pieces, children=()):
    """A span ``name`` that starts at ``t`` and holds ``pieces`` one after the
    other: (span name, seconds), or (None, seconds) for time under no span.
    Returns the span (``children`` first) and its end."""
    held, at = list(children), t
    for piece, seconds in pieces:
        if piece is not None:
            held.append(_FakeSpan(piece, at, at + seconds))
        at += seconds
    return _FakeSpan(name, t, at, held), at


# a checkpointing boundary's host work, before and after the place of its
# step (ms): 0.2 of glue at its head and 0.4 at its end lie under no span
_BEFORE_STEP = [(None, 0.0002), ('epoch_report', 0.001),
                ('state_pack', 0.0005)]
_STEP = [('actor_refresh', 0.0003), ('dispatch', 0.002)]
_AFTER_STEP = [('state_fetch', 0.005), ('checkpoint_wait', 0.004),
               ('epoch_advance', 0.002), ('metrics_write', 0.003),
               ('checkpoint_submit', 0.0001), ('snapshot_release', 0.0025),
               (None, 0.0004)]
_TAIL = [('chunk_account', 0.003), ('eval_share', 0.001)]
_PIECES = {'account': 0.003, 'eval': 0.001, 'report': 0.001, 'pack': 0.0005,
           'refresh': 0.0003, 'enqueue': 0.002, 'ckpt_wait': 0.004,
           'advance': 0.002, 'record': 0.003, 'submit': 0.0001,
           'release': 0.0025, 'epoch': 0.0006}


@pytest.mark.parametrize('path', ['head', 'boundary'])
def test_an_interval_is_its_pieces_and_epoch_is_the_rest(path):
    """ONE rule books an interval wherever its step was made: each piece is
    the seconds of the loop's spans that lie in it, ``epoch`` what is left.
    A whole boundary (its state fetch booked as ``wait``) and one step lie
    between the two completions on both paths, so both give the same keys
    and the same seconds: at the head of the next iteration (the boundary
    kept fetch-then-enqueue), or inside the boundary (enqueue-first: the
    interval ends in the boundary, the rest of it opens the next)."""
    from handyrl_tpu import telemetry
    monitor = telemetry.ChunkMonitor()
    wait = 0.0123

    def head_step(t, refresh):
        pieces = (_STEP if refresh else _STEP[1:]) + [('host_block', wait)]
        return _laid_out('fused_iter', t, pieces)

    def stepping_boundary(t, it_children):
        """The boundary up to its own step's completion, ``fetched`` as the
        loop does, then the rest; returns the closed iteration's children."""
        boundary, t = _laid_out('epoch_boundary', t, _BEFORE_STEP + _STEP
                                + [('host_block', wait)])
        boundary.t1 = None
        monitor.fetched(2, _FakeSpan('fused_iter', 0.0, None, it_children),
                        boundary)
        whole, t = _laid_out('epoch_boundary', boundary.t0, [(
            None, t - boundary.t0)] + _AFTER_STEP, boundary.children)
        return it_children + [whole], t

    # the first completion: an iteration's own step
    it, t = head_step(10.0, refresh=False)
    assert monitor.fetched(1, it) is None and not monitor._chunks
    tail, t = _laid_out('tail', t, _TAIL)
    if path == 'head':
        boundary, t = _laid_out('epoch_boundary', t,
                                _BEFORE_STEP + _AFTER_STEP)
        monitor.closed(_FakeSpan('fused_iter', 10.0, t, it.children
                                 + tail.children + [boundary]))
        nxt, t = head_step(t, refresh=True)
        monitor.fetched(2, nxt)
        (interval, split), = monitor._chunks
        expected = dict(_PIECES, wait=0.005 + wait)
    else:
        held, t = stepping_boundary(t, it.children + tail.children)
        monitor.closed(_FakeSpan('fused_iter', 10.0, t, held))
        # the iteration after it has no step of its own; its boundary steps
        tail, t = _laid_out('tail', t, _TAIL)
        held, t = stepping_boundary(t, tail.children)
        monitor.closed(_FakeSpan('fused_iter', 10.0, t, held))
        (first, before), (interval, split) = monitor._chunks
        # the first interval ends inside its boundary: no state fetch, no
        # hand-over yet, 0.2 ms of glue
        assert before == pytest.approx(dict(
            dict.fromkeys(telemetry.CHUNK_KEYS, 0.0), account=0.003,
            eval=0.001, report=0.001, pack=0.0005, refresh=0.0003,
            enqueue=0.002, wait=wait, epoch=0.0002))
        assert sum(before.values()) == pytest.approx(first)
        expected = dict(_PIECES, wait=0.005 + wait)
    assert list(split) == list(telemetry.CHUNK_KEYS)
    assert split == pytest.approx(expected)
    assert sum(split.values()) == pytest.approx(interval)
    assert interval == pytest.approx(sum(expected.values()))
    # the boundary's own host work is one counter's worth of keys
    assert set(telemetry.BOUNDARY_KEYS) == set(expected) - {
        'enqueue', 'wait', 'account', 'eval', 'ckpt_wait'}


def _booked(monitor, waits, boundary_every=2):
    """Book intervals with the given waits through ``observe``: 20 ms each,
    every ``boundary_every``-th one holds a boundary (4 ms of writer wait,
    6 ms of the boundary's own work) in place of device wait. Returns the
    splits."""
    splits = []
    for n, wait in enumerate(waits, 1):
        split = dict.fromkeys(telemetry_keys(), 0.0)
        split.update(enqueue=0.0015, account=0.001, eval=0.0005, wait=wait)
        if n % boundary_every == 0:
            split.update(ckpt_wait=0.004, report=0.002, advance=0.001,
                         record=0.002, submit=0.0005)
        split['epoch'] = 0.020 - sum(split.values())
        monitor.observe(n, 0.020, split)
        splits.append(split)
    return splits


def telemetry_keys():
    from handyrl_tpu import telemetry
    return telemetry.CHUNK_KEYS


def test_the_host_bound_verdict_and_its_sums():
    """A chunk whose completing fetch found its result ready (it took under
    ``HOST_BOUND_FETCH`` of the running median interval; the whole wait
    where the caller names no fetch) is host-bound; the
    sums keep, for those chunks alone, the turnaround and each piece of it;
    the epoch's block and the two counters say the same."""
    from handyrl_tpu import telemetry
    chunks = telemetry.counter('fused_chunks_total')
    bound = telemetry.counter('fused_chunks_host_bound_total')
    before = chunks.value, bound.value
    monitor = telemetry.ChunkMonitor()
    limit = monitor.HOST_BOUND_FETCH * 0.020
    assert 0.0002 < limit < 0.008       # the waits below stand clear of it
    #        device-bound: 15 and 8 ms of wait; host-bound: 0.1 and 0.2 ms
    waits = [0.015, 0.0001, 0.008, 0.0002, 0.015, 0.0002, 0.015, 0.015]
    splits = _booked(monitor, waits)
    called = [wait < limit for wait in waits]
    assert called == [False, True, False, True, False, True, False, False]
    totals = monitor.totals
    assert totals['chunks'] == 8 and totals['host_bound_chunks'] == 3
    assert (chunks.value - before[0], bound.value - before[1]) == (8, 3)
    assert totals['interval_s'] == pytest.approx(8 * 0.020)
    assert totals['turnaround_s'] == pytest.approx(8 * 0.020 - sum(waits))
    hb = [split for split, yes in zip(splits, called) if yes]
    assert totals['hb_turnaround_s'] == pytest.approx(
        3 * 0.020 - 0.0001 - 0.0002 - 0.0002)
    for key in telemetry.CHUNK_KEYS:
        assert totals['hb_%s_s' % key] == pytest.approx(
            sum(split[key] for split in hb)), key
    assert totals['hb_ckpt_wait_s'] == pytest.approx(3 * 0.004)
    assert totals['hb_boundary_s'] == pytest.approx(sum(
        totals['hb_%s_s' % key] for key in telemetry.BOUNDARY_KEYS))
    # the turnaround of the host-bound chunks is the writer's wait, the
    # boundary's own work and the loop's three pieces, and nothing else
    assert (totals['hb_ckpt_wait_s'] + totals['hb_boundary_s']
            + totals['hb_eval_s'] + totals['hb_account_s']
            + totals['hb_enqueue_s']) == pytest.approx(
                totals['hb_turnaround_s'])
    block = monitor.epoch_block()
    assert block['chunks'] == 8 and block['host_bound_chunks'] == 3
    assert block['turnaround_median_s'] == pytest.approx(
        0.020 - 0.5 * (0.008 + 0.015), abs=1e-6)
    assert block['host_bound_split']['ckpt_wait'] == pytest.approx(0.012)
    assert sum(block['host_bound_split'].values()) == pytest.approx(
        3 * 0.020, abs=1e-5)
    assert block['utilization'] == pytest.approx(
        1 - totals['turnaround_s'] / totals['interval_s'], abs=1e-6)
    # the next epoch's block starts from nothing; the sums go on
    monitor.observe(9, 0.020, splits[1])
    block = monitor.epoch_block()
    assert block['chunks'] == block['host_bound_chunks'] == 1
    assert block['host_bound_split']['ckpt_wait'] == pytest.approx(0.004)
    assert monitor.totals['host_bound_chunks'] == 4
    assert monitor.epoch_block() == {'chunks': 0, 'stalls': []}
    # the test is the completing fetch's own time: a boundary's state fetch
    # of 3 ms is wait, and the chunk whose result was ready is host-bound
    # all the same; one whose fetch itself blocked 3 ms is not
    blocked = dict(splits[1], wait=0.0035, epoch=splits[1]['epoch'] - 0.0034)
    monitor.observe(10, 0.020, blocked, fetch_s=0.0005)
    assert monitor.totals['host_bound_chunks'] == 5
    monitor.observe(11, 0.020, blocked, fetch_s=0.003)
    monitor.observe(12, 0.020, blocked)
    assert monitor.totals['host_bound_chunks'] == 5
    assert monitor.totals['chunks'] == 12


@pytest.mark.parametrize('metric, expected', [
    ('host_bound_chunk_share', 100 * 2 / 5),
    ('turnaround_ms', 1e3 * (5 * 0.020 - 0.0001 - 0.008 - 0.0002
                             - 2 * 0.015) / 5),
    ('host_bound_ckpt_wait_share', 100 * 0.008 / (0.040 - 0.0003)),
    # (a host-bound boundary chunk: 5.5 ms under the boundary's spans and
    # what the 20 ms hold under none, 7.5 ms less the wait)
    ('host_bound_boundary_share', 100 * (2 * 0.013 - 0.0003)
     / (0.040 - 0.0003)),
])
def test_the_counters_on_fused_iter_grow_and_the_reader_takes_their_growth(
        monkeypatch, metric, expected):
    """Every ``fused_iter`` record carries the monitor's sums as they stood
    when it closed, a step-less iteration's too; ``program_counter_ratio``
    with the metric file's arguments reads the growth between the records
    that bound a window, here the five chunks booked between the records
    that ended at 11 and at 18 s."""
    from benchmark.readers import program_counter_ratio
    from benchmark.record import Run
    from handyrl_tpu import telemetry
    monitor = telemetry.ChunkMonitor()
    records = []

    def close(n, t1):
        records.append({'name': 'fused_iter', 't0': t1 - 0.5, 't1': t1,
                        'span_id': n, 'parent_id': None,
                        'attrs': dict(monitor.totals, dispatch=n)})
    waits = [0.015, 0.0001, 0.015, 0.0001, 0.008, 0.0002, 0.015, 0.015,
             0.0002, 0.015]
    booked = 0
    for n in range(10, 20):
        # iterations 13 and 16 are step-less (the boundary before made their
        # step): they book nothing and still carry the sums
        if n not in (13, 16):
            _booked(monitor, [waits[booked]],
                    boundary_every=1 if booked % 2 else 99)
            booked += 1
        close(n, float(n))
    grown = [r['attrs'] for r in records]
    for key in monitor.totals:
        series = [attrs[key] for attrs in grown]
        assert series == sorted(series), key
    assert [a['chunks'] for a in grown] == [1, 2, 3, 3, 4, 5, 5, 6, 7, 8]
    monkeypatch.setattr(telemetry, 'spans',
                        lambda name=None, since=None: list(records))
    run = Run(cell={'name': 'c'}, config={}, traffic={}, train_args={},
              spans={}, window=(11.5, 18.5))
    # waits[2:7]: two of the five under the limit, each with a boundary
    assert program_counter_ratio.read(
        run, **telemetry_metric_args(metric)) == pytest.approx(expected)
    # a window in which no chunk was host-bound leaves the two shares out
    run.window = (17.5, 18.5)
    got = program_counter_ratio.read(run, **telemetry_metric_args(metric))
    if metric in ('host_bound_ckpt_wait_share', 'host_bound_boundary_share'):
        assert got is None
    else:
        assert got == pytest.approx(
            {'host_bound_chunk_share': 0.0, 'turnaround_ms': 5.0}[metric])


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
