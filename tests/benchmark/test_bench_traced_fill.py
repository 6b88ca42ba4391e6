"""``readers/traced_fill_roofline`` and what it stands on: the ply indices
rebuilt from the ``done`` flags against the nets' own counters
(``hidden['pos']``) for the four trunk nets, the pairing of a traced
execution with the chunk it played on a trace shaped as the fused loop leaves
one (``traced_fill.py``), what is left out when a record is missing, and the
two things the harness gained for it: a hook may keep an array and two hooks
may wrap one target; ``reduce_trace`` hands over the window span's
annotations. The rooflines themselves, through their own metric files, are
the four ``test_bench_<configuration>.py``'s. On the checkout alone."""

import numpy as np
import pytest

from benchmark import hooks, reduce_trace, rehearse
from benchmark.manifest import Manifest
from benchmark.readers import traced_fill_roofline as reader

from tests.benchmark import traced_fill

TRUNKS = {'evabyte': 'evabyte.selfplay_4k',
          'trinity_mini': 'trinity_mini.moe_selfplay_4k',
          'smallthinker': 'smallthinker.moe_selfplay_8k',
          'ouro': 'ouro.loop_selfplay_4k'}


# -- the ply indices ------------------------------------------------------------
def test_a_lanes_ply_index_is_its_plies_since_the_last_done():
    done = np.zeros((5, 3), bool)
    done[1, 0] = done[4, 1] = True
    index, after = reader.chunk_ply_indices([7, 0, 2], done)
    assert index.T.tolist() == [[7, 8, 0, 1, 2], [0, 1, 2, 3, 4],
                                [2, 3, 4, 5, 6]]
    assert after.tolist() == [3, 0, 7]
    records = [(0.0, 0.1, {'chunk': 1, 'done': done}),
               (0.1, 0.2, {'chunk': 2, 'done': np.zeros((5, 3), bool)})]
    by_chunk = reader.ply_indices_by_chunk(records)
    assert by_chunk[1][0].tolist() == [0, 0, 0]     # the learner's first ply
    assert by_chunk[2][0].tolist() == [3, 0, 5]
    assert by_chunk[2][4].tolist() == [7, 4, 9]
    # a chunk that went unrecorded leaves every later index unknown
    assert reader.ply_indices_by_chunk(records[1:]) is None
    assert reader.ply_indices_by_chunk([]) == {}


@pytest.fixture(scope='module')
def laid(tmp_path_factory):
    """Every configuration's file at its rehearsal size."""
    dest = str(tmp_path_factory.mktemp('traced_fill_tiny'))
    rehearse.build_root(Manifest(), dest, TRUNKS['ouro'])
    return Manifest(dest)


@pytest.mark.parametrize('name', list(TRUNKS))
def test_the_hosts_ply_indices_are_the_nets_own_counters(laid, name):
    """The program's own ``rollout_chunk`` (what the fused dispatch scans) at
    the rehearsal's size, four lanes whose first games end INSIDE the first
    three chunks of 8 plies: after every chunk the index the reader rebuilds
    from the ``done`` flags alone is ``hidden['pos']`` of both seats,
    exactly."""
    import jax
    import jax.numpy as jnp
    from benchmark import checks
    from handyrl_tpu.device_generation import make_gen_body
    from handyrl_tpu.environment import make_jax_env

    config = laid.load_config(name)
    traffic = laid.load_traffic(Manifest().cell(TRUNKS[name])['traffic'])
    train_args = dict(traffic['train_args'], **config['train_args'], seed=5)
    module = checks.build_module(config, train_args)
    variables = checks.starting_variables(config, train_args)
    env_mod = make_jax_env(config['env_args'])
    rollout_chunk = make_gen_body(env_mod, module.apply, True, True,
                                  module=module)
    run = jax.jit(lambda p, s, h, r: rollout_chunk(p, s, h, r, 8))
    lengths = [3, 9, 13, 22]
    state = env_mod.init_state(len(lengths), 5)._replace(
        length=jnp.asarray(lengths, jnp.int32))
    hidden = module.init_hidden((len(lengths), env_mod.NUM_PLAYERS))
    rng = jax.random.PRNGKey(5)
    records, counters = [], []
    for n in range(1, 5):
        state, hidden, rng, rec = run(variables, state, hidden, rng)
        records.append((0.0, float(n), {'chunk': n,
                                        'done': np.asarray(rec['done'])}))
        counters.append(np.asarray(hidden['pos']))
    done = np.concatenate([r[2]['done'] for r in records])
    assert done.shape == (32, 4)
    assert [int(np.flatnonzero(done[:, lane])[0]) + 1
            for lane in range(4)] == lengths         # each inside a chunk
    by_chunk = reader.ply_indices_by_chunk(records)
    for n, pos in enumerate(counters, 1):
        assert pos.shape == (4, 2) and (pos[:, 0] == pos[:, 1]).all()
        _index, after = reader.chunk_ply_indices(by_chunk[n][0],
                                                 records[n - 1][2]['done'])
        assert after.tolist() == pos[:, 0].tolist(), (name, n)
    assert counters[-1][:, 0].tolist() == [32 - n for n in lengths]


# -- the pairing ---------------------------------------------------------------------
def _three_programs(tmp_path):
    """Three traced programs of 30, 50 and 40 ms (their chunks 5, 6, 7) and
    the four calls around them; the first call's annotation is not in the
    trace (the profiler started behind it)."""
    text, calls = traced_fill.trace_text(
        [(30_000_000, 9_000_000), (50_000_000, 9_000_000),
         (40_000_000, 9_000_000)], 'loop_attention')
    path = traced_fill.write_trace(tmp_path, text)
    reduced = reduce_trace.reduce(path, window_span='train_dispatch')
    host = lambda ns: traced_fill.HOST_EPOCH_S + ns / 1e9
    records = [(host(a), host(b), {'dispatches': 5 + i})
               for i, (a, b) in enumerate(calls)]
    return path, reduced, records


def test_an_execution_is_paired_with_the_call_that_enqueued_it(tmp_path):
    """The fetch lags its dispatch: program d ends inside call d + 1, and the
    device's clock leads the host's by 1.3 ms. Each of the three programs
    goes to the LAST call that ended before it did: the first one to the
    call that closed the window, which the trace holds no annotation of."""
    path, reduced, records = _three_programs(tmp_path)
    assert len(reduced['marks']) == 3
    assert reduced['window'] == [reduced['marks'][0][1],
                                 reduced['marks'][-1][1]]
    lo = min(start for start, _end in reduced['marks']) - 10 ** 9
    hi = reduced['window'][1]
    executions = reader.scope_seconds(
        path, traced_fill.MODULE, 'rollout',
        ('rollout', 'ingest', 'sgd', 'pack'), lo, hi)
    assert [round(s, 6) for _end, s in executions] == [0.03, 0.05, 0.04]
    assert reader.pair(executions, reduced['marks'], records) \
        == [(5, pytest.approx(0.03)), (6, pytest.approx(0.05)),
            (7, pytest.approx(0.04))]
    inner = reader.scope_seconds(path, traced_fill.MODULE, 'loop_attention',
                                 (), lo, hi)
    assert [round(s, 6) for _end, s in inner] == [0.009] * 3
    # inside the window proper (from the first annotation's END: every trace
    # reader's rule) the first program is not whole, and neither is the
    # second: it starts when the first ends, which the device's clock puts
    # 1.5 ms before the call that fetched the first one returns. The third
    # keeps its call
    inside = reader.scope_seconds(
        path, traced_fill.MODULE, 'rollout',
        ('rollout', 'ingest', 'sgd', 'pack'), *reduced['window'])
    assert reader.pair(inside, reduced['marks'], records) \
        == [(7, pytest.approx(0.04))]
    # no operation under the scope: nothing to read
    assert reader.scope_seconds(path, traced_fill.MODULE, 'gqa_attention',
                                (), lo, hi) is None


def test_records_that_cannot_be_the_traces_calls_pair_nothing(tmp_path):
    path, reduced, records = _three_programs(tmp_path)
    executions = [(reduced['marks'][1][1] - 10, 0.05)]
    assert reader.pair(executions, reduced['marks'], records) == [(6, 0.05)]
    # fewer records behind the closing call than the trace has annotations
    assert reader.pair(executions, reduced['marks'], records[:3]) is None
    assert reader.pair(executions, reduced['marks'], []) is None
    # a call that took 20 ms longer than its annotation: not the same call
    t0, t1, captures = records[2]
    off = records[:2] + [(t0 - 0.02, t1, captures)] + records[3:]
    assert reader.pair(executions, reduced['marks'], off) is None


def test_an_unpaired_execution_is_left_out_of_both_sums(tmp_path, shipped):
    """Two traced programs, the second one's chunk never recorded: the
    metric is the first one's alone, and says so (``samples``); with neither
    recorded there is nothing to read."""
    config = shipped.load_config('ouro')
    traffic = shipped.load_traffic('loop_selfplay_4k')
    args = dict(traffic['train_args'], **config['train_args'])
    spec = shipped.load_metric('loop_decode_roofline')
    plies, lanes = 256, 16
    starts = traced_fill.starts_for(1000, plies, lanes)
    chunks = traced_fill.dones(starts, 8, plies) + [
        np.zeros((plies, lanes), bool)] * 2

    def run(drop):
        made = traced_fill.traced_run(
            tmp_path / str(drop), shipped, shipped.cell(TRUNKS['ouro']),
            config, traffic, args, chunks, 9, [(2.0, 0.5), (3.0, 0.5)],
            'loop_attention', drop_chunk=drop)
        # the window proper starts behind the first annotation: widen it to
        # the device's extent, as a trunk cell's one-annotation trace has it
        made.trace['window'] = [0, made.trace['window'][1]]
        return made
    both = reader.read(run(None), **spec['args'])
    assert both['samples'] == 2
    assert [e['chunk'] for e in both['executions']] == [9, 10]
    assert [round(e['fill_rows']['global']) for e in both['executions']] \
        == [1000, 1256]
    one = reader.read(run(10), **spec['args'])
    assert one['samples'] == 1 and one['executions'][0]['chunk'] == 9
    required = lambda rows: 256 * (1291849728 + 1048576 * rows) / 819e9
    fills = [e['fill_rows']['global'] for e in both['executions']]
    assert fills[1] == fills[0] + 256
    assert one['value'] == pytest.approx(100 * required(fills[0]) / 2.0)
    assert both['value'] == pytest.approx(
        100 * (required(fills[0]) + required(fills[1])) / 5.0)
    # a gap in the ordinals: every later index is unknown
    assert reader.read(run(3), **spec['args']) is None


# -- what the harness gained -----------------------------------------------------------
class _Pipe:
    def __init__(self):
        self.chunks = 0

    def parse(self, k):
        self.chunks += 1
        return {'done': np.arange(6).reshape(2, 3) % k == 0,
                'metrics': {'loss': 0.5}}


def test_two_hooks_on_one_target_both_record_and_both_come_off():
    rec = hooks.Recorder()
    specs = {'fetch': {'target': __name__ + ':_Pipe.parse',
                       'capture': {'metrics': 'ret.metrics'}},
             'plies': {'target': __name__ + ':_Pipe.parse',
                       'capture': {'chunk': 'self.chunks',
                                   'done': 'ret.done'}}}
    original = _Pipe.parse
    undo = hooks.install(specs, rec)
    try:
        pipe = _Pipe()
        got = pipe.parse(2)
        pipe.parse(3)
    finally:
        undo()
    assert _Pipe.parse is original
    assert [c['metrics'] for _a, _b, c in rec.spans['fetch']] \
        == [{'loss': 0.5}] * 2
    (_, _, first), (_, _, second) = rec.spans['plies']
    assert (first['chunk'], second['chunk']) == (1, 2)
    assert first['done'].tolist() == [[True, False, True],
                                      [False, True, False]]
    assert first['done'] is not got['done']      # the hook's own copy
    assert second['done'].tolist() == [[True, False, False],
                                       [True, False, False]]


def test_the_shipped_hook_carries_the_done_flags_and_the_ordinal(shipped):
    spec = shipped.load_hooks(['chunk_plies'])['chunk_plies']
    assert spec['target'] == \
        'handyrl_tpu.ops.fused_pipeline:FusedPipeline._parse'
    assert spec['capture'] == {'chunk': 'self.chunks_host',
                               'done': 'ret.done'}
    hooks.resolve(spec['target'])
    # only the cells whose metrics name it install it
    from benchmark.session import spans_of
    for workload, cell in shipped.cells.items():
        window = shipped.load_traffic(cell['traffic'])['window']
        assert ('chunk_plies' in spans_of(shipped, workload, window)) \
            == (workload in TRUNKS.values())
