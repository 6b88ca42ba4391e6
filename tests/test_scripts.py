"""Tooling-script tests: SWA averaging, StableHLO export round-trip, and the
log-parsing plotters."""

import os
import sys

import numpy as np
import pytest

SCRIPTS = os.path.join(os.path.dirname(__file__), '..', 'scripts')
sys.path.insert(0, os.path.abspath(SCRIPTS))


@pytest.fixture(scope='module')
def trained_models(tmp_path_factory):
    """Two real checkpoints from a tiny training run."""
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    model_dir = str(tmp_path_factory.mktemp('swa') / 'models')
    raw = {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 16, 'update_episodes': 25, 'minimum_episodes': 30,
            'epochs': 2, 'generation_envs': 8, 'forward_steps': 8,
            'num_batchers': 1, 'model_dir': model_dir,
        },
    }
    learner = Learner(args=apply_defaults(raw))
    learner.run()
    return model_dir


def test_swa_script(trained_models, monkeypatch):
    import aux_swa
    monkeypatch.setattr(sys, 'argv',
                        ['aux_swa.py', 'TicTacToe', '1', '2', trained_models])
    aux_swa.main()
    assert os.path.exists(os.path.join(trained_models, 'swa.ckpt'))
    # the average must differ from both endpoints
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.evaluation import load_model
    env = make_env({'env': 'TicTacToe'})
    env.reset()
    obs = env.observation(0)
    outs = [load_model(os.path.join(trained_models, name), env).inference(obs)['policy']
            for name in ('1.ckpt', '2.ckpt', 'swa.ckpt')]
    assert not np.allclose(outs[0], outs[2])


def test_export_script(trained_models, monkeypatch, tmp_path):
    import export_model
    out = str(tmp_path / 'model.jaxexp')
    monkeypatch.setattr(sys, 'argv',
                        ['export_model.py', 'TicTacToe',
                         os.path.join(trained_models, 'latest.ckpt'), out])
    export_model.main()

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.evaluation import load_model
    env = make_env({'env': 'TicTacToe'})
    env.reset()
    obs = env.observation(0)
    native = load_model(os.path.join(trained_models, 'latest.ckpt'), env)
    exported = load_model(out, env)
    np.testing.assert_allclose(exported.inference(obs)['policy'],
                               native.inference(obs)['policy'], atol=1e-4)


def test_plot_parsers(tmp_path):
    log = tmp_path / 'train.log'
    log.write_text(
        'waiting training\n'
        'epoch 1\n'
        'win rate = 0.500 (10.0 / 20)\n'
        'generation stats = 0.100 +- 0.900\n'
        'loss = ent:1.500 p:-0.250 total:0.125 v:0.200\n'
        'updated model(50)\n'
        'epoch 2\n'
        'win rate (random) = 0.650 (13.0 / 20)\n'
        'win rate (total) = 0.640 (12.8 / 20)\n'
        'generation stats = 0.150 +- 0.800\n'
        'loss = ent:1.400 p:-0.200 total:0.100 v:0.150\n'
    )
    import loss_plot
    import stats_plot
    import win_rate_plot

    _, series = win_rate_plot.parse(str(log))
    assert series['total'][0][1] == 0.5
    assert series['random'][0] == (2, 0.65, 20)

    losses = loss_plot.parse(str(log))
    assert losses['ent'] == [1.5, 1.4]
    assert losses['p'] == [-0.25, -0.2]

    stats = stats_plot.parse(str(log))
    assert stats == [(0.1, 0.9), (0.15, 0.8)]


def test_eval_checkpoints_script(trained_models, monkeypatch, tmp_path):
    """Offline checkpoint quality curve: one JSON row per checkpoint with a
    win rate from whole-match device evaluation; --skip-scored makes a
    rerun incremental (no duplicate {epoch, opponent} rows: a recurring
    caller scores each checkpoint once)."""
    import json

    import eval_checkpoints
    out = str(tmp_path / 'curve.jsonl')
    monkeypatch.setattr(sys, 'argv',
                        ['eval_checkpoints.py', trained_models, 'TicTacToe',
                         out, '--every', '1', '--games', '12',
                         '--envs', '4'])
    eval_checkpoints.main()
    rows = [json.loads(l) for l in open(out)]
    assert [r['epoch'] for r in rows] == [1, 2]
    for r in rows:
        assert r['games'] >= 12 and 0.0 <= r['win_rate'] <= 1.0
        assert r['opponent'] == 'random'

    # rerun with --skip-scored: everything already scored -> no new rows
    monkeypatch.setattr(sys, 'argv',
                        ['eval_checkpoints.py', trained_models, 'TicTacToe',
                         out, '--every', '1', '--games', '12',
                         '--envs', '4', '--skip-scored'])
    eval_checkpoints.main()
    rows2 = [json.loads(l) for l in open(out)]
    assert [r['epoch'] for r in rows2] == [1, 2], \
        'skip-scored rerun must not append duplicate rows'

    # drop epoch 2's row: a rerun must score exactly the unscored epoch
    # (the incremental half of the contract — a skip-everything regression
    # would leave the file short)
    with open(out, 'w') as f:
        f.write(json.dumps(rows2[0]) + '\n')
    eval_checkpoints.main()
    rows3 = [json.loads(l) for l in open(out)]
    assert [r['epoch'] for r in rows3] == [1, 2], \
        'skip-scored rerun must evaluate epochs missing from the file'


def test_trace_report_json_schema(tmp_path, capsys):
    """scripts/trace_report.py --json output contract: every consumer-facing
    key present, stage/segment rows shaped {n, p50, p95}, and the
    exit-code contract (0 with a complete chain, 2 without)."""
    import json

    import trace_report

    def ev(name, ts, dur, pid, trace_id=None, trace_ids=None):
        args = {}
        if trace_id:
            args['trace_id'] = trace_id
        if trace_ids:
            args['trace_ids'] = trace_ids
        return json.dumps({'name': name, 'cat': 'handyrl', 'ph': 'X',
                           'ts': ts, 'dur': dur, 'pid': pid, 'tid': 1,
                           'args': args})

    trace = tmp_path / 'trace-run1.jsonl'
    trace.write_text('\n'.join([
        ev('task_assign', 1000, 10, 1, trace_id='g7'),
        ev('generate', 2000, 5000, 2, trace_id='g7'),
        ev('upload', 8000, 300, 3, trace_id='g7'),
        ev('ingest', 9000, 100, 1, trace_id='g7'),
        ev('train_step', 10000, 2000, 1, trace_ids=['g7']),
        ev('decode', 9500, 50, 1),
        '{torn half-line',
    ]) + '\n')

    assert trace_report.main([str(tmp_path), '--json']) == 0
    report = json.loads(capsys.readouterr().out)
    for key in ('events', 'processes', 'chains', 'complete_chains',
                'order_violations', 'stage_seconds', 'segment_seconds',
                'generation_to_gradient_seconds'):
        assert key in report, 'missing %r' % key
    assert report['events'] == 6
    assert report['processes'] == 3
    assert report['chains'] == 1
    assert report['complete_chains'] == 1
    assert report['order_violations'] == 0
    for table in ('stage_seconds', 'segment_seconds'):
        for name, row in report[table].items():
            assert set(row) == {'n', 'p50', 'p95'}, (table, name)
            assert row['n'] >= 1
    assert 'decode' in report['stage_seconds']
    g2g = report['generation_to_gradient_seconds']
    assert set(g2g) == {'n', 'p50', 'p95'}
    # generate start (ts=2000us) -> train_step end (12000us) = 10ms
    assert g2g['n'] == 1 and abs(g2g['p50'] - 0.01) < 1e-9

    # exit contract: an incomplete chain (no train_step) exits 2
    broken = tmp_path / 'broken'
    broken.mkdir()
    (broken / 'trace-run2.jsonl').write_text('\n'.join([
        ev('task_assign', 1000, 10, 1, trace_id='g9'),
        ev('generate', 2000, 5000, 2, trace_id='g9'),
    ]) + '\n')
    assert trace_report.main([str(broken), '--json']) == 2
    capsys.readouterr()
    # and an empty dir exits 2 without output
    empty = tmp_path / 'empty'
    empty.mkdir()
    assert trace_report.main([str(empty)]) == 2


def test_trace_report_serve_mode_and_require(tmp_path, capsys):
    """``--serve`` reduces the serving-path spans (hop percentiles, the
    per-replica queue/compute split, replay + reconstruction chains,
    session timelines) and flips the exit contract to "a complete serve
    chain exists"; ``--require`` picks the chain kind explicitly so a
    serve-only trace doesn't read as a training failure."""
    import json

    import trace_report

    def ev(name, ts, dur, pid, trace_id=None, trace_ids=None, **extra):
        args = dict(extra)
        if trace_id:
            args['trace_id'] = trace_id
        if trace_ids:
            args['trace_ids'] = trace_ids
        return json.dumps({'name': name, 'cat': 'handyrl', 'ph': 'X',
                           'ts': ts, 'dur': dur, 'pid': pid, 'tid': 1,
                           'args': args})

    trace = tmp_path / 'trace-serve1.jsonl'
    trace.write_text('\n'.join([
        # request r1: a complete routed chain crossing a failover replay
        # (the link span carries the ORIGINAL trace id)
        ev('client_request', 1000, 9000, 1, trace_id='r1'),
        ev('route_dispatch', 1200, 50, 1, trace_id='r1', replica='r0',
           breaker='closed'),
        ev('router_replay', 4000, 80, 1, trace_id='r1', link='replay',
           from_replica='r0', to_replica='r1'),
        ev('serve_request', 5000, 2000, 20, trace_id='r1', replica='r1'),
        ev('queue_wait', 5200, 300, 20, trace_id='r1'),
        ev('engine_batch', 5600, 900, 20, trace_ids=['r1']),
        # session s1: open + 2 plies + a journal reconstruction linked to
        # the session's open-time trace id
        ev('gateway_open', 500, 100, 3, trace_id='g1', sid='s1'),
        ev('gateway_ply', 2000, 400, 3, trace_id='p1', sid='s1',
           session_trace='g1'),
        ev('gateway_ply', 3000, 500, 3, trace_id='p2', sid='s1',
           session_trace='g1'),
        ev('gateway_reconstruct', 6000, 700, 3, trace_id='g1',
           link='reconstruct', sid='s1', replayed=2, ok=True),
    ]) + '\n')

    assert trace_report.main([str(tmp_path), '--serve', '--json']) == 0
    sv = json.loads(capsys.readouterr().out)['serve']
    assert sv['complete_chains'] == 1
    assert sv['routed_chains'] == 1
    assert sv['replay_chains'] == 1
    assert sv['complete_replay_chains'] == 1
    assert sv['reconstruct_chains'] == 1
    for name in ('client_request', 'route_dispatch', 'serve_request',
                 'queue_wait', 'engine_batch', 'gateway_open',
                 'gateway_ply'):
        row = sv['hop_seconds'][name]
        assert set(row) == {'n', 'p50', 'p95', 'p99'} and row['n'] >= 1
    # the queue-wait vs batch-compute split keys on the replica learned
    # from serve_request (the engine shares the service pid)
    assert sv['replica_split']['r1']['queue_wait']['n'] == 1
    assert sv['replica_split']['r1']['engine_batch']['n'] == 1
    assert sv['sessions']['s1']['plies'] == 2
    assert sv['sessions']['s1']['span_seconds'] == pytest.approx(0.0015)

    # exit contract: the default (training) still fails this serve-only
    # trace; --require any accepts either kind; --serve with an explicit
    # --require training renders the block but gates on training
    assert trace_report.main([str(tmp_path), '--json']) == 2
    capsys.readouterr()
    assert trace_report.main([str(tmp_path), '--json',
                              '--require', 'any']) == 0
    capsys.readouterr()
    assert trace_report.main([str(tmp_path), '--serve',
                              '--require', 'training']) == 2
    capsys.readouterr()
