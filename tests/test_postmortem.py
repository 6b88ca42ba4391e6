"""Postmortem collation: unit tests over synthetic blackbox dumps plus a
slow chaos e2e — a SIGKILLed gather must leave blackbox dumps from >= 2
processes, a firing-then-clearing alert trail in metrics_jsonl, and a
postmortem that names the killed gather's loss as the first failure."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

SCRIPTS = os.path.join(os.path.dirname(__file__), '..', 'scripts')
sys.path.insert(0, os.path.abspath(SCRIPTS))

import postmortem  # noqa: E402


# ---------------------------------------------------------------------------
# unit: discovery, attribution, alert-transition reconstruction


def _dump(role, pid, run_id, reason, t_dump, events):
    return {'schema': 'handyrl_tpu.blackbox/1', 'role': role, 'pid': pid,
            'run_id': run_id, 'reason': reason, 'time': t_dump,
            'stats': {'events': len(events), 'total': len(events)},
            'events': events, 'metrics': {}}


def _write(tmp_path, name, payload):
    path = tmp_path / name
    path.write_text(json.dumps(payload))
    return str(path)


def test_discover_filters_schema_and_run(tmp_path):
    _write(tmp_path, 'gather-0-11-runA.json',
           _dump('gather-0', 11, 'runA', 'gather-lost', 10.0, []))
    _write(tmp_path, 'worker-1-12-runB.json',
           _dump('worker-1', 12, 'runB', 'crash', 11.0, []))
    _write(tmp_path, 'junk.json', {'schema': 'something-else'})
    (tmp_path / 'torn.json').write_text('{not json')
    assert len(postmortem.discover_dumps(str(tmp_path))) == 2
    only_a = postmortem.discover_dumps(str(tmp_path), run_id='runA')
    assert [d['role'] for d in only_a] == ['gather-0']


def test_first_failure_ordered_by_last_event_not_dump_time(tmp_path):
    # the worker-host dumped LATER (t=200) about a death whose last
    # recorded event (t=100) precedes the worker's own loss at t=150 —
    # attribution must follow the event, not the file write
    early = _dump('worker-host', 20, 'r', 'gather-death', 200.0,
                  [{'t': 99.0, 'kind': 'log', 'msg': 'spawning'},
                   {'t': 100.0, 'kind': 'supervisor', 'msg': 'gather 0 died'}])
    late = _dump('worker-3', 21, 'r', 'gather-lost', 160.0,
                 [{'t': 150.0, 'kind': 'guard', 'msg': 'pipe EOF'}])
    report = postmortem.build_report([late, early], last_n=5)
    assert report['first_failure']['role'] == 'worker-host'
    assert report['first_failure']['time'] == 100.0
    times = [e['t'] for e in report['timeline']]
    assert times == sorted(times)
    deaths = [e for e in report['timeline'] if e['kind'] == 'death']
    assert len(deaths) == 2


def test_metrics_alert_transitions_span_rotation(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')

    def rec(t, active, fired):
        return json.dumps({'epoch': 1, 'run_id': 'r', 'time': t,
                           'alerts': {'time': t, 'active': active,
                                      'fired': fired}}) + '\n'
    # older generation (rotated) + live file: firing then clearing
    with open(path + '.1', 'w') as f:
        f.write(rec(10.0, [], {}))
    with open(path, 'w') as f:
        f.write(rec(20.0, ['heartbeat_misses'], {'heartbeat_misses': 1}))
        f.write(rec(30.0, [], {'heartbeat_misses': 1}))
        f.write('{torn half-line')
    alerts = postmortem.load_metrics_alerts(path)[0]
    assert alerts['records'] == 3
    assert alerts['transitions'] == [
        {'t': 20.0, 'alert': 'heartbeat_misses', 'state': 'firing'},
        {'t': 30.0, 'alert': 'heartbeat_misses', 'state': 'cleared'}]
    assert alerts['fired'] == {'heartbeat_misses': 1}
    assert alerts['still_active'] == []


def test_metrics_alerts_fired_between_records(tmp_path):
    # alerts evaluate every few seconds but records land per epoch: a rule
    # firing AND clearing between two records must still leave a
    # transition, reconstructed from the cumulative fired count
    path = str(tmp_path / 'metrics.jsonl')
    with open(path, 'w') as f:
        f.write(json.dumps({'epoch': 1, 'time': 10.0, 'alerts': {
            'time': 10.0, 'active': [], 'fired': {}}}) + '\n')
        f.write(json.dumps({'epoch': 2, 'time': 20.0, 'alerts': {
            'time': 20.0, 'active': [],
            'fired': {'heartbeat_misses': 1}}}) + '\n')
    alerts = postmortem.load_metrics_alerts(path)[0]
    assert alerts['transitions'] == [
        {'t': 20.0, 'alert': 'heartbeat_misses', 'state': 'fired+cleared'}]


def test_main_exit_contract_and_json_schema(tmp_path, capsys):
    empty = tmp_path / 'empty'
    empty.mkdir()
    assert postmortem.main([str(empty)]) == 2
    capsys.readouterr()
    _write(tmp_path, 'gather-0-11-r.json',
           _dump('gather-0', 11, 'r', 'gather-lost', 10.0,
                 [{'t': 9.0, 'kind': 'guard', 'msg': 'pipe EOF'}]))
    assert postmortem.main([str(tmp_path), '--json']) == 0
    report = json.loads(capsys.readouterr().out)
    assert report['schema'] == 'handyrl_tpu.postmortem/1'
    assert report['dumps'] == 1
    assert report['first_failure']['reason'] == 'gather-lost'
    assert report['timeline'][-1]['kind'] == 'death'


# ---------------------------------------------------------------------------
# chaos e2e: SIGKILLed gather -> blackbox dumps + alert + postmortem


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
                          'telemetry_port': %(tport)d,
                          'fault_tolerance': {
                              'heartbeat_interval': 1.0,
                              'liveness_timeout': 8.0,
                              'rpc_timeout': 30.0,
                              'task_deadline': 30.0,
                              'reconnect_initial_delay': 0.25,
                              'reconnect_max_delay': 2.0,
                              'reconnect_max_tries': 60}}}
    learner = Learner(args=apply_defaults(raw), remote=True)
    learner.run()
    print('LEARNER DONE', flush=True)

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


def _wait_for(predicate, deadline, poll=1.0):
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return predicate()


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_chaos_gather_kill_leaves_postmortem_trail(tmp_path):
    entry_port, data_port, tport = 21930, 21931, 21933
    model_dir = str(tmp_path / 'models')
    metrics = str(tmp_path / 'metrics.jsonl')
    blackbox = str(tmp_path / 'blackbox')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {
        'model_dir': model_dir, 'metrics': metrics, 'tport': tport})
    worker_py.write_text(WORKER_SCRIPT)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
                'HANDYRL_TPU_BLACKBOX': blackbox,
                'HANDYRL_TPU_ENTRY_PORT': str(entry_port),
                'HANDYRL_TPU_DATA_PORT': str(data_port),
                'PYTHONPATH': repo + os.pathsep
                + os.environ.get('PYTHONPATH', '')}
    worker_env = {**base_env,
                  'HANDYRL_TPU_CHAOS': 'kill_gather=6,max_kills=1,seed=3'}

    learner_log = open(tmp_path / 'learner.log', 'w')
    worker_log = open(tmp_path / 'worker.log', 'w')
    learner = subprocess.Popen([sys.executable, str(learner_py)],
                               env=base_env, stdout=learner_log,
                               stderr=subprocess.STDOUT)
    worker = None
    statusz = None
    try:
        time.sleep(3)   # let the entry/data servers bind
        worker = subprocess.Popen([sys.executable, str(worker_py)],
                                  env=worker_env, stdout=worker_log,
                                  stderr=subprocess.STDOUT)

        # the chaos kill fires ~6 s into the worker host's life; wait for
        # the evidence (>= 2 dumps: the dead gather's orphaned workers +
        # the worker-host supervisor's declaration)
        def dumped():
            return (os.path.isdir(blackbox)
                    and len(os.listdir(blackbox)) >= 2)
        assert _wait_for(lambda: dumped() or learner.poll() is not None,
                         time.time() + 240), \
            'chaos kill never produced blackbox dumps'

        # live status surface, scraped mid-run from the learner exporter
        payload = json.loads(urllib.request.urlopen(
            'http://127.0.0.1:%d/statusz' % tport, timeout=10
        ).read().decode())
        statusz = payload
        assert payload['role'] == 'learner'
        assert 'progress' in payload and 'recorder' in payload

        def done():
            return (os.path.exists(os.path.join(model_dir, '2.ckpt'))
                    or learner.poll() is not None)
        assert _wait_for(done, time.time() + 240), \
            'learner hung after the injected kill'
        assert os.path.exists(os.path.join(model_dir, '2.ckpt'))
        learner.wait(timeout=120)
        worker.wait(timeout=120)
    finally:
        for proc in (worker, learner):
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()
        learner_log.close()
        worker_log.close()

    # blackbox evidence from >= 2 distinct processes
    dumps = postmortem.discover_dumps(blackbox)
    assert len(dumps) >= 2, 'expected >= 2 dumps, got %r' % (
        sorted(os.listdir(blackbox)))
    reasons = {d['reason'] for d in dumps}
    assert 'gather-death' in reasons            # the supervisor declared it
    assert 'gather-lost' in reasons             # its workers saw pipe EOF
    assert len({d['pid'] for d in dumps}) >= 2

    # the alert engine saw the disconnect: heartbeat_misses fired (and
    # is cumulative in every later record's alerts.fired)
    fired = {}
    for line in open(metrics):
        rec = json.loads(line)
        assert 'alerts' in rec, 'metrics record without an alerts block'
        fired = rec['alerts'].get('fired') or fired
    assert 'heartbeat_misses' in fired, \
        'gather kill never fired heartbeat_misses: %r' % fired
    # an alert landed on the live status surface payload too
    assert 'alerts' in statusz

    # the postmortem names the kill as the first failure and exits 0
    out = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, 'postmortem.py'), blackbox,
         '--metrics', metrics, '--json'],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report['dumps'] >= 2
    assert report['first_failure']['reason'] in ('gather-lost',
                                                 'gather-death')
    assert any(e['kind'] == 'alert' and 'heartbeat_misses' in e['msg']
               for e in report['timeline'])
