"""CPU rehearsal of the harness end to end through main.py --train, each
cell at a tiny size (rehearse.py lays the configuration's rehearsal overlay,
benchmark/rehearsal/<configuration>.json or else tiny.json, over the cell's
files into a manifest root of its own), the watchdog's last words, and the
measuring command's refusal to run without a chip. The platform is pinned
HERE, in the test's environment: run_cell has one path and no CPU switch;
rehearse.py swaps the function that claims the chips."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import rehearse, session
from benchmark.manifest import Manifest, ROOT

from tests.benchmark import contracts
from tests.benchmark.fixture import make_root


def _env():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)   # one CPU device: a one-chip cell
    env.pop('BENCH_RUN', None)
    return env


@pytest.mark.timeout(600)
@pytest.mark.parametrize('workload', list(Manifest().cells))
def test_cell_rehearses_on_the_cpu(workload):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'rehearse.py'),
         '--workload', workload, '--seconds', '2', '--seed', str(2 ** 31 + 11)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(line['checks'].values()), line['checks']
    # four verdicts the harness decides from the window, letter for letter
    # and in order, then the configuration's `checks` as its file lists
    # them: for these cells the six, with tiny.json's three plies, as data
    contracts.a_rehearsal_line(Manifest(), workload, line)
    assert line['correct'] is True
    assert line['attempted'] >= 1 and line['failed'] == 0
    # a hook file that another cell brings wraps nothing here
    assert make_root.HOOK not in line['spans']


def test_a_configurations_own_overlay_is_laid_over_its_files(tmp_path):
    """``rehearsal/<configuration>.json`` where the root has one, else
    ``tiny.json``: every block of it reaches the files it names, and the
    traffic files of a workload's root take its configuration's overlay."""
    root = make_root.build(str(tmp_path / 'root'))
    path = os.path.join(root, 'benchmark', 'rehearsal',
                        make_root.CONFIG + '.json')
    with open(path) as f:
        own = json.load(f)
    own['env_args'] = {'norm_kind': 'layer'}
    own['window']['trace_seconds'] = 2
    with open(path, 'w') as f:
        json.dump(own, f)
    real = Manifest(root)
    assert rehearse.overlay_of(real, make_root.CONFIG)['env_args']
    tiny = rehearse.overlay_of(real, 'geese')
    assert 'env_args' not in tiny and 'model' not in tiny

    rehearse.build_root(real, str(tmp_path / 'own'), make_root.CELL)
    laid = Manifest(str(tmp_path / 'own'))
    config = laid.load_config(make_root.CONFIG)
    assert config['env_args']['norm_kind'] == 'layer'
    assert config['env_args']['env'] == 'HungryGeese'      # the rest stays
    assert config['model']['flops_per_window'] == 77
    assert config['model']['parameters'] == 116512
    assert (config['reference_envs'], config['reference_plies']) == (3, 2)
    traffic = laid.load_traffic(make_root.TRAFFIC)
    assert traffic['train_args']['forward_steps'] == 5
    assert traffic['train_args']['sgd_steps_per_chunk'] == 2   # the cap
    assert traffic['window']['trace_seconds'] == 2
    # a shipped configuration in the same root keeps tiny.json's sizes
    geese = laid.load_config('geese')
    assert geese['env_args']['norm_kind'] == 'group'
    assert geese['reference_plies'] == tiny['config']['reference_plies']

    rehearse.build_root(real, str(tmp_path / 'shipped'), 'geese.sgd_heavy')
    laid = Manifest(str(tmp_path / 'shipped'))
    traffic = laid.load_traffic('sgd_heavy')
    assert traffic['train_args']['forward_steps'] == \
        tiny['train_args']['forward_steps'] == 4
    assert traffic['window']['trace_seconds'] == 1
    assert 'cpu' in laid.load_peaks()


class _Window:
    open = trace_open = close = trace_close = trace_dir = None


def test_the_phase_table_names_the_phase_a_run_is_in():
    marks = {'checks': 10.0, 'learner start': 14.0}
    spans = {'warm_dispatch': [(20.0, 21.0, {})],
             'train_dispatch': [(30.0, 31.0, {}), (31.0, 32.0, {})]}
    window = _Window()
    table = session.phase_table
    assert table(0.0, {}, {}, None, None, 5.0) == [
        ('imports, device claim', 5.0)]
    assert table(0.0, marks, {}, window, 'train_dispatch', 18.0) == [
        ('imports, device claim', 10.0), ('checks', 4.0),
        ('learner start', 4.0)]
    assert table(0.0, marks, spans, window, 'train_dispatch', 33.0)[3:] == [
        ('warm-up', 10.0), ('before the window', 3.0)]
    window.open, window.close = 35.0, 86.0
    assert table(0.0, marks, spans, window, 'train_dispatch', 90.0)[4:] == [
        ('before the window', 5.0), ('window', 51.0), ('flush', 4.0)]
    window.trace_dir, window.trace_open = 'trace', 86.5
    assert table(0.0, marks, spans, window, 'train_dispatch', 88.0)[5:] == [
        ('window', 51.5), ('trace', 1.5)]
    window.trace_close = 89.5
    marks['reading'] = 95.0
    got = table(0.0, marks, spans, window, 'train_dispatch', 100.0)
    assert [phase for phase, _s in got] == list(session.PHASES)
    assert got[-3:] == [('trace', 3.0), ('flush', 5.5), ('reading', 5.0)]
    assert sum(seconds for _phase, seconds in got) == pytest.approx(100.0)


def test_the_limit_is_under_the_drivers_and_grows_by_compile_seconds_only():
    assert session.HARD_LIMIT_S < 360 < session.COLD_LIMIT_S < 1200

    class Compiles:
        def seconds(self, suffix):
            assert suffix == 'backend_compile_duration'
            return self.spent
    dog = session._Watchdog(345, time.perf_counter(), sys.stderr)
    try:
        assert dog._allowance() == 345
        dog.compiles = Compiles()
        dog.compiles.spent = 3.3
        assert dog._allowance() == pytest.approx(348.3)
        dog.compiles.spent = 4000.0
        assert dog._allowance() == session.COLD_LIMIT_S
    finally:
        dog.cancel()


@pytest.mark.timeout(600)
def test_a_run_that_overstays_ends_itself_and_says_in_which_phase():
    """The limit comes through ``run_cell``'s argument (``fixture/
    overstay.py``), a few seconds of it: the run is ended by its own
    watchdog, with the phase table on standard error, exit code 4 and no
    result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'tests', 'benchmark', 'fixture',
                                      'overstay.py'),
         '--workload', 'geese.rollout_heavy', '--seconds', '600',
         '--seed', '3'],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=540)
    assert proc.returncode == 4, proc.stderr[-3000:]
    assert proc.stdout.strip() == ''
    tail = proc.stderr.strip().splitlines()
    first = max(i for i, row in enumerate(tail)
                if 'benchmark: hard time limit' in row)
    assert 'giving up in the phase "' in tail[first]
    rows = [row.split() for row in tail[first + 1:]]
    assert all(row[0] == 'benchmark:' and row[-1] == 's' for row in rows)
    phases = [' '.join(row[1:-2]) for row in rows]
    assert phases[0] == 'imports, device claim'
    assert phases == [p for p in session.PHASES if p in phases]   # in order
    assert tail[first].endswith('"%s"' % phases[-1])
    assert 'checks' in phases and 'reading' not in phases


@pytest.mark.timeout(300)
def test_the_measuring_command_fails_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', 'geese.sgd_heavy', '--seed', '1', '--seconds', '1',
         '--trace', '0'],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0
    assert 'needs 1 TPU chip' in proc.stderr
    assert '"correct"' not in proc.stdout


@pytest.mark.timeout(120)
def test_an_unknown_workload_fails_before_jax_is_touched():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', 'no.such_cell', '--seed', '1'],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=100)
    assert proc.returncode != 0 and proc.stdout.strip() == ''
