"""CPU rehearsal of the harness end to end through main.py --train, each
cell at a tiny size (rehearse.py lays benchmark/rehearsal/tiny.json over the
cells' files into a manifest root of its own), and the measuring command's
refusal to run without a chip. The platform is pinned HERE, in the test's
environment: run_cell has one path and no CPU switch; rehearse.py swaps the
function that claims the chips."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, ROOT


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
    assert line['cpu_rehearsal'] is True and line['platform'] == 'cpu'
    assert all(line['checks'].values()), line['checks']
    assert line['attempted'] >= 1 and line['failed'] == 0
    assert line['reference']['forward']['plies'] == 3   # tiny.json's, as data
    # counts only: nothing under a device metric's name
    assert not set(line) & set(Manifest().metrics)
    assert 'metrics' not in line and 'device' not in line


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
