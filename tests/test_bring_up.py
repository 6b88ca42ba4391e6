"""Bring-up contract (PR 21): the program says where it runs and does not
switch paths silently.

* ``chip_smoke.py`` has no CPU mode: without a TPU it exits non-zero in
  seconds, whatever JAX_PLATFORMS it inherits, and prints no verdict;
* the compile cache is placeable: JAX_COMPILATION_CACHE_DIR wins, else one
  fixed directory inside the checkout; an unusable place raises;
* every device-claiming process prints one ``device_claim`` line;
* the fallbacks that used to carry on now raise.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import handyrl_tpu
from handyrl_tpu import train as train_mod
from handyrl_tpu.config import apply_defaults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, env, cwd=REPO, timeout=120):
    return subprocess.run(cmd, env=env, cwd=cwd, timeout=timeout,
                          capture_output=True, text=True)


# ---- chip_smoke.py ---------------------------------------------------------

@pytest.mark.timeout(150)
@pytest.mark.parametrize('inherited', ['cpu', None])
def test_chip_smoke_fails_fast_without_a_tpu(inherited):
    env = {k: v for k, v in os.environ.items() if k != 'JAX_PLATFORMS'}
    if inherited:
        env['JAX_PLATFORMS'] = inherited
    proc = _run([sys.executable, os.path.join(REPO, 'chip_smoke.py')], env)
    assert proc.returncode not in (0, 2), proc.stderr[-2000:]
    assert 'no TPU found' in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_is_nothing(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(REPO, 'chip_smoke.py'), str(tmp_path))
    proc = _run([sys.executable, 'chip_smoke.py'], dict(os.environ),
                cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert not (tmp_path / 'chiprun_out').exists()


# ---- compile cache placement -----------------------------------------------

_CACHE_PROBE = ('import jax, handyrl_tpu; '
                'print(handyrl_tpu.setup_compile_cache()); '
                'print(jax.config.jax_compilation_cache_dir)')


@pytest.mark.parametrize('placed', [True, False])
def test_compile_cache_placement(tmp_path, placed):
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env['PYTHONPATH'] = REPO
    want = handyrl_tpu.COMPILE_CACHE_DIR
    if placed:
        want = env['JAX_COMPILATION_CACHE_DIR'] = str(tmp_path / 'xla')
    proc = _run([sys.executable, '-c', _CACHE_PROBE], env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want]
    assert os.path.isdir(want)
    # fixed and inside the checkout: nothing of the host, user or process
    assert handyrl_tpu.COMPILE_CACHE_DIR == os.path.join(REPO, '.jax_cache')


def test_compile_cache_unusable_place_raises(tmp_path, monkeypatch):
    blocker = tmp_path / 'file'
    blocker.write_text('not a directory')
    monkeypatch.setattr(handyrl_tpu, '_cache_dir', None)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update('jax_compilation_cache_dir', str(blocker / 'cache'))
    try:
        with pytest.raises(OSError):
            handyrl_tpu.setup_compile_cache()
        assert handyrl_tpu._cache_dir is None
    finally:
        jax.config.update('jax_compilation_cache_dir', before)


# ---- the start-up line -----------------------------------------------------

def test_claim_devices_line(capsys):
    from handyrl_tpu.parallel.mesh import make_mesh
    assert handyrl_tpu.claim_devices('solo')['used'] == 1
    claim = handyrl_tpu.claim_devices('tester', mesh=make_mesh(
        jax.devices()[:4], model_parallel=2))
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith('device_claim ')
    assert json.loads(line[len('device_claim '):]) == claim
    assert claim['role'] == 'tester' and claim['used'] == 4
    assert claim['mesh'] == {'data': 2, 'model': 2}
    assert claim['found'] == len(jax.devices()) == 8
    assert claim['backend'] == claim['platform'] == 'cpu'
    assert claim['jax'] == jax.__version__


# ---- strict gates ----------------------------------------------------------

def _learner_args(tmp_path, env='TicTacToe', **over):
    train = {'batch_size': 12, 'forward_steps': 4, 'update_episodes': 8,
             'minimum_episodes': 8, 'epochs': 1, 'generation_envs': 8,
             'num_batchers': 1, 'model_dir': str(tmp_path / 'models')}
    train.update(over)
    return apply_defaults({'env_args': {'env': env}, 'train_args': train})


@pytest.mark.timeout(300)
def test_device_generation_without_twin_raises(tmp_path):
    """It used to warn and fall back to host envs."""
    learner = train_mod.Learner(args=_learner_args(
        tmp_path, env='ParallelTicTacToe', device_generation=True))
    with pytest.raises(ValueError, match='pure-JAX twin'):
        learner.run()


@pytest.mark.timeout(300)
def test_sharded_fused_pipeline_needs_divisible_envs(tmp_path):
    """batch 8 shards over the 8-device mesh; 12 envs do not. It used to
    drop to the threaded path without a word."""
    learner = train_mod.Learner(args=_learner_args(
        tmp_path, batch_size=8, generation_envs=12,
        device_generation=True, device_replay=True))
    assert learner.trainer.mesh is not None
    with pytest.raises(ValueError, match='does not divide the 8-device'):
        learner.run()


def test_local_device_gathers_refused_on_an_accelerator(monkeypatch):
    learner = train_mod.Learner.__new__(train_mod.Learner)
    for args in ({'generation': {'backend': 'device'}},
                 {'inference': {'enabled': True,
                                'engine_backend': 'device'}}):
        learner.args = args
        monkeypatch.setattr(train_mod.jax, 'default_backend', lambda: 'cpu')
        learner._refuse_local_device_gathers()   # nothing contended
        monkeypatch.setattr(train_mod.jax, 'default_backend', lambda: 'tpu')
        with pytest.raises(ValueError, match='claim the chip'):
            learner._refuse_local_device_gathers()
    learner.args = {'inference': {'enabled': True, 'engine_backend': 'cpu'}}
    learner._refuse_local_device_gathers()


def test_force_cpu_backend_raises_when_the_pin_did_not_take(monkeypatch):
    from handyrl_tpu import connection
    connection.force_cpu_backend()               # the CPU suite: a no-op
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with pytest.raises(RuntimeError, match='already initialized'):
        connection.force_cpu_backend()


def test_device_gather_without_twin_raises(capsys):
    from handyrl_tpu.worker import gather_loop
    args = {'env': {'env': 'ParallelTicTacToe'},
            'generation': {'backend': 'device'}}
    with pytest.raises(ValueError, match='pure-JAX twin'):
        gather_loop(args, None, 0)
    assert 'device_claim {"role": "gather-0"' in capsys.readouterr().out


def test_cache_hit_is_not_booked_as_compile_time():
    """On the chip a warm start showed as many ``xla_compile_seconds`` as a
    cold one: jax reports the time a cache hit SAVED as a duration too."""
    from jax import monitoring

    from handyrl_tpu import telemetry
    assert telemetry.install_jax_monitoring()

    def total():
        hist = telemetry.summarize(telemetry.snapshot())['hists']
        assert not any('cache' in k for k in hist
                       if k.startswith('xla_compile_seconds'))
        return hist.get('xla_compile_seconds{event="backend_compile"}',
                        {}).get('sum', 0.0)

    before = total()
    monitoring.record_event_duration_secs(
        '/jax/compilation_cache/compile_time_saved_sec', 50.0)
    monitoring.record_event_duration_secs(
        '/jax/compilation_cache/cache_retrieval_time_sec', 7.0)
    assert total() == before
    monitoring.record_event_duration_secs(
        '/jax/core/compile/backend_compile_duration', 0.25)
    assert total() == pytest.approx(before + 0.25)
