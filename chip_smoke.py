#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two user-facing paths once, through ``main.py``, on one TPU:

1. ``main.py --train``: HungryGeese / GeeseNet at its shipped width (12
   torus-conv blocks x 32 filters) through the fused device pipeline at the
   repo's flagship geometry (``scripts/run_benchmark_matrix.py`` row
   ``geese-device``), bf16 activations, three epochs, with every tolerance
   turned off: a non-finite update aborts, a steady-state retrace aborts.
2. ``main.py --serve`` with the engines on the device, over the registry
   phase 1 published: a few dozen INFER requests through
   ``serving.client.ServiceClient``, every reply finite and of the right
   shape, then a SIGTERM drain that must answer everything and exit 75.
   The served policy/value are compared with the same checkpoint evaluated
   in float32 on the CPU by ``evaluation.load_model`` (a child pinned to
   the CPU, started after the service has released the chip).

One process owns the chip at a time: this parent never imports jax, and each
phase is one child that has exited before the next starts. The children's
platform is set here, explicitly, to ``tpu`` — an inherited JAX_PLATFORMS
proves nothing — so on a machine without a chip jax raises in seconds and
this script exits non-zero. There is no CPU mode.

Outputs (configs, logs, checkpoints, metrics_jsonl, ``verdict.json``) land in
``chiprun_out/chip_smoke/``, the directory the chip tool copies back. On
success the LAST stdout line is ``{"ok": true, "device": {...}}`` with the
device as jax reported it; on failure nothing of that shape is printed, the
reasons go to stderr and the exit code is 1. The compile cache is wherever
``handyrl_tpu.setup_compile_cache`` puts it: JAX_COMPILATION_CACHE_DIR if
set, else ``.jax_cache/`` in the checkout — a second run hits it.
"""

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, 'chiprun_out', 'chip_smoke')
DEADLINE = time.monotonic() + 1100   # the driver allows 1200 s in all
EPOCHS = 3
N_REQUESTS = 48
# served (TPU, float32 params, default matmul precision = bf16 passes) vs the
# CPU float32 reference: error relative to the largest logit, through 13
# conv layers; the value head ends in tanh
REF_POLICY_RTOL = 0.02
REF_VALUE_ATOL = 0.02

TRAIN_CONFIG = {
    'env_args': {'env': 'HungryGeese'},
    'train_args': {
        # scripts/run_benchmark_matrix.py ROWS['geese-device']
        'batch_size': 64, 'forward_steps': 16,
        'update_episodes': 100, 'minimum_episodes': 200,
        'generation_envs': 64,
        'turn_based_training': False, 'observation': True, 'gamma': 0.99,
        'policy_target': 'VTRACE', 'value_target': 'VTRACE',
        'device_generation': True, 'device_replay': True,
        'device_chunk_steps': 32, 'eval_envs': 32,
        'sgd_steps_per_chunk': 64,
        # the smoke's own: bf16 activations, three epochs, nothing forgiven
        'compute_dtype': 'bfloat16', 'epochs': EPOCHS, 'seed': 0,
        'guard': {'nonfinite_policy': 'abort'},
        'telemetry': {'retrace': 'abort'},
        'serving': {'publish': True},
        'metrics_jsonl': 'metrics.jsonl',
    },
}

REFERENCE_CHILD = r'''
import sys
import numpy as np
from handyrl_tpu.connection import force_cpu_backend
force_cpu_backend()
from handyrl_tpu.environment import make_env
from handyrl_tpu.evaluation import load_model
spec, obs_path, out_path = sys.argv[1:4]
model = load_model(spec, make_env({'env': 'HungryGeese'}))
outs = [model.inference(o) for o in np.load(obs_path)]
np.savez(out_path,
         policy=np.stack([np.asarray(o['policy'], np.float32) for o in outs]),
         value=np.stack([np.asarray(o['value'], np.float32) for o in outs]))
'''


class Failed(Exception):
    """A phase cannot go on (its later checks would only echo this one)."""


def _remaining(cap):
    return max(5.0, min(cap, DEADLINE - time.monotonic()))


def _child_env(platform):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = platform
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    return env


_LIVE = []


def _spawn(cmd, cwd, log, platform='tpu'):
    proc = subprocess.Popen(cmd, cwd=cwd, env=_child_env(platform),
                            stdout=log, stderr=subprocess.STDOUT,
                            start_new_session=True)
    _LIVE.append(proc)
    return proc


def _reap():
    """Stop every process this script started (whole process groups)."""
    for proc in _LIVE:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()


def _tail(path, lines=25):
    try:
        with open(path, errors='replace') as f:
            return ''.join(f.readlines()[-lines:])
    except OSError:
        return ''


def _write_config(workdir, config):
    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, 'config.yaml'), 'w') as f:
        json.dump(config, f, indent=1)   # JSON is YAML


def _claim(log_path, role, failures):
    """The start-up line of the process that claimed the device: it must be
    on the TPU and use every device it found."""
    with open(log_path, errors='replace') as f:
        claims = [json.loads(line[len('device_claim '):]) for line in f
                  if line.startswith('device_claim ')]
    claim = next((c for c in claims if c.get('role') == role), None)
    if claim is None:
        raise Failed('no TPU found: the %s printed no device_claim line (jax '
                     'could not reach one, or the process died first)\n%s'
                     % (role, _tail(log_path)))
    if claim['platform'] != 'tpu' or claim['backend'] != 'tpu':
        failures.append('%s landed on %s/%s, not tpu'
                        % (role, claim['platform'], claim['backend']))
    if claim['used'] != claim['found']:
        failures.append('%s uses %d of the %d devices it found'
                        % (role, claim['used'], claim['found']))
    return claim


_BACKEND_COMPILE = 'xla_compile_seconds{event="backend_compile"}'


def _compile_facts(counters, compile_seconds):
    """Backend-compile seconds and cache traffic of one process."""
    def count(event):
        return int(counters.get(
            'xla_compile_events_total{event="jax/compilation_cache/%s"}'
            % event, 0))
    return {'compile_seconds': round(float(compile_seconds), 2),
            'cache_hits': count('cache_hits'),
            'cache_requests': count('compile_requests_use_cache')}


# ---------------------------------------------------------------------------
# phase 1: the learner


def phase_train(failures):
    workdir = os.path.join(OUT, 'train')
    _write_config(workdir, TRAIN_CONFIG)
    log_path = os.path.join(workdir, 'train.log')
    t0 = time.monotonic()
    with open(log_path, 'w') as log:
        proc = _spawn([sys.executable, os.path.join(REPO, 'main.py'),
                       '--train'], workdir, log)
        try:
            rc = proc.wait(timeout=_remaining(800))
        except subprocess.TimeoutExpired:
            raise Failed('main.py --train did not finish in time\n'
                         + _tail(log_path))
    wall = time.monotonic() - t0
    claim = _claim(log_path, 'learner', failures)
    if rc != 0:
        raise Failed('main.py --train exited %d\n%s' % (rc, _tail(log_path)))

    with open(log_path, errors='replace') as f:
        text = f.read()
    if 'fused device pipeline: rollout+ingest+train in one dispatch' \
            not in text:
        failures.append('the fused device pipeline did not run')
    if claim['found'] > 1 and ('sharded over %d devices' % claim['found']
                               not in text):
        failures.append('fused pipeline not sharded over all %d devices'
                        % claim['found'])
    updates = [int(n) for n in re.findall(r'^updated model\((\d+)\)', text,
                                          re.M)]
    if len(updates) != EPOCHS or not updates or updates[-1] <= 0:
        failures.append('expected %d "updated model(N)" lines with SGD steps '
                        '> 0, got %r' % (EPOCHS, updates))
    losses = re.findall(r'^loss = (.*)$', text, re.M)
    values = [float(v) for line in losses
              for v in re.findall(r':(\S+)', line)]
    if len(losses) != EPOCHS or not all(math.isfinite(v) for v in values):
        failures.append('expected %d finite loss lines, got %r'
                        % (EPOCHS, losses))
    for name in ('latest.ckpt', 'trainer_state.ckpt'):
        if not os.path.exists(os.path.join(workdir, 'models', name)):
            failures.append('models/%s was not written' % name)

    facts = {'wall_seconds': round(wall, 1),
             'sgd_steps': updates[-1] if updates else 0}
    rows = []
    try:
        with open(os.path.join(workdir, 'metrics.jsonl')) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except OSError:
        pass
    if len(rows) != EPOCHS:
        failures.append('metrics_jsonl has %d rows, expected %d'
                        % (len(rows), EPOCHS))
        return claim, facts
    last = rows[-1]
    tel = last.get('telemetry') or {}
    counters, gauges = tel.get('counters', {}), tel.get('gauges', {})
    if last.get('guard_nonfinite') != 0:
        failures.append('non-finite updates: %r' % last.get('guard_nonfinite'))
    if counters.get('xla_retraces_total', 0):
        failures.append('steady-state retraces: %r'
                        % counters['xla_retraces_total'])
    if not gauges.get('xla_steady_state'):
        failures.append('the retrace sentinel never armed (no steady state)')
    # device memory must come from Device.memory_stats(), not the RSS row
    mem = {k: v for k, v in gauges.items()
           if k.startswith('device_mem_bytes_in_use{')}
    on_chip = [k for k, v in mem.items() if 'device="tpu:' in k and v > 0]
    if len(on_chip) != claim['found'] or len(mem) != claim['found']:
        failures.append('device memory rows %r: expected one tpu:<id> row in '
                        'use per device found (%d)'
                        % (sorted(mem), claim['found']))
    facts.update(_compile_facts(
        counters, tel.get('hists', {}).get(_BACKEND_COMPILE, {}).get('sum', 0)))
    facts['episodes'] = last.get('episodes')
    facts['device_mem_bytes_peak'] = {
        k.split('"')[1]: int(v) for k, v in gauges.items()
        if k.startswith('device_mem_bytes_peak{')}
    return claim, facts


# ---------------------------------------------------------------------------
# phase 2: the service


def _observations(n):
    """n HungryGeese observations + legal actions from seeded host play
    (the env is numpy-only; nothing here imports jax)."""
    import random

    from handyrl_tpu.environment import make_env
    random.seed(0)
    env = make_env({'env': 'HungryGeese'})
    env.reset()
    out = []
    while len(out) < n:
        if env.terminal():
            env.reset()
        for p in env.turns():
            out.append((env.observation(p), env.legal_actions(p)))
        env.step({p: random.choice(env.legal_actions(p))
                  for p in env.turns()})
    return out[:n]


def _ready_line(path, proc):
    """Poll the service's log for its serving_ready JSON line."""
    limit = time.monotonic() + _remaining(240)
    while time.monotonic() < limit:
        if proc.poll() is not None:
            raise Failed('main.py --serve exited %d before it was ready\n%s'
                         % (proc.returncode, _tail(path)))
        with open(path, errors='replace') as f:
            for line in f:
                if line.startswith('{"serving_ready"'):
                    return json.loads(line)['serving_ready']
        time.sleep(0.5)
    raise Failed('no serving_ready line in time\n' + _tail(path))


def _scrape(port):
    """Compile counters off the service's Prometheus endpoint."""
    with urllib.request.urlopen('http://127.0.0.1:%d/metrics' % port,
                                timeout=10) as resp:
        text = resp.read().decode()
    counters, seconds = {}, 0.0
    for line in text.splitlines():
        key, _, val = line.rpartition(' ')
        if key.startswith('xla_compile_events_total{'):
            counters[key] = float(val)
        elif key == _BACKEND_COMPILE.replace('{', '_sum{'):
            seconds = float(val)
    return _compile_facts(counters, seconds)


def phase_serve(failures, registry_dir):
    import numpy as np

    from handyrl_tpu.serving.client import (ServiceClient, ServiceError,
                                            ServiceUnavailable)
    workdir = os.path.join(OUT, 'serve')
    _write_config(workdir, {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {
            'inference': {'engine_backend': 'device'},
            'serving': {'port': 0, 'registry_dir': registry_dir,
                        'metrics_port': 19997}}})
    log_path = os.path.join(workdir, 'serve.log')
    model = 'default@%d' % EPOCHS
    rows = _observations(N_REQUESTS)
    t0 = time.monotonic()
    with open(log_path, 'w') as log:
        proc = _spawn([sys.executable, os.path.join(REPO, 'main.py'),
                       '--serve'], workdir, log)
        ready = _ready_line(log_path, proc)
        _claim(log_path, 'serve', failures)
        client = ServiceClient('127.0.0.1', ready['port'], timeout=120.0,
                               name='chip_smoke')
        # half plain inference (policy/value rows, compared with the CPU
        # reference below), half act requests (masked sampling server-side);
        # each half is submitted as one wave so the engine batches it
        half = N_REQUESTS // 2
        rids = [client.submit(model, obs) for obs, _ in rows[:half]]
        replies = [client.collect(rid) for rid in rids]
        rids = [client.submit(model, obs, legal=legal, seed=[7, n])
                for n, (obs, legal) in enumerate(rows[half:])]
        acts = [client.collect(rid) for rid in rids]
        facts = _scrape(ready['metrics_port'])
        status = client.status()

        # graceful drain: a last wave is in flight when SIGTERM lands; every
        # request must be answered (a reply, or a 'draining' error reply)
        rids = [client.submit(model, obs) for obs, _ in rows[:8]]
        proc.send_signal(signal.SIGTERM)
        unanswered = 0
        for rid in rids:
            try:
                client.collect(rid, timeout=60.0)
            except ServiceError:
                pass
            except (TimeoutError, ServiceUnavailable):
                unanswered += 1
        client.close()
        try:
            rc = proc.wait(timeout=_remaining(90))
        except subprocess.TimeoutExpired:
            raise Failed('the service did not exit after SIGTERM\n'
                         + _tail(log_path))
    facts['wall_seconds'] = round(time.monotonic() - t0, 1)
    if rc != 75:
        failures.append('the service exited %d after SIGTERM, not 75\n%s'
                        % (rc, _tail(log_path)))
    if unanswered:
        failures.append('%d request(s) unanswered through the drain'
                        % unanswered)
    if status['received'] != status['answered'] or status['shed']:
        failures.append('service counters: %r' % {
            k: status[k] for k in ('received', 'answered', 'shed')})
    facts['engine_batches'] = status['engine_batches']

    policy = np.stack([np.asarray(r['outputs']['policy'], np.float32)
                       for r in replies])
    value = np.stack([np.asarray(r['outputs']['value'], np.float32)
                      for r in replies])
    if policy.shape != (half, 4) or value.shape != (half, 1) \
            or not (np.isfinite(policy).all() and np.isfinite(value).all()):
        failures.append('served outputs: policy %r value %r, finite %s'
                        % (policy.shape, value.shape,
                           bool(np.isfinite(policy).all()
                                and np.isfinite(value).all())))
    for (_, legal), act in zip(rows[half:], acts):
        if act['action'] not in legal or not 0.0 < float(act['prob']) <= 1.0:
            failures.append('act reply %r for legal %r'
                            % ({k: act[k] for k in ('action', 'prob')}, legal))
            break

    # the same checkpoint in float32 on the CPU, by the repo's own loader
    obs_path = os.path.join(workdir, 'obs.npy')
    ref_path = os.path.join(workdir, 'reference.npz')
    np.save(obs_path, np.stack([obs for obs, _ in rows[:half]]))
    ref_log = os.path.join(workdir, 'reference.log')
    with open(ref_log, 'w') as log:
        ref = _spawn([sys.executable, '-c', REFERENCE_CHILD,
                      'registry://%s/%s' % (registry_dir, model),
                      obs_path, ref_path], workdir, log, platform='cpu')
        try:
            ref_rc = ref.wait(timeout=_remaining(180))
        except subprocess.TimeoutExpired:
            raise Failed('the CPU reference did not finish\n' + _tail(ref_log))
    if ref_rc != 0:
        raise Failed('the CPU reference exited %d\n%s'
                     % (ref_rc, _tail(ref_log)))
    want = np.load(ref_path)
    np.savez(os.path.join(workdir, 'served.npz'), policy=policy, value=value)
    scale = max(1.0, float(np.abs(want['policy']).max()))
    err = {'policy_rel': float(np.abs(policy - want['policy']).max()) / scale,
           'value_abs': float(np.abs(value - want['value']).max())}
    facts['err_vs_cpu_f32'] = {k: round(v, 5) for k, v in err.items()}
    if err['policy_rel'] > REF_POLICY_RTOL or err['value_abs'] > REF_VALUE_ATOL:
        failures.append('served outputs differ from the CPU float32 '
                        'reference: %r (limits %s of the largest logit, %s)'
                        % (facts['err_vs_cpu_f32'], REF_POLICY_RTOL,
                           REF_VALUE_ATOL))
    return facts


# ---------------------------------------------------------------------------


def main():
    if not (os.path.exists(os.path.join(REPO, 'main.py'))
            and os.path.isdir(os.path.join(REPO, 'handyrl_tpu'))):
        print('chip_smoke: main.py and handyrl_tpu/ must sit beside this '
              'script; it drives them and is nothing on its own',
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    verdict = {'ok': False, 'phases': {}}
    failures = []
    try:
        claim, verdict['phases']['train'] = phase_train(failures)
        verdict.update(platform=claim['platform'],
                       device_kind=claim['device_kind'],
                       device_count=claim['found'], jax=claim['jax'])
        if not failures:
            verdict['phases']['serve'] = phase_serve(
                failures, os.path.join(OUT, 'train', 'models'))
    except Failed as exc:
        failures.append(str(exc))
    finally:
        _reap()
    verdict['ok'] = not failures
    verdict['failures'] = failures
    with open(os.path.join(OUT, 'verdict.json'), 'w') as f:
        f.write(json.dumps(verdict) + '\n')
    if failures:
        for reason in failures:
            print('chip_smoke FAILED: ' + reason, file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    print(json.dumps({'ok': True, 'device': {
        'platform': verdict['platform'], 'kind': verdict['device_kind'],
        'count': verdict['device_count']}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
