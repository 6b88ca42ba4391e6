"""CI telemetry smoke: a short CPU training with the exporter enabled.

Proves the whole observability loop end to end in one subprocess run:

  1. launches a tiny in-process (batched-generation) learner with
     ``telemetry_port`` set and a ``metrics_jsonl`` sink;
  2. scrapes ``/metrics`` once while the run is live and validates the
     Prometheus text exposition format line by line;
  3. after the run exits, validates that every metrics_jsonl line parses
     and carries the telemetry schema (run_id + summarized registry).

Exits 0 on success, 1 with a reason on any failure. Stdlib + repo only.
"""

import os
import re
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PORT = int(os.environ.get('TELEMETRY_SMOKE_PORT', '18917'))

LEARNER = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'
from handyrl_tpu.config import apply_defaults
from handyrl_tpu.train import Learner
raw = {'env_args': {'env': 'TicTacToe'},
       'train_args': {'batch_size': 8, 'update_episodes': 20,
                      'minimum_episodes': 20, 'epochs': 2,
                      'forward_steps': 8, 'num_batchers': 1,
                      'generation_envs': 8, 'eval_envs': 4,
                      'model_dir': %(model_dir)r,
                      'metrics_jsonl': %(metrics)r,
                      'telemetry_port': %(port)d}}
learner = Learner(args=apply_defaults(raw))
learner.run()
if learner.trainer.failed:
    raise SystemExit('SMOKE LEARNER TRAIN FAILED: '
                     + (learner.trainer.failed_reason or 'see traceback'))
print('SMOKE LEARNER DONE', learner.model_epoch, flush=True)
'''

_PROM_LINE = re.compile(
    r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(_bucket|_sum|_count)?'
    r'(\{[^{}]*\})? [0-9eE.+-]+)$')


def fail(msg):
    print('TELEMETRY SMOKE FAILED: %s' % msg, flush=True)
    sys.exit(1)


def main():
    import tempfile
    workdir = tempfile.mkdtemp(prefix='telemetry_smoke.')
    metrics = os.path.join(workdir, 'metrics.jsonl')
    script = os.path.join(workdir, 'learner.py')
    with open(script, 'w') as f:
        f.write(LEARNER % {'model_dir': os.path.join(workdir, 'models'),
                           'metrics': metrics, 'port': PORT})

    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}
    proc = subprocess.Popen([sys.executable, script], env=env)
    exposition = ''
    try:
        deadline = time.time() + 300
        url = 'http://127.0.0.1:%d/metrics' % PORT
        while time.time() < deadline and proc.poll() is None:
            try:
                exposition = urllib.request.urlopen(
                    url, timeout=5).read().decode()
                # wait for BOTH needles: episodes appear during generation,
                # stage histograms only once batching starts — scraping in
                # between is a race, not a failure
                if ('episodes_generated_total' in exposition
                        and 'stage_seconds_bucket' in exposition):
                    break
            except OSError:
                pass
            time.sleep(1)
        rc = proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    if rc != 0:
        fail('learner exited rc=%d' % rc)

    # -- Prometheus text exposition -------------------------------------
    if not exposition.strip():
        fail('never scraped a non-empty /metrics response')
    for line in exposition.splitlines():
        if line.strip() and not _PROM_LINE.match(line):
            fail('invalid exposition line: %r' % line)
    for needle in ('episodes_generated_total', 'learner_epoch',
                   'stage_seconds_bucket'):
        if needle not in exposition:
            fail('expected metric %r missing from /metrics' % needle)
    print('exposition OK (%d lines)' % len(exposition.splitlines()))

    # -- metrics_jsonl schema -------------------------------------------
    from handyrl_tpu.telemetry import validate_metrics_line
    lines = [l for l in open(metrics).read().splitlines() if l.strip()]
    if not lines:
        fail('no metrics_jsonl records written')
    for line in lines:
        try:
            validate_metrics_line(line)
        except ValueError as exc:
            fail(str(exc))
    print('metrics_jsonl OK (%d epoch records)' % len(lines))
    print('TELEMETRY SMOKE PASSED')


if __name__ == '__main__':
    main()
