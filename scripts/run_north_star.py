"""North-star quality run: long Hungry Geese self-play on the device pipeline.

BASELINE.json's quality metric is Hungry Geese win-rate-vs-random at scale
(speed is measured by benchmark/, on the chip).
This driver runs the geese-device config for as many episodes as the
wall-clock allows, writing one metrics-JSONL row per epoch (win_rate,
episodes, sgd steps) so scripts/north_star_curve.py can plot the
win-rate-vs-episodes curve.

The reference itself CANNOT run this env here (its HungryGeese wraps
kaggle_environments, not installed in this image — reference
envs/kaggle/hungry_geese.py:67); the same-budget dynamics control is our
host-path engine (per-episode buffer sampling faithful to reference
train.py:291-315), run with --host.

Auto-resume: if the model dir already holds checkpoints, training restarts
from the newest one (params + optimizer state), so the curve continues
across interrupted windows.

Usage:
  python scripts/run_north_star.py [--epochs N] [--host] [--budget-s S] \
      [--metrics-out PATH]

--metrics-out redirects the per-epoch metrics JSONL (default
north_star_<tag>.jsonl). Use it when the model dir starts EMPTY but the
default file already holds a previous run's epochs (the round-5 case:
checkpoints were lost to a re-provision, so a fresh run restarts at
epoch 0 — appending to the old file would interleave two incomparable
runs under the same epoch keys).
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

BASE = {
    'env_args': {'env': 'HungryGeese'},
    'train_args': {'batch_size': 64, 'forward_steps': 16,
                   'update_episodes': 100, 'minimum_episodes': 200,
                   'generation_envs': 64,
                   'turn_based_training': False, 'observation': True,
                   'gamma': 0.99,
                   'policy_target': 'VTRACE', 'value_target': 'VTRACE',
                   'device_generation': True, 'device_replay': True,
                   'device_chunk_steps': 32, 'eval_envs': 32,
                   'sgd_steps_per_chunk': 64,
                   # host snapshot + ckpt files every 10 epochs: the
                   # per-epoch state fetch+serialize was 42% of wall time
                   'checkpoint_interval': 10},
}


def latest_epoch(model_dir: str) -> int:
    if not os.path.isdir(model_dir):
        return 0
    best = 0
    for name in os.listdir(model_dir):
        m = re.match(r'^(\d+)\.ckpt$', name)
        if m:
            best = max(best, int(m.group(1)))
    return best


def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner

    epochs = None   # None = not explicitly given (default 600, see below)
    host = False
    budget_s = None
    metrics_out = None
    argv = sys.argv[1:]
    while argv:
        a = argv.pop(0)
        if a == '--epochs':
            epochs = int(argv.pop(0))
        elif a == '--host':
            host = True
        elif a == '--budget-s':
            budget_s = float(argv.pop(0))
        elif a == '--metrics-out':
            metrics_out = argv.pop(0)
        else:
            raise SystemExit('unknown arg: %s' % a)

    tag = 'host' if host else 'device'
    raw = {'env_args': dict(BASE['env_args']),
           'train_args': dict(BASE['train_args'])}
    if host:
        # reference-dynamics control: same net/targets/cadence, host
        # generation + per-episode buffer sampling (reference
        # train.py:291-315 semantics), torch-free
        for k in ('device_generation', 'device_replay',
                  'device_chunk_steps', 'eval_envs', 'sgd_steps_per_chunk'):
            raw['train_args'].pop(k, None)
        raw['train_args']['generation_envs'] = 16
    model_dir = 'models_north_star_%s' % tag
    raw['train_args']['model_dir'] = model_dir
    raw['train_args']['metrics_jsonl'] = (metrics_out or
                                          'north_star_%s.jsonl' % tag)
    if epochs is None:
        # budget governs when given: the round-5 chip run stopped at the
        # DEFAULT 600-epoch cap after 17 min of a 150-min budget. Only an
        # epoch cap the operator actually TYPED limits a budgeted run —
        # `--epochs 600 --budget-s ...` really stops at 600 now.
        epochs = 10 ** 6 if budget_s is not None else 600
    raw['train_args']['epochs'] = epochs
    start = latest_epoch(model_dir)
    raw['train_args']['restart_epoch'] = start
    if budget_s is not None:
        # leave shutdown margin so the final checkpoint lands inside budget
        os.environ.setdefault('HANDYRL_TPU_DEADLINE',
                              str(time.time() + budget_s))

    args = apply_defaults(raw)
    print('north-star %s run: epochs %d->%d, model_dir=%s' %
          (tag, start, epochs, model_dir), flush=True)
    t0 = time.time()
    learner = Learner(args=args)
    learner.run()
    print(json.dumps({
        'row': 'north-star-%s' % tag,
        'epochs': learner.model_epoch,
        'episodes': learner.num_returned_episodes,
        'wall_s': round(time.time() - t0, 1),
    }), flush=True)


if __name__ == '__main__':
    main()
