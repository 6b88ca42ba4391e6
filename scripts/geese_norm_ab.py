"""A/B: GroupNorm vs full BatchNorm in GeeseNet (round-4 review, item 2).

The round-4 Geister forensics proved the GroupNorm-for-BatchNorm
substitution causes that env's quality gap (reference drops 0.661 → 0.486
when its BatchNorm2d is shimmed to GroupNorm). The reference GeeseNet
carries BatchNorm in the stem + all 12 torus blocks
(reference hungry_geese.py:23-35,43-44), so the same substitution sits
under the flagship net — this measures whether it matters there.

Arms are config-only: identical budget/seeds/geometry through the fused
device pipeline (the geese-device row's config), differing only in
env_args norm_kind ('group' = repo baseline, 'batch' = full reference
BatchNorm parity with running-average inference). Win rates are scored
per opponent — 'rulebase' (the GreedyAgent behavioral port) keeps
discriminating after vs-random saturates.

Run: JAX_PLATFORMS=cpu python scripts/geese_norm_ab.py
     [--epochs N] [--arms group,batch]
Appends one JSON row per arm to benchmarks.jsonl.
"""

import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def run_arm(norm_kind: str, epochs: int):
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner

    raw = {
        'env_args': {'env': 'HungryGeese', 'norm_kind': norm_kind},
        'train_args': {
            'turn_based_training': False, 'observation': True,
            'gamma': 0.99, 'forward_steps': 16, 'compress_steps': 4,
            'batch_size': 64, 'update_episodes': 100,
            'minimum_episodes': 200, 'epochs': epochs,
            'generation_envs': 64, 'num_batchers': 1, 'eval_envs': 32,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE',
            'device_generation': True, 'device_replay': True,
            'device_chunk_steps': 32, 'sgd_steps_per_chunk': 64,
            'eval': {'opponent': ['random', 'rulebase']},
            'model_dir': 'models_ab_norm_%s' % norm_kind,
        },
    }
    args = apply_defaults(raw)
    t0 = time.time()
    learner = Learner(args=args)
    learner.run()
    wall = time.time() - t0

    last = learner.model_epoch - 1
    per_opp = {}
    for epoch in range(max(1, last - 4), last + 1):
        for opp, (en, er, _) in \
                learner.results_per_opponent.get(epoch, {}).items():
            n0, r0 = per_opp.get(opp, (0, 0.0))
            per_opp[opp] = (n0 + en, r0 + er)
    rates = {opp: round((r0 / (n0 + 1e-6) + 1) / 2, 3)
             for opp, (n0, r0) in per_opp.items()}
    games = {opp: n0 for opp, (n0, _) in per_opp.items()}
    return {
        'row': 'geese-norm-ab',
        'norm_kind': norm_kind,
        'backend': jax.default_backend(),
        'epochs': learner.model_epoch,
        'episodes': learner.num_returned_episodes,
        'win_rate_last5': rates, 'eval_games': games,
        'episodes_per_sec': round(learner.num_returned_episodes / wall, 2),
        'wall_s': round(wall, 1),
        'time': time.strftime('%Y-%m-%d %H:%M:%S'),
    }


def main():
    epochs, arms = 10, ['group', 'batch']
    argv = iter(sys.argv[1:])
    for a in argv:
        key, _, val = a.partition('=')
        if key in ('--epochs', '--arms') and not val:
            try:
                val = next(argv)
            except StopIteration:
                raise SystemExit('%s needs a value' % key)
        if key == '--epochs':
            epochs = int(val)
        elif key == '--arms':
            arms = val.split(',')
        else:
            raise SystemExit('unknown argument %r' % a)
    out = os.path.join(os.path.dirname(__file__), '..', 'benchmarks.jsonl')
    for nk in arms:
        row = run_arm(nk, epochs)
        print(json.dumps(row), flush=True)
        with open(os.path.abspath(out), 'a') as f:
            f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
