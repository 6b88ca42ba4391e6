"""A/B: per-window vs per-episode replay weighting (round-3 review, item 7).

The device windower ingests up to ``replay_windows_per_episode`` (W)
uniformly-placed windows per finished episode; ring rows are then drawn
with the same recency bias regardless of origin. W>1 therefore weights
SAMPLING MASS per episode by min(len//fs, W) — long episodes get more —
while the reference draws an EPISODE first and one window inside it
(reference train.py:291-306), i.e. equal mass per episode. Because window
starts are already uniform within the episode, **W=1 is exactly the
reference's weighting**: one uniformly-placed window per episode, ring
row = episode. So the A/B is config-only: identical budget, seeds, and
geometry, W=1 (per-episode) vs the default W (per-window).

Env: HungryGeese — the long-episode env (1..200 plies, hunger-truncated),
where the two weightings actually differ.

Run: JAX_PLATFORMS=cpu python scripts/replay_weighting_ab.py
     [--epochs N] [--arms 1,4] [--init CKPT]
Appends one JSON row per arm to benchmarks.jsonl.

--init (round-4 review, item 5 — the divergent regime): warm-start both arms from
a late-stage checkpoint (e.g. models_north_star_device/latest.ckpt) whose
policy plays LONG episodes, so min(len//fs, W) actually spreads and the
two weightings differ. Requires the full GeeseNet architecture (the
checkpoint's); the windows/episode ratio in each row is the regime gate —
rows where both arms sit near 1.0 are outside the divergent regime and
say nothing about weighting.
"""

import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def run_arm(windows_cap: int, epochs: int, init_ckpt: str = ''):
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.models import build
    from handyrl_tpu.train import Learner

    raw = {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {
            'turn_based_training': False, 'observation': True,
            'gamma': 0.99, 'forward_steps': 16, 'compress_steps': 4,
            'batch_size': 32, 'update_episodes': 100,
            'minimum_episodes': 100, 'epochs': epochs,
            'generation_envs': 32, 'num_batchers': 1, 'eval_envs': 32,
            'policy_target': 'VTRACE', 'value_target': 'VTRACE',
            'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 8,
            'replay_windows_per_episode': windows_cap,
            # rulebase discriminates long after vs-random saturates
            'eval': {'opponent': ['random', 'rulebase']},
            'model_dir': 'models_ab_w%d' % windows_cap,
            'init_params': init_ckpt,
        },
    }
    args = apply_defaults(raw)
    t0 = time.time()
    # --init checkpoints are full-GeeseNet snapshots; the from-scratch A/B
    # keeps the small net for CPU-budget reasons
    net = build('GeeseNet') if init_ckpt else build('GeeseNet', layers=4,
                                                    filters=16)
    learner = Learner(args=args, net=net)
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
    stats = learner.trainer.replay_stats
    eps = max(1, learner.num_returned_episodes)
    return {
        'row': 'replay-weighting-ab',
        'init_ckpt': init_ckpt or None,
        'windows_per_episode_ratio': round(
            (stats.get('windows_ingested') or 0) / eps, 2),
        'windows_per_episode': windows_cap,
        'weighting': 'per-episode (reference)' if windows_cap == 1
                     else 'per-window (x%d cap)' % windows_cap,
        'backend': jax.default_backend(),
        'epochs': learner.model_epoch,
        'episodes': learner.num_returned_episodes,
        'win_rate_last5': rates, 'eval_games': games,
        'windows_ingested': stats.get('windows_ingested'),
        'samples_drawn': stats.get('samples_drawn'),
        'wall_s': round(wall, 1),
        'time': time.strftime('%Y-%m-%d %H:%M:%S'),
    }


def main():
    epochs, arms, init_ckpt = 12, [1, 4], ''
    argv = iter(sys.argv[1:])
    for a in argv:
        key, _, val = a.partition('=')
        if key in ('--epochs', '--arms', '--init') and not val:
            try:
                val = next(argv)
            except StopIteration:
                raise SystemExit('%s needs a value' % key)
        if key == '--epochs':
            epochs = int(val)
        elif key == '--arms':
            arms = [int(x) for x in val.split(',')]
        elif key == '--init':
            init_ckpt = val
        else:
            raise SystemExit('unknown argument %r' % a)
    out = os.path.join(os.path.dirname(__file__), '..', 'benchmarks.jsonl')
    for w in arms:
        row = run_arm(w, epochs, init_ckpt)
        print(json.dumps(row), flush=True)
        with open(os.path.abspath(out), 'a') as f:
            f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
