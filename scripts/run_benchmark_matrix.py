"""Run the BASELINE.md measurement matrix configs and record results.

Each row trains a config for a fixed number of epochs and records
throughput (episodes/sec, SGD steps/sec) and the aggregate win rate vs
random over the last 5 epochs, appending JSON rows to benchmarks.jsonl.

Usage: python scripts/run_benchmark_matrix.py [ROW ...] [--epochs N]
Rows: ttt-td ttt-device ttt-device-mesh8 ttt-vtrace geister
      geister-device geister-fused geese geese-device
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

ROWS = {
    'ttt-td': {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {'batch_size': 64, 'forward_steps': 8,
                       'update_episodes': 200, 'minimum_episodes': 400,
                       'generation_envs': 64},
    },
    'ttt-device': {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {'batch_size': 64, 'forward_steps': 8,
                       'update_episodes': 200, 'minimum_episodes': 400,
                       'generation_envs': 64,
                       'device_generation': True, 'device_replay': True,
                       # ~89 training samples per episode, the measured
                       # ratio of the round-2 threaded run (192*64 samples
                       # per ~136-episode chunk)
                       'sgd_steps_per_chunk': 192},
    },
    # the sharded fused pipeline on a virtual 8-device CPU mesh (multichip
    # evidence without multichip hardware): run with
    #   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
    'ttt-device-mesh8': {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {'batch_size': 64, 'forward_steps': 8,
                       'update_episodes': 200, 'minimum_episodes': 400,
                       'generation_envs': 64, 'eval_envs': 32,
                       'device_generation': True, 'device_replay': True,
                       'sgd_steps_per_chunk': 192},
    },
    'ttt-vtrace': {
        'env_args': {'env': 'TicTacToe'},
        'train_args': {'batch_size': 64, 'forward_steps': 8,
                       'update_episodes': 200, 'minimum_episodes': 400,
                       'generation_envs': 64,
                       'policy_target': 'UPGO', 'value_target': 'VTRACE'},
    },
    'geister': {
        'env_args': {'env': 'Geister'},
        'train_args': {'batch_size': 32, 'forward_steps': 16,
                       'burn_in_steps': 4, 'update_episodes': 100,
                       'minimum_episodes': 200, 'generation_envs': 32,
                       'observation': True},
    },
    # Geister through the device pipeline (DRC recurrent device rollouts).
    # The plain 'geister' row is unusable on the XLA-CPU backend: LLVM
    # codegen of the full DRC update step takes tens of minutes there
    # (first run only, with the persistent compile cache) — the TPU backend
    # is the real target for this net.
    'geister-device': {
        'env_args': {'env': 'Geister'},
        'train_args': {'batch_size': 32, 'forward_steps': 16,
                       'burn_in_steps': 4, 'update_episodes': 100,
                       'minimum_episodes': 200, 'generation_envs': 32,
                       'observation': True,
                       'device_generation': True, 'device_replay': True,
                       'device_chunk_steps': 32, 'eval_envs': 32},
    },
    # Geister through the FUSED pipeline (round 4): observation=True rides
    # the compact turn layout, so sample reuse is PINNED by
    # sgd_steps_per_chunk instead of free-spinning with the threaded
    # trainer (the round-3 quality-gap suspect). Reuse here ~= 4 * 32
    # samples per ~40-window chunk ~= 3x, near the reference's ~1x.
    'geister-fused': {
        'env_args': {'env': 'Geister'},
        'train_args': {'batch_size': 32, 'forward_steps': 16,
                       'burn_in_steps': 4, 'update_episodes': 100,
                       'minimum_episodes': 200, 'generation_envs': 32,
                       'observation': True,
                       'device_generation': True, 'device_replay': True,
                       'device_chunk_steps': 32, 'eval_envs': 32,
                       'sgd_steps_per_chunk': 4},
    },
    'geese': {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {'batch_size': 64, 'forward_steps': 16,
                       'update_episodes': 100, 'minimum_episodes': 200,
                       'generation_envs': 32,
                       'turn_based_training': False, 'observation': True,
                       'gamma': 0.99,
                       'policy_target': 'VTRACE', 'value_target': 'VTRACE'},
    },
    # round-1 review, item 5: the fully device-resident Hungry Geese pipeline —
    # rollouts, replay ring, and SGD all on the accelerator
    'geese-device': {
        'env_args': {'env': 'HungryGeese'},
        'train_args': {'batch_size': 64, 'forward_steps': 16,
                       'update_episodes': 100, 'minimum_episodes': 200,
                       'generation_envs': 64,
                       'turn_based_training': False, 'observation': True,
                       'gamma': 0.99,
                       'policy_target': 'VTRACE', 'value_target': 'VTRACE',
                       'device_generation': True, 'device_replay': True,
                       'device_chunk_steps': 32, 'eval_envs': 32,
                       # ~265 training samples per episode, the measured
                       # ratio of the round-2 threaded run (64*64 samples
                       # per ~17-episode chunk)
                       'sgd_steps_per_chunk': 64},
    },
}

# Round-5 norm A/B arms: DERIVED from their baseline rows so the pair can
# only ever differ in the one knob under test (norm_kind='batch' = full
# reference BatchNorm parity — batch statistics in the training forward,
# running averages served at inference; reference geister.py:107,122,
# hungry_geese.py:23-44, model.py:54). Baselines: 'geister-fused'
# (GroupNorm, 0.466 at 1,243 episodes r4; torch reference bar 0.661 at
# ~1k) and 'geese-device' (GroupNorm).
for _base, _twin in (('geister-fused', 'geister-fused-bn'),
                     ('geese-device', 'geese-device-bn')):
    _row = json.loads(json.dumps(ROWS[_base]))
    _row['env_args']['norm_kind'] = 'batch'
    ROWS[_twin] = _row

# the LSTM-era flagship configuration (BASELINE.md measurement-matrix
# row 4: "Hungry Geese, 4-player self-play, LSTM model"): recurrent
# GeeseNetLSTM through the same fused device pipeline — hidden state
# carried across plies like GeisterNet's DRC, burn-in windows included
ROWS['geese-lstm-device'] = json.loads(json.dumps(ROWS['geese-device']))
ROWS['geese-lstm-device']['env_args']['net_kind'] = 'lstm'
ROWS['geese-lstm-device']['train_args']['burn_in_steps'] = 4

# geister arms for the round-5 spatial-policy-head hypothesis: 'sp' =
# reference head structure alone, 'sp-bn' = head + full BatchNorm (the
# most reference-faithful GeisterNet this repo can express).
for _twin, _extra in (('geister-fused-sp', {'policy_head': 'spatial'}),
                      ('geister-fused-sp-bn', {'policy_head': 'spatial',
                                               'norm_kind': 'batch'}),
                      # + torch-default weight distributions
                      # (blocks.torch_default_inits) — the remaining
                      # dynamics suspect after head+norm measured +0.10
                      ('geister-fused-sp-bn-ti', {'policy_head': 'spatial',
                                                  'norm_kind': 'batch',
                                                  'init_kind': 'torch'})):
    _row = json.loads(json.dumps(ROWS['geister-fused']))
    _row['env_args'].update(_extra)
    ROWS[_twin] = _row


def run_row(name, epochs):
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner

    raw = json.loads(json.dumps(ROWS[name]))   # deep copy
    raw['train_args']['epochs'] = epochs
    raw['train_args']['model_dir'] = 'models_bench_%s' % name
    args = apply_defaults(raw)

    t0 = time.time()
    learner = Learner(args=args)
    init_s = time.time() - t0
    learner.run()
    wall = time.time() - t0

    last = learner.model_epoch - 1
    n = r = 0
    for epoch in range(max(1, last - 4), last + 1):
        if epoch in learner.results:
            en, er, _ = learner.results[epoch]
            n, r = n + en, r + er
    win_rate = (r / (n + 1e-6) + 1) / 2 if n else None

    import jax
    row = {
        'row': name, 'backend': jax.default_backend(),
        'epochs': learner.model_epoch,
        'episodes': learner.num_returned_episodes,
        'episodes_per_sec': round(learner.num_returned_episodes / wall, 2),
        'sgd_steps_per_sec': round(learner.trainer.last_steps_per_sec, 2),
        'win_rate_vs_random_last5': round(win_rate, 3) if win_rate else None,
        'eval_games': n, 'wall_s': round(wall, 1),
        'init_s': round(init_s, 1),
        'time': time.strftime('%Y-%m-%d %H:%M:%S'),
    }
    with open('benchmarks.jsonl', 'a') as f:
        f.write(json.dumps(row) + '\n')
    print(json.dumps(row))


def main():
    epochs = 10
    rows = []
    argv = iter(sys.argv[1:])
    for a in argv:
        if a.startswith('--epochs='):
            epochs = int(a.split('=', 1)[1])
        elif a == '--epochs':
            epochs = int(next(argv))
        elif a in ROWS:
            rows.append(a)
        else:
            raise SystemExit('unknown row %r (choose from %s, or --epochs=N)'
                             % (a, ', '.join(ROWS)))
    rows = rows or ['ttt-td']
    for name in rows:
        run_row(name, epochs)


if __name__ == '__main__':
    main()
