"""Account for shard_map overhead on the virtual CPU mesh (round-3 review, item 8).

Times ONE fused-pipeline dispatch (rollout chunk + window ingest + K SGD
steps, ops/fused_pipeline.py) at mesh sizes 1/2/4/8 with the GLOBAL
problem size held fixed (64 envs, batch 64, 16 SGD steps, 16-ply chunks —
the ttt-device benchmark geometry). On the virtual mesh every "device" is
a thread on the same physical core, so ideal scaling is FLAT wall time
(same total compute, more fixed overhead); any growth over the 1-device
row is the per-shard overhead a real ICI mesh would also pay per chip —
separated here into program count (dispatch), collective cost (psum
bytes), and small-kernel serialization.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python scripts/meshscale_bench.py [--steps N]
Appends one JSON row per mesh size to benchmarks.jsonl.
"""

import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault(
    'XLA_FLAGS', '--xla_force_host_platform_device_count=8')
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')
import numpy as np  # noqa: E402

from handyrl_tpu.config import apply_defaults  # noqa: E402
from handyrl_tpu.environment import make_env, make_jax_env  # noqa: E402
from handyrl_tpu.model import ModelWrapper  # noqa: E402
from handyrl_tpu.ops.device_windows import DeviceWindower  # noqa: E402
from handyrl_tpu.ops.fused_pipeline import FusedPipeline  # noqa: E402
from handyrl_tpu.ops.losses import LossConfig  # noqa: E402
from handyrl_tpu.ops.train_step import init_train_state  # noqa: E402
from handyrl_tpu.parallel.mesh import make_mesh, replicated_sharding  # noqa: E402

ENVS, BATCH, SGD, CHUNK, FS = 64, 64, 16, 16, 8


def measure(ndev: int, steps: int):
    env_args = {'env': 'TicTacToe'}
    env = make_env(env_args)
    env.reset()
    wrapper = ModelWrapper(env.net())
    wrapper.ensure_params(env.observation(0))
    env_mod = make_jax_env(env_args)
    args = apply_defaults({'env_args': env_args, 'train_args': {
        'batch_size': BATCH, 'forward_steps': FS}})['train_args']
    mesh = make_mesh(jax.devices()[:ndev]) if ndev > 1 else None
    wd = DeviceWindower(mode='turn', fs=FS, bi=0, max_steps=9,
                        windows_cap=1, capacity=512 // max(1, ndev),
                        num_players=2, gamma=args['gamma'],
                        has_reward=False)
    fp = FusedPipeline(env_mod, wrapper, LossConfig.from_args(args), wd,
                       args, n_envs=ENVS, chunk_steps=CHUNK, sgd_steps=SGD,
                       batch_size=BATCH, mesh=mesh)
    # actor params must not alias the (donated) train-state params
    params = jax.tree_util.tree_map(jax.numpy.copy, wrapper.params)
    state = init_train_state(wrapper.params)
    if mesh is not None:
        repl = replicated_sharding(mesh)
        params = jax.device_put(params, repl)
        state = jax.device_put(state, repl)

    # warm the ring + compile both programs
    for _ in range(3):
        fp.warm_step(params)
    state, _ = fp.train_step(params, state, 1.0)   # compile fused
    fp.drain()

    t0 = time.time()
    for _ in range(steps):
        state, _ = fp.train_step(params, state, 1.0)
    fp.drain()                                     # hard sync
    dt = (time.time() - t0) / steps

    # program-level accounting from XLA's own cost model
    cost = {}
    try:
        lowered = fp._fused.lower(
            params, state, fp.state, fp.hidden, fp.wstate, fp.ring,
            fp.cursor, fp.size, fp.rng,
            jax.numpy.asarray(1.0, jax.numpy.float32))
        c = lowered.compile().cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else {}
        # XLA reports PER-PARTITION cost; label it so, since every other
        # field in the row (envs, batch, dispatch_ms) is global
        cost = {'flops_per_shard': float(c.get('flops', 0.0)),
                'bytes_per_shard': float(c.get('bytes accessed', 0.0))}
    except Exception as exc:  # noqa: BLE001 — accounting is best-effort
        cost = {'error': str(exc)[:80]}
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(wrapper.params))
    return {'row': 'meshscale-fused', 'ndev': ndev,
            'dispatch_ms': round(dt * 1e3, 1),
            'sgd_steps': SGD, 'envs': ENVS, 'batch': BATCH,
            'param_count': n_params,
            'psum_bytes_per_dispatch': 4 * n_params * SGD * (ndev > 1),
            **cost}


def main():
    steps = 20
    argv = iter(sys.argv[1:])
    for a in argv:
        if a.startswith('--steps='):
            steps = int(a.split('=', 1)[1])
        elif a == '--steps':
            steps = int(next(argv))
    out_path = os.path.join(os.path.dirname(__file__), '..',
                            'benchmarks.jsonl')
    for ndev in (1, 2, 4, 8):
        if ndev > len(jax.devices()):
            break
        row = measure(ndev, steps)
        row['time'] = time.strftime('%Y-%m-%d %H:%M:%S')
        print(json.dumps(row), flush=True)
        with open(os.path.abspath(out_path), 'a') as f:
            f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
