"""Offline win-rate curve from a model_dir of checkpoints.

The online eval share samples too few games per epoch to draw a smooth
quality curve for fast runs (an epoch lasts ~2s in the north-star config);
this scores saved checkpoints directly with the DeviceEvaluator — whole
matches on the accelerator, a few hundred games per point in seconds.

Usage:
  python scripts/eval_checkpoints.py MODEL_DIR ENV OUT.jsonl \
      [--every N] [--games G] [--envs E] [--opponent random|rulebase|CKPT] \
      [--env-args JSON] [--skip-scored]

--skip-scored makes reruns incremental: epochs already present in
OUT.jsonl (for the same opponent) are not re-scored, so a recurring
caller only pays for
checkpoints that appeared since the last pass instead of re-evaluating
the whole curve and appending duplicate rows.

--env-args merges extra env_args (e.g. '{"norm_kind": "batch"}') so the
rebuilt net matches the checkpoints' param tree — REQUIRED when scoring a
run trained with a non-default model config.

Writes one JSON line per checkpoint: {"epoch": N, "opponent": O,
"games": G, "win_rate": W, "mean": M} where win_rate = (mean outcome+1)/2
(the reference's normalization, train.py win-rate lines).
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def main():
    model_dir, env_name, out_path = sys.argv[1:4]
    opts = sys.argv[4:]

    def opt(name, default):
        return int(opts[opts.index(name) + 1]) if name in opts else default

    every = opt('--every', 5)
    games = opt('--games', 192)
    n_envs = opt('--envs', 64)
    opponent = (opts[opts.index('--opponent') + 1]
                if '--opponent' in opts else 'random')
    extra_env_args = (json.loads(opts[opts.index('--env-args') + 1])
                      if '--env-args' in opts else {})

    import numpy as np

    import handyrl_tpu
    handyrl_tpu.setup_compile_cache()
    from handyrl_tpu.device_generation import DeviceEvaluator
    from handyrl_tpu.environment import make_env, make_jax_env
    from handyrl_tpu.model import ModelWrapper

    env_args = {'env': env_name, **extra_env_args}
    env = make_env(env_args)
    env.reset()
    env_mod = make_jax_env(env_args)
    assert env_mod is not None, 'offline device eval needs a jax twin'
    example = env.observation(env.players()[0])

    ckpts = sorted(
        int(m.group(1)) for f in os.listdir(model_dir)
        if (m := re.match(r'^(\d+)\.ckpt$', f)))
    picks = [e for i, e in enumerate(ckpts) if i % every == 0]
    if ckpts and ckpts[-1] not in picks:
        picks.append(ckpts[-1])
    if '--skip-scored' in opts and os.path.exists(out_path):
        scored = set()
        with open(out_path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if row.get('opponent') == opponent and 'epoch' in row:
                    scored.add(row['epoch'])
        picks = [e for e in picks if e not in scored]
        print('skip-scored: %d epochs already in %s'
              % (len(scored), out_path), flush=True)
    print('evaluating %d checkpoints of %d (every %d) from %s'
          % (len(picks), len(ckpts), every, model_dir), flush=True)

    wrapper = ModelWrapper(env.net())
    args = {'eval': {'opponent': [opponent]}}
    # ONE evaluator reused across checkpoints: a fresh instance would
    # re-trace its rollout program per checkpoint. After each params swap,
    # a few chunks are discarded so games started under the previous
    # checkpoint don't contaminate the point.
    ev = None
    with open(out_path, 'a') as out:
        for epoch in picks:
            with open(os.path.join(model_dir, '%d.ckpt' % epoch), 'rb') as f:
                wrapper.load_params_bytes(f.read(), example)
            from handyrl_tpu.utils.fetch import put_tree
            wrapper.params = put_tree(wrapper.params)
            if ev is None:
                ev = DeviceEvaluator(env_mod, wrapper, args, n_envs=n_envs,
                                     chunk_steps=32, seed=1009,
                                     opponents=[opponent])
            else:
                # flush cross-checkpoint games: a full max-length episode
                # plus the one pipelined chunk must drain before counting
                max_steps = int(getattr(env_mod, 'MAX_STEPS', 256))
                for _ in range(max_steps // 32 + 2):
                    ev.step()
            results = []
            while len(results) < games:
                results.extend(ev.step())
            vals = [r['result'][r['args']['player'][0]] for r in results]
            mean = float(np.mean(vals))
            row = {'epoch': epoch, 'opponent': opponent,
                   'games': len(vals),
                   'win_rate': round((mean + 1) / 2, 4),
                   'mean': round(mean, 4)}
            out.write(json.dumps(row) + '\n')
            out.flush()
            print(row, flush=True)


if __name__ == '__main__':
    main()
