"""Geister league-eval throughput on the current backend.

Reruns the round-4 `geister-league-eval-device` measurement (ROADMAP S5):
the full GeisterNet evaluated against a
full-GeisterNet CHECKPOINT opponent, whole matches (up to the env's 200-ply
cap) played inside compiled chunks with the opponent's DRC hidden carried
through the rollout scan (`handyrl_tpu/device_generation.py`). The
dispatch count is the TPU-relevant number: each `DeviceEvaluator.step()`
is ONE device program dispatch and one blocking fetch, vs 100+
dispatches per match on a per-ply host evaluator —
reference counterpart: the eval child processes of
/root/reference/handyrl/evaluation.py run one net call per ply.

Run: python scripts/geister_league_eval.py [--budget-s 120] [--envs 16]
Appends one JSON row to benchmarks.jsonl.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def main():
    budget_s, n_envs, chunk_steps = 120.0, 16, 32
    argv = iter(sys.argv[1:])
    for a in argv:
        key, _, val = a.partition('=')
        if key in ('--budget-s', '--envs', '--chunk') and not val:
            try:
                val = next(argv)
            except StopIteration:
                raise SystemExit('%s needs a value' % key)
        if key == '--budget-s':
            budget_s = float(val)
            if budget_s <= 0:
                raise SystemExit('--budget-s must be > 0')
        elif key == '--envs':
            n_envs = int(val)
        elif key == '--chunk':
            chunk_steps = int(val)
        else:
            raise SystemExit('unknown argument %r' % a)

    import handyrl_tpu
    handyrl_tpu.setup_compile_cache()
    import jax

    from handyrl_tpu.device_generation import DeviceEvaluator
    from handyrl_tpu.envs import jax_geister
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.models.geister import GeisterNet

    obs = jax_geister.observe(jax_geister.init_state(1))
    w = ModelWrapper(GeisterNet())
    w.params = w.module.init(jax.random.PRNGKey(0), obs, None)
    opp = ModelWrapper(GeisterNet())
    opp.params = opp.module.init(jax.random.PRNGKey(1), obs, None)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, 'league_opp.ckpt')
        with open(path, 'wb') as f:
            f.write(opp.params_bytes())
        ev = DeviceEvaluator(jax_geister, w, {}, n_envs=n_envs,
                             chunk_steps=chunk_steps, opponents=[path])
        assert ev.recurrent, 'GeisterNet league opponent must be recurrent'
        t0 = time.time()
        ev.step()                       # compile + first chunk(s)
        compile_s = time.time() - t0

        games = 0
        d0 = ev.dispatches              # the evaluator's own authoritative
        t0 = time.time()                # count (step() is pipelined)
        # run to the budget, but never record a zero-game row: matches last
        # up to 200 plies, so a too-small budget could elapse before the
        # first game finishes (hard cap 4x budget bounds that extension)
        while (time.time() - t0 < budget_s or games == 0) \
                and time.time() - t0 < 4 * budget_s:
            games += len(ev.step())
        dispatches = ev.dispatches - d0
        wall = max(time.time() - t0, 1e-9)
        if games == 0:
            raise SystemExit('no games finished within %.0fs (4x budget) — '
                             'raise --budget-s' % (4 * budget_s))

    row = {
        'row': 'geister-league-eval-device',
        'backend': jax.default_backend(),
        'opponent': 'recurrent DRC checkpoint (full GeisterNet)',
        'games': games,
        'games_per_sec': round(games / wall, 2),
        'dispatches': dispatches,
        'n_envs': n_envs, 'chunk_steps': chunk_steps,
        'compile_s': round(compile_s, 1),
        'note': 'whole 200-ply-max matches on device, one dispatch per '
                '%d-ply chunk; opponent hidden carried in the compiled '
                'rollout (no host fallback)' % chunk_steps,
        'time': time.strftime('%Y-%m-%d %H:%M:%S'),
    }
    print(json.dumps(row), flush=True)
    out = os.path.join(os.path.dirname(__file__), '..', 'benchmarks.jsonl')
    with open(os.path.abspath(out), 'a') as f:
        f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
