#!/usr/bin/env python3
"""How the ``evabyte`` checks' limits are founded: the checks' own functions
(``checks_evabyte.forward_errors`` / ``rollout_errors`` / ``step_errors``)
at the stated precision over several seeds, and with the program's side
degraded, the negative controls that must fail: parameters rounded to
bfloat16 and to 8 bits, one layer left out, and every remote summary left
out. A builder's chip run, never part of a measured run:

    chiprun --timeout 3000 -- python3 benchmark/tolerance_evabyte.py --seeds 4 [--checks forward,rollout,step]

prints one JSON object: ``{check: {case: [readings a seed]}}``, and writes it
to ``chiprun_out/tolerance_evabyte.json``. A control that leaves a layer or
the summaries out degrades the REFERENCE (the program is compared with a
model that differs from it by that part), which reads the same distance.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_SEEDS = 1     # how many of the seeds also read the controls
sys.path.insert(0, ROOT)


def rounded_to(dtype):
    import jax
    import jax.numpy as jnp

    def int8(x):
        scale = jnp.abs(x).max() / 127 + 1e-30
        return (jnp.round(x / scale) * scale).astype(x.dtype)

    def cast(x):
        return x.astype(dtype).astype(x.dtype)
    one = int8 if dtype == 'int8' else cast
    return jax.jit(lambda tree: jax.tree_util.tree_map(one, tree))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--seeds', type=int, default=3)
    parser.add_argument('--first-seed', type=int, default=2934100001)
    parser.add_argument('--checks', default='forward,rollout,step')
    parser.add_argument('--root', default=ROOT)
    opts = parser.parse_args(argv)

    from benchmark import run
    run.place_compile_cache()
    import jax.numpy as jnp
    from benchmark import checks, checks_evabyte as ce
    from benchmark.manifest import Manifest
    from benchmark.session import fold_seed, merged_args
    manifest = Manifest(os.path.abspath(opts.root))
    config = manifest.load_config('evabyte')
    traffic = manifest.load_traffic('selfplay_4k')
    wanted = opts.checks.split(',')
    out = {name: {} for name in wanted}

    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    path = os.path.join(ROOT, 'chiprun_out', 'tolerance_evabyte.json')

    def note(check, case, stats, keys):
        out[check].setdefault(case, []).append({k: stats[k] for k in keys})
        print(check, case, out[check][case][-1], file=sys.stderr, flush=True)
        with open(path, 'w') as f:      # every reading: a cut run keeps its own
            json.dump(out, f, indent=1)

    for n in range(opts.seeds):
        seed = fold_seed(opts.first_seed + n)
        train_args = merged_args(config, traffic, seed)['train_args']
        variables = checks.starting_variables(config, train_args)
        module = checks.build_module(config, train_args)
        degraded = {'bfloat16_parameters': rounded_to(jnp.bfloat16),
                    'int8_parameters': rounded_to('int8')}
        reference_controls = {'one_layer_left_out': {'skip_layer': 1},
                              'summaries_left_out': {'use_remote': False}}
        if 'forward' in wanted:
            keys = ('logits_rms_rel_to_logit_rms', 'value_rms', 'logit_rms')
            note('forward', 'stated', ce.forward_errors(
                config, module, variables, seed), keys)
            if n < CONTROL_SEEDS:
                for case, fn in degraded.items():
                    note('forward', case, ce.forward_errors(
                        config, module, variables, seed,
                        program_variables=fn(variables)), keys)
                for case, args in reference_controls.items():
                    note('forward', case, ce.forward_errors(
                        config, module, variables, seed, **args), keys)
        if 'rollout' in wanted:
            keys = ce.ROLLOUT_LIMITS + ('games', 'resets', 'remote_plies',
                                        'after_reset_plies')
            # the program's side once; the reference's controls read the
            # same records (a model that lacks a part is as far from the
            # program as a program that lacks it)
            records = ce.rollout_records(config, module, variables, seed,
                                         train_args)
            note('rollout', 'stated', ce.rollout_compare(
                config, records, variables), keys)
            if n < CONTROL_SEEDS:
                for case, args in reference_controls.items():
                    note('rollout', case, ce.rollout_compare(
                        config, records, variables, **args), keys)
                note('rollout', 'int8_parameters', ce.rollout_errors(
                    config, module, degraded['int8_parameters'](variables),
                    seed, train_args, reference_variables=variables), keys)
            del records
        if 'step' in wanted:
            keys = ce.STEP_LIMITS + ('worst_leaves', 'grad_err_rel_by_group',
                                     'change_err_rel_by_group',
                                     'change_sign_flipped_share')
            note('step', 'stated', ce.step_errors(
                config, module, variables, seed, train_args), keys)
            if n < CONTROL_SEEDS:
                for case, fn in degraded.items():
                    note('step', case, ce.step_errors(
                        config, module, variables, seed, train_args,
                        program_variables=fn(variables)), keys)
                for case, args in reference_controls.items():
                    note('step', case, ce.step_errors(
                        config, module, variables, seed, train_args,
                        **args), keys)
        del variables
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
