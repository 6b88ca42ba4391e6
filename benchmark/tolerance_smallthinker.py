#!/usr/bin/env python3
"""How the ``smallthinker`` checks' limits are founded: the checks' own
functions (``checks_smallthinker.forward_errors`` / ``rollout_errors`` /
``step_errors``) at the stated precision over several seeds, and with one
side degraded, the SEVEN negative controls that must fail: parameters rounded
to 8 bits (the program's side), and, on the reference's side, one layer left
out, the experts' sum left out, the window ignored on the window layers,
rotary phases put on the global layer, and this architecture's own two: the
router reading ``N_post(h)`` AFTER attention, and SiLU for ReLU in the
experts (the program is compared with a model that differs from it by that
part, which reads the same distance). A builder's chip run, never part of a
measured run:

    chiprun --timeout 3400 -- python3 benchmark/tolerance_smallthinker.py --seeds 8 [--controls 2] [--checks forward,rollout,step]

prints one JSON object: ``{check: {case: [readings a seed]}}``, and writes it
to ``chiprun_out/tolerance_smallthinker.json`` as it goes. ``--root`` names
another manifest root (the CPU rehearsal's, for the toy limits).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--seeds', type=int, default=8)
    parser.add_argument('--controls', type=int, default=2,
                        help='how many of the seeds also read the controls')
    parser.add_argument('--first-seed', type=int, default=4338200001)
    parser.add_argument('--checks', default='forward,rollout,step')
    parser.add_argument('--root', default=ROOT)
    opts = parser.parse_args(argv)

    from benchmark import run
    run.place_compile_cache()
    from benchmark import checks, checks_smallthinker as ct
    from benchmark.manifest import Manifest
    from benchmark.session import fold_seed, merged_args
    from benchmark.tolerance_trinity_mini import rounded_to_8_bits
    manifest = Manifest(os.path.abspath(opts.root))
    config = manifest.load_config('smallthinker')
    traffic = manifest.load_traffic('moe_selfplay_8k')
    wanted = opts.checks.split(',')
    out = {name: {} for name in wanted}

    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    path = os.path.join(ROOT, 'chiprun_out', 'tolerance_smallthinker.json')

    def note(check, case, stats, keys):
        out[check].setdefault(case, []).append(
            {k: stats[k] for k in keys if k in stats})
        print(check, case, out[check][case][-1], file=sys.stderr, flush=True)
        with open(path, 'w') as f:      # every reading: a cut run keeps its own
            json.dump(out, f, indent=1)

    int8 = rounded_to_8_bits()
    for n in range(opts.seeds):
        seed = fold_seed(opts.first_seed + n)
        train_args = merged_args(config, traffic, seed)['train_args']
        variables = checks.starting_variables(config, train_args)
        module = checks.build_module(config, train_args)
        controls = n < opts.controls
        if 'forward' in wanted:
            keys = tuple(name for name, _op in ct.FORWARD_LIMITS) + (
                'logit_rms',)
            note('forward', 'stated', ct.forward_errors(
                config, module, variables, seed), keys)
            if controls:
                note('forward', 'int8_parameters', ct.forward_errors(
                    config, module, variables, seed,
                    program_variables=int8(variables)), keys)
                for case, args in ct.CONTROLS.items():
                    note('forward', case, ct.forward_errors(
                        config, module, variables, seed, **args), keys)
        if 'rollout' in wanted:
            keys = ct.ROLLOUT_LIMITS + ('games', 'resets', 'wrapped_plies',
                                        'after_reset_plies')
            # the program's side once; the reference's controls read the
            # same records
            records = ct.rollout_records(config, module, variables, seed,
                                         train_args)
            note('rollout', 'stated', ct.rollout_compare(
                config, records, variables), keys)
            if controls:
                for case, args in ct.CONTROLS.items():
                    note('rollout', case, ct.rollout_compare(
                        config, records, variables, **args), keys)
                note('rollout', 'int8_parameters', ct.rollout_errors(
                    config, module, int8(variables), seed, train_args,
                    reference_variables=variables), keys)
            del records
        if 'step' in wanted:
            keys = ct.STEP_LIMITS + (
                'router_moved_max_abs', 'rows_held_share', 'rows_dropped',
                'worst_leaves', 'grad_err_rel_by_group',
                'change_err_rel_by_group', 'change_sign_flipped_share')
            note('step', 'stated', ct.step_errors(
                config, module, variables, seed, train_args), keys)
            if controls:
                for case, args in ct.CONTROLS.items():
                    note('step', case, ct.step_errors(
                        config, module, variables, seed, train_args,
                        **args), keys)
                # last, with the reference's weights on the host: the
                # rounded tree, the train state made of it and the step's
                # temporaries leave no room for a second float32 tree
                import jax
                host, rounded = jax.device_get(variables), int8(variables)
                del variables
                note('step', 'int8_parameters', ct.step_errors(
                    config, module, host, seed, train_args,
                    program_variables=rounded), keys)
                del host, rounded
        variables = None
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
