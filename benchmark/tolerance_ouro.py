#!/usr/bin/env python3
"""How the ``ouro`` checks' limits are founded: the checks' own functions
(``checks_ouro.forward_errors`` / ``rollout_errors`` / ``step_errors``) at
the stated precision over several seeds, and with one side degraded, the
EIGHT negative controls that must fail: parameters rounded to 8 bits (the
program's side), and, on the reference's side (the program is compared with
a model that differs from it by that part, which reads the same distance), a
layer left out of every pass; the phases left out; a pass left out (three of
four); every pass but the first reading the previous pass's K and V (one
cache for all passes: forward and rollout); ``N_out`` between the passes left
out; and, in the step check alone, the loss taken from the last pass alone
and the first three passes under ``stop_gradient`` (one use of each weight
instead of four). A builder's chip run, never part of a measured run:

    chiprun --timeout 3400 -- python3 benchmark/tolerance_ouro.py --seeds 8 [--controls 2] [--checks forward,rollout,step]

prints one JSON object: ``{check: {case: [readings a seed]}}``, and writes it
to ``chiprun_out/tolerance_ouro.json`` as it goes. ``--root`` names another
manifest root (the CPU rehearsal's, for the toy limits).
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
    parser.add_argument('--first-seed', type=int, default=4646200001)
    parser.add_argument('--checks', default='forward,rollout,step')
    parser.add_argument('--root', default=ROOT)
    opts = parser.parse_args(argv)

    from benchmark import run
    run.place_compile_cache()
    from benchmark import checks, checks_ouro as co
    from benchmark.manifest import Manifest
    from benchmark.session import fold_seed, merged_args
    from benchmark.tolerance_trinity_mini import rounded_to_8_bits
    manifest = Manifest(os.path.abspath(opts.root))
    config = manifest.load_config('ouro')
    traffic = manifest.load_traffic('loop_selfplay_4k')
    wanted = opts.checks.split(',')
    out = {name: {} for name in wanted}

    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    path = os.path.join(ROOT, 'chiprun_out', 'tolerance_ouro.json')

    def note(check, case, stats, keys):
        out[check].setdefault(case, []).append(
            {k: stats[k] for k in keys if k in stats})
        print(check, case, out[check][case][-1], file=sys.stderr, flush=True)
        with open(path, 'w') as f:      # every reading: a cut run keeps its own
            json.dump(out, f, indent=1)

    def cases(check):
        """The reference's controls that ``check`` can read."""
        skip = co.FORWARD_ONLY if check == 'step' else co.STEP_ONLY
        return {case: args for case, args in co.CONTROLS.items()
                if case not in skip}

    int8 = rounded_to_8_bits()
    for n in range(opts.seeds):
        seed = fold_seed(opts.first_seed + n)
        train_args = merged_args(config, traffic, seed)['train_args']
        variables = checks.starting_variables(config, train_args)
        module = checks.build_module(config, train_args)
        controls = n < opts.controls
        if 'forward' in wanted:
            keys = tuple(name for name, _op in co.FORWARD_LIMITS) + (
                'logits_rms_rel_by_pass', 'logit_rms')
            note('forward', 'stated', co.forward_errors(
                config, module, variables, seed), keys)
            if controls:
                note('forward', 'int8_parameters', co.forward_errors(
                    config, module, variables, seed,
                    program_variables=int8(variables)), keys)
                for case, args in cases('forward').items():
                    note('forward', case, co.forward_errors(
                        config, module, variables, seed, **args), keys)
        if 'rollout' in wanted:
            keys = co.ROLLOUT_LIMITS + ('games', 'resets', 'late_plies',
                                        'after_reset_plies')
            # the program's side once; the reference's controls read the
            # same records
            records = co.rollout_records(config, module, variables, seed,
                                         train_args)
            note('rollout', 'stated', co.rollout_compare(
                config, records, variables), keys)
            if controls:
                for case, args in cases('rollout').items():
                    note('rollout', case, co.rollout_compare(
                        config, records, variables, **args), keys)
                note('rollout', 'int8_parameters', co.rollout_errors(
                    config, module, int8(variables), seed, train_args,
                    reference_variables=variables), keys)
            del records
        if 'step' in wanted:
            keys = co.STEP_LIMITS + (
                'exit_mass', 'worst_leaves', 'grad_err_rel_by_group',
                'change_err_rel_by_group', 'change_sign_flipped_share')
            note('step', 'stated', co.step_errors(
                config, module, variables, seed, train_args), keys)
            if controls:
                for case, args in cases('step').items():
                    note('step', case, co.step_errors(
                        config, module, variables, seed, train_args,
                        **args), keys)
                note('step', 'int8_parameters', co.step_errors(
                    config, module, variables, seed, train_args,
                    program_variables=int8(variables)), keys)
        variables = None
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
