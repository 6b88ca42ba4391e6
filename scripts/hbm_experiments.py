"""Close the gap to the HBM roofline floor (round-3 review, item 4).

The round-3 roofline: the headline update step (GeeseNet B=128 T=16,
bf16 activations) moves 4.26 GB HBM/step, a 5.2 ms floor at the v5e's
819 GB/s, but measures 15.24 ms — MBU 34%. This script produces the two
artifacts the verdict asked for, ON the accelerator:

1. a per-op HBM-traffic table: the compiled executable's optimized HLO,
   each top-level instruction scored by the buffer bytes it touches
   (operands + outputs), sorted — names which convs/fusions carry the
   4.26 GB and whether XLA materializes something avoidable;
2. step-time variants: fp32 / bf16-activations / bf16-activations +
   bf16 params+Adam-moments (halves parameter+optimizer traffic; the
   quality impact is NOT evaluated here — this is a bandwidth
   experiment, not a training recommendation).

Run (needs the TPU): python scripts/hbm_experiments.py [--steps 30]
Appends rows to benchmarks.jsonl and prints the table.

--B/--T shrink the geometry for an off-chip plumbing dry-run
(JAX_PLATFORMS=cpu ... --B 8 --T 4 --steps 2); rows from non-default
geometry are tagged 'dryrun' and are NOT roofline evidence.
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

_DTYPE_BYTES = {'f32': 4, 'bf16': 2, 'f16': 2, 's32': 4, 'u32': 4,
                's8': 1, 'u8': 1, 'pred': 1, 's64': 8, 'u64': 8, 'f64': 8,
                's16': 2, 'u16': 2}


def _shape_bytes(shape_str: str) -> int:
    """'bf16[128,32,7,11]{3,2,1,0}' -> element bytes x product(dims).
    Tuples are handled by summing their parts."""
    total = 0
    for m in re.finditer(r'(\w+)\[([\d,]*)\]', shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(','):
                n *= int(d)
        total += _DTYPE_BYTES[dt] * n
    return total


def per_op_table(compiled, top=25):
    """Score each top-level HLO instruction in the ENTRY computation by
    the bytes of its output + operand shapes (the traffic it would cost
    if every buffer hit HBM once). Fusions count their result + inputs —
    exactly the memory XLA cannot elide; their internals are free."""
    txt = compiled.as_text()
    entry = []
    in_entry = False
    for line in txt.splitlines():
        if line.startswith('ENTRY'):
            in_entry = True
            continue
        if in_entry:
            if line.startswith('}'):
                break
            entry.append(line.strip())
    rows = []
    for line in entry:
        m = re.match(r'(%?[\w.\-]+)\s*=\s*([^ ]+)\s+(\w+)', line)
        if not m:
            continue
        name, shape, op = m.groups()
        out_b = _shape_bytes(shape)
        # operand shapes appear inline in the args list
        args = line[line.find('('):]
        arg_b = _shape_bytes(args)
        rows.append({'op': op, 'name': name.lstrip('%'),
                     'bytes': out_b + arg_b, 'out_bytes': out_b})
    rows.sort(key=lambda r: -r['bytes'])
    return rows[:top], sum(r['bytes'] for r in rows)


# the roofline geometry (bench.py headline); rows at any other geometry
# are plumbing dry-runs, tagged so they can never read as roofline evidence
HEADLINE_B, HEADLINE_T = 128, 16


def variant(name, dtype=None, cast_state=False, torus_impl=None,
            B=HEADLINE_B, T=HEADLINE_T, steps=30):
    import jax
    import jax.numpy as jnp
    from bench import headline_setup, time_compiled_step
    from handyrl_tpu.ops.train_step import build_update_step

    tagged = (name if (B, T) == (HEADLINE_B, HEADLINE_T)
              else '%s-dryrun-B%d-T%d' % (name, B, T))
    module, cfg, batch, state = headline_setup(
        B, T, dtype=jnp.bfloat16 if dtype == 'bf16' else None,
        torus_impl=torus_impl)
    parity = None
    if torus_impl is not None:
        # numerics probe of the REAL lowering (interpret mode and Mosaic
        # are different executors): forward the same params/obs through
        # the wrap-pad twin and this impl before timing anything. The
        # criterion is RELATIVE to the reference logit scale — a fixed
        # 0.05 absolute band on bf16 logits silently loosens as the scale
        # grows and a bad lowering could pass it while being wrong.
        obs = batch['observation'][:64, 0, 0]
        ref = module.clone(torus_impl='pad').apply(state.params, obs, None)
        got = module.apply(state.params, obs, None)
        err = scale = 0.0
        for k in ('policy', 'value'):
            rk = jnp.asarray(ref[k], jnp.float32)
            gk = jnp.asarray(got[k], jnp.float32)
            err = max(err, float(jnp.abs(rk - gk).max()))
            scale = max(scale, float(jnp.abs(rk).max()))
        rel = err / max(scale, 1e-6)
        parity = {'max_abs_err_vs_pad': err, 'ref_scale': scale,
                  'rel_err': rel, 'ok': bool(rel < 0.05)}
        print('parity[%s]: %s' % (tagged, parity), flush=True)
        if not parity['ok']:
            # a lowering that fails parity must never produce a
            # fast-but-wrong headline candidate: skip the timed run and
            # emit an explicitly invalid row instead
            return {'row': 'hbm-experiment', 'variant': tagged,
                    'invalid': True, 'parity': parity,
                    'time': time.strftime('%Y-%m-%d %H:%M:%S')}
    if cast_state:
        # params AND Adam moments in bf16: halves the read+write traffic
        # of every weight and optimizer buffer
        state = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if hasattr(x, 'dtype') and x.dtype == jnp.float32 else x, state)
    step = build_update_step(module, cfg, donate=False)
    lr = jnp.asarray(1e-5, jnp.float32)
    sec, flops, hbm = time_compiled_step(step, state, batch, lr, steps)
    row = {'row': 'hbm-experiment', 'variant': tagged,
           'step_ms': round(sec * 1e3, 2),
           'traj_per_sec': round(B / sec, 1),
           'flops_per_step': flops, 'hbm_bytes_per_step': hbm,
           'time': time.strftime('%Y-%m-%d %H:%M:%S')}
    if parity is not None:
        row['parity'] = parity
    # per-op table for the bf16-activation variant (the headline config)
    try:
        compiled = step.lower(state, batch, lr).compile()
        table, total = per_op_table(compiled)
        row['top_ops'] = [{k: r[k] for k in ('op', 'bytes')}
                          for r in table[:8]]
        row['sum_table_bytes'] = total
        if name in ('bf16-act', 'bf16-act+halo', 'bf16-act+pallas'):   # base name: the print path runs in dry-runs too
            print('--- per-op traffic, %s (top 25) ---' % tagged)
            for r in table:
                print('%12d  %-18s %s' % (r['bytes'], r['op'], r['name']))
    except Exception as exc:  # noqa: BLE001
        row['table_error'] = str(exc)[:120]
    return row


def main():
    steps, B, T = 30, 128, 16
    argv = iter(sys.argv[1:])
    for a in argv:
        key, _, val = a.partition('=')
        if key == '--steps':
            steps = int(val or next(argv))
        elif key == '--B':
            B = int(val or next(argv))
        elif key == '--T':
            T = int(val or next(argv))
        else:
            raise SystemExit('unknown argument %r' % a)
    out = os.path.join(os.path.dirname(__file__), '..', 'benchmarks.jsonl')
    for name, kw in (('fp32', {}),
                     ('bf16-act', {'dtype': 'bf16'}),
                     ('bf16-act+state', {'dtype': 'bf16',
                                         'cast_state': True}),
                     # halo torus conv: same function as bf16-act without
                     # the wrap-pad HBM copies (models/blocks.py) — the
                     # round-5 per-op table's named target
                     ('bf16-act+halo', {'dtype': 'bf16',
                                        'torus_impl': 'halo'})):
        row = variant(name, steps=steps, B=B, T=T, **kw)
        print(json.dumps(row), flush=True)
        with open(os.path.abspath(out), 'a') as f:
            f.write(json.dumps(row) + '\n')


if __name__ == '__main__':
    main()
