#!/usr/bin/env python3
"""How a configuration's trained checkpoint (``checkpoints/<config>.ckpt``)
is made: the program's own ``main.py --train`` on the chip, at the
``sgd_heavy`` mix's geometry, for ``--seconds``; the file is the program's
``models/latest.ckpt``. Not part of a measured run. A later PR whose change
to a net's parameter tree makes a checkpoint unloadable remakes it with this
and says so.

    python3 benchmark/make_checkpoint.py --config geese --seconds 480 \\
        --lr-scale 3 --out chiprun_out/checkpoints [--init <file.ckpt>]

``--lr-scale`` multiplies the learner's base learning rate (3e-8 x the data
count of a batch, i.e. ~6e-5 at 128 windows x 16 plies; the reference runs
the same rule at batches 18 times larger, ~1e-3): the checkpoint is data,
and the minutes on the chip are few. Every ``--every`` seconds the newest
checkpoint is copied aside, and at the end ``curve.json`` lists, epoch by
epoch, SGD steps, episodes, plies per episode and the win rate against the
random opponent, from the program's own ``metrics.jsonl``.
"""

import argparse
import contextlib
import json
import os
import runpy
import shutil
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def curve(metrics_path, plies_per_dispatch):
    """Epoch by epoch from the program's records: how long games were."""
    rows, last = [], None
    with open(metrics_path) as f:
        for line in f:
            rec = json.loads(line)
            if 'dispatches_gen' not in rec:
                continue
            if last and rec['episodes'] > last['episodes']:
                rows.append({
                    'epoch': rec['epoch'], 'steps': rec['steps'],
                    'episodes': rec['episodes'],
                    'plies_per_episode':
                        (rec['dispatches_gen'] - last['dispatches_gen'])
                        * plies_per_dispatch
                        / (rec['episodes'] - last['episodes']),
                    'win_rate_vs_random': rec.get('win_rate'),
                    'entropy': rec.get('entropy')})
            last = rec
    return rows


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--config', required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--lr-scale', type=float, default=1.0)
    parser.add_argument('--every', type=float, default=60.0)
    parser.add_argument('--seed', type=int, default=1)
    parser.add_argument('--init', default='')
    parser.add_argument('--out', required=True)
    opts = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(ROOT, '.jax_cache'))
    from benchmark import session
    from benchmark.manifest import Manifest
    manifest = Manifest(ROOT)
    config = manifest.load_config(opts.config)
    traffic = manifest.load_traffic('sgd_heavy')
    session.claim_devices({'name': 'make_checkpoint', 'chips': 1})
    args = session.merged_args(config, traffic, opts.seed)
    args['train_args'].update(
        init_params=os.path.abspath(opts.init) if opts.init else '',
        update_episodes=400, telemetry={'retrace': 'warn'})
    out = os.path.abspath(os.path.join(opts.out, opts.config))
    run_dir = os.path.join(ROOT, '.bench_runs', 'make_checkpoint',
                           opts.config)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(run_dir, 'config.yaml'), 'w') as f:
        json.dump(args, f, indent=1)

    import handyrl_tpu.train as train
    plain_init = train.Trainer.__init__

    def scaled_init(self, *a, **k):
        plain_init(self, *a, **k)
        self.default_lr *= opts.lr_scale
    train.Trainer.__init__ = scaled_init

    latest = os.path.join(run_dir, 'models', 'latest.ckpt')
    t0 = time.perf_counter()

    def keep_copies():
        while True:
            time.sleep(opts.every)
            elapsed = time.perf_counter() - t0
            if os.path.exists(latest):
                shutil.copy(latest, os.path.join(out, 't%04d.ckpt' % elapsed))
            if elapsed >= opts.seconds:
                os.kill(os.getpid(), signal.SIGTERM)
                return
    threading.Thread(target=keep_copies, daemon=True).start()

    os.chdir(run_dir)
    sys.argv = ['main.py', '--train']
    with open('train.log', 'w') as log, contextlib.redirect_stdout(log):
        try:
            runpy.run_path(os.path.join(ROOT, 'main.py'), run_name='__main__')
        except SystemExit as exc:
            print('exit', exc.code)
    shutil.copy(latest, os.path.join(out, opts.config + '.ckpt'))
    shutil.copy('train.log', out)
    shutil.copy('metrics.jsonl', out)
    train_args = args['train_args']
    rows = curve('metrics.jsonl', train_args['device_chunk_steps']
                 * train_args['generation_envs'])
    with open(os.path.join(out, 'curve.json'), 'w') as f:
        json.dump({'config': opts.config, 'seconds': opts.seconds,
                   'lr_scale': opts.lr_scale, 'seed': opts.seed,
                   'init': opts.init, 'train_args': train_args,
                   'epochs': rows}, f, indent=1)
    for row in rows[-5:]:
        print(json.dumps(row), file=sys.stderr)
    return 0


if __name__ == '__main__':
    sys.exit(main())
