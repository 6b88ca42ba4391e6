#!/usr/bin/env python3
"""CPU rehearsal of a cell at a tiny size: the same harness, hooks, window
logic and readers as ``run.py``. The tiny cell is DATA: a manifest root of
its own (``.bench_runs/rehearsal/<workload>/``) holding every configuration's
file and the traffic files with a rehearsal overlay laid over their sizes,
and a ``peaks.json`` with a row for the CPU. The overlay is the rehearsed
cell's configuration's: ``rehearsal/<configuration>.json`` where the root has
that file, ``rehearsal/tiny.json`` otherwise, so a configuration at published
widths rehearses at a small width of the same code. An overlay's blocks:

    model, env_args   keys replaced in the configuration's blocks of that name
    config            top-level keys of the configuration's file (the sizes
                      of its own checks, such as ``reference_plies``)
    train_args        keys replaced in the traffic's ``train_args``, and in
                      the configuration's own where it sets the same key
    cap               upper limits on keys of the traffic's ``train_args``
    window            keys replaced in the traffic's ``window``
    peaks             rows added to ``peaks.json`` (the CPU's)

``run_cell`` has one path; this script swaps the one function that claims
the chips. It proves paths, arguments and control flow. Nothing it prints is
a measurement: the line is marked ``cpu_rehearsal`` and carries counts only,
never a number under a device metric's name.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name> [--seconds 3] [--trace 0|1] [--root <dir>]

``--root`` is the manifest root to rehearse (a directory with a
``BENCHMARK.json`` and the ``benchmark/`` data files it names); the checkout
unless given. tests/benchmark rehearses a fixture configuration from a root
of its own that way.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        json.dump(obj, f, indent=1)


def overlay_of(real, config):
    """``config``'s rehearsal sizes: its own file, else ``tiny.json``."""
    folder = os.path.join(real.root, 'benchmark', 'rehearsal')
    own = os.path.join(folder, config + '.json')
    with open(own if os.path.exists(own)
              else os.path.join(folder, 'tiny.json')) as f:
        return json.load(f)


def build_root(real, dest, workload):
    """A manifest root at ``dest`` to rehearse ``workload`` in: ``real``'s
    ``BENCHMARK.json``, metrics, hooks and readers; every configuration's
    file under its own overlay; every traffic file under the overlay of
    ``workload``'s configuration (a mix is shared by configurations; the
    root is one workload's)."""
    shutil.rmtree(dest, ignore_errors=True)
    bench = os.path.join(dest, 'benchmark')
    os.makedirs(bench)
    shutil.copy(os.path.join(real.root, 'BENCHMARK.json'), dest)
    for folder in ('metrics', 'hooks', 'readers'):
        os.symlink(os.path.realpath(os.path.join(real.root, 'benchmark',
                                                 folder)),
                   os.path.join(bench, folder))
    for name, entry in real.configs.items():
        config, tiny = real.load_config(name), overlay_of(real, name)
        config.update(tiny.get('config', {}))
        for block in ('model', 'env_args'):
            config[block].update(tiny.get(block, {}))
        config['train_args'].update(
            (key, value) for key, value in tiny['train_args'].items()
            if key in config['train_args'])
        _write(os.path.join(dest, entry['file']), config)
    tiny = overlay_of(real, real.cell(workload)['config'])
    for name in {cell['traffic'] for cell in real.cells.values()}:
        traffic = real.load_traffic(name)
        train_args = traffic['train_args']
        train_args.update(tiny['train_args'])
        for key, cap in tiny['cap'].items():
            train_args[key] = min(train_args[key], cap)
        traffic['window'].update(tiny['window'])
        _write(os.path.join(bench, 'traffic', name + '.json'), traffic)
    _write(os.path.join(bench, 'peaks.json'),
           dict(real.load_peaks(), **tiny['peaks']))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--seconds', type=float, default=3.0)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--root', default=ROOT)
    opts = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import session
    from benchmark.manifest import Manifest
    dest = os.path.join(ROOT, '.bench_runs', 'rehearsal', opts.workload)
    build_root(Manifest(os.path.abspath(opts.root)), dest, opts.workload)

    def any_device(_cell):
        import jax
        return jax.devices()
    session.claim_devices = any_device
    result = session.run_cell(Manifest(dest), opts.workload, opts.seed,
                              opts.seconds, bool(opts.trace), T_PROCESS_START)
    print(json.dumps({
        'cpu_rehearsal': True, 'workload': opts.workload,
        'platform': result['device']['platform'],
        'correct': result['correct'],
        'checks': result['checks'], 'counts': result['counts'],
        'attempted': result['attempted'], 'failed': result['failed'],
        'metrics_read': sorted(result['metrics']),
        'reference': result['reference'], 'flops': result['flops'],
        'compile': result['compile'],
        'spans': result['spans'],
    }), flush=True)
    return 0 if all(result['checks'].values()) else 1


if __name__ == '__main__':
    sys.exit(main())
