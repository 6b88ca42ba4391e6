#!/usr/bin/env python3
"""CPU rehearsal of a cell at a tiny size: the same harness, hooks, window
logic and readers as ``run.py``. The tiny cell is DATA: a manifest root of
its own (``.bench_runs/rehearsal/``) holding the cells' configuration and
traffic files with ``rehearsal/tiny.json`` laid over their sizes, and a
``peaks.json`` with a row for the CPU. ``run_cell`` has one path; this
script swaps the one function that claims the chips. It proves paths,
arguments and control flow. Nothing it prints is a measurement: the line is
marked ``cpu_rehearsal`` and carries counts only, never a number under a
device metric's name.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name> [--seconds 3] [--trace 0|1]
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


def build_root(real, dest):
    """A manifest root at ``dest``: the real ``BENCHMARK.json``, metrics and
    hooks, every configuration and traffic file at the tiny sizes."""
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal', 'tiny.json')) as f:
        tiny = json.load(f)
    shutil.rmtree(dest, ignore_errors=True)
    bench = os.path.join(dest, 'benchmark')
    os.makedirs(bench)
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), dest)
    for folder in ('metrics', 'hooks'):
        os.symlink(os.path.join(ROOT, 'benchmark', folder),
                   os.path.join(bench, folder))
    for name, entry in real.configs.items():
        config = real.load_config(name)
        config.update(tiny['config'])
        _write(os.path.join(dest, entry['file']), config)
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
    opts = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import session
    from benchmark.manifest import Manifest
    dest = os.path.join(ROOT, '.bench_runs', 'rehearsal', opts.workload)
    build_root(Manifest(ROOT), dest)

    def any_device(_cell):
        import jax
        return jax.devices()
    session.claim_devices = any_device
    result = session.run_cell(Manifest(dest), opts.workload, opts.seed,
                              opts.seconds, bool(opts.trace), T_PROCESS_START)
    print(json.dumps({
        'cpu_rehearsal': True, 'workload': opts.workload,
        'platform': result['device']['platform'],
        'checks': result['checks'], 'counts': result['counts'],
        'attempted': result['attempted'], 'failed': result['failed'],
        'metrics_read': sorted(result['metrics']),
        'reference': result['reference'], 'compile': result['compile'],
        'spans': result['spans'],
    }), flush=True)
    return 0 if all(result['checks'].values()) else 1


if __name__ == '__main__':
    sys.exit(main())
