#!/usr/bin/env python3
"""The benchmark's one command: run one cell once, print one JSON line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the cell's TPU chips there:
without them it exits non-zero and prints no result. The last line of
standard output is the result object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``); see
``benchmark/README.md``.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache():
    """One fixed directory inside the checkout unless the operator placed
    the cache; every compile is kept, however short (the program's ~125
    small programs cost ~10 s of every start otherwise; PERF.md section 5)."""
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(ROOT, '.jax_cache'))
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS', '0')
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES', '-1')


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--seconds', type=float, default=None)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    opts = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    place_compile_cache()
    from benchmark.manifest import Manifest, ManifestError
    from benchmark.session import RunFailed, run_cell
    try:
        manifest = Manifest(ROOT)
        seconds = (opts.seconds if opts.seconds is not None
                   else manifest.run_seconds)
        result = run_cell(manifest, opts.workload, opts.seed, seconds,
                          bool(opts.trace), T_PROCESS_START)
    except (ManifestError, RunFailed) as exc:
        print('benchmark: %s' % exc, file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
