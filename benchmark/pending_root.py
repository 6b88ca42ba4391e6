#!/usr/bin/env python3
"""A manifest root whose ``BENCHMARK.json`` also names the per-layer metrics
that a configuration's file lists under ``pending_metrics``.

A metric is pending where its file (``metrics/<name>.json``) and its reader
are in the checkout but its entry cannot join ``BENCHMARK.json`` yet: a PR
that changes the program may only APPEND to ``per_layer``, and
``tests/benchmark/test_bench_evabyte.py`` pins the list's LAST thirteen
names by position, so an appended entry fails an accepted test and an
inserted one changes the accepted list (PERF.md section 7). Until a
``benchmark`` PR pins those names by name, the root built here is how the
pending metrics are read on the chip: the checkout's entries, then one entry
a pending metric, made from the metric's own file and listing the cells of
the configuration that names it. Everything else is a link to the checkout.

    python3 benchmark/pending_root.py <dest>
    python3 benchmark/run.py --root <dest> --workload <cell> --trace 1 ...
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = ('configs', 'traffic', 'metrics', 'hooks', 'readers', 'rehearsal',
        'checkpoints', 'peaks.json')
ENTRY_KEYS = ('name', 'unit', 'better', 'source', 'layer', 'moves')


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def pending_entries(root=ROOT):
    """The ``per_layer`` entries of every pending metric of ``root``."""
    raw = _read(root, 'BENCHMARK.json')
    entries = []
    for config in raw['configs']:
        cells = [cell['name'] for cell in raw['workloads']
                 if cell['config'] == config['name']]
        pending = _read(root, config['file']).get('pending_metrics', {})
        for name in pending.get('names', ()):
            spec = _read(root, 'benchmark', 'metrics', name + '.json')
            entries.append(dict({key: spec[key] for key in ENTRY_KEYS},
                                workloads=cells))
    return entries


def build(dest, root=ROOT):
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(os.path.join(dest, 'benchmark'))
    for name in DATA:
        os.symlink(os.path.join(root, 'benchmark', name),
                   os.path.join(dest, 'benchmark', name))
    raw = _read(root, 'BENCHMARK.json')
    raw['per_layer'] += pending_entries(root)
    with open(os.path.join(dest, 'BENCHMARK.json'), 'w') as f:
        json.dump(raw, f, indent=1)
    return dest


if __name__ == '__main__':
    print(build(os.path.abspath(sys.argv[1])))
