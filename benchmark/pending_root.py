#!/usr/bin/env python3
"""RETIRED by PR 42, and kept for one reason: ``docs/observability.md`` still
names this path, ``tests/test_repo_references.py`` holds that document to
files the tree has, and a ``benchmark`` PR may edit neither. Delete this file
in the first ``benchmark`` PR after that paragraph of the document is gone
(PERF.md section 7, "Left by PR 42").

Nothing is pending any more: ``per_layer`` is pinned by name and takes
appended entries, the entries that waited here (PR 38's and PR 40's) are in
``BENCHMARK.json``, no configuration's file has a ``pending_metrics`` key, and
``tests/benchmark/contracts.py`` refuses a metric file without an entry. What
the code below still does, unchanged from PR 38: build a manifest root whose
``BENCHMARK.json`` is the checkout's plus one entry for each name a
configuration's file lists under ``pending_metrics`` (today: none, so the
root's list is the checkout's), everything else a link to the checkout.

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
