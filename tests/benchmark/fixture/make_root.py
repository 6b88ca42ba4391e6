#!/usr/bin/env python3
"""A manifest root of the fixture's own, made the way a later PR adds a
cell: entries APPENDED to the checkout's ``BENCHMARK.json`` and NEW files
beside links to every file the checkout's ``benchmark/`` has. What it adds:
the configuration ``seeded_toy`` (seeded weights, a third check, a FLOP count
of its own), the traffic mix ``fixture_mix``, the cell of the two, the hook
``fixture_probe``, the reader ``fixture_count``, the per-layer metric
``fixture_probe_calls`` with ``workloads`` of that cell alone, and the
rehearsal overlay ``rehearsal/seeded_toy.json``. No shipped entry, list or
file is changed: tests/benchmark holds every contract on this root too.

``grow`` puts one more metric file and entry behind that, for a cell the
checkout already HAS: the root a checkout becomes once later PRs have added
one of each kind. ``conftest.py`` hands it to every test that takes a
manifest (``grown``, and ``either_root`` beside the checkout), so a pin that
holds only while nothing stands behind it fails in the PR that writes it.
This fixture's own tests (``test_bench_fixture.py``) are the template for a
configuration's test file.

    python3 tests/benchmark/fixture/make_root.py <dest> [--third-check-fails]

then ``benchmark/rehearse.py --root <dest> --workload seeded_toy.fixture_mix``
here, or ``benchmark/run.py --root <dest> ...`` on the chip (a path check,
never a number)."""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
CONFIG, TRAFFIC = 'seeded_toy', 'fixture_mix'
CELL = CONFIG + '.' + TRAFFIC
HOOK, READER, METRIC = 'fixture_probe', 'fixture_count', 'fixture_probe_calls'
# ``grow``'s one more entry, for a cell the checkout HAS, as a program PR that
# adds a counter to a shipped cell would append it
SECOND, ITS_CELL = 'toy_boundaries_in_a_shipped_cell', 'evabyte.selfplay_4k'
# what the overlay sets that tiny.json does not, each to a value of its own
OVERLAY = {'model': {'flops_per_window': 77},
           'config': {'reference_envs': 3, 'reference_plies': 2},
           'train_args': {'forward_steps': 5, 'burn_in_steps': 0}}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _write(obj, *parts):
    with open(os.path.join(*parts), 'w') as f:
        json.dump(obj, f, indent=1)


def _link_shipped(dest):
    """Every file of the checkout's ``benchmark/`` data folders as a link,
    and the folders that hold no data file of a cell whole."""
    bench = os.path.join(ROOT, 'benchmark')
    for folder in ('configs', 'traffic', 'metrics', 'hooks', 'readers',
                   'rehearsal'):
        os.makedirs(os.path.join(dest, 'benchmark', folder))
        for name in os.listdir(os.path.join(bench, folder)):
            if name != '__pycache__':
                os.symlink(os.path.join(bench, folder, name),
                           os.path.join(dest, 'benchmark', folder, name))
    for name in ('checkpoints', 'peaks.json'):
        os.symlink(os.path.join(bench, name),
                   os.path.join(dest, 'benchmark', name))


def build(dest, third_check_ok=True):
    shutil.rmtree(dest, ignore_errors=True)
    _link_shipped(dest)
    bench = os.path.join(dest, 'benchmark')
    raw = _read(ROOT, 'BENCHMARK.json')

    config = _read(HERE, 'seeded_toy.json')
    config['third_check_ok'] = third_check_ok
    file = 'benchmark/configs/%s.json' % CONFIG
    _write(config, dest, file)
    raw['configs'].append({'name': CONFIG, 'source': config['source'],
                           'file': file, 'reduced': [],
                           'why': 'fixture: seeded weights, own FLOP count, '
                                  'a third check'})

    traffic = _read(ROOT, 'benchmark', 'traffic', 'rollout_heavy.json')
    traffic.update(name=TRAFFIC, who='nobody: the fixture\'s mix, '
                   'rollout_heavy with one more hook in its window')
    traffic['window']['spans'] = traffic['window']['spans'] + [HOOK]
    _write(traffic, bench, 'traffic', TRAFFIC + '.json')
    raw['workloads'].append({'name': CELL, 'config': CONFIG,
                             'traffic': TRAFFIC, 'chips': 1,
                             'why': 'fixture cell'})

    _write({'span': HOOK,
            'target': 'handyrl_tpu.train:Learner._hand_over_checkpoint',
            'layer': 'param publish, checkpoint',
            'what': 'fixture: one call an epoch boundary'},
           bench, 'hooks', HOOK + '.json')
    shutil.copy(os.path.join(HERE, READER + '.py'),
                os.path.join(bench, 'readers'))
    entry = {'name': METRIC, 'unit': 'calls', 'better': 'higher',
             'source': 'program_counter',
             'layer': 'param publish, checkpoint',
             'moves': 'train_windows_per_s', 'workloads': [CELL]}
    _write(dict(entry, reader=READER, args={'span': HOOK},
                what='fixture: the hand-overs that ended in the window'),
           bench, 'metrics', METRIC + '.json')
    raw['per_layer'].append(entry)

    overlay = _read(ROOT, 'benchmark', 'rehearsal', 'tiny.json')
    for block, keys in OVERLAY.items():
        overlay.setdefault(block, {}).update(keys)
    _write(overlay, bench, 'rehearsal', CONFIG + '.json')

    _write(raw, dest, 'BENCHMARK.json')
    return dest


def append_entry(root, entry):
    raw = _read(root, 'BENCHMARK.json')
    raw['per_layer'].append(entry)
    _write(raw, root, 'BENCHMARK.json')


def grow(dest):
    """``build``'s root (a configuration, its cell, a traffic file, a hook,
    a reader, a metric file and its entry, all appended) and, behind that,
    one more metric file and entry that lists a shipped cell alone."""
    root = build(dest)
    entry = {'name': SECOND, 'unit': 'calls', 'better': 'higher',
             'source': 'program_counter',
             'layer': 'param publish, checkpoint',
             'moves': 'train_windows_per_s', 'workloads': [ITS_CELL]}
    _write(dict(entry, reader=READER, args={'span': 'epoch_boundary'},
                what='guard: the boundaries that ended in the window'),
           root, 'benchmark', 'metrics', SECOND + '.json')
    append_entry(root, entry)
    return root


if __name__ == '__main__':
    print(build(os.path.abspath(sys.argv[1]),
                third_check_ok='--third-check-fails' not in sys.argv[2:]))
