"""BENCHMARK.json and the data files it names, loaded and checked.

Everything that belongs to one configuration, one traffic mix, one hook or
one metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

    configs/<config>.json    (the path is the entry's ``file``); it names
                             its ``weights``, its ``checks`` and its
                             ``flops`` count, the last two by
                             ``module:function``
    traffic/<traffic>.json
    metrics/<metric>.json    a reader by name with its arguments
    readers/<reader>.py      ``read(run, **args)`` -> number or None
    hooks/<span>.json        ``module:qualname`` to wrap, and what to capture
    rehearsal/<config>.json  the CPU rehearsal's sizes (``rehearse.py``)

A later PR adds files and entries; it edits none of these loaders. A root
other than the checkout (``--root``: a scratch root for a first chip run, the
tests' fixture root) may bring readers of its own: its ``benchmark/readers``
joins the search path of the package ``benchmark.readers``, behind the
checkout's, so a reader there is found by name and shadows none.
"""

import json
import os
import re

from .hooks import HookError, resolve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

NAME_RE = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT_RE = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


class ManifestError(ValueError):
    """BENCHMARK.json or one of its data files breaks the contract."""


def check_name(name, what='name'):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError('%s %r: a name is at most 64 letters, digits, '
                            '"_", "." and "-", and does not start with "." '
                            'or "-"' % (what, name))
    return name


def check_unit(unit, what='unit'):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError('%s %r: a unit is 1 to 16 letters, digits, "_", '
                            '"/", "%%", "." and "-", with no space'
                            % (what, unit))
    return unit


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise ManifestError('cannot read %s: %s' % (path, exc))
    except ValueError as exc:
        raise ManifestError('%s is not JSON: %s' % (path, exc))


def _readers_of(root):
    """``<root>/benchmark/readers`` onto ``benchmark.readers``' path."""
    from . import readers
    folder = os.path.realpath(os.path.join(root, 'benchmark', 'readers'))
    if os.path.isdir(folder) and folder not in map(os.path.realpath,
                                                   readers.__path__):
        readers.__path__.append(folder)


class Manifest:
    """The parsed ``BENCHMARK.json`` of a checkout."""

    def __init__(self, root=ROOT):
        self.root = root
        self.raw = _read_json(os.path.join(root, 'BENCHMARK.json'))
        _readers_of(root)
        self.run_seconds = int(self.raw['run_seconds'])
        self.configs = {}
        for entry in self.raw['configs']:
            check_name(entry['name'], 'configuration')
            for key in entry['reduced']:
                check_name(key, 'reduced key of %s' % entry['name'])
            self.configs[entry['name']] = entry
        self.cells = {}
        for entry in self.raw['workloads']:
            check_name(entry['name'], 'workload')
            check_name(entry['traffic'], 'traffic of %s' % entry['name'])
            if entry['config'] not in self.configs:
                raise ManifestError('workload %s names the unknown '
                                    'configuration %r'
                                    % (entry['name'], entry['config']))
            if entry['chips'] not in (1, 4):
                raise ManifestError('workload %s asks for %r chips'
                                    % (entry['name'], entry['chips']))
            self.cells[entry['name']] = entry
        self.metrics = {}
        for group in ('end_to_end', 'per_layer'):
            for entry in self.raw[group]:
                name = check_name(entry['name'], 'metric')
                check_unit(entry['unit'], 'unit of %s' % name)
                if name in self.metrics:
                    raise ManifestError('metric %s appears twice' % name)
                if entry['better'] not in ('lower', 'higher'):
                    raise ManifestError('metric %s: better is %r'
                                        % (name, entry['better']))
                if entry['source'] not in SOURCES:
                    raise ManifestError('metric %s: source is %r'
                                        % (name, entry['source']))
                for cell in entry.get('workloads', ()):
                    if cell not in self.cells:
                        raise ManifestError('metric %s lists the unknown '
                                            'workload %r' % (name, cell))
                self.metrics[name] = dict(entry, group=group)
        for name, entry in self.metrics.items():
            if entry['group'] != 'per_layer':
                continue
            moved = self.metrics.get(entry['moves'])
            if moved is None or moved['group'] != 'end_to_end':
                raise ManifestError('metric %s moves %r, which is no '
                                    'end-to-end metric'
                                    % (name, entry['moves']))
            for cell in self.cells_of(name):
                if cell not in self.cells_of(entry['moves']):
                    raise ManifestError(
                        'metric %s is reported in %s, where %s, which it '
                        'moves, is not' % (name, cell, entry['moves']))

    # -- lookups -----------------------------------------------------------
    def cell(self, name):
        if name not in self.cells:
            raise ManifestError('no workload %r in BENCHMARK.json (it has %s)'
                                % (name, ', '.join(self.cells)))
        return self.cells[name]

    def cells_of(self, metric):
        return tuple(self.metrics[metric].get('workloads')
                     or self.cells.keys())

    def metrics_of(self, cell, group=None):
        """Names of the metrics ``cell`` reports, in the file's order."""
        return [name for name, entry in self.metrics.items()
                if cell in self.cells_of(name)
                and group in (None, entry['group'])]

    # -- the data files ----------------------------------------------------
    def load_config(self, name):
        """A configuration's file. What it names by ``module:function`` (each
        of its ``checks``, its ``flops``) resolves here or fails by name, and
        its ``weights`` are one of the two kinds."""
        config = _read_json(os.path.join(self.root,
                                         self.configs[name]['file']))
        weights = config['weights']
        if ('checkpoint' in weights) == (weights.get('seeded') is True):
            raise ManifestError(
                'configuration %s: weights is either {"checkpoint": <path>} '
                'or {"seeded": true}, not %r' % (name, weights))
        named = [(check_name(entry['name'], 'check of %s' % name),
                  entry['check']) for entry in config['checks']]
        for key, target in named + [('flops', config['flops'])]:
            try:
                resolve(target)
            except HookError as exc:
                raise ManifestError('configuration %s, %s: %s'
                                    % (name, key, exc))
        return config

    def load_traffic(self, name):
        return _read_json(os.path.join(self.root, 'benchmark', 'traffic',
                                       check_name(name) + '.json'))

    def load_metric(self, name):
        """A metric's own file, checked against its entry in the manifest."""
        spec = _read_json(os.path.join(self.root, 'benchmark', 'metrics',
                                       check_name(name) + '.json'))
        entry = self.metrics[name]
        for key in ('unit', 'better', 'source', 'layer', 'moves'):
            if key in spec and spec[key] != entry.get(key):
                raise ManifestError('metrics/%s.json says %s = %r, '
                                    'BENCHMARK.json says %r'
                                    % (name, key, spec[key], entry.get(key)))
        check_name(spec['reader'], 'reader of %s' % name)
        return spec

    def load_hooks(self, spans):
        """``hooks/<span>.json`` of each named span, keyed by span name. A
        cell installs the hooks its own files name and no others
        (``session.spans_of``)."""
        hooks = {}
        for span in spans:
            spec = _read_json(os.path.join(self.root, 'benchmark', 'hooks',
                                           check_name(span, 'span') + '.json'))
            if spec.get('span', span) != span:
                raise ManifestError('hooks/%s.json names the span %r'
                                    % (span, spec['span']))
            hooks[span] = spec
        return hooks

    def load_peaks(self):
        return _read_json(os.path.join(self.root, 'benchmark', 'peaks.json'))
