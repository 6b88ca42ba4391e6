"""Timing shims around the program's calls into each layer.

A hook is a data file, ``hooks/<span>.json``::

    {"span": "train_dispatch",
     "target": "handyrl_tpu.ops.fused_pipeline:FusedPipeline.train_step",
     "capture": {"dispatches": "self.dispatches"}}

``target`` is ``module:qualname`` of a function or method of the program.
The shim records two clock reads a call; with annotations on (``--trace 1``)
it also opens a ``jax.profiler.TraceAnnotation`` named ``bench:<span>``, so
the span is on the profiler's clock beside the device's operations.
``capture`` reads values after the call, each a dotted path rooted at
``self`` (the bound instance), ``ret`` (the return value) or ``argN``; a step
is an attribute or, failing that, a key. Only numbers, strings, flat dicts
of them and numpy arrays (a chunk's ``done`` flags) are kept. Two hooks may
wrap one target (``chunk_fetch`` and ``chunk_plies`` both wrap
``FusedPipeline._parse``): the later one wraps the earlier one's shim, and
they come off in the reverse order. A target that does not resolve fails by
name.
"""

import functools
import importlib
import time

import numpy as np


class HookError(RuntimeError):
    """A hook's target cannot be found in the program."""


def resolve(target):
    """``module:qualname`` -> (owner object, attribute name, function)."""
    module_name, _, qualname = target.partition(':')
    if not qualname:
        raise HookError('hook target %r is not module:qualname' % target)
    try:
        owner = importlib.import_module(module_name)
    except ImportError as exc:
        raise HookError('hook target %r: cannot import %s (%s)'
                        % (target, module_name, exc))
    parts = qualname.split('.')
    for part in parts[:-1]:
        if not hasattr(owner, part):
            raise HookError('hook target %r: %s has no %r'
                            % (target, owner, part))
        owner = getattr(owner, part)
    if not callable(getattr(owner, parts[-1], None)):
        raise HookError('hook target %r: %s has no callable %r'
                        % (target, owner, parts[-1]))
    return owner, parts[-1], getattr(owner, parts[-1])


def _walk(root, path):
    for step in path:
        if hasattr(root, step):
            root = getattr(root, step)
        else:
            root = root[step]
    return root


def _plain(value):
    if isinstance(value, dict):
        return {str(k): _plain(v) for k, v in value.items()}
    if isinstance(value, (bool, int, float, str)) or value is None:
        return value
    if isinstance(value, np.ndarray) and value.ndim:
        return value.copy()
    return float(value)


class Recorder:
    """Spans kept in memory: ``spans[name]`` is a list of
    ``(t0, t1, captures)`` on ``time.perf_counter``'s clock."""

    def __init__(self, annotate=False):
        self.annotate = annotate
        self.spans = {}
        self.listeners = {}   # span -> [callable(t0, t1, captures)]

    def on(self, span, fn):
        self.listeners.setdefault(span, []).append(fn)

    def record(self, span, t0, t1, captures):
        self.spans.setdefault(span, []).append((t0, t1, captures))
        for fn in self.listeners.get(span, ()):
            fn(t0, t1, captures)


def _shim(fn, span, capture, recorder):
    paths = {name: path.split('.') for name, path in capture.items()}
    annotation = None
    if recorder.annotate:
        from jax.profiler import TraceAnnotation as annotation

    @functools.wraps(fn)
    def shim(*args, **kwargs):
        note = None
        if annotation is not None:
            note = annotation('bench:' + span)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            ret = fn(*args, **kwargs)
        finally:
            t1 = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
        roots = {'self': args[0] if args else None, 'ret': ret}
        roots.update(('arg%d' % i, a) for i, a in enumerate(args))
        captured = {name: _plain(_walk(roots[path[0]], path[1:]))
                    for name, path in paths.items()}
        recorder.record(span, t0, t1, captured)
        return ret
    return shim


def install(specs, recorder):
    """Wrap every hook's target; returns a function that unwraps them."""
    undo = []
    for span, spec in specs.items():
        owner, attr, fn = resolve(spec['target'])
        setattr(owner, attr, _shim(fn, span, spec.get('capture', {}),
                                   recorder))
        undo.append((owner, attr, fn))

    def uninstall():
        for owner, attr, fn in reversed(undo):
            setattr(owner, attr, fn)
    return uninstall
