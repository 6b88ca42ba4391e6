"""Device time of one named phase of a compiled program, per execution, in
milliseconds: ``stat`` (``median``) over the executions of ``module`` that
lie wholly inside the traced window.

The program wraps its phases in ``jax.named_scope`` (``rollout``, ``ingest``,
``sgd``, ``pack`` in ``ops/fused_pipeline.py``), and the scope path of an
operation reaches the trace as the stat ``tf_op`` of the event's METADATA
(``jit(fused_pipeline_train)/ingest/while/body/...``).
``jax.profiler.ProfileData`` does not surface metadata stats, so this file
decodes the ``.xplane.pb`` itself, as far as it needs: protobuf wire format,
field numbers of ``tsl/profiler/protobuf/xplane.proto`` (``FIELDS`` below).

Operations the compiler inserts (layout copies, ``copy-start``, a ``while``
itself) carry no ``tf_op``, and the costliest operations of this program are
such copies. So time is assigned BY NESTING, not by summing labelled
operations: on the ``XLA Ops`` line an operation inside a ``while`` body lies
inside the ``while`` event. Within one execution of the module, each
TOP-LEVEL operation (inside no other) is given the scope that holds most of
the labelled self time among itself and its descendants; a top-level
operation with no labelled descendant is unscoped. A scope's time is the sum
of its top-level operations' durations. ``unscoped`` is the module's
duration less every named scope's time: unlabelled top-level operations and
the gaps between operations.

args: ``module`` (``jit_fused_pipeline_train``); ``scope`` (one of ``scopes``
or ``unscoped``); ``scopes``, every phase name the program uses;
``stat``. The reader opens the run's trace file itself (``run.trace['path']``,
as ``reduce_trace.reduce`` found it; the harness removes it after the metrics
are read). Without ``--trace 1``, or where no operation of
the module carries any of the scopes (a program from before they were
named), there is nothing to read."""

import bisect
import functools
import os

from .. import reduce_trace
from ..record import quantile

# message -> field numbers used here (xplane.proto)
FIELDS = {
    'XSpace': {'planes': 1},
    'XPlane': {'name': 2, 'lines': 3, 'event_metadata': 4,
               'stat_metadata': 5},
    'XLine': {'name': 2, 'timestamp_ns': 3, 'events': 4},
    'XEvent': {'metadata_id': 1, 'offset_ps': 2, 'duration_ps': 3},
    'XEventMetadata': {'name': 2, 'stats': 5},
    'XStat': {'metadata_id': 1, 'str_value': 5, 'ref_value': 7},
    'XStatMetadata': {'name': 2},
    'map_entry': {'key': 1, 'value': 2},
}
SCOPE_STAT = 'tf_op'
UNSCOPED = 'unscoped'


def _varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError('xplane: wire type %d' % wire)
        yield key >> 3, value


def _text(view):
    return bytes(view).decode('utf-8', 'replace')


def _map_entries(plane_fields, number):
    entry_of = FIELDS['map_entry']
    for field, value in plane_fields:
        if field == number:
            entry = dict(_fields(value))
            yield entry.get(entry_of['key'], 0), entry.get(entry_of['value'],
                                                           b'')


def _events(line):
    """(start_ns, end_ns, metadata id) of a line's events."""
    base = 0
    raw = []
    for field, value in _fields(line):
        if field == FIELDS['XLine']['timestamp_ns']:
            base = value
        elif field == FIELDS['XLine']['events']:
            raw.append(value)
    # a trace holds a million of these, so the three leading varint fields
    # are decoded in place; the event's own stats follow and are not needed
    keys = {number << 3: name for name, number in FIELDS['XEvent'].items()}
    out = []
    for event in raw:
        event = bytes(event)
        got = {'metadata_id': 0, 'offset_ps': 0, 'duration_ps': 0}
        pos, end = 0, len(event)
        while pos < end and event[pos] in keys:
            name = keys[event[pos]]
            pos += 1
            value = shift = 0
            while True:
                byte = event[pos]
                pos += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            got[name] = value
        start = base + got['offset_ps'] / 1e3
        out.append((start, start + got['duration_ps'] / 1e3,
                    got['metadata_id']))
    return out


def load(path):
    """The first TPU plane of an ``.xplane.pb``: ``(modules, ops, names,
    paths)``: the events of its ``XLA Modules`` and ``XLA Ops`` lines, each
    metadata id's name and, where it has one, its scope path."""
    with open(path, 'rb') as f:
        space = memoryview(f.read())
    planes = {}
    for field, plane in _fields(space):
        if field != FIELDS['XSpace']['planes']:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for f, v in fields
                     if f == FIELDS['XPlane']['name']), '')
        match = reduce_trace.DEVICE_PLANE.match(name)
        if match:
            planes[int(match.group(1))] = fields
    if not planes:
        return None
    fields = planes[min(planes)]
    stat_names = {}
    for key, value in _map_entries(fields, FIELDS['XPlane']['stat_metadata']):
        stat_names[key] = _text(dict(_fields(value)).get(
            FIELDS['XStatMetadata']['name'], b''))
    names, paths = {}, {}
    for key, value in _map_entries(fields,
                                   FIELDS['XPlane']['event_metadata']):
        for field, item in _fields(value):
            if field == FIELDS['XEventMetadata']['name']:
                names[key] = _text(item)
            elif field == FIELDS['XEventMetadata']['stats']:
                stat = dict(_fields(item))
                if stat_names.get(stat.get(
                        FIELDS['XStat']['metadata_id'])) != SCOPE_STAT:
                    continue
                if FIELDS['XStat']['str_value'] in stat:
                    paths[key] = _text(stat[FIELDS['XStat']['str_value']])
                elif FIELDS['XStat']['ref_value'] in stat:
                    paths[key] = stat_names.get(
                        stat[FIELDS['XStat']['ref_value']], '')
    lines = {}
    for field, line in fields:
        if field == FIELDS['XPlane']['lines']:
            name = next((_text(v) for f, v in _fields(line)
                         if f == FIELDS['XLine']['name']), '')
            if name in (reduce_trace.MODULE_LINE, reduce_trace.OP_LINE):
                lines[name] = _events(line)
    return (lines.get(reduce_trace.MODULE_LINE, []),
            lines.get(reduce_trace.OP_LINE, []), names, paths)


def scope_of(path, scopes):
    """The outermost of ``scopes`` on a scope path, or None."""
    for step in (path or '').split('/'):
        if step in scopes:
            return step
    return None


def split_execution(ops, scope_by_id):
    """``{scope: ns}`` of one execution's operations ``(start, end, id)``:
    every top-level operation's duration under the scope that holds most of
    the labelled self time among it and its descendants."""
    totals = {}
    stack = []   # [end, self_ns, scope, {scope: labelled self ns}]
    top = None   # the open top-level operation: [start, end, labelled]

    def close():
        _end, self_ns, scope, _ = stack.pop()
        if scope is not None:
            top[2][scope] = top[2].get(scope, 0.0) + self_ns

    def finish():
        start, end, labelled = top
        scope = (max(labelled, key=labelled.get) if labelled else UNSCOPED)
        totals[scope] = totals.get(scope, 0.0) + end - start

    for start, end, key in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            close()
        if not stack:
            if top is not None:
                finish()
            top = [start, end, {}]
        else:
            stack[-1][1] -= end - start
        stack.append([end, end - start, scope_by_id.get(key), None])
    while stack:
        close()
    if top is not None:
        finish()
    return totals


def module_executions(loaded, module, lo, hi):
    """``(start ns, end ns, its operations)`` of every execution of
    ``module`` wholly inside [lo, hi], in the order they ran; ``loaded`` is
    ``load``'s tuple (its operations are sorted here, once)."""
    modules, ops, names, _paths = loaded
    ops.sort()
    starts = [op[0] for op in ops]
    for start, end, key in sorted(modules):
        if (reduce_trace._module_name(names.get(key, '')) != module
                or start < lo or end > hi):
            continue
        yield start, end, [
            op for op in ops[bisect.bisect_left(starts, start):
                             bisect.bisect_right(starts, end)]
            if op[1] <= end]


@functools.lru_cache(maxsize=4)
def executions(path, module, scopes, lo, hi):
    """One ``{scope: seconds, 'module': seconds}`` per execution of
    ``module`` wholly inside [lo, hi] ns, or None where no operation of the
    module carries any of ``scopes``. ``unscoped`` there is the summed
    duration of the top-level operations with no labelled descendant."""
    loaded = load(path)
    if loaded is None:
        return None
    scope_by_id = {key: scope_of(path_, scopes)
                   for key, path_ in loaded[3].items()}
    out, labelled = [], False
    for start, end, inside in module_executions(loaded, module, lo, hi):
        labelled = labelled or any(scope_by_id.get(op[2]) for op in inside)
        split = {k: v / 1e9 for k, v in
                 split_execution(inside, scope_by_id).items()}
        split['module'] = (end - start) / 1e9
        out.append(split)
    return out if labelled else None


def read(run, module, scope, scopes, stat='median'):
    if not run.trace:
        return None
    path = run.trace['path']
    if not os.path.exists(path):
        return None
    lo, hi = run.trace['window']
    runs = executions(path, module, tuple(scopes), lo, hi)
    if not runs:
        return None

    def median_ms(values):
        return quantile(values, {'median': 0.5}[stat]) * 1e3

    if scope != UNSCOPED:
        return {'value': median_ms([r.get(scope, 0.0) for r in runs]),
                'samples': len(runs)}
    # the value is a remainder, so the scopes and it add up to the module by
    # construction; its two parts are given beside it, each summed directly,
    # so that time put under the wrong phase would show in them
    owned = [sum(r.get(s, 0.0) for s in scopes) for r in runs]
    return {'value': median_ms([r['module'] - o for r, o in zip(runs, owned)]),
            'samples': len(runs),
            'unlabelled_ops_ms': median_ms(
                [r.get(UNSCOPED, 0.0) for r in runs]),
            'gaps_ms': median_ms([r['module'] - o - r.get(UNSCOPED, 0.0)
                                  for r, o in zip(runs, owned)])}
