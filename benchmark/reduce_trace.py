"""From a profiler trace (``.xplane.pb``) to numbers, with
``jax.profiler.ProfileData`` alone.

What a TPU trace holds (looked at by hand on a v5e trace, PR 23): one plane
per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
execution of a compiled program, named ``jit_<function>(<fingerprint>)``)
and a line ``XLA Ops`` (one event per HLO operation executed; an operation
inside a ``while`` body lies inside the ``while`` event, so durations nest
and must not be summed raw); and the host plane ``/host:CPU`` with one line
per thread, where a ``jax.profiler.TraceAnnotation`` is an event under its
own name. The planes share one timeline, but in the recorded v5e trace
(``testdata/toy.xplane.pb``) the device's events lead the host's by about
1.3 ms (a program starts on the device "before" the host dispatches it), so
an idle gap of a few milliseconds cannot be attributed reliably; the gaps
that matter here (checkpoint writes, tens of ms) can.

``reduce`` returns::

    {"path": <the file>, "devices": 1,
     "window": [t0_ns, t1_ns], "window_s": ..,   # the traced window
     "marks": [[start_ns, end_ns], ...],         # its annotations, by end
     "busy_s": ..,               # union of op intervals, mean over chips
     "modules": {"jit_f": [seconds, ...]},        # per execution, chip 0
     "device_ops": [[name, self_seconds], ...],   # top 10, chip 0
     "idle_gaps": [[program span, seconds], ...]} # top 10, chip 0

The traced window is the span from the end of the first to the end of the
last host annotation ``bench:<window_span>`` (the harness's hook; whole
dispatch cycles); without such annotations, the extent of the device's own
events. An idle gap goes to the program's own span
(``handyrl:<name>``, ``telemetry.trace_span``) that the thread which feeds
the device was inside: those reach inside the calls that the harness's hooks
can only time whole. That thread is the one whose line carries the window's
marks (the dispatch call is made there); a span of another thread, such as a
checkpoint writer's beside the loop, owns no gap, whatever it overlaps.
"""

import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
HOST_PLANE = '/host:CPU'
MODULE_LINE = 'XLA Modules'
OP_LINE = 'XLA Ops'
SPAN_PREFIX = 'bench:'         # the harness's hooks: the window's marks
GAP_PREFIX = 'handyrl:'        # the program's spans: who owns an idle gap
NO_SPAN = '(no program span)'
TOP = 10


def _intervals(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(intervals, lo, hi):
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi))
                             for s, e, _ in intervals if e > lo and s < hi):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _self_times(intervals):
    """Seconds by name, each event's duration less its direct children's
    (events on one line nest; a child lies inside its parent)."""
    totals = {}
    stack = []   # [end, name, self_ns]

    def close():
        _end, name, self_ns = stack.pop()
        totals[name] = totals.get(name, 0.0) + self_ns / 1e9
    for start, end, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][2] -= end - start
        stack.append([end, name, end - start])
    while stack:
        close()
    return totals


def _module_name(event_name):
    """``jit_f(1234)`` -> ``jit_f``."""
    return re.sub(r'\(\d+\)$', '', event_name)


def _host_spans(host, prefix, thread_of=None):
    """The host's annotations that start with ``prefix``, the prefix cut
    off. A line is a thread: with ``thread_of``, only the lines that carry
    an annotation of that full name; all of them where none does."""
    lines = [_intervals(line) for line in host.lines] if host else []
    if thread_of is not None:
        lines = [ivs for ivs in lines
                 if any(name == thread_of for _s, _e, name in ivs)] or lines
    return [(start, end, name[len(prefix):]) for ivs in lines
            for start, end, name in ivs if name.startswith(prefix)]


def _attribute(gaps, spans):
    """Idle seconds by the span the host was inside. A gap that several
    spans overlap goes piece by piece to the SHORTEST span covering each
    piece (the innermost); what no span covers is named so."""
    totals = {}
    spans = sorted(spans, key=lambda s: s[1] - s[0])
    for lo, hi in gaps:
        rest = [(lo, hi)]
        for start, end, name in spans:
            if end <= lo or start >= hi:
                continue
            kept = []
            for a, b in rest:
                cut_lo, cut_hi = max(a, start), min(b, end)
                if cut_lo < cut_hi:
                    totals[name] = (totals.get(name, 0.0)
                                    + (cut_hi - cut_lo) / 1e9)
                    if a < cut_lo:
                        kept.append((a, cut_lo))
                    if cut_hi < b:
                        kept.append((cut_hi, b))
                else:
                    kept.append((a, b))
            rest = kept
        for a, b in rest:
            totals[NO_SPAN] = totals.get(NO_SPAN, 0.0) + (b - a) / 1e9
    return totals


def short_name(name):
    """``%copy.9 = f32[64,200]{1,0:T(8,128)} copy(...)`` -> ``%copy.9
    f32[64,200]``: the trace names an operation by its whole HLO line."""
    head, sep, rest = name.partition(' = ')
    if not sep:
        return name[:80]
    shape = re.match(r'[a-z0-9]+\[[0-9,]*\]', rest)
    return (head + (' ' + shape.group(0) if shape else ''))[:80]


def _top(totals):
    return [[short_name(name), seconds] for name, seconds in
            sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(path, window_span='train_dispatch', gap_prefix=GAP_PREFIX):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, None
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            devices[int(match.group(1))] = {
                line.name: _intervals(line) for line in plane.lines
                if line.name in (MODULE_LINE, OP_LINE)}
        elif plane.name == HOST_PLANE:
            host = plane
    if not devices:
        return None
    marks = sorted(((start, end) for start, end, name
                    in _host_spans(host, SPAN_PREFIX) if name == window_span),
                   key=lambda mark: mark[1])
    if len(marks) >= 2:
        lo, hi = marks[0][1], marks[-1][1]
    else:
        events = [iv for lines in devices.values()
                  for ivs in lines.values() for iv in ivs]
        if not events:
            return None
        lo = min(s for s, _e, _n in events)
        hi = max(e for _s, e, _n in events)
    pieces = {}   # per chip: merged busy intervals inside the window
    for chip, lines in devices.items():
        pieces[chip] = _union(lines.get(OP_LINE) or lines.get(MODULE_LINE)
                              or [], lo, hi)
    busy = [sum(e - s for s, e in p) / 1e9 for p in pieces.values()]
    first = devices[min(devices)]
    ops = first.get(OP_LINE) or first.get(MODULE_LINE) or []
    in_window = [iv for iv in ops if iv[1] > lo and iv[0] < hi]
    edges = [lo] + [t for piece in pieces[min(devices)] for t in piece] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    modules = {}
    for start, end, name in first.get(MODULE_LINE, []):
        if start >= lo and end <= hi:
            modules.setdefault(_module_name(name), []).append(
                (end - start) / 1e9)
    return {
        'path': path, 'devices': len(devices),
        'window': [lo, hi], 'window_s': (hi - lo) / 1e9,
        'marks': [list(mark) for mark in marks],
        'busy_s': sum(busy) / len(busy),
        'modules': modules,
        'device_ops': _top(_self_times(in_window)),
        'idle_gaps': _top(_attribute(gaps, _host_spans(
            host, gap_prefix, thread_of=SPAN_PREFIX + window_span))),
    }


def find_xplane(trace_dir):
    """The newest ``*.xplane.pb`` under ``trace_dir`` (the profiler writes
    ``plugins/profile/<time>/<host>.xplane.pb``), or None."""
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
