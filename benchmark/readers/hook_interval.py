"""A statistic of the time between the ends of successive records of one
span, in milliseconds.

args: ``span``; ``phase`` (``window``, ``before_window`` or ``all``);
``stat`` (``median``, ``p95`` or ``max``); ``drop_first`` records left out (the
first calls compile or load). In the ``window`` phase the record that opened
the window is the first end, so n chunks give n intervals. A host clock is
off by some half a millisecond a read; the statistic is over single
intervals, so it is a per-layer reading or a tail, never a rate."""

from ..record import intervals, quantile

STATS = {'median': 0.5, 'p95': 0.95, 'max': 1.0}


def read(run, span, phase='window', stat='median', drop_first=0):
    records = run.records(span, phase)
    if phase == 'window':
        opener = [r for r in run.spans.get(span, ())
                  if r[1] <= run.window[0]][-1:]
        records = opener + records
    gaps = intervals(records[drop_first:])
    if not gaps:
        return None
    return {'value': quantile(gaps, STATS[stat]) * 1e3, 'samples': len(gaps)}
