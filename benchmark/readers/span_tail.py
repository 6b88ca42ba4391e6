"""Host time at the tail of a span, after the last ``inner`` span inside it
has ended, in milliseconds: ``stat`` over the window's records of ``span``.

For an epoch boundary with ``inner`` = the packed state fetch this is the
time from the moment the device has nothing queued (the fetch waited for the
chunk in flight) to the end of the boundary: checkpoint serialisation, file
writes, the metrics record. The device idles for all of it. A record without
an ``inner`` span inside is left out. args: ``span``, ``inner``, ``stat``."""

from ..record import quantile

STATS = {'median': 0.5, 'p95': 0.95}


def read(run, span, inner, stat='median'):
    tails = []
    for t0, t1, _captures in run.records(span, 'window'):
        ends = [e for _s, e, _c in run.spans.get(inner, ()) if t0 <= e <= t1]
        if ends:
            tails.append(t1 - max(ends))
    if not tails:
        return None
    return {'value': quantile(tails, STATS[stat]) * 1e3,
            'samples': len(tails)}
