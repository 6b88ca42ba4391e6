"""The ratio of two of the program's cumulative counters over the measured
window: ``scale`` x growth of ``numerator`` / growth of ``denominator``.

The counters are attributes that the program sets on one of its own spans at
its close (``host_block`` carries ``plies``, ``builder_plies``,
``windows_ingested``, ... of every chunk fetched so far); the reader takes
them from the program's span ring (``program_span.ring``). Growth is taken
between the two records that bound the window: the last that ended at or
before its opening and the last that ended at or before its close, so it
covers the whole chunks fetched inside the window and nothing else.

args: ``stage``, the span's name; ``numerator`` and ``denominator``, each a counter's name or a
list of the name and further factors (a number, or a path such as
``train_args.generation_envs``) multiplied into it; ``scale`` (100 for a
share in percent). A zero denominator, or a program without the ring or the
counter, leaves the metric out."""

from .program_span import ring


def _term(spec):
    return (spec, ()) if isinstance(spec, str) else (spec[0], spec[1:])


def _growth(run, first, last, spec):
    name, factors = _term(spec)
    if name not in first['attrs'] or name not in last['attrs']:
        return None
    growth = float(last['attrs'][name] - first['attrs'][name])
    for factor in factors:
        growth *= float(run.param(factor))
    return growth


def read(run, stage, numerator, denominator, scale=1.0):
    records = [r for r in ring() or () if r['name'] == stage]
    lo, hi = run.window
    first = [r for r in records if r['t1'] <= lo][-1:]
    last = [r for r in records if r['t1'] <= hi][-1:]
    if not first or not last:
        return None
    top = _growth(run, first[0], last[0], numerator)
    bottom = _growth(run, first[0], last[0], denominator)
    if top is None or not bottom:
        return None
    return float(scale) * top / bottom
