"""A statistic of one of the program's OWN spans over the measured window, in
milliseconds.

The program times its sections itself (``handyrl_tpu.telemetry.trace_span``)
and keeps the finished spans in a ring in memory; the harness runs the
program in this process, so the reader imports the module and reads the ring
(``telemetry.spans()``): records ``name``, ``t0``, ``t1`` on
``time.perf_counter`` (the clock of ``run.window``), ``span_id``,
``parent_id``, ``attrs``. As with a hook's records, a span belongs to the
window when its END lies in it. A program without such a ring (a commit from
before it) gives nothing to read: the metric is left out.

args: ``stage``, the span's name (not ``span``: the harness takes a metric's
``span`` argument for a hook to install); ``stat`` (``median``); ``minus``, a
child span whose time inside each record is taken out (``fused_iter`` minus
``host_block`` is the iteration's host work); ``without``, a child span whose
presence leaves the record out (``epoch_boundary``: boundary iterations);
``also``, spans whose time is added to the NEXT record of ``stage`` to end
after them (``host_block`` also ``state_fetch``: a boundary's state fetch
waits for the chunk in flight, and the fetch of that chunk's result then
returns at once, so the two together are one chunk's wait for the device)."""

from ..record import quantile

STATS = {'median': 0.5}


def ring():
    """Every finished span the program still holds, oldest first, or None."""
    try:
        from handyrl_tpu import telemetry
    except ImportError:
        return None
    spans = getattr(telemetry, 'spans', None)
    return spans() if spans is not None else None


def read(run, stage, stat='median', minus=None, without=None, also=()):
    records = ring()
    if not records:
        return None
    children = {}   # parent span id -> {child name: seconds}
    if minus or without:
        for rec in records:
            if rec['name'] in (minus, without):
                held = children.setdefault(rec['parent_id'], {})
                held[rec['name']] = (held.get(rec['name'], 0.0)
                                     + rec['t1'] - rec['t0'])
    lo, hi = run.window
    seconds, carried = [], 0.0
    for rec in sorted(records, key=lambda r: r['t1']):
        if rec['name'] in also:
            carried += rec['t1'] - rec['t0']
        if rec['name'] != stage:
            continue
        extra, carried = carried, 0.0
        inside = children.get(rec['span_id'], {})
        if not lo < rec['t1'] <= hi or (without and without in inside):
            continue
        seconds.append(rec['t1'] - rec['t0'] + extra
                       - inside.get(minus, 0.0))
    if not seconds:
        return None
    return {'value': quantile(seconds, STATS[stat]) * 1e3,
            'samples': len(seconds)}
