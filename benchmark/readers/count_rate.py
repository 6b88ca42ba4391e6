"""A counter's growth over the measured window, per second.

args: ``span`` and ``capture`` name the counter (a hook's captured value);
``scale`` is a list of factors, each a number or a path such as
``train_args.batch_size``. The counter is read at the two chunk boundaries
that bound the window, so every unit counted is behind a blocking fetch."""


def read(run, span, capture, scale=()):
    first = run.capture_at(span, capture, run.window[0])
    last = run.capture_at(span, capture, run.window[1])
    if first is None or last is None or run.window_s <= 0:
        return None
    factor = 1.0
    for item in scale:
        factor *= float(run.param(item))
    return (last - first) * factor / run.window_s
