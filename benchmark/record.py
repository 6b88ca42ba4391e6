"""What one run leaves for the metric readers, and the small helpers they
share. Times are ``time.perf_counter`` seconds of the run's process."""

import math


class Run:
    """One run of one cell.

    ``spans``   ``{span: [(t0, t1, captures), ...]}`` from the hooks
    ``window``  ``(t_open, t_close)``: the measured window, both ends the end
                of a dispatch span (so it holds whole chunks)
    ``trace``   ``reduce_trace.reduce``'s dict, or None without ``--trace 1``
    ``memory``  ``Device.memory_stats()`` of the fullest device
    ``cell``    the manifest entry; ``config`` / ``traffic`` its two files;
                ``train_args`` what the learner was given
    ``names``   numbers a ``derived`` metric may use besides other metrics
    ``values``  metrics read so far, by name
    """

    def __init__(self, cell, config, traffic, train_args, spans, window,
                 trace=None, memory=None, names=None):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.train_args = train_args
        self.spans, self.window = spans, window
        self.trace, self.memory = trace, memory or {}
        self.names = dict(names or {})
        self.values = {}

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def records(self, span, phase='window'):
        """A span's records whose END lies in the phase: ``window`` (open <
        end <= close), ``before_window`` (end <= open) or ``all``."""
        lo, hi = self.window
        out = []
        for rec in self.spans.get(span, ()):
            inside = lo < rec[1] <= hi
            if (phase == 'all' or (phase == 'window' and inside)
                    or (phase == 'before_window' and rec[1] <= lo)):
                out.append(rec)
        return out

    def capture_at(self, span, name, t):
        """``name`` as captured by the first record of ``span`` that ends at
        or after ``t`` (the reading that belongs to the chunk boundary at
        ``t``), or None."""
        for _t0, t1, captures in self.spans.get(span, ()):
            if t1 >= t:
                return captures.get(name)
        return None

    def param(self, path):
        """``train_args.batch_size``-style path into the cell's data, or a
        plain number."""
        if isinstance(path, (int, float)):
            return path
        head, _, rest = path.partition('.')
        node = {'train_args': self.train_args, 'config': self.config,
                'traffic': self.traffic, 'cell': self.cell}[head]
        for step in rest.split('.'):
            node = node[step]
        return node


def quantile(values, q):
    """Linear interpolation between closest ranks (numpy's default)."""
    ordered = sorted(values)
    if not ordered:
        return None
    pos = q * (len(ordered) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def intervals(records):
    """Seconds between the ends of successive records."""
    ends = [rec[1] for rec in records]
    return [b - a for a, b in zip(ends, ends[1:])]
