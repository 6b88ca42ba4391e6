"""Device time of a named scope that lies INSIDE a compiled program's loops,
per execution of the module, in milliseconds.

``trace_scope_time`` gives each TOP-LEVEL operation of the module to one
phase, so it cannot read a scope that the program opens inside a ``while``
body (``eva_attention`` inside the rollout scan and inside the SGD scan:
models/evabyte.py). This reader sums, over one execution, the SELF time of
every operation at any depth whose scope path (the stat ``tf_op`` of its
metadata) holds ``scope``: an operation's duration less that of the
operations nested in it, so a loop is never counted beside its body. What
the compiler inserts without a scope path (a layout copy inside the scope's
region) is not counted; the value is a lower bound of the scope's time by
that much.

args: ``module``, ``scope``, ``stat`` (``median``).
Decoding of the ``.xplane.pb`` is ``trace_scope_time``'s. Without
``--trace 1``, or where no operation of the module carries the scope (a
program from before it was named), there is nothing to read.
"""

import os

from ..record import quantile
from . import trace_scope_time


def self_times(ops):
    """``[(metadata id, self ns)]`` of one execution's operations
    ``(start, end, id)``: each one's duration less its children's."""
    out, stack = [], []    # stack of [end, self ns, id]
    for start, end, key in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            done = stack.pop()
            out.append((done[2], done[1]))
        if stack:
            stack[-1][1] -= end - start
        stack.append([end, end - start, key])
    out.extend((key, self_ns) for _end, self_ns, key in stack)
    return out


def read(run, module, scope, stat='median'):
    if not run.trace:
        return None
    path = run.trace['path']
    if not os.path.exists(path):
        return None
    loaded = trace_scope_time.load(path)
    if loaded is None:
        return None
    paths = loaded[3]

    def counted(key):
        return scope in (paths.get(key) or '').split('/')
    lo, hi = run.trace['window']
    totals, seen = [], False
    for _start, _end, inside in trace_scope_time.module_executions(
            loaded, module, lo, hi):
        held = [ns for op_key, ns in self_times(inside) if counted(op_key)]
        seen = seen or bool(held)
        totals.append(sum(held) / 1e9)
    if not totals or not seen:
        return None
    return {'value': quantile(totals, {'median': 0.5}[stat]) * 1e3,
            'samples': len(totals)}
