"""Device time of a named scope that lies INSIDE a compiled program's loops,
together with the kernels that the compiler put there under a name of its
own, per execution of the module, in milliseconds.

``trace_inner_scope_time`` sums the self time of every operation whose scope
path (the stat ``tf_op`` of its metadata) holds ``scope``. A
``jax.lax.ragged_dot`` inside the scope does not reach the trace that way:
the TPU compiler replaces it by a Mosaic kernel whose metadata is the
compiler's (``%ragged-dot-none.5 = bf16[65536,1024] custom-call(...)``,
``op_name="ragged-dot-none"``, and ``%ragged-dot-metadata`` for the groups'
offsets), so the scope's sum holds the operations AROUND the grouped product
and leaves the product out (PERF.md section 6, PR 38: the lowered text of
the fused program shows it, and a share of a roofline read 97.8% from it).
This reader counts an operation if its scope path holds ``scope`` OR the
name of its instruction starts with one of ``kernels``: the same self times,
one more way to belong. The ``kernels`` a metric names are part of what it
measures; name only those that the program opens inside ``scope`` and
nowhere else.

args: ``module``, ``scope``, ``kernels`` (instruction-name prefixes, without
the ``%``), ``stat`` (``median``). Without ``--trace 1``, or where no
operation of the module is counted, there is nothing to read.
"""

import os

from ..record import quantile
from . import trace_scope_time
from .trace_inner_scope_time import self_times


def read(run, module, scope, kernels, stat='median'):
    if not run.trace:
        return None
    path = run.trace['path']
    if not os.path.exists(path):
        return None
    loaded = trace_scope_time.load(path)
    if loaded is None:
        return None
    _modules, _ops, names, paths = loaded
    kernels = tuple(kernels)

    def counted(key):
        return (scope in (paths.get(key) or '').split('/')
                or names.get(key, '').lstrip('%').startswith(kernels))
    lo, hi = run.trace['window']
    totals, by_kernel, seen = [], [], False
    for _start, _end, inside in trace_scope_time.module_executions(
            loaded, module, lo, hi):
        held = [(op_key, ns) for op_key, ns in self_times(inside)
                if counted(op_key)]
        seen = seen or bool(held)
        totals.append(sum(ns for _key, ns in held) / 1e9)
        by_kernel.append(sum(
            ns for op_key, ns in held
            if scope not in (paths.get(op_key) or '').split('/')) / 1e9)
    if not totals or not seen:
        return None
    q = {'median': 0.5}[stat]
    return {'value': quantile(totals, q) * 1e3, 'samples': len(totals),
            'kernels_ms': quantile(by_kernel, q) * 1e3}
