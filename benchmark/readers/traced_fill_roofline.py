"""A decode roofline whose required rows are the rows that the TRACED plies'
counters had reached: ``100 x sum(required seconds) / sum(measured seconds)``
over the executions of ``module`` that lie wholly in the traced stretch and
can be paired with the chunk they played.

Before PR 52 the six decode rooflines divided a count at the games' ANALYTIC
mean fill by the time of the one or two dispatches the profiler happened to
catch. A ply that reads only the rows its counters have reached takes a time
that follows ITS OWN fill, and 32 counters spread over a game's length have a
mean that wanders 10-15% from dispatch to dispatch: the quotient read one
dispatch's time against another fill's bytes (105.25% on PR 49's line).
Here numerator and denominator are of the same executions.

**Required seconds of one execution**: ``sgd_flops`` (the update steps'
count, where the scope has one; it does not depend on the fill) over the
chip's bf16 peak, plus ``chunk_bytes`` of the configuration's split
(``rollout``: ``ply_bytes`` a ply whatever the caches hold, ``row_bytes`` a
layer kind -> the bytes one more row in every sequence costs a ply;
``benchmark/flops_trinity_mini.py`` ``rollout_split``) over the HBM peak, the
rows taken from ``rows`` (``module:function`` of ``(model, kind,
ply_index)``, the configuration's ``flops_<x>.py`` ``rows_seen_at``) at every
lane's ply index at every ply of THAT chunk. Both seats of a lane's game share
its counter, so the mean over lanes is the mean over sequences.

**Measured seconds of one execution**: with ``scopes``, the top-level phase
``scope`` of the module by nesting (``trace_scope_time``'s rule: what
``rollout_ms`` reads); without, the self time of every operation whose scope
path holds ``scope`` (``trace_inner_scope_time``'s rule: what
``<x>_attention_ms`` reads).

**The ply indices** are rebuilt from the records of the hook ``span``
(``chunk_plies``: every fetched chunk's ``done`` (plies, lanes) and ordinal,
``FusedPipeline._parse``): a lane's index is 0 at the learner's first ply,
one more each ply, 0 again at the ply behind a ``done``. That is the net's own
counter (``hidden['pos']``; tests/benchmark holds the two equal for the four
trunk nets). The ordinals must run 1, 2, 3, ... from the learner's first
chunk: with a gap the indices are unknown and nothing is read.

**Pairing an execution with its chunk.** ``train_step`` enqueues program d,
THEN fetches chunk d - 1 (the fetch lags its dispatch by one call), so:

1. The profiler starts inside the hook of the dispatch that closed the
   window (call c: its own annotation is NOT in the trace, the program it
   enqueued is) and stops inside the hook of a later one. So annotation i of
   the trace's ``bench:<dispatch span>`` annotations (``run.trace['marks']``,
   i = 1, 2, ...) is the recorder's i-th record of that span behind the one
   that closed the window. Their lengths must agree (5 ms + 1%), or nothing
   is read.
2. Program d starts when program d - 1 ends, which is when call d's fetch
   returns at the earliest: it cannot END before call d has ended, and it
   HAS ended before call d + 1 ends (that call fetched it). So an execution
   that ends at ``e`` was enqueued by the LAST call that ended before ``e``:
   call c + k where k annotations ended before ``e`` (k = 0: the call that
   closed the window; the one program a trunk cell's traced second holds is
   that one). A program takes tens of milliseconds and more; the device's
   clock leads the host's by about one (``reduce_trace``).
3. That record's ``dispatches`` capture is d; the chunk is the ``span``
   record whose ``chunk`` capture is d.

An execution with no such record is unpaired and left out of both sums; with
none paired the metric is left out of the line. The analytic mean never
stands in: it is given BESIDE the value (``analytic_mean_value``: the old
expression over the same executions), with each execution's chunk ordinal,
mean rows a sequence by layer kind (``fill_rows``) and measured milliseconds.

args: ``module``; ``scope`` and, for a top-level phase, ``scopes``; ``span``
(the hook that carries ``done`` and ``chunk``); ``rows``; ``rollout`` (a path
into the cell's data: the split); ``sgd_flops`` (a path, optional). The
dispatch span is the traffic's (``window.dispatch_span``), whose annotations
bound the traced stretch."""

import bisect
import functools
import os

import numpy as np

from ..flops_trinity_mini import chunk_bytes
from ..hooks import resolve
from . import trace_scope_time
from .trace_inner_scope_time import self_times


def chunk_ply_indices(start, done):
    """``(index, next start)``: every lane's ply index at every ply of a chunk
    whose first ply finds the lanes at ``start`` (lanes,) and whose plies
    ended games where ``done`` (plies, lanes) says so."""
    index = np.empty(done.shape, np.int64)
    at = np.asarray(start, np.int64)
    for ply, ended in enumerate(done):
        index[ply] = at
        at = np.where(ended, 0, at + 1)
    return index, at


def ply_indices_by_chunk(records):
    """``{chunk ordinal: (plies, lanes) ply indices}`` from the hook's
    records in the order they were made, or None where the ordinals do not
    run 1, 2, 3, ...: a chunk that went unrecorded leaves every later index
    unknown."""
    out, at = {}, None
    for n, (_t0, _t1, captures) in enumerate(records, 1):
        done = np.asarray(captures['done'], bool)
        if captures['chunk'] != n:
            return None
        if at is None:
            at = np.zeros(done.shape[1], np.int64)
        out[n], at = chunk_ply_indices(at, done)
    return out


@functools.lru_cache(maxsize=1)
def _loaded(path):
    """One decode of the trace for all the cell's rooflines."""
    return trace_scope_time.load(path)


def scope_seconds(path, module, scope, scopes, lo, hi):
    """``[(end ns, seconds of scope)]``, one per execution of ``module``
    wholly inside [lo, hi], or None where no operation carries ``scope``."""
    loaded = _loaded(path)
    if loaded is None:
        return None
    paths = loaded[3]
    if scopes:
        by_id = {key: trace_scope_time.scope_of(path_, scopes)
                 for key, path_ in paths.items()}
    else:
        held = {key for key, path_ in paths.items()
                if scope in (path_ or '').split('/')}
    out = []
    for _start, end, inside in trace_scope_time.module_executions(
            loaded, module, lo, hi):
        if scopes:
            ns = trace_scope_time.split_execution(inside, by_id).get(scope, 0)
        else:
            ns = sum(ns for op_key, ns in self_times(inside)
                     if op_key in held)
        out.append((end, ns / 1e9))
    return out if any(seconds > 0 for _end, seconds in out) else None


def pair(executions, marks, dispatches):
    """``[(dispatch ordinal or None, seconds)]``: each execution ``(end ns,
    seconds)`` with the ``dispatches`` capture of the call that enqueued it.
    ``dispatches`` are the recorder's records from the call that closed the
    window on, ``marks`` the trace's dispatch annotations ``(start ns, end
    ns)``: annotation i is record i (the closing call, record 0, has none).
    None where they cannot be the same calls."""
    if not dispatches or len(dispatches) <= len(marks):
        return None
    for (start, end), (t0, t1, _captures) in zip(marks, dispatches[1:]):
        if abs((end - start) / 1e9 - (t1 - t0)) > 5e-3 + 0.01 * (t1 - t0):
            return None
    ends = [end for _start, end in marks]
    return [(dispatches[bisect.bisect_left(ends, end)][2].get('dispatches'),
             seconds) for end, seconds in executions]


def read(run, module, scope, span, rows, rollout, scopes=None,
         sgd_flops=None):
    if not run.trace or not os.path.exists(run.trace['path']):
        return None
    marks = run.trace.get('marks') or []
    lo, hi = run.trace['window']
    executions = scope_seconds(run.trace['path'], module, scope,
                               tuple(scopes or ()), lo, hi)
    indices = ply_indices_by_chunk(run.spans.get(span, ()))
    if not executions or not indices:
        return None
    dispatch_span = run.traffic['window']['dispatch_span']
    closing_on = [rec for rec in run.spans.get(dispatch_span, ())
                  if rec[1] >= run.window[1]][:len(marks) + 1]
    paired = pair(executions, marks, closing_on)
    if not paired:
        return None
    split, model = run.param(rollout), run.config['model']
    rows_at = resolve(rows)[2]
    flops_s = (run.param(sgd_flops) / run.names['peak.bf16_flops_per_s']
               if sgd_flops else 0.0)
    hbm = run.names['peak.hbm_bytes_per_s']
    analytic_s = flops_s + chunk_bytes(split) / hbm
    required = measured = 0.0
    detail = []
    for chunk, seconds in paired:
        if chunk not in indices:
            continue
        seen = {kind: rows_at(model, kind, indices[chunk])
                for kind in split['row_bytes']}
        required += flops_s + chunk_bytes(split, seen) / hbm
        measured += seconds
        detail.append({'chunk': chunk, 'ms': seconds * 1e3, 'fill_rows': {
            kind: float(np.mean(r)) for kind, r in seen.items()}})
    if not detail or not measured > 0:
        return None
    return {'value': 100 * required / measured, 'samples': len(detail),
            'analytic_mean_value': 100 * len(detail) * analytic_s / measured,
            'executions': detail}
