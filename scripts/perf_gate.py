#!/usr/bin/env python3
"""Perf-regression gate over benchmarks.jsonl (docs/observability.md
"Compiled-performance plane").

Every bench run appends one JSON row to benchmarks.jsonl; this gate turns
that trajectory into a CI check. The newest row per (row, backend,
geometry) key is compared against the MEDIAN of the prior same-key rows —
the median, not the mean, because a single outlier run must not
move the bar — and fails the build when the fresh value falls more than
the per-row noise tolerance below it.

Row handling:
  * rows without a numeric 'value' (pre-schema-v2 history) are skipped;
  * some row kinds stamp hard-bounded side fields (BOUNDED_FIELDS) that
    gate against a fixed ceiling rather than the history median — e.g.
    ``tracing_overhead_pct`` on the serving rows must stay <= 2% (the
    tracing-on/off A/B pair, docs/observability.md "Serving-path
    tracing"); a row that predates the field skips the bound;
  * rows marked ``degraded: true`` (a TPU request that fell back to CPU —
    bench.py stamps backend_requested/backend_actual) never gate and never
    enter the baseline: comparing a fallback row against silicon history
    is exactly the silent-fallback blind spot this plane closes;
  * a key with fewer than --min-history prior rows is "insufficient
    history" (exit 2, or 0 under --allow-insufficient — fresh CI
    geometries have no trajectory yet).

Optional pinned baseline: --baseline FILE consults {key: value} medians
written by a previous --update-baseline run instead of recomputing from
history (the file wins when both exist).

Exit contract: 0 = pass, 1 = regression, 2 = insufficient history /
unusable input.

Usage:
  python scripts/perf_gate.py                         # gate repo history
  python scripts/perf_gate.py --fresh /tmp/row.json   # gate one fresh row
  python scripts/perf_gate.py --tolerance bench-ingest=30 --min-history 2
  python scripts/perf_gate.py --update-baseline --baseline perf_base.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median
from typing import Any, Dict, List, Optional, Tuple

# per-row noise tolerance (percent below the median that still passes):
# host-path benches on shared CI runners are noisy; device benches less so
DEFAULT_TOLERANCE_PCT = 25.0
ROW_TOLERANCE_PCT = {
    'bench-ingest': 30.0,      # host threads vs CI scheduler noise
    'bench-actor': 30.0,
    'bench-actor-device': 30.0,   # fused on-device rollout fleet row
    'bench-serve': 30.0,
    'bench-serve-device': 30.0,   # device-backed serving engines row
    'bench-gateway': 30.0,        # session tier: subprocess + chaos noise
    'bench-headline': 15.0,    # compiled step timing is steadier
    'bench-mesh': 20.0,
}

# hard-bounded side fields: {row kind: {field: max allowed}}. Unlike the
# median gate these are absolute ceilings — the serving tracing A/B pair
# must cost <= 2% regardless of what history says. Rows that predate a
# field simply skip its bound.
BOUNDED_FIELDS: Dict[str, Dict[str, float]] = {
    'bench-serve': {'tracing_overhead_pct': 2.0},
    'bench-serve-device': {'tracing_overhead_pct': 2.0},
    'bench-gateway': {'tracing_overhead_pct': 2.0},
    # durable plane: the episode-WAL A/B pair on the host ingest path;
    # streaming plane: the chunked-ingest A/B pair (reassembly cost)
    'bench-ingest': {'spool_overhead_pct': 2.0,
                     'chunk_overhead_pct': 2.0},
}

Key = Tuple[str, str, str]


def row_key(row: Dict[str, Any]) -> Key:
    return (str(row.get('row') or row.get('metric') or '?'),
            str(row.get('backend') or '?'),
            str(row.get('geometry') or '?'))


def usable(row: Dict[str, Any]) -> bool:
    """Gate-eligible: numeric value (post-v2 schema) and not a degraded
    (backend-fallback) measurement."""
    if row.get('degraded'):
        return False
    try:
        float(row['value'])
    except (KeyError, TypeError, ValueError):
        return False
    return True


def load_history(path: str) -> List[Dict[str, Any]]:
    rows = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue   # a torn/hand-edited line is not a gate failure
            if isinstance(row, dict):
                rows.append(row)
    return rows


def tolerance_for(key: Key, overrides: Dict[str, float]) -> float:
    if key[0] in overrides:
        return overrides[key[0]]
    return ROW_TOLERANCE_PCT.get(key[0], DEFAULT_TOLERANCE_PCT)


def gate_key(key: Key, prior: List[float], fresh: float, tol_pct: float,
             baseline: Optional[float], min_history: int):
    """One key's verdict: ('pass'|'regress'|'insufficient', detail)."""
    base = baseline
    if base is None:
        if len(prior) < min_history:
            return 'insufficient', ('%d prior row(s), need %d'
                                    % (len(prior), min_history))
        base = median(prior)
    if base <= 0:
        return 'insufficient', 'non-positive baseline %r' % (base,)
    floor = base * (1.0 - tol_pct / 100.0)
    pct = 100.0 * (fresh - base) / base
    detail = ('fresh %.2f vs baseline %.2f (%+.1f%%, floor %.2f at '
              '-%.0f%%)' % (fresh, base, pct, floor, tol_pct))
    return ('regress' if fresh < floor else 'pass'), detail


def gate_bounds(key: Key, row: Dict[str, Any]):
    """Hard-bounded side fields for one fresh row: list of
    ('pass'|'regress', field, detail) — empty when the row kind has no
    bounds or the row predates the field."""
    out = []
    for field, bound in sorted(BOUNDED_FIELDS.get(key[0], {}).items()):
        if field not in row:
            continue
        try:
            val = float(row[field])
        except (TypeError, ValueError):
            out.append(('regress', field,
                        '%s=%r is not numeric' % (field, row[field])))
            continue
        out.append(('pass' if val <= bound else 'regress', field,
                    '%s %.2f vs ceiling %.2f' % (field, val, bound)))
    return out


def main(argv=None) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--history',
                    default=os.path.join(repo, 'benchmarks.jsonl'),
                    help='benchmarks JSONL trajectory (default: repo copy)')
    ap.add_argument('--fresh', default='',
                    help='file holding ONE fresh bench JSON row to gate '
                         'against the history (e.g. a CI bench stdout); '
                         'without it the newest history row per key gates '
                         'against its own priors')
    ap.add_argument('--baseline', default='',
                    help='pinned {key: value} baseline JSON (written by '
                         '--update-baseline); wins over the history median')
    ap.add_argument('--update-baseline', action='store_true',
                    help='write the current per-key medians (including the '
                         'fresh row) to --baseline and exit 0')
    ap.add_argument('--tolerance', action='append', default=[],
                    metavar='ROW=PCT',
                    help='override noise tolerance for one row kind '
                         '(repeatable), e.g. bench-ingest=30')
    ap.add_argument('--min-history', type=int, default=2,
                    help='prior same-key rows required to gate (default 2)')
    ap.add_argument('--allow-insufficient', action='store_true',
                    help='exit 0 instead of 2 when a key has no usable '
                         'history yet (fresh CI geometries)')
    ap.add_argument('--key', default='',
                    help='gate only keys whose row kind matches (e.g. '
                         'bench-ingest)')
    args = ap.parse_args(argv)

    overrides: Dict[str, float] = {}
    for spec in args.tolerance:
        name, _, pct = spec.partition('=')
        try:
            overrides[name.strip()] = float(pct)
        except ValueError:
            print('perf_gate: bad --tolerance %r' % spec, file=sys.stderr)
            return 2

    try:
        history = load_history(args.history)
    except OSError as exc:
        print('perf_gate: cannot read history %s: %s'
              % (args.history, exc), file=sys.stderr)
        return 2

    # group usable history per key, newest last (file order == append order)
    per_key: Dict[Key, List[Dict[str, Any]]] = {}
    for row in history:
        if usable(row):
            per_key.setdefault(row_key(row), []).append(row)

    # the rows under test: one external fresh row, or the newest per key
    # (the full dict rides along for the bounded side fields)
    fresh_rows: List[Tuple[Key, float, Dict[str, Any]]] = []
    if args.fresh:
        try:
            with open(args.fresh) as fh:
                text = fh.read().strip()
            fresh = json.loads(text.splitlines()[-1]) if text else {}
        except (OSError, ValueError) as exc:
            print('perf_gate: cannot parse fresh row %s: %s'
                  % (args.fresh, exc), file=sys.stderr)
            return 2
        if not isinstance(fresh, dict) or not usable(fresh):
            why = ('degraded (backend fallback)' if isinstance(fresh, dict)
                   and fresh.get('degraded') else 'no numeric value')
            print('perf_gate: fresh row not gate-eligible (%s) — skipping'
                  % why, file=sys.stderr)
            return 0 if args.allow_insufficient else 2
        fresh_rows.append((row_key(fresh), float(fresh['value']), fresh))
    else:
        for key, rows in per_key.items():
            fresh_rows.append((key, float(rows[-1]['value']), rows[-1]))
            per_key[key] = rows[:-1]   # priors exclude the row under test

    if args.key:
        fresh_rows = [(k, v, r) for k, v, r in fresh_rows
                      if k[0] == args.key]

    baseline_map: Dict[str, float] = {}
    if args.baseline and os.path.exists(args.baseline) \
            and not args.update_baseline:
        try:
            with open(args.baseline) as fh:
                baseline_map = {k: float(v)
                                for k, v in json.load(fh).items()}
        except (OSError, ValueError) as exc:
            print('perf_gate: bad baseline %s: %s' % (args.baseline, exc),
                  file=sys.stderr)
            return 2

    if args.update_baseline:
        if not args.baseline:
            print('perf_gate: --update-baseline needs --baseline FILE',
                  file=sys.stderr)
            return 2
        out = {}
        for key, fresh_val, _row in fresh_rows:
            vals = [float(r['value']) for r in per_key.get(key, [])]
            vals.append(fresh_val)
            out['|'.join(key)] = round(median(vals), 4)
        with open(args.baseline, 'w') as fh:
            json.dump(out, fh, indent=2, sort_keys=True)
        print('perf_gate: wrote %d baseline value(s) to %s'
              % (len(out), args.baseline))
        return 0

    if not fresh_rows:
        print('perf_gate: no gate-eligible rows found', file=sys.stderr)
        return 0 if args.allow_insufficient else 2

    worst = 0
    for key, fresh_val, row in sorted(fresh_rows, key=lambda t: t[:2]):
        prior = [float(r['value']) for r in per_key.get(key, [])]
        verdict, detail = gate_key(
            key, prior, fresh_val, tolerance_for(key, overrides),
            baseline_map.get('|'.join(key)), args.min_history)
        print('perf_gate: %-10s %s: %s' % (verdict.upper(),
                                           '/'.join(key), detail))
        if verdict == 'regress':
            worst = max(worst, 1)
        elif verdict == 'insufficient' and not args.allow_insufficient:
            worst = max(worst, 2) if worst != 1 else worst
        for bverdict, _field, bdetail in gate_bounds(key, row):
            print('perf_gate: %-10s %s: %s' % (bverdict.upper(),
                                               '/'.join(key), bdetail))
            if bverdict == 'regress':
                worst = max(worst, 1)
    return worst


if __name__ == '__main__':
    sys.exit(main())
