"""GL005 — vocabulary drift between code and docs, both directions.

PRs 3/7 established that every metric name, span/stage name, and config
knob belongs to ONE documented vocabulary (docs/observability.md, the
stage glossary, docs/parameters.md, config.py defaults + validation). This
checker turns doc rot into a lint failure:

* a metric/stage literal used at a call site but absent from
  docs/observability.md — an undocumented signal nobody will find on a
  dashboard;
* a metric/stage documented in the catalog tables but used nowhere — the
  doc describes a signal that no longer exists;
* a config knob in ``config.py`` defaults missing its docs/parameters.md
  row, or a documented knob with no default — an operator reading the doc
  would set a key nothing reads;
* a key referenced by ``config.validate()`` that is not a known knob — a
  validation rule silently checking nothing.

Everything is static: ``config.py`` is AST-parsed (no package import), the
docs are parsed for backticked tokens, sources for string literals at the
registry call sites. Dynamically constructed names (``key + '_mean'``) are
matched by the documented-name -> source-substring direction with a
``_mean`` suffix fallback.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, SourceFile

_BACKTICK_RE = re.compile(r'`([^`]+)`')


def _doc_tokens(doc: SourceFile) -> Set[str]:
    """Backticked tokens, matched per line (tokens never span lines) with
    triple-backtick fence lines skipped — a ``` delimiter would otherwise
    desync every later pairing in the file."""
    out: Set[str] = set()
    for line in doc.lines:
        if '```' in line:
            continue
        out.update(_BACKTICK_RE.findall(line))
    return out

# registry entry points whose first positional string literal is a metric
_METRIC_CALLS = {'counter', 'gauge', 'histogram'}
# entry points whose first positional string literal is a stage name
_STAGE_CALLS = {'observe_stage', 'trace_span'}

# package files whose literals are NOT part of the runtime vocabulary
_EXCLUDED_PREFIXES = ('handyrl_tpu/analysis/',)


def _first_str_arg(call: ast.Call) -> Optional[str]:
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    if isinstance(call.func, ast.Name):
        return call.func.id
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return None


def collect_code_vocabulary(sources: Dict[str, SourceFile]
                            ) -> Tuple[Dict[str, Tuple[str, int]],
                                       Dict[str, Tuple[str, int]]]:
    """(metrics, stages): literal name -> first (path, line) using it."""
    metrics: Dict[str, Tuple[str, int]] = {}
    stages: Dict[str, Tuple[str, int]] = {}
    for path, src in sorted(sources.items()):
        if not path.startswith('handyrl_tpu/') \
                or path.startswith(_EXCLUDED_PREFIXES):
            continue
        try:
            tree = ast.parse(src.text)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            lit = _first_str_arg(node)
            if name in _METRIC_CALLS and lit:
                metrics.setdefault(lit, (path, node.lineno))
                for kw in node.keywords:
                    if kw.arg == 'stage' and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        stages.setdefault(kw.value.value, (path, node.lineno))
            elif name in _STAGE_CALLS and lit:
                stages.setdefault(lit, (path, node.lineno))
        # the canonical ingest vocabulary constant (telemetry.INGEST_STAGES)
        if path.endswith('telemetry.py'):
            for node in ast.walk(tree):
                if isinstance(node, ast.Assign) \
                        and any(isinstance(t, ast.Name)
                                and t.id == 'INGEST_STAGES'
                                for t in node.targets):
                    for elt in getattr(node.value, 'elts', []):
                        if isinstance(elt, ast.Constant):
                            stages.setdefault(str(elt.value),
                                              (path, node.lineno))
    return metrics, stages


def collect_alert_names(sources: Dict[str, SourceFile]
                        ) -> Dict[str, Tuple[str, int]]:
    """Alert-rule names: the builtin catalog (telemetry.BUILTIN_ALERTS
    rule dicts' ``name`` values) plus ``name=`` literals handed to
    AlertRule/alert-rule dict constructions at call sites. Each one is an
    operator-facing identifier (``alerts_active{alert=}`` label values,
    metrics_jsonl ``alerts.active`` entries) and must be documented."""
    names: Dict[str, Tuple[str, int]] = {}
    for path, src in sorted(sources.items()):
        if not path.endswith('telemetry.py') \
                or not path.startswith('handyrl_tpu/') \
                or path.startswith(_EXCLUDED_PREFIXES):
            continue
        try:
            tree = ast.parse(src.text)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) \
                    or not any(isinstance(t, ast.Name)
                               and t.id == 'BUILTIN_ALERTS'
                               for t in node.targets):
                continue
            for elt in getattr(node.value, 'elts', []):
                if not isinstance(elt, ast.Dict):
                    continue
                for k, v in zip(elt.keys, elt.values):
                    if isinstance(k, ast.Constant) and k.value == 'name' \
                            and isinstance(v, ast.Constant) \
                            and isinstance(v.value, str):
                        names.setdefault(v.value, (path, k.lineno))
    return names


# ---------------------------------------------------------------------------
# docs parsing


def _doc_line_of(doc: SourceFile, token: str) -> int:
    needle = '`%s`' % token
    for i, line in enumerate(doc.lines, start=1):
        if needle in line:
            return i
    return 1


def _table_first_cells(doc: SourceFile, section_match=None) -> List[str]:
    """Backticked tokens from the first cell of markdown table rows,
    optionally restricted to sections whose heading matches."""
    tokens: List[str] = []
    in_section = section_match is None
    for line in doc.lines:
        if line.startswith('#'):
            if section_match is not None:
                in_section = bool(section_match(line))
            continue
        if not in_section or not line.startswith('|'):
            continue
        cells = line.split('|')
        if len(cells) < 2:
            continue
        first = cells[1]
        if set(first.strip()) <= set('-: '):
            continue
        tokens.extend(_BACKTICK_RE.findall(first))
    return tokens


# ---------------------------------------------------------------------------
# config.py defaults + validate() knob extraction (pure AST, no import)


def _literal_keys(node: ast.Dict, prefix: str = ''
                  ) -> List[Tuple[str, bool]]:
    """[(dotted key, is_container)]: a container key (dict-valued block
    like ``inference``) is a namespace — its children need doc rows, the
    block name itself does not."""
    keys: List[Tuple[str, bool]] = []
    for k, v in zip(node.keys, node.values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            continue
        name = prefix + k.value
        is_container = isinstance(v, ast.Dict) and bool(v.keys)
        keys.append((name, is_container))
        if isinstance(v, ast.Dict):
            keys.extend(_literal_keys(v, name + '.'))
    return keys


def _aux_block_keys(sources: Dict[str, 'SourceFile']
                    ) -> List[Tuple[str, bool]]:
    """The ``telemetry`` block's canonical defaults live in
    telemetry.TELEMETRY_DEFAULTS (config.py keeps the legacy bool); fold
    them in as ``telemetry.<key>`` knobs."""
    src = sources.get('handyrl_tpu/telemetry.py')
    if src is None:
        return []
    try:
        tree = ast.parse(src.text)
    except SyntaxError:
        return []
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            value = node.value
            if any(isinstance(t, ast.Name) and t.id == 'TELEMETRY_DEFAULTS'
                   for t in targets) and isinstance(value, ast.Dict):
                return _literal_keys(value, 'telemetry.')
    return []


def collect_config_keys(config_src: SourceFile
                        ) -> Tuple[List[str], List[Tuple[str, int]]]:
    """([(dotted default key, is_container)], [(validated key literal,
    line), ...])."""
    try:
        tree = ast.parse(config_src.text)
    except SyntaxError:
        return [], []
    keys: List[Tuple[str, bool]] = []
    validated: List[Tuple[str, int]] = []
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Name) \
                        and tgt.id in ('TRAIN_DEFAULTS', 'WORKER_DEFAULTS') \
                        and isinstance(node.value, ast.Dict):
                    keys.extend(_literal_keys(node.value))
        if isinstance(node, ast.FunctionDef) and node.name == 'validate':
            # knob references through the block aliases validate() uses
            _BLOCKS = {'ta', 'ft', 'inf', 'g', 'tel', 'par', 'srv', 'flt',
                       'lg', 'gen'}
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr == 'get' \
                        and isinstance(sub.func.value, ast.Name) \
                        and sub.func.value.id in _BLOCKS:
                    lit = _first_str_arg(sub)
                    if lit:
                        validated.append((lit, sub.lineno))
                elif isinstance(sub, ast.Subscript) \
                        and isinstance(sub.value, ast.Name) \
                        and sub.value.id in _BLOCKS \
                        and isinstance(sub.slice, ast.Constant) \
                        and isinstance(sub.slice.value, str):
                    validated.append((sub.slice.value, sub.lineno))
    return keys, validated


# ---------------------------------------------------------------------------
# the check


OBSERVABILITY_DOC = 'docs/observability.md'
PARAMETERS_DOC = 'docs/parameters.md'
CONFIG_PATH = 'handyrl_tpu/config.py'


def check_gl005(sources: Dict[str, SourceFile]) -> List[Finding]:
    obs = sources.get(OBSERVABILITY_DOC)
    params = sources.get(PARAMETERS_DOC)
    config = sources.get(CONFIG_PATH)
    out: List[Finding] = []
    if obs is None or params is None or config is None:
        return out     # partial fixture trees check what they provide

    source_blob = '\n'.join(
        s.text for p, s in sources.items()
        if p.startswith('handyrl_tpu/') and not p.startswith(_EXCLUDED_PREFIXES))
    metrics, stages = collect_code_vocabulary(sources)
    doc_tokens: Set[str] = _doc_tokens(obs)

    # code -> doc: every metric/stage literal must be documented
    for name, (path, line) in sorted(metrics.items()):
        if name not in doc_tokens:
            src = sources[path]
            out.append(src.finding(
                'GL005', line,
                'metric %r is emitted here but has no row in '
                'docs/observability.md — document it or drop it' % name))
    for name, (path, line) in sorted(stages.items()):
        if name not in doc_tokens:
            src = sources[path]
            out.append(src.finding(
                'GL005', line,
                'stage %r is recorded here but missing from the '
                'docs/observability.md stage glossary' % name))

    # alert rules -> doc: every builtin alert name is an operator-facing
    # identifier (alerts_active{alert=} label, metrics_jsonl alerts.active
    # entry) and must appear in docs/observability.md
    alerts = collect_alert_names(sources)
    for name, (path, line) in sorted(alerts.items()):
        if name not in doc_tokens:
            src = sources[path]
            out.append(src.finding(
                'GL005', line,
                'alert rule %r is defined here but has no row in the '
                'docs/observability.md alert catalog' % name))

    # doc -> alert rules: alert-catalog rows must name a real rule
    alert_rows = _table_first_cells(
        obs, lambda h: 'alert catalog' in h.lower())
    for name in sorted(set(alert_rows)):
        if name not in alerts and name not in source_blob:
            out.append(obs.finding(
                'GL005', _doc_line_of(obs, name),
                'documented alert %r matches no rule in '
                'telemetry.BUILTIN_ALERTS — stale doc row' % name))

    # doc -> code: catalog rows must correspond to something emitted
    def _in_code(name: str) -> bool:
        if name in source_blob:
            return True
        # names assembled at runtime: gauge(key + '_mean')
        return name.endswith('_mean') and name[:-5] in source_blob

    catalog = _table_first_cells(
        obs, lambda h: 'Metric catalog' in h or 'stage glossary' in h.lower()
        or 'Span stage glossary' in h)
    for name in sorted(set(catalog)):
        if not _in_code(name):
            out.append(obs.finding(
                'GL005', _doc_line_of(obs, name),
                'documented metric/stage %r is emitted nowhere in '
                'handyrl_tpu/ — stale doc row' % name))

    # config defaults -> parameters doc
    keys, validated = collect_config_keys(config)
    keys = keys + _aux_block_keys(sources)
    param_tokens: Set[str] = _doc_tokens(params)
    flat_names = {k.split('.')[-1] for k, _c in keys} \
        | {k for k, _c in keys}
    def _config_line_of(bare: str) -> int:
        needle = "'%s':" % bare
        for i, line in enumerate(config.lines, start=1):
            if needle in line:
                return i
        return 1

    for key in sorted(k for k, container in keys if not container):
        bare = key.split('.')[-1]
        if key not in param_tokens and bare not in param_tokens:
            out.append(config.finding(
                'GL005', _config_line_of(bare),
                'config knob %r has a default but no docs/parameters.md '
                'row — operators cannot discover it' % key))

    # parameters doc -> config defaults (train_args / worker_args tables)
    def _param_section(heading: str) -> bool:
        return 'train_args' in heading or 'worker_args' in heading \
            or 'extensions' in heading.lower()

    for name in sorted(set(_table_first_cells(params, _param_section))):
        if name not in flat_names:
            out.append(params.finding(
                'GL005', _doc_line_of(params, name),
                'documented knob %r has no default in config.py — stale '
                'doc row or missing default' % name))

    # validate() must only reference known knobs
    for lit, line in validated:
        if lit not in flat_names:
            out.append(config.finding(
                'GL005', line,
                'validate() references %r which is not a known config '
                'knob — typo or a rule checking nothing' % lit))
    return out
