"""graftlint AST checkers GL001-GL004.

Each checker is a small visitor over ``ast`` producing
:class:`~.core.Finding` objects with a stable rule id. Scoping is by
repo-relative path suffix (``SCOPE_*`` below), so test fixtures placed
under a temporary tree with the same shape exercise the same rules.

GL001 — determinism. Episode records are pure functions of
``(seed, sample_key, params)`` (the PR 5 byte-identity contract); a raw
``random.*`` or global ``np.random.*`` draw, or a wall-clock read, inside
a record-producing path silently breaks replayability. Explicitly seeded
constructions (``random.Random(s)``, ``np.random.default_rng(seq)``,
``np.random.RandomState(s)``) and ``random.seed`` are allowed — they
*establish* determinism rather than consuming hidden global state.

GL002 — host-sync. The train step performs no extra host syncs (the PR 4
on-device guard rides the existing lazy metric fetch); a stray ``.item()``
/ ``float()`` / ``np.asarray`` inside a jit/shard_map-compiled function
forces a blocking device sync per step. Traced functions are found by: ``@jax.jit``-style decorators, names
passed to ``jax.jit``/``shard_map``/``pjit`` (including names returned by a
locally-defined builder whose call is jitted), lexical nesting inside a
traced function, and transitive closure over same-module-set calls.

GL003 — atomic-write. Durable files (checkpoints, metrics, traces) must go
through ``utils/fs.py`` (temp+fsync+rename, CRC sidecars, O_APPEND JSONL):
a raw write-mode ``open()`` anywhere in the package is a torn-file bug
waiting for a preemption (PRs 2/4). ``utils/fs.py`` itself is the one
sanctioned implementation site.

GL004 — lock discipline. Fields annotated ``# guarded-by: <lock>`` must
only be touched inside a matching ``with <recv>.<lock>`` block, in
``__init__``, or in a function whose name ends with ``_locked`` (the
caller-holds-the-lock convention). Threads started in the concurrency
modules must carry ``name=`` (the runtime sanitizer attributes leaks by
name) and be daemon or joined.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Finding, SourceFile

# ---------------------------------------------------------------------------
# rule scopes (repo-relative posix path suffixes)

SCOPE_GL001 = (
    'handyrl_tpu/generation.py',
    'handyrl_tpu/evaluation.py',
    'handyrl_tpu/device_generation.py',
    'handyrl_tpu/agent.py',
    'handyrl_tpu/ops/batch.py',
    # the serving tier serves the SAME act/sample contract the record
    # paths replay: a hidden global draw or wall-clock read in a service
    # reply would fork records between the remote and local paths
    'handyrl_tpu/serving/registry.py',
    'handyrl_tpu/serving/service.py',
    'handyrl_tpu/serving/client.py',
)

SCOPE_GL002 = (
    'handyrl_tpu/ops/train_step.py',
    'handyrl_tpu/ops/fused_pipeline.py',
    'handyrl_tpu/ops/losses.py',
    'handyrl_tpu/ops/targets.py',
    'handyrl_tpu/ops/replay.py',
    'handyrl_tpu/device_generation.py',
    # the NamedSharding/pjit entry points: the partition-rule-built train
    # step and the mesh staging helpers share the no-host-sync contract
    'handyrl_tpu/parallel/partition.py',
    'handyrl_tpu/parallel/mesh.py',
    # the serving tier dispatches compiled forwards through the engines it
    # hosts; any jitted code it grows inherits the no-host-sync contract
    'handyrl_tpu/serving/registry.py',
    'handyrl_tpu/serving/service.py',
    'handyrl_tpu/serving/client.py',
)

SCOPE_GL003_EXEMPT = (
    'handyrl_tpu/utils/fs.py',
)

SCOPE_GL004 = (
    'handyrl_tpu/connection.py',
    'handyrl_tpu/worker.py',
    'handyrl_tpu/inference.py',
    'handyrl_tpu/fault.py',
    'handyrl_tpu/telemetry.py',
    # the service's pending-request book and handle maps are shared by the
    # dispatch thread and every engine thread; the registry's manifest
    # cache by arbitrary resolver threads
    'handyrl_tpu/serving/registry.py',
    'handyrl_tpu/serving/service.py',
    'handyrl_tpu/serving/client.py',
)


def in_scope(path: str, suffixes: Iterable[str]) -> bool:
    return any(path.endswith(s) for s in suffixes)


def _parse(src: SourceFile) -> Optional[ast.Module]:
    try:
        return ast.parse(src.text)
    except SyntaxError:
        return None


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ''


# ---------------------------------------------------------------------------
# GL001 — determinism


_RANDOM_ALLOWED = {'Random', 'SystemRandom', 'seed', 'getstate', 'setstate'}
_NP_RANDOM_ALLOWED = {'default_rng', 'RandomState', 'Generator',
                      'SeedSequence', 'PCG64', 'Philox'}
_WALL_CLOCK = {'time', 'time_ns'}


def check_gl001(src: SourceFile) -> List[Finding]:
    tree = _parse(src)
    if tree is None:
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not isinstance(fn, ast.Attribute):
            continue
        # random.<draw>(...)
        if isinstance(fn.value, ast.Name) and fn.value.id == 'random':
            if fn.attr not in _RANDOM_ALLOWED:
                out.append(src.finding(
                    'GL001', node.lineno,
                    'process-global random.%s() in a record-producing path; '
                    'derive the draw from the task sample_key via '
                    'generation.sample_seed/masked_sample' % fn.attr))
            continue
        # np.random.<draw>(...) / numpy.random.<draw>(...)
        if (isinstance(fn.value, ast.Attribute) and fn.value.attr == 'random'
                and isinstance(fn.value.value, ast.Name)
                and fn.value.value.id in ('np', 'numpy')):
            if fn.attr not in _NP_RANDOM_ALLOWED:
                out.append(src.finding(
                    'GL001', node.lineno,
                    'global np.random.%s() in a record-producing path; use '
                    'an explicitly seeded np.random.default_rng' % fn.attr))
            continue
        # time.time() / time.time_ns() — wall clock in record data
        if (isinstance(fn.value, ast.Name) and fn.value.id == 'time'
                and fn.attr in _WALL_CLOCK):
            out.append(src.finding(
                'GL001', node.lineno,
                'wall-clock time.%s() in a record-producing path; records '
                'must replay bit-identically (use time.perf_counter for '
                'pure timing)' % fn.attr))
    return out


# ---------------------------------------------------------------------------
# GL002 — host syncs inside compiled code


_JIT_CALLEES = {'jit', 'pjit', 'shard_map', 'pmap'}


def _is_jit_callable(fn: ast.AST) -> bool:
    """jax.jit / jit / jax.experimental.pjit.pjit / shard_map / partial(jit)"""
    if isinstance(fn, ast.Name):
        return fn.id in _JIT_CALLEES
    if isinstance(fn, ast.Attribute):
        return fn.attr in _JIT_CALLEES
    if isinstance(fn, ast.Call):   # partial(jax.jit, ...) / partial(shard_map)
        fname = fn.func
        is_partial = (isinstance(fname, ast.Name) and fname.id == 'partial') \
            or (isinstance(fname, ast.Attribute) and fname.attr == 'partial')
        if is_partial and fn.args:
            return _is_jit_callable(fn.args[0])
    return False


class _FnInfo:
    __slots__ = ('node', 'name', 'parent', 'calls', 'returned_names')

    def __init__(self, node, name, parent):
        self.node = node
        self.name = name
        self.parent = parent               # enclosing _FnInfo or None
        self.calls: Set[str] = set()       # simple names called in the body
        self.returned_names: Set[str] = set()


def _collect_functions(tree: ast.Module) -> List[_FnInfo]:
    """Every def/lambda with its enclosing function, called names, and the
    simple names it returns (builder pattern: ``return update``)."""
    infos: List[_FnInfo] = []

    def visit(node, parent):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            info = _FnInfo(node, getattr(node, 'name', '<lambda>'), parent)
            infos.append(info)
            body = node.body if isinstance(node.body, list) else [node.body]
            for stmt in body:
                _scan_body(stmt, info)
            for child in ast.iter_child_nodes(node):
                visit(child, info)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, parent)

    def _scan_body(node, info):
        """Record calls/returns in this function, not in nested defs."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            info.calls.add(node.func.id)
        if isinstance(node, ast.Return):
            if isinstance(node.value, ast.Name):
                info.returned_names.add(node.value.id)
            elif isinstance(node.value, ast.Tuple):
                for elt in node.value.elts:
                    if isinstance(elt, ast.Name):
                        info.returned_names.add(elt.id)
        for child in ast.iter_child_nodes(node):
            _scan_body(child, info)

    visit(tree, None)
    return infos


def _jit_root_names(tree: ast.Module, infos: List[_FnInfo]
                    ) -> Tuple[Set[str], Set[ast.AST]]:
    """(names passed to jit-like calls, decorated/lambda nodes)."""
    names: Set[str] = set()
    nodes: Set[ast.AST] = set()
    by_name: Dict[str, List[_FnInfo]] = {}
    for info in infos:
        by_name.setdefault(info.name, []).append(info)

    for info in infos:
        for dec in getattr(info.node, 'decorator_list', []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _is_jit_callable(target) or _is_jit_callable(dec):
                nodes.add(info.node)

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jit_callable(node.func)):
            continue
        for arg in node.args[:1]:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            elif isinstance(arg, ast.Lambda):
                nodes.add(arg)
            elif (isinstance(arg, ast.Call)
                  and isinstance(arg.func, ast.Name)):
                # jax.jit(build(...)): whatever the local builder returns
                for binfo in by_name.get(arg.func.id, []):
                    names.update(binfo.returned_names)
    return names, nodes


def _traced_functions(trees: Dict[str, ast.Module]
                      ) -> Dict[str, Set[ast.AST]]:
    """Per-path set of function nodes considered traced, closed over the
    whole GL002 module set (jitted code in train_step calls into losses)."""
    all_infos: Dict[str, List[_FnInfo]] = {}
    root_names: Set[str] = set()
    root_nodes: Set[ast.AST] = set()
    for path, tree in trees.items():
        infos = _collect_functions(tree)
        all_infos[path] = infos
        names, nodes = _jit_root_names(tree, infos)
        root_names |= names
        root_nodes |= nodes

    by_name: Dict[str, List[Tuple[str, _FnInfo]]] = {}
    for path, infos in all_infos.items():
        for info in infos:
            by_name.setdefault(info.name, []).append((path, info))

    traced: Set[int] = set()           # id(info)
    worklist: List[Tuple[str, _FnInfo]] = []
    for path, infos in all_infos.items():
        for info in infos:
            if info.name in root_names or info.node in root_nodes:
                worklist.append((path, info))
    while worklist:
        path, info = worklist.pop()
        if id(info) in traced:
            continue
        traced.add(id(info))
        # lexically nested defs trace with their parent
        for cpath, cinfo in ((path, i) for i in all_infos[path]
                             if i.parent is info):
            worklist.append((cpath, cinfo))
        # names the body calls resolve across the module set
        for called in info.calls:
            for tpath, tinfo in by_name.get(called, []):
                worklist.append((tpath, tinfo))

    out: Dict[str, Set[ast.AST]] = {}
    for path, infos in all_infos.items():
        out[path] = {i.node for i in infos if id(i) in traced}
    return out


_SYNC_COERCIONS = {'float', 'int', 'bool'}
_NP_SYNC = {'asarray', 'array'}


def _jnp_rooted(node: ast.AST) -> bool:
    """True for an expression rooted at jnp/jax.numpy/jax.lax."""
    while isinstance(node, (ast.Attribute, ast.Call, ast.Subscript)):
        node = getattr(node, 'func', None) or getattr(node, 'value', None)
        if node is None:
            return False
    return isinstance(node, ast.Name) and node.id == 'jnp'


def _check_traced_body(src: SourceFile, fn_node: ast.AST,
                       out: List[Finding], seen: Set[int]):
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if id(node) in seen:
                continue
            seen.add(id(node))
            if isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Attribute):
                    if fn.attr == 'item' and not node.args:
                        out.append(src.finding(
                            'GL002', node.lineno,
                            '.item() inside a compiled function forces a '
                            'device->host sync per step'))
                    elif (fn.attr in _NP_SYNC
                          and isinstance(fn.value, ast.Name)
                          and fn.value.id in ('np', 'numpy')):
                        out.append(src.finding(
                            'GL002', node.lineno,
                            'np.%s() inside a compiled function '
                            'materializes the traced value on host; use '
                            'jnp ops' % fn.attr))
                    elif (fn.attr == 'device_get'
                          and isinstance(fn.value, ast.Name)
                          and fn.value.id == 'jax'):
                        out.append(src.finding(
                            'GL002', node.lineno,
                            'jax.device_get() inside a compiled function '
                            'is a host sync'))
                elif (isinstance(fn, ast.Name)
                      and fn.id in _SYNC_COERCIONS and node.args
                      and not isinstance(node.args[0], ast.Constant)):
                    out.append(src.finding(
                        'GL002', node.lineno,
                        '%s() coercion of a traced value inside a compiled '
                        'function syncs to host; keep it a device scalar '
                        '(jnp.float32/astype) or hoist to build time'
                        % fn.id))
            elif isinstance(node, (ast.If, ast.While)):
                if _jnp_rooted(node.test):
                    out.append(src.finding(
                        'GL002', node.lineno,
                        'python branching on a traced value (implicit '
                        'bool()) inside a compiled function; use jnp.where '
                        'or lax.cond'))


def check_gl002(sources: Dict[str, SourceFile]) -> List[Finding]:
    """Cross-module check over every GL002-scoped source in ``sources``."""
    scoped = {p: s for p, s in sources.items() if in_scope(p, SCOPE_GL002)}
    trees = {p: t for p, s in scoped.items()
             if (t := _parse(s)) is not None}
    traced = _traced_functions(trees)
    out: List[Finding] = []
    for path, nodes in traced.items():
        seen: Set[int] = set()
        # check outermost traced functions first so nested nodes dedupe
        for node in sorted(nodes, key=lambda n: n.lineno):
            _check_traced_body(scoped[path], node, out, seen)
    return out


# ---------------------------------------------------------------------------
# GL003 — raw write-mode open()


def _mode_of(call: ast.Call) -> Optional[str]:
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant) \
            and isinstance(call.args[1].value, str):
        return call.args[1].value
    for kw in call.keywords:
        if kw.arg == 'mode' and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def check_gl003(src: SourceFile) -> List[Finding]:
    if in_scope(src.path, SCOPE_GL003_EXEMPT):
        return []
    tree = _parse(src)
    if tree is None:
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == 'open'):
            continue
        mode = _mode_of(node)
        if mode and any(c in mode for c in 'wax+'):
            out.append(src.finding(
                'GL003', node.lineno,
                "open(..., %r): durable writes must route through "
                "utils/fs.py (atomic_write_bytes / checksummed_write_bytes "
                "/ append_jsonl) — a raw write dies torn under preemption"
                % mode))
    return out


# ---------------------------------------------------------------------------
# GL004 — guarded-by lock discipline + thread accounting


_GUARDED_BY_RE = re.compile(r'#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_.]*)')


def _guarded_fields(src: SourceFile, tree: ast.Module) -> Dict[str, str]:
    """field name -> lock attribute, from ``self.<field> = ...`` assignments
    whose line (or the line above) carries ``# guarded-by: <lock>``."""
    fields: Dict[str, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for tgt in targets:
            if not (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)):
                continue
            for cand in (node.lineno, node.lineno - 1):
                line = src.lines[cand - 1] if 1 <= cand <= len(src.lines) \
                    else ''
                if cand != node.lineno and not line.strip().startswith('#'):
                    continue   # the line above counts only as a pure comment
                m = _GUARDED_BY_RE.search(line)
                if m:
                    lock = m.group(1)
                    fields[tgt.attr] = lock[5:] if lock.startswith('self.') \
                        else lock
                    break
    return fields


def _enclosing_with_locks(stack: List[ast.AST]) -> Set[str]:
    """Unparsed context-manager expressions of every enclosing ``with``."""
    locks: Set[str] = set()
    for node in stack:
        if isinstance(node, ast.With):
            for item in node.items:
                locks.add(_unparse(item.context_expr))
    return locks


def check_gl004(src: SourceFile) -> List[Finding]:
    tree = _parse(src)
    if tree is None:
        return []
    fields = _guarded_fields(src, tree)
    out: List[Finding] = []

    # -- guarded field accesses --
    def walk(node, stack, fn_stack):
        for child in ast.iter_child_nodes(node):
            new_fn_stack = fn_stack
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                new_fn_stack = fn_stack + [child.name]
            if (fields and isinstance(child, ast.Attribute)
                    and child.attr in fields):
                recv = _unparse(child.value)
                lock = fields[child.attr]
                exempt = any(fn == '__init__' or fn.endswith('_locked')
                             for fn in new_fn_stack)
                held = _enclosing_with_locks(stack + [node])
                want = '%s.%s' % (recv, lock)
                if not exempt and want not in held:
                    out.append(src.finding(
                        'GL004', child.lineno,
                        '%s.%s is guarded-by %s but accessed outside '
                        '"with %s" (allowed: __init__, *_locked helpers, '
                        'or an allow pragma with a reason)'
                        % (recv, child.attr, lock, want)))
            walk(child, stack + [child], new_fn_stack)

    walk(tree, [], [])

    # -- thread accounting --
    has_join = '.join(' in src.text
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        is_thread = (isinstance(fn, ast.Attribute) and fn.attr == 'Thread'
                     and isinstance(fn.value, ast.Name)
                     and fn.value.id == 'threading') \
            or (isinstance(fn, ast.Name) and fn.id == 'Thread')
        if not is_thread:
            continue
        kwargs = {kw.arg for kw in node.keywords if kw.arg}
        if 'name' not in kwargs:
            out.append(src.finding(
                'GL004', node.lineno,
                'threading.Thread(...) without name=: the sanitizer and '
                'crash logs cannot attribute an anonymous thread'))
        daemon = any(kw.arg == 'daemon' and isinstance(kw.value, ast.Constant)
                     and kw.value.value is True for kw in node.keywords)
        if not daemon and not has_join:
            out.append(src.finding(
                'GL004', node.lineno,
                'non-daemon thread started but nothing in this module '
                'joins it: join it, mark it daemon, or pragma why'))
    return out


# unique line-dedup for findings produced by overlapping walks
def dedupe(findings: List[Finding]) -> List[Finding]:
    seen: Set[Tuple[str, str, int, str]] = set()
    out = []
    for f in findings:
        k = (f.rule, f.path, f.line, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out
