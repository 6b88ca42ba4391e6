"""Unified telemetry: metric registry, span timing, leveled logging, exporter.

One observability layer for the whole fleet (the Podracer lesson: scaling an
IMPALA-style learner/actor system is gated on *seeing* where time and
throughput go across processes). Four pieces, all stdlib-only:

* **MetricRegistry** — process-local labeled counters, gauges, and
  fixed-bucket histograms (p50/p95/p99 summaries). Thread-safe, and
  near-zero cost when disabled (``HANDYRL_TPU_TELEMETRY=0`` or the
  ``telemetry: false`` config knob): every mutator is a single flag check.
  ``snapshot()`` returns a plain-data dict that survives the msgpack wire
  codec, so worker and gather processes piggyback their registries on the
  existing heartbeat frames and the learner merges them fleet-wide
  (``merge_snapshots``: counters sum, gauges sum, histogram buckets add).

* **Spans** — ``trace_span``, the one timed-section primitive: name, start
  and end on ``time.perf_counter``, a span id and the id of the enclosing
  span, flat attributes. Finished spans sit in a bounded in-memory ring
  (``spans()``), feed the ``stage_seconds{stage=...}`` histogram family
  and the trace file, and while open are ``handyrl:<name>`` annotations
  in any jax profiler session. The stage vocabulary subsumes the ingest
  StageTimer's canonical names (``INGEST_STAGES``): a live epoch timing
  line and an exported histogram speak the same stage language.

* **Leveled logger** — ``get_logger()``; verbosity from
  ``HANDYRL_TPU_LOG_LEVEL`` (debug/info/warning/error, default info).
  Replaces the scattered bare ``print()`` status lines whose partial writes
  interleave mid-line across the process tree. The reference-format result
  lines (epoch / win rate / loss / updated model) stay on stdout — plot
  tooling parses those.

* **TelemetryExporter** — optional Prometheus-text-format HTTP endpoint
  (stdlib http.server; ``telemetry_port`` config knob, off by default)
  serving the learner's local registry plus the latest merged fleet
  snapshot. A busy port is retried and then falls back to an ephemeral
  one — an occupied port must never take the learner down.

* **Distributed tracing** — episode-lifecycle spans across the whole fleet
  (``HANDYRL_TPU_TRACE=<dir>`` or the ``telemetry.trace_dir`` knob). Every
  process appends Chrome-trace "complete" events (wall-clock microseconds,
  pid/tid, ``args.trace_id``) to ONE shared JSONL per run via single
  ``O_APPEND`` writes; the learner collates a valid Chrome/Perfetto JSON at
  shutdown and ``scripts/trace_report.py`` reduces either file to a
  generation→gradient critical-path summary. The trace context is the
  ``trace_id`` derived from the server-stamped task (``role`` +
  ``sample_key``): it rides the existing task/episode payloads through
  every hop — no new wire fields — so spans from the learner (task_assign,
  ingest, train_step), the gather (upload, engine_batch) and the workers
  (generate) link up by id. Sampling is DETERMINISTIC per trace_id
  (``telemetry.trace_sample_rate``): every process makes the same keep/drop
  decision for an episode without coordination. Span durations also land in
  the ``stage_seconds{stage=...}`` histogram family, so the trace file, the
  metrics registry and the timing lines share one stage vocabulary. Off
  (the default) every trace call is a single falsy-string check.
"""

from __future__ import annotations

import atexit
import bisect
import itertools
import json
import logging
import os
import random
import re
import resource
import statistics
import sys
import threading
import time
import uuid
import zlib
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# enable/disable switch (near-zero cost when off)

_ENABLED = os.environ.get('HANDYRL_TPU_TELEMETRY', '1').strip().lower() \
    not in ('0', 'false', 'off')


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool):
    """Flip collection globally; mirrored into the environment so spawned
    children (batchers, gathers, workers) inherit the choice."""
    global _ENABLED
    _ENABLED = bool(flag)
    os.environ['HANDYRL_TPU_TELEMETRY'] = '1' if _ENABLED else '0'


# the flight recorder rides the same master switch but also has its own
_RECORDER_ON = True


def set_recorder_enabled(flag: bool):
    global _RECORDER_ON
    _RECORDER_ON = bool(flag)


# ---------------------------------------------------------------------------
# run id: one identity for every record/span of a training run

_RUN_ID = os.environ.get('HANDYRL_TPU_RUN_ID') or uuid.uuid4().hex[:12]


def run_id() -> str:
    return _RUN_ID


def set_run_id(rid: Optional[str]):
    """Adopt the learner's run id (workers receive it in the merged config);
    mirrored into the environment so spawned children inherit it."""
    global _RUN_ID
    if rid:
        _RUN_ID = str(rid)
        os.environ['HANDYRL_TPU_RUN_ID'] = _RUN_ID


# ---------------------------------------------------------------------------
# distributed tracing (Chrome-trace events over one shared per-run JSONL)

# Default per-config knobs for the ``telemetry`` block (a bare bool in the
# config is accepted as {'enabled': <bool>} for back-compat).
TELEMETRY_DEFAULTS: Dict[str, Any] = {
    'enabled': True, 'trace_dir': '', 'trace_sample_rate': 1.0,
    'blackbox_dir': 'blackbox', 'recorder_events': 256,
    'metrics_rotate_mb': 0, 'alerts': {},
    # compiled-performance plane (docs/observability.md): device-memory
    # gauges, the retrace sentinel, and the host-block decomposition
    'perf_plane': True, 'retrace': 'warn', 'retrace_warmup_epochs': 1}


def config_block(args: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Normalize the ``telemetry`` config knob: bool (legacy collection
    switch) or a block with ``enabled`` / ``trace_dir`` /
    ``trace_sample_rate``."""
    raw = (args or {}).get('telemetry', True)
    if isinstance(raw, dict):
        out = dict(TELEMETRY_DEFAULTS)
        out.update(raw)
        return out
    return {**TELEMETRY_DEFAULTS, 'enabled': bool(raw)}


class _TraceState:
    """Per-process trace sink: destination dir, sample rate, event buffer."""

    def __init__(self):
        self.dir = os.environ.get('HANDYRL_TPU_TRACE', '').strip()
        rate = os.environ.get('HANDYRL_TPU_TRACE_RATE', '').strip()
        try:
            self.rate = min(1.0, max(0.0, float(rate))) if rate else 1.0
        except ValueError:
            self.rate = 1.0
        self.label = 'proc'
        self.lock = threading.Lock()
        # event buffer + its one-shot metadata flag share the sink lock
        # (lexical discipline checked by graftlint GL004; *_locked helpers
        # are called with it held)
        self.buf: List[str] = []          # guarded-by: lock
        self.meta_done = False            # guarded-by: lock


_TRACE = _TraceState()
_TRACE_FLUSH_AT = 128      # buffered events per O_APPEND write


def trace_enabled() -> bool:
    return bool(_TRACE.dir)


def trace_dir() -> str:
    return _TRACE.dir


def trace_sample_rate() -> float:
    return _TRACE.rate


def configure_tracing(trace_dir: Optional[str] = None,
                      sample_rate: Optional[float] = None,
                      force: bool = False):
    """Adopt trace settings from the run config, mirrored into the
    environment so spawned children (batchers, gathers, workers) inherit
    them. An operator-set ``HANDYRL_TPU_TRACE`` / ``HANDYRL_TPU_TRACE_RATE``
    wins over config values unless ``force`` (tests, the services' runtime
    'trace' op)."""
    if sample_rate is not None and (force or
                                    not os.environ.get('HANDYRL_TPU_TRACE_RATE')):
        _TRACE.rate = min(1.0, max(0.0, float(sample_rate)))
        os.environ['HANDYRL_TPU_TRACE_RATE'] = '%g' % _TRACE.rate
    if trace_dir is not None and (force or
                                  not os.environ.get('HANDYRL_TPU_TRACE')):
        trace_flush()
        with _TRACE.lock:   # a racing trace_event must not emit its meta
            _TRACE.dir = str(trace_dir).strip()   # line into the old sink
            _TRACE.meta_done = False
        os.environ['HANDYRL_TPU_TRACE'] = _TRACE.dir


def set_process_label(label: str):
    """Human-readable process name for the trace viewer's process rows
    (learner / gather-N / worker-N / batcher-N)."""
    _TRACE.label = str(label)


def adopt_config(args: Optional[Dict[str, Any]]):
    """One call for every process that receives the merged run config:
    run id, the collection switch, the trace destination/sampling, and the
    flight-recorder geometry."""
    args = args or {}
    set_run_id(args.get('run_id'))
    tel = config_block(args)
    if not tel.get('enabled', True):
        set_enabled(False)
    configure_tracing(tel.get('trace_dir') or None,
                      tel.get('trace_sample_rate'))
    configure_recorder(tel.get('recorder_events'),
                       tel.get('blackbox_dir'))
    configure_perf_plane(tel.get('perf_plane'), tel.get('retrace'))


def episode_trace_id(task_args: Optional[Dict[str, Any]]) -> Optional[str]:
    """The trace context: derived from the server-stamped task identity
    (``role`` + ``sample_key``), so every process holding the task or an
    episode/result payload built from it computes the SAME id with no new
    wire fields. None when the payload carries no sample_key (local
    fallback streams, pre-ledger peers)."""
    if not isinstance(task_args, dict):
        return None
    skey = task_args.get('sample_key')
    if skey is None:
        return None
    return '%s%d' % (str(task_args.get('role') or 'g'), int(skey))


_MINT_LOCK = threading.Lock()
_MINT_SEQ = [0]                       # guarded-by: _MINT_LOCK


def mint_trace_id() -> str:
    """Serving-path trace context: a fresh request-scoped id (``r<pid
    hash><seq>``), minted once at the edge (``ServiceClient.submit`` /
    a gateway ply) and carried inside the INFER/admin payload so every
    downstream hop — router, replica, engine, failover replay — stamps
    the SAME id. Unlike :func:`episode_trace_id` there is no
    server-stamped identity to recompute from, so the id itself crosses
    the wire (absent key = unsampled; old peers ignore it)."""
    with _MINT_LOCK:
        _MINT_SEQ[0] += 1
        seq = _MINT_SEQ[0]
    return 'r%x.%d' % (os.getpid() & 0xFFFFFF, seq)


def trace_sampled(trace_id) -> bool:
    """Deterministic keep/drop for one episode: hash-based on the trace_id,
    so the learner, gather and worker agree without coordination."""
    if not _TRACE.dir:
        return False
    rate = _TRACE.rate
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return (zlib.crc32(str(trace_id).encode()) % 10000) < rate * 10000


def _emit_locked(line: str):
    if not _TRACE.meta_done:
        _TRACE.meta_done = True
        _TRACE.buf.append(json.dumps(
            {'name': 'process_name', 'ph': 'M', 'pid': os.getpid(), 'tid': 0,
             'args': {'name': '%s-%d' % (_TRACE.label, os.getpid())}}))
    _TRACE.buf.append(line)
    if len(_TRACE.buf) >= _TRACE_FLUSH_AT:
        _flush_locked()


def trace_event(name: str, ts: Optional[float] = None, dur: float = 0.0,
                trace_id=None, always: bool = False, **args):
    """Record one Chrome-trace complete event ("ph": "X"; instants are
    zero-duration spans). ``ts``/``dur`` are wall-clock seconds (converted
    to the microseconds the viewers expect — wall time, so events align
    across processes). Sampling: a truthy ``trace_id`` decides
    deterministically; ``always`` bypasses (callers who already sampled);
    otherwise batch-level events sample probabilistically at the same
    rate."""
    if not _TRACE.dir:
        return
    if trace_id:
        if not trace_sampled(trace_id):
            return
        args['trace_id'] = trace_id
    elif not always:
        rate = _TRACE.rate
        if rate < 1.0 and random.random() >= rate:
            return
    args['run_id'] = _RUN_ID
    try:
        tid = threading.get_native_id()
    except AttributeError:
        tid = threading.get_ident() & 0x7FFFFFFF
    ev = {'name': name, 'cat': 'handyrl', 'ph': 'X',
          'ts': int((time.time() if ts is None else ts) * 1e6),
          'dur': max(0, int(dur * 1e6)),
          'pid': os.getpid(), 'tid': tid, 'args': args}
    with _TRACE.lock:
        _emit_locked(json.dumps(ev))


# -- spans: the one way the program times a section

# The ring has to hold one benchmark window whole, with the set-up before it
# (benchmark/readers take a counter's growth from the last record BEFORE the
# window's opening). A 51 s window of 16 ms chunks is ~3,200 loop iterations
# x 5 spans + ~1,800 epoch boundaries x 10 spans (the writer thread's
# included) = 34,000 spans, set-up's few hundred on top: the size leaves a
# factor of nearly two. Only :func:`spans` copies the ring, and only
# readers call it: nothing on the per-chunk path does.
SPAN_RING_SIZE = 65536
SPAN_ANNOTATION_PREFIX = 'handyrl:'

_SPAN_LOCK = threading.Lock()
_SPAN_RING: deque = deque(maxlen=SPAN_RING_SIZE)   # guarded-by: _SPAN_LOCK
_SPAN_IDS = itertools.count(1)
_SPAN_LOCAL = threading.local()       # .stack: this thread's open spans
_ANNOTATIONS: Optional[tuple] = None  # (TraceAnnotation, StepTraceAnnotation)


def _annotation_classes() -> tuple:
    """jax.profiler's host annotations, once jax is in the process (a
    process that never imported jax has no profiler session to appear in,
    and a span must not be what imports it)."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        if 'jax' not in sys.modules:
            return ()
        try:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation
            _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
        except ImportError:
            _ANNOTATIONS = ()
    return _ANNOTATIONS


class Span:
    """One timed section, open or finished: ``name``, ``t0`` / ``t1`` on
    ``time.perf_counter``, ``span_id``, ``parent_id`` (the enclosing open
    span of the same thread, None at the root) and flat ``attrs``. Made by
    :func:`trace_span`; read back through :func:`spans`."""

    __slots__ = ('name', 'span_id', 'parent_id', 't0', 't1', 'attrs',
                 'children', '_parent', '_note', '_t_wall', '_trace_id')

    def __init__(self, name, trace_id, step_num, attrs):
        self.name = name
        self.attrs = attrs
        self.children: List['Span'] = []       # finished direct children
        self.t0 = self.t1 = None
        self._trace_id = trace_id
        self._note = None
        classes = _annotation_classes()
        if classes:
            self._note = (classes[0](SPAN_ANNOTATION_PREFIX + name)
                          if step_num is None else
                          classes[1](SPAN_ANNOTATION_PREFIX + name,
                                     step_num=step_num))

    def set(self, **attrs):
        """Attributes read at the boundary, after the work (counters)."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def child_seconds(self, name: str) -> float:
        """Seconds spent in the finished direct children called ``name``."""
        return sum(c.t1 - c.t0 for c in self.children if c.name == name)

    def __enter__(self):
        stack = getattr(_SPAN_LOCAL, 'stack', None)
        if stack is None:
            stack = _SPAN_LOCAL.stack = []
        self._parent = stack[-1] if stack else None
        self.parent_id = self._parent.span_id if stack else None
        self.span_id = next(_SPAN_IDS)
        stack.append(self)
        self._t_wall = time.time() if _TRACE.dir else None
        if self._note is not None:
            self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(*exc)
        _SPAN_LOCAL.stack.pop()
        dt = self.t1 - self.t0
        if self._parent is not None:
            self._parent.children.append(self)
            self._parent = None     # a finished span keeps no open one alive
        with _SPAN_LOCK:
            _SPAN_RING.append(self)
        REGISTRY.observe_stage(self.name, dt)
        if self._t_wall is not None and _TRACE.dir:
            trace_event(self.name, ts=self._t_wall, dur=dt,
                        trace_id=self._trace_id, span_id=self.span_id,
                        parent_id=self.parent_id, **self.attrs)
        return False

    def record(self) -> Dict[str, Any]:
        return {'name': self.name, 't0': self.t0, 't1': self.t1,
                'span_id': self.span_id, 'parent_id': self.parent_id,
                'attrs': dict(self.attrs)}


class _NullSpan:
    """What :func:`trace_span` hands out with telemetry off."""

    __slots__ = ()
    children = ()
    t0 = t1 = None

    def set(self, **attrs):
        pass

    def child_seconds(self, name: str) -> float:
        return 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def trace_span(name: str, trace_id=None, step_num: Optional[int] = None,
               **attrs):
    """Timed section (a context manager; ``as`` gives the :class:`Span`,
    whose ``set(**attrs)`` takes what is only known after the work).

    On close the span goes to the process's bounded in-memory ring
    (:func:`spans`), its duration into the ``stage_seconds{stage=...}``
    histogram family and, when tracing is on (and the id — or the rate, for
    id-less spans — samples it), a Chrome-trace event with
    ``args.span_id`` / ``args.parent_id`` into the trace file. While open
    it is a ``jax.profiler.TraceAnnotation`` named ``handyrl:<name>`` (a
    ``StepTraceAnnotation`` when ``step_num`` is given), so any profiler
    session holds the program's spans on the device trace's timeline.
    With telemetry off: one flag check."""
    if not _ENABLED:
        return _NULL_SPAN
    return Span(name, trace_id, step_num, attrs)


def spans(name: Optional[str] = None,
          since: Optional[float] = None) -> List[Dict[str, Any]]:
    """Finished spans still in the ring, oldest first, as plain records
    (``name``, ``t0``, ``t1``, ``span_id``, ``parent_id``, ``attrs``);
    ``name`` keeps one stage, ``since`` those that ended at or after that
    ``time.perf_counter`` reading."""
    with _SPAN_LOCK:
        held = list(_SPAN_RING)
    return [s.record() for s in held
            if (name is None or s.name == name)
            and (since is None or s.t1 >= since)]


def trace_stage(stage: str, seconds: float, count: int = 1):
    """Batch-level stage event (the StageTimer mirror): one span covering
    the just-finished timed section, rate-sampled."""
    if not _TRACE.dir:
        return
    trace_event(stage, ts=time.time() - seconds, dur=seconds, count=count)


def _flush_locked():
    buf = _TRACE.buf
    if not buf or not _TRACE.dir:
        return
    _TRACE.buf = []
    try:
        os.makedirs(_TRACE.dir, exist_ok=True)
        path = os.path.join(_TRACE.dir, 'trace-%s.jsonl' % _RUN_ID)
        data = ('\n'.join(buf) + '\n').encode()
        # one O_APPEND write per flush: complete lines, atomic offset —
        # every fleet process appends to the same per-run file safely
        fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
    except OSError:
        pass   # tracing must never take the run down


def trace_flush():
    if not _TRACE.dir:
        return
    with _TRACE.lock:
        _flush_locked()


atexit.register(trace_flush)


def finalize_trace() -> Optional[str]:
    """Collate this run's JSONL event stream into a valid Chrome-trace /
    Perfetto JSON file (``<dir>/trace-<run_id>.json``); returns the path
    (None when tracing is off or nothing was recorded). Written atomically
    (temp + rename); the JSONL stays the append-forever source of truth."""
    if not _TRACE.dir:
        return None
    trace_flush()
    src = os.path.join(_TRACE.dir, 'trace-%s.jsonl' % _RUN_ID)
    events: List[Dict[str, Any]] = []
    try:
        with open(src) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue   # torn tail line from a killed process
    except OSError:
        return None
    if not events:
        return None
    out = os.path.join(_TRACE.dir, 'trace-%s.json' % _RUN_ID)
    try:
        # atomic publish through the shared fs helper (GL003): a collate
        # interrupted mid-write must not leave a half-JSON next to the
        # intact JSONL source of truth
        from .utils.fs import atomic_write_bytes
        atomic_write_bytes(out, json.dumps(
            {'traceEvents': events, 'displayTimeUnit': 'ms'}).encode('utf-8'))
    except OSError:
        return None
    return out


# ---------------------------------------------------------------------------
# leveled logger (multi-process safe: one line per record, stderr)

_LOG_CONFIGURED = False
_LOG_LOCK = threading.Lock()


def _log_level() -> int:
    name = os.environ.get('HANDYRL_TPU_LOG_LEVEL', 'info').strip().lower()
    return {'debug': logging.DEBUG, 'info': logging.INFO,
            'warning': logging.WARNING, 'warn': logging.WARNING,
            'error': logging.ERROR}.get(name, logging.INFO)


def get_logger(name: str = 'handyrl_tpu') -> logging.Logger:
    """A logger under the ``handyrl_tpu`` root, configured once per process:
    complete single lines to stderr (no more dot streams and status prints
    from N processes splicing mid-line), level from HANDYRL_TPU_LOG_LEVEL."""
    global _LOG_CONFIGURED
    root = logging.getLogger('handyrl_tpu')
    if not _LOG_CONFIGURED:
        with _LOG_LOCK:
            if not _LOG_CONFIGURED:
                handler = logging.StreamHandler(sys.stderr)
                handler.setFormatter(logging.Formatter(
                    '[%(asctime)s %(levelname).1s %(process)d %(name)s] '
                    '%(message)s', datefmt='%H:%M:%S'))
                root.addHandler(handler)
                # every leveled line also lands in the flight-recorder
                # ring, so a blackbox dump carries the process's last
                # log context alongside spans/transitions/guard trips
                root.addHandler(_RecorderLogHandler())
                root.setLevel(_log_level())
                root.propagate = False
                _LOG_CONFIGURED = True
    if name in ('', 'handyrl_tpu'):
        return root
    return root.getChild(name.replace('handyrl_tpu.', '', 1))


# ---------------------------------------------------------------------------
# flight recorder: bounded ring of recent events, dumped on abnormal death

RECORDER_EVENTS_DEFAULT = 256


class FlightRecorder:
    """Bounded in-memory ring of this process's recent events: leveled log
    lines, span completions, state-machine transitions, and guard trips.

    Every fleet process keeps one (learner, gathers, workers, inference
    supervisors, serving services, the fleet resolver). When the process
    dies abnormally — uncaught fatal error, PreemptionGuard signal,
    NonFiniteGuard abort, or a supervisor declaring a child dead — the ring
    is dumped atomically (``utils/fs``) to
    ``<blackbox_dir>/<role>-<pid>-<run_id>.json`` so
    ``scripts/postmortem.py`` can reconstruct each corpse's last seconds
    without a debugger. Recording is one deque append under a lock and
    honours the global telemetry switch (``telemetry: false`` disables it
    with the rest of the plane).
    """

    def __init__(self, capacity: int = RECORDER_EVENTS_DEFAULT):
        self._lock = threading.Lock()
        # ring + counters share one lock (graftlint GL004 discipline)
        self._events: deque = deque(maxlen=max(16, int(capacity)))  # guarded-by: _lock
        self._total = 0                 # guarded-by: _lock
        self._dumps: List[str] = []     # guarded-by: _lock

    @property
    def capacity(self) -> int:
        with self._lock:
            return self._events.maxlen or 0

    def set_capacity(self, capacity: int):
        cap = max(16, int(capacity))
        with self._lock:
            if cap != self._events.maxlen:
                self._events = deque(self._events, maxlen=cap)

    def record(self, kind: str, msg: str, **fields):
        if not (_ENABLED and _RECORDER_ON):
            return
        ev = {'t': round(time.time(), 6), 'kind': str(kind),
              'msg': str(msg)[:500]}
        if fields:
            ev.update(fields)
        with self._lock:
            self._events.append(ev)
            self._total += 1

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(ev) for ev in self._events]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            held = len(self._events)
            return {'events': held, 'total': self._total,
                    'dropped': max(0, self._total - held),
                    'capacity': self._events.maxlen,
                    'dumps': list(self._dumps)}

    def dump(self, reason: str, directory: Optional[str] = None,
             context: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Atomically write the ring (plus a summarized registry snapshot)
        to the blackbox file for this process. Returns the path, or None
        when dumping is disabled (empty dir) or the write failed — a dump
        must never take the dying process down harder."""
        directory = _BLACKBOX_DIR if directory is None else directory
        if not directory:
            return None
        role = re.sub(r'[^A-Za-z0-9_.-]', '_', _TRACE.label or 'proc')
        path = os.path.join(directory,
                            '%s-%d-%s.json' % (role, os.getpid(), _RUN_ID))
        payload = {
            'schema': 'handyrl_tpu.blackbox/1',
            'role': _TRACE.label, 'pid': os.getpid(), 'run_id': _RUN_ID,
            'reason': str(reason), 'time': round(time.time(), 6),
            'stats': self.stats(), 'events': self.events(),
            'metrics': summarize(REGISTRY.snapshot()),
        }
        if context:
            payload['context'] = context
        try:
            os.makedirs(directory, exist_ok=True)
            from .utils.fs import atomic_write_bytes
            atomic_write_bytes(path, json.dumps(payload).encode('utf-8'))
        except Exception:
            return None
        with self._lock:
            if path not in self._dumps:
                self._dumps.append(path)
        return path


class _RecorderLogHandler(logging.Handler):
    """Mirror leveled log lines into the flight-recorder ring."""

    def emit(self, record):  # noqa: D102 (logging API)
        try:
            _RECORDER.record('log', record.getMessage(),
                             level=record.levelname, logger=record.name)
        except Exception:
            pass   # the recorder must never break logging


_RECORDER = FlightRecorder(
    int(os.environ.get('HANDYRL_TPU_RECORDER_EVENTS')
        or RECORDER_EVENTS_DEFAULT))
_BLACKBOX_DIR = os.environ.get('HANDYRL_TPU_BLACKBOX', 'blackbox')


def recorder() -> FlightRecorder:
    return _RECORDER


def recorder_stats() -> Dict[str, Any]:
    return _RECORDER.stats()


def blackbox_dir() -> str:
    return _BLACKBOX_DIR


def configure_recorder(events: Optional[int] = None,
                       directory: Optional[str] = None,
                       force: bool = False):
    """Adopt recorder geometry from the run config, mirrored into the
    environment so spawned children inherit it. Operator-set
    ``HANDYRL_TPU_RECORDER_EVENTS`` / ``HANDYRL_TPU_BLACKBOX`` win over
    config values unless ``force`` (tests)."""
    global _BLACKBOX_DIR
    if events is not None and (force or
                               not os.environ.get('HANDYRL_TPU_RECORDER_EVENTS')):
        _RECORDER.set_capacity(int(events))
        os.environ['HANDYRL_TPU_RECORDER_EVENTS'] = str(_RECORDER.capacity)
    if directory is not None and (force or
                                  not os.environ.get('HANDYRL_TPU_BLACKBOX')):
        _BLACKBOX_DIR = str(directory).strip()
        os.environ['HANDYRL_TPU_BLACKBOX'] = _BLACKBOX_DIR


def record_event(kind: str, msg: str, **fields):
    """Append one event to this process's flight-recorder ring (a single
    deque append under a lock; a no-op with telemetry disabled)."""
    _RECORDER.record(kind, msg, **fields)


def dump_blackbox(reason: str, **context) -> Optional[str]:
    """Dump the flight recorder for an abnormal-death reason (fatal-error,
    preempt, nonfinite-abort, crash declarations). Idempotent per process:
    a later dump atomically replaces the earlier file with a fresher
    ring."""
    path = _RECORDER.dump(reason, context=context or None)
    if path:
        counter('blackbox_dumps_total').inc()
        get_logger('recorder').warning('blackbox dump (%s): %s',
                                       reason, path)
        trace_flush()
    return path


_CRASH_HOOK_INSTALLED = False


def install_crash_dump():
    """Chain ``sys.excepthook`` so an uncaught fatal error dumps the flight
    recorder before the traceback prints. Installed once per process at
    the fleet entry points (learner, gather, worker, serving service,
    fleet resolver). KeyboardInterrupt is left to the PreemptionGuard
    path; SystemExit never reaches the hook."""
    global _CRASH_HOOK_INSTALLED
    if _CRASH_HOOK_INSTALLED:
        return
    _CRASH_HOOK_INSTALLED = True
    prev = sys.excepthook

    def hook(tp, val, tb):
        if not issubclass(tp, KeyboardInterrupt):
            try:
                record_event('fatal', '%s: %s' % (tp.__name__, val))
                dump_blackbox('fatal-error',
                              error='%s: %s' % (tp.__name__, str(val)[:200]))
            except Exception:
                pass   # dumping must never mask the real traceback
        prev(tp, val, tb)

    sys.excepthook = hook


# ---------------------------------------------------------------------------
# metric key codec: 'name' or 'name{k="v",k2="v2"}' (label keys sorted)

_NAME_RE = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*$')


def metric_key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ','.join('%s="%s"' % (k, str(labels[k]).replace('"', "'"))
                     for k in sorted(labels))
    return '%s{%s}' % (name, inner)


def split_key(key: str) -> Tuple[str, str]:
    """('name', 'k="v",...') — the label string is '' when unlabeled."""
    if '{' not in key:
        return key, ''
    name, _, rest = key.partition('{')
    return name, rest.rstrip('}')


def relabel(snapshot: Dict[str, Any], **labels) -> Dict[str, Any]:
    """A copy of ``snapshot`` with ``labels`` appended to every metric key
    (the exporter tags the merged fleet snapshot with source="fleet")."""
    extra = ','.join('%s="%s"' % (k, v) for k, v in sorted(labels.items()))

    def rekey(key: str) -> str:
        name, inner = split_key(key)
        inner = (inner + ',' + extra) if inner else extra
        return '%s{%s}' % (name, inner)

    out = dict(snapshot)
    for section in ('counters', 'gauges'):
        out[section] = {rekey(k): v
                        for k, v in (snapshot.get(section) or {}).items()}
    out['hists'] = {rekey(k): dict(v)
                    for k, v in (snapshot.get('hists') or {}).items()}
    return out


# ---------------------------------------------------------------------------
# metrics

# Default histogram buckets: latency-oriented, seconds. Fixed per metric for
# the life of the process so fleet merges are bucket-aligned.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

# Canonical ingest-path stage vocabulary, shared by StageTimer epoch lines
# and the stage_seconds histogram family. The old
# aggregate 'compute' stage is decomposed into 'dispatch' (the async
# compiled-step call returning) and 'host_block' (block_until_ready / lazy
# metric fetch — the host pinned to the device stream), which is what the
# device-utilization proxy is computed from.
INGEST_STAGES: Tuple[str, ...] = (
    'select', 'decode', 'assemble', 'ipc', 'h2d', 'dispatch', 'host_block')

# Row-count buckets for batching histograms (e.g. the inference engine's
# engine_batch_rows): powers of two matching the padded dispatch buckets.
BATCH_ROW_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# Policy-lag buckets: how many epochs behind the learner the params that
# generated a consumed sample were (the policy_lag_epochs histogram).
LAG_EPOCH_BUCKETS: Tuple[float, ...] = (0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32,
                                        48, 64)

# Sample-age buckets (seconds from learner ingest to consumption): buffer
# dwell spans far past the latency-oriented DEFAULT_BUCKETS.
AGE_SECOND_BUCKETS: Tuple[float, ...] = (0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25,
                                         50, 100, 250, 500, 1000)

# XLA compile durations (jax.monitoring events): seconds, up to the
# minutes-long recurrent-net compiles.
COMPILE_SECOND_BUCKETS: Tuple[float, ...] = (0.01, 0.05, 0.1, 0.25, 0.5, 1,
                                             2.5, 5, 10, 30, 60, 120, 300)

# Numeric encoding of the fleet controller's host health states
# (fault.FleetController) for the per-host ``fleet_host_state`` gauge
# family and the serving fleet's per-replica ``fleet_replica_state``
# gauges: monotone in severity, so operators can alert on `value >= 2`
# (draining or quarantined = the host/replica is not receiving fresh
# work). The serving resolver additionally uses -1 for a retired replica.
HOST_STATE_CODES: Dict[str, int] = {
    'healthy': 0, 'degraded': 1, 'draining': 2, 'quarantined': 3}


class Counter:
    """Monotonic labeled counter."""

    __slots__ = ('_lock', 'value')

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0

    def inc(self, n: int = 1):
        if not _ENABLED:
            return
        with self._lock:
            self.value += n


class Gauge:
    """Last-value labeled gauge."""

    __slots__ = ('_lock', 'value')

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0.0

    def set(self, v: float):
        if not _ENABLED:
            return
        with self._lock:
            self.value = float(v)

    def add(self, n: float = 1.0):
        if not _ENABLED:
            return
        with self._lock:
            self.value += n


class Histogram:
    """Fixed-bucket histogram with closed-form percentile summaries.

    ``bounds`` are ascending upper edges; observations land in the first
    bucket whose bound is >= the value (one overflow bucket past the last
    bound). Quantiles interpolate linearly inside the winning bucket —
    exact enough for p50/p95/p99 dashboards at 14 buckets.
    """

    __slots__ = ('_lock', 'bounds', 'buckets', 'sum', 'count')

    def __init__(self, lock: threading.Lock,
                 bounds: Sequence[float] = DEFAULT_BUCKETS):
        self._lock = lock
        self.bounds = tuple(float(b) for b in bounds)
        self.buckets = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float):
        if not _ENABLED:
            return
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self.buckets[i] += 1
            self.sum += v
            self.count += 1

    def observe_agg(self, total: float, n: int):
        """Fold ``n`` events totalling ``total`` in (a StageTimer batch):
        the mean lands in one bucket, sum/count stay exact."""
        if not _ENABLED or n <= 0:
            return
        i = bisect.bisect_left(self.bounds, total / n)
        with self._lock:
            self.buckets[i] += n
            self.sum += total
            self.count += n

    def quantile(self, q: float) -> float:
        with self._lock:
            return hist_quantile(self.bounds, self.buckets, self.count, q)


def hist_quantile(bounds: Sequence[float], buckets: Sequence[int],
                  count: int, q: float) -> float:
    """Linear-interpolated quantile of a bucketed distribution (also used on
    merged fleet histograms, where no Histogram object exists)."""
    if count <= 0:
        return 0.0
    rank = q * count
    seen = 0.0
    for i, n in enumerate(buckets):
        if n <= 0:
            continue
        if seen + n >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            frac = (rank - seen) / n
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        seen += n
    return float(bounds[-1])


class MetricRegistry:
    """Process-local metric store. One lock guards every update (updates are
    a few arithmetic ops; the timed sections themselves run unlocked)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}    # guarded-by: _lock
        self._gauges: Dict[str, Gauge] = {}        # guarded-by: _lock
        self._hists: Dict[str, Histogram] = {}     # guarded-by: _lock

    def counter(self, name: str, **labels) -> Counter:
        key = metric_key(name, labels)
        # graftlint: allow[GL004] lock-free fast path; the dict only grows and setdefault under the lock makes the miss race benign
        c = self._counters.get(key)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(key, Counter(self._lock))
        return c

    def gauge(self, name: str, **labels) -> Gauge:
        key = metric_key(name, labels)
        # graftlint: allow[GL004] lock-free fast path; the dict only grows and setdefault under the lock makes the miss race benign
        g = self._gauges.get(key)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(key, Gauge(self._lock))
        return g

    def histogram(self, name: str, buckets: Sequence[float] = DEFAULT_BUCKETS,
                  **labels) -> Histogram:
        key = metric_key(name, labels)
        # graftlint: allow[GL004] lock-free fast path; the dict only grows and setdefault under the lock makes the miss race benign
        h = self._hists.get(key)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(key,
                                           Histogram(self._lock, buckets))
        return h

    def observe_stage(self, stage: str, seconds: float, count: int = 1):
        """StageTimer mirror: fold an ingest-stage timing batch into the
        span histogram family (same canonical stage names)."""
        if not _ENABLED:
            return
        self.histogram('stage_seconds', stage=stage).observe_agg(
            seconds, count)
        _RECORDER.record('span', stage, seconds=round(seconds, 6),
                         count=count)

    def snapshot(self, reset: bool = False) -> Dict[str, Any]:
        """Plain-data (msgpack/json-safe) dump of every metric; with
        ``reset`` counters/histograms restart from zero (gauges keep their
        last value — they are levels, not flows)."""
        with self._lock:
            snap = {
                'run_id': _RUN_ID,
                'time': time.time(),
                'counters': {k: c.value for k, c in self._counters.items()},
                'gauges': {k: g.value for k, g in self._gauges.items()},
                'hists': {k: {'bounds': list(h.bounds),
                              'buckets': list(h.buckets),
                              'sum': h.sum, 'count': h.count}
                          for k, h in self._hists.items()},
            }
            if reset:
                for c in self._counters.values():
                    c.value = 0
                for h in self._hists.values():
                    h.buckets = [0] * len(h.buckets)
                    h.sum = 0.0
                    h.count = 0
        return snap


# the process-global registry every subsystem instruments against
REGISTRY = MetricRegistry()
counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram
snapshot = REGISTRY.snapshot


# ---------------------------------------------------------------------------
# fleet merge + summaries


def merge_snapshots(snaps: List[Optional[Dict[str, Any]]]
                    ) -> Dict[str, Any]:
    """Fleet-wide aggregate of per-process snapshots.

    Merge semantics: counters SUM (flows add across processes), gauges SUM
    (queue depths and rates add; per-peer resolution survives via labels —
    e.g. ``gather_episodes_per_sec{gather="3"}`` keys stay distinct),
    histogram buckets ADD elementwise when bounds agree. A peer whose
    bounds DISAGREE for a key is dropped for that key (never mis-binned)
    and the drop is counted: once in the merged
    ``telemetry_hist_bound_conflicts_total`` counter (so the conflict
    survives re-merging up the fleet tree and reaches the exposition) and
    once in the top-level ``hist_bound_conflicts`` field of the returned
    snapshot.
    """
    out: Dict[str, Any] = {'run_id': _RUN_ID, 'time': time.time(),
                           'counters': {}, 'gauges': {}, 'hists': {},
                           'peers': 0}
    conflicts = 0
    for snap in snaps:
        if not isinstance(snap, dict):
            continue
        out['peers'] += 1
        for k, v in (snap.get('counters') or {}).items():
            out['counters'][k] = out['counters'].get(k, 0) + v
        for k, v in (snap.get('gauges') or {}).items():
            out['gauges'][k] = out['gauges'].get(k, 0.0) + v
        for k, h in (snap.get('hists') or {}).items():
            cur = out['hists'].get(k)
            if cur is None:
                out['hists'][k] = {'bounds': list(h['bounds']),
                                   'buckets': list(h['buckets']),
                                   'sum': float(h['sum']),
                                   'count': int(h['count'])}
            elif list(cur['bounds']) == list(h['bounds']):
                cur['buckets'] = [a + b for a, b in
                                  zip(cur['buckets'], h['buckets'])]
                cur['sum'] += float(h['sum'])
                cur['count'] += int(h['count'])
            else:
                conflicts += 1
    if conflicts:
        key = 'telemetry_hist_bound_conflicts_total'
        out['counters'][key] = out['counters'].get(key, 0) + conflicts
        out['hist_bound_conflicts'] = conflicts
        get_logger('telemetry').warning(
            'merge_snapshots: dropped %d histogram(s) with mismatched '
            'bucket bounds (peers disagree on a histogram geometry)',
            conflicts)
    return out


def summarize(snap: Dict[str, Any]) -> Dict[str, Any]:
    """Compact form for metrics_jsonl: counters/gauges verbatim, histograms
    reduced to count/sum/p50/p95/p99 (full buckets stay wire-only)."""
    hists = {}
    for k, h in (snap.get('hists') or {}).items():
        n = int(h['count'])
        hists[k] = {
            'count': n, 'sum': round(float(h['sum']), 6),
            'p50': round(hist_quantile(h['bounds'], h['buckets'], n, 0.50), 6),
            'p95': round(hist_quantile(h['bounds'], h['buckets'], n, 0.95), 6),
            'p99': round(hist_quantile(h['bounds'], h['buckets'], n, 0.99), 6),
        }
    out = {'counters': dict(snap.get('counters') or {}),
           'gauges': {k: round(float(v), 6)
                      for k, v in (snap.get('gauges') or {}).items()},
           'hists': hists}
    if snap.get('peers') is not None:
        out['peers'] = snap['peers']
    return out


# ---------------------------------------------------------------------------
# SLO alert engine: declarative rules over merged registry/fleet snapshots

# Built-in alert catalog. Each rule is declarative: a metric selector
# (name or list of names, summed over matching label sets), a value kind
# (``value`` = current level, ``rate`` = per-second counter increase
# between evaluations, ``ratio`` = rate(metric)/rate(denominator) — the
# burn rate over the existing latency/shed counters), a comparison, a
# sustain window (``for`` seconds the breach must hold before firing) and
# a ``clear_for`` debounce before an active alert clears. ``arm_metric``
# keeps a rule silent until its subsystem has shown life (ingest stall
# must not fire before the first episode ever arrives). Custom rules from
# the ``telemetry.alerts`` config block override built-ins by name.
BUILTIN_ALERTS: Tuple[Dict[str, Any], ...] = (
    {'name': 'ingest_stall',
     'metric': 'learner_episodes_returned_total', 'kind': 'rate',
     'op': '<=', 'threshold': 0.0, 'for': 60.0,
     'arm_metric': 'learner_episodes_returned_total'},
    {'name': 'policy_lag_runaway',
     'metric': 'policy_lag_mean', 'kind': 'value',
     'op': '>', 'threshold': 16.0, 'for': 30.0},
    {'name': 'nonfinite_spike',
     'metric': 'guard_nonfinite_total', 'kind': 'rate',
     'op': '>', 'threshold': 0.2},
    {'name': 'serve_shed_burn',
     'metric': ['serve_shed_total', 'engine_shed_total'], 'kind': 'ratio',
     'denominator': ['serve_requests_total', 'engine_requests_total'],
     'op': '>', 'threshold': 0.05, 'for': 10.0},
    {'name': 'replica_quarantine_flap',
     'metric': ['fleet_replica_transitions_total',
                'fleet_host_transitions_total'],
     'labels': 'to="quarantined"', 'kind': 'rate',
     'op': '>', 'threshold': 0.05},
    {'name': 'heartbeat_misses',
     'metric': ['fleet_heartbeat_misses_total', 'hub_disconnects_total'],
     'kind': 'rate', 'op': '>', 'threshold': 0.0},
    # compiled-performance plane (docs/observability.md "Compiled-
    # performance plane"): sustained HBM pressure, and any post-warm-up
    # XLA recompilation (each one stalls the device for the full compile)
    {'name': 'hbm_pressure',
     'metric': 'device_mem_utilization', 'kind': 'value',
     'op': '>', 'threshold': 0.92, 'for': 30.0, 'clear_for': 30.0},
    {'name': 'retrace_storm',
     'metric': 'xla_retraces_total', 'kind': 'rate',
     'op': '>', 'threshold': 0.0, 'clear_for': 60.0},
    # league plane (docs/league.md): a pool that stops booking rated games
    # starves PFSP and freezes the promotion gate — armed only once the
    # first league game ever lands, so non-league runs stay silent
    {'name': 'league_rating_stall',
     'metric': 'league_games_total', 'kind': 'rate',
     'op': '<=', 'threshold': 0.0, 'for': 120.0,
     'arm_metric': 'league_games_total'},
    # match gateway (docs/serving.md "Match gateway"): the zero-loss
    # session contract — ANY dropped session is an incident (armed once
    # the gateway has ever opened one), and the per-ply latency SLO the
    # session tier promises on top of the fleet's request SLO
    {'name': 'session_drop',
     'metric': 'gateway_session_drops_total', 'kind': 'rate',
     'op': '>', 'threshold': 0.0, 'clear_for': 60.0,
     'arm_metric': 'gateway_sessions_opened_total'},
    {'name': 'gateway_ply_slo',
     'metric': 'gateway_ply_p99_ms', 'kind': 'value',
     'op': '>', 'threshold': 250.0, 'for': 15.0, 'clear_for': 30.0,
     'arm_metric': 'gateway_plies_total'},
    # durable training plane (docs/large_scale_training.md "Zero-loss
    # training plane"): a spool whose segment count keeps climbing means
    # GC has fallen behind the checkpoint consumption horizon (snapshots
    # stopped landing, or keep_segments is mis-sized) — disk is no longer
    # bounded; and ANY resend-buffer eviction is permanent episode loss
    # on a plane that promises zero, so the rate threshold is 0
    {'name': 'spool_growth',
     'metric': 'spool_segments', 'kind': 'value',
     'op': '>', 'threshold': 8.0, 'for': 60.0,
     'arm_metric': 'spool_bytes_total'},
    {'name': 'resend_buffer_loss',
     'metric': 'gather_resend_dropped_total', 'kind': 'rate',
     'op': '>', 'threshold': 0.0, 'clear_for': 60.0,
     'arm_metric': 'gather_uploads_total'},
)

_ALERT_OPS: Dict[str, Callable[[float, float], bool]] = {
    '>': lambda v, t: v > t, '>=': lambda v, t: v >= t,
    '<': lambda v, t: v < t, '<=': lambda v, t: v <= t,
}


def _metric_value(snaps: List[Optional[Dict[str, Any]]],
                  names, label_sub: str = '') -> float:
    """Sum a metric selector over snapshots: counters and gauges by value,
    histograms by observation count; label_sub (e.g. ``to="quarantined"``)
    restricts to matching label sets."""
    if isinstance(names, str):
        names = (names,)
    total = 0.0
    for snap in snaps:
        if not isinstance(snap, dict):
            continue
        for section in ('counters', 'gauges'):
            for key, v in (snap.get(section) or {}).items():
                name, labels = split_key(key)
                if name in names and (not label_sub or label_sub in labels):
                    total += float(v)
        for key, h in (snap.get('hists') or {}).items():
            name, labels = split_key(key)
            if name in names and (not label_sub or label_sub in labels):
                total += int(h.get('count', 0))
    return total


class AlertRule:
    """One normalized rule plus its evaluation state (sustain/clear
    windows, last rate sample)."""

    def __init__(self, spec: Dict[str, Any]):
        self.name = str(spec['name'])
        self.metric = spec.get('metric') or ()
        self.denominator = spec.get('denominator') or ()
        self.kind = str(spec.get('kind', 'value'))
        self.labels = str(spec.get('labels', ''))
        self.op = str(spec.get('op', '>'))
        self.threshold = float(spec.get('threshold', 0.0))
        self.for_s = float(spec.get('for', 0.0))
        self.clear_for = float(spec.get('clear_for', 0.0))
        self.arm_metric = spec.get('arm_metric') or ()
        if self.kind not in ('value', 'rate', 'ratio'):
            raise ValueError('alert %r: unknown kind %r'
                             % (self.name, self.kind))
        if self.op not in _ALERT_OPS:
            raise ValueError('alert %r: unknown op %r' % (self.name, self.op))
        if self.kind == 'ratio' and not self.denominator:
            raise ValueError('alert %r: ratio needs a denominator'
                             % self.name)
        # evaluation state (engine-lock protected via AlertEngine)
        self.active = False
        self.fired = 0
        self.last_value = 0.0
        self.breach_since: Optional[float] = None
        self.ok_since: Optional[float] = None
        self._prev: Optional[Tuple[float, float, float]] = None  # t, num, den

    def _rates(self, snaps, now) -> Tuple[float, float]:
        num = _metric_value(snaps, self.metric, self.labels)
        den = _metric_value(snaps, self.denominator, self.labels) \
            if self.denominator else 0.0
        prev, self._prev = self._prev, (now, num, den)
        if prev is None or now <= prev[0]:
            return 0.0, 0.0
        dt = now - prev[0]
        return (max(0.0, num - prev[1]) / dt,
                max(0.0, den - prev[2]) / dt)

    def value(self, snaps, now) -> float:
        if self.kind == 'value':
            return _metric_value(snaps, self.metric, self.labels)
        num_rate, den_rate = self._rates(snaps, now)
        if self.kind == 'rate':
            return num_rate
        return (num_rate / den_rate) if den_rate > 0 else 0.0


class AlertEngine:
    """Evaluate declarative SLO rules against merged registry snapshots.

    One engine runs on the learner (against local + merged fleet
    snapshots), one on the fleet resolver, one in the serving service.
    Fired alerts land as ``alerts_active{alert=}`` gauges,
    ``alerts_fired_total{alert=}`` counters, WARNING log transitions,
    flight-recorder events, and — on the learner — an ``alerts`` block in
    every metrics_jsonl record. ``maybe_evaluate`` is cadence-gated so the
    learner loop, the epoch writer and /statusz scrapes share one
    evaluation stream (rates need a stable window)."""

    def __init__(self, rules: Optional[Sequence[Dict[str, Any]]] = None,
                 interval: float = 5.0):
        specs = BUILTIN_ALERTS if rules is None else rules
        self.interval = max(0.2, float(interval))
        self._lock = threading.Lock()
        self._rules = [AlertRule(dict(s)) for s in specs]  # guarded-by: _lock
        self._last: Dict[str, Any] = {'time': 0.0, 'active': [],
                                      'fired': {}, 'values': {}}  # guarded-by: _lock
        self._log = get_logger('alerts')

    @classmethod
    def from_config(cls, args: Optional[Dict[str, Any]]
                    ) -> Optional['AlertEngine']:
        """Build from the ``telemetry.alerts`` block: ``{builtin, interval,
        rules: [...]}`` (or a bare rule list; False/{'enabled': False}
        disables). Returns None with alerting or telemetry off."""
        tel = config_block(args)
        if not tel.get('enabled', True) or not _ENABLED:
            return None
        blk = tel.get('alerts')
        if blk is False:
            return None
        if isinstance(blk, (list, tuple)):
            blk = {'rules': list(blk)}
        if not isinstance(blk, dict):
            blk = {}
        if not blk.get('enabled', True):
            return None
        by_name: Dict[str, Dict[str, Any]] = {}
        if blk.get('builtin', True):
            for spec in BUILTIN_ALERTS:
                by_name[str(spec['name'])] = dict(spec)
        for spec in (blk.get('rules') or []):
            if isinstance(spec, dict) and spec.get('name'):
                merged = dict(by_name.get(str(spec['name'])) or {})
                merged.update(spec)
                by_name[str(spec['name'])] = merged
        return cls(list(by_name.values()),
                   interval=float(blk.get('interval', 5.0)))

    def rule_names(self) -> List[str]:
        with self._lock:
            return [r.name for r in self._rules]

    def evaluate(self, snaps: List[Optional[Dict[str, Any]]],
                 now: Optional[float] = None) -> Dict[str, Any]:
        """One evaluation pass; returns the ``alerts`` block."""
        now = time.time() if now is None else float(now)
        fired, cleared = [], []
        with self._lock:
            for rule in self._rules:
                armed = (not rule.arm_metric
                         or _metric_value(snaps, rule.arm_metric) > 0)
                value = rule.value(snaps, now)
                rule.last_value = value
                breach = armed and _ALERT_OPS[rule.op](value, rule.threshold)
                if breach:
                    rule.ok_since = None
                    if rule.breach_since is None:
                        rule.breach_since = now
                    if (not rule.active
                            and now - rule.breach_since >= rule.for_s):
                        rule.active = True
                        rule.fired += 1
                        fired.append((rule.name, value))
                else:
                    rule.breach_since = None
                    if rule.active:
                        if rule.ok_since is None:
                            rule.ok_since = now
                        if now - rule.ok_since >= rule.clear_for:
                            rule.active = False
                            rule.ok_since = None
                            cleared.append((rule.name, value))
            block = {
                'time': round(now, 3),
                'active': sorted(r.name for r in self._rules if r.active),
                'fired': {r.name: r.fired for r in self._rules if r.fired},
                'values': {r.name: round(r.last_value, 6)
                           for r in self._rules},
            }
            self._last = block
        for name, value in fired:
            counter('alerts_fired_total', alert=name).inc()
            gauge('alerts_active', alert=name).set(1)
            record_event('alert', 'fired %s (value=%g)' % (name, value),
                         alert=name, state='firing')
            self._log.warning('alert FIRING: %s (value=%g)', name, value)
        for name, value in cleared:
            gauge('alerts_active', alert=name).set(0)
            record_event('alert', 'cleared %s (value=%g)' % (name, value),
                         alert=name, state='cleared')
            self._log.warning('alert cleared: %s (value=%g)', name, value)
        return block

    def maybe_evaluate(self, collect: Callable[[], List[Dict[str, Any]]],
                       now: Optional[float] = None) -> Dict[str, Any]:
        """Cadence-gated evaluation: runs a pass at most every
        ``interval`` seconds, otherwise returns the cached block."""
        now = time.time() if now is None else float(now)
        with self._lock:
            fresh = now - float(self._last.get('time') or 0.0) < self.interval
        if fresh:
            return self.block()
        return self.evaluate(collect(), now)

    def block(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._last)

    def active(self) -> List[str]:
        with self._lock:
            return [r.name for r in self._rules if r.active]


# ---------------------------------------------------------------------------
# Prometheus text exposition


def _prom_value(v) -> str:
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def render_prometheus(snaps: List[Dict[str, Any]]) -> str:
    """Render snapshots in Prometheus text exposition format 0.0.4.
    Caller guarantees key disjointness across snapshots (the fleet snapshot
    is relabeled with source="fleet")."""
    types: Dict[str, str] = {}
    lines_by_name: Dict[str, List[str]] = {}

    def emit(name: str, labelstr: str, value, kind: str):
        if not _NAME_RE.match(name):
            return
        types.setdefault(name, kind)
        body = '%s{%s}' % (name, labelstr) if labelstr else name
        lines_by_name.setdefault(name, []).append(
            '%s %s' % (body, _prom_value(value)))

    for snap in snaps:
        if not isinstance(snap, dict):
            continue
        for key, v in (snap.get('counters') or {}).items():
            name, labelstr = split_key(key)
            emit(name, labelstr, v, 'counter')
        for key, v in (snap.get('gauges') or {}).items():
            name, labelstr = split_key(key)
            emit(name, labelstr, v, 'gauge')
        for key, h in (snap.get('hists') or {}).items():
            name, labelstr = split_key(key)
            types.setdefault(name, 'histogram')
            cum = 0
            for bound, n in zip(list(h['bounds']) + ['+Inf'],
                                h['buckets']):
                cum += n
                le = ('+Inf' if bound == '+Inf'
                      else _prom_value(bound))
                ls = (labelstr + ',' if labelstr else '') + 'le="%s"' % le
                lines_by_name.setdefault(name, []).append(
                    '%s_bucket{%s} %d' % (name, ls, cum))
            suffix = '{%s}' % labelstr if labelstr else ''
            lines_by_name.setdefault(name, []).append(
                '%s_sum%s %s' % (name, suffix, _prom_value(h['sum'])))
            lines_by_name.setdefault(name, []).append(
                '%s_count%s %d' % (name, suffix, h['count']))

    out: List[str] = []
    for name in sorted(lines_by_name):
        out.append('# TYPE %s %s' % (name, types[name]))
        out.extend(lines_by_name[name])
    return '\n'.join(out) + ('\n' if out else '')


class TelemetryExporter:
    """Prometheus-style scrape endpoint on stdlib http.server.

    ``collect`` returns the snapshots to serve (called per scrape, so the
    endpoint always shows live registry values); ``port=0`` binds an
    ephemeral port (tests), a fixed port serves operators' scrape configs.
    ``/metrics`` answers the exposition text, ``/healthz`` a liveness
    ``ok`` line, ``/statusz`` a JSON health view (run identity, recorder
    stats, plus whatever the ``status`` callable contributes — active
    alerts, fleet states, run progress); every other path 404s.
    """

    def __init__(self, collect: Callable[[], List[Dict[str, Any]]],
                 port: int = 0, host: str = '',
                 status: Optional[Callable[[], Dict[str, Any]]] = None):
        self._collect = collect
        self._status = status
        self._host = host
        self._port = int(port)
        self._server = None
        self._thread = None

    @property
    def port(self) -> int:
        return self._port

    def status_payload(self) -> Dict[str, Any]:
        """The /statusz JSON: base process identity + recorder stats,
        overlaid with the owner's status callable (alerts, fleet states,
        progress, SLO snapshots)."""
        base: Dict[str, Any] = {
            'run_id': _RUN_ID, 'role': _TRACE.label, 'pid': os.getpid(),
            'time': round(time.time(), 3), 'recorder': recorder_stats()}
        if self._status is not None:
            extra = self._status()
            if isinstance(extra, dict):
                base.update(extra)
        return base

    def start(self) -> 'TelemetryExporter':
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        exporter = self

        class Handler(BaseHTTPRequestHandler):
            def _respond(self, body: bytes, ctype: str):
                self.send_response(200)
                self.send_header('Content-Type', ctype)
                self.send_header('Content-Length', str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 (http.server API)
                path = self.path.split('?')[0]
                if path == '/healthz':
                    self._respond(b'ok\n', 'text/plain; charset=utf-8')
                    return
                if path == '/statusz':
                    try:
                        body = json.dumps(exporter.status_payload(),
                                          sort_keys=True).encode()
                    except Exception as exc:   # a broken status callable
                        self.send_error(500, str(exc)[:120])   # 500s, only
                        return
                    self._respond(body, 'application/json; charset=utf-8')
                    return
                if path not in ('/metrics', '/'):
                    self.send_error(404)
                    return
                try:
                    body = render_prometheus(exporter._collect()).encode()
                except Exception as exc:   # a broken collector must not
                    self.send_error(500, str(exc)[:120])   # kill the server
                    return
                self._respond(
                    body, 'text/plain; version=0.0.4; charset=utf-8')

            def log_message(self, fmt, *args):
                get_logger('exporter').debug(fmt, *args)

        # Bind with retry, then fall back to an ephemeral port: a stale
        # TIME_WAIT socket or a colliding process on the configured
        # telemetry_port must degrade the scrape target, not crash the
        # learner. The actual bound port is logged (and kept on .port).
        log = get_logger('exporter')
        requested = self._port
        attempts = ([requested] * 3 + [0]) if requested else [0]
        server, last_err = None, None
        for i, port in enumerate(attempts):
            try:
                server = ThreadingHTTPServer((self._host, port), Handler)
                break
            except OSError as exc:
                last_err = exc
                if port and i + 1 < len(attempts) and attempts[i + 1]:
                    log.warning('telemetry port %d bind failed (%s); '
                                'retrying', port, exc)
                    time.sleep(0.2 * (i + 1))
        if server is None:
            log.error('telemetry exporter could not bind any port (%s); '
                      'exporter disabled for this run', last_err)
            return self
        self._server = server
        self._server.daemon_threads = True
        self._port = self._server.server_address[1]
        if requested and self._port != requested:
            counter('telemetry_port_fallbacks_total').inc()
            log.warning('telemetry_port %d unavailable (%s); serving '
                        '/metrics on ephemeral port %d instead',
                        requested, last_err, self._port)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name='telemetry-exporter',
                                        daemon=True)
        self._thread.start()
        log.info('telemetry exporter serving /metrics on port %d',
                 self._port)
        return self

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


# ---------------------------------------------------------------------------
# XLA compile-event counters (jax.monitoring listeners)

_JAX_MONITORING_INSTALLED = False
# jaxpr_trace_duration / jaxpr_to_mlir_module_duration / backend_compile_duration
_COMPILE_EVENT_PREFIX = '/jax/core/compile/'


def install_jax_monitoring() -> bool:
    """Subscribe to jax.monitoring and count XLA compile activity into the
    registry: ``xla_compile_events_total{event=...}`` (cache hits/misses,
    compile requests) and the ``xla_compile_seconds{event=...}`` duration
    histograms (jaxpr_trace / jaxpr_to_mlir_module / backend_compile).
    Idempotent. Catches unexpected recompiles (a new padded bucket shape, a
    donation-geometry change) that otherwise only show up as mystery
    latency spikes in the trace."""
    global _JAX_MONITORING_INSTALLED
    if _JAX_MONITORING_INSTALLED:
        return True
    import jax.monitoring as _jm

    def _on_event(event, *a, **kw):
        try:
            if 'compil' in event:
                REGISTRY.counter('xla_compile_events_total',
                                 event=str(event).strip('/')).inc()
        except Exception:
            pass   # a metrics listener must never break a compile

    def _on_duration(event, duration, *a, **kw):
        try:
            # trace / lower / backend-compile only, one series each (traces
            # nest, so their sum is not wall time; backend_compile is). The
            # compilation cache's own durations stay out: its
            # compile_time_saved_sec would book the time a cache hit SAVED
            # as time spent compiling
            if event.startswith(_COMPILE_EVENT_PREFIX):
                REGISTRY.histogram(
                    'xla_compile_seconds', buckets=COMPILE_SECOND_BUCKETS,
                    event=event[len(_COMPILE_EVENT_PREFIX):-len('_duration')]
                ).observe(float(duration))
        except Exception:
            pass
        # Retrace sentinel: after mark_steady_state() every lowering event
        # is a recompile the steady-state train loop should never see.
        # Deliberately OUTSIDE the try/except so the abort policy's
        # RetraceError propagates into the jitted call site.
        if _STEADY['on'] and event == _RETRACE_EVENT:
            _note_retrace(event)

    _jm.register_event_listener(_on_event)
    _jm.register_event_duration_secs_listener(_on_duration)
    _JAX_MONITORING_INSTALLED = True
    return True


# ---------------------------------------------------------------------------
# Compiled-performance plane (docs/observability.md "Compiled-performance
# plane"): device-memory gauges, the steady-state retrace sentinel, and the
# dispatch/host_block utilization proxy. All process-local state lives in
# the two dicts below so tests can reset it cleanly.

RETRACE_POLICIES = ('warn', 'abort', 'off')

# The lowering duration event fires on every in-memory jit-cache miss —
# unlike backend_compile, which the persistent compilation cache can skip —
# so it is the reliable "a retrace happened" signal.
_RETRACE_EVENT = '/jax/core/compile/jaxpr_to_mlir_module_duration'

_PERF_PLANE: Dict[str, Any] = {
    'enabled': True, 'retrace': 'warn', 'last_mem': [], 'util': None}

_STEADY: Dict[str, Any] = {
    'on': False, 'since': 0.0, 'retraces': 0, 'note': '',
    'last_compile': '', 'filter_on': False}


class RetraceError(RuntimeError):
    """Raised at a jitted call site when a post-steady-state XLA retrace
    occurs under the ``abort`` policy (HANDYRL_TPU_RETRACE=abort)."""


def configure_perf_plane(enabled=None, retrace=None):
    """Adopt the ``telemetry.perf_plane`` / ``telemetry.retrace`` config
    knobs (called from adopt_config on every process in the fleet)."""
    if enabled is not None:
        _PERF_PLANE['enabled'] = bool(enabled)
    if retrace is not None:
        retrace = str(retrace).strip().lower()
        if retrace in RETRACE_POLICIES:
            _PERF_PLANE['retrace'] = retrace


def perf_plane_enabled() -> bool:
    return _ENABLED and bool(_PERF_PLANE['enabled'])


def retrace_policy() -> str:
    """Active retrace policy: the HANDYRL_TPU_RETRACE env knob (the CI
    override) wins over the ``telemetry.retrace`` config value."""
    env = os.environ.get('HANDYRL_TPU_RETRACE', '').strip().lower()
    if env in RETRACE_POLICIES:
        return env
    return _PERF_PLANE['retrace']


class _CompileNameFilter(logging.Filter):
    """Captures the callable/shape key from jax's ``jax_log_compiles``
    WARNING ("Compiling <fn> with global shapes and types [...]") — the
    only place jax names what it is compiling — and swallows the record so
    the sentinel, not jax, owns the operator-facing message."""

    def filter(self, record):
        try:
            msg = record.getMessage()
            if msg.startswith('Compiling'):
                key = msg.split('. Argument mapping', 1)[0]
                _STEADY['last_compile'] = key[:300]
                return False
            if msg.startswith('Finished '):
                # jax_log_compiles' per-phase "Finished tracing/lowering/
                # compilation" chatter — the sentinel owns the message
                return False
        except Exception:
            pass
        return True


_COMPILE_FILTER = _CompileNameFilter()
_COMPILE_LOGGERS = ('jax._src.interpreters.pxla', 'jax._src.dispatch')


def mark_steady_state(note: str = ''):
    """Declare warm-up over: from here on, every XLA compile is a retrace
    the sentinel counts, records, and (under the abort policy) raises on.
    The Trainer crosses this boundary after ``retrace_warmup_epochs``."""
    if not (perf_plane_enabled() and _JAX_MONITORING_INSTALLED):
        return False
    if _STEADY['on']:
        return True
    _STEADY.update(on=True, since=time.time(), retraces=0, note=note)
    try:
        import jax
        jax.config.update('jax_log_compiles', True)
        if not _STEADY['filter_on']:
            for name in _COMPILE_LOGGERS:
                logging.getLogger(name).addFilter(_COMPILE_FILTER)
            _STEADY['filter_on'] = True
    except Exception:
        pass   # sentinel still counts retraces, just without callable names
    gauge('xla_steady_state').set(1)
    record_event('steady_state', 'steady state marked%s'
                 % ((': ' + note) if note else ''), policy=retrace_policy())
    return True


def clear_steady_state():
    """Leave steady state (learner shutdown, or test teardown). The flag is
    process-global, so in-process learners must clear it or a later jit in
    the same process would trip the sentinel."""
    _STEADY.update(on=False, note='', last_compile='')
    gauge('xla_steady_state').set(0)
    try:
        import jax
        if _STEADY['filter_on']:
            for name in _COMPILE_LOGGERS:
                logging.getLogger(name).removeFilter(_COMPILE_FILTER)
            _STEADY['filter_on'] = False
        jax.config.update('jax_log_compiles', False)
    except Exception:
        pass


def steady_state_active() -> bool:
    return bool(_STEADY['on'])


def steady_retrace_count() -> int:
    return int(_STEADY['retraces'])


# Signature-polymorphic helpers (utils/fetch.py's per-signature packed-
# transfer jits, eval-share probes) legitimately compile NEW programs after
# warm-up — once per fresh signature, by design. They declare those scopes
# with expected_compile() and the sentinel books the compile under
# xla_expected_compiles_total instead of treating it as a retrace.
# Thread-local because jit compilation is synchronous on the calling
# thread, so the listener fires on the same thread that opened the scope.
_EXPECTED_COMPILE = threading.local()


@contextmanager
def expected_compile(reason: str = ''):
    """Declare that any XLA compile inside this scope is expected (a known
    signature-polymorphic helper seeing a fresh signature), exempting it
    from the retrace sentinel's count/warn/abort path."""
    depth = getattr(_EXPECTED_COMPILE, 'depth', 0)
    _EXPECTED_COMPILE.depth = depth + 1
    _EXPECTED_COMPILE.reason = reason
    try:
        yield
    finally:
        _EXPECTED_COMPILE.depth = depth


def _in_expected_compile() -> bool:
    return getattr(_EXPECTED_COMPILE, 'depth', 0) > 0


def _note_retrace(event: str):
    """One post-steady-state recompile: count it, flight-record it, warn —
    and under the abort policy raise so the jitted call site fails loudly.
    The raise sits outside the metric try/except on purpose."""
    policy = retrace_policy()
    if policy == 'off':
        return
    if _in_expected_compile():
        try:
            counter('xla_expected_compiles_total').inc()
        except Exception:
            pass
        return
    who = _STEADY['last_compile'] or ('event ' + event.strip('/'))
    try:
        _STEADY['retraces'] += 1
        counter('xla_retraces_total').inc()
        record_event('retrace', 'steady-state XLA retrace: %s' % who,
                     policy=policy, count=_STEADY['retraces'])
        get_logger('retrace').warning(
            'steady-state XLA retrace #%d (%s) — a shape/donation bucket '
            'regression is recompiling the hot program', _STEADY['retraces'],
            who)
    except Exception:
        pass
    if policy == 'abort':
        raise RetraceError(
            'steady-state XLA retrace under HANDYRL_TPU_RETRACE=abort: %s'
            % who)


def _rss_memory() -> Dict[str, int]:
    """CPU fallback when Device.memory_stats() is unavailable: process RSS
    (current), VmHWM (peak), physical RAM (limit) — all from procfs."""
    in_use = peak = limit = 0
    try:
        page = os.sysconf('SC_PAGE_SIZE')
        with open('/proc/self/statm') as fh:
            in_use = int(fh.read().split()[1]) * page
        limit = os.sysconf('SC_PHYS_PAGES') * page
    except Exception:
        pass
    try:
        with open('/proc/self/status') as fh:
            for line in fh:
                if line.startswith('VmHWM:'):
                    peak = int(line.split()[1]) * 1024
                    break
    except Exception:
        try:
            import resource
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            pass
    return {'bytes_in_use': in_use,
            'peak_bytes_in_use': max(peak, in_use),
            'bytes_limit': limit}


def sample_device_memory(devices=None):
    """Sample per-device memory into the ``device_mem_bytes_*`` gauges.
    Real accelerators report via Device.memory_stats(); backends without it
    (CPU) get one process-RSS row labelled ``process_rss`` — one row, not
    one per CPU "device", since they all share this process's memory."""
    if not perf_plane_enabled():
        return []
    if devices is None:
        try:
            import jax
            devices = jax.local_devices()
        except Exception:
            devices = []
    rows = []
    for dev in devices:
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            label = '%s:%s' % (getattr(dev, 'platform', 'dev'),
                               getattr(dev, 'id', len(rows)))
            row = {'device': label,
                   'bytes_in_use': int(stats.get('bytes_in_use', 0)),
                   'peak_bytes_in_use': int(
                       stats.get('peak_bytes_in_use',
                                 stats.get('bytes_in_use', 0))),
                   'bytes_limit': int(stats.get('bytes_limit', 0))}
            rows.append(row)
        else:
            row = dict(_rss_memory(), device='process_rss')
            rows.append(row)
            break   # every CPU "device" is this same process
    for row in rows:
        dev = row['device']
        gauge('device_mem_bytes_in_use', device=dev).set(row['bytes_in_use'])
        gauge('device_mem_bytes_peak', device=dev).set(
            row['peak_bytes_in_use'])
        gauge('device_mem_bytes_limit', device=dev).set(row['bytes_limit'])
    _PERF_PLANE['last_mem'] = rows
    return rows


def device_memory_utilization(rows=None):
    """Worst-case bytes_in_use/bytes_limit across sampled devices — the
    ``hbm_pressure`` alert input. Only the learner publishes the
    ``device_mem_utilization`` gauge (a ratio must not be summed across
    fleet snapshots the way counters are)."""
    rows = _PERF_PLANE['last_mem'] if rows is None else rows
    util = 0.0
    for row in rows:
        limit = float(row.get('bytes_limit') or 0)
        if limit > 0:
            util = max(util, float(row.get('bytes_in_use', 0)) / limit)
    return util


def utilization_from_stages(stages) -> Optional[float]:
    """Device-utilization proxy from one epoch's ingest-stage seconds:
    host_block / total. Near 1.0 the host spends the epoch waiting on the
    device (device-bound, good); near 0.0 the device is starving behind
    host work (select/decode/assemble/ipc/h2d/dispatch). Accepts plain
    ``{stage: seconds}`` or StageTimer.snapshot's ``{stage: {'s':..}}``."""

    def _sec(val):
        if isinstance(val, dict):
            val = val.get('s', 0.0)
        return float(val or 0.0)

    try:
        total = sum(_sec(stages.get(s)) for s in INGEST_STAGES)
        block = _sec(stages.get('host_block'))
    except Exception:
        return None
    if total <= 0:
        return None
    return block / total


def set_utilization_proxy(value):
    if value is None or not perf_plane_enabled():
        return
    value = max(0.0, min(1.0, float(value)))
    _PERF_PLANE['util'] = value
    gauge('device_utilization_proxy').set(value)


# -- the fused loop's per-chunk record and stall event

# a chunk's split: the loop's spans (direct children of ``fused_iter`` or of
# its ``epoch_boundary``) under the keys the stall event, the epoch record's
# ``fused`` block and the ``fused_iter`` span's counters give them. ONE rule
# books an interval, wherever its step was made: each piece is the seconds of
# those spans that lie inside it, and ``epoch`` is the rest (the
# boundary's and the loop's glue, under no child span). A boundary's
# ``state_fetch`` waits for device work (the pack and its transfer; the chunk
# in flight too where the boundary keeps fetch-then-enqueue), so it is booked
# under ``wait``: ``wait`` is ALL the time the loop was blocked on device
# results in the interval
CHUNK_PIECES = {
    'dispatch': 'enqueue', 'host_block': 'wait', 'state_fetch': 'wait',
    'chunk_account': 'account', 'eval_share': 'eval',
    'epoch_report': 'report', 'state_pack': 'pack',
    'actor_refresh': 'refresh', 'checkpoint_wait': 'ckpt_wait',
    'epoch_advance': 'advance', 'metrics_write': 'record',
    'checkpoint_submit': 'submit', 'snapshot_release': 'release'}
# the boundary's own host work: what the loop thread does there that waits
# for no write (``ckpt_wait`` is the wait for the writer and stands apart;
# ``release`` is where the writer's serialisation takes the interpreter
# lock from the loop)
BOUNDARY_KEYS = ('report', 'pack', 'refresh', 'advance', 'record', 'submit',
                 'release', 'epoch')
CHUNK_KEYS = tuple(dict.fromkeys(CHUNK_PIECES.values())) + ('epoch',)


def _cpu_usage() -> Tuple[float, float, int]:
    """Process CPU seconds, this thread's CPU seconds, and the process's
    involuntary context switches so far."""
    return (time.process_time(), time.thread_time(),
            resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw)


class ChunkMonitor:
    """What the fused loop keeps of each chunk, the verdict on who set its
    pace, and the stall detector.

    A chunk is complete when the loop's blocking fetch of its packed result
    returns (the end of a ``host_block`` span). The INTERVAL between two
    completions is one chunk, whole, split by ``CHUNK_PIECES``: ``fetched``
    books it from the open ``fused_iter`` span (and the open
    ``epoch_boundary``, where the boundary made the step) right after the
    step; ``closed`` keeps what a finished iteration held after its last
    completion for the next interval.

    The interval less its ``wait`` is the loop's TURNAROUND: the host's own
    time from one completion to the next blocking fetch, with one program's
    worth of device work queued. Where the turnaround outlasts that program
    the device runs dry and the fetch that completes the chunk finds its
    result ready: a chunk whose completing ``host_block`` took under
    ``HOST_BOUND_FETCH`` of the running median interval is HOST-BOUND (the
    result's transfer takes 0.5-0.9 ms on the chip: zero is not the test;
    a boundary's ``state_fetch`` of 2-3 ms is ``wait`` but no part of the
    test, which it blurs: PERF.md section 5, PR 40). ``totals`` keeps the
    sums since the loop began, for every ``fused_iter`` span to carry.

    A running median over the last ``WINDOW`` intervals is kept; an interval
    over ``RATIO`` times that median and at least ``FLOOR_S`` over it is a
    stall. The stall's record carries the interval, the median, the split,
    the process's and the thread's CPU seconds and the involuntary context
    switches inside the interval and the load average, and is emitted ONE
    completion later (flight-recorder event ``stall``, counter
    ``fused_stalls_total``, one WARNING line), when ``next_host_block_s`` is
    known (the next interval's ``wait``): near zero, the device had long
    finished and the host was not woken; a whole chunk, the device itself
    was late. ``epoch_block`` gives the epoch record's ``fused`` block and
    starts the next epoch's."""

    WINDOW, MIN_SAMPLES = 64, 8
    RATIO, FLOOR_S = 2.0, 0.5
    # set once against the device trace (PERF.md section 5, PR 40)
    HOST_BOUND_FETCH = 0.075

    def __init__(self):
        self._recent: deque = deque(maxlen=self.WINDOW)
        self._chunks: List[Tuple[float, Dict[str, float]]] = []
        self._stalls: List[Dict[str, Any]] = []
        self._pending: Optional[Dict[str, Any]] = None
        self._usage = _cpu_usage()
        self._done: Optional[float] = None     # the last completion
        self._tail: Dict[str, float] = {}      # pieces since, in closed spans
        self._epoch_hb = 0                     # this epoch's host-bound chunks
        self._epoch_hb_split: Dict[str, float] = {}
        self.totals: Dict[str, Any] = dict(
            {'chunks': 0, 'host_bound_chunks': 0, 'interval_s': 0.0,
             'turnaround_s': 0.0, 'hb_turnaround_s': 0.0,
             'hb_boundary_s': 0.0},
            **{'hb_%s_s' % key: 0.0 for key in CHUNK_KEYS})

    def _collect(self, *scopes):
        """Add to the tail the pieces under ``scopes`` (an iteration, and its
        open boundary) that ended after the last completion (no piece
        straddles one: a completion IS the end of a piece). A finished
        boundary's children count as its iteration's."""
        since = self._done
        for scope in scopes:
            for child in scope.children:
                for piece in (child.children
                              if child.name == 'epoch_boundary' else (child,)):
                    key = CHUNK_PIECES.get(piece.name)
                    if key is not None and (since is None
                                            or piece.t1 > since):
                        self._tail[key] = (self._tail.get(key, 0.0)
                                           + piece.t1 - piece.t0)

    def fetched(self, dispatch: int, span,
                boundary=None) -> Optional[Dict[str, Any]]:
        """The step of iteration ``span`` (still open) has returned: if it
        fetched a chunk, book the interval that chunk's completion ends.
        ``boundary`` is the iteration's open ``epoch_boundary`` span where
        it made the NEXT iteration's step itself, ahead of its state
        fetch."""
        scopes = (span,) if boundary is None else (span, boundary)
        blocks = [c for c in scopes[-1].children if c.name == 'host_block']
        if not blocks:
            return None
        self._collect(*scopes)
        done, before = blocks[-1].t1, self._done
        self._done = done
        pieces, self._tail = self._tail, {}
        if before is None:
            return None
        split = {key: pieces.get(key, 0.0) for key in CHUNK_KEYS}
        split['epoch'] = done - before - sum(split.values())
        return self.observe(dispatch, done - before, split,
                            fetch_s=done - blocks[-1].t0)

    def closed(self, span):
        """Iteration ``span`` has finished: what it held after its last
        completion (all of it, where a boundary had made its step) opens
        the next interval."""
        self._collect(span)

    def observe(self, dispatch: int, interval_s: float,
                split: Dict[str, float],
                fetch_s: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """Book one interval; ``fetch_s`` is the completing fetch's own
        seconds (the whole ``wait`` where not given). Returns the stall
        record this call emitted (the PREVIOUS stall, now complete), if
        any."""
        usage, before = _cpu_usage(), self._usage
        self._usage = usage
        emitted = self._emit(split.get('wait'))
        pace = statistics.median(self._recent) if self._recent else None
        median = pace if len(self._recent) >= self.MIN_SAMPLES else None
        if median is not None and interval_s > max(
                self.RATIO * median, median + self.FLOOR_S):
            self._pending = {
                'dispatch': int(dispatch),
                'interval_s': round(interval_s, 6),
                'median_s': round(median, 6),
                'split': {k: round(v, 6) for k, v in split.items()},
                'process_cpu_s': round(usage[0] - before[0], 6),
                'thread_cpu_s': round(usage[1] - before[1], 6),
                'involuntary_switches': usage[2] - before[2],
                'loadavg': [round(x, 2) for x in os.getloadavg()],
                'next_host_block_s': None}
        fetch_s = split.get('wait', 0.0) if fetch_s is None else fetch_s
        # (the first interval of a loop is measured against itself)
        self._book_turnaround(
            interval_s, split, fetch_s < self.HOST_BOUND_FETCH * (
                interval_s if pace is None else pace))
        self._recent.append(interval_s)
        self._chunks.append((interval_s, split))
        return emitted

    def _book_turnaround(self, interval_s: float, split: Dict[str, float],
                         host_bound: bool):
        """The interval's turnaround and its verdict into the running
        sums."""
        wait = split.get('wait', 0.0)
        totals = self.totals
        totals['chunks'] += 1
        totals['interval_s'] += interval_s
        totals['turnaround_s'] += interval_s - wait
        counter('fused_chunks_total').inc()
        # (an inc of 0 registers the counter: a loop that was never
        # host-bound reads 0, not "no such counter")
        counter('fused_chunks_host_bound_total').inc(int(host_bound))
        if not host_bound:
            return
        self._epoch_hb += 1
        totals['host_bound_chunks'] += 1
        totals['hb_turnaround_s'] += interval_s - wait
        for key, seconds in split.items():
            totals['hb_%s_s' % key] = (totals.get('hb_%s_s' % key, 0.0)
                                       + seconds)
            self._epoch_hb_split[key] = (self._epoch_hb_split.get(key, 0.0)
                                         + seconds)
        totals['hb_boundary_s'] += sum(split.get(key, 0.0)
                                       for key in BOUNDARY_KEYS)

    def _emit(self, next_wait_s: Optional[float]):
        stall, self._pending = self._pending, None
        if stall is None:
            return None
        if next_wait_s is not None:
            stall['next_host_block_s'] = round(next_wait_s, 6)
        self._stalls.append(stall)
        counter('fused_stalls_total').inc()
        record_event('stall', 'dispatch %d' % stall['dispatch'], **stall)
        get_logger('perf').warning('stall: %s', json.dumps(stall))
        return stall

    def flush(self):
        """Loop exit: a stall still waiting for the next completion goes
        out with ``next_host_block_s`` null."""
        return self._emit(None)

    def epoch_block(self) -> Dict[str, Any]:
        chunks, stalls = self._chunks, self._stalls
        self._chunks, self._stalls = [], []
        block: Dict[str, Any] = {'chunks': len(chunks), 'stalls': stalls}
        if chunks:
            intervals = [c[0] for c in chunks]
            waits = [c[1].get('wait', 0.0) for c in chunks]
            block['interval_median_s'] = round(
                statistics.median(intervals), 6)
            block['interval_max_s'] = round(max(intervals), 6)
            for key in ('enqueue', 'wait', 'account', 'eval'):
                block[key + '_median_s'] = round(statistics.median(
                    c[1].get(key, 0.0) for c in chunks), 6)
            block['turnaround_median_s'] = round(statistics.median(
                i - w for i, w in zip(intervals, waits)), 6)
            block['host_bound_chunks'] = self._epoch_hb
            block['host_bound_split'] = {
                k: round(v, 6) for k, v in self._epoch_hb_split.items()}
            # the share of the epoch's chunk intervals that the loop spent
            # blocked on device results (1 - turnaround / interval). Each
            # wait lies inside its interval, so a value over 1 is a fault
            # of the booking and is left to show
            block['utilization'] = round(sum(waits) / sum(intervals), 6)
        self._epoch_hb, self._epoch_hb_split = 0, {}
        return block


def perf_status() -> Dict[str, Any]:
    """Compiled-performance block for /statusz (rendered by --status)."""
    return {
        'steady_state': bool(_STEADY['on']),
        'retraces': int(_STEADY['retraces']),
        'retrace_policy': retrace_policy(),
        'device_memory': list(_PERF_PLANE['last_mem']),
        'device_mem_utilization': device_memory_utilization(),
        'device_utilization_proxy': _PERF_PLANE['util']}


# ---------------------------------------------------------------------------
# JSONL schema helper (shared by tests and the CI smoke script)

FLEET_KEYS = ('epoch', 'steps', 'episodes', 'time', 'run_id', 'telemetry')


def validate_metrics_line(line: str, fleet: bool = False) -> Dict[str, Any]:
    """Parse one metrics_jsonl line and assert the telemetry schema: the
    base keys always, plus the merged ``fleet_telemetry`` aggregate when
    ``fleet`` (server-mode runs). Raises ValueError on any violation."""
    rec = json.loads(line)
    for key in FLEET_KEYS:
        if key not in rec:
            raise ValueError('metrics line missing %r: %s' % (key, line[:120]))
    tel = rec['telemetry']
    if not isinstance(tel, dict) or 'counters' not in tel:
        raise ValueError('telemetry summary malformed: %r' % (tel,))
    if fleet:
        ft = rec.get('fleet_telemetry')
        if not isinstance(ft, dict) or 'counters' not in ft:
            raise ValueError('fleet_telemetry missing/malformed: %r' % (ft,))
    if 'alerts' in rec:
        ab = rec['alerts']
        if not isinstance(ab, dict) or 'active' not in ab:
            raise ValueError('alerts block malformed: %r' % (ab,))
    if 'fused' in rec:
        fb = rec['fused']
        if (not isinstance(fb, dict) or not isinstance(fb.get('chunks'), int)
                or not isinstance(fb.get('stalls'), list)
                or (fb['chunks'] > 0 and 'interval_median_s' not in fb)):
            raise ValueError('fused block malformed: %r' % (fb,))
    return rec


# ---------------------------------------------------------------------------
# operator status view (``main.py --status <host:port>``)


def fetch_statusz(target: str, timeout: float = 5.0) -> Dict[str, Any]:
    """GET http://<target>/statusz and parse the JSON payload."""
    import urllib.request
    with urllib.request.urlopen('http://%s/statusz' % target,
                                timeout=timeout) as resp:
        return json.loads(resp.read().decode('utf-8'))


def render_status(payload: Dict[str, Any]) -> str:
    """Human-readable rendering of one /statusz payload."""
    lines = ['%s pid=%s run=%s' % (payload.get('role', '?'),
                                   payload.get('pid', '?'),
                                   payload.get('run_id', '?'))]
    progress = payload.get('progress')
    if isinstance(progress, dict):
        lines.append('progress: ' + ' '.join(
            '%s=%s' % (k, progress[k]) for k in sorted(progress)))
    alerts = payload.get('alerts')
    if isinstance(alerts, dict):
        active = alerts.get('active') or []
        lines.append('alerts: %s'
                     % (', '.join('FIRING %s' % a for a in active)
                        if active else 'none active'))
        fired = alerts.get('fired') or {}
        if fired:
            lines.append('  fired so far: ' + ', '.join(
                '%s x%d' % (k, fired[k]) for k in sorted(fired)))
    for key in ('fleet_hosts', 'fleet_replicas'):
        states = payload.get(key)
        if isinstance(states, dict) and states:
            lines.append('%s: ' % key.replace('_', ' ') + ', '.join(
                '%s=%s' % (k, states[k]) for k in sorted(states)))
    slo = payload.get('slo')
    if isinstance(slo, dict):
        lines.append('slo: ' + ' '.join(
            '%s=%s' % (k, slo[k]) for k in sorted(slo)))
    perf = payload.get('perf')
    if isinstance(perf, dict):
        bits = ['steady' if perf.get('steady_state') else 'warming',
                'retraces=%s' % perf.get('retraces', 0),
                'policy=%s' % perf.get('retrace_policy', '?')]
        util = perf.get('device_utilization_proxy')
        if util is not None:
            bits.append('device_util=%.0f%%' % (float(util) * 100.0))
        mem_util = perf.get('device_mem_utilization')
        if mem_util:
            bits.append('mem_util=%.0f%%' % (float(mem_util) * 100.0))
        lines.append('perf: ' + ' '.join(bits))
        for row in perf.get('device_memory') or []:
            limit = row.get('bytes_limit') or 0
            lines.append('  mem %s: %.0f MiB in use (peak %.0f) of %s'
                         % (row.get('device', '?'),
                            row.get('bytes_in_use', 0) / 2**20,
                            row.get('peak_bytes_in_use', 0) / 2**20,
                            ('%.0f MiB' % (limit / 2**20)) if limit
                            else 'unknown'))
    sessions = payload.get('sessions')
    if isinstance(sessions, list) and sessions:
        lines.append('sessions: %d active' % len(sessions))
        lines.append('  %-12s %-14s %6s %9s %9s %-8s'
                     % ('sid', 'client', 'plies', 'version', 'ply_p99',
                        'replica'))
        for s in sessions:
            p99 = s.get('ply_p99_ms')
            lines.append('  %-12s %-14s %6s %9s %9s %-8s'
                         % (s.get('sid', '?'), s.get('client', '?'),
                            s.get('plies', 0), s.get('version') or '-',
                            ('%.1fms' % p99) if p99 is not None else '-',
                            s.get('replica') or '-'))
    requests = payload.get('requests')
    if isinstance(requests, list) and requests:
        lines.append('requests:')
        lines.append('  %-10s %8s %9s %9s %9s %9s %s'
                     % ('replica', 'inflight', 'p50', 'p99', 'received',
                        'answered', 'state'))
        for r in requests:
            lines.append('  %-10s %8s %8.1fms %8.1fms %9s %9s %s'
                         % (r.get('replica', '?'), r.get('inflight', 0),
                            float(r.get('p50_ms') or 0.0),
                            float(r.get('p99_ms') or 0.0),
                            r.get('received', 0), r.get('answered', 0),
                            'draining' if r.get('draining') else 'serving'))
    rec = payload.get('recorder')
    if isinstance(rec, dict):
        lines.append('recorder: %s/%s events (%s dropped), %d dump(s)'
                     % (rec.get('events', 0), rec.get('capacity', 0),
                        rec.get('dropped', 0), len(rec.get('dumps') or [])))
    return '\n'.join(lines)


def status_main(args: Optional[Dict[str, Any]], argv: Sequence[str]):
    """``main.py --status <host:port>``: fetch a live /statusz (the
    learner's telemetry_port or a serving metrics_port) and render it."""
    rest = [a for a in argv if not a.startswith('--')]
    target = rest[0] if rest else ''
    if not target:
        port = int((args or {}).get('telemetry_port') or 0)
        if port:
            target = 'localhost:%d' % port
    if not target:
        print('usage: main.py --status <host:port> [--json]')
        raise SystemExit(1)
    if ':' not in target:
        target = 'localhost:' + target
    try:
        payload = fetch_statusz(target)
    except Exception as exc:
        print('status fetch from %s failed: %s' % (target, exc))
        raise SystemExit(1)
    if '--json' in argv:
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print(render_status(payload))
    return payload
