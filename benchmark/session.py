"""One run of one cell: ``main.py --train`` in this process, with the
benchmark's hooks around the program's calls, a measured window bounded by
dispatch completions, and (``--trace 1``) a profiler trace taken after it.

The learner is the program's own: the harness writes the cell's
``config.yaml`` (configuration file + traffic file + ``--seed``) into a run
directory inside the checkout, makes that the working directory and runs
``main.py`` there. The configuration's file names its ``weights`` (a trained
checkpoint handed to the learner as ``init_params``, so the games are those
of a policy past the random-init transient; or ``seeded``: the learner's own
initialisation), the ``checks`` that decide ``correct`` beside the harness's
own, and its ``flops`` count. ``--seed`` is the learner's one seed: games,
rollout sampling, replay sampling, and a seeded configuration's weights.
When the window (and the traced stretch after it) is over, the process sends
itself SIGTERM; the learner takes its own preemption path (final checkpoint,
preempt record, exit code 75).

Timeline of a run::

    process start .. imports, device check, the configuration's checks,
    Learner(), warm-up dispatches (minimum_episodes), the first training
    dispatches
      -> setup_s
    window opens at the END of training dispatch number `skip_dispatches`
    .. whole chunks ..  closes at the first dispatch end >= open + seconds
      -> every end-to-end metric, every hook-based per-layer metric
    [--trace 1: profiler on for `trace_seconds` more of the same loop]
    SIGTERM -> learner's preemption path -> metrics, one JSON line
"""

import contextlib
import importlib
import json
import math
import os
import runpy
import shutil
import signal
import sys
import threading
import time

from . import checks, hooks, reduce_trace
from .manifest import ROOT
from .record import Run, intervals

PREEMPT_EXIT_CODE = 75          # handyrl_tpu.guard.PREEMPT_EXIT_CODE
SEED_MODULUS = 2 ** 31 - 101    # the program adds small offsets to its seed
COMPILE_EVENT_PREFIX = '/jax/core/compile/'
# The driver ends a run at 360 s of wall clock and says only that it was
# still running; a first run in a checkout, which compiles, gets 1,200. The
# watchdog ends the run before that and says where its time went. Founded on
# the four cells' slowest runs (traced, from an empty cache: 180-250 s; my
# chip runs, PRs 31-33): 345 s, plus the seconds this process spent in
# backend compiles (3.3 s of cache reads in a warm run, 21-60 s from an empty
# cache), never past 1,150.
HARD_LIMIT_S = 345
COLD_LIMIT_S = 1150
PHASES = ('imports, device claim', 'checks', 'learner start', 'warm-up',
          'before the window', 'window', 'trace', 'flush', 'reading')


class RunFailed(RuntimeError):
    """The run cannot produce a result line."""


def fold_seed(seed):
    """Any whole number -> what the program's int32 seeds can hold."""
    return int(seed) % SEED_MODULUS


def merged_args(config, traffic, seed):
    """The cell's ``config.yaml`` as a dict: traffic, then the
    configuration's own keys, then ``--seed`` as the learner's seed, the
    harness's file names and, where the configuration's ``weights`` are a
    checkpoint, that file as the learner's ``init_params`` (seeded weights
    are the learner's own initialisation under that seed: no file)."""
    train_args = json.loads(json.dumps(traffic['train_args']))
    train_args.update(config['train_args'])
    train_args.update(seed=fold_seed(seed), metrics_jsonl='metrics.jsonl',
                      model_dir='models')
    if 'checkpoint' in config['weights']:
        train_args['init_params'] = os.path.join(
            ROOT, config['weights']['checkpoint'])
    return {'env_args': dict(config['env_args']), 'train_args': train_args}


def claim_devices(cell):
    """The cell's chips, or no run: anything but ``tpu`` with the cell's
    chip count fails here, before a result could be printed."""
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) != cell['chips']:
        raise RunFailed('cell %s needs %d TPU chip(s); jax found %d x %s'
                        % (cell['name'], cell['chips'], len(devices),
                           devices[0].platform))
    return devices


def spans_of(manifest, workload, window_spec):
    """The hooks this cell installs: those its window names and those its
    own metrics read. A hook file that no file of the cell names wraps
    nothing in this cell's loop."""
    spans = [window_spec[k] for k in
             ('dispatch_span', 'account_span', 'fetch_span')]
    spans += list(window_spec.get('open_after', {}))
    spans += list(window_spec.get('spans', ()))
    for name in manifest.metrics_of(workload):
        args = manifest.load_metric(name).get('args', {})
        spans += [args[k] for k in ('span', 'inner') if k in args]
    return sorted(set(spans))


class _Window:
    """Opens and closes the measured window (and the traced stretch) from
    the dispatch span's ends; raises SIGTERM when the run has what it needs."""

    def __init__(self, seconds, skip, open_after, trace_seconds, trace_dir):
        self.seconds, self.skip = seconds, skip
        self.open_after, self.seen = dict(open_after), {}
        self.trace_seconds, self.trace_dir = trace_seconds, trace_dir
        self.calls = 0
        self.open = self.close = None
        self.trace_open = self.trace_close = None
        self.signalled = False

    def counter(self, span):
        def count(_t0, _t1, _captures):
            self.seen[span] = self.seen.get(span, 0) + 1
        return count

    def on_dispatch(self, _t0, t1, _captures):
        self.calls += 1
        if self.open is None:
            if self.calls >= self.skip and all(
                    self.seen.get(span, 0) >= n
                    for span, n in self.open_after.items()):
                self.open = t1
            return
        if self.close is None:
            if t1 - self.open < self.seconds:
                return
            self.close = t1
            if self.trace_dir:
                import jax
                jax.profiler.start_trace(self.trace_dir)
                self.trace_open = time.perf_counter()
                return
        elif self.trace_dir and self.trace_close is None:
            if time.perf_counter() - self.trace_open < self.trace_seconds:
                return
            import jax
            jax.profiler.stop_trace()
            self.trace_close = time.perf_counter()
        if not self.signalled:
            self.signalled = True
            os.kill(os.getpid(), signal.SIGTERM)


class _CompileLog:
    """Every jax compile event (trace, lowering, backend compile) with the
    time it ended, from jax's own monitoring stream."""

    def __init__(self):
        self.events = []

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kwargs):
        if event.startswith(COMPILE_EVENT_PREFIX):
            self.events.append((time.perf_counter(), event, duration))

    def between(self, lo, hi):
        return [e for e in self.events if lo < e[0] <= hi]

    def seconds(self, suffix):
        return sum(d for _t, e, d in self.events if e.endswith(suffix))


def phase_table(t_process_start, marks, spans, window, dispatch_span, now):
    """``[(phase, seconds so far), ...]`` in ``PHASES``' order, the last
    entry the phase the run is in at ``now``. A phase begins where the
    harness marked it (``marks``) or where its first record says: the first
    call through any hook (warm-up), the first call of the dispatch span,
    the window's opening, the profiler's start, the SIGTERM."""
    first = [records[0][0] for records in list(spans.values()) if records]
    dispatches = spans.get(dispatch_span)
    begins = dict(marks)
    begins.update({
        'imports, device claim': t_process_start,
        'warm-up': min(first) if first else None,
        'before the window': dispatches[0][0] if dispatches else None,
    })
    if window is not None:
        begins.update({
            'window': window.open, 'trace': window.trace_open,
            'flush': (window.trace_close if window.trace_dir
                      else window.close)})
    begun = [(phase, begins[phase]) for phase in PHASES
             if begins.get(phase) is not None]
    ends = [t for _phase, t in begun[1:]] + [now]
    rows = [(phase, end - t) for (phase, t), end in zip(begun, ends)]
    return [row for row in rows[:-1] if row[1] > 0] + rows[-1:]


class _Watchdog:
    """Ends a run that overstays ``limit_s`` of wall clock since the process
    started (plus its backend-compile seconds, to ``COLD_LIMIT_S``): the
    phase table on standard error, exit code 4, no result line. ``run_cell``
    hands it what the table is read from as it makes it."""

    def __init__(self, limit_s, t_process_start, log):
        self.limit_s, self.t0, self.log = limit_s, t_process_start, log
        self.compiles = self.window = self.dispatch_span = None
        self.spans, self.marks = {}, {}
        self._arm()

    def watch(self, compiles, spans, window, dispatch_span):
        self.compiles, self.spans = compiles, spans
        self.window, self.dispatch_span = window, dispatch_span

    def mark(self, phase):
        self.marks[phase] = time.perf_counter()

    def _compile_s(self):
        return (self.compiles.seconds('backend_compile_duration')
                if self.compiles else 0.0)

    def _allowance(self):
        return min(self.limit_s + self._compile_s(), COLD_LIMIT_S)

    def _arm(self):
        left = self._allowance() - (time.perf_counter() - self.t0)
        self.timer = threading.Timer(max(0.0, left), self._ring)
        self.timer.daemon = True
        self.timer.start()

    def _ring(self):
        now = time.perf_counter()
        if now - self.t0 < self._allowance():   # it compiled meanwhile
            return self._arm()
        rows = phase_table(self.t0, self.marks, self.spans, self.window,
                           self.dispatch_span, now)
        self.log.write(
            'benchmark: hard time limit: %.0f s since the process started '
            '(allowed: %.0f + %.1f s of backend compiles), giving up in the '
            'phase "%s"\n' % (now - self.t0, self.limit_s,
                              self._compile_s(), rows[-1][0]))
        for phase, seconds in rows:
            self.log.write('benchmark:   %-22s %8.1f s\n' % (phase, seconds))
        self.log.flush()
        os._exit(4)

    def cancel(self):
        self.timer.cancel()


def _device_info():
    import jax
    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get('peak_bytes_in_use', 0))
    return ({'platform': devices[0].platform, 'kind': devices[0].device_kind,
             'count': len(devices),
             'memory_peak_bytes': int(fullest.get('peak_bytes_in_use', 0))},
            fullest)


def _finite(tree):
    if isinstance(tree, dict):
        return all(_finite(v) for v in tree.values())
    return tree is None or math.isfinite(tree)


def judge(run, dispatch_span, account_span, fetch_span):
    """``attempted``, ``failed`` and the part of ``correct`` that the spans
    decide. An operation is one fused training dispatch in the window; it
    failed if its fetch carried no loss sums or none of a batch that held
    data (a dispatch that trained nothing), if they were not finite, or if
    an update was skipped. The replay ratio is part of the cell: SGD steps
    booked in the window = ``sgd_steps_per_chunk`` x dispatches, at the
    cell's batch size (the learner's ``train_args``; that a mix's ``replay``
    block says the same is tests/benchmark's to hold)."""
    dispatches = run.records(dispatch_span)
    attempted = len(dispatches)
    fetched = [r[2].get('metrics')
               for r in run.records(fetch_span)]
    failed = sum(1 for m in fetched if m is None or not _finite(m)
                 or m.get('nonfinite', 0) > 0
                 or not m.get('data_count', 0) > 0)
    steps = [run.capture_at(account_span, 'steps', t) for t in run.window]
    sgd = run.train_args['sgd_steps_per_chunk']
    booked = None if None in steps else steps[1] - steps[0]
    batch = run.capture_at(account_span, 'batch_size', run.window[0])
    compared = [['failed_dispatches', failed, '==', 0],
                ['sgd_steps_booked_in_window', booked, '==', sgd * attempted],
                ['batch_size', batch, '==', run.train_args['batch_size']]]
    return attempted, failed, {
        'all_updates_finite': failed == 0 and attempted > 0,
        'replay_ratio_as_configured': bool(
            booked == sgd * attempted
            and all(r[2]['sgd_steps'] == sgd for r in dispatches)
            and batch == run.train_args['batch_size']),
    }, compared


def slowest_chunks(run, dispatch_span, top=3, floor_s=0.05):
    """The window's longest dispatch intervals, each with the spans of
    ``floor_s`` or more that ended inside it: where a stall was spent."""
    ends = [r[1] for r in run.spans.get(dispatch_span, ())
            if run.window[0] <= r[1] <= run.window[1]]
    gaps = sorted(zip(ends, ends[1:]), key=lambda ab: ab[0] - ab[1])[:top]
    out = []
    for a, b in gaps:
        inside = [[span, round(t1 - t0, 4)]
                  for span, records in run.spans.items()
                  for t0, t1, _captures in records
                  if a < t1 <= b and t1 - t0 >= floor_s]
        out.append({'at_s': round(a - run.window[0], 3),
                    'seconds': round(b - a, 4), 'spans': inside})
    return out


def read_metrics(manifest, run, names):
    """Each named metric through its own reader; ``derived`` ones last. A
    reader that finds nothing returns None and the metric is left out."""
    specs = {name: manifest.load_metric(name) for name in names}
    out = {}
    order = ([n for n in names if specs[n]['reader'] != 'derived']
             + [n for n in names if specs[n]['reader'] == 'derived'])
    for name in order:
        spec = specs[name]
        reader = importlib.import_module(
            'benchmark.readers.' + spec['reader'])
        got = reader.read(run, **spec.get('args', {}))
        if got is None:
            continue
        entry = got if isinstance(got, dict) else {'value': got}
        if entry['value'] is None or not math.isfinite(entry['value']):
            continue
        entry['unit'] = manifest.metrics[name]['unit']
        out[name] = entry
        run.values[name] = entry['value']
    return out


def run_cell(manifest, workload, seed, seconds, trace, t_process_start,
             log=sys.stderr, hard_limit_s=HARD_LIMIT_S):
    """Run the cell once; returns the result dict (``run.py`` prints it).
    ``manifest.root`` holds the benchmark's data and the run directory; the
    program is the checkout this file lies in. A run that overstays
    ``hard_limit_s`` ends itself and says in which phase (``_Watchdog``)."""
    watchdog = _Watchdog(hard_limit_s, t_process_start, log)
    try:
        return _run_cell(manifest, workload, seed, seconds, trace,
                         t_process_start, log, watchdog)
    finally:
        watchdog.cancel()


def _run_cell(manifest, workload, seed, seconds, trace, t_process_start, log,
              watchdog):
    cell = manifest.cell(workload)
    config = manifest.load_config(cell['config'])
    traffic = manifest.load_traffic(cell['traffic'])
    args = merged_args(config, traffic, seed)
    train_args = args['train_args']
    window_spec = traffic['window']
    if not os.path.exists(os.path.join(ROOT, 'main.py')):
        raise RunFailed('no main.py in %s: the benchmark drives the program '
                        'and is nothing without it' % ROOT)
    claim_devices(cell)

    run_dir = os.path.join(manifest.root, '.bench_runs', workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, 'config.yaml'), 'w') as f:
        json.dump(args, f, indent=1)   # JSON is YAML

    compiles = _CompileLog()
    compiles.install()
    recorder = hooks.Recorder(annotate=bool(trace))
    spans = spans_of(manifest, workload, window_spec)
    uninstall = hooks.install(manifest.load_hooks(spans), recorder)
    trace_dir = os.path.join(run_dir, 'trace') if trace else None
    window = _Window(seconds, int(window_spec['skip_dispatches']),
                     window_spec.get('open_after', {}),
                     float(window_spec['trace_seconds']), trace_dir)
    recorder.on(window_spec['dispatch_span'], window.on_dispatch)
    for span in window.open_after:
        recorder.on(span, window.counter(span))

    watchdog.watch(compiles, recorder.spans, window,
                   window_spec['dispatch_span'])
    watchdog.mark('checks')

    # the configuration's own comparisons, during set-up, on this device
    # and on the very weights the learner starts from
    variables = checks.starting_variables(config, train_args)
    compared = {
        entry['name']: hooks.resolve(entry['check'])[2](
            config, variables, fold_seed(seed), train_args)
        for entry in config['checks']}
    del variables
    watchdog.mark('learner start')

    exit_code = None
    cwd, argv = os.getcwd(), sys.argv
    os.chdir(run_dir)
    sys.argv = ['main.py', '--train']
    try:
        with open('train.log', 'w') as out, contextlib.redirect_stdout(out):
            try:
                runpy.run_path(os.path.join(ROOT, 'main.py'),
                               run_name='__main__')
                exit_code = 0
            except SystemExit as exc:
                exit_code = exc.code
    finally:
        os.chdir(cwd)
        sys.argv = argv
        uninstall()
        watchdog.mark('reading')
    if window.close is None or (trace and window.trace_close is None):
        raise RunFailed('the learner stopped (exit %r) before the window '
                        'closed; see %s/train.log' % (exit_code, run_dir))

    device, memory = _device_info()
    reduced = None
    if trace:
        xplane = reduce_trace.find_xplane(trace_dir)
        reduced = xplane and reduce_trace.reduce(
            xplane, window_span=window_spec['dispatch_span'])
        if reduced:
            device['busy_s'] = reduced['busy_s']
            device['window_s'] = reduced['window_s']

    peaks = manifest.load_peaks().get(device['kind'])
    if peaks is None:
        raise RunFailed('no peaks on record for device kind %r: add a row '
                        'with its source to benchmark/peaks.json'
                        % device['kind'])
    names = {'setup_s': window.open - t_process_start,
             'chips': cell['chips'],
             'flops.train_window': hooks.resolve(config['flops'])[2](
                 config['model'], train_args)}
    names.update(('peak.' + k, v) for k, v in peaks.items()
                 if isinstance(v, (int, float)))
    run = Run(cell, config, traffic, train_args, recorder.spans,
              (window.open, window.close), reduced, memory, names)

    dispatch_span = window_spec['dispatch_span']
    attempted, failed, verdict, window_compared = judge(
        run, dispatch_span, window_spec['account_span'],
        window_spec['fetch_span'])
    in_window = compiles.between(*run.window)
    verdict.update({
        'learner_took_its_preemption_exit': exit_code == PREEMPT_EXIT_CODE,
        'no_compilation_in_window': not in_window,
    })
    verdict.update((name, bool(got['ok'])) for name, got in compared.items())
    metrics = read_metrics(manifest, run, manifest.metrics_of(workload))
    # every metric is read (a derived per-layer metric may need an
    # end-to-end one); the line carries the group the run is for
    keep = manifest.metrics_of(workload,
                               'per_layer' if trace else 'end_to_end')
    result = {
        'correct': all(verdict.values()),
        'attempted': attempted, 'failed': failed,
        'metrics': {k: v for k, v in metrics.items() if k in keep},
        'device': device,
        'workload': workload, 'seed': int(seed), 'seconds': seconds,
        'checks': verdict,
        'reference': compared,
        'flops': {'train_window': names['flops.train_window']},
        'compile': {
            'events_in_window': [[e, round(t - run.window[0], 3)]
                                 for t, e, _d in in_window][:8],
            'backend_compile_s': compiles.seconds('backend_compile_duration'),
            'requests': sum(1 for _t, e, _d in compiles.events
                            if e.endswith('backend_compile_duration')),
        },
        'counts': {
            'warm_dispatches': len(run.spans.get('warm_dispatch', ())),
            'train_dispatches_in_window': attempted,
            'epochs_in_window': len(run.records('epoch_boundary')),
            'window_s': run.window_s,
        },
        'slowest_chunks': slowest_chunks(run, dispatch_span),
        'chunk_ms': [round(1e3 * gap, 1) for gap in intervals(
            [(None, window.open)] + run.records(dispatch_span))],
        'spans': spans,
    }
    if reduced:
        result['breakdown'] = {'device_ops': reduced['device_ops'],
                               'idle_gaps': reduced['idle_gaps']}
    shutil.rmtree(os.path.join(run_dir, 'models'), ignore_errors=True)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    # each number compared beside its limit, then the verdict: the last
    # lines of standard error
    rows = [('window', row) for row in window_compared]
    rows += [(name, row) for name, got in compared.items()
             for row in got.get('compared', ())]
    for name, (what, value, op, limit) in rows:
        log.write('benchmark: %s: %s = %r, limit %s %r\n'
                  % (name, what, value, op, limit))
    log.write('benchmark: checks %s\n' % json.dumps(verdict))
    return result
