"""Headline benchmark: learner trajectories/sec on the flagship config.

Measures the full compiled update step (forward + targets + losses + grads +
Adam) on GeeseNet at the reference's default batch geometry (batch 128 x
forward_steps 16, config.yaml:12-18), on the default JAX device (the TPU
chip under the driver). ``vs_baseline`` is measured-ours / measured-reference:
the denominator comes from bench_baseline.json, produced by
scripts/baseline_torch_learner.py — the same step in PyTorch on this host's
CPU (the reference publishes no numbers of its own; see BASELINE.md).

Robustness contract:
  * exactly ONE JSON line is printed on stdout in every outcome — success,
    backend unavailable, timeout, or signal. The process exits 0 only when
    that line carries a measured value; a line with an ``error`` key exits 1;
  * the backend is probed in a SUBPROCESS with a short deadline, so a
    backend that cannot initialize fails the run fast instead of blocking
    this process (the probe has exited, and released the chip, before the
    parent first touches jax);
  * a global SIGALRM deadline (BENCH_DEADLINE_SEC, default 600) bounds the
    whole run; SIGTERM/SIGINT emit the JSON line before exiting, and
    children get SIGTERM so they can release the device they hold.

Success line also carries diagnostics (extra keys are additive):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": ..., "flops_per_step": N, "mfu": N}
"""

import json
import os
import signal
import subprocess
import sys
import time

_EMITTED = False
_MEASURED = False   # the emitted line carries a value, not an error
_CHILDREN = []

METRIC = 'learner trajectories/sec (GeeseNet B=128 T=16, full update step)'
UNIT = 'trajectories/sec'

# BENCH_MODE=ingest measures the HOST side of the distributed learner path
# instead: batches/sec from buffered episodes through the Batcher
# (select -> bz2 decode -> arena assembly) to a staged, transfer-complete
# device buffer. vs_baseline divides by the SAME pipeline running the
# pre-vectorization reference builder (ops/batch.py make_batch_reference).
INGEST_METRIC = ('host ingest batches/sec (GeeseNet B=128 T=16, '
                 'Batcher -> staged device buffer)')
INGEST_UNIT = 'batches/sec'

# BENCH_MODE=actor measures the distributed ACTOR data path: fleet
# episodes/sec through a real gather + worker-process subtree speaking the
# 4-RPC protocol, with the per-host batched InferenceEngine enabled
# (inference.py) vs the per-worker B=1 reference path — identical seeds,
# identical task stream, byte-compared episode records. vs_baseline is
# engine-eps / per-worker-eps measured by the SAME harness.
ACTOR_METRIC = ('fleet episodes/sec (HungryGeese/GeeseNet, gather+workers '
                'over the 4-RPC protocol, engine-batched inference vs '
                'per-worker B=1)')
ACTOR_UNIT = 'episodes/sec'

# BENCH_MODE=serve measures the standalone model-serving tier: sustained
# requests/sec and tail latency (client-side p50/p95/p99) of a real
# InferenceService subprocess (registry-resolved models, framed INFER
# protocol over TCP, continuous batching) under a synthetic many-client
# load, plus a measured graceful drain: a final wave of in-flight requests
# is answered through a SIGTERM (no request dropped un-answered, exit 75).
# vs_baseline is many-client req/s over single-client req/s measured by the
# SAME harness — the continuous-batching concurrency gain.
SERVE_METRIC = ('service requests/sec (standalone InferenceService, '
                'registry-resolved models, framed INFER protocol over TCP, '
                'synthetic many-client load)')
SERVE_UNIT = 'requests/sec'

# BENCH_MODE=gateway measures the match-gateway session tier: completed
# matches/sec through a real gateway subprocess over a real 2-replica
# fleet (server-held sessions, opponent seats stepped through the fleet,
# one round trip per client ply), with a mid-run replica SIGKILL — the
# row must show ZERO dropped sessions (stranded sessions are rebuilt by
# journal replay). vs_baseline is N-session matches/sec over
# single-session matches/sec measured by the SAME harness — the session
# concurrency gain.
GATEWAY_METRIC = ('gateway matches/sec (MatchGateway over a replicated '
                  'fleet, server-held sessions, mid-run replica SIGKILL '
                  'with journal-replay reconstruction)')
GATEWAY_UNIT = 'matches/sec'

# BENCH_MODE=mesh measures the mesh-sharded learner: SGD steps/sec of the
# partition-rule-built NamedSharding/jit update step at 1/2/4/8 devices
# (one subprocess per mesh size — the virtual-device count is fixed before
# jax import). Each row carries BOTH the wall-clock rate of the sharded
# program on this host's (possibly virtual) mesh AND the per-shard
# strong-scaling projection: the single-device rate at batch B/ndev, i.e.
# what each device of a real ndev-mesh computes per step. On a
# one-core CI host the virtual mesh time-slices its devices, so the
# projection (plus the measured cross-mesh loss parity) carries the
# scaling claim; on real silicon the wall clock does.
MESH_METRIC = ('sharded learner SGD steps/sec (GeeseNet B=128 T=16, '
               'partition-rule NamedSharding jit over the data mesh)')
MESH_UNIT = 'steps/sec'

# Per-chip peaks by device_kind substring: (key, bf16 FLOP/s, HBM bytes/s).
# Public figures: v4 275T & 1.23TB/s, v5e 197T & 819GB/s, v5p 459T &
# 2.77TB/s, v6e 918T & 1.64TB/s.
_PEAKS = (
    ('v6', 918e12, 1.64e12),
    ('v5p', 459e12, 2.77e12),
    ('v5 lite', 197e12, 819e9),
    ('v5e', 197e12, 819e9),
    ('v4', 275e12, 1.23e12),
    ('v3', 123e12, 900e9),
    ('v2', 45e12, 700e9),
)


def _peak(device_kind: str, column: int) -> float:
    kind = device_kind.lower()
    for row in _PEAKS:
        if row[0] in kind:
            return row[column]
    raise KeyError('no peak FLOP/s / HBM bandwidth on record for device_kind '
                   '%r: add it to _PEAKS with its source; a utilization '
                   'against an assumed peak is not a measurement'
                   % device_kind)


def _active_mode() -> str:
    return os.environ.get('BENCH_MODE', 'headline').strip().lower()


def _git_sha() -> str:
    """The repo HEAD sha stamped on every row, so the benchmarks.jsonl
    trajectory can be diffed across commits ('' outside a git checkout)."""
    try:
        out = subprocess.run(
            ['git', 'rev-parse', 'HEAD'], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() if out.returncode == 0 else ''
    except Exception:
        return ''


# bump when the emitted row shape changes incompatibly (keys renamed or
# re-typed) — consumers filter rows by this before diffing trajectories
BENCH_SCHEMA_VERSION = 2


def emit(value=0.0, vs_baseline=0.0, **extra):
    """Print the one JSON result line (at most once) and flush."""
    global _EMITTED, _MEASURED
    if _EMITTED:
        return
    _EMITTED = True
    _MEASURED = 'error' not in extra
    metric, unit = {'ingest': (INGEST_METRIC, INGEST_UNIT),
                    'actor': (ACTOR_METRIC, ACTOR_UNIT),
                    'mesh': (MESH_METRIC, MESH_UNIT),
                    'serve': (SERVE_METRIC, SERVE_UNIT),
                    'gateway': (GATEWAY_METRIC, GATEWAY_UNIT)}.get(
                        _active_mode(), (METRIC, UNIT))
    line = {'metric': metric, 'value': round(float(value), 2), 'unit': unit,
            'vs_baseline': round(float(vs_baseline), 2),
            'git_sha': _git_sha(), 'schema_version': BENCH_SCHEMA_VERSION}
    line.update(extra)
    # silent-fallback guard (ROADMAP "Recent"): every row records what
    # backend the operator asked for vs what the run actually landed on,
    # and an explicit request that fell back (tpu -> cpu) marks the row
    # degraded so perf_gate.py and humans never diff it against real silicon
    requested = (os.environ.get('BENCH_BACKEND')
                 or os.environ.get('JAX_PLATFORMS')
                 or 'auto').split(',')[0].strip().lower()
    actual = str(line.get('backend', 'unknown')).lower()
    line.setdefault('backend_requested', requested)
    line.setdefault('backend_actual', actual)
    if (requested not in ('', 'auto') and actual != 'unknown'
            and actual != requested):
        line['degraded'] = True
        print('WARNING: bench requested backend %r but ran on %r — row '
              'marked degraded' % (requested, actual),
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def _shutdown(signum, _frame):
    for proc in _CHILDREN:
        if proc.poll() is None:
            proc.terminate()  # SIGTERM: the child releases its device
    emit(error='interrupted by signal %d before a number was measured' % signum)
    sys.exit(0 if _MEASURED else 1)


def probe_backend(deadline: float) -> dict:
    """Ask a subprocess what backend/device is reachable, under a hard cap.

    Returns {'backend': ..., 'device_kind': ...} or {'error': ...}. The
    subprocess is the fail-fast layer: if backend init blocks we SIGTERM it
    and report unavailable instead of hanging.
    """
    code = (
        "import json, jax\n"
        "d = jax.devices()[0]\n"
        "print(json.dumps({'backend': jax.default_backend(),"
        " 'device_kind': d.device_kind, 'n': jax.device_count()}))\n"
    )
    proc = subprocess.Popen([sys.executable, '-c', code],
                            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                            text=True)
    _CHILDREN.append(proc)
    try:
        out, _ = proc.communicate(timeout=deadline)
        if proc.returncode == 0 and out.strip():
            return json.loads(out.strip().splitlines()[-1])
        return {'error': 'probe exited rc=%s' % proc.returncode}
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass  # leave it to die with us
        return {'error': 'backend init exceeded %.0fs fail-fast deadline'
                         % deadline}


def peak_flops(device_kind: str) -> float:
    return _peak(device_kind, 1)


def peak_hbm_bw(device_kind: str) -> float:
    return _peak(device_kind, 2)


def time_compiled_step(step_fn, state, batch, lr, steps, warmup=3,
                       chunk=5):
    """AOT-compile ``step_fn`` and time ``steps`` executions.

    Two measurement-integrity rules:

    * The batch is materialized on device FIRST so the timed loop measures
      compute, not per-step host-to-device transfer (``jnp.asarray`` is a
      no-op for arrays already on device, so pre-sharded batches keep
      their shardings).
    * Dispatch is CHUNKED with a hard host-side sync (a scalar fetched to
      numpy) after every ``chunk`` steps: dispatch is asynchronous, so the
      clock may only stop on a value that data-depends on every queued
      step, and a bounded queue keeps the timed loop from running
      arbitrarily far ahead of the device. With chunk=5 the sync's
      latency is amortized to noise.

    Returns (seconds_per_step, flops_per_step, hbm_bytes_per_step); the
    flop and byte counts come from XLA's own cost analysis of the same
    executable.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    compiled = step_fn.lower(state, batch, lr).compile()
    cost = compiled.cost_analysis()
    flops = float(cost.get('flops', 0.0))
    hbm_bytes = float(cost.get('bytes accessed', 0.0))

    def sync(metrics):
        # a host fetch of a scalar that depends on the whole chain
        return float(np.asarray(metrics['total']))

    for _ in range(max(1, warmup)):   # >=1: 'metrics' must be bound
        state, metrics = compiled(state, batch, lr)
    sync(metrics)
    done = 0
    t0 = time.time()
    while done < steps:
        n = min(chunk, steps - done)
        for _ in range(n):
            state, metrics = compiled(state, batch, lr)
        sync(metrics)
        done += n
    return (time.time() - t0) / steps, flops, hbm_bytes


def headline_setup(B=128, T=16, dtype=None, seed=0, torus_impl=None):
    """Build the headline-config pieces: (module, cfg, batch, state).

    The ONE definition of what the headline benchmark measures — GeeseNet
    at the reference's default geometry with TD/TD targets. Shared by
    run_bench and scripts/tpu_scaling_bench.py so the scaling sweep always
    measures the same program as the headline number it explains.
    ``dtype`` (e.g. jnp.bfloat16) clones the net with reduced-precision
    activations; params stay float32 (the learner's compute_dtype mode).
    ``torus_impl`` ('pad'/'halo') selects the TorusConv implementation
    (identical function, different HBM behavior — models/blocks.py).
    """
    import jax
    import numpy as np

    import handyrl_tpu
    handyrl_tpu.setup_compile_cache()
    from handyrl_tpu.models import build
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import init_train_state
    from __graft_entry__ import _synthetic_batch

    module = build('GeeseNet')
    if dtype is not None:
        module = module.clone(dtype=dtype)
    if torus_impl is not None:
        module = module.clone(torus_impl=torus_impl)
    rng = np.random.RandomState(seed)
    batch = _synthetic_batch(B, T, 1, (17, 7, 11), 4, rng)
    params = module.init(jax.random.PRNGKey(0),
                         batch['observation'][:, 0, 0], None)
    state = init_train_state(params)
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='TD', value_target='TD', gamma=0.99)
    return module, cfg, batch, state


def run_bench(probe: dict):
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops.train_step import build_update_step
    from handyrl_tpu.parallel.mesh import make_mesh, shard_batch

    B, T = 128, 16
    steps = 30

    # bf16 activations on the MXU (the learner's compute_dtype mode,
    # tests/test_bf16.py); params and the optimizer stay float32
    module, cfg, batch, state = headline_setup(B, T, dtype=jnp.bfloat16)
    devices = jax.devices()
    # cost_analysis covers the whole (possibly sharded) program, so the
    # denominators are the peaks of every device it runs across; looked up
    # BEFORE timing — an unknown device_kind raises, no peak is assumed
    peak = peak_flops(probe['device_kind']) * len(devices)
    bw = peak_hbm_bw(probe['device_kind']) * len(devices)
    mesh = make_mesh(devices) if len(devices) > 1 else None
    step = build_update_step(module, cfg, mesh=mesh, donate=False)
    if mesh is not None:
        batch = shard_batch(mesh, batch)
    lr = jnp.asarray(1e-5, jnp.float32)

    sec_per_step, flops_per_step, hbm_bytes_per_step = time_compiled_step(
        step, state, batch, lr, steps)
    dt = sec_per_step * steps
    traj_per_sec = B / sec_per_step

    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             'bench_baseline.json')
    vs_baseline = 0.0
    baseline_def = 'no baseline file'
    if os.path.exists(base_path):
        with open(base_path) as f:
            base = json.load(f)
        # we measure in bf16; divide by the FASTER of the torch fp32/bf16
        # rows so the ratio never flatters a dtype mismatch
        fp32 = base.get('torch_cpu_trajectories_per_sec', 0.0)
        bf16 = base.get('torch_cpu_bf16_trajectories_per_sec', 0.0)
        ref = max(fp32, bf16)
        if ref > 0:
            vs_baseline = traj_per_sec / ref
            baseline_def = ('ours-bf16 / torch-cpu-%s (best of fp32 %.1f, '
                            'bf16-autocast %.1f traj/s)'
                            % ('bf16' if bf16 >= fp32 else 'fp32',
                               fp32, bf16))
        else:
            baseline_def = 'baseline file present but has no usable rows'

    mfu = flops_per_step * steps / dt / peak
    # roofline: which wall does the step actually sit against? mbu is the
    # fraction of peak HBM bandwidth the measured step sustains; whichever
    # utilization is higher names the bound
    mbu = hbm_bytes_per_step / sec_per_step / bw
    bound = 'hbm' if mbu >= mfu else 'mxu'
    emit(traj_per_sec, vs_baseline,
         device=probe.get('device_kind', 'unknown'),
         backend=probe.get('backend', 'unknown'),
         step_ms=round(dt / steps * 1e3, 2),
         flops_per_step=flops_per_step,
         hbm_bytes_per_step=hbm_bytes_per_step,
         compute_dtype='bfloat16', vs_baseline_def=baseline_def,
         mfu=round(mfu, 4), mbu=round(mbu, 4), roofline_bound=bound)


def _synthetic_geese_episodes(n_eps, rng, compress_steps=4, num_players=4,
                              min_steps=24, max_steps=96):
    """Buffered-episode stand-ins at the HungryGeese record geometry:
    (17, 7, 11) float32 observation planes per player per ply, 4 actions,
    all seats acting every ply (simultaneous env, solo-training config).
    Planes are sparse binary like real goose boards, so bz2 block sizes —
    and therefore the decode stage this benchmark times — are realistic
    rather than incompressible white noise."""
    from handyrl_tpu.ops.batch import compress_moments
    import numpy as np

    players = list(range(num_players))
    eps = []
    for _ in range(n_eps):
        steps = int(rng.randint(min_steps, max_steps + 1))
        moments = []
        for _t in range(steps):
            moments.append({
                'observation': {p: (rng.rand(17, 7, 11) < 0.08)
                                .astype(np.float32) for p in players},
                'selected_prob': {p: float(rng.rand()) for p in players},
                'action_mask': {p: np.zeros(4, np.float32) for p in players},
                'action': {p: int(rng.randint(4)) for p in players},
                'value': {p: np.array([float(rng.rand())], np.float32)
                          for p in players},
                'reward': {p: 0.0 for p in players},
                'return': {p: float(rng.rand()) - 0.5 for p in players},
                'turn': players,
            })
        eps.append({'args': {'player': players}, 'steps': steps,
                    'outcome': {p: float(np.sign(rng.randn()))
                                for p in players},
                    'moment': compress_moments(moments, compress_steps)})
    return eps


def _measure_ingest(build_fn, episodes, args, n_batches, timer=None):
    """batches/sec through Batcher -> device_put -> transfer complete,
    using the REAL Batcher machinery (same queues, threads, staging)."""
    import jax
    import jax.numpy as jnp
    from collections import deque
    from handyrl_tpu.train import Batcher

    batcher = Batcher(args, deque(episodes), timer=timer, build_fn=build_fn)
    batcher.run()

    def next_batch():
        # with tracing on the thread batcher wraps batches in TracedBatch
        nxt = batcher.batch(timeout=60)
        return nxt.batch if hasattr(nxt, 'trace_ids') else nxt

    nxt = next_batch()               # warmup: thread spin-up, allocators
    dev = jax.tree_util.tree_map(jnp.asarray, nxt)
    jax.block_until_ready(dev)
    t0 = time.time()
    for _ in range(n_batches):
        nxt = next_batch()
        th = time.time()
        dev = jax.tree_util.tree_map(jnp.asarray, nxt)
        jax.block_until_ready(dev)
        if timer is not None:
            timer.add('h2d', time.time() - th)
    dt = time.time() - t0
    batcher.stop()
    return n_batches / max(dt, 1e-9)


def run_ingest(probe: dict):
    """BENCH_MODE=ingest: the host ingest path, CPU-measurable.

    Env knobs (CI smoke shrinks them): BENCH_INGEST_BATCHES (timed batches,
    default 20), BENCH_INGEST_EPISODES (buffer size, default 32),
    BENCH_INGEST_BATCH_SIZE (default 128), BENCH_INGEST_BATCHERS
    (num_batchers, default 2).
    """
    import numpy as np
    from handyrl_tpu import telemetry
    from handyrl_tpu.ops.batch import make_batch, make_batch_reference
    from handyrl_tpu.utils.timing import StageTimer

    B = int(os.environ.get('BENCH_INGEST_BATCH_SIZE', '128'))
    T = 16
    n_batches = int(os.environ.get('BENCH_INGEST_BATCHES', '20'))
    n_eps = int(os.environ.get('BENCH_INGEST_EPISODES', '32'))
    args = {
        # the north-star geese training geometry (scripts/run_north_star.py)
        'turn_based_training': False, 'observation': True,
        'forward_steps': T, 'burn_in_steps': 0, 'compress_steps': 4,
        'maximum_episodes': 100000, 'batch_size': B,
        'num_batchers': int(os.environ.get('BENCH_INGEST_BATCHERS', '2')),
    }
    rng = np.random.RandomState(7)
    episodes = _synthetic_geese_episodes(n_eps, rng)

    ref_fn = (lambda sel, a, timer=None, cache=None:  # noqa: E731
              make_batch_reference(sel, a))
    timer = StageTimer()
    import contextlib
    import shutil
    import tempfile
    trace_rate = float(os.environ.get('BENCH_TRACE_RATE', '0.1'))
    trace_dir = tempfile.mkdtemp(prefix='bench_trace.')
    with contextlib.redirect_stdout(sys.stderr):
        # batcher-thread startup prints must not break the one-JSON-line
        # stdout contract
        ref_bps = _measure_ingest(ref_fn, episodes, args, n_batches)
        new_bps = _measure_ingest(make_batch, episodes, args, n_batches,
                                  timer=timer)
        # tracing-off vs tracing-on(sampled) pair: the disabled-path cost
        # claim ("near-zero when off") is guarded by the headline value
        # above staying the headline; this third leg measures the SAME
        # pipeline with episode tracing live at the sampled rate so a
        # regression in either path shows up in benchmarks.jsonl
        telemetry.configure_tracing(trace_dir, trace_rate, force=True)
        try:
            traced_bps = _measure_ingest(make_batch, episodes, args,
                                         n_batches)
        finally:
            telemetry.configure_tracing('', None, force=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
        # recorder-on vs recorder-off pair: the flight recorder defaults on
        # (an operator kills it with the rest of the plane via
        # `telemetry: false`); this adjacent A/B toggles ONLY the ring so
        # its append cost is isolated from metric/span cost — both legs run
        # back to back against identical warmed caches
        # alternating long legs, best-of-5 per side: the ring cost is far
        # below the run-to-run noise of a short timed pass (scheduler
        # stalls only ever slow a leg down), so max throughput per side is
        # the robust capability estimate and a one-shot pair would report
        # noise with either sign
        rounds = []
        for _ in range(5):
            on = _measure_ingest(make_batch, episodes, args, n_batches * 5)
            telemetry.set_recorder_enabled(False)
            try:
                off = _measure_ingest(make_batch, episodes, args,
                                      n_batches * 5)
            finally:
                telemetry.set_recorder_enabled(True)
            rounds.append((on, off))
        recorder_on_bps = max(on for on, _ in rounds)
        recorder_off_bps = max(off for _, off in rounds)
        recorder_overhead = (100.0 * (1.0 - recorder_on_bps /
                                      recorder_off_bps)
                             if recorder_off_bps else 0.0)
        # compiled-performance-plane on vs off pair: the armed retrace
        # sentinel plus a per-leg device-memory sample (the plane's whole
        # per-epoch cost) must stay in the noise on the host ingest path
        # (acceptance: <=2%) — same alternating best-of discipline as the
        # recorder pair
        telemetry.install_jax_monitoring()
        pp_rounds = []
        for _ in range(3):
            telemetry.mark_steady_state('bench ingest A/B')
            try:
                telemetry.sample_device_memory()
                pp_on = _measure_ingest(make_batch, episodes, args,
                                        n_batches * 5)
            finally:
                telemetry.clear_steady_state()
            telemetry.configure_perf_plane(False)
            try:
                pp_off = _measure_ingest(make_batch, episodes, args,
                                         n_batches * 5)
            finally:
                telemetry.configure_perf_plane(True)
            pp_rounds.append((pp_on, pp_off))
        perf_plane_on_bps = max(on for on, _ in pp_rounds)
        perf_plane_off_bps = max(off for _, off in pp_rounds)
        perf_plane_overhead = (100.0 * (1.0 - perf_plane_on_bps /
                                        perf_plane_off_bps)
                               if perf_plane_off_bps else 0.0)
        # spool-on vs spool-off pair: the durable plane's episode WAL
        # (spool.EpisodeSpool) rides the ingest hot path — one CRC-framed
        # msgpack record per ADMITTED episode, packed + appended before
        # the episode is counted. An episode is admitted once but sampled
        # into many batches, so the honest coupling spools the full
        # buffer exactly once per measured leg, the admission writes
        # interleaved evenly across the builds that consume them (one
        # append per built batch would bill the WAL len(leg)/n_eps times
        # over). Same alternating best-of-5 discipline, acceptance <= 2%
        # (scripts/perf_gate.py 'bench-ingest')
        import threading
        from handyrl_tpu.connection import pack as conn_pack
        from handyrl_tpu.spool import EpisodeSpool
        spool_root = tempfile.mkdtemp(prefix='bench_spool.')
        spool = EpisodeSpool(spool_root, segment_mb=64, keep_segments=2)
        spool_lock = threading.Lock()   # batcher threads share the WAL
        spool_idx = [0]
        builds_per_leg = n_batches * 5
        append_stride = max(1, builds_per_leg // len(episodes))

        def spooled_build(sel, a, timer=None, cache=None):
            with spool_lock:
                idx = spool_idx[0]
                spool_idx[0] += 1
                if idx % append_stride == 0:
                    ep = episodes[(idx // append_stride) % len(episodes)]
                    spool.append(idx, conn_pack({'idx': idx, 'episode': ep}))
            return make_batch(sel, a, timer=timer, cache=cache)

        sp_rounds = []
        try:
            for _ in range(5):
                sp_on = _measure_ingest(spooled_build, episodes, args,
                                        n_batches * 5)
                sp_off = _measure_ingest(make_batch, episodes, args,
                                         n_batches * 5)
                sp_rounds.append((sp_on, sp_off))
        finally:
            spool.close()
            shutil.rmtree(spool_root, ignore_errors=True)
        spool_on_bps = max(on for on, _ in sp_rounds)
        spool_off_bps = max(off for _, off in sp_rounds)
        spool_overhead = (100.0 * (1.0 - spool_on_bps / spool_off_bps)
                          if spool_off_bps else 0.0)
        # streaming-on vs streaming-off pair: with the `streaming:` block
        # enabled, episodes arrive as fixed-T window chunks and the
        # learner-side ChunkAssembler folds them back together (decode per
        # chunk, finiteness screen, return fill + canonical recompress at
        # completion). In the real learner ALL admission work runs on the
        # SERVER thread, concurrent with the batcher threads — and the
        # whole-episode path is not free there either (feed_episodes
        # guard-screens every upload, a full decode). So both legs model
        # the topology: a feeder thread admits the full buffer exactly
        # once per leg, paced by the build counter — the off-leg screening
        # whole episodes (guard.episode_is_finite, the real admission
        # cost), the on-leg folding the chunked buffer through a fresh
        # assembler — and builds/sec measures the DELTA streaming adds to
        # the shared host (chunk bookkeeping + return fill + canonical
        # recompress; on a multi-core learner the bz2 legs overlap, GIL
        # released). Worker-side chunking is prepared untimed (that cost
        # lives on the generation host). Same alternating best-of-5
        # discipline, acceptance <= 2% (`chunk_overhead_pct` in
        # scripts/perf_gate.py 'bench-ingest')
        from handyrl_tpu import guard as guard_mod
        from handyrl_tpu.generation import build_chunk
        from handyrl_tpu.ops.batch import decompress_moments
        from handyrl_tpu.streaming import ChunkAssembler
        stream_args = dict(args)
        stream_args.update(
            gamma=0.8,
            streaming={'enabled': True, 'chunk_steps': 32})
        all_chunks = []
        for i, ep in enumerate(episodes):
            moments = decompress_moments(ep['moment'])
            for m in moments:
                m['return'] = {p: None for p in m['return']}
            gen_args = dict(ep['args'], sample_key=i, task_id=i)
            cs = 32
            for ci, base in enumerate(range(0, len(moments), cs)):
                window = moments[base:base + cs]
                final = base + cs >= len(moments)
                all_chunks.append(build_chunk(
                    gen_args, ci, base, window, stream_args,
                    final=final, outcome=ep['outcome'] if final else None))

        def paced_leg(units, admit):
            """One measured leg with a feeder thread admitting ``units``
            once, spread evenly across the leg's builds (the server-thread
            topology). Returns the measured builds/sec."""
            stride = max(1, builds_per_leg // len(units))
            built = [0]
            cond = threading.Condition()

            def feeder():
                for i, unit in enumerate(units):
                    with cond:
                        while built[0] < i * stride:
                            if not cond.wait(timeout=30.0):
                                return     # leg abandoned
                    admit(unit)

            feeder_th = threading.Thread(target=feeder, daemon=True)
            feeder_th.start()

            def paced_build(sel, a, timer=None, cache=None):
                with cond:
                    built[0] += 1
                    cond.notify_all()
                return make_batch(sel, a, timer=timer, cache=cache)

            bps = _measure_ingest(paced_build, episodes, args,
                                  n_batches * 5)
            with cond:
                built[0] += builds_per_leg     # release any waiting folds
                cond.notify_all()
            feeder_th.join(timeout=60)
            return bps

        st_rounds = []
        for _ in range(5):
            asm = ChunkAssembler(stream_args)
            st_on = paced_leg(all_chunks, asm.add)
            st_off = paced_leg(episodes, guard_mod.episode_is_finite)
            st_rounds.append((st_on, st_off))
        streaming_on_bps = max(on for on, _ in st_rounds)
        streaming_off_bps = max(off for _, off in st_rounds)
        chunk_overhead = (100.0 * (1.0 - streaming_on_bps /
                                   streaming_off_bps)
                          if streaming_off_bps else 0.0)

    default_geom = (B == 128 and T == 16)
    # stage keys in the canonical telemetry order (telemetry.INGEST_STAGES
    # is the one vocabulary shared by bench rows, the HANDYRL_TPU_TIMING
    # epoch line, and the exported stage_seconds histograms)
    snap = timer.snapshot()
    stages = {s: snap[s] for s in telemetry.INGEST_STAGES if s in snap}
    stages.update({s: snap[s] for s in snap if s not in stages})
    emit(new_bps, (new_bps / ref_bps) if ref_bps else 0.0,
         backend=probe.get('backend', 'unknown'),
         device=probe.get('device_kind', 'unknown'),
         batch_size=B, forward_steps=T, episodes=n_eps,
         timed_batches=n_batches,
         reference_batches_per_sec=round(ref_bps, 2),
         vs_baseline_def=('arena builder / reference builder, identical '
                          'Batcher machinery'),
         stages=stages, run_id=telemetry.run_id(),
         tracing_on_batches_per_sec=round(traced_bps, 2),
         tracing_overhead_pct=round(
             100.0 * (1.0 - traced_bps / new_bps), 2) if new_bps else 0.0,
         trace_sample_rate=trace_rate,
         recorder_on_batches_per_sec=round(recorder_on_bps, 2),
         recorder_off_batches_per_sec=round(recorder_off_bps, 2),
         recorder_overhead_pct=round(recorder_overhead, 2),
         perf_plane_on_batches_per_sec=round(perf_plane_on_bps, 2),
         perf_plane_off_batches_per_sec=round(perf_plane_off_bps, 2),
         perf_plane_overhead_pct=round(perf_plane_overhead, 2),
         spool_on_batches_per_sec=round(spool_on_bps, 2),
         spool_off_batches_per_sec=round(spool_off_bps, 2),
         spool_overhead_pct=round(spool_overhead, 2),
         streaming_on_batches_per_sec=round(streaming_on_bps, 2),
         streaming_off_batches_per_sec=round(streaming_off_bps, 2),
         chunk_overhead_pct=round(chunk_overhead, 2),
         geometry=('headline' if default_geom else 'dryrun'))


def _actor_env() -> str:
    return os.environ.get('BENCH_ACTOR_ENV', 'HungryGeese')


def _actor_args(backend: str, workers: int):
    """Merged train_args for one bench fleet (the gather subtree's view).

    ``backend`` is the per-host actor backend: 'worker' (per-worker B=1
    reference), 'engine' (host batched InferenceEngine), or 'device' (the
    fused on-device rollout fleet — DeviceActorGather)."""
    from handyrl_tpu.config import apply_defaults
    args = apply_defaults({'env_args': {'env': _actor_env()}})['train_args']
    args['env'] = {'env': _actor_env()}
    args['seed'] = 11
    args['eval_rate'] = 0.0
    args['worker'] = {'num_parallel': workers, 'num_gathers': 1,
                      'base_worker_id': 0, 'backend': backend}
    args['inference'] = dict(args['inference'],
                             enabled=(backend == 'engine'),
                             batch_wait_ms=float(os.environ.get(
                                 'BENCH_ACTOR_WAIT_MS', '2')))
    if backend == 'device':
        args['generation'] = dict(
            args.get('generation') or {}, backend='device',
            device_actor_envs=int(os.environ.get(
                'BENCH_ACTOR_DEVICE_ENVS', '16')),
            device_actor_chunk_steps=int(os.environ.get(
                'BENCH_ACTOR_DEVICE_CHUNK', '16')))
    return args


def _actor_fleet_run(backend: str, workers: int, total: int, warm: int,
                     snapshot: dict, players: list) -> dict:
    """Spawn ONE real gather (+ its worker processes) over a pipe and act as
    its learner: serve 'g' tasks (each stamped with a deterministic
    sample_key), the fixed model snapshot, and collect episode uploads.

    Returns episodes/sec past the warmup, the packed episode payloads (for
    byte-comparison across inference paths), and the gather's final
    telemetry beacon (engine batch-fill counters ride it)."""
    import time as _time
    from handyrl_tpu.connection import (HEARTBEAT_KIND, pack,
                                        spawn_pipe_workers)
    from handyrl_tpu.worker import gather_loop

    args = _actor_args(backend, workers)
    ep = spawn_pipe_workers(1, gather_loop,
                            lambda i, c: (args, c, i))[0]
    served = 0
    episodes, arrivals, failed = [], [], 0
    beacon = {}
    while True:
        try:
            kind, body = ep.recv()
        except (EOFError, OSError):
            break
        if kind == HEARTBEAT_KIND:
            beacon = body or {}
            continue
        if kind == 'args':
            out = []
            for _ in body:
                if served < total:
                    out.append({'role': 'g', 'player': list(players),
                                'model_id': {p: 1 for p in players},
                                'sample_key': served})
                    served += 1
                else:
                    out.append(None)
            ep.send(out)
        elif kind == 'model':
            ep.send(snapshot)
        elif kind == 'episode':
            now = _time.time()
            for e in body:
                if e is None:
                    failed += 1
                    continue
                episodes.append(e)
                arrivals.append(now)
            ep.send(None)
        elif kind == 'result':
            ep.send(None)
    measured = max(0, len(episodes) - warm)
    span = (arrivals[-1] - arrivals[warm - 1]) if measured > 0 else 0.0
    steps = sum(e['steps'] for e in episodes[warm:])
    tele = (beacon.get('telemetry') or {}).get('counters') or {}
    return {
        'episodes_per_sec': measured / span if span > 0 else 0.0,
        'requests_per_sec': steps / span if span > 0 else 0.0,
        'records': sorted(pack(e) for e in episodes),
        'failed': failed,
        'engine_requests': tele.get('engine_requests_total', 0),
        'engine_batches': tele.get('engine_batches_total', 0),
        'stamped': sum(1 for e in episodes if e.get('record_version')),
        'device_plies': tele.get('device_actor_plies_total', 0),
        'device_episodes': tele.get('device_actor_episodes_total', 0),
        'device_divergence': tele.get('device_actor_divergence_total', 0),
    }


def run_actor(probe: dict):
    """BENCH_MODE=actor: the fleet actor data path, CPU-measurable.

    Env knobs (CI smoke shrinks them): BENCH_ACTOR_WORKERS (default 4),
    BENCH_ACTOR_EPISODES (timed episodes, default 96), BENCH_ACTOR_WARMUP
    (default 16), BENCH_ACTOR_WAIT_MS (engine batch_wait_ms, default 2),
    BENCH_ACTOR_ENV (default TicTacToe).
    """
    from handyrl_tpu import telemetry
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.model import ModelWrapper

    workers = int(os.environ.get('BENCH_ACTOR_WORKERS', '6'))
    warm = int(os.environ.get('BENCH_ACTOR_WARMUP', '4'))
    total = warm + int(os.environ.get('BENCH_ACTOR_EPISODES', '12'))

    # ONE fixed model snapshot (seeded params) served to both fleets: the
    # record comparison needs both paths acting for the same policy
    env = make_env({'env': _actor_env()})
    env.reset()
    wrapper = ModelWrapper(env.net(), seed=7)
    wrapper.ensure_params(env.observation(env.players()[0]))
    snapshot = wrapper.snapshot()
    players = env.players()

    import contextlib
    backend_row = os.environ.get('BENCH_ACTOR_BACKEND', '').strip().lower()
    if backend_row == 'device':
        # device-backend row: the fused on-device rollout fleet against
        # the engine fleet — same harness, seeds, and task stream. Strict
        # envs (TicTacToe/ConnectX) byte-compare; device-contract envs
        # carry a record_version stamp instead (never silently divergent).
        # The device gather uploads a whole task block per burst, so a
        # steady-state rate needs >= 2 blocks in the timed window with the
        # full first block (compile + warmup) excluded — arrival spans
        # inside one burst only measure upload serialization.
        lanes = int(os.environ.get('BENCH_ACTOR_DEVICE_ENVS', '16'))
        warm = max(warm, lanes)
        total = warm + max(total - warm, 2 * lanes)
        with contextlib.redirect_stdout(sys.stderr):
            base = _actor_fleet_run('engine', workers, total, warm,
                                    snapshot, players)
            dev = _actor_fleet_run('device', workers, total, warm,
                                   snapshot, players)
        emit(dev['episodes_per_sec'],
             (dev['episodes_per_sec'] / base['episodes_per_sec'])
             if base['episodes_per_sec'] else 0.0,
             metric=('fleet episodes/sec (%s, device actor backend: fused '
                     'on-device rollout scan vs the engine-batched host '
                     'fleet)' % _actor_env()),
             backend=probe.get('backend', 'unknown'),
             device=probe.get('device_kind', 'unknown'),
             workers=workers, episodes=total - warm, warmup=warm,
             engine_episodes_per_sec=round(base['episodes_per_sec'], 2),
             requests_per_sec=round(dev['requests_per_sec'], 2),
             device_actor_envs=int(os.environ.get(
                 'BENCH_ACTOR_DEVICE_ENVS', '16')),
             device_plies=dev['device_plies'],
             device_divergence=dev['device_divergence'],
             records_identical=(dev['records'] == base['records']
                                and len(dev['records']) == total),
             records_stamped=dev['stamped'],
             failed_episodes=base['failed'] + dev['failed'],
             vs_baseline_def=('device-backend episodes/sec / engine '
                              'episodes/sec, identical harness, seeds '
                              'and task stream'),
             env=_actor_env(),
             run_id=telemetry.run_id(),
             geometry=('headline'
                       if (total - warm >= 12
                           and _actor_env() == 'HungryGeese')
                       else 'dryrun'))
        return
    with contextlib.redirect_stdout(sys.stderr):
        # child-process startup prints must not break the one-line contract
        base = _actor_fleet_run('worker', workers, total, warm, snapshot,
                                players)
        eng = _actor_fleet_run('engine', workers, total, warm, snapshot,
                               players)

    fill = eng['engine_requests'] / max(1, eng['engine_batches'])
    emit(eng['episodes_per_sec'],
         (eng['episodes_per_sec'] / base['episodes_per_sec'])
         if base['episodes_per_sec'] else 0.0,
         backend=probe.get('backend', 'unknown'),
         device=probe.get('device_kind', 'unknown'),
         workers=workers, episodes=total - warm, warmup=warm,
         per_worker_episodes_per_sec=round(base['episodes_per_sec'], 2),
         requests_per_sec=round(eng['requests_per_sec'], 2),
         per_worker_requests_per_sec=round(base['requests_per_sec'], 2),
         batch_fill=round(fill, 2),
         records_identical=(eng['records'] == base['records']
                            and len(eng['records']) == total),
         failed_episodes=base['failed'] + eng['failed'],
         vs_baseline_def=('engine episodes/sec / per-worker B=1 '
                          'episodes/sec, identical harness, seeds and '
                          'task stream'),
         env=_actor_env(),
         run_id=telemetry.run_id(),
         geometry=('headline'
                   if (workers >= 4 and total - warm >= 12
                       and _actor_env() == 'HungryGeese')
                   else 'dryrun'))


def _mesh_child():
    """BENCH_MODE=mesh subprocess: measure ONE mesh size.

    The virtual-device count (XLA_FLAGS) must be fixed before jax imports,
    hence a process per row. Prints exactly one JSON dict on stdout:
    wall steps/sec of the sharded program, the per-shard strong-scaling
    projection (single-device rate at batch B/ndev), the first-step loss
    from fixed seeds (cross-mesh parity), and the per-device staged batch
    bytes counted by ``mesh_shard_bytes_total``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import handyrl_tpu
    handyrl_tpu.setup_compile_cache()
    from handyrl_tpu import telemetry
    from handyrl_tpu.ops.train_step import build_update_step
    from handyrl_tpu.parallel import partition
    from handyrl_tpu.parallel.mesh import make_mesh, shard_batch

    ndev = int(os.environ['BENCH_MESH_CHILD'])
    B = int(os.environ.get('BENCH_MESH_BATCH', '128'))
    T = int(os.environ.get('BENCH_MESH_T', '16'))
    steps = int(os.environ.get('BENCH_MESH_STEPS', '5'))
    devices = jax.devices()
    if len(devices) < ndev:
        print(json.dumps({'ndev': ndev,
                          'error': 'only %d device(s)' % len(devices)}))
        return
    lr = jnp.asarray(1e-5, jnp.float32)
    module, cfg, batch, state = headline_setup(B, T, seed=0)
    row = {'ndev': ndev, 'batch': B, 'forward_steps': T,
           'timed_steps': steps}

    shard_bytes = telemetry.REGISTRY.counter('mesh_shard_bytes_total')
    mark = shard_bytes.value
    if ndev > 1:
        mesh = make_mesh(devices[:ndev])
        state_sh = partition.tree_shardings(mesh, state,
                                            partition.DEFAULT_RULES)
        step = build_update_step(module, cfg, mesh=mesh, donate=False,
                                 state_shardings=state_sh)
        batch = shard_batch(mesh, batch)   # per-shard host->device staging
        row['shard_bytes_per_device'] = (shard_bytes.value - mark) // ndev
    else:
        step = build_update_step(module, cfg, donate=False)
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        row['shard_bytes_per_device'] = sum(
            np.asarray(v).nbytes
            for v in jax.tree_util.tree_leaves(batch))

    # first-step loss from identical seeds: the cross-mesh parity probe
    _, metrics = step(state, batch, lr)
    row['loss'] = float(np.asarray(metrics['total']))
    sec, flops, _bytes = time_compiled_step(step, state, batch, lr, steps)
    row['wall_steps_per_sec'] = round(1.0 / sec, 4)
    row['flops_per_step'] = flops

    # per-shard strong-scaling projection: each device of a real ndev-mesh
    # runs the B/ndev program; its measured single-device rate is the
    # global step rate collectives aside (on a virtual one-core mesh the
    # wall clock above time-slices all ndev shards, so it cannot show this)
    if ndev > 1 and B % ndev == 0:
        m2, c2, b2, s2 = headline_setup(B // ndev, T, seed=0)
        step2 = build_update_step(m2, c2, donate=False)
        b2 = jax.tree_util.tree_map(jnp.asarray, b2)
        sec2, _f, _b = time_compiled_step(step2, s2, b2, lr, steps)
        row['projected_steps_per_sec'] = round(1.0 / sec2, 4)
    else:
        row['projected_steps_per_sec'] = row['wall_steps_per_sec']
    print(json.dumps(row), flush=True)


_FORCE_DEV_RE = r'--xla_force_host_platform_device_count=\d+'


def run_mesh(probe: dict):
    """BENCH_MODE=mesh: SGD-throughput scaling of the sharded learner.

    Env knobs (CI smoke shrinks them): BENCH_MESH_DEVICES ('1,2,4,8'),
    BENCH_MESH_BATCH (global batch, default 128), BENCH_MESH_T (forward
    steps, default 16), BENCH_MESH_STEPS (timed steps per row, default 5).
    On the CPU backend each mesh size runs on XLA host-device partitioning
    (a virtual mesh); real accelerators use the first ndev devices.
    """
    import re

    # One process for each chip: this parent never imports jax (the probe
    # ran in a subprocess that has exited), so each mesh-size child below,
    # run one after the other, is the only claimant of the devices.
    cpu = probe.get('backend') == 'cpu'
    ndevs = [int(x) for x in os.environ.get(
        'BENCH_MESH_DEVICES', '1,2,4,8').split(',') if x.strip()]
    rows = []
    for ndev in ndevs:
        if not cpu and int(probe.get('n', 1)) < ndev:
            continue   # not enough physical devices; no virtualizing a TPU
        env = dict(os.environ, BENCH_MESH_CHILD=str(ndev))
        if cpu:
            flags = re.sub(_FORCE_DEV_RE, '', env.get('XLA_FLAGS', ''))
            env['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=%d'
                % ndev).strip()
            env['JAX_PLATFORMS'] = 'cpu'
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        _CHILDREN.append(proc)
        out, _ = proc.communicate()
        try:
            row = json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError):
            row = {'ndev': ndev, 'error': 'child rc=%s' % proc.returncode}
        rows.append(row)

    good = [r for r in rows if 'error' not in r]
    if not good:
        emit(error='no mesh size produced a measurement',
             rows=rows, device=probe.get('device_kind', 'unknown'))
        return
    base = min(good, key=lambda r: r['ndev'])
    # scaling: wall clock where the mesh is real hardware, the per-shard
    # projection where it is host-virtualized (one core serializes shards)
    key = 'wall_steps_per_sec' if not cpu else 'projected_steps_per_sec'
    for r in good:
        r['scaling_vs_1dev'] = round(r[key] / base['wall_steps_per_sec'], 3)
        r['loss_rel_err'] = (abs(r['loss'] - base['loss'])
                             / max(abs(base['loss']), 1e-12))
    peak = good[-1]
    at4 = next((r for r in good if r['ndev'] == 4), peak)
    emit(peak[key], at4['scaling_vs_1dev'],
         backend=probe.get('backend', 'unknown'),
         device=probe.get('device_kind', 'unknown'),
         batch=base.get('batch'), forward_steps=base.get('forward_steps'),
         devices_measured=[r['ndev'] for r in good],
         rows=rows,
         virtual_mesh=cpu,
         scaling_at_max=peak['scaling_vs_1dev'],
         max_loss_rel_err=max(r['loss_rel_err'] for r in good),
         vs_baseline_def=('steps/sec scaling at 4 devices vs the 1-device '
                          'step at the same global batch; %s'
                          % ('per-shard strong-scaling projection (B/ndev '
                             'single-device rate) on the host-virtualized '
                             'mesh — the wall column time-slices every '
                             'shard onto this host\'s cores' if cpu
                             else 'measured wall clock')),
         geometry=('headline' if base.get('batch') == 128
                   and base.get('forward_steps') == 16 else 'dryrun'))


def _serve_client_load(host, port, model, obs, legal, n_clients, warmup,
                       requests, base_seed, client_factory=None):
    """Drive ``n_clients`` concurrent ServiceClients (one thread each) at
    the service: per-client warmup then ``requests`` timed sequential round
    trips. Returns (requests/sec over the timed span, latency list,
    error count). ``client_factory(ci)`` swaps the client class (the fleet
    phase routes through RoutedClient against a resolver port)."""
    import threading
    from handyrl_tpu.generation import sample_seed
    from handyrl_tpu.serving.client import ServiceClient

    latencies, errors = [], [0]
    spans = []
    lock = threading.Lock()
    barrier = threading.Barrier(n_clients)

    def run(ci):
        if client_factory is not None:
            client = client_factory(ci)
        else:
            client = ServiceClient(host, port, timeout=60.0, name='c%d' % ci)
        mine = []
        try:
            for k in range(warmup):
                client.request(model, obs, legal=legal,
                               seed=sample_seed(base_seed, (ci, k), 0))
            barrier.wait(timeout=120)
            t_start = time.monotonic()
            for k in range(requests):
                t0 = time.monotonic()
                client.request(model, obs, legal=legal,
                               seed=sample_seed(base_seed,
                                                (ci, warmup + k), 0))
                mine.append(time.monotonic() - t0)
            t_end = time.monotonic()
            with lock:
                latencies.extend(mine)
                spans.append((t_start, t_end))
        except Exception:
            with lock:
                errors[0] += 1
        finally:
            client.close()

    threads = [threading.Thread(target=run, args=(ci,),
                                name='serve-bench-%d' % ci)
               for ci in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not spans:
        return 0.0, [], errors[0]
    span = max(e for _s, e in spans) - min(s for s, _e in spans)
    return len(latencies) / max(span, 1e-9), latencies, errors[0]


def _serve_fleet_phase(env_name, wrapper, obs, legal, n_clients, requests,
                       warmup, wait_ms, single_rps):
    """The BENCH_MODE=serve fleet phase: a resolver + BENCH_SERVE_REPLICAS
    managed replicas under the same client load, routed through
    RoutedClient. Returns the extra emit keys (fleet_* scaling vs the
    single-service row, rolling-promote p99 before/during, resolver drain
    exit code), or {} when BENCH_SERVE_REPLICAS=0 disables the phase."""
    import contextlib
    import shutil
    import signal as _signal
    import tempfile
    import threading
    import numpy as np
    from handyrl_tpu.serving.fleet import RoutedClient
    from handyrl_tpu.serving.registry import ModelRegistry

    replicas = int(os.environ.get('BENCH_SERVE_REPLICAS', '2'))
    if replicas <= 0:
        return {}
    root = tempfile.mkdtemp(prefix='bench_fleet_registry.')
    proc = None
    try:
        with contextlib.redirect_stdout(sys.stderr):
            reg = ModelRegistry(root)
            reg.publish('bench', snapshot=wrapper.snapshot(), version=1,
                        steps=1, promote=True)
            # the rolling-promote candidate: published, not yet champion
            reg.publish('bench', snapshot=wrapper.snapshot(), version=2,
                        steps=2, promote=False)
        proc = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--fleet',
             '--env', env_name, '--registry', root, '--port', '0',
             '--line', 'bench', '--replicas', str(replicas),
             '--heartbeat', '0.5', '--wait-ms', str(wait_ms),
             '--max-clients', str(n_clients + 8)],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        _CHILDREN.append(proc)
        ready = json.loads(proc.stdout.readline())['fleet_ready']
        port = int(ready['port'])
        model = 'bench@champion'

        def routed(ci):
            return RoutedClient('localhost', port, timeout=60.0,
                                name='f%d' % ci)

        fleet_rps, lat_before, err_f = _serve_client_load(
            'localhost', port, model, obs, legal, n_clients, warmup,
            requests, base_seed=41, client_factory=routed)

        # rolling promote under load: every replica warms bench@2 before
        # the champion flips, so the client-side p99 must not blip
        admin = RoutedClient('localhost', port, timeout=60.0, name='padm')
        promote_result = {}

        def do_promote():
            try:
                promote_result.update(admin.promote('bench@2', timeout=120))
            except Exception as exc:  # noqa: BLE001 — reported in the row
                promote_result['error'] = str(exc)[:200]

        pt = threading.Thread(target=do_promote, name='bench-promote')
        pt.start()
        _rps_during, lat_during, err_p = _serve_client_load(
            'localhost', port, model, obs, legal, n_clients, 0,
            requests, base_seed=43, client_factory=routed)
        pt.join(timeout=120)
        admin.close()

        # resolver SIGTERM: drains managed replicas, exits 75
        proc.send_signal(_signal.SIGTERM)
        try:
            fleet_exit = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.terminate()
            fleet_exit = None

        def p99(lat):
            ms = [1e3 * v for v in lat]
            return round(float(np.percentile(ms, 99)), 2) if ms else 0.0

        # replication scaling needs cores >= replicas: on a starved host
        # the replicas time-slice one core and fleet_vs_single measures
        # routing overhead, not the scaling headline — stamp the cores so
        # the row is interpretable either way
        cores = os.cpu_count() or 1
        return {
            'fleet_replicas': replicas,
            'fleet_host_cores': cores,
            'fleet_requests_per_sec': round(fleet_rps, 2),
            'fleet_vs_single': (round(fleet_rps / single_rps, 2)
                                if single_rps else 0.0),
            'fleet_client_errors': err_f + err_p,
            'promote_p99_before_ms': p99(lat_before),
            'promote_p99_during_ms': p99(lat_during),
            'promote_warmed': promote_result.get('warmed', []),
            'promote_error': promote_result.get('error'),
            'fleet_drain_exit_code': fleet_exit,
        }
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        shutil.rmtree(root, ignore_errors=True)


def run_serve(probe: dict):
    """BENCH_MODE=serve: the standalone serving tier, CPU-measurable.

    Env knobs (CI smoke shrinks them): BENCH_SERVE_CLIENTS (default 8),
    BENCH_SERVE_REQUESTS (timed requests per client, default 40),
    BENCH_SERVE_WARMUP (per client, default 4), BENCH_SERVE_ENV (default
    HungryGeese), BENCH_SERVE_WAIT_MS (engine batch_wait_ms, default 2),
    BENCH_SERVE_DRAIN (in-flight requests per client through the SIGTERM,
    default 3), BENCH_SERVE_REPLICAS (fleet-phase managed replicas,
    default 2, 0 skips the fleet phase).
    """
    import contextlib
    import shutil
    import signal as _signal
    import tempfile
    import numpy as np
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import sample_seed
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.serving.client import ServiceClient
    from handyrl_tpu.serving.registry import ModelRegistry

    env_name = os.environ.get('BENCH_SERVE_ENV', 'HungryGeese')
    n_clients = int(os.environ.get('BENCH_SERVE_CLIENTS', '8'))
    requests = int(os.environ.get('BENCH_SERVE_REQUESTS', '40'))
    warmup = int(os.environ.get('BENCH_SERVE_WARMUP', '4'))
    wait_ms = os.environ.get('BENCH_SERVE_WAIT_MS', '2')
    drain_n = int(os.environ.get('BENCH_SERVE_DRAIN', '3'))
    engine_backend = os.environ.get(
        'BENCH_SERVE_ENGINE_BACKEND', 'cpu').strip().lower() or 'cpu'

    env = make_env({'env': env_name})
    env.reset()
    obs = env.observation(env.players()[0])
    legal = env.legal_actions(env.players()[0])
    wrapper = ModelWrapper(env.net(), seed=7)
    wrapper.ensure_params(obs)

    root = tempfile.mkdtemp(prefix='bench_serve_registry.')
    try:
        with contextlib.redirect_stdout(sys.stderr):
            ModelRegistry(root).publish('bench', snapshot=wrapper.snapshot(),
                                        version=1, steps=1, promote=True)
        proc = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving',
             '--env', env_name, '--registry', root, '--port', '0',
             '--line', 'bench', '--wait-ms', str(wait_ms),
             '--engine-backend', engine_backend,
             '--max-clients', str(n_clients + 4)],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        _CHILDREN.append(proc)
        ready = json.loads(proc.stdout.readline())['serving_ready']
        port = int(ready['port'])
        model = 'bench@champion'

        # single-client reference first: the vs_baseline denominator (what
        # one sequential client extracts from the same service)
        base_rps, _lat1, err1 = _serve_client_load(
            'localhost', port, model, obs, legal, 1, warmup,
            max(8, requests // 2), base_seed=29)
        many_rps, latencies, err_n = _serve_client_load(
            'localhost', port, model, obs, legal, n_clients, warmup,
            requests, base_seed=31)

        status_client = ServiceClient('localhost', port, name='status')
        status = status_client.status(timeout=30)
        fill = (status.get('engine_requests', 0)
                / max(1, status.get('engine_batches', 1)))

        # tracing-off vs tracing-on(rate 0.1) adjacent A/B pair (the PR 7
        # ingest-pair shape): the 'trace' admin op flips the SAME warmed
        # service process between legs, alternating best-of-3 per side —
        # the serving-path span cost is below one-shot run-to-run noise
        from handyrl_tpu import telemetry as _tel
        trace_rate = float(os.environ.get('BENCH_TRACE_RATE', '0.1'))
        trace_dir_t = tempfile.mkdtemp(prefix='bench_serve_trace.')
        tr_rounds = []
        try:
            for i in range(3):
                status_client.call_admin({'op': 'trace', 'dir': trace_dir_t,
                                          'rate': trace_rate}, timeout=30)
                _tel.configure_tracing(trace_dir_t, trace_rate, force=True)
                on_rps, _lt, _et = _serve_client_load(
                    'localhost', port, model, obs, legal, n_clients, 0,
                    requests, base_seed=51 + i)
                status_client.call_admin({'op': 'trace', 'dir': '',
                                          'rate': None}, timeout=30)
                _tel.configure_tracing('', None, force=True)
                off_rps, _lt, _et = _serve_client_load(
                    'localhost', port, model, obs, legal, n_clients, 0,
                    requests, base_seed=61 + i)
                tr_rounds.append((on_rps, off_rps))
        finally:
            try:
                status_client.call_admin({'op': 'trace', 'dir': '',
                                          'rate': None}, timeout=30)
            except Exception:   # noqa: BLE001 — best-effort reset
                pass
            _tel.configure_tracing('', None, force=True)
            shutil.rmtree(trace_dir_t, ignore_errors=True)
        tracing_on_rps = max(on for on, _ in tr_rounds)
        tracing_off_rps = max(off for _, off in tr_rounds)
        tracing_overhead = (100.0 * (1.0 - tracing_on_rps / tracing_off_rps)
                            if tracing_off_rps else 0.0)

        # measured graceful drain: every in-flight request through the
        # SIGTERM must be ANSWERED (ok or an explicit drain error), and the
        # service must exit 75 (the PreemptionGuard supervisor contract)
        rids = [status_client.submit(model, obs, legal=legal,
                                     seed=sample_seed(37, (0, k), 0))
                for k in range(drain_n * n_clients)]
        t_term = time.monotonic()
        proc.send_signal(_signal.SIGTERM)
        drained = unanswered = 0
        for rid in rids:
            try:
                status_client.collect(rid, timeout=30)
                drained += 1
            except TimeoutError:
                unanswered += 1
            except Exception:
                drained += 1          # an error reply is still an answer
        try:
            exit_code = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.terminate()
            exit_code = None
        drain_seconds = time.monotonic() - t_term
        status_client.close()

        # fleet phase: resolver + replicas under the same load, routed —
        # fleet_vs_single is the replication scaling headline
        fleet_keys = _serve_fleet_phase(
            env_name, wrapper, obs, legal, n_clients, requests, warmup,
            wait_ms, many_rps)

        lat_ms = sorted(1e3 * v for v in latencies)
        pct = (lambda q: round(float(np.percentile(lat_ms, q)), 2)) \
            if lat_ms else (lambda q: 0.0)
        emit(many_rps, (many_rps / base_rps) if base_rps else 0.0,
             backend=probe.get('backend', 'unknown'),
             device=probe.get('device_kind', 'unknown'),
             env=env_name, clients=n_clients,
             engine_backend=engine_backend,
             requests_per_client=requests,
             requests_measured=len(lat_ms),
             single_client_requests_per_sec=round(base_rps, 2),
             p50_ms=pct(50), p95_ms=pct(95), p99_ms=pct(99),
             batch_fill=round(fill, 2),
             shed_total=int(status.get('shed', 0)),
             client_errors=err1 + err_n,
             drain_requests=len(rids), drain_answered=drained,
             drain_unanswered=unanswered,
             drain_seconds=round(drain_seconds, 2),
             drain_exit_code=exit_code,
             tracing_on_requests_per_sec=round(tracing_on_rps, 2),
             tracing_off_requests_per_sec=round(tracing_off_rps, 2),
             tracing_overhead_pct=round(tracing_overhead, 2),
             trace_sample_rate=trace_rate,
             **fleet_keys,
             vs_baseline_def=('%d-client req/s over single-client req/s '
                              'against the same service — the continuous-'
                              'batching concurrency gain' % n_clients),
             geometry=('headline'
                       if (n_clients >= 8 and requests >= 32
                           and env_name == 'HungryGeese') else 'dryrun'))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_gateway(probe: dict):
    """BENCH_MODE=gateway: the match-gateway session tier, CPU-measurable.

    Env knobs (CI smoke shrinks them): BENCH_GATEWAY_SESSIONS (concurrent
    sessions, default 8), BENCH_GATEWAY_MATCHES (matches per session,
    default 2), BENCH_GATEWAY_ENV (default TicTacToe — short matches, so
    the rate measures the session machinery, not the game), and
    BENCH_GATEWAY_REPLICAS (default 2). BENCH_GATEWAY_KILL=0 disables the
    mid-run replica SIGKILL (on by default: the row's dropped_sessions=0
    under the kill IS the robustness headline).
    """
    import contextlib
    import random
    import shutil
    import signal as _signal
    import tempfile
    import threading
    import numpy as np
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.serving.fleet import RoutedClient
    from handyrl_tpu.serving.gateway import GatewayClient
    from handyrl_tpu.serving.registry import ModelRegistry

    env_name = os.environ.get('BENCH_GATEWAY_ENV', 'TicTacToe')
    n_sessions = int(os.environ.get('BENCH_GATEWAY_SESSIONS', '8'))
    matches = int(os.environ.get('BENCH_GATEWAY_MATCHES', '2'))
    replicas = int(os.environ.get('BENCH_GATEWAY_REPLICAS', '2'))
    kill = os.environ.get('BENCH_GATEWAY_KILL', '1') != '0'

    env = make_env({'env': env_name})
    env.reset()
    obs = env.observation(env.players()[0])
    wrapper = ModelWrapper(env.net(), seed=7)
    wrapper.ensure_params(obs)

    root = tempfile.mkdtemp(prefix='bench_gateway_registry.')
    fleet_proc = gw_proc = rc = None
    try:
        with contextlib.redirect_stdout(sys.stderr):
            ModelRegistry(root).publish('bench', snapshot=wrapper.snapshot(),
                                        version=1, steps=1, promote=True)
        fleet_proc = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--fleet',
             '--env', env_name, '--registry', root, '--port', '0',
             '--line', 'bench', '--replicas', str(replicas),
             '--heartbeat', '0.3'],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        _CHILDREN.append(fleet_proc)
        fleet_port = int(json.loads(
            fleet_proc.stdout.readline())['fleet_ready']['port'])
        gw_proc = subprocess.Popen(
            [sys.executable, '-m', 'handyrl_tpu.serving', '--gateway',
             '--resolver', 'localhost:%d' % fleet_port,
             '--registry', root, '--env', env_name,
             '--gateway-model', 'bench@champion',
             '--gateway-workers', str(min(8, n_sessions)),
             '--max-sessions', str(n_sessions + 4), '--seed', '11'],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        _CHILDREN.append(gw_proc)
        gport = int(json.loads(
            gw_proc.stdout.readline())['gateway_ready']['port'])

        ply_lat = []
        lat_lock = threading.Lock()
        errors = [0]

        def play_matches(ci, n, collect=True):
            rng = random.Random(1000 + ci)
            done = 0
            cl = GatewayClient('localhost', gport, timeout=60.0,
                               name='b%d' % ci)
            try:
                for _ in range(n):
                    r = cl.open(env_name, seat=0)
                    sid = r['sid']
                    while not r.get('done'):
                        action = (rng.choice(r['legal'])
                                  if r.get('to_move') and r.get('legal')
                                  else None)
                        t0 = time.monotonic()
                        r = cl.play(sid, action)
                        if collect:
                            with lat_lock:
                                ply_lat.append(time.monotonic() - t0)
                    done += 1
            except Exception:   # noqa: BLE001 — reported in the row
                errors[0] += 1
            finally:
                cl.close()
            return done

        # one warmup match first (replica engines compile on first touch),
        # then the single-session reference: the vs_baseline denominator
        play_matches(0, 1, collect=False)
        t0 = time.monotonic()
        base_done = play_matches(0, max(2, matches), collect=False)
        base_rate = base_done / max(time.monotonic() - t0, 1e-9)

        # N concurrent sessions, a replica SIGKILLed mid-run: every match
        # must still complete (stranded sessions rebuilt by journal replay)
        rc = RoutedClient('localhost', fleet_port, timeout=30.0)
        table = {r['replica']: r for r in rc.replicas()}
        victim = sorted(table)[0] if (kill and len(table) > 1) else None
        completed = [0] * n_sessions
        threads = [threading.Thread(
            target=lambda ci=ci: completed.__setitem__(
                ci, play_matches(ci, matches)),
            name='bench-gw-%d' % ci) for ci in range(n_sessions)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        if victim is not None:
            time.sleep(0.5)
            try:
                os.kill(int(table[victim]['pid']), _signal.SIGKILL)
            except (OSError, KeyError, TypeError):
                victim = None
        for t in threads:
            t.join(timeout=300)
        many_rate = sum(completed) / max(time.monotonic() - t0, 1e-9)

        status_cl = GatewayClient('localhost', gport, timeout=30.0,
                                  name='bstatus')
        status = status_cl.status()

        # tracing-off vs tracing-on(rate 0.1) adjacent A/B pair (the PR 7
        # ingest-pair shape): the 'trace' admin op flips the SAME warmed
        # gateway + every replica between legs, alternating best-of-3 per
        # side on the sequential single-session match rate
        from handyrl_tpu import telemetry as _tel
        from handyrl_tpu.serving.client import (ServiceClient,
                                                parse_endpoint)
        trace_rate = float(os.environ.get('BENCH_TRACE_RATE', '0.1'))
        trace_dir_t = tempfile.mkdtemp(prefix='bench_gateway_trace.')

        def toggle_tracing(dirpath, rate):
            status_cl._call({'op': 'trace', 'dir': dirpath, 'rate': rate})
            for row in rc.replicas():
                try:
                    host, rport = parse_endpoint(row['endpoint'])
                    sc = ServiceClient(host, rport, timeout=30.0,
                                       name='btrace', dial_retries=1)
                    try:
                        sc.call_admin({'op': 'trace', 'dir': dirpath,
                                       'rate': rate}, timeout=30)
                    finally:
                        sc.close()
                except Exception:  # noqa: BLE001 — a corpse mid-respawn
                    pass
            _tel.configure_tracing(dirpath, rate, force=True)

        tr_rounds = []
        # a TicTacToe match is ~10-20ms here, so a 2-match leg is pure
        # scheduler noise — each measured leg needs enough matches that
        # the rate estimate is dominated by ply work, not jitter
        ab_matches = max(10, matches)
        ab_rounds = int(os.environ.get('BENCH_TRACE_ROUNDS', '5'))
        try:
            # one unmeasured leg first — the replica respawned after the
            # SIGKILL recompiles its engine on first touch, and that cost
            # must not land in either side of the pair — then alternate
            # which side goes first per round so settling drift cancels
            play_matches(99, ab_matches, collect=False)
            for i in range(ab_rounds):
                legs = {}
                order = ('on', 'off') if i % 2 == 0 else ('off', 'on')
                for leg in order:
                    if leg == 'on':
                        toggle_tracing(trace_dir_t, trace_rate)
                    else:
                        toggle_tracing('', None)
                    t1 = time.monotonic()
                    d = play_matches((100 if leg == 'on' else 200) + i,
                                     ab_matches, collect=False)
                    legs[leg] = d / max(time.monotonic() - t1, 1e-9)
                tr_rounds.append((legs['on'], legs['off']))
        finally:
            try:
                toggle_tracing('', None)
            except Exception:   # noqa: BLE001 — best-effort reset
                pass
            shutil.rmtree(trace_dir_t, ignore_errors=True)
        tracing_on_rate = max(on for on, _ in tr_rounds)
        tracing_off_rate = max(off for _, off in tr_rounds)
        tracing_overhead = (100.0 * (1.0 - tracing_on_rate
                                     / tracing_off_rate)
                            if tracing_off_rate else 0.0)
        status_cl.close()

        # gateway SIGTERM drains to exit 75 (the supervisor contract),
        # then the fleet follows
        gw_proc.send_signal(_signal.SIGTERM)
        try:
            gw_exit = gw_proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            gw_proc.terminate()
            gw_exit = None
        fleet_proc.send_signal(_signal.SIGTERM)
        try:
            fleet_exit = fleet_proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            fleet_proc.terminate()
            fleet_exit = None

        lat_ms = sorted(1e3 * v for v in ply_lat)
        pct = (lambda q: round(float(np.percentile(lat_ms, q)), 2)) \
            if lat_ms else (lambda q: 0.0)
        emit(many_rate, (many_rate / base_rate) if base_rate else 0.0,
             backend=probe.get('backend', 'unknown'),
             device=probe.get('device_kind', 'unknown'),
             env=env_name, sessions=n_sessions,
             matches_per_session=matches,
             matches_completed=sum(completed),
             fleet_replicas=replicas,
             host_cores=os.cpu_count() or 1,
             single_session_matches_per_sec=round(base_rate, 2),
             ply_p50_ms=pct(50), ply_p95_ms=pct(95), ply_p99_ms=pct(99),
             plies_measured=len(lat_ms),
             killed_replica=victim,
             dropped_sessions=int(status.get('dropped', 0)),
             reconstructs=int(status.get('reconstructs', 0)),
             replayed_plies=int(status.get('replayed_plies', 0)),
             reconstruct_mismatches=int(status.get('mismatches', 0)),
             handoffs=int(status.get('handoffs', 0)),
             shed_total=int(status.get('shed', 0)),
             outcomes_recorded=int(status.get('outcomes', 0)),
             client_errors=errors[0],
             tracing_on_matches_per_sec=round(tracing_on_rate, 2),
             tracing_off_matches_per_sec=round(tracing_off_rate, 2),
             tracing_overhead_pct=round(tracing_overhead, 2),
             trace_sample_rate=trace_rate,
             gateway_drain_exit_code=gw_exit,
             fleet_drain_exit_code=fleet_exit,
             vs_baseline_def=('%d-session matches/s over single-session '
                              'matches/s against the same gateway — the '
                              'session concurrency gain' % n_sessions),
             geometry=('headline'
                       if (n_sessions >= 8 and matches >= 2
                           and env_name == 'TicTacToe') else 'dryrun'))
    finally:
        if rc is not None:
            rc.close()
        for proc in (gw_proc, fleet_proc):
            if proc is not None and proc.poll() is None:
                proc.kill()
        shutil.rmtree(root, ignore_errors=True)


def main():
    if os.environ.get('BENCH_MESH_CHILD'):
        # mesh-mode measurement subprocess: one JSON row, no probe/alarm
        # machinery (the parent owns the deadline and emit contract)
        _mesh_child()
        return
    signal.signal(signal.SIGTERM, _shutdown)
    signal.signal(signal.SIGINT, _shutdown)
    deadline = float(os.environ.get('BENCH_DEADLINE_SEC', '600'))
    signal.signal(signal.SIGALRM, _shutdown)
    signal.alarm(int(deadline))

    probe = probe_backend(min(120.0, deadline / 3))
    if 'error' in probe:
        emit(error='backend unavailable: ' + probe['error'])
        sys.exit(1)
    try:
        if _active_mode() == 'ingest':
            run_ingest(probe)
        elif _active_mode() == 'actor':
            run_actor(probe)
        elif _active_mode() == 'mesh':
            run_mesh(probe)
        elif _active_mode() == 'serve':
            run_serve(probe)
        elif _active_mode() == 'gateway':
            run_gateway(probe)
        else:
            run_bench(probe)
    except Exception as exc:  # noqa: BLE001 — the contract is: always emit
        emit(error='%s: %s' % (type(exc).__name__, str(exc)[:200]),
             device=probe.get('device_kind', 'unknown'))
    # a run that measured nothing is a failed run, whatever it printed
    sys.exit(0 if _MEASURED else 1)


if __name__ == '__main__':
    main()
