"""Learner orchestration: trainer loop, batch prefetch, epoch cadence.

Architecture (counterpart of the reference train.py, reshaped for TPU):

  * ``Trainer`` — background thread owning the jit/pjit-compiled update step
    (ops/train_step.py). The Adam step, clipping, and losses all live on
    device; the host only feeds batches and the EMA-scheduled learning rate
    (lr = 3e-8 * data_cnt_ema / (1 + steps*1e-5), reference
    train.py:327-331,382-384). On a multi-device mesh the batch is sharded
    over 'data' and XLA all-reduces gradients over ICI (replacing
    nn.DataParallel).

  * ``Batcher`` — prefetch threads turning buffered episodes into batches
    (recency-biased window sampling, ops/batch.py) ahead of the update step.

  * ``Learner`` — episode/eval accounting, epoch cadence (update every
    ``update_episodes`` returned episodes), checkpointing
    (models/<epoch>.ckpt msgpack params — loading cannot execute code), and
    two generation front-ends:
      - in-process ``BatchedGenerator`` (TPU-first default): N envs against
        one batched device inference;
      - the 4-RPC worker protocol ('args'/'episode'/'result'/'model') over
        WorkerCluster (local processes) or WorkerServer (remote hosts),
        wire-compatible in shape with the reference (train.py:541-627).

Log line formats (epoch / win rate / generation stats / loss / updated
model) match the reference so its plot tooling carries over (SURVEY.md §5.5).
"""

from __future__ import annotations

import json
import os
import queue
import random
import threading
import time
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import psutil

from . import guard as guard_mod
from . import league as league_mod
from . import claim_devices, telemetry
from .connection import RESUME_KIND
from .connection import pack as conn_pack
from .connection import unpack as conn_unpack
from .environment import make_env, prepare_env
from .fault import FleetController, LedgerJournal, TaskLedger
from .generation import BatchedEvaluator, BatchedGenerator
from .model import ModelWrapper
from .ops.batch import make_batch, select_episode
from .ops.losses import LossConfig
from .ops.train_step import TrainState, build_update_step, init_train_state
from .parallel.mesh import make_mesh, shard_batch
from .spool import EpisodeSpool
from .utils.fetch import put_tree
from .utils.fs import append_jsonl, atomic_write_bytes, \
    checksummed_write_bytes, rotate_file
from .worker import WorkerCluster, WorkerServer, gather_claims_device

_LOG = telemetry.get_logger('train')


class TracedBatch:
    """A built batch plus the sampled episode trace ids of the windows in
    it — the thread-batcher's counterpart of SharedBatch.trace_ids, wrapped
    only while episode tracing is active so the hot path stays untouched
    when it is off."""

    __slots__ = ('batch', 'trace_ids')

    def __init__(self, batch, trace_ids):
        self.batch = batch
        self.trace_ids = trace_ids


def _selected_trace_ids(selected) -> List[str]:
    """Deduplicated, deterministically-sampled trace ids of the episodes a
    batch's windows were selected from (recency bias repeats episodes)."""
    out = []
    for sel in selected:
        tid = telemetry.episode_trace_id(sel.get('args') or {})
        if tid and telemetry.trace_sampled(tid):
            out.append(tid)
    return sorted(set(out))


def _batcher_process(conn, bid: int):
    """Child-process batch builder (config: batcher_processes=True)."""
    from .connection import force_cpu_backend
    force_cpu_backend()
    from .ops.batch import make_block_cache
    telemetry.set_process_label('batcher-%d' % bid)
    _LOG.info('started batcher process %d', bid)
    cache, have_cache = None, False
    while True:
        selected, args = conn.recv()
        if not have_cache:
            cache, have_cache = make_block_cache(args), True
        conn.send(make_batch(selected, args, cache=cache))


_SHM_SLOTS = 4   # in-flight shared-memory batches per batcher child

# The fused loop: how long one eval share may hold the loop, as a share of
# what the iteration's training stretch held it (Learner._run_eval_share).
EVAL_SHARE_OF_TRAINING = 0.25


def _is_free_msg(msg) -> bool:
    return (isinstance(msg, tuple) and len(msg) == 2
            and msg[0] == '__free__')


def _batcher_process_shm(conn, bid: int):
    """Child-process batch builder writing into shared-memory arenas
    (config: batcher_processes + batcher_shared_memory).

    Batches are assembled IN PLACE in a small ring of SharedMemory slots;
    only a slot descriptor crosses the pipe — no pickle, no copy. The first
    batch bootstraps the layout: it is built host-side, sized into the ring
    (spec + segment names ride along in its descriptor), and copied in
    once. A slot is reused only after the trainer's ``('__free__', slot)``
    message confirms the staged device transfer read it.
    """
    from .connection import force_cpu_backend
    force_cpu_backend()
    from .ops.shm_batch import ArenaRing, batch_spec, copy_into
    from .utils.timing import StageTimer
    telemetry.set_process_label('batcher-%d' % bid)
    _LOG.info('started shm batcher process %d', bid)
    from .ops.batch import make_block_cache
    ring = None
    timer = StageTimer()
    cache, have_cache = None, False

    def recv_job():
        while True:
            msg = conn.recv()
            if _is_free_msg(msg):
                ring.release(msg[1])
                continue
            return msg

    def acquire_slot():
        slot = ring.acquire()
        while slot is None:   # all slots in flight: block on a free message
            msg = conn.recv()
            if not _is_free_msg(msg):
                raise RuntimeError('expected a slot-free message, got %r'
                                   % (msg,))
            ring.release(msg[1])
            slot = ring.acquire()
        return slot

    try:
        while True:
            selected, args = recv_job()
            desc = {'bid': bid}
            if not have_cache:
                cache, have_cache = make_block_cache(args), True
            if ring is None:
                batch = make_batch(selected, args, timer=timer, cache=cache)
                ring = ArenaRing(batch_spec(batch), slots=_SHM_SLOTS)
                slot = ring.acquire()
                copy_into(ring.views[slot], batch)
                desc['spec'] = ring.spec
                desc['names'] = ring.names
            else:
                slot = acquire_slot()
                make_batch(selected, args, out=ring.views[slot], timer=timer,
                           cache=cache)
            desc['slot'] = slot
            desc['timing'] = timer.snapshot(reset=True)
            if telemetry.trace_enabled():
                # sampled episode ids of this slot's windows: the trainer's
                # train_step trace event links back through them
                desc['trace'] = _selected_trace_ids(selected)
            conn.send(desc)
    finally:
        # this process OWNS the segments: unlink them on any exit (pipe
        # EOF, crash, ...) so an aborted run strands nothing in /dev/shm
        if ring is not None:
            ring.close()


class Batcher:
    """Batch prefetcher over the shared episode deque.

    Default: prefetch threads (bz2/numpy release the GIL for the heavy
    parts). With ``batcher_processes: True``, window selection stays in the
    learner process and make_batch fans out to spawned CPU processes via
    JobPool — the reference's num_batchers subprocess layout
    (train.py:270-318). ``batcher_shared_memory: True`` additionally swaps
    the pickled batch-over-pipe return for shared-memory arenas the
    children fill in place (ops/shm_batch.py): ``batch()`` then yields
    ``SharedBatch`` wrappers whose ``release()`` hands the slot back.

    ``timer`` (utils.timing.StageTimer) aggregates the select/decode/
    assemble stage breakdown across all batcher threads/processes.
    """

    def __init__(self, args: Dict[str, Any], episodes: deque, timer=None):
        self.args = args
        self.episodes = episodes
        self.timer = timer
        # decoded-block LRU shared by every batcher THREAD (each spawned
        # process keeps its own); recency-biased selection re-reads the
        # same episodes constantly, so steady-state decode cost ~vanishes
        from .ops.batch import make_block_cache
        self.cache = make_block_cache(args)
        self.output_queue: queue.Queue = queue.Queue(maxsize=8)
        self._started = False
        self.stop_flag = False
        self._threads: List[threading.Thread] = []
        self._executor = None
        self._arena_map = None
        self._shm_layouts: Dict[int, tuple] = {}
        # policy-lag accounting: window SELECTION is the consumption point,
        # so lag-in-epochs (learner epoch - the model_id that generated the
        # episode) and age-in-seconds (now - learner ingest stamp) are
        # observed here, for every selection path (threads and processes).
        # ``epoch_fn`` is installed by the Learner (it owns model_epoch).
        self.epoch_fn = None
        self._m_lag = telemetry.REGISTRY.histogram(
            'policy_lag_epochs', buckets=telemetry.LAG_EPOCH_BUCKETS)
        self._m_age = telemetry.REGISTRY.histogram(
            'sample_age_seconds', buckets=telemetry.AGE_SECOND_BUCKETS)

    def _observe_lag(self, selected):
        fn = self.epoch_fn
        if fn is None or not telemetry.enabled():
            return
        epoch, now = int(fn()), time.time()
        for sel in selected:
            args = sel.get('args') or {}
            for mid in (args.get('model_id') or {}).values():
                if mid is None or mid < 0:
                    continue
                self._m_lag.observe(max(0, epoch - int(mid)))
            rt = sel.get('recv_time')
            if rt is not None:
                self._m_age.observe(max(0.0, now - float(rt)))

    def _selector(self):
        while True:
            t0 = time.perf_counter()
            try:
                selected = [select_episode(self.episodes, self.args)
                            for _ in range(self.args['batch_size'])]
            except (IndexError, ValueError):   # buffer transiently empty
                time.sleep(0.1)
                continue
            if self.timer is not None:
                self.timer.add('select', time.perf_counter() - t0)
            self._observe_lag(selected)
            # strip non-picklable/irrelevant entries from the job payload
            job_args = {k: v for k, v in self.args.items()
                        if k in ('turn_based_training', 'observation',
                                 'forward_steps', 'burn_in_steps',
                                 'compress_steps', 'maximum_episodes',
                                 'decode_cache_blocks')}
            yield (selected, job_args)

    def run(self):
        if self._started:
            return
        self._started = True
        if self.args.get('batcher_processes'):
            from .connection import JobPool
            if self.args.get('batcher_shared_memory'):
                from .ops.shm_batch import ArenaMap
                self._arena_map = ArenaMap()
                self._executor = JobPool(
                    _batcher_process_shm, self._selector(),
                    self.args['num_batchers'], transform=self._map_shm)
            else:
                self._executor = JobPool(
                    _batcher_process, self._selector(),
                    self.args['num_batchers'])
            self._executor.start()
            return
        for i in range(self.args['num_batchers']):
            t = threading.Thread(target=self._worker, args=(i,),
                                 name='batcher-%d' % i, daemon=True)
            t.start()
            self._threads.append(t)

    def _map_shm(self, desc):
        """Turn a child's slot descriptor into a zero-copy SharedBatch
        (runs in the JobPool dispatcher thread)."""
        from .ops.shm_batch import SharedBatch
        bid = desc['bid']
        if 'spec' in desc:
            self._shm_layouts[bid] = (desc['spec'], desc['names'])
        spec, names = self._shm_layouts[bid]
        views = self._arena_map.attach(names[desc['slot']], spec)
        if self.timer is not None and desc.get('timing'):
            for stage, row in desc['timing'].items():
                self.timer.add(stage, row['s'], int(row['n']))
        pool, slot = self._executor, desc['slot']
        return SharedBatch(views,
                           lambda: pool.send_to(bid, ('__free__', slot)),
                           trace_ids=desc.get('trace'))

    def _worker(self, bid: int):
        _LOG.info('started batcher %d', bid)
        while not self.stop_flag:
            try:
                t0 = time.perf_counter()
                selected = [select_episode(self.episodes, self.args)
                            for _ in range(self.args['batch_size'])]
                if self.timer is not None:
                    self.timer.add('select', time.perf_counter() - t0)
                self._observe_lag(selected)
                batch = make_batch(selected, self.args, timer=self.timer,
                                   cache=self.cache)
                if telemetry.trace_enabled():
                    batch = TracedBatch(batch, _selected_trace_ids(selected))
            except (IndexError, ValueError):
                time.sleep(0.1)
                continue
            while not self.stop_flag:
                try:
                    self.output_queue.put(batch, timeout=0.5)
                    break
                except queue.Full:
                    continue

    def batch(self, timeout: Optional[float] = None):
        q = (self._executor.output_queue if self._executor is not None
             else self.output_queue)
        telemetry.gauge('batcher_queue_depth').set(q.qsize())
        return q.get(timeout=timeout)

    def stop(self):
        self.stop_flag = True
        for t in self._threads:
            t.join(timeout=5)
        # NOTE: the shared-memory mappings (_arena_map) are deliberately NOT
        # closed here — the trainer thread may still be staging a mapped
        # batch (device_put reads the pages) when shutdown begins, and
        # unmapping under it is a segfault. The set of segments is small
        # and fixed (num_batchers x _SHM_SLOTS); the OS reclaims them at
        # process exit, and the children's resource trackers unlink the
        # names when the (daemon) children die with us.


def train_state_bytes(host_state: TrainState, steps: int,
                      data_cnt_ema: float) -> bytes:
    """``trainer_state.ckpt``'s bytes from plain host values: what
    :meth:`Trainer.load_state_bytes` reads back. Takes the counters as
    arguments so that a checkpoint captured at one epoch boundary can be
    serialised while the live trainer has moved on."""
    from flax import serialization
    return serialization.to_bytes({'state': host_state, 'steps': steps,
                                   'data_cnt_ema': data_cnt_ema})


class Trainer:
    """SGD loop thread: compiled update step + EMA learning-rate schedule."""

    def __init__(self, args: Dict[str, Any], wrapper: ModelWrapper):
        self.args = args
        self.wrapper = wrapper
        self.episodes: deque = deque()
        self.cfg = LossConfig.from_args(args)
        self.device_cfg = self.cfg   # may be relayered by the ingest gate

        # mesh construction: the 'data' axis carries the batch, the 'model'
        # axis (config parallel.model_parallel) is reserved for tensor-
        # parallel partition rules. jax.devices() is the GLOBAL set, so on
        # a multi-host job (parallel/multihost.py initialized by
        # train_main) the mesh spans every process's devices.
        par = args.get('parallel') or {}
        model_parallel = max(1, int(par.get('model_parallel') or 1))
        n_dev = len(jax.devices())
        self.mesh = None
        if n_dev > 1:
            data_size = n_dev // model_parallel
            if n_dev % model_parallel != 0:
                _LOG.warning('parallel.model_parallel %d does not divide '
                             '%d devices; training on a single device',
                             model_parallel, n_dev)
            elif args['batch_size'] % data_size == 0:
                self.mesh = make_mesh(model_parallel=model_parallel)
            else:
                _LOG.warning('batch_size %d not divisible by the %d-way '
                             'data axis; training on a single device',
                             args['batch_size'], data_size)
        self.state: Optional[TrainState] = None
        if wrapper.params is not None:
            own_params = jax.tree_util.tree_map(jnp.array, wrapper.params)
            self.state = init_train_state(own_params)
        # partition rules (parallel/partition.py): regex over the named
        # param/optimizer/batch-stats pytree -> replicate-vs-sharded specs.
        # The derived NamedSharding pytree types the compiled train steps'
        # inputs AND outputs, and is what checkpoints describe in their
        # layout manifest.
        from .parallel.partition import rules_from_config, tree_shardings
        self.partition_rules = rules_from_config(args)
        self.state_sharding = None
        if self.mesh is not None and self.state is not None:
            self.state_sharding = tree_shardings(self.mesh, self.state,
                                                 self.partition_rules)
        # IMPACT clipped target network (streaming.target_clip > 0): the
        # update step takes a frozen params copy whose ratios drive the
        # V-Trace targets (ops/losses.py). Deliberately NOT checkpointed:
        # at restart it re-initializes from the loaded params — one epoch
        # of target lag lost, no checkpoint format change. The fused replay
        # trainer has no target variant, so replay mode ignores the knob.
        stm = args.get('streaming') or {}
        self._use_target = float(stm.get('target_clip') or 0.0) > 0
        if self._use_target and args.get('device_replay'):
            _LOG.warning('streaming.target_clip is ignored in device_replay '
                         'mode (the fused trainer has no target variant)')
            self._use_target = False
        self.target_params = None
        self.target_sync_epochs = max(
            1, int(stm.get('target_sync_epochs') or 1))
        self._target_age_epochs = 0
        # the step donates its input state (params/opt buffers reused in
        # place); the actor-facing wrapper keeps its own copy of the params,
        # refreshed only at epoch boundaries
        self.update_step = build_update_step(
            wrapper.module, self.cfg, self.mesh, donate=True,
            state_shardings=self.state_sharding,
            use_target=self._use_target)

        self.default_lr = 3e-8
        self.data_cnt_ema = args['batch_size'] * args['forward_steps']
        self.steps = 0
        # per-stage ingest-path accounting (select/decode/assemble/ipc/h2d/
        # compute/drain), shared by the batcher threads/processes and the
        # trainer loop; printed per epoch under HANDYRL_TPU_TIMING=1
        from .utils.timing import StageTimer
        self.ingest_timer = StageTimer(registry=telemetry.REGISTRY)
        self.batcher = Batcher(args, self.episodes, timer=self.ingest_timer)
        # depth of the device staging ring: how many batches are held as
        # in-flight device uploads ahead of the compiled step (config
        # 'prefetch_depth'; 1 = the old single-slot overlap)
        self.prefetch_depth = max(1, int(args.get('prefetch_depth') or 1))

        # optional HBM-resident replay: new episodes are windowed once on
        # the host and pushed to a device ring; every SGD step then samples
        # its batch on device (ops/replay.py)
        self.replay = None
        self.ingest_queue: Optional[queue.Queue] = None
        if args.get('device_replay'):
            from .ops.replay import DeviceReplay
            # ring capacity budget per episode: how many training windows a
            # typical episode contributes; override via config
            # 'replay_windows_per_episode' (default assumes ~64-step episodes)
            windows_per_ep = (args.get('replay_windows_per_episode')
                              or max(1, 64 // args['forward_steps']))
            # hard cap on total ring windows: long-episode envs (200-ply
            # geese at forward_steps 4 => 50 windows/ep) must not scale the
            # HBM ring past a few GB; 49152 geese windows ~= 4 GB fp32
            self.replay = DeviceReplay(
                capacity=min(min(args['maximum_episodes'], 4096)
                             * windows_per_ep, 49152),
                mesh=self.mesh)
            self.ingest_queue = queue.Queue(maxsize=1024)
            self._pending_rows: List[Dict[str, Any]] = []
            self._sample_key = jax.random.PRNGKey(args.get('seed', 0) + 1)
            # K SGD steps per program dispatch: sampling, LR schedule and
            # update all stay on device inside one lax.scan, so replay-mode
            # throughput is bounded by compute, not dispatch latency
            self.fused_steps = max(1, int(args.get('replay_fused_steps') or 8))
            self.replay_update = self.build_replay_update(self.cfg)
            # observability: audited by metrics JSONL (replay_* fields)
            self.replay_stats = {'dropped_episodes': 0,
                                 'windows_ingested': 0,
                                 'samples_drawn': 0}
            # device-ingest mode (ops/device_windows.py): the fused loop
            # installs its DeviceWindower here and mirrors the ring's size
            # in _ring_size_host, for ring_occupancy
            self.windower = None
            self._ring_size_host = 0
            self.seen_episodes = 0     # learner-fed count (no host deque)
        self.update_flag = False
        self.update_queue: queue.Queue = queue.Queue(maxsize=1)
        self._loss_sum: Dict[str, float] = {}
        # learning-dynamics accumulators: 'diag_'-prefixed device metrics
        # (rho/c clip counts, importance-ratio moments, grad norm) folded
        # out of the lazy metric fetch, summarized per epoch into
        # ``last_dynamics`` (metrics_jsonl + gauges + the TIMING line)
        self._diag_sum: Dict[str, float] = {}
        self.last_dynamics: Dict[str, float] = {}
        # a net whose attention multiplies only the keys a block of queries
        # can see (models/attention.py) MAY say what share of all (query,
        # key) pairs that is at the trained length: the shapes fix it, and
        # every epoch's record carries it (``_epoch_dynamics``), as does
        # the fused loop's ``host_block`` span
        key_share = getattr(wrapper.module, 'attention_key_share', None)
        self.attention_key_share = None if key_share is None else key_share(
            args['burn_in_steps'] + args['forward_steps'])
        self.shutdown_flag = False
        self.failed = False
        self.failed_reason = ''
        self.started = False

        # non-finite guard: the device update step skips bad steps in place
        # (train_step.py); this side counts them and escalates per policy.
        # rollback_source is installed by the Learner (it owns the
        # checkpoint files); rollback_epoch hands the model-pool rewind
        # back to the Learner's loop after an in-place state restore.
        self.guard = guard_mod.NonFiniteGuard(args.get('guard') or {})
        self.chaos_nan = guard_mod.ChaosNaN()
        self.rollback_source = None
        self.rollback_epoch: Optional[int] = None

        # throughput + profiling (the reference has no tracing at all —
        # SURVEY.md §5.1; here per-epoch step rate is tracked and a JAX
        # profiler trace can be captured via train_args['profile_dir'])
        self.last_steps_per_sec = 0.0
        self._profile_dir = args.get('profile_dir') or ''
        self._profiled = False
        self._trace_active = False

    def build_replay_update(self, cfg: LossConfig):
        """The fused K-step replay trainer for ``cfg`` — the ONE place its
        geometry is defined."""
        from .ops.train_step import build_replay_update
        return build_replay_update(
            self.wrapper.module, cfg, capacity=self.replay.capacity,
            batch_size=self.args['batch_size'], num_steps=self.fused_steps,
            default_lr=self.default_lr, mesh=self.mesh,
            state_shardings=self.state_sharding,
            # window shapes resolved at trace time (first update): by
            # then the DeviceReplay has seen its first windows
            spec_fn=lambda: (self.replay.window_spec, self.replay.treedef))

    def _lr(self) -> float:
        return self.default_lr * self.data_cnt_ema / (1 + self.steps * 1e-5)

    # -- profiler trace lifecycle -----------------------------------------
    # stop_trace is reached from several paths (replay loop, threaded loop,
    # abort/shutdown); jax raises on a second stop, so the state lives in
    # ONE idempotent pair instead of per-path bookkeeping.
    def _start_trace(self):
        jax.profiler.start_trace(self._profile_dir)
        self._profiled = True
        self._trace_active = True

    def _stop_trace(self):
        """Idempotent, exception-safe stop: safe to call from any path, any
        number of times, including after an abort inside the profiled
        window (where jax may have torn the trace down already)."""
        if not self._trace_active:
            return
        self._trace_active = False
        try:
            jax.profiler.stop_trace()
        except Exception as exc:
            _LOG.warning('profiler stop_trace failed (%s: %s)',
                         type(exc).__name__, str(exc)[:120])
        else:
            _LOG.info('profiler trace written to %s', self._profile_dir)

    # -- full-state checkpointing (params + optimizer + schedule) ---------
    # The reference checkpoints the model only (optimizer state and RNG are
    # lost on resume, docs/parameters.md:76-82); here the whole TrainState
    # round-trips so restarts continue the same optimization trajectory.
    def state_bytes(self, host_state: Optional[TrainState] = None) -> bytes:
        from .utils.fetch import fetch_tree
        # fetch the whole state in one packed transfer first: serialization
        # walks leaves with np.asarray — one blocking transfer per leaf
        state = host_state if host_state is not None else fetch_tree(self.state)
        return train_state_bytes(state, self.steps, self.data_cnt_ema)

    def place_state(self, state: TrainState) -> TrainState:
        """Lay a (host or misplaced) TrainState out per the partition
        rules — the layout the compiled steps' in_shardings expect. The
        serialized checkpoint holds full host arrays, so this is also what
        makes restores mesh-shape-portable: whatever mesh wrote the bytes,
        placement happens under the CURRENT mesh."""
        if self.mesh is None:
            return state
        from .parallel.mesh import replicated_sharding
        return jax.device_put(state, self.state_sharding
                              or replicated_sharding(self.mesh))

    def load_state_bytes(self, raw: bytes):
        from flax import serialization
        template = {'state': self.state, 'steps': self.steps,
                    'data_cnt_ema': self.data_cnt_ema}
        payload = serialization.from_bytes(template, raw)
        # build everything before mutating: a parse/convert failure must
        # leave the live state untouched (resume falls back instead).
        # copy=True is load-bearing: from_bytes leaves are numpy VIEWS into
        # ``raw``, and the CPU backend zero-copy-aliases aligned numpy
        # arrays — the compiled update step then DONATES these buffers, so
        # an aliased leaf means XLA reclaiming memory it does not own
        # (non-finite garbage, then a segfault once ``raw`` is collected)
        state = jax.tree_util.tree_map(
            lambda x: jnp.array(x, copy=True), payload['state'])
        if isinstance(state, tuple):
            state = TrainState(*state)
        self.state = self.place_state(state)
        self.steps = int(payload['steps'])
        self.data_cnt_ema = float(payload['data_cnt_ema'])
        # the IMPACT target network is not part of the checkpoint: drop any
        # stale copy so the next epoch re-syncs it from the loaded params
        self.target_params = None

    def update(self, timeout: Optional[float] = None):
        """Called by the learner at each epoch boundary; blocks until the
        trainer hands over (params, steps, full-state blob). The blob is
        serialized inside the trainer loop — the state buffers are donated
        to the next compiled step, so nobody may touch them afterwards.
        ``timeout`` (preemption flush) raises queue.Empty when the trainer
        cannot reach a safe point in time."""
        self.update_flag = True
        params, steps, state_blob = self.update_queue.get(timeout=timeout)
        return params, steps, state_blob

    def train(self):
        if self.state is None:   # non-parametric model
            time.sleep(0.1)
            return self.wrapper.params

        batch_cnt, data_cnt = 0, 0
        pending_metrics: List[Dict[str, jnp.ndarray]] = []
        epoch_t0 = time.time()

        # target-network sync at the epoch boundary: a genuine device copy
        # (jnp.copy) because the live params buffer is donated every step.
        # Also (re)materializes after a restart/rollback replaced the state.
        if self._use_target and (
                self.target_params is None
                or self._target_age_epochs >= self.target_sync_epochs):
            self.target_params = jax.tree_util.tree_map(
                jnp.copy, self.state.params)
            self._target_age_epochs = 0

        if self._profile_dir and not self._profiled and self.steps > 0:
            self._start_trace()
            profile_stop_at = self.steps + 20
        else:
            profile_stop_at = -1

        # device staging ring: up to ``prefetch_depth`` batches held as
        # in-flight device uploads ahead of the compiled step (the old code
        # was the depth-1 special case). Persisted on the instance so
        # batches staged across an epoch boundary are consumed, not dropped.
        if not hasattr(self, '_staged'):
            self._staged = deque()
        staged = self._staged
        timer = self.ingest_timer

        def stage_next():
            t0 = time.perf_counter()
            try:
                nxt = self.batcher.batch(timeout=1.0)
            except queue.Empty:
                timer.add('ipc', time.perf_counter() - t0)
                return None
            timer.add('ipc', time.perf_counter() - t0)
            release = None
            # episode tracing: both wrapper flavors (TracedBatch from the
            # thread batcher, SharedBatch from the shm children) carry the
            # sampled trace ids of the windows in the batch
            tids = getattr(nxt, 'trace_ids', None)
            if hasattr(nxt, 'release'):      # shared-memory slot wrapper
                nxt, release = nxt.batch, nxt.release
            elif tids is not None:           # TracedBatch (thread batcher)
                nxt = nxt.batch
            t0 = time.perf_counter()
            if self.mesh is not None:
                dev = shard_batch(self.mesh, nxt)
            else:
                dev = jax.tree_util.tree_map(jnp.asarray, nxt)
            if release is not None:
                # the batcher child may reuse the slot only once the upload
                # has read the shared pages (device_put copies; this waits
                # for that copy, never for compute)
                jax.block_until_ready(dev)
                release()
            timer.add('h2d', time.perf_counter() - t0)
            return dev, tids

        def top_up():
            while len(staged) < self.prefetch_depth:
                nxt = stage_next()
                if nxt is None:
                    break
                staged.append(nxt)

        while (data_cnt == 0 or not self.update_flag) and not self.shutdown_flag:
            if self.replay is not None:
                # fused path: one dispatch = fused_steps SGD steps, with
                # batch sampling, LR schedule and PRNG advance all on device
                self._ingest_new_episodes()
                if self.replay.size == 0:
                    time.sleep(0.1)
                    continue
                buffers = self.replay.buffers
                size = jnp.asarray(self.replay.size, jnp.int32)
                cursor = jnp.asarray(self.replay.cursor, jnp.int32)
                # optional replay-ratio cap: the threaded trainer otherwise
                # free-spins as fast as dispatch allows (implicit, hardware-
                # dependent reuse — the reference's behavior); with
                # max_sample_reuse the trainer waits for fresh windows once
                # samples-drawn / windows-ingested would exceed the cap,
                # pinning off-policyness to a known ratio
                cap = self.args.get('max_sample_reuse')
                if cap and not self.update_flag:
                    # never throttle an epoch that is waiting to close: the
                    # loop must make >=1 dispatch per epoch to hand back
                    drawn_next = (self.replay_stats['samples_drawn']
                                  + self.args['batch_size'] * self.fused_steps)
                    if drawn_next > float(cap) * max(
                            1, self.replay_stats['windows_ingested']):
                        time.sleep(0.05)
                        continue
                ema = self.data_cnt_ema
                if self.chaos_nan.due(self.steps, self.fused_steps):
                    _LOG.warning('chaos: injecting non-finite update at '
                                 'step %d', self.steps)
                    ema = float('nan')   # poisons the on-device lr schedule
                t_dispatch = time.perf_counter()
                self.state, self._sample_key, metrics = self.replay_update(
                    self.state, buffers, self._sample_key, size, cursor,
                    jnp.asarray(ema, jnp.float32))
                timer.add('dispatch', time.perf_counter() - t_dispatch)
                self.replay_stats['samples_drawn'] += (
                    self.args['batch_size'] * self.fused_steps)
                pending_metrics.append(metrics)
                batch_cnt += self.fused_steps
                self.steps += self.fused_steps
                # drain every 4 dispatches (a fetch costs a device sync) —
                # but immediately when an epoch is waiting to close, so the
                # close needs ONE dispatch, not four (matters when
                # max_sample_reuse throttles the loop)
                if len(pending_metrics) >= 4 or self.update_flag:
                    t_block = time.perf_counter()
                    data_cnt += self._drain_metrics(pending_metrics)
                    timer.add('host_block', time.perf_counter() - t_block)
                    pending_metrics = []
                if 0 <= profile_stop_at <= self.steps:
                    jax.block_until_ready(metrics['total'])
                    self._stop_trace()
                    profile_stop_at = -1
                continue
            if not staged:
                top_up()
                if not staged:
                    continue
            batch, batch_tids = staged.popleft()
            lr_val = self._lr()
            if self.chaos_nan.due(self.steps):
                _LOG.warning('chaos: injecting non-finite update at step %d',
                             self.steps)
                lr_val = float('nan')
            lr = jnp.asarray(lr_val, jnp.float32)
            t_wall = time.time()
            t_dispatch = time.perf_counter()
            if self._use_target:
                self.state, metrics = self.update_step(
                    self.state, batch, lr, self.target_params)
            else:
                self.state, metrics = self.update_step(self.state, batch, lr)
            dt_dispatch = time.perf_counter() - t_dispatch
            timer.add('dispatch', dt_dispatch)
            if batch_tids:
                # the gradient end of the episode trace: one event per
                # update, linking every sampled episode whose window this
                # batch consumed (ids already passed deterministic sampling)
                telemetry.trace_event('train_step', ts=t_wall,
                                      dur=dt_dispatch, always=True,
                                      trace_ids=batch_tids, steps=self.steps)
            # the ring refills (device_put of the next batches) while the
            # dispatched step runs on device
            top_up()
            pending_metrics.append(metrics)
            batch_cnt += 1
            # data_count is a device scalar; fetch lazily every few steps to
            # avoid a sync per update
            if len(pending_metrics) >= 8:
                t_block = time.perf_counter()
                data_cnt += self._drain_metrics(pending_metrics)
                timer.add('host_block', time.perf_counter() - t_block)
                pending_metrics = []
            self.steps += 1
            if self.steps == profile_stop_at:
                jax.block_until_ready(metrics['total'])
                self._stop_trace()

        if pending_metrics:
            t_block = time.perf_counter()
            data_cnt += self._drain_metrics(pending_metrics)
            timer.add('host_block', time.perf_counter() - t_block)

        if batch_cnt > 0:   # zero only when interrupted by shutdown
            loss_sum = self._loss_sum
            self._loss_sum = {}
            print('loss = %s' % ' '.join(
                [k + ':' + '%.3f' % (l / max(data_cnt, 1))
                 for k, l in loss_sum.items()]))
            self.data_cnt_ema = (self.data_cnt_ema * 0.8
                                 + data_cnt / (1e-2 + batch_cnt) * 0.2)
            self.last_steps_per_sec = batch_cnt / max(time.time() - epoch_t0, 1e-9)
            if self._use_target:
                self._target_age_epochs += 1
            self.last_dynamics = self._epoch_dynamics(loss_sum, data_cnt,
                                                      batch_cnt)
            # the epoch's per-stage seconds feed the device-utilization
            # proxy (host_block / total ingest time) whether or not the
            # timing line is printed
            line = self.ingest_timer.snapshot(reset=True)
            util = telemetry.utilization_from_stages(line)
            telemetry.set_utilization_proxy(util)
            if os.environ.get('HANDYRL_TPU_TIMING') == '1':
                # one line per epoch: seconds + event counts per ingest
                # stage ('dispatch' is async-issue time; 'host_block' is
                # the device sync), plus the epoch's dynamics summary
                if util is not None:
                    line['util'] = round(util, 4)
                if self.last_dynamics:
                    line['dynamics'] = self.last_dynamics
                print('ingest timing: %s' % json.dumps(line))
        from .utils.fetch import fetch_tree
        return fetch_tree(self.state.params)

    def ring_occupancy(self) -> float:
        if self.replay is None:
            return 0.0
        if self.windower is not None:
            return self._ring_size_host / self.replay.capacity
        return self.replay.size / self.replay.capacity

    PUSH_CHUNK = 8   # fixed ring-push size => one XLA scatter compile

    def _ingest_new_episodes(self):
        """Window freshly generated episodes and push them into the device
        ring. Each episode is decompressed ONCE; ~steps/forward_steps random
        windows are sliced from the decoded moments; windows accumulate into
        fixed-size chunks so the ring's scatter compiles exactly once."""
        from .ops.batch import build_window, decompress_moments, stack_windows

        ingested = 0
        while ingested < 64:
            try:
                ep = self.ingest_queue.get_nowait()
            except queue.Empty:
                break
            ingested += 1
            moments = decompress_moments(ep['moment'])
            fs, bi = self.args['forward_steps'], self.args['burn_in_steps']
            for _ in range(max(1, ep['steps'] // fs)):
                train_st = random.randrange(1 + max(0, ep['steps'] - fs))
                st = max(0, train_st - bi)
                ed = min(train_st + fs, ep['steps'])
                meta = {'outcome': ep['outcome'], 'start': st, 'end': ed,
                        'train_start': train_st, 'total': ep['steps']}
                self._pending_rows.append(
                    build_window(moments[st:ed], meta, self.args))
        while len(self._pending_rows) >= self.PUSH_CHUNK:
            chunk = self._pending_rows[:self.PUSH_CHUNK]
            self._pending_rows = self._pending_rows[self.PUSH_CHUNK:]
            self.replay.push(stack_windows(chunk))
            self.replay_stats['windows_ingested'] += self.PUSH_CHUNK

    def _drain_metrics(self, pending: List[Dict[str, Any]]) -> int:
        """Fetch queued metric dicts in ONE packed transfer (per-scalar
        float() is a blocking device sync each) and fold them into the
        epoch's loss sums. Returns the summed data_count. The 'nonfinite'
        skip counts ride the same fetch into the guard — escalation costs
        no extra device sync."""
        from .utils.fetch import fetch_tree
        data_cnt = 0
        bad = 0
        total_sum = 0.0
        for m in fetch_tree(pending):
            for k, v in m.items():
                if k == 'data_count':
                    data_cnt += int(v)
                elif k == 'nonfinite':
                    bad += int(v)
                elif k.startswith('diag_'):
                    # learning-dynamics diagnostics: summarized per epoch
                    # by _epoch_dynamics, never on the reference loss line
                    self._diag_sum[k] = self._diag_sum.get(k, 0.0) + float(v)
                else:
                    if k == 'total':
                        total_sum += float(v)
                    self._loss_sum[k] = self._loss_sum.get(k, 0.0) + float(v)
        per_dispatch = self.fused_steps if self.replay is not None else 1
        n_updates = len(pending) * per_dispatch
        self._guard_observe(bad, n_updates - bad,
                            total_sum / data_cnt if data_cnt else None)
        return data_cnt

    def _epoch_dynamics(self, loss_sum: Dict[str, float], data_cnt: int,
                        n_updates: int) -> Dict[str, float]:
        """Reduce the epoch's accumulated ``diag_*`` device metrics into
        the learning-dynamics summary: V-Trace rho/c clip fractions,
        importance-ratio mean/std, policy entropy per acting sample, and
        mean global grad norm per update. Values are mirrored onto gauges
        (live Prometheus exposition) and returned for metrics_jsonl + the
        HANDYRL_TPU_TIMING line."""
        d, self._diag_sum = self._diag_sum, {}
        dc, nu = max(1, data_cnt), max(1, n_updates)
        out: Dict[str, float] = {}
        if 'ent' in loss_sum:
            out['entropy'] = loss_sum['ent'] / dc
        if 'diag_rho_clip' in d:
            out['rho_clip_fraction'] = d['diag_rho_clip'] / dc
            out['c_clip_fraction'] = d.get('diag_c_clip', 0.0) / dc
        if 'diag_rho_sum' in d:
            mean = d['diag_rho_sum'] / dc
            out['importance_ratio_mean'] = mean
            var = max(0.0, d.get('diag_rho_sq_sum', 0.0) / dc - mean * mean)
            out['importance_ratio_std'] = var ** 0.5
        if 'diag_target_clip' in d:
            # IMPACT target-network dynamics (losses.py target_clip):
            # clip fraction + mean of the target/behavior ratio, and the
            # mean current-vs-target log-prob gap (how far the live policy
            # has drifted from the frozen target since the last sync)
            out['target_clip_fraction'] = d['diag_target_clip'] / dc
            out['target_ratio_mean'] = (
                d.get('diag_target_ratio_sum', 0.0) / dc)
            out['target_gap_mean'] = d.get('diag_target_gap_sum', 0.0) / dc
        if 'diag_grad_norm' in d:
            out['grad_norm'] = d['diag_grad_norm'] / nu
        # a net MAY reduce the sums of its own forward pass (the ``aux`` of
        # its ``sequence``) to record keys of its own
        net_dynamics = getattr(self.wrapper.module, 'epoch_dynamics', None)
        if net_dynamics is not None:
            out.update(net_dynamics(d))
        if self.attention_key_share is not None:
            out['attention_key_share'] = self.attention_key_share
        out = {k: round(float(v), 6) for k, v in out.items()}
        for k, v in out.items():
            telemetry.gauge(k).set(v)
        return out

    # -- non-finite guard --------------------------------------------------
    def _guard_observe(self, bad: int, good: int,
                       loss_mean: Optional[float] = None):
        """Fold one drained metrics group into the guard; skip is counted,
        rollback restores the last good checkpoint in place, abort raises
        (the run()-level handler turns that into the failed path)."""
        if bad:
            telemetry.counter('guard_nonfinite_total').inc(bad)
        action = self.guard.observe(bad, good, loss_mean)
        if action == 'abort':
            raise RuntimeError(
                'guard: %d non-finite update(s) under nonfinite_policy='
                'abort' % bad)
        if action == 'rollback':
            self._do_rollback()
        elif bad:
            _LOG.warning('guard: skipped %d non-finite update(s) '
                         '(%d consecutive)', bad, self.guard.consecutive)

    def _do_rollback(self):
        """Restore the last good checkpoint IN PLACE (TrainState + step
        counter + lr EMA) and hand the model-pool epoch rewind to the
        Learner via ``rollback_epoch``. Safe here: called only between
        dispatches, when self.state is a settled value."""
        src = self.rollback_source() if self.rollback_source else None
        if src is None:
            _LOG.error('guard: rollback tripped but no valid checkpoint '
                       'exists yet; continuing with skipped updates')
            self.guard.reset_streak()
            return
        epoch, blob = src
        self.load_state_bytes(blob)
        self.guard.reset_streak()
        self.guard.rollbacks += 1
        self.rollback_epoch = epoch
        telemetry.counter('guard_rollbacks_total').inc()
        _LOG.error('guard: non-finite training burst — rolled back to '
                   'checkpoint epoch %d (steps %d)', epoch, self.steps)

    def run(self):
        _LOG.info('waiting training')
        while (len(self.episodes) < self.args['minimum_episodes']
               and getattr(self, 'seen_episodes', 0)
               < self.args['minimum_episodes']
               and not self.shutdown_flag):
            time.sleep(0.1)
        if self.state is not None and not self.shutdown_flag:
            if self.replay is None:
                self.batcher.run()
            self.started = True
            _LOG.info('started training')
        while not self.shutdown_flag:
            try:
                if not self.failed:
                    params = self.train()
                    state_blob = (self.state_bytes()
                                  if self.state is not None else None)
                else:
                    time.sleep(0.5)
                    params, state_blob = None, None
            except Exception as exc:
                # deliver (None, ...) instead of deadlocking the learner
                # (it blocks on update_queue at every epoch boundary); the
                # learner sees `failed` and shuts the run down — a dead
                # optimizer must not keep minting checkpoint epochs
                import traceback
                traceback.print_exc()
                # an abort inside the profiled window must not strand an
                # open trace (nor crash a later stop with a double-stop)
                self._stop_trace()
                self.failed = True
                self.failed_reason = '%s: %s' % (type(exc).__name__,
                                                 str(exc)[:300])
                params, state_blob = None, None
            self.update_flag = False
            while not self.shutdown_flag:
                try:
                    self.update_queue.put((params, self.steps, state_blob),
                                          timeout=0.5)
                    break
                except queue.Full:
                    continue

    def shutdown(self):
        self.shutdown_flag = True
        self._stop_trace()   # idempotent: a no-op unless a trace is open
        self.batcher.stop()


def _declared_max_steps(env_mod) -> int:
    """The longest game the device env module can play, in plies: its
    ``MAX_STEPS`` or ``MAX_PLIES``. The device windower sizes its circular
    episode history from it (ops/device_windows.py DeviceWindower), so a
    module that declares neither cannot be windowed on device: a guessed
    bound that a game outlasts would let the game overwrite its own first
    plies."""
    max_steps = getattr(env_mod, 'MAX_STEPS',
                        getattr(env_mod, 'MAX_PLIES', None))
    assert max_steps is not None, (
        'device window ingest needs the longest game in plies: %s declares '
        'neither MAX_STEPS nor MAX_PLIES'
        % getattr(env_mod, '__name__', env_mod))
    return int(max_steps)


class _EpochCadence:
    """Epoch trigger shared by every generation front-end: an epoch is due
    every ``update_episodes`` returned episodes past the warmup minimum
    (reference train.py:621-626). One definition so the fused, threaded and
    RPC-server loops cannot drift apart."""

    def __init__(self, args: Dict[str, Any]):
        self._next = args['minimum_episodes'] + args['update_episodes']
        self._step = args['update_episodes']

    def due(self, returned_episodes: int) -> bool:
        if returned_episodes >= self._next:
            self._next += self._step
            return True
        return False


class _CheckpointJob(NamedTuple):
    """One epoch's checkpoint as plain host values, all captured at that
    epoch's boundary: what :meth:`Learner._write_checkpoint` turns into
    files and :meth:`Learner._announce_checkpoint` then tells the registry,
    the retention GC and the durable plane about."""
    epoch: int
    steps: int
    params: Any                     # host (numpy) params
    state: Optional[TrainState]     # host train state still to serialise...
    state_blob: Optional[bytes]     # ...or the trainer thread's own bytes
    data_cnt_ema: float
    layout: Dict[str, Any]          # parallel.partition.checkpoint_layout
    durable: Dict[str, int]         # Learner._durable_marks


class _CheckpointWriter:
    """The one thread beside the fused loop that serialises and writes
    checkpoints, ONE job at a time in hand-over order: ``submit`` refuses a
    second job while one is outstanding, so no epoch's write is ever
    skipped, merged or overtaken. Everything but the write itself stays
    with the loop thread, which asks ``busy`` and calls ``wait``."""

    def __init__(self, write):
        self._write = write
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pending = None            # (job, future) of the job in flight

    def submit(self, job: _CheckpointJob):
        if self._pending is not None:
            raise RuntimeError('checkpoint writer: epoch %d handed over '
                               'with a write outstanding' % job.epoch)
        if self._pool is None:          # the first boundary of a fused run
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix='checkpoint-writer')
        self._pending = (job, self._pool.submit(self._write, job))

    def busy(self) -> bool:
        return self._pending is not None and not self._pending[1].done()

    def wait(self):
        """Block until the job in flight is on disk; returns ``(job,
        seconds blocked)``, ``(None, 0.0)`` with none outstanding. What the
        write raised is raised here, on the caller's thread."""
        pending, self._pending = self._pending, None
        if pending is None:
            return None, 0.0
        job, future = pending
        t0 = time.perf_counter()
        try:
            future.result()
        finally:
            waited = time.perf_counter() - t0
            telemetry.counter('checkpoint_wait_seconds_total').inc(waited)
        return job, waited

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


class Learner:
    """Central conductor: owns the model, episode/eval accounting, epoch
    cadence, checkpoints, and the generation front-end."""

    def __init__(self, args: Dict[str, Any], net=None, remote: bool = False):
        train_args = args['train_args']
        env_args = args['env_args']
        train_args['env'] = env_args
        args = train_args

        from . import setup_compile_cache
        setup_compile_cache()

        self.args = args
        random.seed(args['seed'])

        # -- unified telemetry: one run id for the whole fleet (workers
        # receive it in the merged config and stamp their own registries),
        # a master collection switch, episode-lifecycle tracing, and the
        # optional Prometheus endpoint. The telemetry knob accepts a bool
        # (legacy switch) or a block with trace_dir / trace_sample_rate.
        tel = telemetry.config_block(args)
        if not tel['enabled']:
            telemetry.set_enabled(False)
        args.setdefault('run_id', telemetry.run_id())
        telemetry.set_run_id(args['run_id'])
        telemetry.set_process_label('learner')
        telemetry.configure_tracing(tel.get('trace_dir') or None,
                                    tel.get('trace_sample_rate'))
        telemetry.configure_recorder(tel.get('recorder_events'),
                                     tel.get('blackbox_dir'))
        if telemetry.enabled():
            # XLA compile-event counters (cache hits, compile durations)
            telemetry.install_jax_monitoring()
            # fatal errors leave a blackbox dump behind (sys.excepthook)
            telemetry.install_crash_dump()
        # compiled-performance plane: device-memory gauges, the retrace
        # sentinel (steady state marked after retrace_warmup_epochs), and
        # the dispatch/host_block utilization proxy
        telemetry.configure_perf_plane(tel.get('perf_plane'),
                                       tel.get('retrace'))
        self._retrace_warmup = int(tel.get('retrace_warmup_epochs', 1))
        # SLO alert engine: builtin catalog + telemetry.alerts overrides,
        # evaluated on the server loop / epoch writer / statusz scrapes
        # through one cadence-gated stream (None with alerting off)
        self._alerts = telemetry.AlertEngine.from_config(args)
        self._metrics_rotate_mb = float(tel.get('metrics_rotate_mb') or 0)
        self._last_fleet_telemetry: Optional[dict] = None
        self._exporter = None
        # epoch means of the policy-lag/sample-age histograms are computed
        # as deltas between epochs; marks hold the last-read (sum, count)
        self._lag_marks: Dict[str, tuple] = {}

        self.env = make_env(env_args)
        eval_modify_rate = (args['update_episodes'] ** 0.85) / args['update_episodes']
        self.eval_rate = max(args['eval_rate'], eval_modify_rate)
        self.shutdown_flag = False
        self.flags: set = set()

        # learner-side resilience (guard.py): preemption snapshot-and-exit,
        # episode ingest screening, checkpoint integrity/rollback plumbing
        guard_args = dict(args.get('guard') or {})
        self.preempt = guard_mod.PreemptionGuard(
            enabled=bool(guard_args.get('preempt_signals', True)))
        self._check_episodes = bool(guard_args.get('check_episodes', True))
        self._bad_episodes = 0
        self._chaos = guard_mod.parse_chaos()
        self._final_flushed = False
        self._fused_active = False
        self._fused_trained = False   # a fused train dispatch has returned
        self._last_ckpt_epoch = -1
        self._last_ckpt_steps = -1
        self._ckpt_writer = _CheckpointWriter(self._write_checkpoint)

        self.model_epoch = args['restart_epoch']
        module = net if net is not None else self.env.net()
        compute_dtype = args.get('compute_dtype')
        if compute_dtype and hasattr(module, 'dtype'):
            # bf16 activations on the MXU; params stay float32
            module = module.clone(dtype=jnp.dtype(compute_dtype))
        self.wrapper = ModelWrapper(module, seed=args['seed'])
        self.env.reset()
        self._example_obs = self.env.observation(self.env.players()[0])
        self.wrapper.ensure_params(self._example_obs)
        self._resume = False
        if self.model_epoch < 0:
            # auto-resume (restart_epoch: -1): the supervisor restart path
            # after a preemption exit — pick up the newest checkpoint that
            # passes integrity verification, or start fresh when none does
            self.model_epoch, discarded = guard_mod.newest_valid_epoch(
                self.args.get('model_dir', 'models'))
            args['restart_epoch'] = self.model_epoch
            if discarded:
                telemetry.counter('guard_ckpt_fallbacks_total').inc(
                    len(discarded))
            if self.model_epoch > 0:
                print('auto-resume: newest valid checkpoint is epoch %d'
                      % self.model_epoch)
        if self.model_epoch > 0:
            self._load_resume_params()
            self._resume = True
        elif args.get('init_params'):
            # warm start: params only — epoch counter, optimizer moments and
            # lr EMA start fresh (unlike restart_epoch, which resumes all)
            with open(args['init_params'], 'rb') as f:
                self.wrapper.load_params_bytes(f.read(), self._example_obs)
            print('warm-started params from %s' % args['init_params'])

        # generation accounting
        self.generation_results: Dict[int, tuple] = {}
        self.num_episodes = 0
        self.num_returned_episodes = 0
        # evaluation accounting
        self.results: Dict[int, tuple] = {}
        self.results_per_opponent: Dict[int, dict] = {}
        self.num_results = 0

        # Resolve the per-episode replay-window budget ONCE, from the env's
        # true episode length, so the device windower's per-episode cap and
        # the host ingest rate (both ~steps/forward_steps windows) agree —
        # the default of 64//forward_steps silently under-sampled long
        # episodes (a 200-ply goose yielded 4 windows instead of 12).
        if args.get('device_replay') and not args.get('replay_windows_per_episode'):
            from .environment import make_jax_env
            twin = make_jax_env(env_args)
            if twin is not None:
                max_steps = int(getattr(twin, 'MAX_STEPS',
                                        getattr(twin, 'MAX_PLIES', 64)))
                args['replay_windows_per_episode'] = max(
                    1, max_steps // args['forward_steps'])

        self.remote = remote
        self.use_batched_generation = (not remote
                                       and args.get('batched_generation', True))
        self.ledger: Optional[TaskLedger] = None   # built by server()
        self.fleet: Optional[FleetController] = None   # built by server()
        self.worker = None
        if not self.use_batched_generation:
            if not remote:
                self._refuse_local_device_gathers()
            self.worker = WorkerServer(args) if remote else WorkerCluster(args)

        self.trainer = Trainer(args, self.wrapper)
        claim_devices('learner', mesh=self.trainer.mesh)
        self.trainer.rollback_source = self._rollback_source
        # policy-lag accounting: the batcher stamps lag at window selection
        # against the CURRENT learner epoch (consumption, not ingest)
        self.trainer.batcher.epoch_fn = lambda: self.model_epoch
        # profile_epochs: wrap chosen epochs in a jax.profiler device trace
        # (start at the previous epoch's close, stop at the chosen epoch's
        # close). Disables the legacy one-shot auto-trace — the knob says
        # exactly which epochs the operator wants.
        from .config import parse_epoch_set
        self._profile_epochs = parse_epoch_set(args.get('profile_epochs'))
        if self._profile_epochs:
            if not self.trainer._profile_dir:
                self.trainer._profile_dir = os.path.join(
                    telemetry.trace_dir() or args.get('model_dir', 'models'),
                    'profile')
            self.trainer._profiled = True   # suppress the legacy auto-start
        if self._resume:
            state_path = self.trainer_state_path()
            if os.path.exists(state_path):
                from .parallel.partition import checkpoint_layout, describe_mesh
                from .utils.fs import read_layout_manifest, read_verified_bytes
                raw = read_verified_bytes(state_path)
                layout, lreason = read_layout_manifest(state_path)
                if lreason == 'unparsable':
                    # corrupt manifest = untrustworthy pair, same as a CRC
                    # failure: degrade to params-only resume
                    raw = None
                if raw is None:
                    _LOG.error('discarding corrupt trainer_state.ckpt '
                               '(checksum mismatch, truncation, or corrupt '
                               'layout manifest); the optimizer restarts '
                               'fresh from the model checkpoint')
                    telemetry.counter('guard_ckpt_fallbacks_total').inc()
                else:
                    # mesh-portable restore: the state is full host arrays,
                    # so a mesh-shape change is legal — log it explicitly
                    here = checkpoint_layout(self.trainer.mesh,
                                             self.trainer.partition_rules)
                    if layout is not None and (
                            layout.get('mesh') != here['mesh']
                            or layout.get('processes') != here['processes']):
                        print('mesh-portable restore: checkpoint written '
                              'under %s (%d process(es)), restoring onto '
                              '%s (%d process(es))'
                              % (describe_mesh(layout),
                                 int(layout.get('processes') or 1),
                                 describe_mesh(here), here['processes']))
                    try:
                        self.trainer.load_state_bytes(raw)
                        print('resumed trainer state (steps %d)'
                              % self.trainer.steps)
                    except Exception as exc:
                        _LOG.error('discarding undecodable trainer_state'
                                   '.ckpt (%s: %s); the optimizer restarts '
                                   'fresh', type(exc).__name__,
                                   str(exc)[:120])
                        telemetry.counter('guard_ckpt_fallbacks_total').inc()
        self._trainer_thread: Optional[threading.Thread] = None
        self._registry = None   # lazy ModelRegistry (serving.publish)

        # league training (league.py, docs/league.md): the pool, the
        # persistent rating book, and the per-epoch opponents-sampled
        # tally. Everything below is None with league.enabled false, so
        # task assignment/records/metrics stay byte-identical to the
        # pre-league behavior.
        lg = dict(args.get('league') or {})
        self._league: Optional[league_mod.LeaguePool] = None
        self._league_ratings: Optional[league_mod.RatingBook] = None
        self._league_journal = ''
        self._league_sampled: Dict[str, int] = {}
        if lg.get('enabled'):
            srv = args.get('serving') or {}
            line = str(lg.get('line') or srv.get('line', 'default'))
            self._league = league_mod.LeaguePool(lg, line)
            self._league_ratings = league_mod.make_rating_book(lg)
            self._league_journal = league_mod.journal_path(
                self._registry_root())
            if self._league_ratings.load(self._league_journal):
                print('league: reloaded ratings journal (%d entries, %d '
                      'promotions)' % (len(self._league_ratings.names()),
                                       self._league_ratings.promotions))
            try:
                self._league.refresh(self._ensure_registry())
            except Exception as exc:   # fresh run: no manifest yet
                _LOG.debug('league: initial pool refresh skipped (%s)', exc)
            if self.use_batched_generation:
                _LOG.warning('league.enabled only drives the worker-fleet '
                             "server() task assignment; the in-process "
                             'batched generator keeps mirror self-play')

        # durable training plane (spool.py EpisodeSpool + fault.LedgerJournal,
        # docs/large_scale_training.md "Zero-loss training plane"). Remote
        # only: the in-process front-ends lose nothing a checkpoint does not
        # already cover, and their records must stay byte-identical.
        # _load_durable_state publishes the resume token before the entry
        # listener opens; the spool creates its directory on first append.
        dur = dict(args.get('durability') or {})
        self._spool: Optional[EpisodeSpool] = None
        self._ledger_journal: Optional[LedgerJournal] = None
        self._restored_ledger: Optional[dict] = None
        self._durable_restored = False
        self._spool_horizon = 0          # consumption horizon at last ckpt
        self._run_generation = 0         # restart generation (resume token)
        self._token_path = os.path.join(args.get('model_dir', 'models'),
                                        'run_token.json')
        self._league_last_flush = time.monotonic()
        if remote and bool(dur.get('spool', True)):
            self._spool = EpisodeSpool(
                args.get('model_dir', 'models'),
                segment_mb=float(dur.get('segment_mb', 64)),
                keep_segments=int(dur.get('keep_segments', 2)))
        if remote and bool(dur.get('ledger_snapshot', True)):
            self._ledger_journal = LedgerJournal(
                args.get('model_dir', 'models'))
        # streaming ingest (streaming.py): one assembler merges chunked
        # uploads back into episodes. Constructed unconditionally (cheap,
        # inert while no chunk arrives) so spool recovery can replay chunk
        # records even if the restarted config flipped streaming off.
        from .streaming import ChunkAssembler
        self._assembler = ChunkAssembler(
            args, check_finite=self._check_episodes)
        self._recovered_closed_chunks: list = []
        self._load_durable_state()

        # the scrape endpoint binds only once everything it reads (trainer,
        # worker front-end) exists — a scrape can land any time after this
        export_port = int(args.get('telemetry_port') or 0)
        if export_port and telemetry.enabled():
            self._exporter = telemetry.TelemetryExporter(
                self._telemetry_snapshots, port=export_port,
                status=self._status_info).start()

        self._metrics_path = args.get('metrics_jsonl') or ''
        # optional wall-clock budget (absolute unix time): long quality runs
        # (scripts/run_north_star.py) stop at the next epoch boundary so the
        # final checkpoint lands inside the budget window
        self._deadline = float(os.environ.get('HANDYRL_TPU_DEADLINE', 0) or 0)

    def _refuse_local_device_gathers(self):
        """A chip belongs to one process. Local ``--train`` spawns its
        gathers on THIS host, so a gather that claims the accelerator
        (``generation.backend: device`` or a ``device`` inference engine)
        would be a second claimant on the chip the learner holds — it fails
        or hangs. On a CPU-only learner nothing is contended and the gather
        simply reports the CPU in its own start-up line."""
        if jax.default_backend() != 'cpu' and gather_claims_device(self.args):
            raise ValueError(
                'local --train on the %s backend cannot start gathers with '
                'generation.backend / inference.engine_backend "device": '
                'they would claim the chip this learner holds. Use '
                'device_generation (in-process rollouts), or run the '
                'gathers on another host with --train-server + --worker.'
                % jax.default_backend())

    def _past_epoch_budget(self) -> bool:
        """True when the epoch budget or the wall-clock deadline is spent."""
        if 0 <= self.args['epochs'] <= self.model_epoch:
            return True
        return self._deadline > 0 and time.time() >= self._deadline

    # -- durable training plane ------------------------------------------
    def _load_durable_state(self):
        """Restart recovery for the durable training plane: adopt the
        previous incarnation's resume token (same run_id, generation + 1),
        replay the persisted ledger book, restore the admission counters,
        cancel the tasks whose episodes already reached the spool, and
        feed every spooled episode past the newest checkpoint's
        consumption horizon back into the buffer — all before the fleet is
        served a single task."""
        if self._ledger_journal is None and self._spool is None:
            return
        token = None
        try:
            with open(self._token_path, 'r') as f:
                token = json.load(f)
        except (OSError, ValueError):
            token = None
        if isinstance(token, dict) and token.get('run_id'):
            # keep the dead incarnation's run_id: surviving gathers prove
            # membership against it in the resume-token handshake (and the
            # telemetry/trace stream stays one causal run)
            self.args['run_id'] = str(token['run_id'])
            telemetry.set_run_id(self.args['run_id'])
            self._run_generation = int(token.get('generation') or 0) + 1

        state = self._ledger_journal.load() \
            if self._ledger_journal is not None else None
        if state is not None:
            extra = state.get('extra') or {}
            # counter restore: at least the snapshot values, bounded below
            # by the sample_key watermark over the persisted book — a
            # fresh task must NEVER reuse a restored task's sample_key or
            # the purity contract (episode = f(seed, sample_key, params))
            # would mint two different episodes under one key
            g_max = e_max = -1
            for base in (list((state.get('tasks') or {}).values())
                         + list(state.get('reissue') or ())):
                if not isinstance(base, dict) \
                        or base.get('sample_key') is None:
                    continue
                if base.get('role') == 'g':
                    g_max = max(g_max, int(base['sample_key']))
                elif base.get('role') == 'e':
                    e_max = max(e_max, int(base['sample_key']))
            self.num_episodes = max(int(extra.get('num_episodes') or 0),
                                    g_max + 1)
            self.num_results = max(int(extra.get('num_results') or 0),
                                   e_max + 1)
            self.num_returned_episodes = int(
                extra.get('num_returned_episodes') or 0)
            self._spool_horizon = int(extra.get('spool_horizon') or 0)
            self._durable_restored = True
            print('durable plane: restored ledger book (%d outstanding, '
                  '%d pending re-issue, counters g=%d e=%d returned=%d)'
                  % (len(state.get('tasks') or {}),
                     len(state.get('reissue') or ()), self.num_episodes,
                     self.num_results, self.num_returned_episodes))

        if self._spool is not None:
            recovered = self._spool.recover(self._spool_horizon, conn_unpack)
            if recovered:
                # an episode that reached the spool must neither re-issue
                # nor double-count: drop its task_id from the restored
                # book before the ledger ever sees it (this closes the
                # only crash window — admitted but completion unflushed)
                tasks = (state or {}).get('tasks')
                # records below the restored returned-counter were already
                # counted by the dead incarnation; they only live in the
                # spool because the GC horizon holds back to the oldest
                # open streamed assembly — replaying them would double-count
                counted = self.num_returned_episodes
                episodes = []
                for rec in recovered:
                    episode = rec.get('episode')
                    if episode is None \
                            or int(rec.get('idx') or 0) < counted:
                        continue
                    episodes.append(episode)
                    tid = (episode.get('args') or {}).get('task_id')
                    if tasks is not None and tid is not None:
                        tasks.pop(tid, None)
                self.feed_episodes(episodes, recovered=True)
                # streamed chunk records replay through the assembler under
                # their original spool indices; an episode whose every
                # window was WAL'd reassembles right here — cancel its
                # restored task (tid, plus the sample_key scan for a pure
                # stream whose final attempt differed) and remember the key
                # so the ledger screens post-restart resends of it. A
                # still-open assembly keeps its restored book entry: the
                # re-issue regenerates the missing windows (the delivered
                # ones screen as duplicates in the restored chunk book).
                # The replay screen: a chunk replays iff its assembly is
                # still open in the restored book, closed by a POST-snapshot
                # delta (completion not yet in the restored counters), or
                # spooled past the counter — assemblies completed before the
                # snapshot are already counted and must stay dropped.
                from .streaming import chunk_key
                live_keys = set()
                for pair in (state or {}).get('chunks') or ():
                    try:
                        live_keys.add((str(pair[0][0]), int(pair[0][1])))
                    except Exception:
                        continue
                for k in (state or {}).get('chunks_closed') or ():
                    try:
                        live_keys.add((str(k[0]), int(k[1])))
                    except Exception:
                        continue
                chunk_recs = [
                    rec for rec in recovered
                    if rec.get('chunk') is not None
                    and (int(rec.get('idx') or 0) >= counted
                         or chunk_key(rec['chunk']) in live_keys)]
                if chunk_recs:
                    done = self.feed_chunks(
                        [rec['chunk'] for rec in chunk_recs],
                        recovered=True,
                        marks=[int(rec.get('idx') or 0)
                               for rec in chunk_recs])
                    for key, final_args in done:
                        self._recovered_closed_chunks.append(key)
                        if tasks is None:
                            continue
                        tid = (final_args or {}).get('task_id')
                        if tid is not None:
                            tasks.pop(tid, None)
                        if key and key[0] == 'k':
                            for t, base in list(tasks.items()):
                                if isinstance(base, dict) \
                                        and base.get('sample_key') == key[1] \
                                        and base.get('role') == 'g':
                                    tasks.pop(t, None)
                    print('durable plane: replayed %d spooled chunk(s) '
                          '(%d episode(s) reassembled, %d assembly(ies) '
                          'still open)'
                          % (len(chunk_recs), len(done),
                             self._assembler.open_count()))
                self._durable_restored = True
                print('durable plane: recovered %d spooled episode(s) '
                      'past horizon %d (zero admitted episodes lost)'
                      % (len(recovered), self._spool_horizon))
        self._restored_ledger = state
        if self._durable_restored:
            # the trainer resumes mid-stream: it must not re-wait a full
            # fresh minimum_episodes warmup on top of the restored buffer
            self.trainer.seen_episodes = self.num_returned_episodes

        # publish THIS incarnation's resume token now — before run() opens
        # the entry listener — so every gather (fresh or redialing) sees it
        # in the merged entry config. The NEXT restart adopts the run_id
        # and bumps the generation; reattaching gathers prove membership
        # against it (the RESUME_KIND branch in server()).
        os.makedirs(self.args.get('model_dir', 'models'), exist_ok=True)
        atomic_write_bytes(self._token_path, (json.dumps(
            {'run_id': str(self.args.get('run_id')),
             'generation': self._run_generation}) + '\n').encode('utf-8'))
        self.args['resume_token'] = {
            'run_id': str(self.args.get('run_id')),
            'generation': self._run_generation}

    def _durable_marks(self) -> Dict[str, int]:
        """What :meth:`_sync_durable_state` will publish for the checkpoint
        being captured NOW: the episode counters and the spool's
        consumption horizon as they stand at this epoch boundary (a
        checkpoint written beside the fused loop becomes durable later,
        when the counters have moved on)."""
        # the consumption horizon holds back to the oldest OPEN streamed
        # assembly's first WAL mark: a restart must be able to replay every
        # window of a partially-delivered episode, even ones spooled before
        # episodes that already completed
        horizon = self.num_returned_episodes
        open_mark = self._assembler.min_open_mark()
        if open_mark is not None:
            horizon = min(horizon, int(open_mark))
        return {'num_episodes': self.num_episodes,
                'num_results': self.num_results,
                'num_returned_episodes': self.num_returned_episodes,
                'spool_horizon': horizon}

    def _sync_durable_state(self, marks: Dict[str, int]):
        """Epoch-sync the durable plane (rides every checkpoint write):
        republish the ledger snapshot — folding the delta journal — and
        GC spool segments behind the consumption horizon of ``marks``
        (:meth:`_durable_marks` at the boundary of the checkpoint that has
        just become durable)."""
        if self.ledger is not None and self._ledger_journal is not None:
            self.ledger.flush_journal()
            state = self.ledger.snapshot_state()
            state['extra'] = dict(marks)
            self._ledger_journal.snapshot(state)
        if self._spool is not None:
            self._spool_horizon = marks['spool_horizon']
            self._spool.gc(self._spool_horizon)

    # -- checkpoints ------------------------------------------------------
    def model_path(self, model_id: int) -> str:
        return os.path.join(self.args.get('model_dir', 'models'),
                            str(model_id) + '.ckpt')

    def latest_model_path(self) -> str:
        return os.path.join(self.args.get('model_dir', 'models'), 'latest.ckpt')

    def trainer_state_path(self) -> str:
        return os.path.join(self.args.get('model_dir', 'models'),
                            'trainer_state.ckpt')

    def update_model(self, params, steps: int,
                     state_blob: Optional[bytes] = None, bump: bool = True,
                     write_files: bool = True):
        """Advance the model epoch; persist snapshot + ckpt files unless
        ``write_files`` is False (checkpoint_interval's skip epochs, where
        params never leave the device). Synchronous: when it returns the
        files are on disk and announced. (The threaded/server learner's
        epoch close and every final flush: SGD runs on the trainer thread
        meanwhile, and a worker may ask for this epoch's file at once.)"""
        job = self._advance_epoch(params, steps, state_blob=state_blob,
                                  bump=bump, write_files=write_files)
        if job is not None:
            self._write_checkpoint(job)
            self._announce_checkpoint(job)

    def _bump_epoch(self):
        """The part of an epoch close that needs no bytes: the fused loop's
        boundary does it before it enqueues the next dispatch, which goes
        out under the new epoch's tag, and fetches the train state after."""
        self.model_epoch += 1
        # chaos 'nanepoch': poison updates right after this epoch's
        # checkpoint lands, so a rollback target provably exists
        if self._chaos.get('nanepoch') == self.model_epoch:
            self.trainer.chaos_nan.arm(self.trainer.steps + 1)

    def _advance_epoch(self, params, steps: int,
                       state: Optional[TrainState] = None,
                       state_blob: Optional[bytes] = None, bump: bool = True,
                       write_files: bool = True) -> Optional[_CheckpointJob]:
        """The part of an epoch close that only the loop thread may do:
        bump the epoch, take the host params as the learner's snapshot, and
        capture the checkpoint as a job of plain values (None on a
        ``write_files`` False epoch)."""
        print('updated model(%d)' % steps)
        if bump:
            self._bump_epoch()
        if not write_files:
            return None
        self._last_ckpt_epoch = self.model_epoch
        self._last_ckpt_steps = steps
        # learner-side copy stays on HOST (numpy): it only feeds
        # snapshots/checkpoints; a device copy would cost one upload
        # per leaf each epoch for nothing
        self.wrapper.params = jax.tree_util.tree_map(np.asarray, params)
        # A mesh-layout manifest rides along: checkpoints serialize full
        # host arrays, so they restore under ANY device/host count — the
        # manifest records what wrote them so the mesh change is logged,
        # and a corrupt manifest disqualifies the pair like a bad CRC.
        from .parallel.partition import checkpoint_layout
        return _CheckpointJob(
            epoch=self.model_epoch, steps=steps, params=self.wrapper.params,
            state=state, state_blob=state_blob,
            data_cnt_ema=self.trainer.data_cnt_ema,
            layout=checkpoint_layout(self.trainer.mesh,
                                     self.trainer.partition_rules,
                                     steps=steps),
            durable=self._durable_marks())

    def _write_checkpoint(self, job: _CheckpointJob):
        """``job``'s bytes and files: ``<epoch>.ckpt``, ``latest.ckpt`` and
        ``trainer_state.ckpt``, in that order. Reads nothing but ``job`` and
        the run's paths, so it runs inline (:meth:`update_model`) or on the
        fused loop's writer thread (:meth:`_hand_over_checkpoint`) alike."""
        from flax import serialization
        from .utils.fs import write_layout_manifest
        state_blob = job.state_blob
        if job.state is not None:
            with telemetry.trace_span('checkpoint_serialize') as span:
                state_blob = train_state_bytes(job.state, job.steps,
                                               job.data_cnt_ema)
                span.set(bytes=len(state_blob))
        with telemetry.trace_span('checkpoint_serialize') as span:
            raw = serialization.to_bytes(job.params)
            span.set(bytes=len(raw))
        os.makedirs(self.args.get('model_dir', 'models'), exist_ok=True)
        # atomic (temp + fsync + rename) plus a CRC32 sidecar manifest: a
        # crash mid-write must never leave a truncated latest.ckpt /
        # trainer_state.ckpt, and resume verifies the checksum so silent
        # on-disk corruption falls back instead of poisoning the restart.
        with telemetry.trace_span('checkpoint_write') as span:
            blobs = [(self.model_path(job.epoch), raw),
                     (self.latest_model_path(), raw)]
            if state_blob is not None:
                blobs.append((self.trainer_state_path(), state_blob))
            for path, blob in blobs:
                checksummed_write_bytes(path, blob)
                write_layout_manifest(path, job.layout)
            span.set(files=len(blobs),
                     bytes=sum(len(blob) for _path, blob in blobs))
        telemetry.counter('checkpoint_writes_total').inc()

    def _announce_checkpoint(self, job: _CheckpointJob):
        """``job``'s files are durable: tell everyone who may now rely on
        them. Loop thread only (the registry, the league, the task ledger
        and the spool are not shared with the writer). Everything here goes
        by ``job.epoch``: after a ``checkpoint_interval`` skip epoch the
        live ``self.model_epoch`` is already past it."""
        with telemetry.trace_span('checkpoint_publish_gc'):
            # publish BEFORE retention GC: a version the registry is about
            # to pin must be pinned by the time the GC pass reads the
            # manifest
            self._publish_checkpoint(job.steps, job.epoch)
            self._gc_checkpoints()
            # durable plane rides the checkpoint cadence: the ledger
            # snapshot and the spool GC horizon must describe a state a
            # restart can actually resume from, i.e. one with a durable
            # checkpoint
            self._sync_durable_state(job.durable)

    def _hand_over_checkpoint(self, host_state: TrainState,
                              steps: Optional[int] = None, bump: bool = True):
        """The fused loop's epoch close. That loop owns the only thread
        that feeds the device, so it keeps what only it may do and gives
        serialisation and the fsynced writes to the writer thread; the next
        dispatch is enqueued while they run. Depth ONE: the previous epoch's
        write is awaited and, where the loop's poll has not done so yet,
        announced first (span ``checkpoint_wait``, at every boundary, ~0
        when the writer kept up). ``steps`` is the count ``host_state``
        holds (the trainer's, unless the boundary has enqueued the next
        dispatch already and bumped the epoch: ``bump`` False). Returns this
        epoch's job, for the caller to ``submit`` as the boundary's last
        act, and the seconds blocked."""
        with telemetry.trace_span('checkpoint_wait'):
            waited = self._collect_checkpoint()
        if steps is None:
            steps = self.trainer.steps
        with telemetry.trace_span('epoch_advance'):
            job = self._advance_epoch(host_state.params, steps,
                                      state=host_state, bump=bump)
        return job, waited

    def _collect_checkpoint(self, block: bool = True) -> float:
        """Announce the fused loop's checkpoint once it is on disk; with
        ``block`` wait for it first. Called, blocking, before anything that
        reads or rewrites ``model_dir`` or tells anyone a checkpoint
        exists: the files there are then exactly what synchronous writes
        would have left. A no-op with no write outstanding (always, outside
        the fused loop). Returns the seconds it blocked."""
        if not block and self._ckpt_writer.busy():
            return 0.0
        done, waited = self._ckpt_writer.wait()
        if done is not None:
            self._announce_checkpoint(done)
        return waited

    def _registry_root(self) -> str:
        srv = self.args.get('serving') or {}
        return srv.get('registry_dir') or self.args.get('model_dir', 'models')

    def _ensure_registry(self):
        if self._registry is None:
            from .serving.registry import ModelRegistry
            self._registry = ModelRegistry(self._registry_root())
        return self._registry

    def _publish_checkpoint(self, steps: int, epoch: Optional[int] = None):
        """``serving.publish``: register the just-written numbered
        checkpoint of ``epoch`` (the live epoch by default) with the
        ModelRegistry as ``<line>@<epoch>`` (pinning it
        against ``keep_checkpoints`` GC); ``serving.auto_promote`` also
        makes it the line's champion in the same atomic manifest swap —
        unless the league owns promotion (league.enabled), in which case
        versions publish as candidates and the champion only flips through
        the rating gate (:meth:`_league_epoch_sync`). A registry failure is
        loud but never takes training down."""
        if epoch is None:
            epoch = self.model_epoch
        srv = self.args.get('serving') or {}
        if not srv.get('publish'):
            return
        if self._registry is None:
            from .serving.registry import ModelRegistry
            self._registry = ModelRegistry(self._registry_root())
        try:
            from . import models as model_zoo
            from .model import module_config
            promote = bool(srv.get('auto_promote', True))
            if getattr(self, '_league', None) is not None:
                # rating-gated promotion replaces recency auto_promote
                # (the registry still bootstraps the FIRST version as
                # champion — a line must never be headless)
                promote = False
            self._registry.publish(
                str(srv.get('line', 'default')),
                path=self.model_path(epoch),
                architecture=model_zoo.architecture_name(self.wrapper.module),
                config=module_config(self.wrapper.module) or None,
                steps=int(steps), version=epoch, promote=promote)
        except Exception as exc:
            _LOG.error('registry publish of epoch %d failed (%s: %s); '
                       'training continues unpublished', epoch,
                       type(exc).__name__, str(exc)[:200])
            telemetry.counter('registry_publish_failures_total').inc()
        sync = getattr(self, '_league_epoch_sync', None)
        if sync is not None:
            sync(epoch)

    def _league_epoch_sync(self, epoch: Optional[int] = None):
        """League epoch boundary (after publish, before retention GC):
        refresh the member window from the registry manifest, run the
        rating-gated promotion of ``epoch`` (the live epoch by default),
        export the rating gauges, and journal the book atomically. Failures
        are loud but never take training down."""
        if epoch is None:
            epoch = self.model_epoch
        if getattr(self, '_league', None) is None \
                or self._league_ratings is None:
            return
        book = self._league_ratings
        try:
            reg = self._ensure_registry()
            self._league.refresh(reg)
            # a fresh member is a snapshot of the learner: seed it at the
            # learner's current rating instead of the cold initial_rating
            known = set(book.names())
            for m in self._league.members():
                if m not in known:
                    book.seed(m, book.rating(league_mod.LEARNER))
            if self._league.should_promote(book):
                incumbent = self._league.champion
                reg.promote(self._league.line, epoch)
                book.note_promotion()
                telemetry.counter('league_promotions_total').inc()
                self._league.refresh(reg)
                print('league: promoted %s@%d (learner %.1f vs incumbent '
                      '%s %.1f)' % (self._league.line, epoch,
                                    book.rating(league_mod.LEARNER),
                                    incumbent,
                                    book.rating(incumbent)
                                    if incumbent else float('nan')))
            for name in set(self._league.roster()) | set(book.names()):
                telemetry.gauge('league_rating', member=name).set(
                    round(book.rating(name), 2))
            book.save(self._league_journal)
        except Exception as exc:
            _LOG.error('league: epoch sync failed (%s: %s); training '
                       'continues', type(exc).__name__, str(exc)[:200])

    # -- checkpoint integrity / retention / rollback -----------------------
    def _load_resume_params(self):
        """Load the resume params for ``self.model_epoch``, falling back to
        the newest EARLIER checkpoint that both passes CRC verification and
        deserializes, instead of crashing on corrupt/truncated bytes."""
        from .utils.fs import verify_checkpoint
        model_dir = self.args.get('model_dir', 'models')
        candidates = [self.model_epoch] + [
            e for e in reversed(guard_mod.numbered_checkpoints(model_dir))
            if e < self.model_epoch]
        from .utils.fs import read_layout_manifest
        for epoch in candidates:
            path = self.model_path(epoch)
            ok, reason = verify_checkpoint(path)
            if not ok:
                _LOG.error('discarding checkpoint %s: %s', path, reason)
                telemetry.counter('guard_ckpt_fallbacks_total').inc()
                continue
            # a PRESENT but corrupt layout manifest disqualifies the pair
            # exactly like a failed CRC (missing = legacy, loadable)
            _layout, lreason = read_layout_manifest(path)
            if lreason == 'unparsable':
                _LOG.error('discarding checkpoint %s: corrupt layout '
                           'manifest', path)
                telemetry.counter('guard_ckpt_fallbacks_total').inc()
                continue
            try:
                with open(path, 'rb') as f:
                    self.wrapper.load_params_bytes(f.read(), self._example_obs)
            except Exception as exc:
                _LOG.error('discarding undecodable checkpoint %s (%s: %s)',
                           path, type(exc).__name__, str(exc)[:120])
                telemetry.counter('guard_ckpt_fallbacks_total').inc()
                continue
            if epoch != self.model_epoch:
                print('resume fell back to epoch %d (epoch %d checkpoint '
                      'invalid)' % (epoch, self.model_epoch))
                self.model_epoch = epoch
                self.args['restart_epoch'] = epoch
            return
        raise FileNotFoundError(
            'no loadable checkpoint at or below epoch %d in %s'
            % (self.model_epoch, model_dir))

    def _rollback_source(self):
        """(epoch, trainer_state bytes) of the newest valid checkpoint pair
        for the non-finite guard's in-place rollback; None before the first
        checkpoint lands (the guard then stays in skip mode)."""
        from .utils.fs import read_verified_bytes
        self._collect_checkpoint()   # never a pair with a write half done
        blob = read_verified_bytes(self.trainer_state_path())
        if blob is None:
            return None
        epoch, _discarded = guard_mod.newest_valid_epoch(
            self.args.get('model_dir', 'models'))
        if epoch <= 0:
            return None
        return epoch, blob

    def _apply_rollback(self, epoch: int):
        """The trainer restored its TrainState in place; rewind the
        model-pool epoch and the actor-facing host params to match, so
        subsequent checkpoints overwrite the poisoned trajectory."""
        self._collect_checkpoint()
        try:
            with open(self.model_path(epoch), 'rb') as f:
                self.wrapper.load_params_bytes(f.read(), self._example_obs)
        except Exception as exc:
            _LOG.error('rollback: could not reload epoch %d params (%s: %s)',
                       epoch, type(exc).__name__, str(exc)[:120])
        prev = self.model_epoch
        self.model_epoch = min(self.model_epoch, epoch)
        print('guard: rolled back to epoch %d (from epoch %d)'
              % (self.model_epoch, prev))

    def _fused_guard_observe(self, metrics: Dict[str, float], fp):
        """Guard escalation for the fused loop (single-threaded: the
        rollback happens inline, including the model-pool rewind)."""
        tr = self.trainer
        bad = int(metrics.get('nonfinite') or 0)
        if bad:
            telemetry.counter('guard_nonfinite_total').inc(bad)
        cnt = int(metrics.get('data_count') or 0)
        loss_mean = (float(metrics['total']) / cnt
                     if cnt and 'total' in metrics else None)
        action = tr.guard.observe(bad, max(0, fp.sgd_steps - bad), loss_mean)
        if action == 'abort':
            raise RuntimeError(
                'guard: %d non-finite update(s) under nonfinite_policy='
                'abort' % bad)
        if action == 'skip':
            _LOG.warning('guard: skipped %d non-finite update(s) '
                         '(%d consecutive)', bad, tr.guard.consecutive)
        if action != 'rollback':
            return
        src = self._rollback_source()
        if src is None:
            _LOG.error('guard: rollback tripped but no valid checkpoint '
                       'exists yet; continuing with skipped updates')
            tr.guard.reset_streak()
            return
        epoch, blob = src
        tr.load_state_bytes(blob)   # place_state lays it back on the mesh
        tr.guard.reset_streak()
        tr.guard.rollbacks += 1
        telemetry.counter('guard_rollbacks_total').inc()
        _LOG.error('guard: non-finite training burst — rolled back to '
                   'checkpoint epoch %d (steps %d)', epoch, tr.steps)
        self._apply_rollback(epoch)

    def _poll_rollback(self):
        """Pick up a rollback the trainer thread performed since the last
        loop iteration (threaded/replay trainers; the fused loop rolls back
        inline)."""
        epoch = self.trainer.rollback_epoch
        if epoch is not None:
            self.trainer.rollback_epoch = None
            self._apply_rollback(epoch)

    def _gc_checkpoints(self):
        """``keep_checkpoints: N`` retention: drop numbered ckpts beyond
        the newest N (plus their sidecars). League-opponent checkpoint
        paths and registry-pinned versions (the serving tier's champion or
        any live candidate — serving/registry.py) are never deleted; the
        rollback target (the newest valid epoch) is always inside the kept
        window. An unreadable registry manifest SUSPENDS the GC pass: with
        the pin set unknown, deleting anything could pull a champion out
        from under a live service."""
        keep = int(self.args.get('keep_checkpoints') or 0)
        if keep <= 0:
            return
        from .utils.fs import layout_path, sidecar_path
        model_dir = self.args.get('model_dir', 'models')
        epochs = guard_mod.numbered_checkpoints(model_dir)
        if len(epochs) <= keep:
            return
        from .serving.registry import pinned_checkpoint_paths
        pinned = pinned_checkpoint_paths(self._registry_root())
        if pinned is None:
            return   # corrupt manifest: conservatively collect nothing
        if getattr(self, '_league', None) is not None:
            # league-pool members must outlive the retention window for as
            # long as PFSP can sample them (the member window can trail
            # keep_checkpoints); counted via guard_ckpt_gc_pinned_total
            # like any registry pin
            pinned = pinned | {os.path.abspath(p)
                               for p in self._league.member_paths()}
        protected = {os.path.abspath(o)
                     for o in (self.args.get('eval', {}).get('opponent') or [])
                     if isinstance(o, str) and os.path.exists(o)}
        for epoch in epochs[:-keep]:
            path = self.model_path(epoch)
            apath = os.path.abspath(path)
            if apath in pinned:
                telemetry.counter('guard_ckpt_gc_pinned_total').inc()
                continue   # registry-pinned: serving depends on these bytes
            if apath in protected:
                continue   # checkpoint league opponent
            for p in (path, sidecar_path(path), layout_path(path)):
                try:
                    os.unlink(p)
                except OSError:
                    pass
            telemetry.counter('guard_ckpt_gc_total').inc()

    def final_flush(self):
        """ONE code path for the fused-loop tail flush and the preemption
        snapshot: persist the current TrainState/params at most once, so a
        SIGTERM landing during the final epoch cannot write
        trainer_state.ckpt twice with different step counts."""
        if self._final_flushed:
            return
        # drain, then write inline: this snapshot is the last
        # trainer_state.ckpt written, after every epoch's own. (A failed
        # write is raised here with the flush still to make: run()'s exit
        # makes it.)
        self._collect_checkpoint()
        self._final_flushed = True
        tr = self.trainer
        params = steps = blob = None
        if self._fused_active:
            if tr.state is not None:
                from .utils.fetch import fetch_tree
                host_state = fetch_tree(tr.state)
                params, steps = host_state.params, tr.steps
                blob = tr.state_bytes(host_state)
        elif (tr.started and tr.state is not None
              and self._trainer_thread is not None
              and self._trainer_thread.is_alive()):
            # threaded/server modes: the trainer owns the state — force an
            # epoch close and take the handover at the next batch boundary
            try:
                params, steps, blob = tr.update(timeout=60)
            except queue.Empty:
                _LOG.warning('flush: trainer did not reach a safe point in '
                             'time; keeping the last epoch checkpoint')
        if params is None:
            return
        if (self.model_epoch == self._last_ckpt_epoch
                and steps == self._last_ckpt_steps):
            return   # nothing advanced since the last write
        self.update_model(params, steps, blob, bump=False)

    def _write_preempt_record(self):
        """Final metrics_jsonl record tagged ``preempted`` + the exit-code
        contract line the supervisor greps for. Steps are the FLUSHED
        count (what resume will restore), not the live trainer counter —
        the JSONL step sequence stays monotonic across the restart."""
        telemetry.counter('guard_preemptions_total').inc()
        if getattr(self, '_league_ratings', None) is not None \
                and self._league_journal:
            # the ratings journal rides the preemption flush: the restart
            # reloads it bit-identically (atomic write, sorted keys)
            self._league_ratings.save(self._league_journal)
        steps = max(self._last_ckpt_steps, 0)
        self._write_metrics(steps, extra={
            'preempted': True, 'signal': int(self.preempt.signum or 0)})
        print('preempted: checkpoint flushed at epoch %d (steps %d); '
              'exiting %d for a supervisor restart'
              % (self.model_epoch, steps,
                 guard_mod.PREEMPT_EXIT_CODE), flush=True)

    # -- accounting -------------------------------------------------------
    def feed_episodes(self, episodes: List[Optional[dict]],
                      recovered: bool = False):
        """``recovered=True`` marks a restart replay from the episode
        spool: the episodes were already WAL'd and their ratings already
        journaled, so they skip the spool append and the league booking —
        everything else (guard screen, generation stats, the returned
        counter, the buffer) treats them exactly like a fresh upload."""
        if self._check_episodes:
            # ingest guard: one poisoned actor (NaN observations/rewards)
            # must not contaminate every future batch — drop and count
            # before anything enters the episode deque
            clean: List[Optional[dict]] = []
            for episode in episodes:
                if (episode is not None
                        and not guard_mod.episode_is_finite(episode)):
                    self._bad_episodes += 1
                    telemetry.counter('guard_bad_episodes_total').inc()
                    _LOG.warning('guard: dropped episode with non-finite '
                                 'data (%d total)', self._bad_episodes)
                    continue
                clean.append(episode)
            episodes = clean
        for episode in episodes:
            if episode is None:
                continue
            if self._spool is not None and not recovered:
                # WAL before ANY accounting: a SIGKILL past this line
                # replays the episode on restart; before it, the episode
                # never existed (its ledger task re-issues byte-identically)
                self._spool.append(
                    self.num_returned_episodes,
                    conn_pack({'idx': self.num_returned_episodes,
                               'episode': episode}))
            if episode.get('record_version'):
                # device-actor records that follow the device rng contract
                # instead of the host byte contract arrive stamped; the
                # counter keeps the divergence observable fleet-wide
                telemetry.counter(
                    'device_actor_stamped_episodes_total').inc()
            for p in episode['args']['player']:
                # attribute stats to the model that actually generated the
                # episode (the reference books everything under the current
                # epoch — its correct line is commented out at
                # train.py:461-462; with chunked generation spanning epoch
                # boundaries that skew would only grow)
                model_id = (episode['args'].get('model_id') or {}).get(p, -1)
                if model_id is None or model_id < 0:
                    model_id = self.model_epoch
                outcome = episode['outcome'][p]
                n, r, r2 = self.generation_results.get(model_id, (0, 0, 0))
                self.generation_results[model_id] = (n + 1, r + outcome,
                                                     r2 + outcome ** 2)
            if not recovered:
                self._league_observe_episode(episode)
            self.num_returned_episodes += 1
            if self.num_returned_episodes % 100 == 0:
                # complete line at debug level, not a bare dot stream that
                # splices mid-line with worker-process output
                _LOG.debug('returned %d episodes', self.num_returned_episodes)

        live = [e for e in episodes if e is not None]
        telemetry.counter('learner_episodes_returned_total').inc(len(live))
        # ingest stamp for the sample-age histogram: selection-time age is
        # measured against this learner-side clock (no cross-host skew)
        now = time.time()
        for e in live:
            e.setdefault('recv_time', now)
        self.trainer.episodes.extend(live)
        if self.trainer.ingest_queue is not None:
            # best-effort under backlog, but every drop is counted — the
            # metrics JSONL exposes how much generation never reached the ring
            for e in live:
                try:
                    self.trainer.ingest_queue.put_nowait(e)
                except queue.Full:
                    self.trainer.replay_stats['dropped_episodes'] += 1

        self._evict_episode_overflow()

    def _evict_episode_overflow(self):
        """Bound the host episode deque (memory-pressure-aware), shared by
        the whole-episode and streamed-chunk ingest paths."""
        mem_percent = psutil.virtual_memory().percent
        mem_ok = mem_percent <= 95
        maximum_episodes = (self.args['maximum_episodes'] if mem_ok else
                            int(len(self.trainer.episodes) * 95 / mem_percent))
        if self.trainer.replay is not None:
            # replay mode: training data lives in the HBM ring; the host
            # deque only gates startup and feeds metrics — don't hold a
            # second full copy of the buffer
            maximum_episodes = min(maximum_episodes,
                                   2 * self.args['minimum_episodes'])
        if not mem_ok and 'memory_over' not in self.flags:
            warnings.warn('memory usage %.1f%% with buffer size %d' %
                          (mem_percent, len(self.trainer.episodes)))
            self.flags.add('memory_over')
        while len(self.trainer.episodes) > maximum_episodes:
            self.trainer.episodes.popleft()

    def feed_chunks(self, chunks: List[Optional[dict]],
                    recovered: bool = False,
                    marks: Optional[list] = None) -> list:
        """Streamed-ingest twin of :meth:`feed_episodes` (streaming.py).

        Each (ledger-screened) chunk is WAL'd, folded into its assembly,
        and — the moment its contiguous prefix grows — training-visible as
        a partial buffer entry. A completed assembly closes its ledger
        task and runs the exact whole-episode accounting feed_episodes
        runs, on the byte-identical reassembled record. Returns the
        ``(key, final_args)`` pairs of the episodes completed here (spool
        recovery uses them to cancel restored book entries)."""
        from .streaming import chunk_key
        completed = []
        for j, chunk in enumerate(chunks):
            if chunk is None:
                continue
            mark = marks[j] if marks is not None \
                else self.num_returned_episodes
            if self._spool is not None and not recovered:
                # WAL before ANY accounting (same stance as feed_episodes):
                # recovery replays the chunk, the assembler dedupes it
                self._spool.append(
                    self.num_returned_episodes,
                    conn_pack({'idx': self.num_returned_episodes,
                               'chunk': chunk}))
            res = self._assembler.add(chunk, mark=mark)
            status = res.get('status')
            if status == 'dropped':
                continue
            entry = res.get('entry')
            if res.get('new') and entry is not None:
                entry.setdefault('recv_time', time.time())
                self.trainer.episodes.append(entry)
            if status != 'complete':
                continue
            key = chunk_key(chunk)
            final_args = res.get('final_args') or {}
            completed.append((key, final_args))
            if self.ledger is not None:
                self.ledger.complete_chunked(key, final_args.get('task_id'))
            record = res.get('record')
            if record is None:
                # a poisoned chunk froze the assembly: the task closed, the
                # record drops whole (mirrors the feed_episodes screen)
                self._bad_episodes += 1
                telemetry.counter('guard_bad_episodes_total').inc()
                _LOG.warning('guard: dropped streamed episode with '
                             'non-finite data (%d total)', self._bad_episodes)
                continue
            if record.get('record_version'):
                telemetry.counter(
                    'device_actor_stamped_episodes_total').inc()
            for p in record['args']['player']:
                model_id = (record['args'].get('model_id') or {}).get(p, -1)
                if model_id is None or model_id < 0:
                    model_id = self.model_epoch
                outcome = record['outcome'][p]
                n, r, r2 = self.generation_results.get(model_id, (0, 0, 0))
                self.generation_results[model_id] = (n + 1, r + outcome,
                                                     r2 + outcome ** 2)
            if not recovered:
                self._league_observe_episode(record)
            self.num_returned_episodes += 1
            telemetry.counter('learner_episodes_returned_total').inc()
            if self.num_returned_episodes % 100 == 0:
                _LOG.debug('returned %d episodes',
                           self.num_returned_episodes)
            if self.trainer.ingest_queue is not None and entry is not None:
                try:
                    self.trainer.ingest_queue.put_nowait(entry)
                except queue.Full:
                    self.trainer.replay_stats['dropped_episodes'] += 1
        self._evict_episode_overflow()
        return completed

    def feed_device_chunk(self, done, outcome,
                          model_id: Optional[int] = None) -> int:
        """Episode accounting for device-ingested rollout chunks: only the
        (done, outcome) arrays reach the host — trajectories stay in HBM
        (ops/device_windows.py). Mirrors feed_episodes' generation stats
        (every player's outcome counts, feed over args['player']).
        ``model_id`` is the epoch whose params generated the chunk, captured
        by the caller at dispatch time so stats survive epoch boundaries."""
        if model_id is None:
            model_id = self.model_epoch
        ks, envs = np.nonzero(done)
        num_players = outcome.shape[-1]
        for k, i in zip(ks, envs):
            for p in range(num_players):
                oc = float(outcome[k, i, p])
                n, r, r2 = self.generation_results.get(model_id, (0, 0, 0))
                self.generation_results[model_id] = (n + 1, r + oc,
                                                     r2 + oc ** 2)
            self.num_episodes += 1
            self.num_returned_episodes += 1
            if self.num_returned_episodes % 100 == 0:
                _LOG.debug('returned %d episodes', self.num_returned_episodes)
        telemetry.counter('learner_episodes_returned_total').inc(len(ks))
        return len(ks)

    def feed_results(self, results: List[Optional[dict]],
                     model_id: Optional[int] = None):
        """``model_id`` lets pipelined device evaluators attribute results
        to the epoch whose params were actually playing when the chunk was
        dispatched (they deliver results one dispatch late)."""
        if model_id is None:
            model_id = self.model_epoch
        for result in results:
            if result is None:
                continue
            for p in result['args']['player']:
                res = result['result'][p]
                n, r, r2 = self.results.get(model_id, (0, 0, 0))
                self.results[model_id] = (n + 1, r + res, r2 + res ** 2)
                opp_map = self.results_per_opponent.setdefault(model_id, {})
                opponent = result['opponent']
                n, r, r2 = opp_map.get(opponent, (0, 0, 0))
                opp_map[opponent] = (n + 1, r + res, r2 + res ** 2)
            self._league_observe_result(result)

    # -- league plumbing --------------------------------------------------
    def _league_gen_opponent(self, sample_key: int):
        """PFSP draw for the 'g' task stamped ``sample_key``: the
        ``(member, model_id)`` the opponent seats carry, or None for the
        self-play share / an empty pool. Deterministic per (seed,
        sample_key) — a ledger re-issue keeps the assignment anyway (the
        ledger replays the booked role_args verbatim, fault.py)."""
        if getattr(self, '_league', None) is None \
                or self._league_ratings is None:
            return None
        member = self._league.sample_opponent(
            int(self.args.get('seed') or 0), sample_key, self._league_ratings)
        if member is None:
            return None
        mid = self._league.member_model_id(member)
        if mid is None:
            return None
        return member, mid

    def _league_rating_opponent(self, counter: int):
        """Round-robin rating-match opponent for the 'e' slice, or None
        when this slot stays a configured-pool eval match."""
        if getattr(self, '_league', None) is None:
            return None
        rate = float(self._league.args.get('rating_match_rate', 0.25))
        if rate <= 0.0:
            return None
        # every ceil(1/rate)-th 'e' task becomes a rating match — a
        # deterministic stride, not a draw: coverage is the goal here
        stride = max(1, int(round(1.0 / rate)))
        if counter % stride != 0:
            return None
        return self._league.rating_opponent(counter // stride)

    def _league_model_snapshot(self, model_id) -> Optional[dict]:
        """'model' RPC fallback: resolve a league member version through
        the registry manifest (CRC-verified load) when the numbered
        checkpoint is gone from model_dir. None when the league is off or
        the registry cannot produce the version either."""
        if getattr(self, '_league', None) is None:
            return None
        try:
            snap = self._ensure_registry().load_snapshot(
                self._league.line, str(model_id))
            return {k: snap[k] for k in ('architecture', 'params', 'config')
                    if k in snap}
        except Exception as exc:
            _LOG.warning('league: registry could not resolve model %s '
                         '(%s: %s)', model_id, type(exc).__name__,
                         str(exc)[:120])
            return None

    def _league_observe_episode(self, episode: dict):
        """Book a league 'g' outcome: the learner's score vs the member
        the server seated (stamped league_opponent/league_seat)."""
        if getattr(self, '_league_ratings', None) is None:
            return
        args = episode.get('args') or {}
        member = args.get('league_opponent')
        if not member:
            return
        outcome = episode['outcome'].get(args.get('league_seat'))
        if outcome is None:
            return
        self._league_ratings.record(member, (float(outcome) + 1.0) / 2.0)
        self._league_sampled[member] = self._league_sampled.get(member, 0) + 1
        telemetry.counter('league_games_total').inc()
        self._league_flush_maybe()

    def _league_observe_result(self, result: dict):
        """Book a league rating match ('e' slice): the evaluated seat's
        result vs the member named by the task's opponent override."""
        if getattr(self, '_league_ratings', None) is None:
            return
        args = result.get('args') or {}
        if not args.get('league_rating_match'):
            return
        member = result.get('opponent')
        seats = args.get('player') or []
        if not member or not seats:
            return
        res = result['result'].get(seats[0])
        if res is None:
            return
        self._league_ratings.record(member, (float(res) + 1.0) / 2.0)
        telemetry.counter('league_games_total').inc()
        self._league_flush_maybe()

    def _league_flush_maybe(self):
        """Write the rating journal through shortly after an outcome lands
        (league.rating_flush_seconds min-interval): a hard-killed learner
        loses at most that window of ratings instead of everything since
        the last epoch sync. The journal write is already atomic
        (RatingBook.save -> atomic_write_bytes), so a kill mid-flush
        leaves the previous journal intact."""
        if getattr(self, '_league_ratings', None) is None \
                or not self._league_journal:
            return
        interval = float((self.args.get('league') or {})
                         .get('rating_flush_seconds', 5.0))
        if interval <= 0:
            return
        now = time.monotonic()
        if now - self._league_last_flush < interval:
            return
        self._league_last_flush = now
        self._league_ratings.save(self._league_journal)

    def _print_league_stats(self):
        if getattr(self, '_league', None) is None \
                or self._league_ratings is None:
            return
        book = self._league_ratings
        print('league: learner=%.1f games=%d members=%d champion=%s '
              'promotions=%d'
              % (book.rating(league_mod.LEARNER), book.games_since_promote,
                 len(self._league.members()), self._league.champion,
                 book.promotions))

    # -- telemetry plumbing ----------------------------------------------
    def _telemetry_snapshots(self) -> List[dict]:
        """Exporter collector: live local registry + the latest merged
        fleet snapshot (tagged source="fleet" to keep keys disjoint)."""
        telemetry.gauge('learner_epoch').set(self.model_epoch)
        telemetry.gauge('learner_buffer_episodes').set(
            len(self.trainer.episodes))
        telemetry.gauge('learner_sgd_steps_per_sec').set(
            self.trainer.last_steps_per_sec)
        snaps = [telemetry.snapshot()]
        fleet = self._last_fleet_telemetry
        if fleet and fleet.get('peers'):
            snaps.append(telemetry.relabel(fleet, source='fleet'))
        return snaps

    def _status_info(self) -> Dict[str, Any]:
        """/statusz payload: run progress, alert state, fleet host map.
        Scrape-driven alert evaluation shares the cadence gate with the
        server loop, so a scrape storm cannot distort rate windows."""
        info: Dict[str, Any] = {'progress': {
            'epoch': self.model_epoch,
            'steps': int(getattr(self.trainer, 'steps', 0)),
            'episodes': self.num_returned_episodes,
            'buffer': len(self.trainer.episodes)}}
        if self._alerts is not None:
            info['alerts'] = self._alerts.maybe_evaluate(
                self._telemetry_snapshots)
        if getattr(self, 'fleet', None) is not None:
            info['fleet_hosts'] = self.fleet.snapshot()
        if telemetry.perf_plane_enabled():
            info['perf'] = telemetry.perf_status()
        return info

    def _merge_fleet_telemetry(self) -> dict:
        """Aggregate the registry snapshots that rode in on the latest
        heartbeat per peer (gathers pre-merge their workers' snapshots)."""
        peers = self.worker.peer_info().values() if self.worker else ()
        merged = telemetry.merge_snapshots(
            [p.get('telemetry') for p in peers if isinstance(p, dict)])
        self._last_fleet_telemetry = merged
        return merged

    def _lag_snapshot(self) -> Dict[str, float]:
        """Epoch means of the policy-lag / sample-age histograms (delta
        since the previous epoch), mirrored onto plainly-named gauges so
        ``policy_lag`` and ``sample_age_seconds`` are scrapeable live."""
        out: Dict[str, float] = {}
        batcher = getattr(self.trainer, 'batcher', None)
        if batcher is None:
            return out
        for attr, key in (('_m_lag', 'policy_lag'),
                          ('_m_age', 'sample_age_seconds')):
            hist = getattr(batcher, attr, None)
            if hist is None:
                continue
            s, n = hist.sum, hist.count
            prev_s, prev_n = self._lag_marks.get(key, (0.0, 0))
            self._lag_marks[key] = (s, n)
            if n > prev_n:
                mean = (s - prev_s) / (n - prev_n)
                out[key] = round(mean, 4)
                telemetry.gauge(key + '_mean').set(mean)
        return out

    # -- device profiling (profile_epochs) --------------------------------
    def _maybe_profile(self):
        """Open/close the jax.profiler device trace around the epochs the
        ``profile_epochs`` knob names: epoch N's SGD work runs between the
        close of epoch N-1 and the close of epoch N, so the trace starts
        at the boundary BEFORE a chosen epoch and stops at its close
        (Trainer._start/_stop_trace are idempotent and exception-safe)."""
        if not self._profile_epochs:
            return
        tr = self.trainer
        if tr._trace_active:
            tr._stop_trace()
        if (self.model_epoch + 1) in self._profile_epochs:
            _LOG.info('profiling epoch %d (device trace -> %s)',
                      self.model_epoch + 1, tr._profile_dir)
            try:
                tr._start_trace()
            except Exception as exc:
                _LOG.warning('profiler start failed (%s: %s)',
                             type(exc).__name__, str(exc)[:120])

    # -- epoch boundary ---------------------------------------------------
    def update(self):
        print()
        print('epoch %d' % self.model_epoch)
        self._print_eval_stats()
        self._print_generation_stats()
        self._print_league_stats()

        with telemetry.trace_span('epoch_update'):
            params, steps, state_blob = self.trainer.update()
        if params is None and self.trainer.failed:
            _LOG.error('training failed (see traceback above); shutting down')
            self.shutdown_flag = True
            return
        if params is None:
            params = self.wrapper.params
        self.update_model(params, steps, state_blob)
        self._write_metrics(steps)
        self._maybe_profile()
        self.flags = set()

    def _write_metrics(self, steps: int, extra: Optional[dict] = None):
        if not self._metrics_path:
            return
        rec = {'epoch': self.model_epoch, 'steps': steps,
               'episodes': self.num_returned_episodes, 'time': time.time(),
               'run_id': telemetry.run_id(),
               'sgd_steps_per_sec': round(self.trainer.last_steps_per_sec, 3),
               'buffer': len(self.trainer.episodes)}
        if extra:
            rec.update(extra)
        gen = self.generation_results.get(self.model_epoch - 1)
        if gen:
            n, r, _ = gen
            rec['generation_mean'] = r / (n + 1e-6)
        ev = self.results.get(self.model_epoch - 1)
        if ev:
            n, r, _ = ev
            rec['win_rate'] = (r / (n + 1e-6) + 1) / 2
        # per-opponent rows ride EVERY record (the console line still
        # collapses a 1-opponent pool to the reference format): with a
        # league pool the aggregate win rate hides exactly the per-member
        # signal the ratings are built from
        ev_opp = self.results_per_opponent.get(self.model_epoch - 1)
        if ev_opp:
            rec['eval_opponents'] = {
                name: {'games': n,
                       'win_rate': round((r / (n + 1e-6) + 1) / 2, 4)}
                for name, (n, r, _r2) in sorted(ev_opp.items())}
        if getattr(self, '_league', None) is not None \
                and self._league_ratings is not None:
            book = self._league_ratings
            names = sorted(set(book.names()) | set(self._league.roster()))
            rec['league'] = {
                'champion': self._league.champion,
                'members': self._league.members(),
                'ratings': {n: round(book.rating(n), 2) for n in names},
                'games': {n: book.games(n) for n in names},
                'games_since_promote': book.games_since_promote,
                'promotions': book.promotions,
                'opponents_sampled': dict(sorted(
                    self._league_sampled.items())),
            }
        # fast runs see only a handful of eval games per epoch (an epoch can
        # last ~2s); a trailing-window aggregate keeps the quality curve
        # readable from the JSONL alone
        recent = [self.results[e] for e in
                  range(max(0, self.model_epoch - 10), self.model_epoch)
                  if e in self.results]
        if recent:
            n = sum(t[0] for t in recent)
            r = sum(t[1] for t in recent)
            rec['win_rate_recent10'] = (r / (n + 1e-6) + 1) / 2
            rec['eval_games_recent10'] = n
        if self.trainer.replay is not None:
            stats = self.trainer.replay_stats
            rec['replay_dropped_episodes'] = stats['dropped_episodes']
            rec['replay_ring_occupancy'] = round(
                self.trainer.ring_occupancy(), 4)
            rec['replay_sample_reuse'] = round(
                stats['samples_drawn'] / max(1, stats['windows_ingested']), 3)
        # learning dynamics (ops/train_step.py diag metrics, per epoch):
        # rho/c clip fractions, importance-ratio moments, entropy, grad
        # norm — the off-policy health the streaming-ingest and staleness-
        # weighting work will be judged against (docs/observability.md)
        rec.update(self.trainer.last_dynamics)
        # policy-lag accounting: epoch means of the lag/age histograms the
        # batcher observes at window selection (consumption time)
        rec.update(self._lag_snapshot())
        # guard health: cumulative skipped non-finite updates, in-place
        # rollbacks, and dropped poisoned episodes (guard.py)
        rec['guard_nonfinite'] = self.trainer.guard.total_bad
        rec['guard_rollbacks'] = self.trainer.guard.rollbacks
        rec['guard_bad_episodes'] = self._bad_episodes
        # compiled-performance plane: per-epoch device-memory sample (the
        # hbm_pressure alert input — only the learner publishes the ratio
        # gauge, a ratio must not sum across fleet snapshots), steady-state
        # marking once warm-up is over, and the chaos retrace probe
        # (HANDYRL_TPU_CHAOS=retraceepoch=N) for e2e sentinel drills
        if telemetry.perf_plane_enabled():
            mem_rows = telemetry.sample_device_memory()
            telemetry.gauge('device_mem_utilization').set(
                round(telemetry.device_memory_utilization(mem_rows), 6))
            # the fused loop's warm-up epochs can pass before its training
            # program has compiled once: there, wait for a train dispatch
            if (not telemetry.steady_state_active()
                    and self.model_epoch >= self._retrace_warmup
                    and (self._fused_trained or not self._fused_active)):
                telemetry.mark_steady_state(
                    'epoch %d (retrace_warmup_epochs=%d)'
                    % (self.model_epoch, self._retrace_warmup))
            chaos_at = self._chaos.get('retraceepoch')
            if chaos_at is not None and self.model_epoch == int(chaos_at):
                self._chaos_retrace_probe()
        if getattr(self, 'ledger', None) is not None:
            rec.update({'fleet_' + k: v
                        for k, v in self._fleet_snapshot().items()
                        if k != 'disconnects'})
        # unified telemetry: the learner's own registry plus the merged
        # per-peer snapshots that rode in on heartbeat frames (worker-mode
        # runs), histograms reduced to count/sum/p50/p95/p99
        telemetry.gauge('learner_epoch').set(self.model_epoch)
        telemetry.gauge('learner_buffer_episodes').set(
            len(self.trainer.episodes))
        rec['telemetry'] = telemetry.summarize(telemetry.snapshot())
        if self.worker is not None:
            rec['fleet_telemetry'] = telemetry.summarize(
                self._merge_fleet_telemetry())
        # SLO alert state rides every record: active names, cumulative
        # fired counts, and the last evaluated value per rule
        if self._alerts is not None:
            rec['alerts'] = self._alerts.maybe_evaluate(
                self._telemetry_snapshots)
        # size-based rotation (telemetry.metrics_rotate_mb): long runs must
        # not grow the JSONL unboundedly — atomic rename to `.1` keeps one
        # previous generation around for postmortems
        if self._metrics_rotate_mb > 0 and rotate_file(
                self._metrics_path, self._metrics_rotate_mb):
            telemetry.counter('metrics_rotations_total').inc()
        # append-safe single-write line + fsync: a killed learner can never
        # leave a torn half-line that breaks downstream JSONL parsing
        append_jsonl(self._metrics_path, rec)
        telemetry.trace_flush()   # epoch boundary: land buffered spans

    def _chaos_retrace_probe(self):
        """Chaos hook: compile a deliberately fresh jitted program after
        steady state so an e2e drill can watch the retrace sentinel fire
        (retrace_storm alert, flight-recorder event, abort policy)."""
        _LOG.warning('chaos: compiling a fresh program at epoch %d to '
                     'trigger the retrace sentinel', self.model_epoch)

        def chaos_retrace_probe(x):
            return x + 1.0
        # device_put (not jnp.zeros) so the only fresh compile the sentinel
        # sees — and names — is chaos_retrace_probe itself
        jax.jit(chaos_retrace_probe)(jax.device_put(
            np.zeros((self.model_epoch % 7 + 1,), np.float32)))

    def _run_eval_share(self, evaluator, tracker: Dict[str, int],
                        budget_s: Optional[float] = None):
        """Advance online evaluation until its share of episodes reaches
        eval_rate. The host evaluator advances all its matches ONE ply per
        call while chunked generators deliver episodes in bursts, so it gets
        several plies per loop iteration or it never finishes a match; the
        device evaluator finishes whole batches per call and exits after one
        step once the share is met. ``budget_s`` (the fused loop's) bounds
        how long one share may hold the loop: after its first step, a share
        whose steps have taken that long ends, and what is still owed is
        made up in the shares that follow. ``tracker`` carries the previous
        dispatch's epoch for pipelined evaluators (their results arrive one
        dispatch late)."""
        pipelined = getattr(evaluator, 'pipelined', False)
        t0 = time.perf_counter()
        for n in range(16):
            if self.num_results >= self.eval_rate * self.num_episodes:
                break
            if (n and budget_s is not None
                    and time.perf_counter() - t0 >= budget_s):
                break
            cur = self.model_epoch
            results = evaluator.step()
            self.num_results += len(results)
            self.feed_results(
                results,
                model_id=tracker.get('prev', cur) if pipelined else cur)
            tracker['prev'] = cur

    # -- generation front-end A: in-process batched self-play -------------
    def _run_batched(self):
        """TPU-first local mode: vectorized self-play + interleaved eval in
        this process; no worker processes at all."""
        args = self.args
        actor = ModelWrapper(self.wrapper.module)
        # actor params live ON DEVICE, refreshed once per epoch — binding
        # the learner's numpy copy would re-upload the full parameter set
        # on every rollout/eval dispatch
        actor.params = put_tree(self.wrapper.params)
        env_args = args['env']

        def make_env_fn(i):
            e = make_env({**env_args, 'id': i})
            return e

        env_mod = None
        chunk_steps = int(args.get('device_chunk_steps') or 16)
        if args.get('device_generation'):
            from .environment import make_jax_env
            env_mod = make_jax_env(env_args)
            if env_mod is None:
                raise ValueError(
                    'device_generation: True needs an env with a pure-JAX '
                    'twin; %r has none (unset it to run host envs)'
                    % env_args['env'])

        # device-ingest layout (when the env/config allows assembling
        # training windows on device, ops/device_windows.py). On a
        # multi-device mesh only the fused pipeline runs device ingest
        # (shard_map over 'data': per-shard envs + ring, gradient psum);
        # generation_envs must divide the device count, as batch_size
        # already does where a mesh exists.
        n_dev = len(self.trainer.mesh.devices.flat) \
            if self.trainer.mesh is not None else 1
        eval_envs = int(args.get('eval_envs')
                        or max(4, args.get('generation_envs', 64) // 8))
        # the shard_map'd fused pipeline is pure data parallelism: it
        # requires a 1-wide 'model' axis and replicate-everything partition
        # rules (tensor-parallel configs train through the jit paths, whose
        # in/out shardings come from the rule engine)
        from .parallel.partition import pure_data_parallel
        mesh_fused_ok = (
            self.trainer.mesh is None
            or (int(self.trainer.mesh.shape.get('model', 1)) == 1
                and pure_data_parallel(self.trainer.partition_rules)))
        want_ingest = (env_mod is not None and args.get('device_replay')
                       and args.get('device_ingest', True))
        if (self.trainer.mesh is not None and mesh_fused_ok and want_ingest
                and args.get('generation_envs', 64) % n_dev != 0):
            # the trainer already shards over the mesh; dropping to the
            # threaded path here would change the program, not its size
            raise ValueError(
                'generation_envs %d does not divide the %d-device mesh: the '
                'sharded fused pipeline gives every device the same number '
                'of envs' % (args.get('generation_envs', 64), n_dev))
        if self.trainer.mesh is not None and mesh_fused_ok \
                and eval_envs % n_dev != 0:
            # eval_envs is only a throughput knob — round it up to the mesh
            # rather than silently disqualifying the sharded trainer
            from .parallel.mesh import pad_to_multiple
            eval_envs = pad_to_multiple(eval_envs, n_dev)
        ingest_mode = None
        if want_ingest and mesh_fused_ok:
            simultaneous = bool(getattr(env_mod, 'SIMULTANEOUS', False))
            if simultaneous and not args['turn_based_training']:
                ingest_mode = 'solo'
            elif not simultaneous and args['turn_based_training']:
                # observation=True is admitted too: every env records only
                # the acting seat per ply (``observers()`` defaults empty,
                # reference environment.py:84), so the compact 'turn'
                # window layout computes training math identical to the
                # wide (B,T,P) observation layout for per-sample models
                # (gradient-level proof: tests/test_turn_layout_parity.py);
                # with batch-statistics norms the compact layout's
                # statistics exclude the wide layout's zeroed non-acting
                # seat rows (window-tail pad rows still enter, as in the
                # reference's train-mode BatchNorm). The device loss runs
                # with observation=False to match the layout.
                ingest_mode = 'turn'

        # the loss config the fused pipeline trains with: identical to
        # the host trainer's except when 'turn' ingest serves an
        # observation=True config (see the gate comment above)
        tr = self.trainer
        tr.device_cfg = tr.cfg
        if ingest_mode == 'turn' and args['observation']:
            tr.device_cfg = tr.cfg._replace(observation=False)

        opponents = args.get('eval', {}).get('opponent', []) or ['random']

        def device_eval_ok():
            """'random', checkpoint (feedforward OR recurrent — the
            evaluator plumbs an opponent hidden tree through the rollout
            scan), and (where the env twin vectorizes its agent as
            ``greedy_action``) 'rulebase' opponents run on device; other
            rulebases use the host evaluator."""
            if env_mod is None or not args.get('device_eval', True):
                return False
            if len(opponents) > eval_envs:   # every opponent needs an env
                return False
            for o in opponents:
                if o == 'random':
                    continue
                if o == 'rulebase' and hasattr(env_mod, 'greedy_action'):
                    continue   # vectorized rulebase runs on device
                if isinstance(o, str) and os.path.exists(o):
                    continue   # checkpoint league opponent
                return False
            return True

        if device_eval_ok():
            # eval matches ride the accelerator too: the host evaluator's
            # one-dispatch-per-ply cost dominates chunked device generation
            from .device_generation import DeviceEvaluator
            # shard eval envs only when the sharded fused trainer runs (its
            # replicated actor params are what the eval program binds)
            eval_mesh = (self.trainer.mesh
                         if (self.trainer.mesh is not None
                             and ingest_mode is not None) else None)
            evaluator = DeviceEvaluator(env_mod, actor, args,
                                        n_envs=eval_envs,
                                        chunk_steps=chunk_steps,
                                        mesh=eval_mesh,
                                        opponents=opponents)
        else:
            evaluator = BatchedEvaluator(make_env_fn, actor, args,
                                         n_envs=eval_envs)

        if ingest_mode is not None:
            # the fully-fused loop: rollout + ingest + K SGD steps per
            # dispatch, driven single-threaded (ops/fused_pipeline.py)
            from .ops.device_windows import DeviceWindower
            windower = DeviceWindower(
                mode=ingest_mode, fs=args['forward_steps'],
                bi=args['burn_in_steps'],
                max_steps=_declared_max_steps(env_mod),
                windows_cap=(args.get('replay_windows_per_episode')
                             or max(1, 64 // args['forward_steps'])),
                # on a mesh each shard owns ring_capacity/n_dev rows; the
                # global ring keeps the configured total budget
                capacity=max(1, self.trainer.replay.capacity // n_dev),
                num_players=env_mod.NUM_PLAYERS, gamma=args['gamma'],
                has_reward=hasattr(env_mod, 'rewards'),
                # a net that reads a window as one sequence is told where
                # in its game the window starts
                first_position=hasattr(self.wrapper.module, 'sequence'))
            return self._run_fused(env_mod, actor, evaluator, windower,
                                   ingest_mode)

        gen = None
        if env_mod is not None:
            from .device_generation import DeviceGenerator
            gen = DeviceGenerator(env_mod, actor, args,
                                  n_envs=args.get('generation_envs', 64),
                                  chunk_steps=chunk_steps)
            gen.step = gen.step_chunk   # same streaming surface
        if gen is None:
            gen = BatchedGenerator(make_env_fn, actor, args,
                                   n_envs=args.get('generation_envs', 64))

        cadence = _EpochCadence(args)
        actor_epoch = self.model_epoch
        # pipelined generators return the PREVIOUS dispatch's chunk: stamp
        # episodes with the epoch captured when that chunk was dispatched
        chunk_epoch = self.model_epoch
        eval_tracker: Dict[str, int] = {}

        def stamp_and_feed(episodes, epoch):
            for ep in episodes:
                self.num_episodes += 1
                # in-process generators leave model_id unset (-1): stamp
                # the epoch whose params played the episode
                mid = ep['args'].setdefault('model_id', {})
                for p, v in list(mid.items()):
                    if v is None or v < 0:
                        mid[p] = epoch
            self.feed_episodes(episodes)

        while not self.shutdown_flag:
            if self._deadline and time.time() >= self._deadline:
                break                      # wall-clock budget spent mid-epoch
            if self.preempt.requested():
                _LOG.warning('preemption signal received; snapshotting '
                             'and exiting')
                break
            self._poll_rollback()
            if actor_epoch != self.model_epoch:   # follow latest epoch
                actor.params = put_tree(self.wrapper.params)
                actor_epoch = self.model_epoch
            dispatch_epoch = self.model_epoch
            # pipelined generators return the PREVIOUS dispatch's
            # episodes (stamp with that dispatch's epoch); host-path
            # generators return episodes finished under current params
            stamp_and_feed(gen.step(),
                           chunk_epoch if getattr(gen, 'pipelined', False)
                           else dispatch_epoch)
            chunk_epoch = dispatch_epoch

            self._run_eval_share(evaluator, eval_tracker)

            if cadence.due(self.num_returned_episodes):
                self.update()
                if self._past_epoch_budget():
                    self.shutdown_flag = True

        # account the one speculative chunk still in the pipeline
        if hasattr(gen, 'drain_episodes'):
            stamp_and_feed(gen.drain_episodes(), chunk_epoch)
        if hasattr(evaluator, 'drain'):
            self.feed_results(evaluator.drain(),
                              model_id=eval_tracker.get('prev'))

    # -- generation front-end A': the fully-fused device loop --------------
    def _run_fused(self, env_mod, actor, evaluator, windower, mode):
        """Single-threaded steady state: ONE program dispatch per loop
        iteration runs rollout chunk + window ingest + K SGD steps
        (ops/fused_pipeline.py). The trainer thread stays parked — there is
        no queue competition on the device stream, and the only per-chunk
        host traffic is the previous chunk's (done, outcome) fetch.

        Sample reuse is explicit here: ``sgd_steps_per_chunk`` pins the
        replay ratio instead of letting the trainer thread spin as fast as
        dispatch latency allows."""
        args = self.args
        tr = self.trainer
        self._fused_active = True   # final_flush reads tr.state directly
        n_dev = len(tr.mesh.devices.flat) if tr.mesh is not None else 1
        print('fused device pipeline: rollout+ingest+train in one dispatch '
              '(%s mode%s)' % (mode, ', sharded over %d devices' % n_dev
                               if tr.mesh is not None else ''))
        from .ops.fused_pipeline import FusedPipeline
        if args.get('max_sample_reuse'):
            print('note: max_sample_reuse applies to the threaded replay '
                  'trainer; the fused pipeline pins reuse via '
                  'sgd_steps_per_chunk instead')
        sgd_steps = int(args.get('sgd_steps_per_chunk') or 16)   # doc: config.py
        tr.windower = windower   # ring occupancy reporting
        fp = FusedPipeline(
            env_mod, actor, tr.device_cfg, windower, args,
            n_envs=args.get('generation_envs', 64),
            chunk_steps=int(args.get('device_chunk_steps') or 16),
            sgd_steps=sgd_steps, batch_size=args['batch_size'],
            default_lr=tr.default_lr, seed=args.get('seed', 0),
            mesh=tr.mesh, attention_key_share=tr.attention_key_share)

        cadence = _EpochCadence(args)
        actor_epoch = self.model_epoch
        pending_metrics: List[Any] = []
        epoch_steps = 0
        epoch_t0 = time.time()
        eval_tracker: Dict[str, int] = {}
        # feed_device_chunk is one fetch behind dispatch; chunk -> epoch
        # attribution therefore uses the epoch captured at dispatch time
        epoch_of_dispatch = deque()
        # each iteration is one `fused_iter` span whose children (`dispatch`
        # and `host_block` inside the pipeline, the rest below) feed the
        # stage histograms; the monitor keeps the per-chunk record
        monitor = telemetry.ChunkMonitor()

        def account(prev):
            if prev is None:
                return
            self.feed_device_chunk(prev['done'], prev['outcome'],
                                   epoch_of_dispatch.popleft())
            if prev['metrics'] is not None:
                pending_metrics.append(prev['metrics'])
                # guard: the 'nonfinite' skip count is already a host
                # float on the packed fetch — escalation costs no sync
                self._fused_guard_observe(prev['metrics'], fp)

        # actor/eval params refresh DEVICE-to-device from the train state:
        # no host round trip, and correct even on epochs where
        # checkpoint_interval skipped the host snapshot. A real copy (not an
        # alias) is required — the next fused dispatch donates tr.state.
        # A net that acts through a cache reads every weight each ply: it
        # names the dtype its actor's copy is kept in (``actor_param_dtype``,
        # its compute dtype), and the copy is a cast. Every other net's copy
        # is the float32 parameters as they are.
        actor_dtype = getattr(self.wrapper.module, 'actor_param_dtype', None)
        copy_leaf = (jnp.copy if actor_dtype is None
                     else lambda x: x.astype(actor_dtype))

        def copy_tree(p):
            return jax.tree_util.tree_map(copy_leaf, p)
        if tr.mesh is not None:
            # pin the replicated layout up front so dispatches never
            # re-broadcast device-0 arrays across the mesh
            from .parallel.mesh import replicated_sharding
            repl = replicated_sharding(tr.mesh)
            actor.params = jax.device_put(actor.params, repl)
            if tr.state is not None:
                tr.state = jax.device_put(tr.state, repl)
            copy_params = jax.jit(copy_tree, out_shardings=repl)
        else:
            copy_params = jax.jit(copy_tree)
        if tr.state is not None:
            # first refresh NOW (same values the actor already holds): the
            # copy program compiles during warm-up, not at the first epoch
            # boundary after the retrace sentinel has armed
            actor.params = copy_params(tr.state.params)
            if actor_dtype is not None and isinstance(
                    jax.tree_util.tree_leaves(self.wrapper.params)[0],
                    jax.Array):
                # the learner's snapshot lives on the host from the first
                # checkpoint on (_advance_epoch); a seeded initialisation
                # of gigabytes is moved there now, not left on the device
                # beside the train state that was copied from it
                from .utils.fetch import fetch_tree
                self.wrapper.params = fetch_tree(self.wrapper.params)

        def warming():
            # on a mesh, also hold warmup until EVERY shard's ring slice
            # has at least one window (a shard with local size 0 would
            # feed all-zero batches into the psum'd gradient);
            # ring_min_host is one fetch behind, which only extends
            # warmup by one chunk
            return (self.num_returned_episodes < args['minimum_episodes']
                    or (tr.mesh is not None and fp.dispatches > 0
                        and fp.ring_min_host < 1))

        def step(warm):
            """One chunk: the actor follows the epoch, the chunk is
            enqueued under the epoch's tag and the PREVIOUS chunk's result
            is collected (returned). Called at the head of an iteration or,
            for the next iteration, from inside an epoch boundary."""
            nonlocal actor_epoch
            if actor_epoch != self.model_epoch:
                with telemetry.trace_span('actor_refresh'):
                    actor.params = (copy_params(tr.state.params)
                                    if tr.state is not None
                                    else put_tree(self.wrapper.params))
                actor_epoch = self.model_epoch
            epoch_of_dispatch.append(self.model_epoch)
            if warm:
                return fp.warm_step(actor.params)
            ema = tr.data_cnt_ema
            if tr.chaos_nan.due(tr.steps, fp.sgd_steps):
                _LOG.warning('chaos: injecting non-finite update at '
                             'step %d', tr.steps)
                ema = float('nan')   # poisons the on-device lr schedule
            tr.state, prev = fp.train_step(actor.params, tr.state, ema)
            # the training program has compiled: from here a compile
            # is a retrace (the sentinel arms at the next boundary)
            self._fused_trained = True
            tr.steps += fp.sgd_steps
            return prev

        # a boundary that took the next iteration's step itself
        # (_fused_epoch): the result that step collected, and its seconds
        ahead = None

        def enqueue_next(it, boundary):
            """The next iteration's step, from inside ``boundary``. Not
            where the loop ends at this boundary (no chunk runs past the
            last checkpoint) or the next chunk is a warm-up one (it donates
            nothing)."""
            nonlocal ahead
            if (self.shutdown_flag or self._past_epoch_budget()
                    or self.preempt.requested() or warming()):
                return False
            t0 = time.perf_counter()
            prev = step(False)
            ahead = (prev, time.perf_counter() - t0)
            monitor.fetched(fp.dispatches, it, boundary)
            return True

        while not self.shutdown_flag:
            if self._deadline and time.time() >= self._deadline:
                break                      # wall-clock budget spent mid-epoch
            if self.preempt.requested():
                _LOG.warning('preemption signal received; snapshotting '
                             'and exiting')
                break
            with telemetry.trace_span(
                    'fused_iter',
                    step_num=fp.dispatches + (ahead is None)) as it:
                iter_t0 = time.perf_counter()
                # a checkpoint the writer has finished is announced here,
                # one chunk after its boundary at the earliest
                self._collect_checkpoint(block=False)
                if ahead is None:
                    warm = warming()
                    prev = step(warm)
                    if not warm:
                        epoch_steps += fp.sgd_steps
                    monitor.fetched(fp.dispatches, it)
                else:
                    # the last boundary made this iteration's step: its
                    # seconds count towards the eval share's budget here
                    (prev, ahead_s), ahead, warm = ahead, None, False
                    iter_t0 -= ahead_s
                chunk = fp.dispatches
                with telemetry.trace_span('chunk_account') as span:
                    account(prev)
                    span.set(episodes_admitted=self.num_returned_episodes)
                with telemetry.trace_span('eval_share') as span:
                    # evaluation may hold the loop for a share of what the
                    # training stretch of this iteration just did (its
                    # dispatch, the wait for the previous one, the
                    # accounting): the small nets' 3 ms chunks never feel
                    # it, a net whose eval chunk costs a third of its
                    # training dispatch gets one chunk an iteration instead
                    # of a burst of up to sixteen that stops training for
                    # ten seconds
                    budget_s = EVAL_SHARE_OF_TRAINING * (
                        time.perf_counter() - iter_t0)
                    self._run_eval_share(evaluator, eval_tracker,
                                         budget_s=budget_s)
                    span.set(eval_dispatches=getattr(evaluator, 'dispatches',
                                                     0),
                             budget_ms=round(1e3 * budget_s, 3))
                if cadence.due(self.num_returned_episodes):
                    with telemetry.trace_span('epoch_boundary') as span:
                        first = self._fused_epoch(
                            pending_metrics, epoch_steps,
                            time.time() - epoch_t0, fp, evaluator,
                            monitor.epoch_block(),
                            lambda: enqueue_next(it, span))
                        span.set(epoch=self.model_epoch,
                                 enqueued_first=int(first))
                    pending_metrics.clear()   # account() closes over this list
                    # a chunk the boundary enqueued is the new epoch's
                    epoch_steps = fp.sgd_steps if first else 0
                    epoch_t0 = time.time()
                    if self._past_epoch_budget():
                        self.shutdown_flag = True
                # the monitor's sums since the loop began ride every
                # iteration's span, a step-less one too: a reader takes
                # their growth between any two records
                it.set(dispatch=chunk, warm=int(warm), **monitor.totals)
            monitor.closed(it)
        if ahead is not None:
            # the loop ended between a boundary and the iteration it had
            # enqueued for (a deadline, a signal): that chunk's predecessor
            account(ahead[0])
        monitor.flush()
        account(fp.drain())
        if hasattr(evaluator, 'drain'):
            self.feed_results(evaluator.drain(),
                              model_id=eval_tracker.get('prev'))
        # checkpoint_interval may have skipped the last epoch's file write,
        # and a preemption lands mid-epoch: one shared idempotent flush
        # covers both (it also writes the preempt snapshot, so a SIGTERM
        # during the final epoch cannot write trainer_state twice)
        self.final_flush()

    def _fused_epoch(self, pending_metrics, epoch_steps, epoch_wall,
                     fp, evaluator, fused_block, enqueue_next):
        """Epoch boundary for the fused loop: drain metric futures, print
        the reference-format lines, update the lr EMA, checkpoint.
        ``fused_block`` is the loop's per-chunk record of the epoch
        (telemetry.ChunkMonitor.epoch_block): it rides the metrics record
        and its dispatch/wait split feeds the utilization proxy.
        ``enqueue_next`` is the loop's: it makes the next iteration's step
        (that chunk enqueued, the chunk in flight collected; False where it
        must not). Returns whether the boundary enqueued that chunk BEFORE
        it fetched the train state."""
        tr = self.trainer
        with telemetry.trace_span('epoch_report',
                                  chunks=len(pending_metrics)):
            self._fused_epoch_report(pending_metrics, epoch_steps,
                                     epoch_wall, fp)

        # What a checkpoint costs the loop is the fetch of the train state:
        # it waits for the chunk in flight, and the next dispatch donates
        # tr.state. The fetch is two steps (utils/fetch.py): a pack of the
        # leaves into ONE new device buffer, enqueued behind that chunk, and
        # the blocking transfer of it. The buffer is no argument of the
        # fused program, so where every leaf packs it is a snapshot that
        # outlives the donation: the boundary packs, bumps the epoch,
        # MAKES THE NEXT ITERATION'S STEP (`enqueue_next`: the refreshed
        # actor, the new epoch's tag, the new ema: what that iteration
        # would give it; the step collects the chunk in flight as ever, so
        # the loop stays one dispatch deep) and only then fetches, with the
        # device at work on the new chunk through the transfer, the record
        # and the hand-over. A state with a leaf over LARGE_LEAF_BYTES (a
        # trunk of gigabytes, fetched leaf by leaf because a second device
        # copy has no room) keeps fetch-then-enqueue. Serialisation and the
        # fsynced writes run on the writer thread (PERF.md section 6, PRs 32
        # and 35). With checkpoint_interval > 1, intermediate epochs skip
        # the host round trip entirely — the actor/eval params refresh
        # device-to-device in the fused loop, so nothing here needs host
        # bytes.
        interval = int(self.args.get('checkpoint_interval') or 1)
        final = 0 <= self.args['epochs'] <= self.model_epoch + 1
        # what the record and the checkpoint say of THIS epoch's end: a
        # step made ahead books the next chunk's SGD steps and dispatch
        steps, dispatches = tr.steps, fp.dispatches
        enqueued_first = False
        snapshot = host_state = None
        telemetry.counter('epoch_boundaries_total').inc()
        if interval <= 1 or (self.model_epoch + 1) % interval == 0 or final:
            from .utils import fetch
            with telemetry.trace_span('state_pack'):
                if fetch.packs_whole(tr.state):
                    snapshot = fetch.pack_tree(tr.state, detach=True)
                    self._bump_epoch()
            if snapshot is not None:
                enqueued_first = enqueue_next()
            # ONE packed transfer for params + optimizer state, not one
            # blocking np.asarray per leaf
            with telemetry.trace_span('state_fetch') as span:
                host_state = (fetch.fetch_tree(tr.state) if snapshot is None
                              else fetch.fetch_packed(snapshot))
                span.set(bytes=sum(
                    leaf.nbytes for leaf in
                    jax.tree_util.tree_leaves(host_state)))
            job, waited = self._hand_over_checkpoint(
                host_state, steps, bump=snapshot is None)
            fused_block['ckpt_wait_s'] = round(waited, 6)
        else:
            job = None
            with telemetry.trace_span('epoch_advance'):
                self.update_model(None, steps, write_files=False)
        # (an inc of 0 registers the counter: its share of the boundaries
        # reads 0, not "no such counter", where the order is always kept)
        telemetry.counter('epoch_boundaries_enqueued_first_total').inc(
            int(enqueued_first))
        fused_block['enqueued_first'] = enqueued_first
        try:
            telemetry.set_utilization_proxy(fused_block.get('utilization'))
            rec_extra = {'dispatches_gen': dispatches,
                         'dispatches_eval': getattr(evaluator, 'dispatches',
                                                    0),
                         'fused': fused_block}
            with telemetry.trace_span('metrics_write'):
                self._write_metrics(steps, rec_extra)
            with telemetry.trace_span('checkpoint_submit'):
                self._maybe_profile()
                self.flags = set()
                # the writer starts LAST: its serialisation takes the
                # interpreter lock from what the loop still has to do
                # before the device has its next chunk (PERF.md section 6,
                # PR 32)
                if job is not None:
                    self._ckpt_writer.submit(job)
                    job = None
            # the boundary's references to the fetched state go HERE, under
            # a span, as they went at the function's end before: freeing
            # the packed snapshot's device buffers releases the interpreter
            # lock, the writer thread takes it for its serialisation, and
            # the loop waits 2-3 ms for it (PERF.md section 6, PR 40)
            with telemetry.trace_span('snapshot_release'):
                snapshot = host_state = None
        finally:
            # (a record that failed must not lose the epoch's checkpoint)
            if job is not None:
                self._ckpt_writer.submit(job)
        return enqueued_first

    def _fused_epoch_report(self, pending_metrics, epoch_steps, epoch_wall,
                            fp):
        """What a fused epoch's close prints and sums on the host (span
        ``epoch_report``): the reference-format lines, the loss and
        ``diag_`` sums of the epoch's chunks, the lr EMA, the dynamics and
        the replay counters. No device fetch."""
        tr = self.trainer
        print()
        print('epoch %d' % self.model_epoch)
        self._print_eval_stats()
        self._print_generation_stats()

        data_cnt = 0
        loss_sum: Dict[str, float] = {}
        diag_sum: Dict[str, float] = {}
        for metrics in pending_metrics:   # host floats — no device fetch
            for k, v in metrics.items():
                if k == 'data_count':
                    data_cnt += int(v)
                elif k == 'nonfinite':
                    continue   # guard counter, observed per chunk
                elif k.startswith('diag_'):
                    diag_sum[k] = diag_sum.get(k, 0.0) + float(v)
                else:
                    loss_sum[k] = loss_sum.get(k, 0.0) + float(v)
        if epoch_steps > 0:
            print('loss = %s' % ' '.join(
                [k + ':' + '%.3f' % (l / max(data_cnt, 1))
                 for k, l in sorted(loss_sum.items())]))
            tr.data_cnt_ema = (tr.data_cnt_ema * 0.8
                               + data_cnt / (1e-2 + epoch_steps) * 0.2)
            tr.last_steps_per_sec = epoch_steps / max(epoch_wall, 1e-9)
            tr._diag_sum = diag_sum
            tr.last_dynamics = tr._epoch_dynamics(loss_sum, data_cnt,
                                                  epoch_steps)
        if tr.replay is not None:
            tr.replay_stats['samples_drawn'] += (
                epoch_steps * self.args['batch_size'])
            # ring size + true cumulative ingest count ride the per-chunk
            # packed fetch — no device sync (ring size saturates at
            # capacity once the ring wraps; the ingest counter does not)
            tr._ring_size_host = fp.ring_size_host
            tr.replay_stats['windows_ingested'] = max(
                tr.replay_stats['windows_ingested'],
                fp.windows_ingested_host)

    def _print_eval_stats(self):
        if self.model_epoch not in self.results:
            print('win rate = Nan (0)')
            return

        def output_wp(name, results):
            n, r, r2 = results
            mean = r / (n + 1e-6)
            name_tag = ' (%s)' % name if name != '' else ''
            print('win rate%s = %.3f (%.1f / %d)'
                  % (name_tag, (mean + 1) / 2, (r + n) / 2, n))

        keys = self.results_per_opponent[self.model_epoch]
        if (len(self.args.get('eval', {}).get('opponent', [])) <= 1
                and len(keys) <= 1):
            output_wp('', self.results[self.model_epoch])
        else:
            output_wp('total', self.results[self.model_epoch])
            for key in sorted(keys):
                output_wp(key, keys[key])

    def _print_generation_stats(self):
        if self.model_epoch not in self.generation_results:
            print('generation stats = Nan (0)')
            return
        n, r, r2 = self.generation_results[self.model_epoch]
        mean = r / (n + 1e-6)
        std = (r2 / (n + 1e-6) - mean ** 2) ** 0.5
        print('generation stats = %.3f +- %.3f' % (mean, std))

    # -- generation front-end B: RPC server over workers ------------------
    def server(self):
        """4-RPC conductor: args / episode / result / model
        (reference train.py:541-627; 'model' answers with an architecture
        name + msgpack params snapshot, never pickled code).

        Every assigned task is booked in a :class:`TaskLedger` with a
        deadline; tasks stranded on a detached endpoint (the Hub's
        heartbeat/liveness machinery journals those) or past their deadline
        are re-issued ahead of fresh assignments, WITHOUT re-incrementing
        ``num_episodes``/``num_results`` — so episode accounting converges
        and budgeted runs cannot hang waiting for episodes a dead host will
        never deliver. Duplicate uploads (a gather resending an un-acked
        RPC after reconnect) are dropped by the same book.

        On top of the ledger sits ELASTIC FLEET CONTROL
        (:class:`~.fault.FleetController`): every peer endpoint maps to a
        host key (socket peers by address — gathers on one machine share
        one health record across reconnects; pipe peers individually), and
        each host carries a health state (healthy / degraded / draining /
        quarantined) fed by ledger strandings and by the engine-failover /
        engine-restart counters riding heartbeat telemetry. Flapping hosts
        stop receiving fresh tasks — they get 'idle' placeholders while
        their booked work drains — sit out a quarantine, and are
        re-admitted. State transitions are exported as per-host
        ``fleet_host_state`` gauges, a transitions counter, the per-epoch
        ``fleet:`` line, and ``fleet_host_states`` in metrics_jsonl."""
        _LOG.info('started server')
        cadence = _EpochCadence(self.args)
        ft = self.args.get('fault_tolerance') or {}
        ledger = self.ledger = TaskLedger(
            deadline=float(ft.get('task_deadline', 300.0)))
        if self._restored_ledger is not None:
            # previous incarnation's in-flight book: restored tasks
            # re-issue with their original sample_keys ahead of fresh work
            ledger.restore_state(self._restored_ledger)
            self._restored_ledger = None
        if self._recovered_closed_chunks:
            # streamed assemblies spool recovery reassembled and counted:
            # close their keys so a reattached gather's resend replays
            # screen as duplicates instead of re-building the episode
            ledger.seed_closed_chunks(self._recovered_closed_chunks)
            self._recovered_closed_chunks = []
        if self._ledger_journal is not None:
            ledger.journal = self._ledger_journal
        if self._durable_restored:
            # restored counters already crossed earlier epoch thresholds —
            # the dead incarnation consumed them (its checkpoints exist);
            # drain the cadence so they are not re-fired as empty epochs
            while cadence.due(self.num_returned_episodes):
                pass
        fleet = self.fleet = FleetController(
            degrade_after=int(ft.get('host_degrade_after', 1)),
            quarantine_after=int(ft.get('host_quarantine_after', 3)),
            health_window=float(ft.get('host_health_window', 120.0)),
            quarantine_period=float(ft.get('host_quarantine_period', 60.0)))
        host_of: Dict[Any, str] = {}       # endpoint -> host key
        fault_seen: Dict[Any, float] = {}  # endpoint -> fault counter mark
        m_withheld = telemetry.counter('fleet_tasks_withheld_total')

        def host_key(ep) -> str:
            """Stable host identity for an endpoint: socket peers key by
            address (a respawned/reconnected gather from the same machine
            keeps its health history), pipe peers individually."""
            key = host_of.get(ep)
            if key is None:
                try:
                    sock = getattr(ep, 'sock', None)
                    # a closed FramedConnection still has the attribute
                    # with sock=None — that's a dead socket peer, not a pipe
                    if sock is None and hasattr(ep, 'sock'):
                        raise OSError('socket already closed')
                    key = ('host-%s' % sock.getpeername()[0]
                           if sock is not None
                           else 'local-%d' % ep.fileno())
                except (OSError, AttributeError):
                    key = 'host-unknown'
                host_of[ep] = key
                if fleet.observe(key):
                    telemetry.gauge('fleet_host_state', host=key).set(
                        telemetry.HOST_STATE_CODES[fleet.state(key)])
            return key

        def pump_fleet_health():
            """Feed the controller and mirror its transitions to metrics:
            strandings from the ledger, soft faults (engine restarts and
            worker failovers) from heartbeat telemetry deltas, then the
            time/drain-driven transitions."""
            for ep, _reason, _t in ledger.drain_stranding_events():
                host = host_of.get(ep)
                if host is not None:
                    fleet.record_stranding(host)
            for ep, info in self.worker.peer_info().items():
                if not isinstance(info, dict) or ep not in host_of:
                    continue
                counters = (info.get('telemetry') or {}).get('counters') or {}
                cur = sum(v for k, v in counters.items()
                          if k.startswith(('engine_restarts_total',
                                           'worker_engine_failovers_total')))
                prev = fault_seen.get(ep, 0)
                if cur > prev:   # < prev = the peer process restarted
                    fleet.record_soft_fault(host_of[ep], cur - prev)
                fault_seen[ep] = cur
            outstanding: Dict[str, int] = {}
            for ep, n in ledger.outstanding_by_endpoint().items():
                host = host_of.get(ep)
                if host is not None:
                    outstanding[host] = outstanding.get(host, 0) + n
            fleet.tick(outstanding)
            for host, prev, state, _t in fleet.drain_transitions():
                _LOG.warning('fleet: host %s %s -> %s', host, prev, state)
                telemetry.gauge('fleet_host_state', host=host).set(
                    telemetry.HOST_STATE_CODES[state])
                telemetry.counter('fleet_host_transitions_total',
                                  **{'from': prev, 'to': state}).inc()
            if self._alerts is not None:
                # the cadence gate makes this an ~interval-spaced stream
                # even though the loop spins every recv timeout
                self._alerts.maybe_evaluate(self._telemetry_snapshots)

        while self.worker.connection_count() > 0 or not self.shutdown_flag:
            if self.preempt.requested():
                # preemption: don't wait for the fleet to wind down — the
                # snapshot happens in run()'s flush, gathers redial the
                # restarted learner on their own (PR 2 supervision)
                _LOG.warning('preemption signal received; snapshotting '
                             'and exiting')
                self.shutdown_flag = True
                break
            self._poll_rollback()
            # fleet supervision runs even when no RPC arrives: stranded
            # tasks must re-enter the queue or the epoch cadence starves
            detached = []
            for ep, reason, _t in self.worker.drain_detach_events():
                lost = ledger.fail_endpoint(ep)
                detached.append(ep)
                if lost:
                    _LOG.warning('re-issuing %d task(s) from detached '
                                 'peer (%s)', lost, reason)
            ledger.reap()
            pump_fleet_health()
            for ep in detached:       # after the stranding drain mapped them
                host_of.pop(ep, None)
                fault_seen.pop(ep, None)
            try:
                conn, (req, data) = self.worker.recv(timeout=0.3)
            except queue.Empty:
                continue

            multi_req = isinstance(data, list)
            if not multi_req:
                data = [data]
            send_data = []

            if req == 'args':
                if self.shutdown_flag:
                    send_data = [None] * len(data)
                elif not fleet.admits(host_key(conn)):
                    # drain-before-detach: a draining/quarantined host gets
                    # placeholder tasks — unbooked and uncounted — so its
                    # workers stay warm for re-admission while its in-
                    # flight work either lands or strands on the ledger
                    fleet.stats['withheld'] += len(data)
                    m_withheld.inc(len(data))
                    send_data = [{'role': 'idle', 'wait': 1.0}
                                 for _ in data]
                else:
                    for _ in data:
                        role_args = ledger.next_reissue()
                        if role_args is None:
                            role_args = {'model_id': {}}
                            if self.num_results < self.eval_rate * self.num_episodes:
                                role_args['role'] = 'e'
                            else:
                                role_args['role'] = 'g'

                            if role_args['role'] == 'g':
                                players = self.env.players()
                                role_args['player'] = players
                                for p in players:
                                    role_args['model_id'][p] = self.model_epoch
                                # league (league.py): the PFSP share seats
                                # a pool member on every non-learner seat;
                                # the learner seat rotates so first-mover
                                # advantage cancels over the stream. The
                                # stamped league_opponent/league_seat ride
                                # the ledger's booked role_args, so a
                                # re-issue keeps the exact assignment.
                                drawn = self._league_gen_opponent(
                                    self.num_episodes)
                                if drawn is not None:
                                    member, mid = drawn
                                    seat = players[
                                        self.num_episodes % len(players)]
                                    for p in players:
                                        if p != seat:
                                            role_args['model_id'][p] = mid
                                    role_args['league_opponent'] = member
                                    role_args['league_seat'] = seat
                                # the action-sampling key: with it, the
                                # episode is a pure function of (seed,
                                # sample_key, params) — identical on the
                                # per-worker and engine inference paths,
                                # on whichever worker the task (or its
                                # ledger re-issue) lands
                                role_args['sample_key'] = self.num_episodes
                                self.num_episodes += 1
                            else:
                                players = self.env.players()
                                role_args['player'] = [
                                    players[self.num_results % len(players)]]
                                for p in players:
                                    role_args['model_id'][p] = (
                                        self.model_epoch if p in role_args['player']
                                        else -1)
                                # league rating matches: a deterministic
                                # slice of 'e' tasks pins its opponent to a
                                # round-robin roster member (the worker's
                                # Evaluator honors the stamped override);
                                # registry members ride as model_id seats,
                                # anchors resolve worker-side by name
                                member = self._league_rating_opponent(
                                    self.num_results)
                                if member is not None:
                                    role_args['opponent'] = member
                                    role_args['league_rating_match'] = True
                                    mid = self._league.member_model_id(member)
                                    if mid is not None and mid > 0:
                                        for p in players:
                                            if p not in role_args['player']:
                                                role_args['model_id'][p] = mid
                                role_args['sample_key'] = self.num_results
                                self.num_results += 1
                        ledger.assign(conn, role_args)
                        send_data.append(role_args)

            elif req == 'episode':
                self.feed_episodes(ledger.admit(data))
                # completions flush AFTER the spool append above: an
                # admitted-but-unflushed kill window recovers from the
                # spool (whose task_ids cancel the restored book entries)
                ledger.flush_journal()
                send_data = [None] * len(data)

            elif req == 'chunk':
                # streamed in-flight windows (streaming.py): screened per
                # (assembly, chunk index), WAL'd, merged — same flush-after-
                # spool ordering as whole episodes, extended to partials
                self.feed_chunks(ledger.admit_chunks(data))
                ledger.flush_journal()
                send_data = [None] * len(data)

            elif req == 'result':
                self.feed_results(ledger.admit(data))
                ledger.flush_journal()
                send_data = [None] * len(data)

            elif req == RESUME_KIND:
                # resume-token handshake: a surviving gather redialed a
                # restarted learner. run_id match => reattach in place
                # (its resend buffer replays as ordinary duplicate-screened
                # uploads); mismatch => the gather cold-respawns, exactly
                # today's behavior for a genuinely different run
                for tok in data:
                    tok = tok if isinstance(tok, dict) else {}
                    ok = (str(tok.get('run_id'))
                          == str(self.args.get('run_id')))
                    if ok and int(tok.get('generation', -1)) \
                            != self._run_generation:
                        telemetry.counter('gather_reattach_total').inc()
                        _LOG.info(
                            'gather %s reattached across a learner restart '
                            '(generation %s -> %d)', tok.get('gather'),
                            tok.get('generation'), self._run_generation)
                    send_data.append(
                        {'ok': ok, 'run_id': str(self.args.get('run_id')),
                         'generation': self._run_generation})

            elif req == 'model':
                for model_id in data:
                    snap = None
                    if model_id == self.model_epoch or model_id <= 0:
                        snap = self.wrapper.snapshot()
                    else:
                        try:
                            from .model import module_config
                            from . import models as model_zoo
                            with open(self.model_path(model_id), 'rb') as f:
                                snap = {'architecture': model_zoo
                                        .architecture_name(self.wrapper.module),
                                        'params': f.read()}
                            # non-default module config (e.g. GeisterNet
                            # norm_kind='batch') must ride along or the
                            # worker rebuilds the registry default, whose
                            # param tree rejects these bytes
                            config = module_config(self.wrapper.module)
                            if config:
                                snap['config'] = config
                        except OSError:
                            # league members can outlive model_dir (GC'd
                            # numbered ckpt, registry-owned bytes): resolve
                            # the version through the registry manifest
                            # before falling back to the live snapshot
                            snap = (self._league_model_snapshot(model_id)
                                    or self.wrapper.snapshot())
                    send_data.append(snap)

            if not multi_req and len(send_data) == 1:
                send_data = send_data[0]
            self.worker.send(conn, send_data)

            if cadence.due(self.num_returned_episodes):
                # abandon streamed assemblies no attempt can ever finish
                # (e.g. a dead device-actor stream, whose re-issue keys a
                # new task_id) so they stop pinning the spool GC horizon
                for key in self._assembler.reap(2 * ledger.deadline):
                    ledger.abandon_chunks(key)
                self.update()
                self._print_fleet_stats()
                if self._past_epoch_budget():
                    self.shutdown_flag = True
        _LOG.info('finished server')

    def _fleet_snapshot(self) -> Dict[str, Any]:
        """Aggregate fleet health: server-side ledger + hub counters plus
        the per-gather stats that ride in on heartbeat payloads."""
        led = self.ledger.stats
        hub = self.worker.hub_stats()
        peers = self.worker.peer_info().values()
        snap = {
            'live': self.worker.connection_count(),
            'outstanding': self.ledger.outstanding(),
            'pending_reissue': self.ledger.pending_reissue(),
            'reissued': led['reissued'],
            'expired': led['expired'],
            'duplicates_dropped': led['duplicates'],
            'detached': hub.get('detached', 0),
            'reconnects': sum(int((p or {}).get('reconnects', 0))
                              for p in peers),
            'dropped_uploads': sum(int((p or {}).get('dropped_uploads', 0))
                                   for p in peers),
        }
        reasons = {k[len('disconnect_'):]: v for k, v in hub.items()
                   if k.startswith('disconnect_')}
        if reasons:
            snap['disconnects'] = reasons
        if getattr(self, 'fleet', None) is not None:
            counts = self.fleet.counts()
            snap['hosts'] = sum(counts.values())
            snap['hosts_degraded'] = counts['degraded']
            snap['hosts_draining'] = counts['draining']
            snap['hosts_quarantined'] = counts['quarantined']
            snap['withheld'] = self.fleet.stats['withheld']
            snap['readmitted'] = self.fleet.stats['readmitted']
            # full per-host map: metrics_jsonl only (popped from the
            # printed line, which carries the counts above)
            snap['host_states'] = self.fleet.snapshot()
        return snap

    def _print_fleet_stats(self):
        if getattr(self, 'ledger', None) is None:
            return
        snap = self._fleet_snapshot()
        # learner-side guard health rides the same per-epoch line
        snap['guard_nonfinite'] = self.trainer.guard.total_bad
        snap['guard_rollbacks'] = self.trainer.guard.rollbacks
        snap['guard_bad_episodes'] = self._bad_episodes
        snap.pop('host_states', None)
        reasons = snap.pop('disconnects', {})
        line = ' '.join('%s=%s' % kv for kv in snap.items())
        if reasons:
            line += ' (%s)' % ', '.join(
                '%s=%d' % kv for kv in sorted(reasons.items()))
        print('fleet: ' + line)

    def shutdown(self):
        """Stop the trainer loop and join its thread so no daemon thread is
        left inside XLA at interpreter exit (which aborts the process). The
        join must outlast one full update step — slow recurrent models can
        take seconds per step on CPU, and an unjoined thread inside XLA
        compute at teardown aborts with 'exception not rethrown'."""
        self.shutdown_flag = True
        # the steady-state flag is process-global: an in-process learner
        # (tests, notebooks) must not leave the retrace sentinel armed for
        # whatever jits next in this process
        telemetry.clear_steady_state()
        if self._spool is not None:
            self._spool.close()
        if self._ledger_journal is not None:
            self._ledger_journal.close()
        self._ckpt_writer.close()
        self.trainer.shutdown()
        if self._trainer_thread is not None:
            self._trainer_thread.join(timeout=300)
            if self._trainer_thread.is_alive():
                _LOG.warning('trainer thread still running at shutdown')
        if self._exporter is not None:
            self._exporter.stop()
            self._exporter = None
        # collate this run's trace JSONL into the Chrome/Perfetto JSON (a
        # no-op with tracing off); the JSONL remains the source of truth
        try:
            out = telemetry.finalize_trace()
            if out:
                _LOG.info('episode trace collated to %s', out)
        except Exception as exc:
            _LOG.warning('trace finalize failed (%s: %s)',
                         type(exc).__name__, str(exc)[:120])
        self.preempt.uninstall()

    def run(self):
        # SIGTERM/SIGINT → cooperative snapshot-and-exit (safe points only);
        # chaos 'preempt=<s>' arms a self-SIGTERM for the e2e tests
        self.preempt.install()
        guard_mod.arm_chaos_preempt(self._chaos)
        self._trainer_thread = threading.Thread(target=self.trainer.run,
                                                name='trainer', daemon=True)
        self._trainer_thread.start()
        self._maybe_profile()   # profile_epochs may name the first epoch
        try:
            if self.use_batched_generation:
                self._run_batched()
            else:
                self.worker.run()
                self.server()
        finally:
            try:
                # no way out of run(), an exception's included, leaves a
                # write in flight or a finished one unannounced. A failed
                # write is raised from here, never swallowed: the flush
                # below is still tried first, as when the loop itself wrote
                self._collect_checkpoint()
            finally:
                if self.preempt.fired:
                    # flush the full checkpoint BEFORE tearing children
                    # down: the supervisor restart must find TrainState +
                    # trainer accounting exactly as of the last safe point
                    try:
                        self.final_flush()
                        self._write_preempt_record()
                    except Exception:
                        import traceback
                        traceback.print_exc()
                self.shutdown()


def _init_multihost(args):
    """Activate jax.distributed when configured (train_args['distributed']
    dict or JAX_COORDINATOR_ADDRESS-style env vars); no-op on single host.

    Must run before any other JAX use so jax.devices() sees the global
    device set; parallel/mesh.py then spans hosts transparently (gradient
    all-reduce on ICI within a slice, DCN across slices)."""
    from .parallel import multihost
    dist = (args.get('train_args') or {}).get('distributed') or {}
    active = multihost.initialize(
        coordinator_address=dist.get('coordinator_address'),
        num_processes=dist.get('num_processes'),
        process_id=dist.get('process_id'))
    if active:
        import jax
        print('multi-host: process %d of %d, %d global devices'
              % (jax.process_index(), jax.process_count(),
                 jax.device_count()))
    return active


def train_main(args):
    _init_multihost(args)
    prepare_env(args['env_args'])
    learner = Learner(args=args)
    learner.run()
    if learner.preempt.fired:
        # supervisor contract: EX_TEMPFAIL asks for a restart into the
        # resume path (restart_epoch: -1 auto-resolves the snapshot)
        raise SystemExit(guard_mod.PREEMPT_EXIT_CODE)
    _exit_if_train_failed(learner)


def _exit_if_train_failed(learner):
    """A dead optimizer (train-thread exception, e.g. a RetraceError under
    HANDYRL_TPU_RETRACE=abort) shuts the run down gracefully — but the
    PROCESS must still exit nonzero or CI reads the failure as a pass."""
    if getattr(learner.trainer, 'failed', False):
        raise SystemExit('training failed: %s'
                         % (learner.trainer.failed_reason or 'see traceback'))


def train_server_main(args):
    _init_multihost(args)
    learner = Learner(args=args, remote=True)
    learner.run()
    if learner.preempt.fired:
        raise SystemExit(guard_mod.PREEMPT_EXIT_CODE)
    _exit_if_train_failed(learner)
