"""Config loading with a defaults layer and validation.

The reference reads config.yaml into a raw dict with no defaults or checks
(main.py:9-10); here every knob has a documented default and unknown keys are
reported, so partial configs work.
"""

from __future__ import annotations

import copy
from typing import Any, Dict

import yaml

TRAIN_DEFAULTS: Dict[str, Any] = {
    'turn_based_training': True,
    'observation': False,
    'gamma': 0.8,
    'forward_steps': 16,
    'burn_in_steps': 0,
    'compress_steps': 4,
    'compress_level': 9,          # bz2 compresslevel for episode moments (1 fastest .. 9 smallest); engine-mode workers are compression-dominated, so actor-starved hosts can trade upload bytes for episodes/sec
    'entropy_regularization': 1.0e-1,
    'entropy_regularization_decay': 0.1,
    'update_episodes': 200,
    'batch_size': 128,
    'minimum_episodes': 400,
    'maximum_episodes': 100000,
    'epochs': -1,
    'num_batchers': 2,
    'eval_rate': 0.1,
    'worker': {'num_parallel': 6},
    'lambda': 0.7,
    'policy_target': 'TD',        # 'UPGO' 'VTRACE' 'TD' 'MC'
    'value_target': 'TD',         # 'VTRACE' 'TD' 'MC'
    'eval': {'opponent': ['random']},
    'seed': 0,
    'restart_epoch': 0,           # resume from models/<n>.ckpt; -1 = auto-resume from the newest checkpoint that passes integrity verification (0 when none exists)
    'init_params': '',            # warm-start: load model params (a .ckpt snapshot of the SAME architecture) at epoch 0, fresh optimizer/episode counters — for measurement runs that need a late-stage policy (e.g. the replay-weighting A/B's long-episode regime)
    # --- TPU-native extensions (absent in the reference) ---
    'batched_generation': True,   # in-process vectorized self-play actors
    'generation_envs': 64,        # env count per batched actor
    'eval_envs': None,            # concurrent online-eval matches; None = max(4, generation_envs // 8)
    'device_chunk_steps': 16,     # plies per device-generation program dispatch
    'device_eval': True,          # device-resident eval matches when device_generation is on and the opponent is 'random'
    'device_ingest': True,        # assemble training windows on device and train in the fused program: one dispatch = rollout chunk + ingest + K SGD steps (device_generation + device_replay)
    'device_generation': False,   # fully device-resident rollouts (envs with a pure-JAX twin)
    'device_replay': False,       # HBM-resident replay ring; batches sampled on device
    'replay_windows_per_episode': None,  # windows ingested per episode (uniformly placed); sets both the ring budget and the sampling WEIGHTING — 1 = exact per-episode mass like the reference's draw (train.py:291-306), >1 weights long episodes by min(len//fs, W). None = max(1, 64 // forward_steps)
    'replay_fused_steps': 8,      # SGD steps fused into one device program in device_replay mode
    'max_sample_reuse': None,     # device_replay threaded trainer: cap samples-drawn / windows-ingested (None = free-spin like the reference)
    'sgd_steps_per_chunk': None,  # fused-pipeline SGD steps per rollout chunk (pins the replay ratio); None = 16
    'checkpoint_interval': 1,     # fused loop: write model/trainer ckpt files every N epochs (params still refresh on device every epoch; a final flush always lands on shutdown)
    'model_dir': 'models',        # checkpoint directory
    'metrics_jsonl': '',          # optional structured metrics path
    'distributed': {},            # multi-host learner: coordinator_address / num_processes / process_id

    # mesh partitioning (parallel/partition.py, docs/large_scale_training.md
    # "Mesh-sharded training"): the learner's compiled steps take explicit
    # NamedShardings over the ('data', 'model') mesh; regex partition rules
    # map the param/optimizer pytree to replicate-vs-sharded specs
    'parallel': {
        'model_parallel': 1,      # width of the mesh's 'model' axis (tensor parallelism); devices/model_parallel becomes the 'data' axis the batch shards over
        'partition_rules': [],    # [[regex, spec], ...] over '/'-joined param/optimizer paths, first match wins; spec = null/[] replicate, 'data'/'model' shard dim 0, or a per-dim axis list like [null, 'model']. [] = replicate everything (pure data parallelism); a trailing catch-all replicate rule is implied
    },

    # distributed-fleet fault tolerance (docs/large_scale_training.md):
    # heartbeats, silent-peer detach, supervised reconnect, task re-issue
    'fault_tolerance': {
        'heartbeat_interval': 10.0,    # gather -> server liveness beacon period (s)
        'liveness_timeout': 60.0,      # detach a silent socket peer after (s); must exceed heartbeat_interval
        'rpc_timeout': 120.0,          # gather-side blocking RPC deadline (s); a dead server fails the call instead of hanging it
        'task_deadline': 300.0,        # re-issue an assigned generation/eval task not returned within (s)
        'reconnect_initial_delay': 1.0,  # first reconnect backoff step (s); doubles per failure, jittered
        'reconnect_max_delay': 30.0,   # backoff ceiling (s)
        'reconnect_max_tries': 30,     # redials before a gather gives up (and respawns before a gather slot is abandoned)
        'resend_buffer': 256,          # max unacked uploads a gather retains across reconnects; older ones are dropped + counted

        # elastic fleet control (fault.FleetController, train.py server()):
        # per-host health states derived from ledger strandings + heartbeat
        # fault telemetry; flapping hosts are drained then quarantined
        # (fresh tasks withheld) and re-admitted after the quarantine
        'host_degrade_after': 1,       # fault signals (strandings or engine failovers/restarts) within host_health_window before a host is marked degraded
        'host_quarantine_after': 3,    # strandings within the window before the host is drained (no fresh tasks) and then quarantined
        'host_health_window': 120.0,   # sliding window (s) for per-host fault accounting
        'host_quarantine_period': 60.0,  # quarantine length (s) before a flapping host is re-admitted with a cleared fault history
    },

    # learner-side crash/corruption resilience (guard.py,
    # docs/large_scale_training.md "Preemption and recovery")
    'guard': {
        'nonfinite_policy': 'rollback',  # non-finite update handling: 'skip' (drop + count), 'rollback' (skip, then restore the last good checkpoint after rollback_after consecutive bad updates or a loss-spike trip), 'abort' (fail the run)
        'rollback_after': 8,           # consecutive non-finite updates before an in-place rollback
        'loss_spike_zscore': 0.0,      # >0: also roll back when the (finite) loss deviates this many EMA stddevs from its running mean; 0 disables
        'check_episodes': True,        # drop (and count) incoming episodes whose decoded observations/rewards contain non-finite values before they reach the buffer
        'preempt_signals': True,       # SIGTERM/SIGINT: flush a full checkpoint at the next safe point and exit 75 (supervisor contract: restart into restart_epoch -1)
    },
    'keep_checkpoints': 0,        # GC numbered models/<epoch>.ckpt beyond the newest N after each save (0 = keep all; league-opponent checkpoint paths are never deleted)

    # durable training plane (spool.py EpisodeSpool + fault.LedgerJournal,
    # docs/large_scale_training.md "Zero-loss training plane"): a SIGKILLed
    # remote learner restarts with zero admitted episodes lost — episodes
    # WAL to a segmented spool before they are counted, the task ledger's
    # outstanding book persists snapshot+delta, and surviving gathers
    # reattach through the resume-token handshake instead of respawning
    'durability': {
        'spool': True,            # WAL every admitted episode under model_dir/spool/ before feed_episodes counts it (remote learners only; a restart replays records past the newest checkpoint's consumption horizon back into the buffer)
        'segment_mb': 64,         # spool segment rotation size (MB); only the live segment can hold a torn tail
        'keep_segments': 2,       # closed segments retained past the GC horizon as cushion (GC runs at each epoch sync; disk stays ~= (keep_segments + 1) * segment_mb + live)
        'ledger_snapshot': True,  # persist the TaskLedger book (ledger.snap at each epoch + ledger.delta.wal between), so a restarted learner re-issues stranded tasks with their ORIGINAL sample_keys
    },

    # streaming partial-episode ingest (streaming.py ChunkAssembler,
    # docs/large_scale_training.md "Streaming ingest"): workers flush
    # fixed-T window chunks of in-flight episodes through the upload path
    # instead of holding completed episodes, so long games stop adding
    # full-episode latency to policy lag. Default off; off is byte-identical
    # to the whole-episode path. Chunk boundaries are a pure function of
    # (seed, sample_key, chunk_steps), so re-issued attempts regenerate
    # identical chunks and the assembler's duplicate screen merges them.
    'streaming': {
        'enabled': False,         # flush in-flight episodes as fixed-T chunks (remote 'g' tasks); the final chunk carries the outcome
        'chunk_steps': 32,        # plies per flushed chunk (T); must be a multiple of compress_steps so chunk-local bz2 blocks land on the whole-episode block grid
        'staleness_half_life': 0.0,  # seconds after which a sampled chunk's selection weight halves (per-chunk recv age); 0 = no staleness-aware reselection (selection byte-identical to whole-episode draws)
        'max_reselect': 4,        # bounded re-draws before a stale window is accepted regardless (keeps selection O(1) under backlog)
        'target_clip': 0.0,       # IMPACT-style clipped target network: V-trace rhos computed against a lagged target policy, clipped at this ceiling; 0 = off (independent of streaming.enabled)
        'target_sync_epochs': 1,  # epochs between target-network refreshes from the live params (target_clip > 0)
    },

    # per-host batched inference service for the distributed actor fleet
    # (inference.py, docs/large_scale_training.md "Actor inference service"):
    # workers become pure env-steppers; one engine per host coalesces their
    # act/plan requests into batched forward passes
    'inference': {
        'enabled': False,        # route worker inference through the host engine
        'batch_wait_ms': 2.0,    # coalescing deadline: how long the engine holds the oldest request while the batch fills (it dispatches early once every local worker has a request in flight)
        'max_batch': 64,         # request cap per dispatched forward batch
        'engine_backend': 'cpu',  # 'cpu' pins the engine to host cores; 'device' lets the engine claim a worker-host-local accelerator (a local --train on an accelerator refuses it at start-up: its gathers would claim the learner's chip)
        'vault_size': 3,         # materialized model snapshots cached (engine-side in engine mode, per worker otherwise — including a degraded worker's local fallback vault)

        # self-healing tier (inference.EngineSupervisor / EngineClient,
        # docs/large_scale_training.md "Engine failover and elastic fleet")
        'queue_max': 1024,       # bounded engine intake queue: submits past it are shed with an immediate error reply (backpressure instead of unbounded growth); 0 = unbounded
        'stall_timeout': 30.0,   # watchdog: a busy engine with no tick progress for this long is declared stalled, its requests error-answered, and a fresh engine started
        'restart_max_delay': 10.0,  # supervised engine-restart backoff ceiling (s); first restart after 0.5s, doubling
        'request_timeout': 10.0,  # worker-side deadline (s) on one engine round trip
        'request_retries': 1,    # resends after a timeout before the worker gives up on the engine for that request
        'failover': True,        # degrade to the per-worker inference path when the engine is unreachable (lossless: records stay byte-identical); False = raise, losing that episode
        'reprobe_initial_delay': 2.0,  # circuit breaker: first half-open probe delay (s) after a degradation, doubling up to reprobe_max_delay
        'reprobe_max_delay': 30.0,     # probe backoff ceiling (s)
    },

    # standalone model-serving tier (serving/, docs/serving.md): a
    # long-lived InferenceService process hosting registry-versioned models
    # behind the framed INFER protocol, plus the learner's
    # publish-to-registry hook and the workers' remote-engine endpoint
    'serving': {
        'port': 9997,            # service listen port (main.py --serve); 0 = ephemeral (reported on the ready line)
        'host': '',              # service bind host ('' = all interfaces)
        'endpoint': '',          # 'host:port' of a remote InferenceService (or a comma-separated list of replica endpoints); engine-mode workers dial it instead of the in-Gather engine (same deadlines/retries/circuit-breaker; with several endpoints a dead replica fails over to the next, and only when ALL are down does the worker degrade to the local path byte-identically)
        'line': 'default',       # model line used by the learner's publish hook and for resolving bare-integer request ids ('<line>@<mid>')
        'registry_dir': '',      # ModelRegistry root (registry.json + owned version files); '' = model_dir
        'publish': False,        # learner: register every numbered checkpoint with the registry as '<line>@<epoch>' (pinning it against keep_checkpoints GC)
        'auto_promote': True,    # with publish: each published version also becomes the line's champion (one atomic manifest swap); False = candidates only, promote by hand
        'engines': 1,            # InferenceEngine fleets inside one service process; models partition across them by handle
        'max_clients': 64,       # admission control: connections past this are refused with an error frame (serve_shed_total) instead of queueing unboundedly
        'drain_timeout': 30.0,   # graceful-drain deadline (s) on SIGTERM: every accepted request is answered before exit 75 (the PreemptionGuard supervisor contract)
        'metrics_port': 0,       # service-side Prometheus /metrics port (0 = exporter off)
        'lock_timeout': 10.0,    # registry manifest-lock deadline (s): a mutation that cannot take the cross-process flock within it raises RegistryLockTimeout (counted registry_lock_timeouts_total) instead of hanging on a wedged peer

        # serving fleet (serving/fleet.py, docs/serving.md "Serving fleet"):
        # a ServiceResolver fronting N InferenceService replicas — replicas
        # register + heartbeat SLO snapshots, clients route through the
        # resolver with per-replica circuit breakers, and an optional
        # autoscaler admits/drains replicas off the p99/shed SLO
        'fleet': {
            'resolver': '',              # 'host:port' of the ServiceResolver a replica registers with (and heartbeats to); '' = standalone service, no fleet membership
            'port': 0,                   # resolver listen port (main.py --serve-fleet); 0 = ephemeral (reported on the fleet_ready line)
            'replica': '',               # this replica's stable name; '' = resolver-assigned. A respawned replica re-registering under its old name is re-admitted immediately (the healthy round trip)
            'advertise': '',             # endpoint host advertised to the resolver ('' = the bind host, or 127.0.0.1 when binding all interfaces)
            'heartbeat_interval': 2.0,   # replica -> resolver liveness + SLO beacon period (s)
            'heartbeat_timeout': 10.0,   # resolver quarantines a replica silent for this long (s); must exceed heartbeat_interval
            'refresh_interval': 2.0,     # router-side replica-table refresh period (s); failures also force a refresh
            'replicas': 2,               # replicas the resolver spawns and supervises under --serve-fleet (0 = externally-managed replicas only)
            'min_replicas': 1,           # autoscaler floor: idle-drain never shrinks the healthy fleet below this
            'max_replicas': 4,           # autoscaler ceiling: SLO-breach admission never grows past this
            'autoscale': False,          # consume the heartbeat SLO snapshots: sustained p99/shed breach admits a standby replica, sustained idleness drains one through the SIGTERM graceful-drain contract
            'slo_p99_ms': 0.0,           # autoscaler p99 latency breach threshold (ms); 0 = breach only on request sheds
            'breach_window': 10.0,       # SLO breach must persist this long (s) before a replica is admitted
            'idle_window': 60.0,         # fleet must be fully idle this long (s) before a replica is drained
            'quarantine_period': 30.0,   # quarantine length (s) before a silent replica is speculatively re-admitted (a re-registration re-admits it immediately)
            'metrics_port': 0,           # resolver-side Prometheus /metrics + /statusz port (0 = exporter off); the fleet's alert engine and replica-state view live here
        },

        # match gateway (serving/gateway.py, docs/serving.md "Match
        # gateway"): the sessionful tier over the fleet — clients open
        # matches, the gateway hosts the env, steps opponent seats through
        # the replicas, and survives replica loss by hidden-state handoff
        # (drain) or byte-identical journal reconstruction (SIGKILL)
        'gateway': {
            'port': 0,               # gateway listen port (main.py --gateway); 0 = ephemeral (reported on the gateway_ready line)
            'resolver': '',          # 'host:port' of the fleet resolver the gateway routes plies through; '' = serving.fleet.resolver
            'model': 'default@champion',  # opponent spec a session opens against when the client names none; floating selectors are pinned to a concrete line@version at open, so a mid-match promote never forks the opponent
            'workers': 4,            # session worker threads; each owns its own RoutedClient, so concurrent sessions' plies coalesce into the engine batch without sharing a submitter
            'max_sessions': 64,      # admission control: opens past this are shed with an error reply (gateway_shed_total) — opens are shed, plies never are
            'ply_timeout': 15.0,     # per-ply fleet round-trip deadline (s); also bounds reconstruction replays
            'monitor_interval': 0.5, # fleet-table poll period (s) for the handoff/reconstruct monitor (and the worker routers' refresh interval)
            'session_timeout': 600.0,  # idle sessions (no ply this long, s) are reaped as drops — an abandoned match must not pin fleet affinity forever
            'metrics_port': 0,       # gateway-side Prometheus /metrics port (0 = exporter off)
        },
    },

    # league training (league.py, docs/league.md): PFSP opponent sampling
    # over registry versions + anchors, persistent Elo ratings, and a
    # rating-gated champion promotion replacing recency auto_promote
    'league': {
        'enabled': False,        # worker-fleet 'g' tasks seat PFSP-sampled pool opponents and an 'e' slice becomes rating matches; requires serving.publish (the pool is the registry line). False = mirror self-play, records byte-identical to pre-league behavior
        'line': '',              # registry line the pool draws members from; '' = serving.line
        'anchors': ['random'],   # built-in pool members needing no checkpoint: 'random' (uniform legal play, usable in 'g' and 'e') and 'rulebase'/'rulebase-<key>' (env rule_based_action; 'e' rating matches only)
        'curve': 'variance',     # PFSP weighting over the learner's per-member win rate p: 'variance' (p*(1-p), prefers even matchups), 'hard' ((1-p)^hard_exponent, prefers members the learner loses to), 'uniform'
        'hard_exponent': 2.0,    # exponent k of the 'hard' curve's (1-p)^k weighting
        'self_play_rate': 0.5,   # fraction of 'g' tasks kept as mirror self-play against the current epoch; the rest seat a PFSP-drawn pool member (deterministic per (seed, sample_key))
        'rating_match_rate': 0.25,  # fraction of 'e' tasks turned into rating matches against a round-robin pool member (the rest keep the configured eval.opponent rotation)
        'max_members': 8,        # newest registry versions kept in the member window (champion + rollback target always included); bounds the GC-pinned set
        'initial_rating': 1200.0,  # Elo rating every member (and the learner) starts from
        'k_factor': 32.0,        # Elo K: max rating delta per game (scaled down by sigma/initial_sigma when track_sigma is on)
        'track_sigma': True,     # TrueSkill-lite: per-member sigma shrinks with games played and scales the effective K, so established ratings move slowly and fresh members converge fast
        'initial_sigma': 200.0,  # starting rating uncertainty under track_sigma
        'min_sigma': 50.0,       # sigma floor under track_sigma (effective K never collapses to 0)
        'promote_margin': 30.0,  # rating-gated promotion: the learner must clear the incumbent champion member's rating by this many Elo points
        'min_games': 20,         # rated games the learner must book since the last champion flip before promotion is considered
        'rating_flush_seconds': 5.0,  # write the rating journal through after an outcome lands, at most this often (s) — a hard-killed learner loses at most this window of ratings instead of a whole epoch; 0 = epoch-sync flushes only
    },

    # fleet generation backend (worker.py gather_loop + DeviceActorGather,
    # device_generation.py DeviceActorEngine, docs/large_scale_training.md
    # "Device actor backend"): how a gather host turns its assigned ledger
    # tasks into episode records
    'generation': {
        'backend': '',            # '' = auto (engine when inference.enabled, else worker); 'worker' = per-worker stepping, 'engine' = host-batched inference, 'device' = the fused Anakin scan (envs with a pure-JAX twin); a gather host overrides it with worker_args.backend
        'device_actor_envs': 64,  # parallel envs inside the device actor's compiled scan — one ledger task per env lane
        'device_actor_chunk_steps': 16,  # plies per compiled chunk dispatch; the scan fill-ratio gauge watches lanes idled by finished episodes
        'device_actor_slots': 2,  # stacked opponent-param slots traced into the ONE compiled program (slot 0 = learner params); league pairings beyond this defer to a later block instead of retracing
        'device_actor_record': '',  # '' = auto per the env twin's RNG_COMPAT contract; 'strict' = replay sampling host-side for byte-compatible records; 'device' = faster device-sampled records, record_version-stamped
    },

    # unified telemetry (docs/observability.md): metric registry + spans +
    # heartbeat-piggybacked fleet aggregation + optional Prometheus endpoint
    # + episode-lifecycle distributed tracing. Accepts a bool (legacy
    # collection switch) or a block:
    #   telemetry: {enabled: true, trace_dir: traces/, trace_sample_rate: 0.1}
    # trace_dir (or HANDYRL_TPU_TRACE=<dir>, which wins) turns on Chrome-
    # trace span export across every fleet process; trace_sample_rate keeps
    # a deterministic fraction of episodes so overhead stays bounded.
    'telemetry': True,            # collect metrics (near-zero cost off; also HANDYRL_TPU_TELEMETRY=0)
    'telemetry_port': 0,          # serve Prometheus text format on this port (0 = exporter off; a busy port retries then falls back to an ephemeral one, logged)
    'profile_epochs': '',         # epochs to wrap in a jax.profiler device trace ('3', '2,5', '3-5'); written to <trace_dir|model_dir>/profile unless profile_dir is set

    'batcher_processes': False,   # build batches in spawned CPU processes instead of threads
    'decode_cache_blocks': 1024,  # LRU capacity (bz2 blocks) of the batchers' decoded-moment cache; recency-biased selection re-decodes the same blocks every batch without it. 0 disables; memory cost ~= blocks * compress_steps * per-moment bytes
    'batcher_shared_memory': False,  # with batcher_processes: children assemble batches in shared-memory arenas and the trainer maps them zero-copy (no pickle over the pipe); slots recycle after the staged device upload completes
    'prefetch_depth': 2,          # device staging ring depth: batches held as in-flight host->device uploads ahead of the compiled update step (1 = single-slot overlap, the pre-ring behavior)
    'compute_dtype': '',          # '' = float32; 'bfloat16' for MXU-friendly activations
    'profile_dir': '',            # when set, capture a jax profiler trace early in training
}

WORKER_DEFAULTS: Dict[str, Any] = {
    'server_address': '',
    'num_parallel': 8,
    'backend': '',   # per-host generation-backend override ('' = follow generation.backend): a host that owns an accelerator sets 'device' while the rest of the fleet keeps the worker/engine path
}


def parse_epoch_set(spec) -> set:
    """Parse the ``profile_epochs`` knob: an int, a list of ints, or a
    comma-separated string accepting ranges ('3', '2,5', '3-5,8')."""
    if not spec:
        return set()
    if isinstance(spec, int):
        return {int(spec)}
    if isinstance(spec, (list, tuple)):
        return {int(x) for x in spec}
    out: set = set()
    for part in str(spec).split(','):
        part = part.strip()
        if not part:
            continue
        if '-' in part and not part.startswith('-'):
            lo, _, hi = part.partition('-')
            out.update(range(int(lo), int(hi) + 1))
        else:
            out.add(int(part))
    return out


def _merge(defaults: Dict[str, Any], overrides: Dict[str, Any]) -> Dict[str, Any]:
    out = copy.deepcopy(defaults)
    for k, v in (overrides or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def load_config(path: str = 'config.yaml') -> Dict[str, Any]:
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    return apply_defaults(raw)


def apply_defaults(raw: Dict[str, Any]) -> Dict[str, Any]:
    args = {
        'env_args': raw.get('env_args', {'env': 'TicTacToe'}),
        'train_args': _merge(TRAIN_DEFAULTS, raw.get('train_args', {})),
        'worker_args': _merge(WORKER_DEFAULTS, raw.get('worker_args', {})),
    }
    validate(args)
    return args


def validate(args: Dict[str, Any]) -> None:
    ta = args['train_args']
    # Both estimators dispatch through the same compute_target
    # (ops/targets.py), exactly as the reference's losses.py:63 does for
    # policy AND value — so all four algorithms are legal for either knob.
    _TARGETS = ('MC', 'TD', 'VTRACE', 'UPGO')
    assert ta['policy_target'] in _TARGETS, ta['policy_target']
    assert ta['value_target'] in _TARGETS, ta['value_target']
    assert ta['forward_steps'] >= 1
    assert ta['burn_in_steps'] >= 0
    assert ta['compress_steps'] >= 1
    assert 0.0 <= ta['eval_rate'] <= 1.0
    assert ta['batch_size'] >= 1
    if 'fused_pipeline' in ta:
        raise ValueError(
            'fused_pipeline is not an option: the fused program is the only '
            'device-ingest learner (remove the key)')
    if ta.get('max_sample_reuse') is not None:
        assert float(ta['max_sample_reuse']) > 0, \
            'max_sample_reuse must be > 0 (unset it to free-spin)'
    if ta.get('prefetch_depth') is not None:
        assert int(ta['prefetch_depth']) >= 1, \
            'prefetch_depth must be >= 1 (or null for the default)'
    ft = ta.get('fault_tolerance') or {}
    for key in ('heartbeat_interval', 'liveness_timeout', 'rpc_timeout',
                'task_deadline', 'reconnect_initial_delay',
                'reconnect_max_delay', 'reconnect_max_tries',
                'resend_buffer', 'host_degrade_after',
                'host_quarantine_after', 'host_health_window',
                'host_quarantine_period'):
        if ft.get(key) is not None:
            assert float(ft[key]) > 0, \
                'fault_tolerance.%s must be > 0' % key
    if ft.get('liveness_timeout') and ft.get('heartbeat_interval'):
        assert float(ft['liveness_timeout']) > float(ft['heartbeat_interval']), \
            'liveness_timeout must exceed heartbeat_interval or every ' \
            'healthy peer is detached between beacons'
    assert int(ta.get('restart_epoch') or 0) >= -1, \
        'restart_epoch must be >= -1 (-1 = auto-resume from the newest ' \
        'valid checkpoint)'
    assert int(ta.get('keep_checkpoints') or 0) >= 0, \
        'keep_checkpoints must be >= 0 (0 keeps every checkpoint)'
    dur = ta.get('durability') or {}
    assert isinstance(dur, dict), \
        'durability must be a block (spool / segment_mb / keep_segments / ' \
        'ledger_snapshot)'
    assert float(dur.get('segment_mb', 64)) > 0, \
        'durability.segment_mb must be > 0'
    assert int(dur.get('keep_segments', 2)) >= 0, \
        'durability.keep_segments must be >= 0 (0 = GC every closed ' \
        'segment past the horizon)'
    stm = ta.get('streaming') or {}
    assert isinstance(stm, dict), \
        'streaming must be a block (enabled / chunk_steps / ' \
        'staleness_half_life / max_reselect / target_clip / ' \
        'target_sync_epochs)'
    assert int(stm.get('chunk_steps', 32)) >= 1, \
        'streaming.chunk_steps must be >= 1'
    assert int(stm.get('chunk_steps', 32)) % int(ta['compress_steps']) == 0, \
        'streaming.chunk_steps must be a multiple of compress_steps so ' \
        'chunk-local bz2 blocks align with the whole-episode block grid ' \
        '(byte-identical reassembly)'
    assert float(stm.get('staleness_half_life', 0.0)) >= 0, \
        'streaming.staleness_half_life must be >= 0 (0 = off)'
    assert int(stm.get('max_reselect', 4)) >= 1, \
        'streaming.max_reselect must be >= 1'
    assert float(stm.get('target_clip', 0.0)) >= 0, \
        'streaming.target_clip must be >= 0 (0 = no target network)'
    assert int(stm.get('target_sync_epochs', 1)) >= 1, \
        'streaming.target_sync_epochs must be >= 1'
    g = ta.get('guard') or {}
    assert str(g.get('nonfinite_policy', 'rollback')) in \
        ('skip', 'rollback', 'abort'), \
        "guard.nonfinite_policy must be 'skip', 'rollback' or 'abort'"
    assert int(g.get('rollback_after', 8)) >= 1, \
        'guard.rollback_after must be >= 1'
    assert float(g.get('loss_spike_zscore', 0.0)) >= 0, \
        'guard.loss_spike_zscore must be >= 0 (0 disables the trip)'
    tel = ta.get('telemetry', True)
    assert isinstance(tel, (bool, dict)), \
        'telemetry must be a bool or a block (enabled / trace_dir / ' \
        'trace_sample_rate / blackbox_dir / recorder_events / ' \
        'metrics_rotate_mb / alerts / perf_plane / retrace / ' \
        'retrace_warmup_epochs)'
    tel_enabled = bool(tel.get('enabled', True)) if isinstance(tel, dict) \
        else bool(tel)
    if isinstance(tel, dict):
        rate = float(tel.get('trace_sample_rate', 1.0))
        assert 0.0 <= rate <= 1.0, \
            'telemetry.trace_sample_rate must be a fraction in [0, 1]'
        assert int(tel.get('recorder_events', 256)) >= 16, \
            'telemetry.recorder_events must be >= 16 (the flight-recorder ' \
            'ring needs room for a useful postmortem tail)'
        assert float(tel.get('metrics_rotate_mb', 0)) >= 0, \
            'telemetry.metrics_rotate_mb must be >= 0 (0 disables rotation)'
        alerts = tel.get('alerts', {})
        assert isinstance(alerts, (bool, dict, list)), \
            'telemetry.alerts must be a block ({builtin, interval, rules}), ' \
            'a rule list, or False'
        if isinstance(alerts, dict) and alerts.get('interval') is not None:
            assert float(alerts['interval']) > 0, \
                'telemetry.alerts interval must be > 0 seconds'
        rules = alerts.get('rules') if isinstance(alerts, dict) else \
            (alerts if isinstance(alerts, list) else None)
        for rule in (rules or []):
            assert isinstance(rule, dict) and rule.get('name') \
                and rule.get('metric'), \
                'each telemetry.alerts rule needs at least name + metric'
        assert str(tel.get('retrace', 'warn')).lower() in \
            ('warn', 'abort', 'off'), \
            "telemetry.retrace must be 'warn', 'abort' or 'off'"
        assert int(tel.get('retrace_warmup_epochs', 1)) >= 0, \
            'telemetry.retrace_warmup_epochs must be >= 0'
    if ta.get('profile_epochs'):
        epochs = parse_epoch_set(ta['profile_epochs'])
        assert epochs and all(e >= 1 for e in epochs), \
            "profile_epochs must name epochs >= 1 ('3', '2,5', '3-5')"
    if ta.get('telemetry_port') is not None:
        port = int(ta['telemetry_port'])
        assert 0 <= port <= 65535, \
            'telemetry_port must be a TCP port (0 disables the exporter)'
        assert port == 0 or tel_enabled, \
            'telemetry_port needs telemetry enabled (the exporter serves ' \
            'the registry the collection switch turns off)'
    assert 1 <= int(ta.get('compress_level', 9)) <= 9, \
        'compress_level must be a bz2 compresslevel in 1..9'
    inf = ta.get('inference') or {}
    assert str(inf.get('engine_backend', 'cpu')) in ('cpu', 'device'), \
        "inference.engine_backend must be 'cpu' or 'device'"
    assert float(inf.get('batch_wait_ms', 2.0)) >= 0, \
        'inference.batch_wait_ms must be >= 0 (0 = dispatch immediately)'
    assert int(inf.get('max_batch', 64)) >= 1, \
        'inference.max_batch must be >= 1'
    assert int(inf.get('vault_size', 3)) >= 1, \
        'inference.vault_size must be >= 1'
    assert int(inf.get('queue_max', 1024)) >= 0, \
        'inference.queue_max must be >= 0 (0 = unbounded)'
    assert int(inf.get('request_retries', 1)) >= 0, \
        'inference.request_retries must be >= 0'
    for key in ('stall_timeout', 'restart_max_delay', 'request_timeout',
                'reprobe_initial_delay', 'reprobe_max_delay'):
        if inf.get(key) is not None:
            assert float(inf[key]) > 0, 'inference.%s must be > 0' % key
    srv = ta.get('serving') or {}
    for key in ('port', 'metrics_port'):
        if srv.get(key) is not None:
            port = int(srv[key])
            assert 0 <= port <= 65535, \
                'serving.%s must be a TCP port (0 = %s)' % (
                    key, 'ephemeral' if key == 'port' else 'exporter off')
    assert int(srv.get('engines', 1)) >= 1, \
        'serving.engines must be >= 1'
    assert int(srv.get('max_clients', 64)) >= 1, \
        'serving.max_clients must be >= 1'
    assert float(srv.get('drain_timeout', 30.0)) > 0, \
        'serving.drain_timeout must be > 0'
    assert str(srv.get('line', 'default')).strip(), \
        'serving.line must be a non-empty model-line name'
    endpoint = str(srv.get('endpoint') or '')
    for one in filter(None, (e.strip() for e in endpoint.split(','))):
        _ep_host, _, ep_port = one.rpartition(':')
        assert ep_port.isdigit() and 0 < int(ep_port) <= 65535, \
            "serving.endpoint entries must look like 'host:port' (got %r)" \
            % one
    assert float(srv.get('lock_timeout', 10.0)) > 0, \
        'serving.lock_timeout must be > 0'
    flt = srv.get('fleet') or {}
    for key in ('heartbeat_interval', 'heartbeat_timeout', 'refresh_interval',
                'breach_window', 'idle_window', 'quarantine_period'):
        if flt.get(key) is not None:
            assert float(flt[key]) > 0, 'serving.fleet.%s must be > 0' % key
    if flt.get('heartbeat_timeout') and flt.get('heartbeat_interval'):
        assert float(flt['heartbeat_timeout']) \
            > float(flt['heartbeat_interval']), \
            'serving.fleet.heartbeat_timeout must exceed heartbeat_interval ' \
            'or every live replica is quarantined between beacons'
    if flt.get('port') is not None:
        assert 0 <= int(flt['port']) <= 65535, \
            'serving.fleet.port must be a TCP port (0 = ephemeral)'
    assert int(flt.get('replicas', 2)) >= 0, \
        'serving.fleet.replicas must be >= 0 (0 = external replicas only)'
    assert int(flt.get('min_replicas', 1)) >= 1, \
        'serving.fleet.min_replicas must be >= 1'
    assert int(flt.get('max_replicas', 4)) >= int(flt.get('min_replicas', 1)), \
        'serving.fleet.max_replicas must be >= min_replicas'
    assert float(flt.get('slo_p99_ms', 0.0)) >= 0, \
        'serving.fleet.slo_p99_ms must be >= 0 (0 = breach on sheds only)'
    resolver = str(flt.get('resolver') or '')
    if resolver:
        _r_host, _, r_port = resolver.rpartition(':')
        assert r_port.isdigit() and 0 < int(r_port) <= 65535, \
            "serving.fleet.resolver must look like 'host:port' (got %r)" \
            % resolver
    gw = srv.get('gateway') or {}
    for key in ('port', 'metrics_port'):
        if gw.get(key) is not None:
            assert 0 <= int(gw[key]) <= 65535, \
                'serving.gateway.%s must be a TCP port (0 = %s)' % (
                    key, 'ephemeral' if key == 'port' else 'exporter off')
    assert int(gw.get('workers', 4)) >= 1, \
        'serving.gateway.workers must be >= 1'
    assert int(gw.get('max_sessions', 64)) >= 1, \
        'serving.gateway.max_sessions must be >= 1'
    for key in ('ply_timeout', 'monitor_interval', 'session_timeout'):
        if gw.get(key) is not None:
            assert float(gw[key]) > 0, \
                'serving.gateway.%s must be > 0' % key
    gw_resolver = str(gw.get('resolver') or '')
    if gw_resolver:
        _g_host, _, g_port = gw_resolver.rpartition(':')
        assert g_port.isdigit() and 0 < int(g_port) <= 65535, \
            "serving.gateway.resolver must look like 'host:port' (got %r)" \
            % gw_resolver
    lg = ta.get('league') or {}
    assert str(lg.get('curve', 'variance')) in \
        ('variance', 'hard', 'uniform'), \
        "league.curve must be 'variance', 'hard' or 'uniform'"
    assert float(lg.get('hard_exponent', 2.0)) > 0, \
        'league.hard_exponent must be > 0'
    assert 0.0 <= float(lg.get('self_play_rate', 0.5)) <= 1.0, \
        'league.self_play_rate must be a fraction in [0, 1]'
    assert 0.0 <= float(lg.get('rating_match_rate', 0.25)) <= 1.0, \
        'league.rating_match_rate must be a fraction in [0, 1]'
    assert int(lg.get('max_members', 8)) >= 1, \
        'league.max_members must be >= 1'
    assert float(lg.get('k_factor', 32.0)) > 0, \
        'league.k_factor must be > 0'
    assert float(lg.get('promote_margin', 30.0)) >= 0, \
        'league.promote_margin must be >= 0'
    assert int(lg.get('min_games', 20)) >= 1, \
        'league.min_games must be >= 1'
    assert float(lg.get('initial_sigma', 200.0)) \
        >= float(lg.get('min_sigma', 50.0)) > 0, \
        'league sigma bounds need initial_sigma >= min_sigma > 0'
    assert float(lg.get('rating_flush_seconds', 5.0)) >= 0, \
        'league.rating_flush_seconds must be >= 0 (0 = epoch-sync ' \
        'flushes only)'
    for anchor in (lg.get('anchors') or []):
        assert anchor == 'random' or str(anchor).startswith('rulebase'), \
            "league.anchors entries must be 'random' or 'rulebase[-key]' " \
            '(got %r)' % (anchor,)
    if lg.get('enabled'):
        assert srv.get('publish'), \
            'league.enabled requires serving.publish (pool members ARE the ' \
            "registry line's versions)"
    gen = ta.get('generation') or {}
    _BACKENDS = ('', 'worker', 'engine', 'device')
    assert str(gen.get('backend', '')) in _BACKENDS, \
        "generation.backend must be '', 'worker', 'engine' or 'device'"
    assert int(gen.get('device_actor_envs', 64)) >= 1, \
        'generation.device_actor_envs must be >= 1'
    assert int(gen.get('device_actor_chunk_steps', 16)) >= 1, \
        'generation.device_actor_chunk_steps must be >= 1'
    assert int(gen.get('device_actor_slots', 2)) >= 1, \
        'generation.device_actor_slots must be >= 1 (slot 0 carries the ' \
        'learner params)'
    assert str(gen.get('device_actor_record', '')) in \
        ('', 'strict', 'device'), \
        "generation.device_actor_record must be '', 'strict' or 'device'"
    assert str((args.get('worker_args') or {}).get('backend', '')) \
        in _BACKENDS, \
        "worker_args.backend must be '', 'worker', 'engine' or 'device'"
    par = ta.get('parallel') or {}
    assert int(par.get('model_parallel', 1)) >= 1, \
        'parallel.model_parallel must be >= 1 (1 = no tensor parallelism)'
    rules = par.get('partition_rules') or []
    assert isinstance(rules, (list, tuple)), \
        'parallel.partition_rules must be a list of [regex, spec] pairs'
    import re as _re
    for entry in rules:
        assert isinstance(entry, (list, tuple)) and len(entry) == 2, \
            'each partition rule is a [regex, spec] pair, got %r' % (entry,)
        pattern, spec = entry
        _re.compile(str(pattern))   # raises on an invalid regex
        axes = [spec] if isinstance(spec, str) or spec is None else list(spec)
        for axis in axes:
            assert axis in (None, 'null', '', 'data', 'model'), \
                "partition-rule axes must be null, 'data' or 'model' " \
                '(got %r in %r)' % (axis, entry)
    if ta.get('batcher_shared_memory'):
        assert ta.get('batcher_processes'), \
            'batcher_shared_memory requires batcher_processes (the thread ' \
            'batcher already shares the trainer address space)'
    assert 'env' in args['env_args'], 'env_args.env is required'
