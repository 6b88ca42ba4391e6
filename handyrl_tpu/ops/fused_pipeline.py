"""The fully-fused device pipeline: ONE dispatch = rollout chunk + window
ingest + K SGD steps.

The entire steady-state loop body is one XLA program, so a chunk costs one
dispatch and one fetch:

    rollout chunk (lax.scan over plies, make_gen_body)
      -> windower chunk ingest (episode windows scattered into the HBM ring)
      -> K SGD steps (recency-biased on-device sampling, EMA lr schedule)

The host dispatches it once per chunk and fetches only the previous chunk's
(done, outcome) arrays plus lazily-drained loss metrics. Actor params enter
as a replicated input refreshed once per epoch (self-play acts with the
epoch snapshot while the optimizer advances continuously, exactly like the
reference's worker/learner split, train.py:605-615); training params/opt
state are donated through every dispatch.

A second, SGD-free program covers the minimum_episodes warmup so the steps
counter and Adam state never see empty-ring batches.

Sample-reuse note: steps-per-chunk is a DIAL (sgd_steps_per_chunk), making
the replay ratio explicit: reuse ~= sgd_steps * batch_size / windows-per-
chunk. The threaded replay trainer's reuse is implicit (however fast it
spins vs generation); here it is pinned and logged.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..device_generation import _init_rollout_engine, make_gen_body
from ..parallel.mesh import batch_sharding, replicated_sharding, shard_batch
from .losses import LossConfig
from .replay import recency_slots
from .train_step import (TrainState, _update_core, init_train_state,
                         make_optimizer)


def ply_indices(done, at_start):
    """Every lane's ply index at each ply of a chunk, (K, N), from the
    chunk's ``done`` flags (K, N) and the indices ``at_start`` (N,) of its
    first ply: one more a ply, 0 again behind a ``done``; and the indices
    the next chunk starts at. A cache kept by ONE counter a sequence
    (``models/attention.py`` ``reset_cache``) holds exactly these."""
    K = done.shape[0]
    ply = np.arange(K)[:, None]
    # the ply behind the last ``done`` so far, 0 where none ended yet
    begun = np.maximum.accumulate(np.where(done, ply + 1, 0), axis=0)
    since = np.concatenate([np.zeros_like(begun[:1]), begun])
    index = np.arange(K + 1)[:, None] - since + np.where(since == 0,
                                                         at_start, 0)
    return index[:K], index[K]


class FusedPipeline:
    """Owns the device-resident loop state (env vector, recurrent hidden,
    windower history, HBM ring) and the two compiled programs (warmup /
    steady). The caller owns the TrainState and actor params."""

    def __init__(self, env_mod, wrapper, cfg: LossConfig, windower,
                 args: Dict[str, Any], n_envs: int, chunk_steps: int,
                 sgd_steps: int, batch_size: int,
                 default_lr: float = 3e-8, seed: int = 0, mesh=None,
                 attention_key_share=None):
        self.chunk_steps = chunk_steps
        self.sgd_steps = sgd_steps
        self.mesh = mesh
        ndev = int(np.prod(list(mesh.shape.values()))) if mesh else 1
        self.ndev = ndev
        if mesh is not None:
            assert n_envs % ndev == 0 and batch_size % ndev == 0, \
                'generation_envs and batch_size must divide the mesh'
            assert windower.capacity >= 1, \
                'replay capacity must be >= 1 ring row per shard'
        n_loc = n_envs // ndev            # per-shard envs
        b_loc = batch_size // ndev        # per-shard SGD batch slice
        _init_rollout_engine(self, env_mod, wrapper, n_envs, seed)
        if self.hidden is not None:
            # models may alias hidden leaves (e.g. GeisterNet's
            # ``[zeros] * layers``); every dispatch donates the tree, and
            # XLA refuses to donate one buffer twice — copy into distinct
            # buffers once here
            self.hidden = jax.tree_util.tree_map(jnp.copy, self.hidden)
        rollout_chunk = make_gen_body(env_mod, wrapper.module.apply,
                                      self.recurrent, self.simultaneous)
        ingest = windower.ingest_fn()
        update = _update_core(wrapper.module, cfg, make_optimizer(),
                              axis_name='data' if mesh is not None else None)
        # windower.capacity is PER-SHARD on a mesh (Learner divides the ring
        # budget by the device count); the global ring has ndev * capacity rows
        capacity = windower.capacity
        self.capacity = capacity
        self.dispatches = 0

        # ring/windower state allocated from the record shapes (eval_shape:
        # nothing runs on device for this). On a mesh the GLOBAL shapes are
        # allocated (env axis = n_envs, ring axis = ndev * capacity) and
        # sharded over 'data'; each shard_map body sees the local slice.
        rec_spec = jax.eval_shape(
            lambda p, s, h, r: rollout_chunk(p, s, h, r, chunk_steps),
            wrapper.params, self.state, self.hidden, self.rng)[3]
        self.wstate = windower.init_state(rec_spec)
        if mesh is None:
            self.ring = windower.init_ring(rec_spec)   # sets window_spec
            self.cursor = jnp.zeros((), jnp.int32)
            self.size = jnp.zeros((), jnp.int32)
        else:
            # the global ring is born sharded: every device zero-fills its
            # own rows. Built on the default device and resharded, it put
            # twice the WHOLE ring on chip 0 at start-up (peak 9.4 GB there
            # against 1.2 GB on each other chip of a four-chip v5e host)
            ring_spec = jax.eval_shape(windower.init_ring, rec_spec)
            self.ring = jax.jit(
                lambda: {k: jnp.zeros((ndev * capacity,) + v.shape[1:],
                                      v.dtype)
                         for k, v in ring_spec.items()},
                out_shardings=batch_sharding(mesh))()
            # per-shard ring cursors/sizes and PRNG streams, stored as
            # sharded (ndev,)-leading arrays
            self.cursor = jnp.zeros((ndev,), jnp.int32)
            self.size = jnp.zeros((ndev,), jnp.int32)
            self.rng = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(seed), 7), ndev)
            self._shard_loop_state(mesh)

        self.num_players = int(env_mod.NUM_PLAYERS)
        # metric key order is part of the packed-fetch wire format; derive
        # it statically from the update's abstract aux (no device work, no
        # trace-order dependence)
        probe_update = _update_core(wrapper.module, cfg, make_optimizer())

        def _probe(params):
            from .device_windows import unflatten_window_keys
            batch = unflatten_window_keys(
                {k: jnp.zeros((batch_size,) + shape, dtype)
                 for k, (shape, dtype) in windower.window_spec.items()})
            ts = init_train_state(params)
            _, metrics = probe_update(ts, batch, jnp.float32(0.0))
            return metrics
        self._metric_keys: list = sorted(
            jax.eval_shape(_probe, wrapper.params))

        def gen_ingest(actor_params, env_state, hidden, wstate, ring,
                       cursor, size, rng):
            # the phases carry stable names into every operation's metadata
            # (the device trace's ``tf_op``): time is attributed by scope,
            # not by compiler numbering
            with jax.named_scope('rollout'):
                env_state, hidden, rng, records = rollout_chunk(
                    actor_params, env_state, hidden, rng, chunk_steps)
            with jax.named_scope('ingest'):
                (wstate, ring, cursor, size, rng, n_done, n_win) = ingest(
                    records, wstate, ring, cursor, size, rng)
            return (env_state, hidden, wstate, ring, cursor, size, rng,
                    records['done'], records['outcome'], n_win)

        def pack(done, outcome, size, size_min, n_win, metric_vals):
            # EVERYTHING the host reads per chunk rides ONE f32 array: every
            # distinct-array fetch is its own blocking transfer, so one sync
            # point per dispatch is the budget
            with jax.named_scope('pack'):
                parts = [done.astype(jnp.float32).reshape(-1),
                         outcome.astype(jnp.float32).reshape(-1),
                         size.astype(jnp.float32).reshape(1),
                         size_min.astype(jnp.float32).reshape(1),
                         n_win.astype(jnp.float32).reshape(1)]
                parts += [v.astype(jnp.float32).reshape(1)
                          for v in metric_vals]
                return jnp.concatenate(parts)

        def sgd_tail(train_state, ring, cursor, size, rng, data_cnt_ema,
                     batch_rows):
            """K recency-sampled SGD steps on this shard's ring slice."""
            def body(carry, _):
                ts, key = carry
                key, sub = jax.random.split(key)
                slots = recency_slots(sub, size, cursor, capacity,
                                      batch_rows)
                # ring rows are stored flat and padded
                # (device_windows.init_ring); restore the (B, T, P, ...)
                # window shape after the gather and rebuild the batch
                # pytree (dotted keys -> nested obs). A feed-forward net
                # then reads it folded time-major (losses._fold_bt), which
                # keeps these B rows' axis in the lanes
                with jax.named_scope('sample'):
                    batch = windower.unflatten_rows(
                        {k: ring[k][slots] for k in ring})
                lr = (default_lr * data_cnt_ema
                      / (1 + ts.steps.astype(jnp.float32) * 1e-5))
                with jax.named_scope('update'):
                    ts, metrics = update(ts, batch, lr)
                return (ts, key), metrics

            with jax.named_scope('sgd'):
                (train_state, rng), stacked = jax.lax.scan(
                    body, (train_state, rng), None, length=sgd_steps)
                metrics = jax.tree_util.tree_map(
                    lambda m: jnp.sum(m, axis=0), stacked)
            return train_state, rng, [metrics[k]
                                      for k in self._metric_keys]

        if mesh is None:
            def warmup(actor_params, env_state, hidden, wstate, ring,
                       cursor, size, rng):
                (env_state, hidden, wstate, ring, cursor, size, rng,
                 done, outcome, n_win) = gen_ingest(
                    actor_params, env_state, hidden, wstate, ring, cursor,
                    size, rng)
                return (env_state, hidden, wstate, ring, cursor, size, rng,
                        pack(done, outcome, size, size, n_win, []))

            def fused(actor_params, train_state: TrainState, env_state,
                      hidden, wstate, ring, cursor, size, rng, data_cnt_ema):
                (env_state, hidden, wstate, ring, cursor, size, rng,
                 done, outcome, n_win) = gen_ingest(
                    actor_params, env_state, hidden, wstate, ring, cursor,
                    size, rng)
                train_state, rng, mvals = sgd_tail(
                    train_state, ring, cursor, size, rng, data_cnt_ema,
                    batch_size)
                return (train_state, env_state, hidden, wstate, ring, cursor,
                        size, rng,
                        pack(done, outcome, size, size, n_win, mvals))
        else:
            warmup, fused = self._build_sharded(
                mesh, gen_ingest, sgd_tail, pack, b_loc)

        # donate everything the pipeline owns plus the train state; actor
        # params and the EMA scalar are plain (re-used) inputs. On a mesh
        # the program boundary is TYPED with explicit NamedShardings (the
        # same vocabulary the partition-rule engine speaks): loop state
        # sharded along 'data', actor params / train state / the packed
        # host fetch replicated — placement is part of the program, not an
        # accident of where the caller left the inputs.
        # name the programs so the retrace sentinel (telemetry.py) can
        # report WHICH compiled callable re-lowered after steady state
        warmup.__name__ = 'fused_pipeline_warmup'
        fused.__name__ = 'fused_pipeline_train'
        if mesh is None:
            self._warmup = jax.jit(warmup,
                                   donate_argnums=(1, 2, 3, 4, 5, 6, 7))
            self._fused = jax.jit(fused,
                                  donate_argnums=tuple(range(1, 10)))
        else:
            R, D = replicated_sharding(mesh), batch_sharding(mesh)
            self._warmup = jax.jit(
                warmup,
                in_shardings=(R, D, D, D, D, D, D, D),
                out_shardings=(D, D, D, D, D, D, D, R),
                donate_argnums=(1, 2, 3, 4, 5, 6, 7))
            self._fused = jax.jit(
                fused,
                in_shardings=(R, R, D, D, D, D, D, D, D, R),
                out_shardings=(R, D, D, D, D, D, D, D, R),
                donate_argnums=tuple(range(1, 10)))
        self._pending = None   # (pack_future, has_metrics), one deep
        self.ring_size_host = 0
        self.ring_min_host = 0          # min ring size across shards
        self.windows_ingested_host = 0  # cumulative windows ingested
        # cumulative host counters of the fetched chunks (the ``host_block``
        # span carries them): plies played, plies on which any lane ended a
        # game (those that gave the ingest's window builder work), games
        # ended
        self.plies_host = 0
        self.builder_plies_host = 0
        self.episodes_host = 0
        # what a window read as a sequence wastes and what the rollout's
        # per-sequence state holds: positions trained (batch x
        # forward_steps x SGD steps; the burn-in trains nothing) and those
        # of them past a game's end, from the fetched data count; the
        # bytes of ``hidden`` (a cache kept by counters is never cleared,
        # so only its counters are ever reset)
        self.window_len = windower.fs
        self.batch_size = batch_size
        self.window_positions_host = 0
        self.window_padded_host = 0
        self.chunks_host = 0
        # every ``diag_*`` sum the fetch carries, added up over the fetched
        # chunks under its own name less the prefix (the ``host_block`` span
        # carries them beside the counters above)
        self.diag_host: Dict[str, float] = {}
        self.state_cache_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.hidden))
        self.state_resets = hasattr(wrapper.module, 'reset_hidden')
        # what the decode plies read of a cache kept by counters, where the
        # net can say (``decode_rows``): every lane's ply index, which is its
        # seats' counter, kept from the fetched ``done`` flags
        self.decode_rows = getattr(wrapper.module, 'decode_rows', None)
        self.lane_ply = np.zeros(n_envs, np.int64)
        self.decode_rows_read_host = 0
        self.decode_rows_held_host = 0
        # of all (query, key) pairs of a window, the share the update step's
        # attention multiplies, where the learner knows it (train.py
        # ``Trainer.attention_key_share``): the shapes fix it, and the
        # ``host_block`` span carries it as it is
        self.attention_key_share = attention_key_share
        telemetry.gauge('state_cache_bytes').set(self.state_cache_bytes)

    # -- multi-chip construction -------------------------------------------
    def _shard_loop_state(self, mesh):
        """Lay the (small) loop state out over the mesh: env/hidden/
        windower state and per-shard cursors split along 'data'. The ring,
        the one large piece, is allocated sharded in __init__."""
        self.state = shard_batch(mesh, self.state)
        if self.hidden is not None:
            self.hidden = shard_batch(mesh, self.hidden)
        self.wstate = shard_batch(mesh, self.wstate)
        self.cursor = shard_batch(mesh, self.cursor)
        self.size = shard_batch(mesh, self.size)
        self.rng = shard_batch(mesh, self.rng)

    def _build_sharded(self, mesh, gen_ingest, sgd_tail, pack, b_loc):
        """shard_map'd variants: every shard runs rollout + ingest on its
        own envs and ring slice; the SGD tail samples the per-shard batch
        slice and psums grads/metrics inside the update (train_step.py),
        so train_state stays replicated with no broadcast. The only
        cross-chip traffic in steady state is the gradient/metric psum —
        the layout How-to-Scale calls pure data parallelism, riding ICI."""
        from functools import partial

        from jax.sharding import PartitionSpec as P

        shard_map = partial(jax.shard_map, check_vma=False)

        D, R = P('data'), P()

        def shard_warm(actor_params, env_state, hidden, wstate, ring,
                       cursor, size, rng):
            (env_state, hidden, wstate, ring, c, s, k,
             done, outcome, n_win) = gen_ingest(
                actor_params, env_state, hidden, wstate, ring,
                cursor[0], size[0], rng[0])
            size_tot = jax.lax.psum(s, 'data')
            size_min = jax.lax.pmin(s, 'data')
            win_tot = jax.lax.psum(n_win, 'data')
            return (env_state, hidden, wstate, ring, c[None], s[None],
                    k[None], done, outcome, size_tot, size_min, win_tot)

        def shard_fused(actor_params, train_state, env_state, hidden,
                        wstate, ring, cursor, size, rng, data_cnt_ema):
            (env_state, hidden, wstate, ring, c, s, k,
             done, outcome, n_win) = gen_ingest(
                actor_params, env_state, hidden, wstate, ring,
                cursor[0], size[0], rng[0])
            train_state, k, mvals = sgd_tail(
                train_state, ring, c, s, k, data_cnt_ema, b_loc)
            size_tot = jax.lax.psum(s, 'data')
            size_min = jax.lax.pmin(s, 'data')
            win_tot = jax.lax.psum(n_win, 'data')
            return (train_state, env_state, hidden, wstate, ring, c[None],
                    s[None], k[None], done, outcome, size_tot, size_min,
                    win_tot, jnp.stack(mvals) if mvals else jnp.zeros((0,)))

        sm_warm = shard_map(
            shard_warm, mesh=mesh,
            in_specs=(R, D, D, D, D, D, D, D),
            out_specs=(D, D, D, D, D, D, D, P(None, 'data'),
                       P(None, 'data'), R, R, R))
        sm_fused = shard_map(
            shard_fused, mesh=mesh,
            in_specs=(R, R, D, D, D, D, D, D, D, R),
            out_specs=(R, D, D, D, D, D, D, D, P(None, 'data'),
                       P(None, 'data'), R, R, R, R))

        def warmup(actor_params, env_state, hidden, wstate, ring,
                   cursor, size, rng):
            (env_state, hidden, wstate, ring, cursor, size, rng,
             done, outcome, size_tot, size_min, win_tot) = sm_warm(
                actor_params, env_state, hidden, wstate, ring, cursor,
                size, rng)
            return (env_state, hidden, wstate, ring, cursor, size, rng,
                    pack(done, outcome, size_tot, size_min, win_tot, []))

        def fused(actor_params, train_state, env_state, hidden, wstate,
                  ring, cursor, size, rng, data_cnt_ema):
            (train_state, env_state, hidden, wstate, ring, cursor, size,
             rng, done, outcome, size_tot, size_min, win_tot,
             mvec) = sm_fused(
                actor_params, train_state, env_state, hidden, wstate,
                ring, cursor, size, rng, data_cnt_ema)
            mvals = [mvec[i] for i in range(len(self._metric_keys))]
            return (train_state, env_state, hidden, wstate, ring, cursor,
                    size, rng,
                    pack(done, outcome, size_tot, size_min, win_tot, mvals))

        return warmup, fused

    # -- dispatch helpers --------------------------------------------------
    def _parse(self, pending):
        flat, has_metrics = pending
        K, N, P = self.chunk_steps, self.n_envs, self.num_players
        with telemetry.trace_span('host_block') as span:
            # the wait for the chunk's device work: everything else here
            # is host arithmetic on the fetched array
            flat = np.asarray(flat)
            done = flat[:K * N].reshape(K, N) > 0.5
            outcome = flat[K * N:K * N * (1 + P)].reshape(K, N, P)
            rest = flat[K * N * (1 + P):]
            self.ring_size_host = int(rest[0])
            self.ring_min_host = int(rest[1])
            # true cumulative ingest count (ring size saturates at capacity
            # once the ring wraps, so it cannot stand in for this)
            self.windows_ingested_host += int(rest[2])
            self.plies_host += K * N
            self.builder_plies_host += int(done.any(axis=1).sum())
            self.episodes_host += int(done.sum())
            self.chunks_host += 1
            if self.state_resets:
                telemetry.counter('state_resets_total').inc(
                    int(done.sum()) * P)
            if self.decode_rows is not None:
                plies, self.lane_ply = ply_indices(done, self.lane_ply)
                read, held = self.decode_rows(plies)
                self.decode_rows_read_host += P * read
                self.decode_rows_held_host += P * held
                span.set(decode_rows_read=self.decode_rows_read_host,
                         decode_rows_held=self.decode_rows_held_host)
            keys = self._metric_keys
            if has_metrics and 'data_count' in keys:
                positions = (self.sgd_steps * self.batch_size
                             * self.window_len)
                padded = positions - int(rest[3 + keys.index('data_count')])
                self.window_positions_host += positions
                self.window_padded_host += padded
                telemetry.counter('window_positions_total').inc(positions)
                telemetry.counter('window_padded_positions_total').inc(
                    padded)
            if has_metrics:
                for k, v in zip(keys, rest[3:]):
                    if k.startswith('diag_'):
                        self.diag_host[k[5:]] = (
                            self.diag_host.get(k[5:], 0.0) + float(v))
            span.set(**self.diag_host)
            span.set(plies=self.plies_host,
                     chunks=self.chunks_host,
                     window_positions=self.window_positions_host,
                     window_padded_positions=self.window_padded_host,
                     state_cache_byte_chunks=(self.state_cache_bytes
                                              * self.chunks_host),
                     builder_plies=self.builder_plies_host,
                     # the builder makes one window a loop iteration,
                     # for the games that ended, and stores every one
                     windows_built=self.windows_ingested_host,
                     episodes=self.episodes_host,
                     windows_ingested=self.windows_ingested_host,
                     ring_size=self.ring_size_host,
                     sgd_steps=self.sgd_steps if has_metrics else 0)
            if self.attention_key_share is not None:
                span.set(attention_key_share=self.attention_key_share)
        metrics = None
        if has_metrics:
            metrics = {k: float(v)
                       for k, v in zip(self._metric_keys, rest[3:])}
        return {'done': done, 'outcome': outcome, 'metrics': metrics}

    def _flip(self, pack_future, has_metrics):
        """Pipeline the single per-chunk fetch one dispatch deep."""
        prev, self._pending = self._pending, (pack_future, has_metrics)
        self.dispatches += 1
        if prev is None:
            return None
        return self._parse(prev)

    def warm_step(self, actor_params):
        """Generation+ingest only (pre-minimum_episodes). Returns the parsed
        accounting of the PREVIOUS chunk, or None on the first call."""
        with telemetry.trace_span('dispatch'):
            (self.state, self.hidden, self.wstate, self.ring, self.cursor,
             self.size, self.rng, packed) = self._warmup(
                actor_params, self.state, self.hidden, self.wstate,
                self.ring, self.cursor, self.size, self.rng)
        return self._flip(packed, False)

    def train_step(self, actor_params, train_state: TrainState,
                   data_cnt_ema: float):
        """One fused chunk+ingest+K-SGD-steps dispatch. Returns
        (train_state, parsed_prev_chunk_or_None)."""
        ema = jnp.asarray(data_cnt_ema, jnp.float32)
        with telemetry.trace_span('dispatch'):
            (train_state, self.state, self.hidden, self.wstate, self.ring,
             self.cursor, self.size, self.rng, packed) = self._fused(
                actor_params, train_state, self.state, self.hidden,
                self.wstate, self.ring, self.cursor, self.size, self.rng,
                ema)
        return train_state, self._flip(packed, True)

    def drain(self):
        """Fetch the last in-flight chunk's accounting (loop shutdown)."""
        if self._pending is None:
            return None
        prev, self._pending = self._pending, None
        return self._parse(prev)
