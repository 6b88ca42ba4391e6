"""The compiled SGD update step.

One jit-compiled program per (architecture, config, batch shape): forward,
targets, losses, gradients, global-norm clip at 4.0, Adam with additive
weight decay 1e-5 (the reference optimizer, train.py:331,370), parameter
update. The learning rate is a runtime scalar (the host feeds the EMA
schedule value each step) so schedule changes never recompile.

On a multi-device mesh the batch arrives sharded along 'data' and params
replicated; XLA inserts the gradient all-reduce over ICI.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from .losses import LossConfig, compute_loss, split_batch_stats
from ..parallel.mesh import batch_sharding, replicated_sharding


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    steps: jnp.ndarray  # int32 scalar


def make_optimizer() -> optax.GradientTransformation:
    """clip(4.0) -> grad += wd * param -> Adam moments (lr applied outside)."""
    return optax.chain(
        optax.clip_by_global_norm(4.0),
        optax.add_decayed_weights(1e-5),
        optax.scale_by_adam(),
    )


def init_train_state(params) -> TrainState:
    """``params`` is the model's full flax variables dict. The optimizer
    covers only the trainable collections — a norm_kind='batch' model's
    ``batch_stats`` running averages advance by EMA in the forward
    (losses.py), never by Adam (zero-grad moments + weight decay would
    silently shrink them toward 0)."""
    opt = make_optimizer()
    trainable, _ = split_batch_stats(params)
    return TrainState(params=params, opt_state=opt.init(trainable),
                      steps=jnp.zeros((), jnp.int32))


def _update_core(module, cfg: LossConfig, optimizer, axis_name=None):
    """The un-jitted single SGD step shared by every compiled variant.

    With ``axis_name`` (the shard_map'd fused pipeline), each shard computes
    grads/metrics over its LOCAL batch slice and psums them: the loss is a
    sum over batch elements, so the psum'd gradient equals the single-device
    gradient of the full batch, and the (replicated) optimizer step — incl.
    the global-norm clip, which must see the GLOBAL gradient — is identical
    on every shard, keeping params replicated without a broadcast."""
    apply_fn = module.apply
    # a net that declares ``sequence`` consumes a window as one causal
    # forward (losses.py ``_sequence_prediction``); its ``init_hidden`` is
    # the rollout's cache and the learner never builds one
    sequence_fn = None
    if hasattr(module, 'sequence'):
        def sequence_fn(params, *args):
            return module.apply(params, *args, method=module.sequence)

    # such a net MAY hand the loss ``policy_features`` for its
    # ``policy_logits`` (the head in blocks of positions), MAY return sums
    # of its forward pass and MAY define ``post_update(before, after,
    # aux)``, which runs on the parameter trees after the optimizer
    policy_fn = None
    if hasattr(module, 'policy_logits'):
        def policy_fn(params, features):
            return module.apply(params, features,
                                method=module.policy_logits)

    def init_hidden_for(batch):
        if sequence_fn is not None or not hasattr(module, 'init_hidden'):
            return None
        B = batch['value'].shape[0]
        P = batch['value'].shape[2]
        return module.init_hidden((B, P))

    def update(state: TrainState, batch: Dict[str, Any], lr: jnp.ndarray,
               target_params=None
               ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        init_hidden = init_hidden_for(batch)
        trainable, batch_stats = split_batch_stats(state.params)

        def loss_fn(params):
            return compute_loss(apply_fn, params, init_hidden, batch, cfg,
                                batch_stats=batch_stats,
                                target_params=target_params,
                                sequence_fn=sequence_fn, policy_fn=policy_fn)

        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
        new_bs = aux.pop('batch_stats', None)
        if axis_name is not None:
            grads = jax.lax.psum(grads, axis_name)
            aux = jax.lax.psum(aux, axis_name)
            if new_bs is not None:
                # shard_map path: each shard normalized by ITS batch
                # slice's statistics (torch DataParallel BatchNorm
                # semantics, what the reference trains with); averaging
                # the advanced running stats keeps the replicated train
                # state bit-identical across shards. NOTE the OTHER
                # multi-device path (build_update_step's jit+mesh, no
                # axis_name) lets GSPMD reduce the batch statistics over
                # the GLOBAL sharded batch — sync-BN semantics. Both are
                # faithful BatchNorm; they differ in stat granularity
                # (documented in PARITY.md).
                new_bs = jax.lax.pmean(new_bs, axis_name)
        # non-finite guard: one NaN/Inf gradient must not poison the
        # TrainState forever. All-finite check on the (global) loss, grad
        # norm and the runtime lr scalar, ON DEVICE — a bad step keeps the
        # previous params/optimizer buffers and reports metrics as zeros
        # plus nonfinite=1; the host reads that flag on its existing lazy
        # metric fetch (no extra sync) and escalates per guard policy
        # (guard.py: skip / rollback / abort).
        grad_norm = optax.global_norm(grads)
        ok = (jnp.isfinite(lr)
              & jnp.isfinite(aux['losses']['total'])
              & jnp.isfinite(grad_norm))
        sequence_aux = aux.pop('sequence_aux', None)

        def keep(new, old):
            return jnp.where(ok, new, old)
        # the scope holds the selects too: the compiler fuses a leaf's
        # moments, its update and its select into one operation and names it
        # after the last
        with jax.named_scope('optimizer'):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  trainable)
            updates = jax.tree_util.tree_map(lambda u: -lr * u, updates)
            params = optax.apply_updates(trainable, updates)
            if hasattr(module, 'post_update'):
                params = module.post_update(trainable, params, sequence_aux)
            params = jax.tree_util.tree_map(keep, params, trainable)
            opt_state = jax.tree_util.tree_map(keep, opt_state,
                                               state.opt_state)
        if new_bs is not None:
            params = {**dict(params),
                      'batch_stats': jax.tree_util.tree_map(
                          keep, new_bs, batch_stats)}
        metrics = {**aux['losses'], 'data_count': aux['data_count']}
        # learning-dynamics diagnostics ride the same packed fetch under a
        # 'diag_' prefix: the host routes them to the per-epoch dynamics
        # summary instead of the reference-format loss line. grad_norm is
        # the post-psum GLOBAL gradient (per update, not per sample).
        for k, v in (aux.get('diag') or {}).items():
            metrics['diag_' + k] = v
        metrics['diag_grad_norm'] = grad_norm
        metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                   for k, v in metrics.items()}
        metrics['nonfinite'] = 1.0 - ok.astype(jnp.float32)
        new_state = TrainState(params=params, opt_state=opt_state,
                               steps=state.steps + 1)
        return new_state, metrics

    return update


def build_update_step(module, cfg: LossConfig, mesh=None, donate: bool = True,
                      state_shardings=None, use_target: bool = False):
    """Returns update(state, batch, lr) -> (state, metrics), jit-compiled.

    ``metrics`` carries the per-term loss sums and the turn count of the
    batch (the reference's ``dcnt``) as device scalars.

    With ``use_target`` the compiled signature gains a 4th argument —
    update(state, batch, lr, target_params) — the frozen IMPACT target
    network's trainable params (losses.py target_clip). They are replicated
    like any other scalar input and NOT donated: the live params buffer is
    donated every step, so the target must keep its own device copy to
    survive between refreshes (train.py syncs it every
    streaming.target_sync_epochs epochs).

    On a mesh the program carries explicit NamedSharding types: the batch
    shards along 'data', and the TrainState layout comes from
    ``state_shardings`` — the per-leaf NamedSharding pytree the partition-
    rule engine builds (parallel/partition.py tree_shardings); None keeps
    the fully-replicated (pure data-parallel) layout. The same shardings
    type the outputs, so the donated state round-trips through every step
    without a reshard.
    """
    update = _update_core(module, cfg, make_optimizer())
    # name the program so the retrace sentinel (telemetry.py) can report
    # WHICH compiled callable re-lowered after steady state
    update.__name__ = 'train_update_step'

    if mesh is None:
        return jax.jit(update, donate_argnums=(0,) if donate else ())

    repl = replicated_sharding(mesh)
    data = batch_sharding(mesh)
    state_sh = state_shardings if state_shardings is not None else repl
    # the target copy mirrors the live params' layout (it IS a copy of
    # them), so its sharding is the state tree's params component
    tgt_sh = getattr(state_sh, 'params', state_sh)
    in_sh = (state_sh, data, repl) + ((tgt_sh,) if use_target else ())
    return jax.jit(
        update,
        in_shardings=in_sh,
        out_shardings=(state_sh, repl),
        donate_argnums=(0,) if donate else (),
    )


def build_replay_update(module, cfg: LossConfig, capacity: int,
                        batch_size: int, num_steps: int,
                        default_lr: float = 3e-8, mesh=None,
                        spec_fn=None, state_shardings=None):
    """Fused replay-mode trainer: K SGD steps in ONE compiled program.

    The per-step host round trip (sample dispatch + update dispatch + PRNG
    split) is what bounds replay-mode throughput when a step is short next
    to its three dispatches. Here the whole inner loop
    moves on device: a ``lax.scan`` of ``num_steps`` iterations, each drawing
    a recency-biased batch straight from the HBM ring (same inverse-CDF as
    DeviceReplay.sample), computing the EMA learning-rate schedule from the
    on-device step counter (identical to Trainer._lr: steps is the count of
    completed updates), and applying the update. Metrics come back as sums
    over the K steps, matching what the host accumulator expects.

    Returns fused(state, buffers, key, size, cursor, data_cnt_ema) ->
    (state, key, summed_metrics). The key is carried through and returned so
    steady-state training needs zero host-side PRNG dispatches. On a mesh the
    ring is replicated and each sampled batch is sharding-constrained along
    'data', so XLA runs the same data-parallel step as build_update_step.
    """
    from .replay import recency_slots

    update = _update_core(module, cfg, make_optimizer())
    data = batch_sharding(mesh) if mesh is not None else None

    def gather(buffers, slots):
        """Ring rows are stored FLAT (capacity, prod(window shape)) to
        avoid TPU tile-padding blowup (ops/replay.py); ``spec_fn`` supplies
        DeviceReplay's per-leaf window shapes and treedef at trace time."""
        if spec_fn is None:
            return jax.tree_util.tree_map(lambda b: b[slots], buffers)
        spec, treedef = spec_fn()
        rows = [b[slots].reshape((batch_size,) + shape)
                for b, (shape, _) in zip(buffers, spec)]
        return jax.tree_util.tree_unflatten(treedef, rows)

    def fused(state: TrainState, buffers, key, size, cursor, data_cnt_ema):
        def body(carry, _):
            state, key = carry
            key, sub = jax.random.split(key)
            slots = recency_slots(sub, size, cursor, capacity, batch_size)
            batch = gather(buffers, slots)
            if data is not None:
                batch = jax.lax.with_sharding_constraint(
                    batch, jax.tree_util.tree_map(lambda _: data, batch))
            lr = (default_lr * data_cnt_ema
                  / (1 + state.steps.astype(jnp.float32) * 1e-5))
            state, metrics = update(state, batch, lr)
            return (state, key), metrics

        (state, key), stacked = jax.lax.scan(
            body, (state, key), None, length=num_steps)
        summed = jax.tree_util.tree_map(lambda m: jnp.sum(m, axis=0), stacked)
        return state, key, summed

    fused.__name__ = 'replay_fused_update'
    if mesh is None:
        return jax.jit(fused, donate_argnums=(0, 2))
    repl = replicated_sharding(mesh)
    # the ring stays replicated (each device gathers its batch from a local
    # replica); the TrainState layout comes from the partition-rule engine
    state_sh = state_shardings if state_shardings is not None else repl
    return jax.jit(
        fused,
        in_shardings=(state_sh, repl, repl, repl, repl, repl),
        out_shardings=(state_sh, repl, repl),
        donate_argnums=(0, 2),
    )
