"""Forward pass + loss composition: one pure, jittable function.

Numerical parity targets: the reference training pipeline
(train.py:127-267) — same masks, same importance-sampling clipping, same
two-player value symmetrization and terminal bootstrap — rebuilt as a single
XLA program:

  * feed-forward nets: (B, T, P) folded into one batch dim, time-major
    (``_fold_bt``) — one big MXU matmul stream instead of T small ones;
  * recurrent nets: ``lax.scan`` over time with observation-mask-gated
    hidden carry; burn-in steps run in a separate scan whose carry passes
    through ``stop_gradient`` (the reference's no_grad replay,
    train.py:159-162);
  * nets that declare ``sequence`` (models/evabyte.py): the window's T
    positions of each (window, seat) in ONE causal forward at the window's
    absolute positions, the burn-in prefix in the same call with no
    gradient through its keys and values; a net that runs its layers
    several times hands over every pass's outputs and the gate that weighs
    the passes' losses (``_exit_weighted_losses``);
  * turn-alternating batches (P_obs=1, P=2): the acting player's policy row
    is gathered by multiplying with turn_mask and summing the player axis
    (train.py:179-180); per-player hidden state is gated by
    observation_mask and merged back after each step (train.py:153-173).

Losses are sums (not means) so the EMA learning-rate schedule sees the true
data count, exactly like the reference.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .maskbits import as_float
from .targets import compute_target

tmap = jax.tree_util.tree_map


class LossConfig(NamedTuple):
    """Hashable (static-arg) training configuration for the compiled step."""
    turn_based_training: bool = True
    observation: bool = False
    burn_in_steps: int = 0
    policy_target: str = 'TD'
    value_target: str = 'TD'
    lmb: float = 0.7
    gamma: float = 0.8
    entropy_regularization: float = 0.1
    entropy_regularization_decay: float = 0.1
    # IMPACT-style clipped target network (streaming.target_clip): > 0
    # replaces the V-Trace behavior ratio with the target-network ratio
    # pi_target/mu, clipped at this value. 0 = off (byte-identical step).
    target_clip: float = 0.0

    @classmethod
    def from_args(cls, args: Dict[str, Any]) -> 'LossConfig':
        return cls(
            turn_based_training=bool(args['turn_based_training']),
            observation=bool(args['observation']),
            burn_in_steps=int(args['burn_in_steps']),
            policy_target=str(args['policy_target']),
            value_target=str(args['value_target']),
            lmb=float(args['lambda']),
            gamma=float(args['gamma']),
            entropy_regularization=float(args['entropy_regularization']),
            entropy_regularization_decay=float(args['entropy_regularization_decay']),
            target_clip=float((args.get('streaming') or {})
                              .get('target_clip', 0.0) or 0.0),
        )


def _fold_bt(x):
    """(B, T, P, ...) -> (T*B*P, ...), time-major: the folded index is
    ``(t*B + b)*P + p``, so a batch gathered as B ring rows reaches the
    net's first operand by a transposition of whole rows; folded
    window-major, every ply was relaid (docs/observability.md, "Reading a
    compiled program's layouts")."""
    x = jnp.moveaxis(x, 1, 0)
    return x.reshape((-1,) + x.shape[3:])


def _unfold_bt(x, B, T, P):
    """``_fold_bt``'s inverse on a net's output: (T*B*P, ...) -> (B, T, P, ...)"""
    return jnp.moveaxis(x.reshape((T, B, P) + x.shape[1:]), 0, 1)


def split_batch_stats(variables):
    """Split a flax variables dict into (trainable collections, batch_stats
    or None). Models without a ``batch_stats`` collection (every norm_kind
    but 'batch') pass through unchanged."""
    from collections.abc import Mapping
    if isinstance(variables, Mapping) and 'batch_stats' in variables:
        rest = {k: v for k, v in variables.items() if k != 'batch_stats'}
        return rest, variables['batch_stats']
    return variables, None


# weight of the auxiliary cross-entropy of a sequence net's further heads of
# prediction (``outputs['heads']``) in the total loss: the source publishes
# the heads, not a coefficient for them beside a policy-gradient loss
# (benchmark/configs/evabyte.json ``assumed``)
AUX_HEADS_COEF = 0.1


def _sequence_prediction(sequence_fn, params, batch: Dict[str, Any],
                         cfg: LossConfig):
    """A window as ONE causal forward: the net's ``sequence`` over all
    burn-in + T positions of each (window, seat), at the absolute positions
    the window's ``first_position`` leaf gives; the state at the burn-in's
    end carries no gradient (``no_grad_prefix``). Observations are integer
    ids (B, T, P_obs). (The recurrent branch below is not touched: its two
    ``astype``s for bfloat16 are a `benchmark` PR's, ROADMAP C4.)"""
    ids = batch['observation']
    B, T, P_obs = ids.shape[:3]
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape((B * P_obs, T))
    first = jnp.repeat(batch['first_position'].reshape(B), P_obs)
    out = dict(sequence_fn(params, fold(ids), first,
                           fold(batch['episode_mask'][..., 0]
                                * jnp.ones((1, 1, P_obs))) > 0,
                           cfg.burn_in_steps))
    # sums the forward pass hands on (``compute_loss``), not outputs
    aux = out.pop('aux', None)
    if PASS_GATE in out:
        # a LEADING pass axis on every output: behind the positions', where
        # further heads of prediction have theirs, (B, T, P, passes, ...)
        out = {k: jnp.moveaxis(v, 0, 2) for k, v in out.items()}
    outputs = {k: jnp.moveaxis(v.reshape((B, P_obs, T) + v.shape[2:]), 1, 2)
               for k, v in out.items()}
    if aux is not None:
        outputs['aux'] = aux
    return outputs


def forward_prediction(apply_fn, params, hidden, batch: Dict[str, Any],
                       cfg: LossConfig, batch_stats=None, sequence_fn=None):
    """Run the net over a training window; returns time-major-stacked outputs
    shaped (B, T, P, ...) with policy/value/return masking applied.

    ``batch_stats`` engages reference-BatchNorm training semantics
    (norm_kind='batch', reference model.py:54 train/eval split): the net is
    applied with ``train=True, mutable=['batch_stats']`` so normalization
    uses the CURRENT batch's statistics while the running averages advance
    — once per window for feed-forward nets (the fold makes the statistics
    span B*T*P, exactly like the reference's flattened forward) and once
    per scan step for recurrent nets (the reference's T per-timestep
    BatchNorm calls, burn-in included: torch updates running stats under
    no_grad too). The return becomes ``(outputs, new_batch_stats)``; the
    updated stats are stop_gradient'd (write-only during training — the
    forward reads only batch statistics in train mode)."""
    observations = batch['observation']
    B, T, P_obs = batch['action'].shape[:3]

    def net(bs, obs_in, h_in, params=params):
        """One apply in the right mode; returns (out_dict, new_bs)."""
        if bs is None:
            return dict(apply_fn(params, obs_in, h_in)), None
        out, mut = apply_fn({**dict(params), 'batch_stats': bs}, obs_in,
                            h_in, train=True, mutable=['batch_stats'])
        return dict(out), lax.stop_gradient(mut['batch_stats'])

    if sequence_fn is not None:
        outputs, new_bs = _sequence_prediction(sequence_fn, params, batch,
                                               cfg), None
    elif hidden is None:
        obs = tmap(_fold_bt, observations)
        outputs, new_bs = net(batch_stats, obs, None)
        outputs = {k: _unfold_bt(v, B, T, P_obs)
                   for k, v in outputs.items() if k != 'hidden' and v is not None}
    else:
        obs_tm = tmap(lambda o: jnp.moveaxis(o, 1, 0), observations)   # (T, B, P_obs, ...)
        omask_tm = jnp.moveaxis(batch['observation_mask'], 1, 0)       # (T, B, P, 1)

        def step(params, carry, x):
            h_carry, bs = carry
            obs_t, omask_t = x
            # gate each player's hidden by whether they observed this step
            def gate(h):
                m = omask_t.reshape(omask_t.shape[:2] + (1,) * (h.ndim - 2))
                return h * m
            gated = tmap(gate, h_carry)
            if cfg.turn_based_training and not cfg.observation:
                # only the turn player observed: summing the player axis
                # selects their state (others were zeroed)
                h_in = tmap(lambda h: h.sum(axis=1), gated)
                obs_in = tmap(lambda o: o.reshape((B,) + o.shape[2:]), obs_t)
            else:
                h_in = tmap(lambda h: h.reshape((-1,) + h.shape[2:]), gated)
                obs_in = tmap(lambda o: o.reshape((-1,) + o.shape[2:]), obs_t)
            out, bs = net(bs, obs_in, h_in, params)
            next_h = out.pop('hidden')
            out = {k: v.reshape((B, P_obs) + v.shape[1:])
                   for k, v in out.items() if v is not None}
            next_h = tmap(lambda h: h.reshape((B, -1) + h.shape[1:]), next_h)

            def merge(h, nh):
                m = omask_t.reshape(omask_t.shape[:2] + (1,) * (h.ndim - 2))
                return h * (1 - m) + nh * m
            h_carry = tmap(merge, h_carry, next_h)
            return (h_carry, bs), out

        # The backward pass recomputes a ply from what entered it: the scan
        # then stacks its carry and nothing else. Stacked, a ply's residuals
        # are written in the forward pass's layout and relaid one by one for
        # the backward pass, which costs more than the ply's second forward
        # (PERF.md section 6, PR 41). Recomputing costs a trace more passes
        # over the ply, so the burn-in takes the same traced ply, and its
        # parameters as constants: its carry is cut, and nothing need be
        # linearised to find its gradient zero.
        ply = jax.checkpoint(step)
        bi = cfg.burn_in_steps
        if bi > 0:
            xs_burn = (tmap(lambda o: o[:bi], obs_tm), omask_tm[:bi])
            (hidden, batch_stats), _ = lax.scan(
                functools.partial(ply, lax.stop_gradient(params)),
                (hidden, batch_stats), xs_burn)
            hidden = lax.stop_gradient(hidden)
        xs_main = (tmap(lambda o: o[bi:], obs_tm), omask_tm[bi:])
        (_, new_bs), outputs_tm = lax.scan(functools.partial(ply, params),
                                           (hidden, batch_stats), xs_main)
        outputs = {k: jnp.moveaxis(v, 0, 1) for k, v in outputs_tm.items()}

        # re-attach zero outputs for burn-in steps so downstream slicing is
        # uniform with the feed-forward path
        if bi > 0:
            outputs = {k: jnp.concatenate(
                [jnp.zeros(v.shape[:1] + (bi,) + v.shape[2:], v.dtype), v], axis=1)
                for k, v in outputs.items()}

    masked = {}
    for k, o in outputs.items():
        if k == 'aux':
            masked[k] = o
        elif k == 'policy':
            o = o * batch['turn_mask']
            if o.shape[2] > 1 and P_obs == 1:
                # turn-alternating batch: gather the acting player's row
                o = o.sum(axis=2, keepdims=True)
            masked[k] = o - as_float(batch['action_mask'], o.shape[-1])
        elif o.ndim > batch['observation_mask'].ndim:   # (B, T, P, n, A)
            masked[k] = o * batch['observation_mask'][..., None]
        else:
            masked[k] = o * batch['observation_mask']
    if batch_stats is None and new_bs is None:
        return masked          # historical API: norm-stateless models
    return masked, new_bs


def _entropy(logits: jnp.ndarray) -> jnp.ndarray:
    """Categorical entropy over the last axis; -1e32-masked logits contribute
    exactly zero (their probability underflows to 0 while the logit stays
    finite)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(jnp.exp(logp) * logp).sum(axis=-1)


def compose_losses(outputs: Dict[str, jnp.ndarray],
                   log_selected_policies: jnp.ndarray,
                   total_advantages: jnp.ndarray,
                   targets: Dict[str, Optional[jnp.ndarray]],
                   batch: Dict[str, Any], cfg: LossConfig,
                   policy_entropy: Optional[jnp.ndarray] = None
                   ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """``policy_entropy`` (B, T, P): the policy's entropy where the head
    was taken in blocks (``_policy_in_blocks``) and ``outputs`` has no
    ``policy``."""
    tmasks = batch['turn_mask']
    omasks = batch['observation_mask']

    losses: Dict[str, jnp.ndarray] = {}
    dcnt = tmasks.sum()

    losses['p'] = (-log_selected_policies * total_advantages * tmasks).sum()
    if 'value' in outputs:
        losses['v'] = (((outputs['value'] - targets['value']) ** 2) * omasks).sum() / 2
    if 'return' in outputs:
        huber = optax_huber(outputs['return'], targets['return'])
        losses['r'] = (huber * omasks).sum()

    if policy_entropy is None:
        policy_entropy = _entropy(outputs['policy'])
    entropy = policy_entropy * tmasks.sum(axis=-1)
    losses['ent'] = entropy.sum()

    base = losses['p'] + losses.get('v', 0) + losses.get('r', 0)
    if 'heads' in outputs:
        losses['aux'] = _further_heads_loss(outputs['heads'], batch)
        base = base + AUX_HEADS_COEF * losses['aux']
    decay = 1 - batch['progress'] * (1 - cfg.entropy_regularization_decay)
    entropy_loss = (entropy * decay).sum() * -cfg.entropy_regularization
    losses['total'] = base + entropy_loss
    return losses, dcnt


def _further_heads_loss(heads: jnp.ndarray, batch: Dict[str, Any]
                        ) -> jnp.ndarray:
    """The published multi-byte objective as an auxiliary cross-entropy:
    head j of ``heads`` (B, T, P, n, A) at position t predicts the id the
    same seat observes at t + 2 + j, counted where both positions lie in the
    game and in the window."""
    ids = batch['observation']                           # (B, T, P) int
    in_game = batch['observation_mask'][..., 0]          # (B, T, P)
    T = ids.shape[1]
    logp = jax.nn.log_softmax(heads, axis=-1)
    total = 0.0
    for j in range(heads.shape[3]):
        ahead = 2 + j
        if ahead >= T:
            break
        target = ids[:, ahead:]
        picked = jnp.take_along_axis(logp[:, :T - ahead, :, j],
                                     target[..., None], axis=-1)[..., 0]
        total = total - (picked * in_game[:, :T - ahead]
                         * in_game[:, ahead:]).sum()
    return total


# positions whose logits are live at once where a net hands over
# ``policy_features`` and not ``policy`` (a head of 25,024 ids over 8,192
# positions is 820 MB in float32 for each live copy)
POLICY_BLOCK = 1024


def _policy_in_blocks(policy_fn, params, features, batch):
    """The head, its log-softmax and its entropy ``POLICY_BLOCK`` positions
    at a time, each block rematerialised in the backward pass: the taken
    action's log-probability (B, T, P, 1) and the entropy (B, T, P) of the
    masked policy ``policy_fn(params, features) * turn_mask - action_mask``,
    what the whole-array path computes."""
    lead = features.shape[:3]
    n = lead[0] * lead[1] * lead[2]
    block = min(POLICY_BLOCK, n)
    assert n % block == 0, (n, block)
    rows = lambda x: x.reshape((n // block, block) + x.shape[3:])

    @jax.checkpoint
    def one(args):
        feats, turn, mask, action = args
        logits = policy_fn(params, feats)
        logp = jax.nn.log_softmax(
            logits * turn - as_float(mask, logits.shape[-1]), axis=-1)
        return (jnp.take_along_axis(logp, action, axis=-1),
                -(jnp.exp(logp) * logp).sum(axis=-1))
    picked, entropy = lax.map(one, (
        rows(features), rows(batch['turn_mask']), rows(batch['action_mask']),
        rows(batch['action'])))
    return picked.reshape(lead + (1,)), entropy.reshape(lead)


# A sequence net that runs its layers several times hands over, beside
# ``policy_features`` and ``value`` of EVERY pass, this output: the logit of
# leaving after each pass. Its presence is what says "a pass axis".
PASS_GATE = 'exit_gate'
# weight of the exit distribution's entropy in such a net's loss (the
# source's beta: its config holds none; benchmark/configs/ouro.json
# ``assumed``)
EXIT_ENTROPY_COEF = 0.1


def exit_distribution(gate_logits):
    """gate_logits (passes, ...) -> ``p`` (passes, ...), the probability of
    leaving after each pass, and its entropy (...) in nats: with ``g_t`` the
    sigmoid of pass t's logit, ``p_t = g_t prod_{j<t} (1 - g_j)`` and the
    last pass takes what is left (its own logit is not read)."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay], axis=0)
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]) + before[:-1], before[-1:]],
        axis=0)
    p = jnp.exp(log_p)
    return p, -(p * log_p).sum(axis=0)


@contextlib.contextmanager
def _pass_readout_scope():
    """The scope ``pass_readout`` where ``compute_loss`` opens it. jax writes
    its transforms around the FIRST scope named under them
    (``transpose(jvp(...))``, one step of an operation's path), and here
    nothing is named above: a scope of the seam's own takes the transforms,
    so that ``pass_readout`` stands in a trace as it is, backward pass too
    (as ``models/experts.py`` ``dispatched_sum``)."""
    with jax.named_scope('exit_weighted'), jax.named_scope('pass_readout'):
        yield


def _passes_in_blocks(policy_fn, params, features, batch):
    """``_policy_in_blocks`` a pass, the passes one after the other:
    features (B, T, P, passes, D) -> the taken action's log-probability
    (passes, B, T, P, 1) and the entropy (passes, B, T, P)."""
    return lax.map(
        lambda feats: _policy_in_blocks(policy_fn, params, feats, batch),
        jnp.moveaxis(features, 3, 0))


def _exit_weighted_losses(log_selected, entropies, values, gate_logits,
                          total_advantages, targets, batch, cfg):
    """``compose_losses`` for a net with a pass axis: each pass's loss a
    position from today's three terms (policy gradient on ITS log-probability
    ``log_selected`` (passes, B, T, P, 1), regression of ITS value (passes,
    B, T, P, 1), ITS policy's entropy (passes, B, T, P)) against the SHARED
    advantages and targets, weighted by the exit distribution ``p`` of
    ``gate_logits`` (passes, B, T, P, 1), less ``EXIT_ENTROPY_COEF`` times
    ``p``'s entropy, summed over the acting positions. ``p`` carries its
    gradient: the gate trains through both terms. Beside the losses and the
    count of acting positions, the sums the epoch record reads the gate
    from (they join ``diag``, and ``FusedPipeline._parse`` adds them up onto
    ``host_block``): the count again under the name a sequence net's
    ``aux`` gives it, ``p``'s entropy and its largest possible value, and
    each pass's mass."""
    tmasks = batch['turn_mask']
    acting = tmasks.sum(axis=-1)
    p, exit_entropy = exit_distribution(gate_logits[..., 0])
    weigh = lambda term: (p * term).sum()
    losses = {
        'p': weigh((-log_selected * total_advantages * tmasks)[..., 0]),
        'v': weigh((((values - targets['value']) ** 2)
                    * batch['observation_mask'])[..., 0]) / 2,
        'ent': weigh(entropies * acting),
        'exit_ent': (exit_entropy * acting).sum()}
    decay = 1 - batch['progress'] * (1 - cfg.entropy_regularization_decay)
    losses['total'] = (
        losses['p'] + losses['v']
        - cfg.entropy_regularization * weigh(entropies * acting * decay)
        - EXIT_ENTROPY_COEF * losses['exit_ent'])
    count = tmasks.sum()
    sums = {'window_positions_valid': count,
            'exit_entropy_nats': losses['exit_ent'],
            'exit_entropy_max_nats': count * math.log(p.shape[0])}
    for t in range(p.shape[0]):
        sums['exit_mass_pass_%d' % (t + 1)] = (p[t] * acting).sum()
    return losses, count, sums


def optax_huber(pred: jnp.ndarray, target: jnp.ndarray, delta: float = 1.0
                ) -> jnp.ndarray:
    """Smooth-L1 (huber, delta=1), elementwise."""
    err = pred - target
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    return 0.5 * quad ** 2 + delta * (abs_err - quad)


def compute_loss(apply_fn, params, init_hidden, batch: Dict[str, Any],
                 cfg: LossConfig, batch_stats=None, target_params=None,
                 sequence_fn=None, policy_fn=None
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full pipeline: forward, targets, advantages, composed losses.

    Returns (total_loss, aux) where aux carries per-term sums and the data
    count for the EMA lr schedule. For norm_kind='batch' models the caller
    may pass the full variables dict as ``params`` (the batch_stats
    collection is split off here) or pass ``batch_stats`` explicitly; the
    advanced running averages come back as ``aux['batch_stats']``.

    ``target_params`` (with ``cfg.target_clip`` > 0) engages the
    IMPACT-style clipped target network: a second, stop-gradient forward
    under the slow-moving target params supplies the importance ratio
    pi_target/mu used for the V-Trace corrections — clipped at
    ``target_clip`` for rho, at 1 for c — in place of the current-policy
    ratio. Streamed (staler) data then drives value targets through a
    policy that moves once per ``target_sync_epochs`` instead of every
    SGD step, which is what keeps high-lag chunks trainable. The policy
    gradient itself still differentiates the CURRENT policy's log-prob.

    A sequence net MAY hand over ``policy_features`` in place of ``policy``
    (``policy_fn(params, features)`` gives the logits; the head is then
    taken in blocks of positions, ``_policy_in_blocks``) and MAY return
    ``aux``, sums of its forward pass: its scalars join ``diag`` and the
    whole of it rides back as ``aux['sequence_aux']``. With ``PASS_GATE``
    among its outputs the features, values and gate logits have a pass axis:
    the head is taken a pass, V-trace's ratios, targets and advantages come
    ONCE, from the last pass (the policy the actor played), and the loss is
    ``_exit_weighted_losses``.
    """
    if batch_stats is None:
        params, batch_stats = split_batch_stats(params)
    outputs = forward_prediction(apply_fn, params, init_hidden, batch, cfg,
                                 batch_stats, sequence_fn=sequence_fn)
    new_bs = None
    if batch_stats is not None:
        outputs, new_bs = outputs
    sequence_aux = outputs.pop('aux', None)

    use_target = target_params is not None and cfg.target_clip > 0
    tgt_outputs = None
    if use_target:
        t_params, t_bs = split_batch_stats(target_params)
        tgt_outputs = forward_prediction(apply_fn, t_params, init_hidden,
                                         batch, cfg, t_bs,
                                         sequence_fn=sequence_fn)
        if t_bs is not None:
            tgt_outputs, _ = tgt_outputs   # target stats never advance
        tgt_outputs = {k: lax.stop_gradient(v)
                       for k, v in tgt_outputs.items()}

    bi = cfg.burn_in_steps
    if bi > 0:
        batch = _slice_burn_in(batch, bi)
        outputs = {k: v[:, bi:] for k, v in outputs.items()}
        if tgt_outputs is not None:
            tgt_outputs = {k: v[:, bi:] for k, v in tgt_outputs.items()}

    actions = batch['action']
    emasks = batch['episode_mask']
    omasks = batch['observation_mask']
    value_target_masks = omasks

    clip_rho, clip_c = 1.0, 1.0

    log_b = jnp.log(jnp.clip(batch['selected_prob'], 1e-16, 1)) * emasks
    policy_entropy = passes = None
    if 'policy' in outputs:
        logp = jax.nn.log_softmax(outputs['policy'], axis=-1)
        log_t = jnp.take_along_axis(logp, actions, axis=-1) * emasks
    else:
        assert not use_target, 'no target network beside a head in blocks'
        features = outputs.pop('policy_features')
        if PASS_GATE in outputs:
            with _pass_readout_scope():
                picked, entropies = _passes_in_blocks(policy_fn, params,
                                                      features, batch)
            passes = (picked * emasks, entropies,
                      jnp.moveaxis(outputs.pop('value'), 3, 0),
                      jnp.moveaxis(outputs.pop(PASS_GATE), 3, 0))
            # what follows reads the pass the actor played from
            picked, outputs['value'] = picked[-1], passes[2][-1]
        else:
            picked, policy_entropy = _policy_in_blocks(policy_fn, params,
                                                       features, batch)
        log_t = picked * emasks

    log_rhos = lax.stop_gradient(log_t) - log_b
    rhos = jnp.exp(log_rhos)
    if use_target:
        logp_tgt = jax.nn.log_softmax(tgt_outputs['policy'], axis=-1)
        log_tgt = jnp.take_along_axis(logp_tgt, actions, axis=-1) * emasks
        rhos_tgt = jnp.exp(log_tgt - log_b)
        clipped_rhos = jnp.clip(rhos_tgt, 0, cfg.target_clip)
        cs = jnp.clip(rhos_tgt, 0, clip_c)
    else:
        clipped_rhos = jnp.clip(rhos, 0, clip_rho)
        cs = jnp.clip(rhos, 0, clip_c)
    outputs_nograd = {k: lax.stop_gradient(v) for k, v in outputs.items()}

    if 'value' in outputs_nograd:
        values_nograd = outputs_nograd['value']
        if cfg.turn_based_training and values_nograd.shape[2] == 2:
            # two-player zero-sum: each player's estimate is blended with the
            # negation of the opponent's (train.py:243-247)
            values_opp = -jnp.flip(values_nograd, axis=2)
            omasks_opp = jnp.flip(omasks, axis=2)
            values_nograd = ((values_nograd * omasks + values_opp * omasks_opp)
                             / (omasks + omasks_opp + 1e-8))
            value_target_masks = jnp.clip(omasks + omasks_opp, 0, 1)
        # bootstrap padded steps beyond episode end with the final outcome
        outputs_nograd['value'] = (values_nograd * emasks
                                   + batch['outcome'] * (1 - emasks))

    targets: Dict[str, Any] = {}
    advantages: Dict[str, Any] = {}

    value_args = (outputs_nograd.get('value', None), batch['outcome'], None,
                  cfg.lmb, 1.0, clipped_rhos, cs, value_target_masks)
    return_args = (outputs_nograd.get('return', None), batch['return'],
                   batch['reward'], cfg.lmb, cfg.gamma, clipped_rhos, cs, omasks)

    targets['value'], advantages['value'] = compute_target(cfg.value_target, *value_args)
    targets['return'], advantages['return'] = compute_target(cfg.value_target, *return_args)

    if cfg.policy_target != cfg.value_target:
        _, advantages['value'] = compute_target(cfg.policy_target, *value_args)
        _, advantages['return'] = compute_target(cfg.policy_target, *return_args)

    total_advantages = clipped_rhos * sum(advantages.values())

    exit_sums = {}
    if passes is None:
        losses, dcnt = compose_losses(outputs, log_t, total_advantages,
                                      targets, batch, cfg, policy_entropy)
    else:
        with _pass_readout_scope():
            losses, dcnt, exit_sums = _exit_weighted_losses(
                *passes, total_advantages, targets, batch, cfg)
    # off-policy health diagnostics, summed over acting (step, player)
    # pairs like every loss term so the host normalizes by data_count:
    # V-Trace rho/c clip fractions and the importance-ratio first/second
    # moments (mean/std of the behavior->target ratio). They ride the
    # update step's existing lazy metric fetch — no extra device sync.
    tmask = batch['turn_mask']
    diag = {
        'rho_clip': ((rhos > clip_rho) * tmask).sum(),
        'c_clip': ((rhos > clip_c) * tmask).sum(),
        'rho_sum': (rhos * tmask).sum(),
        'rho_sq_sum': (jnp.square(rhos) * tmask).sum(),
    }
    if use_target:
        # target-network health: clip fraction and first moment of the
        # target/behavior ratio, plus the current-vs-target log-prob gap
        # on taken actions (a drift/KL proxy) — how far the fast policy
        # has moved since the last target sync
        diag['target_clip'] = ((rhos_tgt > cfg.target_clip) * tmask).sum()
        diag['target_ratio_sum'] = (rhos_tgt * tmask).sum()
        diag['target_gap_sum'] = ((lax.stop_gradient(log_t) - log_tgt)
                                  * tmask).sum()
    diag.update(exit_sums)
    aux = {'losses': losses, 'data_count': dcnt, 'diag': diag}
    if sequence_aux is not None:
        diag.update((k, v) for k, v in sequence_aux.items() if v.ndim == 0)
        aux['sequence_aux'] = sequence_aux
    if new_bs is not None:
        aux['batch_stats'] = new_bs
    return losses['total'], aux


def _slice_burn_in(batch: Dict[str, Any], bi: int) -> Dict[str, Any]:
    """Drop burn-in steps from every time-indexed entry (time-size-1 entries
    like outcome pass through, mirroring train.py:221)."""
    def cut(v):
        return v if v.shape[1] <= 1 else v[:, bi:]
    return {k: tmap(cut, v) if isinstance(v, dict) else cut(v)
            for k, v in batch.items()}
