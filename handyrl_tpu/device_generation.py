"""Device-resident self-play: the entire act/sample/step loop inside one jit.

The BatchedGenerator (generation.py) still crosses the host boundary once
per ply (observations up, policies down). For environments implemented as
pure JAX functions (envs/jax_tictactoe.py, envs/jax_hungry_geese.py), this
engine runs K plies of N environments as ONE compiled program — inference,
legal masking, categorical sampling, transition, termination detection and
auto-reset all stay in HBM; the host receives a (K, N, ...) trajectory chunk
and only splices completed episodes into the standard episode records (the
same wire/batch format as every other generator, generation.py:84-91 in the
reference).

Two env protocols:
  * turn-based (jax_tictactoe): observe -> (N, ...) side-to-move view,
    step((N,) actions), turn -> (N,) acting seat;
  * simultaneous (SIMULTANEOUS=True, jax_hungry_geese): observe ->
    (N, P, ...) per-player views, step((N, P) actions), acting -> (N, P)
    mask of players that act this ply.

This is the throughput ceiling path: on a TPU the per-ply cost is one fused
program dispatch regardless of N.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .generation import (Generator, _blank_moment, _finalize_episode,
                         bucketed_inference, build_chunk, masked_sample,
                         pad_to_bucket, sample_seed, seed_env_rng)
from .ops import maskbits
from .ops.batch import compress_moments
from .utils.tree import map_structure


def obs_leading(obs) -> int:
    """Leading (env) dimension of an observation pytree."""
    return jax.tree_util.tree_leaves(obs)[0].shape[0]


def _blank(players):
    return {key: {p: None for p in players} for key in
            ('observation', 'selected_prob', 'action_mask', 'action',
             'value', 'reward', 'return')}


def _ply_inference(env_mod, apply_fn, recurrent, simultaneous,
                   params, state, hidden):
    """Shared per-ply plumbing for the device rollout engines (generation
    and evaluation): observe, run the net — with the recurrent hidden
    gather/scatter for turn-based envs and the (N, P)->(N*P) fold for
    simultaneous ones — and build the illegal-action mask.

    Returns (obs, logits, amask, hidden, out): logits/amask are (N, P, A)
    for simultaneous envs, (N, A) turn-based; ``out`` is the raw model
    output dict with 'hidden' already popped.
    """
    obs = env_mod.observe(state)
    legal = env_mod.legal_mask(state)
    amask = (1.0 - legal) * 1e32
    if simultaneous:
        N, P = obs.shape[:2]
        flat = obs.reshape((N * P,) + obs.shape[2:])
        if recurrent:
            # every player's hidden advances each ply (they all observe);
            # fold (N, P) into the batch dim
            h_in = jax.tree_util.tree_map(
                lambda h: h.reshape((N * P,) + h.shape[2:]), hidden)
            out = dict(apply_fn(params, flat, h_in))
            nh = out.pop('hidden')
            hidden = jax.tree_util.tree_map(
                lambda h: h.reshape((N, P) + h.shape[1:]), nh)
        else:
            out = dict(apply_fn(params, flat, None))
        logits = out['policy'].reshape(N, P, -1) - amask
    else:
        if recurrent:
            # gather the acting player's hidden slot, run the net, scatter
            # the new state back (mirrors the omask-gated training carry)
            rows = jnp.arange(obs_leading(obs))
            player = env_mod.turn(state)
            h_in = jax.tree_util.tree_map(
                lambda h: h[rows, player], hidden)
            out = dict(apply_fn(params, obs, h_in))
            nh = out.pop('hidden')
            hidden = jax.tree_util.tree_map(
                lambda h, x: h.at[rows, player].set(x), hidden, nh)
        else:
            out = dict(apply_fn(params, obs, None))
        logits = out['policy'] - amask
    return obs, logits, amask, hidden, out


def _reset_hidden_where_done(hidden, done, module=None):
    """Fresh episodes start with zero recurrent state. A net whose state is
    a cache kept by counters supplies ``reset_hidden(hidden, done)`` and
    resets those alone; the zero-fill of the whole tree is for the nets
    that do not."""
    if hasattr(module, 'reset_hidden'):
        return module.reset_hidden(hidden, done)
    return jax.tree_util.tree_map(
        lambda h: jnp.where(done.reshape((-1,) + (1,) * (h.ndim - 1)),
                            jnp.zeros_like(h), h), hidden)


class _RecordPacker:
    """Flatten a records pytree into ONE f32 device array and back.

    Each distinct array fetch is its own blocking device->host transfer
    with a fixed cost (utils/fetch.py), so the splice path packs every
    record leaf into a single transfer instead of one per leaf. The pack
    runs as its own tiny jitted program (async dispatch);
    unpack restores shapes/dtypes exactly (int/bool values are small enough
    to round-trip through f32 losslessly)."""

    def __init__(self, records):
        leaves, self.treedef = jax.tree_util.tree_flatten(records)
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self._fn = jax.jit(lambda ls: jnp.concatenate(
            [l.astype(jnp.float32).reshape(-1) for l in ls]))

    def pack(self, records):
        return self._fn(jax.tree_util.tree_leaves(records))

    def unpack(self, flat):
        flat = np.asarray(flat)   # the one transfer
        out, pos = [], 0
        for shape, dtype in zip(self.shapes, self.dtypes):
            n = int(np.prod(shape)) if shape else 1
            out.append(flat[pos:pos + n].reshape(shape).astype(dtype))
            pos += n
        return jax.tree_util.tree_unflatten(self.treedef, out)


# NOTE on observation=True for turn-based envs (the geister-device config):
# the reference generator runs inference ONLY for ``turn_players +
# observers`` each ply (reference generation.py:37-41), and no reference env
# ever overrides ``observers()`` (it defaults to [] — reference
# environment.py:84); the eval-side Agent likewise advances its hidden only
# on its own turns (reference evaluation.py:97-101). So even with
# observation=True, exactly the acting seat observes per ply — the flag only
# widens the BATCH layout to the full player axis (reference train.py:65-68)
# with observation_mask marking the acting seat. The acting-seat-only
# recording below is therefore already reference-exact; an earlier
# "observe-all" helper that ran inference for every seat per ply was removed
# as anti-parity (tests/test_geister_device_parity.py pins the semantics).


def _init_rollout_engine(engine, env_mod, wrapper, n_envs: int, seed: int):
    """Shared env/model bootstrapping for the device rollout engines: env
    state vector, PRNG key, simultaneous/recurrent detection, and the
    per-env recurrent hidden pytree."""
    engine.env_mod = env_mod
    engine.wrapper = wrapper
    engine.n_envs = n_envs
    engine.simultaneous = bool(getattr(env_mod, 'SIMULTANEOUS', False))
    try:
        engine.state = env_mod.init_state(n_envs, seed)
    except TypeError:
        engine.state = env_mod.init_state(n_envs)
    engine.rng = jax.random.PRNGKey(seed)
    engine.recurrent = hasattr(wrapper.module, 'init_hidden')
    engine.hidden = (wrapper.module.init_hidden(
        (n_envs, env_mod.NUM_PLAYERS)) if engine.recurrent else None)


def make_gen_body(env_mod, apply_fn, recurrent: bool, simultaneous: bool,
                  module=None):
    """The one self-play ply: inference, sampling, transition, record.

    Shared between DeviceGenerator's standalone rollout program and the
    fused generate+ingest+train pipeline (ops/fused_pipeline.py) so the
    recorded trajectory semantics have exactly one definition.
    Carry is (env_state, hidden, rng); emits the per-ply record dict.

    The ply body is (re)defined inside ``rollout_chunk`` so it closes over
    the CURRENT trace's params: lax.scan caches traced bodies by function
    identity, and a body shared across traces would smuggle one trace's
    param tracers into the next (UnexpectedTracerError).
    """
    # ``module``: the net, where it resets its own state (``reset_hidden``);
    # by default the one the bound ``apply`` belongs to
    if module is None:
        module = getattr(apply_fn, '__self__', None)

    def rollout_chunk(params, state, hidden, rng, chunk_steps: int):
        def body(carry, _):
            state, hidden, rng = carry
            obs, logits, amask, hidden, out = _ply_inference(
                env_mod, apply_fn, recurrent, simultaneous,
                params, state, hidden)
            rng, key = jax.random.split(rng)
            actions = jax.random.categorical(key, logits)
            probs = jax.nn.softmax(logits, axis=-1)
            sel = jnp.take_along_axis(probs, actions[..., None],
                                      axis=-1)[..., 0]
            if getattr(env_mod, 'MASK_AS_BITS', False):
                # a wide id space: the record, the history and the ring
                # keep the legal set as bits (ops/maskbits.py)
                amask = maskbits.pack(amask > 0)
            if simultaneous:
                N, P = obs.shape[:2]
                value = out.get('value')
                if value is not None:
                    value = value.reshape(N, P, -1)
                act_mask = env_mod.acting(state)           # (N, P)
                nstate = env_mod.step(state, actions)
                done = env_mod.terminal(nstate)
                record = {'obs': obs, 'action': actions, 'prob': sel,
                          'amask': amask, 'value': value,
                          'acting': act_mask, 'done': done,
                          'outcome': env_mod.outcome(nstate)}
            else:
                player = env_mod.turn(state)
                nstate = env_mod.step(state, actions)
                done = env_mod.terminal(nstate)
                record = {'obs': obs, 'action': actions, 'prob': sel,
                          'amask': amask, 'value': out.get('value'),
                          'player': player, 'done': done,
                          'outcome': env_mod.outcome(nstate)}
            if hasattr(env_mod, 'rewards'):
                record['reward'] = env_mod.rewards(nstate)   # (N, P)
            nstate = env_mod.auto_reset(nstate, done)
            if recurrent:
                hidden = _reset_hidden_where_done(hidden, done, module)
            return (nstate, hidden, rng), record

        (state, hidden, rng), records = jax.lax.scan(
            body, (state, hidden, rng), None, length=chunk_steps)
        return state, hidden, rng, dict(records)

    return rollout_chunk


class DeviceGenerator:
    """Runs chunks of device-resident self-play for a pure-JAX env module.

    Dispatch is PIPELINED one chunk deep: each ``step_chunk`` call enqueues
    the NEXT rollout program before fetching the previous chunk's results,
    so the host's blocking fetch overlaps with device execution of the
    following chunk instead of leaving the device idle. Callers see a
    one-chunk delay in episode accounting, nothing else.
    """

    pipelined = True    # step_chunk returns the PREVIOUS dispatch's chunk

    def __init__(self, env_mod, wrapper, args: Dict[str, Any],
                 n_envs: int = 256, chunk_steps: int = 16, seed: int = 0):
        self.args = args
        self.chunk_steps = chunk_steps
        _init_rollout_engine(self, env_mod, wrapper, n_envs, seed)
        self._partials: List[List[dict]] = [[] for _ in range(n_envs)]
        self._pending = None
        self._full_pack = None
        self.dispatches = 0

        rollout_chunk = make_gen_body(env_mod, wrapper.module.apply,
                                      self.recurrent, self.simultaneous)

        @jax.jit
        def rollout(params, state, hidden, rng):
            return rollout_chunk(params, state, hidden, rng, chunk_steps)

        self._rollout = rollout

    def _dispatch(self):
        self.state, self.hidden, self.rng, records = self._rollout(
            self.wrapper.params, self.state, self.hidden, self.rng)
        self.dispatches += 1
        return dict(records)

    # -- host-side episode splicing ---------------------------------------
    def _dispatch_full(self):
        """Dispatch rollout + the full-record pack (splice mode fetches
        EVERY leaf; packed, that is one transfer instead of one per leaf)."""
        records = self._dispatch()
        if self._full_pack is None:
            self._full_pack = _RecordPacker(records)
        return self._full_pack.pack(records)

    def step_chunk(self) -> List[dict]:
        """Run one compiled chunk; return episodes completed within it."""
        if self._pending is None:
            self._pending = self._dispatch_full()
        pack, self._pending = self._pending, self._dispatch_full()
        return self._splice(self._full_pack.unpack(pack))

    def drain_episodes(self) -> List[dict]:
        """Splice the in-flight speculative chunk at loop shutdown."""
        if self._pending is None:
            return []
        pack, self._pending = self._pending, None
        return self._splice(self._full_pack.unpack(pack))

    def _splice(self, rec) -> List[dict]:
        players = list(range(self.env_mod.NUM_PLAYERS))
        episodes: List[dict] = []
        for k in range(self.chunk_steps):
            for i in range(self.n_envs):
                if self.simultaneous:
                    moment = self._moment_simultaneous(rec, k, i, players)
                else:
                    moment = self._moment_turn_based(rec, k, i, players)
                self._partials[i].append(moment)
                if rec['done'][k, i]:
                    episodes.append(self._finalize(i, rec, k, players))
        return episodes

    def _moment_turn_based(self, rec, k, i, players):
        player = int(rec['player'][k, i])
        moment = _blank(players)
        moment['observation'][player] = map_structure(
            lambda v: v[k, i], rec['obs'])
        moment['selected_prob'][player] = float(rec['prob'][k, i])
        moment['action_mask'][player] = rec['amask'][k, i]
        moment['action'][player] = int(rec['action'][k, i])
        if rec.get('value') is not None:
            moment['value'][player] = rec['value'][k, i]
        moment['reward'] = self._rewards(rec, k, i, players)
        moment['turn'] = [player]
        return moment

    def _rewards(self, rec, k, i, players):
        if rec.get('reward') is None:
            return {p: None for p in players}
        return {p: float(rec['reward'][k, i, p]) for p in players}

    def _moment_simultaneous(self, rec, k, i, players):
        moment = _blank(players)
        turn_players = []
        for p in players:
            if not rec['acting'][k, i, p]:
                continue
            turn_players.append(p)
            moment['observation'][p] = map_structure(
                lambda v: v[k, i, p], rec['obs'])
            moment['selected_prob'][p] = float(rec['prob'][k, i, p])
            moment['action_mask'][p] = rec['amask'][k, i, p]
            moment['action'][p] = int(rec['action'][k, i, p])
            if rec.get('value') is not None:
                moment['value'][p] = rec['value'][k, i, p]
        moment['reward'] = self._rewards(rec, k, i, players)
        moment['turn'] = turn_players
        return moment

    def _finalize(self, i, rec, k, players):
        moments = self._partials[i]
        self._partials[i] = []
        outcome = {p: float(rec['outcome'][k, i, p]) for p in players}
        for p in players:
            ret = 0.0
            for t in range(len(moments) - 1, -1, -1):
                ret = (moments[t]['reward'][p] or 0) + self.args['gamma'] * ret
                moments[t]['return'][p] = ret
        return {
            'args': {'role': 'g', 'player': players,
                     'model_id': {p: -1 for p in players}},
            'steps': len(moments),
            'outcome': outcome,
            'moment': compress_moments(moments, self.args['compress_steps']),
        }


class DeviceEvaluator:
    """Device-resident online evaluation vs a roster of opponents.

    The host BatchedEvaluator pays one inference dispatch per ply of every
    match; on a dispatch-latency-heavy backend that makes evaluation the
    dominant cost of the epoch loop (it needs ~10x more dispatches than
    chunked device generation for the same ply count). When every opponent
    is 'random' or a checkpoint path (league play) and the env has a
    pure-JAX twin, the whole match runs on device instead: envs split into
    one contiguous block per opponent, one rotating seat per env plays the
    trained model greedily (the same temperature-0 policy as
    BatchedEvaluator / reference agent.py Agent), the other seats either
    sample uniformly ('random') or play their checkpoint's greedy policy —
    inferenced inside the same compiled ply — and the host receives only
    (done, outcome, seat) per ply, K plies of N matches per dispatch.
    'rulebase' also runs on device when the env twin vectorizes its agent
    (``greedy_action``, e.g. jax_hungry_geese); otherwise it stays on the
    host evaluator (train.py device_eval_ok). Checkpoint opponents for
    recurrent nets carry their own hidden tree through the scan, so e.g.
    Geister league eval keeps the one-dispatch-per-chunk budget.
    """

    def __init__(self, env_mod, wrapper, args: Dict[str, Any],
                 n_envs: int = 64, chunk_steps: int = 16, seed: int = 77,
                 mesh=None, opponents=None):
        self.args = args
        self.chunk_steps = chunk_steps
        _init_rollout_engine(self, env_mod, wrapper, n_envs, seed)
        # one evaluated seat per env, rotated on every reset so first/second
        # (and every goose slot) are balanced like evaluate_mp's scheduler
        self.seat = jnp.arange(n_envs, dtype=jnp.int32) % env_mod.NUM_PLAYERS

        # opponent roster: envs are split into one contiguous block per
        # opponent (league play stays one-dispatch-per-chunk — the round-2
        # device evaluator silently fell back to the per-ply host evaluator
        # for anything but 'random'). 'random' plays uniform; a checkpoint
        # path plays its own greedy policy, inferenced inside the same
        # compiled ply (recurrent checkpoints carry opp_hidden, below).
        self.opponents = [str(o) for o in (opponents or ['random'])]
        assert n_envs >= len(self.opponents), \
            'need at least one eval env per opponent'
        self._opp_params: List[Any] = []
        bounds = np.linspace(0, n_envs, len(self.opponents) + 1).astype(int)
        self._opp_bounds = [(int(a), int(b), name)
                            for a, b, name in zip(bounds[:-1], bounds[1:],
                                                  self.opponents)]
        self._env_opp = np.empty(n_envs, dtype=object)
        for a, b, name in self._opp_bounds:
            self._env_opp[a:b] = name
        if 'rulebase' in self.opponents:
            assert hasattr(env_mod, 'greedy_action'), \
                'device rulebase eval needs the env twin to vectorize it'
        model_opps = [o for o in self.opponents
                      if o not in ('random', 'rulebase')]
        if model_opps:
            # the trained wrapper's params are the ready-made template for
            # msgpack deserialization (same module, same tree)
            from flax import serialization
            for path in model_opps:
                with open(path, 'rb') as f:
                    self._opp_params.append(jax.device_put(
                        serialization.from_bytes(wrapper.params, f.read())))
        # recurrent checkpoint opponents carry their own hidden tree through
        # the scan (gathered/scattered exactly like the main model's); the
        # env blocks are disjoint so ONE tree serves every opponent slice
        self.opp_hidden = (wrapper.module.init_hidden(
            (n_envs, env_mod.NUM_PLAYERS))
            if self.recurrent and model_opps else None)
        if mesh is not None:
            # eval envs sharded over 'data' alongside the fused trainer
            # (params arrive replicated); the plain-jit rollout partitions
            # under GSPMD — eval is embarrassingly parallel over envs
            from .parallel.mesh import replicated_sharding, shard_batch
            self.state = shard_batch(mesh, self.state)
            if self.hidden is not None:
                self.hidden = shard_batch(mesh, self.hidden)
            if self.opp_hidden is not None:
                self.opp_hidden = shard_batch(mesh, self.opp_hidden)
            self.seat = shard_batch(mesh, self.seat)
            self.rng = jax.device_put(self.rng, replicated_sharding(mesh))
        self._pending = None
        self._pack = None
        self.dispatches = 0

        apply_fn = wrapper.module.apply
        simultaneous = self.simultaneous
        recurrent = self.recurrent

        opp_bounds = self._opp_bounds
        model_ix = {name: i for i, name in enumerate(
            o for o in self.opponents if o not in ('random', 'rulebase'))}
        any_rulebase = any(name == 'rulebase' for _, _, name in opp_bounds)

        @jax.jit
        def rollout(params, opp_params, state, hidden, opp_hidden, seat,
                    rng):
            def body(carry, _):
                state, hidden, opp_hidden, seat, rng = carry
                obs, logits, amask, hidden, _ = _ply_inference(
                    env_mod, apply_fn, recurrent, simultaneous,
                    params, state, hidden)
                greedy = jnp.argmax(logits, axis=-1)
                rng, key = jax.random.split(rng)
                opp_act = jax.random.categorical(key, -amask)
                if any_rulebase:   # the env's vectorized rulebase agent
                    rng, rkey = jax.random.split(rng)
                    rule_act = env_mod.greedy_action(state, rkey)
                # opponent blocks: checkpoint policies (greedy) and the
                # rulebase agent, traced into this one program (static
                # slices). Recurrent checkpoints gather/scatter their own
                # hidden tree the same way _ply_inference does the main
                # model's — the blocks are disjoint slices of opp_hidden.
                for a, b, name in opp_bounds:
                    if name == 'random' or a == b:
                        continue
                    if name == 'rulebase':
                        opp_act = opp_act.at[a:b].set(rule_act[a:b])
                        continue
                    pg = opp_params[model_ix[name]]
                    # observations may be a pytree (e.g. geister's
                    # {'scalar', 'board'}): slice every leaf
                    o = jax.tree_util.tree_map(lambda x: x[a:b], obs)
                    if simultaneous:
                        No, Po = jax.tree_util.tree_leaves(o)[0].shape[:2]
                        flat = jax.tree_util.tree_map(
                            lambda x: x.reshape((No * Po,) + x.shape[2:]),
                            o)
                        if recurrent:
                            h_in = jax.tree_util.tree_map(
                                lambda h: h[a:b].reshape((No * Po,)
                                                         + h.shape[2:]),
                                opp_hidden)
                            out_o = dict(apply_fn(pg, flat, h_in))
                            nh = out_o.pop('hidden')
                            opp_hidden = jax.tree_util.tree_map(
                                lambda h, x: h.at[a:b].set(
                                    x.reshape((No, Po) + x.shape[1:])),
                                opp_hidden, nh)
                        else:
                            out_o = dict(apply_fn(pg, flat, None))
                        lg = (out_o['policy'].reshape(No, Po, -1)
                              - amask[a:b])
                    else:
                        if recurrent:
                            rows = jnp.arange(b - a)
                            pl = env_mod.turn(state)[a:b]
                            h_in = jax.tree_util.tree_map(
                                lambda h: h[a:b][rows, pl], opp_hidden)
                            out_o = dict(apply_fn(pg, o, h_in))
                            nh = out_o.pop('hidden')
                            opp_hidden = jax.tree_util.tree_map(
                                lambda h, x: h.at[a + rows, pl].set(x),
                                opp_hidden, nh)
                        else:
                            out_o = dict(apply_fn(pg, o, None))
                        lg = out_o['policy'] - amask[a:b]
                    opp_act = opp_act.at[a:b].set(jnp.argmax(lg, axis=-1))
                if simultaneous:
                    P2 = logits.shape[1]
                    is_main = (jnp.arange(P2)[None, :] == seat[:, None])
                else:
                    is_main = env_mod.turn(state) == seat
                actions = jnp.where(is_main, greedy, opp_act)
                nstate = env_mod.step(state, actions)
                done = env_mod.terminal(nstate)
                record = {'done': done, 'seat': seat,
                          'outcome': env_mod.outcome(nstate)}
                nstate = env_mod.auto_reset(nstate, done)
                seat = jnp.where(done,
                                 (seat + 1) % env_mod.NUM_PLAYERS, seat)
                if recurrent:
                    hidden = _reset_hidden_where_done(hidden, done,
                                                      wrapper.module)
                    if opp_hidden is not None:
                        opp_hidden = _reset_hidden_where_done(
                            opp_hidden, done, wrapper.module)
                return (nstate, hidden, opp_hidden, seat, rng), record

            (state, hidden, opp_hidden, seat, rng), records = jax.lax.scan(
                body, (state, hidden, opp_hidden, seat, rng), None,
                length=chunk_steps)
            return state, hidden, opp_hidden, seat, rng, records

        self._rollout = rollout

    # results arrive one dispatch late: Learner.feed_results must use the
    # dispatch-time epoch for attribution
    pipelined = True

    def _dispatch(self):
        """Dispatch a chunk + its packed (done, seat, outcome) fetchable."""
        (self.state, self.hidden, self.opp_hidden, self.seat, self.rng,
         records) = \
            self._rollout(self.wrapper.params, tuple(self._opp_params),
                          self.state, self.hidden, self.opp_hidden,
                          self.seat, self.rng)
        self.dispatches += 1
        records = dict(records)
        if self._pack is None:
            self._pack = _RecordPacker(records)
        return self._pack.pack(records)

    def step(self) -> List[dict]:
        """One compiled chunk; returns finished eval result records (the
        same shape Learner.feed_results consumes from BatchedEvaluator).
        Pipelined one chunk deep like DeviceGenerator: the next chunk is
        enqueued before the previous one's outcome arrays are fetched (as
        ONE packed array — one blocking transfer per chunk)."""
        if self._pending is None:
            self._pending = self._dispatch()
        pack, self._pending = self._pending, self._dispatch()
        return self._collect(self._pack.unpack(pack))

    def drain(self) -> List[dict]:
        """Collect the in-flight speculative chunk at loop shutdown."""
        if self._pending is None:
            return []
        pack, self._pending = self._pending, None
        return self._collect(self._pack.unpack(pack))

    def _collect(self, rec) -> List[dict]:
        done, seats, outcomes = rec['done'], rec['seat'], rec['outcome']
        players = list(range(self.env_mod.NUM_PLAYERS))
        results: List[dict] = []
        for k, i in zip(*np.nonzero(done)):
            seat = int(seats[k, i])
            results.append({
                'args': {'role': 'e', 'player': [seat],
                         'model_id': {p: (0 if p == seat else -1)
                                      for p in players}},
                'opponent': self._env_opp[i],
                'result': {p: float(outcomes[k, i, p]) for p in players},
            })
        return results


# ---------------------------------------------------------------------------
# device actor backend (generation.backend: device): a gather that OWNS an
# accelerator serves ledger tasks with fused on-device rollouts instead of a
# worker fleet. One compiled program plays every pairing the learner
# stamps — self-play, league PFSP opponents, rating matches — by stacking
# up to ``device_actor_slots`` parameter sets as pytree leaves and
# selecting each seat's logits by a per-(lane, seat) slot index, so a new
# opponent mix is a new params UPLOAD, never a retrace.

# per-seat policies inside the compiled ply (device arrays, not python):
#   SAMPLE  — sample the seat's slot policy (generation 'g' seats)
#   GREEDY  — argmax the seat's slot policy (evaluation model seats,
#             reference agent.py Agent at temperature 0)
#   UNIFORM — uniform over legal actions (mid-0 / 'random' seats; matches
#             RandomModel + masked_sample over a zero policy)
#   FIRST   — first legal action (Agent(RandomModel): argmax of zeros-mask)
#   RULEBASE— the env twin's vectorized ``greedy_action`` heuristic
MODE_SAMPLE, MODE_GREEDY, MODE_UNIFORM, MODE_FIRST, MODE_RULEBASE = range(5)


class Divergence(Exception):
    """A device-played action disagrees with the host sampling contract
    (float-boundary collision between the f32 on-device inverse-CDF and the
    f64 host cumsum); the episode reruns on the host path."""


def resolve_record_mode(env_mod, recurrent: bool, requested: str = '') -> str:
    """Resolve the device-actor record mode for an env twin.

    'strict' — device episodes are verified against the host sampling
    contract at splice time and uploaded BYTE-IDENTICAL to worker/engine
    records (divergent lanes rerun on the host); requires the env to be
    deterministic given the action sequence (``RNG_COMPAT == 'strict'``),
    turn-based, and the model non-recurrent (a hidden-state chain cannot be
    recomputed as one batched call). 'device' — episodes are spliced from
    the on-device trajectory and stamped ``record_version: 1``; never
    silently divergent. '' auto-selects strict whenever legal."""
    compat = str(getattr(env_mod, 'RNG_COMPAT', 'device'))
    simultaneous = bool(getattr(env_mod, 'SIMULTANEOUS', False))
    strict_ok = compat == 'strict' and not recurrent and not simultaneous
    if requested == 'strict':
        if not strict_ok:
            raise ValueError(
                'device_actor_record=strict requires a turn-based env twin '
                "with RNG_COMPAT == 'strict' and a non-recurrent model "
                '(got compat=%r, recurrent=%s, simultaneous=%s)'
                % (compat, recurrent, simultaneous))
        return 'strict'
    if requested == 'device':
        return 'device'
    return 'strict' if strict_ok else 'device'


def _tree_where(cond, a, b):
    """Per-lane select over a state pytree (cond broadcast to each leaf)."""
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(
            cond.reshape((-1,) + (1,) * (x.ndim - 1)), x, y), a, b)


def _u_pick(weights, legal, u):
    """Inverse-CDF draw matching generation.masked_sample's searchsorted:
    the first legal action whose inclusive cumulative weight exceeds
    ``u * total``; the last legal action when rounding pushes u past the
    end. Rows whose weights are all zero (frozen lanes) fall through to
    the last-legal clamp and are discarded by the caller's live mask."""
    legalb = legal > 0
    c = jnp.cumsum(weights * legal, axis=-1)
    total = c[:, -1:]
    cond = (c > u[:, None] * total) & legalb
    acts = legal.shape[-1]
    last_legal = (acts - 1) - jnp.argmax(legalb[:, ::-1], axis=-1)
    return jnp.where(cond.any(axis=-1), jnp.argmax(cond, axis=-1),
                     last_legal).astype(jnp.int32)


class DeviceActorEngine:
    """Fused Anakin-style rollout engine behind the gather task loop.

    ``run_block`` takes a list of server-stamped ledger tasks ('g' episode
    and 'e' evaluation assignments, one lane each), plays them ALL inside
    chunked invocations of ONE jitted scan — inference for every slot's
    params, per-seat action modes, transition, termination — and splices
    the finished lanes into standard upload payloads. Lanes freeze when
    their episode ends (block-synchronous; no auto-reset), so a task's
    record is exactly one episode, attributable to its task_id.

    Tasks the program cannot express (unknown opponents, slot overflow
    beyond the compiled stack, missing sample keys in strict mode) are
    returned for the caller's host fallback instead of forcing a retrace.
    """

    def __init__(self, env_mod, vault, host_env, args: Dict[str, Any],
                 n_envs: int = 64, chunk_steps: int = 16, slots: int = 2,
                 record_mode: str = '', seed: int = 0):
        self.args = args
        self.vault = vault
        self.host_env = host_env
        self.n_envs = int(n_envs)
        self.chunk_steps = int(chunk_steps)
        self.slots = max(1, int(slots))
        self.seed = int(seed)
        self.env_mod = env_mod
        self.num_players = int(env_mod.NUM_PLAYERS)
        self.simultaneous = bool(getattr(env_mod, 'SIMULTANEOUS', False))
        self.max_steps = int(getattr(env_mod, 'MAX_STEPS', 1000))
        self._has_rule = hasattr(env_mod, 'greedy_action')
        # recurrence is architecture-structural: the env's registered net
        # decides it before any snapshot arrives
        self.recurrent = hasattr(host_env.net(), 'init_hidden')
        self.record_mode = resolve_record_mode(env_mod, self.recurrent,
                                               str(record_mode or ''))
        # streaming ingest sink (set by DeviceActorGather when the
        # streaming: block is on): lanes in 'device' record mode flush
        # fixed-T windows through it mid-block instead of holding the
        # finished episode. 'strict' lanes never stream — their byte
        # contract is only proven by the END-of-episode host replay.
        self.emit = None
        self.blocks = 0
        self._built = None          # wrapper the program was traced from
        self._rollout = None
        self._pack = None
        self._stack_key = None
        self._stacked = None
        self._gen = None            # lazy host Generator for strict reruns
        self._m_plies = telemetry.counter('device_actor_plies_total')
        self._m_episodes = telemetry.counter('device_actor_episodes_total')
        self._m_results = telemetry.counter('device_actor_results_total')
        self._m_divergence = telemetry.counter(
            'device_actor_divergence_total')
        self._m_chunk = telemetry.REGISTRY.histogram(
            'device_actor_chunk_seconds')
        self._m_fill = telemetry.gauge('device_actor_fill_ratio')
        telemetry.install_jax_monitoring()

    # -- task classification ----------------------------------------------

    def _classify(self, task) -> Dict[str, Any]:
        """Map one ledger task onto per-seat (mode, slot-mid) vectors, or
        None when the compiled program cannot express it (host fallback)."""
        role = (task or {}).get('role')
        P = self.num_players
        raw = (task or {}).get('model_id') or {}
        mids = {p: int(raw.get(p, -1)) for p in range(P)}
        modes = [MODE_FIRST] * P
        slot_mids = []
        if role == 'g':
            if self.record_mode == 'strict' \
                    and task.get('sample_key') is None:
                return None     # no server key => no byte contract to keep
            for p in range(P):
                if mids[p] >= 1:
                    modes[p] = MODE_SAMPLE
                    slot_mids.append(mids[p])
                elif mids[p] == 0:
                    modes[p] = MODE_UNIFORM
                else:
                    return None
            return {'task': task, 'kind': 'episode', 'modes': modes,
                    'mids': mids, 'slot_mids': slot_mids, 'opponent': None}
        if role == 'e':
            seat = int(task['player'][0])
            opponent = task.get('opponent')
            if not opponent:
                opponents = (self.args.get('eval') or {}).get('opponent', [])
                skey = task.get('sample_key')
                if opponents and skey is not None:
                    # the Evaluator's namespace-2 pool draw, replicated so
                    # the opponent identity matches a host re-issue exactly
                    seq = sample_seed(self.args.get('seed', 0),
                                      (2, int(skey)), 0)
                    opponent = opponents[int(
                        np.random.default_rng(seq).integers(len(opponents)))]
                elif opponents:
                    return None   # unkeyed pool draw: host decides
                else:
                    opponent = 'random'
            for p in range(P):
                if p == seat:
                    modes[p] = MODE_GREEDY if mids[p] >= 1 else MODE_FIRST
                    if mids[p] >= 1:
                        slot_mids.append(mids[p])
                elif mids[p] >= 1:
                    modes[p] = MODE_GREEDY
                    slot_mids.append(mids[p])
                elif opponent == 'random':
                    modes[p] = MODE_UNIFORM
                elif str(opponent).startswith('rulebase') and self._has_rule:
                    modes[p] = MODE_RULEBASE
                else:
                    return None   # checkpoint/serving opponents: host path
            return {'task': task, 'kind': 'result', 'modes': modes,
                    'mids': mids, 'slot_mids': slot_mids,
                    'opponent': opponent}
        return None

    # -- compiled program ---------------------------------------------------

    def _build(self, wrapper):
        """Trace the one chunk program from the first materialized wrapper.
        Everything that varies per block — the stacked params, the per-seat
        slot/mode tables, the precomputed sampling draws, liveness — is a
        program INPUT of fixed shape, so league pairings and model updates
        never retrace."""
        assert hasattr(wrapper.module, 'init_hidden') == self.recurrent, \
            'env net() and snapshot disagree on recurrence'
        env_mod, M = self.env_mod, self.slots
        N, P = self.n_envs, self.num_players
        simultaneous, recurrent = self.simultaneous, self.recurrent
        strict = self.record_mode == 'strict'
        full = self.record_mode == 'device'
        has_rule, has_rew = self._has_rule, hasattr(env_mod, 'rewards')
        apply_fn = wrapper.module.apply

        def chunk(stacked, state, hidden, u_tab, seat_slot, seat_mode,
                  live, t, rng):
            def body(carry, _):
                state, hidden, live, t, rng = carry
                rows = jnp.arange(N)
                per_slot = []
                for m in range(M):
                    pm = jax.tree_util.tree_map(lambda x: x[m], stacked)
                    per_slot.append(_ply_inference(
                        env_mod, apply_fn, recurrent, simultaneous,
                        pm, state, hidden))
                obs, amask = per_slot[0][0], per_slot[0][2]
                legal = (amask <= 0).astype(jnp.float32)
                logitsM = jnp.stack([s[1] for s in per_slot])
                valM = None
                if per_slot[0][4].get('value') is not None:
                    valM = jnp.stack(
                        [s[4]['value'].reshape((N, P, -1))
                         if simultaneous else s[4]['value']
                         for s in per_slot])
                rng, k1, k2, k3 = jax.random.split(rng, 4)
                if simultaneous:
                    cols = jnp.arange(P)[None, :]
                    rows2 = rows[:, None]
                    logits = logitsM[seat_slot, rows2, cols]   # (N, P, A)
                    value = (valM[seat_slot, rows2, cols]
                             if valM is not None else None)
                    mode = seat_mode
                    a_sample = jax.random.categorical(k1, logits)
                    a_unif = jax.random.categorical(k2, -amask)
                else:
                    player = env_mod.turn(state)               # (N,)
                    slot_act = seat_slot[rows, player]
                    logits = logitsM[slot_act, rows]           # (N, A)
                    value = (valM[slot_act, rows]
                             if valM is not None else None)
                    mode = seat_mode[rows, player]
                    if strict:
                        idx = jnp.minimum(t, u_tab.shape[1] - 1)
                        u = u_tab[rows, idx]
                        probs_u = jax.nn.softmax(logits, axis=-1)
                        a_sample = _u_pick(probs_u, legal, u)
                        a_unif = _u_pick(jnp.ones_like(legal), legal, u)
                    else:
                        a_sample = jax.random.categorical(k1, logits)
                        a_unif = jax.random.categorical(k2, -amask)
                    if recurrent:
                        hidden = jax.tree_util.tree_map(
                            lambda *hs: jnp.stack(hs)[slot_act, rows],
                            *[s[3] for s in per_slot])
                if simultaneous and recurrent:
                    cols = jnp.arange(P)[None, :]
                    rows2 = rows[:, None]
                    hidden = jax.tree_util.tree_map(
                        lambda *hs: jnp.stack(hs)[seat_slot, rows2, cols],
                        *[s[3] for s in per_slot])
                probs = jax.nn.softmax(logits, axis=-1)
                a_greedy = jnp.argmax(logits, axis=-1)
                a_first = jnp.argmax(legal, axis=-1)
                action = a_first
                action = jnp.where(mode == MODE_SAMPLE, a_sample, action)
                action = jnp.where(mode == MODE_GREEDY, a_greedy, action)
                action = jnp.where(mode == MODE_UNIFORM, a_unif, action)
                if has_rule:
                    a_rule = env_mod.greedy_action(state, k3)
                    action = jnp.where(mode == MODE_RULEBASE, a_rule, action)
                action = action.astype(jnp.int32)
                sel = jnp.take_along_axis(probs, action[..., None],
                                          axis=-1)[..., 0]
                gate = live[:, None] if simultaneous else live
                action = jnp.where(gate, action, 0)
                nstate = env_mod.step(state, action)
                nstate = _tree_where(live, nstate, state)     # freeze done
                done_now = env_mod.terminal(nstate) & live
                record = {'action': action, 'live': live, 'done': done_now,
                          'outcome': env_mod.outcome(nstate)}
                if simultaneous:
                    record['acting'] = env_mod.acting(state)
                else:
                    record['player'] = env_mod.turn(state)
                if full:
                    record['obs'] = obs
                    record['prob'] = sel
                    record['amask'] = amask
                    if value is not None:
                        record['value'] = value
                    if has_rew:
                        record['reward'] = env_mod.rewards(nstate)
                t = t + live.astype(jnp.int32)
                live = live & ~done_now
                return (nstate, hidden, live, t, rng), record

            (state, hidden, live, t, rng), records = jax.lax.scan(
                body, (state, hidden, live, t, rng), None,
                length=self.chunk_steps)
            return state, hidden, live, t, rng, dict(records)

        self._rollout = jax.jit(chunk)
        self._built = wrapper

    def _stack_params(self, assign: Dict[int, int]):
        """Stack each slot's params as pytree leaves (unused slots padded
        with the first real params so the tree is dense). Cached on the
        slot->mid map: re-serving the same pairing costs nothing."""
        by_slot = [None] * self.slots
        for mid, slot in assign.items():
            by_slot[slot] = mid
        key = tuple(by_slot)
        if key == self._stack_key:
            return self._stacked
        pad = self.vault.params(next(iter(assign)))
        trees = [self.vault.params(mid) if mid is not None else pad
                 for mid in by_slot]
        self._stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees)
        self._stack_key = key
        return self._stacked

    # -- block execution ----------------------------------------------------

    def run_block(self, tasks):
        """Serve one block of ledger tasks on device.

        Returns ``(uploads, deferred)``: uploads are ``(kind, payload)``
        pairs ready for the gather's upload box (payload None for a lane
        that failed — the ledger's deadline re-issues it); deferred tasks
        need the host fallback path."""
        deferred, plan = [], []
        for task in tasks:
            if task.get('role') == 'idle':
                continue
            cls = self._classify(task)
            (plan if cls is not None else deferred).append(cls or task)
        if len(plan) > self.n_envs:
            # more tasks than lanes: overflow rides the host fallback
            deferred.extend(cls['task'] for cls in plan[self.n_envs:])
            plan = plan[:self.n_envs]
        if not plan:
            return [], deferred

        # slot planning: league.plan_slots admits tasks in order until the
        # compiled stack is full; overflow rides the host fallback
        from .league import plan_slots
        assign, admitted = plan_slots(
            [cls['slot_mids'] for cls in plan], self.slots)
        kept = []
        for cls, ok in zip(plan, admitted):
            (kept if ok else deferred).append(cls if ok else cls['task'])
        plan = kept
        if not assign or not plan:
            # nothing slot-backed to run (epoch 0, or pure overflow):
            # the program needs at least one real params tree
            deferred.extend(cls['task'] for cls in plan)
            return [], deferred

        if self._rollout is None:
            self._build(self.vault.model(next(iter(assign))))
        stacked = self._stack_params(assign)

        N, P = self.n_envs, self.num_players
        strict = self.record_mode == 'strict'
        seat_slot = np.zeros((N, P), np.int32)
        seat_mode = np.full((N, P), MODE_FIRST, np.int32)
        live = np.zeros((N,), bool)
        u_len = self.max_steps if strict else 1
        u_tab = np.zeros((N, u_len), np.float32)
        base_seed = self.args.get('seed', 0)
        for i, cls in enumerate(plan):
            live[i] = True
            for p in range(P):
                seat_mode[i, p] = cls['modes'][p]
                mid = cls['mids'][p]
                if cls['modes'][p] in (MODE_SAMPLE, MODE_GREEDY):
                    seat_slot[i, p] = assign[mid]
            if strict:
                skey = cls['task'].get('sample_key')
                if cls['kind'] == 'episode':
                    ekey, d0 = (0, int(skey)), 0
                else:
                    # eval lanes carry no byte contract; draw 0 named the
                    # opponent, so per-ply draws continue the same stream
                    ekey, d0 = (2, int(skey if skey is not None else i)), 1
                for tt in range(u_len):
                    seq = sample_seed(base_seed, ekey, d0 + tt)
                    u_tab[i, tt] = np.random.default_rng(seq).random()

        block_seed = self.seed + 7919 * self.blocks
        self.blocks += 1
        try:
            state = self.env_mod.init_state(N, block_seed)
        except TypeError:
            state = self.env_mod.init_state(N)
        hidden = (self._built.module.init_hidden((N, P))
                  if self.recurrent else None)
        live_d = jnp.asarray(live)
        t_d = jnp.zeros((N,), jnp.int32)
        rng = jax.random.PRNGKey(block_seed)
        u_d = jnp.asarray(u_tab)
        slot_d = jnp.asarray(seat_slot)
        mode_d = jnp.asarray(seat_mode)

        # streaming ingest: per-lane window buffers, flushed through
        # self.emit as each fixed-T window fills (device record mode only:
        # these records are attempt-scoped, so every chunk is stamped and
        # keyed by task_id learner-side)
        stream = None
        if self.emit is not None and not strict \
                and (self.args.get('streaming') or {}).get('enabled'):
            stream = {
                'T': int((self.args.get('streaming') or {})
                         .get('chunk_steps', 32)),
                'lanes': [dict(moments=[], flushed=0, chunk=0, done=False)
                          if cls['kind'] == 'episode' else None
                          for cls in plan],
            }

        chunks, plies_run = [], 0
        n_chunks_cap = max(2, -(-self.max_steps // self.chunk_steps) + 2)
        for _ in range(n_chunks_cap):
            t0 = time.perf_counter()
            state, hidden, live_d, t_d, rng, records = self._rollout(
                stacked, state, hidden, u_d, slot_d, mode_d,
                live_d, t_d, rng)
            if self._pack is None:
                self._pack = _RecordPacker(records)
            rec = self._pack.unpack(self._pack.pack(records))
            self._m_chunk.observe(time.perf_counter() - t0)
            chunks.append(rec)
            plies_run += int(rec['live'].sum())
            if stream is not None:
                self._stream_lanes(plan, rec, stream)
            if not (rec['live'][-1] & ~rec['done'][-1]).any():
                break
        if stream is not None:
            # block cap reached: flush the unfinished lanes' partial tails
            # as non-final windows (the gather's clean-exit flush ships
            # them) — the learner trains on the exposed prefix while the
            # deadline re-issue regenerates the episode under a new task
            for i, st in enumerate(stream['lanes']):
                if st is None or st['done'] \
                        or len(st['moments']) <= st['flushed']:
                    continue
                self._emit_lane_chunk(plan[i], st, final=False)
        self._m_plies.inc(plies_run)
        scheduled = len(chunks) * self.chunk_steps * max(1, len(plan))
        self._m_fill.set(plies_run / max(1, scheduled))
        # observations can be dict pytrees (e.g. Geister) — concat per leaf
        rec = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *chunks)

        uploads = []
        for i, cls in enumerate(plan):
            if stream is not None and stream['lanes'][i] is not None:
                # every window of this lane (final chunk included, when it
                # finished) already rode the emit sink; an unfinished lane
                # re-issues on deadline like a failed one
                continue
            ks = np.nonzero(rec['live'][:, i])[0]
            finished = len(ks) > 0 and bool(rec['done'][ks[-1], i])
            payload = None
            if finished:
                try:
                    if cls['kind'] == 'result':
                        payload = self._result_record(cls, i, rec, ks)
                    elif strict:
                        payload = self._splice_strict(cls, i, rec, ks)
                    else:
                        payload = self._splice_device(cls, i, rec, ks)
                except Exception:
                    import traceback
                    traceback.print_exc()
                    payload = None
            uploads.append((cls['kind'], payload))
        if self.blocks == 1:
            telemetry.mark_steady_state(note='device actor warmup complete')
        return uploads, deferred

    # -- streaming ----------------------------------------------------------

    def _stream_lanes(self, plan, rec, stream):
        """Fold one dispatch's records into the per-lane chunk streams,
        flushing every filled fixed-T window through the emit sink. A lane
        whose episode terminated emits its final chunk (tail + outcome)
        and stops accumulating."""
        players = list(range(self.num_players))
        for i, cls in enumerate(plan):
            st = stream['lanes'][i]
            if st is None or st['done']:
                continue
            try:
                ks = np.nonzero(rec['live'][:, i])[0]
                for k in ks:
                    if self.simultaneous:
                        st['moments'].append(
                            self._lane_moment_simultaneous(
                                rec, k, i, players))
                    else:
                        st['moments'].append(
                            self._lane_moment_turn_based(rec, k, i, players))
                while len(st['moments']) - st['flushed'] >= stream['T']:
                    self._emit_lane_chunk(cls, st, final=False,
                                          upto=st['flushed'] + stream['T'])
                if len(ks) > 0 and bool(rec['done'][ks[-1], i]):
                    outcome = {p: float(rec['outcome'][ks[-1], i, p])
                               for p in players}
                    self._emit_lane_chunk(cls, st, final=True,
                                          outcome=outcome)
                    st['done'] = True
                    telemetry.counter('episodes_generated_total').inc()
                    telemetry.counter('generation_steps_total').inc(
                        len(st['moments']))
                    self._m_episodes.inc()
            except Exception:
                import traceback
                traceback.print_exc()
                # stop streaming this lane; the already-emitted prefix
                # stays usable and the deadline re-issues the task
                st['done'] = True
                telemetry.counter('worker_task_failures_total').inc()

    def _emit_lane_chunk(self, cls, st, final, outcome=None, upto=None):
        """Ship one window of a streamed lane, stamped ``record_version``
        (device records carry no host byte contract; the assembler keys
        stamped streams by task_id so attempts never merge)."""
        upto = len(st['moments']) if upto is None else upto
        window = st['moments'][st['flushed']:upto]
        chunk = build_chunk(cls['task'], st['chunk'], st['flushed'], window,
                            self.args, final=final, outcome=outcome)
        chunk['record_version'] = 1
        st['flushed'] = upto
        st['chunk'] += 1
        self.emit(chunk)

    # -- splicing -----------------------------------------------------------

    def _result_record(self, cls, lane, rec, ks):
        """Evaluation lanes upload outcome-only records (the Evaluator's
        ``{'args', 'opponent', 'result'}`` contract)."""
        k = ks[-1]
        players = list(range(self.num_players))
        self._m_results.inc()
        return {'args': cls['task'], 'opponent': cls['opponent'],
                'result': {p: float(rec['outcome'][k, lane, p])
                           for p in players}}

    def _splice_device(self, cls, lane, rec, ks):
        """Assemble a ``record_version: 1`` episode from the on-device
        trajectory (the DeviceGenerator moment layout, one lane)."""
        task = cls['task']
        players = list(range(self.num_players))
        moments = []
        for k in ks:
            if self.simultaneous:
                moments.append(self._lane_moment_simultaneous(
                    rec, k, lane, players))
            else:
                moments.append(self._lane_moment_turn_based(
                    rec, k, lane, players))
        k = ks[-1]
        outcome = {p: float(rec['outcome'][k, lane, p]) for p in players}
        for p in players:
            ret = 0.0
            for t in range(len(moments) - 1, -1, -1):
                ret = ((moments[t]['reward'][p] or 0)
                       + self.args['gamma'] * ret)
                moments[t]['return'][p] = ret
        telemetry.counter('episodes_generated_total').inc()
        telemetry.counter('generation_steps_total').inc(len(moments))
        self._m_episodes.inc()
        return {
            'args': task, 'steps': len(moments), 'outcome': outcome,
            'moment': compress_moments(
                moments, self.args['compress_steps'],
                level=self.args.get('compress_level', 9)),
            # records from this path follow the device rng contract, not
            # the host byte contract: stamped, never silently divergent
            'record_version': 1,
        }

    def _lane_moment_turn_based(self, rec, k, i, players):
        player = int(rec['player'][k, i])
        moment = _blank(players)
        moment['observation'][player] = map_structure(
            lambda v: v[k, i], rec['obs'])
        moment['selected_prob'][player] = float(rec['prob'][k, i])
        moment['action_mask'][player] = rec['amask'][k, i]
        moment['action'][player] = int(rec['action'][k, i])
        if rec.get('value') is not None:
            moment['value'][player] = rec['value'][k, i]
        moment['reward'] = self._lane_rewards(rec, k, i, players)
        moment['turn'] = [player]
        return moment

    def _lane_moment_simultaneous(self, rec, k, i, players):
        moment = _blank(players)
        turn_players = []
        for p in players:
            if not rec['acting'][k, i, p]:
                continue
            turn_players.append(p)
            moment['observation'][p] = map_structure(
                lambda v: v[k, i, p], rec['obs'])
            moment['selected_prob'][p] = float(rec['prob'][k, i, p])
            moment['action_mask'][p] = rec['amask'][k, i, p]
            moment['action'][p] = int(rec['action'][k, i, p])
            if rec.get('value') is not None:
                moment['value'][p] = rec['value'][k, i, p]
        moment['reward'] = self._lane_rewards(rec, k, i, players)
        moment['turn'] = turn_players
        return moment

    def _lane_rewards(self, rec, k, i, players):
        if rec.get('reward') is None:
            return {p: None for p in players}
        return {p: float(rec['reward'][k, i, p]) for p in players}

    def _splice_strict(self, cls, lane, rec, ks):
        """Replay the lane's device actions through the HOST env + sampling
        contract and verify every draw. A verified lane's moments are, by
        construction, the ones the host Generator would have produced —
        the record is byte-identical and carries no version stamp. Any
        mismatch (f32/f64 cumsum boundary collision) falls back to a full
        host Generator rerun: correctness is unconditional, the device
        speedup is probabilistic."""
        task = cls['task']
        try:
            episode = self._replay_strict(task, lane, rec, ks)
        except Divergence:
            episode = None
        if episode is None:
            self._m_divergence.inc()
            episode = self._host_rerun(task)
        else:
            self._m_episodes.inc()
        return episode

    def _replay_strict(self, task, lane, rec, ks):
        env = self.host_env
        args = self.args
        base_seed = args.get('seed', 0)
        episode_key = (0, int(task['sample_key']))
        seed_env_rng(env, base_seed, episode_key)
        if env.reset():
            raise Divergence
        device_actions = [int(a) for a in rec['action'][ks, lane]]
        plies = []      # [player, obs, legal, seed_seq, reward, action]
        draws = 0
        for a_dev in device_actions:
            if env.terminal():
                raise Divergence             # device episode ran long
            turn_players = env.turns()
            if len(turn_players) != 1:
                raise Divergence             # strict is turn-based only
            p = turn_players[0]
            obs = env.observation(p)
            seed_seq = sample_seed(base_seed, episode_key, draws)
            draws += 1
            legal = env.legal_actions(p)
            if a_dev not in legal:
                raise Divergence
            if env.step({p: a_dev}):
                raise Divergence
            plies.append([p, obs, legal, seed_seq, env.reward(), a_dev])
        if not env.terminal():
            raise Divergence                 # device episode ended early

        # batched recompute per distinct model, chunked to the SAME bucket
        # the Generator's per-ply bucketed_inference dispatches (bucket 8):
        # rows within one bucket are row-independent, but the same row CAN
        # stray across bucket SIZES on some device meshes, so byte parity
        # requires never escalating to a larger bucket here
        models = self.vault.obtain(dict(task['model_id']))
        outputs = [None] * len(plies)
        groups: Dict[int, list] = {}
        for j, ply in enumerate(plies):
            groups.setdefault(id(models[ply[0]]), []).append(j)
        with telemetry.expected_compile('device-actor strict recompute'):
            for idxs in groups.values():
                model = models[plies[idxs[0]][0]]
                if not hasattr(model, 'batch_inference'):
                    for j in idxs:           # RandomModel: zero outputs
                        outputs[j] = bucketed_inference(model, plies[j][1])
                    continue
                for lo in range(0, len(idxs), 8):
                    chunk = idxs[lo:lo + 8]
                    obs_b, _ = pad_to_bucket(
                        [plies[j][1] for j in chunk])
                    out = model.batch_inference(obs_b, None)
                    policy = np.asarray(out['policy'])
                    value = (np.asarray(out['value'])
                             if out.get('value') is not None else None)
                    for row, j in enumerate(chunk):
                        outputs[j] = {
                            'policy': policy[row],
                            'value': (value[row]
                                      if value is not None else None)}

        moments = []
        for j, (p, obs, legal, seed_seq, reward, a_dev) in enumerate(plies):
            action, prob, mask = masked_sample(
                outputs[j]['policy'], legal, seed_seq)
            if action != a_dev:
                raise Divergence             # boundary collision: rerun
            moment = _blank_moment(env.players())
            moment['observation'][p] = obs
            moment['value'][p] = outputs[j].get('value')
            moment['selected_prob'][p] = prob
            moment['action_mask'][p] = mask
            moment['action'][p] = action
            for player in env.players():
                moment['reward'][player] = reward.get(player, None)
            moment['turn'] = [p]
            moments.append(moment)
        return _finalize_episode(env, moments, args, task)

    def _host_rerun(self, task):
        """Byte-exact fallback: the standard host Generator replays the
        task from its server-stamped key (same record any worker would
        upload)."""
        if self._gen is None:
            self._gen = Generator(self.host_env, self.args,
                                  namespace=-1)
        models = self.vault.obtain(dict(task['model_id']))
        with telemetry.expected_compile('device-actor host rerun'):
            return self._gen.execute(models, task)
