"""Pure-JAX twin of the byte game (envs/bytegame.py): N games as arrays.

Two seats move at once. Every ply both emit one of ``ids`` ids (by default
``N_ACTIONS`` = 320: 256 bytes and ``first_ply_ids`` = 64 further ids, which
are legal on a game's first ply only); a seat's observation is ONE int32 id
computed from both seats' previous ids (``BOS``, the first of the further
ids, on the first ply); a ply is a sequence position. A game's length is
drawn at reset, log-uniform in [min_steps, max_steps], with a salt that
decides who takes each ply: seat 0 takes it where ``(a0 - a1 + salt) mod
ids`` lies in (0, ids / 2), seat 1 where it lies above ids / 2. The outcome
is +1 / -1 to the seat that took more plies, 0 / 0 on a tie; no per-ply
reward.

The game's sizes are the env's ``env_args`` (``min_steps``, ``max_steps``,
``ids``, ``first_ply_ids``), so the twin is an object made by
``configured(env_args)`` with the protocol of the other twins
(device_generation.py: ``SIMULTANEOUS``, ``observe`` -> (N, P) ids, ``step``
of (N, P) actions, ``acting``). One game, any id space: a token-level net
plays it over its slice of a vocabulary. A legal set of more than
``MASK_BITS_FROM`` ids is recorded as bits (``MASK_AS_BITS``,
ops/maskbits.py).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

NUM_PLAYERS = 2
N_ACTIONS = 320
N_BYTES = 256
BOS = 256            # what both seats observe on a game's first ply
MIN_STEPS = 2048
MAX_STEPS = 8192
# a float32 mask row of this many ids is 16 KB a position and seat; a wider
# legal set goes into the records, the history and the ring as bits
MASK_BITS_FROM = 4096


class State(NamedTuple):
    key: jnp.ndarray      # (N, 2) per-game PRNG keys
    steps: jnp.ndarray    # (N,) plies played
    length: jnp.ndarray   # (N,) plies this game lasts
    salt: jnp.ndarray     # (N,) the game's rule offset
    last: jnp.ndarray     # (N, P) previous ids; -1 before the first ply
    score: jnp.ndarray    # (N,) plies seat 0 took less plies seat 1 took


def observation_id(own, other, ids=N_ACTIONS):
    """A seat's observation from both seats' previous ids (arrays here,
    plain integers in the host env)."""
    return (other + 17 * own) % ids


def ply_winner(a0, a1, salt, ids=N_ACTIONS):
    """+1 where seat 0 takes the ply, -1 where seat 1 does, else 0 (arrays
    here, plain integers in the host env)."""
    diff = (a0 - a1 + salt) % ids
    half = ids // 2
    return ((diff > 0) & (diff < half)) * 1 - (diff > half) * 1


def draw_length(u, lo, hi):
    """u in [0, 1) -> a length, log-uniform over [lo, hi]."""
    length = jnp.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    return jnp.clip(jnp.floor(length).astype(jnp.int32), lo, hi)


class ByteGame:
    """The twin at one set of sizes."""
    NUM_PLAYERS = NUM_PLAYERS
    SIMULTANEOUS = True
    RNG_COMPAT = 'device'    # lengths and salts come from the device's keys

    def __init__(self, min_steps: int = MIN_STEPS,
                 max_steps: int = MAX_STEPS, ids: int = N_ACTIONS,
                 first_ply_ids: int = N_ACTIONS - N_BYTES):
        assert 1 <= min_steps <= max_steps
        assert 0 <= first_ply_ids < ids
        self.MIN_STEPS, self.MAX_STEPS = int(min_steps), int(max_steps)
        self.N_ACTIONS = int(ids)
        self.N_ALWAYS = int(ids) - int(first_ply_ids)   # legal on every ply
        self.BOS = self.N_ALWAYS
        self.MASK_AS_BITS = self.N_ACTIONS > MASK_BITS_FROM

    def _fresh(self, keys):
        def one(key):
            k_len, k_salt = jax.random.split(key)
            return (draw_length(jax.random.uniform(k_len), self.MIN_STEPS,
                                self.MAX_STEPS),
                    jax.random.randint(k_salt, (), 0, self.N_ACTIONS))
        return jax.vmap(one)(keys)

    def init_state(self, n: int, seed: int = 0) -> State:
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        length, salt = self._fresh(keys)
        return State(key=keys, steps=jnp.zeros((n,), jnp.int32),
                     length=length, salt=salt,
                     last=jnp.full((n, NUM_PLAYERS), -1, jnp.int32),
                     score=jnp.zeros((n,), jnp.int32))

    @staticmethod
    def acting(state: State) -> jnp.ndarray:
        return jnp.ones(state.last.shape, bool)

    @staticmethod
    def terminal(state: State) -> jnp.ndarray:
        return state.steps >= state.length

    def legal_mask(self, state: State) -> jnp.ndarray:
        """(N, P, A) float: every id on the first ply, the first
        ``N_ALWAYS`` afterwards."""
        first = (state.steps == 0)[:, None, None]
        always = (jnp.arange(self.N_ACTIONS) < self.N_ALWAYS)[None, None, :]
        return jnp.broadcast_to(
            first | always, state.last.shape + (self.N_ACTIONS,)
        ).astype(jnp.float32)

    def observe(self, state: State) -> jnp.ndarray:
        """(N, P) int32 ids."""
        own, other = state.last, state.last[:, ::-1]
        return jnp.where(state.steps[:, None] == 0, self.BOS,
                         observation_id(own, other, self.N_ACTIONS)
                         ).astype(jnp.int32)

    @staticmethod
    def outcome(state: State) -> jnp.ndarray:
        sign = jnp.sign(state.score).astype(jnp.float32)
        return jnp.stack([sign, -sign], axis=1)

    def step(self, state: State, actions: jnp.ndarray) -> State:
        actions = actions.astype(jnp.int32)
        return state._replace(
            steps=state.steps + 1, last=actions,
            score=state.score + ply_winner(actions[:, 0], actions[:, 1],
                                           state.salt, self.N_ACTIONS))

    def auto_reset(self, state: State, done: jnp.ndarray) -> State:
        keys = jax.vmap(lambda k: jax.random.split(k)[0])(state.key)
        length, salt = self._fresh(keys)
        n = state.steps.shape[0]
        return State(
            key=keys,
            steps=jnp.where(done, 0, state.steps),
            length=jnp.where(done, length, state.length),
            salt=jnp.where(done, salt, state.salt),
            last=jnp.where(done[:, None], -1, state.last),
            score=jnp.where(done, jnp.zeros((n,), jnp.int32), state.score))


def configured(env_args) -> ByteGame:
    """The twin at the env's own sizes (environment.make_jax_env)."""
    return ByteGame(env_args.get('min_steps', MIN_STEPS),
                    env_args.get('max_steps', MAX_STEPS),
                    env_args.get('ids', N_ACTIONS),
                    env_args.get('first_ply_ids', N_ACTIONS - N_BYTES))
