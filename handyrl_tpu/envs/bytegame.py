"""The byte game: a two-seat simultaneous game whose ply is a sequence
position (the host env; envs/jax_bytegame.py is its device twin and holds
the rule's arithmetic, which both share as plain integer functions).

Every ply both seats emit one of ``ids`` ids (320 by default: 256 bytes and
``first_ply_ids`` = 64 further ids that are legal on the first ply only). A seat observes ONE id, computed from both
seats' previous ids. The game's length (log-uniform in [min_steps,
max_steps]) and the salt of its rule are drawn at reset, or given
(``reset({'length': .., 'salt': ..})``: the parity test hands over the
twin's). The outcome is +1 / -1 / 0 by who took more plies; no per-ply
reward. ``env_args``: ``min_steps``, ``max_steps``, ``ids``,
``first_ply_ids``, ``net_name`` (a net of the model registry that takes
integer ids; ``EvaByteNet`` by default) and ``net``, that net's widths (its
defaults are the published ones).
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional

import numpy as np

from ..environment import BaseEnvironment
from .jax_bytegame import (MAX_STEPS, MIN_STEPS, N_ACTIONS, N_BYTES,
                           NUM_PLAYERS, observation_id, ply_winner)


class Environment(BaseEnvironment):

    def __init__(self, args: Optional[dict] = None):
        super().__init__(args)
        self.args = dict(args or {})
        self.min_steps = int(self.args.get('min_steps', MIN_STEPS))
        self.max_steps = int(self.args.get('max_steps', MAX_STEPS))
        self.ids = int(self.args.get('ids', N_ACTIONS))
        # the ids legal on every ply; the first of the others is BOS
        self.always = self.ids - int(self.args.get('first_ply_ids',
                                                   N_ACTIONS - N_BYTES))
        self.reset()

    def reset(self, args: Optional[dict] = None):
        args = args or {}
        lo, hi = self.min_steps, self.max_steps
        if 'length' in args:
            self.length = int(args['length'])
        else:
            drawn = math.exp(math.log(lo) + random.random()
                             * (math.log(hi + 1) - math.log(lo)))
            self.length = min(max(int(drawn), lo), hi)
        self.salt = int(args.get('salt', random.randrange(self.ids)))
        self.steps = 0
        self.last = [-1] * NUM_PLAYERS
        self.score = 0

    def step(self, actions: Dict[int, Optional[int]]):
        self.last = [int(actions[p]) for p in self.players()]
        self.score += ply_winner(self.last[0], self.last[1], self.salt,
                                 self.ids)
        self.steps += 1

    def turns(self) -> List[int]:
        return self.players()

    def terminal(self) -> bool:
        return self.steps >= self.length

    def outcome(self) -> Dict[int, float]:
        sign = float(np.sign(self.score))
        return {0: sign, 1: -sign}

    def legal_actions(self, player: Optional[int] = None) -> List[int]:
        return list(range(self.ids if self.steps == 0 else self.always))

    def players(self) -> List[int]:
        return list(range(NUM_PLAYERS))

    def observation(self, player: Optional[int] = None) -> np.ndarray:
        player = player or 0
        if self.steps == 0:
            return np.asarray(self.always, np.int32)
        return np.asarray(observation_id(
            self.last[player], self.last[1 - player], self.ids), np.int32)

    # -- string codec (network battle mode) ---------------------------------
    def diff_info(self, player: Optional[int] = None) -> str:
        if self.steps == 0:
            return '%d %d' % (self.length, self.salt)
        return '%d %d' % tuple(self.last)

    def update(self, info: str, reset: bool):
        a, b = (int(v) for v in info.split())
        if reset:
            self.reset({'length': a, 'salt': b})
        else:
            self.step({0: a, 1: b})

    def net(self):
        from .. import models
        widths = {key: tuple(value) if isinstance(value, list) else value
                  for key, value in self.args.get('net', {}).items()}
        return models.build(self.args.get('net_name', 'EvaByteNet'), **widths)

    def __str__(self) -> str:
        return 'ply %d of %d, last %s, score %+d' % (
            self.steps, self.length, self.last, self.score)
