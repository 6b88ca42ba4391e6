"""Environment protocol and registry.

Host-side plug-in API for games. The protocol mirrors the reference
(`/root/reference/handyrl/environment.py:41-145`): the same 17 methods with the
same semantics, so any HandyRL environment can be carried over with only its
neural net rewritten as a Flax module (exposed via ``net()``).

Environments are plain Python — they never see JAX. The framework's device
code consumes only the numpy arrays they produce (``observation``) and the
integer action spaces they define (``legal_actions``).
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional


# Registry: short name -> module path. Environments can also be referenced by
# a fully-qualified dotted module path (mirrors environment.py:9-36).
ENVS = {
    'TicTacToe': 'handyrl_tpu.envs.tictactoe',
    'ParallelTicTacToe': 'handyrl_tpu.envs.parallel_tictactoe',
    'Geister': 'handyrl_tpu.envs.geister',
    'HungryGeese': 'handyrl_tpu.envs.kaggle.hungry_geese',
    'ConnectX': 'handyrl_tpu.envs.kaggle.connectx',
    'ByteGame': 'handyrl_tpu.envs.bytegame',
}

# Pure-JAX twins: envs re-implemented as jittable array functions for
# fully device-resident rollouts (device_generation.py).
JAX_ENVS = {
    'TicTacToe': 'handyrl_tpu.envs.jax_tictactoe',
    'HungryGeese': 'handyrl_tpu.envs.jax_hungry_geese',
    'Geister': 'handyrl_tpu.envs.jax_geister',
    'ConnectX': 'handyrl_tpu.envs.jax_connectx',
    'ByteGame': 'handyrl_tpu.envs.jax_bytegame',
}


def make_jax_env(env_args: Dict[str, Any]):
    """Return the pure-JAX twin for an env, or None: the twin's module, or,
    where the game's sizes are the env's own ``env_args``, the object its
    ``configured(env_args)`` makes (the same protocol as a module)."""
    name = env_args['env']
    if name not in JAX_ENVS:
        return None
    module = importlib.import_module(JAX_ENVS[name])
    if hasattr(module, 'configured'):
        return module.configured(env_args)
    return module


def _resolve_module(env_args: Dict[str, Any]):
    name = env_args['env']
    return importlib.import_module(ENVS.get(name, name))


def prepare_env(env_args: Dict[str, Any]) -> None:
    """Run a module-level ``prepare()`` hook if the env defines one."""
    module = _resolve_module(env_args)
    if hasattr(module, 'prepare'):
        module.prepare()


def make_env(env_args: Dict[str, Any]) -> 'BaseEnvironment':
    module = _resolve_module(env_args)
    return module.Environment(env_args)


class BaseEnvironment:
    """Base class every game implements.

    Required in all games: ``reset``, ``terminal``, ``outcome``,
    ``legal_actions``, ``observation`` and either ``play`` (turn-based) or a
    custom ``step`` (simultaneous). The network-battle interface
    (``diff_info``/``update``/``action2str``/``str2action``) lets a mirror
    environment be reconstructed from per-step deltas — kept identical to the
    reference so the consistency oracle in tests applies unchanged.
    """

    def __init__(self, args: Optional[Dict[str, Any]] = None):
        pass

    def __str__(self) -> str:
        return ''

    # -- core transitions -------------------------------------------------
    def reset(self, args: Optional[Dict[str, Any]] = None):
        raise NotImplementedError()

    def play(self, action: int, player: Optional[int] = None):
        """Apply one player's action (turn-based games)."""
        raise NotImplementedError()

    def step(self, actions: Dict[int, Optional[int]]):
        """Apply a dict of simultaneous actions; default defers to play()."""
        for player, action in actions.items():
            if action is not None:
                self.play(action, player)

    # -- whose move -------------------------------------------------------
    def turn(self) -> int:
        return 0

    def turns(self) -> List[int]:
        return [self.turn()]

    def observers(self) -> List[int]:
        """Players that should observe (for RNN state) without acting."""
        return []

    # -- termination and scoring -----------------------------------------
    def terminal(self) -> bool:
        raise NotImplementedError()

    def reward(self) -> Dict[int, float]:
        """Immediate per-step rewards (optional)."""
        return {}

    def outcome(self) -> Dict[int, float]:
        raise NotImplementedError()

    # -- action/observation spaces ---------------------------------------
    def legal_actions(self, player: Optional[int] = None) -> List[int]:
        raise NotImplementedError()

    def players(self) -> List[int]:
        return [0]

    def observation(self, player: Optional[int] = None):
        raise NotImplementedError()

    # -- string codec (network battle mode) ------------------------------
    def action2str(self, a: int, player: Optional[int] = None) -> str:
        return str(a)

    def str2action(self, s: str, player: Optional[int] = None) -> int:
        return int(s)

    def diff_info(self, player: Optional[int] = None):
        return ''

    def update(self, info, reset: bool):
        raise NotImplementedError()

    # -- model hook -------------------------------------------------------
    def net(self):
        """Return the Flax module for this game (optional)."""
        raise NotImplementedError()
