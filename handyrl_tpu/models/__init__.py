"""Flax model zoo.

Registry maps architecture names to constructors so model snapshots can be
shipped over the wire as (name, flat params) instead of pickled code objects
(the reference pickles whole nn.Modules — train.py:615; we deliberately
don't).
"""

from typing import Callable, Dict

_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    def deco(ctor):
        _REGISTRY[name] = ctor
        return ctor
    return deco


def build(name: str, **kwargs):
    if name not in _REGISTRY:
        # lazily import the built-in model modules, which self-register
        from . import (tictactoe, geister, geese, transformer,  # noqa: F401
                       connect_four, evabyte, trinity, smallthinker,
                       ouro)
    return _REGISTRY[name](**kwargs)


def architecture_name(module) -> str:
    return type(module).__name__
