"""Flax model zoo.

Registry maps architecture names to constructors so model snapshots can be
shipped over the wire as (name, flat params) instead of pickled code objects
(the reference pickles whole nn.Modules — train.py:615; we deliberately
don't).

What the learner reads off a net beside ``__call__(obs, hidden, train) ->
{'policy', 'value', 'hidden', ...}``, each by ``hasattr``; in brackets what a
net without the name gets. The trunk nets (``evabyte``, ``trinity``,
``smallthinker``, ``ouro``) take most of them from ``models/shell.py``, and
tests/test_models.py holds the four to these signatures.

* ``init_hidden(batch_shape=()) -> hidden``: the state one ``__call__`` hands
  the next (``model.py``, ``device_generation.py``; ``ops/train_step.py``
  scans a window through it unless the net has ``sequence``). [None]
* ``reset_hidden(hidden, done) -> hidden``: what a finished game resets, for
  a state that is a cache kept by counters (``device_generation.py``,
  ``ops/fused_pipeline.py``). [the whole tree is zero-filled]
* ``sequence(ids, first_position, valid, no_grad_prefix=0) -> outputs``: a
  training window as ONE causal forward (``ops/train_step.py``,
  ``ops/losses.py`` ``_sequence_prediction``; ``train.py`` then stores each
  window's ``first_position``). Outputs (B, T, ...): ``policy`` or
  ``policy_features``, ``value``, further heads; with ``exit_gate`` each has
  a LEADING pass axis; ``aux`` is a dict of sums. [the window is scanned]
* ``policy_logits(features) -> logits``: the head over ``policy_features``,
  which the loss takes a block of positions at a time
  (``ops/train_step.py``). [``sequence`` returns ``policy``]
* ``post_update(before, after, aux) -> params``: runs on the parameter trees
  after the optimizer, ``aux`` the ``sequence``'s (``ops/train_step.py``).
* ``attention_key_share(T) -> float``: the share of all (query, key) pairs a
  window of ``T`` multiplies, in every epoch's record (``train.py``) and on
  the ``host_block`` span (``ops/fused_pipeline.py``). The nets whose blocks
  of queries take a span of the keys have it: ``trinity`` and
  ``smallthinker`` (``models/shell.py``, the mean over their layers) and
  ``evabyte`` (its local keys; every block takes all the summaries).
  [the key is not set]
* ``decode_rows(pos) -> (read, held)``: the cache rows one ply of sequences
  at counters ``pos`` reads and those their buffers hold; the ``host_block``
  span carries the sums (``ops/fused_pipeline.py``). The four trunks have
  it, each from the ``attention.Span``s its step hands the decode
  attention: ``models/ouro.py`` (a pass's span), ``models/evabyte.py`` (its
  window's rows and its summaries), ``trinity`` and ``smallthinker``
  (``models/shell.py``: the one span of each layer's circle or buffer).
  [the counters are not set]
* ``epoch_dynamics(sums) -> dict``: record keys of the net's own from the
  epoch's ``diag_*`` sums (``train.py`` ``_epoch_dynamics``).
* ``actor_param_dtype``: what the actor's copy of the parameters is cast to
  (``train.py`` ``_run_fused``). [a float32 copy]
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
