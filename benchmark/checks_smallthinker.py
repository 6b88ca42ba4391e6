"""The comparisons that decide ``correct`` for the ``smallthinker``
configuration: on the chip, at published widths, on what the timed path
runs, each against the plain float32 reference (``reference/smallthinker.py``,
``reference/smallthinker_loss.py``) at ``highest`` matmul precision on the
very weights the learner starts from.

``forward_check``   the learner's ``sequence`` (bfloat16 activations, the
                    routing and the sort plan taken before attention, the
                    grouped expert product) and its ``policy_logits`` over
                    ONE seeded window of ``forward_positions`` (8,192)
                    positions: logits, value, and
                    ``routing_agreement_share``, the share of the router's
                    (position, choice) pairs that are the reference's, a
                    LOWER limit.
``rollout_check``   the actor's ``__call__`` through its cache of two
                    lengths (bfloat16 parameters, every held expert on the
                    ply's few rows), driven by the program's own
                    ``rollout_chunk`` for ``rollout_plies`` plies of
                    ``rollout_envs`` games whose FIRST lengths the check
                    sets (``checks_trinity_mini.first_lengths``): one lane
                    plays the whole run (its circles of 4,096 rows go
                    round), one ends early, one late, one past position
                    4,096. All plies, the plies at positions from the window
                    on (``wrapped_``) and the plies of games begun after a
                    reset (``after_reset_``) are each held to limits.
``step_check``      one update of the program's own step on the cell's batch
                    of ONE seeded 8,192-position window that ends inside its
                    game, the legal set as bits: the loss, the gradient's
                    norm, the gradient and the change leaf by leaf (the
                    small leaves held to limits of their own), ``W_r``
                    unchanged, no row dropped.

What names no net is ``checks_trinity_mini``'s, and since PR 45 that is
everything but this file's ``REFERENCE`` (the two plain modules, the
reference's ``cfg``, the embedding's scale, the parameters' groups), its
``CONTROLS``, the game sums that stay on the device, and the step check's
own rows: ``forward_errors``, ``rollout_compare``, ``rollout_errors`` and
``step_errors`` are that file's functions with this reference handed in.
Statistics are RMS errors relative to the RMS of the reference; each limit
sits in the configuration's ``tolerance`` with the readings it was set from
(``tolerance_smallthinker.py`` reads them and the seven negative controls).
"""

import functools
import time

from . import checks
from . import checks_trinity_mini as shared
from .checks_evabyte import _count, _limit, _verdict
from .checks_trinity_mini import (FORWARD_LIMITS, ROLLOUT_LIMITS,
                                  first_lengths, rollout_records,
                                  seeded_batch, seeded_windows)
from .reference import smallthinker, smallthinker_loss

__all__ = ['FORWARD_LIMITS', 'ROLLOUT_LIMITS', 'STEP_LIMITS', 'CONTROLS',
           'first_lengths', 'rollout_records', 'seeded_batch',
           'seeded_windows']

# beside the limits every trunk's step check holds, the change of the
# readout alone (the head and the value row, 16% of the parameters): every
# position reaches it directly, so its first Adam step is the number of the
# step that depends least on the seed, and the one that tells 8-bit
# parameters from the stated precision on every seed (at batch 1 the
# gradient's own error spreads sixfold over seeds: PERF.md section 2)
STEP_LIMITS = shared.STEP_LIMITS + ('readout_change_err_rel_to_change',)
# the reference's side degraded: the program is compared with a model that
# differs from it by that part. The last two are this architecture's own:
# without them a copy of another expert net's block would pass
CONTROLS = {'one_layer_left_out': {'skip_layer': 2},
            'experts_left_out': {'use_experts': False},
            'window_ignored': {'use_window': False},
            'rotary_on_global_layer': {'rotary_on_global': True},
            'router_after_attention': {'route_after_attention': True},
            'silu_for_relu': {'silu_experts': True}}


def reference_config(config):
    model = config['model']
    cfg = {key: model[key] for key in (
        'head_dim', 'window_size', 'rope_theta', 'norm_eps',
        'experts_per_token')}
    cfg['param_scale'] = model.get('param_scale', 1.0)
    cfg['layer_types'] = tuple(model['layer_types'])
    cfg['experts_held'] = tuple(model['experts_held'])
    return cfg


def group_of(path):
    """A parameter's group, by its name in the tree."""
    name = path[-1]
    if name in ('wq', 'wk', 'wv', 'wo'):
        return 'attention'
    if name.startswith('experts_'):
        return 'experts'
    if name == 'router':
        return 'router'
    if name.startswith('norm'):
        return 'norms'
    return 'embed' if name == 'embed' else 'readout'


REFERENCE = shared.Reference(
    net=smallthinker, loss=smallthinker_loss, config=reference_config,
    embed_scale=lambda cfg, _width: 1.0 / cfg['param_scale'],
    groups=('attention', 'experts', 'router', 'norms', 'embed', 'readout'),
    group_of=group_of)


@functools.lru_cache(maxsize=None)
def _game_sums():
    """One game and seat on the device: the squared errors of its plies'
    logits and values and the reference's squared logits, summed over all
    plies, over those at positions from ``window`` on and (``after_reset``)
    over all of them again. A game's 37,984 logits a ply stay where the
    reference made them: fetching them is 1.2 GB a game."""
    import jax
    import jax.numpy as jnp

    def sums(got, logits, value, window, after_reset):
        n = got.shape[0]
        rows = {'d_logit': jnp.square(got[:, 1:] - logits[:n]).sum(axis=1),
                'd_value': jnp.square(got[:, 0] - value[:n]),
                'logit': jnp.square(logits[:n]).sum(axis=1),
                'plies': jnp.ones((n,), jnp.float32)}
        ply = jnp.arange(n)
        parts = {'': ply >= 0, 'wrapped_': ply >= window,
                 'after_reset_': (ply >= 0) & after_reset}
        return {name: {key: (row * keep).sum() for key, row in rows.items()}
                for name, keep in parts.items()}
    return jax.jit(sums)


def device_game_sums(got, want, window, after_reset):
    """``checks_trinity_mini.host_game_sums``' sums, taken where the
    reference's logits are."""
    import jax.numpy as jnp
    return _game_sums()(jnp.asarray(got), want['logits'], want['value'],
                        jnp.int32(window), jnp.asarray(after_reset))


# the shared comparisons, this net's reference handed in; the step check's
# reference works on 8,192 positions at once: a layer's vector-Jacobian
# product takes 5.7 GB beside the two trees of the parameters' size that the
# step's change and first moment are, so those wait on the host (my chip
# run, PR 43)
OURS = {'ref': REFERENCE, 'game_sums': device_game_sums}
forward_errors = functools.partial(shared.forward_errors, ref=REFERENCE)
rollout_compare = functools.partial(shared.rollout_compare, **OURS)
rollout_errors = functools.partial(shared.rollout_errors, **OURS)
step_errors = functools.partial(shared.step_errors, ref=REFERENCE,
                                park_on_host=True)
forward_check = functools.partial(shared.forward_check, ref=REFERENCE)
rollout_check = functools.partial(shared.rollout_check, **OURS)


def step_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = step_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'step_' + name)]
                for name in STEP_LIMITS]
    window = config['model']['window_size']
    hidden = sum(max(n - window, 0) for n in stats['positions'])
    compared += [
        ['router_moved_max_abs', stats['router_moved_max_abs'], '==', 0.0],
        ['rows_dropped', stats['rows_dropped'], '==', 0.0],
        ['nonfinite', stats['nonfinite'], '==', 0.0],
        ['windows', stats['windows'], '==', int(train_args['batch_size'])],
        # the counters the program's own step hands the host are the
        # batch's: the positions past the attention's window, of the valid
        ['positions_hidden_share', stats['positions_hidden_share'], '==',
         hidden / max(sum(stats['positions']), 1)]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)
