"""The update step for one solo-layout window of the token game under the
looped trunk, in plain float32 over the plain forward (``ouro.forward``): the
exit-weighted loss that ``handyrl_tpu/ops/losses.py`` ``compute_loss``
composes for a sequence net with a pass axis, both targets V-trace and no
per-ply reward, and a written-out first Adam step.

V-trace's ratios, targets and advantages come ONCE, from the LAST pass (the
policy the actor played and its value), in numpy (``vtrace.vtrace`` through
``trinity_mini_loss.targets``), and carry no gradient. Pass ``t``'s loss a
position is today's three terms on ITS outputs against those shared numbers;
with ``g_t`` the sigmoid of pass t's gate logit the exit distribution is
``p_1 = g_1``, ``p_t = g_t prod_{j<t} (1 - g_j)``, ``p_last = prod_{j<last}
(1 - g_j)``, and the loss ``sum_t p_t l_t - beta H(p)`` summed over the
window's valid positions, ``beta`` 0.1.
"""

import jax
import jax.numpy as jnp

from . import ouro
from .trinity_mini_loss import (ADAM_B1, first_adam_step,  # noqa: F401
                                _policy_logits)
from .trinity_mini_loss import targets as _last_pass_targets

BETA = 0.1      # the weight of H(p): handyrl_tpu/ops/losses.EXIT_ENTROPY_COEF


def targets(outputs, window, lmb):
    """V-trace value targets and the policy's advantages from the LAST
    pass's logits and value, numpy, (T,)."""
    return _last_pass_targets({'logits': outputs['logits'][-1],
                               'value': outputs['value'][-1]}, window, lmb)


def exit_distribution(gate):
    """gate (passes, T), the logits -> p (passes, T), written out pass by
    pass, and its entropy (T,)."""
    g = jax.nn.sigmoid(gate)
    left = jnp.ones_like(g[0])
    p = []
    for t in range(gate.shape[0] - 1):
        p.append(g[t] * left)
        left = left * (1 - g[t])
    p = jnp.stack(p + [left])
    return p, -(p * jnp.log(jnp.maximum(p, 1e-38))).sum(axis=0)


def loss_of_outputs(out, window, value_target, advantage, entropy_coef,
                    entropy_decay, last_pass_only=False):
    """The total loss (a sum over the window's positions) and its terms,
    from the forward's outputs (``logits`` (passes, T, A), ``value`` and
    ``gate`` (passes, T)). ``last_pass_only`` is a negative control: the
    last pass's loss alone, no gate."""
    valid = window['valid']
    decay = 1 - window['progress'] * (1 - entropy_decay)
    p, exit_entropy = exit_distribution(out['gate'])
    if last_pass_only:
        p = jnp.zeros_like(p).at[-1].set(1.0)
        exit_entropy = jnp.zeros_like(exit_entropy)
    terms = dict.fromkeys(('p', 'v', 'ent'), 0.0)
    total = 0.0
    for t in range(p.shape[0]):
        logp = jax.nn.log_softmax(_policy_logits(out['logits'][t], window))
        picked = jnp.take_along_axis(logp, window['action'][:, None], 1)[:, 0]
        entropy = -(jnp.exp(logp) * logp).sum(axis=-1) * valid
        parts = {'p': -(picked * valid * advantage * valid),
                 'v': ((out['value'][t] * valid - value_target) ** 2)
                 * valid / 2,
                 'ent': entropy}
        for key, part in parts.items():
            terms[key] = terms[key] + (p[t] * part).sum()
        total = total + (p[t] * (parts['p'] + parts['v']
                                 - entropy_coef * entropy * decay)).sum()
    terms['exit_ent'] = (exit_entropy * valid).sum()
    terms['total'] = total - BETA * terms['exit_ent']
    return terms['total'], terms


def loss(variables, window, value_target, advantage, cfg, entropy_coef,
         entropy_decay, last_pass_only=False, **forward_args):
    """The same from the parameters: what ``jax.grad`` differentiates."""
    out = ouro.forward(variables, window['ids'], window['first_position'],
                       window['valid'] > 0, cfg, **forward_args)
    return loss_of_outputs(out, window, value_target, advantage,
                           entropy_coef, entropy_decay, last_pass_only)
