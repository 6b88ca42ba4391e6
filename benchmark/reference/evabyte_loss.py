"""The update step's loss for one solo-layout window of the byte game, in
plain float32 over the plain forward (``evabyte.forward``): what
``handyrl_tpu/ops/losses.py`` ``compute_loss`` composes for a net that
declares ``sequence``, with both targets V-trace and no per-ply reward.

The targets and advantages carry no gradient, so they are computed first, in
numpy (``vtrace.vtrace``), from a forward pass of their own; the loss that is
differentiated then holds them as constants.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import evabyte
from .vtrace import vtrace

AUX_HEADS_COEF = 0.1     # handyrl_tpu/ops/losses.py, configs/evabyte.json


def _policy_logits(logits, window):
    return logits[:, 0] * window['valid'][:, None] - window['action_mask']


def targets(outputs, window, lmb):
    """V-trace value targets and the policy's advantages, numpy, (T,)."""
    valid = np.asarray(window['valid'], np.float64)
    logp = np.asarray(jax.nn.log_softmax(_policy_logits(outputs['logits'],
                                                        window)), np.float64)
    log_t = logp[np.arange(len(valid)), np.asarray(window['action'])] * valid
    log_b = np.log(np.clip(np.asarray(window['selected_prob'], np.float64),
                           1e-16, 1)) * valid
    rho = np.minimum(np.exp(log_t - log_b), 1.0)
    value = np.asarray(outputs['value'], np.float64) * valid
    value = value * valid + window['outcome'] * (1 - valid)
    shape = (1, -1, 1, 1)
    vs, adv = vtrace(value.reshape(shape),
                     np.full((1, 1, 1, 1), window['outcome'], np.float64),
                     None, lmb, 1.0, rho.reshape(shape), rho.reshape(shape),
                     valid.reshape(shape))
    return vs.reshape(-1), (rho * adv.reshape(-1))


def loss_of_outputs(out, window, value_target, advantage, entropy_coef,
                    entropy_decay):
    """The total loss (a sum over the window's positions) and its terms,
    from the forward's outputs (``logits`` (T, heads, A), ``value`` (T,))."""
    valid = window['valid']
    logp = jax.nn.log_softmax(_policy_logits(out['logits'], window))
    picked = jnp.take_along_axis(logp, window['action'][:, None], 1)[:, 0]
    terms = {'p': -(picked * valid * advantage * valid).sum(),
             'v': (((out['value'] * valid - value_target) ** 2)
                   * valid).sum() / 2}
    entropy = -(jnp.exp(logp) * logp).sum(axis=-1) * valid
    terms['ent'] = entropy.sum()
    decay = 1 - window['progress'] * (1 - entropy_decay)
    heads = jax.nn.log_softmax(out['logits'][:, 1:] * valid[:, None, None])
    aux = 0.0
    T = valid.shape[0]
    for j in range(heads.shape[1]):
        ahead = 2 + j
        got = jnp.take_along_axis(heads[:T - ahead, j],
                                  window['ids'][ahead:, None], 1)[:, 0]
        aux = aux - (got * valid[:T - ahead] * valid[ahead:]).sum()
    terms['aux'] = aux
    terms['total'] = (terms['p'] + terms['v'] + AUX_HEADS_COEF * aux
                      - entropy_coef * (entropy * decay).sum())
    return terms['total'], terms


def loss(variables, window, value_target, advantage, cfg, entropy_coef,
         entropy_decay, **forward_args):
    """The same from the parameters: what ``jax.grad`` differentiates."""
    out = evabyte.forward(variables, window['ids'], window['first_position'],
                          window['valid'] > 0, cfg, **forward_args)
    return loss_of_outputs(out, window, value_target, advantage,
                           entropy_coef, entropy_decay)
