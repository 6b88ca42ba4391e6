"""The update step for one solo-layout window of the token game, in plain
float32 over the plain forward (``trinity_mini.forward``): the loss that
``handyrl_tpu/ops/losses.py`` ``compute_loss`` composes for a sequence net
with both targets V-trace and no per-ply reward, a written-out first Adam
step, and the rule that moves the router's bias after it.

The targets and advantages carry no gradient, so they are computed first, in
numpy (``vtrace.vtrace``), from a forward pass of their own; the loss that is
differentiated then holds them as constants.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import trinity_mini
from .vtrace import vtrace

ADAM_B1 = 0.9   # optax.scale_by_adam's default, which make_optimizer takes


def _policy_logits(logits, window):
    return logits * window['valid'][:, None] - window['action_mask']


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
    from the forward's outputs (``logits`` (T, A), ``value`` (T,))."""
    valid = window['valid']
    logp = jax.nn.log_softmax(_policy_logits(out['logits'], window))
    picked = jnp.take_along_axis(logp, window['action'][:, None], 1)[:, 0]
    terms = {'p': -(picked * valid * advantage * valid).sum(),
             'v': (((out['value'] * valid - value_target) ** 2)
                   * valid).sum() / 2}
    entropy = -(jnp.exp(logp) * logp).sum(axis=-1) * valid
    terms['ent'] = entropy.sum()
    decay = 1 - window['progress'] * (1 - entropy_decay)
    terms['total'] = (terms['p'] + terms['v']
                      - entropy_coef * (entropy * decay).sum())
    return terms['total'], terms


def loss(variables, window, value_target, advantage, cfg, entropy_coef,
         entropy_decay, **forward_args):
    """The same from the parameters: what ``jax.vjp`` differentiates."""
    out = trinity_mini.forward(variables, window['ids'],
                               window['first_position'], window['valid'] > 0,
                               cfg, **forward_args)
    return loss_of_outputs(out, window, value_target, advantage,
                           entropy_coef, entropy_decay)


def first_adam_step(g, p, lr, norm):
    """The program's optimizer on its first step, written out for one leaf:
    clip the gradient's global norm (``norm``) to 4, add 1e-5 of the
    parameter, Adam with zero moments (the bias-corrected first step is
    g / (|g| + 1e-8)), times -lr. Returns what reached Adam and the
    parameter's change."""
    g = g * jnp.minimum(1.0, 4.0 / norm) + 1e-5 * p
    return g, -lr * g / (jnp.abs(g) + 1e-8)


def bias_after_step(bias, counts, rate):
    """The router's bias after an update step: ``b + rate * (sign(mean(c) -
    c)`` centred to mean zero), ``c`` (E,) the tokens each expert was chosen
    for in the step's batch. Adam has no part in it."""
    c = np.asarray(counts, np.float64)
    delta = np.sign(c.mean() - c)
    return np.asarray(bias, np.float64) + rate * (delta - delta.mean())


def signs_of_bias_step(before, after, rate):
    """The vector ``d`` in {-1, 0, 1}^E for which ``after - before = rate *
    (d - mean(d))``, and how far the change is from that form (max abs, in
    ``b``'s units): the rule's step whatever the counts were."""
    u = (np.asarray(after, np.float64) - np.asarray(before, np.float64)) \
        / rate                                   # d - mean(d)
    best = None
    for top in (1.0, 0.0, -1.0):                 # what the largest d is
        mean = top - u.max()
        d = np.clip(np.round(u + mean), -1, 1)
        residual = max(np.abs(u + mean - d).max(), abs(d.mean() - mean))
        if best is None or residual < best[0]:
            best = (residual, d)
    return float(best[0] * rate), best[1]


def expert_counts(routes, n_experts):
    """routes (layers, T, k) -> tokens an expert and layer: (layers, E)."""
    routes = np.asarray(routes)
    return np.stack([np.bincount(layer.reshape(-1), minlength=n_experts)
                     for layer in routes])
