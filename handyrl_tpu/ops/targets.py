"""Value/advantage target algorithms: MC, TD(lambda), UPGO, V-Trace.

Numerical parity targets: the backward recursions of the reference
(`/root/reference/handyrl/losses.py:16-78`), re-expressed as ``lax.scan`` over
reversed time so the whole pipeline stays inside one XLA program (no Python
loops over T).

Conventions:
  * arrays are batch-first ``(B, T, ...)`` exactly as the batch builder emits
    them; internally time is moved to the leading axis for the scan.
  * ``masks`` marks *valid* steps; invalid steps collapse to ``lambda = 1``
    via ``lambda_t = lmb + (1 - lmb) * (1 - mask_t)`` (losses.py:71) so they
    pass the bootstrap straight through.
  * ``rewards`` may be None (the outcome-value head trains with no
    intermediate rewards and gamma = 1).

V-Trace follows Espeholt et al. 2018 (arXiv:1802.01561) with importance
ratios rho/c clipped upstream by the loss pipeline.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

ALGORITHMS = ('MC', 'TD', 'UPGO', 'VTRACE')


def _tm(x: Array) -> Array:
    """Batch-first -> time-major."""
    return jnp.moveaxis(x, 1, 0)


def _bf(x: Array) -> Array:
    """Time-major -> batch-first."""
    return jnp.moveaxis(x, 0, 1)


def _zeros_like_rewards(rewards: Optional[Array], template: Array) -> Array:
    return jnp.zeros_like(template) if rewards is None else rewards


def monte_carlo(values: Array, returns: Array) -> Tuple[Array, Array]:
    return returns, returns - values


def td_lambda(values: Array, returns: Array, rewards: Optional[Array],
              lambda_: Array, gamma: float) -> Tuple[Array, Array]:
    """TD(lambda) targets: tv_t = r_t + g*((1-l_{t+1})*V_{t+1} + l_{t+1}*tv_{t+1}),
    boot-strapped from returns at the final step."""
    v, ret, lam = _tm(values), _tm(returns), _tm(lambda_)
    rew = _tm(_zeros_like_rewards(rewards, values))

    def step(carry, x):
        v_next, lam_next, r = x
        tv = r + gamma * ((1 - lam_next) * v_next + lam_next * carry)
        return tv, tv

    init = ret[-1]
    _, tvs = lax.scan(step, init, (v[1:], lam[1:], rew[:-1]), reverse=True)
    tvs = jnp.concatenate([tvs, ret[-1:]], axis=0)
    return _bf(tvs), _bf(tvs - v)


def upgo(values: Array, returns: Array, rewards: Optional[Array],
         lambda_: Array, gamma: float) -> Tuple[Array, Array]:
    """UPGO: bootstrap with max(V_{t+1}, mixed target) so targets never dip
    below the one-step value estimate."""
    v, ret, lam = _tm(values), _tm(returns), _tm(lambda_)
    rew = _tm(_zeros_like_rewards(rewards, values))

    def step(carry, x):
        v_next, lam_next, r = x
        tv = r + gamma * jnp.maximum(v_next, (1 - lam_next) * v_next + lam_next * carry)
        return tv, tv

    init = ret[-1]
    _, tvs = lax.scan(step, init, (v[1:], lam[1:], rew[:-1]), reverse=True)
    tvs = jnp.concatenate([tvs, ret[-1:]], axis=0)
    return _bf(tvs), _bf(tvs - v)


def vtrace(values: Array, returns: Array, rewards: Optional[Array],
           lambda_: Array, gamma: float, rhos: Array, cs: Array
           ) -> Tuple[Array, Array]:
    """V-Trace: vs_t = V_t + sum of c-weighted rho-corrected TD errors;
    advantage evaluated against vs_{t+1}."""
    v, ret, lam = _tm(values), _tm(returns), _tm(lambda_)
    rew = _tm(_zeros_like_rewards(rewards, values))
    rho, c = _tm(rhos), _tm(cs)

    v_next = jnp.concatenate([v[1:], ret[-1:]], axis=0)
    deltas = rho * (rew + gamma * v_next - v)

    def step(carry, x):
        delta, lam_c = x
        out = delta + gamma * lam_c * carry
        return out, out

    init = deltas[-1]
    _, vmv = lax.scan(step, init, (deltas[:-1], lam[1:] * c[:-1]), reverse=True)
    vmv = jnp.concatenate([vmv, deltas[-1:]], axis=0)

    vs = vmv + v
    vs_next = jnp.concatenate([vs[1:], ret[-1:]], axis=0)
    advantages = rew + gamma * vs_next - v
    return _bf(vs), _bf(advantages)


def compute_target(algorithm: str, values: Optional[Array], returns: Array,
                   rewards: Optional[Array], lmb: float, gamma: float,
                   rhos: Array, cs: Array, masks: Array
                   ) -> Tuple[Array, Array]:
    """Dispatch on algorithm name; mirrors losses.py:63-78 including the
    no-baseline Monte-Carlo fallback and the lambda-mask collapse. The
    backward recursion is a ``lax.scan`` on every backend."""
    if values is None:
        return returns, returns
    if algorithm == 'MC':
        return monte_carlo(values, returns)

    lambda_ = lmb + (1 - lmb) * (1 - masks)

    if algorithm == 'TD':
        return td_lambda(values, returns, rewards, lambda_, gamma)
    if algorithm == 'UPGO':
        return upgo(values, returns, rewards, lambda_, gamma)
    if algorithm == 'VTRACE':
        return vtrace(values, returns, rewards, lambda_, gamma, rhos, cs)
    raise ValueError('unknown target algorithm: %s' % algorithm)
