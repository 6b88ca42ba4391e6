"""V-trace targets in plain numpy loops (Espeholt et al. 2018,
arXiv:1802.01561, eq. 1), in the program's conventions:

* arrays are (B, T, ...); the bootstrap beyond the last step is
  ``returns[:, -1]``;
* importance ratios ``rhos`` and ``cs`` arrive already clipped;
* ``masks`` marks valid steps; at an invalid step lambda collapses to 1
  (``lambda_t = lmb + (1 - lmb) * (1 - mask_t)``), as the reference's
  ``losses.py:71`` does;
* ``rewards`` may be None (zero).

    delta_t   = rho_t * (r_t + gamma * V_{t+1} - V_t)
    vs_t - V_t = delta_t + gamma * lambda_{t+1} * c_t * (vs_{t+1} - V_{t+1})
    adv_t     = r_t + gamma * vs_{t+1} - V_t

float64 inside, so that the comparison's tolerance is the program's float32
rounding and not this file's.
"""

import numpy as np


def vtrace(values, returns, rewards, lmb, gamma, rhos, cs, masks):
    v = np.asarray(values, np.float64)
    ret = np.asarray(returns, np.float64)
    rew = np.zeros_like(v) if rewards is None else np.asarray(rewards,
                                                              np.float64)
    rho, c = np.asarray(rhos, np.float64), np.asarray(cs, np.float64)
    lam = lmb + (1 - lmb) * (1 - np.asarray(masks, np.float64))
    steps = v.shape[1]
    vs = np.zeros_like(v)
    adv = np.zeros_like(v)
    carry = None   # vs_{t+1} - V_{t+1}
    for t in reversed(range(steps)):
        v_next = ret[:, -1] if t == steps - 1 else v[:, t + 1]
        delta = rho[:, t] * (rew[:, t] + gamma * v_next - v[:, t])
        if t == steps - 1:
            diff = delta
        else:
            diff = delta + gamma * lam[:, t + 1] * c[:, t] * carry
        vs[:, t] = v[:, t] + diff
        carry = diff
    for t in range(steps):
        vs_next = ret[:, -1] if t == steps - 1 else vs[:, t + 1]
        adv[:, t] = rew[:, t] + gamma * vs_next - v[:, t]
    return vs, adv
