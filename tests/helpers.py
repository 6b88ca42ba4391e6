"""Shared test fixtures: synthetic episodes and batch windows."""

import numpy as np

from handyrl_tpu.ops.batch import compress_moments


def turn_based_episode(steps=5, obs_shape=(3, 3, 3), n_actions=9, seed=None):
    """Synthetic 2-player turn-alternating episode: player t%2 acts at step t."""
    rng = np.random.RandomState(seed if seed is not None else 0)
    moments = []
    for t in range(steps):
        turn = t % 2
        m = {key: {0: None, 1: None} for key in
             ('observation', 'selected_prob', 'action_mask', 'action',
              'value', 'reward', 'return')}
        m['observation'][turn] = rng.rand(*obs_shape).astype(np.float32)
        m['selected_prob'][turn] = 0.5
        amask = np.full(n_actions, 1e32, np.float32)
        amask[:3] = 0
        m['action_mask'][turn] = amask
        m['action'][turn] = t % 3
        m['value'][turn] = np.array([0.1 * t], np.float32)
        m['reward'] = {0: 0.0, 1: 0.0}
        m['return'] = {0: 0.25, 1: -0.25}
        m['turn'] = [turn]
        moments.append(m)
    return {
        'args': {'player': [0, 1]}, 'steps': steps,
        'outcome': {0: 1.0, 1: -1.0},
        'moment': compress_moments(moments, compress_steps=2),
    }


def _synthetic_geese_episodes(n_eps, rng, compress_steps=4, num_players=4,
                              min_steps=24, max_steps=96):
    """Buffered-episode stand-ins at the HungryGeese record geometry:
    (17, 7, 11) float32 observation planes per player per ply, 4 actions,
    all seats acting every ply (simultaneous env, solo-training config).
    Planes are sparse binary like real goose boards."""
    players = list(range(num_players))
    eps = []
    for _ in range(n_eps):
        steps = int(rng.randint(min_steps, max_steps + 1))
        moments = []
        for _t in range(steps):
            moments.append({
                'observation': {p: (rng.rand(17, 7, 11) < 0.08)
                                .astype(np.float32) for p in players},
                'selected_prob': {p: float(rng.rand()) for p in players},
                'action_mask': {p: np.zeros(4, np.float32) for p in players},
                'action': {p: int(rng.randint(4)) for p in players},
                'value': {p: np.array([float(rng.rand())], np.float32)
                          for p in players},
                'reward': {p: 0.0 for p in players},
                'return': {p: float(rng.rand()) - 0.5 for p in players},
                'turn': players,
            })
        eps.append({'args': {'player': players}, 'steps': steps,
                    'outcome': {p: float(np.sign(rng.randn()))
                                for p in players},
                    'moment': compress_moments(moments, compress_steps)})
    return eps


def ragged_act_rows(n, n_actions=9, obs_shape=(3, 3, 3), hidden_dim=None,
                    seed=0):
    """Shared ragged-row fixture: ``n`` act requests with mixed legal-action
    counts (1..n_actions legal moves per row), random observations, and —
    when ``hidden_dim`` is set — a per-row recurrent state vector. Used by
    the padding/bucketing tests and the inference-engine tests, so both
    exercise the same raggedness."""
    rng = np.random.RandomState(seed)
    rows = []
    for _ in range(n):
        count = int(rng.randint(1, n_actions + 1))
        legal = sorted(rng.choice(n_actions, size=count,
                                  replace=False).tolist())
        obs = rng.rand(*obs_shape).astype(np.float32)
        hidden = (rng.rand(hidden_dim).astype(np.float32)
                  if hidden_dim else None)
        rows.append({'obs': obs, 'legal': legal, 'hidden': hidden})
    return rows


def train_args(forward_steps=4, burn_in=0, observation=False, turn_based=True):
    return {
        'turn_based_training': turn_based, 'observation': observation,
        'forward_steps': forward_steps, 'burn_in_steps': burn_in,
        'compress_steps': 2, 'maximum_episodes': 100,
        'lambda': 0.7, 'gamma': 0.8,
        'policy_target': 'TD', 'value_target': 'TD',
        'entropy_regularization': 0.1, 'entropy_regularization_decay': 0.1,
    }


def window(ep, start, end, train_start=None, cs=2):
    st_block, ed_block = start // cs, (end - 1) // cs + 1
    return {
        'args': ep['args'], 'outcome': ep['outcome'],
        'moment': ep['moment'][st_block:ed_block], 'base': st_block * cs,
        'start': start, 'end': end,
        'train_start': start if train_start is None else train_start,
        'total': ep['steps'],
    }
