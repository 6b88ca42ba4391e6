"""On-device window assembly vs the host batch builder: exact parity.

Feeds the SAME synthetic episode through ops/batch.py build_window (host
reference path, itself pinned to reference train.py:33-124 semantics) and
ops/device_windows.py build_windows_{turn,solo}, for every train_start,
including the burn-in-pad and episode-tail-pad regimes.
"""

import numpy as np
import jax
import jax.numpy as jnp

from handyrl_tpu.ops.batch import build_window
from handyrl_tpu.ops.device_windows import (DeviceWindower,
                                            build_windows_solo,
                                            build_windows_turn,
                                            _discounted_returns, _row_width)

FS, BI = 4, 2
L = 16
GAMMA = 0.8


def _turn_episode(S=10, A=5, P=2, seed=0):
    rng = np.random.RandomState(seed)
    obs = rng.rand(S, 3, 3, 3).astype(np.float32)
    prob = rng.uniform(0.1, 1.0, S).astype(np.float32)
    action = rng.randint(0, A, S).astype(np.int32)
    amask = np.where(rng.rand(S, A) < 0.3, 1e32, 0).astype(np.float32)
    value = rng.uniform(-1, 1, (S, 1)).astype(np.float32)
    player = (np.arange(S) % P).astype(np.int32)
    reward = rng.uniform(-0.1, 0.1, (S, P)).astype(np.float32)
    outcome = np.array([1.0, -1.0], np.float32)
    return dict(obs=obs, prob=prob, action=action, amask=amask, value=value,
                player=player, reward=reward, outcome=outcome, S=S, P=P)


def _host_moments(ep):
    """The episode in generator moment format (generation.py records)."""
    S, P = ep['S'], ep['P']
    rets = np.zeros((S, P), np.float32)
    acc = np.zeros(P, np.float32)
    for t in range(S - 1, -1, -1):
        acc = ep['reward'][t] + GAMMA * acc
        rets[t] = acc
    moments = []
    for t in range(S):
        p = int(ep['player'][t])
        m = {key: {q: None for q in range(P)} for key in
             ('observation', 'selected_prob', 'action_mask', 'action',
              'value', 'reward', 'return')}
        m['observation'][p] = ep['obs'][t]
        m['selected_prob'][p] = float(ep['prob'][t])
        m['action_mask'][p] = ep['amask'][t]
        m['action'][p] = int(ep['action'][t])
        m['value'][p] = ep['value'][t]
        m['reward'] = {q: float(ep['reward'][t, q]) for q in range(P)}
        m['return'] = {q: float(rets[t, q]) for q in range(P)}
        m['turn'] = [p]
        moments.append(m)
    return moments, rets


def _turn_hist(ep):
    S = ep['S']
    pad = lambda a: np.concatenate(
        [a, np.zeros((L - S,) + a.shape[1:], a.dtype)])
    valid = np.arange(L) < S
    rew = pad(ep['reward'])
    ret = np.asarray(_discounted_returns(jnp.asarray(rew),
                                         jnp.asarray(valid), GAMMA))
    return {'obs': jnp.asarray(pad(ep['obs'])),
            'prob': jnp.asarray(pad(ep['prob'])),
            'action': jnp.asarray(pad(ep['action'])),
            'amask': jnp.asarray(pad(ep['amask'])),
            'value': jnp.asarray(pad(ep['value'])),
            'player': jnp.asarray(pad(ep['player'])),
            'reward': jnp.asarray(rew),
            'return': jnp.asarray(ret)}


ARGS = {'turn_based_training': True, 'observation': False,
        'forward_steps': FS, 'burn_in_steps': BI}


def test_turn_mode_matches_host_builder_every_train_start():
    ep = _turn_episode()
    moments, _ = _host_moments(ep)
    hist = _turn_hist(ep)
    S = ep['S']
    for ts in range(1 + max(0, S - FS)):
        st = max(0, ts - BI)
        ed = min(ts + FS, S)
        meta = {'outcome': {0: 1.0, 1: -1.0}, 'start': st, 'end': ed,
                'train_start': ts, 'total': S}
        host = build_window(moments[st:ed], meta, ARGS)
        dev = build_windows_turn(hist, jnp.int32(S),
                                 jnp.asarray([ts], jnp.int32),
                                 jnp.asarray(ep['outcome']), FS, BI, L,
                                 ep['P'])
        for key in host:
            h = np.asarray(host[key], np.float32)
            d = np.asarray(dev[key][0], np.float32)
            np.testing.assert_allclose(
                d, h, rtol=1e-5, atol=1e-6,
                err_msg='turn mode key=%s train_start=%d' % (key, ts))


def _solo_episode(S=9, A=4, P=3, seed=3):
    rng = np.random.RandomState(seed)
    acting = rng.rand(S, P) < 0.7
    acting[:, 0] = True   # keep at least one actor per ply
    return dict(
        obs=rng.rand(S, P, 2, 3, 3).astype(np.float32),
        prob=rng.uniform(0.1, 1.0, (S, P)).astype(np.float32),
        action=rng.randint(0, A, (S, P)).astype(np.int32),
        amask=np.where(rng.rand(S, P, A) < 0.3, 1e32, 0).astype(np.float32),
        value=rng.uniform(-1, 1, (S, P, 1)).astype(np.float32),
        acting=acting,
        reward=rng.uniform(-0.1, 0.1, (S, P)).astype(np.float32),
        outcome=np.array([1.0, -1 / 3, -2 / 3], np.float32), S=S, P=P)


def _solo_moments(ep):
    S, P = ep['S'], ep['P']
    rets = np.zeros((S, P), np.float32)
    acc = np.zeros(P, np.float32)
    for t in range(S - 1, -1, -1):
        acc = ep['reward'][t] + GAMMA * acc
        rets[t] = acc
    moments = []
    for t in range(S):
        m = {key: {q: None for q in range(P)} for key in
             ('observation', 'selected_prob', 'action_mask', 'action',
              'value', 'reward', 'return')}
        actors = []
        for p in range(P):
            if not ep['acting'][t, p]:
                continue
            actors.append(p)
            m['observation'][p] = ep['obs'][t, p]
            m['selected_prob'][p] = float(ep['prob'][t, p])
            m['action_mask'][p] = ep['amask'][t, p]
            m['action'][p] = int(ep['action'][t, p])
            m['value'][p] = ep['value'][t, p]
        m['reward'] = {q: float(ep['reward'][t, q]) for q in range(P)}
        m['return'] = {q: float(rets[t, q]) for q in range(P)}
        m['turn'] = actors
        moments.append(m)
    return moments


def _solo_hist(ep):
    S = ep['S']
    pad = lambda a: np.concatenate(
        [a, np.zeros((L - S,) + a.shape[1:], a.dtype)])
    valid = np.arange(L) < S
    rew = pad(ep['reward'])
    ret = np.asarray(_discounted_returns(jnp.asarray(rew),
                                         jnp.asarray(valid), GAMMA))
    return {'obs': jnp.asarray(pad(ep['obs'])),
            'prob': jnp.asarray(pad(ep['prob'])),
            'action': jnp.asarray(pad(ep['action'])),
            'amask': jnp.asarray(pad(ep['amask'])),
            'value': jnp.asarray(pad(ep['value'])),
            'acting': jnp.asarray(pad(ep['acting'])),
            'reward': jnp.asarray(rew),
            'return': jnp.asarray(ret)}


SOLO_ARGS = {'turn_based_training': False, 'observation': True,
             'forward_steps': FS, 'burn_in_steps': BI}


def test_solo_mode_matches_host_builder(monkeypatch):
    ep = _solo_episode()
    moments = _solo_moments(ep)
    hist = _solo_hist(ep)
    S, P = ep['S'], ep['P']
    for seat in range(P):
        # pin the host builder's random seat choice to `seat`
        import random as _random
        monkeypatch.setattr(_random, 'choice', lambda seq: seat)
        for ts in range(1 + max(0, S - FS)):
            st = max(0, ts - BI)
            ed = min(ts + FS, S)
            meta = {'outcome': {q: float(ep['outcome'][q]) for q in range(P)},
                    'start': st, 'end': ed, 'train_start': ts, 'total': S}
            host = build_window(moments[st:ed], meta, SOLO_ARGS)
            dev = build_windows_solo(hist, jnp.int32(S),
                                     jnp.asarray([ts], jnp.int32),
                                     jnp.asarray([seat], jnp.int32),
                                     jnp.asarray(ep['outcome']), FS, BI, L)
            for key in host:
                h = np.asarray(host[key], np.float32)
                d = np.asarray(dev[key][0], np.float32)
                np.testing.assert_allclose(
                    d, h, rtol=1e-5, atol=1e-6,
                    err_msg='solo key=%s seat=%d ts=%d' % (key, seat, ts))


def test_ingest_fills_ring_and_counts_episodes():
    """End-to-end chunk ingestion: two tiny turn-based envs, deterministic
    done pattern, ring receives windows and episode counts add up."""
    K, N, A, P, S = 6, 2, 3, 2, 3   # every env finishes every 3 plies
    rng = np.random.RandomState(1)
    records = {
        'obs': jnp.asarray(rng.rand(K, N, 2, 2).astype(np.float32)),
        'prob': jnp.asarray(rng.uniform(0.2, 1, (K, N)).astype(np.float32)),
        'action': jnp.asarray(rng.randint(0, A, (K, N)).astype(np.int32)),
        'amask': jnp.asarray(np.zeros((K, N, A), np.float32)),
        'value': jnp.asarray(rng.rand(K, N, 1).astype(np.float32)),
        'player': jnp.asarray((np.indices((K, N))[0] % P).astype(np.int32)),
        'done': jnp.asarray((np.indices((K, N))[0] % S) == S - 1),
        'outcome': jnp.asarray(
            np.tile(np.array([1., -1.], np.float32), (K, N, 1))),
    }
    wd = DeviceWindower(mode='turn', fs=2, bi=0, max_steps=8, windows_cap=2,
                        capacity=32, num_players=P, gamma=GAMMA,
                        has_reward=False)
    state = wd.init_state(records)
    ring = wd.init_ring(records)
    state, ring, cursor, size, key, n_done, n_windows = jax.jit(
        wd.ingest_fn())(records, state, ring, jnp.int32(0), jnp.int32(0),
                        jax.random.PRNGKey(0))
    # 2 envs x 2 episodes each completed in 6 plies
    assert int(n_done) == 4
    assert int(n_windows) == 4
    assert int(size) == 4   # S//fs = 1 window per episode
    assert int(cursor) == 4
    # ring rows are stored flat (TPU tile-padding); unflatten to inspect
    got = wd.unflatten_rows(
        jax.tree_util.tree_map(lambda b: np.asarray(b[:4]), ring))
    assert got['observation'].shape == (4, 2, 1, 2, 2)
    assert got['turn_mask'].shape == (4, 2, P, 1)
    # every stored window is fully inside its episode (fs=2 <= S=3)
    assert np.all(got['episode_mask'] == 1.0)
    # counts reset after each done
    assert np.all(np.asarray(state['counts']) == 0)


def test_ingest_with_pytree_observations():
    """Dict observations (geister's {'scalar','board'}) flow through the
    windower: history buffers map over leaves, ring rows use dotted keys,
    and unflatten_rows rebuilds the nested batch pytree."""
    K, N, A, P, S = 6, 2, 3, 2, 3
    rng = np.random.RandomState(2)
    records = {
        'obs': {'scalar': jnp.asarray(rng.rand(K, N, 5).astype(np.float32)),
                'board': jnp.asarray(
                    rng.rand(K, N, 2, 2, 2).astype(np.float32))},
        'prob': jnp.asarray(rng.uniform(0.2, 1, (K, N)).astype(np.float32)),
        'action': jnp.asarray(rng.randint(0, A, (K, N)).astype(np.int32)),
        'amask': jnp.asarray(np.zeros((K, N, A), np.float32)),
        'value': jnp.asarray(rng.rand(K, N, 1).astype(np.float32)),
        'player': jnp.asarray((np.indices((K, N))[0] % P).astype(np.int32)),
        'done': jnp.asarray((np.indices((K, N))[0] % S) == S - 1),
        'outcome': jnp.asarray(
            np.tile(np.array([1., -1.], np.float32), (K, N, 1))),
    }
    wd = DeviceWindower(mode='turn', fs=2, bi=0, max_steps=8, windows_cap=2,
                        capacity=32, num_players=P, gamma=GAMMA,
                        has_reward=False)
    state = wd.init_state(records)
    ring = wd.init_ring(records)
    assert 'observation.scalar' in ring and 'observation.board' in ring
    state, ring, cursor, size, key, n_done, n_windows = jax.jit(
        wd.ingest_fn())(records, state, ring, jnp.int32(0), jnp.int32(0),
                        jax.random.PRNGKey(0))
    assert int(n_done) == 4 and int(size) == 4
    got = wd.unflatten_rows(
        jax.tree_util.tree_map(lambda b: np.asarray(b[:4]), ring))
    # nested batch pytree restored, window shapes intact
    assert set(got['observation']) == {'scalar', 'board'}
    assert got['observation']['scalar'].shape == (4, 2, 1, 5)
    assert got['observation']['board'].shape == (4, 2, 1, 2, 2, 2)
    assert got['turn_mask'].shape == (4, 2, P, 1)
    # stored board content matches the recorded plies for a full window:
    # env 0's first episode occupies plies 0..2; window start is 0 or 1
    src = np.asarray(records['obs']['board'])[:, 0]
    win = got['observation']['board'][:, :, 0]
    found = any(
        np.allclose(win[i], src[st:st + 2])
        for i in range(4) for st in (0, 1))
    assert found


def test_flatten_window_keys_arbitrary_depth_roundtrip():
    """ADVICE r4: deeper-than-one dict nesting must roundtrip (or fail
    fast), not leak dict values into the ring."""
    import pytest
    from handyrl_tpu.ops.device_windows import (flatten_window_keys,
                                                unflatten_window_keys)
    win = {
        'action': np.zeros((2, 3), np.int32),
        'observation': {'board': np.ones((2, 4)),
                        'aux': {'inner': np.full((2, 1), 7.0),
                                'deep': {'leaf': np.zeros((2, 2))}}},
    }
    flat = flatten_window_keys(win)
    assert set(flat) == {'action', 'observation.board',
                         'observation.aux.inner',
                         'observation.aux.deep.leaf'}
    back = unflatten_window_keys(flat)
    assert back['observation']['aux']['deep']['leaf'].shape == (2, 2)
    np.testing.assert_array_equal(back['observation']['aux']['inner'],
                                  win['observation']['aux']['inner'])

    with pytest.raises(AssertionError, match='reserved'):
        flatten_window_keys({'observation': {'bad.key': np.zeros(2)}})
    with pytest.raises(AssertionError, match='not an array'):
        flatten_window_keys({'observation': {'v': [1, 2, 3]}})


# -- the event-driven ingest against the builder it replaced ----------------
# (tests/windower_oracle.py: cond over "any lane ended" + all-lane vmap +
# drop-scatter, verbatim). Same records, same key -> the same ring, bit for
# bit, and the same cursor, size, rng and counts.

import functools

import pytest

from windower_oracle import OracleWindower

PK, PN, PL, PFS, PW, PCAP, PA = 8, 4, 20, 2, 3, 20, 3


def _ends(*plies_lanes, chunks=1):
    done = np.zeros((chunks * PK, PN), bool)
    for ply, lane in plies_lanes:
        done[ply, lane] = True
    return done


DONE_PATTERNS = {
    'no_lane_ends': _ends(),
    'one_lane_ends': _ends((5, 2)),
    'several_lanes_end_on_one_ply': _ends((3, 0), (3, 2), (6, 1)),
    'every_lane_ends_on_one_ply': _ends(*[(3, n) for n in range(PN)]),
    # 32 one-ply games a chunk: every slot of the chunk is an event
    'every_lane_ends_on_every_ply': _ends(
        *[(ply, n) for ply in range(PK) for n in range(PN)]),
    'a_lane_ends_twice': _ends((2, 1), (6, 1), (4, 3)),
    'a_game_spans_three_chunks': _ends((17, 0), (9, 2), chunks=3),
    'a_game_of_exactly_L_plies': _ends((PL - 1, 3), (PL + 2, 3), chunks=3),
    # 16 one-window games a chunk into 20 slots: the second chunk wraps
    'ring_wraps_inside_a_chunk': _ends(
        *[(ply, n) for ply in range(1, 2 * PK, 2) for n in range(PN)],
        chunks=2),
    # the circular history (32 plies a lane here) wraps under whole games
    'the_history_wraps_under_a_game': _ends(
        (14, 1), (34, 1), (45, 1), (10, 0), (25, 0), (41, 0), (12, 2),
        (31, 2), (13, 3), (32, 3), chunks=6),
}


# the observation a ply: a small array, geister's kind of pytree, an array
# wider than 128 values (165 -> history rows padded to 256, ring rows of
# 2 or 6 plies to 384 or 1,024: the path the benchmark cells' 1,309-value
# plies and 20,944-value windows take), and a pytree with such a leaf
OBSERVATIONS = ['array', 'pytree', 'wide', 'wide_pytree']


def _parity_records(rng, mode, has_reward, obs_kind, done, P):
    K, N = done.shape
    lead = (K, N, P) if mode == 'solo' else (K, N)
    f32 = np.float32
    board = rng.rand(*lead, *((3, 5, 11) if obs_kind.startswith('wide')
                              else (2, 2, 2))).astype(f32)
    records = {
        'obs': ({'scalar': rng.rand(*lead, 5).astype(f32), 'board': board}
                if obs_kind.endswith('pytree') else board),
        'prob': rng.uniform(0.2, 1, lead).astype(f32),
        'action': rng.randint(0, PA, lead).astype(np.int32),
        'amask': np.where(rng.rand(*lead, PA) < 0.3, 1e32, 0).astype(f32),
        'value': rng.uniform(-1, 1, lead + (1,)).astype(f32),
        'done': done,
        'outcome': rng.uniform(-1, 1, (K, N, P)).astype(f32),
    }
    if mode == 'solo':
        acting = rng.rand(*lead) < 0.7
        acting[..., 0] = True
        records['acting'] = acting
    else:
        records['player'] = rng.randint(0, P, lead).astype(np.int32)
    if has_reward:
        records['reward'] = rng.uniform(-0.1, 0.1, (K, N, P)).astype(f32)
    return records


@functools.lru_cache(maxsize=None)
def _windower_pair(mode, has_reward, bi):
    """One compiled ingest a side and configuration: the done patterns and
    the observation's structure only change the data (jit caches by shape)."""
    make = lambda cls: cls(
        mode=mode, fs=PFS, bi=bi, max_steps=PL, windows_cap=PW,
        capacity=PCAP, num_players=3 if mode == 'solo' else 2, gamma=GAMMA,
        has_reward=has_reward)
    return tuple((wd, jax.jit(wd.ingest_fn()))
                 for wd in (make(OracleWindower), make(DeviceWindower)))


@pytest.mark.parametrize('pattern', sorted(DONE_PATTERNS))
@pytest.mark.parametrize('bi', [0, 4])
@pytest.mark.parametrize('obs_kind', OBSERVATIONS)
@pytest.mark.parametrize('has_reward', [False, True],
                         ids=['no_reward', 'reward'])
@pytest.mark.parametrize('mode', ['solo', 'turn'])
def test_ingest_is_bit_identical_to_the_all_lane_builder(
        mode, has_reward, obs_kind, bi, pattern):
    done_all = DONE_PATTERNS[pattern]
    pair = _windower_pair(mode, has_reward, bi)
    (oracle, _), (new, _) = pair
    rng = np.random.RandomState(len(pattern) + bi)
    sides = []
    for wd, ingest in pair:
        first = _parity_records(np.random.RandomState(0), mode, has_reward,
                                obs_kind, done_all[:PK], wd.P)
        sides.append([ingest, wd.init_state(first), wd.init_ring(first),
                      jnp.int32(0), jnp.int32(0), jax.random.PRNGKey(7)])
    total = 0
    for c in range(len(done_all) // PK):
        records = _parity_records(rng, mode, has_reward, obs_kind,
                                  done_all[c * PK:(c + 1) * PK], oracle.P)
        outs = []
        for side in sides:
            out = side[0](jax.tree_util.tree_map(jnp.asarray, records),
                          *side[1:])
            side[1:] = out[:5]
            outs.append(out)
        (_, ring_o, cur_o, size_o, key_o, done_o, win_o), \
            (state_n, ring_n, cur_n, size_n, key_n, done_n, win_n) = outs
        assert sorted(ring_o) == sorted(ring_n)
        for key in ring_o:
            # the oracle's rows are as wide as the window; the ring's hold
            # the same values and, where padded to whole tiles, zeros after
            stored, flat = np.asarray(ring_n[key]), ring_o[key].shape[1]
            assert stored.shape == (PCAP, _row_width(flat))
            np.testing.assert_array_equal(
                stored[:, :flat], np.asarray(ring_o[key]),
                err_msg='%s, chunk %d, ring leaf %s' % (pattern, c, key))
            assert not stored[:, flat:].any(), (pattern, c, key)
        assert (int(cur_n), int(size_n), int(done_n), int(win_n)) == \
            (int(cur_o), int(size_o), int(done_o), int(win_o))
        np.testing.assert_array_equal(np.asarray(key_n), np.asarray(key_o))
        np.testing.assert_array_equal(np.asarray(state_n['counts']),
                                      np.asarray(outs[0][0]['counts']))
        assert int(done_n) == done_all[c * PK:(c + 1) * PK].sum()
        total += int(win_n)
    # the patterns do what their names say
    if pattern == 'no_lane_ends':
        assert total == 0 and not np.asarray(ring_n['episode_mask']).any()
    else:
        assert total > 0 and np.asarray(ring_n['episode_mask']).any()
    if pattern in ('ring_wraps_inside_a_chunk',
                   'every_lane_ends_on_every_ply'):
        assert total == 32 > PCAP and int(cur_n) == 32 % PCAP
        assert int(size_n) == PCAP
    # what a consumer reads: the padding stripped, the windows the oracle
    # built, the observation nested again
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           new.unflatten_rows(ring_n),
                           oracle.unflatten_rows(ring_o))
    T = bi + PFS
    wide_leaf = {'wide': 'observation',
                 'wide_pytree': 'observation.board'}.get(obs_kind)
    for key in ring_n:
        if key == wide_leaf:
            assert ring_o[key].shape[1] == T * 165
            assert ring_n[key].shape[1] == {2: 384, 6: 1024}[T]
        else:
            assert ring_n[key].shape == ring_o[key].shape
    if wide_leaf:
        hist_obs = state_n['hist']['obs']
        assert (hist_obs['board'] if obs_kind == 'wide_pytree'
                else hist_obs).shape[2] == 256
    if pattern == 'a_game_of_exactly_L_plies':
        assert PL // PFS >= PW and total == PW + 1


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr, loop and branch bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    yield from _walk_eqns(inner)


def _cell_shape_faults(cls, bi):
    """The shape guard (traced on abstract values, nothing runs): the
    ingest of ``cls`` at the benchmark cells' geometry (N=64 lanes, games
    of up to L=200 plies, W=12 windows a game, T=16 + ``bi`` plies a window
    of the 4-seat 17x7x11 observation, the 49,152-window ring), and what in
    it does not follow the windows stored:

      * a gather of more than one window's rows of one seat (T rows, a row
        padded to whole 128-lane tiles);
      * a value the size of all lanes' windows (N x W x T plies of one
        seat) or more that is not the history or the ring itself, carried
        by a loop or updated in place: so nothing of lanes x windows size,
        no copy or relayout of the history, and the only ring-shaped
        operation is the in-place row write.

    Returns (faults, number of gathers, ring-shaped operations by name,
    the ring's shapes, the windower's ``window_spec``)."""
    K, N, P, W, T, L, A = 32, 64, 4, 12, 16 + bi, 200, 4
    obs = (17, 7, 11)
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    records = {
        'obs': sds((K, N, P) + obs, f32), 'prob': sds((K, N, P), f32),
        'action': sds((K, N, P), jnp.int32), 'amask': sds((K, N, P, A), f32),
        'value': sds((K, N, P, 1), f32), 'acting': sds((K, N, P), bool),
        'done': sds((K, N), bool), 'outcome': sds((K, N, P), f32)}
    wd = cls(mode='solo', fs=16, bi=bi, max_steps=L, windows_cap=W,
             capacity=49152, num_players=P, gamma=1.0, has_reward=False)
    state = jax.eval_shape(wd.init_state, records)
    ring = jax.eval_shape(wd.init_ring, records)
    scalar = sds((), jnp.int32)
    closed = jax.make_jaxpr(wd.ingest_fn())(
        records, state, ring, scalar, scalar, sds((2,), jnp.uint32))

    seat_ply = int(np.prod(obs))
    all_lane_windows = N * W * T * seat_ply
    history = N * L * P * seat_ply
    carriers = {'dynamic_update_slice', 'while', 'scan', 'cond', 'pjit'}
    faults, n_gathers, ring_ops = [], 0, set()
    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        for out in eqn.outvars:
            size = int(np.prod(out.aval.shape))
            if name == 'gather':
                n_gathers += 1
                if size > T * -(-seat_ply // 128) * 128:
                    faults.append((name, out.aval.shape))
            if out.aval.shape == ring['observation'].shape:
                ring_ops.add(name)
            if size >= all_lane_windows and not (
                    size >= history and name in carriers
                    and any(getattr(v.aval, 'shape', None) == out.aval.shape
                            for v in eqn.invars)):
                faults.append((name, out.aval.shape))
    return (faults, n_gathers, ring_ops,
            {key: leaf.shape for key, leaf in ring.items()}, wd.window_spec)


@pytest.mark.parametrize('bi', [0, 4], ids=['T16', 'T20'])
def test_ingest_at_the_cells_shapes_follows_the_windows_stored(bi):
    faults, n_gathers, ring_ops, ring, spec = _cell_shape_faults(
        DeviceWindower, bi)
    assert not faults, faults
    assert n_gathers > 0
    # the observation's window row (20,944 or 26,180 values) is stored
    # padded to whole 128-lane tiles, every narrow leaf as wide as it is,
    # and ``window_spec`` keeps the logical shapes
    T = 16 + bi
    assert ring.pop('observation') == (49152, {16: 20992, 20: 26240}[T])
    assert spec['observation'][0] == (T, 1, 17, 7, 11)
    assert ring == {key: (49152, int(np.prod(spec[key][0]))) for key in ring}
    assert max(width for _, width in ring.values()) == 4 * T <= 128
    # the observation ring: carried by the one window loop, written a row
    # at a time in place (at the stored width), and nothing else
    assert ring_ops == {'while', 'dynamic_update_slice'}


def test_the_shape_guard_rejects_the_all_lane_builder():
    """The guard has teeth: the builder before PR 26 gathers every lane's
    windows (64 x 12 x 16 plies of all four seats) and scatters them."""
    faults, _, ring_ops, _, _ = _cell_shape_faults(OracleWindower, 0)
    assert {'gather', 'scatter'} <= {name for name, _ in faults}
    assert 'scatter' in ring_ops


def test_an_env_module_must_declare_its_longest_game():
    """The circular history is sized from the env's longest game: the
    learner refuses a device env module that declares neither MAX_STEPS nor
    MAX_PLIES (it used to assume 256 plies in silence)."""
    import types

    from handyrl_tpu.envs import jax_geister, jax_hungry_geese
    from handyrl_tpu.train import _declared_max_steps

    assert _declared_max_steps(jax_hungry_geese) == 200
    # Geister's draw comes after MAX_PLIES moves, and its two set-up plies
    # are env steps too: the module says so in MAX_STEPS
    assert _declared_max_steps(jax_geister) == 202
    assert _declared_max_steps(types.SimpleNamespace(MAX_PLIES=40)) == 40
    undeclared = types.ModuleType('jax_endless_game')
    with pytest.raises(AssertionError, match='jax_endless_game declares '
                                             'neither MAX_STEPS'):
        _declared_max_steps(undeclared)


def test_no_geister_game_outlasts_the_declared_bound():
    """The windower's contract, held by the env: under random legal play a
    quarter of the Geister games are draws, and a draw is exactly the
    declared number of env steps (two set-up plies + MAX_PLIES moves),
    never more."""
    from handyrl_tpu.envs import jax_geister as env
    from handyrl_tpu.train import _declared_max_steps

    def play(state, key):
        def ply(carry, _):
            state, key = carry
            key, sub = jax.random.split(key)
            action = jax.random.categorical(
                sub, jnp.where(env.legal_mask(state), 0.0, -1e9))
            state = env.step(state, action)
            done = env.terminal(state)
            return (env.auto_reset(state, done), key), done
        return jax.lax.scan(ply, (state, key), None, length=420)[1]

    done = np.asarray(jax.jit(play)(env.init_state(128, 0),
                                    jax.random.PRNGKey(1)))
    lengths = [b - a for lane in done.T
               for a, b in zip(np.r_[-1, np.flatnonzero(lane)],
                               np.flatnonzero(lane))]
    assert max(lengths) == _declared_max_steps(env) == env.MAX_PLIES + 2
    assert lengths.count(max(lengths)) > 10


# -- the legal set as bits (ops/maskbits.py; ROADMAP M4) -----------------------
def _solo_history(bits, S=9, P=2, A=40, seed=3):
    from handyrl_tpu.ops import maskbits
    rng = np.random.RandomState(seed)
    illegal = rng.rand(L, P, A) < 0.3
    amask = (maskbits.pack(jnp.asarray(illegal)) if bits
             else jnp.asarray(np.where(illegal, 1e32, 0).astype(np.float32)))
    hist = {'obs': jnp.asarray(rng.randint(0, A, (L, P)), jnp.int32),
            'action': jnp.asarray(rng.randint(0, A, (L, P)), jnp.int32),
            'prob': jnp.asarray(rng.uniform(0.1, 1, (L, P)), jnp.float32),
            'amask': amask,
            'value': jnp.asarray(rng.uniform(-1, 1, (L, P, 1)), jnp.float32),
            'acting': jnp.ones((L, P), bool)}
    return hist, illegal, S


def test_a_bit_mask_goes_into_the_window_as_bits_padded_with_ones():
    """The windower keeps the dtype the game's twin recorded: a ``uint8``
    mask stays ``uint8`` (an eighth of a byte an id where the float mask is
    four), its padding rows all ones, and it unpacks to the float window's
    mask exactly."""
    from handyrl_tpu.ops import maskbits
    ts, seat = jnp.asarray([0, 3, 7]), jnp.asarray([0, 1, 1])
    outcome = jnp.asarray([1.0, -1.0])
    wins = {}
    for bits in (False, True):
        hist, _illegal, S = _solo_history(bits)
        wins[bits] = build_windows_solo(hist, jnp.int32(S), ts, seat, outcome,
                                        FS, BI, L, first_position=True)
    packed, plain = wins[True]['action_mask'], wins[False]['action_mask']
    assert packed.dtype == jnp.uint8 and packed.shape == (3, FS + BI, 1, 5)
    assert plain.dtype == jnp.float32 and plain.shape == (3, FS + BI, 1, 40)
    np.testing.assert_array_equal(maskbits.as_float(packed, 40), plain)
    # the first window starts BI plies before the game: padding, all bits set
    assert (np.asarray(packed)[0, :BI] == 255).all()
    for key in wins[False]:
        if key != 'action_mask':
            np.testing.assert_array_equal(wins[True][key], wins[False][key])


@pytest.mark.parametrize('net_name', ['EvaByteNet', 'TrinityNet'])
def test_a_bit_mask_in_the_ring_trains_as_the_float_mask(net_name):
    """``compute_loss`` on windows whose ``action_mask`` is bits against the
    same windows with the float mask: the same loss and the same gradient,
    bit for bit, on the whole-array path (a net that returns ``policy``) and
    on the path that takes the head in blocks (``policy_features``)."""
    from handyrl_tpu import models
    from handyrl_tpu.ops import maskbits
    from handyrl_tpu.ops.losses import LossConfig, compute_loss
    T, A = 16, 40
    widths = {
        'EvaByteNet': dict(hidden_size=32, layers=1, heads_held=2,
                           heads_published=2, head_dim=8, mlp_size=48,
                           vocab=A, chunk_size=4, window_size=8,
                           max_positions=32, query_block=8, pred_heads=2),
        'TrinityNet': dict(hidden_size=32, layer_types=('sliding', 'full'),
                           dense_layers=1, heads_held=2, kv_heads_held=1,
                           head_dim=8, mlp_size=48, expert_size=16,
                           experts_published=8, experts_held=(0, 1, 2),
                           experts_per_token=2, vocab=A, window_size=8,
                           max_positions=32, query_block=8, dense_rows=2)}
    net = models.build(net_name, dtype=jnp.float32, **widths[net_name])
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    rng = np.random.RandomState(1)
    valid = (np.arange(T)[None, :] < np.asarray([[T], [11]])).astype(
        np.float32)
    illegal = (rng.rand(2, T, 1, A) < 0.3) | (valid[..., None, None] == 0)
    illegal[..., 0] = valid[..., None] == 0       # id 0 legal in the game
    col = lambda x: jnp.asarray(x, jnp.float32)[..., None, None]
    batch = {
        'observation': jnp.asarray(rng.randint(0, A, (2, T, 1)), jnp.int32),
        'selected_prob': col(rng.uniform(0.05, 0.5, (2, T))),
        'action': jnp.zeros((2, T, 1, 1), jnp.int32),
        'value': col(rng.uniform(-0.1, 0.1, (2, T))),
        'reward': col(np.zeros((2, T))), 'return': col(np.zeros((2, T))),
        'outcome': jnp.asarray([1.0, -1.0]).reshape(2, 1, 1, 1),
        'episode_mask': col(valid), 'turn_mask': col(valid),
        'observation_mask': col(valid),
        'progress': jnp.asarray(np.linspace(0, 1, T)[None, :, None]
                                * np.ones((2, 1, 1)), jnp.float32),
        'first_position': jnp.zeros((2, 1, 1, 1), jnp.int32)}
    masks = {'float': jnp.asarray(np.where(illegal, 1e32, 0), jnp.float32),
             'bits': maskbits.pack(jnp.asarray(illegal))}
    assert masks['bits'].dtype == jnp.uint8 and masks['bits'].shape[-1] == 5
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='VTRACE', value_target='VTRACE')
    sequence = lambda p, *a: net.apply(p, *a, method=net.sequence)
    policy = None
    if hasattr(net, 'policy_logits'):
        policy = lambda p, f: net.apply(p, f, method=net.policy_logits)

    def loss_and_grad(mask):
        return jax.jit(jax.value_and_grad(lambda p: compute_loss(
            net.apply, p, None, dict(batch, action_mask=mask), cfg,
            sequence_fn=sequence, policy_fn=policy)[0]))(variables)
    (want, want_grad), (got, got_grad) = (loss_and_grad(masks[k])
                                          for k in ('float', 'bits'))
    assert np.isfinite(float(want)) and float(want) == float(got)
    jax.tree_util.tree_map(np.testing.assert_array_equal, want_grad, got_grad)


def test_a_head_taken_in_blocks_is_the_whole_head(monkeypatch):
    """``_policy_in_blocks`` over four blocks of positions against one: the
    same log-probabilities and entropies, so the same loss."""
    from handyrl_tpu.ops import losses
    rng = np.random.RandomState(2)
    B, T, D, A = 2, 16, 8, 24
    features = jnp.asarray(rng.randn(B, T, 1, D), jnp.float32)
    head = jnp.asarray(rng.randn(D, A), jnp.float32)
    batch = {'turn_mask': jnp.ones((B, T, 1, 1)),
             'action_mask': jnp.asarray(
                 np.where(rng.rand(B, T, 1, A) < 0.3, 1e32, 0), jnp.float32),
             'action': jnp.asarray(rng.randint(0, A, (B, T, 1, 1)), jnp.int32)}
    policy = lambda w, f: f @ w
    whole = losses._policy_in_blocks(policy, head, features, batch)
    monkeypatch.setattr(losses, 'POLICY_BLOCK', 8)
    blocks = losses._policy_in_blocks(policy, head, features, batch)
    for a, b in zip(whole, blocks):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    logp = jax.nn.log_softmax(features @ head - batch['action_mask'])
    np.testing.assert_allclose(
        whole[0], jnp.take_along_axis(logp, batch['action'], axis=-1),
        rtol=1e-6, atol=1e-6)
