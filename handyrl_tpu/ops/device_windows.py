"""On-device window assembly: rollout records -> replay ring, zero host copies.

The host splice path (device_generation.step_chunk -> moment dicts -> bz2 ->
ingest decompress -> build_window -> ring push) rebuilds every episode in
Python: ~chunk_steps x n_envs dict constructions per dispatch. On a single
host core that, not the accelerator, bounds the fully-device pipeline.

This module closes the loop in HBM. A per-env episode history lives on
device as fixed (N, L, ...) buffers; one jitted program consumes a rollout
chunk ply by ply (lax.scan), and wherever an episode terminates it

  * draws ``clip(steps // forward_steps, 1, W)`` random training windows
    (the host ingestion rate, train.py _ingest_new_episodes),
  * materializes them with the EXACT pad/mask semantics of
    ops/batch.py build_window (reference train.py:33-124): prob pad 1,
    action_mask pad +1e32, value tail = final outcome, progress pad 1,
    episode/turn/observation masks,
  * and scatters them into the DeviceReplay ring with prefix-sum slot
    compaction (invalid lanes dropped via out-of-range scatter indices).

The host sees only (episodes_done, outcome) scalars per chunk. Two layouts
are supported, mirroring build_window's two player-axis regimes:

  * 'solo' (simultaneous env, turn_based_training=False): one random seat
    per window; every window leaf has P axis 1 (reference train.py:57-58).
  * 'turn' (turn-based, observation=False): obs/prob/action/action_mask
    carry the turn player (P axis 1) while value/reward/return/outcome and
    the masks span all players (reference train.py:65-68).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _take(hist_leaf, idxm):
    """hist_leaf (L, ...) gathered at idxm (T,) -> (T, ...)."""
    return hist_leaf[idxm]


def flatten_window_keys(win: Dict[str, Any]) -> Dict[str, Any]:
    """Window dicts may carry a PYTREE observation (e.g. geister's
    {'scalar', 'board'}); the ring stores flat 2-D rows per leaf, so
    nested dict levels become dotted keys ('observation.board'), recursing
    to arbitrary depth. Keys must not contain '.' (asserted — a dotted
    env observation key would collide with the path encoding) and every
    flattened value must be an array-like, so a deeper-than-expected
    pytree fails HERE with a clear message, not later inside the ring."""
    out = {}

    def walk(prefix, v):
        if isinstance(v, dict):
            for sk, sv in v.items():
                assert '.' not in str(sk), (
                    'observation key %r contains "." which is reserved for '
                    'the ring\'s flattened-path encoding' % (sk,))
                walk('%s.%s' % (prefix, sk) if prefix else str(sk), sv)
        else:
            assert hasattr(v, 'shape'), (
                'window leaf %r is %r, not an array — unsupported pytree '
                'node in the observation?' % (prefix, type(v)))
            out[prefix] = v

    for k, v in win.items():
        walk(str(k), v)
    return out


def unflatten_window_keys(win: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of flatten_window_keys — rebuilds the batch pytree the
    loss consumes (batch['observation'] nested again, any depth)."""
    out: Dict[str, Any] = {}
    for k, v in win.items():
        parts = k.split('.')
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def build_windows_solo(hist: Dict[str, Any], S, ts, seat, outcome,
                       fs: int, bi: int, L: int):
    """Windows for ONE env in solo layout.

    hist leaves are (L, P, ...); S scalar episode length; ts (W,) train
    starts; seat (W,) evaluated seats; outcome (P,). Returns a window dict
    with leading axis W.
    """
    T = bi + fs

    def one(ts_w, seat_w):
        m = ts_w - bi + jnp.arange(T)                    # (T,)
        in_ep = (m >= 0) & (m < S)
        idxm = jnp.clip(m, 0, L - 1)
        acting = _take(hist['acting'], idxm)[:, seat_w]  # (T,)
        valid = in_ep & acting
        tail = (m >= S)

        def vmask(x, fill, cond):
            c = cond.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(c, x, fill)

        obs = jax.tree_util.tree_map(          # obs may be a pytree
            lambda x: vmask(_take(x, idxm)[:, seat_w][:, None], 0.0, valid),
            hist['obs'])                                            # (T,1,...)
        prob = jnp.where(valid, _take(hist['prob'], idxm)[:, seat_w], 1.0)
        act = jnp.where(valid, _take(hist['action'], idxm)[:, seat_w], 0)
        amask = vmask(_take(hist['amask'], idxm)[:, seat_w][:, None],
                      1e32, valid)
        val = _take(hist['value'], idxm)[:, seat_w, 0]
        val = jnp.where(valid, val,
                        jnp.where(tail, outcome[seat_w], 0.0))
        if 'reward' in hist:
            rew = jnp.where(in_ep, _take(hist['reward'], idxm)[:, seat_w], 0.0)
            ret = jnp.where(in_ep, _take(hist['return'], idxm)[:, seat_w], 0.0)
        else:
            rew = jnp.zeros((T,), jnp.float32)
            ret = jnp.zeros((T,), jnp.float32)
        progress = jnp.where(in_ep, m.astype(jnp.float32) / S, 1.0)
        f32 = jnp.float32
        return {
            'observation': obs,
            'selected_prob': prob.astype(f32)[:, None, None],
            'action': act.astype(jnp.int32)[:, None, None],
            'action_mask': amask.astype(f32),
            'value': val.astype(f32)[:, None, None],
            'reward': rew.astype(f32)[:, None, None],
            'return': ret.astype(f32)[:, None, None],
            'outcome': outcome[seat_w].astype(f32).reshape(1, 1, 1),
            'episode_mask': in_ep.astype(f32)[:, None, None],
            'turn_mask': valid.astype(f32)[:, None, None],
            'observation_mask': valid.astype(f32)[:, None, None],
            'progress': progress.astype(f32)[:, None],
        }

    return jax.vmap(lambda t, s: flatten_window_keys(one(t, s)))(ts, seat)


def build_windows_turn(hist: Dict[str, Any], S, ts, outcome,
                       fs: int, bi: int, L: int, num_players: int):
    """Windows for ONE env in turn-based (observation=False) layout.

    hist leaves are (L, ...) with the turn player's data per ply plus
    hist['player'] (L,); outcome (P,). Returns a window dict with leading
    axis W; mask/value leaves span all P players, data leaves P axis 1.
    """
    T = bi + fs
    P = num_players

    def one(ts_w):
        m = ts_w - bi + jnp.arange(T)
        in_ep = (m >= 0) & (m < S)
        idxm = jnp.clip(m, 0, L - 1)
        player = _take(hist['player'], idxm)             # (T,)
        tail = (m >= S)

        def vmask(x, fill, cond):
            c = cond.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(c, x, fill)

        obs = jax.tree_util.tree_map(          # obs may be a pytree
            lambda x: vmask(_take(x, idxm)[:, None], 0.0, in_ep),
            hist['obs'])
        prob = jnp.where(in_ep, _take(hist['prob'], idxm), 1.0)
        act = jnp.where(in_ep, _take(hist['action'], idxm), 0)
        amask = vmask(_take(hist['amask'], idxm)[:, None], 1e32, in_ep)
        # (T, P) per-player masks: the turn player acted and observed
        is_turn = (player[:, None] == jnp.arange(P)[None, :]) \
            & in_ep[:, None]
        val_turn = _take(hist['value'], idxm)[:, 0]       # (T,)
        val = jnp.where(is_turn, val_turn[:, None],
                        jnp.where(tail[:, None], outcome[None, :], 0.0))
        if 'reward' in hist:
            rew = jnp.where(in_ep[:, None],
                            _take(hist['reward'], idxm), 0.0)   # (T, P)
            ret = jnp.where(in_ep[:, None],
                            _take(hist['return'], idxm), 0.0)
        else:
            rew = jnp.zeros((T, P), jnp.float32)
            ret = jnp.zeros((T, P), jnp.float32)
        progress = jnp.where(in_ep, m.astype(jnp.float32) / S, 1.0)
        f32 = jnp.float32
        return {
            'observation': obs,
            'selected_prob': prob.astype(f32)[:, None, None],
            'action': act.astype(jnp.int32)[:, None, None],
            'action_mask': amask.astype(f32),
            'value': val.astype(f32)[:, :, None],
            'reward': rew.astype(f32)[:, :, None],
            'return': ret.astype(f32)[:, :, None],
            'outcome': outcome.astype(f32).reshape(1, P, 1),
            'episode_mask': in_ep.astype(f32)[:, None, None],
            'turn_mask': is_turn.astype(f32)[:, :, None],
            'observation_mask': is_turn.astype(f32)[:, :, None],
            'progress': progress.astype(f32)[:, None],
        }

    return jax.vmap(lambda t: flatten_window_keys(one(t)))(ts)


def _discounted_returns(rewards, valid, gamma: float):
    """Backward discounted returns over the (L, P) reward history.

    ret[m] = r[m] + gamma * ret[m+1] within the valid prefix; zeros outside.
    """
    def body(carry, xs):
        r, v = xs
        nxt = r + gamma * carry
        nxt = jnp.where(v.reshape((-1,) + (1,) * (r.ndim - 1)), nxt, 0.0)
        return nxt, nxt

    rev = lambda x: jnp.flip(x, axis=0)
    _, rets = jax.lax.scan(body, jnp.zeros_like(rewards[0]),
                           (rev(rewards), rev(valid)))
    return rev(rets)


class DeviceWindower:
    """Owns the per-env episode history and the chunk-ingest program.

    ``ingest(records, state, ring, cursor, size, rng)`` consumes one rollout
    chunk and returns updated (state, ring, cursor, size, rng, n_done).
    The ring/state/cursor/size live as device arrays owned by the caller
    (single-owner: the trainer thread), so buffers are donated in place.
    """

    def __init__(self, mode: str, fs: int, bi: int, max_steps: int,
                 windows_cap: int, capacity: int, num_players: int,
                 gamma: float, has_reward: bool):
        assert mode in ('solo', 'turn')
        self.mode = mode
        self.fs, self.bi = fs, bi
        self.L = max_steps
        self.W = max(1, windows_cap)
        self.capacity = capacity
        self.P = num_players
        self.gamma = gamma
        self.has_reward = has_reward
        self.window_spec: Optional[Dict[str, Tuple]] = None  # set by init_ring
        self._ingest = None   # jitted lazily once ring shapes exist

    # -- state/ring allocation --------------------------------------------
    def init_state(self, records) -> Dict[str, Any]:
        """Zero history buffers shaped after one rollout chunk's records."""
        hist = {}
        for key in self._hist_keys():
            # records leaf (K, N, ...) -> hist (N, L, ...); 'obs' may be a
            # pytree (dict observations), so map over leaves
            hist[key] = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (leaf.shape[1], self.L) + leaf.shape[2:], leaf.dtype),
                records[key])
        return {'hist': hist,
                'counts': jnp.zeros((records['done'].shape[1],), jnp.int32)}

    def _hist_keys(self):
        keys = ['obs', 'action', 'prob', 'amask', 'value']
        keys.append('acting' if self.mode == 'solo' else 'player')
        if self.has_reward:
            keys.append('reward')
        return keys

    def init_ring(self, records) -> Dict[str, Any]:
        """Zero ring buffers, shaped via eval_shape — NOTHING runs on
        device here. (Running the window builder eagerly, op by op, makes
        every un-jitted op its own compile + dispatch.)

        Ring storage is FLATTENED per window: leaf (capacity, prod(shape)).
        TPU tiled layouts pad the two minormost dims to (8, 128); storing
        windows in natural (T, P, ...) shape put tiny trailing dims (e.g.
        Hungry Geese's 7x11 board) in the tile, inflating a 4 GB ring to a
        31 GB allocation. 2-D storage pads ~1%; consumers reshape after
        gather via ``window_spec``."""
        def spec_of(key):
            return jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(
                    (self.L,) + tuple(leaf.shape[2:]), leaf.dtype),
                records[key])

        hist1 = {k: spec_of(k) for k in self._hist_keys()}
        if self.has_reward:
            hist1['return'] = hist1['reward']
        outcome1 = jax.ShapeDtypeStruct((self.P,), jnp.float32)
        ts = jax.ShapeDtypeStruct((1,), jnp.int32)
        s_one = jax.ShapeDtypeStruct((), jnp.int32)
        if self.mode == 'solo':
            win = jax.eval_shape(
                lambda h, s, t, seat, oc: build_windows_solo(
                    h, s, t, seat, oc, self.fs, self.bi, self.L),
                hist1, s_one, ts, ts, outcome1)
        else:
            win = jax.eval_shape(
                lambda h, s, t, oc: build_windows_turn(
                    h, s, t, oc, self.fs, self.bi, self.L, self.P),
                hist1, s_one, ts, outcome1)
        self.window_spec = {k: (tuple(w.shape[1:]), w.dtype)
                            for k, w in win.items()}
        return {k: jnp.zeros(
                    (self.capacity, int(np.prod(shape)) if shape else 1),
                    dtype)
                for k, (shape, dtype) in self.window_spec.items()}

    def unflatten_rows(self, rows: Dict[str, Any]) -> Dict[str, Any]:
        """(n, flat) ring rows -> batch pytree: (n,) + window shape per
        leaf, dotted keys rebuilt into the nested observation."""
        return unflatten_window_keys(
            {k: v.reshape((v.shape[0],) + self.window_spec[k][0])
             for k, v in rows.items()})

    # -- the ingest program ------------------------------------------------
    def ingest(self, records, state, ring, cursor, size, rng):
        if self._ingest is None:
            # donate history/ring/cursor/size/rng: the trainer thread is the
            # single owner and always rebinds them from the outputs
            self._ingest = jax.jit(self.ingest_fn(),
                                   donate_argnums=(1, 2, 3, 4, 5))
        return self._ingest(records, state, ring, cursor, size, rng)

    def ingest_fn(self):
        """The pure (un-jitted) chunk-ingest function — used by the jitted
        standalone path above and inlined into the fused
        generate+ingest+train program (ops/fused_pipeline.py)."""
        return self._build_ingest()

    def _build_ingest(self):
        fs, bi, L, W, cap = self.fs, self.bi, self.L, self.W, self.capacity
        P, gamma, mode = self.P, self.gamma, self.mode
        has_reward = self.has_reward
        hist_record_keys = [k for k in self._hist_keys() if k != 'return']

        def ply(carry, rec):
            hist, counts, ring, cursor, size, rng = carry
            hist = dict(hist)   # never mutate the traced carry structure
            N = counts.shape[0]
            rows = jnp.arange(N)
            idx = jnp.clip(counts, 0, L - 1)

            for key in hist_record_keys:
                hist[key] = jax.tree_util.tree_map(
                    lambda h, r: h.at[rows, idx].set(r),
                    hist[key], rec[key])
            counts = counts + 1
            done = rec['done']                       # (N,) bool
            S = counts                               # (N,) episode lengths
            rng, k_ts, k_seat = jax.random.split(rng, 3)
            outcome = rec['outcome']                 # (N, P)

            def finalize(_):
                """Returns recompute + window build + ring scatter — only
                reached on plies where some episode actually ended (most
                plies skip all of this via the cond below)."""
                win_hist = dict(hist)
                if has_reward:
                    valid = (jnp.arange(L)[None, :] < S[:, None])  # (N, L)
                    win_hist['return'] = jax.vmap(
                        _discounted_returns, in_axes=(0, 0, None))(
                            hist['reward'], valid, gamma)

                # windows per finished episode: the host ingestion rate
                wcount = jnp.clip(S // fs, 1, W)     # (N,)
                span = jnp.maximum(S - fs, 0) + 1    # train_start in [0, span)
                u = jax.random.uniform(k_ts, (N, W))
                ts = jnp.minimum((u * span[:, None]).astype(jnp.int32),
                                 span[:, None] - 1)

                if mode == 'solo':
                    seat = jax.random.randint(k_seat, (N, W), 0, P)
                    windows = jax.vmap(
                        build_windows_solo,
                        in_axes=(0, 0, 0, 0, 0, None, None, None))(
                            win_hist, S, ts, seat, outcome, fs, bi, L)
                else:
                    windows = jax.vmap(
                        build_windows_turn,
                        in_axes=(0, 0, 0, 0, None, None, None, None))(
                            win_hist, S, ts, outcome, fs, bi, L, P)

                # ring slots with prefix-sum compaction over done envs
                dcount = jnp.where(done, wcount, 0)  # (N,)
                base = cursor + jnp.cumsum(dcount) - dcount
                w_ix = jnp.arange(W)[None, :]
                slot = (base[:, None] + w_ix) % cap
                valid_w = done[:, None] & (w_ix < wcount[:, None])
                slot = jnp.where(valid_w, slot, cap)  # cap = dropped
                flat_slot = slot.reshape(-1)

                def scatter(rb, wb):
                    # ring rows are flat (see init_ring): (N, W, ...) ->
                    # (N*W, prod(window shape))
                    return rb.at[flat_slot].set(
                        wb.reshape((wb.shape[0] * wb.shape[1], -1)),
                        mode='drop')

                return (jax.tree_util.tree_map(scatter, ring, windows),
                        jnp.sum(dcount))

            ring, n_new = jax.lax.cond(
                jnp.any(done), finalize,
                lambda _: (ring, jnp.int32(0)), None)
            cursor = (cursor + n_new) % cap
            size = jnp.minimum(size + n_new, cap)
            counts = jnp.where(done, 0, counts)
            return ((hist, counts, ring, cursor, size, rng),
                    (jnp.sum(done), n_new))

        def ingest(records, state, ring, cursor, size, rng):
            rec_scan = {k: records[k] for k in hist_record_keys}
            rec_scan['done'] = records['done']
            rec_scan['outcome'] = records['outcome']
            ((hist, counts, ring, cursor, size, rng),
             (dones, wins)) = jax.lax.scan(
                ply, (state['hist'], state['counts'], ring, cursor, size,
                      rng), rec_scan)
            return ({'hist': hist, 'counts': counts}, ring, cursor, size,
                    rng, jnp.sum(dones), jnp.sum(wins))

        return ingest
