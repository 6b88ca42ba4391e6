"""The window builder as it stood before PR 26, kept as the parity oracle.

``lax.cond`` over "any lane ended a game on this ply", then windows for ALL
lanes x W (``vmap`` of the per-env builders over the whole (N, L, ...)
history) and a scatter with ``mode='drop'`` that keeps the finished lanes'.
Copied verbatim from ``handyrl_tpu/ops/device_windows.py`` at commit a6bc120
(only the class header, the imports and ``init_ring`` are new): the
event-driven builder must leave ring, cursor, size, rng and both counts
bit-identical to this for the same records and key
(tests/test_device_windows.py). Its ring keeps the rows of that commit too,
as wide as the window and no wider: the production ring must hold the same
values in its logical columns and zeros in its padding.

Two additions for a net that reads a window as a sequence (PR 34), neither
of which touches a float observation's arithmetic: an integer observation is
masked with the id 0 of its own dtype, and with ``first_position`` a window
also carries the game ply of its first row.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from handyrl_tpu.ops.device_windows import (DeviceWindower,
                                            _discounted_returns,
                                            flatten_window_keys)


def _take(hist_leaf, idxm):
    """hist_leaf (L, ...) gathered at idxm (T,) -> (T, ...)."""
    return hist_leaf[idxm]


def build_windows_solo(hist: Dict[str, Any], S, ts, seat, outcome,
                       fs: int, bi: int, L: int, first_position=False):
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
            lambda x: vmask(_take(x, idxm)[:, seat_w][:, None],
                            0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                            else 0, valid),
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
        window = {
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
        if first_position:
            window['first_position'] = m[:1].astype(jnp.int32).reshape(
                1, 1, 1)
        return window

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



class OracleWindower(DeviceWindower):
    """DeviceWindower with the all-lane builder, its (N, L, ...) history
    and its ring of unpadded rows."""

    def init_ring(self, records) -> Dict[str, Any]:
        """The production ring's leaves at their logical width."""
        super().init_ring(records)               # sets window_spec
        return {k: jnp.zeros((self.capacity, int(np.prod(shape))), dtype)
                for k, (shape, dtype) in self.window_spec.items()}

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
                        in_axes=(0, 0, 0, 0, 0, None, None, None, None))(
                            win_hist, S, ts, seat, outcome, fs, bi, L,
                            self.first_position)
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
