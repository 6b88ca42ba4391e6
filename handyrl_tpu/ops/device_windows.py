"""On-device window assembly: rollout records -> replay ring, zero host copies.

The host splice path (device_generation.step_chunk -> moment dicts -> bz2 ->
ingest decompress -> build_window -> ring push) rebuilds every episode in
Python: ~chunk_steps x n_envs dict constructions per dispatch. On a single
host core that, not the accelerator, bounds the fully-device pipeline.

This module closes the loop in HBM, and its cost follows what it stores. A
per-lane episode history lives on device as a CIRCULAR buffer in the ring's
own row layout (one flat row a ply and leaf; the solo observation one row a
ply and seat; DeviceWindower.init_state); one program consumes a rollout
chunk:

  * the chunk's K plies of every lane go into the history as one block a
    leaf;
  * game lengths, the windows due ``clip(steps // forward_steps, 1, W)``
    (the host ingestion rate, train.py _ingest_new_episodes) and the random
    train starts and seats are computed for the whole (K, N) chunk at once,
    from one key pair a ply;
  * then ONE LOOP builds the windows of the games that ended in the chunk,
    one window an iteration, in slot order (ply, lane, window): it gathers
    the T history rows of that lane (and seat), applies the EXACT pad/mask
    semantics of ops/batch.py build_window (reference train.py:33-124):
    prob pad 1, action_mask pad +1e32, value tail = final outcome, progress
    pad 1, episode/turn/observation masks, and writes one row of each ring
    leaf in place. Its trip count is the number of windows stored: no lane
    whose game goes on is read, nothing of size lanes x windows or of the
    history's size is gathered, copied or scattered, and there is no cap on
    how many games may end on a ply.

The ring is bit-identical to what the all-lane builder left (every lane's W
windows on each ply on which any lane ended a game, the finished lanes' kept
by a drop-scatter), which is the parity oracle in tests/windower_oracle.py.

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


def _mask_rows(x, fill, cond):
    """x (T, ...) with rows where ``cond`` (T,) is false replaced by fill."""
    return jnp.where(cond.reshape((-1,) + (1,) * (x.ndim - 1)), x, fill)


def _padded_mask(amask, cond):
    """The illegal-action mask's rows (T, 1, ...) in the dtype the game's
    twin recorded it in, every id illegal where ``cond`` (T,) is false:
    +1e32 in a float mask, all bits set in a ``uint8`` one (ops/maskbits.py),
    which stays ``uint8`` into the ring."""
    if amask.dtype == jnp.uint8:
        return _mask_rows(amask, jnp.uint8(255), cond)
    return _mask_rows(amask, 1e32, cond).astype(jnp.float32)


def _masked_obs(x, cond):
    """Observation rows (T, ...) given a player axis, zero where ``cond``
    (T,) is false: 0.0 for boards, the id 0 for integer observations (a
    float fill would make the leaf float)."""
    fill = 0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0
    return _mask_rows(x[:, None], fill, cond)


def _window_solo(take, S, ts_w, seat_w, outcome, fs: int, bi: int, L: int,
                 has_reward: bool, first_position: bool = False):
    """ONE solo-layout window. ``take(key, idxm)`` returns the EVALUATED
    SEAT's values of that history leaf at game plies idxm (T,): (T, ...),
    whatever the storage (the observation may stay flat: only its leading
    axis is used here). With ``first_position`` the window also says which
    game ply its first row is (a net that reads a window as a sequence
    places rotary phases, chunks and windows by absolute position)."""
    T = bi + fs
    m = ts_w - bi + jnp.arange(T)                    # (T,)
    in_ep = (m >= 0) & (m < S)
    idxm = jnp.clip(m, 0, L - 1)
    valid = in_ep & take('acting', idxm)
    tail = (m >= S)

    obs = jax.tree_util.tree_map(              # obs may be a pytree
        lambda x: _masked_obs(x, valid), take('obs', idxm))
    prob = jnp.where(valid, take('prob', idxm), 1.0)
    act = jnp.where(valid, take('action', idxm), 0)
    amask = _padded_mask(take('amask', idxm)[:, None], valid)
    val = jnp.where(valid, take('value', idxm)[:, 0],
                    jnp.where(tail, outcome[seat_w], 0.0))
    if has_reward:
        rew = jnp.where(in_ep, take('reward', idxm), 0.0)
        ret = jnp.where(in_ep, take('return', idxm), 0.0)
    else:
        rew = jnp.zeros((T,), jnp.float32)
        ret = jnp.zeros((T,), jnp.float32)
    progress = jnp.where(in_ep, m.astype(jnp.float32) / S, 1.0)
    f32 = jnp.float32
    window = {
        'observation': obs,
        'selected_prob': prob.astype(f32)[:, None, None],
        'action': act.astype(jnp.int32)[:, None, None],
        'action_mask': amask,
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
        window['first_position'] = m[:1].astype(jnp.int32).reshape(1, 1, 1)
    return window


def _window_turn(take, S, ts_w, outcome, fs: int, bi: int, L: int,
                 num_players: int, has_reward: bool):
    """ONE turn-layout window. ``take(key, idxm)`` returns that history
    leaf (the turn player's data; reward/return for all P) at game plies
    idxm (T,): (T, ...). Mask/value leaves span all P players, data leaves
    P axis 1."""
    T = bi + fs
    P = num_players
    m = ts_w - bi + jnp.arange(T)
    in_ep = (m >= 0) & (m < S)
    idxm = jnp.clip(m, 0, L - 1)
    player = take('player', idxm)                    # (T,)
    tail = (m >= S)

    obs = jax.tree_util.tree_map(              # obs may be a pytree
        lambda x: _mask_rows(x[:, None], 0.0, in_ep), take('obs', idxm))
    prob = jnp.where(in_ep, take('prob', idxm), 1.0)
    act = jnp.where(in_ep, take('action', idxm), 0)
    amask = _mask_rows(take('amask', idxm)[:, None], 1e32, in_ep)
    # (T, P) per-player masks: the turn player acted and observed
    is_turn = (player[:, None] == jnp.arange(P)[None, :]) & in_ep[:, None]
    val_turn = take('value', idxm)[:, 0]             # (T,)
    val = jnp.where(is_turn, val_turn[:, None],
                    jnp.where(tail[:, None], outcome[None, :], 0.0))
    if has_reward:
        rew = jnp.where(in_ep[:, None], take('reward', idxm), 0.0)  # (T, P)
        ret = jnp.where(in_ep[:, None], take('return', idxm), 0.0)
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


def build_windows_solo(hist: Dict[str, Any], S, ts, seat, outcome,
                       fs: int, bi: int, L: int,
                       first_position: bool = False):
    """Windows for ONE env in solo layout, from a game-ordered history.

    hist leaves are (L, P, ...); S scalar episode length; ts (W,) train
    starts; seat (W,) evaluated seats; outcome (P,). Returns a window dict
    with leading axis W. (The shape oracle of ``init_ring`` and the host
    parity tests; the ingest reads its flat history through the same
    ``_window_solo``.)
    """
    def one(ts_w, seat_w):
        take = lambda key, idxm: jax.tree_util.tree_map(
            lambda x: x[idxm][:, seat_w], hist[key])
        return flatten_window_keys(_window_solo(
            take, S, ts_w, seat_w, outcome, fs, bi, L, 'reward' in hist,
            first_position))

    return jax.vmap(one)(ts, seat)


def build_windows_turn(hist: Dict[str, Any], S, ts, outcome,
                       fs: int, bi: int, L: int, num_players: int):
    """Windows for ONE env in turn-based (observation=False) layout.

    hist leaves are (L, ...) with the turn player's data per ply plus
    hist['player'] (L,); outcome (P,). Returns a window dict with leading
    axis W; mask/value leaves span all P players, data leaves P axis 1.
    """
    def one(ts_w):
        take = lambda key, idxm: jax.tree_util.tree_map(
            lambda x: x[idxm], hist[key])
        return flatten_window_keys(_window_turn(
            take, S, ts_w, outcome, fs, bi, L, num_players,
            'reward' in hist))

    return jax.vmap(one)(ts)


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


def _ply_width(leaf, rows: int) -> int:
    """Values in one history row of a records leaf (K, N, ...) that takes
    ``rows`` rows a ply."""
    return int(np.prod(leaf.shape[2:])) // rows


def _row_width(flat: int) -> int:
    """Stored width of a row of ``flat`` values, in the history and in the
    ring: a wide row is padded to whole 128-lane tiles. With no padding in
    it, row-major order is the layout the device gives the buffer by
    default, which is the one a row write and a row gather use; a
    1,309-wide history row or a 20,944-wide ring row would get the row
    axis minor instead (least padding), and a relayout of the whole buffer
    into every program that touches it and out again."""
    return -(-flat // 128) * 128 if flat > 128 else flat


def _pad_rows(x, width: int):
    """x (..., flat) with zeros appended to its minor axis up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _first_true(mask):
    """Flat indices of the true entries of ``mask``, in increasing order,
    then the others: (mask.size,) int32. A stable sort: ``jnp.nonzero`` with
    a static size lowers to a scatter-add over every entry. Keep ``mask``
    to the (K, N) chunk: the v5e's compiler takes 14 s over a sort of
    24,576 flags and 0.2 s over one of 2,048."""
    return jnp.argsort(jnp.logical_not(mask.reshape(-1)),
                       stable=True).astype(jnp.int32)


class DeviceWindower:
    """Owns the per-env episode history and the chunk-ingest function.

    ``ingest_fn()(records, state, ring, cursor, size, rng)`` consumes one
    rollout chunk and returns updated (state, ring, cursor, size, rng,
    n_done, n_windows). The ring/state/cursor/size live as device arrays
    owned by the caller (the fused program, which donates them in place).

    THE CONTRACT: a game is at most ``max_steps`` plies (the env module's
    MAX_STEPS / MAX_PLIES; train.py asserts that it declares one). The
    circular history holds ``max_steps`` plies plus the rest of a chunk a
    lane, so a longer game would overwrite its own first plies before its
    windows are built.
    """

    def __init__(self, mode: str, fs: int, bi: int, max_steps: int,
                 windows_cap: int, capacity: int, num_players: int,
                 gamma: float, has_reward: bool,
                 first_position: bool = False):
        assert mode in ('solo', 'turn')
        # a ``first_position`` leaf a window, for a net that asks for it
        # (one that declares ``sequence``); solo layout only
        assert not first_position or mode == 'solo'
        self.first_position = first_position
        self.mode = mode
        self.fs, self.bi = fs, bi
        self.L = max_steps
        self.W = max(1, windows_cap)
        self.capacity = capacity
        self.P = num_players
        self.gamma = gamma
        self.has_reward = has_reward
        self.window_spec: Optional[Dict[str, Tuple]] = None  # set by init_ring

    # -- state/ring allocation --------------------------------------------
    def _hist_keys(self):
        keys = ['obs', 'action', 'prob', 'amask', 'value']
        keys.append('acting' if self.mode == 'solo' else 'player')
        if self.has_reward:
            keys.append('reward')
        return keys

    def _rows_per_ply(self, key):
        """History rows one ply takes in that leaf: the solo observation
        keeps one row PER SEAT, so a window gathers its seat's rows and
        never touches the other seats'; every other leaf is one row."""
        return self.P if (self.mode == 'solo' and key == 'obs') else 1

    def init_state(self, records) -> Dict[str, Any]:
        """Zero history shaped after one rollout chunk's records.

        The history is a CIRCULAR buffer of C plies a lane, written a
        whole chunk at a time at ``head``, in the ring's own row layout:
        records leaf (K, N, ...) -> (N, C * rows, width) with the ply's
        values flattened into the minor axis (natural (..., 7, 11) minor
        dimensions are tiled to (8, 128) on the TPU; see init_ring) and a
        wide row padded to whole tiles (_row_width). C is
        the smallest multiple of K that holds a longest game (L plies)
        plus the rest of the chunk it ended in, so that at the chunk's end
        every game that ended inside it is still whole, and a chunk never
        wraps. Every lane advances one ply a chunk step, so ``head`` is one
        number; it is stored per lane only so that the whole state splits
        along the lane axis on a mesh."""
        K, N = records['done'].shape
        C = K * -(-(self.L + K - 1) // K)
        hist = {}
        for key in self._hist_keys():
            rows = self._rows_per_ply(key)
            # 'obs' may be a pytree (dict observations): map over leaves
            hist[key] = jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (N, C * rows, _row_width(_ply_width(leaf, rows))),
                    leaf.dtype),
                records[key])
        return {'hist': hist,
                'counts': jnp.zeros((N,), jnp.int32),
                'head': jnp.zeros((N,), jnp.int32)}

    def init_ring(self, records) -> Dict[str, Any]:
        """Zero ring buffers, shaped via eval_shape — NOTHING runs on
        device here. (Running the window builder eagerly, op by op, makes
        every un-jitted op its own compile + dispatch.)

        Ring storage is FLATTENED per window: leaf (capacity, stored width
        of prod(shape) values). TPU tiled layouts pad the two minormost
        dims to (8, 128); storing windows in natural (T, P, ...) shape put
        tiny trailing dims (e.g. Hungry Geese's 7x11 board) in the tile,
        inflating a 4 GB ring to a 31 GB allocation. The whole window row
        is padded once (_row_width: 20,944 -> 20,992 values), so that the
        device keeps the ring by rows, as the window loop writes it and the
        SGD loop gathers it, and no dispatch relays it. ``window_spec``
        keeps the LOGICAL window shapes; consumers strip the padding and
        reshape after the gather (``unflatten_rows``)."""
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
                    h, s, t, seat, oc, self.fs, self.bi, self.L,
                    self.first_position),
                hist1, s_one, ts, ts, outcome1)
        else:
            win = jax.eval_shape(
                lambda h, s, t, oc: build_windows_turn(
                    h, s, t, oc, self.fs, self.bi, self.L, self.P),
                hist1, s_one, ts, outcome1)
        self.window_spec = {k: (tuple(w.shape[1:]), w.dtype)
                            for k, w in win.items()}
        return {k: jnp.zeros(
                    (self.capacity, _row_width(int(np.prod(shape)))), dtype)
                for k, (shape, dtype) in self.window_spec.items()}

    def unflatten_rows(self, rows: Dict[str, Any]) -> Dict[str, Any]:
        """(n, stored width) ring rows -> batch pytree: the ONE place that
        strips a row's padding (_row_width) and restores (n,) + window shape
        per leaf, dotted keys rebuilt into the nested observation."""
        def window(key, v):
            shape = self.window_spec[key][0]
            return v[:, :int(np.prod(shape))].reshape((v.shape[0],) + shape)
        return unflatten_window_keys(
            {k: window(k, v) for k, v in rows.items()})

    # -- the ingest program ------------------------------------------------
    def ingest_fn(self):
        """The pure (un-jitted) chunk-ingest function, inlined into the
        fused generate+ingest+train program (ops/fused_pipeline.py)."""
        fs, bi, L, W, cap = self.fs, self.bi, self.L, self.W, self.capacity
        P, gamma, solo = self.P, self.gamma, self.mode == 'solo'
        has_reward = self.has_reward
        first_position = self.first_position
        T = bi + fs
        hist_keys = self._hist_keys()

        def ingest(records, state, ring, cursor, size, rng):
            done = records['done']                   # (K, N) bool
            K, N = done.shape
            C = state['hist']['prob'].shape[1]
            assert C % K == 0 and C >= L + K - 1, \
                'history of %d plies a lane was not made for chunks of %d' \
                % (C, K)
            head = state['head'][0]

            # 1. the chunk's K plies of every lane, one block a leaf. The
            # plies are flattened into rows one at a time: a scan over the
            # ply axis keeps that axis major in the records; flattened in
            # one piece the compiler makes it minor, and the rollout's
            # per-ply writes into the records cost 5 ms a chunk more (v5e)
            tree_map = jax.tree_util.tree_map
            stored = state['hist']           # exactly the hist_keys leaves

            def as_rows(h, rec):         # one ply -> (N, rows a ply, width)
                return _pad_rows(rec.reshape((N, h.shape[1] // C, -1)),
                                 h.shape[2])

            def store(h, rows):          # (K, N, rows a ply, width)
                return jax.lax.dynamic_update_slice(
                    h, jnp.swapaxes(rows, 0, 1).reshape((N, -1, h.shape[2])),
                    (0, head * (h.shape[1] // C), 0))
            hist = tree_map(store, stored, jax.lax.map(
                lambda ply: tree_map(as_rows, stored, ply),
                {key: records[key] for key in hist_keys}))

            # 2. every (ply, lane)'s game length so far: the plies since
            # the lane's last end in this chunk, or since before it
            step = jnp.arange(1, K + 1, dtype=jnp.int32)[:, None]
            ended = jax.lax.cummax(jnp.where(done, step, 0), axis=0)
            ended = jnp.concatenate(
                [jnp.zeros((1, N), jnp.int32), ended[:-1]])
            S = step - ended + jnp.where(ended == 0, state['counts'], 0)
            first = head + step - S          # the game's first ply (mod C)

            # 3. the host ingestion rate and the random train starts and
            # seats, from one key pair a ply (finished lanes use theirs)
            def ply_keys(key, _):
                key, k_ts, k_seat = jax.random.split(key, 3)
                return key, (k_ts, k_seat)
            rng, (k_ts, k_seat) = jax.lax.scan(ply_keys, rng, None, length=K)
            wcount = jnp.clip(S // fs, 1, W)         # (K, N)
            span = jnp.maximum(S - fs, 0) + 1        # train_start in [0, span)
            u = jax.vmap(lambda k: jax.random.uniform(k, (N, W)))(k_ts)
            ts = jnp.minimum((u * span[..., None]).astype(jnp.int32),
                             span[..., None] - 1)    # (K, N, W)
            if solo:
                seat = jax.vmap(
                    lambda k: jax.random.randint(k, (N, W), 0, P))(k_seat)

            def rows_of(key, n, at):
                """That lane's history rows ``at``, less their padding."""
                rows = self._rows_per_ply(key)
                return tree_map(
                    lambda h, rec: h[n, at][:, :_ply_width(rec, rows)],
                    hist[key], records['reward' if key == 'return' else key])

            # 4. the games that ended, in slot order (ply, then lane), and
            # with rewards their discounted returns, game by game
            games = _first_true(done)
            if has_reward:
                def returns_of(i, returns):
                    k, n = games[i] // N, games[i] % N
                    at = (first[k, n] + jnp.arange(L)) % C
                    valid = jnp.arange(L) < S[k, n]
                    ret = _discounted_returns(
                        rows_of('reward', n, at), valid, gamma)
                    return returns.at[n, jnp.where(valid, at, C)].set(
                        ret, mode='drop')
                hist['return'] = jax.lax.fori_loop(
                    0, jnp.sum(done), returns_of,
                    jnp.zeros_like(hist['reward']))

            # 5. one window an iteration, game after game and a game's
            # windows in turn (so in slot order): T history rows of one
            # lane (and seat) in, one ring row out
            n_win = jnp.sum(jnp.where(done, wcount, 0))

            def build(j, carry):
                ring, game, w = carry
                k, n = games[game] // N, games[game] % N

                def take(key, idxm):
                    at = (first[k, n] + idxm) % C
                    if key == 'obs':   # stays flat: rows in, a ring row out
                        return rows_of(key, n, at * P + seat[k, n, w]
                                       if solo else at)
                    ply = records['reward' if key == 'return' else key]
                    x = rows_of(key, n, at).reshape((T,) + ply.shape[2:])
                    return x[:, seat[k, n, w]] if solo else x

                if solo:
                    win = _window_solo(
                        take, S[k, n], ts[k, n, w], seat[k, n, w],
                        records['outcome'][k, n], fs, bi, L, has_reward,
                        first_position)
                else:
                    win = _window_turn(
                        take, S[k, n], ts[k, n, w],
                        records['outcome'][k, n], fs, bi, L, P, has_reward)
                win = flatten_window_keys(win)
                slot = (cursor + j) % cap
                ring = {key: jax.lax.dynamic_update_slice(
                            rb, _pad_rows(win[key].reshape((1, -1)),
                                          rb.shape[1]), (slot, 0))
                        for key, rb in ring.items()}
                last = w + 1 == wcount[k, n]
                return ring, game + last, jnp.where(last, 0, w + 1)
            ring, _, _ = jax.lax.fori_loop(
                0, n_win, build, (ring, jnp.int32(0), jnp.int32(0)))

            state = {'hist': {key: hist[key] for key in hist_keys},
                     'counts': jnp.where(done[-1], 0, S[-1]),
                     'head': (state['head'] + K) % C}
            return (state, ring, (cursor + n_win) % cap,
                    jnp.minimum(size + n_win, cap), rng,
                    jnp.sum(done), n_win)

        return ingest
