"""Pure-JAX vectorized Geister (device-resident twin of envs/geister.py).

N games advance as one program. The board is a flat (N, 36) piece-code array
(-1 empty, else color*2 + type with type 0=blue, 1=red); the setup phase is
part of the action space (ids 144..213 pick one of the 70 blue layouts) so
the policy drives it like any other move; move decode/encode uses
precomputed per-color lookup tables (actions are always encoded from the
mover's rotated perspective, matching the host env's codec).

Observation = the acting player's view: 18 scalars + 7 board planes with
opponent piece types hidden and the second player's board rotated 180
degrees — identical semantics to the host env's ``observation`` (the
imperfect-information surface).
"""

from __future__ import annotations

import itertools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

NUM_PLAYERS = 2
BOARD = 36
N_MOVE = 4 * BOARD          # 144
N_SET = 70
N_ACTIONS = N_MOVE + N_SET  # 214
MAX_PLIES = 200
# the longest game in env steps (what a rollout records): the two set-up
# plies come before the MAX_PLIES moves of a drawn game. Buffers that hold a
# whole game are sized from this (ops/device_windows.py)
MAX_STEPS = MAX_PLIES + 2
SIMULTANEOUS = False
# the host env hides piece colors behind its own rng (secret setup); device
# records cannot replay through the host sampling contract byte-identically
RNG_COMPAT = 'device'

BLUE, RED = 0, 1

# ---- precomputed tables (numpy, at import) -------------------------------

_STEPS = np.array([(-1, 0), (0, -1), (0, 1), (1, 0)], np.int32)
_GOALS = np.array([[(-1, 5), (6, 5)], [(-1, 0), (6, 0)]], np.int32)
_LAYOUTS = np.array(list(itertools.combinations(range(8), 4)), np.int32)

# home squares as flat cells, layout-slot order (matches the host env)
def _sq(s):
    return 'ABCDEF'.find(s[0]) * 6 + '123456'.find(s[1])

_HOME = np.array([
    [_sq(s) for s in ['B2', 'C2', 'D2', 'E2', 'B1', 'C1', 'D1', 'E1']],
    [_sq(s) for s in ['E5', 'D5', 'C5', 'B5', 'E6', 'D6', 'C6', 'B6']],
], np.int32)

# layout -> per-slot piece type for each color: (70, 8)
_LAYOUT_TYPES = np.ones((N_SET, 8), np.int32)
for _i, _combo in enumerate(_LAYOUTS):
    _LAYOUT_TYPES[_i, _combo] = 0          # chosen slots are blue

# move decode per color: from-cell, to-cell (-1 = offboard), goal flag
_MOVE_FROM = np.zeros((2, N_MOVE), np.int32)
_MOVE_TO = np.full((2, N_MOVE), -1, np.int32)
_MOVE_GOAL = np.zeros((2, N_MOVE), bool)
for _c in range(2):
    for _a in range(N_MOVE):
        d, sq36 = _a // BOARD, _a % BOARD
        x, y = sq36 // 6, sq36 % 6
        if _c == 1:
            x, y = 5 - x, 5 - y
            d = 3 - d
        tx, ty = x + _STEPS[d][0], y + _STEPS[d][1]
        _MOVE_FROM[_c, _a] = x * 6 + y
        if 0 <= tx < 6 and 0 <= ty < 6:
            _MOVE_TO[_c, _a] = tx * 6 + ty
        else:
            _MOVE_GOAL[_c, _a] = any(
                tx == g[0] and ty == g[1] for g in _GOALS[_c])

MOVE_FROM = jnp.asarray(_MOVE_FROM)
MOVE_TO = jnp.asarray(_MOVE_TO)
MOVE_GOAL = jnp.asarray(_MOVE_GOAL)
HOME = jnp.asarray(_HOME)
LAYOUT_TYPES = jnp.asarray(_LAYOUT_TYPES)
ROT_PERM = jnp.asarray(np.arange(BOARD)[::-1].copy())


class State(NamedTuple):
    board: jnp.ndarray       # (N, 36) int8: -1 empty, else color*2+type
    color: jnp.ndarray       # (N,) int8 side to move
    plies: jnp.ndarray       # (N,) int32, starts at -2 (setup phase)
    win: jnp.ndarray         # (N,) int8: -1 none, 0/1 winner, 2 draw
    counts: jnp.ndarray      # (N, 4) int32 alive per piece code


def init_state(n: int, seed: int = 0) -> State:
    return State(
        board=jnp.full((n, BOARD), -1, jnp.int8),
        color=jnp.zeros((n,), jnp.int8),
        plies=jnp.full((n,), -2, jnp.int32),
        win=jnp.full((n,), -1, jnp.int8),
        counts=jnp.zeros((n, 4), jnp.int32),
    )


def turn(state: State) -> jnp.ndarray:
    return state.color.astype(jnp.int32)


def terminal(state: State) -> jnp.ndarray:
    return state.win >= 0


def outcome(state: State) -> jnp.ndarray:
    """(N, 2): +1/-1 for a win, 0 for draw/unfinished."""
    w = state.win
    first = jnp.where(w == 0, 1.0, jnp.where(w == 1, -1.0, 0.0))
    return jnp.stack([first, -first], axis=1)


def rewards(state: State) -> jnp.ndarray:
    """(N, 2) per-ply rewards: -0.01 to both players every ply (the host
    env's ply-cost shaping, envs/geister.py reward())."""
    n = state.board.shape[0]
    return jnp.full((n, NUM_PLAYERS), -0.01, jnp.float32)


def legal_mask(state: State) -> jnp.ndarray:
    """(N, 214) float 1 = legal for the side to move."""
    n = state.board.shape[0]
    setup = state.plies < 0

    c = state.color.astype(jnp.int32)
    piece = state.board.astype(jnp.int32)
    own = (piece >= 0) & (piece // 2 == c[:, None])            # (N, 36)
    own_from = jnp.take_along_axis(own, MOVE_FROM[c], axis=1)  # (N, 144)
    to = MOVE_TO[c]                                            # (N, 144)
    to_piece = jnp.take_along_axis(piece, jnp.maximum(to, 0), axis=1)
    to_own = (to_piece >= 0) & (to_piece // 2 == c[:, None])
    onboard_ok = (to >= 0) & ~to_own
    from_type = jnp.take_along_axis(piece, MOVE_FROM[c], axis=1) % 2
    goal_ok = (to < 0) & MOVE_GOAL[c] & (from_type == BLUE)
    move_legal = own_from & (onboard_ok | goal_ok)

    mask = jnp.concatenate([
        jnp.where(setup[:, None], False, move_legal),
        jnp.broadcast_to(setup[:, None], (n, N_SET)),
    ], axis=1)
    return mask.astype(jnp.float32)


def step(state: State, actions: jnp.ndarray) -> State:
    n = state.board.shape[0]
    c = state.color.astype(jnp.int32)
    piece_self_base = c * 2
    setup = state.plies < 0

    # ---- setup branch: place 8 pieces per the chosen layout --------------
    layout = jnp.clip(actions - N_MOVE, 0, N_SET - 1)
    types = LAYOUT_TYPES[layout]                              # (N, 8)
    home = HOME[c]                                            # (N, 8)
    set_board = state.board
    set_pieces = (piece_self_base[:, None] + types).astype(jnp.int8)
    set_board = set_board.at[jnp.arange(n)[:, None], home].set(
        jnp.where(setup[:, None], set_pieces,
                  jnp.take_along_axis(state.board, home, axis=1)))
    # a setup always places 4 blue + 4 red for the mover
    setup_add = (jax.nn.one_hot(piece_self_base, 4, dtype=jnp.int32)
                 + jax.nn.one_hot(piece_self_base + 1, 4, dtype=jnp.int32)) * 4
    set_counts = state.counts + jnp.where(setup[:, None], setup_add, 0)

    # ---- move branch -----------------------------------------------------
    a = jnp.clip(actions, 0, N_MOVE - 1)
    frm = MOVE_FROM[c, a]
    to = MOVE_TO[c, a]
    is_goal = MOVE_GOAL[c, a] & (to < 0)
    moving = jnp.take_along_axis(state.board, frm[:, None], axis=1)[:, 0]
    target = jnp.take_along_axis(
        state.board, jnp.maximum(to, 0)[:, None], axis=1)[:, 0]
    captures = (~setup) & (to >= 0) & (target >= 0)
    cap_code = jnp.clip(target, 0, 3).astype(jnp.int32)

    move_board = state.board
    move_board = move_board.at[jnp.arange(n), frm].set(
        jnp.where(setup, moving, -1).astype(jnp.int8))
    # place mover on destination (only when staying on board)
    dest = jnp.maximum(to, 0)
    new_dest = jnp.where((~setup) & (to >= 0), moving,
                         jnp.take_along_axis(move_board, dest[:, None],
                                             axis=1)[:, 0])
    move_board = move_board.at[jnp.arange(n), dest].set(
        new_dest.astype(jnp.int8))

    move_counts = set_counts - jnp.where(
        captures[:, None],
        jax.nn.one_hot(cap_code, 4, dtype=jnp.int32), 0)
    # a goal escape removes the escaping piece from the board counts
    escape = (~setup) & is_goal
    move_counts = move_counts - jnp.where(
        escape[:, None],
        jax.nn.one_hot(jnp.clip(moving, 0, 3), 4, dtype=jnp.int32), 0)

    board = jnp.where(setup[:, None], set_board, move_board)
    counts = jnp.where(setup[:, None], set_counts, move_counts)

    # ---- wins ------------------------------------------------------------
    opp = 1 - c
    cap_all_blue = captures & (jnp.take_along_axis(
        counts, (opp * 2 + BLUE)[:, None], axis=1)[:, 0] == 0) \
        & (cap_code % 2 == BLUE)
    cap_all_red = captures & (jnp.take_along_axis(
        counts, (opp * 2 + RED)[:, None], axis=1)[:, 0] == 0) \
        & (cap_code % 2 == RED)
    plies = state.plies + 1
    win = state.win
    win = jnp.where((~setup) & is_goal, c.astype(jnp.int8), win)
    win = jnp.where(cap_all_blue & (win < 0), c.astype(jnp.int8), win)
    win = jnp.where(cap_all_red & (win < 0), opp.astype(jnp.int8), win)
    win = jnp.where((plies >= MAX_PLIES) & (win < 0), jnp.int8(2), win)

    return State(board=board, color=(1 - state.color).astype(jnp.int8),
                 plies=plies, win=win, counts=counts)


def observe(state: State) -> jnp.ndarray:
    """Acting player's view as a dict-free stack: this device twin returns
    {'scalar': (N, 18), 'board': (N, 7, 6, 6)} to match GeisterNet's input."""
    return observe_as(state, state.color.astype(jnp.int32))


def observe_as(state: State, viewer: jnp.ndarray) -> jnp.ndarray:
    """View for an arbitrary (N,) viewer seat (host observation(player),
    geister.py:302-340): board from the viewer's perspective, opponent
    piece types hidden, turn flag set when the viewer is to move."""
    c = viewer.astype(jnp.int32)
    opp = 1 - c
    piece = state.board.astype(jnp.int32)
    turn_view = (state.color.astype(jnp.int32) == c)

    def cnt(code):
        return jnp.take_along_axis(state.counts, code[:, None], axis=1)[:, 0]

    n_my_b, n_my_r = cnt(c * 2 + BLUE), cnt(c * 2 + RED)
    n_op_b, n_op_r = cnt(opp * 2 + BLUE), cnt(opp * 2 + RED)

    def onehot4(v):
        return jax.nn.one_hot(jnp.clip(v - 1, 0, 3), 4, dtype=jnp.float32) \
            * (v > 0)[:, None]

    scalar = jnp.concatenate([
        (c == 0).astype(jnp.float32)[:, None],
        turn_view.astype(jnp.float32)[:, None],
        onehot4(n_my_b), onehot4(n_my_r), onehot4(n_op_b), onehot4(n_op_r),
    ], axis=1)

    my_b = (piece == (c * 2 + BLUE)[:, None]).astype(jnp.float32)
    my_r = (piece == (c * 2 + RED)[:, None]).astype(jnp.float32)
    op_any = ((piece >= 0) & (piece // 2 == opp[:, None])).astype(jnp.float32)
    zeros = jnp.zeros_like(my_b)
    planes = jnp.stack([
        jnp.ones_like(my_b), my_b + my_r, op_any, my_b, my_r, zeros, zeros,
    ], axis=1)                                          # (N, 7, 36)
    # rotate 180 for the second player
    rotated = planes[:, :, ROT_PERM]
    planes = jnp.where((c == 1)[:, None, None], rotated, planes)
    board_planes = planes.reshape(-1, 7, 6, 6)
    return {'scalar': scalar, 'board': board_planes}


def auto_reset(state: State, done: jnp.ndarray) -> State:
    fresh = init_state(state.board.shape[0])
    pick = lambda f, s: jnp.where(done.reshape((-1,) + (1,) * (s.ndim - 1)), f, s)
    return State(*(pick(f, s) for f, s in zip(fresh, state)))
