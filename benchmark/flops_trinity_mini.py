"""Operations and bytes the Trinity-Mini trunk requires on this chip's
share, from shapes alone.

Counted: every matrix product's multiply-adds as 2 FLOP each (the five
attention projections of the heads held, the dense MLP, the router at its
published width, the shared expert, the routed experts at the EVEN share:
a position's 8 choices meet ``8 x held / published`` held experts, the head
over the ids held and the value row), and attention itself over the pairs
the equations name: for query n the keys ``max(first, n - window + 1) .. n``
on a sliding layer and ``first .. n`` on a full one, a product with the key
and one with the value each, for every query head. Not counted: norms,
rotary phases, softmax, the gate's sigmoid, the sort into groups, the loss,
V-trace and Adam. The backward pass is twice the forward; recomputation
(the learner rematerialises a layer at a time) is never counted. The
router's products are counted forward only where noted: it takes no
gradient, but its input does not either, so its backward is nothing.
"""

import numpy as np

_BYTES = {'bfloat16': 2, 'float32': 4}


def _layers(model):
    kinds = list(model['layer_types'])
    dense = int(model['dense_layers'])
    return kinds, dense, len(kinds) - dense


def attention_parameters(model):
    """W_q, W_g, W_o of the query heads held and W_k, W_v of the KV heads
    held, one layer."""
    D, d = model['hidden_size'], model['head_dim']
    return 3 * D * model['heads_held'] * d + 2 * D * model['kv_heads_held'] * d


def expert_parameters(model):
    """One expert (routed or shared): three matrices."""
    return 3 * model['hidden_size'] * model['expert_size']


def held_per_position(model):
    """Held experts a position meets in one layer at the even share."""
    return (model['experts_per_token'] * len(model['experts_held'])
            / model['experts_published'])


def matmul_parameters(model):
    """Parameters that a position multiplies with a gradient behind them:
    (attention, dense MLP, shared experts, routed experts at the even share,
    readout)."""
    _kinds, dense, expert = _layers(model)
    D = model['hidden_size']
    return ((dense + expert) * attention_parameters(model),
            dense * 3 * D * model['mlp_size'],
            expert * expert_parameters(model),
            expert * held_per_position(model) * expert_parameters(model),
            D * model['vocab'] + D)


def router_parameters(model):
    _kinds, _dense, expert = _layers(model)
    return expert * model['hidden_size'] * model['experts_published']


def attention_pairs(model, kind, positions, first_position=0):
    """Sum over the queries of a sequence of the keys each one sees."""
    W = model['window_size']
    total = 0
    for p in range(first_position, first_position + positions):
        seen = p - first_position + 1
        total += min(seen, W) if kind == 'sliding' else seen
    return total


def attention_flops(model, positions):
    """Forward FLOPs of attention proper over one sequence: a product with
    the key and one with the value for every pair and query head, over the
    layers of each kind."""
    kinds, _dense, _expert = _layers(model)
    pairs = sum(attention_pairs(model, kind, positions) for kind in kinds)
    return model['heads_held'] * 2 * 2 * model['head_dim'] * pairs


def forward_flops(model, positions):
    """One sequence of ``positions`` through the trunk, from position 0."""
    return (2 * positions * (sum(matmul_parameters(model))
                             + router_parameters(model))
            + attention_flops(model, positions))


def train_window_flops(model, train_args):
    """Forward + backward of one trained window: ``forward_steps`` positions
    forward and back (3 x forward; the router forward only) after
    ``burn_in_steps`` forward only."""
    fs = int(train_args['forward_steps'])
    bi = int(train_args.get('burn_in_steps') or 0)
    whole = forward_flops(model, bi + fs)
    return (3 * whole - 2 * forward_flops(model, bi)
            - 2 * 2 * fs * router_parameters(model))


def _dispatch(train_args):
    fs = int(train_args['forward_steps']) + int(
        train_args.get('burn_in_steps') or 0)
    windows = int(train_args['batch_size']) * int(
        train_args['sgd_steps_per_chunk'])
    sequences = int(train_args['generation_envs']) * 2
    return fs, windows, sequences, int(train_args['device_chunk_steps'])


def rollout_split(plies, ply_bytes, row_bytes, analytic_rows):
    """What a chunk of decode plies must read, split by what the fill moves:
    ``ply_bytes``, the bytes a ply reads whatever the caches hold (the
    actor's weights), and ``row_bytes``, a layer kind -> the bytes ONE more
    row in every sequence's cache costs a ply over the layers of that kind.
    ``analytic_rows`` are the rows a sequence that the counts took before PR
    52 and ``chunk_bytes`` still takes where no ply index is given: the mean
    over the plies of the games the env draws."""
    return {'plies': int(plies), 'ply_bytes': int(ply_bytes),
            'row_bytes': {k: int(v) for k, v in row_bytes.items()},
            'analytic_rows': {k: float(v) for k, v in analytic_rows.items()}}


def chunk_bytes(rollout, rows=None):
    """The bytes the ``plies`` decode plies of ONE chunk must read.
    ``rows``: a layer kind -> the rows a query of that kind sees, an array
    over (ply, sequence or lane) of THIS chunk (``rows_seen_at`` of the ply
    indices its counters had reached); without it, the analytic mean."""
    if rows is None:
        rows = rollout['analytic_rows']
    plies = rollout['plies']
    return plies * (rollout['ply_bytes'] + sum(
        per_row * float(np.mean(rows[kind]))
        for kind, per_row in rollout['row_bytes'].items()))


def rows_seen_at(model, kind, ply_index):
    """The K (or V) rows a decode query of a ``kind`` layer must see at the
    ply indices ``ply_index`` (0 at a game's first ply): ``p + 1`` on a full
    layer, as far as its buffer goes; ``min(p + 1, window)`` on a sliding
    one."""
    assert kind in ('full', 'sliding'), kind
    cap = model['window_size' if kind == 'sliding' else 'max_positions']
    return np.minimum(np.asarray(ply_index) + 1, cap)


def moe_experts_scope(model, train_args):
    """What the named scope ``moe_experts`` requires in ONE fused dispatch:
    ``sgd_flops``, forward and backward of the routed experts' products over
    the rows routed to the held experts at the even share (compute-bound),
    and ``rollout_bytes``, what a chunk of decode plies must read: every
    held expert's weights once a ply and layer (memory-bound: 2 rows an
    expert)."""
    fs, windows, _sequences, plies = _dispatch(train_args)
    _kinds, _dense, expert = _layers(model)
    sgd = 3 * windows * 2 * fs * expert * held_per_position(model) \
        * expert_parameters(model)
    rollout = plies * expert * len(model['experts_held']) \
        * expert_parameters(model) * _BYTES[model['actor_param_dtype']]
    return {'sgd_flops': int(sgd), 'rollout_bytes': int(rollout)}


def gqa_attention_scope(model, train_args):
    """What the named scope ``gqa_attention`` requires in ONE fused dispatch:
    ``sgd_flops``, forward and backward of the five projections and of
    attention over the pairs the equations name, and ``rollout_bytes``, what
    a chunk of decode plies must read: the actor's attention weights once a
    ply and layer, and every sequence's K and V rows that a query sees: the
    circle's ``window_size`` rows on a sliding layer, and on a full layer
    the rows the counter has reached. ``rollout`` is that count split
    (``rollout_split``): ``chunk_bytes`` of it at a chunk's own ply indices
    (``rows_seen_at``) is what that chunk required; ``rollout_bytes`` is its
    value at the analytic mean, a ply drawn uniformly from a game of mean
    length (games are log-uniform in [min_steps, max_steps])."""
    import math
    fs, windows, sequences, plies = _dispatch(train_args)
    kinds, _dense, _expert = _layers(model)
    sgd = 3 * windows * (2 * fs * len(kinds) * attention_parameters(model)
                         + attention_flops(model, fs))
    lo, hi = model['min_steps'], model['max_steps']
    # a ply drawn uniformly from a game's plies, games log-uniform in length:
    # E[position] = E[L^2] / (2 E[L])
    mean_len = (hi - lo) / math.log(hi / lo)
    mean_sq = (hi * hi - lo * lo) / (2 * math.log(hi / lo))
    mean_rows = mean_sq / (2 * mean_len)
    row = model['kv_heads_held'] * model['head_dim'] * 2 \
        * _BYTES[model['compute_dtype']]
    analytic = {'sliding': min(model['window_size'], mean_rows),
                'full': mean_rows}
    split = rollout_split(
        plies,
        len(kinds) * attention_parameters(model)
        * _BYTES[model['actor_param_dtype']],
        {kind: kinds.count(kind) * sequences * row for kind in analytic},
        analytic)
    return {'sgd_flops': int(sgd), 'rollout_bytes': int(chunk_bytes(split)),
            'rollout': split}
