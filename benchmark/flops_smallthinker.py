"""Operations and bytes the SmallThinker trunk requires on this chip's
share, from shapes alone, by the convention of ``flops_trinity_mini.py``.

Counted: every matrix product's multiply-adds as 2 FLOP each (the four
attention projections of the heads held, the router at its published width,
the routed experts at the EVEN share: a position's 6 choices meet ``6 x held
/ published`` held experts, the head over the ids held and the value row),
and attention itself over the pairs the equations name: for query n the keys
``max(first, n - window + 1) .. n`` on a window layer and ``first .. n`` on a
global one, a product with the key and one with the value each, for every
query head. Not counted: norms, rotary phases, softmax, the ReLU gate, the
sort into groups, the loss, V-trace and Adam. The backward pass is twice the
forward; recomputation (the learner rematerialises a layer at a time) is
never counted, and neither is what an implementation computes beyond the
pairs named (the shared sequence attention takes every key block of the
sequence, masked). The router's products are counted forward only: it takes
no gradient, and its input takes none through it.
"""

import numpy as np

from .flops_trinity_mini import (_BYTES, _dispatch,  # noqa: F401
                                 chunk_bytes, expert_parameters,
                                 held_per_position, rollout_split)

KINDS = ('global', 'window')


def layers_of(model, kind=None):
    return sum(1 for k in model['layer_types'] if kind in (None, k))


def attention_parameters(model):
    """W_q, W_o of the query heads held and W_k, W_v of the KV heads held,
    one layer."""
    D, d = model['hidden_size'], model['head_dim']
    return 2 * D * model['heads_held'] * d + 2 * D * model['kv_heads_held'] * d


def matmul_parameters(model):
    """Parameters that a position multiplies with a gradient behind them:
    (attention, routed experts at the even share, readout)."""
    n, D = layers_of(model), model['hidden_size']
    return (n * attention_parameters(model),
            n * held_per_position(model) * expert_parameters(model),
            D * model['vocab'] + D)


def router_parameters(model):
    return layers_of(model) * model['hidden_size'] * model['experts_published']


def attention_pairs(model, kind, positions, first_position=0):
    """Sum over the queries of a sequence of the keys each one sees."""
    seen = range(1, positions + 1)
    if kind == 'window':
        return sum(min(n, model['window_size']) for n in seen)
    return sum(seen)


def attention_flops(model, positions, kind=None):
    """Forward FLOPs of attention proper over one sequence: a product with
    the key and one with the value for every pair and query head, over the
    layers of ``kind`` (all of them by default)."""
    pairs = sum(attention_pairs(model, k, positions)
                for k in model['layer_types'] if kind in (None, k))
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
    return int(3 * whole - 2 * forward_flops(model, bi)
               - 2 * 2 * fs * router_parameters(model))


def reglu_experts_scope(model, train_args):
    """What the named scope ``reglu_experts`` requires in ONE fused
    dispatch: ``sgd_flops``, forward and backward of the three products of
    an expert over the rows routed to the held experts at the even share
    (compute-bound), and ``rollout_bytes``, what a chunk of decode plies
    must read: every held expert's weights once a ply and layer
    (memory-bound: 3 rows an expert)."""
    fs, windows, _sequences, plies = _dispatch(train_args)
    n = layers_of(model)
    sgd = 3 * windows * 2 * fs * n * held_per_position(model) \
        * expert_parameters(model)
    rollout = plies * n * len(model['experts_held']) \
        * expert_parameters(model) * _BYTES[model['actor_param_dtype']]
    return {'sgd_flops': int(sgd), 'rollout_bytes': int(rollout)}


def rows_seen_at(model, kind, ply_index):
    """The K (or V) rows a decode query of a ``kind`` layer must see at the
    ply indices ``ply_index`` (0 at a game's first ply): ``p + 1`` on a
    global layer, as far as its buffer goes; ``min(p + 1, window)`` on a
    window one."""
    assert kind in KINDS, kind
    cap = model['window_size' if kind == 'window' else 'max_positions']
    return np.minimum(np.asarray(ply_index) + 1, cap)


def mean_rows_seen(model, kind):
    """``rows_seen_at`` at its mean over the plies of the games the env
    draws: lengths log-uniform in [min_steps, max_steps] (weight 1 / L a
    length), a ply drawn uniformly from all plies played."""
    W = model['window_size']
    rows = plies = 0.0
    for L in range(int(model['min_steps']), int(model['max_steps']) + 1):
        if kind == 'window' and L > W:
            seen = W * (W + 1) // 2 + (L - W) * W
        else:
            seen = L * (L + 1) // 2
        rows += seen / L
        plies += 1.0            # L plies, weight 1 / L
    return rows / plies


def attention_scope(model, train_args, kind):
    """What the named scope ``<kind>_attention`` requires in ONE fused
    dispatch, over the layers of that kind: ``sgd_flops``, forward and
    backward of the four projections and of attention over the pairs the
    equations name, and ``rollout_bytes``, what a chunk of decode plies must
    read: the actor's attention weights once a ply and layer, and every
    sequence's K and V rows that a query sees. ``rollout`` is that count
    split (``flops_trinity_mini.rollout_split``): ``chunk_bytes`` of it at a
    chunk's own ply indices (``rows_seen_at``) is what that chunk required;
    ``rollout_bytes`` is its value at the analytic mean
    (``mean_rows_seen``)."""
    assert kind in KINDS, kind
    fs, windows, sequences, plies = _dispatch(train_args)
    n = layers_of(model, kind)
    sgd = 3 * windows * (2 * fs * n * attention_parameters(model)
                         + attention_flops(model, fs, kind))
    row = model['kv_heads_held'] * model['head_dim'] * 2 \
        * _BYTES[model['compute_dtype']]
    split = rollout_split(
        plies,
        n * attention_parameters(model) * _BYTES[model['actor_param_dtype']],
        {kind: n * sequences * row}, {kind: mean_rows_seen(model, kind)})
    return {'sgd_flops': int(sgd), 'rollout_bytes': int(chunk_bytes(split)),
            'rollout': split}


def window_attention_scope(model, train_args):
    return attention_scope(model, train_args, 'window')


def global_attention_scope(model, train_args):
    return attention_scope(model, train_args, 'global')
