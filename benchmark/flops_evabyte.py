"""Operations and bytes the EvaByte trunk requires, from shapes alone.

Counted: every matrix product's multiply-adds as 2 FLOP each (the four
attention projections of the heads held, the three MLP matrices, the eight
heads of prediction and the value row), and attention itself over the pairs
the equations name: for query n the exact set ``L_n`` (the positions of its
window up to n) and the remote set ``R_n`` (every chunk of the windows
before), a product with the key and one with the value each. Not counted:
norms, rotary phases, softmax, the chunk summaries (T x d a head), the loss,
V-trace and Adam. The backward pass is twice the forward; recomputation (the
learner rematerialises a layer at a time) is never counted.
"""

import numpy as np

from .flops_trinity_mini import _BYTES, chunk_bytes, rollout_split


def matmul_parameters(model):
    """Parameters that a position multiplies: (attention, mlp, readout)."""
    D, M = model['hidden_size'], model['mlp_size']
    A = model['heads_held'] * model['head_dim']
    return (model['layers'] * 4 * D * A, model['layers'] * 3 * D * M,
            model['pred_heads'] * D * model['vocab'] + D)


def attention_pairs(model, positions, first_position=0):
    """Sum over the queries of a sequence of ``|L_n| + |R_n|``."""
    W, chunk = model['window_size'], model['chunk_size']
    total = 0
    for p in range(first_position, first_position + positions):
        local = p % W + 1 - max(0, first_position - p // W * W)
        remote = max(0, p // W * W - first_position) // chunk
        total += local + remote
    return total


def attention_flops(model, positions):
    """Forward FLOPs of attention proper over one sequence: a product with
    the key and one with the value for every pair, head and layer."""
    return (model['layers'] * model['heads_held'] * 2 * 2 * model['head_dim']
            * attention_pairs(model, positions))


def forward_flops(model, positions):
    """One sequence of ``positions`` through the trunk, from position 0."""
    return (2 * positions * sum(matmul_parameters(model))
            + attention_flops(model, positions))


def train_window_flops(model, train_args):
    """Forward + backward of one trained window: ``forward_steps`` positions
    forward and back (3 x forward) after ``burn_in_steps`` forward only."""
    fs = int(train_args['forward_steps'])
    bi = int(train_args.get('burn_in_steps') or 0)
    whole = forward_flops(model, bi + fs)
    return 3 * whole - 2 * forward_flops(model, bi)


def rows_seen_at(model, kind, ply_index):
    """The cache rows a decode query must see at the ply indices
    ``ply_index`` (0 at a game's first ply): the K (or V) rows of its own
    window up to itself, ``|L_n| = p % window + 1``, and one summary (an RFA
    pair) for every chunk of the windows before, ``|R_n| = (p // window) x
    (window / chunk)``. The one kind is ``eva``."""
    assert kind == 'eva', kind
    p = np.asarray(ply_index)
    W, chunk = model['window_size'], model['chunk_size']
    return p % W + 1 + p // W * (W // chunk)


def eva_attention_scope(model, train_args):
    """What the named scope ``eva_attention`` requires in ONE fused dispatch:
    ``sgd_flops``, forward and backward of the projections and of attention
    over the trained windows (compute-bound), and ``rollout_bytes``, what a
    chunk of decode plies must read: the actor's attention weights once a
    ply and layer, every sequence's cache (window K and V and the
    summaries) once a ply and layer (memory-bound). ``rollout`` is that count
    split (``flops_trinity_mini.rollout_split``): ``chunk_bytes`` of it at a
    chunk's own ply indices (``rows_seen_at``) is what that chunk required;
    ``rollout_bytes`` is its value with every row of the cache read, window
    and summaries whole, which no ply's query needs (before PR 52 the count
    the roofline took)."""
    fs = int(train_args['forward_steps']) + int(
        train_args.get('burn_in_steps') or 0)
    windows = int(train_args['batch_size']) * int(
        train_args['sgd_steps_per_chunk'])
    attention, _mlp, _readout = matmul_parameters(model)
    sgd = 3 * windows * (2 * fs * attention + attention_flops(model, fs))
    width = _BYTES[model['actor_param_dtype']]
    row = (model['layers'] * model['heads_held'] * model['head_dim'] * 2
           * _BYTES[model['compute_dtype']])
    sequences = int(train_args['generation_envs']) * 2
    split = rollout_split(
        train_args['device_chunk_steps'], attention * width,
        {'eva': sequences * row},
        {'eva': model['window_size']
         + model['max_positions'] // model['chunk_size']})
    return {'sgd_flops': sgd, 'rollout_bytes': int(chunk_bytes(split)),
            'rollout': split}
