"""Operations and bytes the looped trunk requires on this chip's share, from
shapes alone, by the convention of ``flops_smallthinker.py``.

A layer's parameters are multiplied once a PASS, so a position costs
``passes`` times the stack: counted are every matrix product's multiply-adds
as 2 FLOP each (the four attention projections of the heads held and the
three of the MLP, a pass and layer; the head over the ids held, the value row
and the gate's row, once a pass of a TRAINED position and, the head and the
value row, once a decode ply) and attention itself over the pairs the
equations name: for query n the keys ``first .. n`` of the same pass and
layer, a product with the key and one with the value each, for every query
head, in every pass. Not counted: norms, rotary phases, softmax, SiLU, the
loss, V-trace and Adam. The backward pass is twice the forward;
recomputation (the learner rematerialises a layer at a time) is never
counted, and neither is what an implementation computes beyond the pairs
named (the shared sequence attention takes every key block of the sequence,
masked).
"""

from . import flops_smallthinker
from .flops_trinity_mini import _BYTES, _dispatch, chunk_bytes, rollout_split


def attention_parameters(model):
    """W_q, W_o of the query heads held and W_k, W_v of the KV heads held,
    one layer."""
    D, d = model['hidden_size'], model['head_dim']
    return 2 * D * model['heads_held'] * d + 2 * D * model['kv_heads_held'] * d


def mlp_parameters(model):
    return 3 * model['hidden_size'] * model['mlp_size']


def readout_parameters(model):
    """The head over the ids held, the value row and the gate's row."""
    return model['hidden_size'] * (model['vocab'] + 2)


def matmul_parameters(model):
    """Parameters that a TRAINED position multiplies, each as often as it
    does: (attention, MLP, readout), the stack and the readout once a pass."""
    n = model['passes']
    return (n * model['layers'] * attention_parameters(model),
            n * model['layers'] * mlp_parameters(model),
            n * readout_parameters(model))


def attention_pairs(positions):
    """Sum over the queries of a sequence of the keys each one sees."""
    return positions * (positions + 1) // 2


def attention_flops(model, positions):
    """Forward FLOPs of attention proper over one sequence: a product with
    the key and one with the value for every pair and query head, in every
    layer of every pass."""
    return model['passes'] * model['layers'] * model['heads_held'] \
        * 2 * 2 * model['head_dim'] * attention_pairs(positions)


def forward_flops(model, positions):
    """One sequence of ``positions`` through all passes, from position 0."""
    return (2 * positions * sum(matmul_parameters(model))
            + attention_flops(model, positions))


def train_window_flops(model, train_args):
    """Forward + backward of one trained window: ``forward_steps`` positions
    forward and back (3 x forward) after ``burn_in_steps`` forward only."""
    fs = int(train_args['forward_steps'])
    bi = int(train_args.get('burn_in_steps') or 0)
    return int(3 * forward_flops(model, bi + fs)
               - 2 * forward_flops(model, bi))


def rows_seen_at(model, kind, ply_index):
    """The K (or V) rows of ONE (pass, layer) that a decode query must see at
    the ply indices ``ply_index`` (0 at a game's first ply): the counter's
    rows ``p + 1``, no window; every layer is of the one kind ``global``."""
    return flops_smallthinker.rows_seen_at(model, kind, ply_index)


def rows_written(model):
    """``rows_seen_at`` at its mean over the plies of the games the env draws
    (lengths log-uniform in [min_steps, max_steps])."""
    return flops_smallthinker.mean_rows_seen(
        dict(model, window_size=model['max_positions']), 'global')


def _cache_split(model, train_args, ply_bytes):
    """``ply_bytes`` a ply whatever the fill, and a row of every sequence in
    every (pass, layer)."""
    _fs, _windows, sequences, plies = _dispatch(train_args)
    uses = model['passes'] * model['layers']
    return rollout_split(
        plies, ply_bytes, {'global': uses * sequences * _row_bytes(model)},
        {'global': rows_written(model)})


def _row_bytes(model):
    """One position's K and V of one (pass, layer)."""
    return model['kv_heads_held'] * model['head_dim'] * 2 \
        * _BYTES[model['compute_dtype']]


def loop_attention_scope(model, train_args):
    """What the named scope ``loop_attention`` requires in ONE fused
    dispatch: ``sgd_flops``, forward and backward of the four projections
    and of attention over the causal pairs, every layer of every pass, and
    ``rollout_bytes``, what a chunk of decode plies must read: the actor's
    attention weights once a PASS and layer, and every sequence's K and V
    rows written so far of every (pass, layer). ``rollout`` is that count
    split (``flops_trinity_mini.rollout_split``): ``chunk_bytes`` of it at a
    chunk's own ply indices (``rows_seen_at``) is what that chunk required;
    ``rollout_bytes`` is its value at the analytic mean (``rows_written``)."""
    fs, windows, _sequences, _plies = _dispatch(train_args)
    uses = model['passes'] * model['layers']
    sgd = 3 * windows * (2 * fs * uses * attention_parameters(model)
                         + attention_flops(model, fs))
    split = _cache_split(
        model, train_args,
        uses * attention_parameters(model) * _BYTES[model['actor_param_dtype']])
    return {'sgd_flops': int(sgd), 'rollout_bytes': int(chunk_bytes(split)),
            'rollout': split}


def decode_rollout(model, train_args):
    """What the decode plies of ONE chunk must read, split
    (``flops_trinity_mini.rollout_split``): a ply's fixed bytes are the
    actor's layer weights once a PASS (a pass's ~310 MB outlast any fast
    memory), the head and the value row once; a row is every sequence's K and
    V of every (pass, layer)."""
    uses = model['passes'] * model['layers']
    weights = (uses * (attention_parameters(model) + mlp_parameters(model))
               + model['hidden_size'] * (model['vocab'] + 1)) \
        * _BYTES[model['actor_param_dtype']]
    return _cache_split(model, train_args, weights)


def decode_ply_bytes(model, train_args):
    """The bytes ONE decode ply must read at the games' mean fill:
    ``decode_rollout`` at the analytic mean, a ply."""
    split = decode_rollout(model, train_args)
    return int(chunk_bytes(split) / split['plies'])
