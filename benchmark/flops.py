"""Operations the forward and backward passes require, from shapes alone.

Counted: the multiply-adds of every convolution, as 2 FLOP each. Not
counted: normalisation, activations, the two heads (384 FLOP a position,
0.002%), the loss, V-trace and Adam (elementwise, thousands of FLOP against
tens of millions). The backward pass is taken as twice the forward, the
usual count for a layer with weights; recomputation is never counted.
"""


def forward_flops_per_position(model):
    """One board through the net: ``conv_layers`` of the configuration's
    ``model`` block, each ``count`` times H*W*k*k*in*out multiply-adds."""
    height, width = model['board']
    total = 0
    for layer in model['conv_layers']:
        total += (layer['count'] * height * width * layer['kernel'] ** 2
                  * layer['in'] * layer['out'] * 2)
    return total


def train_window_flops(model, train_args):
    """Forward + backward of one trained window: ``forward_steps``
    positions forward and back (3x forward), and ``burn_in_steps``
    positions forward only (a recurrent net's burn-in carries no
    gradient)."""
    forward = forward_flops_per_position(model)
    return (3 * int(train_args['forward_steps'])
            + int(train_args.get('burn_in_steps') or 0)) * forward
