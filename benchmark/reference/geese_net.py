"""Plain float32 forward passes of the two Hungry Geese nets.

``jax.numpy`` only: no flax, no ``lax.conv``, no kernels, no batching tricks.
A 3x3 convolution is nine shifted matrix products. Callers run these under
``jax.default_matmul_precision('highest')`` (on a TPU a float32 product is
otherwise made of bfloat16 passes), with the program's own parameter tree,
so a difference is a difference in arithmetic and never in weights.

Followed: DeNA/HandyRL ``handyrl/envs/kaggle/hungry_geese.py:23-57``
(``TorusConv2d``, ``GeeseNet``). Departures, each the program's own and
listed in ``configs/*.json``:

* normalisation is GroupNorm with 8 groups (the repo's ``norm_kind group``)
  where the reference has ``BatchNorm2d``;
* the layout is NHWC (observations arrive (C, H, W) and are moved), which
  changes no number;
* ``GeeseNetLSTM`` is this repo's net, not the reference's: a stem of 4
  residual blocks, then one ConvLSTM cell whose 3x3 gate convolution over
  ``[x, h]`` pads with ZEROS (flax ``SAME``), not around the torus, gates in
  the order i, f, o, g, and the same two heads read from the cell's output.
"""

import jax
import jax.numpy as jnp

GROUPS = 8
EPS = 1e-6   # flax.linen.GroupNorm's default epsilon


def _conv3x3(x, kernel, wrap):
    """Cross-correlation of ``x`` (..., H, W, C) with ``kernel`` (3, 3, C, F),
    padding by one cell: around the torus if ``wrap``, else with zeros."""
    if wrap:
        def shifted(i, j):
            return jnp.roll(x, (1 - i, 1 - j), axis=(-3, -2))
    else:
        height, width = x.shape[-3], x.shape[-2]
        pad = [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
        padded = jnp.pad(x, pad)

        def shifted(i, j):
            return padded[..., i:i + height, j:j + width, :]
    out = 0.0
    for i in range(3):
        for j in range(3):
            out = out + jnp.matmul(shifted(i, j), kernel[i, j])
    return out


def _group_norm(x, scale, bias):
    """Per sample, over (H, W, channels of the group)."""
    shape = x.shape
    grouped = x.reshape(shape[:-1] + (GROUPS, shape[-1] // GROUPS))
    mean = grouped.mean(axis=(-4, -3, -1), keepdims=True)
    var = ((grouped - mean) ** 2).mean(axis=(-4, -3, -1), keepdims=True)
    normed = ((grouped - mean) / jnp.sqrt(var + EPS)).reshape(shape)
    return normed * scale + bias


def _torus_block(x, p):
    y = _conv3x3(x, p['Conv_0']['kernel'], wrap=True)
    return _group_norm(y, p['GroupNorm_0']['scale'], p['GroupNorm_0']['bias'])


def _heads(h, head_mask, params):
    h_head = (h * head_mask).sum(axis=(-3, -2))
    h_avg = h.mean(axis=(-3, -2))
    policy = jnp.matmul(h_head, params['Dense_0']['kernel'])
    value = jnp.tanh(jnp.matmul(jnp.concatenate([h_head, h_avg], axis=-1),
                                params['Dense_1']['kernel']))
    return policy, value


def _float32(tree):
    return jax.tree_util.tree_map(lambda v: jnp.asarray(v, jnp.float32), tree)


def forward(variables, obs, hidden=None):
    """GeeseNet. ``obs`` (..., 17, 7, 11) -> policy (..., 4), value (..., 1)."""
    params = _float32(variables['params'])
    x = jnp.moveaxis(jnp.asarray(obs, jnp.float32), -3, -1)
    h = jax.nn.relu(_torus_block(x, params['TorusConv_0']))
    layer = 1
    while 'TorusConv_%d' % layer in params:
        h = jax.nn.relu(h + _torus_block(h, params['TorusConv_%d' % layer]))
        layer += 1
    policy, value = _heads(h, x[..., :1], params)
    return {'policy': policy, 'value': value}


def forward_lstm(variables, obs, hidden=None):
    """GeeseNetLSTM, one ply. ``hidden`` is (h, c), each (..., 7, 11, F), or
    None for the zero state. Returns the next hidden state too."""
    params = _float32(variables['params'])
    x = jnp.moveaxis(jnp.asarray(obs, jnp.float32), -3, -1)
    h = jax.nn.relu(_torus_block(x, params['TorusConv_0']))
    layer = 1
    while 'TorusConv_%d' % layer in params:
        h = jax.nn.relu(h + _torus_block(h, params['TorusConv_%d' % layer]))
        layer += 1
    cell = params['ConvLSTMCell_0']['Conv_0']
    features = cell['kernel'].shape[-1] // 4
    if hidden is None:
        zeros = jnp.zeros(h.shape[:-1] + (features,), jnp.float32)
        hidden = (zeros, zeros)
    h_prev, c_prev = _float32(hidden)
    gates = _conv3x3(jnp.concatenate([h, h_prev], axis=-1), cell['kernel'],
                     wrap=False) + cell['bias']
    i, f, o, g = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    policy, value = _heads(h, x[..., :1], params)
    return {'policy': policy, 'value': value, 'hidden': (h, c)}
