"""Hungry Geese net.

Capability peer of the reference GeeseNet (hungry_geese.py:38-57): 12
residual torus-conv blocks over the 17x7x11 board encoding; policy read out
at the acting goose's head cell, value from head + global average pooling.
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from . import register
from .blocks import ConvLSTMCell, TorusConv, to_nhwc


@register('GeeseNetLSTM')
class GeeseNetLSTM(nn.Module):
    """Recurrent Hungry Geese net (the LSTM-era baseline configuration,
    BASELINE.md row 4): torus-conv stem, ConvLSTM core carrying state across
    plies, the same head readout as GeeseNet."""
    filters: int = 32
    stem_layers: int = 4
    norm_kind: str = 'group'
    torus_impl: str = 'pad'
    dtype: jnp.dtype = jnp.float32

    def init_hidden(self, batch_shape=()):
        # distinct arrays per leaf: donating consumers hand the tree to
        # XLA, which refuses to donate one buffer twice
        shape = tuple(batch_shape) + (7, 11, self.filters)
        return (jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype))

    @nn.compact
    def __call__(self, obs, hidden, train: bool = False):
        x = to_nhwc(obs)
        h = nn.relu(TorusConv(self.filters, norm_kind=self.norm_kind,
                              impl=self.torus_impl, dtype=self.dtype)(x, train))
        for _ in range(self.stem_layers):
            h = nn.relu(h + TorusConv(self.filters, norm_kind=self.norm_kind,
                                      impl=self.torus_impl,
                                      dtype=self.dtype)(h, train))
        if hidden is None:
            hidden = self.init_hidden(h.shape[:-3])
        h, next_hidden = ConvLSTMCell(self.filters, dtype=self.dtype)(h, hidden)

        head_mask = x[..., :1]
        h_head = (h * head_mask).sum(axis=(-3, -2))
        h_avg = h.mean(axis=(-3, -2))
        policy = nn.Dense(4, use_bias=False, dtype=self.dtype)(h_head)
        value = jnp.tanh(nn.Dense(1, use_bias=False, dtype=self.dtype)(
            jnp.concatenate([h_head, h_avg], axis=-1)))
        return {'policy': policy, 'value': value, 'hidden': next_hidden}


@register('GeeseNet')
class GeeseNet(nn.Module):
    filters: int = 32
    layers: int = 12
    # 'batch' = the reference TorusConv2d's nn.BatchNorm2d in the stem +
    # all 12 blocks (reference hungry_geese.py:23-35,43-44) with full
    # running-average semantics; default follows the measured A/B
    # (PARITY.md "norm": 0.940 group vs 0.929 batch against random — the
    # round-4 Geister forensics had flipped the burden of proof onto
    # GroupNorm for this net too).
    norm_kind: str = 'group'
    # 'halo' computes the identical torus conv without materializing the
    # wrap-padded activation (blocks.TorusConv docstring / round-5 per-op
    # HBM table); parity pinned by tests/test_torus_halo.py.
    torus_impl: str = 'pad'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs, hidden=None, train: bool = False):
        x = to_nhwc(obs)                       # (..., 7, 11, 17)
        h = nn.relu(TorusConv(self.filters, norm_kind=self.norm_kind,
                              impl=self.torus_impl,
                              dtype=self.dtype)(x, train))
        for _ in range(self.layers):
            h = nn.relu(h + TorusConv(self.filters,
                                      norm_kind=self.norm_kind,
                                      impl=self.torus_impl,
                                      dtype=self.dtype)(h, train))

        # pool features at the acting goose's head cell (channel 0 of obs)
        head_mask = x[..., :1]                 # (..., 7, 11, 1)
        h_head = (h * head_mask).sum(axis=(-3, -2))   # (..., F)
        h_avg = h.mean(axis=(-3, -2))                 # (..., F)

        policy = nn.Dense(4, use_bias=False, dtype=self.dtype)(h_head)
        value = jnp.tanh(nn.Dense(1, use_bias=False, dtype=self.dtype)(
            jnp.concatenate([h_head, h_avg], axis=-1)))
        return {'policy': policy, 'value': value}
