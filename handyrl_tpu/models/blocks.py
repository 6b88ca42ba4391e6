"""Shared Flax building blocks.

Model convention (framework-wide):
  * ``module(obs, hidden)`` returns a dict with 'policy' (logits over the
    action space), optionally 'value' / 'return' (shape (..., 1)), and
    'hidden' (next recurrent state pytree) for RNNs.
  * observations arrive channel-first (C, H, W) exactly as environments emit
    them (parity with the reference protocol); blocks transpose to NHWC at
    the input edge because that is the layout XLA tiles best onto the MXU.
  * normalization defaults to GroupNorm (stateless — nothing mutable to
    thread through lax.scan or checkpoints, no cross-chip batch-stat sync);
    nets that measurably need the reference's BatchNorm learning dynamics
    (GeisterNet — the round-4 forensics) take ``norm_kind='batch'``, a full
    flax nn.BatchNorm whose ``batch_stats`` collection the trainer threads
    through the forward (ops/losses.py) and whose running averages every
    inference path reads via the plain ``module.apply`` default.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn


def to_nhwc(x: jnp.ndarray) -> jnp.ndarray:
    """(..., C, H, W) -> (..., H, W, C)."""
    return jnp.moveaxis(x, -3, -1)


def torch_default_inits(fan_in: int):
    """(kernel_init, bias_init) mirroring torch's Conv2d/Linear defaults:
    kaiming_uniform(a=sqrt(5)) == uniform(+-1/sqrt(fan_in)) for the kernel
    (std 1.73x SMALLER than flax's lecun_normal default) and
    uniform(+-1/sqrt(fan_in)) for the bias (flax default: zeros). fan_in
    counts receptive field x channels for convs, in_features for dense.
    An init-dynamics knob for the Geister early-curve investigation —
    weight DISTRIBUTIONS differ between frameworks even when every
    architectural choice matches (torch nn/init kaiming_uniform +
    Conv2d/Linear reset_parameters semantics)."""
    kernel = nn.initializers.variance_scaling(1.0 / 3.0, 'fan_in', 'uniform')
    bound = 1.0 / (fan_in ** 0.5)

    def bias(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return kernel, bias


def conv_inits(init_kind: str, in_ch: int, kernel: int) -> dict:
    """kwargs for nn.Conv under the given init regime ('flax' = defaults)."""
    if init_kind == 'flax':
        return {}
    if init_kind == 'torch':
        k, b = torch_default_inits(in_ch * kernel * kernel)
        return {'kernel_init': k, 'bias_init': b}
    raise ValueError('unknown init_kind %r' % (init_kind,))


def dense_inits(init_kind: str, in_features: int) -> dict:
    """kwargs for nn.Dense under the given init regime."""
    if init_kind == 'flax':
        return {}
    if init_kind == 'torch':
        k, b = torch_default_inits(in_features)
        return {'kernel_init': k, 'bias_init': b}
    raise ValueError('unknown init_kind %r' % (init_kind,))


class BatchStatsNorm(nn.Module):
    """(norm_kind='batchstats' — the round-4 investigation variant, kept
    for the A/B record; 'batch' is now full nn.BatchNorm with running
    averages.) Train-mode BatchNorm semantics as a PURE function: per-channel
    normalization by the CURRENT batch's statistics over every non-channel
    axis, with learned scale/bias — no running averages, so nothing
    mutable threads through scan/jit/checkpoints.

    Why it exists: the round-4 Geister quality forensics measured the
    GroupNorm-for-BatchNorm substitution as THE cause of the quality gap
    vs the reference (its nn.BatchNorm2d stem/heads, reference
    geister.py:107,122 — swap them for GroupNorm and the reference drops
    from 0.661 to 0.486 at ~1k episodes, exactly this repo's level;
    ROADMAP D5). Batch statistics in the training forward are the
    learning-dynamics ingredient; this block provides them without
    running-stats state.

    Inference caveats: the training/benchmark paths (device + batched
    evaluators and generators) run batched env vectors, so inference
    statistics match training's regime. The SEQUENTIAL host paths —
    worker-mode Evaluator/exec_match and NetworkAgent (evaluation.py) —
    infer at B=1, where this block degrades to per-sample (instance)
    statistics: a different network function than trained (the torch
    reference uses running averages there instead). Window-tail pad rows
    also enter the statistics during training, exactly as they entered
    the reference's train-mode BatchNorm.
    """
    dtype: jnp.dtype = jnp.float32
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        axes = tuple(range(x.ndim - 1))
        # statistics in float32 regardless of activation dtype (bf16
        # mean/var over ~1k elements loses the variance to cancellation;
        # flax's own norm layers upcast the same way)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = ((xf - mean) / jnp.sqrt(var + self.eps)).astype(self.dtype)
        scale = self.param('scale', nn.initializers.ones, (c,), self.dtype)
        bias = self.param('bias', nn.initializers.zeros, (c,), self.dtype)
        return y * scale + bias


def make_norm(kind: str, filters: int, dtype, train: bool = False) -> nn.Module:
    """'group' (stateless default) | 'batch' (FULL reference-parity
    BatchNorm: current-batch statistics in the training forward, running
    averages served at inference — matches the reference's nn.BatchNorm2d
    train/eval split, reference geister.py:107,122 + model.py:54) |
    'batchstats' (the round-4 pure investigation variant above, batch
    statistics with NO running averages) | 'layer'.

    'batch' carries a mutable ``batch_stats`` collection: the training
    forward must apply with ``mutable=['batch_stats']`` and ``train=True``
    (ops/losses.py threads it, incl. through the recurrent scan); every
    other apply reads the running averages, so the sequential B=1 host
    paths (worker-mode Evaluator, NetworkAgent) see the SAME network
    function as the batched ones — the trap BatchStatsNorm had.

    torch-parity notes: momentum 0.9 here == torch's 0.1 (flax weights the
    old average, torch the new term); eps 1e-5 matches; flax updates the
    running variance with the biased estimator where torch uses unbiased —
    an O(1/batch-elements) difference, negligible at conv feature-map
    sizes."""
    if kind == 'batch':
        return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                            epsilon=1e-5, dtype=dtype)
    if kind == 'batchstats':
        return BatchStatsNorm(dtype=dtype)
    if kind == 'layer':
        return nn.LayerNorm(dtype=dtype)
    if kind == 'group':
        return nn.GroupNorm(num_groups=min(8, filters), dtype=dtype)
    if kind == 'group1':   # the heads' single-group flavor
        return nn.GroupNorm(num_groups=1, dtype=dtype)
    # never fall back silently: a typo'd kind reinstating GroupNorm would
    # quietly reintroduce the exact regression 'batch' exists to fix
    raise ValueError('unknown norm kind %r' % (kind,))


class ConvBlock(nn.Module):
    """3x3 conv + optional normalization, operating on NHWC."""
    filters: int
    kernel: int = 3
    norm: bool = True
    norm_kind: str = 'group'
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(self.filters, (self.kernel, self.kernel), padding='SAME',
                    use_bias=not self.norm, dtype=self.dtype,
                    **conv_inits(self.init_kind, x.shape[-1], self.kernel))(x)
        if self.norm:
            x = make_norm(self.norm_kind, self.filters, self.dtype, train)(x)
        return x


class TorusConv(nn.Module):
    """Conv with wrap-around (toroidal) padding, NHWC.

    TPU-native counterpart of the reference's TorusConv2d
    (hungry_geese.py:23-35). Two mathematically identical implementations
    (pinned against each other by tests/test_torus_halo.py):

    * ``impl='pad'``: jnp.pad(mode='wrap') then a VALID conv. Simple, but
      the wrap-pad materializes a padded copy of the full activation in
      HBM for every block — the round-5 per-op table showed these
      copies/slices as the largest single HBM consumers of the GeeseNet
      update step (ROADMAP S1; not re-measured on today's code).
    * ``impl='halo'``: the conv runs with XLA window padding (zero-pad
      folded into the conv HLO — no materialized pad), and the missing
      wrapped contributions are added back exactly: kernel-row strips for
      the top/bottom output rows, kernel-column strips for the left/right
      output columns, and the four diagonal corner taps. All correction
      operands are 1-row/1-col strips, so the full-tensor pad copy never
      exists.

    Both impls share the same param tree ('Conv_0' kernel/bias), so
    checkpoints transfer and an A/B is config-only."""
    filters: int
    kernel: int = 3
    norm: bool = True
    norm_kind: str = 'group'
    impl: str = 'pad'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        kh, kw = self.kernel // 2, self.kernel // 2
        conv_padding = ('VALID' if self.impl == 'pad'
                        else ((kh, kh), (kw, kw)))
        conv = nn.Conv(self.filters, (self.kernel, self.kernel),
                       padding=conv_padding, use_bias=not self.norm,
                       dtype=self.dtype)
        if self.impl == 'pad':
            pad = [(0, 0)] * (x.ndim - 3) + [(kh, kh), (kw, kw), (0, 0)]
            x = conv(jnp.pad(x, pad, mode='wrap'))
        elif self.impl == 'halo':
            if self.kernel != 3:
                raise ValueError('halo impl is written for 3x3 kernels '
                                 '(got %d)' % (self.kernel,))
            x = _halo_correct(conv(x), x, conv, self.dtype)
        else:
            raise ValueError('unknown TorusConv impl %r' % (self.impl,))
        if self.norm:
            x = make_norm(self.norm_kind, self.filters, self.dtype, train)(x)
        return x


def _halo_correct(y, x, conv: nn.Conv, dtype) -> jnp.ndarray:
    """Add the wrapped-edge contributions a zero-padded 3x3 conv omitted.

    y: conv(x) with window padding (1,1),(1,1); x: (..., H, W, C) NHWC.
    Every omitted term has a source index out of range in rows, columns,
    or both; the three classes are reinstated separately:

      rows    output row 0 misses kernel-row-0 terms sourced from row H-1
              (and symmetrically row H-1 / kernel row 2 / source row 0),
              with IN-RANGE columns -> a 1-row conv, columns zero-padded;
      cols    symmetric with kernel columns;
      corners output (0,0) misses only the (di,dj)=(-1,-1) tap sourced at
              (H-1, W-1) -> one C x F contraction per corner.
    """
    w = conv.variables['params']['kernel'].astype(dtype)   # (3, 3, C, F)
    x = x.astype(dtype)
    lead, (H, W, C) = x.shape[:-3], x.shape[-3:]
    F = w.shape[-1]
    x4 = x.reshape((-1, H, W, C))
    dn = jax.lax.conv_dimension_numbers(
        x4.shape, w.shape, ('NHWC', 'HWIO', 'NHWC'))

    def strip_conv(src, kern, padding):
        out = jax.lax.conv_general_dilated(
            src, kern, (1, 1), padding, dimension_numbers=dn)
        return out.reshape(lead + out.shape[1:])

    # row wraps: single source row, single kernel row, columns zero-padded
    top = strip_conv(x4[:, H - 1:H], w[0:1], ((0, 0), (1, 1)))  # (..,1,W,F)
    bot = strip_conv(x4[:, 0:1], w[2:3], ((0, 0), (1, 1)))
    # column wraps: single source column, single kernel column
    left = strip_conv(x4[:, :, W - 1:], w[:, 0:1], ((1, 1), (0, 0)))
    right = strip_conv(x4[:, :, 0:1], w[:, 2:3], ((1, 1), (0, 0)))

    corner = lambda i, j, ki, kj: jnp.tensordot(
        x[..., i, j, :], w[ki, kj], axes=1)               # (..., F)

    y = y.at[..., 0, :, :].add(top[..., 0, :, :])
    y = y.at[..., H - 1, :, :].add(bot[..., 0, :, :])
    y = y.at[..., :, 0, :].add(left[..., :, 0, :])
    y = y.at[..., :, W - 1, :].add(right[..., :, 0, :])
    y = y.at[..., 0, 0, :].add(corner(H - 1, W - 1, 0, 0))
    y = y.at[..., 0, W - 1, :].add(corner(H - 1, 0, 0, 2))
    y = y.at[..., H - 1, 0, :].add(corner(0, W - 1, 2, 0))
    y = y.at[..., H - 1, W - 1, :].add(corner(0, 0, 2, 2))
    return y


class SpatialPolicyHead(nn.Module):
    """Per-cell policy logits with the reference Conv2dHead's structure
    (reference geister.py:100-113): 3x3 conv (no bias) + norm + relu, then
    a 1x1 conv emitting ``out_filters`` logits PER CELL, flattened
    channel-major so logit index = f*H*W + x*W + y — the '4 x 36' move
    encoding. The spatial parameterization is the head's point: each
    cell's logits come from its own 3x3 neighborhood (a strong inductive
    bias for per-piece directional moves) instead of a global dense map.
    """
    filters: int
    out_filters: int
    norm_kind: str = 'group1'
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Conv(self.filters, (3, 3), padding='SAME', use_bias=False,
                    dtype=self.dtype,
                    **conv_inits(self.init_kind, x.shape[-1], 3))(x)
        h = make_norm(self.norm_kind, self.filters, self.dtype, train)(h)
        h = nn.relu(h)
        h = nn.Conv(self.out_filters, (1, 1), dtype=self.dtype,
                    **conv_inits(self.init_kind, self.filters, 1))(h)
        h = jnp.moveaxis(h, -1, -3)            # (..., F, H, W)
        return h.reshape(*h.shape[:-3], -1)


class PolicyHead(nn.Module):
    """1x1 conv squeeze -> leaky-relu -> dense logits (no bias)."""
    out_filters: int
    outputs: int
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Conv(self.out_filters, (1, 1), dtype=self.dtype,
                    **conv_inits(self.init_kind, x.shape[-1], 1))(x)
        h = nn.leaky_relu(h, negative_slope=0.1)
        h = h.reshape(*h.shape[:-3], -1)
        return nn.Dense(self.outputs, use_bias=False, dtype=self.dtype,
                        **dense_inits(self.init_kind, h.shape[-1]))(h)


class ScalarHead(nn.Module):
    """1x1 conv + norm + relu -> dense scalar(s) (no bias)."""
    filters: int
    outputs: int = 1
    norm_kind: str = 'group1'
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype,
                    **conv_inits(self.init_kind, x.shape[-1], 1))(x)
        h = make_norm(self.norm_kind, self.filters, self.dtype, train)(h)
        h = nn.relu(h)
        h = h.reshape(*h.shape[:-3], -1)
        return nn.Dense(self.outputs, use_bias=False, dtype=self.dtype,
                        **dense_inits(self.init_kind, h.shape[-1]))(h)


class ConvLSTMCell(nn.Module):
    """Convolutional LSTM cell on NHWC feature maps.

    State is an (h, c) tuple with shape (..., H, W, F). Gates come from one
    fused convolution over [x, h] — a single large MXU matmul per step.
    """
    features: int
    kernel: int = 3
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, state):
        h_prev, c_prev = state
        xin = jnp.concatenate([x, h_prev], axis=-1)
        gates = nn.Conv(4 * self.features, (self.kernel, self.kernel),
                        padding='SAME', dtype=self.dtype,
                        **conv_inits(self.init_kind, xin.shape[-1],
                                     self.kernel))(xin)
        i, f, o, g = jnp.split(gates, 4, axis=-1)
        c = nn.sigmoid(f) * c_prev + nn.sigmoid(i) * jnp.tanh(g)
        h = nn.sigmoid(o) * jnp.tanh(c)
        return h, (h, c)


class DRC(nn.Module):
    """Deep Repeated ConvLSTM (Guez et al. 2019, arXiv:1901.03559).

    ``num_layers`` stacked ConvLSTM cells applied ``num_repeats`` times per
    observation; layer i>0 consumes layer i-1's fresh hidden state. Hidden
    state: tuple(list_h, list_c) with NHWC leaves.
    """
    num_layers: int = 3
    features: int = 32
    kernel: int = 3
    num_repeats: int = 3
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    def initial_state(self, spatial: Sequence[int], batch_shape=()):
        shape = tuple(batch_shape) + tuple(spatial) + (self.features,)
        zeros = jnp.zeros(shape, self.dtype)
        hs = [zeros for _ in range(self.num_layers)]
        cs = [zeros for _ in range(self.num_layers)]
        return (hs, cs)

    @nn.compact
    def __call__(self, x, state):
        if state is None:
            state = self.initial_state(x.shape[-3:-1], x.shape[:-3])
        cells = [ConvLSTMCell(self.features, self.kernel,
                              init_kind=self.init_kind, dtype=self.dtype)
                 for _ in range(self.num_layers)]
        hs, cs = list(state[0]), list(state[1])
        for _ in range(self.num_repeats):
            for i, cell in enumerate(cells):
                inp = x if i == 0 else hs[i - 1]
                _, (hs[i], cs[i]) = cell(inp, (hs[i], cs[i]))
        return hs[-1], (hs, cs)
