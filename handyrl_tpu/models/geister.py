"""Geister net: recurrent DRC (Deep Repeated ConvLSTM) policy/value/return.

Capability peer of the reference GeisterNet (geister.py:131-167): scalar
features broadcast onto the 6x6 board, conv stem, 3-layer x 3-repeat DRC
body, move policy (4x36) + setup policy (70) heads, tanh value head and a
separate return head.
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from . import register
from .blocks import (ConvBlock, DRC, PolicyHead, ScalarHead,
                     SpatialPolicyHead, to_nhwc)


@register('GeisterNet')
class GeisterNet(nn.Module):
    filters: int = 32
    drc_layers: int = 3
    drc_repeats: int = 3
    # 'batch' = the reference's BatchNorm2d placement (geister.py:107,122)
    # with FULL semantics: current-batch statistics in the training forward
    # (the learning-dynamics ingredient the round-4 forensics proved causal
    # — the reference drops 0.661 -> 0.486 when its BatchNorm is swapped
    # for GroupNorm) plus running averages served on every inference path
    # (reference model.py:54 — self.eval() before inference). The round-4
    # pure-statistics half-measure is kept as 'batchstats' for the record;
    # it measured tied with GroupNorm (0.452 vs 0.466 at ~1k episodes,
    # benchmarks.jsonl geister rows). The default is what ROADMAP D5 is
    # about to change.
    norm_kind: str = 'group'
    # 'dense' = the measured r1-r4 baseline head (1x1 conv -> Dense over
    # the flattened map); 'spatial' = the reference Conv2dHead structure
    # (3x3 conv + norm + relu -> 1x1 conv, 4 logits PER CELL — reference
    # geister.py:100-113,144). The round-5 rescores measured BOTH norm
    # arms flat at ~0.45 vs the reference's 0.661 while its policy stays
    # near-uniform — the spatially-local head is the next suspect: per-
    # cell logits see their own 3x3 neighborhood instead of learning a
    # global 288->144 dense map. Default: see ROADMAP D5.
    policy_head: str = 'dense'
    # 'torch' reproduces the reference framework's default weight
    # distributions (kaiming-uniform kernels, uniform biases —
    # blocks.torch_default_inits); 'flax' is this repo's measured
    # baseline (lecun_normal, zero biases). Initialization is the
    # remaining dynamics suspect for the early-curve Geister gap after
    # norm + head were measured (benchmarks.jsonl geister-fused-sp* rows).
    init_kind: str = 'flax'
    dtype: jnp.dtype = jnp.float32

    def init_hidden(self, batch_shape=()):
        """Zero DRC state: (hs, cs) lists of (..., 6, 6, F) arrays —
        DISTINCT arrays per leaf (donating consumers may pass the tree to
        XLA, which refuses to donate one buffer twice)."""
        shape = tuple(batch_shape) + (6, 6, self.filters)
        mk = lambda: jnp.zeros(shape, self.dtype)  # noqa: E731
        return ([mk() for _ in range(self.drc_layers)],
                [mk() for _ in range(self.drc_layers)])

    @nn.compact
    def __call__(self, obs, hidden, train: bool = False):
        board = to_nhwc(obs['board'])                    # (..., 6, 6, 7)
        scalar = obs['scalar']                           # (..., 18)
        s_map = jnp.broadcast_to(scalar[..., None, None, :],
                                 board.shape[:-1] + scalar.shape[-1:])
        x = jnp.concatenate([board, s_map], axis=-1)     # (..., 6, 6, 25)

        # 'group' maps the heads to their original 'group1' (num_groups=1)
        # so the default reproduces the measured baseline configuration
        # exactly; only 'batch' switches the heads' statistics
        head_norm = 'group1' if self.norm_kind == 'group' else self.norm_kind
        h = nn.relu(ConvBlock(self.filters, norm_kind=self.norm_kind,
                              init_kind=self.init_kind,
                              dtype=self.dtype)(x, train))
        body = DRC(self.drc_layers, self.filters,
                   num_repeats=self.drc_repeats, init_kind=self.init_kind,
                   dtype=self.dtype)
        if hidden is None:
            hidden = self.init_hidden(h.shape[:-3])
        h, next_hidden = body(h, hidden)

        if self.policy_head == 'spatial':
            p_move = SpatialPolicyHead(8, 4, norm_kind=head_norm,
                                       init_kind=self.init_kind,
                                       dtype=self.dtype)(h, train)
        else:
            p_move = PolicyHead(8, 4 * 36, init_kind=self.init_kind,
                                dtype=self.dtype)(h)
        # setup-phase logits conditioned only on the side-to-move bit
        turn_color = scalar[..., :1]
        from .blocks import dense_inits
        p_set = nn.Dense(70, dtype=self.dtype,
                         **dense_inits(self.init_kind, 1))(turn_color)
        policy = jnp.concatenate([p_move, p_set], axis=-1)

        value = jnp.tanh(ScalarHead(2, 1, norm_kind=head_norm,
                                    init_kind=self.init_kind,
                                    dtype=self.dtype)(h, train))
        ret = ScalarHead(2, 1, norm_kind=head_norm,
                         init_kind=self.init_kind,
                         dtype=self.dtype)(h, train)
        return {'policy': policy, 'value': value, 'return': ret,
                'hidden': next_hidden}
