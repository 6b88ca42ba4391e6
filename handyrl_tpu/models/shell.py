"""The shell the trunk nets share: what stands around a stack of decoder
layers whichever architecture they are. A net's own file holds its block,
its fields with their published defaults, ``setup`` (the order of its
parameters) and what only it has; ``models/__init__.py`` has the protocol
the learner reads off a net.

Each class names what it expects of its subclass beside ``dtype`` and the
``blocks`` its ``setup`` makes (``step(x, pos, (k, v)) -> x, (k, v)`` and
``attention_part(x, positions, valid)`` each).
"""

import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from . import attention, experts
from .trunk import dot, f32, rms_norm


class TrunkNet(nn.Module):
    """One position through the cache; the net has ``init_hidden`` (its rows
    through ``attention.init_cache``), ``_embed(ids)`` and ``_readout(x)``."""

    @property
    def actor_param_dtype(self):
        """The actor's copy of the parameters is kept in the compute dtype
        (train.py ``actor_refresh``): rollout reads every weight each ply."""
        return self.dtype

    # a finished game resets its sequences' counters, not their buffers:
    # what a counter has not reached is masked
    reset_hidden = staticmethod(attention.reset_cache)

    def __call__(self, obs, hidden, train: bool = False):
        """One position a sequence: obs (B,) int32 ids, layer by layer
        through ``hidden``'s rows; the counter moves on by one."""
        if hidden is None:
            hidden = self.init_hidden(obs.shape)
        pos = hidden['pos']
        x = self._embed(obs)
        ks, vs = [], []
        for i, block in enumerate(self.blocks):
            x, (k, v) = block.step(x, pos, (hidden['k'][i], hidden['v'][i]))
            ks.append(k)
            vs.append(v)
        out = self._readout(x)
        out['hidden'] = {'k': tuple(ks), 'v': tuple(vs), 'pos': pos + 1}
        return out

    def attention_part(self, layer: int, x, positions, valid):
        """Layer ``layer``'s attention output for this chip's heads alone,
        before the branch's norm where it has one (the head-share tests sum
        four of these against the uncut layer)."""
        return self.blocks[layer].attention_part(x, positions, valid)


class ScaledTrunkNet(TrunkNet):
    """Inputs and outputs under ``param_scale`` (every matrix is stored at
    ``param_scale`` times its value, each product's result divided by it),
    over ``embed``, ``norm_out``, ``head`` and ``value``."""

    def _embed(self, ids):
        return self.embed[ids].astype(f32) / self.param_scale

    def _row(self, features, w):
        return dot(features, w, self.dtype, out=f32) / self.param_scale

    def _features(self, x):
        return rms_norm(x, self.norm_out, self.norm_eps, self.dtype)

    def _value(self, features):
        return jnp.tanh(self._row(features, self.value))

    def policy_logits(self, features):
        """The head over the ids held, float32: features (..., D)."""
        return self._row(features, self.head)

    def _readout(self, x):
        h = self._features(x)
        return {'policy': self.policy_logits(h), 'value': self._value(h)}


class ExpertTrunkNet(ScaledTrunkNet):
    """Layers of two kinds (``layer_types``; ``windowed_kind`` names the
    kind that sees ``window_size`` keys), experts of which this chip holds a
    share: a block's ``sequence`` also returns the experts' counts and the
    dispatch's tally (``models/experts.py``)."""
    dense_layers = 0            # the first layers that have no experts

    @property
    def held(self):
        return (tuple(range(self.experts_published))
                if self.experts_held is None else tuple(self.experts_held))

    @property
    def expert_layers(self):
        return tuple(range(self.dense_layers, len(self.layer_types)))

    @property
    def windows(self):
        """A layer's window; None on a layer that sees everything."""
        return [self.window_size if kind == self.windowed_kind else None
                for kind in self.layer_types]

    @property
    def cache_rows(self):
        """A layer's rows: a circle of ``window_size``, or the longest
        game's."""
        return [self.max_positions if window is None else window
                for window in self.windows]

    def init_hidden(self, batch_shape=()):
        return attention.init_cache(
            batch_shape, self.cache_rows,
            self.kv_heads_held * self.head_dim, self.dtype)

    def decode_rows(self, pos):
        """(read, held): the rows of K (as many of V) that ONE ply of
        sequences at counters ``pos`` (numpy) reads, and those that their
        buffers hold, over every layer: each layer hands the decode
        attention the ONE span of its circle or buffer."""
        pos = np.asarray(pos)
        read = sum(attention.spans_rows_read(
            [attention.pass_span(pos, 0, rows)],
            self.kv_heads_held * self.head_dim, self.dtype).sum()
            for rows in self.cache_rows)
        return int(read), sum(self.cache_rows) * pos.size

    def _layers(self, ids, first_position, valid, no_grad_prefix):
        """The window through every layer: the features (B, T, D) in
        ``dtype``, the expert layers' counts and the dispatches' tally."""
        positions = first_position[:, None] + jnp.arange(ids.shape[1])
        x = self._embed(ids)
        counts, tally = [], jnp.zeros((2,), jnp.int32)
        for block in self.blocks:
            # one layer rematerialised at a time: the backward pass keeps
            # each layer's input and recomputes the rest
            x, c, t = nn.remat(type(block).sequence, static_argnums=(4,))(
                block, x, positions, valid, no_grad_prefix)
            if c is not None:
                counts.append(c)
                tally = tally + t
        return self._features(x), counts, tally

    def sequence(self, ids, first_position, valid, no_grad_prefix: int = 0):
        """T positions a sequence in one causal forward. ids (B, T) int32,
        first_position (B,), valid (B, T) bool. Returns ``policy_features``
        (B, T, D) in ``dtype`` (``policy_logits`` of them are the policy:
        the loss takes the head a block of positions at a time), ``value``
        (B, T, 1) float32 and ``aux``: the sums the forward pass hands to
        the epoch record and to ``post_update``."""
        h, counts, tally = self._layers(ids, first_position, valid,
                                        no_grad_prefix)
        out = {'policy_features': h, 'value': self._value(h)}
        if counts:
            out['aux'] = experts.rows_aux(jnp.stack(counts), self.held,
                                          tally)
        return out

    def attention_key_share(self, T):
        """Of the layers' ``T x T`` (query, key) pairs, the share a window
        of ``T`` positions multiplies (1.0: all of them)."""
        return attention.key_share(T, self.windows, self.query_block)

    def epoch_dynamics(self, sums):
        """The epoch record's keys from the epoch's ``diag_*`` sums."""
        return experts.rows_dynamics(
            sums, len(self.held) * len(self.expert_layers))
