"""Ouro-2.6B as a policy trunk: a stack of decoder layers RUN SEVERAL TIMES
over ONE set of weights, a readout a pass, and an exit gate that weighs the
passes' losses.

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
(``model_type`` ``ouro``; 48 layers of hidden 2,048; 16 query heads = 16 KV
heads of 128; SwiGLU of 5,632; every layer ``full_attention``; ``rope_theta``
1e6, no scaling; RMSNorm eps 1e-6; vocabulary 49,152, untied;
``total_ut_steps`` 4: the whole stack runs four times over one set of
weights; ``early_exit_threshold`` 1: the actor never leaves early and plays
from the last pass). ``N(x, g) = x / rms(x) * g``; on the float32 residual:

* ``x_0 = E[id]``. For pass ``t = 1..4``: ``u = x_{t-1}``; for each layer
  ``l``: ``a = u + N2_l(Attn_l(N1_l(u)))``, ``u = a + N4_l(MLP_l(N3_l(a)))``;
  then ``x_t = N_out(u)``. ``x_t`` feeds the next pass AND is pass ``t``'s
  features;
* ``Attn_l(n)``: ``q, k, v = W_q n, W_k n, W_v n`` a head of 128; rotary
  phases (rotate-half over all 128, theta 1e6) at the position's index on
  EVERY layer and in every pass; a causal soft-max over all keys of the SAME
  pass and layer; ``W_o``. No bias, no QK-norm, no gate.
  ``MLP_l(n) = W_down(silu(W_gate n) * W_up n)``;
* the readout of pass ``t``: logits ``x_t W_head``, a value row (tanh) and
  the gate's logit ``w_g . x_t + b_g`` (one row shared by the passes). The
  exit distribution, the exit-weighted loss and the sums the epoch record
  reads the gate from are the learner's (``ops/losses.py``
  ``exit_distribution``, ``_exit_weighted_losses``).

The layer holds ``heads_held`` / ``kv_heads_held`` of the published heads:
this chip's share where four chips share each layer by heads. Attention
gives its heads' part of ``W_o``'s sum and passes it on as it is (``N2``
norms the part); no code stands in for the other chips.

Two entries over one set of parameters, each ONE ``lax.scan`` over the
passes with the weights closed over, so the lowered program holds the stack
once and a weight's gradient is the sum over its uses:

* ``sequence(ids, first_position, valid)``: T positions a sequence, every
  pass's features, value and gate logit with a LEADING PASS AXIS;
* ``__call__(id, hidden)``: one position through the cache, all passes, the
  head on the last alone. ``hidden`` holds K and V of every (pass, layer):
  a layer's buffer has the passes' ``max_positions`` rows one behind the
  other (``models/attention.py`` ``init_pass_cache``), keys stored already
  turned, and ONE counter a sequence. On a TPU a ply reads only the row
  blocks a counter has reached (``models/decode_kernel.py``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from . import attention, register
from .shell import ScaledTrunkNet
from .trunk import burn_in_as_state, dot, f32, heads_of, rms_norm, rotary

LAYER_LEAVES = ('wq', 'wk', 'wv', 'wo', 'w_gate', 'w_up', 'w_down',
                'norm_1', 'norm_2', 'norm_3', 'norm_4')


class _Spec(NamedTuple):
    """What a layer's arithmetic reads beside its weights."""
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    query_block: int
    inv: float          # 1 / param_scale, taken on each product's result
    dtype: Any


class OuroBlock(nn.Module):
    """One decoder layer's weights: this chip's heads, the whole MLP. The
    arithmetic is the module-level functions below, which take the weights
    as a dict so that a scan over the passes closes over them."""
    hidden_size: int
    heads_held: int
    kv_heads_held: int
    head_dim: int
    mlp_size: int
    param_scale: float

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        ones = nn.initializers.ones
        D, d, F = self.hidden_size, self.head_dim, self.mlp_size
        A, KV = self.heads_held * d, self.kv_heads_held * d
        shapes = {'wq': (D, A), 'wk': (D, KV), 'wv': (D, KV), 'wo': (A, D),
                  'w_gate': (D, F), 'w_up': (D, F), 'w_down': (F, D)}
        self.leaves = {name: self.param(
            name, init if name in shapes else ones, shapes.get(name, (D,)))
            for name in LAYER_LEAVES}

    def weights(self):
        return dict(self.leaves)


def _qkv(spec, p, n, positions):
    """n (..., D) in ``dtype`` at ``positions`` (...,) -> q (..., H, d), k, v
    (..., KV, d), q and k turned by their positions' phases."""
    q = heads_of(n, p['wq'], spec.heads, spec.dtype, spec.inv)
    k = heads_of(n, p['wk'], spec.kv_heads, spec.dtype, spec.inv)
    v = heads_of(n, p['wv'], spec.kv_heads, spec.dtype, spec.inv)
    pos = positions[..., None]
    return (rotary(q, pos, spec.rope_theta),
            rotary(k, pos, spec.rope_theta), v)


def _attention_part(spec, p, x, positions, valid, no_grad_prefix=0):
    """This chip's heads' part of ``W_o``'s sum for (B, T, D) float32 inputs:
    (B, T, D) float32, before the branch's norm."""
    n = rms_norm(x, p['norm_1'], spec.norm_eps, spec.dtype)
    q, k, v = _qkv(spec, p, n, positions)
    k, v = burn_in_as_state(k, v, no_grad_prefix)
    y = jax.vmap(lambda *seq: attention.sequence_attention(
        *seq, None, spec.query_block))(q, k, v, positions, valid)
    return dot(y, p['wo'], spec.dtype, out=f32) * spec.inv


def _mlp(spec, p, a):
    """``a + N4(MLP(N3(a)))`` on the float32 residual."""
    with jax.named_scope('trunk_mlp'):
        n = rms_norm(a, p['norm_3'], spec.norm_eps, spec.dtype)
        act = (jax.nn.silu(dot(n, p['w_gate'], spec.dtype) * spec.inv)
               * (dot(n, p['w_up'], spec.dtype) * spec.inv))
        m = dot(act, p['w_down'], spec.dtype, out=f32) * spec.inv
        return a + rms_norm(m, p['norm_4'], spec.norm_eps, f32)


def _layer_sequence(spec, no_grad_prefix, p, x, positions, valid):
    """One layer over a whole window: (B, T, D) float32 -> the same."""
    with jax.named_scope('loop_attention'):
        part = _attention_part(spec, p, x, positions, valid, no_grad_prefix)
        a = x + rms_norm(part, p['norm_2'], spec.norm_eps, f32)
    return _mlp(spec, p, a)


def _layer_step(spec, p, x, pos, t, rows, ck, cv):
    """One layer, one position a sequence, in pass ``t``: x (B, D) float32 at
    each sequence's own ``pos`` (B,); ck, cv (B, passes * rows, KV * d)."""
    with jax.named_scope('loop_attention'):
        n = rms_norm(x, p['norm_1'], spec.norm_eps, spec.dtype)
        q, k, v = _qkv(spec, p, n, pos)                  # (B, H | KV, d)
        with jax.named_scope('state_update'):
            ck, cv = attention.pass_write(ck, cv, k, v, pos, t, rows)
        y = attention.cache_attention(q, ck, cv, pos, False, spec.kv_heads,
                                      spec.dtype, t=t, rows=rows)
        part = dot(y, p['wo'], spec.dtype, out=f32) * spec.inv
        a = x + rms_norm(part, p['norm_2'], spec.norm_eps, f32)
    return _mlp(spec, p, a), ck, cv


@register('OuroNet')
class OuroNet(ScaledTrunkNet):
    """The looped trunk with its untied head read as a policy over the ids
    held, a value row and the exit gate's row. Observations are int32 ids.
    The defaults are the published counts; the depth, the heads and the
    slice of the vocabulary held are the deployment's cut (ISSUE 46)."""
    hidden_size: int = 2048
    layers: int = 48
    heads_held: int = 16
    kv_heads_held: int = 16
    head_dim: int = 128
    mlp_size: int = 5632
    vocab: int = 49152
    passes: int = 4
    max_positions: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # queries a block of the window's attention: at 4,096 keys 512 is fine
    # on this chip (PERF.md, PR 43); a net that trains longer windows must
    # take 128
    query_block: int = 512
    # every matrix is STORED at ``param_scale`` times its value and each
    # product's result divided by it, as models/trinity.py
    param_scale: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        D = self.hidden_size
        self.embed = self.param('embed', init, (self.vocab, D))
        self.blocks = [OuroBlock(
            D, self.heads_held, self.kv_heads_held, self.head_dim,
            self.mlp_size, self.param_scale, name='layer_%d' % i)
            for i in range(self.layers)]
        self.norm_out = self.param('norm_out', nn.initializers.ones, (D,))
        self.head = self.param('head', init, (D, self.vocab))
        self.value = self.param('value', init, (D, 1))
        self.gate = self.param('gate', init, (D, 1))
        self.gate_bias = self.param('gate_bias', nn.initializers.zeros, (1,))

    @property
    def spec(self):
        return _Spec(self.heads_held, self.kv_heads_held, self.head_dim,
                     self.rope_theta, self.norm_eps, self.query_block,
                     1 / self.param_scale, self.dtype)

    # -- the cache -----------------------------------------------------------
    def init_hidden(self, batch_shape=()):
        return attention.init_pass_cache(
            batch_shape, self.passes, [self.max_positions] * self.layers,
            self.kv_heads_held * self.head_dim, self.dtype)

    # -- inputs and outputs --------------------------------------------------
    def __call__(self, obs, hidden, train: bool = False):
        """One position a sequence: obs (B,) int32 ids. Every pass runs; the
        head and the value row read the last."""
        if hidden is None:
            hidden = self.init_hidden(obs.shape)
        pos, rows, spec = hidden['pos'], self.max_positions, self.spec
        weights = [block.weights() for block in self.blocks]
        norm_out = self.norm_out

        def one_pass(carry, t):
            x, ks, vs = carry
            ks, vs = list(ks), list(vs)
            for i, p in enumerate(weights):
                x, ks[i], vs[i] = _layer_step(spec, p, x, pos, t, rows,
                                              ks[i], vs[i])
            x = rms_norm(x, norm_out, spec.norm_eps, f32)
            return (x, tuple(ks), tuple(vs)), None
        (x, ks, vs), _ = jax.lax.scan(
            one_pass, (self._embed(obs), hidden['k'], hidden['v']),
            jnp.arange(self.passes))
        h = x.astype(self.dtype)
        return {'policy': self.policy_logits(h), 'value': self._value(h),
                'hidden': {'k': ks, 'v': vs, 'pos': pos + 1}}

    def sequence(self, ids, first_position, valid, no_grad_prefix: int = 0):
        """T positions a sequence in one causal forward a pass. ids (B, T)
        int32, first_position (B,), valid (B, T) bool. Returns, each with a
        LEADING PASS AXIS: ``policy_features`` (passes, B, T, D) in ``dtype``
        (``policy_logits`` of them are a pass's policy), ``value`` and
        ``exit_gate`` (passes, B, T, 1) float32 (the gate's LOGIT)."""
        T = ids.shape[1]
        positions = first_position[:, None] + jnp.arange(T)
        spec = self.spec
        weights = [block.weights() for block in self.blocks]
        norm_out = self.norm_out
        # one layer rematerialised at a time, as models/smallthinker.py
        layer = jax.checkpoint(functools.partial(_layer_sequence, spec,
                                                 no_grad_prefix))

        def one_pass(x, _):
            for p in weights:
                x = layer(p, x, positions, valid)
            with jax.named_scope('pass_readout'):
                x = rms_norm(x, norm_out, spec.norm_eps, f32)
            return x, x.astype(spec.dtype)
        _, features = jax.lax.scan(one_pass, self._embed(ids), None,
                                   length=self.passes)
        with jax.named_scope('pass_readout'):
            value = self._value(features)
            gate = self._row(features, self.gate) + self.gate_bias
        return {'policy_features': features, 'value': value,
                'exit_gate': gate}

    def decode_rows(self, pos):
        """(read, held): the rows of K (as many of V) that ONE ply of
        sequences at counters ``pos`` (numpy) reads, and those that their
        buffers hold, over every (pass, layer)."""
        read = attention.spans_rows_read(
            [attention.pass_span(np.asarray(pos), 0, self.max_positions)],
            self.kv_heads_held * self.head_dim, self.dtype)
        each = self.passes * self.layers
        return each * int(read.sum()), each * self.max_positions * read.size

    def attention_part(self, layer: int, x, positions, valid):
        return _attention_part(self.spec, self.blocks[layer].weights(), x,
                               positions, valid)

    def epoch_dynamics(self, sums):
        """The epoch record's keys from the epoch's ``diag_*`` sums."""
        n = sums.get('diag_window_positions_valid', 0.0)
        if not n:
            return {}
        dynamics = {'exit_entropy_share': 100.0 * sums.get(
            'diag_exit_entropy_nats', 0.0)
            / max(sums.get('diag_exit_entropy_max_nats', 0.0), 1e-9)}
        for t in range(1, self.passes + 1):
            key = 'exit_mass_pass_%d' % t
            dynamics[key] = sums.get('diag_' + key, 0.0) / n
        return dynamics

