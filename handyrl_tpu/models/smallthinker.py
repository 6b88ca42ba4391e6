"""SmallThinker-21BA3B as a policy trunk: sparse ReLU-gated experts ROUTED
BEFORE ATTENTION, of which this chip holds a share, and grouped-query
attention of two kinds in one net.

Source:
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
(52 layers of hidden 2560; 28 query heads and 4 KV heads of 128, so ``W_q``
is 3,584 wide; ``sliding_window_layout`` = ``rope_layout`` = ``0,1,1,1``
thirteen times; 64 primary experts of width 768, 6 a token, soft-max over the
kept logits; vocabulary 151,936, untied; ``rope_theta`` 1.5e6; RMSNorm eps
1e-6). A layer, on the float32 residual ``h``:

* ``a = N_in(h)``;
* the router, before attention: ``l = W_r a`` in float32 (64 logits, no
  bias); ``S`` the 6 largest of ``l``; ``w_e = exp(l_e) / sum_{e' in S}
  exp(l_e')`` (a soft-max over all 64 renormalised over the kept 6 gives the
  same numbers);
* ``q = W_q a``, ``k = W_k a``, ``v = W_v a``; no bias, no QK-norm, no gate.
  Query head ``n`` reads KV head ``n // 7``. Layout 0 is a ``global`` layer:
  no positional encoding at all, ``j <= i``; layout 1 a ``window`` layer:
  rotary phases on ``q`` and ``k`` and ``i - 4096 < j <= i``.
  ``attn = W_o softmax(q k^T / sqrt(128)) v``;
* ``h = h + attn``; ``m = N_post(h)``; ``h = h + sum_{e in S} w_e W_d^e
  (relu(W_g^e m) * W_u^e m)``. No shared expert, no dense layer;
* ``N_out``, an untied head; no factor on the embedding.

The layer holds ``heads_held`` / ``kv_heads_held`` of the published heads and
the experts ``experts_held`` (indices among the published 64): this chip's
share where four chips share each layer. It routes over ALL experts at the
published router width, keeps the published 6 and their soft-max over all 6,
and computes the part of the sum that ITS experts give (``models/experts.py``);
attention gives its heads' part of ``W_o``'s sum (``models/attention.py``).
Both partial sums go on as they are: no code stands in for the other chips.
In a block the routing and the sort plan of the (row, choice) pairs are
computed from ``a`` BEFORE attention is called (scope ``pre_route``); after
attention only the gathers, the products and the weighted sum remain.

Departures from the source (the configuration's file lists them): a value
row (2560 -> 1, tanh) beside the head and the head read as a policy over the
ids held; the router takes NO gradient and ``post_update`` restores ``W_r``
after Adam's weight decay (a lone share's gradient points at the absent
experts); no balancing rule (the source's config has none); ``param_scale``
as ``models/trinity.py``.

Two entries over one set of parameters, as ``models/trinity.py``:
``sequence(ids, first_position, valid)`` and ``__call__(id, hidden)``;
``hidden`` holds K and V of two lengths side by side: a circle of
``window_size`` rows on a window layer (keys stored already turned),
``max_positions`` rows on a global one, and ONE counter a sequence. On a TPU
a ply reads only the row blocks a counter has reached
(``models/decode_kernel.py``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import attention, experts, register
from .shell import ExpertTrunkNet
from .trunk import burn_in_as_state, dot, f32, heads_of, rotary
# under this name tests/benchmark plants a fault through it
from .trunk import rms_norm as _rms_norm

PUBLISHED_LAYERS = ('global', 'window', 'window', 'window') * 13


class SmallThinkerBlock(nn.Module):
    """One decoder layer: the router, this chip's heads and its experts."""
    hidden_size: int
    heads_held: int
    kv_heads_held: int
    head_dim: int
    kind: str                     # 'global' | 'window'
    window_size: int
    rope_theta: float
    norm_eps: float
    expert_size: int
    experts_published: int
    experts_held: Tuple[int, ...]
    experts_per_token: int
    query_block: int
    dense_rows: int
    param_scale: float
    dtype: Any

    activation = staticmethod(jax.nn.relu)      # the experts' gate: ReGLU

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        ones = nn.initializers.ones
        D, d = self.hidden_size, self.head_dim
        A, KV = self.heads_held * d, self.kv_heads_held * d
        F, held = self.expert_size, len(self.experts_held)
        self.wq = self.param('wq', init, (D, A))
        self.wk = self.param('wk', init, (D, KV))
        self.wv = self.param('wv', init, (D, KV))
        self.wo = self.param('wo', init, (A, D))
        self.norm_in = self.param('norm_in', ones, (D,))
        self.norm_post = self.param('norm_post', ones, (D,))
        self.router = self.param('router', init, (D, self.experts_published))
        self.experts_gate = self.param('experts_gate', init, (held, D, F))
        self.experts_up = self.param('experts_up', init, (held, D, F))
        self.experts_down = self.param('experts_down', init, (held, F, D))

    @property
    def inv(self):
        """What each product's result is multiplied by (``param_scale``)."""
        return 1 / self.param_scale

    @property
    def attention_scope(self):
        return self.kind + '_attention'

    def _input(self, x):
        """``N_in(h)`` in float32 (what the router reads) and in ``dtype``."""
        a32 = _rms_norm(x, self.norm_in, self.norm_eps, f32)
        return a32, a32.astype(self.dtype)

    # -- the router, before attention ----------------------------------------
    def pre_route(self, a32):
        """a32 (n, D), the float32 normed layer input -> everything of the
        expert layer that depends on the routing alone: ``slot`` (n, k) and
        the weights ``w`` (n, k), the tokens each published expert was
        chosen for (E,), and either the sort plan of the pairs (many rows)
        or every held expert's weight a row (a decode ply's few). No
        gradient passes (module docstring)."""
        logits = jnp.dot(a32, self.router.astype(f32),
                         precision=jax.lax.Precision.HIGHEST) * self.inv
        kept, ids = jax.lax.top_k(logits, self.experts_per_token)
        # the choices, for whoever asks for them (the checks compare them
        # with the reference's); nothing is kept where nobody does
        self.sow('intermediates', 'route_ids', ids)
        w = jax.lax.stop_gradient(jax.nn.softmax(kept, axis=-1))
        counts = experts.chosen_counts(ids, self.experts_published)
        slot = experts.held_slot(ids, self.experts_held,
                                 self.experts_published)
        held = len(self.experts_held)
        if a32.shape[0] <= self.dense_rows:
            return slot, w, counts, experts.every_row_gate(slot, w, held)
        return slot, w, counts, experts.sort_plan(slot, held,
                                                  self.experts_published)

    def experts_part(self, m, routing):
        """m (n, D) in ``dtype``, ``N_post(h)`` -> this chip's experts' part
        of the layer's sum (n, D) float32, and the dispatch's tally
        (``experts.SortPlan.tally``; 0 for a few rows, which take no
        buffer)."""
        slot, w, _counts, plan = routing
        matrices = (self.experts_gate, self.experts_up, self.experts_down)
        how = (self.activation, self.dtype, self.inv)
        if not isinstance(plan, experts.SortPlan):
            with jax.named_scope('reglu_experts'):
                return experts.every_row_products(m, plan, *matrices, *how), \
                    jnp.int32(0)

        def products(rows, groups, *matrices):
            with jax.named_scope('reglu_experts'):
                return experts.grouped_products(rows, groups, *matrices,
                                                *how)
        f = experts.dispatched_sum(m, plan, slot, w, self.experts_published,
                                   products, matrices, 'expert_dispatch')
        return f, plan.tally

    # -- attention -----------------------------------------------------------
    def _qkv(self, a, positions):
        """a (..., D) in ``dtype`` at ``positions`` (...,) -> q (..., H, d),
        k, v (..., KV, d)."""
        q = heads_of(a, self.wq, self.heads_held, self.dtype, self.inv)
        k = heads_of(a, self.wk, self.kv_heads_held, self.dtype, self.inv)
        v = heads_of(a, self.wv, self.kv_heads_held, self.dtype, self.inv)
        if self.kind == 'window':
            pos = positions[..., None]
            q = rotary(q, pos, self.rope_theta)
            k = rotary(k, pos, self.rope_theta)
        return q, k, v

    def _out(self, y):
        """This chip's heads' part of ``W_o``'s sum."""
        return dot(y, self.wo, self.dtype, out=f32) * self.inv

    def _attention(self, a, positions, valid, no_grad_prefix=0):
        q, k, v = self._qkv(a, positions)
        k, v = burn_in_as_state(k, v, no_grad_prefix)
        window = self.window_size if self.kind == 'window' else None
        y = jax.vmap(lambda *seq: attention.sequence_attention(
            *seq, window, self.query_block))(q, k, v, positions, valid)
        return self._out(y)

    def attention_part(self, x, positions, valid):
        """This chip's part of the attention output: (B, T, D) float32 (the
        head-share test sums four of these)."""
        return self._attention(self._input(x)[1], positions, valid)

    def _after_attention(self, x, routing):
        m = _rms_norm(x, self.norm_post, self.norm_eps, self.dtype)
        f, tally = self.experts_part(m, routing)
        return x + f, tally

    # -- a whole window ------------------------------------------------------
    def sequence(self, x, positions, valid, no_grad_prefix=0):
        B, T, D = x.shape
        a32, a = self._input(x)
        with jax.named_scope('pre_route'):
            routing = self.pre_route(a32.reshape(B * T, D))
        with jax.named_scope(self.attention_scope):
            x = x + self._attention(a, positions, valid, no_grad_prefix)
        x, tally = self._after_attention(x.reshape(B * T, D), routing)
        return x.reshape(B, T, D), routing[2], tally

    # -- one position through the cache --------------------------------------
    def step(self, x, pos, cache):
        """x (B, D) float32 at each sequence's own position ``pos`` (B,);
        cache = (k, v), each (B, rows, KV * d): ``window_size`` rows written
        round and round on a window layer, ``max_positions`` on a global
        one."""
        ck, cv = cache
        a32, a = self._input(x)
        with jax.named_scope('pre_route'):
            routing = self.pre_route(a32)
        with jax.named_scope(self.attention_scope):
            q, k, v = self._qkv(a, pos)                  # (B, H | KV, d)
            with jax.named_scope('state_update'):
                ck, cv = attention.cache_write(ck, cv, k, v, pos)
            y = attention.cache_attention(q, ck, cv, pos,
                                          self.kind == 'window',
                                          self.kv_heads_held, self.dtype)
            x = x + self._out(y)
        x, _tally = self._after_attention(x, routing)
        return x, (ck, cv)


@register('SmallThinkerNet')
class SmallThinkerNet(ExpertTrunkNet):
    """The trunk with its untied head read as a policy over the ids held and
    a value row. Observations are int32 ids. The defaults are the published
    counts, at which every layer IS the published layer; the depth, the
    heads, the experts and the slice of the vocabulary held are the
    deployment's cut (ISSUE 43)."""
    hidden_size: int = 2560
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    heads_held: int = 28
    kv_heads_held: int = 4
    head_dim: int = 128
    expert_size: int = 768
    experts_published: int = 64
    experts_held: Optional[Tuple[int, ...]] = None     # None: all of them
    experts_per_token: int = 6
    vocab: int = 151936
    window_size: int = 4096
    max_positions: int = 8192
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    # queries a block of the window's attention. 128 and not Trinity's 512:
    # at 8,192 keys a block's float32 scores are 117 MB at 512, and the
    # chip's compiler falls off a cliff there (one layer's attention,
    # forward and backward: 170 ms at 512, 5.3 ms at 128; PERF.md, PR 43)
    query_block: int = 128
    # rows at or under which every held expert takes every row (a decode
    # ply: 32 rows), as models/trinity.py
    dense_rows: int = 128
    # every matrix is STORED at ``param_scale`` times its value and each
    # product's result divided by it, as models/trinity.py
    param_scale: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    windowed_kind = 'window'

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        self.embed = self.param('embed', init, (self.vocab, self.hidden_size))
        self.blocks = [SmallThinkerBlock(
            self.hidden_size, self.heads_held, self.kv_heads_held,
            self.head_dim, kind, self.window_size, self.rope_theta,
            self.norm_eps, self.expert_size, self.experts_published,
            self.held, self.experts_per_token, self.query_block,
            self.dense_rows, self.param_scale, self.dtype,
            name='layer_%d' % i)
            for i, kind in enumerate(self.layer_types)]
        self.norm_out = self.param('norm_out', nn.initializers.ones,
                                   (self.hidden_size,))
        self.head = self.param('head', init, (self.hidden_size, self.vocab))
        self.value = self.param('value', init, (self.hidden_size, 1))

    def sequence(self, ids, first_position, valid, no_grad_prefix: int = 0):
        """The shell's, and in ``aux`` how many of the trained positions a
        window layer hides a key from."""
        h, counts, tally = self._layers(ids, first_position, valid,
                                        no_grad_prefix)
        # the sequence starts with an empty cache at its first position:
        # from ``window_size`` positions on a window layer hides a key
        T = ids.shape[1]
        trained = valid & (jnp.arange(T) >= no_grad_prefix)
        hidden = trained & (jnp.arange(T) >= self.window_size)
        return {'policy_features': h, 'value': self._value(h), 'aux': dict(
            experts.rows_aux(jnp.stack(counts), self.held, tally),
            window_positions_valid=trained.sum().astype(f32),
            window_positions_hidden=hidden.sum().astype(f32))}

    def experts_part(self, layer: int, a32, m):
        """Layer ``layer``'s experts' sum for (n, D) rows: routed from
        ``a32``, computed on ``m`` (the expert-share test sums four)."""
        block = self.blocks[layer]
        return block.experts_part(m.astype(self.dtype),
                                  block.pre_route(a32))[0]

    # -- after the optimizer ---------------------------------------------------
    def post_update(self, before, after, aux):
        """What follows an update step, on the parameter trees: the router
        stays as it was (it takes no gradient, and Adam's weight decay
        would still move it)."""
        params = dict(after['params'])
        for i in self.expert_layers:
            name = 'layer_%d' % i
            params[name] = dict(params[name],
                                router=before['params'][name]['router'])
        return dict(after, params=params)

    def epoch_dynamics(self, sums):
        """The epoch record's keys from the epoch's ``diag_*`` sums."""
        dynamics = super().epoch_dynamics(sums)
        if dynamics:
            dynamics['window_hidden_position_share'] = (
                100.0 * sums.get('diag_window_positions_hidden', 0.0)
                / max(sums.get('diag_window_positions_valid', 0.0), 1.0))
        return dynamics
