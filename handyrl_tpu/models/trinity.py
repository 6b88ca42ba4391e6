"""Trinity-Mini as a policy trunk: grouped-query attention of two kinds in
one net, and sparse experts of which this chip holds a share.

Source: https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
(``model_type: afmoe``: 32 layers of hidden 2048; 32 query heads and 4 KV
heads of 128; ``layer_types`` three ``sliding_attention`` (window 2048) then
one ``full_attention``, eight times; the first 2 layers dense (SwiGLU 6144),
the others 128 routed experts (SwiGLU 1024 each), 8 a token, one shared
expert; sigmoid scores, ``route_norm``, ``route_scale`` 2.826,
``load_balance_coeff`` 0.001; vocabulary 200,192, untied; ``rope_theta`` 1e4;
RMSNorm eps 1e-5). A layer, on the float32 residual ``h``:

* ``a = N_in(h)``; ``q = W_q a``, ``k = W_k a``, ``v = W_v a``, ``g = W_g a``;
  ``q``, ``k`` normalised over a head's 128 (one weight vector each a layer).
  A ``sliding`` layer turns ``q`` and ``k`` by rotary phases and query ``i``
  sees key ``j`` iff ``i - window < j <= i``; a ``full`` layer has no
  positional encoding and ``j <= i``. Query head ``n`` reads KV head ``n //
  (heads / kv_heads)``; ``attn = W_o (softmax(q k^T / sqrt(d)) v *
  sigmoid(g))``;
* ``h = h + N_post_attn(attn)``; ``m = N_pre_mlp(h)``; ``h = h +
  N_post_mlp(f(m))``;
* dense: ``f(m) = W_down (silu(W_gate m) * W_up m)``;
* experts: ``s = sigmoid(W_r m)`` in float32; ``S`` the 8 largest of ``s +
  b``; ``w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)``; ``f(m) =
  Shared(m) + sum_{e in S} w_e Expert_e(m)``; after every update ``b <- b +
  rate * centred(sign(mean(c) - c))``, ``c`` the tokens each expert was
  chosen for (``post_update``).

The layer holds ``heads_held`` / ``kv_heads_held`` of the published heads
and the experts ``experts_held`` (indices among the published 128): this
chip's share where several chips share each layer. It routes over ALL
experts at the published router width, keeps the published 8 a token and
their normalisation over all 8, and computes the part of the sum that ITS
experts give (rows sorted by expert, one grouped product, no capacity and
no dropped row) plus the shared expert; the attention output is its heads'
part of ``W_o``'s sum. Both partial results go on as they are: no code
stands in for the other chips. On a lone share the absent experts add
nothing, so the part of the router's gradient this chip sees points at
them; in a deployment that gradient is summed over the shares. Here the
router takes NO gradient (``W_r`` stays as seeded; ``b`` follows the rule).

``param_scale`` (1 by default, a power of two): every matrix is stored at
``param_scale`` times its value and each product's result divided by it, so
the same function of the same weights, whose stored numbers the learner's
one learning rate (3e-8 a trained position: 2.5e-4 for 8,192 of them, made
for nets of 1e5 parameters) moves by 1 / ``param_scale`` of their size. At
the plain parametrisation a seeded net of this width loses its positions'
differences within ten update steps of that rate (Adam's first steps are
sign steps, coherent over a 2048 x 6144 matrix), and every token then
chooses the same 8 experts (PERF.md section 6, PR 38).

Two entries over one set of parameters, as ``models/evabyte.py``:
``sequence(ids, first_position, valid)`` (a window as one causal forward;
it returns ``policy_features``, which ``policy_logits`` turns into logits a
block of positions at a time, ops/losses.py) and ``__call__(id, hidden)``
(one position through the cache). ``hidden`` holds K and V of two lengths
side by side: a circle of ``window_size`` rows on a sliding layer (keys are
stored already turned, so a row needs no position), ``max_positions`` rows
on a full one, (sequence, row, KV heads x d), and ONE counter a sequence.
Nothing is cleared: what the counter has not reached is masked, or, on a
TPU, not read (``models/decode_kernel.py``: the 8 query heads of the held
KV head are the rows of the block kernel's matrix).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import attention, experts, register
from .shell import ExpertTrunkNet
from .trunk import burn_in_as_state, dot, f32, heads_of, rms_norm, rotary

PUBLISHED_LAYERS = ('sliding', 'sliding', 'sliding', 'full') * 8


def _swiglu(x, w_gate, w_up, w_down, dtype, inv):
    """``inv``: 1 / ``param_scale``, taken on each product's result."""
    act = (jax.nn.silu(dot(x, w_gate, dtype) * inv)
           * (dot(x, w_up, dtype) * inv))
    return dot(act, w_down, dtype, out=f32) * inv


class TrinityBlock(nn.Module):
    """One decoder layer: this chip's heads, and either the whole dense MLP
    or the router, the shared expert and this chip's routed experts."""
    hidden_size: int
    heads_held: int
    kv_heads_held: int
    head_dim: int
    kind: str                     # 'sliding' | 'full'
    window_size: int
    rope_theta: float
    norm_eps: float
    mlp_size: int                 # the dense MLP's width; 0 on an expert layer
    expert_size: int
    experts_published: int
    experts_held: Tuple[int, ...]
    experts_per_token: int
    route_scale: float
    query_block: int
    dense_rows: int
    param_scale: float
    dtype: Any

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        ones = nn.initializers.ones
        D, d = self.hidden_size, self.head_dim
        A, KV = self.heads_held * d, self.kv_heads_held * d
        self.wq = self.param('wq', init, (D, A))
        self.wk = self.param('wk', init, (D, KV))
        self.wv = self.param('wv', init, (D, KV))
        self.wg = self.param('wg', init, (D, A))
        self.wo = self.param('wo', init, (A, D))
        self.q_norm = self.param('q_norm', ones, (d,))
        self.k_norm = self.param('k_norm', ones, (d,))
        for name in ('norm_in', 'norm_post_attn', 'norm_pre_mlp',
                     'norm_post_mlp'):
            setattr(self, name, self.param(name, ones, (D,)))
        if self.mlp_size:
            M = self.mlp_size
            self.w_gate = self.param('w_gate', init, (D, M))
            self.w_up = self.param('w_up', init, (D, M))
            self.w_down = self.param('w_down', init, (M, D))
            return
        E, F, held = self.experts_published, self.expert_size, \
            len(self.experts_held)
        self.router = self.param('router', init, (D, E))
        self.router_bias = self.param('router_bias', nn.initializers.zeros,
                                      (E,))
        self.experts_gate = self.param('experts_gate', init, (held, D, F))
        self.experts_up = self.param('experts_up', init, (held, D, F))
        self.experts_down = self.param('experts_down', init, (held, F, D))
        self.shared_gate = self.param('shared_gate', init, (D, F))
        self.shared_up = self.param('shared_up', init, (D, F))
        self.shared_down = self.param('shared_down', init, (F, D))

    @property
    def inv(self):
        """What each product's result is multiplied by (``param_scale``)."""
        return 1 / self.param_scale

    # -- attention -----------------------------------------------------------
    def _qkvg(self, x, positions):
        """x (..., D) float32 at ``positions`` (...,) -> q (..., H, d), k, v
        (..., KV, d) and the gate (..., H * d), in ``dtype``."""
        a = rms_norm(x, self.norm_in, self.norm_eps, self.dtype)
        q = heads_of(a, self.wq, self.heads_held, self.dtype, self.inv)
        k = heads_of(a, self.wk, self.kv_heads_held, self.dtype, self.inv)
        v = heads_of(a, self.wv, self.kv_heads_held, self.dtype, self.inv)
        q = rms_norm(q, self.q_norm, self.norm_eps, self.dtype)
        k = rms_norm(k, self.k_norm, self.norm_eps, self.dtype)
        if self.kind == 'sliding':
            pos = positions[..., None]
            q = rotary(q, pos, self.rope_theta)
            k = rotary(k, pos, self.rope_theta)
        return q, k, v, dot(a, self.wg, self.dtype) * self.inv

    def _out(self, y, gate):
        """This chip's part of ``W_o``'s sum, before the branch's norm."""
        return dot(y * jax.nn.sigmoid(gate.astype(f32)).astype(y.dtype),
                   self.wo, self.dtype, out=f32) * self.inv

    def _after_attention(self, x, part):
        return x + rms_norm(part, self.norm_post_attn, self.norm_eps, f32)

    def attention_part(self, x, positions, valid, no_grad_prefix=0):
        """This chip's part of the attention output, before the branch's
        norm: (B, T, D) float32 (the head-share test sums four of these)."""
        q, k, v, gate = self._qkvg(x, positions)
        k, v = burn_in_as_state(k, v, no_grad_prefix)
        window = self.window_size if self.kind == 'sliding' else None
        y = jax.vmap(lambda *seq: attention.sequence_attention(
            *seq, window, self.query_block))(q, k, v, positions, valid)
        return self._out(y, gate)

    # -- the MLP: dense, or the experts held -------------------------------
    def _route(self, m32):
        """Scores and the published top-k over ALL experts, in float32:
        (ids (n, k), weights (n, k), tokens an expert (E,)). No gradient
        passes (module docstring)."""
        s = jax.nn.sigmoid(jnp.dot(m32, self.router.astype(f32),
                                   precision=jax.lax.Precision.HIGHEST)
                           * self.inv)
        _, ids = jax.lax.top_k(s + self.router_bias.astype(f32),
                               self.experts_per_token)
        # the choices, for whoever asks for them (the checks compare them
        # with the reference's); nothing is kept where nobody does
        self.sow('intermediates', 'route_ids', ids)
        picked = jnp.take_along_axis(s, ids, axis=1)
        w = self.route_scale * picked / (
            picked.sum(axis=1, keepdims=True) + 1e-20)
        counts = experts.chosen_counts(ids, self.experts_published)
        return ids, jax.lax.stop_gradient(w), counts

    def _experts_every_row(self, m, slot, w):
        """A few rows: every held expert on every row, weighted by ``w_e``
        or by 0."""
        with jax.named_scope('moe_route'):
            gate = experts.every_row_gate(slot, w, len(self.experts_held))
        with jax.named_scope('moe_experts'):
            return experts.every_row_products(
                m, gate, self.experts_gate, self.experts_up,
                self.experts_down, jax.nn.silu, self.dtype,
                self.inv), jnp.int32(0)

    def _experts_grouped(self, m, slot, w):
        """Dropless under any imbalance (``models/experts.py``): the (row,
        choice) pairs sorted by the expert's slot, the pairs held here
        through the short buffer or every pair through the long one, ONE
        grouped product over the experts held, and the weighted sum back by
        row. Also the plan's tally (the rows dropped, 0, and whether the
        short buffer was taken)."""
        with jax.named_scope('moe_route'):
            plan = experts.sort_plan(slot, len(self.experts_held),
                                     self.experts_published)

        def products(rows, groups, *matrices):
            with jax.named_scope('moe_experts'):
                return experts.grouped_products(
                    rows, groups, *matrices, jax.nn.silu, self.dtype,
                    self.inv)
        f = experts.dispatched_sum(
            m, plan, slot, w, self.experts_published, products,
            (self.experts_gate, self.experts_up, self.experts_down),
            'moe_route')
        return f, plan.tally

    def mlp_branch(self, x, shared: bool = True):
        """x (n, D) float32 -> ``f(N_pre_mlp(x))`` before the branch's norm
        and, on an expert layer, the tokens each published expert was chosen
        for (E,) and the dispatch's tally (``experts.SortPlan.tally``; 0 for
        a few rows, which take no buffer). ``shared=False`` leaves the
        shared expert out (the share test counts it once)."""
        m32 = rms_norm(x, self.norm_pre_mlp, self.norm_eps, f32)
        m = m32.astype(self.dtype)
        if self.mlp_size:
            with jax.named_scope('trunk_mlp'):
                return _swiglu(m, self.w_gate, self.w_up, self.w_down,
                               self.dtype, self.inv), None, None
        with jax.named_scope('moe_route'):
            ids, w, counts = self._route(m32)
            slot = experts.held_slot(ids, self.experts_held,
                                     self.experts_published)
        branch = (self._experts_every_row if m.shape[0] <= self.dense_rows
                  else self._experts_grouped)
        f, tally = branch(m, slot, w)
        if shared:
            with jax.named_scope('moe_shared'):
                f = f + _swiglu(m, self.shared_gate, self.shared_up,
                                self.shared_down, self.dtype, self.inv)
        return f, counts, tally

    def _mlp(self, x):
        f, counts, tally = self.mlp_branch(x)
        x = x + rms_norm(f, self.norm_post_mlp, self.norm_eps, f32)
        return x, counts, tally

    # -- a whole window ------------------------------------------------------
    def sequence(self, x, positions, valid, no_grad_prefix=0):
        B, T, D = x.shape
        with jax.named_scope('gqa_attention'):
            x = self._after_attention(x, self.attention_part(
                x, positions, valid, no_grad_prefix))
        x, counts, tally = self._mlp(x.reshape(B * T, D))
        return x.reshape(B, T, D), counts, tally

    # -- one position through the cache --------------------------------------
    def step(self, x, pos, cache):
        """x (B, D) float32 at each sequence's own position ``pos`` (B,);
        cache = (k, v), each (B, rows, KV * d): ``window_size`` rows written
        round and round on a sliding layer, ``max_positions`` on a full one
        (a row is the KV heads side by side: with a head axis of its own a
        lone KV head of 128 would be padded to a tile of 8)."""
        ck, cv = cache
        KV = self.kv_heads_held
        with jax.named_scope('gqa_attention'):
            q, k, v, gate = self._qkvg(x, pos)           # (B, H | KV, d)
            with jax.named_scope('state_update'):
                ck, cv = attention.cache_write(ck, cv, k, v, pos)
            y = attention.cache_attention(q, ck, cv, pos,
                                          self.kind == 'sliding', KV,
                                          self.dtype)
            x = self._after_attention(x, self._out(y, gate))
        x, _counts, _tally = self._mlp(x)
        return x, (ck, cv)


@register('TrinityNet')
class TrinityNet(ExpertTrunkNet):
    """The trunk with its untied head read as a policy over the ids held and
    a value row. Observations are int32 ids. The defaults are the published
    counts, at which every layer IS the published layer; the depth, the
    heads, the experts and the slice of the vocabulary held are the
    deployment's cut (ISSUE 38)."""
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    dense_layers: int = 2
    heads_held: int = 32
    kv_heads_held: int = 4
    head_dim: int = 128
    mlp_size: int = 6144
    expert_size: int = 1024
    experts_published: int = 128
    experts_held: Optional[Tuple[int, ...]] = None     # None: all of them
    experts_per_token: int = 8
    route_scale: float = 2.826
    bias_update_rate: float = 0.001
    vocab: int = 200192
    window_size: int = 2048
    max_positions: int = 8192
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    query_block: int = 512
    # rows at or under which every held expert takes every row (a decode
    # ply: 32 rows, 2 an expert): the weights are read once either way, and
    # a grouped product over a handful of rows is all tile padding
    dense_rows: int = 128
    # every matrix is STORED at ``param_scale`` times its value and each
    # product's result divided by it (a power of two: the same bits), so
    # the learner's one learning rate moves a weight by 1 / param_scale of
    # what it would; 1 is the plain parametrisation (module docstring)
    param_scale: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    windowed_kind = 'sliding'

    def setup(self):
        init = nn.initializers.normal(0.02 * self.param_scale)
        self.embed = self.param('embed', init, (self.vocab, self.hidden_size))
        self.blocks = [TrinityBlock(
            self.hidden_size, self.heads_held, self.kv_heads_held,
            self.head_dim, kind, self.window_size, self.rope_theta,
            self.norm_eps, self.mlp_size if i < self.dense_layers else 0,
            self.expert_size, self.experts_published, self.held,
            self.experts_per_token, self.route_scale, self.query_block,
            self.dense_rows, self.param_scale, self.dtype,
            name='layer_%d' % i)
            for i, kind in enumerate(self.layer_types)]
        self.norm_out = self.param('norm_out', nn.initializers.ones,
                                   (self.hidden_size,))
        self.head = self.param('head', init, (self.hidden_size, self.vocab))
        self.value = self.param('value', init, (self.hidden_size, 1))

    def _embed(self, ids):
        return self.embed[ids].astype(f32) * (self.hidden_size ** 0.5
                                              / self.param_scale)

    def mlp_branch(self, layer: int, x, shared: bool = True):
        """Layer ``layer``'s ``f(m)`` for (n, D) inputs, before the branch's
        norm (the expert-share test sums eight of these)."""
        return self.blocks[layer].mlp_branch(x, shared)[0]

    # -- after the optimizer ---------------------------------------------------
    def post_update(self, before, after, aux):
        """What follows an update step, on the parameter trees: the router
        stays as it was (it takes no gradient, and Adam's weight decay
        would still move it); ``b`` is Adam's to leave alone and moves by
        the source's rule, ``rate * (sign(mean(c) - c)`` centred to mean
        zero), from the tokens each expert was chosen for in this step."""
        params = dict(after['params'])
        for n, i in enumerate(self.expert_layers):
            name = 'layer_%d' % i
            c = aux['moe_counts'][n].astype(f32)
            delta = jnp.sign(c.mean() - c)
            old = before['params'][name]
            params[name] = dict(
                params[name], router=old['router'],
                router_bias=old['router_bias'] + self.bias_update_rate
                * (delta - delta.mean()))
        return dict(after, params=params)
