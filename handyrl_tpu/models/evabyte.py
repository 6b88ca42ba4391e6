"""EvaByte as a policy trunk: a byte-level decoder whose attention is exact
inside a window and reads everything older through chunk summaries.

Source: https://huggingface.co/EvaByte/EvaByte/blob/main/config.json
(EvaByte 6.5B: 32 layers, hidden 4096, 32 heads of 128, SwiGLU 11008,
vocabulary 320, ``chunk_size`` 16, ``window_size`` 2048, 8 prediction heads,
``rope_theta`` 1e5, RMSNorm with unit offset, float32 residual adds and
logits). With absolute position p, chunk c(p) = p // 16, window w(p) = p //
2048, per head for query n:

* the exact set ``L_n = {m <= n : w(m) = w(n)}``;
* the remote set ``R_n``: the chunks lying wholly in windows before w(n),
  each read through ``k~_c = mean_{m in c} k_m + mu_h`` and ``v~_c = sum_{m in
  c} softmax_{m in c}(s <k_m, phi_h>) v_m`` (``mu_h``, ``phi_h`` learned per
  head and layer);
* ONE softmax over both (EVA, "Efficient Attention via Control Variates",
  ICLR 2023, in the chunked form of the model's published code).

Two entries over one set of parameters:

* ``sequence(ids, first_position, valid)``: T positions of each sequence in
  one causal forward (the learner's window, ops/losses.py; the checks). A
  block of ``query_block`` queries multiplies, of the ``T`` local keys, the
  ``window + query_block`` that end with its own last query (a key further
  back lies in an earlier window whatever the first position: 2,304 of
  4,096 in the cell), masked by position inside them, and all ``T // chunk
  + 1`` summaries, under the one soft-max (``sequence_attention``; the
  span's arithmetic is ``models/attention.py``'s ``block_keys`` and
  ``block_span``, PERF.md, PR 55);
* ``__call__(id, hidden)``: one position through the cache (rollout, eval,
  the serving engine). ``hidden`` holds, a layer, ONE buffer of K and one of
  V (window + max_positions / chunk, heads * d): the current window's rows,
  then the summaries of the whole game; and ONE position counter a
  sequence. A row is the heads side by side, so the step's attention reads
  a buffer as it lies (``models/attention.py`` ``span_attention`` over
  ``eva_spans``: on a TPU the block kernel, which reads only the row blocks
  the window's slot and the summaries' count have reached, PERF.md, PR 54;
  summaries in buffers of their own are fetched whole into fast memory and
  written back every ply, PERF.md, PR 50). A row is written at the
  sequence's own counter; nothing is ever cleared: what a counter does not
  reach is masked, so a new game resets the counter alone (``reset_hidden``).

The layer holds ``heads_held`` of the ``heads_published`` heads: this chip's
share where four chips share each layer by heads. ``W_q``, ``W_k``, ``W_v``
have those heads' columns and ``W_o`` their rows; the attention output is
this chip's part of ``W_o``'s sum and is passed on as such. No code stands
in for the other chips.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from . import attention, register
from .shell import TrunkNet
from .trunk import NEG, burn_in_as_state, dot, f32, rms_norm, rotary

# the published norm multiplies by one plus its weight
_rms_norm = functools.partial(rms_norm, unit_offset=True)


def _summarise(k, v, mu, phi, member):
    """Chunk summaries of one sequence. k, v (H, T, d); ``member`` (C, T)
    says which positions each chunk holds. Returns k~, v~ (H, C, d)."""
    with jax.named_scope('eva_summarise'):
        m = member.astype(k.dtype)
        count = jnp.maximum(member.sum(axis=1), 1).astype(f32)
        k_mean = jnp.einsum('ct,htd->hcd', m, k,
                            preferred_element_type=f32) / count[None, :, None]
        sk = (k_mean + mu.astype(f32)[:, None, :]).astype(k.dtype)
        scale = k.shape[-1] ** -0.5
        logit = scale * jnp.einsum('htd,hd->ht', k, phi.astype(k.dtype),
                                   preferred_element_type=f32)
        logit = jnp.where(member[None], logit[:, None, :], NEG)   # (H, C, T)
        weight = jax.nn.softmax(logit, axis=-1) * member[None]
        sv = jnp.einsum('hct,htd->hcd', weight.astype(v.dtype), v,
                        preferred_element_type=f32).astype(v.dtype)
        return sk, sv


def _block_attention(q, k, v, sk, sv, positions, valid, chunk_window,
                     present, window, query_block):
    """``sequence_attention`` after the summaries, traced where it is called:
    q, k, v (H, T, d) and the summaries sk, sv (H, C, d) of the chunks in
    windows ``chunk_window`` (C,), those ``present`` that hold a valid
    position -> (T, H * d)."""
    H, T, d = q.shape
    W = window
    scale = d ** -0.5
    bq = min(query_block, T)
    n_keys = attention.block_keys(T, W, query_block)
    if n_keys < T:
        pk = attention.key_positions(positions, valid)

    @jax.checkpoint
    def block(args):
        qb, pq, *b = args                          # (H, bq, d), (bq,), [()]
        if b:
            kb, vb, pb = attention.block_span(k, v, pk, b[0], bq, n_keys)
        else:
            kb, vb, pb = k, v, positions
        local = ((pq[:, None] // W == pb[None, :] // W)
                 & (pb[None, :] <= pq[:, None]))
        if not b:
            local = local & valid[None, :]
        remote = ((chunk_window[None, :] < pq[:, None] // W)
                  & present[None, :])
        s_local = scale * jnp.einsum('hqd,hkd->hqk', qb, kb,
                                     preferred_element_type=f32)
        s_remote = scale * jnp.einsum('hqd,hcd->hqc', qb, sk,
                                      preferred_element_type=f32)
        scores = jnp.concatenate(
            [jnp.where(local[None], s_local, NEG),
             jnp.where(remote[None], s_remote, NEG)], axis=-1)
        prob = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return (jnp.einsum('hqk,hkd->hqd', prob[..., :n_keys], vb,
                           preferred_element_type=f32)
                + jnp.einsum('hqc,hcd->hqd', prob[..., n_keys:], sv,
                             preferred_element_type=f32)).astype(v.dtype)

    qs = q.reshape(H, T // bq, bq, d).swapaxes(0, 1)           # (nb, H, bq, d)
    # only a block that takes a span of the keys needs its index
    index = (jnp.arange(T // bq),) if n_keys < T else ()
    out = jax.lax.map(block, (qs, positions.reshape(T // bq, bq)) + index)
    return out.transpose(0, 2, 1, 3).reshape(T, H * d)


_shared_block_attention = jax.jit(_block_attention,
                                  static_argnames=('window', 'query_block'))


def sequence_attention(q, k, v, mu, phi, positions, valid, window, chunk,
                       query_block):
    """One sequence. q, k, v (T, H, d), mu, phi (H, d) -> (T, H * d): query
    ``n`` under ONE soft-max over its exact set (the keys of its own window
    of ``window`` positions up to itself) and the summaries of the chunks in
    the windows before. ``positions`` must rise by one an index (the net
    passes ``first_position + arange(T)``), so a key more than ``window - 1``
    indices before a query is never local to it: a block of queries
    multiplies only the ``attention.block_keys`` keys that end with its own
    last query, and inside them the mask is by position, a window's boundary
    wherever it falls. Every block multiplies all ``T // chunk + 1``
    summaries. A query that sees nothing (padding past its window's last
    valid key, with no window before) gets the mean of the values its block
    was handed: nothing reads it.

    The blocks of a layer that takes a span are one function however many
    layers a net has: traced, differentiated and lowered ONCE a program, as
    ``attention.sequence_attention``'s are and for its reason (a block's
    slices cost the host more to trace than they save the chip otherwise:
    PERF.md, PR 48). Where the span is every key (a window as long as the
    sequence) the blocks are traced where they stand."""
    T, H, d = q.shape
    q, k, v = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))          # (H, T, d)
    n_chunks = T // chunk + 1
    chunk_ids = positions[0] // chunk + jnp.arange(n_chunks)
    member = ((positions[None, :] // chunk == chunk_ids[:, None])
              & valid[None, :])                                   # (C, T)
    sk, sv = _summarise(k, v, mu, phi, member)
    chunk_window = chunk_ids * chunk // window
    present = member.any(axis=1)
    span = attention.block_keys(T, window, query_block) < T
    return (_shared_block_attention if span else _block_attention)(
        q, k, v, sk, sv, positions, valid, chunk_window, present,
        window=window, query_block=query_block)


def eva_spans(pos, window, chunk, n_rows):
    """What the query at position ``pos`` (one a sequence, jax or numpy)
    sees of a layer's buffer of ``n_rows`` rows: its window's rows up to its
    own slot, and a summary for every chunk of the windows BEFORE its own
    (the running summary of the chunk being played, at row ``window + pos //
    chunk``, lies past that count until its window is over)."""
    return [attention.Span(0, pos % window + 1, window),
            attention.Span(window, pos // window * (window // chunk),
                           n_rows - window)]


class EvaBlock(nn.Module):
    """One decoder layer: this chip's heads of the attention, the whole MLP."""
    hidden_size: int
    heads_held: int
    head_dim: int
    mlp_size: int
    chunk_size: int
    window_size: int
    rope_theta: float
    norm_eps: float
    query_block: int
    dtype: Any

    def setup(self):
        init = nn.initializers.normal(0.02)
        D, A = self.hidden_size, self.heads_held * self.head_dim
        self.wq = self.param('wq', init, (D, A))
        self.wk = self.param('wk', init, (D, A))
        self.wv = self.param('wv', init, (D, A))
        self.wo = self.param('wo', init, (A, D))
        self.mu = self.param('mu', init, (self.heads_held, self.head_dim))
        self.phi = self.param('phi', init, (self.heads_held, self.head_dim))
        self.w_gate = self.param('w_gate', init, (D, self.mlp_size))
        self.w_up = self.param('w_up', init, (D, self.mlp_size))
        self.w_down = self.param('w_down', init, (self.mlp_size, D))
        self.norm_attn = self.param('norm_attn', nn.initializers.zeros, (D,))
        self.norm_mlp = self.param('norm_mlp', nn.initializers.zeros, (D,))

    # -- shared -------------------------------------------------------------
    def _qkv(self, x, positions):
        """x (..., D) float32 -> q, k, v (..., H, d) in ``dtype``, q and k
        turned by their positions' phases."""
        h = _rms_norm(x, self.norm_attn, self.norm_eps, self.dtype)
        shape = x.shape[:-1] + (self.heads_held, self.head_dim)
        q = dot(h, self.wq, self.dtype).reshape(shape)
        k = dot(h, self.wk, self.dtype).reshape(shape)
        v = dot(h, self.wv, self.dtype).reshape(shape)
        pos = positions[..., None]
        return (rotary(q, pos, self.rope_theta),
                rotary(k, pos, self.rope_theta), v)

    def mlp(self, x):
        with jax.named_scope('trunk_mlp'):
            h = _rms_norm(x, self.norm_mlp, self.norm_eps, self.dtype)
            act = (jax.nn.silu(dot(h, self.w_gate, self.dtype))
                   * dot(h, self.w_up, self.dtype))
            return x + dot(act, self.w_down, self.dtype, out=f32)

    # -- a whole window -----------------------------------------------------
    def attention_part(self, x, positions, valid, no_grad_prefix=0):
        """This chip's part of the attention output for (B, T, D) float32
        inputs at absolute ``positions`` (B, T): (B, T, D) float32."""
        with jax.named_scope('eva_attention'):
            q, k, v = self._qkv(x, positions)
            k, v = burn_in_as_state(k, v, no_grad_prefix)
            y = jax.vmap(lambda q, k, v, positions, valid: sequence_attention(
                q, k, v, self.mu, self.phi, positions, valid,
                self.window_size, self.chunk_size, self.query_block))(
                    q, k, v, positions, valid)
            return dot(y, self.wo, self.dtype, out=f32)

    def sequence(self, x, positions, valid, no_grad_prefix=0):
        x = x + self.attention_part(x, positions, valid, no_grad_prefix)
        return self.mlp(x)

    # -- one position through the cache -------------------------------------
    def step(self, x, pos, cache):
        """x (B, D) float32 at each sequence's own position ``pos`` (B,);
        cache = (k, v (B, window + chunks, H * d)): the window's rows, then
        the game's summaries; a row is the heads side by side, as the
        decode attention reads it."""
        ck, cv = cache
        W, chunk = self.window_size, self.chunk_size
        H, d = self.heads_held, self.head_dim
        B = x.shape[0]
        rows = jnp.arange(B)
        with jax.named_scope('eva_attention'):
            q, k, v = self._qkv(x, pos)                        # (B, H, d)
            slot = pos % W
            with jax.named_scope('state_update'):
                ck, cv = attention.cache_write(ck, cv, k, v, slot)
            # the chunk this position lies in, summarised over its members
            # so far; its slot is read only once its window is over, by
            # which time it is whole
            start = (slot // chunk) * chunk
            inside = start[:, None] + jnp.arange(chunk)[None, :]   # (B, chunk)
            member = inside <= slot[:, None]
            heads_first = lambda c: jnp.swapaxes(               # (H, chunk, d)
                c.reshape(chunk, H, d), 0, 1)
            sk, sv = jax.vmap(
                lambda kc, vc, m: _summarise(
                    heads_first(kc), heads_first(vc),
                    self.mu, self.phi, m[None]))(
                ck[rows[:, None], inside], cv[rows[:, None], inside],
                member)                                        # (B, H, 1, d)
            with jax.named_scope('state_update'):
                ck = ck.at[rows, W + pos // chunk].set(sk.reshape(B, H * d))
                cv = cv.at[rows, W + pos // chunk].set(sv.reshape(B, H * d))
            # one query row a head: the heads' queries side by side as ONE
            # matrix against the rows, one soft-max over rows and summaries
            y = attention.span_attention(
                q, ck, cv, eva_spans(pos, W, chunk, ck.shape[1]), self.dtype)
            x = x + dot(y, self.wo, self.dtype, out=f32)
        return self.mlp(x), (ck, cv)


@register('EvaByteNet')
class EvaByteNet(TrunkNet):
    """The trunk with eight heads of prediction (head 0 is the policy over
    the ids, heads 1-7 the published multi-byte objective) and a value row.
    Observations are int32 ids. The published widths are the defaults; the
    depth and the heads held are the deployment's cut (ISSUE 34)."""
    hidden_size: int = 4096
    layers: int = 4
    heads_held: int = 8
    heads_published: int = 32
    head_dim: int = 128
    mlp_size: int = 11008
    vocab: int = 320
    chunk_size: int = 16
    window_size: int = 2048
    max_positions: int = 8192
    pred_heads: int = 8
    rope_theta: float = 1e5
    norm_eps: float = 1e-5
    # queries a block of the window's attention: a block takes ``window +
    # query_block`` local keys, so a smaller one takes fewer (2,304 of 4,096
    # at 256) in more and smaller products. One layer's attention, forward
    # and backward at the cell's shapes: 12.9 ms at 512, 9.75 at 256, 13.9
    # at 128 (PERF.md, PR 55)
    query_block: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        init = nn.initializers.normal(0.02)
        self.embed = self.param('embed', init, (self.vocab, self.hidden_size))
        self.blocks = [EvaBlock(
            self.hidden_size, self.heads_held, self.head_dim, self.mlp_size,
            self.chunk_size, self.window_size, self.rope_theta, self.norm_eps,
            self.query_block, self.dtype, name='layer_%d' % i)
            for i in range(self.layers)]
        self.norm_out = self.param('norm_out', nn.initializers.zeros,
                                   (self.hidden_size,))
        self.heads = self.param(
            'heads', init, (self.pred_heads, self.hidden_size, self.vocab))
        self.value = self.param('value', init, (self.hidden_size, 1))

    # -- the cache -----------------------------------------------------------
    def init_hidden(self, batch_shape=()):
        """A layer's K and V: the window's rows, then a summary a chunk of
        the longest game, in ONE buffer each."""
        rows = self.window_size + self.max_positions // self.chunk_size
        return attention.init_cache(
            batch_shape, [rows] * self.layers,
            self.heads_held * self.head_dim, self.dtype)

    def decode_rows(self, pos):
        """(read, held): the rows of K (as many of V) that ONE ply of
        sequences at counters ``pos`` (numpy) reads, and those that their
        buffers hold, over every layer."""
        pos = np.asarray(pos)
        held = self.window_size + self.max_positions // self.chunk_size
        read = attention.spans_rows_read(
            eva_spans(pos, self.window_size, self.chunk_size, held),
            self.heads_held * self.head_dim, self.dtype)
        return self.layers * int(read.sum()), self.layers * held * pos.size

    def attention_key_share(self, T):
        """The local (query, key) pairs a window of ``T`` multiplies, as a
        share of all ``T x T``: every layer's blocks take one span."""
        return attention.key_share(T, [self.window_size], self.query_block)

    # -- inputs and outputs ----------------------------------------------------
    def _embed(self, ids):
        return self.embed[ids].astype(f32)

    def _window_readout(self, x):
        h = _rms_norm(x, self.norm_out, self.norm_eps, self.dtype)
        logits = jnp.einsum('...d,ndv->...nv', h,
                            self.heads.astype(self.dtype),
                            preferred_element_type=f32)
        value = jnp.tanh(dot(h, self.value, self.dtype, out=f32))
        return {'policy': logits[..., 0, :], 'value': value,
                'heads': logits[..., 1:, :]}

    def _readout(self, x):
        out = self._window_readout(x)
        out.pop('heads')    # acting reads head 0 alone
        return out

    def sequence(self, ids, first_position, valid, no_grad_prefix: int = 0):
        """T positions a sequence in one causal forward. ids (B, T) int32,
        first_position (B,) the absolute position of each sequence's first
        element, valid (B, T) bool. Returns policy (B, T, vocab), value
        (B, T, 1), heads (B, T, pred_heads - 1, vocab), all float32."""
        positions = first_position[:, None] + jnp.arange(ids.shape[1])
        x = self._embed(ids)
        for block in self.blocks:
            # one layer rematerialised at a time: the backward pass keeps
            # each layer's input and recomputes the rest
            x = nn.remat(EvaBlock.sequence, static_argnums=(4,))(
                block, x, positions, valid, no_grad_prefix)
        return self._window_readout(x)
