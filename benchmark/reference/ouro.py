"""Plain float32 forward of the Ouro-2.6B policy trunk on this chip's share
of each layer: a stack of layers run ``passes`` times over one set of
weights.

``jax.numpy`` only: no flax, no cache, NO SCAN OVER THE PASSES: a Python loop
over ``passes x layers`` layer applications, every pass's logits, value and
gate logit. Every mask is written over ALL positions of the sequence from
their absolute positions; the only concession to size is that queries are
taken in blocks. Callers run it under
``jax.default_matmul_precision('highest')`` with the program's own parameter
tree, so a difference is a difference in arithmetic and never in weights.

The equations (ISSUE 46; ByteDance/Ouro-2.6B ``config.json``; the lines the
config does not settle are the configuration file's ``assumed``).
``N(x, g) = x / rms(x) * g``, eps 1e-6:

* ``x_0 = Emb[id]``. For pass ``t = 1..passes``: ``u = x_{t-1}``; for each
  layer ``l``: ``a = u + N(Attn_l(N(u, g1_l)), g2_l)``,
  ``u = a + N(MLP_l(N(a, g3_l)), g4_l)``; then ``x_t = N(u, g_out)``.
  ``x_t`` feeds the next pass AND is pass ``t``'s features;
* ``Attn_l(n)``: ``q = W_q n``, ``k = W_k n``, ``v = W_v n`` a head of 128;
  ``q`` and ``k`` turned by rotary phases (theta 1e6, rotate-half over all
  128) at the position's index on EVERY layer and in every pass; query ``i``
  sees key ``j`` of the SAME pass and layer iff ``j <= i``;
  ``W_o softmax(q k^T / sqrt(d)) v``. No bias, no QK-norm, no gate.
  ``MLP_l(n) = W_down(silu(W_gate n) * W_up n)``;
* pass ``t``'s readout: logits ``x_t W_head``, a value row (tanh), and the
  gate's logit ``w_g . x_t + b_g`` (one row for all passes).

The layer holds some of the published heads (``W_q``, ``W_k``, ``W_v`` those
heads' columns, ``W_o`` their rows): its attention output is this chip's PART
of ``W_o``'s sum, normed as it is. A weight set that holds all heads gives
the uncut layer by the same code.
"""

import jax
import jax.numpy as jnp

# what names no architecture is the other plain reference's: the stored
# tree's values under ``param_scale``, ``N(x, g)`` and the rotary phases
from .trinity_mini import NEG, rms_norm, rotary, values


def keys_and_values(p, n, positions, cfg, use_rotary=True):
    """k (turned) and v of every position: (T, KV, d) each."""
    T, d = n.shape[0], cfg['head_dim']
    k = (n @ p['wk']).reshape(T, -1, d)
    if use_rotary:
        k = rotary(k, positions, cfg['rope_theta'])
    return k, (n @ p['wv']).reshape(T, -1, d)


def attention_part(p, n, positions, valid, cfg, use_rotary=True, kv=None,
                   block=256):
    """This chip's part of the layer's attention output, (T, hidden), and
    the keys and values it was taken over. ``kv``: K and V to read in place
    of this pass's own (a negative control)."""
    T, d = n.shape[0], cfg['head_dim']
    H = p['wq'].shape[1] // d
    q = (n @ p['wq']).reshape(T, H, d)
    if use_rotary:
        q = rotary(q, positions, cfg['rope_theta'])
    own = keys_and_values(p, n, positions, cfg, use_rotary)
    k, v = own if kv is None else kv
    # every query head beside its own key and value head
    k, v = (jnp.repeat(x, H // x.shape[1], axis=1) for x in (k, v))
    scale = d ** -0.5
    block = min(block, T)
    assert T % block == 0, (T, block)

    def one_block(args):
        qb, pq = args
        seen = (positions[None, :] <= pq[:, None]) & valid[None, :]
        s = scale * jnp.einsum('qhd,khd->hqk', qb, k)
        prob = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
        return jnp.einsum('hqk,khd->qhd', prob, v).reshape(block, H * d)
    out = jax.lax.map(one_block, (q.reshape(T // block, block, H, d),
                                  positions.reshape(T // block, block)))
    return out.reshape(T, H * d) @ p['wo'], own


def mlp(p, n):
    return (jax.nn.silu(n @ p['w_gate']) * (n @ p['w_up'])) @ p['w_down']


def embed(p, ids, cfg):
    return p['embed'][ids].astype(jnp.float32) / cfg.get('param_scale', 1.0)


def layer(p, x, positions, valid, cfg, use_rotary=True, kv=None):
    """One decoder layer on (T, hidden): the residual after it, and the keys
    and values its attention computed from ITS input."""
    p, eps = values(p, cfg), cfg['norm_eps']
    part, own = attention_part(p, rms_norm(x, p['norm_1'], eps), positions,
                               valid, cfg, use_rotary, kv)
    a = x + rms_norm(part, p['norm_2'], eps)
    return a + rms_norm(mlp(p, rms_norm(a, p['norm_3'], eps)),
                        p['norm_4'], eps), own


def between(norm_out, u, cfg):
    """``N_out``: what the next pass starts from and this pass's features."""
    return rms_norm(u, norm_out, cfg['norm_eps'])


def readout(p, xs, cfg):
    """xs (passes, T, hidden), the ``x_t``: every pass's logits (passes, T,
    ids held), value (passes, T) and gate logit (passes, T)."""
    p = values(p, cfg)
    return {'logits': xs @ p['head'],
            'value': jnp.tanh(xs @ p['value'])[..., 0],
            'gate': (xs @ p['gate'])[..., 0] + p['gate_bias'][0]}


def forward(variables, ids, first_position, valid, cfg, skip_layer=None,
            passes=None, use_rotary=True, norm_between=True,
            previous_pass_kv=False, stop_gradient_passes=False):
    """One sequence: ``ids`` (T,) int, the absolute position of its first
    element, ``valid`` (T,) bool. Returns ``logits`` (passes, T, ids held),
    ``value`` and ``gate`` (passes, T). The further arguments are the
    negative controls': a layer left out of every pass; fewer passes; the
    phases left out; ``N_out`` left out BETWEEN the passes (each pass's
    features still take it); every pass but the first reading the PREVIOUS
    pass's keys and values (one cache for all passes); all passes but the
    last under ``stop_gradient`` (one use of each weight)."""
    p = variables['params']
    positions = first_position + jnp.arange(ids.shape[0])
    kept = [i for i in range(cfg['layers']) if i != skip_layer]
    x = embed(p, ids, cfg)
    xs, held = [], {}
    n_passes = cfg['passes'] if passes is None else passes
    for t in range(n_passes):
        for i in kept:
            x, own = layer(p['layer_%d' % i], x, positions, valid, cfg,
                           use_rotary,
                           held.get(i) if previous_pass_kv else None)
            held[i] = own
        features = between(p['norm_out'], x, cfg)
        if norm_between:
            x = features
        if stop_gradient_passes and t < n_passes - 1:
            x, features = (jax.lax.stop_gradient(a) for a in (x, features))
        xs.append(features)
    return readout(p, jnp.stack(xs), cfg)
