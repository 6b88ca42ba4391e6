"""Plain float32 forward of the SmallThinker-21BA3B policy trunk on this
chip's share of each layer.

``jax.numpy`` only: no flax, no cache, no grouping of rows by expert. Every
mask is written over ALL positions of the sequence from their absolute
positions; every held expert is computed on EVERY row and weighted by ``w_e``
or by 0. The only concessions to size are that queries are taken in blocks
and the experts one after the other. Callers run it under
``jax.default_matmul_precision('highest')`` with the program's own parameter
tree, so a difference is a difference in arithmetic and never in weights.

The equations (ISSUE 43; PowerInfer/SmallThinker-21BA3B-Instruct
``config.json``; the lines the config does not settle are the configuration
file's ``assumed``). ``N(x, g) = x / rms(x) * g``, eps 1e-6; per layer, on
the residual ``h``:

* ``a = N(h, g_in)``;
* the router, BEFORE attention: ``l = W_r a`` (64 logits, no bias); ``S``
  the 6 largest of ``l``; ``w_e = exp(l_e) / sum_{e' in S} exp(l_e')``;
* ``q = W_q a``, ``k = W_k a``, ``v = W_v a``: no bias, no QK-norm, no gate.
  Query head ``n`` reads KV head ``n // (heads / kv_heads)``. A ``global``
  layer has no positional encoding at all and query ``i`` sees key ``j`` iff
  ``j <= i``; a ``window`` layer turns ``q`` and ``k`` by rotary phases
  (theta 1.5e6, rotate-half over all 128) and ``i - 4096 < j <= i``.
  ``attn = W_o softmax(q k^T / sqrt(d)) v``;
* ``h = h + attn``; ``m = N(h, g_post)``; ``h = h + sum_{e in S, e held
  here} w_e W_d^e (relu(W_g^e m) * W_u^e m)``. The router's weights carry no
  gradient (``departures_from_source``);
* ``h0 = Emb[id]`` (no factor); last ``N(h, g_out)``, the untied head over
  the ids held and a value row (tanh).

The layer holds some of the published heads and experts (``W_q`` those
heads' columns, ``W_o`` their rows; the experts ``experts_held``): its
attention output and its experts' sum are this chip's PART of the whole. A
weight set that holds all of them gives the uncut layer by the same code.
"""

import jax
import jax.numpy as jnp

# what names no architecture is the other plain reference's: the stored
# tree's values under ``param_scale``, ``N(x, g)`` and the rotary phases
from .trinity_mini import NEG, rms_norm, rotary, values


def attention_part(p, a, positions, valid, cfg, kind, block=256):
    """This chip's part of the layer's attention output: (T, hidden)."""
    T, d = a.shape[0], cfg['head_dim']
    H, KV = p['wq'].shape[1] // d, p['wk'].shape[1] // d
    q = (a @ p['wq']).reshape(T, H, d)
    k = (a @ p['wk']).reshape(T, KV, d)
    v = (a @ p['wv']).reshape(T, KV, d)
    if kind != 'global':    # the controls' kinds turn too (layer_kinds)
        q = rotary(q, positions, cfg['rope_theta'])
        k = rotary(k, positions, cfg['rope_theta'])
    # every query head beside its own key and value head
    k, v = (jnp.repeat(x, H // KV, axis=1) for x in (k, v))
    scale = d ** -0.5
    block = min(block, T)
    assert T % block == 0, (T, block)

    def one_block(args):
        qb, pq = args
        seen = (positions[None, :] <= pq[:, None]) & valid[None, :]
        if kind == 'window':
            seen = seen & (positions[None, :] > pq[:, None]
                           - cfg['window_size'])
        s = scale * jnp.einsum('qhd,khd->hqk', qb, k)
        prob = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
        return jnp.einsum('hqk,khd->qhd', prob, v).reshape(block, H * d)
    out = jax.lax.map(one_block, (q.reshape(T // block, block, H, d),
                                  positions.reshape(T // block, block)))
    return out.reshape(T, H * d) @ p['wo']


def route(p, a, cfg):
    """The published router over ALL experts: (ids (T, k), weights (T, k)),
    the soft-max taken over the kept logits."""
    kept, ids = jax.lax.top_k(a @ p['router'], cfg['experts_per_token'])
    return ids, jax.lax.stop_gradient(jax.nn.softmax(kept, axis=-1))


def reglu(x, w_gate, w_up, w_down, activation=jax.nn.relu):
    return (activation(x @ w_gate) * (x @ w_up)) @ w_down


def experts_part(p, m, ids, w, cfg, activation=jax.nn.relu):
    """The held experts' part of the sum: each of them on every row, its
    weight ``w_e`` where the row chose it, else 0."""
    def one(total, args):
        e, w_gate, w_up, w_down = args
        weight = (w * (ids == e)).sum(axis=1)                   # (T,)
        return total + weight[:, None] * reglu(m, w_gate, w_up, w_down,
                                               activation), None
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.asarray(cfg['experts_held']), p['experts_gate'],
         p['experts_up'], p['experts_down']))
    return total


def embed(p, ids, cfg):
    return p['embed'][ids].astype(jnp.float32) / cfg.get('param_scale', 1.0)


def layer(p, x, positions, valid, cfg, kind, use_experts=True,
          route_after_attention=False, silu_experts=False):
    """One decoder layer on (T, hidden): the residual after it and the
    router's choices (T, k). ``kind`` is the attention's (``global`` |
    ``window``). The further arguments are negative controls: the experts'
    sum left out, the router reading ``N_post(h)`` AFTER attention (another
    architecture's order), SiLU in the experts' gate."""
    p, eps = values(p, cfg), cfg['norm_eps']
    a = rms_norm(x, p['norm_in'], eps)
    if not route_after_attention:
        ids, w = route(p, a, cfg)
    x = x + attention_part(p, a, positions, valid, cfg, kind)
    m = rms_norm(x, p['norm_post'], eps)
    if route_after_attention:
        ids, w = route(p, m, cfg)
    if use_experts:
        x = x + experts_part(p, m, ids, w, cfg,
                             jax.nn.silu if silu_experts else jax.nn.relu)
    return x, ids


def readout(p, x, cfg):
    """The head's logits (T, ids held) and the value (T,)."""
    p = values(p, cfg)
    h = rms_norm(x, p['norm_out'], cfg['norm_eps'])
    return {'logits': h @ p['head'], 'value': jnp.tanh(h @ p['value'])[:, 0]}


def layer_kinds(cfg, use_window=True, rotary_on_global=False):
    """Each layer's attention kind as the reference runs it; two controls
    turn a window layer into one that sees everything (its phases kept),
    and put phases on the global layers."""
    kinds = []
    for kind in cfg['layer_types']:
        if kind == 'window' and not use_window:
            kinds.append('window_unbounded')
        elif kind == 'global' and rotary_on_global:
            kinds.append('global_rotary')
        else:
            kinds.append(kind)
    return kinds


def forward(variables, ids, first_position, valid, cfg, skip_layer=None,
            use_window=True, rotary_on_global=False, **layer_args):
    """One sequence: ``ids`` (T,) int, the absolute position of its first
    element, ``valid`` (T,) bool. Returns ``logits`` (T, ids held),
    ``value`` (T,) and ``routes``, the router's choices of every layer
    (layers, T, k). The further arguments are the negative controls'."""
    p = variables['params']
    positions = first_position + jnp.arange(ids.shape[0])
    x = embed(p, ids, cfg)
    routes = []
    for i, kind in enumerate(layer_kinds(cfg, use_window, rotary_on_global)):
        if i == skip_layer:
            continue
        x, chosen = layer(p['layer_%d' % i], x, positions, valid, cfg, kind,
                          **layer_args)
        routes.append(chosen)
    return dict(readout(p, x, cfg), routes=jnp.stack(routes))
