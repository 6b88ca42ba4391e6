"""Plain float32 forward of the Trinity-Mini policy trunk on this chip's
share of each layer.

``jax.numpy`` only: no flax, no cache, no grouping of rows by expert. Every
mask is written over ALL positions of the sequence from their absolute
positions; every held expert is computed on EVERY row and weighted by ``w_e``
or by 0. The only concessions to size are that queries are taken in blocks
and the experts one after the other. Callers run it under
``jax.default_matmul_precision('highest')`` with the program's own parameter
tree, so a difference is a difference in arithmetic and never in weights.

The equations (ISSUE 38; ``afmoe``, arcee-ai/Trinity-Mini ``config.json``;
the lines the config does not settle are the configuration file's
``assumed``). ``N(x, g) = x / rms(x) * g``; per layer, on the residual ``h``:

* ``a = N(h, g_in)``; ``q = W_q a``, ``k = W_k a``, ``v = W_v a``, ``g = W_g
  a``; ``q = N(q, g_q)``, ``k = N(k, g_k)`` over each head's 128. A
  ``sliding`` layer turns ``q`` and ``k`` by rotary phases (theta 1e4,
  rotate-half) and query ``i`` sees key ``j`` iff ``i - window < j <= i``; a
  ``full`` layer has no positions and ``j <= i``. Query head ``n`` reads KV
  head ``n // (heads / kv_heads)``. ``attn = W_o (softmax(q k^T / sqrt(d)) v
  * sigmoid(g))``.
* ``h = h + N(attn, g_post_attn)``; ``m = N(h, g_pre_mlp)``; ``h = h +
  N(f(m), g_post_mlp)``.
* dense: ``f(m) = W_down (silu(W_gate m) * W_up m)``.
* experts: ``s = sigmoid(W_r m)``; ``S`` the 8 largest of ``s + b``; ``w_e =
  route_scale * s_e / (sum_{e in S} s_e + 1e-20)``; ``f(m) = Shared(m) +
  sum_{e in S, e held here} w_e Expert_e(m)``. The router's weights carry no
  gradient (``departures_from_source``).
* ``h0 = Emb[id] * sqrt(hidden)``; last ``N(h, g_out)``, the untied head
  over the ids held and a value row (tanh).

The layer holds some of the published heads and experts (``W_q``, ``W_g``
those heads' columns, ``W_o`` their rows; the experts ``experts_held``): its
attention output and its experts' sum are this chip's PART of the whole, and
the branch norms act on the parts. A weight set that holds all of them
gives the uncut layer by the same code.
"""

import jax
import jax.numpy as jnp

NEG = -1e30


def values(p, cfg):
    """The weights' values from the stored tree: every matrix is stored at
    ``param_scale`` times its value (1 where the key is absent)."""
    scale = cfg.get('param_scale', 1.0)
    return jax.tree_util.tree_map(
        lambda x: x / scale if x.ndim >= 2 else x, p)


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, theta):
    """x (T, H, d) turned by the phases of its absolute positions: the pair
    (i, i + d/2) by the angle p * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_part(p, a, positions, valid, cfg, kind, block=256):
    """This chip's part of the layer's attention output: (T, hidden)."""
    T, d = a.shape[0], cfg['head_dim']
    H, KV = p['wq'].shape[1] // d, p['wk'].shape[1] // d
    q = rms_norm((a @ p['wq']).reshape(T, H, d), p['q_norm'], cfg['norm_eps'])
    k = rms_norm((a @ p['wk']).reshape(T, KV, d), p['k_norm'],
                 cfg['norm_eps'])
    v = (a @ p['wv']).reshape(T, KV, d)
    if kind != 'full':      # the controls' kinds turn too (layer_kinds)
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
        if kind == 'sliding':
            seen = seen & (positions[None, :] > pq[:, None]
                           - cfg['window_size'])
        s = scale * jnp.einsum('qhd,khd->hqk', qb, k)
        prob = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
        return jnp.einsum('hqk,khd->qhd', prob, v).reshape(block, H * d)
    out = jax.lax.map(one_block, (q.reshape(T // block, block, H, d),
                                  positions.reshape(T // block, block)))
    gate = jax.nn.sigmoid(a @ p['wg'])
    return (out.reshape(T, H * d) * gate) @ p['wo']


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, m, cfg):
    """The published router over ALL experts: (ids (T, k), weights (T, k))."""
    s = jax.nn.sigmoid(m @ p['router'])
    _, ids = jax.lax.top_k(s + p['router_bias'], cfg['experts_per_token'])
    picked = jnp.take_along_axis(s, ids, axis=1)
    w = cfg['route_scale'] * picked / (picked.sum(axis=1, keepdims=True)
                                       + 1e-20)
    return ids, jax.lax.stop_gradient(w)


def experts_part(p, m, ids, w, cfg):
    """The held experts' part of the sum: each of them on every row, its
    weight ``w_e`` where the row chose it, else 0."""
    def one(total, args):
        e, w_gate, w_up, w_down = args
        weight = (w * (ids == e)).sum(axis=1)                   # (T,)
        return total + weight[:, None] * swiglu(m, w_gate, w_up,
                                                w_down), None
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.asarray(cfg['experts_held']), p['experts_gate'],
         p['experts_up'], p['experts_down']))
    return total


def embed(p, ids, cfg):
    return (p['embed'][ids].astype(jnp.float32) / cfg.get('param_scale', 1.0)
            * p['embed'].shape[1] ** 0.5)


def layer(p, x, positions, valid, cfg, kind, use_experts=True):
    """One decoder layer on (T, hidden): the residual after it and, on an
    expert layer, the router's choices (T, k). ``kind`` is the attention's
    (``sliding`` | ``full``); a layer with ``router`` is an expert layer.
    ``use_experts=False`` leaves the routed experts' sum out (a control)."""
    p, eps = values(p, cfg), cfg['norm_eps']
    part = attention_part(p, rms_norm(x, p['norm_in'], eps), positions,
                          valid, cfg, kind)
    x = x + rms_norm(part, p['norm_post_attn'], eps)
    m = rms_norm(x, p['norm_pre_mlp'], eps)
    if 'router' not in p:
        ids = None
        f = swiglu(m, p['w_gate'], p['w_up'], p['w_down'])
    else:
        ids, w = route(p, m, cfg)
        f = swiglu(m, p['shared_gate'], p['shared_up'], p['shared_down'])
        if use_experts:
            f = f + experts_part(p, m, ids, w, cfg)
    return x + rms_norm(f, p['norm_post_mlp'], eps), ids


def readout(p, x, cfg):
    """The head's logits (T, ids held) and the value (T,)."""
    p = values(p, cfg)
    h = rms_norm(x, p['norm_out'], cfg['norm_eps'])
    return {'logits': h @ p['head'], 'value': jnp.tanh(h @ p['value'])[:, 0]}


def layer_kinds(cfg, use_window=True, rotary_on_full=False):
    """Each layer's attention kind as the reference runs it; the two
    controls turn a window layer into one that sees everything (its phases
    kept), and put phases on the full layers."""
    kinds = []
    for kind in cfg['layer_types']:
        if kind == 'sliding' and not use_window:
            kinds.append('sliding_unbounded')
        elif kind == 'full' and rotary_on_full:
            kinds.append('full_rotary')
        else:
            kinds.append(kind)
    return kinds


def forward(variables, ids, first_position, valid, cfg, skip_layer=None,
            use_experts=True, use_window=True, rotary_on_full=False):
    """One sequence: ``ids`` (T,) int, the absolute position of its first
    element, ``valid`` (T,) bool. Returns ``logits`` (T, ids held),
    ``value`` (T,) and ``routes``, the router's choices of every expert
    layer (layers, T, k). The further arguments are the negative
    controls': a layer left out, the routed experts' sum left out, the
    window ignored, phases on the full layers."""
    p = variables['params']
    positions = first_position + jnp.arange(ids.shape[0])
    x = embed(p, ids, cfg)
    routes = []
    for i, kind in enumerate(layer_kinds(cfg, use_window, rotary_on_full)):
        if i == skip_layer:
            continue
        x, chosen = layer(p['layer_%d' % i], x, positions, valid, cfg, kind,
                          use_experts)
        if chosen is not None:
            routes.append(chosen)
    return dict(readout(p, x, cfg),
                routes=jnp.stack(routes) if routes else None)
