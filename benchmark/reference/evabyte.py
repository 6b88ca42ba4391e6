"""Plain float32 forward of the EvaByte policy trunk, and its training loss.

``jax.numpy`` only: no flax, no cache, no chunking tricks. Every mask is
written over ALL positions of the sequence from their absolute positions;
the only concession to size is that queries are taken in blocks, so that a
4,096-position window fits. Callers run it under
``jax.default_matmul_precision('highest')`` with the program's own parameter
tree, so a difference is a difference in arithmetic and never in weights.

The equations (ISSUE 34, A; EVA, "Efficient Attention via Control Variates",
ICLR 2023, in the chunked form of EvaByte's modelling code). Absolute
position p, chunk c(p) = p // chunk, window w(p) = p // window, head width d,
scale s = d^-1/2, rotary phases on q and k:

* block: ``h = x + Attn(N(x))``, ``y = h + W_down(silu(W_gate N(h)) * W_up
  N(h))``, ``N(x) = x / rms(x) * (1 + g)``, no bias;
* attention, per head, for query n: the exact set ``L_n = {m <= n : w(m) =
  w(n)}`` and the remote set ``R_n = {chunks lying wholly in windows before
  w(n)}``. A chunk's summary is ``k~_c = mean_{m in c} k_m + mu_h`` and ``v~_c
  = sum_{m in c} softmax_{m in c}(s <k_m, phi_h>) v_m``; one softmax over the
  exact keys and the summaries together;
* eight linear heads on the final norm (head 0 the policy) and a value row
  (tanh).

The layer holds ``heads_held`` of the published heads: ``W_q``, ``W_k``,
``W_v`` have that many heads' columns and ``W_o`` their rows, so the layer's
attention output is this chip's PART of ``W_o``'s sum. Nothing stands in for
the heads that lie on the other chips; a weight set that holds all of them
gives the uncut layer by the same code.
"""

import jax
import jax.numpy as jnp

NEG = -1e30


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def rotary(x, positions, theta):
    """x (T, H, d) turned by the phases of its absolute positions: the pair
    (i, i + d/2) by the angle p * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def summaries(k, v, mu, phi, positions, valid, chunk):
    """Every chunk the sequence touches: (chunk ids (C,), k~ (C, H, d),
    v~ (C, H, d), present (C,)). A chunk cut by the sequence's start or by
    the game's end is summarised over the members the sequence has."""
    first = positions[0] // chunk
    n_chunks = k.shape[0] // chunk + 1
    ids = first + jnp.arange(n_chunks)
    member = (positions[None, :] // chunk == ids[:, None]) & valid[None, :]
    count = member.sum(axis=1)
    m = member.astype(jnp.float32)
    k_mean = jnp.einsum('ct,thd->chd', m, k) \
        / jnp.maximum(count, 1)[:, None, None] + mu[None]
    scale = k.shape[-1] ** -0.5
    logit = scale * jnp.einsum('thd,hd->th', k, phi)            # (T, H)
    logit = jnp.where(member[:, :, None], logit[None], NEG)     # (C, T, H)
    weight = jax.nn.softmax(logit, axis=1) * m[:, :, None]
    v_sum = jnp.einsum('cth,thd->chd', weight, v)
    return ids, k_mean, v_sum, count > 0


def attention_part(p, x, positions, valid, cfg, block=256, use_remote=True):
    """This chip's part of the layer's attention output: (T, hidden)."""
    T = x.shape[0]
    d = cfg['head_dim']
    H = p['wq'].shape[1] // d
    q = rotary((x @ p['wq']).reshape(T, H, d), positions, cfg['rope_theta'])
    k = rotary((x @ p['wk']).reshape(T, H, d), positions, cfg['rope_theta'])
    v = (x @ p['wv']).reshape(T, H, d)
    ids, sk, sv, present = summaries(k, v, p['mu'], p['phi'], positions,
                                     valid, cfg['chunk_size'])
    window = cfg['window_size']
    chunk_window = ids * cfg['chunk_size'] // window
    scale = d ** -0.5
    block = min(block, T)
    assert T % block == 0, (T, block)

    def one_block(args):
        qb, pq = args
        local = ((pq[:, None] // window == positions[None, :] // window)
                 & (positions[None, :] <= pq[:, None]) & valid[None, :])
        remote = ((chunk_window[None, :] < pq[:, None] // window)
                  & present[None, :] & use_remote)
        s_local = scale * jnp.einsum('qhd,khd->hqk', qb, k)
        s_remote = scale * jnp.einsum('qhd,chd->hqc', qb, sk)
        scores = jnp.concatenate([jnp.where(local[None], s_local, NEG),
                                  jnp.where(remote[None], s_remote, NEG)],
                                 axis=-1)
        prob = jax.nn.softmax(scores, axis=-1)
        y = (jnp.einsum('hqk,khd->qhd', prob[..., :T], v)
             + jnp.einsum('hqc,chd->qhd', prob[..., T:], sv))
        return y.reshape(block, H * d)
    # the queries in blocks, one after the other (a loop, so that the
    # program is one block long whatever T is)
    out = jax.lax.map(one_block, (q.reshape(T // block, block, H, d),
                                  positions.reshape(T // block, block)))
    return out.reshape(T, H * d) @ p['wo']


def mlp(p, x):
    return (jax.nn.silu(x @ p['w_gate']) * (x @ p['w_up'])) @ p['w_down']


def embed(p, ids):
    return p['embed'][ids].astype(jnp.float32)


def layer(p_layer, x, positions, valid, cfg, use_remote=True):
    """One decoder block on (T, hidden)."""
    x = x + attention_part(
        p_layer, rms_norm(x, p_layer['norm_attn'], cfg['norm_eps']),
        positions, valid, cfg, use_remote=use_remote)
    return x + mlp(p_layer, rms_norm(x, p_layer['norm_mlp'], cfg['norm_eps']))


def readout(p, x, cfg):
    """The eight heads' logits (T, heads, vocab) and the value (T,)."""
    h = rms_norm(x, p['norm_out'], cfg['norm_eps'])
    return {'logits': jnp.einsum('td,ndv->tnv', h, p['heads']),
            'value': jnp.tanh(h @ p['value'])[:, 0]}


def forward(variables, ids, first_position, valid, cfg, skip_layer=None,
            use_remote=True, remat=False):
    """One sequence: ``ids`` (T,) int, the absolute position of its first
    element, ``valid`` (T,) bool. Returns ``logits`` (T, heads, vocab) and
    ``value`` (T,). ``skip_layer`` and ``use_remote`` are the negative
    controls' (a layer left out; every summary left out); ``remat`` keeps
    only each layer's input for a backward pass. The three pieces (``embed``,
    ``layer``, ``readout``) are what a caller with 620M parameters jits one
    at a time: one layer's program serves all four."""
    p = variables['params']
    positions = first_position + jnp.arange(ids.shape[0])
    x = embed(p, ids)

    def block(p_layer, x):
        return layer(p_layer, x, positions, valid, cfg, use_remote)
    if remat:
        block = jax.checkpoint(block)
    for i in range(cfg['layers']):
        if i != skip_layer:
            x = block(p['layer_%d' % i], x)
    return readout(p, x, cfg)
