"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference handles long sequences purely by windowing (truncated-BPTT
windows + chunked episode storage, SURVEY.md §5.7) and contains no attention
layers. This module makes long-context attention a first-class capability of
the framework for attention-based policy nets: queries stay resident on each
device's sequence shard while key/value shards rotate around the ring via
``ppermute`` (one hop per step, riding ICI), with the numerically-stable
online-softmax accumulation of Liu et al. 2023 (Ring Attention,
arXiv:2310.01889) / Milakov & Gimelshein 2018 (online softmax).

``ring_attention(q, k, v, mesh, axis)`` == exact softmax attention; each
device only ever holds 1/N of the sequence. Tested against full attention on
the 8-device CPU mesh.
"""

from __future__ import annotations

from typing import Optional

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

shard_map = partial(jax.shard_map, check_vma=False)


def _block_attention(q, k, v, m_prev, l_prev, o_prev, scale):
    """One blockwise attention step with online-softmax accumulation.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D);
    m/l: running max / normalizer (B, H, Tq); o: unnormalized output.
    """
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale          # (B,H,Tq,Tk)
    m_block = s.max(axis=-1)                                  # (B,H,Tq)
    m_new = jnp.maximum(m_prev, m_block)
    p = jnp.exp(s - m_new[..., None])                         # (B,H,Tq,Tk)
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + p.sum(axis=-1)
    o_new = (o_prev * correction[..., None]
             + jnp.einsum('bhqk,bkhd->bhqd', p, v))
    return m_new, l_new, o_new


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis: str = 'data',
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Exact multi-head attention with the sequence sharded over ``axis``.

    Args: q, k, v of shape (B, T, H, D) with T divisible by the mesh axis
    size. Returns (B, T, H, D) attention output, sharded like q.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis]

    def local_fn(q_loc, k_loc, v_loc):
        B, Tq, H, D = q_loc.shape
        idx = lax.axis_index(axis)
        m = jnp.full((B, H, Tq), -jnp.inf, q_loc.dtype)
        l = jnp.zeros((B, H, Tq), q_loc.dtype)
        o = jnp.zeros((B, H, Tq, D), q_loc.dtype)

        def body(i, carry):
            m, l, o, k_cur, v_cur = carry
            m, l, o = _block_attention(q_loc, k_cur, v_cur, m, l, o, scale)
            # rotate k/v one hop around the ring
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return m, l, o, k_nxt, v_nxt

        m, l, o, _, _ = lax.fori_loop(0, n, body, (m, l, o, k_loc, v_loc))
        out = o / l[..., None]                                # normalize
        return jnp.einsum('bhqd->bqhd', out)

    spec = P(None, axis, None, None)
    return shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Reference single-device attention for parity checks."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)
