"""The arithmetic every trunk net is written in, as functions of arrays and
owned by none of them: ``models/attention.py``, ``models/experts.py``, the
shell (``models/shell.py``) and each net's own file import from here, and
no net's file from another's.

The residual stream is float32; a product's operands are cast to the compute
``dtype`` and its result is ``dtype`` unless ``out`` says otherwise. ``inv``
is 1 / ``param_scale`` (``models/trinity.py``'s docstring has the reason).
"""

import jax
import jax.numpy as jnp

NEG = -1e30         # a masked score: soft-max gives it weight 0 in float32
f32 = jnp.float32


def dot(x, w, dtype, out=None):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out or dtype)


def heads_of(a, w, heads, dtype, inv):
    """``a`` (..., D) through ``w`` (D, heads * d), the result times ``inv``:
    (..., heads, d)."""
    return (dot(a, w, dtype) * inv).reshape(a.shape[:-1] + (heads, -1))


def rms_norm(x, g, eps, dtype, unit_offset=False):
    """Float32 in, ``dtype`` out. The two published forms: the weight
    multiplies, or (``unit_offset``) one plus the weight does."""
    x = x.astype(f32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    g = g.astype(f32)
    return (y * (1.0 + g if unit_offset else g)).astype(dtype)


def rotary(x, positions, theta):
    """x (..., d) at absolute ``positions`` (broadcast over x's leading
    axes): the pair (i, i + d/2) turned by p * theta^(-2i/d), in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=f32) / half)
    angle = positions.astype(f32)[..., None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def burn_in_as_state(k, v, no_grad_prefix):
    """k, v (B, T, heads, d) of a window whose first ``no_grad_prefix``
    positions are the burn-in: state, not trained, so their keys and values
    (and what is summarised from them) carry no gradient."""
    if not no_grad_prefix:
        return k, v
    keep = (jnp.arange(k.shape[1]) >= no_grad_prefix)[None, :, None, None]
    return (jnp.where(keep, k, jax.lax.stop_gradient(k)),
            jnp.where(keep, v, jax.lax.stop_gradient(v)))
