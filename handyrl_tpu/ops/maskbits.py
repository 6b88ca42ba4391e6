"""The legal set of a wide id space as bits: one uint8 holds eight ids'
flags, a set bit says ILLEGAL (so a padded row is all ones), bit ``j`` of
byte ``i`` is id ``8 i + j``. A float32 mask of 25,024 ids is 100 KB a
position; as bits it is 3,128 bytes. Records, the windower's history and the
ring keep whatever dtype the game's device twin hands over; the loss unpacks
a ``uint8`` mask where it meets one (``as_float``)."""

import jax.numpy as jnp

ILLEGAL = 1e32     # what the float mask subtracts from an illegal id's logit


def pack(illegal):
    """illegal (..., A) bool -> (..., ceil(A / 8)) uint8."""
    pad = -illegal.shape[-1] % 8
    if pad:
        illegal = jnp.pad(illegal, [(0, 0)] * (illegal.ndim - 1) + [(0, pad)],
                          constant_values=True)
    flags = illegal.reshape(illegal.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    return (flags << jnp.arange(8, dtype=jnp.uint8)).sum(
        axis=-1, dtype=jnp.uint8)


def as_float(mask, n_ids):
    """A mask as the loss subtracts it: a float mask as it is, a ``uint8``
    one unpacked to (..., n_ids) float32 of 0 and 1e32."""
    if mask.dtype != jnp.uint8:
        return mask
    flags = (mask[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    flags = flags.reshape(mask.shape[:-1] + (-1,))[..., :n_ids]
    return flags.astype(jnp.float32) * ILLEGAL
