"""The decode ply's attention over a (pass, layer) cache as a Pallas kernel
(the repo's FIRST Pallas kernel): for each sequence it reads only the row
blocks of pass ``t`` that the sequence's counter has reached, from the
layer's buffers AS THEY LIE in HBM. The all-rows products of
``models/attention.py`` read every row of a buffer and mask what lies past
the counter afterwards; at the games' mean fill that is 2.7 of the 5.6 GB a
ply of ``models/ouro.py`` reads (PERF.md, PR 46).

ONE program walks the sequences in turn and, within a sequence, the blocks
``0 .. pos // block`` of pass ``t``'s rows (``t * rows + j * block``) with its
own double-buffered asynchronous copies: while block ``j`` is multiplied,
block ``j + 1`` (or the next sequence's block 0) is on its way, so no copy
waits at a sequence's end. The soft-max is the online one (a running
maximum, sum and accumulator in float32). Rows past the counter exist in the
LAST block only: there the scores go to ``NEG`` AND the V rows to zero, since
a row never written may hold anything. The heads' queries stand side by side
(``attention.heads_side_by_side``: row h holds head h's query at head h's
columns), so a block's scores are ONE matrix product on the rows as they lie.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .trunk import NEG, f32

# rows a block: chosen on the chip (PERF.md, PR 53)
BLOCK = 512


def rows_read(pos):
    """The rows the kernel's walk reads for a sequence whose counter is
    ``pos``: its blocks up to and with the counter's own, whole."""
    return (pos // BLOCK + 1) * BLOCK


def _attend(wide, k, v, state, scale, left=None):
    """One block into the running soft-max. wide (m, W), k, v (block, W);
    of the last block only the first ``left`` rows lie under the counter."""
    m, l, acc = state
    s = scale * jax.lax.dot_general(wide, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
    if left is not None:
        s = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < left, s, NEG)
        v = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < left, v,
            jnp.zeros_like(v))
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    shrink = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return (m_new, shrink * l + p.sum(axis=1, keepdims=True),
            shrink * acc + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=f32))


def _kernel(t_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *,
            heads, block, rows):
    B, _, W = q_ref.shape
    d = W // heads
    m = -(-heads // 8) * 8
    scale = d ** -0.5
    base = t_ref[0] * rows
    own = (jax.lax.broadcasted_iota(jnp.int32, (m, W), 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, (m, W), 0))

    def copies(b, j, slot):
        r = pl.multiple_of(base + j * block, block)
        return (pltpu.make_async_copy(k_hbm.at[b, pl.ds(r, block)],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[b, pl.ds(r, block)],
                                      vbuf.at[slot], sem.at[1, slot]))

    def start(b, j, slot):
        for copy in copies(b, j, slot):
            copy.start()

    def wait(b, j, slot):
        for copy in copies(b, j, slot):
            copy.wait()

    def sequence(b, step):
        pos = pos_ref[b]
        last = pos // block
        wide = jnp.where(own, q_ref[b].astype(f32), 0.0).astype(q_ref.dtype)

        def whole(j, carry):
            step, state = carry
            slot = step % 2
            start(b, j + 1, 1 - slot)
            wait(b, j, slot)
            return step + 1, _attend(wide, kbuf[slot], vbuf[slot], state,
                                     scale)
        step, state = jax.lax.fori_loop(
            0, last, whole,
            (step, (jnp.full((m, 1), NEG, f32), jnp.zeros((m, 1), f32),
                    jnp.zeros((m, W), f32))))
        slot = step % 2

        @pl.when(b + 1 < B)
        def _():
            start(b + 1, 0, 1 - slot)
        wait(b, last, slot)
        _, l, acc = _attend(wide, kbuf[slot], vbuf[slot], state, scale,
                            pos - last * block + 1)
        # head h's values are block h of row h; the rest is dropped
        o_ref[b] = jnp.where(own, acc / l, 0.0).sum(
            axis=0, keepdims=True).astype(o_ref.dtype)
        return step + 1

    start(0, 0, 0)
    jax.lax.fori_loop(0, B, sequence, 0)


def pass_attention(q, ck, cv, pos, t, rows, dtype, block=None):
    """q (B, H, d), one query head a KV head, over pass ``t``'s rows
    ``0 .. pos`` of ck, cv (B, passes * rows, H * d), the whole buffers ->
    (B, H * d) in ``dtype``. ``rows`` must be a multiple of ``block``
    (``BLOCK`` unless a test or a measurement says otherwise). Off the TPU
    the kernel is interpreted (the tests)."""
    B, H, d = q.shape
    block = block or BLOCK
    W = H * d
    assert rows % block == 0 and ck.shape[2] == W, (rows, block, ck.shape)
    # a copy past a buffer's end is a fault of the chip, not a masked row
    pos = jnp.clip(pos, 0, rows - 1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, heads=H, block=block, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, block, W), ck.dtype),
                            pltpu.VMEM((2, block, W), cv.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, 1, W), dtype),
        interpret=jax.default_backend() != 'tpu',
        name='pass_attention',
    )(jnp.reshape(t, (1,)).astype(jnp.int32), pos, q.reshape(B, 1, W), ck, cv)
    return out.reshape(B, W)
