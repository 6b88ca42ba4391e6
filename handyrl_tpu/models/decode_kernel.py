"""The decode ply's attention over a layer's cache as a Pallas kernel (the
repo's FIRST Pallas kernel): for each sequence it reads only the row blocks
that the sequence's counters have reached, from the layer's buffers AS THEY
LIE in HBM. The all-rows products of ``models/attention.py`` read every row
of a buffer and mask what a counter has not reached afterwards; at the
games' mean fill that is 2.7 of the 5.6 GB a ply of ``models/ouro.py`` reads
(PERF.md, PR 46) and 0.7 of the 1.34 GB of ``models/evabyte.py``'s (PR 54).

What a sequence reads is a short, static-length list of SPANS, each the
first ``count`` rows from row ``first`` on: a looped net's pass ``t`` is ONE
span (``first = t * rows``, ``count = pos + 1``), ``evabyte``'s step TWO
(its window's rows up to the slot, then the summaries of the windows
before), all under ONE soft-max. The first span has always reached a row; a
later span that has reached nothing is not read (no copy is started for it).

ONE program walks the sequences in turn and, within a sequence, its spans'
blocks ``first + j * block`` with its own double-buffered asynchronous
copies: while a block is multiplied, the next one (the span's, the next
span's first, or the next sequence's first) is on its way, so no copy waits
at a span's or a sequence's end. The soft-max is the online one (a running
maximum, sum and accumulator in float32). Rows past a count exist in a
span's LAST block only: there the scores go to ``NEG`` AND the V rows to
zero, since a row never written may hold anything. The heads' queries stand
side by side (``attention.heads_side_by_side``: row h holds head h's query at
head h's columns), so a block's scores are ONE matrix product on the rows as
they lie.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .trunk import NEG, f32

# bytes of K (as many of V) a copy: chosen on the chip (PERF.md, PRs 53, 54)
COPY_BYTES = 512 * 1024


def block_rows(width, dtype):
    """The rows a block: ``COPY_BYTES`` of rows of ``width`` in ``dtype``."""
    return COPY_BYTES // (width * jnp.dtype(dtype).itemsize)


def takes(spans, width, dtype):
    """Whether the walk reads ``spans`` of rows of ``width`` in ``dtype``:
    whole lanes, and every span's first row (where it is known before the
    program runs) and extent whole blocks."""
    block = width % 128 == 0 and block_rows(width, dtype)
    return bool(block) and all(
        extent % block == 0
        and (not isinstance(first, int) or first % block == 0)
        for first, _, extent in spans)


def _reached(spans, clip):
    """Each span's count, held to its extent: a copy past a span's end is a
    fault of the chip, not a masked row. The first span holds the soft-max
    up: it has always reached a row."""
    return [clip(count, int(s == 0), extent)
            for s, (_, count, extent) in enumerate(spans)]


def rows_read(spans, width, dtype):
    """The rows the walk reads for sequences whose ``spans`` hold numpy
    counts: each span's blocks up to and with its last reached row's,
    whole; none of a span that has reached nothing."""
    block = block_rows(width, dtype)
    return sum(-(-count // block) * block
               for count in _reached(spans, np.clip))


def _attend(wide, k, v, state, scale, left=None):
    """One block into the running soft-max. wide (m, W), k, v (block, W);
    of a span's last block only the first ``left`` rows are reached."""
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


def _kernel(first_ref, count_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, heads, block, spans):
    B, _, W = q_ref.shape
    d = W // heads
    m = -(-heads // 8) * 8
    scale = d ** -0.5
    own = (jax.lax.broadcasted_iota(jnp.int32, (m, W), 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, (m, W), 0))

    def copies(b, s, j, slot):
        r = pl.multiple_of(first_ref[s] + j * block, block)
        return (pltpu.make_async_copy(k_hbm.at[b, pl.ds(r, block)],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[b, pl.ds(r, block)],
                                      vbuf.at[slot], sem.at[1, slot]))

    def start(b, s, j, slot):
        for copy in copies(b, s, j, slot):
            copy.start()

    def wait(b, s, j, slot):
        for copy in copies(b, s, j, slot):
            copy.wait()

    def start_after(b, s, slot):
        """Where the walk goes after span ``s`` of sequence ``b``: the next
        span of ``b`` that has reached a row, else the next sequence."""
        none_yet = True
        for later in range(s + 1, spans):
            reached = count_ref[later * B + b] > 0
            pl.when(none_yet & reached)(
                functools.partial(start, b, later, 0, slot))
            none_yet = none_yet & ~reached
        pl.when(none_yet & (b + 1 < B))(
            functools.partial(start, b + 1, 0, 0, slot))

    def sequence(b, step):
        wide = jnp.where(own, q_ref[b].astype(f32), 0.0).astype(q_ref.dtype)

        def span(s, count, step, state):
            last = (count - 1) // block

            def whole(j, carry):
                step, state = carry
                slot = step % 2
                start(b, s, j + 1, 1 - slot)
                wait(b, s, j, slot)
                return step + 1, _attend(wide, kbuf[slot], vbuf[slot], state,
                                         scale)
            step, state = jax.lax.fori_loop(0, last, whole, (step, state))
            slot = step % 2
            start_after(b, s, 1 - slot)
            wait(b, s, last, slot)
            return step + 1, _attend(wide, kbuf[slot], vbuf[slot], state,
                                     scale, count - last * block)

        step, state = span(
            0, count_ref[b], step,
            (jnp.full((m, 1), NEG, f32), jnp.zeros((m, 1), f32),
             jnp.zeros((m, W), f32)))
        for s in range(1, spans):
            count = count_ref[s * B + b]
            step, state = jax.lax.cond(
                count > 0, functools.partial(span, s, count),
                lambda step, state: (step, state), step, state)
        _, l, acc = state
        # head h's values are block h of row h; the rest is dropped
        o_ref[b] = jnp.where(own, acc / l, 0.0).sum(
            axis=0, keepdims=True).astype(o_ref.dtype)
        return step

    start(0, 0, 0, 0)
    jax.lax.fori_loop(0, B, sequence, 0)


def span_attention(q, ck, cv, spans, dtype, block=None):
    """q (B, H, d), one query head a KV head, over each sequence's ``spans``
    of ck, cv (B, rows, H * d), the whole buffers -> (B, H * d) in ``dtype``.
    A span is ``(first, count, extent)``: the first ``count`` (B,) rows from
    row ``first`` (a scalar, traced or not) on, of at most ``extent``; first
    and extent must be multiples of ``block`` (``block_rows`` unless a test
    or a measurement says otherwise). Off the TPU the kernel is interpreted
    (the tests)."""
    B, H, d = q.shape
    W = H * d
    block = block or block_rows(W, ck.dtype)
    assert ck.shape[2] == W and all(
        extent % block == 0 for _, _, extent in spans), (spans, block, ck.shape)
    first = jnp.stack([jnp.asarray(first, jnp.int32) for first, _, _ in spans])
    count = jnp.concatenate(
        [count.astype(jnp.int32) for count in _reached(spans, jnp.clip)])
    out = pl.pallas_call(
        functools.partial(_kernel, heads=H, block=block, spans=len(spans)),
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
        name='span_attention',
    )(first, count, q.reshape(B, 1, W), ck, cv)
    return out.reshape(B, W)
