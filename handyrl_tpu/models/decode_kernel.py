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
zero, since a row never written may hold anything. The queries are the rows
of ONE matrix as wide as a buffer's row, head ``n`` of ``H / KV`` a KV head
at the columns of KV head ``n // (H / KV)`` and zeros at the others', so a
block's scores are ONE matrix product on the rows as they lie: without
groups the heads side by side (``attention.heads_side_by_side``), with the
expert nets' ONE held KV head simply their 8 or 7 query heads (PR 58).

The buffers are HELD to HBM as the call's operands (``_in_hbm``): left to
the compiler, a buffer that fits fast memory (the expert nets' 16.8 MB
circles) may be fetched WHOLE into it for the call that reads it or the
scatter that writes it, and copied back, every ply (PERF.md, PR 58).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .trunk import NEG, f32

# bytes of K (as many of V) a copy: chosen on the chip (PERF.md, PRs 53, 54,
# 58: 512 rows of 512 in ``ouro``, 256 of 1,024 in ``evabyte``, 2,048 of 128
# in the expert nets)
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
            *, kv_heads, block, spans):
    B, G, W = q_ref.shape
    d = W // kv_heads
    m = -(-kv_heads * G // 8) * 8
    scale = d ** -0.5
    row = jax.lax.broadcasted_iota(jnp.int32, (m, W), 0)
    own = jax.lax.broadcasted_iota(jnp.int32, (m, W), 1) // d == row // G
    # the rows of each group's g-th head: one a KV head, all of ``own``
    # where a group is one head
    gth = [own & (row % G == g) for g in range(G)] if G > 1 else [own]

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
        q, wide = q_ref[b].astype(f32), 0.0
        for g, rows in enumerate(gth):
            wide = jnp.where(rows, q[g:g + 1], wide)
        wide = wide.astype(q_ref.dtype)

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
        # a head's values are its KV head's block of its row; the rest is
        # dropped
        for g, rows in enumerate(gth):
            o_ref[b, g:g + 1] = jnp.where(rows, acc / l, 0.0).sum(
                axis=0, keepdims=True).astype(o_ref.dtype)
        return step

    start(0, 0, 0, 0)
    jax.lax.fori_loop(0, B, sequence, 0)


def span_attention(q, ck, cv, spans, dtype, block=None):
    """q (B, H, d), ``H / KV`` query heads a KV head (head ``n`` reads KV head
    ``n // (H / KV)``), over each sequence's ``spans`` of ck, cv (B, rows,
    KV * d), the whole buffers -> (B, H * d) in ``dtype``. A span is
    ``(first, count, extent)``: the first ``count`` (B,) rows from row
    ``first`` (a scalar, traced or not) on, of at most ``extent``; first and
    extent must be multiples of ``block`` (``block_rows`` unless a test or a
    measurement says otherwise). Off the TPU the kernel is interpreted (the
    tests).

    The kernel is handed the g-th head of every group side by side, (B, G,
    KV * d), and hands its values back so; its wide query matrix has a row a
    head, row ``kv * G + g`` head ``kv * G + g``'s query at KV head ``kv``'s
    columns and zeros at the others' (``attention.heads_side_by_side`` is
    the case G = 1; with ONE KV head the heads are simply the matrix's
    rows)."""
    B, H, d = q.shape
    W = ck.shape[2]
    KV = W // d
    G = H // KV
    block = block or block_rows(W, ck.dtype)
    assert KV * G == H and KV * d == W and all(
        extent % block == 0 for _, _, extent in spans), (spans, block, ck.shape)
    first = jnp.stack([jnp.asarray(first, jnp.int32) for first, _, _ in spans])
    count = jnp.concatenate(
        [count.astype(jnp.int32) for count in _reached(spans, jnp.clip)])
    out = pl.pallas_call(
        functools.partial(_kernel, kv_heads=KV, block=block,
                          spans=len(spans)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, block, W), ck.dtype),
                            pltpu.VMEM((2, block, W), cv.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, G, W), dtype),
        interpret=jax.default_backend() != 'tpu',
        name='span_attention',
    )(first, count, _groups_side_by_side(q, KV), _in_hbm(ck), _in_hbm(cv))
    return jnp.swapaxes(out.reshape(B, G, KV, d), 1, 2).reshape(B, H * d)


def _in_hbm(buffer):
    """``buffer`` as a kernel's operand that stays where it lies: without
    the constraint the compiler may fetch a buffer that fits fast memory
    WHOLE into it for the call, and copy it back after (PERF.md, PR 58)."""
    if jax.default_backend() != 'tpu' or not isinstance(buffer,
                                                        jax.core.Tracer):
        # interpreted, or run eagerly (a net's ``init``): the call is a
        # program of its own, with nothing around it to fetch ahead for
        return buffer
    return pltpu.with_memory_space_constraint(buffer, pltpu.HBM)


def _groups_side_by_side(q, kv_heads):
    """q (B, H, d) -> (B, G, KV * d): row g holds every group's g-th head."""
    B, H, d = q.shape
    return jnp.swapaxes(q.reshape(B, kv_heads, H // kv_heads, d), 1,
                        2).reshape(B, H // kv_heads, kv_heads * d)
