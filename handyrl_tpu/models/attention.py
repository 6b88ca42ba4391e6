"""Grouped-query attention of one kind a layer, as functions of arrays: a
whole sequence in blocks of queries, and one position through a cache of K
and V rows. A layer either sees everything before a query (``window`` None)
or the ``window`` keys up to and with the query's own. What comes before
(projections, norms, phases) and after (gates, ``W_o``) is the net's, as are
the scopes these run under (``models/trinity.py``, ``models/smallthinker.py``).
A net that runs its layers several times (``models/ouro.py``) keeps K and V
of every (pass, layer): ``init_pass_cache``, ``pass_write``, ``pass_rows``.
``cache_attention`` is the ONE decode attention. What a decode query sees is
a short list of ``Span``s of a layer's buffers under one soft-max: ONE for a
plain cache, a circle or a looped net's pass, two for ``models/evabyte.py``'s
step (its window's rows, then the summaries), which calls ``span_attention``
itself.

Which form runs where: where the program runs on a TPU and the spans lie in
whole blocks of whole lanes, the block kernel of ``models/decode_kernel.py``
walks the row blocks each span's count has reached, from the buffers as they
lie in HBM, with the ``H / KV`` query heads of a group as the rows of its
matrix (one head a KV head is the group of one), so rows past a count are
not read on the chip and no buffer is fetched whole into fast memory.
Elsewhere (the CPU's tests, shapes the walk does not take) the all-rows
products read every row and mask those past a counter afterwards, the forms
the tests hold the kernel to: ``grouped_cache_attention`` under
``rows_seen`` with groups, the heads' queries side by side
(``seen_attention``) under ``spans_seen`` without.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .trunk import NEG, f32


def block_keys(T, window, query_block):
    """How many keys each block of ``query_block`` queries multiplies, from
    the shapes alone: with a window the ``window + query_block`` keys that
    end with the block's own last query (from key 0 while fewer lie before
    it), every key where that reaches ``T`` and where there is no window."""
    bq = min(query_block, T)
    assert T % bq == 0, (T, bq)
    return T if window is None else min(window + bq, T)


def key_share(T, windows, query_block):
    """The (query, key) pairs ``sequence_attention`` multiplies as a share of
    all ``T x T``, the mean over layers whose windows (None: a layer that
    sees everything) are ``windows``. 1.0: nothing is left out."""
    return sum(block_keys(T, window, query_block)
               for window in windows) / (len(windows) * T)


def key_positions(positions, valid):
    """The keys' positions (T,) for a block that takes a span: a key that is
    padding stands one past every query, so ``valid`` needs no slice of its
    own: ONE array a key to slice beside K and V."""
    return jnp.where(valid, positions, jnp.iinfo(positions.dtype).max)


def block_span(k, v, pk, b, bq, n_keys):
    """What block ``b`` of ``bq`` queries takes of k, v (heads, T, d) and of
    the keys' positions ``pk`` (T,): the ``n_keys`` (``block_keys``) that
    end with the block's own last query, from key 0 while fewer lie before
    it. The arithmetic that this module's loop and ``models/evabyte.py``'s
    (two key sets under one soft-max) share."""
    start = jnp.maximum((b + 1) * bq - n_keys, 0)
    return (jax.lax.dynamic_slice_in_dim(k, start, n_keys, 1),
            jax.lax.dynamic_slice_in_dim(v, start, n_keys, 1),
            jax.lax.dynamic_slice_in_dim(pk, start, n_keys, 0))


def _sequence_attention(q, k, v, positions, valid, window, query_block):
    """``sequence_attention``, traced where it is called."""
    T, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    q = q.reshape(T, KV, G, d).transpose(1, 2, 0, 3)           # (KV, G, T, d)
    k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)        # (KV, T, d)
    scale = d ** -0.5
    bq = min(query_block, T)
    n_keys = block_keys(T, window, query_block)
    if n_keys < T:
        pk = key_positions(positions, valid)

    @jax.checkpoint
    def block(args):
        qb, pq, *b = args                      # (KV, G, bq, d), (bq,), [()]
        if b:
            kb, vb, pb = block_span(k, v, pk, b[0], bq, n_keys)
            seen = pb[None, :] <= pq[:, None]
        else:
            kb, vb, pb = k, v, positions
            seen = (pb[None, :] <= pq[:, None]) & valid[None, :]
        if window is not None:
            seen = seen & (pb[None, :] > pq[:, None] - window)
        s = scale * jnp.einsum('kgqd,ktd->kgqt', qb, kb,
                               preferred_element_type=f32)
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, NEG),
                              axis=-1).astype(v.dtype)
        return jnp.einsum('kgqt,ktd->kgqd', prob, vb,
                          preferred_element_type=f32).astype(v.dtype)

    qs = q.reshape(KV, G, T // bq, bq, d).transpose(2, 0, 1, 3, 4)
    # only a block that takes a span of the keys needs its index
    index = (jnp.arange(T // bq),) if n_keys < T else ()
    out = jax.lax.map(block, (qs, positions.reshape(T // bq, bq)) + index)
    return out.transpose(0, 3, 1, 2, 4).reshape(T, H * d)


_shared_attention = jax.jit(_sequence_attention,
                            static_argnames=('window', 'query_block'))


def sequence_attention(q, k, v, positions, valid, window, query_block):
    """One sequence. q (T, H, d), k, v (T, KV, d) -> (T, H * d); query head
    ``n`` reads KV head ``n // (H / KV)``; query ``i`` sees key ``j`` iff
    ``j <= i`` by position, the key is valid and, with a ``window``,
    ``i - window < j``. ``positions`` must rise by one an index (the nets
    pass ``first_position + arange(T)``), so what a query sees is bounded by
    INDEX too, and a block of queries multiplies only the ``block_keys`` keys
    that end with its own last query; inside them the mask is the one above,
    by position. A query that sees no key at all (padding further than a
    window past the last valid key) gets the mean of the values its block
    was handed: nothing reads it.

    A layer whose blocks take a span is one function however many layers of
    its shape a net has: traced, differentiated and lowered ONCE a program
    (a block's slices cost the host more to trace than they save the chip
    otherwise: PERF.md, PR 48). A layer that takes every key is traced
    where it stands, the program it always was."""
    span = block_keys(q.shape[0], window, query_block) < q.shape[0]
    return (_shared_attention if span else _sequence_attention)(
        q, k, v, positions, valid, window=window, query_block=query_block)


def init_cache(batch_shape, rows, width, dtype):
    """K and V of every layer, ``rows[i]`` rows of ``width`` on layer i, and
    ONE counter a sequence."""
    lead = tuple(batch_shape)
    zeros = lambda: tuple(jnp.zeros(lead + (n, width), dtype) for n in rows)
    return {'k': zeros(), 'v': zeros(), 'pos': jnp.zeros(lead, jnp.int32)}


def reset_cache(hidden, done):
    """A finished game resets its sequences' counters, not their buffers:
    what a counter has not reached is masked."""
    pos = hidden['pos']
    done = done.reshape(done.shape + (1,) * (pos.ndim - done.ndim))
    return dict(hidden, pos=jnp.where(done, 0, pos))


def cache_write(ck, cv, k, v, pos):
    """A position's k, v (B, KV, d) into each sequence's row ``pos % rows``
    of ck, cv (B, rows, KV * d): a row is the KV heads side by side (with a
    head axis of its own a lone KV head of 128 would be padded to a tile of
    8). A buffer shorter than the game is written round and round."""
    slot = pos % ck.shape[1]
    seq = jnp.arange(ck.shape[0])
    return (ck.at[seq, slot].set(k.reshape(k.shape[0], -1)),
            cv.at[seq, slot].set(v.reshape(v.shape[0], -1)))


def rows_seen(n_rows, pos, circle):
    """(B, n_rows): the rows each sequence's counter ``pos`` (B,) has
    reached. Nothing is ever cleared: a ``circle`` that has gone round holds
    the ``n_rows`` positions up to this one, before that (and in a buffer as
    long as the game) rows 0..pos count."""
    seen = jnp.arange(n_rows)[None, :] <= pos[:, None]
    return seen | (pos[:, None] >= n_rows) if circle else seen


def _on_tpu():
    """Whether the program being traced runs on a TPU."""
    return jax.default_backend() == 'tpu'


class Span(NamedTuple):
    """The first ``count`` (one a sequence) rows from row ``first`` (a
    scalar) on, of a buffer that holds ``extent`` rows there: what a decode
    query sees of one stretch of a layer's buffers. The mask of the all-rows
    products (``spans_seen``), the block kernel's walk and the count of the
    rows a ply reads (``spans_rows_read``) all derive from a query's spans."""
    first: Any
    count: Any
    extent: int


def pass_span(pos, t, rows):
    """What a query at counter ``pos`` sees of a looped net's pass ``t``."""
    return Span(t * rows, pos + 1, rows)


def spans_seen(n_rows, spans):
    """(B, n_rows): the rows of a buffer that lie in one of ``spans``."""
    row = jnp.arange(n_rows)[None, :]
    seen = False
    for first, count, extent in spans:
        seen = seen | ((row >= first)
                       & (row - first < jnp.minimum(count, extent)[:, None]))
    return seen


def _block_kernel(spans, width, dtype):
    """``models/decode_kernel.py`` where its walk takes ``spans`` of rows of
    ``width`` (the KV heads side by side): on a TPU, in whole blocks of
    whole lanes; None elsewhere.
    Imported here, so that only a program that runs the kernel pays for
    importing Pallas (0.85 s)."""
    if not _on_tpu():
        return None
    from . import decode_kernel
    return decode_kernel if decode_kernel.takes(spans, width, dtype) else None


def span_attention(q, ck, cv, spans, dtype):
    """The side-by-side decode attention: q (B, H, d), one query head a KV
    head, over each sequence's ``spans`` of ck, cv (B, rows, H * d), the
    layer's WHOLE buffers as they lie, under ONE soft-max -> (B, H * d).
    On a TPU that is the block kernel, which takes each span's row blocks up
    to its count's own and reads nothing past them; elsewhere (the CPU's
    tests, shapes the kernel does not take) the all-rows products under
    ``spans_seen``, the form the tests hold the kernel to: over a lone
    span's ``extent`` rows, over the whole buffers for more."""
    _, H, d = q.shape
    kernel = _block_kernel(spans, H * d, ck.dtype)
    if kernel:
        return kernel.span_attention(q, ck, cv, spans, dtype)
    if len(spans) == 1 and spans[0].extent < ck.shape[1]:
        first, count, extent = spans[0]
        ck, cv = (jax.lax.dynamic_slice_in_dim(c, first, extent, axis=1)
                  for c in (ck, cv))
        spans = [Span(0, count, extent)]
    return seen_attention(q, ck, cv, spans_seen(ck.shape[1], spans), dtype)


def seen_attention(q, ck, cv, seen, dtype):
    """The all-rows side-by-side products: q (B, H, d) over EVERY row of ck,
    cv (B, rows, H * d), those not ``seen`` (B, rows) masked afterwards."""
    B, H, d = q.shape
    s = side_by_side_scores(heads_side_by_side(q), ck, d)
    prob = jax.nn.softmax(jnp.where(seen[:, None], s, NEG),
                          axis=-1).astype(cv.dtype)
    out = side_by_side_values(prob, cv)
    return own_blocks(out, H).astype(dtype).reshape(B, H * d)


def spans_rows_read(spans, width, dtype):
    """How many rows the decode attention reads for sequences whose ``spans``
    of rows of ``width`` hold numpy counts (any shape): the kernel's whole
    blocks of each span up to its count's own, every row of every span
    where the products run."""
    kernel = _block_kernel(spans, width, dtype)
    if kernel:
        return kernel.rows_read(spans, width, dtype)
    return sum(np.full_like(span.count, span.extent) for span in spans)


def cache_attention(q, ck, cv, pos, circle, kv_heads, dtype, t=None,
                    rows=None):
    """The decode ply's attention, the one a layer's ``step`` calls: q (B,
    H, d) at each sequence's own position ``pos`` (B,) over the rows written
    so far of ck, cv (B, rows, kv_heads * d) -> (B, H * d); rows not
    ``rows_seen`` are masked (keys are stored already turned, so a row needs
    no position). A net that runs its layers several times hands over a
    layer's WHOLE buffers (B, passes * rows, ...) with the pass ``t`` and
    its ``rows``.

    Every layer hands ONE span of its buffers as they lie: ``pass_span``,
    for a plain cache or a circle the one of a lone pass (a circle that has
    gone round has reached all its rows, before that rows ``0..pos``, as
    ``rows_seen`` says). On a TPU, at shapes its walk takes, the block
    kernel reads it: a group's query heads as the rows of one matrix
    against their KV head's rows, only the row blocks the counter has
    reached. Elsewhere the shapes say which product to take: with one query
    head a KV head the grouped form is the slow one (``heads_side_by_side``),
    so without groups the side-by-side products over the rows, read once as
    they lie, with no relayout; with groups ``grouped_cache_attention``
    over ``pass_rows``, the form the kernel is held to."""
    span = (pass_span(pos, 0, ck.shape[1]) if t is None
            else pass_span(pos, t, rows))
    if q.shape[1] == kv_heads:
        return span_attention(q, ck, cv, [span], dtype)
    kernel = _block_kernel([span], ck.shape[2], ck.dtype)
    if kernel:
        return kernel.span_attention(q, ck, cv, [span], dtype)
    if t is not None:
        ck, cv = pass_rows(ck, t, rows), pass_rows(cv, t, rows)
    return grouped_cache_attention(q, ck, cv, pos, circle, kv_heads, dtype)


def grouped_cache_attention(q, ck, cv, pos, circle, kv_heads, dtype):
    """``cache_attention`` by KV head: the ``H / kv_heads`` query heads of a
    group as one matrix against their KV head's rows."""
    B, n_rows = ck.shape[:2]
    H, d = q.shape[1:]
    seen = rows_seen(n_rows, pos, circle)
    rows = lambda c: c.reshape(B, n_rows, kv_heads, d)
    s = d ** -0.5 * jnp.einsum(
        'bkgd,brkd->bkgr', q.reshape(B, kv_heads, H // kv_heads, -1),
        rows(ck), preferred_element_type=f32)
    prob = jax.nn.softmax(jnp.where(seen[:, None, None], s, NEG),
                          axis=-1).astype(cv.dtype)
    y = jnp.einsum('bkgr,brkd->bkgd', prob, rows(cv),
                   preferred_element_type=f32).astype(dtype)
    return y.reshape(B, -1)


def init_pass_cache(batch_shape, passes, rows, width, dtype):
    """K and V of every (pass, layer) of a net that runs its layers
    ``passes`` times: layer i's buffer holds the passes' ``rows[i]`` rows one
    behind the other (pass t's are rows ``t * rows[i]`` and on), so a layer
    and sequence stay ONE buffer whatever the pass; ONE counter a sequence,
    reset as ``reset_cache`` resets it."""
    return init_cache(batch_shape, [passes * n for n in rows], width, dtype)


def pass_write(ck, cv, k, v, pos, t, rows):
    """``cache_write`` into pass ``t``'s rows of a layer's buffers: the
    counter never reaches ``rows`` (a full-length cache)."""
    return cache_write(ck, cv, k, v, t * rows + pos)


def pass_rows(c, t, rows):
    """Pass ``t``'s rows of a layer's buffer (B, passes * rows, width): what
    ``cache_attention`` reads in that pass where the kernel does not run,
    (B, rows, width)."""
    return jax.lax.dynamic_slice_in_dim(c, t * rows, rows, axis=1)


def heads_side_by_side(q):
    """q (B, H, d), one query head a KV head -> (B, m, H * d), m = H rounded
    up to 8: row h holds ``q[h]`` at head h's columns and zeros at the
    others'. With one query row a head a score is a matrix-vector product,
    which the chip's compiler takes apart into float32 multiplies and sums
    over a float32 copy of the rows (9 passes over HBM for one, PERF.md,
    PR 46); laid so, the H queries are ONE matrix against a sequence's rows
    as they lie (B, rows, H * d), on the matrix unit."""
    B, H, d = q.shape
    m = -(-H // 8) * 8
    return jnp.einsum('mh,bhd->bmhd', jnp.eye(m, H, dtype=q.dtype),
                      q).reshape(B, m, H * d)


def side_by_side_scores(wide, rows, d):
    """Every head's scaled scores over one set of rows: ``wide`` (B, m,
    H * d) against rows (B, n, H * d) -> (B, m, n) float32, row h head h's
    (a row past H scores 0 everywhere)."""
    assert rows.shape[2] == wide.shape[2], (wide.shape, rows.shape)
    return d ** -0.5 * jnp.einsum('bmc,brc->bmr', wide, rows,
                                  preferred_element_type=f32)


def side_by_side_values(prob, rows):
    """prob (B, m, n) in the rows' dtype over rows (B, n, H * d) -> (B, m,
    H * d) float32: head h's weighted values are block h of row h
    (``own_blocks``), the other blocks are other heads' values under head
    h's weights and are dropped. Sets of rows under one soft-max add up
    here, before the blocks are taken."""
    return jnp.einsum('bmr,brc->bmc', prob, rows, preferred_element_type=f32)


def own_blocks(out, H):
    """Block h of row h of ``side_by_side_values``' (B, m, H * d): (B, H, d)."""
    B, m = out.shape[:2]
    heads = jnp.arange(H)
    return out.reshape(B, m, H, -1)[:, heads, heads]
