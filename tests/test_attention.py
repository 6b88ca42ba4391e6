"""``models/attention.py`` ``sequence_attention`` on its own: a block of
queries multiplies only the ``block_keys`` keys that end with its own, what comes out is what
every block against EVERY key gives (the function as it stood before, kept
here as the plain reference), value and gradient, and the program it lowers
to stays near that one's size (the set-up seconds of every run, PR 47)."""

import contextlib
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.models import attention

D = 4
f32 = jnp.float32


def all_keys_attention(q, k, v, positions, valid, window, query_block):
    """``sequence_attention`` before PR 48: every block of queries against
    all ``T`` keys, most of them masked away."""
    T, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    q = q.reshape(T, KV, G, d).transpose(1, 2, 0, 3)
    k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
    scale = d ** -0.5
    bq = min(query_block, T)
    assert T % bq == 0, (T, bq)

    @jax.checkpoint
    def block(args):
        qb, pq = args
        seen = (positions[None, :] <= pq[:, None]) & valid[None, :]
        if window is not None:
            seen = seen & (positions[None, :] > pq[:, None] - window)
        s = scale * jnp.einsum('kgqd,ktd->kgqt', qb, k,
                               preferred_element_type=f32)
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, attention.NEG),
                              axis=-1).astype(v.dtype)
        return jnp.einsum('kgqt,ktd->kgqd', prob, v,
                          preferred_element_type=f32).astype(v.dtype)

    qs = q.reshape(KV, G, T // bq, bq, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(block, (qs, positions.reshape(T // bq, bq)))
    return out.transpose(0, 3, 1, 2, 4).reshape(T, H * d)


def _seen(positions, valid, window):
    seen = (positions[None, :] <= positions[:, None]) & valid[None, :]
    if window is not None:
        seen = seen & (positions[None, :] > positions[:, None] - window)
    return np.asarray(seen)


BQ = 8
# blocks a sequence x the window (None: everything before; 3 and 13: shorter
# than any sequence of two blocks; 'reaches': ``window + bq == T``; 100:
# longer than the sequence) x (query heads, KV heads) x (first position,
# padding at the tail)
BLOCKS = (1, 2, 8)
WINDOWS = (None, 3, 13, 'reaches', 100)
HEADS = ((2, 2), (7, 1))
TAILS = ((0, 0), (1000, 5))
CASES = [(n, window, heads, tail)
         for n, window, (heads, tail) in itertools.product(
             BLOCKS, WINDOWS, zip(HEADS, TAILS))]
CASES += [(8, 13, (4, 2), (7, 30)), (8, None, (4, 2), (0, 11)),
          (3, None, (2, 1), (3, 0)), (5, 13, (2, 2), (0, 9))]


def _case_id(case):
    n, window, (H, KV), (first, tail) = case
    return '%dx%d-w%s-h%dkv%d-p%d-tail%d' % (n, BQ, window, H, KV, first,
                                             tail)


@pytest.mark.parametrize('case', CASES, ids=_case_id)
def test_sequence_attention_is_the_all_keys_function(case):
    n, window, (H, KV), (first, tail) = case
    T = n * BQ
    if window == 'reaches':
        window = max(T - BQ, 1)
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q = jax.random.normal(ks[0], (T, H, D), f32)
    k = jax.random.normal(ks[1], (T, KV, D), f32)
    v = jax.random.normal(ks[2], (T, KV, D), f32)
    positions = first + jnp.arange(T)
    valid = jnp.arange(T) < T - tail
    # padding further than a window past the last valid key sees nothing and
    # averages whatever keys it was handed, here as there; nothing reads it,
    # so it carries no cotangent either. Every VALID row sees itself.
    sees = _seen(positions, valid, window).any(axis=1)
    assert sees[:T - tail].all()
    cot = jax.random.normal(ks[3], (T, H * D), f32) * sees[:, None]

    def run(f):
        out = lambda *a: f(*a, positions, valid, window, BQ)
        return jax.jit(out)(q, k, v), jax.jit(jax.grad(
            lambda *a: (out(*a) * cot).sum(), (0, 1, 2)))(q, k, v)
    got, grads = run(attention.sequence_attention)
    want, wants = run(all_keys_attention)
    np.testing.assert_allclose(got[sees], want[sees], rtol=2e-5, atol=2e-5)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)


SHAPES = [(8192, 4096, 128), (8192, None, 128), (4096, 2048, 512),
          (4096, None, 512), (64, 13, 8), (64, 56, 8), (64, 100, 8),
          (24, None, 8), (16, None, 512), (16, 4, 512), (88, None, 8)]


@pytest.mark.parametrize('T,window,bq', SHAPES)
def test_the_plan_leaves_no_visible_key_out_and_the_share_counts_it(
        T, window, bq):
    """By brute force: every (query, key) pair a block hands to a product,
    against the pairs the mask lets through and against ``key_share``."""
    bq_ = min(bq, T)
    n_keys = attention.block_keys(T, window, bq)
    multiplied = np.zeros((T, T), bool)
    for b in range(T // bq_):
        start = max((b + 1) * bq_ - n_keys, 0)
        multiplied[b * bq_:(b + 1) * bq_, start:start + n_keys] = True
    positions = 17 + np.arange(T)
    visible = _seen(positions, np.ones(T, bool), window)
    assert not (visible & ~multiplied).any()
    assert attention.key_share(T, [window], bq) == multiplied.mean()
    assert attention.key_share(T, [window, None], bq) == pytest.approx(
        (multiplied.mean() + attention.key_share(T, [None], bq)) / 2)


def _key_extents(T, window, bq, H=2, KV=1, d=8, batch=None):
    """The key extents of every ``dot_general`` in the jaxpr of
    ``sequence_attention`` at these shapes (no arrays: shapes alone), the
    loop bodies it holds and its gathers."""
    lead = () if batch is None else (batch,)
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(lead + s,
                                                                dtype)
    f = lambda *seq: attention.sequence_attention(*seq, window, bq)
    if batch is not None:
        f = jax.vmap(f)
    jaxpr = jax.make_jaxpr(f)(
        shape(T, H, d), shape(T, KV, d), shape(T, KV, d),
        shape(T, dtype=jnp.int32), shape(T, dtype=jnp.bool_))
    extents, counts = [], {'scan': 0, 'gather': 0}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'dot_general':
                # scores: (.., bq, d) x (.., keys, d); values: (.., bq, keys)
                # x (.., keys, d): the keys are the second operand's axis -2
                extents.append(eqn.invars[1].aval.shape[-2])
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return extents, counts['scan'], counts['gather']


def test_a_block_multiplies_only_its_own_keys_in_one_loop_body():
    # a window layer at ``smallthinker``'s shapes: ONE loop body, no product
    # over all 8,192 keys, under the nets' ``vmap`` over windows or without
    assert _key_extents(8192, 4096, 128) == ([4224, 4224], 1, 0)
    assert _key_extents(8192, 4096, 128, batch=2)[:2] == ([4224, 4224], 1)
    assert attention.block_keys(8192, 4096, 128) == 4224
    # ``trinity_mini``'s sliding layers
    assert _key_extents(4096, 2048, 512)[:2] == ([2560, 2560], 1)
    # a window that reaches the sequence, a layer that sees everything, one
    # block: every key, one body
    assert _key_extents(64, 56, 8) == ([64, 64], 1, 0)
    assert _key_extents(8192, None, 128, batch=2) == ([8192, 8192], 1, 0)
    assert _key_extents(16, 4, 512) == ([16, 16], 1, 0)
    assert attention.block_keys(16, 4, 512) == 16
    assert attention.key_share(4096, [None], 512) == 1.0


@pytest.mark.parametrize('T,window,bq', [
    (4096, None, 512), (64, 56, 8), (16, 4, 512), (16, None, 512)])
def test_blocks_that_take_every_key_run_the_all_keys_program(T, window, bq):
    """No slice, no index, the mask as it was: the text it lowers to is the
    all-keys function's to the character, so a net of such layers alone
    (``models/ouro.py``) builds the programs it built before PR 48."""
    shape = jax.ShapeDtypeStruct
    args = (shape((T, 4, D), jnp.bfloat16), shape((T, 2, D), jnp.bfloat16),
            shape((T, 2, D), jnp.bfloat16), shape((T,), jnp.int32),
            shape((T,), jnp.bool_))
    text = lambda f: jax.jit(jax.grad(lambda *a: f(*a, window, bq).astype(
        f32).sum(), (0, 1, 2))).lower(*args).as_text()
    assert text(attention.sequence_attention) == text(all_keys_attention)


def test_a_blocks_slice_stays_a_slice_under_vmap():
    """The slice's start is the block's index alone, the same for every
    window of a batch: ``vmap`` writes it as a ``gather`` of one index, which
    compiles to the ``dynamic-slice`` it is."""
    f = jax.vmap(lambda *seq: attention.sequence_attention(*seq, 13, 8))
    shape = jax.ShapeDtypeStruct
    text = jax.jit(f).lower(
        shape((2, 64, 2, D), f32), shape((2, 64, 1, D), f32),
        shape((2, 64, 1, D), f32), shape((2, 64), jnp.int32),
        shape((2, 64), jnp.bool_)).compile().as_text()
    assert ' gather(' not in text and 'dynamic-slice(' in text


def _lowered_characters(f, T, window, bq, layers=4, B=2, H=8, d=128):
    """The text ``jax.grad`` of a stack of ``layers`` layers of ``f`` lowers
    to, each under ``vmap`` over windows and ``jax.checkpoint`` as the nets
    run it (shapes alone: nothing is compiled or run)."""
    def loss(q, k, v, positions, valid):
        x = q
        for _ in range(layers):
            x = x + jax.checkpoint(lambda x: jax.vmap(
                lambda *seq: f(*seq, window, bq))(
                    x, k, v, positions, valid).reshape(x.shape))(x)
        return (x.astype(f32) ** 2).sum()
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct((B,) + s,
                                                                dtype)
    return len(jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shape(T, H, d), shape(T, 1, d), shape(T, 1, d),
        shape(T, dtype=jnp.int32), shape(T, dtype=jnp.bool_)).as_text())


# what the final form read when it was written (PR 48) plus a tenth: a full
# layer is the all-keys program itself (1.0 x); a window layer's body (one
# ``lax.map`` of ``window + bq`` keys a block) reads 1.34 x traced where it
# is called, and the four layers of the stack share ONE function: 0.43 x. Two
# groups a full layer read 1.96 x, PR 47's eight 7.8 x, a chunk loop under a
# ``cond`` with a running soft-max 3.5 x, and set-up paid for each
@pytest.mark.timeout(120)
@pytest.mark.parametrize('f,T,window,bq,most', [
    ('sequence_attention', 4096, None, 512, 1.1),
    ('sequence_attention', 8192, 4096, 128, 0.53),
    ('_sequence_attention', 8192, 4096, 128, 1.45)])
def test_the_update_program_stays_near_the_all_keys_programs_size(
        f, T, window, bq, most):
    ours = _lowered_characters(getattr(attention, f), T, window, bq)
    theirs = _lowered_characters(all_keys_attention, T, window, bq)
    assert most <= 1.5 and ours <= most * theirs, (ours, theirs)


def test_window_layers_of_one_shape_share_one_lowered_function():
    """However many layers: the body's ``dynamic_slice``s stand once in the
    forward function and once in each function ``jax.grad`` makes of it."""
    slices = lambda layers: jax.jit(jax.grad(lambda q, k, v, p, ok: sum(
        attention.sequence_attention(q + i, k, v, p, ok, 13, 8).sum()
        for i in range(layers)))).lower(
            *(jax.ShapeDtypeStruct(s, d) for s, d in (
                ((64, 2, D), f32), ((64, 1, D), f32), ((64, 1, D), f32),
                ((64,), jnp.int32), ((64,), jnp.bool_)))
        ).as_text().count('dynamic_slice')
    assert slices(4) == slices(1) > 0


# -- the ONE decode attention picks its product from the shapes (PR 51) --------
def _rows(heads, n_rows=20, dtype=f32):
    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    return (jax.random.normal(keys[0], (3, heads, 8), dtype),
            jax.random.normal(keys[1], (3, n_rows, heads * 8), dtype),
            jax.random.normal(keys[2], (3, n_rows, heads * 8), dtype))


@pytest.mark.parametrize('circle', [False, True], ids=['buffer', 'circle'])
@pytest.mark.parametrize('heads', [2, 4, 9])
def test_a_group_of_one_is_the_grouped_product_row_for_row(heads, circle):
    """``cache_attention`` with one query head a KV head (the heads side by
    side as ONE matrix against the rows as they lie) is the grouped product
    on the same rows, at counters of its own a sequence: at a buffer's
    first and last row, and on a circle not yet full, full, and gone round
    (where the counter is past the rows and every row counts)."""
    q, ck, cv = _rows(heads)
    pos = jnp.asarray([0, 19, 47] if circle else [0, 7, 19])
    want = attention.grouped_cache_attention(q, ck, cv, pos, circle, heads,
                                             f32)
    got = attention.cache_attention(q, ck, cv, pos, circle, heads, f32)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if circle:      # the third sequence sees what a buffer would mask
        masked = attention.cache_attention(q, ck, cv, pos % 20, False,
                                           heads, f32)
        assert float(jnp.abs(got[2] - masked[2]).max()) > 1e-3
        np.testing.assert_allclose(got[:2], masked[:2], atol=1e-5)


def _assert_the_rows_are_multiplied_as_they_lie(text, rows):
    """Each (B, rows, H * d) bfloat16 buffer is the operand of ONE product
    whose only batch axis is the sequence, and no float32 array as large as
    a buffer is made."""
    products = re.findall(
        r'stablehlo\.dot_general [^\n]*batching_dims = (\[[\d, ]*\] x '
        r'\[[\d, ]*\])[^\n]*tensor<%dx%dx%dxbf16>\) ->' % rows, text)
    assert products == ['[0] x [0]'] * 2
    sizes = [int(np.prod([int(n) for n in dims.split('x')]))
             for dims in re.findall(r'tensor<([\dx]+)xf32>', text)]
    assert sizes and max(sizes) < np.prod(rows)


def _lowered_decode(heads, kv_heads, circle):
    shape = jax.ShapeDtypeStruct
    rows = shape((2, 96, kv_heads * 16), jnp.bfloat16)
    return rows, jax.jit(
        lambda q, ck, cv, pos: attention.cache_attention(
            q, ck, cv, pos, circle, kv_heads, jnp.bfloat16)).lower(
        shape((2, heads, 16), jnp.bfloat16), rows, rows,
        shape((2,), jnp.int32)).as_text()


@pytest.mark.parametrize('circle', [False, True], ids=['buffer', 'circle'])
def test_a_group_of_one_multiplies_the_rows_as_they_lie(circle):
    """The guard against a net WITHOUT groups reaching the grouped product
    (62.8 ms a ply against 7.8 on the chip, PERF.md, PR 46), read from the
    shapes and from no flag: in the text ``cache_attention`` lowers to at
    ``H == kv_heads`` each buffer is the operand of ONE product whose only
    batch axis is the sequence, and no float32 array as large as a buffer
    is made; with groups the rows get a KV-head axis, as they always did."""
    rows, text = _lowered_decode(8, 8, circle)
    _assert_the_rows_are_multiplied_as_they_lie(text, rows.shape)
    _rows_of_groups, grouped = _lowered_decode(8, 2, circle)
    assert 'tensor<2x96x2x16xbf16>' in grouped
    assert 'tensor<2x96x2x16xbf16>' not in text


# -- one query row a head: the heads side by side (PR 46, PR 50) ---------------
@pytest.mark.parametrize('heads', [2, 8, 9])
def test_the_side_by_side_halves_over_two_row_sets_are_one_soft_max_over_both(
        heads):
    """The queries laid side by side once, the scores of each set of rows,
    ONE soft-max, the values of each set added before the heads' own blocks
    are taken (``models/evabyte.py``'s window rows and summaries): what
    the grouped product without groups reads over the rows concatenated."""
    keys = jax.random.split(jax.random.PRNGKey(heads), 5)
    q = jax.random.normal(keys[0], (3, heads, 8))
    ka, va, kb, vb = (jax.random.normal(key, (3, n, heads * 8))
                      for key, n in zip(keys[1:], (12, 12, 8, 8)))
    pos = jnp.asarray([0, 11, 17])
    want = attention.grouped_cache_attention(
        q, jnp.concatenate([ka, kb], axis=1), jnp.concatenate([va, vb], 1),
        pos, False, heads, f32)
    wide = attention.heads_side_by_side(q)
    assert wide.shape == (3, -(-heads // 8) * 8, heads * 8)
    seen = jnp.arange(20)[None, :] <= pos[:, None]
    s = jnp.concatenate([attention.side_by_side_scores(wide, ka, 8),
                         attention.side_by_side_scores(wide, kb, 8)], axis=-1)
    prob = jax.nn.softmax(jnp.where(seen[:, None], s, attention.NEG), axis=-1)
    out = (attention.side_by_side_values(prob[..., :12], va)
           + attention.side_by_side_values(prob[..., 12:], vb))
    got = attention.own_blocks(out, heads).reshape(3, -1)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_evabyte_step_multiplies_its_buffers_as_they_lie():
    """The guard against the decode ply's products coming back as one query
    row a head (which the chip's compiler takes apart into float32 multiplies
    and sums over the rows, PERF.md, PRs 46 and 50): in the text
    ``EvaBlock.step`` lowers to, each of a layer's two buffers (K and V: the
    window's rows, then the summaries) is the operand of ONE product whose
    only batch axis is the sequence, and no float32 array as large as a
    buffer is made."""
    from handyrl_tpu.models.evabyte import EvaBlock
    B, W, H, d, chunks = 2, 64, 8, 16, 32
    block = EvaBlock(64, H, d, 96, 4, W, 1e5, 1e-5, 8, jnp.bfloat16)
    shape = jax.ShapeDtypeStruct
    cache = (shape((B, W + chunks, H * d), jnp.bfloat16),) * 2
    args = (shape((B, 64), f32), shape((B,), jnp.int32), cache)
    variables = jax.tree_util.tree_map(
        lambda p: shape(p.shape, jnp.bfloat16), jax.eval_shape(
            lambda *a: block.init(jax.random.PRNGKey(0), *a,
                                  method=EvaBlock.step), *args))
    text = jax.jit(lambda v, *a: block.apply(
        v, *a, method=EvaBlock.step)).lower(variables, *args).as_text()
    _assert_the_rows_are_multiplied_as_they_lie(
        text, (B, W + chunks, H * d))


def test_the_epoch_record_and_the_gauge_carry_what_the_learner_holds():
    """``Trainer._epoch_dynamics`` hands the share the learner read from the
    net's hook at start to every epoch's record and its gauge; a learner
    whose net has no hook holds None and records nothing (the fused loop's
    span and a whole run's records: tests/test_fused_pipeline.py)."""
    import types

    from handyrl_tpu import telemetry, train

    def record(share):
        trainer = types.SimpleNamespace(
            _diag_sum={'diag_grad_norm': 3.0}, attention_key_share=share,
            wrapper=types.SimpleNamespace(module=object()))
        return train.Trainer._epoch_dynamics(trainer, {}, 1, 1)
    want = attention.key_share(64, [16, None], 8)
    assert want == (24 / 64 + 1) / 2
    assert record(want) == {'grad_norm': 3.0, 'attention_key_share': want}
    assert telemetry.gauge('attention_key_share').value == want
    assert record(None) == {'grad_norm': 3.0}


# -- the block kernel of the decode ply: a looped trunk's pass (PR 53) ----------
from handyrl_tpu.models import decode_kernel                     # noqa: E402

PASSES, PASS_ROWS = 3, 64


def _pass_buffers(block, dtype=f32, heads=2, head_dim=16):
    """q, the layer's whole K and V buffers, and one counter a sequence: 0,
    ``block - 1``, ``block``, ``rows - 1`` and two in between, in ONE call."""
    keys = jax.random.split(jax.random.PRNGKey(block), 3)
    pos = jnp.asarray([0, block - 1, block, PASS_ROWS - 1, 5, block + 3])
    B, W = pos.shape[0], heads * head_dim
    return (jax.random.normal(keys[0], (B, heads, head_dim), dtype),
            jax.random.normal(keys[1], (B, PASSES * PASS_ROWS, W), dtype),
            jax.random.normal(keys[2], (B, PASSES * PASS_ROWS, W), dtype),
            pos)


def _pass_kernel(q, ck, cv, pos, t, rows, dtype, block):
    """The kernel over a looped net's one span a pass."""
    return decode_kernel.span_attention(
        q, ck, cv, [attention.pass_span(pos, t, rows)], dtype, block=block)


def _spoiled(c, pos, t, value):
    """``c`` with pass t's rows past each counter, and EVERY row of the other
    passes, set to ``value``: what the kernel never reads or masks."""
    row = jnp.arange(c.shape[1])[None, :, None]
    keep = (row >= t * PASS_ROWS) & (row <= t * PASS_ROWS + pos[:, None, None])
    return jnp.where(keep, c, value)


@pytest.mark.parametrize('t', range(PASSES))
@pytest.mark.parametrize('block', [8, 16, 32, 64])
def test_the_block_kernel_is_the_all_rows_form_and_reads_nothing_past_a_counter(
        block, t):
    """``decode_kernel.span_attention`` over one span (interpreted here) against the
    all-rows products at the same inputs, at every pass offset, for counters
    at a block's first and last row, a buffer's first and last, and a mix of
    them across the sequences of one call; the rows past each counter and
    the other passes' rows hold NaN (K) and 1e30 (V) in what the kernel is
    handed, and zeros in what the all-rows form is (its masked weights are
    exact zeros, which a NaN would still spoil). Tolerance: both sides sum
    the same float32 products of at most 64 rows; the online soft-max
    rescales by ``exp(m_old - m_new)`` a block where the all-rows form
    subtracts one maximum, a few float32 roundings of values of order 1:
    2e-6 stands 8 x over the largest difference seen (2.4e-7)."""
    q, ck, cv, pos = _pass_buffers(block)
    want = attention.cache_attention(
        q, _spoiled(ck, pos, t, 0.0), _spoiled(cv, pos, t, 0.0), pos, False,
        2, f32, t=jnp.int32(t), rows=PASS_ROWS)
    got = _pass_kernel(
        q, _spoiled(ck, pos, t, jnp.nan), _spoiled(cv, pos, t, 1e30), pos,
        jnp.int32(t), PASS_ROWS, f32, block)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_block_kernel_in_bfloat16_stays_within_the_weights_rounding():
    """In the cell's dtype the two forms round the soft-max's weights to
    bfloat16 at different scales (the kernel before its division by the
    sum, the all-rows form after), 2**-9 relative a weight: outputs of order
    0.3 agree to 2**-7 with room; a masked row that leaked would move them
    by their own size."""
    q, ck, cv, pos = _pass_buffers(16, jnp.bfloat16)
    want = attention.cache_attention(q, ck, cv, pos, False, 2, f32,
                                     t=jnp.int32(1), rows=PASS_ROWS)
    got = _pass_kernel(q, ck, cv, pos, jnp.int32(1), PASS_ROWS, f32, 16)
    np.testing.assert_allclose(got, want, atol=2 ** -7)


def test_a_counter_past_the_buffer_is_held_to_its_last_row():
    """No copy may start past a buffer's end (on the chip that is a fault,
    not a masked row): a counter at or past ``rows`` reads what ``rows - 1``
    reads, which is what the all-rows mask gives it too."""
    q, ck, cv, _ = _pass_buffers(16)
    pos = jnp.full((q.shape[0],), PASS_ROWS + 5)
    got = _pass_kernel(q, ck, cv, pos, jnp.int32(2), PASS_ROWS, f32, 16)
    want = attention.cache_attention(q, ck, cv, pos, False, 2, f32,
                                     t=jnp.int32(2), rows=PASS_ROWS)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize('on_tpu,heads,kv_heads,rows,kernel', [
    (True, 2, 2, 64, True),       # whole blocks of whole lanes, on a TPU
    (False, 2, 2, 64, False),     # the CPU: the all-rows products
    (True, 4, 2, 64, True),       # a grouped layer: the same walk
    (False, 4, 2, 64, False),     # a grouped layer on the CPU: its products
    (True, 2, 2, 72, False),      # rows that are no whole blocks
    (True, 4, 2, 72, False),      # the same, grouped
])
def test_the_kernel_is_chosen_from_the_backend_and_the_shapes(
        monkeypatch, on_tpu, heads, kv_heads, rows, kernel):
    """``cache_attention`` over a looped net's whole buffers takes the block
    kernel where the program runs on a TPU and the shapes are the kernel's,
    with groups or without, and ``pass_rows`` with the all-rows products
    elsewhere: read from the lowered text (a kernel interpreted here lowers
    to a loop with no product over a pass's rows). ``spans_rows_read``
    counts the pass's span by the same choice, from the buffers' width."""
    monkeypatch.setattr(attention, '_on_tpu', lambda: on_tpu)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 16)
    shape = jax.ShapeDtypeStruct
    buffers = shape((2, 3 * rows, kv_heads * 64), f32)
    text = jax.jit(lambda q, ck, cv, pos, t: attention.cache_attention(
        q, ck, cv, pos, False, kv_heads, f32, t=t, rows=rows)).lower(
        shape((2, heads, 64), f32), buffers, buffers, shape((2,), jnp.int32),
        shape((), jnp.int32)).as_text()
    sliced = 'tensor<2x%dx%dxf32>' % (rows, kv_heads * 64) in text
    assert sliced != kernel
    span = attention.pass_span(np.asarray([0, 15, 16, rows - 1]), 0, rows)
    read = attention.spans_rows_read([span], kv_heads * 64, f32)
    assert read.tolist() == ([16, 16, 32, 64] if kernel else [rows] * 4)


@pytest.mark.parametrize('circle', [False, True], ids=['buffer', 'circle'])
def test_a_plain_cache_or_a_circle_hands_one_span_and_takes_the_kernel_on_a_tpu(
        monkeypatch, circle):
    """A side-by-side layer with a plain cache or a circle hands the ONE span
    of its buffer: on a TPU, at shapes the walk takes, the kernel reads it
    (the rows past a counter hold NaN and 1e30 here, which the all-rows
    products would not survive); a circle that has gone round has reached
    all its rows. On the CPU it keeps the products."""
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 16)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (3, 2, 64), f32)
    ck, cv = (jax.random.normal(key, (3, 64, 128), f32) for key in keys[1:])
    pos = jnp.asarray([0, 63, 150] if circle else [0, 17, 63])
    want = attention.grouped_cache_attention(q, ck, cv, pos, circle, 2, f32)
    products = attention.cache_attention(q, ck, cv, pos, circle, 2, f32)
    np.testing.assert_allclose(products, want, atol=1e-5)
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    past = ~attention.rows_seen(64, pos, circle)[:, :, None]
    got = attention.cache_attention(q, jnp.where(past, jnp.nan, ck),
                                    jnp.where(past, 1e30, cv), pos, circle, 2,
                                    f32)
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- a group's query heads as the rows of the kernel's matrix (PR 58) -----------
GROUPS = [(8, 1), (7, 1), (4, 2)]        # (query heads, KV heads)
GROUP_ROWS = 64


def _group_counters(cache, block):
    """One call's counters: a block's first and last row, the buffer's, two
    in between; on a circle that has gone round, counters past its rows
    beside ones that are not."""
    before = [0, block - 1, block, GROUP_ROWS - 1, 5, block + 3]
    return jnp.asarray({
        'buffer': before, 'circle_before_it_has_gone_round': before,
        'circle_gone_round': [GROUP_ROWS, GROUP_ROWS + block + 3,
                              3 * GROUP_ROWS - 1, 7 * GROUP_ROWS, 5,
                              GROUP_ROWS - 1]}[cache])


def _group_buffers(heads, kv_heads, pos, dtype=f32, head_dim=64):
    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    B = pos.shape[0]
    return (jax.random.normal(keys[0], (B, heads, head_dim), dtype),
            jax.random.normal(keys[1], (B, GROUP_ROWS, kv_heads * head_dim),
                              dtype),
            jax.random.normal(keys[2], (B, GROUP_ROWS, kv_heads * head_dim),
                              dtype))


@pytest.mark.parametrize('cache', ['buffer',
                                   'circle_before_it_has_gone_round',
                                   'circle_gone_round'])
@pytest.mark.parametrize('heads,kv_heads', GROUPS)
@pytest.mark.parametrize('block', [16, 32])
def test_the_grouped_walk_is_the_grouped_product_and_reads_nothing_past_a_counter(
        monkeypatch, block, heads, kv_heads, cache):
    """``cache_attention`` as it chooses on a TPU (the kernel interpreted
    here) with ``H / KV`` query heads a KV head, the expert cells' 8 and 7 on
    ONE and 2 on each of two, against ``grouped_cache_attention``: the rows
    past each counter hold NaN (K) and 1e30 (V) in what the kernel is
    handed and zeros in what the products are; a circle that has gone round
    has reached every row. Tolerance as the one-span case's."""
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: block)
    circle = cache != 'buffer'
    pos = _group_counters(cache, block)
    q, ck, cv = _group_buffers(heads, kv_heads, pos)
    past = ~attention.rows_seen(GROUP_ROWS, pos, circle)[:, :, None]
    assert bool(past.any())
    want = attention.grouped_cache_attention(
        q, jnp.where(past, 0.0, ck), jnp.where(past, 0.0, cv), pos, circle,
        kv_heads, f32)
    got = attention.cache_attention(
        q, jnp.where(past, jnp.nan, ck), jnp.where(past, 1e30, cv), pos,
        circle, kv_heads, f32)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize('heads,kv_heads', GROUPS)
def test_the_grouped_walk_in_bfloat16_stays_within_the_weights_rounding(
        monkeypatch, heads, kv_heads):
    """The grouped walk in the cells' dtype, to the one-span case's 2**-7."""
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 16)
    pos = _group_counters('circle_gone_round', 16)
    q, ck, cv = _group_buffers(heads, kv_heads, pos, jnp.bfloat16)
    want = attention.grouped_cache_attention(q, ck, cv, pos, True, kv_heads,
                                             f32)
    got = attention.cache_attention(q, ck, cv, pos, True, kv_heads, f32)
    np.testing.assert_allclose(got, want, atol=2 ** -7)


# -- two spans a sequence under one soft-max: a window and its summaries (PR 54)
EVA_W, EVA_CHUNK, EVA_MAX = 64, 4, 256
EVA_ROWS = EVA_W + EVA_MAX // EVA_CHUNK           # 64 window rows, 64 summaries


def _eva_spans(pos):
    from handyrl_tpu.models.evabyte import eva_spans
    return eva_spans(pos, EVA_W, EVA_CHUNK, EVA_ROWS)


def _eva_counters(case, block):
    """The positions of one call: a slot at a block's first and last row and
    the window's last, the first position of the second window (slot 0
    beside a window's 16 summaries), that window's last, the game's last."""
    cases = {'slot_0': 0, 'a_blocks_last_row': block - 1,
             'a_blocks_first_row': block, 'the_windows_last_row': EVA_W - 1,
             'slot_0_beside_summaries': EVA_W,
             'the_second_windows_last_row': 2 * EVA_W - 1,
             'the_games_last_position': EVA_MAX - 1}
    if case == 'a_mix_in_one_call':
        return jnp.asarray(sorted(cases.values()) + [5, EVA_W + block + 3])
    return jnp.asarray([cases[case], cases[case]])


def _eva_buffers(pos, dtype=f32, heads=2, head_dim=64):
    keys = jax.random.split(jax.random.PRNGKey(int(pos.sum())), 3)
    B, W = pos.shape[0], heads * head_dim
    return (jax.random.normal(keys[0], (B, heads, head_dim), dtype),
            jax.random.normal(keys[1], (B, EVA_ROWS, W), dtype),
            jax.random.normal(keys[2], (B, EVA_ROWS, W), dtype))


def _outside_spans(c, pos, value):
    """``c`` with every row that lies in neither span (the window's rows
    past the slot, the summaries from the running one at row ``W + pos //
    chunk`` on) set to ``value``."""
    seen = attention.spans_seen(EVA_ROWS, _eva_spans(pos))
    assert not bool(seen[jnp.arange(pos.shape[0]),
                         EVA_W + pos // EVA_CHUNK].any())
    return jnp.where(seen[:, :, None], c, value)


@pytest.mark.parametrize('case', [
    'slot_0', 'a_blocks_last_row', 'a_blocks_first_row',
    'the_windows_last_row', 'slot_0_beside_summaries',
    'the_second_windows_last_row', 'the_games_last_position',
    'a_mix_in_one_call'])
@pytest.mark.parametrize('block', [8, 16, 32, 64])
def test_the_walk_over_two_spans_is_the_all_rows_form_under_their_mask(
        block, case):
    """``decode_kernel.span_attention`` (interpreted here) over a window's
    rows and the summaries of the windows before, ONE soft-max, against the
    all-rows products under ``spans_seen`` (``attention.span_attention`` on
    the CPU); what lies in neither span, the running summary's row among it,
    holds NaN (K) and 1e30 (V) in what the kernel is handed and zeros in
    what the products are. A sequence in its first window has an EMPTY
    second span, which is not read. Tolerance as the one-span case's."""
    pos = _eva_counters(case, block)
    q, ck, cv = _eva_buffers(pos)
    spans = _eva_spans(pos)
    want = attention.span_attention(
        q, _outside_spans(ck, pos, 0.0), _outside_spans(cv, pos, 0.0), spans,
        f32)
    got = decode_kernel.span_attention(
        q, _outside_spans(ck, pos, jnp.nan), _outside_spans(cv, pos, 1e30),
        spans, f32, block=block)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_walk_over_two_spans_in_bfloat16_stays_within_the_weights_rounding():
    """The two-span walk in the cell's dtype, to the one-span case's 2**-7."""
    pos = _eva_counters('a_mix_in_one_call', 16)
    q, ck, cv = _eva_buffers(pos, jnp.bfloat16)
    want = attention.span_attention(q, ck, cv, _eva_spans(pos), f32)
    got = decode_kernel.span_attention(q, ck, cv, _eva_spans(pos), f32,
                                       block=16)
    np.testing.assert_allclose(got, want, atol=2 ** -7)


@pytest.mark.parametrize('on_tpu,width,window,kernel', [
    (True, 128, 64, True),        # whole blocks of whole lanes, on a TPU
    (False, 128, 64, False),      # the CPU: the all-rows products
    (True, 96, 64, False),        # rows that are no whole lanes
    (True, 128, 72, False),       # a window that is no whole blocks
])
def test_two_spans_take_the_kernel_by_the_backend_and_the_shapes(
        monkeypatch, on_tpu, width, window, kernel):
    """``span_attention`` over two spans takes the block kernel where the
    program runs on a TPU and both spans lie in whole blocks of whole lanes,
    the all-rows products (one product over all the buffer's rows) where
    not; ``spans_rows_read`` counts by the same choice, from the same
    spans: whole blocks of each span, none of an empty one."""
    from handyrl_tpu.models.evabyte import eva_spans
    monkeypatch.setattr(attention, '_on_tpu', lambda: on_tpu)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 16)
    rows = window + 64
    spans = lambda pos: eva_spans(pos, window, 4, rows)
    shape = jax.ShapeDtypeStruct
    buffers = shape((2, rows, width), f32)
    text = jax.jit(lambda q, ck, cv, pos: attention.span_attention(
        q, ck, cv, spans(pos), f32)).lower(
        shape((2, 2, width // 2), f32), buffers, buffers,
        shape((2,), jnp.int32)).as_text()
    assert ('tensor<2x8x%dxf32>' % rows in text) != kernel
    pos = np.asarray([0, 15, 16, window - 1, window, 2 * window + 20])
    read = attention.spans_rows_read(spans(pos), width, f32)
    assert read.tolist() == ([16, 16, 32, 64, 16 + 16, 32 + 32] if kernel
                             else [rows] * 6)


@pytest.fixture(scope='module')
def one_chip():
    """A DESCRIBED v5e chip to compile for (no chip is attached and nothing
    runs); the one fixture of the suite that loads the TPU's compiler, made
    inside a test so that every worker collects the same tests."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # no compiler here, or another process has it
        pytest.skip('no v5e:2x2 topology can be described here: %r' % (e,))
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compiling_for_the_chip(monkeypatch):
    """Inside, a program is compiled for the described chip, not interpreted;
    an entry written for a described chip cannot be read back, so the
    persistent cache stays out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update('jax_enable_compilation_cache', cached)
        compilation_cache.reset_cache()


def test_the_block_kernel_compiles_for_the_chip_at_the_cells_shapes(
        one_chip, monkeypatch):
    """What interpret mode cannot show: the chip's compiler takes the kernel
    at ``ouro.loop_selfplay_4k``'s shapes (32 sequences, 4 heads of 128,
    4 passes of 4,096 rows, bfloat16, the shipped block) as ONE custom call
    with no temporary beside its two double buffers of fast memory: the
    layer's buffers go in as they lie. A compile is not a measurement."""
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)
    buffers = shape((32, 4 * 4096, 512), jnp.bfloat16)
    with _compiling_for_the_chip(monkeypatch):
        compiled = jax.jit(
            lambda q, ck, cv, pos, t: decode_kernel.span_attention(
                q, ck, cv, [attention.pass_span(pos, t, 4096)],
                jnp.bfloat16)).lower(
            shape((32, 4, 128), jnp.bfloat16), buffers, buffers,
            shape((32,), jnp.int32), shape((), jnp.int32)).compile()
    assert compiled.as_text().count('tpu_custom_call') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize('sequences', [32, 8])
def test_the_two_span_walk_compiles_for_the_chip_at_the_cells_shapes(
        one_chip, monkeypatch, sequences):
    """The chip's compiler takes the two-span walk at
    ``evabyte.selfplay_4k``'s shapes (the rollout's 32 sequences and
    evaluation's 8, 8 heads of 128, a window of 2,048 rows and 512
    summaries, bfloat16, the shipped block) as ONE custom call with no
    temporary beside its double buffers of fast memory. A compile is not a
    measurement."""
    from handyrl_tpu.models.evabyte import eva_spans
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)
    buffers = shape((sequences, 2560, 1024), jnp.bfloat16)
    with _compiling_for_the_chip(monkeypatch):
        compiled = jax.jit(
            lambda q, ck, cv, pos: attention.span_attention(
                q, ck, cv, eva_spans(pos, 2048, 16, 2560),
                jnp.bfloat16)).lower(
            shape((sequences, 8, 128), jnp.bfloat16), buffers, buffers,
            shape((sequences,), jnp.int32)).compile()
    assert compiled.as_text().count('tpu_custom_call') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.timeout(300)
@pytest.mark.parametrize('sequences', [32, 8])
@pytest.mark.parametrize('config', ['trinity_mini', 'smallthinker'])
def test_the_expert_nets_ply_compiles_with_its_caches_left_in_hbm(
        one_chip, monkeypatch, config, sequences):
    """The chip's compiler takes the decode ply of both expert cells' nets
    (``benchmark/configs``: the rollout's 32 sequences and evaluation's 8, 8
    or 7 query heads on ONE KV head of 128, circles of 2,048 or 4,096 rows
    and a buffer of 8,192, bfloat16, the shipped block), scanned with the
    cache in the carry as the rollout scans it, with ONE custom call a
    layer, NO value of a cache's shape in fast memory (``S(1)`` in its
    layout) and NO copy of that shape: the buffers are read as they lie and
    written in place (``state_update``'s scatters). Without the kernel, and
    with it but without its operands held to HBM, ``trinity_mini``'s buffers
    were fetched whole into fast memory and copied back every ply (PERF.md,
    PR 58). A compile is not a measurement."""
    import json
    import os
    import re
    from handyrl_tpu.models import build
    path = os.path.join(os.path.dirname(__file__), '..', 'benchmark',
                        'configs', config + '.json')
    with open(path) as f:
        env_args = json.load(f)['env_args']
    net = build(env_args['net_name'], **env_args['net'])
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if x.dtype == f32 else x.dtype,
            sharding=one_chip), tree)
    ids = jnp.zeros((sequences,), jnp.int32)
    hidden = jax.eval_shape(lambda: net.init_hidden((sequences,)))
    params = jax.eval_shape(
        lambda: net.init(jax.random.PRNGKey(0), ids, None))

    def plies(params, ids, hidden):
        def ply(carry, _):
            out = net.apply(params, *carry)
            ids = jnp.argmax(out['policy'], axis=-1).astype(jnp.int32)
            return (ids, net.reset_hidden(out['hidden'], ids == 7)), ()
        return jax.lax.scan(ply, (ids, hidden), None, length=2)[0]

    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(plies, donate_argnums=(2,)).lower(
            placed(params), placed(ids), placed(hidden)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(
        net.layer_types)
    caches = {'bf16[%d,%d,%d]' % k.shape for k in hidden['k']}
    assert len(caches) == 2
    values = re.findall(r'= (\w+\[[\d,]*\])(\{[^ ]*\})? ([\w\-]+)\(', text)
    cache_values = [(layout, op) for shape, layout, op in values
                    if shape in caches]
    assert cache_values, 'the writes, at the least'
    assert not [v for v in cache_values if 'S(1)' in v[0]]
    assert not [v for v in cache_values if v[1].startswith('copy')]


def test_the_kernels_buffers_are_held_to_hbm_where_a_program_is_traced_for_a_tpu(
        monkeypatch):
    """``decode_kernel._in_hbm``: inside a traced program for a TPU the
    kernel's two buffers carry the constraint (twice in the jaxpr, K and V);
    run eagerly there (a net's ``init``, which the constraint refuses) and
    where the kernel is interpreted they go in as they are."""
    shape = jax.ShapeDtypeStruct
    buffers = shape((2, 64, 128), f32)
    traced = lambda: str(jax.make_jaxpr(
        lambda q, ck, cv, pos: decode_kernel.span_attention(
            q, ck, cv, [attention.pass_span(pos, 0, 64)], f32, block=16))(
        shape((2, 8, 128), f32), buffers, buffers, shape((2,), jnp.int32)))
    assert 'memory_space_constraint' not in traced()
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert traced().count('with_memory_space_constraint') == 2
    eager = jnp.zeros((2, 64, 128))
    assert decode_kernel._in_hbm(eager) is eager
