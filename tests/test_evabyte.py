"""EvaByte as a policy trunk (models/evabyte.py), the byte game and what they
forced in the normal path, at small widths on the CPU with seeded weights:
the program's ``sequence`` against the plain reference, ``__call__`` through
its cache against ``sequence``, the four head-shares against the uncut
layer, env/twin parity, the windower on integer observations with a
``first_position`` leaf (against tests/windower_oracle.py), a cache kept by
counters through the rollout scan, and ``fetch_tree`` with a large leaf."""

import functools
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import evabyte as reference          # noqa: E402
from handyrl_tpu.environment import make_env, make_jax_env    # noqa: E402
from handyrl_tpu.models import attention, evabyte              # noqa: E402
from handyrl_tpu.models.evabyte import EvaByteNet             # noqa: E402

f32 = jnp.float32

WIDTHS = dict(hidden_size=64, layers=2, heads_held=2, heads_published=8,
              head_dim=16, mlp_size=96, chunk_size=4, window_size=16,
              max_positions=64, query_block=8)
CFG = dict(layers=2, head_dim=16, chunk_size=4, window_size=16,
           rope_theta=1e5, norm_eps=1e-5)
T = 40     # 2.5 attention windows


@functools.lru_cache(maxsize=None)
def _net_and_variables(dtype='float32'):
    net = EvaByteNet(dtype=jnp.dtype(dtype), **WIDTHS)
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    # seeded weights large enough that every term matters: mu, phi and the
    # norms' offsets are zero or tiny at initialisation
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    variables = jax.tree_util.tree_map(
        lambda x: x * 8 if x.ndim >= 2
        else 0.3 * jax.random.normal(next(keys), x.shape), variables)
    return net, variables


def _ids(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 320, (n, T)),
                       jnp.int32)


def _sequence(net, variables, ids, first, valid):
    return jax.jit(lambda v, i, f, m: net.apply(
        v, i, f, m, method=net.sequence))(variables, ids, first, valid)


def test_the_cut_has_the_parameter_count_the_configuration_states():
    shapes = jax.eval_shape(
        lambda: EvaByteNet().init(jax.random.PRNGKey(0),
                                  jnp.zeros((1,), jnp.int32),
                                  EvaByteNet().init_hidden((1,))))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes)) == 620019712


@pytest.mark.parametrize('first,length', [(0, T), (5, T), (13, 33), (16, T),
                                          (23, 21)])
def test_sequence_matches_the_plain_reference(first, length):
    """Windows that start on and off chunk and window boundaries, whole and
    ending inside their game: every head's logits and the value."""
    net, variables = _net_and_variables()
    ids = _ids(1, seed=first)
    valid = jnp.arange(T)[None, :] < length
    got = _sequence(net, variables, ids, jnp.asarray([first]), valid)
    with jax.default_matmul_precision('highest'):
        want = reference.forward(variables, ids[0], first, valid[0], CFG)
    keep = np.asarray(valid[0])
    logits = np.concatenate([np.asarray(got['policy'])[0][:, None],
                             np.asarray(got['heads'])[0]], axis=1)
    assert np.asarray(want['logits']).std() > 0.5
    np.testing.assert_allclose(logits[keep], np.asarray(want['logits'])[keep],
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(got['value'])[0, keep, 0],
                               np.asarray(want['value'])[keep], atol=2e-4)


@pytest.mark.parametrize('control', ['skip_layer', 'no_remote'])
def test_the_reference_controls_differ_from_the_model(control):
    """What a negative control leaves out shows: a layer, the summaries."""
    _net, variables = _net_and_variables()
    ids = _ids(1)[0]
    valid = jnp.ones((T,), bool)
    with jax.default_matmul_precision('highest'):
        want = reference.forward(variables, ids, 0, valid, CFG)
        args = ({'skip_layer': 1} if control == 'skip_layer'
                else {'use_remote': False})
        other = reference.forward(variables, ids, 0, valid, CFG, **args)
    diff = np.abs(np.asarray(want['logits']) - np.asarray(other['logits']))
    if control == 'no_remote':     # the first window reads no summary
        assert diff[:16].max() < 1e-5
        diff = diff[16:]
    assert diff.max() > 0.05


# -- a block of queries takes its local keys as a span by index (PR 55) --------
def all_keys_attention(q, k, v, mu, phi, positions, valid, window, chunk,
                       query_block):
    """``evabyte.sequence_attention`` before PR 55: every block of queries
    against all ``T`` keys and all the summaries, most local columns masked
    away. The summaries are the model's own (``_summarise``, untouched)."""
    T, H, d = q.shape
    W = window
    q, k, v = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))
    n_chunks = T // chunk + 1
    chunk_ids = positions[0] // chunk + jnp.arange(n_chunks)
    member = ((positions[None, :] // chunk == chunk_ids[:, None])
              & valid[None, :])
    sk, sv = evabyte._summarise(k, v, mu, phi, member)
    chunk_window = chunk_ids * chunk // W
    present = member.any(axis=1)
    scale = d ** -0.5
    bq = min(query_block, T)
    assert T % bq == 0, (T, bq)

    @jax.checkpoint
    def block(args):
        qb, pq = args
        local = ((pq[:, None] // W == positions[None, :] // W)
                 & (positions[None, :] <= pq[:, None]) & valid[None, :])
        remote = ((chunk_window[None, :] < pq[:, None] // W)
                  & present[None, :])
        s_local = scale * jnp.einsum('hqd,hkd->hqk', qb, k,
                                     preferred_element_type=f32)
        s_remote = scale * jnp.einsum('hqd,hcd->hqc', qb, sk,
                                      preferred_element_type=f32)
        scores = jnp.concatenate(
            [jnp.where(local[None], s_local, evabyte.NEG),
             jnp.where(remote[None], s_remote, evabyte.NEG)], axis=-1)
        prob = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return (jnp.einsum('hqk,hkd->hqd', prob[..., :T], v,
                           preferred_element_type=f32)
                + jnp.einsum('hqc,hcd->hqd', prob[..., T:], sv,
                             preferred_element_type=f32)).astype(v.dtype)

    qs = q.reshape(H, T // bq, bq, d).swapaxes(0, 1)
    out = jax.lax.map(block, (qs, positions.reshape(T // bq, bq)))
    return out.transpose(0, 2, 1, 3).reshape(T, H * d)


def _local(positions, valid, window):
    """(T, T): the keys of each query's exact set, by position."""
    return ((positions[:, None] // window == positions[None, :] // window)
            & (positions[None, :] <= positions[:, None]) & valid[None, :])


def _sees(positions, valid, window, chunk):
    """(T,), (T,): the queries that see a key, and those that see a summary."""
    chunks = positions[0] // chunk + np.arange(len(positions) // chunk + 1)
    present = ((positions[None, :] // chunk == chunks[:, None])
               & valid[None, :]).any(axis=1)
    remote = ((chunks * chunk // window)[None, :]
              < positions[:, None] // window) & present[None, :]
    return _local(positions, valid, window).any(axis=1), remote.any(axis=1)


# (T, window, chunk, query block): a span of ``window + bq`` keys shorter
# than the sequence in all but the last two, where every block takes every
# key (a window as long as the sequence; a sequence of one block)
SPAN_SHAPES = [(64, 16, 4, 8), (96, 32, 8, 16), (48, 8, 4, 8), (64, 8, 4, 16),
               (40, 16, 4, 8), (16, 16, 4, 8), (8, 4, 2, 512)]
# first positions that put a window's boundary inside a block of queries, at
# a block's edge, and (where the sequence is no longer than a window)
# nowhere in the sequence; a whole window and one with a padded tail
SPAN_CASES = [((T, W, chunk, bq), first, tail)
              for T, W, chunk, bq in SPAN_SHAPES
              for first in (W - 3, 5 * W + 1,           # inside a block
                            0, 3 * W + bq % W)          # at a block's edge
              for tail in (0, T // 4 + 1)]
SPAN_CASES += [((16, 16, 4, 8), 32, 0), ((16, 16, 4, 8), 16, 7),
               ((16, 32, 4, 8), 37, 0), ((16, 32, 4, 8), 5, 6)]   # nowhere


@pytest.mark.parametrize(
    'shape,first,tail', SPAN_CASES,
    ids=lambda x: 'x'.join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_a_blocks_span_of_keys_is_the_all_keys_block(shape, first, tail):
    """Value and gradient (q, k, v, mu, phi) of ``sequence_attention``, whose
    blocks take ``window + query_block`` local keys by index, against every
    block over all ``T`` keys: a column sliced away held ``NEG``, whose
    ``exp`` is exactly 0, so the two differ by summation order alone."""
    T, window, chunk, bq = shape
    H, d = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(T + first + tail), 6)
    q, k, v = (jax.random.normal(ks[i], (T, H, d), f32) for i in range(3))
    mu, phi = (jax.random.normal(ks[3 + i], (H, d), f32) for i in range(2))
    positions = first + jnp.arange(T)
    valid = jnp.arange(T) < T - tail
    # a query that sees nothing (padding in a window with no valid key and
    # none before it) averages what its block was handed, here as there;
    # nothing reads it, so it carries no cotangent. Every valid one sees
    # itself
    near, far = _sees(np.asarray(positions), np.asarray(valid), window, chunk)
    sees = near | far
    assert near[:T - tail].all()
    cot = jax.random.normal(ks[5], (T, H * d), f32) * sees[:, None]

    def run(f):
        out = lambda *a: f(*a, positions, valid, window, chunk, bq)
        return jax.jit(out)(q, k, v, mu, phi), jax.jit(jax.grad(
            lambda *a: (out(*a) * cot).sum(), (0, 1, 2, 3, 4)))(
                q, k, v, mu, phi)
    got, grads = run(evabyte.sequence_attention)
    want, wants = run(all_keys_attention)
    np.testing.assert_allclose(got[sees], want[sees], rtol=2e-5, atol=2e-5)
    # mu shifts every summary's score alike: it moves only a query that
    # sees keys AND summaries under its one soft-max
    moved = [True] * 3 + [bool((near & far).any()), bool(far.any())]
    for g, w, some in zip(grads, wants, moved):
        assert (float(jnp.abs(w).max()) > 1e-3) == some
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize('first', [0, 1, 511, 1000, 1536, 2047, 6000])
@pytest.mark.parametrize('T,window,bq', [
    (4096, 2048, 256), (4096, 2048, 512), (64, 16, 8), (48, 8, 16),
    (16, 16, 8), (16, 4, 512)])
def test_no_local_key_lies_outside_a_blocks_span_and_the_share_counts_it(
        T, window, bq, first):
    """By brute force: every (query, key) pair the mask ``local`` lets
    through lies in the columns its block is handed, wherever the window's
    boundaries fall, and ``attention_key_share`` is the share handed."""
    bq_ = min(bq, T)
    n_keys = attention.block_keys(T, window, bq)
    handed = np.zeros((T, T), bool)
    for b in range(T // bq_):
        start = max((b + 1) * bq_ - n_keys, 0)
        handed[b * bq_:(b + 1) * bq_, start:start + n_keys] = True
    local = _local(first + np.arange(T), np.ones(T, bool), window)
    assert local.any() and not (local & ~handed).any()
    net = EvaByteNet(**dict(WIDTHS, window_size=window, query_block=bq))
    assert net.attention_key_share(T) == handed.mean() == n_keys / T


def test_the_cells_share_of_the_local_columns():
    """What the learner's gauge ``attention_key_share`` reads in the cell:
    2,304 of a window's 4,096 local columns a block of 256 queries; 1.0
    where the span is the whole sequence."""
    assert EvaByteNet().attention_key_share(4096) == 2304 / 4096 == 0.5625
    assert EvaByteNet().attention_key_share(2048) == 1.0
    assert EvaByteNet(query_block=512).attention_key_share(4096) == 0.625


def test_the_cells_window_multiplies_a_span_in_one_loop_traced_once():
    """From the jaxpr of the net's ``sequence`` at the cell's sizes
    (abstract: nothing is allocated or run): every product inside a block
    loop takes 2,304 local keys or the 257 summaries, none all 4,096 (the
    summaries' own products, outside the loops, contract over a chunk's
    membership of all ``T`` as they did); and the four layers call ONE
    traced function of the loop."""
    net, T = EvaByteNet(), 4096
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
        net.init_hidden((1,))))
    jaxpr = jax.make_jaxpr(
        lambda p, i, f, m: net.apply(p, i, f, m, method=net.sequence))(
            params, shape((2, T), jnp.int32), shape((2,), jnp.int32),
            shape((2, T), jnp.bool_))
    extents, loops, bodies = [], [], []

    def walk(jaxpr, in_loop):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == 'dot_general' and in_loop:
                extents.append(eqn.invars[1].aval.shape[-2])
            if eqn.params.get('name') == '_block_attention':
                loops.append(id(eqn.params['jaxpr']))
            if name == 'scan':
                bodies.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, in_loop or name == 'scan')
    walk(jaxpr.jaxpr, False)
    assert attention.block_keys(T, net.window_size, net.query_block) == 2304
    assert sorted(set(extents)) == [257, 2304], extents
    assert len(extents) == 4 * net.layers and len(bodies) == net.layers
    assert len(loops) == net.layers and len(set(loops)) == 1


@pytest.mark.parametrize('T,window,chunk,bq', [
    (16, 16, 4, 8), (64, 56, 8, 8), (8, 4, 2, 512)])
def test_blocks_that_take_every_key_run_the_all_keys_program(T, window,
                                                             chunk, bq):
    """No slice, no index, the mask as it was: where ``window + bq`` reaches
    the sequence the text the gradient lowers to is the all-keys
    function's to the character."""
    shape = jax.ShapeDtypeStruct
    args = ([shape((T, 2, 8), jnp.bfloat16)] * 3 + [shape((2, 8), f32)] * 2
            + [shape((T,), jnp.int32), shape((T,), jnp.bool_)])
    text = lambda f: jax.jit(jax.grad(lambda *a: f(
        *a, window, chunk, bq).astype(f32).sum(), (0, 1, 2, 3, 4))).lower(
            *args).as_text()
    assert text(evabyte.sequence_attention) == text(all_keys_attention)


def test_step_through_three_windows_matches_sequence():
    """One position at a time through the cache, 2.5 windows of 16 and ten
    chunks of 4, against the same ids as one causal forward, in the cell's
    compute dtype (in float32, with the other trunks: tests/test_models.py
    ``test_a_trunks_sequence_and_its_steps_agree``)."""
    dtype, atol = 'bfloat16', 0.25
    net, variables = _net_and_variables(dtype)
    ids = _ids(3)
    step = jax.jit(net.apply)
    hidden = net.init_hidden((3,))
    policy, value = [], []
    for t in range(T):
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        policy.append(out['policy'])
        value.append(out['value'])
    seq = _sequence(net, variables, ids, jnp.zeros((3,), jnp.int32),
                    jnp.ones((3, T), bool))
    np.testing.assert_allclose(np.stack(policy, 1), seq['policy'], atol=atol)
    np.testing.assert_allclose(np.stack(value, 1), seq['value'], atol=atol)
    assert list(np.asarray(hidden['pos'])) == [T] * 3


def test_a_new_game_resets_counters_and_leaves_the_buffers():
    """A sequence that starts over on a stale cache reads none of it (that
    ``reset_hidden`` touches the counter alone: tests/test_models.py)."""
    net, variables = _net_and_variables()
    ids = _ids(2)
    step = jax.jit(net.apply)
    hidden = net.init_hidden((2,))
    for t in range(24):
        hidden = step(variables, ids[:, t], hidden)['hidden']
    reset = net.reset_hidden(hidden, jnp.asarray([True, False]))
    assert list(np.asarray(reset['pos'])) == [0, 24]
    fresh = net.init_hidden((2,))
    for t in range(20):
        out_stale = step(variables, ids[:, t], reset)
        out_fresh = step(variables, ids[:, t], fresh)
        reset, fresh = out_stale['hidden'], out_fresh['hidden']
        np.testing.assert_array_equal(out_stale['policy'][0],
                                      out_fresh['policy'][0])


# -- the decode step's products against the formula it replaced ---------------
def _plain_step(block, x, pos, cache):
    """``EvaBlock.step`` as it stood before PR 50, in plain einsums over the
    buffers with a head axis: one query row a head against the window's rows
    and the summaries, ONE soft-max over both, the two value sums."""
    from handyrl_tpu.models.evabyte import _summarise
    from handyrl_tpu.models.trunk import NEG, dot, f32
    ck, cv, csk, csv = cache                    # (B, rows, H, d)
    W, chunk = block.window_size, block.chunk_size
    B = x.shape[0]
    rows = jnp.arange(B)
    q, k, v = block._qkv(x, pos)
    slot = pos % W
    ck, cv = ck.at[rows, slot].set(k), cv.at[rows, slot].set(v)
    inside = ((slot // chunk) * chunk)[:, None] + jnp.arange(chunk)[None, :]
    sk, sv = jax.vmap(lambda kc, vc, m: _summarise(
        jnp.swapaxes(kc, 0, 1), jnp.swapaxes(vc, 0, 1), block.mu, block.phi,
        m[None]))(ck[rows[:, None], inside], cv[rows[:, None], inside],
                  inside <= slot[:, None])
    csk = csk.at[rows, pos // chunk].set(sk[:, :, 0])
    csv = csv.at[rows, pos // chunk].set(sv[:, :, 0])
    local = jnp.arange(W)[None, :] <= slot[:, None]
    remote = (jnp.arange(csk.shape[1])[None, :]
              < (pos // W * (W // chunk))[:, None])
    scale = block.head_dim ** -0.5
    s_local = scale * jnp.einsum('bhd,bwhd->bhw', q, ck,
                                 preferred_element_type=f32)
    s_remote = scale * jnp.einsum('bhd,bchd->bhc', q, csk,
                                  preferred_element_type=f32)
    prob = jax.nn.softmax(jnp.concatenate(
        [jnp.where(local[:, None], s_local, NEG),
         jnp.where(remote[:, None], s_remote, NEG)], axis=-1),
        axis=-1).astype(cv.dtype)
    y = (jnp.einsum('bhw,bwhd->bhd', prob[..., :W], cv,
                    preferred_element_type=f32)
         + jnp.einsum('bhc,bchd->bhd', prob[..., W:], csv,
                      preferred_element_type=f32))
    x = x + dot(y.reshape(B, -1), block.wo, block.dtype, out=f32)
    return block.mlp(x), (ck, cv, csk, csv)


# counters a sequence (window 16, chunk 4, 16 summaries); every buffer is
# full of another game's rows, so whatever a mask lets through shows
COUNTERS = {
    'before_the_window_wraps': [0, 3, 15],
    'after_it_has_wrapped': [16, 17, 30],      # slot < pos: stale rows above
    'summaries_of_three_windows': [48, 55, 63],
    'just_reset_beside_one_that_is_not': [0, 37, 0],
}


@pytest.mark.parametrize('heads', [8, 3])
@pytest.mark.parametrize('case', sorted(COUNTERS))
def test_the_step_side_by_side_is_the_plain_step(case, heads):
    """The heads' queries as ONE matrix against a buffer as it lies, the
    window's rows and then the summaries (``models/attention.py``; at 3
    heads the matrix is padded to 8 rows), give what one query row a head
    gave over buffers of their own: the layer's output and every row."""
    import flax.linen as nn
    from handyrl_tpu.models.evabyte import EvaBlock
    block = EvaBlock(64, heads, 16, 96, 4, 16, 1e5, 1e-5, 8, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(heads), 7)
    pos = jnp.asarray(COUNTERS[case])
    x = jax.random.normal(keys[0], (3, 64))
    # a layer's K and V: 16 window rows, then 16 summaries
    flat = tuple(jax.random.normal(key, (3, 32, heads * 16))
                 for key in keys[1:3])
    variables = jax.tree_util.tree_map(
        lambda p: p * 8 if p.ndim >= 2
        else 0.3 * jax.random.normal(keys[5], p.shape),
        block.init(keys[6], x, pos, flat, method=EvaBlock.step))
    got, got_cache = block.apply(variables, x, pos, flat,
                                 method=EvaBlock.step)
    with_heads = lambda c: c.reshape(3, 16, heads, 16)
    want, (ck, cv, csk, csv) = nn.apply(_plain_step, block)(
        variables, x, pos,
        (with_heads(flat[0][:, :16]), with_heads(flat[1][:, :16]),
         with_heads(flat[0][:, 16:]), with_heads(flat[1][:, 16:])))
    assert float(jnp.abs(want - x).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(got_cache, ((ck, csk), (cv, csv))):
        np.testing.assert_array_equal(
            a, jnp.concatenate(b, axis=1).reshape(a.shape))


# -- the step as it is chosen on a TPU: the block kernel over two spans --------
def _play(net, variables, ids, resets=()):
    """Every ply's policy and the last cache of ``ids`` (B, T) played through
    ``__call__``; the sequences of ``resets[t]`` start a new game at ply t.
    A program of its own each call: traced anew under what the caller has
    patched."""
    step = jax.jit(lambda v, i, h: net.apply(v, i, h))
    hidden = net.init_hidden(ids.shape[:1])
    policy = []
    for t in range(ids.shape[1]):
        if t in resets:
            hidden = net.reset_hidden(hidden, jnp.asarray(resets[t]))
        out = step(variables, ids[:, t], hidden)
        hidden = out['hidden']
        policy.append(out['policy'])
    return np.stack(policy, 1), hidden


@pytest.mark.parametrize('dtype,atol', [('float32', 2e-5), ('bfloat16', 0.12)])
def test_the_kernels_step_is_the_products_step_ply_by_ply_across_a_windows_end(
        monkeypatch, dtype, atol):
    """``EvaByteNet`` (a row of 128 lanes: 2 heads of 64) played 40 plies
    through its cache, 2.5 windows of 16, with the step as it is chosen on a
    TPU (the two-span walk, interpreted) against the step of the all-rows
    products: every ply's policy, and the caches row for row (both write the
    same rows; what either reads is the spans'). One sequence starts a new
    game at ply 21 over buffers that still hold the old game's rows and
    summaries, so its second span is EMPTY again beside two that are not.
    In bfloat16 the two round the soft-max's weights at different scales
    (tests/test_attention.py), 2**-7 of outputs that the readout scales by
    ~10."""
    from handyrl_tpu.models import attention, decode_kernel
    net = EvaByteNet(dtype=jnp.dtype(dtype), **dict(WIDTHS, head_dim=64))
    variables = jax.tree_util.tree_map(
        lambda x: x * 8 if x.ndim >= 2 else x,
        net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32), None))
    ids = _ids(3, seed=5)
    resets = {21: [False, True, False]}
    want, want_hidden = _play(net, variables, ids, resets)
    calls = []
    real = decode_kernel.span_attention
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 8)
    monkeypatch.setattr(decode_kernel, 'span_attention',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    got, got_hidden = _play(net, variables, ids, resets)
    assert len(calls) == net.layers        # a call a layer, traced once
    assert list(np.asarray(got_hidden['pos'])) == [T, T - 21, T]
    np.testing.assert_allclose(got, want, atol=atol)
    if dtype == 'float32':
        for a, b in zip(got_hidden['k'] + got_hidden['v'],
                        want_hidden['k'] + want_hidden['v']):
            np.testing.assert_allclose(a, b, atol=atol)


def test_the_kernels_step_traces_under_a_mesh_as_the_sharded_fused_path_runs_it(
        monkeypatch):
    """The sharded fused path runs the rollout inside ``jax.shard_map`` over
    the lanes (``ops/fused_pipeline.py``): each shard's step sees its local
    sequences, and so does the kernel's call (the walk is over the
    sequences it is handed). Four sequences over two devices, the step as
    chosen on a TPU (interpreted), against the unsharded products' step."""
    from functools import partial
    from jax.sharding import Mesh, PartitionSpec as P
    from handyrl_tpu.models import attention, decode_kernel
    net = EvaByteNet(dtype=jnp.float32, **dict(WIDTHS, head_dim=64))
    variables = net.init(jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32),
                         None)
    ids = _ids(4, seed=9)
    hidden = dict(net.init_hidden((4,)), pos=jnp.asarray([0, 7, 16, 39]))
    hidden = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype)
        if x.dtype == jnp.float32 else x, hidden)
    want = jax.jit(lambda v, i, h: net.apply(v, i, h))(
        variables, ids[:, 0], hidden)
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 8)
    calls, real = [], decode_kernel.span_attention
    monkeypatch.setattr(
        decode_kernel, 'span_attention',
        lambda q, *a, **k: calls.append(q.shape[0]) or real(q, *a, **k))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ('data',))
    step = jax.jit(partial(jax.shard_map, check_vma=False)(
        lambda v, i, h: net.apply(v, i, h), mesh=mesh,
        in_specs=(P(), P('data'), P('data')), out_specs=P('data')))
    got = step(variables, ids[:, 0], hidden)
    assert calls == [2] * net.layers       # a shard's two sequences a call
    np.testing.assert_allclose(got['policy'], want['policy'], atol=2e-5)
    for a, b in zip(got['hidden']['k'], want['hidden']['k']):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_net_counts_the_rows_its_plies_read_from_the_steps_own_spans(
        monkeypatch):
    """``EvaByteNet.decode_rows`` at the published sizes, tied to the
    kernel's own count and to the yardstick: at EVERY ply index of the
    longest game a ply reads, where the kernel runs, whole blocks of 256
    rows of the window up to its slot and of the summaries of the windows
    before: at least what the query must see
    (``benchmark.flops_evabyte.rows_seen_at``), never more than the 2,560
    rows a buffer holds, and exactly 2,560 where the products run (the CPU;
    heads too narrow for whole lanes)."""
    from benchmark.flops_evabyte import rows_seen_at
    from handyrl_tpu.models import attention, decode_kernel
    from handyrl_tpu.models.evabyte import eva_spans
    net = EvaByteNet()
    model = {'window_size': net.window_size, 'chunk_size': net.chunk_size}
    plies = np.arange(net.max_positions)
    held = net.window_size + net.max_positions // net.chunk_size
    assert held == 2560
    assert net.decode_rows(plies) == (net.layers * held * plies.size,
                                      net.layers * held * plies.size)
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    assert decode_kernel.block_rows(net.heads_held * net.head_dim,
                                    net.dtype) == 256
    read = attention.spans_rows_read(
        eva_spans(plies, net.window_size, net.chunk_size, held),
        net.heads_held * net.head_dim, net.dtype)
    must = rows_seen_at(model, 'eva', plies)
    assert (read >= must).all() and (read <= held).all()
    assert (read - must < 2 * 256).all()      # a block's rounding a span
    np.testing.assert_array_equal(
        read, -(-(plies % 2048 + 1) // 256) * 256
        + -(-(plies // 2048 * 128) // 256) * 256)
    assert net.decode_rows(plies) == (net.layers * int(read.sum()),
                                      net.layers * held * plies.size)
    # ply by ply as the pipeline asks, any shape
    for p in (0, 255, 256, 2047, 2048, 4095, 4096, 8191):
        assert net.decode_rows(np.full((2, 3), p))[0] \
            == net.layers * 6 * int(read[p])
    narrow = EvaByteNet(head_dim=8)         # 64 lanes: not the kernel's
    assert narrow.decode_rows(plies)[0] == narrow.layers * held * plies.size


def test_the_four_head_shares_sum_to_the_uncut_layer():
    """The cut is tied to the model: an uncut reference layer of 8 heads,
    its weights dealt to four shares of 2 heads; the program's attention
    part of each share, summed, is the uncut layer's attention, and the MLP,
    which every chip computes alike, is counted once."""
    uncut = EvaByteNet(dtype=jnp.float32, **dict(WIDTHS, heads_held=8))
    full = jax.tree_util.tree_map(
        lambda x: x * 8, uncut.init(jax.random.PRNGKey(3),
                                    jnp.zeros((1,), jnp.int32), None))
    layer = full['params']['layer_0']
    share_net = EvaByteNet(dtype=jnp.float32, **WIDTHS)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, T, 64)), jnp.float32)
    positions = 7 + jnp.arange(T)[None, :]
    valid = jnp.ones((1, T), bool)
    total = 0.0
    for share in range(4):
        cols = slice(share * 32, (share + 1) * 32)   # 2 heads of 16
        held = dict(layer, wq=layer['wq'][:, cols], wk=layer['wk'][:, cols],
                    wv=layer['wv'][:, cols], wo=layer['wo'][cols],
                    mu=layer['mu'][share * 2:share * 2 + 2],
                    phi=layer['phi'][share * 2:share * 2 + 2])
        variables = {'params': dict(full['params'], layer_0=held)}
        total = total + share_net.apply(
            variables, 0, x, positions, valid,
            method=share_net.attention_part)
    with jax.default_matmul_precision('highest'):
        normed = reference.rms_norm(x[0], layer['norm_attn'], 1e-5)
        want_attention = reference.attention_part(layer, normed,
                                                  positions[0], valid[0], CFG)
        h = x[0] + want_attention
        want_layer = h + reference.mlp(layer, reference.rms_norm(
            h, layer['norm_mlp'], 1e-5))
    np.testing.assert_allclose(total[0], want_attention, atol=3e-4)
    # the whole layer from the shares: x + summed parts, then the MLP once
    block = share_net.bind(variables).blocks[0]   # any share: the MLP is whole
    np.testing.assert_allclose(block.mlp(x + total)[0], want_layer,
                               atol=3e-4)


# -- the byte game ------------------------------------------------------------
ENV_ARGS = {'env': 'ByteGame', 'min_steps': 5, 'max_steps': 12,
            'net': WIDTHS}


@pytest.mark.parametrize('ids', [320, 25024])
@pytest.mark.parametrize('seed', [0, 1, 2])
def test_the_twin_plays_the_host_envs_games(seed, ids):
    """Same lengths, salts and actions: the same observations, legal ids,
    ends and outcomes, ply for ply, through two games a lane; over the
    byte game's own 320 ids (the defaults, as before) and over 25,024, of
    which 64 are legal on the first ply only."""
    env_args = ENV_ARGS if ids == 320 else dict(ENV_ARGS, ids=ids,
                                                first_ply_ids=64)
    twin = make_jax_env(env_args)
    assert (twin.N_ACTIONS, twin.BOS, twin.MASK_AS_BITS) \
        == (ids, ids - 64, ids > 4096)
    n = 3
    state = twin.init_state(n, seed)
    envs = [make_env(env_args) for _ in range(n)]
    for i, env in enumerate(envs):
        env.reset({'length': int(state.length[i]), 'salt': int(state.salt[i])})
    rng = np.random.default_rng(seed)
    games = 0
    for _ply in range(30):
        obs = np.asarray(twin.observe(state))
        legal = np.asarray(twin.legal_mask(state))
        assert obs.dtype == np.int32 and twin.acting(state).all()
        assert legal.shape[-1] == ids and (0 <= obs).all() and (obs < ids).all()
        actions = np.zeros((n, 2), np.int32)
        for i, env in enumerate(envs):
            for p in env.players():
                assert obs[i, p] == env.observation(p) \
                    and env.observation(p).dtype == np.int32
                assert list(np.flatnonzero(legal[i, p])) \
                    == env.legal_actions(p)
                actions[i, p] = rng.choice(env.legal_actions(p))
            env.step({p: actions[i, p] for p in env.players()})
        state = twin.step(state, jnp.asarray(actions))
        done = np.asarray(twin.terminal(state))
        outcome = np.asarray(twin.outcome(state))
        for i, env in enumerate(envs):
            assert done[i] == env.terminal()
            if done[i]:
                games += 1
                assert env.outcome() == {0: outcome[i, 0], 1: outcome[i, 1]}
                assert 5 <= env.steps <= 12 and outcome[i].sum() == 0
        state = twin.auto_reset(state, jnp.asarray(done))
        for i, env in enumerate(envs):
            if done[i]:
                env.reset({'length': int(state.length[i]),
                           'salt': int(state.salt[i])})
    assert games >= 2 * n


def test_game_lengths_are_log_uniform_between_the_envs_bounds():
    twin = make_jax_env({'env': 'ByteGame'})
    assert (twin.MIN_STEPS, twin.MAX_STEPS, twin.N_ACTIONS) \
        == (2048, 8192, 320)
    length = np.asarray(twin.init_state(4000, 0).length)
    assert length.min() >= 2048 and length.max() <= 8192
    assert abs(np.mean(length) - 4432) < 120
    # log-uniform: as many games in [2048, 4096) as in [4096, 8192]
    assert abs(np.mean(length < 4096) - 0.5) < 0.03


def test_the_first_ply_alone_admits_the_further_ids():
    twin = make_jax_env(ENV_ARGS)
    state = twin.init_state(2, 0)
    assert np.asarray(twin.legal_mask(state)).sum(-1).tolist() == [[320] * 2] * 2
    state = twin.step(state, jnp.asarray([[300, 1], [2, 319]]))
    assert np.asarray(twin.legal_mask(state)).sum(-1).tolist() == [[256] * 2] * 2


# -- the rollout scan keeps the cache by counters --------------------------------
def test_rollout_chunk_across_a_games_end_matches_sequence():
    """The program's own rollout scan over games that end inside the chunk:
    every ply's value against one causal forward over each game's ids, so a
    counter reset in place (and nothing else) is what starts a new game."""
    from handyrl_tpu.device_generation import make_gen_body
    net, variables = _net_and_variables()
    twin = make_jax_env(ENV_ARGS)
    rollout = make_gen_body(twin, net.apply, True, True)
    state = twin.init_state(2, 3)
    hidden = net.init_hidden((2, 2))
    _state, hidden, _rng, rec = jax.jit(
        lambda p, s, h, r: rollout(p, s, h, r, 30))(
        variables, state, hidden, jax.random.PRNGKey(0))
    done = np.asarray(rec['done'])
    assert done.sum() >= 4
    for lane in range(2):
        ends = [0] + list(np.flatnonzero(done[:, lane]) + 1) + [30]
        for a, b in zip(ends, ends[1:]):
            for seat in range(2):
                ids = jnp.zeros((1, T), jnp.int32).at[0, :b - a].set(
                    rec['obs'][a:b, lane, seat])
                seq = _sequence(net, variables, ids,
                                jnp.zeros((1,), jnp.int32),
                                jnp.ones((1, T), bool))
                np.testing.assert_allclose(
                    rec['value'][a:b, lane, seat], seq['value'][0, :b - a],
                    atol=2e-4)
    last_end = (np.flatnonzero(done[:, 0]) + 1).max()
    assert int(hidden['pos'][0, 0]) == 30 - last_end


# -- the learner's third branch ------------------------------------------------
def test_the_update_reads_a_window_as_one_sequence_and_books_the_further_heads():
    """``compute_loss`` through ``sequence``: burn-in carries no gradient
    into the parameters through its own outputs, the auxiliary term is
    there, and the loss equals the plain reference's (tests/benchmark holds
    that at the rehearsal's size through the check itself)."""
    from benchmark import checks_evabyte
    from handyrl_tpu.ops.losses import LossConfig, compute_loss
    net, variables = _net_and_variables()
    config = {'model': dict(WIDTHS, vocab=320), 'env_args': ENV_ARGS}
    batch, _window = checks_evabyte.seeded_batch(
        config, 5, {'forward_steps': 32, 'batch_size': 1})
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    cfg = LossConfig(turn_based_training=False, observation=True,
                     policy_target='VTRACE', value_target='VTRACE',
                     gamma=0.99)
    seq = lambda p, *a: net.apply(p, *a, method=net.sequence)
    loss, aux = compute_loss(net.apply, variables, None, batch, cfg,
                             sequence_fn=seq)
    assert np.isfinite(float(loss)) and float(aux['losses']['aux']) > 0
    valid = int(np.asarray(batch['turn_mask']).sum())
    assert int(aux['data_count']) == valid < 32
    # 8 burn-in positions: same forward, their outputs leave the loss
    burn = cfg._replace(burn_in_steps=8)
    loss_b, aux_b = compute_loss(net.apply, variables, None, batch, burn,
                                 sequence_fn=seq)
    assert int(aux_b['data_count']) == valid - 8
    grads = jax.grad(lambda v: compute_loss(net.apply, v, None, batch, burn,
                                            sequence_fn=seq)[0])(variables)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


# -- the windower: integer observations and first_position -----------------------
@pytest.mark.parametrize('bi', [0, 4])
def test_windows_of_integer_ids_with_first_position_match_the_oracle(bi):
    from handyrl_tpu.ops.device_windows import DeviceWindower, _row_width
    from windower_oracle import OracleWindower
    K, N, P, L, fs, W, cap, A = 8, 4, 2, 20, 6, 2, 24, 5
    rng = np.random.RandomState(bi)
    make = lambda cls: cls(mode='solo', fs=fs, bi=bi, max_steps=L,
                           windows_cap=W, capacity=cap, num_players=P,
                           gamma=1.0, has_reward=False, first_position=True)

    def records(done):
        lead = (K, N, P)
        return {'obs': rng.randint(1, 320, lead).astype(np.int32),
                'prob': rng.uniform(0.2, 1, lead).astype(np.float32),
                'action': rng.randint(0, A, lead).astype(np.int32),
                'amask': np.where(rng.rand(*lead, A) < 0.3, 1e32,
                                  0).astype(np.float32),
                'value': rng.uniform(-1, 1, lead + (1,)).astype(np.float32),
                'acting': np.ones(lead, bool), 'done': done,
                'outcome': rng.uniform(-1, 1, lead).astype(np.float32)}
    done = np.zeros((3 * K, N), bool)
    for ply, lane in ((6, 0), (13, 1), (14, 3), (19, 0), (18, 2)):
        done[ply, lane] = True
    sides = []
    for cls in (OracleWindower, DeviceWindower):
        wd = make(cls)
        first = records(done[:K])
        sides.append([wd, jax.jit(wd.ingest_fn()), wd.init_state(first),
                      wd.init_ring(first), jnp.int32(0), jnp.int32(0),
                      jax.random.PRNGKey(7)])
    rng = np.random.RandomState(bi)
    for c in range(3):
        rec = jax.tree_util.tree_map(jnp.asarray,
                                     records(done[c * K:(c + 1) * K]))
        for side in sides:
            side[2:] = side[1](rec, *side[2:])[:5]
    (oracle, _, _, ring_o, cur_o, size_o, _), \
        (new, _, _, ring_n, cur_n, size_n, _) = sides
    assert (int(cur_n), int(size_n)) == (int(cur_o), int(size_o)) \
        and int(size_n) >= 5
    assert sorted(ring_n) == sorted(ring_o) and 'first_position' in ring_n
    assert ring_n['observation'].dtype == jnp.int32 \
        == ring_n['first_position'].dtype
    for key in ring_o:
        flat = ring_o[key].shape[1]
        assert ring_n[key].shape == (cap, _row_width(flat))
        np.testing.assert_array_equal(np.asarray(ring_n[key])[:, :flat],
                                      np.asarray(ring_o[key]), err_msg=key)
    batch = new.unflatten_rows({k: v[:int(size_n)] for k, v in ring_n.items()})
    T_ = bi + fs
    assert batch['observation'].shape == (int(size_n), T_, 1)
    assert batch['first_position'].shape == (int(size_n), 1, 1, 1)
    first = np.asarray(batch['first_position'])[:, 0, 0, 0]
    assert first.min() >= -bi and (first + T_ > 0).all()
    # a row's id is zero exactly where the row lies outside the game
    inside = np.asarray(batch['episode_mask'])[..., 0, 0] > 0
    assert ((np.asarray(batch['observation'])[..., 0] > 0) == inside).all()


def test_the_four_cells_ring_gets_no_new_leaf():
    from handyrl_tpu.ops.device_windows import DeviceWindower
    wd = DeviceWindower(mode='solo', fs=4, bi=0, max_steps=8, windows_cap=1,
                        capacity=4, num_players=2, gamma=1.0,
                        has_reward=False)
    f32 = np.float32
    rec = {'obs': np.zeros((2, 3, 2, 5), f32), 'prob': np.zeros((2, 3, 2), f32),
           'action': np.zeros((2, 3, 2), np.int32),
           'amask': np.zeros((2, 3, 2, 4), f32),
           'value': np.zeros((2, 3, 2, 1), f32),
           'acting': np.ones((2, 3, 2), bool), 'done': np.zeros((2, 3), bool),
           'outcome': np.zeros((2, 3, 2), f32)}
    assert 'first_position' not in wd.init_ring(rec)


# -- fetch_tree -----------------------------------------------------------------
@pytest.mark.parametrize('threshold', [64, 1 << 30])
def test_fetch_tree_takes_a_large_leaf_on_its_own(monkeypatch, threshold):
    """Over the threshold a leaf never enters the packed buffer (a second
    copy of it on the device); under it, it does. Values, shapes, dtypes
    and structure are the same either way."""
    from handyrl_tpu.utils import fetch
    monkeypatch.setattr(fetch, 'LARGE_LEAF_BYTES', threshold)
    packed = []
    real = fetch._packer
    monkeypatch.setattr(fetch, '_packer', lambda sig: (
        packed.append(sig) or real(sig)))
    tree = {'big': jnp.arange(100, dtype=jnp.float32).reshape(10, 10),
            'small': jnp.arange(4, dtype=jnp.float32),
            'tiny': jnp.ones((2,), jnp.float32), 'host': np.arange(3)}
    out = fetch.fetch_tree(tree)
    jax.tree_util.tree_map(np.testing.assert_array_equal, out,
                           jax.tree_util.tree_map(np.asarray, tree))
    assert all(isinstance(leaf, np.ndarray)
               for leaf in jax.tree_util.tree_leaves(out))
    shapes = [shape for _dtype, group in packed for shape in group]
    assert ((10, 10) in shapes) == (threshold > 400)
    # the way back: a large host leaf is uploaded on its own, a leaf that is
    # on the device already stays there
    again = fetch.put_tree(dict(out, on_device=tree['small']))
    assert again['on_device'] is tree['small']
    np.testing.assert_array_equal(again['big'], tree['big'])


# -- the eval share's hold on the fused loop ---------------------------------------
class _ClockedEvaluator:
    """Every step holds the loop for ``step_s`` of the (faked) clock and
    brings ``results_a_step`` results."""

    def __init__(self, clock, step_s, results_a_step=0):
        self.clock, self.step_s = clock, step_s
        self.results_a_step, self.calls = results_a_step, 0

    def step(self):
        self.calls += 1
        self.clock[0] += self.step_s
        return [None] * self.results_a_step


def _eval_share(monkeypatch, evaluator, clock, budget_s, owed=10,
                shares=1):
    import types
    from handyrl_tpu import train
    monkeypatch.setattr(train.time, 'perf_counter', lambda: clock[0])
    learner = types.SimpleNamespace(
        num_results=0, eval_rate=1.0, num_episodes=owed, model_epoch=0,
        feed_results=lambda results, model_id=None: None)
    calls = []
    for _ in range(shares):
        before = evaluator.calls
        train.Learner._run_eval_share(learner, evaluator, {},
                                      budget_s=budget_s)
        calls.append(evaluator.calls - before)
    return calls


@pytest.mark.parametrize('budget_s,step_s,want', [
    (None, 1.0, 16),      # no budget (the loops that are not fused): sixteen
    (1e9, 1.0, 16),       # a budget never reached: sixteen
    (0.0, 1.0, 1),        # always one step, whatever the budget
    (0.475, 0.633, 1),    # an eval chunk a third of a 1.9 s dispatch: one
    (0.5, 0.2, 3),        # 0.2, 0.4 under it; the third step ends at 0.6
    (0.004, 0.0, 16),     # steps that come back at once never spend it
])
def test_an_eval_share_holds_the_loop_no_longer_than_its_budget(
        monkeypatch, budget_s, step_s, want):
    """After its first step a share ends once its steps have held the loop
    for ``budget_s`` (train.py ``_run_eval_share``; the fused loop passes
    ``EVAL_SHARE_OF_TRAINING`` of its training stretch)."""
    clock = [100.0]
    evaluator = _ClockedEvaluator(clock, step_s)
    assert _eval_share(monkeypatch, evaluator, clock, budget_s) == [want]


def test_what_a_bounded_share_still_owes_is_made_up_by_the_next(monkeypatch):
    """The share's rule is unchanged: steps until ``eval_rate`` x episodes
    results are in. A budget only spreads them over the shares that
    follow, and a share that owes nothing takes no step."""
    clock = [0.0]
    evaluator = _ClockedEvaluator(clock, 1.0, results_a_step=1)
    assert _eval_share(monkeypatch, evaluator, clock, 0.5, owed=3,
                       shares=5) == [1, 1, 1, 0, 0]


def test_no_net_names_an_eval_share():
    """How long evaluation may hold the loop is the learner's to decide,
    for every net alike (REVIEW of PR 34)."""
    from handyrl_tpu import train
    from handyrl_tpu.models import build
    assert 0 < train.EVAL_SHARE_OF_TRAINING < 1
    for net in ('EvaByteNet', 'GeeseNet'):
        assert not hasattr(build(net), 'eval_share_steps')
