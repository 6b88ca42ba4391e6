"""The dispatch of models/experts.py, sized by what is held here (ISSUE 44):
``dispatched_sum`` through the short buffer and through the ``cond``'s other
side against the every-pair functions called one by one on the same routing
(forward, and the gradients of the rows, the router's weights and the three
matrices), the plan's ``fits`` and the two sums of ``rows_aux`` at held pairs
0, exactly ``C`` and ``C + 1``, the shapes the short side's jaxpr holds, and
both nets with every pair on the experts held (the every-pair side under
``nn.remat``)."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from handyrl_tpu.models import experts                              # noqa: E402

PUBLISHED, N, D, F = 8, 64, 16, 24
INV = 0.25


def _routing(n, k, held, pairs, seed=0):
    """slot (n, k) with exactly ``pairs`` of the n * k entries on the
    ``held`` experts here (the rest on an absent one), and weights."""
    rng = np.random.default_rng(seed)
    flat = np.full((n * k,), held, np.int32)
    flat[rng.permutation(n * k)[:pairs]] = rng.integers(0, held, pairs)
    w = rng.dirichlet(np.ones(k), n).astype(np.float32)
    return jnp.asarray(flat.reshape(n, k)), jnp.asarray(w)


def _operands(held, dtype, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    m = jax.random.normal(keys[0], (N, D)).astype(dtype)
    gate, up = (jax.random.normal(key, (held, D, F)) for key in keys[1:3])
    return m, (gate, up, jax.random.normal(keys[3], (held, F, D)))


def _products(dtype):
    return lambda rows, groups, *matrices: experts.grouped_products(
        rows, groups, *matrices, jax.nn.relu, dtype, INV)


def _dispatched(m, w, matrices, slot, held, dtype):
    plan = experts.sort_plan(slot, held, PUBLISHED)
    out = experts.dispatched_sum(m, plan, slot, w, PUBLISHED,
                                 _products(dtype), matrices, 'dispatch')
    return out, plan


def _every_pair(m, w, matrices, slot, held, dtype):
    """The every-pair functions one by one, as the callers had them."""
    plan = experts.sort_plan(slot, held, PUBLISHED)
    y = _products(dtype)(experts.to_expert_order(m, plan), plan.groups,
                         *matrices)
    return experts.weighted_sum_back(y, plan, slot, w, held), plan


def _value_and_grads(path, m, w, matrices, slot, held, dtype):
    cotangent = jax.random.normal(jax.random.PRNGKey(9), (N, D))

    def scalar(m, w, matrices):
        out, plan = path(m, w, matrices, slot, held, dtype)
        return (out * cotangent).sum(), (out, plan)
    (_, (out, plan)), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1, 2), has_aux=True))(m, w, matrices)
    return out, plan, grads


def _close(got, want, dtype):
    """Relative to the largest element: float32 sums differ by the order
    of a row's up-to-k additions alone; under bfloat16 a float32 result
    gets ``test_one_layer_matches_the_plain_reference``'s 3e-4 and a
    bfloat16 one (the gradient of the rows: a row's up-to-k terms are
    added in bfloat16 one by one, each sum rounded) two ulps."""
    assert got.dtype == want.dtype and got.shape == want.shape
    limit = (1e-6 if dtype == jnp.float32
             else 2.0 ** -6 if got.dtype == jnp.bfloat16 else 3e-4)
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= limit * scale


def _cases():
    """(k, held, pairs held here, whether the short side is taken)."""
    for k in (1, 6):
        M = N * k
        for held in (2, 4):
            C = experts.short_length(M, held, PUBLISHED)
            for pairs in (0, C, C + 1):
                if pairs <= M:
                    yield k, held, pairs, pairs <= C


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('k,held,pairs,short', list(_cases()))
def test_the_dispatch_is_the_every_pair_path_on_the_same_routing(
        k, held, pairs, short, dtype):
    """2 of 8 held: a buffer of half the pairs and a ``cond``; 4 of 8:
    ``C == M`` and one path (``pairs`` 0 and ``M`` there; ``M + 1`` cannot
    be)."""
    dtype = jnp.dtype(dtype)
    M = N * k
    C = experts.short_length(M, held, PUBLISHED)
    assert C == (M // 2 if held == 2 else M)
    slot, w = _routing(N, k, held, pairs)
    m, matrices = _operands(held, dtype)
    got, plan, got_grads = _value_and_grads(_dispatched, m, w, matrices,
                                            slot, held, dtype)
    want, _, want_grads = _value_and_grads(_every_pair, m, w, matrices,
                                           slot, held, dtype)
    assert int(plan.groups.sum()) == pairs and int(plan.dropped) == 0
    assert bool(plan.fits) == short
    assert got.dtype == jnp.float32
    _close(got, want, dtype)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        _close(a, b, dtype)
    if pairs:
        assert float(jnp.abs(want).max()) > 0
        assert float(jnp.abs(want_grads[0].astype(jnp.float32)).max()) > 0
    # the two sums a layer and step, and the epoch record's share of them
    counts = jnp.zeros((1, PUBLISHED), jnp.int32)
    aux = experts.rows_aux(counts, tuple(range(held)), plan.tally)
    assert float(aux['moe_dispatches']) == 1
    assert float(aux['moe_dispatches_short']) == (1 if short else 0)
    assert float(aux['moe_rows_dropped']) == 0


def test_the_share_of_short_dispatches_in_the_epoch_record():
    sums = {'diag_moe_rows_held': 10.0, 'diag_moe_rows_routed': 80.0,
            'diag_moe_rows_fullest': 4.0, 'diag_moe_rows_dropped': 0.0,
            'diag_moe_dispatches': 8.0, 'diag_moe_dispatches_short': 7.0}
    dynamics = experts.rows_dynamics(sums, 4)
    assert dynamics['moe_short_buffer_share'] == 87.5
    assert dynamics['moe_rows_dropped'] == 0
    assert experts.rows_dynamics({}, 4) == {}


@pytest.mark.parametrize('pairs,held,published,want', [
    (65536, 16, 128, 16384),      # trinity_mini's cell
    (49152, 16, 64, 24576),       # smallthinker's
    (120, 4, 16, 64),             # the tiny nets of the tests: whole tiles
    (120, 8, 16, 120), (120, 16, 16, 120), (8, 1, 64, 8)])
def test_the_short_buffer_is_twice_the_even_share(pairs, held, published,
                                                  want):
    assert experts.short_length(pairs, held, published) == want


def _wide_shapes(jaxpr_text, rows):
    """The array types of ``rows`` rows and a width over 1 in a jaxpr."""
    return set(re.findall(r'\b[a-z]+\d*\[%d,(?!1\])\d+(?:,\d+)*\]' % rows,
                          jaxpr_text))


@pytest.mark.parametrize('k', [1, 6])
def test_the_short_side_holds_no_array_of_M_rows_wider_than_one(k):
    """Forward and transposed: in the jaxpr of the short side and of its
    gradient ``M`` is the length of index vectors and of the (M, 1) mask
    alone; the every-pair side of the same call has the (M, D) buffers."""
    held, dtype = 2, jnp.dtype('bfloat16')
    M = N * k
    C = experts.short_length(M, held, PUBLISHED)
    slot, w = _routing(N, k, held, C)
    m, matrices = _operands(held, dtype)

    def scalar(m, w, matrices):
        return _dispatched(m, w, matrices, slot, held, dtype)[0].sum()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(scalar, argnums=(0, 1, 2)))(
        m, w, matrices)
    conds = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == 'cond']
    assert len(conds) == 2                  # the forward's and the backward's
    for eqn in conds:
        every_pair, short = (str(b) for b in eqn.params['branches'])
        assert _wide_shapes(short, C)
        assert _wide_shapes(every_pair, M)
        if M != N:      # at k = 1 the rows themselves are M
            assert not _wide_shapes(short, M), _wide_shapes(short, M)
        assert 'repeat' not in short
    # outside the two ``cond``s nothing of the dispatch is left: the plan's
    # one-hot (M, held + 1) of slots is all that has M rows and a width
    outside = '\n'.join(str(eqn) for eqn in jaxpr.jaxpr.eqns
                        if eqn.primitive.name != 'cond')
    if M != N:
        assert _wide_shapes(outside, M) <= {'bool[%d,3]' % M,
                                            'i32[%d,3]' % M}


def test_the_backward_pass_keeps_the_callers_scopes_as_they_are():
    """A scope reader looks for the scope's name among the steps of an
    operation's path. jax writes its transforms around the first scope
    named under them, and the backward ``cond`` differentiates a side
    inside the layer: without a scope of ``dispatched_sum``'s own in
    between, the recomputed and transposed operations would stand under
    ``jvp(dispatch)`` / ``transpose(jvp(products))`` and be counted by no
    metric."""
    held, dtype, k = 2, jnp.dtype('bfloat16'), 6
    slot, w = _routing(N, k, held, 100)
    m, matrices = _operands(held, dtype)

    def products(rows, groups, *matrices):
        with jax.named_scope('the_products'):
            return _products(dtype)(rows, groups, *matrices)

    def scalar(m, w, matrices):
        plan = experts.sort_plan(slot, held, PUBLISHED)
        return (experts.dispatched_sum(m, plan, slot, w, PUBLISHED, products,
                                       matrices, 'the_dispatch') ** 2).sum()
    text = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2))).lower(
        m, w, matrices).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ('the_dispatch', 'the_products'):
        ours = [path for path in paths if scope in path]
        assert any('transpose(' in path for path in ours)
        assert all(scope in path.split('/') for path in ours), [
            path for path in ours if scope not in path.split('/')][:3]


# -- both nets, every pair on the experts held --------------------------------------
def _all_on_held(module, chosen):
    """A tiny net of ``module`` whose router sends every row to the experts
    ``chosen``, the way that module's
    ``test_all_rows_on_one_held_expert_are_computed`` sends it to one."""
    net, variables = module._net_and_variables()
    params = dict(variables['params'])
    if 'router_bias' in params['layer_%d' % net.expert_layers[0]]:
        bias = jnp.zeros((net.experts_published,)).at[
            jnp.asarray(chosen)].set(10.0)
        for i in net.expert_layers:
            name = 'layer_%d' % i
            params[name] = dict(params[name], router_bias=bias)
        return net, {'params': params}
    params['embed'] = params['embed'].at[:, 0].set(40.0)
    for i in net.expert_layers:
        name = 'layer_%d' % i
        router = params[name]['router'].at[0].set(0.0)
        router = router.at[0, jnp.asarray(chosen)].set(40.0)
        params[name] = dict(params[name], router=router,
                            norm_in=params[name]['norm_in'].at[0].set(1.0))
    return net, {'params': params}


@pytest.mark.parametrize('name', ['test_smallthinker', 'test_trinity'])
def test_every_pair_on_the_experts_held_takes_the_long_buffer(name):
    """``test_all_rows_on_one_held_expert_are_computed`` keeps a third (a
    half) of the pairs here, which fit in the short buffer; here EVERY
    choice of every row is an expert held, twice the buffer and more, so
    each layer takes the ``cond``'s other side under ``nn.remat``: nothing
    is dropped, no dispatch is counted short, and the logits and the
    gradient are those of the same net with the short side's 1/1."""
    module = __import__(name)
    k = module.WIDTHS['experts_per_token']
    net, variables = _all_on_held(module, list(range(k)))
    length = module.T
    ids = module._ids(1, 8)
    first, valid = jnp.zeros((1,), jnp.int32), jnp.ones((1, length), bool)

    def loss(v):
        out = net.apply(v, ids, first, valid, method=net.sequence)
        logits = net.apply(v, out['policy_features'],
                           method=net.policy_logits)
        return (logits ** 2).mean() + out['value'].mean(), (logits,
                                                            out['aux'])
    (_, (logits, aux)), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(variables)
    layers = len(net.expert_layers)
    held = len(net.held)
    assert float(aux['moe_rows_held']) == layers * length * k
    assert length * k > experts.short_length(
        length * k, held, module.WIDTHS['experts_published'])
    assert float(aux['moe_rows_dropped']) == 0
    assert float(aux['moe_dispatches']) == layers
    assert float(aux['moe_dispatches_short']) == 0
    want = module._plain(net, variables, ids[0], jnp.int32(0), valid[0])
    np.testing.assert_allclose(logits[0], want['logits'], atol=5e-4)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(leaf).all()) for leaf in leaves)
    name = 'layer_%d' % net.expert_layers[-1]
    assert float(jnp.abs(
        grads['params'][name]['experts_down'][:k]).max()) > 0
