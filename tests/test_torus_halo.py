"""TorusConv impl='halo' must be bit-for-bit the same FUNCTION as
impl='pad' (the wrap-pad reference semantics of the torus conv,
reference hungry_geese.py:23-35) — same param tree, same outputs, same
gradients. The halo path exists purely to remove the wrap-pad's
full-activation HBM copies (the round-5 per-op table; ROADMAP S1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.models.blocks import TorusConv
from handyrl_tpu.models.geese import GeeseNet


def _pair(filters=8, norm=True, dtype=jnp.float32):
    pad = TorusConv(filters, norm=norm, impl='pad', dtype=dtype)
    halo = TorusConv(filters, norm=norm, impl='halo', dtype=dtype)
    return pad, halo


@pytest.mark.parametrize('norm', [True, False])
@pytest.mark.parametrize('shape', [(4, 7, 11, 17), (2, 3, 5, 5, 8),
                                   (1, 2, 2, 6)])
def test_outputs_match(norm, shape):
    pad, halo = _pair(norm=norm)
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    params = pad.init(jax.random.PRNGKey(1), x)
    # identical param trees: checkpoints transfer between impls
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(halo.init(jax.random.PRNGKey(1), x)))
    yp = pad.apply(params, x)
    yh = halo.apply(params, x)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yh),
                               rtol=2e-5, atol=2e-5)


def test_outputs_match_bf16():
    """The production headline runs bf16 activations — pin parity there
    too (looser tolerance: different accumulation order in the .at[].add
    correction chain vs the fused pad conv)."""
    pad, halo = _pair(filters=16, norm=True, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 7, 11, 17))
    params = pad.init(jax.random.PRNGKey(7), x)
    yp = np.asarray(pad.apply(params, x), np.float32)
    yh = np.asarray(halo.apply(params, x), np.float32)
    np.testing.assert_allclose(yp, yh, rtol=0.05, atol=0.05)


def test_gradients_match():
    pad, halo = _pair(norm=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 7, 11, 6))
    params = pad.init(jax.random.PRNGKey(3), x)

    def loss(mod, p, xx):
        return (mod.apply(p, xx) ** 2).sum()

    gp_p, gp_x = jax.grad(lambda p, xx: loss(pad, p, xx), argnums=(0, 1))(
        params, x)
    gh_p, gh_x = jax.grad(lambda p, xx: loss(halo, p, xx), argnums=(0, 1))(
        params, x)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-4),
        gp_p, gh_p)
    np.testing.assert_allclose(np.asarray(gp_x), np.asarray(gh_x),
                               rtol=1e-4, atol=1e-4)


def test_non3x3_kernel_rejected():
    mod = TorusConv(4, kernel=5, impl='halo')
    x = jnp.zeros((1, 7, 11, 3))
    with pytest.raises(ValueError):
        mod.init(jax.random.PRNGKey(0), x)


def test_geesenet_halo_twin():
    """Full GeeseNet forward agrees across torus impls with shared params."""
    obs = jax.random.normal(jax.random.PRNGKey(4), (2, 17, 7, 11))
    net_pad = GeeseNet(torus_impl='pad')
    net_halo = GeeseNet(torus_impl='halo')
    params = net_pad.init(jax.random.PRNGKey(5), obs)
    out_p = net_pad.apply(params, obs)
    out_h = net_halo.apply(params, obs)
    for k in ('policy', 'value'):
        np.testing.assert_allclose(np.asarray(out_p[k]), np.asarray(out_h[k]),
                                   rtol=2e-5, atol=2e-5)
