"""``utils/fetch.py``'s two named steps: ``pack_tree`` (asynchronous, on the
device) and ``fetch_packed`` (the blocking transfer). ``fetch_tree`` is their
composition, and a detached pack of a tree whose leaves all pack is a
snapshot that outlives the tree's donation: the fused loop's epoch boundary
enqueues its next dispatch between the two steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.utils import fetch


def _mixed_tree():
    """Two dtypes of several leaves, a dtype of one leaf, scalars, and
    leaves that are on the host already."""
    key = jax.random.PRNGKey(3)
    return {
        'w': jax.random.normal(key, (5, 7)),
        'b': jnp.arange(7, dtype=jnp.float32),
        'scale': jnp.float32(1.5),
        'count': jnp.asarray(11, jnp.int32),
        'steps': jnp.arange(3, dtype=jnp.int32),
        'half': jnp.ones((4,), jnp.bfloat16) * 3,     # its dtype's only leaf
        'host': np.arange(6.0).reshape(2, 3),
        'number': 7,
    }


def _same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert type(x) is type(y)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x, y)
        else:
            assert x == y


@pytest.mark.parametrize('threshold', [64 << 20, 100, 0],
                         ids=['all_pack', 'one_large_leaf', 'none_pack'])
@pytest.mark.parametrize('detach', [False, True])
def test_the_two_steps_compose_to_fetch_tree(monkeypatch, threshold, detach):
    """Mixed dtypes, host leaves, a group of one leaf and (``threshold``
    100: ``w`` is 140 bytes) a leaf that goes alone: both steps in a row
    give what ``fetch_tree`` gives, leaf for leaf, dtype and shape."""
    monkeypatch.setattr(fetch, 'LARGE_LEAF_BYTES', threshold)
    tree = _mixed_tree()
    want = jax.tree_util.tree_map(
        lambda x: np.asarray(x) if isinstance(x, jax.Array) else x, tree)
    _same(fetch.fetch_tree(tree), want)
    packed = fetch.pack_tree(tree, detach=detach)
    assert isinstance(packed, fetch.PackedTree)
    _same(fetch.fetch_packed(packed), want)
    assert fetch.packs_whole(tree) == (threshold == 64 << 20)
    # a leaf that goes alone is not copied: the pack holds the leaf itself
    alone = [leaf for leaf in packed.out if isinstance(leaf, jax.Array)]
    assert len(alone) == {64 << 20: 0, 100: 1, 0: 6}[threshold]
    assert all(any(leaf is src for src in tree.values()) for leaf in alone)


def test_host_only_and_empty_trees_pass_through():
    tree = {'a': np.ones(3), 'b': 2.5, 'c': ()}
    packed = fetch.pack_tree(tree, detach=True)
    assert packed.groups == [] and fetch.packs_whole(tree)
    _same(fetch.fetch_packed(packed), tree)
    assert fetch.fetch_tree({}) == {}


def test_a_detached_pack_survives_the_donation_of_its_source():
    """The packed buffers are new ones: a program that donates every leaf
    of the tree (and overwrites it) after ``pack_tree`` leaves the snapshot
    as it was. Without ``detach`` a dtype's only leaf is the leaf itself."""
    tree = {k: v for k, v in _mixed_tree().items()
            if isinstance(v, jax.Array)}
    want = jax.tree_util.tree_map(np.array, tree)
    packed = fetch.pack_tree(tree, detach=True)
    shared = fetch.pack_tree(tree)
    assert any(flat is tree['half'] for _i, _s, flat in shared.groups)
    assert not any(flat is leaf for _i, _s, flat in packed.groups
                   for leaf in tree.values())
    overwrite = jax.jit(
        lambda t: jax.tree_util.tree_map(lambda x: x * 0 - 1, t),
        donate_argnums=0)
    after = overwrite(tree)
    jax.block_until_ready(after)
    assert all(leaf.is_deleted() for leaf in tree.values())
    _same(fetch.fetch_packed(packed), want)
    assert float(after['scale']) == -1.0
