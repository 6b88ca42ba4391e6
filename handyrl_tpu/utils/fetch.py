"""Packed device->host transfers.

Every DISTINCT array fetch is its own blocking device->host transfer: a
fixed cost per array (sync with the device stream, transfer set-up) that
does not shrink with the array, while the bytes themselves are cheap at
these sizes. Naive ``np.asarray`` per pytree leaf therefore costs leaves x
that fixed cost at every epoch boundary. ``fetch_tree`` flattens the tree
into ONE device buffer per dtype (a tiny jitted concat, dispatched async)
and pays one transfer per dtype group instead; a leaf over
``LARGE_LEAF_BYTES`` goes on its own. (The per-transfer cost on a
directly attached chip: not measured on today's code, ROADMAP S2.)

The two steps have names of their own, ``pack_tree`` (asynchronous) and
``fetch_packed`` (blocking). The packed buffer is a new one, so a tree whose
leaves all pack can be handed to a program that donates it between the two:
the fused loop's epoch boundary enqueues its next dispatch there.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry

# A leaf over this many bytes is fetched on its own: packing exists to save
# the fixed cost of a transfer, which a leaf of tens of megabytes does not
# feel, while the packed buffer is a second copy of every leaf in it ON THE
# DEVICE: for a train state of gigabytes, one it has no room for.
LARGE_LEAF_BYTES = 64 << 20

_PACKERS: Dict[Tuple, Any] = {}
_SPLITTERS: Dict[Tuple, Any] = {}


def _packer(sig: Tuple) -> Any:
    """One cached jitted concat per (dtype, shapes) signature."""
    fn = _PACKERS.get(sig)
    if fn is None:
        fn = jax.jit(lambda ls: jnp.concatenate([l.reshape(-1) for l in ls]))
        _PACKERS[sig] = fn
    return fn


def _splitter(sig: Tuple) -> Any:
    """One cached jitted split+reshape per (dtype, shapes) signature."""
    fn = _SPLITTERS.get(sig)
    if fn is None:
        _, shapes = sig

        def split(flat):
            out, pos = [], 0
            for shape in shapes:
                n = 1
                for s in shape:
                    n *= s
                out.append(jax.lax.dynamic_slice(flat, (pos,), (n,))
                           .reshape(shape))
                pos += n
            return out

        fn = jax.jit(split)
        _SPLITTERS[sig] = fn
    return fn


class PackedTree(NamedTuple):
    """A pytree on its way to the host (``pack_tree`` -> ``fetch_packed``):
    ``out`` holds the leaves that need no packing in their places (host
    values; a device leaf that goes on its own), ``groups`` one ``(leaf
    indices, shapes, flat device buffer)`` per packed dtype."""
    treedef: Any
    out: List[Any]
    groups: List[Tuple[List[int], Tuple, Any]]


def packs_whole(tree: Any) -> bool:
    """No device leaf of ``tree`` is over ``LARGE_LEAF_BYTES``: a detached
    pack of it is a second device copy that the device has room for."""
    return all(leaf.nbytes <= LARGE_LEAF_BYTES
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array))


def pack_tree(tree: Any, detach: bool = False) -> PackedTree:
    """Step one of a fetch, which returns at once: the device leaves of each
    dtype are concatenated into ONE new device buffer by a tiny jitted
    program that is enqueued behind whatever produces them. A dtype's only
    leaf, and a leaf over ``LARGE_LEAF_BYTES``, go as they are.

    With ``detach`` a dtype's only leaf is copied on the device too: the
    pack of a tree that ``packs_whole`` then shares no buffer with it, so it
    is a SNAPSHOT that outlives the tree's donation to a later program."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    device_ix: Dict[Any, List[int]] = {}
    out: List[Any] = [None] * len(leaves)
    for i, leaf in enumerate(leaves):
        if not isinstance(leaf, jax.Array) or leaf.nbytes > LARGE_LEAF_BYTES:
            out[i] = leaf
        else:
            device_ix.setdefault(jnp.asarray(leaf).dtype, []).append(i)
    groups = []
    for dtype, idxs in device_ix.items():
        group = [leaves[i] for i in idxs]
        shapes = tuple(g.shape for g in group)
        # per-signature cached jit: a FRESH signature compiles once by
        # design, so the scope is declared to the retrace sentinel
        with telemetry.expected_compile('fetch_tree packer'):
            if len(group) > 1:
                flat = _packer((str(dtype), shapes))(group)
            else:
                flat = jnp.copy(group[0]) if detach else group[0]
        groups.append((idxs, shapes, flat))
    return PackedTree(treedef, out, groups)


def fetch_packed(packed: PackedTree) -> Any:
    """Step two, which blocks: one device->host transfer per packed buffer
    and per leaf that goes alone, then the split back into leaves (views of
    the fetched buffer)."""
    out = [np.asarray(leaf) if isinstance(leaf, jax.Array) else leaf
           for leaf in packed.out]
    for idxs, shapes, flat in packed.groups:
        flat_host = np.asarray(flat).reshape(-1)
        pos = 0
        for i, shape in zip(idxs, shapes):
            n = int(np.prod(shape)) if shape else 1
            out[i] = flat_host[pos:pos + n].reshape(shape)
            pos += n
    return jax.tree_util.tree_unflatten(packed.treedef, out)


def fetch_tree(tree: Any) -> Any:
    """Device pytree -> host numpy pytree in one round trip per dtype:
    ``pack_tree`` then ``fetch_packed``.

    Leaves already on host (numpy / python scalars) pass through untouched.
    Structure, shapes, and dtypes are preserved exactly.
    """
    return fetch_packed(pack_tree(tree))


def put_tree(tree: Any) -> Any:
    """Host numpy pytree -> device pytree in one upload per dtype.

    The mirror of ``fetch_tree``: leaves are concatenated on the HOST, sent
    as one buffer, and split back by a tiny cached jitted program — instead
    of one `device_put` round trip per leaf (actor-params refresh happens
    every epoch)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: Dict[Any, List[int]] = {}
    out: List[Any] = [None] * len(leaves)
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, jax.Array):
            # already on the device (a seeded initialisation): it stays. A
            # round trip would be a SECOND device copy of it, which a
            # parameter set of gigabytes has no room for beside the train
            # state that is made from it
            out[i] = leaf
            continue
        arr = np.asarray(leaf)
        leaves[i] = arr
        if arr.nbytes > LARGE_LEAF_BYTES:
            out[i] = jax.device_put(arr)
            continue
        groups.setdefault(arr.dtype, []).append(i)
    for dtype, idxs in groups.items():
        group = [leaves[i] for i in idxs]
        if len(group) == 1:
            out[idxs[0]] = jax.device_put(group[0])
            continue
        shapes = tuple(tuple(g.shape) for g in group)
        flat = np.concatenate([g.reshape(-1) for g in group])
        with telemetry.expected_compile('put_tree splitter'):
            parts = _splitter((str(dtype), shapes))(jax.device_put(flat))
        for i, part in zip(idxs, parts):
            out[i] = part
    return jax.tree_util.tree_unflatten(treedef, out)
