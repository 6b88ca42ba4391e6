"""The sparse-expert layer of a chip that holds a SHARE of a layer's experts,
as functions of arrays: the nets that use it (``models/trinity.py``,
``models/smallthinker.py``) route however their source says, hand the
routing over as ``(ids, weights)`` and name the scopes these run under.

A token's ``k`` choices among the published experts become ``slot``s among
the experts held here (``held_slot``; an absent expert's slot is ``held``).
Many rows (a training window) take the grouped path, in three parts:

* ``sort_plan(slot, held)``: a counting sort of the (row, choice) pairs by
  slot, pairs of absent experts last. It depends on the ROUTING alone, so a
  net whose router reads the layer's input may compute it before attention;
* ``to_expert_order(m, plan)``: the rows gathered into that order;
  ``grouped_products``: ONE ``jax.lax.ragged_dot`` a matrix over the experts
  held, whose work follows the rows that came and not the buffer's size;
* ``weighted_sum_back(y, plan, slot, w, held)``: each row's choices back
  beside it and summed under the router's weights.

Dropless under any imbalance: the buffer holds every pair, so no capacity
and no dropped row (``plan.dropped`` is 0 by construction, and counted).
A few rows (a decode ply) take ``every_row_gate`` / ``every_row_products``:
every held expert on every row, weighted by ``w_e`` or by 0; the weights are
read once either way, and a grouped product over a handful of rows is all
tile padding.

``inv`` is 1 / ``param_scale``, taken on each product's result; ``dtype``
is the compute dtype the stored matrices are cast to.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


def held_slot(ids, experts_held, experts_published):
    """Published expert ids -> slots among the experts held here; an expert
    that lies on another chip gets the slot ``len(experts_held)``."""
    held = len(experts_held)
    # a host constant: as a device scatter of arange into a constant the
    # v5e's compiler aborts inside a scan (one constant, two operands)
    slot = np.full((experts_published,), held, np.int32)
    slot[list(experts_held)] = np.arange(held)
    return jnp.asarray(slot)[ids]


def chosen_counts(ids, experts_published):
    """ids (n, k) -> the tokens each published expert was chosen for (E,)."""
    return (ids[..., None] == jnp.arange(experts_published)).sum(
        axis=(0, 1), dtype=jnp.int32)


def rows_aux(counts, held, dropped):
    """The sums a net's ``sequence`` hands the host (``aux``), from the
    layers' ``chosen_counts`` stacked (layers, E), the indices of the
    experts held and the rows dropped."""
    ours = counts[:, jnp.asarray(held)]
    return {'moe_counts': counts,
            'moe_rows_held': ours.sum().astype(f32),
            'moe_rows_routed': counts.sum().astype(f32),
            'moe_rows_fullest': ours.max().astype(f32),
            'moe_rows_dropped': dropped.astype(f32)}


def rows_dynamics(sums, slots):
    """The epoch record's keys from the epoch's ``diag_*`` sums of
    ``rows_aux``; ``slots`` is experts held x expert layers."""
    held = sums.get('diag_moe_rows_held')
    if not held:
        return {}
    return {'moe_rows_held_share':
            100.0 * held / sums['diag_moe_rows_routed'],
            'moe_load_max_over_mean':
            sums['diag_moe_rows_fullest'] * slots / held,
            'moe_rows_dropped': sums.get('diag_moe_rows_dropped', 0.0)}


class SortPlan(NamedTuple):
    """Where each (row, choice) pair goes (``dest``) and which pair each
    buffer row holds (``source``), both (M,) permutations of the pairs; the
    rows a held expert has (``groups``, (held,)); which buffer rows belong
    to a held expert at all (``in_group``, (M, 1)); the pairs of held
    experts that found no row (0)."""
    dest: jax.Array
    source: jax.Array
    groups: jax.Array
    in_group: jax.Array
    dropped: jax.Array


def sort_plan(slot, held):
    """slot (n, k) -> the plan of the n * k pairs."""
    M = slot.size
    flat = slot.reshape(M)
    onehot = flat[:, None] == jnp.arange(held + 1)              # (M, held+1)
    rank = (jnp.cumsum(onehot, axis=0, dtype=jnp.int32)
            * onehot).sum(axis=1) - 1
    sizes = onehot.sum(axis=0, dtype=jnp.int32)
    dest = (jnp.cumsum(sizes) - sizes)[flat] + rank             # (M,)
    source = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32))
    groups = sizes[:held]
    # rows past the last group belong to no expert held here: the grouped
    # product leaves whatever was there, forward AND backward, so nothing
    # of them is read and no cotangent of theirs passes
    in_group = (jnp.arange(M) < groups.sum())[:, None]
    # by construction 0: the buffer holds every pair
    dropped = jnp.sum((flat < held) & (dest >= M), dtype=jnp.int32)
    return SortPlan(dest, source, groups, in_group, dropped)


def to_expert_order(m, plan):
    """m (n, D) -> (M, D): each row once a choice, sorted by expert."""
    K = plan.source.shape[0] // m.shape[0]
    # both gathers are by permutations of the pairs, and say so: their
    # transposes are then plain scatters, not scatter-adds
    rows = jnp.repeat(m, K, axis=0).at[plan.source].get(unique_indices=True)
    return jnp.where(plan.in_group, rows, 0)


def grouped_products(rows, groups, w_gate, w_up, w_down, activation, dtype,
                     inv):
    """``W_down (activation(W_gate x) * W_up x)`` of each row's own expert:
    rows (M, D) in expert order, the matrices (held, D | F, F | D)."""
    grouped = lambda x, p: jax.lax.ragged_dot(
        x, p.astype(dtype), groups, preferred_element_type=dtype) * inv
    act = activation(grouped(rows, w_gate)) * grouped(rows, w_up)
    return grouped(act, w_down)                                 # (M, D)


def weighted_sum_back(y, plan, slot, w, held):
    """y (M, D) in expert order -> (n, D) float32: ``sum_k w_k y_k`` over a
    row's choices that are held here."""
    n, K = slot.shape
    y = jnp.where(plan.in_group, y, 0)
    y = y.at[plan.dest].get(unique_indices=True).reshape(n, K, -1)
    return jnp.einsum('nkd,nk->nd', y, (w * (slot < held)).astype(y.dtype),
                      preferred_element_type=f32)


def every_row_gate(slot, w, held):
    """(n, held): ``w_e`` where the row chose held expert ``e``, else 0."""
    return ((slot[..., None] == jnp.arange(held)) * w[..., None]).sum(axis=1)


def every_row_products(m, gate, w_gate, w_up, w_down, activation, dtype,
                       inv):
    """Every held expert on every row of m (n, D), summed under ``gate``."""
    cast = lambda p: p.astype(dtype)
    act = (activation(jnp.einsum('nd,edf->enf', m, cast(w_gate)) * inv)
           * (jnp.einsum('nd,edf->enf', m, cast(w_up)) * inv))
    # in ``dtype``, as the grouped product's rows are. The weighted sum
    # ends the caller's scope: the compiler names a fusion after its last
    # operation, and without the barrier the product that reads ``w_down``
    # is fused on into what follows the scope and timed there
    y = jnp.einsum('enf,efd->end', act, cast(w_down)) * inv
    return jax.lax.optimization_barrier(jnp.einsum(
        'end,ne->nd', y.astype(f32), gate))
