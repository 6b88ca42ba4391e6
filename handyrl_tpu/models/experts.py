"""The sparse-expert layer of a chip that holds a SHARE of a layer's experts,
as functions of arrays: the nets that use it (``models/trinity.py``,
``models/smallthinker.py``) route however their source says, hand the
routing over as ``(ids, weights)`` and name the scopes these run under.

A token's ``k`` choices among the published experts become ``slot``s among
the experts held here (``held_slot``; an absent expert's slot is ``held``).
Many rows (a training window) take the grouped path, in three parts:

* the plan, ``sort_plan(slot, held, experts_published)``: a counting sort of
  the ``M = n * k`` (row, choice) pairs by slot, pairs of absent experts
  last, and whether the pairs of the experts held here fit in the short
  buffer (``fits``). It depends on the ROUTING alone, so a net whose router
  reads the layer's input may compute it before attention;
* the dispatch, ``dispatched_sum``, sized by what is held here. The short
  buffer has ``short_length`` rows, twice the even share of this chip's
  experts. Where the held pairs fit in it (``plan.fits``), the first
  ``C`` entries of the plan are all there is: ``C`` rows gathered from ``m``
  itself into expert order, the products over them, and the ``C`` results
  added beside their rows under the router's weights; ``M`` is then the
  length of index vectors only. Where they do not fit, behind ONE
  ``jax.lax.cond``, the every-pair buffer: ``to_expert_order`` (all ``M``
  pairs gathered, those of absent experts zeroed) and ``weighted_sum_back``.
  A net that holds half its experts or more has ``C == M`` and the
  every-pair buffer alone, with no ``cond``;
* the products, ``grouped_products``: ONE ``jax.lax.ragged_dot`` a matrix
  over the experts held, whose work follows the rows that came and not the
  buffer's size.

Dropless under any imbalance: the short buffer is taken only where every
held pair has a row in it and the other holds every pair, so no capacity and
no dropped row (``plan.dropped`` is 0 by construction, and counted).
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

from .trunk import f32


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


def rows_aux(counts, held, tally):
    """The sums a net's ``sequence`` hands the host (``aux``), from the
    layers' ``chosen_counts`` stacked (layers, E), the indices of the
    experts held and the layers' ``SortPlan.tally`` added up."""
    dropped, short = tally
    ours = counts[:, jnp.asarray(held)]
    return {'moe_counts': counts,
            'moe_rows_held': ours.sum().astype(f32),
            'moe_rows_routed': counts.sum().astype(f32),
            'moe_rows_fullest': ours.max().astype(f32),
            'moe_rows_dropped': dropped.astype(f32),
            'moe_dispatches': jnp.asarray(counts.shape[0], f32),
            'moe_dispatches_short': short.astype(f32)}


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
            'moe_rows_dropped': sums.get('diag_moe_rows_dropped', 0.0),
            'moe_short_buffer_share':
            100.0 * sums.get('diag_moe_dispatches_short', 0.0)
            / max(sums.get('diag_moe_dispatches', 0.0), 1.0)}


class SortPlan(NamedTuple):
    """Where each (row, choice) pair goes (``dest``) and which pair each
    buffer row holds (``source``), both (M,) permutations of the pairs; the
    rows a held expert has (``groups``, (held,)); which buffer rows belong
    to a held expert at all (``in_group``, (M, 1)); the pairs of held
    experts that found no row (0); whether the held experts' pairs fit in
    the short buffer (``fits``)."""
    dest: jax.Array
    source: jax.Array
    groups: jax.Array
    in_group: jax.Array
    dropped: jax.Array
    fits: jax.Array

    @property
    def tally(self):
        """What a layer adds to ``rows_aux``'s sums, (2,) int32: the rows
        dropped (0) and whether its dispatch takes the short buffer."""
        return jnp.stack([self.dropped, self.fits.astype(jnp.int32)])


# rows a tile of the grouped products' operands: the short buffer is whole
# tiles
ROW_TILE = 8


def short_length(pairs, held, experts_published):
    """The rows of the short dispatch buffer: twice the even share of the
    ``held`` experts here of ``pairs`` (row, choice) pairs routed over
    ``experts_published``, in whole row tiles, and at most all of them."""
    even = -(-2 * pairs * held // experts_published)
    return min(pairs, -(-even // ROW_TILE) * ROW_TILE)


def sort_plan(slot, held, experts_published):
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
    fits = groups.sum() <= short_length(M, held, experts_published)
    return SortPlan(dest, source, groups, in_group, dropped, fits)


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


def _short_to_expert_order(m, plan, C):
    """m (n, D) -> (C, D): the rows of the first ``C`` pairs of the plan,
    gathered from ``m`` itself (pair ``p`` is a choice of row ``p // k``)."""
    K = plan.source.shape[0] // m.shape[0]
    return jnp.where(plan.in_group[:C], m[plan.source[:C] // K], 0)


def _short_weighted_sum_back(y, plan, w):
    """y (C, D), the results of the plan's first ``C`` pairs -> (n, D)
    float32: each under its pair's weight, added beside its row. Every
    held pair is among them (``plan.fits``), so ``in_group`` is the mask
    ``slot < held`` of the every-pair sum."""
    n, K = w.shape
    C = y.shape[0]
    pair = plan.source[:C]
    # the weight in ``y``'s dtype and the product in float32, as the
    # every-pair sum's einsum takes them
    wy = y.astype(f32) * w.reshape(n * K)[pair].astype(y.dtype).astype(
        f32)[:, None]
    return jnp.zeros((n, y.shape[1]), f32).at[pair // K].add(
        jnp.where(plan.in_group[:C], wy, 0))


def dispatched_sum(m, plan, slot, w, experts_published, products, matrices,
                   scope):
    """m (n, D) in ``dtype`` -> (n, D) float32: ``sum_k w_k y_k`` over each
    row's choices that are held here, ``y = products(rows, groups,
    *matrices)`` on the rows in expert order (the caller's
    ``grouped_products`` under the caller's scope). The gather into that
    order and the sum back run under the named ``scope``, through the short
    buffer where the held pairs fit in it and through the every-pair buffer
    where they do not (module docstring). Call it under no scope: a
    branch's operations carry the scopes of the ``cond``'s call before
    their own."""
    M, held = plan.source.shape[0], plan.groups.shape[0]
    C = short_length(M, held, experts_published)

    def short(m, w, matrices, plan, slot):
        with jax.named_scope(scope):
            rows = _short_to_expert_order(m, plan, C)
        y = products(rows, plan.groups, *matrices)
        with jax.named_scope(scope):
            return _short_weighted_sum_back(y, plan, w)

    def every_pair(m, w, matrices, plan, slot):
        with jax.named_scope(scope):
            rows = to_expert_order(m, plan)
        y = products(rows, plan.groups, *matrices)
        with jax.named_scope(scope):
            return weighted_sum_back(y, plan, slot, w, held)

    if C == M:
        return every_pair(m, w, matrices, plan, slot)

    # Differentiated by jax, a ``cond`` hands every side's residuals out of
    # the forward pass, each side writing zeros for the other's: the short
    # side would fill the M-row arrays it is there to avoid, and the
    # every-pair side's peak would hold the short side's too. So the
    # backward pass is a ``cond`` of its own that keeps what it was given
    # and recomputes the side it takes (the layers are rematerialised a
    # layer at a time as it is).
    def run(m, w, matrices, plan, slot):
        return jax.lax.cond(plan.fits, short, every_pair,
                            m, w, matrices, plan, slot)

    def forward(*given):
        return run(*given), given

    def backward(given, g):
        plan, slot = given[3:]

        def pull(side):
            def recomputed(*taken):
                # jax writes its transforms around the first scope named
                # under them (``transpose(jvp(...))``): this one, so that
                # the side's own scopes stand in a trace as they are
                with jax.named_scope('dispatched_sum'):
                    return side(*taken, plan, slot)
            return lambda *taken: jax.vjp(recomputed, *taken)[1](g)
        return jax.lax.cond(plan.fits, pull(short), pull(every_pair),
                            *given[:3]) + (None, None)

    either = jax.custom_vjp(run)
    either.defvjp(forward, backward)
    return either(m, w, matrices, plan, slot)


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
