"""The comparisons that decide ``correct`` for the ``smallthinker``
configuration: on the chip, at published widths, on what the timed path
runs, each against the plain float32 reference (``reference/smallthinker.py``,
``reference/smallthinker_loss.py``) at ``highest`` matmul precision on the
very weights the learner starts from.

``forward_check``   the learner's ``sequence`` (bfloat16 activations, the
                    routing and the sort plan taken before attention, the
                    grouped expert product) and its ``policy_logits`` over
                    ONE seeded window of ``forward_positions`` (8,192)
                    positions: logits, value, and
                    ``routing_agreement_share``, the share of the router's
                    (position, choice) pairs that are the reference's, a
                    LOWER limit.
``rollout_check``   the actor's ``__call__`` through its cache of two
                    lengths (bfloat16 parameters, every held expert on the
                    ply's few rows), driven by the program's own
                    ``rollout_chunk`` for ``rollout_plies`` plies of
                    ``rollout_envs`` games whose FIRST lengths the check
                    sets (``checks_trinity_mini.first_lengths``): one lane
                    plays the whole run (its circles of 4,096 rows go
                    round), one ends early, one late, one past position
                    4,096. All plies, the plies at positions from the window
                    on (``wrapped_``) and the plies of games begun after a
                    reset (``after_reset_``) are each held to limits.
``step_check``      one update of the program's own step on the cell's batch
                    of ONE seeded 8,192-position window that ends inside its
                    game, the legal set as bits: the loss, the gradient's
                    norm, the gradient and the change leaf by leaf (the
                    small leaves held to limits of their own), ``W_r``
                    unchanged, no row dropped.

What names no net is ``checks_trinity_mini``'s (the seeded windows and batch,
the rollout's driver, the leaf sums); what reads the reference is here.
Statistics are RMS errors relative to the RMS of the reference; each limit
sits in the configuration's ``tolerance`` with the readings it was set from
(``tolerance_smallthinker.py`` reads them and the seven negative controls).
"""

import functools
import time

import numpy as np

from . import checks
from .checks_evabyte import (_adder, _count, _items, _leaves_by_name, _limit,
                             _verdict)
from .checks_trinity_mini import STEP_LIMITS as SHARED_STEP_LIMITS
from .checks_trinity_mini import (FORWARD_LIMITS, LR, ROLLOUT_LIMITS,
                                  _leaf_sums, _sq, first_lengths,
                                  program_sequence, rollout_records,
                                  seeded_batch, seeded_windows)
from .reference import smallthinker as reference
from .reference import smallthinker_loss as reference_loss

__all__ = ['FORWARD_LIMITS', 'ROLLOUT_LIMITS', 'STEP_LIMITS', 'CONTROLS',
           'first_lengths', 'rollout_records', 'seeded_batch',
           'seeded_windows']

GROUPS = ('attention', 'experts', 'router', 'norms', 'embed', 'readout')
# beside the limits every trunk's step check holds, the change of the
# readout alone (the head and the value row, 16% of the parameters): every
# position reaches it directly, so its first Adam step is the number of the
# step that depends least on the seed, and the one that tells 8-bit
# parameters from the stated precision on every seed (at batch 1 the
# gradient's own error spreads sixfold over seeds: PERF.md section 2)
STEP_LIMITS = SHARED_STEP_LIMITS + ('readout_change_err_rel_to_change',)
# the reference's side degraded: the program is compared with a model that
# differs from it by that part. The last two are this architecture's own:
# without them a copy of another expert net's block would pass
CONTROLS = {'one_layer_left_out': {'skip_layer': 2},
            'experts_left_out': {'use_experts': False},
            'window_ignored': {'use_window': False},
            'rotary_on_global_layer': {'rotary_on_global': True},
            'router_after_attention': {'route_after_attention': True},
            'silu_for_relu': {'silu_experts': True}}


def reference_config(config):
    model = config['model']
    cfg = {key: model[key] for key in (
        'head_dim', 'window_size', 'rope_theta', 'norm_eps',
        'experts_per_token')}
    cfg['param_scale'] = model.get('param_scale', 1.0)
    cfg['layer_types'] = tuple(model['layer_types'])
    cfg['experts_held'] = tuple(model['experts_held'])
    return cfg


class _Plain:
    """The reference as small programs, each jitted once a process and a
    kind of layer: ``layer`` (one block at ``highest`` precision), its
    vector-Jacobian product, the readout, and the loss's gradient at the
    readout. Everything a seed decides is an ARGUMENT."""

    def __init__(self, cfg, skip_layer=None, use_window=True,
                 rotary_on_global=False, **layer_args):
        import jax
        self.cfg, self.skip_layer = cfg, skip_layer
        self.kinds = reference.layer_kinds(cfg, use_window, rotary_on_global)

        def highest(fn, **jit_args):
            def wrapped(*args, **kwargs):
                with jax.default_matmul_precision('highest'):
                    return fn(*args, **kwargs)
            return jax.jit(wrapped, **jit_args)

        def layer(p_layer, x, positions, valid, kind):
            return reference.layer(p_layer, x, positions, valid, cfg, kind,
                                   **layer_args)
        self.layer = highest(layer, static_argnums=(4,))
        self.layer_vjp = highest(
            lambda p_layer, x, positions, valid, kind, ct: jax.vjp(
                lambda p_, x_: layer(p_, x_, positions, valid, kind)[0],
                p_layer, x)[1](ct), static_argnums=(4,))
        self.readout = highest(lambda top, x: reference.readout(top, x, cfg))

        def head_loss(top, x, win, value_target, advantage, coef, decay):
            return reference_loss.loss_of_outputs(
                reference.readout(top, x, cfg), win, value_target, advantage,
                coef, decay)
        self.head_grad = highest(jax.value_and_grad(
            head_loss, argnums=(0, 1), has_aux=True))
        self.embed_add = jax.jit(
            lambda g, ids, ct: g.at[ids].add(ct / cfg['param_scale']),
            donate_argnums=(0,))

    def kept(self):
        return [(i, kind) for i, kind in enumerate(self.kinds)
                if i != self.skip_layer]

    @staticmethod
    def top(variables):
        p = variables['params']
        return {k: p[k] for k in ('norm_out', 'head', 'value')}

    def hidden(self, variables, ids, first, valid):
        """The input of every kept layer, the last one's output, and the
        router's choices of every kept layer."""
        import jax.numpy as jnp
        p = variables['params']
        positions = first + jnp.arange(ids.shape[0])
        xs, routes = [reference.embed(p, ids, self.cfg)], []
        for i, kind in self.kept():
            x, chosen = self.layer(p['layer_%d' % i], xs[-1], positions,
                                   valid, kind)
            xs.append(x)
            routes.append(chosen)
        return positions, xs, routes

    def forward(self, variables, ids, first, valid):
        _positions, xs, routes = self.hidden(variables, ids, first, valid)
        return dict(self.readout(self.top(variables), xs[-1]), routes=routes)

    def loss_and_grad(self, variables, win, value_target, advantage, coef,
                      decay, grads):
        """``jax.vjp`` of the reference loss, block by block, each piece
        ADDED to ``grads`` (a tree of the parameters' shapes, donated)."""
        valid = win['valid'] > 0
        positions, xs, _routes = self.hidden(
            variables, win['ids'], win['first_position'], valid)
        (total, terms), (g_top, ct) = self.head_grad(
            self.top(variables), xs[-1], win, value_target, advantage,
            coef, decay)
        grads = dict(grads)
        for key, piece in g_top.items():
            grads[key] = _adder()(grads[key], piece)
        p = variables['params']
        for (i, kind), x in reversed(list(zip(self.kept(), xs[:-1]))):
            name = 'layer_%d' % i
            piece, ct = self.layer_vjp(p[name], x, positions, valid, kind, ct)
            grads[name] = _adder()(grads[name], piece)
        grads['embed'] = self.embed_add(grads['embed'], win['ids'], ct)
        return total, terms, grads


@functools.lru_cache(maxsize=None)
def _plain(cfg_items, args_items=()):
    return _Plain(dict(cfg_items), **dict(args_items))


def plain(config, reference_args):
    return _plain(_items(reference_config(config)), _items(reference_args))


# -- forward -----------------------------------------------------------------
def forward_errors(config, module, variables, seed, program_variables=None,
                   **reference_args):
    """The RMS errors of the program's ``sequence`` against the reference
    over the seeded windows' valid positions, and the share of the router's
    choices that agree. ``program_variables`` and ``reference_args`` are
    the negative controls'."""
    import jax.numpy as jnp

    ids, first, valid = seeded_windows(
        config, seed, int(config['forward_windows']),
        int(config['forward_positions']))
    want_of = plain(config, reference_args).forward
    logits, value, routes = program_sequence(module)(
        variables if program_variables is None else program_variables,
        jnp.asarray(ids), jnp.asarray(first), jnp.asarray(valid))
    T, k = ids.shape[1], routes.shape[-1]
    routes = routes.reshape(routes.shape[0], ids.shape[0], T, k)
    sums = dict.fromkeys(('d_logit', 'logit', 'd_value', 'agree', 'pairs'),
                         0.0)
    for w in range(ids.shape[0]):
        want = want_of(variables, jnp.asarray(ids[w]), jnp.asarray(first[w]),
                       jnp.asarray(valid[w]))
        keep = jnp.asarray(valid[w])
        sums['d_logit'] += float(_sq((logits[w] - want['logits'])
                                     * keep[:, None]))
        sums['logit'] += float(_sq(want['logits'] * keep[:, None]))
        sums['d_value'] += float(_sq((value[w] - want['value']) * keep))
        # a reference that left a layer out (a control) has other layers'
        # choices: nothing to compare, and the share reads 0
        if len(want['routes']) != routes.shape[0]:
            continue
        for ours, theirs in zip(routes[:, w], want['routes']):
            same = (ours[:, :, None] == theirs[:, None, :]).any(axis=2)
            sums['agree'] += float((same * keep[:, None]).sum())
            sums['pairs'] += float(keep.sum()) * k
    n = float(valid.sum())
    logit_rms = (sums['logit'] / (n * logits.shape[-1])) ** 0.5
    return {'logits_rms_rel_to_logit_rms':
            (sums['d_logit'] / (n * logits.shape[-1])) ** 0.5
            / max(logit_rms, 1e-9),
            'value_rms': (sums['d_value'] / n) ** 0.5,
            'routing_agreement_share': sums['agree'] / max(sums['pairs'], 1),
            'logit_rms': logit_rms, 'positions': int(n)}


def forward_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = forward_errors(config, module, variables, seed)
    n_params = _count(variables)
    compared = [['parameters', n_params, '==', config['model']['parameters']]]
    for name, op in FORWARD_LIMITS:
        limit = config.get('tolerance', {}).get(
            'forward_' + name, float('inf') if op == '<=' else 0.0)
        compared.append([name, stats[name], op, limit])
    return _verdict(compared, n_params, **stats,
                    seconds=time.perf_counter() - began)


# -- rollout through the cache -----------------------------------------------
@functools.lru_cache(maxsize=None)
def _game_sums():
    """One game and seat on the device: the squared errors of its plies'
    logits and values and the reference's squared logits, summed over all
    plies, over those at positions from ``window`` on and (``after_reset``)
    over all of them again. A game's 37,984 logits a ply stay where the
    reference made them: fetching them is 1.2 GB a game."""
    import jax
    import jax.numpy as jnp

    def sums(got, logits, value, window, after_reset):
        n = got.shape[0]
        rows = {'d_logit': jnp.square(got[:, 1:] - logits[:n]).sum(axis=1),
                'd_value': jnp.square(got[:, 0] - value[:n]),
                'logit': jnp.square(logits[:n]).sum(axis=1),
                'plies': jnp.ones((n,), jnp.float32)}
        ply = jnp.arange(n)
        parts = {'': ply >= 0, 'wrapped_': ply >= window,
                 'after_reset_': (ply >= 0) & after_reset}
        return {name: {key: (row * keep).sum() for key, row in rows.items()}
                for name, keep in parts.items()}
    return jax.jit(sums)


def rollout_compare(config, records, variables, **reference_args):
    """Every ply's policy logits and value against the reference's full
    forward over each game's ids (``variables``: float32, what the actor's
    copy was cast from), over all plies and over two parts of them: the
    plies at positions from ``window_size`` on (``wrapped_``) and the plies
    of games that began after a reset (``after_reset_``)."""
    import jax.numpy as jnp
    obs, out, done = records['obs'], records['out'], records['done']
    want_of = plain(config, reference_args).forward
    window = config['model']['window_size']
    # one length for every game: the forward check's, so that the same
    # compiled layers serve both (causal: the padded tail is unseen)
    block = int(config['forward_positions'])
    parts = {name: dict.fromkeys(('d_logit', 'd_value', 'logit', 'plies'),
                                 0.0)
             for name in ('', 'wrapped_', 'after_reset_')}
    games = 0
    ply = np.arange(len(done))[:, None]
    counter = ply - np.maximum.accumulate(
        np.where(np.roll(done, 1, axis=0) & (ply > 0), ply, 0), axis=0)
    for n in range(obs.shape[1]):
        ends = [0] + list(np.flatnonzero(done[:, n]) + 1) + [len(done)]
        for a, b in zip(ends, ends[1:]):
            if a == b:
                continue
            games += 1
            for seat in range(obs.shape[2]):
                ids = np.zeros(-(-(b - a) // block) * block, np.int32)
                ids[:b - a] = obs[a:b, n, seat]   # causal: the tail is unseen
                want = want_of(variables, jnp.asarray(ids), jnp.int32(0),
                               jnp.ones(ids.shape, bool))
                got = _game_sums()(
                    jnp.asarray(out[a:b, n, seat]), want['logits'],
                    want['value'], jnp.int32(window), jnp.asarray(a > 0))
                for name, part in parts.items():
                    for key in part:
                        part[key] += float(got[name][key])
    stats = {'plies': int(len(done)), 'games': games,
             'sequences': int(obs.shape[1] * obs.shape[2]),
             'resets': int(done.sum()),
             'distinct_counters': max(len(set(row)) for row in counter)}
    ids_held = out.shape[-1] - 1
    logit_rms = (parts['']['logit'] / (parts['']['plies'] * ids_held)) ** 0.5
    for name, part in parts.items():
        n = max(part['plies'], 1)
        stats[name + 'plies' if name else 'compared_plies'] = int(
            part['plies'])
        stats[name + 'logits_rms_rel_to_logit_rms'] = (
            part['d_logit'] / (n * ids_held)) ** 0.5 / max(logit_rms, 1e-9)
        stats[name + 'value_rms'] = (part['d_value'] / n) ** 0.5
    stats['logit_rms'] = logit_rms
    return stats


def rollout_errors(config, module, variables, seed, train_args,
                   actor_dtype=None, reference_variables=None,
                   **reference_args):
    records = rollout_records(config, module, variables, seed, train_args,
                              actor_dtype)
    return rollout_compare(
        config, records,
        variables if reference_variables is None else reference_variables,
        **reference_args)


def rollout_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = rollout_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'rollout_' + name)]
                for name in ROLLOUT_LIMITS]
    lanes = int(config['rollout_envs'])
    compared += [
        ['plies', stats['plies'], '>=', config['model']['window_size'] + 1],
        ['resets', stats['resets'], '>=', min(lanes, 4) - 1],
        ['distinct_counters', stats['distinct_counters'], '>=',
         min(lanes, 4)],
        ['wrapped_plies', stats['wrapped_plies'], '>=', 2]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)


# -- one update ----------------------------------------------------------------
def group_of(path):
    """A parameter's group, by its name in the tree."""
    name = path[-1]
    if name in ('wq', 'wk', 'wv', 'wo'):
        return 'attention'
    if name.startswith('experts_'):
        return 'experts'
    if name == 'router':
        return 'router'
    if name.startswith('norm'):
        return 'norms'
    return 'embed' if name == 'embed' else 'readout'


def step_errors(config, module, variables, seed, train_args,
                program_variables=None, **reference_args):
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import (_update_core, init_train_state,
                                            make_optimizer)

    args = apply_defaults({'env_args': dict(config['env_args']),
                           'train_args': dict(train_args)})['train_args']
    cfg = LossConfig.from_args(args)
    batch, windows = seeded_batch(config, seed, train_args)
    start = variables if program_variables is None else program_variables

    # the program: one step of the very update the fused loop scans, on a
    # batch of the cell's size
    update = jax.jit(_update_core(module, cfg, make_optimizer()),
                     donate_argnums=(0,))
    state = init_train_state(jax.tree_util.tree_map(jnp.copy, start))
    state, metrics = update(state, jax.tree_util.tree_map(jnp.asarray, batch),
                            jnp.float32(LR))
    metrics = {k: float(v) for k, v in metrics.items()}
    change = jax.jit(lambda new, old: jax.tree_util.tree_map(
        jnp.subtract, new, old))(state.params, start)
    moment = [s for s in state.opt_state if hasattr(s, 'mu')][0].mu
    router_moved = max(float(jnp.abs(layer['router']).max())
                       for layer in change['params'].values()
                       if isinstance(layer, dict) and 'router' in layer)
    del state
    # to the host while the reference works: a layer's vector-Jacobian
    # product over 8,192 positions takes 5.7 GB beside the two trees of
    # the parameters' size it needs (my chip run, PR 43)
    change, moment = jax.device_get((change, moment))

    # the reference: numpy targets, then jax.vjp of the plain loss, summed
    # over the batch's windows
    ref = plain(config, reference_args)
    total, terms = 0.0, {}
    grads = jax.tree_util.tree_map(jnp.zeros_like, variables['params'])
    for window in windows:
        win = {k: jnp.asarray(v) for k, v in window.items()}
        out = ref.forward(variables, win['ids'], win['first_position'],
                          win['valid'] > 0)
        value_target, advantage = reference_loss.targets(
            {'logits': out['logits'], 'value': out['value']}, window,
            cfg.lmb)
        del out
        one, its_terms, grads = ref.loss_and_grad(
            variables, win, jnp.asarray(value_target, jnp.float32),
            jnp.asarray(advantage, jnp.float32),
            jnp.float32(cfg.entropy_regularization),
            jnp.float32(cfg.entropy_regularization_decay), grads)
        total += float(one)
        for k, v in its_terms.items():
            terms[k] = terms.get(k, 0.0) + float(v)
    grads = {'params': grads}
    norm = float(sum(float(_sq(g)) for g in
                     jax.tree_util.tree_leaves(grads))) ** 0.5
    sums = jax.jit(_leaf_sums)(change, moment, grads, variables,
                               jnp.float32(LR), jnp.float32(norm))
    del grads, change, moment
    leaves = {name: {k: float(v) for k, v in leaf.items()}
              for name, leaf in _leaves_by_name(sums).items()}
    # the router follows a rule of its own (it stays), held below
    adam = {n: leaf for n, leaf in leaves.items()
            if group_of(n.split('/')) != 'router'}
    small = [n for n in adam if adam[n]['small']]

    def rel(err, refkey, names=None):
        picked = [adam[n] for n in (adam if names is None else names)]
        return (sum(x[err] for x in picked)
                / max(sum(x[refkey] for x in picked), 1e-30)) ** 0.5
    groups = {g: [n for n in adam if group_of(n.split('/')) == g]
              for g in GROUPS}
    worst_grad = max(adam, key=lambda n: rel('grad_err', 'grad', [n]))
    worst_change = max(adam, key=lambda n: rel('change_err', 'change', [n]))
    return {
        'loss_rel_err': abs(metrics['total'] - total)
        / max(abs(total), 1e-9),
        'grad_norm_rel_err': abs(metrics['diag_grad_norm'] - norm)
        / max(norm, 1e-9),
        'grad_err_rel_to_grad': rel('grad_err', 'grad'),
        'grad_err_worst_leaf': rel('grad_err', 'grad', [worst_grad]),
        'change_err_rel_to_change': rel('change_err', 'change'),
        'change_err_worst_leaf': rel('change_err', 'change', [worst_change]),
        'small_grad_err_rel_to_grad': rel('grad_err', 'grad', small),
        'small_change_err_rel_to_change': rel('change_err', 'change', small),
        'small_moved_rel_to_change': rel('moved', 'change', small),
        'readout_change_err_rel_to_change': rel('change_err', 'change',
                                                groups['readout']),
        'router_moved_max_abs': router_moved,
        'rows_held_share': metrics['diag_moe_rows_held']
        / max(metrics['diag_moe_rows_routed'], 1.0),
        'rows_dropped': metrics['diag_moe_rows_dropped'],
        'positions_hidden_share': metrics['diag_window_positions_hidden']
        / max(metrics['diag_window_positions_valid'], 1.0),
        'worst_leaves': {'grad': worst_grad, 'change': worst_change},
        'small_leaves': len(small),
        'loss': metrics['total'], 'reference_loss': total,
        'terms': {k: [metrics.get(k), v] for k, v in terms.items()},
        'grad_norm': metrics['diag_grad_norm'], 'reference_grad_norm': norm,
        'grad_err_rel_by_group': {
            g: rel('grad_err', 'grad', groups[g]) for g in GROUPS
            if groups[g]},
        'change_err_rel_by_group': {
            g: rel('change_err', 'change', groups[g]) for g in GROUPS
            if groups[g]},
        'nonfinite': metrics['nonfinite'],
        'windows': len(windows),
        'positions': [int(w['valid'].sum()) for w in windows],
        'change_sign_flipped_share': sum(
            x['flipped'] for x in adam.values())
        / sum(x['size'] for x in adam.values()),
    }


def step_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = step_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'step_' + name)]
                for name in STEP_LIMITS]
    window = config['model']['window_size']
    hidden = sum(max(n - window, 0) for n in stats['positions'])
    compared += [
        ['router_moved_max_abs', stats['router_moved_max_abs'], '==', 0.0],
        ['rows_dropped', stats['rows_dropped'], '==', 0.0],
        ['nonfinite', stats['nonfinite'], '==', 0.0],
        ['windows', stats['windows'], '==', int(train_args['batch_size'])],
        # the counters the program's own step hands the host are the
        # batch's: the positions past the attention's window, of the valid
        ['positions_hidden_share', stats['positions_hidden_share'], '==',
         hidden / max(sum(stats['positions']), 1)]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)
