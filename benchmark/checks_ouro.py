"""The comparisons that decide ``correct`` for the ``ouro`` configuration: on
the chip, at published widths, on what the timed path runs, each against the
plain float32 reference (``reference/ouro.py``, ``reference/ouro_loss.py``:
no cache and no scan over the passes) at ``highest`` matmul precision on the
very weights the learner starts from.

``forward_check``   the learner's ``sequence`` (bfloat16 activations, ONE
                    scan over the passes) and its ``policy_logits`` over ONE
                    seeded window of ``forward_positions`` positions: EVERY
                    pass's logits, value and gate logit.
``rollout_check``   the actor's ``__call__`` through its (pass, layer) cache
                    (bfloat16 parameters, all passes a ply, the head on the
                    last), driven by the program's own ``rollout_chunk`` for
                    ``rollout_plies`` plies of ``rollout_envs`` games whose
                    FIRST lengths the check sets (``checks_trinity_mini.
                    first_lengths``): one lane plays the whole run, one ends
                    early, one late, one past the run's half. All plies, the
                    plies from the run's half on (``late_``: the fullest
                    buffers) and the plies of games begun after a reset
                    (``after_reset_``) are each held to limits.
``step_check``      one update of the program's own step on the cell's batch
                    (the legal set as bits): the exit-weighted loss, the
                    gradient's norm, the TIED gradient leaf by leaf from
                    Adam's first moment, the first Adam step, and the step's
                    own counters of the exit distribution.

``checks_trinity_mini``'s body takes its reference as ``ref``, but walks a
stack ONCE and reads a router's leaves: of it this file takes what names
neither (the seeded window and batch, the rollout's records, a leaf's sums,
the game sums ``checks_smallthinker`` keeps on the device) and writes the
walk over ``passes x layers`` applications itself (``_Looped``). Statistics
are RMS errors relative to the RMS of the reference; each limit sits in the
configuration's ``tolerance`` with the readings it was set from
(``tolerance_ouro.py`` reads them and the eight negative controls).
"""

import functools
import time

import numpy as np

from . import checks
from . import checks_trinity_mini as shared
from .checks_evabyte import (_adder, _count, _items, _leaves_by_name, _limit,
                             _verdict)
from .checks_smallthinker import device_game_sums
from .checks_trinity_mini import LR, _sq, seeded_batch, seeded_windows
from .reference import ouro as reference
from .reference import ouro_loss as reference_loss

__all__ = ['FORWARD_LIMITS', 'ROLLOUT_LIMITS', 'STEP_LIMITS', 'CONTROLS',
           'STEP_ONLY', 'FORWARD_ONLY', 'seeded_batch', 'seeded_windows']

GROUPS = ('attention', 'mlp', 'norms', 'embed', 'readout')
# the reference's side degraded: the program is compared with a model that
# differs from it by that part. The last five are this architecture's own
CONTROLS = {'one_layer_left_out': {'skip_layer': 1},
            'phases_left_out': {'use_rotary': False},
            'one_pass_left_out': {'passes': 3},
            'one_cache_for_all_passes': {'previous_pass_kv': True},
            'norm_between_passes_left_out': {'norm_between': False},
            'loss_of_the_last_pass_alone': {'last_pass_only': True},
            'first_passes_under_stop_gradient': {
                'stop_gradient_passes': True}}
# the two that change the loss or its gradient and no output, and the one
# whose gradient the block-by-block reference does not walk
STEP_ONLY = ('loss_of_the_last_pass_alone',
             'first_passes_under_stop_gradient')
FORWARD_ONLY = ('one_cache_for_all_passes',)


def reference_config(config):
    model = config['model']
    cfg = {key: model[key] for key in (
        'layers', 'passes', 'head_dim', 'rope_theta', 'norm_eps')}
    cfg['param_scale'] = model.get('param_scale', 1.0)
    return cfg


def group_of(path):
    """A parameter's group, by its name in the tree."""
    name = path[-1]
    if name in ('wq', 'wk', 'wv', 'wo'):
        return 'attention'
    if name in ('w_gate', 'w_up', 'w_down'):
        return 'mlp'
    if name.startswith('norm'):
        return 'norms'
    return 'embed' if name == 'embed' else 'readout'


class _Looped:
    """The reference as small programs, each jitted once a process: one
    layer at ``highest`` precision (the same program serves every layer of
    every pass), its vector-Jacobian product, ``N_out`` and its, the readout
    of all passes, and the exit-weighted loss's gradient at the readout.
    Everything a seed decides is an ARGUMENT."""

    def __init__(self, cfg, skip_layer=None, passes=None, use_rotary=True,
                 norm_between=True, previous_pass_kv=False,
                 last_pass_only=False, stop_gradient_passes=False):
        import jax
        self.cfg = cfg
        self.kept = [i for i in range(cfg['layers']) if i != skip_layer]
        self.passes = cfg['passes'] if passes is None else passes
        self.norm_between = norm_between
        self.previous_pass_kv = previous_pass_kv
        self.stop_gradient_passes = stop_gradient_passes

        def highest(fn):
            def wrapped(*args):
                with jax.default_matmul_precision('highest'):
                    return fn(*args)
            return jax.jit(wrapped)

        def layer(p_layer, x, positions, valid, kv=None):
            return reference.layer(p_layer, x, positions, valid, cfg,
                                   use_rotary, kv)
        self.layer = highest(layer)
        self.layer_vjp = highest(
            lambda p_layer, x, positions, valid, ct: jax.vjp(
                lambda p_, x_: layer(p_, x_, positions, valid)[0],
                p_layer, x)[1](ct))
        between = lambda g, u: reference.between(g, u, cfg)
        self.between = highest(between)
        self.between_vjp = highest(
            lambda g, u, ct: jax.vjp(between, g, u)[1](ct))
        self.readout = highest(lambda top, xs: reference.readout(top, xs, cfg))

        def head_loss(top, xs, win, value_target, advantage, coef, decay):
            return reference_loss.loss_of_outputs(
                reference.readout(top, xs, cfg), win, value_target,
                advantage, coef, decay, last_pass_only)
        self.head_grad = highest(jax.value_and_grad(
            head_loss, argnums=(0, 1), has_aux=True))
        self.embed_add = jax.jit(
            lambda g, ids, ct: g.at[ids].add(ct / cfg['param_scale']),
            donate_argnums=(0,))

    @staticmethod
    def top(variables):
        p = variables['params']
        return {k: p[k] for k in ('head', 'value', 'gate', 'gate_bias')}

    def hidden(self, variables, ids, first, valid):
        """A pass: the input of every kept layer and what left the last
        (before ``N_out``); and the passes' features, stacked."""
        import jax.numpy as jnp
        p = variables['params']
        positions = first + jnp.arange(ids.shape[0])
        x = reference.embed(p, ids, self.cfg)
        inputs, outs, features, held = [], [], [], {}
        for _t in range(self.passes):
            inputs.append([])
            for i in self.kept:
                inputs[-1].append(x)
                x, own = self.layer(
                    p['layer_%d' % i], x, positions, valid,
                    held.get(i) if self.previous_pass_kv else None)
                held[i] = own
            outs.append(x)
            features.append(self.between(p['norm_out'], x))
            if self.norm_between:
                x = features[-1]
        return positions, inputs, outs, jnp.stack(features)

    def forward(self, variables, ids, first, valid):
        return self.readout(self.top(variables),
                            self.hidden(variables, ids, first, valid)[3])

    def loss_and_grad(self, variables, win, value_target, advantage, coef,
                      decay, grads):
        """``jax.vjp`` of the reference loss, application by application from
        the last pass's last layer to the first pass's first, each piece
        ADDED to ``grads`` (a tree of the parameters' shapes, donated): a
        layer's leaf takes ``passes`` pieces, the tied gradient."""
        assert not self.previous_pass_kv, 'a forward control'
        valid = win['valid'] > 0
        positions, inputs, outs, xs = self.hidden(
            variables, win['ids'], win['first_position'], valid)
        (total, terms), (g_top, ct_xs) = self.head_grad(
            self.top(variables), xs, win, value_target, advantage, coef,
            decay)
        grads = dict(grads)
        for key, piece in g_top.items():
            grads[key] = _adder()(grads[key], piece)
        p = variables['params']
        ct_next = None      # what the next pass's first layer hands back
        for t in reversed(range(self.passes)):
            if self.stop_gradient_passes and t < self.passes - 1:
                return total, terms, grads
            ct = ct_xs[t]
            if ct_next is not None and self.norm_between:
                ct = ct + ct_next
            piece, ct = self.between_vjp(p['norm_out'], outs[t], ct)
            grads['norm_out'] = _adder()(grads['norm_out'], piece)
            if ct_next is not None and not self.norm_between:
                ct = ct + ct_next
            for i, x in reversed(list(zip(self.kept, inputs[t]))):
                name = 'layer_%d' % i
                piece, ct = self.layer_vjp(p[name], x, positions, valid, ct)
                grads[name] = _adder()(grads[name], piece)
            ct_next = ct
        grads['embed'] = self.embed_add(grads['embed'], win['ids'], ct_next)
        return total, terms, grads


@functools.lru_cache(maxsize=None)
def _plain(cfg_items, args_items=()):
    return _Looped(dict(cfg_items), **dict(args_items))


def plain(config, reference_args):
    return _plain(_items(reference_config(config)), _items(reference_args))


# -- forward -----------------------------------------------------------------
def program_sequence(module):
    """The learner's window forward with every pass's head taken whole:
    logits (passes, B, T, A), value and gate logit (passes, B, T)."""
    import jax

    def run(variables, ids, first, valid):
        out = module.apply(variables, ids, first, valid,
                           method=module.sequence)
        logits = module.apply(variables, out['policy_features'],
                              method=module.policy_logits)
        return logits, out['value'][..., 0], out['exit_gate'][..., 0]
    return jax.jit(run)


def forward_errors(config, module, variables, seed, program_variables=None,
                   **reference_args):
    """The RMS errors of the program's ``sequence`` against the reference
    over the seeded windows' valid positions, all passes together and the
    logits' pass by pass. ``program_variables`` and ``reference_args`` are
    the negative controls'; where one side runs fewer passes the LAST passes
    of both are compared (the actor plays from the last)."""
    import jax.numpy as jnp

    ids, first, valid = seeded_windows(
        config, seed, int(config['forward_windows']),
        int(config['forward_positions']))
    want_of = plain(config, reference_args).forward
    got = program_sequence(module)(
        variables if program_variables is None else program_variables,
        jnp.asarray(ids), jnp.asarray(first), jnp.asarray(valid))
    sums = {key: 0.0 for key in ('d_logit', 'logit', 'd_value', 'd_gate')}
    for w in range(ids.shape[0]):
        want = want_of(variables, jnp.asarray(ids[w]), jnp.asarray(first[w]),
                       jnp.asarray(valid[w]))
        n = min(want['logits'].shape[0], got[0].shape[0])
        logits, value, gate = (x[-n:, w] for x in got)
        want = {key: x[-n:] for key, x in want.items()}
        keep = jnp.asarray(valid[w])
        by_pass = lambda x: jnp.sum(jnp.square(x * keep[None, :, None]),
                                    axis=(1, 2))
        sums['d_logit'] = sums['d_logit'] + np.asarray(
            by_pass(logits - want['logits']), np.float64)
        sums['logit'] = sums['logit'] + np.asarray(
            by_pass(want['logits']), np.float64)
        sums['d_value'] += float(_sq((value - want['value']) * keep))
        sums['d_gate'] += float(_sq((gate - want['gate']) * keep))
    n = float(valid.sum()) * len(sums['logit'])
    logit_rms = (sums['logit'].sum() / (n * got[0].shape[-1])) ** 0.5
    return {'logits_rms_rel_to_logit_rms':
            (sums['d_logit'].sum() / sums['logit'].sum()) ** 0.5,
            'value_rms': (sums['d_value'] / n) ** 0.5,
            'gate_rms': (sums['d_gate'] / n) ** 0.5,
            'logits_rms_rel_by_pass': [
                float(x) for x in (sums['d_logit'] / sums['logit']) ** 0.5],
            'passes': int(got[0].shape[0]),
            'logit_rms': logit_rms, 'positions': int(valid.sum())}


FORWARD_LIMITS = (('logits_rms_rel_to_logit_rms', '<='), ('value_rms', '<='),
                  ('gate_rms', '<='))


def forward_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = forward_errors(config, module, variables, seed)
    n_params = _count(variables)
    compared = [['parameters', n_params, '==', config['model']['parameters']],
                ['passes', stats['passes'], '==', config['model']['passes']]]
    compared += [[name, stats[name], op, _limit(config, 'forward_' + name)]
                 for name, op in FORWARD_LIMITS]
    return _verdict(compared, n_params, **stats,
                    seconds=time.perf_counter() - began)


# -- rollout through the cache -----------------------------------------------
def _with_half(config):
    """The configuration as ``checks_trinity_mini.first_lengths`` reads it:
    the position past which its fourth lane ends is the run's half (this net
    has no attention window)."""
    return dict(config, model=dict(
        config['model'], window_size=int(config['rollout_plies']) // 2))


def rollout_records(config, module, variables, seed, train_args,
                    actor_dtype=None):
    return shared.rollout_records(_with_half(config), module, variables,
                                  seed, train_args, actor_dtype)


def rollout_compare(config, records, variables, **reference_args):
    """Every ply's policy logits and value (the LAST pass's: what the actor
    played) against the reference's full forward over each game's ids
    (``variables``: float32, what the actor's copy was cast from), over all
    plies and over two parts of them: the plies from the run's half on
    (``late_``) and the plies of games that began after a reset
    (``after_reset_``)."""
    import jax.numpy as jnp
    obs, out, done = records['obs'], records['out'], records['done']
    want_of = plain(config, reference_args).forward
    half = int(config['rollout_plies']) // 2
    block = int(config['forward_positions'])
    names = {'': '', 'wrapped_': 'late_', 'after_reset_': 'after_reset_'}
    parts = {name: dict.fromkeys(('d_logit', 'd_value', 'logit', 'plies'),
                                 0.0) for name in names}
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
                sums = device_game_sums(
                    out[a:b, n, seat],
                    {'logits': want['logits'][-1], 'value': want['value'][-1]},
                    half, a > 0)
                for name, part in parts.items():
                    for key in part:
                        part[key] += float(sums[name][key])
    stats = {'plies': int(len(done)), 'games': games,
             'sequences': int(obs.shape[1] * obs.shape[2]),
             'resets': int(done.sum()),
             'distinct_counters': max(len(set(row)) for row in counter)}
    ids_held = out.shape[-1] - 1
    logit_rms = (parts['']['logit'] / (parts['']['plies'] * ids_held)) ** 0.5
    for name, part in parts.items():
        n, name = max(part['plies'], 1), names[name]
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


ROLLOUT_LIMITS = ('logits_rms_rel_to_logit_rms', 'value_rms',
                  'late_logits_rms_rel_to_logit_rms', 'late_value_rms',
                  'after_reset_logits_rms_rel_to_logit_rms',
                  'after_reset_value_rms')


def rollout_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = rollout_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'rollout_' + name)]
                for name in ROLLOUT_LIMITS]
    lanes = int(config['rollout_envs'])
    compared += [
        ['resets', stats['resets'], '>=', min(lanes, 4) - 1],
        ['distinct_counters', stats['distinct_counters'], '>=',
         min(lanes, 4)],
        ['late_plies', stats['late_plies'], '>=', 2],
        ['after_reset_plies', stats['after_reset_plies'], '>=', 2]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)


# -- one update ----------------------------------------------------------------
def step_errors(config, module, variables, seed, train_args,
                program_variables=None, **reference_args):
    """One update of the program's own step against the reference's TIED
    gradient (each layer's leaf the sum of its ``passes`` uses) and first
    Adam step, and the step's counters of the exit distribution against the
    reference's."""
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
    del state

    # the reference: numpy targets from the last pass, then jax.vjp of the
    # plain loss, summed over the batch's windows
    plain_ref = plain(config, reference_args)
    total, terms = 0.0, {}
    grads = jax.tree_util.tree_map(jnp.zeros_like, variables['params'])
    for window in windows:
        win = {k: jnp.asarray(v) for k, v in window.items()}
        out = plain_ref.forward(variables, win['ids'], win['first_position'],
                                win['valid'] > 0)
        value_target, advantage = reference_loss.targets(out, window, cfg.lmb)
        del out
        one, its_terms, grads = plain_ref.loss_and_grad(
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
    sums = jax.jit(functools.partial(shared._leaf_sums, reference_loss))(
        change, moment, grads, variables, jnp.float32(LR), jnp.float32(norm))
    del grads, change, moment
    leaves = {name: {k: float(v) for k, v in leaf.items()}
              for name, leaf in _leaves_by_name(sums).items()}
    small = [n for n in leaves if leaves[n]['small']]

    def rel(err, refkey, names=None):
        picked = [leaves[n] for n in (leaves if names is None else names)]
        return (sum(x[err] for x in picked)
                / max(sum(x[refkey] for x in picked), 1e-30)) ** 0.5
    groups = {g: [n for n in leaves if group_of(n.split('/')) == g]
              for g in GROUPS}
    worst_grad = max(leaves, key=lambda n: rel('grad_err', 'grad', [n]))
    worst_change = max(leaves, key=lambda n: rel('change_err', 'change', [n]))
    positions = [int(w['valid'].sum()) for w in windows]
    # the total is a sum of terms of both signs that passes near zero on
    # some seeds (206 against 180 beside an entropy term of 6,000: my chip
    # run, PR 46), so its error is taken relative to the terms' sizes
    loss_scale = (abs(terms['p']) + abs(terms['v'])
                  + abs(total - terms['p'] - terms['v']))
    return {
        'loss_rel_err': abs(metrics['total'] - total) / max(loss_scale, 1e-9),
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
        'exit_entropy_rel_err': abs(metrics['diag_exit_entropy_nats']
                                    - terms['exit_ent'])
        / max(abs(terms['exit_ent']), 1e-9),
        'exit_mass': sum(metrics['diag_exit_mass_pass_%d' % (t + 1)]
                         for t in range(config['model']['passes']))
        / max(sum(positions), 1),
        'positions_valid': metrics['diag_window_positions_valid'],
        'worst_leaves': {'grad': worst_grad, 'change': worst_change},
        'small_leaves': len(small),
        'loss': metrics['total'], 'reference_loss': total,
        'terms': {k: [metrics.get(k), v] for k, v in terms.items()},
        'grad_norm': metrics['diag_grad_norm'], 'reference_grad_norm': norm,
        'grad_err_rel_by_group': {
            g: rel('grad_err', 'grad', groups[g]) for g in GROUPS},
        'change_err_rel_by_group': {
            g: rel('change_err', 'change', groups[g]) for g in GROUPS},
        'nonfinite': metrics['nonfinite'],
        'windows': len(windows), 'positions': positions,
        'change_sign_flipped_share': sum(
            x['flipped'] for x in leaves.values())
        / sum(x['size'] for x in leaves.values()),
    }


# as ``checks_smallthinker``: beside the limits every trunk's step check
# holds, the change of the readout alone, and this net's own counter: the
# exit distribution's entropy summed over the batch's valid positions
STEP_LIMITS = shared.STEP_LIMITS + ('readout_change_err_rel_to_change',
                                    'exit_entropy_rel_err')


def step_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = step_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'step_' + name)]
                for name in STEP_LIMITS]
    compared += [
        # the exit distribution is one: its passes' masses add up to the
        # valid positions the step counted, which are the batch's
        ['exit_mass_off_one', abs(stats['exit_mass'] - 1.0), '<=', 1e-3],
        ['positions_valid', stats['positions_valid'], '==',
         float(sum(stats['positions']))],
        ['nonfinite', stats['nonfinite'], '==', 0.0],
        ['windows', stats['windows'], '==', int(train_args['batch_size'])]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)
