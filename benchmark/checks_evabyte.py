"""The comparisons that decide ``correct`` for the ``evabyte`` configuration:
on the chip, at published widths, on what the timed path runs, each against
the plain float32 reference (``reference/evabyte.py``,
``reference/evabyte_loss.py``) at ``highest`` matmul precision on the very
weights the learner starts from.

``forward_check``   the learner's ``sequence`` (bfloat16 activations) over
                    seeded windows of ``forward_positions`` positions that
                    start anywhere in a game: all eight heads' logits and
                    the value.
``rollout_check``   the actor's ``__call__`` through its cache (bfloat16
                    parameters, as ``actor_refresh`` casts them), driven by
                    the program's own ``rollout_chunk`` for ``rollout_plies``
                    plies of ``rollout_envs`` games whose FIRST lengths the
                    check sets (``first_lengths``), so that the lanes'
                    counters differ from the first game's end on, three
                    games end inside the run (a reset of counters over a
                    cache that keeps its rows), one lane crosses a window
                    boundary and plays on, and one crosses it, reads
                    summaries and is reset after that. The policy's logits
                    and the values of every ply against the reference's full
                    forward over the ids each game produced; the plies that
                    read summaries and the plies after a reset are held to
                    limits of their own, beside all of them together.
``step_check``      one update of the program's own step on ``batch_size``
                    seeded windows of different lengths (the timed batch):
                    the loss, the gradient's norm, the gradient leaf by leaf
                    (Adam's first moment after one step IS the gradient that
                    reached it, times 1 - b1), and the parameters' change
                    leaf by leaf, against ``jax.grad`` of the reference loss
                    summed over the windows and a plain first Adam step.

Statistics are RMS errors (PERF.md section 2: a maximum over thousands of
terms doubles from seed to seed); each limit sits in the configuration's
``tolerance`` with the readings it was set from. ``tolerance_evabyte.py``
runs the same functions with one side degraded: the negative controls the
limits are held against (a builder's chip run, not part of a measured run).
"""

import functools

import numpy as np

from . import checks
from .reference import evabyte as reference
from .reference import evabyte_loss as reference_loss

GROUPS = ('attention', 'mlp', 'norms', 'embed', 'readout')
LR = 1e-3     # the step check's learning rate: any value, both sides use it


def reference_config(config):
    model = config['model']
    return {key: model[key] for key in (
        'layers', 'head_dim', 'chunk_size', 'window_size', 'rope_theta',
        'norm_eps')}


class _Plain:
    """The reference as four small programs, each jitted once a process:
    ``layer`` (one block at ``highest`` precision; the same program serves
    every layer and every sequence of its length), its vector-Jacobian
    product, the readout, and the loss's gradient at the readout. A whole
    forward or backward in ONE program is a compiled entry of over 100 MB at
    these widths (unrolled six-pass float32 products), more than the chip
    machine's compile cache keeps. Everything a seed decides is an ARGUMENT:
    as a constant it would make every seed a new program to compile."""

    def __init__(self, cfg, skip_layer=None, use_remote=True):
        import jax
        self.cfg, self.skip_layer = cfg, skip_layer

        def highest(fn):
            def wrapped(*args):
                with jax.default_matmul_precision('highest'):
                    return fn(*args)
            return jax.jit(wrapped)

        def layer(p_layer, x, positions, valid):
            return reference.layer(p_layer, x, positions, valid, cfg,
                                   use_remote)
        self.layer = highest(layer)
        self.layer_vjp = highest(
            lambda p_layer, x, positions, valid, ct: jax.vjp(
                lambda p_, x_: layer(p_, x_, positions, valid),
                p_layer, x)[1](ct))
        self.readout = highest(lambda top, x: reference.readout(top, x, cfg))

        def head_loss(top, x, win, value_target, advantage, coef, decay):
            return reference_loss.loss_of_outputs(
                reference.readout(top, x, cfg), win, value_target, advantage,
                coef, decay)
        self.head_grad = highest(jax.value_and_grad(
            head_loss, argnums=(0, 1), has_aux=True))
        self.embed_add = jax.jit(lambda g, ids, ct: g.at[ids].add(ct),
                                 donate_argnums=(0,))

    def layers(self, variables):
        p = variables['params']
        return [p['layer_%d' % i] for i in range(self.cfg['layers'])
                if i != self.skip_layer]

    @staticmethod
    def top(variables):
        p = variables['params']
        return {k: p[k] for k in ('norm_out', 'heads', 'value')}

    def hidden(self, variables, ids, first, valid):
        """The input of every layer and the last one's output."""
        import jax.numpy as jnp
        positions = first + jnp.arange(ids.shape[0])
        xs = [reference.embed(variables['params'], ids)]
        for p_layer in self.layers(variables):
            xs.append(self.layer(p_layer, xs[-1], positions, valid))
        return positions, xs

    def forward(self, variables, ids, first, valid):
        _positions, xs = self.hidden(variables, ids, first, valid)
        return self.readout(self.top(variables), xs[-1])

    def loss_and_grad(self, variables, win, value_target, advantage, coef,
                      decay, grads):
        """``jax.grad`` of the reference loss, block by block: the loss's
        gradient at the readout, then each layer's vector-Jacobian product
        from the last to the first, then the embedding's rows. Each piece is
        ADDED to ``grads`` (a tree of the parameters' shapes, donated) as it
        comes, so a batch's gradient is summed window by window with one
        layer's gradient in flight."""
        valid = win['valid'] > 0
        positions, xs = self.hidden(variables, win['ids'],
                                    win['first_position'], valid)
        (total, terms), (g_top, ct) = self.head_grad(
            self.top(variables), xs[-1], win, value_target, advantage,
            coef, decay)
        grads = dict(grads)
        for key, piece in g_top.items():
            grads[key] = _adder()(grads[key], piece)
        kept = [i for i in range(self.cfg['layers']) if i != self.skip_layer]
        for i, p_layer, x in reversed(list(zip(
                kept, self.layers(variables), xs[:-1]))):
            piece, ct = self.layer_vjp(p_layer, x, positions, valid, ct)
            grads['layer_%d' % i] = _adder()(grads['layer_%d' % i], piece)
        grads['embed'] = self.embed_add(grads['embed'], win['ids'], ct)
        return total, terms, grads


@functools.lru_cache(maxsize=None)
def _adder():
    """``a + b`` leaf by leaf, into ``a``'s buffers."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _plain(cfg_items, args_items=()):
    return _Plain(dict(cfg_items), **dict(args_items))


def _items(mapping):
    return tuple(sorted(mapping.items()))


def _rms(x):
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


def _count(variables):
    import jax
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(variables))


def _verdict(compared, n_params, **extra):
    ops = {'<=': lambda a, b: a <= b, '>=': lambda a, b: a >= b,
           '==': lambda a, b: a == b}
    ok = all(np.isfinite(value) and ops[op](value, limit)
             for _what, value, op, limit in compared)
    return dict(extra, parameters=n_params, compared=compared, ok=bool(ok))


def _limit(config, name):
    """A limit of the configuration's ``tolerance``; a number that has none
    yet is reported and holds none (a first chip run)."""
    return config.get('tolerance', {}).get(name, float('inf'))


# -- forward -----------------------------------------------------------------
def seeded_windows(config, seed, n, positions):
    """ids (n, T), first positions (n,), valid (n, T): windows that start
    anywhere in a game of up to ``max_steps`` plies; every other one ends
    inside its game (padding follows), as a trained window may."""
    rng = np.random.default_rng(seed)
    model = config['model']
    ids = rng.integers(0, model['vocab'], (n, positions)).astype(np.int32)
    first = rng.integers(0, model['max_positions'] - positions + 1,
                         (n,)).astype(np.int32)
    length = np.where(np.arange(n) % 2 == 1,
                      rng.integers(positions // 2, positions, (n,)),
                      positions)
    return ids, first, np.arange(positions)[None, :] < length[:, None]


def forward_errors(config, module, variables, seed, program_variables=None,
                   **reference_args):
    """The RMS errors of the program's ``sequence`` against the reference
    over the seeded windows' valid positions. ``program_variables`` and
    ``reference_args`` are the negative controls'."""
    import jax
    import jax.numpy as jnp

    ids, first, valid = seeded_windows(
        config, seed, int(config['forward_windows']),
        int(config['forward_positions']))
    plain = _plain(_items(reference_config(config)),
                   _items(reference_args)).forward
    program = jax.jit(lambda v, i, f, m: module.apply(
        v, i, f, m, method=module.sequence))
    got = program(variables if program_variables is None
                  else program_variables, jnp.asarray(ids),
                  jnp.asarray(first), jnp.asarray(valid))
    got_logits = np.concatenate(
        [np.asarray(got['policy'], np.float32)[:, :, None],
         np.asarray(got['heads'], np.float32)], axis=2)
    got_value = np.asarray(got['value'], np.float32)[..., 0]
    d_logit, d_value, ref_logit = [], [], []
    for w in range(ids.shape[0]):
        want = plain(variables, jnp.asarray(ids[w]), jnp.asarray(first[w]),
                     jnp.asarray(valid[w]))
        keep = valid[w]
        logits = np.asarray(want['logits'], np.float32)[keep]
        ref_logit.append(logits)
        d_logit.append(got_logits[w][keep] - logits)
        d_value.append(got_value[w][keep]
                       - np.asarray(want['value'], np.float32)[keep])
    logit_rms = _rms(np.concatenate(ref_logit))
    return {'logits_rms_rel_to_logit_rms':
            _rms(np.concatenate(d_logit)) / max(logit_rms, 1e-9),
            'value_rms': _rms(np.concatenate(d_value)),
            'logit_rms': logit_rms,
            'positions': int(valid.sum())}


def forward_check(config, variables, seed, train_args):
    module = checks.build_module(config, train_args)
    stats = forward_errors(config, module, variables, seed)
    n_params = _count(variables)
    compared = [['parameters', n_params, '==', config['model']['parameters']]]
    compared += [[name, stats[name], '<=', _limit(config, 'forward_' + name)]
                 for name in ('logits_rms_rel_to_logit_rms', 'value_rms')]
    return _verdict(compared, n_params, **stats)


# -- rollout through the cache -----------------------------------------------
def first_lengths(config, seed, plies):
    """The FIRST length of each of the check's games, one band a lane (a
    lane beyond the fourth takes its band again): the whole run and no
    reset, across a window boundary; an early end; a late end; and one that
    crosses a window boundary, reads summaries for a chunk or more and ends
    after that. A game that follows a reset takes the length the env draws,
    which is past the run's end. So from the first end on no two bands hold
    the same counter, and a reset is part of every run, whatever the seed."""
    model = config['model']
    window, chunk = model['window_size'], model['chunk_size']
    assert window + 2 * chunk < plies <= model['max_positions']
    bands = [(model['max_positions'], model['max_positions'] + 1),
             (plies // 8, plies // 4),
             (plies // 2, 3 * plies // 4),
             (window + chunk, plies - max(1, plies // 16))]
    rng = np.random.default_rng(seed + 2)
    return np.asarray([rng.integers(*bands[n % len(bands)])
                       for n in range(int(config['rollout_envs']))], np.int32)


def rollout_records(config, module, variables, seed, train_args,
                    actor_dtype=None):
    """Drive the program's ``rollout_chunk`` over ``rollout_envs`` games and
    keep, a ply, lane and seat: the id observed, the value and the policy's
    logits (the records carry the value; the logits ride beside it, the one
    wrapper here: the probe's ``value`` is ``[value, logits]``) and whether
    the game ended."""
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.device_generation import make_gen_body
    from handyrl_tpu.environment import make_jax_env

    env_mod = make_jax_env(config['env_args'])
    n_envs = int(config['rollout_envs'])
    chunk = int(train_args['device_chunk_steps'])
    chunks = -(-int(config['rollout_plies']) // chunk)
    dtype = actor_dtype or getattr(module, 'actor_param_dtype', None)
    actor = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if dtype else x, p))(variables)

    def probe(params, obs, hidden):
        out = dict(module.apply(params, obs, hidden))
        out['value'] = jnp.concatenate(
            [out['value'].astype(jnp.float32), out['policy']], axis=-1)
        return out
    rollout_chunk = make_gen_body(env_mod, probe, True, True, module=module)
    run = jax.jit(lambda p, s, h, r: rollout_chunk(p, s, h, r, chunk),
                  donate_argnums=(2,))
    state = env_mod.init_state(n_envs, seed)
    state = state._replace(length=jnp.asarray(
        first_lengths(config, seed, chunks * chunk)))
    hidden = module.init_hidden((n_envs, env_mod.NUM_PLAYERS))
    rng = jax.random.PRNGKey(seed)
    obs, out, done = [], [], []
    for _ in range(chunks):
        state, hidden, rng, rec = run(actor, state, hidden, rng)
        obs.append(np.asarray(rec['obs']))
        out.append(np.asarray(rec['value'], np.float32))
        done.append(np.asarray(rec['done']))
    return {'obs': np.concatenate(obs), 'out': np.concatenate(out),
            'done': np.concatenate(done)}


def rollout_compare(config, records, variables, **reference_args):
    """Every ply's policy logits and value against the reference's full
    forward over each game's ids (``variables``: float32, what the actor's
    copy was cast from), over all plies and over two parts of them: the
    plies at positions from ``window_size`` on, which read summaries
    (``remote_``), and the plies of games that began after a reset
    (``after_reset_``)."""
    import jax.numpy as jnp
    obs, out, done = records['obs'], records['out'], records['done']
    plain = _plain(_items(reference_config(config)),
                   _items(reference_args)).forward
    window = config['model']['window_size']
    # one length for every game: the forward check's, so that the same
    # compiled layer serves both (causal: the padded tail is unseen)
    block = int(config['forward_positions'])
    parts = {name: {'d_logit': [], 'd_value': [], 'ref': []}
             for name in ('', 'remote_', 'after_reset_')}
    games = 0
    # each lane's position counter at every ply: plies since its last reset
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
                want = plain(variables, jnp.asarray(ids), jnp.int32(0),
                             jnp.ones(ids.shape, bool))
                logits = np.asarray(want['logits'], np.float32)[:b - a, 0]
                d_logit = out[a:b, n, seat, 1:] - logits
                d_value = out[a:b, n, seat, 0] - np.asarray(
                    want['value'], np.float32)[:b - a]
                for name, keep in (('', slice(None)),
                                   ('remote_', slice(window, None)),
                                   ('after_reset_',
                                    slice(None) if a else slice(0, 0))):
                    parts[name]['ref'].append(logits[keep])
                    parts[name]['d_logit'].append(d_logit[keep])
                    parts[name]['d_value'].append(d_value[keep])
    stats = {'plies': int(len(done)), 'games': games,
             'sequences': int(obs.shape[1] * obs.shape[2]),
             'resets': int(done.sum()),
             'distinct_counters': max(len(set(row)) for row in counter)}
    logit_rms = _rms(np.concatenate(parts['']['ref']))
    for name, part in parts.items():
        stats[name + 'plies' if name else 'compared_plies'] = int(
            sum(len(d) for d in part['d_value']))
        stats[name + 'logits_rms_rel_to_logit_rms'] = _rms(
            np.concatenate(part['d_logit'])) / max(logit_rms, 1e-9)
        stats[name + 'value_rms'] = _rms(np.concatenate(part['d_value']))
    stats['logit_rms'] = logit_rms
    return stats


def rollout_errors(config, module, variables, seed, train_args,
                   actor_dtype=None, reference_variables=None,
                   **reference_args):
    """``reference_variables`` gives the reference other weights than the
    actor's (a negative control degrades ``variables``)."""
    records = rollout_records(config, module, variables, seed, train_args,
                              actor_dtype)
    return rollout_compare(
        config, records,
        variables if reference_variables is None else reference_variables,
        **reference_args)


ROLLOUT_LIMITS = ('logits_rms_rel_to_logit_rms', 'value_rms',
                  'remote_logits_rms_rel_to_logit_rms', 'remote_value_rms',
                  'after_reset_logits_rms_rel_to_logit_rms',
                  'after_reset_value_rms')


def rollout_check(config, variables, seed, train_args):
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
        ['remote_plies', stats['remote_plies'], '>=',
         2 * config['model']['chunk_size']]]
    return _verdict(compared, _count(variables), **stats)


# -- one update ----------------------------------------------------------------
def group_of(path):
    """A parameter's group, by its name in the tree."""
    name = path[-1]
    if name in ('wq', 'wk', 'wv', 'wo', 'mu', 'phi'):
        return 'attention'
    if name in ('w_gate', 'w_up', 'w_down'):
        return 'mlp'
    if name.startswith('norm'):
        return 'norms'
    return 'embed' if name == 'embed' else 'readout'


def _by_group(tree, reduce):
    """``reduce`` (leaf -> float, summed) over the leaves of each group."""
    import jax
    sums = dict.fromkeys(GROUPS, 0.0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = tuple(getattr(k, 'key', str(k)) for k in path)
        sums[group_of(keys)] += float(reduce(leaf))
    return sums


def seeded_batch(config, seed, train_args):
    """``batch_size`` solo-layout windows of the byte game as the windower
    stores them, from seeded ids, actions and behaviour probabilities, and
    of DIFFERENT lengths: the first ends inside its window (padding, the
    value's tail and every mask are exercised), the next fills it, and so
    on by turns. Returns the batch and the same windows one by one as the
    reference reads them."""
    rng = np.random.default_rng(seed + 1)
    model = config['model']
    T = int(train_args['forward_steps'])
    A = model['vocab']
    f = np.float32
    rows, windows = [], []
    for b in range(int(train_args['batch_size'])):
        length = int(rng.integers(T // 2, T)) if b % 2 == 0 else T
        valid = (np.arange(T) < length).astype(f)
        first = int(rng.integers(0, model['max_positions'] - T + 1))
        ids = rng.integers(0, A, (T,)).astype(np.int32) * (valid > 0)
        legal = np.ones((T, A), bool)
        legal[1:, 256:] = False       # the further ids: the first ply only
        action = rng.integers(0, 256, (T,)).astype(np.int32) * (valid > 0)
        prob = np.where(valid > 0, rng.uniform(0.001, 0.02, (T,)), 1.0)
        amask = np.where(legal & (valid[:, None] > 0), 0.0, 1e32)
        outcome = float(rng.choice([-1.0, 1.0]))
        progress = np.where(
            valid > 0, (first + np.arange(T)) / (first + length), 1.0)
        value = np.where(valid > 0, rng.uniform(-0.1, 0.1, (T,)), outcome)
        col = lambda x: np.asarray(x)[:, None, None]
        rows.append({
            'observation': ids[:, None],
            'selected_prob': col(prob).astype(f), 'action': col(action),
            'action_mask': amask.astype(f)[:, None, :],
            'value': col(value).astype(f),
            'reward': np.zeros((T, 1, 1), f), 'return': np.zeros((T, 1, 1), f),
            'outcome': np.full((1, 1, 1), outcome, f),
            'episode_mask': col(valid), 'turn_mask': col(valid),
            'observation_mask': col(valid),
            'progress': progress.astype(f)[:, None],
            'first_position': np.full((1, 1, 1), first, np.int32),
        })
        windows.append({
            'ids': ids, 'first_position': first, 'valid': valid,
            'action': action, 'selected_prob': prob.astype(f),
            'action_mask': amask.astype(f), 'outcome': outcome,
            'progress': progress.astype(f)})
    batch = {key: np.stack([row[key] for row in rows]) for key in rows[0]}
    return batch, windows


ADAM_B1 = 0.9   # optax.scale_by_adam's default, which make_optimizer takes


def plain_first_adam_step(g, p, lr, norm):
    """The program's optimizer on its first step, written out for one leaf:
    clip the gradient's global norm (``norm``) to 4, add 1e-5 of the
    parameter, Adam with zero moments (the bias-corrected first step is
    g / (|g| + 1e-8)), times -lr. Returns what reached Adam and the
    parameter's change."""
    import jax.numpy as jnp
    g = g * jnp.minimum(1.0, 4.0 / norm) + 1e-5 * p
    return g, -lr * g / (jnp.abs(g) + 1e-8)


def _leaf_sums(change, moment, grads, params, lr, norm):
    """A leaf: the squared error and the squared size of the gradient (the
    program's, read from Adam's first moment) and of the change, and how
    many elements moved the other way. Scalars only leave the program: no
    tree the parameters' size is made beside the four that come in."""
    import jax
    import jax.numpy as jnp
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))

    def one(c, m, g, p):
        want_grad, want_change = plain_first_adam_step(g, p, lr, norm)
        got_grad = m.astype(jnp.float32) / (1 - ADAM_B1)
        return {'grad_err': sq(got_grad - want_grad), 'grad': sq(want_grad),
                'change_err': sq(c - want_change), 'change': sq(want_change),
                'moved': sq(c), 'flipped': jnp.sum(c * want_change < 0)}
    return jax.tree_util.tree_map(one, change, moment, grads, params)


def _leaves_by_name(tree):
    import jax
    return {'/'.join(getattr(k, 'key', str(k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                tree, is_leaf=lambda x: isinstance(x, dict) and 'grad' in x
            )[0]}


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
    del state

    # the reference: numpy targets, then jax.grad of the plain loss, summed
    # over the batch's windows; a window and its targets are arguments (a
    # seed never makes a program)
    ref_cfg = reference_config(config)
    plain = _plain(_items(ref_cfg), _items(reference_args))
    total, terms = 0.0, {}
    grads = jax.tree_util.tree_map(jnp.zeros_like, variables['params'])
    for window in windows:
        win = {k: jnp.asarray(v) for k, v in window.items()}
        value_target, advantage = reference_loss.targets(
            plain.forward(variables, win['ids'], win['first_position'],
                          win['valid'] > 0), window, cfg.lmb)
        one, its_terms, grads = plain.loss_and_grad(
            variables, win, jnp.asarray(value_target, jnp.float32),
            jnp.asarray(advantage, jnp.float32),
            jnp.float32(cfg.entropy_regularization),
            jnp.float32(cfg.entropy_regularization_decay), grads)
        total += float(one)
        for k, v in its_terms.items():
            terms[k] = terms.get(k, 0.0) + float(v)
    grads = {'params': grads}
    sq = lambda g: jnp.sum(jnp.square(g.astype(jnp.float32)))
    grad_norm = _by_group(grads, sq)
    norm = sum(grad_norm.values()) ** 0.5
    sums = jax.jit(_leaf_sums)(change, moment, grads, variables,
                               jnp.float32(LR), jnp.float32(norm))
    del grads, change, moment
    leaves = {name: {k: float(v) for k, v in leaf.items()}
              for name, leaf in _leaves_by_name(sums).items()}

    def rel(err, ref, names=None):
        picked = [leaves[n] for n in (names or leaves)]
        return (sum(x[err] for x in picked)
                / max(sum(x[ref] for x in picked), 1e-30)) ** 0.5
    groups = {g: [n for n in leaves if group_of(n.split('/')) == g]
              for g in GROUPS}
    worst_grad = max(leaves, key=lambda n: rel('grad_err', 'grad', [n]))
    worst_change = max(leaves, key=lambda n: rel('change_err', 'change', [n]))
    # a first Adam step is lr x sign(g) wherever |g| >> 1e-8: an element
    # whose gradient's rounding error exceeds the gradient moves the other
    # way, a distance of 2 lr. The share of such elements explains the
    # change's error (err^2 ~ 4 x share) and is reported beside it
    stats = {
        'loss_rel_err': abs(metrics['total'] - total)
        / max(abs(total), 1e-9),
        'grad_norm_rel_err': abs(metrics['diag_grad_norm'] - norm)
        / max(norm, 1e-9),
        'grad_err_rel_to_grad': rel('grad_err', 'grad'),
        'grad_err_worst_leaf': rel('grad_err', 'grad', [worst_grad]),
        'change_err_rel_to_change': rel('change_err', 'change'),
        'change_err_worst_leaf': rel('change_err', 'change', [worst_change]),
        'worst_leaves': {'grad': worst_grad, 'change': worst_change},
        'loss': metrics['total'], 'reference_loss': total,
        'terms': {k: [metrics.get(k), v] for k, v in terms.items()},
        'grad_norm': metrics['diag_grad_norm'],
        'reference_grad_norm_by_group': {
            g: v ** 0.5 for g, v in grad_norm.items()},
        'grad_err_rel_by_group': {
            g: rel('grad_err', 'grad', groups[g]) for g in GROUPS},
        'change_err_rel_by_group': {
            g: rel('change_err', 'change', groups[g]) for g in GROUPS},
        'change_norm_by_group': {
            g: sum(leaves[n]['moved'] for n in groups[g]) ** 0.5
            for g in GROUPS},
        'nonfinite': metrics['nonfinite'],
        'windows': len(windows),
        'positions': [int(w['valid'].sum()) for w in windows],
        'change_sign_flipped_share': sum(
            x['flipped'] for x in leaves.values()) / _count(variables),
    }
    return stats


STEP_LIMITS = ('loss_rel_err', 'grad_norm_rel_err', 'grad_err_rel_to_grad',
               'grad_err_worst_leaf', 'change_err_rel_to_change',
               'change_err_worst_leaf')


def step_check(config, variables, seed, train_args):
    module = checks.build_module(config, train_args)
    stats = step_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'step_' + name)]
                for name in STEP_LIMITS]
    compared.append(['nonfinite', stats['nonfinite'], '==', 0.0])
    compared.append(['windows', stats['windows'], '==',
                     int(train_args['batch_size'])])
    return _verdict(compared, _count(variables), **stats)
