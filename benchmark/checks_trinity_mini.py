"""The comparisons that decide ``correct`` for the ``trinity_mini``
configuration: on the chip, at published widths, on what the timed path
runs, each against the plain float32 reference (``reference/trinity_mini.py``,
``reference/trinity_mini_loss.py``) at ``highest`` matmul precision on the
very weights the learner starts from.

``forward_check``   the learner's ``sequence`` (bfloat16 activations, the
                    grouped expert product) and its ``policy_logits`` over
                    seeded windows of ``forward_positions`` positions that
                    start anywhere in a game: logits, value, and
                    ``routing_agreement_share``, the share of the router's
                    (position, choice) pairs that are the reference's.
``rollout_check``   the actor's ``__call__`` through its cache (bfloat16
                    parameters, every held expert on the ply's few rows),
                    driven by the program's own ``rollout_chunk`` for
                    ``rollout_plies`` plies of ``rollout_envs`` games whose
                    FIRST lengths the check sets (``first_lengths``): one
                    lane plays the whole run (its circle of ``window_size``
                    rows goes round and its full layer outgrows it), one
                    ends early, one late, one past position ``window_size``.
                    All plies, the plies at positions from ``window_size``
                    on (``wrapped_``) and the plies of games begun after a
                    reset (``after_reset_``) are each held to limits.
``step_check``      one update of the program's own step on ``batch_size``
                    seeded windows of different lengths with the legal set
                    as bits (the timed batch): the loss, the gradient's
                    norm, the gradient and the change leaf by leaf (the
                    small leaves, the norms' weights and the value row, held
                    to limits of their own), ``W_r`` unchanged, and ``b``
                    after the step against the reference's rule.

Statistics are RMS errors relative to the RMS of the reference; each limit
sits in the configuration's ``tolerance`` with the readings it was set from
(``tolerance_trinity_mini.py`` reads them and the negative controls).

Nothing below ``REFERENCE`` names a net: ``_Plain``, ``forward_errors``,
``rollout_compare``, ``step_errors`` and the two checks that only wrap them
take the reference they compare with as the argument ``ref`` (a
``Reference``: the two plain modules and the few facts about the net that
the comparison needs beside them), this file's own where none is given. A
second expert trunk's checks (``checks_smallthinker.py``) hand in theirs and
copy nothing.
"""

import functools
import inspect
import time
import typing

import numpy as np

from . import checks
from .checks_evabyte import (_adder, _count, _items, _leaves_by_name, _limit,
                             _verdict)
from .reference import trinity_mini, trinity_mini_loss

LR = 1e-3       # the step check's learning rate: any value, both sides use it
CONTROLS = {'one_layer_left_out': {'skip_layer': 2},
            'experts_left_out': {'use_experts': False},
            'window_ignored': {'use_window': False},
            'rotary_on_full_layer': {'rotary_on_full': True}}


def reference_config(config):
    model = config['model']
    cfg = {key: model[key] for key in (
        'head_dim', 'window_size', 'rope_theta', 'norm_eps', 'route_scale',
        'experts_per_token')}
    cfg['param_scale'] = model.get('param_scale', 1.0)
    cfg['layer_types'] = tuple(model['layer_types'])
    cfg['experts_held'] = tuple(model['experts_held'])
    return cfg


def group_of(path):
    """A parameter's group, by its name in the tree."""
    name = path[-1]
    if name in ('wq', 'wk', 'wv', 'wg', 'wo', 'q_norm', 'k_norm'):
        return 'attention'
    if name.startswith('experts_'):
        return 'experts'
    if name.startswith('router'):
        return 'router'
    if name.startswith('shared_'):
        return 'shared'
    if name in ('w_gate', 'w_up', 'w_down'):
        return 'mlp'
    if name.startswith('norm'):
        return 'norms'
    return 'embed' if name == 'embed' else 'readout'


class Reference(typing.NamedTuple):
    """What the comparisons read of one trunk's plain reference."""
    net: typing.Any        # the module: embed, layer, layer_kinds, readout
    loss: typing.Any       # the module: targets, loss_of_outputs,
    #                        first_adam_step, ADAM_B1
    config: typing.Any     # the configuration's file -> the reference's cfg
    embed_scale: typing.Any   # (cfg, hidden width) -> what ``net.embed``
    #                           multiplies a looked-up row by
    groups: tuple          # the parameters' groups, in reporting order
    group_of: typing.Any   # a leaf's path -> its group; ``router`` is the
    #                        group that follows rules of its own


REFERENCE = Reference(
    net=trinity_mini, loss=trinity_mini_loss, config=reference_config,
    embed_scale=lambda cfg, width: width ** 0.5 / cfg['param_scale'],
    groups=('attention', 'experts', 'router', 'shared', 'mlp', 'norms',
            'embed', 'readout'),
    group_of=group_of)


class _Plain:
    """The reference as small programs, each jitted once a process and a
    kind of layer: ``layer`` (one block at ``highest`` precision), its
    vector-Jacobian product, the readout, and the loss's gradient at the
    readout. Everything a seed decides is an ARGUMENT."""

    def __init__(self, ref, cfg, skip_layer=None, **controls):
        import jax
        reference, reference_loss = ref.net, ref.loss
        self.ref, self.cfg, self.skip_layer = ref, cfg, skip_layer
        # a control is either ``layer_kinds``' (which kind of attention a
        # layer runs) or the layer's own
        of_kinds = set(inspect.signature(reference.layer_kinds).parameters)
        self.kinds = reference.layer_kinds(cfg, **{
            k: v for k, v in controls.items() if k in of_kinds})
        layer_args = {k: v for k, v in controls.items() if k not in of_kinds}

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
            lambda g, ids, ct: g.at[ids].add(
                ct * ref.embed_scale(cfg, g.shape[1])),
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
        router's choices of the expert layers."""
        import jax.numpy as jnp
        p = variables['params']
        positions = first + jnp.arange(ids.shape[0])
        xs, routes = [self.ref.net.embed(p, ids, self.cfg)], []
        for i, kind in self.kept():
            x, chosen = self.layer(p['layer_%d' % i], xs[-1], positions,
                                   valid, kind)
            xs.append(x)
            if chosen is not None:
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
def _plain(ref, cfg_items, args_items=()):
    return _Plain(ref, dict(cfg_items), **dict(args_items))


def plain(ref, config, reference_args):
    return _plain(ref, _items(ref.config(config)), _items(reference_args))


def _sq(x):
    import jax.numpy as jnp
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


# -- forward -----------------------------------------------------------------
def seeded_windows(config, seed, n, positions):
    """ids (n, T), first positions (n,), valid (n, T): windows that start
    anywhere in a game of up to ``max_positions`` plies; every other one
    ends inside its game (padding follows), as a trained window may."""
    rng = np.random.default_rng(seed)
    model = config['model']
    ids = rng.integers(0, model['vocab'], (n, positions)).astype(np.int32)
    first = rng.integers(0, model['max_positions'] - positions + 1,
                         (n,)).astype(np.int32)
    length = np.where(np.arange(n) % 2 == 1,
                      rng.integers(positions // 2, positions, (n,)),
                      positions)
    return ids, first, np.arange(positions)[None, :] < length[:, None]


def program_sequence(module):
    """The learner's window forward with the head taken whole and the
    router's choices beside it: (logits (B, T, A), value (B, T), choices
    (layers, B * T, k))."""
    import jax
    import jax.numpy as jnp

    def run(variables, ids, first, valid):
        out, state = module.apply(variables, ids, first, valid,
                                  method=module.sequence,
                                  mutable=['intermediates'])
        logits = module.apply(variables, out['policy_features'],
                              method=module.policy_logits)
        sown = state['intermediates']
        routes = jnp.stack([sown['layer_%d' % i]['route_ids'][0]
                            for i in module.expert_layers])
        return logits, out['value'][..., 0], routes
    return jax.jit(run)


def forward_errors(config, module, variables, seed, program_variables=None,
                   ref=REFERENCE, **reference_args):
    """The RMS errors of the program's ``sequence`` against the reference
    over the seeded windows' valid positions, and the share of the router's
    choices that agree. ``program_variables`` and ``reference_args`` are
    the negative controls'."""
    import jax.numpy as jnp

    ids, first, valid = seeded_windows(
        config, seed, int(config['forward_windows']),
        int(config['forward_positions']))
    want_of = plain(ref, config, reference_args).forward
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


FORWARD_LIMITS = (('logits_rms_rel_to_logit_rms', '<='), ('value_rms', '<='),
                  ('routing_agreement_share', '>='))


def forward_check(config, variables, seed, train_args, ref=REFERENCE):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = forward_errors(config, module, variables, seed, ref=ref)
    n_params = _count(variables)
    compared = [['parameters', n_params, '==', config['model']['parameters']]]
    for name, op in FORWARD_LIMITS:
        limit = config.get('tolerance', {}).get(
            'forward_' + name, float('inf') if op == '<=' else 0.0)
        compared.append([name, stats[name], op, limit])
    return _verdict(compared, n_params, **stats,
                    seconds=time.perf_counter() - began)


# -- rollout through the cache -----------------------------------------------
def first_lengths(config, seed, plies):
    """The FIRST length of each of the check's games, one band a lane (a
    lane beyond the fourth takes its band again): the whole run and no
    reset, so that its circle goes round and its full layer outgrows it; an
    early end; a late end; and one that ends past position ``window_size``,
    after its circle has wrapped. A game that follows a reset takes the
    length the env draws, which is past the run's end."""
    model = config['model']
    window = model['window_size']
    assert window + 2 < plies <= model['max_positions']
    bands = [(model['max_positions'], model['max_positions'] + 1),
             (plies // 8, plies // 4),
             (plies // 2, 3 * plies // 4),
             (window + 1, plies - max(1, (plies - window) // 4))]
    rng = np.random.default_rng(seed + 2)
    return np.asarray([rng.integers(*bands[n % len(bands)])
                       for n in range(int(config['rollout_envs']))], np.int32)


def rollout_records(config, module, variables, seed, train_args,
                    actor_dtype=None):
    """Drive the program's ``rollout_chunk`` over ``rollout_envs`` games and
    keep, a ply, lane and seat: the id observed, the value and the policy's
    logits (the probe's ``value`` is ``[value, logits]``) and whether the
    game ended."""
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


def host_game_sums(got, want, window, after_reset):
    """One game and seat, on the host in float64: the squared errors of its
    plies' logits and values and the reference's squared logits, summed
    over all plies, over those at positions from ``window`` on and (a game
    begun after a reset) over all of them again. ``got`` is a ply's
    ``[value, logits]``; ``want`` the reference's forward over the game's
    ids, padded to whole blocks."""
    n = len(got)
    logits = np.asarray(want['logits'], np.float32)[:n]
    d_logit = got[:, 1:] - logits
    d_value = got[:, 0] - np.asarray(want['value'], np.float32)[:n]
    square = lambda x: float(np.square(x, dtype=np.float64).sum())
    return {name: {'logit': square(logits[keep]),
                   'd_logit': square(d_logit[keep]),
                   'd_value': square(d_value[keep]),
                   'plies': len(d_value[keep])}
            for name, keep in (
                ('', slice(None)), ('wrapped_', slice(window, None)),
                ('after_reset_', slice(None) if after_reset else slice(0, 0)))}


def rollout_compare(config, records, variables, ref=REFERENCE,
                    game_sums=host_game_sums, **reference_args):
    """Every ply's policy logits and value against the reference's full
    forward over each game's ids (``variables``: float32, what the actor's
    copy was cast from), over all plies and over two parts of them: the
    plies at positions from ``window_size`` on (``wrapped_``) and the plies
    of games that began after a reset (``after_reset_``). ``game_sums`` adds
    up one game's errors (``host_game_sums``; a net whose logits a game are
    too many to fetch hands in one that stays on the device)."""
    import jax.numpy as jnp
    obs, out, done = records['obs'], records['out'], records['done']
    want_of = plain(ref, config, reference_args).forward
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
                sums = game_sums(out[a:b, n, seat], want, window, a > 0)
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
        n = max(part['plies'], 1)
        stats[name + 'plies' if name else 'compared_plies'] = int(
            part['plies'])
        stats[name + 'logits_rms_rel_to_logit_rms'] = (
            part['d_logit'] / (n * ids_held)) ** 0.5 / max(logit_rms, 1e-9)
        stats[name + 'value_rms'] = (part['d_value'] / n) ** 0.5
    stats['logit_rms'] = logit_rms
    return stats


def rollout_errors(config, module, variables, seed, train_args,
                   actor_dtype=None, reference_variables=None, **compare_args):
    """``compare_args``: ``rollout_compare``'s own (``ref``, ``game_sums``)
    and the reference's negative controls."""
    records = rollout_records(config, module, variables, seed, train_args,
                              actor_dtype)
    return rollout_compare(
        config, records,
        variables if reference_variables is None else reference_variables,
        **compare_args)


ROLLOUT_LIMITS = ('logits_rms_rel_to_logit_rms', 'value_rms',
                  'wrapped_logits_rms_rel_to_logit_rms', 'wrapped_value_rms',
                  'after_reset_logits_rms_rel_to_logit_rms',
                  'after_reset_value_rms')


def rollout_check(config, variables, seed, train_args, **compare_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = rollout_errors(config, module, variables, seed, train_args,
                           **compare_args)
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
def seeded_batch(config, seed, train_args):
    """``batch_size`` solo-layout windows of the token game as the windower
    stores them, the legal set as bits, from seeded ids, actions and
    behaviour probabilities, and of DIFFERENT lengths: the first ends inside
    its window (padding, the value's tail and every mask are exercised), the
    next fills it, and so on by turns. Returns the batch and the same
    windows one by one as the reference reads them (a float mask)."""
    rng = np.random.default_rng(seed + 1)
    model, env = config['model'], config['env_args']
    T = int(train_args['forward_steps'])
    A = model['vocab']
    always = A - int(env['first_ply_ids'])
    f = np.float32
    rows, windows = [], []
    for b in range(int(train_args['batch_size'])):
        length = int(rng.integers(T // 2, T)) if b % 2 == 0 else T
        valid = (np.arange(T) < length).astype(f)
        first = int(rng.integers(0, model['max_positions'] - T + 1))
        ids = rng.integers(0, A, (T,)).astype(np.int32) * (valid > 0)
        legal = np.ones((T, A), bool)
        legal[1:, always:] = False    # the further ids: the first ply only
        action = rng.integers(0, always, (T,)).astype(np.int32) * (valid > 0)
        prob = np.where(valid > 0, rng.uniform(0.2, 2.0, (T,)) / A, 1.0)
        illegal = ~(legal & (valid[:, None] > 0))
        outcome = float(rng.choice([-1.0, 1.0]))
        progress = np.where(
            valid > 0, (first + np.arange(T)) / (first + length), 1.0)
        value = np.where(valid > 0, rng.uniform(-0.1, 0.1, (T,)), outcome)
        col = lambda x: np.asarray(x)[:, None, None]
        rows.append({
            'observation': ids[:, None],
            'selected_prob': col(prob).astype(f), 'action': col(action),
            'action_mask': np.packbits(illegal, axis=-1,
                                       bitorder='little')[:, None, :],
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
            'action_mask': np.where(illegal, f(1e32), f(0)),
            'outcome': outcome, 'progress': progress.astype(f)})
    batch = {key: np.stack([row[key] for row in rows]) for key in rows[0]}
    return batch, windows


def _leaf_sums(reference_loss, change, moment, grads, params, lr, norm):
    """A leaf: the squared error and the squared size of the gradient (the
    program's, read from Adam's first moment) and of the change, and how
    many elements moved the other way. Scalars only leave the program."""
    import jax
    import jax.numpy as jnp

    def one(c, m, g, p):
        want_grad, want_change = reference_loss.first_adam_step(
            g, p, lr, norm)
        got_grad = m.astype(jnp.float32) / (1 - reference_loss.ADAM_B1)
        return {'grad_err': _sq(got_grad - want_grad), 'grad': _sq(want_grad),
                'change_err': _sq(c - want_change),
                'change': _sq(want_change), 'moved': _sq(c),
                'flipped': jnp.sum(c * want_change < 0),
                'size': jnp.float32(c.size),
                # a vector or a single row or column: a norm's weight,
                # the value row
                'small': jnp.float32(c.ndim == 1 or 1 in c.shape)}
    return jax.tree_util.tree_map(one, change, moment, grads, params)


def step_errors(config, module, variables, seed, train_args,
                program_variables=None, ref=REFERENCE, park_on_host=False,
                **reference_args):
    """One update of the program's own step against the reference's
    gradient and first Adam step. What a net has beyond the shared numbers
    is read where the program gives it: a router's bias (``router_bias``
    leaves) against the reference's rule, the step's counters of positions
    past an attention window (``diag_window_positions_*``). ``park_on_host``
    moves the program's change and first moment to the host while the
    reference works (a long window's vector-Jacobian product needs the
    room)."""
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
    model = config['model']

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
    bias_after = {name: np.asarray(layer['router_bias'], np.float64)
                  for name, layer in state.params['params'].items()
                  if isinstance(layer, dict) and 'router_bias' in layer}
    router_moved = max(
        float(jnp.abs(layer['router']).max())
        for layer in change['params'].values()
        if isinstance(layer, dict) and 'router' in layer)
    del state
    if park_on_host:
        change, moment = jax.device_get((change, moment))

    # the reference: numpy targets, then jax.vjp of the plain loss, summed
    # over the batch's windows
    plain_ref = plain(ref, config, reference_args)
    total, terms, routes = 0.0, {}, []
    grads = jax.tree_util.tree_map(jnp.zeros_like, variables['params'])
    for window in windows:
        win = {k: jnp.asarray(v) for k, v in window.items()}
        out = plain_ref.forward(variables, win['ids'], win['first_position'],
                                win['valid'] > 0)
        routes.append(np.stack([np.asarray(r) for r in out['routes']]))
        value_target, advantage = ref.loss.targets(
            {'logits': out['logits'], 'value': out['value']}, window,
            cfg.lmb)
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
    sums = jax.jit(functools.partial(_leaf_sums, ref.loss))(
        change, moment, grads, variables, jnp.float32(LR), jnp.float32(norm))
    del grads, change, moment
    leaves = {name: {k: float(v) for k, v in leaf.items()}
              for name, leaf in _leaves_by_name(sums).items()}
    # the router's leaves follow rules of their own, held below
    adam = {n: leaf for n, leaf in leaves.items()
            if ref.group_of(n.split('/')) != 'router'}
    small = [n for n in adam if adam[n]['small']]

    def rel(err, refkey, names=None):
        picked = [adam[n] for n in (adam if names is None else names)]
        return (sum(x[err] for x in picked)
                / max(sum(x[refkey] for x in picked), 1e-30)) ** 0.5
    groups = {g: [n for n in adam if ref.group_of(n.split('/')) == g]
              for g in ref.groups}
    worst_grad = max(adam, key=lambda n: rel('grad_err', 'grad', [n]))
    worst_change = max(adam, key=lambda n: rel('change_err', 'change', [n]))
    stats = {
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
        'worst_leaves': {'grad': worst_grad, 'change': worst_change},
        'small_leaves': len(small),
        'loss': metrics['total'], 'reference_loss': total,
        'terms': {k: [metrics.get(k), v] for k, v in terms.items()},
        'grad_norm': metrics['diag_grad_norm'], 'reference_grad_norm': norm,
        'grad_err_rel_by_group': {
            g: rel('grad_err', 'grad', groups[g]) for g in ref.groups
            if groups[g]},
        'change_err_rel_by_group': {
            g: rel('change_err', 'change', groups[g]) for g in ref.groups
            if groups[g]},
        'nonfinite': metrics['nonfinite'],
        'windows': len(windows),
        'positions': [int(w['valid'].sum()) for w in windows],
        'change_sign_flipped_share': sum(
            x['flipped'] for x in adam.values())
        / sum(x['size'] for x in adam.values()),
    }
    if 'diag_window_positions_valid' in metrics:
        stats['positions_hidden_share'] = (
            metrics['diag_window_positions_hidden']
            / max(metrics['diag_window_positions_valid'], 1.0))
    if bias_after:
        stats.update(_bias_against_the_rule(
            ref.loss, model, start['params'], bias_after, routes))
    return stats


def _bias_against_the_rule(reference_loss, model, start_p, bias_after,
                           routes):
    """``b``: its change is the rule's for SOME vector of signs (exact: the
    program's choices differ from the reference's on ~1% of the pairs, so
    an expert whose count lies at the mean may take either sign), and that
    vector is the reference's wherever the reference's count is clear of
    the mean."""
    rate = model['bias_update_rate']
    ref_counts = None
    if len(routes[0]) == len(bias_after):   # no layer left out (a control)
        ref_counts = reference_loss.expert_counts(
            np.concatenate(routes, axis=1), model['experts_published'])
    bias_err, wrong_signs, sign_agree = 0.0, 0, []
    for n, name in enumerate(sorted(bias_after,
                                    key=lambda s: int(s.split('_')[1]))):
        before = np.asarray(start_p[name]['router_bias'], np.float64)
        residual, signs = reference_loss.signs_of_bias_step(
            before, bias_after[name], rate)
        bias_err = max(bias_err, residual)
        if ref_counts is not None:
            c = ref_counts[n].astype(np.float64)
            clear = np.abs(c - c.mean()) > max(2.0, c.mean() / 16)
            want = np.sign(c.mean() - c)
            wrong_signs += int((signs != want)[clear].sum())
            sign_agree.append(np.mean(signs == want))
    return {'bias_err_max_abs': bias_err,
            'bias_signs_against_reference': wrong_signs,
            'bias_sign_agrees_with_reference_share':
                float(np.mean(sign_agree)) if sign_agree else None,
            'bias_layers': len(bias_after)}


STEP_LIMITS = ('loss_rel_err', 'grad_norm_rel_err', 'grad_err_rel_to_grad',
               'grad_err_worst_leaf', 'change_err_rel_to_change',
               'change_err_worst_leaf', 'small_grad_err_rel_to_grad',
               'small_change_err_rel_to_change')


def step_check(config, variables, seed, train_args):
    began = time.perf_counter()
    module = checks.build_module(config, train_args)
    stats = step_errors(config, module, variables, seed, train_args)
    compared = [[name, stats[name], '<=', _limit(config, 'step_' + name)]
                for name in STEP_LIMITS]
    compared += [
        ['router_moved_max_abs', stats['router_moved_max_abs'], '==', 0.0],
        ['bias_err_max_abs', stats['bias_err_max_abs'], '<=', 1e-6],
        ['bias_signs_against_reference',
         stats['bias_signs_against_reference'], '==', 0],
        ['bias_layers', stats['bias_layers'], '==',
         len(config['model']['layer_types'])
         - config['model']['dense_layers']],
        ['rows_dropped', stats['rows_dropped'], '==', 0.0],
        ['nonfinite', stats['nonfinite'], '==', 0.0],
        ['windows', stats['windows'], '==', int(train_args['batch_size'])]]
    return _verdict(compared, _count(variables), **stats,
                    seconds=time.perf_counter() - began)
