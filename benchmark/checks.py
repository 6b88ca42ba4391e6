"""The comparisons that decide ``correct``, run during set-up on the device
the cell runs on: (a) the configuration's net, as the program builds it,
against the plain float32 forward in ``reference/`` on observations drawn
from the env twin with the run's seed; (b) ``ops/targets.py``'s V-trace
against the numpy reference on one seeded batch. Tolerances sit beside the
configuration (``configs/*.json`` ``tolerance``) and here (V-trace), each
with its reason.
"""

import numpy as np

from . import hooks

# V-trace is additions and multiplications in float32 over 16 steps, no
# matrix product: the program and the float64 numpy reference differ by
# float32 rounding of values of size ~10 (a few 1e-6). 1e-4 leaves room for
# a reordered scan and none for bfloat16 (rounding step 4e-2 at that size).
VTRACE_ATOL = 1e-4
VTRACE_SHAPE = (128, 16, 4, 1)   # (batch, forward_steps, players, 1)
OPENING_PLIES = 8                # random plies before the first observation


def _observations(twin, seed, n_envs, plies):
    """(plies, n_envs * players, C, H, W): boards of ``n_envs`` games played
    with uniformly random moves from the seed, finished games restarted."""
    import jax
    import jax.numpy as jnp

    def rollout(seed):
        # the seed is an ARGUMENT: as a constant it would make every new
        # seed a new program to compile (8 s of set-up a run; PR 23)
        key = jax.random.PRNGKey(seed)

        def ply(carry, _):
            state, key = carry
            key, sub = jax.random.split(key)
            actions = jax.random.randint(
                sub, (n_envs, twin.NUM_PLAYERS), 0, twin.N_ACTIONS, jnp.int32)
            state = twin.step(state, actions)
            state = twin.auto_reset(state, twin.terminal(state))
            obs = twin.observe(state)
            return (state, key), obs.reshape((-1,) + obs.shape[2:])
        state = twin.init_state(n_envs, seed)
        _, obs = jax.lax.scan(ply, (state, key), None,
                              length=OPENING_PLIES + plies)
        return obs[OPENING_PLIES:]
    return jax.jit(rollout)(jnp.asarray(seed, jnp.int32))


def forward_check(config, checkpoint, seed):
    """The program's net at the configuration's widths and compute dtype
    against the plain reference, on ``reference_envs`` games stepped through
    ``reference_plies`` plies with the hidden state carried by each side for
    itself. The weights are the ones the learner starts from (the
    configuration's trained ``checkpoint``, a flax msgpack of the program's
    own ``latest.ckpt``); the observations come from ``seed``."""
    import jax
    import jax.numpy as jnp
    from flax import serialization
    from handyrl_tpu.environment import make_env, make_jax_env

    module = make_env(config['env_args']).net()
    dtype = config['train_args'].get('compute_dtype')
    if dtype:
        module = module.clone(dtype=jnp.dtype(dtype))
    twin = make_jax_env(config['env_args'])
    plies = int(config['reference_plies'])
    obs = _observations(twin, seed, int(config['reference_envs']), plies)
    hidden = (module.init_hidden((obs.shape[1],))
              if hasattr(module, 'init_hidden') else None)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), obs[0],
                            hidden)
    with open(checkpoint, 'rb') as f:
        variables = serialization.from_bytes(
            jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), shapes), f.read())
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree_util.tree_leaves(variables))
    reference = hooks.resolve(config['reference'])[2]

    def plain(variables, obs, hidden):
        with jax.default_matmul_precision('highest'):
            return reference(variables, obs, hidden)
    program_step, reference_step = jax.jit(module.apply), jax.jit(plain)

    policy_err = value_err = logit_max = 0.0
    h_prog, h_ref = hidden, None
    for t in range(plies):
        got = program_step(variables, obs[t], h_prog)
        want = reference_step(variables, obs[t], h_ref)
        h_prog, h_ref = got.get('hidden'), want.get('hidden')
        want_policy = np.asarray(want['policy'], np.float32)
        logit_max = max(logit_max, float(np.abs(want_policy).max()))
        policy_err = max(policy_err, float(np.abs(
            np.asarray(got['policy'], np.float32) - want_policy).max()))
        value_err = max(value_err, float(np.abs(
            np.asarray(got['value'], np.float32)
            - np.asarray(want['value'], np.float32)).max()))
    tol = config['tolerance']
    rel = policy_err / max(logit_max, 1e-9)
    return {
        'observations': int(obs.shape[1]), 'plies': plies,
        'parameters': n_params,
        'policy_err_rel_to_max_logit': rel, 'max_logit': logit_max,
        'value_err_abs': value_err,
        'ok': bool(n_params == config['model']['parameters']
                   and np.isfinite(rel) and np.isfinite(value_err)
                   and rel <= tol['policy_rel_to_max_logit']
                   and value_err <= tol['value_abs']),
    }


def vtrace_check(seed, gamma=0.99, lmb=0.7):
    """``ops/targets.py`` (as the program calls it) against numpy."""
    import jax
    import jax.numpy as jnp
    from handyrl_tpu.ops.targets import compute_target

    from .reference.vtrace import vtrace

    rng = np.random.default_rng(seed)
    shape = VTRACE_SHAPE
    values = rng.normal(size=shape).astype(np.float32)
    returns = rng.normal(size=shape).astype(np.float32)
    rewards = (0.1 * rng.normal(size=shape)).astype(np.float32)
    rhos = np.minimum(np.exp(rng.normal(size=shape)), 1.0).astype(np.float32)
    cs = np.minimum(np.exp(rng.normal(size=shape)), 1.0).astype(np.float32)
    masks = (rng.random(shape) < 0.8).astype(np.float32)
    program = jax.jit(lambda v, ret, rew, rho, c, m: compute_target(
        'VTRACE', v, ret, rew, lmb, gamma, rho, c, m))
    got = program(*(jnp.asarray(a) for a in
                    (values, returns, rewards, rhos, cs, masks)))
    want = vtrace(values, returns, rewards, lmb, gamma, rhos, cs, masks)
    errs = [float(np.abs(np.asarray(g, np.float64) - w).max())
            for g, w in zip(got, want)]
    return {'target_err_abs': errs[0], 'advantage_err_abs': errs[1],
            'ok': bool(all(np.isfinite(e) and e <= VTRACE_ATOL
                           for e in errs))}
