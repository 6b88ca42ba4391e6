"""Pallas TPU kernels for the backward target recursions.

The TD(lambda)/UPGO/V-Trace recursions are T sequential elementwise steps
over tiny (B, P, 1) slices — as ``lax.scan`` they compile to a T-iteration
loop of small fused bodies. Here the whole backward pass is ONE Pallas
kernel: data is laid out time-major as (T, N) with N = B*P padded to the
128-lane tile, the T loop is unrolled inside the kernel (T is static), and
every step is a VPU elementwise op on a full lane vector. One kernel launch,
zero intermediate HBM traffic.

Gradients never flow through targets (they consume stop_gradient'd values —
losses.py), so no custom VJP is needed; callers get stop_gradient semantics.

Status (measured on a real TPU v5e chip, round 2): the kernels compile,
run, and agree with the scan reference on silicon (tests/test_pallas_targets.py
with HANDYRL_TPU_TESTS=1), but inside the full update step they are SLOWER
than the lax.scan path — 56.9 vs 51.4 ms/step for TD/TD and 110.7 vs 50.0
for UPGO/VTRACE at B=128 T=16 (the builders' round-2 record, taken on a
set-up that no longer exists; ROADMAP D3). The recursion is elementwise
on tiny (T, B·P) blocks, so XLA fuses the scan into the surrounding program,
while a pallas_call is an opaque custom call that forces its inputs to be
materialized and breaks fusion. The scan path is therefore the default on
every backend; set ``HANDYRL_PALLAS_TARGETS=1`` to opt in (the step build
then verifies the kernel against the scan, and raises if it cannot).
``interpret=True`` makes the same kernels testable on CPU.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


_PROBED = False


def _probe_on_device() -> None:
    """Compile and run one tiny TD(λ) kernel on the live backend and compare
    it against the lax.scan reference. The opt-in asked for the kernels: one
    that does not compile, or compiles but disagrees, raises here — at step
    build — instead of training on a path the operator did not choose."""
    import numpy as np
    from . import targets as scan_ref
    rng = np.random.RandomState(0)
    shape = (2, 8, 1, 1)
    values = rng.randn(*shape).astype(np.float32)
    returns = rng.randn(*shape).astype(np.float32)
    rewards = rng.randn(*shape).astype(np.float32)
    lambda_ = (0.7 + 0.3 * (rng.rand(*shape) > 0.5)).astype(np.float32)
    got_t, got_a = td_lambda_pallas(values, returns, rewards,
                                    lambda_, 0.9)
    want_t, want_a = scan_ref.td_lambda(values, returns, rewards,
                                        lambda_, 0.9)
    if not (np.allclose(np.asarray(got_t), np.asarray(want_t),
                        rtol=1e-4, atol=1e-4)
            and np.allclose(np.asarray(got_a), np.asarray(want_a),
                            rtol=1e-4, atol=1e-4)):
        raise RuntimeError('pallas targets probe: the compiled kernel '
                           'DISAGREES with lax.scan on this backend')


def use_pallas_targets() -> bool:
    """True when explicitly opted in (HANDYRL_PALLAS_TARGETS=1) — and then
    the kernels have executed and matched the reference recursion in this
    process (probed once), or this call raised. Off by default: the scan
    path measured faster inside the full update step (module docstring).

    The probe compiles and executes a real kernel, so it must run OUTSIDE
    any jit trace: step builders call this eagerly before tracing
    (ops/train_step.py), and ``compute_target`` inside the trace then reads
    the settled answer."""
    global _PROBED
    if os.environ.get('HANDYRL_PALLAS_TARGETS') != '1':
        return False
    if not _PROBED:
        backend = jax.default_backend()
        if backend != 'tpu':
            raise RuntimeError(
                'HANDYRL_PALLAS_TARGETS=1 needs a TPU backend (the kernels '
                'are Mosaic programs); this process is on %r' % backend)
        _probe_on_device()
        _PROBED = True
    return True


# ---- kernels (refs are (T, N) or (1, N) VMEM blocks) ---------------------

def _td_kernel(v_ref, g_ref, rew_ref, lam_ref, out_ref, *, T, gamma):
    carry = g_ref[0, :]
    out_ref[T - 1, :] = carry
    for t in range(T - 2, -1, -1):
        lam = lam_ref[t + 1, :]
        carry = rew_ref[t, :] + gamma * ((1 - lam) * v_ref[t + 1, :] + lam * carry)
        out_ref[t, :] = carry


def _upgo_kernel(v_ref, g_ref, rew_ref, lam_ref, out_ref, *, T, gamma):
    carry = g_ref[0, :]
    out_ref[T - 1, :] = carry
    for t in range(T - 2, -1, -1):
        v_next = v_ref[t + 1, :]
        lam = lam_ref[t + 1, :]
        mixed = (1 - lam) * v_next + lam * carry
        carry = rew_ref[t, :] + gamma * jnp.maximum(v_next, mixed)
        out_ref[t, :] = carry


def _vtrace_kernel(delta_ref, lamc_ref, out_ref, *, T, gamma):
    """vmv_t = delta_t + gamma * (lam_{t+1} c_t) * vmv_{t+1}; lamc_ref holds
    the pre-multiplied factor aligned at index t."""
    carry = delta_ref[T - 1, :]
    out_ref[T - 1, :] = carry
    for t in range(T - 2, -1, -1):
        carry = delta_ref[t, :] + gamma * lamc_ref[t, :] * carry
        out_ref[t, :] = carry


# ---- host-side wrappers --------------------------------------------------

def _to_tn(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """(B, T, P, 1) -> time-major (T, N_padded); returns (array, N)."""
    B, T = x.shape[0], x.shape[1]
    flat = jnp.moveaxis(x, 1, 0).reshape(T, -1)
    N = flat.shape[1]
    pad = (-N) % LANES
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, N


def _from_tn(tn: jnp.ndarray, shape) -> jnp.ndarray:
    B, T, P = shape[0], shape[1], shape[2]
    return jnp.moveaxis(tn[:, :B * P].reshape(T, B, P, 1), 0, 1)


def _call(kernel, out_T, args, *, T, gamma, interpret):
    specs = [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in args]
    return pl.pallas_call(
        functools.partial(kernel, T=T, gamma=gamma),
        out_shape=jax.ShapeDtypeStruct((out_T, args[0].shape[1]), jnp.float32),
        in_specs=specs,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(*args)


def td_lambda_pallas(values, returns, rewards, lambda_, gamma,
                     interpret: bool = False):
    shape = values.shape
    T = shape[1]
    v, _ = _to_tn(values)
    lam, _ = _to_tn(lambda_)
    rew, _ = _to_tn(rewards if rewards is not None else jnp.zeros_like(values))
    g = _to_tn(returns[:, -1:])[0]
    tvs = _call(_td_kernel, T, (v, g, rew, lam), T=T, gamma=gamma,
                interpret=interpret)
    tvs = _from_tn(tvs, shape)
    return tvs, tvs - values


def upgo_pallas(values, returns, rewards, lambda_, gamma,
                interpret: bool = False):
    shape = values.shape
    T = shape[1]
    v, _ = _to_tn(values)
    lam, _ = _to_tn(lambda_)
    rew, _ = _to_tn(rewards if rewards is not None else jnp.zeros_like(values))
    g = _to_tn(returns[:, -1:])[0]
    tvs = _call(_upgo_kernel, T, (v, g, rew, lam), T=T, gamma=gamma,
                interpret=interpret)
    tvs = _from_tn(tvs, shape)
    return tvs, tvs - values


def vtrace_pallas(values, returns, rewards, lambda_, gamma, rhos, cs,
                  interpret: bool = False):
    shape = values.shape
    T = shape[1]
    rew = rewards if rewards is not None else jnp.zeros_like(values)
    v_next = jnp.concatenate([values[:, 1:], returns[:, -1:]], axis=1)
    deltas = rhos * (rew + gamma * v_next - values)
    # lamc aligned at t: lambda_{t+1} * c_t (last row unused)
    lamc = jnp.concatenate([lambda_[:, 1:] * cs[:, :-1],
                            jnp.zeros_like(cs[:, -1:])], axis=1)
    d_tn, _ = _to_tn(deltas)
    lamc_tn, _ = _to_tn(lamc)
    vmv = _call(_vtrace_kernel, T, (d_tn, lamc_tn), T=T, gamma=gamma,
                interpret=interpret)
    vmv = _from_tn(vmv, shape)
    vs = vmv + values
    vs_next = jnp.concatenate([vs[:, 1:], returns[:, -1:]], axis=1)
    advantages = rew + gamma * vs_next - values
    return vs, advantages
