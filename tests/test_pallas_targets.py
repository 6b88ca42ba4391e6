"""Pallas target kernels vs the lax.scan reference.

Default suite run (CPU conftest pin): kernels execute in interpret mode.
With ``HANDYRL_TPU_TESTS=1`` and a live TPU backend, every parity test ALSO
runs the genuinely compiled kernels on silicon (interpret=False) — the
proof that the Pallas path works as Pallas, not only as its interpreter.
"""

import jax
import numpy as np
import pytest

from handyrl_tpu.ops import targets as ref
from handyrl_tpu.ops import pallas_targets as pt

B, T, P = 4, 16, 2
SHAPE = (B, T, P, 1)

_ON_TPU = jax.default_backend() == 'tpu'

# interpret=True runs anywhere; interpret=False only compiles on real TPU
INTERPRET_MODES = [True] + ([False] if _ON_TPU else [])


@pytest.fixture(params=INTERPRET_MODES,
                ids=['interpret', 'compiled'][:len(INTERPRET_MODES)])
def interpret(request):
    return request.param


def _rand(seed, shape=SHAPE):
    rng = np.random.RandomState(seed)
    values = rng.randn(*shape).astype(np.float32)
    returns = rng.randn(*shape).astype(np.float32)
    rewards = rng.randn(*shape).astype(np.float32)
    rhos = rng.uniform(0.1, 1.0, shape).astype(np.float32)
    cs = rng.uniform(0.1, 1.0, shape).astype(np.float32)
    masks = (rng.rand(*shape) > 0.3).astype(np.float32)
    lambda_ = 0.7 + (1 - 0.7) * (1 - masks)
    return values, returns, rewards, rhos, cs, lambda_


@pytest.mark.parametrize('gamma', [1.0, 0.8])
@pytest.mark.parametrize('use_rewards', [True, False])
def test_td_pallas_matches_scan(gamma, use_rewards, interpret):
    values, returns, rewards, _, _, lambda_ = _rand(0)
    rew = rewards if use_rewards else None
    want_t, want_a = ref.td_lambda(values, returns, rew, lambda_, gamma)
    got_t, got_a = pt.td_lambda_pallas(values, returns, rew, lambda_, gamma,
                                       interpret=interpret)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                               rtol=1e-5, atol=1e-5)


def test_upgo_pallas_matches_scan(interpret):
    values, returns, rewards, _, _, lambda_ = _rand(1)
    want_t, _ = ref.upgo(values, returns, rewards, lambda_, 0.9)
    got_t, _ = pt.upgo_pallas(values, returns, rewards, lambda_, 0.9,
                              interpret=interpret)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=1e-5, atol=1e-5)


def test_vtrace_pallas_matches_scan(interpret):
    values, returns, rewards, rhos, cs, lambda_ = _rand(2)
    want_v, want_a = ref.vtrace(values, returns, rewards, lambda_, 0.9, rhos, cs)
    got_v, got_a = pt.vtrace_pallas(values, returns, rewards, lambda_, 0.9,
                                    rhos, cs, interpret=interpret)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                               rtol=1e-5, atol=1e-5)


def test_kernels_at_learner_shape(interpret):
    """B=128, T=16 with the four geese seats: the shape the learner's
    update step hands the target recursion (N = B*P = 512 lanes)."""
    values, returns, rewards, rhos, cs, lambda_ = _rand(
        4, shape=(128, 16, 4, 1))
    for name, fn, extra in (('td_lambda', pt.td_lambda_pallas, ()),
                            ('upgo', pt.upgo_pallas, ()),
                            ('vtrace', pt.vtrace_pallas, (rhos, cs))):
        want = getattr(ref, name)(values, returns, rewards, lambda_, 0.99,
                                  *extra)
        got = fn(values, returns, rewards, lambda_, 0.99, *extra,
                 interpret=interpret)
        for w, g in zip(want, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def test_nonmultiple_of_128_lanes(interpret):
    """B*P = 6 forces lane padding."""
    rng = np.random.RandomState(3)
    shape = (3, 5, 2, 1)
    values = rng.randn(*shape).astype(np.float32)
    returns = rng.randn(*shape).astype(np.float32)
    lambda_ = np.full(shape, 0.7, np.float32)
    want_t, _ = ref.td_lambda(values, returns, None, lambda_, 0.9)
    got_t, _ = pt.td_lambda_pallas(values, returns, None, lambda_, 0.9,
                                   interpret=interpret)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=1e-5, atol=1e-5)


def test_gate_closed_without_opt_in(monkeypatch):
    """Scan is the default everywhere (measured faster on TPU; module
    docstring) — the gate only opens with HANDYRL_PALLAS_TARGETS=1."""
    monkeypatch.delenv('HANDYRL_PALLAS_TARGETS', raising=False)
    assert pt.use_pallas_targets() is False


@pytest.mark.skipif(_ON_TPU, reason='the opt-in legitimately opens on TPU')
def test_gate_raises_off_tpu_when_opted_in(monkeypatch):
    """The opt-in asks for the kernels: on a backend that cannot compile
    them the step build raises BEFORE the probe runs — it never quietly
    answers False and trains on the scan path instead."""
    monkeypatch.setenv('HANDYRL_PALLAS_TARGETS', '1')
    monkeypatch.setattr(pt, '_PROBED', False)
    with pytest.raises(RuntimeError, match='needs a TPU backend'):
        pt.use_pallas_targets()
    assert pt._PROBED is False


@pytest.mark.skipif(_ON_TPU, reason='probe legitimately passes on TPU')
def test_probe_raises_off_tpu():
    """The startup probe compiles a real (non-interpret) kernel; on a
    backend where that cannot work the compiler's error propagates — no
    catch-all turns it into a scan fallback."""
    with pytest.raises(Exception):
        pt._probe_on_device()


@pytest.mark.skipif(not _ON_TPU, reason='needs a live TPU backend')
def test_probe_passes_and_gate_opens_on_tpu(monkeypatch):
    """On real silicon the startup probe must compile, run, and agree
    with the scan reference — and the gate opens once opted in."""
    monkeypatch.setenv('HANDYRL_PALLAS_TARGETS', '1')
    monkeypatch.setattr(pt, '_PROBED', False)
    pt._probe_on_device()
    assert pt.use_pallas_targets() is True
