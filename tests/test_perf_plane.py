"""Compiled-performance plane: device-memory gauges (stubbed accelerator
stats + the CPU RSS fallback), the steady-state retrace sentinel (counting,
flight-recorder events, warn/abort policies), the dispatch/host_block span
split on a real compiled CPU train step."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.model import ModelWrapper  # noqa: F401 (env setup parity)


@pytest.fixture(autouse=True)
def _clean_perf_plane(monkeypatch):
    """Every test starts outside steady state with the plane enabled and
    no env policy override, and leaves the process the same way."""
    monkeypatch.delenv('HANDYRL_TPU_RETRACE', raising=False)
    telemetry.configure_perf_plane(True, 'warn')
    telemetry.clear_steady_state()
    yield
    telemetry.clear_steady_state()
    telemetry.configure_perf_plane(True, 'warn')


# ---------------------------------------------------------------------------
# device-memory plane


class _StubDevice:
    platform = 'tpu'
    id = 3
    device_kind = 'fake-tpu'

    def memory_stats(self):
        return {'bytes_in_use': 6 * 2**30, 'peak_bytes_in_use': 7 * 2**30,
                'bytes_limit': 16 * 2**30}


def test_sample_device_memory_uses_backend_stats():
    rows = telemetry.sample_device_memory(devices=[_StubDevice()])
    assert rows == [{'device': 'tpu:3', 'bytes_in_use': 6 * 2**30,
                     'peak_bytes_in_use': 7 * 2**30,
                     'bytes_limit': 16 * 2**30}]
    snap = telemetry.REGISTRY.snapshot()
    assert snap['gauges']['device_mem_bytes_in_use{device="tpu:3"}'] \
        == 6 * 2**30
    assert snap['gauges']['device_mem_bytes_limit{device="tpu:3"}'] \
        == 16 * 2**30
    assert telemetry.device_memory_utilization(rows) == pytest.approx(6 / 16)


def test_sample_device_memory_cpu_rss_fallback():
    """CPU devices have no memory_stats: ONE process_rss row (all CPU
    "devices" share this process), real RSS and a physical-RAM limit."""
    rows = telemetry.sample_device_memory()   # real jax CPU devices
    assert len(rows) == 1 and rows[0]['device'] == 'process_rss'
    assert rows[0]['bytes_in_use'] > 0
    assert rows[0]['bytes_limit'] > rows[0]['bytes_in_use']
    assert rows[0]['peak_bytes_in_use'] >= rows[0]['bytes_in_use']
    util = telemetry.device_memory_utilization(rows)
    assert 0.0 < util < 1.0
    assert telemetry.perf_status()['device_memory'] == rows


def test_sample_device_memory_disabled_plane_is_inert():
    telemetry.configure_perf_plane(False)
    try:
        assert telemetry.sample_device_memory(devices=[_StubDevice()]) == []
    finally:
        telemetry.configure_perf_plane(True)


def test_hbm_pressure_builtin_alert_fires_on_sustained_ratio():
    rules = [dict(r) for r in telemetry.BUILTIN_ALERTS
             if r['name'] == 'hbm_pressure']
    assert rules, 'hbm_pressure must be in the builtin catalog'
    rule = rules[0]
    rule['for'] = 0.0   # no sustain window in a unit test
    eng = telemetry.AlertEngine([rule])
    telemetry.gauge('device_mem_utilization').set(0.95)
    now = time.time()
    eng.evaluate([telemetry.snapshot()], now=now)
    state = eng.evaluate([telemetry.snapshot()], now=now + 1.0)
    assert 'hbm_pressure' in state['active']
    telemetry.gauge('device_mem_utilization').set(0.0)


# ---------------------------------------------------------------------------
# retrace sentinel


def _fresh_jit():
    return jax.jit(lambda x: x * 3.0)


def _arr(n):
    # device_put, NOT jnp.ones: array construction must not itself compile
    # a program mid-test
    return jax.device_put(np.ones((n,), np.float32))


def test_warmup_compile_does_not_count_then_steady_retrace_does():
    assert telemetry.install_jax_monitoring()
    fn = _fresh_jit()
    fn(_arr(2))                        # warm-up compile, before the mark
    assert telemetry.steady_retrace_count() == 0
    assert telemetry.mark_steady_state('unit test')
    assert telemetry.steady_state_active()
    before = telemetry.REGISTRY.snapshot()['counters'].get(
        'xla_retraces_total', 0)
    fn(_arr(2))                        # cache hit: not a retrace
    assert telemetry.steady_retrace_count() == 0
    fn(_arr(4))                        # new shape: retrace
    assert telemetry.steady_retrace_count() == 1
    snap = telemetry.REGISTRY.snapshot()
    assert snap['counters']['xla_retraces_total'] == before + 1
    assert snap['gauges'].get('xla_steady_state') == 1
    # the flight recorder carries the event with the callable/shape key
    kinds = [e for e in telemetry.recorder().events()
             if e.get('kind') == 'retrace']
    assert kinds and 'retrace' in kinds[-1]['msg']


def test_clear_steady_state_disarms_the_sentinel():
    assert telemetry.install_jax_monitoring()
    telemetry.mark_steady_state()
    telemetry.clear_steady_state()
    assert not telemetry.steady_state_active()
    _fresh_jit()(_arr(6))              # fresh compile after clear
    assert telemetry.steady_retrace_count() == 0
    assert telemetry.REGISTRY.snapshot()['gauges'].get(
        'xla_steady_state') == 0


def test_abort_policy_raises_at_the_jit_call_site(monkeypatch):
    assert telemetry.install_jax_monitoring()
    fn = _fresh_jit()
    fn(_arr(2))
    telemetry.mark_steady_state()
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'abort')
    with pytest.raises(telemetry.RetraceError):
        fn(_arr(8))


def test_retrace_policy_env_overrides_config(monkeypatch):
    telemetry.configure_perf_plane(retrace='abort')
    assert telemetry.retrace_policy() == 'abort'
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'off')
    assert telemetry.retrace_policy() == 'off'
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'bogus')
    assert telemetry.retrace_policy() == 'abort'   # bad env falls through


def test_off_policy_ignores_retraces(monkeypatch):
    assert telemetry.install_jax_monitoring()
    fn = _fresh_jit()
    fn(_arr(2))
    telemetry.mark_steady_state()
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'off')
    fn(_arr(10))
    assert telemetry.steady_retrace_count() == 0


def test_retrace_storm_builtin_alert_in_catalog():
    names = [r['name'] for r in telemetry.BUILTIN_ALERTS]
    assert 'retrace_storm' in names


def test_expected_compile_scope_exempts_signature_polymorphic_jits(
        monkeypatch):
    """utils/fetch.py's per-signature packers compile fresh programs by
    design; inside expected_compile() the sentinel books them under
    xla_expected_compiles_total and neither counts nor aborts."""
    assert telemetry.install_jax_monitoring()
    fn = _fresh_jit()
    fn(_arr(2))
    telemetry.mark_steady_state()
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'abort')
    before = telemetry.REGISTRY.snapshot()['counters'].get(
        'xla_expected_compiles_total', 0)
    with telemetry.expected_compile('unit test'):
        fn(_arr(12))                   # fresh shape, declared expected
    assert telemetry.steady_retrace_count() == 0
    snap = telemetry.REGISTRY.snapshot()
    assert snap['counters']['xla_expected_compiles_total'] == before + 1
    with pytest.raises(telemetry.RetraceError):
        fn(_arr(14))                   # outside the scope it aborts again


def test_fetch_tree_growth_is_expected_not_a_retrace(monkeypatch):
    """The real fetch path: a metric-set growth (more scalar leaves than
    warm-up saw) must NOT trip the abort policy — the exact failure the
    telemetry smoke exposed."""
    from handyrl_tpu.utils.fetch import fetch_tree
    assert telemetry.install_jax_monitoring()
    fetch_tree({'a': _arr(2), 'b': _arr(3)})       # warm one signature
    telemetry.mark_steady_state()
    monkeypatch.setenv('HANDYRL_TPU_RETRACE', 'abort')
    out = fetch_tree({'a': _arr(2), 'b': _arr(3), 'c': _arr(4)})
    assert telemetry.steady_retrace_count() == 0
    assert isinstance(out['c'], np.ndarray) and out['c'].shape == (4,)


# ---------------------------------------------------------------------------
# dispatch / host_block decomposition


def test_dispatch_host_block_split_on_real_train_step():
    """The trainer's timing seam, exercised with a REAL compiled CPU train
    step: dispatch (async issue) and host_block (block_until_ready) land in
    separate stage_seconds histograms, and the utilization proxy follows."""
    from handyrl_tpu.models.tictactoe import SimpleConv2dModel
    from handyrl_tpu.ops.batch import make_batch
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.train_step import build_update_step, \
        init_train_state
    from handyrl_tpu.utils.timing import StageTimer
    from helpers import turn_based_episode, train_args, window

    eps = [window(turn_based_episode(5, seed=i), 0, 4) for i in range(4)]
    batch = make_batch(eps, train_args(forward_steps=4))
    module = SimpleConv2dModel()
    obs = jax.tree_util.tree_map(lambda o: o[:, 0, 0], batch['observation'])
    params = module.init(jax.random.PRNGKey(0), obs, None)
    state = init_train_state(params)
    step = build_update_step(module, LossConfig(), donate=False)
    lr = jnp.asarray(1e-3, jnp.float32)

    timer = StageTimer(registry=telemetry.REGISTRY)
    with timer.section('dispatch'):
        state, metrics = step(state, batch, lr)
    with timer.section('host_block'):
        jax.block_until_ready(metrics['total'])
    snap = timer.snapshot()
    assert snap['dispatch']['s'] >= 0 and snap['dispatch']['n'] == 1
    assert snap['host_block']['n'] == 1
    hists = telemetry.REGISTRY.snapshot()['hists']
    assert 'stage_seconds{stage="dispatch"}' in hists
    assert 'stage_seconds{stage="host_block"}' in hists

    util = telemetry.utilization_from_stages(snap)
    assert util is not None and 0.0 <= util <= 1.0
    telemetry.set_utilization_proxy(util)
    assert telemetry.REGISTRY.snapshot()['gauges'][
        'device_utilization_proxy'] == pytest.approx(util)
    assert telemetry.perf_status()['device_utilization_proxy'] \
        == pytest.approx(util)


def test_utilization_from_stages_shapes_and_edges():
    assert telemetry.utilization_from_stages(
        {'dispatch': 1.0, 'host_block': 3.0}) == pytest.approx(0.75)
    # StageTimer.snapshot shape ({'s':..., 'n':...}) is accepted too
    assert telemetry.utilization_from_stages(
        {'dispatch': {'s': 1.0, 'n': 2},
         'host_block': {'s': 1.0, 'n': 1}}) == pytest.approx(0.5)
    assert telemetry.utilization_from_stages({}) is None
    assert telemetry.utilization_from_stages({'select': 0.0}) is None


def test_ingest_stage_vocabulary_has_the_decomposed_stages():
    assert 'dispatch' in telemetry.INGEST_STAGES
    assert 'host_block' in telemetry.INGEST_STAGES
    assert 'compute' not in telemetry.INGEST_STAGES
    assert 'drain' not in telemetry.INGEST_STAGES


def test_statusz_render_includes_perf_block():
    out = telemetry.render_status({
        'role': 'learner', 'pid': 1, 'run_id': 'r',
        'perf': {'steady_state': True, 'retraces': 2,
                 'retrace_policy': 'warn',
                 'device_utilization_proxy': 0.8,
                 'device_mem_utilization': 0.4,
                 'device_memory': [
                     {'device': 'process_rss', 'bytes_in_use': 2**30,
                      'peak_bytes_in_use': 2**30, 'bytes_limit': 2**32}]}})
    assert 'steady' in out and 'retraces=2' in out
    assert 'device_util=80%' in out and 'mem_util=40%' in out
    assert 'process_rss' in out


# ---------------------------------------------------------------------------
# config plumbing


def test_config_validates_retrace_knobs():
    from handyrl_tpu.config import apply_defaults, validate
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'telemetry': {'retrace': 'sometimes'}}}
    with pytest.raises(AssertionError):
        validate(apply_defaults(raw))
    raw['train_args']['telemetry'] = {'retrace': 'abort',
                                      'retrace_warmup_epochs': 2}
    validate(apply_defaults(raw))


def test_adopt_config_configures_perf_plane():
    telemetry.adopt_config({'telemetry': {'perf_plane': False,
                                          'retrace': 'off'}})
    try:
        assert not telemetry.perf_plane_enabled()
        assert telemetry.retrace_policy() == 'off'
    finally:
        telemetry.configure_perf_plane(True, 'warn')
