"""Test harness configuration.

Force JAX onto a virtual 8-device CPU mesh so all sharding/pjit code paths
run the same program they would on a TPU slice. ``JAX_PLATFORMS=cpu`` set
before ``import jax`` is all it takes; child processes inherit it.
"""

import os

# the suite assumes the 8-virtual-device CPU mesh (some tests hard-assert it)
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (_flags + ' --xla_force_host_platform_device_count=8').strip()


# ---------------------------------------------------------------------------
# In-tree 'timeout' mark: pytest-timeout is not installable in this image, so
# the deadlock guards on the multiprocess/socket e2e tests are enforced here
# with a SIGALRM watchdog (tests run in the main thread). A hung test raises
# TimeoutError instead of stalling CI until the job limit.

import signal  # noqa: E402

import pytest  # noqa: E402

from handyrl_tpu import setup_compile_cache  # noqa: E402

# the suite re-traces the same programs constantly; package import is
# side-effect free, so the persistent compile cache is enabled here
setup_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        'timeout(seconds): fail the test if it runs longer than the deadline')
    config.addinivalue_line(
        'markers',
        'slow: excluded from the tier-1 run (-m "not slow"); exercised by '
        'dedicated CI steps (e.g. the chaos smoke)')


# Socket/multiprocess integration tests rely on POSIX semantics (SIGALRM
# hang watchdog, spawn+pipe teardown timing); on the windows CI leg they are
# skipped — the unit/oracle/golden suite still runs there in full.
_POSIX_ONLY_FILES = (
    'test_remote_cluster.py', 'test_network.py', 'test_cluster.py',
    'test_cli.py', 'test_eval_cli.py', 'test_multihost.py',
    'test_batcher_processes.py', 'test_stress.py',
    'test_fault_tolerance.py', 'test_guard.py', 'test_engine_failover.py',
    'test_serving.py',
)


def pytest_collection_modifyitems(config, items):
    import sys
    if sys.platform == 'win32':
        skip_win = pytest.mark.skip(
            reason='POSIX-only integration test (SIGALRM watchdog / '
                   'spawn+socket teardown semantics)')
        for item in items:
            if os.path.basename(str(item.fspath)) in _POSIX_ONLY_FILES:
                item.add_marker(skip_win)


@pytest.fixture(autouse=True)
def _retrace_sentinel_ends_with_its_test():
    """The retrace sentinel's policy and its steady-state flag are
    process-global (telemetry.clear_steady_state: "learner shutdown, or test
    teardown"). A test that runs a cell's config in-process leaves the
    ``abort`` policy behind, and one that warms a device actor marks steady
    state with no learner to shut down: the next jit on that xdist worker
    then raises RetraceError. Which files share a worker changes with every
    file added, so each test leaves the process as a fresh one has it."""
    yield
    from handyrl_tpu import telemetry
    if telemetry.steady_state_active():
        telemetry.clear_steady_state()
    telemetry._STEADY['retraces'] = 0    # only mark_steady_state zeroes it
    telemetry.configure_perf_plane(True, 'warn')


@pytest.fixture
def kernel_form(monkeypatch):
    """``cache_attention`` as it chooses on a TPU, the kernel interpreted
    here, in blocks of 16 rows (three a pass of tests/test_ouro.py's nets,
    one a circle and four a full layer of the expert nets'); the count of
    the kernel's calls while a program is traced."""
    from handyrl_tpu.models import attention, decode_kernel
    calls = []
    real = decode_kernel.span_attention
    monkeypatch.setattr(attention, '_on_tpu', lambda: True)
    monkeypatch.setattr(decode_kernel, 'block_rows', lambda *_: 16)
    monkeypatch.setattr(decode_kernel, 'span_attention',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    return calls


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    mark = item.get_closest_marker('timeout')
    if mark is None or not hasattr(signal, 'SIGALRM'):
        return (yield)
    seconds = int(mark.args[0]) if mark.args else 300

    def _expired(signum, frame):
        raise TimeoutError('test exceeded %ds timeout' % seconds)

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)
