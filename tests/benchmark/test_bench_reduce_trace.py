"""The trace reducer on a hand-written trace (exact answers) and on a small
trace recorded on a v5e chip (the real format)."""

import os

import pytest

from benchmark import reduce_trace

TESTDATA = os.path.join(os.path.dirname(reduce_trace.__file__), 'testdata')
US = 1e-6


@pytest.fixture(scope='module')
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, 'synthetic.xplane.txt')) as f:
        text = ''.join(line for line in f if not line.startswith('#'))
    path = tmp_path_factory.mktemp('trace') / 'synthetic.xplane.pb'
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return reduce_trace.reduce(str(path), window_span='train_dispatch')


def test_window_is_first_to_last_dispatch_end(synthetic):
    assert synthetic['devices'] == 1
    assert synthetic['window'] == [1000, 21000]
    assert synthetic['window_s'] == pytest.approx(20 * US)


def test_module_durations_only_of_executions_inside_the_window(synthetic):
    # the fourth execution ends at 24.5 us, past the window: left out
    assert synthetic['modules']['jit_fused_pipeline_train'] == \
        pytest.approx([4 * US] * 3)
    assert synthetic['modules']['jit_other'] == pytest.approx([0.5 * US])


def test_busy_is_the_union_of_nested_ops(synthetic):
    # three executions x (3 us while + 0.5 us copy) + 0.5 us + the 0.5 us of
    # the fourth while that lies inside the window; the fusion is nested
    assert synthetic['busy_s'] == pytest.approx(11.5 * US)


def test_top_ops_are_self_times(synthetic):
    ops = dict(synthetic['device_ops'])
    # while.1: 3 x (3 - 1) + 2 (the fourth has no child) ; clipped events
    # count whole, which is why the window holds whole chunks
    assert ops['while.1'] == pytest.approx(9 * US)
    assert ops['fusion.2'] == pytest.approx(3 * US)
    assert ops['copy.3'] == pytest.approx(2 * US)


def test_gaps_go_to_the_innermost_program_span(synthetic):
    gaps = dict(synthetic['idle_gaps'])
    # idle 11.5..16 and 19..19.5 us: 12..15 inside the program's
    # checkpoint_write, the rest inside its epoch_boundary; its dispatch
    # spans own what they overlap. The harness's own `bench:` annotations
    # (update_model, train_dispatch) mark the window and own nothing
    assert gaps['checkpoint_write'] == pytest.approx(3 * US)
    assert gaps["epoch_boundary"] == pytest.approx(2 * US)
    assert gaps["dispatch"] == pytest.approx(1.5 * US)
    assert not {'update_model', 'train_dispatch'} & set(gaps)
    total = sum(gaps.values())
    assert total == pytest.approx(synthetic['window_s']
                                  - synthetic['busy_s'])
    assert reduce_trace.NO_SPAN in gaps


def test_a_gap_belongs_to_the_thread_that_feeds_the_device(synthetic):
    """The trace's second host line is a checkpoint writer: its
    checkpoint_serialize (15.2..15.8 us) and its own checkpoint_write
    (19..19.5 us) lie over idle time and are shorter than the loop's
    epoch_boundary, which was the rule for an owner while any thread's span
    could be one. The loop's thread is the line that carries the window's
    marks; the writer's spans own nothing."""
    gaps = dict(synthetic['idle_gaps'])
    assert 'checkpoint_serialize' not in gaps
    # the loop's own checkpoint_write, 12..15 us, and not 0.5 us more
    assert gaps['checkpoint_write'] == pytest.approx(3 * US)
    assert gaps['epoch_boundary'] == pytest.approx(2 * US)


def test_without_the_windows_marks_every_thread_may_own_a_gap(tmp_path):
    """A trace that holds no ``bench:<window_span>`` annotation names no
    loop thread: the window is the extent of the device's events and the
    owners come from every line, as before."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, 'synthetic.xplane.txt')) as f:
        text = ''.join(line for line in f if not line.startswith('#'))
    path = tmp_path / 'unmarked.xplane.pb'
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        text.replace('bench:train_dispatch', 'bench:other')))
    gaps = dict(reduce_trace.reduce(str(path))['idle_gaps'])
    assert gaps['checkpoint_serialize'] == pytest.approx(0.6 * US)


def test_no_device_plane_reduces_to_nothing(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / 'host_only.xplane.pb'
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    assert reduce_trace.reduce(str(path)) is None


RECORDED = os.path.join(TESTDATA, 'toy.xplane.pb')


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason='no recorded chip trace in benchmark/testdata')
def test_recorded_chip_trace():
    """testdata/record_trace.py on a v5e: five executions of
    jit_bench_toy_step, a 20 ms host sleep inside bench:epoch_boundary (the
    toy has no program, so its gaps are read on the `bench:` annotations)."""
    got = reduce_trace.reduce(RECORDED, window_span='train_dispatch',
                              gap_prefix=reduce_trace.SPAN_PREFIX)
    assert got['devices'] == 1
    runs = got['modules']['jit_bench_toy_step']
    # the device timeline leads the host by ~1.3 ms in this trace, so the
    # executions of ~2 us sit "before" their own dispatch annotations: two
    # to four of the five fall inside the window
    assert 2 <= len(runs) <= 4
    assert all(0 < r < 1e-3 for r in runs)
    assert 0 < got['busy_s'] < got['window_s']
    assert got['window_s'] > 0.02  # holds the sleep
    gaps = dict(got['idle_gaps'])
    assert gaps['epoch_boundary'] >= 0.015
    assert got['device_ops']
