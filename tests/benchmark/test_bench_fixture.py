"""What a configuration's file names: ``weights``, ``checks`` and ``flops``,
held on a fixture configuration that is no entry of ``BENCHMARK.json``
(``fixture/seeded_toy.json``: seeded weights, a FLOP count of its own and a
third check) in a cell that brings its own traffic mix, hook, reader, metric
and rehearsal overlay (``fixture/make_root.py``), run through the harness's
one path on the CPU rehearsal, and on the loader's refusals.

THE TEMPLATE for a configuration's own test file (benchmark/README.md,
"Adding a configuration and its cell"): a test that reads a manifest takes
it from ``conftest.py``'s ``either_root`` (the checkout, then the root later
PRs will have grown it into) or from a root this fixture builds, and never
from ``Manifest()`` alone; what it pins it names (``contracts.PAIR``,
``make_root.CONFIG``) or finds by its kind (``_with_a_checkpoint``), and
nothing here indexes ``configs``, ``workloads`` or ``per_layer`` from the
end. On the checkout alone: what starts a subprocess (``rehearsed`` and the
two runs with something broken) and the checkpoint's bytes."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import checks
from benchmark.manifest import Manifest, ManifestError, ROOT
from benchmark.session import fold_seed, merged_args

from tests.benchmark import contracts
from tests.benchmark.contracts import PAIR, SIX
from tests.benchmark.fixture import make_root, toy

FIXTURE = os.path.dirname(os.path.abspath(make_root.__file__))

SEED = 2 ** 31 + 11


def _env():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)   # one CPU device: a one-chip cell
    env.pop('BENCH_RUN', None)
    return env


def _rehearse(root, *extra, script=os.path.join(ROOT, 'benchmark',
                                                'rehearse.py')):
    proc = subprocess.run(
        [sys.executable, script, '--root', str(root), '--workload',
         make_root.CELL, '--seconds', '2', '--seed', str(SEED), *extra],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=540)
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return proc, json.loads(lines[-1])


@pytest.fixture(scope='module')
def rehearsed(tmp_path_factory):
    root = make_root.build(str(tmp_path_factory.mktemp('fixture_root')))
    # traced, so that the line lists the per-layer metrics that were read
    proc, line = _rehearse(root, '--trace', '1')
    assert proc.returncode == 0, proc.stderr[-3000:]
    run_dir = os.path.join(ROOT, '.bench_runs', 'rehearsal', make_root.CELL,
                           '.bench_runs', make_root.CELL)
    contracts.a_rehearsal_line(Manifest(root), make_root.CELL, line)
    with open(os.path.join(run_dir, 'config.yaml')) as f:
        return proc, line, json.load(f)


@pytest.mark.timeout(600)
def test_seeded_weights_give_the_learner_no_file(rehearsed):
    _proc, line, written = rehearsed
    assert 'init_params' not in written['train_args']
    assert written['train_args']['seed'] == fold_seed(SEED)
    assert line['reference']['third_check']['had_init_params'] is False
    assert line['correct'] is True


@pytest.mark.timeout(600)
def test_the_third_check_is_a_verdict_key_after_the_six(rehearsed):
    _proc, line, _written = rehearsed
    assert list(line['checks']) == SIX + ['third_check']
    assert list(line['reference']) == SIX[4:] + ['third_check']
    assert all(line['checks'].values())


@pytest.mark.timeout(600)
def test_the_configurations_own_flop_count_reaches_the_line(rehearsed):
    _proc, line, written = rehearsed
    # a number a step (the file's 12345, the overlay's in a rehearsal) x
    # the window's forward_steps
    per_step = make_root.OVERLAY['model']['flops_per_window']
    assert line['flops'] == {
        'train_window': per_step * written['train_args']['forward_steps']}


@pytest.mark.timeout(600)
def test_the_cell_rehearses_through_its_own_overlay(rehearsed):
    """``rehearsal/seeded_toy.json``, not ``tiny.json``: its ``model``, its
    check sizes and its ``train_args`` reached the run; what it leaves to
    ``tiny.json``'s values is as there."""
    _proc, line, written = rehearsed
    own = make_root.OVERLAY
    assert written['train_args']['forward_steps'] == \
        own['train_args']['forward_steps'] == 5
    forward = line['reference']['forward_matches_reference']
    assert forward['plies'] == own['config']['reference_plies'] == 2
    with open(os.path.join(ROOT, 'benchmark', 'rehearsal',
                           'tiny.json')) as f:
        tiny = json.load(f)
    assert tiny['train_args']['forward_steps'] == 4
    assert tiny['config']['reference_plies'] == 3
    assert written['train_args']['batch_size'] == \
        tiny['train_args']['batch_size']


@pytest.mark.timeout(600)
def test_the_cells_own_hook_reader_and_metric_are_found_by_name(rehearsed):
    _proc, line, _written = rehearsed
    assert make_root.HOOK in line['spans']
    assert make_root.METRIC in line['metrics_read']
    # the shared path's metrics list no cells, so the toy cell reads them
    # under their one name (those a CPU run has something to read for: the
    # loop's spans and the arithmetic; names only, never a number)
    assert {'dispatch_enqueue_ms', 'fetch_wait_ms', 'train_mfu'} \
        <= set(line['metrics_read'])
    assert line['counts']['epochs_in_window'] >= 1


@pytest.mark.timeout(600)
def test_each_number_compared_is_on_stderr_beside_its_limit(rehearsed):
    proc, _line, _written = rehearsed
    tail = proc.stderr.strip().splitlines()[-12:]
    assert tail[-1].startswith('benchmark: checks {')
    for what in ('sgd_steps_booked_in_window', 'policy_rms_rel_to_logit_rms',
                 'value_rms', 'target_err_abs', 'third_check_ok'):
        assert any(what in row and 'limit' in row for row in tail), what


@pytest.mark.timeout(600)
def test_the_checks_variables_are_the_learners_starting_parameters(
        rehearsed, tmp_path, monkeypatch):
    """Bit for bit: the digest the third check took of the variables it was
    handed, in the rehearsal's process, against a Learner built here from
    the ``config.yaml`` the harness wrote."""
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    _proc, line, written = rehearsed
    args = json.loads(json.dumps(written))
    args['train_args'].update(
        model_dir=str(tmp_path / 'models'),
        metrics_jsonl=str(tmp_path / 'metrics.jsonl'),
        guard=dict(args['train_args']['guard'], preempt_signals=False))
    monkeypatch.chdir(tmp_path)
    want = toy.digest(Learner(args=apply_defaults(args)).wrapper.params)
    assert line['reference']['third_check']['variables_sha256'] == want
    other = dict(written['train_args'], seed=written['train_args']['seed'] + 1)
    config = json.load(open(os.path.join(FIXTURE, 'seeded_toy.json')))
    assert toy.digest(checks.starting_variables(config, other)) != want


@pytest.mark.timeout(600)
def test_a_false_third_check_makes_correct_false(tmp_path):
    root = make_root.build(str(tmp_path / 'root'), third_check_ok=False)
    proc, line = _rehearse(root)
    assert proc.returncode == 1
    assert line['checks']['third_check'] is False
    assert all(line['checks'][key] for key in SIX)
    assert line['correct'] is False


@pytest.mark.timeout(600)
def test_a_training_dispatch_that_trains_nothing_is_not_correct(tmp_path):
    """The rest of a run with the timed path broken underneath: every
    training dispatch runs the rollout and ingest and leaves the train state
    as it was (``fixture/broken_step.py``). The host's own book of SGD steps
    does not see it (the replay ratio reads as configured); the fetch that
    carries no loss sums does."""
    root = make_root.build(str(tmp_path / 'root'))
    proc, line = _rehearse(root, script=os.path.join(FIXTURE,
                                                     'broken_step.py'))
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert line['checks']['all_updates_finite'] is False
    assert line['failed'] == line['attempted'] >= 1
    assert line['correct'] is False


def _broken(tmp_path, edit):
    root = make_root.build(str(tmp_path / 'root'))
    path = os.path.join(root, 'benchmark', 'configs', 'seeded_toy.json')
    with open(path) as f:
        config = json.load(f)
    edit(config)
    with open(path, 'w') as f:
        json.dump(config, f)
    return Manifest(root)


@pytest.mark.parametrize('edit, named', [
    (lambda c: c['checks'][2].update(check='tests.benchmark.fixture.toy:gone'),
     'tests.benchmark.fixture.toy:gone'),
    (lambda c: c['checks'][0].update(check='benchmark.no_such_module:f'),
     'benchmark.no_such_module:f'),
    (lambda c: c.update(flops='benchmark.flops:no_such_count'),
     'benchmark.flops:no_such_count'),
    (lambda c: c.update(flops='not_a_target'), 'not_a_target'),
])
def test_an_unresolvable_check_or_flops_fails_by_name_at_load(tmp_path, edit,
                                                              named):
    manifest = _broken(tmp_path, edit)
    with pytest.raises(ManifestError) as err:
        manifest.load_config('seeded_toy')
    assert named in str(err.value) and 'seeded_toy' in str(err.value)


@pytest.mark.parametrize('weights', [
    {}, {'seeded': False}, {'seeded': True, 'checkpoint': 'x.ckpt'},
    {'seeded': 'yes'}])
def test_weights_are_a_checkpoint_or_seeded_and_not_both(tmp_path, weights):
    manifest = _broken(tmp_path, lambda c: c.update(weights=weights))
    with pytest.raises(ManifestError) as err:
        manifest.load_config('seeded_toy')
    assert 'weights' in str(err.value)


@pytest.mark.parametrize('name', PAIR)
def test_shipped_configurations_state_all_three_explicitly(name,
                                                           either_root):
    contracts.the_pair_states_all_three_explicitly(either_root, name)


@pytest.mark.parametrize('name', list(Manifest().configs))
def test_any_configuration_states_its_weights_checks_and_flops(name,
                                                               either_root):
    contracts.any_configuration(either_root, name)


def test_a_seeded_configuration_states_them_too(fifth):
    for name in fifth.configs:
        contracts.any_configuration(fifth, name)
    assert fifth.load_config(make_root.CONFIG)['weights']['seeded'] is True


def _with_a_checkpoint(manifest):
    return [name for name in manifest.configs
            if 'checkpoint' in manifest.load_config(name)['weights']]


def test_the_configurations_with_a_checkpoint_are_found_by_the_kind(
        fifth, either_root):
    assert _with_a_checkpoint(fifth) == PAIR == _with_a_checkpoint(
        either_root)


@pytest.mark.parametrize('name', _with_a_checkpoint(Manifest()))
def test_a_checkpoints_variables_are_the_files_bytes(name):
    """``weights.checkpoint``: the learner's ``init_params`` and the checks'
    variables are the same file, leaf for leaf."""
    import jax
    import numpy as np
    from flax import serialization
    manifest = Manifest()
    config = manifest.load_config(name)
    train_args = merged_args(config, manifest.load_traffic('sgd_heavy'),
                             SEED)['train_args']
    path = os.path.join(ROOT, config['weights']['checkpoint'])
    assert train_args['init_params'] == path
    got = checks.starting_variables(config, train_args)
    with open(path, 'rb') as f:
        want = serialization.msgpack_restore(f.read())
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sum(np.asarray(a).size for a in jax.tree_util.tree_leaves(got)) \
        == config['model']['parameters']


def test_seeded_weights_are_the_seeds_and_no_files(fifth):
    """The twin of the test above for ``weights.seeded``: no ``init_params``
    reaches the learner, the same seed gives the same variables leaf for
    leaf, another seed gives others."""
    config = fifth.load_config(make_root.CONFIG)
    traffic = fifth.load_traffic(make_root.TRAFFIC)
    train_args = merged_args(config, traffic, SEED)['train_args']
    assert 'init_params' not in train_args
    first = toy.digest(checks.starting_variables(config, train_args))
    again = merged_args(config, traffic, SEED)['train_args']
    assert toy.digest(checks.starting_variables(config, again)) == first
    other = merged_args(config, traffic, SEED + 1)['train_args']
    assert toy.digest(checks.starting_variables(config, other)) != first
