"""Hooks: resolution by name, the shim's record, the shipped hook files."""

import os

import pytest

from benchmark import hooks
from benchmark.manifest import ROOT, Manifest, ManifestError
from benchmark.session import spans_of


class _Thing:
    def __init__(self):
        self.count = 0
        self.args = {'batch_size': 8}

    def work(self, n):
        self.count += n
        return {'metrics': {'loss': 1.5}, 'n': n}


def test_unresolved_hook_fails_by_name():
    rec = hooks.Recorder()
    with pytest.raises(hooks.HookError) as err:
        hooks.install({'gone': {'target': 'handyrl_tpu.train:Learner.no_such_method'}}, rec)
    assert 'handyrl_tpu.train:Learner.no_such_method' in str(err.value)
    with pytest.raises(hooks.HookError) as err:
        hooks.install({'gone': {'target': 'handyrl_tpu.no_such_module:f'}}, rec)
    assert 'no_such_module' in str(err.value)


def test_shim_records_times_and_captures_and_uninstalls():
    rec = hooks.Recorder()
    seen = []
    rec.on('work', lambda t0, t1, cap: seen.append(cap))
    spec = {'target': __name__ + ':_Thing.work',
            'capture': {'count': 'self.count', 'batch': 'self.args.batch_size',
                        'loss': 'ret.metrics', 'n': 'arg1'}}
    undo = hooks.install({'work': spec}, rec)
    try:
        thing = _Thing()
        assert thing.work(3)['n'] == 3
        thing.work(4)
    finally:
        undo()
    (t0, t1, cap), (_, _, cap2) = rec.spans['work']
    assert t0 <= t1
    assert cap == {'count': 3, 'batch': 8, 'loss': {'loss': 1.5}, 'n': 3}
    assert cap2['count'] == 7 and seen == [cap, cap2]
    _Thing().work(1)
    assert len(rec.spans['work']) == 2   # unwrapped again


def test_every_shipped_hook_resolves():
    folder = os.path.join(ROOT, 'benchmark', 'hooks')
    spans = sorted(f[:-5] for f in os.listdir(folder) if f.endswith('.json'))
    specs = Manifest().load_hooks(spans)
    assert {'train_dispatch', 'warm_dispatch', 'chunk_account',
            'chunk_fetch', 'epoch_boundary', 'update_model',
            'eval_share'} <= set(specs)
    for span, spec in specs.items():
        hooks.resolve(spec['target'])


def test_no_hook_is_installed_that_nothing_reads():
    """Every hook file is named by some cell's window or metric. The hook
    ``state_fetch`` went with PR 45: it wrapped ``utils.fetch:fetch_tree``,
    which an enqueue-first boundary has not called since PR 35, and no
    metric read its records; ``fetch_wait_ms`` reads the PROGRAM's span of
    that name by ``stage``, for which the harness installs nothing."""
    manifest = Manifest()
    folder = os.path.join(ROOT, 'benchmark', 'hooks')
    files = {f[:-5] for f in os.listdir(folder) if f.endswith('.json')}
    named = set()
    for workload, cell in manifest.cells.items():
        window = manifest.load_traffic(cell['traffic'])['window']
        named |= set(spans_of(manifest, workload, window))
    assert files == named
    assert 'state_fetch' not in files
    spec = manifest.load_metric('fetch_wait_ms')
    assert spec['args']['also'] == ['state_fetch']
    assert not {'span', 'inner'} & set(spec['args'])


@pytest.mark.parametrize('workload', list(Manifest().cells))
def test_a_cell_installs_only_the_hooks_its_own_files_name(workload):
    """A hook file that a later PR adds wraps nothing in an old cell."""
    manifest = Manifest()
    window = manifest.load_traffic(manifest.cell(workload)['traffic'])['window']
    spans = spans_of(manifest, workload, window)
    named = {window['dispatch_span'], window['account_span'],
             window['fetch_span'], *window['open_after'], *window['spans']}
    for name in manifest.metrics_of(workload):
        args = manifest.load_metric(name).get('args', {})
        named |= {args[k] for k in ('span', 'inner') if k in args}
    assert set(spans) == named
    assert set(manifest.load_hooks(spans)) == named   # every one has a file
    with pytest.raises(ManifestError):
        manifest.load_hooks(['no_such_span'])
