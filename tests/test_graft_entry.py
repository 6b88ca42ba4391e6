"""Driver entry points: entry() compiles, dryrun_multichip executes."""

import jax

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    fn, (params, obs) = graft.entry()
    out = jax.jit(fn)(params, obs)
    assert out['policy'].shape == (64, 4)
    assert out['value'].shape == (64, 1)


def test_dryrun_multichip_two_devices(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)   # the dry run checkpoints under its cwd
    graft.dryrun_multichip(2)


def test_dryrun_multichip_eight_devices(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    graft.dryrun_multichip(8)
