"""The fused device pipeline sharded over the 8-virtual-device CPU mesh:
device generation + device window ingest + device replay + SGD, end to end.

This is the multi-chip layout of the flagship loop (round-2 review, item 3):
shard_map over 'data' with per-shard env slices and ring shards, replicated
train state, and gradient psum — the only cross-chip traffic in steady
state. The reference scales actors with worker processes
(reference worker.py:169-254); this scales them with chips.
"""

import glob
import os

import jax
import numpy as np
import pytest

from handyrl_tpu.config import apply_defaults
from handyrl_tpu.train import Learner


@pytest.mark.timeout(560)
def test_ttt_fused_pipeline_sharded_e2e(tmp_path, capsys):
    assert len(jax.devices()) == 8       # conftest's virtual CPU mesh
    args = apply_defaults({
        'env_args': {'env': 'TicTacToe'},
        'train_args': {
            'batch_size': 16, 'forward_steps': 8, 'update_episodes': 30,
            'minimum_episodes': 16, 'generation_envs': 16, 'eval_envs': 8,
            'epochs': 3, 'device_generation': True, 'device_replay': True,
            'sgd_steps_per_chunk': 2, 'device_chunk_steps': 8,
            'model_dir': os.path.join(str(tmp_path), 'models')}})
    ln = Learner(args=args)
    ln.run()
    out = capsys.readouterr().out
    assert 'sharded over 8 devices' in out
    assert '"found": 8, "used": 8, "mesh": {"data": 8, "model": 1}' in out
    assert ln.model_epoch == 3
    assert ln.trainer.steps > 0
    assert ln.num_returned_episodes >= 30 * 3
    ckpts = glob.glob(os.path.join(str(tmp_path), 'models', '*.ckpt'))
    assert any(os.path.basename(p) == 'latest.ckpt' for p in ckpts)


def _ttt_pipeline(mesh, fs, windows_cap, capacity, windower_cls=None):
    """A TicTacToe FusedPipeline (16 envs, chunks of 8 plies) on ``mesh`` or
    one device, and the actor params placed for it."""
    from handyrl_tpu.environment import make_env, make_jax_env
    from handyrl_tpu.model import ModelWrapper
    from handyrl_tpu.ops.device_windows import DeviceWindower
    from handyrl_tpu.ops.fused_pipeline import FusedPipeline
    from handyrl_tpu.ops.losses import LossConfig

    env_args = {'env': 'TicTacToe'}
    env = make_env(env_args)
    env.reset()
    wrapper = ModelWrapper(env.net())
    wrapper.ensure_params(env.observation(0))
    args = apply_defaults({'env_args': env_args, 'train_args': {
        'batch_size': 16, 'forward_steps': fs}})['train_args']
    wd = (windower_cls or DeviceWindower)(
        mode='turn', fs=fs, bi=0, max_steps=9, windows_cap=windows_cap,
        capacity=capacity, num_players=2, gamma=0.8, has_reward=False)
    fp = FusedPipeline(make_jax_env(env_args), wrapper,
                       LossConfig.from_args(args), wd, args, n_envs=16,
                       chunk_steps=8, sgd_steps=2, batch_size=16, mesh=mesh)
    params = wrapper.params
    if mesh is not None:
        params = jax.device_put(params, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    return fp, params


def test_fused_pipeline_state_is_sharded(tmp_path):
    """The loop state really lives on the mesh: env axis and ring rows are
    split over 'data', train params replicated."""
    from handyrl_tpu.parallel.mesh import make_mesh

    fp, params = _ttt_pipeline(make_mesh(), fs=8, windows_cap=1,
                               capacity=64)     # per-shard rows

    def names(arr):
        spec = arr.sharding.spec
        return tuple(spec) if spec else ()

    first_env_leaf = jax.tree_util.tree_leaves(fp.state)[0]
    assert names(first_env_leaf)[:1] == ('data',)
    ring_leaf = next(iter(fp.ring.values()))
    assert ring_leaf.shape[0] == 64 * 8          # global rows = shards x 8
    assert names(ring_leaf)[:1] == ('data',)
    assert np.asarray(fp.cursor).shape == (8,)   # one cursor per shard

    # one warmup dispatch executes across the mesh and returns a global
    # done/outcome pack of the full env count
    parsed = fp.warm_step(params)
    assert parsed is None                        # pipelined one deep
    parsed = fp.warm_step(params)
    assert parsed['done'].shape == (8, 16)
    assert parsed['outcome'].shape == (8, 16, 2)


@pytest.mark.timeout(560)
@pytest.mark.parametrize('sharded', [False, True],
                         ids=['one_device', 'cpu_mesh'])
def test_host_block_counters_are_hand_countable(sharded):
    """``builder_plies``, ``windows_built``, ``windows_ingested`` and
    ``episodes`` on the ``host_block`` span say what the event-driven
    builder did: recounted by hand from the fetched done[K, N] of real
    chunks (the device counts the windows, the host the rest), then from a
    synthetic chunk handed to the parser."""
    import time

    from handyrl_tpu import telemetry
    from handyrl_tpu.parallel.mesh import make_mesh

    FS, W, K, N = 2, 3, 8, 16
    fp, params = _ttt_pipeline(make_mesh() if sharded else None, fs=FS,
                               windows_cap=W,
                               capacity=64 if sharded else 512)

    t_start = time.perf_counter()
    chunks = [fp.warm_step(params) for _ in range(4)][1:] + [fp.drain()]
    done = np.concatenate([c['done'] for c in chunks])       # (4K, N)
    plies = np.zeros(N, int)
    episodes = windows = 0
    for row in done:
        plies += 1
        for lane in np.flatnonzero(row):
            episodes += 1
            windows += min(max(plies[lane] // FS, 1), W)
            plies[lane] = 0
    assert episodes > N and windows > episodes    # 5-9 plies: 2-3 windows
    expect = {'plies': done.size, 'episodes': episodes,
              'builder_plies': int(done.any(axis=1).sum()),
              'windows_built': windows, 'windows_ingested': windows}
    blocks = telemetry.spans('host_block', since=t_start)
    assert len(blocks) == 4
    assert {k: blocks[-1]['attrs'][k] for k in expect} == expect
    assert fp.ring_size_host == min(windows, 512)

    # a synthetic chunk: 5 games end on 3 of the 8 plies, 7 windows stored
    synthetic = np.zeros((K, N), bool)
    synthetic[1, [0, 5]] = synthetic[4, 5] = synthetic[6, [2, 9]] = True
    flat = np.concatenate([synthetic.reshape(-1).astype(np.float32),
                           np.zeros(K * N * 2, np.float32),
                           np.asarray([windows + 7, 0, 7], np.float32)])
    fp._parse((flat, False))
    after = telemetry.spans('host_block', since=t_start)[-1]['attrs']
    assert {k: after[k] - expect[k] for k in expect} == {
        'plies': K * N, 'episodes': 5, 'builder_plies': 3,
        'windows_built': 7, 'windows_ingested': 7}


def test_host_block_carries_the_rows_a_nets_decode_plies_read():
    """A net that can say what a ply reads of its cache (``decode_rows``)
    is asked once a fetched chunk with every lane's ply index at every ply,
    rebuilt from the ``done`` flags across chunks (0 at the first ply, 0
    again behind a ``done``), and the sums of both seats ride ``host_block``;
    a net that cannot leaves the span without them."""
    import time

    from handyrl_tpu import telemetry
    from handyrl_tpu.ops.fused_pipeline import ply_indices

    K, N = 8, 16
    fp, _params = _ttt_pipeline(None, fs=2, windows_cap=3, capacity=64)
    t_start = time.perf_counter()
    rng = np.random.RandomState(3)
    chunks = [rng.rand(K, N) < 0.15 for _ in range(3)]

    def parse(done):
        fp._parse((np.concatenate([
            done.reshape(-1).astype(np.float32),
            np.zeros(K * N * 2 + 3, np.float32)]), False))
        return telemetry.spans('host_block', since=t_start)[-1]['attrs']
    assert 'decode_rows_read' not in parse(chunks[0])
    # reads the rows up to its counter in blocks of 4, holds 12 a sequence
    asked = []
    fp.decode_rows = lambda pos: asked.append(pos) or (
        int(((pos // 4 + 1) * 4).sum()), 12 * pos.size)
    fp.lane_ply[:] = 0
    index = np.zeros(N, int)
    read = 0
    for done in chunks:
        attrs = parse(done)
        for row in done:
            read += 2 * int(((index // 4 + 1) * 4).sum())
            index = np.where(row, 0, index + 1)
        assert attrs['decode_rows_read'] == read
    assert attrs['decode_rows_held'] == 2 * 12 * K * N * 3
    assert fp.lane_ply.tolist() == index.tolist()
    assert [a.shape for a in asked] == [(K, N)] * 3
    plies, after = ply_indices(chunks[2], np.arange(N))
    assert plies[0].tolist() == list(range(N)) and after.shape == (N,)


@pytest.mark.timeout(560)
@pytest.mark.parametrize('sharded', [False, True],
                         ids=['one_device', 'cpu_mesh'])
def test_fused_run_leaves_the_all_lane_builders_ring(sharded):
    """Three dispatches of the fused program (one warm-up, two with SGD) on
    the window builder and on the all-lane builder it replaced
    (tests/windower_oracle.py), same seed: the same games, so the same
    ring, cursors, sizes, key chain and host counters, bit for bit; the
    ring wraps on the way (capacity 8 a shard or 40 in all)."""
    from handyrl_tpu.ops.train_step import init_train_state
    from handyrl_tpu.parallel.mesh import make_mesh
    from windower_oracle import OracleWindower

    sides = []
    for cls in (OracleWindower, None):
        fp, params = _ttt_pipeline(make_mesh() if sharded else None, fs=2,
                                   windows_cap=3,
                                   capacity=8 if sharded else 40,
                                   windower_cls=cls)
        # a copy: the dispatch donates the train state, not the actor's params
        train_state = init_train_state(
            jax.tree_util.tree_map(lambda x: x + 0, params))
        if sharded:
            train_state = jax.device_put(
                train_state, jax.sharding.NamedSharding(
                    fp.mesh, jax.sharding.PartitionSpec()))
        fp.warm_step(params)
        for _ in range(2):
            train_state, parsed = fp.train_step(params, train_state, 1.0)
        last = fp.drain()
        sides.append((fp, train_state, parsed, last))
    (old, state_old, _, last_old), (new, state_new, _, last_new) = sides
    assert old.dispatches == new.dispatches == 3
    np.testing.assert_array_equal(last_new['done'], last_old['done'])
    assert new.episodes_host == old.episodes_host > 16
    assert new.windows_ingested_host == old.windows_ingested_host \
        > new.capacity * new.ndev                 # the ring wrapped
    assert new.ring_size_host == old.ring_size_host
    assert new.builder_plies_host == old.builder_plies_host
    for key in old.ring:
        np.testing.assert_array_equal(np.asarray(new.ring[key]),
                                      np.asarray(old.ring[key]), err_msg=key)
    for name in ('cursor', 'size', 'rng'):
        np.testing.assert_array_equal(np.asarray(getattr(new, name)),
                                      np.asarray(getattr(old, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(new.wstate['counts']),
                                  np.asarray(old.wstate['counts']))
    assert int(state_new.steps) == int(state_old.steps) == 4
    for a, b in zip(jax.tree_util.tree_leaves(state_new.params),
                    jax.tree_util.tree_leaves(state_old.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.timeout(560)
def test_the_fused_sample_reads_a_padded_ring_as_an_unpadded_one(monkeypatch):
    """The ring's wide rows are padded to whole 128-lane tiles (TicTacToe at
    eight plies a window: 216 observation values stored as 256). The fused
    learner's ``sample`` must hand the update step the batch it would gather
    from rows of the logical width holding the same windows (the oracle's
    ring, tests/windower_oracle.py; two dispatches of two steps, same seed,
    so the same games and slots). The update step is replaced by one that
    shows its batch."""
    import jax.numpy as jnp

    from handyrl_tpu.ops import fused_pipeline, train_step
    from handyrl_tpu.ops.device_windows import DeviceWindower, _row_width
    from windower_oracle import OracleWindower

    seen = []

    def showing_update(*args, **kwargs):
        def update(state, batch, lr):
            jax.debug.callback(lambda b: seen.append(b), batch, ordered=True)
            return state, {'seen': jnp.float32(1.0)}
        return update
    monkeypatch.setattr(fused_pipeline, '_update_core', showing_update)

    sides = []
    for cls in (OracleWindower, DeviceWindower):
        fp, params = _ttt_pipeline(None, fs=8, windows_cap=1, capacity=64,
                                   windower_cls=cls)
        train_state = train_step.init_train_state(
            jax.tree_util.tree_map(lambda x: x + 0, params))
        fp.warm_step(params)
        for _ in range(2):
            train_state, _ = fp.train_step(params, train_state, 1.0)
        fp.drain()
        jax.effects_barrier()
        sides.append((fp, seen[:]))
        seen.clear()
    (old, batches_old), (new, batches_new) = sides
    flat = old.ring['observation'].shape[1]
    assert flat == 8 * 27 and _row_width(flat) == 256
    assert new.ring['observation'].shape == (64, 256)
    assert {k: v.shape for k, v in new.ring.items() if k != 'observation'} \
        == {k: v.shape for k, v in old.ring.items() if k != 'observation'}
    assert new.ring_size_host == old.ring_size_host > 16
    assert len(batches_new) == len(batches_old) == 4
    for got, want in zip(batches_new, batches_old):
        assert got['observation'].shape == (16, 8, 1, 3, 3, 3)
        assert np.asarray(got['episode_mask']).any()
        jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
