"""Multi-host wiring test: two real processes join one jax.distributed job
on the CPU backend, see the global device set, and run a cross-process
collective. This validates the path train_main activates via
``_init_multihost`` (train.py) / ``multihost.initialize`` before any JAX
use — the learner-side counterpart of the reference's multi-node story
(which only ever distributes CPU actors, reference worker.py:185-254)."""

import os
import socket
import subprocess
import sys

import pytest

_CHILD = r"""
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
sys.path.insert(0, %(repo)r)
from handyrl_tpu.parallel import multihost

ok = multihost.initialize()          # resolved from JAX_COORDINATOR_ADDRESS
assert ok, 'env-driven initialize() should activate'
assert multihost.is_coordinator() == (jax.process_index() == 0)

import jax.numpy as jnp
from jax.experimental import multihost_utils
# one real cross-process collective: everyone receives process 0's value
val = multihost_utils.broadcast_one_to_all(
    jnp.asarray(100.0 + jax.process_index()))
print('OK', jax.process_index(), jax.process_count(), jax.device_count(),
      float(val), flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(('', 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.timeout(180)
def test_two_process_jax_distributed_cpu(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / 'child.py'
    script.write_text(_CHILD % {'repo': repo})
    port = _free_port()

    children = []
    for pid in range(2):
        env = dict(os.environ,
                   JAX_PLATFORMS='cpu',
                   JAX_COORDINATOR_ADDRESS='localhost:%d' % port,
                   JAX_NUM_PROCESSES='2',
                   JAX_PROCESS_ID=str(pid))
        env.pop('XLA_FLAGS', None)   # 1 device per process, no virtual mesh
        children.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outputs = []
    for proc in children:
        out, _ = proc.communicate(timeout=150)
        outputs.append(out)
        assert proc.returncode == 0, out

    for pid, out in enumerate(outputs):
        line = next(l for l in out.splitlines() if l.startswith('OK'))
        _, idx, count, devices, val = line.split()
        assert int(idx) == pid
        assert int(count) == 2
        assert int(devices) == 2          # global view: one CPU device each
        assert float(val) == 100.0        # coordinator's value won


_TRAIN_CHILD = r"""
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
sys.path.insert(0, %(repo)r)
from handyrl_tpu.parallel import multihost

ok = multihost.initialize()
assert ok, 'env-driven initialize() should activate'

import hashlib
import numpy as np
import jax.numpy as jnp
from __graft_entry__ import _synthetic_batch
from handyrl_tpu.models import build
from handyrl_tpu.ops.losses import LossConfig
from handyrl_tpu.ops.train_step import build_update_step, init_train_state
from handyrl_tpu.parallel import partition

# the global 2-device mesh (1 CPU device per process)
mesh = multihost.global_mesh()
assert int(np.prod(list(mesh.shape.values()))) == 2, mesh

# identical construction on both processes: params replicate, and each
# process contributes its OWN half of the global batch
module = build('SimpleConv2dModel')
rng = np.random.RandomState(0)
gbatch = _synthetic_batch(8, 4, 1, (3, 3, 3), 9, rng)
params = module.init(jax.random.PRNGKey(0),
                     gbatch['observation'][:, 0, 0], None)
state = init_train_state(params)
cfg = LossConfig(turn_based_training=False, observation=True,
                 policy_target='TD', value_target='TD', gamma=0.9)
shardings = partition.tree_shardings(mesh, state, partition.DEFAULT_RULES)
step = build_update_step(module, cfg, mesh=mesh, donate=False,
                         state_shardings=shardings)

pid = jax.process_index()
local = jax.tree_util.tree_map(lambda x: x[4 * pid:4 * (pid + 1)], gbatch)
batch = partition.host_to_global_batch(mesh, local)
state2, metrics = step(state, batch,
                       jnp.asarray(1e-4, jnp.float32))

# every leaf is replicated: hash THIS process's local replica; the parent
# asserts both processes hold bit-identical updated params
h = hashlib.sha1()
for path, leaf in sorted(
        jax.tree_util.tree_flatten_with_path(state2.params)[0],
        key=lambda kv: str(kv[0])):
    h.update(np.asarray(leaf.addressable_shards[0].data).tobytes())
print('OK', jax.process_index(), int(state2.steps.addressable_shards[0].data),
      h.hexdigest(),
      float(np.asarray(metrics['total'].addressable_shards[0].data)),
      flush=True)
"""


@pytest.mark.timeout(240)
def test_two_process_sharded_train_step(tmp_path):
    """The learner-side multi-host story end to end on a 2-process CPU
    mesh: jax.distributed via parallel/multihost.py (gloo collectives), the
    partition-rule-built NamedSharding train step over the global mesh,
    each process feeding its local batch shard — and both processes ending
    with bit-identical replicated params."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / 'train_child.py'
    script.write_text(_TRAIN_CHILD % {'repo': repo})
    port = _free_port()

    children = []
    for pid in range(2):
        env = dict(os.environ,
                   JAX_PLATFORMS='cpu',
                   JAX_COORDINATOR_ADDRESS='localhost:%d' % port,
                   JAX_NUM_PROCESSES='2',
                   JAX_PROCESS_ID=str(pid))
        env.pop('XLA_FLAGS', None)   # 1 device per process
        children.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outputs = []
    for proc in children:
        out, _ = proc.communicate(timeout=210)
        outputs.append(out)
        assert proc.returncode == 0, out

    rows = []
    for pid, out in enumerate(outputs):
        line = next(l for l in out.splitlines() if l.startswith('OK'))
        _, idx, steps, digest, loss = line.split()
        assert int(idx) == pid
        assert int(steps) == 1           # one SGD step applied everywhere
        rows.append((digest, float(loss)))
    # identical replicated params AND identical (psum'd) loss on both hosts
    assert rows[0][0] == rows[1][0]
    assert rows[0][1] == pytest.approx(rows[1][1], rel=1e-6)


def test_initialize_noop_without_configuration(monkeypatch):
    for var in ('JAX_COORDINATOR_ADDRESS', 'COORDINATOR_ADDRESS',
                'MEGASCALE_COORDINATOR_ADDRESS'):
        monkeypatch.delenv(var, raising=False)
    from handyrl_tpu.parallel import multihost
    assert multihost.initialize() is False
