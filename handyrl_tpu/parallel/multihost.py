"""Multi-host TPU initialization helpers.

On a multi-host pod slice every host runs the same program; JAX needs the
distributed runtime initialized before first use so `jax.devices()` sees the
global device set. The learner's mesh helpers (parallel/mesh.py) then span
hosts transparently: data-parallel sharding puts the gradient all-reduce on
ICI within a slice and DCN across slices.

Typical launch (one learner process per host):

    from handyrl_tpu.parallel import multihost
    multihost.initialize()           # no-op on single-host
    ...
    train_main(args)

Worker hosts (CPU episode generators) do NOT call this — they are plain
processes speaking the framed-TCP protocol to the learner host.
"""

from __future__ import annotations

import os
from typing import Optional


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Initialize jax.distributed when running multi-host; returns True when
    distributed mode was activated.

    With no arguments, uses the standard cluster-environment autodetection
    (TPU pod metadata / JAX_COORDINATOR_ADDRESS etc.); single-host runs are
    detected and left untouched.
    """
    import jax

    if coordinator_address is None:
        # NB: MEGASCALE_COORDINATOR_ADDRESS is deliberately NOT consulted —
        # it names libtpu's multislice DCN transport endpoint, not the
        # jax.distributed coordinator service
        coordinator_address = next(
            (os.environ[k] for k in
             ('JAX_COORDINATOR_ADDRESS', 'COORDINATOR_ADDRESS')
             if os.environ.get(k)), None)
        if coordinator_address is None:
            return False
    if num_processes is None and os.environ.get('JAX_NUM_PROCESSES'):
        num_processes = int(os.environ['JAX_NUM_PROCESSES'])
    if process_id is None and os.environ.get('JAX_PROCESS_ID'):
        process_id = int(os.environ['JAX_PROCESS_ID'])

    # (XLA:CPU's cross-process collectives ride gloo, jax's default)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def global_mesh(model_parallel: int = 1):
    """Mesh over ALL devices in the (possibly multi-host) job."""
    from .mesh import make_mesh
    import jax
    return make_mesh(jax.devices(), model_parallel=model_parallel)


def is_coordinator() -> bool:
    import jax
    return jax.process_index() == 0
