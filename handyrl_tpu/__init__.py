"""handyrl_tpu — a TPU-native distributed self-play RL framework.

Capability peer of DeNA/HandyRL (IMPALA-style learner/worker self-play with
TD(lambda) / Monte-Carlo / V-Trace / UPGO off-policy corrections), rebuilt
JAX-first: Flax models, a single jit/pjit-compiled update step over a device
mesh, batched actor inference, and host-side Python only for environments and
orchestration.
"""

__version__ = "0.1.0"

# Runtime concurrency sanitizer (analysis/sanitizer.py): opt-in via
# HANDYRL_TPU_SANITIZE=1 — the chaos/e2e CI legs run under it. It must
# install BEFORE any framework lock or thread exists, which is exactly
# import time; unset (the default) this is a single env check and the
# package import stays side-effect free.
import os as _os
if _os.environ.get('HANDYRL_TPU_SANITIZE', '').strip().lower() \
        not in ('', '0', 'false', 'off'):
    from .analysis import sanitizer as _sanitizer
    _sanitizer.install_from_env()

# The persistent XLA compilation cache's default home: one fixed directory
# inside the checkout (listed in .gitignore and .chiprunignore). The path is
# part of what makes a cache reusable — no host fingerprint, home directory,
# pid or temporary name in it — so a second run from the same checkout hits.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    '.jax_cache')

_cache_dir = None


def setup_compile_cache() -> str:
    """Enable the persistent XLA compilation cache (explicit, idempotent);
    returns the directory in use.

    The fused pipeline and the recurrent update steps take tens of seconds
    to compile; caching makes every compile a one-time cost across
    processes and runs. Called from the framework's own entry points (CLI,
    Learner, spawned workers, tests) — NOT at package import, so
    embedding applications keep full control of jax config and ``import
    handyrl_tpu`` stays side-effect free.

    Placement: where the operator set ``JAX_COMPILATION_CACHE_DIR`` (jax
    reads it into its config at import) nothing is set in code; otherwise
    the cache lives in :data:`COMPILE_CACHE_DIR`. Spawned children resolve
    the same place by the same rule. XLA:CPU entries are specialized to the
    compiling host's CPU, so a checkout on a filesystem shared between
    different machines should place the cache per host with the variable.
    A directory that cannot be created raises here, at start-up, instead
    of leaving a run that silently never caches.
    """
    global _cache_dir
    if _cache_dir is not None:
        return _cache_dir
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        jax.config.update('jax_compilation_cache_dir', cache_dir)
        # cache across backends including CPU, and even quick compiles —
        # the test suite re-traces the same programs constantly
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.5)
    _os.makedirs(cache_dir, exist_ok=True)
    _cache_dir = cache_dir
    return cache_dir


def claim_devices(role: str, mesh=None) -> dict:
    """Print the one start-up line every device-claiming process owes its
    operator: which backend jax landed on, the device kind, how many
    devices it found and how many this process will use (the mesh's, or
    one). jax falls back to
    the CPU by itself when an accelerator cannot be reached, and a mesh
    gate may train on fewer devices than the host has — this line is where
    both show. ``chip_smoke.py`` parses it (``device_claim <json>``)."""
    import json

    import jax
    devices = jax.devices()
    claim = {
        'role': role,
        'backend': jax.default_backend(),
        'platform': devices[0].platform,
        'device_kind': devices[0].device_kind,
        'found': len(devices),
        'used': len(mesh.devices.flat) if mesh is not None else 1,
        'mesh': ({k: int(v) for k, v in mesh.shape.items()}
                 if mesh is not None else None),
        'jax': jax.__version__,
    }
    print('device_claim ' + json.dumps(claim), flush=True)
    return claim
