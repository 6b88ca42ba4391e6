"""Device duration of one XLA module (a compiled program) in the traced
window, in milliseconds: ``stat`` (``median``) over its executions that lie
wholly inside the window. args: ``module``, e.g. ``jit_fused_pipeline_train``."""

from ..record import quantile


def read(run, module, stat='median'):
    if not run.trace:
        return None
    durations = run.trace['modules'].get(module)
    if not durations:
        return None
    return {'value': quantile(durations, {'median': 0.5}[stat]) * 1e3,
            'samples': len(durations)}
