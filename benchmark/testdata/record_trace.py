#!/usr/bin/env python3
"""Records the small TPU trace that tests/benchmark checks the reducer on.

Run on the chip (``python3 benchmark/testdata/record_trace.py <out_dir>``):
five executions of a named toy program, each inside a ``bench:train_dispatch``
annotation, with a host sleep inside ``bench:epoch_boundary`` after the third
so that the trace holds one long idle gap with a known owner. Writes
``toy.xplane.pb`` and ``toy.summary.txt`` (planes, lines, first events) to
``out_dir``. Nothing here is a measurement.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    def toy_step(x):
        def body(carry, _):
            return jnp.tanh(carry @ carry) * 0.5, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out
    toy_step.__name__ = 'bench_toy_step'
    step = jax.jit(toy_step)
    x = jnp.ones((256, 256), jnp.bfloat16)
    step(x).block_until_ready()

    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, 'toy_trace')
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for i in range(5):
        with TraceAnnotation('bench:train_dispatch'):
            x = step(x)
            x.block_until_ready()
        if i == 2:
            with TraceAnnotation('bench:epoch_boundary'):
                time.sleep(0.02)
    jax.profiler.stop_trace()

    found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)[0]
    shutil.copy(found, os.path.join(out_dir, 'toy.xplane.pb'))
    shutil.rmtree(trace_dir, ignore_errors=True)
    data = ProfileData.from_file(os.path.join(out_dir, 'toy.xplane.pb'))
    with open(os.path.join(out_dir, 'toy.summary.txt'), 'w') as f:
        for plane in data.planes:
            lines = list(plane.lines)
            f.write('plane %r: %d lines\n' % (plane.name, len(lines)))
            for line in lines:
                events = list(line.events)
                f.write('  line %r: %d events\n' % (line.name, len(events)))
                for e in events[:12]:
                    f.write('    %-60s start %.0f dur %.0f\n'
                            % (e.name[:60], e.start_ns, e.duration_ns))
    print('recorded', os.path.getsize(os.path.join(out_dir, 'toy.xplane.pb')),
          'bytes')


if __name__ == '__main__':
    main(sys.argv[1])
