"""A synthetic traced run for ``readers/traced_fill_roofline``: the recorder's
spans and a profiler trace shaped as the fused loop leaves them, with the
answers known by construction. No test of its own: the four
``test_bench_<configuration>.py`` and ``test_bench_traced_fill.py`` build
their cases from it.

The loop it draws (``ops/fused_pipeline.py``): call d of ``train_step``
enqueues program d and then fetches chunk d - 1, so program d starts when
program d - 1 ends and call d returns just behind that. The profiler starts
behind the call that closed the window (its annotation is not in the trace,
its program is) and stops behind a later call. The device's clock LEADS the
host's by 1.3 ms, as in the trace recorded on a v5e
(``benchmark/testdata/toy.xplane.pb``).
"""

import os

import numpy as np

from benchmark import reduce_trace
from benchmark.readers import traced_fill_roofline
from benchmark.record import Run

MODULE = 'jit_fused_pipeline_train'
LEAD_NS = 1_300_000         # the device's events lead the host's
FETCH_NS = 200_000          # a call returns this long behind its program
T0_NS = 50_000_000          # the first traced program's start, trace clock
HOST_EPOCH_S = 1000.0       # perf_counter at the trace clock's zero


def dones(starts, chunks, plies):
    """``chunks`` arrays (plies, lanes) of done flags after which lane l
    stands at ply index ``starts[l]``: one game's end a lane, none where the
    lane has played since the first ply."""
    starts = np.asarray(starts)
    total = chunks * plies
    assert (starts <= total).all()
    flat = np.zeros((total, len(starts)), bool)
    for lane, at in enumerate(starts):
        if at < total:
            flat[total - 1 - at, lane] = True
    return list(flat.reshape(chunks, plies, len(starts)))


def starts_for(mean_rows, plies, lanes, spread=64):
    """Ply indices at a chunk's first ply, ``spread`` apart from lane to
    lane, at which a query of a layer that sees everything reads
    ``mean_rows`` rows at the chunk's mean: ``p + 1`` over ``plies`` plies
    from ``start`` is ``start + (plies + 1) / 2``."""
    centre = mean_rows - (plies + 1) / 2
    offsets = (np.arange(lanes) - (lanes - 1) / 2) * spread
    starts = np.round(centre + offsets).astype(int)
    assert (starts >= 0).all(), starts
    return starts


def trace_text(programs, scope_path):
    """The ``.xplane.pb`` as text. ``programs``: one ``(phase ns, inner ns)``
    an execution of ``MODULE``, back to back from ``T0_NS``: a top-level
    ``while`` of ``phase ns`` under the scope ``rollout`` that holds a fusion
    of ``inner ns`` under ``scope_path``, then 1 ms under ``sgd``. Program i
    (0-based) was enqueued by call i; every call but the first (the one that
    closed the window) carries an annotation, from its program's enqueue
    (1 ms behind the previous call's end) to ``FETCH_NS`` behind the END of
    the program before, on the host's clock (the device's is ``LEAD_NS``
    ahead).
    Returns ``(text, [(start ns, end ns) of every call])``."""
    device, ops, calls = [], [], []
    at = T0_NS
    previous_end = T0_NS - 10_000_000      # program -1's, as the host sees it
    for phase, inner in programs:
        end = at + phase + 1_000_000
        device.append((at, end))
        ops += [(2, at, phase), (3, at + 1000, inner),
                (4, at + phase, 1_000_000)]
        # the host's clock: the call returns behind the program before
        call_end = at + LEAD_NS + FETCH_NS
        calls.append((previous_end + 1_000_000, call_end))
        previous_end = call_end
        at = end
    # the call behind the last program fetches it
    calls.append((previous_end + 1_000_000, at + LEAD_NS + FETCH_NS))

    def events(rows):
        return '\n'.join(
            '    events { metadata_id: %d offset_ps: %d duration_ps: %d }'
            % (key, start * 1000, duration * 1000)
            for key, start, duration in rows)
    text = '''
planes { id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "%s(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%%while.1 = (s32[]) while(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/rollout/while" } } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.3 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/rollout/while/body/%s/dot_general" } } }
  event_metadata { key: 4 value { id: 4 name: "%%fusion.4 = bf16[64,8] fusion(...)" stats { metadata_id: 1 str_value: "jit(fused_pipeline_train)/sgd/while/body/dot_general" } } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
%s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
%s }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "bench:train_dispatch" } }
  lines { id: 7 name: "python" timestamp_ns: 0
%s }
}
''' % (MODULE, scope_path,
       events((1, start, end - start) for start, end in device),
       events(ops),
       events((1, start, end - start)
              for start, end in calls[1:]))
    return text, calls


def write_trace(folder, text):
    from jax.profiler import ProfileData
    os.makedirs(str(folder), exist_ok=True)
    path = os.path.join(str(folder), 'host.xplane.pb')
    with open(path, 'wb') as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def traced_run(folder, manifest, cell, config, traffic, train_args, chunks,
               first_traced, times, scope_path, drop_chunk=None):
    """A ``Run`` as ``session`` would hand the readers one. ``chunks``: the
    done flags (plies, lanes) of every chunk from the learner's first on, the
    traced ones included; traced program i played chunk ``first_traced + i``
    (an ordinal, from 1) and took ``times[i]`` = ``(phase s, inner s)``. The
    window closed with the call that enqueued the first traced program.
    ``drop_chunk``: the ordinal of a chunk whose ``chunk_plies`` record is
    left out."""
    text, calls = trace_text(
        [(int(a * 1e9), int(b * 1e9)) for a, b in times], scope_path)
    path = write_trace(folder, text)
    reduced = reduce_trace.reduce(path, window_span='train_dispatch')
    host = lambda ns: HOST_EPOCH_S + ns / 1e9
    dispatches = [(host(a), host(b), {'dispatches': first_traced + i})
                  for i, (a, b) in enumerate(calls)]
    fetched = [(0.0, float(n), {'chunk': n, 'done': done})
               for n, done in enumerate(chunks, 1) if n != drop_chunk]
    peaks = manifest.load_peaks()['TPU v5 lite']
    window = (dispatches[0][1] - 51.0, dispatches[0][1])
    return Run(cell, config, traffic, train_args,
               {'train_dispatch': dispatches, 'chunk_plies': fetched},
               window, trace=reduced,
               names={'peak.' + k: v for k, v in peaks.items()
                      if k != 'source'})


def one_program_at(folder, manifest, cell, config, traffic, train_args,
                   starts, times, scope_path, **kwargs):
    """``traced_run`` of ONE traced program (what a trunk cell's traced
    second holds) whose chunk finds the lanes at ``starts`` and ends no
    game: as many chunks of history as bring them there."""
    plies = int(train_args['device_chunk_steps'])
    history = int(max(starts)) // plies + 2
    chunks = dones(starts, history, plies) + [
        np.zeros((plies, len(starts)), bool)]
    return traced_run(folder, manifest, cell, config, traffic, train_args,
                      chunks, history + 1, [times], scope_path, **kwargs)


# -- the cases the four configurations' tests share ----------------------------
# (case, the traced chunk's mean ply index as a multiple of the games' mean)
CASES = [('time_follows_fill', 0.7), ('time_follows_fill', 1.0),
         ('time_follows_fill', 1.3), ('reads_every_row', 1.0),
         ('unpaired', 1.0)]
HBM, BF16 = 819e9, 197e12    # peaks.json, TPU v5 lite


def rows_by_hand(model, kind, index):
    """The rows a decode query of ``kind`` must see at ply index ``index``,
    by the layer kind's equation (not by ``flops_<x>.rows_seen_at``)."""
    if kind in ('global', 'full'):
        return min(index + 1, model['max_positions'])
    if kind in ('window', 'sliding'):
        return min(index + 1, model['window_size'])
    assert kind == 'eva', kind
    window, chunk = model['window_size'], model['chunk_size']
    return index % window + 1 + (index // window) * (window // chunk)


def required_seconds(model, split, sgd_flops, starts, every_row=False):
    """What ONE dispatch requires when its chunk finds the lanes at
    ``starts`` and ends no game: ply by ply and lane by lane; with
    ``every_row`` each query reads its buffer to the end instead."""
    total = 0.0
    for ply in range(split['plies']):
        for kind, per_row in split['row_bytes'].items():
            last = model['max_positions'] - 1
            rows = [rows_by_hand(model, kind, last if every_row else s + ply)
                    for s in starts]
            total += per_row * sum(rows) / len(rows)
    return (sgd_flops / BF16
            + (split['plies'] * split['ply_bytes'] + total) / HBM)


def roofline_case(folder, manifest, cell, config, traffic, train_args, metric,
                  case, fill, mean_index, bandwidth=HBM):
    """One of ``CASES`` through ``metric``'s own file. Returns ``(what the
    reader gave, the share the case has by construction, the old
    expression's value)``: ``time_follows_fill``: the dispatch takes exactly
    the chip's least time for the rows its own counters reached (100%);
    ``reads_every_row``: the time of every row of the buffers, the bytes at
    ``bandwidth``; ``unpaired``: the traced program's chunk was never
    recorded."""
    spec = manifest.load_metric(metric)
    assert spec['reader'] == 'traced_fill_roofline'
    args = spec['args']
    model = config['model']
    split = model[args['rollout'].rsplit('.', 1)[1]]
    sgd_flops = (model[args['sgd_flops'].rsplit('.', 1)[1]]
                 if 'sgd_flops' in args else 0)
    plies, lanes = split['plies'], int(train_args['generation_envs'])
    starts = starts_for(fill * mean_index + 1, plies, lanes)
    required = required_seconds(model, split, sgd_flops, starts)
    measured = required
    if case == 'reads_every_row':
        whole = required_seconds(model, split, sgd_flops, starts, True)
        measured = sgd_flops / BF16 + (whole - sgd_flops / BF16) \
            * HBM / bandwidth
    # a top-level phase is the `while`; an inner scope the fusion inside it
    times = ((measured, measured / 3) if 'scopes' in args
             else (1.5 * measured, measured))
    kwargs = {}
    if case == 'unpaired':
        kwargs['drop_chunk'] = int(max(starts)) // plies + 3
    run = one_program_at(folder, manifest, cell, config, traffic, train_args,
                         starts, times, args['scope'], **kwargs)
    analytic = sgd_flops / BF16 + split['plies'] * (
        split['ply_bytes'] + sum(
            per_row * split['analytic_rows'][kind]
            for kind, per_row in split['row_bytes'].items())) / HBM
    return (traced_fill_roofline.read(run, **args),
            100 * required / measured, 100 * analytic / measured)
