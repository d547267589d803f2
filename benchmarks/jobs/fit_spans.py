"""The program's own spans of a fit, as the per-layer readers read them.

`oni_ml_tpu.models.lda.train_corpus` is one span `fit` with the fit's layer
boundaries under it (`oni_ml_tpu/telemetry/spans.py`; under jax's profiler
each is an event on the host plane, on the device trace's clock).  The names
are the program's contract with these readers:

  fit            the root: train_corpus, entry to return
  fit.engine     which E-step engine (plan cache, or the crossover measured)
  fit.batches    make_batches / the bucketed layout, on the host
  fit.init       the trainer, checkpoint restore, beta's initialisation
  fit.plan       host-only decisions: dense or not, corpus dtype, blocks
  fit.stack      np.stack of the batches and their transfer
  fit.densify    the dense corpus, one jit per group
  fit.runner     building the chunk dispatch and its first arguments
  em.run_chunk   enqueue of one EM chunk program; a fit's first one holds
                 the program's trace, lowering and cache fetch
  em.host_sync   blocking on the device, the float64 stop rule
  fit.readback   gamma and beta to the host
  fit.save       the result's files

A span `<name>` may be followed, inside it, by one event `<name>.counts`
whose stats are what was known only after the work (`fit.counts`:
`em_iters`, `doc_sweeps`, `compile_requests`...; `fit.batches.counts`:
`rows`, the padded rows of one EM iteration).

Only the window's fits are traced, each inside the benchmark's own
`bench:fit` annotation; `per_fit` hands a reader the program's spans of each
traced fit that ran an EM program on the device.  A program without spans
(the parent of the PR that added them) gives no fits, and every reader
returns nothing.
"""

from __future__ import annotations

import os

from benchmarks.harness import program_trace, xplane
from benchmarks.jobs import fit_trace

SPANS = ("fit", "fit.engine", "fit.batches", "fit.init", "fit.plan",
         "fit.stack", "fit.densify", "fit.runner", "em.run_chunk",
         "em.host_sync", "fit.readback", "fit.save")
COUNTS = ".counts"

_loaded: dict = {}


def spans(ctx: dict) -> list:
    """The program's spans in the run's trace (`program_trace.load_spans`):
    what a test put under `ctx["program_trace"]["spans"]`, else those of the
    run's newest `.xplane.pb` (read once per file)."""
    if "program_trace" in ctx:
        return ctx["program_trace"]["spans"]
    path = program_trace.newest(trace_dir=ctx.get("trace_dir"))
    if path is None:
        return []
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = program_trace.load_spans(path, SPANS)
    return _loaded[key]


def per_fit(ctx: dict) -> list:
    """One dict per traced fit that ran an EM program on the device and
    holds a program `fit` span:
      fit       (start, end) of the program's `fit` span
      first_em  when the fit's first EM program started on the device
      children  [(name, start, end, stats)] the spans directly under `fit`,
                in order
      counts    {span name: [stats of each of its `.counts` events]}
    """
    trace, loaded = ctx["trace"], spans(ctx)
    if not loaded:
        return []
    dev = xplane.fullest_device(trace)
    fits = []
    for lo, hi, programs in fit_trace.per_fit(trace, dev):
        roots = [e for e in loaded
                 if e[0] == "fit" and lo <= e[1] and e[1] + e[2] <= hi]
        if not programs or not roots:
            continue
        _, start, dur, _, line = roots[0]
        inside = [e for e in loaded
                  if e[4] == line and start <= e[1] and e[1] + e[2] <= start + dur
                  and e is not roots[0]]
        children, counts, end_of_last = [], {}, start
        for name, s, d, stats, _ in inside:
            if name.endswith(COUNTS):
                counts.setdefault(name[:-len(COUNTS)], []).append(stats)
            elif s >= end_of_last:          # not nested in a sibling
                children.append((name, s, s + d, stats))
                end_of_last = s + d
        fits.append({"fit": (start, start + dur), "first_em": programs[0][0],
                     "children": children, "counts": counts})
    return fits


def counted(fit: dict, span: str, key: str):
    """The sum of `key` over the span's `.counts` events, or None where no
    such event carries it."""
    values = [stats[key] for stats in fit["counts"].get(span, ())
              if isinstance(stats.get(key), (int, float))]
    return sum(values) if values else None


def place_seconds(fit: dict, names=None, first_only: bool = False) -> float:
    """Seconds of the fit's placement, [`fit` start, first EM program on
    the device], that lie under the children named in `names` (all children
    when None; only the first such child with `first_only`)."""
    lo, hi = fit["fit"][0], fit["first_em"]
    total = 0.0
    for name, s, e, _ in fit["children"]:
        if names is None or name in names:
            total += max(min(e, hi) - max(s, lo), 0.0)
            if first_only:
                break
    return total


def mean_place(ctx: dict, names, first_only: bool = False):
    """Mean over the traced fits of `place_seconds`; nothing without
    spans."""
    fits = per_fit(ctx)
    if not fits:
        return None
    return sum(place_seconds(f, names, first_only) for f in fits) / len(fits)
