"""The host's copies of the day, as the program's own spans split them: a
traced fit's tail and `fit.stack`'s two halves.

Names beyond `jobs/fit_spans.SPANS` (a fixed tuple; so this file loads its
own from the same trace, as `jobs/est_spans.py` does):

  em.host_sync          blocking on the device; then the reads of
                        `steps_done`, sweeps and likelihoods, the float64
                        stop rule, likelihood.dat's lines
  fit.readback          gamma, then beta, to the host; inside it, per device
                        array:
    fit.readback.d2h      `to_host`: the transfer and its float64 copy
                          (`.counts`: `bytes` as they left the device,
                          `shards`)
    fit.readback.scatter  the masked stores into the result's gamma
                          (`.counts`: `rows`, `bytes`)
  fit.save              the result's files
  fit.teardown          dropping the trainer and the batches' host buffers
  fit.stack             inside it, per shape group:
    fit.stack.copy        assembling the group's host stack: since PR 39 a
                          view of `make_batches`' buffer where the batches
                          allow it (then the masks alone are copied), one
                          `np.stack` pass where they do not (`.counts`:
                          `bytes`, `copied_bytes`)
    fit.stack.put         handing the stack to the runtime (`.counts`:
                          `bytes`, `shards`)

The TAIL of a traced fit is [end of its last EM program on the fullest
device, end of the program's `fit` span]: `fit_readback_s`'s interval less
the microseconds between the root span's end and the annotation's.  The five
`readback_*` metrics (and `est_save_s` where files are written) are the
program's split of it and sum to it; the two `place_stack_*` split
`place_transfer_s`.

A program without these spans (the parent of the PR that added them) gives
nothing, and every reader returns nothing.
"""

from __future__ import annotations

import os

from benchmarks.harness import program_trace, xplane
from benchmarks.jobs import fit_spans, fit_trace

SPANS = ("fit", "em.host_sync", "fit.readback", "fit.readback.d2h",
         "fit.readback.scatter", "fit.save", "fit.teardown", "fit.stack",
         "fit.stack.copy", "fit.stack.put")
# What the tail is split into; `fit.readback` itself is left out, so that
# its own time (between its sub-spans) reads as unattributed.
TAIL_PARTS = ("em.host_sync", "fit.readback.d2h", "fit.readback.scatter",
              "fit.save", "fit.teardown")
# A fit that holds none of these is a program from before them.
SUBSPANS = ("fit.readback.d2h", "fit.readback.scatter", "fit.stack.copy",
            "fit.stack.put")
COUNTS = fit_spans.COUNTS
# The sum of a key over a span's `.counts` events of one fit.
counted = fit_spans.counted

_loaded: dict = {}


def spans(ctx: dict) -> list:
    """The spans named in SPANS: what a test put under
    `ctx["program_trace"]["spans"]`, else those of the run's newest
    `.xplane.pb` (read once per file)."""
    if "program_trace" in ctx:
        return [e for e in ctx["program_trace"]["spans"]
                if program_trace.is_span(e[0], SPANS)]
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
    holds a program `fit` span carrying at least one of this file's
    sub-spans:
      call      (start, end) of the benchmark's annotation
      fit       (start, end) of the program's `fit` span
      place     (start of the `fit` span, start of the fit's first EM
                program on the device): the placement
      tail      (end of the last EM program, end of the `fit` span)
      spans     [(name, start, end)] on the root's thread inside the
                annotation, in order
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
        inside, counts = [], {}
        for name, s, d, stats, at in loaded:
            if at != line or s < lo or s + d > hi:
                continue
            if name.endswith(COUNTS):
                counts.setdefault(name[:-len(COUNTS)], []).append(stats)
            else:
                inside.append((name, s, s + d))
        if not any(name in SUBSPANS for name, _, _ in inside):
            continue
        fits.append({"call": (lo, hi), "fit": (start, start + dur),
                     "place": (start, programs[0][0]),
                     "tail": (programs[-1][1], start + dur),
                     "spans": inside, "counts": counts})
    return fits


def seconds(fit: dict, names, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that lie under the fit's spans named in
    `names`."""
    return sum(max(min(e, hi) - max(s, lo), 0.0)
               for name, s, e in fit["spans"] if name in names)


def mean(ctx: dict, names, part: str):
    """Mean over the traced fits of the seconds under the spans `names`
    inside the fit's `part` ("tail" or "place"); nothing without spans."""
    fits = per_fit(ctx)
    if not fits:
        return None
    return sum(seconds(f, names, *f[part]) for f in fits) / len(fits)

