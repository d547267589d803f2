"""The drop-in CLI's own spans of a traced call, for the readers of the
`est_files` job (`est_load_s`, `est_save_s`).

`oni_ml_tpu.runner.lda_cli.main` is, in the program's spans
(`oni_ml_tpu/telemetry/spans.py`): `est.load` (the model.dat parse; its
`.counts` event says `bytes`, `docs`, `pairs`), a root of its own, then the
fit's root `fit` with `fit.save` under it (whose `.counts` says the bytes of
each file written, `rows` and `values`).  `jobs/fit_spans.SPANS` is a fixed
tuple without the load span, so this file loads its own names from the same
trace; a span is placed by lying inside the job's `bench:fit` annotation of
the traced call.  A program without these spans (the parent of the PR that
added them) gives nothing, and the readers return nothing.
"""

from __future__ import annotations

import os

from benchmarks.harness import program_trace

SPANS = ("est.load", "fit.save")

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


def mean_seconds(ctx: dict, name: str):
    """Mean over the traced calls of the seconds under span `name` inside
    the call's annotation; nothing where no traced call holds it."""
    loaded = spans(ctx)
    per_call = []
    for lo, hi in ctx["trace"]["fits"]:
        inside = [d for n, s, d, _, _ in loaded
                  if n == name and lo <= s and s + d <= hi]
        if inside:
            per_call.append(sum(inside))
    return sum(per_call) / len(per_call) if per_call else None
