"""estep_glue_pct: share of the device's busy time that the E-step spends
around its kernels, not in them: the own time of every operation that runs
inside a loop over a group's stacked batches (a `while` nested in the EM
program's `while`: the scan of models/fused.py `accumulate`), kernel calls
left out, plus those loops' own time.  In `flow20_fit` that is the copy of
each batch out of its stack.  Busiest device, traced window.

The nesting is read from the device's timeline, not from the operations'
scope: under the compile cache's settings jax hands XLA the primitive's name
alone (harness/program_trace.py), so the program's `estep` scope does not
reach the trace.  A program whose spans are not in the trace gives nothing
(the metric came with them).
"""

import re

from benchmarks.harness import xplane
from benchmarks.jobs import fit_spans, fit_trace

LOOP = re.compile(r"^%?while(\.\d+)*\b")


def glue_seconds(ops: list) -> float:
    """Own seconds of the operations at loop depth 2 or more that are no
    kernel calls, and of the loops at depth 1 or more themselves.  `ops`:
    (name, start, duration), nested by their intervals."""
    glue = 0.0
    stack = []      # [end, own seconds, counts as glue, is a loop]
    def close(until):
        nonlocal glue
        while stack and stack[-1][0] <= until:
            _, own, counts, _ = stack.pop()
            if counts:
                glue += max(own, 0.0)
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][1] -= dur
        loops = sum(1 for frame in stack if frame[3])
        loop = bool(LOOP.match(name))
        counts = (loops >= 1 if loop else loops >= 2
                  and not fit_trace.ESTEP_KERNEL.search(name))
        stack.append([start + dur, dur, counts, loop])
    close(float("inf"))
    return glue


def read(ctx):
    trace = ctx["trace"]
    if trace["rehearsal"] or not fit_spans.per_fit(ctx):
        return None
    dev = xplane.fullest_device(trace)
    busy = trace["devices"][dev]["busy_s"]
    inside = [op for lo, hi in fit_trace.em_programs(trace, dev)
              for op in xplane.clip(trace["devices"][dev]["ops"], lo, hi)]
    return 100.0 * glue_seconds(inside) / busy if inside and busy else None
