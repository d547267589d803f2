"""place_plan_s: the part of a fit's placement spent on host-only decisions
and set-up: `fit.engine` (plan cache or crossover), `fit.init` (trainer,
checkpoint, beta's initialisation), `fit.plan` (dense or not, the
`max_dense_cell` scan, blocks, kernel) and `fit.runner` (building the chunk
dispatch).  The program's spans, clipped to the placement; mean over the
traced fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    return fit_spans.mean_place(
        ctx, ("fit.engine", "fit.init", "fit.plan", "fit.runner"))
