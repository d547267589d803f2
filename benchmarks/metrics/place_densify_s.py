"""place_densify_s: the part of a fit's placement spent in
`fused.densify_groups` (`fit.densify`: per group a fresh jit's trace,
lowering, cache fetch and enqueue).  The program's span, clipped to the
placement; mean over the traced fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    return fit_spans.mean_place(ctx, ("fit.densify",))
