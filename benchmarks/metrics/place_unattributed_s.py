"""place_unattributed_s: the root's self time in a fit's placement: [`fit`
start, first EM program on the device] less what lies under any of `fit`'s
child spans.  With the five other `place_*` metrics it sums to `fit_place_s`
(which starts at the benchmark's annotation, microseconds earlier); where it
grows, a layer boundary is missing from the program.  Mean over the traced
fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    fits = fit_spans.per_fit(ctx)
    if not fits:
        return None
    return sum(f["first_em"] - f["fit"][0] - fit_spans.place_seconds(f)
               for f in fits) / len(fits)
