"""place_batches_s: the part of a fit's placement spent batching on the host
(`fit.batches`: `io/corpus.make_batches` or the bucketed layout).  The
program's span, on the device trace's clock, clipped to the fit's placement
([`fit` start, first EM program on the device]); mean over the traced fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    return fit_spans.mean_place(ctx, ("fit.batches",))
