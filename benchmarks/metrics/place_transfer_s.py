"""place_transfer_s: the part of a fit's placement spent stacking the
batches and putting them on the device (`fit.stack`: `np.stack` and `put`;
its `fit.stack.counts` event says how many bytes).  The program's span,
clipped to the placement; mean over the traced fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    return fit_spans.mean_place(ctx, ("fit.stack",))
