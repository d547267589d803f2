"""place_first_dispatch_s: the fit's first `em.run_chunk` span, which holds
the chunk program's trace, lowering and cache fetch before its enqueue, up
to the moment the program starts on the device.  Mean over the traced
fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    return fit_spans.mean_place(ctx, ("em.run_chunk",), first_only=True)
