"""est_save_s: writing final.beta, final.gamma and final.other in the
reference's text format (`LDAResult.save`), the program's span `fit.save`
of the traced call, on the device trace's clock.  Mean over the traced
calls."""

from benchmarks.jobs import est_spans


def read(ctx):
    return est_spans.mean_seconds(ctx, "fit.save")
