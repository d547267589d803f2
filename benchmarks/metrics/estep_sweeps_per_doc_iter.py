"""estep_sweeps_per_doc_iter: fixed-point sweeps the E-step ran per document
and EM iteration: `doc_sweeps` of the traced fits' `fit.counts` events (the
sum over EM iterations and batches of each kernel block's sweeps x its rows,
padding included) over their padded rows (`fit.batches.counts` `rows`) x EM
iterations (`fit.counts` `em_iters`).  `em_mfu` and `estep_roofline` count
one sweep: this is the factor they leave out."""

from benchmarks.jobs import fit_spans


def read(ctx):
    sweeps = row_iters = 0
    for fit in fit_spans.per_fit(ctx):
        done = fit_spans.counted(fit, "fit", "doc_sweeps")
        rows = fit_spans.counted(fit, "fit.batches", "rows")
        iters = fit_spans.counted(fit, "fit", "em_iters")
        if done is None or not rows or not iters:
            return None
        sweeps += done
        row_iters += rows * iters
    return sweeps / row_iters if row_iters else None
