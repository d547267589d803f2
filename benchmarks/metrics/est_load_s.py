"""est_load_s: the drop-in CLI's load of the day's model.dat
(`Corpus.from_model_dat`: the text parse into CSR arrays), the program's
span `est.load` of the traced call, on the device trace's clock.  It lies
before the fit's root span, inside the job's annotation of the call, so
`fit_place_s` holds it too.  Mean over the traced calls."""

from benchmarks.jobs import est_spans


def read(ctx):
    return est_spans.mean_seconds(ctx, "est.load")
