"""readback_scatter_s: the part of a traced fit's tail under the spans
`fit.readback.scatter`: per batch the masked store of its documents' rows into
the result's float64 gamma (their `.counts` say `rows` and `bytes`).  Mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("fit.readback.scatter",), "tail")
