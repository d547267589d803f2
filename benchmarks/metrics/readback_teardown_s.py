"""readback_teardown_s: the part of a traced fit's tail under the span
`fit.teardown`: dropping the trainer, its executables and the batches' host
buffers at `train_corpus`' return.  Mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("fit.teardown",), "tail")
