"""readback_sync_s: the part of a traced fit's tail (`jobs/fit_tail.py`: end
of its last EM program on the device to the end of the program's `fit` span)
that lies under `em.host_sync`: after the device has finished, the reads of
`steps_done`, the sweeps and the likelihoods, the float64 stop rule and
likelihood.dat's lines.  Mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("em.host_sync",), "tail")
