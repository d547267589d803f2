"""readback_d2h_s: the part of a traced fit's tail under the spans
`fit.readback.d2h`: `to_host` of each group's gamma and of beta, which is the
device-to-host transfer and its copy into a fresh float64 array (their
`.counts` say `bytes` as they left the device and `shards`).  Mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    return fit_tail.mean(ctx, ("fit.readback.d2h",), "tail")
