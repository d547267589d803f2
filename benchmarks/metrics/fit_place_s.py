"""fit_place_s: from each traced fit's start (the annotation around
train_corpus) to its first EM chunk program on the device: batching on the
host, the transfer, densify and whatever the fit traces or fetches first.
Mean over the traced fits."""

from benchmarks.harness import xplane
from benchmarks.jobs import fit_trace


def read(ctx):
    trace = ctx["trace"]
    if trace["rehearsal"]:
        return None
    dev = xplane.fullest_device(trace)
    waits = [programs[0][0] - lo
             for lo, _, programs in fit_trace.per_fit(trace, dev) if programs]
    return sum(waits) / len(waits) if waits else None
