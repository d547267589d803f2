"""fit_readback_s: from the end of each traced fit's last EM chunk program
on the device to the fit's end (the annotation around train_corpus): the
driver's last host sync (likelihoods read, float64 stop rule) and the
device-to-host read of gamma and beta.  Mean over the traced fits.  The
number of EM programs a fit dispatched (one per `host_sync_every` = 16 EM
iterations) is logged beside it."""

import sys

from benchmarks.harness import xplane
from benchmarks.jobs import fit_trace


def read(ctx):
    trace = ctx["trace"]
    if trace["rehearsal"]:
        return None
    dev = xplane.fullest_device(trace)
    fits = [f for f in fit_trace.per_fit(trace, dev) if f[2]]
    print(f"bench: EM programs dispatched per traced fit: "
          f"{[len(programs) for _, _, programs in fits]}", file=sys.stderr)
    tails = [hi - programs[-1][1] for _, hi, programs in fits]
    return sum(tails) / len(tails) if tails else None
