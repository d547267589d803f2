"""readback_unattributed_s: a traced fit's tail (`jobs/fit_tail.py`) under
none of `em.host_sync`, `fit.readback.d2h`, `fit.readback.scatter`, `fit.save`
and `fit.teardown`: the root's and `fit.readback`'s own time there.  With the
four other `readback_*` metrics (and `est_save_s` where files are written) it
sums to the tail, which is `fit_readback_s` less the microseconds between the
root span's end and the annotation's; where it grows, a boundary is missing
from the program.  Mean over the traced fits."""

from benchmarks.jobs import fit_tail


def read(ctx):
    fits = fit_tail.per_fit(ctx)
    if not fits:
        return None
    return sum(
        f["tail"][1] - f["tail"][0]
        - fit_tail.seconds(f, fit_tail.TAIL_PARTS, *f["tail"])
        for f in fits) / len(fits)
