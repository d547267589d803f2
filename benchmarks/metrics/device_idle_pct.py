"""device_idle_pct: share of the traced window in which the busiest of the
cell's devices ran no operation (1 - union of its operations / window)."""

from benchmarks.harness import xplane


def read(ctx):
    trace = ctx["trace"]
    if trace["rehearsal"]:
        return None
    dev = xplane.fullest_device(trace)
    return 100.0 * (1.0 - trace["devices"][dev]["busy_s"] / trace["window_s"])
