"""estep_roofline: the E-step kernels' share of their roofline.

Kernel time: the device time of the E-step kernel calls the trace shows on
the busiest device.  Least time: max(flops / peak FLOP/s, bytes / peak B/s)
with the work counted from shapes (jobs/fit_work.py): per document and EM
iteration ONE fixed-point sweep's two products plus the expected-counts
product (a floor: the program does not report its sweeps), one read of each
document's [V] row at 4 bytes, and per call beta in and the [K, V] expected
counts out.  Documents are the cell's real ones, not the padded rows: the
count is of the work, not of the implementation.  Which bound binds is in
`binding()`: at K=20 and K=50 the bytes (6*K flops per 4-byte element is
under the chip's 240 flops per byte).
"""

from benchmarks.harness import xplane
from benchmarks.jobs import fit_trace, fit_work


def read(ctx):
    trace = ctx["trace"]
    if trace["rehearsal"] or ctx["peaks"] is None:
        return None
    dev = xplane.fullest_device(trace)
    calls = [(n, s, d) for n, s, d in trace["devices"][dev]["ops"]
             if fit_trace.ESTEP_KERNEL.search(n)]
    kernel_s = sum(d for _, _, d in calls)
    if not calls or kernel_s <= 0:
        return None
    rows = ctx["doc_iters"] / ctx["chips"]
    least, _ = binding(rows, len(calls), ctx["num_terms"], ctx["num_topics"],
                       ctx["peaks"])
    return 100.0 * least / kernel_s


def binding(rows: float, calls: int, num_terms: int, num_topics: int,
            peaks: dict) -> tuple:
    """(least seconds, "flops" | "bytes") for `rows` document-iterations
    in `calls` kernel calls."""
    flops = fit_work.estep_call_flops(rows, num_terms, num_topics)
    nbytes = (fit_work.estep_call_bytes(rows, num_terms, num_topics)
              + (calls - 1) * fit_work.estep_call_bytes(
                  0, num_terms, num_topics))
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["bytes_per_s"]
    return max(by_flops, by_bytes), "flops" if by_flops > by_bytes else "bytes"
