"""em_mfu: the whole EM step's share of the chips' peak, a floor.

docs/s over the traced window x 6*V*K flops per document per EM iteration
(jobs/fit_work.py) / (chips x peak FLOP/s).  Source: the host's clock around
the window; the peak from the harness's table.
"""

from benchmarks.jobs import fit_work


def read(ctx):
    if ctx["peaks"] is None:
        return None
    flops = ctx["end_to_end"]["em_docs_per_s"] * fit_work.flops_per_doc_iter(
        ctx["num_terms"], ctx["num_topics"])
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"])
