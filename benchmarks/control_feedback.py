"""control.py's readings on a cell of job kind `fit_feedback`, the day after
analyst feedback, with `store_exact` beside every reading:

    python benchmarks/control_feedback.py --workload flow20_feedback_fit --seeds 3 --deep 1 [--first-seed N]

`control.main` is run as it is, with the three names it reaches through
swapped for the length of the call and restored in `finally`, as
jobs/fit_feedback.py swaps `fit.run`'s corpus maker:
`corpus_gen.make_corpus` makes the feedback day
(`fit_feedback.feedback_corpus`); `lda_plain.fit` records the dtype it ran
in as its plan's corpus store, so that the bfloat16 control reads as a
bfloat16 store; `fit_check.compare` adds `store_exact` of the fit it is
handed (the program's own plan, or the reference's dtype) against the day's
largest cell, and `fit_check.judge` its row, with the traffic file's
`store_limit`.  One JSON line per reading on standard output.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import control  # noqa: E402
from benchmarks.harness import cells, corpus_gen, fit_check  # noqa: E402
from benchmarks.jobs import fit_feedback  # noqa: E402
from benchmarks.reference import lda_plain  # noqa: E402


def main(argv=None) -> int:
    args = control.argparse.ArgumentParser()
    args.add_argument("--workload", required=True)
    known, _ = args.parse_known_args(argv)
    limit = cells.resolve(known.workload)["traffic"]["store_limit"]
    made = {}
    plain = (corpus_gen.make_corpus, lda_plain.fit, fit_check.compare,
             fit_check.judge)
    make, fit, compare, judge = plain

    def feedback_day(traffic, num_terms, seed):
        made["csr"] = fit_feedback.feedback_corpus(traffic, num_terms, seed,
                                                   make)
        return made["csr"]

    def reference(*a, dtype="float32", **kw):
        out = fit(*a, dtype=dtype, **kw)
        out.plan = dict(out.plan, corpus_store={"value": dtype})
        return out

    def with_store(timed, *rest):
        exact = fit_feedback.store_exact(getattr(timed, "plan", {}) or {},
                                         made["csr"])
        return dict(compare(timed, *rest), store_exact=exact)

    def judged(values, limits):
        correct, rows = judge(values, limits)
        value = values["store_exact"]
        return (correct and value <= limit,
                [*rows, ["store_exact", float(value), limit]])

    (corpus_gen.make_corpus, lda_plain.fit, fit_check.compare,
     fit_check.judge) = (feedback_day, reference, with_store, judged)
    try:
        return control.main(argv)
    finally:
        (corpus_gen.make_corpus, lda_plain.fit, fit_check.compare,
         fit_check.judge) = plain


if __name__ == "__main__":
    sys.exit(main())
