"""Job kind `fit_feedback`: job `fit` (jobs/fit.py) over the day after
analyst feedback.  The reference product's analysts mark benign events
severity 3 in `flow_scores.csv`, and the next day's pre-LDA stage unions
each marked row into the day DUPFACTOR=1000 times before the word counts
sum by (IP, word) (ml_ops.sh:23-32, flow_pre_lda.scala:150-171, 253-268):
every marked row adds DUPFACTOR to two cells, its source's and its
destination's.  So the day's cells reach 1,000 and more, which bfloat16
does not hold exactly.

The day is `corpus_gen.make_corpus`'s (the traffic file's `corpus` block,
the same seed) with the traffic file's `feedback` block folded in by
`feedback_corpus`.  `fit.run` is reused whole, unedited, through a SCOPED
SWAP: for the length of the call the module attribute
`corpus_gen.make_corpus`, the one name `fit.run` makes its corpus through,
is `feedback_corpus`, and it is restored in `finally`.  So the program, the
window, the probes and the plain reference (benchmarks/reference/
lda_plain.py, int32 counts, float32 at `highest`) all see the feedback day.

One number is appended to `fit_check`'s: `store_exact`, 0 where the timed
fit's `plan["corpus_store"]` is none (token lists) or a dtype that holds
every integer up to the day's largest cell, which this job takes from the
CSR arrays and not from the program; 1 otherwise.  `correct` is
`fit_check.judge`'s AND `store_exact` within the traffic file's
`store_limit` (0).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import corpus_gen
from benchmarks.jobs import fit

PHASES = fit.PHASES


def feedback_corpus(traffic: dict, num_terms: int, seed: int,
                    make=None) -> corpus_gen.CsrCorpus:
    """The day of `traffic["corpus"]` and `seed` (`make`, by default
    corpus_gen.make_corpus), then `traffic["feedback"]`: 2 x `rows` hits
    dealt as a multinomial over `cells` distinct (document, word) pairs
    drawn uniformly from the day's, each hit adding `dup_factor` to its
    cell.  Words are distinct within a document, so a CSR count is the
    densified cell; no document's length changes."""
    fb = traffic["feedback"]
    csr = (make or corpus_gen.make_corpus)(traffic, num_terms, seed)
    rng = np.random.default_rng([int(seed), 1000])   # apart from the day's
    cells = rng.choice(len(csr.counts), int(fb["cells"]), replace=False)
    hits = rng.multinomial(2 * int(fb["rows"]),
                           np.full(len(cells), 1.0 / len(cells)))
    counts = csr.counts.astype(np.int64)
    counts[cells] += int(fb["dup_factor"]) * hits
    if counts.max() > int(fb["cell_max"]):
        raise AssertionError(
            f"feedback generator: a cell of {int(counts.max())} is over "
            f"cell_max {fb['cell_max']}")
    return corpus_gen.CsrCorpus(num_terms=csr.num_terms, doc_ptr=csr.doc_ptr,
                                word_idx=csr.word_idx,
                                counts=counts.astype(np.int32))


def exact_up_to(dtype_name: str) -> int:
    """The largest N such that a dtype holds every integer 0..N exactly."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    if jnp.issubdtype(dtype, jnp.integer):
        return int(jnp.iinfo(dtype).max)
    return 2 ** (int(jnp.finfo(dtype).nmant) + 1)


def store_exact(plan: dict, csr: corpus_gen.CsrCorpus) -> float:
    """0 where the plan stored no dense corpus, or one whose dtype holds
    the day's largest cell exactly; else 1."""
    store = (plan.get("corpus_store") or {}).get("value")
    if store is None:
        return 0.0
    return 0.0 if exact_up_to(store) >= int(csr.counts.max()) else 1.0


def run(ctx: dict) -> dict:
    """`fit.run` over the feedback day, and `store_exact`."""
    made = {}
    plain = corpus_gen.make_corpus

    def make(traffic, num_terms, seed):
        made["csr"] = feedback_corpus(traffic, num_terms, seed, plain)
        return made["csr"]

    corpus_gen.make_corpus = make
    try:
        out = fit.run(ctx)
    finally:
        corpus_gen.make_corpus = plain
    value = store_exact(out["observed"]["plan"], made["csr"])
    limit = ctx["traffic"]["store_limit"]
    ctx["log"](f"feedback: largest cell {int(made['csr'].counts.max())}, "
               f"store {out['observed']['plan'].get('corpus_store')}")
    return dict(out, correct=bool(out["correct"] and value <= limit),
                compared=[*out["compared"], ["store_exact", value, limit]])
