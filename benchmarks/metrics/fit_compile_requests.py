"""fit_compile_requests: how many executables a fit asks jax's compilation
cache for (each a fresh `jax.jit` traced, lowered and fetched while the
device waits): `compile_requests` of the `fit.counts` event, the delta of
`plans.warmup.compile_counts()` across train_corpus.  Mean over the traced
fits."""

from benchmarks.jobs import fit_spans


def read(ctx):
    counts = [fit_spans.counted(f, "fit", "compile_requests")
              for f in fit_spans.per_fit(ctx)]
    counts = [c for c in counts if c is not None]
    return sum(counts) / len(counts) if counts else None
