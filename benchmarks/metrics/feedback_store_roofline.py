"""feedback_store_roofline: the E-step kernels' share of their roofline on
the analyst-feedback day, with the corpus counted at the 2 B a cell of the
integer store (dense_estep.corpus_dtype's int16 rung) that the timed fit's
plan chose.

`estep_store_roofline`'s arithmetic, by import: the same kernel time,
documents and work (`jobs/fit_work.py`), the one read of each document's
[V] row counted at the bytes a cell the configuration states (2 under
`dense_precision` bf16).  Given only where the timed fit stored its dense
corpus in an integer dtype of that itemsize, so that it reads the kernels
the integer store feeds and nothing else: nothing for token lists (the
parent of the integer store, whose gate fell to float32 on this day), for
a float store, or where the cell cannot be found.
"""

from __future__ import annotations

from benchmarks.metrics import estep_store_roofline


def integer_store_itemsize(ctx) -> int | None:
    """Bytes a cell of the timed fit's dense corpus where the plan stored
    it in an integer dtype, else None."""
    import numpy as np

    store = ((ctx.get("plan") or {}).get("corpus_store") or {}).get("value")
    if store is None:
        return None
    dtype = np.dtype(store) if store != "bfloat16" else None
    if dtype is None or dtype.kind not in "iu":
        return None
    return dtype.itemsize


def read(ctx):
    itemsize = integer_store_itemsize(ctx)
    if itemsize is None or itemsize != estep_store_roofline.stated_itemsize(
            ctx):
        return None
    return estep_store_roofline.read(ctx)
