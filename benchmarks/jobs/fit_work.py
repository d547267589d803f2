"""Operations and bytes of the fit job's work, from shapes alone.

The count is of the WORK, not of an implementation, so it reads the same
whichever engine runs.  Per document and fixed-point sweep any dense
formulation of the E-step does two [B, V] x [V, K] products
(q = exp(Elog theta) beta, and gamma = alpha + exp(Elog theta) * (c / q) beta^T),
and per EM iteration one more for the expected counts: 2 * V * K flops
each, at the padded vocabulary the dense corpus is stored at.  The program
does not report how many sweeps ran (LDAResult has no vi_iters), so the
count takes ONE sweep per EM iteration: 6 * V * K per document, a floor.
"""

LANE = 128


def padded_terms(num_terms: int) -> int:
    return -(-num_terms // LANE) * LANE


def flops_per_doc_iter(num_terms: int, num_topics: int) -> float:
    return 6.0 * padded_terms(num_terms) * num_topics


def estep_call_flops(batch: int, num_terms: int, num_topics: int) -> float:
    """One E-step call over a [batch, V] block of documents."""
    return batch * flops_per_doc_iter(num_terms, num_topics)


def estep_call_bytes(batch: int, num_terms: int, num_topics: int,
                     itemsize: int = 4) -> float:
    """One read of the [B, V] block at its stored itemsize, beta [K, V] in,
    gamma [B, K] in and out, and the [K, V] expected counts out (f32)."""
    v = padded_terms(num_terms)
    return (batch * v * itemsize + 2 * num_topics * v * 4
            + 2 * batch * num_topics * 4)
