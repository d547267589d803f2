"""Shared stop rule for the variational fixed point.

Every E-step engine (XLA batched, sparse Pallas, dense row-major and
W-major Pallas, vocab-sharded XLA plan) and the float64 NumPy oracle
(tests/reference_lda.py) stop the per-block gamma iteration with the
SAME predicate, kept here so the rule cannot drift between backends:

continue while  it < var_max_iters
          and  (it == 0
                or (delta > var_tol                       # not converged
                    and (delta >= STALL_GATE              # still far out
                         or delta < prev)))               # still shrinking

where `delta` is the block max over docs of mean_k |gamma_new - gamma|
RELATIVE to the doc's mean gamma (alpha + N_d/K — an exact iteration
invariant, since gamma rows sum to K*alpha + N_d).

Two exits beyond the iteration cap:

- **var_tol** (relative): at the stock 1e-6 this is far tighter than
  lda-c's per-doc relative-likelihood stop at its stock 1e-6 (the ELBO
  is quadratic in delta-gamma near the fixed point), while actually
  being reachable — an ABSOLUTE 1e-6 against typical gamma magnitudes
  sits below f32 resolution and silently turns var_max_iters into a
  trip count (reference semantics anchor: oni-lda-c settings.txt "var
  convergence", SURVEY.md §2.8).

- **stagnation** (`delta >= prev`), gated by STALL_GATE: on TPU the
  MXU's bf16-truncated matmul inputs (XLA DEFAULT precision) put a
  ~2^-8 relative noise floor under the iterates — below it the fixed
  point jitters instead of contracting, so once the delta stops
  shrinking there, further iterations cannot improve gamma and
  stagnation == converged at this arithmetic's achievable precision.
  The gate confines the test to deltas already below STALL_GATE:
  far from the fixed point the delta is NOT guaranteed monotone (a
  warm start whose beta moved, or a fresh start escaping a saddle, can
  legitimately produce a growing delta for an iteration), and without
  the gate one such transient would abort the loop badly unconverged.
  On full-f32 backends (CPU tests, interpret mode) the gated region's
  deltas decrease strictly until var_tol in practice, so the exit
  changes nothing there.  The dense plans leave it out wherever the
  products keep float32 accuracy, whatever the store (`stall=False`,
  dense_estep._stall_exit).
"""

from __future__ import annotations

import jax.numpy as jnp

# Stagnation may only fire once the block delta is below this relative
# level (~"within 1% of the fixed point") — comfortably above the bf16
# MXU noise floor (~2^-8 ≈ 4e-3) it exists to detect, comfortably below
# any transient worth iterating through.
STALL_GATE = 1e-2


def fp_continue(it, delta, prev, var_max_iters: int, var_tol: float,
                stall: bool = True):
    """Traced continue-predicate for the fixed-point `while_loop`.

    Pure jnp on scalars, so it traces identically inside Pallas kernels,
    shard_map'd bodies (delta/prev may carry varying axes), and plain
    XLA.  `prev` is the previous iteration's delta (init: +inf with
    `it == 0` short-circuiting the first evaluation).

    `stall=False` drops the stagnation exit: var_tol or var_max_iters
    alone, the reference's rule.  For a block whose products keep float32
    accuracy (dense_estep, `accurate`), where there is no bfloat16 floor
    to detect: on the day after analyst feedback a document that one
    large cell dominates converges slowly, and the chip's float32
    rounding stalled its block's delta under STALL_GATE before it had
    converged, which sent it to another topic than the reference's
    (PERF.md).
    """
    converging = delta > var_tol
    if stall:
        converging = jnp.logical_and(
            converging, jnp.logical_or(delta >= STALL_GATE, delta < prev))
    return jnp.logical_and(
        it < var_max_iters, jnp.logical_or(it == 0, converging))
