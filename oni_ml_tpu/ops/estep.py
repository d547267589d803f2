"""Batched variational E-step for LDA — the TPU replacement for the
reference engine's per-document inner loop.

The reference (oni-lda-c, reconstructed in SURVEY.md §2.8/§3.3) runs, per
document, a phi/gamma coordinate-ascent fixed point:

    phi_nk ∝ beta_{k,w_n} * exp(digamma(gamma_k))
    gamma_k = alpha + sum_n c_n phi_nk

Here that loop is vectorized over a padded batch of documents [B, L] using
the matrix form of the same fixed point (Hoffman et al., "Online Learning
for LDA", NIPS 2010): phi is never materialized across iterations — each
step needs only

    phinorm[b,l] = sum_k expEt[b,k] * beta[k, w[b,l]]
    gamma[b,k]   = alpha + expEt[b,k] * sum_l (c/phinorm)[b,l] * beta[k, w[b,l]]

which is two batched matvecs against the gathered beta slab [B, L, K] —
dense, static-shaped work that XLA maps onto the MXU/VPU.  Padding tokens
carry count 0 and padded docs are masked, so both are arithmetically inert.

Sufficient statistics are scattered into [V, K] with a segment-sum over the
flattened token axis — the on-device analogue of the reference's
`MPI_Reduce` of per-rank SS arrays (the cross-device part is a `psum` by
the caller; see oni_ml_tpu/parallel).

The building blocks (`gather_beta`, `fixed_point`, `suff_stats`,
`batch_likelihood`) are exposed separately so the distributed layer can
recompose them — e.g. building the beta slab with a psum over a
vocab-sharded beta — without duplicating any math.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma, gammaln

from .stop import fp_continue

# Matches lda-c's floor for log beta of zero-mass words.
LOG_ZERO = -100.0


class EStepResult(NamedTuple):
    gamma: jnp.ndarray        # [B, K] variational doc-topic posteriors
    suff_stats: jnp.ndarray   # [V, K] expected word-topic counts
    alpha_ss: jnp.ndarray     # scalar: sum_d sum_k E[log theta_dk]
    likelihood: jnp.ndarray   # scalar: sum over real docs of the ELBO
    vi_iters: jnp.ndarray     # scalar: fixed-point iterations used
    doc_sweeps: jnp.ndarray   # scalar int32: document-sweeps run — each
                              # kernel block's sweeps x its rows, padding
                              # included (a batch that iterates as one
                              # counts sweeps x its rows): the work run,
                              # additive across batches and shards


# The fields of an EStepResult that are PARTIAL sufficient statistics:
# additive across document subsets, so per-shard/per-rank results
# combine into the global result by summation alone (gamma is per-doc
# state and vi_iters a max — neither reduces by sum; doc_sweeps does,
# but counts work run and is no statistic the M-step reads).  This is the
# payload contract of the distributed suff-stats allreduce — the named
# arrays models/lda.py's _distributed_loop hands parallel/allreduce:
# word-topic counts for the M-step, the ELBO for the convergence
# check, and the E[log theta] total for the alpha Newton.  The order
# matches fused.make_partial_runner's return tuple (suff, ll, ass,
# gammas, vi, doc_sweeps) with the tail that is not reduced dropped.
PARTIAL_STAT_FIELDS = ("suff_stats", "likelihood", "alpha_ss")


def e_log_dirichlet(param: jnp.ndarray) -> jnp.ndarray:
    """Dirichlet expectation E_q[log x] = digamma(p_i) - digamma(sum p)
    over the last axis.  Used for both E[log theta] (gamma rows) and the
    online trainer's E[log beta] (lambda rows)."""
    return digamma(param) - digamma(param.sum(-1, keepdims=True))


# Internal alias: gamma-flavoured call sites read better with this name.
_e_log_theta = e_log_dirichlet


def check_warm_pair(gamma_prev, warm) -> None:
    """gamma_prev and warm travel together: without this guard, a
    gamma_prev passed alone would silently warm-start on the XLA path
    (`None != 0` is True) but crash on the Pallas/dense paths — one
    backend changing the math where another errors."""
    if gamma_prev is not None and warm is None:
        raise ValueError(
            "gamma_prev requires an explicit `warm` gate (0 = fresh "
            "init, nonzero = seed from gamma_prev)"
        )


def gather_beta(log_beta: jnp.ndarray, word_idx: jnp.ndarray) -> jnp.ndarray:
    """[K, V] log beta + [B, L] word ids -> [B, L, K] probability slab."""
    return jnp.exp(log_beta).T[word_idx]


def fixed_point(
    beta_bt: jnp.ndarray,    # [B, L, K] gathered beta
    alpha: jnp.ndarray,      # scalar
    counts: jnp.ndarray,     # [B, L]
    doc_mask: jnp.ndarray,   # [B]
    var_max_iters: int,
    var_tol: float,
    gamma_prev=None,         # [B, K] warm start (None = fresh init)
    warm=None,               # traced scalar gating gamma_prev
):
    """Per-document gamma fixed point.  Returns (gamma [B, K], iters).

    `gamma_prev`/`warm` mirror the dense kernels' warm start (config
    knob warm_start_gamma): warm != 0 resumes from the previous EM
    iteration's posterior — same fixed point, fewer iterations once
    beta stabilizes — else the reference's fresh alpha + N_d/K init."""
    B, L, K = beta_bt.shape
    dtype = beta_bt.dtype
    n_d = counts.sum(-1, keepdims=True)                  # [B, 1]
    gamma0 = alpha + n_d / K * jnp.ones((B, K), dtype)   # lda-c init: alpha + N/k
    # var_tol is RELATIVE to the per-doc gamma scale: the row sum of
    # gamma is invariant (sum_k gamma_k = K*alpha + N_d exactly, since
    # phi rows normalize), so mean_k gamma = alpha + N_d/K for every
    # iterate.  An absolute tolerance at lda-c's stock 1e-6 sits below
    # f32 resolution for typical gamma magnitudes and never fires; the
    # relative test is reachable yet still far tighter than lda-c's
    # per-doc relative-likelihood stop (the ELBO is quadratic in
    # delta-gamma near the fixed point).
    inv_scale = 1.0 / (alpha + n_d[:, 0] / K)            # [B]
    if gamma_prev is not None:
        check_warm_pair(gamma_prev, warm)
        gamma0 = jnp.where(warm != 0, gamma_prev, gamma0)

    def body(state):
        gamma, delta_old, _, it = state
        exp_et = jnp.exp(_e_log_theta(gamma))                        # [B, K]
        phinorm = jnp.einsum("blk,bk->bl", beta_bt, exp_et) + 1e-30  # [B, L]
        gamma_new = alpha + exp_et * jnp.einsum(
            "bl,blk->bk", counts / phinorm, beta_bt
        )
        delta = jnp.max(
            jnp.abs(gamma_new - gamma).mean(-1) * inv_scale * doc_mask
        )                                                            # scalar
        return gamma_new, delta, delta_old, it + 1

    def cond(state):
        # var_tol or gated stagnation — the shared rule (ops/stop.py).
        _, delta, prev, it = state
        return fp_continue(it, delta, prev, var_max_iters, var_tol)

    # The scalar delta carry is derived from `counts` (not a fresh
    # constant) so that under shard_map its varying-axes type matches the
    # body output; each device shard then iterates until its own docs
    # converge — no cross-shard sync inside the loop.
    delta0 = jnp.max(counts[:, 0]) * 0.0 + jnp.asarray(jnp.inf, dtype)
    gamma, _, _, iters = jax.lax.while_loop(
        cond, body, (gamma0, delta0, delta0, jnp.asarray(0, jnp.int32))
    )
    return gamma, iters


def phi_weighted(beta_bt, gamma, counts, doc_mask):
    """Converged per-token quantities.

    Returns (phi_c [B, L, K], phinorm [B, L]) where phi_c[b,l,k] is
    phi[b,l,k] * counts[b,l], masked to real docs.
    """
    exp_et = jnp.exp(_e_log_theta(gamma))
    phinorm = jnp.einsum("blk,bk->bl", beta_bt, exp_et) + 1e-30
    phi_c = beta_bt * (counts / phinorm)[..., None] * exp_et[:, None, :]
    return phi_c * doc_mask[:, None, None], phinorm


def suff_stats(phi_c: jnp.ndarray, word_idx: jnp.ndarray, num_segments: int):
    """Scatter phi-weighted counts into [num_segments, K]."""
    B, L, K = phi_c.shape
    return jax.ops.segment_sum(
        phi_c.reshape(B * L, K), word_idx.reshape(B * L), num_segments=num_segments
    )


def batch_likelihood_from_tok(gamma, tok_ll, alpha, doc_mask):
    """ELBO from a precomputed per-doc token term (sum_l c*log(phinorm),
    already masked) plus the gamma-dependent Dirichlet terms.  The dense
    kernel computes tok_ll while C is VMEM-resident and hands it here."""
    K = gamma.shape[-1]
    e_lt = _e_log_theta(gamma)
    doc_ll = (
        gammaln(K * alpha)
        - K * gammaln(alpha)
        + ((alpha - gamma) * e_lt).sum(-1)
        + gammaln(gamma).sum(-1)
        - gammaln(gamma.sum(-1))
    )
    likelihood = (doc_ll * doc_mask).sum() + tok_ll.sum()
    alpha_ss = (e_lt.sum(-1) * doc_mask).sum()
    return likelihood, alpha_ss


def batch_likelihood(gamma, phinorm, counts, alpha, doc_mask):
    """ELBO summed over real docs + alpha suff stats (sum E[log theta]).

    Uses the collapsed form: sum_l c*log(phinorm) absorbs the token term
    and the z-entropy; beta is a point estimate in lda-c so there is no
    beta-prior term (SURVEY §2.8).
    """
    tok_ll = (counts * jnp.log(phinorm)).sum(-1) * doc_mask
    return batch_likelihood_from_tok(gamma, tok_ll, alpha, doc_mask)


_BACKENDS = ("auto", "xla", "pallas", "sparse", "dense")


def resolve_backend(backend: str, b: int, l: int, k: int,
                    v: int) -> "tuple[str, dict]":
    """Which implementation an `e_step` call with these shapes runs:
    (engine, refused), where `refused` maps every engine that stood
    before it in the preference order to the gate that said no.

    "auto" prefers the fused sparse Pallas kernel, then the
    fixed-point-only Pallas kernel, then plain XLA; both kernels need a
    TPU backend and a VMEM-feasible doc block.  A forced engine whose
    shape gate says no raises — it never falls through."""
    import os

    if backend == "auto":
        env = os.environ.get("ONI_ML_TPU_ESTEP", "auto")
        # "dense"/"compact" in the env are DRIVER-level hints (models/lda.py
        # picks them up in LDATrainer._plan_estep, where the densification
        # is amortized across the run).  Honoring them per call here would
        # re-scatter the batch every EM iteration — the exact cost the dense
        # paths exist to avoid — so auto dispatch ignores them; only an
        # explicit backend="dense" argument densifies inline.  "sparse"
        # passes through: the fused sparse kernel has no per-call setup
        # to amortize, so forcing it per call is well-defined.
        backend = "auto" if env in ("dense", "compact") else env
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown E-step backend {backend!r} (set via ONI_ML_TPU_ESTEP "
            "or the backend= argument); expected auto, xla, pallas, "
            "sparse, or dense"
        )
    if backend == "xla":
        return "xla", {}
    from . import dense_estep, pallas_estep, sparse_estep

    picks = {
        "dense": lambda: dense_estep.pick_block(b, v, k),
        "sparse": lambda: sparse_estep.pick_block(b, l, k),
        "pallas": lambda: pallas_estep.pick_block(b, l, k),
    }
    if backend != "auto":
        if picks[backend]() is None:
            shape = (f"B={b}, V={v}, K={k}" if backend == "dense"
                     else f"B={b}, L={l}, K={k}")
            raise ValueError(
                f"{backend} E-step forced but {shape} has no "
                "VMEM-feasible doc block (unset "
                f"ONI_ML_TPU_ESTEP={backend} or reduce the batch"
                + ("/vocab size)" if backend == "dense" else ")")
            )
        return backend, {}
    refused = {}
    platform = jax.default_backend()
    for engine in ("sparse", "pallas"):
        if platform != "tpu":
            refused[engine] = f"backend is {platform}, not tpu"
        elif picks[engine]() is None:
            refused[engine] = (
                f"no VMEM-feasible doc block for B={b}, L={l}, K={k}"
            )
        else:
            return engine, refused
    return "xla", refused


def _report_dispatch(engine: str, refused: dict, requested: str,
                     shape: str) -> None:
    """Say which engine a traced E-step took and which gates refused
    the ones preferred over it: a log line always, and an
    {"kind": "estep_dispatch"} journal record under an active recorder
    (docs/observability.md).  Runs at trace time, once per compiled
    shape — a Pallas gate never hands a call to XLA without a word."""
    import logging

    logging.getLogger(__name__).info(
        "e_step %s -> %s (requested %s%s)", shape, engine, requested,
        "".join(f"; {e} refused: {why}" for e, why in refused.items()),
    )
    from ..telemetry.spans import current_recorder

    rec = current_recorder()
    if rec is not None:
        rec.journal_record({
            "kind": "estep_dispatch",
            "engine": engine,
            "requested": requested,
            "refused": refused,
            "shape": shape,
        })


def e_step(
    log_beta: jnp.ndarray,   # [K, V] log p(word|topic)
    alpha: jnp.ndarray,      # scalar symmetric Dirichlet prior
    word_idx: jnp.ndarray,   # [B, L] int32, 0 where padded
    counts: jnp.ndarray,     # [B, L] f32, 0 where padded
    doc_mask: jnp.ndarray,   # [B] f32, 1 for real docs
    var_max_iters: int,
    var_tol: float,
    backend: str = "auto",
    gamma_prev=None,         # [B, K] warm start (None = fresh init)
    warm=None,               # traced scalar gating gamma_prev
) -> EStepResult:
    """Run the per-document fixed point to convergence for one batch.

    backend: "auto" uses the fused sparse Pallas E-step on TPU when the
    shapes admit it (ops/sparse_estep.py — fixed point AND suff-stats/
    ELBO tail in one VMEM residency), else the fixed-point-only Pallas
    kernel (ops/pallas_estep.py), else pure XLA; "xla" / "pallas" /
    "sparse" / "dense" force a path (ONI_ML_TPU_ESTEP env var overrides
    "auto").  "dense" densifies the batch per call — drivers that own the
    batches amortize the densification instead (models/fused.py).  The
    choice is `resolve_backend`'s, and every call reports it.
    """
    b, l = word_idx.shape
    k, v = log_beta.shape
    engine, refused = resolve_backend(backend, b, l, k, v)
    _report_dispatch(engine, refused, backend, f"b{b}.l{l}.k{k}.v{v}")
    interpret = jax.default_backend() != "tpu"
    if engine == "dense":
        from . import dense_estep

        dense = dense_estep.densify(word_idx, counts, v)
        return dense_estep.e_step_dense(
            log_beta, alpha, dense, doc_mask, var_max_iters, var_tol,
            interpret=interpret, gamma_prev=gamma_prev, warm=warm,
        )
    if engine == "sparse":
        from . import sparse_estep

        return sparse_estep.e_step(
            log_beta, alpha, word_idx, counts, doc_mask,
            var_max_iters, var_tol, interpret=interpret,
            gamma_prev=gamma_prev, warm=warm,
        )
    if engine == "pallas":
        from . import pallas_estep

        return pallas_estep.e_step(
            log_beta, alpha, word_idx, counts, doc_mask,
            var_max_iters, var_tol, interpret=interpret,
            gamma_prev=gamma_prev, warm=warm,
        )
    V = log_beta.shape[1]
    beta_bt = gather_beta(log_beta, word_idx)
    gamma, iters = fixed_point(beta_bt, alpha, counts, doc_mask,
                               var_max_iters, var_tol,
                               gamma_prev=gamma_prev, warm=warm)
    phi_c, phinorm = phi_weighted(beta_bt, gamma, counts, doc_mask)
    suff = suff_stats(phi_c, word_idx, V)
    likelihood, alpha_ss = batch_likelihood(gamma, phinorm, counts, alpha, doc_mask)
    return EStepResult(gamma, suff, alpha_ss, likelihood, iters, iters * b)


# Lets the fused runner know this callable accepts gamma_prev/warm (a
# user-supplied custom e_step_fn may not; the runner then stays fresh).
e_step._oni_warm_capable = True


def m_step(suff_stats: jnp.ndarray, topic_total=None) -> jnp.ndarray:
    """MLE beta from accumulated word-topic suff stats [V, K] -> [K, V]
    log-normalized per topic, with lda-c's -100 floor for zero mass.

    `topic_total` [K, 1] overrides the per-topic normalizer — the vocab-
    sharded M-step passes the psum over the model axis so each shard
    normalizes its local slice against the global total."""
    ss = suff_stats.T  # [K, V]
    total = ss.sum(-1, keepdims=True) if topic_total is None else topic_total
    return jnp.where(
        ss > 0, jnp.log(jnp.maximum(ss, 1e-300)) - jnp.log(total), LOG_ZERO
    )
