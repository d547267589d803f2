"""The plain reference: variational EM for LDA (Blei, Ng, Jordan 2003; the
lda-c `lda est` the reference product ran), in straightforward jax.numpy.

float32 with `jax.default_matmul_precision("highest")`, dense document
blocks so that it fits, no kernels, no buckets, nothing imported from the
program and nothing the program made.  One EM iteration:

  E-step, per document d with counts c_dw:
      gamma_dk <- alpha + sum_w c_dw * phi_dwk,
      phi_dwk  ~  beta_kw * exp(digamma(gamma_dk) - digamma(sum_k gamma_dk))
    iterated from gamma = alpha + N_d / K (or, with `warm_start`, from the
    previous EM iteration's gamma) until the document's mean |delta gamma|
    falls under var_tol relative to its mean gamma, or var_max_iters sweeps.
  ELBO (beta a point estimate, collapsed over z), summed over documents:
      lgamma(K alpha) - K lgamma(alpha) + sum_k (alpha - gamma_k) Elog_k
      + sum_k lgamma(gamma_k) - lgamma(sum_k gamma_k)
      + sum_w c_w log(sum_k beta_kw exp(Elog_k))
  M-step: beta_kw ~ sum_d c_dw phi_dwk, normalised over w; log beta floored
    at -100 where a word has no mass (lda-c).
  alpha: Newton in log space on the symmetric Dirichlet's likelihood
    (lda-c opt_alpha), from the current alpha, at most alpha_max_iters trips.
  Stop: |(ll_prev - ll) / ll_prev| < em_tol, or em_max_iters.  The M-step of
    the converged iteration is applied.

`dtype="bfloat16"` computes every array and sum in bfloat16: the control of
the benchmark's `correct` (the nearest precision below the configuration's),
never the reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma, gammaln
from scipy.special import polygamma, psi

LOG_ZERO = -100.0


@dataclass
class Faults:
    """What a broken program would do, planted in the reference when it
    stands in the program's place (tests and the control script only).

    freeze_model: the step returns its state unchanged.
    stat_weight(start, stop, num_docs) -> weight of a document block's
      statistics; 0 leaves the block out.
    skip_unweighted: a block left out gets no gamma either (it was never
      computed), else gamma is computed and only the exchange is missing.
    alter_answer(fit): changes the finished answers where they are produced.
    """

    freeze_model: bool = False
    stat_weight: Callable[[int, int, int], float] | None = None
    skip_unweighted: bool = True
    alter_answer: Callable | None = None


@dataclass
class PlainFit:
    log_beta: np.ndarray            # [K, V] float64, after the last M-step
    gamma: np.ndarray               # [D, K] float64, the last E-step's
    alpha: float
    likelihoods: list = field(default_factory=list)   # ll per EM iteration
    em_iters: int = 0
    log_beta_first: np.ndarray | None = None   # after the first M-step
    alpha_first: float | None = None
    plan: dict = field(default_factory=lambda: {"engine": "plain reference"})


def init_log_beta(seed: int, k: int, v: int) -> np.ndarray:
    """lda-c `random` initialisation as the product's CLI asks for it
    (ml_ops.sh:80): uniform noise + 1/V, normalised per topic.  The same
    draw rule as the configuration states (threefry key from `seed`), so
    both sides start from the same point without exchanging an array."""
    noise = jax.random.uniform(jax.random.PRNGKey(seed), (k, v),
                               dtype=jnp.float32) + 1.0 / v
    return np.asarray(jnp.log(noise / noise.sum(-1, keepdims=True)),
                      np.float64)


def block_triplets(doc_ptr, word_idx, counts, block_docs: int) -> tuple:
    """CSR -> per block of `block_docs` documents the flat (row in block,
    word, count) triplets, every block padded with zero counts to one common
    length: ([nb, M] int32, [nb, M] int32, [nb, M] float32).  One compiled
    shape whatever the longest document holds."""
    lengths = np.diff(doc_ptr)
    doc = np.repeat(np.arange(len(lengths)), lengths)
    block = doc // block_docs
    nb = -(-len(lengths) // block_docs)
    per_block = np.bincount(block, minlength=nb)
    m = -(-int(per_block.max()) // 4096) * 4096
    pos = np.arange(len(doc)) - np.repeat(
        np.r_[0, np.cumsum(per_block)[:-1]], per_block)
    rows = np.zeros((nb, m), np.int32)
    cols = np.zeros((nb, m), np.int32)
    vals = np.zeros((nb, m), np.float32)
    rows[block, pos] = doc - block * block_docs
    cols[block, pos] = word_idx
    vals[block, pos] = counts
    return rows, cols, vals


def _e_step_block(beta, alpha, rows, cols, vals, gamma_prev, warm, *,
                  num_terms, var_max_iters, var_tol):
    """One block of documents.  Returns gamma [B, K], the block's expected
    counts [K, V], its ELBO and its sum of E[log theta]."""
    dt = beta.dtype
    b, k = gamma_prev.shape[0], beta.shape[0]
    dense = jnp.zeros((b, num_terms), dt).at[rows, cols].add(vals.astype(dt))
    n_d = dense.sum(-1)
    real = (n_d > 0).astype(dt)
    scale = alpha + n_d / k
    gamma0 = jnp.where(warm, gamma_prev.astype(dt),
                       jnp.broadcast_to(scale[:, None], (b, k)))

    def e_log_theta(gamma):
        return digamma(gamma) - digamma(gamma.sum(-1, keepdims=True))

    def sweep(state):
        gamma, done, it = state
        e = jnp.exp(e_log_theta(gamma))
        q = e @ beta + jnp.asarray(1e-30, dt)
        new = alpha + e * ((dense / q) @ beta.T)
        delta = jnp.abs(new - gamma).mean(-1) / scale
        gamma = jnp.where(done[:, None], gamma, new)
        return gamma, done | (delta < var_tol), it + 1

    def unfinished(state):
        _, done, it = state
        return (it < var_max_iters) & ~jnp.all(done)

    gamma, _, _ = jax.lax.while_loop(
        unfinished, sweep, (gamma0, n_d <= 0, jnp.asarray(0, jnp.int32)))

    elog = e_log_theta(gamma)
    e = jnp.exp(elog)
    q = e @ beta + jnp.asarray(1e-30, dt)
    stats = beta * (e.T @ (dense / q * real[:, None]))
    doc_ll = (gammaln(k * alpha) - k * gammaln(alpha)
              + ((alpha - gamma) * elog).sum(-1)
              + gammaln(gamma).sum(-1) - gammaln(gamma.sum(-1))
              + (dense * jnp.log(q)).sum(-1))
    return (gamma, stats, (doc_ll * real).sum(),
            (elog.sum(-1) * real).sum())


def newton_alpha(alpha_ss: float, alpha: float, num_docs: int, k: int,
                 max_iters: int) -> float:
    """lda-c opt_alpha in log space, float64, from the current alpha."""
    log_a = np.log(alpha)
    for _ in range(max_iters):
        a = np.exp(log_a)
        df = num_docs * k * (psi(k * a) - psi(a)) + alpha_ss
        if abs(df) <= 1e-5:
            break
        d2f = num_docs * k * (k * polygamma(1, k * a) - polygamma(1, a))
        log_a = log_a - df / (d2f * a + df)
    a = float(np.exp(log_a))
    return a if np.isfinite(a) and a > 0 else float(alpha)


def fit(doc_ptr, word_idx, counts, num_terms: int, settings: dict, *,
        max_steps: int | None = None, stop_rule: bool = True,
        block_docs: int = 2048, dtype: str = "float32",
        faults: Faults | None = None) -> PlainFit:
    """EM from the configuration's initialisation, to convergence or for
    `max_steps` iterations (`stop_rule=False`: exactly that many, to follow
    a program that decides for itself where to stop).  `settings` is the
    configuration file's `lda` group."""
    k = int(settings["num_topics"])
    num_docs = len(doc_ptr) - 1
    steps = int(settings["em_max_iters"])
    if max_steps is not None:
        steps = min(steps, max_steps)
    dt = jnp.dtype(dtype)
    faults = faults or Faults()
    block_docs = min(block_docs, num_docs)
    pad = -num_docs % block_docs    # whole blocks only: one compiled shape
    rows_d, cols_d, vals_d = (jnp.asarray(a) for a in block_triplets(
        doc_ptr, word_idx, counts, block_docs))

    e_step = jax.jit(
        _e_step_block,
        static_argnames=("num_terms", "var_max_iters", "var_tol"))
    kw = dict(num_terms=num_terms,
              var_max_iters=int(settings["var_max_iters"]),
              var_tol=float(settings["var_tol"]))

    beta = jnp.exp(jnp.asarray(
        init_log_beta(int(settings["seed"]), k, num_terms), jnp.float32)
    ).astype(dt)
    alpha = float(settings["alpha_init"])
    gamma = jnp.zeros((num_docs + pad, k), dt)
    out = PlainFit(log_beta=None, gamma=None, alpha=alpha)
    ll_prev = None
    with jax.default_matmul_precision("highest"):
        for step in range(steps):
            stats = jnp.zeros((k, num_terms), dt)
            ll = jnp.zeros((), dt)
            alpha_ss = jnp.zeros((), dt)
            warm = bool(settings["warm_start"]) and step > 0
            new_gamma = []
            for lo in range(0, num_docs + pad, block_docs):
                hi = lo + block_docs
                weight = (1.0 if faults.stat_weight is None
                          else faults.stat_weight(lo, hi, num_docs))
                if weight == 0 and faults.skip_unweighted:
                    new_gamma.append(jnp.zeros((block_docs, k), dt))
                    continue
                i = lo // block_docs
                g, s, block_ll, block_ss = e_step(
                    beta, jnp.asarray(alpha, dt), rows_d[i], cols_d[i],
                    vals_d[i], gamma[lo:hi], warm, **kw)
                new_gamma.append(g)
                if weight:
                    w = jnp.asarray(weight, dt)
                    stats = stats + w * s
                    ll = ll + w * block_ll
                    alpha_ss = alpha_ss + w * block_ss
            gamma = jnp.concatenate(new_gamma)
            ll = float(ll)
            out.likelihoods.append(ll)
            if not faults.freeze_model:
                total = stats.astype(jnp.float32).sum(-1, keepdims=True)
                beta = jnp.where(stats > 0, stats.astype(jnp.float32) / total,
                                 0.0).astype(dt)
                if settings["estimate_alpha"]:
                    alpha = newton_alpha(float(alpha_ss), alpha, num_docs, k,
                                         int(settings["alpha_max_iters"]))
            if step == 0:
                out.log_beta_first = _log_floor(beta)
                out.alpha_first = alpha
            out.em_iters = step + 1
            if stop_rule and ll_prev is not None and abs(
                    (ll_prev - ll) / ll_prev) < float(settings["em_tol"]):
                break
            ll_prev = ll
    out.log_beta = _log_floor(beta)
    out.alpha = alpha
    out.gamma = np.asarray(gamma[:num_docs], np.float64)
    return out


def _log_floor(beta) -> np.ndarray:
    b = np.asarray(beta.astype(jnp.float32), np.float64)
    return np.where(b > 0, np.log(np.maximum(b, 1e-300)), LOG_ZERO)
