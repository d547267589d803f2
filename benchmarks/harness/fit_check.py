"""The comparison that decides `correct` for a fit cell.

What is compared is what the timed call produced at the timed sizes:
`timed` is the last fit of the window; `probe1` is the same call on the same
corpus stopped after one EM iteration (`em_max_iters`, which the compiled
programs take as a dynamic trip count).  N is the timed fit's own number of
EM iterations where that is no more than the traffic file's `check_steps`:
then `probeN` IS the timed fit, held whole, final state and all.  A longer
fit is held through a second probe stopped after `check_steps` iterations:
one more than the program's `host_sync_every`, so that the probe and the
timed fit's first N iterations cross a dispatch boundary and a host sync of
the EM driver (where EM learns topics its path is chaotic and no state that
late can be held: PERF.md section 2).  `ref` is the plain reference's first
N EM iterations from the same initialisation.  Every number is a gap that is
0 for a perfect program and has a limit of its own in the cell's traffic
file (`limits`), set between what sound runs read and what the
lower-precision control and the planted faults read (PERF.md section 2).

  ll_rel        each of the first N EM iterations' ELBO of the timed fit,
                and of the probe(s), against the reference's: worst
                |gap| / |reference|
  beta1_gap     the first M-step's move of each topic row (probability
  betaN_gap     space), and the move after N: gap between the program's
                norm and the reference's, against the reference's norm of
                that row or of the median row, worst row
  alpha_rel     alpha after one and after N iterations, worst relative
  gammaN_max    the N-th E-step's posterior of every document, in document
                order: worst document's L1 gap against its L1 norm
  gammaN_mean   the mean document's
  rowsum        timed fit's final gamma: sum_k gamma_dk - N_d is the same
                K*alpha for every document (a row in the wrong place, or a
                row never computed, breaks it): worst document
  stop_rule     0 if the timed fit stopped where |dll/ll| < em_tol first
                held (or at em_max_iters) and not before; else 1
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("ll_rel", "beta1_gap", "betaN_gap", "alpha_rel", "gammaN_max",
           "gammaN_mean", "rowsum", "stop_rule")


def _row_move_gap(log_beta_prog, log_beta_ref, log_beta0) -> float:
    b0 = np.exp(log_beta0)
    move_p = np.linalg.norm(np.exp(log_beta_prog) - b0, axis=-1)
    move_r = np.linalg.norm(np.exp(log_beta_ref) - b0, axis=-1)
    return float(np.max(
        np.abs(move_p - move_r) / np.maximum(move_r, np.median(move_r))))


def _rel(a, b) -> float:
    return float(abs(a - b) / abs(b))


def stop_rule_broken(likelihoods, em_iters, em_tol, em_max_iters) -> float:
    ll = np.asarray(likelihoods, np.float64)
    if len(ll) != em_iters or em_iters < 1 or em_iters > em_max_iters:
        return 1.0
    conv = np.abs((ll[:-1] - ll[1:]) / ll[:-1])
    early = bool(np.any(conv[:-1] < em_tol))
    stopped = em_iters == em_max_iters or (
        len(conv) > 0 and conv[-1] < em_tol)
    return 0.0 if stopped and not early else 1.0


def compare(timed, probe1, probe_n, ref, log_beta0, doc_tokens, lda: dict
            ) -> dict:
    """name -> value, for every name in NUMBERS.  `timed`, `probe1`,
    `probe_n` carry log_beta, gamma, alpha, likelihoods, em_iters; `ref`
    ran as many iterations as `probe_n` was asked for."""
    want = len(ref.likelihoods)
    out = {}
    runs = [timed.likelihoods[:want], probe_n.likelihoods,
            probe1.likelihoods]
    if [len(r) for r in runs] != [want, want, 1]:
        out["ll_rel"] = 1.0          # a trajectory is missing iterations
    else:
        out["ll_rel"] = max(_rel(ll, ref.likelihoods[i])
                            for r in runs for i, ll in enumerate(r))
    out["beta1_gap"] = _row_move_gap(
        probe1.log_beta, ref.log_beta_first, log_beta0)
    out["betaN_gap"] = _row_move_gap(probe_n.log_beta, ref.log_beta,
                                     log_beta0)
    out["alpha_rel"] = max(_rel(probe1.alpha, ref.alpha_first),
                           _rel(probe_n.alpha, ref.alpha))
    doc_gap = (np.abs(np.asarray(probe_n.gamma) - ref.gamma).sum(-1)
               / np.abs(ref.gamma).sum(-1))
    out["gammaN_max"] = float(doc_gap.max())
    out["gammaN_mean"] = float(doc_gap.mean())
    excess = np.asarray(timed.gamma).sum(-1) - doc_tokens
    level = np.median(excess)
    out["rowsum"] = float(np.max(
        np.abs(excess - level) / (doc_tokens + abs(level))))
    out["stop_rule"] = stop_rule_broken(
        timed.likelihoods, timed.em_iters, lda["em_tol"],
        lda["em_max_iters"])
    return out


def judge(values: dict, limits: dict) -> tuple[bool, list]:
    """(correct, rows): a row is [name, value, limit].  A number with no
    limit, or one that is not finite, is not correct."""
    rows, ok = [], True
    for name in NUMBERS:
        value, limit = values[name], limits.get(name)
        good = (limit is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and bool(good)
        rows.append([name, float(value), limit])
    return ok, rows
