"""Pallas TPU kernel for the E-step fixed point.

The XLA path (ops/estep.py) re-reads the gathered beta slab from HBM on
every variational iteration: ~20 iterations x 2 contractions over a
[B, L, K] slab is the dominant HBM traffic of the whole EM loop.  This
kernel blocks documents into VMEM-sized chunks and runs the ENTIRE
gamma fixed point — digamma, phinorm, gamma update, convergence check —
with the chunk's slab resident in VMEM, so the slab crosses HBM exactly
once per EM iteration instead of once per variational iteration.

Layout: the slab rides as [K, B, L] (documents and tokens on the two
minor, tiled dimensions).  With K=20 topics a [B, L, K] block would pad
the 128-lane axis 6.4x; [K, BB, L] blocks pad nothing and make the two
per-iteration contractions K-unrolled VPU reductions over [BB, L] tiles.

digamma is not a Mosaic primitive, so the kernel carries its own:
the standard recurrence psi(x) = psi(x+1) - 1/x pushed until x >= 6
(branchless, 7 steps covers any positive f32 gamma) followed by the
asymptotic series ln x - 1/2x - 1/12x^2 + 1/120x^4 - 1/252x^6, whose
truncation error at x >= 6 (~1e-9) is below f32 resolution.

Semantics match estep.fixed_point except that convergence is decided
per document block rather than over the full batch (each block stops
iterating when ITS docs converge — the same per-shard independence the
distributed layer already has), so converged gammas agree to var_tol.

Reference anchor: this is the inner loop of oni-lda-c's doc E-step
(SURVEY.md §2.8, §3.3) — the hot loop of the whole reference system.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import estep
from .stop import fp_continue

# VMEM working-set model for picking the doc block size.  Two terms
# dominate: the double-buffered slab block (2 * K*BB*L*4) and the
# K-unrolled column temporaries, which the 128-lane tiling pads from
# [BB, 1] to [BB, 128] each — two live sets of K of them
# (2 * K*BB*128*4).  Empirically calibrated against Mosaic's 16MB
# scoped-VMEM limit: (K=20, L=128, bb=512) blew it by 88KB and
# (K=50, L=16, bb=256) by 3.4MB, while everything under ~12MB by this
# model compiles with room to spare.
_VMEM_BUDGET = 12 * 1024 * 1024
# 128-doc blocks also benched faster than 256 at the production shapes
# (more pipeline overlap across grid steps).
_MAX_BLOCK_DOCS = 128


def _vmem_estimate(bb: int, l: int, k: int, precision: str = "f32") -> int:
    """Working-set bytes at doc block `bb`.  `precision` is the SLAB
    storage dtype ("bf16" halves the double-buffered slab term — the
    dominant one), mirroring dense_estep._vmem_estimate's signature;
    before this took a precision, bf16 block picks sized VMEM as f32
    and silently halved the feasible block space."""
    slab_item = 2 if precision == "bf16" else 4
    return 2 * k * bb * l * slab_item + 2 * k * bb * 128 * 4


def newton_recip(q: jnp.ndarray) -> jnp.ndarray:
    """Newton-polished VPU reciprocal: the hardware's approximate
    reciprocal (~1.6e-5 max rel error on v5e) plus one Newton step,
    landing ~1.4e-7 — about 1 ulp of f32, i.e. numerically
    interchangeable with the exact divide at a third of its cost (the
    vector divide dominated the fixed-point bodies).  Measured again
    inside a Mosaic kernel on the v5e under jax 0.9.0 (PERF.md, PR 21):
    1.6e-5 before the step, 1.4e-7 after.  Interpret mode (CPU tests)
    emulates a coarser approximation (~4e-3, 1.4e-5 after the step)."""
    r0 = pl.reciprocal(q, approx=True)
    return r0 * (2.0 - q * r0)


def gammaln_pos(x: jnp.ndarray) -> jnp.ndarray:
    """log Gamma(x) for strictly positive x, f32-accurate, elementwise
    VPU ops only (usable inside Pallas kernels).  Same recurrence-shift
    structure as digamma_pos: push x above 6 while accumulating the
    product Gamma(x+n)/Gamma(x) = x(x+1)...(x+n-1), then Stirling."""
    prod = jnp.ones_like(x)
    for _ in range(7):
        small = x < 6.0
        prod = prod * jnp.where(small, x, 1.0)
        x = x + jnp.where(small, 1.0, 0.0)
    inv = 1.0 / x
    inv2 = inv * inv
    # 0.5*log(2*pi)
    series = (
        (x - 0.5) * jnp.log(x)
        - x
        + 0.9189385332046727
        + inv * (1.0 / 12.0 - inv2 * (1.0 / 360.0 - inv2 / 1260.0))
    )
    return series - jnp.log(prod)


def digamma_pos(x: jnp.ndarray) -> jnp.ndarray:
    """digamma for strictly positive x, f32-accurate.  Works inside
    Pallas kernels (elementwise VPU ops only)."""
    acc = jnp.zeros_like(x)
    for _ in range(7):
        small = x < 6.0
        acc = acc - jnp.where(small, 1.0 / x, 0.0)
        x = x + jnp.where(small, 1.0, 0.0)
    inv = 1.0 / x
    inv2 = inv * inv
    series = (
        jnp.log(x)
        - 0.5 * inv
        - inv2 * (1.0 / 12.0 - inv2 * (1.0 / 120.0 - inv2 / 252.0))
    )
    return series + acc


def _fixed_point_kernel(
    alpha_ref, warm_ref, slab_ref, counts_ref, mask_ref, gamma_in_ref,
    gamma_ref, iters_ref,
    *, var_max_iters: int, var_tol: float,
):
    """One grid step = one block of BB documents, slab block [K, BB, L]
    in VMEM for the whole variational loop.

    warm_ref selects the start: 0 = the reference's fresh alpha + N_d/K
    init, 1 = resume from gamma_in_ref (warm_start_gamma — same fixed
    point, fewer iterations once beta stabilizes)."""
    k_topics = slab_ref.shape[0]
    alpha = alpha_ref[0, 0]
    warm = warm_ref[0, 0]
    counts = counts_ref[:]                      # [BB, L]
    mask = mask_ref[:]                          # [BB, 1]
    n_d = jnp.sum(counts, axis=1, keepdims=True)
    # Relative stop: mean_k gamma = alpha + N_d/K is iteration-invariant
    # (gamma rows sum to K*alpha + N_d exactly), so this normalizer makes
    # var_tol a relative tolerance — reachable in f32, unlike an absolute
    # 1e-6 against gamma magnitudes (see ops/estep.py fixed_point).
    inv_scale = 1.0 / (alpha + n_d / k_topics)  # [BB, 1]

    def e_log_theta(gamma):
        return digamma_pos(gamma) - digamma_pos(
            jnp.sum(gamma, axis=1, keepdims=True)
        )

    def body(state):
        gamma, it, delta_old, _ = state
        exp_et = jnp.exp(e_log_theta(gamma))    # [BB, K]
        phinorm = jnp.zeros_like(counts)
        for k in range(k_topics):               # K-unrolled VPU reduction
            phinorm = phinorm + slab_ref[k] * exp_et[:, k : k + 1]
        ratio = counts * newton_recip(phinorm + 1e-30)
        cols = []
        for k in range(k_topics):
            t = jnp.sum(ratio * slab_ref[k], axis=1, keepdims=True)
            cols.append(alpha + exp_et[:, k : k + 1] * t)
        gamma_new = jnp.concatenate(cols, axis=1)
        delta = jnp.max(
            jnp.mean(jnp.abs(gamma_new - gamma), axis=1, keepdims=True)
            * inv_scale * mask
        )
        return gamma_new, it + 1, delta, delta_old

    def cond(state):
        # var_tol or gated stagnation — the shared rule (ops/stop.py).
        _, it, delta, prev = state
        return fp_continue(it, delta, prev, var_max_iters, var_tol)

    fresh0 = (alpha + n_d / k_topics) + jnp.zeros(
        (counts.shape[0], k_topics), counts.dtype
    )
    gamma0 = jnp.where(warm != 0, gamma_in_ref[:], fresh0)
    gamma, iters, _, _ = jax.lax.while_loop(
        cond,
        body,
        (gamma0, jnp.asarray(0, jnp.int32),
         jnp.asarray(jnp.inf, counts.dtype),
         jnp.asarray(jnp.inf, counts.dtype)),
    )
    gamma_ref[:] = gamma
    iters_ref[pl.program_id(0), 0] = iters


def pick_block(b: int, l: int, k: int, precision: str = "f32") -> int | None:
    """Largest power-of-two doc block whose estimated kernel working set
    (double-buffered slab + the K sets of lane-padded column temporaries,
    _vmem_estimate) fits the VMEM budget.  None if no valid block exists
    (fall back to the XLA path).  A bf16-stored slab needs its doc
    block on the 16-sublane tile (f32 tiles at 8)."""
    bb = 16 if precision == "bf16" else 8
    best = None
    while bb <= min(b, _MAX_BLOCK_DOCS) and b % bb == 0:
        if _vmem_estimate(bb, l, k, precision) > _VMEM_BUDGET:
            break
        best = bb
        bb *= 2
    return best


def fixed_point(
    slab_kbl: jnp.ndarray,   # [K, B, L] gathered beta, f32
    alpha: jnp.ndarray,
    counts: jnp.ndarray,     # [B, L]
    doc_mask: jnp.ndarray,   # [B]
    var_max_iters: int,
    var_tol: float,
    block: int | None = None,
    interpret: bool = False,
    gamma_prev=None,         # [B, K] warm start (None = fresh init)
    warm=None,               # traced scalar gating gamma_prev
):
    """Pallas gamma fixed point.  Returns (gamma [B, K], iters scalar,
    doc_sweeps scalar: the sum over doc blocks of sweeps x rows)."""
    k_topics, b, l = slab_kbl.shape
    bb = block or pick_block(b, l, k_topics)
    if bb is None:
        raise ValueError(
            f"no VMEM-feasible doc block for B={b}, L={l}, K={k_topics}"
        )
    grid = b // bb
    kernel = functools.partial(
        _fixed_point_kernel, var_max_iters=var_max_iters, var_tol=var_tol
    )
    dtype = slab_kbl.dtype
    if gamma_prev is None:
        gamma_in = jnp.zeros((b, k_topics), dtype)
        warm = jnp.asarray(0, jnp.int32)
    else:
        estep.check_warm_pair(gamma_prev, warm)
        gamma_in = jnp.asarray(gamma_prev, dtype)
        warm = jnp.asarray(warm, jnp.int32)
    gamma, iters = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (k_topics, bb, l), lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((bb, l), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, k_topics), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bb, k_topics), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            # Whole-array SMEM buffer; each grid step writes its own row.
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k_topics), slab_kbl.dtype),
            jax.ShapeDtypeStruct((grid, 1), jnp.int32),
        ],
        interpret=interpret,
        name="pallas_estep",
    )(
        jnp.reshape(jnp.asarray(alpha, slab_kbl.dtype), (1, 1)),
        jnp.reshape(warm, (1, 1)),
        slab_kbl,
        counts,
        jnp.reshape(doc_mask, (b, 1)),
        gamma_in,
    )
    return gamma, iters.max(), iters.sum() * bb


def e_step(
    log_beta: jnp.ndarray,   # [K, V]
    alpha: jnp.ndarray,
    word_idx: jnp.ndarray,   # [B, L]
    counts: jnp.ndarray,     # [B, L]
    doc_mask: jnp.ndarray,   # [B]
    var_max_iters: int,
    var_tol: float,
    interpret: bool = False,
    gamma_prev=None,         # [B, K] warm start (None = fresh init)
    warm=None,               # traced scalar gating gamma_prev
) -> estep.EStepResult:
    """Drop-in for estep.e_step with the fixed point in Pallas.

    The slab is gathered once in [K, B, L] layout (zero tile padding),
    the kernel converges gamma block-wise in VMEM, and the remaining
    single-pass terms (phi, suff-stats scatter, ELBO) stay in XLA.
    """
    v = log_beta.shape[1]
    slab_kbl = jnp.exp(log_beta)[:, word_idx]           # [K, B, L]
    gamma, iters, sweeps = fixed_point(
        slab_kbl, alpha, counts, doc_mask, var_max_iters, var_tol,
        interpret=interpret, gamma_prev=gamma_prev, warm=warm,
    )
    # Single-pass tail terms: same code as the XLA backend (XLA fuses the
    # layout transpose into the consumers).
    beta_bt = slab_kbl.transpose(1, 2, 0)               # [B, L, K]
    phi_c, phinorm = estep.phi_weighted(beta_bt, gamma, counts, doc_mask)
    suff = estep.suff_stats(phi_c, word_idx, v)
    likelihood, alpha_ss = estep.batch_likelihood(
        gamma, phinorm, counts, alpha, doc_mask
    )
    return estep.EStepResult(gamma, suff, alpha_ss, likelihood, iters,
                             sweeps)
