"""Sparse bucketed Pallas E-step: the full variational E-step fused
into one kernel over live tokens only.

The r03 capture measured 10.5% MXU / 3.1% HBM on the EM headline — the
dense engine (ops/dense_estep.py) rides the MXU but materializes K×V
work per chunk while the corpus is ~1.6%-dense CSR, i.e. ~60x the
FLOPs the math needs at the bench shape.  LightLDA (PAPERS.md) is the
existence proof that exploiting token sparsity — not a fancier sampler
— buys the next order of magnitude.  This kernel is that path: per doc
block, only the documents' live `beta[:, words]` columns cross HBM (the
[K, BB, L] gathered slab), and the per-EM-iteration work is K×L, not
K×V.

What it fuses that ops/pallas_estep.py leaves to XLA: the converged
tail.  The older sparse kernel converges gamma in VMEM but then XLA
re-reads the slab from HBM to build phi, scatter suff-stats, and
evaluate the ELBO — one full extra slab pass per EM iteration plus
digamma/gammaln in the lane-hostile [B, K] layout.  Here the tail runs
in-kernel while the slab is still VMEM-resident: the kernel emits the
phi-weighted counts `phi_c [K, BB, L]` (suff-stats factor — one XLA
segment-sum scatter per EM iteration remains, the sparse analogue of
densify's one scatter per run), the per-doc ELBO terms, and
sum_k E[log theta], exactly like the dense kernels' tails.

Precision: `precision="bf16"` stores the gathered slab half-width —
halving both its HBM crossing and its VMEM residency, the dominant
traffic — with every product accumulated in f32 and the gamma carry
f32 (the f64 host convergence check upstream is untouched).  Unlike
the dense engine's bf16 mode (operand truncation the TPU MXU performs
anyway — bit-identical), a bf16 slab genuinely rounds exp(log beta) to
8 significand bits, so results agree with f32 to bf16 tolerance, not
bit-exactly; the default stays f32.

Layout: documents arrive via `Corpus.bucketed_layout` (io/corpus.py) —
length-sorted power-of-two buckets floored at the 128-lane tile, packed
[BB, L] word-id/count tiles with an inverse permutation restoring
document order bit-exactly.  Block shapes resolve through the plans
cache (`sparse_estep_bb` for the doc block with the analytic VMEM pick
as prior, `sparse_estep_l` for the layout's lane-tile floor), and the
dense-vs-sparse engine decision is a MEASURED crossover persisted the
same way scoring's dispatch_calibration is (`estep_engine` knob, keyed
by exact shape and by density band) — data-driven, surviving process
death.

Reference anchor: same fixed point, convergence rule, and ELBO terms as
oni-lda-c's per-document inner loop (SURVEY.md §2.8, §3.3).
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.special import gammaln

from . import estep
from .pallas_estep import digamma_pos, gammaln_pos, newton_recip as _recip
from .stop import fp_continue

# VMEM working-set model, mirroring ops/dense_estep.py's: the ceiling
# gates the analytic block pick and _vmem_limit sizes the per-kernel
# scoped limit (2x headroom over the model, like dense — Mosaic's real
# stack allocation ran ~1.6x the modeled set there).  The phi_c output
# block doubles the slab-sized VMEM relative to pallas_estep's
# fixed-point-only kernel, which is why this model is separate.
_VMEM_CEILING = 64 * 1024 * 1024
# Doc-block cap, like dense_estep's: larger blocks stopped helping there
# (less pipeline overlap across grid steps).
_MAX_BLOCK_DOCS = 256

_PRECISIONS = ("f32", "bf16")


def _check_precision(precision: str) -> None:
    if precision not in _PRECISIONS:
        raise ValueError(
            f"unknown sparse E-step precision {precision!r}; expected "
            f"one of {'/'.join(_PRECISIONS)}"
        )


def _vmem_estimate(bb: int, l: int, k: int, precision: str = "f32") -> int:
    slab_item = 2 if precision == "bf16" else 4
    lp = -(-l // 128) * 128          # VMEM tiles pad the lane dim to 128
    return (
        2 * k * bb * lp * slab_item  # double-buffered slab block
        + 2 * k * bb * lp * 4        # double-buffered phi_c output block
        + 2 * k * bb * 128 * 4       # K-unrolled lane-padded column temps
        + 4 * bb * lp * 4            # counts/phinorm/ratio/log temporaries
    )


def _vmem_limit(bb: int, l: int, k: int, precision: str = "f32") -> int:
    est = _vmem_estimate(bb, l, k, precision)
    return min(max(32 * 1024 * 1024, est * 2), 128 * 1024 * 1024)


def scoped_vmem_kib(b: int, l: int, k: int,
                    precision: str = "f32") -> int | None:
    """Scoped-VMEM KiB drivers must pass as the
    xla_tpu_scoped_vmem_limit_kib compiler option when this kernel is
    fusion-wrapped inside a larger jitted program (the fused chunk
    runner) — XLA drops the pallas_call's own CompilerParams limit
    there, exactly as observed for the dense kernels."""
    bb = pick_block(b, l, k, precision)
    if bb is None:
        return None
    return _vmem_limit(bb, l, k, precision) // 1024


def _planned_block(b: int, l: int, k: int, precision: str) -> int | None:
    """Measured doc-block override from the plan cache (knob
    `sparse_estep_bb`): a probe/bench-recorded block for this exact
    (B, L, K, precision) on this backend.  The analytic VMEM pick stays
    the prior — pick_block re-validates a planned value against the
    same feasibility rules, so a stale or hand-edited entry can never
    produce an illegal grid.  Multi-host runs skip the lookup (the
    block feeds rank-collective engine decisions and per-host caches
    could hold different winners, like dense_estep._planned_block)."""
    try:
        if jax.process_count() > 1:
            return None
        from ..plans import lookup_value

        val = lookup_value("sparse_estep_bb",
                           shape=f"b{b}.l{l}.k{k}.{precision}")
        return int(val) if val else None
    except Exception:
        return None


def pick_block(b: int, l: int, k: int, precision: str = "f32") -> int | None:
    """Largest power-of-two doc block (<= 256) dividing `b` whose
    estimated working set fits the VMEM ceiling — or the plan cache's
    measured block for this shape when one exists and passes the same
    feasibility checks.  None = infeasible (callers fall back to the
    fixed-point-only Pallas kernel or pure XLA).  A bf16 slab puts the
    doc block on the 16-sublane tile (f32 tiles at 8)."""
    sub = 16 if precision == "bf16" else 8
    planned = _planned_block(b, l, k, precision)
    if (
        planned
        and planned <= b
        and b % planned == 0
        and planned % sub == 0
        and _vmem_estimate(planned, l, k, precision) <= _VMEM_CEILING
    ):
        return planned
    bb = sub
    best = None
    while bb <= min(b, _MAX_BLOCK_DOCS) and b % bb == 0:
        if _vmem_estimate(bb, l, k, precision) > _VMEM_CEILING:
            break
        best = bb
        bb *= 2
    return best


def pad_multiple_for(precision: str = "f32") -> int:
    """Batch-axis pad multiple the bucketed layout must use for this
    slab precision: doc blocks sit on the sublane tile (8 for f32, 16
    for bf16) and must divide the padded batch, so a layout padded to
    8 can strand a bf16 bucket (e.g. B=24) with no feasible block."""
    _check_precision(precision)
    return 16 if precision == "bf16" else 8


def resolve_layout_len(config_value=None,
                       use_plans: bool = True) -> "tuple[int, str]":
    """The bucketed layout's minimum packed tile length (the lane-tile
    floor `Corpus.bucketed_layout` pads buckets up to), resolved
    through the plans cache: knob `sparse_estep_l`, default from
    LDAConfig.sparse_min_bucket_len.  Returns (length, source).

    `use_plans=False` resolves from config/default only — multi-process
    distributed EM runs pin it (models/lda.py): per-host plan caches
    can legally hold different measured winners, and a rank-divergent
    bucket floor would give ranks different per-shard batch shapes
    than the 1-rank run, breaking the byte-identical-artifacts
    contract."""
    from ..plans import resolve

    kw = {} if use_plans else {"store": None}
    val, src = resolve("sparse_estep_l", config_value, **kw)
    return max(1, int(val)), src


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _sparse_kernel(
    alpha_ref, warm_ref, slab_ref, counts_ref, mask_ref, gamma_in_ref,
    gamma_ref, phic_ref, docll_ref, ass_ref, iters_ref,
    *, var_max_iters: int, var_tol: float,
):
    """One grid step = one block of BB documents; the [K, BB, L] slab
    block stays in VMEM for the fixed point AND the converged tail.

    The slab may arrive STORED bf16 (precision="bf16"): it is consumed
    via f32-promoting elementwise ops — every accumulation (phinorm,
    the gamma-update reduction, phi_c) runs f32, and the gamma carry is
    f32, so bf16 only rounds the gathered beta values themselves.

    warm_ref selects the fixed point's start: 0 = the reference's fresh
    alpha + N_d/K init, 1 = resume from gamma_in_ref (warm_start_gamma
    — same fixed point, fewer iterations once beta stabilizes)."""
    k_topics = slab_ref.shape[0]
    alpha = alpha_ref[0, 0]
    warm = warm_ref[0, 0]
    counts = counts_ref[...]                    # [BB, L] f32
    mask = mask_ref[...]                        # [BB, 1]
    n_d = jnp.sum(counts, axis=1, keepdims=True)
    # Relative stop normalizer: mean_k gamma = alpha + N_d/K for every
    # iterate (gamma rows sum to K*alpha + N_d exactly), making var_tol
    # a relative tolerance — reachable in f32 (see ops/estep.py).
    inv_scale = 1.0 / (alpha + n_d / k_topics)  # [BB, 1]

    def e_log_theta(gamma):
        return digamma_pos(gamma) - digamma_pos(
            jnp.sum(gamma, axis=1, keepdims=True)
        )

    def phinorm_of(exp_et):
        # K-unrolled FMA over the zero-padding [BB, L] tiles (a [BB, L,
        # K] block would pad K=20 to the 128-lane tile 6.4x; [K, BB, L]
        # pads nothing — same layout argument as pallas_estep).  A bf16
        # slab upcasts per use; accumulation is f32 either way.
        ph = jnp.zeros_like(counts)
        for k in range(k_topics):
            ph = ph + slab_ref[k] * exp_et[:, k : k + 1]
        return ph + 1e-30

    def body(state):
        gamma, it, delta_old, _ = state
        exp_et = jnp.exp(e_log_theta(gamma))    # [BB, K] f32
        ratio = counts * _recip(phinorm_of(exp_et))
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
    gamma0 = jnp.where(warm != 0, gamma_in_ref[...], fresh0)
    gamma, iters, _, _ = jax.lax.while_loop(
        cond,
        body,
        (gamma0, jnp.asarray(0, jnp.int32),
         jnp.asarray(jnp.inf, counts.dtype),
         jnp.asarray(jnp.inf, counts.dtype)),
    )

    # Converged single-pass tail while the slab is still VMEM-resident:
    # phi_c for the suff-stats scatter, the per-doc ELBO terms (token
    # term sum_l c*log(phinorm) AND the gamma-Dirichlet terms), and
    # sum_k E[log theta] — everything the older sparse path re-read the
    # slab from HBM for, computed here with the doc axis on the vector
    # sublanes.  Always full f32 off the converged gamma.
    e_lt = e_log_theta(gamma)
    exp_et = jnp.exp(e_lt)
    phinorm = phinorm_of(exp_et)
    ratio = (counts * _recip(phinorm)) * mask
    gamma_ref[...] = gamma
    tok = jnp.sum(counts * jnp.log(phinorm), axis=1, keepdims=True)
    core = jnp.sum(
        (alpha - gamma) * e_lt + gammaln_pos(gamma), axis=1, keepdims=True
    ) - gammaln_pos(jnp.sum(gamma, axis=1, keepdims=True))
    docll_ref[...] = (core + tok) * mask
    ass_ref[...] = jnp.sum(e_lt, axis=1, keepdims=True) * mask
    for k in range(k_topics):
        phic_ref[k] = slab_ref[k] * (ratio * exp_et[:, k : k + 1])
    iters_ref[pl.program_id(0), 0] = iters


def fixed_point_full(
    slab_kbl: jnp.ndarray,   # [K, B, L] gathered exp(beta), f32 or bf16
    alpha: jnp.ndarray,
    counts: jnp.ndarray,     # [B, L] f32
    doc_mask: jnp.ndarray,   # [B]
    var_max_iters: int,
    var_tol: float,
    block: int | None = None,
    interpret: bool = False,
    gamma_prev=None,         # [B, K] warm start (None = fresh init)
    warm=None,               # traced scalar gating gamma_prev
):
    """Fused sparse E-step core.  Returns (gamma [B, K] f32,
    phi_c [K, B, L] f32, docll [B], alpha_ss_part [B], iters scalar,
    doc_sweeps scalar) — docll is the full per-doc ELBO minus the
    alpha-prior constant, phi_c the per-token phi-weighted counts ready
    for the [V, K] segment-sum scatter, doc_sweeps the sum over doc
    blocks of a block's sweeps x its rows."""
    k_topics, b, l = slab_kbl.shape
    precision = "bf16" if slab_kbl.dtype == jnp.bfloat16 else "f32"
    bb = block or pick_block(b, l, k_topics, precision)
    if bb is None:
        raise ValueError(
            f"no VMEM-feasible doc block for B={b}, L={l}, K={k_topics} "
            f"({precision})"
        )
    if b % bb:
        raise ValueError(
            f"doc block {bb} does not divide batch size {b}; the grid "
            "would silently drop the remainder documents"
        )
    grid = b // bb
    kernel = functools.partial(
        _sparse_kernel, var_max_iters=var_max_iters, var_tol=var_tol
    )
    counts = jnp.asarray(counts, jnp.float32)
    if gamma_prev is None:
        gamma_in = jnp.zeros((b, k_topics), jnp.float32)
        warm = jnp.asarray(0, jnp.int32)
    else:
        estep.check_warm_pair(gamma_prev, warm)
        gamma_in = jnp.asarray(gamma_prev, jnp.float32)
        warm = jnp.asarray(warm, jnp.int32)
    gamma, phic, docll, ass, iters = pl.pallas_call(
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
            pl.BlockSpec(
                (k_topics, bb, l), lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((bb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k_topics), jnp.float32),
            jax.ShapeDtypeStruct((k_topics, b, l), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bb, l, k_topics, precision)
        ),
        interpret=interpret,
        name="sparse_estep",
    )(
        jnp.reshape(jnp.asarray(alpha, jnp.float32), (1, 1)),
        jnp.reshape(warm, (1, 1)),
        slab_kbl,
        counts,
        jnp.reshape(jnp.asarray(doc_mask, jnp.float32), (b, 1)),
        gamma_in,
    )
    return gamma, phic, docll[:, 0], ass[:, 0], iters.max(), iters.sum() * bb


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
    precision: str = "f32",  # "bf16": half-width slab storage
    block: int | None = None,
) -> estep.EStepResult:
    """Drop-in for estep.e_step with the FULL E-step fused in Pallas.

    The slab is gathered once in [K, B, L] layout (zero tile padding;
    bf16-stored when precision="bf16"), the kernel converges gamma and
    emits phi_c/ELBO/alpha-ss in one VMEM residency, and the only XLA
    work left is the [V, K] segment-sum scatter of phi_c plus the
    alpha-prior constant — K×L work per doc where the dense engine pays
    K×V.
    """
    _check_precision(precision)
    v = log_beta.shape[1]
    k_topics = log_beta.shape[0]
    slab_kbl = jnp.exp(log_beta)[:, word_idx]           # [K, B, L]
    if precision == "bf16":
        slab_kbl = slab_kbl.astype(jnp.bfloat16)
    gamma, phic, docll, ass, iters, sweeps = fixed_point_full(
        slab_kbl, alpha, counts, doc_mask, var_max_iters, var_tol,
        block=block, interpret=interpret, gamma_prev=gamma_prev, warm=warm,
    )
    b, l = word_idx.shape
    suff = jax.ops.segment_sum(
        phic.transpose(1, 2, 0).reshape(b * l, k_topics),
        word_idx.reshape(b * l),
        num_segments=v,
    )
    alpha_const = gammaln(k_topics * alpha) - k_topics * gammaln(alpha)
    likelihood = docll.sum() + doc_mask.sum() * alpha_const
    return estep.EStepResult(gamma, suff, ass.sum(), likelihood, iters,
                             sweeps)


def make_e_step_fn(precision: str = "f32", interpret: "bool | None" = None):
    """Driver-facing sparse engine: a warm-capable callable with
    estep.e_step's signature, for LDATrainer/make_chunk_runner's
    e_step_fn hook.  `interpret=None` auto-selects interpret mode off
    TPU (the tier-1 CPU path)."""
    _check_precision(precision)

    def sparse_e_step(log_beta, alpha, word_idx, counts, doc_mask,
                      var_max_iters, var_tol, gamma_prev=None, warm=None):
        interp = (
            jax.default_backend() != "tpu" if interpret is None
            else interpret
        )
        return e_step(
            log_beta, alpha, word_idx, counts, doc_mask,
            var_max_iters, var_tol, interpret=interp,
            gamma_prev=gamma_prev, warm=warm, precision=precision,
        )

    sparse_e_step._oni_warm_capable = True
    sparse_e_step._oni_sparse_engine = True
    sparse_e_step.precision = precision
    return sparse_e_step


# ---------------------------------------------------------------------------
# FLOP accounting — effective (sparse) vs dense-equivalent
# ---------------------------------------------------------------------------


def effective_flops(b: int, l: int, k: int, vi_iters: float) -> float:
    """FLOPs the E-step MATH needs per EM iteration at this shape: two
    K-contractions over the [B, L] live-token slab per VI iteration
    plus the converged tail pass — 4*B*K*L*(vi+1).  This is the
    numerator of the roofline's "useful fraction of peak"
    (useful_mxu_pct): an engine that executes more than this is padding
    (the dense engine's K×V qmat) or re-reading (the split sparse
    path's XLA tail)."""
    return 4.0 * b * k * l * (float(vi_iters) + 1.0)


def dense_equiv_flops(b: int, v: int, k: int, vi_iters: float) -> float:
    """FLOPs the DENSE engine executes for the same batch: the same two
    contractions over the lane-padded [B, W] densified corpus —
    effective_flops with L replaced by padded_width(V).  The ratio
    dense_equiv/effective is the density-driven waste factor (~60x at
    the 1.6%-dense bench shape)."""
    from . import dense_estep

    return 4.0 * b * k * dense_estep.padded_width(v) * (
        float(vi_iters) + 1.0
    )


# ---------------------------------------------------------------------------
# Measured dense-vs-sparse crossover — persisted like dispatch_calibration
# ---------------------------------------------------------------------------

# Per-process memo of resolved crossovers, keyed by exact shape sig.
_CROSSOVER_CACHE: "dict[str, dict]" = {}


def density_pct(l: int, v: int) -> float:
    """Row density of the densified batch: L live-token columns out of
    V — the x-axis of the dense-vs-sparse crossover."""
    return 100.0 * l / max(v, 1)


def _density_band(pct: float) -> int:
    """Log2 density band (clamped): 1.6% -> band 1 (covers ~1.4-2.8%),
    so a crossover measured at one shape generalizes to neighbouring
    densities without claiming exact-shape evidence."""
    import math

    return max(-3, min(7, int(round(math.log2(max(pct, 1e-3))))))


def crossover_shapes(k: int, v: int, b: int, l: int,
                     precision: str) -> "tuple[str, str]":
    """(exact shape sig, density-band sig) the crossover records under
    — exact beats band at lookup, band lets probes seed whole density
    regimes."""
    exact = f"k{k}.v{v}.b{b}.l{l}.{precision}"
    band = f"dlog{_density_band(density_pct(l, v))}.k{k}.{precision}"
    return exact, band


def _journal_crossover(rec: dict) -> None:
    """Journal the resolved crossover so every run's engine choice is
    attributable post-hoc ({"kind": "estep_crossover"} — see
    docs/observability.md).  Never raises."""
    try:
        from ..telemetry.spans import current_recorder

        r = current_recorder()
        if r is not None:
            r.journal_record({
                "kind": "estep_crossover",
                "engine": rec["engine"],
                "shape": rec["shape"],
                "dense_s": rec["dense_s"],
                "sparse_s": rec["sparse_s"],
                "source": rec["source"],
            })
    except Exception:
        pass


def measure_crossover(k: int, v: int, b: int, l: int, *,
                      precision: str = "f32", reps: int = 2) -> dict:
    """Time one E-step through each engine at this exact shape and
    return the winner: {"engine", "dense_s", "sparse_s", "source",
    "shape"}.  The densify scatter runs OUTSIDE the dense timing (the
    production driver amortizes it over the run), so the comparison is
    per-EM-iteration marginal cost — the quantity the engine choice
    actually trades.  An engine whose shape is block-infeasible times
    as None and loses by default; both-infeasible returns "dense"
    (the dense family's own fallbacks — compact, XLA — take over)."""
    from . import dense_estep

    _check_precision(precision)
    exact, _ = crossover_shapes(k, v, b, l, precision)
    rng = np.random.default_rng(0)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )
    word_np = rng.integers(0, v, size=(b, l)).astype(np.int32)
    counts_np = rng.integers(1, 5, size=(b, l)).astype(np.float32)
    word_idx = jnp.asarray(word_np)
    counts = jnp.asarray(counts_np)
    mask = jnp.ones((b,), jnp.float32)
    alpha = jnp.float32(2.5)
    interp = jax.default_backend() != "tpu"
    # Bounded fixed point: the crossover compares per-iteration engine
    # cost, not convergence (var_tol=0 pins the trip count so both
    # engines execute identical VI work).
    vi = 8

    def best_of(fn):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            t = min(t, time.perf_counter() - t0)
        return t

    sparse_s = dense_s = None
    if pick_block(b, l, k, precision) is not None:
        sparse_fn = jax.jit(functools.partial(
            e_step, var_max_iters=vi, var_tol=0.0, interpret=interp,
            precision=precision,
        ))
        run = lambda: sparse_fn(log_beta, alpha, word_idx, counts, mask)  # noqa: E731
        jax.block_until_ready(run())            # compile + warm
        sparse_s = best_of(run)
    if dense_estep.pick_block(b, v, k, precision) is not None:
        store = dense_estep.corpus_dtype(
            dense_estep.max_dense_cell(word_np, counts_np), precision
        )
        dense = dense_estep.densify(word_idx, counts, v, dtype=store)
        dense_fn = jax.jit(functools.partial(
            dense_estep.e_step_dense, var_max_iters=vi, var_tol=0.0,
            interpret=interp, precision=precision,
        ))
        run_d = lambda: dense_fn(log_beta, alpha, dense, mask)  # noqa: E731
        jax.block_until_ready(run_d())          # compile + warm
        dense_s = best_of(run_d)
    if sparse_s is not None and (dense_s is None or sparse_s <= dense_s):
        engine = "sparse"
    else:
        engine = "dense"
    # Leave no trace in jax's tracing caches.  A cached inner jaxpr
    # keeps the source location of whoever traced it first, a Mosaic
    # kernel is serialized with its locations, and jax cannot strip
    # them from the compilation-cache key: without this, the training
    # program of a process that MEASURED here carries this function's
    # locations inside its kernels and misses the persistent cache in
    # every later process that loads the plan instead (seen on the
    # v5e: PERF.md, PR 21).
    jax.clear_caches()
    return {
        "engine": engine,
        "dense_s": dense_s,
        "sparse_s": sparse_s,
        "source": "measured",
        "shape": exact,
    }


def engine_crossover(k: int, v: int, b: int, l: int, *,
                     precision: str = "f32", force: bool = False) -> dict:
    """The measured dense-vs-sparse engine decision for this shape —
    dispatch_calibration's pattern applied to the E-step engines.

    Resolution order: this process's memo, then a plan-cache entry
    (knob `estep_engine`, exact shape beating the density band —
    source "plan", so run 2 re-measures nothing), else a fresh
    measurement persisted under BOTH keys with its timings as
    provenance.  ONI_ML_TPU_ESTEP_ENGINE=sparse|dense overrides with a
    pin (source "env").  Every resolution journals a
    {"kind": "estep_crossover"} record under an active recorder."""
    _check_precision(precision)
    exact, band = crossover_shapes(k, v, b, l, precision)
    env = os.environ.get("ONI_ML_TPU_ESTEP_ENGINE", "")
    if env:
        if env not in ("sparse", "dense"):
            raise ValueError(
                f"ONI_ML_TPU_ESTEP_ENGINE={env!r}: expected sparse or "
                "dense"
            )
        rec = {"engine": env, "dense_s": None, "sparse_s": None,
               "source": "env", "shape": exact}
        _journal_crossover(rec)
        return rec
    if not force and exact in _CROSSOVER_CACHE:
        return _CROSSOVER_CACHE[exact]
    if not force:
        from ..plans import lookup_value

        for shape in (exact, band):
            planned = lookup_value("estep_engine", shape=shape)
            if isinstance(planned, dict) and planned.get("engine") in (
                "sparse", "dense",
            ):
                rec = {
                    "engine": planned["engine"],
                    "dense_s": planned.get("dense_s"),
                    "sparse_s": planned.get("sparse_s"),
                    "source": "plan",
                    "shape": shape,
                }
                _CROSSOVER_CACHE[exact] = rec
                _journal_crossover(rec)
                return rec
    rec = measure_crossover(k, v, b, l, precision=precision)
    _CROSSOVER_CACHE[exact] = rec
    from ..plans import note_sweep, record_value

    note_sweep("estep_engine")
    value = {kk: rec[kk] for kk in ("engine", "dense_s", "sparse_s")}
    measurements = {"dense_s": rec["dense_s"], "sparse_s": rec["sparse_s"]}
    record_value("estep_engine", value, shape=exact, source="autotune",
                 measurements=measurements)
    record_value("estep_engine", value, shape=band, source="autotune",
                 measurements=measurements)
    _journal_crossover(rec)
    return rec
