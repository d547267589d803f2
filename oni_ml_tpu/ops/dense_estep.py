"""Dense-corpus Pallas E-step: the gather/scatter-free fast path.

Profiling the round-1 pipeline on the v5e showed the per-token memory ops
— the [K, B, L] beta slab gather (~5.6 ms) and the [B*L, K] -> [V, K]
suff-stats scatter (~4-9 ms) — dominate the EM iteration, not the
variational fixed point itself (XLA's TPU gather/scatter cost is
per-index, ~10 ns/token, regardless of layout; six scatter formulations
benchmarked 7-14 ms).  The TPU-native fix is to stop indexing per token
altogether: densify the corpus once per batch group into C[b, v] (counts
matrix, zero for absent words) and run the whole E-step as MXU matmuls:

    q     = exp_et @ beta          # phinorm for every (doc, word) pair
    ratio = C / q                  # zero wherever C is zero
    gamma = alpha + exp_et * (ratio @ beta^T)
    T     = exp_et^T @ ratio       # suff stats:  SS[k,v] = beta[k,v]*T[k,v]

The identity behind T: phi_c[b,l,k] = beta[k,w]*exp_et[b,k]*c/phinorm, so
summing over tokens with w[b,l]=v factors beta[k,v] out of the scatter —
what remains is a plain matmul over the doc axis.  The densification is
~60x more FLOPs than the sparse math at the bench shape (1.6% density)
but runs ~2x faster end-to-end, because it rides the MXU at full tile
utilization instead of the gather unit (measured 6.6 ms vs 15.2 ms for
the full E-step at K=20, V=8192, B=4096, L=128).

The kernel blocks documents; C_block, q, and ratio live in VMEM for the
entire per-block fixed point, beta rides along whole (it re-reads HBM
once per block), and the T accumulator is a revisited output block
summed across sequential grid steps.  C crosses HBM exactly once per EM
iteration: a batch that lives in a shape group's stack [NB, B, W] is
read out of the stack in place (`batch_index`, `_corpus_call`), not from
a copy of it.

Within the fixed point the [BB, V] ratio divide — not the matmuls —
was the dominant cost (the VPU's vector divide runs ~1/3 the kernel's
time; the matmuls hit ~35 TF/s).  It is replaced by the hardware's
approximate reciprocal plus one Newton step (_recip), which lands ~1
ulp from the exact divide and took the headline-shape fixed-point
iteration from ~221 us to ~89 us (EM iteration 4.7 -> ~2.0 ms).

Scale limits: the dense path needs C on device ([stacked docs] x V x 4
bytes — the driver's dense_hbm_budget gates this) and a VMEM-feasible
doc block (`pick_block`; the 50-topic/50k-vocab config-3 shape fits at
BB=64).  Shapes beyond either limit fall back to the sparse Pallas/XLA
paths (ops/pallas_estep.py).  Data-parallel meshes keep this kernel:
parallel.make_data_parallel_dense_e_step shard_maps it over the doc
axis with suff-stats psum'd over ICI.  Vocab-sharded runs get their own
XLA-level dense plan (parallel.make_vocab_sharded_dense_e_step — this
kernel needs full V per device, that one column-shards C and beta).

Reference anchor: this replaces oni-lda-c's per-document inner loop
(SURVEY.md §2.8, §3.3) — `lda est` E-step semantics are preserved
exactly (same fixed point, same convergence rule, same ELBO terms).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.special import gammaln

from . import estep
# newton_recip: the [BB, V] ratio = C/q divide was ~2/3 of the
# fixed-point body's time (7.1 -> 2.1 us per iteration per 128-doc
# block at V=8192, K=20); the matmuls themselves run at ~35 TF/s.
from .pallas_estep import digamma_pos, gammaln_pos, newton_recip as _recip
from .stop import fp_continue

# VMEM working-set model: double-buffered C block + q + ratio (each
# [BB, V] f32) + beta and the T accumulator (each [K, V] f32), plus
# slack for small temporaries.  Calibrated on v5e: BB=64 compiles under
# the default 16MB scoped limit, BB=128 needs ~48MB, BB=256 ~80MB (the
# chip has 128MB of VMEM; the scoped limit is raised per-kernel below).
_VMEM_CEILING = 96 * 1024 * 1024

_PRECISIONS = ("f32", "bf16")


def _check_precision(precision: str) -> None:
    if precision not in _PRECISIONS:
        raise ValueError(
            f"unknown dense E-step precision {precision!r} (set via "
            "LDAConfig.dense_precision); expected one of "
            f"{'/'.join(_PRECISIONS)}"
        )
    if precision == "bf16":
        # The "bf16 changes no results" equivalence (config.py
        # dense_precision) holds only under XLA's DEFAULT matmul
        # precision, where f32 MXU inputs are already bf16-truncated.
        # A process/context default of "highest"/"float32" would make
        # the f32 path genuinely full-precision and the bf16 operand
        # storage a silent numerics change — refuse instead.
        override = getattr(jax.config, "jax_default_matmul_precision", None)
        if override is not None and str(override).upper() not in (
            "DEFAULT", "BFLOAT16", "FASTEST",
        ):
            raise ValueError(
                "dense_precision='bf16' requires XLA's DEFAULT matmul "
                f"precision; the active default is {override!r} (set via "
                "jax.default_matmul_precision), under which bf16 operand "
                "storage would change results. Use dense_precision='f32'."
            )


def _cast_for(precision: str):
    """Matmul-operand cast for the fixed-point iterations.  "bf16" is a
    VMEM-bandwidth optimization, not a numerics trade on TPU: XLA's
    DEFAULT matmul precision already truncates f32 MXU inputs to bf16
    (measured: f32-input and bf16-input dots are bit-identical on v5e,
    both ~6e-3 from the f64 truth; accumulation stays f32 either way).
    Storing the [W, BB]-sized operands half-width cuts the VMEM traffic
    feeding the MXU, measured ~10% off the fixed-point iteration.  On
    CPU (tests, interpret) f32 matmuls are exact, so "bf16" there
    emulates the TPU's input truncation.  The tail pass — suff-stats,
    token ELBO — always runs full-width off the converged gamma."""
    dt = jnp.bfloat16 if precision == "bf16" else None
    return (lambda x: x.astype(dt)) if dt else (lambda x: x)


def _vmem_estimate(bb: int, v: int, k: int, precision: str = "f32") -> int:
    est = (4 * bb * v + 2 * k * v) * 4
    if precision == "bf16":
        # bf16 copies of the ratio block, exp_et, and beta live alongside
        # their f32 originals during the fixed point.
        est += (bb * v + bb * k + k * v) * 2
    return est


def _vmem_limit(bb: int, v: int, k: int, precision: str = "f32") -> int:
    # Mosaic's real stack allocation runs ~1.6x the modeled working set
    # (measured: 56.2MB actual vs 34.9MB modeled at BB=256, V=8192, K=20);
    # 2x keeps headroom without hitting the 128MB physical VMEM.
    est = _vmem_estimate(bb, v, k, precision)
    return min(max(32 * 1024 * 1024, est * 2), 128 * 1024 * 1024)


def scoped_vmem_kib(b: int, v: int, k: int, wmajor: bool = False,
                    precision: str = "f32") -> int | None:
    """Scoped-VMEM KiB the dense kernel needs at pick_block's block size —
    for drivers to pass as the xla_tpu_scoped_vmem_limit_kib compiler
    option.  Needed because XLA drops the pallas_call's own
    CompilerParams vmem limit when the kernel is fusion-wrapped inside a
    multi-batch lax.scan (observed: a [NB>=2] stacked group compiles the
    kernel as kCustom fusion with the default 16MB scoped limit)."""
    pick = pick_block_w if wmajor else pick_block
    bb = pick(b, v, k, precision)
    if bb is None:
        return None
    return _vmem_limit(bb, padded_width(v), k, precision) // 1024


def _planned_block(knob: str, b: int, v: int, k: int,
                   precision: str) -> int | None:
    """Measured doc-block override from the plan cache
    (oni_ml_tpu/plans): a probe/bench-recorded block for this exact
    (B, V, K, precision) on this backend.  The analytic VMEM-model pick
    below stays the prior — a planned block is only a candidate, and
    the callers re-validate it against the same feasibility rules, so a
    stale or hand-edited cache entry can never produce an illegal
    grid.  Multi-host runs skip the lookup entirely: the block pick
    feeds rank-collective engine decisions, and per-host caches could
    hold different winners."""
    try:
        if jax.process_count() > 1:
            return None
        from ..plans import lookup_value

        val = lookup_value(knob, shape=f"b{b}.v{v}.k{k}.{precision}")
        return int(val) if val else None
    except Exception:
        return None


def pick_block(b: int, v: int, k: int, precision: str = "f32") -> int | None:
    """Largest power-of-two doc block (<= 256) dividing `b` whose
    estimated working set fits the VMEM ceiling — or the plan cache's
    measured block for this shape when one exists and passes the same
    feasibility checks.  None = infeasible."""
    w = padded_width(v)
    planned = _planned_block("dense_estep_block", b, v, k, precision)
    if (
        planned
        and planned <= b
        and b % planned == 0
        # BB is the sublane dimension of the [BB, V] block — the
        # analytic space only ever emits multiples of 8, and a
        # hand-edited entry must not hand Mosaic an unaligned tile.
        and planned % 8 == 0
        and _vmem_estimate(planned, w, k, precision) <= _VMEM_CEILING
    ):
        return planned
    bb = 8
    best = None
    while bb <= min(b, 256) and b % bb == 0:
        if _vmem_estimate(bb, w, k, precision) > _VMEM_CEILING:
            break
        best = bb
        bb *= 2
    return best


def pick_block_w(b: int, v: int, k: int,
                 precision: str = "f32") -> int | None:
    """Doc block for the W-major layout.  The doc axis is the LANE
    dimension of the C^T block there, so Mosaic requires it divisible by
    128 — or equal to the full batch (single-block grid).  None =
    infeasible in this layout (callers fall back to row-major)."""
    w = padded_width(v)
    planned = _planned_block("dense_estep_block_w", b, v, k, precision)
    if (
        planned
        and planned <= b
        and b % planned == 0
        and (planned % 128 == 0 or planned == b)
        and _vmem_estimate(planned, w, k, precision) <= _VMEM_CEILING
    ):
        return planned
    best = None
    bb = 128
    while bb <= min(b, 256) and b % bb == 0:
        if _vmem_estimate(bb, w, k, precision) > _VMEM_CEILING:
            break
        best = bb
        bb *= 2
    if best is None and b <= 256 and (
        _vmem_estimate(b, w, k, precision) <= _VMEM_CEILING
    ):
        best = b  # block == full array: any lane extent is legal
    return best


def padded_width(num_terms: int) -> int:
    """Vocab width the dense path uses: next multiple of the 128-lane
    tile.  The kernel contracts over the full width, so the extra
    columns must hold REAL zeros (Mosaic's tile padding is undefined
    memory) — densify() allocates them zeroed and e_step_dense pads beta
    to match."""
    return -(-num_terms // 128) * 128


def max_dense_cell(word_idx, counts) -> float:
    """Largest value any densified cell will hold: the max over
    (doc, word) of the SUMMED counts of duplicate tokens.

    This — not the max raw per-token count — is what the bf16-exactness
    gate must bound: duplicate (doc, word) tokens sum in densify(), and
    the corpus deliberately contains them (the ingest keeps duplicate
    pairs as separate tokens, and the analyst-feedback path replicates
    a row DUPFACTOR=1000 times, so a feedback doc holds the same word
    as ~1000 count-1 tokens whose CELL is ~1000 while every raw count
    is 1)."""
    w = np.asarray(word_idx)
    c = np.asarray(counts, np.float64)
    if w.size == 0:
        return 0.0
    # Sort each row by word: a word's tokens become one run, whose sum
    # is the row's running sum at the run's end less that at the end of
    # the run before it.
    order = np.argsort(w, axis=1, kind="stable")
    ws = np.take_along_axis(w, order, 1)
    upto = np.cumsum(np.take_along_axis(c, order, 1), axis=1)
    end = np.ones(w.shape, bool)
    end[:, :-1] = ws[:, 1:] != ws[:, :-1]
    rows, cols = np.nonzero(end)
    ends = upto[rows, cols]
    before = np.concatenate([[0.0], ends[:-1]])
    before[np.concatenate([[True], rows[1:] != rows[:-1]])] = 0.0
    return float((ends - before).max())


# bf16's 8 significand bits hold every integer up to here exactly.
_BF16_EXACT_MAX = 256


def corpus_dtype(cell_max: float, precision: str = "f32"):
    """Storage dtype for the densified corpus.

    bf16 when the dense path runs in bf16 operand mode AND every
    DENSIFIED CELL (per-(doc, word) summed count — see max_dense_cell;
    raw per-token counts undercount duplicates) is <= 256: bf16's 8
    significand bits represent integers exactly up to 256, so the
    f32-promoting consumers in the kernels see the exact counts —
    bit-identical results — while the corpus' HBM streaming (the
    dominant per-iteration memory traffic once the fixed point is
    matmul-bound) halves.  Anything larger — e.g. the DUPFACTOR=1000
    feedback cells — keeps f32."""
    if precision == "bf16" and cell_max <= _BF16_EXACT_MAX:
        return jnp.bfloat16
    return jnp.float32


def corpus_store_dtype(batches, precision: str = "f32"):
    """corpus_dtype for a fit's host batches, reading only what can
    change the answer.  Returns (dtype, cell_scan, scan_tokens): what
    the gate had to read ("none", "bounds" or "exact") and how many
    padded tokens its passes went over.

    Anything but bf16 operand mode stores f32 whatever the cells hold:
    no token is read.  Under bf16 the answer is EXACT — equal to
    corpus_dtype(max over batches of max_dense_cell, "bf16") — from the
    cheapest evidence that decides it: a cell is at least its largest
    token (one raw count over 256: f32) and at most its row's sum
    (every document's sum <= 256: bf16); only the rows between the two
    bounds — duplicates may or may not pile up in them, the DUPFACTOR
    feedback document is 1000 count-1 tokens of one word — pay
    max_dense_cell's sort, batch by batch, up to the first cell over
    256."""
    if precision != "bf16":
        return jnp.float32, "none", 0
    counts = [np.asarray(b.counts) for b in batches]
    read = 0
    for c in counts:
        read += c.size
        if c.size and c.max() > _BF16_EXACT_MAX:
            return jnp.float32, "bounds", read
    undecided = []
    for b, c in zip(batches, counts):
        read += c.size
        rows = np.flatnonzero(
            c.sum(axis=1, dtype=np.float64) > _BF16_EXACT_MAX)
        if rows.size:
            undecided.append((b.word_idx, c, rows))
    if not undecided:
        return jnp.bfloat16, "bounds", read
    for w, c, rows in undecided:
        read += rows.size * c.shape[1]
        if max_dense_cell(np.asarray(w)[rows], c[rows]) > _BF16_EXACT_MAX:
            return jnp.float32, "exact", read
    return jnp.bfloat16, "exact", read


def densify(word_idx, counts, num_terms: int, width: int | None = None,
            dtype=None):
    """[B, L] token lists -> [B, W] dense counts.  One scatter, run once
    per batch group and amortized over every EM iteration (padded tokens
    carry count 0, so they contribute nothing to column 0).

    W defaults to padded_width(V) — the 128-lane tile the Pallas kernel
    needs.  The XLA-level vocab-sharded dense path passes an explicit
    `width` (the model-axis-divisible padded vocab) instead: XLA has no
    lane-tile requirement, and matching the sharded beta width exactly
    keeps shard ownership aligned with the sparse plan's.

    `dtype` is the STORAGE dtype (see corpus_dtype); the scatter always
    accumulates in the counts dtype and converts once at the end, so a
    bf16 store is an exact conversion, never a bf16 accumulation."""
    if width is None:
        width = padded_width(num_terms)
    elif width < num_terms:
        raise ValueError(f"width {width} < num_terms {num_terms}")
    b = word_idx.shape[0]
    with jax.named_scope("densify"):
        dense = jnp.zeros((b, width), counts.dtype)
        dense = dense.at[jnp.arange(b)[:, None], word_idx].add(counts)
        return dense if dtype is None else dense.astype(dtype)


_BLOCK_STATICS = ("var_max_iters", "var_tol", "precision")


@functools.partial(jax.jit, static_argnames=_BLOCK_STATICS)
def _block_e_step(
    alpha, warm, beta, c, mask, gamma_in,
    *, var_max_iters: int, var_tol: float, precision: str = "f32",
):
    """One block of BB documents' E-step, as values: beta [K, V]
    exp(log_beta), c [BB, V] f32 or bf16, mask [BB, 1], gamma_in [BB, K]
    -> (gamma, T's part [K, V], docll [BB, 1], alpha_ss part [BB, 1],
    sweeps).  `_dense_kernel` is its refs in and out.

    A `jit` of its own, for its trace cache and nothing else (Mosaic
    inlines it): every `pallas_call` traces its kernel anew, 0.4 s a
    kernel on the chip's host, and a fit calls the kernel once a shape
    group; groups whose batches share a shape share this function's
    jaxpr (PERF.md, PR 37: read through a scan over slices they shared
    the scan's body).

    warm selects the fixed point's start: 0 = the reference's fresh
    init alpha + N_d/K (lda-c semantics), 1 = resume from gamma_in
    (the previous EM iteration's posterior — same fixed point, fewer
    iterations once beta stabilizes; config knob warm_start_gamma)."""
    k_topics = beta.shape[0]
    # The corpus block may arrive STORED bf16 (corpus_dtype: exact for
    # counts <= 256, halves its HBM streaming).  It is consumed via
    # f32-promoting elementwise ops — the upcast fuses per use instead
    # of materializing a second full-width copy in VMEM — so the
    # storage dtype changes no results.
    n_d = jnp.sum(c, axis=1, keepdims=True, dtype=jnp.float32)
    # Relative stop normalizer: mean_k gamma = alpha + N_d/K for every
    # iterate (gamma rows sum to K*alpha + N_d exactly), making var_tol
    # a relative tolerance — reachable in f32 (see ops/estep.py).
    inv_scale = 1.0 / (alpha + n_d / k_topics)   # [BB, 1]
    cast = _cast_for(precision)
    beta_m = cast(beta)

    def e_log_theta(gamma):
        return digamma_pos(gamma) - digamma_pos(
            jnp.sum(gamma, axis=1, keepdims=True)
        )

    def qmat(exp_et, b):
        # [BB, K] @ [K, V]; matches the sparse path's phinorm + 1e-30.
        return jax.lax.dot_general(
            exp_et, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + 1e-30

    def body(state):
        gamma, it, delta_old, _ = state
        exp_et = jnp.exp(e_log_theta(gamma))   # [BB, K]
        q = qmat(cast(exp_et), beta_m)
        ratio = c * _recip(q)
        s = jax.lax.dot_general(               # [BB, V] @ [V, K]^T contraction
            cast(ratio), beta_m, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        gamma_new = alpha + exp_et * s
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
        (c.shape[0], k_topics), jnp.float32
    )
    gamma0 = jnp.where(warm != 0, gamma_in, fresh0)
    gamma, iters, _, _ = jax.lax.while_loop(
        cond,
        body,
        (gamma0, jnp.asarray(0, jnp.int32),
         jnp.asarray(jnp.inf, jnp.float32),
         jnp.asarray(jnp.inf, jnp.float32)),
    )

    # Converged single-pass tail, all while C is still VMEM-resident:
    # suff-stats factor T plus the ELBO's per-doc terms — the token term
    # sum_v C*log(q) AND the gamma-Dirichlet terms (digamma/gammaln),
    # computed here where the doc axis rides the vector lanes instead of
    # on the XLA side's [B, K] layout (K=20 padded to 128 lanes made
    # those transcendentals ~0.4 ms of every EM iteration).  Always full
    # f32 off the converged gamma, whatever the iteration precision was.
    e_lt = e_log_theta(gamma)
    exp_et = jnp.exp(e_lt)
    q = qmat(exp_et, beta)
    ratio = (c * _recip(q)) * mask
    tok = jnp.sum(c * jnp.log(q), axis=1, keepdims=True)
    core = jnp.sum(
        (alpha - gamma) * e_lt + gammaln_pos(gamma), axis=1, keepdims=True
    ) - gammaln_pos(jnp.sum(gamma, axis=1, keepdims=True))
    docll = (core + tok) * mask
    ass = jnp.sum(e_lt, axis=1, keepdims=True) * mask
    t_part = jax.lax.dot_general(              # [K, BB] @ [BB, V]
        exp_et * mask, ratio, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return gamma, t_part, docll, ass, iters


def _dense_kernel(
    alpha_ref, warm_ref, beta_ref, c_ref, mask_ref, gamma_in_ref,
    gamma_ref, t_ref, docll_ref, ass_ref, iters_ref, **settings,
):
    """One grid step = one block of BB documents; C block, q, and ratio
    stay in VMEM for the whole fixed point (`_block_e_step`).  The T
    accumulator is a revisited output block summed over the grid."""
    gamma, t_part, docll, ass, iters = _block_e_step(
        alpha_ref[0, 0], warm_ref[0, 0], beta_ref[...], c_ref[...],
        mask_ref[...], gamma_in_ref[...], **settings)
    _store_block(gamma_ref, t_ref, docll_ref, ass_ref, iters_ref,
                 gamma, t_part, docll, ass, iters)


def _store_block(gamma_ref, t_ref, docll_ref, ass_ref, iters_ref,
                 gamma, t_part, docll, ass, iters):
    """A block's results into the kernel's output refs, either layout."""
    gamma_ref[...] = gamma
    docll_ref[...] = docll
    ass_ref[...] = ass

    @pl.when(pl.program_id(0) == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    t_ref[...] += t_part
    iters_ref[pl.program_id(0), 0] = iters


@functools.partial(jax.jit, static_argnames=_BLOCK_STATICS)
def _block_e_step_w(
    alpha, warm, beta, ct, mask, gamma_in,
    *, var_max_iters: int, var_tol: float, precision: str = "f32",
):
    """W-major variant of _block_e_step: the corpus block rides as
    C^T [W, BB] and gamma as gamma^T [K, BB] (mask [1, BB]), so the
    gamma-update contraction s = beta @ ratio^T produces a [K, BB]
    result whose small-K axis pads to the 8-sublane granularity
    (20 -> 24) instead of the 128-lane tile (20 -> 128) the row-major
    layout pays — recovering ~5x of the MXU work on that matmul.  The
    phinorm matmul contracts over K either way (inherent to LDA's
    K-mixture).  Math is identical modulo float reassociation."""
    k_topics = beta.shape[0]
    # bf16-stored corpus is consumed via f32-promoting ops — exact, no
    # materialized upcast (see _dense_kernel).
    n_d = jnp.sum(ct, axis=0, keepdims=True,   # [1, BB]
                  dtype=jnp.float32)
    # Relative stop normalizer (see _dense_kernel / ops/estep.py).
    inv_scale = 1.0 / (alpha + n_d / k_topics)  # [1, BB]
    cast = _cast_for(precision)
    beta_m = cast(beta)

    def e_log_theta_t(gamma_t):
        return digamma_pos(gamma_t) - digamma_pos(
            jnp.sum(gamma_t, axis=0, keepdims=True)
        )

    def qmat_t(exp_et_t, b):
        # [K, W] x [K, BB] contracting K -> [W, BB] phinorm.
        return jax.lax.dot_general(
            b, exp_et_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + 1e-30

    def body(state):
        gamma_t, it, delta_old, _ = state
        exp_et_t = jnp.exp(e_log_theta_t(gamma_t))   # [K, BB]
        q_t = qmat_t(cast(exp_et_t), beta_m)
        ratio_t = ct * _recip(q_t)
        s_t = jax.lax.dot_general(                   # [K, W] x [W, BB]
            beta_m, cast(ratio_t), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        gamma_new = alpha + exp_et_t * s_t
        delta = jnp.max(
            jnp.mean(jnp.abs(gamma_new - gamma_t), axis=0, keepdims=True)
            * inv_scale * mask
        )
        return gamma_new, it + 1, delta, delta_old

    def cond(state):
        # var_tol or gated stagnation — the shared rule (ops/stop.py).
        _, it, delta, prev = state
        return fp_continue(it, delta, prev, var_max_iters, var_tol)

    fresh0 = (alpha + n_d / k_topics) + jnp.zeros(
        (k_topics, ct.shape[1]), jnp.float32
    )
    gamma0 = jnp.where(warm != 0, gamma_in, fresh0)
    gamma_t, iters, _, _ = jax.lax.while_loop(
        cond,
        body,
        (gamma0, jnp.asarray(0, jnp.int32),
         jnp.asarray(jnp.inf, jnp.float32),
         jnp.asarray(jnp.inf, jnp.float32)),
    )

    # f32 tail off the converged gamma: suff-stats factor plus the full
    # per-doc ELBO terms in the lane-efficient [K, BB] layout (see
    # _dense_kernel).
    e_lt = e_log_theta_t(gamma_t)
    exp_et_t = jnp.exp(e_lt)
    q_t = qmat_t(exp_et_t, beta)
    ratio_t = (ct * _recip(q_t)) * mask
    tok = jnp.sum(ct * jnp.log(q_t), axis=0, keepdims=True)
    core = jnp.sum(
        (alpha - gamma_t) * e_lt + gammaln_pos(gamma_t),
        axis=0, keepdims=True,
    ) - gammaln_pos(jnp.sum(gamma_t, axis=0, keepdims=True))
    docll = (core + tok) * mask
    ass = jnp.sum(e_lt, axis=0, keepdims=True) * mask
    t_part = jax.lax.dot_general(                    # [K, BB] x [W, BB]
        exp_et_t * mask, ratio_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return gamma_t, t_part, docll, ass, iters




def _dense_kernel_w(
    alpha_ref, warm_ref, beta_ref, ct_ref, mask_ref, gamma_in_ref,
    gamma_ref, t_ref, docll_ref, ass_ref, iters_ref, **settings,
):
    """W-major twin of _dense_kernel, around `_block_e_step_w`."""
    gamma_t, t_part, docll, ass, iters = _block_e_step_w(
        alpha_ref[0, 0], warm_ref[0, 0], beta_ref[...], ct_ref[...],
        mask_ref[...], gamma_in_ref[...], **settings)
    _store_block(gamma_ref, t_ref, docll_ref, ass_ref, iters_ref,
                 gamma_t, t_part, docll, ass, iters)


def _corpus_call(kernel, batch_index, *, grid: int, in_specs, out_specs,
                 **kw):
    """The `pallas_call` of `kernel` over `grid` document blocks: ONE
    kernel, two ways to address its corpus.

    `batch_index` None: the corpus operand is one batch, [B, W] or
    [W, B].  Else it is a shape group's whole STACK, [NB, B, W] or
    [NB, W, B], and the traced int32 `batch_index` rides as a
    scalar-prefetch operand that the corpus BlockSpec's index map reads
    (`_spec`): the kernel DMAs its [BB, W] blocks straight out of
    the stack, where a `lax.scan` over the stack has XLA copy each batch
    out first (a read and a write of the whole batch every EM iteration:
    PERF.md, PR 37).  Every other operand keeps its per-batch shape and
    block, and the kernel body never sees the index: same blocks, same
    grid, same arithmetic, the same numbers."""
    if batch_index is None:
        return pl.pallas_call(kernel, grid=(grid,), in_specs=in_specs,
                              out_specs=out_specs, **kw)
    call = pl.pallas_call(
        lambda n_ref, *refs: kernel(*refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(grid,), in_specs=in_specs,
            out_specs=out_specs),
        **kw)
    return functools.partial(
        call, jnp.reshape(jnp.asarray(batch_index, jnp.int32), (1,)))


def _spec(block: tuple, block_axis: int | None = None, *,
          space=pltpu.VMEM, stack: bool = False):
    """BlockSpec of `block`: the grid steps it along `block_axis`, or
    (None) every step maps to the one block, which is the whole operand
    or a revisited accumulator.  `stack`: the corpus operand where it is
    a group's stack (`_corpus_call`), the same block under a squeezed
    leading axis that the prefetched batch index picks; every other
    spec's index map is handed that index too and passes it by."""
    def at(i, *index_ref):
        here = tuple(i if a == block_axis else 0 for a in range(len(block)))
        return ((index_ref[0][0],) if stack else ()) + here

    lead = (pl.Squeezed(),) if stack else ()
    return pl.BlockSpec(lead + block, at, memory_space=space)


def _check_corpus_rank(dense, batch_index) -> None:
    want = 2 if batch_index is None else 3
    if dense.ndim != want:
        raise ValueError(
            f"dense corpus of rank {dense.ndim}: one batch is rank 2, a "
            "stack of batches (rank 3) comes with its batch_index")


def dense_fixed_point_w(
    exp_beta: jnp.ndarray,       # [K, W] exp(log_beta)
    alpha: jnp.ndarray,
    dense_counts_t: jnp.ndarray,  # [W, B] (transposed corpus)
    doc_mask: jnp.ndarray,        # [B]
    var_max_iters: int,
    var_tol: float,
    block: int | None = None,
    interpret: bool = False,
    gamma_prev=None,            # [B, K] warm start (None = fresh init)
    warm=None,                  # traced scalar bool/int gating gamma_prev
    precision: str = "f32",
    batch_index=None,           # dense_counts_t is a stack [NB, W, B]
):
    """W-major twin of dense_fixed_point; same returns."""
    _check_corpus_rank(dense_counts_t, batch_index)
    k_topics, v = exp_beta.shape
    b = dense_counts_t.shape[-1]
    bb = block or pick_block_w(b, v, k_topics, precision)
    if bb is None:
        raise ValueError(
            f"no W-major-feasible doc block for B={b}, V={v}, K={k_topics} "
            "(the doc axis rides the 128-lane dimension); use the "
            "row-major dense layout"
        )
    if b % bb:
        raise ValueError(
            f"doc block {bb} does not divide batch size {b}; the grid "
            "would silently drop the remainder documents"
        )
    grid = b // bb
    kernel = functools.partial(
        _dense_kernel_w, var_max_iters=var_max_iters, var_tol=var_tol,
        precision=precision,
    )
    # Outputs/state stay f32 even when the corpus is STORED bf16
    # (corpus_dtype); the kernel upcasts the block on entry.
    dtype = jnp.promote_types(dense_counts_t.dtype, jnp.float32)
    if gamma_prev is None:
        gamma_in = jnp.zeros((k_topics, b), dtype)
        warm = jnp.asarray(0, jnp.int32)
    else:
        estep.check_warm_pair(gamma_prev, warm)
        gamma_in = jnp.asarray(gamma_prev, dtype).T
        warm = jnp.asarray(warm, jnp.int32)
    gamma_t, t, docll, ass, iters = _corpus_call(
        kernel, batch_index,
        grid=grid,
        in_specs=[
            _spec((1, 1), space=pltpu.SMEM),             # alpha
            _spec((1, 1), space=pltpu.SMEM),             # warm
            _spec((k_topics, v)),                        # beta, whole
            _spec((v, bb), 1, stack=batch_index is not None),
            _spec((1, bb), 1),                           # mask
            _spec((k_topics, bb), 1),                    # gamma in
        ],
        out_specs=[
            _spec((k_topics, bb), 1),                    # gamma
            # Revisited accumulator: every grid step maps to block (0, 0).
            _spec((k_topics, v)),
            _spec((1, bb), 1),                           # docll
            _spec((1, bb), 1),                           # alpha_ss part
            pl.BlockSpec(memory_space=pltpu.SMEM),       # sweeps a block
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_topics, b), dtype),
            jax.ShapeDtypeStruct((k_topics, v), dtype),
            jax.ShapeDtypeStruct((1, b), dtype),
            jax.ShapeDtypeStruct((1, b), dtype),
            jax.ShapeDtypeStruct((grid, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bb, v, k_topics, precision)
        ),
        interpret=interpret,
        name="dense_estep_wmajor",
    )(
        jnp.reshape(jnp.asarray(alpha, dtype), (1, 1)),
        jnp.reshape(warm, (1, 1)),
        exp_beta,
        dense_counts_t,
        jnp.reshape(doc_mask, (1, b)),
        gamma_in,
    )
    return gamma_t.T, t, docll[0], ass[0], iters.max(), iters.sum() * bb


def dense_fixed_point(
    exp_beta: jnp.ndarray,    # [K, V] exp(log_beta)
    alpha: jnp.ndarray,
    dense_counts: jnp.ndarray,  # [B, V]
    doc_mask: jnp.ndarray,      # [B]
    var_max_iters: int,
    var_tol: float,
    block: int | None = None,
    interpret: bool = False,
    gamma_prev=None,            # [B, K] warm start (None = fresh init)
    warm=None,                  # traced scalar bool/int gating gamma_prev
    precision: str = "f32",
    batch_index=None,           # dense_counts is a stack [NB, B, V]
):
    """Returns (gamma [B, K], T [K, V], docll [B], alpha_ss_part [B],
    iters scalar, doc_sweeps scalar) — docll is the full per-doc ELBO
    minus the alpha-prior constant (token term + gamma-Dirichlet terms,
    masked), alpha_ss_part is the per-doc sum_k E[log theta] (masked),
    iters the most sweeps any doc block ran and doc_sweeps the sum over
    blocks of a block's sweeps x its rows (EStepResult.doc_sweeps).

    With `batch_index` (a traced int32 scalar) `dense_counts` is a shape
    group's whole stack and the kernel reads batch `batch_index` of it in
    place (`_corpus_call`); everything else is that one batch's."""
    _check_corpus_rank(dense_counts, batch_index)
    k_topics, v = exp_beta.shape
    b = dense_counts.shape[-2]
    bb = block or pick_block(b, v, k_topics, precision)
    if bb is None:
        raise ValueError(
            f"no VMEM-feasible doc block for B={b}, V={v}, K={k_topics}"
        )
    if b % bb:
        raise ValueError(
            f"doc block {bb} does not divide batch size {b}; the grid "
            "would silently drop the remainder documents"
        )
    grid = b // bb
    kernel = functools.partial(
        _dense_kernel, var_max_iters=var_max_iters, var_tol=var_tol,
        precision=precision,
    )
    # Outputs/state stay f32 even when the corpus is STORED bf16
    # (corpus_dtype); the kernel upcasts the block on entry.
    dtype = jnp.promote_types(dense_counts.dtype, jnp.float32)
    if gamma_prev is None:
        gamma_in = jnp.zeros((b, k_topics), dtype)
        warm = jnp.asarray(0, jnp.int32)
    else:
        estep.check_warm_pair(gamma_prev, warm)
        gamma_in = jnp.asarray(gamma_prev, dtype)
        warm = jnp.asarray(warm, jnp.int32)
    gamma, t, docll, ass, iters = _corpus_call(
        kernel, batch_index,
        grid=grid,
        in_specs=[
            _spec((1, 1), space=pltpu.SMEM),             # alpha
            _spec((1, 1), space=pltpu.SMEM),             # warm
            _spec((k_topics, v)),                        # beta, whole
            _spec((bb, v), 0, stack=batch_index is not None),
            _spec((bb, 1), 0),                           # mask
            _spec((bb, k_topics), 0),                    # gamma in
        ],
        out_specs=[
            _spec((bb, k_topics), 0),                    # gamma
            # Revisited accumulator: every grid step maps to block (0, 0).
            _spec((k_topics, v)),
            _spec((bb, 1), 0),                           # docll
            _spec((bb, 1), 0),                           # alpha_ss part
            pl.BlockSpec(memory_space=pltpu.SMEM),       # sweeps a block
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k_topics), dtype),
            jax.ShapeDtypeStruct((k_topics, v), dtype),
            jax.ShapeDtypeStruct((b, 1), dtype),
            jax.ShapeDtypeStruct((b, 1), dtype),
            jax.ShapeDtypeStruct((grid, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(bb, v, k_topics, precision)
        ),
        interpret=interpret,
        name="dense_estep_rowmajor",
    )(
        jnp.reshape(jnp.asarray(alpha, dtype), (1, 1)),
        jnp.reshape(warm, (1, 1)),
        exp_beta,
        dense_counts,
        jnp.reshape(doc_mask, (b, 1)),
        gamma_in,
    )
    return gamma, t, docll[:, 0], ass[:, 0], iters.max(), iters.sum() * bb


def e_step_dense(
    log_beta: jnp.ndarray,      # [K, V]
    alpha: jnp.ndarray,
    dense_counts: jnp.ndarray,  # [B, padded_width(V)] from densify()
    doc_mask: jnp.ndarray,      # [B]
    var_max_iters: int,
    var_tol: float,
    block: int | None = None,
    interpret: bool = False,
    wmajor: bool = False,       # dense_counts is [W, B] (densify .T)
    gamma_prev=None,            # [B, K] warm start (None = fresh init)
    warm=None,                  # traced scalar gating gamma_prev
    precision: str = "f32",     # "bf16": half-precision MXU iterations
    batch_index=None,           # traced int32: dense_counts is a STACK
) -> estep.EStepResult:
    """estep.e_step semantics over a pre-densified batch: `dense_counts`
    itself, or with `batch_index` batch `batch_index` of the stack
    `dense_counts` ([NB, B, W], W-major [NB, W, B]), read in place and to
    the same numbers as `dense_counts[batch_index]` (`_corpus_call`).

    The padded columns are inert: C is zero there (densify allocates
    them zeroed), beta is zero-padded here, so q = 1e-30 and ratio = 0
    in the pad — every contraction over the padded width is exact.
    """
    _check_precision(precision)
    v = log_beta.shape[1]
    w = dense_counts.shape[-2] if wmajor else dense_counts.shape[-1]
    exp_beta = jnp.exp(log_beta)
    if w != v:
        exp_beta = jnp.pad(exp_beta, ((0, 0), (0, w - v)))
    fp = dense_fixed_point_w if wmajor else dense_fixed_point
    gamma, t, docll, ass, iters, sweeps = fp(
        exp_beta, alpha, dense_counts, doc_mask, var_max_iters, var_tol,
        block=block, interpret=interpret, gamma_prev=gamma_prev, warm=warm,
        precision=precision, batch_index=batch_index,
    )
    suff = (exp_beta * t)[:, :v].T             # [V, K]
    # The kernel emits the per-doc ELBO terms (token + gamma-Dirichlet)
    # and sum_k E[log theta]; only the alpha-prior constant — identical
    # for every real doc — remains for the host-side sum.
    k_topics = log_beta.shape[0]
    alpha_const = gammaln(k_topics * alpha) - k_topics * gammaln(alpha)
    likelihood = docll.sum() + doc_mask.sum() * alpha_const
    alpha_ss = ass.sum()
    return estep.EStepResult(gamma, suff, alpha_ss, likelihood, iters, sweeps)


def plan(b: int, v: int, k: int, precision: str = "f32",
         wmajor: bool = True):
    """One-stop dense-path decision for single-batch drivers (the
    online trainer and the bench; the batch trainer plans per shard
    over multiple batch shapes and keeps its own logic): returns
    (feasible, use_wmajor, compiler_options).

    feasible — available(): a VMEM-feasible doc block exists on this
    backend (TPU only); use_wmajor — the W-major layout's 128-lane
    doc-block constraint holds (backend-independent, so forced-dense
    interpret runs keep W-major coverage; callers store the corpus
    transposed when set); compiler_options — the
    xla_tpu_scoped_vmem_limit_kib dict drivers must pass to jax.jit,
    or None (TPU only; see scoped_vmem_kib).

    Also validates `precision` eagerly (including the bf16
    matmul-precision-override refusal) so drivers fail at plan time,
    not deep inside a trace."""
    _check_precision(precision)
    feasible = available(b, v, k, precision)
    use_wmajor = wmajor and pick_block_w(b, v, k, precision) is not None
    options = None
    if feasible:
        kib = scoped_vmem_kib(b, v, k, wmajor=use_wmajor,
                              precision=precision)
        if kib:
            options = {"xla_tpu_scoped_vmem_limit_kib": str(kib)}
    return feasible, use_wmajor, options


def available(b: int, v: int, k: int, precision: str = "f32") -> bool:
    """True when the shapes admit a VMEM-feasible block on TPU (at the
    precision the caller will actually run — bf16 mode needs more VMEM
    for its half-width operand copies)."""
    return (
        jax.default_backend() == "tpu"
        and pick_block(b, v, k, precision) is not None
    )
