"""Sharded E/M steps: shard_map wrappers over ops/estep building blocks.

Two execution plans, both SPMD over the (data, model) mesh:

1. **Data-parallel** (`make_data_parallel_e_step`) — the direct analogue
   of the reference's 20-rank MPI document sharding (README.md:121):
   batches shard over `data`, beta replicates, suff-stats/likelihood
   `psum` over ICI.  This is the default whenever beta fits per device.

2. **Vocab-sharded** (`make_vocab_sharded_fns`) — for huge-V corpora
   (BASELINE.json config 4: high-cardinality DNS vocab).  beta [K, V] and
   suff-stats [V, K] shard their vocabulary axis over `model`; each shard
   gathers the beta slab for the tokens whose words it owns and a
   `psum` over `model` assembles the full [B, L, K] slab (one collective
   per batch — the slab, not beta, so HBM never holds another full copy).
   The fixed point then runs identically on every model shard; suff-stats
   scatter only into the locally-owned vocab slice.  The M-step
   renormalizes with a `psum` of per-topic totals over `model`.

Both plans compose: a (8, 4) mesh runs 8-way document parallelism with
4-way vocabulary sharding.

Scope since the distributed-EM restructure: these shard_map plans are
HOST-LOCAL — the mesh spans one process's devices
(`parallel.local_mesh`), and their psums ride that host's ICI only.
Cross-PROCESS reduction is no longer expressed here at all: one
global-mesh SPMD program spanning processes is unexecutable on the CPU
runtime and forced the sparse engine dense, so the process dimension
moved to the explicit sufficient-statistics allreduce
(`parallel/allreduce.py`) over corpus-derived document shards
(`parallel/shard_plan.py`).  A multi-host run composes the two layers:
shard_map within the host, collective across hosts.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import estep
from ..ops.stop import fp_continue
from .mesh import DATA_AXIS, MODEL_AXIS


def _fresh_warm_fill(log_beta, word_idx):
    """Default (gamma_prev, warm) for fresh-start calls: zeros that are
    never read back (warm=0).  One definition so the sharded plans
    cannot drift on the fresh-start convention."""
    return (
        jnp.zeros((word_idx.shape[0], log_beta.shape[0]), log_beta.dtype),
        jnp.asarray(0, jnp.int32),
    )


def make_data_parallel_e_step(mesh: Mesh):
    """e_step-compatible callable: inputs batch-sharded over `data`,
    outputs gamma sharded / reductions replicated."""

    def local(log_beta, alpha, word_idx, counts, doc_mask, gamma_prev,
              warm, var_max_iters, var_tol):
        res = estep.e_step(
            log_beta, alpha, word_idx, counts, doc_mask, var_max_iters,
            var_tol, gamma_prev=gamma_prev, warm=warm,
        )
        return estep.EStepResult(
            gamma=res.gamma,
            suff_stats=jax.lax.psum(res.suff_stats, DATA_AXIS),
            alpha_ss=jax.lax.psum(res.alpha_ss, DATA_AXIS),
            likelihood=jax.lax.psum(res.likelihood, DATA_AXIS),
            vi_iters=jax.lax.pmax(res.vi_iters, DATA_AXIS),
            doc_sweeps=jax.lax.psum(res.doc_sweeps, DATA_AXIS),
        )

    def wrapped(log_beta, alpha, word_idx, counts, doc_mask,
                var_max_iters, var_tol, gamma_prev=None, warm=None):
        if gamma_prev is None:
            gamma_prev, warm = _fresh_warm_fill(log_beta, word_idx)
        fn = shard_map(
            partial(local, var_max_iters=var_max_iters, var_tol=var_tol),
            mesh=mesh,
            in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P()),
            out_specs=estep.EStepResult(
                gamma=P(DATA_AXIS),
                suff_stats=P(),
                alpha_ss=P(),
                likelihood=P(),
                vi_iters=P(),
                doc_sweeps=P(),
            ),
        )
        return fn(log_beta, alpha, word_idx, counts, doc_mask, gamma_prev,
                  warm)

    wrapped._oni_data_parallel = True  # lets the trainer's dense-mode
    wrapped._oni_warm_capable = True   # check recognize its own wrapper
    return wrapped


def make_data_parallel_dense_e_step(mesh: Mesh, wmajor: bool = False,
                                    precision: str = "f32"):
    """Dense-corpus E-step (ops/dense_estep.py) over batch-sharded dense
    counts: each data shard runs the MXU kernel on its local documents,
    suff-stats/likelihood psum over ICI — the dense analogue of
    make_data_parallel_e_step, so multi-chip runs keep the flagship
    kernel instead of falling back to the sparse path.

    `dense` is the full densified batch ([B, W] row-major or [W, B]
    W-major); the local batch is B / data_size, so dense feasibility
    (pick_block / pick_block_w) must be checked against the PER-SHARD
    batch by the caller.  gamma_prev/warm thread the warm-start state
    exactly as in the single-device path."""
    from ..ops import dense_estep

    batch_axis = 1 if wmajor else 0

    def local(log_beta, alpha, dense, doc_mask, gamma_prev, warm,
              var_max_iters, var_tol, interpret):
        res = dense_estep.e_step_dense(
            log_beta, alpha, dense, doc_mask,
            var_max_iters=var_max_iters, var_tol=var_tol,
            interpret=interpret, wmajor=wmajor,
            gamma_prev=gamma_prev, warm=warm, precision=precision,
        )
        return estep.EStepResult(
            gamma=res.gamma,
            suff_stats=jax.lax.psum(res.suff_stats, DATA_AXIS),
            alpha_ss=jax.lax.psum(res.alpha_ss, DATA_AXIS),
            likelihood=jax.lax.psum(res.likelihood, DATA_AXIS),
            vi_iters=jax.lax.pmax(res.vi_iters, DATA_AXIS),
            doc_sweeps=jax.lax.psum(res.doc_sweeps, DATA_AXIS),
        )

    dense_spec = (
        P(None, DATA_AXIS) if wmajor else P(DATA_AXIS, None)
    )

    def wrapped(log_beta, alpha, dense, doc_mask, gamma_prev, warm,
                var_max_iters, var_tol, interpret=False):
        if dense.shape[batch_axis] % mesh.shape[DATA_AXIS]:
            raise ValueError(
                f"batch {dense.shape[batch_axis]} not divisible by data "
                f"axis {mesh.shape[DATA_AXIS]}"
            )
        fn = shard_map(
            partial(local, var_max_iters=var_max_iters, var_tol=var_tol,
                    interpret=interpret),
            mesh=mesh,
            in_specs=(P(), P(), dense_spec, P(DATA_AXIS), P(DATA_AXIS),
                      P()),
            out_specs=estep.EStepResult(
                gamma=P(DATA_AXIS),
                suff_stats=P(),
                alpha_ss=P(),
                likelihood=P(),
                vi_iters=P(),
                doc_sweeps=P(),
            ),
            # pallas_call's out_shape carries no varying-mesh-axes info,
            # so shard_map's vma check cannot see through it.
            check_vma=False,
        )
        return fn(log_beta, alpha, dense, doc_mask, gamma_prev, warm)

    return wrapped


def make_vocab_sharded_dense_e_step(mesh: Mesh, precision: str = "f32"):
    """Dense-corpus E-step with the VOCABULARY sharded over `model` and
    documents over `data` — BASELINE.json config 4 (high-cardinality DNS
    vocab, dns_pre_lda.scala:320-326) at MXU density.

    Each device owns C_l [B/d, W/m] (its doc rows x its vocab columns)
    and beta_l [K, W/m]; the densified corpus never exists whole on any
    chip, so huge-V corpora that blow the single-chip HBM budget shard
    down to fit.  Per fixed-point iteration the only collective is the
    gamma-update contraction s = psum_model(ratio_l @ beta_l^T) — a
    [B/d, K] array (K=20: a few KB), riding ICI — because q[b, w] and
    ratio[b, w] are local to the vocab shard that owns column w, while
    gamma/exp_et are replicated across the model axis (every shard in a
    model group computes them identically from the psum'd s, so no
    broadcast is ever materialized).  This mirrors the sparse
    vocab-sharded plan's slab psum (local_e_step above) but moves the
    arithmetic from gather/scatter to XLA matmuls on the MXU; at config-4
    scale the corpus streams from HBM each iteration regardless, so an
    XLA-level loop costs nothing over a Pallas kernel and composes with
    sharding for free.

    The batch trainer selects this plan automatically
    (models/lda.py _use_dense_vocab_sharded) when the trainer is
    vocab-sharded, dense_em allows it, and the per-device corpus slices
    fit the HBM budget; the per-EM-iteration semantics are pinned to the
    unwrapped dense kernel by
    tests/test_sharded.py::test_vocab_sharded_DENSE_e_step_parity and
    end-to-end by test_full_training_parity_vocab_sharded_dense.

    Semantics match ops/dense_estep.e_step_dense (same fresh init, same
    q + 1e-30 guard, same masked-delta stop, full-f32 tail with in-loop
    optional bf16 operand storage, warm start via gamma_prev/warm).
    Requirements: dense width == log_beta width, both divisible by the
    model-axis size; batch divisible by the data-axis size.  Pad the
    vocab with pad_vocab + LOG_ZERO beta columns — padded C columns are
    zero so every contraction over them is exact.
    """
    from jax.scipy.special import digamma, gammaln

    from ..ops import dense_estep

    d_sz, m_sz = mesh.shape[DATA_AXIS], mesh.shape[MODEL_AXIS]
    dense_estep._check_precision(precision)
    cast = dense_estep._cast_for(precision)

    def local(log_beta_l, alpha, c_l, doc_mask, gamma_prev, warm,
              var_max_iters, var_tol):
        k = log_beta_l.shape[0]
        beta_l = jnp.exp(log_beta_l)               # [K, W_l]
        beta_m = cast(beta_l)
        mask_col = doc_mask[:, None]
        # f32 accumulation: the corpus may be STORED bf16
        # (dense_estep.corpus_dtype) and is consumed via f32-promoting
        # ops throughout.
        n_d = jax.lax.psum(
            jnp.sum(c_l, axis=1, dtype=jnp.float32), MODEL_AXIS
        )                                          # [B_l]
        # Relative stop normalizer, identical across the model group
        # (n_d is psum'd), so the stop stays collective-consistent.
        inv_scale = 1.0 / (alpha + n_d / k)        # [B_l]

        def e_log_theta(gamma):
            return digamma(gamma) - digamma(gamma.sum(1, keepdims=True))

        def qmat(exp_et, b):
            return jax.lax.dot_general(
                exp_et, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + 1e-30

        def body(state):
            gamma, it, delta_old, _ = state
            exp_et = jnp.exp(e_log_theta(gamma))   # [B_l, K] (replicated
            q = qmat(cast(exp_et), beta_m)         #  across model)
            ratio = c_l / q
            s = jax.lax.psum(                      # [B_l, K]: THE collective
                jax.lax.dot_general(
                    cast(ratio), beta_m, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ),
                MODEL_AXIS,
            )
            gamma_new = alpha + exp_et * s
            # gamma is bit-identical across the model group, so every
            # shard reaches the same stop decision — the psum inside the
            # loop stays collective-consistent.
            delta = jnp.max(
                jnp.mean(jnp.abs(gamma_new - gamma), axis=1)
                * inv_scale * doc_mask
            )
            return gamma_new, it + 1, delta, delta_old

        def cond(state):
            # var_tol or gated stagnation — the shared rule
            # (ops/stop.py), identical across the model group.
            _, it, delta, prev = state
            return fp_continue(it, delta, prev, var_max_iters, var_tol)

        fresh0 = alpha + (n_d / k)[:, None] + jnp.zeros(
            (c_l.shape[0], k), jnp.float32
        )
        gamma0 = jnp.where(warm != 0, gamma_prev, fresh0)
        # delta varies over `data` (each data row stops independently);
        # the initial scalar must carry the same varying-axes type.
        delta0 = jax.lax.pcast(
            jnp.asarray(jnp.inf, jnp.float32), DATA_AXIS, to="varying"
        )
        gamma, iters, _, _ = jax.lax.while_loop(
            cond, body,
            (gamma0, jnp.asarray(0, jnp.int32), delta0, delta0),
        )

        # Full-f32 tail off the converged gamma (dense-kernel semantics).
        e_lt = e_log_theta(gamma)
        exp_et = jnp.exp(e_lt)
        q = qmat(exp_et, beta_l)
        ratio = (c_l / q) * mask_col
        t_l = jax.lax.dot_general(                 # [K, W_l]
            exp_et * mask_col, ratio, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        suff_l = (beta_l * t_l).T                  # [W_l, K]
        # Token ELBO term spans the sharded vocab axis: psum over model.
        # The gamma-Dirichlet terms and alpha_ss are per-doc quantities
        # computed identically on every model shard: psum over data ONLY
        # (a model psum would count them m times).
        tok = jax.lax.psum(
            jnp.sum(c_l * jnp.log(q) * mask_col), MODEL_AXIS
        )
        core = jnp.sum(
            (
                jnp.sum((alpha - gamma) * e_lt + gammaln(gamma), axis=1)
                - gammaln(gamma.sum(axis=1))
            )
            * doc_mask
        )
        alpha_const = gammaln(k * alpha) - k * gammaln(alpha)
        ll = core + tok + doc_mask.sum() * alpha_const
        ass = jnp.sum(e_lt.sum(axis=1) * doc_mask)
        return estep.EStepResult(
            gamma=gamma,
            suff_stats=jax.lax.psum(suff_l, DATA_AXIS),
            alpha_ss=jax.lax.psum(ass, DATA_AXIS),
            likelihood=jax.lax.psum(ll, DATA_AXIS),
            vi_iters=jax.lax.pmax(iters, DATA_AXIS),
            doc_sweeps=jax.lax.psum(
                iters * doc_mask.shape[0], DATA_AXIS),
        )

    def wrapped(log_beta, alpha, dense, doc_mask, gamma_prev, warm,
                var_max_iters, var_tol):
        b, w = dense.shape
        if b % d_sz:
            raise ValueError(
                f"batch {b} not divisible by data axis {d_sz}"
            )
        if w % m_sz:
            raise ValueError(
                f"dense width {w} not divisible by model axis {m_sz} "
                "(pad with parallel.pad_vocab)"
            )
        if log_beta.shape[1] != w:
            raise ValueError(
                f"log_beta width {log_beta.shape[1]} != dense width {w} "
                "(pad log_beta with LOG_ZERO columns to match)"
            )
        fn = shard_map(
            partial(local, var_max_iters=var_max_iters, var_tol=var_tol),
            mesh=mesh,
            in_specs=(P(None, MODEL_AXIS), P(), P(DATA_AXIS, MODEL_AXIS),
                      P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=estep.EStepResult(
                gamma=P(DATA_AXIS),
                suff_stats=P(MODEL_AXIS, None),
                alpha_ss=P(),
                likelihood=P(),
                vi_iters=P(),
                doc_sweeps=P(),
            ),
        )
        return fn(log_beta, alpha, dense, doc_mask, gamma_prev, warm)

    return wrapped


def make_vocab_sharded_fns(mesh: Mesh):
    """Returns (e_step_fn, m_step_fn) with beta/suff-stats vocab-sharded
    over `model` and batches sharded over `data`.

    Global shapes stay [K, V] / [V, K]; shard_map sees per-device slices
    [K, V/m] / [V/m, K].  V must be divisible by the model-axis size
    (pad the vocabulary — padded words never appear in word_idx, so their
    suff-stats stay zero and m_step floors them to LOG_ZERO).
    """
    m = mesh.shape[MODEL_AXIS]

    def local_e_step(log_beta_l, alpha, word_idx, counts, doc_mask,
                     gamma_prev, warm, var_max_iters, var_tol):
        K, v_local = log_beta_l.shape
        shard = jax.lax.axis_index(MODEL_AXIS)
        offset = shard * v_local
        # Gather only locally-owned words, zero elsewhere; psum over the
        # model axis assembles the full [B, L, K] slab.
        local_idx = word_idx - offset
        owned = (local_idx >= 0) & (local_idx < v_local)
        safe_idx = jnp.clip(local_idx, 0, v_local - 1)
        slab_l = estep.gather_beta(log_beta_l, safe_idx)   # [B, L, K]
        slab_l = jnp.where(owned[..., None], slab_l, 0.0)
        beta_bt = jax.lax.psum(slab_l, MODEL_AXIS)

        gamma, iters = estep.fixed_point(
            beta_bt, alpha, counts, doc_mask, var_max_iters, var_tol,
            gamma_prev=gamma_prev, warm=warm,
        )
        phi_c, phinorm = estep.phi_weighted(beta_bt, gamma, counts, doc_mask)
        # Scatter only into the owned vocab slice.
        phi_c = jnp.where(owned[..., None], phi_c, 0.0)
        ss_l = estep.suff_stats(phi_c, safe_idx, v_local)  # [V/m, K]
        likelihood, alpha_ss = estep.batch_likelihood(
            gamma, phinorm, counts, alpha, doc_mask
        )
        return estep.EStepResult(
            gamma=gamma,
            suff_stats=jax.lax.psum(ss_l, DATA_AXIS),
            alpha_ss=jax.lax.psum(alpha_ss, DATA_AXIS),
            likelihood=jax.lax.psum(likelihood, DATA_AXIS),
            vi_iters=jax.lax.pmax(iters, DATA_AXIS),
            doc_sweeps=jax.lax.psum(
                iters * doc_mask.shape[0], DATA_AXIS),
        )

    def e_step_fn(log_beta, alpha, word_idx, counts, doc_mask,
                  var_max_iters, var_tol, gamma_prev=None, warm=None):
        if log_beta.shape[1] % m:
            raise ValueError(
                f"vocab size {log_beta.shape[1]} not divisible by model axis {m}"
            )
        if gamma_prev is None:
            gamma_prev, warm = _fresh_warm_fill(log_beta, word_idx)
        fn = shard_map(
            partial(local_e_step, var_max_iters=var_max_iters, var_tol=var_tol),
            mesh=mesh,
            in_specs=(P(None, MODEL_AXIS), P(), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=estep.EStepResult(
                gamma=P(DATA_AXIS),
                suff_stats=P(MODEL_AXIS, None),
                alpha_ss=P(),
                likelihood=P(),
                vi_iters=P(),
                doc_sweeps=P(),
            ),
        )
        return fn(log_beta, alpha, word_idx, counts, doc_mask, gamma_prev,
                  warm)

    def local_m_step(ss_l):
        # ss_l: [V/m, K].  Per-topic totals need the full vocab, so psum
        # the local sums over the model axis and hand the dense m_step
        # the global normalizer.
        total = jax.lax.psum(ss_l.T.sum(-1, keepdims=True), MODEL_AXIS)
        return estep.m_step(ss_l, topic_total=total)

    def m_step_fn(suff):
        fn = shard_map(
            local_m_step,
            mesh=mesh,
            in_specs=(P(MODEL_AXIS, None),),
            out_specs=P(None, MODEL_AXIS),
        )
        return fn(suff)

    # Lets the trainer's dense-mode check recognize this package's own
    # vocab-sharded plan (a user's custom e_step_fn must never be
    # silently bypassed by the dense path).
    e_step_fn._oni_vocab_sharded = True
    e_step_fn._oni_warm_capable = True
    m_step_fn._oni_vocab_sharded = True
    return e_step_fn, m_step_fn


def pad_vocab(v: int, model_size: int) -> int:
    """Smallest padded vocab size divisible by the model axis."""
    return -(-v // model_size) * model_size


def make_sharded_score_fn(mesh: Mesh):
    """Data-parallel event SCORING over the same (data, model) mesh the
    training side holds: the event axis (int32 model-row index arrays)
    shards over `data`, theta/p replicate, and each device runs the
    two-gather dot on its own slice — the scoring analogue of the
    reference's 20-rank document split, with no collective at all (the
    per-event dot is embarrassingly parallel).

    Returns a jitted (theta [D+1, K], p [V+1, K], ip_idx [N], word_idx
    [N]) -> scores [N] with the output sharded over `data`; the scoring
    pipeline (scoring/pipeline.py) drives it chunk by chunk for
    multi-device grants and composes on-device threshold compaction on
    the sharded scores.  N must divide by the data-axis size (the
    pipeline's chunker guarantees it).  Parity with the single-device
    scorer is pinned by tests/test_scoring_pipeline.py and executed in
    the driver's dryrun_multichip — which is why the per-shard body is
    the scoring pipeline's own kernel, not a local copy."""
    from ..scoring.pipeline import score_dot_rows

    return jax.jit(shard_map(
        score_dot_rows,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
    ))
