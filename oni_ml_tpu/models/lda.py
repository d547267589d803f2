"""Variational-EM LDA trainer — the in-tree replacement for the
reference's MPI `oni-lda-c` engine (SURVEY.md §2.8, ml_ops.sh:80).

Reference contract reproduced here:
- input: LDA-C corpus (`model.dat`), K topics, initial symmetric alpha,
  `random` topic initialization;
- outputs: `final.beta` (K x V log p(w|z)), `final.gamma` (D x K
  unnormalized doc-topic Dirichlets), `final.other`, `likelihood.dat`
  (one "<likelihood>\\t<convergence>" line per EM iteration, README.md:119);
- EM loop: per-doc variational fixed point (E) -> MLE beta + Newton alpha
  (M) until |Δℓ/ℓ| < em_tol.

TPU-native design: documents ride padded length-bucketed batches
(io/corpus.py); each (B, L) shape compiles once and the EM loop replays
compiled programs.  Sufficient statistics accumulate on device in [V, K];
the distributed variant (oni_ml_tpu/parallel) shards batches across the
mesh's `data` axis and `psum`s the suff stats over ICI where the reference
did an `MPI_Reduce` across 20 ranks.
"""

from __future__ import annotations

import os
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma, polygamma

from ..config import LDAConfig
from ..io import Batch, Corpus, formats, make_batches
from ..ops import estep
from ..telemetry.spans import current_recorder, maybe_span, now_ns
from . import fused


# ---------------------------------------------------------------------------
# Newton update for the symmetric Dirichlet alpha (lda-c opt_alpha)
# ---------------------------------------------------------------------------


def _alpha_objective_grads(log_a: jnp.ndarray, ss: jnp.ndarray, d: int, k: int):
    a = jnp.exp(log_a)
    df = d * k * (digamma(k * a) - digamma(a)) + ss
    d2f = d * k * k * polygamma(1, k * a) - d * k * polygamma(1, a)
    return a, df, d2f


# static_argnames spelled explicitly for max_iters: every caller passes
# it by KEYWORD, and argnums-only treatment of a keyword arg leans on
# JAX's signature inference (argnum -> name resolution), which is
# version-dependent behavior — a JAX where it no longer applies would
# trace max_iters as dynamic and fail on the Python `if max_iters <= 16`
# below.  d/k stay positional at every call site, so argnums covers them.
@partial(jax.jit, static_argnums=(2, 3), static_argnames=("max_iters",))
def update_alpha(alpha_ss: jnp.ndarray, alpha_init: jnp.ndarray, d: int, k: int,
                 max_iters: int = 100):
    """Maximize L(a) = D(lgam(Ka) - K lgam(a)) + a * ss over the symmetric
    Dirichlet parameter with Newton iterations in log space.

    This is the standard lda-c `opt_alpha` scheme: iterate
    log a <- log a - df / (d2f * a + df) from the current alpha, which is
    Newton's method on the reparameterized objective and keeps a > 0.

    `max_iters` (lda-c's MAX_ALPHA_ITER=100 by default) bounds the
    scalar Newton while_loop — the worst shape for a TPU (sequenced
    scalar digamma/trigamma per trip).  Mid-EM the warm start from the
    previous alpha converges in a handful of trips, so a small cap
    (LDAConfig.alpha_max_iters; tools/tpu_probes.py's alpha_ab probe
    measures the cost) trades nothing measurable in practice; the
    default preserves lda-c semantics exactly.

    When max_iters <= 16 the loop is UNROLLED with a convergence mask
    instead of lowered as lax.while_loop: the r05 alpha_ab probe put
    the estimate at ~0.5 ms of the ~0.94 ms device floor per EM
    iteration, and a dynamic-trip scalar while_loop pays per-trip
    loop machinery that an unrolled scalar chain (one fused kernel)
    does not.  The mask replicates the while_loop exit exactly —
    trips after |df| <= 1e-5 leave the state untouched — so the two
    lowerings compute the same value (pinned in tests/test_lda.py).

    The device work carries the scope `alpha`."""
    with jax.named_scope("alpha"):
        ss = alpha_ss

        def body(state):
            log_a, _, it = state
            a, df, d2f = _alpha_objective_grads(log_a, ss, d, k)
            log_a_new = log_a - df / (d2f * a + df)
            return log_a_new, jnp.abs(df), it + 1

        def cond(state):
            log_a, df_abs, it = state
            return jnp.logical_and(it < max_iters, df_abs > 1e-5)

        log_a0 = jnp.log(alpha_init)
        if max_iters <= 16:
            log_a = log_a0
            df_abs = jnp.asarray(jnp.inf, log_a0.dtype)
            for _ in range(max_iters):
                a_it, df, d2f = _alpha_objective_grads(log_a, ss, d, k)
                step = log_a - df / (d2f * a_it + df)
                active = df_abs > 1e-5
                log_a = jnp.where(active, step, log_a)
                df_abs = jnp.where(active, jnp.abs(df), df_abs)
        else:
            log_a, _, _ = jax.lax.while_loop(
                cond, body,
                (log_a0, jnp.asarray(jnp.inf, log_a0.dtype),
                 jnp.asarray(0, jnp.int32)),
            )
        a = jnp.exp(log_a)
        # Guard divergence (lda-c restarts with alpha*10; we fall back to the
        # previous value, which keeps EM monotone-safe).
        bad = jnp.logical_or(jnp.isnan(a), jnp.logical_or(a <= 0, jnp.isinf(a)))
        return jnp.where(bad, alpha_init, a)


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


@dataclass
class LDAResult:
    log_beta: np.ndarray       # [K, V]
    gamma: np.ndarray          # [D, K]
    alpha: float
    likelihoods: list = field(default_factory=list)  # [(ll, conv)] per EM iter
    em_iters: int = 0
    # Dispatch-knob resolution this fit ran under (plans.resolve):
    # {knob: {"value", "source": "config"|"plan"|"default"}} — surfaced
    # in the runner's lda stage record.
    plan: dict = field(default_factory=dict)
    # Document-sweeps the E-step ran over the whole fit (the sum over EM
    # iterations and batches of EStepResult.doc_sweeps: padded rows
    # count, so it lies between padded rows x em_iters and that x
    # var_max_iters; distributed: this rank's shards), and the most
    # sweeps any batch ran in one E-step.
    doc_sweeps: int = 0
    vi_max: int = 0

    def save(
        self,
        directory: str,
        num_terms: int | None = None,
        include_likelihood: bool = True,
    ) -> dict:
        """Write final.beta / final.gamma / final.other (and, unless the
        trainer already streamed it, likelihood.dat) with the reference
        formats (README.md:116-119).  Every file is complete and closed
        when this returns.  Returns what the `fit.save` span counts: the
        bytes of each file, the `rows` and `values` of the two matrices
        together, and which `writer` wrote them: `native` or `python`
        (io/formats._write_matrix)."""
        k, v = self.log_beta.shape
        paths = {name: os.path.join(directory, "final." + name)
                 for name in ("beta", "gamma", "other")}
        formats.write_beta(paths["beta"], self.log_beta)
        formats.write_gamma(paths["gamma"], self.gamma)
        formats.write_other(paths["other"], k, num_terms or v, self.alpha)
        if include_likelihood:
            with open(os.path.join(directory, "likelihood.dat"), "w") as f:
                for ll, conv in self.likelihoods:
                    formats.append_likelihood(f, ll, conv)
        counts = {f"{name}_bytes": os.path.getsize(path)
                  for name, path in paths.items()}
        counts["rows"] = k + len(self.gamma)
        counts["values"] = int(self.log_beta.size + np.size(self.gamma))
        counts["writer"] = formats.matrix_writer
        return counts


def to_host(x, mesh=None) -> np.ndarray:
    """Device->host as float64.  Arrays sharded over a multi-host mesh are
    not fully addressable from any one process, so gather first."""
    if mesh is not None and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x, dtype=np.float64)


def _is_coordinator() -> bool:
    """True on the single process that owns shared-filesystem writes."""
    return jax.process_index() == 0


def save_checkpoint(
    path: str,
    log_beta: np.ndarray,
    alpha: float,
    em_iter: int,
    likelihoods: list[tuple[float, float]],
) -> None:
    """Atomic in-training checkpoint: (beta, alpha, EM iteration, likelihood
    history).  The reference has no in-training resume at all — a crashed
    20-rank MPI run restarts from scratch (SURVEY §5.3-5.4).

    Call only from the coordinator process in multi-host runs (the
    trainers gate on it); day_dir is a shared filesystem there."""
    tmp = path + ".tmp.npz"  # savez appends nothing to an .npz name
    np.savez(
        tmp,
        log_beta=np.asarray(log_beta),
        alpha=np.float64(alpha),
        em_iter=np.int64(em_iter),
        likelihoods=np.asarray(likelihoods, np.float64).reshape(-1, 2),
    )
    os.replace(tmp, path)


def load_checkpoint(path: str) -> dict:
    """Load a batch EM checkpoint, rejecting streaming-LDA checkpoints
    that can share the same out_dir/checkpoint.npz filename: new-format
    ones carry `lam`, and legacy ones smuggled a strictly positive
    lambda through `log_beta` where real log-probabilities are <= 0
    (the mirror of online_lda.load_stream_checkpoint's guard)."""
    with np.load(path) as z:
        if "lam" in z.files:
            raise ValueError(
                f"{path} is a streaming-LDA checkpoint; resume it with "
                "the online trainer or remove it"
            )
        log_beta = z["log_beta"]
        if log_beta.size and (log_beta > 0).all():
            raise ValueError(
                f"{path} holds strictly positive values — a legacy "
                "streaming-LDA checkpoint (lambda), not batch log_beta; "
                "resume it with the online trainer or remove it"
            )
        return {
            "log_beta": log_beta,
            "alpha": float(z["alpha"]),
            "em_iter": int(z["em_iter"]),
            "likelihoods": [tuple(row) for row in z["likelihoods"]],
        }


def init_log_beta(key: jax.Array, k: int, v: int, dtype=jnp.float32) -> jnp.ndarray:
    """`random` initialization per the reference CLI (ml_ops.sh:80):
    uniform noise + 1/V, log-normalized per topic (lda-c random_initialize_ss)."""
    noise = jax.random.uniform(key, (k, v), dtype=dtype) + 1.0 / v
    return jnp.log(noise / noise.sum(-1, keepdims=True))


def _estep_env() -> str:
    """ONI_ML_TPU_ESTEP, the operator's pin of the E-step ("" when unset):
    "dense" / "compact" / "sparse" pin a family, "xla" / "pallas" stand the
    dense family down.  Read here for the engine family
    (`resolve_estep_engine`) and once a fit for the fused driver's plan
    (`LDATrainer._plan_estep`); estep.resolve_backend reads it on its own
    account when a token-list E-step is traced."""
    return os.environ.get("ONI_ML_TPU_ESTEP", "")


class _EStepPlan(NamedTuple):
    """What `_fused_loop` reads of the E-step it is about to run, decided
    once on the host by `LDATrainer._plan_estep`."""

    family: str             # "dense" | "dense_vocab_sharded" | "compact"
                            # | "tokens" (e_step_fn over token lists)
    kernel: str             # plan_record["estep_kernel"], fit.plan's `kernel`
    sweep_width: float      # columns a row-sweep reads (the roofline record)
    wmajor: bool = False    # dense corpus stored [W, B]
    store: object = None    # the dense corpus' dtype; None: token lists
    cell_scan: str = "none"     # what dense_estep.corpus_store_dtype read
    scan_tokens: int = 0
    budget: int = 0             # dense_budget(): bytes a device the dense
    budget_source: str = "stated"   # families were held to, and where from
    compact: "fused.CompactPlan | None" = None
    dense_e_fn: Callable | None = None      # the sharded dense E-step
    dense_put: Callable | None = None       # its corpus' device layout
    dense_mesh: object = None               # densify under this mesh
    dense_width: int | None = None          # densify to this width
    compiler_options: dict | None = None    # the scoped-VMEM limit


# What an unstated `dense_hbm_budget` falls back to where the backend
# reports no memory limit (the CPU).
FALLBACK_DENSE_BUDGET = 2 * 1024**3


def dense_budget(config: LDAConfig, mesh=None) -> tuple[int, str]:
    """(bytes a device, source) the dense E-step families are held to:
    `config.dense_hbm_budget` where it is stated ("stated"); else three
    quarters of the device's own memory limit ("device": 11.81 GiB of a
    v5e's 15.75 GiB, so an entry point that cannot state a budget -- the
    lda-c drop-in CLI -- still fills the chip); else 2 GiB ("fallback":
    the CPU reports no limit)."""
    if config.dense_hbm_budget is not None:
        return int(config.dense_hbm_budget), "stated"
    device = (jax.local_devices()[0] if mesh is None
              else mesh.devices.flat[0])
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit) * 3 // 4, "device"
    return FALLBACK_DENSE_BUDGET, "fallback"


class LDATrainer:
    """Single-process EM driver over bucketed batches.

    The `e_step_fn` hook lets the distributed layer substitute a wrapped
    E-step (shard_map over the mesh's data axis, psum on the outputs)
    without changing the math; see oni_ml_tpu/parallel.
    """

    def __init__(
        self,
        config: LDAConfig,
        num_terms: int,
        e_step_fn: Callable | None = None,
        m_step_fn: Callable | None = None,
        mesh=None,
        vocab_sharded: bool = False,
        collective=None,
        shard_plan=None,
        shard_batches=None,
        yield_hook: Callable | None = None,
    ):
        """When `mesh` is set, batches are device_put ONCE with the
        data-axis layout (and beta with the vocab-sharded layout if
        requested).  Since the distributed-EM restructure the mesh is
        HOST-LOCAL only (parallel.local_mesh): cross-process training
        runs the E-step locally per document shard and reduces the
        sufficient statistics through `collective`
        (parallel/allreduce.py) — `shard_plan`/`shard_batches` (shard
        index -> that shard's batches, doc_index GLOBAL) switch fit()
        onto the distributed driver (`_distributed_loop`).

        `yield_hook` (a context-manager factory; see
        serving/coscheduler.py) makes the fit PREEMPTIBLE at its
        natural dispatch grain: the fused driver enters one slot per
        chunk dispatch, the stepwise driver one per EM iteration, the
        distributed driver one per local E-step round — a co-resident
        serving plane wins the next dispatch slot at every boundary."""
        self.config = config
        self.num_terms = num_terms
        self.mesh = mesh
        self.vocab_sharded = vocab_sharded
        self.collective = collective
        self.shard_plan = shard_plan
        self._shard_batches = shard_batches
        self.yield_hook = yield_hook
        self._partial_runner = None  # distributed-loop jit, fit-reused
        base = e_step_fn or estep.e_step
        self._e_base = base
        self._m_base = m_step_fn or estep.m_step
        self._e_step = jax.jit(
            partial(
                base,
                var_max_iters=config.var_max_iters,
                var_tol=config.var_tol,
            )
        )
        # Warm-start variant for the stepwise loop (separate jit: the
        # fresh path must not pay for unused gamma_prev plumbing).
        self._e_step_warm = None   # stays None for non-capable e_fns
        if getattr(base, "_oni_warm_capable", False):
            self._e_step_warm = jax.jit(
                lambda lb, a, w, c, m, g, wm: base(
                    lb, a, w, c, m,
                    var_max_iters=config.var_max_iters,
                    var_tol=config.var_tol,
                    gamma_prev=g, warm=wm,
                )
            )
        self._m_step = jax.jit(self._m_base)

    def fit(
        self,
        batches: Sequence[Batch],
        num_docs: int,
        likelihood_file: str | None = None,
        progress: Callable[[int, float, float], None] | None = None,
        initial_log_beta: np.ndarray | None = None,
        initial_alpha: float | None = None,
        checkpoint_path: str | None = None,
    ) -> LDAResult:
        """Run EM to convergence.  `initial_log_beta`/`initial_alpha` warm-
        start the model (checkpoint resume, tests pinning the init); by
        default beta gets the reference's `random` initialization.

        With `checkpoint_path`, training state (beta, alpha, iteration,
        likelihood history) is persisted every `config.checkpoint_every`
        EM iterations and, if the file already exists, training resumes
        from it instead of reinitializing."""
        with maybe_span("fit.init"):
            cfg = self.config
            k, v = cfg.num_topics, self.num_terms
            dtype = jnp.dtype(cfg.compute_dtype)

            restored: list[tuple[float, float]] = []
            start_it = 0
            if checkpoint_path and os.path.exists(checkpoint_path):
                ckpt = load_checkpoint(checkpoint_path)
                if ckpt["log_beta"].shape != (k, v):
                    raise ValueError(
                        f"checkpoint beta shape {ckpt['log_beta'].shape} does "
                        f"not match config ({k}, {v})"
                    )
                initial_log_beta = ckpt["log_beta"]
                initial_alpha = ckpt["alpha"]
                restored = ckpt["likelihoods"]
                # Resuming a run checkpointed at (or past) the last iteration
                # re-runs one iteration: gamma comes from the final E-step.
                start_it = min(ckpt["em_iter"], cfg.em_max_iters - 1)

            if initial_log_beta is not None:
                log_beta = jnp.asarray(initial_log_beta, dtype)
            else:
                log_beta = init_log_beta(jax.random.PRNGKey(cfg.seed), k, v, dtype)
            alpha = jnp.asarray(
                cfg.alpha_init if initial_alpha is None else initial_alpha, dtype
            )
            if self.mesh is not None:
                from ..parallel.mesh import (
                    DATA_AXIS,
                    batch_sharding,
                    beta_sharding,
                    replicated,
                )

                data_size = self.mesh.shape[DATA_AXIS]
                for b in batches:
                    if b.word_idx.shape[0] % data_size:
                        raise ValueError(
                            f"batch of {b.word_idx.shape[0]} docs not divisible "
                            f"by data axis {data_size}"
                        )
                log_beta = jax.device_put(
                    log_beta,
                    beta_sharding(self.mesh)
                    if self.vocab_sharded
                    else replicated(self.mesh),
                )

                def put(x):
                    return jax.device_put(jnp.asarray(x), batch_sharding(self.mesh))

            else:

                def put(x):
                    return jnp.asarray(x)

            gamma_out = np.zeros((num_docs, k), dtype=np.float64)
            likelihoods: list[tuple[float, float]] = list(restored[:start_it])
            # Only the coordinator streams likelihood.dat: in multi-host runs
            # every process executes fit() against a shared day dir, and two
            # appenders on one file would interleave.
            ll_file = (
                open(likelihood_file, "w")
                if likelihood_file and _is_coordinator()
                else None
            )
            if ll_file:
                for ll_r, conv_r in likelihoods:
                    formats.append_likelihood(ll_file, ll_r, conv_r)
            ll_prev = likelihoods[-1][0] if likelihoods else None
            if self._shard_batches is not None:
                # Distributed EM: one explicit reduce per EM iteration, so
                # the chunk/host-sync knobs don't apply — the reduce IS the
                # host sync.
                self.plan_record = {}
                loop = self._distributed_loop
            else:
                self._em_chunk, self._em_sync = self._resolve_em_plan(batches)
                loop = (
                    self._fused_loop if self._em_chunk > 1
                    else self._stepwise_loop
                )
        try:
            log_beta, alpha, it, doc_sweeps, vi_max = loop(
                batches, put, log_beta, alpha, ll_prev, start_it, num_docs,
                likelihoods, ll_file, progress, checkpoint_path, gamma_out,
            )
        finally:
            if ll_file:
                ll_file.close()
        if (
            checkpoint_path
            and _is_coordinator()
            and os.path.exists(checkpoint_path)
        ):
            os.remove(checkpoint_path)  # run completed; day dir stays clean

        with maybe_span("fit.readback", what="log_beta"):
            beta_host = self._to_host(log_beta)
            alpha = float(alpha)
        return LDAResult(
            log_beta=beta_host,
            gamma=gamma_out,
            alpha=alpha,
            likelihoods=likelihoods,
            em_iters=it,
            plan=getattr(self, "plan_record", {}),
            doc_sweeps=doc_sweeps,
            vi_max=vi_max,
        )

    def _to_host(self, x) -> np.ndarray:
        """`to_host` under the span `fit.readback.d2h`: `bytes` as they
        left the device, `shards` the devices that held them."""
        with maybe_span("fit.readback.d2h") as sp:
            host = to_host(x, self.mesh)
            if sp.live:
                sp.annotate(bytes=x.nbytes,
                            shards=len(x.sharding.device_set))
        return host

    def _read_back_gamma(self, g_arr, batches, gamma_out) -> None:
        """The final posteriors of `batches`, held by ONE device array (a
        batch's [B, K], or a shape group's [NB, B, K] in the batches'
        order), into their documents' rows of `gamma_out`: one transfer
        (`fit.readback.d2h`), then a masked store per batch
        (`fit.readback.scatter`: `rows` and float64 `bytes` written).
        The one read-back of all three drivers."""
        k = gamma_out.shape[1]
        g_host = self._to_host(g_arr).reshape(len(batches), -1, k)
        with maybe_span("fit.readback.scatter") as sp:
            rows = 0
            for g, b in zip(g_host, batches):
                sel = b.doc_mask == 1
                idx = b.doc_index[sel]
                gamma_out[idx] = g[sel]
                rows += len(idx)
            sp.annotate(rows=rows, bytes=rows * k * gamma_out.itemsize)

    def _resolve_em_plan(self, batches) -> tuple[int, int]:
        """Resolve the fused driver's dispatch knobs through the plan
        layer (oni_ml_tpu/plans): an explicitly-set config value always
        wins, else a measured plan entry for this backend+shape, else
        the shipped default.  The resolution rides `plan_record` (and
        LDAResult.plan) so stage records can name the source each run
        actually trained under."""
        cfg = self.config
        if cfg.host_sync_every < 0:
            # min(chunk, negative) would request negative steps every
            # dispatch — a silent zero-iteration "fit" writing out the
            # random init as if trained.
            raise ValueError(
                f"host_sync_every must be >= 0, got {cfg.host_sync_every}"
            )
        from ..plans import em_shape, resolve

        # Multi-host runs resolve from config/defaults only: every rank
        # must build the SAME chunk program, and per-host plan caches
        # (each host's ~/.cache) could legally hold different measured
        # winners — a rank-divergent while_loop bound would desync the
        # training collectives.
        kw = {"store": None} if jax.process_count() > 1 else {}
        sig = em_shape(cfg.num_topics, self.num_terms, batches)
        chunk, chunk_src = resolve(
            "fused_em_chunk", cfg.fused_em_chunk, shape=sig, **kw
        )
        sync, sync_src = resolve(
            "host_sync_every", cfg.host_sync_every, shape=sig, **kw
        )
        chunk, sync = int(chunk), max(0, int(sync))
        self.plan_record = {
            "fused_em_chunk": {"value": chunk, "source": chunk_src},
            "host_sync_every": {"value": sync, "source": sync_src},
        }
        return chunk, sync

    # -- EM drivers ---------------------------------------------------------
    #
    # All share the fit() contract: advance (log_beta, alpha) from
    # `start_it` until convergence or em_max_iters, appending to
    # `likelihoods`, streaming `ll_file`/`progress`/checkpoints, and
    # scattering the final E-step's gammas into `gamma_out`; they return
    # (log_beta, alpha, last iteration, doc_sweeps, vi_max) — the last
    # two are LDAResult's.

    def _log_iteration(
        self, it, ll, ll_prev, likelihoods, ll_file, progress
    ) -> float:
        """Record one EM iteration host-side; returns its convergence."""
        conv = abs((ll_prev - ll) / ll_prev) if ll_prev is not None else 1.0
        likelihoods.append((ll, conv))
        if ll_file:
            formats.append_likelihood(ll_file, ll, conv)
            ll_file.flush()
        if progress:
            progress(it, ll, conv)
        return conv

    def _maybe_checkpoint(self, checkpoint_path, log_beta, alpha, it,
                          likelihoods) -> None:
        cfg = self.config
        if (
            checkpoint_path
            and cfg.checkpoint_every
            and it % cfg.checkpoint_every == 0
        ):
            # to_host is collective on multi-host meshes (process_allgather)
            # — every process must reach it; only the coordinator writes.
            beta_host = to_host(log_beta, self.mesh)
            if _is_coordinator():
                save_checkpoint(
                    checkpoint_path, beta_host, float(alpha), it, likelihoods,
                )

    def _stepwise_loop(
        self, batches, put, log_beta, alpha, ll_prev, start_it, num_docs,
        likelihoods, ll_file, progress, checkpoint_path, gamma_out,
    ):
        """One device dispatch per batch per EM iteration; the likelihood
        syncs to the host every iteration (convergence decided in float64).
        Kept for fused_em_chunk <= 1 and as the numerical cross-check for
        the fused driver."""
        cfg = self.config
        k, v = cfg.num_topics, self.num_terms
        dtype = jnp.dtype(cfg.compute_dtype)
        with maybe_span("fit.stack", batches=len(batches)):
            dev_batches = [
                (
                    put(b.word_idx),
                    put(b.counts.astype(dtype)),
                    put(b.doc_mask.astype(dtype)),
                )
                for b in batches
            ]
        # Warm start mirrors the fused driver's semantics (same gammas
        # seed the next iteration's fixed point) so the stepwise loop
        # stays its numerical cross-check under the default config.
        use_warm = cfg.warm_start_gamma and getattr(
            self._e_base, "_oni_warm_capable", False
        )
        # Roofline accounting (telemetry/roofline.py) is recorder-gated;
        # the harvest itself happens AFTER the loop so the programs are
        # already traced (the AOT cost read is then a compilation-cache
        # hit, never a cold compile ahead of first results).
        rl = None
        if current_recorder() is not None and dev_batches:
            from ..telemetry import roofline as rl
        t_loop0 = now_ns()
        n_e_disp = n_a_disp = n_warm_disp = 0
        doc_sweeps = vi_max = 0
        gammas = []
        it = start_it
        for it in range(start_it + 1, cfg.em_max_iters + 1):
            # One EM iteration is the stepwise driver's preemption
            # grain (fused_em_chunk=1 means the iteration IS the
            # chunk): the whole dispatch burst — E-steps, M-step,
            # alpha Newton — runs inside one yield-hook slot, and a
            # co-resident scoring flush wins the slot between
            # iterations.
            slot = (self.yield_hook() if self.yield_hook is not None
                    else nullcontext())
            # One EM iteration's dispatches are this driver's chunk: the
            # same span as the fused driver's enqueue.
            with slot, maybe_span("em.run_chunk", chunk=1, n_steps=1,
                                  first=it == start_it + 1):
                total_ss = jnp.zeros((v, k), dtype)
                total_ll = jnp.zeros((), dtype)
                total_ass = jnp.zeros((), dtype)
                sweeps = vi = jnp.zeros((), jnp.int32)
                prev_gammas = gammas if use_warm else []
                gammas = []
                for bi, (widx, cnts, mask) in enumerate(dev_batches):
                    if prev_gammas:
                        res = self._e_step_warm(
                            log_beta, alpha, widx, cnts, mask,
                            prev_gammas[bi], jnp.asarray(1, jnp.int32),
                        )
                        n_warm_disp += 1
                    else:
                        res = self._e_step(
                            log_beta, alpha, widx, cnts, mask
                        )
                    total_ss = total_ss + res.suff_stats
                    total_ll = total_ll + res.likelihood
                    total_ass = total_ass + res.alpha_ss
                    sweeps = sweeps + res.doc_sweeps
                    vi = jnp.maximum(vi, res.vi_iters)
                    gammas.append(res.gamma)
                    n_e_disp += 1

                log_beta = self._m_step(total_ss)
                if cfg.estimate_alpha:
                    alpha = update_alpha(total_ass, alpha, num_docs, k,
                                         max_iters=cfg.alpha_max_iters)
                    n_a_disp += 1

            # The per-iteration convergence read is the stepwise
            # driver's one deliberate device sync; span it like the
            # fused driver's em.host_sync so the flight recorder
            # prices the stall instead of it hiding in iteration wall.
            with maybe_span("em.host_sync", it=it) as sp:
                ll = float(total_ll)
                sweeps, vi = int(sweeps), int(vi)
                sp.annotate(steps=1, doc_sweeps=sweeps, vi_max=vi)
            doc_sweeps += sweeps
            vi_max = max(vi_max, vi)
            conv = self._log_iteration(
                it, ll, ll_prev, likelihoods, ll_file, progress
            )
            self._maybe_checkpoint(
                checkpoint_path, log_beta, alpha, it, likelihoods
            )
            if ll_prev is not None and conv < cfg.em_tol:
                break
            ll_prev = ll

        if rl is not None:
            with maybe_span("fit.roofline"):
                # Harvest the stepwise driver's jitted entry points — the
                # per-batch E-step and the alpha Newton are the "E-step" and
                # "alpha update" roofline phases (the fused driver inlines
                # both into em.run_chunk).  Done post-loop: the programs are
                # already traced (cache-hit lowering), and with warm starts
                # the warm variant dominated dispatches (all but the first
                # iteration), so price against the variant that actually
                # ran the majority — a mixed run is an approximation the
                # record's shape suffix names.
                b0 = batches[0].word_idx.shape[0]
                widx0, cnts0, mask0 = dev_batches[0]
                if n_warm_disp * 2 >= n_e_disp and gammas:
                    rl.ensure_harvested(
                        "em.e_step", self._e_step_warm, log_beta, alpha,
                        widx0, cnts0, mask0, gammas[0],
                        jnp.asarray(1, jnp.int32), shape=f"b{b0}.warm",
                    )
                else:
                    rl.ensure_harvested(
                        "em.e_step", self._e_step, log_beta, alpha, widx0,
                        cnts0, mask0, shape=f"b{b0}",
                    )
                if n_a_disp:
                    rl.ensure_harvested(
                        "em.update_alpha", update_alpha,
                        jnp.zeros((), dtype), alpha, num_docs, k,
                        max_iters=cfg.alpha_max_iters,
                    )
                # One roofline record per stepwise phase, joined with the
                # loop wall (the E-step dominates it; the alpha Newton's
                # record shares the wall and self-describes via
                # `wall_shared`) — journaled as {"kind": "roofline"} and
                # published as roofline.* gauges.
                wall_s = (now_ns() - t_loop0) / 1e9
                rl.emit("em.e_step", wall_s, dispatches=n_e_disp,
                        em_iters=it - start_it)
                if n_a_disp:
                    rl.emit("em.update_alpha", wall_s, dispatches=n_a_disp,
                            wall_shared="em.e_step")

        with maybe_span("fit.readback", what="gamma"):
            for g, b in zip(gammas, batches):
                self._read_back_gamma(g, [b], gamma_out)
        return log_beta, alpha, it, doc_sweeps, vi_max

    def _distributed_loop(
        self, batches, put, log_beta, alpha, ll_prev, start_it, num_docs,
        likelihoods, ll_file, progress, checkpoint_path, gamma_out,
    ):
        """Pod-scale EM: host-local E-step per document shard, explicit
        sufficient-statistics allreduce, identical M-step everywhere.

        Each owned shard's stacked groups run through ONE jitted
        partial-stats program (fused.make_partial_runner — the full
        E-step, including the sparse Pallas engine over the shard's
        bucketed layout with its per-bucket segment-sum already folded
        into the [V, K] factor).  The per-shard partials cross
        processes through parallel/allreduce.reduce_partials — whose
        fixed pairwise tree over the corpus-derived shard plan makes
        the reduced bytes identical on every rank AND invariant to the
        rank count — and then every rank runs the same M-step, alpha
        Newton, and float64 convergence check from the reduced stats.
        Rank parity of the final model is ASSERTED (digest allgather),
        not assumed."""
        import hashlib

        from ..parallel.allreduce import reduce_partials

        cfg = self.config
        k = cfg.num_topics
        dtype = jnp.dtype(cfg.compute_dtype)
        coll, plan = self.collective, self.shard_plan
        owned = sorted(self._shard_batches)

        put_stacked = put
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import DATA_AXIS

            stacked_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))

            def put_stacked(x):
                return jax.device_put(jnp.asarray(x), stacked_sh)

        compiler_options = None
        if (
            getattr(self._e_base, "_oni_sparse_engine", False)
            and jax.default_backend() == "tpu"
        ):
            from ..ops import sparse_estep

            # Same scoped-VMEM forwarding the fused driver needs: XLA
            # drops a fusion-wrapped pallas_call's own CompilerParams
            # limit inside the jitted program.
            kibs = [
                sparse_estep.scoped_vmem_kib(
                    b.word_idx.shape[0], b.word_idx.shape[1], k,
                    getattr(self._e_base, "precision", "f32"),
                )
                for bs in self._shard_batches.values() for b in bs
            ]
            if any(kibs):
                compiler_options = {
                    "xla_tpu_scoped_vmem_limit_kib": str(
                        max(filter(None, kibs))
                    )
                }
        # The jitted partial-stats program is FIT-REUSED: a standing
        # service (WindowTrainer with a collective) calls fit() every
        # refresh with fresh shard batches but identical group shapes,
        # and rebuilding the jit wrapper each fit would re-trace a
        # program the compilation cache already holds.  Keyed by the
        # compiler options in case the scoped-VMEM forwarding changes
        # with the shard census.
        co_key = (tuple(sorted(compiler_options.items()))
                  if compiler_options else None)
        if (self._partial_runner is None
                or self._partial_runner[0] != co_key):
            self._partial_runner = (co_key, fused.make_partial_runner(
                num_topics=k, num_terms=self.num_terms,
                var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
                e_step_fn=self._e_base, warm_start=cfg.warm_start_gamma,
                compiler_options=compiler_options,
            ))
        runner = self._partial_runner[1]
        shard_groups = [
            fused.stack_batches(
                self._shard_batches[s], np.dtype(cfg.compute_dtype),
                put_stacked,
            )
            for s in owned
        ]
        gammas_prev = [
            tuple(
                put_stacked(g)
                for g in fused.initial_gammas(sg.arrays, k, dtype)
            )
            for sg in shard_groups
        ]
        have_prev = False
        # env > config, matching every other distributed knob.  Applies
        # to the bulk suff-stats reduce ONLY — the f64 gamma merge
        # below pins f32 (= uncompressed) so posteriors stay exact.
        ar_precision = (
            os.environ.get("ONI_ML_TPU_ALLREDUCE_PRECISION", "")
            or cfg.allreduce_precision
        )
        ar0 = dict(coll.stats)
        t_loop0 = now_ns()
        n_reduce = 0
        doc_sweeps = vi_max = 0
        it = start_it
        for it in range(start_it + 1, cfg.em_max_iters + 1):
            warm = jnp.asarray(
                1 if (have_prev and cfg.warm_start_gamma) else 0, jnp.int32
            )
            shard_stats = {}
            new_gammas = []
            # The local E-step round is the distributed driver's
            # preemption grain (the reduce that follows is host-side
            # comms, never held under the slot — a slow peer must not
            # block a co-resident scoring flush).
            slot = (self.yield_hook() if self.yield_hook is not None
                    else nullcontext())
            with slot:
                for si, sg, gp in zip(owned, shard_groups, gammas_prev):
                    ss, ll, ass, gammas, vi, sweeps = runner(
                        log_beta, alpha, sg.arrays, gp, warm
                    )
                    new_gammas.append(gammas)
                    # The partial transfer is THE deliberate device
                    # sync of the distributed driver (one per shard per
                    # iteration); span it so the flight recorder prices
                    # it next to the allreduce wait instead of it
                    # hiding in iteration wall.
                    with maybe_span("em.host_sync", it=it,
                                    shard=si) as sp:
                        shard_stats[si] = dict(zip(
                            estep.PARTIAL_STAT_FIELDS,
                            (np.asarray(ss), np.asarray(ll),
                             np.asarray(ass)),
                        ))
                        sweeps, vi = int(sweeps), int(vi)
                        sp.annotate(steps=1, doc_sweeps=sweeps, vi_max=vi)
                    doc_sweeps += sweeps
                    vi_max = max(vi_max, vi)
            gammas_prev, have_prev = new_gammas, True
            reduced = reduce_partials(coll, plan, shard_stats,
                                      f"em{it}", precision=ar_precision)
            n_reduce += 1
            log_beta = self._m_step(jnp.asarray(reduced["suff_stats"]))
            if cfg.estimate_alpha:
                alpha = update_alpha(
                    jnp.asarray(reduced["alpha_ss"], dtype), alpha,
                    num_docs, k, max_iters=cfg.alpha_max_iters,
                )
            # reduced[...] is a HOST array (the allreduce output); the
            # span prices the implicit alpha/beta dependency drain.
            with maybe_span("em.host_sync", it=it):
                ll = float(reduced["likelihood"])
            conv = self._log_iteration(
                it, ll, ll_prev, likelihoods, ll_file, progress
            )
            self._maybe_checkpoint(
                checkpoint_path, log_beta, alpha, it, likelihoods
            )
            if ll_prev is not None and conv < cfg.em_tol:
                break
            ll_prev = ll

        if current_recorder() is not None and n_reduce:
            # The comms side of the roofline: measured allreduce bytes
            # and wall over the whole fit ({"kind": "roofline"},
            # cost_source "measured_comms" — interconnect traffic, so
            # no HBM utilization fraction is claimed).
            from ..telemetry import roofline

            d = coll.stats
            roofline.emit(
                "em.allreduce", (now_ns() - t_loop0) / 1e9,
                dispatches=n_reduce,
                measured_bytes=float(
                    d["bytes_out"] - ar0["bytes_out"]
                    + d["bytes_in"] - ar0["bytes_in"]
                ),
                transport=coll.transport, nprocs=coll.num_processes,
                allreduce_wall_s=round(d["wall_s"] - ar0["wall_s"], 6),
            )

        # Scatter owned shards' final posteriors (global doc ids), then
        # merge across ranks: unowned rows are exact zeros, so the sum
        # is a disjoint union whatever the combine order.
        with maybe_span("fit.readback", what="gamma"):
            for si, sg, gms in zip(owned, shard_groups, gammas_prev):
                bs = self._shard_batches[si]
                for g_arr, slots in zip(gms, sg.batch_slots):
                    self._read_back_gamma(
                        g_arr, [bs[bi] for bi in slots], gamma_out)
        if coll.num_processes > 1:
            # Ship only the OWNED contiguous row blocks (a rank owns
            # 1/P of the documents; gathering the full mostly-zero
            # [D, K] from every rank would move P× the bytes) and place
            # them by shard bounds — pure placement into disjoint
            # ranges, no arithmetic, so the merged gamma is exact and
            # rank-identical.
            payload = {
                s: gamma_out[plan.bounds[s][0]:plan.bounds[s][1]]
                for s in owned
            }
            # precision pinned: the gamma merge ships f64 posteriors
            # whose exactness the artifact byte-identity contract
            # depends on — never bf16-compress it.
            for g in coll.allgather_arrays(payload, "em_gamma",
                                           precision="f32"):
                for s, rows in g.items():
                    st, en = plan.bounds[s]
                    gamma_out[st:en] = rows

        # Rank parity: every rank derived its model from the same
        # reduced stats; divergence (mixed configs, a nondeterministic
        # kernel) must fail loudly here, not ship mismatched artifacts.
        beta_host = to_host(log_beta, self.mesh)
        digest = hashlib.sha256(beta_host.tobytes()).hexdigest()
        digests = coll.allgather_obj(
            (digest, float(alpha), it), "em_parity"
        )
        if any(d != digests[0] for d in digests):
            raise RuntimeError(
                f"distributed EM rank parity violated: {digests}"
            )
        return log_beta, alpha, it, doc_sweeps, vi_max

    def _exchange(self, batches, num_docs: int) -> dict:
        """What the mesh adds to a fit, from shapes alone: the shards the
        documents are split into, the real documents each holds (a batch
        is cut into contiguous equal parts along its padded rows, so the
        shards differ), and the bytes one EM iteration's `psum`s over
        `data` hand each device.  Every sharded E-step (parallel/
        sharded.py) sums the same five results once a BATCH: the [V, K]
        statistics (its own `model` slice of them), the ELBO, alpha's
        statistic, and the two sweep counters."""
        if self.mesh is None:
            return {"data_shards": 1, "allreduce_bytes": 0,
                    "rows_per_shard_min": num_docs,
                    "rows_per_shard_max": num_docs}
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

        d = self.mesh.shape[DATA_AXIS]
        rows = sum(np.asarray(b.doc_mask, np.int64).reshape(d, -1).sum(1)
                   for b in batches)
        itemsize = jnp.dtype(self.config.compute_dtype).itemsize
        per_batch = (
            self.num_terms // (self.mesh.shape[MODEL_AXIS]
                               if self.vocab_sharded else 1)
            * self.config.num_topics * itemsize + 2 * itemsize + 2 * 4)
        return {"data_shards": d,
                "allreduce_bytes": len(batches) * per_batch,
                "rows_per_shard_min": int(rows.min()),
                "rows_per_shard_max": int(rows.max())}

    def _use_dense_vocab_sharded(self, batches, mode, budget) -> bool:
        """Gate for the vocab-sharded dense plan
        (parallel.make_vocab_sharded_dense_e_step): an XLA-level matmul
        fixed point with C and beta sharded over `model` — config 4's
        MXU path.  No Pallas/VMEM feasibility applies (XLA tiles any
        shape); the auto-mode gate is device memory: each data shard
        materializes its [B/d, W] densify transient before the model
        axis splits it, and the run keeps a resident [docs/d, W/m]
        corpus slice per device."""
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

        d = self.mesh.shape[DATA_AXIS]
        m = self.mesh.shape[MODEL_AXIS]
        own_vocab = getattr(self._e_base, "_oni_vocab_sharded", False)
        incompatible = (
            "the vocabulary is sharded and the installed e_step_fn is "
            "not this package's vocab-sharded plan"
            if not own_vocab
            else f"padded vocab {self.num_terms} not divisible by "
            f"model axis {m}"
            if self.num_terms % m
            else None
        )
        if incompatible:
            if mode == "on":
                raise ValueError(f"dense E-step forced but {incompatible}")
            return False
        if mode == "on":
            return True
        if jax.default_backend() != "tpu":
            return False
        total_docs = sum(b.word_idx.shape[0] for b in batches)
        sparse_bytes = sum(b.word_idx.size * 8 for b in batches) // d
        transient = (
            max(b.word_idx.shape[0] for b in batches) // d
            * self.num_terms * 4
        )
        resident = total_docs // d * (self.num_terms // m) * 4
        return (
            transient + resident + sparse_bytes
            <= budget
        )

    def _plan_estep(self, batches) -> _EStepPlan:
        """Which E-step the fused driver runs over `batches`, in which
        layout, stored as what, under which scoped-VMEM limit: every
        host-side decision between the batches and their placement, made
        here once and handed to `_fused_loop` as one record.  Nothing is
        placed and the trainer is left as it was.

        *Full-width dense* (ops/dense_estep.py; `dense_em`, forced by
        ONI_ML_TPU_ESTEP=dense).  Auto needs a TPU backend, the stock
        E-step or this package's own sharded wrappers (a user's custom
        e_step_fn must not be silently bypassed), a VMEM-feasible doc
        block for every PER-SHARD batch shape, and the densified corpus
        under the HBM budget (`dense_budget`: the configuration's where it
        states one, else three quarters of the device's own memory
        limit).  With a data mesh the Pallas kernel runs
        under shard_map (parallel.make_data_parallel_dense_e_step),
        suff-stats psum'd over ICI; a vocab-sharded trainer takes the
        XLA-level make_vocab_sharded_dense_e_step plan instead
        (`_use_dense_vocab_sharded`).

        *Compact-vocab dense* (fused.CompactPlan: each batch densified
        over its own words, when the full vocabulary is too wide).  Auto
        needs the TPU backend, the stock E-step and the compacted corpus
        under the HBM budget; ONI_ML_TPU_ESTEP=compact forces it (tests /
        interpret runs); forced dense with no feasible full-width block
        is rescued through it.  Single-process only — the multi-chip
        huge-V story is the vocab-sharded dense plan (parallel/sharded.py).

        *Token lists* otherwise: `e_step_fn` serves the stacked
        (word_idx, counts, mask) groups (the sparse engine, the sharded
        wrappers, estep.e_step's own preference order, a custom fn)."""
        from ..ops import dense_estep

        cfg = self.config
        k, v = cfg.num_topics, self.num_terms
        precision = cfg.dense_precision
        budget, budget_source = dense_budget(cfg, self.mesh)
        env = _estep_env()
        on_tpu = jax.default_backend() == "tpu"
        shards = 1
        if self.mesh is not None:
            from ..parallel.mesh import DATA_AXIS

            shards = self.mesh.shape[DATA_AXIS]
        # The kernels see each data shard's slice of a batch: feasibility
        # and VMEM limits are per (rows a shard, L) shape.
        shapes = sorted({b.word_idx.shape for b in batches})
        local = [(rows // shards, length) for rows, length in shapes]
        custom = (self._e_base is not estep.e_step
                  and not getattr(self._e_base, "_oni_data_parallel", False))

        # "compact" forces the compact-vocab variant (full-width dense
        # stands down); "sparse" / "xla" / "pallas" stand the whole dense
        # family down.
        mode = {"dense": "on", "compact": "off", "xla": "off",
                "pallas": "off", "sparse": "off"}.get(env, cfg.dense_em)
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"LDAConfig.dense_em={mode!r}: expected 'auto', 'on', or "
                "'off'"
            )
        dense, compact = False, None
        if mode == "off":
            pass
        elif self.vocab_sharded:
            dense = self._use_dense_vocab_sharded(batches, mode, budget)
        elif custom:
            if mode == "on":
                raise ValueError(
                    "dense E-step forced but a custom e_step_fn is installed")
        else:
            feasible = all(
                dense_estep.pick_block(rows, v, k, precision) is not None
                for rows, _ in local
            )
            if mode == "auto":
                # Peak device memory during densify_groups holds BOTH the
                # sparse stacked arrays (scatter inputs; int32 idx + f32
                # counts) and the dense output, so budget the sum.  The
                # budget is per DEVICE: a data mesh shards the doc axis,
                # dividing both terms.
                sparse_bytes = sum(
                    b.word_idx.size * 8 for b in batches) // shards
                dense = (
                    feasible and on_tpu
                    and fused.dense_groups_bytes(batches, v) // shards
                    + sparse_bytes <= budget
                )
            elif feasible:
                dense = True
            else:
                # Forced dense with an infeasible full-V shape: the
                # compact-vocab variant is still the dense family —
                # rescue through it when it can serve (single-process,
                # per-batch widths blockable), else keep the hard error.
                if self.mesh is None:
                    compact = fused.plan_compact(
                        batches, k, precision, wmajor=cfg.dense_wmajor)
                if compact is None:
                    raise ValueError(
                        "dense E-step forced but a batch shape has no "
                        f"VMEM-feasible doc block (V={v}, K={k}) and the "
                        "compact-vocab fallback is not feasible either"
                    )

        # The compact variant on its own account: forced, or on auto where
        # full-width dense stood down.  Any other ONI_ML_TPU_ESTEP value
        # rules it out.
        compact_mode = ("on" if env == "compact"
                        else "off" if env else cfg.dense_em)
        blocked = (
            "a mesh is active (the multi-chip huge-V story is the "
            "vocab-sharded dense plan)"
            if self.mesh is not None or self.vocab_sharded
            else "a custom e_step_fn is installed"
            if self._e_base is not estep.e_step
            else None
        )
        if env == "compact" and blocked:
            raise ValueError(f"compact dense E-step forced but {blocked}")
        try_compact = (
            not dense and compact is None and not blocked
            and (compact_mode == "on" or compact_mode == "auto" and on_tpu))

        store, cell_scan, scan_tokens = None, "none", 0
        if dense or compact is not None or try_compact:
            # bf16 corpus storage when exact and the run is already in
            # bf16 operand mode — halves the corpus' HBM streaming with
            # bit-identical results.  The gate bounds the DENSIFIED cells
            # (duplicate (doc, word) tokens sum — the DUPFACTOR feedback
            # path makes ~1000-count cells out of count-1 tokens), not the
            # raw counts; at f32 it reads no token.
            store, cell_scan, scan_tokens = dense_estep.corpus_store_dtype(
                batches, precision)
        if try_compact:
            compact = fused.plan_compact(
                batches, k, precision, wmajor=cfg.dense_wmajor,
                itemsize=jnp.dtype(store).itemsize,
            )
            if compact is None and compact_mode == "on":
                raise ValueError(
                    "compact dense E-step forced but a batch's compact "
                    "width admits no VMEM-feasible doc block"
                )
            if compact is not None and compact_mode == "auto":
                # Peak device memory: the whole compacted corpus plus the
                # largest single group's sparse stacks
                # (compact_stack_batches uploads sparse arrays one group
                # at a time, unlike densify_groups which holds them all).
                stacks = max(
                    sum(b.word_idx.size * 8 for b in batches
                        if b.word_idx.shape == shape)
                    for shape in shapes)
                if compact.corpus_bytes + stacks > budget:
                    compact = None

        # -- the family: its kernel's name and layout, and the (rows, width)
        # blocks of its Pallas kernel, `kib` their scoped-VMEM need
        all_rows = sum(b.word_idx.shape[0] for b in batches)
        plan = dict(store=store, cell_scan=cell_scan,
                    scan_tokens=scan_tokens, budget=budget,
                    budget_source=budget_source)
        blocks, kib = [], None
        if dense and self.vocab_sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel import sharded
            from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

            # XLA-level vocab-sharded dense plan: stacked dense groups
            # [NB, B, W] shard docs over `data` and vocab columns over
            # `model`; width == the (model-divisible) padded vocab, so
            # suff-stats land exactly in the sparse plan's shard layout
            # and the vocab-sharded m_step consumes them unchanged.  Rows
            # come out of the scatter sharded over `data` (`dense_mesh`);
            # `dense_put` only drops the columns other `model` shards own.
            dense_sh = NamedSharding(self.mesh, P(None, DATA_AXIS, MODEL_AXIS))
            plan.update(
                family="dense_vocab_sharded",
                kernel="dense_vocab_sharded_xla",
                dense_put=lambda x: jax.device_put(x, dense_sh),
                dense_mesh=self.mesh, dense_width=v, sweep_width=v,
                dense_e_fn=sharded.bound(
                    sharded.make_vocab_sharded_dense_e_step(
                        self.mesh, precision=precision),
                    var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
                ),
            )
        elif dense:
            # W-major needs the doc axis on the 128-lane dimension; fall
            # back to row-major when any batch shape can't block that way.
            wmajor = cfg.dense_wmajor and all(
                dense_estep.pick_block_w(rows, v, k, precision)
                for rows, _ in local
            )
            plan.update(
                family="dense", wmajor=wmajor,
                kernel="dense_wmajor" if wmajor else "dense_rowmajor",
                sweep_width=dense_estep.padded_width(v),
            )
            if self.mesh is not None:
                from ..parallel import sharded

                # Each device densifies its own documents
                # (fused.densify_stack under the mesh) into the layout
                # this kernel reads: nothing to put.
                plan["kernel"] += "_shard_map"
                plan.update(
                    dense_mesh=self.mesh,
                    dense_e_fn=sharded.bound(
                        sharded.make_data_parallel_dense_e_step(
                            self.mesh, wmajor=wmajor, precision=precision),
                        var_max_iters=cfg.var_max_iters,
                        var_tol=cfg.var_tol,
                        interpret=not on_tpu,
                    ),
                )
            blocks = [(rows, v) for rows, _ in local]
            kib = partial(dense_estep.scoped_vmem_kib, wmajor=wmajor,
                          precision=precision)
        elif compact is not None:
            # Compact-vocab dense groups are built straight from the host
            # batches (no sparse stacked upload to discard).  The chunk
            # runner dispatches on the group layout itself
            # (fused._compact_dense gathers beta columns and scatters
            # suff-stats rows per batch).
            plan.update(
                family="compact", compact=compact, wmajor=compact.wmajor,
                kernel=("compact_wmajor" if compact.wmajor
                        else "compact_rowmajor"),
                sweep_width=sum(
                    len(us) * shape[0] * wc
                    for us, shape, wc in zip(
                        compact.uniques, shapes, compact.widths)
                ) / all_rows,
            )
            blocks = [(shape[0], wc)
                      for shape, wc in zip(shapes, compact.widths)]
            kib = partial(dense_estep.scoped_vmem_kib,
                          wmajor=compact.wmajor, precision=precision)
        else:
            plan.update(
                family="tokens",
                sweep_width=sum(
                    b.word_idx.size for b in batches) / all_rows,
            )
            if getattr(self._e_base, "_oni_sparse_engine", False):
                from ..ops import sparse_estep

                plan["kernel"] = "sparse_fused"
                blocks = shapes
                kib = partial(
                    sparse_estep.scoped_vmem_kib,
                    precision=getattr(self._e_base, "precision", "f32"))
            elif getattr(self._e_base, "_oni_vocab_sharded", False):
                plan["kernel"] = "xla_vocab_sharded"
            elif not custom:
                # estep.e_step's own preference order, at the shape each
                # device's call sees (it reports the refusals itself).
                plan["kernel"] = "+".join(sorted({
                    estep.resolve_backend("auto", rows, length, k, v)[0]
                    for rows, length in local
                }))
            else:
                plan["kernel"] = "custom"
        # XLA drops a Pallas kernel's own scoped-VMEM limit when the call
        # is fusion-wrapped inside the chunk program (a stacked-group
        # scan); forward the limit as a program-level compiler option
        # instead.  The option only exists on the TPU compiler (CPU
        # interpret runs have no VMEM to limit).
        if on_tpu and blocks:
            kibs = [kib(rows, width, k) for rows, width in blocks]
            if any(kibs):
                plan["compiler_options"] = {
                    "xla_tpu_scoped_vmem_limit_kib": str(
                        max(filter(None, kibs)))
                }
        return _EStepPlan(**plan)

    def _fused_loop(
        self, batches, put, log_beta, alpha, ll_prev, start_it, num_docs,
        likelihoods, ll_file, progress, checkpoint_path, gamma_out,
    ):
        """Device-resident EM (models/fused.py): up to fused_em_chunk
        iterations per compiled call.  The device checks convergence in
        compute dtype to stop mid-chunk; the host re-derives conv in
        float64 at chunk boundaries (_log_iteration) and that value is
        authoritative — a device stop that float64 disagrees with (the
        ~1-ulp |Δll/ll| boundary) resumes, so the stop decision always
        matches the conv written to likelihood.dat and the stepwise
        driver's float64 semantics."""
        cfg = self.config
        k = cfg.num_topics
        dtype = jnp.dtype(cfg.compute_dtype)

        # -- the plan: host-only decisions, before anything is placed ----
        with maybe_span("fit.plan", batches=len(batches)) as sp:
            plan = self._plan_estep(batches)
            sp.annotate(kernel=plan.kernel, cell_scan=plan.cell_scan,
                        scan_tokens=plan.scan_tokens)
            self.plan_record["dense_hbm_budget"] = {
                "value": plan.budget, "source": plan.budget_source}
            self.plan_record["exchange"] = self._exchange(batches, num_docs)

        # -- placement: the stack (fit.stack), then densify (fit.densify) -
        put_stacked = put
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import DATA_AXIS

            stacked_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))

            def put_stacked(x):
                return jax.device_put(jnp.asarray(x), stacked_sh)

        if plan.family == "compact":
            groups = fused.compact_stack_batches(
                batches, np.dtype(cfg.compute_dtype), put, plan.compact,
                corpus_store=plan.store,
            )
        else:
            groups = fused.stack_batches(
                batches, np.dtype(cfg.compute_dtype), put_stacked
            )
            if plan.family != "tokens":
                groups = fused.densify_groups(
                    groups, self.num_terms, wmajor=plan.wmajor,
                    put=plan.dense_put, width=plan.dense_width,
                    dtype=plan.store, mesh=plan.dense_mesh,
                )

        with maybe_span("fit.runner") as sp:
            # The devices that hold corpus shards (on a mesh, one
            # distinct slice per data shard).
            corpus = groups.arrays[0][0]
            self.plan_record["estep_kernel"] = {
                "value": plan.kernel,
                "corpus_devices": sorted(
                    s.device.id for s in corpus.addressable_shards),
                "corpus_slices": len(
                    {str(s.index) for s in corpus.addressable_shards}),
                "platform": jax.default_backend(),
            }
            run_chunk = fused.make_chunk_runner(
                num_docs=num_docs,
                num_topics=k,
                num_terms=self.num_terms,
                chunk=self._em_chunk,
                var_max_iters=cfg.var_max_iters,
                var_tol=cfg.var_tol,
                em_tol=cfg.em_tol,
                estimate_alpha=cfg.estimate_alpha,
                e_step_fn=self._e_base,
                m_step_fn=self._m_base,
                compiler_options=plan.compiler_options,
                dense_wmajor=plan.wmajor,
                warm_start=cfg.warm_start_gamma,
                dense_e_step_fn=plan.dense_e_fn,
                dense_precision=cfg.dense_precision,
                alpha_max_iters=cfg.alpha_max_iters,
                yield_hook=self.yield_hook,
            )
            # "reused": an earlier fit of this process built the chunk
            # program, and this fit's first dispatch traces nothing.
            # `stack_indexed_batches`: the batches whose kernel reads them
            # out of their group's stack in place
            # (fused.reads_stack_in_place); `sliced_batches`: the rest, a
            # single-batch group's `stack[0]` or a scan's slice.
            in_place = sum(
                g[0].shape[0] for g in groups.arrays
                if fused.reads_stack_in_place(g, plan.dense_e_fn))
            sp.annotate(program=getattr(run_chunk, "program", None),
                        batches=len(batches),
                        stack_indexed_batches=in_place,
                        sliced_batches=len(batches) - in_place)
            ll_prev_dev = jnp.asarray(
                np.nan if ll_prev is None else ll_prev, dtype
            )
            # Same data-axis commitment as every other device input: on
            # a multi-host mesh an uncommitted buffer spanning
            # non-addressable devices fails outright, and even
            # single-host meshes would pay a reshard on the first chunk
            # (gamma buffers are [NB, B, K] with B on the data axis,
            # like the stacked batches).
            gammas_prev = tuple(
                put_stacked(g)
                for g in fused.initial_gammas(
                    groups.arrays, k, dtype, dense_wmajor=plan.wmajor
                )
            )
            have_prev = jnp.asarray(False)
        it = start_it
        res = None
        # Host-sync cadence: host_sync_every bounds the iterations per
        # dispatch independently of the compiled chunk size, so
        # likelihood.dat streams (and progress fires) at least that
        # often — with chunk=128 and checkpointing off a whole fit is
        # otherwise ONE dispatch and a crash loses every likelihood
        # line.  The chunk program takes its step count dynamically
        # (like the checkpoint cap below), so no recompile.  Both knobs
        # arrive plan-resolved (_resolve_em_plan; negative
        # host_sync_every already rejected there).
        sync_chunk = self._em_chunk
        if self._em_sync:
            sync_chunk = min(sync_chunk, self._em_sync)
        t_loop0 = now_ns()
        n_disp = 0
        doc_sweeps = vi_max = 0
        while it < cfg.em_max_iters:
            stop = min(it + sync_chunk, cfg.em_max_iters)
            if checkpoint_path and cfg.checkpoint_every:
                next_ckpt = (
                    it // cfg.checkpoint_every + 1
                ) * cfg.checkpoint_every
                stop = min(stop, next_ckpt)
            res = run_chunk(
                log_beta, alpha, ll_prev_dev, groups.arrays, stop - it,
                gammas_prev, have_prev,
            )
            n_disp += 1
            # Carry the chunk's final posteriors so warm start survives
            # the host sync at chunk boundaries.
            gammas_prev, have_prev = res.gammas, res.steps_done > 0
            log_beta, alpha, ll_prev_dev = res.log_beta, res.alpha, res.ll_prev
            # The host sync: int()/np.asarray block on the device here,
            # then likelihood.dat lines stream, progress fires (the
            # runner's journal em_ll points ride it), and checkpoints
            # land — the flight-recorder span that, with fused.py's
            # em.run_chunk dispatch span, decomposes an EM wall into
            # enqueue glue vs blocking sync (telemetry/spans.py).
            with maybe_span("em.host_sync", it=it) as sp:
                steps = int(res.steps_done)
                sweeps = int(np.asarray(res.doc_sweeps)[:steps].sum())
                vi = int(np.asarray(res.vi_iters)[:steps].max(initial=0))
                sp.annotate(steps=steps, doc_sweeps=sweeps, vi_max=vi)
                doc_sweeps += sweeps
                vi_max = max(vi_max, vi)
                host_conv = None
                for ll in np.asarray(res.lls[:steps], np.float64):
                    it += 1
                    ll = float(ll)
                    host_conv = self._log_iteration(
                        it, ll, ll_prev, likelihoods, ll_file, progress
                    )
                    ll_prev = ll
                self._maybe_checkpoint(
                    checkpoint_path, log_beta, alpha, it, likelihoods
                )
            if steps == 0:
                break
            # float64 conv (what likelihood.dat records) decides the stop;
            # res.converged only ends a chunk early.  Near em_tol the
            # compute-dtype device check can disagree by ~1 ulp — if it
            # stopped but float64 says not converged, keep iterating.
            if host_conv is not None and host_conv < cfg.em_tol:
                break

        if current_recorder() is not None and n_disp:
            # The EM roofline record: the chunk program's harvested
            # per-dispatch cost (fused.py's runner wrapper registers it
            # at first instrumented dispatch) joined with the loop's
            # monotonic wall — enqueue glue AND blocking host syncs, the
            # whole EM phase.  Journaled as {"kind": "roofline"}; on
            # backends with registered peaks the record carries
            # mxu_pct/hbm_pct, elsewhere `utilization: null`.  XLA's
            # cost analysis counts the chunk's while_loop body once and
            # a Pallas call as nothing, so mxu_pct is not the EM's
            # utilization; useful_mxu_pct is: it rests on the work
            # counted here, every sweep the E-step ran (two [rows, W] x
            # [W, K] products each) plus one product per row and EM
            # iteration for the expected counts, at the width the kernel
            # sweeps.
            from ..telemetry import roofline

            rows = sum(b.word_idx.shape[0] for b in batches)
            roofline.emit(
                "em.run_chunk", (now_ns() - t_loop0) / 1e9,
                dispatches=n_disp, em_iters=it - start_it,
                chunk=self._em_chunk, doc_sweeps=doc_sweeps,
                effective_flops=(
                    4.0 * doc_sweeps + 2.0 * rows * (it - start_it)
                ) * plan.sweep_width * k,
            )

        if res is not None and int(res.steps_done) > 0:
            with maybe_span("fit.readback", what="gamma"):
                for g_arr, slots in zip(res.gammas, groups.batch_slots):
                    self._read_back_gamma(
                        g_arr, [batches[bi] for bi in slots], gamma_out)
        return log_beta, alpha, it, doc_sweeps, vi_max


def warm_start_log_beta(
    topic_probs: np.ndarray, num_terms: int
) -> np.ndarray:
    """[V0, K] p(word|topic) from a previous fit -> a [K, num_terms]
    log-beta EM init padded for vocabulary growth.

    Day N's window contains words day N−1 never saw; its beta needs a
    row for each.  New words get one symmetric-prior quantum of mass
    (1/num_terms — what a uniform Dirichlet prior would put there) and
    every topic renormalizes, so the previous topics carry over almost
    unchanged while unseen words start at small-but-trainable mass
    rather than the LOG_ZERO floor (a floored word could never grow
    back under the multiplicative fixed point).  Shrinking the
    vocabulary is refused: global word ids are first-seen-stable, so a
    smaller V means the caller mixed id spaces."""
    p = np.asarray(topic_probs, np.float64)
    if p.ndim != 2:
        raise ValueError(f"topic_probs must be [V, K], got {p.shape}")
    v0, k = p.shape
    if num_terms < v0:
        raise ValueError(
            f"vocabulary cannot shrink: previous topics cover {v0} "
            f"words, new corpus has {num_terms} — window word ids are "
            "first-seen-stable, so a smaller V means mixed id spaces"
        )
    if not np.isfinite(p).all() or (p < 0).any():
        raise ValueError("topic_probs must be finite and nonnegative")
    prior = 1.0 / max(num_terms, 1)
    full = np.concatenate(
        [p, np.full((num_terms - v0, k), prior, np.float64)], axis=0
    )
    full = full / np.maximum(full.sum(axis=0, keepdims=True), 1e-300)
    beta = full.T  # [K, num_terms]
    return np.where(
        beta > 0, np.log(np.maximum(beta, 1e-300)), estep.LOG_ZERO
    )


class WindowTrainer:
    """Shape-stable, warm-startable EM driver for continuous window
    refreshes (runner/continuous.py; ROADMAP item 3).

    One instance lives for the window's whole vocabulary capacity tier
    and is reused refresh-over-refresh: the jitted E/M programs hang
    off the inner LDATrainer, so window N+1 re-dispatches the programs
    window N traced — with the window's pow2 vocab padding and the
    full-batch-size bucket padding below, a drifting doc census never
    changes a compiled shape.  Batches always pad to the FULL batch
    size (make_batches' default padding, not the pipeline's
    multiple-of-8 tail padding) for exactly that reason.

    `fit()` seeds EM from the previous refresh's topics
    (warm_start_log_beta pads for vocabulary growth) when given them;
    the existing float64 convergence check then early-exits after the
    few iterations the stream actually moved — the warm-start-vs-fresh
    trade the streaming_freshness bench measures.

    With a `collective` (parallel/allreduce.py) the refresh trains
    DISTRIBUTED: the warm-start seed broadcasts from the coordinator
    (rank-identical topics even when only rank 0 holds the publish
    history), documents shard by the PR 11 plan, the local E-steps
    reduce through the collective, and — because a standing service
    refits the SAME trainer forever — the per-shard batch census pads
    to power-of-two counts (`pad_batch_census_pow2`) so the stacked
    [NB, B, L] group shapes stay compiled-stable while the window's
    doc count wobbles.  `yield_hook` threads through to the EM driver
    (see LDATrainer) so refresh fits are preemptible by a co-resident
    serving plane."""

    def __init__(self, config: LDAConfig, num_terms: int, *,
                 collective=None, yield_hook=None) -> None:
        self.config = config
        self.num_terms = num_terms
        self.collective = collective
        self._trainer = LDATrainer(
            config, num_terms=num_terms, collective=collective,
            yield_hook=yield_hook,
        )
        self.fits = 0

    def fit(
        self,
        corpus: Corpus,
        *,
        topic_probs: "np.ndarray | None" = None,
        alpha: "float | None" = None,
        progress: "Callable | None" = None,
    ) -> LDAResult:
        """One window refresh: corpus -> LDAResult.  With
        `topic_probs` (the previous published [V_prev, K] matrix), EM
        warm-starts from them (rows padded for vocab growth) and
        `alpha` seeds the Newton; without, the reference's random
        init.  `result.plan["warm_start"]` records which path ran."""
        cfg = self.config
        if corpus.num_terms != self.num_terms:
            raise ValueError(
                f"window corpus has V={corpus.num_terms} but this "
                f"trainer's capacity tier is {self.num_terms} — "
                "rebuild the trainer at the new tier (one program "
                "family per tier, by design)"
            )
        if self.collective is not None:
            # Rank-identical warm start: the coordinator's seed is THE
            # seed (only it holds the drift-gated publish history);
            # every rank trains from the broadcast copy.  The tag keys
            # on the fit count, which advances in lockstep.
            topic_probs, alpha = self.collective.broadcast_obj(
                (topic_probs, alpha) if self.collective.rank == 0
                else None,
                f"window_seed{self.fits}",
            )
        warm = topic_probs is not None
        init_lb = (
            warm_start_log_beta(topic_probs, self.num_terms)
            if warm else None
        )
        if self.collective is not None:
            batches, num_docs = self._shard_window(corpus)
        else:
            batches = make_batches(
                corpus, batch_size=cfg.batch_size,
                min_bucket_len=cfg.min_bucket_len,
            )
            num_docs = corpus.num_docs
        result = self._trainer.fit(
            batches,
            num_docs,
            progress=progress,
            initial_log_beta=init_lb,
            initial_alpha=alpha if warm else None,
        )
        self.fits += 1
        result.plan["warm_start"] = {
            "value": bool(warm), "source": "window"
        }
        if self.collective is not None:
            result.plan["em_shards"] = {
                "value": self._trainer.shard_plan.num_shards,
                "source": "window",
            }
            result.plan["allreduce"] = {
                "transport": self.collective.transport,
                "nprocs": self.collective.num_processes,
            }
        return result

    def _shard_window(self, corpus: Corpus):
        """Per-refresh shard plan + batches for the distributed driver.
        The plan re-derives from the window's live doc count every
        refresh (documents churn), but the trainer — and its jitted
        partial-stats program — is REUSED: shard batches are plain
        attributes on LDATrainer, and the census padding below keeps
        the stacked group shapes the cached program was traced at."""
        from ..parallel.shard_plan import plan_shards, resolve_em_shards

        cfg = self.config
        coll = self.collective
        plan = plan_shards(
            corpus.num_docs, coll.num_processes,
            resolve_em_shards(cfg.em_shards, coll.num_processes),
        )
        shard_batches = {
            s: pad_batch_census_pow2([
                Batch(b.word_idx, b.counts,
                      b.doc_index + plan.bounds[s][0], b.doc_mask)
                for b in make_batches(
                    corpus.shard(*plan.bounds[s]),
                    batch_size=cfg.batch_size,
                    min_bucket_len=cfg.min_bucket_len,
                )
            ])
            for s in plan.owned(coll.rank)
        }
        self._trainer.shard_plan = plan
        self._trainer._shard_batches = shard_batches
        return (
            [b for s in sorted(shard_batches)
             for b in shard_batches[s]],
            corpus.num_docs,
        )


def pad_batch_census_pow2(batches: "list[Batch]") -> "list[Batch]":
    """Pad each (B, L)-shaped batch group's COUNT to a power of two
    with fully-masked empty batches.

    The window's vocab pads to pow2 capacity tiers and its batches pad
    to the full batch size, but the distributed driver stacks same-
    shaped batches into [NB, B, L] groups — and NB is the one shape
    left keyed on the raw doc census, so a window gaining one batch
    would retrace the partial-stats program.  Census tiers close the
    gap: NB pads to pow2 exactly like the vocabulary does.  A pad
    batch is inert by the same mechanism as in-batch pad rows —
    doc_mask 0 zeroes its suff-stats/likelihood contributions, and the
    gamma scatter selects no rows (doc_index 0 is never read)."""
    groups: "dict[tuple, list[Batch]]" = {}
    order: list = []
    for b in batches:
        key = b.word_idx.shape
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(b)
    out: "list[Batch]" = []
    for key in order:
        grp = groups[key]
        target = 1
        while target < len(grp):
            target *= 2
        bb, ll = key
        for _ in range(target - len(grp)):
            grp.append(Batch(
                np.zeros((bb, ll), np.int32),
                np.zeros((bb, ll), np.float32),
                np.zeros((bb,), np.int32),
                np.zeros((bb,), np.float32),
            ))
        out.extend(grp)
    return out


def resolve_estep_engine(
    corpus: Corpus, config: LDAConfig, mesh=None, vocab_sharded: bool = False,
    distributed: bool = False, shard_plan=None,
) -> "tuple[str, str]":
    """Resolve the E-step engine FAMILY for a batch training run:
    ("sparse" | "dense", source).

    "sparse" is the fused bucketed Pallas engine (ops/sparse_estep.py:
    corpus packed by Corpus.bucketed_layout, K×L work per doc);
    "dense" is everything that exists today — the dense/compact/XLA/
    Pallas family, chosen within by LDATrainer._plan_estep and
    estep.e_step's auto.  Precedence mirrors the rest of
    the plan layer: ONI_ML_TPU_ESTEP env ("env") > an explicit
    LDAConfig.estep_engine ("config") > the MEASURED dense-vs-sparse
    crossover from the plan cache (sparse_estep.engine_crossover —
    source "plan" when a persisted entry serves, "measured" when this
    run sweeps it once) on TPU, else the dense family ("default").

    The sparse engine is single-process PER RANK — a mesh whose data
    axis would shard its layout still takes the dense family, and
    forcing sparse there is an error, not a silent fallback.  But
    `distributed=True` (host-local E-step shards + explicit allreduce,
    parallel/allreduce.py) IS a set of single-process programs: with no
    local mesh the sparse engine is fully allowed, feasibility is
    checked over every shard's bucket shapes (`shard_plan`), and the
    crossover is consulted at the dominant LOCAL shard shape — the
    shapes the kernel will actually see, which per-shard batching makes
    smaller than the whole-corpus shapes."""
    env = _estep_env()
    choice = config.estep_engine
    if choice not in ("auto", "dense", "sparse"):
        raise ValueError(
            f"LDAConfig.estep_engine={choice!r}: expected 'auto', "
            "'dense', or 'sparse'"
        )
    forced_sparse = env == "sparse" or (not env and choice == "sparse")
    if mesh is not None or vocab_sharded:
        if forced_sparse:
            raise ValueError(
                "the sparse bucketed E-step engine is single-process; "
                "meshes keep the sharded dense/sparse plans "
                "(unset ONI_ML_TPU_ESTEP=sparse / estep_engine='sparse'"
                + (" or drop the local mesh — distributed EM runs the "
                   "sparse engine host-locally without one)"
                   if distributed else ")")
            )
        return "dense", "default"
    if forced_sparse and config.dense_em == "on":
        raise ValueError(
            "estep_engine='sparse' conflicts with dense_em='on' — pin "
            "one engine family, not both"
        )
    if env:
        return ("sparse", "env") if env == "sparse" else ("dense", "env")
    if choice != "auto":
        return choice, "config"
    if jax.default_backend() != "tpu" or config.dense_em == "on":
        # CPU/interpret runs keep today's paths (the dense family's
        # auto already resolves to XLA there); dense_em="on" is an
        # explicit family pin.
        return "dense", "default"
    from ..ops import sparse_estep

    l_len, _ = sparse_estep.resolve_layout_len(
        config.sparse_min_bucket_len, use_plans=not distributed
    )
    # Shapes only — the O(tokens) packing pass is deferred to
    # train_corpus's sparse branch, so a dense-winning crossover never
    # pays for (or keeps cached) padded tiles it won't train on.
    # Distributed runs derive them per SHARD: each shard buckets
    # independently, so the engine must be feasible for every shard's
    # shapes and the crossover keys on the shapes a rank actually
    # dispatches.
    pieces = (
        [corpus.shard(st, en) for st, en in shard_plan.bounds]
        if distributed and shard_plan is not None
        else [corpus]
    )
    shapes = [
        s
        for piece in pieces
        for s in piece.bucket_shapes(
            min_len=l_len, batch_cap=config.batch_size,
            pad_multiple=sparse_estep.pad_multiple_for(
                config.dense_precision
            ),
        )
    ]
    if not shapes:
        return "dense", "default"
    # EVERY bucket shape must admit a block — the VMEM-worst bucket is
    # typically a small-B huge-L one, not the largest batch.
    if any(
        sparse_estep.pick_block(
            bb, ll, config.num_topics, config.dense_precision
        ) is None
        for bb, ll, _ in shapes
    ):
        return "dense", "default"
    b_dom, l_dom, _ = max(shapes, key=lambda s: s[2])
    cross = sparse_estep.engine_crossover(
        config.num_topics, corpus.num_terms, b_dom, l_dom,
        precision=config.dense_precision,
    )
    return cross["engine"], cross["source"]


def train_corpus(
    corpus: Corpus,
    config: LDAConfig,
    out_dir: str | None = None,
    progress: Callable[[int, float, float], None] | None = None,
    mesh=None,
    vocab_sharded: bool = False,
    save_final: bool = True,
    distributed: "bool | None" = None,
    collective=None,
) -> LDAResult:
    """Convenience: corpus -> batches -> fit -> (optionally) reference
    output files in `out_dir`.

    With `mesh`, documents shard over the mesh's `data` axis (suff-stats
    psum — the reference's MPI_Reduce, SURVEY §2.8); with
    `vocab_sharded` additionally, beta/suff-stats shard their vocabulary
    axis over `model` (BASELINE.json config 4).  Since the distributed
    restructure the mesh must be HOST-LOCAL (parallel.local_mesh): one
    global SPMD program spanning processes is not a thing this trainer
    builds any more (the CPU runtime cannot execute it, and it forced
    the sparse engine dense).

    `distributed` (default: auto — `jax.process_count() > 1`) switches
    to pod-scale EM: every rank receives the SAME full corpus, trains
    only its document shards host-locally (parallel/shard_plan.py),
    and the sufficient statistics cross processes through the explicit
    allreduce (parallel/allreduce.py).  Also runnable single-process
    (the byte-identity baseline, bench distributed_em, the MULTICHIP
    dryrun topology plans).

    `save_final=False` keeps likelihood.dat streaming and checkpoint
    resume (both keyed off `out_dir`) but skips the final.* writes —
    the streaming dataplane demotes those to background checkpoint
    sinks that overlap scoring, so the trainer must not also write
    them inline on the critical path.

    The whole call is the span `fit` (telemetry/spans.py), the root the
    fit's layer boundaries hang under: fit.engine, fit.batches,
    fit.init, fit.plan, fit.stack (per shape group fit.stack.copy, the
    group's host stack: a view of the batches' buffer or a copy,
    `copied_bytes` says which; and fit.stack.put, handing it to the
    runtime: `bytes` each), fit.densify, fit.runner, em.run_chunk /
    em.host_sync, fit.readback (per device array fit.readback.d2h,
    `to_host`: `bytes` as they left the device, `shards`; for gamma also
    fit.readback.scatter, the masked stores into the result: `rows`,
    `bytes`), fit.save (which counts the bytes of each file it wrote, the
    matrices' `rows` and `values`, and says their `writer`),
    fit.teardown.  On its close it counts what the fit ran (`em_iters`,
    `doc_sweeps`, the engine and kernel), which dense budget the plan
    held and where it came from (`dense_budget`, `dense_budget_source`:
    stated / device / fallback -- `dense_budget()`), the lines appended
    to likelihood.dat (`ll_lines`), what the mesh added (`data_shards`,
    `allreduce_bytes` an EM iteration and device, `rows_per_shard_min` /
    `_max`: LDATrainer._exchange) and, while plans.warmup's listener is
    live, jax's compile counters across the fit.
    """
    if distributed is None:
        distributed = jax.process_count() > 1
    from ..plans import warmup

    with maybe_span(
        "fit", num_docs=corpus.num_docs, num_terms=corpus.num_terms,
        k=config.num_topics,
        mesh=None if mesh is None else str(dict(mesh.shape)),
    ) as sp:
        compiles0 = warmup.compile_counts() if warmup.counting() else None
        train = _train_corpus_distributed if distributed else _train_corpus
        kw = {"collective": collective} if distributed else {}
        result = train(
            corpus, config, out_dir=out_dir, progress=progress, mesh=mesh,
            vocab_sharded=vocab_sharded, save_final=save_final, **kw,
        )
        sp.annotate(
            engine=result.plan["estep_engine"]["value"],
            kernel=result.plan.get("estep_kernel", {}).get("value"),
            em_iters=result.em_iters, doc_sweeps=result.doc_sweeps,
            vi_max=result.vi_max,
        )
        sp.annotate(**result.plan.get("exchange", {}))
        budget = result.plan.get("dense_hbm_budget")
        if budget:      # the fused driver's plan: which budget, whose
            sp.annotate(dense_budget=budget["value"],
                        dense_budget_source=budget["source"])
        if out_dir and _is_coordinator():
            # likelihood.dat: one line an EM iteration, appended as they
            # came (LDATrainer._log_iteration).
            sp.annotate(ll_lines=len(result.likelihoods))
        if compiles0 is not None:
            delta = warmup.counts_delta(compiles0)
            sp.annotate(**{key: delta[key] for key in (
                "compile_requests", "cache_hits", "trace_s", "compile_s")})
    return result


def _train_corpus(
    corpus: Corpus,
    config: LDAConfig,
    out_dir: str | None = None,
    progress: Callable[[int, float, float], None] | None = None,
    mesh=None,
    vocab_sharded: bool = False,
    save_final: bool = True,
) -> LDAResult:
    """train_corpus in one process (its docstring; the `fit` span is
    open)."""
    e_fn = m_fn = None
    num_terms = corpus.num_terms
    initial_log_beta = None
    if vocab_sharded and mesh is None:
        raise ValueError("vocab_sharded=True requires a mesh")
    with maybe_span("fit.engine"):
        engine, engine_src = resolve_estep_engine(
            corpus, config, mesh=mesh, vocab_sharded=vocab_sharded
        )
    sparse_layout = None
    sparse_l_record = None
    if engine == "sparse":
        from ..ops import sparse_estep

        sparse_l, sparse_l_src = sparse_estep.resolve_layout_len(
            config.sparse_min_bucket_len
        )
        sparse_l_record = {"value": sparse_l, "source": sparse_l_src}
        # The batch axis pads to the engine precision's sublane tile
        # (16 for bf16) so every bucket's padded doc count admits a
        # kernel block; a forced-sparse run whose shapes still cannot
        # block fails HERE with the shapes named, not mid-training
        # inside the chunk program.
        pad = sparse_estep.pad_multiple_for(config.dense_precision)
        bad = [
            (bb, ll)
            for bb, ll, _ in corpus.bucket_shapes(
                min_len=sparse_l, batch_cap=config.batch_size,
                pad_multiple=pad,
            )
            if sparse_estep.pick_block(
                bb, ll, config.num_topics, config.dense_precision
            ) is None
        ]
        if bad:
            raise ValueError(
                f"sparse E-step engine selected but bucket shapes {bad} "
                "admit no VMEM-feasible doc block at precision "
                f"{config.dense_precision!r} (K={config.num_topics}); "
                "use the dense family for this corpus"
            )
        with maybe_span("fit.batches", layout="bucketed") as sp:
            sparse_layout = corpus.bucketed_layout(
                min_len=sparse_l, batch_cap=config.batch_size,
                pad_multiple=pad,
            )
            # The sparse engine trains over the bucketed layout's packed
            # tiles; Batch.doc_index carries the permutation, so fit()'s
            # gamma scatter restores document order bit-exactly
            # (layout.inv_perm is the same map, pinned by tests).
            batches = list(sparse_layout.batches)
            sp.annotate(**_batch_counts(batches, [corpus]))
        e_fn = sparse_estep.make_e_step_fn(precision=config.dense_precision)
    data_size = 1
    if mesh is not None:
        e_fn, m_fn, num_terms, initial_log_beta, data_size = (
            _mesh_trainer_setup(corpus, config, mesh, vocab_sharded)
        )

    if sparse_layout is None:
        with maybe_span("fit.batches", layout="make_batches") as sp:
            batches = make_batches(
                corpus, batch_size=config.batch_size,
                min_bucket_len=config.min_bucket_len,
                # Every device's slice of every batch — the tail batches
                # too — is a multiple of the 8-row sublane tile, or one
                # ragged tail takes the Pallas kernels away from the
                # whole run (their doc blocks must divide the per-shard
                # batch).
                pad_multiple=8 * data_size,
            )
            sp.annotate(**_batch_counts(batches, [corpus]))
    with maybe_span("fit.init", what="trainer"):
        trainer = LDATrainer(
            config,
            num_terms=num_terms,
            e_step_fn=e_fn,
            m_step_fn=m_fn,
            mesh=mesh,
            vocab_sharded=vocab_sharded,
        )
    ll_path = os.path.join(out_dir, "likelihood.dat") if out_dir else None
    ckpt_path = (
        os.path.join(out_dir, "checkpoint.npz")
        if out_dir and config.checkpoint_every
        else None
    )
    result = trainer.fit(
        batches,
        corpus.num_docs,
        likelihood_file=ll_path,
        progress=progress,
        initial_log_beta=initial_log_beta,
        checkpoint_path=ckpt_path,
    )
    # Engine attribution rides the same plan record every other
    # resolved knob does (stage records surface it per run).
    result.plan["estep_engine"] = {"value": engine, "source": engine_src}
    if sparse_l_record is not None:
        result.plan["sparse_estep_l"] = sparse_l_record
    if num_terms != corpus.num_terms:
        result.log_beta = result.log_beta[:, : corpus.num_terms]
    if out_dir and save_final and _is_coordinator():
        # likelihood.dat was already streamed (crash-safe) during fit;
        # multi-host: the result is identical on every process (to_host
        # gathers collectively) but only the coordinator owns the files.
        with maybe_span("fit.save") as sp:
            sp.annotate(**result.save(out_dir, num_terms=corpus.num_terms,
                                      include_likelihood=False))
    with maybe_span("fit.teardown"):
        # Dropping the trainer drops its jitted entry points and their
        # executables (the stepwise driver's: 24 ms on the CPU, 5% of a
        # small fit), and the batches' host buffers go back to malloc
        # (two munmaps of 50 MB at a flow day's size): time of the fit
        # that lay under no span while it happened at the return.
        del trainer, batches, sparse_layout
    return result


def _batch_counts(batches, corpora) -> dict:
    """What the `fit.batches` span counts: the batches, their padded
    rows and their distinct shapes, and the work of the fill: `tokens`,
    the real (document, word) cells of the corpora that were batched
    (their CSR length: no pass over the batches), placed into `cells`
    padded ones (the sum of B * L over the batches' shapes)."""
    return {
        "batches": len(batches),
        "rows": sum(b.word_idx.shape[0] for b in batches),
        "shapes": len({b.word_idx.shape for b in batches}),
        "tokens": sum(len(c.word_idx) for c in corpora),
        "cells": sum(b.word_idx.size for b in batches),
    }


def _mesh_trainer_setup(corpus: Corpus, config: LDAConfig, mesh,
                        vocab_sharded: bool):
    """Shared mesh-path trainer setup for train_corpus AND the
    distributed variant (one copy of the divisibility check, the
    idle-model-axis warning, and the e_fn/m_fn selection):
    (e_fn, m_fn, num_terms, initial_log_beta, data_size)."""
    from ..parallel import sharded
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    if config.batch_size % mesh.shape[DATA_AXIS]:
        # fit() re-checks per batch; failing here gives the clearer
        # message before any batching work happens.
        raise ValueError(
            f"batch_size {config.batch_size} not divisible by data axis "
            f"{mesh.shape[DATA_AXIS]}"
        )
    if not vocab_sharded and mesh.shape[MODEL_AXIS] > 1:
        import warnings

        warnings.warn(
            f"mesh has model axis {mesh.shape[MODEL_AXIS]} but "
            "vocab_sharded=False: those devices will replicate work",
            stacklevel=3,
        )
    if vocab_sharded:
        e_fn, m_fn, num_terms, initial_log_beta = _vocab_sharded_setup(
            corpus, config, mesh
        )
    else:
        e_fn = sharded.make_data_parallel_e_step(mesh)
        m_fn = None
        num_terms = corpus.num_terms
        initial_log_beta = None
    return e_fn, m_fn, num_terms, initial_log_beta, mesh.shape[DATA_AXIS]


def _vocab_sharded_setup(corpus: Corpus, config: LDAConfig, mesh):
    """(e_fn, m_fn, padded num_terms, initial_log_beta) for a
    vocab-sharded trainer: the shard_map'd E/M pair with the vocabulary
    padded to the mesh's model axis, the init padded with LOG_ZERO
    columns so padded words carry ~no mass and single- vs multi-device
    runs agree numerically."""
    from ..parallel import sharded
    from ..parallel.mesh import MODEL_AXIS

    e_fn, m_fn = sharded.make_vocab_sharded_fns(mesh)
    num_terms = sharded.pad_vocab(corpus.num_terms, mesh.shape[MODEL_AXIS])
    initial_log_beta = None
    if num_terms != corpus.num_terms:
        base = init_log_beta(
            jax.random.PRNGKey(config.seed),
            config.num_topics,
            corpus.num_terms,
            jnp.dtype(config.compute_dtype),
        )
        initial_log_beta = jnp.pad(
            base,
            ((0, 0), (0, num_terms - corpus.num_terms)),
            constant_values=estep.LOG_ZERO,
        )
    return e_fn, m_fn, num_terms, initial_log_beta


def _train_corpus_distributed(
    corpus: Corpus,
    config: LDAConfig,
    out_dir: str | None = None,
    progress: Callable[[int, float, float], None] | None = None,
    mesh=None,
    vocab_sharded: bool = False,
    save_final: bool = True,
    collective=None,
) -> LDAResult:
    """Pod-scale distributed EM (ROADMAP item 1): host-local E-step
    shards + explicit sufficient-statistics allreduce.

    Every rank holds the SAME full corpus (stage_corpus's shared
    model.dat, or the in-memory corpus single-process) and the same
    deterministic shard plan; each trains only its owned contiguous
    document shards on its own devices — including the PR 9 sparse
    Pallas engine over a per-shard bucketed layout — and the [V, K]
    beta factor, alpha suff-stats, and ELBO scalar cross processes
    through parallel/allreduce.  The M-step, alpha Newton, convergence
    check, and likelihood journal then run identically on every rank
    from the reduced stats (parity asserted), so the LDAResult is
    rank-identical and the coordinator alone writes the shared files.

    The engine decision is made ONCE on the coordinator (crossover
    consulted at the local shard shapes, plan lookups per-host) and
    broadcast, so ranks can never train under different engines."""
    from ..parallel.allreduce import PeerFailure, get_collective
    from ..parallel.mesh import is_local_mesh
    from ..parallel.shard_plan import plan_shards, resolve_em_shards

    coll = collective if collective is not None else get_collective()
    if mesh is not None and not is_local_mesh(mesh):
        raise ValueError(
            "distributed EM is host-local: the mesh may span this "
            "process's devices only (parallel.local_mesh()); the "
            "cross-process reduction is the explicit suff-stats "
            "allreduce, not a global mesh spanning processes"
        )
    if vocab_sharded and mesh is None:
        raise ValueError("vocab_sharded=True requires a mesh")
    nshards = resolve_em_shards(config.em_shards, coll.num_processes)
    plan = plan_shards(corpus.num_docs, coll.num_processes, nshards)
    # One engine for the whole process group: the coordinator resolves
    # (its plan cache, its crossover measurement at the local shard
    # shapes) and broadcasts — per-host plan caches may legally
    # disagree, and rank-divergent engines would silently break the
    # cross-rank-count byte-identity contract.
    if coll.rank == 0:
        try:
            with maybe_span("fit.engine"):
                decision = resolve_estep_engine(
                    corpus, config, mesh=mesh, vocab_sharded=vocab_sharded,
                    distributed=True, shard_plan=plan,
                )
        except BaseException as e:
            # Library-level relay (the runner's stage barrier is not in
            # play for direct train_corpus callers): without this, a
            # coordinator-only config error leaves every peer blocked
            # in the broadcast for the full collective timeout with a
            # misleading "peer stalled or died" message.
            coll.fail(f"estep engine resolution: {e!r}")
            raise
    else:
        decision = None
    engine, engine_src = coll.broadcast_obj(decision, "estep_engine")

    e_fn = m_fn = None
    num_terms = corpus.num_terms
    initial_log_beta = None
    sparse_l_record = None
    owned = plan.owned(coll.rank)
    shard_corpora = {s: corpus.shard(*plan.bounds[s]) for s in owned}
    data_size = 1
    if mesh is not None:
        e_fn, m_fn, num_terms, initial_log_beta, data_size = (
            _mesh_trainer_setup(corpus, config, mesh, vocab_sharded)
        )

    if engine == "sparse":
        from ..ops import sparse_estep

        # ALWAYS plans-off in distributed mode (matching the engine
        # resolution's use_plans=not distributed): a measured
        # sparse_estep_l serving only at some rank counts would give
        # the 1-rank and N-rank runs different bucketed layouts —
        # breaking the byte-identical-artifacts contract — and train at
        # a different L than the coordinator's feasibility/crossover
        # checks keyed on.
        sparse_l, sparse_l_src = sparse_estep.resolve_layout_len(
            config.sparse_min_bucket_len, use_plans=False,
        )
        sparse_l_record = {"value": sparse_l, "source": sparse_l_src}
        pad = sparse_estep.pad_multiple_for(config.dense_precision)
        # Feasibility over EVERY shard of the GLOBAL plan (not just the
        # owned ones): the engine decision must be a function of the
        # plan alone so every rank count trains the same shards the
        # same way; a forced-sparse corpus whose shard shapes cannot
        # block fails HERE with the shapes named.
        bad = [
            (bb, ll)
            for st, en in plan.bounds
            for bb, ll, _ in corpus.shard(st, en).bucket_shapes(
                min_len=sparse_l, batch_cap=config.batch_size,
                pad_multiple=pad,
            )
            if sparse_estep.pick_block(
                bb, ll, config.num_topics, config.dense_precision
            ) is None
        ]
        if bad:
            raise ValueError(
                f"sparse E-step engine selected but shard bucket shapes "
                f"{bad} admit no VMEM-feasible doc block at precision "
                f"{config.dense_precision!r} (K={config.num_topics}); "
                "use the dense family for this corpus"
            )
        e_fn = sparse_estep.make_e_step_fn(precision=config.dense_precision)

        def shard_layout(sc):
            return sc.bucketed_layout(
                min_len=sparse_l, batch_cap=config.batch_size,
                pad_multiple=pad,
            ).batches
    else:
        def shard_layout(sc):
            return make_batches(
                sc, batch_size=config.batch_size,
                min_bucket_len=config.min_bucket_len,
                pad_multiple=8 * data_size,
            )

    with maybe_span("fit.batches", shards=len(shard_corpora)) as sp:
        shard_batches = {
            s: [
                Batch(b.word_idx, b.counts,
                      b.doc_index + plan.bounds[s][0], b.doc_mask)
                for b in shard_layout(sc)
            ]
            for s, sc in shard_corpora.items()
        }
        flat = [b for s in sorted(shard_batches) for b in shard_batches[s]]
        sp.annotate(**_batch_counts(flat, shard_corpora.values()))

    with maybe_span("fit.init", what="trainer"):
        trainer = LDATrainer(
            config,
            num_terms=num_terms,
            e_step_fn=e_fn,
            m_step_fn=m_fn,
            mesh=mesh,
            vocab_sharded=vocab_sharded,
            collective=coll,
            shard_plan=plan,
            shard_batches=shard_batches,
        )
    ll_path = os.path.join(out_dir, "likelihood.dat") if out_dir else None
    ckpt_path = (
        os.path.join(out_dir, "checkpoint.npz")
        if out_dir and config.checkpoint_every
        else None
    )
    rec = current_recorder()
    if rec is not None:
        # The journaled shard plan: enough to reconstruct the exact
        # split this run trained under ({"kind": "shard_plan"}).
        rec.journal_record(plan.record(coll.rank))
    ar0 = dict(coll.stats)
    try:
        result = trainer.fit(
            flat,
            corpus.num_docs,
            likelihood_file=ll_path,
            progress=progress,
            initial_log_beta=initial_log_beta,
            checkpoint_path=ckpt_path,
        )
    except PeerFailure:
        raise          # already relayed by whoever actually failed
    except BaseException as e:
        # Same library-level relay for mid-fit failures (a rank's OOM
        # or IO error): peers stuck in the next iteration's allreduce
        # see the key within one poll slice instead of the timeout.
        coll.fail(f"distributed fit rank {coll.rank}: {e!r}")
        raise
    result.plan["estep_engine"] = {"value": engine, "source": engine_src}
    if sparse_l_record is not None:
        result.plan["sparse_estep_l"] = sparse_l_record
    # Provenance mirrors resolve_em_shards' precedence: env beats
    # config beats the auto default.
    result.plan["em_shards"] = {
        "value": plan.num_shards,
        "source": (
            "env" if os.environ.get("ONI_ML_TPU_EM_SHARDS")
            else "config" if config.em_shards else "default"
        ),
    }
    d = coll.stats
    result.plan["allreduce"] = {
        "transport": coll.transport,
        # APPLIED precision — the Collective's own rule, so this
        # provenance can never disagree with what the data-plane ops
        # journaled (psum/local/1-process runs never compress).
        "precision": coll.applied_precision(
            os.environ.get("ONI_ML_TPU_ALLREDUCE_PRECISION", "")
            or config.allreduce_precision
        ),
        "nprocs": coll.num_processes,
        "ops": d["ops"] - ar0["ops"],
        "bytes_out": d["bytes_out"] - ar0["bytes_out"],
        "bytes_in": d["bytes_in"] - ar0["bytes_in"],
        "wall_s": round(d["wall_s"] - ar0["wall_s"], 6),
    }
    if num_terms != corpus.num_terms:
        result.log_beta = result.log_beta[:, : corpus.num_terms]
    if out_dir and save_final and _is_coordinator():
        with maybe_span("fit.save") as sp:
            sp.annotate(**result.save(out_dir, num_terms=corpus.num_terms,
                                      include_likelihood=False))
    return result
