"""Online (streaming) variational LDA — BASELINE.json config 5.

The reference engine is strictly batch: one day of netflow becomes one
corpus, EM runs to convergence, done (ml_ops.sh:80; SURVEY.md §2.8).  For
hourly micro-batches that design re-trains from scratch every hour.  This
module adds the streaming alternative: stochastic variational inference
(Hoffman, Blei, Bach, "Online Learning for Latent Dirichlet Allocation",
NIPS 2010 — see PAPERS.md), where each micro-batch performs one
natural-gradient step on a variational Dirichlet posterior lambda [K, V]
over the topics:

    rho_t   = (tau0 + t)^(-kappa)
    lambda <- (1 - rho_t) lambda + rho_t (eta + D/|S_t| * suff_stats_t)

The per-document local step is *identical math* to the batch E-step
(ops/estep.py): Hoffman's update uses exp(E_q[log beta]) everywhere the
batch algorithm uses beta, so we simply feed ``E_q[log beta]`` (digamma
form) to ``e_step`` — no duplicated inner loop, and the same Pallas/
sharded substitutions apply.

TPU notes: the whole update (E-step fixed point + scatter + blend) is one
jitted program per (B, L) shape; lambda lives on device across the stream
so each micro-batch moves only its own tokens over PCIe/ICI.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import OnlineLDAConfig
from ..io import Batch
from ..ops import estep
from ..ops.estep import e_log_dirichlet as expected_log_beta
from . import fused
from .lda import LDAResult


def save_stream_checkpoint(
    path: str,
    lam: np.ndarray,
    alpha: float,
    step: int,
    history: list[tuple[float, float]],
) -> None:
    """Atomic streaming checkpoint with SVI-native field names: `lam`
    (the variational Dirichlet posterior over topics — NOT a log beta),
    `step` (micro-batch count), `history` rows of (likelihood, rho).
    Early revisions smuggled these through the batch checkpoint's
    log_beta/em_iter/likelihoods fields; load_stream_checkpoint still
    reads that layout."""
    tmp = path + ".tmp.npz"  # savez appends nothing to an .npz name
    np.savez(
        tmp,
        lam=np.asarray(lam),
        alpha=np.float64(alpha),
        step=np.int64(step),
        history=np.asarray(history, np.float64).reshape(-1, 2),
    )
    os.replace(tmp, path)


def load_stream_checkpoint(path: str) -> dict:
    with np.load(path) as z:
        if "lam" in z.files:
            return {
                "lam": z["lam"],
                "alpha": float(z["alpha"]),
                "step": int(z["step"]),
                "history": [tuple(row) for row in z["history"]],
            }
        # Legacy layout (batch-checkpoint field names smuggling lambda).
        # A real batch EM checkpoint shares these field names AND the
        # (K, V) shape but holds log-probabilities (all <= 0), while a
        # variational lambda is strictly positive Dirichlet parameters —
        # reject it instead of streaming NaN topics out of digamma.
        lam = z["log_beta"]
        if not (lam > 0).all():
            raise ValueError(
                f"{path} is a batch EM checkpoint (log_beta has "
                "non-positive entries), not a streaming-LDA checkpoint; "
                "resume it with the batch trainer or remove it"
            )
        return {
            "lam": lam,
            "alpha": float(z["alpha"]),
            "step": int(z["em_iter"]),
            "history": [tuple(row) for row in z["likelihoods"]],
        }


@dataclass
class StreamStepInfo:
    step: int
    rho: float
    batch_docs: int
    # ELBO local term over the micro-batch.  Kept as a DEVICE scalar so the
    # streaming hot path never blocks on a host sync between micro-batches;
    # float(info.likelihood) materializes it on demand.
    likelihood: "jnp.ndarray"
    tokens: int

    @property
    def per_token_ll(self) -> float:
        return float(self.likelihood) / max(self.tokens, 1)


class OnlineLDATrainer:
    """Streaming natural-gradient LDA over padded micro-batches.

    ``total_docs`` is the population size D the stream is drawn from (for
    the reference pipelines: the expected number of active IPs in the
    window being modeled).  It scales each micro-batch's sufficient
    statistics to a full-corpus estimate; a too-small D under-weights new
    evidence but never destabilizes the update.

    With a ``mesh``, micro-batches shard over its `data` axis and the
    suff-stats psum over ICI (the shard_map'd E-step from
    oni_ml_tpu/parallel); lambda replicates.  Vocab sharding is a batch-
    only feature for now — the natural-gradient blend wants the full
    lambda row normalizer every step.  The ``e_step_fn`` hook still
    allows arbitrary substitution, exactly as in the batch trainer.
    """

    def __init__(
        self,
        config: OnlineLDAConfig,
        num_terms: int,
        total_docs: int,
        e_step_fn: Callable | None = None,
        mesh=None,
        checkpoint_path: str | None = None,
        collective=None,
        distributed: "bool | None" = None,
    ):
        self.config = config
        self.num_terms = num_terms
        self.total_docs = total_docs
        self.mesh = mesh
        self.checkpoint_path = checkpoint_path
        self.step_count = 0
        self.history: list[StreamStepInfo] = []
        dtype = jnp.dtype(config.compute_dtype)

        # Distributed streaming (parallel/allreduce.py): each rank runs
        # the local E-step on its contiguous row slice of EVERY
        # micro-batch and the suff-stats allreduce feeds the identical
        # natural-gradient blend on every rank — the host-local
        # restructure of the old global-mesh data sharding, which the
        # CPU runtime could not execute at all.  lambda stays
        # rank-identical (asserted by the multihost suite).
        if distributed is None:
            distributed = jax.process_count() > 1
        self._coll = None
        if distributed:
            from ..parallel.allreduce import get_collective
            from ..parallel.mesh import is_local_mesh

            if mesh is not None and not is_local_mesh(mesh):
                raise ValueError(
                    "distributed streaming LDA is host-local: the mesh "
                    "may span this process's devices only "
                    "(parallel.local_mesh())"
                )
            self._coll = (
                collective if collective is not None else get_collective()
            )

        if mesh is not None and e_step_fn is None:
            from ..parallel.mesh import MODEL_AXIS
            from ..parallel.sharded import make_data_parallel_e_step

            if mesh.shape[MODEL_AXIS] > 1:
                raise ValueError(
                    "online LDA supports data-parallel meshes only; "
                    f"got model axis {mesh.shape[MODEL_AXIS]}"
                )
            e_step_fn = make_data_parallel_e_step(mesh)

        # Hoffman's init: lambda ~ Gamma(100, 1/100) per entry.
        key = jax.random.PRNGKey(config.seed)
        self._lam = jax.random.gamma(
            key, 100.0, (config.num_topics, num_terms), dtype
        ) / 100.0
        self._alpha = jnp.asarray(config.alpha, dtype)
        if checkpoint_path is not None and os.path.exists(checkpoint_path):
            ckpt = load_stream_checkpoint(checkpoint_path)
            if ckpt["lam"].shape != self._lam.shape:
                raise ValueError(
                    f"checkpoint lambda shape {ckpt['lam'].shape} does "
                    f"not match ({config.num_topics}, {num_terms})"
                )
            self._lam = jnp.asarray(ckpt["lam"], dtype)
            self.step_count = ckpt["step"]
            self.history = [
                StreamStepInfo(step=i + 1, rho=rho, batch_docs=0,
                               likelihood=jnp.asarray(ll, dtype), tokens=0)
                for i, (ll, rho) in enumerate(ckpt["history"])
            ]
        if mesh is not None:
            from ..parallel.mesh import replicated

            self._lam = jax.device_put(self._lam, replicated(mesh))

        if config.dense_em not in ("auto", "on", "off"):
            raise ValueError(
                f"OnlineLDAConfig.dense_em={config.dense_em!r}: expected "
                "'auto', 'on', or 'off'"
            )
        if config.dense_em == "on" and (e_step_fn is not None
                                        or mesh is not None):
            # Fail at construction, not at the first step() call: a
            # misconfigured streaming job should die before startup.
            raise ValueError(
                "dense_em='on' needs the default single-process "
                "E-step (no mesh, no custom e_step_fn)"
            )
        self._custom_e_fn = e_step_fn is not None
        base = e_step_fn or estep.e_step
        self._e_fn = partial(
            base, var_max_iters=config.var_max_iters, var_tol=config.var_tol
        )
        # One jitted update per micro-batch shape: the dense-vs-sparse
        # choice and the scoped-VMEM compiler option both depend on B,
        # which is only known when the first batch of a shape arrives.
        # LRU-bounded (see _get_update): callers should bucket/pad
        # micro-batch shapes (io.make_batches does) — naturally ragged
        # streams would otherwise accumulate one compiled program per
        # distinct (B, L) without limit.
        self._updates: dict = {}

    # Max distinct (B, L) compiled updates kept resident.  io.make_batches
    # produces one B and a handful of power-of-two L buckets, so a real
    # deployment never evicts; the bound only protects long-running jobs
    # fed un-bucketed ragged micro-batches from unbounded compile-cache
    # growth (evicting the least-recently-used program costs a recompile
    # if that shape ever returns).
    _UPDATE_CACHE_MAX = 32

    def _use_dense(self, b: int) -> bool:
        from ..ops import dense_estep

        cfg = self.config
        # dense_em='on' with a mesh/custom e_fn is rejected in __init__.
        if cfg.dense_em == "off" or self._custom_e_fn or self.mesh is not None:
            return False
        feasible = dense_estep.pick_block(b, self.num_terms,
                                          cfg.num_topics) is not None
        if cfg.dense_em == "on":
            if not feasible:
                raise ValueError(
                    f"dense_em forced but B={b}, V={self.num_terms}, "
                    f"K={cfg.num_topics} has no VMEM-feasible doc block"
                )
            return True
        return feasible and jax.default_backend() == "tpu"

    def _make_e_fn(self, b: int):
        """Per-batch-shape E-step choice: the dense MXU path when
        feasible (ops/dense_estep.py — one densify scatter per
        micro-batch instead of a beta-slab gather per fixed-point
        iteration), else the configured sparse/sharded e_fn.  Returns
        (e_fn, compiler_options)."""
        from ..ops import dense_estep

        cfg = self.config
        if not self._use_dense(b):
            return self._e_fn, None
        v, k = self.num_terms, cfg.num_topics
        _, wmajor, compiler_options = dense_estep.plan(b, v, k)

        def e_fn(elog_beta, alpha, word_idx, counts, doc_mask):
            dense = dense_estep.densify(word_idx, counts, v)
            if wmajor:
                dense = dense.T
            return dense_estep.e_step_dense(
                elog_beta, alpha, dense, doc_mask,
                cfg.var_max_iters, cfg.var_tol,
                interpret=jax.default_backend() != "tpu",
                wmajor=wmajor,
            )

        return e_fn, compiler_options

    def _cache_get(self, key):
        got = self._updates.pop(key, None)
        if got is not None:
            self._updates[key] = got      # re-insert: most recently used
        return got

    def _cache_update(self, key, jitted):
        while len(self._updates) >= self._UPDATE_CACHE_MAX:
            self._updates.pop(next(iter(self._updates)))
        self._updates[key] = jitted
        return jitted

    def _get_update(self, b: int, l: int):
        key = (b, l)
        got = self._cache_get(key)
        if got is not None:
            return got
        cfg = self.config
        total_docs = self.total_docs
        e_fn, compiler_options = self._make_e_fn(b)

        def update(lam, rho, word_idx, counts, doc_mask):
            res = e_fn(expected_log_beta(lam), self._alpha, word_idx,
                       counts, doc_mask)
            batch_docs = jnp.maximum(doc_mask.sum(), 1.0)
            lam_hat = cfg.eta + (total_docs / batch_docs) * res.suff_stats.T
            new_lam = (1.0 - rho) * lam + rho * lam_hat
            return new_lam, res.likelihood, res.gamma

        return self._cache_update(
            key, jax.jit(update, donate_argnums=(0,),
                         compiler_options=compiler_options)
        )

    def _get_update_many(self, n: int, b: int, l: int):
        """The chunked streaming program: `n` same-shape micro-batches
        as ONE jitted `lax.scan` — lambda never leaves the device
        between the scanned natural-gradient steps, and the rho
        schedule advances in-scan from the traced start step.  This is
        models/fused.py's chunking applied to SVI: one dispatch per
        chunk instead of one per step (per-dispatch cost, not measured
        on the current machine)."""
        key = ("many", n, b, l)
        got = self._cache_get(key)
        if got is not None:
            return got
        cfg = self.config
        total_docs = self.total_docs
        e_fn, compiler_options = self._make_e_fn(b)
        tau0, kappa, eta = cfg.tau0, cfg.kappa, cfg.eta

        def update_many(lam, t0, word_idx, counts, doc_mask):
            def body(carry, xs):
                lam, t = carry
                w, c, m = xs
                # step()'s host-side rho, evaluated on device (f32 pow
                # instead of float64 — the schedules agree to ~1e-7
                # relative).  t stays f32 bookkeeping whatever the
                # batch compute_dtype: in bf16 t + 1.0 rounds back to
                # t past 256 and the schedule would freeze.
                rho = ((tau0 + t) ** (-kappa)).astype(lam.dtype)
                res = e_fn(expected_log_beta(lam), self._alpha, w, c, m)
                batch_docs = jnp.maximum(m.sum(), 1.0)
                lam_hat = (
                    eta + (total_docs / batch_docs) * res.suff_stats.T
                )
                lam = (1.0 - rho) * lam + rho * lam_hat
                return (lam, t + 1.0), res.likelihood

            (lam, _), lls = jax.lax.scan(
                body, (lam, t0), (word_idx, counts, doc_mask)
            )
            return lam, lls

        return self._cache_update(
            key, jax.jit(update_many, donate_argnums=(0,),
                         compiler_options=compiler_options)
        )

    @classmethod
    def from_topic_probs(
        cls,
        config: OnlineLDAConfig,
        topic_probs: np.ndarray,
        total_docs: int,
        pseudo_tokens: float = 1e4,
        num_terms: "int | None" = None,
        **kwargs,
    ) -> "OnlineLDATrainer":
        """Seed the stream from an EXISTING model instead of Hoffman's
        random init: `topic_probs` is the [V, K] p(word|topic) matrix
        the batch pipeline publishes (word_results.csv columns, each
        topic summing to 1 over words).  lambda[k, v] = eta +
        pseudo_tokens * p[v, k], so E_q[beta] ≈ p for pseudo_tokens >>
        eta*V and the first natural-gradient steps REFINE the batch
        topics rather than washing them out (rho at t=0 is already
        < tau0^-kappa).  This is the serving refresh loop's entry point
        (oni_ml_tpu/serving/refresh.py): day artifacts -> streaming
        updates without a retrain.

        `num_terms` > V seeds a GROWN vocabulary (continuous
        ingestion: day N's window holds words day N−1 never saw —
        first-seen word ids are stable, so the new words are exactly
        rows V..num_terms-1): the new lambda rows start at the
        symmetric prior eta alone (p's contribution is zero — the old
        model had no opinion about them), so E_q[beta] for new words
        begins at the prior and the stream's evidence grows them.
        Shrinking (num_terms < V) is refused: stable first-seen ids
        mean a smaller vocabulary is a mixed id space, not growth."""
        p = np.asarray(topic_probs, np.float64)
        if p.ndim != 2 or p.shape[1] != config.num_topics:
            raise ValueError(
                f"topic_probs must be [V, {config.num_topics}], got "
                f"{p.shape}"
            )
        if not np.isfinite(p).all() or (p < 0).any():
            raise ValueError("topic_probs must be finite and nonnegative")
        if num_terms is None:
            num_terms = p.shape[0]
        if num_terms < p.shape[0]:
            raise ValueError(
                f"num_terms={num_terms} would SHRINK the vocabulary "
                f"(topic_probs covers {p.shape[0]} words): window word "
                "ids are first-seen-stable, so pass the grown vocab "
                "size or slice topic_probs explicitly"
            )
        if num_terms > p.shape[0]:
            p = np.concatenate(
                [p, np.zeros((num_terms - p.shape[0],
                              config.num_topics), np.float64)],
                axis=0,
            )
        trainer = cls(config, num_terms=num_terms,
                      total_docs=total_docs, **kwargs)
        if trainer.step_count > 0:
            # A checkpoint_path kwarg restored an in-progress stream:
            # the RESUME wins — overwriting lambda with the seed while
            # keeping the checkpoint's step_count would put the rho
            # schedule at step N over reset topics, a silently
            # inconsistent state.
            return trainer
        dtype = jnp.dtype(config.compute_dtype)
        lam = jnp.asarray(config.eta + pseudo_tokens * p.T, dtype)
        if trainer.mesh is not None:
            from ..parallel.mesh import replicated

            lam = jax.device_put(lam, replicated(trainer.mesh))
        trainer._lam = lam
        return trainer

    @property
    def lam(self) -> jnp.ndarray:
        return self._lam

    def _check_data_divisible(self, ndocs: int) -> None:
        from ..parallel.mesh import DATA_AXIS

        data_size = self.mesh.shape[DATA_AXIS]
        if ndocs % data_size:
            raise ValueError(
                f"micro-batch of {ndocs} docs not divisible by data "
                f"axis {data_size}"
            )

    def _put_batch(self, batch: Batch):
        """Device placement for one micro-batch (data-axis sharded when a
        mesh is active, plain transfer otherwise)."""
        dtype = jnp.dtype(self.config.compute_dtype)
        arrays = (
            jnp.asarray(batch.word_idx),
            jnp.asarray(batch.counts, dtype),
            jnp.asarray(batch.doc_mask, dtype),
        )
        if self.mesh is None:
            return arrays
        from ..parallel.mesh import batch_sharding

        self._check_data_divisible(batch.word_idx.shape[0])
        sh = batch_sharding(self.mesh)
        return tuple(jax.device_put(a, sh) for a in arrays)

    def _get_update_dist(self, b: int, l: int):
        """The distributed split of `_get_update`: a jitted local
        partial program (this rank's row slice -> suff-stats + ELBO)
        and a jitted blend program consuming the REDUCED stats — the
        explicit allreduce runs on the host between them, so the
        natural-gradient update is computed identically on every rank
        from identical inputs."""
        key = ("dist", b, l)
        got = self._cache_get(key)
        if got is not None:
            return got
        cfg = self.config
        total_docs = self.total_docs
        e_fn, compiler_options = self._make_e_fn(b)

        def local_part(lam, word_idx, counts, doc_mask):
            res = e_fn(expected_log_beta(lam), self._alpha, word_idx,
                       counts, doc_mask)
            return res.suff_stats, res.likelihood

        def blend(lam, rho, ss, batch_docs):
            lam_hat = cfg.eta + (total_docs / batch_docs) * ss.T
            return (1.0 - rho) * lam + rho * lam_hat

        pair = (
            jax.jit(local_part, compiler_options=compiler_options),
            jax.jit(blend, donate_argnums=(0,)),
        )
        return self._cache_update(key, pair)

    def _step_distributed(self, batch: Batch) -> StreamStepInfo:
        """One update with the micro-batch row-split across ranks and
        the suff-stats crossing processes through the collective.
        `batch_docs` stays the GLOBAL real-doc count (each rank sees
        the full batch host-side; only the device work splits), so the
        update equals the single-process step up to reduction order."""
        from ..parallel.allreduce import tree_combine

        cfg = self.config
        coll = self._coll
        p, r = coll.num_processes, coll.rank
        b, l = batch.word_idx.shape
        if b % p:
            raise ValueError(
                f"micro-batch of {b} docs not divisible by {p} "
                "processes (make_batches pad_multiple must cover the "
                "process count)"
            )
        t = self.step_count
        rho = float((cfg.tau0 + t) ** (-cfg.kappa))
        dtype = jnp.dtype(cfg.compute_dtype)
        lo, hi = r * b // p, (r + 1) * b // p
        if self.mesh is not None:
            # The PER-RANK slice is what the host-local mesh shards.
            self._check_data_divisible(hi - lo)
        part_prog, blend_prog = self._get_update_dist(hi - lo, l)
        ss, ll = part_prog(
            self._lam,
            jnp.asarray(batch.word_idx[lo:hi]),
            jnp.asarray(batch.counts[lo:hi], dtype),
            jnp.asarray(batch.doc_mask[lo:hi], dtype),
        )
        # precision pinned: the streaming lambda-blend parity contract
        # (rank-count-invariant lambda BYTES) would not survive a
        # bf16-compressed wire; the env knob targets the batch
        # suff-stats reduce, not this path.
        reduced = tree_combine(coll.allgather_arrays(
            {"suff_stats": np.asarray(ss), "likelihood": np.asarray(ll)},
            f"svi{t}", precision="f32",
        ))
        self._lam = blend_prog(
            self._lam,
            jnp.asarray(rho, dtype),
            jnp.asarray(reduced["suff_stats"], dtype),
            jnp.asarray(max(float(batch.doc_mask.sum()), 1.0), dtype),
        )
        self.step_count += 1
        info = StreamStepInfo(
            step=self.step_count,
            rho=rho,
            batch_docs=int(batch.doc_mask.sum()),
            likelihood=jnp.asarray(reduced["likelihood"], dtype),
            tokens=int(batch.counts.sum()),
        )
        self.history.append(info)
        self._maybe_stream_checkpoint(prev_count=self.step_count - 1)
        return info

    def step(self, batch: Batch) -> StreamStepInfo:
        """One natural-gradient update from one micro-batch."""
        if self._coll is not None and self._coll.num_processes > 1:
            return self._step_distributed(batch)
        cfg = self.config
        t = self.step_count
        rho = float((cfg.tau0 + t) ** (-cfg.kappa))
        dtype = jnp.dtype(cfg.compute_dtype)
        widx, cnts, mask = self._put_batch(batch)
        update = self._get_update(widx.shape[0], widx.shape[1])
        from ..telemetry.spans import current_recorder

        if current_recorder() is not None:
            # Roofline harvest of the refresh-loop's natural-gradient
            # program, once per process, BEFORE the dispatch below
            # donates self._lam (lowering only reads shapes).
            from ..telemetry import roofline

            roofline.ensure_harvested(
                "serve.refresh_step", update, self._lam,
                jnp.asarray(rho, dtype), widx, cnts, mask,
                shape=f"b{widx.shape[0]}.l{widx.shape[1]}",
            )
        self._lam, ll, _ = update(
            self._lam, jnp.asarray(rho, dtype), widx, cnts, mask
        )
        self.step_count += 1
        info = StreamStepInfo(
            step=self.step_count,
            rho=rho,
            batch_docs=int(batch.doc_mask.sum()),
            likelihood=ll,  # device scalar; no sync on the hot path
            tokens=int(batch.counts.sum()),
        )
        self.history.append(info)
        self._maybe_stream_checkpoint(prev_count=self.step_count - 1)
        return info

    def _maybe_stream_checkpoint(self, prev_count: int) -> None:
        """Checkpoint when a checkpoint_every boundary was crossed since
        `prev_count` (chunked steps cross it mid-chunk; only the
        end-of-chunk lambda is materialized, so the checkpoint lands on
        the first step call after the boundary)."""
        cfg = self.config
        every = cfg.checkpoint_every
        if not (self.checkpoint_path and every):
            return
        if (self.step_count // every) <= (prev_count // every):
            return
        from .lda import _is_coordinator

        # _to_host is collective on multi-host meshes
        # (process_allgather) — every process must reach it; only
        # the coordinator writes.
        lam_host = self._to_host(self._lam)
        if _is_coordinator():
            save_stream_checkpoint(
                self.checkpoint_path,
                lam_host,
                float(self._alpha),
                self.step_count,
                [(float(h.likelihood), h.rho) for h in self.history],
            )

    def _put_stack(self, run: Sequence[Batch]):
        """Device placement for a stacked [N, B, ...] run of same-shape
        micro-batches (docs axis 1 sharded over `data` on a mesh)."""
        dtype = jnp.dtype(self.config.compute_dtype)
        w, _ = fused.stack_run([b.word_idx for b in run])
        c, _ = fused.stack_run([b.counts for b in run], dtype)
        m, _ = fused.stack_run([b.doc_mask for b in run], dtype)
        if self.mesh is None:
            return jnp.asarray(w), jnp.asarray(c), jnp.asarray(m)
        from ..parallel.mesh import stacked_batch_sharding

        self._check_data_divisible(w.shape[1])
        sh = stacked_batch_sharding(self.mesh)
        return tuple(jax.device_put(a, sh) for a in (w, c, m))

    def _run_chunk(self, run: Sequence[Batch]) -> list[StreamStepInfo]:
        """Execute a same-shape run of micro-batches as one scan chunk."""
        cfg = self.config
        w, c, m = self._put_stack(run)
        update = self._get_update_many(len(run), w.shape[1], w.shape[2])
        prev = self.step_count
        t0 = jnp.asarray(float(prev), jnp.float32)  # f32 bookkeeping
        self._lam, lls = update(self._lam, t0, w, c, m)
        infos = []
        for i, b in enumerate(run):
            rho = float((cfg.tau0 + self.step_count) ** (-cfg.kappa))
            self.step_count += 1
            info = StreamStepInfo(
                step=self.step_count,
                rho=rho,
                batch_docs=int(b.doc_mask.sum()),
                likelihood=lls[i],  # device scalar; no sync here
                tokens=int(b.counts.sum()),
            )
            self.history.append(info)
            infos.append(info)
        self._maybe_stream_checkpoint(prev_count=prev)
        return infos

    def step_many(
        self, batches: Sequence[Batch], chunk: int = 16
    ) -> list[StreamStepInfo]:
        """Natural-gradient updates over `batches` IN ORDER, executing
        each contiguous same-shape run as device-resident scans (one
        dispatch per scan — see _get_update_many).  Runs split into
        power-of-two scan lengths capped at `chunk` (a 7-batch run =
        scan4 + scan2 + step): any run of >= 2 amortizes dispatches,
        while the number of compiled scan programs stays bounded at
        log2(chunk) per micro-batch shape — a 7-batch epoch reuses the
        same two programs every epoch.  Numerically it is step()
        applied to each micro-batch in sequence (modulo the rho
        schedule's f32 evaluation); only the dispatch granularity and
        checkpoint timing coarsen."""
        if self._coll is not None and self._coll.num_processes > 1:
            # Chunked device-resident scans cannot host-reduce between
            # steps; distributed streams take the per-step path (the
            # allreduce IS the per-step host boundary).
            return [self.step(b) for b in batches]
        if chunk < 2:
            return [self.step(b) for b in batches]
        infos: list[StreamStepInfo] = []
        i, n = 0, len(batches)
        while i < n:
            shape = batches[i].word_idx.shape
            j = i
            while j < n and batches[j].word_idx.shape == shape:
                j += 1
            while i < j:
                c = min(j - i, chunk)
                c = 1 << (c.bit_length() - 1)   # largest power of two <= c
                if c >= 2:
                    infos.extend(self._run_chunk(batches[i:i + c]))
                else:
                    infos.append(self.step(batches[i]))
                i += c
        return infos

    def fit_stream(
        self,
        batches: Iterable[Batch],
        progress: Callable[[StreamStepInfo], None] | None = None,
        chunk: int = 16,
    ) -> "OnlineLDATrainer":
        """Consume a micro-batch stream, buffering contiguous same-shape
        runs into step_many chunks (progress fires per micro-batch, but
        only after its chunk completes)."""
        buf: list[Batch] = []

        def flush():
            infos = self.step_many(buf, chunk=chunk)
            buf.clear()
            if progress:
                for info in infos:
                    progress(info)

        for b in batches:
            if buf and (
                b.word_idx.shape != buf[0].word_idx.shape
                or len(buf) >= chunk
            ):
                flush()
            buf.append(b)
        flush()
        return self

    # -- model extraction ---------------------------------------------------

    def _to_host(self, x) -> np.ndarray:
        from .lda import to_host

        return to_host(x, self.mesh)

    def log_beta(self) -> np.ndarray:
        """Point-estimate topics: log E_q[beta] = log(lambda / sum lambda),
        with the batch engine's LOG_ZERO floor so downstream file contracts
        (final.beta, word_results.csv) behave identically."""
        lam = self._to_host(self._lam)
        beta = lam / lam.sum(-1, keepdims=True)
        return np.where(beta > 0, np.log(np.maximum(beta, 1e-300)),
                        estep.LOG_ZERO)

    def held_out_per_token_ll(self, batches: Sequence[Batch]) -> float:
        """Held-out per-token log-likelihood (document completion,
        models/evaluate.py) of unseen docs under the current topics —
        the quality number for streaming runs, where training ELBO per
        micro-batch (history) is too noisy to compare configurations."""
        from .evaluate import held_out_per_token_ll

        return held_out_per_token_ll(
            self.log_beta(), float(self._alpha), batches,
            var_max_iters=self.config.var_max_iters,
            var_tol=self.config.var_tol,
        )

    def infer_gamma(self, batches: Sequence[Batch], num_docs: int) -> np.ndarray:
        """Final inference pass: doc-topic posteriors for ``num_docs`` docs
        under the current (frozen) topics — produces final.gamma for the
        scoring stage just like the batch trainer's last E-step.  Runs
        through the same (possibly shard_map'd) E-step as training."""
        cfg = self.config
        # One jitted wrapper for the trainer's lifetime: the serving
        # refresh loop calls infer_gamma every few batches, and a fresh
        # jax.jit per call would pay wrapper-cache misses on the scoring
        # worker thread instead of hitting the (B, L)-shape cache.
        e_fn = getattr(self, "_infer_e_fn", None)
        if e_fn is None:
            e_fn = self._infer_e_fn = jax.jit(self._e_fn)
        log_b = expected_log_beta(self._lam)
        gamma_out = np.zeros((num_docs, cfg.num_topics), np.float64)
        for b in batches:
            widx, cnts, mask = self._put_batch(b)
            res = e_fn(log_b, self._alpha, widx, cnts, mask)
            g = self._to_host(res.gamma)
            sel = b.doc_mask == 1
            gamma_out[b.doc_index[sel]] = g[sel]
        return gamma_out

    def result(
        self, batches: Sequence[Batch] | None = None, num_docs: int = 0
    ) -> LDAResult:
        gamma = (
            self.infer_gamma(batches, num_docs)
            if batches is not None
            else np.zeros((0, self.config.num_topics))
        )
        # likelihood.dat contract: column 2 is the relative change between
        # consecutive entries (README.md:119), here between micro-batch
        # ELBOs — NOT the learning rate, which lives in history[i].rho.
        raw = [float(h.likelihood) for h in self.history]
        lls = [
            (ll, abs((raw[i - 1] - ll) / raw[i - 1]) if i else 1.0)
            for i, ll in enumerate(raw)
        ]
        return LDAResult(
            log_beta=self.log_beta(),
            gamma=gamma,
            alpha=float(self._alpha),
            likelihoods=lls,
            em_iters=self.step_count,
        )


def train_corpus_online(
    corpus,
    config: OnlineLDAConfig,
    out_dir: str | None = None,
    epochs: int = 1,
    progress: Callable[[StreamStepInfo], None] | None = None,
    mesh=None,
) -> LDAResult:
    """Stream an in-memory corpus through the online trainer, micro-batch
    by micro-batch, then write the reference-format outputs.

    This is the drop-in path for `ml_ops --online`: the day's corpus is
    consumed as a stream (each bucketed batch = one micro-batch), which on
    hourly data extends naturally to feeding each hour's batches as they
    arrive without retraining from scratch.
    """
    from ..io import make_batches

    # Distributed streams row-split every micro-batch across ranks, so
    # the batch axis must divide by the process count AND each rank's
    # row slice must still divide by the (host-local) mesh's data axis
    # — i.e. pad to a multiple of base_pad * nproc, not merely their
    # rounding (ceil(base/nproc)*nproc would hand shard_map an uneven
    # per-rank slice on tail batches).
    nproc = jax.process_count()
    base_pad = mesh.shape["data"] if mesh is not None else 8
    pad = base_pad if nproc <= 1 else base_pad * nproc
    batches = make_batches(
        corpus, batch_size=config.batch_size,
        min_bucket_len=config.min_bucket_len,
        pad_multiple=pad,
    )
    ckpt_path = (
        os.path.join(out_dir, "checkpoint.npz")
        if out_dir and config.checkpoint_every
        else None
    )
    trainer = OnlineLDATrainer(
        config,
        num_terms=corpus.num_terms,
        total_docs=corpus.num_docs,
        mesh=mesh,
        checkpoint_path=ckpt_path,
    )
    # The epoch-shuffled stream order is deterministic in the seed, so a
    # resumed run fast-forwards past the first `step_count` micro-batches.
    done = trainer.step_count
    rng = np.random.default_rng(config.seed)
    for _ in range(epochs):
        # Stable-group the epoch's shuffled order by micro-batch shape
        # (still deterministic in the seed, still a valid SVI sampling
        # order): same-shape runs then stream through fit_stream's
        # chunked device-resident scans instead of per-step dispatches.
        order = sorted(
            rng.permutation(len(batches)),
            key=lambda i: batches[i].word_idx.shape,
        )
        skip, done = min(done, len(order)), max(done - len(order), 0)
        trainer.fit_stream(
            (batches[i] for i in order[skip:]), progress=progress
        )
    result = trainer.result(batches, corpus.num_docs)
    from .lda import _is_coordinator

    if ckpt_path and os.path.exists(ckpt_path) and _is_coordinator():
        os.remove(ckpt_path)
    if out_dir and _is_coordinator():
        # Multi-host: result is identical on every rank (collective
        # gathers), but the shared day dir has exactly one writer.
        result.save(out_dir, num_terms=corpus.num_terms)
    return result
