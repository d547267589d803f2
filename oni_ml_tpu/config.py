"""Typed configuration — the single source of truth for every knob.

The reference smears its constants across 6+ files (TOPIC_COUNT in
ml_ops.sh:26, k=20 in lda_pre.py:11, hardcoded 20-wide fallbacks in
flow_post_lda.scala:228-231 / dns_post_lda.scala:313-316, alpha=2.5 on the
lda CLI at ml_ops.sh:80, DUPFACTOR at ml_ops.sh:31).  Here every one of
those lives in exactly one dataclass field, and the scorer fallbacks are
*derived* from num_topics instead of being 20 literal floats.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field

# The values of LDAConfig.estep_engine.
ESTEP_ENGINES = ("auto", "dense", "compact", "sparse", "xla", "pallas")


@dataclass(frozen=True)
class LDAConfig:
    """Variational-EM LDA hyperparameters.

    Defaults mirror the reference invocation ``lda est 2.5 20 settings.txt``
    (ml_ops.sh:80) and Blei lda-c's stock settings.txt (var max iter 20,
    var convergence 1e-6, em max iter 100, em convergence 1e-4, alpha
    estimated).
    """

    num_topics: int = 20
    alpha_init: float = 2.5
    estimate_alpha: bool = True
    # Cap on the per-M-step alpha-Newton (lda-c's MAX_ALPHA_ITER).  A
    # scalar while_loop is the TPU's worst shape; caps <= 16 take
    # update_alpha's UNROLLED convergence-masked lowering (one fused
    # scalar chain; what the dynamic-trip loop costs on the chip is an
    # unverified lead, ROADMAP A6), and warm mid-EM Newton converges in
    # a handful of trips so the same |df| exit fires either way.  The
    # default is the bench cap of 8: cap-8-vs-cap-100 training
    # equivalence is pinned in tests/test_lda.py; the lda-c drop-in CLI
    # (runner/lda_cli.py) pins the reference's 100-trip while_loop for
    # exact lda-c semantics.
    alpha_max_iters: int = 8
    em_max_iters: int = 100
    em_tol: float = 1e-4
    var_max_iters: int = 20
    # Inner fixed-point stop (shared rule, ops/stop.py): exit when the
    # per-doc mean |delta gamma| drops under var_tol RELATIVE to the
    # doc's mean gamma (alpha + N_d/K, an exact iteration invariant),
    # OR on gated stagnation — once already near convergence
    # (< ops.stop.STALL_GATE) and the delta stops shrinking, the
    # iterate has reached its arithmetic's noise floor (on TPU the
    # MXU's bf16-truncated matmul inputs put a ~2^-8 relative floor
    # under the iterates, below which they jitter instead of
    # contracting) and more iterations cannot improve gamma (not where
    # the products keep float32 accuracy, the day after analyst feedback
    # on any store: there the reference's rule alone, since the exit
    # stopped slowly converging one-word documents short of their fixed
    # point; dense_estep._stall_exit).  At 1e-6
    # the relative test is still far tighter than lda-c's per-doc
    # relative-likelihood stop at its stock 1e-6 (the ELBO is quadratic
    # in delta-gamma near the fixed point); an absolute 1e-6 against
    # typical gamma magnitudes sits below f32 resolution and silently
    # turns var_max_iters into a trip count.
    var_tol: float = 1e-6
    # Device batching: documents per E-step batch (padded, bucketed by length).
    batch_size: int = 1024
    # Length buckets are powers of two starting here; docs pad up to the
    # nearest bucket, which bounds the number of distinct compiled shapes.
    min_bucket_len: int = 16
    # Accumulate suff-stats / likelihood in f32 even if phi math runs lower.
    compute_dtype: str = "float32"
    seed: int = 0
    # Checkpoint every N EM iterations (0 = disabled).
    checkpoint_every: int = 0
    # Run up to this many EM iterations per device program (models/fused.py):
    # the convergence check happens on device and the host syncs only at
    # chunk boundaries.  0 or 1 falls back to one dispatch per iteration.
    # The default of 128 amortizes the per-dispatch cost, not measured
    # on the current machine (ROADMAP A1 re-runs the sweep that chose
    # it) — and the device while_loop exits the moment
    # |dll/ll| < em_tol, so a chunk larger than the
    # iterations-to-convergence costs THROUGHPUT nothing.
    #
    # The OBSERVABILITY tradeoff: everything host-visible —
    # likelihood.dat streaming, progress callbacks, the run journal's
    # em_ll points, checkpointing, and the authoritative float64
    # convergence check — lives at dispatch boundaries.  With
    # em_max_iters=100 and checkpoint_every=0, chunk=128 makes an
    # ENTIRE production fit one device dispatch: a crash loses every
    # likelihood line and a multi-hour run is opaque until it returns.
    # That is why host_sync_every below now DEFAULTS ON (16): the sync
    # cadence is bounded independently of the chunk size, so raising
    # fused_em_chunk can never again silently collapse crash-safety and
    # progress to end-of-run.  Raise fused_em_chunk freely; lower
    # host_sync_every only with the per-dispatch cost in mind.
    #
    # Both knobs resolve through the measured-plan cache
    # (oni_ml_tpu/plans) when left at these defaults: a recorded sweep
    # for this backend+shape wins over the default, and an
    # explicitly-set config value wins over both (source recorded per
    # run).
    fused_em_chunk: int = 128
    # Upper bound on EM iterations between HOST syncs in the fused
    # driver, independent of fused_em_chunk: each dispatch runs at most
    # min(fused_em_chunk, host_sync_every) iterations, so likelihood.dat
    # lines stream, progress fires, and the telemetry journal gets its
    # em_ll points at least that often even when checkpointing is off.
    # The chunk program is compiled once at fused_em_chunk and driven
    # with a dynamic step count, so tightening this costs only the
    # extra dispatches (per-dispatch cost, not measured on the current
    # machine), no recompiles.  Default 16: one dispatch per 16 EM
    # iterations buys a bounded-loss likelihood stream; 0 = sync every
    # fused_em_chunk iterations (maximum throughput, coarsest
    # observability — a whole fit can be one dispatch).
    host_sync_every: int = 16
    # Device-byte ceiling (per device) for the densified corpus under
    # estep_engine="auto".  None (the default: not stated) follows the
    # device: three quarters of its own memory limit (`memory_stats()`'s
    # `bytes_limit`), or 2 GiB where the backend reports none (the CPU) --
    # models/lda.py dense_budget.  A stated value is obeyed as it is.
    dense_hbm_budget: "int | None" = None
    # Warm-start each EM iteration's variational fixed point from the
    # previous iteration's gamma instead of the reference's fresh
    # alpha + N_d/K init (every in-package engine: XLA, Pallas, dense,
    # and the sharded wrappers; a user-supplied custom e_step_fn stays
    # fresh).  Reaches the same optimum —
    # measured: identical EM iteration count and final likelihood to
    # ~1e-6 relative on a structured 60k-doc corpus, ~5-20% faster;
    # per-iteration likelihood trajectory pinned to the fresh-start run
    # within 1e-3 relative and the final state to 1e-5
    # (tests/test_dense_estep.py::test_fused_warm_start_matches_fresh_
    # trajectory).  Default ON; mid-run likelihood.dat values can differ
    # from fresh-start lda-c semantics in late decimals, so the lda-c
    # drop-in CLI (runner/lda_cli.py) and anyone needing bit-parity pin
    # this False.
    warm_start_gamma: bool = True
    # Storage dtype for the dense fixed-point matmul OPERANDS: "f32"
    # (default) or "bf16".  Under XLA's DEFAULT matmul precision on
    # current single-pass-bf16-MXU TPUs (measured on v5e) this changes
    # NO results — that default already truncates f32 MXU inputs to
    # bf16 (accumulation stays f32) — it only stores the [W, BB]-sized
    # operands half-width in VMEM, measured ~10% off the E-step at the
    # headline shape.  The equivalence does NOT survive a process-wide
    # jax.default_matmul_precision("highest"/"float32") override or a
    # hardware/XLA default change; ops/dense_estep.plan() checks the
    # active default and refuses bf16 when it isn't DEFAULT.  On CPU
    # backends (tests, interpret mode) f32 matmuls are exact, so "bf16"
    # there emulates the TPU's input truncation instead.  The
    # suff-stats / ELBO tail pass always runs full-width off the
    # converged gamma.  bf16 mode additionally STORES the densified
    # corpus in the narrowest exact rung of a ladder
    # (ops/dense_estep.corpus_dtype): bf16 where every densified cell
    # is <= 256 (bf16's 8 significand bits), int16 where every cell is
    # <= 32,767 (the day after analyst feedback: a DUPFACTOR=1000 row
    # makes cells of 1,000 and more), f32 otherwise — both 2-byte
    # stores halving the corpus' device memory and per-iteration HBM
    # streaming, the count promoted to f32 where the kernels use it:
    # the bf16 store with bit-identical results.  The products are
    # keyed to the day's cells, not to the store: wherever a densified
    # cell passes 256, at either precision and on any store, they keep
    # f32 accuracy (a document that one word of 1,000 counts dominates
    # amplifies a bf16 rounding in the products by the sweeps): the
    # kernels' fixed point from exact bf16 pieces packed into the MXU's
    # contraction depth, the rest at HIGHEST
    # (ops/dense_estep._sweep_products, _products, accurate_products).
    # The gate (ops/dense_estep.corpus_store_dtype, span `fit.plan.gate`,
    # which says the `rung` and whether the products are `accurate`) is
    # exact and reads only what can change its answer: at "f32" the
    # store is f32 and the largest count of the fit's Corpus decides
    # (one entry a (document, word) cell), or where the batches come
    # from no Corpus the ladder's first rung over them; at "bf16" the
    # largest raw count (over 32,767: f32; over 256: the int16 rung at
    # best), then every
    # document's sum (all within the rung's maximum: decided), then, of
    # the documents over it, the per-(doc, word) sums of those where a
    # word repeats (one where every word is distinct has cells equal to
    # its tokens).  The plan runs the gate BEFORE its full-width budget
    # test and budgets the dense corpus at the store's itemsize, so a
    # day that fits the budget only at 2 B a cell is full-width dense;
    # the densify then fills the 2-byte stack a batch at a time
    # (models/fused.densify_stack), holding one batch's float32 block,
    # never a float32 copy of a group (PERF.md).
    dense_precision: str = "f32"
    # Which E-step the batch trainer runs: the one selector of kernel and
    # family, read by models/lda.py `estep_engine` (ESTEP_ENGINES above).
    #   "auto": the measured choice.  On a TPU the dense-vs-sparse
    #     crossover persisted in the plan cache (sparse_estep.
    #     engine_crossover: measured once per backend+shape, source "plan"
    #     on run 2) picks the family; within the dense family
    #     LDATrainer._plan_estep takes full-width dense under the HBM
    #     budget and the VMEM gates, else compact-vocab dense (each batch
    #     remapped onto its own Wc-wide vocabulary slice), else token
    #     lists through estep.e_step's own preference order.  Off the TPU:
    #     token lists (XLA on the CPU).
    #   "dense": full-width dense forced; a width with no VMEM-feasible
    #     doc block is rescued through compact-vocab dense, else refused.
    #   "compact": compact-vocab dense forced (single-process, stock
    #     E-step).
    #   "sparse": the fused sparse bucketed Pallas engine forced
    #     (ops/sparse_estep.py: corpus packed by Corpus.bucketed_layout,
    #     K×L work per doc instead of K×V); single-process per rank, so a
    #     mesh refuses it.
    #   "xla" / "pallas": token lists through that estep.e_step backend.
    # The dense layout is no option: W-major ([W, B], the small-K output
    # axis padding to 8 sublanes, not 128 lanes) wherever
    # dense_estep.pick_block_w admits every per-shard batch shape, else
    # row-major [B, W].
    estep_engine: str = "auto"
    # Minimum packed tile length for the sparse engine's bucketed
    # layout (Corpus.bucketed_layout min_len): buckets pad up to
    # power-of-two lengths floored here.  128 = the Pallas lane tile,
    # so [K, BB, L] slab blocks never pad lanes; resolves through the
    # plan cache (knob "sparse_estep_l") when left at the default.
    sparse_min_bucket_len: int = 128
    # Distributed EM document shard count (parallel/shard_plan.py).
    # 0 = auto: DEFAULT_EM_SHARDS (8), grown to the next power of two
    # covering the process count.  The shard plan — and with it the
    # sufficient-statistics reduction tree — is derived from the corpus
    # and THIS number, never from the process count, which is what
    # makes a 2-rank run's coordinator artifacts byte-identical to a
    # 1-rank run's (the reduction applies the same fixed pairwise tree
    # either way).  ONI_ML_TPU_EM_SHARDS overrides.
    em_shards: int = 0
    # Wire precision of the distributed suff-stats allreduce payload:
    # "f32" (exact — the byte-identity default) or "bf16"
    # (round-to-nearest-even compressed, HALF the KV-ring bytes per EM
    # iteration, f32 accumulation after the unpack).  bf16 keeps the
    # reduced stats rank-identical and rank-count-invariant, but they
    # are bf16-tolerance vs an f32-wire run, not bit-equal — leave at
    # f32 when artifacts must match a single-process fit byte-for-byte.
    # Applies to the bulk suff-stats reduce only; the f64 gamma merge
    # always ships exact.  ONI_ML_TPU_ALLREDUCE_PRECISION overrides.
    allreduce_precision: str = "f32"

    @property
    def k(self) -> int:
        return self.num_topics


@dataclass(frozen=True)
class OnlineLDAConfig:
    """Streaming (stochastic variational) LDA hyperparameters —
    BASELINE.json config 5.  tau0/kappa defaults follow Hoffman et al.
    (NIPS 2010); eta is the symmetric topic-word Dirichlet prior."""

    num_topics: int = 20
    alpha: float = 2.5           # doc-topic prior (fixed in SVI)
    eta: float = 0.01            # topic-word prior
    tau0: float = 64.0           # learning-rate delay
    kappa: float = 0.7           # learning-rate decay in (0.5, 1]
    var_max_iters: int = 20
    var_tol: float = 1e-6        # relative to mean gamma (see LDAConfig)
    batch_size: int = 1024       # docs per micro-batch
    min_bucket_len: int = 16
    compute_dtype: str = "float32"
    seed: int = 0
    # Checkpoint (lambda, step) every N micro-batch steps (0 = disabled).
    checkpoint_every: int = 0
    # Dense-corpus E-step for micro-batches (ops/dense_estep.py):
    # "auto" uses it on TPU when the (B, V) shape fits VMEM blocks —
    # for streaming, the one densify scatter per micro-batch replaces a
    # beta-slab gather in EVERY fixed-point iteration, so it pays for
    # itself immediately; "on"/"off" force.  Single-process only (the
    # data-parallel mesh path keeps the shard_map'd sparse E-step).
    dense_em: str = "auto"


@dataclass(frozen=True)
class FeedbackConfig:
    """Analyst feedback loop: non-threatening rows are replicated DUPFACTOR
    times into the corpus so their probability rises above the threshold
    (ml_ops.sh:31, flow_pre_lda.scala:253-268)."""

    dup_factor: int = 1000
    nonthreatening_severity: int = 3


@dataclass(frozen=True)
class ScoringConfig:
    """Event scoring (flow_post_lda.scala:227-239, dns_post_lda.scala:312-321).

    The reference hardcodes per-topic fallback vectors of 0.05 (flow) and
    0.1 (dns) for unseen IPs/words; we keep the values but derive the width.
    """

    threshold: float = 1e-20
    flow_fallback: float = 0.05
    dns_fallback: float = 0.1
    proxy_fallback: float = 0.1
    # Batch-path scoring engine: "host" (default) is the float64 path
    # whose scored-CSV bytes are golden-pinned — the parity oracle;
    # "device" runs the fused gather·dot·threshold pipeline
    # (scoring/pipeline.py): f32 on-chip arithmetic (~1e-6 relative
    # score drift in the emitted columns), chunked double-buffered
    # dispatch, survivors-only PCIe readback, sharded over the mesh for
    # multi-device grants.  "" = follow ONI_ML_TPU_SCORE (default host).
    engine: str = ""
    # Events per device dispatch for engine="device"
    # (scoring/pipeline.py DEFAULT_CHUNK; sweep with
    # tools/score_probe.py on the chip — the sweep records its
    # winner into the plan cache, and runs leaving this at the default
    # resolve through it: plans knob "score_device_chunk").
    device_chunk: int = 1 << 16


@dataclass(frozen=True)
class ServingConfig:
    """Streaming scoring service (oni_ml_tpu/serving/): micro-batch
    accumulation, host/device scorer dispatch, and the online-LDA
    refresh cadence.  The batch pipeline's once-a-day artifacts load
    into a ModelRegistry and a BatchScorer serves arriving events
    continuously; none of these knobs affect the batch stages."""

    # Flush an accumulating micro-batch when it reaches this many
    # events...  (plan knob "serve_max_batch": left at the default,
    # BatchScorer resolves it through the measured-plan cache)
    max_batch: int = 4096
    # ...or when its oldest event has waited this long, whichever first
    # (plan knob "serve_max_wait_ms").
    max_wait_ms: float = 50.0
    # Host-vs-device scorer dispatch.  0 (the default) prices the
    # decision from a MEASURED per-dispatch overhead calibration
    # (scoring.dispatch_calibration): the device path engages only for
    # batches past the measured break-even, and is pinned off entirely
    # on backends where its marginal per-event cost cannot beat the
    # host (on the v5e the measured break-even sits far above one
    # micro-batch: PERF.md, PR 21).  A positive int restores the legacy hard
    # threshold (batches >= it take the device scorer); None pins host
    # everywhere.  ONI_ML_TPU_SCORE_BREAK_EVEN overrides the measured
    # constant.  Flushes are capped at max_batch, so a hard threshold
    # must stay <= max_batch for the device path to be reachable.
    device_score_min: int = 0
    # Backpressure bound on the pending-event queue: submit() BLOCKS
    # once this many events are queued, so an ingest stream that
    # outruns scoring throttles at the source instead of growing the
    # queue (one future per event) until OOM.
    queue_max: int = 1 << 16
    # Fold the last N scored micro-batches into one online-LDA
    # natural-gradient step and republish theta/p to the registry every
    # N batches (serving/refresh.py); 0 disables refresh.
    refresh_every: int = 0
    # Population size D for the refresh trainer's suff-stats scaling
    # (OnlineLDATrainer total_docs); 0 = the loaded model's IP count.
    refresh_total_docs: int = 0
    # Events scoring under this threshold are emitted as suspicious
    # (the serving analogue of ScoringConfig.threshold).
    threshold: float = 1e-20
    # Per-batch latency/throughput/queue-depth JSON lines also append
    # here ("" = stdout only) — the metrics.json convention of
    # runner/ml_ops.py, one line per micro-batch.
    metrics_path: str = ""
    # OpenMetrics scrape endpoint (telemetry/exporter.py): serve binds
    # GET /metrics on this port, exposing the live counters, the
    # fixed-boundary latency histograms (with correct p50/p99/p999),
    # and the roofline utilization gauges to any Prometheus-compatible
    # collector.  0 = no endpoint.
    metrics_port: int = 0
    # Bind address for the scrape endpoint.  Loopback by default: the
    # endpoint exposes backend/model internals, so reaching it from
    # other hosts (a real Prometheus collector) is an explicit opt-in
    # ("0.0.0.0"), never the default.
    metrics_host: str = "127.0.0.1"
    # Headless-run file sink: the same OpenMetrics text written here at
    # stream end ("" = off) — CI and piped runs get the scrape bytes
    # without an HTTP listener.
    openmetrics_path: str = ""
    # -- multi-tenant fleet (serving/fleet.py, `ml_ops serve --fleet`) --
    # Fleet manifest path: a JSON file declaring the tenants
    # (serving/tenants.py load_manifest).  "" = single-model serving.
    fleet_manifest: str = ""
    # Cross-tenant flush triggers for the FleetScorer — the fleet
    # analogues of max_batch/max_wait_ms above, resolved through the
    # plan cache the same way (plan knobs "fleet_max_batch" /
    # "fleet_max_wait_ms"): the accumulating cross-tenant micro-batch
    # flushes at this many events total, or when its globally-oldest
    # event has waited this long.
    fleet_max_batch: int = 4096
    fleet_max_wait_ms: float = 50.0
    # Per-tenant admission-queue bound: a tenant with this many events
    # pending either blocks its own producers (admission="block" —
    # backpressure, priced as serve.<tenant>.admission_stall_s) or
    # sheds them (admission="reject" — AdmissionRejected raised, the
    # event never enqueued, journaled as admission_reject).  A
    # manifest entry's queue_max/admission override per tenant.  One
    # tenant saturating its own bound cannot grow another tenant's
    # latency: the scorer drains globally oldest-first and every queue
    # is bounded independently.
    tenant_queue_max: int = 8192
    admission: str = "block"
    # -- tiered model residency (serving/residency.py) --
    # HBM-hot capacity: at most this many tenants per K-group are
    # members of the stacked device snapshot at once; the rest page
    # between host-warm (pinned numpy in the per-tenant registry) and
    # checkpoint-cold (spilled to disk / reloaded from the day dir) by
    # an admission-driven LRU/LFU policy.  0 = unbounded (legacy: every
    # published tenant is stack-resident — plan knob
    # "fleet_hot_tenants" may still supply a measured capacity when
    # left at 0).  With a capacity set, the stack pads to power-of-two
    # tenant-capacity TIERS, so the compiled program family is keyed by
    # capacity, not census: promotion/eviction churn within a tier
    # retraces nothing.
    fleet_hot_tenants: int = 0
    # Host-warm capacity: at most this many NON-hot tenants keep their
    # theta/p pinned in host RAM; beyond it, the policy's coldest warm
    # tenants spill to checkpoint-cold (atomic npz under
    # residency_spill_dir, or reload straight from their day_dir).
    # 0 = unbounded (cold tier unused).
    fleet_warm_tenants: int = 0
    # Eviction victim selection: "lru" (least recently admitted) or
    # "lfu" (least admissions overall, ties broken by recency).  Both
    # are admission-aware: a tenant with events currently queued is
    # never evicted while a quiescent candidate exists.
    residency_policy: str = "lru"
    # Cold-tier spill directory for tenants published without a
    # reloadable day_dir ("" = a per-process temp dir).
    residency_spill_dir: str = ""
    # Stacked-snapshot DEVICE storage dtype: "f32" (default) or "bf16".
    # bf16 stores the stacked theta/p half-width on device — double the
    # HBM-hot tenant residency per byte — with f32 accumulation in the
    # gather-dot kernel; scores drift ~2^-8 relative vs the f32 stack
    # (documented tolerance, pinned in tests/test_residency.py).  The
    # f32 host path and the golden scoring bytes are untouched.
    stack_precision: str = "f32"
    # -- featurize plane (sources/device.py, ops/featurize_kernel.py) --
    # Which engine builds word rows on the flush path.  "host" = the
    # per-event Python featurizers (the golden oracle); "device" = the
    # compiled vocabulary tables — vectorized parse + packed-code LUT
    # gather feeding the UNCHANGED score dispatch, so scores stay
    # bitwise identical to host; "fused" additionally jit-fuses
    # LUT-gather + theta/p gather + dot into ONE dispatch per
    # single-tenant K-group (f32, ~1e-6 score envelope — opt-in).
    # "auto" resolves through the plan cache (plan knob
    # "featurize_engine") and defaults to "device": an unlowerable
    # vocabulary already degrades per-model to the host oracle, so
    # device is safe as the blanket default.  ONI_ML_TPU_FEATURIZE
    # overrides everything (the bench A/B toggle).
    featurize_engine: str = "auto"
    # Pow2 pad floor for the fused dispatch's micro-batch dimension
    # (plan knob "featurize_block"): flushes pad up to at least this
    # many rows so ragged flush sizes land in a handful of compiled
    # shapes instead of one per pow2 tier below it.
    featurize_block: int = 2048
    # Minimum flush-segment size (events) before the device featurize
    # engine pays for its dispatch: smaller segments take the host
    # oracle even when the engine is "device"/"fused" (the paged
    # 64-tenant regression, builder's CPU figure — tiny per-tenant
    # flushes sat below the device break-even).  0 resolves through
    # the plan cache (plan knob "featurize_break_even", measured by
    # bench.py's featurize phase) and falls back to the shipped
    # default; ONI_ML_TPU_FEATURIZE_BREAK_EVEN overrides everything.
    featurize_break_even: int = 0
    # -- replicated elastic serving (serving/router.py / replica.py) --
    # Frame codec for the router<->replica wire (serving/wire.py):
    # "columnar" (default — typed arrays as zero-copy buffers) or
    # "pickle", the negotiated one-release fallback.  This knob sets
    # what THIS side sends and what the hello negotiation answers;
    # what a receiver will DECODE is gated per link — a non-columnar
    # frame only unpickles on a link whose negotiation settled on the
    # fallback, and then through wire_pickle's allowlisted unpickler.
    wire_format: str = "columnar"
    # Accept the negotiated pickle fallback from PEERS?  Off
    # (default): a hello offering only "pickle" is refused and
    # non-columnar frames fail as ConnectionError — a cross-host
    # fleet keeps zero pickle decode surface on its ports.  On: a
    # peer may negotiate the one-release fallback (same trust
    # domain).  Forcing wire_format="pickle" implies acceptance on
    # that side — the operator chose the fallback fleet-wide.
    wire_accept_pickle: bool = False
    # Same-host shm upgrade: when both ends opt in and the hello
    # handshake proves the peer shares this host, data frames move to
    # a wire.ShmRing pair and the TCP data socket degrades to a
    # liveness signal.  Off = every frame stays on TCP.
    wire_shm: bool = True
    # Per-slab byte size of each shm ring (two slabs per direction).
    # Bounds the largest data frame a ring carries; bigger frames
    # (none today — score batches cap at ~20 KiB) fall back to TCP.
    wire_shm_slab_bytes: int = 1 << 20
    # -- autoscaler (serving/autoscale.py) --
    # Controller tick cadence: each tick samples the router's
    # admission-window occupancy + stall rates and re-evaluates the
    # Little's-law replica target.
    autoscale_interval_s: float = 0.5
    # Hysteresis bands on EWMA'd per-replica window utilization:
    # above `high` the controller scales up, below `low` it scales
    # down, in between it holds — the gap is what keeps an oscillating
    # load from flapping the fleet.
    autoscale_high: float = 0.75
    autoscale_low: float = 0.25
    # EWMA half-life for the utilization signal (seconds): a sample
    # this old carries half the weight of the current one.
    autoscale_halflife_s: float = 2.0
    # Minimum seconds between scaling actions (either direction): a
    # join/drain is expensive (model pushes + warmup), so one must
    # prove out before the next is considered.
    autoscale_cooldown_s: float = 5.0
    # Replica-count clamp for controller decisions.  The controller
    # only ever drains replicas it spawned itself.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    # Replica liveness cadence: each ReplicaServer publishes a KV
    # heartbeat this often, and the router declares a replica lost —
    # promoting its tenants' shadows — after replica_heartbeat_miss
    # consecutive intervals without one (connection EOF and the fail
    # key short-circuit the wait).  The product is the detection half
    # of the failover latency budget.
    replica_heartbeat_s: float = 0.25
    replica_heartbeat_miss: int = 8
    # Router control-plane op timeout (add_tenant/publish/drain/stats
    # round trips — NOT the per-event scoring path, which is async).
    route_op_timeout_s: float = 30.0
    # The router journals one priced {"kind": "route"} record per edge
    # every this many forwarded events (per-event records would dwarf
    # the journal at fleet rates); 0 journals only the stream-end
    # rollup.
    route_journal_every: int = 1024
    # Bounded per-replica admission window: at most this many events
    # in flight (submitted, response not yet demuxed) per replica edge;
    # a submit beyond it BLOCKS, and the stall is priced into the
    # route edge stats like a dataplane channel stall.  This is the
    # router-side Little's-law bound — per-replica throughput tops out
    # at window / round-trip — and the backstop that keeps one slow
    # replica's backlog (and the admission journal) from growing
    # unboundedly inside the router.  0 = unbounded.
    route_max_inflight: int = 1024


@dataclass(frozen=True)
class TelemetryConfig:
    """Flight recorder (oni_ml_tpu/telemetry/, docs/observability.md):
    the crash-safe run journal, span tracing, and the background
    device-liveness heartbeat.  Journaling is ON by default — it is the
    resume/post-mortem contract, and its cost is one buffered line per
    recorded event with a bounded fsync cadence."""

    # Append a crash-safe JSONL run journal (run_journal.jsonl in the
    # day directory): stage spans, EM likelihood points, scoring
    # DispatchStats, heartbeats.  The runner resumes against it.
    journal: bool = True
    # fsync after this many appends (stage boundaries always fsync);
    # a SIGKILL loses at most this many records.
    journal_fsync_every: int = 16
    # Background device-liveness probe interval; 0 disables.  When on,
    # a backend that stops answering becomes a clean BackendLost at the
    # next stage boundary (journaled as backend_lost) instead of a
    # silent hang.
    heartbeat_s: float = 0.0
    # One in-process probe round trip must answer within this long.
    heartbeat_timeout_s: float = 60.0
    # Consecutive misses before the subprocess-probe escalation and,
    # failing that too, the loss declaration.
    heartbeat_max_misses: int = 2


@dataclass(frozen=True)
class DataplaneConfig:
    """Streaming dataplane (oni_ml_tpu/dataplane/): in-memory columnar
    hand-offs through the pre→corpus→EM→score chain with bounded-buffer
    overlap, and the inter-stage files demoted to background checkpoint
    writes.  Artifacts stay byte-identical to the serial file-contract
    path (--no-dataplane) — the dataplane changes WHEN files are
    written and what the next stage reads, never the bytes."""

    # Stream hand-offs + background checkpoint sinks on (--no-dataplane
    # restores the exact serial path: inline writes, every stage
    # re-reading its input from the file contract).  Single-process
    # runs only; multi-host ranks always take the file contract.
    enabled: bool = True
    # Write the demoted inter-stage files (features.pkl,
    # word_counts.dat, words/doc/model.dat, final.*, likelihood.dat,
    # doc/word_results.csv).  --no-checkpoints skips them all: the run
    # produces only its product artifacts (results CSV, metrics.json,
    # run_journal.jsonl), and a later `--stages` resume is REFUSED
    # against the missing file contract (fail-fast with the artifact
    # name) instead of silently recomputing.  Batch single-host
    # full-chain runs only.
    checkpoints: bool = True
    # Rows per columnar chunk on the featurizer→corpus edge.  Small
    # enough that interning overlaps the pre stage's checkpoint writes
    # from the first chunk; large enough that per-chunk remap overhead
    # (an np.unique pass) stays negligible against ~1.5M-row days.
    chunk_rows: int = 1 << 18
    # Bounded-buffer depth per channel: a producer can run at most
    # this many chunks ahead of its consumer before its put() stalls
    # (the stall is priced as a dataplane.stall span).
    channel_capacity: int = 4
    # Concurrent background checkpoint writers.  Two overlaps the
    # pickle dump with the word-counts emit on the pre stage without
    # letting file IO steal every core from the compute stages.
    sink_workers: int = 2


@dataclass(frozen=True)
class ContinuousConfig:
    """Continuous ingestion (runner/continuous.py): the standing
    service that kills the day boundary — raw events stream through
    featurization into a ring-buffered CSR corpus window
    (dataplane/window.py), each refresh warm-starts EM from the
    previous window's topics, and a held-out-likelihood drift detector
    (models/drift.py) gates every fleet publish.  Time knobs are in
    SIMULATED event-time seconds (a day replay at ×N wall speed keeps
    the same window semantics)."""

    # Window span: events older than this (by event time) retire from
    # the training window at the next advance.  Default: 4 hours.
    window_s: float = 4 * 3600.0
    # Refresh cadence: advance + retrain + drift-check + gated publish
    # every this much event time.  Default: 30 minutes — the freshness
    # target is "minutes, not next-day".
    refresh_every_s: float = 1800.0
    # Hash-split fraction of window documents scored held-out per
    # refresh (models/evaluate.py document completion) — the drift
    # detector's input and the warm-vs-fresh quality cross-check.
    holdout_frac: float = 0.1
    # Drift declaration: the refresh's held-out per-token likelihood
    # sitting more than this many nats below the rolling-history
    # baseline vetoes the publish.
    drift_tol_nats: float = 0.5
    # Rolling history depth (refreshes) the baseline medians over, and
    # the checks required before drift can fire at all.
    drift_history: int = 8
    drift_min_history: int = 2
    # A refresh whose window holds fewer live documents than this
    # skips training entirely (bootstrap guard).
    min_refresh_docs: int = 32
    # The window's vocabulary pads to power-of-two capacity tiers
    # floored here, so vocab growth inside a tier never changes the
    # compiled [K, V] beta shape — the training-side twin of the
    # fleet's pow2 tenant-capacity tiers.  Crossing a tier boundary
    # mints exactly one new program family.
    vocab_floor: int = 4096
    # Docs per E-step batch for window refreshes.  Window batches
    # always pad to the FULL batch size (not the pipeline's multiple-
    # of-8 tail padding): a drifting doc census must reuse the same
    # compiled (B, L) family every refresh.
    batch_size: int = 256
    # Length-bucket floor for window batches, raised from the
    # pipeline's 16: with buckets floored at 64, the pow2 L family is
    # {64, 128, 256, ...} — a window whose doc-length tail wobbles
    # refresh-over-refresh stops minting novel (B, L) shapes (each
    # novel shape is one retrace), at the cost of some pad compute on
    # short documents.
    min_bucket_len: int = 64
    # EM dispatch chunk for window refreshes: 1 = the stepwise driver,
    # whose compiled unit is one (B, L) E-step — shape-stable across
    # refreshes whatever the batch COUNT does.  The fused chunk
    # runner's stacked [NB, B, L] groups re-key on the batch census,
    # which would retrace on every window that gains a batch.
    fused_em_chunk: int = 1
    # Warm-start policy: "auto" seeds EM from the previous published
    # topics except on the first fit or right after a drift veto
    # (drift means the old topics stopped describing the stream);
    # "always"/"never" force.
    warm_start: str = "auto"
    # Detection-quality publish gate (models/drift.QualityGate): every
    # candidate model is scored against a pinned labeled-injection
    # suite (sources/inject.py) and a recall@k drop of more than
    # quality_tol below the rolling baseline vetoes the publish exactly
    # like an LL drift.  Off by default — it costs one suite
    # featurization at startup plus one scoring pass per refresh.
    quality_gate: bool = False
    quality_tol: float = 0.25
    quality_history: int = 8
    quality_min_history: int = 2
    # Injection-suite shape: benign events, attack events per scenario,
    # RNG seed, and ranking depth (0 = k defaults to the attack count).
    quality_events: int = 2000
    quality_attack_events: int = 8
    quality_seed: int = 7
    quality_k: int = 0


@dataclass(frozen=True)
class PlansConfig:
    """Measured execution plans (oni_ml_tpu/plans/): the persistent
    autotune + plan cache that replaces hand-tuned constants with
    per-(backend, shape) measured values, plus the persistent jax
    compilation cache that lets traced programs survive process death.

    Precedence is fixed: an explicitly-set config knob always wins over
    a plan entry, which wins over the shipped default — and every
    consumer records which source it ran under (`source: "config" |
    "plan" | "default"` in stage/serve records)."""

    # Plan lookups/records on (--no-plans turns off; ONI_ML_TPU_PLANS=0
    # is the process-wide kill switch).
    enabled: bool = True
    # Plan-cache file ("" = ONI_ML_TPU_PLAN_CACHE env, else
    # ~/.cache/oni_ml_tpu/plans.jsonl).
    cache_path: str = ""
    # Persistent XLA compilation cache (jax_compilation_cache_dir):
    # every compiled program serializes to disk, so a re-run re-traces
    # nothing (--no-compilation-cache opts out).  Where it lives is not
    # a config matter: JAX_COMPILATION_CACHE_DIR when set, else the
    # fixed <checkout>/.jax_cache (plans/warmup.cache_dir).
    compilation_cache: bool = True


@dataclass(frozen=True)
class PipelineConfig:
    """End-to-end run configuration (replaces /etc/duxbay.conf + env vars)."""

    data_dir: str = "."            # per-day working directory (LPATH analogue)
    flow_path: str = ""            # netflow CSV file/dir/glob/comma list
                                   # (FLOW_PATH; multi-file = config-3
                                   # 30-day corpus, one joint ECDF)
    dns_path: str = ""             # raw DNS CSV/parquet paths (DNS_PATH)
    proxy_path: str = ""           # proxy/HTTP log CSV paths (PROXY_PATH)
    top_domains_path: str = ""     # Alexa top-1m.csv (dns_pre_lda.scala:62)
    qtiles_path: str = ""          # precomputed flow cuts (SURVEY §2.7)
    # Pre-stage shard workers: day files split into line-aligned byte
    # ranges and featurized concurrently (native std::threads, or
    # concurrent.futures in the pure-Python fallback), with a
    # deterministic first-seen merge that keeps word_counts.dat and
    # every downstream artifact byte-identical across worker counts.
    # 0 = auto (one worker per host core), 1 = the exact legacy
    # sequential path.  The reference's answer to this stage was a
    # 62-executor Spark cluster (dns_pre_lda.scala:1-2).
    pre_workers: int = 0
    lda: LDAConfig = field(default_factory=LDAConfig)
    online_lda: OnlineLDAConfig = field(default_factory=OnlineLDAConfig)
    feedback: FeedbackConfig = field(default_factory=FeedbackConfig)
    scoring: ScoringConfig = field(default_factory=ScoringConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    plans: PlansConfig = field(default_factory=PlansConfig)
    dataplane: DataplaneConfig = field(default_factory=DataplaneConfig)
    continuous: ContinuousConfig = field(default_factory=ContinuousConfig)
    # Mesh shape: (data, model). data shards documents, model shards the
    # vocabulary axis of beta.  (1, 1) = single device.
    mesh_shape: tuple = (1, 1)

    def day_dir(self, fdate: str) -> str:
        return os.path.join(self.data_dir, fdate)

    def replace(self, **kw) -> "PipelineConfig":
        return dataclasses.replace(self, **kw)
