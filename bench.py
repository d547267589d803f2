"""Benchmark: LDA EM throughput + scale config + DNS scoring, one chip.

Headline: docs/sec through the production EM path (device-resident
chunked driver, models/fused.py, with the dense-corpus Pallas E-step,
ops/dense_estep.py) at the suspicious-connects scale — the work the
reference spread over 20 MPI ranks of oni-lda-c (SURVEY.md §3.3).

Utilization accounting: alongside docs/sec the
bench models the kernel's executed FLOPs and HBM traffic and reports
achieved TFLOP/s / GB/s against the chip peaks, so the number is
auditable against the roofline instead of free-floating.

Secondary metrics (carried as extra keys on the single JSON line the
driver records): the reference-semantics fresh-start engine (warm
start is the production default; the secondary keeps the delta
attributable), wall-clock to convergence (BASELINE.json's first named
metric), DNS + flow scoring throughput/p50, config-3 scale (K=50,
V=50k), config-4 huge-V (V=512k, compact-vocab dense engine),
streaming SVI steady state (config 5), and two full synthetic days
end-to-end (the reference's actual unit of work).

Every phase runs in its OWN subprocess (`python bench.py --phase NAME`)
under a per-phase timeout: the orchestrator never initializes a jax
backend, so on a machine with one chip each phase holds it in turn.
The record line is printed when the headline is measured and
re-printed (grown) after each secondary, so the last line is always the
most complete record so far.  A phase that fails, times out or reports
itself skipped makes the exit code non-zero, and the record carries
only numbers measured in this run on the device it names.

Prints the JSON record line (possibly several times as it grows; the
last line is the most complete): {"metric", "value", "unit", ...}.
"""

import glob
import json
import os
import sys
import time

import numpy as np

from oni_ml_tpu.telemetry.roofline import peaks_for as _peaks_for


def _live_peaks():
    """Peak FLOP/s and HBM bytes/s of the device this process runs on:
    the telemetry roofline's peak-spec registry
    (oni_ml_tpu/telemetry/roofline.py, the single home of these
    constants with their provenance), keyed by the live device
    fingerprint.  A device that is not in the table is an error, not a
    default."""
    from oni_ml_tpu import plans

    fingerprint = plans.device_fingerprint()
    spec = _peaks_for(fingerprint)
    if spec is None:
        raise RuntimeError(
            f"bench: device {fingerprint!r} has no entry in "
            "telemetry/roofline.PEAK_SPECS; add its published peaks "
            "there before reporting a utilization"
        )
    return spec


def _sync(x):
    """Wait for the device to finish `x` (block_until_ready — jax
    returns before the device does) and return its first element as a
    float.  Every timed region ends here."""
    import jax

    leaf = jax.block_until_ready(jax.tree_util.tree_leaves(x)[0])
    return float(np.asarray(leaf).ravel()[0])


# Alpha-Newton cap for the throughput benches: <= 16 takes
# update_alpha's unrolled lowering (models/lda.py); the production
# config default and the lda-c drop-in CLI keep the reference's 100.
ALPHA_MAX_ITERS = 8


def _setup_em(k, v, b, l, *, chunk, var_max_iters, em_tol,
              force_sparse=False, wmajor=True, warm_start=False,
              precision="bf16", compact=False, word_law="uniform",
              n_batches=1, engine=None):
    """Shared corpus/dense-path/runner setup for the EM benches:
    returns (log_beta, groups, run_chunk, use_dense, used_wmajor,
    corpus_itemsize, gammas0, info).

    `engine` pins the E-step engine for A/B measurement: "dense"
    forces the dense-corpus kernel even off-TPU (interpret mode — the
    CPU crossover baseline), "sparse" forces the fused sparse bucketed
    kernel (ops/sparse_estep.py), None keeps the production auto
    resolution.  info["estep_engine"] names what actually ran.

    word_law="loguniform" draws token ids log-uniformly over [1, V]
    (zipf s≈1) — the realistic frequency law for config-4's
    combinatorial DNS word space, where a batch touches only a few
    tens of thousands of distinct words out of V≈512k.  `compact`
    routes such a batch through the compact-vocab dense engine
    (fused.compact_stack_batches semantics) when full-V dense is
    infeasible; `info` carries the compact width for the bench
    record.

    `n_batches` stacks that many B-doc batches resident (the day-scale
    shape: the chunk runner scans the stack each EM iteration, so the
    per-iteration fixed cost amortizes — tools/tpu_probes.py
    batch_amort).  The default 1 draws the identical corpus as every
    prior round, keeping phase numbers comparable."""
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused
    from oni_ml_tpu.ops import dense_estep

    if compact and n_batches != 1:
        raise ValueError("n_batches > 1 is not wired for the compact "
                         "engine probe")
    rng = np.random.default_rng(0)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )
    nb = n_batches
    if word_law == "loguniform":
        word_np = np.minimum(
            v - 1, np.floor(v ** rng.uniform(size=(nb, b, l)))
        ).astype(np.int32)
    else:
        word_np = rng.integers(0, v, size=(nb, b, l)).astype(np.int32)
    word_idx = jnp.asarray(word_np)
    counts = jnp.asarray(
        rng.integers(1, 5, size=(nb, b, l)).astype(np.float32)
    )
    doc_mask = jnp.ones((nb, b), jnp.float32)

    if engine not in (None, "dense", "sparse"):
        raise ValueError(f"unknown bench EM engine {engine!r}")
    if engine == "sparse":
        force_sparse = True       # the dense family stands down
    use_dense, use_wmajor, compiler_options = dense_estep.plan(
        b, v, k, precision, wmajor=wmajor
    )
    want_wmajor = wmajor  # caller's layout preference, pre-feasibility
    use_dense = use_dense and not force_sparse
    if engine == "dense" and not use_dense:
        # Forced dense off-TPU: the interpret-mode baseline the
        # dense-vs-sparse crossover compares against.  Feasibility
        # still gates (an infeasible shape has no dense baseline).
        if dense_estep.pick_block(b, v, k, precision) is None:
            raise ValueError(
                f"dense engine forced but B={b}, V={v}, K={k} has no "
                "VMEM-feasible doc block"
            )
        use_dense = True
        use_wmajor = (
            wmajor
            and dense_estep.pick_block_w(b, v, k, precision) is not None
        )
    wmajor = use_dense and use_wmajor
    corpus_itemsize = 4
    info = {}
    e_step_fn = None
    if engine == "sparse":
        from oni_ml_tpu.ops import sparse_estep

        if sparse_estep.pick_block(b, l, k, precision) is None:
            raise ValueError(
                f"sparse engine forced but B={b}, L={l}, K={k} has no "
                "VMEM-feasible doc block"
            )
        e_step_fn = sparse_estep.make_e_step_fn(precision=precision)
        info["estep_engine"] = "sparse"
        kib = sparse_estep.scoped_vmem_kib(b, l, k, precision)
        if kib and jax.default_backend() == "tpu":
            compiler_options = {"xla_tpu_scoped_vmem_limit_kib": str(kib)}
    # Gate bf16 storage on the DENSIFIED cells (duplicate words in a
    # doc sum), exactly like the trainer.
    store = dense_estep.corpus_dtype(
        dense_estep.max_dense_cell(word_idx.reshape(-1, l),
                                   counts.reshape(-1, l)), precision
    )
    plan = None
    if compact and not use_dense and not force_sparse:
        from oni_ml_tpu.io import Batch

        batch0 = Batch(word_idx=word_np[0],
                       counts=np.asarray(counts)[0],
                       doc_mask=np.asarray(doc_mask)[0],
                       doc_index=np.arange(b))
        plan = fused.plan_compact(
            [batch0], k, precision, wmajor=want_wmajor,
            itemsize=jnp.dtype(store).itemsize,
        )
    if use_dense:
        corpus_itemsize = jnp.dtype(store).itemsize
        dense = jax.jit(jax.vmap(
            lambda w, c: dense_estep.densify(w, c, v, dtype=store)
        ))(word_idx, counts)
        if wmajor:
            dense = jnp.transpose(dense, (0, 2, 1))
        groups = ((dense, doc_mask),)
    elif plan is not None:
        # Compact-vocab dense engine: the batch's own Wc-wide slice of
        # the vocabulary through the same MXU kernel, suff-stats
        # scattered back to full V inside the chunk runner.  Built by
        # the same production code the trainer uses.
        use_dense = True
        wmajor = plan.wmajor
        corpus_itemsize = jnp.dtype(store).itemsize
        wc = plan.widths[0]
        groups = fused.compact_stack_batches(
            [batch0], np.float32, jnp.asarray, plan, corpus_store=store
        ).arrays
        kib = dense_estep.scoped_vmem_kib(b, wc, k, wmajor=wmajor,
                                          precision=precision)
        compiler_options = (
            {"xla_tpu_scoped_vmem_limit_kib": str(kib)}
            if kib and jax.default_backend() == "tpu" else None
        )
        info.update({"compact_width": wc,
                     "unique_words": int(len(plan.uniques[0][0])),
                     "engine_variant": "compact"})
    else:
        if engine != "sparse":     # the sparse engine set its own kib
            compiler_options = None
        groups = ((word_idx, counts, doc_mask),)
    if "estep_engine" not in info:
        # "sparse_auto": sparse stacked groups through estep.e_step's
        # auto dispatch (fused sparse kernel on TPU, XLA on CPU).
        info["estep_engine"] = (
            "compact" if info.get("engine_variant") == "compact"
            else "dense" if use_dense else "sparse_auto"
        )

    run_chunk = fused.make_chunk_runner(
        num_docs=nb * b, num_topics=k, num_terms=v, chunk=chunk,
        var_max_iters=var_max_iters, var_tol=1e-6, em_tol=em_tol,
        estimate_alpha=True, compiler_options=compiler_options,
        dense_wmajor=wmajor, warm_start=warm_start,
        e_step_fn=e_step_fn,
        dense_precision=precision if use_dense else "f32",
        # cap ALPHA_MAX_ITERS takes update_alpha's unrolled lowering
        # (one fused scalar chain instead of a dynamic-trip while_loop
        # — the r05 alpha_ab probe charged ~0.5 ms/EM-iter to the
        # estimate); warm mid-run Newton converges in <8 trips so the
        # same exit fires (equivalence pinned in tests/test_lda.py).
        alpha_max_iters=ALPHA_MAX_ITERS,
    )
    # Report the cap the runner was ACTUALLY built with, threaded back
    # from make_chunk_runner itself: tools/tpu_probes.py's alpha_ab
    # monkeypatches the maker to override alpha_max_iters inside its
    # wrapper, and re-reading the module constant here would record 8
    # for a newton100 run.
    info["alpha_max_iters"] = getattr(
        run_chunk, "alpha_max_iters", ALPHA_MAX_ITERS
    )
    gammas0 = fused.initial_gammas(groups, k, jnp.float32,
                                   dense_wmajor=wmajor)
    return (log_beta, groups, run_chunk, use_dense, wmajor,
            corpus_itemsize, gammas0, info)


def bench_em(k, v, b, l, chunk=128, rounds=5, var_max_iters=20,
             force_sparse=False, wmajor=True, warm_start=False,
             precision="bf16", compact=False, word_law="uniform",
             n_batches=1, engine=None):
    """Production fused-EM throughput at (K, V, B, L); returns a dict:
    docs_per_sec, t_iter (seconds per EM iteration), use_dense, wmajor,
    corpus_itemsize, estep_engine (what actually ran — `engine` pins
    "dense"/"sparse" for A/B crossover measurement), and mean_vi (mean
    inner fixed-point iterations per EM step in the timed rounds —
    shows the var_tol early exit and warm start collapsing the inner
    loop as beta stabilizes).

    chunk EM iterations run device-resident per host call; the default
    amortizes the per-dispatch cost, which is not measured on the
    current machine (ROADMAP A1 re-runs the chunk sweep).

    precision="bf16" stores the dense kernel's matmul operands
    half-width.  On TPU this is bit-identical to f32 (XLA DEFAULT
    matmul precision already feeds the MXU bf16-truncated inputs) and
    ~10% faster, so the headline uses it."""
    import jax.numpy as jnp

    (log_beta, groups, run_chunk, use_dense, wmajor, corpus_itemsize,
     gammas0, info) = _setup_em(
        k, v, b, l, chunk=chunk, var_max_iters=var_max_iters,
        em_tol=0.0, force_sparse=force_sparse, wmajor=wmajor,
        warm_start=warm_start, precision=precision, compact=compact,
        word_law=word_law, n_batches=n_batches, engine=engine,
    )
    alpha = jnp.float32(2.5)
    have = jnp.asarray(False)
    res = run_chunk(log_beta, alpha, jnp.float32(np.nan), groups, chunk,
                    gammas0, have)
    _sync(res.lls[-1])
    # Second warmup: one extra chunk after the compile keeps the timed
    # rounds honest about the steady state.  Gammas feed back so
    # warm start carries across chunk boundaries like the production
    # driver.
    res = run_chunk(res.log_beta, res.alpha, res.ll_prev, groups, chunk,
                    res.gammas, res.steps_done > 0)
    _sync(res.lls[-1])

    best = float("inf")
    vi = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        res = run_chunk(res.log_beta, res.alpha, res.ll_prev, groups, chunk,
                        res.gammas, res.steps_done > 0)
        ll = _sync(res.lls[-1])
        best = min(best, (time.perf_counter() - t0) / chunk)
        vi.append(float(np.asarray(res.vi_iters, np.float64).mean()))
    assert np.isfinite(ll)
    # Measured roofline record (telemetry/roofline.py): the chunk
    # program's XLA cost analysis over the best timed round — the
    # harvested counterpart of em_utilization's analytic model, so the
    # two can be cross-checked in one payload.  Degrades to
    # wall-time-only (utilization null) off-TPU / without cost support.
    from oni_ml_tpu.telemetry import roofline as _rl

    jitted = getattr(run_chunk, "jitted", None)
    if jitted is not None:
        _rl.harvest_jitted(
            "em.run_chunk", jitted, res.log_beta, res.alpha, res.ll_prev,
            groups, chunk, res.gammas, res.steps_done > 0,
            shape=f"k{k}.v{v}.b{b}.l{l}.c{chunk}",
        )
    # Effective vs dense-equivalent FLOP accounting
    # (ops/sparse_estep.py): `effective` is the live-token work the
    # math needs, `dense_equiv` what the full-V dense engine executes
    # for the same batch — their ratio is the density waste factor, and
    # the roofline's useful_mxu_pct is effective over peak ("useful
    # fraction of peak" next to mxu_pct's "fraction of peak").
    from oni_ml_tpu.ops import sparse_estep as _sp

    mean_vi = float(np.mean(vi))
    eff_iter = _sp.effective_flops(n_batches * b, l, k, mean_vi)
    dense_eq_iter = _sp.dense_equiv_flops(n_batches * b, v, k, mean_vi)
    rl_rec = _rl.roofline_record("em.run_chunk", wall_s=best * chunk,
                                 dispatches=1,
                                 effective_flops=eff_iter * chunk)
    rl_rec.pop("kind", None)   # payload section, not a journal line
    return {
        "roofline": rl_rec,
        "flops_effective_per_iter": eff_iter,
        "flops_dense_equiv_per_iter": dense_eq_iter,
        "docs_per_sec": n_batches * b / best,
        "t_iter": best,
        "use_dense": use_dense,
        "wmajor": wmajor,
        "corpus_itemsize": corpus_itemsize,
        "mean_vi": mean_vi,
        # Dispatch settings ride along so phase records stay
        # self-describing across rounds (r03's 1.31M was chunk=32 +
        # while-loop alpha; r05 runs chunk=128 + unrolled cap-8).
        # alpha_max_iters arrives via `info` — the EFFECTIVE value the
        # chunk runner was built with (_setup_em), not the module
        # constant a probe may have overridden.
        "chunk": chunk,
        **info,
    }


def bench_dense_vs_sparse(k, v, b, l, chunk=32, rounds=2,
                          precision="bf16"):
    """Measured dense-vs-sparse E-step engine comparison at one shape —
    the bench-side twin of the trainer's inline crossover sweep
    (sparse_estep.engine_crossover), run through the REAL fused chunk
    driver with each engine pinned.

    Returns {"dense": {...}, "sparse": {...}, "winner",
    "resolved_engine", "resolved_source"}: per-engine docs/s, t_iter,
    and roofline (effective vs dense-equivalent FLOPs), the measured
    winner — persisted to the plan cache under the exact-shape AND
    density-band keys, so the engine choice survives process death and
    run 2 resolves it with source "plan" — and what the crossover now
    RESOLVES to (the number the acceptance gate checks: the resolved
    engine is never slower than the dense baseline, because it is the
    measured winner)."""
    from oni_ml_tpu import plans
    from oni_ml_tpu.ops import dense_estep, sparse_estep

    out = {"shape": f"k{k}.v{v}.b{b}.l{l}.{precision}"}
    timed = {}
    for engine in ("dense", "sparse"):
        feasible = (
            dense_estep.pick_block(b, v, k, precision)
            if engine == "dense"
            else sparse_estep.pick_block(b, l, k, precision)
        )
        if feasible is None:
            out[engine] = {"skipped": "no VMEM-feasible doc block"}
            continue
        em = bench_em(k, v, b, l, chunk=chunk, rounds=rounds,
                      warm_start=True, precision=precision, engine=engine)
        timed[engine] = em
        out[engine] = {
            "docs_per_sec": round(em["docs_per_sec"], 1),
            "t_iter": em["t_iter"],
            "mean_vi": round(em["mean_vi"], 2),
            "roofline": em.get("roofline"),
        }
    if not timed:
        out["winner"] = None
        return out
    winner = max(timed, key=lambda e: timed[e]["docs_per_sec"])
    out["winner"] = winner
    # Persist the measured crossover exactly like the trainer's inline
    # sweep (dispatch_calibration pattern): exact shape + density band.
    exact, band = sparse_estep.crossover_shapes(k, v, b, l, precision)
    value = {
        "engine": winner,
        "dense_s": timed.get("dense", {}).get("t_iter"),
        "sparse_s": timed.get("sparse", {}).get("t_iter"),
    }
    measurements = {
        e: round(timed[e]["docs_per_sec"], 1) for e in timed
    }
    plans.note_sweep("estep_engine")
    for shape in (exact, band):
        plans.record_value("estep_engine", value, shape=shape,
                           source="autotune", measurements=measurements,
                           unit="docs/sec")
    # What a fresh auto run now resolves to: the plan entry just
    # recorded (source "plan" proves the persistence round-trip).
    sparse_estep._CROSSOVER_CACHE.pop(exact, None)
    cross = sparse_estep.engine_crossover(k, v, b, l, precision=precision)
    out["resolved_engine"] = cross["engine"]
    out["resolved_source"] = cross["source"]
    return out


def bench_convergence(k=20, v=8192, b=4096, l=128, em_tol=1e-4,
                      max_iters=256, chunk=32, precision="bf16",
                      warm_start=True):
    """Wall-clock from random init to |d(ll)/ll| < em_tol at the
    headline shape — BASELINE.json's first named metric ("netflow LDA
    wall-clock to convergence").  Compile time is excluded via a
    zero-step warmup call; the measured span covers every EM iteration,
    M-step, alpha Newton update, and chunk-boundary host sync the
    production driver performs."""
    import jax.numpy as jnp

    (log_beta, groups, run_chunk, use_dense, _, _, gammas0, _) = _setup_em(
        k, v, b, l, chunk=chunk, var_max_iters=20, em_tol=em_tol,
        precision=precision, warm_start=warm_start,
    )
    # Compile warmup without executing any EM iteration.
    res = run_chunk(log_beta, jnp.float32(2.5), jnp.float32(np.nan),
                    groups, 0, gammas0, jnp.asarray(False))
    _sync(res.steps_done)

    t0 = time.perf_counter()
    log_b, alpha, ll_prev = log_beta, jnp.float32(2.5), jnp.float32(np.nan)
    gp, have = gammas0, jnp.asarray(False)
    iters = 0
    done = 0
    while iters < max_iters:
        res = run_chunk(log_b, alpha, ll_prev, groups,
                        min(chunk, max_iters - iters), gp, have)
        gp, have = res.gammas, res.steps_done > 0
        log_b, alpha, ll_prev = res.log_beta, res.alpha, res.ll_prev
        done = int(_sync(res.steps_done))
        iters += done
        if bool(np.asarray(res.converged)) or done == 0:
            break
    seconds = time.perf_counter() - t0
    engine = _engine_label(use_dense, precision, warm=warm_start)
    return seconds, iters, float(_sync(res.lls[max(done - 1, 0)])), engine


def em_utilization(k, v, b, t_iter, var_max_iters=20, wmajor=True,
                   precision="bf16", corpus_itemsize=4):
    """Roofline accounting for one dense-path EM iteration.

    FLOPs: the kernel runs (var_max_iters VI iterations + 1 tail pass),
    each two K-small matmuls of 2*B*K*W flops — pass the MEASURED mean
    executed iterations (bench_em's mean_vi) as var_max_iters, not the
    cap: under warm start the early exit collapses the inner loop and a
    cap-based count would overstate achieved FLOP/s.  In the W-major layout
    (the production default) the phinorm contraction pads K to the
    128-lane tile while the gamma-update output pads K only to the
    8-sublane granularity.  HBM: the dense corpus crosses once per EM
    iteration (2 bytes/element when stored bf16 — corpus_dtype), beta
    re-reads once per doc block (grid = B/bb blocks), plus
    model/outputs.
    """
    from oni_ml_tpu.ops import dense_estep

    peaks = _live_peaks()
    w = dense_estep.padded_width(v)
    pick = dense_estep.pick_block_w if wmajor else dense_estep.pick_block
    grid = b // (pick(b, v, k, precision) or b)
    flops_useful = 4.0 * b * k * w * (var_max_iters + 1)
    k_q = max(k, 128)                  # contraction pad (phinorm matmul)
    # gamma-update matmul: K pads to 8 sublanes W-major, 128 lanes row-major
    k_s = max(k, -(-k // 8) * 8) if wmajor else max(k, 128)
    flops_padded = flops_useful * (k_q + k_s) / (2.0 * k)
    bytes_hbm = (
        float(corpus_itemsize) * b * w + 4.0 * (b * k + (grid + 3) * k * w)
    )
    return {
        "achieved_tflops": round(flops_useful / t_iter / 1e12, 2),
        "mxu_pct": round(
            100 * flops_padded / t_iter / peaks.flops_per_s, 1),
        "hbm_gbps": round(bytes_hbm / t_iter / 1e9, 1),
        "hbm_pct": round(
            100 * bytes_hbm / t_iter / peaks.hbm_bytes_per_s, 1),
    }


def bench_online_svi(k=20, v=8192, b=4096, l=128, steps=64, chunk=64):
    """Steady-state streaming SVI throughput (BASELINE.json config 5):
    docs/sec through OnlineLDATrainer.step_many at the headline
    micro-batch shape — the chunked device-resident scan path
    production streams use for replay/catch-up.  steps/chunk are 64/64
    so the timed pass is ONE dispatch (per-dispatch cost: not measured
    on the current machine): step_many lowers scans at the largest
    power of two <= chunk (online_lda.py splits 48 into scan32+scan16
    — TWO dispatches), so 64/64 is the smallest shape above 48 that
    truly runs as one.  The stream's host->device transfer stays in the
    timed region — arriving micro-batch data is real steady-state
    cost.  One warm chunk absorbs compile + densify warmup;
    dense_em='auto' picks the dense MXU E-step on TPU."""
    from oni_ml_tpu.config import OnlineLDAConfig
    from oni_ml_tpu.io import Batch
    from oni_ml_tpu.models import OnlineLDATrainer

    rng = np.random.default_rng(1)
    cfg = OnlineLDAConfig(num_topics=k, batch_size=b)
    tr = OnlineLDATrainer(cfg, num_terms=v, total_docs=b * steps)
    batches = [
        Batch(
            word_idx=rng.integers(0, v, size=(b, l)).astype(np.int32),
            counts=rng.integers(1, 5, size=(b, l)).astype(np.float32),
            doc_index=np.arange(b, dtype=np.int32),
            doc_mask=np.ones((b,), np.float32),
        )
        for _ in range(4)
    ]
    if steps % chunk:
        raise ValueError(f"steps={steps} must be a multiple of "
                         f"chunk={chunk}: a sub-chunk remainder takes "
                         "the per-step path, whose cold compile would "
                         "land inside the timed region")
    stream = [batches[i % len(batches)] for i in range(steps)]
    infos = tr.step_many(stream[:chunk], chunk=chunk)   # compile + warm
    _sync(infos[-1].likelihood)
    t0 = time.perf_counter()
    infos = tr.step_many(stream, chunk=chunk)
    _sync(infos[-1].likelihood)
    dt = time.perf_counter() - t0
    return b * steps / dt


def bench_dns_scoring(n_events=400_000, reps=3):
    """Full score_dns stage (model-row resolution, batched device dots,
    threshold/sort, native CSV emit) over a synthetic day; returns
    (events_per_sec, p50_seconds)."""
    from oni_ml_tpu.features.native_dns import featurize_dns_sources
    from oni_ml_tpu.scoring import ScoringModel, score_dns_csv

    rng = np.random.default_rng(7)
    k = 20
    n_ips, n_doms = 5000, 2000
    rows = [
        [
            "t",
            str(1454000000 + int(rng.integers(0, 86400))),
            str(int(rng.integers(40, 1500))),
            f"10.{i % 250}.{(i // 250) % 250}.{int(rng.integers(1, 250))}",
            f"sub{int(rng.integers(0, 100))}.dom{int(rng.integers(0, n_doms))}.com",
            "1",
            str(int(rng.integers(1, 17))),
            str(int(rng.integers(0, 4))),
        ]
        for i in range(n_events)
    ]
    feats = featurize_dns_sources([rows])  # production (native) container
    ips = sorted({feats.client_ip(i) for i in range(min(n_ips, n_events))})
    vocab = sorted(set(feats.word))
    theta = rng.dirichlet(np.ones(k), size=len(ips))
    p = rng.dirichlet(np.ones(len(vocab)), size=k).T
    model = ScoringModel.from_results(ips, theta, vocab, p, fallback=0.1)

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        blob, scores = score_dns_csv(feats, model, threshold=1e-3)
        times.append(time.perf_counter() - t0)
    p50 = float(np.median(times))
    assert len(blob) and len(scores)  # threshold keeps some events
    return n_events / p50, p50


def _powerlaw_cdf(n: int, a: float) -> np.ndarray:
    """CDF over ranks 0..n-1 with p(rank) ∝ (rank+1)^-a.  searchsorted
    against uniform draws samples a Zipf-like distribution over a
    BOUNDED population (np.random's zipf is unbounded)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def _write_flow_day(f, n_events, n_src=4000, n_dst=2000, seed=11,
                    chunk=200_000, ip_zipf_a=None, n_svc_ports=None):
    """Write a synthetic 27-column netflow day (no header) to an open
    text file, chunked so multi-million-event days don't hold every
    line in RAM.

    Layout follows the reference schema exactly (features/flow.py
    FLOW_COLUMNS: hour@4, minute@5, second@6, tdur@7, sip@8, dip@9,
    sport@10, dport@11, proto@12, flag@13, fwd@14, stos@15, ipkt@16,
    ibyt@17, then 9 unused columns).  An earlier version carried an
    extra leading timestamp column that shifted everything one right —
    the featurizer then read sip="0.0" and a dip-string port for every
    row, collapsing the synthetic day to one port bucket and a
    degenerate vocabulary.

    Realistic-cardinality mode (config-3 at-spec tooling): with `ip_zipf_a` set, source/destination IPs draw from a
    power-law (rank^-a) population instead of uniform — a few hot
    hosts, a long tail, document cardinality that scales with the
    active-IP count the way the reference's two-documents-per-event
    mapping does (flow_pre_lda.scala:366-380) — and the address space
    widens to three octets (src 10.a.b.c / dst 11.a.b.c, disjoint) so
    populations beyond 65k stay distinct.  With `n_svc_ports` set,
    that many distinct low service ports (<=1024, power-law
    popularity) replace the fixed 6-service mix, scaling the realized
    word vocabulary toward config 3's "full IP-pair vocabulary" shape.
    Both default OFF; the default byte stream is unchanged."""
    rng = np.random.default_rng(seed)
    svc = np.asarray([80, 443, 22, 53, 8080, 25])
    svc_cdf = None
    if n_svc_ports is not None:
        # One FIXED service mix regardless of the per-day seed: real
        # traffic keeps the same services day over day.  Drawing the
        # subset from the per-day rng gave every day file a fresh
        # 48-port sample, and a 30-day corpus realized ~770 distinct
        # ports — a 16x vocabulary inflation artifact (786k words
        # instead of the ~50k the binned word space yields).
        svc_rng = np.random.default_rng(1011)
        svc = np.sort(svc_rng.choice(np.arange(1, 1025),
                                     size=n_svc_ports, replace=False))
        svc_cdf = _powerlaw_cdf(n_svc_ports, 1.05)
    src_cdf = dst_cdf = None
    if ip_zipf_a is not None:
        src_cdf = _powerlaw_cdf(n_src, ip_zipf_a)
        dst_cdf = _powerlaw_cdf(n_dst, ip_zipf_a)
    # The 2-octet encodings overflow (non-IP strings like 10.0.1367.44)
    # past 65536 hosts, so the wide disjoint spaces engage for ANY mode
    # whose population needs them — uniform draws with a large --n-src
    # included, not just power-law mode (round-5 review finding).  The
    # default populations keep the byte-identical round-1..4 stream.
    # Past 2^24 even three octets alias (rank v and v-2^24 collide),
    # which would silently cap realized cardinality — refuse instead.
    if n_src > (1 << 24) or n_dst > (1 << 24):
        raise ValueError(
            f"IP populations cap at 2^24 per side (got n_src={n_src}, "
            f"n_dst={n_dst}): the 3-octet encodings alias beyond that, "
            "silently deflating realized doc cardinality"
        )
    if ip_zipf_a is not None or n_src > 65536 or n_dst > 65536:

        def fmt_src(v):
            return f"10.{(v >> 16) & 255}.{(v >> 8) & 255}.{v & 255}"

        def fmt_dst(v):
            return f"11.{(v >> 16) & 255}.{(v >> 8) & 255}.{v & 255}"
    else:

        def fmt_src(v):
            return f"10.0.{v >> 8}.{v & 255}"

        def fmt_dst(v):
            return f"10.1.{v >> 8}.{v & 255}"

    for start in range(0, n_events, chunk):
        m = min(chunk, n_events - start)
        hours = rng.integers(0, 24, size=m)
        mins = rng.integers(0, 60, size=m)
        secs = rng.integers(0, 60, size=m)
        if src_cdf is None:
            sip_i = rng.integers(0, n_src, size=m)
            dip_i = rng.integers(0, n_dst, size=m)
        else:
            sip_i = np.searchsorted(src_cdf, rng.random(m), side="right")
            dip_i = np.searchsorted(dst_cdf, rng.random(m), side="right")
        sports = rng.integers(1024, 60000, size=m)
        if svc_cdf is None:
            dports = svc[rng.integers(0, len(svc), size=m)]
        else:
            dports = svc[np.searchsorted(svc_cdf, rng.random(m),
                                         side="right")]
        ipkts = rng.integers(1, 100, size=m)
        ibyts = rng.integers(40, 100_000, size=m)
        f.write("\n".join(
            "2016-01-22 00:00:00,2016,1,22,"
            f"{hours[i]},{mins[i]},{secs[i]},0.0,"
            f"{fmt_src(sip_i[i])},"
            f"{fmt_dst(dip_i[i])},"
            f"{sports[i]},{dports[i]},TCP,,0,0,{ipkts[i]},{ibyts[i]},"
            "0,0,0,0,0,0,0,0,0"
            for i in range(m)
        ) + "\n")


def bench_flow_scoring(n_events=400_000, reps=3):
    """Full score_flow stage over a synthetic day — the reference's
    PRIMARY workload (flow_post_lda.scala:227-248): per event TWO
    model-row gathers and dot products (src and dest perspective),
    min(src, dest) thresholding, ascending sort, native CSV emit.
    Returns (events_per_sec, p50_seconds).  The threshold is set to the
    first run's median min-score so ~half the rows are emitted —
    representative of a real TOL without depending on the synthetic
    score distribution."""
    import os
    import tempfile

    from oni_ml_tpu.features.native_flow import featurize_flow_file
    from oni_ml_tpu.scoring import ScoringModel, score_flow_csv

    rng = np.random.default_rng(11)
    k = 20
    fd, path = tempfile.mkstemp(suffix=".csv")
    try:
        with os.fdopen(fd, "w") as f:
            _write_flow_day(f, n_events)
        feats = featurize_flow_file(path)
    finally:
        os.unlink(path)

    n = feats.num_raw_events
    if hasattr(feats, "ip_table"):         # native-backed container
        ips, vocab = list(feats.ip_table), list(feats.word_table)
    else:
        ips = sorted(
            {feats.sip(i) for i in range(n)}
            | {feats.dip(i) for i in range(n)}
        )
        vocab = sorted(set(feats.src_word[:n]) | set(feats.dest_word[:n]))
    theta = rng.dirichlet(np.ones(k), size=len(ips))
    p = rng.dirichlet(np.ones(len(vocab)), size=k).T
    model = ScoringModel.from_results(ips, theta, vocab, p, fallback=0.05)

    blob, scores = score_flow_csv(feats, model, threshold=np.inf)
    threshold = float(np.median(scores))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        blob, scores = score_flow_csv(feats, model, threshold=threshold)
        times.append(time.perf_counter() - t0)
    p50 = float(np.median(times))
    assert len(blob) and len(scores)
    return n_events / p50, p50


def bench_scoring_e2e(n_events=400_000, reps=3, chunk=None):
    """CSV-in -> results-out flow scoring at day scale through BOTH
    engines: the float64 host path (the golden-bytes oracle and
    production default) and the device pipeline (scoring/pipeline.py:
    fused gather·dot·threshold, chunked double-buffered dispatch,
    survivors-only readback, f32 on-chip).  The payload carries the
    dispatch/transfer accounting and the measured host-vs-device
    break-even (scoring.dispatch_calibration) so every round documents
    the constant the serving dispatch ran under, plus the projected
    dispatch count for a 400k-event day — the number the r05 regression
    was about (1 full-result f64 round-trip -> ceil(N/chunk) index-only
    H2D with survivors-only D2H)."""
    import os
    import tempfile

    from oni_ml_tpu.features.native_flow import featurize_flow_file
    from oni_ml_tpu.scoring import (
        DEFAULT_CHUNK,
        DispatchStats,
        ScoringModel,
        dispatch_calibration,
        score_flow_csv,
    )

    chunk = chunk or DEFAULT_CHUNK
    rng = np.random.default_rng(11)
    k = 20
    fd, path = tempfile.mkstemp(suffix=".csv")
    try:
        with os.fdopen(fd, "w") as f:
            _write_flow_day(f, n_events)
        t0 = time.perf_counter()
        feats = featurize_flow_file(path)     # CSV-in
        featurize_s = time.perf_counter() - t0
    finally:
        os.unlink(path)
    n = feats.num_raw_events
    if hasattr(feats, "ip_table"):
        ips, vocab = list(feats.ip_table), list(feats.word_table)
    else:
        ips = sorted(
            {feats.sip(i) for i in range(n)} | {feats.dip(i) for i in range(n)}
        )
        vocab = sorted(set(feats.src_word[:n]) | set(feats.dest_word[:n]))
    theta = rng.dirichlet(np.ones(k), size=len(ips))
    p = rng.dirichlet(np.ones(len(vocab)), size=k).T
    model = ScoringModel.from_results(ips, theta, vocab, p, fallback=0.05)

    # Representative TOL (half the rows emitted) picked from a host
    # warmup pass; the same pass warms caches for the timed reps.
    _, scores = score_flow_csv(feats, model, threshold=np.inf)
    threshold = float(np.median(scores))
    # Compile the device programs outside the timed region.
    score_flow_csv(feats, model, threshold, engine="device", chunk=chunk)

    out_path = path + ".results"
    rates, stats = {}, None
    try:
        for engine in ("host", "device"):
            times = []
            for _ in range(reps):
                st = DispatchStats() if engine == "device" else None
                t0 = time.perf_counter()
                blob, s = score_flow_csv(
                    feats, model, threshold,
                    engine=engine, chunk=chunk, stats=st,
                )
                with open(out_path, "wb") as f:
                    f.write(blob)                 # results-out
                times.append(time.perf_counter() - t0)
                if st is not None:
                    stats = st
            p50 = float(np.median(times))
            rates[engine] = (n_events / p50, p50)
            assert len(blob) and len(s)
    finally:
        if os.path.exists(out_path):
            os.unlink(out_path)
    host_eps, host_p50 = rates["host"]
    dev_eps, dev_p50 = rates["device"]
    return {
        # Headline: CSV-in -> results-out through the production
        # default engine (featurize + host score + write).
        "value": round(n_events / (featurize_s + host_p50), 1),
        "unit": "events/sec",
        "n_events": n_events,
        "featurize_s": round(featurize_s, 3),
        "host_events_per_sec": round(host_eps, 1),
        "host_p50_s": round(host_p50, 3),
        "device_events_per_sec": round(dev_eps, 1),
        "device_p50_s": round(dev_p50, 3),
        "chunk": chunk,
        "dispatch": stats.as_record(),
        "projected_dispatches_400k": -(-400_000 // chunk),
        "calibration": dispatch_calibration(),
        # Bench-settings note: scoring runs at
        # the module defaults; no non-default dispatch caps here.
        "engine_default": "host (float64 oracle)",
    }


def _write_dns_day(f, n_events, n_clients=20_000, n_doms=5_000, seed=13,
                   chunk=200_000):
    """Write a synthetic 8-column DNS day (CSV) chunked to an open
    file."""
    rng = np.random.default_rng(seed)
    for start in range(0, n_events, chunk):
        m = min(chunk, n_events - start)
        ts = rng.integers(1454000000, 1454086400, size=m)
        flen = rng.integers(40, 1500, size=m)
        cli = rng.integers(0, n_clients, size=m)
        dom = rng.integers(0, n_doms, size=m)
        sub = rng.integers(0, 500, size=m)
        qtype = rng.integers(1, 17, size=m)
        rcode = rng.integers(0, 4, size=m)
        f.write("\n".join(
            f"t,{ts[i]},{flen[i]},"
            f"10.{cli[i] >> 8}.{cli[i] & 255}.9,"
            f"sub{sub[i]}.dom{dom[i]}.com,1,{qtype[i]},{rcode[i]}"
            for i in range(m)
        ) + "\n")


def critical_path_summary(metrics, total_s):
    """The streaming dataplane's headline accounting: per-stage wall
    (inline wall + the stage's background tasks/checkpoint writes, i.e.
    the stage's TOTAL work), the sum of those walls (what a fully
    serial execution would cost), the overlapped end-to-end wall, and

        overlap_efficiency = 1 - e2e / sum_of_stage_walls

    — the fraction of total work the stage overlap hid (0 on a serial
    run; negative would mean the dataplane added more glue than it
    overlapped, which is exactly the regression this number exists to
    catch via tools/bench_diff.py)."""
    stage_wall = {
        m["stage"]: float(m["wall_s"]) for m in metrics
        if "wall_s" in m and m["stage"] in ("pre", "corpus", "lda",
                                            "score")
    }
    dp = next((m for m in metrics if m.get("stage") == "dataplane"), None)
    per_stage = dict(stage_wall)
    background = 0.0
    if dp is not None:
        for task in dp.get("tasks", {}).values():
            if not task.get("ok"):
                continue
            # A task's channel-backpressure stall (a producer blocked
            # in put() while its consumer works) is idle wait, not
            # work — counting it would double-count the consumer's
            # inline wall and inflate overlap_efficiency.
            work = task["wall_s"] - task.get("stall_s", 0.0)
            background += work
            if task.get("stage") in per_stage:
                per_stage[task["stage"]] += work
    work = sum(per_stage.values())
    out = {
        "per_stage_wall_s": {k: round(v, 3) for k, v in per_stage.items()},
        "stage_wall_s": {k: round(v, 3) for k, v in stage_wall.items()},
        "background_wall_s": round(background, 3),
        "sum_of_stage_walls_s": round(work, 3),
        "e2e_wall_s": round(total_s, 3),
        "overlap_efficiency": (
            round(1.0 - total_s / work, 4) if work > 0 else None
        ),
    }
    if dp is not None:
        out["edges"] = dp.get("edges", {})
    return out


def bench_pipeline_e2e(n_events=5_000_000, n_src=40_000, n_dst=8_000,
                       em_max_iters=40, dsource="flow", pre_workers=0,
                       compare_pre_workers1=True):
    """One full `run_pipeline` day — the reference's actual unit of work
    (`./ml_ops.sh YYYYMMDD flow`, timed per stage at ml_ops.sh:57-108):
    featurize + word counts, corpus build, LDA to convergence, scoring +
    emit, on a synthetic ~5M-event flow day.  Returns (total_seconds,
    {stage: seconds}, events_per_sec, pre_detail, critical_path) so any
    host-side stage that comes to dominate the device work is visible
    in the breakdown, and the dataplane's stage overlap is a tracked
    headline number (critical_path["overlap_efficiency"]).

    `pre_detail` carries the pre stage's parallel-featurization record:
    resolved worker count, per-pass walls, merge overhead, the
    featurizer→corpus handoff mode, and — when `compare_pre_workers1`
    and the resolved count is > 1 — a `pre_s_workers1` sequential
    re-measurement of just the pre stage, so the sharding win (or
    single-core parity) is recorded in the bench payload itself."""
    import shutil
    import tempfile

    from oni_ml_tpu.config import (
        FeedbackConfig,
        LDAConfig,
        PipelineConfig,
        ScoringConfig,
    )
    from oni_ml_tpu.features.shards import resolve_pre_workers
    from oni_ml_tpu.runner.ml_ops import Stage, run_pipeline

    # Under the orchestrator, BENCH_E2E_DIR scopes this run's day dirs
    # so the parent can clean up a killed child's leftovers without
    # touching other processes' tempdirs.
    work = tempfile.mkdtemp(prefix="oni_e2e_",
                            dir=os.environ.get("BENCH_E2E_DIR") or None)
    try:
        raw = os.path.join(work, f"{dsource}_day.csv")
        with open(raw, "w") as f:
            if dsource == "flow":
                _write_flow_day(f, n_events, n_src=n_src, n_dst=n_dst)
            else:
                _write_dns_day(f, n_events, n_clients=n_src)
        cfg = PipelineConfig(
            data_dir=work,
            flow_path=raw if dsource == "flow" else "",
            dns_path=raw if dsource == "dns" else "",
            lda=LDAConfig(batch_size=4096, em_max_iters=em_max_iters),
            feedback=FeedbackConfig(),
            # Reference-like tiny TOL: almost nothing emitted — the
            # emit-heavy path is measured by bench_flow_scoring.
            scoring=ScoringConfig(threshold=1e-20),
            pre_workers=pre_workers,
        )
        t0 = time.perf_counter()
        metrics = run_pipeline(cfg, "20160122", dsource, force=True)
        total = time.perf_counter() - t0
        stages = {
            m["stage"]: round(m["wall_s"], 2)
            for m in metrics
            if "wall_s" in m
        }
        pre_rec = next(
            (m for m in metrics if m.get("stage") == "pre"), {}
        )
        pre_detail = {
            "pre_workers": pre_rec.get("pre_workers"),
            "wall": pre_rec.get("wall"),
            "handoff": next(
                (m.get("handoff") for m in metrics
                 if m.get("stage") == "corpus"), None,
            ),
        }
        if "merge_wall_s" in pre_rec:
            pre_detail["merge_wall_s"] = pre_rec["merge_wall_s"]
        critical = critical_path_summary(metrics, total)
        if compare_pre_workers1 and resolve_pre_workers(pre_workers) > 1:
            # Sequential baseline of JUST the pre stage into a second
            # day dir (same raw file): the sharding comparison the
            # acceptance contract wants recorded, without re-running
            # LDA/scoring.
            work1 = os.path.join(work, "w1")
            os.makedirs(work1, exist_ok=True)
            m1 = run_pipeline(
                cfg.replace(data_dir=work1, pre_workers=1),
                "20160122", dsource, force=True, stages=[Stage.PRE],
            )
            w1 = next(
                (m["wall_s"] for m in m1
                 if m.get("stage") == "pre" and "wall_s" in m), None,
            )
            if w1 is not None and stages.get("pre"):
                pre_detail["pre_s_workers1"] = round(w1, 2)
                pre_detail["pre_speedup_vs_workers1"] = round(
                    w1 / stages["pre"], 2
                )
        return total, stages, n_events / total, pre_detail, critical
    finally:
        shutil.rmtree(work, ignore_errors=True)


class _Record:
    """The single growing JSON record.  `emit()` prints the whole line
    and flushes; the driver parses the LAST line, so re-printing after
    each completed phase means a run cut short loses only the phases
    that never finished."""

    def __init__(self):
        self.data = None

    def set_headline(self, **kw):
        self.data = dict(kw)
        self.emit()

    def add_secondary(self, name, payload):
        self.data.setdefault("secondary", {})[name] = payload
        self.emit()

    def annotate(self, key, value):
        """Top-level annotation on the grown record."""
        self.data[key] = value
        self.emit()

    def emit(self):
        if self.data is not None:
            print(json.dumps(self.data), flush=True)


def _emit_failure(error: str) -> None:
    """Final stdout line for a run with no headline: the error and no
    number."""
    print(json.dumps({
        "metric": "lda_em_throughput", "value": None, "unit": "docs/sec",
        "error": error,
    }), flush=True)


# Headline shape: config-1 suspicious-connects scale.
HEADLINE_SHAPE = (20, 8192, 4096, 128)          # (K, V, B, L)
PRECISION = "bf16"


def _engine_label(use_dense: bool, precision: str = PRECISION, *,
                  warm: bool = False, compact: bool = False) -> str:
    """One place to spell the record's engine field — five hand-built
    ternaries drifted apart once already (a hardcoded convergence
    label survived a sparse fallback).  Every EM phase runs the same
    fused run_chunk driver, so 'fused+' is unconditional."""
    if not use_dense:
        return "fused+sparse"
    kind = "fused+" + ("compact-dense" if compact else "dense")
    return kind + "+" + precision + ("+warm" if warm else "")


def _headline_chunk():
    """The headline phase's EM chunk, resolved through the plan cache
    (oni_ml_tpu/plans): on a backend with a recorded sweep the bench
    LOADS the measured winner instead of re-sweeping; elsewhere it
    runs the shipped default.  Returns (chunk, source)."""
    from oni_ml_tpu import plans

    k1, v1, b1, l1 = HEADLINE_SHAPE
    chunk, src = plans.resolve(
        "fused_em_chunk", None, shape=f"k{k1}.v{v1}.b{b1}.l{l1}"
    )
    return int(chunk), src


def bench_plans_payload() -> dict:
    """The record's `plans` section: per-knob resolved value + source +
    measurement provenance for the tuning constants this round ran
    under, plus the backend fingerprints the cache was keyed by."""
    from oni_ml_tpu import plans

    chunk, chunk_src = _headline_chunk()
    out = {
        "backend": plans.device_fingerprint(),
        "host": plans.host_fingerprint(),
        "store": plans.default_path(),
        "knobs": {
            "fused_em_chunk": {"value": chunk, "source": chunk_src},
        },
    }
    store = plans.current_store()
    if store is None:
        out["disabled"] = True
        return out
    fps = (plans.device_fingerprint(), plans.host_fingerprint())
    for e in store.entries():
        if e.backend not in fps:
            continue
        rec = out["knobs"].setdefault(e.knob, {})
        prov = {"value": e.value, "shape": e.shape,
                "entry_source": e.source}
        if e.measurements:
            prov["measurements"] = e.measurements
        rec.setdefault("entries", []).append(prov)
    return out


def phase_headline():
    """Config-1 at the bench's fastest supported configuration — warm
    start (the production default since round 3) + bf16 operand storage
    (opt-in; LDAConfig.dense_precision defaults to f32).  The engine
    field names both so the number stays attributable; the fresh-start
    phase covers lda-c reference semantics.  The EM chunk comes from
    the plan cache (_headline_chunk) — a backend with a recorded sweep
    runs its measured winner instead of re-deriving it."""
    k1, v1, b1, l1 = HEADLINE_SHAPE
    chunk, chunk_src = _headline_chunk()
    em = bench_em(k1, v1, b1, l1, chunk=chunk, precision=PRECISION,
                  warm_start=True)
    util = (
        em_utilization(k1, v1, b1, em["t_iter"], wmajor=em["wmajor"],
                       precision=PRECISION,
                       corpus_itemsize=em["corpus_itemsize"],
                       var_max_iters=em["mean_vi"])
        if em["use_dense"]
        else {}
    )
    engine = _engine_label(em["use_dense"], warm=True)
    # Measured dense-vs-sparse crossover at the headline shape: both
    # engines through the real chunk driver, winner persisted to the
    # plan cache (run 2 resolves it with source "plan"), per-engine
    # roofline carrying effective vs dense-equivalent FLOPs.  Short
    # chunk/rounds: this is an attribution section, not the headline.
    dvs = bench_dense_vs_sparse(k1, v1, b1, l1,
                                chunk=min(chunk, 32), rounds=2)
    return {"value": round(em["docs_per_sec"], 1), "unit": "docs/sec",
            "engine": engine, "utilization": util,
            "estep_engine": em.get("estep_engine"),
            "dense_vs_sparse": dvs,
            # The measured (cost-analysis) twin of the analytic
            # `utilization` model above — tracked side by side so drift
            # between the two is itself a finding.
            "roofline": em.get("roofline"),
            "flops_effective_per_iter": em.get("flops_effective_per_iter"),
            "flops_dense_equiv_per_iter": em.get(
                "flops_dense_equiv_per_iter"),
            "mean_vi_iters": round(em["mean_vi"], 2),
            "chunk": em["chunk"],
            "chunk_source": chunk_src,
            "alpha_max_iters": em["alpha_max_iters"],
            # Computed HERE, in the phase subprocess that already owns
            # a backend: the orchestrator must never initialize one
            # (bench.py's subprocess-isolation contract), so it lifts
            # this section from the headline payload instead of
            # fingerprinting the device itself.
            "plans": bench_plans_payload()}


def phase_fresh_start():
    """Headline config under the reference's fresh-start gamma init
    (lda-c likelihood.dat semantics, what runner/lda_cli.py pins and
    --no-warm-start selects) — reported so the warm-start default's
    gain stays attributable."""
    k1, v1, b1, l1 = HEADLINE_SHAPE
    em_f = bench_em(k1, v1, b1, l1, rounds=3, warm_start=False,
                    precision=PRECISION)
    return {"value": round(em_f["docs_per_sec"], 1), "unit": "docs/sec",
            "mean_vi_iters": round(em_f["mean_vi"], 2),
            "engine": _engine_label(em_f["use_dense"])}


def phase_k50_v50k():
    """Config-3 scale (BASELINE.json: 50 topics, full vocabulary)."""
    em3 = bench_em(50, 50_000, 2048, 128, rounds=3,
                   precision=PRECISION, warm_start=True)
    return {"value": round(em3["docs_per_sec"], 1), "unit": "docs/sec",
            "engine": _engine_label(em3["use_dense"], warm=True)}


def phase_online_svi():
    """Config-5: streaming SVI steady state at the headline shape."""
    return {"value": round(bench_online_svi(), 1), "unit": "docs/sec"}


def phase_convergence():
    """Wall-clock to convergence (BASELINE.json's first named metric).
    Runs the headline engine configuration (warm+bf16 when dense is
    feasible); the engine field keeps the cross-round semantics
    attributable — r01's convergence number was fresh-start f32."""
    conv_s, conv_iters, conv_ll, engine = bench_convergence()
    return {"value": round(conv_s, 3), "unit": "seconds",
            "em_iters": conv_iters, "final_ll": round(conv_ll, 1),
            "engine": engine}


def phase_dns_scoring():
    """DNS scoring stage (BASELINE.md "DNS scoring p50")."""
    score_eps, score_p50 = bench_dns_scoring()
    return {"value": round(score_eps, 1), "unit": "events/sec",
            "p50_seconds": round(score_p50, 3), "n_events": 400_000}


def phase_flow_scoring():
    """Flow scoring stage — the reference's primary workload (doubled
    min(src,dest) gather, flow_post_lda.scala:227-248)."""
    flow_eps, flow_p50 = bench_flow_scoring()
    return {"value": round(flow_eps, 1), "unit": "events/sec",
            "p50_seconds": round(flow_p50, 3), "n_events": 400_000}


def phase_scoring_e2e():
    """CSV-in -> results-out scoring through both engines, with the
    dispatch/transfer probe and the measured host-vs-device break-even
    in the payload (tracked per round since the r05 device-loses
    regression)."""
    return bench_scoring_e2e()


def phase_config4():
    """Config-4 scale (BASELINE.json: high-cardinality DNS vocab,
    dns_pre_lda.scala:320-326).  At V=512k the full-V dense corpus
    cannot fit one chip's VMEM blocks/HBM budget; word ids drawn
    log-uniformly (zipf s≈1) — the realistic frequency law for the
    combinatorial DNS word space — let the compact-vocab dense engine
    turn the batch's few tens of thousands of distinct words back into
    MXU matmuls.  The multi-chip design for this config is
    parallel.make_vocab_sharded_dense_e_step (C and beta column-sharded
    over `model`, [B, K] psum per fixed-point iteration),
    correctness-pinned on the virtual mesh."""
    em4 = bench_em(20, 524_288, 2048, 128, rounds=2, warm_start=True,
                   compact=True, word_law="loguniform")
    engine4 = _engine_label(
        em4["use_dense"] or em4.get("engine_variant") == "compact",
        warm=True, compact=em4.get("engine_variant") == "compact",
    )
    out = {"value": round(em4["docs_per_sec"], 1), "unit": "docs/sec",
           "v": 524_288, "engine": engine4,
           "word_law": "loguniform",
           "multichip_plan": "vocab_sharded_dense"}
    if "compact_width" in em4:
        out["compact_width"] = em4["compact_width"]
        out["unique_words"] = em4["unique_words"]
    return out


def bench_serving_slo(n_events=4096, rate_eps=4000.0, burst_len=64,
                      max_batch=256, max_wait_ms=10.0,
                      device_score_min=0):
    """Sustained events/s + p50/p99/p999 latency through the REAL
    serving stack (ModelRegistry -> BatchScorer -> futures) under
    Poisson and bursty arrivals from tools/load_gen.py — the number the
    'millions of users' claim is judged against (ROADMAP item 3).
    Quantiles come off the shared fixed-boundary histogram, the same
    estimator `ml_ops serve --metrics-port` exposes live."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    return load_gen.run_slo(
        n_events=n_events, rate_eps=rate_eps, burst_len=burst_len,
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        # 0 = auto: the measured dispatch calibration prices host vs
        # device, exactly like production serve.
        device_score_min=device_score_min,
    )


def phase_serving_slo():
    """Serving SLO under open-loop load: headline value is the
    sustained Poisson events/s; the payload carries both patterns'
    p50/p99/p999 so tail blowup under bursts is tracked per round."""
    res = bench_serving_slo()
    poisson = res.get("poisson", {})
    return {"value": poisson.get("sustained_eps"), "unit": "events/sec",
            **res}


def bench_serving_slo_fleet(n_tenants=4, mix="poisson:1,bursty:1",
                            n_events=4096, rate_eps=4000.0,
                            burst_len=64, max_batch=256,
                            max_wait_ms=10.0, device_score_min=0):
    """Multi-tenant serving SLO: >= 4 tenants with weighted mixed
    Poisson/bursty arrivals multiplexed through ONE FleetScorer and
    one shared compiled batch family (serving/fleet.py) — the
    multi-tenant number behind the 'millions of users' claim
    (ROADMAP item 3 close-out).  Reports per-tenant sustained
    events/s and p50/p99/p999 alongside the aggregate, plus the
    plans-counter proof that the measured window performed ZERO
    per-tenant retraces after the warmup burst (the compiled family is
    keyed by shape, not tenant)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    return load_gen.run_fleet_slo(
        n_tenants, mix, n_events=n_events, rate_eps=rate_eps,
        burst_len=burst_len, max_batch=max_batch,
        max_wait_ms=max_wait_ms, device_score_min=device_score_min,
    )


def phase_serving_slo_fleet():
    """Fleet SLO under cross-tenant open-loop load: headline value is
    the aggregate sustained events/s over >= 4 tenants; the payload
    carries each tenant's pattern, sustained rate, and latency
    quantiles, so per-tenant tail isolation is tracked per round — and
    the plans section must show retraces_after_warmup == 0."""
    res = bench_serving_slo_fleet()
    agg = res.get("aggregate", {})
    return {"value": agg.get("sustained_eps"), "unit": "events/sec",
            **res}


def bench_serving_slo_fleet_paged(n_tenants=256, zipf_s=1.1,
                                  hot_tenants=32, warm_tenants=64,
                                  mix="poisson:1,bursty:1",
                                  n_events=6144, rate_eps=6000.0,
                                  burst_len=64, max_batch=256,
                                  max_wait_ms=10.0,
                                  device_score_min=0):
    """Thousand-tenant-class serving under tiered model residency
    (serving/residency.py): a Zipf-distributed census whose working
    set EXCEEDS the HBM-hot capacity (hot_tenants << n_tenants, the
    warm tier bounded too so the tail pages through checkpoint-cold
    spills), driven open-loop through one FleetScorer.  Reports
    sustained events/s and per-tenant p50/p99/p999 *including*
    promotion misses (a paging tenant's futures wait out its own
    promotion), promotion/eviction/cold-load counts with the total
    priced promotion stall, final tier occupancy — and the
    plans-counter proof that the whole promote/evict churn performed
    ZERO post-warmup retraces (the compiled family is keyed by the
    power-of-two capacity tier, not by which tenants are resident)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    return load_gen.run_fleet_slo(
        n_tenants, mix, n_events=n_events, rate_eps=rate_eps,
        burst_len=burst_len, max_batch=max_batch,
        max_wait_ms=max_wait_ms, device_score_min=device_score_min,
        zipf_s=zipf_s, hot_tenants=hot_tenants,
        warm_tenants=warm_tenants,
    )


def bench_serving_slo_replicated(replica_counts=(1, 2, 4),
                                 n_tenants=256, zipf_s=1.1,
                                 events_per_replica=3072,
                                 chaos_events=4096,
                                 chaos_rate_eps=1500.0,
                                 route_window=64, max_wait_ms=20.0):
    """Replicated elastic serving (serving/router.py + replica.py +
    placement.py, ROADMAP item 5): the 256-tenant Zipf census behind
    the async router on 1, 2, and 4 REAL replica subprocesses
    (`ml_ops replica` — own Python, own backend, honest blast
    radius).  Saturation legs measure aggregate sustained events/s per
    replica count — per-replica capacity is the router's bounded
    admission window over the round trip (Little's law), so the
    aggregate scales near-linearly until the host's cores saturate —
    and the chaos leg SIGKILLs one of two replicas mid-replay:
    shadow promotion + admission-journal replay must yield ZERO failed
    futures (victims included), bit-identical survivor scores, a
    bounded p999 during the failover window, and zero post-recovery
    retraces on the survivor (the compiled family came off the shared
    plan/compilation cache at warmup)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    return load_gen.run_replicated_slo(
        replica_counts, n_tenants=n_tenants, zipf_s=zipf_s,
        events_per_replica=events_per_replica,
        chaos_events=chaos_events, chaos_rate_eps=chaos_rate_eps,
        route_window=route_window, max_wait_ms=max_wait_ms,
        spawn="process",
    )


def phase_serving_slo_replicated():
    """Replicated serving SLO: headline value is the aggregate
    sustained events/s at the LARGEST replica count; the payload
    carries sustained eps per count, replica_scaling_efficiency (>=
    0.7 at 2 replicas is the acceptance floor), the chaos phase's
    failover p999 / time-to-recovery / zero-failed-futures proof, and
    the zero-retrace counters — all gated by bench_diff direction
    keys."""
    res = bench_serving_slo_replicated()
    top = str(max(res["replica_counts"]))
    return {"value": res["sustained_eps_by_count"].get(top),
            "unit": "events/sec", **res}


def bench_serving_crosshost(router_counts=(1, 2)):
    """Cross-host serving (serving/wire.py + autoscale.py +
    parallel/membership.py over TCP): the columnar zero-copy wire
    under multi-router fan-in and a Little's-law autoscaler.  Three
    legs, all on REAL subprocess boundaries: (1) fan-in — the same
    census driven by 1 then 2 router PROCESSES against a shared
    replica fleet; each router bounds its own per-edge admission
    window, so aggregate events/s must exceed the single-router
    admission ceiling with zero router-to-router coordination (the
    acceptance gate) and bit-identical scores against the in-process
    oracle; (2) router-kill chaos — SIGKILL one of two routers
    mid-replay; the survivor absorbs the victim's census from its
    last progress checkpoint with zero failed futures and
    bit-identical redriven scores; (3) autoscale — an offered-load
    staircase under the occupancy controller; the fleet must grow on
    the step up (reaction_s journaled per decision) and drain back
    down after, every decision in the ``{"kind": "autoscale"}``
    ledger carried in the payload."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    return load_gen.run_crosshost_slo(router_counts)


def phase_serving_crosshost():
    """Cross-host serving SLO: headline value is the aggregate
    sustained events/s at the largest router count; the payload
    carries aggregate eps per router count, router_scaling_efficiency
    and the fanin_exceeds_single_router gate, wire_bytes_per_event
    for the columnar frames, the chaos leg's zero-failed-futures +
    bit-identical proof, and the autoscaler's decision ledger with
    scale_up_reaction_s — all gated by bench_diff direction keys."""
    res = bench_serving_crosshost()
    return {"value": res["sustained_eps"], "unit": "events/sec",
            **res}


def phase_serving_slo_fleet_paged():
    """Paged fleet SLO: headline value is the aggregate sustained
    events/s over a 256-tenant Zipf census with only 32 HBM-hot slots
    (working set > HBM-hot capacity by construction); the payload
    carries the head tenants' quantiles, a distribution summary over
    every tenant, the residency ledger (promotions / evictions /
    cold loads / promotion_stall_s), and the zero-retrace proof."""
    res = bench_serving_slo_fleet_paged()
    agg = res.get("aggregate", {})
    return {"value": agg.get("sustained_eps"), "unit": "events/sec",
            **res}


# -- device-resident featurization --------------------------------------


def bench_featurize_device(batch_sizes=(512, 2048, 8192), repeats=5,
                           fleet_tenants=16, fleet_events=6144,
                           seed=11):
    """Host vs device vs fused featurization (sources/device.py +
    ops/featurize_kernel.py) over the synthetic DNS day, at several
    micro-batch sizes, plus a saturated fleet A/B re-run.

    Three engines over identical pre-admitted rows, each timed
    through featurize AND score (the unit serving actually pays per
    flush):

      * host  — the golden-oracle event featurizer (per-row Python
        word building) feeding batched_scores;
      * device — the compiled table path (vectorized parse + packed
        codes + row gather, the serving default; scores stay bitwise
        identical to host) feeding the same batched_scores;
      * fused — featurize+gather+dot in ONE jitted dispatch
        (fused_featurize_scores, f32 on-chip).

    The fleet leg re-runs the fleet SLO harness saturated (offered
    rate far above capacity, so sustained events/s measures drain
    capacity per replica, not the arrival pacing) under
    ONI_ML_TPU_FEATURIZE=host and =device, and reports the events/s
    ratio — the serving-visible win of the featurize plane.  The
    device legs also dispatch `lut_rows` once so the run carries a
    `serve.featurize_rows` roofline harvest record (wall-only on
    CPU), and the fleet payloads carry the zero-post-warmup-retrace
    counters."""
    from oni_ml_tpu.ops.featurize_kernel import lut_rows
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.scoring.pipeline import fused_featurize_scores
    from oni_ml_tpu.scoring.score import batched_scores
    from oni_ml_tpu.sources import get as get_source
    from oni_ml_tpu.sources.device import DeviceBatch, compile_featurizer

    spec = get_source("dns")
    day, model, cuts = _synthetic_day(
        n_events=max(batch_sizes), n_clients=64, n_doms=16, seed=seed
    )
    rows = [r.strip().split(",") if isinstance(r, str) else list(r)
            for r in day]
    fz = spec.event_featurizer(tuple(cuts))
    dev, info = compile_featurizer(spec, tuple(cuts), model)
    if dev is None:
        raise RuntimeError(f"featurize compile gated: {info['reason']}")

    def _time(fn):
        fn()                       # warmup (compiles + caches)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    host_eps, device_eps, fused_eps = {}, {}, {}
    for b in batch_sizes:
        sub = [rows[i % len(rows)] for i in range(b)]

        def host_leg():
            feats = fz(sub)
            ip = np.concatenate([model.ip_rows(k)
                                 for k, _ in spec.event_pairs(feats)])
            w = np.concatenate([model.word_rows(ws)
                                for _, ws in spec.event_pairs(feats)])
            return batched_scores(model, ip, w, None)

        def device_leg():
            batch = DeviceBatch(dev, fz, sub, sub)
            ip, w, _ = batch.pair_rows()
            return batched_scores(model, ip, w, None)

        def fused_leg():
            batch = DeviceBatch(dev, fz, sub, sub)
            d, codes, ip = batch.fused_operands()
            return fused_featurize_scores(model, d, codes, ip, block=b)

        host_eps[str(b)] = round(b / _time(host_leg), 1)
        device_eps[str(b)] = round(b / _time(device_leg), 1)
        fused_eps[str(b)] = round(b / _time(fused_leg), 1)
        # One on-device row-gather dispatch per tier: harvests the
        # serve.featurize_rows roofline record for this shape.
        batch = DeviceBatch(dev, fz, sub, sub)
        _, codes, _ = batch.fused_operands()
        lut_rows(dev, codes, block=b)

    top = str(max(batch_sizes))
    res = {
        "source": spec.name,
        "compile": {k: info[k] for k in
                    ("mode", "lut", "code_space", "vocab")},
        "host_eps": host_eps, "device_eps": device_eps,
        "fused_eps": fused_eps,
        "speedup_device": round(device_eps[top] / host_eps[top], 2),
        "speedup_fused": round(fused_eps[top] / host_eps[top], 2),
    }

    # Size-aware engine break-even: measure the segment size where a
    # device featurize dispatch starts beating the vectorized host
    # parse on THIS backend, and persist it as the
    # featurize_break_even plan knob — the paged A/B below then runs
    # with the knob LIVE, so its many small per-tenant segments (the
    # 0.91x regression shape) go host-side while big flushes keep the
    # device win.
    from oni_ml_tpu import plans
    from oni_ml_tpu.sources.device import measure_break_even

    break_even, be_samples = measure_break_even(fz, rows, rows, model)
    persisted = False
    if break_even is not None:
        persisted = plans.record_value(
            "featurize_break_even", int(break_even),
            source="bench.featurize_device",
            measurements={"samples": be_samples},
        )
    res["break_even"] = {
        "value": break_even, "persisted": persisted,
        "samples": be_samples,
    }

    # Fleet A/B: saturated offered rate -> sustained_eps is the drain
    # capacity of ONE replica under each featurize engine.  Best of
    # `fleet_trials` per engine: the end-to-end fleet number is
    # scheduler-noisy on a shared host, and the A/B wants capacity,
    # not the unluckiest trial.  The flat leg is the 16-tenant fleet;
    # the paged leg re-runs the tiered-residency census saturated
    # (events/s per replica before/after the featurize plane, the
    # acceptance re-run).
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import load_gen

    def _fleet_ab(run, trials=2):
        out = {}
        for engine in ("host", "device"):
            prev = os.environ.get("ONI_ML_TPU_FEATURIZE")
            os.environ["ONI_ML_TPU_FEATURIZE"] = engine
            try:
                legs = [run() for _ in range(trials)]
            finally:
                if prev is None:
                    os.environ.pop("ONI_ML_TPU_FEATURIZE", None)
                else:
                    os.environ["ONI_ML_TPU_FEATURIZE"] = prev
            best = max(legs,
                       key=lambda o: o["aggregate"]["sustained_eps"])
            out[f"{engine}_eps"] = best["aggregate"]["sustained_eps"]
            out[f"{engine}_plans"] = best.get("plans", {})
        out["speedup"] = round(out["device_eps"] / out["host_eps"], 2)
        return out

    fleet = _fleet_ab(lambda: load_gen.run_fleet_slo(
        fleet_tenants, "poisson:1", n_events=fleet_events,
        rate_eps=1e9, max_batch=256, max_wait_ms=5.0,
        device_score_min=None, seed=seed,
    ))
    paged = _fleet_ab(lambda: load_gen.run_fleet_slo(
        64, "poisson:1", n_events=fleet_events, rate_eps=1e9,
        max_batch=256, max_wait_ms=5.0, device_score_min=None,
        seed=seed, zipf_s=1.1, hot_tenants=16, warm_tenants=32,
    ))
    res["fleet"] = fleet
    res["fleet_paged"] = paged
    res["fleet_host_eps"] = fleet["host_eps"]
    res["fleet_device_eps"] = fleet["device_eps"]
    return res


def phase_featurize_device():
    """Device featurization: headline value is the fleet drain rate
    per replica under the device engine; the payload carries host/
    device/fused events/s per micro-batch tier, the compile-table
    summary (mode/LUT size/code space), the host-vs-device fleet
    speedup, and each fleet leg's zero-retrace counters — gated by
    bench_diff's featurize direction keys (events/s, higher-better)."""
    res = bench_featurize_device()
    return {"value": res["fleet_device_eps"], "unit": "events/sec",
            **res}


# -- continuous ingestion: streaming freshness --------------------------


def bench_streaming_freshness(n_events=40_000, n_src=400, n_dst=200,
                              slice_s=900.0, speed=1440.0,
                              window_s=4 * 3600.0,
                              refresh_every_s=1800.0, k=8,
                              em_max_iters=100):
    """A replayed CPU day through the continuous-ingestion service
    (runner/continuous.py): one synthetic flow day sliced by event
    time and paced at ×speed real time into the standing
    window→warm-start-EM→drift-gated-publish loop, with events scored
    through the co-resident FleetScorer the moment a model is live.

    The three headline claims this phase carries evidence for:
      * event-arrival→scored-and-servable freshness in MINUTES
        (freshness_event_p50/p99_min — cadence lag + refresh wall,
        replay-speed-invariant), vs next-day for the batch pipeline;
      * warm-start EM wall ≥~30% under fresh-fit at matched held-out
        likelihood (the fresh_control section: ONE fresh fit on the
        exact snapshot a warm refresh just trained);
      * zero post-warmup retraces while train and serve share the
        process (the window's pow2 vocab capacity tiers + full-batch
        padding + one reused WindowTrainer + the fleet's capacity-
        tiered stack)."""
    import dataclasses
    import shutil
    import tempfile

    from oni_ml_tpu.config import ContinuousConfig, PipelineConfig
    from oni_ml_tpu.runner.continuous import (
        paced_slices,
        run_continuous,
        slice_events,
    )

    workdir = tempfile.mkdtemp(
        prefix="oni_e2e_stream_", dir=os.environ.get("BENCH_E2E_DIR")
    )
    try:
        day_path = os.path.join(workdir, "day.csv")
        with open(day_path, "w") as f:
            _write_flow_day(f, n_events, n_src=n_src, n_dst=n_dst,
                            seed=17)
        with open(day_path) as f:
            lines = f.readlines()
        slices = slice_events(lines, "flow", slice_s)
        config = PipelineConfig(
            data_dir=workdir,
            continuous=ContinuousConfig(
                window_s=window_s, refresh_every_s=refresh_every_s,
            ),
        )
        config = dataclasses.replace(
            config,
            lda=dataclasses.replace(
                config.lda, num_topics=k, em_max_iters=em_max_iters
            ),
        )
        t0 = time.perf_counter()
        payload = run_continuous(
            config, "flow", paced_slices(slices, speed),
            out_dir=os.path.join(workdir, "continuous"),
            fresh_control=True,
        )
        payload["replay_wall_s"] = round(time.perf_counter() - t0, 1)
        payload["replay_speed"] = speed
        payload["n_events"] = n_events
        control = payload.get("fresh_control") or {}
        payload["warm_start_speedup"] = control.get("warm_start_speedup")
        payload["held_out_ll_delta"] = control.get("held_out_ll_delta")
        # The refresh ledger is journal/metrics material, not bench
        # payload material (it scales with the refresh count).
        payload.pop("refresh_records", None)
        return payload
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def phase_streaming_freshness():
    """Streaming freshness: headline value is the wall p50 of
    event-arrival→servable freshness over the replayed day (lower
    better); the payload carries the speed-invariant event-time
    freshness in minutes, warm-vs-fresh EM walls at matched held-out
    likelihood, publish/veto counts, and the zero-retrace proof —
    bench_diff gates freshness/warm_start_speedup/held_out_ll with
    direction-aware keys."""
    res = bench_streaming_freshness()
    return {"value": res.get("freshness_p50_s"), "unit": "seconds",
            **res}


# -- composed standing service (continuous x fleet x cosched) -----------


def _trim_fleet_payload(payload):
    """Bench-payload hygiene for a FleetContinuousService result: the
    per-tenant refresh ledgers and the router failover detail scale
    with run length — the journal holds them; the bench keeps counts."""
    for t in (payload.get("tenants") or {}).values():
        t.pop("refresh_records", None)
    router = payload.get("router") or {}
    if isinstance(router.get("failovers"), list):
        router["failovers"] = len(router["failovers"])
    return payload


def _drive_fleet(fleet, tagged, speed, *, kill=False):
    """Replay a multi-tenant tagged day through a standing fleet; when
    `kill`, SIGKILL the first tenant's primary replica mid-run —
    preferring a moment a refresh fit is actually in flight, forcing
    it by 60% of the replay otherwise."""
    from oni_ml_tpu.runner.continuous import paced_tagged

    killed = None
    n_total = len(tagged)
    for i, (tenant, sl) in enumerate(paced_tagged(tagged, speed)):
        fleet.ingest(tenant, sl)
        if kill and killed is None and fleet.binding is not None:
            ready = all(fleet.binding.ready(t) for t in fleet.streams)
            if ready and (fleet.cosched.refresh_active
                          or i >= int(0.6 * n_total)):
                victim = fleet.router.placement()[
                    min(fleet.streams)].primary
                if victim in fleet.replica_procs:
                    fleet.kill_replica(victim)
                    killed = victim
    return killed


def bench_continuous_replicated(n_events=12_000, n_src=200, n_dst=120,
                                slice_s=900.0, speed=1440.0,
                                window_s=4 * 3600.0,
                                refresh_every_s=1800.0, k=6,
                                em_max_iters=40, replicas=2):
    """The ONE-standing-service composed bench: two tenants' synthetic
    flow days interleaved in event time and replayed at ×speed through
    `FleetContinuousService` — per-tenant continuous windows, warm-
    start refreshes on the shared preemptible worker, drift-gated
    publishes fanned out to `replicas` SIGKILL-able subprocess
    replicas, every slice scored through the router.

    Two legs, one payload:
      * coscheduled leg (the product path): mid-run a chaos SIGKILL of
        a primary replica, so freshness, serve-p99-during-refresh, AND
        replica-kill recovery (zero failed futures, failovers > 0) are
        measured in the SAME run;
      * uncoscheduled control leg (`CoScheduler(enabled=False)`): same
        topology and measurement, no arbitration — the denominator for
        the co-scheduler's serve-tail claim.

    Acceptance (bench_diff keys): serve p99 during refresh stays
    within 2x idle p99, event-time freshness in minutes no worse than
    the single-tenant streaming_freshness phase, failed_futures == 0
    through the kill, zero post-warmup retraces."""
    import dataclasses
    import shutil
    import tempfile

    from oni_ml_tpu.config import ContinuousConfig, PipelineConfig
    from oni_ml_tpu.runner.continuous import (
        FleetContinuousService,
        interleave_streams,
        slice_events,
    )

    workdir = tempfile.mkdtemp(
        prefix="oni_e2e_fleet_", dir=os.environ.get("BENCH_E2E_DIR")
    )
    try:
        per_tenant = {}
        for idx, tenant in enumerate(("acme", "globex")):
            day_path = os.path.join(workdir, f"{tenant}.csv")
            with open(day_path, "w") as f:
                _write_flow_day(f, n_events // 2, n_src=n_src,
                                n_dst=n_dst, seed=23 + idx)
            with open(day_path) as f:
                lines = f.readlines()
            per_tenant[tenant] = slice_events(lines, "flow", slice_s)
        tagged = interleave_streams(per_tenant)
        streams = {t: "flow" for t in per_tenant}
        config = PipelineConfig(
            data_dir=workdir,
            continuous=ContinuousConfig(
                window_s=window_s, refresh_every_s=refresh_every_s,
            ),
        )
        config = dataclasses.replace(
            config,
            lda=dataclasses.replace(
                config.lda, num_topics=k, em_max_iters=em_max_iters
            ),
        )

        def _leg(name, coscheduled, kill):
            fleet = FleetContinuousService(
                config, streams,
                out_dir=os.path.join(workdir, name),
                replicated=replicas, coscheduler=coscheduled,
                # Named, not defaulted: this process trains on the
                # device it holds, its replicas serve on the host CPU.
                replica_platform="cpu",
            )
            t0 = time.perf_counter()
            try:
                killed = _drive_fleet(
                    fleet, tagged, speed, kill=kill)
            finally:
                payload = fleet.close()
            payload["replay_wall_s"] = round(
                time.perf_counter() - t0, 1)
            payload["killed_replica"] = killed
            return _trim_fleet_payload(payload)

        main = _leg("cosched", True, kill=True)
        control = _leg("control", False, kill=False)

        serving = main.get("serving") or {}
        ctrl_serving = control.get("serving") or {}
        cosched = main.get("cosched") or {}

        def _ms(v):
            return round(v * 1e3, 3) if v is not None else None

        idle = serving.get("serve_idle_p99_ms")
        during = serving.get("serve_refresh_p99_ms")
        ratio = (round(during / idle, 3)
                 if during and idle else None)
        res = {
            "replicas": replicas,
            "replay_speed": speed,
            "n_events": main.get("events"),
            "events_scored": serving.get("events_scored"),
            "failed_futures": serving.get("failed_futures"),
            "failovers": (main.get("router") or {}).get("failovers"),
            "killed_replica": main.get("killed_replica"),
            "freshness_p50_s": main.get("freshness_p50_s"),
            "freshness_p99_s": main.get("freshness_p99_s"),
            "freshness_event_p50_min": main.get(
                "freshness_event_p50_min"),
            "freshness_event_p99_min": main.get(
                "freshness_event_p99_min"),
            "p99_idle_ms": idle,
            "p99_during_refresh_ms": during,
            "refresh_over_idle_ratio": ratio,
            "p99_idle_uncoscheduled_ms": ctrl_serving.get(
                "serve_idle_p99_ms"),
            "p99_during_refresh_uncoscheduled_ms": ctrl_serving.get(
                "serve_refresh_p99_ms"),
            "yield_wait_p99_ms": _ms(cosched.get("yield_wait_p99_s")),
            "preempt_wait_p99_ms": _ms(
                cosched.get("preempt_wait_p99_s")),
            "train_chunks": cosched.get("train_chunks"),
            "yields": cosched.get("yields"),
            "preempts": cosched.get("preempts"),
            "refreshes": main.get("refreshes"),
            "publishes": main.get("publishes"),
            "coalesced_refreshes": main.get("coalesced_refreshes"),
            "refresh_errors": main.get("refresh_errors"),
            "retraces_after_warmup": main.get("retraces_after_warmup"),
            "sustained_eps": (
                round(main["events"] / main["replay_wall_s"], 1)
                if main.get("events") and main.get("replay_wall_s")
                else None),
            "replay_wall_s": main.get("replay_wall_s"),
            "coscheduled": main,
            "uncoscheduled": control,
        }
        return res
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def phase_continuous_replicated():
    """Composed standing service: headline value is the serve p99
    DURING a refresh fit (lower better) on the coscheduled leg — the
    number the two-priority chunk scheduler exists to hold down; the
    payload carries the uncoscheduled control leg, the fleet freshness
    quantiles, the chaos-kill recovery proof (failed_futures == 0,
    failovers >= 1), yield/preempt tails, and the zero-retrace count —
    bench_diff gates them with direction-aware keys."""
    res = bench_continuous_replicated()
    return {"value": res.get("p99_during_refresh_ms"),
            "unit": "ms", **res}


# -- detection quality (labeled-injection P/R@k) ------------------------


def bench_detection_quality(n_events=8000, attack_events=8, seed=7,
                            num_topics=2, em_max_iters=15):
    """Detection-quality SLO over labeled injected days: for EVERY
    registered source, synthesize a benign day, plant the source's
    attack scenarios (sources/inject.py), train a small LDA on the
    injected day, and score it back through the serving path
    (sources/quality.QualitySuite) — precision/recall@k and
    score-separation per scenario, all higher-better.

    The shape is deliberate: a large MODAL benign day (discrete value
    modes concentrate benign word mass), attacks rare relative to it
    (8 events/scenario in 8000), and only 2 topics so the model has no
    spare capacity to dedicate a topic to the attack tokens — the
    regime where rank-based metrics mean something (see
    sources/builtin.py synth_benign docstrings)."""
    from oni_ml_tpu import sources as src_registry
    from oni_ml_tpu.config import LDAConfig, ScoringConfig
    from oni_ml_tpu.io.corpus import Corpus
    from oni_ml_tpu.models import train_corpus
    from oni_ml_tpu.scoring import ScoringModel
    from oni_ml_tpu.sources import inject, quality

    per_source = {}
    for name in src_registry.names():
        spec = src_registry.get(name)
        t0 = time.perf_counter()
        day = inject.inject_scenarios(
            name, n_events=n_events, seed=seed,
            attack_events=attack_events,
        )
        feats = spec.featurize(day.lines)
        cuts = spec.cuts_of(feats)
        corpus = Corpus.from_features(feats)
        cfg = LDAConfig(num_topics=num_topics,
                        em_max_iters=em_max_iters)
        res = train_corpus(corpus, cfg, out_dir=None, save_final=False)
        model = ScoringModel.from_lda(
            corpus.doc_names, res.gamma, corpus.vocab, res.log_beta,
            spec.fallback(ScoringConfig()),
        )
        suite = quality.QualitySuite(
            name, cuts, n_events=n_events, seed=seed,
            attack_events=attack_events,
        )
        out = suite.evaluate(model)
        out["vocab"] = len(corpus.vocab)
        out["docs"] = corpus.num_docs
        out["wall_s"] = round(time.perf_counter() - t0, 2)
        per_source[name] = out
    return per_source


def phase_detection_quality():
    """Detection quality: headline value is the mean recall@k across
    all registered sources (higher better; 1.0 = every injected attack
    inside the top-k most-suspicious events).  The payload carries the
    full per-source / per-scenario breakdown plus precision@k and
    score-separation — bench_diff gates all three as higher-better
    keys."""
    per_source = bench_detection_quality()
    recalls = [m["recall_at_k"] for m in per_source.values()]
    return {
        "value": round(float(np.mean(recalls)), 6),
        "unit": "fraction",
        "recall_at_k": round(float(np.mean(recalls)), 6),
        "precision_at_k": round(float(np.mean(
            [m["precision_at_k"] for m in per_source.values()]
        )), 6),
        "score_separation": round(float(np.mean(
            [m["score_separation"] for m in per_source.values()]
        )), 6),
        "sources": per_source,
    }


# -- distributed EM (host-local shards + explicit allreduce) ------------


def _dist_em_corpus(docs=2048, v=2048, seed=7, mean_len=48):
    """Deterministic synthetic corpus for the distributed-EM scaling
    run — built directly in CSR so every worker process reconstructs
    the identical corpus from the seed (the shard plan, and therefore
    the reduction tree, must match across the baseline and the
    cluster run)."""
    from oni_ml_tpu.io.corpus import Corpus

    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.poisson(mean_len, docs), 4, None).astype(np.int64)
    ptr = np.zeros(docs + 1, np.int64)
    np.cumsum(lengths, out=ptr[1:])
    nnz = int(ptr[-1])
    return Corpus(
        [f"d{i}" for i in range(docs)],
        [f"w{i}" for i in range(v)],
        ptr,
        rng.integers(0, v, nnz).astype(np.int32),
        rng.integers(1, 4, nnz).astype(np.int32),
    )


def run_distributed_worker(argv) -> int:
    """`bench.py --distributed-worker PORT RANK NPROCS OUT MODE`: one
    rank of the distributed_em phase.  MODE "dist" trains through the
    host-local-shards + allreduce path; "plain" is the single-process
    fused-driver baseline on the same corpus/config.  The fit runs
    twice and the SECOND wall is reported, so both sides measure
    steady-state execution, not tracing."""
    port, rank, nprocs, out_path, mode = (
        argv[0], int(argv[1]), int(argv[2]), argv[3], argv[4]
    )
    docs = int(argv[5]) if len(argv) > 5 else 2048
    em_iters = int(argv[6]) if len(argv) > 6 else 6
    if nprocs > 1:
        from oni_ml_tpu.parallel import initialize_distributed

        initialize_distributed(f"localhost:{port}", nprocs, rank)
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models import train_corpus

    corpus = _dist_em_corpus(docs=docs)
    cfg = LDAConfig(num_topics=10, em_max_iters=em_iters, em_tol=0.0,
                    batch_size=512, min_bucket_len=16,
                    checkpoint_every=0, estimate_alpha=True)
    distributed = mode == "dist"
    res = None
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = train_corpus(corpus, cfg, distributed=distributed)
        walls.append(time.perf_counter() - t0)
    out = {
        "rank": rank,
        "mode": mode,
        "wall_s": walls[-1],
        "warm_wall_s": walls[0],
        "em_iters": res.em_iters,
        "docs": corpus.num_docs,
        "final_ll": res.likelihoods[-1][0],
        "allreduce": res.plan.get("allreduce"),
        "em_shards": (res.plan.get("em_shards") or {}).get("value"),
    }
    with open(out_path, "w") as f:
        json.dump(out, f)
    print(f"DIST_WORKER_OK {rank}", flush=True)
    return 0


def _spawn_dist_workers(workdir, nprocs, mode, timeout=300.0,
                        docs=2048, em_iters=6, precision=""):
    """Launch the worker ranks as fresh processes on the host CPU and
    collect their result JSONs.  The platform is named here, not
    inherited: the ranks reduce over the KV ring between processes on
    one host, and several processes cannot share a chip.  `precision` pins the
    suff-stats allreduce wire precision via the documented env
    override (the bf16 bytes-halving leg)."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                     "ONI_ML_TPU_ESTEP", "ONI_ML_TPU_ALLREDUCE_PRECISION")
    }
    env["JAX_PLATFORMS"] = "cpu"
    if precision:
        env["ONI_ML_TPU_ALLREDUCE_PRECISION"] = precision
    outs = [os.path.join(workdir, f"{mode}{precision}{r}.json")
            for r in range(nprocs)]
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--distributed-worker", str(port), str(r), str(nprocs),
             outs[r], mode, str(docs), str(em_iters)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(nprocs)
    ]
    logs = []
    try:
        for p in procs:
            log, _ = p.communicate(timeout=timeout)
            logs.append(log)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, log in zip(procs, logs):
        if p.returncode != 0:
            raise RuntimeError(
                f"distributed_em worker failed (rc={p.returncode}): "
                f"{log[-800:]}"
            )
    results = []
    for path in outs:
        with open(path) as f:
            results.append(json.load(f))
    return results


def bench_distributed_em(nprocs=2, docs=2048, em_iters=6):
    """2-process CPU scaling run of pod-scale distributed EM
    (models/lda.py `_train_corpus_distributed`: host-local E-step
    shards, KV-ring suff-stats allreduce) against the single-process
    fused-driver baseline on the identical corpus/config.

    Reports per-host E-step wall, allreduce bytes + wall per EM
    iteration, and scaling efficiency = T_1 / (P * T_P) — the numbers
    the billion-event-day claim needs tracked per round.  CPU walls;
    the ICI transport is not measured here."""
    import tempfile

    workdir = tempfile.mkdtemp(prefix="oni_dist_em_")
    try:
        base = _spawn_dist_workers(workdir, 1, "plain",
                                   docs=docs, em_iters=em_iters)[0]
        dist = _spawn_dist_workers(workdir, nprocs, "dist",
                                   docs=docs, em_iters=em_iters)
        # bf16 wire-compression leg: same corpus/config, the
        # suff-stats allreduce payload packed to bf16 (f32
        # accumulation after unpack) — the payload carries the
        # measured bytes halving and the likelihood drift so the
        # compression claim is evidence, not arithmetic.
        bf16 = _spawn_dist_workers(workdir, nprocs, "dist",
                                   docs=docs, em_iters=em_iters,
                                   precision="bf16")
    finally:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    per_host_wall = max(w["wall_s"] for w in dist)
    iters = max(dist[0]["em_iters"], 1)
    ar = dist[0]["allreduce"] or {}
    ar_bytes = ar.get("bytes_out", 0) + ar.get("bytes_in", 0)
    ar16 = bf16[0]["allreduce"] or {}
    ar16_bytes = ar16.get("bytes_out", 0) + ar16.get("bytes_in", 0)
    iters16 = max(bf16[0]["em_iters"], 1)
    return {
        "nprocs": nprocs,
        "docs": dist[0]["docs"],
        "em_iters": dist[0]["em_iters"],
        "em_shards": dist[0]["em_shards"],
        "transport": ar.get("transport"),
        "docs_per_sec": dist[0]["docs"] * iters / per_host_wall,
        "per_host_estep_wall_s": per_host_wall,
        "single_proc_wall_s": base["wall_s"],
        "single_proc_docs_per_sec": (
            base["docs"] * max(base["em_iters"], 1) / base["wall_s"]
        ),
        "scaling_efficiency": base["wall_s"] / (nprocs * per_host_wall),
        "allreduce_precision": ar.get("precision", "f32"),
        "allreduce_bytes_per_iter": ar_bytes / iters,
        "allreduce_wall_s_per_iter": ar.get("wall_s", 0.0) / iters,
        "allreduce_ops": ar.get("ops", 0),
        # The bf16 wire-compression leg vs the f32 leg above:
        # bytes_ratio ~0.5 on the bulk suff-stats (the gamma merge and
        # control plane stay exact, so the whole-fit ratio sits a bit
        # above one half); ll_drift is the |final-LL| delta the
        # compressed wire introduced (bf16-tolerance, not bit-equal).
        "allreduce_bf16": {
            "bytes_per_iter": ar16_bytes / iters16,
            "bytes_ratio": (
                round(ar16_bytes / ar_bytes, 4) if ar_bytes else None
            ),
            "wall_s_per_iter": ar16.get("wall_s", 0.0) / iters16,
            "ll_drift": abs(bf16[0]["final_ll"] - dist[0]["final_ll"]),
            # Relative to the ELBO magnitude — the comparable number
            # (absolute nats scale with corpus size).
            "ll_drift_rel": (
                abs(bf16[0]["final_ll"] - dist[0]["final_ll"])
                / abs(dist[0]["final_ll"])
                if dist[0]["final_ll"] else None
            ),
        },
        # Rank parity is part of the phase's contract, not just the
        # test suite's: identical reduced stats => identical ll.
        "rank_ll_spread": float(
            max(w["final_ll"] for w in dist)
            - min(w["final_ll"] for w in dist)
        ),
    }


def phase_distributed_em():
    """Distributed-EM scaling: headline value is the 2-process run's
    docs/sec; the payload carries scaling efficiency (higher-better)
    and per-iteration allreduce bytes/wall (wall lower-better) for the
    bench_diff direction gates."""
    res = bench_distributed_em()
    return {"value": round(res["docs_per_sec"], 1), "unit": "docs/sec",
            **res}


def phase_pipeline_e2e():
    """The reference's actual unit of work: one full day start-to-finish
    (`./ml_ops.sh YYYYMMDD flow`, ml_ops.sh:57-108), with the stage
    breakdown exposing any host-side stage that dominates.  Runs the
    pre stage sharded (pre_workers=auto) and records the sequential
    pre-stage baseline alongside, so the featurization win — or
    single-core parity — is in the payload, not just in docs prose."""
    total, stages, eps, pre, critical = bench_pipeline_e2e()
    return {"value": round(total, 1), "unit": "seconds",
            "events_per_sec": round(eps, 1), "n_events": 5_000_000,
            "stages": stages, "pre": pre,
            "critical_path": critical,
            "overlap_efficiency": critical.get("overlap_efficiency"),
            "pre_workers": pre.get("pre_workers")}


def phase_pipeline_e2e_dns():
    """DNS day (combinatorial word space; one document per querying
    client, dns_pre_lda.scala:330-334)."""
    total, stages, eps, pre, critical = bench_pipeline_e2e(
        n_events=2_000_000, n_src=20_000, dsource="dns"
    )
    return {"value": round(total, 1), "unit": "seconds",
            "events_per_sec": round(eps, 1), "n_events": 2_000_000,
            "stages": stages, "pre": pre,
            "critical_path": critical,
            "overlap_efficiency": critical.get("overlap_efficiency"),
            "pre_workers": pre.get("pre_workers")}


# (name, phase function, timeout in seconds).  The headline comes first
# and alone supplies the record's value; every other phase is a
# secondary: the cheap attribution/stage phases, then the heavy scale
# configs and full days.  The serving and distributed phases start
# replica/worker children of their own and name those children's
# platform themselves.
PHASES = [
    ("headline", phase_headline, 600.0),
    ("lda_em_throughput_fresh_start", phase_fresh_start, 480.0),
    ("lda_em_convergence", phase_convergence, 300.0),
    ("dns_scoring", phase_dns_scoring, 360.0),
    ("flow_scoring", phase_flow_scoring, 420.0),
    ("scoring_e2e", phase_scoring_e2e, 480.0),
    ("serving_slo", phase_serving_slo, 480.0),
    ("serving_slo_fleet", phase_serving_slo_fleet, 480.0),
    ("serving_slo_fleet_paged", phase_serving_slo_fleet_paged, 480.0),
    # Device-resident featurization: host/device/fused word-building
    # A/B plus the saturated fleet drain-rate re-run.
    ("featurize_device", phase_featurize_device, 480.0),
    # Replicated elastic serving: replica subprocesses behind a router.
    ("serving_slo_replicated", phase_serving_slo_replicated, 600.0),
    # Cross-host serving: columnar wire + multi-router fan-in +
    # autoscaler.
    ("serving_crosshost", phase_serving_crosshost, 600.0),
    # Continuous ingestion: a paced day replay through the standing
    # window→warm-EM→gated-publish loop with co-resident serving.
    ("streaming_freshness", phase_streaming_freshness, 600.0),
    # Composed standing service: two tenants x continuous windows x
    # preemptible co-scheduled refreshes x replicated fleet, with a
    # mid-run replica SIGKILL and an uncoscheduled control leg in the
    # same payload.
    ("continuous_replicated", phase_continuous_replicated, 900.0),
    # Detection-quality SLO: labeled-injection P/R@k for every
    # registered source.
    ("detection_quality", phase_detection_quality, 300.0),
    # Multi-process scaling proof: worker processes on the host CPU.
    ("distributed_em", phase_distributed_em, 600.0),
    ("lda_em_throughput_k50_v50k", phase_k50_v50k, 720.0),
    ("lda_em_throughput_config4_v512k", phase_config4, 720.0),
    ("pipeline_e2e", phase_pipeline_e2e, 900.0),
    ("pipeline_e2e_dns", phase_pipeline_e2e_dns, 720.0),
    ("lda_online_svi", phase_online_svi, 900.0),
]


# Run-scoped parent dir for the e2e phases' synthetic-day workdirs:
# the orchestrator creates it, hands it to phase subprocesses via
# BENCH_E2E_DIR, and cleans ONLY inside it — never other processes'
# oni_e2e_* dirs in the shared tempdir.
_RUN_E2E_DIR: "str | None" = None


def _clean_orphan_workdirs():
    """Remove e2e day dirs a killed phase subprocess left behind (its
    finally: never ran) — scoped to THIS run's BENCH_E2E_DIR."""
    import shutil

    if _RUN_E2E_DIR:
        for d in glob.glob(os.path.join(_RUN_E2E_DIR, "oni_e2e_*")):
            shutil.rmtree(d, ignore_errors=True)


def _run_phase_subprocess(name: str, timeout: float):
    """One phase in a fresh process with a hard timeout; the phase and
    whatever it started are killed when it runs over.  Returns
    (payload | None, error | None)."""
    import signal
    import subprocess

    env = dict(os.environ)
    if _RUN_E2E_DIR:
        env["BENCH_E2E_DIR"] = _RUN_E2E_DIR
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        start_new_session=True,
    )
    try:
        out, errout = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.communicate()
        return None, f"timeout after {timeout:.0f}s"
    finally:
        _clean_orphan_workdirs()
    if proc.returncode != 0:
        tail = (errout or "").strip().splitlines()
        return None, f"rc={proc.returncode}: {' | '.join(tail[-2:])[:300]}"
    for line in reversed((out or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict):     # a stray numeric/list line isn't ours
            return parsed, None
    return None, "no JSON payload line in phase output"


def _run_phase(name: str, fn, timeout: float, inproc: bool):
    """Dispatch one phase: a fresh subprocess under a hard timeout (the
    production path), or in-process when BENCH_INPROC=1 (tests — their
    monkeypatched bench_* stubs don't exist in a subprocess).

    Successful payloads gain `phase_wall_s` (compile + backend init +
    measurement, i.e. the phase's cost to the whole run).  A payload
    that reports itself `skipped` measured nothing and counts as a
    failure."""
    t0 = time.perf_counter()
    if inproc:
        try:
            payload, err = fn(), None
        except Exception as exc:
            payload, err = None, str(exc)[:300]
    else:
        payload, err = _run_phase_subprocess(name, timeout)
    wall = round(time.perf_counter() - t0, 1)
    if isinstance(payload, dict) and payload.get("skipped"):
        payload, err = None, f"skipped: {payload['skipped']}"
    if isinstance(payload, dict):
        payload["phase_wall_s"] = wall
    return payload, err, wall


def run_phase(name: str) -> int:
    """`python bench.py --phase NAME`: run one phase in THIS process
    and print its payload as the last stdout line."""
    for pname, fn, _ in PHASES:
        if pname == name:
            print(json.dumps(fn()), flush=True)
            return 0
    print(f"bench: unknown phase {name!r}", file=sys.stderr)
    return 2


def _bench_diff_gate(record: "_Record", base_path: str) -> int:
    """Opt-in post-run regression gate
    (BENCH_DIFF_AGAINST=payload.json): diff this run's grown
    record against a prior captured payload via tools/bench_diff,
    annotate the record with the row set (so the verdict travels IN the
    payload the driver parses), and return bench_diff's exit semantics
    — 0 clean, 1 regression(s), 2 unusable baseline — for CI use."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"
    ))
    import bench_diff

    try:
        old = bench_diff.load_payload(base_path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        record.annotate("bench_diff",
                        {"against": base_path, "error": str(e)})
        print(f"bench: bench_diff: unusable baseline {base_path}: {e}",
              file=sys.stderr)
        return 2
    rows = bench_diff.diff_payloads(old, dict(record.data or {}))
    regressions = [r for r in rows if r["regression"]]
    # annotate() re-emits, so the LAST payload line carries the verdict.
    record.annotate("bench_diff", {
        "against": base_path,
        "compared": len(rows),
        "regressions": len(regressions),
        "rows": rows,
    })
    for r in regressions:
        print(f"bench: bench_diff REGRESSION {r['name']}: "
              f"{r['old']} -> {r['new']}", file=sys.stderr)
    if not rows:
        print("bench: bench_diff: no comparable metrics vs "
              f"{base_path}", file=sys.stderr)
        return 2
    return 1 if regressions else 0


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] == "--phase":
        return run_phase(sys.argv[2])
    if len(sys.argv) >= 7 and sys.argv[1] == "--distributed-worker":
        return run_distributed_worker(sys.argv[2:])

    record = _Record()
    # Lint preflight: a bench round on a tree that fails the static
    # gate (oni_ml_tpu/analysis — retrace hazards, unlocked shared
    # state, schema drift) measures code CI would reject; abort before
    # spending a second of chip time.  BENCH_LINT=0 opts out (e.g.
    # measuring a deliberately dirty work-in-progress tree).
    if os.environ.get("BENCH_LINT", "1") != "0":
        from oni_ml_tpu.analysis import run_analysis

        lint = run_analysis()
        if not lint.ok:
            for f in lint.findings:
                print(f"bench: lint: {f.format()}", file=sys.stderr)
            for path, msg in lint.parse_errors:
                print(f"bench: lint: {path}: parse error: {msg}",
                      file=sys.stderr)
            _emit_failure(
                f"lint preflight failed: {sum(lint.counts().values())} "
                f"finding(s) {lint.counts()}, "
                f"{len(lint.parse_errors)} parse error(s) — run "
                "`python tools/graftlint.py`, or BENCH_LINT=0 to "
                "measure anyway"
            )
            return 1

    inproc = os.environ.get("BENCH_INPROC") == "1"
    global _RUN_E2E_DIR
    if not inproc:
        import tempfile

        _RUN_E2E_DIR = tempfile.mkdtemp(prefix="oni_bench_run_")
    failed = []
    try:
        # Headline first: it alone supplies the record's value.
        head_name, head_fn, head_timeout = PHASES[0]
        payload, err, wall = _run_phase(head_name, head_fn, head_timeout,
                                        inproc)
        if payload is None:
            print(f"bench: headline failed after {wall:.0f}s: {err}",
                  file=sys.stderr)
            _emit_failure(f"headline failed: {err}")
            return 1
        record.set_headline(
            metric="lda_em_throughput",
            value=payload["value"],
            unit=payload["unit"],
            engine=payload.get("engine"),
            estep_engine=payload.get("estep_engine"),
            dense_vs_sparse=payload.get("dense_vs_sparse"),
            utilization=payload.get("utilization", {}),
            roofline=payload.get("roofline"),
            mean_vi_iters=payload.get("mean_vi_iters"),
            phase_wall_s=payload.get("phase_wall_s"),
        )
        # Tuning-constant provenance for the whole round: which knob
        # values this bench ran under and where each came from (config
        # / plan / default, with the recorded measurements).  Lifted
        # from the headline phase's payload: that subprocess owns a
        # backend; the orchestrator never initializes one.
        record.annotate(
            "plans",
            payload.get("plans")
            or {"error": "headline payload carried no plans section"},
        )
        for name, fn, timeout in PHASES[1:]:
            payload, err, wall = _run_phase(name, fn, timeout, inproc)
            if payload is not None:
                record.add_secondary(name, payload)
                continue
            print(f"bench: phase {name} failed after {wall:.0f}s: {err}",
                  file=sys.stderr)
            record.add_secondary(name, {"error": err, "phase_wall_s": wall})
            failed.append(name)
    finally:
        if _RUN_E2E_DIR:
            import shutil

            shutil.rmtree(_RUN_E2E_DIR, ignore_errors=True)
            _RUN_E2E_DIR = None
    if failed:
        record.annotate("failed_phases", failed)
    rc = 1 if failed else 0
    diff_base = os.environ.get("BENCH_DIFF_AGAINST")
    if diff_base:
        # Opt-in post-run regression gate: compare against the named
        # prior payload, annotate the record, and let the nonzero exit
        # carry into CI (a healthy measured round on a regressed tree
        # must not exit 0 when the operator asked for the gate).
        rc = max(rc, _bench_diff_gate(record, diff_base))
    return rc


if __name__ == "__main__":
    sys.exit(main())
