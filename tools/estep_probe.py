"""Density × bucket-shape sweep for the sparse E-step engine — the
EM twin of tools/score_probe.py: tunes the sparse engine's block shapes
and the dense-vs-sparse crossover on the chip in one command:

    python tools/estep_probe.py [--k K] [--v V] [--b B]
        [--densities 0.5,1,2,5,10] [--precision bf16] [--reps 2]

Per density the probe synthesizes a corpus whose per-doc live-token
count L makes the densified batch exactly that dense (L = density·V,
padded to the power-of-two bucket the layout pass would pick), then:

1. **Block sweep** — times `sparse_estep.e_step` at every feasible
   power-of-two doc block and records the winner into the plan cache
   (knob `sparse_estep_bb`, shape key b{B}.l{L}.k{K}.{precision}) with
   the full measurement set as provenance, so
   `sparse_estep.pick_block` resolves it as the measured prior on the
   next run (source "plan", zero re-sweeps).
2. **Crossover** — measures dense-vs-sparse at the same shape
   (`sparse_estep.measure_crossover`: one pinned E-step each, densify
   outside the dense timing) and persists the winner under BOTH the
   exact-shape and density-band keys (knob `estep_engine`), exactly
   like the trainer's inline sweep — the dispatch_calibration pattern.

One JSON line per measurement; a final `plan_cache_update` line names
every knob recorded.  Runs on any backend (CPU numbers exercise the
machinery and pin the interpret-mode crossover; the cache is
backend-fingerprint-keyed, so a CPU record can never leak onto a
chip).
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_DENSITIES = (0.5, 1.0, 2.0, 5.0, 10.0)


def _bucket_len(n: int, min_len: int) -> int:
    b = min_len
    while b < n:
        b *= 2
    return b


def sweep_density(k: int, v: int, b: int, density_pct: float,
                  precision: str, reps: int) -> "dict | None":
    """One density point: block sweep + crossover.  Returns the summary
    record (None when no bucket shape is feasible at this density)."""
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.ops import sparse_estep

    backend = jax.default_backend()
    min_len, _ = sparse_estep.resolve_layout_len(None)
    l_raw = max(1, int(round(density_pct / 100.0 * v)))
    l = _bucket_len(l_raw, min_len)
    rng = np.random.default_rng(11)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )
    word_idx = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    counts = jnp.asarray(rng.integers(1, 5, size=(b, l)).astype(np.float32))
    mask = jnp.ones((b,), jnp.float32)
    alpha = jnp.float32(2.5)
    interp = backend != "tpu"
    vi = 8                       # pinned trip count (crossover convention)

    # -- block sweep ------------------------------------------------------
    sub = 16 if precision == "bf16" else 8
    candidates = []
    bb = sub
    while bb <= min(b, sparse_estep._MAX_BLOCK_DOCS) and b % bb == 0:
        if sparse_estep._vmem_estimate(
            bb, l, k, precision
        ) <= sparse_estep._VMEM_CEILING:
            candidates.append(bb)
        bb *= 2
    if not candidates:
        print(json.dumps({
            "probe": "estep_block_sweep", "backend": backend,
            "density_pct": density_pct, "b": b, "l": l, "k": k,
            "skipped": "no VMEM-feasible doc block",
        }), flush=True)
        return None

    from oni_ml_tpu import plans

    measurements = {}
    for cand in candidates:
        fn = jax.jit(functools.partial(
            sparse_estep.e_step, var_max_iters=vi, var_tol=0.0,
            interpret=interp, precision=precision, block=cand,
        ))
        float(np.asarray(                      # compile + warm
            fn(log_beta, alpha, word_idx, counts, mask).likelihood
        ))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            res = fn(log_beta, alpha, word_idx, counts, mask)
            float(np.asarray(res.likelihood))  # sync
            best = min(best, time.perf_counter() - t0)
        measurements[cand] = round(b / best)
        print(json.dumps({
            "probe": "estep_block_sweep", "backend": backend,
            "density_pct": density_pct, "b": b, "l": l, "k": k,
            "precision": precision, "block": cand,
            "docs_per_sec": round(b / best), "t_estep_ms":
            round(best * 1e3, 3),
        }), flush=True)
    best_bb = max(measurements, key=measurements.get)
    shape = f"b{b}.l{l}.k{k}.{precision}"
    plans.note_sweep("sparse_estep_bb")
    recorded_bb = plans.record_value(
        "sparse_estep_bb", int(best_bb), shape=shape, source="probe",
        measurements={str(c): m for c, m in measurements.items()},
        unit="docs/sec", density_pct=density_pct,
    )

    # -- dense-vs-sparse crossover ---------------------------------------
    cross = sparse_estep.engine_crossover(
        k, v, b, l, precision=precision, force=True
    )
    print(json.dumps({
        "probe": "estep_crossover", "backend": backend,
        "density_pct": density_pct, **cross,
    }), flush=True)
    return {
        "density_pct": density_pct, "b": b, "l": l, "shape": shape,
        "sparse_estep_bb": int(best_bb), "recorded": recorded_bb,
        "engine": cross["engine"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Sparse E-step density x bucket-shape sweep; "
        "records sparse_estep_bb winners and the dense/sparse "
        "crossover into the plan cache."
    )
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--v", type=int, default=8192)
    ap.add_argument("--b", type=int, default=1024)
    ap.add_argument("--densities", default=None,
                    help="comma list of corpus densities in percent "
                    "(default 0.5,1,2,5,10)")
    ap.add_argument("--precision", default="bf16",
                    choices=("f32", "bf16"))
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)
    densities = (
        tuple(float(d) for d in args.densities.split(","))
        if args.densities else DEFAULT_DENSITIES
    )
    from oni_ml_tpu import plans

    summaries = []
    for d in densities:
        s = sweep_density(args.k, args.v, args.b, d, args.precision,
                          args.reps)
        if s is not None:
            summaries.append(s)
    print(json.dumps({
        "probe": "plan_cache_update",
        "store": plans.default_path(),
        "backend": plans.device_fingerprint(),
        "knobs_recorded": ["sparse_estep_bb", "estep_engine"],
        "points": summaries,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
