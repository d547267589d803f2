"""Glue amortization at day scale on the virtual mesh.

docs/architecture.md's collective-volume model concedes the flagship
single-batch config tops out at ≈2.6× on 8 chips — Amdahl on the
per-EM-iteration fixed cost that does not shrink with the document
split (a per-dispatch cost amortized by the chunk, plus device-side
fixed work like the alpha update; neither measured on the current
machine) — and claims
multi-chip pays at day-scale corpora because many resident batches
amortize that fixed cost.  This tool MEASURES the amortization
structure on the 8-device virtual CPU mesh (relative shape, not TPU
absolute times): per-EM-iteration wall against resident batch count
for the production fused chunk runner with the data-parallel sharded
E-step, then the least-squares split into fixed (glue) and marginal
(per-batch compute) components:

    python tools/glue_amortization.py [--out JSON_PATH]

The fixed component is per EM ITERATION, not per batch — so its share
of the iteration falls as 1/n_batches, which is exactly the mechanism
the day-scale multi-chip claim rests on.  Results are pasted (with
provenance) into docs/architecture.md next to the arithmetic.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def measure(k=20, v=8192, b=512, l=64, n_batches=(1, 2, 4, 8, 16),
            chunk=4, var_max_iters=10, rounds=3, n_devices=8) -> dict:
    import __graft_entry__ as graft

    graft._ensure_devices(n_devices)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oni_ml_tpu.io.corpus import Batch
    from oni_ml_tpu.models import fused
    from oni_ml_tpu.parallel import make_mesh
    from oni_ml_tpu.parallel.mesh import DATA_AXIS
    from oni_ml_tpu.parallel.sharded import make_data_parallel_e_step

    mesh = make_mesh(data=n_devices, model=1,
                     devices=jax.devices()[:n_devices])
    put = lambda x: jax.device_put(  # noqa: E731  (doc axis = axis 1)
        x, NamedSharding(mesh, P(None, DATA_AXIS)))
    e_fn = make_data_parallel_e_step(mesh)

    rng = np.random.default_rng(0)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta0 = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )

    rows = []
    for nb in n_batches:
        batches = [
            Batch(
                word_idx=rng.integers(0, v, size=(b, l)).astype(np.int32),
                counts=rng.integers(1, 5, size=(b, l)).astype(np.float32),
                doc_index=np.arange(i * b, (i + 1) * b, dtype=np.int32),
                doc_mask=np.ones((b,), np.float32),
            )
            for i in range(nb)
        ]
        groups = fused.stack_batches(batches, np.float32, put)
        run_chunk = fused.make_chunk_runner(
            num_docs=nb * b, num_topics=k, num_terms=v, chunk=chunk,
            var_max_iters=var_max_iters, var_tol=1e-6, em_tol=0.0,
            estimate_alpha=True, e_step_fn=e_fn,
        )
        gammas0 = tuple(
            put(g) for g in fused.initial_gammas(
                groups.arrays, k, jnp.float32)
        )
        log_beta, alpha = log_beta0, jnp.float32(2.5)
        res = run_chunk(log_beta, alpha, jnp.float32(np.nan),
                        groups.arrays, chunk, gammas0, jnp.asarray(False))
        float(res.lls[-1])          # compile + settle
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            res = run_chunk(res.log_beta, res.alpha, res.ll_prev,
                            groups.arrays, chunk, res.gammas,
                            res.steps_done > 0)
            assert np.isfinite(float(res.lls[-1]))
            best = min(best, (time.perf_counter() - t0) / chunk)
        rows.append({"n_batches": nb, "t_iter_ms": round(best * 1e3, 2),
                     "t_iter_per_batch_ms": round(best * 1e3 / nb, 2)})

    # Least-squares t(n) = glue + n * per_batch over the measured rows.
    ns = np.asarray([r["n_batches"] for r in rows], np.float64)
    ts = np.asarray([r["t_iter_ms"] for r in rows], np.float64)
    per_batch, glue = np.polyfit(ns, ts, 1)
    rec = {
        "metric": "glue_amortization_cpu_mesh",
        "k": k, "v": v, "b_per_batch": b, "l": l,
        "n_devices": n_devices, "chunk": chunk,
        "rows": rows,
        "fit_glue_ms": round(float(glue), 2),
        "fit_per_batch_ms": round(float(per_batch), 2),
        "glue_share_1_batch": round(float(glue / ts[0]), 3),
        "glue_share_max_batches": round(float(glue / ts[-1]), 3),
    }
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    rec = measure(rounds=args.rounds)
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
