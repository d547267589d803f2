"""Compile-only HBM feasibility probe for config 4.

The architecture doc's config-4 claims were arithmetic: "the densified
[B, V] corpus alone is ~4 GB/chip under data parallelism (infeasible on
a 16 GB v5e), the vocab-sharded dense plan shards it to ~0.5 GB and
fits".  This tool turns that into a COMPILER-verified fact at real
width — it AOT-lowers and compiles both plans at V=512k / K=20 /
per-chip B=2048 on the 8-device virtual mesh (no execution, no
multi-GB allocation: XLA's buffer assignment is static) and records
each plan's per-device argument/output/temp/peak bytes from
`compiled.memory_analysis()`:

    python tools/config4_hbm_probe.py [--v 524288] [--b 2048] [--k 20]
                                      [--out JSON_PATH]

Anchors: BASELINE.json config 4 (huge-V DNS regime,
dns_pre_lda.scala:320-326); docs/architecture.md "Multi-chip
collective-volume model".

Fidelity notes:
- Sizes are per-device (jax reports post-sharding buffer bytes).
- The vocab-sharded plan is the production XLA path — its numbers are
  exactly what a TPU run would place in HBM, modulo layout padding.
- The data-parallel dense plan compiles the Pallas kernel in interpret
  mode off-TPU, so its TEMP bytes over-estimate the Mosaic kernel's
  scratch; its ARGUMENT bytes (the resident corpus shard — the basis
  of the infeasibility claim) are layout-exact either way.  At real
  config-4 width the kernel's VMEM feasibility gate
  (dense_estep.pick_block) refuses the plan outright before lowering —
  the probe records that refusal as the plan's verdict.
"""

import argparse
import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16e9   # TPU v5e per-chip HBM (public spec)


def _stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        # jax 0.4.x CompiledMemoryStats has no peak field; the
        # args+outputs+temps sum is the conservative residency bound
        # (aliasing can only shrink it), which is what the fits-HBM
        # verdict needs.
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes)
    rec = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(peak),
    }
    rec["fits_hbm"] = bool(rec["peak_bytes"] < HBM_BYTES)
    rec["peak_gb"] = round(rec["peak_bytes"] / 1e9, 2)
    return rec


def probe(v: int, b: int, k: int, n_devices: int = 8,
          var_max_iters: int = 20) -> dict:
    """Compile both config-4 plans at width `v`, per-chip batch `b`.
    Returns the record (no execution)."""
    import __graft_entry__ as graft

    graft._ensure_devices(n_devices)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oni_ml_tpu.parallel import make_mesh
    from oni_ml_tpu.parallel.sharded import (
        make_data_parallel_dense_e_step,
        make_vocab_sharded_dense_e_step,
    )

    rec = {"metric": "config4_hbm_probe", "k": k, "v": v,
           "b_per_chip": b, "n_devices": n_devices,
           "hbm_bytes": int(HBM_BYTES), "plans": {}}

    def sds(shape, dtype, mesh, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    # -- Plan of record: vocab-sharded dense on (data=1, model=8) ------
    # Columns of C and beta shard over `model`; the corpus never exists
    # whole on any chip.
    vs_mesh = make_mesh(data=1, model=n_devices,
                        devices=jax.devices()[:n_devices])
    vs_fn = make_vocab_sharded_dense_e_step(vs_mesh)
    args_vs = (
        sds((k, v), jnp.float32, vs_mesh, P(None, "model")),   # log_beta
        sds((), jnp.float32, vs_mesh, P()),                    # alpha
        sds((b, v), jnp.float32, vs_mesh, P("data", "model")),  # dense C
        sds((b,), jnp.float32, vs_mesh, P("data")),            # doc_mask
        sds((b, k), jnp.float32, vs_mesh, P("data")),          # gamma_prev
        sds((), jnp.int32, vs_mesh, P()),                      # warm
    )
    compiled = jax.jit(
        partial(vs_fn, var_max_iters=var_max_iters, var_tol=1e-6)
    ).lower(*args_vs).compile()
    rec["plans"]["vocab_sharded_dense"] = _stats(compiled)

    # -- The rejected alternative: data-parallel dense, per-chip B=b ---
    # Every chip holds its FULL [b, V] document shard; B_global = b * n.
    dp_mesh = make_mesh(data=n_devices, model=1,
                        devices=jax.devices()[:n_devices])
    dp_fn = make_data_parallel_dense_e_step(dp_mesh, wmajor=False)
    bg = b * n_devices
    args_dp = (
        sds((k, v), jnp.float32, dp_mesh, P()),                # replicated
        sds((), jnp.float32, dp_mesh, P()),
        sds((bg, v), jnp.float32, dp_mesh, P("data", None)),   # dense C
        sds((bg,), jnp.float32, dp_mesh, P("data")),
        sds((bg, k), jnp.float32, dp_mesh, P("data")),
        sds((), jnp.int32, dp_mesh, P()),
    )
    try:
        compiled = jax.jit(
            partial(dp_fn, var_max_iters=var_max_iters, var_tol=1e-6,
                    interpret=jax.default_backend() != "tpu")
        ).lower(*args_dp).compile()
        rec["plans"]["data_parallel_dense"] = _stats(compiled)
        dp_ok = (rec["plans"]["data_parallel_dense"]["argument_bytes"]
                 >= b * v * 4)
    except ValueError as e:
        # At real config-4 width the kernel's own VMEM feasibility gate
        # (dense_estep.pick_block) refuses before lowering even starts —
        # a stronger infeasibility verdict than any HBM estimate, so
        # record it as the plan's result.  ONLY that specific refusal
        # counts: an unrelated ValueError (spec mismatch, divisibility)
        # must fail the probe, not masquerade as compiler-verified
        # infeasibility.
        if "no VMEM-feasible doc block" not in str(e):
            raise
        rec["plans"]["data_parallel_dense"] = {
            "infeasible": True, "reason": str(e),
        }
        dp_ok = True

    vs_peak = rec["plans"]["vocab_sharded_dense"]["peak_bytes"]
    rec["dp_corpus_resident_gb"] = round(b * v * 4 / 1e9, 2)
    rec["claim_verified"] = bool(vs_peak < HBM_BYTES and dp_ok)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--v", type=int, default=524288)
    ap.add_argument("--b", type=int, default=2048)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rec = probe(args.v, args.b, args.k)
    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
