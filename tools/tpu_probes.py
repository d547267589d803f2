"""On-chip profiling probes for the fused-EM iteration.  Run them on the
chip (ROADMAP A1 and A6 name what each has to settle):

    python tools/tpu_probes.py [cap_sweep] [alpha_ab] [chunk_sweep]
                               [batch_amort]

(no args = all four).  Each probe prints one JSON line per
measurement.  What they answer:

cap_sweep — fixed-cost decomposition of one EM iteration.  docs/s at
  forced var_max_iters caps, warm start OFF so the cap is the actual
  trip count; regressing t_iter on the cap gives slope = per-VI-
  iteration cost and intercept = the fixed per-EM-iteration cost (XLA
  glue + corpus streaming + tail pass).

alpha_ab — attribute the alpha-Newton update's cost.  estimate_alpha
  runs a SCALAR Newton loop (digamma/trigamma per trip) inside every
  EM iteration — the TPU's worst-case shape; the A/B prices the
  unrolled cap-8 lowering against fixed alpha and the 100-trip
  while_loop.

chunk_sweep — host-dispatch amortization: docs/s against EM
  iterations per dispatch.  The per-dispatch cost is not measured on
  the current machine; this is the probe that measures it, and it
  records its winner into the plan cache.

batch_amort — per-EM-iteration wall and docs/s vs resident batch count
  (1/2/4 stacked B=4096 batches through the production chunk runner's
  scan).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K, V, B, L = 20, 8192, 4096, 128          # headline shape (config 1)


def cap_sweep():
    import bench

    for cap in (1, 3, 6, 12, 20):
        em = bench.bench_em(K, V, B, L, chunk=32, rounds=3, var_max_iters=cap,
                            warm_start=False, precision="bf16")
        print(json.dumps({
            "probe": "cap_sweep", "cap": cap,
            "t_iter_ms": round(em["t_iter"] * 1e3, 3),
            "mean_vi": round(em["mean_vi"], 2),
            "docs_per_sec": round(em["docs_per_sec"]),
        }), flush=True)


def alpha_ab():
    # Three-way since r05: newton100 forces the dynamic while_loop
    # lowering (the pre-r05 production shape), newton8 is the capped
    # UNROLLED lowering bench now uses (r05 charged ~0.5 ms/EM-iter to
    # the estimate at chunk=32 — this row says how much the unroll
    # recovers), fixed is the no-estimate floor.
    import bench
    from oni_ml_tpu.models import fused

    orig = fused.make_chunk_runner

    def newton100(**kw):
        kw["alpha_max_iters"] = 100
        return orig(**kw)

    def newton8(**kw):
        # Pinned here, not inherited from bench's current tuning, so
        # the emitted label stays true if bench's cap ever moves.
        kw["alpha_max_iters"] = 8
        return orig(**kw)

    def no_alpha(**kw):
        kw["estimate_alpha"] = False
        return orig(**kw)

    try:
        for label, maker in (("newton100", newton100),
                             ("newton8", newton8),
                             ("fixed", no_alpha)):
            fused.make_chunk_runner = maker
            em = bench.bench_em(K, V, B, L, chunk=32, rounds=3,
                                warm_start=True,
                                precision="bf16")
            print(json.dumps({
                "probe": "alpha_ab", "alpha": label,
                "t_iter_ms": round(em["t_iter"] * 1e3, 3),
                "docs_per_sec": round(em["docs_per_sec"]),
            }), flush=True)
    finally:
        fused.make_chunk_runner = orig


def chunk_sweep():
    import bench

    # Where the curve flattens is the per-dispatch cost over the device
    # time of one iteration — neither measured on the current machine.
    measurements = {}
    for chunk in (32, 64, 128, 256, 512):
        em = bench.bench_em(K, V, B, L, chunk=chunk, rounds=3,
                            warm_start=True, precision="bf16")
        measurements[chunk] = round(em["docs_per_sec"])
        print(json.dumps({
            "probe": "chunk_sweep", "chunk": chunk,
            "t_iter_ms": round(em["t_iter"] * 1e3, 3),
            "docs_per_sec": round(em["docs_per_sec"]),
        }), flush=True)
    # Persist the winner as a measured plan (oni_ml_tpu/plans): the
    # next run on this backend trains at the measured chunk.
    from oni_ml_tpu import plans

    best = max(measurements, key=measurements.get)
    plans.note_sweep("fused_em_chunk")
    recorded = plans.record_value(
        "fused_em_chunk", int(best), shape=f"k{K}.v{V}.b{B}.l{L}",
        source="probe", measurements=measurements, unit="docs/sec",
    )
    # Both records always attempted (no short-circuit): a failed
    # exact-shape write must not silently skip the wildcard one.
    recorded_wild = plans.record_value(
        "fused_em_chunk", int(best), shape="*",
        source="probe", measurements=measurements, unit="docs/sec",
        note="wildcard projection: the amortized term is the "
             "per-dispatch cost, taken as shape-independent",
    )
    print(json.dumps({
        "probe": "plan_cache_update",
        # False: plans disabled / cache unwritable for that write.
        "recorded": recorded,
        "recorded_wildcard": recorded_wild,
        "store": plans.default_path(),
        "backend": plans.device_fingerprint(),
        "fused_em_chunk": int(best),
    }), flush=True)


def batch_amort():
    import bench

    # Capped at 4: three points show the fixed-cost amortization curve,
    # and n=8's setup is long for what the extra point adds.
    for nb in (1, 2, 4):
        em = bench.bench_em(K, V, B, L, chunk=32, rounds=3,
                            warm_start=True, precision="bf16",
                            n_batches=nb)
        print(json.dumps({
            "probe": "batch_amort", "n_batches": nb,
            "t_iter_ms": round(em["t_iter"] * 1e3, 3),
            "t_iter_per_batch_ms": round(em["t_iter"] * 1e3 / nb, 3),
            "docs_per_sec": round(em["docs_per_sec"]),
        }), flush=True)


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print("tpu_probes: backend is not TPU — these probes measure "
              "device behavior; run on the chip host", file=sys.stderr)
        return 2
    which = sys.argv[1:] or ["cap_sweep", "alpha_ab", "chunk_sweep",
                             "batch_amort"]
    for name in which:
        fn = globals().get(name)
        if fn is None:
            print(f"tpu_probes: unknown probe {name!r}", file=sys.stderr)
            return 2
        fn()
    return 0


if __name__ == "__main__":
    sys.exit(main())
