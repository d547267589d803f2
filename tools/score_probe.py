"""Scoring-dispatch chunk sweep — the scoring twin of the EM chunk
sweep (tools/tpu_probes.py chunk_sweep): tunes
`ScoringConfig.device_chunk` on the chip in one command:

    python tools/score_probe.py [n_events] [chunk [chunk ...]]

(defaults: 400k events — the bench day size — over chunks 8k..256k).
Each measurement prints one JSON line: events/sec through the fused
flow filter pipeline (scoring/pipeline.py filtered_flow_scores — two
gathers + dot + min + threshold + compaction per chunk, double-buffered
dispatch) at a threshold keeping ~half the events, plus the pipeline's
own dispatch/transfer accounting so the record shows WHAT moved, not
just how fast.  A final line reports the measured host-vs-device
break-even (scoring.dispatch_calibration) — the constant the serving
dispatch runs under on this backend.

A per-dispatch cost (not measured on the current machine) predicts a
hyperbola here:
t(chunk) ≈ n/chunk · glue + n · per_event — the sweep's flat point is
the chunk where glue is amortized, and that is what device_chunk
should be set to.  Runs on any backend (CPU numbers exercise the
machinery; only TPU numbers should retune the default — the record
carries the backend so they cannot be confused)."""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_CHUNKS = (8192, 16384, 32768, 65536, 131072, 262144)


def sweep(n_events: int, chunks, reps: int = 3) -> None:
    import jax

    from oni_ml_tpu.scoring import (
        DispatchStats,
        ScoringModel,
        dispatch_calibration,
        filtered_flow_scores,
    )
    from oni_ml_tpu.scoring.score import _batched_scores

    backend = jax.default_backend()
    rng = np.random.default_rng(7)
    k, n_ips, n_words = 20, 40_000, 8_000
    model = ScoringModel(
        ip_index={}, theta=rng.random((n_ips + 1, k)),
        word_index={}, p=rng.random((n_words + 1, k)),
    )
    sa, da = (rng.integers(0, n_ips, n_events).astype(np.int32)
              for _ in range(2))
    sw, dw = (rng.integers(0, n_words, n_events).astype(np.int32)
              for _ in range(2))
    # ~half the events survive: representative of a real TOL without
    # depending on the synthetic score distribution (bench convention).
    mn = np.minimum(
        _batched_scores(model, sa, sw), _batched_scores(model, da, dw)
    )
    threshold = float(np.median(mn))

    measurements = {}
    for chunk in chunks:
        # Warm the compiled program for this chunk outside the timing.
        filtered_flow_scores(model, sa, sw, da, dw, threshold, chunk=chunk)
        best, stats = float("inf"), None
        for _ in range(reps):
            st = DispatchStats()
            t0 = time.perf_counter()
            out = filtered_flow_scores(
                model, sa, sw, da, dw, threshold, chunk=chunk, stats=st
            )
            dt = time.perf_counter() - t0
            if dt < best:
                best, stats = dt, st
        assert len(out[0])
        measurements[chunk] = round(n_events / best)
        print(json.dumps({
            "probe": "score_chunk_sweep", "backend": backend,
            "chunk": chunk, "n_events": n_events,
            "events_per_sec": round(n_events / best),
            "p50_ms": round(best * 1e3, 2),
            "dispatches": stats.dispatches,
            "h2d_mb": round(stats.h2d_bytes / 1e6, 2),
            "d2h_mb": round(stats.d2h_bytes / 1e6, 2),
            "survivors": stats.survivors,
        }), flush=True)

    # The sweep's winner seeds the plan cache directly (oni_ml_tpu/
    # plans knob "score_device_chunk", keyed by this backend's
    # fingerprint): the next pipeline/serving run on this backend loads
    # the measured chunk instead of the shipped default, and
    # `tools/plan_cache.py export` turns the session into a committable
    # seed file.  Only TPU measurements should retune production — but
    # the cache is backend-keyed, so a CPU record can never leak onto a
    # chip.
    from oni_ml_tpu import plans

    best_chunk = max(measurements, key=measurements.get)
    plans.note_sweep("score_device_chunk")
    recorded = plans.record_value(
        "score_device_chunk", int(best_chunk), source="probe",
        measurements=measurements, unit="events/sec",
        n_events=n_events,
    )
    # dispatch_calibration(force=True) re-measures AND re-records its
    # own plan entry (scoring/score.py).
    print(json.dumps({
        "probe": "score_dispatch_calibration", "backend": backend,
        **dispatch_calibration(force=True),
    }), flush=True)
    print(json.dumps({
        "probe": "plan_cache_update",
        "recorded": recorded,        # False: plans disabled/unwritable
        "store": plans.default_path(),
        "backend": plans.device_fingerprint(),
        "score_device_chunk": int(best_chunk),
        "knobs_recorded": ["score_device_chunk", "dispatch_calibration"],
    }), flush=True)


def main() -> int:
    args = [int(a) for a in sys.argv[1:]]
    n_events = args[0] if args else 400_000
    chunks = tuple(args[1:]) or DEFAULT_CHUNKS
    sweep(n_events, chunks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
