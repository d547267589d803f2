"""Roofline accounting: XLA cost analysis per compiled entry point,
joined with measured wall time, against a per-backend peak-spec
registry.

The r03 capture measured 10.5% MXU / 3.1% HBM utilization on the EM
headline — numbers that existed only as a hand-derived note in a bench
capture.  This module makes "how far from the hardware are we, per
phase?" a first-class, journaled, regression-trackable record:

1. **Harvest** — every jitted entry point the runner stages dispatch is
   harvested at AOT-warmup/first-trace time: `compiled.cost_analysis()`
   yields the program's FLOPs and bytes accessed (per dispatch), which
   land in a process-wide cost registry keyed by entry name.  Harvest
   NEVER raises: a backend/jax version without cost analysis records
   `source: "unavailable"` and every downstream record degrades to
   wall-time-only.
2. **Peaks** — `peaks_for()` maps the plans-layer backend fingerprint
   to published peak FLOP/s and HBM bytes/s (`PEAK_SPECS`, provenance
   carried per entry).  CPU and unknown backends have NO peaks, so
   tier-1 degrades to achieved-FLOPs-only (`utilization: null`), never
   an exception.
3. **Join** — `emit(phase, wall_s, dispatches)` multiplies the entry's
   per-dispatch cost by the dispatch count, divides by the measured
   wall (span wall times — the monotonic clocks of telemetry/spans.py),
   and appends a `{"kind": "roofline", ...}` record to the active
   journal plus `roofline.<phase>.*` gauges on the active Recorder, so
   `tools/trace_view.py` renders utilization counter lanes and the
   OpenMetrics exporter serves the gauges live.

Caveat worth stating once: cost analysis prices the program XLA
compiled, per dispatch.  For chunked programs whose trip count is a
runtime operand (the fused-EM while_loop), XLA's static count covers
one body execution — the emitted record carries `dispatches` and the
raw per-dispatch cost so the reader can see exactly what was counted;
`bench.py`'s analytic `em_utilization` model remains the cross-check.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass

from .spans import current_recorder


# ---------------------------------------------------------------------------
# Peak-spec registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PeakSpec:
    """Published per-chip peaks for one accelerator generation."""

    flops_per_s: float       # matmul peak the MXU path can reach
    hbm_bytes_per_s: float   # HBM bandwidth peak
    provenance: str


# Matched as substrings against the plans-layer device fingerprint
# ("backend:device_kind:count", lowercase, spaces -> _).  First match
# wins.  CPU and unrecognized backends deliberately have NO entry:
# peaks_for() returns None and every record degrades to
# `utilization: null` (the tier-1 contract) instead of inventing a
# denominator.
PEAK_SPECS: "tuple[tuple[tuple[str, ...], PeakSpec], ...]" = (
    (
        ("v5e", "v5_lite", "v5litepod"),
        PeakSpec(
            flops_per_s=197e12,
            hbm_bytes_per_s=819e9,
            provenance=(
                "TPU v5e public spec (Google Cloud documentation, "
                "\"TPU v5e\"): 197 TFLOP/s bf16 matmul (the MXU path "
                "XLA feeds f32 inputs at DEFAULT precision), "
                "819 GB/s HBM"
            ),
        ),
    ),
)


def peaks_for(fingerprint: "str | None") -> "PeakSpec | None":
    """PeakSpec for a plans-layer backend fingerprint, or None when the
    backend has no registered peaks (CPU, unknown)."""
    if not fingerprint:
        return None
    fp = fingerprint.lower()
    if fp.startswith(("cpu", "host", "nodevice")):
        return None
    for patterns, spec in PEAK_SPECS:
        if any(p in fp for p in patterns):
            return spec
    return None


def _backend_fingerprint() -> str:
    """The plans-layer device fingerprint, without ever letting a
    fingerprint probe take the caller down."""
    try:
        from ..plans import device_fingerprint

        return device_fingerprint()
    except Exception:
        return "nodevice"


# ---------------------------------------------------------------------------
# Cost harvest — one registry per process
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_COSTS: "dict[str, dict]" = {}
# Roofline records emitted this process (bounded) — what the runner
# folds into metrics.json and bench payloads lift their sections from.
_EMITTED: deque = deque(maxlen=256)
_EMIT_COUNT = 0


def _pick(analysis: dict, *keys: str) -> "float | None":
    for k in keys:
        v = analysis.get(k)
        if isinstance(v, (int, float)) and v >= 0:
            return float(v)
    return None


def harvest_compiled(name: str, compiled, *, shape: str = "") -> dict:
    """Read `compiled.cost_analysis()` off an AOT-compiled/lowered
    program and register its per-dispatch cost under `name`.  Never
    raises: unavailability (a backend without a cost model) registers
    `source: "unavailable"` so emit() degrades to wall-time-only
    records."""
    flops = bytes_accessed = None
    source = "unavailable"
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, dict):
            flops = _pick(analysis, "flops")
            bytes_accessed = _pick(analysis, "bytes accessed",
                                   "bytes_accessed")
            if flops is not None or bytes_accessed is not None:
                source = "cost_analysis"
    except Exception:
        pass
    entry = {
        "flops": flops,
        "bytes": bytes_accessed,
        "shape": shape,
        "backend": _backend_fingerprint(),
        "source": source,
    }
    with _LOCK:
        _COSTS[name] = entry
    return entry


def harvest_jitted(name: str, fn, *args, shape: str = "", **kw):
    """Harvest a `jax.jit` entry point by AOT-lowering it at the call's
    shapes (`fn.lower(*args).compile()` — abstract or concrete args
    both work; no data is moved).  The persistent compilation cache
    (plans/warmup.py) makes the compile a disk hit when the live
    dispatch already traced this program.  Returns the registered entry
    or None; never raises."""
    try:
        compiled = fn.lower(*args, **kw).compile()
    except Exception:
        with _LOCK:
            cur = _COSTS.get(name)
            if cur is None or cur.get("shape") != shape:
                # No usable cost for THIS shape: a stale entry harvested
                # at a different shape would mis-price every dispatch,
                # so replace it — emit() degrades to wall-time-only.
                _COSTS[name] = {
                    "flops": None, "bytes": None, "shape": shape,
                    "backend": _backend_fingerprint(),
                    "source": "unavailable",
                }
        return None
    return harvest_compiled(name, compiled, shape=shape)


def ensure_harvested(name: str, fn, *args, shape: str = "", **kw) -> None:
    """harvest_jitted, once per entry name AND shape — the hook hot
    dispatch paths call under an active recorder.  A repeat at the same
    shape is free; a shape change (a different chunk plan, a resized
    micro-batch) re-harvests so the per-dispatch cost joined with wall
    times is always the cost of the program actually dispatched."""
    with _LOCK:
        cur = _COSTS.get(name)
        if cur is not None and cur.get("shape") == shape:
            return
    harvest_jitted(name, fn, *args, shape=shape, **kw)


def cost_for(name: str) -> "dict | None":
    with _LOCK:
        return dict(_COSTS[name]) if name in _COSTS else None


def costs_snapshot() -> dict:
    with _LOCK:
        return {k: dict(v) for k, v in _COSTS.items()}


def reset() -> None:
    """Clear the process registries (tests)."""
    with _LOCK:
        _COSTS.clear()
        _EMITTED.clear()


# ---------------------------------------------------------------------------
# Record construction + emission
# ---------------------------------------------------------------------------


def roofline_record(phase: str, wall_s: float, *, entry: "str | None" = None,
                    dispatches: int = 1,
                    effective_flops: "float | None" = None,
                    measured_bytes: "float | None" = None,
                    **extra) -> dict:
    """Build one roofline record: the entry's per-dispatch cost times
    `dispatches`, over the measured wall, against the backend's peaks.

    Always returns a record.  Without harvested cost: wall-time-only
    (`flops`/`bytes`/`utilization` null).  With cost but no peaks (CPU):
    achieved FLOP/s / bytes/s, `utilization` null.

    `effective_flops` (total over the wall) is the FLOPs the MATH
    needed — for the E-step engines, the live-token work
    (sparse_estep.effective_flops) as opposed to the dense-equivalent
    FLOPs the program executed.  When given, the record carries
    `effective_flops`/`effective_flops_per_s` alongside the executed
    counts, and `utilization` gains `useful_mxu_pct` (effective over
    peak): "fraction of peak" vs "useful fraction of peak", so padding
    waste is visible as the gap between `mxu_pct` and
    `useful_mxu_pct`.

    `measured_bytes` (total over the wall) is for COMMUNICATION phases
    with no XLA cost to harvest — the distributed-EM suff-stats
    allreduce (parallel/allreduce.py) prices its cross-process traffic
    here: the record carries the measured bytes and bytes/s under
    `cost_source: "measured_comms"`, with `utilization` left null
    (interconnect bytes are not HBM bytes — the rate is the number,
    not a fraction of a memory peak)."""
    cost = cost_for(entry or phase)
    backend = (cost or {}).get("backend") or _backend_fingerprint()
    rec = {
        "kind": "roofline",
        "phase": phase,
        "entry": entry or phase,
        "backend": backend,
        "wall_s": round(float(wall_s), 6),
        "dispatches": int(dispatches),
        "cost_source": (cost or {}).get("source", "unharvested"),
        "flops": None,
        "bytes": None,
        "flops_per_s": None,
        "bytes_per_s": None,
        "effective_flops": None,
        "effective_flops_per_s": None,
        "peaks": None,
        "utilization": None,
        **extra,
    }
    if wall_s <= 0:
        return rec
    if measured_bytes is not None and cost is None:
        rec["cost_source"] = "measured_comms"
        rec["bytes"] = float(measured_bytes)
        rec["bytes_per_s"] = float(measured_bytes) / wall_s
    if effective_flops is not None:
        rec["effective_flops"] = float(effective_flops)
        rec["effective_flops_per_s"] = float(effective_flops) / wall_s
    spec = peaks_for(backend)
    if cost is not None:
        flops = cost.get("flops")
        nbytes = cost.get("bytes")
        if flops is not None:
            rec["flops"] = flops * dispatches
            rec["flops_per_s"] = rec["flops"] / wall_s
        if nbytes is not None:
            rec["bytes"] = nbytes * dispatches
            rec["bytes_per_s"] = rec["bytes"] / wall_s
    if spec is not None and (cost is not None
                             or rec["effective_flops_per_s"] is not None):
        rec["peaks"] = {
            "flops_per_s": spec.flops_per_s,
            "hbm_bytes_per_s": spec.hbm_bytes_per_s,
            "provenance": spec.provenance,
        }
        util = {}
        if rec["flops_per_s"] is not None:
            util["mxu_pct"] = round(
                100.0 * rec["flops_per_s"] / spec.flops_per_s, 2
            )
        if rec["bytes_per_s"] is not None:
            util["hbm_pct"] = round(
                100.0 * rec["bytes_per_s"] / spec.hbm_bytes_per_s, 2
            )
        if rec["effective_flops_per_s"] is not None:
            util["useful_mxu_pct"] = round(
                100.0 * rec["effective_flops_per_s"] / spec.flops_per_s, 2
            )
        rec["utilization"] = util or None
    return rec


def emit(phase: str, wall_s: float, *, entry: "str | None" = None,
         dispatches: int = 1, effective_flops: "float | None" = None,
         recorder=None, journal=None, **extra) -> dict:
    """Build and publish one roofline record: append to the journal
    (explicit `journal`/RunJournal, else the active Recorder's bound
    journal), set `roofline.<phase>.*` gauges on the Recorder, and keep
    it in the process ledger (`emitted_records()`) for the runner's
    metrics.json / bench payload sections.  Never raises."""
    rec = roofline_record(phase, wall_s, entry=entry,
                          dispatches=dispatches,
                          effective_flops=effective_flops, **extra)
    try:
        r = recorder if recorder is not None else current_recorder()
        if r is not None:
            if rec["flops_per_s"] is not None:
                r.gauge(f"roofline.{phase}.flops_per_s", rec["flops_per_s"])
            if rec["bytes_per_s"] is not None:
                r.gauge(f"roofline.{phase}.bytes_per_s", rec["bytes_per_s"])
            if rec["effective_flops_per_s"] is not None:
                r.gauge(f"roofline.{phase}.effective_flops_per_s",
                        rec["effective_flops_per_s"])
            util = rec.get("utilization") or {}
            for k, v in util.items():
                r.gauge(f"roofline.{phase}.{k}", v)
        j = journal
        if j is None and r is not None:
            r.journal_record(rec)
        elif j is not None:
            # Accept a RunJournal or a raw Journal.
            append = getattr(j, "append", None)
            if append is not None:
                append(dict(rec))
        global _EMIT_COUNT
        with _LOCK:
            _EMITTED.append(rec)
            _EMIT_COUNT += 1
    except Exception:
        pass
    return rec


def emit_count() -> int:
    """Total emits this process — callers snapshot it to scope
    emitted_records() to their own run (tests drive several pipelines
    per process)."""
    with _LOCK:
        return _EMIT_COUNT


def emitted_records(since: int = 0) -> "list[dict]":
    """Records emitted after the `since` count (bounded by the ledger's
    retention)."""
    with _LOCK:
        new = _EMIT_COUNT - since
        recs = list(_EMITTED)[-new:] if new > 0 else []
        return [dict(r) for r in recs]


# ---------------------------------------------------------------------------
# Entry-point coverage — the contract the telemetry lint enforces
# ---------------------------------------------------------------------------

# Every file under oni_ml_tpu/ that creates a `jax.jit(` entry point
# must appear here, naming how its programs are harvested for cost
# analysis (or why they are exempt).  tests/test_telemetry.py's
# jit-coverage lint fails the suite when a new jit site lands in a file
# not accounted for — the drift guard that keeps the roofline's phase
# coverage honest as kernels are added.
HARVEST_COVERAGE: "dict[str, str]" = {
    "models/fused.py": (
        "em.run_chunk — harvested at first instrumented dispatch via "
        "roofline.ensure_harvested in the chunk runner wrapper"
    ),
    "models/lda.py": (
        "em.update_alpha + em.e_step — harvested in the stepwise "
        "driver (fused runs inline them into em.run_chunk)"
    ),
    "models/online_lda.py": (
        "serve.refresh_step — the online-LDA update dispatched by the "
        "serving refresh loop; harvested opportunistically at step time "
        "(scan-shaped programs re-lower per chunk length)"
    ),
    "models/evaluate.py": (
        "exempt: holdout likelihood evaluation — an offline quality "
        "metric outside the runner's dispatch path"
    ),
    "ops/featurize_kernel.py": (
        "serve.featurize_rows + serve.featurize_fused — the LUT "
        "word-row gather and the fused featurize+gather+dot dispatch; "
        "harvested at first dispatch per padded shape via "
        "roofline.ensure_harvested in lut_rows/fused_scores"
    ),
    "ops/dense_estep.py": (
        "exempt: _block_e_step / _block_e_step_w are the kernel BODIES' "
        "arithmetic, jitted only for the trace cache (every pallas_call "
        "traces its kernel anew; shape groups of one batch shape share "
        "the jaxpr) and inlined by Mosaic into the kernels of the jitted "
        "chunk/E-step programs — cost is harvested at the callers' "
        "entries (em.run_chunk, em.e_step)"
    ),
    # plans/warmup.py is the AOT harvest hook itself, not an entry
    # point: _aot() reads cost_analysis off every program it compiles.
    # It does not belong in the registry: the harvest-coverage lint keys
    # entries to real jax.jit AST nodes.
    "parallel/allreduce.py": (
        "exempt: _psum_gather's jitted resharding identity is the "
        "control-plane collective transport (the explicit suff-stats "
        "allreduce), not a compute dispatch phase — its traffic is "
        "priced directly by the {\"kind\": \"allreduce\"} journal "
        "records and the em.allreduce roofline record's "
        "measured_bytes path, which is more accurate than an XLA "
        "cost-analysis harvest of a data-movement-only program"
    ),
    "ops/sparse_estep.py": (
        "estep crossover probes only — measure_crossover's jitted "
        "engine timers are one-shot sweeps whose result IS the "
        "measurement (persisted to the plan cache), not a dispatch "
        "phase; production sparse-engine dispatch is harvested at the "
        "drivers' entries (em.run_chunk, em.e_step), same as the dense "
        "kernels, with effective-FLOPs accounting via "
        "sparse_estep.effective_flops at emit time"
    ),
    "scoring/pipeline.py": (
        "score.device.{full,filtered,filtered_flow} — harvested by "
        "plans.warmup.warmup_scoring AOT and ensure_harvested at "
        "dispatch"
    ),
    "scoring/score.py": (
        "serve.micro_batch — harvested by plans.warmup.warmup_serving "
        "over the padded power-of-two batch family"
    ),
    "parallel/sharded.py": (
        "sharded twins of the scoring/EM entry points — cost harvested "
        "through their single-device callers' entries; per-shard cost "
        "equals the caller's divided by the data axis"
    ),
    "telemetry/heartbeat.py": (
        "exempt: the liveness probe (x + 1) — a round-trip timer, not "
        "a compute phase; its latency routes into the "
        "heartbeat.probe_latency_s histogram instead"
    ),
}
