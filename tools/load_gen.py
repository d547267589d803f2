"""Poisson + bursty load generator for the serving SLO plane.

Two uses:

1. **In-process harness** (`run_slo`, what `bench.py serving_slo`
   calls): build a synthetic day, stand up the real serving stack
   (ModelRegistry -> BatchScorer), replay a timed arrival schedule
   against it, and measure per-event enqueue->resolved latency into a
   shared telemetry histogram — sustained events/s and true
   p50/p99/p999 come back off the fixed bucket boundaries
   (telemetry/spans.Histogram), the same estimator the OpenMetrics
   endpoint serves.
2. **Stream mode** (`--emit-lines`): pace raw CSV event lines to
   stdout under the chosen arrival pattern, for piping into a real
   `ml_ops serve --metrics-port PORT` and scraping the endpoint live.

Arrival patterns:

- `poisson` — exponential inter-arrival gaps at the offered rate; the
  memoryless open-loop model of independent event sources.
- `bursty`  — on/off bursts: `burst_len` events arrive back-to-back,
  burst heads spaced so the LONG-RUN average equals the offered rate.
  Same throughput, pathological queue spikes — the pattern that
  separates a p50-tuned batcher from one with a p999.

Latency is measured enqueue -> future-resolved by a FIFO collector
thread (flushes resolve in order, so waiting in submit order wakes
promptly after each resolution).  A submit that falls behind schedule
is NOT dropped — the backlog shows up as latency, exactly like a real
overloaded ingest.

Usage:

    python tools/load_gen.py --pattern both --events 4096 --rate 2000
    python tools/load_gen.py --pattern bursty --emit-lines --events 10000 \
        --rate 500 | python -m oni_ml_tpu.runner.ml_ops serve ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

PATTERNS = ("poisson", "bursty")

# Collector-slot sentinel for a load-shed submit (AdmissionRejected):
# distinguishes "no future will ever exist here" from "producer not
# there yet" (None), so a shed mid-replay releases the tenant's
# collector instead of parking it until the global done event.
_SHED = object()


def arrival_offsets(pattern: str, n: int, rate_eps: float, *,
                    seed: int = 0, burst_len: int = 64) -> np.ndarray:
    """Arrival times in seconds from stream start, length n,
    long-run-averaging `rate_eps` events/s under either pattern."""
    if rate_eps <= 0:
        raise ValueError(f"rate_eps must be > 0, got {rate_eps}")
    if pattern == "poisson":
        rng = np.random.default_rng(seed)
        return np.cumsum(rng.exponential(1.0 / rate_eps, size=n))
    if pattern == "bursty":
        # Burst heads at burst_len/rate intervals; every event in a
        # burst arrives at its head (zero intra-burst gap).
        bl = max(1, int(burst_len))
        heads = np.arange(-(-n // bl), dtype=np.float64) * (bl / rate_eps)
        return np.repeat(heads, bl)[:n]
    raise ValueError(f"unknown pattern {pattern!r} (want {PATTERNS})")


def run_load(scorer, raws, offsets: np.ndarray, *, recorder=None,
             pattern: str = "load", timeout_s: float = 120.0) -> dict:
    """Replay `raws` against a BatchScorer at `offsets`' schedule and
    return the measured SLO numbers.  Latencies observe into the shared
    histogram `loadgen.<pattern>.latency_ms` on `recorder` (a private
    Recorder when none given) — quantiles come off its fixed bucket
    boundaries, per the telemetry lint."""
    from oni_ml_tpu.telemetry.spans import Recorder

    rec = recorder or Recorder()
    hist = rec.histogram(f"loadgen.{pattern}.latency_ms")
    n = len(raws)
    fifo: list = [None] * n
    done = threading.Event()
    state = {"resolved": 0, "errors": 0, "t_last": None}

    def collect():
        for i in range(n):
            while fifo[i] is None:           # producer not there yet
                if done.wait(0.0005):
                    if fifo[i] is None:      # producer gave up
                        return
                    break
            fut, t_submit = fifo[i]
            try:
                fut.result(timeout=timeout_s)
                t_now = time.perf_counter()
                state["t_last"] = t_now
                hist.observe((t_now - t_submit) * 1e3)
                state["resolved"] += 1
            except Exception:
                state["errors"] += 1

    collector = threading.Thread(target=collect, name="loadgen-collect",
                                 daemon=True)
    collector.start()
    t0 = time.perf_counter()
    behind_s = 0.0
    try:
        for i, raw in enumerate(raws):
            target = t0 + offsets[i]
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            else:
                behind_s = max(behind_s, now - target)
            t_submit = time.perf_counter()
            fut = scorer.submit(raw)
            fifo[i] = (fut, t_submit)
        scorer.flush()
    finally:
        # Unconditionally release the collector: a submit that raises
        # mid-replay (scorer closed underneath us, featurizer error)
        # must not leave the daemon thread spinning on an unfilled slot
        # for the life of the process.
        done.set()
        collector.join(timeout=timeout_s + 30.0)
    wall = (state["t_last"] or time.perf_counter()) - t0
    s = hist.summary()
    # A single-burst schedule has every offset at 0 (span 0): the
    # offered rate is then unmeasurable from the schedule, not a
    # nonsense n/epsilon number.
    span = float(offsets[-1]) if n else 0.0
    return {
        "pattern": pattern,
        "events": n,
        "offered_eps": round(n / span, 1) if span > 0 else None,
        "sustained_eps": round(state["resolved"] / wall, 1) if wall > 0
        else None,
        "wall_s": round(wall, 3),
        "resolved": state["resolved"],
        "errors": state["errors"],
        "max_sched_lag_s": round(behind_s, 3),
        "p50_ms": s["p50"] and round(s["p50"], 3),
        "p99_ms": s["p99"] and round(s["p99"], 3),
        "p999_ms": s["p999"] and round(s["p999"], 3),
        "mean_ms": s["mean"] and round(s["mean"], 3),
        "max_ms": s["max"] and round(s["max"], 3),
    }


# ---------------------------------------------------------------------------
# multi-tenant fleet harness (bench.py serving_slo_fleet)
# ---------------------------------------------------------------------------


def parse_mix(mix: str) -> "list[tuple[str, float]]":
    """``"poisson:2,bursty:1"`` -> [("poisson", 2.0), ("bursty", 1.0)]
    — the weighted per-tenant arrival mixing directive.  A bare pattern
    name means weight 1."""
    out: list = []
    for part in mix.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        if name not in PATTERNS:
            raise ValueError(
                f"unknown pattern {name!r} in mix {mix!r} "
                f"(want {PATTERNS})"
            )
        weight = float(w) if w else 1.0
        if weight <= 0:
            raise ValueError(f"mix weight must be > 0 in {mix!r}")
        out.append((name, weight))
    if not out:
        raise ValueError(f"empty mix {mix!r}")
    return out


def fleet_mix(n_tenants: int, mix: str, rate_eps: float,
              zipf_s: float = 0.0) -> "list[dict]":
    """Assign every tenant a (pattern, weight, rate share) by cycling
    the parsed mix: weights split the aggregate offered rate, so
    ``--tenants 4 --mix poisson:3,bursty:1`` offers 3/8 of the load to
    each Poisson tenant and 1/8 to each bursty one.

    `zipf_s > 0` replaces the cycled mix weights with a Zipf law:
    tenant i gets weight 1/(i+1)^s (patterns still cycle).  This is
    the fleet-scale skew model — a few head tenants dominate the
    offered load while a long tail of cold tenants trickles — exactly
    the working-set shape the tiered-residency paging bench needs: the
    head stays HBM-hot, the tail pages."""
    if n_tenants < 1:
        raise ValueError(f"n_tenants must be >= 1, got {n_tenants}")
    if zipf_s < 0:
        raise ValueError(f"zipf_s must be >= 0, got {zipf_s}")
    pats = parse_mix(mix)
    assigned = [pats[i % len(pats)] for i in range(n_tenants)]
    if zipf_s > 0:
        assigned = [
            (p, float((i + 1) ** -zipf_s))
            for i, (p, _) in enumerate(assigned)
        ]
    total_w = sum(w for _, w in assigned)
    return [
        {"tenant": f"t{i}", "pattern": p, "weight": w,
         "rate_eps": rate_eps * w / total_w}
        for i, (p, w) in enumerate(assigned)
    ]


def _tenant_models(base_model, n: int, seed0: int = 1000):
    """N distinct, validly-normalized models over ONE synthetic day's
    IP/word populations (same shapes -> one pack group; distinct values
    -> cross-tenant demux corruption cannot hide).  Sharing the day
    makes a 1024-tenant census cheap: featurization runs once, only
    the [D+1,K]/[V+1,K] matrices are per-tenant."""
    from oni_ml_tpu.scoring import ScoringModel

    ips = sorted(base_model.ip_index, key=base_model.ip_index.get)
    vocab = sorted(base_model.word_index, key=base_model.word_index.get)
    k = base_model.num_topics
    out = []
    for i in range(n):
        rng = np.random.default_rng(seed0 + i)
        out.append(ScoringModel.from_results(
            ips, rng.dirichlet(np.ones(k), size=len(ips)),
            vocab, rng.dirichlet(np.ones(len(vocab)), size=k).T,
            fallback=0.1,
        ))
    return out


def _fleet_stack(tenant_mix, n_events_per_tenant: int, *,
                 fleet_max_batch: int, fleet_max_wait_ms: float,
                 device_score_min, events_by_tenant=None,
                 shared_day: bool = False, hot_tenants: int = 0,
                 warm_tenants: int = 0, residency_policy: str = "lru",
                 spill_dir: str = "", stack_precision: str = "f32",
                 admission: str = "", tenant_queue_max: int = 0,
                 recorder=None):
    """N synthetic tenant days (distinct models, same K -> ONE pack
    group / ONE compiled batch family) behind the real fleet stack
    (FleetRegistry -> FleetScorer).

    `hot_tenants > 0` attaches the tiered ResidencyManager
    (serving/residency.py): capacity-tiered stack, admission-driven
    paging, `warm_tenants` bounding the host tier (beyond it tenants
    spill to checkpoint-cold npz under `spill_dir`).  `shared_day`
    builds ONE synthetic day and distinct per-tenant models over its
    populations — the only way a 256–1024-tenant census stays cheap
    enough to bench on CPU.  Returns (rows_by_tenant, fleet, scorer,
    residency)."""
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.serving import (
        DnsEventFeaturizer,
        FleetRegistry,
        FleetScorer,
        ResidencyManager,
        TenantSpec,
    )

    tiered = hot_tenants > 0
    fleet = FleetRegistry(
        capacity_tiers=tiered, stack_precision=stack_precision,
        recorder=recorder,
    )
    residency = None
    if tiered:
        residency = ResidencyManager(
            fleet, hot_capacity=hot_tenants,
            warm_capacity=warm_tenants, policy=residency_policy,
            spill_dir=spill_dir, recorder=recorder,
        )
    featurizers: dict = {}
    rows_by_tenant: dict = {}
    if shared_day:
        base_rows, base_model, base_cuts = _synthetic_day(
            n_events=n_events_per_tenant, n_clients=64, n_doms=16,
            seed=100,
        )
        models = _tenant_models(base_model, len(tenant_mix))
    for i, tm in enumerate(tenant_mix):
        if shared_day:
            rows, model, cuts = base_rows, models[i], base_cuts
        else:
            rows, model, cuts = _synthetic_day(
                n_events=n_events_per_tenant, n_clients=64, n_doms=16,
                seed=100 + i,
            )
        n_t = (events_by_tenant[tm["tenant"]]
               if events_by_tenant else len(rows))
        fleet.add_tenant(TenantSpec(
            tenant=tm["tenant"], dsource="dns", weight=tm["weight"],
        ), hot=not tiered)
        fleet.publish(tm["tenant"], model, source="load-gen-fleet")
        if residency is not None:
            residency.register(tm["tenant"])
        featurizers[tm["tenant"]] = DnsEventFeaturizer(cuts)
        rows_by_tenant[tm["tenant"]] = [
            rows[j % len(rows)] for j in range(n_t)
        ]
    cfg = ServingConfig(
        fleet_max_batch=fleet_max_batch,
        fleet_max_wait_ms=fleet_max_wait_ms,
        device_score_min=device_score_min,
        admission=admission or ServingConfig.admission,
        tenant_queue_max=(tenant_queue_max
                          or ServingConfig.tenant_queue_max),
    )
    scorer = FleetScorer(fleet, featurizers, cfg, residency=residency)
    if residency is not None:
        residency.set_pending_probe(
            lambda t: len(scorer._lanes[t].pending) > 0
        )
    return rows_by_tenant, fleet, scorer, residency


def run_fleet_slo(n_tenants: int = 4, mix: str = "poisson:1,bursty:1",
                  *, n_events: int = 4096, rate_eps: float = 4000.0,
                  burst_len: int = 64, max_batch: int = 256,
                  max_wait_ms: float = 10.0, device_score_min=0,
                  seed: int = 0, recorder=None,
                  timeout_s: float = 120.0, zipf_s: float = 0.0,
                  hot_tenants: int = 0, warm_tenants: int = 0,
                  residency_policy: str = "lru", spill_dir: str = "",
                  stack_precision: str = "f32", admission: str = "",
                  tenant_queue_max: int = 0,
                  per_tenant_detail: int = 16) -> dict:
    """The serving_slo_fleet measurement: >= `n_tenants` tenants with
    weighted mixed Poisson/bursty arrivals multiplexed through ONE
    FleetScorer (one shared compiled batch family), per-tenant
    enqueue->resolved latency measured by one FIFO collector per tenant
    (a tenant's futures resolve in its own submit order, so per-tenant
    waits wake promptly), plus the aggregate.  The returned "plans"
    section carries compile-trace counters around the MEASURED window —
    after the warmup burst, a healthy fleet shows
    retraces_after_warmup == 0: the zero-per-tenant-retrace proof the
    acceptance criteria name.

    Paged mode (`hot_tenants > 0`, the serving_slo_fleet_paged bench):
    the fleet runs under the tiered ResidencyManager with a Zipf
    tenant mix (`zipf_s`) whose working set exceeds the HBM-hot
    capacity — per-tenant latency then INCLUDES promotion misses (a
    paging tenant's futures wait out its own promotion), events split
    across tenants by Zipf weight, the day is shared across tenants
    (distinct models), and the payload gains a "residency" section:
    promotions, evictions, cold loads/spills, total priced promotion
    stall, and final tier occupancy.  Zero-retrace applies unchanged:
    churn inside a capacity tier never mints a program."""
    from oni_ml_tpu.plans import warmup as plans_warmup
    from oni_ml_tpu.serving import AdmissionRejected
    from oni_ml_tpu.telemetry.spans import Recorder

    rec = recorder or Recorder()
    paged = hot_tenants > 0
    tenant_mix = fleet_mix(n_tenants, mix, rate_eps, zipf_s)
    if paged and zipf_s > 0:
        # Working-set skew: event counts follow the Zipf weights, so
        # the head stays hot and the tail pages — every tenant still
        # sends at least one event (a tenant never touched would not
        # exercise its paging path).
        total_w = sum(tm["weight"] for tm in tenant_mix)
        events_by_tenant = {
            tm["tenant"]: max(1, int(round(
                n_events * tm["weight"] / total_w)))
            for tm in tenant_mix
        }
        n_per = max(ev for ev in events_by_tenant.values())
    else:
        events_by_tenant = None
        n_per = max(1, n_events // n_tenants)
    rows_by_tenant, fleet, scorer, residency = _fleet_stack(
        tenant_mix, n_per, fleet_max_batch=max_batch,
        fleet_max_wait_ms=max_wait_ms,
        device_score_min=device_score_min,
        events_by_tenant=events_by_tenant, shared_day=paged,
        hot_tenants=hot_tenants, warm_tenants=warm_tenants,
        residency_policy=residency_policy, spill_dir=spill_dir,
        stack_precision=stack_precision, admission=admission,
        tenant_queue_max=tenant_queue_max, recorder=rec,
    )
    agg_hist = rec.histogram("loadgen.fleet.latency_ms")
    tenant_hists = {
        tm["tenant"]: rec.histogram(
            f"loadgen.fleet.{tm['tenant']}.latency_ms"
        )
        for tm in tenant_mix
    }
    try:
        # Warmup burst OUTSIDE the measured window: every compiled
        # shape the packed dispatch family needs traces here, so the
        # timed replay measures steady-state serving, and the
        # compile-counter delta across the replay proves zero retraces.
        # The compile counters are monitoring events off the persistent
        # compilation cache — wire it, or the "proof" counts nothing.
        plans_warmup.setup_compilation_cache()
        plans_warmup._ensure_listener()
        warm_futs = []
        # Paged mode: warm the HEAD tenants only, enough to fill the
        # hot tier — the capacity tier (and with it the compiled
        # stacked shape) reaches its high-water here, so in-window
        # paging churn swaps stack CONTENT, never shape.  Warming all
        # 256+ tenants would just thrash the hot tier before the
        # measurement.
        warm_mix = tenant_mix[:hot_tenants] if paged else tenant_mix
        for i, tm in enumerate(warm_mix):
            rows = rows_by_tenant[tm["tenant"]]
            for r in rows[:max(1, min(len(rows), max_batch))]:
                try:
                    warm_futs.append(scorer.submit(tm["tenant"], r))
                except AdmissionRejected:
                    # Under admission="reject" with queues smaller than
                    # the warmup burst, shedding here is expected; the
                    # events that DID land still trace every shape.
                    scorer.flush()
        scorer.flush()
        for f in warm_futs:
            f.result(timeout=timeout_s)
        counts_before = plans_warmup.compile_counts()
        # Scope the "packed" section to the MEASURED window: the warmup
        # burst's events/batches must not inflate scored-vs-offered
        # cross-checks against n_events/aggregate.resolved.
        events_before = scorer.events_scored
        batches_before = scorer.batches_flushed

        # Per-tenant schedules, merged into one globally-ordered
        # submission timeline.
        schedules: dict = {}
        merged: list = []
        for i, tm in enumerate(tenant_mix):
            t = tm["tenant"]
            n_t = len(rows_by_tenant[t])
            offs = arrival_offsets(
                tm["pattern"], n_t, tm["rate_eps"],
                seed=seed + i, burst_len=burst_len,
            )
            schedules[t] = offs
            merged.extend(
                (float(offs[j]), t, j) for j in range(n_t)
            )
        merged.sort()
        fifo = {t: [None] * len(rows_by_tenant[t]) for t in schedules}
        done = threading.Event()
        states = {
            t: {"resolved": 0, "errors": 0, "shed": 0, "t_last": None}
            for t in schedules
        }

        def collect(tenant):
            slots = fifo[tenant]
            state = states[tenant]
            hist = tenant_hists[tenant]
            for i in range(len(slots)):
                while slots[i] is None:
                    if done.wait(0.0005):
                        if slots[i] is None:
                            return
                        break
                if slots[i] is _SHED:
                    # The submit was load-shed (AdmissionRejected) — no
                    # future exists for this slot; the collector must
                    # release it, not wait on it forever.
                    continue
                fut, t_submit = slots[i]
                try:
                    fut.result(timeout=timeout_s)
                    t_now = time.perf_counter()
                    state["t_last"] = t_now
                    lat_ms = (t_now - t_submit) * 1e3
                    hist.observe(lat_ms)
                    agg_hist.observe(lat_ms)
                    state["resolved"] += 1
                except Exception:
                    state["errors"] += 1

        collectors = [
            threading.Thread(target=collect, args=(t,),
                             name=f"loadgen-fleet-{t}", daemon=True)
            for t in schedules
        ]
        for c in collectors:
            c.start()
        t0 = time.perf_counter()
        behind_s = 0.0
        try:
            for off, tenant, j in merged:
                target = t0 + off
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                else:
                    behind_s = max(behind_s, now - target)
                t_submit = time.perf_counter()
                try:
                    fut = scorer.submit(
                        tenant, rows_by_tenant[tenant][j]
                    )
                except AdmissionRejected:
                    # Shedding is an expected outcome of paged /
                    # admission="reject" runs, not a harness failure:
                    # mark the slot so the tenant's collector skips it
                    # (an unfilled slot would park the thread until the
                    # global release, silently eating every later
                    # latency sample of that tenant) and keep
                    # replaying the schedule.
                    fifo[tenant][j] = _SHED
                    states[tenant]["shed"] += 1
                    continue
                fifo[tenant][j] = (fut, t_submit)
            scorer.flush()
        finally:
            done.set()
            for c in collectors:
                c.join(timeout=timeout_s + 30.0)
        counts_after = plans_warmup.compile_counts()
        t_last_all = max(
            (s["t_last"] for s in states.values()
             if s["t_last"] is not None),
            default=None,
        )
        wall = (t_last_all or time.perf_counter()) - t0
        resolved = sum(s["resolved"] for s in states.values())
        errors = sum(s["errors"] for s in states.values())

        def _quant(h):
            s = h.summary()
            return {
                "p50_ms": s["p50"] and round(s["p50"], 3),
                "p99_ms": s["p99"] and round(s["p99"], 3),
                "p999_ms": s["p999"] and round(s["p999"], 3),
                "mean_ms": s["mean"] and round(s["mean"], 3),
                "max_ms": s["max"] and round(s["max"], 3),
            }

        tenants_all = {}
        for tm in tenant_mix:
            t = tm["tenant"]
            state = states[t]
            span = float(schedules[t][-1]) if len(schedules[t]) else 0.0
            t_wall = (state["t_last"] or t0) - t0
            tenants_all[t] = {
                "pattern": tm["pattern"],
                "weight": round(tm["weight"], 6),
                "events": len(rows_by_tenant[t]),
                "offered_eps": round(len(schedules[t]) / span, 1)
                if span > 0 else None,
                "sustained_eps": round(state["resolved"] / t_wall, 1)
                if t_wall > 0 else None,
                "resolved": state["resolved"],
                "errors": state["errors"],
                "shed": state["shed"],
                **_quant(tenant_hists[t]),
            }
        # At fleet scale the full per-tenant dict would dominate the
        # payload: emit detail for the HEAD tenants (mix order = Zipf
        # head first) plus a distribution summary over EVERY tenant's
        # quantiles, and say so — a truncated report must never read
        # as a complete one.
        truncated = len(tenants_all) > per_tenant_detail
        tenants_out = dict(
            list(tenants_all.items())[:per_tenant_detail])

        def _dist(key):
            vals = [v[key] for v in tenants_all.values()
                    if isinstance(v.get(key), (int, float))]
            if not vals:
                return None
            return {
                "min": round(min(vals), 3),
                "median": round(float(np.median(vals)), 3),
                "max": round(max(vals), 3),
            }

        tenant_summary = {
            key: _dist(key)
            for key in ("sustained_eps", "p50_ms", "p99_ms", "p999_ms")
        }
        return {
            "n_tenants": n_tenants,
            "mix": mix,
            "zipf_s": zipf_s or None,
            "n_events": sum(len(r) for r in rows_by_tenant.values()),
            "offered_eps": rate_eps,
            "burst_len": burst_len,
            "fleet_max_batch": scorer.max_batch,
            "fleet_max_wait_ms": scorer.max_wait_ms,
            "aggregate": {
                "sustained_eps": round(resolved / wall, 1)
                if wall > 0 else None,
                "wall_s": round(wall, 3),
                "resolved": resolved,
                "errors": errors,
                "shed": sum(s["shed"] for s in states.values()),
                "max_sched_lag_s": round(behind_s, 3),
                **_quant(agg_hist),
            },
            "tenants": tenants_out,
            "tenants_truncated": truncated,
            "tenant_summary": tenant_summary,
            # Tiered-residency accounting (paged mode): per-tenant
            # latencies above already INCLUDE promotion misses — a
            # paging tenant's futures wait out its own promotion.
            "residency": (residency.stats_snapshot()
                          if residency is not None else None),
            "packed": {
                # Measured window only (warmup deltas subtracted);
                # tenant_stats stays cumulative — its per-tenant
                # submitted/scored include the warmup burst.
                "batches": scorer.batches_flushed - batches_before,
                "events_scored": scorer.events_scored - events_before,
                "tenant_stats": scorer.tenant_stats(),
            },
            # The zero-retrace proof: compile requests the persistent
            # cache could not serve DURING the measured window.  After
            # the warmup burst every padded shape is compiled, so a
            # healthy fleet reports 0 here — per-tenant hot paths ride
            # one shared program family, keyed by shape, not tenant.
            "plans": {
                "warmup_events": len(warm_futs),
                "counting": plans_warmup._ensure_listener(),
                "traces_before": counts_before.get("traces"),
                "traces_after": counts_after.get("traces"),
                "retraces_after_warmup": (
                    counts_after.get("traces", 0)
                    - counts_before.get("traces", 0)
                ),
            },
        }
    finally:
        scorer.close()
        if residency is not None:
            residency.close()


# ---------------------------------------------------------------------------
# replicated fleet harness (bench.py serving_slo_replicated)
# ---------------------------------------------------------------------------

# Replica subprocesses this harness starts run on the host CPU, by
# name: what it loads is the serving plane's host path (routing, wire,
# demux), several replicas cannot share one chip, and placing one
# replica per chip is ROADMAP A5.
REPLICA_PLATFORM = "cpu"


def _replicated_stack(n_replicas: int, tenant_mix, models, cuts, *,
                      max_batch: int, max_wait_ms: float,
                      route_window: int, spawn: str, workdir: str,
                      device_score_min, recorder=None, journal=None):
    """Router + N serve replicas hosting the shared-day census
    (serving/router.py + replica.py).  `spawn="process"` runs each
    replica as a real `ml_ops replica` subprocess — its own Python,
    its own backend, the honest blast radius — while `spawn="thread"`
    hosts ReplicaServer in-process for cheap tests.  Returns (router,
    procs, servers)."""
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.serving import FleetRouter, TenantSpec

    cfg = ServingConfig(
        fleet_max_batch=max_batch, fleet_max_wait_ms=max_wait_ms,
        device_score_min=device_score_min,
        route_max_inflight=route_window,
    )
    procs: dict = {}
    servers: dict = {}
    router = FleetRouter(cfg, recorder=recorder, journal=journal)
    kv_dir = os.path.join(workdir, f"kv{n_replicas}")
    for i in range(n_replicas):
        rid = f"r{i}"
        if spawn == "process":
            from oni_ml_tpu.runner.route import _spawn_replica

            extra = [
                "--fleet-max-batch", str(max_batch),
                "--fleet-max-wait-ms", str(max_wait_ms),
            ]
            if device_score_min is None:
                extra += ["--device-score-min", "none"]
            proc, host, port = _spawn_replica(
                rid, kv_dir, workdir, extra, platform=REPLICA_PLATFORM)
            procs[rid] = proc
        else:
            from oni_ml_tpu.serving import ReplicaServer

            srv = ReplicaServer(rid, cfg)
            servers[rid] = srv
            host, port = srv.host, srv.port
        router.connect_replica(rid, host, port)
    for i, tm in enumerate(tenant_mix):
        router.add_tenant(
            TenantSpec(tenant=tm["tenant"], dsource="dns",
                       weight=tm["weight"]),
            cuts, models[i],
        )
    router.start(warmup=True)
    return router, procs, servers


def _replicated_teardown(router, procs, servers) -> None:
    try:
        router.close()
    except Exception:
        pass
    for proc in procs.values():
        if proc.poll() is None:
            proc.terminate()
    for proc in procs.values():
        try:
            proc.wait(timeout=30.0)
        except Exception:
            proc.kill()
    for srv in servers.values():
        srv.stop()


def _zipf_counts(tenants, weights, total: int) -> "dict[str, int]":
    """Split `total` events across tenants proportionally to their
    Zipf weights, every tenant getting at least one (a tenant never
    touched exercises nothing)."""
    total_w = sum(weights)
    return {
        t: max(1, int(round(total * w / total_w)))
        for t, w in zip(tenants, weights)
    }


def _trace_count(stats: dict) -> int:
    out = 0
    for s in stats.values():
        c = s.get("compile") or {}
        out += int(c.get("traces") or 0)
    return out


def _scaling_leg(n_replicas: int, tenant_mix, models, rows, cuts, *,
                 events_per_replica: int, chunk: int, max_batch: int,
                 max_wait_ms: float, route_window: int, spawn: str,
                 workdir: str, device_score_min,
                 timeout_s: float) -> dict:
    """Saturation throughput at one replica count: one closed-loop
    feeder per replica drives ITS tenants (census split by primary
    placement, per-tenant volumes by Zipf weight) through submit_many
    chunks as fast as the bounded admission window admits.  Per-replica
    throughput is the Little's-law window/round-trip bound, so
    aggregate sustained events/s scales with the replica count until
    the host's cores saturate."""
    router, procs, servers = _replicated_stack(
        n_replicas, tenant_mix, models, cuts, max_batch=max_batch,
        max_wait_ms=max_wait_ms, route_window=route_window,
        spawn=spawn, workdir=workdir,
        device_score_min=device_score_min,
    )
    try:
        placement = router.placement()
        weight = {tm["tenant"]: tm["weight"] for tm in tenant_mix}
        by_rep: dict = {}
        for t, p in placement.items():
            by_rep.setdefault(p.primary, []).append(t)
        counts: dict = {}
        for r, tenants in by_rep.items():
            counts.update(_zipf_counts(
                tenants, [weight[t] for t in tenants],
                events_per_replica,
            ))
        # Warmup OUTSIDE the measured window: a few flushes trace the
        # packed shapes (and the shared plan/compilation cache means a
        # respawned replica pays nothing again).
        warm = []
        for t in placement:
            warm += router.submit_many(
                t, [rows[j % len(rows)] for j in range(8)])
        router.flush()
        for f in warm:
            f.result(timeout=timeout_s)
        stats_before = router.replica_stats()
        results: dict = {}
        errors: "list[int]" = []

        def feed(rep, tenants):
            futs = []
            errs = 0
            try:
                remaining = {t: counts[t] for t in tenants}
                sent = {t: 0 for t in tenants}
                while any(remaining.values()):
                    for t in tenants:
                        take = min(chunk, remaining[t])
                        if not take:
                            continue
                        futs += router.submit_many(t, [
                            rows[(sent[t] + j) % len(rows)]
                            for j in range(take)
                        ])
                        sent[t] += take
                        remaining[t] -= take
                router.flush()
                for f in futs:
                    try:
                        f.result(timeout=timeout_s)
                    except Exception:
                        errs += 1
            except Exception:
                # A feeder that dies (replica lost beyond failover,
                # router closed) must surface as ERRORS in the
                # payload, never as a silently-thinner denominator
                # behind a plausible sustained_eps.
                errs += sum(1 for f in futs if not f.done())
                errs = max(errs, 1)
            finally:
                errors.append(errs)
                results[rep] = len(futs)

        feeders = [
            threading.Thread(target=feed, args=(r, ts),
                             name=f"loadgen-rep-{r}", daemon=True)
            for r, ts in by_rep.items()
        ]
        t0 = time.perf_counter()
        for f in feeders:
            f.start()
        for f in feeders:
            f.join(timeout=timeout_s + 60.0)
        wall = time.perf_counter() - t0
        stats_after = router.replica_stats()
        total = sum(results.values())
        return {
            "replicas": n_replicas,
            "events": total,
            "wall_s": round(wall, 3),
            "sustained_eps": round(total / wall, 1) if wall else None,
            "errors": sum(errors),
            "retraces_in_window": (
                _trace_count(stats_after) - _trace_count(stats_before)
            ),
            "route": router.stats()["edges"],
        }
    finally:
        _replicated_teardown(router, procs, servers)


def _chaos_leg(tenant_mix, models, rows, cuts, *, chaos_events: int,
               chaos_rate_eps: float, kill_frac: float, chunk: int,
               max_batch: int, max_wait_ms: float, route_window: int,
               spawn: str, workdir: str, device_score_min,
               recorder, seed: int, timeout_s: float) -> dict:
    """Kill-a-replica chaos at 2 replicas: open-loop Poisson replay
    across the whole census, SIGKILL one replica mid-stream, and
    measure what the failover actually cost — zero failed futures
    for tenants on the surviving replica (and zero for the victims
    too: the admission journal replays them onto the promoted
    shadow), p999 DURING the failover window, time to full recovery,
    bit-identical survivor scores, and zero post-recovery retraces on
    the survivor."""
    from oni_ml_tpu.serving import DnsEventFeaturizer, score_features

    router, procs, servers = _replicated_stack(
        2, tenant_mix, models, cuts, max_batch=max_batch,
        max_wait_ms=max_wait_ms, route_window=route_window,
        spawn=spawn, workdir=workdir,
        device_score_min=device_score_min, recorder=recorder,
    )
    try:
        placement = router.placement()
        tenants = [tm["tenant"] for tm in tenant_mix]
        weight = {tm["tenant"]: tm["weight"] for tm in tenant_mix}
        victim = placement[tenants[0]].primary
        counts = _zipf_counts(tenants, [weight[t] for t in tenants],
                              chaos_events)
        # Warmup outside the window.
        warm = []
        for t in tenants:
            warm += router.submit_many(
                t, [rows[j % len(rows)] for j in range(8)])
        router.flush()
        for f in warm:
            f.result(timeout=timeout_s)
        stats_before = router.replica_stats()
        # Merged open-loop Poisson schedule, event volumes by Zipf
        # weight; per-tenant FIFO collectors record absolute submit /
        # resolve stamps so the failover window can be reconstructed.
        merged: list = []
        for i, t in enumerate(tenants):
            offs = arrival_offsets(
                "poisson", counts[t],
                chaos_rate_eps * weight[t] / sum(weight.values()),
                seed=seed + i,
            )
            merged.extend((float(offs[j]), t, j)
                          for j in range(counts[t]))
        merged.sort()
        fifo = {t: [None] * counts[t] for t in tenants}
        samples = {t: [] for t in tenants}   # (t_sub, t_res, ok, score)
        done = threading.Event()

        def collect(tenant):
            slots = fifo[tenant]
            out = samples[tenant]
            for i in range(len(slots)):
                while slots[i] is None:
                    if done.wait(0.0005):
                        if slots[i] is None:
                            return
                        break
                fut, t_sub = slots[i]
                try:
                    score, _ = fut.result(timeout=timeout_s)
                    out.append(
                        (t_sub, time.perf_counter(), True, score))
                except Exception:
                    out.append(
                        (t_sub, time.perf_counter(), False, None))

        collectors = [
            threading.Thread(target=collect, args=(t,),
                             name=f"loadgen-chaos-{t}", daemon=True)
            for t in tenants
        ]
        for c in collectors:
            c.start()
        kill_at = int(len(merged) * kill_frac)
        t_kill = None
        t0 = time.perf_counter()
        try:
            for i, (off, tenant, j) in enumerate(merged):
                if i == kill_at:
                    if procs:
                        procs[victim].kill()  # SIGKILL, the real thing
                    else:
                        servers[victim].kill()
                    t_kill = time.perf_counter()
                target = t0 + off
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                t_sub = time.perf_counter()
                fut = router.submit(tenant, rows[j % len(rows)])
                fifo[tenant][j] = (fut, t_sub)
            router.flush()
        finally:
            # Unconditionally release the collectors (run_fleet_slo's
            # contract): a submit that raises mid-chaos must not leave
            # one busy-polling daemon thread per tenant for the life
            # of the bench process.
            done.set()
            for c in collectors:
                c.join(timeout=timeout_s + 60.0)
        # -- post-run accounting -----------------------------------------
        victims = {t for t, p in placement.items()
                   if p.primary == victim}
        surviving = set(tenants) - victims
        err_surv = sum(
            1 for t in surviving for s in samples[t] if not s[2])
        err_vic = sum(
            1 for t in victims for s in samples[t] if not s[2])
        # In flight at the kill: submitted before, resolved after —
        # full recovery is when the LAST of them lands.
        t_rec = t_kill
        for t in victims:
            for t_sub, t_res, ok, _ in samples[t]:
                if ok and t_sub <= t_kill < t_res:
                    t_rec = max(t_rec, t_res)
        recovery_s = t_rec - t_kill
        fo_hist = recorder.histogram(
            "loadgen.replicated.failover_ms")
        all_hist = recorder.histogram(
            "loadgen.replicated.latency_ms")
        window_n = 0
        for t in tenants:
            for t_sub, t_res, ok, _ in samples[t]:
                if not ok:
                    continue
                lat_ms = (t_res - t_sub) * 1e3
                all_hist.observe(lat_ms)
                if t_kill <= t_sub <= t_rec:
                    fo_hist.observe(lat_ms)
                    window_n += 1
        # Survivor bit-identity: a surviving tenant's scores must equal
        # the single-process oracle (packing/routing never changes
        # arithmetic, even while the other replica dies).
        probe = sorted(surviving)[0] if surviving else None
        bit_identical = None
        if probe is not None:
            # Collector order == submit order == event index j, and
            # event j scored rows[j % len(rows)].
            got = [s[3] for s in samples[probe]]
            used = [rows[j % len(rows)] for j in range(len(got))]
            feats = DnsEventFeaturizer(cuts)(used)
            oracle = score_features(
                models[tenants.index(probe)], feats, "dns")
            bit_identical = (
                len(got) == counts[probe]
                and all(s is not None for s in got)
                and bool(np.array_equal(
                    np.asarray(got, np.float64), oracle))
            )
        stats_after = router.replica_stats()
        surv_traces = _trace_count(
            {r: s for r, s in stats_after.items() if r != victim}
        ) - _trace_count(
            {r: s for r, s in stats_before.items() if r != victim}
        )
        fo = fo_hist.summary()
        al = all_hist.summary()
        # The recovery record lands on a reader thread after the
        # journal replay + shadow backfill; give it a moment rather
        # than racing it.
        deadline = time.monotonic() + 15.0
        failovers = router.stats()["failovers"]
        while not failovers and time.monotonic() < deadline:
            time.sleep(0.02)
            failovers = router.stats()["failovers"]
        return {
            "replicas": 2,
            "killed": victim,
            "offered_eps": chaos_rate_eps,
            "events": len(merged),
            "victim_tenants": len(victims),
            "errors_surviving": err_surv,
            "errors_victim_tenants": err_vic,
            "p50_ms": al["p50"] and round(al["p50"], 3),
            "p99_ms": al["p99"] and round(al["p99"], 3),
            "p999_ms": al["p999"] and round(al["p999"], 3),
            "failover_window_events": window_n,
            "failover_p999_ms": fo["p999"] and round(fo["p999"], 3),
            "time_to_recovery_s": round(recovery_s, 4),
            "survivor_bit_identical": bit_identical,
            "retraces_after_recovery": surv_traces,
            "failover_record": failovers[-1] if failovers else None,
        }
    finally:
        _replicated_teardown(router, procs, servers)


def run_replicated_slo(replica_counts=(1, 2, 4), *,
                       n_tenants: int = 256, zipf_s: float = 1.1,
                       events_per_replica: int = 3072,
                       chunk: int = 32, max_batch: int = 256,
                       max_wait_ms: float = 20.0,
                       route_window: int = 64,
                       chaos: bool = True,
                       chaos_events: int = 4096,
                       chaos_rate_eps: float = 1500.0,
                       kill_frac: float = 0.4,
                       spawn: str = "process",
                       day_events: int = 512, seed: int = 0,
                       device_score_min=0, recorder=None,
                       timeout_s: float = 300.0) -> dict:
    """The serving_slo_replicated measurement (ROADMAP item 5): the
    same Zipf tenant census served by 1, 2, and 4 replicas behind the
    async router, saturation throughput per count (the bounded
    per-replica admission window makes per-replica capacity a real
    Little's-law bound, so aggregate events/s scales with the count),
    plus a kill-a-replica chaos phase measuring p999 during failover,
    time-to-full-recovery, zero failed futures, bit-identical
    survivor scores, and zero post-recovery retraces."""
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.telemetry.spans import Recorder

    rec = recorder or Recorder()
    workdir = tempfile.mkdtemp(prefix="oni_replicated_")
    rows, base_model, cuts = _synthetic_day(
        n_events=day_events, n_clients=64, n_doms=16, seed=100)
    tenant_mix = fleet_mix(n_tenants, "poisson:1", 1000.0, zipf_s)
    models = _tenant_models(base_model, n_tenants)
    try:
        scaling: dict = {}
        for n in replica_counts:
            scaling[str(n)] = _scaling_leg(
                n, tenant_mix, models, rows, cuts,
                events_per_replica=events_per_replica, chunk=chunk,
                max_batch=max_batch, max_wait_ms=max_wait_ms,
                route_window=route_window, spawn=spawn,
                workdir=workdir, device_score_min=device_score_min,
                timeout_s=timeout_s,
            )
        counts = sorted(int(k) for k in scaling)
        eps = {n: scaling[str(n)]["sustained_eps"] for n in counts}
        base = eps.get(counts[0])
        efficiency = {
            str(n): (round(eps[n] / (n / counts[0] * base), 4)
                     if base and eps.get(n) else None)
            for n in counts
        }
        eff2 = efficiency.get("2")
        out = {
            "n_tenants": n_tenants,
            "zipf_s": zipf_s,
            "spawn": spawn,
            "route_window": route_window,
            "max_wait_ms": max_wait_ms,
            "replica_counts": list(counts),
            "scaling": scaling,
            "sustained_eps_by_count": {
                str(n): eps[n] for n in counts},
            "replica_scaling_efficiency": eff2,
            "replica_scaling_efficiency_by_count": efficiency,
            "retraces_in_windows": sum(
                s["retraces_in_window"] for s in scaling.values()),
        }
        if chaos and len(tenant_mix) >= 2:
            out["chaos"] = _chaos_leg(
                tenant_mix, models, rows, cuts,
                chaos_events=chaos_events,
                chaos_rate_eps=chaos_rate_eps, kill_frac=kill_frac,
                chunk=chunk, max_batch=max_batch,
                max_wait_ms=max_wait_ms, route_window=route_window,
                spawn=spawn, workdir=workdir,
                device_score_min=device_score_min, recorder=rec,
                seed=seed, timeout_s=timeout_s,
            )
            out["failover_p999_ms"] = out["chaos"]["failover_p999_ms"]
            out["time_to_recovery_s"] = (
                out["chaos"]["time_to_recovery_s"])
        return out
    finally:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Cross-host serving: multi-router fan-in + Little's-law autoscaling
# ---------------------------------------------------------------------------


def _crosshost_census(n_tenants: int, zipf_s: float,
                      day_events: int):
    """The shared census, built DETERMINISTICALLY from its parameters
    alone: every fan-in router worker is a separate process with no
    channel to ship models over, so each rebuilds the identical day,
    tenant mix, and per-tenant models from the same seeds — placement
    is a pure function of membership, the census a pure function of
    its size."""
    from oni_ml_tpu.runner.serve import _synthetic_day

    rows, base_model, cuts = _synthetic_day(
        n_events=day_events, n_clients=64, n_doms=16, seed=100)
    tenant_mix = fleet_mix(n_tenants, "poisson:1", 1000.0, zipf_s)
    models = _tenant_models(base_model, n_tenants)
    return rows, cuts, tenant_mix, models


def _worker_drive(router, rows, cuts, models, tenant_index, cmd,
                  timeout_s: float) -> dict:
    """One closed-loop drive inside a router worker: feeders grouped
    by primary replica (a full admission window on one edge must not
    stall the others) push submit_many chunks, progress checkpoints
    stream to stdout (the parent's router-kill reassignment reads
    them), and the optional `verify` tenant's scores are pinned
    bit-identical against the single-process host oracle."""
    from oni_ml_tpu.serving import DnsEventFeaturizer, score_features

    counts = {t: int(n) for t, n in cmd["counts"].items()
              if int(n) > 0}
    start = {t: int(v) for t, v in (cmd.get("start") or {}).items()}
    chunk = max(1, int(cmd.get("chunk", 8)))
    verify = cmd.get("verify")
    placement = router.placement()
    by_rep: dict = {}
    for t in counts:
        by_rep.setdefault(placement[t].primary, []).append(t)
    futs: dict = {t: [] for t in counts}
    sent = {t: start.get(t, 0) for t in counts}
    plock = threading.Lock()
    reported = [0]
    feed_errors = [0]

    def _report(force: bool = False) -> None:
        done_n = sum(sent[t] - start.get(t, 0) for t in counts)
        if force or done_n - reported[0] >= 256:
            reported[0] = done_n
            print(json.dumps({"progress": done_n,
                              "sent": dict(sent)}), flush=True)

    edges0 = {r: dict(e)
              for r, e in router.stats()["edges"].items()}
    t0 = time.perf_counter()

    def feed(tenants):
        try:
            remaining = {t: counts[t] for t in tenants}
            while any(remaining.values()):
                for t in tenants:
                    take = min(chunk, remaining[t])
                    if not take:
                        continue
                    futs[t] += router.submit_many(t, [
                        rows[(sent[t] + j) % len(rows)]
                        for j in range(take)
                    ])
                    with plock:
                        sent[t] += take
                        remaining[t] -= take
                        _report()
        except Exception:
            with plock:
                feed_errors[0] += 1

    feeders = [
        threading.Thread(target=feed, args=(ts,), daemon=True,
                         name=f"loadgen-fanin-{r}")
        for r, ts in by_rep.items()
    ]
    for f in feeders:
        f.start()
    for f in feeders:
        f.join(timeout=timeout_s + 60.0)
    router.flush()
    errors = feed_errors[0]
    scores: dict = {}
    for t, fs in futs.items():
        vals = []
        for f in fs:
            try:
                vals.append(f.result(timeout=timeout_s)[0])
            except Exception:
                errors += 1
                vals.append(None)
        scores[t] = vals
    wall = time.perf_counter() - t0
    with plock:
        _report(force=True)
    edges1 = router.stats()["edges"]
    d_bytes = sum(e["bytes"] - edges0.get(r, {}).get("bytes", 0)
                  for r, e in edges1.items())
    d_events = sum(e["events"] - edges0.get(r, {}).get("events", 0)
                   for r, e in edges1.items())
    total = sum(len(v) for v in scores.values())
    out = {
        "router": router.router_id,
        "events": total,
        "wall_s": round(wall, 3),
        "eps": round(total / wall, 1) if wall else None,
        "errors": errors,
        "wire_bytes": d_bytes,
        "wire_events": d_events,
        "wire_bytes_per_event": (round(d_bytes / d_events, 1)
                                 if d_events else None),
    }
    if verify and scores.get(verify):
        got = scores[verify]
        off = start.get(verify, 0)
        used = [rows[(off + j) % len(rows)] for j in range(len(got))]
        feats = DnsEventFeaturizer(cuts)(used)
        oracle = score_features(models[tenant_index[verify]], feats,
                                "dns")
        out["verify_tenant"] = verify
        out["bit_identical"] = (
            all(s is not None for s in got)
            and bool(np.array_equal(np.asarray(got, np.float64),
                                    oracle))
        )
    return out


def _router_worker_main(config_json: str) -> int:
    """Subprocess entry for one fan-in router (`--router-worker`,
    spawned by run_router_fanin): its own Python, its own GIL — the
    per-router submit-loop ceiling is real, so aggregate events/s can
    exceed what one router process sustains.  Rebuilds the census
    deterministically (_crosshost_census), discovers replicas through
    the shared KV roster, then serves line-delimited JSON commands on
    stdin: drive / stats / exit."""
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.parallel.membership import FileKVClient
    from oni_ml_tpu.serving import FleetRouter, TenantSpec

    cfg_in = json.loads(config_json)
    timeout_s = float(cfg_in.get("timeout_s", 300.0))
    rows, cuts, tenant_mix, models = _crosshost_census(
        int(cfg_in["n_tenants"]), float(cfg_in["zipf_s"]),
        int(cfg_in.get("day_events", 256)))
    tenant_index = {tm["tenant"]: i
                    for i, tm in enumerate(tenant_mix)}
    cfg = ServingConfig(
        fleet_max_batch=int(cfg_in["max_batch"]),
        fleet_max_wait_ms=float(cfg_in["max_wait_ms"]),
        route_max_inflight=int(cfg_in["route_window"]),
        device_score_min=cfg_in.get("device_score_min", 0),
    )
    router = FleetRouter(cfg, kv=FileKVClient(cfg_in["kv_dir"]),
                         router_id=cfg_in["router_id"])
    expect = set(cfg_in.get("expect") or [])
    deadline = time.monotonic() + timeout_s
    connected = router.connect_from_membership()
    while expect - set(connected) and time.monotonic() < deadline:
        time.sleep(0.1)
        connected = router.connect_from_membership()
    missing = sorted(expect - set(connected))
    if missing:
        print(json.dumps({"error": f"missing replicas {missing}"}),
              flush=True)
        router.close()
        return 3
    for i, tm in enumerate(tenant_mix):
        router.add_tenant(
            TenantSpec(tenant=tm["tenant"], dsource="dns",
                       weight=tm["weight"]),
            cuts, models[i],
        )
    router.start(warmup=bool(cfg_in.get("warmup", True)))
    print(json.dumps({"ready": True, "router": router.router_id,
                      "replicas": connected}), flush=True)
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            cmd = json.loads(line)
            op = cmd.get("cmd")
            if op == "drive":
                res = _worker_drive(router, rows, cuts, models,
                                    tenant_index, cmd, timeout_s)
                print(json.dumps({"result": res}), flush=True)
            elif op == "stats":
                print(json.dumps({"stats": router.stats()}),
                      flush=True)
            elif op == "exit":
                break
    finally:
        router.close()
    return 0


class _RouterWorker:
    """Parent-side handle on one `--router-worker` subprocess:
    line-delimited JSON over stdin/stdout, a reader thread folding
    progress checkpoints into `self.progress` (what the router-kill
    reassignment reads off a freshly-dead victim) and queuing
    results."""

    def __init__(self, worker_cfg: dict) -> None:
        import subprocess

        self.router_id = worker_cfg["router_id"]
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--router-worker", json.dumps(worker_cfg)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1,
        )
        self.ready = threading.Event()
        self.ready_info: dict = {}
        self.progress: dict = {"progress": 0, "sent": {}}
        self._results: list = []
        self._cond = threading.Condition()
        threading.Thread(
            target=self._read, daemon=True,
            name=f"loadgen-worker-{self.router_id}").start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if "ready" in msg:
                with self._cond:
                    self.ready_info = msg
                self.ready.set()
            elif "progress" in msg:
                with self._cond:
                    self.progress = msg
            else:
                with self._cond:
                    self._results.append(msg)
                    self._cond.notify_all()
        self.ready.set()    # EOF unblocks a waiter on a dead worker

    def wait_ready(self, timeout_s: float) -> dict:
        if not self.ready.wait(timeout_s) or not self.ready_info:
            raise RuntimeError(
                f"router worker {self.router_id} never came up")
        return self.ready_info

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def drive(self, counts: dict, *, start=None, verify=None,
              chunk: int = 8) -> None:
        self.send({"cmd": "drive", "counts": counts,
                   "start": start or {}, "chunk": chunk,
                   "verify": verify})

    def result(self, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while not self._results:
                left = deadline - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    raise RuntimeError(
                        f"router worker {self.router_id} gave no "
                        "result")
                self._cond.wait(min(left, 0.1))
            msg = self._results.pop(0)
        if "result" not in msg:
            raise RuntimeError(
                f"router worker {self.router_id}: {msg}")
        return msg["result"]

    def kill(self) -> None:
        self.proc.kill()

    def close(self) -> None:
        try:
            self.send({"cmd": "exit"})
        except Exception:
            pass
        try:
            self.proc.wait(timeout=30.0)
        except Exception:
            self.proc.kill()


def _fanin_leg(n_routers: int, worker_cfg: dict, tenant_mix,
               events_total: int, *, chunk: int,
               timeout_s: float) -> dict:
    """Aggregate throughput at one router count: the census split
    round-robin across N router processes, each driving its slice
    closed-loop against the SAME replica fleet (zero router
    coordination — placement is a pure function of the shared
    roster)."""
    tenants = [tm["tenant"] for tm in tenant_mix]
    weight = {tm["tenant"]: tm["weight"] for tm in tenant_mix}
    counts = _zipf_counts(tenants, [weight[t] for t in tenants],
                          events_total)
    workers = [
        _RouterWorker({**worker_cfg,
                       "router_id": f"fanin{n_routers}-{i}",
                       "warmup": i == 0})
        for i in range(n_routers)
    ]
    try:
        for w in workers:
            w.wait_ready(timeout_s)
        # Greedy weight-balanced slices: every router gets an equal
        # share of the OFFERED load, not just of the tenant count —
        # under skew a head-tenant slice would otherwise spend its
        # tail draining one admission window while the others idle.
        order = sorted(tenants, key=lambda t: -weight[t])
        slices: "list[list[str]]" = [[] for _ in range(n_routers)]
        loads = [0.0] * n_routers
        for t in order:
            i = min(range(n_routers), key=loads.__getitem__)
            slices[i].append(t)
            loads[i] += weight[t]
        t0 = time.perf_counter()
        for w, sl in zip(workers, slices):
            w.drive({t: counts[t] for t in sl}, verify=sl[0],
                    chunk=chunk)
        results = [w.result(timeout_s + 120.0) for w in workers]
        parent_wall = time.perf_counter() - t0
        # The serving window is each worker's submit->resolved wall;
        # the parent's wall additionally serializes result retrieval
        # and the in-worker oracle verify, which is measurement
        # overhead, not routing.
        wall = max(r["wall_s"] for r in results)
        total = sum(r["events"] for r in results)
        wb = sum(r["wire_bytes"] for r in results)
        we = sum(r["wire_events"] for r in results)
        return {
            "routers": n_routers,
            "events": total,
            "wall_s": round(wall, 3),
            "parent_wall_s": round(parent_wall, 3),
            "aggregate_eps": round(total / wall, 1) if wall else None,
            "per_router_eps": {r["router"]: r["eps"]
                               for r in results},
            "errors": sum(r["errors"] for r in results),
            "bit_identical": all(r.get("bit_identical")
                                 for r in results),
            "wire_bytes_per_event": (round(wb / we, 1)
                                     if we else None),
        }
    finally:
        for w in workers:
            w.close()


def _router_chaos_leg(worker_cfg: dict, tenant_mix,
                      chaos_events: int, *, kill_frac: float,
                      chunk: int, timeout_s: float) -> dict:
    """Router-kill chaos at 2 routers: SIGKILL one router process
    mid-census and have the survivor ABSORB the victim's remaining
    slice from its last progress checkpoint — replicas never notice
    (no replica died, no failover), the survivor resolves every one
    of its own futures, and the absorbed slice stays bit-identical to
    the host oracle.  Events between the victim's last checkpoint and
    the kill are re-driven (scoring is pure, duplicates are
    harmless); the count is reported, never hidden."""
    tenants = [tm["tenant"] for tm in tenant_mix]
    weight = {tm["tenant"]: tm["weight"] for tm in tenant_mix}
    counts = _zipf_counts(tenants, [weight[t] for t in tenants],
                          chaos_events)
    survivor = _RouterWorker({**worker_cfg, "router_id": "chaos-a",
                              "warmup": True})
    victim = _RouterWorker({**worker_cfg, "router_id": "chaos-b",
                            "warmup": False})
    try:
        survivor.wait_ready(timeout_s)
        victim.wait_ready(timeout_s)
        sl_a = tenants[0::2]
        sl_b = tenants[1::2]
        counts_b = {t: counts[t] for t in sl_b}
        total_b = sum(counts_b.values())
        survivor.drive({t: counts[t] for t in sl_a}, verify=sl_a[0],
                       chunk=chunk)
        victim.drive(counts_b, chunk=chunk)
        kill_at = int(total_b * kill_frac)
        deadline = time.monotonic() + timeout_s
        while victim.progress["progress"] < kill_at:
            if victim.proc.poll() is not None:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "victim router never reached the kill point")
            time.sleep(0.002)
        victim.kill()   # SIGKILL, the real thing
        t_kill = time.perf_counter()
        sent_b = dict(victim.progress.get("sent") or {})
        remaining = {t: counts_b[t] - int(sent_b.get(t, 0))
                     for t in counts_b}
        remaining = {t: n for t, n in remaining.items() if n > 0}
        redriven = sum(remaining.values())
        absorb = None
        if remaining:
            verify_t = max(remaining, key=remaining.get)
            survivor.drive(
                remaining,
                start={t: int(sent_b.get(t, 0)) for t in remaining},
                verify=verify_t, chunk=chunk)
        res_a = survivor.result(timeout_s + 120.0)
        if remaining:
            absorb = survivor.result(timeout_s + 120.0)
        t_done = time.perf_counter()
        return {
            "routers": 2,
            "killed": victim.router_id,
            "events": chaos_events,
            "victim_checkpointed_events": int(
                victim.progress.get("progress", 0)),
            "redriven_events": redriven,
            "survivor_errors": (res_a["errors"]
                                + (absorb["errors"] if absorb else 0)),
            "survivor_bit_identical": (
                bool(res_a.get("bit_identical"))
                and (absorb is None
                     or bool(absorb.get("bit_identical")))),
            "time_to_absorb_s": round(t_done - t_kill, 3),
        }
    finally:
        survivor.close()
        victim.close()


def run_router_fanin(router_counts=(1, 2), *, n_replicas: int = 1,
                     n_tenants: int = 8, zipf_s: float = 0.0,
                     events_total: int = 2048, chunk: int = 8,
                     max_batch: int = 256, max_wait_ms: float = 40.0,
                     route_window: int = 16, chaos: bool = True,
                     chaos_events: int = 1024,
                     kill_frac: float = 0.4, day_events: int = 256,
                     device_score_min=None,
                     timeout_s: float = 300.0) -> dict:
    """Multi-router fan-in over one replica fleet: the same census
    driven by 1 then N router PROCESSES, aggregate events/s compared
    across counts, plus the router-kill chaos leg.  The single-router
    ceiling being beaten is the ADMISSION ceiling, so the defaults pin
    it deliberately: each router bounds its own per-edge outstanding
    events (route_window), the replica micro-batch wait puts a
    latency floor under the round trip, and Little's law caps one
    router at window/RTT per edge with the host mostly idle — a
    second router process brings its own windows, and the aggregate
    doubles without any router-to-router coordination.  The default
    fleet is a SINGLE replica: this leg isolates the ROUTER plane,
    and with one scorer both routers' events coalesce into the same
    micro-batches, so the extra admission windows turn into larger
    flushes rather than contending scorer threads (replica-plane
    scaling is the replicated bench's measurement).  Replicas are
    host-pinned by default (device_score_min=None) for the same
    reason — on a small host the shared device-dispatch cost would
    otherwise cap both legs at the same compute ceiling.  The
    replica fleet is spawned once and shared across legs (tenant
    re-pushes are version-idempotent)."""
    from oni_ml_tpu.runner.route import _spawn_replica

    workdir = tempfile.mkdtemp(prefix="oni_fanin_")
    kv_dir = os.path.join(workdir, "kv")
    _, _, tenant_mix, _ = _crosshost_census(n_tenants, zipf_s,
                                            day_events)
    procs: dict = {}
    extra = ["--fleet-max-batch", str(max_batch),
             "--fleet-max-wait-ms", str(max_wait_ms)]
    if device_score_min is None:
        extra += ["--device-score-min", "none"]
    try:
        for i in range(n_replicas):
            rid = f"r{i}"
            proc, _, _ = _spawn_replica(rid, kv_dir, workdir, extra,
                                        platform=REPLICA_PLATFORM)
            procs[rid] = proc
        worker_cfg = {
            "kv_dir": kv_dir, "n_tenants": n_tenants,
            "zipf_s": zipf_s, "day_events": day_events,
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "route_window": route_window,
            "device_score_min": device_score_min,
            "expect": sorted(procs), "timeout_s": timeout_s,
        }
        legs: dict = {}
        for n in router_counts:
            legs[str(n)] = _fanin_leg(
                n, worker_cfg, tenant_mix, events_total,
                chunk=chunk, timeout_s=timeout_s)
        eps = {int(k): v["aggregate_eps"] for k, v in legs.items()}
        ns = sorted(eps)
        base = eps.get(ns[0])
        efficiency = {
            str(n): (round(eps[n] / (n / ns[0] * base), 4)
                     if base and eps.get(n) else None)
            for n in ns
        }
        out = {
            "n_replicas": n_replicas,
            "n_tenants": n_tenants,
            "router_counts": ns,
            "fanin": legs,
            "aggregate_eps_by_routers": {str(n): eps[n] for n in ns},
            "router_scaling_efficiency": (
                efficiency.get(str(ns[-1])) if len(ns) > 1 else None),
            "router_scaling_efficiency_by_count": efficiency,
            "fanin_exceeds_single_router": (
                (eps[ns[-1]] or 0) > (eps[ns[0]] or 0)
                if len(ns) > 1 else None),
            "errors": sum(v["errors"] for v in legs.values()),
            "bit_identical": all(v["bit_identical"]
                                 for v in legs.values()),
            "wire_bytes_per_event": (
                legs[str(ns[-1])]["wire_bytes_per_event"]),
        }
        if chaos:
            out["chaos"] = _router_chaos_leg(
                worker_cfg, tenant_mix, chaos_events,
                kill_frac=kill_frac, chunk=chunk,
                timeout_s=timeout_s)
        return out
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=30.0)
            except Exception:
                proc.kill()
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)


def run_autoscale_sweep(steps=((500.0, 2.0), (5000.0, 6.0),
                               (400.0, 6.0)), *,
                        n_tenants: int = 16, zipf_s: float = 1.1,
                        route_window: int = 32, max_batch: int = 256,
                        max_wait_ms: float = 20.0,
                        day_events: int = 256, device_score_min=0,
                        interval_s: float = 0.2,
                        halflife_s: float = 1.0,
                        cooldown_s: float = 2.0,
                        max_replicas: int = 4,
                        sample_every: int = 16, seed: int = 0,
                        timeout_s: float = 300.0) -> dict:
    """Offered load swept through the AutoScaler: open-loop Poisson
    steps (rate, duration) against a fleet that starts at ONE replica
    and is sized by the controller alone.  Per step: sampled p99,
    achieved events/s, and the replica count the controller chose;
    overall: the full decision ledger, the up-reaction time (band
    breach -> replica joined), and wire bytes/event off the router's
    edge counters.  When a window fills, submit blocks — the backlog
    IS the occupancy signal the controller steers on."""
    import queue as queue_mod

    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.serving import (
        AutoScaler,
        FleetRouter,
        ReplicaServer,
        TenantSpec,
    )

    rows, cuts, tenant_mix, models = _crosshost_census(
        n_tenants, zipf_s, day_events)
    tenants = [tm["tenant"] for tm in tenant_mix]
    cfg = ServingConfig(
        fleet_max_batch=max_batch, fleet_max_wait_ms=max_wait_ms,
        route_max_inflight=route_window,
        device_score_min=device_score_min,
        autoscale_interval_s=interval_s,
        autoscale_halflife_s=halflife_s,
        autoscale_cooldown_s=cooldown_s,
        autoscale_max_replicas=max_replicas,
    )
    journal: list = []
    servers: dict = {}
    spawned = [0]

    def _spawn():
        rid = f"as{spawned[0]}"
        spawned[0] += 1
        srv = ReplicaServer(rid, cfg)
        servers[rid] = srv
        return rid, srv.host, srv.port

    def _stop(rid):
        srv = servers.pop(rid, None)
        if srv is not None:
            srv.stop()

    router = FleetRouter(cfg, journal=journal)
    rid0, host0, port0 = _spawn()
    router.connect_replica(rid0, host0, port0)
    for i, tm in enumerate(tenant_mix):
        router.add_tenant(
            TenantSpec(tenant=tm["tenant"], dsource="dns",
                       weight=tm["weight"]),
            cuts, models[i],
        )
    router.start(warmup=True)
    scaler = AutoScaler(router, spawn=_spawn, stop=_stop,
                        config=cfg, journal=journal)
    scaler.start()
    try:
        step_out = []
        for si, (rate, dur) in enumerate(steps):
            n = int(rate * dur)
            offs = arrival_offsets("poisson", n, rate,
                                   seed=seed + si)
            lat: list = []
            errs = [0]
            q: "queue_mod.Queue" = queue_mod.Queue()

            def collect(q=q, lat=lat, errs=errs):
                while True:
                    item = q.get()
                    if item is None:
                        return
                    fut, t_sub = item
                    try:
                        fut.result(timeout=timeout_s)
                        lat.append(
                            (time.perf_counter() - t_sub) * 1e3)
                    except Exception:
                        errs[0] += 1

            col = threading.Thread(target=collect, daemon=True,
                                   name=f"loadgen-as-{si}")
            col.start()
            futs = []
            t0 = time.perf_counter()
            for j in range(n):
                target = t0 + float(offs[j])
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                t_sub = time.perf_counter()
                fut = router.submit(tenants[j % len(tenants)],
                                    rows[j % len(rows)])
                futs.append(fut)
                if j % sample_every == 0:
                    q.put((fut, t_sub))
            router.flush()
            q.put(None)
            col.join(timeout=timeout_s + 60.0)
            step_errors = errs[0]
            # Drain the step entirely (every future, not just the
            # samples): "zero failed futures" is a gate, and the
            # inter-step drain is what lets a scale-down show up in
            # the NEXT low step instead of mid-backlog.
            for f in futs:
                try:
                    f.result(timeout=timeout_s)
                except Exception:
                    step_errors += 1
            wall = time.perf_counter() - t0
            arr = np.sort(np.asarray(lat)) if lat else None
            step_out.append({
                "offered_eps": rate,
                "duration_s": dur,
                "events": n,
                "achieved_eps": round(n / wall, 1) if wall else None,
                "p50_ms": (round(float(
                    arr[int(0.50 * (len(arr) - 1))]), 3)
                    if arr is not None else None),
                "p99_ms": (round(float(
                    arr[int(0.99 * (len(arr) - 1))]), 3)
                    if arr is not None else None),
                "errors": step_errors,
                "replicas_after": len(router.stats()["replicas"]),
            })
        decisions = list(scaler.decisions)
        actions = [d for d in decisions
                   if d["action"] in ("up", "down")]
        ups = [d for d in actions if d["action"] == "up"]
        edges = router.stats()["edges"]
        tb = sum(e["bytes"] for e in edges.values())
        te = sum(e["events"] for e in edges.values())
        return {
            "steps": step_out,
            "replica_counts": [s["replicas_after"]
                               for s in step_out],
            "max_replicas_reached": max(
                (s["replicas_after"] for s in step_out), default=1),
            "ledger": decisions,
            "actions": actions,
            "scaled_up": len(ups),
            "scaled_down": sum(1 for d in actions
                               if d["action"] == "down"),
            "scale_up_reaction_s": (
                round(min(d.get("reaction_s", 0.0) for d in ups), 3)
                if ups else None),
            "wire_bytes_per_event": (round(tb / te, 1)
                                     if te else None),
            "errors": sum(s["errors"] for s in step_out),
        }
    finally:
        scaler.close()
        router.close()
        for srv in list(servers.values()):
            srv.stop()


def run_crosshost_slo(router_counts=(1, 2), *, n_replicas: int = 1,
                      n_tenants: int = 8, zipf_s: float = 1.1,
                      events_total: int = 2048, chunk: int = 8,
                      max_batch: int = 256, max_wait_ms: float = 40.0,
                      route_window: int = 16, chaos: bool = True,
                      chaos_events: int = 1024,
                      autoscale_steps=((500.0, 2.0), (5000.0, 6.0),
                                       (400.0, 6.0)),
                      day_events: int = 256, device_score_min=None,
                      seed: int = 0,
                      timeout_s: float = 300.0) -> dict:
    """The serving_crosshost measurement: router fan-in + router-kill
    chaos (run_router_fanin) and the Little's-law autoscale sweep
    (run_autoscale_sweep), with the bench_diff headline keys hoisted
    to the top level.  The fan-in knobs here feed the fan-in leg
    only; the autoscale sweep keeps its own control-law-tuned
    defaults (tighter wait, wider window, device scoring on) because
    it measures the REPLICA plane, not the admission plane."""
    fanin = run_router_fanin(
        router_counts, n_replicas=n_replicas, n_tenants=n_tenants,
        events_total=events_total, chunk=chunk,
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        route_window=route_window, chaos=chaos,
        chaos_events=chaos_events, day_events=day_events,
        device_score_min=device_score_min, timeout_s=timeout_s)
    autoscale = run_autoscale_sweep(
        autoscale_steps, n_tenants=max(8, n_tenants),
        zipf_s=zipf_s, max_batch=max_batch,
        day_events=day_events, seed=seed,
        timeout_s=timeout_s)
    eps_by = fanin["aggregate_eps_by_routers"]
    errors = fanin["errors"] + autoscale["errors"]
    chaos_out = fanin.get("chaos")
    if chaos_out:
        errors += chaos_out["survivor_errors"]
    return {
        "fanin": fanin,
        "autoscale": autoscale,
        "sustained_eps": max(
            (v for v in eps_by.values() if v), default=None),
        "router_scaling_efficiency": (
            fanin["router_scaling_efficiency"]),
        "fanin_exceeds_single_router": (
            fanin["fanin_exceeds_single_router"]),
        "wire_bytes_per_event": (
            fanin["wire_bytes_per_event"]
            or autoscale["wire_bytes_per_event"]),
        "scale_up_reaction_s": autoscale["scale_up_reaction_s"],
        "max_replicas_reached": autoscale["max_replicas_reached"],
        "errors": errors,
    }


def _stack(n_events: int, *, max_batch: int, max_wait_ms: float,
           device_score_min):
    """Synthetic day + the real serving stack over it (the dry-run
    day generator of runner/serve.py at load-test size; the day is
    deterministic — `--seed` varies the arrival schedule only)."""
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.serving import (
        BatchScorer,
        DnsEventFeaturizer,
        ModelRegistry,
    )

    rows, model, cuts = _synthetic_day(
        n_events=n_events, n_clients=64, n_doms=16
    )
    registry = ModelRegistry()
    registry.publish(model, source="load-gen-synthetic")
    cfg = ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        device_score_min=device_score_min,
    )
    scorer = BatchScorer(registry, DnsEventFeaturizer(cuts), cfg)
    return rows, scorer


def run_slo(patterns=PATTERNS, *, n_events: int = 4096,
            rate_eps: float = 4000.0, burst_len: int = 64,
            max_batch: int = 256, max_wait_ms: float = 10.0,
            device_score_min=0, seed: int = 0, recorder=None) -> dict:
    """The serving_slo measurement: one fresh BatchScorer per arrival
    pattern (a clean queue — pattern B must not inherit pattern A's
    backlog), same synthetic day, same offered rate."""
    out: dict = {
        "n_events": n_events,
        "offered_eps": rate_eps,
        "burst_len": burst_len,
        "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
    }
    for pattern in patterns:
        rows, scorer = _stack(
            n_events, max_batch=max_batch, max_wait_ms=max_wait_ms,
            device_score_min=device_score_min,
        )
        offsets = arrival_offsets(pattern, len(rows), rate_eps,
                                  seed=seed, burst_len=burst_len)
        try:
            out[pattern] = run_load(scorer, rows, offsets,
                                    pattern=pattern, recorder=recorder)
        finally:
            scorer.close()
    return out


def emit_lines(pattern: str, n_events: int, rate_eps: float, *,
               burst_len: int = 64, seed: int = 0, out=sys.stdout,
               tenants: int = 0,
               tenant_ids: "list[str] | None" = None,
               dsource: str = "dns") -> int:
    """Stream mode: pace raw CSV lines to `out` under the pattern —
    feedstock for a real `ml_ops serve` behind a pipe.  With
    `tenants=N` (or an explicit `tenant_ids` list — required to match
    a real manifest's ids, since the synthetic default is ``t<i>``),
    lines round-robin across the tenant ids in the fleet stream
    framing (``<tenant>\\t<line>``) for piping into
    `ml_ops serve --fleet`.

    Any registered source emits: ``dns`` keeps the serve harness's
    `_synthetic_day` rows (the models a synthetic fleet publishes are
    built over that exact day), every other source draws its
    registry `synth_benign` day — in particular ``--dsource proxy``
    produces correctly framed proxy events that a proxy-lane fleet
    admits (one raw CSV line per event, no header line, tab-framed
    tenant prefix)."""
    ids = tenant_ids or (
        [f"t{i}" for i in range(tenants)] if tenants else []
    )
    if dsource == "dns":
        from oni_ml_tpu.runner.serve import _synthetic_day

        rows, _, _ = _synthetic_day(n_events=n_events, n_clients=64,
                                    n_doms=16)
        lines = [",".join(row) for row in rows]
    else:
        from oni_ml_tpu.sources import get as get_source

        lines = [ln.rstrip("\n") for ln in
                 get_source(dsource).synth_benign(n_events, seed)]
    offsets = arrival_offsets(pattern, len(lines), rate_eps, seed=seed,
                              burst_len=burst_len)
    t0 = time.perf_counter()
    for i, line in enumerate(lines):
        target = t0 + offsets[i]
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        prefix = f"{ids[i % len(ids)]}\t" if ids else ""
        out.write(prefix + line + "\n")
        out.flush()
    return len(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Poisson/bursty load generator for the serving SLO "
        "bench (in-process harness or paced stdout stream)."
    )
    ap.add_argument("--pattern", choices=PATTERNS + ("both",),
                    default="both")
    ap.add_argument("--events", type=int, default=4096)
    ap.add_argument("--rate", type=float, default=4000.0,
                    metavar="EVENTS_PER_SEC")
    ap.add_argument("--burst-len", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--host-only", action="store_true",
                    help="pin the host scorer (skip the device "
                    "dispatch calibration)")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="fleet mode: drive N tenants with mixed "
                    "arrivals through one FleetScorer and report "
                    "per-tenant SLO summaries alongside the aggregate "
                    "(0 = single-model mode)")
    ap.add_argument("--mix", default="poisson:1,bursty:1",
                    metavar="PAT:W,...",
                    help="fleet arrival mix: weighted patterns cycled "
                    "across tenants; weights split the offered rate "
                    "(default poisson:1,bursty:1)")
    ap.add_argument("--zipf", type=float, default=0.0, metavar="S",
                    help="fleet mode: Zipf-distributed tenant weights "
                    "1/(i+1)^S replacing the cycled mix weights — the "
                    "head dominates the load, the tail trickles "
                    "(0 = off)")
    ap.add_argument("--hot-tenants", type=int, default=0, metavar="N",
                    help="fleet mode: tiered residency with at most N "
                    "HBM-hot tenants (serving/residency.py); events "
                    "split by Zipf weight and per-tenant latency "
                    "includes promotion misses (0 = legacy all-hot)")
    ap.add_argument("--warm-tenants", type=int, default=0, metavar="N",
                    help="host-warm capacity beyond hot; coldest "
                    "tenants spill to checkpoint-cold npz (0 = "
                    "unbounded)")
    ap.add_argument("--residency-policy", choices=["lru", "lfu"],
                    default="lru",
                    help="eviction victim selection for --hot-tenants")
    ap.add_argument("--admission", choices=["block", "reject"],
                    default="",
                    help="fleet admission policy override: \"reject\" "
                    "sheds on full tenant queues (shed counts land in "
                    "the payload) instead of backpressuring the "
                    "replay (default: fleet config)")
    ap.add_argument("--tenant-ids", default="", metavar="ID,ID,...",
                    help="with --emit-lines: explicit tenant ids for "
                    "the fleet framing, matching a real manifest "
                    "(default: synthetic t0..tN-1 from --tenants)")
    ap.add_argument("--replicated", default="", metavar="N,N,...",
                    help="replicated-fleet mode: measure aggregate "
                    "sustained events/s at each replica count (real "
                    "`ml_ops replica` subprocesses behind the async "
                    "router) plus the kill-a-replica chaos leg "
                    "(serving_slo_replicated harness)")
    ap.add_argument("--route-window", type=int, default=64,
                    metavar="N",
                    help="replicated mode: bounded per-replica "
                    "admission window (route_max_inflight)")
    ap.add_argument("--routers", default="", metavar="N,N,...",
                    help="multi-router fan-in mode: the same census "
                    "driven by each router-process count against one "
                    "shared replica fleet (zero router coordination), "
                    "plus the router-kill chaos leg — aggregate "
                    "events/s by count (run_router_fanin)")
    ap.add_argument("--router-worker", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dsource", default="dns",
                    help="with --emit-lines: which registered source's "
                    "synthetic day to emit (dns keeps the serve "
                    "harness day; flow/proxy draw the registry "
                    "synth_benign day)")
    ap.add_argument("--emit-lines", action="store_true",
                    help="pace raw CSV lines to stdout instead of "
                    "running the in-process harness (pipe into "
                    "`ml_ops serve`); requires a single --pattern")
    args = ap.parse_args(argv)
    if args.router_worker:
        # Subprocess half of run_router_fanin: stdout is the JSON
        # command protocol, nothing else may print there.
        return _router_worker_main(args.router_worker)
    if args.routers:
        counts = tuple(
            int(c) for c in args.routers.split(",") if c.strip()
        )
        # The fan-in leg's admission-plane defaults (window 16, wait
        # 40ms, host-pinned single replica) are tuned; only forward a
        # knob the user actually moved off the generic CLI default.
        kw: dict = {}
        if args.route_window != 64:
            kw["route_window"] = args.route_window
        if args.max_wait_ms != 10.0:
            kw["max_wait_ms"] = args.max_wait_ms
        res = run_router_fanin(
            counts, n_tenants=args.tenants or 8,
            zipf_s=args.zipf, max_batch=args.max_batch, **kw,
        )
        print(json.dumps(res), flush=True)
        return 0
    if args.emit_lines:
        if args.pattern == "both":
            print("load_gen: --emit-lines needs a single --pattern",
                  file=sys.stderr)
            return 2
        ids = [t.strip() for t in args.tenant_ids.split(",")
               if t.strip()] or None
        n = emit_lines(args.pattern, args.events, args.rate,
                       burst_len=args.burst_len, seed=args.seed,
                       tenants=args.tenants, tenant_ids=ids,
                       dsource=args.dsource)
        print(f"load_gen: emitted {n} events", file=sys.stderr)
        return 0
    if args.replicated:
        counts = tuple(
            int(c) for c in args.replicated.split(",") if c.strip()
        )
        res = run_replicated_slo(
            counts, n_tenants=args.tenants or 256,
            zipf_s=args.zipf or 1.1, route_window=args.route_window,
            max_wait_ms=args.max_wait_ms, max_batch=args.max_batch,
            seed=args.seed,
            device_score_min=None if args.host_only else 0,
        )
        print(json.dumps(res), flush=True)
        return 0
    if args.tenants:
        res = run_fleet_slo(
            args.tenants, args.mix, n_events=args.events,
            rate_eps=args.rate, burst_len=args.burst_len,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            device_score_min=None if args.host_only else 0,
            seed=args.seed, zipf_s=args.zipf,
            hot_tenants=args.hot_tenants,
            warm_tenants=args.warm_tenants,
            residency_policy=args.residency_policy,
            admission=args.admission,
        )
        print(json.dumps(res), flush=True)
        return 0
    patterns = PATTERNS if args.pattern == "both" else (args.pattern,)
    res = run_slo(
        patterns, n_events=args.events, rate_eps=args.rate,
        burst_len=args.burst_len, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        device_score_min=None if args.host_only else 0,
        seed=args.seed,
    )
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
