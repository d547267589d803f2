"""Compare two bench payloads and fail loudly on regression.

The post-bench CI step:
feed it the previous round's captured payload (BENCH_rNN.json — the
driver wrapper with a "parsed" object — or a raw `python bench.py`
headline line) and the fresh one, and it diffs every comparable number:

  * the headline metric (direction inferred from the unit: rates are
    higher-better, seconds lower-better),
  * every secondary phase's `value` present in BOTH payloads,
  * roofline utilization (headline `utilization.mxu_pct` / `hbm_pct`,
    absolute percentage points),
  * the streaming dataplane's `overlap_efficiency` from the
    pipeline_e2e phases (absolute drop — the number is a fraction of
    hidden work, so relative deltas near 0 are noise).

Exit codes: 0 = no regression beyond thresholds, 1 = regression
(printed per row), 2 = unusable input.  `--json` emits the full row
set for dashboards.

Usage:

    python tools/bench_diff.py old_record.json new_record.json \
        [--threshold-pct 10] [--efficiency-drop 0.05] [--json]
"""

from __future__ import annotations

import argparse
import json
import sys

# Phases whose payloads carry an overlap_efficiency headline.
_OVERLAP_PHASES = ("pipeline_e2e", "pipeline_e2e_dns")

# Serving SLO phases: their payloads carry nested latency quantiles and
# sustained rates whose regression DIRECTIONS differ per key —
# sustained_eps is higher-better (events/sec), the pNN_ms quantiles are
# lower-better (ms) — so each key compares under its own unit instead
# of riding the phase's single headline value.  serving_slo nests per
# arrival pattern; serving_slo_fleet nests an aggregate plus one
# summary per tenant; serving_slo_fleet_paged additionally carries the
# tiered-residency ledger (its aggregate p99 INCLUDES promotion
# misses, so a paging regression gates through the same keys).
_SERVING_PHASES = ("serving_slo", "serving_slo_fleet",
                   "serving_slo_fleet_paged")
_SERVING_KEYS = (
    ("sustained_eps", "events/sec"),     # higher-better
    ("p50_ms", "ms"),                    # lower-better
    ("p99_ms", "ms"),
    ("p999_ms", "ms"),
)

# Tiered-residency keys (serving_slo_fleet_paged "residency" section):
# the total priced promotion stall is dead time on paging tenants'
# latency paths (lower-better).  Promotion/eviction COUNTS are
# reported but not gated — they change with the Zipf draw and
# capacity config, not with performance.
_RESIDENCY_KEYS = (
    ("promotion_stall_s", "s"),          # lower-better
)

# Continuous-ingestion freshness phase: direction per key — freshness
# latencies (wall seconds AND speed-invariant event-time minutes) are
# lower-better, the warm-start speedup (fresh wall / warm wall at
# matched held-out likelihood) is higher-better.  The held-out
# likelihood itself gates on ABSOLUTE drop (nats — a relative delta on
# a negative log-likelihood is meaningless), like overlap_efficiency.
_STREAMING_PHASE = "streaming_freshness"
_STREAMING_KEYS = (
    ("freshness_p50_s", "s"),              # lower-better
    ("freshness_p99_s", "s"),
    ("freshness_event_p50_min", "min"),    # minutes; latency direction
    ("freshness_event_p99_min", "min"),
    ("warm_start_speedup", "x"),           # higher-better
)


def _streaming_rows(name: str, old: dict, new: dict,
                    threshold_pct: float, ll_drop: float) -> "list[dict]":
    rows = []
    for key, unit in _STREAMING_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    r = _abs_row(f"{name}.held_out_ll", old.get("held_out_ll"),
                 new.get("held_out_ll"), "nats", ll_drop)
    if r:
        rows.append(r)
    return rows


# Composed standing-service phase (continuous x fleet x cosched):
# direction per key — the fleet freshness latencies (wall seconds and
# speed-invariant event-time minutes) and the serve-latency tails are
# lower-better; `p99_during_refresh_ms` is the co-scheduler's
# acceptance number (the serve tail WHILE a refresh fit holds the
# process), and the yield/preempt waits are the arbitration's own
# priced cost.  `sustained_eps` is the replayed multi-tenant drain
# rate (higher-better).  The chaos bits (failed_futures == 0,
# failovers >= 1, zero retraces) are asserted by the test suite and
# reported in the payload, not trended here — they are correctness
# bits, not performance trends.
_CONTINUOUS_REPLICATED_PHASE = "continuous_replicated"
_CONTINUOUS_REPLICATED_KEYS = (
    ("freshness_p50_s", "s"),                    # lower-better
    ("freshness_p99_s", "s"),
    ("freshness_event_p50_min", "min"),          # minutes; latency
    ("freshness_event_p99_min", "min"),
    ("p99_idle_ms", "ms"),                       # lower-better
    ("p99_during_refresh_ms", "ms"),             # the cosched claim
    ("yield_wait_p99_ms", "ms"),
    ("preempt_wait_p99_ms", "ms"),
    ("sustained_eps", "events/sec"),             # higher-better
)


def _continuous_replicated_rows(name: str, old: dict, new: dict,
                                threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _CONTINUOUS_REPLICATED_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    return rows


# Detection-quality phase: every key is HIGHER-better —
# precision/recall@k are fractions of attacks ranked inside the top-k,
# score_separation is the median benign-vs-attack log-score gap in
# nats.  A recall drop gates exit 1 exactly like a p99 blowup; the
# per-source sections gate too, so one source regressing cannot hide
# behind the cross-source mean.
_QUALITY_PHASE = "detection_quality"
_QUALITY_KEYS = (
    ("recall_at_k", "fraction"),         # higher-better
    ("precision_at_k", "fraction"),      # higher-better
    ("score_separation", "nats"),        # higher-better
)


def _quality_rows(name: str, old: dict, new: dict,
                  threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _QUALITY_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    old_src = old.get("sources") or {}
    new_src = new.get("sources") or {}
    for src in sorted(set(old_src) & set(new_src)):
        o, n = old_src[src], new_src[src]
        if not isinstance(o, dict) or not isinstance(n, dict):
            continue
        for key, unit in _QUALITY_KEYS:
            r = _rel_row(f"{name}:{src}.{key}", o.get(key), n.get(key),
                         unit, threshold_pct)
            if r:
                rows.append(r)
    return rows


# Device-featurization phase: every gated key is events/sec
# (higher-better) — the per-micro-batch host/device/fused rates are
# nested {batch_size: eps} dicts, the fleet drain rates are scalars.
# The speedup ratios are derived (reported, not separately gated: a
# device-rate regression already gates through its own key).
_FEATURIZE_PHASE = "featurize_device"
_FEATURIZE_TIER_KEYS = (
    ("host_eps", "events/sec"),          # higher-better
    ("device_eps", "events/sec"),        # higher-better
    ("fused_eps", "events/sec"),         # higher-better
)
_FEATURIZE_KEYS = (
    ("fleet_host_eps", "events/sec"),    # higher-better
    ("fleet_device_eps", "events/sec"),  # higher-better
)


def _featurize_rows(name: str, old: dict, new: dict,
                    threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _FEATURIZE_TIER_KEYS:
        o, n = old.get(key) or {}, new.get(key) or {}
        if not isinstance(o, dict) or not isinstance(n, dict):
            continue
        for batch in sorted(set(o) & set(n), key=str):
            r = _rel_row(f"{name}.{key}@{batch}", o[batch], n[batch],
                         unit, threshold_pct)
            if r:
                rows.append(r)
    for key, unit in _FEATURIZE_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    return rows


# Replicated elastic serving phase: direction per key — aggregate
# sustained events/s per replica count and the scaling efficiency are
# higher-better; the chaos phase's p999-during-failover and
# time-to-full-recovery are lower-better (a slower promotion is a
# regression exactly like a latency blowup).  Error/retrace counts are
# asserted by the test suite, not gated here (they are correctness
# bits, not performance trends).
_REPLICATED_PHASE = "serving_slo_replicated"
_REPLICATED_KEYS = (
    ("replica_scaling_efficiency", "fraction"),  # higher-better
    ("failover_p999_ms", "ms"),                  # lower-better
    ("time_to_recovery_s", "s"),                 # lower-better
)


def _replicated_rows(name: str, old: dict, new: dict,
                     threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _REPLICATED_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    old_eps = old.get("sustained_eps_by_count") or {}
    new_eps = new.get("sustained_eps_by_count") or {}
    for count in sorted(set(old_eps) & set(new_eps), key=int):
        r = _rel_row(f"{name}.sustained_eps[{count}]",
                     old_eps.get(count), new_eps.get(count),
                     "events/sec", threshold_pct)
        if r:
            rows.append(r)
    return rows


# Distributed-EM scaling phase: direction per key — scaling efficiency
# is a fraction of ideal speedup (higher-better), the per-iteration
# allreduce wall is dead time on the EM critical path (lower-better).
# Bytes per iteration are reported but not gated: they change with the
# payload schema, not with performance.
_DISTRIBUTED_PHASE = "distributed_em"
_DISTRIBUTED_KEYS = (
    ("scaling_efficiency", "fraction"),  # higher-better
    ("allreduce_wall_s_per_iter", "s"),  # lower-better
)


def _distributed_rows(name: str, old: dict, new: dict,
                      threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _DISTRIBUTED_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    return rows


# Cross-host serving phase: direction per key — aggregate sustained
# events/s (at the winning router count) and the router scaling
# efficiency are higher-better; the columnar wire's bytes-per-event is
# overhead on every frame (lower-better — a fatter encoding IS the
# regression the zero-copy wire exists to prevent); the autoscaler's
# scale-up reaction is dead time between the band breach and the
# joined replica (lower-better).  The fanin_exceeds_single_router /
# bit_identical / zero-error bits are asserted by the test suite and
# the bench gate, not trended here.
_CROSSHOST_PHASE = "serving_crosshost"
_CROSSHOST_KEYS = (
    ("sustained_eps", "events/sec"),           # higher-better
    ("router_scaling_efficiency", "fraction"),  # higher-better
    ("wire_bytes_per_event", "bytes/event"),   # lower-better
    ("scale_up_reaction_s", "s"),              # lower-better
)


def _crosshost_rows(name: str, old: dict, new: dict,
                    threshold_pct: float) -> "list[dict]":
    rows = []
    for key, unit in _CROSSHOST_KEYS:
        r = _rel_row(f"{name}.{key}", old.get(key), new.get(key), unit,
                     threshold_pct)
        if r:
            rows.append(r)
    old_eps = (old.get("fanin") or {}).get(
        "aggregate_eps_by_routers") or {}
    new_eps = (new.get("fanin") or {}).get(
        "aggregate_eps_by_routers") or {}
    for count in sorted(set(old_eps) & set(new_eps), key=int):
        r = _rel_row(f"{name}.aggregate_eps[{count}r]",
                     old_eps.get(count), new_eps.get(count),
                     "events/sec", threshold_pct)
        if r:
            rows.append(r)
    return rows


def _serving_groups(payload: dict) -> "dict[str, dict]":
    """label -> latency-summary dict for every comparable group in a
    serving SLO payload: arrival patterns (serving_slo), the fleet
    aggregate, and each tenant (serving_slo_fleet)."""
    groups: dict = {}
    for pattern in ("poisson", "bursty"):
        g = payload.get(pattern)
        if isinstance(g, dict):
            groups[pattern] = g
    agg = payload.get("aggregate")
    if isinstance(agg, dict):
        groups["aggregate"] = agg
    tenants = payload.get("tenants")
    if isinstance(tenants, dict):
        for tid in sorted(tenants):
            if isinstance(tenants[tid], dict):
                groups[f"tenant.{tid}"] = tenants[tid]
    return groups


def _serving_rows(name: str, old: dict, new: dict,
                  threshold_pct: float) -> "list[dict]":
    """Per-group, per-key comparison rows for one serving SLO phase
    present in both payloads: a p99/p999 blowup gates exit 1 exactly
    like a throughput drop, each under its own direction.  A paged
    payload's residency ledger contributes its own direction-aware
    keys (promotion stall lower-better)."""
    rows = []
    old_groups = _serving_groups(old)
    new_groups = _serving_groups(new)
    for label in sorted(set(old_groups) & set(new_groups)):
        for key, unit in _SERVING_KEYS:
            r = _rel_row(
                f"{name}:{label}.{key}",
                old_groups[label].get(key), new_groups[label].get(key),
                unit, threshold_pct,
            )
            if r:
                rows.append(r)
    old_res, new_res = old.get("residency"), new.get("residency")
    if isinstance(old_res, dict) and isinstance(new_res, dict):
        for key, unit in _RESIDENCY_KEYS:
            r = _rel_row(f"{name}:residency.{key}", old_res.get(key),
                         new_res.get(key), unit, threshold_pct)
            if r:
                rows.append(r)
    return rows


def load_payload(path: str) -> dict:
    """A bench payload from either container: the driver's capture
    wrapper ({"parsed": {...}}), a raw headline object, or a
    failure payload (whose comparable numbers live in "last_good")."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    if isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    if data.get("value") is None and isinstance(
        data.get("last_good"), dict
    ):
        data = data["last_good"]
    return data


def _higher_is_better(unit: str) -> bool:
    u = (unit or "").lower()
    if u in ("bytes", "bytes/event"):   # wire overhead: lower-better
        return False
    if "/" in u:          # docs/sec, events/sec, ...
        return True
    return u not in ("seconds", "second", "s", "ms", "milliseconds",
                     "min", "minutes")


def _rel_row(name: str, old, new, unit: str, threshold_pct: float):
    """One relative-delta comparison row; regression when the metric
    moved the WRONG direction by more than threshold_pct."""
    if not isinstance(old, (int, float)) or not isinstance(
        new, (int, float)
    ) or old == 0:
        return None
    delta_pct = 100.0 * (new - old) / abs(old)
    worse = -delta_pct if _higher_is_better(unit) else delta_pct
    return {
        "name": name, "old": old, "new": new, "unit": unit,
        "delta_pct": round(delta_pct, 2),
        "regression": worse > threshold_pct,
    }


def _abs_row(name: str, old, new, unit: str, max_drop: float):
    """Absolute-drop comparison (utilization points, overlap
    efficiency): regression when new < old - max_drop."""
    if not isinstance(old, (int, float)) or not isinstance(
        new, (int, float)
    ):
        return None
    return {
        "name": name, "old": old, "new": new, "unit": unit,
        "delta_abs": round(new - old, 4),
        "regression": (old - new) > max_drop,
    }


def diff_payloads(old: dict, new: dict, threshold_pct: float = 10.0,
                  efficiency_drop: float = 0.05,
                  util_drop_pct: float = 2.0,
                  ll_drop: float = 0.25) -> "list[dict]":
    rows = []
    # Headline.
    r = _rel_row(
        f"headline:{new.get('metric', old.get('metric', '?'))}",
        old.get("value"), new.get("value"), new.get("unit", ""),
        threshold_pct,
    )
    if r:
        rows.append(r)
    # Secondary phases present in both.
    old_sec = old.get("secondary") or {}
    new_sec = new.get("secondary") or {}
    for name in sorted(set(old_sec) & set(new_sec)):
        o, n = old_sec[name], new_sec[name]
        if not isinstance(o, dict) or not isinstance(n, dict):
            continue
        r = _rel_row(f"phase:{name}", o.get("value"), n.get("value"),
                     n.get("unit", o.get("unit", "")), threshold_pct)
        if r:
            rows.append(r)
    # Roofline utilization on the headline (absolute points — 10.5% MXU
    # dropping to 8% is a real kernel regression even though the
    # relative delta reads -24%).
    old_util = old.get("utilization") or {}
    new_util = new.get("utilization") or {}
    for key in ("mxu_pct", "hbm_pct"):
        r = _abs_row(f"utilization:{key}", old_util.get(key),
                     new_util.get(key), "pct", util_drop_pct)
        if r:
            rows.append(r)
    # Serving SLO latency/throughput keys (direction per key: rates
    # higher-better, millisecond quantiles lower-better) — from the
    # secondary phase payloads, and from the headline payload itself
    # when the compared run IS a serving phase capture.
    for name in _SERVING_PHASES:
        o, n = old_sec.get(name), new_sec.get(name)
        if isinstance(o, dict) and isinstance(n, dict):
            rows.extend(_serving_rows(f"phase:{name}", o, n,
                                      threshold_pct))
    if _serving_groups(old) and _serving_groups(new):
        rows.extend(_serving_rows("headline", old, new, threshold_pct))
    # Replicated-serving keys (per-count sustained eps + scaling
    # efficiency higher-better, failover p999 / recovery lower-better)
    # — phase payloads and replicated-headline captures.
    o, n = old_sec.get(_REPLICATED_PHASE), new_sec.get(_REPLICATED_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_replicated_rows(f"phase:{_REPLICATED_PHASE}", o, n,
                                     threshold_pct))
    if ("replica_scaling_efficiency" in old
            and "replica_scaling_efficiency" in new):
        rows.extend(_replicated_rows("headline", old, new,
                                     threshold_pct))
    # Cross-host serving keys (fan-in eps + scaling efficiency
    # higher-better; wire bytes/event + autoscale reaction
    # lower-better) — phase payloads and crosshost-headline captures.
    o, n = old_sec.get(_CROSSHOST_PHASE), new_sec.get(_CROSSHOST_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_crosshost_rows(f"phase:{_CROSSHOST_PHASE}", o, n,
                                    threshold_pct))
    if ("router_scaling_efficiency" in old
            and "router_scaling_efficiency" in new):
        rows.extend(_crosshost_rows("headline", old, new,
                                    threshold_pct))
    # Device-featurization keys (events/s per engine per micro-batch
    # tier + fleet drain rates, all higher-better) — phase payloads
    # and featurize-headline captures.
    o, n = old_sec.get(_FEATURIZE_PHASE), new_sec.get(_FEATURIZE_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_featurize_rows(f"phase:{_FEATURIZE_PHASE}", o, n,
                                    threshold_pct))
    if "fleet_device_eps" in old and "fleet_device_eps" in new:
        rows.extend(_featurize_rows("headline", old, new,
                                    threshold_pct))
    # Distributed-EM scaling keys (efficiency higher-better, allreduce
    # wall lower-better) — from the secondary phase payloads, and from
    # the headline payload when the compared run IS a distributed_em
    # capture.
    o, n = old_sec.get(_DISTRIBUTED_PHASE), new_sec.get(_DISTRIBUTED_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_distributed_rows(f"phase:{_DISTRIBUTED_PHASE}", o, n,
                                      threshold_pct))
    if "scaling_efficiency" in old and "scaling_efficiency" in new:
        rows.extend(_distributed_rows("headline", old, new, threshold_pct))
    # Streaming-freshness keys (freshness latencies lower-better,
    # warm-start speedup higher-better, held-out LL absolute-drop
    # gated) — phase payloads and freshness-headline captures.
    o, n = old_sec.get(_STREAMING_PHASE), new_sec.get(_STREAMING_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_streaming_rows(f"phase:{_STREAMING_PHASE}", o, n,
                                    threshold_pct, ll_drop))
    if ("freshness_p50_s" in old and "freshness_p50_s" in new
            and "p99_during_refresh_ms" not in new):
        # A composed continuous_replicated capture also carries
        # freshness keys; its own branch below owns them there.
        rows.extend(_streaming_rows("headline", old, new,
                                    threshold_pct, ll_drop))
    # Composed standing-service keys (freshness + serve-during-refresh
    # tails + yield/preempt waits lower-better, sustained eps
    # higher-better) — phase payloads and composed-headline captures
    # (sentinel: p99_during_refresh_ms, unique to this phase).
    o, n = (old_sec.get(_CONTINUOUS_REPLICATED_PHASE),
            new_sec.get(_CONTINUOUS_REPLICATED_PHASE))
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_continuous_replicated_rows(
            f"phase:{_CONTINUOUS_REPLICATED_PHASE}", o, n,
            threshold_pct))
    if ("p99_during_refresh_ms" in old
            and "p99_during_refresh_ms" in new):
        rows.extend(_continuous_replicated_rows(
            "headline", old, new, threshold_pct))
    # Detection-quality keys (all higher-better: recall/precision@k,
    # score separation; per-source sections too) — phase payloads and
    # quality-headline captures.
    o, n = old_sec.get(_QUALITY_PHASE), new_sec.get(_QUALITY_PHASE)
    if isinstance(o, dict) and isinstance(n, dict):
        rows.extend(_quality_rows(f"phase:{_QUALITY_PHASE}", o, n,
                                  threshold_pct))
    if "recall_at_k" in old and "recall_at_k" in new:
        rows.extend(_quality_rows("headline", old, new, threshold_pct))
    # Streaming-dataplane overlap efficiency (absolute fraction).
    for name in _OVERLAP_PHASES:
        o, n = old_sec.get(name), new_sec.get(name)
        if not isinstance(o, dict) or not isinstance(n, dict):
            continue
        r = _abs_row(f"overlap_efficiency:{name}",
                     o.get("overlap_efficiency"),
                     n.get("overlap_efficiency"), "fraction",
                     efficiency_drop)
        if r:
            rows.append(r)
    return rows


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(
        description="Diff two bench payloads; exit 1 on regression "
        "beyond thresholds."
    )
    ap.add_argument("old", help="baseline payload (BENCH_rNN.json or "
                    "raw bench.py output)")
    ap.add_argument("new", help="candidate payload")
    ap.add_argument("--threshold-pct", type=float, default=10.0,
                    help="relative regression tolerance for headline / "
                    "phase values (default 10%%)")
    ap.add_argument("--efficiency-drop", type=float, default=0.05,
                    help="max tolerated absolute drop in "
                    "overlap_efficiency (default 0.05)")
    ap.add_argument("--util-drop-pct", type=float, default=2.0,
                    help="max tolerated absolute drop in utilization "
                    "percentage points (default 2.0)")
    ap.add_argument("--ll-drop", type=float, default=0.25,
                    help="max tolerated absolute drop in the streaming "
                    "phase's held-out per-token log-likelihood, in "
                    "nats (default 0.25)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the comparison rows as JSON")
    args = ap.parse_args(argv)
    try:
        old = load_payload(args.old)
        new = load_payload(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    rows = diff_payloads(old, new, args.threshold_pct,
                         args.efficiency_drop, args.util_drop_pct,
                         args.ll_drop)
    if not rows:
        print("bench_diff: no comparable metrics between the two "
              "payloads", file=sys.stderr)
        return 2
    regressions = [r for r in rows if r["regression"]]
    if args.as_json:
        print(json.dumps({"rows": rows,
                          "regressions": len(regressions)}, indent=2))
    else:
        for r in rows:
            delta = (f"{r['delta_pct']:+.2f}%" if "delta_pct" in r
                     else f"{r['delta_abs']:+.4f}")
            flag = "  REGRESSION" if r["regression"] else ""
            print(f"{r['name']:<44} {r['old']:>14} -> {r['new']:>14} "
                  f"({delta}){flag}")
        verdict = (f"{len(regressions)} regression(s)" if regressions
                   else "no regressions")
        print(f"bench_diff: {len(rows)} metrics compared, {verdict}")
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
