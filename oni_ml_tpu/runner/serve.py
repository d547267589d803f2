"""`ml_ops serve` — run the streaming scoring service from a completed
day directory (SURVEY §5: the reference's only serving story is
re-running tomorrow's batch).

    python -m oni_ml_tpu.runner.ml_ops serve \
        --day-dir /data/days/20160122 --dsource flow \
        --input - --refresh-every 8

reads raw CSV events (one per line) from --input (file or stdin),
scores them in micro-batches against the registry's active model, emits
one {"stage": "serve", ...} metrics line per batch, prints flagged
events (score < threshold) as JSON lines, and — with --refresh-every —
folds the stream into online-LDA updates that hot-swap refreshed models
in without a restart.

`--dry-run` runs the whole stack (registry -> micro-batches ->
mid-stream hot-swap -> refresh republish) against a small synthetic
in-memory day and verifies the exactly-once contract; it needs no day
directory, no accelerator, and finishes in seconds — the CI smoke
(tools/serve_smoke.py) wraps it.
"""

from __future__ import annotations

import argparse
import json
import pickle
import sys

import numpy as np

from ..config import OnlineLDAConfig, ScoringConfig, ServingConfig
from ..sources import get as get_source
from ..sources import names as source_names
from ..serving import (
    BatchScorer,
    MetricsEmitter,
    ModelRegistry,
    RefreshLoop,
    featurizer_from_features,
)


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ml_ops serve",
        description="streaming scoring service over a completed day's "
        "model (micro-batch serving with online-LDA hot-swap refresh)",
    )
    p.add_argument("--day-dir", default=None,
                   help="completed day directory (doc_results.csv / "
                   "word_results.csv / features.pkl)")
    p.add_argument("--dsource", choices=list(source_names()),
                   default="flow")
    p.add_argument("--input", default="-", metavar="PATH",
                   help="raw event CSV stream; '-' = stdin (default)")
    p.add_argument("--threshold", type=float,
                   default=ScoringConfig.threshold,
                   help="emit events scoring under this as suspicious")
    # None = "not passed": the flag applies to whichever scorer the
    # mode runs (BatchScorer max_batch/max_wait_ms, or the
    # FleetScorer's fleet_max_batch/fleet_max_wait_ms under --fleet),
    # and a None sentinel — unlike comparing against the default value
    # — distinguishes 'unset' from 'explicitly set to the default' for
    # the dry runs' rescaling.
    p.add_argument("--max-batch", type=int, default=None,
                   help="micro-batch flush size (default: config/plan; "
                   "under --fleet this sets the cross-tenant flush "
                   "size)")
    p.add_argument("--max-wait-ms", type=float, default=None,
                   help="micro-batch latency trigger in ms (default: "
                   "config/plan; under --fleet this sets the "
                   "cross-tenant trigger)")
    p.add_argument("--device-score-min", type=int,
                   default=ServingConfig.device_score_min,
                   help="batches at/above this size score on device "
                   "(jit); smaller stay on the host f64 path; 0 = "
                   "pick the break-even from the measured dispatch "
                   "calibration (the default, so the device path can "
                   "never silently lose to host)")
    p.add_argument("--refresh-every", type=int, default=0, metavar="N",
                   help="fold every N scored batches into one online-LDA "
                   "step and hot-swap the refreshed model in (0=off)")
    p.add_argument("--metrics", default="", metavar="PATH",
                   help="also append per-batch metrics JSON lines here")
    p.add_argument("--metrics-port", type=int,
                   default=ServingConfig.metrics_port, metavar="PORT",
                   help="serve an OpenMetrics scrape endpoint (GET "
                   "/metrics) on this port: live counters, fixed-"
                   "boundary latency histograms with p50/p99/p999, and "
                   "roofline utilization gauges (0 = off)")
    p.add_argument("--metrics-host", default=ServingConfig.metrics_host,
                   metavar="ADDR",
                   help="bind address for --metrics-port (default "
                   "loopback; pass 0.0.0.0 to let remote collectors "
                   "scrape)")
    p.add_argument("--openmetrics", default=ServingConfig.openmetrics_path,
                   metavar="PATH",
                   help="write the same OpenMetrics text here at stream "
                   "end — the headless/CI file sink")
    p.add_argument("--journal", default="", metavar="PATH",
                   help="append every serving event to a crash-safe "
                   "telemetry journal (telemetry/journal.py JSONL: "
                   "atomic line writes, fsync cadence) — the serving "
                   "analogue of the pipeline's run_journal.jsonl; "
                   "tools/trace_view.py summarizes it")
    p.add_argument("--top-domains", default=None,
                   help="top-1m.csv whitelist for DNS featurization")
    p.add_argument("--no-plans", action="store_true",
                   help="disable measured-plan lookups "
                   "(oni_ml_tpu/plans): max_batch/max_wait_ms and the "
                   "dispatch calibration fall back to config/defaults")
    p.add_argument("--no-compilation-cache", action="store_true",
                   help="do not wire jax_compilation_cache_dir (by "
                   "default compiled scoring programs persist across "
                   "restarts, and startup AOT-warms the device scorer "
                   "at the plan's shapes before the first event)")
    p.add_argument("--dry-run", action="store_true",
                   help="exercise the full serving stack on a synthetic "
                   "in-memory day (no --day-dir needed) and exit")
    p.add_argument("--fleet", default="", metavar="MANIFEST",
                   help="multi-tenant fleet mode: serve every tenant in "
                   "this JSON manifest (serving/tenants.py) through one "
                   "shared compiled batch family; stream lines are "
                   "'<tenant>\\t<raw csv line>'.  With --dry-run, the "
                   "literal value 'synthetic' (or 'synthetic:N') runs "
                   "the fleet acceptance path on N in-memory tenants "
                   "(default 2) and exits")
    p.add_argument("--hot-tenants", type=int,
                   default=ServingConfig.fleet_hot_tenants, metavar="N",
                   help="tiered residency: at most N tenants per "
                   "K-group stay HBM-hot (stack-resident); the rest "
                   "page host-warm/checkpoint-cold on demand "
                   "(serving/residency.py).  0 = unbounded unless a "
                   "measured plan supplies a capacity")
    p.add_argument("--warm-tenants", type=int,
                   default=ServingConfig.fleet_warm_tenants, metavar="N",
                   help="at most N non-hot tenants keep host-resident "
                   "models; beyond that the coldest spill to "
                   "checkpoint-cold (0 = unbounded)")
    p.add_argument("--residency-policy",
                   choices=["lru", "lfu"],
                   default=ServingConfig.residency_policy,
                   help="eviction victim selection (admission-aware "
                   "LRU or LFU)")
    p.add_argument("--residency-spill",
                   default=ServingConfig.residency_spill_dir,
                   metavar="DIR",
                   help="cold-tier spill dir for tenants without a "
                   "reloadable day_dir (default: per-process temp "
                   "dir; manifest tenants reload from their day_dir "
                   "and never spill)")
    p.add_argument("--stack-precision", choices=["f32", "bf16"],
                   default=ServingConfig.stack_precision,
                   help="stacked-snapshot device storage dtype; bf16 "
                   "doubles HBM-hot tenant residency per byte with "
                   "f32 accumulation (~2^-8 relative score drift, "
                   "documented tolerance)")
    return p


def _serving_config(args) -> ServingConfig:
    mb, mw = args.max_batch, args.max_wait_ms
    return ServingConfig(
        max_batch=mb if mb is not None else ServingConfig.max_batch,
        max_wait_ms=mw if mw is not None else ServingConfig.max_wait_ms,
        fleet_max_batch=(mb if mb is not None
                         else ServingConfig.fleet_max_batch),
        fleet_max_wait_ms=(mw if mw is not None
                           else ServingConfig.fleet_max_wait_ms),
        device_score_min=args.device_score_min,
        refresh_every=args.refresh_every,
        threshold=args.threshold,
        metrics_path=args.metrics,
        metrics_port=getattr(args, "metrics_port", 0),
        metrics_host=getattr(args, "metrics_host",
                             ServingConfig.metrics_host),
        openmetrics_path=getattr(args, "openmetrics", ""),
        fleet_manifest=getattr(args, "fleet", ""),
        fleet_hot_tenants=getattr(args, "hot_tenants",
                                  ServingConfig.fleet_hot_tenants),
        fleet_warm_tenants=getattr(args, "warm_tenants",
                                   ServingConfig.fleet_warm_tenants),
        residency_policy=getattr(args, "residency_policy",
                                 ServingConfig.residency_policy),
        residency_spill_dir=getattr(args, "residency_spill",
                                    ServingConfig.residency_spill_dir),
        stack_precision=getattr(args, "stack_precision",
                                ServingConfig.stack_precision),
    )


def _load_featurizer(day_dir: str, top_domains_path: "str | None"):
    import os

    feats_path = os.path.join(day_dir, "features.pkl")
    if not os.path.exists(feats_path):
        raise FileNotFoundError(
            f"{feats_path} missing — serving pins word identity to the "
            "trained day's quantile cuts, which ride in features.pkl "
            "(run the pre stage, or keep the day dir intact)"
        )
    with open(feats_path, "rb") as f:
        features = pickle.load(f)
    top = frozenset()
    if top_domains_path:
        from ..features import load_top_domains

        top = load_top_domains(top_domains_path)
    return featurizer_from_features(features, top_domains=top)


def _looks_like_header(line: str, dsource: str) -> bool:
    """True when a stream's FIRST line is a column-name header: the
    source spec's always-numeric probe column (flow `hour`, dns
    `unix_tstamp`, proxy `duration`) doesn't parse.  Only consulted
    for the first line, so mid-stream garbage rows keep the batch
    path's NaN-featurize-and-score semantics."""
    parts = line.strip().split(",")
    col = get_source(dsource).header_probe_col
    if len(parts) <= col:
        return False
    try:
        float(parts[col])
        return False
    except ValueError:
        return True


def _make_serve_roofline(metrics, journal):
    """Serve roofline gauge, computed at SCRAPE time (and once at
    shutdown): the warmed micro-batch program's harvested cost over the
    cumulative DEVICE scoring wall (the serve.device_score_ms histogram
    — device-path flushes only; pricing host flushes as device
    dispatches would inflate the gauge arbitrarily) — achieved vs peak
    for the serving phase, utilization null off-TPU.  Shared by the
    single-model and fleet serve paths (the fleet's per-flush aggregate
    record feeds the same histograms)."""
    from ..telemetry import roofline as _roofline

    def _serve_roofline(emit_journal: bool = False):
        rec = metrics.recorder
        kw = {"journal": journal} if emit_journal else {}
        hd = rec.histograms.get("serve.device_score_ms")
        if hd is not None and hd.count:
            dev_events = rec.counters.get("serve.device_events")
            return _roofline.emit(
                "serve.micro_batch", hd.total / 1e3, dispatches=hd.count,
                recorder=rec, path="device",
                events=dev_events.value
                if dev_events is not None else None, **kw,
            )
        # Host-path-only session (every flush under break-even): no
        # device program ran, so there is no cost to join — emit a
        # wall-time-only record over the full scoring wall (the entry
        # name is unharvested by construction), never the device
        # program's cost times host flushes.
        h = rec.histograms.get("serve.score_ms")
        if h is None or not h.count:
            return None
        return _roofline.emit(
            "serve.micro_batch", h.total / 1e3, dispatches=h.count,
            recorder=rec, entry="serve.micro_batch.host", path="host",
            **kw,
        )

    return _serve_roofline


def serve_stream(args) -> int:
    from ..config import ScoringConfig as SC
    from ..plans import warmup as plans_warmup

    if not args.day_dir:
        raise SystemExit("serve needs --day-dir (or --dry-run)")
    # Persistent compilation cache BEFORE the first trace: a restarted
    # service deserializes yesterday's compiled scorers instead of
    # re-tracing them while events queue.  (--no-plans scoping is
    # main()'s job — it binds both this path and --dry-run.)
    cc_rec = plans_warmup.setup_compilation_cache(
        enabled=not args.no_compilation_cache
    )
    cfg = _serving_config(args)
    sc = SC()
    fallback = get_source(args.dsource).fallback(sc)
    registry = ModelRegistry()
    snap = registry.load_day(args.day_dir, fallback)
    featurizer = _load_featurizer(args.day_dir, args.top_domains)
    if featurizer.dsource != args.dsource:
        raise SystemExit(
            f"--dsource {args.dsource} but {args.day_dir} holds "
            f"{featurizer.dsource} features"
        )
    journal = None
    if getattr(args, "journal", ""):
        from ..telemetry import Journal

        journal = Journal(args.journal)
    metrics = MetricsEmitter(path=cfg.metrics_path, journal=journal)
    metrics.emit({
        "stage": "serve", "event": "model_loaded",
        "source": snap.source, "model_version": snap.version,
        "ips": len(snap.model.ip_index),
        "vocab": len(snap.model.word_index),
    })

    _serve_roofline = _make_serve_roofline(metrics, journal)

    mserver = None
    if cfg.metrics_port:
        from ..telemetry import MetricsServer

        mserver = MetricsServer(
            metrics.recorder, port=cfg.metrics_port,
            host=cfg.metrics_host, refresh=_serve_roofline,
        )
        metrics.emit({
            "stage": "serve", "event": "metrics_endpoint",
            "port": mserver.port, "path": "/metrics",
        })

    # Everything below runs under one finally that releases the HTTP
    # endpoint, the metrics file, and the journal: a mid-stream
    # exception must not leave the ThreadingHTTPServer bound (an
    # in-process restart on the same port would EADDRINUSE) or the
    # sinks open.
    try:
        refresh = (
            RefreshLoop(
                registry,
                OnlineLDAConfig(num_topics=snap.model.num_topics),
                every=cfg.refresh_every,
                total_docs=cfg.refresh_total_docs,
            )
            if cfg.refresh_every
            else None
        )

        def on_batch(snapshot, feats, scores):
            for i in np.where(scores < cfg.threshold)[0]:
                print(json.dumps({
                    "flagged": feats.featurized_row(int(i)),
                    "score": float(scores[i]),
                    "model_version": snapshot.version,
                }), flush=True)
            if refresh is not None:
                from ..serving import event_documents

                ips, words = event_documents(feats, featurizer.dsource)
                new = refresh.observe(snapshot, ips, words)
                if new is not None:
                    metrics.emit({
                        "stage": "serve", "event": "model_refresh",
                        "model_version": new.version,
                        "source": new.source,
                    })

        scorer = BatchScorer(
            registry, featurizer, cfg, metrics=metrics, on_batch=on_batch
        )
        # AOT warmup at the PLAN's shapes: the padded micro-batch device
        # programs (break-even .. max_batch, powers of two) compile NOW
        # — into the persistent cache — instead of stalling the first
        # over-break-even flush mid-stream.  The emitted record names
        # every resolved knob's source and the cache-hit vs trace
        # counts, so a restarted service can be ASSERTED warm, not
        # assumed.
        try:
            warm = plans_warmup.warmup_serving(
                snap.model.theta.shape[0], snap.model.p.shape[0],
                snap.model.num_topics, scorer.max_batch,
                cfg.device_score_min,
            )
        except Exception as e:  # warmup must never block serving
            warm = {"error": repr(e)[:200]}
        calibration = None
        if cfg.device_score_min in (0, "auto"):
            # The measurement the host-vs-device choice rests on (the
            # scorer's constructor took it, or loaded it from the plan
            # cache): a stream that never leaves the host says why.
            from ..scoring import dispatch_calibration

            calibration = dispatch_calibration()
        metrics.emit({
            "stage": "serve", "event": "plans",
            "knobs": scorer.plan,
            "dispatch_calibration": calibration,
            "compilation_cache": cc_rec,
            "warmup": warm,
        })
        stream = sys.stdin if args.input == "-" else open(args.input)
        submitted = rejected = header_skipped = 0
        header = None
        first = True
        try:
            for line in stream:
                if not line.strip():
                    continue
                # The batch pre stage drops the CSV header and its
                # duplicates (featurize_flow's removeHeader); serving
                # must match, or a piped raw day file scores one phantom
                # event (header numerics parse NaN, word lands in the
                # max bins).  Mid-stream garbage rows still score —
                # batch parity.
                if first:
                    first = False
                    if _looks_like_header(line, args.dsource):
                        header = line
                        header_skipped += 1
                        continue
                if header is not None and line == header:
                    header_skipped += 1
                    continue
                try:
                    scorer.submit(line)
                    submitted += 1
                except ValueError:
                    rejected += 1
        finally:
            if stream is not sys.stdin:
                stream.close()
            scorer.close()
        metrics.emit({
            "stage": "serve", "event": "stream_end",
            "submitted": submitted, "rejected": rejected,
            "header_skipped": header_skipped,
            "events_scored": scorer.events_scored,
            "batches": scorer.batches_flushed,
            "final_model_version": registry.version,
        })
        # Final roofline record (journaled) + OpenMetrics file sink,
        # then the shutdown aggregate from the shared registry: the
        # counters and latency distributions — now with true
        # p50/p99/p999 — the per-batch lines fed all along.
        _serve_roofline(emit_journal=True)
        if cfg.openmetrics_path:
            from ..telemetry import write_openmetrics

            try:
                write_openmetrics(cfg.openmetrics_path, metrics.recorder)
            except OSError as e:
                print(f"serve: openmetrics sink failed: {e!r}",
                      file=sys.stderr)
        metrics.emit({
            "stage": "serve", "event": "registry_snapshot",
            **metrics.snapshot(),
        })
        return 0 if scorer.events_scored == submitted else 1
    finally:
        if mserver is not None:
            mserver.close()
        metrics.close()
        if journal is not None:
            journal.close()


# ---------------------------------------------------------------------------
# --dry-run: synthetic end-to-end smoke
# ---------------------------------------------------------------------------


def _synthetic_day(n_events: int = 96, n_clients: int = 8, n_doms: int = 6,
                   seed: int = 42):
    """A tiny deterministic DNS day: raw rows + the model trained
    'yesterday' on them (dirichlet-random theta/p over the day's actual
    IP/word populations, like bench.py's scoring benches).  `seed`
    varies the day — fleet harnesses use distinct seeds per tenant so
    cross-tenant demux corruption cannot hide behind identical
    models."""
    from ..features.dns import featurize_dns
    from ..scoring import ScoringModel

    rng = np.random.default_rng(seed)
    rows = [
        [
            "t", str(1454000000 + int(rng.integers(0, 86400))),
            str(int(rng.integers(40, 1500))),
            f"10.0.0.{i % n_clients}",
            f"sub{int(rng.integers(0, 20))}.dom{int(rng.integers(0, n_doms))}.com",
            "1", str(int(rng.integers(1, 17))), str(int(rng.integers(0, 4))),
        ]
        for i in range(n_events)
    ]
    feats = featurize_dns(rows)
    ips = sorted({feats.client_ip(i) for i in range(feats.num_events)})
    vocab = sorted(set(feats.word))
    k = 5
    theta = rng.dirichlet(np.ones(k), size=len(ips))
    p = rng.dirichlet(np.ones(len(vocab)), size=k).T
    model = ScoringModel.from_results(ips, theta, vocab, p, fallback=0.1)
    cuts = (feats.time_cuts, feats.frame_length_cuts,
            feats.subdomain_length_cuts, feats.entropy_cuts,
            feats.numperiods_cuts)
    return rows, model, cuts


def dry_run(args) -> int:
    """Load a synthetic model, score a stream of >= 3 micro-batches,
    hot-swap to a refreshed model mid-stream, and verify zero dropped /
    double-scored events — the acceptance path, runnable anywhere."""
    from ..serving import DnsEventFeaturizer, event_documents

    rows, model, cuts = _synthetic_day()
    registry = ModelRegistry()
    registry.publish(model, source="dry-run-synthetic")
    # Flags carry through; only values the operator did NOT pass
    # rescale to the 96-event synthetic day (max_batch=4096 would make
    # one batch and refresh_every=0 no swap — neither exercises the
    # acceptance path; the max_wait_ms default already fits the dry
    # run).
    cfg = ServingConfig(
        max_batch=(args.max_batch
                   if args.max_batch is not None else 32),
        max_wait_ms=(args.max_wait_ms
                     if args.max_wait_ms is not None
                     else ServingConfig.max_wait_ms),
        refresh_every=args.refresh_every or 2,
        threshold=args.threshold,
        device_score_min=args.device_score_min,
        metrics_path=args.metrics,
    )
    metrics = MetricsEmitter(path=cfg.metrics_path)
    refresh = RefreshLoop(registry, OnlineLDAConfig(
        num_topics=model.num_topics), every=cfg.refresh_every)
    swaps = []

    def on_batch(snapshot, feats, scores):
        ips, words = event_documents(feats, "dns")
        new = refresh.observe(snapshot, ips, words)
        if new is not None:
            swaps.append(new.version)

    featurizer = DnsEventFeaturizer(cuts)
    scorer = BatchScorer(registry, featurizer, cfg, metrics=metrics,
                         on_batch=on_batch)
    futures = [scorer.submit(r) for r in rows]
    # Resolve BEFORE close so the flushes exercise the live triggers
    # (max_batch here; max_wait has its own test), not the close drain.
    results = [f.result(timeout=30.0) for f in futures]
    scorer.close()
    versions = sorted({v for _, v in results})
    triggers: dict[str, int] = {}
    for r in metrics.records:
        if "trigger" in r:
            triggers[r["trigger"]] = triggers.get(r["trigger"], 0) + 1
    ok = (
        len(results) == len(rows)                   # zero dropped
        and all(f.done() for f in futures)          # every future resolved
        and scorer.events_scored == len(rows)       # zero double-scored
        and scorer.batches_flushed >= 3
        and len(swaps) >= 1                         # hot-swap happened
        and len(versions) >= 2                      # ...and served traffic
        and all(np.isfinite(s) for s, _ in results)
    )
    summary = {
        "serve_dry_run": "ok" if ok else "FAILED",
        "events": len(rows),
        "events_scored": scorer.events_scored,
        "batches": scorer.batches_flushed,
        "triggers": triggers,
        "refresh_swaps": len(swaps),
        "model_versions_served": versions,
        "final_model_version": registry.version,
    }
    print(json.dumps(summary), flush=True)
    metrics.close()
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# --fleet: multi-tenant serving
# ---------------------------------------------------------------------------


def serve_fleet_stream(args) -> int:
    """Serve every tenant of a fleet manifest through one FleetScorer:
    shared device residency + one AOT-warmed compiled batch family,
    per-tenant admission/metrics/hot-swap.  Stream lines are
    ``<tenant>\\t<raw csv line>`` (a single-tenant manifest also
    accepts untagged lines)."""
    from ..config import ScoringConfig as SC
    from ..plans import warmup as plans_warmup
    from ..serving import (
        FleetRegistry,
        FleetScorer,
        ResidencyManager,
        load_manifest,
        resolve_hot_capacity,
    )

    cc_rec = plans_warmup.setup_compilation_cache(
        enabled=not args.no_compilation_cache
    )
    cfg = _serving_config(args)
    specs = load_manifest(args.fleet)
    journal = None
    if getattr(args, "journal", ""):
        from ..telemetry import Journal

        journal = Journal(args.journal)
    metrics = MetricsEmitter(path=cfg.metrics_path, journal=journal)
    # Tiered residency: an explicit --hot-tenants (or a measured plan
    # capacity) bounds HBM-hot stack membership; the stack then pads to
    # power-of-two capacity tiers so paging churn never retraces.
    hot_cap, hot_src = resolve_hot_capacity(cfg)
    tiered = hot_cap > 0
    fleet = FleetRegistry(
        journal=journal, recorder=metrics.recorder,
        capacity_tiers=tiered, stack_precision=cfg.stack_precision,
    )
    residency = None
    if tiered:
        residency = ResidencyManager(
            fleet, hot_capacity=hot_cap,
            warm_capacity=cfg.fleet_warm_tenants,
            policy=cfg.residency_policy,
            spill_dir=cfg.residency_spill_dir,
            journal=journal, recorder=metrics.recorder,
            capacity_source=hot_src,
        )
    sc = SC()
    featurizers: dict = {}
    for spec in specs:
        if not spec.day_dir:
            raise SystemExit(
                f"fleet manifest tenant {spec.tenant!r} has no day_dir"
            )
        # Under residency every tenant starts host-warm: a
        # thousand-tenant census pays ZERO startup stack builds; the
        # first admissions fill the hot tier.
        fleet.add_tenant(spec, hot=not tiered)
        fallback = get_source(spec.dsource).fallback(sc)
        snap = fleet.load_day(spec.tenant, spec.day_dir, fallback)
        if residency is not None:
            residency.register(
                spec.tenant, day_source=(spec.day_dir, fallback),
            )
        fz = _load_featurizer(spec.day_dir, args.top_domains)
        if fz.dsource != spec.dsource:
            raise SystemExit(
                f"tenant {spec.tenant!r} declares dsource "
                f"{spec.dsource} but {spec.day_dir} holds "
                f"{fz.dsource} features"
            )
        featurizers[spec.tenant] = fz
        metrics.emit({
            "stage": "serve", "event": "model_loaded",
            "tenant": spec.tenant, "source": snap.source,
            "model_version": snap.version,
            "tier": (residency.tier_of(spec.tenant)
                     if residency is not None else "hot"),
            "ips": len(snap.model.ip_index),
            "vocab": len(snap.model.word_index),
        })
    _serve_roofline = _make_serve_roofline(metrics, journal)
    mserver = None
    if cfg.metrics_port:
        from ..telemetry import MetricsServer

        mserver = MetricsServer(
            metrics.recorder, port=cfg.metrics_port,
            host=cfg.metrics_host, refresh=_serve_roofline,
        )
        metrics.emit({
            "stage": "serve", "event": "metrics_endpoint",
            "port": mserver.port, "path": "/metrics",
        })
    try:
        refreshes: dict = {}
        for spec in specs:
            every = spec.refresh_every or cfg.refresh_every
            if every:
                k = fleet.active(spec.tenant).model.num_topics
                refreshes[spec.tenant] = RefreshLoop(
                    fleet.view(spec.tenant),
                    OnlineLDAConfig(num_topics=k),
                    every=every,
                    total_docs=cfg.refresh_total_docs,
                )

        def on_batch(tenant, snapshot, feats, scores):
            # `scorer` binds at call time (defined just below): the
            # lane's resolved threshold is the one resolution of
            # spec-override-else-config, shared with the flagged
            # counters.
            for i in np.where(
                    scores < scorer.tenant_threshold(tenant))[0]:
                print(json.dumps({
                    "tenant": tenant,
                    "flagged": feats.featurized_row(int(i)),
                    "score": float(scores[i]),
                    "model_version": snapshot.version,
                }), flush=True)
            refresh = refreshes.get(tenant)
            if refresh is not None:
                from ..serving import event_documents

                ips, words = event_documents(
                    feats, featurizers[tenant].dsource
                )
                new = refresh.observe(snapshot, ips, words)
                if new is not None:
                    metrics.emit({
                        "stage": "serve", "event": "model_refresh",
                        "tenant": tenant,
                        "model_version": new.version,
                        "source": new.source,
                    })

        scorer = FleetScorer(
            fleet, featurizers, cfg, metrics=metrics,
            on_batch=on_batch, journal=journal, residency=residency,
        )
        if residency is not None:
            residency.set_pending_probe(
                lambda t: len(scorer._lanes[t].pending) > 0
            )
        # AOT warmup per pack group: the padded compiled batch family
        # is shared across every tenant of a K-group, so warming the
        # STACKED shapes once covers the whole fleet — and because
        # hot-swaps preserve per-tenant row counts (and paging churn
        # preserves the capacity-tier shape), these are the only
        # shapes serving will ever dispatch (zero retraces after
        # warmup, the acceptance criterion the fleet SLO bench pins).
        # Under residency the stack materializes at the FIRST
        # promotions, so warm the hot tier with the head tenants
        # before asking for stacked shapes.
        warm: "list | dict"
        try:
            warm = []
            ks = sorted({fleet.tenant_k(s.tenant) for s in specs})
            if residency is not None:
                by_k: dict = {}
                for s in specs:
                    by_k.setdefault(
                        fleet.tenant_k(s.tenant), []).append(s.tenant)
                for k, group in by_k.items():
                    for t in group[:max(1, hot_cap)]:
                        residency.ensure_hot(t)
            for k in ks:
                stack = fleet.stack(k)
                mult = max(
                    get_source(fleet.spec(t).dsource).pairs_per_event
                    for t in stack.tenants
                )
                warm.append({
                    "k": k, "tenants": len(stack.tenants),
                    "capacity": stack.capacity or None,
                    **plans_warmup.warmup_serving(
                        stack.model.theta.shape[0],
                        stack.model.p.shape[0], k,
                        scorer.max_batch * mult,
                        cfg.device_score_min,
                    ),
                })
        except Exception as e:  # warmup must never block serving
            warm = {"error": repr(e)[:200]}
        metrics.emit({
            "stage": "serve", "event": "plans",
            "knobs": (
                {**scorer.plan, **residency.plan}
                if residency is not None else scorer.plan
            ),
            "compilation_cache": cc_rec,
            "warmup": warm,
        })
        from ..serving import AdmissionRejected

        stream = sys.stdin if args.input == "-" else open(args.input)
        submitted = rejected = header_skipped = 0
        default_tenant = specs[0].tenant if len(specs) == 1 else None
        first_seen: dict = {}
        headers: dict = {}
        try:
            for line in stream:
                if not line.strip():
                    continue
                tenant, sep, payload = line.partition("\t")
                if sep:
                    tenant = tenant.strip()
                elif default_tenant is not None:
                    tenant, payload = default_tenant, line
                else:
                    rejected += 1      # untagged line, ambiguous tenant
                    continue
                if tenant not in featurizers:
                    rejected += 1
                    continue
                # Per-tenant header handling, batch-parity semantics
                # (serve_stream): each tenant's FIRST line may be a CSV
                # header; duplicates of it are dropped too.
                if first_seen.get(tenant) is None:
                    first_seen[tenant] = True
                    if _looks_like_header(
                            payload, featurizers[tenant].dsource):
                        headers[tenant] = payload
                        header_skipped += 1
                        continue
                if headers.get(tenant) is not None \
                        and payload == headers[tenant]:
                    header_skipped += 1
                    continue
                try:
                    scorer.submit(tenant, payload)
                    submitted += 1
                except AdmissionRejected:
                    rejected += 1      # journaled + counted per tenant
                except ValueError:
                    rejected += 1
        finally:
            if stream is not sys.stdin:
                stream.close()
            scorer.close()
        metrics.emit({
            "stage": "serve", "event": "stream_end",
            "submitted": submitted, "rejected": rejected,
            "header_skipped": header_skipped,
            "events_scored": scorer.events_scored,
            "batches": scorer.batches_flushed,
            "tenant_stats": scorer.tenant_stats(),
            "residency": (residency.stats_snapshot()
                          if residency is not None else None),
            "final_versions": {
                s.tenant: fleet.version(s.tenant) for s in specs
            },
        })
        _serve_roofline(emit_journal=True)
        if cfg.openmetrics_path:
            from ..telemetry import write_openmetrics

            try:
                write_openmetrics(cfg.openmetrics_path, metrics.recorder)
            except OSError as e:
                print(f"serve: openmetrics sink failed: {e!r}",
                      file=sys.stderr)
        metrics.emit({
            "stage": "serve", "event": "registry_snapshot",
            **metrics.snapshot(),
        })
        if submitted == 0 and rejected > 0:
            # A whole stream of rejects means the FRAMING is wrong
            # (untagged lines into a multi-tenant fleet, or tenant tags
            # not in the manifest) — rc 0 here would let a CI smoke
            # call "success" on zero scored events.
            print(
                f"serve: all {rejected} stream lines rejected — check "
                "the '<tenant>\\t<line>' framing against the manifest "
                "tenant ids", file=sys.stderr,
            )
            return 1
        return 0 if scorer.events_scored == submitted else 1
    finally:
        if residency is not None:
            residency.close()
        if mserver is not None:
            mserver.close()
        metrics.close()
        if journal is not None:
            journal.close()


def _parse_synthetic_fleet(value: str) -> "int | None":
    """'synthetic' / 'synthetic:N' -> N (default 2); anything else is a
    manifest path -> None."""
    if value == "synthetic":
        return 2
    if value.startswith("synthetic:"):
        try:
            n = int(value.split(":", 1)[1])
        except ValueError:
            raise SystemExit(
                f"--fleet {value!r}: N in 'synthetic:N' must be an "
                "integer"
            ) from None
        if n < 2:
            raise SystemExit("--fleet synthetic:N needs N >= 2 (the "
                             "fleet acceptance path is cross-tenant)")
        return n
    return None


def dry_run_fleet(args) -> int:
    """Fleet acceptance path on synthetic in-memory tenants: distinct
    per-tenant models score a tagged interleaved stream through ONE
    FleetScorer, tenant 0 hot-swaps mid-stream, and the run verifies
    per-tenant exactly-once delivery, cross-tenant packing (flushes
    spanning >= 2 tenants), and swap isolation (the other tenants'
    versions and futures are untouched).  Runnable anywhere, seconds,
    no day directory — the fleet half of tools/serve_smoke.py."""
    from ..serving import (
        DnsEventFeaturizer,
        FleetRegistry,
        FleetScorer,
        TenantSpec,
    )

    n_tenants = _parse_synthetic_fleet(args.fleet)
    if n_tenants is None:
        # A real manifest under --dry-run must not be SILENTLY replaced
        # by the synthetic fleet — an operator smoke-testing their
        # production manifest would get "ok" without it ever being
        # opened.
        raise SystemExit(
            "--dry-run --fleet takes 'synthetic[:N]' (the dry run "
            "builds in-memory tenants); to serve a real manifest, run "
            "without --dry-run"
        )
    tenants = [f"t{i}" for i in range(n_tenants)]
    days = {
        t: _synthetic_day(seed=42 + i)
        for i, t in enumerate(tenants)
    }
    fleet = FleetRegistry()
    featurizers = {}
    for t in tenants:
        rows, model, cuts = days[t]
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, model, source=f"dry-run-{t}")
        featurizers[t] = DnsEventFeaturizer(cuts)
    cfg = ServingConfig(
        fleet_max_batch=(args.max_batch
                         if args.max_batch is not None else 32),
        fleet_max_wait_ms=(args.max_wait_ms
                           if args.max_wait_ms is not None
                           else ServingConfig.fleet_max_wait_ms),
        threshold=args.threshold,
        device_score_min=args.device_score_min,
        metrics_path=args.metrics,
    )
    metrics = MetricsEmitter(path=cfg.metrics_path)
    scorer = FleetScorer(fleet, featurizers, cfg, metrics=metrics)
    futures: dict = {t: [] for t in tenants}
    # First half of every tenant's day, interleaved round-robin — the
    # packed flushes must span tenants.
    half = {t: len(days[t][0]) // 2 for t in tenants}
    for i in range(max(half.values())):
        for t in tenants:
            if i < half[t]:
                futures[t].append(scorer.submit(t, days[t][0][i]))
    scorer.flush()
    first_results = {
        t: [f.result(timeout=30.0) for f in futures[t]] for t in tenants
    }
    # Mid-stream hot-swap of tenant 0 ONLY, then the second half.
    swapped = tenants[0]
    fleet.publish(swapped, _perturbed_model(days[swapped][1]),
                  source="dry-run-refresh")
    for t in tenants:
        for row in days[t][0][half[t]:]:
            futures[t].append(scorer.submit(t, row))
    scorer.flush()
    results = {
        t: [f.result(timeout=30.0) for f in futures[t]] for t in tenants
    }
    scorer.close()
    versions = {t: sorted({v for _, v in results[t]}) for t in tenants}
    packed_flushes = sum(
        1 for r in metrics.records
        if "tenants" in r and isinstance(r.get("tenants"), int)
        and r["tenants"] >= 2
    )
    ok = (
        all(len(results[t]) == len(days[t][0]) for t in tenants)
        and scorer.events_scored == sum(
            len(days[t][0]) for t in tenants
        )
        and packed_flushes >= 1                      # cross-tenant packing
        and versions[swapped][-1] >= 2               # swap served traffic
        and all(versions[t] == [1] for t in tenants[1:])  # isolation
        and all(
            np.isfinite(s) for t in tenants for s, _ in results[t]
        )
    )
    summary = {
        "serve_fleet_dry_run": "ok" if ok else "FAILED",
        "tenants": n_tenants,
        "events": sum(len(days[t][0]) for t in tenants),
        "events_scored": scorer.events_scored,
        "batches": scorer.batches_flushed,
        "packed_flushes": packed_flushes,
        "versions_served": versions,
        "first_flush_events": sum(len(v) for v in first_results.values()),
        "tenant_stats": scorer.tenant_stats(),
    }
    print(json.dumps(summary), flush=True)
    metrics.close()
    return 0 if ok else 1


def _perturbed_model(model):
    """A validly-normalized variant of `model` — the dry run's stand-in
    for a refreshed publish (same shapes, different values, so the
    stacked snapshot rebuilds without a retrace)."""
    from ..scoring import ScoringModel

    rng = np.random.default_rng(7)
    theta = model.theta * rng.uniform(0.5, 1.5, model.theta.shape)
    theta[:-1] /= theta[:-1].sum(1, keepdims=True)
    p = model.p * rng.uniform(0.5, 1.5, model.p.shape)
    p[:-1] /= p[:-1].sum(0, keepdims=True)
    return ScoringModel(
        ip_index=model.ip_index, theta=theta,
        word_index=model.word_index, p=p,
    )


def main(argv: "list[str] | None" = None) -> int:
    args = build_serve_parser().parse_args(argv)
    # --no-plans binds BOTH entry paths here, once: a BatchScorer
    # (serve or dry run) would otherwise resolve flush triggers from —
    # and record its dispatch calibration into — the live user cache;
    # a smoke run must not tune production.
    import contextlib

    from ..plans import NullStore, use_store

    ctx = (
        use_store(NullStore()) if args.no_plans
        else contextlib.nullcontext()
    )
    with ctx:
        if args.dry_run:
            if args.fleet:
                return dry_run_fleet(args)
            return dry_run(args)
        if args.fleet:
            if _parse_synthetic_fleet(args.fleet) is not None:
                raise SystemExit(
                    "--fleet synthetic is a --dry-run mode; a live "
                    "serve needs a manifest file"
                )
            return serve_fleet_stream(args)
        return serve_stream(args)


if __name__ == "__main__":
    raise SystemExit(main())
