"""Multi-tenant serving fleet (oni_ml_tpu/serving/fleet.py +
tenants.py): manifest/spec validation, FleetRegistry stacked snapshots
and per-tenant hot-swap, cross-tenant packed-dispatch score parity
(bit-identical to single-tenant scoring), admission backpressure and
rejection, the hot-swap isolation stress the acceptance criteria name,
per-tenant metrics on the live /metrics endpoint, the fleet dry-run
CLI, the load_gen fleet SLO harness, and bench_diff's
latency-direction-aware serving keys.  All CPU, no markers — this file
is the tier-1 fleet smoke.
"""

import json
import os
import pickle
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from oni_ml_tpu import plans
from oni_ml_tpu.config import ServingConfig
from oni_ml_tpu.plans import KNOBS, PlanStore, use_store
from oni_ml_tpu.runner.serve import _synthetic_day
from oni_ml_tpu.scoring import ScoringModel
from oni_ml_tpu.serving import (
    AdmissionRejected,
    DnsEventFeaturizer,
    FleetRegistry,
    FleetScorer,
    FlowEventFeaturizer,
    MetricsEmitter,
    RefreshLoop,
    TenantSpec,
    demux_scores,
    event_documents,
    load_manifest,
    parse_manifest,
    score_features,
)
from oni_ml_tpu.telemetry.spans import Recorder

from test_features import flow_row


@pytest.fixture(scope="module")
def days():
    """Three distinct synthetic DNS days (distinct seeds -> distinct
    models; same K -> one pack group) shared by the fleet tests."""
    return {f"t{i}": _synthetic_day(seed=42 + i) for i in range(3)}


def _perturbed(model: ScoringModel, seed: int = 7) -> ScoringModel:
    rng = np.random.default_rng(seed)
    theta = model.theta * rng.uniform(0.5, 1.5, model.theta.shape)
    theta[:-1] /= theta[:-1].sum(1, keepdims=True)
    p = model.p * rng.uniform(0.5, 1.5, model.p.shape)
    p[:-1] /= p[:-1].sum(0, keepdims=True)
    return ScoringModel(
        ip_index=model.ip_index, theta=theta,
        word_index=model.word_index, p=p,
    )


def _fleet(days, tenants=("t0", "t1"), **cfg_kw):
    """FleetRegistry + FleetScorer over `tenants`, host-pinned."""
    fleet = FleetRegistry()
    featurizers = {}
    for t in tenants:
        rows, model, cuts = days[t]
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, model, source=f"day-{t}")
        featurizers[t] = DnsEventFeaturizer(cuts)
    cfg = ServingConfig(device_score_min=None, **cfg_kw)
    metrics = MetricsEmitter(to_stdout=False)
    scorer = FleetScorer(fleet, featurizers, cfg, metrics=metrics)
    return fleet, featurizers, metrics, scorer


# ---------------------------------------------------------------------------
# specs + manifest
# ---------------------------------------------------------------------------


def test_tenant_spec_validation():
    TenantSpec(tenant="ok_id_1", dsource="dns")        # valid
    with pytest.raises(ValueError, match="tenant id"):
        TenantSpec(tenant="bad.dots", dsource="dns")
    with pytest.raises(ValueError, match="tenant id"):
        # '-' rewrites to '_' in OpenMetrics names: "acme-eu" and
        # "acme_eu" would merge onto one exposition series.
        TenantSpec(tenant="acme-eu", dsource="dns")
    with pytest.raises(ValueError, match="tenant id"):
        TenantSpec(tenant="", dsource="dns")
    with pytest.raises(ValueError, match="dsource"):
        TenantSpec(tenant="a", dsource="http")
    with pytest.raises(ValueError, match="admission"):
        TenantSpec(tenant="a", dsource="dns", admission="drop")
    with pytest.raises(ValueError, match="queue_max"):
        TenantSpec(tenant="a", dsource="dns", queue_max=-1)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec(tenant="a", dsource="dns", weight=0.0)


def test_manifest_roundtrip_and_rejections(tmp_path):
    path = str(tmp_path / "fleet.json")
    with open(path, "w") as f:
        json.dump({"tenants": [
            {"tenant": "alpha", "day_dir": "/d/a", "dsource": "flow",
             "weight": 2.0},
            {"tenant": "beta", "day_dir": "/d/b", "dsource": "dns",
             "queue_max": 64, "admission": "reject"},
        ]}, f)
    specs = load_manifest(path)
    assert [s.tenant for s in specs] == ["alpha", "beta"]
    assert specs[0].weight == 2.0
    assert specs[1].admission == "reject"
    with pytest.raises(ValueError, match="duplicate"):
        parse_manifest({"tenants": [
            {"tenant": "a", "dsource": "dns"},
            {"tenant": "a", "dsource": "dns"},
        ]})
    with pytest.raises(ValueError, match="unknown keys"):
        parse_manifest({"tenants": [{"tenant": "a", "dsourc": "dns"}]})
    with pytest.raises(ValueError, match="zero tenants"):
        parse_manifest({"tenants": []})
    with pytest.raises(ValueError, match="'tenants' list"):
        parse_manifest(["a"])


# ---------------------------------------------------------------------------
# FleetRegistry: per-tenant hot-swap + stacked snapshots
# ---------------------------------------------------------------------------


def test_registry_per_tenant_versions_monotonic(days):
    fleet = FleetRegistry()
    for t in ("t0", "t1"):
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
    _, m0, _ = days["t0"]
    _, m1, _ = days["t1"]
    fleet.publish("t0", m0, "a")
    fleet.publish("t1", m1, "b")
    fleet.publish("t0", _perturbed(m0), "a2")
    # t0's swap bumped ONLY t0.
    assert fleet.version("t0") == 2
    assert fleet.version("t1") == 1
    assert fleet.previous("t0").version == 1
    # The retired snapshot stays pinned (registry.py semantics).
    assert fleet.previous("t0").model is m0


def test_stacked_snapshot_offsets_and_contents(days):
    fleet = FleetRegistry()
    models = {}
    for t in ("t0", "t1", "t2"):
        _, m, _ = days[t]
        models[t] = m
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, m, t)
    stack = fleet.stack_for("t0")
    assert stack.tenants == ("t0", "t1", "t2")
    # Each tenant's slice (INCLUDING its fallback row) is its model.
    for t in stack.tenants:
        m = models[t]
        i0 = stack.ip_base[t]
        w0 = stack.word_base[t]
        np.testing.assert_array_equal(
            stack.model.theta[i0:i0 + m.theta.shape[0]], m.theta)
        np.testing.assert_array_equal(
            stack.model.p[w0:w0 + m.p.shape[0]], m.p)
    assert stack.model.theta.shape[0] == sum(
        m.theta.shape[0] for m in models.values())


def test_stack_double_buffered_install(days):
    fleet = FleetRegistry()
    for t in ("t0", "t1"):
        _, m, _ = days[t]
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, m, t)
    before = fleet.stack_for("t0")
    theta_before = before.model.theta.copy()
    fleet.publish("t0", _perturbed(days["t0"][1]), "swap")
    after = fleet.stack_for("t0")
    # Fresh instance installed; the old one an in-flight flush holds is
    # untouched — and t1's slice is bit-identical across the swap.
    assert after is not before
    assert after.stack_version > before.stack_version
    np.testing.assert_array_equal(before.model.theta, theta_before)
    t1 = days["t1"][1]
    w0 = after.word_base["t1"]
    np.testing.assert_array_equal(
        after.model.p[w0:w0 + t1.p.shape[0]], t1.p)
    assert after.version_of("t0") == 2
    assert after.version_of("t1") == 1


def test_registry_errors(days):
    fleet = FleetRegistry()
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"))
    with pytest.raises(ValueError, match="already added"):
        fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"))
    with pytest.raises(KeyError, match="unknown tenant"):
        fleet.active("nope")
    with pytest.raises(RuntimeError, match="no model published"):
        fleet.active("t0")
    with pytest.raises(RuntimeError, match="no published model"):
        fleet.tenant_k("t0")


def test_fleet_publish_journal_and_counter(days, tmp_path):
    from oni_ml_tpu.telemetry import Journal

    jpath = str(tmp_path / "fleet.jsonl")
    journal = Journal(jpath)
    rec = Recorder()
    fleet = FleetRegistry(journal=journal, recorder=rec)
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"))
    _, m, _ = days["t0"]
    fleet.publish("t0", m, "day-one")
    fleet.publish("t0", _perturbed(m), "refresh")
    journal.close()
    records = [r for r in Journal.replay(jpath)
               if r["kind"] == "fleet_publish"]
    assert [r["version"] for r in records] == [1, 2]
    assert records[0]["tenant"] == "t0"
    assert records[0]["source"] == "day-one"
    assert records[0]["k"] == m.num_topics
    assert rec.counters["serve.t0.publishes"].value == 2


def test_refresh_loop_over_fleet_view(days):
    """serving/refresh.py works unchanged against a per-tenant view:
    its publish routes through the fleet (version bump + stack
    rebuild)."""
    fleet = FleetRegistry()
    rows, model, cuts = days["t0"]
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"))
    fleet.publish("t0", model, "day")
    view = fleet.view("t0")
    from oni_ml_tpu.config import OnlineLDAConfig

    loop = RefreshLoop(view, OnlineLDAConfig(
        num_topics=model.num_topics), every=1)
    fz = DnsEventFeaturizer(cuts)
    feats = fz([fz.validate(r) for r in rows[:16]])
    ips, words = event_documents(feats, "dns")
    new = loop.observe(fleet.active("t0"), ips, words)
    assert new is not None and new.version == 2
    assert fleet.version("t0") == 2
    assert fleet.stack_for("t0").version_of("t0") == 2


def test_mixed_k_tenants_get_separate_stacks(days):
    """Tenants whose K diverges form separate pack groups (per-tenant
    segment dispatch) — heterogeneous fleets degrade to more
    dispatches, never to wrong scores."""
    rows0, m0, cuts0 = days["t0"]
    rows1, m1, cuts1 = days["t1"]
    # Rebuild t1's model with K+1 topics over the same populations.
    rng = np.random.default_rng(0)
    ips = sorted(m1.ip_index, key=m1.ip_index.get)
    vocab = sorted(m1.word_index, key=m1.word_index.get)
    k2 = m1.num_topics + 1
    m1b = ScoringModel.from_results(
        ips, rng.dirichlet(np.ones(k2), size=len(ips)),
        vocab, rng.dirichlet(np.ones(len(vocab)), size=k2).T,
        fallback=0.1,
    )
    fleet = FleetRegistry()
    for t, m in (("t0", m0), ("t1", m1b)):
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, m, t)
    assert fleet.stack_for("t0").tenants == ("t0",)
    assert fleet.stack_for("t1").tenants == ("t1",)
    metrics = MetricsEmitter(to_stdout=False)
    scorer = FleetScorer(
        fleet, {"t0": DnsEventFeaturizer(cuts0),
                "t1": DnsEventFeaturizer(cuts1)},
        ServingConfig(device_score_min=None), metrics=metrics,
    )
    try:
        futs0 = [scorer.submit("t0", r) for r in rows0[:8]]
        futs1 = [scorer.submit("t1", r) for r in rows1[:8]]
        scorer.flush()
        s0 = np.array([f.result(30.0)[0] for f in futs0])
        [f.result(30.0) for f in futs1]
    finally:
        scorer.close()
    agg = [r for r in metrics.records
           if "segments" in r and "tenant" not in r]
    # Both tenants flushed together but dispatched as two segments.
    assert any(r["segments"] == 2 and r["tenants"] == 2 for r in agg)
    fz0 = DnsEventFeaturizer(cuts0)
    feats0 = fz0([fz0.validate(r) for r in rows0[:8]])
    np.testing.assert_array_equal(
        s0, score_features(m0, feats0, "dns", device_min=None))


# ---------------------------------------------------------------------------
# packed scoring parity
# ---------------------------------------------------------------------------


def test_packed_scores_bit_identical_to_single_tenant(days):
    """The tentpole invariant: a cross-tenant packed flush produces
    BIT-IDENTICAL scores to scoring each tenant alone — packing changes
    which dispatch a row rides, never its arithmetic."""
    fleet, featurizers, metrics, scorer = _fleet(
        days, tenants=("t0", "t1", "t2"))
    futs = {t: [] for t in ("t0", "t1", "t2")}
    try:
        for i in range(64):
            for t in futs:
                futs[t].append(scorer.submit(t, days[t][0][i]))
        scorer.flush()
        got = {t: np.array([f.result(30.0)[0] for f in fs])
               for t, fs in futs.items()}
    finally:
        scorer.close()
    for t, fs in futs.items():
        fz = featurizers[t]
        feats = fz([fz.validate(days[t][0][i]) for i in range(64)])
        expected = score_features(days[t][1], feats, "dns",
                                  device_min=None)
        np.testing.assert_array_equal(got[t], expected)
    # And the packed flushes really did span tenants.
    agg = [r for r in metrics.records if "tenant" not in r
           and isinstance(r.get("tenants"), int)]
    assert any(r["tenants"] == 3 and r["segments"] == 1 for r in agg)


def test_flow_tenant_pairs_min_combined(days):
    """A flow tenant's packed pairs (two per event, src then dst)
    demux back through the min-combine — parity with the single-model
    flow scorer."""
    lines = ["header"] + [
        flow_row(sip=f"10.0.0.{i % 5}", dip=f"10.0.1.{i % 7}",
                 ipkt=str(5 + i), ibyt=str(500 + 13 * i))
        for i in range(24)
    ]
    from oni_ml_tpu.features.flow import featurize_flow

    day_feats = featurize_flow(lines)
    ips = sorted({day_feats.sip(i) for i in range(day_feats.num_events)}
                 | {day_feats.dip(i)
                    for i in range(day_feats.num_events)})
    vocab = sorted(set(day_feats.src_word) | set(day_feats.dest_word))
    rng = np.random.default_rng(3)
    k = 5
    flow_model = ScoringModel.from_results(
        ips, rng.dirichlet(np.ones(k), size=len(ips)),
        vocab, rng.dirichlet(np.ones(len(vocab)), size=k).T,
        fallback=0.05,
    )
    cuts = (day_feats.time_cuts, day_feats.ibyt_cuts,
            day_feats.ipkt_cuts)
    rows_dns, dns_model, dns_cuts = days["t0"]
    fleet = FleetRegistry()
    fleet.add_tenant(TenantSpec(tenant="fl", dsource="flow"))
    fleet.add_tenant(TenantSpec(tenant="dn", dsource="dns"))
    fleet.publish("fl", flow_model, "flow-day")
    fleet.publish("dn", dns_model, "dns-day")
    featurizers = {"fl": FlowEventFeaturizer(cuts),
                   "dn": DnsEventFeaturizer(dns_cuts)}
    metrics = MetricsEmitter(to_stdout=False)
    scorer = FleetScorer(fleet, featurizers,
                         ServingConfig(device_score_min=None),
                         metrics=metrics)
    flow_lines = lines[1:17]
    try:
        f_futs = [scorer.submit("fl", ln) for ln in flow_lines]
        d_futs = [scorer.submit("dn", r) for r in rows_dns[:16]]
        scorer.flush()
        got_flow = np.array([f.result(30.0)[0] for f in f_futs])
        got_dns = np.array([f.result(30.0)[0] for f in d_futs])
    finally:
        scorer.close()
    ffz = featurizers["fl"]
    feats = ffz([ffz.validate(ln) for ln in flow_lines])
    np.testing.assert_array_equal(
        got_flow,
        score_features(flow_model, feats, "flow", device_min=None))
    dfz = featurizers["dn"]
    dfeats = dfz([dfz.validate(r) for r in rows_dns[:16]])
    np.testing.assert_array_equal(
        got_dns,
        score_features(dns_model, dfeats, "dns", device_min=None))
    # Same K -> the flow and dns tenants packed into ONE dispatch.
    agg = [r for r in metrics.records if "tenant" not in r
           and isinstance(r.get("tenants"), int)]
    assert any(r["tenants"] == 2 and r["segments"] == 1 for r in agg)


def test_scorer_label_tracks_actual_dispatch(days, monkeypatch):
    """The flush's `scorer` label (which gates the device roofline
    histogram) follows the per-group PACKED PAIR dispatch decision, not
    the flush's raw event count."""
    from oni_ml_tpu.serving import fleet as fleet_mod

    # Pretend the break-even is 40 pairs: a 32-pair dns flush is
    # host-labeled even if someone counted 2 tenants x 16 events
    # against a lower bound; monkeypatching only the fleet's reference
    # leaves the actual scoring dispatch untouched (host on CPU).
    monkeypatch.setattr(
        fleet_mod, "use_device_path", lambda n, dmin: n >= 40)
    fleet, _, metrics, scorer = _fleet(
        days, tenants=("t0", "t1"), fleet_max_batch=32,
        fleet_max_wait_ms=60_000.0)
    try:
        futs = [scorer.submit(t, days[t][0][i])
                for i in range(16) for t in ("t0", "t1")]
        [f.result(30.0) for f in futs]
    finally:
        scorer.close()
    agg = [r for r in metrics.records
           if "tenant" not in r and "segments" in r]
    assert agg and all(r["scorer"] == "host" for r in agg)
    assert all(r["segments_device"] == 0 for r in agg)
    # A 48-pair group (>= the fake break-even) labels device.
    monkeypatch.setattr(
        fleet_mod, "use_device_path", lambda n, dmin: n >= 20)
    fleet2, _, metrics2, scorer2 = _fleet(
        days, tenants=("t0", "t1"), fleet_max_batch=32,
        fleet_max_wait_ms=60_000.0)
    try:
        futs = [scorer2.submit(t, days[t][0][i])
                for i in range(16) for t in ("t0", "t1")]
        [f.result(30.0) for f in futs]
    finally:
        scorer2.close()
    agg2 = [r for r in metrics2.records
            if "tenant" not in r and "segments" in r]
    assert agg2 and all(r["scorer"] == "device" for r in agg2)
    assert all(r["segments_device"] == r["segments"] for r in agg2)


def test_demux_scores_helper():
    s = np.array([0.4, 0.9, 0.7, 0.2, 0.8, 0.1])
    np.testing.assert_array_equal(
        demux_scores(s, 2), np.array([0.2, 0.8, 0.1]))
    np.testing.assert_array_equal(demux_scores(s, 1), s)


def test_no_cross_tenant_score_leakage(days):
    """Two tenants submit THE SAME raw rows against different models:
    each tenant's scores must come from its own model slice."""
    rows, m0, cuts = days["t0"]
    m1 = _perturbed(m0, seed=11)
    fleet = FleetRegistry()
    for t, m in (("a", m0), ("b", m1)):
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, m, t)
    fz = DnsEventFeaturizer(cuts)
    scorer = FleetScorer(fleet, {"a": fz, "b": fz},
                         ServingConfig(device_score_min=None))
    try:
        fa = [scorer.submit("a", r) for r in rows[:32]]
        fb = [scorer.submit("b", r) for r in rows[:32]]
        scorer.flush()
        sa = np.array([f.result(30.0)[0] for f in fa])
        sb = np.array([f.result(30.0)[0] for f in fb])
    finally:
        scorer.close()
    feats = fz([fz.validate(r) for r in rows[:32]])
    np.testing.assert_array_equal(
        sa, score_features(m0, feats, "dns", device_min=None))
    np.testing.assert_array_equal(
        sb, score_features(m1, feats, "dns", device_min=None))
    assert not np.array_equal(sa, sb)   # distinct models, distinct scores


# ---------------------------------------------------------------------------
# admission: backpressure + rejection
# ---------------------------------------------------------------------------


def test_admission_reject_sheds_load(days, tmp_path):
    from oni_ml_tpu.telemetry import Journal

    jpath = str(tmp_path / "admit.jsonl")
    journal = Journal(jpath)
    rows, model, cuts = days["t0"]
    fleet = FleetRegistry()
    fleet.add_tenant(TenantSpec(
        tenant="t0", dsource="dns", queue_max=4, admission="reject"))
    fleet.publish("t0", model, "day")
    metrics = MetricsEmitter(to_stdout=False)
    # A huge flush size + long wait keep the worker idle while the
    # queue fills.
    scorer = FleetScorer(
        fleet, {"t0": DnsEventFeaturizer(cuts)},
        ServingConfig(device_score_min=None,
                      fleet_max_batch=1 << 14,
                      fleet_max_wait_ms=60_000.0),
        metrics=metrics, journal=journal,
    )
    try:
        futs = [scorer.submit("t0", r) for r in rows[:4]]
        with pytest.raises(AdmissionRejected) as ei:
            scorer.submit("t0", rows[4])
        assert ei.value.tenant == "t0"
        assert (ei.value.depth, ei.value.capacity) == (4, 4)
        scorer.flush()
        [f.result(30.0) for f in futs]
        stats = {s["tenant"]: s for s in scorer.tenant_stats()}
        assert stats["t0"]["rejected"] == 1
        assert stats["t0"]["scored"] == 4
        assert metrics.recorder.counters[
            "serve.t0.admission_rejects"].value == 1
    finally:
        scorer.close()
        journal.close()
    recs = [r for r in Journal.replay(jpath)
            if r["kind"] == "admission_reject"]
    assert recs and recs[0]["tenant"] == "t0"
    assert recs[0]["capacity"] == 4


def test_admission_block_backpressures(days):
    """admission="block" (the default): a producer outrunning scoring
    throttles at its own tenant's bound, everything still streams
    through exactly once, and the stall is priced."""
    rows, model, cuts = days["t0"]
    fleet = FleetRegistry()
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns",
                                queue_max=4))
    fleet.publish("t0", model, "day")
    metrics = MetricsEmitter(to_stdout=False)
    scorer = FleetScorer(
        fleet, {"t0": DnsEventFeaturizer(cuts)},
        ServingConfig(device_score_min=None, fleet_max_batch=2,
                      fleet_max_wait_ms=5.0),
        metrics=metrics,
    )
    try:
        futs = [scorer.submit("t0", r) for r in rows[:24]]
        results = [f.result(60.0) for f in futs]
        assert len(results) == 24
        assert scorer.events_scored == 24
    finally:
        scorer.close()


def test_unknown_tenant_and_malformed_event(days):
    _, _, _, scorer = _fleet(days)
    try:
        with pytest.raises(KeyError, match="unknown tenant"):
            scorer.submit("ghost", days["t0"][0][0])
        with pytest.raises(ValueError):
            scorer.submit("t0", "not,enough,columns")
    finally:
        scorer.close()


# ---------------------------------------------------------------------------
# hot-swap isolation (acceptance criterion)
# ---------------------------------------------------------------------------


def test_hot_swap_isolation_under_sustained_load(days):
    """Publish tenant A's model repeatedly while tenant B streams:
    B sees ZERO failed futures, B's served version never moves, B's
    scores stay bit-identical to its own model, and A's registry
    versions stay monotonic."""
    fleet, featurizers, metrics, scorer = _fleet(
        days, tenants=("t0", "t1"),
        fleet_max_batch=32, fleet_max_wait_ms=5.0)
    n_pub = 12
    stop = threading.Event()
    published = []

    def publisher():
        for i in range(n_pub):
            snap = fleet.publish(
                "t0", _perturbed(days["t0"][1], seed=100 + i),
                source=f"swap-{i}")
            published.append(snap.version)
            time.sleep(0.002)
        stop.set()

    pub = threading.Thread(target=publisher, daemon=True)
    futs_b, futs_a = [], []
    pub.start()
    rows0, rows1 = days["t0"][0], days["t1"][0]
    i = 0
    while not stop.is_set() or i < 64:
        futs_a.append(scorer.submit("t0", rows0[i % len(rows0)]))
        futs_b.append(scorer.submit("t1", rows1[i % len(rows1)]))
        i += 1
        time.sleep(0.0005)
    scorer.flush()
    pub.join(timeout=30.0)
    try:
        res_a = [f.result(30.0) for f in futs_a]
        res_b = [f.result(30.0) for f in futs_b]
    finally:
        scorer.close()
    # A's registry versions are strictly monotonic, and versions served
    # to A's futures never decrease in submit order.
    assert published == list(range(2, n_pub + 2))
    versions_a = [v for _, v in res_a]
    assert all(b >= a for a, b in zip(versions_a, versions_a[1:]))
    assert fleet.version("t0") == n_pub + 1
    # Isolation: every B future resolved, on version 1, bit-identical
    # to B's own model throughout the swap storm.
    assert len(res_b) == len(futs_b)
    assert {v for _, v in res_b} == {1}
    fz = featurizers["t1"]
    m1 = days["t1"][1]
    raws = [rows1[j % len(rows1)] for j in range(len(res_b))]
    feats = fz([fz.validate(r) for r in raws])
    np.testing.assert_array_equal(
        np.array([s for s, _ in res_b]),
        score_features(m1, feats, "dns", device_min=None))
    # No error records for tenant t1.
    assert not any(r.get("tenant") == "t1" and "error" in r
                   for r in metrics.records)


# ---------------------------------------------------------------------------
# metrics plane
# ---------------------------------------------------------------------------


def test_per_tenant_metric_namespaces(days):
    _, _, metrics, scorer = _fleet(days, tenants=("t0", "t1"))
    try:
        futs = [scorer.submit(t, days[t][0][i])
                for i in range(16) for t in ("t0", "t1")]
        scorer.flush()
        [f.result(30.0) for f in futs]
    finally:
        scorer.close()
    rec = metrics.recorder
    for t in ("t0", "t1"):
        assert rec.counters[f"serve.{t}.events"].value == 16
        assert rec.histograms[f"serve.{t}.latency_ms"].count >= 1
    # The aggregate namespace counts every event exactly once (the
    # per-tenant records must not double into it).
    assert rec.counters["serve.events"].value == 32


def test_metrics_endpoint_exposes_per_tenant_series(days):
    """Acceptance: per-tenant metrics visible on the live /metrics
    endpoint."""
    from oni_ml_tpu.telemetry import MetricsServer

    _, _, metrics, scorer = _fleet(days, tenants=("t0", "t1"))
    try:
        futs = [scorer.submit(t, days[t][0][i])
                for i in range(8) for t in ("t0", "t1")]
        scorer.flush()
        [f.result(30.0) for f in futs]
        server = MetricsServer(metrics.recorder, port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
        finally:
            server.close()
    finally:
        scorer.close()
    assert "serve_t0_latency_ms" in text
    assert "serve_t1_latency_ms" in text
    assert "serve_t0_events_total" in text
    assert "serve_latency_ms" in text          # aggregate still there


# ---------------------------------------------------------------------------
# plans integration
# ---------------------------------------------------------------------------


def test_fleet_scorer_resolves_plan_knobs(days, tmp_path):
    st = PlanStore(str(tmp_path / "plans.jsonl"))
    fp = plans.fingerprint(KNOBS["fleet_max_batch"].scope)
    st.record("fleet_max_batch", fp, "*", 512, source="probe")
    with use_store(st):
        fleet, featurizers, _, scorer = _fleet(days, tenants=("t0",))
        try:
            assert scorer.max_batch == 512
            assert scorer.plan["max_batch"]["source"] == "plan"
            assert scorer.plan["max_wait_ms"]["source"] == "default"
        finally:
            scorer.close()
        # A plan flush size past the fleet's total admission capacity
        # would make the max_batch trigger unreachable — degrade to
        # the shipped default.
        st.record("fleet_max_batch", fp, "*", 1 << 20, source="probe")
        metrics = MetricsEmitter(to_stdout=False)
        scorer2 = FleetScorer(
            fleet, featurizers,
            ServingConfig(device_score_min=None), metrics=metrics)
        try:
            assert scorer2.max_batch == ServingConfig.fleet_max_batch
            assert scorer2.plan["max_batch"]["source"] == "default"
        finally:
            scorer2.close()


# ---------------------------------------------------------------------------
# CLI: fleet dry run + live manifest stream
# ---------------------------------------------------------------------------


def test_fleet_dry_run_cli(capsys):
    from oni_ml_tpu.runner import ml_ops

    assert ml_ops.main(
        ["serve", "--dry-run", "--fleet", "synthetic"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(last)
    assert summary["serve_fleet_dry_run"] == "ok"
    assert summary["tenants"] == 2
    assert summary["packed_flushes"] >= 1
    assert summary["versions_served"]["t0"][-1] >= 2
    assert summary["versions_served"]["t1"] == [1]


def test_fleet_dry_run_cli_n_tenants(capsys):
    from oni_ml_tpu.runner import ml_ops

    assert ml_ops.main(
        ["serve", "--dry-run", "--fleet", "synthetic:3"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(last)
    assert summary["serve_fleet_dry_run"] == "ok"
    assert summary["tenants"] == 3
    with pytest.raises(SystemExit):
        ml_ops.main(["serve", "--dry-run", "--fleet", "synthetic:1"])
    with pytest.raises(SystemExit, match="integer"):
        ml_ops.main(["serve", "--dry-run", "--fleet", "synthetic:four"])
    # A REAL manifest under --dry-run must not silently run the
    # synthetic path and report ok about a file it never opened.
    with pytest.raises(SystemExit, match="synthetic"):
        ml_ops.main(["serve", "--dry-run", "--fleet", "/tmp/m.json"])


def _write_day_dir(path, rows, model, dsource="dns"):
    """A minimal completed day directory: results CSVs + features.pkl
    (the three artifacts serve's fleet loader reads)."""
    from oni_ml_tpu.features.dns import featurize_dns
    from oni_ml_tpu.io import formats

    os.makedirs(path, exist_ok=True)
    ips = sorted(model.ip_index, key=model.ip_index.get)
    vocab = sorted(model.word_index, key=model.word_index.get)
    formats.write_doc_results(
        os.path.join(path, "doc_results.csv"), ips, model.theta[:-1])
    formats.write_word_results(
        os.path.join(path, "word_results.csv"), vocab,
        np.log(np.asarray(model.p[:-1], np.float64)).T)
    feats = featurize_dns(rows)
    with open(os.path.join(path, "features.pkl"), "wb") as f:
        pickle.dump(feats, f)


def test_fleet_live_stream_from_manifest(tmp_path, capsys):
    """`ml_ops serve --fleet manifest.json` end to end: two day
    directories, tenant-tagged input lines, per-tenant stream_end
    accounting, rc 0."""
    from oni_ml_tpu.runner import ml_ops

    manifest = {"tenants": []}
    input_lines = []
    for i, t in enumerate(("alpha", "beta")):
        rows, model, _ = _synthetic_day(seed=60 + i)
        day = str(tmp_path / t)
        _write_day_dir(day, rows, model)
        manifest["tenants"].append(
            {"tenant": t, "day_dir": day, "dsource": "dns"})
        input_lines += [f"{t}\t" + ",".join(r) for r in rows[:24]]
    mpath = str(tmp_path / "fleet.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    ipath = str(tmp_path / "events.csv")
    with open(ipath, "w") as f:
        f.write("\n".join(input_lines) + "\n")
    rc = ml_ops.main([
        "serve", "--fleet", mpath, "--input", ipath, "--no-plans",
        "--no-compilation-cache", "--device-score-min", "0",
        "--max-batch", "12",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    end = next(json.loads(ln) for ln in out.splitlines()
               if '"stream_end"' in ln)
    assert end["submitted"] == 48
    assert end["events_scored"] == 48
    # --max-batch reaches the FLEET scorer (48 events / 12 per flush).
    assert end["batches"] >= 4
    plans_rec = next(json.loads(ln) for ln in out.splitlines()
                     if '"event": "plans"' in ln)
    assert plans_rec["knobs"]["max_batch"]["value"] == 12
    per_tenant = {s["tenant"]: s for s in end["tenant_stats"]}
    assert per_tenant["alpha"]["scored"] == 24
    assert per_tenant["beta"]["scored"] == 24
    assert end["final_versions"] == {"alpha": 1, "beta": 1}
    loaded = [json.loads(ln) for ln in out.splitlines()
              if '"model_loaded"' in ln]
    assert {r["tenant"] for r in loaded} == {"alpha", "beta"}
    # A stream whose EVERY line is rejected (untagged lines into a
    # multi-tenant fleet — a framing mismatch) must NOT exit 0.
    bad = str(tmp_path / "untagged.csv")
    with open(bad, "w") as f:
        f.write("\n".join(ln.split("\t", 1)[1]
                          for ln in input_lines[:8]) + "\n")
    rc = ml_ops.main([
        "serve", "--fleet", mpath, "--input", bad, "--no-plans",
        "--no-compilation-cache",
    ])
    capsys.readouterr()
    assert rc == 1


def test_fleet_live_stream_rejects_synthetic_outside_dry_run():
    from oni_ml_tpu.runner import ml_ops

    with pytest.raises(SystemExit, match="dry-run"):
        ml_ops.main(["serve", "--fleet", "synthetic"])


# ---------------------------------------------------------------------------
# load_gen fleet harness + bench_diff serving keys
# ---------------------------------------------------------------------------


def _tools():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = os.path.join(here, "tools")
    if p not in sys.path:
        sys.path.insert(0, p)


def test_parse_mix_and_fleet_mix():
    _tools()
    import load_gen

    assert load_gen.parse_mix("poisson:2,bursty:1") == [
        ("poisson", 2.0), ("bursty", 1.0)]
    assert load_gen.parse_mix("poisson") == [("poisson", 1.0)]
    with pytest.raises(ValueError, match="unknown pattern"):
        load_gen.parse_mix("uniform:1")
    with pytest.raises(ValueError, match="weight"):
        load_gen.parse_mix("poisson:0")
    mix = load_gen.fleet_mix(4, "poisson:3,bursty:1", 4000.0)
    assert [m["pattern"] for m in mix] == [
        "poisson", "bursty", "poisson", "bursty"]
    # Weights split the aggregate offered rate.
    assert sum(m["rate_eps"] for m in mix) == pytest.approx(4000.0)
    assert mix[0]["rate_eps"] == pytest.approx(4000.0 * 3 / 8)


def test_run_fleet_slo_small():
    _tools()
    import load_gen

    res = load_gen.run_fleet_slo(
        2, "poisson:1,bursty:1", n_events=64, rate_eps=5000.0,
        max_batch=32, max_wait_ms=5.0, device_score_min=None,
    )
    assert res["n_tenants"] == 2
    agg = res["aggregate"]
    assert agg["resolved"] == res["n_events"]
    assert agg["errors"] == 0
    assert agg["p99_ms"] is not None
    assert set(res["tenants"]) == {"t0", "t1"}
    for t, summary in res["tenants"].items():
        assert summary["resolved"] == summary["events"]
        assert summary["pattern"] in ("poisson", "bursty")
        assert summary["p50_ms"] is not None
    # The zero-retrace proof rides every payload (0 on a host-pinned
    # run by construction; the field is what the TPU bench gates on).
    assert res["plans"]["retraces_after_warmup"] == 0
    # Measured window only — the warmup burst is excluded.
    assert res["packed"]["events_scored"] == res["n_events"]


def test_bench_diff_serving_latency_directions(tmp_path):
    _tools()
    import bench_diff

    def fleet_payload(p99_t1, eps=4000):
        return {
            "metric": "serving", "value": eps, "unit": "events/sec",
            "secondary": {"serving_slo_fleet": {
                "value": eps, "unit": "events/sec",
                "aggregate": {"sustained_eps": eps, "p50_ms": 10,
                              "p99_ms": 20, "p999_ms": 25},
                "tenants": {
                    "t0": {"sustained_eps": eps / 2, "p99_ms": 20,
                           "p999_ms": 22},
                    "t1": {"sustained_eps": eps / 2, "p99_ms": p99_t1,
                           "p999_ms": 23},
                },
            }},
        }

    # A per-tenant p99 blowup is a REGRESSION (ms = lower-better)...
    rows = bench_diff.diff_payloads(
        fleet_payload(20), fleet_payload(40))
    reg = [r for r in rows if r["regression"]]
    assert [r["name"] for r in reg] == [
        "phase:serving_slo_fleet:tenant.t1.p99_ms"]
    # ...while a p99 IMPROVEMENT of the same magnitude is not.
    rows = bench_diff.diff_payloads(
        fleet_payload(40), fleet_payload(20))
    assert not [r for r in rows if r["regression"]]
    # sustained_eps keeps the higher-better direction.
    rows = bench_diff.diff_payloads(
        fleet_payload(20, eps=4000), fleet_payload(20, eps=2000))
    assert any(r["regression"]
               and r["name"].endswith("sustained_eps")
               for r in rows)
    # serving_slo (single-model) pattern groups compare too.
    old = {"secondary": {"serving_slo": {
        "value": 1, "unit": "events/sec",
        "poisson": {"sustained_eps": 1000, "p99_ms": 5,
                    "p999_ms": 9}}}}
    new = json.loads(json.dumps(old))
    new["secondary"]["serving_slo"]["poisson"]["p999_ms"] = 30
    rows = bench_diff.diff_payloads(old, new)
    assert any(r["regression"] and "p999" in r["name"] for r in rows)


def test_bench_serving_slo_fleet_smoke():
    """bench.py's fleet phase wrapper returns the acceptance payload
    shape: aggregate + >= 4 per-tenant summaries + the plans proof."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if here not in sys.path:
        sys.path.insert(0, here)
    import bench

    res = bench.bench_serving_slo_fleet(
        n_tenants=4, n_events=128, rate_eps=8000.0, max_batch=32,
        max_wait_ms=5.0, device_score_min=None)
    assert res["n_tenants"] == 4
    assert len(res["tenants"]) == 4
    assert res["aggregate"]["resolved"] == res["n_events"]
    assert res["plans"]["retraces_after_warmup"] == 0
