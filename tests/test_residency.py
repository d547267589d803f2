"""Tiered model residency (oni_ml_tpu/serving/residency.py + the
capacity-tiered stacks in serving/fleet.py): tier transitions under
admission-driven LRU/LFU paging, warm→hot promotion bit-identity,
cold-tier checkpoint round trips at preserved versions, capacity-tier
shape stability with compile-trace proof (one new program family per
power-of-two census crossing, zero retraces for churn within a tier,
plans-on AND plans-off), the eviction-storm isolation the acceptance
criteria name, the device-buffer-bound regression test for the
stack-rebuild path, bf16 stacked storage at its documented tolerance,
the Zipf load_gen mix + paged fleet SLO harness, bench_diff's paged
direction keys, and the residency journal/trace vocabulary.  All CPU,
no markers — tier-1.
"""

import gc
import json
import os
import threading
import time

import numpy as np
import pytest

from oni_ml_tpu import plans
from oni_ml_tpu.config import ServingConfig
from oni_ml_tpu.plans import KNOBS, NullStore, PlanStore, use_store
from oni_ml_tpu.runner.serve import _synthetic_day
from oni_ml_tpu.scoring import ScoringModel
from oni_ml_tpu.serving import (
    TIER_COLD,
    TIER_HOT,
    TIER_WARM,
    DnsEventFeaturizer,
    FleetRegistry,
    FleetScorer,
    MetricsEmitter,
    ModelRegistry,
    ResidencyManager,
    TenantSpec,
    load_spill,
    resolve_hot_capacity,
    score_features,
    spill_model,
)
from oni_ml_tpu.telemetry.spans import Recorder


@pytest.fixture(scope="module")
def days():
    """Six distinct synthetic DNS days (distinct seeds -> distinct
    models; same K -> one pack group) shared by the residency tests."""
    return {f"t{i}": _synthetic_day(seed=42 + i) for i in range(6)}


def _tiered_fleet(days, tenants, *, hot=2, warm=0, policy="lru",
                  spill_dir="", stack_precision="f32",
                  device_score_min=None, journal=None, recorder=None,
                  fleet_max_batch=64):
    """Capacity-tiered FleetRegistry + ResidencyManager + FleetScorer
    over `tenants` (everything starts host-warm; admissions fill the
    hot tier)."""
    rec = recorder or Recorder()
    fleet = FleetRegistry(journal=journal, recorder=rec,
                          capacity_tiers=True,
                          stack_precision=stack_precision)
    mgr = ResidencyManager(
        fleet, hot_capacity=hot, warm_capacity=warm, policy=policy,
        spill_dir=spill_dir, journal=journal, recorder=rec,
    )
    featurizers = {}
    for t in tenants:
        rows, model, cuts = days[t]
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"), hot=False)
        fleet.publish(t, model, source=f"day-{t}")
        mgr.register(t)
        featurizers[t] = DnsEventFeaturizer(cuts)
    cfg = ServingConfig(device_score_min=device_score_min,
                        fleet_max_batch=fleet_max_batch)
    metrics = MetricsEmitter(to_stdout=False, recorder=rec)
    scorer = FleetScorer(fleet, featurizers, cfg, metrics=metrics,
                         residency=mgr)
    mgr.set_pending_probe(lambda t: len(scorer._lanes[t].pending) > 0)
    return fleet, mgr, featurizers, metrics, scorer


def _score(scorer, days, tenant, n=8, timeout=30.0):
    futs = [scorer.submit(tenant, r) for r in days[tenant][0][:n]]
    scorer.flush()
    return np.array([f.result(timeout=timeout)[0] for f in futs]), \
        sorted({v for f in futs for v in [f.result(timeout)[1]]})


def _expected(days, featurizers, tenant, n=8):
    fz = featurizers[tenant]
    feats = fz([fz.validate(r) for r in days[tenant][0][:n]])
    return score_features(days[tenant][1], feats, "dns",
                          device_min=None)


# ---------------------------------------------------------------------------
# checkpoint round trips + registry unload/restore
# ---------------------------------------------------------------------------


def test_spill_round_trip_bit_identical(days, tmp_path):
    _, model, _ = days["t0"]
    path = str(tmp_path / "t0.npz")
    size = spill_model(path, model)
    assert size > 0 and os.path.exists(path)
    back = load_spill(path)
    np.testing.assert_array_equal(back.theta, model.theta)
    np.testing.assert_array_equal(back.p, model.p)
    assert back.ip_index == model.ip_index
    assert back.word_index == model.word_index


def test_registry_unload_restore_preserves_version(days):
    _, model, _ = days["t0"]
    reg = ModelRegistry()
    reg.publish(model, "day")
    reg.publish(model, "day2")
    assert reg.version == 2
    snap = reg.unload()
    assert snap.version == 2 and not reg.loaded
    with pytest.raises(RuntimeError, match="no model published"):
        reg.active()
    # Version rewind and double-restore both refuse.
    with pytest.raises(ValueError, match="restore version"):
        reg.restore(model, "ckpt", 1)
    reg.restore(model, "ckpt", 2)
    assert reg.loaded and reg.active().version == 2
    with pytest.raises(RuntimeError, match="unload first"):
        reg.restore(model, "ckpt", 2)


def test_unload_requires_eviction_first(days):
    fleet = FleetRegistry(capacity_tiers=True)
    _, model, _ = days["t0"]
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"))
    fleet.publish("t0", model, "day")
    with pytest.raises(RuntimeError, match="stack-resident"):
        fleet.unload_tenant("t0")
    fleet.set_hot("t0", False)
    snap = fleet.unload_tenant("t0")
    assert snap.version == 1


# ---------------------------------------------------------------------------
# tier transitions + promotion bit-identity
# ---------------------------------------------------------------------------


def test_full_tier_cycle_scores_bit_identical(days, tmp_path):
    """The tentpole invariant: a tenant scored after warm→hot (and
    after cold→warm→hot) promotion produces BIT-IDENTICAL results to
    one that was always hot — paging changes where the model lives,
    never its arithmetic."""
    tenants = tuple(f"t{i}" for i in range(6))
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, tenants, hot=2, warm=2, spill_dir=str(tmp_path))
    try:
        for t in tenants:                       # warm -> hot churn
            got, versions = _score(scorer, days, t)
            np.testing.assert_array_equal(
                got, _expected(days, featurizers, t))
            assert versions == [1]              # paging never bumps
        tiers = mgr.tiers()
        assert tiers[TIER_HOT] == 2
        assert tiers[TIER_COLD] >= 1            # warm bound forced spills
        # Round 2: every tenant has paged at least once by now; the
        # cold ones reload from their spill checkpoints.
        for t in tenants:
            got, versions = _score(scorer, days, t)
            np.testing.assert_array_equal(
                got, _expected(days, featurizers, t))
            assert versions == [1]
        stats = mgr.stats_snapshot()
        assert stats["promotions"] >= len(tenants)
        assert stats["evictions"] >= 1
        assert stats["cold_loads"] >= 1
        assert stats["failures"] == 0
        assert stats["promotion_stall_s"] >= 0
    finally:
        scorer.close()
        mgr.close()


def test_lru_vs_lfu_victim_selection(days):
    """LRU evicts the least recently admitted hot tenant; LFU evicts
    the least admitted overall."""
    for policy, expect_victim in (("lru", "t0"), ("lfu", "t1")):
        fleet, mgr, featurizers, _, scorer = _tiered_fleet(
            days, ("t0", "t1", "t2"), hot=2, policy=policy)
        try:
            # t0 touched 3x (oldest last touch), t1 touched once
            # (most recent of the two before t2 arrives).
            _score(scorer, days, "t0")
            _score(scorer, days, "t0")
            _score(scorer, days, "t0")
            _score(scorer, days, "t1")
            _score(scorer, days, "t2")   # forces one eviction
            assert mgr.tier_of("t2") == TIER_HOT
            assert mgr.tier_of(expect_victim) == TIER_WARM, policy
        finally:
            scorer.close()
            mgr.close()


def test_promotion_failure_is_tenant_scoped(days, tmp_path):
    """A cold tenant whose checkpoint vanished fails ITS futures with
    the promotion error; other tenants keep scoring."""
    tenants = ("t0", "t1", "t2")
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, tenants, hot=1, warm=1, spill_dir=str(tmp_path))
    try:
        _score(scorer, days, "t0")
        _score(scorer, days, "t1")
        _score(scorer, days, "t2")
        # The warm-capacity sweep runs async on the pager; wait for
        # t0's warm->cold demotion to land.
        deadline = time.monotonic() + 10.0
        while mgr.tier_of("t0") != TIER_COLD \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert mgr.tier_of("t0") == TIER_COLD
        for f in os.listdir(str(tmp_path)):
            os.remove(os.path.join(str(tmp_path), f))
        futs = [scorer.submit("t0", r) for r in days["t0"][0][:4]]
        scorer.flush()
        for f in futs:
            with pytest.raises(Exception):
                f.result(timeout=30.0)
        # The fleet survives: another tenant still scores correctly.
        got, _ = _score(scorer, days, "t2")
        np.testing.assert_array_equal(
            got, _expected(days, featurizers, "t2"))
        assert mgr.stats_snapshot()["failures"] >= 1
    finally:
        scorer.close()
        mgr.close()


def test_eviction_storm_isolation(days, tmp_path, monkeypatch):
    """The acceptance test: hot resident tenants' futures NEVER fail
    and their scores stay bit-identical while another tenant pages
    through a slow cold load — paging a tenant in must not stall or
    corrupt a resident one."""
    from oni_ml_tpu.serving import residency as residency_mod

    real_load = residency_mod.load_spill

    def slow_load(path):
        time.sleep(0.05)               # a deliberately slow checkpoint
        return real_load(path)

    monkeypatch.setattr(residency_mod, "load_spill", slow_load)
    tenants = ("t0", "t1", "t2")
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, tenants, hot=2, warm=0, spill_dir=str(tmp_path),
        fleet_max_batch=8)
    try:
        _score(scorer, days, "t0")
        _score(scorer, days, "t1")
        # Force t2 cold so its every promotion pays the slow load.
        fleet.set_hot("t2", False)
        fleet.unload_tenant("t2")
        spill_model(os.path.join(str(tmp_path), "t2.npz"),
                    days["t2"][1])
        with mgr._lock:
            st = mgr._state["t2"]
            st.tier = TIER_COLD
            st.spill_path = os.path.join(str(tmp_path), "t2.npz")
            st.cold_version = 1
            st.cold_source = "day-t2"
            mgr._refresh_drainable_locked()
        expected_t1 = _expected(days, featurizers, "t1")
        errors: list = []
        results: list = []
        stop = threading.Event()

        def resident_load():
            while not stop.is_set():
                futs = [scorer.submit("t1", r)
                        for r in days["t1"][0][:8]]
                scorer.flush()
                try:
                    results.append(np.array(
                        [f.result(timeout=30.0)[0] for f in futs]))
                except Exception as e:      # pragma: no cover
                    errors.append(e)

        th = threading.Thread(target=resident_load, daemon=True)
        th.start()

        def force_cold():
            """Push t2 back to checkpoint-cold for the next cycle
            (t0 refills the tier; eviction victim choice is policy's,
            so demote explicitly once t2 has left the stack)."""
            deadline = time.monotonic() + 15.0
            while mgr.tier_of("t2") == TIER_HOT \
                    and time.monotonic() < deadline:
                _score(scorer, days, "t0")
                time.sleep(0.01)
            if mgr.tier_of("t2") == TIER_WARM:
                fleet.unload_tenant("t2")
                with mgr._lock:
                    st = mgr._state["t2"]
                    st.tier = TIER_COLD
                    st.spill_path = os.path.join(
                        str(tmp_path), "t2.npz")
                    st.cold_version = 1
                    st.cold_source = "day-t2"
                    mgr._refresh_drainable_locked()

        # Page t2 in repeatedly while t1 scores: each cycle pays the
        # slow cold load on the pager thread.
        for _ in range(3):
            got, _ = _score(scorer, days, "t2", timeout=60.0)
            np.testing.assert_array_equal(
                got, _expected(days, featurizers, "t2"))
            force_cold()
        stop.set()
        th.join(timeout=30.0)
        assert not errors                  # zero failed resident futures
        assert len(results) >= 3
        for got in results:                # bit-identical throughout
            np.testing.assert_array_equal(got, expected_t1)
        assert mgr.stats_snapshot()["cold_loads"] >= 2
    finally:
        stop.set()
        scorer.close()
        mgr.close()


# ---------------------------------------------------------------------------
# capacity tiers: shape stability + zero retraces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plans_on", [True, False])
def test_capacity_tier_shape_stability_and_retraces(plans_on, tmp_path):
    """Census grows 3→4 (inside capacity 4: zero new programs) →5
    (crosses the power-of-two boundary: exactly one new program
    family), then promote/evict churn within the tier retraces
    NOTHING.  Device path pinned so every flush dispatches the
    compiled gather-dot; holds with the plan cache on and off.

    Each parametrization builds days with its OWN population sizes —
    distinct stacked shapes, so the second run cannot ride the first
    run's in-process jit cache and the trace deltas stay meaningful."""
    from oni_ml_tpu.plans import warmup as plans_warmup

    # Distinct populations per param (crossing different pow2 slot
    # boundaries), so the second run cannot ride the first run's
    # compiled programs.
    n_clients, n_doms = (9, 6) if plans_on else (20, 9)
    local_days = {
        f"t{i}": _synthetic_day(seed=42 + i, n_clients=n_clients,
                                n_doms=n_doms)
        for i in range(8)
    }
    store = (PlanStore(str(tmp_path / "plans.jsonl"))
             if plans_on else NullStore())
    with use_store(store):
        tenants = tuple(f"t{i}" for i in range(8))
        fleet, mgr, featurizers, _, scorer = _tiered_fleet(
            local_days, tenants, hot=5, warm=0, device_score_min=1)
        try:
            # The trace counters are monitoring events off the
            # persistent compilation cache — wire it (hermetic dir via
            # conftest's JAX_COMPILATION_CACHE_DIR) so they count.
            plans_warmup.setup_compilation_cache()
            plans_warmup._ensure_listener()
            for t in tenants[:3]:
                _score(scorer, local_days, t)
            k = local_days["t0"][1].num_topics
            assert fleet.tier(k)["capacity"] == 4   # pow2 ceiling of 3
            shape3 = fleet.stack_for("t0").model.theta.shape
            # Assert on compile REQUESTS: an in-memory jit hit makes
            # no request at all, so a zero delta is immune to whatever
            # the persistent disk cache happens to hold.
            base = plans_warmup.compile_counts()["compile_requests"]
            # 3 -> 4: same tier, same shape, ZERO new programs.
            _score(scorer, local_days, tenants[3])
            assert fleet.stack_for("t0").model.theta.shape == shape3
            c4 = plans_warmup.compile_counts()["compile_requests"]
            assert c4 - base == 0
            # 4 -> 5: crosses the boundary -> capacity 8: the stacked
            # shape changes exactly ONCE, minting one new program
            # family (the gather-dot program plus its per-shape weight
            # uploads — a handful of traces from the single shape
            # change, never per-tenant).
            _score(scorer, local_days, tenants[4])
            assert fleet.tier(k)["capacity"] == 8
            shape5 = fleet.stack_for("t0").model.theta.shape
            assert shape5 != shape3
            c5 = plans_warmup.compile_counts()["compile_requests"]
            assert 1 <= c5 - c4 <= 3, (c4, c5)
            # Churn within the tier: the hot capacity is 5, so every
            # further promotion EVICTS a policy victim — census stays
            # 5, the capacity tier stays 8, and the shape (and with it
            # the compiled family, keyed by capacity, not by which
            # tenants are resident) never changes: zero retraces,
            # exactly, across the whole promote/evict storm.
            for t in (tenants[5], tenants[6], tenants[7], tenants[0],
                      tenants[2], tenants[4], tenants[6], tenants[1]):
                _score(scorer, local_days, t)   # promotes, evicting
                assert fleet.stack_for(t).model.theta.shape == shape5
            assert mgr.stats_snapshot()["evictions"] >= 5
            c_churn = plans_warmup.compile_counts()["compile_requests"]
            assert c_churn - c5 == 0
        finally:
            scorer.close()
            mgr.close()


def test_capacity_padding_never_changes_scores(days):
    """Pad rows are dead weight by construction: the padded stack's
    packed scores equal the unpadded fleet's bit-for-bit."""
    plain = FleetRegistry()
    tiered = FleetRegistry(capacity_tiers=True)
    for reg in (plain, tiered):
        for t in ("t0", "t1", "t2"):
            reg.add_tenant(TenantSpec(tenant=t, dsource="dns"))
            reg.publish(t, days[t][1], t)
    s_plain = plain.stack_for("t0")
    s_tiered = tiered.stack_for("t0")
    assert s_tiered.model.theta.shape[0] > s_plain.model.theta.shape[0]
    assert s_tiered.capacity == 4
    for t in ("t0", "t1", "t2"):
        m = days[t][1]
        i0, w0 = s_tiered.ip_base[t], s_tiered.word_base[t]
        np.testing.assert_array_equal(
            s_tiered.model.theta[i0:i0 + m.theta.shape[0]], m.theta)
        np.testing.assert_array_equal(
            s_tiered.model.p[w0:w0 + m.p.shape[0]], m.p)


# ---------------------------------------------------------------------------
# device-buffer bound across a promote/evict storm (stack-rebuild audit)
# ---------------------------------------------------------------------------


def test_device_buffer_count_bounded_across_storm(days):
    """The stack-rebuild audit's regression pin: old stacked device
    buffers must become collectible after every swap — a storm of
    promote/evict cycles (each rebuilding the stack and re-uploading
    it on first device dispatch) must not grow the live device-buffer
    census."""
    import jax

    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, ("t0", "t1", "t2"), hot=2, device_score_min=1)
    try:
        first: dict = {}
        for t in ("t0", "t1", "t2"):     # settle: all shapes compiled
            first[t], _ = _score(scorer, days, t)
        gc.collect()
        baseline = len(jax.live_arrays())
        for i in range(12):              # the storm
            t = f"t{i % 3}"
            got, _ = _score(scorer, days, t)
            # Same (device f32) path before and after paging: the
            # promoted tenant's scores stay BIT-identical through the
            # whole storm.
            np.testing.assert_array_equal(got, first[t])
        gc.collect()
        after = len(jax.live_arrays())
        # Exactly one stack (2 device arrays) may be live per K-group
        # plus transient slack; 12 rebuild cycles must NOT have pinned
        # 12 retired stacks (24+ arrays).
        assert after <= baseline + 8, (baseline, after)
    finally:
        scorer.close()
        mgr.close()


# ---------------------------------------------------------------------------
# bf16 stacked storage
# ---------------------------------------------------------------------------


def test_bf16_stack_halves_device_bytes_and_meets_tolerance(days):
    """ServingConfig.stack_precision="bf16": the stacked snapshot's
    device cache stores bfloat16 (half the HBM bytes -> double the
    hot-tier residency per byte), accumulation stays f32, and packed
    scores agree with the f32 stack within the DOCUMENTED tolerance
    (2^-7 relative — bf16's 8 significand bits through a K-term dot).
    The host f64 path is untouched."""
    import jax.numpy as jnp

    from oni_ml_tpu.scoring.score import _device_model

    got = {}
    for precision in ("f32", "bf16"):
        fleet, mgr, featurizers, _, scorer = _tiered_fleet(
            days, ("t0", "t1"), hot=2, stack_precision=precision,
            device_score_min=1)
        try:
            got[precision], _ = _score(scorer, days, "t0", n=16)
            stack = fleet.stack_for("t0")
            theta_dev, p_dev = _device_model(stack.model)
            want = (jnp.bfloat16 if precision == "bf16"
                    else jnp.float32)
            assert theta_dev.dtype == want and p_dev.dtype == want
        finally:
            scorer.close()
            mgr.close()
    f32, bf16 = got["f32"], got["bf16"]
    assert np.all(np.isfinite(bf16))
    rel = np.abs(bf16 - f32) / np.maximum(np.abs(f32), 1e-300)
    assert rel.max() <= 2 ** -7, rel.max()
    # And the host path (device_min=None) ignores the marker entirely:
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, ("t0",), hot=1, stack_precision="bf16",
        device_score_min=None)
    try:
        host, _ = _score(scorer, days, "t0", n=16)
        np.testing.assert_array_equal(
            host, _expected(days, featurizers, "t0", n=16))
    finally:
        scorer.close()
        mgr.close()


def test_stack_precision_validation():
    with pytest.raises(ValueError, match="stack_precision"):
        FleetRegistry(stack_precision="f16")


# ---------------------------------------------------------------------------
# plans resolution
# ---------------------------------------------------------------------------


def test_resolve_hot_capacity_precedence(tmp_path):
    cfg_off = ServingConfig()
    cfg_on = ServingConfig(fleet_hot_tenants=5)
    with use_store(NullStore()):
        assert resolve_hot_capacity(cfg_off) == (0, "default")
        assert resolve_hot_capacity(cfg_on) == (5, "config")
    st = PlanStore(str(tmp_path / "plans.jsonl"))
    fp = plans.fingerprint(KNOBS["fleet_hot_tenants"].scope)
    st.record("fleet_hot_tenants", fp, "*", 16, source="probe")
    with use_store(st):
        assert resolve_hot_capacity(cfg_off) == (16, "plan")
        assert resolve_hot_capacity(cfg_on) == (5, "config")


def test_residency_manager_validation(days):
    fleet = FleetRegistry(capacity_tiers=True)
    with pytest.raises(ValueError, match="policy"):
        ResidencyManager(fleet, hot_capacity=2, policy="fifo")
    with pytest.raises(ValueError, match="capacities"):
        ResidencyManager(fleet, hot_capacity=-1)
    mgr = ResidencyManager(fleet, hot_capacity=2)
    try:
        fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"),
                         hot=False)
        fleet.publish("t0", days["t0"][1], "d")
        mgr.register("t0")
        with pytest.raises(ValueError, match="already registered"):
            mgr.register("t0")
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# journal vocabulary + trace lanes
# ---------------------------------------------------------------------------


def test_residency_journal_records_and_trace_lanes(days, tmp_path):
    from oni_ml_tpu.telemetry import Journal

    jpath = str(tmp_path / "residency.jsonl")
    journal = Journal(jpath)
    rec = Recorder()
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, ("t0", "t1", "t2"), hot=1, warm=1,
        spill_dir=str(tmp_path / "spill"), journal=journal,
        recorder=rec)
    try:
        for t in ("t0", "t1", "t2", "t0"):
            _score(scorer, days, t)
    finally:
        scorer.close()
        mgr.close()
        journal.close()
    records = list(Journal.replay(jpath))
    promotes = [r for r in records
                if r["kind"] == "residency_promote" and r.get("ok")]
    evicts = [r for r in records if r["kind"] == "residency_evict"]
    assert promotes and evicts
    hot_legs = [r for r in promotes if r.get("tier_from") in
                (TIER_WARM, TIER_COLD) and "stall_s" in r]
    assert hot_legs
    for r in hot_legs:
        assert r["stall_s"] >= 0 and r["capacity"] >= r["census"]
    assert any(r.get("tier_to") == TIER_COLD and
               isinstance(r.get("spill_bytes"), int) for r in evicts)
    # Occupancy gauges live on the recorder.
    assert "residency.hot" in rec.gauges
    assert rec.gauges["residency.hot"] <= 1
    assert rec.counters["residency.promotions"].value >= 4
    # trace_view renders lanes + the per-tenant paging table.
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import trace_view

    trace = trace_view.journal_to_trace(records)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "residency hot occupancy" in names
    assert any(n.startswith("residency evict ->") for n in names)
    table = trace_view.residency_table(records)
    assert {r["tenant"] for r in table} == {"t0", "t1", "t2"}
    assert sum(r["promotions"] for r in table) >= 4
    # Journal kinds are in the committed schema (the lint gate pins
    # the full contract; this is the fast tier-1 cross-check).
    schema_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "oni_ml_tpu", "analysis", "schema", "journal_schema.json")
    with open(schema_path) as f:
        schema = json.load(f)
    assert "residency_promote" in schema["kinds"]
    assert "residency_evict" in schema["kinds"]


# ---------------------------------------------------------------------------
# load_gen: zipf mix + the paged fleet SLO harness
# ---------------------------------------------------------------------------


def _load_gen():
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import load_gen

    return load_gen


def test_fleet_mix_zipf():
    lg = _load_gen()
    mix = lg.fleet_mix(4, "poisson:1", 1000.0, zipf_s=1.0)
    weights = [tm["weight"] for tm in mix]
    assert weights == pytest.approx([1.0, 0.5, 1 / 3, 0.25])
    rates = [tm["rate_eps"] for tm in mix]
    assert sum(rates) == pytest.approx(1000.0)
    assert rates[0] > rates[-1]
    with pytest.raises(ValueError, match="zipf_s"):
        lg.fleet_mix(4, "poisson:1", 1000.0, zipf_s=-1)
    # zipf off keeps the cycled mix weights.
    plain = lg.fleet_mix(4, "poisson:3,bursty:1", 1000.0)
    assert [tm["weight"] for tm in plain] == [3.0, 1.0, 3.0, 1.0]


def test_run_fleet_slo_paged_small():
    """The serving_slo_fleet_paged harness at toy scale: working set
    (12 tenants) exceeds the hot capacity (3), all three tiers
    populated, per-tenant latency includes promotion misses, zero
    post-warmup retraces, and the payload carries the residency
    ledger + the truncation-honest tenant summary."""
    lg = _load_gen()
    res = lg.run_fleet_slo(
        12, "poisson:1,bursty:1", n_events=360, rate_eps=3000.0,
        zipf_s=1.1, hot_tenants=3, warm_tenants=4,
        device_score_min=None, timeout_s=60.0, per_tenant_detail=4,
    )
    agg = res["aggregate"]
    assert agg["errors"] == 0
    assert agg["resolved"] == res["n_events"]
    assert agg["p99_ms"] is not None
    resd = res["residency"]
    assert resd["hot_capacity"] == 3
    assert resd["tiers"][TIER_HOT] <= 3
    assert resd["tiers"][TIER_COLD] >= 1
    assert resd["promotions"] >= 3
    assert resd["failures"] == 0
    assert resd["promotion_stall_s"] >= 0
    assert res["plans"]["retraces_after_warmup"] == 0
    assert res["zipf_s"] == 1.1
    assert res["tenants_truncated"] is True
    assert len(res["tenants"]) == 4
    summary = res["tenant_summary"]
    assert summary["p99_ms"]["max"] >= summary["p99_ms"]["min"]
    # Zipf head got more events than the tail.
    events = [v["events"] for v in res["tenants"].values()]
    assert events[0] >= events[-1]


def test_bench_diff_paged_directions(tmp_path):
    """serving_slo_fleet_paged gates like the other serving phases
    (per-group directions) PLUS the residency promotion stall
    (lower-better)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import bench_diff

    def payload(p99, stall, eps=2000.0):
        return {
            "metric": "m", "value": 1.0, "unit": "x",
            "secondary": {"serving_slo_fleet_paged": {
                "value": eps, "unit": "events/sec",
                "aggregate": {"sustained_eps": eps, "p50_ms": 50.0,
                              "p99_ms": p99, "p999_ms": p99 * 1.1},
                "residency": {"promotion_stall_s": stall,
                              "promotions": 350},
            }},
        }

    a = str(tmp_path / "a.json")
    with open(a, "w") as f:
        json.dump(payload(1000.0, 200.0), f)
    # p99 blowup -> regression.
    b = str(tmp_path / "b.json")
    with open(b, "w") as f:
        json.dump(payload(2000.0, 200.0), f)
    rows = bench_diff.diff_payloads(
        bench_diff.load_payload(a), bench_diff.load_payload(b))
    reg = [r["name"] for r in rows if r["regression"]]
    assert ("phase:serving_slo_fleet_paged:aggregate.p99_ms" in reg)
    # Promotion stall blowup -> regression; stall DROP is not.
    c = str(tmp_path / "c.json")
    with open(c, "w") as f:
        json.dump(payload(1000.0, 400.0), f)
    rows = bench_diff.diff_payloads(
        bench_diff.load_payload(a), bench_diff.load_payload(c))
    reg = [r["name"] for r in rows if r["regression"]]
    assert reg == [
        "phase:serving_slo_fleet_paged:residency.promotion_stall_s"]
    d = str(tmp_path / "d.json")
    with open(d, "w") as f:
        json.dump(payload(1000.0, 50.0), f)
    rows = bench_diff.diff_payloads(
        bench_diff.load_payload(a), bench_diff.load_payload(d))
    assert not [r for r in rows if r["regression"]]


def test_legacy_fleet_unaffected_without_residency(days):
    """A FleetScorer with no residency manager keeps the exact PR 10
    behavior: every published tenant is stack-resident, no capacity
    padding, solo fallback never engages."""
    fleet = FleetRegistry()
    featurizers = {}
    for t in ("t0", "t1"):
        fleet.add_tenant(TenantSpec(tenant=t, dsource="dns"))
        fleet.publish(t, days[t][1], t)
        featurizers[t] = DnsEventFeaturizer(days[t][2])
    stack = fleet.stack_for("t0")
    assert stack.capacity == 0
    assert stack.model.theta.shape[0] == sum(
        days[t][1].theta.shape[0] for t in ("t0", "t1"))
    metrics = MetricsEmitter(to_stdout=False)
    scorer = FleetScorer(fleet, featurizers,
                         ServingConfig(device_score_min=None),
                         metrics=metrics)
    try:
        futs = [scorer.submit("t0", r) for r in days["t0"][0][:8]]
        scorer.flush()
        got = np.array([f.result(timeout=30.0)[0] for f in futs])
        np.testing.assert_array_equal(
            got, _expected(days, featurizers, "t0"))
        solo = [r for r in metrics.records
                if isinstance(r.get("stack_version"), type(None))
                and "tenant" in r and "events" in r]
        assert not solo
    finally:
        scorer.close()


# ---------------------------------------------------------------------------
# CLI: paged fleet serve over real day directories
# ---------------------------------------------------------------------------


def test_paged_fleet_live_stream_from_manifest(tmp_path, capsys):
    """`ml_ops serve --fleet m.json --hot-tenants 1 --warm-tenants 1`
    end to end: 3 day-dir tenants through ONE hot slot, so serving the
    tagged stream forces warm→hot promotions and (with the warm tier
    bounded) day-dir cold reloads — every event still scores
    exactly-once at version 1, and the stream_end record carries the
    residency ledger."""
    import pickle

    from oni_ml_tpu.features.dns import featurize_dns
    from oni_ml_tpu.io import formats
    from oni_ml_tpu.runner import ml_ops

    def write_day(path, rows, model):
        os.makedirs(path, exist_ok=True)
        ips = sorted(model.ip_index, key=model.ip_index.get)
        vocab = sorted(model.word_index, key=model.word_index.get)
        formats.write_doc_results(
            os.path.join(path, "doc_results.csv"), ips,
            model.theta[:-1])
        formats.write_word_results(
            os.path.join(path, "word_results.csv"), vocab,
            np.log(np.asarray(model.p[:-1], np.float64)).T)
        feats = featurize_dns(rows)
        with open(os.path.join(path, "features.pkl"), "wb") as f:
            pickle.dump(feats, f)

    manifest = {"tenants": []}
    input_lines = []
    for i, t in enumerate(("alpha", "beta", "gamma")):
        rows, model, _ = _synthetic_day(seed=70 + i)
        day = str(tmp_path / t)
        write_day(day, rows, model)
        manifest["tenants"].append(
            {"tenant": t, "day_dir": day, "dsource": "dns"})
        # Two visits per tenant, interleaved: the second visit pages
        # the tenant back IN after later tenants evicted it.
        input_lines.append([f"{t}\t" + ",".join(r) for r in rows[:12]])
    mpath = str(tmp_path / "fleet.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    # The stream arrives through a FIFO, one tenant's visit at a time
    # with a pause after it: a LIVE stream.  Read from a file, all 36
    # events are admitted within a millisecond and the stream closes
    # before the pager thread has run; close() then resolves the
    # still-paging tenants through the solo fallback and the ledger
    # shows one promotion where it should show one per visit (seen on a
    # loaded machine: the suite's other workers starve the pager).
    import threading
    import time

    ipath = str(tmp_path / "events.fifo")
    os.mkfifo(ipath)

    def feed():
        with open(ipath, "w") as f:
            for visit in range(2):
                for lines in input_lines:
                    f.write("\n".join(lines[visit * 6:(visit + 1) * 6]))
                    f.write("\n")
                    f.flush()
                    time.sleep(0.25)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    rc = ml_ops.main([
        "serve", "--fleet", mpath, "--input", ipath, "--no-plans",
        "--no-compilation-cache", "--device-score-min", "0",
        "--max-batch", "6", "--hot-tenants", "1",
        "--warm-tenants", "1",
    ])
    feeder.join(timeout=30)
    assert not feeder.is_alive()
    out = capsys.readouterr().out
    assert rc == 0
    end = next(json.loads(ln) for ln in out.splitlines()
               if '"stream_end"' in ln)
    assert end["submitted"] == 36
    assert end["events_scored"] == 36
    per_tenant = {s["tenant"]: s for s in end["tenant_stats"]}
    assert all(per_tenant[t]["scored"] == 12
               for t in ("alpha", "beta", "gamma"))
    # Paging never bumped a version.
    assert end["final_versions"] == {
        "alpha": 1, "beta": 1, "gamma": 1}
    resd = end["residency"]
    assert resd["hot_capacity"] == 1
    assert resd["promotions"] >= 3
    assert resd["failures"] == 0
    # The warm bound forced at least one tenant through checkpoint-
    # cold, reloaded from its DAY DIR (no spill: day_source set).
    assert resd["cold_loads"] >= 1
    assert resd["tiers"][TIER_HOT] == 1
    # The plans record names the resolved residency capacity.
    plans_rec = next(json.loads(ln) for ln in out.splitlines()
                     if '"event": "plans"' in ln)
    assert plans_rec["knobs"]["hot_tenants"]["value"] == 1
    assert plans_rec["knobs"]["hot_tenants"]["source"] == "config"


# ---------------------------------------------------------------------------
# review regressions: refresh-vs-cold staleness, publish-while-cold,
# never-published warm victims, unmanaged tenants
# ---------------------------------------------------------------------------


def _force_tenant_cold(fleet, mgr, tenant):
    """Drive one warm tenant to checkpoint-cold through the manager's
    own demotion path."""
    assert mgr.tier_of(tenant) == TIER_WARM
    mgr._demote_cold(tenant)
    assert mgr.tier_of(tenant) == TIER_COLD


def test_refreshed_model_survives_cold_demotion(days, tmp_path):
    """A day-dir tenant republished (refresh) and then paged cold must
    come back as the REFRESHED model at the refreshed version — never
    the stale day artifacts under the new version number (the silent
    wrong-score mode the review caught)."""
    from test_residency import _tiered_fleet  # self-import for clarity

    tenants = ("t0", "t1")
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, tenants, hot=1, warm=4, spill_dir=str(tmp_path))
    try:
        # Re-register t0 as a day-dir tenant at its published version.
        with mgr._lock:
            st = mgr._state["t0"]
            st.day_source = (str(tmp_path / "nonexistent_day"), 0.1)
            st.day_version = fleet.version("t0")
        _score(scorer, days, "t0")
        # Refresh publish: version 2, different values.
        refreshed = ScoringModel(
            ip_index=days["t0"][1].ip_index,
            theta=days["t0"][1].theta.copy(),
            word_index=days["t0"][1].word_index,
            p=days["t0"][1].p.copy(),
        )
        rng = np.random.default_rng(9)
        refreshed.theta = refreshed.theta * rng.uniform(
            0.5, 1.5, refreshed.theta.shape)
        refreshed.theta[:-1] /= refreshed.theta[:-1].sum(
            1, keepdims=True)
        fleet.publish("t0", refreshed, "refresh")
        expected = None
        # Evict t0 (t1 takes the slot), then demote it cold: because
        # version 2 != day_version 1, the LIVE model must spill — the
        # stale day dir (which here doesn't even exist) is not
        # consulted.
        _score(scorer, days, "t1")
        assert mgr.tier_of("t0") == TIER_WARM
        fz = featurizers["t0"]
        feats = fz([fz.validate(r) for r in days["t0"][0][:8]])
        expected = score_features(refreshed, feats, "dns",
                                  device_min=None)
        _force_tenant_cold(fleet, mgr, "t0")
        with mgr._lock:
            assert mgr._state["t0"].cold_spilled is True
        got, versions = _score(scorer, days, "t0")
        np.testing.assert_array_equal(got, expected)
        assert versions == [2]
        assert mgr.stats_snapshot()["failures"] == 0
    finally:
        scorer.close()
        mgr.close()


def test_publish_while_cold_is_adopted(days, tmp_path):
    """A RefreshLoop publish landing while the tenant is checkpoint-
    cold must not block promotion: the pager adopts the newer
    published model instead of restoring over it."""
    tenants = ("t0", "t1")
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, tenants, hot=1, warm=4, spill_dir=str(tmp_path))
    try:
        _score(scorer, days, "t0")
        _score(scorer, days, "t1")          # t0 -> warm
        _force_tenant_cold(fleet, mgr, "t0")
        # Publish while cold (registry version bumps, model loaded).
        fleet.publish("t0", days["t0"][1], "refresh-while-cold")
        got, versions = _score(scorer, days, "t0")
        np.testing.assert_array_equal(
            got, _expected(days, featurizers, "t0"))
        assert versions == [2]
        assert mgr.tier_of("t0") == TIER_HOT
        assert mgr.stats_snapshot()["failures"] == 0
    finally:
        scorer.close()
        mgr.close()


def test_never_published_warm_tenant_does_not_stall_pager(days):
    """A registered-but-never-published tenant over the warm bound has
    nothing to unload: the enforcement sweep must skip it and return
    (the review caught an infinite pager spin here), and the pager
    stays live for real promotions."""
    fleet, mgr, featurizers, _, scorer = _tiered_fleet(
        days, ("t0", "t1"), hot=1, warm=1)
    try:
        fleet.add_tenant(TenantSpec(tenant="ghost", dsource="dns"),
                         hot=False)
        mgr.register("ghost")               # never published
        mgr._post_enforce()                 # would previously spin
        time.sleep(0.1)
        # Pager still processes promotions afterwards.
        got, _ = _score(scorer, days, "t0")
        np.testing.assert_array_equal(
            got, _expected(days, featurizers, "t0"))
        assert mgr.tier_of("ghost") == TIER_WARM
        assert mgr._pager.is_alive()
    finally:
        scorer.close()
        mgr.close()


def test_unmanaged_tenant_drains_promptly_with_residency(days):
    """A fleet tenant never registered with the residency manager
    keeps legacy always-drainable behavior — its events must resolve
    without waiting for shutdown."""
    rec = Recorder()
    fleet = FleetRegistry(recorder=rec, capacity_tiers=True)
    mgr = ResidencyManager(fleet, hot_capacity=1, recorder=rec)
    featurizers = {}
    # t0 managed (starts warm), t1 unmanaged and stack-resident.
    fleet.add_tenant(TenantSpec(tenant="t0", dsource="dns"), hot=False)
    fleet.publish("t0", days["t0"][1], "t0")
    mgr.register("t0")
    featurizers["t0"] = DnsEventFeaturizer(days["t0"][2])
    fleet.add_tenant(TenantSpec(tenant="t1", dsource="dns"))
    fleet.publish("t1", days["t1"][1], "t1")
    featurizers["t1"] = DnsEventFeaturizer(days["t1"][2])
    metrics = MetricsEmitter(to_stdout=False, recorder=rec)
    scorer = FleetScorer(fleet, featurizers,
                         ServingConfig(device_score_min=None),
                         metrics=metrics, residency=mgr)
    try:
        futs = [scorer.submit("t1", r) for r in days["t1"][0][:8]]
        scorer.flush()
        got = np.array([f.result(timeout=5.0)[0] for f in futs])
        fz = featurizers["t1"]
        feats = fz([fz.validate(r) for r in days["t1"][0][:8]])
        np.testing.assert_array_equal(
            got, score_features(days["t1"][1], feats, "dns",
                                device_min=None))
    finally:
        scorer.close()
        mgr.close()
