"""Smoke tests for the driver-facing bench entry points.

bench.py is the round gate the driver runs on real hardware; these
protect it from API drift (it imports deep into fused/dense/scoring).
On the CPU test backend the dense gate is off, so bench_em exercises
the sparse fused path; shapes are tiny to keep compiles cheap.
"""

import json

import numpy as np
import pytest


def test_bench_em_sparse_smoke():
    import bench

    em = bench.bench_em(4, 128, 32, 16, chunk=2, rounds=1, var_max_iters=3)
    assert np.isfinite(em["docs_per_sec"]) and em["docs_per_sec"] > 0
    assert em["t_iter"] > 0
    assert em["use_dense"] is False  # CPU backend: dense gate needs TPU
    assert em["wmajor"] is False
    assert em["corpus_itemsize"] == 4  # sparse: no dense corpus stored
    assert 0 < em["mean_vi"] <= 3


def test_bench_em_compact_smoke():
    """phase_config4's engine path at toy scale: with the full-V dense
    gate off (CPU), compact=True must route through the compact-vocab
    dense engine (plan_compact + compact_stack_batches) and report its
    width/unique-word evidence fields."""
    import bench

    em = bench.bench_em(4, 4096, 32, 16, chunk=2, rounds=1,
                        var_max_iters=3, compact=True,
                        word_law="loguniform")
    assert np.isfinite(em["docs_per_sec"]) and em["docs_per_sec"] > 0
    assert em["use_dense"] is True           # compact engine IS dense
    assert em["engine_variant"] == "compact"
    # log-uniform draw over [1, 4096) from 32*16 tokens: far fewer
    # uniques than V, padded up to the compact width
    assert 0 < em["unique_words"] <= 32 * 16
    assert em["unique_words"] <= em["compact_width"] < 4096


def test_bench_flow_day_matches_schema(tmp_path):
    """The synthetic flow day must align with FLOW_COLUMNS — an earlier
    version carried an extra leading column, so the featurizer read
    sip='0.0' and a dip string as the port for EVERY row, collapsing
    the benched vocabulary to one port bucket."""
    import io

    import bench
    from oni_ml_tpu.features.flow import FLOW_COLUMNS, NUM_FLOW_COLUMNS
    from oni_ml_tpu.features.native_flow import featurize_flow_file

    buf = io.StringIO()
    bench._write_flow_day(buf, 500, n_src=50, n_dst=20)
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 500
    cols = lines[0].split(",")
    assert len(cols) == NUM_FLOW_COLUMNS
    assert cols[FLOW_COLUMNS["sip"]].startswith("10.0.")
    assert cols[FLOW_COLUMNS["dip"]].startswith("10.1.")
    assert int(cols[FLOW_COLUMNS["dport"]]) in (80, 443, 22, 53, 8080, 25)
    assert 0 <= int(cols[FLOW_COLUMNS["hour"]]) < 24
    p = tmp_path / "day.csv"
    p.write_text(buf.getvalue())
    feats = featurize_flow_file(str(p))
    if not hasattr(feats, "ip_table"):     # pure-Python fallback (no g++)
        import pytest

        pytest.skip("native featurizer unavailable")
    ips = set(feats.ip_table)
    assert "0.0" not in ips
    # Both endpoints present as documents; multiple port buckets.
    assert any(ip.startswith("10.0.") for ip in ips)
    assert any(ip.startswith("10.1.") for ip in ips)
    words = {feats.word_table[w] for w in feats.sw_id[:feats.num_raw_events]}
    ports = {w.split("_")[0] for w in words}
    assert len(ports) > 1 and "111111.0" not in ports


def test_bench_flow_day_realistic_cardinality():
    """Power-law mode (config-3 at-spec tooling): IPs draw from a
    rank^-a population over a 3-octet address space (src 10.* / dst
    11.*, disjoint), service ports widen beyond the fixed 6-service
    mix — and the DEFAULT byte stream is untouched (the round-1..4
    phases must stay comparable)."""
    import io

    import bench

    buf = io.StringIO()
    bench._write_flow_day(buf, 20_000, n_src=300_000, n_dst=150_000,
                          seed=5, ip_zipf_a=1.2, n_svc_ports=48)
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 20_000
    sips = {ln.split(",")[8] for ln in lines}
    dips = {ln.split(",")[9] for ln in lines}
    # Long tail realized: thousands of distinct hosts from 20k events.
    assert len(sips) > 3_000 and len(dips) > 1_500
    assert all(s.startswith("10.") for s in sips)
    assert all(d.startswith("11.") for d in dips)
    dports = {int(ln.split(",")[11]) for ln in lines}
    assert len(dports) >= 40 and max(dports) <= 1024
    # Hot-host skew: the most active host sees far more than uniform.
    from collections import Counter

    top = Counter(ln.split(",")[8] for ln in lines).most_common(1)[0][1]
    assert top > 20_000 // 48

    # Default mode: byte-wise identical schema/space as before.
    buf2 = io.StringIO()
    bench._write_flow_day(buf2, 1_000, seed=5)
    l2 = buf2.getvalue().strip().splitlines()
    assert {int(ln.split(",")[11]) for ln in l2} <= {80, 443, 22, 53,
                                                     8080, 25}
    assert all(ln.split(",")[8].startswith("10.0.") for ln in l2)
    assert all(ln.split(",")[9].startswith("10.1.") for ln in l2)

    # The service mix is FIXED across day seeds (real traffic keeps the
    # same services day over day): drawing it from the per-day rng gave
    # every day a fresh port subset, and a 30-day corpus realized ~770
    # distinct ports — a 16x vocabulary inflation artifact.
    bufA, bufB = io.StringIO(), io.StringIO()
    bench._write_flow_day(bufA, 500, seed=7, ip_zipf_a=1.2,
                          n_svc_ports=12)
    bench._write_flow_day(bufB, 500, seed=8, ip_zipf_a=1.2,
                          n_svc_ports=12)
    pa = {int(ln.split(",")[11])
          for ln in bufA.getvalue().strip().splitlines()}
    pb = {int(ln.split(",")[11])
          for ln in bufB.getvalue().strip().splitlines()}
    assert len(pa | pb) <= 12

    # Uniform mode with a >65536 population must use the wide encoding
    # too — the 2-octet form would silently emit non-IP strings like
    # 10.0.1367.44 (round-5 review finding).
    buf3 = io.StringIO()
    bench._write_flow_day(buf3, 2_000, n_src=200_000, n_dst=1_000,
                          seed=5)
    for ln in buf3.getvalue().strip().splitlines():
        for col in (8, 9):
            octets = ln.split(",")[col].split(".")
            assert len(octets) == 4
            assert all(0 <= int(o) <= 255 for o in octets)


def test_bench_em_stacked_batches_smoke():
    """n_batches stacks day-scale resident batches through the chunk
    runner's scan (tpu_probes batch_amort): docs/s must account for
    every stacked document and the run must stay finite.  n_batches=1
    keeps the legacy single-batch shape (drawn from the same rng
    stream) so prior-round phase numbers stay comparable."""
    import bench

    em1 = bench.bench_em(4, 256, 32, 16, chunk=2, rounds=1,
                         force_sparse=True)
    em3 = bench.bench_em(4, 256, 32, 16, chunk=2, rounds=1,
                         force_sparse=True, n_batches=3)
    for em in (em1, em3):
        assert np.isfinite(em["docs_per_sec"]) and em["docs_per_sec"] > 0
    # Same wall-clock basis: docs_per_sec = total docs / t_iter
    # (compare via the identical division — a multiply round-trip is
    # off by an ulp for ~1 in 7 timing values).
    assert em1["docs_per_sec"] == 32 / em1["t_iter"]
    assert em3["docs_per_sec"] == 96 / em3["t_iter"]


def test_bench_dns_scoring_smoke():
    import bench

    eps, p50 = bench.bench_dns_scoring(n_events=2000, reps=1)
    assert np.isfinite(eps) and eps > 0
    assert p50 > 0


def test_bench_pipeline_e2e_smoke():
    import bench

    total, stages, eps, pre, critical = bench.bench_pipeline_e2e(
        n_events=3000, n_src=50, n_dst=30, em_max_iters=3
    )
    assert total > 0 and eps > 0
    assert set(stages) == {"pre", "corpus", "lda", "score"}
    # The critical-path breakdown: per-stage walls (inline + that
    # stage's background tasks), the serial-equivalent sum, the
    # overlapped e2e wall, and the headline overlap_efficiency.
    assert set(critical["stage_wall_s"]) == {"pre", "corpus", "lda",
                                             "score"}
    assert critical["sum_of_stage_walls_s"] > 0
    assert critical["e2e_wall_s"] > 0
    assert critical["overlap_efficiency"] is not None
    assert "edges" in critical  # dataplane ran: per-edge stall stats
    # The pre record carries the parallel-featurization payload: the
    # resolved worker count, per-pass walls, the handoff mode, and (on
    # a multi-core host) the sequential comparison.
    assert pre["pre_workers"] >= 1
    assert pre["handoff"] == "direct"
    assert isinstance(pre["wall"], dict)
    if pre["pre_workers"] > 1:
        assert pre["pre_s_workers1"] > 0
    total, stages, eps, pre, _ = bench.bench_pipeline_e2e(
        n_events=2000, n_src=40, em_max_iters=3, dsource="dns",
        compare_pre_workers1=False,
    )
    assert total > 0 and eps > 0
    assert set(stages) == {"pre", "corpus", "lda", "score"}
    assert "pre_s_workers1" not in pre


def test_bench_flow_scoring_smoke():
    import bench

    eps, p50 = bench.bench_flow_scoring(n_events=2000, reps=1)
    assert np.isfinite(eps) and eps > 0
    assert p50 > 0


def test_em_utilization_fields(monkeypatch):
    """Utilization is priced against the peaks of the LIVE device: a
    device the roofline registry does not list (the CPU here) is an
    error, never a v5e by default."""
    import bench
    from oni_ml_tpu import plans

    with pytest.raises(RuntimeError, match="PEAK_SPECS"):
        bench.em_utilization(20, 8192, 4096, 5e-3)
    monkeypatch.setattr(plans, "device_fingerprint",
                        lambda: "tpu:tpu_v5_lite:1")
    util = bench.em_utilization(20, 8192, 4096, 5e-3)
    assert set(util) == {
        "achieved_tflops", "mxu_pct", "hbm_gbps", "hbm_pct"
    }
    assert all(v > 0 for v in util.values())


def _patch_phases(bench, monkeypatch):
    # In-process phase execution: the monkeypatched bench_* stubs below
    # don't exist inside the production path's phase subprocesses.
    monkeypatch.setenv("BENCH_INPROC", "1")
    monkeypatch.setattr(
        bench, "bench_em",
        lambda *a, **k: {"docs_per_sec": 1000.0, "t_iter": 0.004,
                         "use_dense": False, "wmajor": False,
                         "corpus_itemsize": 4, "mean_vi": 5.0,
                         "chunk": k.get("chunk", 128),
                         "alpha_max_iters": 8},
    )
    monkeypatch.setattr(
        bench, "bench_dns_scoring", lambda *a, **k: (5000.0, 0.08)
    )
    monkeypatch.setattr(
        bench, "bench_flow_scoring", lambda *a, **k: (4000.0, 0.1)
    )
    monkeypatch.setattr(bench, "bench_online_svi", lambda *a, **k: 2000.0)
    monkeypatch.setattr(
        bench, "bench_pipeline_e2e",
        lambda *a, **k: (60.0, {"pre": 10.0, "lda": 40.0}, 80000.0,
                         {"pre_workers": 2, "wall": {}, "handoff": "direct",
                          "pre_s_workers1": 18.0},
                         {"stage_wall_s": {"pre": 10.0, "lda": 40.0},
                          "per_stage_wall_s": {"pre": 12.0, "lda": 40.0},
                          "background_wall_s": 2.0,
                          "sum_of_stage_walls_s": 52.0,
                          "e2e_wall_s": 60.0,
                          "overlap_efficiency": -0.1538, "edges": {}}),
    )
    monkeypatch.setattr(
        bench, "bench_convergence",
        lambda *a, **k: (1.5, 20, -1e5, "fused+sparse"),
    )
    monkeypatch.setattr(
        bench, "bench_scoring_e2e",
        lambda *a, **k: {"value": 90000.0, "unit": "events/sec",
                         "n_events": 400_000,
                         "host_events_per_sec": 500000.0,
                         "device_events_per_sec": 800000.0,
                         "chunk": 65536, "dispatch": {"dispatches": 7},
                         "projected_dispatches_400k": 7,
                         "calibration": {"break_even": 4096}},
    )
    monkeypatch.setattr(
        bench, "bench_serving_slo",
        lambda *a, **k: {
            "n_events": 4096, "offered_eps": 4000.0,
            "poisson": {"sustained_eps": 3900.0, "p50_ms": 6.0,
                        "p99_ms": 18.0, "p999_ms": 25.0},
            "bursty": {"sustained_eps": 3800.0, "p50_ms": 4.0,
                       "p99_ms": 30.0, "p999_ms": 55.0},
        },
    )
    monkeypatch.setattr(
        bench, "bench_serving_slo_replicated",
        lambda *a, **k: {
            "n_tenants": 256, "zipf_s": 1.1, "spawn": "process",
            "route_window": 64, "max_wait_ms": 20.0,
            "replica_counts": [1, 2, 4],
            "scaling": {
                "1": {"replicas": 1, "events": 3072, "wall_s": 1.1,
                      "sustained_eps": 2800.0, "errors": 0,
                      "retraces_in_window": 0},
                "2": {"replicas": 2, "events": 6144, "wall_s": 1.1,
                      "sustained_eps": 5500.0, "errors": 0,
                      "retraces_in_window": 0},
                "4": {"replicas": 4, "events": 12288, "wall_s": 1.15,
                      "sustained_eps": 10700.0, "errors": 0,
                      "retraces_in_window": 0},
            },
            "sustained_eps_by_count": {"1": 2800.0, "2": 5500.0,
                                       "4": 10700.0},
            "replica_scaling_efficiency": 0.98,
            "replica_scaling_efficiency_by_count": {
                "1": 1.0, "2": 0.98, "4": 0.95},
            "retraces_in_windows": 0,
            "chaos": {
                "replicas": 2, "killed": "r0", "offered_eps": 1500.0,
                "events": 4096, "victim_tenants": 128,
                "errors_surviving": 0, "errors_victim_tenants": 0,
                "p50_ms": 93.0, "p99_ms": 215.0, "p999_ms": 253.0,
                "failover_window_events": 88,
                "failover_p999_ms": 202.0,
                "time_to_recovery_s": 0.18,
                "survivor_bit_identical": True,
                "retraces_after_recovery": 0,
                "failover_record": {"promoted": 128, "resent": 64,
                                    "recovery_s": 0.035},
            },
            "failover_p999_ms": 202.0,
            "time_to_recovery_s": 0.18,
        },
    )
    monkeypatch.setattr(
        bench, "bench_serving_crosshost",
        lambda *a, **k: {
            "fanin": {
                "router_counts": [1, 2], "n_replicas": 1,
                "aggregate_eps_by_routers": {"1": 358.5, "2": 682.2},
                "router_scaling_efficiency": 0.95,
                "fanin_exceeds_single_router": True,
                "wire_bytes_per_event": 134.0, "errors": 0,
                "chaos": {"survivor_errors": 0, "redriven_events": 64,
                          "survivor_bit_identical": True},
            },
            "autoscale": {
                "errors": 0, "wire_bytes_per_event": 131.0,
                "scale_up_reaction_s": 0.4, "max_replicas_reached": 3,
            },
            "sustained_eps": 682.2,
            "router_scaling_efficiency": 0.95,
            "fanin_exceeds_single_router": True,
            "wire_bytes_per_event": 134.0,
            "scale_up_reaction_s": 0.4,
            "max_replicas_reached": 3, "errors": 0,
        },
    )
    monkeypatch.setattr(
        bench, "bench_streaming_freshness",
        lambda *a, **k: {
            "dsource": "flow", "tenant": "stream", "slices": 96,
            "events": 40_000, "refreshes": 47, "publishes": 47,
            "vetoes": 0, "freshness_p50_s": 0.4,
            "freshness_p99_s": 2.4, "freshness_event_p50_min": 14.4,
            "freshness_event_p99_min": 29.0, "freshness_samples": 95,
            "warm": {"fits": 46, "mean_wall_s": 0.06,
                     "mean_em_iters": 5.4},
            "fresh": {"fits": 1, "mean_wall_s": 1.2,
                      "mean_em_iters": 74.0},
            "fresh_control": {"warm_start_speedup": 4.3,
                              "held_out_ll_delta": -0.36},
            "warm_start_speedup": 4.3, "held_out_ll": -6.08,
            "held_out_ll_delta": -0.36, "retraces_after_warmup": 0,
            "replay_speed": 1440.0,
        },
    )
    monkeypatch.setattr(
        bench, "bench_continuous_replicated",
        lambda *a, **k: {
            "replicas": 2, "replay_speed": 1440.0, "n_events": 12_000,
            "events_scored": 9970, "failed_futures": 0, "failovers": 1,
            "killed_replica": "r1",
            "freshness_p50_s": 1.3, "freshness_p99_s": 8.8,
            "freshness_event_p50_min": 0.04,
            "freshness_event_p99_min": 82.6,
            "p99_idle_ms": 92.8, "p99_during_refresh_ms": 106.6,
            "refresh_over_idle_ratio": 1.15,
            "p99_idle_uncoscheduled_ms": 62.3,
            "p99_during_refresh_uncoscheduled_ms": 63.0,
            "yield_wait_p99_ms": 7.1, "preempt_wait_p99_ms": None,
            "train_chunks": 2031, "yields": 9, "preempts": 0,
            "refreshes": 63, "publishes": 45,
            "coalesced_refreshes": 31, "refresh_errors": 0,
            "retraces_after_warmup": 0, "sustained_eps": 198.0,
            "replay_wall_s": 60.6,
        },
    )
    monkeypatch.setattr(
        bench, "bench_detection_quality",
        lambda *a, **k: {
            src: {"recall_at_k": 1.0, "precision_at_k": 1.0,
                  "score_separation": 2.5, "k": 24, "attacks": 24,
                  "per_scenario": {}, "events": 8024, "vocab": 900,
                  "docs": 48, "wall_s": 3.1}
            for src in ("flow", "dns", "proxy")
        },
    )
    monkeypatch.setattr(
        bench, "bench_distributed_em",
        lambda *a, **k: {
            "nprocs": 2, "docs": 2048, "em_iters": 6, "em_shards": 8,
            "transport": "kvring", "docs_per_sec": 60000.0,
            "per_host_estep_wall_s": 0.2, "single_proc_wall_s": 0.35,
            "single_proc_docs_per_sec": 35000.0,
            "scaling_efficiency": 0.875,
            "allreduce_bytes_per_iter": 230000.0,
            "allreduce_wall_s_per_iter": 0.004,
            "allreduce_ops": 7, "rank_ll_spread": 0.0,
        },
    )
    monkeypatch.setattr(
        bench, "bench_serving_slo_fleet",
        lambda *a, **k: {
            "n_tenants": 4, "mix": "poisson:1,bursty:1",
            "n_events": 4096, "offered_eps": 4000.0,
            "aggregate": {"sustained_eps": 3700.0, "p50_ms": 7.0,
                          "p99_ms": 21.0, "p999_ms": 40.0,
                          "resolved": 4096, "errors": 0},
            "tenants": {
                f"t{i}": {"pattern": "poisson" if i % 2 == 0
                          else "bursty",
                          "sustained_eps": 925.0, "p50_ms": 7.0,
                          "p99_ms": 22.0, "p999_ms": 41.0}
                for i in range(4)
            },
            "plans": {"retraces_after_warmup": 0},
        },
    )
    monkeypatch.setattr(
        bench, "bench_serving_slo_fleet_paged",
        lambda *a, **k: {
            "n_tenants": 256, "zipf_s": 1.1, "mix": "poisson:1,bursty:1",
            "n_events": 6144, "offered_eps": 6000.0,
            "aggregate": {"sustained_eps": 2200.0, "p50_ms": 48.0,
                          "p99_ms": 1100.0, "p999_ms": 1200.0,
                          "resolved": 6144, "errors": 0},
            "tenants": {"t0": {"pattern": "poisson",
                               "sustained_eps": 1200.0, "p50_ms": 7.0,
                               "p99_ms": 50.0, "p999_ms": 60.0}},
            "tenants_truncated": True,
            "residency": {"policy": "lru", "hot_capacity": 32,
                          "warm_capacity": 64,
                          "tiers": {"hot": 32, "warm": 64, "cold": 160},
                          "promotions": 350, "evictions": 320,
                          "cold_loads": 250, "spills": 400,
                          "failures": 0, "promotion_stall_s": 200.0},
            "plans": {"retraces_after_warmup": 0},
        },
    )


def test_bench_em_engine_pinning_smoke():
    """bench_em's engine pin: "sparse" runs the fused sparse bucketed
    kernel, "dense" forces the dense kernel in interpret mode on CPU —
    the two sides of the dense_vs_sparse crossover measurement — and
    the payload names what ran plus the effective/dense-equivalent
    FLOP accounting."""
    import bench

    em_s = bench.bench_em(4, 256, 32, 16, chunk=2, rounds=1,
                          var_max_iters=3, engine="sparse",
                          precision="f32")
    assert em_s["estep_engine"] == "sparse"
    assert em_s["use_dense"] is False
    assert em_s["flops_effective_per_iter"] > 0
    # 256 pads to the 128-lane tile; L=16 -> 16x dense-equivalent waste.
    assert em_s["flops_dense_equiv_per_iter"] == \
        em_s["flops_effective_per_iter"] * (256 / 16)
    assert em_s["roofline"]["effective_flops"] > 0

    em_d = bench.bench_em(4, 256, 32, 16, chunk=2, rounds=1,
                          var_max_iters=3, engine="dense",
                          precision="f32")
    assert em_d["estep_engine"] == "dense"
    assert em_d["use_dense"] is True
    assert np.isfinite(em_d["docs_per_sec"])


def test_bench_dense_vs_sparse_records_crossover(monkeypatch, tmp_path):
    """The dense_vs_sparse section measures both engines, persists the
    winner to the plan cache, and the RESOLVED engine (what a fresh
    auto run would pick, source "plan") is never slower than the dense
    baseline — the crossover proving itself on CPU."""
    import bench
    from oni_ml_tpu.ops import sparse_estep

    monkeypatch.setenv("ONI_ML_TPU_PLAN_CACHE",
                       str(tmp_path / "plans.jsonl"))
    sparse_estep._CROSSOVER_CACHE.clear()
    dvs = bench.bench_dense_vs_sparse(4, 256, 32, 16, chunk=2, rounds=1,
                                      precision="f32")
    assert dvs["winner"] in ("dense", "sparse")
    assert dvs["resolved_engine"] == dvs["winner"]
    assert dvs["resolved_source"] == "plan"
    assert dvs[dvs["winner"]]["docs_per_sec"] >= \
        dvs["dense"]["docs_per_sec"]
    for engine in ("dense", "sparse"):
        assert dvs[engine]["roofline"]["wall_s"] > 0
    sparse_estep._CROSSOVER_CACHE.clear()


def test_bench_main_diff_gate(capsys, monkeypatch, tmp_path):
    """BENCH_DIFF_AGAINST wires tools/bench_diff into main() as an
    opt-in post-run gate: the comparison rides the final record and a
    regression flips the exit code to 1 for CI."""
    import bench

    _patch_phases(bench, monkeypatch)
    base = {"metric": "lda_em_throughput", "value": 10_000.0,
            "unit": "docs/sec"}
    path = tmp_path / "base.json"
    path.write_text(json.dumps(base))
    monkeypatch.setenv("BENCH_DIFF_AGAINST", str(path))
    assert bench.main() == 1          # stub headline 1000 << 10000
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["bench_diff"]["regressions"] == 1
    assert rec["bench_diff"]["against"] == str(path)

    # A compatible baseline exits 0 with the comparison still recorded.
    base["value"] = 999.0
    path.write_text(json.dumps(base))
    assert bench.main() == 0
    rec = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1]
    )
    assert rec["bench_diff"]["regressions"] == 0


def test_bench_main_last_line_is_complete_record(capsys, monkeypatch):
    """main() re-prints the growing record after each phase (so a run
    cut short keeps the headline); the driver parses the LAST line,
    which must be the complete record with every secondary — and only
    numbers this run measured (no history ratio, no earlier round)."""
    import bench

    _patch_phases(bench, monkeypatch)
    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) >= 2           # headline first, grown records after
    first = json.loads(out[0])
    assert first["metric"] == "lda_em_throughput"
    assert "secondary" not in first    # printed before any secondary ran
    rec = json.loads(out[-1])
    assert {"metric", "value", "unit"} <= set(rec)
    assert not {"vs_baseline", "prev_round", "last_good"} & set(rec)
    assert rec["metric"] == "lda_em_throughput"
    assert set(rec["secondary"]) == {
        "lda_em_throughput_fresh_start",
        "lda_em_throughput_k50_v50k",
        "lda_em_throughput_config4_v512k",
        "lda_online_svi",
        "lda_em_convergence",
        "dns_scoring",
        "flow_scoring",
        "scoring_e2e",
        "serving_slo",
        "serving_slo_fleet",
        "serving_slo_fleet_paged",
        "featurize_device",
        "serving_slo_replicated",
        "serving_crosshost",
        "streaming_freshness",
        "continuous_replicated",
        "detection_quality",
        "distributed_em",
        "pipeline_e2e",
        "pipeline_e2e_dns",
    }
    # Every phase — success or error stub — carries its wall-clock so
    # the record shows where a slow round-end run spent its time.
    assert rec["phase_wall_s"] >= 0
    assert all(
        v.get("phase_wall_s", -1) >= 0 for v in rec["secondary"].values()
    )


def test_bench_lint_preflight_aborts_on_findings(capsys, monkeypatch):
    """With BENCH_LINT on (conftest turns it off suite-wide), a failing
    lint report aborts main() with a structured failure payload before
    any phase runs — CI-rejected code never spends chip time."""
    import bench

    import oni_ml_tpu.analysis as analysis
    from oni_ml_tpu.analysis.engine import Finding, Report

    monkeypatch.setenv("BENCH_LINT", "1")
    report = Report(
        findings=[Finding("monotonic-clock", "oni_ml_tpu/x.py", 3,
                          "bare time.time()")],
        suppressed=0, baselined=0, files_scanned=1,
        parse_errors=[("oni_ml_tpu/bad.py", "SyntaxError: boom")],
    )
    monkeypatch.setattr(analysis, "run_analysis", lambda: report)
    _patch_phases(bench, monkeypatch)
    assert bench.main() == 1
    captured = capsys.readouterr()
    rec = json.loads(captured.out.strip().splitlines()[-1])
    assert rec["value"] is None
    assert "lint preflight failed" in rec["error"]
    assert "1 parse error(s)" in rec["error"]
    assert set(rec) == {"metric", "value", "unit", "error"}
    assert "[monotonic-clock]" in captured.err
    assert "parse error" in captured.err


def test_bench_main_secondary_failure_is_a_nonzero_exit(capsys, monkeypatch):
    """A crashing secondary must not lose the headline or the other
    secondaries — it is recorded as an error stub — and the exit code
    says the run was not whole."""
    import bench

    _patch_phases(bench, monkeypatch)
    monkeypatch.setattr(
        bench, "bench_online_svi",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    assert bench.main() == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    svi = rec["secondary"]["lda_online_svi"]
    assert svi["error"] == "boom" and svi["phase_wall_s"] >= 0
    assert rec["secondary"]["dns_scoring"]["value"] > 0
    assert rec["failed_phases"] == ["lda_online_svi"]


def test_bench_main_skipped_phase_is_a_nonzero_exit(capsys, monkeypatch):
    """A phase that reports itself skipped measured nothing: it rides
    the record as an error and fails the run like a crash does."""
    import bench

    _patch_phases(bench, monkeypatch)
    phases = [
        (n, (lambda: {"value": 0.0, "skipped": "no chip"})
         if n == "dns_scoring" else f, t)
        for n, f, t in bench.PHASES
    ]
    monkeypatch.setattr(bench, "PHASES", phases)
    assert bench.main() == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["secondary"]["dns_scoring"]["error"] == "skipped: no chip"
    assert rec["failed_phases"] == ["dns_scoring"]


def test_bench_main_headline_failure_prints_no_number(capsys, monkeypatch):
    """No headline, no record: exit 1 with the error and nothing that
    could be read as a measurement (no replay of an earlier round)."""
    import bench

    monkeypatch.setattr(bench, "_run_phase",
                        lambda n, f, t, i: (None, "rc=1: dead", 1.0))
    assert bench.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    rec = json.loads(out[0])
    assert rec == {"metric": "lda_em_throughput", "value": None,
                   "unit": "docs/sec",
                   "error": "headline failed: rc=1: dead"}


def test_bench_phase_subprocess_unknown_phase_reports_error():
    """The production per-phase isolation path: a phase subprocess that
    exits non-zero (here: unknown phase name, rc=2) must come back as a
    (None, error) pair, not an exception or a bogus payload."""
    import bench

    payload, err = bench._run_phase_subprocess("no_such_phase", 60.0)
    assert payload is None
    assert "rc=2" in err and "no_such_phase" in err


def test_bench_online_svi_smoke():
    import bench

    dps = bench.bench_online_svi(k=4, v=256, b=64, l=16, steps=4, chunk=2)
    assert np.isfinite(dps) and dps > 0


def test_bench_convergence_smoke():
    import bench

    s, iters, ll, engine = bench.bench_convergence(
        k=4, v=128, b=32, l=16, em_tol=1e-3, max_iters=24, chunk=8
    )
    assert s > 0 and 0 < iters <= 24 and np.isfinite(ll)
    # CPU-pinned test env: dense is TPU-gated, so the engine label must
    # report what actually ran (review finding: it was hardcoded once).
    assert engine == "fused+sparse"


def test_bench_em_reports_effective_alpha_max_iters():
    """The payload's alpha_max_iters is threaded from the chunk runner
    make_chunk_runner actually built (via _setup_em's info), so a
    monkeypatched maker that overrides the cap — tools/tpu_probes.py's
    alpha_ab newton100 — reports its real setting instead of re-reading
    bench.ALPHA_MAX_ITERS."""
    import bench
    from oni_ml_tpu.models import fused

    em = bench.bench_em(4, 128, 32, 16, chunk=2, rounds=1, var_max_iters=3)
    assert em["alpha_max_iters"] == bench.ALPHA_MAX_ITERS

    orig = fused.make_chunk_runner

    def newton100(**kw):
        kw["alpha_max_iters"] = 100
        return orig(**kw)

    fused.make_chunk_runner = newton100
    try:
        em = bench.bench_em(4, 128, 32, 16, chunk=2, rounds=1,
                            var_max_iters=3)
    finally:
        fused.make_chunk_runner = orig
    assert em["alpha_max_iters"] == 100


def test_bench_diff_regression_gate(tmp_path):
    """tools/bench_diff.py: the documented post-bench step — compares
    headline / phases / utilization / overlap_efficiency between two
    payloads and exits 1 on regression beyond thresholds."""
    import io
    import os
    import sys
    from contextlib import redirect_stdout

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import bench_diff

    def payload(value, e2e_seconds, eff, mxu):
        return {
            "metric": "lda_em_throughput", "value": value,
            "unit": "docs/sec",
            "utilization": {"mxu_pct": mxu, "hbm_pct": 3.1},
            "secondary": {
                "pipeline_e2e": {"value": e2e_seconds, "unit": "seconds",
                                 "overlap_efficiency": eff},
                "dns_scoring": {"value": 150000.0, "unit": "events/sec"},
            },
        }

    old_p = tmp_path / "old.json"
    new_p = tmp_path / "new.json"
    # The driver wrapper form ({"parsed": ...}) must unwrap.
    old_p.write_text(json.dumps(
        {"n": 5, "rc": 0, "parsed": payload(1e6, 100.0, 0.10, 10.5)}
    ))

    # 1) Improvement everywhere: exit 0.
    new_p.write_text(json.dumps(payload(1.2e6, 90.0, 0.15, 11.0)))
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_diff.main([str(old_p), str(new_p)]) == 0
    assert "no regressions" in buf.getvalue()

    # 2) Throughput collapse: exit 1, headline row flagged.
    new_p.write_text(json.dumps(payload(0.5e6, 100.0, 0.10, 10.5)))
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_diff.main([str(old_p), str(new_p)]) == 1
    assert "REGRESSION" in buf.getvalue()

    # 3) Seconds are lower-better: slower e2e beyond threshold fails.
    new_p.write_text(json.dumps(payload(1e6, 150.0, 0.10, 10.5)))
    assert bench_diff.main(
        [str(old_p), str(new_p), "--json"]) == 1

    # 4) overlap_efficiency drop beyond --efficiency-drop fails even
    # with every wall within tolerance.
    new_p.write_text(json.dumps(payload(1e6, 101.0, 0.01, 10.5)))
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_diff.main([str(old_p), str(new_p)]) == 1
    assert "overlap_efficiency" in buf.getvalue()
    # ...but within tolerance passes.
    new_p.write_text(json.dumps(payload(1e6, 101.0, 0.08, 10.5)))
    assert bench_diff.main([str(old_p), str(new_p)]) == 0

    # 5) Utilization absolute-point drop fails.
    new_p.write_text(json.dumps(payload(1e6, 100.0, 0.10, 7.0)))
    assert bench_diff.main([str(old_p), str(new_p)]) == 1

    # 6) --json emits structured rows.
    new_p.write_text(json.dumps(payload(1.1e6, 95.0, 0.12, 10.6)))
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_diff.main([str(old_p), str(new_p), "--json"]) == 0
    out = json.loads(buf.getvalue())
    assert out["regressions"] == 0
    names = {r["name"] for r in out["rows"]}
    assert "headline:lda_em_throughput" in names
    assert "phase:pipeline_e2e" in names
    assert "overlap_efficiency:pipeline_e2e" in names
    assert "utilization:mxu_pct" in names

    # 7) Unusable input: exit 2.
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 2]")
    assert bench_diff.main([str(bad), str(new_p)]) == 2


def test_bench_distributed_em_smoke():
    """The REAL distributed_em phase machinery at toy scale: spawns the
    1-process baseline and a 2-rank CPU cluster (fresh worker
    processes, KV-ring transport) and reports the acceptance payload —
    allreduce bytes/wall per iteration, scaling efficiency, and zero
    rank-ELBO spread (parity)."""
    import bench

    res = bench.bench_distributed_em(nprocs=2, docs=192, em_iters=2)
    assert res["nprocs"] == 2
    assert res["transport"] == "kvring"
    assert res["em_iters"] == 2
    assert res["docs_per_sec"] > 0
    assert res["single_proc_docs_per_sec"] > 0
    assert res["scaling_efficiency"] > 0
    assert res["allreduce_bytes_per_iter"] > 0
    assert res["allreduce_wall_s_per_iter"] > 0
    # em_iters reduces + the gamma merge ride the same collective.
    assert res["allreduce_ops"] == res["em_iters"] + 1
    assert res["rank_ll_spread"] == 0.0
    # The bf16 wire-compression leg: the bulk suff-stats payload
    # halves, the gamma merge + control plane stay exact, so the
    # whole-fit ratio sits between 0.4 and ~0.95; the compressed wire
    # may not silently change the f32 default leg.
    assert res["allreduce_precision"] == "f32"
    bf16 = res["allreduce_bf16"]
    assert 0.3 < bf16["bytes_ratio"] < 0.98
    assert bf16["bytes_per_iter"] < res["allreduce_bytes_per_iter"]
    # bf16-tolerance, not bit-equal: a few percent of the ELBO
    # magnitude at toy scale, never garbage.
    assert bf16["ll_drift_rel"] < 0.05


def test_bench_diff_distributed_em_directions(tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import bench_diff

    def payload(eff, ar_wall):
        return {
            "metric": "lda_em_throughput", "value": 1000.0,
            "unit": "docs/sec",
            "secondary": {"distributed_em": {
                "value": 2000.0, "unit": "docs/sec",
                "scaling_efficiency": eff,
                "allreduce_wall_s_per_iter": ar_wall,
                "allreduce_bytes_per_iter": 219000.0,
            }},
        }

    # Efficiency DROP is a regression (fraction = higher-better)...
    rows = bench_diff.diff_payloads(payload(0.8, 0.01), payload(0.5, 0.01))
    reg = [r["name"] for r in rows if r["regression"]]
    assert reg == ["phase:distributed_em.scaling_efficiency"]
    # ...allreduce-wall GROWTH is a regression (s = lower-better)...
    rows = bench_diff.diff_payloads(payload(0.8, 0.01), payload(0.8, 0.02))
    reg = [r["name"] for r in rows if r["regression"]]
    assert reg == ["phase:distributed_em.allreduce_wall_s_per_iter"]
    # ...and the same moves in the GOOD direction gate nothing.
    rows = bench_diff.diff_payloads(payload(0.5, 0.02), payload(0.8, 0.01))
    assert not [r for r in rows if r["regression"]]
    # A headline-level distributed_em capture compares directly too.
    old = {"value": 2000.0, "unit": "docs/sec",
           "scaling_efficiency": 0.8, "allreduce_wall_s_per_iter": 0.01}
    new = dict(old, scaling_efficiency=0.4)
    rows = bench_diff.diff_payloads(old, new)
    assert any(r["regression"]
               and r["name"] == "headline.scaling_efficiency"
               for r in rows)
