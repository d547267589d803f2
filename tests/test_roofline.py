"""Roofline accounting + SLO metrics plane: peak-spec registry, XLA
cost-analysis harvest (with CPU/older-jax degradation), the
fixed-boundary log-bucket histogram's quantile estimates, the
OpenMetrics exporter (text + HTTP endpoint), the load generator's
arrival patterns and SLO harness, the batcher's per-stage latency
decomposition, heartbeat latency routing, and the trace_view
utilization lanes.  All CPU tier-1.
"""

import json
import math
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from oni_ml_tpu.telemetry import (
    HeartbeatMonitor,
    Journal,
    Recorder,
    RunJournal,
    render_openmetrics,
)
from oni_ml_tpu.telemetry import roofline
from oni_ml_tpu.telemetry.spans import Histogram

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _clean_registry():
    roofline.reset()
    yield
    roofline.reset()


# ---------------------------------------------------------------------------
# peak-spec registry
# ---------------------------------------------------------------------------


def test_peaks_for_v5e_fingerprints():
    # The plans-layer fingerprint shapes this registry must match:
    # "backend:device_kind:count", normalized lowercase/underscores.
    for fp in ("tpu:tpu_v5_lite:1", "tpu:tpu_v5e:4", "tpu:v5litepod-8:8"):
        spec = roofline.peaks_for(fp)
        assert spec is not None, fp
        assert spec.flops_per_s == 197e12
        assert spec.hbm_bytes_per_s == 819e9
        assert "TPU v5e" in spec.provenance  # provenance rides every spec


def test_peaks_for_cpu_and_unknown_are_none():
    for fp in ("cpu:cpu:1", "nodevice", "host:x86_64:2", None, "",
               "tpu:tpu_v9_hypothetical:1"):
        assert roofline.peaks_for(fp) is None


# ---------------------------------------------------------------------------
# cost harvest
# ---------------------------------------------------------------------------


def test_harvest_jitted_on_cpu_registers_cost():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((64, 64), jnp.float32)
    entry = roofline.harvest_jitted("t.matmul", f, x, x, shape="64x64")
    assert entry is not None
    assert entry["source"] in ("cost_analysis", "unavailable")
    assert roofline.cost_for("t.matmul") == entry
    if entry["source"] == "cost_analysis":
        # 64x64x64 matmul: 2*N^3 flops.
        assert entry["flops"] == pytest.approx(2 * 64**3, rel=0.5)


def test_harvest_compiled_tolerates_missing_cost_analysis():
    class NoCost:
        def cost_analysis(self):
            raise NotImplementedError("older jax / odd backend")

    entry = roofline.harvest_compiled("t.nocost", NoCost())
    assert entry["source"] == "unavailable"
    assert entry["flops"] is None and entry["bytes"] is None
    # The record built on top is wall-time-only, not an exception.
    rec = roofline.roofline_record("t.nocost", wall_s=1.0)
    assert rec["flops_per_s"] is None and rec["utilization"] is None
    assert rec["wall_s"] == 1.0


def test_ensure_harvested_is_once_per_name():
    calls = []

    class Fn:
        def lower(self, *a, **kw):
            calls.append(1)
            return self

        def compile(self):
            return self

        def cost_analysis(self):
            return {"flops": 10.0, "bytes accessed": 20.0}

    roofline.ensure_harvested("t.once", Fn())
    roofline.ensure_harvested("t.once", Fn())
    assert len(calls) == 1
    assert roofline.cost_for("t.once")["flops"] == 10.0


def test_ensure_harvested_reharvests_on_shape_change():
    """A later dispatch of the same entry at a different shape must not
    be priced with the stale shape's cost — the registry re-harvests
    when the shape key changes (and is still free on repeats)."""
    calls = []

    class Fn:
        def __init__(self, flops):
            self.flops = flops

        def lower(self, *a, **kw):
            calls.append(1)
            return self

        def compile(self):
            return self

        def cost_analysis(self):
            return {"flops": self.flops, "bytes accessed": 1.0}

    roofline.ensure_harvested("t.shape", Fn(10.0), shape="c8192")
    roofline.ensure_harvested("t.shape", Fn(10.0), shape="c8192")
    assert len(calls) == 1
    roofline.ensure_harvested("t.shape", Fn(2.0), shape="c1024")
    assert len(calls) == 2
    cost = roofline.cost_for("t.shape")
    assert cost["flops"] == 2.0 and cost["shape"] == "c1024"


def test_harvest_failure_invalidates_stale_shape_entry():
    """When re-lowering at a NEW shape fails, the old shape's cost must
    not survive to mis-price the new dispatches: the entry degrades to
    unavailable (wall-time-only records) for the current shape."""
    class Good:
        def lower(self, *a, **kw):
            return self

        def compile(self):
            return self

        def cost_analysis(self):
            return {"flops": 10.0, "bytes accessed": 20.0}

    class Broken:
        def lower(self, *a, **kw):
            raise RuntimeError("lowering failed")

    roofline.ensure_harvested("t.inval", Good(), shape="c8192")
    assert roofline.cost_for("t.inval")["flops"] == 10.0
    roofline.ensure_harvested("t.inval", Broken(), shape="c1024")
    cost = roofline.cost_for("t.inval")
    assert cost["source"] == "unavailable" and cost["shape"] == "c1024"
    assert cost["flops"] is None


# ---------------------------------------------------------------------------
# record math + emission
# ---------------------------------------------------------------------------


def _fake_cost(name, flops, nbytes, backend):
    class C:
        def cost_analysis(self):
            return {"flops": flops, "bytes accessed": nbytes}

    entry = roofline.harvest_compiled(name, C())
    entry["backend"] = backend
    with roofline._LOCK:
        roofline._COSTS[name] = entry


def test_roofline_record_math_against_peaks():
    _fake_cost("t.em", 197e10, 819e7, "tpu:tpu_v5_lite:1")  # 1% peaks @1s
    rec = roofline.roofline_record("t.em", wall_s=1.0, dispatches=2)
    assert rec["flops"] == 2 * 197e10
    assert rec["flops_per_s"] == pytest.approx(2 * 197e10)
    assert rec["utilization"]["mxu_pct"] == pytest.approx(2.0)
    assert rec["utilization"]["hbm_pct"] == pytest.approx(2.0)
    assert rec["peaks"]["flops_per_s"] == 197e12
    assert rec["kind"] == "roofline" and rec["dispatches"] == 2


def test_roofline_record_cpu_degrades_to_no_utilization():
    _fake_cost("t.cpu", 1e9, 1e6, "cpu:cpu:1")
    rec = roofline.roofline_record("t.cpu", wall_s=0.5)
    assert rec["flops_per_s"] == pytest.approx(2e9)  # achieved still real
    assert rec["peaks"] is None and rec["utilization"] is None


def test_emit_journals_record_and_sets_gauges(tmp_path):
    _fake_cost("t.phase", 197e10, 819e7, "tpu:tpu_v5_lite:1")
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    rec = Recorder(journal=j)
    before = roofline.emit_count()
    out = roofline.emit("t.phase", 1.0, recorder=rec, extra_field=7)
    j.close()
    assert out["extra_field"] == 7
    lines = [r for r in Journal.replay(path) if r.get("kind") == "roofline"]
    assert len(lines) == 1
    assert lines[0]["phase"] == "t.phase"
    assert lines[0]["utilization"]["mxu_pct"] == pytest.approx(1.0)
    assert rec.gauges["roofline.t.phase.mxu_pct"] == pytest.approx(1.0)
    assert rec.gauges["roofline.t.phase.flops_per_s"] > 0
    assert roofline.emitted_records(since=before)[0]["phase"] == "t.phase"


def test_emit_accepts_run_journal_and_never_raises(tmp_path):
    path = str(tmp_path / "j.jsonl")
    rj = RunJournal(Journal(path))
    roofline.emit("t.unharvested", 0.25, journal=rj)  # no cost: fine
    rj.close()
    recs = [r for r in Journal.replay(path) if r.get("kind") == "roofline"]
    assert len(recs) == 1
    assert recs[0]["cost_source"] == "unharvested"
    assert recs[0]["utilization"] is None
    roofline.emit("t.nothing", 0.1)  # no recorder, no journal: no raise


def test_subprocess_cpu_journal_carries_wall_time_only_record(tmp_path):
    """Satellite acceptance: under JAX_PLATFORMS=cpu the journal still
    carries the roofline record — no peaks, no exceptions,
    `utilization: null` — whatever cost_analysis does on this backend."""
    jpath = str(tmp_path / "run_journal.jsonl")
    script = """
import jax, jax.numpy as jnp
from oni_ml_tpu.telemetry import Journal, RunJournal
from oni_ml_tpu.telemetry import roofline

f = jax.jit(lambda a, b: a @ b)
x = jnp.ones((32, 32), jnp.float32)
roofline.harvest_jitted("em.run_chunk", f, x, x)
rj = RunJournal(Journal({jpath!r}))
roofline.emit("em.run_chunk", 0.5, dispatches=3, journal=rj)
rj.close()
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", script.format(jpath=jpath)],
        capture_output=True, text=True, env=env, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [r for r in Journal.replay(jpath)
            if r.get("kind") == "roofline"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["phase"] == "em.run_chunk" and rec["dispatches"] == 3
    assert rec["utilization"] is None and rec["peaks"] is None
    assert rec["wall_s"] == 0.5


# ---------------------------------------------------------------------------
# fixed-boundary log-bucket histogram quantiles
# ---------------------------------------------------------------------------


def test_histogram_quantiles_accurate_within_bucket_width():
    import threading

    h = Histogram("t", threading.RLock())
    vals = np.linspace(1.0, 1000.0, 5000)
    for v in vals:
        h.observe(float(v))
    # 2^(1/4) buckets: estimates within ~±10% of the true quantile.
    for q in (0.5, 0.9, 0.99, 0.999):
        true = float(np.quantile(vals, q))
        est = h.quantile(q)
        assert abs(est - true) / true < 0.10, (q, est, true)
    s = h.summary()
    assert s["p50"] == h.quantile(0.5)
    assert s["p999"] is not None and s["p999"] <= s["max"]
    assert s["count"] == 5000


def test_histogram_quantile_edge_cases():
    import threading

    h = Histogram("t", threading.RLock())
    assert h.quantile(0.5) is None           # empty
    h.observe(3.0)
    assert h.quantile(0.5) == pytest.approx(3.0)   # single value clamps
    assert h.quantile(0.999) == pytest.approx(3.0)
    # q=0 on an all-positive histogram clamps to the observed min — the
    # empty zero bucket must not fabricate a 0.
    assert h.quantile(0.0) == pytest.approx(3.0)
    z = Histogram("z", threading.RLock())
    for _ in range(10):
        z.observe(0.0)                        # zero bucket only
    assert z.quantile(0.5) == 0.0
    neg = Histogram("n", threading.RLock())
    neg.observe(-2.0)
    assert neg.quantile(0.5) == -2.0


def test_histogram_boundary_values_respect_le_semantics():
    import threading

    h = Histogram("t", threading.RLock())
    h.observe(2.0)   # exactly on a bucket boundary (2^(4/4))
    buckets = h.openmetrics_buckets()
    le2 = [c for le, c in buckets if le == 2.0]
    assert le2 == [1]  # counted at le=2, not pushed into the next bucket


def test_histogram_drops_non_finite_observations():
    """A NaN must not poison sum/mean for the life of the process, and
    +/-inf has no bucket: non-finite observations are dropped entirely,
    so `_count` stays equal to the +Inf bucket and the exposition stays
    valid OpenMetrics."""
    import threading

    h = Histogram("t", threading.RLock())
    h.observe(1.0)
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe(float("-inf"))
    h.observe(3.0)
    s = h.summary()
    assert s["count"] == 2
    assert s["sum"] == pytest.approx(4.0)
    assert s["min"] == 1.0 and s["max"] == 3.0
    assert h.zero_count == 0                  # inf never misfiled there
    assert h.openmetrics_buckets()[-1] == (math.inf, 2)


def test_histogram_openmetrics_snapshot_consistent():
    """summary and buckets come back from ONE lock acquisition, and the
    +Inf bucket equals the count — the invariant the exporter's
    exposition must hold under concurrent observes."""
    import threading

    h = Histogram("t", threading.RLock())
    for v in (0.5, 1.0, 4.0):
        h.observe(v)
    s, buckets = h.openmetrics_snapshot()
    assert s["count"] == 3 and buckets[-1] == (math.inf, s["count"])


def test_histogram_openmetrics_buckets_cumulative():
    import threading

    h = Histogram("t", threading.RLock())
    for v in (0.0, 1.0, 2.0, 500.0):
        h.observe(v)
    buckets = h.openmetrics_buckets()
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)          # cumulative, non-decreasing
    assert buckets[-1] == (math.inf, 4)      # +Inf carries the total
    les = [le for le, _ in buckets[:-1]]
    assert les == sorted(les)


# ---------------------------------------------------------------------------
# OpenMetrics exporter
# ---------------------------------------------------------------------------


def test_render_openmetrics_format():
    rec = Recorder()
    rec.counter("serve.events").add(56)
    rec.histogram("serve.latency_ms").observe(5.0)
    rec.histogram("serve.latency_ms").observe(7.0)
    rec.gauge("roofline.em.run_chunk.mxu_pct", 10.5)
    text = render_openmetrics(rec)
    assert text.endswith("# EOF\n")
    assert "# TYPE serve_events counter" in text
    assert "serve_events_total 56" in text
    assert "# TYPE roofline_em_run_chunk_mxu_pct gauge" in text
    assert "roofline_em_run_chunk_mxu_pct 10.5" in text
    assert "# TYPE serve_latency_ms histogram" in text
    assert 'serve_latency_ms_bucket{le="+Inf"} 2' in text
    assert "serve_latency_ms_sum 12" in text
    assert "serve_latency_ms_count 2" in text
    # every bucket line's le parses and cumulative counts ascend
    cums = []
    for line in text.splitlines():
        if line.startswith("serve_latency_ms_bucket"):
            cums.append(int(line.rsplit(" ", 1)[1]))
    assert cums == sorted(cums) and cums[-1] == 2


def test_render_openmetrics_refresh_hook_runs_and_is_isolated():
    rec = Recorder()
    calls = []

    def refresh():
        calls.append(1)
        rec.gauge("live.g", 1.0)

    text = render_openmetrics(rec, refresh=refresh)
    assert calls == [1] and "live_g 1" in text

    def broken():
        raise RuntimeError("scrape must survive this")

    assert render_openmetrics(rec, refresh=broken).endswith("# EOF\n")


def test_metrics_server_serves_live_registry():
    from oni_ml_tpu.telemetry import MetricsServer

    rec = Recorder()
    rec.counter("serve.events").add(3)
    srv = MetricsServer(rec, port=0)   # ephemeral port
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        resp = urllib.request.urlopen(url, timeout=10)
        body = resp.read().decode()
        assert resp.headers["Content-Type"].startswith(
            "application/openmetrics-text"
        )
        assert "serve_events_total 3" in body
        rec.counter("serve.events").add(4)   # live: no snapshot staleness
        body2 = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "serve_events_total 7" in body2
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10
            )
    finally:
        srv.close()


def test_write_openmetrics_file_sink(tmp_path):
    from oni_ml_tpu.telemetry import write_openmetrics

    rec = Recorder()
    rec.counter("c").add(1)
    path = str(tmp_path / "metrics.om")
    write_openmetrics(path, rec)
    with open(path) as f:
        text = f.read()
    assert text == render_openmetrics(rec)


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------


def test_arrival_offsets_poisson_statistics():
    import load_gen

    offs = load_gen.arrival_offsets("poisson", 20000, 1000.0, seed=1)
    assert len(offs) == 20000
    gaps = np.diff(offs)
    assert (gaps >= 0).all()
    assert np.mean(gaps) == pytest.approx(1e-3, rel=0.05)


def test_arrival_offsets_bursty_shape():
    import load_gen

    offs = load_gen.arrival_offsets("bursty", 256, 1000.0, burst_len=64)
    # 4 bursts of 64 at 64ms spacing; zero gaps inside a burst.
    assert len(offs) == 256
    assert set(np.unique(offs).round(6)) == {0.0, 0.064, 0.128, 0.192}
    # long-run average rate is the offered rate
    assert 256 / (offs[-1] + 64 / 1000.0) == pytest.approx(1000.0)
    with pytest.raises(ValueError):
        load_gen.arrival_offsets("nope", 10, 1.0)
    with pytest.raises(ValueError):
        load_gen.arrival_offsets("poisson", 10, 0.0)


def test_run_slo_measures_both_patterns():
    import load_gen

    res = load_gen.run_slo(
        n_events=192, rate_eps=4000.0, burst_len=32, max_batch=32,
        max_wait_ms=5.0, device_score_min=None,  # host-pinned: fast CPU
    )
    for pattern in ("poisson", "bursty"):
        r = res[pattern]
        assert r["resolved"] == 192 and r["errors"] == 0
        assert r["sustained_eps"] > 0
        for q in ("p50_ms", "p99_ms", "p999_ms"):
            assert r[q] is not None and r[q] > 0
        assert r["p50_ms"] <= r["p99_ms"] <= r["p999_ms"] <= r["max_ms"]


def test_bench_serving_slo_at_test_size():
    import bench

    res = bench.bench_serving_slo(n_events=96, rate_eps=4000.0,
                                  burst_len=32, max_batch=32,
                                  max_wait_ms=5.0,
                                  device_score_min=None)  # host: fast CPU
    assert "poisson" in res and "bursty" in res
    assert res["poisson"]["p999_ms"] is not None
    assert res["bursty"]["sustained_eps"] > 0


# ---------------------------------------------------------------------------
# batcher per-stage latency decomposition
# ---------------------------------------------------------------------------


def test_batch_records_decompose_latency(tmp_path):
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.serving import (
        BatchScorer,
        DnsEventFeaturizer,
        MetricsEmitter,
        ModelRegistry,
    )

    rows, model, cuts = _synthetic_day()
    reg = ModelRegistry()
    reg.publish(model, source="t")
    metrics = MetricsEmitter(to_stdout=False)
    scorer = BatchScorer(
        reg, DnsEventFeaturizer(cuts),
        ServingConfig(max_batch=32, max_wait_ms=10.0,
                      device_score_min=None),
        metrics=metrics,
    )
    futs = [scorer.submit(r) for r in rows]
    for f in futs:
        f.result(timeout=30.0)
    scorer.close()
    batch_recs = [r for r in metrics.records if "latency_ms" in r]
    assert batch_recs
    for r in batch_recs:
        assert {"queue_wait_ms", "score_ms", "demux_ms"} <= set(r)
        # decomposition is consistent: stages sum to no more than the
        # end-to-end latency (+ scheduling slack)
        assert r["queue_wait_ms"] <= r["latency_ms"] + 1e-6
    snap = metrics.snapshot()
    for h in ("serve.latency_ms", "serve.queue_wait_ms",
              "serve.score_ms", "serve.demux_ms"):
        assert snap["histograms"][h]["count"] == len(batch_recs)
        assert snap["histograms"][h]["p999"] is not None
    # device_score_min=None pinned the host path: the device-only
    # histogram the serve roofline joins against must stay empty.
    assert "serve.device_score_ms" not in snap["histograms"]


def test_metrics_device_score_histogram_tracks_device_flushes_only():
    from oni_ml_tpu.serving import MetricsEmitter

    m = MetricsEmitter(to_stdout=False, recorder=Recorder())
    m.emit({"stage": "serve", "scorer": "host", "score_ms": 5.0,
            "events": 8})
    m.emit({"stage": "serve", "scorer": "device", "score_ms": 2.0,
            "events": 16})
    m.emit({"stage": "serve", "scorer": "device", "score_ms": 3.0,
            "events": 32})
    snap = m.snapshot()
    assert snap["histograms"]["serve.score_ms"]["count"] == 3
    dev = snap["histograms"]["serve.device_score_ms"]
    assert dev["count"] == 2 and dev["sum"] == pytest.approx(5.0)
    assert snap["counters"]["serve.device_events"] == 48


# ---------------------------------------------------------------------------
# heartbeat latency routing
# ---------------------------------------------------------------------------


def test_heartbeat_probe_latency_feeds_shared_histogram():
    rec = Recorder()
    answers = iter([0.001, 0.004, None, 0.002])
    hb = HeartbeatMonitor(
        interval_s=0.01, timeout_s=0.1, max_misses=5,
        probe=lambda t: next(answers), recorder=rec,
    )
    assert hb.beat_once() and hb.beat_once()
    assert not hb.beat_once()      # miss
    assert hb.beat_once()
    h = rec.histograms["heartbeat.probe_latency_s"]
    assert h.count == 3
    assert h.max == pytest.approx(0.004)
    assert rec.counters["heartbeat.misses"].value == 1
    # degradation is visible on the exporter plane before any loss
    text = render_openmetrics(rec)
    assert "heartbeat_probe_latency_s_count 3" in text


def test_heartbeat_binds_ambient_recorder():
    from oni_ml_tpu.telemetry import use_recorder

    rec = Recorder()
    with use_recorder(rec):
        hb = HeartbeatMonitor(interval_s=1.0, probe=lambda t: 0.001)
    assert hb.recorder is rec
    hb.beat_once()
    assert rec.histograms["heartbeat.probe_latency_s"].count == 1


# ---------------------------------------------------------------------------
# trace_view utilization + liveness lanes
# ---------------------------------------------------------------------------


def test_trace_view_renders_roofline_and_heartbeat_counter_lanes(tmp_path):
    import trace_view

    path = str(tmp_path / "run_journal.jsonl")
    rj = RunJournal(Journal(path))
    rj.stage_begin("lda")
    rj.append({
        "kind": "roofline", "phase": "em.run_chunk", "wall_s": 1.0,
        "dispatches": 4, "flops_per_s": 5.47e12, "bytes_per_s": 25.5e9,
        "utilization": {"mxu_pct": 10.5, "hbm_pct": 3.1},
    })
    rj.append({
        "kind": "roofline", "phase": "score.device.filtered",
        "wall_s": 0.5, "dispatches": 2, "flops_per_s": 2e9,
        "utilization": None,
    })
    rj.heartbeat(True, latency_s=0.002)
    rj.stage_end("lda", ok=True, wall_s=2.0)
    rj.stage_begin("score")          # unfinished marker still works
    rj.close()

    records = trace_view.Journal.replay(path)
    trace = trace_view.journal_to_trace(records)
    evs = trace["traceEvents"]
    lanes = {e["name"]: e for e in evs if e["ph"] == "C"}
    assert lanes["roofline em.run_chunk"]["args"]["mxu_pct"] == 10.5
    assert lanes["roofline em.run_chunk"]["args"]["hbm_pct"] == 3.1
    # no utilization -> achieved-GFLOPs lane, not silence
    assert lanes["roofline score.device.filtered"]["args"][
        "gflops_per_s"] == pytest.approx(2.0)
    assert lanes["heartbeat latency_ms"]["args"]["latency_ms"] == \
        pytest.approx(2.0)
    assert any(e["name"] == "stage.score (unfinished)" for e in evs)
    json.dumps(trace)
    # summary prints the roofline section without raising
    import io

    buf = io.StringIO()
    trace_view.print_summary(records, 0, out=buf)
    out = buf.getvalue()
    assert "roofline" in out and "mxu_pct=10.5" in out


# ---------------------------------------------------------------------------
# pipeline + EM integration: instrumented runs journal rooflines
# ---------------------------------------------------------------------------


def test_scoring_pipeline_emits_roofline_under_recorder(tmp_path, day_model):
    from oni_ml_tpu.scoring.pipeline import filtered_scores
    from oni_ml_tpu.telemetry import use_recorder

    model = day_model
    rng = np.random.default_rng(5)
    n = 512
    ip = rng.integers(0, model.theta.shape[0], n).astype(np.int32)
    w = rng.integers(0, model.p.shape[0], n).astype(np.int32)
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    rec = Recorder(journal=j)
    with use_recorder(rec):
        filtered_scores(model, ip, w, 0.5, chunk=256)
    j.close()
    recs = [r for r in Journal.replay(path) if r.get("kind") == "roofline"]
    assert len(recs) == 1
    r = recs[0]
    assert r["phase"] == "score.device.filtered"
    assert r["dispatches"] == 2 and r["events"] == 512
    assert r["utilization"] is None          # CPU: no peaks
    # CPU cost analysis exists here; at minimum the record never raises
    assert r["cost_source"] in ("cost_analysis", "unavailable")


@pytest.fixture
def day_model():
    from oni_ml_tpu.runner.serve import _synthetic_day

    _, model, _ = _synthetic_day()
    return model


def test_fused_em_emits_roofline_under_recorder(tmp_path):
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.io import Corpus
    from oni_ml_tpu.models.lda import train_corpus
    from oni_ml_tpu.telemetry import use_recorder

    rng = np.random.default_rng(0)
    ptr = [0]
    widx: list = []
    cnts: list = []
    for _ in range(48):
        n = int(rng.integers(3, 10))
        widx.extend(rng.integers(0, 50, n).tolist())
        cnts.extend(rng.integers(1, 4, n).tolist())
        ptr.append(len(widx))
    corpus = Corpus(
        doc_names=[f"ip{d}" for d in range(48)],
        vocab=[f"w{i}" for i in range(50)],
        doc_ptr=np.asarray(ptr, np.int64),
        word_idx=np.asarray(widx, np.int32),
        counts=np.asarray(cnts, np.int32),
    )
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    rec = Recorder(journal=j)
    cfg = LDAConfig(num_topics=4, em_max_iters=4, fused_em_chunk=2,
                    host_sync_every=2, batch_size=64)
    with use_recorder(rec):
        train_corpus(corpus, cfg)
    j.close()
    recs = [r for r in Journal.replay(path)
            if r.get("kind") == "roofline" and r["phase"] == "em.run_chunk"]
    assert len(recs) == 1
    assert recs[0]["dispatches"] >= 1
    assert recs[0]["wall_s"] > 0
    assert recs[0]["utilization"] is None    # CPU tier-1 degradation


def test_pipeline_run_journals_em_roofline_and_metrics_rollup(tmp_path):
    """The acceptance path on CPU tier-1: a journaled pipeline run's
    run_journal.jsonl carries the EM roofline record (wall-time-only /
    utilization null here), and metrics.json carries the run-level
    {"stage": "roofline"} rollup."""
    from test_features import flow_row

    from oni_ml_tpu.config import (
        FeedbackConfig,
        LDAConfig,
        PipelineConfig,
        ScoringConfig,
    )
    from oni_ml_tpu.runner import run_pipeline

    rng = np.random.default_rng(7)
    lines = ["dummy,header"]
    for _ in range(60):
        lines.append(flow_row(
            hour=int(rng.integers(0, 24)),
            minute=int(rng.integers(0, 60)),
            second=int(rng.integers(0, 60)),
            sip=f"10.0.0.{rng.integers(1, 9)}",
            dip=f"172.16.0.{rng.integers(1, 9)}",
            ipkt=str(rng.integers(1, 100)),
            ibyt=str(rng.integers(40, 10000)),
        ))
    raw = tmp_path / "flow.csv"
    raw.write_text("\n".join(lines) + "\n")
    cfg = PipelineConfig(
        data_dir=str(tmp_path), flow_path=str(raw),
        lda=LDAConfig(num_topics=4, em_max_iters=4, batch_size=32,
                      min_bucket_len=16, seed=3, fused_em_chunk=2,
                      host_sync_every=2),
        feedback=FeedbackConfig(dup_factor=5),
        scoring=ScoringConfig(threshold=1.1),
    )
    metrics = run_pipeline(cfg, "20160122", "flow")
    jpath = os.path.join(str(tmp_path), "20160122", "run_journal.jsonl")
    recs = [r for r in Journal.replay(jpath)
            if r.get("kind") == "roofline"]
    em = [r for r in recs if r["phase"] == "em.run_chunk"]
    assert em, recs
    assert em[0]["dispatches"] >= 1 and em[0]["wall_s"] > 0
    assert em[0]["utilization"] is None       # CPU: no peaks
    rollup = [m for m in metrics if m.get("stage") == "roofline"]
    assert rollup and any(
        r["phase"] == "em.run_chunk" for r in rollup[0]["records"]
    )


def test_serve_stream_openmetrics_endpoint_and_sink(tmp_path, capsys):
    """`ml_ops serve --metrics-port --openmetrics --journal` over a
    real (tiny) day dir: the live endpoint serves the serve histograms
    with quantiles, the file sink lands the same format, and the
    journal carries the serve.micro_batch roofline record."""
    import pickle
    import socket

    from oni_ml_tpu.runner import ml_ops
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.scoring import ScoringModel  # noqa: F401 (day build)

    rows, model, cuts = _synthetic_day()
    day = tmp_path / "day"
    day.mkdir()
    # Write the day-dir serving contract (`key,v1 v2 ... vK` rows):
    # results CSVs + features.pkl.
    with open(day / "doc_results.csv", "w") as f:
        for ip, th in zip(model.ip_index, model.theta[:-1]):
            f.write(ip + "," + " ".join(f"{v:.8f}" for v in th) + "\n")
    with open(day / "word_results.csv", "w") as f:
        for w, pr in zip(model.word_index, model.p[:-1]):
            f.write(w + "," + " ".join(f"{v:.8f}" for v in pr) + "\n")

    from oni_ml_tpu.features.dns import featurize_dns

    feats = featurize_dns(rows)
    with open(day / "features.pkl", "wb") as f:
        pickle.dump(feats, f)
    stream = day / "events.csv"
    with open(stream, "w") as f:
        for r in rows:
            f.write(",".join(r) + "\n")
    with socket.socket() as s:                   # a free ephemeral port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    om_path = str(tmp_path / "final.om")
    jpath = str(tmp_path / "serve_journal.jsonl")
    rc = ml_ops.main([
        "serve", "--day-dir", str(day), "--dsource", "dns",
        "--input", str(stream), "--max-batch", "16",
        "--max-wait-ms", "5", "--device-score-min", "100000",
        "--metrics-port", str(port), "--openmetrics", om_path,
        "--journal", jpath, "--no-plans", "--no-compilation-cache",
    ])
    capsys.readouterr()
    assert rc == 0
    with open(om_path) as f:
        text = f.read()
    assert text.endswith("# EOF\n")
    assert "serve_latency_ms_bucket" in text
    assert "serve_queue_wait_ms_count" in text
    assert "serve_events_total" in text
    recs = [r for r in Journal.replay(jpath)
            if r.get("kind") == "roofline"]
    assert recs and recs[0]["phase"] == "serve.micro_batch"
    assert recs[0]["dispatches"] >= 1
    # --device-score-min 100000 pinned every flush to the HOST scorer:
    # the record must be wall-time-only (path "host"), never the warmed
    # device program's cost multiplied by host flushes.
    assert recs[0]["path"] == "host"
    assert recs[0]["flops"] is None


# ---------------------------------------------------------------------------
# serving metrics snapshot quantiles (the satellite fix)
# ---------------------------------------------------------------------------


def test_metrics_snapshot_reports_true_quantiles():
    from oni_ml_tpu.serving import MetricsEmitter

    m = MetricsEmitter(to_stdout=False, recorder=Recorder())
    for i in range(1000):
        m.emit({"stage": "serve", "batch": i, "events": 1,
                "latency_ms": 1.0 + i})   # 1..1000 ms
    snap = m.snapshot()
    lat = snap["histograms"]["serve.latency_ms"]
    assert lat["count"] == 1000
    assert lat["p50"] == pytest.approx(500, rel=0.10)
    assert lat["p99"] == pytest.approx(990, rel=0.10)
    assert lat["p999"] == pytest.approx(999, rel=0.10)
    # JSON-line stream schema unchanged: records still verbatim dicts
    assert m.records[0] == {"stage": "serve", "batch": 0, "events": 1,
                            "latency_ms": 1.0}
