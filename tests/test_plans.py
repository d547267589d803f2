"""Measured execution plans (oni_ml_tpu/plans): store durability and
invalidation, resolution precedence, the bounded autotune harness, the
compile-cache warmup counters, and the runner e2e contract — a second
run re-sweeps nothing and re-traces nothing, and a plans-on run's
artifacts are byte-identical to a plans-off run."""

import json
import os
import subprocess
import sys

import pytest

from oni_ml_tpu import plans
from oni_ml_tpu.plans import (
    KNOBS,
    NullStore,
    PlanStore,
    autotune,
    resolve,
    use_store,
)
from oni_ml_tpu.plans.store import SCHEMA_VERSION

from test_features import flow_row


def _store(tmp_path, name="plans.jsonl") -> PlanStore:
    return PlanStore(str(tmp_path / name))


def _fp(knob="fused_em_chunk"):
    return plans.fingerprint(KNOBS[knob].scope)


# ---------------------------------------------------------------------------
# store: durability, invalidation, layering
# ---------------------------------------------------------------------------


def test_store_roundtrip_and_latest_wins(tmp_path):
    st = _store(tmp_path)
    st.record("fused_em_chunk", "tpu:x:1", "*", 32, source="probe")
    st.record("fused_em_chunk", "tpu:x:1", "*", 64, source="autotune",
              measurements={"32": 1.0, "64": 2.0})
    # In-memory view and a fresh replay both see the LATEST entry.
    assert st.lookup("fused_em_chunk", "tpu:x:1").value == 64
    st.close()
    st2 = _store(tmp_path)
    e = st2.lookup("fused_em_chunk", "tpu:x:1")
    assert e.value == 64 and e.source == "autotune"
    assert e.measurements == {"32": 1.0, "64": 2.0}


def test_store_exact_shape_beats_wildcard(tmp_path):
    st = _store(tmp_path)
    st.record("fused_em_chunk", "tpu:x:1", "*", 128)
    st.record("fused_em_chunk", "tpu:x:1", "k20.v8192", 64)
    assert st.lookup("fused_em_chunk", "tpu:x:1", "k20.v8192").value == 64
    assert st.lookup("fused_em_chunk", "tpu:x:1", "k50.v50000").value == 128
    assert st.lookup("fused_em_chunk", "tpu:x:1").value == 128


def test_store_corrupt_tail_tolerated(tmp_path):
    """A SIGKILL mid-append truncates the final line; replay drops it
    silently and keeps every earlier entry (the telemetry journal's
    contract, inherited)."""
    st = _store(tmp_path)
    st.record("fused_em_chunk", "tpu:x:1", "*", 64)
    st.close()
    path = tmp_path / "plans.jsonl"
    with open(path, "ab") as f:
        f.write(b'{"schema": 1, "knob": "fused_em_chunk", "backe')
    st2 = _store(tmp_path)
    assert st2.lookup("fused_em_chunk", "tpu:x:1").value == 64
    assert st2.dropped_records == 0      # clean tail truncation


def test_store_garbage_lines_dropped_and_counted(tmp_path):
    path = tmp_path / "plans.jsonl"
    good = {"schema": SCHEMA_VERSION, "knob": "fused_em_chunk",
            "backend": "tpu:x:1", "shape": "*", "value": 64}
    with open(path, "w") as f:
        f.write("not json at all\n")
        f.write(json.dumps(good) + "\n")
    st = _store(tmp_path)
    assert st.lookup("fused_em_chunk", "tpu:x:1").value == 64
    assert st.dropped_records == 1       # mid-file damage is COUNTED


def test_schema_version_mismatch_invalidates(tmp_path):
    path = tmp_path / "plans.jsonl"
    rec = {"schema": SCHEMA_VERSION + 1, "knob": "fused_em_chunk",
           "backend": "tpu:x:1", "shape": "*", "value": 7}
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    st = _store(tmp_path)
    assert st.lookup("fused_em_chunk", "tpu:x:1") is None
    with use_store(st):
        value, source = resolve("fused_em_chunk", KNOBS["fused_em_chunk"].default)
    assert (value, source) == (KNOBS["fused_em_chunk"].default, "default")


def test_backend_fingerprint_mismatch_invalidates(tmp_path):
    """An entry measured on another backend (a v5e's, on this CPU
    suite) never resolves — falls back to the default, never crashes."""
    st = _store(tmp_path)
    st.record("fused_em_chunk", "tpu:tpu_v5_lite:1", "*", 999)
    with use_store(st):
        value, source = resolve("fused_em_chunk", KNOBS["fused_em_chunk"].default)
    assert source == "default"
    assert value == KNOBS["fused_em_chunk"].default != 999


def test_no_plan_ships_with_the_package(tmp_path):
    """A fresh store holds nothing: every entry a run resolves was
    measured on the machine it runs on, and the latest measurement of a
    key wins."""
    st = _store(tmp_path)
    assert st.entries() == []
    st.record("fused_em_chunk", "tpu:tpu_v5_lite:1",
              "k20.v8192.b4096.l128", 128, source="autotune")
    st.record("fused_em_chunk", "tpu:tpu_v5_lite:1",
              "k20.v8192.b4096.l128", 256, source="autotune")
    assert st.lookup("fused_em_chunk", "tpu:tpu_v5_lite:1",
                     "k20.v8192.b4096.l128").value == 256


def test_invalid_cached_value_rejected(tmp_path):
    """A hand-edited/garbage value fails the knob's validator and
    resolution falls through to the default."""
    st = _store(tmp_path)
    st.record("fused_em_chunk", _fp(), "*", "not-an-int")
    st.record("host_sync_every", _fp("host_sync_every"), "*", -5)
    with use_store(st):
        assert resolve("fused_em_chunk", None)[1] == "default"
        assert resolve("host_sync_every", None)[1] == "default"


# ---------------------------------------------------------------------------
# resolution precedence
# ---------------------------------------------------------------------------


def test_explicit_config_override_wins(tmp_path):
    st = _store(tmp_path)
    st.record("fused_em_chunk", _fp(), "*", 64)
    with use_store(st):
        # Explicit (non-default) config beats the plan...
        assert resolve("fused_em_chunk", 32) == (32, "config")
        # ...a default-valued config yields to the plan...
        assert resolve("fused_em_chunk",
                       KNOBS["fused_em_chunk"].default) == (64, "plan")
        # ...and no entry at all means the default, by value.
        assert resolve("host_sync_every", KNOBS["host_sync_every"].default) \
            == (KNOBS["host_sync_every"].default, "default")


def test_disabled_plans_env_kills_lookups(tmp_path, monkeypatch):
    st = _store(tmp_path)
    st.record("fused_em_chunk", _fp(), "*", 64)
    monkeypatch.setenv("ONI_ML_TPU_PLANS", "0")
    # current_store() is None: resolve falls through even inside a
    # use_store scope.
    with use_store(st):
        assert plans.current_store() is None
        assert resolve("fused_em_chunk", 128)[1] == "default"
        assert plans.lookup_value("fused_em_chunk") is None


def test_null_store_disables_in_scope(tmp_path):
    st = _store(tmp_path)
    st.record("fused_em_chunk", _fp(), "*", 64)
    with use_store(NullStore()):
        assert plans.current_store() is None
    with use_store(st):
        assert resolve("fused_em_chunk", None) == (64, "plan")


def test_pre_workers_resolution(tmp_path):
    from oni_ml_tpu.features.shards import resolve_pre_workers

    st = _store(tmp_path)
    with use_store(st):
        # Explicit count is "config"; auto with no plan is cpu-count.
        assert resolve_pre_workers(3, with_source=True) == (3, "config")
        n, src = resolve_pre_workers(0, with_source=True)
        assert src == "default" and n >= 1
        st.record("pre_workers", plans.host_fingerprint(), "*", 2,
                  source="probe")
        assert resolve_pre_workers(0, with_source=True) == (2, "plan")
        # Tuple and scalar forms agree.
        assert resolve_pre_workers(0) == 2
        # An absurd operator-edited entry degrades to untuned — it must
        # not plan a million shards.
        st.record("pre_workers", plans.host_fingerprint(), "*",
                  1_000_000, source="probe")
        assert resolve_pre_workers(0, with_source=True)[1] == "default"
    with pytest.raises(ValueError):
        resolve_pre_workers(-1)


def test_dispatch_calibration_persists_across_processes(
    tmp_path, monkeypatch
):
    """The one inline autotune sweep: a fresh 'process' (cleared module
    cache) loads the recorded calibration (source 'plan') instead of
    re-measuring."""
    from oni_ml_tpu.scoring import score as score_mod

    monkeypatch.delenv("ONI_ML_TPU_SCORE_BREAK_EVEN", raising=False)
    monkeypatch.setenv("ONI_ML_TPU_PLAN_CACHE",
                       str(tmp_path / "cal.jsonl"))
    monkeypatch.setattr(score_mod, "_CALIBRATION", None)
    sweeps0 = plans.counters["autotune_sweeps"]
    cal = score_mod.dispatch_calibration()
    assert cal["source"] == "measured"
    assert plans.counters["autotune_sweeps"] == sweeps0 + 1
    # "New process": only the in-memory cache is cleared.
    monkeypatch.setattr(score_mod, "_CALIBRATION", None)
    cal2 = score_mod.dispatch_calibration()
    assert cal2["source"] == "plan"
    assert cal2["break_even"] == cal["break_even"]
    assert plans.counters["autotune_sweeps"] == sweeps0 + 1  # no re-sweep


def test_serving_batcher_resolves_plan_knobs(tmp_path):
    """BatchScorer picks plan-recorded max_batch/max_wait_ms when the
    config sits at defaults, and reports the source per knob."""
    from oni_ml_tpu.config import ServingConfig
    from oni_ml_tpu.serving import BatchScorer, ModelRegistry
    from oni_ml_tpu.runner.serve import _synthetic_day
    from oni_ml_tpu.serving.events import DnsEventFeaturizer

    rows, model, cuts = _synthetic_day(n_events=8)
    st = _store(tmp_path)
    st.record("serve_max_batch", _fp("serve_max_batch"), "*", 16,
              source="probe")
    with use_store(st):
        registry = ModelRegistry()
        registry.publish(model, source="test")
        scorer = BatchScorer(
            registry, DnsEventFeaturizer(cuts),
            ServingConfig(device_score_min=None),
        )
        try:
            assert scorer.max_batch == 16
            assert scorer.plan["max_batch"] == {
                "value": 16, "source": "plan"
            }
            assert scorer.plan["max_wait_ms"]["source"] == "default"
            # Explicit config still wins.
            scorer2 = BatchScorer(
                registry, DnsEventFeaturizer(cuts),
                ServingConfig(max_batch=4, device_score_min=None),
            )
            try:
                assert scorer2.max_batch == 4
                assert scorer2.plan["max_batch"]["source"] == "config"
            finally:
                scorer2.close()
            # A plan flush size past the backpressure bound would make
            # the max_batch trigger unreachable — it degrades to the
            # shipped default instead.
            st.record("serve_max_batch", _fp("serve_max_batch"), "*",
                      1 << 20, source="probe")
            scorer3 = BatchScorer(
                registry, DnsEventFeaturizer(cuts),
                ServingConfig(device_score_min=None),
            )
            try:
                assert scorer3.max_batch == ServingConfig.max_batch
                assert scorer3.plan["max_batch"]["source"] == "default"
            finally:
                scorer3.close()
        finally:
            scorer.close()


def test_serving_knob_resolution_is_host_scoped(tmp_path):
    """The serving flush triggers fingerprint the HOST, never the
    device: a host-pinned BatchScorer (device_score_min=None) must not
    initialize a jax backend at construction — a host-only service
    must start on a machine whose chip another process holds."""
    import subprocess
    import sys as _sys

    code = (
        "from oni_ml_tpu.config import ServingConfig\n"
        "from oni_ml_tpu.serving import BatchScorer, ModelRegistry\n"
        "from oni_ml_tpu.runner.serve import _synthetic_day\n"
        "from oni_ml_tpu.serving.events import DnsEventFeaturizer\n"
        "rows, model, cuts = _synthetic_day(n_events=8)\n"
        "reg = ModelRegistry(); reg.publish(model, source='t')\n"
        "s = BatchScorer(reg, DnsEventFeaturizer(cuts),\n"
        "                ServingConfig(device_score_min=None))\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, list(xb._backends)\n"
        "s.close()\n"
        "print('ok')\n"
    )
    env = dict(os.environ)
    env["ONI_ML_TPU_PLAN_CACHE"] = str(tmp_path / "p.jsonl")
    proc = subprocess.run(
        [_sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ok" in proc.stdout


def test_dense_block_plan_validated(tmp_path):
    """A planned doc block is only a candidate: it must divide the
    batch and fit the VMEM model, else the analytic pick stands."""
    from oni_ml_tpu.ops import dense_estep

    b, v, k = 256, 1024, 20
    analytic = dense_estep.pick_block(b, v, k)
    analytic_w = dense_estep.pick_block_w(b, v, k)
    st = _store(tmp_path)
    shape = f"b{b}.v{v}.k{k}.f32"
    with use_store(st):
        st.record("dense_estep_block", _fp("dense_estep_block"),
                  shape, 32, source="probe")
        assert dense_estep.pick_block(b, v, k) == 32
        # Non-dividing block: rejected, analytic prior wins.
        st.record("dense_estep_block", _fp("dense_estep_block"),
                  shape, 100, source="probe")
        assert dense_estep.pick_block(b, v, k) == analytic
        # W-major: a non-multiple-of-128 planned block (other than the
        # full batch) is illegal for the lane layout — rejected.
        st.record("dense_estep_block_w", _fp("dense_estep_block_w"),
                  shape, 64, source="probe")
        assert dense_estep.pick_block_w(b, v, k) == analytic_w
        # A legal W-major plan (the full batch) is honored.
        st.record("dense_estep_block_w", _fp("dense_estep_block_w"),
                  shape, b, source="probe")
        assert dense_estep.pick_block_w(b, v, k) == b


def test_recorded_plan_resolves_on_matching_backend(monkeypatch, tmp_path):
    """The bench acceptance: on a backend with a recorded chunk sweep
    the headline chunk loads from the plan instead of re-deriving —
    and a machine without one runs the default."""
    monkeypatch.setenv("ONI_ML_TPU_PLAN_CACHE",
                       str(tmp_path / "p.jsonl"))
    import bench

    chunk, src = bench._headline_chunk()
    assert (chunk, src) == (KNOBS["fused_em_chunk"].default, "default")
    monkeypatch.setattr(plans, "_DEVICE_FP", "tpu:tpu_v5_lite:1")
    plans.record_value("fused_em_chunk", 64,
                       shape="k20.v8192.b4096.l128",
                       measurements={"64": 2.0, "128": 1.0})
    chunk, src = bench._headline_chunk()
    assert (chunk, src) == (64, "plan")
    payload = bench.bench_plans_payload()
    assert payload["knobs"]["fused_em_chunk"]["source"] == "plan"
    entries = payload["knobs"]["fused_em_chunk"]["entries"]
    assert any(
        e["entry_source"] == "autotune"
        and e.get("measurements", {}).get("64") == 2.0
        for e in entries
    )


# ---------------------------------------------------------------------------
# autotune harness
# ---------------------------------------------------------------------------


def test_autotune_budget_respected_under_fake_clock(tmp_path):
    """Budget is wall-clock: with a 10s-per-measure fake clock and a
    25s budget, exactly three candidates run (the first always does),
    the result is marked truncated, and the winner + measurements are
    recorded with provenance."""
    st = _store(tmp_path)
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    measured = []

    def measure(c):
        measured.append(c)
        return float(c)       # bigger candidate, better rate

    with use_store(st):
        res = autotune("score_device_chunk", measure, budget_s=25.0,
                       clock=clock, mode="max")
    assert measured == [8192, 16384, 32768]
    assert res.truncated and res.value == 32768
    e = st.lookup("score_device_chunk", _fp("score_device_chunk"))
    assert e.value == 32768
    assert e.record["truncated"] is True
    assert e.record["budget_s"] == 25.0
    assert e.measurements == {"8192": 8192.0, "16384": 16384.0,
                              "32768": 32768.0}


def test_autotune_unbounded_sweeps_whole_space(tmp_path):
    st = _store(tmp_path)
    with use_store(st):
        res = autotune("serve_max_batch", lambda c: -float(c),
                       mode="min", record=False)
    assert res.value == max(KNOBS["serve_max_batch"].candidates)
    assert not res.truncated
    assert st.lookup("serve_max_batch", _fp("serve_max_batch")) is None


def test_autotune_first_candidate_always_completes(tmp_path):
    """A zero budget still measures one candidate — a plan with no
    measurements is not a plan."""
    st = _store(tmp_path)
    t = [0.0]

    def clock():
        t[0] += 100.0
        return t[0]

    with use_store(st):
        res = autotune("score_device_chunk", float, budget_s=0.0,
                       clock=clock)
    assert len(res.measurements) == 1 and res.truncated


def test_autotune_counts_sweeps():
    before = plans.counters["autotune_sweeps"]
    with use_store(NullStore()):
        autotune("serve_max_batch", float, candidates=(1, 2),
                 record=False)
    assert plans.counters["autotune_sweeps"] == before + 1


# ---------------------------------------------------------------------------
# compile-cache warmup + counters
# ---------------------------------------------------------------------------


def test_warmup_scoring_hits_cache_second_time(tmp_path, monkeypatch):
    """AOT warmup populates the persistent compilation cache; once
    warm, no path re-traces: an in-process re-warm is served from
    memory (zero requests), and a FRESH jit wrapper of the same
    program (the cross-process shape) is a persistent-cache hit — the
    counter contract the runner's plans record relies on."""
    import jax
    import numpy as np

    from oni_ml_tpu.plans import warmup

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    cc = warmup.setup_compilation_cache()
    assert cc["enabled"] and cc["counting"] and cc["dir"] == str(
        tmp_path / "cc")
    w1 = warmup.warmup_scoring(101, 51, 5, 256, dsource="flow")
    assert w1["compiled"] == 1
    assert w1["compile_requests"] >= w1["compiled"]
    w2 = warmup.warmup_scoring(101, 51, 5, 256, dsource="flow")
    assert w2["traces"] == 0
    assert warmup.cache_entries(cc["dir"]) > 0

    # Cross-process shape: a fresh jit wrapper (new trace, same
    # program) must be served by the persistent cache, not recompiled.
    def mk():
        def plans_probe_fn(a, b):
            return (a * b).sum(-1)

        return jax.jit(plans_probe_fn)

    x = jax.ShapeDtypeStruct((16, 3), np.float32)
    mk().lower(x, x).compile()            # first: trace + serialize
    before = warmup.compile_counts()
    mk().lower(x, x).compile()            # fresh wrapper: cache HIT
    delta = warmup.counts_delta(before)
    assert delta["compile_requests"] >= 1
    assert delta["traces"] == 0
    assert delta["cache_hits"] == delta["compile_requests"]


def test_warmup_serving_respects_host_pin(monkeypatch):
    """When the calibration pins the host path the serving warmup
    compiles nothing (there is no device program the stream could
    reach)."""
    from oni_ml_tpu.plans import warmup

    out = warmup.warmup_serving(101, 51, 5, 1024, None)
    assert out == {"compiled": 0, "reason": "host path pinned"}
    from oni_ml_tpu.scoring import score as score_mod

    monkeypatch.setattr(score_mod, "_CALIBRATION",
                        {"break_even": 64, "source": "test"})
    out = warmup.warmup_serving(101, 51, 5, 256, 0)
    # pow2 shapes 64, 128, 256
    assert out["compiled"] == 3


# ---------------------------------------------------------------------------
# runner e2e: second run re-sweeps and re-traces nothing; plans on/off
# byte-identical
# ---------------------------------------------------------------------------


def _flow_day_file(tmp_path) -> str:
    import numpy as np

    rng = np.random.default_rng(7)
    lines = []
    for _ in range(60):
        lines.append(flow_row(
            hour=int(rng.integers(0, 24)),
            minute=int(rng.integers(0, 60)),
            second=int(rng.integers(0, 60)),
            sip=f"10.0.0.{rng.integers(1, 9)}",
            dip=f"172.16.0.{rng.integers(1, 9)}",
            col10=str(rng.choice([80, 443, 55000])),
            col11=str(rng.choice([80, 6000])),
            ipkt=str(rng.integers(1, 100)),
            ibyt=str(rng.integers(40, 10000)),
        ))
    raw = tmp_path / "flow.csv"
    raw.write_text("\n".join(lines) + "\n")
    return str(raw)


def _run_day(raw, data_dir, plan_cache, jax_cache, extra=()):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "ONI_ML_TPU_PLAN_CACHE": plan_cache,
        "JAX_COMPILATION_CACHE_DIR": jax_cache,
    })
    cmd = [
        sys.executable, "-m", "oni_ml_tpu.runner.ml_ops",
        "20160122", "flow", "1.1",
        "--flow-path", raw, "--data-dir", str(data_dir),
        "--em-max-iters", "2", "--batch-size", "64",
        "--pre-workers", "1", "--force", *extra,
    ]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(os.path.join(data_dir, "20160122", "metrics.json")) as f:
        return json.load(f)


def test_second_run_zero_sweeps_zero_retraces_and_plans_off_parity(
    tmp_path,
):
    """The acceptance contract, end to end in real processes:

    - run 1 (plans on, cold compile cache) populates the caches;
    - run 2 (same backend+shapes, fresh process) performs ZERO autotune
      sweeps and ZERO re-traces — every XLA compile request is a
      persistent-cache hit, asserted via the runner's plans record;
    - run 3 (--no-plans --no-compilation-cache) produces byte-identical
      word_counts.dat / flow_results.csv to run 2: measured plans are a
      throughput layer, never a semantics layer.
    """
    raw = _flow_day_file(tmp_path)
    plan_cache = str(tmp_path / "plans.jsonl")
    jax_cache = str(tmp_path / "jax_cache")
    d1, d2, d3 = (tmp_path / n for n in ("run1", "run2", "run3"))

    m1 = _run_day(raw, d1, plan_cache, jax_cache)
    rec1 = next(m for m in m1 if m.get("stage") == "plans")
    assert rec1["compilation_cache"]["enabled"]

    m2 = _run_day(raw, d2, plan_cache, jax_cache)
    rec2 = next(m for m in m2 if m.get("stage") == "plans")
    assert rec2["autotune_sweeps"] == 0
    if rec2["compilation_cache"].get("counting"):
        assert rec2["compile_requests"] > 0
        assert rec2["traces"] == 0          # zero re-traces
        assert rec2["cache_hits"] == rec2["compile_requests"]
    # Knob sources are named per stage.
    lda2 = next(m for m in m2 if m.get("stage") == "lda")
    assert lda2["plans"]["fused_em_chunk"]["source"] in (
        "default", "plan"
    )
    pre2 = next(m for m in m2 if m.get("stage") == "pre")
    assert pre2["plans"]["pre_workers"] == {
        "value": 1, "source": "config"
    }

    m3 = _run_day(raw, d3, plan_cache, jax_cache,
                  extra=("--no-plans", "--no-compilation-cache"))
    rec3 = next(m for m in m3 if m.get("stage") == "plans")
    assert rec3["enabled"] is False
    assert rec3["compilation_cache"] == {"enabled": False}

    for name in ("word_counts.dat", "flow_results.csv",
                 "doc_results.csv", "word_results.csv"):
        a = (d2 / "20160122" / name).read_bytes()
        b = (d3 / "20160122" / name).read_bytes()
        assert a == b, f"{name} differs between plans-on and plans-off"


def test_lda_stage_records_plan_sources(tmp_path):
    """In-process: a plan entry for fused_em_chunk is picked up by the
    trainer (source 'plan'), and an explicit config override beats it
    (source 'config') — surfaced through the lda stage record."""
    from oni_ml_tpu.config import LDAConfig, PipelineConfig, PlansConfig
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    raw = _flow_day_file(tmp_path)
    plan_path = str(tmp_path / "plans.jsonl")
    st = PlanStore(plan_path)
    st.record("fused_em_chunk", _fp(), "*", 2, source="probe")
    st.close()

    def run(data_dir, lda):
        cfg = PipelineConfig(
            data_dir=str(data_dir), flow_path=raw, lda=lda,
            pre_workers=1,
            plans=PlansConfig(cache_path=plan_path,
                              compilation_cache=False),
        )
        metrics = run_pipeline(cfg, "20160122", "flow", force=True)
        return next(m for m in metrics if m.get("stage") == "lda")

    lda_rec = run(tmp_path / "p",
                  LDAConfig(em_max_iters=2, batch_size=64))
    assert lda_rec["plans"]["fused_em_chunk"] == {
        "value": 2, "source": "plan"
    }
    lda_rec = run(tmp_path / "c",
                  LDAConfig(em_max_iters=2, batch_size=64,
                            fused_em_chunk=4))
    assert lda_rec["plans"]["fused_em_chunk"] == {
        "value": 4, "source": "config"
    }
    assert lda_rec["plans"]["host_sync_every"]["source"] == "default"


# ---------------------------------------------------------------------------
# compilation-cache placement: one rule, set from outside
# ---------------------------------------------------------------------------


def test_cache_dir_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, IS the directory — no
    config field or flag names another; unset, the cache is the fixed
    <checkout>/.jax_cache: nothing derived from the home directory, a
    temp dir, a pid or the clock."""
    import dataclasses

    from oni_ml_tpu.config import PlansConfig
    from oni_ml_tpu.plans import warmup

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert warmup.cache_dir() == str(tmp_path / "env")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert warmup.cache_dir() == os.path.join(repo, ".jax_cache")
    assert warmup.cache_dir() == warmup.cache_dir()
    assert "compilation_cache_dir" not in {
        f.name for f in dataclasses.fields(PlansConfig)}
    import inspect

    assert list(inspect.signature(
        warmup.setup_compilation_cache).parameters) == ["enabled"]


_CACHE_CHILD = (
    "import json, os, sys\n"
    "import jax, jax.numpy as jnp\n"
    "from oni_ml_tpu.plans import warmup\n"
    "rec = warmup.setup_compilation_cache()\n"
    "before = warmup.compile_counts()\n"
    "jax.block_until_ready("
    "jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((32, 32))))\n"
    "print(json.dumps({'dir': rec['dir'],"
    " 'jax_dir': jax.config.jax_compilation_cache_dir,"
    " **warmup.counts_delta(before)}))\n"
)


def test_cache_set_from_outside_is_the_only_dir_written(tmp_path):
    """Two processes with JAX_COMPILATION_CACHE_DIR set: both use that
    directory (jax's own config says so too), the second is served by
    what the first wrote, and nothing lands anywhere else."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache = tmp_path / "outside"
    home = tmp_path / "home"
    home.mkdir()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               HOME=str(home), XDG_CACHE_HOME=str(home / ".cache"),
               JAX_PLATFORMS="cpu",
               ONI_ML_TPU_PLAN_CACHE=str(tmp_path / "plans.jsonl"))
    runs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD], capture_output=True,
            text=True, env=env, cwd=repo, timeout=180)
        assert proc.returncode == 0, proc.stderr[-800:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    for rec in runs:
        assert rec["dir"] == rec["jax_dir"] == str(cache)
    assert runs[0]["traces"] >= 1
    assert runs[1]["cache_hits"] >= 1 and runs[1]["traces"] == 0
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    assert not (home / ".cache").exists()

