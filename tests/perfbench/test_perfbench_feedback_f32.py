"""The day after analyst feedback on the default 4-byte store: configuration
`flow20_feedback_f32`, cell `flow20_feedback_f32_fit`, `flow20_fit`'s day
with `flow20_feedback`'s DUPFACTOR=1000 rows folded in, stored float32.
Its entries by name (the same rule on the guard's appended copy of
BENCHMARK.json), its files against `flow20_fit`'s and `flow20_feedback`'s
(job `fit_feedback`, reused whole), `products_gate_s` on hand-made traces,
and the program through the cell at a tiny size on the CPU on the
full-width dense plan, float32 store and packed products, `correct`
against the plain reference under `flow20_fit`'s limits; a program that
makes no such decision runs the cell to its line too, and `correct` is
what judges it."""

import argparse

import pytest
from unittest import mock

import entry_rules
from benchmarks import rehearse, run as bench_run
from benchmarks.harness import cells, fit_check, xplane
from test_perfbench_contract import appended  # noqa: F401  (the guard)

CELL, CONFIG = "flow20_feedback_f32_fit", "flow20_feedback_f32"
FEEDBACK, FEEDBACK_CONFIG = "flow20_feedback_fit", "flow20_feedback"
METRIC = "products_gate_s"


# -- the entries: one rule by name ---------------------------------------

def feedback_f32_cell_configuration_and_metrics(bench):
    found = cells.resolve(CELL)
    assert found["cell"] == entry_rules.by_name(bench, "workloads")[CELL]
    assert (found["cell"]["config"], found["cell"]["traffic"],
            found["cell"]["chips"]) == (
        CONFIG, "resident_163840_feedback", 1)
    assert entry_rules.in_order(bench, "workloads", [FEEDBACK, CELL])
    assert entry_rules.in_order(bench, "configs", [FEEDBACK_CONFIG, CONFIG])
    entry = entry_rules.by_name(bench, "configs")[CONFIG]
    config, traffic = found["config"], found["traffic"]
    assert entry["reduced"] == config["reduced"] == []
    # the reference engine's arithmetic (exact 4-byte counts, full-precision
    # sums) on the feedback loop's day: no other configuration cites its
    # repository
    feedback = cells.resolve(FEEDBACK)
    assert entry["source"] == config["source"]
    url = entry["source"].split()[0]
    assert url == "https://github.com/OpenNetworkInsight/oni-lda-c"
    for cited in ("ml_ops.sh:80", "int counts",
                  "flow_pre_lda.scala:253-268", "DUPFACTOR=1000"):
        assert cited in entry["source"], cited
    assert len(entry["source"]) <= 200

    def repository(source):
        return "/".join(source.split()[0].split("/")[2:5]).lower()

    assert [c["name"] for c in bench["configs"]
            if repository(c["source"]) == repository(url)] == [CONFIG]
    flow20 = cells.resolve(entry_rules.FIT)
    # flow20's width and LDA, flow20_feedback's feedback, the default store
    assert config["num_terms"] == flow20["config"]["num_terms"] == 8192
    assert config["lda"] == flow20["config"]["lda"]
    assert config["feedback"] == feedback["config"]["feedback"]
    assert config["program"] == {"estep_engine": "auto",
                                 "dense_hbm_budget": 12 * 2**30,
                                 "dense_precision": "f32"}
    assert config["precision"]["stated"].startswith(
        "corpus stored float32, 4 B a cell")
    assert "float32 accuracy" in config["precision"]["stated"]
    assert config["precision"]["control"] == (
        flow20["config"]["precision"]["control"])
    for key in ("day", "feedback"):
        assert "NOT a sourced figure" in config["assumed"][key], key
    # resident_163840's day, batches, steps and limits, with the feedback
    # of resident_458752_feedback
    for key in ("corpus", "num_docs", "batch_size", "mesh", "trace_fits",
                "reference_block_docs", "check_steps", "limits"):
        assert traffic[key] == flow20["traffic"][key], key
    assert {k: traffic["feedback"][k] for k in
            ("rows", "dup_factor", "cells", "cell_max")} == {
        k: feedback["traffic"]["feedback"][k] for k in
        ("rows", "dup_factor", "cells", "cell_max")} == {
        "rows": 1000, "dup_factor": 1000, "cells": 500, "cell_max": 32255}
    assert (traffic["job"], traffic["store_limit"], traffic["num_docs"],
            traffic["trace_fits"]) == (
        "fit_feedback", 0, 163840, 2)
    metrics = entry_rules.by_name(bench, "per_layer")
    assert metrics[METRIC] == {
        "name": METRIC, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "corpus placement",
        "moves": "fit_s", "workloads": [CELL]}
    assert entry_rules.in_order(bench, "per_layer",
                                ["feedback_gate_s", METRIC])
    reported = {m["name"] for m in found["per_layer"]}
    assert {METRIC, "estep_roofline", "em_mfu", "place_plan_s",
            "place_densify_s"} <= reported
    # the 2-byte stores' readers are their cells'
    assert not {"place_gate_s", "estep_store_roofline",
                "feedback_store_roofline", "feedback_gate_s",
                "collective_exposed_pct", "est_load_s"} & reported
    for other in (entry_rules.FIT, entry_rules.DP4, entry_rules.EST,
                  "flow20_bigday_fit", FEEDBACK):
        assert METRIC not in {
            m["name"] for m in cells.resolve(other)["per_layer"]}


def test_the_cell_its_configuration_and_its_metric_as_named():
    feedback_f32_cell_configuration_and_metrics(entry_rules.load())


def test_the_rule_minds_no_appended_cell(appended, monkeypatch):  # noqa: F811
    monkeypatch.setattr(cells, "ROOT", appended)
    monkeypatch.setattr(cells, "BENCH_DIR", appended + "/benchmarks")
    bench = entry_rules.load()
    assert [w["name"] for w in bench["workloads"]][-1] == "flow20_guard_fit"
    feedback_f32_cell_configuration_and_metrics(bench)


# -- the reader ------------------------------------------------------------

def _gate_spans(t, accurate=True, gate=True):
    counts = {"store": "float32", "rung": "f32", "cell_scan": "none",
              "over_bf16_tokens": 7}
    if accurate:
        counts["accurate"] = 1
    rows = [("fit", t + 0.5, 8.0, {}),
            ("fit.plan", t + 1.0, 2.0, {}),
            ("fit.plan.gate", t + 1.25, 0.25, {}),
            ("fit.plan.gate.counts", t + 1.4, 0.0, counts)]
    if not gate:
        rows = rows[:2]
    return [(n, s, d, stats, "python") for n, s, d, stats in rows]


def _ctx(accurate=True, gate=True):
    def ops(t):
        return [("tpu_custom_call.1 f32[8,20] custom-call", t + 6.0, 1.5)]

    events = {
        "devices": {0: ops(0.0) + ops(10.0)},
        "modules": {0: [("jit_run_chunk_dispatch(1)", 6.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 16.0, 2.0)]},
        "annotations": [("fit", 0.0, 10.0), ("fit", 10.0, 10.0)],
    }
    spans = sorted(_gate_spans(0.0, accurate, gate)
                   + _gate_spans(10.0, accurate, gate)
                   # another thread's gate
                   + [("fit.plan.gate", 1.0, 4.0, {}, "worker")],
                   key=lambda e: e[1])
    return {"trace": dict(xplane.reduce_events(events), rehearsal=False),
            "chips": 1, "program_trace": {"spans": spans, "scopes": {}},
            "trace_dir": "/x/.bench_trace/" + CELL}


def _read(ctx):
    return cells.load_module("metrics", METRIC).read(ctx)


def test_products_gate_s_is_the_gates_seconds_a_fit():
    assert _read(_ctx()) == pytest.approx(0.25)
    assert _read(_ctx()) == cells.load_module(
        "metrics", "place_gate_s").read(_ctx())


@pytest.mark.parametrize("accurate, gate", [(False, True), (True, False)],
                         ids=["gate_without_the_decision", "no_gate"])
def test_products_gate_s_gives_nothing_without_the_decision(accurate, gate):
    assert _read(_ctx(accurate=accurate, gate=gate)) is None


# -- the program through the cell, tiny, on the full-width dense plan -----

SEED = 2**31 + 4849


def _tiny_found(num_docs=3072):
    """The cell at the rehearsal's width and `num_docs` documents, its
    feedback in the proportion of flow20_feedback_fit's rehearsal (4 cells,
    8 rows), with its budget over the day's 4 B dense size."""
    from benchmarks.harness import rehearse_compile
    from oni_ml_tpu.ops import dense_estep

    found = rehearse.shrink(cells.resolve(CELL))
    found["traffic"]["num_docs"] = num_docs
    found["traffic"]["feedback"].update(cells=4, rows=8)
    v = found["config"]["num_terms"]
    shapes = rehearse_compile.batch_shapes(found["traffic"], v, 1)
    rows = sum(b for b, _ in shapes)
    sparse = sum(b * length * 8 for b, length in shapes)
    found["config"]["program"]["dense_hbm_budget"] = (
        5 * rows * dense_estep.padded_width(v) + sparse)
    return found


def _run_on_a_stood_in_tpu(found, override=None):
    """run_cell of the cell with `on_tpu` stood in for while the plan is
    made (its scoped-VMEM option is the chip compiler's and is left out);
    `override` replaces fields of every plan.  The plans made, and the
    line."""
    import jax

    from oni_ml_tpu.models import LDATrainer

    plans, real = [], LDATrainer._plan_estep

    def on_a_stood_in_tpu(self, batches):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            plan = real(self, batches)._replace(compiler_options=None,
                                                **(override or {}))
        plans.append(plan)
        return plan

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.0,
                              trace=0)
    with mock.patch.object(LDATrainer, "_plan_estep", on_a_stood_in_tpu):
        line = bench_run.run_cell(
            args, {"platform": "cpu", "kind": "rehearsal", "count": 1},
            found)
    return plans, line


def test_the_cell_rehearsed_on_the_dense_float32_plan_is_correct():
    """The plan takes full-width dense with the float32 store, says the
    day's cells pass 256 and takes the packed products; the kernels run
    interpreted, and the fit is `correct` against the plain reference
    under `flow20_fit`'s limits, `store_exact` 0."""
    import jax.numpy as jnp

    plans, line = _run_on_a_stood_in_tpu(_tiny_found())
    assert plans and {(p.family, p.store, p.accurate) for p in plans} == {
        ("dense", jnp.float32, True)}
    assert line["correct"] is True
    assert set(line["compared"]) == set(fit_check.NUMBERS) | {"store_exact"}
    limits = cells.resolve(entry_rules.FIT)["traffic"]["limits"]
    for name, row in line["compared"].items():
        assert row["limit"] == limits.get(name, 0)
    assert line["compared"]["store_exact"]["value"] == 0.0


def test_a_program_that_takes_default_products_runs_the_cell_to_its_line():
    """The same cell with the plan's decision taken away (what a program
    without it does: XLA's DEFAULT products on the float32 store) runs to
    its line: the job holds the program to nothing but `correct`, which
    the chip's bfloat16 operands fail and the CPU's float32 ones pass."""
    plans, line = _run_on_a_stood_in_tpu(_tiny_found(768),
                                         override={"accurate": False})
    assert plans and not any(p.accurate for p in plans)
    assert set(line["compared"]) == set(fit_check.NUMBERS) | {"store_exact"}
    assert line["correct"] is True
