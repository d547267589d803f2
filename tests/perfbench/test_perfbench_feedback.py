"""The day after analyst feedback: configuration `flow20_feedback`, cell
`flow20_feedback_fit`, `flow20_bigday_fit`'s day with DUPFACTOR=1000
feedback rows folded in, which one chip holds only at 2 B a cell and which
bfloat16 does not hold exactly.  Its entries by name (the same rule on the
guard's appended copy of BENCHMARK.json), its files against
`flow20_bigday`'s, the feedback generator, the job's `store_exact`, its two
readers on hand-made traces, and the program through the cell at a tiny
size on the CPU on the full-width dense plan with the int16 store,
`correct` against the plain reference under `flow20_fit`'s limits."""

import argparse
import copy

import numpy as np
import pytest
from unittest import mock

import entry_rules
from benchmarks import rehearse, run as bench_run
from benchmarks.harness import cells, corpus_gen, fit_check, xplane
from benchmarks.jobs import fit as fit_job, fit_feedback
from benchmarks.metrics import estep_store_roofline
from test_perfbench_contract import appended  # noqa: F401  (the guard)

CELL, CONFIG = "flow20_feedback_fit", "flow20_feedback"
BIGDAY, BIGDAY_CONFIG = "flow20_bigday_fit", "flow20_bigday"
NEW_METRICS = ("feedback_store_roofline", "feedback_gate_s")


# -- the entries: one rule by name ---------------------------------------

def feedback_cell_configuration_and_metrics(bench):
    found = cells.resolve(CELL)
    assert found["cell"] == entry_rules.by_name(bench, "workloads")[CELL]
    assert (found["cell"]["config"], found["cell"]["traffic"],
            found["cell"]["chips"]) == (CONFIG, "resident_458752_feedback", 1)
    entry = entry_rules.by_name(bench, "configs")[CONFIG]
    config, traffic = found["config"], found["traffic"]
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    for where in ("ml_ops.sh:26,31,80", "flow_pre_lda.scala:150-171,253-268"):
        assert where in entry["source"]
    bigday = cells.resolve(BIGDAY)
    # flow20_bigday's letter for letter, the bf16 opt-in included
    for key in ("num_terms", "lda", "program"):
        assert config[key] == bigday["config"][key], key
    assert config["program"]["dense_precision"] == "bf16"
    assert (config["feedback"]["dup_factor"],
            config["feedback"]["severity"]) == (1000, 3)
    assert config["precision"]["control"] == (
        bigday["config"]["precision"]["control"])
    assert config["precision"]["stated"].startswith(
        "corpus stored at 2 B a cell, exact")
    assert "never rounded" in config["deployment"]["guarantee"]
    assert "NOT a sourced figure" in config["assumed"]["feedback"]
    # resident_458752's day, batches, steps and limits, with the feedback
    for key in ("corpus", "num_docs", "batch_size", "mesh", "trace_fits",
                "reference_block_docs", "check_steps", "limits"):
        assert traffic[key] == bigday["traffic"][key], key
    assert traffic["job"] == "fit_feedback" and traffic["store_limit"] == 0
    assert {k: traffic["feedback"][k] for k in
            ("rows", "dup_factor", "cells", "cell_max")} == {
        "rows": 1000, "dup_factor": 1000, "cells": 500, "cell_max": 32255}
    metrics = entry_rules.by_name(bench, "per_layer")
    assert metrics["feedback_store_roofline"] == {
        "name": "feedback_store_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "E-step kernels",
        "moves": "em_docs_per_s", "workloads": [CELL]}
    assert metrics["feedback_gate_s"] == {
        "name": "feedback_gate_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "corpus placement",
        "moves": "fit_s", "workloads": [CELL]}
    assert entry_rules.in_order(bench, "per_layer",
                                ["estep_store_roofline", *NEW_METRICS])
    reported = {m["name"] for m in found["per_layer"]}
    assert set(NEW_METRICS) | {"estep_roofline", "em_mfu", "place_plan_s",
                               "place_densify_s"} <= reported
    # the big day's two readers are its own; these two are this cell's
    assert not {"place_gate_s", "estep_store_roofline",
                "collective_exposed_pct", "est_load_s"} & reported
    for other in (entry_rules.FIT, entry_rules.DP4, entry_rules.EST, BIGDAY):
        assert not set(NEW_METRICS) & {
            m["name"] for m in cells.resolve(other)["per_layer"]}


def test_the_cell_its_configuration_and_its_metrics_as_the_issue_names_them():
    feedback_cell_configuration_and_metrics(entry_rules.load())


def test_the_rule_minds_no_appended_cell(appended, monkeypatch):  # noqa: F811
    monkeypatch.setattr(cells, "ROOT", appended)
    monkeypatch.setattr(cells, "BENCH_DIR", appended + "/benchmarks")
    bench = entry_rules.load()
    assert [w["name"] for w in bench["workloads"]][-1] == "flow20_guard_fit"
    feedback_cell_configuration_and_metrics(bench)


# -- the feedback generator -------------------------------------------------

def _tiny_traffic():
    return rehearse.shrink(cells.resolve(CELL))


SEED = 2**31 + 4243


@pytest.fixture(scope="module")
def tiny_days():
    found = _tiny_traffic()
    traffic, v = found["traffic"], found["config"]["num_terms"]
    plain = rehearse.shrink(cells.resolve(BIGDAY))["traffic"]
    return (traffic, v, corpus_gen.make_corpus(plain, v, SEED),
            fit_feedback.feedback_corpus(traffic, v, SEED))


def test_the_feedback_day_is_the_big_days_with_2000_hits_on_500_pairs(
        tiny_days):
    traffic, v, day, fed = tiny_days
    # the same documents, words and lengths: the shapes are the big day's
    np.testing.assert_array_equal(fed.doc_ptr, day.doc_ptr)
    np.testing.assert_array_equal(fed.word_idx, day.word_idx)
    added = fed.counts.astype(np.int64) - day.counts
    hit = np.flatnonzero(added)
    assert 0 < len(hit) <= 500 and (added >= 0).all()
    assert (added % 1000 == 0).all()
    assert added.sum() == 2000 * 1000           # 1,000 rows, two cells each
    assert fed.counts.max() <= 32255 and fed.counts.max() > 256


def test_the_feedback_day_is_the_seeds_alone(tiny_days):
    traffic, v, _, fed = tiny_days
    again = fit_feedback.feedback_corpus(traffic, v, SEED)
    np.testing.assert_array_equal(again.counts, fed.counts)
    other = fit_feedback.feedback_corpus(traffic, v, SEED + 1)
    assert not np.array_equal(other.word_idx, fed.word_idx)


def test_the_generator_refuses_a_cell_over_32255():
    traffic = copy.deepcopy(_tiny_traffic()["traffic"])
    traffic["feedback"]["cells"] = 1     # 2,000 hits on one cell
    with pytest.raises(AssertionError, match="over cell_max 32255"):
        fit_feedback.feedback_corpus(traffic, 512, SEED)


@pytest.mark.parametrize("store, exact", [
    (None, 0.0), ("int16", 0.0), ("float32", 0.0), ("bfloat16", 1.0),
    ("uint8", 1.0), ("float16", 1.0)])
def test_store_exact_is_the_stores_range_against_the_largest_cell(
        tiny_days, store, exact):
    *_, fed = tiny_days
    plan = {"corpus_store": {"value": store}}
    assert fit_feedback.store_exact(plan, fed) == exact
    assert fit_feedback.store_exact({}, fed) == 0.0
    assert [fit_feedback.exact_up_to(n) for n in (
        "bfloat16", "int16", "float32")] == [256, 32767, 2**24]


def _tiny_found(num_docs=3072):
    """The cell at the rehearsal's width and `num_docs` documents, its
    feedback in the proportion of the full cell's (500 cells of 458,752
    documents) and not the 500 cells the tiny day would hold: at 768
    documents 500 cells are a third of the day and its topics, and then
    no path of the program stays near the reference."""
    found = rehearse.shrink(cells.resolve(CELL))
    found["traffic"]["num_docs"] = num_docs
    found["traffic"]["feedback"].update(cells=4, rows=8)
    return found


def test_a_bfloat16_store_over_cells_above_256_is_not_correct():
    """The plain reference in the program's place reporting a bfloat16
    store: every other number is its own and reads 0, `store_exact` 1."""
    def bf16_store(fit):
        fit.plan = {"corpus_store": {"value": "bfloat16"}}

    from benchmarks.reference.lda_plain import Faults

    found = _tiny_found(768)
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.0, trace=0)
    line = bench_run.run_cell(
        args, {"platform": "cpu", "kind": "rehearsal", "count": 1}, found,
        program=fit_job.fake_program(Faults(alter_answer=bf16_store),
                                     block_docs=256))
    assert line["compared"]["store_exact"] == {"value": 1.0, "limit": 0}
    assert line["correct"] is False
    assert all(row["value"] <= row["limit"]
               for name, row in line["compared"].items()
               if name != "store_exact")


# -- the readers -----------------------------------------------------------

def _gate_spans(t, gate=True, rung=True):
    counts = {"store": "int16", "cell_scan": "bounds",
              "over_bf16_tokens": 491}
    if rung:
        counts["rung"] = "int16"
    rows = [("fit", t + 0.5, 8.0, {}),
            ("fit.plan", t + 1.0, 2.0, {}),
            ("fit.plan.gate", t + 1.25, 1.5, {}),
            ("fit.plan.gate.counts", t + 2.7, 0.0, counts)]
    if not gate:
        rows = rows[:2]
    return [(n, s, d, stats, "python") for n, s, d, stats in rows]


def _ctx(gate=True, rung=True, store="int16", kernel_s=1.5, cell=CELL):
    def ops(t):
        return [("fusion.1 f32[8] fusion", t + 3.0, 0.5),
                ("tpu_custom_call.1 f32[8,20] custom-call", t + 6.0,
                 kernel_s)]

    events = {
        "devices": {0: ops(0.0) + ops(10.0)},
        "modules": {0: [("jit_run_chunk_dispatch(1)", 6.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 16.0, 2.0)]},
        "annotations": [("fit", 0.0, 10.0), ("fit", 10.0, 10.0)],
    }
    spans = sorted(_gate_spans(0.0, gate, rung) + _gate_spans(10.0, gate, rung)
                   # another thread's gate, a fit outside the window
                   + [("fit.plan.gate", 1.0, 4.0, {}, "worker"),
                      ("fit", 30.0, 1.0, {}, "python")],
                   key=lambda e: e[1])
    return {"trace": dict(xplane.reduce_events(events), rehearsal=False),
            "chips": 1, "program_trace": {"spans": spans, "scopes": {}},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "doc_iters": 2 * 13 * 4096.0, "num_terms": 8192,
            "num_topics": 20, "trace_dir": "/x/.bench_trace/" + cell,
            "plan": {"corpus_store": {"value": store}}}


def _read(name, ctx):
    return cells.load_module("metrics", name).read(ctx)


def test_feedback_gate_s_is_the_ladders_seconds_a_fit():
    assert _read("feedback_gate_s", _ctx()) == pytest.approx(1.5)
    assert _read("feedback_gate_s", _ctx()) == _read("place_gate_s", _ctx())


@pytest.mark.parametrize("gate, rung", [(False, True), (True, False)],
                         ids=["no_gate", "gate_without_the_ladder"])
def test_feedback_gate_s_gives_nothing_without_the_ladder(gate, rung):
    assert _read("feedback_gate_s", _ctx(gate=gate, rung=rung)) is None


def test_the_feedback_store_roofline_counts_the_int16_store_at_2_bytes():
    ctx = _ctx()
    got = _read("feedback_store_roofline", ctx)
    rows, calls, v, k = 2 * 13 * 4096.0, 2, 8192, 20
    nbytes = rows * v * 2 + calls * (2 * k * v * 4) + 2 * rows * k * 4
    assert got == pytest.approx(100 * (nbytes / 819e9) / 3.0)
    assert got == pytest.approx(estep_store_roofline.read(ctx))


@pytest.mark.parametrize("store, cell", [
    (None, CELL), ("bfloat16", CELL), ("float32", CELL),
    ("int16", "no_such_cell")],
    ids=["token_lists", "bf16_store", "f32_store", "no_cell"])
def test_the_feedback_store_roofline_gives_nothing_it_cannot_place(
        store, cell):
    assert _read("feedback_store_roofline", _ctx(store=store,
                                                 cell=cell)) is None
    assert _read("feedback_store_roofline", dict(_ctx(), peaks=None)) is None


# -- the program through the cell, tiny, on the full-width dense plan -----

def test_the_cell_rehearsed_on_the_dense_int16_plan_is_correct():
    """The cell at the rehearsal's width with its budget set between the
    day's 2 B and 4 B dense sizes and `on_tpu` stood in for while the plan
    is made (its scoped-VMEM option is the chip compiler's and is left
    out): the plan takes full-width dense with the int16 store, the
    kernels run interpreted on 16-bit integer cells, and the fit is
    `correct` against the plain reference under `flow20_fit`'s limits,
    `store_exact` 0."""
    import jax

    from benchmarks.harness import rehearse_compile
    from oni_ml_tpu.models import LDATrainer
    from oni_ml_tpu.ops import dense_estep

    found = _tiny_found()
    v = found["config"]["num_terms"]
    shapes = rehearse_compile.batch_shapes(found["traffic"], v, 1)
    rows = sum(b for b, _ in shapes)
    sparse = sum(b * length * 8 for b, length in shapes)
    found["config"]["program"]["dense_hbm_budget"] = (
        3 * rows * dense_estep.padded_width(v) + sparse)
    plans, real = [], LDATrainer._plan_estep

    def on_a_stood_in_tpu(self, batches):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            plan = real(self, batches)._replace(compiler_options=None)
        plans.append(plan)
        return plan

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.0,
                              trace=0)
    with mock.patch.object(LDATrainer, "_plan_estep", on_a_stood_in_tpu):
        line = bench_run.run_cell(
            args, {"platform": "cpu", "kind": "rehearsal", "count": 1},
            found)
    assert plans and {(p.family, p.store) for p in plans} == {
        ("dense", jax.numpy.int16)}
    assert line["correct"] is True
    assert set(line["compared"]) == set(fit_check.NUMBERS) | {"store_exact"}
    limits = cells.resolve(entry_rules.FIT)["traffic"]["limits"]
    for name, row in line["compared"].items():
        assert row["limit"] == limits.get(name, 0)
    assert line["compared"]["store_exact"]["value"] == 0.0
