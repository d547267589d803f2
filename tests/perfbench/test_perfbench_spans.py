"""The readers of the program's own spans (PR 27): their arithmetic on a
hand-made trace, on a recorded chip trace, and their entries in
BENCHMARK.json."""

import gzip
import json
import os

import pytest

import entry_rules
from benchmarks.harness import cells, program_trace, xplane
from benchmarks.jobs import fit_spans
from benchmarks.metrics import estep_glue_pct

DATA = os.path.join(cells.BENCH_DIR, "data", "flow20_fit_spans.json.gz")

# name -> (unit, source, layer, moves): the issue's table.
NEW = entry_rules.SPAN_METRICS
PLACE = [n for n in NEW if n.startswith("place_")]


def _read(name, ctx):
    return cells.load_module("metrics", name).read(ctx)


def _synthetic():
    """Two annotated fits of 10 s and 8 s.  The first EM program of each
    starts 6 s into its fit; the program's `fit` span starts 0.5 s after the
    annotation.  Device: densify at 3-4 s, then one EM program: a `while`
    holding a tail kernel, and a nested `while` (the scan) holding a copy
    and a kernel per batch."""
    def fit_ops(t):
        return [
            ("fusion.1 f32[8] fusion", t + 3.0, 1.0),
            ("while.1 f32[20] while", t + 6.0, 2.0),
            ("tpu_custom_call.1 f32[8,20] custom-call", t + 6.0, 0.25),
            ("while.2 s32[] while", t + 6.5, 1.25),
            ("dynamic-slice_fusion.1 f32[8] fusion", t + 6.5, 0.25),
            ("tpu_custom_call.2 f32[2,8,20] fusion", t + 6.75, 0.25),
            ("dynamic-slice_fusion.1 f32[8] fusion", t + 7.0, 0.25),
            ("tpu_custom_call.2 f32[2,8,20] fusion", t + 7.25, 0.25),
            ("add_fusion f32[20] fusion", t + 7.875, 0.125),
        ]

    def fit_spans_of(t, sweeps):
        rows = [
            ("fit", t + 0.5, 7.5, {"num_docs": 12}),
            ("fit.engine", t + 0.5, 0.25, {}),
            ("fit.batches", t + 0.75, 1.0, {}),
            ("fit.batches.counts", t + 1.7, 0.0, {"batches": 3, "rows": 16}),
            ("fit.init", t + 1.75, 0.25, {"what": "trainer"}),
            ("fit.init", t + 2.0, 0.25, {}),
            ("fit.plan", t + 2.25, 0.5, {}),
            ("fit.stack", t + 2.75, 0.25, {}),
            ("fit.densify", t + 3.0, 0.5, {}),
            ("fit.runner", t + 3.5, 0.25, {}),
            # 0.25 s under no child span: the root's own time
            ("em.run_chunk", t + 4.0, 2.5, {"first": 1}),   # ends after the
            ("em.host_sync", t + 6.5, 1.0, {}),             # device starts
            ("em.host_sync.counts", t + 7.4, 0.0, {"steps": 2}),
            ("em.run_chunk", t + 7.5, 0.125, {"first": 0}),
            ("fit.readback", t + 7.75, 0.125, {}),
            ("fit.counts", t + 7.9, 0.0,
             {"em_iters": 2, "doc_sweeps": sweeps, "compile_requests": 7}),
        ]
        return [(n, s, d, stats, "python") for n, s, d, stats in rows]

    events = {
        "devices": {0: fit_ops(0.0) + fit_ops(10.0)},
        "modules": {0: [("jit_run_chunk_dispatch(1)", 6.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 16.0, 2.0)]},
        "annotations": [("fit", 0.0, 10.0), ("fit", 10.0, 8.0)],
    }
    spans = sorted(fit_spans_of(0.0, 96) + fit_spans_of(10.0, 160)
                   + [("fit", 30.0, 1.0, {}, "python")],   # outside the window
                   key=lambda e: e[1])
    trace = dict(xplane.reduce_events(events), rehearsal=False)
    return {"trace": trace, "chips": 1,
            "program_trace": {"spans": spans, "scopes": {}}}


@pytest.mark.parametrize("name, want", [
    ("place_batches_s", 1.0),
    ("place_plan_s", 0.25 + 0.5 + 0.5 + 0.25),     # engine, init x2, plan, runner
    ("place_transfer_s", 0.25),
    ("place_densify_s", 0.5),
    ("place_first_dispatch_s", 2.0),               # 2.5 s clipped at the device's start
    ("place_unattributed_s", 0.25),
    ("fit_compile_requests", 7.0),
    ("estep_sweeps_per_doc_iter", (96 + 160) / (2 * 16 * 2)),
    # per fit: the loop while.2's own 0.25 s + its two copies 0.5 s (the add
    # after it is at depth 1: no glue); busy per fit 1 + 2
    ("estep_glue_pct", 100 * 0.75 / 3.0),
])
def test_reader_arithmetic_on_a_hand_made_trace(name, want):
    assert _read(name, _synthetic()) == pytest.approx(want)


def test_the_place_metrics_sum_to_the_placement():
    ctx = _synthetic()
    total = sum(_read(name, ctx) for name in PLACE)
    # fit_place_s starts at the annotation, 0.5 s before the program's span
    assert total == pytest.approx(_read("fit_place_s", ctx) - 0.5)
    assert total == pytest.approx(5.5)


def test_per_fit_keeps_direct_children_and_counts_apart():
    fits = fit_spans.per_fit(_synthetic())
    assert len(fits) == 2
    first = fits[0]
    assert first["fit"] == (0.5, 8.0) and first["first_em"] == 6.0
    assert [c[0] for c in first["children"]] == [
        "fit.engine", "fit.batches", "fit.init", "fit.init", "fit.plan",
        "fit.stack", "fit.densify", "fit.runner", "em.run_chunk",
        "em.host_sync", "em.run_chunk", "fit.readback"]
    assert fit_spans.counted(first, "fit", "doc_sweeps") == 96
    assert fit_spans.counted(first, "em.host_sync", "steps") == 2
    assert fit_spans.counted(first, "fit", "no_such_count") is None
    assert fit_spans.counted(fits[1], "fit", "doc_sweeps") == 160


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_that_finds_no_spans_returns_nothing(name):
    """The parent of the PR that added the spans: same trace, no spans."""
    ctx = _synthetic()
    ctx["program_trace"] = {"spans": [], "scopes": {}}
    assert _read(name, ctx) is None


def test_glue_counts_loops_inside_loops_and_leaves_kernels_out():
    ops = [("while.1 while", 0.0, 10.0),
           ("tpu_custom_call.1 custom-call", 0.0, 1.0),   # tail kernel: no glue
           ("exp.1 exponential", 1.0, 1.0),               # depth 1: no glue
           ("while.2 while", 2.0, 6.0),                   # own 6 - 5 = 1
           ("copy.1 copy", 2.0, 2.0),                     # depth 2: glue
           ("tpu_custom_call.2 fusion", 4.0, 3.0),        # kernel: no glue
           ("while_like_fusion fusion", 9.0, 0.5)]        # no loop, depth 1
    assert estep_glue_pct.glue_seconds(ops) == pytest.approx(1.0 + 2.0)
    assert estep_glue_pct.glue_seconds([]) == 0.0
    for name in ("%while.64 = (f32[20,8192]{1,0}, s32[]) while(...)",
                 "while.64 f32[20,8192] while", "while"):
        assert estep_glue_pct.LOOP.match(name), name
    for name in ("while_add_fusion f32[8] fusion", "%fusion.3 = f32[8]"):
        assert not estep_glue_pct.LOOP.match(name), name


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    events = rec["events"]
    events["devices"] = {int(k): [tuple(e) for e in v]
                         for k, v in events["devices"].items()}
    events["modules"] = {int(k): [tuple(e) for e in v]
                         for k, v in events["modules"].items()}
    events["annotations"] = [tuple(a) for a in events["annotations"]]
    trace = xplane.reduce_events(events)
    ctx = {"trace": trace, "chips": 1, "program_trace": {
        "spans": [tuple(s) for s in rec["spans"]], "scopes": {}}}
    return rec, ctx


@pytest.mark.parametrize("name", sorted(NEW) + ["fit_place_s"])
def test_recorded_chip_trace_reads_its_recorded_numbers(recorded, name):
    rec, ctx = recorded
    assert ctx["trace"]["window_s"] == pytest.approx(
        rec["expected"]["window_s"], rel=1e-9)
    assert _read(name, ctx) == pytest.approx(
        rec["expected"]["metrics"][name], rel=1e-9)


def test_recorded_chip_trace_meets_the_issues_limits(recorded):
    _, ctx = recorded
    place = _read("fit_place_s", ctx)
    assert sum(_read(n, ctx) for n in PLACE) == pytest.approx(place, rel=0.02)
    assert _read("place_unattributed_s", ctx) < 0.1 * place
    assert 1.0 <= _read("estep_sweeps_per_doc_iter", ctx) <= 20.0
    assert _read("fit_compile_requests", ctx) == 7.0
    assert 0.0 < _read("estep_glue_pct", ctx) < 100.0
    fits = fit_spans.per_fit(ctx)
    assert len(fits) == 2
    for fit, (lo, hi) in zip(fits, ctx["trace"]["fits"]):
        # the program's spans lie inside the benchmark's annotation
        assert lo <= fit["fit"][0] and fit["fit"][1] <= hi
        assert fit_spans.counted(fit, "fit.batches", "rows") == 167056
        assert fit_spans.counted(fit, "fit", "em_iters") == 13


def test_every_idle_gap_over_10_ms_in_a_recorded_fit_lies_in_a_span(recorded):
    _, ctx = recorded
    trace = ctx["trace"]
    fits = fit_spans.per_fit(ctx)
    for gap_lo, gap_hi in xplane.idle_gaps(trace, 0):
        if gap_hi - gap_lo <= 0.010:
            continue
        for fit in fits:
            lo, hi = fit["fit"]
            if lo <= gap_lo and gap_hi <= hi:
                covered = xplane.overlap(
                    [[gap_lo, gap_hi]],
                    xplane.union([(s, e) for _, s, e, _ in fit["children"]]))
                assert covered >= 0.99 * (gap_hi - gap_lo), (gap_lo, gap_hi)


@pytest.mark.parametrize("name", sorted(NEW))
def test_benchmark_json_holds_the_entry_and_its_reader_file(name):
    entry_rules.span_metric_entry(entry_rules.load(), name)


def test_newest_takes_the_newest_trace_of_the_checkout(tmp_path):
    assert program_trace.newest(str(tmp_path)) is None
    for i, cell in enumerate(("a", "b")):
        d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "x.xplane.pb").write_bytes(b"")
        os.utime(d / "x.xplane.pb", (100 + i, 100 + i))
    assert program_trace.newest(str(tmp_path)).endswith(
        os.path.join("b", "plugins", "profile", "t", "x.xplane.pb"))
    # a run's readers are handed its own directory: another cell's newer
    # trace (a second worker of the test suite) is not theirs
    assert program_trace.newest(
        trace_dir=str(tmp_path / ".bench_trace" / "a")).endswith(
        os.path.join("a", "plugins", "profile", "t", "x.xplane.pb"))
    assert program_trace.newest(trace_dir=str(tmp_path / "none")) is None


def test_span_names_and_counts_events():
    assert program_trace.is_span("fit.batches", fit_spans.SPANS)
    assert program_trace.is_span("fit.batches.counts", fit_spans.SPANS)
    assert not program_trace.is_span("bench:fit", fit_spans.SPANS)
    assert not program_trace.is_span("fit.batchesX", fit_spans.SPANS)
    assert program_trace.scope_of({"tf_op": "pallas_call:"}) == "pallas_call"
    assert program_trace.scope_of({"flops": "2"}) == ""
