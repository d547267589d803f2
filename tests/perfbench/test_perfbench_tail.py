"""The readers of the fit's tail and of `fit.stack`'s two halves (PR 38):
their arithmetic on a hand-made trace, the sum rules, nothing without the
sub-spans, a recorded chip trace, and (since PR 40) their entries in
BENCHMARK.json.  `fit.stack.copy` is the assembling of a group's host stack:
since PR 39 a view of `make_batches`' buffer where the batches allow it."""

import gzip
import json
import os

import pytest

import entry_rules
from benchmarks.harness import cells, program_trace, xplane
from benchmarks.jobs import fit_spans, fit_tail

DATA = os.path.join(cells.BENCH_DIR, "data", "flow20_fit_tail_spans.json.gz")

NEW = tuple(entry_rules.TAIL_METRICS)
READBACK = [n for n in NEW if n.startswith("readback_")]


def _read(name, ctx):
    return cells.load_module("metrics", name).read(ctx)


def _fit_spans_of(t, subspans=True):
    """One call of 10 s from `t`: the root `fit` from t+0.5 to t+9.75, its
    EM program on the device from t+6 to t+8, so a tail of 1.75 s; every
    boundary a multiple of 1/16 s."""
    rows = [
        ("est.load", t + 0.125, 0.25, {}),
        ("fit", t + 0.5, 9.25, {"num_docs": 12}),
        ("fit.batches", t + 0.75, 1.0, {}),
        ("fit.batches.counts", t + 1.7, 0.0, {"rows": 16}),
        ("fit.stack", t + 2.0, 1.0, {"groups": 2}),
        ("fit.stack.copy", t + 2.0, 0.25, {}),
        ("fit.stack.copy.counts", t + 2.2, 0.0, {"bytes": 64}),
        ("fit.stack.put", t + 2.25, 0.25, {}),
        ("fit.stack.put.counts", t + 2.4, 0.0, {"bytes": 64, "shards": 1}),
        ("fit.stack.copy", t + 2.5, 0.375, {}),
        ("fit.stack.copy.counts", t + 2.8, 0.0, {"bytes": 32}),
        ("fit.stack.put", t + 2.875, 0.125, {}),
        ("fit.stack.put.counts", t + 2.9, 0.0, {"bytes": 32, "shards": 1}),
        ("fit.stack.counts", t + 2.99, 0.0, {"h2d_bytes": 96}),
        ("fit.densify", t + 3.0, 0.5, {}),
        ("em.run_chunk", t + 4.0, 2.5, {"first": 1}),
        # blocks on the device until t+8, then 0.25 s of reads and lines
        ("em.host_sync", t + 6.5, 1.75, {}),
        ("em.host_sync.counts", t + 8.2, 0.0, {"steps": 2}),
        # gamma: two device arrays, and 1/16 s of the span's own
        ("fit.readback", t + 8.25, 0.8125, {"what": "gamma"}),
        ("fit.readback.d2h", t + 8.25, 0.25, {}),
        ("fit.readback.d2h.counts", t + 8.4, 0.0,
         {"bytes": 8, "shards": 1}),
        ("fit.readback.scatter", t + 8.5, 0.25, {}),
        ("fit.readback.scatter.counts", t + 8.7, 0.0,
         {"rows": 8, "bytes": 16}),
        ("fit.readback.d2h", t + 8.75, 0.125, {}),
        ("fit.readback.d2h.counts", t + 8.8, 0.0,
         {"bytes": 4, "shards": 1}),
        ("fit.readback.scatter", t + 8.875, 0.125, {}),
        ("fit.readback.scatter.counts", t + 8.9, 0.0, {"rows": 4, "bytes": 8}),
        ("fit.readback", t + 9.0625, 0.125, {"what": "log_beta"}),
        ("fit.readback.d2h", t + 9.0625, 0.125, {}),
        ("fit.readback.d2h.counts", t + 9.1, 0.0,
         {"bytes": 2, "shards": 1}),
        ("fit.save", t + 9.1875, 0.25, {}),
        ("fit.save.counts", t + 9.4, 0.0, {"rows": 3}),
        # 1/16 s of the root's own, then the teardown, then 1/8 s more
        ("fit.teardown", t + 9.5, 0.125, {}),
        ("fit.counts", t + 9.74, 0.0, {"em_iters": 2}),
    ]
    if not subspans:
        rows = [r for r in rows
                if not r[0].startswith(fit_tail.SUBSPANS)]
    return [(n, s, d, stats, "python") for n, s, d, stats in rows]


def _synthetic(subspans=True):
    """Two annotated calls of 10 s."""
    def ops(t):
        return [("fusion.1 f32[8] fusion", t + 3.0, 0.5),
                ("while.1 f32[20] while", t + 6.0, 2.0),
                ("tpu_custom_call.1 f32[8,20] custom-call", t + 6.0, 1.5)]

    events = {
        "devices": {0: ops(0.0) + ops(10.0)},
        "modules": {0: [("jit_run_chunk_dispatch(1)", 6.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 16.0, 2.0)]},
        "annotations": [("fit", 0.0, 10.0), ("fit", 10.0, 10.0)],
    }
    spans = sorted(_fit_spans_of(0.0, subspans)
                   + _fit_spans_of(10.0, subspans)
                   # a fit outside the window, another thread's spans
                   + [("fit", 30.0, 1.0, {}, "python"),
                      ("fit.readback.d2h", 8.3, 0.5, {}, "worker")],
                   key=lambda e: e[1])
    trace = dict(xplane.reduce_events(events), rehearsal=False)
    return {"trace": trace, "chips": 1,
            "program_trace": {"spans": spans, "scopes": {}}}


@pytest.mark.parametrize("name, want", [
    ("readback_sync_s", 0.25),          # 1.75 s clipped at the device's end
    ("readback_d2h_s", 0.25 + 0.125 + 0.125),
    ("readback_scatter_s", 0.25 + 0.125),
    ("readback_teardown_s", 0.125),
    # fit.readback's own 1/16, the root's 1/16 before the teardown and 1/8
    # after it
    ("readback_unattributed_s", 0.25),
    ("place_stack_copy_s", 0.25 + 0.375),
    ("place_stack_put_s", 0.25 + 0.125),
])
def test_reader_arithmetic_on_a_hand_made_trace(name, want):
    assert _read(name, _synthetic()) == pytest.approx(want)


def test_the_readback_metrics_and_the_save_sum_to_the_tail():
    ctx = _synthetic()
    total = sum(_read(name, ctx) for name in READBACK)
    total += _read("est_save_s", ctx)
    fits = fit_tail.per_fit(ctx)
    assert [f["tail"] for f in fits] == [(8.0, 9.75), (18.0, 19.75)]
    assert total == pytest.approx(1.75)
    # fit_readback_s ends at the annotation, 0.25 s after the root span
    assert total == pytest.approx(_read("fit_readback_s", ctx) - 0.25)


def test_the_stack_metrics_sum_to_the_transfer():
    ctx = _synthetic()
    assert (_read("place_stack_copy_s", ctx) + _read("place_stack_put_s", ctx)
            == pytest.approx(_read("place_transfer_s", ctx)))
    assert _read("place_transfer_s", ctx) == pytest.approx(1.0)


def test_spans_are_clipped_to_the_tail_and_to_the_placement():
    ctx = _synthetic()
    fit = fit_tail.per_fit(ctx)[0]
    assert fit["call"] == (0.0, 10.0) and fit["fit"] == (0.5, 9.75)
    assert fit["place"] == (0.5, 6.0)
    # a sync that ended before the device did lies outside the tail
    assert fit_tail.seconds(fit, ("em.host_sync",), 8.5, 9.75) == 0.0
    assert fit_tail.seconds(fit, ("em.host_sync",), *fit["tail"]) == 0.25
    # a put cut by the placement's end counts up to it
    assert fit_tail.seconds(fit, ("fit.stack.put",), 0.5, 2.3125) == (
        pytest.approx(0.0625))
    # the other thread's span of the same name is not the fit's
    assert [s for n, s, e in fit["spans"] if n == "fit.readback.d2h"] == [
        8.25, 8.75, 9.0625]
    assert fit_tail.counted(fit, "fit.readback.d2h", "bytes") == 8 + 4 + 2
    assert fit_tail.counted(fit, "fit.readback.scatter", "rows") == 12
    assert fit_tail.counted(fit, "fit", "no_such_count") is None


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("program", ["no_spans", "parent"])
def test_a_reader_that_finds_no_sub_spans_returns_nothing(name, program):
    """A program without spans, and the parent of the PR that added the
    sub-spans (it has `fit`, `em.host_sync`, `fit.readback`, `fit.stack`,
    `fit.teardown`): same trace, nothing read, nothing raised."""
    ctx = _synthetic(subspans=False)
    if program == "no_spans":
        ctx["program_trace"] = {"spans": [], "scopes": {}}
    assert fit_tail.per_fit(ctx) == []
    assert _read(name, ctx) is None


def test_the_accepted_readers_read_the_same_trace_as_before():
    """`jobs/fit_spans.py` loads its own names: the sub-spans and
    `fit.teardown` are not among its children."""
    ctx = _synthetic()
    assert _read("place_transfer_s", ctx) == _read(
        "place_transfer_s", _synthetic(subspans=False))
    for fit in fit_spans.per_fit({
            **ctx, "program_trace": {"spans": [
                e for e in ctx["program_trace"]["spans"]
                if program_trace.is_span(e[0], fit_spans.SPANS)],
                "scopes": {}}}):
        assert not {n for n, *_ in fit["children"]} & set(fit_tail.SUBSPANS)


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


@pytest.mark.parametrize(
    "name", sorted(NEW) + ["fit_readback_s", "place_transfer_s"])
def test_recorded_chip_trace_reads_its_recorded_numbers(recorded, name):
    rec, ctx = recorded
    assert ctx["trace"]["window_s"] == pytest.approx(
        rec["expected"]["window_s"], rel=1e-9)
    assert _read(name, ctx) == pytest.approx(
        rec["expected"]["metrics"][name], rel=1e-9)


def test_recorded_chip_trace_meets_the_issues_limits(recorded):
    rec, ctx = recorded
    fits = fit_tail.per_fit(ctx)
    assert len(fits) == 2
    tail = sum(f["tail"][1] - f["tail"][0] for f in fits) / len(fits)
    after = sum(f["call"][1] - f["fit"][1] for f in fits) / len(fits)
    assert sum(_read(n, ctx) for n in READBACK) == pytest.approx(
        tail, abs=0.002)
    assert tail == pytest.approx(_read("fit_readback_s", ctx) - after,
                                 abs=1e-9)
    assert 0.0 <= after < 0.001
    assert 0.0 <= _read("readback_unattributed_s", ctx) < 0.010
    assert (_read("place_stack_copy_s", ctx) + _read("place_stack_put_s", ctx)
            == pytest.approx(_read("place_transfer_s", ctx), abs=0.005))
    for fit in fits:
        assert fit_tail.counted(fit, "fit.readback.scatter", "rows") == 163840
        assert (fit_tail.counted(fit, "fit.stack.copy", "bytes")
                == fit_tail.counted(fit, "fit.stack.put", "bytes")
                == fit_tail.counted(fit, "fit.stack", "h2d_bytes"))


def test_span_names_of_the_tail_and_their_counts_events():
    names = fit_tail.SPANS
    assert len(set(names)) == len(names) == 10
    assert set(fit_tail.TAIL_PARTS) | set(fit_tail.SUBSPANS) <= set(names)
    assert program_trace.is_span("fit.readback.d2h", names)
    assert program_trace.is_span("fit.stack.put.counts", names)
    assert not program_trace.is_span("fit.readback.d2hX", names)
    assert not program_trace.is_span("fit.plan", names)
    # fit_spans loads none of the new names, so its children stay direct
    assert not set(fit_tail.SUBSPANS) & set(fit_spans.SPANS)


# -- the entries (PR 40) ---------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_benchmark_json_holds_the_entry_and_its_reader_file(name):
    entry_rules.tail_metric_entry(entry_rules.load(), name)


def test_the_seven_entries_follow_what_the_benchmark_had_in_the_issues_order():
    entry_rules.tail_metric_entries(entry_rules.load())
