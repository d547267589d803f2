"""The benchmark's own arithmetic: window, work counts, trace reduction."""

import gzip
import json
import os

import pytest

from benchmarks.harness import window, xplane
from benchmarks.jobs import fit_work
from benchmarks.metrics import estep_roofline

DATA = os.path.join(os.path.dirname(xplane.__file__), "..", "data")


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _window(job_seconds, seconds=10.0, stall_at=None, stall=0.0):
    clock, calls = Clock(), []

    def job():
        calls.append(clock.now)
        clock.now += job_seconds + (stall if len(calls) == stall_at else 0.0)
        return 1000.0

    return window.run_window(job, seconds, clock=clock)


def test_window_closes_when_the_job_in_flight_returns():
    win = _window(3.0)
    assert win["jobs"] == 4 and win["window_s"] == pytest.approx(12.0)
    rate = window.rates(win)
    assert rate["work_per_s"] == pytest.approx(4000.0 / 12.0)
    assert rate["s_per_job"] == pytest.approx(3.0)


def test_an_injected_stall_lowers_the_rate_and_raises_the_time_per_job():
    steady = window.rates(_window(2.0))
    stalled = window.rates(_window(2.0, stall_at=2, stall=1.5))
    assert stalled["work_per_s"] < steady["work_per_s"]
    assert stalled["s_per_job"] > steady["s_per_job"]
    # all work over all time: 5 jobs in 11.5 s, nothing left out
    assert stalled["work_per_s"] == pytest.approx(5000.0 / 11.5)


def test_window_runs_one_job_at_least_and_stops_at_max_jobs():
    assert _window(5.0, seconds=0.0)["jobs"] == 1
    clock = Clock()

    def job():
        clock.now += 1.0
        return 1.0

    assert window.run_window(job, 100.0, clock=clock, max_jobs=2)["jobs"] == 2


@pytest.mark.parametrize("terms,topics,padded,flops", [
    (8192, 20, 8192, 6 * 8192 * 20),          # flow20: 983,040 per doc
    (50169, 50, 50176, 6 * 50176 * 50),       # config 3's width: 15,052,800
])
def test_flops_per_document_iteration(terms, topics, padded, flops):
    assert fit_work.padded_terms(terms) == padded
    assert fit_work.flops_per_doc_iter(terms, topics) == flops


def test_estep_call_bytes_by_hand():
    # [4096, 8192] block at 4 B + beta and counts [20, 8192] f32 + gamma in/out
    want = 4096 * 8192 * 4 + 2 * 20 * 8192 * 4 + 2 * 4096 * 20 * 4
    assert fit_work.estep_call_bytes(4096, 8192, 20) == want
    assert fit_work.estep_call_flops(4096, 8192, 20) == 4096 * 983040


def test_the_bytes_bind_the_estep_roofline_at_both_widths():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    for terms, topics in ((8192, 20), (50169, 50)):
        least, which = estep_roofline.binding(4096, 1, terms, topics, peaks)
        assert which == "bytes"
        assert least == pytest.approx(
            fit_work.estep_call_bytes(4096, terms, topics) / 819e9)


def test_union_overlap_and_self_times():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane.overlap([[0, 3], [5, 6]], [[2, 5.5]]) == pytest.approx(1.5)
    ops = [("while", 0.0, 10.0), ("kernel", 1.0, 4.0), ("fusion", 6.0, 2.0)]
    own = xplane.self_times(ops)
    assert own == {"while": pytest.approx(4.0), "kernel": pytest.approx(4.0),
                   "fusion": pytest.approx(2.0)}


def _events():
    return {
        "devices": {0: [("jit_place", 1.0, 1.0), ("kernel", 3.0, 2.0),
                        ("kernel", 6.0, 2.0), ("copy", 8.5, 0.5),
                        ("kernel", 13.0, 4.0), ("before", -5.0, 1.0)]},
        "modules": {0: [("jit_run_chunk_dispatch(1)", 3.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 6.0, 2.0),
                        ("jit_run_chunk_dispatch(1)", 13.0, 4.0)]},
        "annotations": [("fit", 0.0, 10.0), ("fit", 10.0, 8.0)],
    }


def test_reduction_busy_union_idle_share_and_gap_attribution():
    trace = xplane.reduce_events(_events())
    assert trace["window_s"] == pytest.approx(18.0)
    assert trace["busy_s"] == pytest.approx(1 + 2 + 2 + 0.5 + 4)
    gaps = xplane.idle_gaps(trace, 0)
    assert sum(e - s for s, e in gaps) == pytest.approx(18.0 - 9.5)
    from benchmarks.jobs.fit import PHASES

    phases = {(s, e): xplane.phase_of(trace, 0, (s + e) / 2, PHASES)
              for s, e in gaps}
    assert phases[(0.0, 1.0)] == "place"       # fit begun, no EM program yet
    assert phases[(2.0, 3.0)] == "place"
    assert phases[(5.0, 6.0)] == "em_sync"     # between two EM programs
    assert phases[(8.0, 8.5)] == "readback"    # after the fit's last one
    assert phases[(9.0, 13.0)] == "place"      # centre lies in the second fit
    out = xplane.breakdown(trace, PHASES)
    assert out["device_ops"][0] == ["kernel", pytest.approx(8.0)]
    assert out["idle_gaps"][0] == ["place", pytest.approx(6.0)]
    assert xplane.phase_of(trace, 0, 30.0, PHASES) == "between_fits"


def test_short_names_keep_the_hlo_name_the_result_and_the_opcode():
    long = ("%tpu_custom_call.35 = (f32[37,4096,20]{2,1,0:T(8,128)}, "
            "f32[20,8192]{1,0:T(8,128)S(1)}) fusion(f32[37,4096,20]{2,1,0} "
            "%get-tuple-element.2703), kind=kCustom, calls=%fused")
    assert xplane.short(long) == "tpu_custom_call.35 f32[37,4096,20] fusion"
    assert xplane.short("dot_general.1") == "dot_general.1"


def test_fit_readers_on_the_synthetic_trace():
    from benchmarks.metrics import (device_idle_pct, fit_place_s,
                                    fit_readback_s)

    ctx = {"trace": dict(xplane.reduce_events(_events()), rehearsal=False)}
    assert device_idle_pct.read(ctx) == pytest.approx(100 * 8.5 / 18)
    assert fit_place_s.read(ctx) == pytest.approx((3.0 + 3.0) / 2)
    assert fit_readback_s.read(ctx) == pytest.approx((2.0 + 1.0) / 2)


def test_a_reader_that_finds_nothing_returns_nothing():
    from benchmarks.metrics import estep_roofline

    trace = dict(xplane.reduce_events(_events()), rehearsal=False)
    assert estep_roofline.read({"trace": trace, "peaks": None}) is None


def test_a_window_with_no_device_operation_is_refused():
    events = _events()
    events["devices"] = {0: [("before", -5.0, 1.0)]}
    with pytest.raises(ValueError, match="no operation ran"):
        xplane.reduce_events(events)


def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    path = os.path.join(DATA, "flow20_fit_trace.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    events = recorded["events"]
    events["devices"] = {int(k): [tuple(e) for e in v]
                         for k, v in events["devices"].items()}
    events["modules"] = {int(k): [tuple(e) for e in v]
                         for k, v in events["modules"].items()}
    events["annotations"] = [tuple(a) for a in events["annotations"]]
    trace = xplane.reduce_events(events)
    want = recorded["expected"]
    assert trace["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert trace["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < trace["busy_s"] < trace["window_s"]
    # the numbers the chip run itself printed for this trace
    assert trace["window_s"] == pytest.approx(6.658993487)
    assert trace["busy_s"] == pytest.approx(2.308248176)
    from benchmarks.harness import cells, device
    from benchmarks.jobs.fit import PHASES

    ctx = dict(trace=trace, chips=1, peaks=device.peaks_for("TPU v5 lite"),
               num_terms=8192, num_topics=20, doc_iters=163840 * 44.0,
               em_iters=44, num_docs=163840,
               end_to_end={"em_docs_per_s": 163840 * 44 / trace["window_s"]})
    for name, value in want["metrics"].items():
        got = cells.load_module("metrics", name).read(ctx)
        assert got == (None if value is None else pytest.approx(value)), name
    assert 0 < want["metrics"]["estep_roofline"] < 100
    assert xplane.breakdown(trace, PHASES) == want["breakdown"]
    kernels = sum(s for n, s in want["breakdown"]["device_ops"]
                  if n.startswith("tpu_custom_call"))
    assert kernels == pytest.approx(1.5216, abs=1e-3)   # the dump's sum


def _tiny_traffic():
    from benchmarks import rehearse
    from benchmarks.harness import cells

    return rehearse.shrink(cells.resolve("flow20_fit"))["traffic"]


def test_the_seed_draws_the_content_and_keeps_the_multiset_of_sizes():
    import numpy as np

    from benchmarks.harness import corpus_gen

    traffic = _tiny_traffic()
    big = 2**31 + 12345                      # the driver's seeds are large
    a = corpus_gen.make_corpus(traffic, 512, big)
    again = corpus_gen.make_corpus(traffic, 512, big)
    b = corpus_gen.make_corpus(traffic, 512, 7)
    assert np.array_equal(a.word_idx, again.word_idx)
    assert np.array_equal(a.doc_ptr, again.doc_ptr)
    assert np.array_equal(a.counts, again.counts)
    assert not np.array_equal(a.doc_ptr, b.doc_ptr)      # other documents,
    lengths = corpus_gen.length_multiset(
        traffic["num_docs"], traffic["corpus"]["length"], 512)
    assert sorted(a.doc_lengths()) == sorted(lengths)    # the same sizes
    assert sorted(b.doc_lengths()) == sorted(lengths)
    assert a.counts.min() >= 1 and a.counts.max() <= 255
    assert a.word_idx.min() >= 0 and a.word_idx.max() < 512
    # distinct words within every document
    doc_of = np.repeat(np.arange(a.num_docs), a.doc_lengths())
    assert len(np.unique(doc_of * 512 + a.word_idx)) == len(a.word_idx)


@pytest.mark.parametrize("traffic, num_terms", [("resident_163840", 8192)])
def test_the_length_law_is_the_recorded_source_histogram(traffic, num_terms):
    import numpy as np

    from benchmarks.harness import cells, corpus_gen

    spec = cells.load_json(cells.ROOT, "benchmarks", "traffic",
                           traffic + ".json")
    lengths = corpus_gen.length_multiset(
        spec["num_docs"], spec["corpus"]["length"], num_terms)
    assert len(lengths) == spec["num_docs"] and lengths.min() >= 1
    source = spec["fit"]["source_histogram"]["distinct_words_quantiles"]
    for q, want in source.items():
        assert np.quantile(lengths, float(q)) == pytest.approx(want, rel=0.03)
    assert lengths.mean() == pytest.approx(
        spec["fit"]["source_histogram"]["distinct_words_mean"], rel=0.06)
