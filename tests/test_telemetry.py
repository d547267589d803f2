"""Telemetry flight recorder: journal write/replay, span tracing +
Chrome trace export, heartbeat loss detection, and the serving metrics
registry refactor.  (The grep-lints that lived here are AST rules in
oni_ml_tpu/analysis/ now — see tests/test_analysis.py.)

Everything here is the fast tier-1 smoke — no device, no subprocesses
(the SIGKILL crash-recovery path lives in tests/test_journal_crash.py).
"""

import contextlib
import json
import os
import threading

import pytest

from oni_ml_tpu.telemetry import (
    BackendLost,
    HeartbeatMonitor,
    Journal,
    Recorder,
    RunJournal,
    current_recorder,
    maybe_span,
    use_recorder,
)


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------


def test_journal_append_replay_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path, fsync_every=2) as j:
        for i in range(5):
            j.append({"kind": "x", "i": i})
    records = Journal.replay(path)
    assert [r["i"] for r in records] == list(range(5))
    # Every record is stamped with seq / wall t / monotonic ns.
    assert [r["seq"] for r in records] == list(range(5))
    assert all("t" in r and "mono_ns" in r for r in records)
    # mono_ns is non-decreasing (monotonic clock).
    ns = [r["mono_ns"] for r in records]
    assert ns == sorted(ns)


def test_journal_replay_tolerates_truncated_tail(tmp_path):
    """The hard-kill signature: a half-written final line must replay
    to every complete record, dropped-line count ZERO (clean
    truncation is expected, not damage)."""
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.append({"kind": "a"})
        j.append({"kind": "b"})
    with open(path, "ab") as f:  # simulate a kill mid-append
        f.write(b'{"kind": "c", "truncat')
    records, dropped = Journal.replay_report(path)
    assert [r["kind"] for r in records] == ["a", "b"]
    assert dropped == 0


def test_journal_replay_counts_midfile_damage(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.append({"kind": "a"})
    with open(path, "ab") as f:
        f.write(b"NOT JSON AT ALL\n")
    with Journal(path) as j:
        j.append({"kind": "b"})
    records, dropped = Journal.replay_report(path)
    assert [r["kind"] for r in records] == ["a", "b"]
    assert dropped == 1


def test_journal_replay_missing_file_is_empty():
    assert Journal.replay("/nonexistent/never/j.jsonl") == []


def test_journal_append_is_one_line_per_record(tmp_path):
    """Atomic line writes: concurrent writers may interleave RECORDS
    but never bytes — every line parses alone."""
    path = str(tmp_path / "j.jsonl")
    j = Journal(path, fsync_every=0)

    def writer(tag):
        for i in range(50):
            j.append({"kind": "w", "tag": tag, "i": i})

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    j.close()
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln]
    assert len(lines) == 200
    for ln in lines:
        assert isinstance(json.loads(ln), dict)


def test_run_journal_completed_stages_and_force_boundary(tmp_path):
    path = str(tmp_path / "j.jsonl")
    rj = RunJournal(Journal(path))
    rj.run_start(force=False)
    rj.stage_begin("pre")
    rj.stage_end("pre", ok=True, wall_s=1.0)
    rj.stage_begin("corpus")
    rj.stage_end("corpus", ok=True)
    rj.stage_begin("lda")
    rj.stage_end("lda", ok=False, error="boom")  # failed: NOT complete
    rj.close()
    done = RunJournal.completed_stages(Journal.replay(path))
    assert done == {"pre", "corpus"}

    # A force run invalidates prior completions; its own completions
    # count again.
    rj = RunJournal(Journal(path))
    rj.run_start(force=True)
    rj.stage_end("pre", ok=True)
    rj.close()
    done = RunJournal.completed_stages(Journal.replay(path))
    assert done == {"pre"}


def test_run_journal_tolerates_none_journal():
    rj = RunJournal(None)
    rj.run_start()
    rj.stage_begin("pre")
    rj.stage_end("pre")
    rj.em_likelihood(1, -10.0, 0.5)
    rj.heartbeat(True)
    rj.backend_lost(reason="x")
    rj.close()  # no raise = pass


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_export_chrome_trace():
    rec = Recorder()
    with rec.span("outer", label="o"):
        with rec.span("inner"):
            pass
        rec.counter("things").add(3)
        rec.histogram("lat_s").observe(0.25)
    trace = rec.chrome_trace()
    # Chrome trace-event JSON object form: traceEvents list, every
    # event has name/ph/ts(+dur for X), numeric pid/tid — what
    # Perfetto / chrome://tracing validate on load.
    assert set(trace) >= {"traceEvents"}
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and len(evs) >= 3
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "C", "i"):
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert {"outer", "inner"} <= set(xs)
    # inner nests inside outer on the timeline.
    assert xs["inner"]["ts"] >= xs["outer"]["ts"]
    assert (xs["inner"]["ts"] + xs["inner"]["dur"]
            <= xs["outer"]["ts"] + xs["outer"]["dur"] + 1e-3)
    assert xs["outer"]["args"]["label"] == "o"
    # depth tracked per thread: inner recorded at depth 1.
    inner_ev = next(e for e in rec.events if e["name"] == "inner")
    assert inner_ev["depth"] == 1
    # counters ride as "C" events and in the snapshot.
    assert any(e["ph"] == "C" and e["name"] == "things" for e in evs)
    snap = rec.snapshot()
    assert snap["counters"]["things"] == 3
    assert snap["histograms"]["lat_s"]["count"] == 1
    # the whole trace is json-serializable
    json.dumps(trace)


def test_span_error_annotated():
    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("fails"):
            raise ValueError("nope")
    ev = next(e for e in rec.events if e["name"] == "fails")
    assert "ValueError" in ev["args"]["error"]


def test_maybe_span_is_noop_without_recorder():
    assert current_recorder() is None
    with maybe_span("nothing", a=1):
        pass  # no recorder: must not raise, must not record anywhere


@pytest.mark.parametrize("listening", [False, True])
def test_a_span_says_whether_anybody_reads_it(listening):
    """`live`: what a call site asks before it works out counts that cost
    more than a keyword (a sharding's device set, say)."""
    rec = Recorder()
    with use_recorder(rec) if listening else contextlib.nullcontext():
        with maybe_span("asked") as sp:
            if sp.live:
                sp.annotate(costly=1)
    assert sp.live is listening
    assert [e["args"] for e in rec.events] == (
        [{"costly": 1}] if listening else [])


def test_use_recorder_binds_and_restores():
    rec = Recorder()
    assert current_recorder() is None
    with use_recorder(rec):
        assert current_recorder() is rec
        with maybe_span("seen"):
            pass
    assert current_recorder() is None
    assert any(e["name"] == "seen" for e in rec.events)


def test_recorder_journals_spans(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    rec = Recorder(journal=j)
    with rec.span("stage.pre", fdate="20160122"):
        pass
    j.close()
    spans = [r for r in Journal.replay(path) if r.get("kind") == "span"]
    assert len(spans) == 1
    assert spans[0]["name"] == "stage.pre"
    assert spans[0]["dur_ns"] >= 0
    assert spans[0]["args"]["fdate"] == "20160122"


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_declares_lost_after_misses_and_check_raises(tmp_path):
    path = str(tmp_path / "j.jsonl")
    rj = RunJournal(Journal(path))
    hb = HeartbeatMonitor(
        interval_s=0.01, timeout_s=0.1, max_misses=2, journal=rj,
        probe=lambda t: None,          # backend never answers
    )
    assert hb.beat_once() is False     # miss 1: not yet lost
    hb.check()
    assert hb.beat_once() is False     # miss 2: lost
    assert hb.lost.is_set()
    with pytest.raises(BackendLost):
        hb.check()
    rj.close()
    records = Journal.replay(path)
    kinds = [r["kind"] for r in records]
    assert kinds.count("heartbeat") == 2
    assert "backend_lost" in kinds
    lost = next(r for r in records if r["kind"] == "backend_lost")
    assert "2 consecutive liveness probes missed" in lost["reason"]


def test_heartbeat_never_starts_a_process(monkeypatch):
    """The process that runs the pipeline holds the chip, and a second
    process asking for it fails while the first is healthy — so neither
    a healthy beat nor the path to a declared loss may start one."""
    import subprocess

    def refuse(*a, **kw):
        raise AssertionError("the heartbeat started a process")

    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(os, "posix_spawn", refuse)
    healthy = HeartbeatMonitor(interval_s=0.01, timeout_s=60.0)
    assert healthy.beat_once() is True     # the real in-process probe
    dead = HeartbeatMonitor(interval_s=0.01, timeout_s=0.1, max_misses=2,
                            probe=lambda t: None)
    assert dead.beat_once() is False
    assert dead.beat_once() is False
    assert dead.lost.is_set()


def test_heartbeat_recovers_and_journals_latency(tmp_path):
    path = str(tmp_path / "j.jsonl")
    rj = RunJournal(Journal(path))
    answers = iter([None, 0.001, 0.002])
    hb = HeartbeatMonitor(
        interval_s=0.01, timeout_s=0.1, max_misses=3, journal=rj,
        probe=lambda t: next(answers),
    )
    assert hb.beat_once() is False
    assert hb.beat_once() is True      # recovered: misses reset
    assert hb.misses == 0
    assert hb.beat_once() is True
    hb.check()                         # never lost
    rj.close()
    beats = [r for r in Journal.replay(path) if r["kind"] == "heartbeat"]
    assert [b["ok"] for b in beats] == [False, True, True]
    assert beats[1]["latency_s"] > 0


def test_heartbeat_on_lost_callback_and_thread_lifecycle():
    fired = []
    hb = HeartbeatMonitor(
        interval_s=0.005, timeout_s=0.05, max_misses=1,
        probe=lambda t: None,
        on_lost=fired.append,
    )
    hb.start()
    hb.lost.wait(timeout=5.0)
    hb.stop()
    assert hb.lost.is_set()
    assert fired and "missed" in fired[0]



def test_heartbeat_real_device_probe_answers_on_cpu():
    """The production probe (tiny jitted add + transfer) against the
    test CPU backend: alive, with a measured latency."""
    from oni_ml_tpu.telemetry.heartbeat import device_add_probe

    lat = device_add_probe(timeout_s=60.0)
    assert lat is not None and lat > 0


# ---------------------------------------------------------------------------
# serving metrics on the shared registry
# ---------------------------------------------------------------------------


def test_metrics_emitter_feeds_shared_registry_and_journal(tmp_path):
    from oni_ml_tpu.serving import MetricsEmitter

    jpath = str(tmp_path / "serve.jsonl")
    j = Journal(jpath)
    rec = Recorder()
    m = MetricsEmitter(to_stdout=False, recorder=rec, journal=j)
    m.emit({"stage": "serve", "batch": 0, "events": 32, "flagged": 2,
            "latency_ms": 5.0, "score_ms": 1.25, "queue_depth": 3})
    m.emit({"stage": "serve", "batch": 1, "events": 16, "flagged": 0,
            "latency_ms": 7.0, "score_ms": 0.75, "queue_depth": 1})
    m.emit({"stage": "serve", "batch": 2, "events": 8, "error": "boom"})
    m.close()
    j.close()
    snap = m.snapshot()
    assert snap["counters"]["serve.emits"] == 3
    assert snap["counters"]["serve.events"] == 56
    assert snap["counters"]["serve.flagged"] == 2
    assert snap["counters"]["serve.errors"] == 1
    lat = snap["histograms"]["serve.latency_ms"]
    assert lat["count"] == 2 and lat["min"] == 5.0 and lat["max"] == 7.0
    # the deque record view is unchanged (test_serving.py's contract)
    assert len(m.records) == 3
    # every emit journaled as a serve record
    serves = [r for r in Journal.replay(jpath) if r["kind"] == "serve"]
    assert len(serves) == 3 and serves[0]["events"] == 32


def test_metrics_emitter_binds_ambient_recorder():
    from oni_ml_tpu.serving import MetricsEmitter

    rec = Recorder()
    with use_recorder(rec):
        m = MetricsEmitter(to_stdout=False)
    m.emit({"stage": "serve", "events": 4})
    assert rec.counters["serve.events"].value == 4


# ---------------------------------------------------------------------------
# trace_view tool
# ---------------------------------------------------------------------------


def test_trace_view_converts_journal_to_valid_chrome_trace(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import trace_view

    path = str(tmp_path / "run_journal.jsonl")
    rj = RunJournal(Journal(path))
    rj.run_start(fdate="20160122")
    rj.stage_begin("pre")
    rj.stage_end("pre", ok=True, wall_s=0.5, events=100)
    rj.stage_begin("lda")
    for i in range(3):
        rj.em_likelihood(i + 1, -100.0 + i, 0.1)
    rj.heartbeat(True, latency_s=0.001)
    rj.heartbeat(False, misses=1)
    rj.stage_end("lda", ok=True, wall_s=2.0)
    rj.stage_skipped("score", "outputs exist")
    rj.backend_lost(reason="test")
    rj.stage_begin("score")  # never ends: the killed run's last stage
    rj.close()

    records = trace_view.Journal.replay(path)
    trace = trace_view.journal_to_trace(records)
    evs = trace["traceEvents"]
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "C", "i"):
            assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
    names = [e["name"] for e in evs]
    assert "stage.pre" in names and "stage.lda" in names
    assert names.count("em likelihood") == 3
    assert "BACKEND LOST" in names
    assert "stage.score (unfinished)" in names
    json.dumps(trace)

    rows = trace_view.stage_summary(records)
    by = {r["stage"]: r for r in rows}
    assert by["lda"]["wall_s"] == 2.0 and by["lda"]["runs"] == 1
    assert by["score"]["skips"] == 1
    # CLI end-to-end: writes the trace file, prints the summary.
    out = str(tmp_path / "t.json")
    assert trace_view.main([path, "--out", out]) == 0
    with open(out) as f:
        assert "traceEvents" in json.load(f)


# The four grep-lints that lived here (monotonic-clock, tuned-constant,
# quantile, harvest-coverage) moved to the AST rule engine in
# oni_ml_tpu/analysis/ (same or stricter coverage, one suppression
# mechanism instead of per-lint allowlists).  tests/test_analysis.py
# enforces them now — including the live-repo clean run.
