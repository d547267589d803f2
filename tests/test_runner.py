"""End-to-end pipeline tests: the runner reproduces ml_ops.sh's
stage sequence and file contract on a synthetic day, with per-stage
resume (SURVEY §5.3-5.4)."""

import json
import os

import numpy as np
import pytest

from oni_ml_tpu.config import (
    FeedbackConfig,
    LDAConfig,
    PipelineConfig,
    ScoringConfig,
)
from oni_ml_tpu.io import formats
from oni_ml_tpu.runner import Stage, run_pipeline

from test_features import dns_row, flow_row


def _stages(metrics):
    """Pipeline-stage names in order, without the run-level `plans` /
    `roofline` / `dataplane` accounting records run_pipeline appends
    after the stages."""
    return [m["stage"] for m in metrics
            if m["stage"] not in ("plans", "roofline", "dataplane")]


def test_dns_parquet_source(tmp_path):
    """Mixed CSV + parquet dns_path featurizes in listed order with
    comma-bearing parquet fields intact (the reference read Hive parquet,
    dns_pre_lda.scala:142)."""
    pytest.importorskip("pyarrow")
    import pyarrow as pa
    import pyarrow.parquet as pq

    from oni_ml_tpu.sources.builtin import _dns_sources

    n = 6
    table = pa.table({
        "frame_time": ["Mar 10, 2016 01:02:03"] * n,
        "unix_tstamp": list(range(1454000000, 1454000000 + n)),
        "frame_len": [60 + i for i in range(n)],
        "ip_dst": [f"10.2.0.{1 + i}" for i in range(n)],
        "dns_qry_name": [f"h{i}.svc.example.com" for i in range(n)],
        "dns_qry_class": ["1"] * n,
        "dns_qry_type": ["1"] * n,
        "dns_qry_rcode": ["0"] * n,
    })
    pq_path = tmp_path / "day.parquet"
    pq.write_table(table, pq_path)
    csv_path = tmp_path / "day.csv"
    csv_path.write_text(",".join(dns_row(ip="10.3.0.1")) + "\n")

    sources = _dns_sources(f"{pq_path},{csv_path}")
    assert isinstance(sources[0], list) and isinstance(sources[1], str)

    from oni_ml_tpu.features.native_dns import featurize_dns_sources

    feats = featurize_dns_sources(sources)
    assert feats.num_events == n + 1
    # Parquet rows come first (listed order), commas preserved.
    assert feats.rows[0][0] == "Mar 10, 2016 01:02:03"
    assert feats.client_ip(n) == "10.3.0.1"


@pytest.fixture()
def flow_day(tmp_path):
    rng = np.random.default_rng(7)
    lines = ["dummy,header"]
    for i in range(60):
        lines.append(
            flow_row(
                hour=int(rng.integers(0, 24)),
                minute=int(rng.integers(0, 60)),
                second=int(rng.integers(0, 60)),
                sip=f"10.0.0.{rng.integers(1, 9)}",
                dip=f"172.16.0.{rng.integers(1, 9)}",
                col10=str(rng.choice([80, 443, 55000, 0])),
                col11=str(rng.choice([80, 6000, 70000])),
                ipkt=str(rng.integers(1, 100)),
                ibyt=str(rng.integers(40, 10000)),
            )
        )
    raw = tmp_path / "flow.csv"
    raw.write_text("\n".join(lines) + "\n")
    cfg = PipelineConfig(
        data_dir=str(tmp_path),
        flow_path=str(raw),
        lda=LDAConfig(num_topics=4, em_max_iters=6, batch_size=32,
                      min_bucket_len=16, seed=3),
        feedback=FeedbackConfig(dup_factor=5),
        scoring=ScoringConfig(threshold=1.1),
    )
    return cfg, tmp_path


def test_flow_pipeline_end_to_end(flow_day):
    cfg, tmp_path = flow_day
    metrics = run_pipeline(cfg, "20160122", "flow")
    day = tmp_path / "20160122"
    for name in ["features.pkl", "word_counts.dat", "words.dat", "doc.dat",
                 "model.dat", "final.beta", "final.gamma", "final.other",
                 "likelihood.dat", "doc_results.csv", "word_results.csv",
                 "flow_results.csv", "metrics.json"]:
        assert (day / name).exists(), name
    # Stage metrics observable and complete.
    assert _stages(metrics) == ["pre", "corpus", "lda", "score"]
    # likelihood.dat: monotone non-decreasing likelihood.
    ll = formats.read_likelihood(str(day / "likelihood.dat"))
    assert ll.shape[1] == 2
    lls = ll[:, 0]
    assert all(b >= a - 1e-3 * abs(a) for a, b in zip(lls, lls[1:]))
    # threshold 1.1 > any probability -> every event flagged, ascending.
    results = (day / "flow_results.csv").read_text().splitlines()
    assert len(results) == 60
    mins = [min(float(r.split(",")[-2]), float(r.split(",")[-1])) for r in results]
    assert mins == sorted(mins)
    # final.other carries the centralized config (k, V, alpha).
    other = formats.read_other(str(day / "final.other"))
    assert other["num_topics"] == 4


def test_publish_delivers_day_dir(flow_day):
    """--publish: the completed day dir lands at DEST (the reference's
    final scp to the UI node, ml_ops.sh:118-121)."""
    cfg, tmp_path = flow_day
    dest = tmp_path / "ui_node"
    dest.mkdir()
    metrics = run_pipeline(cfg, "20160122", "flow", publish=str(dest))
    pub = [m for m in metrics if m.get("stage") == "publish"]
    assert len(pub) == 1 and pub[0]["transport"] == "copy"
    for name in ("flow_results.csv", "doc_results.csv", "final.beta",
                 "metrics.json"):
        assert (dest / "20160122" / name).exists(), name
    for name in ("flow_results.csv", "doc_results.csv", "final.beta"):
        src = (tmp_path / "20160122" / name).read_bytes()
        assert (dest / "20160122" / name).read_bytes() == src
    # delivered metrics cover all four stages; the local copy also
    # records the publish step afterwards
    import json as _json

    delivered = _json.loads((dest / "20160122" / "metrics.json").read_text())
    assert _stages(delivered) == ["pre", "corpus", "lda", "score"]
    local = _json.loads((tmp_path / "20160122" / "metrics.json").read_text())
    assert local[-1]["stage"] == "publish"
    # re-publish over an existing delivery is idempotent, not an error
    run_pipeline(cfg, "20160122", "flow", publish=str(dest))


def test_publish_remote_failure_raises(flow_day, monkeypatch):
    import subprocess

    from oni_ml_tpu.runner.ml_ops import publish_day

    calls = {}

    def fake_run(argv, capture_output, text):
        calls["argv"] = argv

        class R:
            returncode = 1
            stderr = "ssh: connect refused"

        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    import pytest

    with pytest.raises(RuntimeError, match="connect refused"):
        publish_day("/data/20160122", "uinode:/var/oni")
    assert calls["argv"] == ["scp", "-r", "/data/20160122", "uinode:/var/oni"]


def test_flow_pipeline_resume_skips_done_stages(flow_day):
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160122", "flow")
    metrics2 = run_pipeline(cfg, "20160122", "flow")
    assert all(m.get("skipped") for m in metrics2
               if m["stage"] != "plans")
    # Forcing a single stage re-runs exactly that stage.
    metrics3 = run_pipeline(cfg, "20160122", "flow", force=True,
                            stages=[Stage.SCORE])
    assert _stages(metrics3) == ["score"]
    assert not metrics3[0].get("skipped")


def test_flow_pipeline_with_feedback(flow_day):
    cfg, tmp_path = flow_day
    header = ",".join(f"c{i}" for i in range(22))
    fb_row = ["3", "2016-01-22 10:00:00", "10.0.0.1", "172.16.0.1", "80",
              "55000", "TCP", ".AP.", "5", "500"] + ["x"] * 12
    (tmp_path / "flow_scores.csv").write_text(
        header + "\n" + ",".join(fb_row) + "\n"
    )
    metrics = run_pipeline(cfg, "20160123", "flow")
    pre = metrics[0]
    assert pre["feedback_rows"] == 5  # dup_factor
    assert pre["events"] == 65
    # Feedback duplicates train the model but are NOT scored: the results
    # hold exactly the 60 raw events.
    score = next(m for m in metrics if m["stage"] == "score")
    assert score["scored_events"] == 60
    results = (tmp_path / "20160123" / "flow_results.csv").read_text().splitlines()
    assert len(results) == 60


def test_dns_pipeline_end_to_end(tmp_path):
    rng = np.random.default_rng(11)
    names = ["mail.google.com", "x.intel.com", "a.b.evil-dga-q7.biz",
             "google.com", "4.3.2.1.in-addr.arpa"]
    rows = [
        ",".join(
            dns_row(
                tstamp=str(1454000000 + int(rng.integers(0, 86400))),
                flen=str(rng.integers(40, 500)),
                ip=f"10.0.1.{rng.integers(1, 6)}",
                qname=str(rng.choice(names)),
                qtype=str(rng.choice([1, 28])),
                rcode="0",
            )
        )
        for _ in range(50)
    ]
    raw = tmp_path / "dns.csv"
    raw.write_text("\n".join(rows) + "\n")
    top = tmp_path / "top-1m.csv"
    top.write_text("1,google.com\n2,intel.com\n")
    cfg = PipelineConfig(
        data_dir=str(tmp_path),
        dns_path=str(raw),
        top_domains_path=str(top),
        lda=LDAConfig(num_topics=3, em_max_iters=5, batch_size=16,
                      min_bucket_len=16, seed=5),
        scoring=ScoringConfig(threshold=1.1),
    )
    run_pipeline(cfg, "20160122", "dns")
    day = tmp_path / "20160122"
    results = (day / "dns_results.csv").read_text().splitlines()
    assert len(results) == 50
    scores = [float(r.split(",")[-1]) for r in results]
    assert scores == sorted(scores)
    # Sanity: every result row carries the word column and the scores are
    # real probabilities.
    assert all(0 <= s <= 1 for s in scores)
    metrics_path = json.loads((day / "metrics.json").read_text())
    assert _stages(metrics_path) == ["pre", "corpus", "lda", "score"]


def test_flow_pipeline_online_lda(flow_day):
    """--online swaps the batch EM engine for streaming SVI; every file
    contract downstream (final.*, results CSV ordering) is unchanged."""
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160124", "flow", online=True)
    day = tmp_path / "20160124"
    for name in ["final.beta", "final.gamma", "final.other",
                 "flow_results.csv"]:
        assert (day / name).exists(), name
    gm = formats.read_gamma(str(day / "final.gamma"))
    assert (gm > 0).all()
    results = (day / "flow_results.csv").read_text().splitlines()
    assert len(results) == 60
    mins = [min(float(r.split(",")[-2]), float(r.split(",")[-1]))
            for r in results]
    assert mins == sorted(mins)


def test_runner_cli_smoke(flow_day, capsys):
    cfg, tmp_path = flow_day
    from oni_ml_tpu.runner.ml_ops import main

    rc = main([
        "20160122", "flow", "1.1",
        "--data-dir", str(tmp_path),
        "--flow-path", cfg.flow_path,
        "--topics", "4", "--em-max-iters", "3", "--batch-size", "32",
    ])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    records = [json.loads(l) for l in out]
    assert _stages(records) == ["pre", "corpus", "lda", "score"]
    assert (tmp_path / "20160122" / "flow_results.csv").exists()


def test_runner_profile_flag(flow_day, tmp_path_factory):
    cfg, tmp_path = flow_day
    from oni_ml_tpu.runner.ml_ops import main

    prof_dir = str(tmp_path_factory.mktemp("prof"))
    rc = main([
        "20160125", "flow", "1.1",
        "--data-dir", str(tmp_path), "--flow-path", cfg.flow_path,
        "--topics", "3", "--em-max-iters", "2", "--batch-size", "32",
        "--profile", prof_dir,
    ])
    assert rc == 0
    import os
    captured = [
        os.path.join(r, f) for r, _, fs in os.walk(prof_dir) for f in fs
    ]
    assert captured, "profiler produced no trace files"


def test_runner_rejects_bad_date():
    from oni_ml_tpu.runner.ml_ops import main

    with pytest.raises(SystemExit):
        main(["2016", "flow"])


def test_runner_perf_flags(flow_day, capsys):
    """--[no-]warm-start / --dense-precision must reach LDAConfig and the
    run must still produce the full stage sequence (on CPU the dense path
    is gated off, so these only steer config — the semantics knobs are
    exercised by tests/test_dense_estep.py)."""
    cfg, tmp_path = flow_day
    from oni_ml_tpu.runner.ml_ops import _build_config, build_parser, main

    args = build_parser().parse_args([
        "20160122", "flow", "1.1", "--dense-precision", "bf16",
    ])
    built = _build_config(args)
    assert built.lda.warm_start_gamma is True      # default on
    assert built.lda.dense_precision == "bf16"

    fresh = _build_config(build_parser().parse_args([
        "20160122", "flow", "1.1", "--no-warm-start",
    ]))
    assert fresh.lda.warm_start_gamma is False

    rc = main([
        "20160122", "flow", "1.1",
        "--data-dir", str(tmp_path), "--flow-path", cfg.flow_path,
        "--topics", "4", "--em-max-iters", "3", "--batch-size", "32",
        "--no-warm-start", "--dense-precision", "bf16", "--force",
    ])
    assert rc == 0


def test_eval_quality_flag_records_held_out_metrics(flow_day):
    cfg, tmp_path = flow_day
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    metrics = run_pipeline(cfg, "20160122", "flow", force=True,
                           eval_quality=True)
    lda = next(m for m in metrics if m["stage"] == "lda")
    assert np.isfinite(lda["completion_per_token_ll"])
    assert lda["completion_per_token_ll"] < 0
    assert lda["completion_perplexity"] > 1

    # Resumed run (lda stage skipped): the metric still appears,
    # computed from the saved final.beta/final.other.
    metrics2 = run_pipeline(cfg, "20160122", "flow", eval_quality=True)
    lda2 = next(m for m in metrics2 if m["stage"] == "lda")
    # The journal-driven resume (telemetry flight recorder) upgrades
    # the skip evidence when the prior run journaled its completion;
    # "outputs exist" remains the file-contract fallback.
    assert lda2.get("skipped") in (
        "journal: stage completed in a prior run", "outputs exist",
    )
    np.testing.assert_allclose(
        lda2["completion_per_token_ll"], lda["completion_per_token_ll"],
        rtol=1e-6,
    )


def test_pre_stage_spills_raw_lines(flow_day):
    """stage_pre streams raw rows to raw_lines.bin (native path):
    features.pkl must reference the spill file, not embed the bytes,
    and a vanished spill file must fail the score stage with a
    recoverable message."""
    import pickle

    from oni_ml_tpu.features import native_flow
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160122", "flow", force=True)
    day = tmp_path / "20160122"
    spill = day / "raw_lines.bin"
    assert spill.exists() and spill.stat().st_size > 0
    with open(day / "features.pkl", "rb") as f:
        feats = pickle.load(f)
    from oni_ml_tpu.features.blob import MmapBlob

    assert isinstance(feats.lines_blob, MmapBlob)
    # The pickle references the spill path; raw row bytes must not be
    # embedded (a distinctive slice of the spilled blob is absent).
    probe = spill.read_bytes()[:64]
    assert probe not in (day / "features.pkl").read_bytes()
    # Resume with the spill file gone: the score stage must say how to
    # recover instead of crashing deep in emit.
    (day / "flow_results.csv").unlink()
    spill.unlink()
    with pytest.raises(FileNotFoundError, match="re-run the pre stage"):
        run_pipeline(cfg, "20160122", "flow", stages=["score"])


def test_moved_day_dir_rescore(flow_day):
    """features.pkl records the spill path from pre time; a published/
    moved/renamed day dir must still re-score — stage_score re-resolves
    the spill beside features.pkl instead of trusting the stale
    absolute path (round-3 advisor finding: the stale path surfaced as
    a confusing FileNotFoundError)."""
    import dataclasses
    import shutil

    from oni_ml_tpu.features import native_flow
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160122", "flow", force=True)
    old_day = tmp_path / "20160122"
    results = (old_day / "flow_results.csv").read_bytes()

    # Move the whole data dir (publish/rename scenario): the recorded
    # spill path now points into a directory that no longer exists.
    new_root = tmp_path.parent / (tmp_path.name + "_moved")
    shutil.move(str(tmp_path), str(new_root))
    tmp_path.mkdir()  # keep the fixture's dir alive for pytest cleanup
    cfg2 = dataclasses.replace(cfg, data_dir=str(new_root))
    (new_root / "20160122" / "flow_results.csv").unlink()
    run_pipeline(cfg2, "20160122", "flow", stages=["score"])
    assert (new_root / "20160122" / "flow_results.csv").read_bytes() \
        == results


def test_moved_day_dir_stale_spill_refused(flow_day):
    """Re-resolution adopts a same-named spill ONLY when its size
    matches the one recorded at pre time: a stale raw_lines.bin left
    behind by an earlier interrupted run in a copied day dir would
    otherwise be silently scored against mismatched row offsets —
    wrong lines, not an error (round-4 advisor finding)."""
    import dataclasses
    import shutil

    from oni_ml_tpu.features import native_flow
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160122", "flow", force=True)
    new_root = tmp_path.parent / (tmp_path.name + "_moved2")
    shutil.move(str(tmp_path), str(new_root))
    tmp_path.mkdir()  # keep the fixture's dir alive for pytest cleanup
    day = new_root / "20160122"
    spill = day / "raw_lines.bin"
    spill.write_bytes(spill.read_bytes() + b"stale trailing garbage\n")
    (day / "flow_results.csv").unlink()
    cfg2 = dataclasses.replace(cfg, data_dir=str(new_root))
    with pytest.raises(FileNotFoundError, match="stale or partial"):
        run_pipeline(cfg2, "20160122", "flow", stages=["score"])


def test_partial_spill_at_recorded_path_refused(flow_day):
    """The size identity check guards the RECORDED path too, not only
    the post-move re-resolution: a pre re-run interrupted mid-ingest
    leaves a partial raw_lines.bin at the recorded path while the
    complete run's features.pkl survives — scoring would silently read
    wrong lines (round-5 review finding)."""
    from oni_ml_tpu.features import native_flow
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    cfg, tmp_path = flow_day
    run_pipeline(cfg, "20160122", "flow", force=True)
    day = tmp_path / "20160122"
    spill = day / "raw_lines.bin"
    spill.write_bytes(spill.read_bytes()[: spill.stat().st_size // 2])
    (day / "flow_results.csv").unlink()
    with pytest.raises(FileNotFoundError, match="stale or partial"):
        run_pipeline(cfg, "20160122", "flow", stages=["score"])


def test_eval_holdout_true_held_out_split(flow_day):
    """--eval-holdout: beta trains on the hash-split remainder, the
    excluded docs' per-token ll is recorded, and the file contract is
    intact — doc_results/final.gamma cover EVERY document (held-out
    thetas inferred under the trained beta)."""
    cfg, tmp_path = flow_day
    from oni_ml_tpu.models.evaluate import hash_split
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    metrics = run_pipeline(cfg, "20160122", "flow", force=True,
                           eval_holdout=0.3)
    lda = next(m for m in metrics if m["stage"] == "lda")
    assert 0 < lda["held_out_docs"]
    assert lda["held_out_frac"] == 0.3
    assert np.isfinite(lda["held_out_per_token_ll"])
    assert lda["held_out_per_token_ll"] < 0
    assert lda["held_out_perplexity"] > 1

    day = tmp_path / "20160122"
    doc_rows = (day / "doc_results.csv").read_text().splitlines()
    docs = formats.read_doc_dat(str(day / "doc.dat"))
    assert len(doc_rows) == len(docs)          # every doc has a theta row
    gamma = formats.read_gamma(str(day / "final.gamma"))
    assert gamma.shape[0] == len(docs)
    # Scoring runs against the full-contract model outputs.
    assert (day / "flow_results.csv").exists()

    # The split is deterministic by doc NAME: same fraction, same docs.
    t1, h1 = hash_split(docs, 0.3)
    t2, h2 = hash_split(list(reversed(docs)), 0.3)
    assert {docs[i] for i in h1} == {docs[len(docs) - 1 - i] for i in h2}
    assert lda["held_out_docs"] == len(h1)


def test_eval_holdout_rejects_online_and_bad_frac(flow_day):
    cfg, _ = flow_day
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    with pytest.raises(ValueError, match="batch-mode only"):
        run_pipeline(cfg, "20160124", "flow", force=True, online=True,
                     eval_holdout=0.2, stages=[Stage.LDA])
    with pytest.raises(ValueError, match="mutually exclusive"):
        run_pipeline(cfg, "20160124", "flow", force=True,
                     eval_quality=True, eval_holdout=0.2,
                     stages=[Stage.LDA])
    with pytest.raises(ValueError, match="in \\(0, 1\\)"):
        from oni_ml_tpu.models.evaluate import hash_split

        hash_split(["a", "b"], 1.5)


def test_dns_sources_expand_dir_and_glob(tmp_path):
    """dns_path accepts directories and globs like FLOW_PATH; empty
    expansions raise instead of producing an empty day."""
    import pytest

    from oni_ml_tpu.sources.builtin import _dns_sources

    d = tmp_path / "dns_parts"
    d.mkdir()
    for i in range(3):
        (d / f"part-{i}.csv").write_text(
            ",".join(dns_row(ip=f"10.3.0.{i}")) + "\n"
        )
    by_dir = _dns_sources(str(d))
    by_glob = _dns_sources(str(d / "part-*.csv"))
    by_list = _dns_sources(",".join(str(d / f"part-{i}.csv")
                                    for i in range(3)))
    assert by_dir == by_glob == by_list
    assert len(by_dir) == 3
    empty = tmp_path / "empty_dir_"
    empty.mkdir()
    with pytest.raises(OSError, match="no DNS input files"):
        _dns_sources(str(empty))
