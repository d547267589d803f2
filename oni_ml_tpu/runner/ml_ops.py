"""Pipeline runner — replaces ml_ops.sh (SURVEY.md §2.1).

`ml_ops.sh YYYYMMDD {flow|dns} [TOL]` drove five processes across three
runtimes (Spark/YARN, local Python, a 20-rank MPI binary) glued by HDFS
copies, scp fan-outs, and sleep-based barriers.  Here the same run is one
process driving device computations:

    python -m oni_ml_tpu.runner.ml_ops 20160122 flow 1e-20 \
        --flow-path raw.csv --data-dir /data

Stages (each persists its reference-format outputs into the day directory
and can be resumed individually — the per-stage checkpointing the
reference's architecture implies but never implements, SURVEY §5.3-5.4):

    pre     raw events -> FeatureTable (features.pkl) + word_counts.dat
    corpus  word_counts.dat -> words.dat / doc.dat / model.dat
    lda     model.dat -> final.beta/.gamma/.other + likelihood.dat
            -> doc_results.csv / word_results.csv
    score   features + results -> <dsource>_results.csv

Config comes from flags (duxbay.conf's env-var contract is honored as
fallback: FLOW_PATH, DNS_PATH, LPATH, TOL, DUPFACTOR).  Per-stage
wall-clock and row counts stream as JSON lines to stdout and
metrics.json — the structured observability the reference lacked
(its diagnostics were bash `time` + println, SURVEY §5.1, §5.5).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import pickle
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable

from ..config import (
    DataplaneConfig,
    FeedbackConfig,
    LDAConfig,
    OnlineLDAConfig,
    PipelineConfig,
    PlansConfig,
    ScoringConfig,
    TelemetryConfig,
)
from ..io import Corpus, formats
from ..models import train_corpus, train_corpus_online
from ..scoring import ScoringModel


class Stage(str, Enum):
    PRE = "pre"
    CORPUS = "corpus"
    LDA = "lda"
    SCORE = "score"


class MissingArtifactError(RuntimeError):
    """A stage needed an upstream checkpoint that is not on disk — a
    `--stages` invocation against an incomplete (or --no-checkpoints)
    day.  Raised BEFORE the loader so the operator gets the artifact
    name and the flag that regenerates it, not a stack trace from deep
    inside a parser."""


STAGE_ORDER = [Stage.PRE, Stage.CORPUS, Stage.LDA, Stage.SCORE]

# Stage -> files that mark it complete (resume contract).
_STAGE_OUTPUTS = {
    Stage.PRE: ["features.pkl", "word_counts.dat"],
    Stage.CORPUS: ["words.dat", "doc.dat", "model.dat"],
    Stage.LDA: [
        "final.beta", "final.gamma", "final.other", "likelihood.dat",
        "doc_results.csv", "word_results.csv",
    ],
    Stage.SCORE: [],  # results file name depends on dsource
}


@dataclass
class RunContext:
    config: PipelineConfig
    fdate: str
    dsource: str  # "flow" | "dns"
    day_dir: str
    mesh: object = None
    vocab_sharded: bool = False
    online: bool = False
    eval_quality: bool = False
    eval_holdout: float = 0.0
    metrics: list = field(default_factory=list)
    # In-process featurizer→corpus handoff: stage_pre parks the live
    # feature container here so stage_corpus builds the Corpus straight
    # from its interned tables (Corpus.from_features) instead of
    # re-parsing word_counts.dat; stage_corpus clears it once consumed.
    features: object = None
    # Background word_counts.dat writer (stage_pre): the file is the
    # resume/audit contract, not an input to this run, so its write
    # overlaps the LDA stage.  Joined (and errors re-raised) before
    # run_pipeline returns.
    wc_writer: object = None
    wc_writer_err: list = field(default_factory=list)
    # Telemetry flight recorder (oni_ml_tpu/telemetry/): the crash-safe
    # run journal (RunJournal; None on non-coordinator ranks and when
    # disabled), the stages this run may skip because a replayed
    # journal marked them complete, the span recorder, and the optional
    # device heartbeat whose check() gates each stage entry.
    journal: object = None
    journal_done: set = field(default_factory=set)
    recorder: object = None
    heartbeat: object = None
    # Streaming dataplane (oni_ml_tpu/dataplane/): the per-run
    # orchestrator for background checkpoint sinks, overlap tasks, and
    # bounded inter-stage channels (None = the serial file-contract
    # path: --no-dataplane, or any multi-process rank).  The hand-off
    # slots carry live stage outputs downstream so no stage re-reads
    # what the previous one just computed: `features` (pre→corpus AND
    # pre→score — the featurized day is scoring's input too, so with a
    # dataplane it survives until the score stage consumes it),
    # `corpus_handoff` (corpus→lda), `model_handoff` (lda→score, the
    # round-trip-exact ScoringModel), and `score_prep` (the
    # tokenization/index prep task running concurrently with EM).
    plane: object = None
    corpus_handoff: object = None
    model_handoff: object = None
    score_prep: object = None
    # Stages this invocation may run (wanted) — stage fns consult it to
    # decide whether a downstream hand-off is worth producing.
    wanted: list = field(default_factory=list)
    # True when a replayed journal shows a prior --no-checkpoints run
    # of this day: fail-fast messages then name the provenance of the
    # missing file contract.
    prior_no_checkpoints: bool = False
    # Background-write failures collected at dataplane drain (the
    # generalization of wc_writer_err) — the run fails on them after
    # the finally block, without masking the run's own exception.
    background_errs: list = field(default_factory=list)

    def path(self, name: str) -> str:
        return os.path.join(self.day_dir, name)

    def results_name(self) -> str:
        return f"{self.dsource}_results.csv"

    def emit(self, record: dict) -> None:
        record = {"fdate": self.fdate, "dsource": self.dsource, **record}
        print(json.dumps(record), flush=True)
        self.metrics.append(record)


def _stage_done(ctx: RunContext, stage: Stage) -> "str | None":
    """Why this stage can be skipped, or None if it must run.

    The file contract is necessary either way (a journal that says
    "done" about artifacts someone deleted must not win); the journal
    upgrades the evidence — replayed `stage end` records from a prior
    run of this day mean the resume is journal-driven, which the skip
    record names so post-mortems can tell the two apart."""
    names = _STAGE_OUTPUTS[stage] or [ctx.results_name()]
    if not all(os.path.exists(ctx.path(n)) for n in names):
        return None
    if stage.value in ctx.journal_done:
        return "journal: stage completed in a prior run"
    return "outputs exist"


def _require_artifacts(ctx: RunContext, names: list, stage: Stage,
                       regen_stage: Stage) -> None:
    """Fail fast — naming the artifact and the regenerating flag —
    when a stage's file-contract input is missing (the `--stages` /
    resume path; in-process runs hand the live object downstream and
    never get here)."""
    missing = [n for n in names if not os.path.exists(ctx.path(n))]
    if not missing:
        return
    msg = (
        f"stage {stage.value} needs {missing[0]} in {ctx.day_dir} and it "
        f"does not exist; regenerate it with `ml_ops {ctx.fdate} "
        f"{ctx.dsource} --stages {regen_stage.value} --force`"
        + (f" (also missing: {', '.join(missing[1:])})"
           if len(missing) > 1 else "")
    )
    if ctx.prior_no_checkpoints:
        msg += (
            " — note: a prior run of this day used --no-checkpoints, so "
            "no inter-stage files were written; resume is refused by "
            "design, re-run the full day"
        )
    raise MissingArtifactError(msg)


def _score_wanted(ctx: RunContext) -> bool:
    """Whether this invocation may still run the score stage — decides
    if the lda stage should produce the model hand-off and spawn the
    scoring-prep overlap task."""
    return Stage.SCORE in (ctx.wanted or STAGE_ORDER)


def _coord_decision(value: bool) -> bool:
    """Make a per-stage decision on the coordinator and broadcast it, so
    ranks can never desync on filesystem state (a rank skipping a stage
    the others run would starve their suff-stats allreduce).  The
    broadcast doubles as the inter-stage barrier: non-coordinators wait
    here until the coordinator has finished the previous stage's writes.

    Rides the coordination client's KV store (parallel/allreduce.py) —
    NOT an XLA collective, which the CPU runtime cannot execute across
    processes (the old multihost_utils broadcast was exactly that, and
    the root of the suite's XlaRuntimeError)."""
    import jax

    if jax.process_count() == 1:
        return value
    from ..parallel.allreduce import get_collective

    return bool(get_collective().broadcast_obj(
        bool(value), "stage_decision"
    ))


def _all_ranks_ok(ok: bool) -> bool:
    """All-gather per-rank outcome flags; True only if EVERY rank
    succeeded.  Unlike a one-to-all broadcast this also relays
    non-coordinator failures (e.g. a rank whose shared-FS read raised
    before it entered the stage's collectives).  KV-store allgather —
    the wait polls the failure key, so a rank that already posted a
    structured failure surfaces as PeerFailure here rather than a
    barrier timeout."""
    import jax

    if jax.process_count() == 1:
        return ok
    from ..parallel.allreduce import get_collective

    flags = get_collective().allgather_obj(bool(ok), "stage_outcome")
    return all(flags)


def _run_stage(ctx: RunContext, stage: Stage, fn: Callable[[], dict]) -> None:
    from ..telemetry.spans import maybe_span  # jax-free fast import

    if ctx.heartbeat is not None:
        # Fail CLEANLY at the stage boundary once the backend is gone —
        # entering the stage would hang in its first device call.
        ctx.heartbeat.check()
    if ctx.journal is not None:
        ctx.journal.stage_begin(stage.value)
    from ..plans import warmup as _plans_warmup

    cc0 = _plans_warmup.compile_counts()
    t0 = time.perf_counter()
    try:
        with maybe_span(f"stage.{stage.value}", fdate=ctx.fdate,
                        dsource=ctx.dsource):
            info = fn()
    except BaseException as e:
        if ctx.journal is not None:
            ctx.journal.stage_end(
                stage.value, ok=False,
                wall_s=round(time.perf_counter() - t0, 3),
                error=repr(e)[:300],
            )
        raise
    wall_s = round(time.perf_counter() - t0, 3)
    # What the stage compiled (requests, cache hits, fresh traces, and
    # the seconds jax spent on them): the stage wall minus these is the
    # steady-state share.  Every stage ends on host data, so the wall
    # covers the device work it started.
    compiled = _plans_warmup.counts_delta(cc0)
    if compiled["compile_requests"] or compiled["trace_s"]:
        info = {**info, "compile": compiled}
    ctx.emit({"stage": stage.value, "wall_s": wall_s, **info})
    if ctx.journal is not None:
        # sync=True inside stage_end: the resume contract is durable
        # the moment the stage's outputs are.
        ctx.journal.stage_end(stage.value, ok=True, wall_s=wall_s, **info)


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------


def stage_pre(ctx: RunContext) -> dict:
    cfg = ctx.config
    from ..features.shards import resolve_pre_workers
    from ..sources import get as get_source

    workers, workers_src = resolve_pre_workers(
        cfg.pre_workers, with_source=True
    )
    timings: dict = {}
    # The whole day rides the source spec's `featurize_day` hook —
    # feedback ingestion, pinned-cut resolution, native/spill-file
    # streaming — so a registered source needs zero edits here.
    features, fb_rows = get_source(ctx.dsource).featurize_day(
        cfg, ctx.path("raw_lines.bin"), workers, timings,
    )
    if ctx.plane is not None:
        return _finish_pre_dataplane(ctx, features, fb_rows, workers,
                                     workers_src, timings)
    t0 = time.perf_counter()
    with open(ctx.path("features.pkl"), "wb") as f:
        pickle.dump(features, f, protocol=pickle.HIGHEST_PROTOCOL)
    timings["pickle_s"] = round(time.perf_counter() - t0, 3)
    # Native containers emit the whole word_counts buffer in C++ from
    # their interned tables + aggregated id arrays; building ~1.5M
    # Python (str,str,int) tuples and writing line-by-line was half the
    # pre stage on a 2M-event day.  Byte-identical to the fallback
    # (pinned by tests/test_scoring.py::test_native_word_counts_emit_*).
    t0 = time.perf_counter()
    n_wc = None
    blob = None
    if hasattr(features, "wc_ip"):
        from ..native_emit import word_counts_emit

        blob = word_counts_emit(features)
    if blob is not None:
        timings["wc_emit_s"] = round(time.perf_counter() - t0, 3)
        n_wc = len(features.wc_ip)
        # word_counts.dat is the resume/audit contract (_stage_done),
        # not an input to THIS run — stage_corpus consumes the live
        # container via Corpus.from_features.  Writing it on a
        # background thread overlaps the file IO with the LDA stage;
        # run_pipeline joins (and surfaces errors) before returning.
        # The write is tmp+rename so the contract name only ever names
        # a COMPLETE file: _stage_done checks bare existence, and the
        # overlap window spans the whole LDA stage — a hard kill
        # mid-write must not leave a truncated word_counts.dat that a
        # resumed run would silently parse.
        wc_path = ctx.path("word_counts.dat")
        # Remove any PRIOR run's contract file before the overlap
        # window opens: tmp+rename protects against truncation, not
        # staleness — a force rerun killed during LDA must leave a day
        # dir whose resume re-runs pre, never one that silently pairs
        # this run's features.pkl with the previous run's
        # word_counts.dat.
        for stale in (wc_path, wc_path + ".tmp"):
            try:
                os.unlink(stale)
            except FileNotFoundError:
                pass

        def _write_wc(blob=blob, path=wc_path):
            try:
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException as e:  # surfaced at join
                ctx.wc_writer_err.append(e)

        import threading

        ctx.wc_writer = threading.Thread(
            target=_write_wc, name="wc-writer"
        )
        ctx.wc_writer.start()
        timings["wc_write"] = "background"
    else:
        triples = features.word_counts()
        # Same atomic publish as the background path: a crash mid-write
        # must not leave a partial contract file under the real name.
        formats.write_word_counts(ctx.path("word_counts.dat.tmp"), triples)
        os.replace(ctx.path("word_counts.dat.tmp"),
                   ctx.path("word_counts.dat"))
        n_wc = len(triples)
        timings["wc_emit_s"] = round(time.perf_counter() - t0, 3)
        timings["wc_write"] = "inline"
    ctx.features = features  # direct handoff to stage_corpus
    return _pre_record(ctx, features, fb_rows, workers, workers_src,
                       timings, n_wc)


def _pre_record(ctx: RunContext, features, fb_rows, workers, workers_src,
                timings, n_wc) -> dict:
    """The pre stage's metrics record, shared by the serial and
    dataplane tails."""
    merge_wall = timings.pop("merge_s", None)
    out = {
        "events": features.num_events,
        # Native containers carry interned id arrays (wc_ip); the pure-
        # Python oracle does not.  Which one featurized the day depends
        # on whether the C++ library built (native_build.py).
        "featurizer": "native" if hasattr(features, "wc_ip") else "python",
        "word_count_rows": n_wc,
        "feedback_rows": len(fb_rows),
        "pre_workers": workers,
        "plans": {
            "pre_workers": {"value": workers, "source": workers_src}
        },
        "wall": timings,
    }
    if merge_wall is not None:
        out["merge_wall_s"] = merge_wall
    return out


def _finish_pre_dataplane(ctx: RunContext, features, fb_rows, workers,
                          workers_src, timings) -> dict:
    """Dataplane tail of the pre stage: the live container is the
    hand-off (to corpus assembly AND, later, to scoring), and both
    file artifacts — features.pkl and word_counts.dat — are demoted to
    background checkpoint sinks whose writes overlap the downstream
    stages.  Stale contract files are cleared synchronously BEFORE the
    overlap window opens (tmp+rename protects against truncation, not
    staleness — see the serial path's word_counts note)."""
    from ..dataplane import atomic_write, atomic_write_bytes, clear_stale

    plane = ctx.plane
    pkl_path = ctx.path("features.pkl")
    wc_path = ctx.path("word_counts.dat")
    clear_stale(pkl_path, wc_path)

    def _write_pkl(path=pkl_path, features=features):
        def _dump(tmp):
            with open(tmp, "wb") as f:
                pickle.dump(features, f, protocol=pickle.HIGHEST_PROTOCOL)
        atomic_write(path, _dump)

    n_wc = None
    if hasattr(features, "wc_ip"):
        n_wc = len(features.wc_ip)

        def _write_wc(path=wc_path, features=features):
            from ..native_emit import word_counts_emit

            blob = word_counts_emit(features)
            if blob is not None:
                atomic_write_bytes(path, blob)
            else:
                atomic_write(path, lambda tmp: formats.write_word_counts(
                    tmp, features.word_counts()))
    else:
        # Fallback containers materialize triples anyway; count them
        # here (the record needs n_wc) and only the write goes async.
        triples = features.word_counts()
        n_wc = len(triples)

        def _write_wc(path=wc_path, triples=triples):
            atomic_write(path, lambda tmp: formats.write_word_counts(
                tmp, triples))

    if plane.checkpoints:
        plane.checkpoint("features_pkl", _write_pkl, stage=Stage.PRE.value)
        plane.checkpoint("word_counts", _write_wc, stage=Stage.PRE.value)
        timings["pickle"] = "background"
        timings["wc_write"] = "background"
    else:
        timings["pickle"] = "skipped"
        timings["wc_write"] = "skipped"
    ctx.features = features  # hand-off: corpus assembly + scoring
    return _pre_record(ctx, features, fb_rows, workers, workers_src,
                       timings, n_wc)


def stage_corpus(ctx: RunContext) -> dict:
    plane = ctx.plane
    stream_info = None
    if ctx.features is not None and plane is not None:
        # Streaming dataplane: the featurizer's columnar word counts
        # flow through a bounded channel into incremental first-seen
        # assembly while the pre stage's demoted checkpoint writes
        # (features.pkl, word_counts.dat) are still in flight — the
        # full-day pre→corpus barrier is gone.  Identical corpus to
        # Corpus.from_features (pinned by tests/test_dataplane.py).
        # The features container stays parked: it is the score stage's
        # input too.
        from ..dataplane import (
            consume_corpus,
            stream_word_counts,
            word_count_columns,
        )

        wc = word_count_columns(ctx.features)
        ch = plane.channel("pre.wc->corpus")
        plane.spawn(
            "wc_stream",
            lambda: stream_word_counts(
                wc, ch, ctx.config.dataplane.chunk_rows
            ),
            stage=Stage.CORPUS.value,
            # The producer's put() backpressure waits are idle, not
            # work: exclude them from the task's work accounting so
            # bench's sum-of-stage-walls can't double-count the
            # consumer's inline wall.
            stall=lambda: ch.stats()["put_stall_s"],
        )
        corpus, builder = consume_corpus(ch, wc.ip_table, wc.word_table)
        handoff = "direct"
        stream_info = {"chunks": builder.chunks, "rows": builder.rows}
    elif ctx.features is not None:
        # In-process serial run: the featurizer's container is still
        # live — build the CSR straight from its interned tables
        # instead of re-parsing the ~word_count_rows text triples
        # stage_pre just held in native arrays (identical output,
        # pinned by tests/test_pre_parallel.py).
        corpus = Corpus.from_features(ctx.features)
        handoff = "direct"
        ctx.features = None  # release featurizer arrays before LDA
    else:
        # Resume path (--stages corpus, or pre skipped as done): the
        # emitted file is the contract.
        _require_artifacts(ctx, ["word_counts.dat"], Stage.CORPUS,
                           Stage.PRE)
        corpus = Corpus.from_word_counts_file(ctx.path("word_counts.dat"))
        handoff = "file"
    if plane is not None:
        # The LDA-C corpus triplet demoted to a background checkpoint
        # overlapping EM; the live corpus hands off in memory, so the
        # lda stage no longer re-parses model.dat it just watched this
        # stage write.
        from ..dataplane import clear_stale

        clear_stale(*(ctx.path(n) for n in _STAGE_OUTPUTS[Stage.CORPUS]))
        plane.checkpoint(
            "corpus_dat", lambda: corpus.save_atomic(ctx.day_dir),
            stage=Stage.CORPUS.value,
        )
        ctx.corpus_handoff = corpus
    else:
        corpus.save(ctx.day_dir)
    out = {
        "docs": corpus.num_docs,
        "vocab": corpus.num_terms,
        "tokens": corpus.num_tokens,
        "handoff": handoff,
    }
    if stream_info is not None:
        out["stream"] = stream_info
    return out


def _em_progress(ctx: RunContext):
    """Progress callback streaming EM likelihood points into the run
    journal — fired at the fused driver's host-sync cadence
    (LDAConfig.host_sync_every), so a killed fit leaves its sub-run
    likelihood trajectory on disk, not just likelihood.dat's possibly
    unflushed tail."""
    if ctx.journal is None:
        return None

    def progress(it: int, ll: float, conv: float) -> None:
        ctx.journal.em_likelihood(it, ll, conv)

    return progress


def stage_lda(ctx: RunContext) -> dict:
    plane = ctx.plane
    if ctx.corpus_handoff is not None:
        # Streamed corpus: EM consumes the CSR the corpus stage just
        # assembled in memory — the serial path's write-model.dat-then
        # -re-parse-it round trip is gone (the file is a background
        # checkpoint, not this stage's input).  Identical training:
        # same id orderings, same CSR values (tests/test_dataplane.py
        # pins final.beta/likelihood.dat bytes against the file path).
        corpus = ctx.corpus_handoff
        ctx.corpus_handoff = None
        corpus_src = "handoff"
    else:
        _require_artifacts(ctx, ["model.dat", "words.dat", "doc.dat"],
                           Stage.LDA, Stage.CORPUS)
        corpus = Corpus.from_model_dat(
            ctx.path("model.dat"), ctx.path("words.dat"),
            ctx.path("doc.dat")
        )
        corpus_src = "file"
    # The streamlined demotion path: plain batch EM only (the online
    # and holdout trainers own their file writes inline; they keep the
    # serial tail).
    streamline = (plane is not None and not ctx.online
                  and not ctx.eval_holdout)
    if (plane is not None and ctx.features is not None
            and not ctx.online and _score_wanted(ctx)):
        # Scoring prep overlaps EM: the event tokenization / model-row
        # index resolution depends only on the corpus orderings and
        # the featurized day — both final here — so it runs on a
        # background task for the whole fit and scoring dispatch
        # starts the moment the model converges.
        from ..dataplane import build_scoring_prep

        feats = ctx.features
        ctx.score_prep = plane.spawn(
            "score_prep",
            lambda: build_scoring_prep(
                feats, corpus.doc_names, corpus.vocab, ctx.dsource
            ),
            stage=Stage.SCORE.value,
        )
    held_metrics = {}
    if ctx.online:
        if ctx.vocab_sharded:
            raise ValueError(
                "--online supports data-parallel meshes only "
                "(vocab sharding is batch-mode)"
            )
        if ctx.eval_holdout:
            raise ValueError("--eval-holdout is batch-mode only")
        online_progress = None
        if ctx.journal is not None:
            def online_progress(info, _ctx=ctx):
                # StreamStepInfo: step/likelihood map onto the same
                # em_ll stream batch EM writes (conv has no online
                # analogue; rho is the useful third column).
                _ctx.journal.append({
                    "kind": "em_ll", "iter": int(info.step),
                    "ll": float(info.likelihood), "rho": float(info.rho),
                })
        result = train_corpus_online(
            corpus, ctx.config.online_lda, out_dir=ctx.day_dir,
            mesh=ctx.mesh, progress=online_progress,
        )
    elif ctx.eval_holdout:
        result, held_metrics = _train_with_holdout(ctx, corpus)
    else:
        # With checkpoints off, out_dir=None turns off likelihood.dat
        # streaming and checkpoint.npz resume too — the run's
        # observability record is the journal's em_ll stream.
        out_dir = ctx.day_dir if (plane is None or plane.checkpoints) \
            else None
        result = train_corpus(
            corpus,
            ctx.config.lda,
            out_dir=out_dir,
            mesh=ctx.mesh,
            vocab_sharded=ctx.vocab_sharded,
            progress=_em_progress(ctx),
            # Streamlined runs demote final.* to checkpoint sinks
            # below; the trainer must not also write them inline.
            save_final=not streamline,
        )
    from ..models.lda import _is_coordinator

    if _is_coordinator():
        if streamline:
            _demote_lda_artifacts(ctx, corpus, result)
        else:
            # result is rank-identical (collective gathers in
            # train_corpus*); the shared day dir has exactly one writer.
            formats.write_doc_results(
                ctx.path("doc_results.csv"), corpus.doc_names, result.gamma
            )
            formats.write_word_results(
                ctx.path("word_results.csv"), corpus.vocab, result.log_beta
            )
    if streamline and _score_wanted(ctx):
        # lda→score hand-off: the ScoringModel assembled in memory with
        # the results CSVs' round-trip arithmetic (ScoringModel.from_lda
        # — identical doubles, so identical scored bytes), parked so
        # scoring starts without reading back the demoted checkpoints.
        from ..sources import get as get_source

        ctx.model_handoff = ScoringModel.from_lda(
            corpus.doc_names, result.gamma, corpus.vocab, result.log_beta,
            get_source(ctx.dsource).fallback(ctx.config.scoring),
        )
    lls = [ll for ll, _ in result.likelihoods]
    out = {
        "em_iters": result.em_iters,
        "final_likelihood": lls[-1] if lls else None,
        "alpha": result.alpha,
        "corpus": corpus_src,
    }
    # Dispatch-knob provenance (plans.resolve via the trainer): which
    # source — config override, measured plan, or shipped default —
    # each tuned constant came from this run.
    plan_rec = getattr(result, "plan", None)
    if plan_rec:
        out["plans"] = plan_rec
    if ctx.eval_quality and _is_coordinator():
        out.update(_completion_score(ctx, result.log_beta, result.alpha,
                                     corpus))
    out.update(held_metrics)
    return out


def _demote_lda_artifacts(ctx: RunContext, corpus, result) -> None:
    """Submit the model artifacts (final.beta/gamma/other,
    doc_results.csv, word_results.csv) as background checkpoint sinks
    overlapping the score stage — same bytes as the serial inline
    writes, published atomically because the write window now spans
    downstream compute."""
    from ..dataplane import atomic_write, clear_stale

    plane = ctx.plane
    clear_stale(*(ctx.path(n) for n in (
        "final.beta", "final.gamma", "final.other",
        "doc_results.csv", "word_results.csv",
    )))
    log_beta, gamma, alpha = result.log_beta, result.gamma, result.alpha
    k = log_beta.shape[0]
    num_terms = corpus.num_terms
    doc_names, vocab = corpus.doc_names, corpus.vocab

    def _write_final():
        atomic_write(ctx.path("final.beta"),
                     lambda tmp: formats.write_beta(tmp, log_beta))
        atomic_write(ctx.path("final.gamma"),
                     lambda tmp: formats.write_gamma(tmp, gamma))
        atomic_write(ctx.path("final.other"),
                     lambda tmp: formats.write_other(tmp, k, num_terms,
                                                     alpha))

    plane.checkpoint("final_model", _write_final, stage=Stage.LDA.value)
    plane.checkpoint(
        "doc_results",
        lambda: atomic_write(
            ctx.path("doc_results.csv"),
            lambda tmp: formats.write_doc_results(tmp, doc_names, gamma),
        ),
        stage=Stage.LDA.value,
    )
    plane.checkpoint(
        "word_results",
        lambda: atomic_write(
            ctx.path("word_results.csv"),
            lambda tmp: formats.write_word_results(tmp, vocab, log_beta),
        ),
        stage=Stage.LDA.value,
    )


def _train_with_holdout(ctx: RunContext, corpus):
    """--eval-holdout FRAC: hash-split documents BEFORE training, train
    beta on the remainder only, and report the true held-out
    per-token log-likelihood of the excluded split (document-completion
    protocol, models/evaluate.py).  Unlike --eval-quality's
    training-set completion score, this number is valid for
    hyperparameter selection — beta never saw the held-out documents.

    The pipeline file contract is preserved: final.gamma /
    doc_results.csv still carry EVERY document (held-out docs get their
    doc-topic posterior inferred post-hoc under the trained beta — the
    scorer needs a theta row per IP), and final.beta/likelihood.dat
    reflect the train-split run."""
    import math

    import numpy as np

    from ..io import make_batches
    from ..models.evaluate import hash_split, held_out_per_token_ll
    from ..models.lda import LDAResult, _is_coordinator
    from ..ops import estep

    cfg = ctx.config.lda
    train_idx, held_idx = hash_split(corpus.doc_names, ctx.eval_holdout)
    if len(held_idx) == 0 or len(train_idx) == 0:
        raise ValueError(
            f"--eval-holdout {ctx.eval_holdout} split to "
            f"{len(train_idx)} train / {len(held_idx)} held-out docs of "
            f"{corpus.num_docs}; need both non-empty (tiny day?)"
        )
    # out_dir stays the day dir so likelihood.dat streams crash-safe and
    # checkpoint_every keeps working; train_corpus's final.* writes
    # cover the train subset only and are overwritten with the
    # full-contract versions below in the same process.
    result = train_corpus(
        corpus.select(train_idx),
        cfg,
        out_dir=ctx.day_dir,
        mesh=ctx.mesh,
        vocab_sharded=ctx.vocab_sharded,
        progress=_em_progress(ctx),
    )

    held_batches = make_batches(
        corpus.select(held_idx), batch_size=cfg.batch_size,
        min_bucket_len=cfg.min_bucket_len,
    )
    score = held_out_per_token_ll(
        result.log_beta, result.alpha, held_batches,
        var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
    )

    # Full-contract gamma: train rows from the fit, held-out rows
    # inferred under the trained beta (full tokens — what the scorer
    # conditions on for p(event)).
    import jax.numpy as jnp

    full_gamma = np.zeros((corpus.num_docs, result.gamma.shape[1]))
    full_gamma[train_idx] = result.gamma
    log_beta_dev = jnp.asarray(result.log_beta, jnp.float32)
    for b in held_batches:
        res = estep.e_step(
            log_beta_dev, jnp.float32(result.alpha),
            jnp.asarray(b.word_idx),
            jnp.asarray(b.counts, jnp.float32),
            jnp.asarray(b.doc_mask, jnp.float32),
            var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
            backend="xla",
        )
        sel = b.doc_mask == 1
        full_gamma[held_idx[b.doc_index[sel]]] = np.asarray(
            res.gamma, np.float64
        )[sel]

    full = LDAResult(
        log_beta=result.log_beta, gamma=full_gamma, alpha=result.alpha,
        likelihoods=result.likelihoods, em_iters=result.em_iters,
    )
    if _is_coordinator():
        # likelihood.dat was already streamed during fit.
        full.save(ctx.day_dir, include_likelihood=False)
    return full, {
        "held_out_frac": ctx.eval_holdout,
        "held_out_docs": int(len(held_idx)),
        "held_out_per_token_ll": score,
        "held_out_perplexity": math.exp(-score),
    }


def _completion_score(ctx: RunContext, log_beta, alpha, corpus=None) -> dict:
    """Document-completion score of the day's model (models/evaluate.py):
    gamma fits on each doc's even token slots, the odd slots score under
    the predictive distribution.  Run over the TRAINING day, this is a
    drift-monitoring number comparable across days — NOT a true held-out
    score (the odd tokens helped fit beta, so it is optimistic; for
    hyperparameter selection use models.evaluate on an excluded corpus
    split)."""
    import math

    from ..io import make_batches
    from ..models.evaluate import held_out_per_token_ll

    if corpus is None:
        corpus = Corpus.from_model_dat(
            ctx.path("model.dat"), ctx.path("words.dat"), ctx.path("doc.dat")
        )
    score = held_out_per_token_ll(
        log_beta, alpha, make_batches(corpus, ctx.config.lda.batch_size)
    )
    return {
        "completion_per_token_ll": score,
        "completion_perplexity": math.exp(-score),
    }


def stage_score(ctx: RunContext) -> dict:
    if ctx.features is not None:
        # Streaming dataplane: the live featurized day IS the scoring
        # input — no features.pkl read-back (that file is a background
        # checkpoint of the same object, so the arrays are identical).
        features = ctx.features
        ctx.features = None
        feat_src = "handoff"
    else:
        _require_artifacts(ctx, ["features.pkl"], Stage.SCORE, Stage.PRE)
        with open(ctx.path("features.pkl"), "rb") as f:
            features = pickle.load(f)
        feat_src = "file"
        _resolve_spill_blobs(ctx, features)
    from ..sources import get as get_source

    fallback = get_source(ctx.dsource).fallback(ctx.config.scoring)
    if ctx.model_handoff is not None:
        model = ctx.model_handoff
        ctx.model_handoff = None
        model_src = "handoff"
    else:
        _require_artifacts(
            ctx, ["doc_results.csv", "word_results.csv"], Stage.SCORE,
            Stage.LDA,
        )
        model = ScoringModel.from_files(
            ctx.path("doc_results.csv"), ctx.path("word_results.csv"),
            fallback,
        )
        model_src = "file"
    prep = None
    if ctx.score_prep is not None:
        # Join the EM-overlapped tokenization/index prep; by the time
        # training has converged this is normally already done, so the
        # span prices (near-)zero wait — a long join here means the
        # overlap failed to hide the prep and shows up in trace_view.
        from ..telemetry.spans import maybe_span

        with maybe_span("dataplane.prep_join"):
            prep = ctx.score_prep.result()
        ctx.score_prep = None
    return _score_day(ctx, features, model, prep,
                      feat_src=feat_src, model_src=model_src)


def _resolve_spill_blobs(ctx: RunContext, features) -> None:
    # Spilled raw rows (stage_pre) are referenced by the path recorded
    # at pre time.  The spill file lives beside features.pkl, so a
    # moved/renamed/published day dir invalidates the recorded path
    # while the file itself is right here — when (and ONLY when) the
    # recorded path is gone, re-resolve against this day dir (round-3
    # advisor finding: the stale path used to surface as a bare
    # FileNotFoundError deep in scoring; a valid recorded path always
    # wins, so a stale same-named spill here can't be silently
    # substituted), failing recoverably, naming the move, when neither
    # location has the file.
    for attr in ("lines_blob", "rows_blob"):
        blob = getattr(features, attr, None)
        if blob is None or not hasattr(blob, "path"):
            continue

        def _check_size(path):
            # Identity check before trusting ANY candidate — recorded
            # or re-resolved: a spill of a DIFFERENT size than the one
            # features.pkl was written against (stale leftover of an
            # earlier run in a copied day dir, or a partial rewrite
            # from an interrupted pre re-run at the recorded path)
            # would be scored against mismatched row offsets — wrong
            # lines, not an error (round-4 advisor finding; round-5
            # review widened it to the recorded path).  Size at spill
            # time rides in the pickle; pre-round-5 pickles lack it
            # and keep the old adopt-by-name behavior.
            want = getattr(blob, "size", None)
            have = os.path.getsize(path)
            if want is not None and have != want:
                raise FileNotFoundError(
                    f"features.pkl references spilled raw rows of "
                    f"{want} bytes (size at pre time); {path} holds "
                    f"{have} bytes — a stale or partial spill from a "
                    "different run, refusing to score against "
                    "mismatched offsets; re-run the pre stage "
                    "(--stages pre --force)"
                )

        if os.path.exists(blob.path):
            _check_size(blob.path)
            continue  # recorded path valid: never silently substitute
        local = ctx.path(os.path.basename(blob.path))
        if os.path.exists(local):
            _check_size(local)
            blob.path = local
        else:
            raise FileNotFoundError(
                f"features.pkl references spilled raw rows at {blob.path}, "
                f"and no {os.path.basename(blob.path)} exists in this day "
                f"directory ({ctx.day_dir}) either — the spill file was "
                "deleted or the day dir moved without it; re-run the pre "
                "stage (--stages pre --force)"
            )

def _score_day(ctx: RunContext, features, model, prep,
               feat_src: str, model_src: str) -> dict:
    sc = ctx.config.scoring
    from ..scoring import DispatchStats
    from ..sources import get as get_source

    score_fn = get_source(ctx.dsource).score_csv
    # engine="device" runs the fused on-chip filter pipeline
    # (scoring/pipeline.py), data-parallel over the run's mesh when one
    # is active — the same mesh the LDA stage trained on.  The default
    # host engine keeps the golden float64 CSV bytes.
    from ..plans import resolve
    from ..scoring.score import _score_engine

    engine = _score_engine(sc.engine)
    engine_src = (
        "config" if sc.engine
        else "env" if os.environ.get("ONI_ML_TPU_SCORE") else "default"
    )
    device = engine == "device"
    chunk = sc.device_chunk
    plans_rec = None
    if device:
        # Resolve only on the engine that USES the knob: a host run's
        # record must not attribute a device chunk it never dispatched.
        chunk, chunk_src = resolve("score_device_chunk", sc.device_chunk)
        chunk = int(chunk)
        plans_rec = {
            "score_device_chunk": {"value": chunk, "source": chunk_src}
        }
    stats = DispatchStats() if device else None
    warm = None
    if device and ctx.mesh is None:
        # AOT-compile the plan's entry points before the chunk loop so
        # the persistent compilation cache holds them (and the first
        # dispatch doesn't stall on a trace); counters distinguish
        # cache hits from fresh traces.  Warm at the EFFECTIVE chunk —
        # the pipeline shrinks it for days smaller than the plan's
        # chunk, and a warmup at the unshrunk shape would compile a
        # program the day never dispatches.
        from ..plans.warmup import warmup_scoring
        from ..scoring.pipeline import _effective_chunk

        try:
            warm = warmup_scoring(
                model.theta.shape[0], model.p.shape[0],
                model.num_topics,
                _effective_chunk(features.num_raw_events, chunk, None),
                dsource=ctx.dsource,
            )
        except Exception as e:  # warmup must never fail the stage
            warm = {"error": repr(e)[:200]}
    blob, scores = score_fn(
        features, model, sc.threshold,
        engine=sc.engine, chunk=chunk, mesh=ctx.mesh,
        stats=stats, prep=prep,
    )
    res_path = ctx.path(ctx.results_name())
    if ctx.plane is not None:
        # The results CSV is a PRODUCT, not a checkpoint: its write is
        # demoted to a background sink (overlapping the run's drain /
        # metrics tail) but never skipped by --no-checkpoints.
        from ..dataplane import atomic_write_bytes, clear_stale

        clear_stale(res_path)
        ctx.plane.output(
            "results_csv",
            lambda: atomic_write_bytes(res_path, blob),
            stage=Stage.SCORE.value,
        )
    else:
        with open(res_path, "wb") as f:
            f.write(blob)
    out = {
        "scored_events": features.num_raw_events,
        "scorer": {"value": engine, "source": engine_src},
        "flagged": int(len(scores)),
        "min_score": float(scores[0]) if len(scores) else None,
        "features": feat_src,
        "model": model_src,
        "prep": "overlapped" if prep is not None else "inline",
    }
    if plans_rec is not None:
        out["plans"] = plans_rec
    if warm is not None:
        out["warmup"] = warm
    if stats is not None:
        out["score_dispatch"] = stats.as_record()
        if ctx.journal is not None:
            ctx.journal.dispatch_stats(stats.as_record(), stage="score")
    return out


_STAGE_FNS = {
    Stage.PRE: stage_pre,
    Stage.CORPUS: stage_corpus,
    Stage.LDA: stage_lda,
    Stage.SCORE: stage_score,
}


def publish_day(day_dir: str, dest: str) -> dict:
    """Deliver the completed day directory to the operational-analytics
    consumer — the reference's final `scp -r ${LPATH} ${UINODE}:${RPATH}`
    (ml_ops.sh:118-121).  `dest` is either a local/NFS directory (copied
    with shutil) or an scp-style `host:path` remote."""
    name = os.path.basename(os.path.normpath(day_dir))
    if ":" in dest.split(os.sep, 1)[0]:
        import subprocess

        proc = subprocess.run(
            ["scp", "-r", day_dir, dest], capture_output=True, text=True
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"publish to {dest} failed (rc={proc.returncode}): "
                f"{proc.stderr.strip()[-500:]}"
            )
        return {"published": f"{dest}/{name}", "transport": "scp"}
    import shutil

    target = os.path.join(dest, name)
    shutil.copytree(day_dir, target, dirs_exist_ok=True)
    return {"published": target, "transport": "copy"}


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def run_pipeline(
    config: PipelineConfig,
    fdate: str,
    dsource: str,
    force: bool = False,
    stages: list[Stage] | None = None,
    mesh=None,
    vocab_sharded: bool = False,
    online: bool = False,
    publish: str | None = None,
    eval_quality: bool = False,
    eval_holdout: float = 0.0,
) -> list[dict]:
    """Run (or resume) the pipeline for one day.  Completed stages are
    skipped unless `force`; `stages` restricts to a subset (they still run
    in pipeline order)."""
    from ..sources import names as source_names

    if dsource not in source_names():
        raise ValueError(
            f"dsource must be one of {'|'.join(source_names())}, "
            f"got {dsource!r}"
        )
    if online and eval_holdout:
        raise ValueError("--eval-holdout is batch-mode only")
    if eval_quality and eval_holdout:
        # Combining them would score the FULL corpus under a beta
        # trained on the remainder — a third metric that matches
        # neither flag's documented semantics and silently breaks
        # --eval-quality's day-over-day comparability.
        raise ValueError(
            "--eval-quality and --eval-holdout are mutually exclusive: "
            "use --eval-quality for drift monitoring (full-day training "
            "and scoring) or --eval-holdout for a true held-out score"
        )
    dp = config.dataplane
    if not dp.checkpoints:
        # Checkpoints-off is the pure-streaming mode: nothing but the
        # product artifacts is written, so there is no file contract to
        # resume against.  Restrict it to the configurations where that
        # is coherent — a full in-process batch chain.
        if not dp.enabled:
            raise ValueError(
                "--no-checkpoints requires the streaming dataplane "
                "(drop --no-dataplane)"
            )
        if stages is not None:
            raise ValueError(
                "--no-checkpoints cannot run a --stages subset: without "
                "the file contract there is nothing for a partial run "
                "to read or resume from"
            )
        if online or eval_holdout:
            raise ValueError(
                "--no-checkpoints supports the plain batch pipeline "
                "only (the online/holdout trainers own their file "
                "contracts)"
            )
    day_dir = formats.ensure_dir(config.day_dir(fdate))
    ctx = RunContext(
        config=config,
        fdate=fdate,
        dsource=dsource,
        day_dir=day_dir,
        mesh=mesh,
        vocab_sharded=vocab_sharded,
        online=online,
        eval_quality=eval_quality,
        eval_holdout=eval_holdout,
    )
    import jax

    # Measured-plans layer (oni_ml_tpu/plans): wire the persistent
    # compilation cache BEFORE the first trace so every compiled
    # program serializes to disk (a re-run deserializes instead of
    # re-tracing — the counters below prove it per run), then pin the
    # run's plan store so every consumer resolves tuned knobs against
    # the same cache.
    plc = config.plans
    from ..plans import NullStore, PlanStore, counters_snapshot, use_store
    from ..plans import warmup as _plans_warmup

    cc_rec = _plans_warmup.setup_compilation_cache(
        enabled=plc.compilation_cache
    )
    if not plc.enabled:
        plan_store: "PlanStore | NullStore | None" = NullStore()
    elif plc.cache_path:
        plan_store = PlanStore(plc.cache_path)
    else:
        plan_store = None        # the default store (the user cache)
    plans_cc0 = _plans_warmup.compile_counts()
    plans_ctr0 = counters_snapshot()
    from ..telemetry import roofline as _rl0

    roofline0 = _rl0.emit_count()   # scope the rollup to THIS run

    # Multi-host contract (--multihost): every rank runs run_pipeline
    # against a SHARED day dir.  Host-only stages (pre/corpus/score) and
    # all file writes execute on the coordinator alone; stage_lda runs
    # on every rank — each trains its document shards HOST-LOCALLY and
    # the sufficient statistics cross processes through the explicit
    # allreduce (parallel/allreduce.py), never a global mesh spanning
    # processes.  Stage skip/run decisions broadcast from the
    # coordinator (KV store) so ranks cannot desync on filesystem
    # state.
    multiproc = jax.process_count() > 1
    is_coord = jax.process_index() == 0
    if multiproc and mesh is not None:
        from ..parallel.mesh import is_local_mesh

        if not is_local_mesh(mesh):
            raise ValueError(
                "multi-process runs take a HOST-LOCAL mesh only "
                "(parallel.local_mesh(); --mesh under --multihost is "
                "interpreted per host): distributed EM shards documents "
                "across processes and allreduces the suff-stats "
                "explicitly instead of building one global SPMD program"
            )
    wanted = stages or STAGE_ORDER
    ctx.wanted = list(wanted)
    if not dp.checkpoints and multiproc:
        # Multi-host ranks coordinate through the shared file contract
        # (the plane is single-process only) — a pure-streaming run is
        # impossible there, and silently writing the full contract
        # would contradict what the operator asked for.
        raise ValueError(
            "--no-checkpoints requires a single-process run: multi-host "
            "ranks coordinate through the inter-stage file contract"
        )

    # Telemetry flight recorder (docs/observability.md).  Coordinator
    # only: the shared day dir has exactly one journal writer, like
    # metrics.json.  The existing journal is replayed FIRST (tolerating
    # a killed run's truncated tail) so `--stages` resume can pick up
    # from it; then this run appends behind a run_start marker.
    tel = config.telemetry
    hb = None
    from ..telemetry.spans import use_recorder

    if tel.journal and is_coord:
        from ..telemetry import (
            HeartbeatMonitor,
            Journal,
            Recorder,
            RunJournal,
        )

        jpath = ctx.path("run_journal.jsonl")
        replayed = Journal.replay(jpath)
        prior_done = RunJournal.completed_stages(replayed)
        # Provenance for fail-fast messages: a prior --no-checkpoints
        # run explains a day dir with a journal but no file contract.
        ctx.prior_no_checkpoints = any(
            r.get("kind") == "run_start"
            and r.get("checkpoints") is False
            for r in replayed
        )
        ctx.journal = RunJournal(
            Journal(jpath, fsync_every=tel.journal_fsync_every)
        )
        ctx.journal_done = set() if force else prior_done
        ctx.journal.run_start(
            force=force, fdate=fdate, dsource=dsource,
            stages=[Stage(s).value for s in wanted],
            replayed_records=len(replayed),
            journal_done=sorted(prior_done),
            checkpoints=dp.checkpoints,
        )
        ctx.recorder = Recorder(journal=ctx.journal.journal)
        if tel.heartbeat_s > 0:
            hb = HeartbeatMonitor(
                interval_s=tel.heartbeat_s,
                timeout_s=tel.heartbeat_timeout_s,
                max_misses=tel.heartbeat_max_misses,
                journal=ctx.journal,
                # Probe round trips feed the run's shared registry
                # (heartbeat.probe_latency_s histogram): degradation is
                # on the metrics plane before BackendLost ever fires.
                recorder=ctx.recorder,
            ).start()
            ctx.heartbeat = hb

    # Streaming dataplane (oni_ml_tpu/dataplane): single-process runs
    # only — multi-host ranks coordinate through the shared file
    # contract, exactly as before.  The plane owns the run's background
    # checkpoint sinks, overlap tasks, and bounded channels; it is
    # drained (joined, errors surfaced) in the finally below, the
    # generalization of the old word_counts writer join.
    plane_record = None
    if dp.enabled and not multiproc:
        from ..dataplane import Dataplane

        ctx.plane = Dataplane(
            dp,
            recorder=ctx.recorder,
            journal=ctx.journal.journal if ctx.journal is not None
            else None,
        )

    run_ok = False
    run_err: "BaseException | None" = None
    try:
        with (use_recorder(ctx.recorder) if ctx.recorder is not None
              else contextlib.nullcontext()), \
             (use_store(plan_store) if plan_store is not None
              else contextlib.nullcontext()):
            _run_stages(ctx, wanted, force, multiproc, is_coord)
        run_ok = True
    except BaseException as e:
        run_err = e
        raise
    finally:
        # The background word_counts.dat writer (stage_pre) must finish
        # before this process hands the day dir to anyone — it is the
        # resume/audit contract.  Joined even on a failing run so a
        # crashed LDA stage can't leave a half-written contract file
        # racing the interpreter exit.
        th = ctx.wc_writer
        if th is not None:
            th.join()
            ctx.wc_writer = None
        if ctx.plane is not None:
            # Drain the dataplane: join every background checkpoint
            # sink and overlap task (demoted writes are part of the
            # run's contract — the day dir must be complete before
            # this process hands it to anyone), collect their errors,
            # and keep the per-task/per-edge accounting for the
            # metrics record below.
            plane_record = ctx.plane.drain()
            ctx.background_errs.extend(ctx.plane.errors)
        if ctx.wc_writer_err:
            ctx.background_errs.extend(
                ("word_counts", e) for e in ctx.wc_writer_err
            )
        if hb is not None:
            hb.stop()
        if ctx.journal is not None:
            # A failed background checkpoint write fails the RUN (the
            # RuntimeError below) — the journal's run_end must not
            # record ok=True for an invocation whose caller saw an
            # exception and whose contract file is missing.
            err = run_err if run_err is not None else (
                ctx.background_errs[0][1] if ctx.background_errs else None
            )
            ctx.journal.run_end(
                ok=run_ok and not ctx.background_errs,
                **({} if err is None else {"error": repr(err)[:300]}),
            )
            ctx.journal.close()
        if plc.cache_path and plan_store is not None:
            # Run-scoped store (--plan-cache): close its journal fd on
            # every exit path; the process-wide default store stays
            # open.
            plan_store.close()
    if ctx.background_errs:
        name, first = ctx.background_errs[0]
        raise RuntimeError(
            f"dataplane background write/task {name!r} failed"
        ) from first
    if is_coord:
        # The run's plans/compile accounting: how many XLA compile
        # requests the persistent cache served (a fully warmed re-run
        # shows traces == 0) and how many autotune sweeps actually ran
        # (a tuned backend shows 0) — the acceptance counters, in
        # metrics.json where tests can assert them.
        cc_end = dict(cc_rec)
        if cc_rec.get("enabled"):
            cc_end["entries_end"] = _plans_warmup.cache_entries(
                cc_rec["dir"]
            )
        ctr = counters_snapshot()
        ctx.emit({
            "stage": "plans",
            "enabled": plc.enabled,
            "store": getattr(
                plan_store, "path", None
            ) if plan_store is not None else "default",
            "compilation_cache": cc_end,
            **_plans_warmup.counts_delta(plans_cc0),
            **{k: ctr[k] - plans_ctr0.get(k, 0) for k in ctr},
        })
        # Roofline rollup (telemetry/roofline.py): every per-phase
        # record the stages emitted into the journal, surfaced in
        # metrics.json too, so "how far from the hardware was this
        # run, per phase?" is greppable without replaying the journal.
        from ..telemetry import roofline as _roofline

        rl_records = _roofline.emitted_records(since=roofline0)
        if rl_records:
            ctx.emit({"stage": "roofline", "records": rl_records})
        if plane_record is not None and (
            plane_record["tasks"] or plane_record["edges"]
        ):
            # Dataplane accounting: per-task walls with stage
            # attribution (the work the overlap hid) and per-edge
            # queue/stall totals — what bench.py's pipeline_e2e
            # critical-path breakdown and trace_view's stall table
            # consume.
            ctx.emit({"stage": "dataplane", **plane_record})

    def _dump_metrics() -> None:
        with open(ctx.path("metrics.json"), "w") as f:
            json.dump(ctx.metrics, f, indent=1)

    # metrics.json lands BEFORE publish so the delivered day dir carries
    # the run's metrics — and so a failed delivery cannot lose them.
    if is_coord:
        _dump_metrics()
    if publish and is_coord:
        t0 = time.perf_counter()
        info = publish_day(day_dir, publish)
        ctx.emit(
            {"stage": "publish",
             "wall_s": round(time.perf_counter() - t0, 3), **info}
        )
        _dump_metrics()  # refresh the local copy with the publish record
    return ctx.metrics


def _release_handoffs(ctx: RunContext, stage: Stage) -> None:
    """Drop hand-offs whose consumer (this stage) will not run.  The
    featurizer container has TWO consumers on the dataplane — corpus
    assembly and scoring — so it survives a skipped corpus stage when
    the score stage is still coming; a serial run keeps the legacy
    release-before-LDA's-peak behavior (scoring re-reads
    features.pkl)."""
    if stage is Stage.CORPUS:
        if ctx.plane is None or not _score_wanted(ctx):
            ctx.features = None
    elif stage is Stage.LDA:
        ctx.corpus_handoff = None
    elif stage is Stage.SCORE:
        ctx.features = None
        ctx.model_handoff = None


def _run_stages(ctx: RunContext, wanted, force: bool, multiproc: bool,
                is_coord: bool) -> None:
    for stage in STAGE_ORDER:
        if stage not in wanted:
            _release_handoffs(ctx, stage)
            continue
        done = (
            _stage_done(ctx, stage) if (is_coord or not multiproc) else None
        )
        skip = bool(done) and not force
        if multiproc:
            skip = _coord_decision(skip)
        if skip:
            _release_handoffs(ctx, stage)
            if is_coord:
                record = {"stage": stage.value, "skipped": done}
                if ctx.journal is not None:
                    ctx.journal.stage_skipped(stage.value, done)
                if stage is Stage.LDA and ctx.eval_quality:
                    # The eval only needs the saved model; a resumed run
                    # still gets its day-quality number.
                    other = formats.read_other(ctx.path("final.other"))
                    log_beta = formats.read_beta(ctx.path("final.beta"))
                    record.update(
                        _completion_score(ctx, log_beta, other["alpha"])
                    )
                ctx.emit(record)
            continue
        err: Exception | None = None
        if is_coord or stage is Stage.LDA:
            try:
                _run_stage(ctx, stage, lambda s=stage: _STAGE_FNS[s](ctx))
            except Exception as e:  # relayed to the other ranks below
                err = e
        if multiproc:
            if err is not None:
                # Structured failure relay (parallel/allreduce.py): the
                # failure key unblocks peers stuck INSIDE the stage's
                # suff-stats allreduce (their waits poll it between
                # slices) as well as peers already at the outcome
                # barrier below — they raise PeerFailure ("failed on
                # another rank"), a BackendLost subclass, so ml_ops
                # exits rc=3 with the structured payload instead of a
                # raw traceback.
                from ..parallel.allreduce import get_collective

                get_collective().fail(f"stage {stage.value}: {err!r}")
            # Outcome barrier: a stage failure on ANY rank must fail
            # every rank — otherwise the survivors block forever in the
            # next decision broadcast.  A rank that dies WITHOUT posting
            # (SIGKILL) surfaces on its peers as a bounded PeerFailure
            # timeout in the collective wait (covered by
            # tests/test_multihost.py's failure-injection tests).
            try:
                ok = _all_ranks_ok(err is None)
            except Exception as barrier_err:
                # The barrier collective itself can fail when another
                # rank is inside a different collective or already died;
                # the local stage error (if any) is the root cause and
                # must not be masked by it.
                if err is not None:
                    raise err from barrier_err
                raise
            if not ok and err is None:
                from ..parallel.allreduce import PeerFailure

                raise PeerFailure(
                    f"stage {stage.value} failed on another rank; "
                    "aborting this rank"
                )
        if err is not None:
            raise err


def _build_config(args: argparse.Namespace) -> PipelineConfig:
    env = os.environ
    return PipelineConfig(
        data_dir=args.data_dir or env.get("LPATH", "."),
        flow_path=args.flow_path or env.get("FLOW_PATH", ""),
        dns_path=args.dns_path or env.get("DNS_PATH", ""),
        proxy_path=args.proxy_path or env.get("PROXY_PATH", ""),
        top_domains_path=args.top_domains or "",
        qtiles_path=args.qtiles or "",
        pre_workers=args.pre_workers,
        lda=LDAConfig(
            num_topics=args.topics,
            alpha_init=args.alpha,
            em_max_iters=args.em_max_iters,
            batch_size=args.batch_size,
            seed=args.seed,
            checkpoint_every=args.checkpoint_every,
            warm_start_gamma=args.warm_start,
            dense_precision=args.dense_precision,
            em_shards=args.em_shards,
        ),
        online_lda=OnlineLDAConfig(
            num_topics=args.topics,
            alpha=args.alpha,
            eta=args.eta,
            tau0=args.tau0,
            kappa=args.kappa,
            batch_size=args.batch_size,
            seed=args.seed,
            checkpoint_every=args.checkpoint_every,
        ),
        feedback=FeedbackConfig(
            dup_factor=(
                args.dup_factor
                if args.dup_factor is not None
                else int(env.get("DUPFACTOR", 1000))
            )
        ),
        scoring=ScoringConfig(threshold=args.tol),
        telemetry=TelemetryConfig(
            journal=not args.no_journal,
            heartbeat_s=args.heartbeat,
        ),
        plans=PlansConfig(
            enabled=not args.no_plans,
            cache_path=args.plan_cache or "",
            compilation_cache=not args.no_compilation_cache,
        ),
        dataplane=DataplaneConfig(
            enabled=not args.no_dataplane,
            checkpoints=not args.no_checkpoints,
        ),
    )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ml_ops",
        description="oni_ml_tpu suspicious-connects pipeline "
        "(replaces ml_ops.sh YYYYMMDD {flow|dns} [TOL]); "
        "`ml_ops serve --help` for the streaming scoring service, "
        "`ml_ops continuous --help` for windowed streaming ingestion "
        "with warm-start EM and drift-gated publishes "
        "(--stream/--replicated composes the multi-tenant standing "
        "service over the replica fleet)",
    )
    from ..sources import names as source_names

    p.add_argument("fdate", help="day to analyze, YYYYMMDD")
    p.add_argument("dsource", choices=list(source_names()))
    p.add_argument(
        "tol", nargs="?", type=float,
        default=float(os.environ.get("TOL", 1.1)),
        help="suspicion threshold (ml_ops.sh:17-18 defaults TOL=1.1)",
    )
    p.add_argument("--data-dir", default=None, help="working dir (LPATH)")
    p.add_argument(
        "--flow-path", default=None,
        help="netflow CSV input: file, directory, glob, or "
        "comma-separated list — multiple files ingest as one corpus "
        "with joint quantile cuts (the reference's HDFS FLOW_PATH "
        "location; config 3's 30-day corpus)",
    )
    p.add_argument(
        "--dns-path", default=None,
        help="DNS input: CSV/parquet file, directory, glob, or "
        "comma-separated list (the reference's comma-separated Hive "
        "parquet paths, dns_pre_lda.scala:142)",
    )
    p.add_argument(
        "--proxy-path", default=None,
        help="proxy/HTTP log CSV input: file, directory, glob, or "
        "comma-separated list (sources/generic.ProxySource columns)",
    )
    p.add_argument("--top-domains", default=None, help="top-1m.csv path")
    p.add_argument(
        "--qtiles", default=None,
        help="precomputed flow quantile cuts file (flow_qtiles format); "
        "skips the in-run ECDF pass and pins word identity across days",
    )
    p.add_argument(
        "--pre-workers", type=int, default=0, metavar="N",
        help="pre-stage shard workers: day files split into line-aligned "
        "byte ranges featurized concurrently, with a deterministic "
        "first-seen merge keeping every output byte-identical to the "
        "sequential pass (0 = auto from host cores, 1 = legacy "
        "single-pass)",
    )
    p.add_argument("--topics", type=int, default=20)
    p.add_argument("--alpha", type=float, default=2.5)
    p.add_argument("--em-max-iters", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=1024)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--checkpoint-every", type=int, default=0,
        help="persist (beta, alpha, iter) every N EM iterations; an "
        "interrupted lda stage resumes from the checkpoint (0=off)",
    )
    p.add_argument(
        "--dup-factor", type=int, default=None,
        help="feedback duplication (default: DUPFACTOR env or 1000)",
    )
    p.add_argument(
        "--eval-quality", action="store_true",
        help="score the day's model by document completion "
        "(per-token log-likelihood / perplexity on each doc's "
        "odd token slots; models/evaluate.py) and record it in the "
        "lda stage metrics — a drift-monitoring number comparable "
        "across days, optimistic vs a true held-out split",
    )
    p.add_argument(
        "--eval-holdout", type=float, default=0.0, metavar="FRAC",
        help="hash-split FRAC of documents out BEFORE training, train "
        "beta on the remainder, and record the true held-out per-token "
        "log-likelihood of the excluded split in the lda stage metrics "
        "— valid for hyperparameter selection, unlike --eval-quality's "
        "training-set completion score.  doc_results.csv still covers "
        "every document (held-out docs get their theta inferred under "
        "the trained beta).  Batch mode only; mutually exclusive with "
        "--eval-quality",
    )
    p.add_argument(
        "--warm-start", action=argparse.BooleanOptionalAction, default=True,
        help="seed each EM iteration's variational fixed point from the "
        "previous gamma (same optimum, fewer inner iterations; default "
        "on — use --no-warm-start for the reference's fresh-start "
        "likelihood.dat semantics, whose mid-run values differ in late "
        "decimals)",
    )
    p.add_argument(
        "--dense-precision", choices=["f32", "bf16"], default="f32",
        help="dense E-step matmul operand storage; bf16 is bit-identical "
        "under XLA's DEFAULT matmul precision on current TPUs (measured "
        "on v5e; that default already truncates MXU inputs — refused if "
        "a jax.default_matmul_precision override is active) and ~10%% "
        "faster.  bf16 also stores the dense corpus at 2 B a cell where "
        "that is exact: bfloat16 for counts up to 256, int16 up to "
        "32,767 (DUPFACTOR feedback cells, whose products then run at "
        "float32), else float32",
    )
    p.add_argument(
        "--online", action="store_true",
        help="streaming (stochastic variational) LDA instead of batch EM",
    )
    p.add_argument("--eta", type=float, default=0.01,
                   help="online: topic-word Dirichlet prior")
    p.add_argument("--tau0", type=float, default=64.0,
                   help="online: learning-rate delay")
    p.add_argument("--kappa", type=float, default=0.7,
                   help="online: learning-rate decay exponent")
    p.add_argument("--force", action="store_true", help="re-run all stages")
    p.add_argument(
        "--stages", default=None,
        help="comma-separated subset of pre,corpus,lda,score",
    )
    p.add_argument(
        "--mesh", default=None, metavar="DATA,MODEL",
        help="device mesh shape; MODEL>1 shards the vocabulary",
    )
    p.add_argument(
        "--publish", default=None, metavar="DEST",
        help="after all stages complete, deliver the day directory to "
        "DEST: a local/NFS path (copied) or an scp-style host:path — "
        "the reference's final scp to the UI node (ml_ops.sh:118-121)",
    )
    p.add_argument(
        "--multihost", action="store_true",
        help="initialize jax.distributed (one controller process per host; "
        "coordinator/process env via JAX_COORDINATOR_ADDRESS etc.) for "
        "pod-scale distributed EM — the reference's mpiexec -f "
        "machinefile fan-out (ml_ops.sh:80), minus MPI: each rank trains "
        "a deterministic contiguous document shard on ITS OWN devices "
        "(--mesh is per host: parallel.local_mesh) and the beta/alpha "
        "sufficient statistics cross processes through an explicit "
        "allreduce (psum over ICI on real pods, a coordination-service "
        "KV ring on CPU clusters).  Requires --data-dir on a filesystem "
        "shared by all hosts: the coordinator is the only writer; other "
        "ranks read the shared stage outputs and join the reduce",
    )
    p.add_argument(
        "--em-shards", type=int, default=0, metavar="N",
        help="distributed-EM document shard count (0 = auto: 8, grown "
        "to cover the process count).  The shard plan — and the "
        "suff-stats reduction tree — derives from the corpus and N, "
        "not the rank count, so runs at different rank counts with the "
        "same N produce byte-identical coordinator artifacts "
        "(ONI_ML_TPU_EM_SHARDS overrides)",
    )
    p.add_argument(
        "--no-journal", action="store_true",
        help="disable the crash-safe run journal "
        "(run_journal.jsonl in the day dir: stage spans, EM likelihood "
        "points, scoring dispatch stats — the resume/post-mortem "
        "contract; docs/observability.md)",
    )
    p.add_argument(
        "--heartbeat", type=float, default=0.0, metavar="SECS",
        help="probe device liveness every SECS seconds on a background "
        "thread (tiny jitted add + transfer, journaled); a backend that "
        "stops answering becomes a clean BackendLost failure at the "
        "next stage boundary instead of a silent hang (0 = off)",
    )
    p.add_argument(
        "--no-plans", action="store_true",
        help="disable measured-plan lookups (oni_ml_tpu/plans): every "
        "tuned knob falls back to config/default exactly as before the "
        "plan cache existed; nothing is read from or written to the "
        "cache",
    )
    p.add_argument(
        "--plan-cache", default=None, metavar="PATH",
        help="plan-cache JSONL file for this run (default: "
        "ONI_ML_TPU_PLAN_CACHE env, else ~/.cache/oni_ml_tpu/"
        "plans.jsonl)",
    )
    p.add_argument(
        "--no-compilation-cache", action="store_true",
        help="do not wire jax_compilation_cache_dir (by default every "
        "compiled program persists to JAX_COMPILATION_CACHE_DIR — or, "
        "when it is unset, <checkout>/.jax_cache — so a re-run "
        "re-traces nothing; "
        "the run's metrics record compile requests vs cache hits)",
    )
    p.add_argument(
        "--no-dataplane", action="store_true",
        help="disable the streaming dataplane (oni_ml_tpu/dataplane): "
        "run the serial file-contract pipeline — every stage writes "
        "its artifacts inline and the next stage reads them back from "
        "disk.  Artifacts are byte-identical either way; the dataplane "
        "only changes when files land and what stages read",
    )
    p.add_argument(
        "--no-checkpoints", action="store_true",
        help="skip the demoted inter-stage checkpoint files entirely "
        "(features.pkl, word_counts.dat, words/doc/model.dat, final.*, "
        "likelihood.dat, doc/word_results.csv): the run streams "
        "everything in memory and writes only its products (results "
        "CSV, metrics.json, run_journal.jsonl).  A later --stages "
        "resume against such a day is refused — there is no file "
        "contract to resume from.  Full-chain batch runs only",
    )
    p.add_argument(
        "--profile", default=None, metavar="DIR",
        help="capture a jax.profiler trace of the whole run into DIR "
        "(view with TensorBoard); replaces the reference's bash `time` "
        "stage timing (SURVEY §5.1)",
    )
    return p


def main(argv: list[str] | None = None) -> int:
    import sys

    if argv is None:
        argv = sys.argv[1:]
    # `ml_ops serve ...` is the streaming scoring service (runner/serve.py)
    # — a long-running process over a COMPLETED day's artifacts, not a
    # fifth batch stage, so it routes before the YYYYMMDD parser.
    if argv and argv[0] == "serve":
        from . import serve

        return serve.main(argv[1:])
    # `ml_ops continuous ...` is the windowed streaming-ingestion mode
    # (runner/continuous.py): a standing train-and-serve loop — ring-
    # buffered corpus window, warm-start EM refreshes, drift-gated
    # fleet publishes — rather than a per-day batch run, so it routes
    # before the YYYYMMDD parser like serve.  With `--stream ...
    # --replicated N` it is the COMPOSED standing service: N tenants
    # share one train/serve co-scheduler (preemptible refresh chunks)
    # and publish through the replicated router fleet.
    if argv and argv[0] == "continuous":
        from . import continuous

        return continuous.main(argv[1:])
    # `ml_ops replica ...` / `ml_ops route ...` are the replicated
    # elastic serving fleet (runner/route.py): N serve replica
    # processes behind an async router with consistent-hash tenant
    # placement and shadow-promotion failover — long-running services,
    # so they route before the YYYYMMDD parser like serve.
    if argv and argv[0] == "replica":
        from .route import replica_main

        return replica_main(argv[1:])
    if argv and argv[0] == "route":
        from .route import route_main

        return route_main(argv[1:])
    # `ml_ops lint ...` is the static-analysis gate (oni_ml_tpu/analysis)
    # — same engine as tools/graftlint.py and the oni-graftlint console
    # script; routes before the YYYYMMDD parser like serve.
    if argv and argv[0] == "lint":
        from ..analysis.cli import main as lint_main

        return lint_main(argv[1:])
    p = build_parser()
    args = p.parse_args(argv)
    if len(args.fdate) != 8 or not args.fdate.isdigit():
        p.error("fdate must be YYYYMMDD (ml_ops.sh:8-20)")

    if args.multihost:
        from ..parallel import initialize_distributed

        # TPU pods / SLURM auto-detect through jax's cluster plugins;
        # plain CPU clusters (this jax version has no env-var cluster
        # plugin) bootstrap from the documented explicit env vars.
        env = os.environ
        initialize_distributed(
            env.get("JAX_COORDINATOR_ADDRESS") or None,
            int(env["JAX_NUM_PROCESSES"])
            if env.get("JAX_NUM_PROCESSES") else None,
            int(env["JAX_PROCESS_ID"])
            if env.get("JAX_PROCESS_ID") else None,
        )

    mesh = None
    vocab_sharded = False
    if args.mesh:
        from ..parallel.mesh import local_mesh, mesh_from_spec

        try:
            if args.multihost:
                # Per-host mesh: distributed EM is host-local; the
                # cross-process reduce is the explicit allreduce, so
                # the spec applies to THIS process's devices.
                parts = args.mesh.split(",")
                if len(parts) != 2:
                    raise ValueError(
                        f"mesh spec must be 'DATA,MODEL', got "
                        f"{args.mesh!r}"
                    )
                mesh = local_mesh(int(parts[0]), int(parts[1]))
                vocab_sharded = int(parts[1]) > 1
            else:
                mesh, vocab_sharded = mesh_from_spec(args.mesh)
        except ValueError as e:
            p.error(str(e))
    stages = (
        [Stage(s) for s in args.stages.split(",")] if args.stages else None
    )

    import contextlib

    profile_ctx = contextlib.nullcontext()
    if args.profile:
        import jax

        profile_ctx = jax.profiler.trace(
            args.profile, create_perfetto_trace=True
        )
    from ..telemetry import BackendLost

    try:
        with profile_ctx:
            run_pipeline(
                _build_config(args),
                args.fdate,
                args.dsource,
                force=args.force,
                stages=stages,
                mesh=mesh,
                vocab_sharded=vocab_sharded,
                online=args.online,
                publish=args.publish,
                eval_quality=args.eval_quality,
                eval_holdout=args.eval_holdout,
            )
    except BackendLost as e:
        # The heartbeat's whole point: a dead backend exits as a
        # structured, journaled failure, not a hang or a bare
        # traceback.  The journal already carries the backend_lost
        # record and every completed stage.
        print(
            json.dumps({
                "fdate": args.fdate, "dsource": args.dsource,
                "error": "backend_lost", "detail": str(e),
            }),
            flush=True,
        )
        return 3
    except MissingArtifactError as e:
        # A --stages resume against a missing upstream checkpoint:
        # structured fail-fast naming the artifact and the regenerating
        # flag, not a loader stack trace.
        print(
            json.dumps({
                "fdate": args.fdate, "dsource": args.dsource,
                "error": "missing_artifact", "detail": str(e),
            }),
            flush=True,
        )
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
