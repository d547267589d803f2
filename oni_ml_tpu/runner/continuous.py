"""Continuous ingestion — the standing service that kills the day
boundary (ROADMAP item 3; `ml_ops continuous`).

The batch pipeline's unit of work is one FINISHED day: an event at
00:05 is servable ~24 h later, and every day pays a full
EM-from-scratch even when the topics barely moved.  This runner
generalizes the PR 8 streaming dataplane into a standing loop on one
process — the same devices the serving fleet scores from:

    raw slices ──► featurization ──► CorpusWindow (ring-buffered CSR,
       │                              first-seen vocab growth,
       │                              O(evicted) retirement)
       └────────► FleetScorer (events scored under the CURRENT model
                  the moment they arrive — servable in seconds)

    every refresh_every_s of event time:
        window.advance ─► snapshot (pow2 vocab capacity tier)
        ─► WindowTrainer.fit  (warm-started from the previous
           published topics; the f64 convergence check early-exits
           after the few iterations the stream actually moved)
        ─► DriftDetector.evaluate/check  (held-out per-token LL vs
           the journal's rolling history)
        ─► publish gate: drifted models are VETOED and never reach
           FleetRegistry — serving keeps the prior version
           bit-identically; healthy models hot-swap in.

Zero post-warmup retraces by construction: the window pads its
vocabulary to pow2 capacity tiers (the compiled [K, V] family is
keyed by tier, not census), window batches pad to the full batch
size, the refresh reuses ONE WindowTrainer's jitted programs, and the
fleet's capacity-tiered stack keys the serving dispatch by capacity.
The freshness ledger (event arrival → a model covering the event
published) is the headline the streaming_freshness bench reports.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import tempfile
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from ..config import PipelineConfig
from ..io import formats
from ..sources import get as get_source
from ..sources import names as source_names


@dataclass
class IngestSlice:
    """One paced ingest unit: raw event lines covering [t0, t1) of
    EVENT time, stamped with the wall clock it was delivered at."""

    lines: list
    t0: float
    t1: float
    arrival_wall: float = 0.0
    index: int = 0

    @property
    def events(self) -> int:
        return len(self.lines)


def event_time_s(line: str, dsource: str) -> float:
    """Event-time seconds for one raw CSV line, through the source
    spec's clock hook (flow: h/m/s columns; dns: unix_tstamp; declared
    sources: their `time_field`)."""
    return get_source(dsource).event_time_s(line)


def slice_events(
    lines, dsource: str, slice_s: float, *, t_base: "float | None" = None
) -> "list[IngestSlice]":
    """Order raw lines by event time and cut them into fixed
    `slice_s`-second slices — the replay decomposition of a historical
    day into the stream the day never was.  Deterministic: stable sort
    by event time, empty slices dropped.  Lines whose time columns do
    not parse (the reference day files' header row, truncated tails)
    are skipped, matching the featurizers' garbage-row tolerance."""
    if slice_s <= 0:
        raise ValueError(f"slice_s must be > 0, got {slice_s}")
    rows = []
    parsed = []
    # lint: ok(hot-path-event-loop, ingest-time slice ordering — one time-field parse per line at admission, off the flush path)
    for ln in lines:
        if not ln.strip():
            continue
        try:
            parsed.append(event_time_s(ln, dsource))
        except (ValueError, IndexError):
            continue          # header / malformed row: not an event
        rows.append(ln)
    times = np.asarray(parsed, np.float64)
    order = np.argsort(times, kind="stable")
    if t_base is None:
        t_base = float(times[order[0]]) if len(order) else 0.0
    slices: list[IngestSlice] = []
    cur: list = []
    cur_idx = 0
    for j in order:
        idx = int((times[j] - t_base) // slice_s)
        if cur and idx != cur_idx:
            slices.append(IngestSlice(
                lines=cur, t0=t_base + cur_idx * slice_s,
                t1=t_base + (cur_idx + 1) * slice_s, index=len(slices),
            ))
            cur = []
        if not cur:
            cur_idx = idx
        cur.append(rows[int(j)])
    if cur:
        slices.append(IngestSlice(
            lines=cur, t0=t_base + cur_idx * slice_s,
            t1=t_base + (cur_idx + 1) * slice_s, index=len(slices),
        ))
    return slices


def paced_slices(slices, speed: float, *, sleep=time.sleep):
    """Deliver slices at ×`speed` real time: the wall gap between
    consecutive slices is their event-time gap divided by `speed`.
    Stamps each slice's `arrival_wall` at delivery.  `speed=inf` (or
    any non-positive sleep result) delivers as fast as downstream
    consumes — the no-sleep test/bench mode."""
    if speed <= 0:
        raise ValueError(f"speed must be > 0, got {speed}")
    t_wall0 = time.perf_counter()
    t_sim0 = None
    for sl in slices:
        if t_sim0 is None:
            t_sim0 = sl.t1
        due = t_wall0 + (sl.t1 - t_sim0) / speed
        delay = due - time.perf_counter()
        if delay > 0 and np.isfinite(delay):
            sleep(delay)
        sl.arrival_wall = time.perf_counter()
        yield sl


@dataclass
class _SliceLedger:
    """Freshness bookkeeping for one ingested slice: arrival wall
    stamp, event count, event-time span end.  The service keeps only
    slices not yet covered by a publish (covered entries drop at the
    publish that covers them — they can never be re-covered)."""

    index: int
    arrival_wall: float
    events: int
    t1: float


@dataclass
class ContinuousResult:
    """run_continuous' payload (also what `ml_ops continuous`
    prints)."""

    payload: dict = field(default_factory=dict)


def _featurize_slice(lines, dsource: str, cuts):
    """One slice through the source's batch featurizer with PINNED cuts
    (a slice's own ECDF would bin values differently slice-over-slice
    and churn the vocabulary for nothing — serving/events.py's rule)."""
    return get_source(dsource).featurize(
        lines, skip_header=False, precomputed_cuts=cuts
    )


def _derive_cuts(lines, dsource: str, qtiles_path: str = ""):
    """Pin the stream's quantile cuts: from a qtiles file when the
    source supports one (stable word identity across service restarts),
    else from the bootstrap slice's own ECDF."""
    return get_source(dsource).derive_cuts(lines, qtiles_path)


class ContinuousService:
    """The standing train-and-serve loop.  Drive it with
    `run(slices)` (a paced IngestSlice iterable) or slice-by-slice via
    `ingest_slice` + `maybe_refresh` — tests inject drift that way."""

    def __init__(
        self,
        config: PipelineConfig,
        dsource: str,
        *,
        out_dir: str,
        tenant: str = "stream",
        fresh_control: bool = False,
        warmup_refreshes: "int | None" = None,
        journal=None,
        recorder=None,
        coscheduler=None,
        collective=None,
        publisher=None,
        freshness_sink=None,
    ) -> None:
        """Standalone by default; the composed (fleet) mode injects
        shared infrastructure:

        journal/recorder
            ONE RunJournal/Recorder shared by every per-tenant service
            (the fleet orchestrator owns their lifecycle; this service
            then scopes its histogram names by tenant and never calls
            run_start/run_end).
        coscheduler
            serving.CoScheduler — refresh fits run as preemptible
            chunks (the trainer's yield hook), slice scoring takes the
            high-priority serve slot.
        collective
            parallel.Collective — window refreshes train DISTRIBUTED
            (suff-stats allreduce, warm-start broadcast, vocab capacity
            tiers rank-synchronized so compiled shapes agree).
        publisher
            RouterBinding — publishes fan out through the replicated
            FleetRouter instead of the in-process FleetRegistry, and
            slice scoring rides the router's replicas.
        freshness_sink
            callable(wall_s, event_s) per covered slice — the fleet's
            cross-tenant freshness aggregate.
        """
        if dsource not in source_names():
            raise ValueError(
                f"dsource must be one of {'|'.join(source_names())}, "
                f"got {dsource!r}"
            )
        self.config = config
        self.cc = config.continuous
        self.dsource = dsource
        self.out_dir = formats.ensure_dir(out_dir)
        self.tenant = tenant
        self.fresh_control = fresh_control
        if warmup_refreshes is None:
            # "Post-warmup" starts once the window first reaches steady
            # state: while it is still FILLING (the first
            # window_s/refresh_every_s refreshes), each refresh can
            # legitimately meet a novel doc-length bucket and trace it
            # — that is startup, not churn.
            warmup_refreshes = int(
                np.ceil(self.cc.window_s
                        / max(self.cc.refresh_every_s, 1e-9))
            ) + 1
        self.warmup_refreshes = int(warmup_refreshes)

        from ..dataplane import CorpusWindow
        from ..models.drift import DriftDetector
        from ..serving import FleetRegistry, TenantSpec
        from ..telemetry import Journal, Recorder, RunJournal

        self.cosched = coscheduler
        self.collective = collective
        self.publisher = publisher
        self._freshness_sink = freshness_sink
        # Ingest (window growth, ledger append, scoring) and refresh
        # (advance/snapshot, ledger resolution) run on DIFFERENT
        # threads in the composed mode; this lock covers exactly the
        # window+ledger mutations.  Uncontended in the classic
        # single-thread drive.
        self._lock = threading.Lock()
        tel = config.telemetry
        self._owns_journal = journal is None
        # Fleet composition (shared out_dir, maybe-shared recorder):
        # scope histogram names and the metrics filename by tenant so
        # N services never collide.
        self._shared = (journal is not None or publisher is not None
                        or freshness_sink is not None)
        self.journal = None
        self.recorder = None
        if journal is not None:
            self.journal = journal
            self.recorder = recorder
            replayed = []
        elif tel.journal:
            jpath = os.path.join(self.out_dir, "run_journal.jsonl")
            replayed = Journal.replay(jpath)
            self.journal = RunJournal(
                Journal(jpath, fsync_every=tel.journal_fsync_every)
            )
            self.journal.run_start(
                mode="continuous", dsource=dsource, tenant=tenant,
                window_s=self.cc.window_s,
                refresh_every_s=self.cc.refresh_every_s,
                replayed_records=len(replayed),
            )
            self.recorder = Recorder(journal=self.journal.journal)
        else:
            replayed = []
        raw_journal = (
            self.journal.journal if self.journal is not None else None
        )
        self.window = CorpusWindow(
            self.cc.window_s, vocab_floor=self.cc.vocab_floor,
            recorder=self.recorder, journal=raw_journal,
        )
        self.drift = DriftDetector(
            tol_nats=self.cc.drift_tol_nats,
            history=self.cc.drift_history,
            min_history=self.cc.drift_min_history,
            journal=raw_journal, recorder=self.recorder,
        )
        # A restarted service resumes its drift baseline from the
        # journal instead of re-learning it over min_history refreshes.
        self.drift.prime(replayed)
        self._replayed = replayed
        self._qgate = None          # built lazily once cuts are pinned
        self.fleet = FleetRegistry(
            journal=raw_journal, recorder=self.recorder,
            capacity_tiers=True,
        )
        self.fleet.add_tenant(TenantSpec(tenant=tenant, dsource=dsource))
        self.scorer = None          # created at first publish
        self.cuts = None            # pinned at bootstrap
        self.trainer = None         # one per vocab capacity tier
        self.tier_rebuilds = 0
        self._prev_probs = None     # last PUBLISHED [V_real, K]
        self._prev_alpha = None
        self._last_fresh_iters = None
        self._next_refresh_t = None
        self._ledger: list[_SliceLedger] = []
        from ..telemetry.spans import Recorder as _Recorder

        rec = self.recorder or _Recorder()
        # Shared-recorder (fleet) mode scopes histogram names by tenant
        # — N services on one Recorder must not fold their ledgers into
        # one histogram (the per-tenant freshness contract).
        scope = f".{tenant}" if self._shared else ""
        # Two freshness ledgers: wall-clock (what THIS replay measured,
        # speed-dependent) and event-time (cadence lag + refresh wall —
        # what a real-time deployment would deliver, speed-invariant).
        self._freshness = rec.histogram("continuous.freshness_s" + scope)
        self._freshness_event = rec.histogram(
            "continuous.freshness_event_s" + scope
        )
        # Slice-level serve wall (submit→flush return), split by
        # whether a refresh fit was active at entry: the co-scheduler's
        # acceptance number is the refresh-active tail vs the idle one.
        self._serve_idle_ms = rec.histogram(
            "continuous.serve_idle_ms" + scope
        )
        self._serve_refresh_ms = rec.histogram(
            "continuous.serve_refresh_ms" + scope
        )
        self._freshness_count = 0
        self._tier_syncs = 0
        # A standing service runs indefinitely: per-refresh detail is
        # bounded (the journal holds the full history); aggregates are
        # running sums.
        from collections import deque as _deque

        self.refresh_records: "_deque[dict]" = _deque(maxlen=1024)
        self.refresh_count = 0
        self._fit_agg = {
            True: {"fits": 0, "wall_s": 0.0, "em_iters": 0},
            False: {"fits": 0, "wall_s": 0.0, "em_iters": 0},
        }
        self.events = 0
        self.slices = 0
        self.events_rejected = 0
        self.flagged = 0
        self.skipped_refreshes = 0
        self.control_record = None
        self._warmup_counts = None
        self._lda_cfg = None
        self._flagged_file = None
        self._last_ll = None

    # -- per-slice ingest ------------------------------------------------

    def ingest_slice(self, sl: IngestSlice) -> None:
        from ..dataplane import word_count_columns

        if sl.arrival_wall == 0.0:
            sl.arrival_wall = time.perf_counter()
        if self.cuts is None:
            self.cuts = _derive_cuts(sl.lines, self.dsource,
                                     self.config.qtiles_path)
        feats = _featurize_slice(sl.lines, self.dsource, self.cuts)
        with self._lock:
            self.window.ingest(word_count_columns(feats), sl.t0, sl.t1)
            self._ledger.append(_SliceLedger(
                index=sl.index, arrival_wall=sl.arrival_wall,
                events=sl.events, t1=sl.t1,
            ))
        if self._next_refresh_t is None:
            self._next_refresh_t = sl.t1 + self.cc.refresh_every_s
        self.slices += 1
        self.events += sl.events
        self._score_slice(sl)

    def _score_slice(self, sl: IngestSlice) -> None:
        """Scored-the-moment-they-arrive: every event rides the
        serving path under the CURRENT published model — the local
        FleetScorer (classic mode) or the replicated router (composed
        mode).  Under the co-scheduler this is the HIGH-priority side:
        the serve slot is claimed before submitting, so a refresh fit
        mid-flight yields at its next chunk boundary and this flush
        wins the next dispatch slot.  `refresh_active` is sampled
        BEFORE the slot wait — a slice arriving while a fit held the
        device is a during-refresh sample even though it scores after
        the yield.  Flagged (suspicious) events land through the
        scorer's on_batch sink (_start_scorer); a malformed event is
        shed and counted, never allowed to kill the standing service
        (serve mode's contract)."""
        via_router = (self.publisher is not None
                      and self.publisher.ready(self.tenant))
        if not via_router and self.scorer is None:
            return               # nothing published yet: ledger only
        refresh_active = (self.cosched.refresh_active
                          if self.cosched is not None else False)
        # In-process scoring shares ONE dispatch stream with the
        # trainer, so the slot waits out the in-flight chunk; router
        # scoring is remote (no shared stream), so the slot registers
        # pressure without blocking — the flush dispatches now and the
        # trainer defers its NEXT chunk.
        slot = (self.cosched.serve_slot(wait=not via_router)
                if self.cosched is not None else nullcontext())
        t0 = time.perf_counter()
        with slot:
            if via_router:
                self.publisher.submit_slice(
                    self.tenant, sl, refresh_active=refresh_active)
            else:
                for ln in sl.lines:
                    try:
                        self.scorer.submit(self.tenant, ln)
                    except ValueError:
                        self.events_rejected += 1
                self.scorer.flush()
        wall_ms = (time.perf_counter() - t0) * 1e3
        (self._serve_refresh_ms if refresh_active
         else self._serve_idle_ms).observe(wall_ms)

    def refresh_due(self, now_sim: float) -> bool:
        """Advance the cadence clock; True if `now_sim` crossed a
        refresh boundary.  Ingest-thread only (the composed mode's
        worker never touches the cadence clock) — the caller owns
        actually running `refresh`, possibly on another thread."""
        if (self._next_refresh_t is None
                or now_sim < self._next_refresh_t):
            return False
        while (self._next_refresh_t is not None
               and now_sim >= self._next_refresh_t):
            self._next_refresh_t += self.cc.refresh_every_s
        return True

    def maybe_refresh(self, now_sim: float) -> "dict | None":
        """Run one refresh if `now_sim` crossed the cadence boundary."""
        if not self.refresh_due(now_sim):
            return None
        return self.refresh(now_sim)

    # -- the refresh -----------------------------------------------------

    def _lda_config(self):
        if self._lda_cfg is None:
            import dataclasses

            cc = self.cc
            self._lda_cfg = dataclasses.replace(
                self.config.lda,
                batch_size=cc.batch_size,
                min_bucket_len=cc.min_bucket_len,
                fused_em_chunk=cc.fused_em_chunk,
            )
        return self._lda_cfg

    def refresh(self, now_sim: float) -> dict:
        from ..models.lda import WindowTrainer

        idx = self.refresh_count + self.skipped_refreshes + 1
        with self._lock:
            self.window.advance(now_sim)
            if self.collective is not None:
                # Distributed refresh: every rank grew its vocabulary
                # from the slices IT ingested, so agree on one pow2
                # capacity tier (the max) BEFORE the snapshot — all
                # ranks then compile and allreduce at the same [K, V].
                from ..parallel import sync_capacity_tier

                self._tier_syncs += 1
                agreed = sync_capacity_tier(
                    self.collective, self.window.vocab_size,
                    self.cc.vocab_floor,
                    tag=f"{self.tenant}.tier{self._tier_syncs}",
                    journal=self.journal,
                )
                self.window.reserve_capacity(agreed)
            snap = self.window.snapshot()
        corpus = snap.corpus
        if corpus.num_docs < self.cc.min_refresh_docs:
            self.skipped_refreshes += 1
            return {"refresh": idx, "skipped": "window_too_small",
                    "docs": corpus.num_docs}
        cfg = self._lda_config()
        if (self.trainer is None
                or self.trainer.num_terms != corpus.num_terms):
            # One program family per vocabulary capacity tier: churn
            # inside a tier retraces nothing; crossing a boundary
            # mints exactly one new trainer (and family).
            self.trainer = WindowTrainer(
                cfg, corpus.num_terms,
                collective=self.collective,
                yield_hook=(self.cosched.yield_hook
                            if self.cosched is not None else None),
            )
            self.tier_rebuilds += 1
        mode = self._train_mode()
        seed_probs = self._prev_probs if mode == "warm" else None
        seed_alpha = self._prev_alpha if mode == "warm" else None
        refresh_wall0 = time.perf_counter()
        t0 = time.perf_counter()
        # The fit bracket marks this service refresh-active: scoring
        # that lands inside it is a "during refresh" latency sample,
        # and the co-scheduler journals the fit's chunk/yield rollup
        # at exit.
        fit_ctx = (self.cosched.train_fit(self.tenant)
                   if self.cosched is not None else nullcontext())
        with fit_ctx:
            result = self.trainer.fit(
                corpus, topic_probs=seed_probs, alpha=seed_alpha,
            )
        train_wall = time.perf_counter() - t0
        ll, held_docs = self.drift.evaluate(
            result.log_beta, result.alpha, corpus,
            holdout_frac=self.cc.holdout_frac,
            batch_size=cfg.batch_size,
            min_bucket_len=cfg.min_bucket_len,
            var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
        )
        decision = self.drift.check(
            ll, held_docs=held_docs, docs=corpus.num_docs,
            window_t0=round(snap.t0, 3), window_t1=round(snap.t1, 3),
        )
        version = self._version()
        ok = self.drift.gate(
            decision, version=version, tenant=self.tenant,
            mode=mode, em_iters=result.em_iters,
        )
        publish_wall = None
        quality_info = {}
        if ok:
            model = self._build_model(snap, result)
            qgate = self._quality_gate()
            if qgate is not None:
                qdec = qgate.check(model)
                ok = qgate.gate(
                    qdec, version=version, tenant=self.tenant,
                )
                quality_info = {
                    "quality_recall": round(qdec.recall, 6),
                    "quality_regressed": qdec.regressed,
                }
            if ok:
                self._publish(model, snap)
                publish_wall = time.perf_counter()
                self._prev_probs = np.asarray(
                    model.p[:-1], np.float64
                )  # drop fallback row: the [V_real, K] warm-start seed
                self._prev_alpha = result.alpha
        if mode == "fresh":
            self._last_fresh_iters = result.em_iters
        iters_saved = (
            self._last_fresh_iters - result.em_iters
            if mode == "warm" and self._last_fresh_iters is not None
            else None
        )
        fresh = self._freshness_record(publish_wall, now_sim,
                                       refresh_wall0)
        record = {
            "refresh": idx,
            "mode": mode,
            "warm_start": mode == "warm",
            "em_iters": result.em_iters,
            "iters_saved": iters_saved,
            "train_wall_s": round(train_wall, 4),
            "held_out_ll": round(ll, 6),
            "held_docs": held_docs,
            "drifted": decision.drifted,
            "published": ok,
            "version": self._version(),
            "docs": corpus.num_docs,
            "vocab": snap.real_vocab,
            "vocab_capacity": snap.vocab_capacity,
            "window_chunks": snap.chunks,
            **quality_info,
            **fresh,
        }
        self.refresh_records.append(record)
        self.refresh_count += 1
        agg = self._fit_agg[mode == "warm"]
        agg["fits"] += 1
        agg["wall_s"] += train_wall
        agg["em_iters"] += result.em_iters
        self._last_ll = ll
        if (self.fresh_control and self.control_record is None
                and mode == "warm" and ok
                and idx > self.warmup_refreshes):
            self.control_record = self._run_fresh_control(
                corpus, record, seed_probs, seed_alpha
            )
        if (self._warmup_counts is None
                and idx >= self.warmup_refreshes):
            from ..plans import warmup as plans_warmup

            self._warmup_counts = plans_warmup.compile_counts()
        return record

    def _train_mode(self) -> str:
        cc = self.cc
        if cc.warm_start not in ("auto", "always", "never"):
            raise ValueError(
                f"ContinuousConfig.warm_start={cc.warm_start!r}: "
                "expected 'auto', 'always', or 'never'"
            )
        if self._prev_probs is None or cc.warm_start == "never":
            return "fresh"
        if cc.warm_start == "always":
            return "warm"
        return self.drift.mode        # fresh right after a veto

    def _build_model(self, snap, result):
        from ..scoring import ScoringModel

        fallback = get_source(self.dsource).fallback(self.config.scoring)
        corpus = snap.corpus
        # The published model covers the REAL vocabulary only: the
        # tier's pad words never occur in an event and must not ride
        # into word_index.
        return ScoringModel.from_lda(
            corpus.doc_names,
            result.gamma,
            corpus.vocab[: snap.real_vocab],
            result.log_beta[:, : snap.real_vocab],
            fallback,
        )

    def _version(self) -> int:
        if self.publisher is not None:
            return self.publisher.version(self.tenant)
        return (
            self.fleet.version(self.tenant)
            if self.tenant in self.fleet.tenants() else 0
        )

    def _publish(self, model, snap) -> None:
        source = f"window@{round(snap.t1, 1)}"
        if self.publisher is not None:
            # Composed mode: the refreshed model fans out through the
            # replicated router (primary AND shadow) instead of the
            # in-process registry.
            self.publisher.publish(self, model, source)
            return
        self.fleet.publish(self.tenant, model, source=source)
        if self.scorer is None:
            self._start_scorer()

    def _quality_gate(self):
        """The detection-quality publish gate, built lazily: the
        injection suite needs the stream's pinned cuts, which exist
        only after the bootstrap slice.  Off unless
        ContinuousConfig.quality_gate."""
        if not self.cc.quality_gate:
            return None
        if self._qgate is None:
            from ..models.drift import QualityGate
            from ..sources.quality import QualitySuite

            cc = self.cc
            suite = QualitySuite(
                self.dsource, self.cuts,
                n_events=cc.quality_events, seed=cc.quality_seed,
                attack_events=cc.quality_attack_events, k=cc.quality_k,
            )
            raw_journal = (
                self.journal.journal if self.journal is not None
                else None
            )
            if raw_journal is not None:
                # The suite's provenance record: what was injected,
                # under which seed — the ground truth every subsequent
                # quality_gate record is judged against.
                raw_journal.append(suite.manifest)
            self._qgate = QualityGate(
                suite,
                tol=cc.quality_tol,
                history=cc.quality_history,
                min_history=cc.quality_min_history,
                journal=raw_journal, recorder=self.recorder,
            )
            self._qgate.prime(self._replayed)
        return self._qgate

    def _start_scorer(self) -> None:
        from ..serving import FleetScorer

        fz = get_source(self.dsource).event_featurizer(self.cuts)
        # Flagged-event product sink: the scored output IS the
        # pipeline's purpose — suspicious connects stream to
        # flagged_events.jsonl as they score (serve mode's on_batch
        # contract), not just into the freshness ledger.
        self._flagged_file = open(
            os.path.join(self.out_dir, "flagged_events.jsonl"), "a"
        )

        def on_batch(tenant, snapshot, feats, scores):
            threshold = self.scorer.tenant_threshold(tenant)
            for i in np.where(scores < threshold)[0]:
                self.flagged += 1
                self._flagged_file.write(json.dumps({
                    "tenant": tenant,
                    "flagged": feats.featurized_row(int(i)),
                    "score": float(scores[i]),
                    "model_version": snapshot.version,
                }) + "\n")
            self._flagged_file.flush()

        self.scorer = FleetScorer(
            self.fleet, {self.tenant: fz}, self.config.serving,
            on_batch=on_batch, journal=self.journal,
        )

    def _freshness_record(self, publish_wall: "float | None",
                          now_sim: float,
                          refresh_wall0: float) -> dict:
        """Resolve the freshness ledger at a successful publish: every
        not-yet-covered slice's events became servable under a model
        trained on a window containing them.  Wall freshness is what
        THIS replay measured (speed-dependent); event-time freshness
        is the cadence lag plus the refresh's own wall — what a
        real-time deployment would deliver, invariant to the replay
        speed."""
        if publish_wall is None:
            return {"freshness_slices": 0}
        refresh_cost = publish_wall - refresh_wall0
        n = 0
        wall_max = 0.0
        event_max = 0.0
        with self._lock:
            covered, self._ledger = self._ledger, []
        # Covered entries can never be re-covered: they were swapped
        # out above, so a standing service's ledger holds only the
        # slices since the last successful publish (bounded, and each
        # publish's scan is O(new slices), not O(slices ever)).
        for entry in covered:
            wall = publish_wall - entry.arrival_wall
            event_s = max(now_sim - entry.t1, 0.0) + refresh_cost
            n += 1
            wall_max = max(wall_max, wall)
            event_max = max(event_max, event_s)
            self._freshness_count += 1
            self._freshness.observe(wall)
            self._freshness_event.observe(event_s)
            if self._freshness_sink is not None:
                self._freshness_sink(wall, event_s)
        if n and self.journal is not None:
            # The freshness-latency lane trace_view plots: per publish,
            # the worst newly-covered slice's arrival→servable gap.
            # Tenant-keyed: the fleet journal interleaves N ledgers.
            self.journal.append({
                "kind": "freshness",
                "tenant": self.tenant,
                "slices": n,
                "wall_max_s": round(wall_max, 3),
                "event_max_s": round(event_max, 3),
            })
        return {"freshness_slices": n}

    def _run_fresh_control(self, corpus, record, seed_probs,
                           seed_alpha):
        """The apples-to-apples warm-vs-fresh measurement: re-run the
        warm fit AND one fresh fit back-to-back on the exact snapshot
        a warm refresh just trained (neither is published) — same
        data, same shapes, both on already-traced programs, so the
        bench's warm_start_speedup compares pure EM walls at matched
        held-out likelihood, not a compile against a cache hit."""
        cfg = self._lda_config()

        def _eval(result):
            ll, _ = self.drift.evaluate(
                result.log_beta, result.alpha, corpus,
                holdout_frac=self.cc.holdout_frac,
                batch_size=cfg.batch_size,
                min_bucket_len=cfg.min_bucket_len,
                var_max_iters=cfg.var_max_iters, var_tol=cfg.var_tol,
            )
            return ll

        t0 = time.perf_counter()
        warm_res = self.trainer.fit(
            corpus, topic_probs=seed_probs, alpha=seed_alpha
        )
        warm_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        fresh_res = self.trainer.fit(corpus)
        fresh_wall = time.perf_counter() - t0
        warm_ll = _eval(warm_res)
        fresh_ll = _eval(fresh_res)
        self._last_fresh_iters = fresh_res.em_iters
        return {
            "at_refresh": record["refresh"],
            "warm_wall_s": round(warm_wall, 4),
            "fresh_wall_s": round(fresh_wall, 4),
            "warm_em_iters": warm_res.em_iters,
            "fresh_em_iters": fresh_res.em_iters,
            "warm_start_speedup": round(
                fresh_wall / max(warm_wall, 1e-9), 3
            ),
            "held_out_ll_warm": round(warm_ll, 6),
            "held_out_ll_fresh": round(fresh_ll, 6),
            "held_out_ll_delta": round(warm_ll - fresh_ll, 6),
        }

    # -- drive + close ---------------------------------------------------

    def run(self, slices) -> dict:
        """Consume a paced slice stream to exhaustion, then close."""
        try:
            for sl in slices:
                self.ingest_slice(sl)
                self.maybe_refresh(sl.t1)
        finally:
            payload = self.close()
        return payload

    def close(self) -> dict:
        if self.scorer is not None:
            self.scorer.close(timeout=60.0)
            self.scorer = None
        if self._flagged_file is not None:
            self._flagged_file.close()
            self._flagged_file = None
        payload = self.summary()
        with self._lock:
            journal, self.journal = self.journal, None
        if journal is not None and self._owns_journal:
            journal.run_end(
                ok=True, publishes=self.drift.publishes,
                vetoes=self.drift.vetoes,
            )
            journal.close()
        # shared journal: the fleet closes it
        metrics_name = (
            f"continuous_metrics.{self.tenant}.json" if self._shared
            else "continuous_metrics.json"
        )
        with open(os.path.join(self.out_dir, metrics_name), "w") as f:
            json.dump(payload, f, indent=1)
        return payload

    def summary(self) -> dict:
        def _fit_stats(warm: bool) -> dict:
            agg = self._fit_agg[warm]
            if not agg["fits"]:
                return {"fits": 0}
            return {
                "fits": agg["fits"],
                "mean_wall_s": round(agg["wall_s"] / agg["fits"], 4),
                "mean_em_iters": round(
                    agg["em_iters"] / agg["fits"], 2
                ),
            }

        fresh_q = {}
        if self._freshness_count:
            fresh_q = {
                "freshness_p50_s": round(
                    self._freshness.quantile(0.50), 3
                ),
                "freshness_p99_s": round(
                    self._freshness.quantile(0.99), 3
                ),
                "freshness_event_p50_min": round(
                    self._freshness_event.quantile(0.50) / 60.0, 3
                ),
                "freshness_event_p99_min": round(
                    self._freshness_event.quantile(0.99) / 60.0, 3
                ),
            }
        serve_q = {}
        if self._serve_idle_ms.count:
            serve_q["serve_idle_p99_ms"] = round(
                self._serve_idle_ms.quantile(0.99), 3
            )
        if self._serve_refresh_ms.count:
            serve_q["serve_refresh_p99_ms"] = round(
                self._serve_refresh_ms.quantile(0.99), 3
            )
        retraces = None
        if self._warmup_counts is not None:
            from ..plans import warmup as plans_warmup

            delta = plans_warmup.counts_delta(self._warmup_counts)
            retraces = delta.get("traces", 0)
        return {
            "dsource": self.dsource,
            "tenant": self.tenant,
            "slices": self.slices,
            "events": self.events,
            "events_rejected": self.events_rejected,
            "flagged": self.flagged,
            "refreshes": self.refresh_count,
            "skipped_refreshes": self.skipped_refreshes,
            "publishes": self.drift.publishes,
            "vetoes": self.drift.vetoes,
            "quality_checks": (
                self._qgate.checks if self._qgate is not None else 0
            ),
            "quality_vetoes": (
                self._qgate.vetoes if self._qgate is not None else 0
            ),
            "version": self._version(),
            **fresh_q,
            **serve_q,
            "freshness_samples": self._freshness_count,
            "uncovered_slices": len(self._ledger),
            "warm": _fit_stats(True),
            "fresh": _fit_stats(False),
            "fresh_control": self.control_record,
            "held_out_ll": self._last_ll,
            "vocab": self.window.vocab_size,
            "vocab_capacity": self.window.vocab_capacity(),
            "tier_rebuilds": self.tier_rebuilds,
            "evicted_chunks": self.window.evicted_chunks,
            "retraces_after_warmup": retraces,
            # Bounded recent detail (maxlen 1024); the journal holds
            # the full history.
            "refresh_records": list(self.refresh_records),
        }


def run_continuous(
    config: PipelineConfig,
    dsource: str,
    slices,
    *,
    out_dir: str,
    tenant: str = "stream",
    fresh_control: bool = False,
    warmup_refreshes: "int | None" = None,
) -> dict:
    """Convenience wrapper: stand up a ContinuousService, wire the
    persistent compilation cache (the zero-retrace counters count
    nothing without it), and drive the slice stream to exhaustion."""
    from ..plans import warmup as plans_warmup

    plans_warmup.setup_compilation_cache(
        enabled=config.plans.compilation_cache
    )
    plans_warmup._ensure_listener()
    service = ContinuousService(
        config, dsource, out_dir=out_dir, tenant=tenant,
        fresh_control=fresh_control, warmup_refreshes=warmup_refreshes,
    )
    return service.run(slices)


# ---------------------------------------------------------------------------
# the composed standing service: N tenants, one co-scheduler, one fleet
# ---------------------------------------------------------------------------


class RouterBinding:
    """Publishing and scoring for N per-tenant services through ONE
    replicated FleetRouter.

    Bootstrap: the router computes placement once at start() over the
    full tenant census, so the binding HOLDS each tenant's first
    published model until every expected tenant has one, then
    add_tenant()s the census and start()s the router.  Until then
    `ready()` is False and services only ledger their slices — exactly
    the classic mode's pre-first-publish behavior.  Later publishes
    fan out live through router.publish (primary AND shadow, with the
    drain/publish-race convergence loop).

    Scoring: submit_slice ships a slice as one submit_many frame and
    hands the futures to a FIFO resolver thread — ingest never blocks
    on a score round-trip; each event's submit→resolve latency lands
    in the idle or during-refresh histogram by the refresh_active flag
    sampled at submit.  `failed` counts futures that errored: the
    chaos contract is that a replica SIGKILL leaves it at ZERO (the
    router resubmits in-flight hops to the promoted shadow)."""

    def __init__(self, router, tenants, *, journal=None,
                 recorder=None) -> None:
        from collections import deque as _deque

        from ..telemetry.spans import Recorder as _Recorder

        self.router = router
        self.expected = set(tenants)
        self._journal = getattr(journal, "journal", journal)
        rec = recorder if recorder is not None else _Recorder()
        self._serve_idle_ms = rec.histogram("route.serve_idle_ms")
        self._serve_refresh_ms = rec.histogram("route.serve_refresh_ms")
        self._lock = threading.Lock()
        self._started = False
        self._pending: dict = {}    # tenant -> (service, model) pre-start
        self._versions: dict = {}
        self.resolved = 0
        self.failed = 0
        self._cond = threading.Condition()
        self._queue = _deque()      # (future, t_submit, refresh_active)
        self._stopped = False
        self._resolver = threading.Thread(
            target=self._resolve_loop, name="oni-cont-resolver",
            daemon=True)
        self._resolver.start()

    def ready(self, tenant: str) -> bool:
        with self._lock:
            return self._started

    def version(self, tenant: str) -> int:
        with self._lock:
            return self._versions.get(tenant, 0)

    def publish(self, service, model, source: str) -> int:
        from ..serving import TenantSpec

        tenant = service.tenant
        with self._lock:
            if not self._started:
                self._pending[tenant] = (service, model)
                self._versions[tenant] = (
                    self._versions.get(tenant, 0) + 1
                )
                if set(self._pending) == self.expected:
                    for t, (svc, m) in sorted(self._pending.items()):
                        self.router.add_tenant(
                            TenantSpec(tenant=t, dsource=svc.dsource),
                            svc.cuts, m,
                        )
                    self._pending.clear()
                    self.router.start()
                    self._started = True
                return self._versions[tenant]
        v = self.router.publish(tenant, model, source=source)
        with self._lock:
            self._versions[tenant] = v
            return v

    def submit_slice(self, tenant: str, sl: IngestSlice, *,
                     refresh_active: bool = False) -> None:
        futs = self.router.submit_many(tenant, list(sl.lines))
        self.router.flush()
        t0 = time.perf_counter()
        with self._cond:
            for f in futs:
                self._queue.append((f, t0, refresh_active))
            self._cond.notify_all()

    def _resolve_loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stopped:
                    self._cond.wait()
                if not self._queue:
                    return      # stopped AND drained: close() semantics
                fut, t0, during = self._queue.popleft()
            try:
                fut.result(timeout=120.0)
            except Exception:
                with self._cond:
                    self.failed += 1
                continue
            wall_ms = (time.perf_counter() - t0) * 1e3
            (self._serve_refresh_ms if during
             else self._serve_idle_ms).observe(wall_ms)
            with self._cond:
                self.resolved += 1

    def close(self, timeout_s: float = 300.0) -> None:
        """Stop accepting and drain every queued future first — a
        clean shutdown must resolve (not drop) in-flight scores."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._resolver.join(timeout=timeout_s)

    def stats(self) -> dict:
        with self._lock:
            out = {
                "started": self._started,
                "versions": dict(self._versions),
            }
        with self._cond:
            out["events_scored"] = self.resolved
            out["failed_futures"] = self.failed
            out["pending"] = len(self._queue)
        for key, h in (("serve_idle_p99_ms", self._serve_idle_ms),
                       ("serve_refresh_p99_ms", self._serve_refresh_ms)):
            if h.count:
                out[key] = round(h.quantile(0.99), 3)
        return out


class FleetContinuousService:
    """One standing service: N per-tenant ContinuousServices composed
    over ONE journal/recorder, ONE train/serve co-scheduler, an
    optional collective (distributed refreshes), and — when
    `replicated`/`router` — the replicated serving fleet.

    The perf core is the priority split: ingest + scoring stay on the
    caller's thread (high priority, serve slots), refresh fits run on
    ONE background worker (low priority, preemptible chunks), so a
    tenant's fit never blocks another tenant's — or its own — scoring
    beyond a chunk boundary.  Cadence that outruns the fit coalesces
    (the queued refresh trains on a window containing the newer slices
    anyway) instead of building an unbounded backlog.

    Drive with `run(tagged)` where tagged yields (tenant, IngestSlice)
    in event-time order (`interleave_streams` + `paced_tagged`), or
    slice-by-slice via `ingest`."""

    def __init__(
        self,
        config: PipelineConfig,
        streams: "dict[str, str]",
        *,
        out_dir: str,
        replicated: int = 0,
        router=None,
        coscheduler: bool = True,
        collective=None,
        warmup_refreshes: "int | None" = None,
        replica_extra: "list[str] | None" = None,
        replica_platform: "str | None" = None,
    ) -> None:
        from ..serving import CoScheduler
        from ..telemetry import Journal, Recorder, RunJournal
        from ..telemetry.spans import Recorder as _Recorder

        if not streams:
            raise ValueError("streams must name at least one tenant")
        self.config = config
        self.out_dir = formats.ensure_dir(out_dir)
        self.streams = dict(streams)
        # Created before _spawn_fleet so every cross-thread attribute
        # write below can take it.
        self._plock = threading.Lock()
        tel = config.telemetry
        self.journal = None
        self.recorder = None
        if tel.journal:
            jpath = os.path.join(self.out_dir, "run_journal.jsonl")
            self.journal = RunJournal(
                Journal(jpath, fsync_every=tel.journal_fsync_every)
            )
            self.journal.run_start(
                mode="continuous_fleet", tenants=sorted(self.streams),
                replicated=int(replicated or (router is not None)),
                cosched=bool(coscheduler),
                window_s=config.continuous.window_s,
                refresh_every_s=config.continuous.refresh_every_s,
            )
            self.recorder = Recorder(journal=self.journal.journal)
        raw_journal = (
            self.journal.journal if self.journal is not None else None
        )
        # coscheduler=False is OBSERVE-ONLY, not absent: the control
        # leg of the composed bench still needs the refresh-active tag
        # on serve latency and the chunk/slot counters — it just never
        # waits (no arbitration).
        self.cosched = CoScheduler(
            recorder=self.recorder, journal=raw_journal,
            enabled=bool(coscheduler),
        )
        rec = self.recorder or _Recorder()
        # Fleet-wide freshness aggregate next to the per-tenant
        # ledgers: the composed bench's headline quantiles.
        self._fresh_wall = rec.histogram("fleet.freshness_s")
        self._fresh_event = rec.histogram("fleet.freshness_event_s")

        self.router = router
        self._owns_router = False
        self.replica_procs: dict = {}
        self._workdir = None
        if self.router is None and replicated:
            if not replica_platform:
                # This process trains on the device it holds; replicas
                # it starts run where the caller says, never on a
                # platform picked behind the caller's back.
                raise ValueError(
                    "replicated=N spawns replica processes and needs "
                    "replica_platform (--replica-platform cpu|tpu)"
                )
            self._spawn_fleet(int(replicated), replica_extra or [],
                              replica_platform)
        self.binding = None
        if self.router is not None:
            self.binding = RouterBinding(
                self.router, self.streams,
                journal=self.journal, recorder=self.recorder,
            )

        self.services: "dict[str, ContinuousService]" = {}
        for tenant, dsource in sorted(self.streams.items()):
            self.services[tenant] = ContinuousService(
                config, dsource, out_dir=self.out_dir, tenant=tenant,
                warmup_refreshes=warmup_refreshes,
                journal=self.journal, recorder=self.recorder,
                coscheduler=self.cosched, collective=collective,
                publisher=self.binding,
                freshness_sink=self._observe_freshness,
            )

        self.coalesced_refreshes = 0
        self.refresh_errors = 0
        self._warm0 = None
        self._closed = False
        self._payload = None
        self._refresh_pending: "dict[str, bool]" = {}
        self._rq: "queue.Queue" = queue.Queue()
        self._worker = threading.Thread(
            target=self._refresh_loop, name="oni-continuous-refresh",
            daemon=True)
        self._worker.start()

    def _spawn_fleet(self, n: int, extra: list, platform: str) -> None:
        from ..parallel import FileKVClient
        from ..serving import FleetRouter
        from .route import _spawn_replica

        workdir = tempfile.mkdtemp(prefix="oni_cont_fleet_")
        with self._plock:
            self._workdir = workdir
        kv_dir = os.path.join(workdir, "kv")
        os.makedirs(kv_dir, exist_ok=True)
        router = FleetRouter(
            self.config.serving, journal=self.journal,
            recorder=self.recorder, kv=FileKVClient(kv_dir),
        )
        for i in range(n):
            rid = f"r{i}"
            proc, host, port = _spawn_replica(
                rid, kv_dir, workdir, list(extra), platform=platform)
            self.replica_procs[rid] = proc
            router.connect_replica(rid, host, port)
        with self._plock:
            self.router = router
            self._owns_router = True

    def kill_replica(self, rid: str) -> None:
        """Chaos hook: SIGKILL a spawned replica subprocess — no
        drain, no goodbye.  The recovery contract (zero failed score
        futures, publishes converging through the promoted shadow) is
        what the composed bench and the chaos test pin."""
        proc = self.replica_procs[rid]
        proc.kill()
        proc.wait(timeout=30.0)

    def _observe_freshness(self, wall_s: float, event_s: float) -> None:
        self._fresh_wall.observe(wall_s)
        self._fresh_event.observe(event_s)

    # -- drive ----------------------------------------------------------

    def ingest(self, tenant: str, sl: IngestSlice) -> None:
        svc = self.services[tenant]
        svc.ingest_slice(sl)
        if svc.refresh_due(sl.t1):
            with self._plock:
                if self._refresh_pending.get(tenant):
                    # Cadence outran the fit: coalesce — the queued
                    # refresh trains on a window that will contain
                    # this slice anyway.
                    self.coalesced_refreshes += 1
                    return
                self._refresh_pending[tenant] = True
            self._rq.put((tenant, sl.t1))

    def _refresh_loop(self) -> None:
        from ..plans import warmup as plans_warmup

        while True:
            item = self._rq.get()
            try:
                if item is None:
                    return
                tenant, now_sim = item
                try:
                    self.services[tenant].refresh(now_sim)
                except Exception as e:
                    # An abandoned refresh must not kill the standing
                    # fleet: nothing was published (the gate never
                    # ran), the ledger keeps its uncovered slices, and
                    # the next cadence boundary retries over a window
                    # that still contains them.
                    with self._plock:
                        self.refresh_errors += 1
                    if self.journal is not None:
                        try:
                            self.journal.append({
                                "kind": "refresh_abandon",
                                "tenant": tenant,
                                "error": repr(e)[:200],
                            })
                        except Exception:
                            pass
                if self._warm0 is None and all(
                    s._warmup_counts is not None
                    for s in self.services.values()
                ):
                    # Every tenant crossed ITS warmup boundary: traces
                    # from here on are the fleet's retrace count (the
                    # compile counters are process-global, so summing
                    # per-tenant deltas would double-count).
                    with self._plock:
                        self._warm0 = plans_warmup.compile_counts()
            finally:
                if item is not None:
                    with self._plock:
                        self._refresh_pending[item[0]] = False
                self._rq.task_done()

    def run(self, tagged) -> dict:
        """Consume an event-time-ordered (tenant, slice) stream to
        exhaustion, then close."""
        try:
            for tenant, sl in tagged:
                self.ingest(tenant, sl)
        finally:
            payload = self.close()
        return payload

    def close(self) -> dict:
        with self._plock:
            if self._closed:
                return self._payload
            self._closed = True
        self._rq.join()            # every queued refresh lands first
        self._rq.put(None)
        self._worker.join(timeout=600.0)
        if self.binding is not None:
            self.binding.close()   # resolve every in-flight future
        tenants = {
            t: svc.close() for t, svc in sorted(self.services.items())
        }
        payload = self.summary(tenants)
        if self._owns_router and self.router is not None:
            try:
                self.router.close()
            except Exception:
                pass
            for proc in self.replica_procs.values():
                if proc.poll() is None:
                    proc.terminate()
            for proc in self.replica_procs.values():
                try:
                    proc.wait(timeout=30.0)
                except Exception:
                    proc.kill()
        with self._plock:
            journal, self.journal = self.journal, None
        if journal is not None:
            journal.run_end(
                ok=True,
                refreshes=payload["refreshes"],
                publishes=payload["publishes"],
                refresh_errors=self.refresh_errors,
            )
            journal.close()
        with open(os.path.join(self.out_dir,
                               "fleet_continuous_metrics.json"),
                  "w") as f:
            json.dump(payload, f, indent=1)
        with self._plock:
            self._payload = payload
        return payload

    def summary(self, tenants: "dict | None" = None) -> dict:
        if tenants is None:
            tenants = {
                t: svc.summary()
                for t, svc in sorted(self.services.items())
            }
        fresh = {}
        if self._fresh_wall.count:
            fresh = {
                "freshness_p50_s": round(
                    self._fresh_wall.quantile(0.50), 3),
                "freshness_p99_s": round(
                    self._fresh_wall.quantile(0.99), 3),
                "freshness_event_p50_min": round(
                    self._fresh_event.quantile(0.50) / 60.0, 3),
                "freshness_event_p99_min": round(
                    self._fresh_event.quantile(0.99) / 60.0, 3),
            }
        retraces = None
        if self._warm0 is not None:
            from ..plans import warmup as plans_warmup

            retraces = plans_warmup.counts_delta(self._warm0).get(
                "traces", 0)
        out = {
            "tenants": tenants,
            "events": sum(t["events"] for t in tenants.values()),
            "slices": sum(t["slices"] for t in tenants.values()),
            "refreshes": sum(t["refreshes"] for t in tenants.values()),
            "publishes": sum(t["publishes"] for t in tenants.values()),
            "coalesced_refreshes": self.coalesced_refreshes,
            "refresh_errors": self.refresh_errors,
            "retraces_after_warmup": retraces,
            **fresh,
        }
        if self.cosched is not None:
            out["cosched"] = self.cosched.summary()
        if self.binding is not None:
            out["serving"] = self.binding.stats()
        if self.router is not None:
            try:
                out["router"] = self.router.stats()
            except Exception:
                pass
        return out


def interleave_streams(per_tenant: "dict[str, list]") -> list:
    """Merge per-tenant slice lists into ONE event-time-ordered
    (tenant, slice) replay — the multi-tenant day the fleet relives.
    Deterministic: ties break by tenant name."""
    tagged = [
        (t, sl) for t in sorted(per_tenant) for sl in per_tenant[t]
    ]
    tagged.sort(key=lambda p: (p[1].t1, p[0]))
    return tagged


def paced_tagged(tagged, speed: float, *, sleep=time.sleep):
    """`paced_slices` for a tagged (tenant, slice) stream: one shared
    event clock paces every tenant, preserving their relative gap
    structure at ×`speed` real time."""
    if speed <= 0:
        raise ValueError(f"speed must be > 0, got {speed}")
    t_wall0 = time.perf_counter()
    t_sim0 = None
    for tenant, sl in tagged:
        if t_sim0 is None:
            t_sim0 = sl.t1
        due = t_wall0 + (sl.t1 - t_sim0) / speed
        delay = due - time.perf_counter()
        if delay > 0 and np.isfinite(delay):
            sleep(delay)
        sl.arrival_wall = time.perf_counter()
        yield tenant, sl


def run_fleet_continuous(
    config: PipelineConfig,
    streams: "dict[str, str]",
    tagged,
    *,
    out_dir: str,
    replicated: int = 0,
    router=None,
    coscheduler: bool = True,
    collective=None,
    warmup_refreshes: "int | None" = None,
    replica_platform: "str | None" = None,
) -> dict:
    """Convenience wrapper for the composed mode: compilation cache +
    compile counters, then drive the tagged stream to exhaustion."""
    from ..plans import warmup as plans_warmup

    plans_warmup.setup_compilation_cache(
        enabled=config.plans.compilation_cache
    )
    plans_warmup._ensure_listener()
    fleet = FleetContinuousService(
        config, streams, out_dir=out_dir, replicated=replicated,
        router=router, coscheduler=coscheduler, collective=collective,
        warmup_refreshes=warmup_refreshes,
        replica_platform=replica_platform,
    )
    return fleet.run(tagged)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ml_ops continuous",
        description="continuous ingestion: windowed streaming corpus, "
        "warm-start EM refreshes, drift-gated fleet publishes — "
        "freshness in minutes, not next-day (tools/day_replay.py "
        "paces a historical day into this mode)",
    )
    p.add_argument("dsource", nargs="?", default=None,
                   choices=list(source_names()),
                   help="single-tenant stream source (omit when using "
                   "--stream fleet mode)")
    p.add_argument("--stream", action="append", default=[],
                   metavar="TENANT=DSOURCE:PATH",
                   help="fleet mode: one tenant stream (repeatable) — "
                   "N tenants compose into ONE standing service "
                   "sharing the journal, the train/serve co-scheduler "
                   "and (with --replicated) the serving fleet")
    p.add_argument("--replicated", type=int, default=0, metavar="N",
                   help="serve through the fleet router over N "
                   "spawned replica subprocesses (ml_ops replica) "
                   "instead of the in-process scorer")
    p.add_argument("--replica-platform", default=None, metavar="PLATFORM",
                   help="jax platform the --replicated replicas run on "
                   "(cpu, tpu) — required with --replicated: this "
                   "process holds the device it trains on, and a "
                   "replica's platform is never defaulted")
    p.add_argument("--multihost", action="store_true",
                   help="distributed window refreshes over the "
                   "ambient collective (parallel/allreduce env "
                   "bootstrap; rank-synchronized vocab tiers, "
                   "suff-stats allreduce, warm-start broadcast)")
    p.add_argument("--no-cosched", action="store_true",
                   help="disable the train/serve co-scheduler "
                   "(control mode: refresh fits run unpreemptible)")
    p.add_argument("--flow-path", default=None,
                   help="raw netflow CSV to replay (FLOW_PATH env)")
    p.add_argument("--dns-path", default=None,
                   help="raw DNS CSV to replay (DNS_PATH env)")
    p.add_argument("--proxy-path", default=None,
                   help="raw proxy/HTTP log CSV to replay (PROXY_PATH "
                   "env)")
    p.add_argument("--quality-gate", action="store_true",
                   help="veto publishes that regress recall@k on the "
                   "labeled-injection suite (sources/inject.py)")
    p.add_argument("--data-dir", default=None,
                   help="output/journal directory (LPATH env)")
    p.add_argument("--qtiles", default=None,
                   help="pinned flow quantile cuts (stable word "
                   "identity across restarts)")
    p.add_argument("--speed", type=float, default=60.0,
                   help="replay speed multiplier over event time "
                   "(60 = an hour of events per wall minute)")
    p.add_argument("--slice-s", type=float, default=300.0,
                   help="ingest slice span in EVENT seconds")
    p.add_argument("--window-s", type=float, default=None,
                   help="override ContinuousConfig.window_s")
    p.add_argument("--refresh-s", type=float, default=None,
                   help="override ContinuousConfig.refresh_every_s")
    p.add_argument("--tenant", default="stream")
    p.add_argument("--fresh-control", action="store_true",
                   help="measure one fresh fit against a warm refresh's "
                   "snapshot (the warm_start_speedup number)")
    p.add_argument("--no-sleep", action="store_true",
                   help="deliver slices as fast as consumed (tests/CI)")
    return p


def _parse_stream_specs(specs: "list[str]") -> "dict[str, tuple]":
    """Parse repeated --stream TENANT=DSOURCE:PATH flags."""
    out: dict = {}
    for spec in specs:
        tenant, eq, rest = spec.partition("=")
        dsource, colon, path = rest.partition(":")
        if not eq or not colon or not tenant or not path:
            raise ValueError(
                f"--stream expects TENANT=DSOURCE:PATH, got {spec!r}"
            )
        if dsource not in source_names():
            raise ValueError(
                f"--stream {spec!r}: dsource must be one of "
                f"{'|'.join(source_names())}"
            )
        if tenant in out:
            raise ValueError(f"--stream: duplicate tenant {tenant!r}")
        out[tenant] = (dsource, path)
    return out


def _main_fleet(args, config: PipelineConfig) -> int:
    streams = _parse_stream_specs(args.stream)
    per_tenant = {}
    for tenant, (dsource, path) in streams.items():
        if not os.path.exists(path):
            print(f"continuous: no input file at {path!r}", flush=True)
            return 2
        with open(path) as f:
            lines = f.readlines()
        per_tenant[tenant] = slice_events(lines, dsource, args.slice_s)
    collective = None
    if args.multihost:
        from ..parallel import get_collective

        collective = get_collective()
    speed = float("inf") if args.no_sleep else args.speed
    tagged = paced_tagged(interleave_streams(per_tenant), speed)
    payload = run_fleet_continuous(
        config, {t: ds for t, (ds, _) in streams.items()}, tagged,
        out_dir=os.path.join(config.data_dir, "continuous_fleet"),
        replicated=args.replicated, collective=collective,
        coscheduler=not args.no_cosched,
        replica_platform=args.replica_platform,
    )
    print(json.dumps(payload), flush=True)
    return 0


def main(argv: "list[str] | None" = None) -> int:
    import dataclasses

    args = build_parser().parse_args(argv)
    env = os.environ
    config = PipelineConfig(
        data_dir=args.data_dir or env.get("LPATH", "."),
        qtiles_path=args.qtiles or "",
    )
    cc = config.continuous
    overrides = {}
    if args.window_s is not None:
        overrides["window_s"] = args.window_s
    if args.refresh_s is not None:
        overrides["refresh_every_s"] = args.refresh_s
    if args.quality_gate:
        overrides["quality_gate"] = True
    if overrides:
        config = config.replace(
            continuous=dataclasses.replace(cc, **overrides)
        )
    if args.stream:
        return _main_fleet(args, config)
    if args.dsource is None:
        print("continuous: a DSOURCE argument or --stream flags are "
              "required", flush=True)
        return 2
    path = (
        getattr(args, f"{args.dsource}_path", None)
        or env.get(f"{args.dsource.upper()}_PATH", "")
    )
    if not path or not os.path.exists(path):
        print(f"continuous: no input file at {path!r}", flush=True)
        return 2
    with open(path) as f:
        lines = f.readlines()
    slices = slice_events(lines, args.dsource, args.slice_s)
    speed = float("inf") if args.no_sleep else args.speed
    payload = run_continuous(
        config, args.dsource, paced_slices(slices, speed),
        out_dir=os.path.join(config.data_dir, "continuous"),
        tenant=args.tenant, fresh_control=args.fresh_control,
    )
    print(json.dumps(payload), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
