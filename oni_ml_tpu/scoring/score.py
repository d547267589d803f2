"""Suspicious-connects scoring (flow_post_lda.scala:227-248,
dns_post_lda.scala:312-331).

p(event) = Σ_k p(topic k | event's IP) · p(event's word | topic k); events
scoring below a threshold are emitted ascending (most suspicious first).

Design: the reference broadcasts two driver-side hash maps to every
Spark executor and loops per event.  Here the model is two dense
matrices — theta [D+1, K] and p [V+1, K], each with its fallback
vector as the extra final row — and scoring one batch of events is two
row gathers + a row-wise dot, vectorized HOST-side numpy in float64
(the reference's double precision; see _batched_scores for why this
stage is deliberately not a device op — at K=20 it is memory-bound
bookkeeping on host-resident data, not MXU work).  Unseen IPs/words
index the fallback row, preserving the reference's quirky asymmetric
fallbacks (0.05/topic flow, 0.1/topic dns; a fully-unseen flow event
scores 20·0.05·0.05 = 0.05, i.e. NOT maximally suspicious —
SURVEY §2.6).

Scoring reuses the featurization computed by the pre stage (FlowFeatures /
DnsFeatures) instead of re-running it the way the post scripts do.

Engines: the host float64 path above is the default and the golden-
bytes oracle; scoring/pipeline.py is the DEVICE engine — a fused
gather·dot·threshold kernel with chunked double-buffered dispatch,
survivors-only readback, and a data-parallel sharded path for
multi-device grants (opt in per call via engine="device", per run via
ScoringConfig.engine, or process-wide via ONI_ML_TPU_SCORE=device).
The host-vs-device decision for the serving path is priced from a
measured per-dispatch overhead calibration (dispatch_calibration), not
a raw size threshold.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass

import numpy as np

from ..features.flow import FLOW_COLUMNS, FlowFeatures
from ..features.dns import DNS_COLUMNS, DnsFeatures
from ..io import formats


@dataclass
class ScoringModel:
    """theta/p matrices plus key->row maps, fallback row appended last."""

    ip_index: dict[str, int]
    theta: np.ndarray            # [D+1, K], row D = fallback
    word_index: dict[str, int]
    p: np.ndarray                # [V+1, K], row V = fallback

    @property
    def num_topics(self) -> int:
        return self.theta.shape[1]

    @classmethod
    def from_results(
        cls,
        doc_names: list[str],
        doc_topic: np.ndarray,
        vocab: list[str],
        word_topic: np.ndarray,
        fallback: float,
    ) -> "ScoringModel":
        k = doc_topic.shape[1] if doc_topic.size else word_topic.shape[1]
        theta = np.concatenate(
            [np.asarray(doc_topic, np.float64), np.full((1, k), fallback)]
        )
        p = np.concatenate(
            [np.asarray(word_topic, np.float64), np.full((1, k), fallback)]
        )
        return cls(
            ip_index={ip: i for i, ip in enumerate(doc_names)},
            theta=theta,
            word_index={w: i for i, w in enumerate(vocab)},
            p=p,
        )

    @classmethod
    def from_files(
        cls, doc_results_path: str, word_results_path: str, fallback: float
    ) -> "ScoringModel":
        """Load the lda_post-format CSVs the reference's scorers broadcast
        (flow_post_lda.scala:101-123)."""
        doc_names, doc_topic = formats.read_doc_results(doc_results_path)
        vocab, word_topic = formats.read_word_results(word_results_path)
        return cls.from_results(doc_names, doc_topic, vocab, word_topic, fallback)

    @classmethod
    def from_lda(
        cls, doc_names: list[str], gamma: np.ndarray, vocab: list[str],
        log_beta: np.ndarray, fallback: float,
    ) -> "ScoringModel":
        """In-memory model from a trained LDA result, equal *to the
        double* to writing doc_results.csv / word_results.csv and
        loading them back with `from_files` — the EM→score hand-off the
        streaming dataplane uses so scoring never waits on (or reads
        back) the demoted result-file checkpoints.

        Round-trip exactness: the writers format with `str(float64)`
        (shortest repr, which parses back to the identical double), so
        replicating their normalization arithmetic — per-row here,
        exactly as write_doc_results folds each row — yields the
        file-path matrices bit-for-bit, and therefore byte-identical
        scored CSVs (pinned by tests/test_dataplane.py)."""
        gamma = np.asarray(gamma, dtype=np.float64)
        doc_topic = np.zeros_like(gamma)
        totals = gamma.sum(axis=1)
        nz = totals > 0
        # Elementwise row / row-sum, vectorized: identical doubles to
        # the per-row fold write_doc_results performs (same pairwise
        # row reduction, same single division per element).
        doc_topic[nz] = gamma[nz] / totals[nz][:, None]
        log_beta = np.asarray(log_beta, dtype=np.float64)
        # Verbatim write_word_results arithmetic (exp+normalize with
        # the row-max shift), transposed to V x K.
        shifted = np.exp(log_beta - log_beta.max(axis=1, keepdims=True))
        word_topic = (shifted / shifted.sum(axis=1, keepdims=True)).T
        return cls.from_results(doc_names, doc_topic, vocab, word_topic,
                                fallback)

    def ip_rows(self, ips: list[str]) -> np.ndarray:
        return _index_rows(self.ip_index, ips, len(self.ip_index))

    def word_rows(self, words: list[str]) -> np.ndarray:
        return _index_rows(self.word_index, words, len(self.word_index))


def _index_rows(index: dict[str, int], queries: list[str],
                fallback_row: int) -> np.ndarray:
    """Row per query via one dict.get pass into a preallocated int32
    array; misses get the fallback row.

    This replaced a sorted-U-array searchsorted LUT (round-4 DNS p50
    reconciliation): on a high-cardinality DNS day the queries are the
    featurizer's interned table — O(unique) ≈ O(events), ~400k keys —
    and the LUT path spent ~0.7 s/day converting them into a fixed-
    width numpy U array (4·48 B per element) before the search, 3.7×
    the cost of just probing the dict (measured 0.33 s vs 0.09 s on a
    395k-key table).  A generator into np.fromiter has no per-key
    Python-function cost, and dict semantics need no oddball side path
    for NULs or over-long hostile strings."""
    get = index.get
    return np.fromiter(
        (get(s, fallback_row) for s in queries), np.int32, len(queries)
    )


def _batched_scores(model: ScoringModel, ip_idx, word_idx, batch: int = 1 << 20):
    """score[i] = <theta[ip_idx[i]], p[word_idx[i]]> — two K-wide row
    gathers and a dot, on the HOST in fixed-size numpy chunks.

    This is deliberately not a device op: at K=20 it is ~40 flops per
    event against two gathered rows — pure memory-bound host work on
    data that already lives host-side (the featurized day), while a
    device round trip ships the index arrays out and the scores back
    for no arithmetic advantage (the transfer outweighs the compute;
    not measured on the current machine, ROADMAP A4).  float64
    accumulation matches the reference's double-precision scoring
    (the earlier device path computed f32 — a deliberate re-pin of
    the golden scoring bytes); chunking bounds the gathered
    temporaries.  Reference anchor: the per-event Map lookup + dot of
    flow_post_lda.scala:227-239."""
    n = len(ip_idx)
    theta = np.asarray(model.theta, np.float64)
    p = np.asarray(model.p, np.float64)
    from .. import native_emit

    got = native_emit.score_dot(theta, p, ip_idx, word_idx)
    if got is not None:
        # Fused C gather-dot: no [N, K] gather temporaries (numpy
        # materializes ~1.6 GB of them on a 5M-event day — the gathers,
        # not the einsum, were 90% of the stage).  Bit-identical
        # accumulation order; parity pinned by the golden emit tests
        # and test_score_dot_native_matches_numpy.
        return got
    # Same range check the native path applies (native_emit.score_dot):
    # numpy would silently WRAP negative ids — usually into the
    # fallback row, masking a caller bug — so every engine raises.
    _check_index_range(model, ip_idx, word_idx)
    out = np.empty(n, dtype=np.float64)
    k = theta.shape[1]
    for lo in range(0, n, batch):
        hi = min(lo + batch, n)
        a = theta[np.asarray(ip_idx[lo:hi], np.int32)]
        b = p[np.asarray(word_idx[lo:hi], np.int32)]
        # Sequential k-order accumulation — bit-identical to the C
        # fast path above AND to the reference's per-event fold
        # (flow_post_lda.scala:231: zip/map/sum over the k pairs).
        # np.einsum uses SIMD partial sums whose add order differs in
        # the last ulp, which moves str(score) bytes in the scored CSV.
        acc = a[:, 0] * b[:, 0]
        for j in range(1, k):
            acc = acc + a[:, j] * b[:, j]
        out[lo:hi] = acc
    return out


def _check_index_range(model: ScoringModel, ip_idx, word_idx) -> None:
    """The shared out-of-range guard (see _batched_scores): numpy wraps
    negative ids and jnp.take CLIPS out-of-range ones — either way a
    caller bug would silently score against the wrong (usually fallback)
    row, so every engine raises instead."""
    ip_arr = np.asarray(ip_idx)
    w_arr = np.asarray(word_idx)
    if len(ip_arr) and (
        int(ip_arr.min()) < 0 or int(ip_arr.max()) >= model.theta.shape[0]
        or int(w_arr.min()) < 0 or int(w_arr.max()) >= model.p.shape[0]
    ):
        raise IndexError("model-row index out of range")


# One compiled program per padded batch size (power-of-two, see
# device_scores); keyed per call on nothing else — theta/p ride as
# traced operands so a hot-swapped model reuses the same executable.
_DEVICE_SCORE_FN = None


def _device_score_fn():
    global _DEVICE_SCORE_FN
    if _DEVICE_SCORE_FN is None:
        import jax

        from .pipeline import score_dot_rows

        _DEVICE_SCORE_FN = jax.jit(score_dot_rows)
    return _DEVICE_SCORE_FN


def _device_model(model: ScoringModel, stats=None):
    """Device copies of theta/p, cached on the model instance so a
    long-running scorer transfers each published model once, not once
    per micro-batch or per chunk.  f32 on the wire: HALF the H2D bytes
    of the float64 host matrices, and at K=20 the f32 gather+accumulate
    agrees with the float64 host oracle to ~1e-6 relative
    (tests/test_scoring_pipeline.py::test_f32_transfer_tolerance pins
    the bound) — the golden CSV contract never routes through here.

    A model carrying a `_device_dtype = "bfloat16"` marker (the
    serving fleet's stacked snapshots under
    ServingConfig.stack_precision="bf16") stores half-width again —
    double the HBM-hot tenant residency per byte.  The gather-dot
    kernel (pipeline.score_dot_rows) casts gathered rows up to f32
    before accumulating, so only the STORAGE is bf16; scores drift
    ~2^-8 relative vs the f32 stack (tests/test_residency.py pins the
    documented tolerance).  `stats` (pipeline.DispatchStats) records
    the one-time transfer."""
    cached = getattr(model, "_device_cache", None)
    if cached is None:
        import jax.numpy as jnp

        dtype = jnp.dtype(getattr(model, "_device_dtype", None)
                          or jnp.float32)
        cached = (
            jnp.asarray(model.theta, dtype),
            jnp.asarray(model.p, dtype),
        )
        model._device_cache = cached
        if stats is not None:
            stats.weight_h2d_bytes += dtype.itemsize * (
                model.theta.size + model.p.size
            )
    return cached


def device_scores(
    model: ScoringModel, ip_idx, word_idx, *, chunk: int | None = None,
    mesh=None, stats=None,
) -> np.ndarray:
    """score[i] = <theta[ip_idx[i]], p[word_idx[i]]> on device — the
    large-batch serving scorer.  Micro-batch-sized inputs (<= one
    pipeline chunk) pad to the next power of two and run as one jit
    call, so a stream of ragged micro-batch sizes compiles
    O(log max_batch) programs; anything larger runs through the
    chunked, double-buffered pipeline (scoring/pipeline.py) so a
    replay/day-scale batch never becomes one monolithic dispatch.
    `mesh` routes chunks through the data-parallel sharded scorer for
    multi-device grants.  Results come back float64 for drop-in use
    where _batched_scores is used.

    Accuracy: f32 gather + f32 accumulate over K terms — agrees with the
    host float64 path to ~1e-6 relative at K=20 (pinned in tests), far
    inside the orders-of-magnitude spread suspicion thresholds cut at.
    Anything needing the reference's exact double-precision bytes (the
    batch score stage) stays on _batched_scores."""
    from . import pipeline

    _check_index_range(model, ip_idx, word_idx)
    n = len(ip_idx)
    if n == 0:
        return np.zeros(0, np.float64)
    limit = pipeline.DEFAULT_CHUNK if chunk is None else chunk
    if n > limit or mesh is not None:
        return pipeline.chunked_scores(
            model, ip_idx, word_idx, chunk=limit, mesh=mesh, stats=stats
        )
    theta, p = _device_model(model, stats=stats)
    m = 1 << (n - 1).bit_length()
    ip_pad = np.zeros(m, np.int32)
    w_pad = np.zeros(m, np.int32)
    ip_pad[:n] = np.asarray(ip_idx, np.int32)
    w_pad[:n] = np.asarray(word_idx, np.int32)
    if stats is not None:
        stats.dispatches += 1
        stats.chunks += 1
        stats.chunk = m
        stats.events += n
        stats.h2d_bytes += ip_pad.nbytes + w_pad.nbytes
        stats.d2h_bytes += 4 * n
    out = _device_score_fn()(theta, p, ip_pad, w_pad)
    return np.asarray(out[:n], np.float64)


# Sentinel for batched_scores/ServingConfig: pick the engine from the
# measured dispatch calibration instead of a raw size threshold.
AUTO_DEVICE_MIN = 0

_CALIBRATION: dict | None = None


def dispatch_calibration(force: bool = False) -> dict:
    """Measured break-even batch size for the host-vs-device dispatch
    decision: a raw size threshold can route batches onto a path whose
    per-dispatch cost exceeds the host's whole stage, so the decision
    is priced from this process's own measurements on the machine it
    runs on (first measured on the v5e in PR 21: PERF.md).

    Returns {"dispatch_s", "host_event_s", "device_event_s",
    "break_even", "source"}; break_even None means the device's marginal
    per-event cost is not below the host's on this backend, so the
    device path can NEVER win and auto dispatch pins the host path.
    The record rides in bench.py's scoring_e2e payload so every round
    documents the constant it ran under.  ONI_ML_TPU_SCORE_BREAK_EVEN
    overrides with a pinned constant (<= 0 means "never device").

    Persistence (oni_ml_tpu/plans): a fresh measurement records itself
    to the plan cache keyed by the device-backend fingerprint, and the
    next PROCESS on this backend loads it (source "plan") instead of
    re-measuring — the calibration is the one autotune sweep the
    pipeline runs inline, so a second run performs zero sweeps.
    `force=True` re-measures and overwrites the cached entry.

    Cost: a few tiny synthetic scoring calls, run once per backend on
    the first auto dispatch anywhere, then cached on disk."""
    global _CALIBRATION
    if _CALIBRATION is not None and not force:
        return _CALIBRATION
    env = os.environ.get("ONI_ML_TPU_SCORE_BREAK_EVEN")
    if env is not None:
        be = int(env)
        _CALIBRATION = {
            "dispatch_s": None, "host_event_s": None,
            "device_event_s": None,
            "break_even": be if be > 0 else None, "source": "env",
        }
        return _CALIBRATION
    if not force:
        from ..plans import lookup_value

        planned = lookup_value("dispatch_calibration")
        if isinstance(planned, dict) and "break_even" in planned:
            be = planned.get("break_even")
            _CALIBRATION = {
                "dispatch_s": planned.get("dispatch_s"),
                "host_event_s": planned.get("host_event_s"),
                "device_event_s": planned.get("device_event_s"),
                "break_even": int(be) if be is not None else None,
                "source": "plan",
            }
            return _CALIBRATION
    rng = np.random.default_rng(0)
    k, d, v, n = 20, 1024, 1024, 4096
    model = ScoringModel(
        ip_index={}, theta=rng.random((d + 1, k)),
        word_index={}, p=rng.random((v + 1, k)),
    )
    ia = rng.integers(0, d, n).astype(np.int32)
    ib = rng.integers(0, v, n).astype(np.int32)

    def best_of(fn, reps=3):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    host_event_s = best_of(lambda: _batched_scores(model, ia, ib)) / n
    # Warm both compiled shapes before timing (compile is once-ever).
    device_scores(model, ia[:1], ib[:1])
    device_scores(model, ia, ib)
    dispatch_s = best_of(lambda: device_scores(model, ia[:1], ib[:1]))
    t_n = best_of(lambda: device_scores(model, ia, ib))
    device_event_s = max(0.0, (t_n - dispatch_s) / (n - 1))
    if device_event_s >= host_event_s:
        break_even = None            # device can never win here
    else:
        break_even = int(
            np.ceil(dispatch_s / (host_event_s - device_event_s))
        )
    _CALIBRATION = {
        "dispatch_s": dispatch_s, "host_event_s": host_event_s,
        "device_event_s": device_event_s, "break_even": break_even,
        "source": "measured",
    }
    from ..plans import note_sweep, record_value

    note_sweep("dispatch_calibration")
    record_value(
        "dispatch_calibration",
        {k2: v for k2, v in _CALIBRATION.items() if k2 != "source"},
        source="autotune",
    )
    return _CALIBRATION


def use_device_path(n: int, device_min) -> bool:
    """The one host-vs-device dispatch decision, shared by
    batched_scores and the serving metrics label so they cannot drift:
    None pins host (the batch pipeline's float64 oracle),
    AUTO_DEVICE_MIN (0) / "auto" consults dispatch_calibration(), and a
    positive int keeps the legacy hard threshold (tests and operators
    pinning a path)."""
    if device_min is None or n == 0:
        return False
    if device_min == "auto" or device_min == AUTO_DEVICE_MIN:
        break_even = dispatch_calibration()["break_even"]
        return break_even is not None and n >= break_even
    return n >= device_min


def batched_scores(
    model: ScoringModel, ip_idx, word_idx, device_min: int | None = None
) -> np.ndarray:
    """Size-dispatched scorer for the serving path: device_min=None
    pins the host float64 path (the batch pipeline's behavior), 0 or
    "auto" picks device-vs-host from the measured per-dispatch overhead
    (dispatch_calibration — the device path can no longer silently lose
    to host as it did in r05), and a positive int is a legacy hard
    threshold."""
    if use_device_path(len(ip_idx), device_min):
        return device_scores(model, ip_idx, word_idx)
    return _batched_scores(model, ip_idx, word_idx)


def _keep_order(scores: np.ndarray, threshold: float) -> np.ndarray:
    """Event indices under threshold, ascending by score (the
    reference's `filter < TOL` + `sortByKey()`).  The device pipeline's
    on-chip compaction (scoring/pipeline.py) is pinned to this exact
    ordering — including stable threshold-boundary ties — by
    tests/test_scoring_pipeline.py."""
    keep = np.where(scores < threshold)[0]
    return keep[np.argsort(scores[keep], kind="stable")]


def _score_engine(engine: str | None) -> str:
    """Batch-path engine selection: "host" (default) is the float64
    oracle whose scored-CSV bytes are golden-pinned; "device" runs the
    fused gather·dot·threshold pipeline with f32 on-chip arithmetic
    (~1e-6 relative score drift in the emitted columns — opt in via
    ScoringConfig.engine or ONI_ML_TPU_SCORE=device)."""
    if not engine:
        engine = os.environ.get("ONI_ML_TPU_SCORE", "host")
    if engine not in ("host", "device"):
        raise ValueError(
            f"scoring engine must be 'host' or 'device', got {engine!r}"
        )
    return engine


def _flow_endpoint_strings(features, n: int):
    """(sips, dips) without the O(N) per-event METHOD dispatch: the
    Python-backed containers store raw rows, so one column-slicing
    comprehension replaces 2N bound-method calls (the native containers
    never reach here — they carry interned id arrays).  Instance-dict
    lookup, NOT getattr: the native containers expose `rows` as a
    materializing @property, which this fast path must never trip."""
    rows = features.__dict__.get("rows")
    if rows is not None:
        s_col, d_col = FLOW_COLUMNS["sip"], FLOW_COLUMNS["dip"]
        return ([r[s_col] for r in rows[:n]], [r[d_col] for r in rows[:n]])
    return (
        [features.sip(i) for i in range(n)],
        [features.dip(i) for i in range(n)],
    )


def _dns_client_strings(features, n: int):
    """Client IPs without per-event method dispatch (see
    _flow_endpoint_strings; instance-dict lookup for the same
    property-trip reason)."""
    rows = features.__dict__.get("rows")
    if rows is not None:
        ip_col = DNS_COLUMNS["ip_dst"]
        return [r[ip_col] for r in rows[:n]]
    return [features.client_ip(i) for i in range(n)]


def flow_event_indices(features, ip_index: dict, word_index: dict):
    """Model-row index arrays (sip, sw, dip, dw) for every raw flow
    event, resolved against the given `{ip: row}` / `{word: row}`
    orderings (the doc_results / word_results row orders); misses get
    the fallback row `len(index)`.  Shared by the inline scoring path
    and the dataplane's scoring prep (which runs it concurrently with
    EM — it depends only on the corpus orderings, never the trained
    model)."""
    n = features.num_raw_events
    fb_ip, fb_w = len(ip_index), len(word_index)
    if hasattr(features, "sip_id"):
        # Native-backed features carry interned id arrays: resolve model
        # rows once per unique IP/word, then gather — O(unique) dict
        # lookups instead of O(events).
        ip_map = _index_rows(ip_index, features.ip_table, fb_ip)
        word_map = _index_rows(word_index, features.word_table, fb_w)
        return (
            ip_map[features.sip_id[:n]], word_map[features.sw_id[:n]],
            ip_map[features.dip_id[:n]], word_map[features.dw_id[:n]],
        )
    sips, dips = _flow_endpoint_strings(features, n)
    return (
        _index_rows(ip_index, sips, fb_ip),
        _index_rows(word_index, features.src_word[:n], fb_w),
        _index_rows(ip_index, dips, fb_ip),
        _index_rows(word_index, features.dest_word[:n], fb_w),
    )


def dns_event_indices(features, ip_index: dict, word_index: dict):
    """Model-row index arrays (ip, word) for every raw DNS event (see
    flow_event_indices)."""
    n = features.num_raw_events
    fb_ip, fb_w = len(ip_index), len(word_index)
    if hasattr(features, "word_id"):
        ip_map = _index_rows(ip_index, features.ip_table, fb_ip)
        word_map = _index_rows(word_index, features.word_table, fb_w)
        return ip_map[features.ip_id[:n]], word_map[features.word_id[:n]]
    return (
        _index_rows(ip_index, _dns_client_strings(features, n), fb_ip),
        _index_rows(word_index, features.word[:n], fb_w),
    )


def _prep_indices(prep, features, model: ScoringModel, dsource: str,
                  index_fn):
    """Event index arrays from a dataplane ScoringPrep when one is
    supplied (verified against this model's index spaces — a mismatch
    is a bug and fails loudly), else resolved inline."""
    if prep is not None:
        if prep.dsource != dsource:
            raise ValueError(
                f"scoring prep is for dsource {prep.dsource!r}, "
                f"scoring {dsource!r}"
            )
        if prep.num_raw_events != features.num_raw_events:
            raise ValueError(
                f"scoring prep covers {prep.num_raw_events} raw events, "
                f"features carry {features.num_raw_events}"
            )
        prep.check_model(model)
        return prep.indices
    return index_fn(features, model.ip_index, model.word_index)


def _flow_scored(features, model: ScoringModel, threshold: float,
                 engine: str | None = None, chunk: int | None = None,
                 mesh=None, stats=None, prep=None):
    """Shared flow scoring core -> (blob | None, rows | None, scores):
    exactly one of blob/rows is set — native emit produces the bytes
    buffer, the Python loop produces the row list — so each public
    wrapper converts at most once.  Row formatting only ever touches
    post-filter survivors (`order`), never the full day.

    engine="device" routes the score+filter through the fused on-chip
    pipeline (scoring/pipeline.py): f32 arithmetic, chunked dispatch,
    survivors-only readback; `mesh` shards it data-parallel.  The
    default host engine stays the float64 golden-bytes oracle.
    `prep` (dataplane ScoringPrep) supplies the event index arrays
    precomputed concurrently with EM."""
    n = features.num_raw_events
    sip_idx, sw_idx, dip_idx, dw_idx = _prep_indices(
        prep, features, model, "flow", flow_event_indices
    )
    if _score_engine(engine) == "device":
        from . import pipeline

        order, src_k, dest_k, sorted_scores = pipeline.filtered_flow_scores(
            model, sip_idx, sw_idx, dip_idx, dw_idx, threshold,
            chunk=chunk or pipeline.DEFAULT_CHUNK, mesh=mesh, stats=stats,
        )
        # Emit indexes by event position: scatter the survivors' scores
        # back into full-length arrays (positions outside `order` are
        # never read — only survivors are formatted).
        src_scores = np.zeros(n, np.float64)
        dest_scores = np.zeros(n, np.float64)
        src_scores[order] = src_k
        dest_scores[order] = dest_k
    else:
        src_scores = _batched_scores(model, sip_idx, sw_idx)
        dest_scores = _batched_scores(model, dip_idx, dw_idx)
        min_scores = np.minimum(src_scores, dest_scores)
        order = _keep_order(min_scores, threshold)
        sorted_scores = min_scores[order]
    blob = rows = None
    if hasattr(features, "sip_id"):
        from .. import native_emit

        blob = native_emit.flow_emit(features, src_scores, dest_scores, order)
    if blob is None:
        rows = [
            ",".join(
                features.featurized_row(i)
                + [str(src_scores[i]), str(dest_scores[i])]
            )
            for i in order
        ]
    return blob, rows, sorted_scores


def score_flow_csv(
    features: FlowFeatures, model: ScoringModel, threshold: float,
    engine: str | None = None, chunk: int | None = None,
    mesh=None, stats=None, prep=None,
) -> tuple[bytes, np.ndarray]:
    """Flow scoring with the output as one CSV buffer (newline-
    terminated rows) — the fast path for the runner, which writes the
    bytes straight to <dsource>_results.csv.  Row assembly runs in C++
    for native-backed features (native_src/row_emit.cpp; >90% of the
    stage is emit otherwise), bit-identical to the Python loop.
    engine/chunk/mesh/stats select and instrument the device pipeline;
    `prep` supplies dataplane-precomputed event indices (see
    _flow_scored)."""
    blob, rows, scores = _flow_scored(features, model, threshold,
                                      engine, chunk, mesh, stats, prep)
    if blob is None:
        blob = "".join(r + "\n" for r in rows).encode(
            "utf-8", "surrogateescape"
        )
    return blob, scores


def score_flow(
    features: FlowFeatures, model: ScoringModel, threshold: float,
    engine: str | None = None,
) -> tuple[list[str], np.ndarray]:
    """Flow scoring: score = min(<theta_sip, p_srcword>, <theta_dip,
    p_destword>); emit rows under threshold sorted ascending by that min
    (flow_post_lda.scala:227-248).  Returns (csv_rows, min_scores) where
    each row is the 35 featurized columns + src_score + dest_score.

    Only raw events are scored: the feedback duplicates appended after
    index num_raw_events train the model but must not reappear in the
    suspicious-connects output (the reference's post stage re-reads raw
    data without feedback injection)."""
    blob, rows, scores = _flow_scored(features, model, threshold, engine)
    if rows is None:
        rows = (
            blob.decode("utf-8", "surrogateescape").split("\n")[:-1]
            if blob else []
        )
    return rows, scores


def _dns_scored(features, model: ScoringModel, threshold: float,
                engine: str | None = None, chunk: int | None = None,
                mesh=None, stats=None, prep=None):
    """Shared DNS scoring core (see _flow_scored)."""
    n = features.num_raw_events
    ip_idx, word_idx = _prep_indices(
        prep, features, model, "dns", dns_event_indices
    )
    if _score_engine(engine) == "device":
        from . import pipeline

        order, sorted_scores = pipeline.filtered_scores(
            model, ip_idx, word_idx, threshold,
            chunk=chunk or pipeline.DEFAULT_CHUNK, mesh=mesh, stats=stats,
        )
        scores = np.zeros(n, np.float64)
        scores[order] = sorted_scores   # survivors only; see _flow_scored
    else:
        scores = _batched_scores(model, ip_idx, word_idx)
        order = _keep_order(scores, threshold)
        sorted_scores = scores[order]
    blob = rows = None
    if hasattr(features, "word_id"):
        from .. import native_emit

        blob = native_emit.dns_emit(features, scores, order)
    if blob is None:
        rows = [
            ",".join(features.featurized_row(i) + [str(scores[i])])
            for i in order
        ]
    return blob, rows, sorted_scores


def score_dns_csv(
    features: DnsFeatures, model: ScoringModel, threshold: float,
    engine: str | None = None, chunk: int | None = None,
    mesh=None, stats=None, prep=None,
) -> tuple[bytes, np.ndarray]:
    """DNS scoring as one CSV buffer (see score_flow_csv)."""
    blob, rows, scores = _dns_scored(features, model, threshold,
                                     engine, chunk, mesh, stats, prep)
    if blob is None:
        blob = "".join(r + "\n" for r in rows).encode(
            "utf-8", "surrogateescape"
        )
    return blob, scores


def score_dns(
    features: DnsFeatures, model: ScoringModel, threshold: float,
    engine: str | None = None,
) -> tuple[list[str], np.ndarray]:
    """DNS scoring: single <theta_ip_dst, p_word> per event
    (dns_post_lda.scala:312-331).  Each emitted row is the 15 featurized
    columns + score.  Only raw events are scored (see score_flow)."""
    blob, rows, scores = _dns_scored(features, model, threshold, engine)
    if rows is None:
        rows = (
            blob.decode("utf-8", "surrogateescape").split("\n")[:-1]
            if blob else []
        )
    return rows, scores
