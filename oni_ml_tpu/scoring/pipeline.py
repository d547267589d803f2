"""Device-resident scoring pipeline: fused gather·dot·threshold kernels
driven by chunked, double-buffered dispatch with survivors-only readback.

The old device scorer shipped the full float64 score vector back over
PCIe in one monolithic dispatch, for ~40 flops of useful work per event
(per-dispatch cost, not measured on the current machine; whether the
device path beats the host one is ROADMAP A4).  This module restructures
the device path so the only things that ever cross the link are:

    H2D  theta/p once per published model (float32 — half the bytes of
         the float64 host matrices; see `scoring.score._device_model`),
         then int32 index arrays, one fixed-size chunk at a time;
    D2H  one int32 survivor count per chunk plus the compacted
         (event index, score) pairs of the survivors themselves —
         a suspicion threshold keeps a tiny fraction of a day, so the
         return traffic collapses from 8·N bytes to ~8·K_survivors.

The kernel itself fuses the two model-row gathers, the K-wide dot, the
`score < threshold` filter, and a stable compaction (kept events first,
original order preserved) into ONE jit program, so the filter runs
on-chip instead of on the host after a full-result round-trip.

Dispatch is double-buffered: chunk i+1's host-side padding + H2D +
compute are enqueued (JAX dispatch is asynchronous) before chunk i's
survivor count is synced, so transfer and compute overlap and the link
is never idle waiting on the host loop.  One fixed chunk shape means one
compiled program regardless of day length.

Multi-device grants score data-parallel: the same chunk loop routes
each chunk through `parallel.make_sharded_score_fn`'s shard_map'd
gather-dot (event axis over `data`, theta/p replicated — the scoring
analogue of the reference's 20-rank document split), with threshold
compaction jit-composed on the sharded scores.

Numerics: on-chip arithmetic is float32 (gather + accumulate over K
terms) against the float64 host oracle in `scoring.score`; at K=20 the
agreement is ~1e-6 relative (pinned by tests/test_scoring_pipeline.py),
far inside the orders-of-magnitude spread suspicion thresholds cut at.
Boundary caveat: the filter compares f32 scores against the f32-cast
threshold, so an event whose float64 score sits within f32 rounding of
the cut can flip membership vs the host engine — set parity is exact
for thresholds no score sits on (real TOLs cut orders of magnitude,
and the parity tests/dryrun pick their cuts in a measured gap).
The float64 host path remains the default batch engine and the golden-
bytes parity oracle; the device engine is opt-in (ScoringConfig.engine /
ONI_ML_TPU_SCORE=device).

Every public entry point accepts a `DispatchStats` probe so tests (and
tools/score_probe.py) can assert the transfer contract instead of
trusting prose: for an N-event day at chunk C the pipeline performs
ceil(N/C) index-only H2D dispatches and survivors-only D2H — never the
old 1 full-result float64 round-trip.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..config import ScoringConfig

# Events per device dispatch.  The shipped value (ScoringConfig.
# device_chunk — config.py is the tuned-constant home; 65536 int32
# indices = 256 KiB H2D per array per chunk, sized to amortize the
# per-dispatch cost — not measured on the current machine — over
# thousands of events, small enough that two in-flight chunks are noise
# next to the model in HBM) is the DEFAULT; runs resolve the effective
# chunk through the plan cache (plans knob "score_device_chunk" —
# tools/score_probe.py sweeps and records it on the chip).
DEFAULT_CHUNK = ScoringConfig.device_chunk


@dataclass
class DispatchStats:
    """Transfer/dispatch accounting for one pipeline run — the probe the
    acceptance tests assert against.  h2d_bytes counts index-array bytes
    only (weights are accounted separately in weight_h2d_bytes because
    they ship once per published model, not per call); d2h_bytes counts
    the per-chunk survivor-count scalars plus the compacted survivor
    payload actually sliced back."""

    dispatches: int = 0          # jit kernel launches (accumulates)
    chunks: int = 0              # logical event chunks processed (accum.)
    chunk: int = 0               # effective chunk size of the LAST call
    events: int = 0              # events scored (accumulates)
    survivors: int = 0           # events past the threshold (accum.)
    h2d_bytes: int = 0           # index-array host->device bytes (accum.)
    d2h_bytes: int = 0           # device->host bytes actually sliced
                                 # back: count scalars + survivor slabs,
                                 # pow2-rounded per chunk (accumulates)
    weight_h2d_bytes: int = 0    # model theta/p transfer (once per swap)

    def as_record(self) -> dict:
        """JSON-friendly payload for bench/probe records."""
        return {f: getattr(self, f) for f in self.__dataclass_fields__}


def score_dot_rows(theta, p, ip_idx, word_idx):
    """THE gather-dot scoring kernel — two model-row gathers and a
    K-wide dot.  Every device scoring path (the fused filter kernels
    below, scoring.score._device_score_fn's padded micro-batch
    dispatch, and parallel.make_sharded_score_fn's per-shard body)
    traces THIS one definition: the pinned bitwise parity between
    chunked / one-shot / sharded scores depends on them not drifting
    in accumulate dtype or sum order.

    The astype is a no-op for the f32 weights every path ships today;
    it exists for the serving fleet's bf16 stacked snapshots
    (score._device_model storage marker): gathers stream half-width
    rows out of HBM, the multiply-accumulate still runs f32 — bf16 is
    a STORAGE precision here, never an accumulate precision."""
    import jax.numpy as jnp

    a = jnp.take(theta, ip_idx, axis=0).astype(jnp.float32)
    b = jnp.take(p, word_idx, axis=0).astype(jnp.float32)
    return jnp.sum(a * b, axis=-1)


# Cached jit programs.  Shapes key the underlying jit cache, so one
# function object serves every chunk size; theta/p ride as traced
# operands so hot-swapped models reuse the same executables.
_FNS: dict = {}


def _get_fn(name: str):
    fn = _FNS.get(name)
    if fn is None:
        import jax
        import jax.numpy as jnp

        dot = score_dot_rows

        def compact(scores, threshold, valid_n):
            # Stable on-device compaction: kept events first in original
            # event order.  Kept rows get their (distinct) position as
            # the sort key, dropped rows all get the one-past-the-end
            # sentinel, so the permutation is deterministic without
            # leaning on argsort stability.
            m = scores.shape[0]
            pos = jnp.arange(m, dtype=jnp.int32)
            keep = (scores < threshold) & (pos < valid_n)
            count = jnp.sum(keep.astype(jnp.int32))
            perm = jnp.argsort(jnp.where(keep, pos, m))
            return count, jnp.take(pos, perm), perm

        def score(theta, p, ip_idx, word_idx):
            return dot(theta, p, ip_idx, word_idx)

        def filt(theta, p, ip_idx, word_idx, threshold, valid_n):
            s = dot(theta, p, ip_idx, word_idx)
            count, pos, perm = compact(s, threshold, valid_n)
            return count, pos, jnp.take(s, perm)

        def filt_flow(theta, p, sip, sw, dip, dw, threshold, valid_n):
            src = dot(theta, p, sip, sw)
            dest = dot(theta, p, dip, dw)
            mn = jnp.minimum(src, dest)
            count, pos, perm = compact(mn, threshold, valid_n)
            return (count, pos, jnp.take(src, perm),
                    jnp.take(dest, perm), jnp.take(mn, perm))

        def compact_only(s, threshold, valid_n):
            count, pos, perm = compact(s, threshold, valid_n)
            return count, pos, jnp.take(s, perm)

        def compact_min(src, dest, threshold, valid_n):
            mn = jnp.minimum(src, dest)
            count, pos, perm = compact(mn, threshold, valid_n)
            return (count, pos, jnp.take(src, perm),
                    jnp.take(dest, perm), jnp.take(mn, perm))

        _FNS.update(
            score=jax.jit(score),
            filt=jax.jit(filt),
            filt_flow=jax.jit(filt_flow),
            compact_only=jax.jit(compact_only),
            compact_min=jax.jit(compact_min),
        )
        fn = _FNS[name]
    return fn


# One shard_map'd gather-dot per mesh (parallel/sharded.py), cached so
# repeated chunk dispatches reuse the compiled program.
_SHARDED_FNS: dict = {}


def _sharded_score_fn(mesh):
    fn = _SHARDED_FNS.get(mesh)
    if fn is None:
        from ..parallel.sharded import make_sharded_score_fn

        fn = _SHARDED_FNS[mesh] = make_sharded_score_fn(mesh)
    return fn


def _replicated_model(model, mesh, stats: "DispatchStats | None"):
    """theta/p replicated over the mesh, cached per (model, mesh) so a
    multi-device grant transfers each published model once."""
    cache = getattr(model, "_device_cache_mesh", None)
    if cache is None or cache[0] is not mesh:
        import jax
        import jax.numpy as jnp

        from ..parallel.mesh import replicated

        sh = replicated(mesh)
        theta = jax.device_put(
            jnp.asarray(model.theta, jnp.float32), sh
        )
        p = jax.device_put(jnp.asarray(model.p, jnp.float32), sh)
        model._device_cache_mesh = cache = (mesh, theta, p)
        if stats is not None:
            stats.weight_h2d_bytes += (
                4 * model.theta.size + 4 * model.p.size
            )
    return cache[1], cache[2]


def _effective_chunk(n: int, chunk: int, mesh) -> int:
    """Shrink the chunk for small inputs (next power of two, so program
    count stays O(log chunk) like device_scores' padding) and keep it
    divisible by the mesh's data axis."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    eff = min(chunk, 1 << max(0, (n - 1)).bit_length())
    if mesh is not None:
        from ..parallel.mesh import DATA_AXIS

        d = mesh.shape[DATA_AXIS]
        eff = -(-eff // d) * d
    return max(eff, 1)


def _pad_chunk(a: np.ndarray, lo: int, hi: int, chunk: int) -> np.ndarray:
    """One fixed-size int32 chunk; the tail pads with row 0 (a valid
    model row — the kernel's valid_n mask keeps pad rows from ever
    surviving the filter)."""
    out = np.zeros(chunk, np.int32)
    out[: hi - lo] = a[lo:hi]
    return out


def _run_chunks(n: int, chunk: int, dispatch, collect,
                label: str = "score.device.chunks", harvest=None):
    """The double-buffered dispatch loop shared by every pipeline entry:
    chunk i+1 is enqueued (pad + H2D + compute, all asynchronous under
    JAX dispatch) BEFORE chunk i's results are synced, so host-side
    collection overlaps device compute and the link never drains.

    When a telemetry Recorder is active (telemetry/spans.py) the whole
    loop records one `label` span (events/chunks in args) — the
    device-scoring wall the flight recorder correlates against stage
    spans; per-chunk accounting stays DispatchStats' job.  `harvest`
    (optional callable) registers the dispatched program's XLA cost
    analysis under `label` AFTER the loop — the live dispatches have
    already traced the program, so the AOT lower+compile behind the
    harvest is a compilation-cache hit rather than a cold compile
    ahead of first results — and the loop wall then joins it into a
    journaled {"kind": "roofline"} record (telemetry/roofline.py) —
    the scoring-dispatch utilization lane.  Both are recorder-gated:
    uninstrumented runs pay nothing."""
    from ..telemetry.spans import current_recorder, maybe_span, now_ns

    nchunks = -(-n // chunk)
    instrumented = current_recorder() is not None
    t0 = now_ns()
    with maybe_span(label, events=n, chunk=chunk, chunks=nchunks):
        pending = [dispatch(0)]
        for i in range(1, nchunks):
            pending.append(dispatch(i))
            collect(*pending.pop(0))
        collect(*pending.pop(0))
    if instrumented:
        if harvest is not None:
            try:
                harvest()
            except Exception:
                pass  # cost harvest must never fail a scoring run
        from ..telemetry import roofline

        roofline.emit(label, (now_ns() - t0) / 1e9, dispatches=nchunks,
                      events=n, chunk=chunk)
    return nchunks


def _model_arrays(model, mesh, stats):
    if mesh is not None:
        return _replicated_model(model, mesh, stats)
    from .score import _device_model

    return _device_model(model, stats=stats)


def chunked_scores(
    model, ip_idx, word_idx, *, chunk: int = DEFAULT_CHUNK,
    mesh=None, stats: "DispatchStats | None" = None,
) -> np.ndarray:
    """Full score vector through the chunked device pipeline — the
    serving path's large-batch scorer (every event needs its score to
    resolve its future, so no threshold compaction here; the win is
    f32 transfers, fixed-shape chunking, and dispatch overlap).
    Returns float64 for drop-in use where the host path is used."""
    from .score import _check_index_range

    _check_index_range(model, ip_idx, word_idx)
    ip = np.asarray(ip_idx, np.int32)
    w = np.asarray(word_idx, np.int32)
    n = len(ip)
    if n == 0:
        return np.zeros(0, np.float64)
    chunk = _effective_chunk(n, chunk, mesh)
    theta, p = _model_arrays(model, mesh, stats)
    fn = _sharded_score_fn(mesh) if mesh is not None else _get_fn("score")
    out = np.empty(n, np.float64)
    if stats is not None:
        stats.chunk = chunk
        stats.events += n

    def dispatch(i):
        lo = i * chunk
        hi = min(lo + chunk, n)
        ipc = _pad_chunk(ip, lo, hi, chunk)
        wc = _pad_chunk(w, lo, hi, chunk)
        if stats is not None:
            stats.dispatches += 1
            stats.chunks += 1
            stats.h2d_bytes += ipc.nbytes + wc.nbytes
        return lo, hi, fn(theta, p, ipc, wc)

    def collect(lo, hi, s):
        out[lo:hi] = np.asarray(s[: hi - lo], np.float64)
        if stats is not None:
            stats.d2h_bytes += 4 * (hi - lo)

    _run_chunks(n, chunk, dispatch, collect, label="score.device.full",
                harvest=None if mesh is not None else lambda:
                _harvest_entry("score.device.full", "score", chunk,
                               theta, p))
    if stats is not None:
        stats.survivors += n
    return out


def _harvest_entry(entry: str, fn_name: str, chunk: int, theta, p,
                   threshold=None) -> None:
    """Register `fn_name`'s per-dispatch XLA cost under `entry` (once
    per shape) at this call's shapes — the hook _run_chunks fires under
    an active recorder.  Index operands are zeros: lowering only reads
    shapes/dtypes.  The shape signature matches warmup_scoring's
    exactly, so an AOT-warmed entry is already registered and this is a
    no-op — a mismatched key would discard the free warmup harvest and
    re-lower the program on the scoring path."""
    from ..telemetry import roofline

    idx = np.zeros(chunk, np.int32)
    if fn_name == "score":
        args = (theta, p, idx, idx)
    elif fn_name == "filt":
        args = (theta, p, idx, idx, np.float32(threshold), np.int32(chunk))
    else:  # filt_flow
        args = (theta, p, idx, idx, idx, idx, np.float32(threshold),
                np.int32(chunk))
    sig = f"ip{theta.shape[0]}.w{p.shape[0]}.k{theta.shape[1]}.c{chunk}"
    roofline.ensure_harvested(entry, _get_fn(fn_name), *args, shape=sig)


def _survivor_slice(c: int, m: int) -> int:
    """Device-slice length for c survivors out of an m-row chunk: the
    next power of two, so the readback compiles O(log chunk) slice
    programs instead of one per distinct survivor count (a fresh
    length costs a ~30 ms trace/compile — the same order as the
    dispatch glue this pipeline amortizes).  The pad rows transfer and
    are trimmed on host; at most 2x the survivor payload."""
    return min(m, 1 << (c - 1).bit_length())


def _merge_survivors(parts):
    """Concatenate per-chunk survivor slabs (already in event order) and
    sort ascending by score — exactly `_keep_order`'s semantics: stable,
    so threshold-boundary ties keep event order."""
    pos = np.concatenate([p[0] for p in parts])
    cols = [
        np.concatenate([p[j] for p in parts])
        for j in range(1, len(parts[0]))
    ]
    order = np.argsort(cols[-1], kind="stable")
    return (pos[order], *[c[order] for c in cols])


def filtered_scores(
    model, ip_idx, word_idx, threshold, *, chunk: int = DEFAULT_CHUNK,
    mesh=None, stats: "DispatchStats | None" = None,
):
    """DNS-shaped fused pipeline: (event_indices, scores) of the events
    scoring under `threshold`, ascending by score with stable event-
    order ties — the device twin of host `_keep_order` over
    `_batched_scores`.  Only survivors cross PCIe back."""
    from .score import _check_index_range

    _check_index_range(model, ip_idx, word_idx)
    ip = np.asarray(ip_idx, np.int32)
    w = np.asarray(word_idx, np.int32)
    n = len(ip)
    empty = (np.zeros(0, np.int64), np.zeros(0, np.float64))
    if n == 0:
        return empty
    chunk = _effective_chunk(n, chunk, mesh)
    theta, p = _model_arrays(model, mesh, stats)
    thr = np.float32(threshold)
    parts = []
    if stats is not None:
        stats.chunk = chunk
        stats.events += n

    def dispatch(i):
        lo = i * chunk
        hi = min(lo + chunk, n)
        ipc = _pad_chunk(ip, lo, hi, chunk)
        wc = _pad_chunk(w, lo, hi, chunk)
        valid = np.int32(hi - lo)
        if stats is not None:
            stats.chunks += 1
            stats.h2d_bytes += ipc.nbytes + wc.nbytes
        if mesh is not None:
            # Two composed programs on the mesh path: the shard_map'd
            # gather-dot (scores stay device-resident, sharded over
            # `data`) and the jit compaction over the sharded scores.
            if stats is not None:
                stats.dispatches += 2
            s = _sharded_score_fn(mesh)(theta, p, ipc, wc)
            return lo, _get_fn("compact_only")(s, thr, valid)
        if stats is not None:
            stats.dispatches += 1
        return lo, _get_fn("filt")(theta, p, ipc, wc, thr, valid)

    def collect(lo, out):
        count, pos, s = out
        c = int(count)           # one scalar D2H syncs the chunk
        if stats is not None:
            stats.d2h_bytes += 4
        if c:
            cp = _survivor_slice(c, pos.shape[0])
            parts.append((
                np.asarray(pos[:cp], np.int64)[:c] + lo,  # survivors-only
                np.asarray(s[:cp], np.float64)[:c],       # D2H (pow2 pad)
            ))
            if stats is not None:
                stats.d2h_bytes += 8 * cp
                stats.survivors += c

    _run_chunks(n, chunk, dispatch, collect,
                label="score.device.filtered",
                harvest=None if mesh is not None else lambda:
                _harvest_entry("score.device.filtered", "filt", chunk,
                               theta, p, threshold))
    if not parts:
        return empty
    return _merge_survivors(parts)


def filtered_flow_scores(
    model, sip_idx, sw_idx, dip_idx, dw_idx, threshold, *,
    chunk: int = DEFAULT_CHUNK, mesh=None,
    stats: "DispatchStats | None" = None,
):
    """Flow-shaped fused pipeline: both endpoint dots, min(src, dest)
    thresholding, and compaction in one program per chunk.  Returns
    (event_indices, src_scores, dest_scores, min_scores) for the
    survivors, ascending by min score with stable ties."""
    from .score import _check_index_range

    _check_index_range(model, sip_idx, sw_idx)
    _check_index_range(model, dip_idx, dw_idx)
    arrays = [
        np.asarray(a, np.int32)
        for a in (sip_idx, sw_idx, dip_idx, dw_idx)
    ]
    n = len(arrays[0])
    empty = (np.zeros(0, np.int64),) + tuple(
        np.zeros(0, np.float64) for _ in range(3)
    )
    if n == 0:
        return empty
    chunk = _effective_chunk(n, chunk, mesh)
    theta, p = _model_arrays(model, mesh, stats)
    thr = np.float32(threshold)
    parts = []
    if stats is not None:
        stats.chunk = chunk
        stats.events += n

    def dispatch(i):
        lo = i * chunk
        hi = min(lo + chunk, n)
        pads = [_pad_chunk(a, lo, hi, chunk) for a in arrays]
        valid = np.int32(hi - lo)
        if stats is not None:
            stats.chunks += 1
            stats.h2d_bytes += sum(a.nbytes for a in pads)
        if mesh is not None:
            if stats is not None:
                stats.dispatches += 3
            sfn = _sharded_score_fn(mesh)
            src = sfn(theta, p, pads[0], pads[1])
            dest = sfn(theta, p, pads[2], pads[3])
            return lo, _get_fn("compact_min")(src, dest, thr, valid)
        if stats is not None:
            stats.dispatches += 1
        return lo, _get_fn("filt_flow")(theta, p, *pads, thr, valid)

    def collect(lo, out):
        count, pos, src, dest, mn = out
        c = int(count)
        if stats is not None:
            stats.d2h_bytes += 4
        if c:
            cp = _survivor_slice(c, pos.shape[0])
            parts.append((
                np.asarray(pos[:cp], np.int64)[:c] + lo,
                np.asarray(src[:cp], np.float64)[:c],
                np.asarray(dest[:cp], np.float64)[:c],
                np.asarray(mn[:cp], np.float64)[:c],
            ))
            if stats is not None:
                stats.d2h_bytes += 16 * cp
                stats.survivors += c

    _run_chunks(n, chunk, dispatch, collect,
                label="score.device.filtered_flow",
                harvest=None if mesh is not None else lambda:
                _harvest_entry("score.device.filtered_flow", "filt_flow",
                               chunk, theta, p, threshold))
    if not parts:
        return empty
    return _merge_survivors(parts)


def fused_featurize_scores(model, dev, codes, ip_idx, word_base: int = 0,
                           *, block: "int | None" = None, threshold=None,
                           stats: "DispatchStats | None" = None):
    """The featurize+gather+dot(+threshold) single-dispatch flush path:
    packed codes from a compiled device featurizer (sources/device.py)
    ride ONE jit program that gathers word rows through the LUT, applies
    the stacked-snapshot `word_base` offset, and runs `score_dot_rows` —
    optionally with the on-device `score < threshold` keep mask.  Thin
    re-export of ops/featurize_kernel.py so serving callers stay inside
    the scoring facade; f32 scores (the fused engine's documented
    envelope), float64 on return."""
    from ..ops.featurize_kernel import fused_scores

    return fused_scores(model, dev, codes, ip_idx, word_base,
                        block=block, threshold=threshold, stats=stats)
