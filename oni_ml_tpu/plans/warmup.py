"""AOT warmup + persistent-compilation-cache wiring.

Two mechanisms, one goal — no compiled state dies with the process:

- `setup_compilation_cache()` wires `jax_compilation_cache_dir` to
  `cache_dir()` (env `JAX_COMPILATION_CACHE_DIR` when set, else the
  fixed `<checkout>/.jax_cache`) with the min-compile-time/
  min-entry-size gates opened, so every XLA executable this process
  builds is serialized to disk and the next process deserializes
  instead of recompiling.
- `warmup_*()` AOT-compiles the scoring entry points at the active
  plan's shapes (`jax.jit(...).lower(shapes).compile()` against
  `jax.ShapeDtypeStruct`s — no data needed), so `ml_ops serve` has its
  device programs resident before the first event arrives, and the
  persistent cache holds them before any traffic-dependent dispatch.

Hit/trace accounting is REAL, not inferred: a `jax.monitoring` listener
counts `/jax/compilation_cache/compile_requests_use_cache` and
`/jax/compilation_cache/cache_hits` events, so stage/serve records can
assert "second run: zero re-traces" (`traces = requests - hits`)
instead of trusting prose.
"""

from __future__ import annotations

import os
import time

_COUNTS = {"compile_requests": 0, "cache_hits": 0,
           "trace_s": 0.0, "compile_s": 0.0}
_LISTENING: "bool | None" = False


def _ensure_listener() -> bool:
    """Register the monitoring listener once per process.  Returns
    whether counting is live (the monitoring module is jax-internal;
    absence degrades counters to zero, never to a crash)."""
    global _LISTENING
    if _LISTENING:
        return True
    if _LISTENING is None:          # tried and failed; don't retry
        return False
    try:
        from jax._src import monitoring

        def _on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                _COUNTS["compile_requests"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                _COUNTS["cache_hits"] += 1

        def _on_duration(name: str, secs: float, **kw) -> None:
            # jax times tracing, lowering and the backend compile (or
            # the cache retrieval that replaces it) apart.  Traces nest
            # — an inner jit's trace is inside the outer one's — so
            # trace_s is an upper bound and is kept out of compile_s.
            if name == "/jax/core/compile/jaxpr_trace_duration":
                _COUNTS["trace_s"] += secs
            elif name in (
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration",
            ):
                _COUNTS["compile_s"] += secs

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True
    except Exception:
        _LISTENING = None
        return False
    return True


def counting() -> bool:
    """Whether jax's compile events are being counted (the listener is
    registered): only then does a delta of `compile_counts()` mean
    anything."""
    return _LISTENING is True


def compile_counts() -> dict:
    """Cumulative per-process compile counters.  `traces` is the
    number of compile requests the persistent cache could NOT serve —
    the quantity a warmed second run drives to zero.  `compile_s` is
    the seconds jax spent lowering and compiling (or fetching from the
    cache), `trace_s` the seconds it spent tracing: what a stage's wall
    time has to shed before it says anything about the steady state."""
    c = dict(_COUNTS)
    c["traces"] = c["compile_requests"] - c["cache_hits"]
    return c


def counts_delta(before: dict) -> dict:
    now = compile_counts()
    return {
        k: (round(now[k] - before.get(k, 0), 3)
            if isinstance(now[k], float) else now[k] - before.get(k, 0))
        for k in now
    }


def cache_dir() -> str:
    """The one compilation-cache placement rule every entry point goes
    through.  `JAX_COMPILATION_CACHE_DIR`, when set, is the directory
    and nothing in code names another.  Unset, the cache is the fixed
    `<checkout>/.jax_cache` (git-ignored): the directory is part of the
    cache key, so a path derived from the home directory, a pid or the
    clock would never hit on a machine that is rebuilt per run."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def cache_entries(cache_dir: str) -> int:
    """Serialized executables currently in the cache dir."""
    try:
        return sum(
            1 for n in os.listdir(cache_dir) if n.endswith("-cache")
        )
    except OSError:
        return 0


def setup_compilation_cache(enabled: bool = True) -> dict:
    """Point jax at the persistent compilation cache (`cache_dir()`)
    and open its gates (min compile time / entry size → 0: the point is
    surviving process death, not only skipping slow compiles).  Returns
    the record the runner/serve put in their metrics: {enabled, dir,
    entries, counting}."""
    if not enabled:
        return {"enabled": False}
    d = cache_dir()
    try:
        os.makedirs(d, exist_ok=True)
        import jax

        prev = jax.config.jax_compilation_cache_dir
        if prev != d:
            # jax read the env var at import, so this only runs for the
            # checkout default or an env var set after jax was imported.
            jax.config.update("jax_compilation_cache_dir", d)
            if prev is not None:
                # jax materializes its cache object lazily and does NOT
                # re-read the dir config afterwards — a process whose
                # cache already initialized elsewhere must drop it, or
                # entries silently keep landing in the old dir.
                from jax._src.compilation_cache import reset_cache

                reset_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # A Mosaic kernel travels inside its custom call as serialized
        # MLIR, source locations included, where jax's stripping of
        # metadata from the cache key cannot reach.  With full
        # tracebacks in those locations the key depends on the line
        # numbers of the kernel's callers: the same kernel lowered
        # from two call sites is two programs (PERF.md, PR 21).
        # Innermost frame only, so the key is the kernel's, not the
        # caller's.
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
    except Exception as e:
        return {"enabled": False, "error": repr(e)[:200]}
    counting = _ensure_listener()
    return {
        "enabled": True,
        "dir": d,
        "entries": cache_entries(d),
        "counting": counting,
    }


# ---------------------------------------------------------------------------
# AOT warmup of the scoring entry points
# ---------------------------------------------------------------------------


def _aot(fn, *args, harvest: str = "", shape: str = ""):
    compiled = fn.lower(*args).compile()
    if harvest:
        # AOT warmup is the cheapest place to read XLA's cost analysis:
        # the program is already lowered+compiled here, so the roofline
        # layer's per-dispatch FLOPs/bytes come for free
        # (telemetry/roofline.py; unavailability degrades, never
        # raises).
        from ..telemetry import roofline

        roofline.harvest_compiled(harvest, compiled, shape=shape)
    return compiled


def warmup_scoring(num_ip_rows: int, num_word_rows: int, k: int,
                   chunk: int, *, dsource: str = "flow") -> dict:
    """Precompile the fused filter kernel the batch scoring stage
    dispatches at the plan's chunk size —
    filtered_scores/filtered_flow_scores trace exactly this program.
    The kernel family follows the source's pair layout (the registry's
    `pairs_per_event`): two-pair sources run the 4-index min-combining
    filter, single-pair sources the 2-index one.  `num_*_rows` include
    the fallback row (model.theta.shape[0] / model.p.shape[0]).  The
    serving path's padded gather-dot family warms separately
    (warmup_serving)."""
    import jax
    import numpy as np

    from ..scoring.pipeline import _get_fn
    from ..sources import get as get_source

    _ensure_listener()
    before = compile_counts()
    t0 = time.perf_counter()
    f32 = np.float32
    theta = jax.ShapeDtypeStruct((num_ip_rows, k), f32)
    p = jax.ShapeDtypeStruct((num_word_rows, k), f32)
    idx = jax.ShapeDtypeStruct((chunk,), np.int32)
    thr = jax.ShapeDtypeStruct((), f32)
    valid = jax.ShapeDtypeStruct((), np.int32)
    sig = f"ip{num_ip_rows}.w{num_word_rows}.k{k}.c{chunk}"
    if get_source(dsource).pairs_per_event == 2:
        _aot(_get_fn("filt_flow"), theta, p, idx, idx, idx, idx, thr, valid,
             harvest="score.device.filtered_flow", shape=sig)
    else:
        _aot(_get_fn("filt"), theta, p, idx, idx, thr, valid,
             harvest="score.device.filtered", shape=sig)
    out = {"compiled": 1, "chunk": chunk,
           "wall_s": round(time.perf_counter() - t0, 3)}
    out.update(counts_delta(before))
    return out


def warmup_serving(num_ip_rows: int, num_word_rows: int, k: int,
                   max_batch: int, device_min) -> dict:
    """Precompile the serving device scorer's padded micro-batch
    programs: one per power-of-two shape from the break-even up to
    max_batch (the O(log max_batch) program family device_scores
    dispatches over).  No-op ({"compiled": 0}) when the dispatch
    calibration pins the host path — there is nothing the stream could
    ever run on device."""
    import jax
    import numpy as np

    from ..scoring.score import _device_score_fn, use_device_path

    _ensure_listener()
    before = compile_counts()
    t0 = time.perf_counter()
    # The largest program a flush can dispatch: device_scores pads the
    # batch to the next power of two, so a non-pow2 max_batch still
    # reaches the pow2 ABOVE it — warm through that shape, not just
    # the ones <= max_batch.
    hi = 1 << max(0, max_batch - 1).bit_length()
    # Smallest batch the dispatch rule would ever send to the device
    # (real batch sizes cap at max_batch, so the hi probe tests the
    # full flush, padded).
    lo = None
    m = 1
    while m <= hi:
        if use_device_path(min(m, max_batch), device_min):
            lo = m
            break
        m <<= 1
    if lo is None:
        return {"compiled": 0, "reason": "host path pinned"}
    fn = _device_score_fn()
    theta = jax.ShapeDtypeStruct((num_ip_rows, k), np.float32)
    p = jax.ShapeDtypeStruct((num_word_rows, k), np.float32)
    compiled = 0
    m = lo
    while m <= hi:
        idx = jax.ShapeDtypeStruct((m,), np.int32)
        # Harvest every shape; the LAST (largest) program's cost stays
        # registered under the entry — the full-flush shape the SLO
        # bench and the serve roofline gauge price against.
        _aot(fn, theta, p, idx, idx, harvest="serve.micro_batch",
             shape=f"ip{num_ip_rows}.w{num_word_rows}.k{k}.b{m}")
        compiled += 1
        m <<= 1
    out = {"compiled": compiled, "shapes": f"{lo}..{hi}",
           "wall_s": round(time.perf_counter() - t0, 3)}
    out.update(counts_delta(before))
    return out
