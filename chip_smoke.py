#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: kernels, day, serve, day again
    python chip_smoke.py --chips 4    # one four-chip host: kernels, day, day --mesh 4,1

Drives the main path once, through the entry points a user calls, at the
width of BASELINE.json config 1 (K=20, V=8192, B=4096, L=128):

- `kernels`: every Pallas E-step kernel compiled with interpret=False at
  the block shapes the config-1 path produces, compared with the plain
  XLA E-step at the tolerances the CPU tests use;
- `day`: a seeded synthetic flow day (2,000,000 events) through
  `ml_ops 20160122 flow ...` — pre, corpus, LDA, score — with the
  engine selection the code makes on a `tpu` backend;
- `serve`: `ml_ops serve` over that day's model, the first 32,768 lines
  of the day as the stream, at least one micro-batch scored on device;
- `day_repeat`: the day again from a new process, which must find the
  EM program in the persistent compilation cache (zero fresh compiles);
- with `--chips 4`: the day on a `--mesh 4,1`, corpus shards on four
  distinct devices, final likelihood equal to the one-chip fit's.

One process holds a chip at a time: this parent never imports jax, and
the legs are sequential children (`--leg NAME`).  A leg that fails, or
a machine where `jax.devices()[0].platform` is not "tpu", makes the
exit code non-zero and no result line is printed.  The last line of
standard output on success is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The leg bodies (`leg_kernels`, `leg_day`, `leg_serve`) are importable,
and tests/test_chip_smoke.py runs them at a tiny size on the CPU with
the kernels interpreted.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
FDATE = "20160122"
# The whole run must end inside the driver's 1200 s; the parent stops
# starting (and kills) children once this much has gone.
DEADLINE_S = 1140.0


class SmokeFailure(RuntimeError):
    """A leg's check did not hold."""


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """How large the smoke runs.  The defaults are config-1 width; the
    tier-1 test shrinks every field."""

    events: int = 2_000_000
    n_src: int = 40_000
    n_dst: int = 8_000
    seed: int = 11
    topics: int = 20
    batch: int = 4096
    em_iters: int = 8
    serve_lines: int = 32_768
    # Block shape of the kernels leg: the padded vocabulary and the
    # dominant (B, L) bucket of the day above.
    vocab: int = 8192
    bucket_len: int = 128
    # Suspicion threshold of the repeated day.  The first day runs the
    # documented `1e-20`, which flags nothing on synthetic traffic; the
    # repeat flags the improbable tail so the sorted-ascending check
    # has rows to read.
    repeat_tol: float = 1e-4
    # Micro-batch size at which a second serve run pins the device
    # scorer, used only when the inline calibration kept every batch
    # on the host.
    device_score_min: int = 1024


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Leg: kernels
# ---------------------------------------------------------------------------

# (gamma rtol, gamma atol, suff rtol, suff atol, likelihood rtol,
# alpha_ss rtol) from tests/test_dense_estep.py, test_sparse_estep.py
# and test_pallas_estep.py: the f32 kernels against the XLA path, and
# the bf16 variants against the same reference at bf16 tolerance.
_TOL_F32 = (2e-3, 1e-3, 2e-3, 2e-4, 1e-5, 1e-4)
_TOL_BF16 = (5e-2, 5e-2, 0.1, 5e-3, 5e-3, 5e-3)


def _kernel_problem(size: SmokeSize):
    import jax.numpy as jnp
    import numpy as np

    k, v, b, l = size.topics, size.vocab, size.batch, size.bucket_len
    rng = np.random.default_rng(size.seed)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )
    word_idx = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    counts = jnp.asarray(rng.integers(1, 5, size=(b, l)), jnp.float32)
    mask = np.ones((b,), np.float32)
    mask[-3:] = 0.0                      # padded docs, as a real tail has
    return log_beta, jnp.float32(2.5), word_idx, counts, jnp.asarray(mask)


def leg_kernels(size: SmokeSize, *, chips: int = 1,
                interpret: bool = False) -> dict:
    """Compile and run every Pallas E-step kernel once at the config-1
    block shape and compare it with the XLA path.  Every kernel is
    tried; the leg fails at the end, naming each kernel that did not
    compile or did not agree, with the compiler's message."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from oni_ml_tpu.ops import dense_estep, estep, pallas_estep, sparse_estep
    from oni_ml_tpu.parallel import make_mesh
    from oni_ml_tpu.parallel.sharded import make_data_parallel_dense_e_step
    from oni_ml_tpu.plans import warmup

    warmup.setup_compilation_cache()
    log_beta, alpha, word_idx, counts, mask = _kernel_problem(size)
    k, v = log_beta.shape
    b = word_idx.shape[0]
    kw = dict(var_max_iters=20, var_tol=1e-6)

    def timed(fn, *args):
        """(result, seconds) with the clock stopped after the device
        finished."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        return out, time.perf_counter() - t0

    ref, _ = timed(jax.jit(partial(estep.e_step, backend="xla", **kw)),
                   log_beta, alpha, word_idx, counts, mask)
    dense = jax.block_until_ready(jax.jit(
        lambda w, c: dense_estep.densify(w, c, v))(word_idx, counts))
    dense_t = jax.block_until_ready(jnp.transpose(dense))
    zeros_g = jnp.zeros((b, k), jnp.float32)
    cold = jnp.asarray(0, jnp.int32)

    def dense_case(wmajor, precision):
        fn = jax.jit(partial(
            dense_estep.e_step_dense, interpret=interpret, wmajor=wmajor,
            precision=precision, **kw))
        return fn, (log_beta, alpha, dense_t if wmajor else dense, mask)

    def sparse_case(precision):
        fn = jax.jit(partial(sparse_estep.e_step, interpret=interpret,
                             precision=precision, **kw))
        return fn, (log_beta, alpha, word_idx, counts, mask)

    def shard_map_case(n, wmajor):
        mesh = make_mesh(data=n, model=1, devices=jax.devices()[:n])
        fn = jax.jit(partial(
            make_data_parallel_dense_e_step(mesh, wmajor=wmajor),
            interpret=interpret, **kw))
        return fn, (log_beta, alpha, dense_t if wmajor else dense, mask,
                    zeros_g, cold)

    # Compiled for a TPU, the dense kernels' f32 matmuls run at XLA's
    # DEFAULT precision: the MXU rounds their operands to bf16, exactly
    # what precision="bf16" does (config.py dense_precision).  So on
    # the chip both dense precisions are held to the tolerance the CPU
    # tests give that arithmetic; interpreted on a CPU, f32 is exact.
    dense_f32 = _TOL_F32 if interpret else _TOL_BF16
    cases = {
        "dense_rowmajor_f32": (lambda: dense_case(False, "f32"), dense_f32),
        "dense_rowmajor_bf16": (lambda: dense_case(False, "bf16"),
                                _TOL_BF16),
        "dense_wmajor_f32": (lambda: dense_case(True, "f32"), dense_f32),
        "dense_wmajor_bf16": (lambda: dense_case(True, "bf16"), _TOL_BF16),
        "sparse_fused_f32": (lambda: sparse_case("f32"), _TOL_F32),
        "sparse_fused_bf16": (lambda: sparse_case("bf16"), _TOL_BF16),
        "pallas_fixed_point_f32": (
            lambda: (jax.jit(partial(pallas_estep.e_step,
                                     interpret=interpret, **kw)),
                     (log_beta, alpha, word_idx, counts, mask)),
            _TOL_F32),
        "shard_map_1x1_dense_wmajor_f32": (
            lambda: shard_map_case(1, True), dense_f32),
        "shard_map_1x1_dense_rowmajor_f32": (
            lambda: shard_map_case(1, False), dense_f32),
    }
    if chips > 1:
        cases[f"shard_map_{chips}x1_dense_wmajor_f32"] = (
            lambda: shard_map_case(chips, True), dense_f32)
        cases[f"shard_map_{chips}x1_dense_rowmajor_f32"] = (
            lambda: shard_map_case(chips, False), dense_f32)

    sel = np.asarray(mask) == 1
    ref_gamma = np.asarray(ref.gamma)[sel]
    ref_suff = np.asarray(ref.suff_stats)
    results, failures = {}, []
    for name, (build, tol) in cases.items():
        rec: dict = {}
        results[name] = rec
        try:
            fn, args = build()
            cc0 = warmup.compile_counts()
            got, first_s = timed(fn, *args)
            cc = warmup.counts_delta(cc0)
            _, steady_s = timed(fn, *args)
        except Exception as e:  # reported below; the leg still fails
            rec.update(compiled=False, error=f"{type(e).__name__}: {e}")
            failures.append(f"{name}: {rec['error'][:2000]}")
            print(f"chip_smoke: kernel {name}: FAILED TO COMPILE/RUN: "
                  f"{rec['error'][:2000]}", flush=True)
            continue
        gamma = np.asarray(got.gamma)[sel]
        suff = np.asarray(got.suff_stats)
        rec.update(
            compiled=True,
            tolerance="f32" if tol is _TOL_F32 else "bf16-operand",
            first_call_s=round(first_s, 3),
            compile_s=cc["compile_s"], trace_s=cc["trace_s"],
            cache_hits=cc["cache_hits"],
            steady_s=round(steady_s, 5),
            vi_iters=int(got.vi_iters),
            gamma_max_err=float(np.max(np.abs(gamma - ref_gamma)
                                       / (np.abs(ref_gamma) + 1.0))),
            suff_max_err=float(np.max(np.abs(suff - ref_suff)
                                      / (np.abs(ref_suff) + 1.0))),
            ll_rel_err=float(abs(float(got.likelihood)
                                 - float(ref.likelihood))
                             / abs(float(ref.likelihood))),
            alpha_ss_rel_err=float(abs(float(got.alpha_ss)
                                       - float(ref.alpha_ss))
                                   / abs(float(ref.alpha_ss))),
        )
        g_rtol, g_atol, s_rtol, s_atol, ll_rtol, a_rtol = tol
        bad = []
        if not np.allclose(gamma, ref_gamma, rtol=g_rtol, atol=g_atol):
            bad.append("gamma")
        if not np.allclose(suff, ref_suff, rtol=s_rtol, atol=s_atol):
            bad.append("suff_stats")
        if rec["ll_rel_err"] > ll_rtol:
            bad.append("likelihood")
        if rec["alpha_ss_rel_err"] > a_rtol:
            bad.append("alpha_ss")
        if not np.isfinite(float(got.likelihood)):
            bad.append("likelihood not finite")
        rec["agrees_with_xla"] = not bad
        if bad:
            failures.append(f"{name}: disagrees with the XLA path on "
                            f"{', '.join(bad)} ({rec})")
        print(f"chip_smoke: kernel {name}: compiled, "
              f"compile {rec['compile_s']:.2f}s (trace {rec['trace_s']:.2f}s"
              f", cache hits {rec['cache_hits']}) steady "
              f"{rec['steady_s'] * 1e3:.2f}ms, "
              f"gamma_err {rec['gamma_max_err']:.2e} "
              f"suff_err {rec['suff_max_err']:.2e} "
              f"ll_rel {rec['ll_rel_err']:.2e} at {rec['tolerance']} "
              f"tolerance: "
              f"{'OK' if not bad else 'MISMATCH: ' + ','.join(bad)}",
              flush=True)
    _check(not failures, "kernels leg failed:\n  " + "\n  ".join(failures))
    return {"shape": {"k": k, "v": v, "b": b, "l": int(word_idx.shape[1])},
            "interpret": interpret, "kernels": results}


# ---------------------------------------------------------------------------
# Leg: day
# ---------------------------------------------------------------------------


def flow_day_path(workdir: str, size: SmokeSize) -> str:
    """The seeded synthetic flow day, written on first use by the same
    generator bench.py's pipeline phase uses."""
    path = os.path.join(workdir, "flow_day.csv")
    if not os.path.exists(path):
        sys.path.insert(0, ROOT)
        from bench import _write_flow_day

        os.makedirs(workdir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            _write_flow_day(f, size.events, n_src=size.n_src,
                            n_dst=size.n_dst, seed=size.seed)
        os.replace(tmp, path)
    return path


def _read_results(path: str, tol: float) -> int:
    """Rows of <dsource>_results.csv, checked: each row's score (the
    min of its last two columns) is under `tol` and no lower than the
    row before it."""
    prev = -1.0
    n = 0
    with open(path) as f:
        for line in f:
            cols = line.rstrip("\n").split(",")
            score = min(float(cols[-2]), float(cols[-1]))
            _check(score < tol, f"{path} row {n} scores {score} >= {tol}")
            _check(score >= prev,
                   f"{path} is not sorted ascending at row {n}")
            prev = score
            n += 1
    return n


def leg_day(workdir: str, size: SmokeSize, *, name: str = "day",
            tol: float = 1e-20, mesh: "str | None" = None) -> dict:
    """One `ml_ops YYYYMMDD flow TOL` run through the CLI's own main,
    with the default engine selection, then the checks on what it
    wrote.  Returns the record the parent prints."""
    import math

    from oni_ml_tpu import native_build
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.runner import ml_ops

    native = native_build.load_all()
    print(f"chip_smoke: {name}: native: " + " ".join(
        f"{lib}={status}" for lib, status in native.items()), flush=True)
    _check("python-fallback" not in native.values(),
           f"a native library fell back to pure Python: {native}")

    t0 = time.perf_counter()
    day_csv = flow_day_path(workdir, size)
    gen_s = time.perf_counter() - t0
    data_dir = os.path.join(workdir, name)
    shutil.rmtree(data_dir, ignore_errors=True)
    argv = [FDATE, "flow", repr(tol), "--flow-path", day_csv,
            "--data-dir", data_dir, "--topics", str(size.topics),
            "--batch-size", str(size.batch),
            "--em-max-iters", str(size.em_iters)]
    if mesh:
        argv += ["--mesh", mesh]
    print("chip_smoke: " + name + ": ml_ops " + " ".join(argv), flush=True)
    os.makedirs(data_dir, exist_ok=True)
    with open(os.path.join(data_dir, "stdout.log"), "w") as log, \
            contextlib.redirect_stdout(log):
        rc = ml_ops.main(argv)
    _check(rc == 0, f"ml_ops exited {rc}")

    day_dir = os.path.join(data_dir, FDATE)
    with open(os.path.join(day_dir, "metrics.json")) as f:
        records = {r["stage"]: r for r in json.load(f) if "stage" in r}
    for stage in ("pre", "corpus", "lda", "score"):
        _check(stage in records, f"stage {stage} left no record")
        _check("skipped" not in records[stage],
               f"stage {stage} was skipped: {records[stage]}")
    pre, lda, score = records["pre"], records["lda"], records["score"]
    plans = lda.get("plans", {})
    engine = plans.get("estep_engine", {})
    kernel = plans.get("estep_kernel", {})
    _check(pre.get("featurizer") == "native",
           f"the day was featurized by {pre.get('featurizer')!r}, "
           "not the native library")
    if kernel.get("platform") == "tpu":
        _check("xla" not in str(kernel.get("value")).split("+"),
               f"on a tpu backend the E-step fell through to {kernel}")

    with open(os.path.join(day_dir, "likelihood.dat")) as f:
        lls = [float(line.split()[0]) for line in f if line.strip()]
    _check(len(lls) == lda["em_iters"] and lls,
           f"likelihood.dat has {len(lls)} lines for {lda['em_iters']} "
           "EM iterations")
    _check(all(math.isfinite(x) for x in lls),
           f"likelihood not finite: {lls}")
    em_tol = LDAConfig().em_tol
    for a, b in zip(lls, lls[1:]):
        _check(b >= a - em_tol * abs(a),
               f"likelihood decreased beyond em_tol: {a} -> {b}")

    results = os.path.join(day_dir, "flow_results.csv")
    _check(os.path.exists(results), "flow_results.csv missing")
    rows = _read_results(results, tol)
    _check(rows == score["flagged"],
           f"flow_results.csv has {rows} rows, the score stage flagged "
           f"{score['flagged']}")

    stages = {}
    for stage in ("pre", "corpus", "lda", "score"):
        rec = records[stage]
        comp = rec.get("compile", {})
        # Lowering plus backend compile (or cache fetch).  jax's trace
        # timer nests, so trace_s is shown beside the split, not in it.
        spent = comp.get("compile_s", 0.0)
        stages[stage] = {
            "wall_s": rec["wall_s"],
            "compile_s": round(spent, 3),
            "trace_s": comp.get("trace_s", 0.0),
            "steady_s": round(max(rec["wall_s"] - spent, 0.0), 3),
            "compile_requests": comp.get("compile_requests", 0),
            "cache_hits": comp.get("cache_hits", 0),
            "fresh_compiles": comp.get("traces", 0),
        }
    out = {
        "argv": argv,
        "generate_day_s": round(gen_s, 3),
        "native": native,
        "events": pre["events"],
        "docs": records["corpus"]["docs"],
        "vocab": records["corpus"]["vocab"],
        "featurizer": pre["featurizer"],
        "estep_engine": engine,
        "estep_kernel": kernel,
        "em_plans": {n: plans[n] for n in ("fused_em_chunk",
                                           "host_sync_every") if n in plans},
        "scorer": score.get("scorer"),
        "em_iters": lda["em_iters"],
        "likelihoods": lls,
        "flagged": rows,
        "stages": stages,
        "crossover": _journal_records(day_dir, "estep_crossover"),
        "estep_dispatch": _journal_records(day_dir, "estep_dispatch"),
        "run_plans": {n: records.get("plans", {}).get(n) for n in (
            "compile_requests", "cache_hits", "traces", "autotune_sweeps",
            "compilation_cache")},
    }
    print(f"chip_smoke: {name}: events={out['events']} docs={out['docs']} "
          f"vocab={out['vocab']} featurizer={out['featurizer']} "
          f"engine={engine.get('value')} (from {engine.get('source')}) "
          f"kernel={kernel.get('value')} on devices "
          f"{kernel.get('corpus_devices')} scorer="
          f"{(out['scorer'] or {}).get('value')} (from "
          f"{(out['scorer'] or {}).get('source')}) flagged={rows}",
          flush=True)
    for rec in out["crossover"]:
        print(f"chip_smoke: {name}: engine crossover {json.dumps(rec)}",
              flush=True)
    print(f"chip_smoke: {name}: likelihood {lls[0]:.6g} -> {lls[-1]:.6g} "
          f"over {len(lls)} EM iterations, finite, non-decreasing",
          flush=True)
    for stage, rec in stages.items():
        print(f"chip_smoke: {name}: stage {stage}: wall {rec['wall_s']}s = "
              f"compile {rec['compile_s']}s + steady {rec['steady_s']}s "
              f"(tracing <= {rec['trace_s']}s of it); compile requests {rec['compile_requests']}, cache hits "
              f"{rec['cache_hits']}, fresh {rec['fresh_compiles']}",
              flush=True)
    return out


def _journal_records(day_dir: str, kind: str) -> list:
    out = []
    with open(os.path.join(day_dir, "run_journal.jsonl")) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == kind:
                out.append({k: v for k, v in rec.items()
                            if k not in ("seq", "t", "mono_ns")})
    return out


# ---------------------------------------------------------------------------
# Leg: serve
# ---------------------------------------------------------------------------


def _serve_once(workdir: str, day_dir: str, stream: str, tag: str,
                extra: list) -> dict:
    from oni_ml_tpu.runner import ml_ops

    metrics_path = os.path.join(workdir, f"serve_{tag}_metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    argv = ["serve", "--day-dir", day_dir, "--dsource", "flow",
            "--input", stream, "--metrics", metrics_path] + extra
    print("chip_smoke: serve: ml_ops " + " ".join(argv), flush=True)
    t0 = time.perf_counter()
    with open(os.path.join(workdir, f"serve_{tag}_stdout.log"), "w") as log, \
            contextlib.redirect_stdout(log):
        rc = ml_ops.main(argv)
    wall_s = time.perf_counter() - t0
    with open(metrics_path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    batches = [r for r in records if "batch" in r and "events" in r]
    errors = [r for r in records if "error" in r or "on_batch_error" in r]
    end = next(r for r in records if r.get("event") == "stream_end")
    plans = next(r for r in records if r.get("event") == "plans")
    _check(rc == 0, f"ml_ops serve exited {rc}: {end}")
    _check(not errors, f"serve batches failed: {errors[:3]}")
    return {
        "argv": argv, "wall_s": round(wall_s, 3), "stream_end": end,
        "plans": plans, "batches": batches,
        "device_batches": sum(r.get("scorer") == "device" for r in batches),
        "host_batches": sum(r.get("scorer") == "host" for r in batches),
    }


def leg_serve(workdir: str, size: SmokeSize, *, day_dir: str) -> dict:
    """`ml_ops serve` over a finished day: the head of the day file as
    the stream, every future resolved, and at least one micro-batch on
    the device scorer."""
    stream = os.path.join(workdir, "serve_stream.csv")
    with open(flow_day_path(workdir, size)) as src, open(stream, "w") as dst:
        for i, line in enumerate(src):
            if i >= size.serve_lines:
                break
            dst.write(line)
    lines = min(size.serve_lines, size.events)

    run = _serve_once(workdir, day_dir, stream, "auto", [])
    calibration = run["plans"]["dispatch_calibration"]
    print(f"chip_smoke: serve: dispatch calibration "
          f"{json.dumps(calibration)}", flush=True)
    runs = {"auto": run}
    if run["device_batches"] == 0:
        # The calibration kept every batch on the host; exercise the
        # device scorer through the existing pin.
        print("chip_smoke: serve: calibration kept every batch on the "
              f"host; second run with --device-score-min "
              f"{size.device_score_min}", flush=True)
        run = _serve_once(workdir, day_dir, stream, "pinned",
                          ["--device-score-min", str(size.device_score_min)])
        runs["pinned"] = run
    for tag, r in runs.items():
        end = r["stream_end"]
        _check(end["submitted"] == lines and end["rejected"] == 0,
               f"serve {tag}: submitted {end['submitted']} of {lines} "
               f"lines, rejected {end['rejected']}")
        _check(end["events_scored"] == end["submitted"],
               f"serve {tag}: {end['submitted'] - end['events_scored']} "
               "futures never resolved")
        warm = r["plans"].get("warmup", {})
        _check("error" not in warm, f"serve {tag}: warmup failed: {warm}")
        steady = sorted(b["score_ms"] for b in r["batches"])
        warmed = (
            f"warmup compiled {warm['compiled']} programs in "
            f"{warm['wall_s']}s (requests {warm['compile_requests']}, "
            f"cache hits {warm['cache_hits']})" if warm.get("compiled")
            else f"no device program to warm ({warm.get('reason')})")
        print(f"chip_smoke: serve {tag}: {end['events_scored']} events in "
              f"{len(r['batches'])} batches ({r['device_batches']} device, "
              f"{r['host_batches']} host), zero failed futures; {warmed}; "
              f"steady score_ms median {steady[len(steady) // 2]}",
              flush=True)
    _check(run["device_batches"] >= 1,
           "no micro-batch was scored on the device")
    return {"lines": lines, "calibration": calibration, "runs": runs}


# ---------------------------------------------------------------------------
# Children and parent
# ---------------------------------------------------------------------------


def _device_report(chips: int) -> dict:
    """Fail unless this process sees `chips` TPU devices; otherwise the
    device and version record every leg result carries."""
    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(
            f"jax.devices()[0].platform is {platform!r}, not 'tpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): "
            "chip_smoke.py measures nothing without the chip")
    if len(devices) < chips:
        raise SmokeFailure(
            f"--chips {chips} needs {chips} TPU devices, this machine "
            f"has {len(devices)}")
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def run_leg(leg: str, workdir: str, chips: int, size: SmokeSize) -> dict:
    """One child's whole life: the device check, the leg, its record."""
    device = _device_report(chips)
    print(f"chip_smoke: {leg}: platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']} "
          f"jax={device['jax']} jaxlib={device['jaxlib']} "
          f"libtpu={device['libtpu']}", flush=True)
    day_dir = os.path.join(workdir, "day", FDATE)
    if leg == "kernels":
        body = leg_kernels(size, chips=chips)
    elif leg == "day":
        body = leg_day(workdir, size, name="day")
    elif leg == "day_repeat":
        body = leg_day(workdir, size, name="day_repeat",
                       tol=size.repeat_tol)
    elif leg == "day_mesh":
        body = leg_day(workdir, size, name="day_mesh", mesh=f"{chips},1")
    elif leg == "serve":
        body = leg_serve(workdir, size, day_dir=day_dir)
    else:
        raise SmokeFailure(f"unknown leg {leg!r}")
    return {"leg": leg, "device": device, **body}


def _child_main(args) -> int:
    os.makedirs(args.workdir, exist_ok=True)
    try:
        record = run_leg(args.leg, args.workdir, args.chips, SmokeSize())
    except SmokeFailure as e:
        print(f"chip_smoke: {args.leg}: FAILED: {e}", flush=True)
        return 1
    with open(os.path.join(args.workdir, f"{args.leg}.json"), "w") as f:
        json.dump(record, f, indent=1)
    return 0


def _cross_checks(chips: int, records: dict) -> None:
    """Checks that need two legs' records."""
    if chips == 1:
        rep = records["day_repeat"]["stages"]["lda"]
        _check(rep["fresh_compiles"] == 0 and rep["cache_hits"] > 0,
               "the repeated day compiled the EM programs afresh: "
               f"{rep}")
        print(f"chip_smoke: day_repeat: lda stage compile requests "
              f"{rep['compile_requests']}, cache hits {rep['cache_hits']}, "
              f"fresh compiles 0", flush=True)
        return
    one, mesh = records["day"], records["day_mesh"]
    devices = mesh["estep_kernel"].get("corpus_devices", [])
    _check(len(set(devices)) == chips
           and mesh["estep_kernel"].get("corpus_slices") == chips,
           f"--mesh {chips},1 put corpus shards on devices {devices} "
           f"({mesh['estep_kernel']})")
    a, b = one["likelihoods"][-1], mesh["likelihoods"][-1]
    # tests/test_sharded.py::test_full_training_parity: rtol=1e-4.
    _check(abs(a - b) <= 1e-4 * abs(a),
           f"final likelihood one chip {a} vs {chips} chips {b}: "
           f"relative difference {abs(a - b) / abs(a):.3e} > 1e-4")
    print(f"chip_smoke: day_mesh: corpus shards on devices {devices}; "
          f"final likelihood {b:.8g} vs one chip {a:.8g} "
          f"(relative difference {abs(a - b) / abs(a):.2e})", flush=True)


def _parent_main(args) -> int:
    legs = (("kernels", "day", "serve", "day_repeat") if args.chips == 1
            else ("kernels", "day", "day_mesh"))
    workdir = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    env = dict(os.environ)
    # A smoke run neither reads nor tunes the user's plan cache: the
    # inline autotunes run cold and their records are printed.
    env["ONI_ML_TPU_PLAN_CACHE"] = os.path.join(workdir, "plans.jsonl")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    t_start = time.monotonic()
    records: dict = {}
    try:
        for leg in legs:
            left = DEADLINE_S - (time.monotonic() - t_start)
            if left <= 0:
                print(f"chip_smoke: out of time before leg {leg}",
                      flush=True)
                return 1
            t0 = time.monotonic()
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--leg", leg,
                 "--chips", str(args.chips), "--workdir", workdir],
                cwd=ROOT, env=env, start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                # The child's whole process group, finished or not:
                # nothing this run started outlives it.
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
            if rc != 0:
                print(f"chip_smoke: leg {leg} "
                      + ("ran out of time" if rc is None
                         else f"exited {rc}"), flush=True)
                return 1
            with open(os.path.join(workdir, f"{leg}.json")) as f:
                records[leg] = json.load(f)
            print(f"chip_smoke: leg {leg} ok in "
                  f"{time.monotonic() - t0:.1f}s", flush=True)
        try:
            _cross_checks(args.chips, records)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", flush=True)
            return 1
        device = records[legs[0]]["device"]
        print(f"chip_smoke: all legs ok in "
              f"{time.monotonic() - t_start:.1f}s", flush=True)
        print(json.dumps({
            "ok": True,
            "device": {"platform": device["platform"],
                       "kind": device["kind"], "count": device["count"]},
        }), flush=True)
        return 0
    finally:
        _keep_reports(workdir)


def _keep_reports(workdir: str) -> None:
    """Leg records and logs to chiprun_out/ (small; the day file and
    the day directories are not kept)."""
    out = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    os.makedirs(out, exist_ok=True)
    for dirpath, _, names in os.walk(workdir):
        for n in names:
            if n.endswith((".json", ".log", ".jsonl")) \
                    or n == "likelihood.dat":
                rel = os.path.relpath(os.path.join(dirpath, n), workdir)
                shutil.copyfile(os.path.join(dirpath, n),
                                os.path.join(out, rel.replace(os.sep, "__")))
    shutil.rmtree(workdir, ignore_errors=True)


def main(argv: "list[str] | None" = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: one chip (default); 4: one four-chip host, "
                   "adds the --mesh 4,1 day")
    p.add_argument("--leg", default=None, help=argparse.SUPPRESS)
    p.add_argument("--workdir", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.leg:
        return _child_main(args)
    return _parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
