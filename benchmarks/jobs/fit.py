"""Job kind `fit`: whole LDA fits over one resident corpus, back to back,
through the user's entry `oni_ml_tpu.models.lda.train_corpus`.

Set-up (all of it `setup_s`): caches pointed into the checkout, the corpus
made on the host from the seed, ONE whole warm-up fit with exactly the
window's call so that every bucket shape and tail batch is compiled or
fetched.  Window: `harness.window`.  After the window: a probe (the same call
stopped after 1 EM iteration) and, where the timed fit ran longer than the
traffic file's `check_steps`, a second one stopped there; the peak memory;
then, with the program's results copied to the host and its device state
dropped, the plain reference's EM iterations, as many as the timed fit's or
`check_steps`, and `harness.fit_check`.
"""

from __future__ import annotations

import gc
import os
import time
from types import SimpleNamespace

import numpy as np

from benchmarks.harness import corpus_gen, fit_check, window


# How the breakdown names an idle gap: by where it lies among the EM chunk
# programs (`XLA Modules` events matching `program`) of the annotated fit.
PHASES = {"program": r"run_chunk", "before": "place", "between": "em_sync",
          "after": "readback", "outside": "between_fits"}


class Program:
    """Everything the job takes from the system under test."""

    def __init__(self):
        from oni_ml_tpu.config import LDAConfig
        from oni_ml_tpu.io.corpus import Corpus
        from oni_ml_tpu.models.lda import train_corpus
        from oni_ml_tpu.parallel.mesh import make_mesh
        from oni_ml_tpu.plans import warmup

        self._config, self._corpus = LDAConfig, Corpus
        self._train, self._make_mesh, self._warmup = (
            train_corpus, make_mesh, warmup)

    def setup(self) -> dict:
        return self._warmup.setup_compilation_cache()

    def compile_counts(self) -> dict:
        return self._warmup.compile_counts()

    def make_input(self, csr: corpus_gen.CsrCorpus, mesh_shape):
        import jax

        corpus = self._corpus(
            doc_names=[str(i) for i in range(csr.num_docs)],
            vocab=[str(i) for i in range(csr.num_terms)],
            doc_ptr=csr.doc_ptr, word_idx=csr.word_idx, counts=csr.counts)
        mesh = None
        if mesh_shape:
            n = mesh_shape[0] * mesh_shape[1]
            mesh = self._make_mesh(data=mesh_shape[0], model=mesh_shape[1],
                                   devices=jax.devices()[:n])
        return corpus, mesh

    def fit(self, inputs, lda: dict, program: dict, batch_size: int):
        corpus, mesh = inputs
        cfg = self._config(
            num_topics=lda["num_topics"], alpha_init=lda["alpha_init"],
            estimate_alpha=lda["estimate_alpha"],
            alpha_max_iters=lda["alpha_max_iters"],
            em_max_iters=lda["em_max_iters"], em_tol=lda["em_tol"],
            var_max_iters=lda["var_max_iters"], var_tol=lda["var_tol"],
            warm_start_gamma=lda["warm_start"], seed=lda["seed"],
            batch_size=batch_size, **program)
        return self._train(corpus, cfg, mesh=mesh)


def host_copy(result) -> SimpleNamespace:
    """The fit's answers as host arrays (so its device state can go)."""
    return SimpleNamespace(
        log_beta=np.asarray(result.log_beta, np.float64),
        gamma=np.asarray(result.gamma, np.float64),
        alpha=float(result.alpha),
        likelihoods=[float(row[0]) if np.ndim(row) else float(row)
                     for row in result.likelihoods],
        em_iters=int(result.em_iters),
        plan=dict(getattr(result, "plan", {}) or {}),
    )


def run(ctx: dict) -> dict:
    """ctx: what run.run_cell hands every job kind (config, traffic, seed,
    seconds, trace, t_start, log, the annotation and tracing hooks, and
    `program`, where tests put a broken one).  Returns the pieces of the
    result line."""
    config, traffic, log = ctx["config"], ctx["traffic"], ctx["log"]
    os.environ.setdefault("ONI_ML_TPU_PLAN_CACHE", ctx["plan_cache"])
    program = ctx.get("program") or Program()
    log(f"cache: {program.setup()}")

    lda = dict(config["lda"])
    if lda.get("seed") is None:     # beta's random initialisation: the run's
        lda["seed"] = int(ctx["seed"]) % (2**31 - 1)
    t0 = time.perf_counter()
    csr = corpus_gen.make_corpus(traffic, config["num_terms"], ctx["seed"])
    inputs = program.make_input(csr, traffic.get("mesh"))
    log(f"corpus: {csr.num_docs} docs, {len(csr.word_idx)} distinct pairs, "
        f"{int(csr.counts.sum())} tokens, made in "
        f"{time.perf_counter() - t0:.2f}s")

    def fit(**override):
        return program.fit(inputs, dict(lda, **override),
                           config.get("program", {}), traffic["batch_size"])

    t0 = time.perf_counter()
    before = program.compile_counts()
    warm = fit()
    log(f"warm-up fit: {time.perf_counter() - t0:.2f}s, "
        f"{warm.em_iters} EM iterations, plan {warm.plan}, compiles "
        f"{_delta(program.compile_counts(), before)}")
    del warm
    setup_s = time.perf_counter() - ctx["t_start"]   # from process start

    # -- the window ------------------------------------------------------
    last = {}

    def one_fit() -> float:
        with ctx["annotate"]("fit"):
            last["fit"] = fit()      # the last one is the one checked
        return float(csr.num_docs * last["fit"].em_iters)

    before = program.compile_counts()
    with ctx["tracing"]():
        win = window.run_window(
            one_fit, ctx["seconds"],
            max_jobs=traffic["trace_fits"] if ctx["trace"] else None)
    compiled = _delta(program.compile_counts(), before)
    log(f"window: {win['jobs']} fits in {win['window_s']:.3f}s, ending at "
        f"{[round(t, 2) for t in win['ends']]}, compiles {compiled}")
    if compiled.get("traces", 0):
        raise RuntimeError(
            f"{compiled['traces']} fresh compiles inside the window: the "
            "warm-up did not cover the window's shapes")
    timed = host_copy(last.pop("fit"))
    log(f"timed fit: {timed.em_iters} EM iterations, plan {timed.plan}")

    # -- the probes, the memory, the reference ---------------------------
    steps = min(int(traffic["check_steps"]), timed.em_iters)
    probe1 = host_copy(fit(em_max_iters=1))
    # A timed fit of no more than `check_steps` iterations is held whole.
    probe_n = (timed if steps == timed.em_iters
               else host_copy(fit(em_max_iters=steps)))
    memory_peak = ctx["memory_peak"]()
    del inputs, fit
    gc.collect()

    t0 = time.perf_counter()
    from benchmarks.reference import lda_plain

    ref = lda_plain.fit(
        csr.doc_ptr, csr.word_idx, csr.counts, csr.num_terms, lda,
        max_steps=steps, stop_rule=False,
        block_docs=traffic["reference_block_docs"])
    values = fit_check.compare(
        timed, probe1, probe_n, ref,
        lda_plain.init_log_beta(lda["seed"], lda["num_topics"],
                                csr.num_terms),
        csr.doc_tokens().astype(np.float64), lda)
    log(f"reference and comparison: {time.perf_counter() - t0:.2f}s")
    correct, compared = fit_check.judge(values, traffic.get("limits", {}))

    rate = window.rates(win)
    return {
        "correct": correct,
        "compared": compared,
        "attempted": win["jobs"],
        "failed": 0,
        "setup_s": setup_s,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"em_docs_per_s": rate["work_per_s"],
                       "fit_s": rate["s_per_job"]},
        # What the per-layer readers may read besides the trace.
        "phases": PHASES,
        "observed": {
            "window_s": win["window_s"], "fits": win["jobs"],
            "doc_iters": win["work"],
            "em_iters": timed.em_iters, "num_docs": csr.num_docs,
            "num_topics": lda["num_topics"], "num_terms": csr.num_terms,
            "batch_size": traffic["batch_size"], "plan": timed.plan,
        },
    }


def _delta(now: dict, before: dict) -> dict:
    return {k: round(now[k] - before.get(k, 0), 3) for k in now}


def fake_program(faults=None, dtype: str = "float32", block_docs: int = 128):
    """The plain reference put in the program's place (tests and the
    control script): `faults` plants what a broken program would do."""
    from benchmarks.reference import lda_plain

    class Fake:
        def setup(self):
            return {"enabled": False, "fake": True}

        def compile_counts(self):
            return {"traces": 0}

        def make_input(self, csr, mesh_shape):
            return csr

        def fit(self, csr, lda, program, batch_size):
            out = lda_plain.fit(
                csr.doc_ptr, csr.word_idx, csr.counts, csr.num_terms, lda,
                dtype=dtype, faults=faults, block_docs=block_docs)
            if faults is not None and faults.alter_answer:
                faults.alter_answer(out)
            return out

    return Fake()
