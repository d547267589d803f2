"""Job kind `est_files`: the reference's own contract as the product runs
it, `lda est` from model.dat on disk to final.* on disk, through the
drop-in entry `oni_ml_tpu.runner.lda_cli.main` with the reference's eight
arguments (ml_ops.sh:80) and nothing else: no environment variable, no
option, nothing a user of the CLI could not set.  (The compile cache and
the plan cache pointed into the checkout are the harness's own, as in
jobs/fit.py.)

Set-up (all of it `setup_s`): the corpus made on the host from the seed and
written ONCE as model.dat, with settings.txt beside it, by the reference's
writer (reference/ldac_files.py), under `<checkout>/.bench_files/`, which
the run removes at its end; ONE warm-up call with the window's own
arguments.  Window (`harness.window`): the call back to back, concurrency
1, every fit into a fresh empty directory (the one before it is removed
first: one fit's files on the disk at a time); a fit's work is num_docs x the
lines of the likelihood.dat it left; `fit_s` runs from the call of `main`
to its return: settings, load, fit, the four files.  After the window: the
same call with `em max iter 1` in a second settings.txt (and, where the
timed fit ran longer than `check_steps`, a third stopped there), the peak
memory, then the plain reference under the CLI's pinned semantics (fresh
start, `alpha_max_iters` 100, seed 0) and `harness.fit_check`, unedited, on
what the reference's READERS return from the FILES of the window's last
fit.  Added to its numbers, exact and decided here: `files` = 0 only if all
four files exist, parse, are K x V, D x K, 3 lines and one line an EM
iteration as the call said it ran, every checked value carries its ten
digits, final.other names this corpus, and likelihood.dat's second column
is |dll/ll| of its first and ends where the stop rule says.  Its limit is
the traffic file's `files_limit`; `correct` is fit_check's verdict AND
`files` within it.
"""

from __future__ import annotations

import contextlib
import gc
import io
import itertools
import os
import re
import shutil
import time

import numpy as np

from benchmarks.harness import cells, corpus_gen, fit_check, window
from benchmarks.jobs import fit as fit_job
from benchmarks.reference import ldac_files

SAID_ITERS = re.compile(r"em iterations: (\d+)")
SAID_PLAN = re.compile(
    r"engine: (\S+)  kernel: (\S+)  dense budget: (\S+) \((\w+)\)")


class Program:
    """Everything the job takes from the system under test: the CLI's
    `main`, and the compile counters of `plans.warmup`."""

    def __init__(self):
        from oni_ml_tpu.plans import warmup
        from oni_ml_tpu.runner import lda_cli

        self._main, self._warmup = lda_cli.main, warmup

    def setup(self) -> dict:
        return self._warmup.setup_compilation_cache()

    def compile_counts(self) -> dict:
        return self._warmup.compile_counts()

    def est(self, argv: list) -> dict:
        """One call of `main(argv)`: its exit code, and what it printed of
        the fit (the EM iterations; the engine, kernel and dense budget
        where the program says them)."""
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            rc = self._main(list(argv))
        return dict(parse_said(said.getvalue()), rc=rc)


def parse_said(text: str) -> dict:
    out = {"em_iters": None, "plan": {}}
    m = SAID_ITERS.search(text)
    if m:
        out["em_iters"] = int(m.group(1))
    m = SAID_PLAN.search(text)
    if m:
        out["plan"] = {"engine": m.group(1), "kernel": m.group(2),
                       "dense_budget": m.group(3),
                       "dense_budget_source": m.group(4)}
    return out


def pinned(lda: dict) -> dict:
    """The configuration's `lda` group; the CLI has no seed argument, so a
    seed the file leaves open is the program's default, 0."""
    lda = dict(lda)
    if lda.get("seed") is None:
        lda["seed"] = 0
    return lda


def files_number(fit, problems: list, said_iters, lda: dict) -> tuple:
    """(`files`, problems): 0.0 where the four files keep the contract."""
    problems = list(problems)
    if fit is not None:
        problems += ldac_files.conv_problems(
            fit.ll, lda["em_tol"], lda["em_max_iters"])
        if said_iters is not None and said_iters != fit.em_iters:
            problems.append(f"likelihood.dat: {fit.em_iters} lines, the call "
                            f"said {said_iters} EM iterations")
    return (1.0 if problems else 0.0), problems


def run(ctx: dict) -> dict:
    """ctx: what run.run_cell hands every job kind.  Returns the pieces of
    the result line."""
    config, traffic, log = ctx["config"], ctx["traffic"], ctx["log"]
    os.environ.setdefault("ONI_ML_TPU_PLAN_CACHE", ctx["plan_cache"])
    program = ctx.get("program") or Program()
    log(f"cache: {program.setup()}")
    lda = pinned(config["lda"])
    k = int(lda["num_topics"])

    t0 = time.perf_counter()
    csr = corpus_gen.make_corpus(traffic, config["num_terms"], ctx["seed"])
    # The vocabulary a reader of model.dat sees: the largest word id + 1.
    num_terms = int(csr.word_idx.max()) + 1
    log(f"corpus: {csr.num_docs} docs, {len(csr.word_idx)} distinct pairs, "
        f"{int(csr.counts.sum())} tokens, {num_terms} terms, made in "
        f"{time.perf_counter() - t0:.2f}s")
    work = os.path.join(cells.ROOT, ".bench_files", f"est-{os.getpid()}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        return _run(ctx, program, lda, k, csr, num_terms, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(ctx, program, lda, k, csr, num_terms, work) -> dict:
    traffic, log = ctx["traffic"], ctx["log"]
    t0 = time.perf_counter()
    model_dat = os.path.join(work, "model.dat")
    nbytes = ldac_files.write_model_dat(
        model_dat, csr.doc_ptr, csr.word_idx, csr.counts)
    log(f"model.dat: {nbytes} bytes written in "
        f"{time.perf_counter() - t0:.2f}s")
    made = itertools.count()

    def settings_for(**override) -> str:
        path = os.path.join(work, f"settings{next(made)}.txt")
        ldac_files.write_settings(path, dict(lda, **override))
        return path

    def est(settings: str) -> tuple:
        """One call into a fresh empty directory: (directory, what the
        call said)."""
        out_dir = os.path.join(work, f"out{next(made)}")
        os.makedirs(out_dir)
        said = program.est(ldac_files.est_argv(
            lda, settings, model_dat, out_dir, traffic.get("nproc", 20)))
        if said["rc"] != 0:
            raise RuntimeError(f"lda_cli.main returned {said['rc']}")
        return out_dir, said

    def read(out_dir: str) -> tuple:
        return ldac_files.read_fit(out_dir, csr.num_docs, k, num_terms)

    def ll_lines(out_dir: str) -> int:
        with open(os.path.join(out_dir, "likelihood.dat")) as f:
            return sum(1 for _ in f)

    settings = settings_for()
    t0 = time.perf_counter()
    before = program.compile_counts()
    out_dir, said = est(settings)
    log(f"warm-up call: {time.perf_counter() - t0:.2f}s, "
        f"{said['em_iters']} EM iterations, plan {said['plan']}, compiles "
        f"{fit_job._delta(program.compile_counts(), before)}")
    shutil.rmtree(out_dir)
    setup_s = time.perf_counter() - ctx["t_start"]   # from process start

    # -- the window ------------------------------------------------------
    last = {}

    def one_fit() -> float:
        # Only the last fit's files are kept (they are the ones checked):
        # the disk holds one fit's 45 MB at a time, as a day's directory
        # does, not a window's worth of pages waiting to be written back.
        if "dir" in last:
            shutil.rmtree(last["dir"])
        with ctx["annotate"]("fit"):
            last["dir"], last["said"] = est(settings)
        return float(csr.num_docs * ll_lines(last["dir"]))

    before = program.compile_counts()
    with ctx["tracing"]():
        win = window.run_window(
            one_fit, ctx["seconds"],
            max_jobs=traffic["trace_fits"] if ctx["trace"] else None)
    compiled = fit_job._delta(program.compile_counts(), before)
    log(f"window: {win['jobs']} calls in {win['window_s']:.3f}s, ending at "
        f"{[round(t, 2) for t in win['ends']]}, compiles {compiled}")
    if compiled.get("traces", 0):
        raise RuntimeError(
            f"{compiled['traces']} fresh compiles inside the window: the "
            "warm-up did not cover the window's shapes")
    t0 = time.perf_counter()
    timed, problems = read(last["dir"])
    files, problems = files_number(timed, problems, last["said"]["em_iters"],
                                   lda)
    plan = dict(last["said"]["plan"])
    log(f"timed fit: {timed.em_iters if timed else None} EM iterations "
        f"from likelihood.dat, plan {plan}, files read in "
        f"{time.perf_counter() - t0:.2f}s")
    for problem in problems:
        log(f"files: {problem}")

    # -- the probes, the memory, the reference ---------------------------
    values = dict.fromkeys(fit_check.NUMBERS, 1.0)   # files that do not
    em_iters = timed.em_iters if timed else 0        # parse compare as 1
    if timed is not None:
        t0 = time.perf_counter()
        steps = min(int(traffic["check_steps"]), timed.em_iters)
        probe1, _ = read(est(settings_for(em_max_iters=1))[0])
        # A timed fit of no more than `check_steps` iterations is held
        # whole.
        probe_n = (timed if steps == timed.em_iters
                   else read(est(settings_for(em_max_iters=steps))[0])[0])
        log(f"probes: {time.perf_counter() - t0:.2f}s")
    memory_peak = ctx["memory_peak"]()
    gc.collect()
    if timed is not None and probe1 is not None and probe_n is not None:
        t0 = time.perf_counter()
        from benchmarks.reference import lda_plain

        ref = lda_plain.fit(
            csr.doc_ptr, csr.word_idx, csr.counts, num_terms, lda,
            max_steps=steps, stop_rule=False,
            block_docs=traffic["reference_block_docs"])
        values = fit_check.compare(
            timed, probe1, probe_n, ref,
            lda_plain.init_log_beta(lda["seed"], k, num_terms),
            csr.doc_tokens().astype(np.float64), lda)
        log(f"reference and comparison: {time.perf_counter() - t0:.2f}s")
    correct, compared = fit_check.judge(values, traffic.get("limits", {}))
    files_limit = traffic.get("files_limit")
    compared.append(["files", files, files_limit])
    correct = correct and files_limit is not None and files <= files_limit

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
        "phases": fit_job.PHASES,
        "observed": {
            "window_s": win["window_s"], "fits": win["jobs"],
            "doc_iters": win["work"],
            "em_iters": em_iters, "num_docs": csr.num_docs,
            "num_topics": timed.other["num_topics"] if timed else k,
            "num_terms": timed.other["num_terms"] if timed else num_terms,
            "batch_size": traffic["batch_size"], "plan": plan,
        },
    }


def fake_program(lda: dict, faults=None, dtype: str = "float32",
                 block_docs: int = 128, spoil=None):
    """The plain reference put in the CLI's place (tests and the control
    script): it reads settings.txt and model.dat with the reference's own
    readers, fits, and writes the four files with the reference's writer.
    `faults` plants what a broken program would do (lda_plain.Faults);
    `spoil(out_dir)` breaks the files after they are written."""
    from benchmarks.reference import lda_plain

    lda = pinned(lda)

    class Fake:
        def setup(self):
            return {"enabled": False, "fake": True}

        def compile_counts(self):
            return {"traces": 0}

        def est(self, argv):
            _, alpha, k, settings, _, model_dat, _, out_dir = argv
            run = dict(lda, alpha_init=float(alpha), num_topics=int(k),
                       **ldac_files.read_settings(settings))
            ptr, words, counts = ldac_files.read_model_dat(model_dat)
            num_terms = int(words.max()) + 1
            out = lda_plain.fit(ptr, words, counts, num_terms, run,
                                dtype=dtype, faults=faults,
                                block_docs=block_docs)
            if faults is not None and faults.alter_answer:
                faults.alter_answer(out)
            ldac_files.write_fit(out_dir, out, num_terms)
            if spoil is not None:
                spoil(out_dir)
            return {"rc": 0, "em_iters": out.em_iters,
                    "plan": dict(out.plan)}

    return Fake()
