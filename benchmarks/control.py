"""Readings that the limits of `correct` are set from, on the chip at a
cell's own size, many seeds in one process (set-up is long):

    python benchmarks/control.py --workload flow20_fit --seeds 12 --deep 3 [--first-seed N]

For every seed: the corpus, one whole fit of the program (the timed call),
its probe stopped after 1 EM iteration (and, where the fit ran longer than
the traffic file's `check_steps`, a second one stopped there) and the plain
reference's N iterations, N the fit's own count or `check_steps`: every
number of harness/fit_check.py, as a run of the benchmark compares them (the
lower readings).  For the first `--deep` seeds also, each in the program's
place and stopped after the same N iterations: the control (the reference in
bfloat16) and the reference with a fault planted (the upper readings).  Every
reading goes through `fit_check.judge` with the cell's limits.  One JSON line
per reading on standard output; the benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.harness import cells, corpus_gen, device, fit_check  # noqa: E402

def swap_two_rows(fit) -> None:
    sums = fit.gamma.sum(-1)
    a, b = int(np.argmin(sums)), int(np.argmax(sums))
    fit.gamma[[a, b]] = fit.gamma[[b, a]]


def faults():
    from benchmarks.reference.lda_plain import Faults

    return {
        "state_unchanged": Faults(freeze_model=True),
        "half_batch": Faults(
            stat_weight=lambda lo, hi, n: 2.0 * ((lo // (hi - lo)) % 2 == 0)),
        "no_exchange": Faults(stat_weight=lambda lo, hi, n: 1.0 * (lo < n / 4),
                              skip_unweighted=False),
        "answer_altered": Faults(alter_answer=swap_two_rows),
    }


def as_probes(fit_n) -> tuple:
    """An N-iteration fit as (timed, probe1, probeN) for compare()."""
    first = SimpleNamespace(log_beta=fit_n.log_beta_first,
                            alpha=fit_n.alpha_first,
                            likelihoods=fit_n.likelihoods[:1])
    return fit_n, first, fit_n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--deep", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("ONI_ML_TPU_PLAN_CACHE",
                          os.path.join(ROOT, ".jax_cache", "plans.jsonl"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    found = cells.resolve(args.workload)
    stamp = device.stamp(found["cell"]["chips"])
    config, traffic = found["config"], found["traffic"]

    from benchmarks.jobs import fit as fit_job
    from benchmarks.reference import lda_plain

    program = fit_job.Program()
    program.setup()

    def say(seed, who, values, seconds, **more):
        correct, rows = fit_check.judge(values, traffic.get("limits", {}))
        print(json.dumps(dict(
            more, cell=args.workload, seed=seed, who=who, correct=correct,
            seconds=round(seconds, 2), values=values,
            over=[name for name, value, limit in rows
                  if limit is None or not value <= limit],
            device=stamp["kind"])), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        lda = dict(config["lda"])
        if lda.get("seed") is None:
            lda["seed"] = seed % (2**31 - 1)
        csr = corpus_gen.make_corpus(traffic, config["num_terms"], seed)
        tokens = csr.doc_tokens().astype(np.float64)
        beta0 = lda_plain.init_log_beta(lda["seed"], lda["num_topics"],
                                        csr.num_terms)
        inputs = program.make_input(csr, traffic.get("mesh"))
        t0 = time.perf_counter()
        def fit(**override):
            return fit_job.host_copy(program.fit(
                inputs, dict(lda, **override), config.get("program", {}),
                traffic["batch_size"]))

        timed, probe1 = fit(), fit(em_max_iters=1)
        steps = min(int(traffic["check_steps"]), timed.em_iters)
        probe_n = timed if steps == timed.em_iters else fit(
            em_max_iters=steps)
        t_prog = time.perf_counter() - t0
        del inputs, fit
        gc.collect()

        def reference(**kw):
            return lda_plain.fit(
                csr.doc_ptr, csr.word_idx, csr.counts, csr.num_terms, lda,
                max_steps=steps, stop_rule=False,
                block_docs=traffic["reference_block_docs"], **kw)

        t0 = time.perf_counter()
        ref = reference()
        t_ref = time.perf_counter() - t0
        say(seed, "program", fit_check.compare(
            timed, probe1, probe_n, ref, beta0, tokens, lda), t_prog,
            em_iters=timed.em_iters, reference_s=round(t_ref, 2),
            dll=[abs((a - b) / a) for a, b in zip(
                timed.likelihoods[-3:], timed.likelihoods[-2:])])
        print(f"control: seed {seed}: program {timed.em_iters} EM iterations, "
              f"fit and probes {t_prog:.1f}s, reference {t_ref:.1f}s",
              file=sys.stderr, flush=True)
        if i >= args.deep:
            continue
        # A state left unchanged reads 1 by construction and needs no run;
        # the exchange between chips can only be left out where there is one.
        runs = {"control_bf16": dict(dtype="bfloat16"),
                "half_batch": dict(faults=faults()["half_batch"])}
        if found["cell"]["chips"] > 1:
            runs["no_exchange"] = dict(faults=faults()["no_exchange"])
        judged = dict(lda, em_max_iters=steps)
        for who, kw in runs.items():
            t0 = time.perf_counter()
            broken = reference(**kw)
            say(seed, who, fit_check.compare(
                *as_probes(broken), ref, beta0, tokens, judged),
                time.perf_counter() - t0)
        altered = copy.deepcopy(ref)
        swap_two_rows(altered)
        say(seed, "answer_altered", fit_check.compare(
            *as_probes(altered), ref, beta0, tokens, judged), 0.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
