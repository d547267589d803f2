"""Readings that the limits of `flow20_est_files`' `correct` are set from,
on the chip at the cell's own size, many seeds in one process (control.py
does the same for the `fit` job, whose Program it is tied to):

    python benchmarks/control_est.py --workload flow20_est_files --seeds 12 --deep 3 [--first-seed N]

For every seed: the corpus, written as model.dat; one whole call of the
drop-in CLI (timed from call to return), its probe stopped after 1 EM
iteration, the four FILES of each read back by the reference's readers, the
plain reference's N iterations under the CLI's pinned semantics, and every
number of harness/fit_check.py plus `files`, as a run of the benchmark
compares them (the lower readings; `em_iters` is the seed's iteration
count).  For the first `--deep` seeds also, each WRITTEN TO THE FILES by the
reference's writer and read back: the control (the reference in bfloat16),
the reference with half of its blocks left out, and the sound reference's
files with two rows of final.gamma swapped and with eight digits instead of
ten (the upper readings).  One JSON line per reading on standard output;
the benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.harness import cells, corpus_gen, device, fit_check  # noqa: E402
from benchmarks.jobs import est_files  # noqa: E402
from benchmarks.reference import ldac_files  # noqa: E402


def swap_two_gamma_rows(out_dir: str) -> None:
    path = os.path.join(out_dir, "final.gamma")
    with open(path) as f:
        lines = f.read().splitlines(True)
    sums = np.array([sum(map(float, line.split())) for line in lines])
    a, b = int(sums.argmin()), int(sums.argmax())
    lines[a], lines[b] = lines[b], lines[a]
    with open(path, "w") as f:
        f.write("".join(lines))


def eight_digits(out_dir: str) -> None:
    path = os.path.join(out_dir, "final.gamma")
    np.savetxt(path, np.loadtxt(path, ndmin=2), fmt="%5.8f")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--deep", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--tiny", action="store_true",
                    help="the script end to end on the CPU at rehearse.py's "
                         "tiny size: nothing it prints is a reading")
    args = ap.parse_args(argv)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("ONI_ML_TPU_PLAN_CACHE",
                          os.path.join(ROOT, ".jax_cache", "plans.jsonl"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    found = cells.resolve(args.workload)
    if args.tiny:
        from benchmarks import rehearse

        found = rehearse.shrink(found)
        stamp = {"kind": "rehearsal"}
    else:
        stamp = device.stamp(found["cell"]["chips"])
    config, traffic = found["config"], found["traffic"]
    lda = est_files.pinned(config["lda"])
    k = int(lda["num_topics"])

    from benchmarks.reference import lda_plain

    program = est_files.Program()
    program.setup()
    work = os.path.join(ROOT, ".bench_files", f"control-{os.getpid()}")

    def say(seed, who, values, files, problems, seconds, **more):
        correct, rows = fit_check.judge(values, traffic.get("limits", {}))
        over = [name for name, value, limit in rows
                if limit is None or not value <= limit]
        if files > traffic["files_limit"]:
            over.append("files")
        print(json.dumps(dict(
            more, cell=args.workload, seed=seed, who=who,
            correct=correct and "files" not in over,
            seconds=round(seconds, 2), values=dict(values, files=files),
            over=over, problems=problems[:4], device=stamp["kind"])),
            flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        csr = corpus_gen.make_corpus(traffic, config["num_terms"], seed)
        num_terms = int(csr.word_idx.max()) + 1
        tokens = csr.doc_tokens().astype(np.float64)
        beta0 = lda_plain.init_log_beta(lda["seed"], k, num_terms)
        model_dat = os.path.join(work, "model.dat")
        ldac_files.write_model_dat(model_dat, csr.doc_ptr, csr.word_idx,
                                   csr.counts)

        def est(name, **override):
            settings = os.path.join(work, name + ".txt")
            ldac_files.write_settings(settings, dict(lda, **override))
            out_dir = os.path.join(work, name)
            os.makedirs(out_dir)
            t0 = time.perf_counter()
            said = program.est(ldac_files.est_argv(
                lda, settings, model_dat, out_dir))
            return out_dir, said, time.perf_counter() - t0

        def read(out_dir):
            return ldac_files.read_fit(out_dir, csr.num_docs, k, num_terms)

        if i == 0:          # the process's first call compiles
            est("warm")
        out_dir, said, call_s = est("timed")
        timed, problems = read(out_dir)
        files, problems = est_files.files_number(
            timed, problems, said["em_iters"], lda)
        probe1, _ = read(est("probe1", em_max_iters=1)[0])
        steps = min(int(traffic["check_steps"]), timed.em_iters)
        probe_n = timed if steps == timed.em_iters else read(
            est("probe_n", em_max_iters=steps)[0])[0]

        def reference(**kw):
            return lda_plain.fit(
                csr.doc_ptr, csr.word_idx, csr.counts, num_terms, lda,
                max_steps=steps, stop_rule=False,
                block_docs=traffic["reference_block_docs"], **kw)

        t0 = time.perf_counter()
        ref = reference()
        t_ref = time.perf_counter() - t0
        say(seed, "program", fit_check.compare(
            timed, probe1, probe_n, ref, beta0, tokens, lda), files,
            problems, call_s, em_iters=timed.em_iters, plan=said["plan"],
            reference_s=round(t_ref, 2), conv=timed.conv[-3:])
        print(f"control: seed {seed}: {timed.em_iters} EM iterations, the "
              f"call {call_s:.2f}s, reference {t_ref:.1f}s",
              file=sys.stderr, flush=True)
        if i >= args.deep:
            continue
        judged = dict(lda, em_max_iters=steps)
        half = lda_plain.Faults(
            stat_weight=lambda lo, hi, n: 2.0 * ((lo // (hi - lo)) % 2 == 0))
        for who, kw, spoil in (
                ("control_bf16", dict(dtype="bfloat16"), None),
                ("half_batch", dict(faults=half), None),
                ("rows_swapped", None, swap_two_gamma_rows),
                ("eight_digits", None, eight_digits)):
            t0 = time.perf_counter()
            out_dir = os.path.join(work, who)
            os.makedirs(out_dir)
            broken = ref if kw is None else reference(**kw)
            ldac_files.write_fit(out_dir, broken, num_terms)
            if spoil is not None:
                spoil(out_dir)
            back, problems = read(out_dir)
            files, problems = est_files.files_number(
                back, problems, broken.em_iters, judged)
            if back is None:
                values = dict.fromkeys(fit_check.NUMBERS, 1.0)
            else:
                first = SimpleNamespace(
                    log_beta=broken.log_beta_first, alpha=broken.alpha_first,
                    likelihoods=broken.likelihoods[:1])
                values = fit_check.compare(
                    back, first, back, ref, beta0, tokens, judged)
            say(seed, who, values, files, problems,
                time.perf_counter() - t0)
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
