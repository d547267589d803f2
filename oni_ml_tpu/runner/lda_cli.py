"""Drop-in CLI for the reference's `lda` binary (oni-lda-c).

The reference orchestrator invokes its MPI LDA engine as

    mpiexec -n 20 -f machinefile ./lda est 2.5 20 settings.txt 20 \
        ../FDATE/model.dat random ../FDATE

(ml_ops.sh:80; argument meanings reconstructed in SURVEY.md §2.8).  This
module accepts the same argument vector so an existing deployment can
swap `mpiexec ... ./lda` for `python -m oni_ml_tpu.runner.lda_cli` and
get the TPU engine with unchanged scripts:

    python -m oni_ml_tpu.runner.lda_cli est 2.5 20 settings.txt 20 \
        ../FDATE/model.dat random ../FDATE

Differences from the reference, by design:
- `<nproc>` is accepted and ignored — device parallelism comes from the
  mesh (all local devices by default; ONI_ML_TPU_MESH="data,model" to
  override), not from a rank count.
- `random` is the only supported init (the reference's only used mode);
  `seeded`/`manual` from stock lda-c are not reproduced.
- per-rank `<i>.beta`/`<i>.gamma` shard files are not written — they
  were an MPI implementation artifact; `final.*` and `likelihood.dat`
  are the real contract (README.md:116-121).

What holds when `main` returns 0 (the contract `lda_post.py` reads,
README.md:116-121; benchmarks/configs/flow20_est.json `guarantees`):
`final.beta` (K rows x V values of log p(word | topic)), `final.gamma`
(D rows x K, in model.dat's document order), `final.other` (num_topics,
num_terms, alpha) and `likelihood.dat` (one line an EM iteration:
likelihood, float64 |dll/ll|) are complete, closed and readable by a
process that opens them after the return; every value carries ten digits
after the point (`%5.10f`; `%10.10f\t%5.5e` for likelihood.dat); nothing
is written after the return.

Spans (telemetry/spans.py): `est.load` (the model.dat parse; counts
`bytes`, `docs`, `pairs`, and says which `reader` parsed it: `native`
or `python`, io/formats.read_model_dat), a root of its own before the
fit's root `fit`, whose `fit.save` counts the bytes of each file written
and says which `writer` wrote the two matrices (`native` or `python`,
io/formats._write_matrix) and whose close counts `ll_lines`.

settings.txt uses Blei lda-c's key-value format:

    var max iter 20
    var convergence 1e-6
    em max iter 100
    em convergence 1e-4
    alpha estimate
"""

from __future__ import annotations

import os
import sys

from ..config import LDAConfig


def read_settings(path: str) -> dict:
    """Parse lda-c settings.txt: 'key words value' lines, last token the
    value; `alpha estimate|fixed` is a bare flag."""
    out: dict = {}
    with open(path) as f:
        for raw in f:
            line = raw.strip().lower()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            if parts[:2] == ["alpha", "estimate"]:
                out["estimate_alpha"] = True
            elif parts[:2] == ["alpha", "fixed"]:
                out["estimate_alpha"] = False
            elif parts[:3] == ["var", "max", "iter"] and len(parts) > 3:
                n = int(float(parts[3]))
                # lda-c treats -1 as "iterate until converged"; our loop
                # bound is finite, so map it to a cap no real doc reaches.
                out["var_max_iters"] = 10_000 if n == -1 else n
            elif parts[:2] == ["var", "convergence"] and len(parts) > 2:
                out["var_tol"] = float(parts[2])
            elif parts[:3] == ["em", "max", "iter"] and len(parts) > 3:
                out["em_max_iters"] = int(float(parts[3]))
            elif parts[:2] == ["em", "convergence"] and len(parts) > 2:
                out["em_tol"] = float(parts[2])
            # Unknown keys and truncated lines are ignored, like lda-c's
            # fscanf-based reader.
    return out


def config_from_settings(path: str, alpha: float, k: int) -> LDAConfig:
    # warm_start_gamma pinned off: this CLI is the drop-in for
    # oni-lda-c (ml_ops.sh:80), whose E-step fresh-initializes gamma
    # every EM iteration — warm start reaches the same optimum but
    # shifts mid-run likelihood.dat values in late decimals, and this
    # surface promises the reference's exact semantics.
    # alpha_max_iters pinned to lda-c's MAX_ALPHA_ITER=100 (the
    # production default moved to the unrolled cap of 8 — equivalent
    # training, pinned in tests/test_lda.py — but THIS surface promises
    # the reference's exact alpha-Newton loop).
    return LDAConfig(num_topics=k, alpha_init=alpha,
                     warm_start_gamma=False, alpha_max_iters=100,
                     **read_settings(path))


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    wants_help = bool(argv) and argv[0] in ("-h", "--help")
    if wants_help or len(argv) != 8 or argv[0] != "est":
        print(
            "usage: python -m oni_ml_tpu.runner.lda_cli est <alpha> "
            "<num_topics> <settings.txt> <nproc-ignored> <model.dat> "
            "random <out_dir>",
            file=sys.stdout if wants_help else sys.stderr,
        )
        return 0 if wants_help else 2
    _, alpha_s, k_s, settings_path, _nproc, corpus_path, init, out_dir = argv
    if init != "random":
        print(f"only 'random' init is supported, got {init!r}", file=sys.stderr)
        return 2

    from ..io import Corpus, formats
    from ..models import train_corpus
    from ..telemetry.spans import maybe_span

    cfg = config_from_settings(settings_path, float(alpha_s), int(k_s))
    # The load is a span of its own, a root BEFORE the fit's root `fit`
    # (train_corpus opens that one, for this caller as for any other).
    with maybe_span("est.load", path=os.path.basename(corpus_path)) as sp:
        corpus = Corpus.from_model_dat(corpus_path)
        sp.annotate(bytes=os.path.getsize(corpus_path),
                    docs=corpus.num_docs, pairs=len(corpus.word_idx),
                    reader=formats.model_dat_reader)

    mesh = None
    vocab_sharded = False
    mesh_env = os.environ.get("ONI_ML_TPU_MESH", "")
    if mesh_env:
        from ..parallel.mesh import mesh_from_spec

        try:
            mesh, vocab_sharded = mesh_from_spec(mesh_env)
        except ValueError as e:
            print(f"ONI_ML_TPU_MESH: {e}", file=sys.stderr)
            return 2

    os.makedirs(out_dir, exist_ok=True)
    result = train_corpus(
        corpus, cfg, out_dir=out_dir, mesh=mesh, vocab_sharded=vocab_sharded
    )
    final_ll = result.likelihoods[-1][0] if result.likelihoods else float("nan")
    print(
        f"em iterations: {result.em_iters}  "
        f"final likelihood: {final_ll:.6f}  "
        f"alpha: {result.alpha:.6f}"
    )
    # Which E-step served the day and under which dense budget: this
    # surface cannot state one, so the budget follows the device
    # (models/lda.py dense_budget) and the operator reads here what that
    # came to.
    budget = result.plan.get("dense_hbm_budget", {})
    print(
        f"engine: {result.plan['estep_engine']['value']}  "
        f"kernel: {result.plan.get('estep_kernel', {}).get('value')}  "
        f"dense budget: {budget.get('value')} ({budget.get('source')})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
