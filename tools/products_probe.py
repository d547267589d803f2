"""One W-major (and row-major) batch of a feedback day on the int16 store:
the dense kernel's time at a fixed sweep count under each way of taking
its float32 products, and each product's error against float64 on the
batch's own operands.

    python tools/products_probe.py [--sweeps 20] [--reps 15] [--seed N]
        [--variants highest,committed] [--error-variants ...] [--errors 1]

The batch: 4,096 documents of `benchmarks/traffic/
resident_458752_feedback.json`'s day (every document that holds a feedback
cell, then the shortest others), densified [8192, 4096] into int16, beta
and gamma after `--em` EM iterations of `dense_estep.e_step_dense` under
the committed products.  Variants: "highest" (every product at
Precision.HIGHEST), "committed" (`dense_estep`'s own products), and
packed variants by the partial products they stack and their order
(`TERMS`: all nine or HIGHEST's six, hi x hi first or the smallest first;
"split9_hihi_apart", errors only: hi x hi as a product of its own).
Each variant prints one JSON line: ms a batch (median of `--reps` calls
after a warm-up, `block_until_ready` inside the timing), the sweeps a
document ran, and with `--errors` the max and median relative error of
q_t = beta^T exp_et and s_t = beta ratio_t against float64 for one
[W, 256] block, taken inside a Pallas kernel.  Runs on any backend; a
time from the CPU says nothing about the chip.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K, V, B = 20, 8192, 4096
_NINE = tuple((i, j) for i in range(3) for j in range(3))
_SIX = ((0, 0), (0, 1), (1, 0), (0, 2), (2, 0), (1, 1))
# the partial products of each packed variant, in contraction order
# (hi x hi first, or the smallest first: descending i + j); "committed"
# is dense_estep's own
TERMS = {
    "split9_hihi_first": _NINE,
    "split9_small_first": tuple(sorted(_NINE, key=lambda t: -sum(t))),
    "split6_hihi_first": _SIX,
    "split6_small_first": tuple(sorted(_SIX, key=lambda t: -sum(t))),
}


def feedback_batch(seed: int, rows: int = B):
    """Token lists [rows, L] of the feedback day: every document holding
    a feedback cell, then the shortest others."""
    from benchmarks.harness import cells
    from benchmarks.jobs import fit_feedback

    traffic = cells.load_json(cells.BENCH_DIR, "traffic",
                              "resident_458752_feedback.json")
    csr = fit_feedback.feedback_corpus(traffic, V, seed)
    lens = np.diff(csr.doc_ptr)
    doc_of = np.repeat(np.arange(len(lens)), lens)
    fed = np.unique(doc_of[csr.counts > 256])
    rest = np.setdiff1d(np.argsort(lens, kind="stable")[:rows], fed)
    docs = np.concatenate([fed, rest])[:rows]
    length = int(lens[docs].max())
    w = np.zeros((rows, length), np.int32)
    c = np.zeros((rows, length), np.float32)
    for r, d in enumerate(docs):
        a, b = csr.doc_ptr[d], csr.doc_ptr[d + 1]
        w[r, :b - a] = csr.word_idx[a:b]
        c[r, :b - a] = csr.counts[a:b]
    return w, c, len(fed)


def use(variant: str):
    """Point the kernels' int16 rule at `variant`; clear the traces."""
    import jax

    from oni_ml_tpu.ops import dense_estep as de

    if not hasattr(de, "kernel_products"):
        return        # a checkout that takes every int16 product at HIGHEST
    if not hasattr(de, "_probe_orig"):
        de._probe_orig = (de.kernel_products, getattr(
            de, "_packed_products", None))
    kernel_products, packed = de._probe_orig
    if variant == "highest":
        de.kernel_products = lambda dtype, k: (
            "highest" if jax.numpy.issubdtype(dtype, jax.numpy.integer)
            else "default")
    elif variant == "committed":
        de.kernel_products, de._packed_products = kernel_products, packed
    else:
        de.kernel_products = kernel_products
        de._packed_products = lambda beta, wmajor: packed(
            beta, wmajor, terms=TERMS[variant])
    jax.clear_caches()


def block_errors(beta, exp_et_t, ratio_t, variant):
    """Max and median relative error of q_t and s_t against float64 for
    one [W, BB] block, each product taken inside a Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from oni_ml_tpu.ops import dense_estep as de

    def kernel(b_ref, e_ref, r_ref, q_ref, s_ref):
        b = b_ref[...]
        if variant == "highest":
            hi = jax.lax.Precision.HIGHEST
            q_ref[...] = jax.lax.dot_general(
                b, e_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hi)
            s_ref[...] = jax.lax.dot_general(
                b, r_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hi)
        elif variant == "split9_hihi_apart":
            # hi x hi alone, the other eight packed, the two summed
            phinorm, gamma_update = de._probe_orig[1](
                b, True, terms=TERMS["split9_small_first"][:-1])
            hb, he = b.astype(jnp.bfloat16), e_ref[...].astype(jnp.bfloat16)
            q_ref[...] = (phinorm(e_ref[...]) - 1e-30) + de._mm(
                hb, he, ((0,), (0,)))
            s_ref[...] = gamma_update(r_ref[...])
        else:
            packed = de._probe_orig[1]
            phinorm, gamma_update = (
                de._sweep_products(b, jnp.int16, "bf16", True)[:2]
                if variant == "committed"
                else packed(b, True, terms=TERMS[variant]))
            q_ref[...] = phinorm(e_ref[...]) - 1e-30
            s_ref[...] = gamma_update(r_ref[...])

    from jax.experimental.pallas import tpu as pltpu

    w, bb = ratio_t.shape
    q, s = pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct((w, bb), jnp.float32),
                           jax.ShapeDtypeStruct((beta.shape[0], bb),
                                                jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=jax.default_backend() != "tpu",
    )(beta, exp_et_t, ratio_t)
    b64, e64, r64 = (np.asarray(x, np.float64)
                     for x in (beta, exp_et_t, ratio_t))
    out = {}
    for name, got, want in (("q_t", q, b64.T @ e64), ("s_t", s, b64 @ r64)):
        got = np.asarray(got, np.float64)
        live = want > 0
        rel = np.abs(got[live] - want[live]) / want[live]
        out[name] = {"max": float(rel.max()), "median": float(np.median(rel))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweeps", type=int, default=20)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--em", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2164000003)
    ap.add_argument("--variants",
                    default="highest,committed,split6_small_first")
    ap.add_argument("--error-variants", default=None,
                    help="variants whose errors to take (default: "
                    "--variants)")
    ap.add_argument("--layouts", default="wmajor,rowmajor")
    ap.add_argument("--errors", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.scipy.special import digamma

    from oni_ml_tpu.ops import dense_estep as de, estep, stop

    t0 = time.perf_counter()
    w, c, fed = feedback_batch(args.seed)
    wj, cj = jnp.asarray(w), jnp.asarray(c)
    dense = {"rowmajor": de.densify(wj, cj, V, dtype=jnp.int16)}
    dense["wmajor"] = dense["rowmajor"].T
    mask = jnp.ones((B,), jnp.float32)
    alpha = jnp.float32(2.5)
    rng = np.random.default_rng(args.seed)
    log_beta = jnp.log(jnp.asarray(rng.dirichlet(np.ones(V), size=K),
                                   jnp.float32))
    gamma = None
    # a few EM iterations under the committed products: beta and gamma as a
    # fit holds them
    interpret = jax.default_backend() != "tpu"
    for _ in range(args.em):
        res = de.e_step_dense(log_beta, alpha, dense["wmajor"], mask, 20,
                              1e-6, wmajor=True, precision="bf16",
                              interpret=interpret)
        log_beta, gamma = estep.m_step(res.suff_stats), res.gamma
    info = {"what": "batch", "device": jax.devices()[0].device_kind,
            "feedback_docs": int(fed), "max_cell": float(c.max()),
            "L": int(w.shape[1]),
            "setup_s": round(time.perf_counter() - t0, 2),
            "beta_min_live": float(jnp.exp(log_beta)[
                log_beta > estep.LOG_ZERO].min())}
    print(json.dumps(info), flush=True)

    exp_beta = jnp.exp(log_beta)
    e_t = jnp.exp(digamma(gamma) - digamma(gamma.sum(1, keepdims=True))).T
    q64 = (np.asarray(exp_beta, np.float64).T @ np.asarray(e_t, np.float64))
    ratio_t = jnp.asarray(np.asarray(dense["wmajor"], np.float64)
                          / (q64 + 1e-30), jnp.float32)
    stop.STALL_GATE = -1.0      # no stagnation exit: `--sweeps` exactly
    for variant in args.variants.split(","):
        use(variant)
        for layout in args.layouts.split(","):
            wm = layout == "wmajor"
            fn = jax.jit(lambda lb, d, g, wm=wm: de.e_step_dense(
                lb, alpha, d, mask, args.sweeps, -1.0, wmajor=wm,
                gamma_prev=g, warm=jnp.asarray(1), precision="bf16",
                interpret=interpret))
            out = fn(log_beta, dense[layout], gamma)
            jax.block_until_ready(out)
            times = []
            for _ in range(args.reps):
                t = time.perf_counter()
                jax.block_until_ready(fn(log_beta, dense[layout], gamma))
                times.append(time.perf_counter() - t)
            rec = {"what": "time", "variant": variant, "layout": layout,
                   "ms": round(1e3 * float(np.median(times)), 3),
                   "ms_min": round(1e3 * min(times), 3),
                   "sweeps_per_doc": float(out.doc_sweeps) / B,
                   "gamma_sum": float(jnp.sum(out.gamma))}
            print(json.dumps(rec), flush=True)
    for variant in (args.error_variants or args.variants).split(","):
        if not args.errors:
            break
        use("committed")
        rec = {"what": "error", "variant": variant,
               **block_errors(exp_beta, e_t[:, :256], ratio_t[:, :256],
                              variant)}
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
