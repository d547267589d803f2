"""The integer rung of the dense corpus' store ladder: a day whose cells
pass 256 (the analyst-feedback day: each marked row adds DUPFACTOR=1000 to
two cells) is stored int16, exact to 32,767, at 2 B a cell; every consumer
promotes the count to float32 where it uses it and takes its products at
float32 accuracy (bfloat16 operands would put the rounding of beta,
amplified by the sweeps, into every document that one feedback word
dominates): the kernels' fixed point from exact bfloat16 pieces of its
float32 operands, packed into the MXU's contraction depth
(dense_estep._packed_products), everything else at HIGHEST.  On the CPU,
where a float32 product is exact, the int16-stored program under
`dense_precision="bf16"` is therefore the float32-stored program under
"f32" to float32 rounding: the kernels (row-major and W-major, fresh and
warm, a stack read in place and a direct call) and whole fits on the
full-width, compact and data-parallel dense plans run the same sweeps and
iterations to within a few float32 ulps, amplified by the fixed point;
the densify and the vocab-sharded XLA plan's E-step (HIGHEST, no packing)
are the float32 store's bit for bit.  Seeded random corpora and beta,
kernels interpreted.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import Corpus
from oni_ml_tpu.models import fused, train_corpus
from oni_ml_tpu.ops import dense_estep, estep
from oni_ml_tpu.telemetry import spans

NB, B, V, K = 3, 256, 200, 4
# How far the int16 store's packed products leave the float32 store's
# products on the CPU (exact there): float32 accumulation in another order,
# a few ulps a product, amplified by the fixed point.  Measured on the
# CPU: kernels' gamma and statistics 4.2e-6 relative at most; a whole
# fit's gamma 3.5e-5, beta 5.3e-8 absolute (of 0.52), alpha 1.1e-7 and the
# likelihoods 3.1e-7 relative.
KERNEL_RTOL = 2e-5
FIT_RTOL = {"gamma": 2e-4, "beta": 1e-5, "alpha": 1e-5, "likelihood": 1e-5}
FIT_BETA_ATOL = 1e-6


def _feedback_counts(rng, shape, v):
    """Token lists [.., L] of distinct words, counts 1-3, and in a tenth
    of the rows one token of a feedback cell (1,000 to 32,767) or two
    tokens of one word that sum to 32,767."""
    w = np.argsort(rng.random((*shape[:-1], v)), axis=-1)[..., :shape[-1]]
    w = w.astype(np.int32)
    c = rng.integers(1, 4, shape).astype(np.float32)
    rows = rng.random(shape[:-1]) < 0.1
    c[..., 0] = np.where(rows, rng.integers(1000, 32767, shape[:-1]),
                         c[..., 0])
    pair = rng.random(shape[:-1]) < 0.05      # 16,000 + 16,767 = 32,767
    w[..., 1] = np.where(pair, w[..., 0], w[..., 1])
    c[..., 0] = np.where(pair, 16000.0, c[..., 0])
    c[..., 1] = np.where(pair, 16767.0, c[..., 1])
    return w, c


def _log_beta(rng, k, v):
    return jnp.log(jnp.asarray(rng.dirichlet(np.ones(v), size=k),
                               jnp.float32))


@functools.lru_cache(maxsize=None)
def _stacks(wmajor):
    """One group of three batches [3, 256, W], stored float32 and int16
    from the same counts, the last rows of each batch masked."""
    rng = np.random.default_rng(43)
    w, c = _feedback_counts(rng, (NB, B, 24), V)
    w, c = jnp.asarray(w), jnp.asarray(c)
    out = {}
    for dtype in (jnp.float32, jnp.int16):
        d = jax.vmap(lambda a, b: dense_estep.densify(a, b, V, dtype=dtype))(
            w, c)
        out[dtype] = jnp.swapaxes(d, 1, 2) if wmajor else d
    masks = jnp.asarray(np.arange(B) < B - 5 - np.arange(NB)[:, None],
                        jnp.float32)
    gamma = jnp.asarray(rng.uniform(0.5, 3.0, (NB, B, K)), jnp.float32)
    return _log_beta(rng, K, V), out, masks, gamma


def test_every_feedback_cell_survives_the_int16_store():
    f32, i16 = (_stacks(False)[1][d] for d in (jnp.float32, jnp.int16))
    assert i16.dtype == jnp.int16 and float(f32.max()) == 32767.0
    assert float(f32.max()) > 256
    np.testing.assert_array_equal(np.asarray(i16, np.float32),
                                  np.asarray(f32))


@pytest.mark.parametrize("call", ["in_place", "direct"])
@pytest.mark.parametrize("warm", [False, True], ids=["fresh", "warm"])
@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_the_int16_store_is_the_float32_store_bit_for_bit(wmajor, warm,
                                                         call):
    """`e_step_dense` on the int16 stack under the bf16 operand mode the
    store comes with, against the float32 stack of the same counts under
    "f32", batch 1 read in place by its index or handed over alone (the
    direct call of a single-batch group or a tail): the same sweeps, and
    every other field equal to float32 rounding (KERNEL_RTOL): the packed
    products sum exact partial products in another order (the name dates
    from when both took one float32 product)."""
    log_beta, stacks, masks, gamma = _stacks(wmajor)

    def e_step(corpus, index, precision):
        return dense_estep.e_step_dense(
            log_beta, jnp.float32(2.5), corpus, masks[1], var_max_iters=12,
            var_tol=1e-5, interpret=True, wmajor=wmajor,
            gamma_prev=gamma[1], warm=jnp.asarray(warm), precision=precision,
            batch_index=index)

    got = {}
    for dtype, stack in stacks.items():
        precision = "bf16" if dtype == jnp.int16 else "f32"
        if call == "in_place":
            got[dtype] = jax.jit(lambda i, s=stack, p=precision: e_step(
                s, i, p))(jnp.asarray(1, jnp.int32))
        else:
            got[dtype] = jax.jit(lambda c, p=precision: e_step(c, None, p))(
                stack[1])
    assert int(got[jnp.int16].vi_iters) >= 2        # a fixed point ran
    for field in ("vi_iters", "doc_sweeps"):
        assert int(getattr(got[jnp.int16], field)) == int(
            getattr(got[jnp.float32], field)), field
    for field in ("gamma", "suff_stats", "alpha_ss", "likelihood"):
        np.testing.assert_allclose(
            np.asarray(getattr(got[jnp.int16], field)),
            np.asarray(getattr(got[jnp.float32], field)),
            rtol=KERNEL_RTOL, err_msg=field)


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_bfloat16_products_would_move_a_feedback_document(wmajor):
    """Why the int16 store's products are float32: under bfloat16 operands
    the float32-stored E-step of the same counts ends a document that a
    feedback cell dominates elsewhere (its gamma off by 2% and more: 3.8%
    here), while every document the feedback misses stays within 1%."""
    log_beta, stacks, masks, gamma = _stacks(wmajor)
    run = {p: dense_estep.e_step_dense(
        log_beta, jnp.float32(2.5), stacks[jnp.float32][1], masks[1],
        var_max_iters=20, var_tol=1e-6, interpret=True, wmajor=wmajor,
        precision=p).gamma for p in ("f32", "bf16")}
    gap = (np.abs(np.asarray(run["bf16"]) - np.asarray(run["f32"])).sum(-1)
           / np.asarray(run["f32"]).sum(-1))
    dense = np.asarray(stacks[jnp.float32][1], np.float32)
    fed = (dense.max(axis=0 if wmajor else 1) > 256) & (
        np.asarray(masks[1]) > 0)
    assert fed.any() and gap[fed].max() > 2e-2
    assert gap[~fed & (np.asarray(masks[1]) > 0)].max() < 1e-2


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_the_group_densifies_into_int16_a_batch_at_a_time(wmajor):
    """fused.densify_stack into the int16 store: every cell the float32
    densify's, summed duplicates up to 32,767 included, and the compiled
    program holds one batch's float32 block, not the group's."""
    rng = np.random.default_rng(7)
    nb, rows, length, width = 8, 64, 16, 512
    w, c = _feedback_counts(rng, (nb, rows, length), width)
    got = fused.densify_stack(jnp.asarray(w), jnp.asarray(c),
                              num_terms=width, width=None, dtype=jnp.int16,
                              wmajor=wmajor)
    want = fused.densify_stack(jnp.asarray(w), jnp.asarray(c),
                               num_terms=width, width=None,
                               dtype=jnp.float32, wmajor=wmajor)
    assert got.dtype == jnp.int16 and float(want.max()) == 32767.0
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want))
    sparse = [jax.ShapeDtypeStruct((nb, rows, length), dt)
              for dt in (jnp.int32, jnp.float32)]
    mem = fused.densify_stack.lower(
        *sparse, num_terms=width, width=None, dtype=jnp.int16,
        wmajor=wmajor).compile().memory_analysis()
    group_f32 = nb * rows * width * 4
    assert mem.output_size_in_bytes == group_f32 // 2
    assert mem.temp_size_in_bytes <= 2 * rows * width * 4 < group_f32


def _feedback_corpus(num_docs=96, v=160, seed=5):
    """Documents of 4-20 distinct words at counts 1-3; every eighth holds
    a feedback cell of 1,000-8,000 (a DUPFACTOR row or several)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 21, num_docs)
    words = [rng.choice(v, n, replace=False) for n in lens]
    counts = [rng.integers(1, 4, n).astype(np.float32) for n in lens]
    for d in range(0, num_docs, 8):
        counts[d][0] = 1000.0 * rng.integers(1, 9)
    return Corpus(doc_names=[f"ip{d}" for d in range(num_docs)],
                  vocab=[f"w{i}" for i in range(v)],
                  doc_ptr=np.concatenate([[0], np.cumsum(lens)]),
                  word_idx=np.concatenate(words).astype(np.int32),
                  counts=np.concatenate(counts))


def _config(**kw):
    return LDAConfig(**dict(dict(
        num_topics=K, em_max_iters=4, em_tol=0.0, batch_size=32, seed=3,
        fused_em_chunk=3, estep_engine="dense", dense_precision="bf16"),
        **kw))


def _fit(cfg, **kw):
    """train_corpus of the feedback corpus; the result and the args of the
    plan's gate and of the plan itself."""
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        res = train_corpus(_feedback_corpus(), cfg, **kw)
    gate, = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    plan, = [e["args"] for e in rec.events if e["name"] == "fit.plan"]
    return res, gate, plan


def _assert_same_fit(a, b):
    """The same EM iterations, and beta, gamma, alpha and every iteration's
    likelihood equal to float32 rounding (FIT_RTOL)."""
    assert len(a.likelihoods) == len(b.likelihoods)
    np.testing.assert_allclose(np.exp(a.log_beta), np.exp(b.log_beta),
                               rtol=FIT_RTOL["beta"], atol=FIT_BETA_ATOL)
    np.testing.assert_allclose(a.gamma, b.gamma, rtol=FIT_RTOL["gamma"])
    np.testing.assert_allclose(a.alpha, b.alpha, rtol=FIT_RTOL["alpha"])
    np.testing.assert_allclose([ll for ll, _ in a.likelihoods],
                               [ll for ll, _ in b.likelihoods],
                               rtol=FIT_RTOL["likelihood"])


@pytest.mark.parametrize("family", ["dense", "compact", "data_mesh"])
def test_a_whole_fit_on_the_int16_store_is_the_float32_stores(family):
    """The gate climbs to int16 on the feedback day and the whole fit,
    through `train_corpus` under "bf16", is the float32-stored fit under
    "f32" to float32 rounding (`_assert_same_fit`; the name dates from when
    both took one float32 product): on the full-width dense plan, on the
    compact plan (which takes the rung: its groups are told from token
    lists by the integer vocab_map, not by the corpus' dtype), and on a
    data mesh, where every device densifies its own rows into the int16
    store.  The plan and its `fit.plan` span say which products the
    kernels took: packed on either store, since a cell of the day passes
    256 (the rule is keyed to the cells, not to the store)."""
    from oni_ml_tpu.parallel import make_mesh

    kw, over = {}, {}
    if family == "compact":
        over["estep_engine"] = "compact"
    elif family == "data_mesh":
        kw["mesh"] = make_mesh(data=2, model=1, devices=jax.devices()[:2])
    got, gate, plan = _fit(_config(**over), **kw)
    want, _, _ = _fit(_config(dense_precision="f32", **over), **kw)
    assert (gate["store"], gate["rung"]) == ("int16", "int16")
    assert got.plan["corpus_store"]["value"] == "int16"
    assert got.plan["corpus_store"]["rung"] == "int16"
    assert want.plan["corpus_store"]["value"] == "float32"
    assert got.plan["estep_kernel"]["value"].startswith(
        "compact" if family == "compact" else "dense")
    assert got.plan["products"] == {"value": "split6_packed",
                                    "phinorm_passes": 1}
    assert want.plan["products"] == {"value": "split6_packed",
                                     "phinorm_passes": 1}
    assert (got.plan["corpus_store"]["accurate"],
            want.plan["corpus_store"]["accurate"]) == (1, 1)
    assert (plan["products"], plan["phinorm_passes"]) == ("split6_packed", 1)
    _assert_same_fit(got, want)


def test_the_vocab_sharded_dense_plan_takes_the_int16_store():
    """The XLA-level vocab-sharded dense E-step on an int16-stored corpus
    under "bf16" is its float32-stored run under "f32" bit for bit: the
    same promote, the same float32 products."""
    from oni_ml_tpu.parallel import make_mesh, make_vocab_sharded_dense_e_step

    rng = np.random.default_rng(29)
    b, l, v = 16, 16, 256
    w, c = _feedback_counts(rng, (b, l), v)
    mask = jnp.asarray(np.arange(b) < b - 2, jnp.float32)
    log_beta = _log_beta(rng, K, v)
    mesh = make_mesh(data=2, model=4)
    g0 = jnp.zeros((b, K), jnp.float32)
    res = {}
    for dtype, precision in ((jnp.float32, "f32"), (jnp.int16, "bf16")):
        fn = make_vocab_sharded_dense_e_step(mesh, precision=precision)
        dense = dense_estep.densify(jnp.asarray(w), jnp.asarray(c), v,
                                    width=v, dtype=dtype)
        res[dtype] = jax.jit(lambda lb, a, d, m, f=fn: f(
            lb, a, d, m, g0, jnp.asarray(0), var_max_iters=15,
            var_tol=1e-6))(log_beta, jnp.float32(2.5), dense, mask)
    for field in estep.EStepResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res[jnp.int16], field)),
            np.asarray(getattr(res[jnp.float32], field)), err_msg=field)


def test_at_f32_the_feedback_day_reads_its_first_rung_once_and_stores_float32(
        monkeypatch):
    """`dense_precision="f32"`: the store is float32 whatever the cells
    hold, and the store's gate reads no token (`cell_scan` "none",
    `scan_tokens` 0); the same span asks the first rung's question, does a
    cell pass 256, which keys the products: once a fit, from the Corpus'
    counts, one entry a cell, in one pass, with no row sum and no summed
    cell, and again in the next fit of the same corpus."""
    def refuse(*a, **kw):
        raise AssertionError("an f32 fit climbed the ladder")

    monkeypatch.setattr(dense_estep, "max_dense_cell", refuse)
    monkeypatch.setattr(dense_estep, "_ladder_gate", refuse)
    corpus = _feedback_corpus()
    cfg = _config(dense_precision="f32")
    gates = []
    for _ in range(2):
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            res = train_corpus(corpus, cfg)
        gates += [e["args"] for e in rec.events
                  if e["name"] == "fit.plan.gate"]
        densify, = [e["args"] for e in rec.events
                    if e["name"] == "fit.densify"]
        assert (densify["store"], densify["itemsize"]) == ("float32", 4)
        assert res.plan["corpus_store"] == {
            "value": "float32", "rung": "f32", "cell_scan": "none",
            "accurate": 1}
        assert res.plan["products"] == {"value": "split6_packed",
                                        "phinorm_passes": 1}
    for gate in gates:
        assert (gate["store"], gate["rung"], gate["cell_scan"],
                gate["scan_tokens"], gate["accurate"]) == (
            "float32", "f32", "none", 0, 1)
        assert (gate["undecided_rows"], gate["repeat_rows"]) == (0, 0)
        assert (gate["largest_count"], gate["accurate_scan_tokens"]) == (
            corpus.counts.max(), corpus.counts.size)
        assert gate["largest_count"] > 256


@pytest.mark.parametrize("low,high", [(1e-12, 1.0), (1e-6, 1e6)],
                         ids=["beta", "ratio"])
def test_split3_sums_back_to_its_operand_bit_for_bit(low, high):
    """The three bfloat16 pieces of a float32 add back to it exactly,
    across beta's range (1e-12 to 1) and the ratio's: the packed products'
    every partial product is then exact in float32."""
    rng = np.random.default_rng(44)
    x = np.exp(rng.uniform(np.log(low), np.log(high), 200_000)).astype(
        np.float32)
    pieces = dense_estep.split3(jnp.asarray(x))
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    total = sum(np.asarray(p, np.float64) for p in pieces)
    np.testing.assert_array_equal(total, x.astype(np.float64))
    # and hi alone is the bfloat16 rounding the default product would take
    assert np.abs(np.asarray(pieces[0], np.float64) - x).max() > 0


def _operands(rng, k, w, bb):
    """beta [K, W] (floored Dirichlet rows), exp_et [K, BB] in (0, 1], and
    a ratio [W, BB] of counts over phinorm's scale."""
    beta = np.maximum(rng.dirichlet(np.full(w, 0.3), size=k), 1e-12)
    exp_et = np.exp(rng.normal(-2.0, 1.5, (k, bb))).clip(None, 1.0)
    ratio = rng.integers(0, 4, (w, bb)) * np.exp(rng.normal(6, 2, (w, bb)))
    ratio[:3] *= 1000.0                    # feedback cells
    return (beta.astype(np.float32), exp_et.astype(np.float32),
            ratio.astype(np.float32))


@pytest.mark.parametrize("k", [20, 128])
@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_the_int16_kernels_products_are_float32_products(wmajor, k):
    """`_sweep_products` over an int16 block, the fixed point's phinorm,
    gamma update and the tail's phinorm, in either layout: within float32
    accumulation of a float64 product.  K = 20 packs (six terms, one MXU
    pass of contraction 120); K = 128 would take six passes packed, as
    many as HIGHEST, and keeps HIGHEST.  A bfloat16 product reads 1e-3 and
    more here."""
    rng = np.random.default_rng(k)
    w, bb = 512, 128
    beta, exp_et, ratio = _operands(rng, k, w, bb)
    want_products = "split6_packed" if k == 20 else "highest"
    assert dense_estep.kernel_products(jnp.int16, k) == want_products
    assert dense_estep.phinorm_passes(want_products, k) == (
        1 if k == 20 else 6)
    phinorm, gamma_update, tail = dense_estep._sweep_products(
        jnp.asarray(beta), jnp.int16, "bf16", wmajor)
    b64 = beta.astype(np.float64)
    q64 = b64.T @ exp_et.astype(np.float64)                 # [W, BB]
    s64 = b64 @ ratio.astype(np.float64)                    # [K, BB]
    if wmajor:
        q = [phinorm(jnp.asarray(exp_et)), tail(jnp.asarray(exp_et))]
        s = gamma_update(jnp.asarray(ratio))
    else:
        q = [phinorm(jnp.asarray(exp_et.T)).T, tail(jnp.asarray(exp_et.T)).T]
        s = gamma_update(jnp.asarray(ratio.T)).T
    # float32 accumulation over K or W = 512 terms: 2.1e-6 at most here
    for got in q:
        np.testing.assert_allclose(np.asarray(got, np.float64) - 1e-30, q64,
                                   rtol=5e-6)
    np.testing.assert_allclose(np.asarray(s, np.float64), s64, rtol=5e-6)
    rough = np.asarray(jnp.asarray(beta, jnp.bfloat16).astype(jnp.float32).T
                       @ jnp.asarray(exp_et, jnp.bfloat16).astype(
                           jnp.float32), np.float64)
    assert np.abs(rough - q64).max() / q64.max() > 1e-4


def _dots(jaxpr):
    """Every dot_general of a jaxpr, its sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_dots(inner))
    return out


def _body_dots(wmajor, store, precision):
    fn = dense_estep._block_e_step_w if wmajor else dense_estep._block_e_step
    k, w, bb = 20, 256, 128
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((k, w), f32),
            jax.ShapeDtypeStruct((w, bb) if wmajor else (bb, w), store),
            jax.ShapeDtypeStruct((1, bb) if wmajor else (bb, 1), f32),
            jax.ShapeDtypeStruct((k, bb) if wmajor else (bb, k), f32))
    closed = jax.make_jaxpr(lambda *a: fn(
        *a, var_max_iters=20, var_tol=1e-6, precision=precision))(*args)
    return _dots(closed.jaxpr)


@pytest.mark.parametrize("store,precision", [
    (jnp.float32, "f32"), (jnp.float32, "bf16"), (jnp.bfloat16, "bf16")],
    ids=["f32", "f32_bf16", "bf16"])
@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_the_floating_stores_kernel_bodies_hold_no_split_product(
        wmajor, store, precision):
    """A floating store's kernel body takes the products it took before
    the int16 store was packed: four products (phinorm and the gamma
    update a sweep, phinorm and the statistics in the tail) at XLA's
    DEFAULT, the sweep's on the operand cast of `_cast_for(precision)`
    and the tail's on float32, where the int16 body's products are bfloat16
    pieces and one HIGHEST product, the tail's statistics."""
    dots = _body_dots(wmajor, store, precision)
    assert len(dots) == 4
    assert all(d.params["precision"] in (None, (None, None)) for d in dots)
    sweep = jnp.bfloat16 if precision == "bf16" else jnp.float32
    assert sorted(str(v.aval.dtype) for d in dots for v in d.invars) == sorted(
        [jnp.dtype(sweep).name] * 4 + ["float32"] * 4)
    packed = _body_dots(wmajor, jnp.int16, "bf16")
    assert len(packed) > 4
    highest = [d for d in packed
               if d.params["precision"] not in (None, (None, None))]
    assert len(highest) == 1
    assert {str(v.aval.dtype) for d in packed if d not in highest
            for v in d.invars} == {"bfloat16"}


def test_the_int16_block_keeps_its_doc_block_with_the_packed_operands():
    """The VMEM model counts the packed products' bfloat16 operands (the
    ratio's three pieces, beta's two stacks) on the int16 store only, and
    the feedback day's [4096, 8192] batches keep the bfloat16 store's doc
    block of 256 in either layout."""
    b, v, k = 4096, 8192, 20
    packed = dense_estep._vmem_estimate(256, v, k, "bf16", jnp.int16)
    floating = dense_estep._vmem_estimate(256, v, k, "bf16", jnp.bfloat16)
    assert floating == dense_estep._vmem_estimate(256, v, k, "bf16")
    assert packed > floating
    for pick in (dense_estep.pick_block, dense_estep.pick_block_w):
        assert pick(b, v, k, "bf16", jnp.int16) == 256
        assert pick(b, v, k, "bf16") == 256
    assert dense_estep.scoped_vmem_kib(
        b, v, k, wmajor=True, precision="bf16", store=jnp.int16
    ) > dense_estep.scoped_vmem_kib(b, v, k, wmajor=True, precision="bf16")
