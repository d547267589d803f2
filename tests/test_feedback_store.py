"""The integer rung of the dense corpus' store ladder: a day whose cells
pass 256 (the analyst-feedback day: each marked row adds DUPFACTOR=1000 to
two cells) is stored int16, exact to 32,767, at 2 B a cell; every consumer
promotes the count to float32 where it uses it and takes its products at
HIGHEST, float32 (dense_estep._products: bfloat16 operands would put the
rounding of beta, amplified by the sweeps, into every document that one
feedback word dominates).  On the CPU, where a float32 product is exact,
the int16-stored program under `dense_precision="bf16"` is therefore the
float32-stored program under "f32" bit for bit: the kernels (row-major and
W-major, fresh and warm, a stack read in place and a direct call), the
densify, and whole fits on the full-width, compact and data-parallel dense
plans, and the vocab-sharded XLA plan's E-step.  Seeded random corpora and
beta, kernels interpreted.
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
    direct call of a single-batch group or a tail): every field the same
    numbers."""
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
    for field in estep.EStepResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got[jnp.int16], field)),
            np.asarray(getattr(got[jnp.float32], field)), err_msg=field)


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
        fused_em_chunk=3, dense_em="on", dense_precision="bf16"), **kw))


def _fit(cfg, **kw):
    """train_corpus of the feedback corpus; the result and the plan's gate
    args."""
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        res = train_corpus(_feedback_corpus(), cfg, **kw)
    gate, = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    return res, gate


def _assert_same_fit(a, b):
    np.testing.assert_array_equal(a.log_beta, b.log_beta)
    np.testing.assert_array_equal(a.gamma, b.gamma)
    assert a.alpha == b.alpha and a.likelihoods == b.likelihoods


@pytest.mark.parametrize("family", ["dense", "compact", "data_mesh"])
def test_a_whole_fit_on_the_int16_store_is_the_float32_stores(
        family, monkeypatch):
    """The gate climbs to int16 on the feedback day and the whole fit,
    through `train_corpus` under "bf16", is the float32-stored fit under
    "f32", bit for bit: on the full-width dense plan, on the compact plan
    (which takes the rung: its groups are told from token lists by the
    integer vocab_map, not by the corpus' dtype), and on a data mesh,
    where every device densifies its own rows into the int16 store."""
    from oni_ml_tpu.parallel import make_mesh

    kw, over = {}, {}
    if family == "compact":
        monkeypatch.setenv("ONI_ML_TPU_ESTEP", "compact")
        over["dense_em"] = "auto"
    elif family == "data_mesh":
        kw["mesh"] = make_mesh(data=2, model=1, devices=jax.devices()[:2])
    got, gate = _fit(_config(**over), **kw)
    want, _ = _fit(_config(dense_precision="f32", **over), **kw)
    assert (gate["store"], gate["rung"]) == ("int16", "int16")
    assert got.plan["corpus_store"]["value"] == "int16"
    assert got.plan["corpus_store"]["rung"] == "int16"
    assert want.plan["corpus_store"]["value"] == "float32"
    assert got.plan["estep_kernel"]["value"].startswith(
        "compact" if family == "compact" else "dense")
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


def test_at_f32_the_feedback_day_reads_no_token_and_stores_float32(
        monkeypatch):
    """`dense_precision="f32"`: the gate reads no token, the rung is f32,
    and the densify says so; the ladder is bf16 operand mode's alone."""
    def refuse(word_idx, counts):
        raise AssertionError("an f32 fit read its tokens")

    monkeypatch.setattr(dense_estep, "max_dense_cell", refuse)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        res = train_corpus(_feedback_corpus(), _config(dense_precision="f32"))
    gate, = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    densify, = [e["args"] for e in rec.events if e["name"] == "fit.densify"]
    assert (gate["store"], gate["rung"], gate["cell_scan"],
            gate["scan_tokens"], gate["over_bf16_tokens"]) == (
        "float32", "f32", "none", 0, 0)
    assert (densify["store"], densify["itemsize"]) == ("float32", 4)
    assert res.plan["corpus_store"] == {"value": "float32", "rung": "f32",
                                        "cell_scan": "none"}
