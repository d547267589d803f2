"""Multi-device parity: sharded E/M steps must reproduce the single-device
engine on the 8-device virtual CPU mesh (SURVEY §4: "asserting sharded-vs-
single-device ... equality of suff-stats psums")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import make_batches
from oni_ml_tpu.models import LDATrainer, train_corpus
from oni_ml_tpu.parallel import (
    make_data_parallel_e_step,
    make_mesh,
    make_vocab_sharded_fns,
    pad_vocab,
)
from oni_ml_tpu.ops import estep

import reference_lda as ref
from test_lda import corpus_from_docs


@pytest.fixture(scope="module")
def problem():
    docs, _ = ref.make_synthetic_corpus(num_docs=48, num_terms=37, num_topics=3,
                                        seed=11)
    corpus = corpus_from_docs(docs, 37)
    rng = np.random.default_rng(5)
    K = 4
    noise = rng.uniform(size=(K, 37)) + 1 / 37
    log_beta = np.log(noise / noise.sum(-1, keepdims=True))
    return corpus, K, log_beta


def test_devices_available():
    assert len(jax.devices()) == 8, jax.devices()


def test_data_parallel_e_step_parity(problem):
    corpus, K, log_beta = problem
    mesh = make_mesh(data=8, model=1)
    batches = make_batches(corpus, batch_size=64, min_bucket_len=64)
    assert len(batches) == 1
    b = batches[0]
    args = (
        jnp.asarray(log_beta, jnp.float32),
        jnp.float32(2.5),
        jnp.asarray(b.word_idx),
        jnp.asarray(b.counts),
        jnp.asarray(b.doc_mask),
    )
    single = estep.e_step(*args, var_max_iters=30, var_tol=1e-7)
    fn = make_data_parallel_e_step(mesh)
    sharded = jax.jit(
        lambda *a: fn(*a, var_max_iters=30, var_tol=1e-7)
    )(*args)
    np.testing.assert_allclose(np.asarray(sharded.gamma), np.asarray(single.gamma),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sharded.suff_stats), np.asarray(single.suff_stats),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(sharded.likelihood), float(single.likelihood),
                               rtol=1e-5)
    np.testing.assert_allclose(float(sharded.alpha_ss), float(single.alpha_ss),
                               rtol=1e-5)


def test_vocab_sharded_e_step_parity(problem):
    corpus, K, log_beta = problem
    mesh = make_mesh(data=2, model=4)
    V = corpus.num_terms
    v_pad = pad_vocab(V, 4)
    lb_pad = np.pad(log_beta, ((0, 0), (0, v_pad - V)),
                    constant_values=estep.LOG_ZERO)
    batches = make_batches(corpus, batch_size=64, min_bucket_len=64)
    b = batches[0]
    args = (
        jnp.asarray(lb_pad, jnp.float32),
        jnp.float32(2.5),
        jnp.asarray(b.word_idx),
        jnp.asarray(b.counts),
        jnp.asarray(b.doc_mask),
    )
    single = estep.e_step(*args, var_max_iters=30, var_tol=1e-7)
    e_fn, m_fn = make_vocab_sharded_fns(mesh)
    sharded = jax.jit(
        lambda *a: e_fn(*a, var_max_iters=30, var_tol=1e-7)
    )(*args)
    np.testing.assert_allclose(np.asarray(sharded.gamma), np.asarray(single.gamma),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sharded.suff_stats), np.asarray(single.suff_stats),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(sharded.likelihood), float(single.likelihood),
                               rtol=1e-5)
    # sharded m_step matches the dense one
    lb_single = estep.m_step(single.suff_stats)
    lb_sharded = jax.jit(m_fn)(sharded.suff_stats)
    np.testing.assert_allclose(np.asarray(lb_sharded), np.asarray(lb_single),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mesh_shape,vocab_sharded", [
    ((8, 1), False),
    ((2, 4), True),
])
def test_full_training_parity(problem, mesh_shape, vocab_sharded):
    corpus, K, log_beta = problem
    cfg = LDAConfig(num_topics=K, em_max_iters=5, em_tol=0.0, batch_size=64,
                    min_bucket_len=64, estimate_alpha=True, seed=9)
    single = train_corpus(corpus, cfg)
    mesh = make_mesh(data=mesh_shape[0], model=mesh_shape[1])
    multi = train_corpus(corpus, cfg, mesh=mesh, vocab_sharded=vocab_sharded)
    np.testing.assert_allclose(
        [l for l, _ in multi.likelihoods], [l for l, _ in single.likelihoods],
        rtol=1e-4)
    np.testing.assert_allclose(np.exp(multi.log_beta), np.exp(single.log_beta),
                               atol=1e-4)
    np.testing.assert_allclose(multi.gamma, single.gamma, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(multi.alpha, single.alpha, rtol=1e-4)


def test_full_training_parity_vocab_sharded_dense(problem):
    """End-to-end config-4 plan: train_corpus with vocab_sharded=True and
    dense_em='on' must route through make_vocab_sharded_dense_e_step and
    reproduce the single-device trajectory (fresh start pinned: the
    single-device CPU run stays sparse, and warm start would change
    trajectories by design)."""
    corpus, K, log_beta = problem
    cfg = LDAConfig(num_topics=K, em_max_iters=5, em_tol=0.0, batch_size=64,
                    min_bucket_len=64, estimate_alpha=True, seed=9,
                    warm_start_gamma=False)
    single = train_corpus(corpus, cfg)
    mesh = make_mesh(data=2, model=4)
    import dataclasses

    multi = train_corpus(corpus, dataclasses.replace(cfg, dense_em="on"),
                         mesh=mesh, vocab_sharded=True)
    np.testing.assert_allclose(
        [l for l, _ in multi.likelihoods], [l for l, _ in single.likelihoods],
        rtol=1e-4)
    np.testing.assert_allclose(np.exp(multi.log_beta), np.exp(single.log_beta),
                               atol=1e-4)
    np.testing.assert_allclose(multi.gamma, single.gamma, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(multi.alpha, single.alpha, rtol=1e-4)


@pytest.mark.parametrize("mesh_shape", [(2, 4), (1, 8), (4, 2)])
def test_vocab_sharded_DENSE_e_step_parity(problem, mesh_shape):
    """Config-4 plan: the dense MXU E-step with the vocabulary sharded
    over `model` must reproduce the unsharded dense kernel — gamma,
    suff-stats, likelihood, and the warm-start path."""
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.parallel import make_vocab_sharded_dense_e_step

    corpus, K, log_beta = problem
    d, m = mesh_shape
    mesh = make_mesh(data=d, model=m)
    V = corpus.num_terms
    batches = make_batches(corpus, batch_size=64, min_bucket_len=64)
    b = batches[0]
    dense = np.asarray(dense_estep.densify(
        jnp.asarray(b.word_idx), jnp.asarray(b.counts), V
    ))
    w = dense.shape[1]
    # densify pads W to the 128-lane tile; pad further to the model axis
    # (here 128 % m == 0 already) and pad log_beta to match.
    assert w % m == 0
    lb_pad = np.pad(log_beta, ((0, 0), (0, w - V)),
                    constant_values=estep.LOG_ZERO)
    args = (
        jnp.asarray(lb_pad, jnp.float32),
        jnp.float32(2.5),
        jnp.asarray(dense),
        jnp.asarray(b.doc_mask),
    )
    kw = dict(var_max_iters=30, var_tol=1e-7)
    single = dense_estep.e_step_dense(*args, interpret=True, **kw)

    fn = make_vocab_sharded_dense_e_step(mesh)
    zeros_g = jnp.zeros((dense.shape[0], K), jnp.float32)
    sharded = jax.jit(
        lambda *a: fn(*a, zeros_g, jnp.asarray(0, jnp.int32), **kw)
    )(*args)
    np.testing.assert_allclose(np.asarray(sharded.gamma),
                               np.asarray(single.gamma),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sharded.suff_stats), np.asarray(single.suff_stats),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(sharded.likelihood),
                               float(single.likelihood), rtol=1e-5)
    np.testing.assert_allclose(float(sharded.alpha_ss),
                               float(single.alpha_ss), rtol=1e-5)

    # Warm start from the converged gamma: same fixed point, fewer
    # iterations, matching the unsharded kernel's warm path.
    warm_single = dense_estep.e_step_dense(
        *args, interpret=True, gamma_prev=single.gamma, warm=1, **kw
    )
    warm_sharded = jax.jit(
        lambda *a: fn(*a, sharded.gamma, jnp.asarray(1, jnp.int32), **kw)
    )(*args)
    assert int(warm_sharded.vi_iters) <= int(sharded.vi_iters)
    np.testing.assert_allclose(np.asarray(warm_sharded.gamma),
                               np.asarray(warm_single.gamma),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(warm_sharded.likelihood),
                               float(warm_single.likelihood), rtol=1e-5)


def test_vocab_sharded_dense_guards(problem):
    from oni_ml_tpu.parallel import make_vocab_sharded_dense_e_step

    corpus, K, log_beta = problem
    mesh = make_mesh(data=2, model=4)
    fn = make_vocab_sharded_dense_e_step(mesh)
    kw = dict(var_max_iters=5, var_tol=1e-6)
    g0 = jnp.zeros((5, K), jnp.float32)
    with pytest.raises(ValueError, match="not divisible by data"):
        fn(jnp.zeros((K, 128)), 2.5, jnp.zeros((5, 128)), jnp.ones((5,)),
           g0, 0, **kw)
    with pytest.raises(ValueError, match="not divisible by model"):
        fn(jnp.zeros((K, 130)), 2.5, jnp.zeros((8, 130)), jnp.ones((8,)),
           g0, 0, **kw)
    with pytest.raises(ValueError, match="width"):
        fn(jnp.zeros((K, 256)), 2.5, jnp.zeros((8, 128)), jnp.ones((8,)),
           g0, 0, **kw)


@pytest.mark.parametrize("wmajor", [False, True])
def test_data_parallel_dense_one_one_mesh_parity(problem, wmajor):
    """Interpret-mode variant of chip_smoke.py's shard_map kernel check: the
    shard_map'd dense kernel under a degenerate (1,1) mesh must equal
    the unwrapped kernel (on the real chip the same comparison runs
    Mosaic-compiled — the suite is CPU-pinned, so that half lives in
    chip_smoke.py's kernels leg)."""
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.parallel.sharded import make_data_parallel_dense_e_step

    corpus, K, log_beta = problem
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    batches = make_batches(corpus, batch_size=64, min_bucket_len=64)
    b = batches[0]
    V = corpus.num_terms
    dense = dense_estep.densify(
        jnp.asarray(b.word_idx), jnp.asarray(b.counts), V
    )
    if wmajor:
        dense = jnp.transpose(dense)
    lb = jnp.asarray(log_beta, jnp.float32)
    kw = dict(var_max_iters=20, var_tol=1e-6)
    plain = dense_estep.e_step_dense(
        lb, jnp.float32(2.5), dense, jnp.asarray(b.doc_mask),
        interpret=True, wmajor=wmajor, **kw
    )
    fn = make_data_parallel_dense_e_step(mesh, wmajor=wmajor)
    zeros_g = jnp.zeros((dense.shape[1 if wmajor else 0], K), jnp.float32)
    sharded = jax.jit(
        lambda *a: fn(*a, interpret=True, **kw)
    )(lb, jnp.float32(2.5), dense, jnp.asarray(b.doc_mask), zeros_g,
      jnp.asarray(0, jnp.int32))
    np.testing.assert_allclose(np.asarray(sharded.gamma),
                               np.asarray(plain.gamma),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sharded.suff_stats),
                               np.asarray(plain.suff_stats),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(sharded.likelihood),
                               float(plain.likelihood), rtol=1e-6)


def test_batch_size_divisibility_guard(problem):
    corpus, K, _ = problem
    mesh = make_mesh(data=8, model=1)
    cfg = LDAConfig(num_topics=K, batch_size=12)  # 12 % 8 != 0
    with pytest.raises(ValueError, match="not divisible"):
        train_corpus(corpus, cfg, mesh=mesh)


# ---------------------------------------------------------------------------
# The four-chip deployment (PR 29): data=4, documents sharded, beta and alpha
# replicated, every statistic summed over the shards in every EM iteration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def day():
    """A seeded corpus of 200 ragged documents and the settings group the
    benchmark's configurations hold (benchmarks/configs/flow20_dp4.json),
    at a width the CPU sweeps in seconds."""
    docs, _ = ref.make_synthetic_corpus(num_docs=200, num_terms=48,
                                        num_topics=3, seed=19)
    lda = dict(num_topics=4, alpha_init=2.5, estimate_alpha=True,
               var_max_iters=20, var_tol=1e-6, em_max_iters=6, em_tol=0.0,
               alpha_max_iters=8, warm_start=True, seed=23)
    return corpus_from_docs(docs, 48), lda


def _fit(corpus, lda, mesh=None):
    cfg = LDAConfig(
        num_topics=lda["num_topics"], alpha_init=lda["alpha_init"],
        estimate_alpha=lda["estimate_alpha"],
        alpha_max_iters=lda["alpha_max_iters"],
        em_max_iters=lda["em_max_iters"], em_tol=lda["em_tol"],
        var_max_iters=lda["var_max_iters"], var_tol=lda["var_tol"],
        warm_start_gamma=lda["warm_start"], seed=lda["seed"],
        batch_size=64, min_bucket_len=4, dense_em="on")
    return train_corpus(corpus, cfg, mesh=mesh)


def test_four_shard_fit_is_the_one_device_fit_and_the_plain_reference(day):
    """The deployment's guarantee: beta, alpha, the gamma of every
    document and every EM iteration's ELBO of the `data=4` fit are the
    one-device fit's (float32 sums in another order: 1e-4 of beta's and
    gamma's scale, 1e-6 of the ELBO) and the plain reference's
    (benchmarks/reference/lda_plain.py: another E-step stop and a float64
    Newton, so 2e-3 of gamma, 1e-3 of alpha, 1e-5 of the ELBO)."""
    from benchmarks.reference import lda_plain

    from oni_ml_tpu.telemetry import spans

    corpus, lda = day
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        four, one = _fit(corpus, lda, mesh), _fit(corpus, lda)
    # Both fits hold a group of two batches, which every shard's kernel
    # reads out of its slice of the group's stack in place; the three
    # single-batch groups are called directly.
    runners = [e["args"] for e in rec.events if e["name"] == "fit.runner"]
    assert [(r["batches"], r["stack_indexed_batches"], r["sliced_batches"])
            for r in runners] == [(5, 2, 3)] * 2
    assert four.plan["estep_kernel"]["value"].endswith("_shard_map")
    assert four.plan["estep_kernel"]["corpus_slices"] == 4
    assert four.em_iters == one.em_iters == lda["em_max_iters"]
    np.testing.assert_allclose(np.exp(four.log_beta), np.exp(one.log_beta),
                               atol=1e-6)
    np.testing.assert_allclose(four.gamma, one.gamma, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(four.alpha, one.alpha, rtol=1e-5)
    np.testing.assert_allclose([ll for ll, _ in four.likelihoods],
                               [ll for ll, _ in one.likelihoods], rtol=1e-6)

    plain = lda_plain.fit(corpus.doc_ptr, corpus.word_idx, corpus.counts,
                          corpus.num_terms, lda, stop_rule=False,
                          block_docs=64)
    np.testing.assert_allclose(np.exp(four.log_beta), np.exp(plain.log_beta),
                               atol=2e-4)
    np.testing.assert_allclose(four.gamma, plain.gamma, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(four.alpha, plain.alpha, rtol=1e-3)
    np.testing.assert_allclose([ll for ll, _ in four.likelihoods],
                               plain.likelihoods, rtol=1e-5)


@pytest.mark.parametrize("n", range(3))
@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_four_shards_read_a_batch_out_of_their_slices_of_the_stack(
        problem, wmajor, n):
    """Under the mesh the stack is `P(None, data, None)` (W-major
    `P(None, None, data)`) and the index replicated: every shard's kernel
    reads batch `n` of its own rows in place, and computes what it
    computes from that batch handed over alone."""
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.parallel.sharded import (
        bound, make_data_parallel_dense_e_step)

    corpus, K, log_beta = problem
    rng = np.random.default_rng(5)
    stack = jnp.stack([
        dense_estep.densify(
            jnp.asarray(rng.integers(0, corpus.num_terms, (64, 6 + 2 * i)),
                        jnp.int32),
            jnp.asarray(rng.integers(1, 4, (64, 6 + 2 * i)), jnp.float32),
            corpus.num_terms)
        for i in range(3)])
    if wmajor:
        stack = jnp.transpose(stack, (0, 2, 1))
    mask = np.ones((64,), np.float32)
    mask[-5:] = 0.0
    gamma_prev = jnp.asarray(rng.uniform(0.5, 3.0, (64, K)), jnp.float32)
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    fn = bound(make_data_parallel_dense_e_step(mesh, wmajor=wmajor),
               var_max_iters=20, var_tol=1e-6, interpret=True)
    assert fn._oni_stack_capable
    args = (jnp.asarray(log_beta, jnp.float32), jnp.float32(2.5))
    rest = (jnp.asarray(mask), gamma_prev, jnp.asarray(1, jnp.int32))
    alone = jax.jit(fn)(*args, stack[n], *rest)
    in_place = jax.jit(
        lambda whole, i: fn(*args, whole, *rest, batch_index=i))(
        stack, jnp.asarray(n, jnp.int32))
    # (Bit for bit on one device, tests/test_dense_estep.py; the two
    # `shard_map` programs the CPU compiles here differ in the last bit.)
    for field in alone._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(in_place, field)),
            np.asarray(getattr(alone, field)), rtol=2e-6, err_msg=field)
    assert int(in_place.doc_sweeps) == int(alone.doc_sweeps) > 64
    with pytest.raises(ValueError, match="not divisible"):
        fn(*args, stack[:, :62] if not wmajor else stack[:, :, :62],
           *rest, batch_index=0)


@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_four_shards_statistics_add_up_to_the_one_device_statistics(
        problem, wmajor):
    """What crosses the chips: each shard's [V, K] statistics, ELBO and
    alpha statistic over its quarter of a batch, summed; the sum is the
    whole batch's, and beta is normalised once, from the sum."""
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.parallel.sharded import make_data_parallel_dense_e_step

    corpus, K, log_beta = problem
    b, = make_batches(corpus, batch_size=64, min_bucket_len=64)
    dense = dense_estep.densify(jnp.asarray(b.word_idx),
                                jnp.asarray(b.counts), corpus.num_terms)
    mask = jnp.asarray(b.doc_mask)
    lb, alpha = jnp.asarray(log_beta, jnp.float32), jnp.float32(2.5)
    kw = dict(var_max_iters=20, var_tol=1e-6, interpret=True)
    fresh = (jnp.zeros((64, K), jnp.float32), jnp.asarray(0, jnp.int32))

    def one_device(rows):
        block = dense[rows]
        return dense_estep.e_step_dense(
            lb, alpha, block.T if wmajor else block, mask[rows],
            wmajor=wmajor, **kw)

    whole = one_device(slice(None))
    quarters = [one_device(slice(16 * i, 16 * (i + 1))) for i in range(4)]
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    fn = make_data_parallel_dense_e_step(mesh, wmajor=wmajor)
    summed = jax.jit(lambda *a: fn(*a, **kw))(
        lb, alpha, dense.T if wmajor else dense, mask, *fresh)

    for name in ("suff_stats", "likelihood", "alpha_ss"):
        parts = sum(np.asarray(getattr(q, name), np.float64)
                    for q in quarters)
        np.testing.assert_allclose(np.asarray(getattr(summed, name)), parts,
                                   rtol=1e-5, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(getattr(summed, name)),
                                   np.asarray(getattr(whole, name)),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(
        np.asarray(summed.gamma),
        np.concatenate([np.asarray(q.gamma) for q in quarters]),
        rtol=1e-6, atol=1e-6)
    beta = np.exp(np.asarray(estep.m_step(summed.suff_stats), np.float64))
    np.testing.assert_allclose(beta.sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        beta, np.exp(np.asarray(estep.m_step(whole.suff_stats), np.float64)),
        rtol=1e-5, atol=1e-9)
    # a shard's statistics alone are not the batch's: the exchange matters
    assert not np.allclose(np.asarray(quarters[0].suff_stats),
                           np.asarray(whole.suff_stats), rtol=0.1)
