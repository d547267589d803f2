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
