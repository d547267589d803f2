"""Dense-corpus E-step (ops/dense_estep.py) vs the sparse reference path.

The dense kernel must reproduce estep.e_step exactly up to float32
reassociation: same fixed point, same convergence rule, same ELBO and
suff-stats semantics.  Runs in Pallas interpret mode on the CPU backend
(tests/conftest.py), mirroring how test_pallas_estep.py validates the
sparse kernel.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import Batch
from oni_ml_tpu.models import fused
from oni_ml_tpu.ops import dense_estep, estep


def _random_batch(rng, b, l, v, n_masked=0):
    word_idx = rng.integers(0, v, size=(b, l)).astype(np.int32)
    counts = rng.integers(1, 5, size=(b, l)).astype(np.float32)
    # Ragged tail: zero-count padding tokens on some docs.
    for i in range(b // 3):
        pad = rng.integers(1, l)
        word_idx[i, pad:] = 0
        counts[i, pad:] = 0.0
    doc_mask = np.ones((b,), np.float32)
    if n_masked:
        doc_mask[-n_masked:] = 0.0
        counts[-n_masked:] = 0.0
        word_idx[-n_masked:] = 0
    return (
        jnp.asarray(word_idx),
        jnp.asarray(counts),
        jnp.asarray(doc_mask),
    )


def _log_beta(rng, k, v):
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    return jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )


def test_densify_matches_loop():
    rng = np.random.default_rng(0)
    b, l, v = 8, 16, 50
    word_idx, counts, _ = _random_batch(rng, b, l, v)
    dense = np.asarray(dense_estep.densify(word_idx, counts, v))
    assert dense.shape == (b, dense_estep.padded_width(v))
    expect = np.zeros((b, v), np.float32)
    for i in range(b):
        for j in range(l):
            expect[i, int(word_idx[i, j])] += float(counts[i, j])
    np.testing.assert_allclose(dense[:, :v], expect, rtol=0, atol=0)
    assert dense[:, v:].sum() == 0.0


@pytest.mark.parametrize(
    "b,l,v,k,n_masked",
    [(16, 32, 300, 4, 0), (32, 16, 130, 7, 5), (8, 8, 128, 3, 2)],
)
def test_dense_parity_vs_xla(b, l, v, k, n_masked):
    rng = np.random.default_rng(b * 1000 + v)
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)

    ref = estep.e_step(
        log_beta, alpha, word_idx, counts, doc_mask,
        var_max_iters=20, var_tol=1e-6, backend="xla",
    )
    dense = dense_estep.densify(word_idx, counts, v)
    got = dense_estep.e_step_dense(
        log_beta, alpha, dense, doc_mask,
        var_max_iters=20, var_tol=1e-6, interpret=True,
    )

    np.testing.assert_allclose(
        np.asarray(got.gamma), np.asarray(ref.gamma), rtol=2e-3, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(got.suff_stats), np.asarray(ref.suff_stats),
        rtol=2e-3, atol=1e-4,
    )
    np.testing.assert_allclose(
        float(got.likelihood), float(ref.likelihood), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(got.alpha_ss), float(ref.alpha_ss), rtol=1e-4
    )


@pytest.mark.parametrize(
    "b,l,v,k,n_masked",
    [(16, 32, 300, 4, 0), (32, 16, 130, 7, 5), (8, 8, 128, 3, 2)],
)
def test_wmajor_parity_vs_xla(b, l, v, k, n_masked):
    """The W-major (transposed-corpus) kernel — the production default —
    must match the sparse XLA reference exactly like the row-major one."""
    rng = np.random.default_rng(b * 1000 + v + 1)
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)

    ref = estep.e_step(
        log_beta, alpha, word_idx, counts, doc_mask,
        var_max_iters=20, var_tol=1e-6, backend="xla",
    )
    dense_t = dense_estep.densify(word_idx, counts, v).T
    got = dense_estep.e_step_dense(
        log_beta, alpha, dense_t, doc_mask,
        var_max_iters=20, var_tol=1e-6, interpret=True, wmajor=True,
    )
    np.testing.assert_allclose(
        np.asarray(got.gamma), np.asarray(ref.gamma), rtol=2e-3, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(got.suff_stats), np.asarray(ref.suff_stats),
        rtol=2e-3, atol=1e-4,
    )
    np.testing.assert_allclose(
        float(got.likelihood), float(ref.likelihood), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(got.alpha_ss), float(ref.alpha_ss), rtol=1e-4
    )


def test_masked_docs_are_inert():
    """A masked doc must contribute nothing to suff stats / likelihood and
    converge to gamma = alpha (its dense row is all zeros)."""
    rng = np.random.default_rng(3)
    b, l, v, k = 8, 8, 140, 3
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=3)
    log_beta = _log_beta(rng, k, v)
    dense = dense_estep.densify(word_idx, counts, v)
    got = dense_estep.e_step_dense(
        log_beta, jnp.float32(1.5), dense, doc_mask,
        var_max_iters=10, var_tol=1e-6, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got.gamma)[-3:], 1.5, rtol=1e-6
    )


def test_backend_dispatch():
    rng = np.random.default_rng(7)
    b, l, v, k = 8, 8, 140, 3
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    log_beta = _log_beta(rng, k, v)
    ref = estep.e_step(
        log_beta, 2.5, word_idx, counts, doc_mask,
        var_max_iters=10, var_tol=1e-6, backend="xla",
    )
    got = estep.e_step(
        log_beta, 2.5, word_idx, counts, doc_mask,
        var_max_iters=10, var_tol=1e-6, backend="dense",
    )
    np.testing.assert_allclose(
        np.asarray(got.gamma), np.asarray(ref.gamma), rtol=2e-3, atol=1e-3
    )

    with pytest.raises(ValueError, match="unknown E-step backend"):
        estep.e_step(
            log_beta, 2.5, word_idx, counts, doc_mask,
            var_max_iters=10, var_tol=1e-6, backend="palas",
        )
    # Forced dense on an infeasible shape names the problem.
    with pytest.raises(ValueError, match="dense E-step forced"):
        estep.e_step(
            _log_beta(rng, 3, 7), 2.5,
            word_idx[:5], counts[:5], doc_mask[:5],
            var_max_iters=10, var_tol=1e-6, backend="dense",
        )


def test_fused_runner_dense_groups_match_sparse():
    """The fused chunk runner must produce the same EM trajectory from
    densified groups as from sparse groups."""
    rng = np.random.default_rng(11)
    b, l, v, k = 16, 16, 260, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)

    sparse_groups = (
        (word_idx[None], counts[None], doc_mask[None]),
    )
    dense = dense_estep.densify(word_idx, counts, v)
    dense_groups = ((dense[None], doc_mask[None]),)

    run = fused.make_chunk_runner(
        num_docs=b - 2, num_topics=k, num_terms=v, chunk=4,
        var_max_iters=20, var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
    )
    run_w = fused.make_chunk_runner(
        num_docs=b - 2, num_topics=k, num_terms=v, chunk=4,
        var_max_iters=20, var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
        dense_wmajor=True,
    )
    wmajor_groups = ((dense.T[None], doc_mask[None]),)
    r_sparse = run(log_beta, alpha, jnp.float32(np.nan), sparse_groups, 4)
    r_dense = run(log_beta, alpha, jnp.float32(np.nan), dense_groups, 4)
    r_wmajor = run_w(log_beta, alpha, jnp.float32(np.nan), wmajor_groups, 4)

    for r in (r_dense, r_wmajor):
        np.testing.assert_allclose(
            np.asarray(r.lls), np.asarray(r_sparse.lls), rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(r.log_beta), np.asarray(r_sparse.log_beta),
            rtol=5e-3, atol=5e-3,
        )
        np.testing.assert_allclose(
            float(r.alpha), float(r_sparse.alpha), rtol=1e-3
        )
    # gammas come back doc-major from both dense layouts
    np.testing.assert_allclose(
        np.asarray(r_wmajor.gammas[0]), np.asarray(r_dense.gammas[0]),
        rtol=2e-3, atol=1e-3,
    )


def test_trainer_dense_mode_matches_sparse():
    """LDATrainer end-to-end with dense_em='on' vs 'off' on a tiny corpus."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(5)
    b, l, v = 16, 16, 200
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    batch = Batch(
        word_idx=np.asarray(word_idx),
        counts=np.asarray(counts),
        doc_mask=np.asarray(doc_mask),
        doc_index=np.arange(b),
    )
    results = {}
    for mode in ("on", "off"):
        cfg = LDAConfig(
            num_topics=4, em_max_iters=6, em_tol=0.0,
            var_max_iters=20, fused_em_chunk=3, seed=1, dense_em=mode,
            # This test pins dense-vs-sparse NUMERICS; warm start (dense
            # only) would make the trajectories differ by design.
            warm_start_gamma=False,
        )
        trainer = LDATrainer(cfg, num_terms=v)
        results[mode] = trainer.fit([batch], num_docs=b - 2)

    on, off = results["on"], results["off"]
    np.testing.assert_allclose(on.log_beta, off.log_beta, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(
        [ll for ll, _ in on.likelihoods],
        [ll for ll, _ in off.likelihoods],
        rtol=1e-4,
    )


def test_explicit_block_must_divide_batch():
    rng = np.random.default_rng(2)
    b, l, v, k = 16, 8, 140, 3
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    dense = dense_estep.densify(word_idx, counts, v)
    with pytest.raises(ValueError, match="does not divide"):
        dense_estep.e_step_dense(
            _log_beta(rng, k, v), 2.5, dense, doc_mask,
            var_max_iters=5, var_tol=1e-6, block=12, interpret=True,
        )


def test_dense_em_typo_raises():
    from oni_ml_tpu.models.lda import LDATrainer

    trainer = LDATrainer(
        LDAConfig(num_topics=4, dense_em="true"), num_terms=200
    )
    batch = Batch(
        word_idx=np.zeros((16, 8), np.int32),
        counts=np.zeros((16, 8), np.float32),
        doc_mask=np.ones((16,), np.float32),
        doc_index=np.arange(16),
    )
    with pytest.raises(ValueError, match="dense_em"):
        trainer._plan_estep([batch])


def test_forced_dense_with_vocab_sharding_raises():
    """Dense mode composes with a data mesh but needs the full
    vocabulary; a vocab-sharded trainer must reject it loudly."""
    from oni_ml_tpu.models.lda import LDATrainer
    from oni_ml_tpu.parallel import make_mesh

    with pytest.warns(UserWarning, match="left idle"):
        mesh = make_mesh(data=2, model=2)   # 2x2 of the 8 virtual devices
    trainer = LDATrainer(
        LDAConfig(num_topics=4, dense_em="on"), num_terms=200, mesh=mesh,
        vocab_sharded=True,
    )
    batch = Batch(
        word_idx=np.zeros((16, 8), np.int32),
        counts=np.zeros((16, 8), np.float32),
        doc_mask=np.ones((16,), np.float32),
        doc_index=np.arange(16),
    )
    with pytest.raises(ValueError, match="vocabulary is sharded"):
        trainer._plan_estep([batch])


def test_dense_sharded_matches_single_device():
    """The shard_map'd dense E-step (data-parallel mesh) must reproduce
    the single-device dense result: psum'd suff-stats/likelihood equal,
    gamma identical per document."""
    import jax

    from oni_ml_tpu.parallel import make_mesh, sharded

    rng = np.random.default_rng(31)
    b, l, v, k = 32, 16, 260, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=3)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)
    dense = dense_estep.densify(word_idx, counts, v)

    single = dense_estep.e_step_dense(
        log_beta, alpha, dense, doc_mask,
        var_max_iters=15, var_tol=1e-6, interpret=True,
    )
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    fn = sharded.make_data_parallel_dense_e_step(mesh, wmajor=False)
    got = fn(
        log_beta, alpha, dense, doc_mask,
        jnp.zeros((b, k), jnp.float32), jnp.asarray(0, jnp.int32),
        var_max_iters=15, var_tol=1e-6, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got.gamma), np.asarray(single.gamma),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(got.suff_stats), np.asarray(single.suff_stats),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        float(got.likelihood), float(single.likelihood), rtol=1e-6
    )

    # W-major layout through the same wrapper
    fn_w = sharded.make_data_parallel_dense_e_step(mesh, wmajor=True)
    got_w = fn_w(
        log_beta, alpha, dense.T, doc_mask,
        jnp.zeros((b, k), jnp.float32), jnp.asarray(0, jnp.int32),
        var_max_iters=15, var_tol=1e-6, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_w.gamma), np.asarray(single.gamma),
        rtol=2e-3, atol=1e-3,
    )
    np.testing.assert_allclose(
        float(got_w.likelihood), float(single.likelihood), rtol=1e-5
    )


def test_trainer_dense_data_mesh_matches_unsharded():
    """End-to-end: train_corpus with dense forced on a data mesh must
    match the unsharded dense run's trajectory."""
    import jax

    from oni_ml_tpu.models import train_corpus
    from oni_ml_tpu.parallel import make_mesh

    import reference_lda as ref
    from test_lda import corpus_from_docs

    docs, _ = ref.make_synthetic_corpus(num_docs=64, num_terms=200,
                                        num_topics=3, seed=8)
    corpus = corpus_from_docs(docs, 200)
    base = LDAConfig(num_topics=3, em_max_iters=6, em_tol=0.0,
                     batch_size=32, min_bucket_len=64, seed=4,
                     fused_em_chunk=3, dense_em="on")
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    res_mesh = train_corpus(corpus, base, mesh=mesh)
    res_single = train_corpus(corpus, base)
    np.testing.assert_allclose(
        [ll for ll, _ in res_mesh.likelihoods],
        [ll for ll, _ in res_single.likelihoods],
        rtol=1e-4,
    )
    np.testing.assert_allclose(
        res_mesh.log_beta, res_single.log_beta, rtol=5e-3, atol=5e-3
    )


def test_env_dense_does_not_leak_into_auto_dispatch(monkeypatch):
    """ONI_ML_TPU_ESTEP=dense is a driver hint; per-call e_step auto must
    not densify inline (that would re-scatter every EM iteration) and must
    not raise on shapes the dense path can't block."""
    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "dense")
    rng = np.random.default_rng(9)
    # B=5 has no feasible dense block (not divisible by 8): auto dispatch
    # must still succeed via the sparse paths.
    word_idx, counts, doc_mask = _random_batch(rng, 5, 8, 60, 0)
    log_beta = _log_beta(rng, 3, 60)
    res = estep.e_step(
        log_beta, 2.5, word_idx, counts, doc_mask,
        var_max_iters=5, var_tol=1e-6,
    )
    ref = estep.e_step(
        log_beta, 2.5, word_idx, counts, doc_mask,
        var_max_iters=5, var_tol=1e-6, backend="xla",
    )
    np.testing.assert_allclose(
        np.asarray(res.gamma), np.asarray(ref.gamma), rtol=1e-5
    )


def test_scoped_vmem_kib_and_multibatch_groups():
    """The scoped-VMEM compiler option must be computable for feasible
    shapes (XLA drops the kernel's own limit inside stacked-group scans)
    and the fused runner must accept stacked NB>=2 dense groups."""
    kib = dense_estep.scoped_vmem_kib(1024, 13530, 20)
    assert kib is not None and kib >= 32 * 1024
    assert dense_estep.scoped_vmem_kib(5, 100, 4) is None  # infeasible

    rng = np.random.default_rng(21)
    b, l, v, k = 16, 8, 140, 3
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    dense = dense_estep.densify(word_idx, counts, v)
    groups = ((jnp.stack([dense, dense]), jnp.stack([doc_mask, doc_mask])),)
    # The xla_tpu_* option itself only exists on the TPU compiler; on the
    # CPU test backend exercise the plumbing with a portable no-op option.
    run = fused.make_chunk_runner(
        num_docs=2 * b, num_topics=k, num_terms=v, chunk=2,
        var_max_iters=5, var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
        compiler_options={},
    )
    res = run(_log_beta(rng, k, v), jnp.float32(2.5), jnp.float32(np.nan),
              groups, 2)
    assert np.isfinite(float(res.lls[-1]))


def test_use_dense_auto_is_off_on_cpu():
    from oni_ml_tpu.models.lda import LDATrainer

    cfg = LDAConfig(num_topics=4, dense_em="auto")
    trainer = LDATrainer(cfg, num_terms=200)
    batch = Batch(
        word_idx=np.zeros((16, 8), np.int32),
        counts=np.zeros((16, 8), np.float32),
        doc_mask=np.ones((16,), np.float32),
        doc_index=np.arange(16),
    )
    # CPU backend in tests: the token lists stay, nothing is stored dense
    plan = trainer._plan_estep([batch])
    assert (plan.family, plan.kernel, plan.store) == ("tokens", "xla", None)


@pytest.mark.parametrize("wmajor", [False, True])
def test_warm_start_converges_faster_to_same_point(wmajor):
    """Seeding the fixed point with the converged gamma must finish in
    fewer inner iterations and land on the same posterior (the update
    operator is unchanged; only the start moves)."""
    rng = np.random.default_rng(42)
    b, l, v, k = 16, 32, 300, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)
    dense = dense_estep.densify(word_idx, counts, v)
    if wmajor:
        dense = dense.T

    fresh = dense_estep.e_step_dense(
        log_beta, alpha, dense, doc_mask,
        var_max_iters=50, var_tol=1e-6, interpret=True, wmajor=wmajor,
    )
    warm = dense_estep.e_step_dense(
        log_beta, alpha, dense, doc_mask,
        var_max_iters=50, var_tol=1e-6, interpret=True, wmajor=wmajor,
        gamma_prev=fresh.gamma, warm=1,
    )
    assert int(warm.vi_iters) < int(fresh.vi_iters), (
        int(warm.vi_iters), int(fresh.vi_iters)
    )
    np.testing.assert_allclose(
        np.asarray(warm.gamma), np.asarray(fresh.gamma), rtol=1e-3, atol=1e-3
    )
    np.testing.assert_allclose(
        float(warm.likelihood), float(fresh.likelihood), rtol=1e-5
    )

    # warm=0 with a garbage gamma_prev must reproduce the fresh run
    # exactly (the flag, not the buffer, decides).
    gated = dense_estep.e_step_dense(
        log_beta, alpha, dense, doc_mask,
        var_max_iters=50, var_tol=1e-6, interpret=True, wmajor=wmajor,
        gamma_prev=jnp.full_like(fresh.gamma, 7.0), warm=0,
    )
    np.testing.assert_array_equal(
        np.asarray(gated.gamma), np.asarray(fresh.gamma)
    )


def test_fused_warm_start_matches_fresh_trajectory():
    """warm_start=True reaches the same EM optimum; likelihoods track the
    fresh-start run closely at every iteration."""
    rng = np.random.default_rng(17)
    b, l, v, k = 16, 16, 260, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)
    dense = dense_estep.densify(word_idx, counts, v)
    groups = ((dense[None], doc_mask[None]),)

    runs = {}
    for warm in (False, True):
        run = fused.make_chunk_runner(
            num_docs=b - 2, num_topics=k, num_terms=v, chunk=8,
            var_max_iters=20, var_tol=1e-6, em_tol=0.0,
            estimate_alpha=True, warm_start=warm,
        )
        runs[warm] = run(log_beta, alpha, jnp.float32(np.nan), groups, 8)

    # Mid-trajectory values differ by O(var_tol effects) — the fixed
    # point is reached from a different start — but must track closely
    # and agree tightly once converged.
    np.testing.assert_allclose(
        np.asarray(runs[True].lls), np.asarray(runs[False].lls), rtol=1e-3
    )
    np.testing.assert_allclose(
        float(runs[True].lls[-1]), float(runs[False].lls[-1]), rtol=1e-5
    )
    # Compare topics in probability space: log-space values of ~e^-55
    # mass words are numerically meaningless between equally-converged
    # runs.
    np.testing.assert_allclose(
        np.exp(np.asarray(runs[True].log_beta)),
        np.exp(np.asarray(runs[False].log_beta)),
        rtol=1e-2, atol=1e-5,
    )


@pytest.mark.parametrize("wmajor", [False, True])
def test_bf16_precision_close_and_validated(wmajor):
    """dense_precision="bf16" stores the fixed-point matmul operands in
    bfloat16.  On TPU that is bit-identical to the default (XLA's
    DEFAULT matmul precision already truncates f32 MXU inputs to bf16);
    on the CPU test backend it emulates that truncation, so the result
    must track the exact-f32 path within bf16 input-rounding error
    while the f32 tail keeps the likelihood tight."""
    rng = np.random.default_rng(5)
    b, l, v, k = 16, 32, 260, 5
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    log_beta = _log_beta(rng, k, v)
    dense = dense_estep.densify(word_idx, counts, v)
    dense = dense.T if wmajor else dense

    kw = dict(var_max_iters=20, var_tol=1e-6, interpret=True,
              wmajor=wmajor)
    exact = dense_estep.e_step_dense(
        log_beta, jnp.float32(2.5), dense, doc_mask, **kw
    )
    half = dense_estep.e_step_dense(
        log_beta, jnp.float32(2.5), dense, doc_mask,
        precision="bf16", **kw
    )
    np.testing.assert_allclose(
        np.asarray(half.gamma), np.asarray(exact.gamma),
        rtol=5e-2, atol=5e-2,
    )
    np.testing.assert_allclose(
        float(half.likelihood), float(exact.likelihood), rtol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(half.suff_stats), np.asarray(exact.suff_stats),
        rtol=0.1, atol=5e-3,
    )

    with pytest.raises(ValueError, match="dense E-step precision"):
        dense_estep.e_step_dense(
            log_beta, jnp.float32(2.5), dense, doc_mask,
            precision="fp8", **kw
        )


def test_bf16_refused_under_matmul_precision_override():
    """The 'bf16 changes no results' promise only holds under XLA's
    DEFAULT matmul precision; a process-wide "highest"/"float32"
    override must be refused, not silently degraded."""
    import jax

    jax.config.update("jax_default_matmul_precision", "float32")
    try:
        with pytest.raises(ValueError, match="DEFAULT matmul precision"):
            dense_estep._check_precision("bf16")
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    dense_estep._check_precision("bf16")  # back to DEFAULT: accepted


def test_trainer_dense_precision_bf16_tracks_f32():
    """LDAConfig.dense_precision='bf16' through the full batch trainer:
    on the CPU test backend it emulates the TPU's MXU input truncation,
    so the trained model must track the f32 run within bf16 rounding
    while the EM structure (iteration count, finite lls) is identical."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(6)
    b, l, v = 16, 16, 200
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v)
    batch = Batch(
        word_idx=np.asarray(word_idx),
        counts=np.asarray(counts),
        doc_mask=np.asarray(doc_mask),
        doc_index=np.arange(b),
    )
    results = {}
    for prec in ("f32", "bf16"):
        cfg = LDAConfig(
            num_topics=4, em_max_iters=5, em_tol=0.0,
            var_max_iters=20, fused_em_chunk=3, seed=1,
            dense_em="on", dense_precision=prec,
            # This test pins bf16-vs-f32 NUMERICS; warm start compounds
            # start-point differences across EM iterations by design.
            warm_start_gamma=False,
        )
        results[prec] = LDATrainer(cfg, num_terms=v).fit([batch], num_docs=b)

    f32, bf16 = results["f32"], results["bf16"]
    assert len(f32.likelihoods) == len(bf16.likelihoods)
    np.testing.assert_allclose(
        bf16.log_beta, f32.log_beta, rtol=5e-2, atol=5e-2
    )
    np.testing.assert_allclose(
        [ll for ll, _ in bf16.likelihoods],
        [ll for ll, _ in f32.likelihoods],
        rtol=1e-2,
    )


@pytest.mark.parametrize("wmajor", [False, True])
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_bf16_corpus_storage_is_bit_identical(wmajor, precision):
    """A bf16-STORED corpus (counts <= 256: exact in bf16) must produce
    bitwise-identical results to the f32-stored corpus under either
    operand precision — the storage dtype only changes HBM traffic."""
    rng = np.random.default_rng(23)
    b, l, v, k = 16, 16, 260, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    log_beta = _log_beta(rng, k, v)
    kw = dict(var_max_iters=20, var_tol=1e-6, interpret=True,
              wmajor=wmajor, precision=precision)

    d32 = dense_estep.densify(word_idx, counts, v)
    d16 = dense_estep.densify(word_idx, counts, v, dtype=jnp.bfloat16)
    assert d16.dtype == jnp.bfloat16
    # Exactness precondition: every stored count round-trips.
    np.testing.assert_array_equal(
        np.asarray(d16, np.float32), np.asarray(d32)
    )
    if wmajor:
        d32, d16 = d32.T, d16.T

    r32 = dense_estep.e_step_dense(log_beta, jnp.float32(2.5), d32,
                                   doc_mask, **kw)
    r16 = dense_estep.e_step_dense(log_beta, jnp.float32(2.5), d16,
                                   doc_mask, **kw)
    assert r16.gamma.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(r16.gamma),
                                  np.asarray(r32.gamma))
    np.testing.assert_array_equal(np.asarray(r16.suff_stats),
                                  np.asarray(r32.suff_stats))
    assert float(r16.likelihood) == float(r32.likelihood)


def test_corpus_dtype_decision():
    assert dense_estep.corpus_dtype(4.0, "bf16") == jnp.bfloat16
    assert dense_estep.corpus_dtype(256.0, "bf16") == jnp.bfloat16
    assert dense_estep.corpus_dtype(257.0, "bf16") == jnp.float32
    assert dense_estep.corpus_dtype(4.0, "f32") == jnp.float32


def test_max_dense_cell_sums_duplicates():
    """The bf16 gate must bound DENSIFIED cells: duplicate (doc, word)
    tokens sum in densify — the DUPFACTOR=1000 feedback path makes a
    ~1000-count cell out of count-1 tokens, which a max over raw counts
    never sees."""
    word_idx = np.zeros((2, 300), np.int32)
    counts = np.ones((2, 300), np.float32)
    word_idx[1] = np.arange(300) % 297      # doc 1: mostly distinct
    cell_max = dense_estep.max_dense_cell(word_idx, counts)
    assert cell_max == 300.0                # doc 0: one word, 300 tokens
    assert float(np.max(counts)) == 1.0     # the raw-count max is blind
    # ...and the decision falls back to exact f32 storage.
    assert dense_estep.corpus_dtype(cell_max, "bf16") == jnp.float32
    # Consistency with the real scatter:
    dense = np.asarray(
        dense_estep.densify(jnp.asarray(word_idx), jnp.asarray(counts), 300),
        np.float32,
    )
    assert dense.max() == cell_max


class _Unreadable:
    """A host batch whose arrays cannot be read."""

    @property
    def counts(self):
        raise AssertionError("the storage gate read a token")

    word_idx = counts


def _gate_batch(word_idx, counts):
    word_idx = np.asarray(word_idx, np.int32)
    b = word_idx.shape[0]
    return Batch(word_idx=word_idx, counts=np.asarray(counts, np.float32),
                 doc_mask=np.ones((b,), np.float32), doc_index=np.arange(b))


def _gate_case(name):
    """(batches, precision) of a named storage-gate case; every case but
    `f32` has a quiet first batch in front of the one that decides."""
    quiet = _gate_batch(np.arange(32).reshape(4, 8), np.ones((4, 8)))
    w = np.tile(np.arange(300), (2, 1))
    c = np.ones((2, 300))
    if name == "f32":
        return [_Unreadable(), _Unreadable()], "f32"
    if name == "raw_257":
        c[1, 7] = 257.0
    elif name == "row_sums_small":
        w, c = w[:, :256], c[:, :256]       # every row sums to 256
    elif name == "dupfactor":
        w = np.zeros((2, 1000), np.int64)   # 1,000 count-1 tokens, one word
        c = np.ones((2, 1000))
        w[1] = np.arange(1000)
    elif name == "row_300_cells_small":
        w[0, 150:] = w[0, :150]             # every cell of row 0 is 2
        c[1, 299] = 0.0
    return [quiet, _gate_batch(w, c)], "bf16"


@pytest.mark.parametrize("case,scan,dtype", [
    ("f32", "none", jnp.float32),
    ("raw_257", "bounds", jnp.float32),
    ("row_sums_small", "bounds", jnp.bfloat16),
    ("dupfactor", "exact", jnp.float32),
    ("row_300_cells_small", "exact", jnp.bfloat16),
])
def test_corpus_store_dtype_reads_only_what_decides(case, scan, dtype,
                                                    monkeypatch):
    """The storage gate: f32 reads no token; bf16 is decided by the
    largest raw count or the largest row sum where one of them can, and
    only rows between the two bounds reach the exact reader."""
    batches, precision = _gate_case(case)
    exact_rows = []
    reader = dense_estep.max_dense_cell

    def counting_reader(word_idx, counts):
        exact_rows.append(np.shape(word_idx)[0])
        return reader(word_idx, counts)

    monkeypatch.setattr(dense_estep, "max_dense_cell", counting_reader)
    got, cell_scan, scan_tokens = dense_estep.corpus_store_dtype(
        batches, precision)
    assert (got, cell_scan) == (dtype, scan)
    if scan == "none":
        assert scan_tokens == 0 and not exact_rows
        return
    tokens = [b.counts.size for b in batches]
    assert got == dense_estep.corpus_dtype(
        max(reader(b.word_idx, b.counts) for b in batches), precision)
    if case == "raw_257":                   # one pass, up to the culprit
        assert scan_tokens == sum(tokens) and not exact_rows
    elif scan == "bounds":                  # the max pass and the row sums
        assert scan_tokens == 2 * sum(tokens) and not exact_rows
    else:                                   # plus the undecided rows alone
        over = [int((b.counts.sum(axis=1) > 256).sum()) for b in batches]
        assert exact_rows == [n for n in over if n]
        assert scan_tokens == 2 * sum(tokens) + sum(
            n * b.counts.shape[1] for n, b in zip(over, batches))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_corpus_store_dtype_is_the_exact_reader_on_random_batches(precision):
    """200 random batches with duplicates planted around the 256 line:
    the gate's dtype is corpus_dtype of the exact reader's maximum."""
    rng = np.random.default_rng(256)
    seen = set()
    for _ in range(200):
        b, l, v = rng.integers(1, 9), rng.integers(1, 40), rng.integers(1, 50)
        top = int(rng.choice([1, 3, 40, 256, 257, 300]))
        w = rng.integers(0, v, (b, l))
        c = rng.integers(0, top + 1, (b, l)).astype(np.float64)
        for _ in range(rng.integers(0, 4)):     # a word repeated in a row
            row, n = rng.integers(0, b), rng.integers(1, l + 1)
            w[row, :n] = w[row, 0]
            c[row, :n] = rng.choice([1.0, 256.0 / n, 300.0 // n + 1])
        batches = [_gate_batch(w, np.floor(c))]
        cells = np.zeros((b, v))
        np.add.at(cells, (np.arange(b)[:, None], w), batches[0].counts)
        assert dense_estep.max_dense_cell(w, batches[0].counts) == cells.max()
        if rng.random() < 0.5:
            batches.insert(0, _gate_batch(np.zeros((2, 3)), np.ones((2, 3))))
        want = dense_estep.corpus_dtype(
            max(dense_estep.max_dense_cell(x.word_idx, x.counts)
                for x in batches), precision)
        got, cell_scan, _ = dense_estep.corpus_store_dtype(batches, precision)
        assert got == want, (w, c)
        seen.add((cell_scan, got))
    if precision == "f32":
        assert seen == {("none", jnp.float32)}
    else:
        assert seen == {(scan, dtype) for scan in ("bounds", "exact")
                        for dtype in (jnp.float32, jnp.bfloat16)}


def test_vocab_sharded_dense_bf16_corpus_matches():
    """The XLA-level vocab-sharded dense plan with a bf16-stored corpus
    must match its f32-stored run bitwise (f32-promoting consumers)."""
    import jax

    from oni_ml_tpu.parallel import make_mesh, make_vocab_sharded_dense_e_step

    rng = np.random.default_rng(29)
    b, l, v, k = 16, 16, 256, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    log_beta = _log_beta(rng, k, v)
    mesh = make_mesh(data=2, model=4)
    fn = make_vocab_sharded_dense_e_step(mesh)
    kw = dict(var_max_iters=15, var_tol=1e-6)
    g0 = jnp.zeros((b, k), jnp.float32)
    res = {}
    for dt in (None, jnp.bfloat16):
        dense = dense_estep.densify(word_idx, counts, v, width=v, dtype=dt)
        res[dt] = jax.jit(
            lambda lb, a, d, m: fn(lb, a, d, m, g0,
                                   jnp.asarray(0, jnp.int32), **kw)
        )(log_beta, jnp.float32(2.5), dense, doc_mask)
    np.testing.assert_array_equal(np.asarray(res[None].gamma),
                                  np.asarray(res[jnp.bfloat16].gamma))
    assert float(res[None].likelihood) == float(res[jnp.bfloat16].likelihood)


def _compact_groups_for(word_idx, counts, doc_mask, wmajor=False):
    """Hand-build a one-batch compact-dense group the way
    fused.compact_stack_batches does: sorted unique vocab, 128-lane
    padded width, sentinel word-0 padding in the vocab map."""
    u = np.unique(np.asarray(word_idx))
    wc = -(-len(u) // 128) * 128
    vmap_ = np.zeros(wc, np.int32)
    vmap_[: len(u)] = u
    local = np.searchsorted(u, np.asarray(word_idx)).astype(np.int32)
    dense_local = dense_estep.densify(
        jnp.asarray(local), counts, wc, width=wc
    )
    if wmajor:
        dense_local = dense_local.T
    return (
        (dense_local[None], doc_mask[None], jnp.asarray(vmap_)[None]),
    ), wc, len(u)


def test_fused_runner_compact_groups_match_sparse():
    """Compact-vocab dense groups (per-batch vocabulary remap +
    suff-stats scatter-back) must reproduce the sparse EM trajectory —
    both layouts, with real sentinel padding in the vocab map."""
    rng = np.random.default_rng(13)
    b, l, v, k = 16, 16, 700, 4
    word_idx, counts, doc_mask = _random_batch(rng, b, l, v, n_masked=2)
    log_beta = _log_beta(rng, k, v)
    alpha = jnp.float32(2.5)

    sparse_groups = ((word_idx[None], counts[None], doc_mask[None]),)
    compact_groups, wc, n_unique = _compact_groups_for(
        word_idx, counts, doc_mask
    )
    assert wc < v            # actually compacted
    assert wc > n_unique     # sentinel-padded columns exist

    run = fused.make_chunk_runner(
        num_docs=b - 2, num_topics=k, num_terms=v, chunk=4,
        var_max_iters=20, var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
    )
    r_sparse = run(log_beta, alpha, jnp.float32(np.nan), sparse_groups, 4)
    r_compact = run(log_beta, alpha, jnp.float32(np.nan), compact_groups, 4)

    compact_groups_w, _, _ = _compact_groups_for(
        word_idx, counts, doc_mask, wmajor=True
    )
    run_w = fused.make_chunk_runner(
        num_docs=b - 2, num_topics=k, num_terms=v, chunk=4,
        var_max_iters=20, var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
        dense_wmajor=True,
    )
    r_wmajor = run_w(
        log_beta, alpha, jnp.float32(np.nan), compact_groups_w, 4
    )

    for r in (r_compact, r_wmajor):
        np.testing.assert_allclose(
            np.asarray(r.lls), np.asarray(r_sparse.lls), rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(r.log_beta), np.asarray(r_sparse.log_beta),
            rtol=5e-3, atol=5e-3,
        )
        np.testing.assert_allclose(
            float(r.alpha), float(r_sparse.alpha), rtol=1e-3
        )
    # Words absent from the batch got no suff-stats: their beta rows
    # come out of the M-step exactly like the sparse run's.
    absent = np.setdiff1d(np.arange(v), np.unique(np.asarray(word_idx)))
    assert absent.size
    np.testing.assert_allclose(
        np.asarray(r_compact.log_beta)[:, absent],
        np.asarray(r_sparse.log_beta)[:, absent],
        rtol=1e-5,
    )


def test_plan_compact_widths_and_grouping():
    """plan_compact: per-group widths are 128-lane multiples covering
    the widest batch; infeasible compact widths return None."""
    rng = np.random.default_rng(7)

    def batch(b, l, v):
        w, c, m = _random_batch(rng, b, l, v)
        return Batch(
            word_idx=np.asarray(w), counts=np.asarray(c),
            doc_mask=np.asarray(m), doc_index=np.arange(b),
        )

    batches = [batch(16, 16, 5000), batch(16, 16, 5000), batch(8, 8, 5000)]
    plan = fused.plan_compact(batches, num_topics=4)
    assert plan is not None
    assert len(plan.widths) == 2  # (8,8) and (16,16) shape groups
    for g, us in enumerate(plan.uniques):
        wmax = max(len(u) for u in us)
        assert plan.widths[g] % 128 == 0
        assert plan.widths[g] >= wmax
        assert plan.widths[g] - wmax < 128
    # corpus bytes: sum over groups of NB * B * Wc * itemsize
    shapes = sorted({b.word_idx.shape for b in batches})
    expect = 0
    for (shape, wc) in zip(shapes, plan.widths):
        nb = sum(1 for b in batches if b.word_idx.shape == shape)
        expect += nb * shape[0] * wc * 4
    assert plan.corpus_bytes == expect


def test_trainer_compact_mode_matches_sparse(monkeypatch):
    """LDATrainer end-to-end: ONI_ML_TPU_ESTEP=compact (forced compact-
    vocab dense) vs dense_em='off' on a tiny corpus with two batch
    shapes."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(23)
    v = 900

    def batch(b, l, start):
        w, c, m = _random_batch(rng, b, l, v)
        return Batch(
            word_idx=np.asarray(w), counts=np.asarray(c),
            doc_mask=np.asarray(m), doc_index=start + np.arange(b),
        )

    batches = [batch(16, 16, 0), batch(16, 16, 16), batch(8, 8, 32)]
    results = {}
    for force in ("compact", ""):
        if force:
            monkeypatch.setenv("ONI_ML_TPU_ESTEP", force)
        else:
            monkeypatch.delenv("ONI_ML_TPU_ESTEP", raising=False)
        cfg = LDAConfig(
            num_topics=4, em_max_iters=6, em_tol=0.0,
            var_max_iters=20, fused_em_chunk=3, seed=1,
            dense_em="off" if not force else "auto",
            warm_start_gamma=False,
        )
        trainer = LDATrainer(cfg, num_terms=v)
        results[force] = trainer.fit(batches, num_docs=40)

    on, off = results["compact"], results[""]
    np.testing.assert_allclose(on.log_beta, off.log_beta, rtol=5e-3,
                               atol=5e-3)
    np.testing.assert_allclose(
        [ll for ll, _ in on.likelihoods],
        [ll for ll, _ in off.likelihoods],
        rtol=1e-4,
    )
    # gamma rows come back to the same per-doc slots either way
    np.testing.assert_allclose(on.gamma, off.gamma, rtol=5e-3, atol=5e-3)


def test_trainer_compact_warm_start_trajectory(monkeypatch):
    """Warm start through the compact path: same optimum as the fresh
    compact run within tolerance (mirrors the dense warm-start pin)."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(31)
    v = 600
    w, c, m = _random_batch(rng, 16, 16, v)
    batch = Batch(
        word_idx=np.asarray(w), counts=np.asarray(c),
        doc_mask=np.asarray(m), doc_index=np.arange(16),
    )
    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "compact")
    res = {}
    for warm in (True, False):
        cfg = LDAConfig(
            num_topics=4, em_max_iters=8, em_tol=0.0, var_max_iters=20,
            fused_em_chunk=3, seed=1, warm_start_gamma=warm,
        )
        res[warm] = LDATrainer(cfg, num_terms=v).fit([batch], num_docs=16)
    np.testing.assert_allclose(
        res[True].likelihoods[-1][0], res[False].likelihoods[-1][0],
        rtol=1e-3,
    )
    np.testing.assert_allclose(
        res[True].log_beta, res[False].log_beta, rtol=5e-2, atol=5e-2
    )


def test_forced_dense_infeasible_rescues_to_compact():
    """dense_em='on' with a full-V-infeasible vocabulary but feasible
    per-batch compact widths must route to the compact plan instead of
    raising."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(3)
    v = 4_000_000  # no VMEM-feasible full-V doc block at any batch size
    assert dense_estep.pick_block(16, v, 4) is None
    w, c, m = _random_batch(rng, 16, 16, v)
    batch = Batch(
        word_idx=np.asarray(w), counts=np.asarray(c),
        doc_mask=np.asarray(m), doc_index=np.arange(16),
    )
    trainer = LDATrainer(
        LDAConfig(num_topics=4, dense_em="on"), num_terms=v
    )
    plan = trainer._plan_estep([batch])
    assert plan.family == "compact" and plan.kernel.startswith("compact_")
    assert plan.compact.widths[0] <= 512  # 16x16 tokens -> tiny compact width


def test_forced_compact_with_mesh_raises(monkeypatch):
    """ONI_ML_TPU_ESTEP=compact on a meshed trainer must fail loudly
    (like every other forced-engine misconfiguration), not silently run
    sparse."""
    from oni_ml_tpu.models.lda import LDATrainer
    from oni_ml_tpu.parallel import make_mesh

    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "compact")
    trainer = LDATrainer(
        LDAConfig(num_topics=4), num_terms=200,
        mesh=make_mesh(data=8, model=1),
    )
    batch = Batch(
        word_idx=np.zeros((16, 8), np.int32),
        counts=np.zeros((16, 8), np.float32),
        doc_mask=np.ones((16,), np.float32),
        doc_index=np.arange(16),
    )
    with pytest.raises(ValueError, match="compact dense E-step forced"):
        trainer._plan_estep([batch])


def test_env_dense_infeasible_rescue_leaves_nothing_behind(monkeypatch):
    """ONI_ML_TPU_ESTEP=dense with an infeasible full-V shape must
    route through the compact rescue — and the rescue is the plan's
    return value: the trainer holds nothing of it, so a later decision
    starts from nothing."""
    from oni_ml_tpu.models.lda import LDATrainer

    rng = np.random.default_rng(3)
    v = 4_000_000
    w, c, m = _random_batch(rng, 16, 16, v)
    batch = Batch(
        word_idx=np.asarray(w), counts=np.asarray(c),
        doc_mask=np.asarray(m), doc_index=np.arange(16),
    )
    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "dense")
    trainer = LDATrainer(LDAConfig(num_topics=4), num_terms=v)
    before = dict(vars(trainer))
    plan = trainer._plan_estep([batch])
    assert plan.family == "compact" and plan.compact is not None
    assert vars(trainer) == before               # no stash to consume
    again = trainer._plan_estep([batch])         # the same answer, afresh
    assert again.compact.widths == plan.compact.widths
    # another pin for the same trainer: no rescue left over
    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "xla")
    assert trainer._plan_estep([batch]).compact is None


# -- a batch read out of its group's stack, in place --------------------------

_STACK_NB, _STACK_B, _STACK_V, _STACK_K = 3, 256, 200, 4


@functools.lru_cache(maxsize=None)
def _stack_problem(wmajor, store):
    """A group of three batches [3, 256, W] (two doc blocks a batch in
    either layout), the last rows of each masked and the batches unlike
    each other; `store` bf16 keeps the corpus half-width (counts <= 4:
    exact) and runs the bf16 operand mode that such a store comes with."""
    rng = np.random.default_rng(37)
    nb, b, v, k = _STACK_NB, _STACK_B, _STACK_V, _STACK_K
    dense, masks = [], []
    for n in range(nb):
        w, c, m = _random_batch(rng, b, 12 + 4 * n, v, n_masked=3 + n)
        dense.append(dense_estep.densify(w, c, v, dtype=store))
        masks.append(m)
    stack = jnp.stack(dense)
    if wmajor:
        stack = jnp.transpose(stack, (0, 2, 1))
    gamma_prev = jnp.asarray(
        rng.uniform(0.5, 3.0, size=(nb, b, k)), jnp.float32)
    return (_log_beta(rng, k, v), stack, jnp.stack(masks), gamma_prev,
            "bf16" if store == jnp.bfloat16 else "f32")


@pytest.mark.parametrize("n", range(_STACK_NB))
@pytest.mark.parametrize("warm", [False, True], ids=["fresh", "warm"])
@pytest.mark.parametrize("store", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16_stored"])
@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_batch_read_from_the_stack_is_the_batch_sliced_out_bit_for_bit(
        wmajor, store, warm, n):
    """`e_step_dense(stack, batch_index=n)` against `e_step_dense(stack[n])`:
    one kernel, the same blocks of the same bytes from another address
    (dense_estep._corpus_call), so every field is the same numbers.  Both
    run under `jit` with `n` traced, as the accumulator's scan calls them."""
    log_beta, stack, masks, gamma_prev, precision = _stack_problem(
        wmajor, store)

    def e_step(corpus, m, g, index):
        return dense_estep.e_step_dense(
            log_beta, jnp.float32(2.5), corpus, m, var_max_iters=12,
            var_tol=1e-5, interpret=True, wmajor=wmajor, gamma_prev=g,
            warm=jnp.asarray(warm), precision=precision, batch_index=index)

    sliced = jax.jit(lambda c, m, g: e_step(c, m, g, None))(
        stack[n], masks[n], gamma_prev[n])
    in_place = jax.jit(lambda i, m, g: e_step(stack, m, g, i))(
        jnp.asarray(n, jnp.int32), masks[n], gamma_prev[n])
    assert int(in_place.vi_iters) >= 2      # a fixed point ran
    for field in estep.EStepResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(in_place, field)),
            np.asarray(getattr(sliced, field)), err_msg=field)


@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_a_stack_and_its_index_come_together(wmajor):
    log_beta, stack, masks, _, _ = _stack_problem(wmajor, jnp.float32)
    kw = dict(var_max_iters=4, var_tol=1e-5, interpret=True, wmajor=wmajor)
    with pytest.raises(ValueError, match="rank 3"):
        dense_estep.e_step_dense(log_beta, jnp.float32(2.5), stack,
                                 masks[0], **kw)
    with pytest.raises(ValueError, match="rank 2"):
        dense_estep.e_step_dense(log_beta, jnp.float32(2.5), stack[0],
                                 masks[0], batch_index=0, **kw)
