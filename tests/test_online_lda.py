"""Online (streaming SVI) LDA: invariants, learning progress, agreement
with the batch engine, and reference file contracts."""

import numpy as np
import jax.numpy as jnp
import pytest

from oni_ml_tpu.config import LDAConfig, OnlineLDAConfig
from oni_ml_tpu.io import make_batches
from oni_ml_tpu.models import (
    OnlineLDATrainer,
    train_corpus,
    train_corpus_online,
)
from oni_ml_tpu.ops import estep

import reference_lda as ref
from test_lda import corpus_from_docs


def _full_corpus_ll(corpus, log_beta, alpha=2.5):
    """ELBO of the whole corpus under frozen topics (one batch E-step)."""
    batches = make_batches(corpus, batch_size=256, min_bucket_len=64)
    total = 0.0
    for b in batches:
        res = estep.e_step(
            jnp.asarray(log_beta, jnp.float32),
            jnp.float32(alpha),
            jnp.asarray(b.word_idx),
            jnp.asarray(b.counts),
            jnp.asarray(b.doc_mask),
            var_max_iters=30,
            var_tol=1e-7,
        )
        total += float(res.likelihood)
    return total


def test_online_learns_topics():
    docs, _ = ref.make_synthetic_corpus(num_docs=120, num_terms=40,
                                        num_topics=3, seed=11)
    V, K = 40, 4
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, kappa=0.7, seed=1)

    trainer = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)
    ll_init = _full_corpus_ll(corpus, trainer.log_beta())
    batches = make_batches(corpus, cfg.batch_size, cfg.min_bucket_len)
    rng = np.random.default_rng(0)
    for _ in range(5):
        for i in rng.permutation(len(batches)):
            trainer.step(batches[i])
    ll_final = _full_corpus_ll(corpus, trainer.log_beta())
    assert ll_final > ll_init + 0.05 * abs(ll_init), (ll_init, ll_final)

    # topics normalized in probability space
    np.testing.assert_allclose(
        np.exp(trainer.log_beta()).sum(-1), np.ones(K), rtol=1e-6)
    # learning rate follows the Robbins-Monro schedule, strictly decreasing
    rhos = [h.rho for h in trainer.history]
    assert all(a > b for a, b in zip(rhos, rhos[1:]))


def test_online_approaches_batch_quality():
    docs, _ = ref.make_synthetic_corpus(num_docs=150, num_terms=30,
                                        num_topics=3, seed=5)
    V, K = 30, 3
    corpus = corpus_from_docs(docs, V)

    batch_cfg = LDAConfig(num_topics=K, em_max_iters=30, em_tol=1e-6,
                          batch_size=256, min_bucket_len=64, seed=2)
    batch_res = train_corpus(corpus, batch_cfg)
    ll_batch = _full_corpus_ll(corpus, batch_res.log_beta,
                               alpha=batch_res.alpha)

    online_cfg = OnlineLDAConfig(num_topics=K, batch_size=16,
                                 min_bucket_len=64, tau0=8.0, seed=2)
    online_res = train_corpus_online(corpus, online_cfg, epochs=8)
    ll_online = _full_corpus_ll(corpus, online_res.log_beta)

    # online should land within a few percent of the batch optimum
    assert ll_online > ll_batch - 0.05 * abs(ll_batch), (ll_batch, ll_online)


def test_held_out_ll_improves_with_training():
    """Document-completion held-out per-token LL (models/evaluate.py):
    a trained model must predict unseen docs' held-out halves better
    than the random init, and the batch optimum must score at least
    comparably to it on the same held-out split."""
    docs, _ = ref.make_synthetic_corpus(num_docs=200, num_terms=30,
                                        num_topics=3, seed=7)
    V, K = 30, 3
    train_corpus_docs = corpus_from_docs(docs[:150], V)
    heldout = corpus_from_docs(docs[150:], V)
    ho_batches = make_batches(heldout, batch_size=64, min_bucket_len=64)

    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, kappa=0.7, seed=1)
    trainer = OnlineLDATrainer(cfg, num_terms=V,
                               total_docs=train_corpus_docs.num_docs)
    ll_init = trainer.held_out_per_token_ll(ho_batches)
    batches = make_batches(train_corpus_docs, cfg.batch_size,
                           cfg.min_bucket_len)
    rng = np.random.default_rng(0)
    for _ in range(5):
        for i in rng.permutation(len(batches)):
            trainer.step(batches[i])
    ll_trained = trainer.held_out_per_token_ll(ho_batches)
    assert ll_trained > ll_init + 0.1, (ll_init, ll_trained)
    # per-token log-prob is bounded above by 0
    assert ll_trained < 0.0

    from oni_ml_tpu.models.evaluate import held_out_per_token_ll
    batch_res = train_corpus(
        train_corpus_docs,
        LDAConfig(num_topics=K, em_max_iters=30, em_tol=1e-6,
                  batch_size=256, min_bucket_len=64, seed=2),
    )
    ll_batch = held_out_per_token_ll(batch_res.log_beta, batch_res.alpha,
                                     ho_batches)
    assert ll_batch > ll_init + 0.1, (ll_init, ll_batch)
    # the two engines should land in the same quality neighborhood
    assert abs(ll_batch - ll_trained) < 0.5, (ll_batch, ll_trained)


def test_online_writes_reference_files(tmp_path):
    docs, _ = ref.make_synthetic_corpus(num_docs=40, num_terms=25,
                                        num_topics=2, seed=3)
    V, K = 25, 3
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=32)
    result = train_corpus_online(corpus, cfg, out_dir=str(tmp_path), epochs=2)

    from oni_ml_tpu.io import formats
    lb = formats.read_beta(str(tmp_path / "final.beta"))
    gm = formats.read_gamma(str(tmp_path / "final.gamma"))
    other = formats.read_other(str(tmp_path / "final.other"))
    assert lb.shape == (K, V)
    assert gm.shape == (corpus.num_docs, K)
    assert other["num_topics"] == K and other["num_terms"] == V
    # gamma rows cover every document and stay positive
    assert (gm > 0).all()
    np.testing.assert_allclose(lb, result.log_beta, atol=1e-9)


def test_online_sharded_matches_single_device():
    """Data-parallel online steps (suff-stats psum over the mesh) produce
    the same lambda as a single device."""
    import jax
    from oni_ml_tpu.parallel import make_mesh

    docs, _ = ref.make_synthetic_corpus(num_docs=64, num_terms=20,
                                        num_topics=2, seed=4)
    V, K = 20, 3
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, seed=6)
    batches = make_batches(corpus, cfg.batch_size, cfg.min_bucket_len)

    single = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    sharded = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs,
                               mesh=mesh)
    for b in batches:
        single.step(b)
        sharded.step(b)
    np.testing.assert_allclose(np.asarray(single.lam),
                               np.asarray(sharded.lam), rtol=2e-4, atol=2e-4)
    # vocab sharding is explicitly rejected for online mode
    bad_mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    import pytest
    with pytest.raises(ValueError, match="data-parallel"):
        OnlineLDATrainer(cfg, num_terms=V, total_docs=10, mesh=bad_mesh)


def test_stream_checkpoint_roundtrip_and_resume(tmp_path):
    """The streaming checkpoint writes SVI-native fields (lam/step/
    history) and a fresh trainer resumes from it bit-for-bit."""
    from oni_ml_tpu.models.online_lda import load_stream_checkpoint

    docs, _ = ref.make_synthetic_corpus(num_docs=60, num_terms=25,
                                        num_topics=2, seed=12)
    V, K = 25, 3
    corpus = corpus_from_docs(docs, V)
    ck = str(tmp_path / "stream.npz")
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          checkpoint_every=2, seed=5)
    tr = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs,
                          checkpoint_path=ck)
    for b in make_batches(corpus, cfg.batch_size, cfg.min_bucket_len):
        tr.step(b)

    z = load_stream_checkpoint(ck)
    assert set(z) == {"lam", "alpha", "step", "history"}
    assert z["step"] % cfg.checkpoint_every == 0 and z["step"] > 0
    assert all(0 < rho <= 1 for _, rho in z["history"])

    resumed = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs,
                               checkpoint_path=ck)
    assert resumed.step_count == z["step"]
    np.testing.assert_array_equal(np.asarray(resumed.lam), z["lam"])
    assert [h.rho for h in resumed.history] == [r for _, r in z["history"]]


def test_stream_checkpoint_reads_legacy_layout(tmp_path):
    """Checkpoints written by early revisions (batch-checkpoint field
    names smuggling lambda through log_beta) still load."""
    from oni_ml_tpu.models.online_lda import load_stream_checkpoint

    lam = np.random.default_rng(0).gamma(100.0, 0.01, (3, 25))
    legacy = str(tmp_path / "legacy.npz")
    np.savez(legacy, log_beta=lam, alpha=np.float64(2.5),
             em_iter=np.int64(7),
             likelihoods=np.array([[-100.0, 0.5], [-90.0, 0.4]]))
    z = load_stream_checkpoint(legacy)
    assert z["step"] == 7 and z["alpha"] == 2.5
    np.testing.assert_array_equal(z["lam"], lam)
    assert z["history"] == [(-100.0, 0.5), (-90.0, 0.4)]

    tr = OnlineLDATrainer(
        OnlineLDAConfig(num_topics=3, batch_size=16, min_bucket_len=64),
        num_terms=25, total_docs=10, checkpoint_path=legacy,
    )
    assert tr.step_count == 7 and len(tr.history) == 2

    # A genuine batch EM checkpoint (log-probabilities, all <= 0) shares
    # the legacy field names and shape but must be rejected, not fed to
    # digamma as a "lambda".
    batch_ck = str(tmp_path / "batch.npz")
    np.savez(batch_ck, log_beta=np.log(lam / lam.sum(-1, keepdims=True)),
             alpha=np.float64(2.5), em_iter=np.int64(3),
             likelihoods=np.array([[-50.0, 1.0]]))
    with pytest.raises(ValueError, match="batch EM checkpoint"):
        load_stream_checkpoint(batch_ck)


def test_stream_extends_without_restart():
    """New micro-batches keep refining the same model state — the streaming
    property the batch reference lacks (retrain-from-scratch per day)."""
    docs, _ = ref.make_synthetic_corpus(num_docs=80, num_terms=30,
                                        num_topics=3, seed=9)
    V, K = 30, 3
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0)
    batches = make_batches(corpus, cfg.batch_size, cfg.min_bucket_len)
    trainer = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)

    # "hour 1": first half of the stream
    half = len(batches) // 2
    for b in batches[:half]:
        trainer.step(b)
    steps_after_h1 = trainer.step_count
    lam_h1 = np.asarray(trainer.lam).copy()

    # "hour 2" arrives: continues from the same state
    for b in batches[half:]:
        trainer.step(b)
    assert trainer.step_count == steps_after_h1 + (len(batches) - half)
    assert not np.allclose(np.asarray(trainer.lam), lam_h1)


def test_dense_micro_batch_matches_sparse():
    """dense_em="on" must track the default sparse E-step trajectory:
    same natural-gradient updates up to dense-vs-sparse float
    reassociation (the dense path is the TPU fast path; CPU runs it in
    interpret mode)."""
    docs, _ = ref.make_synthetic_corpus(num_docs=48, num_terms=40,
                                        num_topics=3, seed=5)
    corpus = corpus_from_docs(docs, 40)
    runs = {}
    for mode in ("off", "on"):
        cfg = OnlineLDAConfig(num_topics=4, batch_size=16,
                              min_bucket_len=64, seed=3, dense_em=mode)
        tr = OnlineLDATrainer(cfg, corpus.num_terms, total_docs=48)
        for b in make_batches(corpus, cfg.batch_size, cfg.min_bucket_len):
            tr.step(b)
        runs[mode] = np.asarray(tr.lam)
    np.testing.assert_allclose(runs["on"], runs["off"], rtol=2e-3, atol=2e-3)


def test_dense_em_validation():
    with pytest.raises(ValueError, match="dense_em"):
        OnlineLDATrainer(
            OnlineLDAConfig(num_topics=4, dense_em="dense"),
            num_terms=50, total_docs=10,
        )
    # forced dense + custom e_step_fn is contradictory — and must fail
    # at construction, not at the first step()
    with pytest.raises(ValueError, match="dense_em='on'"):
        OnlineLDATrainer(
            OnlineLDAConfig(num_topics=4, dense_em="on"),
            num_terms=50, total_docs=10,
            e_step_fn=lambda *a, **k: None,
        )


def test_update_cache_is_bounded():
    """The per-(B, L) jitted-update cache must not grow without bound
    when fed un-bucketed ragged micro-batch shapes."""
    tr = OnlineLDATrainer(
        OnlineLDAConfig(num_topics=4, dense_em="off"),
        num_terms=50, total_docs=10_000,
    )
    cap = tr._UPDATE_CACHE_MAX
    for l in range(1, cap + 10):
        tr._get_update(8, l)
    assert len(tr._updates) == cap
    # LRU: a hit refreshes recency, so the hit survives the next insert.
    first_kept = (8, 10)
    tr._get_update(*first_kept)
    tr._get_update(8, cap + 10)
    assert first_kept in tr._updates


def test_step_many_matches_sequential_steps():
    """The chunked scan path (step_many) is step() applied in sequence:
    same lambda, same step/rho bookkeeping, same likelihood history —
    modulo the rho schedule's f32 in-scan evaluation."""
    docs, _ = ref.make_synthetic_corpus(num_docs=96, num_terms=30,
                                        num_topics=3, seed=9)
    V, K = 30, 4
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, seed=2)
    batches = list(make_batches(corpus, cfg.batch_size, cfg.min_bucket_len))
    stream = (batches * 3)[:12]

    seq = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)
    for b in stream:
        seq.step(b)
    chunked = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)
    infos = chunked.step_many(stream, chunk=4)

    np.testing.assert_allclose(np.asarray(seq.lam), np.asarray(chunked.lam),
                               rtol=1e-4, atol=1e-5)
    assert chunked.step_count == seq.step_count == 12
    assert [i.step for i in infos] == list(range(1, 13))
    np.testing.assert_allclose([i.rho for i in infos],
                               [h.rho for h in seq.history], rtol=1e-6)
    np.testing.assert_allclose(
        [float(i.likelihood) for i in infos],
        [float(h.likelihood) for h in seq.history], rtol=1e-4)
    # sub-chunk remainders and shape changes take the per-step path
    assert chunked.step_many(stream[:3], chunk=4)


def test_step_many_mixed_shapes_preserves_order():
    """Shape changes split runs; order and results still match step()."""
    rng = np.random.default_rng(5)
    from oni_ml_tpu.io import Batch

    V, K, B = 25, 3, 8

    def mk(l, seed):
        r = np.random.default_rng(seed)
        return Batch(
            word_idx=r.integers(0, V, size=(B, l)).astype(np.int32),
            counts=r.integers(1, 4, size=(B, l)).astype(np.float32),
            doc_index=np.arange(B, dtype=np.int32),
            doc_mask=np.ones((B,), np.float32),
        )

    stream = ([mk(16, i) for i in range(5)] + [mk(32, 10 + i) for i in range(2)]
              + [mk(16, 20 + i) for i in range(2)])
    cfg = OnlineLDAConfig(num_topics=K, batch_size=B, tau0=8.0, seed=3)
    seq = OnlineLDATrainer(cfg, num_terms=V, total_docs=64)
    for b in stream:
        seq.step(b)
    chunked = OnlineLDATrainer(cfg, num_terms=V, total_docs=64)
    chunked.step_many(stream, chunk=4)
    np.testing.assert_allclose(np.asarray(seq.lam), np.asarray(chunked.lam),
                               rtol=1e-4, atol=1e-5)


def test_step_many_sharded_matches_single_device():
    """The stacked [N, B, L] chunk shards docs (axis 1) over `data` and
    scans the shard_map'd E-step — same lambda as the unsharded chunk."""
    import jax
    from oni_ml_tpu.parallel import make_mesh

    docs, _ = ref.make_synthetic_corpus(num_docs=64, num_terms=20,
                                        num_topics=2, seed=4)
    V, K = 20, 3
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, seed=6)
    batches = list(make_batches(corpus, cfg.batch_size, cfg.min_bucket_len))
    stream = (batches * 3)[:8]

    single = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs)
    single.step_many(stream, chunk=4)
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    sharded = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs,
                               mesh=mesh)
    sharded.step_many(stream, chunk=4)
    np.testing.assert_allclose(np.asarray(single.lam),
                               np.asarray(sharded.lam), rtol=2e-4, atol=2e-4)


def test_chunked_checkpoint_lands_after_boundary(tmp_path):
    """A checkpoint_every boundary crossed mid-chunk checkpoints at the
    chunk end (the only materialized lambda), not silently never."""
    docs, _ = ref.make_synthetic_corpus(num_docs=64, num_terms=20,
                                        num_topics=2, seed=8)
    V, K = 20, 3
    corpus = corpus_from_docs(docs, V)
    cfg = OnlineLDAConfig(num_topics=K, batch_size=16, min_bucket_len=64,
                          tau0=8.0, seed=7, checkpoint_every=3)
    batches = list(make_batches(corpus, cfg.batch_size, cfg.min_bucket_len))
    path = str(tmp_path / "stream.npz")
    tr = OnlineLDATrainer(cfg, num_terms=V, total_docs=corpus.num_docs,
                          checkpoint_path=path)
    tr.step_many((batches * 2)[:4], chunk=4)   # crosses step 3 mid-chunk
    from oni_ml_tpu.models.online_lda import load_stream_checkpoint

    ck = load_stream_checkpoint(path)
    assert ck["step"] == 4                     # end-of-chunk state
    np.testing.assert_allclose(ck["lam"], np.asarray(tr.lam))
