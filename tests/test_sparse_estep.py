"""Sparse bucketed Pallas E-step (ops/sparse_estep.py) + the corpus
layout pass (Corpus.bucketed_layout) + the measured dense-vs-sparse
crossover.

The fused kernel must agree with estep.e_step's XLA path to fixed-point
tolerance on gamma, suff-stats, ELBO, and alpha suff-stats — it is a
full E-step, not just the fixed point — and the layout pass must
restore document order bit-exactly.  Kernel math runs under interpret
mode on every CPU run; the compiled variant is TPU-marked.
"""

import functools
import os

import numpy as np
import jax.numpy as jnp
import pytest

from oni_ml_tpu.io import Corpus
from oni_ml_tpu.ops import estep, sparse_estep


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


# Kernel-math parametrization: interpret=True runs on every CPU suite
# run at a tiny shape; interpret=False is the real Mosaic compile at the
# config-1 block shape, exercised only when a TPU backend is attached
# (ONI_ML_TPU_TESTS_ON_TPU=1; tests/conftest.py has the command).
INTERPRET = [
    pytest.param(True, id="interpret"),
    pytest.param(
        False, id="compiled",
        marks=pytest.mark.skipif(
            not _on_tpu(), reason="compiled Pallas needs a TPU backend"
        ),
    ),
]


TINY = (4, 50, 32, 16)
# BASELINE.json config 1, the (K, V, B, L) block shape production
# tiles: what a `compiled` run on the chip has to prove.
CONFIG1 = (20, 8192, 4096, 128)


@functools.lru_cache(maxsize=None)
def _problem(shape):
    K, V, B, L = shape
    rng = np.random.default_rng(0)
    noise = rng.uniform(size=(K, V)) + 1.0 / V
    lb = jnp.asarray(np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32)
    w = jnp.asarray(rng.integers(0, V, size=(B, L)), jnp.int32)
    c = jnp.asarray(rng.integers(1, 5, size=(B, L)), jnp.float32)
    m = jnp.asarray((rng.uniform(size=B) > 0.2).astype(np.float32))
    return lb, jnp.float32(2.5), w, c, m


@pytest.fixture()
def problem(request):
    """The tiny shape for every interpreted (CPU) run; the config-1
    block shape when the test's `interpret` parameter is False, i.e.
    when the kernel is Mosaic-compiled on an attached TPU."""
    callspec = getattr(request.node, "callspec", None)
    compiled = callspec is not None and \
        callspec.params.get("interpret") is False
    return _problem(CONFIG1 if compiled else TINY)


def _gamma_tol(interpret):
    """Interpreted on a CPU both sides are exact f32.  Compiled for the
    chip, the TPU's f32 log is good to ~1e-4 absolute — in XLA and in
    Mosaic alike — and through digamma that puts EVERY f32
    implementation of the fixed point ~7e-4 relative from the float64
    truth at the config-1 shape (measured on the v5e, PERF.md PR 21):
    two of them agree to the dense tests' 2e-3, not to 5e-4."""
    return 5e-4 if interpret else 2e-3


@pytest.fixture()
def plan_cache(tmp_path, monkeypatch):
    """Hermetic plan cache for tests that record/lookup entries."""
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv("ONI_ML_TPU_PLAN_CACHE", path)
    sparse_estep._CROSSOVER_CACHE.clear()
    yield path
    sparse_estep._CROSSOVER_CACHE.clear()


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("interpret", INTERPRET)
def test_full_e_step_parity(problem, interpret):
    """The fused kernel's gamma, suff-stats, ELBO, and alpha suff-stats
    all match the XLA reference — the tail runs in-kernel here, so this
    pins much more than the old fixed-point-only parity."""
    lb, a, w, c, m = problem
    ref = estep.e_step(lb, a, w, c, m, var_max_iters=50, var_tol=1e-7,
                       backend="xla")
    sp = sparse_estep.e_step(lb, a, w, c, m, 50, 1e-7,
                             interpret=interpret)
    sel = np.asarray(m) == 1
    np.testing.assert_allclose(
        np.asarray(sp.gamma)[sel], np.asarray(ref.gamma)[sel],
        rtol=_gamma_tol(interpret), atol=_gamma_tol(interpret),
    )
    np.testing.assert_allclose(
        np.asarray(sp.suff_stats), np.asarray(ref.suff_stats),
        rtol=2e-3, atol=2e-4,
    )
    np.testing.assert_allclose(
        float(sp.likelihood), float(ref.likelihood), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(sp.alpha_ss), float(ref.alpha_ss), rtol=1e-4
    )


@pytest.mark.parametrize("interpret", INTERPRET)
def test_bf16_slab_within_tolerance(problem, interpret):
    """bf16 slab storage rounds exp(log beta) to 8 significand bits —
    results agree with f32 to bf16 tolerance (NOT bit-exactly, unlike
    the dense engine's operand-truncation bf16 mode)."""
    lb, a, w, c, m = problem
    f32 = sparse_estep.e_step(lb, a, w, c, m, 50, 1e-7,
                              interpret=interpret)
    b16 = sparse_estep.e_step(lb, a, w, c, m, 50, 1e-7,
                              interpret=interpret, precision="bf16")
    np.testing.assert_allclose(
        float(b16.likelihood), float(f32.likelihood), rtol=5e-3
    )
    sel = np.asarray(m) == 1
    np.testing.assert_allclose(
        np.asarray(b16.gamma)[sel], np.asarray(f32.gamma)[sel],
        rtol=2e-2, atol=2e-2,
    )


def test_unknown_precision_rejected(problem):
    lb, a, w, c, m = problem
    with pytest.raises(ValueError, match="precision"):
        sparse_estep.e_step(lb, a, w, c, m, 5, 1e-6, interpret=True,
                            precision="fp8")


def test_iteration_cap_respected(problem):
    lb, a, w, c, m = problem
    sp = sparse_estep.e_step(lb, a, w, c, m, 3, 0.0, interpret=True)
    assert int(sp.vi_iters) == 3


def test_warm_start(problem):
    lb, a, w, c, m = problem
    fresh = sparse_estep.e_step(lb, a, w, c, m, 40, 1e-5, interpret=True)
    warm = sparse_estep.e_step(lb, a, w, c, m, 40, 1e-5, interpret=True,
                               gamma_prev=fresh.gamma, warm=1)
    assert int(warm.vi_iters) < int(fresh.vi_iters)
    np.testing.assert_allclose(float(warm.likelihood),
                               float(fresh.likelihood), rtol=1e-5)
    cold = sparse_estep.e_step(lb, a, w, c, m, 40, 1e-5, interpret=True,
                               gamma_prev=jnp.full_like(fresh.gamma, 7.0),
                               warm=0)
    np.testing.assert_array_equal(np.asarray(cold.gamma),
                                  np.asarray(fresh.gamma))


def test_forced_backend_through_estep(problem):
    """estep.e_step(backend='sparse') routes here; infeasible shapes
    fail loudly instead of silently falling back."""
    lb, a, w, c, m = problem
    ref = estep.e_step(lb, a, w, c, m, 20, 1e-6, backend="xla")
    sp = estep.e_step(lb, a, w, c, m, 20, 1e-6, backend="sparse")
    np.testing.assert_allclose(float(sp.likelihood),
                               float(ref.likelihood), rtol=1e-5)
    # B=12 divides by neither 8 nor 16: no feasible block.
    w12 = w[:12]
    c12 = c[:12]
    m12 = m[:12]
    with pytest.raises(ValueError, match="sparse E-step forced"):
        estep.e_step(lb, a, w12, c12, m12, 20, 1e-6, backend="sparse")


def test_make_e_step_fn_is_warm_capable(problem):
    lb, a, w, c, m = problem
    fn = sparse_estep.make_e_step_fn(precision="f32", interpret=True)
    assert fn._oni_warm_capable and fn._oni_sparse_engine
    res = fn(lb, a, w, c, m, 10, 1e-6)
    assert np.isfinite(float(res.likelihood))
    warm = fn(lb, a, w, c, m, 10, 1e-6, gamma_prev=res.gamma,
              warm=jnp.asarray(1, jnp.int32))
    assert int(warm.vi_iters) <= int(res.vi_iters)


# ---------------------------------------------------------------------------
# Block picking + plans
# ---------------------------------------------------------------------------


def test_pick_block_analytic():
    assert sparse_estep.pick_block(4096, 128, 20) in (128, 256)
    assert sparse_estep.pick_block(32, 16, 4) == 32
    # Non-8-divisible batch: no feasible block.
    assert sparse_estep.pick_block(12, 16, 4) is None
    # bf16 blocks sit on the 16-sublane tile.
    bb = sparse_estep.pick_block(64, 128, 4, "bf16")
    assert bb is not None and bb % 16 == 0
    # Huge L shrinks the block instead of blowing VMEM.
    bb = sparse_estep.pick_block(4096, 8192, 20)
    if bb is not None:
        assert sparse_estep._vmem_estimate(bb, 8192, 20) \
            <= sparse_estep._VMEM_CEILING


def test_vmem_estimate_pads_lanes_and_halves_bf16_slab():
    # L=16 occupies 128 lanes in VMEM tiles: the estimate must not
    # pretend a short bucket is 8x cheaper than it is.
    assert sparse_estep._vmem_estimate(8, 16, 4) == \
        sparse_estep._vmem_estimate(8, 128, 4)
    f32 = sparse_estep._vmem_estimate(16, 256, 4, "f32")
    b16 = sparse_estep._vmem_estimate(16, 256, 4, "bf16")
    assert b16 < f32


def test_planned_block_override_and_validation(plan_cache):
    from oni_ml_tpu import plans

    # A measured winner for this exact shape wins over the analytic
    # pick; garbage entries (wrong divisibility) are rejected.
    analytic = sparse_estep.pick_block(64, 128, 4)
    assert analytic == 64
    plans.record_value("sparse_estep_bb", 32, shape="b64.l128.k4.f32",
                       source="probe")
    assert sparse_estep.pick_block(64, 128, 4) == 32
    plans.record_value("sparse_estep_bb", 24, shape="b64.l128.k4.f32",
                       source="probe")     # not a multiple of 8
    assert sparse_estep.pick_block(64, 128, 4) == analytic
    plans.record_value("sparse_estep_bb", 48, shape="b64.l128.k4.f32",
                       source="probe")     # does not divide 64
    assert sparse_estep.pick_block(64, 128, 4) == analytic


def test_resolve_layout_len_sources(plan_cache):
    from oni_ml_tpu import plans
    from oni_ml_tpu.config import LDAConfig

    val, src = sparse_estep.resolve_layout_len(
        LDAConfig.sparse_min_bucket_len
    )
    assert (val, src) == (LDAConfig.sparse_min_bucket_len, "default")
    # Explicit config value wins as "config".
    val, src = sparse_estep.resolve_layout_len(64)
    assert (val, src) == (64, "config")
    # A plan entry beats the default.
    plans.record_value("sparse_estep_l", 256, source="probe")
    val, src = sparse_estep.resolve_layout_len(
        LDAConfig.sparse_min_bucket_len
    )
    assert (val, src) == (256, "plan")


def test_effective_vs_dense_equiv_flops():
    eff = sparse_estep.effective_flops(4096, 128, 20, 19)
    deq = sparse_estep.dense_equiv_flops(4096, 8192, 20, 19)
    assert eff == 4.0 * 4096 * 20 * 128 * 20
    # The 1.6%-dense bench shape wastes ~64x (8192 pads to itself).
    assert deq / eff == pytest.approx(8192 / 128)


# ---------------------------------------------------------------------------
# Corpus layout pass
# ---------------------------------------------------------------------------


def _toy_corpus(n_docs=60, v=120, seed=5):
    rng = np.random.default_rng(seed)
    triples = []
    for d in range(n_docs):
        n = int(rng.integers(1, 30))
        for word in rng.integers(0, v, n):
            triples.append((f"ip{d}", f"w{word}", int(rng.integers(1, 4))))
    return Corpus.from_word_counts(triples)


def test_bucketed_layout_packs_and_restores():
    corpus = _toy_corpus()
    lay = corpus.bucketed_layout(min_len=8, batch_cap=16)
    # Every real doc appears exactly once, rows match the CSR exactly.
    seen = []
    for b in lay.batches:
        assert b.word_idx.shape[0] % 8 == 0       # pad_multiple
        L = b.word_idx.shape[1]
        assert L >= 8 and (L & (L - 1)) == 0      # power-of-two bucket
        for i in range(b.word_idx.shape[0]):
            if b.doc_mask[i] == 0:
                assert (b.counts[i] == 0).all()
                continue
            d = int(b.doc_index[i])
            seen.append(d)
            lo, hi = int(corpus.doc_ptr[d]), int(corpus.doc_ptr[d + 1])
            n = hi - lo
            assert n <= L
            assert (b.word_idx[i, :n] == corpus.word_idx[lo:hi]).all()
            assert (b.counts[i, :n] == corpus.counts[lo:hi]).all()
            assert (b.counts[i, n:] == 0).all()
    assert sorted(seen) == list(range(corpus.num_docs))
    # perm is the packed order; restore() inverts it bit-exactly.
    assert list(lay.perm) == seen
    packed = np.asarray(lay.perm, np.float64) * 3.5
    restored = lay.restore(packed)
    np.testing.assert_array_equal(
        restored, np.arange(corpus.num_docs) * 3.5
    )
    with pytest.raises(ValueError, match="packed rows"):
        lay.restore(packed[:-1])


def test_bucketed_layout_sorted_by_length_and_cached():
    corpus = _toy_corpus()
    lay = corpus.bucketed_layout(min_len=8, batch_cap=16)
    lengths = corpus.doc_lengths()[lay.perm]
    assert (np.diff(lengths) >= 0).all()          # sorted by token count
    # One-time: the same parameters return the cached object.
    assert corpus.bucketed_layout(min_len=8, batch_cap=16) is lay
    assert corpus.bucketed_layout(min_len=16, batch_cap=16) is not lay


def test_bucket_shapes_match_layout():
    """bucket_shapes is the no-packing twin of bucketed_layout: the
    engine gates feasibility-check through it, so the two must agree
    shape-for-shape at every parameterization."""
    corpus = _toy_corpus()
    for min_len, cap, pad in [(8, 16, 8), (16, 32, 16), (128, 4096, 8)]:
        shapes = corpus.bucket_shapes(min_len=min_len, batch_cap=cap,
                                      pad_multiple=pad)
        lay = corpus.bucketed_layout(min_len=min_len, batch_cap=cap,
                                     pad_multiple=pad)
        assert [(s[0], s[1]) for s in shapes] == \
            [b.word_idx.shape for b in lay.batches]
        assert [s[2] for s in shapes] == \
            [int(b.doc_mask.sum()) for b in lay.batches]


def test_bucketed_layout_deterministic():
    a = _toy_corpus().bucketed_layout(min_len=8, batch_cap=16)
    b = _toy_corpus().bucketed_layout(min_len=8, batch_cap=16)
    assert len(a.batches) == len(b.batches)
    for x, y in zip(a.batches, b.batches):
        np.testing.assert_array_equal(x.word_idx, y.word_idx)
        np.testing.assert_array_equal(x.counts, y.counts)
        np.testing.assert_array_equal(x.doc_index, y.doc_index)
    np.testing.assert_array_equal(a.perm, b.perm)


# ---------------------------------------------------------------------------
# Crossover
# ---------------------------------------------------------------------------


def test_crossover_measures_then_resolves_from_plan(plan_cache):
    from oni_ml_tpu import plans

    before = plans.counters_snapshot()["autotune_sweeps"]
    rec = sparse_estep.engine_crossover(4, 512, 32, 16)
    assert rec["source"] == "measured"
    assert rec["engine"] in ("dense", "sparse")
    assert plans.counters_snapshot()["autotune_sweeps"] == before + 1
    # A fresh process (memo cleared) resolves from the persisted plan —
    # zero re-sweeps on run 2.
    sparse_estep._CROSSOVER_CACHE.clear()
    rec2 = sparse_estep.engine_crossover(4, 512, 32, 16)
    assert rec2["source"] == "plan"
    assert rec2["engine"] == rec["engine"]
    assert plans.counters_snapshot()["autotune_sweeps"] == before + 1
    # The density band generalizes to a neighbouring exact shape.
    sparse_estep._CROSSOVER_CACHE.clear()
    rec3 = sparse_estep.engine_crossover(4, 1024, 64, 32)
    assert rec3["source"] == "plan"
    assert rec3["shape"].startswith("dlog")


def test_crossover_env_pin(plan_cache, monkeypatch):
    monkeypatch.setenv("ONI_ML_TPU_ESTEP_ENGINE", "dense")
    rec = sparse_estep.engine_crossover(4, 512, 32, 16)
    assert rec == {"engine": "dense", "dense_s": None, "sparse_s": None,
                   "source": "env", "shape": rec["shape"]}
    monkeypatch.setenv("ONI_ML_TPU_ESTEP_ENGINE", "fastest")
    with pytest.raises(ValueError, match="ONI_ML_TPU_ESTEP_ENGINE"):
        sparse_estep.engine_crossover(4, 512, 32, 16)


def test_crossover_journals_record(plan_cache, tmp_path):
    from oni_ml_tpu.telemetry import Journal, Recorder, use_recorder

    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    with use_recorder(Recorder(journal=j)):
        sparse_estep.engine_crossover(4, 512, 32, 16)
    j.close()
    recs = [r for r in Journal.replay(path)
            if r.get("kind") == "estep_crossover"]
    assert len(recs) == 1
    assert recs[0]["engine"] in ("dense", "sparse")
    assert recs[0]["source"] == "measured"
    assert recs[0]["shape"] == "k4.v512.b32.l16.f32"


def test_density_bands():
    assert sparse_estep.crossover_shapes(20, 8192, 4096, 128, "bf16") == (
        "k20.v8192.b4096.l128.bf16", "dlog1.k20.bf16"
    )
    # 1.6% density lands in band 1; 0.8% in band 0 — neighbours get
    # separate evidence.
    assert sparse_estep._density_band(1.6) == 1
    assert sparse_estep._density_band(0.8) == 0
    assert sparse_estep._density_band(100.0) == 7   # clamped


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------


def test_resolve_engine_cpu_auto_stays_dense_family(plan_cache):
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models.lda import resolve_estep_engine

    corpus = _toy_corpus()
    engine, src = resolve_estep_engine(corpus, LDAConfig(num_topics=4))
    assert (engine, src) == ("dense", "default")


def test_resolve_engine_forced_and_conflicts(plan_cache, monkeypatch):
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models.lda import resolve_estep_engine

    corpus = _toy_corpus()
    cfg = LDAConfig(num_topics=4, estep_engine="sparse")
    assert resolve_estep_engine(corpus, cfg) == ("sparse", "config")
    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "sparse")
    assert resolve_estep_engine(corpus, LDAConfig(num_topics=4)) == \
        ("sparse", "env")
    # env beats config.
    assert resolve_estep_engine(
        corpus, LDAConfig(num_topics=4, estep_engine="dense")
    ) == ("sparse", "env")
    monkeypatch.delenv("ONI_ML_TPU_ESTEP")
    with pytest.raises(ValueError, match="dense_em"):
        resolve_estep_engine(
            corpus,
            LDAConfig(num_topics=4, estep_engine="sparse", dense_em="on"),
        )
    with pytest.raises(ValueError, match="estep_engine"):
        resolve_estep_engine(
            corpus, LDAConfig(num_topics=4, estep_engine="fastest")
        )


def test_sparse_engine_rejected_on_mesh(plan_cache):
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models.lda import resolve_estep_engine
    from oni_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=1)
    corpus = _toy_corpus()
    cfg = LDAConfig(num_topics=4, estep_engine="sparse")
    with pytest.raises(ValueError, match="single-process"):
        resolve_estep_engine(corpus, cfg, mesh=mesh)
    # Auto on a mesh quietly stays with the dense family.
    assert resolve_estep_engine(
        corpus, LDAConfig(num_topics=4), mesh=mesh
    ) == ("dense", "default")


def _train(cfg, corpus, out_dir=None):
    from oni_ml_tpu.models.lda import train_corpus

    return train_corpus(corpus, cfg, out_dir=out_dir)


def test_train_sparse_engine_matches_dense_family(plan_cache, tmp_path):
    """engine='sparse' vs engine='dense' (pinned) on the same corpus:
    final likelihood agrees within the bf16-class tolerance, gamma
    rows land in document order, and the plan record names the
    engine."""
    from oni_ml_tpu.config import LDAConfig

    corpus = _toy_corpus(n_docs=80, v=150)
    base = dict(num_topics=4, em_max_iters=5, batch_size=32,
                fused_em_chunk=4, host_sync_every=0, seed=0)
    out = tmp_path / "sparse"
    out.mkdir()
    res_s = _train(
        LDAConfig(estep_engine="sparse", sparse_min_bucket_len=16, **base),
        corpus, out_dir=str(out),
    )
    res_d = _train(LDAConfig(estep_engine="dense", **base), corpus)
    assert res_s.plan["estep_engine"] == {"value": "sparse",
                                          "source": "config"}
    assert res_s.plan["sparse_estep_l"]["value"] == 16
    assert res_d.plan["estep_engine"] == {"value": "dense",
                                          "source": "config"}
    ll_s = res_s.likelihoods[-1][0]
    ll_d = res_d.likelihoods[-1][0]
    np.testing.assert_allclose(ll_s, ll_d, rtol=1e-4)
    # Document order restored: per-doc posteriors agree row-for-row
    # despite the layout permutation reordering the device batches.
    assert res_s.gamma.shape == res_d.gamma.shape
    np.testing.assert_allclose(res_s.gamma, res_d.gamma,
                               rtol=2e-3, atol=2e-3)


def test_train_sparse_engine_pinned_is_byte_deterministic(
    plan_cache, tmp_path
):
    """Two runs with the engine pinned produce byte-identical
    artifacts — the acceptance contract for pinned-engine runs."""
    from oni_ml_tpu.config import LDAConfig

    cfg = LDAConfig(num_topics=4, em_max_iters=4, batch_size=32,
                    fused_em_chunk=4, host_sync_every=0, seed=0,
                    estep_engine="sparse", sparse_min_bucket_len=16)
    outs = []
    for name in ("a", "b"):
        d = tmp_path / name
        d.mkdir()
        _train(cfg, _toy_corpus(n_docs=50, v=100), out_dir=str(d))
        outs.append({
            f: (d / f).read_bytes()
            for f in ("final.beta", "final.gamma", "likelihood.dat")
        })
    assert outs[0] == outs[1]


def test_train_sparse_engine_via_env(plan_cache, tmp_path, monkeypatch):
    from oni_ml_tpu.config import LDAConfig

    monkeypatch.setenv("ONI_ML_TPU_ESTEP", "sparse")
    corpus = _toy_corpus(n_docs=40, v=80)
    res = _train(
        LDAConfig(num_topics=4, em_max_iters=3, batch_size=32,
                  sparse_min_bucket_len=16, fused_em_chunk=2,
                  host_sync_every=0),
        corpus,
    )
    assert res.plan["estep_engine"] == {"value": "sparse", "source": "env"}
    assert np.isfinite(res.likelihoods[-1][0])


def test_train_sparse_bf16_pads_batch_axis_to_sublane_tile(plan_cache):
    """A bucket packing to 24 docs has no bf16-feasible block when the
    layout pads to 8 (24 % 16 != 0): the layout must pad the batch axis
    to the engine precision's sublane tile instead of crashing
    mid-training (code-review finding on this PR)."""
    from oni_ml_tpu.config import LDAConfig

    corpus = _toy_corpus(n_docs=24, v=60)
    assert sparse_estep.pad_multiple_for("bf16") == 16
    res = _train(
        LDAConfig(num_topics=4, em_max_iters=2, batch_size=32,
                  estep_engine="sparse", sparse_min_bucket_len=16,
                  dense_precision="bf16", fused_em_chunk=2,
                  host_sync_every=0),
        corpus,
    )
    assert np.isfinite(res.likelihoods[-1][0])
    assert res.gamma.shape == (24, 4)


def test_train_sparse_infeasible_bucket_fails_fast(plan_cache):
    """A huge-L bucket that admits no VMEM block fails at engine setup
    with the shapes named — not deep inside the chunk program (the
    small-B/huge-L bucket is the VMEM-worst shape, invisible to a
    largest-batch-only gate)."""
    from oni_ml_tpu.config import LDAConfig

    rng = np.random.default_rng(1)
    triples = [("fat", f"w{w}", 1) for w in range(17_000)]
    for d in range(8):
        for w in rng.integers(0, 500, 10):
            triples.append((f"ip{d}", f"w{w}", 1))
    corpus = Corpus.from_word_counts(triples)
    assert max(corpus.doc_lengths()) == 17_000   # bucket L = 32768
    with pytest.raises(ValueError, match="32768"):
        _train(
            LDAConfig(num_topics=20, em_max_iters=2, batch_size=32,
                      estep_engine="sparse", sparse_min_bucket_len=16,
                      fused_em_chunk=2, host_sync_every=0),
            corpus,
        )


def test_train_sparse_engine_stepwise_driver(plan_cache):
    """fused_em_chunk<=1 routes the sparse engine through the stepwise
    loop — the numerical cross-check driver must accept it too."""
    from oni_ml_tpu.config import LDAConfig

    corpus = _toy_corpus(n_docs=40, v=80)
    res = _train(
        LDAConfig(num_topics=4, em_max_iters=3, batch_size=32,
                  estep_engine="sparse", sparse_min_bucket_len=16,
                  fused_em_chunk=1),
        corpus,
    )
    assert res.em_iters == 3
    assert np.isfinite(res.likelihoods[-1][0])
