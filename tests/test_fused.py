"""Fused (device-resident chunked) EM vs the stepwise driver.

The two drivers must produce the same training trajectory: the fused loop
only changes WHERE the loop control runs (device vs host), not the math.
"""

import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import make_batches
from oni_ml_tpu.models import LDATrainer, train_corpus

import reference_lda as ref
from test_lda import corpus_from_docs


@pytest.fixture(scope="module")
def problem():
    docs, _ = ref.make_synthetic_corpus(
        num_docs=48, num_terms=40, num_topics=3, seed=11
    )
    return corpus_from_docs(docs, 40)


def run(corpus, **cfg_kw):
    cfg = LDAConfig(
        num_topics=4, alpha_init=2.5, batch_size=16, min_bucket_len=4,
        seed=3, **cfg_kw
    )
    return train_corpus(corpus, cfg)


def test_fused_matches_stepwise_fixed_iters(problem):
    # em_tol=0 pins the iteration count; small batch/bucket sizes force
    # multiple shape groups and multiple batches per group.
    a = run(problem, em_max_iters=6, em_tol=0.0, fused_em_chunk=0)
    b = run(problem, em_max_iters=6, em_tol=0.0, fused_em_chunk=4)
    assert a.em_iters == b.em_iters == 6
    np.testing.assert_allclose(
        [l for l, _ in a.likelihoods], [l for l, _ in b.likelihoods],
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.exp(a.log_beta), np.exp(b.log_beta), atol=1e-4
    )
    np.testing.assert_allclose(a.gamma, b.gamma, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(a.alpha, b.alpha, rtol=1e-4)


def test_fused_convergence_stop(problem):
    # A loose tolerance converges well before em_max_iters; the on-device
    # check must stop at the same iteration as the host-side check.
    a = run(problem, em_max_iters=50, em_tol=1e-3, fused_em_chunk=0)
    b = run(problem, em_max_iters=50, em_tol=1e-3, fused_em_chunk=8)
    assert a.em_iters < 50  # the tolerance actually fired
    assert b.em_iters == a.em_iters
    assert len(b.likelihoods) == b.em_iters
    assert b.likelihoods[-1][1] < 1e-3  # logged conv reflects the stop


def test_fused_chunk_boundaries_do_not_matter(problem):
    # chunk=1..3 slice the same 5 iterations differently; results agree.
    runs = [
        run(problem, em_max_iters=5, em_tol=0.0, fused_em_chunk=c)
        for c in (2, 3, 5)
    ]
    for r in runs[1:]:
        np.testing.assert_allclose(
            [l for l, _ in runs[0].likelihoods],
            [l for l, _ in r.likelihoods],
            rtol=1e-5,
        )


def test_fused_progress_and_likelihood_stream(problem, tmp_path):
    seen = []
    cfg = LDAConfig(
        num_topics=4, em_max_iters=5, em_tol=0.0, batch_size=16,
        min_bucket_len=4, fused_em_chunk=2, seed=3,
    )
    out = tmp_path / "day"
    out.mkdir()
    res = train_corpus(
        problem, cfg, out_dir=str(out),
        progress=lambda it, ll, conv: seen.append((it, ll, conv)),
    )
    assert [it for it, _, _ in seen] == [1, 2, 3, 4, 5]
    lines = (out / "likelihood.dat").read_text().strip().splitlines()
    assert len(lines) == 5
    ll0 = float(lines[0].split("\t")[0])
    np.testing.assert_allclose(ll0, res.likelihoods[0][0], rtol=1e-6)


# f32 on the CPU: the dense family sums a document's words as one
# [B, W] x [W, K] product, the token-list family as a gather and a sum
# over L, so the two round differently.  Worst seen over the cases below
# (builder, CPU, PR 31): ELBO 1.0e-6 relative, beta 7e-7 absolute as
# probabilities (1.5e-4 as logs, at the small entries), alpha 1.4e-5,
# gamma 1.9e-5 relative.
LL_RTOL, BETA_ATOL, LOG_BETA_ATOL = 5e-6, 5e-6, 5e-4
ALPHA_RTOL, GAMMA_RTOL = 5e-5, 1e-4


def _single_dense_group_problem(seed, *, k=4, v=96, b=16, l=8, mask=None,
                                wmajor=False, **runner_kw):
    """One batch of documents twice: as a single dense group of one
    stacked batch (the dense family, which the chunk program serves like
    any other group) and as the token lists it was densified from (the
    sparse family: estep.e_step, XLA here), with the chunk runner that
    serves both.  -> (log_beta, dense_groups, token_groups, run)."""
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused
    from oni_ml_tpu.ops import dense_estep

    rng = np.random.default_rng(seed)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32
    )
    widx = jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32)
    cnts = jnp.asarray(rng.integers(1, 5, size=(b, l)), jnp.float32)
    dense = dense_estep.densify(widx, cnts, v)
    if wmajor:
        dense = jnp.transpose(dense)           # [W, B]
    m = (jnp.ones((b,), jnp.float32) if mask is None
         else jnp.asarray(mask, jnp.float32))
    kw = dict(
        num_topics=k, num_terms=v, var_tol=1e-6,
        em_tol=0.0, estimate_alpha=True, dense_wmajor=wmajor,
    )
    kw.update(runner_kw)
    kw.setdefault("var_max_iters", 8)
    kw.setdefault("num_docs", b)
    run = fused.make_chunk_runner(**kw)
    return (log_beta, ((dense[None], m[None]),),
            ((widx[None], cnts[None], m[None]),), run)


def _assert_same_chunk(dense, tokens, steps):
    assert int(dense.steps_done) == int(tokens.steps_done) == steps
    np.testing.assert_allclose(dense.lls[:steps], tokens.lls[:steps],
                               rtol=LL_RTOL)
    np.testing.assert_allclose(np.exp(dense.log_beta),
                               np.exp(tokens.log_beta), atol=BETA_ATOL)
    np.testing.assert_allclose(dense.log_beta, tokens.log_beta,
                               atol=LOG_BETA_ATOL)
    np.testing.assert_allclose(dense.alpha, tokens.alpha, rtol=ALPHA_RTOL)
    np.testing.assert_allclose(dense.gammas[0], tokens.gammas[0],
                               rtol=GAMMA_RTOL)
    np.testing.assert_array_equal(dense.doc_sweeps, tokens.doc_sweeps)


def test_single_dense_group_matches_token_list_chunk_runner():
    """A single dense group of one batch against the token lists it was
    densified from — same likelihood trajectory, beta, alpha, gammas —
    including across a warm chunk boundary."""
    import jax.numpy as jnp

    log_beta, dense, tokens, run = _single_dense_group_problem(
        5, chunk=3, warm_start=True
    )

    a0 = jnp.float32(2.5)
    nan = jnp.float32(np.nan)
    rd = run(log_beta, a0, nan, dense, 3)
    rt = run(log_beta, a0, nan, tokens, 3)
    _assert_same_chunk(rd, rt, 3)

    # Warm chunk boundary: feed each family its own carry, compare again.
    rd2 = run(rd.log_beta, rd.alpha, rd.ll_prev, dense, 2, rd.gammas, True)
    rt2 = run(rt.log_beta, rt.alpha, rt.ll_prev, tokens, 2, rt.gammas,
              True)
    _assert_same_chunk(rd2, rt2, 2)
    # Warm start engaged: no more inner iterations than from cold.
    assert int(rd2.vi_iters[0]) <= int(rd.vi_iters[0])

    # Zero-step chunk returns the input beta bit-exactly.
    rd0 = run(rd.log_beta, rd.alpha, rd.ll_prev, dense, 0, rd.gammas, True)
    assert int(rd0.steps_done) == 0
    np.testing.assert_array_equal(rd0.log_beta, rd.log_beta)


def test_single_dense_group_matches_token_lists_wmajor():
    """Same equivalence under the W-major corpus layout."""
    import jax.numpy as jnp

    log_beta, dense, tokens, run = _single_dense_group_problem(
        9, chunk=4, warm_start=True, wmajor=True
    )
    a0, nan = jnp.float32(2.5), jnp.float32(np.nan)
    _assert_same_chunk(run(log_beta, a0, nan, dense, 4),
                       run(log_beta, a0, nan, tokens, 4), 4)


def test_single_dense_group_masked_docs_and_cold_start():
    """Padded (masked-out) documents must not contribute to beta or the
    likelihood in either family, and warm_start=False must agree with no
    gamma carry."""
    import jax.numpy as jnp

    from oni_ml_tpu.ops import dense_estep

    mask = [1, 1, 1, 1, 1, 0, 0, 0]
    log_beta, dense, tokens, run = _single_dense_group_problem(
        13, k=3, v=64, b=8, l=6, mask=mask, chunk=3,
        var_max_iters=6, warm_start=False, num_docs=5,
    )
    a0, nan = jnp.float32(2.5), jnp.float32(np.nan)
    rd = run(log_beta, a0, nan, dense, 3)
    rt = run(log_beta, a0, nan, tokens, 3)
    # a masked row's gamma is whatever the family leaves there
    live = np.asarray(mask, bool)
    rd = rd._replace(gammas=(rd.gammas[0][:, live],))
    rt = rt._replace(gammas=(rt.gammas[0][:, live],))
    _assert_same_chunk(rd, rt, 3)

    # Masked docs truly inert: rerunning with the masked rows' counts
    # scrambled must not change beta or the likelihood trajectory.
    rng = np.random.default_rng(99)
    c_arr = np.asarray(
        rng.integers(1, 4, size=(8, 6)), np.float32
    )  # any counts; only rows 5+ differ between the two runs
    w_arr = np.asarray(rng.integers(0, 64, size=(8, 6)), np.int32)
    d1 = dense_estep.densify(jnp.asarray(w_arr), jnp.asarray(c_arr), 64)
    c2 = c_arr.copy()
    c2[5:] = rng.integers(10, 50, size=(3, 6))
    d2 = dense_estep.densify(jnp.asarray(w_arr), jnp.asarray(c2), 64)
    m = jnp.asarray(mask, jnp.float32)
    ra = run(log_beta, a0, nan, ((d1[None], m[None]),), 3)
    rb = run(log_beta, a0, nan, ((d2[None], m[None]),), 3)
    np.testing.assert_allclose(rb.lls, ra.lls, rtol=1e-6)
    np.testing.assert_allclose(rb.log_beta, ra.log_beta, atol=1e-6)


@pytest.mark.parametrize("seed,k,v,b,l,warm", [
    (21, 3, 100, 8, 5, True),    # v below the tile (pads 100 -> 128)
    (22, 2, 128, 8, 11, False),  # v exactly on the 128-lane tile
    (23, 5, 200, 16, 7, True),   # v off-tile (pads to 256), odd K
    (24, 6, 32, 32, 4, False),   # tiny model, wider batch, cold start
])
def test_single_dense_group_fuzz_shapes(seed, k, v, b, l, warm):
    """Shape sweep through the dense-vs-token-list equivalence — guards
    padding-width interactions (v on/off the 128-lane tile), odd K,
    and both warm/cold starts at shapes the fixed tests don't hit.
    (B < 8 is NOT in the sweep: the kernel's doc block needs 8
    sublanes — pinned as a clean refusal below.)"""
    import jax.numpy as jnp

    log_beta, dense, tokens, run = _single_dense_group_problem(
        seed, k=k, v=v, b=b, l=l, chunk=2, warm_start=warm,
    )
    a0, nan = jnp.float32(2.5), jnp.float32(np.nan)
    _assert_same_chunk(run(log_beta, a0, nan, dense, 2),
                       run(log_beta, a0, nan, tokens, 2), 2)


def test_dense_group_sub8_batch_refuses_cleanly():
    """The dense kernel's doc block needs 8 sublanes, so a B=4 dense
    group must fail with the explicit no-VMEM-feasible-block error —
    not silently mis-tile.  (In production the trainer's plan checks
    feasibility per batch shape and routes such shapes to the token
    lists before any dense group exists.)"""
    import jax.numpy as jnp

    log_beta, dense, _, run = _single_dense_group_problem(
        25, k=3, v=64, b=4, l=4, chunk=2, warm_start=False,
    )
    with pytest.raises(ValueError, match="no VMEM-feasible doc block"):
        run(log_beta, jnp.float32(2.5), jnp.float32(np.nan), dense, 2)


def test_host_sync_every_bounds_dispatch_without_changing_results(
    problem, monkeypatch
):
    """host_sync_every caps EM iterations per device dispatch
    independently of fused_em_chunk (likelihood.dat / progress stream at
    least that often — the crash-safety note in config.py), and the
    trajectory is unchanged: the chunk program just runs with a smaller
    dynamic step count."""
    from oni_ml_tpu.models import fused

    steps_seen = []
    orig = fused.make_chunk_runner

    def counting_maker(**kw):
        runner = orig(**kw)

        def counting(log_beta, alpha, ll_prev, groups, n_steps, *a, **k):
            steps_seen.append(int(n_steps))
            return runner(log_beta, alpha, ll_prev, groups, n_steps,
                          *a, **k)

        return counting

    monkeypatch.setattr(fused, "make_chunk_runner", counting_maker)
    base = run(problem, em_max_iters=6, em_tol=0.0, fused_em_chunk=64)
    assert steps_seen == [6]  # one dispatch covers the whole fit

    steps_seen.clear()
    synced = run(problem, em_max_iters=6, em_tol=0.0, fused_em_chunk=64,
                 host_sync_every=2)
    assert steps_seen == [2, 2, 2]  # bounded dispatches, same total
    assert synced.em_iters == base.em_iters == 6
    np.testing.assert_allclose(
        [ll for ll, _ in synced.likelihoods],
        [ll for ll, _ in base.likelihoods], rtol=1e-6,
    )
    np.testing.assert_allclose(synced.log_beta, base.log_beta, atol=1e-5)


# ---------------------------------------------------------------------------
# A process keeps the programs its fits build (fused._chunk_program,
# fused.densify_stack)
# ---------------------------------------------------------------------------

DENSE_CFG = dict(num_topics=4, alpha_init=2.5, seed=3, em_max_iters=5,
                 em_tol=0.0, batch_size=16, min_bucket_len=4,
                 dense_em="on")


def _recorded_fit(corpus, cfg, yield_hook=None):
    """One fit through the trainer under its own Recorder ->
    (result, its spans in start order)."""
    from oni_ml_tpu.telemetry import spans

    batches = make_batches(corpus, batch_size=cfg.batch_size,
                           min_bucket_len=cfg.min_bucket_len)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        result = LDATrainer(
            cfg, num_terms=corpus.num_terms, yield_hook=yield_hook,
        ).fit(batches, corpus.num_docs)
    return result, sorted(rec.events, key=lambda e: e["start_ns"])


def _span_args(events, name):
    return [e["args"] for e in events if e["name"] == name]


def _assert_same_fit(a, b):
    np.testing.assert_array_equal(a.log_beta, b.log_beta)
    np.testing.assert_array_equal(a.gamma, b.gamma)
    assert a.alpha == b.alpha
    assert a.likelihoods == b.likelihoods


def test_second_fit_reuses_the_programs_of_the_first(problem):
    """Two fits of one corpus and config in one process: the second asks
    jax for no executable and traces nothing, says so on its spans, and
    is the first fit (and a fit with nothing kept) to the bit."""
    from oni_ml_tpu.models import fused
    from oni_ml_tpu.plans import warmup
    from oni_ml_tpu.telemetry import spans

    warmup.setup_compilation_cache()
    cfg = LDAConfig(**DENSE_CFG)
    fused.clear_programs()
    first = train_corpus(problem, cfg)

    before = warmup.compile_counts()
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        second = train_corpus(problem, cfg)
    delta = warmup.counts_delta(before)
    assert delta["compile_requests"] == 0 and delta["traces"] == 0
    assert delta["trace_s"] == 0.0
    _assert_same_fit(second, first)
    events = sorted(rec.events, key=lambda e: e["start_ns"])
    assert [a["program"] for a in _span_args(events, "fit.runner")] == [
        "reused"]
    assert [a["program"] for a in _span_args(events, "fit.densify")] == [
        "reused"]
    dispatches = _span_args(events, "em.run_chunk")
    assert [a["first"] for a in dispatches] == [True] + [False] * (
        len(dispatches) - 1)

    fused.clear_programs()
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        fresh = train_corpus(problem, cfg)
    assert [a["program"] for a in _span_args(rec.events, "fit.runner")] == [
        "built"]
    _assert_same_fit(fresh, first)


@pytest.mark.parametrize("family", ["dense", "compact"])
def test_f32_fit_stores_what_the_exact_reader_would_without_reading(
        problem, family, monkeypatch):
    """Both dense families ask the one storage gate; at f32 it sorts no
    token (the exact reader raises here) and the fit is, to the bit, the
    fit whose gate sorted every token as the parent's did."""
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.telemetry import spans

    cfg = LDAConfig(**DENSE_CFG)
    if family == "compact":
        cfg = LDAConfig(**dict(DENSE_CFG, dense_em="auto"))
        monkeypatch.setenv("ONI_ML_TPU_ESTEP", "compact")
    gate = dense_estep.corpus_store_dtype
    asked = []

    def every_token_sorted(batches, precision):
        cell_max = max(dense_estep.max_dense_cell(b.word_idx, b.counts)
                       for b in batches)
        return dense_estep.corpus_dtype(cell_max, precision), "exact", 0

    def recording(batches, precision):
        asked.append(precision)
        return gate(batches, precision)

    def refuse(word_idx, counts):
        raise AssertionError("an f32 fit sorted its tokens")

    with monkeypatch.context() as m:
        m.setattr(dense_estep, "corpus_store_dtype", every_token_sorted)
        want = train_corpus(problem, cfg)
    monkeypatch.setattr(dense_estep, "corpus_store_dtype", recording)
    monkeypatch.setattr(dense_estep, "max_dense_cell", refuse)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        got = train_corpus(problem, cfg)
    _assert_same_fit(got, want)
    # the compact plan's item size comes from the same answer
    assert asked == ["f32"]
    plan, = _span_args(rec.events, "fit.plan")
    assert plan["kernel"].startswith(family)
    assert (plan["cell_scan"], plan["scan_tokens"]) == ("none", 0)
    # a copy and a put a shape group, in both families; the compact
    # family's device densify lies between the two, under neither
    (stack,) = [e for e in rec.events if e["name"] == "fit.stack"]
    inside = [e for e in rec.events if e["parent"] == stack["id"]]
    assert [e["name"] for e in inside] == (
        ["fit.stack.copy", "fit.stack.put"] * stack["args"]["groups"])
    copied = sum(e["args"]["bytes"] for e in inside[0::2])
    put = sum(e["args"]["bytes"] for e in inside[1::2])
    assert copied == stack["args"]["h2d_bytes"]
    assert (put == copied) == (family == "dense")
    # what the host wrote: everything it remapped on the compact path,
    # the masks alone where a dense group is a view of the batches' buffer
    wrote = sum(e["args"]["copied_bytes"] for e in inside[0::2])
    assert wrote == stack["args"]["copied_bytes"]
    batches = make_batches(problem, batch_size=cfg.batch_size,
                           min_bucket_len=cfg.min_bucket_len,
                           pad_multiple=8)
    assert wrote == (copied if family == "compact" else
                     sum(b.doc_mask.nbytes for b in batches))


def _sparse_chunk_problem(seed=7, k=3, v=40, b=8, l=6):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32)
    groups = ((
        jnp.asarray(rng.integers(0, v, size=(2, b, l)), jnp.int32),
        jnp.asarray(rng.integers(1, 4, size=(2, b, l)), jnp.float32),
        jnp.ones((2, b), jnp.float32),
    ),)
    kw = dict(num_docs=2 * b, num_topics=k, num_terms=v, chunk=3,
              var_max_iters=6, var_tol=1e-6, em_tol=1e-9,
              estimate_alpha=True, warm_start=True, alpha_max_iters=8)
    return log_beta, groups, kw


def _run_chunk(runner, log_beta, groups):
    import jax.numpy as jnp

    res = runner(log_beta, jnp.float32(2.5), jnp.float32(np.nan), groups, 3)
    return [np.asarray(x) for x in (res.log_beta, res.alpha, res.lls,
                                    res.gammas[0])]


def _slow_m_step(ss):
    from oni_ml_tpu.ops import estep

    return estep.m_step(ss)


@pytest.mark.parametrize("change", [
    dict(num_docs=17), dict(em_tol=1e-3), dict(var_max_iters=5),
    dict(alpha_max_iters=100), dict(warm_start=False),
    dict(estimate_alpha=False),
    dict(compiler_options={"xla_embed_ir_in_executable": True}),
    dict(m_step_fn=_slow_m_step), dict(env="xla"),
], ids=lambda c: next(iter(c)))
def test_program_key_builds_for_anything_the_trace_reads(change, monkeypatch):
    """Each value the chunk program's trace reads is part of its key: a
    change builds a new program, and that program computes what a build
    with nothing kept computes."""
    from oni_ml_tpu.models import fused

    log_beta, groups, kw = _sparse_chunk_problem()
    fused.clear_programs()
    base = fused.make_chunk_runner(**kw)
    assert base.program == "built"
    assert fused.make_chunk_runner(**kw).jitted is base.jitted

    change = dict(change)
    if "env" in change:
        monkeypatch.setenv("ONI_ML_TPU_ESTEP", change.pop("env"))
    changed = fused.make_chunk_runner(**dict(kw, **change))
    assert changed.program == "built"
    assert changed.jitted is not base.jitted
    again = fused.make_chunk_runner(**dict(kw, **change))
    assert again.program == "reused" and again.jitted is changed.jitted
    got = _run_chunk(changed, log_beta, groups)

    fused.clear_programs()
    fresh = fused.make_chunk_runner(**dict(kw, **change))
    assert fresh.program == "built" and fresh.jitted is not changed.jitted
    for a, b in zip(got, _run_chunk(fresh, log_beta, groups)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("change", [
    dict(seed=4), dict(em_max_iters=3), dict(host_sync_every=2),
    dict(yield_hook=True),
], ids=lambda c: next(iter(c)))
def test_program_key_reuses_across_what_the_trace_never_sees(problem, change):
    """The seed, the iteration cap, the sync cadence and the preemption
    hook are not in the traced program: a fit that differs in one of them
    reuses it, and still computes its own result."""
    from contextlib import nullcontext

    from oni_ml_tpu.models import fused

    fused.clear_programs()
    _, events = _recorded_fit(problem, LDAConfig(**DENSE_CFG))
    assert _span_args(events, "fit.runner")[0]["program"] == "built"

    change = dict(change)
    slots = []

    def hook():
        slots.append(1)
        return nullcontext()

    yield_hook = hook if change.pop("yield_hook", False) else None
    cfg = LDAConfig(**dict(DENSE_CFG, **change))
    got, events = _recorded_fit(problem, cfg, yield_hook=yield_hook)
    assert _span_args(events, "fit.runner")[0]["program"] == "reused"
    assert len(slots) == (len(_span_args(events, "em.run_chunk"))
                          if yield_hook else 0)

    fused.clear_programs()
    want, events = _recorded_fit(problem, cfg)
    assert _span_args(events, "fit.runner")[0]["program"] == "built"
    _assert_same_fit(got, want)


def test_unhashable_argument_builds_a_fresh_program_each_time():
    """A value the key cannot hash is no error: the call builds its own
    program, as every call once did, and keeps nothing."""
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused

    log_beta, groups, kw = _sparse_chunk_problem()
    fused.clear_programs()
    want = _run_chunk(fused.make_chunk_runner(**kw), log_beta, groups)
    kept = len(fused._PROGRAMS)
    kw["em_tol"] = jnp.float32(kw["em_tol"])      # a jax array: no hash
    a, b = fused.make_chunk_runner(**kw), fused.make_chunk_runner(**kw)
    assert a.program == b.program == "built" and a.jitted is not b.jitted
    assert len(fused._PROGRAMS) == kept
    for x, y in zip(_run_chunk(a, log_beta, groups), want):
        np.testing.assert_array_equal(x, y)


def _assert_a_fit_keeps_nothing_of_itself(problem, cfg, monkeypatch,
                                          make_mesh=lambda: None):
    """A fit that builds the programs, then one that reuses them under
    spies: the second is the first to the bit, and once its result is
    dropped no array of it is on any device and no batch or trainer is
    alive.  Returns the first fit."""
    import gc
    import weakref

    import jax

    from oni_ml_tpu.models import fused, lda

    fused.clear_programs()
    serial = train_corpus(problem, cfg, mesh=make_mesh())  # constants stay
    gc.collect()
    live0 = len(jax.live_arrays())
    seen = []
    real_batches, real_init = lda.make_batches, lda.LDATrainer.__init__

    def spy_batches(*a, **k):
        out = real_batches(*a, **k)
        seen.extend(weakref.ref(b) for b in out)
        return out

    def spy_init(self, *a, **k):
        seen.append(weakref.ref(self))
        real_init(self, *a, **k)

    monkeypatch.setattr(lda, "make_batches", spy_batches)
    monkeypatch.setattr(lda.LDATrainer, "__init__", spy_init)
    result = train_corpus(problem, cfg, mesh=make_mesh())
    monkeypatch.undo()
    assert len(seen) > 2 and len(fused._PROGRAMS) == 1
    _assert_same_fit(result, serial)
    del result
    gc.collect()
    assert len(jax.live_arrays()) == live0
    assert [r() for r in seen] == [None] * len(seen)
    return serial


def test_program_table_is_bounded_and_keeps_nothing_of_a_fit(
        problem, monkeypatch):
    """More distinct programs than the table holds leave exactly its size,
    the oldest gone; a fit that has returned leaves no array on the device
    and no batch or trainer alive; two threads asking for one program at
    once build one, and both fits are the serial fit."""
    import threading

    from oni_ml_tpu.models import fused

    _, _, kw = _sparse_chunk_problem()
    fused.clear_programs()
    for n in range(fused._PROGRAMS_MAX + 3):
        assert fused.make_chunk_runner(
            **dict(kw, num_docs=100 + n)).program == "built"
    assert len(fused._PROGRAMS) == fused._PROGRAMS_MAX
    assert fused.make_chunk_runner(
        **dict(kw, num_docs=100 + n)).program == "reused"
    assert fused.make_chunk_runner(**dict(kw, num_docs=100)).program == "built"
    assert len(fused._PROGRAMS) == fused._PROGRAMS_MAX

    # -- retention --------------------------------------------------------
    cfg = LDAConfig(**DENSE_CFG)
    serial = _assert_a_fit_keeps_nothing_of_itself(problem, cfg, monkeypatch)

    # -- two threads, one program -----------------------------------------
    fused.clear_programs()
    gate = threading.Barrier(2)
    out = {}

    def fit(i):
        gate.wait()
        out[i] = _recorded_fit(problem, cfg)

    threads = [threading.Thread(target=fit, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert len(fused._PROGRAMS) == 1
    assert sorted(_span_args(out[i][1], "fit.runner")[0]["program"]
                  for i in range(2)) == ["built", "reused"]
    for i in range(2):
        _assert_same_fit(out[i][0], serial)


def test_program_table_under_many_threads():
    """More threads than cores asking for a few programs at once, the
    interpreter switching as often as it can: each program is built once
    and every other asker gets that one."""
    import os
    import sys
    import threading

    from oni_ml_tpu.models import fused

    _, _, kw = _sparse_chunk_problem()
    keys = range(fused._PROGRAMS_MAX // 2)
    workers = 4 * (os.cpu_count() or 4)
    fused.clear_programs()
    gate = threading.Barrier(workers)
    got = [[] for _ in range(workers)]

    def ask(i):
        gate.wait()
        for _ in range(20):
            for n in keys:
                r = fused.make_chunk_runner(**dict(kw, num_docs=200 + n))
                got[i].append((n, r.program, r.jitted))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    asked = [x for per in got for x in per]
    assert len(asked) == workers * 20 * len(keys)
    for n in keys:
        mine = [(tag, jitted) for m, tag, jitted in asked if m == n]
        assert sum(tag == "built" for tag, _ in mine) == 1
        assert len({id(jitted) for _, jitted in mine}) == 1
    assert len(fused._PROGRAMS) == len(keys)


# ---------------------------------------------------------------------------
# The same under a mesh (PR 29): each device densifies its own documents, and
# the callables a mesh fit builds are the same objects for the same mesh
# ---------------------------------------------------------------------------

def _mesh(data=4, first=0):
    import jax

    from oni_ml_tpu.parallel import make_mesh

    return make_mesh(data=data, model=1,
                     devices=jax.devices()[first:first + data])


MESH_CFG = dict(DENSE_CFG, batch_size=32)


@pytest.mark.parametrize("wmajor", [False, True],
                         ids=["rowmajor", "wmajor"])
def test_densify_under_a_mesh_keeps_its_inputs_document_sharding(wmajor):
    """The compiled densify program of a `data=4` mesh holds no collective
    (every device scatters its own rows) and its output is sharded over
    `data` on the document axis, as its inputs are; the values are the
    one-device program's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oni_ml_tpu.models import fused

    mesh = _mesh()
    rng = np.random.default_rng(5)
    nb, b, length, v = 3, 32, 6, 40
    widx = rng.integers(0, v, size=(nb, b, length)).astype(np.int32)
    cnts = rng.integers(0, 4, size=(nb, b, length)).astype(np.float32)
    docs = NamedSharding(mesh, P(None, "data"))
    args = (jax.device_put(widx, docs), jax.device_put(cnts, docs))
    kw = dict(num_terms=v, width=None, dtype=jnp.float32, wmajor=wmajor)

    text = fused.densify_stack.lower(*args, mesh=mesh, **kw).compile(
    ).as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective

    out = fused.densify_stack(*args, mesh=mesh, **kw)
    want = NamedSharding(
        mesh, P(None, None, "data") if wmajor else P(None, "data"))
    assert out.sharding.is_equivalent_to(want, out.ndim)
    rows = {s.device.id: s.data.shape for s in out.addressable_shards}
    assert len(rows) == 4
    assert set(rows.values()) == {
        (nb, 128, b // 4) if wmajor else (nb, b // 4, 128)}
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(fused.densify_stack(jnp.asarray(widx), jnp.asarray(cnts),
                                       **kw)))
    # The fault this replaced: left to itself the compiler hands every
    # device the whole dense stack (21.9 GB in the four-chip cell).
    assert fused.densify_stack(*args, **kw).sharding.is_fully_replicated


def test_second_mesh_fit_reuses_the_programs_of_the_first(problem):
    """Two fits on equal meshes in one process: the second asks jax for no
    executable, says `reused` on both spans, counts what the mesh adds on
    its root span, and is the first fit to the bit."""
    from oni_ml_tpu.models import fused
    from oni_ml_tpu.plans import warmup
    from oni_ml_tpu.telemetry import spans

    warmup.setup_compilation_cache()
    cfg = LDAConfig(**MESH_CFG)
    fused.clear_programs()
    first = train_corpus(problem, cfg, mesh=_mesh())

    before = warmup.compile_counts()
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        second = train_corpus(problem, cfg, mesh=_mesh())   # a new Mesh
    delta = warmup.counts_delta(before)
    assert delta["compile_requests"] == 0 and delta["traces"] == 0
    _assert_same_fit(second, first)
    assert [a["program"] for a in _span_args(rec.events, "fit.runner")] == [
        "reused"]
    densify, = _span_args(rec.events, "fit.densify")
    assert densify["program"] == "reused" and densify["sharded"] is True
    assert densify["dense_bytes"] == 4 * densify["dense_bytes_device"]
    root, = _span_args(rec.events, "fit")
    assert root["compile_requests"] == 0 and root["data_shards"] == 4
    assert (root["rows_per_shard_min"] <= problem.num_docs / 4
            <= root["rows_per_shard_max"])
    # one [V, K] statistic, two float32 and two int32 scalars a batch
    batches = make_batches(problem, batch_size=cfg.batch_size,
                           min_bucket_len=cfg.min_bucket_len,
                           pad_multiple=32)
    assert root["allreduce_bytes"] == len(batches) * (
        problem.num_terms * cfg.num_topics * 4 + 16)
    # every stack goes to, and every gamma comes from, the four shards;
    # beta is replicated over them
    puts = _span_args(rec.events, "fit.stack.put")
    d2h = _span_args(rec.events, "fit.readback.d2h")
    assert len(puts) + 1 == len(d2h) and {a["shards"] for a in puts} == {4}
    # the sharded put is handed views of the batches' buffer too
    stack, = _span_args(rec.events, "fit.stack")
    copies = _span_args(rec.events, "fit.stack.copy")
    assert [a["bytes"] for a in copies] == [a["bytes"] for a in puts]
    assert sum(a["bytes"] for a in copies) == stack["h2d_bytes"]
    assert (sum(a["copied_bytes"] for a in copies) == stack["copied_bytes"]
            == sum(b.doc_mask.nbytes for b in batches))
    assert [a["shards"] for a in d2h] == [4] * len(d2h)
    assert d2h[-1]["bytes"] == cfg.num_topics * problem.num_terms * 4

    # without a mesh the same spans say so
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        train_corpus(problem, cfg)
    assert {a["shards"]
            for a in _span_args(rec.events, "fit.stack.put")} == {1}
    assert _span_args(rec.events, "fit.densify")[0]["sharded"] is False
    root, = _span_args(rec.events, "fit")
    assert (root["data_shards"], root["allreduce_bytes"]) == (1, 0)


def test_two_meshes_are_two_programs(problem):
    """The chunk program and the densify programs are keyed by the mesh:
    other devices, or another split, build their own; coming back to a
    mesh reuses what it built."""
    from oni_ml_tpu.models import fused
    from oni_ml_tpu.telemetry import spans

    cfg = LDAConfig(**MESH_CFG)
    fused.clear_programs()
    fused.densify_stack.clear_cache()
    said = []
    for mesh in (_mesh(), _mesh(first=4), _mesh(data=2), _mesh()):
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            train_corpus(problem, cfg, mesh=mesh)
        said.append((_span_args(rec.events, "fit.runner")[0]["program"],
                     _span_args(rec.events, "fit.densify")[0]["program"]))
    assert said == [("built", "built")] * 3 + [("reused", "reused")]
    assert len(fused._PROGRAMS) == 3


def test_a_mesh_fit_keeps_nothing_of_itself(problem, monkeypatch):
    """PR 28's retention test on a mesh: the memoised callables hold the
    mesh alone."""
    _assert_a_fit_keeps_nothing_of_itself(
        problem, LDAConfig(**MESH_CFG), monkeypatch, make_mesh=_mesh)


# -- a group's batches read out of its stack, in place ------------------------

def _three_batch_group(wmajor, k=4, v=96, b=16, l=8):
    """-> (log_beta, the dense group [3, B, W] with its masks, gammas_prev):
    three unlike batches, some documents masked."""
    import jax.numpy as jnp

    from oni_ml_tpu.ops import dense_estep

    rng = np.random.default_rng(11)
    noise = rng.uniform(size=(k, v)) + 1.0 / v
    log_beta = jnp.asarray(
        np.log(noise / noise.sum(-1, keepdims=True)), jnp.float32)
    dense = jnp.stack([
        dense_estep.densify(
            jnp.asarray(rng.integers(0, v, size=(b, l)), jnp.int32),
            jnp.asarray(rng.integers(1, 5, size=(b, l)), jnp.float32), v)
        for _ in range(3)])
    if wmajor:
        dense = jnp.transpose(dense, (0, 2, 1))
    masks = np.ones((3, b), np.float32)
    masks[1, -3:] = masks[2, -5:] = 0.0
    gammas = jnp.asarray(rng.uniform(0.5, 3.0, size=(3, b, k)), jnp.float32)
    return log_beta, (dense, jnp.asarray(masks)), gammas


def _accumulate(wmajor, groups, gammas, log_beta, warm, **kw):
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused

    acc = fused.make_em_accumulator(
        num_topics=log_beta.shape[0], num_terms=log_beta.shape[1],
        var_max_iters=8, var_tol=1e-6, dense_wmajor=wmajor,
        warm_start=True, **kw)
    return jax.jit(acc)(log_beta, jnp.float32(2.5), groups, gammas,
                        jnp.asarray(warm))


def _own_dense_e_step(capable, seen=None):
    """A `dense_e_step_fn` of the caller's own around the dense kernel,
    which declares `_oni_stack_capable` or does not; `seen` collects the
    corpus shape and the keywords of every call."""
    from oni_ml_tpu.ops import dense_estep

    def own(lb, alpha, corpus, m, g_in, warm, **kw):
        if seen is not None:
            seen.append((corpus.shape, sorted(kw)))
        return dense_estep.e_step_dense(
            lb, alpha, corpus, m, var_max_iters=8, var_tol=1e-6,
            interpret=True, gamma_prev=g_in, warm=warm, **kw)

    if capable:
        own._oni_stack_capable = True
    return own


@pytest.mark.parametrize("warm", [False, True], ids=["fresh", "warm"])
@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_group_of_three_batches_is_three_groups_of_one(wmajor, warm):
    """The accumulator over ONE dense group of three batches (the kernel
    reads each batch out of the stack in place) gives what it gives over
    the same batches as three single-batch groups (the direct call on
    `stack[0]`): the same statistics summed in the same order, to what
    two XLA programs of one arithmetic differ by on the CPU (the kernel
    itself is bit for bit: tests/test_dense_estep.py)."""
    from oni_ml_tpu.models import fused

    log_beta, (dense, masks), gammas = _three_batch_group(wmajor)
    assert fused.reads_stack_in_place((dense, masks), None)
    stacked = _accumulate(wmajor, ((dense, masks),), (gammas,), log_beta,
                          warm)
    singles = tuple((dense[n:n + 1], masks[n:n + 1]) for n in range(3))
    assert not any(fused.reads_stack_in_place(g, None) for g in singles)
    apart = _accumulate(wmajor, singles,
                        tuple(gammas[n:n + 1] for n in range(3)), log_beta,
                        warm)
    for got, want in zip(stacked[:3], apart[:3]):
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(
        stacked[3][0], np.concatenate([np.asarray(g) for g in apart[3]]),
        rtol=2e-6)
    assert [int(x) for x in stacked[4:]] == [int(x) for x in apart[4:]]


@pytest.mark.parametrize("capable", [False, True],
                         ids=["per_batch_contract", "declares_stack"])
def test_only_a_callable_that_says_so_is_handed_the_stack(capable):
    """A user's own `dense_e_step_fn` (and the vocab-sharded XLA plan)
    keeps the per-batch contract: `[B, W]` slices and no `batch_index`.
    One that declares `_oni_stack_capable` gets the whole stack and the
    index; both come to the same numbers."""
    import jax

    from oni_ml_tpu.models import fused

    log_beta, (dense, masks), gammas = _three_batch_group(False)
    seen = []
    own = _own_dense_e_step(capable, seen)
    assert fused.reads_stack_in_place((dense, masks), own) is capable
    got = _accumulate(False, ((dense, masks),), (gammas,), log_beta, True,
                      dense_e_step_fn=own)
    assert seen == [(dense.shape, ["batch_index"]) if capable
                    else (dense.shape[1:], [])]
    want = _accumulate(False, ((dense, masks),), (gammas,), log_beta, True)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("capable", [True, False],
                         ids=["in_place", "per_batch_contract"])
def test_chunk_program_slices_no_batch_out_of_a_stack_it_reads_in_place(
        capable):
    """Structure, no chip: the lowered chunk program of a two-batch dense
    group holds no `dynamic_slice` that yields a whole `[B, W]` batch (on
    the chip XLA made each one a copy of the batch, every EM iteration: a
    quarter of the device's time, PERF.md PR 37).  The kernel's own blocks
    are `[8, W]` here, a third of a batch.  A callable under the per-batch
    contract shows that this looks where the slice would be."""
    import re

    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused
    from oni_ml_tpu.ops import dense_estep, estep

    k, v, b, w = 4, 96, 24, 128
    assert dense_estep.pick_block(b, v, k) == 8
    program = fused._build_chunk_program(
        num_docs=2 * b, num_topics=k, num_terms=v, chunk=4, var_max_iters=8,
        var_tol=1e-6, em_tol=0.0, estimate_alpha=True,
        e_step_fn=estep.e_step, m_step_fn=estep.m_step,
        compiler_options=None, dense_wmajor=False, warm_start=True,
        dense_e_step_fn=_own_dense_e_step(capable), dense_precision="f32",
        alpha_max_iters=100)
    f32 = jnp.float32
    groups = ((jax.ShapeDtypeStruct((2, b, w), f32),
               jax.ShapeDtypeStruct((2, b), f32)),)
    text = program.lower(
        jax.ShapeDtypeStruct((k, v), f32), jax.ShapeDtypeStruct((), f32),
        jax.ShapeDtypeStruct((), f32), groups,
        jax.ShapeDtypeStruct((), jnp.int32),
        (jax.ShapeDtypeStruct((2, b, k), f32),),
        jax.ShapeDtypeStruct((), jnp.bool_)).as_text()
    assert "stablehlo.while" in text
    whole_batch = re.findall(
        rf"dynamic_slice.*-> tensor<(?:1x)?{b}x{w}xf32>", text)
    assert bool(whole_batch) is not capable, whole_batch
    # the kernel's own block reads are there either way
    assert re.search(rf"dynamic_slice.*-> tensor<(?:1x)?8x{w}xf32>", text)


def test_groups_of_one_batch_shape_trace_the_kernels_arithmetic_once(
        monkeypatch):
    """Every `pallas_call` traces its kernel anew, and a stack-indexed
    call's operand has its group's NB in its shape, so two groups cannot
    share a scan body's jaxpr as sliced batches did.  What they share is
    `dense_estep._block_e_step`, the kernel's arithmetic under a `jit` of
    its own: one trace for every group whose batches have one shape (on
    the chip's host a kernel's trace is 0.4 s of every process's first
    fit, `setup_s`: PERF.md PR 37)."""
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused
    from oni_ml_tpu.ops import dense_estep

    traces = []
    real = dense_estep._cast_for          # called once a trace of the body

    def counting(precision):
        traces.append(precision)
        return real(precision)

    monkeypatch.setattr(dense_estep, "_cast_for", counting)
    k, v, b, w = 3, 100, 40, 128          # a shape no other test traces
    accumulate = fused.make_em_accumulator(
        num_topics=k, num_terms=v, var_max_iters=4, var_tol=1e-6,
        warm_start=True)
    f32 = jnp.float32
    groups = tuple((jax.ShapeDtypeStruct((nb, b, w), f32),
                    jax.ShapeDtypeStruct((nb, b), f32)) for nb in (3, 2, 1))
    gammas = tuple(jax.ShapeDtypeStruct((nb, b, k), f32) for nb in (3, 2, 1))
    jax.jit(accumulate).lower(
        jax.ShapeDtypeStruct((k, v), f32), jax.ShapeDtypeStruct((), f32),
        groups, gammas, jax.ShapeDtypeStruct((), jnp.bool_))
    assert traces == ["f32"]
