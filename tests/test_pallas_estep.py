"""Pallas E-step kernel vs the XLA path.

The kernel must agree with estep.e_step to fixed-point tolerance: same
converged gammas, suff-stats, ELBO.  Also covers the in-kernel digamma
(jax.scipy's is not a Mosaic primitive) and block-size selection.

Kernel math runs under interpret mode on EVERY CPU suite run (the
``interpret`` parametrization below); the compiled Mosaic variant of
each parity test is TPU-marked, so a chip-attached run
(ONI_ML_TPU_TESTS_ON_TPU=1) exercises the real lowering with the same
assertions instead of a separate smoke file.
"""

import functools

import numpy as np
import jax.numpy as jnp
import pytest
from jax.scipy.special import digamma

from oni_ml_tpu.ops import estep, pallas_estep


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


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


def test_digamma_matches_scipy():
    # Positive reals across the regimes the recurrence + series cover:
    # tiny (gamma can be ~alpha ~ 1e-3), mid, and large.
    x = jnp.asarray(
        np.concatenate(
            [np.linspace(1e-4, 0.1, 57), np.linspace(0.1, 6, 100),
             np.linspace(6, 500, 100)]
        ),
        jnp.float32,
    )
    ours = np.asarray(pallas_estep.digamma_pos(x))
    ref = np.asarray(digamma(x))
    np.testing.assert_allclose(
        ours, ref, rtol=2e-6, atol=2e-6 * np.maximum(np.abs(ref), 1.0).max()
    )


def test_gammaln_matches_scipy():
    # Same regimes as digamma: the dense kernel evaluates gammaln at
    # gamma entries (>= alpha, can be ~1e-3 after alpha Newton steps)
    # and at row sums (up to ~alpha*K + N_d).
    from jax.scipy.special import gammaln

    # Per-range asserts: a global atol scaled by gammaln(5000) ~ 3.8e4
    # would swamp the small-x regime entirely.
    for lo, hi, n in [(1e-4, 0.1, 57), (0.1, 6.0, 100), (6.0, 5000.0, 100)]:
        x = jnp.asarray(np.linspace(lo, hi, n), jnp.float32)
        ours = np.asarray(pallas_estep.gammaln_pos(x))
        ref = np.asarray(gammaln(x))
        np.testing.assert_allclose(
            ours, ref,
            rtol=4e-6, atol=4e-6 * np.maximum(np.abs(ref), 1.0).max(),
        )


@pytest.mark.parametrize("interpret", INTERPRET)
def test_e_step_parity(problem, interpret):
    lb, a, w, c, m = problem
    ref = estep.e_step(lb, a, w, c, m, var_max_iters=50, var_tol=1e-7,
                       backend="xla")
    pal = pallas_estep.e_step(lb, a, w, c, m, var_max_iters=50, var_tol=1e-7,
                              interpret=interpret)
    sel = np.asarray(m) == 1
    np.testing.assert_allclose(
        np.asarray(pal.gamma)[sel], np.asarray(ref.gamma)[sel],
        rtol=_gamma_tol(interpret), atol=_gamma_tol(interpret),
    )
    np.testing.assert_allclose(
        np.asarray(pal.suff_stats), np.asarray(ref.suff_stats),
        rtol=2e-3, atol=2e-4,
    )
    np.testing.assert_allclose(
        float(pal.likelihood), float(ref.likelihood), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(pal.alpha_ss), float(ref.alpha_ss), rtol=1e-4
    )


@pytest.mark.parametrize("interpret", INTERPRET)
def test_iteration_cap_respected(problem, interpret):
    lb, a, w, c, m = problem
    pal = pallas_estep.e_step(lb, a, w, c, m, var_max_iters=3, var_tol=0.0,
                              interpret=interpret)
    assert int(pal.vi_iters) == 3


def test_pick_block():
    # Power-of-two batches pick the largest VMEM-feasible block.
    assert pallas_estep.pick_block(4096, 128, 20) == 128
    assert pallas_estep.pick_block(16, 16, 4) == 16
    # Non-8-divisible batch: no feasible block -> caller falls back.
    assert pallas_estep.pick_block(12, 16, 4) is None
    # Huge L shrinks the block instead of blowing VMEM.
    bb = pallas_estep.pick_block(4096, 2048, 20)
    assert bb is not None
    assert pallas_estep._vmem_estimate(bb, 2048, 20) <= pallas_estep._VMEM_BUDGET
    # Large K also shrinks the block (the column temporaries scale with
    # K): the (K=50, L=16) case that OOM'd at bb=256 must stay under.
    bb = pallas_estep.pick_block(4096, 16, 50)
    assert bb is not None
    assert pallas_estep._vmem_estimate(bb, 16, 50) <= pallas_estep._VMEM_BUDGET


def test_vmem_estimate_takes_precision():
    """A bf16-stored slab halves the dominant VMEM term, so bf16 block
    picks must size against the real footprint — before _vmem_estimate
    took a precision, bf16 picks sized VMEM as f32 and halved the
    feasible block space (ISSUE 9 satellite)."""
    f32 = pallas_estep._vmem_estimate(64, 2048, 20, "f32")
    b16 = pallas_estep._vmem_estimate(64, 2048, 20, "bf16")
    assert b16 < f32
    # At a slab-dominated shape the bf16 pick reaches a strictly larger
    # block than the f32 pick.
    bb_f32 = pallas_estep.pick_block(4096, 4096, 20, "f32")
    bb_b16 = pallas_estep.pick_block(4096, 4096, 20, "bf16")
    assert bb_b16 is not None
    assert bb_f32 is None or bb_b16 >= bb_f32
    # bf16 blocks sit on the 16-sublane tile.
    assert pallas_estep.pick_block(64, 128, 4, "bf16") % 16 == 0


def test_auto_backend_on_cpu_uses_xla(problem):
    # On the CPU test backend, auto must not take the Pallas path.
    lb, a, w, c, m = problem
    assert estep.resolve_backend("auto", 32, 16, 4, 50)[0] == "xla"
    res = estep.e_step(lb, a, w, c, m, var_max_iters=5, var_tol=1e-6)
    assert np.isfinite(float(res.likelihood))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_warm_start_sparse_paths(problem, backend):
    """gamma_prev/warm through the sparse engines (XLA fixed point and
    the Pallas kernel): warm from the converged gamma must reach the
    same point in fewer iterations, and warm=0 with garbage gamma_prev
    must reproduce the fresh run exactly."""
    lb, alpha, w, c, m = problem
    # var_tol must be reachable in f32 (gamma ~ 10, eps ~ 1e-6 relative)
    # or both runs just hit the cap and the warm speedup is invisible.
    kw = dict(var_max_iters=40, var_tol=1e-5, backend=backend)
    if backend == "pallas":
        # interpret-mode dispatch: call the module directly.
        def run(**extra):
            return pallas_estep.e_step(lb, alpha, w, c, m, 40, 1e-5,
                                       interpret=True, **extra)
    else:
        def run(**extra):
            return estep.e_step(lb, alpha, w, c, m, **kw, **extra)

    fresh = run()
    warm = run(gamma_prev=fresh.gamma, warm=1)
    assert int(warm.vi_iters) < int(fresh.vi_iters)
    np.testing.assert_allclose(np.asarray(warm.gamma),
                               np.asarray(fresh.gamma),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(warm.likelihood),
                               float(fresh.likelihood), rtol=1e-5)

    cold = run(gamma_prev=jnp.full_like(fresh.gamma, 7.0), warm=0)
    np.testing.assert_array_equal(np.asarray(cold.gamma),
                                  np.asarray(fresh.gamma))


@pytest.mark.parametrize("backend", ["xla", "pallas", "sparse", "dense"])
def test_gamma_prev_without_warm_raises(problem, backend):
    """gamma_prev alone must error identically on every backend — never
    silently warm-start on one and crash on another."""
    lb, alpha, w, c, m = problem
    fresh = estep.e_step(lb, alpha, w, c, m, 10, 1e-5, backend="xla")
    with pytest.raises(ValueError, match="warm"):
        estep.e_step(lb, alpha, w, c, m, 10, 1e-5, backend=backend,
                     gamma_prev=fresh.gamma)
