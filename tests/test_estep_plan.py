"""What the fused driver's plan decides, engine by engine.

A characterisation: every expectation below was read off the five gates
the plan was once spread over (all of them now in
`LDATrainer._plan_estep`, chosen among by `LDAConfig.estep_engine`
alone), so the kernel's label, the `fit.plan` span,
`plan_record["estep_kernel"]`, the width the roofline record multiplies
by and the gates' error messages are held letter for letter.

Held elsewhere, not doubled here: the data mesh's `_shard_map` suffix and
`corpus_slices` (tests/test_sharded.py
test_four_shard_fit_is_the_one_device_fit_and_the_plain_reference), the
`cell_scan` / `scan_tokens` of the bf16 storage gate (tests/test_fit_spans.py
test_fit_plan_says_what_the_storage_gate_read) and the root span's `kernel`
(tests/test_fit_spans.py test_fit_span_counts_what_the_fit_ran).
"""

import jax
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import make_batches
from oni_ml_tpu.models import LDATrainer, fused, train_corpus
from oni_ml_tpu.ops import dense_estep, estep
from oni_ml_tpu.parallel import make_mesh
from oni_ml_tpu.telemetry import roofline, spans

import reference_lda as ref
from test_lda import corpus_from_docs

V, K = 40, 4
WIDE_V = 1_000_000     # no VMEM-feasible doc block at the full width
CFG = dict(num_topics=K, alpha_init=2.5, seed=3, em_max_iters=2, em_tol=0.0,
           batch_size=16, min_bucket_len=4)


@pytest.fixture(scope="module")
def corpus():
    docs, _ = ref.make_synthetic_corpus(
        num_docs=48, num_terms=V, num_topics=3, seed=11)
    return corpus_from_docs(docs, V)


def _custom_e_step(*args, **kw):
    from oni_ml_tpu.ops import estep

    return estep.e_step(*args, **kw)


def _token_width(batches, _cfg):
    return sum(b.word_idx.size for b in batches) / sum(
        b.word_idx.shape[0] for b in batches)


def _compact_width(batches, _cfg):
    plan = fused.plan_compact(batches, K)
    by_shape = {}
    for b in batches:
        by_shape[b.word_idx.shape] = by_shape.get(b.word_idx.shape, 0) + 1
    return sum(
        n * shape[0] * wc
        for (shape, n), wc in zip(sorted(by_shape.items()), plan.widths)
    ) / sum(b.word_idx.shape[0] for b in batches)


def _dense_width(_batches, _cfg):
    return dense_estep.padded_width(V)


def _refuse_wmajor(monkeypatch):
    """Row-major through the real gate: no shape blocks W-major."""
    monkeypatch.setattr(dense_estep, "pick_block_w", lambda *a, **kw: None)


# case -> (config, mesh (data, model) or None, vocab_sharded,
#          trainer kwargs or None for train_corpus,
#          kernel, width the roofline record sweeps, corpus slices);
# a case named *_rowmajor runs with W-major refused (`_refuse_wmajor`)
CASES = {
    "config_dense": (dict(estep_engine="dense"), None, False, None,
                     "dense_wmajor", _dense_width, 1),
    "config_dense_rowmajor": (dict(estep_engine="dense"), None, False,
                              None, "dense_rowmajor", _dense_width, 1),
    "config_compact": (dict(estep_engine="compact"), None, False, None,
                       "compact_wmajor", _compact_width, 1),
    "config_compact_rowmajor": (dict(estep_engine="compact"), None, False,
                                None, "compact_rowmajor", _compact_width,
                                1),
    "config_sparse": (dict(estep_engine="sparse"), None, False, None,
                      "sparse_fused", _token_width, 1),
    "config_xla": (dict(estep_engine="xla"), None, False, None, "xla",
                   _token_width, 1),
    "config_pallas": (dict(estep_engine="pallas"), None, False, None,
                      "pallas", _token_width, 1),
    "cpu_default": ({}, None, False, None, "xla", _token_width, 1),
    "rescue_config": (dict(estep_engine="dense"), None, False,
                      dict(num_terms=WIDE_V),
                      "compact_wmajor", _compact_width, 1),
    "custom_e_step": ({}, None, False,
                      dict(num_terms=V, e_step_fn=_custom_e_step),
                      "custom", _token_width, 1),
    "data_mesh_dense": (dict(estep_engine="dense", batch_size=32), (4, 1),
                        False, None, "dense_wmajor_shard_map",
                        _dense_width, 4),
    "data_mesh_dense_rowmajor": (
        dict(estep_engine="dense", batch_size=32), (4, 1),
        False, None, "dense_rowmajor_shard_map", _dense_width, 4),
    "data_mesh_default": (dict(batch_size=32), (4, 1), False, None,
                          "xla", _token_width, 4),
    "data_mesh_xla": (dict(estep_engine="xla", batch_size=32), (4, 1),
                      False, None, "xla", _token_width, 4),
    "vocab_mesh_dense": (dict(estep_engine="dense", batch_size=32), (2, 2),
                         True, None, "dense_vocab_sharded_xla",
                         lambda b, c: V, 4),
    "vocab_mesh_default": (dict(batch_size=32), (2, 2), True, None,
                           "xla_vocab_sharded", _token_width, 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_plan_names_the_kernel_and_the_width_it_sweeps(
        corpus, case, monkeypatch):
    over, mesh_shape, vocab_sharded, direct, kernel, width, slices = (
        CASES[case])
    if case.endswith("_rowmajor"):
        _refuse_wmajor(monkeypatch)
    cfg = LDAConfig(**dict(CFG, **over))
    mesh = None
    if mesh_shape is not None:
        d, m = mesh_shape
        mesh = make_mesh(data=d, model=m, devices=jax.devices()[:d * m])
    emitted = []
    monkeypatch.setattr(
        roofline, "emit",
        lambda phase, wall_s, **kw: emitted.append((phase, kw)))
    monkeypatch.setattr(roofline, "ensure_harvested", lambda *a, **kw: None)
    seen = []
    real = make_batches

    def spy(*a, **kw):
        seen[:] = real(*a, **kw)
        return list(seen)

    rec = spans.Recorder()
    with spans.use_recorder(rec):
        if direct is None:
            from oni_ml_tpu.models import lda

            monkeypatch.setattr(lda, "make_batches", spy)
            result = train_corpus(corpus, cfg, mesh=mesh,
                                  vocab_sharded=vocab_sharded)
            batches = seen
            if cfg.estep_engine == "sparse":    # the bucketed layout's own
                batches = None
        else:
            batches = make_batches(corpus, batch_size=cfg.batch_size,
                                   min_bucket_len=cfg.min_bucket_len)
            result = LDATrainer(cfg, **direct).fit(batches, corpus.num_docs)

    plan, = [e["args"] for e in rec.events if e["name"] == "fit.plan"]
    assert plan["kernel"] == kernel
    assert (plan["cell_scan"], plan["scan_tokens"]) == ("none", 0)
    record = result.plan["estep_kernel"]
    assert record["value"] == kernel
    assert record["platform"] == "cpu"
    assert record["corpus_slices"] == slices
    assert record["corpus_devices"] == [
        dev.id for dev in (jax.devices()[:1] if mesh is None
                           else mesh.devices.flat)]
    assert set(record) == {"value", "corpus_devices", "corpus_slices",
                           "platform"}
    (phase, kw), = emitted
    assert phase == "em.run_chunk"
    assert kw["em_iters"] == 2 and kw["doc_sweeps"] == result.doc_sweeps
    if batches is not None:
        assert plan["batches"] == len(batches)
        rows = sum(b.word_idx.shape[0] for b in batches)
        assert kw["effective_flops"] == pytest.approx(
            (4.0 * result.doc_sweeps + 2.0 * rows * 2)
            * width(batches, cfg) * K, rel=1e-12)


def _one_batch(num_terms=V):
    from oni_ml_tpu.io import Batch

    rng = np.random.default_rng(0)
    return Batch(
        word_idx=rng.integers(0, min(num_terms, 1000), (16, 8)).astype(
            np.int32),
        counts=np.ones((16, 8), np.float32),
        doc_mask=np.ones((16,), np.float32),
        doc_index=np.arange(16),
    )


# case -> (config, trainer kwargs, the whole message)
ERRORS = {
    "dense_with_custom_e_step": (
        dict(estep_engine="dense"),
        dict(num_terms=V, e_step_fn=_custom_e_step),
        "dense E-step forced but a custom e_step_fn is installed"),
    "compact_under_a_mesh": (
        dict(estep_engine="compact"), dict(num_terms=V, mesh=(8, 1)),
        "compact dense E-step forced but a mesh is active (the multi-chip "
        "huge-V story is the vocab-sharded dense plan)"),
    "compact_with_custom_e_step": (
        dict(estep_engine="compact"),
        dict(num_terms=V, e_step_fn=_custom_e_step),
        "compact dense E-step forced but a custom e_step_fn is installed"),
    "no_block_and_no_rescue": (
        dict(estep_engine="dense"), dict(num_terms=WIDE_V, mesh=(8, 1)),
        "dense E-step forced but a batch shape has no VMEM-feasible doc "
        f"block (V={WIDE_V}, K={K}) and the compact-vocab fallback is not "
        "feasible either"),
    "bad_estep_engine": (
        dict(estep_engine="on"), dict(num_terms=V),
        "LDAConfig.estep_engine='on': expected one of auto, dense, "
        "compact, sparse, xla, pallas"),
    "dense_on_a_foreign_vocab_sharded_e_step": (
        dict(estep_engine="dense"),
        dict(num_terms=V, mesh=(2, 2), vocab_sharded=True),
        "dense E-step forced but the vocabulary is sharded and the "
        "installed e_step_fn is not this package's vocab-sharded plan"),
}


@pytest.mark.parametrize("case", sorted(ERRORS))
def test_plan_refuses_with_the_gates_messages(case, monkeypatch):
    over, kw, message = ERRORS[case]
    kw = dict(kw)
    if "mesh" in kw:
        d, m = kw["mesh"]
        kw["mesh"] = make_mesh(data=d, model=m,
                               devices=jax.devices()[:d * m])
        if "e_step_fn" not in kw and not kw.get("vocab_sharded"):
            from oni_ml_tpu.parallel import sharded

            kw["e_step_fn"] = sharded.make_data_parallel_e_step(kw["mesh"])
    with pytest.raises(ValueError) as err:
        LDATrainer(LDAConfig(**dict(CFG, **over)), **kw).fit(
            [_one_batch(kw["num_terms"])], 16)
    assert str(err.value) == message


@pytest.mark.parametrize("case", ["xla", "pallas", "sparse", "data_mesh_xla"])
def test_forced_token_backend_reaches_the_e_step(corpus, case, monkeypatch):
    """A token-list engine is bound into the stock E-step once (into each
    shard's, under a data mesh): every call the fit traces asks
    estep.e_step for that backend, and the plan names it."""
    backend = case.rpartition("_")[2]
    calls = []
    real = estep.e_step

    def spy(*a, backend="auto", **kw):
        calls.append(backend)
        return real(*a, backend=backend, **kw)

    spy._oni_warm_capable = True
    monkeypatch.setattr(estep, "e_step", spy)
    fused.clear_programs()
    cfg = LDAConfig(**dict(CFG, estep_engine=backend, batch_size=32))
    if case.startswith("data_mesh"):
        mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
        result = train_corpus(corpus, cfg, mesh=mesh)
    else:
        batches = make_batches(corpus, batch_size=cfg.batch_size,
                               min_bucket_len=cfg.min_bucket_len)
        result = LDATrainer(cfg, num_terms=V).fit(batches, corpus.num_docs)
    assert calls and set(calls) == {backend}
    assert result.plan["estep_kernel"]["value"] == backend
