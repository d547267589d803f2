"""The rule that sets how many rows an under-full bucket's batch pads to
(`dense_estep.tail_rows`, applied by `lda._fit_batches` at both sites that
batch a fit with `make_batches`): 8 rows a shard up to 256, past that the
128-lane tile, so that a bucketed day's tails admit the W-major block and
the plan keeps the W-major kernel.  Where no tail passes 256 rows a shard
the batches are the 8-row rule's, array for array."""

import jax
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import Corpus, make_batches
from oni_ml_tpu.models import LDATrainer
from oni_ml_tpu.models import lda
from oni_ml_tpu.ops import dense_estep
from oni_ml_tpu.parallel import make_mesh, sharded

import reference_lda as ref
from test_lda import corpus_from_docs


# (documents in the tail, data shards, batch size) -> rows
RULE = {
    "one_batch_of_8": ((8, 1, None), 8),
    "whole_block_256": ((256, 1, None), 256),
    "past_the_whole_block": ((257, 1, None), 384),
    # 31 lane tiles: 3,968, not 4,096
    "flow20_fit_bucket_16": ((3952, 1, None), 3968),
    "flow20_fit_bucket_256": ((3352, 1, None), 3456),
    "flow20_fit_dp4_bucket_16": ((15808, 4, None), 15872),
    "flow20_fit_dp4_bucket_256": ((13408, 4, None), 13824),
    "flow20_fit_dp4_bucket_128": ((32, 4, None), 32),
    "ragged_under_a_shard_tile": ((5, 4, None), 32),
    "capped_by_the_batch": ((3000, 1, 3072), 3072),
    "capped_below_the_lane_tile": ((1000, 1, 1000), 1000),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_tail_rows(case):
    (n, data, batch), want = RULE[case]
    got = dense_estep.tail_rows(n, data, batch)
    assert got == want
    rows = got // data
    assert got % data == 0 and rows % 8 == 0 or got == batch
    # every tail the rule leaves uncapped admits a W-major block
    if got != batch:
        assert dense_estep.pick_block_w(rows, 8192, 20) is not None


def _census(docs_by_len: dict, v: int = 40, seed: int = 0) -> Corpus:
    """A corpus of `n` documents of `length` distinct-enough tokens for each
    (length: n): count-1 tokens, word ids drawn from the seed."""
    rng = np.random.default_rng(seed)
    lens = np.concatenate([np.full(n, length, np.int64)
                           for length, n in docs_by_len.items()])
    rng.shuffle(lens)
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    return Corpus(
        doc_names=[f"d{i}" for i in range(len(lens))],
        vocab=[f"w{i}" for i in range(v)],
        doc_ptr=ptr,
        word_idx=rng.integers(0, v, int(ptr[-1])).astype(np.int32),
        counts=np.ones(int(ptr[-1]), np.int32),
    )


# census shaped like a cell's day: length -> documents, scaled by the data
# shards; the batch; the batch shapes the 8-row rule gives and the tail rule
# gives, and `tail_pad_rows`
FLOW20_FIT = {10: 3952, 20: 8192, 100: 8, 200: 3352}
CENSUS = {
    "flow20_fit": (FLOW20_FIT, 1, 4096,
                   ((3952, 16), (3968, 16)), ((3352, 256), (3456, 256)), 120),
    "flow20_fit_dp4": (FLOW20_FIT, 4, 16384,
                       ((15808, 16), (15872, 16)),
                       ((13408, 256), (13824, 256)), 480),
}


def _plan(batches, cfg, data):
    """The plan `train_corpus` makes over `batches` with the dense family
    forced (the CPU takes none on auto); nothing is placed."""
    if data == 1:
        trainer = LDATrainer(cfg, num_terms=40)
    else:
        mesh = make_mesh(data=data, model=1, devices=jax.devices()[:data])
        trainer = LDATrainer(cfg, num_terms=40, mesh=mesh,
                             e_step_fn=sharded.make_data_parallel_e_step(mesh))
    return trainer._plan_estep(batches)


@pytest.mark.parametrize("cell", sorted(CENSUS))
def test_a_flow20_census_plans_the_wmajor_kernel(cell):
    law, data, batch, (old16, new16), (old256, new256), added = CENSUS[cell]
    corpus = _census({length: n * data for length, n in law.items()})
    cfg = LDAConfig(num_topics=4, batch_size=batch, estep_engine="dense")
    batches, tail_pad = lda._fit_batches(corpus, cfg, data)
    shapes = {b.word_idx.shape for b in batches}
    assert {new16, new256} <= shapes and not {old16, old256} & shapes
    assert tail_pad == added == sum(
        b.word_idx.shape[0] for b in batches) - sum(
        b.word_idx.shape[0] for b in make_batches(
            corpus, batch, min_bucket_len=16, pad_multiple=8 * data))
    suffix = "" if data == 1 else "_shard_map"
    assert _plan(batches, cfg, data).kernel == "dense_wmajor" + suffix
    # the 8-row rule's tails refuse the W-major block: the parent's plan
    parent = make_batches(corpus, batch, min_bucket_len=16,
                          pad_multiple=8 * data)
    assert {old16, old256} <= {b.word_idx.shape for b in parent}
    assert _plan(parent, cfg, data).kernel == "dense_rowmajor" + suffix


# census of a cell whose tails the rule leaves alone: law, batch
BYPASSED = {
    # flow20_est_files: flow20_fit's day in batches of 1,024
    "flow20_est_files": ({10: 3952, 20: 8192, 100: 8, 200: 3352}, 1024),
    # flow20_bigday_fit: every bucket over a batch but one tail of 24
    "flow20_bigday_fit": ({10: 12288, 20: 8200, 100: 24, 200: 12300}, 4096),
}


@pytest.mark.parametrize("cell", sorted(BYPASSED))
def test_a_census_without_a_tail_past_256_rows_batches_as_before(cell):
    law, batch = BYPASSED[cell]
    corpus = _census(law)
    cfg = LDAConfig(num_topics=4, batch_size=batch)
    got, tail_pad = lda._fit_batches(corpus, cfg, 1)
    want = make_batches(corpus, batch, min_bucket_len=16, pad_multiple=8)
    assert tail_pad == 0
    assert [b.word_idx.shape for b in got] == [b.word_idx.shape for b in want]
    for g, w in zip(got, want):
        for field in ("word_idx", "counts", "doc_index", "doc_mask"):
            np.testing.assert_array_equal(getattr(g, field),
                                          getattr(w, field))


def test_the_wmajor_fit_on_the_new_tails_is_the_rowmajor_fit(monkeypatch):
    """A bucketed day whose tail passes 256 rows (330 documents in batches
    of 512: 336 rows by the 8-row rule, 384 by the tail rule) fits W-major
    to what the row-major kernel fits on the 8-row rule's batches, at the
    tolerances the layout parity tests hold (tests/test_dense_estep.py)."""
    v = 64
    docs, _ = ref.make_synthetic_corpus(
        num_docs=330, num_terms=v, num_topics=3, seed=5,
        doc_len_range=(5, 16))
    more, _ = ref.make_synthetic_corpus(
        num_docs=20, num_terms=v, num_topics=3, seed=6,
        doc_len_range=(40, 60))
    corpus = corpus_from_docs(docs + more, v)
    cfg = LDAConfig(num_topics=3, batch_size=512, estep_engine="dense",
                    seed=2, em_max_iters=4, em_tol=0.0)
    new, tail_pad = lda._fit_batches(corpus, cfg, 1)
    assert tail_pad > 0 and max(b.word_idx.shape[0] for b in new) == 384
    old = make_batches(corpus, 512, min_bucket_len=16, pad_multiple=8)
    assert max(b.word_idx.shape[0] for b in old) == 336

    got = LDATrainer(cfg, num_terms=v).fit(new, corpus.num_docs)
    # row-major through the plan's own gate: no shape blocks W-major
    monkeypatch.setattr(dense_estep, "pick_block_w", lambda *a, **kw: None)
    want = LDATrainer(cfg, num_terms=v).fit(old, corpus.num_docs)
    assert got.plan["estep_kernel"]["value"] == "dense_wmajor"
    assert want.plan["estep_kernel"]["value"] == "dense_rowmajor"
    assert got.em_iters == want.em_iters == 4
    np.testing.assert_allclose([ll for ll, _ in got.likelihoods],
                               [ll for ll, _ in want.likelihoods], rtol=1e-4)
    np.testing.assert_allclose(got.log_beta, want.log_beta,
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(got.alpha, want.alpha, rtol=1e-3)
    np.testing.assert_allclose(got.gamma, want.gamma, rtol=2e-3, atol=1e-3)
