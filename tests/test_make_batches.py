"""make_batches against the per-document loop it replaced (PR 32).

`_make_batches_loop` is that loop, unchanged: the oracle.  The array
fill in io/corpus.py has to give the same batches array for array, and
has to run no Python statement once a document."""

import sys

import numpy as np
import pytest

from oni_ml_tpu.io import Batch, Corpus, make_batches
from oni_ml_tpu.io import corpus as corpus_mod


def _bucket_len(n: int, min_bucket: int) -> int:
    if min_bucket < 1:
        raise ValueError(f"min_bucket_len must be >= 1, got {min_bucket}")
    b = min_bucket
    while b < n:
        b *= 2
    return b


def _make_batches_loop(
    corpus: Corpus,
    batch_size: int,
    min_bucket_len: int = 16,
    pad_batch_to_multiple: bool = True,
    pad_multiple: "int | None" = None,
) -> list[Batch]:
    if pad_multiple is None:
        pad_multiple = batch_size
    lengths = corpus.doc_lengths()
    buckets: dict[int, list[int]] = {}
    for d in range(corpus.num_docs):
        # Empty docs (possible only via hand-built corpora) ride the smallest
        # bucket; their zero counts make them inert anyway.
        L = _bucket_len(max(int(lengths[d]), 1), min_bucket_len)
        buckets.setdefault(L, []).append(d)

    batches: list[Batch] = []
    for L in sorted(buckets):
        docs = buckets[L]
        bucket_b = min(batch_size,
                       -(-len(docs) // pad_multiple) * pad_multiple)
        for start in range(0, len(docs), batch_size):
            chunk = docs[start : start + batch_size]
            B = bucket_b if pad_batch_to_multiple else len(chunk)
            widx = np.zeros((B, L), dtype=np.int32)
            cnts = np.zeros((B, L), dtype=np.float32)
            didx = np.zeros((B,), dtype=np.int32)
            mask = np.zeros((B,), dtype=np.float32)
            for i, d in enumerate(chunk):
                lo, hi = int(corpus.doc_ptr[d]), int(corpus.doc_ptr[d + 1])
                n = hi - lo
                widx[i, :n] = corpus.word_idx[lo:hi]
                cnts[i, :n] = corpus.counts[lo:hi]
                didx[i] = d
                mask[i] = 1.0
            batches.append(Batch(widx, cnts, didx, mask))
    return batches


def _corpus(lengths, seed=0, widx_dtype=np.int32, counts_dtype=np.int32,
            ptr_offset=0) -> Corpus:
    """A hand-built corpus of the given distinct-word counts; word ids
    and counts are never 0, so a padded cell cannot pass for a real one."""
    lengths = np.asarray(lengths, np.int64)
    rng = np.random.default_rng(seed)
    ptr = np.zeros(len(lengths) + 1, np.int64)
    np.cumsum(lengths, out=ptr[1:])
    ptr += ptr_offset
    nnz = int(ptr[-1])
    return Corpus(
        [f"ip{d}" for d in range(len(lengths))], [],
        ptr,
        rng.integers(1, 5000, nnz).astype(widx_dtype),
        rng.integers(1, 300, nnz).astype(counts_dtype),
    )


def _formats_ragged() -> Corpus:
    """tests/test_formats.py test_make_batches_covers_all_docs' corpus."""
    rng = np.random.default_rng(1)
    triples = []
    for d in range(37):
        n = int(rng.integers(1, 60))
        for w in rng.choice(100, size=n, replace=False):
            triples.append((f"ip{d}", f"w{w}", int(rng.integers(1, 5))))
    return Corpus.from_word_counts(triples)


def _formats_underfull() -> Corpus:
    """tests/test_formats.py's underfull-bucket corpus: 3 huge documents
    and 2,000 small ones."""
    triples = []
    for d in range(3):
        for w in range(100):
            triples.append((f"big{d}", f"w{w}", 1))
    for d in range(2000):
        triples.append((f"s{d}", f"w{d % 100}", 1))
        triples.append((f"s{d}", f"w{(d + 1) % 100}", 1))
    return Corpus.from_word_counts(triples)


def _heavy_tail(num_docs: int, seed: int) -> np.ndarray:
    """The cells' law of distinct words a document: clients around a
    median of 27, 8% hubs near 280 (PERF.md section 2)."""
    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.lognormal(np.log(27), 0.25, num_docs), 1, 200)
    hubs = rng.random(num_docs) < 0.08
    lengths[hubs] = rng.normal(280, 20, int(hubs.sum())).clip(200, 346)
    return lengths.astype(np.int64)


_EDGES = [16, 17, 32, 33, 512, 513]

# id -> (corpus builder, make_batches keyword arguments)
CASES = {
    "formats_ragged": (_formats_ragged, dict(batch_size=8, min_bucket_len=16)),
    "formats_underfull_pad8": (
        _formats_underfull,
        dict(batch_size=1024, min_bucket_len=16, pad_multiple=8)),
    "formats_underfull_pad_none": (
        _formats_underfull, dict(batch_size=1024, min_bucket_len=16)),
    "power_of_two_edges": (
        lambda: _corpus(_EDGES * 5 + [1, 15], seed=2),
        dict(batch_size=4, pad_multiple=2)),
    "min_bucket_1": (
        lambda: _corpus([1, 2, 3, 4, 5, 8, 9, 1, 2, 64, 65], seed=3),
        dict(batch_size=4, min_bucket_len=1, pad_multiple=2)),
    "min_bucket_24_not_a_power_of_two": (
        lambda: _corpus([1, 24, 25, 48, 49, 96, 97, 192, 193, 30] * 3, seed=4),
        dict(batch_size=8, min_bucket_len=24, pad_multiple=8)),
    "min_bucket_128": (
        lambda: _corpus(_EDGES * 3 + [128, 129, 256, 257], seed=5),
        dict(batch_size=8, min_bucket_len=128, pad_multiple=8)),
    "empty_documents_among_full": (
        lambda: _corpus([0, 5, 0, 0, 40, 17, 0, 33, 0], seed=6),
        dict(batch_size=4, pad_multiple=2)),
    "only_empty_documents": (
        lambda: _corpus([0] * 7, seed=7), dict(batch_size=4)),
    "empty_corpus": (lambda: _corpus([], seed=8), dict(batch_size=8)),
    "one_document": (
        lambda: _corpus([21], seed=9), dict(batch_size=8, pad_multiple=8)),
    "pad_multiple_32": (
        lambda: _corpus(_heavy_tail(700, 10), seed=10),
        dict(batch_size=256, pad_multiple=32)),
    "no_batch_padding": (
        lambda: _corpus(_heavy_tail(300, 11), seed=11),
        dict(batch_size=64, pad_batch_to_multiple=False)),
    "bucket_of_exactly_batch_size": (
        lambda: _corpus([20] * 64 + [40] * 3, seed=12),
        dict(batch_size=64, pad_multiple=8)),
    "bucket_of_batch_size_plus_one": (
        lambda: _corpus([20] * 65 + [40] * 3, seed=13),
        dict(batch_size=64, pad_multiple=8)),
    "bucket_under_pad_multiple": (
        lambda: _corpus([20] * 3 + [300] * 2, seed=14),
        dict(batch_size=64, pad_multiple=8)),
    "int32_counts_int64_word_idx": (
        lambda: _corpus(_heavy_tail(200, 15), seed=15,
                        widx_dtype=np.int64, counts_dtype=np.int32),
        dict(batch_size=32, pad_multiple=8)),
    "float64_counts": (
        lambda: _corpus(_heavy_tail(200, 16), seed=16,
                        counts_dtype=np.float64),
        dict(batch_size=32, pad_multiple=8)),
    "doc_ptr_not_from_zero": (
        lambda: _corpus(_heavy_tail(100, 17), seed=17, ptr_offset=11),
        dict(batch_size=32, pad_multiple=8)),
    "document_longer_than_a_slab": (
        lambda: _corpus(
            [30, corpus_mod._FILL_SLAB_TOKENS + 5, 18, 0, 70], seed=18),
        dict(batch_size=4, pad_multiple=1)),
    # 20,000 documents of about 47 words: the fill's slabs are crossed.
    "heavy_tail_20000": (
        lambda: _corpus(_heavy_tail(20000, 19), seed=19),
        dict(batch_size=4096, pad_multiple=8)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_make_batches_equals_the_loop(case):
    build, kwargs = CASES[case]
    corpus = build()
    want = _make_batches_loop(corpus, **kwargs)
    got = make_batches(corpus, **kwargs)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        for field in ("word_idx", "counts", "doc_index", "doc_mask"):
            a, b = getattr(g, field), getattr(w, field)
            where = f"{case}: batch {i} {field}"
            assert a.dtype == b.dtype, where
            assert a.shape == b.shape, where
            assert a.flags.c_contiguous, where
            assert np.array_equal(a, b), where


def test_make_batches_case_corpora_are_what_they_say():
    """The cases' own premises: the edge case crosses every stated
    power of two, the heavy tail holds the cells' law and more tokens
    than one slab."""
    edges = make_batches(CASES["power_of_two_edges"][0](), batch_size=4,
                         pad_multiple=2)
    assert sorted({b.bucket_len for b in edges}) == [16, 32, 64, 512, 1024]
    tail = _heavy_tail(20000, 19)
    assert 24 <= np.median(tail) <= 30
    assert 0.06 <= (tail >= 200).mean() <= 0.10
    assert tail.sum() > 2 * corpus_mod._FILL_SLAB_TOKENS
    assert CASES["empty_corpus"][0]().num_docs == 0


@pytest.mark.parametrize("min_bucket_len", [0, -4])
def test_make_batches_refuses_a_bucket_floor_under_one(min_bucket_len):
    corpus = _corpus([3, 20], seed=20)
    with pytest.raises(ValueError, match="min_bucket_len must be >= 1"):
        _make_batches_loop(corpus, 8, min_bucket_len=min_bucket_len)
    with pytest.raises(ValueError, match="min_bucket_len must be >= 1"):
        make_batches(corpus, 8, min_bucket_len=min_bucket_len)


def test_make_batches_memoises_nothing():
    """A pure function: a second call returns fresh arrays, and a
    corpus changed in place between calls is batched as it now is."""
    corpus = _corpus(_heavy_tail(50, 21), seed=21)
    first = make_batches(corpus, 16, pad_multiple=8)
    second = make_batches(corpus, 16, pad_multiple=8)
    assert not np.shares_memory(first[0].word_idx, second[0].word_idx)
    assert not hasattr(corpus, "_layout_cache")
    corpus.word_idx[:] = 7
    third = make_batches(corpus, 16, pad_multiple=8)
    assert set(np.unique(third[0].word_idx)) <= {0, 7}
    assert not np.array_equal(first[0].word_idx, third[0].word_idx)


def _py_lines(fn, filename) -> int:
    """Python line events inside `filename` while fn() runs."""
    lines = 0

    def local(frame, event, arg):
        nonlocal lines
        if event == "line":
            lines += 1
        return local

    def tracer(frame, event, arg):
        return local if frame.f_code.co_filename == filename else None

    before = sys.gettrace()
    sys.settrace(tracer)
    try:
        fn()
    finally:
        sys.settrace(before)
    return lines


def test_make_batches_runs_no_statement_once_a_document():
    """No clock: the Python lines io/corpus.py executes do not grow with
    the documents.  2,000 and 16,000 documents in the same three buckets,
    one batch a bucket, both inside one slab of the fill: the same count.
    64,000 documents cross slabs, and pay a few lines a slab."""
    pattern = [2, 5, 20, 33]                   # 15 words a document
    def lines_for(num_docs):
        corpus = _corpus(pattern * (num_docs // 4), seed=22)
        batches = []
        n = _py_lines(
            lambda: batches.extend(
                make_batches(corpus, batch_size=65536, pad_multiple=8)),
            corpus_mod.__file__)
        assert [b.bucket_len for b in batches] == [16, 32, 64]
        return n, -(-int(corpus.doc_ptr[-1]) // corpus_mod._FILL_SLAB_TOKENS)

    small, slabs_small = lines_for(2000)
    large, slabs_large = lines_for(16000)
    assert slabs_small == slabs_large == 1
    assert small == large
    assert small < 200                         # the loop ran 10 a document
    huge, slabs_huge = lines_for(64000)
    assert slabs_huge == 4
    assert 0 < huge - large <= 12 * (slabs_huge - 1)
    # The oracle, for scale: it does grow.
    corpus = _corpus(pattern * 500, seed=22)
    loop = _py_lines(
        lambda: _make_batches_loop(corpus, 65536, pad_multiple=8), __file__)
    assert loop > 10 * 2000
