"""In-memory corpus: first-seen-order vocab/doc ids + CSR token arrays +
padded/bucketed device batches.

The reference builds its corpus in three sequential dict passes
(lda_pre.py:30-94): word ids assigned in first-seen order over
``doc_wc.dat``, doc ids 1-based in first-seen order.  That ordering is part
of the file contract (words.dat / doc.dat line numbers are the join keys
used by lda_post.py:57 linecache lookups), so ``from_word_counts``
reproduces it exactly.

TPU shape discipline: documents are power-law ragged, so we bucket docs by
unique-word count into power-of-two length buckets and pad each bucket to a
fixed batch size.  Every (batch_size, bucket_len) pair is one compiled XLA
program; padding tokens carry count 0 and padding docs are masked, both of
which are arithmetically inert in the E-step (phi * 0 = 0 contributions).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Iterator, Sequence

import numpy as np

from . import formats


@dataclass
class Corpus:
    """Bag-of-words corpus in CSR layout.

    doc_names[d] is the document key (an IP address in the reference's
    pipelines); vocab[w] is the word string.  Token j of document d lives at
    word_idx[doc_ptr[d]:doc_ptr[d+1]] with multiplicity counts[...].
    """

    doc_names: list[str]
    vocab: list[str]
    doc_ptr: np.ndarray  # [D+1] int64
    word_idx: np.ndarray  # [NNZ] int32
    counts: np.ndarray  # [NNZ] int32

    @property
    def num_docs(self) -> int:
        return len(self.doc_ptr) - 1

    @property
    def num_terms(self) -> int:
        return len(self.vocab)

    @property
    def num_tokens(self) -> int:
        return int(self.counts.sum())

    def doc_lengths(self) -> np.ndarray:
        return np.diff(self.doc_ptr)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_word_counts(cls, triples: Iterable[tuple[str, str, int]]) -> "Corpus":
        """Build from ``(ip, word, count)`` triples, assigning ids in
        first-seen order exactly like lda_pre.py:30-77.

        Interning stays a dict pass (it defines the id order), but the
        CSR fill is vectorized: flat (doc, word, count) arrays gathered
        in one ``np.fromiter`` pass each, then a stable argsort by doc
        groups tokens per document while preserving their appearance
        order — the former nested per-doc/per-token Python loop scaled
        with every token of the day."""
        word_ids: dict[str, int] = {}
        doc_ids: dict[str, int] = {}
        d_list: list[int] = []
        w_list: list[int] = []
        c_list: list[int] = []
        for ip, word, count in triples:
            w_list.append(word_ids.setdefault(word, len(word_ids)))
            d = doc_ids.get(ip)
            if d is None:
                d = len(doc_ids)
                doc_ids[ip] = d
            d_list.append(d)
            c_list.append(count)

        nnz = len(d_list)
        d_arr = np.fromiter(d_list, dtype=np.int64, count=nnz)
        widx = np.fromiter(w_list, dtype=np.int32, count=nnz)
        cnts = np.fromiter(c_list, dtype=np.int32, count=nnz)
        perm = np.argsort(d_arr, kind="stable")
        ptr = np.zeros(len(doc_ids) + 1, dtype=np.int64)
        np.cumsum(np.bincount(d_arr, minlength=len(doc_ids)), out=ptr[1:])
        return cls(
            list(doc_ids), list(word_ids), ptr, widx[perm], cnts[perm]
        )

    @classmethod
    def from_features(cls, features) -> "Corpus":
        """Direct featurizer→corpus handoff: build the CSR straight
        from a native feature container's interned tables and
        aggregated id arrays (``wc_ip``/``wc_word``/``wc_count``),
        skipping the word_counts.dat text round-trip entirely — the
        in-process ``run_pipeline`` used to emit ~1.5M triples as text
        in stage_pre only for stage_corpus to re-parse and re-intern
        the identical strings moments later.

        Identical output to ``from_word_counts(features.word_counts())``
        (and therefore to parsing the emitted file): corpus word/doc
        ids are assigned in first-seen order over the aggregated
        triples, which here is a vectorized first-occurrence remap of
        the featurizer's table ids.  Pure-Python containers (no
        ``wc_ip``) route through their triples."""
        wc_ip = getattr(features, "wc_ip", None)
        if wc_ip is None:
            return cls.from_word_counts(features.word_counts())
        wc_word = np.asarray(features.wc_word)
        wc_count = np.asarray(features.wc_count)
        wc_ip = np.asarray(wc_ip)

        def first_seen(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            """(table ids in first-seen order, old->new id map)."""
            uniq, first = np.unique(ids, return_index=True)
            order = uniq[np.argsort(first, kind="stable")]
            remap = np.empty(
                int(uniq.max()) + 1 if len(uniq) else 0, dtype=np.int64
            )
            remap[order] = np.arange(len(order))
            return order, remap

        w_order, w_remap = first_seen(wc_word)
        d_order, d_remap = first_seen(wc_ip)
        d_arr = d_remap[wc_ip] if len(wc_ip) else np.zeros(0, np.int64)
        perm = np.argsort(d_arr, kind="stable")
        ptr = np.zeros(len(d_order) + 1, dtype=np.int64)
        np.cumsum(np.bincount(d_arr, minlength=len(d_order)), out=ptr[1:])
        widx = (
            w_remap[wc_word][perm].astype(np.int32)
            if len(wc_word)
            else np.zeros(0, np.int32)
        )
        word_table = features.word_table
        ip_table = features.ip_table
        return cls(
            [ip_table[int(j)] for j in d_order],
            [word_table[int(j)] for j in w_order],
            ptr,
            widx,
            wc_count[perm].astype(np.int32, copy=False),
        )

    @classmethod
    def from_word_counts_file(cls, path: str) -> "Corpus":
        """Build from a word_counts file, preferring the native (C++)
        ingest when available — identical output, one buffered pass
        (io/native.py); set ONI_ML_TPU_NO_NATIVE=1 to force Python."""
        from . import native

        if native.available():
            return native.load_corpus(path)
        return cls.from_word_counts(formats.read_word_counts(path))

    @classmethod
    def from_model_dat(
        cls, path: str, words_path: str | None = None, docs_path: str | None = None
    ) -> "Corpus":
        ptr, widx, cnts = formats.read_model_dat(path)
        vocab = formats.read_words_dat(words_path) if words_path else [
            str(i) for i in range(int(widx.max()) + 1 if len(widx) else 0)
        ]
        docs = formats.read_doc_dat(docs_path) if docs_path else [
            str(i + 1) for i in range(len(ptr) - 1)
        ]
        return cls(docs, vocab, ptr, widx, cnts)

    def shard(self, start: int, stop: int) -> "Corpus":
        """Contiguous document slice [start, stop) — the distributed-EM
        shard view (parallel/shard_plan.py).  Zero-copy: CSR arrays are
        numpy views and the vocabulary is shared (word ids stay GLOBAL,
        so per-shard suff-stats land in the same [V, K] layout and the
        cross-process allreduce sums them directly).  Doc ids are
        shard-local; callers that scatter into global buffers offset
        `Batch.doc_index` by `start`."""
        if not (0 <= start <= stop <= self.num_docs):
            raise ValueError(
                f"shard [{start}, {stop}) out of range for "
                f"{self.num_docs} documents"
            )
        lo, hi = int(self.doc_ptr[start]), int(self.doc_ptr[stop])
        return Corpus(
            self.doc_names[start:stop],
            self.vocab,
            self.doc_ptr[start:stop + 1] - self.doc_ptr[start],
            self.word_idx[lo:hi],
            self.counts[lo:hi],
        )

    def select(self, doc_indices) -> "Corpus":
        """Sub-corpus of the given documents (shared vocabulary, same
        word ids — models trained on a subset stay comparable/usable
        against the full corpus).  Used by the runner's --eval-holdout
        split."""
        doc_indices = np.asarray(doc_indices, np.int64)
        lens = self.doc_lengths()[doc_indices]
        ptr = np.zeros(len(doc_indices) + 1, np.int64)
        np.cumsum(lens, out=ptr[1:])
        widx = np.empty(int(ptr[-1]), self.word_idx.dtype)
        cnts = np.empty(int(ptr[-1]), self.counts.dtype)
        for j, d in enumerate(doc_indices):
            lo, hi = int(self.doc_ptr[d]), int(self.doc_ptr[d + 1])
            widx[ptr[j]:ptr[j + 1]] = self.word_idx[lo:hi]
            cnts[ptr[j]:ptr[j + 1]] = self.counts[lo:hi]
        return Corpus(
            [self.doc_names[int(d)] for d in doc_indices],
            self.vocab, ptr, widx, cnts,
        )

    def bucket_shapes(
        self,
        min_len: int = 128,
        batch_cap: int = 4096,
        pad_multiple: int = 8,
    ) -> "list[tuple[int, int, int]]":
        """The padded (B, L, real_docs) batch shapes `bucketed_layout`
        with the same parameters would produce — derived from doc
        lengths alone, no packing, so engine feasibility gates can
        check EVERY shape (the VMEM-worst bucket is often a small-B,
        huge-L one, not the largest batch) without paying the
        O(tokens) layout pass.  Pinned equal to the real layout's
        shapes by tests/test_sparse_estep.py."""
        if min_len < 1:
            raise ValueError(f"min_len must be >= 1, got {min_len}")
        lengths = np.maximum(self.doc_lengths(), 1)
        buck = np.maximum(
            min_len, 2 ** np.ceil(np.log2(lengths)).astype(np.int64)
        )
        shapes: list[tuple[int, int, int]] = []
        for L in np.unique(buck):
            n = int((buck == L).sum())
            for start in range(0, n, batch_cap):
                c = min(batch_cap, n - start)
                shapes.append(
                    (-(-c // pad_multiple) * pad_multiple, int(L), c)
                )
        return shapes

    def bucketed_layout(
        self,
        min_len: int = 128,
        batch_cap: int = 4096,
        pad_multiple: int = 8,
    ) -> "BucketedLayout":
        """Pack the corpus into length-sorted power-of-two buckets of
        padded [B, L] word-id/count tiles — the sparse Pallas E-step's
        corpus layout (ops/sparse_estep.py).

        Documents are stable-sorted by token count and binned into
        power-of-two length buckets floored at `min_len` (the 128-lane
        tile by default, so the kernel's [K, BB, L] slab blocks pad no
        lanes); each bucket splits into batches of at most `batch_cap`
        docs, the batch axis padded to a multiple of `pad_multiple`
        (the sublane granularity).  The whole pass is vectorized CSR
        gathers — no per-doc Python loop — and the result is cached on
        this Corpus, keyed by the three parameters.  The returned
        layout's perm/inv_perm restore document order bit-exactly.
        """
        key = (min_len, batch_cap, pad_multiple)
        cache = getattr(self, "_layout_cache", None)
        if cache is None:
            cache = {}
            # Corpus is a plain dataclass; the cache rides as an
            # instance attribute so dataclass equality/replace ignore it.
            object.__setattr__(self, "_layout_cache", cache)
        if key in cache:
            return cache[key]
        if min_len < 1:
            raise ValueError(f"min_len must be >= 1, got {min_len}")
        lengths = self.doc_lengths()
        d = self.num_docs
        # Stable sort by token count: ties keep first-seen doc order, so
        # the layout (and therefore every artifact downstream of a
        # pinned sparse run) is deterministic.
        order = np.argsort(lengths, kind="stable").astype(np.int64)
        # Power-of-two bucket length per doc, floored at min_len
        # (empty docs ride the smallest bucket; their zero counts are
        # arithmetically inert, same rule as make_batches).
        clamped = np.maximum(lengths, 1)
        buck = np.maximum(
            min_len,
            2 ** np.ceil(np.log2(clamped)).astype(np.int64),
        )
        batches: list[Batch] = []
        perm_parts: list[np.ndarray] = []
        for L in np.unique(buck[order]):
            docs = order[buck[order] == L]
            for start in range(0, len(docs), batch_cap):
                chunk = docs[start:start + batch_cap]
                n = len(chunk)
                b = -(-n // pad_multiple) * pad_multiple
                # Vectorized CSR gather: token j of packed row i lives
                # at word_idx[ptr[d_i] + j] while j < len(d_i), else
                # pad (id 0, count 0).
                col = np.arange(int(L), dtype=np.int64)[None, :]
                lens = lengths[chunk][:, None]
                src = np.minimum(
                    self.doc_ptr[chunk][:, None] + col,
                    len(self.word_idx) - 1 if len(self.word_idx) else 0,
                )
                live = col < lens
                widx = np.zeros((b, int(L)), np.int32)
                cnts = np.zeros((b, int(L)), np.float32)
                if len(self.word_idx):
                    widx[:n] = np.where(live, self.word_idx[src], 0)
                    cnts[:n] = np.where(live, self.counts[src], 0)
                didx = np.zeros((b,), np.int32)
                didx[:n] = chunk
                mask = np.zeros((b,), np.float32)
                mask[:n] = 1.0
                batches.append(Batch(widx, cnts, didx, mask))
                perm_parts.append(chunk)
        perm = (
            np.concatenate(perm_parts) if perm_parts
            else np.zeros(0, np.int64)
        )
        inv_perm = np.empty(d, np.int64)
        inv_perm[perm] = np.arange(d, dtype=np.int64)
        layout = BucketedLayout(
            batches=tuple(batches), perm=perm, inv_perm=inv_perm,
            min_len=min_len,
        )
        cache[key] = layout
        return layout

    # -- serialization (reference contracts) --------------------------------

    def save(self, directory: str) -> None:
        """Write words.dat / doc.dat / model.dat into ``directory``."""
        import os

        formats.write_words_dat(os.path.join(directory, "words.dat"), self.vocab)
        formats.write_doc_dat(os.path.join(directory, "doc.dat"), self.doc_names)
        formats.write_model_dat(
            os.path.join(directory, "model.dat"), self.doc_ptr, self.word_idx, self.counts
        )

    def save_atomic(self, directory: str) -> None:
        """`save()` with tmp+rename publication per file — what the
        dataplane's background corpus-checkpoint sink uses.  The write
        window overlaps the whole LDA stage there, so a hard kill
        mid-write must never leave a COMPLETE-looking partial file
        under a contract name that a resumed run's `_stage_done`
        existence check would trust (identical bytes to `save()`,
        pinned by tests/test_dataplane.py)."""
        import os

        def _publish(name, write_fn, *args):
            tmp = os.path.join(directory, name + ".tmp")
            write_fn(tmp, *args)
            os.replace(tmp, os.path.join(directory, name))

        _publish("words.dat", formats.write_words_dat, self.vocab)
        _publish("doc.dat", formats.write_doc_dat, self.doc_names)
        _publish("model.dat", formats.write_model_dat, self.doc_ptr,
                 self.word_idx, self.counts)


@dataclass
class Batch:
    """One padded device batch of documents.

    word_idx[B, L] int32 (0 where padded), counts[B, L] f32 (0 where padded),
    doc_index[B] int32 global doc ids (0 where padded), doc_mask[B] f32.
    """

    word_idx: np.ndarray
    counts: np.ndarray
    doc_index: np.ndarray
    doc_mask: np.ndarray

    @property
    def batch_size(self) -> int:
        return self.word_idx.shape[0]

    @property
    def bucket_len(self) -> int:
        return self.word_idx.shape[1]


@dataclass(frozen=True)
class BucketedLayout:
    """Length-sorted, power-of-two-bucketed packing of a corpus — the
    sparse E-step engine's device layout (ops/sparse_estep.py).

    `batches` are ordinary padded `Batch` tiles, built like
    make_batches' with array operations and no per-doc loop (here a
    stable argsort by token count, then one CSR gather a batch), but
    ordered by length within a bucket, and with the bucket floor at the
    Pallas lane tile (min_len=128 by default) so a [K, BB, L] slab
    block never pads its lane dimension.

    `perm[j]` is the ORIGINAL doc id of the j-th real (unmasked) row in
    packed order; `inv_perm` inverts it, so `values[inv_perm]` restores
    document order bit-exactly from per-row results concatenated in
    layout order (`restore()`).  The layout is cached on the Corpus —
    building it is an O(tokens) host pass that must run once per
    (min_len, batch_cap, pad_multiple), not once per consumer.
    """

    batches: tuple          # tuple[Batch]
    perm: np.ndarray        # [D] int64: packed position -> original doc id
    inv_perm: np.ndarray    # [D] int64: original doc id -> packed position
    min_len: int

    def restore(self, packed_rows: np.ndarray) -> np.ndarray:
        """Per-doc values in packed (layout) order -> original document
        order.  Exact: a pure permutation gather, no arithmetic."""
        packed_rows = np.asarray(packed_rows)
        if packed_rows.shape[0] != len(self.perm):
            raise ValueError(
                f"{packed_rows.shape[0]} packed rows for "
                f"{len(self.perm)} documents"
            )
        return packed_rows[self.inv_perm]


# Tokens one slab of make_batches' fill holds a destination index for:
# 2 MiB of int64, so the index and the cells it scatters into stay in
# cache (the whole day's index at once ran at memory speed, PERF.md PR 32).
_FILL_SLAB_TOKENS = 1 << 18


def make_batches(
    corpus: Corpus,
    batch_size: int,
    min_bucket_len: int = 16,
    pad_batch_to_multiple: bool = True,
    pad_multiple: "int | None" = None,
) -> list[Batch]:
    """Bucket docs by unique-word count, pad to (batch_size, bucket_len).

    Returns batches ordered by bucket then position; the union of doc_index
    over all batches (where doc_mask == 1) is exactly range(num_docs).
    Bucket lengths are min_bucket_len * 2**j; within a bucket documents
    keep corpus order and are cut every `batch_size`.

    With `pad_multiple` set, an under-full bucket pads its batch axis
    to the next multiple of it instead of the full `batch_size` (full
    buckets still pad to batch_size for shape reuse across chunks).
    Under a power-law doc-length distribution (realistic config-3
    corpora: a few hot IPs with huge documents) the tail buckets hold
    a handful of docs each, and padding those to [batch_size,
    bucket_len] costs batch_size/len(docs) times the E-step compute
    and memory for nothing.  `pad_multiple` must be divisible by the
    mesh's data axis so every batch remains shardable — train_corpus /
    train_corpus_online thread it from their mesh; the None default
    keeps the old full-batch_size padding, so direct callers that
    shard over meshes this module can't see stay correct.

    The fill is array operations over the CSR arrays: one stable sort
    of the documents by bucket, each document's first padded cell, and
    per slab of `_FILL_SLAB_TOKENS` tokens one destination index and
    one scatter each of word ids and counts.  Python loops run once a
    bucket, a batch and a slab, never once a document or a token.  A
    pure function: every call batches afresh.  Each field's batches
    are C-contiguous [B, L] views of one buffer, laid bucket by bucket,
    batch after batch, so a bucket's batches are one contiguous
    [NB, B, L] run of it: `fused.stack_batches` hands that run to the
    device as it lies.  The buffer lives while any batch, or any such
    view of a run, does: through the fit's puts, which may still be
    reading it after they return.  Nobody writes to it.
    tests/test_make_batches.py holds it to the per-document loop it
    replaced, array for array.
    """
    if pad_multiple is None:
        pad_multiple = batch_size
    num_docs = corpus.num_docs
    if num_docs == 0:
        return []
    if min_bucket_len < 1:
        raise ValueError(
            f"min_bucket_len must be >= 1, got {min_bucket_len}"
        )
    lengths = corpus.doc_lengths()
    # The bucket lengths up to the longest document, as Python integers,
    # and each document's bucket by comparison against them: exact for
    # any min_bucket_len (a document of 32 words rides 32, of 33 rides
    # 64).  Empty docs (possible only via hand-built corpora) ride the
    # smallest bucket; their zero counts make them inert anyway.
    bucket_lens = [min_bucket_len]
    longest = int(lengths.max())
    while bucket_lens[-1] < longest:
        bucket_lens.append(bucket_lens[-1] * 2)
    bucket = np.searchsorted(
        np.asarray(bucket_lens, dtype=np.int64), lengths, side="left"
    ).astype(np.uint8)
    # Stable: corpus order within a bucket.
    order = np.argsort(bucket, kind="stable")
    docs_per_bucket = np.bincount(bucket, minlength=len(bucket_lens))

    # Lay the batches end to end in one flat run of cells.  shift[d]
    # takes document d's tokens from their CSR positions to their
    # cells: token t of the corpus lands in cell t + shift[doc of t].
    doc_ptr = corpus.doc_ptr
    shift = np.empty(num_docs, dtype=np.intp)
    layout: list[tuple[int, int, int, np.ndarray, np.ndarray]] = []
    cells = 0
    per_bucket = np.split(order, np.cumsum(docs_per_bucket)[:-1])
    for L, docs in zip(bucket_lens, per_bucket):
        if not len(docs):
            continue
        bucket_b = min(batch_size,
                       -(-len(docs) // pad_multiple) * pad_multiple)
        for start in range(0, len(docs), batch_size):
            chunk = docs[start : start + batch_size]
            B = bucket_b if pad_batch_to_multiple else len(chunk)
            shift[chunk] = (
                np.arange(cells, cells + len(chunk) * L, L) - doc_ptr[chunk]
            )
            didx = np.zeros((B,), dtype=np.int32)
            didx[: len(chunk)] = chunk
            mask = np.zeros((B,), dtype=np.float32)
            mask[: len(chunk)] = 1.0
            layout.append((cells, B, L, didx, mask))
            cells += B * L

    widx = np.zeros(cells, dtype=np.int32)
    cnts = np.zeros(cells, dtype=np.float32)
    tok_lo, tok_hi = int(doc_ptr[0]), int(doc_ptr[-1])
    cuts = np.unique(np.append(
        np.searchsorted(
            doc_ptr, np.arange(tok_lo, tok_hi, _FILL_SLAB_TOKENS),
            side="left",
        ),
        num_docs,
    )).tolist()
    for d0, d1 in zip(cuts[:-1], cuts[1:]):
        t0, t1 = int(doc_ptr[d0]), int(doc_ptr[d1])
        # intp: numpy converts any other index width before it scatters.
        cell = np.repeat(shift[d0:d1], lengths[d0:d1])
        cell += np.arange(t0, t1, dtype=np.intp)
        # Cast the slab first: a scatter that also casts takes numpy's
        # buffered path.
        widx[cell] = corpus.word_idx[t0:t1].astype(np.int32, copy=False)
        cnts[cell] = corpus.counts[t0:t1].astype(np.float32, copy=False)

    return [
        Batch(
            widx[at : at + B * L].reshape(B, L),
            cnts[at : at + B * L].reshape(B, L),
            didx, mask,
        )
        for at, B, L, didx, mask in layout
    ]
