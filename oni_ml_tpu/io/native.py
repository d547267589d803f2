"""ctypes binding for the native corpus ingest (oni_ml_tpu/native_src/corpus_ingest.cpp):
two readers in one library, `load_corpus` for word counts and
`read_model_dat` for the LDA-C corpus file.

The reference's corpus build (lda_pre.py, SURVEY.md §2.4) is three
sequential Python passes over the day's word counts — its single-node
bottleneck.  The native path does one buffered C++ pass and hands back
CSR arrays + id maps with semantics identical to the pure-Python
``Corpus.from_word_counts`` (first-seen-order ids, per-doc token
grouping), so callers can use whichever is available.

``model.dat`` was the fit stage's last text path left in Python: a loop
a line and a token (io/formats.read_model_dat), 62% of the drop-in CLI's
``lda est`` call on a 163,840-document day.  The native reader makes two
passes over the file's bytes into the same three CSR arrays; it decides
only the plain grammar and hands every other file back to the loop (see
``read_model_dat`` below).

Loading strategy (oni_ml_tpu/native_build.py, shared with the native flow
featurizer): use the prebuilt ``_native/liboni_ingest.so`` (built by
``make -C native``); if missing or stale, compile it once on demand with
g++ into the same location.  If neither works (no compiler),
``available()`` is False and callers fall back to Python.  Set
``ONI_ML_TPU_NO_NATIVE=1`` to force the Python path.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ..native_build import NativeLib


def _configure(lib: ctypes.CDLL) -> None:
    lib.oni_ingest_create.restype = ctypes.c_void_p
    lib.oni_ingest_destroy.argtypes = [ctypes.c_void_p]
    lib.oni_ingest_file.restype = ctypes.c_int64
    lib.oni_ingest_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.oni_last_error.restype = ctypes.c_char_p
    lib.oni_last_error.argtypes = [ctypes.c_void_p]
    for fn in ("oni_num_docs", "oni_num_terms", "oni_nnz"):
        getattr(lib, fn).restype = ctypes.c_int64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.oni_fill_csr.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.oni_names_bytes.restype = ctypes.c_int64
    lib.oni_names_bytes.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.oni_fill_names.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p
    ]
    lib.oni_model_open.restype = ctypes.c_void_p
    lib.oni_model_open.argtypes = [ctypes.c_char_p]
    lib.oni_model_close.argtypes = [ctypes.c_void_p]
    for fn in ("oni_model_num_docs", "oni_model_nnz"):
        getattr(lib, fn).restype = ctypes.c_int64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.oni_model_fill.restype = ctypes.c_int32
    lib.oni_model_fill.argtypes = [
        ctypes.c_void_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]


_LIB = NativeLib(
    os.path.join(
        os.path.dirname(__file__), "..", "native_src", "corpus_ingest.cpp"
    ),
    os.path.join(os.path.dirname(__file__), "_native", "liboni_ingest.so"),
    _configure,
)


def _load() -> ctypes.CDLL | None:
    return _LIB.load()


def available() -> bool:
    return _LIB.available()


def load_corpus(paths: str | list[str]):
    """Parse one or more word_counts files natively -> Corpus.

    Multiple paths concatenate exactly like the reference's
    ``cat part-* > doc_wc.dat`` (ml_ops.sh:61).  Raises RuntimeError if
    the native library is unavailable, ValueError on malformed input
    (including UnicodeDecodeError for non-UTF-8 bytes, matching the
    Python reader).
    """
    from .corpus import Corpus

    lib = _load()
    if lib is None:
        raise RuntimeError("native ingest unavailable (g++/.so missing)")
    if isinstance(paths, str):
        paths = [paths]
    h = lib.oni_ingest_create()
    try:
        for p in paths:
            if lib.oni_ingest_file(h, os.fsencode(p)) < 0:
                err = lib.oni_last_error(h).decode("utf-8", "replace")
                raise ValueError(f"{p}: {err}")
        d = lib.oni_num_docs(h)
        nnz = lib.oni_nnz(h)
        doc_ptr = np.empty(d + 1, dtype=np.int64)
        word_idx = np.empty(nnz, dtype=np.int32)
        counts = np.empty(nnz, dtype=np.int32)
        lib.oni_fill_csr(h, doc_ptr, word_idx, counts)

        def names(which: int) -> list[str]:
            nb = lib.oni_names_bytes(h, which)
            buf = ctypes.create_string_buffer(int(nb))
            lib.oni_fill_names(h, which, buf)
            # surrogateescape, matching the Python reader (io/formats
            # _open): hostile raw wire bytes in IPs/words round-trip
            # byte-for-byte through words.dat/doc.dat instead of
            # crashing the corpus stage.
            raw = buf.raw[:nb].decode("utf-8", "surrogateescape")
            return raw.split("\n")[:-1]  # trailing separator

        return Corpus(
            doc_names=names(0),
            vocab=names(1),
            doc_ptr=doc_ptr,
            word_idx=word_idx,
            counts=counts,
        )
    finally:
        lib.oni_ingest_destroy(ctypes.c_void_p(h))


def read_model_dat(
    path: str,
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """Parse an LDA-C ``model.dat`` natively -> (doc_ptr [D+1] int64,
    word_idx [NNZ] int32, counts [NNZ] int32), or None when the native
    pass cannot decide the file.

    It decides exactly the plain grammar: ASCII digits, ``:`` and ASCII
    whitespace, every line's header equal to its count of ``w:c`` fields,
    every number within int32.  None means "run the Python loop"
    (io/formats.read_model_dat), which accepts what ``int()`` accepts and
    raises its own exceptions: the library is unavailable, the file
    cannot be read, or it holds anything else (a sign, an underscore, a
    non-ASCII byte, a short or long line, a value past int32)."""
    lib = _load()
    if lib is None:
        return None
    h = lib.oni_model_open(os.fsencode(path))
    if not h:
        return None
    try:
        doc_ptr = np.empty(lib.oni_model_num_docs(h) + 1, dtype=np.int64)
        nnz = lib.oni_model_nnz(h)
        word_idx = np.empty(nnz, dtype=np.int32)
        counts = np.empty(nnz, dtype=np.int32)
        if lib.oni_model_fill(h, doc_ptr, word_idx, counts) != 0:
            return None
        return doc_ptr, word_idx, counts
    finally:
        lib.oni_model_close(ctypes.c_void_p(h))
