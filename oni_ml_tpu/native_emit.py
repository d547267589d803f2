"""ctypes binding for the native emit/score library
(oni_ml_tpu/native_src/row_emit.cpp) — package-level because it serves
three layers: the pre stage's word_counts buffer (runner), the corpus
stage's model.dat buffer and the LDA stage's final.beta / final.gamma
files (io.formats), and the score stage's scored-CSV assembly + fused
gather-dot (scoring).

Each emitter builds its whole output buffer in C++ from the arena
blobs / numeric columns / CSR arrays the callers already hold, and each
is byte-identical to its Python fallback loop (pinned by the parity
tests in tests/test_scoring.py and tests/test_formats.py, plus the
golden fixture).  `matrix_emit` alone writes its file from C, slab by
slab (a 45 MB final.gamma never exists as one buffer); its fallback and
specification is np.savetxt (tests/test_native_matrix_emit.py).

The row emitters qualify only for native-backed feature containers —
the pure-Python DnsFeatures/FlowFeatures keep rows as lists and take
the Python loop."""

from __future__ import annotations

import ctypes
import os

import numpy as np

from .native_build import NativeLib, bytes_at

_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)
_F64P = ctypes.POINTER(ctypes.c_double)


def _configure(lib: ctypes.CDLL) -> None:
    lib.emit_free.argtypes = [ctypes.c_void_p]
    lib.score_dot.restype = None
    lib.score_dot.argtypes = [
        _F64P, _F64P, ctypes.c_int64,
        _I32P, _I32P, ctypes.c_int64, _F64P,
    ]
    lib.model_emit.restype = ctypes.c_void_p
    lib.model_emit.argtypes = [
        _I64P, ctypes.c_int64, _I32P, _I64P, _I64P,
    ]
    lib.matrix_emit.restype = ctypes.c_int
    lib.matrix_emit.argtypes = [
        ctypes.c_char_p, _F64P, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.wc_emit.restype = ctypes.c_void_p
    lib.wc_emit.argtypes = (
        [ctypes.c_char_p, _I64P] * 2
        + [_I32P, _I32P, _I64P]
        + [ctypes.c_int64, _I64P]
    )
    lib.flow_emit.restype = ctypes.c_void_p
    lib.flow_emit.argtypes = (
        [ctypes.c_char_p, _I64P] * 3
        + [_I32P] * 5
        + [_F64P, _I64P, _I64P, _I64P]
        + [_F64P, _F64P]
        + [_I64P, ctypes.c_int64, _I64P]
    )
    lib.dns_emit.restype = ctypes.c_void_p
    lib.dns_emit.argtypes = (
        [ctypes.c_char_p, _I64P] * 4
        + [_I32P] * 3
        + [_I64P, _I64P, _F64P, _I64P, _F64P]
        + [_I64P, ctypes.c_int64, _I64P]
    )


_LIB = NativeLib(
    os.path.join(
        os.path.dirname(__file__), "native_src", "row_emit.cpp"
    ),
    os.path.join(os.path.dirname(__file__), "_native", "liboni_emit.so"),
    _configure,
    deps=(
        os.path.join(
            os.path.dirname(__file__), "native_src", "common.h"
        ),
    ),
)


def available() -> bool:
    return _LIB.available()


def _table_blob(strs: list[str]) -> tuple[bytes, np.ndarray]:
    """Re-encode a decoded string table into (blob, offsets) — tables
    hold unique strings only, so this is tiny next to the row count."""
    enc = [s.encode("utf-8", "surrogateescape") for s in strs]
    off = np.zeros(len(enc) + 1, np.int64)
    if enc:
        np.cumsum([len(e) for e in enc], out=off[1:])
    return b"".join(enc), off


def _i64p(a: np.ndarray):
    return np.ascontiguousarray(a, np.int64).ctypes.data_as(_I64P)


def _i32p(a: np.ndarray):
    return np.ascontiguousarray(a, np.int32).ctypes.data_as(_I32P)


def _f64p(a: np.ndarray):
    return np.ascontiguousarray(a, np.float64).ctypes.data_as(_F64P)


def _collect(lib, ptr, out_len) -> bytes:
    # bytes_at, not ctypes.string_at: the latter truncates its size to
    # a C int, so a >= 2 GiB emit (realistic 30-day word_counts)
    # crashed with "Negative size" (round-5 config-3 run).
    try:
        return bytes_at(ptr, out_len.value)
    finally:
        lib.emit_free(ptr)


def _blob_arg(blob):
    """bytes pass through; MmapBlob (spilled raw lines, features/blob.py)
    hands over the address of its read-only mapping — the emitter only
    reads, and the OS pages rows in on demand."""
    return blob.as_c_char_p() if hasattr(blob, "as_c_char_p") else blob


def flow_emit(features, src_scores, dest_scores, order) -> bytes | None:
    """Scored-CSV buffer for NativeFlowFeatures, or None when the
    native library is unavailable."""
    lib = _LIB.load()
    if lib is None:
        return None
    ip_blob, ip_off = _table_blob(features.ip_table)
    word_blob, word_off = _table_blob(features.word_table)
    # keep the contiguous arrays alive across the call
    holds = [
        np.ascontiguousarray(features.line_off, np.int64),
        ip_off, word_off,
        np.ascontiguousarray(features.sip_id, np.int32),
        np.ascontiguousarray(features.dip_id, np.int32),
        np.ascontiguousarray(features.wp_id, np.int32),
        np.ascontiguousarray(features.sw_id, np.int32),
        np.ascontiguousarray(features.dw_id, np.int32),
        np.ascontiguousarray(features.num_time, np.float64),
        np.ascontiguousarray(features.ibyt_bin, np.int64),
        np.ascontiguousarray(features.ipkt_bin, np.int64),
        np.ascontiguousarray(features.time_bin, np.int64),
        np.ascontiguousarray(src_scores, np.float64),
        np.ascontiguousarray(dest_scores, np.float64),
        np.ascontiguousarray(order, np.int64),
    ]
    out_len = ctypes.c_int64(0)
    ptr = lib.flow_emit(
        _blob_arg(features.lines_blob), _i64p(holds[0]),
        ip_blob, _i64p(holds[1]),
        word_blob, _i64p(holds[2]),
        _i32p(holds[3]), _i32p(holds[4]),
        _i32p(holds[5]), _i32p(holds[6]), _i32p(holds[7]),
        _f64p(holds[8]), _i64p(holds[9]), _i64p(holds[10]),
        _i64p(holds[11]),
        _f64p(holds[12]), _f64p(holds[13]),
        _i64p(holds[14]), len(holds[14]), ctypes.byref(out_len),
    )
    return _collect(lib, ptr, out_len)


def score_dot(theta, p, ip_idx, word_idx) -> "np.ndarray | None":
    """out[i] = <theta[ip_idx[i]], p[word_idx[i]]> in float64, k-order
    accumulation — bit-identical to the sequential k-order fold (the
    reference's zip/map/sum; fp-contract pinned off in the C).  NOT
    einsum: np.einsum's SIMD partial sums round in a different order
    in the last ulp, which is exactly why scoring/score.py dropped it.
    None when the native library is unavailable."""
    lib = _LIB.load()
    if lib is None:
        return None
    theta = np.ascontiguousarray(theta, np.float64)
    p = np.ascontiguousarray(p, np.float64)
    if theta.shape[1] != p.shape[1]:
        raise ValueError(f"K mismatch: theta {theta.shape} vs p {p.shape}")
    ip_idx = np.asarray(ip_idx)
    word_idx = np.asarray(word_idx)
    if len(ip_idx) != len(word_idx):
        # The numpy path raised a broadcast error here; the C loop
        # would read past the shorter buffer.
        raise ValueError(
            f"index length mismatch: {len(ip_idx)} ips vs "
            f"{len(word_idx)} words"
        )
    # Range check BEFORE the int32 cast (an int64 id of 2**32 would
    # wrap to 0 and silently score row 0): the C loop would otherwise
    # dot whatever memory an out-of-range id points at.  Negative ids
    # raise too — numpy fancy indexing would WRAP them (usually into
    # the fallback row, masking a caller bug), so _batched_scores'
    # fallback applies the same pre-cast check to keep the two engines
    # behavior-identical.  (In-repo callers always come through the
    # fallback-row LUT, which never produces these.)
    if len(ip_idx) and (
        int(ip_idx.min()) < 0 or int(ip_idx.max()) >= theta.shape[0]
        or int(word_idx.min()) < 0 or int(word_idx.max()) >= p.shape[0]
    ):
        raise IndexError("model-row index out of range")
    ip_idx = np.ascontiguousarray(ip_idx, np.int32)
    word_idx = np.ascontiguousarray(word_idx, np.int32)
    out = np.empty(len(ip_idx), np.float64)
    lib.score_dot(
        _f64p(theta), _f64p(p), theta.shape[1],
        _i32p(ip_idx), _i32p(word_idx), len(ip_idx),
        out.ctypes.data_as(_F64P),
    )
    return out


def model_emit(doc_ptr, word_idx, counts) -> bytes | None:
    """The LDA-C model.dat buffer ("N w:c ..." per doc) from CSR arrays
    — byte-identical to formats.write_model_dat's line loop.  None when
    the native library is unavailable."""
    lib = _LIB.load()
    if lib is None:
        return None
    holds = [
        np.ascontiguousarray(doc_ptr, np.int64),
        np.ascontiguousarray(word_idx, np.int32),
        np.ascontiguousarray(counts, np.int64),
    ]
    ptr = holds[0]
    n_docs = len(ptr) - 1
    if n_docs <= 0:
        return b""                        # empty corpus: empty file
    # The C loop trusts doc_ptr as in-bounds slice offsets — enforce
    # what the Python fallback got for free from numpy indexing.
    if (
        len(holds[1]) != len(holds[2])
        or ptr[0] != 0
        or np.any(np.diff(ptr) < 0)
        or int(ptr[-1]) > len(holds[1])
    ):
        raise ValueError("CSR arrays inconsistent with doc_ptr")
    out_len = ctypes.c_int64(0)
    ptr = lib.model_emit(
        _i64p(holds[0]), n_docs, _i32p(holds[1]), _i64p(holds[2]),
        ctypes.byref(out_len),
    )
    return _collect(lib, ptr, out_len)


def matrix_emit(path: str, a: np.ndarray) -> bool:
    """Write the 2-D float64 `a` to `path` as np.savetxt(path, a,
    fmt="%5.10f") would, byte for byte, in one native pass: no Python
    object a row or a value, the file complete and closed at the return.
    False, with nothing a caller has to undo, where this cannot: the
    library is unavailable, `a` is not a 2-D float64 array, `path` is not
    a plain file name (np.savetxt compresses by extension), or the file
    could not be written (np.savetxt then raises for the caller)."""
    if not (
        isinstance(path, str)
        and isinstance(a, np.ndarray)
        and a.ndim == 2
        and a.dtype == np.float64
        and not path.endswith((".gz", ".bz2", ".xz", ".lzma"))
        and available()
    ):
        return False
    a = np.ascontiguousarray(a)
    rows, cols = a.shape
    return _LIB.load().matrix_emit(
        os.fsencode(path), a.ctypes.data_as(_F64P), rows, cols
    ) == 0


def word_counts_emit(features) -> bytes | None:
    """The `ip,word,count` word_counts file as one buffer, straight
    from a native container's interned tables + aggregated id arrays
    (NativeFlowFeatures / NativeDnsFeatures both carry wc_ip / wc_word
    / wc_count).  None when the native library is unavailable; output
    bit-identical to formats.write_word_counts over .word_counts()."""
    lib = _LIB.load()
    if lib is None:
        return None
    ip_blob, ip_off = _table_blob(features.ip_table)
    word_blob, word_off = _table_blob(features.word_table)
    holds = [
        ip_off, word_off,
        np.ascontiguousarray(features.wc_ip, np.int32),
        np.ascontiguousarray(features.wc_word, np.int32),
        np.ascontiguousarray(features.wc_count, np.int64),
    ]
    out_len = ctypes.c_int64(0)
    ptr = lib.wc_emit(
        ip_blob, _i64p(holds[0]),
        word_blob, _i64p(holds[1]),
        _i32p(holds[2]), _i32p(holds[3]), _i64p(holds[4]),
        len(holds[2]), ctypes.byref(out_len),
    )
    return _collect(lib, ptr, out_len)


def dns_emit(features, scores, order) -> bytes | None:
    """Scored-CSV buffer for NativeDnsFeatures, or None when the native
    library is unavailable."""
    lib = _LIB.load()
    if lib is None:
        return None
    dom_blob, dom_off = _table_blob(features.domain_table)
    sub_blob, sub_off = _table_blob(features.subdomain_table)
    word_blob, word_off = _table_blob(features.word_table)
    holds = [
        np.ascontiguousarray(features.row_off, np.int64),
        dom_off, sub_off, word_off,
        np.ascontiguousarray(features.dom_id, np.int32),
        np.ascontiguousarray(features.sub_id, np.int32),
        np.ascontiguousarray(features.word_id, np.int32),
        np.ascontiguousarray(features.subdomain_length, np.int64),
        np.ascontiguousarray(features.num_periods, np.int64),
        np.ascontiguousarray(features.subdomain_entropy, np.float64),
        np.ascontiguousarray(features.top_domain, np.int64),
        np.ascontiguousarray(scores, np.float64),
        np.ascontiguousarray(order, np.int64),
    ]
    out_len = ctypes.c_int64(0)
    ptr = lib.dns_emit(
        _blob_arg(features.rows_blob), _i64p(holds[0]),
        dom_blob, _i64p(holds[1]),
        sub_blob, _i64p(holds[2]),
        word_blob, _i64p(holds[3]),
        _i32p(holds[4]), _i32p(holds[5]), _i32p(holds[6]),
        _i64p(holds[7]), _i64p(holds[8]), _f64p(holds[9]), _i64p(holds[10]),
        _f64p(holds[11]),
        _i64p(holds[12]), len(holds[12]), ctypes.byref(out_len),
    )
    return _collect(lib, ptr, out_len)
