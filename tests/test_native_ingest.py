"""Native (C++) corpus ingest: exact parity with the Python builder,
multi-file concatenation, malformed-input errors, and fallback."""

import os

import numpy as np
import pytest

from oni_ml_tpu.io import Corpus, formats
from oni_ml_tpu.io import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native ingest not built and no g++"
)


def _random_triples(n, seed, n_ips=37, n_words=211):
    rng = np.random.default_rng(seed)
    return [
        (
            f"10.{rng.integers(0, 4)}.0.{rng.integers(1, n_ips)}",
            f"{rng.integers(0, 70000)}_{rng.integers(0, 10)}"
            f"_{rng.integers(0, 10)}_{rng.integers(0, 5)}"[:n_words],
            int(rng.integers(1, 1000)),
        )
        for _ in range(n)
    ]


def _assert_same(a: Corpus, b: Corpus):
    assert a.doc_names == b.doc_names
    assert a.vocab == b.vocab
    np.testing.assert_array_equal(a.doc_ptr, b.doc_ptr)
    np.testing.assert_array_equal(a.word_idx, b.word_idx)
    np.testing.assert_array_equal(a.counts, b.counts)


def test_parity_with_python(tmp_path):
    triples = _random_triples(5000, seed=1)
    path = str(tmp_path / "wc.dat")
    formats.write_word_counts(path, triples)
    nat = native.load_corpus(path)
    py = Corpus.from_word_counts(formats.read_word_counts(path))
    _assert_same(nat, py)
    assert nat.num_tokens == sum(c for _, _, c in triples)


def test_parity_edge_cases(tmp_path):
    path = str(tmp_path / "wc.dat")
    with open(path, "w") as f:
        f.write("1.2.3.4,80.0_1.0_2.0_3.0,5\n")
        f.write("\n")  # empty line skipped
        f.write("1.2.3.4,80.0_1.0_2.0_3.0,7\n")  # duplicate pair kept
        f.write("a,b ip,-1_80.0_1.0,3\n")  # comma inside ip: rsplit wins
        f.write("5.6.7.8,w,1")  # no trailing newline
    nat = native.load_corpus(path)
    py = Corpus.from_word_counts(formats.read_word_counts(path))
    _assert_same(nat, py)
    assert nat.doc_names == ["1.2.3.4", "a,b ip", "5.6.7.8"]
    assert nat.doc_ptr.tolist() == [0, 2, 3, 4]


def test_multi_file_concat(tmp_path):
    t1 = _random_triples(300, seed=2)
    t2 = _random_triples(300, seed=3)
    p1, p2, pall = (str(tmp_path / n) for n in ["a.dat", "b.dat", "all.dat"])
    formats.write_word_counts(p1, t1)
    formats.write_word_counts(p2, t2)
    formats.write_word_counts(pall, t1 + t2)
    _assert_same(native.load_corpus([p1, p2]), native.load_corpus(pall))


def test_universal_newlines(tmp_path):
    """CRLF and lone-CR files parse like Python's text-mode reader."""
    body = "1.2.3.4,w1,5{sep}1.2.3.4,w2,3{sep}5.6.7.8,w1,2{sep}"
    expect = Corpus.from_word_counts(
        [("1.2.3.4", "w1", 5), ("1.2.3.4", "w2", 3), ("5.6.7.8", "w1", 2)]
    )
    for sep in ["\r\n", "\r"]:
        path = str(tmp_path / "wc.dat")
        with open(path, "w", newline="") as f:
            f.write(body.format(sep=sep))
        _assert_same(native.load_corpus(path), expect)


def test_count_overflow_raises(tmp_path):
    path = str(tmp_path / "wc.dat")
    with open(path, "w") as f:
        f.write("1.2.3.4,w,4294967297\n")
    with pytest.raises(ValueError, match="out of range"):
        native.load_corpus(path)


def test_non_utf8_round_trips(tmp_path):
    """Raw wire bytes that are not valid UTF-8 (hostile DNS names, odd
    IP field contents) must flow through the corpus stage byte-for-byte
    via surrogateescape — in BOTH readers — not crash it."""
    path = str(tmp_path / "wc.dat")
    payload = b"1.2.3.4,w\xe9rd,5\n"
    with open(path, "wb") as f:
        f.write(payload)
    c = native.load_corpus(path)
    assert c.vocab == ["w\udce9rd"]
    out = str(tmp_path / "out")
    os.makedirs(out, exist_ok=True)
    c.save(out)
    with open(os.path.join(out, "words.dat"), "rb") as f:
        assert f.read() == b"0,w\xe9rd\n"
    # (Python-reader parity for the same bytes lives in test_formats.py,
    # which runs even without the native build.)


def test_malformed_line_raises(tmp_path):
    path = str(tmp_path / "bad.dat")
    with open(path, "w") as f:
        f.write("1.2.3.4,w,3\n")
        f.write("no-commas-here\n")
    with pytest.raises(ValueError, match="line 2"):
        native.load_corpus(path)
    with open(path, "w") as f:
        f.write("1.2.3.4,w,notanumber\n")
    with pytest.raises(ValueError, match="count"):
        native.load_corpus(path)


def test_from_word_counts_file_uses_native_and_env_disables(tmp_path):
    triples = _random_triples(100, seed=5)
    path = str(tmp_path / "wc.dat")
    formats.write_word_counts(path, triples)
    via_file = Corpus.from_word_counts_file(path)
    _assert_same(via_file, Corpus.from_word_counts(triples))
    # Env kill-switch forces the Python path (checked at load; simulate by
    # stubbing available()).
    orig = native.available
    native.available = lambda: False
    try:
        via_py = Corpus.from_word_counts_file(path)
    finally:
        native.available = orig
    _assert_same(via_file, via_py)


def test_native_is_faster_smoke(tmp_path):
    """Not a strict benchmark, but the native path must not be slower on a
    corpus big enough to matter (it's ~10-40x faster in practice)."""
    import time

    triples = _random_triples(200_000, seed=7)
    path = str(tmp_path / "big.dat")
    formats.write_word_counts(path, triples)
    t0 = time.perf_counter()
    nat = native.load_corpus(path)
    t_nat = time.perf_counter() - t0
    t0 = time.perf_counter()
    py = Corpus.from_word_counts(formats.read_word_counts(path))
    t_py = time.perf_counter() - t0
    _assert_same(nat, py)
    assert t_nat < t_py, (t_nat, t_py)


# ---------------------------------------------------------------------------
# model.dat: the native reader against the Python loop, its specification
# ---------------------------------------------------------------------------


def _outcome(path):
    """What read_model_dat gives: its arrays, or its exception's type and
    message."""
    try:
        return formats.read_model_dat(path)
    except Exception as e:  # noqa: BLE001 - whatever the reader raises
        return type(e), str(e)


def _native_and_loop(path, monkeypatch):
    """(the outcome with the native reader on, the reader that served it,
    the loop's outcome)."""
    got, reader = _outcome(path), formats.model_dat_reader
    with monkeypatch.context() as m:
        m.setattr(native, "available", lambda: False)
        want = _outcome(path)
        assert formats.model_dat_reader == "python"
    return got, reader, want


def _assert_same_outcome(got, want):
    if isinstance(want[0], type):
        assert got == want
        return
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    assert [a.dtype for a in got] == [np.int64, np.int32, np.int32]


def _ragged_csr(rng, num_docs, max_len=12, max_id=1 << 20, max_count=1000):
    lens = rng.integers(0, max_len, num_docs)       # empty documents too
    ptr = np.r_[0, np.cumsum(lens)].astype(np.int64)
    widx = rng.integers(0, max_id, int(ptr[-1])).astype(np.int32)
    cnts = rng.integers(1, max_count, int(ptr[-1])).astype(np.int32)
    return ptr, widx, cnts


GOLDEN = b"3 0:5 1:2 3:1\n2 0:1 2:7\n1 0:3\n"

# Plain files: the native reader decides them, as the loop does.
PLAIN = {
    "golden": GOLDEN,
    "crlf": GOLDEN.replace(b"\n", b"\r\n"),
    "lone_cr": GOLDEN.replace(b"\n", b"\r"),
    "lf_then_cr": b"1 0:1\n\r1 1:1\n\r",
    "tabs_and_runs_of_spaces": b"3\t0:5   1:2 \t 3:1 \n2  0:1\t2:7\t\n",
    "vt_and_ff": b"2\x0b0:5\x0c1:2\n",
    "indented": b"  1 0:3\n\t1 1:4\n",
    "blank_lines_start_middle_end": b"\n\n1 0:3\n\n \t \n2 0:1 1:1\n\n\n",
    "no_final_newline": b"2 0:1 1:1\n1 4:4",
    "empty_file": b"",
    "only_blank_lines": b"\n \n\r\n",
    "zero_documents": b"0\n1 5:1\n0\n0",
    "int32_max_id_and_count": b"1 2147483647:2147483647\n",
    "leading_zeros": b"01 007:0010\n0000000002 1:1 2:2\n",
}


@pytest.mark.parametrize("case", sorted(PLAIN))
def test_model_dat_plain_files_match_the_loop(tmp_path, monkeypatch, case):
    path = tmp_path / "model.dat"
    path.write_bytes(PLAIN[case])
    got, reader, want = _native_and_loop(str(path), monkeypatch)
    assert reader == "native"
    assert not isinstance(want[0], type), want
    _assert_same_outcome(got, want)


def test_model_dat_golden_arrays(tmp_path):
    path = tmp_path / "model.dat"
    path.write_bytes(GOLDEN)
    ptr, widx, cnts = native.read_model_dat(str(path))
    assert ptr.tolist() == [0, 3, 5, 6]
    assert widx.tolist() == [0, 1, 3, 0, 2, 0]
    assert cnts.tolist() == [5, 2, 1, 1, 7, 3]


@pytest.mark.parametrize("writer", ["native", "python"])
def test_model_dat_roundtrips_both_writers(tmp_path, monkeypatch, writer):
    """write_model_dat's output for a ragged CSR with empty documents,
    from either writer, comes back element for element from either
    reader."""
    from oni_ml_tpu import native_emit

    if writer == "native" and not native_emit.available():
        pytest.skip("native emit unavailable")
    if writer == "python":
        monkeypatch.setattr(native_emit, "model_emit", lambda *a: None)
    ptr, widx, cnts = _ragged_csr(np.random.default_rng(11), 300)
    widx[0], cnts[0] = 2**31 - 1, 2**31 - 1
    path = str(tmp_path / "model.dat")
    formats.write_model_dat(path, ptr, widx, cnts)
    got, reader, want = _native_and_loop(path, monkeypatch)
    assert reader == "native"
    _assert_same_outcome(got, want)
    _assert_same_outcome(got, (ptr, widx, cnts))


# Files the native reader does not decide: the loop reads them again and
# raises its own exception, or accepts what int() and str.split() accept.
# (bytes, what the loop does: an exception type, or None where it accepts)
REFUSED = {
    "header_larger_than_line": (b"1 0:1\n3 0:1 1:1\n", ValueError),
    "header_smaller_than_line": (b"1 0:1 1:1\n", ValueError),
    "pairs_without_header": (b"0:1 1:1\n", ValueError),
    "field_without_colon": (b"1 01\n", ValueError),
    "two_colons": (b"1 1:2:3\n", ValueError),
    "empty_id": (b"1 :2\n", ValueError),
    "empty_count": (b"1 1:\n", ValueError),
    "colon_in_header": (b"1:1 0:1\n", ValueError),
    "minus_id": (b"1 -1:2\n", None),
    "plus_id": (b"1 +1:2\n", None),
    "plus_header": (b"+1 0:1\n", None),
    "underscore": (b"1 1_0:2\n", None),
    "letter": (b"1 a:2\n", ValueError),
    "nul": (b"1 1\x00:2\n", ValueError),
    "non_utf8_byte": (b"1 \xe9:2\n", ValueError),
    "bom": (b"\xef\xbb\xbf1 0:1\n", ValueError),
    "id_2_31": (b"1 2147483648:1\n", OverflowError),
    "count_2_31": (b"1 0:1\n1 1:2147483648\n", OverflowError),
    "eleven_digits": (b"1 00000000001:1\n", None),
    "five_thousand_digits": (b"1 " + b"0" * 5000 + b":1\n", ValueError),
    "unit_separator_is_whitespace_to_split": (b"1\x1f0:1\n", None),
    "nbsp_is_whitespace_to_split": (b"1\xc2\xa00:1\n", None),
    "arabic_indic_digit": (b"1 \xd9\xa1:2\n", None),
    "bad_line_after_good_ones": (GOLDEN + b"1 x:1\n", ValueError),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_model_dat_refused_files_get_the_loops_outcome(
        tmp_path, monkeypatch, case):
    data, raises = REFUSED[case]
    path = tmp_path / "model.dat"
    path.write_bytes(data)
    assert native.read_model_dat(str(path)) is None
    got, reader, want = _native_and_loop(str(path), monkeypatch)
    assert reader == "python"
    assert (want[0] if isinstance(want[0], type) else None) is raises, want
    _assert_same_outcome(got, want)


def test_model_dat_refusals_read_as_the_loop_reads_them(tmp_path):
    """Where the loop accepts what the native reader refused, the arrays
    are int()'s reading."""
    path = tmp_path / "model.dat"
    path.write_bytes(b"+2 -1:2 1_0:0_3\n1\xc2\xa0\xd9\xa1:2\n")
    ptr, widx, cnts = formats.read_model_dat(str(path))
    assert formats.model_dat_reader == "python"
    assert (ptr.tolist(), widx.tolist(), cnts.tolist()) == (
        [0, 2, 3], [-1, 10, 1], [2, 3, 2])


def test_model_dat_missing_file_and_directory(tmp_path, monkeypatch):
    for path in (tmp_path / "nothing.dat", tmp_path):
        got, reader, want = _native_and_loop(str(path), monkeypatch)
        assert reader == "python" and got == want
        assert issubclass(want[0], OSError)


def test_model_dat_env_forces_the_loop(tmp_path, monkeypatch):
    """ONI_ML_TPU_NO_NATIVE=1 is read when a library first loads: a fresh
    loader under it is the fallback, and read_model_dat is the loop."""
    from oni_ml_tpu import native_build

    path = tmp_path / "model.dat"
    path.write_bytes(GOLDEN)
    want = formats.read_model_dat(str(path))
    assert formats.model_dat_reader == "native"
    monkeypatch.setattr(native_build, "_LIBRARIES", [])
    real = native._LIB
    monkeypatch.setattr(native, "_LIB", native_build.NativeLib(
        real._src, real._lib_path, real._configure, deps=real._deps))
    monkeypatch.setenv("ONI_ML_TPU_NO_NATIVE", "1")
    got = formats.read_model_dat(str(path))
    assert native._LIB.status == "python-fallback"
    assert formats.model_dat_reader == "python"
    _assert_same_outcome(got, want)
    c = Corpus.from_model_dat(str(path))
    assert c.vocab == ["0", "1", "2", "3"] and c.doc_names == ["1", "2", "3"]


def test_from_model_dat_same_corpus_under_both_readers(tmp_path, monkeypatch):
    c = Corpus.from_word_counts(_random_triples(2000, seed=9))
    c.save(str(tmp_path))
    paths = [str(tmp_path / n) for n in ("model.dat", "words.dat", "doc.dat")]
    nat = Corpus.from_model_dat(*paths)
    assert formats.model_dat_reader == "native"
    monkeypatch.setattr(native, "available", lambda: False)
    py = Corpus.from_model_dat(*paths)
    assert formats.model_dat_reader == "python"
    _assert_same(nat, py)
    _assert_same(nat, c)
    _assert_same(Corpus.from_model_dat(paths[0]), Corpus(
        [str(i + 1) for i in range(c.num_docs)],
        [str(i) for i in range(c.num_terms)],
        c.doc_ptr, c.word_idx, c.counts))


def test_native_model_dat_is_faster_smoke(tmp_path, monkeypatch):
    """Not a strict benchmark: a few hundred thousand pairs, and the native
    reader must not be slower than the loop (it is ~25x faster)."""
    import time

    ptr, widx, cnts = _ragged_csr(np.random.default_rng(3), 6000,
                                  max_len=100, max_id=50000)
    path = str(tmp_path / "big.dat")
    formats.write_model_dat(path, ptr, widx, cnts)
    t0 = time.perf_counter()
    nat = formats.read_model_dat(path)
    t_nat = time.perf_counter() - t0
    assert formats.model_dat_reader == "native"
    monkeypatch.setattr(native, "available", lambda: False)
    t0 = time.perf_counter()
    py = formats.read_model_dat(path)
    t_py = time.perf_counter() - t0
    _assert_same_outcome(nat, py)
    assert len(widx) > 250_000 and t_nat < t_py, (t_nat, t_py)
