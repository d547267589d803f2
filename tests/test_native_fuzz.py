"""Randomized native-vs-Python parity fuzzing.

The targeted parity tests (test_native_flow / test_native_dns) pin known
edge cases; these sweep randomized structure — field garbage, weird
ports, hostile query names, random widths — across several seeds so a
future change that breaks parity off the happy path fails loudly.
Every assertion is exact equality: the native paths are speedups, never
approximations.
"""

import numpy as np
import pytest

from oni_ml_tpu.features import dns as pydns
from oni_ml_tpu.features import flow as pyflow
from oni_ml_tpu.features import native_dns, native_flow


def _rand_token(rng) -> str:
    kind = rng.integers(0, 8)
    if kind == 0:
        return ""
    if kind == 1:
        return str(rng.integers(-100, 70000))
    if kind == 2:
        return f"{rng.uniform(-1e4, 1e4):.3f}"
    if kind == 3:
        return "##"
    if kind == 4:
        return rng.choice(["nan", "inf", "-inf", "1e999", "1e-999", "+5"])
    if kind == 5:
        return "x" * int(rng.integers(1, 8))
    if kind == 6:
        # str(float) fixed/scientific boundary magnitudes (exponent in
        # [-4, 16) prints fixed; outside prints "1e+16"-style).
        return rng.choice([
            "1e15", "1e16", "-1e16", "9999999999999998", "1e-4", "0.0001",
            "0.00001", "2.5e-5", "123456789012345678", "1e100",
        ])
    return " " + str(rng.integers(0, 99)) + " "


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_flow_fuzz_parity(tmp_path, seed):
    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    rng = np.random.default_rng(seed)
    path = tmp_path / "flow.csv"
    _write_fuzz_flow_csv(rng, path)

    with open(path) as f:
        py = pyflow.featurize_flow(line.rstrip("\n") for line in f)
    nat = native_flow.featurize_flow_file(str(path))
    assert nat.num_events == py.num_events
    np.testing.assert_array_equal(nat.num_time, py.num_time)
    np.testing.assert_array_equal(nat.time_cuts, py.time_cuts)
    np.testing.assert_array_equal(nat.ibyt_bin, py.ibyt_bin)
    np.testing.assert_array_equal(nat.ipkt_bin, py.ipkt_bin)
    assert nat.src_word == py.src_word
    assert nat.dest_word == py.dest_word
    assert nat.word_counts() == py.word_counts()
    assert nat.rows == py.rows


def _rand_qname(rng) -> str:
    parts = []
    for _ in range(int(rng.integers(0, 7))):
        n = int(rng.integers(0, 12))
        parts.append(
            "".join(rng.choice(list("abcdef0123456789-"), size=n))
        )
    name = ".".join(parts)
    suffix = rng.integers(0, 6)
    if suffix == 0:
        name += ".in-addr.arpa"
    elif suffix == 1:
        name += ".co.uk"
    elif suffix == 2:
        name += "."
    elif suffix == 3:
        name += ".com"
    return name


def _write_fuzz_flow_csv(rng, path):
    lines = ["hdr,line"]
    for _ in range(300):
        width = int(rng.choice([27, 27, 27, 26, 28, 5]))
        lines.append(",".join(_rand_token(rng) for _ in range(width)))
    path.write_text("\n".join(lines) + "\n")


def _write_fuzz_dns_csv(rng, path):
    lines = []
    for _ in range(300):
        width = int(rng.choice([8, 8, 8, 7, 9]))
        fields = [_rand_token(rng) for _ in range(width)]
        # Mostly structured qnames, but leave ~25% as raw fuzz tokens so
        # extract_subdomain also sees garbage (##, padded numbers, ...).
        if width == 8 and rng.random() < 0.75:
            fields[4] = _rand_qname(rng)
        lines.append(",".join(fields))
    path.write_text("\n".join(lines) + "\n")


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_dns_fuzz_parity(tmp_path, seed):
    if not native_dns.available():
        pytest.skip("native dns featurizer unavailable")
    rng = np.random.default_rng(100 + seed)
    path = tmp_path / "dns.csv"
    _write_fuzz_dns_csv(rng, path)

    rows = [
        line.split(",")
        for line in (path.read_text().rstrip("\n")).split("\n")
        if line
    ]
    py = pydns.featurize_dns(rows, top_domains=frozenset({"abc", "google"}))
    nat = native_dns.featurize_dns_sources(
        [str(path)], top_domains=frozenset({"abc", "google"})
    )
    assert nat.num_events == py.num_events
    assert nat.domain == py.domain
    assert nat.subdomain == py.subdomain
    np.testing.assert_array_equal(nat.subdomain_entropy, py.subdomain_entropy)
    np.testing.assert_array_equal(nat.num_periods, py.num_periods)
    for name in ("time_cuts", "frame_length_cuts", "subdomain_length_cuts",
                 "entropy_cuts", "numperiods_cuts"):
        np.testing.assert_array_equal(getattr(nat, name), getattr(py, name))
    assert nat.word == py.word
    assert nat.word_counts() == py.word_counts()


@pytest.mark.parametrize("seed", [0, 1])
def test_flow_fuzz_spill_parity(tmp_path, seed):
    """Spilled-vs-in-memory raw-line storage under fuzzed inputs: the
    stored rows (and everything derived) must be identical whichever
    store the bytes live in."""
    if not native_flow.available():
        pytest.skip("native flow featurizer unavailable")
    rng = np.random.default_rng(500 + seed)
    path = tmp_path / "flow.csv"
    _write_fuzz_flow_csv(rng, path)

    nat = native_flow.featurize_flow_file(str(path))
    spill = native_flow.featurize_flow_file(
        str(path), spill_path=str(tmp_path / "raw.bin")
    )
    assert spill.rows == nat.rows
    assert spill.word_counts() == nat.word_counts()
    assert len(spill.lines_blob) == len(nat.lines_blob)


@pytest.mark.parametrize("seed", [0, 1])
def test_dns_fuzz_spill_parity(tmp_path, seed):
    if not native_dns.available():
        pytest.skip("native dns featurizer unavailable")
    rng = np.random.default_rng(700 + seed)
    path = tmp_path / "dns.csv"
    _write_fuzz_dns_csv(rng, path)

    nat = native_dns.featurize_dns_sources([str(path)])
    # The generators never emit transport bytes, so fallback to the
    # Python container would mean a regression — fail loudly instead of
    # skipping the parity check.
    assert isinstance(nat, native_dns.NativeDnsFeatures)
    spill = native_dns.featurize_dns_sources(
        [str(path)], spill_path=str(tmp_path / "rows.bin")
    )
    assert isinstance(spill, native_dns.NativeDnsFeatures)
    assert spill.rows == nat.rows
    assert spill.word_counts() == nat.word_counts()


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_emit_kernels_parity(seed, tmp_path):
    """Randomized parity for the round-3 native emitters: model_emit
    (CSR -> LDA-C lines), wc_emit over a native container built from
    randomized DNS rows, and score_dot vs the sequential-fold numpy
    path — exact equality on randomized shapes including empty docs,
    zero-nnz corpora, and single-row models."""
    from oni_ml_tpu import native_emit
    from oni_ml_tpu.io import formats

    if not native_emit.available():
        pytest.skip("native emit unavailable")
    rng = np.random.default_rng(1000 + seed)

    # wc_emit: word_counts buffer over a native container from random
    # rows, vs formats.write_word_counts over the Python triples.
    if native_dns.available():
        rows = [
            ["t", str(1454000000 + int(rng.integers(0, 9999))),
             str(int(rng.integers(1, 2000))),
             f"10.{rng.integers(0, 5)}.{rng.integers(0, 5)}.{rng.integers(0, 9)}",
             f"s{rng.integers(0, 6)}.d{rng.integers(0, 9)}.com", "1",
             str(int(rng.integers(1, 17))), str(int(rng.integers(0, 4)))]
            for _ in range(int(rng.integers(1, 300)))
        ]
        feats = native_dns.featurize_dns_sources([rows])
        wc_blob = native_emit.word_counts_emit(feats)
        wp = tmp_path / f"wc{seed}.dat"
        formats.write_word_counts(str(wp), feats.word_counts())
        assert wc_blob == wp.read_bytes()

    # model_emit: random ragged CSR with empty docs and big counts.
    n_docs = int(rng.integers(0, 40))
    lens = rng.integers(0, 12, n_docs)
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    nnz = int(ptr[-1]) if n_docs else 0
    widx = rng.integers(0, 1 << 20, nnz).astype(np.int32)
    cnts = rng.integers(1, 1 << 40, nnz).astype(np.int64)
    blob = native_emit.model_emit(ptr, widx, cnts)
    p = tmp_path / f"m{seed}.dat"
    import oni_ml_tpu.native_emit as ne
    real = ne.model_emit
    ne.model_emit = lambda *a: None
    try:
        formats.write_model_dat(str(p), ptr, widx, cnts)
    finally:
        ne.model_emit = real
    assert blob == p.read_bytes()

    # score_dot: random K incl. 1; values spanning magnitudes.
    k = int(rng.integers(1, 33))
    theta = rng.random((int(rng.integers(1, 50)), k)) * 10.0 ** rng.integers(-8, 8)
    pm = rng.random((int(rng.integers(1, 50)), k))
    n = int(rng.integers(0, 500))
    ia = rng.integers(0, len(theta), n).astype(np.int32)
    ib = rng.integers(0, len(pm), n).astype(np.int32)
    a, b = theta[ia], pm[ib]
    if n:
        want = a[:, 0] * b[:, 0]
        for j in range(1, k):
            want = want + a[:, j] * b[:, j]
    else:
        want = np.zeros(0)
    got = native_emit.score_dot(theta, pm, ia, ib)
    assert np.array_equal(got, want)


def test_narrow_i32_guards_overflow():
    """wc_count narrowing must raise, not wrap (round-3 advisor
    finding: astype(int32) silently corrupts counts >= 2^31)."""
    import pytest

    from oni_ml_tpu.features import native_dns, native_flow

    for mod in (native_flow, native_dns):
        ok = mod._narrow_i32(np.array([0, 5, 2**31 - 1], dtype=np.int64))
        assert ok.dtype == np.int32 and ok.tolist() == [0, 5, 2**31 - 1]
        assert mod._narrow_i32(np.zeros(0, dtype=np.int64)).dtype == np.int32
        with pytest.raises(OverflowError):
            mod._narrow_i32(np.array([1, 2**31], dtype=np.int64))


_MODEL_DAT_ALPHABET = (
    b"0123456789" * 3 + b"::  \n\n\r\t\x0b\x0c" + b"-+_a.,\x00\x1f\x85\xa0\xe9\xc2\xd9"
)


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_model_dat_readers_parity(seed, tmp_path, monkeypatch):
    """Randomized parity for the native model.dat reader: random ragged
    corpora through write_model_dat come back from both readers as they
    went in, and a good file with random bytes replaced, inserted and
    deleted gives the same outcome from both: the same arrays, dtype for
    dtype, or the same exception with the same message."""
    from oni_ml_tpu.io import formats, native
    from test_native_ingest import (
        _assert_same_outcome, _native_and_loop, _ragged_csr,
    )

    if not native.available():
        pytest.skip("native ingest unavailable")
    rng = np.random.default_rng(2000 + seed)
    path = str(tmp_path / "model.dat")

    def both():
        got, reader, want = _native_and_loop(path, monkeypatch)
        _assert_same_outcome(got, want)
        if isinstance(want[0], type):   # the loop raised: it alone decided
            assert reader == "python"
        return got, reader

    csr = _ragged_csr(rng, int(rng.integers(0, 60)), max_len=15,
                      max_id=1 << 31, max_count=1 << 31)
    formats.write_model_dat(path, *csr)
    got, reader = both()
    assert reader == "native"
    _assert_same_outcome(got, csr)

    with open(path, "rb") as f:
        good = f.read() or b"1 0:1\n"
    readers = set()
    for _ in range(150):
        data = bytearray(good)
        for _ in range(int(rng.integers(1, 4))):
            at = int(rng.integers(0, len(data) + 1))
            byte = _MODEL_DAT_ALPHABET[
                int(rng.integers(0, len(_MODEL_DAT_ALPHABET)))]
            kind = rng.integers(0, 3)
            if kind == 0 and at < len(data):
                data[at] = byte
            elif kind == 1:
                data.insert(at, byte)
            elif at < len(data):
                del data[at]
        with open(path, "wb") as f:
            f.write(data)
        readers.add(both()[1])
    assert readers == {"native", "python"}   # both sides of the border


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_matrix_emit_prints_as_python(seed, tmp_path):
    """Randomized exactness for the native final.beta / final.gamma
    writer: every token of the file is Python's ``"%5.10f" % x``, on over
    10^5 doubles drawn over magnitudes 1e-14 to 1e9, on the exact ties of
    the tenth place (x * 10^10 = t * 5^10 / 2 ends in one half exactly
    where x is an odd multiple t of 2^-11: half-even decides them) and on
    the doubles next to each tie."""
    from decimal import Decimal

    from oni_ml_tpu import native_emit
    from oni_ml_tpu.io import formats

    if not native_emit.available():
        pytest.skip("native emit unavailable")
    rng = np.random.default_rng(3000 + seed)
    n = 100_000
    drawn = rng.uniform(1.0, 10.0, n) * 10.0 ** rng.integers(-14, 9, n)
    drawn *= rng.choice([-1.0, 1.0], n)
    odd = 2 * rng.integers(0, 1 << rng.integers(1, 42, 20_000)) + 1
    ties = odd * 2.0 ** -11 * rng.choice([-1.0, 1.0], len(odd))
    assert all((Decimal(x) * 10**10) % 1 == Decimal("0.5")
               for x in np.abs(ties[:2000]).tolist())
    values = np.concatenate([
        drawn, ties, np.nextafter(ties, np.inf), np.nextafter(ties, -np.inf),
        # around the integer path's upper border, and the widest values
        rng.uniform(-2.0**31, 2.0**31, 2_000),
        rng.uniform(1.0, 10.0, 1_000) * 10.0 ** rng.integers(9, 308, 1_000),
        rng.uniform(1.0, 10.0, 1_000) * 10.0 ** rng.integers(-320, -14, 1_000),
    ])
    values = rng.permutation(values)[: len(values) // 20 * 20]
    path = tmp_path / "final.gamma"
    formats.write_gamma(str(path), values.reshape(-1, 20))
    assert formats.matrix_writer == "native"
    lines = path.read_text().split("\n")
    assert lines.pop() == "" and len(lines) == len(values) // 20
    got = [tok for line in lines for tok in line.split(" ")]
    want = ["%5.10f" % x for x in values.tolist()]
    assert len(got) == len(want)
    bad = [(x, g, w) for x, g, w in zip(values.tolist(), got, want) if g != w]
    assert not bad, bad[:5]
