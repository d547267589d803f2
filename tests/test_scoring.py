"""Scoring tests: dot-product semantics, the reference's fallback quirks
(0.05 flow / 0.1 dns, SURVEY §2.6), threshold filter + ascending sort."""

import numpy as np

from oni_ml_tpu.features import featurize_dns, featurize_flow
from oni_ml_tpu.io import formats
from oni_ml_tpu.scoring import ScoringModel, score_dns, score_flow

from test_features import ZERO_CUTS, dns_row, flow_row


def make_model(doc_names, vocab, k=4, fallback=0.05, seed=0):
    rng = np.random.default_rng(seed)
    theta = rng.dirichlet(np.ones(k), size=len(doc_names))
    p = rng.dirichlet(np.ones(len(vocab)), size=k).T if vocab else np.zeros((0, k))
    return ScoringModel.from_results(doc_names, theta, vocab, p, fallback)


def test_unseen_flow_event_scores_fallback_squared():
    # Fully-unseen IP and word: score = K * 0.05 * 0.05 = 0.05 at K=20 —
    # the reference's "unseen traffic is NOT maximally suspicious" quirk.
    model = ScoringModel.from_results([], np.zeros((0, 20)), [], np.zeros((0, 20)), 0.05)
    f = featurize_flow(["h", flow_row()], precomputed_cuts=ZERO_CUTS)
    rows, scores = score_flow(f, model, threshold=1.0)
    assert len(rows) == 1
    np.testing.assert_allclose(scores[0], 20 * 0.05 * 0.05, rtol=1e-6)


def test_flow_score_is_min_of_src_dest():
    f = featurize_flow(["h", flow_row(sip="a", dip="b")], precomputed_cuts=ZERO_CUTS)
    k = 3
    theta_a = np.array([1.0, 0.0, 0.0])
    theta_b = np.array([0.0, 1.0, 0.0])
    p_src = np.array([0.9, 0.1, 0.0])   # <theta_a, p_src> = 0.9
    p_dst = np.array([0.2, 0.3, 0.5])   # <theta_b, p_dst> = 0.3
    model = ScoringModel.from_results(
        ["a", "b"], np.stack([theta_a, theta_b]),
        [f.src_word[0], f.dest_word[0]], np.stack([p_src, p_dst]), 0.05,
    )
    rows, scores = score_flow(f, model, threshold=1.0)
    np.testing.assert_allclose(scores[0], 0.3)
    cols = rows[0].split(",")
    # row = 35 featurized cols + src_score + dest_score
    assert len(cols) == 37
    np.testing.assert_allclose(float(cols[-2]), 0.9)
    np.testing.assert_allclose(float(cols[-1]), 0.3)


def test_threshold_filters_and_sorts_ascending():
    events = [flow_row(sip=f"ip{i}", dip="d") for i in range(5)]
    f = featurize_flow(["h"] + events, precomputed_cuts=ZERO_CUTS)
    k = 2
    # Give each sip a distinct score via theta[0]; word prob fixed.
    theta = np.array([[0.5, 0.5], [0.1, 0.9], [0.9, 0.1], [0.3, 0.7], [0.7, 0.3]])
    vocab = [f.src_word[0], f.dest_word[0]]
    p = np.array([[1.0, 0.0], [1.0, 0.0]])  # score = theta[0]
    model = ScoringModel.from_results(
        [f"ip{i}" for i in range(5)] + ["d"],
        np.concatenate([theta, [[1.0, 0.0]]]), vocab, p, 0.05,
    )
    rows, scores = score_flow(f, model, threshold=0.6)
    # dest score = <theta_d, p_dest> = 1.0 -> min = src score
    assert list(scores) == sorted(scores)
    assert all(s < 0.6 for s in scores)
    assert len(rows) == 3  # 0.5, 0.1, 0.3 survive


def test_dns_scoring_fallback_and_row_shape():
    f = featurize_dns([dns_row(ip="known"), dns_row(ip="unknown")])
    theta = np.full((1, 20), 1 / 20)
    p = np.full((1, 20), 1 / 20)
    model = ScoringModel.from_results(["known"], theta, [f.word[0]], p, 0.1)
    rows, scores = score_dns(f, model, threshold=1.0)
    assert len(rows) == 2
    # unknown ip: 20 * 0.1 * (1/20) = 0.1; known: 20 * (1/20)^2 = 0.05
    np.testing.assert_allclose(sorted(scores), [0.05, 0.1], rtol=1e-6)
    assert all(len(r.split(",")) == 16 for r in rows)


def test_model_roundtrip_through_result_files(tmp_path):
    rng = np.random.default_rng(3)
    gamma = rng.uniform(size=(4, 5))
    log_beta = np.log(rng.dirichlet(np.ones(7), size=5))
    doc_names = [f"10.0.0.{i}" for i in range(4)]
    vocab = [f"w{i}" for i in range(7)]
    dpath, wpath = str(tmp_path / "d.csv"), str(tmp_path / "w.csv")
    formats.write_doc_results(dpath, doc_names, gamma)
    formats.write_word_results(wpath, vocab, log_beta)
    model = ScoringModel.from_files(dpath, wpath, fallback=0.1)
    # theta rows normalized; p columns come from exp-normalized beta.
    np.testing.assert_allclose(model.theta[:4].sum(axis=1), 1.0, rtol=1e-12)
    np.testing.assert_allclose(
        model.p[:7], np.exp(log_beta).T, rtol=1e-10, atol=1e-12
    )
    assert model.ip_index["10.0.0.2"] == 2
    assert model.word_index["w6"] == 6
    np.testing.assert_allclose(model.theta[4], 0.1)  # fallback row


def _weird_flow_day(tmp_path, n=400):
    """Native-backed flow day with str(float) boundary ports, NaN rows,
    and CRLF — the emit seams that must stay byte-identical."""
    from oni_ml_tpu.features import native_flow

    rng = np.random.default_rng(5)
    lines = ["hdr"]
    ports = ["80", "443", "0", "1e15", "1e16", "0.0001", "52100", "##"]
    for i in range(n):
        c = ["x"] * 27
        c[4], c[5], c[6] = str(int(rng.integers(0, 24))), "30", "15"
        c[8], c[9] = f"10.0.0.{i % 17}", f"192.168.9.{i % 13}"
        c[10], c[11] = ports[i % len(ports)], ports[(i * 3 + 1) % len(ports)]
        c[16], c[17] = str(int(rng.integers(1, 300))), "##" if i % 37 == 0 else str(int(rng.integers(40, 5000)))
        lines.append(",".join(c))
    p = tmp_path / "flow.csv"
    p.write_bytes(("\r\n".join(lines) + "\n").encode())
    return native_flow.featurize_flow_file(str(p))


def test_native_flow_emit_matches_python_bytes(tmp_path):
    from oni_ml_tpu import native_emit
    from oni_ml_tpu.scoring import score_flow_csv
    from oni_ml_tpu.scoring.score import _batched_scores, _keep_order

    if not native_emit.available():
        import pytest

        pytest.skip("native emit unavailable")
    feats = _weird_flow_day(tmp_path)
    rng = np.random.default_rng(0)
    ips = sorted(set(feats.ip_table))[: len(feats.ip_table) // 2]
    vocab = sorted(set(feats.word_table))[: max(1, len(feats.word_table) // 2)]
    k = 6
    model = ScoringModel.from_results(
        ips, rng.dirichlet(np.ones(k), size=len(ips)),
        vocab, rng.dirichlet(np.ones(len(vocab)), size=k).T, fallback=0.05,
    )
    blob, scores = score_flow_csv(feats, model, threshold=np.inf)
    assert len(scores) == feats.num_raw_events  # all kept

    # Python reference loop over the same order
    n = feats.num_raw_events
    ip_map = model.ip_rows(feats.ip_table)
    word_map = model.word_rows(feats.word_table)
    src = _batched_scores(model, ip_map[feats.sip_id[:n]], word_map[feats.sw_id[:n]])
    dest = _batched_scores(model, ip_map[feats.dip_id[:n]], word_map[feats.dw_id[:n]])
    order = _keep_order(np.minimum(src, dest), np.inf)
    want = "".join(
        ",".join(feats.featurized_row(i) + [str(src[i]), str(dest[i])]) + "\n"
        for i in order
    ).encode("utf-8")
    assert blob == want


def test_native_dns_emit_matches_python_bytes():
    from oni_ml_tpu.features import native_dns
    from oni_ml_tpu import native_emit
    from oni_ml_tpu.scoring import score_dns_csv
    from oni_ml_tpu.scoring.score import _batched_scores, _keep_order

    if not (native_emit.available() and native_dns.available()):
        import pytest

        pytest.skip("native libs unavailable")
    rng = np.random.default_rng(2)
    qnames = ["www.google.com", "a.b.co.uk", "4.3.2.1.in-addr.arpa", "x",
              "dga-9x.evil.biz", "deep.sub.example.org", "comma,in.field.com"]
    rows = [
        ["t", str(1454000000 + int(rng.integers(0, 9999))),
         str(int(rng.integers(40, 1500))), f"172.16.0.{i % 9}",
         qnames[i % len(qnames)], "1", str(int(rng.integers(1, 17))),
         str(int(rng.integers(0, 4)))]
        for i in range(300)
    ]
    feats = native_dns.featurize_dns_sources([rows])
    k = 5
    ips = sorted(set(feats.ip_table))[:5]
    vocab = sorted(set(feats.word_table))[: max(1, len(feats.word_table) - 3)]
    model = ScoringModel.from_results(
        ips, rng.dirichlet(np.ones(k), size=len(ips)),
        vocab, rng.dirichlet(np.ones(len(vocab)), size=k).T, fallback=0.1,
    )
    blob, scores = score_dns_csv(feats, model, threshold=np.inf)

    n = feats.num_raw_events
    ip_map = model.ip_rows(feats.ip_table)
    word_map = model.word_rows(feats.word_table)
    s = _batched_scores(model, ip_map[feats.ip_id[:n]], word_map[feats.word_id[:n]])
    order = _keep_order(s, np.inf)
    want = "".join(
        ",".join(feats.featurized_row(i) + [str(s[i])]) + "\n" for i in order
    ).encode("utf-8")
    assert blob == want


def test_model_row_lookup_matches_dict_semantics():
    """The vectorized searchsorted LUT must reproduce dict.get exactly,
    including hostile keys: numpy's U dtype strips TRAILING NULs on
    conversion, so 'foo\\x00' and 'foo' would otherwise collide (raw
    DNS names are legal inputs here)."""
    k = 3
    long_name = "x" * 250 + ".evil"   # past the vector-path width cap
    names = ["foo", "foo\x00", "a\x00b", "zz", "", "Ⴆ.example", long_name]
    theta = np.arange(len(names) * k, dtype=np.float64).reshape(-1, k)
    model = ScoringModel.from_results(
        names, theta, ["w"], np.ones((1, k)), fallback=0.1
    )
    queries = names + ["foo\x00\x00", "miss", "a", "a\x00", "\x00",
                       long_name + "!", "y" * 300]
    fb = len(model.ip_index)
    want = [model.ip_index.get(q, fb) for q in queries]
    got = list(model.ip_rows(queries))
    assert got == want

    empty = ScoringModel.from_results([], np.zeros((0, k)), [],
                                      np.zeros((0, k)), fallback=0.1)
    assert list(empty.ip_rows(["x", "y\x00"])) == [0, 0]


def test_surrogate_bytes_flow_through_scoring():
    """A DNS day containing a non-UTF-8 raw name (surrogateescape
    str) must score without crashing, and the emitted CSV must carry
    the ORIGINAL raw bytes."""
    from oni_ml_tpu.features.native_dns import featurize_dns_sources
    from oni_ml_tpu.scoring import score_dns_csv

    rows = [
        ["t", str(1454000000 + i), "100", f"10.0.0.{i % 4}",
         "evil\udce9\udc80.bad" if i == 3 else f"s{i % 5}.ok.com",
         "1", "1", "0"]
        for i in range(40)
    ]
    feats = featurize_dns_sources([rows])   # falls back to Python path
    vocab = sorted(set(feats.word))
    ips = sorted({feats.client_ip(i) for i in range(feats.num_events)})
    model = ScoringModel.from_results(
        ips, np.full((len(ips), 4), 0.25), vocab,
        np.full((len(vocab), 4), 0.25), fallback=0.1,
    )
    blob, scores = score_dns_csv(feats, model, threshold=np.inf)
    assert len(scores) == 40
    assert b"evil\xe9\x80.bad" in blob


def test_index_rows_hostile_keys_exact_dict_semantics():
    """Model-row resolution must match dict.get exactly for hostile
    keys — NULs anywhere, over-long strings, empty — with misses on
    the fallback row.  (The former searchsorted LUT needed an oddball
    side path for these; the dict path is exact by construction, and
    this pins it stays so.)"""
    from oni_ml_tpu.scoring.score import _index_rows

    cases = [
        "", "a", "a" * 48, "a" * 49, "a" * 300,
        "x\x00", "x\x00y", "\x00", "a" * 48 + "\x00",
    ]
    index = {s: i for i, s in enumerate(cases)}
    assert _index_rows(index, cases, -1).tolist() == list(range(len(cases)))
    assert _index_rows(index, ["missing", "y\x00"], -1).tolist() == [-1, -1]
    assert _index_rows({}, ["a"], 7).tolist() == [7]
    assert _index_rows(index, [], -1).tolist() == []


def _wc_parity(feats, tmp_path):
    from oni_ml_tpu.io import formats
    from oni_ml_tpu import native_emit

    blob = native_emit.word_counts_emit(feats)
    if blob is None:  # no toolchain: nothing to compare
        return
    path = tmp_path / "wc.dat"
    formats.write_word_counts(str(path), feats.word_counts())
    assert blob == path.read_bytes()


def test_native_word_counts_emit_flow(tmp_path):
    """wc_emit parity: the C++ word_counts buffer is byte-identical to
    formats.write_word_counts over the container's Python triples.
    The day comes from bench._write_flow_day (schema-correct since the
    round-3 column-shift fix), so the parity runs over realistic
    multi-word/multi-ip tables, not a degenerate single-source day."""
    import bench
    from oni_ml_tpu.features.native_flow import featurize_flow_file

    p = tmp_path / "day.csv"
    with open(p, "w") as f:
        bench._write_flow_day(f, 400, n_src=40, n_dst=20)
    _wc_parity(featurize_flow_file(str(p)), tmp_path)


def test_native_word_counts_emit_dns(tmp_path):
    import numpy as np

    from oni_ml_tpu.features.native_dns import featurize_dns_sources

    rng = np.random.default_rng(6)
    rows = [
        ["t", str(1454000000 + i), str(int(rng.integers(40, 1500))),
         f"10.0.{i % 7}.{i % 11}", f"s{i % 9}.dom{i % 13}.com", "1",
         str(int(rng.integers(1, 17))), str(int(rng.integers(0, 4)))]
        for i in range(500)
    ]
    _wc_parity(featurize_dns_sources([rows]), tmp_path)


def test_native_lib_missing_symbol_degrades(tmp_path, monkeypatch):
    """A prebuilt .so predating a newly added export (no compiler to
    rebuild) must degrade to the Python fallback (load() -> None), not
    crash the caller with AttributeError at symbol-configure time."""
    from oni_ml_tpu import native_build
    from oni_ml_tpu.native_build import NativeLib

    # A private registry (as tests/test_bringup.py does): the package's
    # own would keep this throwaway library for every later test of the
    # worker, and load_all() would name it.
    monkeypatch.setattr(native_build, "_LIBRARIES", [])

    src = tmp_path / "t.cpp"
    src.write_text('extern "C" int foo() { return 1; }\n')

    import shutil

    import pytest

    if not shutil.which("g++") or __import__("os").environ.get(
        "ONI_ML_TPU_NO_NATIVE"
    ):
        pytest.skip("no C++ toolchain: the build-failure path returns "
                    "None before configure ever runs")

    def configure(lib):
        lib.no_such_symbol.restype = None   # AttributeError on lookup

    nl = NativeLib(str(src), str(tmp_path / "t.so"), configure)
    with pytest.warns(UserWarning, match="native symbol configuration"):
        assert nl.load() is None
    assert not nl.available()


def test_score_dot_native_matches_numpy():
    """The C gather-dot must be BIT-identical to the einsum path (same
    k-order accumulation, fp-contract off): scored CSVs embed
    str(score), so even one ulp moves golden bytes."""
    from oni_ml_tpu import native_emit

    if not native_emit.available():
        import pytest

        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(11)
    for k in (3, 20, 50):
        theta = rng.random((500, k))
        p = rng.random((70, k))
        ia = rng.integers(0, 500, 20_000).astype(np.int32)
        ib = rng.integers(0, 70, 20_000).astype(np.int32)
        # Reference accumulation: strict sequential fold over k (the
        # reference's zip/map/sum, flow_post_lda.scala:231).
        a, b = theta[ia], p[ib]
        want = a[:, 0] * b[:, 0]
        for j in range(1, k):
            want = want + a[:, j] * b[:, j]
        got = native_emit.score_dot(theta, p, ia, ib)
        assert got.dtype == np.float64
        assert np.array_equal(got, want)   # bitwise, not allclose


def test_batched_scores_rejects_out_of_range_ids_both_engines():
    """Both scoring engines must RAISE on out-of-range/negative model
    rows — numpy fancy indexing would silently wrap -1 into the
    fallback row, masking a caller bug, and the C loop would read
    arbitrary memory."""
    import pytest

    import oni_ml_tpu.native_emit as ne
    from oni_ml_tpu.scoring.score import _batched_scores

    class M:
        theta = np.ones((4, 3))
        p = np.ones((5, 3))

    bad = [
        (np.array([-1, 0], np.int32), np.array([0, 1], np.int32)),
        (np.array([0, 4], np.int32), np.array([0, 1], np.int32)),
        (np.array([0, 1], np.int32), np.array([0, 5], np.int32)),
    ]
    real = ne.score_dot
    for engines in ("native", "fallback"):
        if engines == "fallback":
            ne.score_dot = lambda *a, **k: None
        try:
            for ia, ib in bad:
                with pytest.raises(IndexError):
                    _batched_scores(M(), ia, ib)
            ok = _batched_scores(
                M(), np.array([0, 3], np.int32), np.array([4, 0], np.int32)
            )
            assert np.allclose(ok, 3.0)
        finally:
            ne.score_dot = real


def test_score_dot_rejects_pre_cast_overflow_ids():
    """Range validation must run BEFORE the int32 cast: an int64 id of
    2**32 wraps to 0 post-cast and would silently score row 0."""
    import pytest

    from oni_ml_tpu import native_emit

    if not native_emit.available():
        pytest.skip("native lib unavailable")
    theta = np.ones((4, 3))
    p = np.ones((5, 3))
    with pytest.raises(IndexError):
        native_emit.score_dot(
            theta, p,
            np.array([2 ** 32, 0], np.int64), np.array([0, 1], np.int64),
        )
