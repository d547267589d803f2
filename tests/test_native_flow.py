"""Native (C++) flow featurizer vs the pure-Python path.

The native path must be featurization-identical: same kept rows, same
numeric columns, same words, same first-seen-order word counts, same
scored output.  Skips when the native lib can't build (no g++).
"""

import pickle

import numpy as np
import pytest

from oni_ml_tpu.features import flow as pyflow
from oni_ml_tpu.features import native_flow

from test_features import flow_row

pytestmark = pytest.mark.skipif(
    not native_flow.available(), reason="native flow featurizer unavailable"
)


def make_day(tmp_path, n=500, seed=7, with_edge_rows=True):
    rng = np.random.default_rng(seed)
    lines = ["word,count,header"]
    for _ in range(n):
        lines.append(
            flow_row(
                hour=int(rng.integers(0, 24)),
                minute=int(rng.integers(0, 60)),
                second=int(rng.integers(0, 60)),
                sip=f"10.0.{rng.integers(0, 4)}.{rng.integers(1, 60)}",
                dip=f"172.16.{rng.integers(0, 4)}.{rng.integers(1, 60)}",
                col10=str(rng.choice([80, 443, 55000, 0, 1024, 1025])),
                col11=str(rng.choice([80, 6000, 70000, 0, 1024])),
                ipkt=str(rng.integers(1, 100)),
                ibyt=str(rng.integers(40, 10000)),
            )
        )
    if with_edge_rows:
        lines.insert(5, "word,count,header")        # duplicate header
        lines.insert(7, "short,row")                # wrong field count
        lines.append(",".join(["##"] * 27))         # NaN everything
        lines.append(flow_row(col10="0", col11="0"))      # both ports zero
        lines.append(flow_row(col10="80", col11="80"))    # equal ports
        lines.append(flow_row(col10="bogus", col11="80"))  # NaN port
        # Overflow/underflow numerals: Python float() saturates to
        # inf / 0.0; the native parser must match, not yield NaN.
        lines.append(flow_row(ibyt="1e999", ipkt="1e-999"))
        lines.append(flow_row(col10="1e999", col11="80"))
    path = tmp_path / "flow.csv"
    path.write_text("\n".join(lines) + "\n")
    return path, lines


def featurize_both(tmp_path, feedback_rows=(), **kw):
    path, lines = make_day(tmp_path, **kw)
    with open(path) as f:
        py = pyflow.featurize_flow(
            (line.rstrip("\n") for line in f), feedback_rows=feedback_rows
        )
    nat = native_flow.featurize_flow_file(
        str(path), feedback_rows=feedback_rows
    )
    assert isinstance(nat, native_flow.NativeFlowFeatures)
    return py, nat


def assert_parity(py, nat):
    assert nat.num_events == py.num_events
    assert nat.num_raw_events == py.num_raw_events
    np.testing.assert_array_equal(nat.time_cuts, py.time_cuts)
    np.testing.assert_array_equal(nat.ibyt_cuts, py.ibyt_cuts)
    np.testing.assert_array_equal(nat.ipkt_cuts, py.ipkt_cuts)
    np.testing.assert_array_equal(nat.num_time, py.num_time)
    np.testing.assert_array_equal(nat.time_bin, py.time_bin)
    np.testing.assert_array_equal(nat.ibyt_bin, py.ibyt_bin)
    np.testing.assert_array_equal(nat.ipkt_bin, py.ipkt_bin)
    assert nat.word_port == py.word_port
    assert nat.src_word == py.src_word
    assert nat.dest_word == py.dest_word
    assert nat.ip_pair == py.ip_pair
    assert nat.rows == py.rows
    assert nat.word_counts() == py.word_counts()
    for i in range(0, py.num_events, max(1, py.num_events // 7)):
        assert nat.featurized_row(i) == py.featurized_row(i)
        assert nat.sip(i) == py.sip(i)
        assert nat.dip(i) == py.dip(i)


def test_parity_random_day(tmp_path):
    py, nat = featurize_both(tmp_path)
    assert_parity(py, nat)


def test_parity_with_feedback(tmp_path):
    fb = [flow_row(sip="9.9.9.9", dip="8.8.8.8", col10="80", col11="55000")] * 7
    py, nat = featurize_both(tmp_path, feedback_rows=fb)
    assert_parity(py, nat)
    # Feedback rows train but are not scored.
    assert nat.num_events == nat.num_raw_events + 7


def test_parity_precomputed_cuts(tmp_path):
    path, _ = make_day(tmp_path)
    cuts = (
        np.linspace(0, 20, 10),
        np.linspace(0, 9000, 10),
        np.linspace(0, 80, 5),
    )
    with open(path) as f:
        py = pyflow.featurize_flow(
            (line.rstrip("\n") for line in f), precomputed_cuts=cuts
        )
    nat = native_flow.featurize_flow_file(str(path), precomputed_cuts=cuts)
    assert_parity(py, nat)


def test_parity_long_cut_lists(tmp_path):
    # >15 cuts used to alias the native word cache's packed key; 12-bit
    # fields must keep words exact for any realistic cut list.
    path, _ = make_day(tmp_path)
    cuts = (
        np.linspace(0, 23, 20),
        np.linspace(0, 9000, 20),
        np.linspace(0, 80, 17),
    )
    with open(path) as f:
        py = pyflow.featurize_flow(
            (line.rstrip("\n") for line in f), precomputed_cuts=cuts
        )
    nat = native_flow.featurize_flow_file(str(path), precomputed_cuts=cuts)
    assert_parity(py, nat)


def test_absurd_cut_lists_rejected(tmp_path):
    path, _ = make_day(tmp_path, n=10)
    cuts = (np.zeros(5000), np.zeros(10), np.zeros(5))
    with pytest.raises(ValueError, match="4095"):
        native_flow.featurize_flow_file(str(path), precomputed_cuts=cuts)


def test_empty_directory_errors(tmp_path):
    # A directory input expands to its files; an EMPTY expansion must
    # raise, not return an empty day.
    with pytest.raises(OSError, match="no flow input files"):
        native_flow.featurize_flow_file(str(tmp_path))


def test_multi_file_ingest_matches_concatenated(tmp_path):
    """Comma list / glob / directory inputs featurize identically to
    the concatenated single file: one joint ECDF over the union, part
    headers dropped (the reference's removeHeader over an HDFS
    location, flow_pre_lda.scala:249)."""
    path, lines = make_day(tmp_path, n=400)
    # Split into three "part files", each carrying the same header line
    # (Spark part files all carry it; removeHeader drops the copies).
    header, rows = lines[0], lines[1:]
    parts_dir = tmp_path / "parts"
    parts_dir.mkdir()
    for i, chunk in enumerate((rows[:150], rows[150:300], rows[300:])):
        (parts_dir / f"part-{i:05d}.csv").write_text(
            "\n".join([header] + chunk) + "\n"
        )
    whole = native_flow.featurize_flow_file(str(path))
    for spec in (
        ",".join(
            str(parts_dir / f"part-{i:05d}.csv") for i in range(3)
        ),
        str(parts_dir / "part-*.csv"),
        str(parts_dir),
    ):
        multi = native_flow.featurize_flow_file(spec)
        assert_parity(multi, whole) if isinstance(
            multi, native_flow.NativeFlowFeatures
        ) else None
        assert multi.num_events == whole.num_events
        assert multi.word_counts() == whole.word_counts()
        assert multi.rows == whole.rows


def test_job_output_markers_skipped(tmp_path):
    """Spark hiddenFileFilter semantics: a real job-output dir carries
    _SUCCESS / .part-*.crc / _metadata markers alongside the part
    files — directory and glob expansion must skip '_'/'.'-prefixed
    names (they are checksums/flags, not data), while explicitly named
    files always pass."""
    path, lines = make_day(tmp_path, n=60)
    out_dir = tmp_path / "job_out"
    out_dir.mkdir()
    (out_dir / "part-00000.csv").write_text("\n".join(lines) + "\n")
    (out_dir / "_SUCCESS").write_text("")
    (out_dir / "_metadata").write_bytes(b"\x00\x01binary")
    (out_dir / ".part-00000.csv.crc").write_bytes(b"\x00crc")
    assert native_flow.expand_flow_paths(str(out_dir)) == [
        str(out_dir / "part-00000.csv")
    ]
    assert native_flow.expand_flow_paths(str(out_dir / "*")) == [
        str(out_dir / "part-00000.csv")
    ]
    # Explicit naming bypasses the filter.
    assert native_flow.expand_flow_paths(str(out_dir / "_SUCCESS")) == [
        str(out_dir / "_SUCCESS")
    ]
    # A glob matching day DIRECTORIES expands each like the directory
    # branch (multi-day spec: /data/flow/2016*) — never returns a
    # directory path for the reader to open().
    assert native_flow.expand_flow_paths(str(tmp_path / "job_*")) == [
        str(out_dir / "part-00000.csv")
    ]
    # Hidden DIRECTORIES matched by a glob are skipped too (_logs/,
    # mid-job _temporary/ attempt dirs).
    logs = out_dir / "_logs"
    logs.mkdir()
    (logs / "history.csv").write_text("not,flow,data\n")
    assert native_flow.expand_flow_paths(str(out_dir / "*")) == [
        str(out_dir / "part-00000.csv")
    ]
    # ...but a pattern whose basename itself starts with '_' is a
    # deliberate selection of hidden names and passes.
    assert native_flow.expand_flow_paths(str(out_dir / "_SUC*")) == [
        str(out_dir / "_SUCCESS")
    ]
    whole = native_flow.featurize_flow_file(str(path))
    multi = native_flow.featurize_flow_file(str(out_dir))
    assert multi.num_events == whole.num_events
    assert multi.word_counts() == whole.word_counts()


def test_multi_file_python_fallback_matches(tmp_path):
    """The pure-Python fallback chains files with the same header
    semantics as the native path."""
    from itertools import chain

    path, lines = make_day(tmp_path, n=120)
    header, rows = lines[0], lines[1:]
    p1, p2 = tmp_path / "a.csv", tmp_path / "b.csv"
    p1.write_text("\n".join([header] + rows[:60]) + "\n")
    p2.write_text("\n".join([header] + rows[60:]) + "\n")
    with open(path) as f:
        whole = pyflow.featurize_flow(line.rstrip("\n") for line in f)
    from oni_ml_tpu.features.lineio import iter_raw_lines

    multi = pyflow.featurize_flow(
        chain.from_iterable(iter_raw_lines(str(p)) for p in (p1, p2))
    )
    assert multi.num_events == whole.num_events
    assert multi.word_counts() == whole.word_counts()
    assert multi.rows == whole.rows


def test_pickle_roundtrip(tmp_path):
    _, nat = featurize_both(tmp_path, n=50)
    again = pickle.loads(pickle.dumps(nat))
    assert again.word_counts() == nat.word_counts()
    assert again.featurized_row(3) == nat.featurized_row(3)
    assert again.num_raw_events == nat.num_raw_events


def test_scoring_identical(tmp_path):
    from oni_ml_tpu.scoring import ScoringModel, score_flow

    py, nat = featurize_both(tmp_path)
    k = 4
    rng = np.random.default_rng(0)
    ips = sorted({ip for ip, _, _ in py.word_counts()})
    words = sorted({w for _, w, _ in py.word_counts()})
    model = ScoringModel.from_results(
        doc_names=ips,
        doc_topic=rng.dirichlet(np.ones(k), size=len(ips)),
        vocab=words,
        word_topic=rng.dirichlet(np.ones(k), size=len(words)),
        fallback=0.05,
    )
    rows_py, s_py = score_flow(py, model, threshold=1.1)
    rows_nat, s_nat = score_flow(nat, model, threshold=1.1)
    assert rows_py == rows_nat
    np.testing.assert_array_equal(s_py, s_nat)


def test_spill_parity_and_pickle_bound(tmp_path):
    """spill_path streams raw rows to disk at ingest: identical
    featurization/rows/scoring surface, and features.pkl stays small
    because it references the spill file instead of embedding the
    bytes."""
    from oni_ml_tpu.features.blob import MmapBlob

    path, _ = make_day(tmp_path)
    nat = native_flow.featurize_flow_file(str(path))
    spill = native_flow.featurize_flow_file(
        str(path), spill_path=str(tmp_path / "raw_lines.bin")
    )
    assert isinstance(spill.lines_blob, MmapBlob)
    assert len(spill.lines_blob) == len(nat.lines_blob)
    assert spill.rows == nat.rows
    for i in range(0, nat.num_events, max(1, nat.num_events // 7)):
        assert spill.featurized_row(i) == nat.featurized_row(i)
    assert spill.word_counts() == nat.word_counts()

    # The pickle must NOT embed the blob: bound it by the non-blob data.
    import pickle as pkl

    spill_pkl = pkl.dumps(spill)
    assert len(spill_pkl) < len(pkl.dumps(nat)) - len(nat.lines_blob) // 2
    again = pkl.loads(spill_pkl)
    assert again.rows == nat.rows

    # Post-hoc spill of an in-memory container reaches the same state.
    nat.spill_lines(str(tmp_path / "raw_lines2.bin"))
    assert isinstance(nat.lines_blob, MmapBlob)
    assert nat.rows == spill.rows
    nat.spill_lines(str(tmp_path / "raw_lines3.bin"))  # idempotent no-op
    assert nat.lines_blob.path == str(tmp_path / "raw_lines2.bin")


def test_spill_with_feedback_and_scoring(tmp_path):
    """Feedback rows ingested after mark_raw append to the spill file;
    native emit reads rows through the mmap and must produce the exact
    bytes of the in-memory path."""
    from oni_ml_tpu.scoring import ScoringModel, score_flow_csv

    fb = [flow_row(sip="9.9.9.9", dip="8.8.8.8", col10="80",
                   col11="55000")] * 5
    path, _ = make_day(tmp_path)
    nat = native_flow.featurize_flow_file(str(path), feedback_rows=fb)
    spill = native_flow.featurize_flow_file(
        str(path), feedback_rows=fb,
        spill_path=str(tmp_path / "raw_lines.bin"),
    )
    assert spill.num_events == nat.num_events
    assert spill.num_raw_events == nat.num_raw_events

    k = 4
    rng = np.random.default_rng(0)
    ips = sorted({ip for ip, _, _ in nat.word_counts()})
    words = sorted({w for _, w, _ in nat.word_counts()})
    model = ScoringModel.from_results(
        doc_names=ips,
        doc_topic=rng.dirichlet(np.ones(k), size=len(ips)),
        vocab=words,
        word_topic=rng.dirichlet(np.ones(k), size=len(words)),
        fallback=0.05,
    )
    blob_nat, s_nat = score_flow_csv(nat, model, threshold=1.1)
    blob_spill, s_spill = score_flow_csv(spill, model, threshold=1.1)
    assert blob_nat == blob_spill
    np.testing.assert_array_equal(s_nat, s_spill)


def test_spill_bounds_rss(tmp_path):
    """Featurizing a day with spill_path must keep the high-water RSS
    well below input size + numeric arrays: the raw bytes never live in
    RAM ('Done = a test featurizing a synthetic day with RSS
    bounded well below input size').  Measured in a subprocess so other
    tests' allocations can't mask the high-water mark; rows carry a fat
    pad column so the blob dominates the per-event arrays."""
    import subprocess
    import sys
    import textwrap

    pad = "x" * 400
    n = 60_000
    day = tmp_path / "fat_day.csv"
    with open(day, "w") as f:
        f.write("h1,h2,h3\n")
        for i in range(n):
            f.write(
                f"a,b,c,{i % 24},{i % 60},{i % 60},d,e,10.0.0.{i % 250},"
                f"10.1.0.{i % 250},1024,{80 + i % 3},TCP,{pad},0,0,"
                f"{1 + i % 90},{40 + i % 9000},0,0,0,0,0,0,0,x,y\n"
            )
    input_bytes = day.stat().st_size
    assert input_bytes > 25 * 1024**2

    # VmHWM (resets on exec) rather than ru_maxrss (which Linux
    # carries ACROSS execve — a child forked from the jax-loaded pytest
    # process inherits its ~170MB high-water mark).
    script = textwrap.dedent(
        """
        import sys
        from oni_ml_tpu.features import native_flow
        spill = len(sys.argv) > 2
        kw = {"spill_path": sys.argv[2]} if spill else {}
        feats = native_flow.featurize_flow_file(sys.argv[1], **kw)
        assert feats.num_events > 0
        hwm = [l for l in open("/proc/self/status") if l.startswith("VmHWM")]
        print(hwm[0].split()[1])
        """
    )

    import os

    env = dict(os.environ)

    def rss_kb(*args):
        out = subprocess.run(
            [sys.executable, "-c", script, *args],
            capture_output=True, text=True, check=True, env=env,
        )
        return int(out.stdout.strip())

    # Baseline: the same interpreter + imports with no featurize work,
    # measured on THIS host so the bounds are relative, not absolute.
    idle_script = script.replace(
        "feats = native_flow.featurize_flow_file(sys.argv[1], **kw)\n"
        "assert feats.num_events > 0",
        "feats = None",
    )
    assert "feats = None" in idle_script

    def idle_kb():
        out = subprocess.run(
            [sys.executable, "-c", idle_script, str(day)],
            capture_output=True, text=True, check=True, env=env,
        )
        return int(out.stdout.strip())

    idle = idle_kb()
    spill_kb = rss_kb(str(day), str(tmp_path / "s1.bin"))
    plain_kb = rss_kb(str(day))
    # The in-memory run must carry ~the whole blob over the spill run...
    assert (plain_kb - spill_kb) * 1024 > 0.6 * input_bytes
    # ...and the spill run's increment over the idle baseline must stay
    # well below input size — i.e. the blob is never resident; what
    # remains is the numeric per-event arrays and table interning.
    assert (spill_kb - idle) * 1024 < 0.6 * input_bytes
