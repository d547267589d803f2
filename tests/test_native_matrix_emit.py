"""final.beta / final.gamma through the native writer
(native_emit.matrix_emit behind io/formats.write_beta / write_gamma):
the bytes are np.savetxt(path, a, fmt="%5.10f")'s on every input, the
switch and the environment give the Python writer back, and `fit.save`
says which of the two wrote."""

import os
import time

import numpy as np
import pytest

from benchmarks.reference import ldac_files
from oni_ml_tpu import native_emit
from oni_ml_tpu.io import formats
from oni_ml_tpu.models import lda as lda_mod
from oni_ml_tpu.telemetry import spans

pytestmark = pytest.mark.skipif(
    not native_emit.available(), reason="native emit not built and no g++"
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "expected")

EDGE_VALUES = [
    0.0, -0.0, 5e-11, 1.5e-10, 2.5e-10, -2.5e-10, 0.99999999995,
    9.99999999995, 2.0**-1074, 1e-320, 2.0**30 - 0.5, 2.0**30, 1e15, 1e22,
    -1e300, np.inf, -np.inf, np.nan,
    # beyond the issue's list: a nan with its sign bit set (printf would
    # name the sign, Python does not), the largest double's 309 digits,
    # the last value of the integer path and the smallest normal.
    -np.nan, 1.7976931348623157e308, -(2.0**30 - 2.0**-23), 2.0**-1022,
]


def _rng():
    return np.random.default_rng(36)


MATRICES = {
    "gamma_f32": lambda: _rng().gamma(0.3, 5.0, (500, 20)).astype(np.float32),
    "gamma_f64": lambda: _rng().gamma(0.3, 5.0, (500, 20)),
    "log_beta_f32": lambda: np.log(
        _rng().dirichlet(np.full(300, 0.05), 20)
        + 1e-30).astype(np.float32),
    "log_beta_f64": lambda: np.log(
        _rng().dirichlet(np.full(300, 0.05), 20) + 1e-300),
    "k1": lambda: _rng().gamma(1.0, 2.0, (40, 1)),
    "d1": lambda: _rng().gamma(1.0, 2.0, (1, 20)),
    "one_value": lambda: np.array([[2.5]]),
    "zero_rows": lambda: np.zeros((0, 20)),
    "zero_cols": lambda: np.zeros((3, 0)),
    "non_contiguous": lambda: _rng().gamma(1.0, 2.0, (60, 40))[::3, 1::2],
    "fortran": lambda: np.asfortranarray(_rng().gamma(1.0, 2.0, (30, 20))),
    "edge_row": lambda: np.array([EDGE_VALUES]),
    "edge_column": lambda: np.array(EDGE_VALUES)[:, None],
    # more than one slab of 1 MiB, so the flush in mid-row is exercised,
    # and long values next to the slab's end
    "two_slabs": lambda: _rng().gamma(0.3, 5.0, (6000, 20)),
    "long_values": lambda: np.full((40, 100), -1.7976931348623157e308),
    # what savetxt accepts and the native pass hands back to it
    "one_d": lambda: _rng().gamma(1.0, 2.0, 7),
    "int_list": lambda: [[1, 2], [3, 4]],
}


def _savetxt_bytes(tmp_path, a):
    path = tmp_path / "savetxt"
    np.savetxt(str(path), np.asarray(a, dtype=np.float64), fmt="%5.10f")
    return path.read_bytes()


@pytest.mark.parametrize("which", ["beta", "gamma"])
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_matrix_bytes_are_savetxts(tmp_path, name, which):
    a = MATRICES[name]()
    got = tmp_path / f"final.{which}"
    getattr(formats, f"write_{which}")(str(got), a)
    assert got.read_bytes() == _savetxt_bytes(tmp_path, a)
    assert formats.matrix_writer == (
        "python" if name == "one_d" else "native")


@pytest.mark.parametrize("which", ["beta", "gamma"])
@pytest.mark.parametrize("source", ["flow", "dns"])
def test_golden_matrices_come_back_byte_for_byte(tmp_path, source, which):
    """The golden day's files, read and written again: np.savetxt's bytes,
    which are the file's own (ten digits round-trip at these magnitudes)."""
    golden = os.path.join(GOLDEN, source, f"final.{which}")
    a = np.loadtxt(golden, ndmin=2)
    got = tmp_path / f"final.{which}"
    getattr(formats, f"write_{which}")(str(got), a)
    assert formats.matrix_writer == "native"
    assert got.read_bytes() == _savetxt_bytes(tmp_path, a)
    with open(golden, "rb") as f:
        assert got.read_bytes() == f.read()


def test_native_overwrites_a_longer_file(tmp_path):
    path = tmp_path / "final.gamma"
    path.write_bytes(b"x" * 4096)
    formats.write_gamma(str(path), np.array([[1.0, 2.0]]))
    assert formats.matrix_writer == "native"
    assert path.read_bytes() == b"1.0000000000 2.0000000000\n"


@pytest.mark.parametrize("bad", ["missing_dir", "is_dir"])
def test_unwritable_path_raises_what_savetxt_raises(tmp_path, bad):
    path = tmp_path / "nowhere" / "final.beta" if bad == "missing_dir" \
        else tmp_path
    a = np.ones((2, 2))
    with pytest.raises(OSError) as want:
        np.savetxt(str(path), a, fmt="%5.10f")
    with pytest.raises(OSError) as got:
        formats.write_beta(str(path), a)
    assert type(got.value) is type(want.value)
    assert got.value.errno == want.value.errno
    assert formats.matrix_writer == "python"


def test_compressing_extension_is_savetxts(tmp_path):
    """np.savetxt compresses by the file name's extension; such a name is
    not the native writer's."""
    import gzip

    a = _rng().gamma(1.0, 2.0, (5, 4))
    path = tmp_path / "final.gamma.gz"
    formats.write_gamma(str(path), a)
    assert formats.matrix_writer == "python"
    plain = tmp_path / "plain"
    np.savetxt(str(plain), a, fmt="%5.10f")
    with gzip.open(path, "rb") as f:
        assert f.read() == plain.read_bytes()


def test_binding_says_cannot(tmp_path):
    path = str(tmp_path / "m")
    ok = np.ones((2, 3))
    assert native_emit.matrix_emit(path, ok) is True
    for a in (np.ones(3), np.ones((2, 3), np.float32), np.ones((1, 2, 3)),
              [[1.0, 2.0]]):
        assert native_emit.matrix_emit(path, a) is False
    assert native_emit.matrix_emit(str(tmp_path / "no" / "m"), ok) is False


def _result(d=37, k=4, v=96, dtype=np.float64):
    rng = _rng()
    return lda_mod.LDAResult(
        log_beta=np.log(rng.dirichlet(np.full(v, 0.1), k)).astype(dtype),
        gamma=rng.gamma(0.5, 3.0, (d, k)).astype(dtype),
        alpha=2.5, likelihoods=[(-1234.5, 1.0), (-1200.25, 2.7e-2)],
    )


def _save(result, directory):
    os.makedirs(directory)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        with spans.maybe_span("fit.save") as sp:
            sp.annotate(**result.save(str(directory)))
    (save,) = [e for e in rec.events if e["name"] == "fit.save"]
    return save["args"], {
        n: (directory / n).read_bytes() for n in ldac_files.FILES}


def _fresh_loader(monkeypatch):
    """A loader that has not loaded yet: ONI_ML_TPU_NO_NATIVE is read when
    a library first loads."""
    from oni_ml_tpu import native_build

    monkeypatch.setattr(native_build, "_LIBRARIES", [])
    real = native_emit._LIB
    monkeypatch.setattr(native_emit, "_LIB", native_build.NativeLib(
        real._src, real._lib_path, real._configure, deps=real._deps))


@pytest.mark.parametrize("how", ["patched", "env"])
def test_save_under_both_writers(tmp_path, monkeypatch, how):
    result = _result(dtype=np.float32)
    counts, files = _save(result, tmp_path / "native")
    assert counts["writer"] == "native"
    if how == "patched":
        monkeypatch.setattr(native_emit, "available", lambda: False)
    else:
        _fresh_loader(monkeypatch)
        monkeypatch.setenv("ONI_ML_TPU_NO_NATIVE", "1")
    py_counts, py_files = _save(result, tmp_path / "python")
    assert py_counts["writer"] == "python"
    if how == "env":
        assert native_emit._LIB.status == "python-fallback"
    assert py_files == files
    assert {**py_counts, "writer": "native"} == counts
    assert counts["gamma_bytes"] == len(files["final.gamma"])
    assert counts["beta_bytes"] == len(files["final.beta"])
    assert counts["rows"] == 4 + 37 and counts["values"] == 4 * 96 + 37 * 4


def test_cli_same_files_and_lines_under_both_writers(tmp_path, monkeypatch,
                                                     capsys):
    """The writer is no part of the result: `lda est` leaves the same four
    files and prints the same lines under either, and `fit.save` names the
    one that wrote."""
    from test_lda_cli import _est, _seeded_day

    day, (ptr, _, _, v) = _seeded_day(tmp_path)
    said, saves = [], []
    for writer in ("native", "python"):
        if writer == "python":
            monkeypatch.setattr(native_emit, "available", lambda: False)
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            assert _est(day, tmp_path / writer) == 0
        said.append(capsys.readouterr().out)
        saves += [e["args"] for e in rec.events if e["name"] == "fit.save"]
    assert [s["writer"] for s in saves] == ["native", "python"]
    assert said[0] == said[1] and "em iterations" in said[0]
    for name in ldac_files.FILES:
        assert (tmp_path / "native" / name).read_bytes() == (
            tmp_path / "python" / name).read_bytes(), name
    fit, problems = ldac_files.read_fit(
        str(tmp_path / "native"), len(ptr) - 1, 4, v)
    assert fit is not None and problems == []


def test_native_matrix_is_faster_smoke(tmp_path):
    """Not a strict benchmark: 400,000 values, and the native writer must
    not be slower than np.savetxt (it is over 10x faster)."""
    a = _rng().gamma(0.3, 5.0, (20_000, 20))
    t0 = time.perf_counter()
    formats.write_gamma(str(tmp_path / "native"), a)
    t_nat = time.perf_counter() - t0
    assert formats.matrix_writer == "native"
    t0 = time.perf_counter()
    np.savetxt(str(tmp_path / "python"), a, fmt="%5.10f")
    t_py = time.perf_counter() - t0
    assert (tmp_path / "native").read_bytes() == (
        tmp_path / "python").read_bytes()
    assert t_nat < t_py, (t_nat, t_py)
