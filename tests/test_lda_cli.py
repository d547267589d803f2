"""lda-c-compatible CLI: settings.txt parsing + the reference argument
vector producing the final.* / likelihood.dat contract."""

import numpy as np
import pytest

from oni_ml_tpu.io import Corpus, formats
from oni_ml_tpu.runner import lda_cli

import reference_lda as ref
from test_lda import corpus_from_docs


def test_read_settings(tmp_path):
    p = tmp_path / "settings.txt"
    p.write_text(
        "var max iter 30\n"
        "var convergence 1e-7\n"
        "em max iter 12\n"
        "em convergence 1e-5\n"
        "alpha estimate\n"
    )
    s = lda_cli.read_settings(str(p))
    assert s == {
        "var_max_iters": 30,
        "var_tol": 1e-7,
        "em_max_iters": 12,
        "em_tol": 1e-5,
        "estimate_alpha": True,
    }


def test_read_settings_alpha_fixed_and_unknown_keys(tmp_path):
    p = tmp_path / "settings.txt"
    p.write_text("alpha fixed\nsome future knob 3\nem max iter 5\n")
    s = lda_cli.read_settings(str(p))
    assert s == {"estimate_alpha": False, "em_max_iters": 5}


def test_read_settings_unbounded_var_iter_sentinel(tmp_path):
    # lda-c's inf-settings.txt uses -1 for "iterate until converged".
    p = tmp_path / "settings.txt"
    p.write_text("var max iter -1\n")
    s = lda_cli.read_settings(str(p))
    assert s["var_max_iters"] >= 10_000


def test_mesh_from_spec():
    from oni_ml_tpu.parallel import mesh_from_spec

    mesh, vocab_sharded = mesh_from_spec("4,2")
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    assert vocab_sharded
    mesh, vocab_sharded = mesh_from_spec("8,1")
    assert not vocab_sharded
    for bad in ("8", "a,b", "1,2,3"):
        with pytest.raises(ValueError, match="DATA,MODEL"):
            mesh_from_spec(bad)


def test_cli_reference_argv_end_to_end(tmp_path):
    docs, _ = ref.make_synthetic_corpus(
        num_docs=20, num_terms=25, num_topics=3, seed=1
    )
    corpus = corpus_from_docs(docs, 25)
    day = tmp_path / "day"
    day.mkdir()
    corpus.save(str(day))
    settings = tmp_path / "settings.txt"
    settings.write_text(
        "var max iter 20\nvar convergence 1e-6\n"
        "em max iter 8\nem convergence 0\nalpha estimate\n"
    )

    rc = lda_cli.main([
        "est", "2.5", "4", str(settings), "20",
        str(day / "model.dat"), "random", str(day),
    ])
    assert rc == 0

    beta = formats.read_beta(str(day / "final.beta"))
    gamma = formats.read_gamma(str(day / "final.gamma"))
    assert beta.shape == (4, 25)
    assert gamma.shape == (corpus.num_docs, 4)
    np.testing.assert_allclose(np.exp(beta).sum(-1), np.ones(4), rtol=1e-4)
    lls = [
        float(line.split("\t")[0])
        for line in (day / "likelihood.dat").read_text().splitlines()
    ]
    assert len(lls) == 8
    assert lls[-1] > lls[0]  # training improved the likelihood


def test_cli_rejects_bad_argv(capsys):
    assert lda_cli.main(["est", "2.5"]) == 2
    assert lda_cli.main([
        "est", "2.5", "4", "s.txt", "20", "m.dat", "seeded", "out",
    ]) == 2


def test_help_flag_exits_zero(capsys):
    from oni_ml_tpu.runner.lda_cli import main as lda_main
    from oni_ml_tpu.features.qtiles import main as qtiles_main

    assert lda_main(["--help"]) == 0
    assert "usage" in capsys.readouterr().out
    assert qtiles_main(["-h"]) == 0
    assert "usage" in capsys.readouterr().out
    # empty argv stays the error path
    assert lda_main([]) == 2
    assert qtiles_main([]) == 2


# ---------------------------------------------------------------------------
# The drop-in as a deployment (PR 33): the files against the plain
# reference, the written guarantees, the spans, and the dense budget that
# follows the device because this surface cannot state one.
# ---------------------------------------------------------------------------

import json  # noqa: E402
import os  # noqa: E402
import threading  # noqa: E402

import jax  # noqa: E402

from benchmarks.reference import lda_plain, ldac_files  # noqa: E402
from oni_ml_tpu.config import LDAConfig  # noqa: E402
from oni_ml_tpu.io import make_batches  # noqa: E402
from oni_ml_tpu.models import LDATrainer, lda as lda_mod  # noqa: E402
from oni_ml_tpu.telemetry import spans  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EST_LDA = dict(num_topics=4, alpha_init=2.5, estimate_alpha=True,
               var_max_iters=20, var_tol=1e-6, em_max_iters=6, em_tol=0.0,
               alpha_max_iters=100, warm_start=False, seed=0)


def _seeded_day(tmp_path, num_docs=150, num_terms=96, seed=7):
    """A seeded tiny model.dat + settings.txt written by the REFERENCE's
    writer, and the CSR arrays they came from."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, num_docs)
    ptr = np.r_[0, np.cumsum(lens)].astype(np.int64)
    words = np.concatenate(
        [np.sort(rng.choice(num_terms, n, replace=False)) for n in lens]
    ).astype(np.int32)
    words[0] = num_terms - 1             # the file's vocabulary is max id + 1
    counts = rng.integers(1, 5, len(words)).astype(np.int32)
    day = tmp_path / "day"
    day.mkdir()
    ldac_files.write_model_dat(str(day / "model.dat"), ptr, words, counts)
    ldac_files.write_settings(str(day / "settings.txt"), EST_LDA)
    return day, (ptr, words, counts, num_terms)


def _est(day, out):
    out.mkdir()
    return lda_cli.main(ldac_files.est_argv(
        EST_LDA, str(day / "settings.txt"), str(day / "model.dat"),
        str(out)))


def test_cli_files_against_the_plain_reference(tmp_path):
    """lda-c's semantics, read from the FILES: every EM iteration's
    likelihood, beta, alpha and every document's gamma in model.dat's
    order against benchmarks/reference/lda_plain.py under fresh start and
    100 Newton trips."""
    day, (ptr, words, counts, v) = _seeded_day(tmp_path)
    assert _est(day, tmp_path / "out") == 0
    got, problems = ldac_files.read_fit(
        str(tmp_path / "out"), len(ptr) - 1, EST_LDA["num_topics"], v)
    assert problems == []
    want = lda_plain.fit(ptr, words, counts, v, EST_LDA, stop_rule=False)
    assert got.em_iters == want.em_iters == 6
    np.testing.assert_allclose(got.likelihoods, want.likelihoods, rtol=1e-4)
    np.testing.assert_allclose(got.alpha, want.alpha, rtol=1e-3)
    np.testing.assert_allclose(np.exp(got.log_beta), np.exp(want.log_beta),
                               atol=2e-4)
    np.testing.assert_allclose(got.gamma, want.gamma, rtol=5e-3, atol=1e-3)
    assert ldac_files.conv_problems(got.ll, 0.0, 6) == []


def test_cli_guarantees_hold_when_main_returns(tmp_path, capsys):
    """When main returns 0 the four files are complete, closed and
    readable, every value carries ten digits, and nothing is written
    afterwards (no thread outlives the call)."""
    day, (ptr, _, _, v) = _seeded_day(tmp_path)
    threads = threading.active_count()
    assert _est(day, tmp_path / "out") == 0
    assert threading.active_count() == threads
    out = tmp_path / "out"
    assert sorted(os.listdir(out)) == sorted(ldac_files.FILES)
    before = {n: (os.path.getsize(out / n), os.stat(out / n).st_mtime_ns)
              for n in ldac_files.FILES}
    fit, problems = ldac_files.read_fit(str(out), len(ptr) - 1, 4, v)
    assert problems == [] and fit.gamma.shape == (len(ptr) - 1, 4)
    for name in ("final.beta", "final.gamma"):
        for value in (out / name).read_text().split():
            assert ldac_files.FIXED.fullmatch(value), (name, value)
    said = capsys.readouterr().out
    assert "em iterations: 6" in said
    assert "engine: dense  kernel: xla  dense budget: 2147483648 (fallback)" \
        in said
    assert before == {
        n: (os.path.getsize(out / n), os.stat(out / n).st_mtime_ns)
        for n in ldac_files.FILES}


def test_cli_spans_the_load_and_counts_the_files(tmp_path):
    """`est.load` once a call, a root before the root `fit`; its counters
    are the file's size and the corpus' counts; `fit.save` counts the
    bytes it wrote; the root counts likelihood.dat's lines and says whose
    the dense budget was."""
    day, (ptr, words, _, _) = _seeded_day(tmp_path)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        assert _est(day, tmp_path / "a") == 0
        assert _est(day, tmp_path / "b") == 0
    events = sorted(rec.events, key=lambda e: e["start_ns"])
    loads = [e for e in events if e["name"] == "est.load"]
    fits = [e for e in events if e["name"] == "fit"]
    saves = [e for e in events if e["name"] == "fit.save"]
    assert len(loads) == len(fits) == len(saves) == 2
    for out, load, fit, save in zip("ab", loads, fits, saves):
        assert load["parent"] is None and load["depth"] == 0
        assert fit["parent"] is None and fit["root"] == fit["id"]
        assert load["start_ns"] + load["dur_ns"] <= fit["start_ns"]
        assert load["args"]["bytes"] == os.path.getsize(day / "model.dat")
        assert load["args"]["docs"] == len(ptr) - 1
        assert load["args"]["pairs"] == len(words)
        assert save["parent"] == fit["id"]
        for name in ("beta", "gamma", "other"):
            assert save["args"][f"{name}_bytes"] == os.path.getsize(
                tmp_path / out / f"final.{name}")
        assert save["args"]["rows"] == 4 + len(ptr) - 1
        assert save["args"]["values"] == 4 * 96 + (len(ptr) - 1) * 4
        lines = (tmp_path / out / "likelihood.dat").read_text().splitlines()
        assert fit["args"]["ll_lines"] == len(lines) == 6
        assert fit["args"]["dense_budget"] == lda_mod.FALLBACK_DENSE_BUDGET
        assert fit["args"]["dense_budget_source"] == "fallback"


def _native_ingest():
    from oni_ml_tpu.io import native

    if not native.available():
        pytest.skip("native ingest not built and no g++")
    return native


@pytest.mark.parametrize("reader", ["native", "python"])
def test_cli_load_span_names_the_reader(tmp_path, monkeypatch, reader):
    """`est.load` says which reader parsed model.dat: `native`, or `python`
    where the library is not to be had."""
    native = _native_ingest()
    if reader == "python":
        monkeypatch.setattr(native, "available", lambda: False)
    day, (ptr, words, _, _) = _seeded_day(tmp_path)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        assert _est(day, tmp_path / "out") == 0
    (load,) = [e for e in rec.events if e["name"] == "est.load"]
    assert load["args"]["reader"] == reader
    assert load["args"]["docs"] == len(ptr) - 1
    assert load["args"]["pairs"] == len(words)


def test_cli_same_files_and_lines_under_both_readers(tmp_path, monkeypatch,
                                                     capsys):
    """The reader is no part of the result: the four files are the same
    bytes and `main` prints the same lines under either."""
    native = _native_ingest()
    day, _ = _seeded_day(tmp_path)
    assert _est(day, tmp_path / "native") == 0
    assert formats.model_dat_reader == "native"
    said_native = capsys.readouterr().out
    monkeypatch.setattr(native, "available", lambda: False)
    assert _est(day, tmp_path / "python") == 0
    assert formats.model_dat_reader == "python"
    assert capsys.readouterr().out == said_native
    for name in ldac_files.FILES:
        assert (tmp_path / "native" / name).read_bytes() == (
            tmp_path / "python" / name).read_bytes(), name


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


# (stated, what the device reports) -> (bytes, source)
BUDGETS = {
    "not_stated_no_stats": (None, None, (2 * 1024**3, "fallback")),
    "not_stated_no_limit": (None, {"bytes_in_use": 5}, (2 * 1024**3,
                                                       "fallback")),
    "not_stated_v5e": (None, {"bytes_limit": 15_750_000_000},
                       (11_812_500_000, "device")),
    "stated_small": (4096, {"bytes_limit": 15_750_000_000},
                     (4096, "stated")),
    "stated_over_the_device": (2**40, {"bytes_limit": 1000},
                               (2**40, "stated")),
}


@pytest.mark.parametrize("case", sorted(BUDGETS))
def test_dense_budget_follows_the_device_unless_stated(monkeypatch, case):
    stated, stats, want = BUDGETS[case]
    monkeypatch.setattr(jax, "local_devices", lambda: [_Device(stats)])
    cfg = LDAConfig(num_topics=4, dense_hbm_budget=stated)
    assert lda_mod.dense_budget(cfg) == want


def test_dense_budget_on_the_cpu_is_the_fallback():
    assert LDAConfig(num_topics=4).dense_hbm_budget is None
    assert lda_mod.dense_budget(LDAConfig(num_topics=4)) == (
        2 * 1024**3, "fallback")


@pytest.mark.parametrize("config", ["flow20", "flow20_dp4"])
def test_the_cells_that_state_a_budget_plan_as_before(monkeypatch, config):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        program = json.load(f)["program"]
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Device({"bytes_limit": 15_750_000_000})])
    cfg = LDAConfig(num_topics=20, **program)
    assert lda_mod.dense_budget(cfg) == (12 * 1024**3, "stated")


# what the plan is held to -> the family it takes for one small corpus
# whose dense form needs 57,344 B (on a backend that says it is a TPU)
PLANS = {
    "device_holds_it": (None, 1_000_000, "dense", "device"),
    "device_too_small": (None, 40_000, "tokens", "device"),
    "stated_holds_it": (1_000_000, 40_000, "dense", "stated"),
    "stated_too_small": (30_000, 1_000_000, "tokens", "stated"),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_plan_holds_the_dense_family_to_the_budget(monkeypatch, case):
    stated, limit, family, source = PLANS[case]
    docs, _ = ref.make_synthetic_corpus(
        num_docs=48, num_terms=200, num_topics=3, seed=11)
    corpus = corpus_from_docs(docs, 200)
    cfg = LDAConfig(num_topics=4, batch_size=16, min_bucket_len=4,
                    dense_hbm_budget=stated)
    batches = make_batches(corpus, cfg.batch_size, cfg.min_bucket_len,
                           pad_multiple=8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Device({"bytes_limit": limit})])
    plan = LDATrainer(cfg, num_terms=200)._plan_estep(batches)
    assert (plan.family, plan.budget_source) == (family, source)
    assert plan.budget == (stated if stated is not None else limit * 3 // 4)
