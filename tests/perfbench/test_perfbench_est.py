"""The cell `flow20_est_files` (job kind `est_files`) at a tiny size on the
CPU: the reference's side of the file contract, `correct` decided from the
FILES (true for the program and for the sound reference in its place, false
for every planted fault and the lower-precision control), the two readers of
the CLI's spans, and the cell's entries."""

import argparse
import json
import os

import numpy as np
import pytest

import entry_rules
from benchmarks import control_est, rehearse, run as bench_run
from benchmarks.harness import cells, fit_check
from benchmarks.jobs import est_files, est_spans
from benchmarks.reference import lda_plain, ldac_files
from oni_ml_tpu.io import formats

CELL = "flow20_est_files"
STAMP = {"platform": "cpu", "kind": "rehearsal", "count": 1}


def _cell():
    return rehearse.shrink(cells.resolve(CELL))


def _run(program=None, trace=0, seed=2**31 + 29, found=None):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.0,
                              trace=trace)
    return bench_run.run_cell(args, STAMP, found or _cell(), program=program)


def _fake(**kw):
    return est_files.fake_program(_cell()["config"]["lda"], **kw)


def _failed(line):
    return [n for n, row in line["compared"].items()
            if row["limit"] is None or not row["value"] <= row["limit"]]


# -- the reference's side of the file contract ---------------------------

def _csr(seed=3, docs=40, terms=30):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 7, docs)
    ptr = np.r_[0, np.cumsum(lens)].astype(np.int64)
    words = np.concatenate(
        [rng.choice(terms, n, replace=False) for n in lens] + [[terms - 1]]
    ).astype(np.int32)
    ptr = np.r_[ptr, ptr[-1] + 1]
    counts = rng.integers(1, 256, len(words)).astype(np.int32)
    return ptr, words, counts


def test_model_dat_writer_and_reader_agree_with_the_programs(tmp_path):
    ptr, words, counts = _csr()
    ours, theirs = str(tmp_path / "a.dat"), str(tmp_path / "b.dat")
    n = ldac_files.write_model_dat(ours, ptr, words, counts)
    formats.write_model_dat(theirs, ptr, words, counts)
    assert open(ours, "rb").read() == open(theirs, "rb").read()
    assert n == os.path.getsize(ours)
    for back in (ldac_files.read_model_dat(ours),
                 formats.read_model_dat(ours)):
        for got, want in zip(back, (ptr, words, counts)):
            np.testing.assert_array_equal(got, want)


def test_settings_and_argv_are_the_references(tmp_path):
    lda = _cell()["config"]["lda"]
    config = cells.resolve(CELL)["config"]
    assert ldac_files.settings_lines(lda) == config["entry"]["settings.txt"]
    path = str(tmp_path / "settings.txt")
    ldac_files.write_settings(path, lda)
    assert ldac_files.read_settings(path) == {
        key: lda[key] for key in ("var_max_iters", "var_tol", "em_max_iters",
                                  "em_tol", "estimate_alpha")}
    from oni_ml_tpu.runner import lda_cli

    assert lda_cli.read_settings(path) == ldac_files.read_settings(path)
    argv = ldac_files.est_argv(lda, "s.txt", "m.dat", "out")
    assert argv == ["est", "2.5", "20", "s.txt", "20", "m.dat", "random",
                    "out"]
    assert [a if not a.startswith("<") else None
            for a in config["entry"]["argv"]] == [
        "est", "2.5", "20", None, "20", None, "random", None]


def _written(tmp_path, em_iters=3, docs=12, k=4, terms=9):
    rng = np.random.default_rng(5)
    ll = np.sort(-1e6 * rng.random(em_iters) - 1.0)
    fit = lda_plain.PlainFit(
        log_beta=np.log(rng.dirichlet(np.ones(terms), k)),
        gamma=rng.random((docs, k)) * 300, alpha=2.345,
        likelihoods=ll.tolist(), em_iters=em_iters)
    out = str(tmp_path)
    os.makedirs(out, exist_ok=True)
    ldac_files.write_fit(out, fit, terms)
    return out, fit


def test_the_readers_take_the_references_and_the_programs_files(tmp_path):
    out, fit = _written(tmp_path / "ref")
    back, problems = ldac_files.read_fit(out, 12, 4, 9)
    assert problems == []
    np.testing.assert_allclose(back.log_beta, fit.log_beta, atol=1e-10)
    np.testing.assert_allclose(back.gamma, fit.gamma, atol=1e-10)
    assert back.alpha == 2.345 and back.em_iters == 3
    assert back.other == {"num_topics": 4, "num_terms": 9, "alpha": 2.345}
    # the program's own writers give the same bytes
    theirs = tmp_path / "prog"
    theirs.mkdir()
    formats.write_beta(str(theirs / "final.beta"), fit.log_beta)
    formats.write_gamma(str(theirs / "final.gamma"), fit.gamma)
    formats.write_other(str(theirs / "final.other"), 4, 9, fit.alpha)
    with open(theirs / "likelihood.dat", "w") as f:
        prev = None
        for ll in fit.likelihoods:
            formats.append_likelihood(
                f, ll, 1.0 if prev is None else abs((prev - ll) / prev))
            prev = ll
    for name in ldac_files.FILES:
        assert (theirs / name).read_bytes() == open(
            os.path.join(out, name), "rb").read(), name


def _cut_gamma_short(out):
    path = os.path.join(out, "final.gamma")
    body = open(path).read()
    open(path, "w").write(body[:len(body) * 2 // 3])


def _drop_a_gamma_row(out):
    path = os.path.join(out, "final.gamma")
    lines = open(path).read().splitlines(True)
    open(path, "w").write("".join(lines[:-1]))


def _drop_a_likelihood_line(out):
    path = os.path.join(out, "likelihood.dat")
    lines = open(path).read().splitlines(True)
    open(path, "w").write("".join(lines[:1] + lines[2:]))


def _wrong_num_terms(out):
    path = os.path.join(out, "final.other")
    lines = open(path).read().splitlines()
    k, v = lines[1].split()
    lines[1] = f"{k} {int(v) + 1}"
    open(path, "w").write("\n".join(lines) + "\n")


def _a_file_missing(out):
    os.remove(os.path.join(out, "final.beta"))


def _conv_column_of_another_fit(out):
    path = os.path.join(out, "likelihood.dat")
    rows = [line.split("\t") for line in open(path).read().splitlines()]
    open(path, "w").write("".join(f"{ll}\t{7.0e-5:5.5e}\n" for ll, _ in rows))


# what breaks the files -> does a file still parse to arrays
SPOILED = {
    "gamma_cut_short": (_cut_gamma_short, False),
    "a_gamma_row_missing": (_drop_a_gamma_row, False),
    "eight_digits": (control_est.eight_digits, True),
    "wrong_num_terms": (_wrong_num_terms, True),
    "a_file_missing": (_a_file_missing, False),
}


@pytest.mark.parametrize("how", sorted(SPOILED))
def test_the_readers_name_what_breaks_the_contract(tmp_path, how):
    spoil, parses = SPOILED[how]
    out, _ = _written(tmp_path)
    spoil(out)
    fit, problems = ldac_files.read_fit(out, 12, 4, 9)
    assert problems, how
    assert (fit is not None) == parses


def test_likelihood_dats_second_column_is_held_to_its_first(tmp_path):
    fit = lda_plain.PlainFit(
        log_beta=np.zeros((2, 3)), gamma=np.ones((4, 2)), alpha=1.0,
        likelihoods=[-2e6, -1.1e6, -1.01e6, -1.001e6, -1.00095e6])
    ldac_files.write_fit(str(tmp_path), fit, 3)
    back, problems = ldac_files.read_fit(str(tmp_path), 4, 2, 3)
    assert problems == [] and back.em_iters == 5
    ll = back.ll                   # convergence 1, .45, .082, .0089, 5e-5
    assert ldac_files.conv_problems(ll, 1e-4, 100) == []
    assert ldac_files.conv_problems(ll, 1e-5, 5) == []      # the cap
    assert ldac_files.conv_problems(ll, 1e-5, 100)          # no stop yet
    assert ldac_files.conv_problems(ll, 1e-2, 100)          # a line late
    assert ldac_files.conv_problems(ll[:-1], 1e-4, 100)     # a line early
    wrong = ll.copy()
    wrong[2, 1] *= 1.001
    assert ldac_files.conv_problems(wrong, 1e-4, 100)


# -- run.run_cell on the new job -----------------------------------------

def test_the_program_through_its_cli_is_correct_from_the_files():
    line = _run()
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"em_docs_per_s", "fit_s", "setup_s"}
    assert list(line["compared"]) == list(fit_check.NUMBERS) + ["files"]
    assert line["compared"]["files"] == {"value": 0.0, "limit": 0.0}
    assert _failed(line) == []
    json.dumps(line)
    # the run removes what it wrote
    assert not os.path.exists(os.path.join(
        cells.ROOT, ".bench_files", f"est-{os.getpid()}"))


def test_the_traced_line_reports_the_load_and_the_save():
    line = _run(trace=1)
    assert line["correct"] is True
    for name in ("est_load_s", "est_save_s", "em_iters_per_fit"):
        assert line["metrics"][name]["value"] > 0, name
    assert "setup_s" not in line["metrics"]


def test_the_reference_in_the_clis_place_is_correct():
    line = _run(program=_fake())
    assert line["correct"] is True, _failed(line)


def _stop_one_iteration_early(fit):
    fit.likelihoods.pop()
    fit.em_iters -= 1


# what a broken program would leave on disk -> the numbers that must fail
FILE_FAULTS = {
    "gamma_cut_short": (dict(spoil=_cut_gamma_short), {"files"}),
    "two_gamma_rows_swapped": (dict(spoil=control_est.swap_two_gamma_rows),
                               {"gammaN_max", "rowsum"}),
    "a_likelihood_line_missing": (dict(spoil=_drop_a_likelihood_line),
                                  {"files"}),
    "eight_digits_not_ten": (dict(spoil=control_est.eight_digits), {"files"}),
    "wrong_num_terms": (dict(spoil=_wrong_num_terms), {"files"}),
    "a_file_missing": (dict(spoil=_a_file_missing), {"files"}),
    "conv_column_of_another_fit": (dict(spoil=_conv_column_of_another_fit),
                                   {"files"}),
    "stops_before_the_rule_holds": (
        dict(faults=lda_plain.Faults(alter_answer=_stop_one_iteration_early)),
        {"files", "stop_rule"}),
    "half_of_the_blocks_left_out": (
        dict(faults=lda_plain.Faults(
            stat_weight=lambda lo, hi, n: 2.0 * ((lo // (hi - lo)) % 2 == 0))),
        {"gammaN_mean"}),
    "state_left_unchanged": (
        dict(faults=lda_plain.Faults(freeze_model=True)), {"beta1_gap"}),
}


@pytest.mark.parametrize("fault", sorted(FILE_FAULTS))
def test_a_planted_fault_comes_out_not_correct(fault):
    kw, must_fail = FILE_FAULTS[fault]
    line = _run(program=_fake(**kw))
    assert line["correct"] is False
    assert must_fail <= set(_failed(line)), (fault, _failed(line))


def test_the_lower_precision_control_comes_out_not_correct():
    line = _run(program=_fake(dtype="bfloat16"))
    assert line["correct"] is False
    assert set(_failed(line)) - {"files"}


def test_files_without_a_limit_is_not_correct():
    found = _cell()
    del found["traffic"]["files_limit"]
    line = _run(program=_fake(), found=found)
    assert line["correct"] is False and _failed(line) == ["files"]


def test_a_call_that_fails_fails_the_run():
    class Refuses(type(_fake())):
        def est(self, argv):
            return {"rc": 2, "em_iters": None, "plan": {}}

    with pytest.raises(RuntimeError, match="returned 2"):
        _run(program=Refuses())


def test_what_the_cli_says_of_its_plan_is_parsed():
    said = est_files.parse_said(
        "em iterations: 13  final likelihood: -1.5  alpha: 0.9\n"
        "engine: dense  kernel: dense_rowmajor  "
        "dense budget: 11811160064 (device)\n")
    assert said == {"em_iters": 13, "plan": {
        "engine": "dense", "kernel": "dense_rowmajor",
        "dense_budget": "11811160064", "dense_budget_source": "device"}}
    # a program that says less (the parent) gives what it says
    assert est_files.parse_said("em iterations: 4  alpha: 1\n") == {
        "em_iters": 4, "plan": {}}
    assert est_files.parse_said("") == {"em_iters": None, "plan": {}}


# -- the readers of the CLI's spans --------------------------------------

def _ctx(spans):
    return {"trace": {"fits": [(10.0, 20.0), (30.0, 38.0)]},
            "program_trace": {"spans": spans}}


def test_the_readers_place_the_spans_inside_the_traced_calls():
    spans = [
        ("est.load", 2.0, 1.0, {}, "t"),       # the warm-up: no annotation
        ("est.load", 10.5, 3.0, {}, "t"),
        ("est.load.counts", 13.4, 0.0, {"docs": 7}, "t"),
        ("fit", 13.5, 6.0, {}, "t"),
        ("fit.save", 18.0, 1.5, {}, "t"),
        ("est.load", 30.25, 2.0, {}, "t"),
        ("fit", 32.5, 5.0, {}, "t"),
        ("fit.save", 36.0, 1.0, {}, "t"),
    ]
    ctx = _ctx(spans)
    assert cells.load_module("metrics", "est_load_s").read(ctx) == 2.5
    assert cells.load_module("metrics", "est_save_s").read(ctx) == 1.25
    assert est_spans.mean_seconds(ctx, "fit") is None   # not its span


def test_a_program_without_the_spans_gives_nothing():
    ctx = _ctx([("fit", 13.5, 6.0, {}, "t"), ("fit.batches", 14, 1, {}, "t")])
    assert cells.load_module("metrics", "est_load_s").read(ctx) is None
    assert cells.load_module("metrics", "est_save_s").read(ctx) is None


# -- the entries ---------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_as_the_issue_names_them():
    entry_rules.est_files_cell_configuration_and_metrics(entry_rules.load())
