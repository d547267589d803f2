"""The rest of a run, driven past the harness's look for a chip, at a tiny
size on the CPU: the last line's keys, `correct` true for the program and
false for every planted fault and for the lower-precision control, and the
proof that a cell, a configuration and a metric are added as files."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import control, rehearse, run as bench_run
from benchmarks.harness import cells
from benchmarks.jobs import fit as fit_job
from benchmarks.reference import lda_plain

STAMP = {"platform": "cpu", "kind": "rehearsal", "count": 1}


def _cell(name="flow20_fit"):
    return rehearse.shrink(cells.resolve(name))


def _run(program=None, trace=0, seed=2**31 + 29, found=None):
    args = argparse.Namespace(workload="flow20_fit", seed=seed, seconds=0.0,
                              trace=trace)
    return bench_run.run_cell(args, STAMP, found or _cell(), program=program)


def test_program_is_correct_and_the_last_line_has_the_contract_keys():
    line = _run()
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"em_docs_per_s", "fit_s", "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for row in line["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(line)


def test_traced_line_reports_per_layer_metrics_and_a_breakdown():
    line = _run(trace=1)
    assert line["correct"] is True
    assert "em_iters_per_fit" in line["metrics"]
    assert "setup_s" not in line["metrics"]
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10
    # off the chip no reader gives a device number
    assert not {"em_mfu", "estep_roofline", "device_idle_pct"} & set(
        line["metrics"])


def test_the_reference_in_the_programs_place_is_correct():
    assert _run(program=fit_job.fake_program())["correct"] is True


def _stop_one_iteration_early(fit):
    fit.likelihoods.pop()
    fit.em_iters -= 1


# state_unchanged, half_batch, no_exchange, answer_altered: the faults the
# control script reads on the chip, and one more alteration of an answer.
FAULTS = dict(control.faults(), stops_before_the_rule_holds=lda_plain.Faults(
    alter_answer=_stop_one_iteration_early))


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_not_correct(fault):
    line = _run(program=fit_job.fake_program(FAULTS[fault]))
    assert line["correct"] is False
    failed = [n for n, row in line["compared"].items()
              if not row["value"] <= row["limit"]]
    assert failed, fault


def test_the_lower_precision_control_comes_out_not_correct():
    line = _run(program=fit_job.fake_program(dtype="bfloat16"))
    assert line["correct"] is False


def test_a_number_without_a_limit_is_not_correct():
    found = _cell()
    del found["traffic"]["limits"]["ll_rel"]
    assert _run(program=fit_job.fake_program(), found=found)[
        "correct"] is False


def test_fresh_compiles_inside_the_window_fail_the_run():
    class Compiles(type(fit_job.fake_program())):
        n = 0

        def compile_counts(self):
            Compiles.n += 1
            return {"traces": Compiles.n}

    with pytest.raises(RuntimeError, match="fresh compiles inside"):
        _run(program=Compiles())


NEW_METRIC = '''"""docs_per_fit: a metric a later PR adds as a file."""


def read(ctx):
    return float(ctx["num_docs"])
'''


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = json.loads((root / "benchmarks/configs/flow20.json").read_text())
    config.update(name="flow30", num_terms=384)
    config["lda"]["num_topics"] = 30
    (root / "benchmarks/configs/flow30.json").write_text(json.dumps(config))
    traffic = json.loads(
        (root / "benchmarks/traffic/resident_163840.json").read_text())
    traffic.update(num_docs=640)
    traffic["corpus"]["planted_topics"] = 30
    (root / "benchmarks/traffic/resident_640.json").write_text(
        json.dumps(traffic))
    (root / "benchmarks/metrics/docs_per_fit.py").write_text(NEW_METRIC)
    bench["configs"].append({
        "name": "flow30", "source": "a later PR's", "reduced": [],
        "file": "benchmarks/configs/flow30.json", "why": "added as a file"})
    bench["workloads"].append({
        "name": "flow30_fit", "config": "flow30", "traffic": "resident_640",
        "chips": 1, "why": "added as files"})
    bench["per_layer"].append({
        "name": "docs_per_fit", "unit": "docs", "better": "higher",
        "source": "program_counter", "layer": "convergence",
        "moves": "fit_s", "workloads": ["flow30_fit"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, PYTHONPATH=cells.ROOT)
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks/rehearse.py"), "tiny",
         "--workload", "flow30_fit", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=600, cwd=root)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["docs_per_fit"]["value"] == rehearse.TINY[
        "num_docs"]
    assert "em_iters_per_fit" in line["metrics"]
    # no file that was there has been edited
    for path, body in before.items():
        assert path.read_bytes() == body, path
