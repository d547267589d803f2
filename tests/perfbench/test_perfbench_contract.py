"""BENCHMARK.json against the contract's own limits (the rules themselves are
`entry_rules.py`'s), the guard that every rule of this directory stands an
appended cell, configuration and metric, the peaks table and the rule that a
run off the chip gives no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import entry_rules
from benchmarks.harness import cells, device


@pytest.fixture(scope="module")
def bench():
    return entry_rules.load()


def test_top_level_keys_and_command(bench):
    entry_rules.top_level_keys_and_command(bench)


def test_names_units_and_lines(bench):
    entry_rules.names_units_and_lines(bench)


def test_entries_have_just_the_contract_keys(bench):
    entry_rules.entries_have_just_the_contract_keys(bench)


def test_cells_configs_and_metrics_hang_together(bench):
    entry_rules.cells_configs_and_metrics_hang_together(bench)


def test_one_layer_one_spelling(bench):
    entry_rules.one_layer_one_spelling(bench)


# -- the guard: a later PR appends, and no rule may mind -------------------

GUARD_METRIC = '''"""docs_per_fit: a metric a later PR adds as a file."""


def read(ctx):
    return None
'''
# What a later PR's metric may say of its cells: only the cell it came
# with, or (no `workloads` key) every cell that reports what it moves.
APPENDED = {"a_metric_of_the_new_cell": {"workloads": ["flow20_guard_fit"]},
            "a_metric_of_every_cell": {}}


@pytest.fixture(scope="module", params=sorted(APPENDED))
def appended(request, tmp_path_factory):
    """A checkout's BENCHMARK.json and benchmarks/ (the recorded traces
    left out) with one more configuration, one more one-chip cell and one
    more per-layer metric APPENDED, each with the file its name leads to:
    all that a `model_config`, `perf_opt` or `tracing` PR may do there."""
    root = tmp_path_factory.mktemp("appended")
    shutil.copytree(cells.BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = entry_rules.load()
    config = cells.load_json(cells.BENCH_DIR, "configs", "flow20.json")
    config.update(name="flow20_guard", source="a later PR's")
    (root / "benchmarks/configs/flow20_guard.json").write_text(
        json.dumps(config))
    shutil.copy(root / "benchmarks/traffic/resident_163840.json",
                root / "benchmarks/traffic/resident_guard.json")
    (root / "benchmarks/metrics/docs_per_fit.py").write_text(GUARD_METRIC)
    bench["configs"].append({
        "name": "flow20_guard", "source": "a later PR's", "reduced": [],
        "file": "benchmarks/configs/flow20_guard.json",
        "why": "appended by the guard"})
    bench["workloads"].append({
        "name": "flow20_guard_fit", "config": "flow20_guard",
        "traffic": "resident_guard", "chips": 1,
        "why": "appended by the guard"})
    bench["per_layer"].append({
        "name": "docs_per_fit", "unit": "docs", "better": "higher",
        "source": "program_counter", "layer": "convergence",
        "moves": "fit_s", **APPENDED[request.param]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return str(root)


@pytest.mark.parametrize("rule", entry_rules.RULES,
                         ids=lambda rule: rule.__name__)
def test_no_rule_minds_an_appended_cell_configuration_and_metric(
        appended, rule, monkeypatch):
    """Every rule the tests of this directory hold BENCHMARK.json's entries
    to, the same functions, on the appended copy: one that pins a list's
    end or its length (PR 33's `bench["workloads"][-1]`, `configs[-1]`,
    `per_layer[-2:]`, PR 29's `four == [CELL]`) fails here, in the PR that
    writes it, not in the next PR that appends."""
    monkeypatch.setattr(cells, "ROOT", appended)
    monkeypatch.setattr(cells, "BENCH_DIR",
                        os.path.join(appended, "benchmarks"))
    bench = entry_rules.load()
    assert [w["name"] for w in bench["workloads"]][-1] == "flow20_guard_fit"
    rule(bench)


def test_peaks_table_knows_the_v5e_and_nothing_by_default():
    peaks = device.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9 and "Google Cloud" in peaks["source"]
    with pytest.raises(device.NoChip, match="not in the benchmark's table"):
        device.peaks_for("TPU v9 imaginary")


def test_stamp_refuses_the_cpu():
    with pytest.raises(device.NoChip, match="not 'tpu'"):
        device.stamp(1)


def test_the_command_off_the_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "flow20_fit", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no result" in done.stderr
