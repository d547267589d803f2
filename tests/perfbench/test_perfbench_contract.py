"""BENCHMARK.json against the contract's own limits, the peaks table and
the rule that a run off the chip gives no result."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness import cells, device

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 x cells runs, run_seconds + 60 each, 180 s a cell to compile,
    # 1200 s spare, for the full 24 cells, inside 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.exists(os.path.join(cells.ROOT, bench["command"][1]))
    assert os.path.getsize(
        os.path.join(cells.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in bench[group]]
        assert all(NAME.match(n) for n in names), names
        assert len(names) == len(set(names)), names
        for entry in bench[group]:
            for key in ("why", "layer"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200, (entry["name"], key)
                assert "\n" not in text and "\t" not in text
    for config in bench["configs"]:
        assert 1 <= len(config["source"]) <= 200
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_entries_have_just_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_configs_and_metrics_hang_together(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cell_names = {w["name"] for w in bench["workloads"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "traffic", w["traffic"] + ".json"))
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|width|topics|terms)",
                                 key), "a width may never be reduced"
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m.get("workloads", [])) <= cell_names
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        found = cells.resolve(w["name"])
        assert {"setup_s"} < {m["name"] for m in found["end_to_end"]}
        assert found["per_layer"], w["name"]
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "jobs", found["traffic"]["job"] + ".py"))
        limits = found["traffic"]["limits"]
        from benchmarks.harness import fit_check

        assert set(limits) == set(fit_check.NUMBERS), w["name"]


def test_one_layer_one_spelling(bench):
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)


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
