"""What BENCHMARK.json's entries are held to, as functions of the parsed
file.  The accepted tests call them on the tree's benchmark; the guard in
test_perfbench_contract.py calls the same functions on a copy with one more
configuration, one more one-chip cell and one more per-layer metric
APPENDED, which is all a later PR may do to that file.  So a rule finds an
entry by its NAME and says what it holds and which named entries it follows;
none asks where a list ends or how long it is.

The files an entry leads to are looked for under `cells.ROOT` /
`cells.BENCH_DIR` as they stand when the rule runs (the guard points both at
its copy).
"""

import os
import re

from benchmarks.harness import cells, fit_check

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")

FIT, DP4, EST = "flow20_fit", "flow20_fit_dp4", "flow20_est_files"

# name -> (unit, source, layer, moves): PR 27's table.
SPAN_METRICS = {
    "place_batches_s": ("s", "program_span", "corpus placement", "fit_s"),
    "place_plan_s": ("s", "program_span", "corpus placement", "fit_s"),
    "place_transfer_s": ("s", "program_span", "corpus placement", "fit_s"),
    "place_densify_s": ("s", "program_span", "corpus placement", "fit_s"),
    "place_first_dispatch_s": ("s", "program_span", "EM driver", "fit_s"),
    "place_unattributed_s": ("s", "program_span", "corpus placement",
                             "fit_s"),
    "fit_compile_requests": ("count", "program_counter", "EM driver",
                             "fit_s"),
    "estep_sweeps_per_doc_iter": ("sweeps", "program_counter",
                                  "E-step kernels", "em_docs_per_s"),
    "estep_glue_pct": ("%", "device_trace", "whole EM step", "em_docs_per_s"),
}
# name -> layer, in the entries' order: PR 38's readers (entries: PR 40).
TAIL_METRICS = {
    "readback_sync_s": "EM driver",
    "readback_d2h_s": "EM driver",
    "readback_scatter_s": "EM driver",
    "readback_teardown_s": "EM driver",
    "readback_unattributed_s": "EM driver",
    "place_stack_copy_s": "corpus placement",
    "place_stack_put_s": "corpus placement",
}


def load() -> dict:
    return cells.load_json(cells.ROOT, "BENCHMARK.json")


def by_name(bench: dict, group: str) -> dict:
    return {entry["name"]: entry for entry in bench[group]}


def in_order(bench: dict, group: str, names) -> bool:
    """The entries `names` are all in `group` and follow one another in that
    order, whatever stands before, between or after them."""
    names = list(names)
    return [entry["name"] for entry in bench[group]
            if entry["name"] in names] == names


# -- the contract's own limits ---------------------------------------------

def top_level_keys_and_command(bench):
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


def names_units_and_lines(bench):
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


def entries_have_just_the_contract_keys(bench):
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


def cells_configs_and_metrics_hang_together(bench):
    configs = by_name(bench, "configs")
    cell_names = set(by_name(bench, "workloads"))
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
        body = cells.load_json(cells.ROOT, c["file"])
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|width|topics|terms)",
                                 key), "a width may never be reduced"
    e2e = set(by_name(bench, "end_to_end"))
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
        assert set(found["traffic"]["limits"]) == set(fit_check.NUMBERS), (
            w["name"])


def one_layer_one_spelling(bench):
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)


# -- the four-chip cell (PR 29) --------------------------------------------

def dp4_is_a_four_chip_cell_inside_the_allowance(bench):
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert DP4 in four
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    cell = cells.resolve(DP4)["cell"]
    assert cell == by_name(bench, "workloads")[DP4]
    assert (cell["config"], cell["traffic"]) == ("flow20_dp4",
                                                 "resident_655360_dp4")
    assert "one host process feeds 4 chips" in cell["why"]


def dp4s_configuration_is_flow20_at_four_shards(bench):
    entry = by_name(bench, "configs")["flow20_dp4"]
    assert entry["reduced"] == ["ranks"] and len(entry["source"]) <= 200
    assert "mpiexec -n 20" in entry["source"]
    new = cells.load_json(cells.ROOT, entry["file"])
    old = cells.load_json(cells.BENCH_DIR, "configs", "flow20.json")
    assert new["source"] == entry["source"]
    for key in ("num_terms", "lda", "precision"):   # letter for letter
        assert new[key] == old[key], key
    assert new["program"] == {"estep_engine": "auto",
                              "dense_hbm_budget": 12 * 2**30}
    assert new["ranks"] == 4 and new["reduced"] == ["ranks"]
    assert new["cut"]["ranks"].startswith("20 -> 4")
    assert new["deployment"]["mesh"] == {"data": 4, "model": 1}
    for word in ("EVERY EM iteration", "synchronously", "float32"):
        assert word in new["deployment"]["exchange"]


def dp4s_two_metrics_are_the_cells_alone(bench):
    found = cells.resolve(DP4)
    names = {m["name"] for m in found["per_layer"]}
    old = {m["name"] for m in cells.resolve(FIT)["per_layer"]}
    assert names - old == {"collective_exposed_pct", "shard_busy_skew_pct"}
    # Every accepted metric applies through `moves`, its entry untouched:
    # the program calls its kernels under a mesh what it calls them on one
    # device, so `estep_roofline` and `estep_glue_pct` read them here too.
    assert old - names == set()
    assert not [m for m in bench["per_layer"]
                if m["name"] in old and "workloads" in m]
    assert {m["name"] for m in found["end_to_end"]} == {
        "em_docs_per_s", "fit_s", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in names - old:
            assert (m["unit"], m["better"], m["source"], m["layer"],
                    m["moves"], m["workloads"]) == (
                "%", "lower", "device_trace", "exchange", "em_docs_per_s",
                [DP4])


# -- the drop-in CLI's cell (PR 33) ----------------------------------------

def est_files_cell_configuration_and_metrics(bench):
    found = cells.resolve(EST)
    assert found["cell"] == by_name(bench, "workloads")[EST]
    assert (found["cell"]["config"], found["cell"]["traffic"],
            found["cell"]["chips"]) == ("flow20_est", "est_files_163840", 1)
    config, traffic = found["config"], found["traffic"]
    entry = by_name(bench, "configs")["flow20_est"]
    assert entry["reduced"] == config["reduced"] == ["ranks"]
    assert entry["source"] == config["source"]
    assert config["program"] == {} and config["architecture"] is None
    flow20 = cells.resolve(FIT)
    assert config["num_terms"] == flow20["config"]["num_terms"]
    assert dict(flow20["config"]["lda"], warm_start=False,
                alpha_max_iters=100, seed=0) == config["lda"]
    assert set(config["guarantees"]) >= {"complete", "shape", "format"}
    # flow20_fit's day, letter for letter
    assert traffic["corpus"] == flow20["traffic"]["corpus"]
    assert traffic["num_docs"] == flow20["traffic"]["num_docs"]
    assert (traffic["job"], traffic["mesh"], traffic["trace_fits"],
            traffic["files_limit"]) == ("est_files", None, 1, 0.0)
    assert "files" not in traffic["limits"]
    metrics = by_name(bench, "per_layer")
    for name, layer in (("est_load_s", "corpus ingest"),
                        ("est_save_s", "model files")):
        assert metrics[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": layer, "moves": "fit_s",
            "workloads": [EST]}
    assert in_order(bench, "per_layer", [
        "shard_busy_skew_pct", "est_load_s", "est_save_s"])
    reported = {m["name"] for m in found["per_layer"]}
    assert {"est_load_s", "est_save_s", "fit_place_s",
            "estep_sweeps_per_doc_iter", "estep_roofline"} <= reported
    assert not {"collective_exposed_pct", "shard_busy_skew_pct"} & reported


# -- the readers of the program's spans (PRs 27 and 38) --------------------

def span_metric_entry(bench, name):
    unit, source, layer, moves = SPAN_METRICS[name]
    assert by_name(bench, "per_layer")[name] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves}
    assert os.path.exists(os.path.join(cells.BENCH_DIR, "metrics",
                                       name + ".py"))
    # the six the benchmark had stay first, as they were
    assert [m["name"] for m in bench["per_layer"]][:6] == [
        "em_mfu", "estep_roofline", "device_idle_pct", "fit_readback_s",
        "fit_place_s", "em_iters_per_fit"]
    assert name in {m["name"] for m in cells.resolve(FIT)["per_layer"]}


def span_metric_entries(bench):
    for name in SPAN_METRICS:
        span_metric_entry(bench, name)


def tail_metric_entry(bench, name):
    assert by_name(bench, "per_layer")[name] == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_span", "layer": TAIL_METRICS[name],
        "moves": "fit_s"}
    assert os.path.exists(os.path.join(cells.BENCH_DIR, "metrics",
                                       name + ".py"))
    # no `workloads`: every cell that reports `fit_s` reports it
    for cell in (FIT, DP4, EST):
        assert name in {m["name"] for m in cells.resolve(cell)["per_layer"]}


def tail_metric_entries(bench):
    for name in TAIL_METRICS:
        tail_metric_entry(bench, name)
    # appended in the issue's order, after what the benchmark had
    assert in_order(bench, "per_layer", ["est_save_s", *TAIL_METRICS])


# Every rule above, for the guard.
RULES = (
    top_level_keys_and_command,
    names_units_and_lines,
    entries_have_just_the_contract_keys,
    cells_configs_and_metrics_hang_together,
    one_layer_one_spelling,
    dp4_is_a_four_chip_cell_inside_the_allowance,
    dp4s_configuration_is_flow20_at_four_shards,
    dp4s_two_metrics_are_the_cells_alone,
    est_files_cell_configuration_and_metrics,
    span_metric_entries,
    tail_metric_entries,
)
