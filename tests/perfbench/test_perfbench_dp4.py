"""The four-chip cell `flow20_fit_dp4` (PR 29): its files against the
issue's sizes, its rehearsal on four virtual devices, `correct` false where
a shard's statistics are left out of the sum, and the two readers of the
exchange on hand-made devices and on a recorded four-chip trace."""

import argparse
import gzip
import json
import os
import subprocess
import sys

import pytest

import entry_rules
from benchmarks import control, rehearse, run as bench_run
from benchmarks.harness import cells, device, fit_check, xplane
from benchmarks.jobs import fit as fit_job
from benchmarks.jobs import fit_exchange, fit_trace
from benchmarks.metrics import (collective_exposed_pct, estep_glue_pct,
                                shard_busy_skew_pct)

CELL = "flow20_fit_dp4"
DATA = os.path.join(cells.BENCH_DIR, "data", "flow20_fit_dp4_trace.json.gz")
STAMP = {"platform": "cpu", "kind": "rehearsal", "count": 4}


@pytest.fixture(scope="module")
def bench():
    return entry_rules.load()


# -- the files -------------------------------------------------------------

def test_the_cell_is_a_four_chip_cell_inside_the_allowance(bench):
    entry_rules.dp4_is_a_four_chip_cell_inside_the_allowance(bench)


def test_the_configuration_is_flow20_at_four_shards(bench):
    entry_rules.dp4s_configuration_is_flow20_at_four_shards(bench)


def test_the_traffic_is_the_accepted_days_law_at_four_times_its_documents():
    new = cells.load_json(cells.BENCH_DIR, "traffic",
                          "resident_655360_dp4.json")
    old = cells.load_json(cells.BENCH_DIR, "traffic", "resident_163840.json")
    assert new["corpus"] == old["corpus"]
    assert new["num_docs"] == 4 * old["num_docs"] == 655360
    assert new["mesh"] == [4, 1]
    assert new["batch_size"] // new["mesh"][0] == old["batch_size"] == 4096
    assert (new["job"], new["trace_fits"], new["check_steps"],
            new["reference_block_docs"]) == ("fit", 1, 17, 2048)
    assert set(new["limits"]) == set(fit_check.NUMBERS)


def test_both_new_metrics_are_the_cells_alone(bench):
    entry_rules.dp4s_two_metrics_are_the_cells_alone(bench)


def test_allreduce_bytes_by_hand():
    # [8192, 20] float32 statistics, two float32 and two int32 scalars
    assert fit_exchange.allreduce_bytes_per_batch(8192, 20) == 655376
    # the cell's day makes 42 batches (31 + 5 + 3 full shapes, 3 tails)
    assert fit_exchange.allreduce_bytes_per_iter(42, 8192, 20) == 27525792
    assert fit_exchange.allreduce_bytes_per_iter(3, 512, 20, 2) == 3 * (
        512 * 20 * 2 + 4 + 8)


# -- the third rehearsal: the placement at the real size -------------------

@pytest.fixture
def topo():
    """A described v5e 2x2, by the worker that is given this file; an
    executable compiled for it is written to the persistent cache and cannot
    be read back without a chip, so the cache is off around the test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from benchmarks.harness import rehearse_compile

    try:
        described = rehearse_compile.describe_topology()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_rehearsed_densify_of_the_cell_fits_a_chip(topo):
    """`rehearse.py compile --workload flow20_fit_dp4` densifies through the
    program's own `fused.densify_stack(..., mesh=)`: every device scatters
    its own rows.  With a jit of the rehearsal's own it reproduced the
    failure PR 29 repaired in the program (19.38 GB of 15.75 GB)."""
    from benchmarks.harness import rehearse_compile

    layout = rehearse_compile.cell_layout(CELL, topo)
    assert layout.data == 4 and layout.mesh.shape == {"data": 4, "model": 1}
    assert layout.by_shape[(16384, 32)] == 31 and not layout.wmajor
    peak = rehearse_compile.densify_peak_bytes(layout)
    # the largest group's dense rows of one device, their scatter's scratch
    # (one more copy of them) and the sparse stack's shard
    own = 31 * 4096 * 8192 * 4
    assert 2 * own <= peak < 2.05 * own
    assert peak < device.peaks_for("TPU v5 lite")["hbm_bytes"]


# -- the rehearsal and `correct` -------------------------------------------

def _cell():
    return rehearse.shrink(cells.resolve(CELL))


def _run(program=None, trace=0, seed=2**31 + 31):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.0,
                              trace=trace)
    return bench_run.run_cell(args, STAMP, _cell(), program=program)


def test_rehearsal_on_four_virtual_devices_is_correct():
    """`rehearse.py virtual4`, the command: `correct` true under the cell's
    limits, every accepted span metric's reader and both new readers called
    without raising; off the chip neither gives a device number."""
    env = dict(os.environ, PYTHONPATH=cells.ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "rehearse.py"),
         "virtual4", "--workload", CELL, "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=cells.ROOT)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert "'data_shards': 4" in done.stderr       # the program said so
    assert "em_iters_per_fit" in line["metrics"]
    assert not {"collective_exposed_pct", "shard_busy_skew_pct",
                "device_idle_pct"} & set(line["metrics"])
    for row in line["compared"].values():
        assert row["value"] <= row["limit"]


def test_the_program_counts_the_exchange_as_the_benchmark_does():
    """A fit of the shrunk cell on a `data=4` mesh under a Recorder: the
    root span's `allreduce_bytes` is fit_exchange's count for its batches,
    its shards hold all the documents between them."""
    from benchmarks.harness import corpus_gen
    from oni_ml_tpu.telemetry import spans

    found = _cell()
    config, traffic = found["config"], found["traffic"]
    program = fit_job.Program()
    csr = corpus_gen.make_corpus(traffic, config["num_terms"], 2**31 + 37)
    inputs = program.make_input(csr, traffic["mesh"])
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        program.fit(inputs, dict(config["lda"], seed=5, em_max_iters=2),
                    config["program"], traffic["batch_size"])
    by_name = {e["name"]: e["args"] for e in rec.events}
    root = by_name["fit"]
    assert root["data_shards"] == 4
    assert root["allreduce_bytes"] == fit_exchange.allreduce_bytes_per_iter(
        by_name["fit.batches"]["batches"], config["num_terms"],
        config["lda"]["num_topics"])
    assert (4 * root["rows_per_shard_min"] <= csr.num_docs
            <= 4 * root["rows_per_shard_max"])


def test_the_reference_in_the_programs_place_is_correct():
    assert _run(program=fit_job.fake_program())["correct"] is True


# A shard's statistics left out of the sum is the fault this cell exists to
# catch; the others are the accepted cell's, under this cell's limits.
FAULTS = {name: control.faults()[name]
          for name in ("no_exchange", "half_batch", "state_unchanged")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_not_correct(fault):
    line = _run(program=fit_job.fake_program(FAULTS[fault]))
    assert line["correct"] is False
    assert [n for n, row in line["compared"].items()
            if not row["value"] <= row["limit"]], fault


def test_the_lower_precision_control_comes_out_not_correct():
    assert _run(program=fit_job.fake_program(dtype="bfloat16"))[
        "correct"] is False


# -- the two readers -------------------------------------------------------

def _device(kernel_s, wait_s, overlapped=False):
    """One EM program of 10 s: a loop holding, a batch, a kernel, a
    synchronous all-reduce that waits `wait_s` for the other shards, an
    asynchronous pair around a second kernel (its start 0.1 s, its done
    0.25 s), and, with `overlapped`, an all-gather that lies wholly under
    the first kernel."""
    ops = [
        ("%while.1 = (f32[20,8192]) while(...)", 0.0, 10.0),
        ("%tpu_custom_call.1 = f32[4096,20] custom-call(...)", 0.0,
         kernel_s),
        ("%all-reduce.1 = f32[8192,20] all-reduce(...)", kernel_s, wait_s),
        ("%add_fusion = f32[8192,20] fusion(...)", 6.0, 0.5),
        ("%all-reduce-start.2 = f32[] all-reduce-start(...)", 6.5, 0.1),
        ("%tpu_custom_call.2 = f32[4096,20] custom-call(...)", 6.6, 2.0),
        ("%all-reduce-done.2 = f32[] all-reduce-done(...)", 8.6, 0.25),
    ]
    if overlapped:
        ops.append(("%all-gather.3 = f32[8] all-gather(...)", 1.0, 1.0))
    return ops


def _trace(devices):
    return dict(xplane.reduce_events({
        "devices": dict(enumerate(devices)),
        "modules": {d: [("jit_run_chunk_dispatch(1)", 0.0, 10.0)]
                    for d in range(len(devices))},
        "annotations": [("fit", -1.0, 12.0)],
    }), rehearsal=False)


def test_exposed_collective_time_leaves_out_what_a_kernel_overlaps():
    exposed = collective_exposed_pct.exposed_seconds
    assert exposed(_device(4.0, 1.0)) == pytest.approx(1.0 + 0.1 + 0.25)
    assert exposed(_device(4.0, 1.0, overlapped=True)) == pytest.approx(1.35)
    assert exposed([op for op in _device(4.0, 1.0)
                    if not fit_exchange.is_collective(op[0])]) == 0.0
    # the loop around everything is no operation of its own
    assert fit_exchange.CONTAINER.match("%while.1 = (f32[20,8192]) while(")
    assert not fit_exchange.CONTAINER.match("%while_add_fusion = f32[] fus")
    assert fit_exchange.is_collective("all-reduce.7 f32[8192,20] all-reduce")
    assert not fit_exchange.is_collective("%fusion.3 = fusion(%all-reduce.1)")


def test_the_readers_on_equal_and_on_skewed_devices():
    equal = _trace([_device(4.0, 1.0)] * 4)
    assert shard_busy_skew_pct.read({"trace": equal}) == pytest.approx(0.0)
    assert collective_exposed_pct.read({"trace": equal}) == pytest.approx(
        100 * 1.35 / 10)
    # one shard sweeps 5 s where the others sweep 3 s and wait 2 s for it
    skewed = _trace([_device(5.0, 0.0)] + [_device(3.0, 2.0)] * 3)
    own_most, own_least = 5.0 + 0.5 + 2.0, 3.0 + 0.5 + 2.0
    assert shard_busy_skew_pct.read({"trace": skewed}) == pytest.approx(
        100 * (own_most - own_least) / own_most)
    # every device is busy all but the same 0.4 s: the harness's busy time
    # cannot tell them apart, and the busiest is the first of them
    assert collective_exposed_pct.read({"trace": skewed}) == pytest.approx(
        100 * 0.35 / 10)


def test_one_device_or_a_rehearsal_gives_nothing():
    one = _trace([_device(4.0, 1.0)])
    four = dict(_trace([_device(4.0, 1.0)] * 4), rehearsal=True)
    for reader in (collective_exposed_pct, shard_busy_skew_pct):
        assert reader.read({"trace": one}) is None
        assert reader.read({"trace": four}) is None


def test_recorded_four_chip_trace_reduces_to_its_recorded_numbers():
    with gzip.open(DATA, "rt") as f:
        recorded = json.load(f)
    names = recorded["names"]
    events = {
        "devices": {int(d): [(names[i], s * 1e-9, n * 1e-9)
                             for i, s, n in ops]
                    for d, ops in recorded["devices"].items()},
        "modules": {int(d): [(names[i], s * 1e-9, n * 1e-9)
                             for i, s, n in ops]
                    for d, ops in recorded["modules"].items()},
        "annotations": [(n, s * 1e-9, d * 1e-9)
                        for n, s, d in recorded["annotations"]],
    }
    assert sorted(events["devices"]) == [0, 1, 2, 3]
    trace = dict(xplane.reduce_events(events), rehearsal=False)
    want = recorded["expected"]
    assert trace["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert trace["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    ctx = {"trace": trace, "chips": 4, "doc_iters": want["doc_iters"],
           "num_terms": 8192, "num_topics": 20,
           "peaks": device.peaks_for("TPU v5 lite")}
    for name in ("collective_exposed_pct", "shard_busy_skew_pct",
                 "device_idle_pct", "estep_roofline"):
        got = cells.load_module("metrics", name).read(ctx)
        assert got == pytest.approx(want["metrics"][name], rel=1e-9), name
        assert 0 <= got <= 100
    # The accepted readers find the sharded kernels by the name the
    # one-device fit's have: 42 calls an EM iteration on every device (39
    # in the three scans, 3 tails), a sixth of their roofline, and the glue
    # share is the copies around them, not the kernels themselves.
    dev = xplane.fullest_device(trace)
    ops = trace["devices"][dev]["ops"]
    kernels = [op for op in ops if fit_trace.ESTEP_KERNEL.search(op[0])]
    assert len(kernels) == 42 * 13
    assert not [n for n, _, _ in ops if n.startswith("shard_map")]
    assert 10 < want["metrics"]["estep_roofline"] < 25
    inside = [op for lo, hi in fit_trace.em_programs(trace, dev)
              for op in xplane.clip(ops, lo, hi)]
    glue = 100 * estep_glue_pct.glue_seconds(inside) / trace["devices"][
        dev]["busy_s"]
    assert glue == pytest.approx(want["metrics"]["estep_glue_pct"], rel=1e-9)
    assert 15 < glue < 40
    # the trace holds all-reduces in its EM programs, and no all-gather
    ops = [n for d in events["devices"].values() for n, _, _ in d]
    assert any(n.startswith("all-reduce") for n in ops)
    assert not any(n.startswith("all-gather") for n in ops)
