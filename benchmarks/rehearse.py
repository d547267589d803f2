"""The rehearsals that cost no chip time, for every cell in BENCHMARK.json
(on-chip-measurement guide, section 2):

    python benchmarks/rehearse.py tiny     [--workload X]   the command end to end on the CPU at a tiny size
    python benchmarks/rehearse.py virtual4 [--workload X]   the same on four virtual CPU devices, for cells that ask for 4 chips
    python benchmarks/rehearse.py compile  [--workload X]   the EM chunk program at the REAL size, compiled for a described v5e:2x2, memory_analysis() printed

Nothing printed here is a device number.  A later PR that adds a cell adds
its entry to BENCHMARK.json and runs these before it spends chip time.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {"num_docs": 768, "num_terms": 512, "batch_size": 128,
        "reference_block_docs": 256}


def shrink(found: dict) -> dict:
    """The cell at a size the CPU holds: fewer documents, a narrow
    vocabulary, small batches; every other setting as the files have it."""
    found = copy.deepcopy(found)
    found["config"]["num_terms"] = TINY["num_terms"]
    found["config"].get("program", {}).pop("estep_engine", None)
    traffic = found["traffic"]
    traffic["num_docs"] = TINY["num_docs"]
    per_device = TINY["batch_size"]
    if traffic.get("mesh"):
        per_device *= traffic["mesh"][0]
    traffic["batch_size"] = per_device
    traffic["reference_block_docs"] = TINY["reference_block_docs"]
    law = traffic.get("corpus", {}).get("length", {})
    if "quantiles" in law:   # no document wider than half the vocabulary
        law["quantiles"] = [[p, min(n, TINY["num_terms"] // 2)]
                            for p, n in law["quantiles"]]
    traffic["trace_fits"] = 1
    return found


def cells_of(args) -> list:
    """The names of the cells to rehearse."""
    if args.workload:
        return [args.workload]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def run_tiny(workload: str, trace: int, seed: int, virtual: bool) -> dict:
    from benchmarks import run as bench_run
    from benchmarks.harness import cells

    found = cells.resolve(workload)
    chips = found["cell"]["chips"]
    if virtual != (chips > 1):
        return {}
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=trace)
    stamp = {"platform": "cpu", "kind": "rehearsal", "count": chips}
    return bench_run.run_cell(args, stamp, shrink(found))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("tiny", "virtual4", "compile"))
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--trace", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(ROOT, ".jax_cache", "rehearse-cpu"))
    os.environ.setdefault(
        "ONI_ML_TPU_PLAN_CACHE",
        os.path.join(ROOT, ".jax_cache", "rehearse-cpu", "plans.jsonl"))
    if args.mode == "virtual4":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    if args.mode == "compile":
        from benchmarks.harness import rehearse_compile

        for name in cells_of(args):
            rehearse_compile.compile_cell(name)
        return 0
    bad = 0
    for name in cells_of(args):
        line = run_tiny(name, args.trace, args.seed, args.mode == "virtual4")
        if line:
            print(json.dumps(line))
            bad += not line["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
