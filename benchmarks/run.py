"""The benchmark's command: one process, one cell, one last line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name (harness/cells.py): the cell in BENCHMARK.json,
its configuration and traffic files, the job kind's module, and with
`--trace 1` one reader per per-layer metric.  Exits non-zero and prints no
result line when jax finds no TPU, fewer chips than the cell asks for, or a
device that is not in the table of peaks.
"""

from __future__ import annotations

import os
import time


def _since_process_start() -> float:
    """Seconds since the kernel started this process, the interpreter's own
    start-up included (0 where /proc does not say)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


# `setup_s` counts from here: process start.
T_START = time.perf_counter() - _since_process_start()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import cells, device, xplane  # noqa: E402


def log(message: str) -> None:
    print(f"bench: {message}", file=sys.stderr, flush=True)


def run_cell(args, stamp: dict, found: dict, program=None) -> dict:
    """Everything after the look for a chip.  Returns the result line.
    `found` is the resolved cell (rehearsals shrink it); tests pass a broken
    `program`.  `setup_s` counts from process start to window start; how
    much of it went before this point (the interpreter, importing jax, the
    TPU runtime handing over the chip) is logged beside it."""
    log(f"process start to chip: {time.perf_counter() - T_START:.2f}s "
        "(counted in setup_s)")
    chips = found["cell"]["chips"]
    job = cells.load_module("jobs", found["traffic"]["job"])
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    ctx = {
        "config": found["config"], "traffic": found["traffic"],
        "chips": chips, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "t_start": T_START, "log": log,
        "program": program,
        "plan_cache": os.path.join(ROOT, ".jax_cache", "plans.jsonl"),
        "annotate": lambda name: contextlib.nullcontext(),
        "tracing": contextlib.nullcontext,
        "memory_peak": lambda: device.memory_peak_bytes(chips),
    }
    if args.trace:
        ctx["annotate"] = xplane.annotate
        ctx["tracing"] = lambda: xplane.tracing(trace_dir)
    out = job.run(ctx)

    measured = dict(out["end_to_end"], setup_s=out["setup_s"])
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {},
            "device": dict(stamp, memory_peak_bytes=out["memory_peak_bytes"])}
    if not args.trace:
        for m in found["end_to_end"]:
            line["metrics"][m["name"]] = {
                "value": measured[m["name"]], "unit": m["unit"]}
    else:
        trace = xplane.reduce(trace_dir, chips)
        line["device"].update(busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
        reader_ctx = dict(out["observed"], trace=trace, chips=chips,
                          trace_dir=trace_dir,
                          peaks=device.peaks_for(stamp["kind"])
                          if stamp["platform"] == "tpu" else None,
                          end_to_end=measured)
        for m in found["per_layer"]:
            value = cells.load_module("metrics", m["name"]).read(reader_ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        line["breakdown"] = xplane.breakdown(trace, out.get("phases"))
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in out["compared"]}
    for name, value, limit in out["compared"]:
        log(f"compared {name} {value:.6g} limit {limit}")
    log(f"correct {line['correct']}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # jax's persistent compilation cache: a fixed directory inside the
    # checkout, unless the caller set JAX_COMPILATION_CACHE_DIR.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        found = cells.resolve(args.workload)
        stamp = device.stamp(found["cell"]["chips"])
    except device.NoChip as e:
        log(f"no result: {e}")
        return 3
    line = run_cell(args, stamp, found)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
