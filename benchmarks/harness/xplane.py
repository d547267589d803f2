"""From the profiler's `.xplane.pb` to what the per-layer readers read.

Two stages, so that the second can be tested on a small recorded trace
(`benchmarks/data/`):

  load(trace_dir, chips)  the trace file -> plain lists: per device the
      operations (name, start, duration in seconds), and the host's
      annotations (`annotate`), all on the trace's own clock.
  reduce_events(events)   -> the window, each device's busy union, the
      operations' self times, the idle gaps and what the host was in.

Device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event
per executed HLO operation (a `while` spans its body's operations, so busy
time is a union and an operation's own time leaves its children out).  The
program gives its kernels no names and has no `named_scope` yet, so readers
match what the trace calls things; the patterns sit in the readers' files.

Off the chip (rehearsals and tests only) there is no device plane: the
CPU client's executor threads stand in as one pseudo device, so that the
whole path runs; nothing read from it is a device number.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

ANNOTATION = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION + name)


@contextlib.contextmanager
def tracing(trace_dir: str):
    """jax's profiler around the block; the directory holds one trace."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _events(line) -> list:
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def load(trace_dir: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, modules, annotations, host_ops = {}, {}, [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = _events(line)
                annotations += [(n[len(ANNOTATION):], s, d)
                                for n, s, d in events
                                if n.startswith(ANNOTATION)]
                if "XLAPjRtCpuClient" in line.name or "XLAEigen" in line.name:
                    host_ops += [e for e in events if e[2] > 0
                                 and not e[0].startswith("ThreadpoolListener")]
    rehearsal = not devices
    if rehearsal:
        devices = {0: host_ops}
    return {"devices": {k: sorted(v, key=lambda e: e[1])
                        for k, v in devices.items()},
            "modules": {k: sorted(v, key=lambda e: e[1])
                        for k, v in modules.items()},
            "annotations": sorted(annotations, key=lambda e: e[1]),
            "rehearsal": rehearsal}


def union(intervals: list) -> list:
    """Sorted (start, end) pairs -> the disjoint intervals they cover."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def overlap(a: list, b: list) -> float:
    """Seconds covered by both of two lists of disjoint sorted intervals."""
    total, j = 0.0, 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        i = j
        while i < len(b) and b[i][0] < end:
            total += min(end, b[i][1]) - max(start, b[i][0])
            i += 1
    return total


def self_times(ops: list) -> dict:
    """name -> seconds of its own (its span less the spans inside it)."""
    out: dict = {}
    stack: list = []          # [name, end, child_seconds]
    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, inside, dur = stack.pop()
            out[name] = out.get(name, 0.0) + max(dur - inside, 0.0)
            if stack:
                stack[-1][2] += dur
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, 0.0, dur])
    close(float("inf"))
    return out


def clip(ops: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in ops if s < hi and s + d > lo]


def reduce_events(events: dict) -> dict:
    """The traced window is from the first annotated job's start to the
    last one's end.  Per device: busy union inside it."""
    fits = [a for a in events["annotations"] if a[0] == "fit"]
    if not fits:
        raise ValueError("the trace holds no annotated job")
    lo, hi = fits[0][1], max(s + d for _, s, d in fits)
    per_device = {}
    for dev, ops in events["devices"].items():
        ops = clip(ops, lo, hi)
        busy = union([(s, s + d) for _, s, d in ops])
        per_device[dev] = {
            "ops": ops, "busy": busy,
            "busy_s": sum(e - s for s, e in busy),
        }
    if not per_device or not any(d["busy_s"] for d in per_device.values()):
        raise ValueError("no operation ran on the device in the window")
    busy = [d["busy_s"] for d in per_device.values()]
    return {
        "window": (lo, hi), "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "fits": [(s, s + d) for _, s, d in fits],
        "devices": per_device,
        "modules": {dev: clip(m, lo, hi)
                    for dev, m in events.get("modules", {}).items()},
        "rehearsal": events.get("rehearsal", False),
    }


def reduce(trace_dir: str, chips: int) -> dict:
    return reduce_events(load(trace_dir, chips))


def idle_gaps(trace: dict, dev: int) -> list:
    """(start, end) of every stretch of the window in which device `dev`
    ran nothing."""
    lo, hi = trace["window"]
    gaps, at = [], lo
    for start, end in trace["devices"][dev]["busy"]:
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def fullest_device(trace: dict) -> int:
    return max(trace["devices"], key=lambda d: trace["devices"][d]["busy_s"])


def short(name: str) -> str:
    """`%fusion.3 = f32[8,128]{...} fusion(...), kind=kLoop` -> `fusion.3
    f32[8,128] fusion`: the HLO name, its first result and its opcode."""
    m = re.match(r"%?([^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])?[^ ]* ?.*? "
                 r"([a-z\-]+)\(", name)
    if not m:
        return name[:80]
    return " ".join(x for x in m.groups() if x)[:80]


def phase_of(trace: dict, dev: int, t: float, phases: dict | None) -> str:
    """Where time `t` lies among the job's main programs on the device
    (`phases`, from the job's module): before the first one of the
    annotated job it falls in, between two, after the last, or outside
    every annotated job."""
    if not phases:
        return "idle"
    for start, end in trace["fits"]:
        if start <= t < end:
            main = [(s, s + d) for n, s, d in trace["modules"].get(dev, [])
                    if start <= s < end and re.search(phases["program"], n)]
            if not main or t < main[0][0]:
                return phases["before"]
            return (phases["after"] if t >= max(e for _, e in main)
                    else phases["between"])
    return phases["outside"]


def breakdown(trace: dict, phases: dict | None = None, top: int = 10) -> dict:
    dev = fullest_device(trace)
    own: dict = {}
    for name, seconds in self_times(trace["devices"][dev]["ops"]).items():
        own[short(name)] = own.get(short(name), 0.0) + seconds
    ops = sorted(own.items(), key=lambda kv: -kv[1])[:top]
    by_phase: dict = {}
    for s, e in idle_gaps(trace, dev):
        phase = phase_of(trace, dev, (s + e) / 2, phases)
        by_phase[phase] = by_phase.get(phase, 0.0) + (e - s)
    gaps = sorted(by_phase.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, seconds] for name, seconds in ops],
        "idle_gaps": [[name, seconds] for name, seconds in gaps],
    }
