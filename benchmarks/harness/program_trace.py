"""What `xplane.load` drops from the profiler's `.xplane.pb`: the program's
own spans and what the profiler knows of every device operation's origin.

  spans   the host plane's events whose names are the program's spans
          (`oni_ml_tpu/telemetry/spans.py` enters a `TraceAnnotation` per
          span, and one short `<name>.counts` event for what is known only
          after the work), with their stats: (name, start, duration, stats,
          thread line), seconds on the trace's own clock, the clock of
          `xplane.load`'s device events.
  scopes  per device, for each `XLA Ops` event the op_name of its HLO
          instruction: (name, start, duration, scope).  The profiler keeps
          it on the event's METADATA (stat `tf_op`, "<op_name>:"), which
          `jax.profiler.ProfileData` does not hand out; so this module
          reads the file's wire format itself.  It needs four messages of
          xplane.proto (XSpace, XPlane, XLine, XEvent) and two more for the
          names (XEventMetadata, XStatMetadata), and nothing outside the
          standard library.  How much of the `jax.named_scope` path the
          op_name holds is jax's to decide: with
          `jax_include_full_tracebacks_in_locations` off, as
          `plans.warmup.setup_compilation_cache` sets it to keep the Mosaic
          kernels' cache keys still, jax 0.9 gives XLA the primitive alone
          (`pallas_call`, `while`), so no reader may lean on the path
          (PERF.md, PR 27).

`run.py` hands the readers the run's own trace directory (`ctx["trace_dir"]`,
`<checkout>/.bench_trace/<cell>`): `newest(trace_dir=...)` takes the newest
`*.xplane.pb` under it.  Without one it takes the newest under
`<checkout>/.bench_trace/*/`, which is another run's where two cells are
traced in one checkout at once (the test suite's workers).
"""

from __future__ import annotations

import glob
import os
import re
import struct

from benchmarks.harness import cells, xplane

# The stat of an operation's metadata that holds its op_name, "<op_name>:".
SCOPE_STAT = "tf_op"


def newest(root: str | None = None,
           trace_dir: str | None = None) -> str | None:
    under = trace_dir or os.path.join(root or cells.ROOT, ".bench_trace", "*")
    files = glob.glob(os.path.join(
        under, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


# -- the wire format -----------------------------------------------------

def _varint(buf, at: int) -> tuple:
    value, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: memoryview):
    """(field number, wire type, value) of one message: varints as ints,
    length-delimited fields as memoryviews, fixed64 and fixed32 as their
    bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire in (1, 2, 5):
            if wire == 2:
                size, at = _varint(buf, at)
            else:
                size = 8 if wire == 1 else 4
            value = buf[at:at + size]
            at += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """One XStat -> (name, value); a `ref_value` names its string."""
    name, value = None, None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, None
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """One XPlane -> {"name", "lines": [(line name, [event, ...])]} with an
    event = (name, start seconds, duration seconds, stats, metadata stats)."""
    name, lines, events_meta, stat_names = "", [], {}, {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            events_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            for n, _, x in _fields(value):
                if n == 2:
                    stat_names[key] = _text(x)
    meta = {}
    for key, value in events_meta.items():
        ev_name, display, stats = "", "", []
        for n, _, x in _fields(value):
            if n == 2:
                ev_name = _text(x)
            elif n == 4:
                display = _text(x)
            elif n == 5:
                stats.append(x)
        meta[key] = (ev_name or display,
                     dict(_stat(s, stat_names) for s in stats))
    out = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for n, _, x in _fields(line):
            if n == 2:
                line_name = _text(x)
            elif n == 3:
                t0_ns = _signed(x)
            elif n == 4:
                events.append(x)
        decoded = []
        for event in events:
            meta_id, offset_ps, duration_ps, stats = 0, 0, 0, []
            for n, _, x in _fields(event):
                if n == 1:
                    meta_id = x
                elif n == 2:
                    offset_ps = _signed(x)
                elif n == 3:
                    duration_ps = _signed(x)
                elif n == 4:
                    stats.append(x)
            ev_name, meta_stats = meta.get(meta_id, ("", {}))
            decoded.append((
                ev_name, t0_ns * 1e-9 + offset_ps * 1e-12, duration_ps * 1e-12,
                dict(_stat(s, stat_names) for s in stats), meta_stats))
        out.append((line_name, decoded))
    return {"name": name, "lines": out}


def planes(path: str, wanted) -> list:
    """The planes of the file whose name `wanted(name)` accepts."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = []
    for number, _, v in _fields(data):
        if number != 1:
            continue
        name = next((_text(x) for n, _, x in _fields(v) if n == 2), "")
        if wanted(name):
            out.append(_plane(v))
    return out


# -- what the readers read ----------------------------------------------

def is_span(name: str, span_names) -> bool:
    base = name[:-len(".counts")] if name.endswith(".counts") else name
    return base in span_names


def scope_of(meta_stats: dict) -> str:
    return str(meta_stats.get(SCOPE_STAT) or "").rstrip(":")


def load_spans(path: str, span_names) -> list:
    """[(name, start, duration, stats, thread line)] of the host plane's
    events named in `span_names` (and their `.counts`), sorted by start.
    A program that has no spans gives an empty list."""
    spans = []
    for plane in planes(path, lambda name: name == "/host:CPU"):
        for line_name, events in plane["lines"]:
            spans += [(n, s, d, stats, line_name)
                      for n, s, d, stats, _ in events
                      if is_span(n, span_names)]
    return sorted(spans, key=lambda e: e[1])


def load_scopes(path: str, chips: int = 1) -> dict:
    """{device: [(name, start, duration, scope)]} of each device's `XLA Ops`
    line, sorted by start."""
    device = re.compile(r"/device:TPU:(\d+)")

    def wanted(name):
        m = device.fullmatch(name)
        return bool(m and int(m.group(1)) < chips)

    scopes = {}
    for plane in planes(path, wanted):
        dev = int(device.fullmatch(plane["name"]).group(1))
        for line_name, events in plane["lines"]:
            if line_name == xplane.OPS_LINE:
                scopes[dev] = sorted(
                    ((n, s, d, scope_of(meta)) for n, s, d, _, meta in events),
                    key=lambda e: e[1])
    return scopes


def load(path: str, span_names, chips: int = 1) -> dict:
    """{"spans": load_spans(...), "scopes": load_scopes(...)}."""
    return {"spans": load_spans(path, span_names),
            "scopes": load_scopes(path, chips)}
