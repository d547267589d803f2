"""Versioned on-disk plan store: JSONL, atomic appends, corrupt-tail
tolerant, keyed by (knob, backend fingerprint, shape signature) under a
code schema version.

Write path and durability semantics are the telemetry journal's
(telemetry/journal.py): one `os.write` per line, so concurrent writers
and a mid-write kill can truncate only the final line, and replay
tolerates exactly that truncation.  A plan entry is never load-bearing
for correctness — every consumer validates what it reads and falls back
to config/defaults — so a damaged store degrades to "untuned", never to
"crashed".

Entry shape (one JSON line; Journal stamps seq/t/mono_ns on top):

    {"schema": 1, "knob": "fused_em_chunk",
     "backend": "tpu:tpu_v5_lite:1", "shape": "*", "value": 128,
     "source": "autotune", "measurements": {"16": 821000, ...},
     ...provenance...}

Invalidation is by omission: entries whose `schema` differs from this
code's SCHEMA_VERSION are dropped at load, and lookups match the
CURRENT backend fingerprint — a cache written on one backend simply
misses on another.  Latest entry per (knob, backend, shape) wins.
"""

from __future__ import annotations

import os
from typing import NamedTuple

from ..telemetry.journal import Journal

SCHEMA_VERSION = 1

ENV_PATH = "ONI_ML_TPU_PLAN_CACHE"


def cache_base() -> str:
    """The one user-cache directory every plans artifact lives under
    ($XDG_CACHE_HOME or ~/.cache, then oni_ml_tpu/) — shared with the
    compilation cache (plans/warmup.py) so the two resolutions cannot
    drift."""
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    return os.path.join(base, "oni_ml_tpu")


def default_path() -> str:
    """Live store path: ONI_ML_TPU_PLAN_CACHE, else
    <cache_base()>/plans.jsonl."""
    env = os.environ.get(ENV_PATH)
    if env:
        return env
    return os.path.join(cache_base(), "plans.jsonl")


class PlanEntry(NamedTuple):
    knob: str
    backend: str
    shape: str
    value: object
    source: str          # "autotune" | "probe" | ...
    measurements: "dict | None"
    record: dict         # the full on-disk record (provenance)

    @property
    def key(self):
        return (self.knob, self.backend, self.shape)


def _entry_from_record(rec: dict) -> "PlanEntry | None":
    """Schema gate + field extraction; None drops the record."""
    if not isinstance(rec, dict) or rec.get("schema") != SCHEMA_VERSION:
        return None
    knob, backend = rec.get("knob"), rec.get("backend")
    if not knob or not backend or "value" not in rec:
        return None
    meas = rec.get("measurements")
    return PlanEntry(
        knob=str(knob),
        backend=str(backend),
        shape=str(rec.get("shape") or "*"),
        value=rec["value"],
        source=str(rec.get("source") or "unknown"),
        measurements=meas if isinstance(meas, dict) else None,
        record=rec,
    )


class PlanStore:
    """Lazy-loaded plan cache over one JSONL file.

    Reads replay the file with the journal's truncated-tail tolerance;
    appends go through a Journal (single-write atomic lines).  The
    in-memory map updates on record(), so a process sees its own
    appends without re-reading the file."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._entries: "dict | None" = None   # key -> PlanEntry
        self._dropped = 0
        self._journal: "Journal | None" = None

    # -- load ------------------------------------------------------------
    def _load(self) -> dict:
        if self._entries is not None:
            return self._entries
        entries: dict = {}
        records, dropped = Journal.replay_report(self.path)
        for rec in records:
            entry = _entry_from_record(rec)
            if entry is None:
                dropped += 1
                continue
            entries[entry.key] = entry   # latest wins
        self._entries = entries
        self._dropped = dropped
        return entries

    def reload(self) -> None:
        self._entries = None

    @property
    def dropped_records(self) -> int:
        """Undecodable/mismatched-schema records seen at load — the
        'file is damaged vs clean tail truncation' signal."""
        self._load()
        return self._dropped

    # -- queries ---------------------------------------------------------
    def entries(self) -> list[PlanEntry]:
        return list(self._load().values())

    def lookup(self, knob: str, backend: str,
               shape: str = "*") -> "PlanEntry | None":
        """Latest entry for (knob, backend): exact shape match first,
        then the '*' wildcard.  A fingerprint or schema mismatch is a
        miss, never an error."""
        entries = self._load()
        hit = entries.get((knob, backend, shape))
        if hit is None and shape != "*":
            hit = entries.get((knob, backend, "*"))
        return hit

    # -- writes ----------------------------------------------------------
    def record(self, knob: str, backend: str, shape: str, value, *,
               source: str = "autotune", measurements=None,
               **info) -> dict:
        """Append one entry (atomic single-write line) and update the
        in-memory map."""
        rec = {
            "schema": SCHEMA_VERSION,
            "knob": knob,
            "backend": backend,
            "shape": shape or "*",
            "value": value,
            "source": source,
            **info,
        }
        if measurements is not None:
            # JSON object keys are strings; normalize so round-trips
            # compare equal.
            rec["measurements"] = {
                str(k): v for k, v in dict(measurements).items()
            }
        if self._journal is None:
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            # fsync per append: plan entries are rare and precious
            # (each one cost a measurement sweep).
            self._journal = Journal(self.path, fsync_every=1)
        stamped = self._journal.append(rec)
        entry = _entry_from_record(rec)
        if entry is not None:
            self._load()[entry.key] = entry
        return stamped

    def clear(self) -> None:
        """Remove the file."""
        self.close()
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass
        self._entries = None

    def close(self) -> None:
        if self._journal is not None:
            self._journal.close()
            self._journal = None


class NullStore:
    """The disabled store (--no-plans): every lookup misses, every
    record drops.  Kept a distinct type so use_store(NullStore())
    reads as an explicit opt-out at call sites."""

    path = None

    def lookup(self, *a, **kw):
        return None

    def record(self, *a, **kw):
        return {}

    def entries(self):
        return []

    def clear(self) -> None:
        pass

    def close(self) -> None:
        pass
