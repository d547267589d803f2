"""Measured execution plans: persistent autotune + plan cache.

Several of this framework's throughput knobs — the EM iterations per
dispatch, the host-vs-device scoring break-even — trade a per-dispatch
cost (not measured on the current machine; ROADMAP A1) against device
work, so their right values belong to a backend and a shape, not to
`config.py`.  This package turns those hand-tuned knobs into measured,
persisted, per-(backend, shape) execution plans:

- `store.PlanStore` — a versioned on-disk JSONL store (atomic
  single-write lines like the telemetry journal, corrupt-tail tolerant)
  keyed by backend fingerprint + shape signature + code schema version.
  Entries append to `~/.cache/oni_ml_tpu/plans.jsonl` (or
  `ONI_ML_TPU_PLAN_CACHE`).
- `autotune.autotune` — a bounded sweep harness: measure a declared
  candidate space under a wall-clock budget, record the winner WITH its
  measurements so every constant in the cache carries provenance.
- `resolve()` — the one precedence rule every consumer uses: an
  explicitly-set config knob always wins (`source: "config"`), else a
  matching plan entry (`"plan"`), else the shipped default
  (`"default"`).  Consumers surface the source in their stage/serve
  records so a run is always attributable to the constants it ran
  under.
- `warmup` — AOT warmup + persistent-compilation-cache wiring
  (`jax_compilation_cache_dir`), so both traced-program and tuned-plan
  state survive process death.

`ONI_ML_TPU_PLANS=0` disables every lookup and record (consumers fall
back to config/default exactly as before this package existed).
"""

from __future__ import annotations

import contextlib
import contextvars
import os

from .autotune import AutotuneResult, autotune
from .knobs import KNOBS, Knob
from .store import (
    SCHEMA_VERSION,
    NullStore,
    PlanEntry,
    PlanStore,
    default_path,
)

__all__ = [
    "AutotuneResult",
    "KNOBS",
    "Knob",
    "NullStore",
    "PlanEntry",
    "PlanStore",
    "SCHEMA_VERSION",
    "autotune",
    "counters",
    "counters_snapshot",
    "current_store",
    "fingerprint",
    "default_path",
    "default_store",
    "device_fingerprint",
    "em_shape",
    "host_fingerprint",
    "lookup_value",
    "note_sweep",
    "record_value",
    "resolve",
    "use_store",
]


# Process-wide observability counters the runner/bench surface in their
# records: how many plan lookups hit, how many fell to defaults, and —
# the acceptance number — how many autotune sweeps actually ran.
counters = {"plan_hits": 0, "defaults": 0, "config": 0,
            "autotune_sweeps": 0}


def note_sweep(knob: str) -> None:
    """Count one autotune measurement pass (the harness and the
    self-measuring knobs like dispatch_calibration both call this), so
    'a second run performs zero sweeps' is assertable from records."""
    counters["autotune_sweeps"] += 1


# ---------------------------------------------------------------------------
# Backend fingerprints
# ---------------------------------------------------------------------------


def _norm(s: str) -> str:
    return s.strip().lower().replace(" ", "_")


def host_fingerprint() -> str:
    """Fingerprint for host-side knobs (pre_workers): machine + cores.
    jax-free, so the featurization path never drags the device stack in."""
    import platform

    return _norm(f"host:{platform.machine()}:{os.cpu_count() or 1}")


_DEVICE_FP: "str | None" = None


def device_fingerprint() -> str:
    """Fingerprint for device-side knobs: backend platform + device kind
    + device count.  Initializes the jax backend on first use (cached);
    'nodevice' when no backend answers, so lookups simply miss instead
    of raising."""
    global _DEVICE_FP
    if _DEVICE_FP is None:
        try:
            import jax

            dev = jax.devices()[0]
            kind = getattr(dev, "device_kind", "") or ""
            _DEVICE_FP = _norm(
                f"{jax.default_backend()}:{kind}:{jax.device_count()}"
            )
        except Exception:
            _DEVICE_FP = "nodevice"
    return _DEVICE_FP


def fingerprint(scope: str) -> str:
    return host_fingerprint() if scope == "host" else device_fingerprint()


# ---------------------------------------------------------------------------
# Store selection
# ---------------------------------------------------------------------------

_DEFAULT: "PlanStore | None" = None
_current: contextvars.ContextVar = contextvars.ContextVar(
    "oni_plan_store", default=None
)


def plans_enabled() -> bool:
    return os.environ.get("ONI_ML_TPU_PLANS", "1") not in ("0", "off", "no")


def default_store() -> PlanStore:
    """The process default store at `default_path()` (env
    ONI_ML_TPU_PLAN_CACHE or ~/.cache/oni_ml_tpu/plans.jsonl).
    Re-resolved when the env path changes (tests repoint it)."""
    global _DEFAULT
    path = default_path()
    if _DEFAULT is None or _DEFAULT.path != path:
        if _DEFAULT is not None:
            _DEFAULT.close()
        _DEFAULT = PlanStore(path)
    return _DEFAULT


def current_store() -> "PlanStore | None":
    """The store consumers resolve against: the `use_store` context's
    store when one is active, else the default store; None when plans
    are disabled (ONI_ML_TPU_PLANS=0)."""
    if not plans_enabled():
        return None
    store = _current.get()
    if store is not None:
        return None if isinstance(store, NullStore) else store
    return default_store()


@contextlib.contextmanager
def use_store(store: "PlanStore | NullStore | None"):
    """Scope the active plan store (the runner pins its run's store
    here, like telemetry's use_recorder).  Pass a NullStore to disable
    plan lookups inside the scope (--no-plans)."""
    token = _current.set(store)
    try:
        yield store
    finally:
        _current.reset(token)


# ---------------------------------------------------------------------------
# Resolution — the one precedence rule
# ---------------------------------------------------------------------------

_UNSET = object()


def resolve(knob: str, config_value, *, shape: str = "*", store=_UNSET):
    """-> (value, source) for one knob.

    Precedence: an explicitly-set config value — one that differs from
    the knob's shipped default — always wins (`"config"`); else a plan
    entry matching (backend fingerprint, shape) with exact shape beating
    the `"*"` wildcard (`"plan"`); else the default (`"default"`).
    `config_value=None` means "the caller has no config surface for
    this knob" and skips straight to the plan.

    The config-vs-default comparison is by VALUE: setting a knob
    explicitly to its shipped default is indistinguishable from leaving
    it alone, and a matching plan may override it (ROADMAP C9)."""
    spec = KNOBS[knob]
    if config_value is not None and config_value != spec.default:
        counters["config"] += 1
        return config_value, "config"
    st = current_store() if store is _UNSET else store
    if st is not None:
        entry = st.lookup(knob, fingerprint(spec.scope), shape)
        if entry is not None and spec.valid(entry.value):
            counters["plan_hits"] += 1
            return entry.value, "plan"
    counters["defaults"] += 1
    return (spec.default if config_value is None else config_value,
            "default")


def lookup_value(knob: str, shape: str = "*"):
    """Plan-entry value for `knob` at `shape`, or None — the minimal
    probe for consumers with their own validation/fallback logic
    (dense_estep.pick_block, dispatch_calibration).  Never raises.

    Deliberately does NOT bump the `plan_hits` counter: the caller may
    still reject the value against constraints this layer cannot see
    (block feasibility, shape gates), and the counters must describe
    knobs that actually RAN from a plan — resolve() counts those."""
    try:
        st = current_store()
        if st is None:
            return None
        spec = KNOBS[knob]
        entry = st.lookup(knob, fingerprint(spec.scope), shape)
        if entry is not None and spec.valid(entry.value):
            return entry.value
    except Exception:
        return None
    return None


def record_value(knob: str, value, *, shape: str = "*",
                 source: str = "autotune", measurements=None,
                 **info) -> bool:
    """Append one plan entry to the active store.  Never raises — a
    read-only cache dir must not fail the measurement that produced the
    value.  Returns whether the entry was actually written (False when
    plans are disabled or the write failed), so probes can report the
    cache update honestly instead of claiming a seed that never
    landed."""
    try:
        st = current_store()
        if st is None:
            return False
        spec = KNOBS[knob]
        st.record(knob, fingerprint(spec.scope), shape, value,
                  source=source, measurements=measurements, **info)
        return True
    except Exception:
        return False


def counters_snapshot() -> dict:
    return dict(counters)


# ---------------------------------------------------------------------------
# Shape signatures
# ---------------------------------------------------------------------------


def em_shape(k: int, v: int, batches=None) -> str:
    """Shape signature for the EM knobs: topics, vocab, and the largest
    batch shape (the bucketed batches' dominant compiled shape)."""
    sig = f"k{k}.v{v}"
    if batches:
        b, ln = max((bt.word_idx.shape for bt in batches))
        sig += f".b{b}.l{ln}"
    return sig
