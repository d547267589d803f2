"""The tuned-knob registry: every constant the autotune/plan layer may
own, with its shipped default, fingerprint scope, and declared sweep
space.

Defaults are read FROM config.py (the one allowed home of tuned-constant
literals besides this package — enforced by the tuned-constant grep-lint
in tests/test_telemetry.py), so the resolve() config-override detection
can never drift from the dataclass defaults.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from ..config import LDAConfig, ScoringConfig, ServingConfig


def _pos_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and v > 0


def _pos_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0


def _calibration_dict(v) -> bool:
    if not isinstance(v, dict) or "break_even" not in v:
        return False
    be = v["break_even"]
    # break_even must be numeric or None ("device can never win") — a
    # hand-edited entry like "auto" would otherwise crash int(be) in
    # dispatch_calibration instead of degrading to a re-measure.
    return be is None or (
        isinstance(be, (int, float)) and not isinstance(be, bool)
    )


def _engine_dict(v) -> bool:
    # The dense-vs-sparse crossover record (sparse_estep.engine_crossover):
    # engine must name a real family — a hand-edited "fastest" would
    # otherwise silently fall through every engine gate downstream.
    return isinstance(v, dict) and v.get("engine") in ("dense", "sparse")


def _featurize_engine_dict(v) -> bool:
    # The featurize-plane engine record: same rule as _engine_dict, over
    # the sources/device.py engine family.
    return isinstance(v, dict) and v.get("engine") in (
        "host", "device", "fused"
    )


@dataclass(frozen=True)
class Knob:
    """One tunable: `scope` picks the fingerprint (a host knob like
    pre_workers must not be invalidated by a device swap, and a device
    knob must not survive one); `candidates` is the declared autotune
    sweep space; `valid` rejects garbage cache entries (a plan file is
    operator-editable, so consumers never trust it blindly)."""

    name: str
    default: object
    scope: str = "device"              # "device" | "host"
    candidates: tuple = ()
    valid: Callable = field(default=_pos_int)
    doc: str = ""


KNOBS = {
    k.name: k
    for k in (
        Knob(
            "fused_em_chunk", LDAConfig.fused_em_chunk,
            candidates=(16, 32, 64, 128, 256),
            doc="EM iterations per device dispatch (models/fused.py); "
                "the r05 sweep's ~65 ms/dispatch glue term is what this "
                "amortizes",
        ),
        Knob(
            "host_sync_every", LDAConfig.host_sync_every,
            # 0 (sync only at chunk boundaries — maximum throughput,
            # coarsest observability) is deliberately NOT in the plan
            # space and fails the validator: a throughput sweep would
            # always pick it, silently collapsing the crash-safety
            # cadence config.py promises cannot collapse without an
            # explicit config choice.  Setting 0 in config still works
            # (config overrides bypass plan validation).
            candidates=(8, 16, 32), valid=_pos_int,
            doc="EM iterations between host syncs (observability "
                "cadence), bounded independently of fused_em_chunk",
        ),
        Knob(
            "dense_estep_block", None, valid=_pos_int,
            doc="measured doc-block override for ops/dense_estep."
                "pick_block (the analytic pick is the prior); shape "
                "key b{B}.v{V}.k{K}.{precision}",
        ),
        Knob(
            "dense_estep_block_w", None, valid=_pos_int,
            doc="W-major twin of dense_estep_block (pick_block_w)",
        ),
        Knob(
            "sparse_estep_bb", None, valid=_pos_int,
            doc="measured doc-block override for ops/sparse_estep."
                "pick_block (the analytic VMEM pick is the prior); "
                "shape key b{B}.l{L}.k{K}.{precision} — "
                "tools/estep_probe.py sweeps it",
        ),
        Knob(
            "sparse_estep_l", LDAConfig.sparse_min_bucket_len,
            candidates=(128, 256), valid=_pos_int,
            doc="minimum packed tile length (lane-tile floor) for the "
                "sparse engine's bucketed corpus layout "
                "(Corpus.bucketed_layout via sparse_estep."
                "resolve_layout_len)",
        ),
        Knob(
            "estep_engine", None, valid=_engine_dict,
            doc="measured dense-vs-sparse E-step engine crossover "
                "(sparse_estep.engine_crossover record, minus its "
                "source/shape fields), keyed by exact shape and by "
                "density band — the dispatch_calibration pattern for "
                "the EM engines",
        ),
        Knob(
            "score_device_chunk", ScoringConfig.device_chunk,
            candidates=(8192, 16384, 32768, 65536, 131072, 262144),
            doc="events per device dispatch in the fused scoring "
                "pipeline (scoring/pipeline.py; tools/score_probe.py "
                "sweeps it)",
        ),
        Knob(
            "dispatch_calibration", None, valid=_calibration_dict,
            doc="measured host-vs-device scoring break-even "
                "(scoring.score.dispatch_calibration record, minus "
                "its source field)",
        ),
        Knob(
            "pre_workers", None, scope="host", candidates=(1, 2, 4, 8),
            doc="pre-stage shard workers for this host "
                "(features/shards.resolve_pre_workers; "
                "tools/pre_probe.py sweeps it)",
        ),
        # The serving flush triggers are HOST-scoped deliberately: they
        # are queueing/latency knobs, not device properties, and a
        # device fingerprint would make BatchScorer.__init__ initialize
        # the jax backend even for host-pinned serving
        # (device_score_min=None) — a host-only service must start
        # beside a process that holds the chip.
        Knob(
            "serve_max_batch", ServingConfig.max_batch, scope="host",
            candidates=(512, 1024, 2048, 4096, 8192),
            doc="serving micro-batch flush size (serving/batcher.py)",
        ),
        Knob(
            "serve_max_wait_ms", ServingConfig.max_wait_ms, scope="host",
            candidates=(10.0, 25.0, 50.0, 100.0), valid=_pos_num,
            doc="serving micro-batch latency trigger (ms)",
        ),
        # Fleet flush triggers: host-scoped for the same reason as the
        # single-model serve knobs above — queueing policy, not device
        # property.
        Knob(
            "fleet_max_batch", ServingConfig.fleet_max_batch,
            scope="host", candidates=(512, 1024, 2048, 4096, 8192),
            doc="cross-tenant micro-batch flush size "
                "(serving/fleet.py FleetScorer)",
        ),
        Knob(
            "fleet_max_wait_ms", ServingConfig.fleet_max_wait_ms,
            scope="host", candidates=(10.0, 25.0, 50.0, 100.0),
            valid=_pos_num,
            doc="cross-tenant micro-batch latency trigger (ms)",
        ),
        # Device-scoped: HBM-hot tenant capacity is a property of the
        # device's memory, not of the host's queueing policy — a plan
        # measured against one accelerator's HBM must not survive a
        # backend swap.  The ServingConfig default of 0 means
        # "unbounded" and is mapped to None (the pure-plan-knob
        # convention, like dense_estep_block) by the resolver in
        # serving/residency.py, so a measured capacity engages only
        # when the operator left the knob unset.
        Knob(
            "featurize_engine", None, valid=_featurize_engine_dict,
            doc="measured featurize-plane engine pick for this backend "
                "(sources/device.py resolve_engine; consulted only when "
                "ServingConfig.featurize_engine is left at \"auto\" and "
                "ONI_ML_TPU_FEATURIZE is unset)",
        ),
        Knob(
            "featurize_block", ServingConfig.featurize_block,
            candidates=(1024, 2048, 4096, 8192),
            doc="pow2 pad floor for the fused featurize dispatch's "
                "micro-batch dimension (ops/featurize_kernel.py; bounds "
                "the compiled-shape family below the flush cap)",
        ),
        # Device-scoped like dispatch_calibration: the crossover where
        # a device featurize dispatch beats the vectorized host parse
        # is a property of the accelerator (dispatch glue + compile
        # residency), not of the host's queueing policy.
        Knob(
            "featurize_break_even", None, valid=_pos_int,
            candidates=(16, 32, 64, 128, 256, 512),
            doc="minimum flush segment size for the device featurize "
                "path (sources/device.py resolve_break_even; below it "
                "the host featurizer wins — measured by the featurize "
                "bench phase, 0 in ServingConfig = use this knob)",
        ),
        Knob(
            "fleet_hot_tenants", None,
            candidates=(4, 8, 16, 32, 64),
            doc="HBM-hot stacked-snapshot tenant capacity per K-group "
                "(serving/residency.py tiered paging; 0 in config = "
                "unbounded legacy residency)",
        ),
    )
}
