"""Multi-tenant serving fleet: shared device residency, cross-tenant
micro-batch multiplexing, per-tenant SLO isolation.

`ml_ops serve` hosted exactly one model and one stream; a production
deployment scores many tenants/days concurrently on the same devices.
The scarce resources are the device-resident weights and the padded
AOT-warmed compiled-program family (plans/warmup.warmup_serving) — so
the fleet shares THOSE while isolating everything per-tenant:

`FleetRegistry`
    N hot models with per-tenant atomic hot-swap: one
    serving/registry.py `ModelRegistry` per tenant (validation +
    double-buffered publish + monotonic versions, unchanged), plus a
    *stacked snapshot* per topic-count K — every member tenant's
    [D_t+1, K] theta and [V_t+1, K] p concatenated row-wise with
    per-tenant base offsets.  The stack is itself double-buffered: a
    publish rebuilds it OUTSIDE the registry lock and swaps one
    reference, so tenant A's `RefreshLoop` publish never stalls tenant
    B's scoring path, and because every tenant's row count is stable
    across swaps the stacked shape — and therefore the compiled program
    — survives every hot-swap (keyed by shape, not tenant: zero
    retraces).

`FleetScorer`
    Cross-tenant micro-batch multiplexing into ONE compiled dispatch:
    events from every tenant's admission queue drain globally
    oldest-first into a shared micro-batch; each tenant segment
    featurizes with its own day's quantile cuts, maps onto its own
    model slice via `tenant base offset + local row` — the tenant-id
    column driving the on-device gather — and all segments of a
    K-group score as one `batched_scores` call at a shared padded
    shape.  Tenants whose K diverges form their own pack group
    (per-tenant segment dispatch), so heterogeneous fleets degrade to
    more dispatches, never to wrong scores.  Results demux back to
    per-tenant `ScoreFuture`s (journaled as `{"kind": "demux"}`),
    with per-tenant `serve.<tenant>.*` histograms/counters on the
    shared metrics plane and bounded per-tenant admission
    (serving/tenants.py) for ingress isolation.

Correctness invariant, pinned by tests/test_fleet.py: a packed
cross-tenant flush produces bit-identical scores to scoring each
tenant's events alone through `score_features` — packing changes WHICH
dispatch a row rides, never its arithmetic.
"""

from __future__ import annotations

import heapq
import threading
import time
from dataclasses import dataclass

import numpy as np

from ..config import ServingConfig
from ..scoring import ScoringModel
from ..scoring.score import batched_scores, use_device_path
from ..sources.device import DeviceBatch, device_batch, resolve_engine
from .metrics import MetricsEmitter
from .registry import ModelRegistry, ModelSnapshot
from .tenants import (
    AdmissionRejected,
    TenantLane,
    TenantSpec,
    _PendingEvent,
)


@dataclass(frozen=True)
class StackedSnapshot:
    """One pack group's shared-residency view: every member tenant's
    theta/p concatenated row-wise (each slice INCLUDES its own fallback
    row, so per-tenant fallback semantics survive packing).  Readers
    treat every field as immutable; a publish installs a fresh instance
    (so the device cache `scoring.score._device_model` hangs off re-
    uploads the new weights exactly once, while in-flight flushes
    finish on the instance — and device buffers — they started with)."""

    k: int
    tenants: tuple[str, ...]
    model: ScoringModel            # stacked [sum(D_t+1), K] / [sum(V_t+1), K]
    members: dict                  # tenant -> ModelSnapshot the stack was built from
    ip_base: dict                  # tenant -> row offset into stacked theta
    word_base: dict                # tenant -> row offset into stacked p
    stack_version: int             # monotonic per K-group build counter
    capacity: int = 0              # tenant-slot capacity tier (0 = exact census)
    precision: str = "f32"         # device storage dtype of the stacked model

    def version_of(self, tenant: str) -> int:
        return self.members[tenant].version


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _build_stack(k: int, tenants: "list[str]", snaps: dict,
                 stack_version: int, *, tier: "dict | None" = None,
                 precision: str = "f32") -> StackedSnapshot:
    """Concatenate member models into one stacked ScoringModel.  Pure
    function of the member snapshots — called OUTSIDE any lock.

    `tier` (capacity-tier mode, the tiered-residency path) pads the
    stacked matrices with zero rows up to ``capacity * slot_rows``:
    `capacity` is the power-of-two tenant-slot count and the slot row
    budgets cover the largest tenant the K-group has ever seen, so the
    stacked SHAPE — and with it the compiled program family — is a
    function of the capacity tier alone, not of which tenants happen to
    be resident.  Promotion/eviction churn within a tier then retraces
    nothing; only crossing a power-of-two census boundary mints one new
    program family.  The pad rows are never indexed (tenant base
    offsets only cover real members), so padding cannot change a
    score.

    `precision="bf16"` marks the stacked model for half-width DEVICE
    storage (scoring.score._device_model honors the marker): double the
    HBM-hot residency per byte, f32 accumulation in the gather-dot
    kernel, ~2^-8 relative score drift vs the f32 stack (documented
    tolerance).  Host matrices stay float64 either way."""
    thetas, ps = [], []
    ip_base: dict = {}
    word_base: dict = {}
    ip_off = word_off = 0
    for t in tenants:
        m = snaps[t].model
        ip_base[t] = ip_off
        word_base[t] = word_off
        thetas.append(np.asarray(m.theta, np.float64))
        ps.append(np.asarray(m.p, np.float64))
        ip_off += m.theta.shape[0]
        word_off += m.p.shape[0]
    capacity = 0
    if tier is not None:
        capacity = int(tier["capacity"])
        pad_ip = capacity * int(tier["ip_slot"]) - ip_off
        pad_word = capacity * int(tier["word_slot"]) - word_off
        if pad_ip < 0 or pad_word < 0:
            raise RuntimeError(
                f"capacity tier {tier} cannot hold {len(tenants)} "
                f"members ({ip_off}/{word_off} rows)"
            )
        if pad_ip:
            thetas.append(np.zeros((pad_ip, k)))
        if pad_word:
            ps.append(np.zeros((pad_word, k)))
    stacked = ScoringModel(
        ip_index={}, theta=np.concatenate(thetas),
        word_index={}, p=np.concatenate(ps),
    )
    if precision == "bf16":
        stacked._device_dtype = "bfloat16"
    return StackedSnapshot(
        k=k, tenants=tuple(tenants), model=stacked, members=dict(snaps),
        ip_base=ip_base, word_base=word_base, stack_version=stack_version,
        capacity=capacity, precision=precision,
    )


class _TenantRegistryView:
    """ModelRegistry facade for ONE tenant of a FleetRegistry — what a
    per-tenant RefreshLoop binds to, so the refresh machinery works
    unchanged while its publishes route through the fleet's stack
    rebuild."""

    def __init__(self, fleet: "FleetRegistry", tenant: str) -> None:
        self._fleet = fleet
        self._tenant = tenant

    def publish(self, model: ScoringModel, source: str) -> ModelSnapshot:
        return self._fleet.publish(self._tenant, model, source)

    def active(self) -> ModelSnapshot:
        return self._fleet.active(self._tenant)

    def previous(self) -> "ModelSnapshot | None":
        return self._fleet.previous(self._tenant)

    @property
    def version(self) -> int:
        return self._fleet.version(self._tenant)


class FleetRegistry:
    """N per-tenant ModelRegistries + per-K stacked snapshots with
    double-buffered installs.  `journal`/`recorder` are optional
    telemetry hooks: every publish journals a `{"kind":
    "fleet_publish"}` record and bumps `serve.<tenant>.publishes`."""

    def __init__(self, journal=None, recorder=None, *,
                 capacity_tiers: bool = False,
                 stack_precision: str = "f32") -> None:
        if stack_precision not in ("f32", "bf16"):
            raise ValueError(
                f"stack_precision must be f32|bf16, got {stack_precision!r}"
            )
        self._lock = threading.Lock()
        self._registries: dict[str, ModelRegistry] = {}
        self._specs: dict[str, TenantSpec] = {}
        self._order: list[str] = []
        self._tenant_k: dict[str, int] = {}
        self._stacks: dict[int, StackedSnapshot] = {}
        self._stack_builds: dict[int, int] = {}
        # -- tiered residency state (serving/residency.py drives it) --
        # _hot: stack membership per tenant (True = HBM-hot).  Legacy
        # fleets never flip it, so every published tenant stays
        # stack-resident.  _tenant_rows remembers each tenant's
        # (theta, p) row counts across cold unloads so the capacity
        # tier's slot budgets survive paging; _tiers holds the per-K
        # high-water {capacity, ip_slot, word_slot} — monotone, so
        # shrinking census never shrinks the compiled shape.
        self._hot: dict[str, bool] = {}
        self._tenant_rows: dict[str, tuple] = {}
        self._tiers: dict[int, dict] = {}
        self._capacity_tiers = capacity_tiers
        self._stack_precision = stack_precision
        self._journal = getattr(journal, "journal", journal)
        self._recorder = recorder

    @property
    def capacity_tiers(self) -> bool:
        return self._capacity_tiers

    @property
    def stack_precision(self) -> str:
        return self._stack_precision

    # -- tenant membership --------------------------------------------------

    def add_tenant(self, spec: TenantSpec, *, hot: bool = True) -> None:
        """Register one tenant.  `hot=False` (the tiered-residency
        startup path) keeps the tenant OUT of the stacked snapshot until
        a promotion admits it — a thousand-tenant fleet then pays one
        stack build per hot slot, not one per tenant."""
        with self._lock:
            if spec.tenant in self._registries:
                raise ValueError(f"tenant {spec.tenant!r} already added")
            self._registries[spec.tenant] = ModelRegistry()
            self._specs[spec.tenant] = spec
            self._order.append(spec.tenant)
            self._hot[spec.tenant] = hot

    def tenants(self) -> "list[str]":
        with self._lock:
            return list(self._order)

    def spec(self, tenant: str) -> TenantSpec:
        with self._lock:
            return self._specs[tenant]

    def view(self, tenant: str) -> _TenantRegistryView:
        self._registry(tenant)          # raise early on unknown tenant
        return _TenantRegistryView(self, tenant)

    def _registry(self, tenant: str) -> ModelRegistry:
        with self._lock:
            reg = self._registries.get(tenant)
        if reg is None:
            raise KeyError(
                f"unknown tenant {tenant!r} (known: {self.tenants()})"
            )
        return reg

    # -- publish / read -----------------------------------------------------

    def publish(self, tenant: str, model: ScoringModel,
                source: str) -> ModelSnapshot:
        """Validate and atomically promote `model` for ONE tenant, then
        install a rebuilt stacked snapshot for its K-group.  The
        per-tenant swap has registry.py semantics (validation failure
        leaves the active snapshot untouched); the stack rebuild runs
        outside the lock and never blocks another tenant's scoring."""
        reg = self._registry(tenant)
        snap = reg.publish(model, source)     # validates; per-tenant swap
        k = model.theta.shape[1]
        with self._lock:
            old_k = self._tenant_k.get(tenant)
            self._tenant_k[tenant] = k
            self._tenant_rows[tenant] = (
                model.theta.shape[0], model.p.shape[0],
            )
            stale = old_k if old_k is not None and old_k != k else None
            hot = self._hot.get(tenant, True)
        if stale is not None:
            self._refresh_stack(stale)
        if hot:
            self._refresh_stack(k)
        if self._journal is not None:
            self._journal.append({
                "kind": "fleet_publish", "tenant": tenant,
                "version": snap.version, "source": source, "k": k,
                "ip_rows": model.theta.shape[0],
                "word_rows": model.p.shape[0],
            })
        if self._recorder is not None:
            self._recorder.counter(f"serve.{tenant}.publishes").add(1)
        return snap

    def load_day(self, tenant: str, day_dir: str,
                 fallback: float) -> ModelSnapshot:
        """registry.load_day for one tenant — read the artifacts
        through the per-tenant registry's loader, publish through the
        fleet so the stack rebuilds."""
        doc = ModelRegistry()
        snap = doc.load_day(day_dir, fallback)
        return self.publish(tenant, snap.model, source=day_dir)

    def active(self, tenant: str) -> ModelSnapshot:
        return self._registry(tenant).active()

    def previous(self, tenant: str) -> "ModelSnapshot | None":
        return self._registry(tenant).previous()

    def version(self, tenant: str) -> int:
        return self._registry(tenant).version

    # -- stacked snapshots --------------------------------------------------

    def tenant_k(self, tenant: str) -> int:
        with self._lock:
            k = self._tenant_k.get(tenant)
        if k is None:
            raise RuntimeError(
                f"tenant {tenant!r} has no published model yet"
            )
        return k

    def stack(self, k: int) -> StackedSnapshot:
        with self._lock:
            snap = self._stacks.get(k)
        if snap is None:
            raise RuntimeError(f"no stacked snapshot for K={k}")
        return snap

    def stack_for(self, tenant: str) -> StackedSnapshot:
        return self.stack(self.tenant_k(tenant))

    def _tier_locked(self, k: int, census: int) -> "dict | None":
        """Caller holds self._lock.  The K-group's capacity tier:
        power-of-two tenant-slot count covering the hot-census
        high-water, slot row budgets covering the largest tenant the
        group KNOWS (hot, warm, or cold — a warm tenant must fit its
        slot the day it promotes without changing the compiled shape).
        Monotone: census shrink never shrinks a tier, so the program
        family only changes when the census first crosses a
        power-of-two boundary (or a strictly larger tenant joins the
        group)."""
        if not self._capacity_tiers:
            return None
        ip_slot = word_slot = 1
        for t in self._order:
            if self._tenant_k.get(t) != k:
                continue
            rows = self._tenant_rows.get(t)
            if rows is not None:
                ip_slot = max(ip_slot, _pow2(rows[0]))
                word_slot = max(word_slot, _pow2(rows[1]))
        prev = self._tiers.get(k, {})
        tier = {
            "capacity": max(_pow2(census), prev.get("capacity", 1)),
            "ip_slot": max(ip_slot, prev.get("ip_slot", 1)),
            "word_slot": max(word_slot, prev.get("word_slot", 1)),
        }
        self._tiers[k] = tier
        return tier

    def tier(self, k: int) -> "dict | None":
        """The K-group's current capacity tier (None when capacity
        tiers are off) — what the shape-stability tests assert on."""
        with self._lock:
            t = self._tiers.get(k)
            return dict(t) if t is not None else None

    def _refresh_stack(self, k: int) -> None:
        """Rebuild the K-group's stacked snapshot from the HOT members'
        CURRENT actives and install it — concatenation runs outside the
        lock; the install re-checks that no member published (or paged)
        meanwhile (loop until the built stack matches the live member
        versions, so concurrent publishes converge on a stack
        containing both)."""
        while True:
            with self._lock:
                members = [
                    t for t in self._order
                    if self._tenant_k.get(t) == k
                    and self._hot.get(t, True)
                ]
                regs = {t: self._registries[t] for t in members}
                tier = self._tier_locked(k, len(members))
            try:
                snaps = {t: regs[t].active() for t in members}
            except RuntimeError:
                # A member snapshotted as hot was paged out (and its
                # registry unloaded) while we held no lock — its
                # membership flip already re-queued a rebuild; retry
                # against the fresh census.
                continue
            if not snaps:
                with self._lock:
                    self._stacks.pop(k, None)
                return
            with self._lock:
                self._stack_builds[k] = self._stack_builds.get(k, 0) + 1
                build = self._stack_builds[k]
            built = _build_stack(k, members, snaps, build, tier=tier,
                                 precision=self._stack_precision)
            with self._lock:
                live = {
                    t: self._registries[t].version
                    for t in members
                    if self._tenant_k.get(t) == k
                    and self._hot.get(t, True)
                }
                if live == {t: s.version for t, s in snaps.items()}:
                    cur = self._stacks.get(k)
                    if cur is None or cur.stack_version < build:
                        self._stacks[k] = built
                    return
            # a member published (or paged) while we concatenated —
            # rebuild.

    # -- tiered residency hooks (serving/residency.py) ---------------------

    def is_hot(self, tenant: str) -> bool:
        with self._lock:
            return self._hot.get(tenant, True)

    def hot_census(self, k: int) -> "list[str]":
        """HOT members of the K-group, in registration order."""
        with self._lock:
            return [
                t for t in self._order
                if self._tenant_k.get(t) == k and self._hot.get(t, True)
            ]

    def set_hot(self, tenant: str, hot: bool) -> None:
        """Flip one tenant's stack membership and rebuild its K-group's
        stacked snapshot — the promotion/eviction primitive.  The
        rebuild runs OUTSIDE the lock exactly like a hot-swap publish,
        so resident tenants' scoring never stalls on another tenant's
        paging; under capacity tiers the stacked shape is unchanged,
        so the compiled program family survives too."""
        self.set_hot_many({tenant: hot})

    def set_hot_many(self, changes: "dict[str, bool]") -> None:
        """Flip several memberships with ONE stack rebuild per affected
        K-group — a paired promotion+eviction costs one concatenation,
        not two."""
        for tenant in changes:
            self._registry(tenant)      # raise early on unknown tenant
        ks: set = set()
        with self._lock:
            for tenant, hot in changes.items():
                if self._hot.get(tenant, True) == hot:
                    continue
                self._hot[tenant] = hot
                k = self._tenant_k.get(tenant)
                if k is not None:
                    ks.add(k)
        for k in sorted(ks):
            self._refresh_stack(k)

    def unload_tenant(self, tenant: str) -> "ModelSnapshot | None":
        """Drop one NON-hot tenant's host-resident snapshot (keeping
        its version counter) — the warm→cold demotion.  Returns the
        snapshot that was active so the caller can checkpoint it."""
        if self.is_hot(tenant):
            raise RuntimeError(
                f"tenant {tenant!r} is stack-resident — evict to warm "
                "before unloading to cold"
            )
        return self._registry(tenant).unload()

    def restore_tenant(self, tenant: str, model: ScoringModel,
                       source: str, version: int) -> ModelSnapshot:
        """Reinstall a cold tenant's checkpointed model at its original
        version — the cold→warm promotion.  Does NOT touch the stack;
        a subsequent set_hot(tenant, True) completes warm→hot."""
        return self._registry(tenant).restore(model, source, version)

    def loaded(self, tenant: str) -> bool:
        return self._registry(tenant).loaded


def tenant_pairs(feats, dsource: str, model: ScoringModel,
                 ip_base: int, word_base: int):
    """One tenant segment's (ip_rows, word_rows) in STACKED coordinates
    plus its pairs-per-event multiplicity: flow events contribute two
    (endpoint, word) pairs each — src block then dst block, min-combined
    at demux (flow_post_lda.scala:227-239) — DNS and other client-keyed
    sources one.  The per-source pair layout comes from the source
    spec's `event_pairs` hook, so a new registered source serves through
    this path with zero edits here.  Row lookups go through the tenant's
    OWN index maps (misses land on the tenant's fallback row), then
    shift by the tenant's base offset into the stacked matrices: the
    tenant-id column realized as an index offset, which is what lets one
    compiled gather serve every tenant."""
    from ..sources import get as get_source

    pairs = get_source(dsource).event_pairs(feats)
    ip = np.concatenate(
        [model.ip_rows(keys) for keys, _ in pairs]
    ) + np.int32(ip_base)
    w = np.concatenate(
        [model.word_rows(words) for _, words in pairs]
    ) + np.int32(word_base)
    return ip.astype(np.int32), w.astype(np.int32), len(pairs)


def demux_scores(scores_seg: np.ndarray, mult: int) -> np.ndarray:
    """Per-event scores from a tenant's pair-score segment: multi-pair
    sources (flow's mult=2 src/dst blocks) min-combine block-wise,
    single-pair sources pass through."""
    if mult == 2:
        n = scores_seg.shape[0] // 2
        return np.minimum(scores_seg[:n], scores_seg[n:])
    if mult > 2:
        n = scores_seg.shape[0] // mult
        return scores_seg.reshape(mult, n).min(axis=0)
    return scores_seg


class FleetScorer:
    """Cross-tenant micro-batching front end over a FleetRegistry.

    `featurizers` maps tenant -> serving featurizer (serving/events.py
    semantics: validate one event, featurize a list, name its dsource).
    `on_batch(tenant, snapshot, feats, scores)` runs per tenant segment
    after each flush — per-tenant refresh loops and flagged-event sinks
    hang off it.  Flush triggers (`fleet_max_batch` /
    `fleet_max_wait_ms`) resolve through the plan layer exactly like
    the single-model scorer's serve_max_batch/serve_max_wait_ms."""

    def __init__(
        self,
        fleet: FleetRegistry,
        featurizers: dict,
        config: "ServingConfig | None" = None,
        metrics: "MetricsEmitter | None" = None,
        on_batch=None,
        journal=None,
        residency=None,
        dynamic: bool = False,
    ) -> None:
        self.fleet = fleet
        self.config = config or ServingConfig()
        # Tiered residency (serving/residency.py): when attached, the
        # worker drains only HBM-hot tenants' lanes; a non-hot tenant's
        # admission requests an async promotion and its events wait in
        # their own bounded lane — the promotion miss shows up as THAT
        # tenant's latency, never as a stall on a resident tenant.
        self._residency = residency
        from ..plans import resolve

        mb, mb_src = resolve("fleet_max_batch", self.config.fleet_max_batch)
        mw, mw_src = resolve("fleet_max_wait_ms",
                             self.config.fleet_max_wait_ms)
        self.metrics = metrics
        self.on_batch = on_batch
        self._journal = getattr(journal, "journal", journal) \
            if journal is not None \
            else (metrics._journal if metrics is not None else None)
        # `dynamic=True` (the replicated-serving replica path,
        # serving/replica.py): the scorer starts with however many
        # tenants the registry knows — possibly zero — and grows lanes
        # at runtime via add_tenant() as the router places tenants on
        # this replica.  The worker simply parks on "no drainable
        # lane" until the first lane appears.
        self._dynamic = dynamic
        self._lanes: dict[str, TenantLane] = {}
        for tenant in fleet.tenants():
            spec = fleet.spec(tenant)
            fz = featurizers.get(tenant)
            if fz is None:
                raise ValueError(f"no featurizer for tenant {tenant!r}")
            self._lanes[tenant] = self._make_lane(spec, fz)
        if not self._lanes and not dynamic:
            raise ValueError("FleetScorer needs at least one tenant")
        # Remember the plan resolution so the dynamic add_tenant path
        # can re-apply the degradation guard as capacity grows.
        self._plan_max_batch = int(mb)
        self._plan_max_batch_src = mb_src
        total_capacity = sum(l.queue_max for l in self._lanes.values())
        if self._lanes and mb_src == "plan" and int(mb) > total_capacity:
            # Same degradation guard as BatchScorer: a plan flush size
            # above the fleet's total admission capacity would make the
            # max_batch trigger unreachable (every flush silently
            # becomes the latency timer) — fall back to the default.
            mb, mb_src = self.config.fleet_max_batch, "default"
        self.max_batch = int(mb)
        self.max_wait_ms = float(mw)
        # Featurize plane (sources/device.py): which engine builds word
        # rows on the flush path, and the pow2 pad floor for the fused
        # dispatch.  Resolved once at construction — engine swaps are a
        # restart, like every other serving engine knob.
        eng, eng_src = resolve_engine(self.config.featurize_engine)
        self._featurize_engine = eng
        fb, fb_src = resolve("featurize_block", self.config.featurize_block)
        self._featurize_block = int(fb)
        # Size-aware engine gate: below the measured break-even a
        # device featurize dispatch LOSES to the vectorized host parse
        # on pure glue (the 0.91x paged A/B), so small segments stay
        # host-side even under a device/fused engine.  Resolved once,
        # like the engine itself.
        if eng == "host":
            be, be_src = 1, "engine"
        else:
            from ..sources.device import resolve_break_even

            be, be_src = resolve_break_even(
                self.config.featurize_break_even)
        self._featurize_break_even = int(be)
        self.plan = {
            "max_batch": {"value": self.max_batch, "source": mb_src},
            "max_wait_ms": {"value": self.max_wait_ms, "source": mw_src},
            "featurize_engine": {"value": eng, "source": eng_src},
            "featurize_block": {"value": self._featurize_block,
                                "source": fb_src},
            "featurize_break_even": {
                "value": self._featurize_break_even, "source": be_src},
        }
        if self.max_batch < 1:
            raise ValueError(f"fleet_max_batch ({self.max_batch}) must "
                             "be >= 1")
        if self.max_wait_ms <= 0:
            raise ValueError(
                f"fleet_max_wait_ms must be > 0, got {self.max_wait_ms}"
            )
        if self.config.device_score_min in (0, "auto"):
            # Pay the one-time host-vs-device calibration at
            # construction, never inside a latency-bounded flush
            # (BatchScorer's contract).
            from ..scoring import dispatch_calibration

            dispatch_calibration()
        self._cond = threading.Condition()
        self._closed = False
        self._force_flush = False
        self._batch_seq = 0
        self._events_scored = 0
        import contextvars

        if self._residency is not None:
            # Promotion completions must wake a worker parked on "no
            # drainable lane"; the waker only touches the condvar, so
            # the pager thread never nests the manager lock inside it.
            self._residency.add_waker(self._wake)
        ctx = contextvars.copy_context()
        self._worker = threading.Thread(
            target=lambda: ctx.run(self._run),
            name="oni-fleet-scorer", daemon=True,
        )
        self._worker.start()

    def _make_lane(self, spec: TenantSpec, fz) -> TenantLane:
        """Validated lane construction — shared by __init__ and the
        dynamic add_tenant path so both enforce the same
        dsource/queue/admission resolution."""
        if getattr(fz, "dsource", None) != spec.dsource:
            raise ValueError(
                f"tenant {spec.tenant!r} declares dsource "
                f"{spec.dsource!r} but its featurizer is "
                f"{getattr(fz, 'dsource', None)!r}"
            )
        lane = TenantLane(
            spec=spec,
            featurizer=fz,
            queue_max=spec.queue_max or self.config.tenant_queue_max,
            admission=spec.admission or self.config.admission,
            threshold=(spec.threshold
                       if spec.threshold is not None
                       else self.config.threshold),
        )
        if lane.queue_max < 1:
            raise ValueError(
                f"tenant {lane.spec.tenant!r} queue_max must be >= 1"
            )
        return lane

    def add_tenant(self, spec: TenantSpec, featurizer) -> None:
        """Grow one admission lane at runtime (dynamic fleets only —
        the replicated-serving router places tenants on a running
        replica).  The tenant must already be registered (and
        published) in the FleetRegistry; the new lane becomes
        drainable on the next take."""
        if not self._dynamic:
            raise RuntimeError(
                "add_tenant on a static FleetScorer — construct with "
                "dynamic=True"
            )
        self.fleet.spec(spec.tenant)    # raise early on unknown tenant
        lane = self._make_lane(spec, featurizer)
        with self._cond:
            if self._closed:
                raise RuntimeError("FleetScorer is closed")
            if spec.tenant in self._lanes:
                raise ValueError(
                    f"tenant {spec.tenant!r} already has a lane"
                )
            self._lanes[spec.tenant] = lane
            # Re-apply the plan-flush degradation guard at the grown
            # capacity: a plan-sourced max_batch above the fleet's
            # total admission capacity is unreachable (silent
            # latency-timer flushes); once capacity covers it, the
            # measured plan value takes effect.
            if self._plan_max_batch_src == "plan":
                total = sum(l.queue_max for l in self._lanes.values())
                if self._plan_max_batch > total:
                    self.max_batch = self.config.fleet_max_batch
                    src = "default"
                else:
                    self.max_batch = self._plan_max_batch
                    src = "plan"
                self.plan["max_batch"] = {
                    "value": self.max_batch, "source": src,
                }
            self._cond.notify_all()

    def _wake(self) -> None:
        with self._cond:
            self._cond.notify_all()

    # -- producer side ------------------------------------------------------

    def submit(self, tenant: str, raw):
        """Enqueue one raw event for `tenant`.  Raises ValueError on a
        malformed event (never enqueued), KeyError on an unknown
        tenant, RuntimeError after close().  A full tenant queue either
        BLOCKS (admission="block" — backpressure, the stall priced into
        `serve.<tenant>.admission_stall_s` and journaled like a
        dataplane edge) or raises AdmissionRejected
        (admission="reject" — load shedding, journaled as
        `{"kind": "admission_reject"}`)."""
        lane = self._lanes.get(tenant)
        if lane is None:
            raise KeyError(
                f"unknown tenant {tenant!r} "
                f"(known: {sorted(self._lanes)})"
            )
        admit = getattr(lane.featurizer, "admit", None)
        if admit is not None:
            # Edge columnar parse: the line splits ONCE here; the flush
            # path reuses the row (device featurize consumes it
            # directly, the host oracle still gets `raw`).
            validated, row = admit(raw)
        else:
            validated = lane.featurizer.validate(raw)
            row = None
        reject_info = None
        with self._cond:
            if self._closed:
                raise RuntimeError("FleetScorer is closed")
            if lane.full_locked() and lane.admission == "reject":
                lane.rejected += 1
                reject_info = (len(lane.pending), lane.queue_max)
            else:
                wait_ns = 0
                t0 = None
                while not self._closed and lane.full_locked():
                    if t0 is None:
                        t0 = time.perf_counter_ns()
                    self._cond.wait()
                if t0 is not None:
                    wait_ns = time.perf_counter_ns() - t0
                    lane.admission_stall_ns += wait_ns
                if self._closed:
                    raise RuntimeError("FleetScorer is closed")
                p = _PendingEvent(validated, time.perf_counter(), row)
                lane.pending.append(p)
                lane.submitted += 1
                depth = len(lane.pending)
                self._cond.notify_all()
        if reject_info is not None:
            depth, capacity = reject_info
            self._journal_safe({
                "kind": "admission_reject", "tenant": tenant,
                "depth": depth, "capacity": capacity,
            })
            if self.metrics is not None:
                self.metrics.recorder.counter(
                    f"serve.{tenant}.admission_rejects"
                ).add(1)
            raise AdmissionRejected(tenant, depth, capacity)
        if wait_ns and self.metrics is not None:
            self.metrics.recorder.histogram(
                f"serve.{tenant}.admission_stall_s"
            ).observe(wait_ns / 1e9)
        if wait_ns:
            # The dataplane's stall-pricing record shape (channel.py
            # _note), on the admission edge: the fleet's ingress
            # backpressure shows up in trace_view next to every other
            # priced stall.
            self._journal_safe({
                "kind": "dataplane", "event": "depth",
                "edge": f"admit.{tenant}", "side": "put",
                "depth": depth, "wait_s": round(wait_ns / 1e9, 6),
            })
        if self._residency is not None:
            # Outside _cond: the residency manager has its own lock and
            # pager thread, and nesting it under the scorer's condvar
            # would deadlock against the promotion waker.  The touch is
            # the LRU/LFU admission signal; a non-hot tenant's touch
            # enqueues an async promotion (idempotent).
            self._residency.note_admission(tenant)
        return p.future

    def flush(self) -> None:
        """Flush whatever is queued without waiting for either trigger
        (no-op on an empty fleet queue — BatchScorer semantics)."""
        with self._cond:
            if any(lane.pending for lane in self._lanes.values()):
                self._force_flush = True
                self._cond.notify_all()

    def close(self, timeout: "float | None" = None) -> bool:
        """Drain every tenant queue, then stop the worker.  With a
        finite timeout, an overlong drain FAILS the still-queued
        futures and returns False instead of abandoning them."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout)
        if not self._worker.is_alive():
            return True
        undrained: list = []
        with self._cond:
            for lane in self._lanes.values():
                undrained.extend(lane.pending)
                lane.pending.clear()
        err = RuntimeError(
            f"FleetScorer.close timed out after {timeout}s with "
            f"{len(undrained)} events undrained"
        )
        for p in undrained:
            p.future._fail(err)
        return False

    @property
    def events_scored(self) -> int:
        with self._cond:
            return self._events_scored

    @property
    def batches_flushed(self) -> int:
        with self._cond:
            return self._batch_seq

    def tenant_stats(self) -> "list[dict]":
        with self._cond:
            return [self._lanes[t].stats_locked()
                    for t in sorted(self._lanes)]

    def tenant_threshold(self, tenant: str) -> float:
        """The resolved suspicion threshold for one tenant (spec
        override, else the fleet config) — the ONE resolution, so
        flagged-event consumers can't drift from the lane's own
        flagged accounting."""
        return self._lanes[tenant].threshold

    # -- worker side --------------------------------------------------------

    def _request_stranded_locked(self) -> None:
        """Caller holds self._cond.  An event admitted while its tenant
        was hot strands if the tenant is evicted before the drain (no
        later admission re-triggers paging): re-request promotion for
        every pending, non-drainable lane.  Lock ordering is safe one
        way — the manager never acquires the scorer's condvar while
        holding its own lock (wakers fire lock-free)."""
        if self._residency is None:
            return
        ready = self._residency.drainable
        stranded = [
            l.spec.tenant for l in self._lanes.values()
            if l.pending and l.spec.tenant not in ready
        ]
        if stranded:
            self._residency.request_promotions(stranded)

    def _drainable_locked(self) -> "list[TenantLane]":
        """Caller holds self._cond.  Lanes the worker may drain NOW:
        pending events whose tenant is HBM-hot (or residency off).
        After close() every lane drains — a still-paging tenant's
        events resolve through the solo fallback instead of blocking
        shutdown.  A paging tenant's lane is simply invisible to the
        flush triggers: its events wait out the promotion in their own
        bounded queue while resident tenants keep flushing."""
        lanes = self._lanes.values()
        if self._residency is None or self._closed:
            return [l for l in lanes if l.pending]
        ready = self._residency.drainable
        # Unmanaged tenants (in the fleet but never registered with
        # the residency manager) keep legacy always-drainable behavior
        # — they can never be promoted, so gating them on the hot set
        # would park their events until shutdown.
        return [l for l in lanes
                if l.pending and (l.spec.tenant in ready
                                  or not self._residency.is_managed(
                                      l.spec.tenant))]

    def _take_batch(self):
        """Block until a flush trigger fires; returns (batch, trigger,
        total_depth_after) where batch is [(tenant, _PendingEvent)]
        drained GLOBALLY OLDEST-FIRST across the drainable tenant
        queues — the no-head-of-line-blocking drain: a bursty tenant
        fills its own bounded queue, but cannot delay an older event of
        another tenant.  Empty batch means shutdown."""
        max_wait_s = self.max_wait_ms / 1e3
        lanes = self._lanes
        with self._cond:
            while not self._closed and not self._drainable_locked():
                self._request_stranded_locked()
                self._cond.wait()
            if not self._drainable_locked():
                return [], "shutdown", 0
            trigger = "close" if self._closed else None
            while trigger is None:
                ready = self._drainable_locked()
                if not ready:
                    # Every drainable lane was taken by a promotion
                    # reversal mid-wait; park again.
                    self._request_stranded_locked()
                    self._cond.wait()
                    if self._closed:
                        trigger = "close"
                    continue
                if self._force_flush:
                    trigger = "flush"
                    break
                total = sum(len(l.pending) for l in ready)
                if total >= self.max_batch:
                    trigger = "max_batch"
                    break
                oldest = min(l.pending[0].t_enqueue for l in ready)
                waited = time.perf_counter() - oldest
                if waited >= max_wait_s:
                    trigger = "max_wait"
                    break
                self._cond.wait(max_wait_s - waited)
                if self._closed:
                    trigger = "close"
            self._force_flush = False
            # K-way merge on enqueue time via a heap of lane heads:
            # O(batch log tenants) while holding the lock every
            # submitter shares — a linear scan per taken event would
            # make admission stalls scale with tenant count.
            heads = [
                (lane.pending[0].t_enqueue, lane.spec.tenant)
                for lane in self._drainable_locked()
            ]
            heapq.heapify(heads)
            batch: list = []
            while heads and len(batch) < self.max_batch:
                _, t = heapq.heappop(heads)
                lane = lanes[t]
                batch.append((t, lane.pending.popleft()))
                if lane.pending:
                    heapq.heappush(
                        heads, (lane.pending[0].t_enqueue, t)
                    )
            depth = sum(len(l.pending) for l in lanes.values())
            self._cond.notify_all()   # release blocked submitters
            return batch, trigger, depth

    def _run(self) -> None:
        while True:
            batch, trigger, depth = self._take_batch()
            if not batch:
                return
            try:
                self._score_batch(batch, trigger, depth)
            except Exception as e:
                # The worker survives anything a batch throws; futures
                # already resolved keep their scores, the rest fail
                # with the cause (BatchScorer contract).
                for _, p in batch:
                    p.future._fail(e)

    def _lane_features(self, lane, items, model):
        """Featurize one tenant segment: device-compiled tables when the
        engine allows it, the model snapshot is known, AND every pending
        event carried an admission-parsed row — otherwise the host
        featurizer (the golden oracle; also the fallback for unlowerable
        vocabularies, which `device_batch` reports as None after
        journaling one `featurize_compile` record)."""
        if (model is not None and self._featurize_engine != "host"
                and len(items) >= self._featurize_break_even):
            rows = [p.row for p in items]
            if all(r is not None for r in rows):
                batch, info = device_batch(
                    lane.featurizer, rows, [p.raw for p in items], model,
                )
                if info is not None:
                    self._journal_safe(info)
                if batch is not None:
                    return batch
        return lane.featurizer([p.raw for p in items])

    @staticmethod
    def _pair_rows(feats, dsource: str, model: ScoringModel,
                   ip_base: int, word_base: int):
        """tenant_pairs through the device featurizer's LUT gather when
        the segment was device-featurized against THIS model (identity
        check: a republish between featurize and score falls back to the
        host oracle rather than gathering stale rows)."""
        if isinstance(feats, DeviceBatch) and feats.model is model:
            return feats.pair_rows(ip_base, word_base)
        return tenant_pairs(feats, dsource, model, ip_base, word_base)

    def _fused_group(self, tenant, stack, feats_by_tenant, tenant_scores,
                     tenant_snaps, tenant_device, failures) -> bool:
        """The fused single-dispatch flush path (featurize+gather+dot in
        one jit program, ops/featurize_kernel.py) for a single-tenant
        K-group whose segment was device-featurized against the stack
        member's model.  Returns False — caller runs the generic packed
        path — whenever the preconditions don't hold; returns True with
        scores demuxed on success (and on failure, which is recorded
        like any other group failure)."""
        feats = feats_by_tenant[tenant]
        member = stack.members[tenant]
        if not (isinstance(feats, DeviceBatch)
                and feats.model is member.model):
            return False
        try:
            from ..scoring.pipeline import fused_featurize_scores

            dev, codes, ip = feats.fused_operands(stack.ip_base[tenant])
            t_g0 = time.perf_counter()
            pair_scores = fused_featurize_scores(
                stack.model, dev, codes, ip,
                word_base=stack.word_base[tenant],
                block=self._featurize_block,
            )
            if self.metrics is not None:
                rec = self.metrics.recorder
                rec.histogram("serve.device_score_ms").observe(
                    (time.perf_counter() - t_g0) * 1e3
                )
                rec.counter("serve.device_events").add(
                    feats.num_raw_events
                )
            tenant_scores[tenant] = demux_scores(
                pair_scores, dev.pairs_per_event
            )
            tenant_snaps[tenant] = member
            tenant_device[tenant] = True
        except Exception as e:
            failures.setdefault(tenant, e)
        return True

    def _score_batch(self, batch, trigger: str, depth: int) -> None:
        cfg = self.config
        t0 = time.perf_counter()
        # Segment the drained batch per tenant (submit order preserved
        # inside each segment), then group tenants by topic count K:
        # one stacked snapshot — one compiled dispatch — per group.
        segments: dict[str, list] = {}
        for tenant, p in batch:
            segments.setdefault(tenant, []).append(p)
        stacks: dict[int, "StackedSnapshot | None"] = {}
        tenant_scores: dict[str, np.ndarray] = {}
        tenant_snaps: dict = {}
        failures: dict[str, Exception] = {}
        groups: dict[int, list] = {}
        solo: list = []
        feats_by_tenant: dict = {}
        # Each tenant's K is read ONCE here and reused at demux/emit:
        # a concurrent publish may change a tenant's K mid-flush, and a
        # re-read after scoring would look up a stack this flush never
        # grabbed (KeyError failing OTHER tenants' futures too).
        tenant_ks: dict[str, int] = {}
        for tenant, items in segments.items():
            lane = self._lanes[tenant]
            try:
                k = self.fleet.tenant_k(tenant)
                tenant_ks[tenant] = k
                if k not in stacks:
                    try:
                        stacks[k] = self.fleet.stack(k)
                    except RuntimeError:
                        # No hot member in the K-group at all (every
                        # tenant paged out) — the group scores solo.
                        stacks[k] = None
                stack = stacks[k]
                member = (stack.members.get(tenant)
                          if stack is not None else None)
                feats = self._lane_features(
                    lane, items,
                    member.model if member is not None else None,
                )
                if feats.num_raw_events != len(items):
                    raise RuntimeError(
                        f"tenant {tenant!r} featurizer returned "
                        f"{feats.num_raw_events} rows for "
                        f"{len(items)} events"
                    )
                feats_by_tenant[tenant] = feats
                if member is not None:
                    groups.setdefault(k, []).append(tenant)
                else:
                    # Residency miss at scoring time (tenant evicted
                    # between take and score, or a close-time drain of
                    # a still-paging lane): score against the tenant's
                    # OWN registry snapshot.  The gather-dot is per-row
                    # arithmetic, so on the default f32 stack solo
                    # scores are bit-identical to packed ones.  Under
                    # stack_precision="bf16" the solo path scores at
                    # FULL precision (the registry model carries no
                    # storage marker), so it agrees with the packed
                    # path within bf16's documented tolerance, not
                    # bitwise — strictly more accurate, never wrong.
                    solo.append(tenant)
            except Exception as e:
                # Tenant-scoped failure isolation: a tenant whose
                # featurization (or stack lookup) fails takes down ITS
                # futures only — the rest of the flush still scores.
                failures[tenant] = e
        dispatches = 0
        device_dispatches = 0
        tenant_device: dict[str, bool] = {}
        for k, group in sorted(groups.items()):
            stack = stacks[k]
            if (self._featurize_engine == "fused" and len(group) == 1
                    and self._fused_group(group[0], stack,
                                          feats_by_tenant, tenant_scores,
                                          tenant_snaps, tenant_device,
                                          failures)):
                dispatches += 1
                device_dispatches += 1
                continue
            try:
                parts = []
                mults = {}
                for tenant in group:
                    ip, w, mult = self._pair_rows(
                        feats_by_tenant[tenant],
                        self._lanes[tenant].spec.dsource,
                        stack.members[tenant].model,
                        stack.ip_base[tenant],
                        stack.word_base[tenant],
                    )
                    parts.append((tenant, ip, w))
                    mults[tenant] = mult
                ip_all = np.concatenate([ip for _, ip, _ in parts])
                w_all = np.concatenate([w for _, _, w in parts])
                # ONE dispatch for the whole K-group: every tenant's
                # pairs ride the same padded compiled program.  The
                # device-path decision is made on the packed PAIR
                # count, not the flush's event count (flow events pack
                # two pairs each, and each K group decides
                # independently); device dispatches feed the serve
                # roofline histograms per GROUP — exact wall, exact
                # events — so a flush mixing device and host groups
                # can never price host scoring as device dispatches.
                is_device = use_device_path(
                    len(ip_all), cfg.device_score_min
                )
                t_g0 = time.perf_counter()
                pair_scores = batched_scores(
                    stack.model, ip_all, w_all, cfg.device_score_min
                )
                dispatches += 1
                if is_device:
                    device_dispatches += 1
                    if self.metrics is not None:
                        rec = self.metrics.recorder
                        rec.histogram("serve.device_score_ms").observe(
                            (time.perf_counter() - t_g0) * 1e3
                        )
                        rec.counter("serve.device_events").add(sum(
                            feats_by_tenant[t].num_raw_events
                            for t in group
                        ))
                off = 0
                for tenant, ip, _ in parts:
                    seg = pair_scores[off:off + len(ip)]
                    off += len(ip)
                    tenant_scores[tenant] = demux_scores(
                        seg, mults[tenant]
                    )
                    tenant_snaps[tenant] = stack.members[tenant]
                    tenant_device[tenant] = is_device
            except Exception as e:
                for tenant in group:
                    failures.setdefault(tenant, e)
        # Solo fallback dispatches — one per missed tenant, each on the
        # tenant's own (unstacked) model.
        for tenant in solo:
            try:
                try:
                    snap = self.fleet.active(tenant)
                except RuntimeError:
                    if self._residency is None:
                        raise
                    # Checkpoint-cold tenant drained NOW (close-time
                    # drain, or a demotion racing this flush): read
                    # the checkpoint through without a tier change —
                    # the events score against the exact unloaded
                    # model at its preserved version instead of
                    # failing.
                    snap = self._residency.read_through(tenant)
                ip, w, mult = tenant_pairs(
                    feats_by_tenant[tenant],
                    self._lanes[tenant].spec.dsource,
                    snap.model, 0, 0,
                )
                is_device = use_device_path(
                    len(ip), cfg.device_score_min
                )
                t_g0 = time.perf_counter()
                pair_scores = batched_scores(
                    snap.model, ip, w, cfg.device_score_min
                )
                dispatches += 1
                if is_device:
                    device_dispatches += 1
                    if self.metrics is not None:
                        rec = self.metrics.recorder
                        rec.histogram("serve.device_score_ms").observe(
                            (time.perf_counter() - t_g0) * 1e3
                        )
                        rec.counter("serve.device_events").add(
                            feats_by_tenant[tenant].num_raw_events
                        )
                tenant_scores[tenant] = demux_scores(pair_scores, mult)
                tenant_snaps[tenant] = snap
                tenant_device[tenant] = is_device
            except Exception as e:
                failures.setdefault(tenant, e)
        t1 = time.perf_counter()
        # Demux: resolve per-tenant futures against the snapshot the
        # segment actually scored on (version isolation: tenant B's
        # futures carry B's version even while A hot-swaps or pages).
        flagged: dict[str, int] = {}
        for tenant, items in segments.items():
            if tenant in failures:
                for p in items:
                    p.future._fail(failures[tenant])
                continue
            scores = tenant_scores[tenant]
            version = tenant_snaps[tenant].version
            for p, s in zip(items, scores):
                p.future._resolve(float(s), version)
            flagged[tenant] = int(
                np.sum(scores < self._lanes[tenant].threshold)
            )
        t2 = time.perf_counter()
        scored_n = sum(
            len(items) for t, items in segments.items()
            if t not in failures
        )
        with self._cond:
            seq = self._batch_seq
            self._batch_seq += 1
            self._events_scored += scored_n
            for tenant, items in segments.items():
                if tenant in failures:
                    continue
                self._lanes[tenant].scored += len(items)
                self._lanes[tenant].flagged += flagged[tenant]
        self._journal_safe({
            "kind": "demux", "batch": seq, "events": len(batch),
            "tenants": len(segments), "segments": dispatches,
            "residency_misses": len(solo),
            "featurize": self._featurize_engine,
            "featurize_device_tenants": sum(
                isinstance(f, DeviceBatch)
                for f in feats_by_tenant.values()
            ),
            "score_ms": round((t1 - t0) * 1e3, 3),
            "demux_ms": round((t2 - t1) * 1e3, 3),
        })
        # Per-tenant consumers + metrics, then the aggregate record.
        # "device" only when at least one K-group's packed dispatch
        # actually took the device path (metrics._count feeds the
        # device roofline histogram off this label, flush-level records
        # only).
        score_s = t1 - t0
        n = len(batch)
        scorer_label = "device" if device_dispatches else "host"
        for tenant, items in sorted(segments.items()):
            if tenant in failures:
                self._emit_safe({
                    "stage": "serve", "tenant": tenant, "batch": seq,
                    "events": len(items),
                    "error": repr(failures[tenant]), "trigger": trigger,
                })
                continue
            k = tenant_ks[tenant]
            snap = tenant_snaps[tenant]
            if self.on_batch is not None:
                try:
                    self.on_batch(tenant, snap, feats_by_tenant[tenant],
                                  tenant_scores[tenant])
                except Exception as e:
                    # Consumer failures never take down scoring.
                    self._emit_safe({
                        "stage": "serve", "tenant": tenant,
                        "batch": seq, "on_batch_error": repr(e),
                    })
            oldest = items[0].t_enqueue
            stack = stacks.get(k)
            self._emit_safe({
                "stage": "serve", "tenant": tenant, "batch": seq,
                "events": len(items), "trigger": trigger,
                "model_version": snap.version,
                # None = a solo (residency-miss) dispatch: the tenant's
                # segment never rode a stacked program this flush.
                "stack_version": (
                    stack.stack_version
                    if stack is not None and tenant in stack.members
                    else None
                ),
                # The tenant's OWN segment's dispatch decision — in a
                # mixed-K flush a host-scored tenant must not be
                # labeled by another group's device dispatch.
                "scorer": ("device" if tenant_device.get(tenant)
                           else "host"),
                "latency_ms": round((t1 - oldest) * 1e3, 3),
                "queue_wait_ms": round((t0 - oldest) * 1e3, 3),
                "score_ms": round(score_s * 1e3, 3),
                "demux_ms": round((t2 - t1) * 1e3, 3),
                "flagged": flagged[tenant],
            })
        oldest_all = batch[0][1].t_enqueue
        self._emit_safe({
            "stage": "serve", "batch": seq, "events": n,
            "tenants": len(segments), "segments": dispatches,
            "segments_device": device_dispatches,
            "trigger": trigger, "scorer": scorer_label,
            "latency_ms": round((t1 - oldest_all) * 1e3, 3),
            "queue_wait_ms": round((t0 - oldest_all) * 1e3, 3),
            "score_ms": round(score_s * 1e3, 3),
            "demux_ms": round((t2 - t1) * 1e3, 3),
            "events_per_sec": round(n / score_s, 1) if score_s else None,
            "queue_depth": depth,
            "flagged": sum(flagged.values()),
        })

    # -- telemetry sinks ----------------------------------------------------

    def _emit_safe(self, record: dict) -> None:
        if self.metrics is None:
            return
        try:
            self.metrics.emit(record)
        except Exception as e:
            import sys

            print(f"fleet metrics emit failed: {e!r}", file=sys.stderr)

    def _journal_safe(self, record: dict) -> None:
        if self._journal is None:
            return
        try:
            self._journal.append(record)
        except Exception as e:
            import sys

            print(f"fleet journal append failed: {e!r}", file=sys.stderr)
