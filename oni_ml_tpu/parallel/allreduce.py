"""Explicit cross-process collectives for distributed EM.

PR 9's sparse engine (and every E-step engine before it) is a
single-process program; the old multi-host story ran ONE global-mesh
SPMD program spanning processes, which the CPU runtime cannot execute
at all (`XlaRuntimeError: Multiprocess computations aren't implemented
on the CPU backend`) and which forced the sparse engine back to dense.
The restructure (ROADMAP item 1): each process runs the full E-step
*host-locally* over its document shards (parallel/shard_plan.py), and
the [V, K] beta sufficient statistics, the alpha suff-stats scalar, and
the ELBO scalar cross processes through THIS layer — an explicit,
pluggable allreduce in the spirit of DrJAX's MapReduce-as-JAX-
primitives (arXiv:2403.07128), instead of collectives hidden inside a
sharded training program.

Transports (``Collective.transport``):

- ``local`` — process_count == 1: every op is the identity.
- ``psum`` — a real multi-device runtime (TPU pods): rank payloads are
  committed into a process-sharded global array and a jitted identity
  with replicated ``out_shardings`` lowers the gather onto ICI/DCN.
- ``kvring`` — the portable process-group ring for CPU multi-process:
  a classic ring allgather over the ``jax.distributed`` coordination
  client's key-value store, chunked (``max_chunk_bytes``) and bounded
  (``timeout_s``, with peer-failure polling between wait slices).

The REDUCTION is deliberately transport-independent and host-side:
gather the per-rank partials, then ``tree_combine`` — a fixed pairwise
association tree in shard order.  Because the tree is anchored to the
corpus-derived shard plan (not the process count), the reduced f32
bytes are identical on every rank AND invariant to how many processes
computed the partials — the byte-identical-artifacts contract of
tests/test_multihost.py.

Failure semantics (the PR 4 ``BackendLost``/rc=3 machinery): a rank
that fails mid-stage posts a failure key (``Collective.fail``); every
peer's blocked wait polls it between slices and raises ``PeerFailure``
("failed on another rank") — a ``BackendLost`` subclass, so
``ml_ops`` exits with the structured rc=3 payload instead of a raw
XLA traceback.  A peer that dies without posting (SIGKILL) surfaces as
a bounded ``PeerFailure`` timeout instead of a hang.

Every data-plane op is priced like a dataplane stall: the wait rides an
``allreduce.wait`` span and a ``{"kind": "allreduce"}`` journal record
carries per-op bytes, rounds, and wall.
"""

from __future__ import annotations

import functools
import os
import pickle
import time

import numpy as np

from ..telemetry.heartbeat import BackendLost
from ..telemetry.spans import current_recorder, maybe_span


class PeerFailure(BackendLost):
    """A collective op observed another rank's failure (or a peer's
    death via timeout).  Subclasses BackendLost so the runner's
    structured rc=3 exit path (runner/ml_ops.py main) applies."""


# Per-KV-value chunk bound (bytes actually stored): the coordination
# service is a control-plane store with a 4 MiB gRPC message cap, so
# bulk payloads ship in bounded slices instead of one arbitrarily
# large message.  Values ride the client's *_bytes KV variants, which
# under the pinned jaxlib 0.9.0 deliver a value that arrives while the
# get is BLOCKED — the allreduce wait pattern — reliably (the
# multihost and allreduce suites run on them).
DEFAULT_MAX_CHUNK_BYTES = 2 << 20
# Bound on any single collective wait.  Ranks run EM iterations in
# lockstep, so legitimate skew is one iteration's wall-clock variance;
# the default leaves room for a slow host without turning a dead peer
# into an indefinite hang.  ONI_ML_TPU_ALLREDUCE_TIMEOUT_S overrides
# (the failure-injection tests tighten it).
DEFAULT_TIMEOUT_S = 600.0
# Wait-slice length: between slices the blocked rank polls the failure
# key, so a cooperative peer failure surfaces within one slice.
POLL_SLICE_S = 2.0
# How long a rank that has ALREADY posted its own failure keeps trying
# to complete in-flight collectives (letting the outcome barrier drain
# cleanly when peers are still forwarding) before giving up: without
# this cap, at >= 3 ranks the failed rank can wait the FULL collective
# timeout for ring blocks its (already-aborted) peers will never send.
FAIL_DRAIN_S = 5.0


def _bf16_pack(arr: np.ndarray) -> np.ndarray:
    """f32/f64 -> bf16 bit pattern as uint16, round-to-nearest-even —
    the standard truncate-with-carry trick on the f32 view.  Used to
    HALVE the kvring wire bytes of a float payload; accumulation after
    the matching unpack stays f32, so only the per-rank partials lose
    mantissa, never the reduction arithmetic."""
    a = np.asarray(arr, np.float32)
    # Round-trip through flat 1-d: .view() is shape-preserving only on
    # contiguous data, and ascontiguousarray would silently promote a
    # 0-d scalar (the likelihood/alpha suff-stats) to shape (1,).
    u = np.ascontiguousarray(a).reshape(-1).view(np.uint32)
    rounded = ((u + np.uint32(0x7FFF) + ((u >> np.uint32(16))
                                         & np.uint32(1)))
               >> np.uint32(16)).astype(np.uint16)
    # NaN guard: the carry add wraps high-payload NaN bit patterns
    # into +/-0.0 — a diverged rank's suff-stats must stay NaN on the
    # wire so the fit fails loudly, exactly like the f32 wire would.
    is_nan = ((u & np.uint32(0x7F800000)) == np.uint32(0x7F800000)) \
        & ((u & np.uint32(0x007FFFFF)) != 0)
    if is_nan.any():
        quiet = (((u >> np.uint32(16)) & np.uint32(0x8000))
                 | np.uint32(0x7FC0)).astype(np.uint16)
        rounded = np.where(is_nan, quiet, rounded)
    return rounded.reshape(a.shape)


def _bf16_unpack(u16: np.ndarray) -> np.ndarray:
    """uint16 bf16 bit pattern -> f32 (exact: bf16 embeds in f32)."""
    u16 = np.asarray(u16)
    return ((u16.reshape(-1).astype(np.uint32) << np.uint32(16))
            .view(np.float32).reshape(u16.shape))


# Wire marker for a bf16-compressed array inside a pickled payload.
# Self-describing per VALUE, so every rank decompresses whatever
# arrives identically — the reduced bytes stay rank-identical even if
# (misconfigured) ranks disagree on the compression knob.
_BF16_TAG = "__oni_bf16__"


def _compress_named(named: dict, precision: str) -> dict:
    if precision != "bf16":
        return named
    return {
        k: (_BF16_TAG, _bf16_pack(v))
        if np.asarray(v).dtype.kind == "f" else v
        for k, v in named.items()
    }


def _decompress_named(named: dict) -> dict:
    out = {}
    for k, v in named.items():
        if isinstance(v, tuple) and len(v) == 2 and v[0] == _BF16_TAG:
            out[k] = _bf16_unpack(v[1])
        else:
            out[k] = v
    return out


def tree_combine(parts):
    """Deterministic pairwise-tree sum of a list of pytrees of arrays
    (np or jnp): adjacent pairs combine level by level, an odd tail
    carries up unchanged.  For a contiguous, power-of-two-aligned block
    of leaves this reproduces the canonical tree's subtree node exactly
    — the property the cross-rank reduction leans on for byte-identical
    results across rank counts (see parallel/shard_plan.py)."""
    parts = list(parts)
    if not parts:
        raise ValueError("tree_combine of no parts")
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            a, b = parts[i], parts[i + 1]
            if isinstance(a, dict):
                nxt.append({k: a[k] + b[k] for k in a})
            else:
                nxt.append(a + b)
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


@functools.lru_cache(maxsize=8)
def _psum_programs(nprocs: int):
    """(row_sharding, jitted identity-reshard) for the psum transport,
    cached per process count: the devices of a process are fixed for
    its lifetime, and rebuilding the mesh + a fresh jit wrapper per
    call would re-trace the gather on every EM iteration of the one op
    sitting on the distributed critical path."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = np.asarray(jax.devices())
    mesh = Mesh(devs.reshape(nprocs, -1), ("proc", "local"))
    row = NamedSharding(mesh, PartitionSpec("proc"))
    rep = NamedSharding(mesh, PartitionSpec())
    # jit entry point registered in telemetry/roofline.py
    # HARVEST_COVERAGE (control-plane collective, not a dispatch phase).
    return row, jax.jit(lambda x: x, out_shardings=rep)


def _psum_gather(local: np.ndarray, nprocs: int) -> np.ndarray:
    """[*shape] per-rank payload -> [nprocs, *shape] stacked gather over
    the runtime's own interconnect: the local row commits into a
    process-sharded global array and a jitted identity with replicated
    out_shardings lowers the reshard to an all-gather riding ICI/DCN
    (the DrJAX pattern).  Single-process this degenerates to a copy —
    which is how the CPU suite and the dryrun exercise the code path;
    multi-host numbers are projections until the next TPU grant."""
    import jax

    local = np.asarray(local)
    # Bit-exact transport for 8-byte dtypes: without x64 enabled, jax
    # canonicalizes float64/int64 commits down to 32 bits — which would
    # silently round the f64 gamma merge on the pod path while the
    # kvring transport (pickle) preserved it.  View as uint32 pairs,
    # gather, view back: the gather moves bytes, never arithmetic.
    wide_dtype = local.dtype if local.dtype.itemsize == 8 else None
    if wide_dtype is not None:
        if local.ndim == 0:
            raise ValueError(
                "psum transport cannot bit-cast a 0-d 8-byte scalar; "
                "reshape it to (1,) first"
            )
        local = np.ascontiguousarray(local).view(np.uint32)
    row, gather = _psum_programs(nprocs)
    glob = jax.make_array_from_process_local_data(row, local[None, ...])
    gathered = np.asarray(gather(glob))
    if wide_dtype is not None:
        gathered = gathered.view(wide_dtype)
    return gathered


class Collective:
    """One process's handle on the run's process group.

    Every method is COLLECTIVE: all ranks must call the same ops in the
    same order (the key-sequence counter advances in lockstep).  The
    control plane (broadcast/allgather of small pickled objects,
    barriers, failure relay) always rides the coordination client's KV
    store — it exists on every multi-process backend, CPU included;
    only the bulk array plane switches transports.
    """

    def __init__(self, client=None, rank: "int | None" = None,
                 nprocs: "int | None" = None, *,
                 transport: "str | None" = None,
                 timeout_s: "float | None" = None,
                 max_chunk_bytes: int = DEFAULT_MAX_CHUNK_BYTES,
                 namespace: str = "oni/ar",
                 payload_precision: "str | None" = None):
        import jax

        self.rank = jax.process_index() if rank is None else rank
        self.num_processes = (
            jax.process_count() if nprocs is None else nprocs
        )
        if client is None and self.num_processes > 1:
            from jax._src import distributed

            client = distributed.global_state.client
            if client is None:
                raise RuntimeError(
                    "multi-process collective without an initialized "
                    "jax.distributed client — call "
                    "parallel.initialize_distributed() first"
                )
        self._client = client
        env_t = os.environ.get("ONI_ML_TPU_ALLREDUCE_TIMEOUT_S", "")
        self.timeout_s = (
            float(env_t) if env_t
            else (DEFAULT_TIMEOUT_S if timeout_s is None else timeout_s)
        )
        self.max_chunk_bytes = max_chunk_bytes
        self._ns = namespace
        self._seq = 0
        if transport is None:
            transport = os.environ.get("ONI_ML_TPU_ALLREDUCE", "")
        if not transport:
            if self.num_processes == 1:
                transport = "local"
            else:
                transport = (
                    "kvring" if jax.default_backend() == "cpu" else "psum"
                )
        if transport not in ("local", "kvring", "psum"):
            raise ValueError(
                f"unknown allreduce transport {transport!r}: expected "
                "local, kvring, or psum"
            )
        self.transport = transport
        if payload_precision is None:
            payload_precision = os.environ.get(
                "ONI_ML_TPU_ALLREDUCE_PRECISION", "") or "f32"
        if payload_precision not in ("f32", "bf16"):
            raise ValueError(
                f"unknown allreduce payload_precision "
                f"{payload_precision!r}: expected f32 or bf16"
            )
        # Default WIRE precision for float payloads on the kvring
        # transport: "bf16" halves the per-iteration KV-ring bytes
        # (round-to-nearest-even pack, exact f32 unpack, f32
        # accumulation in the reduction tree).  Per-call overrides let
        # the trainer compress the bulk suff-stats while the f64 gamma
        # merge stays exact.  The psum transport ignores it: its
        # payloads ride ICI as device arrays, not pickled KV chunks.
        self.payload_precision = payload_precision
        self._failed_reason: "str | None" = None
        # Process-local accounting (bench distributed_em reads it):
        # cumulative data-plane ops, payload bytes out/in, wall.
        self.stats = {"ops": 0, "bytes_out": 0, "bytes_in": 0,
                      "wall_s": 0.0}

    def applied_precision(self, precision: "str | None" = None) -> str:
        """The wire precision an allgather with this `precision`
        request would ACTUALLY use — the one rule, shared by the
        data-plane op and every provenance record: bf16 compresses
        only multi-process kvring payloads (psum rides ICI as device
        arrays; a single process never touches the wire at all)."""
        if precision is None:
            precision = self.payload_precision
        return ("bf16" if precision == "bf16"
                and self.transport == "kvring"
                and self.num_processes > 1 else "f32")

    # -- failure relay ----------------------------------------------------

    def fail(self, reason: str) -> None:
        """Post this rank's failure for every peer's wait-slice poll to
        observe.  Best-effort (the process is on its way out).  Also
        marks THIS collective as failed, which caps its own later waits
        at FAIL_DRAIN_S — a rank that already failed must not block the
        full timeout on barriers its peers have abandoned."""
        self._failed_reason = str(reason)[:500]
        if self._client is None:
            return
        try:
            self._client.key_value_set_bytes(
                self._ns + "/fail",
                pickle.dumps((self.rank, str(reason)[:500])),
                allow_overwrite=True,
            )
        except Exception:
            pass

    def check_peer_failure(self) -> None:
        """Raise PeerFailure if any OTHER rank posted a failure."""
        if self._client is None:
            return
        try:
            raw = self._client.blocking_key_value_get_bytes(
                self._ns + "/fail", 1
            )
        except Exception:
            return
        rank, reason = pickle.loads(raw)
        if rank == self.rank:
            return
        raise PeerFailure(
            f"distributed run failed on another rank "
            f"(rank {rank}: {reason})"
        )

    # -- KV primitives ----------------------------------------------------

    def _next_base(self, tag: str) -> str:
        self._seq += 1
        return f"{self._ns}/{self._seq}-{tag}"

    def _kv_get(self, key: str) -> bytes:
        """Blocking get with a bounded deadline and peer-failure polling
        between wait slices — the coordination-client health barrier of
        the failure-relay contract."""
        budget = (
            min(self.timeout_s, FAIL_DRAIN_S)
            if self._failed_reason is not None else self.timeout_s
        )
        deadline = time.monotonic() + budget
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                if self._failed_reason is not None:
                    raise PeerFailure(
                        "abandoning collective drain after this rank's "
                        f"own failure: {self._failed_reason}"
                    )
                raise PeerFailure(
                    f"collective wait for {key!r} timed out after "
                    f"{self.timeout_s:.0f}s — a peer rank is stalled or "
                    "died without posting a failure"
                )
            slice_ms = max(1, int(min(POLL_SLICE_S, remaining) * 1000))
            try:
                return self._client.blocking_key_value_get_bytes(
                    key, slice_ms)
            except Exception as e:
                if "DEADLINE_EXCEEDED" not in str(e):
                    raise
                self.check_peer_failure()

    def _put_chunked(self, key: str, data: bytes) -> None:
        """Publish `data` under `key` in bounded chunks; the
        chunk-count marker lands LAST so a reader never observes a
        partial value."""
        n = -(-len(data) // self.max_chunk_bytes) if data else 0
        for i in range(n):
            self._client.key_value_set_bytes(
                f"{key}/c{i}",
                data[i * self.max_chunk_bytes:(i + 1) * self.max_chunk_bytes],
            )
        self._client.key_value_set_bytes(f"{key}/n", str(n).encode())

    def _get_chunked(self, key: str, delete: bool = False) -> bytes:
        n = int(self._kv_get(f"{key}/n"))
        parts = [self._kv_get(f"{key}/c{i}") for i in range(n)]
        if delete:
            # Single-reader keys (ring messages): the consumer retires
            # them so the coordination service's store stays bounded.
            try:
                for i in range(n):
                    self._client.key_value_delete(f"{key}/c{i}")
                self._client.key_value_delete(f"{key}/n")
            except Exception:
                pass
        return b"".join(parts)

    # -- control plane ----------------------------------------------------

    def broadcast_obj(self, obj, tag: str):
        """Coordinator (rank 0) -> all: the stage-decision primitive.
        Works on every backend (pure KV), unlike the old XLA
        broadcast_one_to_all that required cross-process computations."""
        if self.num_processes == 1:
            return obj
        base = self._next_base(tag)
        if self.rank == 0:
            self._put_chunked(base, pickle.dumps(obj, protocol=4))
            return obj
        return pickle.loads(self._get_chunked(base))

    def allgather_obj(self, obj, tag: str) -> list:
        """Every rank's object, in rank order, on every rank."""
        if self.num_processes == 1:
            return [obj]
        payload = pickle.dumps(obj, protocol=4)
        blocks, *_ = self._ring_allgather(payload, tag)
        return [pickle.loads(b) for b in blocks]

    def barrier(self, tag: str) -> None:
        """All ranks reach this point (with failure relay while
        waiting); returns when every rank has."""
        self.allgather_obj(True, tag)

    # -- data plane -------------------------------------------------------

    def _ring_allgather(self, payload: bytes, tag: str):
        """Classic ring allgather over the KV store: P-1 rounds, each
        rank forwarding one block per round to its successor (a
        single-reader key, retired after the read).  Returns
        (blocks_by_rank, bytes_out, bytes_in, rounds)."""
        base = self._next_base(tag)
        p, r = self.num_processes, self.rank
        blocks: list = [None] * p
        blocks[r] = payload
        bytes_out = bytes_in = 0
        for s in range(p - 1):
            send = (r - s) % p
            self._put_chunked(f"{base}/s{s}/r{r}", blocks[send])
            bytes_out += len(blocks[send])
            got = self._get_chunked(
                f"{base}/s{s}/r{(r - 1) % p}", delete=True
            )
            blocks[(r - s - 1) % p] = got
            bytes_in += len(got)
        return blocks, bytes_out, bytes_in, p - 1

    def allgather_arrays(self, named: "dict[str, np.ndarray]",
                         tag: str, *,
                         precision: "str | None" = None
                         ) -> "list[dict[str, np.ndarray]]":
        """The bulk primitive: every rank's named-array dict, in rank
        order, on every rank.  Journaled as {"kind": "allreduce"} with
        per-op bytes/rounds/wall, the wait priced under an
        allreduce.wait span like a dataplane stall.

        `precision` overrides the collective's payload_precision for
        this op.  Under "bf16" on the kvring transport, float arrays
        ship as round-to-nearest-even bf16 bit patterns (half the wire
        bytes) and EVERY rank — including the sender reading its own
        block — unpacks them to f32 before use, so the reduction sees
        identical f32 inputs everywhere and the reduced bytes stay
        rank-identical.  Non-float arrays and non-kvring transports
        pass through untouched."""
        named = {k: np.asarray(v) for k, v in named.items()}
        applied = self.applied_precision(precision)
        if self.num_processes == 1:
            return [named]
        t0 = time.monotonic()
        with maybe_span("allreduce.wait", tag=tag,
                        transport=self.transport):
            if self.transport == "psum":
                stacked = {
                    k: _psum_gather(v, self.num_processes)
                    for k, v in named.items()
                }
                out = [
                    {k: stacked[k][p] for k in stacked}
                    for p in range(self.num_processes)
                ]
                bytes_out = sum(v.nbytes for v in named.values())
                bytes_in = bytes_out * (self.num_processes - 1)
                rounds = 1
            else:
                payload = pickle.dumps(
                    _compress_named(named, applied), protocol=4
                )
                blocks, bytes_out, bytes_in, rounds = (
                    self._ring_allgather(payload, tag)
                )
                out = [_decompress_named(pickle.loads(b))
                       for b in blocks]
        wall = time.monotonic() - t0
        self.stats["ops"] += 1
        self.stats["bytes_out"] += bytes_out
        self.stats["bytes_in"] += bytes_in
        self.stats["wall_s"] += wall
        rec = current_recorder()
        if rec is not None:
            rec.journal_record({
                "kind": "allreduce",
                "tag": tag,
                "transport": self.transport,
                "nprocs": self.num_processes,
                "rounds": rounds,
                "precision": applied,
                "bytes_out": bytes_out,
                "bytes_in": bytes_in,
                "wall_s": round(wall, 6),
            })
        return out


def reduce_partials(coll: Collective, plan, shard_stats: "dict[int, dict]",
                    tag: str, *,
                    precision: "str | None" = None
                    ) -> "dict[str, np.ndarray]":
    """The sufficient-statistics allreduce: per-shard partial stats in,
    globally-reduced stats out — identical bytes on every rank, and
    invariant to the rank count for a fixed shard plan.

    `shard_stats` maps this rank's OWNED shard indices to named-array
    dicts.  Aligned plans (rank runs are canonical tree nodes) exchange
    one pre-combined subtree root per rank; unaligned plans exchange
    per-shard partials so the canonical shard-order tree can still be
    applied identically everywhere.

    `precision="bf16"` compresses the wire payload (kvring transport:
    half the bytes per EM iteration) with f32 accumulation after the
    unpack; the reduced bytes are still rank-identical and
    rank-count-invariant — just bf16-tolerance vs the f32 wire, not
    bit-equal to it (the PR 9 sparse-engine precision contract)."""
    owned = sorted(shard_stats)
    if plan.aligned:
        local = tree_combine([shard_stats[s] for s in owned])
        gathered = coll.allgather_arrays(local, tag,
                                         precision=precision)
        return tree_combine(gathered)
    flat: "dict[str, np.ndarray]" = {}
    for s in owned:
        for k, v in shard_stats[s].items():
            flat[f"{s}:{k}"] = v
    gathered = coll.allgather_arrays(flat, tag, precision=precision)
    by_shard: "dict[int, dict]" = {}
    for g in gathered:
        for key, v in g.items():
            s, name = key.split(":", 1)
            by_shard.setdefault(int(s), {})[name] = v
    return tree_combine([by_shard[s] for s in sorted(by_shard)])


_COLLECTIVE: "Collective | None" = None


def get_collective() -> Collective:
    """The process-wide collective (one per process so the KV key
    sequence stays in lockstep across every consumer: the trainer's
    suff-stats reduce, the runner's stage decisions, the streaming
    trainer's lambda reduce)."""
    global _COLLECTIVE
    if _COLLECTIVE is None:
        _COLLECTIVE = Collective()
    return _COLLECTIVE


def _reset_collective_for_tests() -> None:
    global _COLLECTIVE
    _COLLECTIVE = None
