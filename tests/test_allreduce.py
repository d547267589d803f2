"""Unit suite for the distributed-EM collective layer: shard plans
(parallel/shard_plan.py), the deterministic tree reduction, and the
KV-ring transport (parallel/allreduce.py) driven in-process over a fake
coordination-client KV store — chunking, uneven rank counts, payload
asymmetry, failure relay, and timeouts, all without spawning a cluster
(tests/test_multihost.py owns the real 2-process paths).
"""

import pickle
import threading
import time

import numpy as np
import pytest

from oni_ml_tpu.parallel.allreduce import (
    Collective,
    PeerFailure,
    reduce_partials,
    tree_combine,
)
from oni_ml_tpu.parallel.shard_plan import (
    DEFAULT_EM_SHARDS,
    ShardPlan,
    plan_shards,
    resolve_em_shards,
)


# ---------------------------------------------------------------------------
# Shard plans
# ---------------------------------------------------------------------------


def test_plan_partitions_exactly():
    for d in (0, 1, 7, 80, 1001):
        for s in (1, 2, 8, 16):
            plan = plan_shards(d, 1, s)
            assert plan.num_shards == s
            assert plan.bounds[0][0] == 0
            assert plan.bounds[-1][1] == d
            for (a0, a1), (b0, b1) in zip(plan.bounds, plan.bounds[1:]):
                assert a1 == b0          # contiguous, ordered
            sizes = [e - st for st, e in plan.bounds]
            assert max(sizes) - min(sizes) <= 1
            assert sum(sizes) == d


def test_plan_bounds_invariant_to_rank_count():
    """THE byte-identity precondition: shard bounds depend on
    (num_docs, num_shards) only — owners change with the process
    count, the shards never do."""
    for p in (1, 2, 4, 8):
        assert plan_shards(1001, p, 8).bounds == plan_shards(1001, 1, 8).bounds


def test_plan_ownership_contiguous_and_balanced():
    plan = plan_shards(100, 3, 8)
    assert len(plan.owners) == 8
    assert sorted(set(plan.owners)) == [0, 1, 2]
    # Contiguous runs per rank, sizes differing by at most one.
    runs = [plan.owners.index(r) for r in (0, 1, 2)]
    assert runs == sorted(runs)
    counts = [plan.owners.count(r) for r in (0, 1, 2)]
    assert max(counts) - min(counts) <= 1
    assert not plan.aligned                 # 3 does not divide 8 evenly
    assert plan_shards(100, 2, 8).aligned
    assert plan_shards(100, 4, 8).aligned


def test_plan_validation():
    with pytest.raises(ValueError, match="cannot cover"):
        plan_shards(100, 4, 2)
    with pytest.raises(ValueError, match="at least one document shard"):
        resolve_em_shards(2, num_procs=4)
    assert resolve_em_shards(0, 1) == DEFAULT_EM_SHARDS
    assert resolve_em_shards(0, 16) == 16   # grown past the default
    assert resolve_em_shards(32, 2) == 32   # explicit wins


def test_plan_env_override(monkeypatch):
    monkeypatch.setenv("ONI_ML_TPU_EM_SHARDS", "4")
    assert resolve_em_shards(0, 2) == 4
    assert resolve_em_shards(16, 2) == 4    # env wins over config


def test_plan_record_roundtrip():
    plan = plan_shards(80, 2, 8)
    rec = plan.record(rank=1)
    assert rec["kind"] == "shard_plan"
    assert rec["owned_shards"] == [4, 5, 6, 7]
    assert rec["local_docs"] == 40
    assert rec["aligned"] is True
    assert len(rec["bounds"]) == 8


# ---------------------------------------------------------------------------
# Tree reduction
# ---------------------------------------------------------------------------


def test_tree_combine_alignment_invariance():
    """A contiguous aligned block's local combine is exactly the
    canonical subtree: combining per-rank roots equals combining all
    leaves at once, bit for bit — the cross-rank-count identity the
    artifacts contract rides on."""
    rng = np.random.default_rng(0)
    parts = [rng.standard_normal((33, 5)).astype(np.float32)
             for _ in range(8)]
    full = tree_combine(parts)
    for p in (1, 2, 4, 8):
        w = 8 // p
        roots = [tree_combine(parts[i * w:(i + 1) * w]) for i in range(p)]
        np.testing.assert_array_equal(tree_combine(roots), full)


def test_tree_combine_dicts_and_odd_tail():
    parts = [{"a": np.float32(i), "b": np.ones(2, np.float32) * i}
             for i in range(5)]
    out = tree_combine(parts)
    assert out["a"] == np.float32(10)
    np.testing.assert_array_equal(out["b"], np.ones(2, np.float32) * 10)
    with pytest.raises(ValueError):
        tree_combine([])


# ---------------------------------------------------------------------------
# KV-ring transport over a fake coordination client
# ---------------------------------------------------------------------------


class _MemKV:
    """In-process stand-in for the jaxlib DistributedRuntimeClient KV
    store: blocking gets with DEADLINE_EXCEEDED timeouts, write-once
    sets, deletes — enough to drive the ring across threads."""

    def __init__(self):
        self._d = {}
        self._cv = threading.Condition()

    def key_value_set_bytes(self, key, value, allow_overwrite=False):
        with self._cv:
            if key in self._d and not allow_overwrite:
                raise RuntimeError(f"ALREADY_EXISTS: {key}")
            self._d[key] = value
            self._cv.notify_all()

    def blocking_key_value_get_bytes(self, key, timeout_in_ms):
        deadline = time.monotonic() + timeout_in_ms / 1000.0
        with self._cv:
            while key not in self._d:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise RuntimeError(f"DEADLINE_EXCEEDED: {key}")
                self._cv.wait(remaining)
            return self._d[key]

    def key_value_delete(self, key):
        with self._cv:
            self._d.pop(key, None)


def _ring(kv, nprocs, fn, timeout_s=20.0, max_chunk=64):
    """Run `fn(collective, rank)` on one thread per rank over a shared
    fake KV; returns per-rank results, re-raising the first error."""
    colls = [
        Collective(client=kv, rank=r, nprocs=nprocs, transport="kvring",
                   timeout_s=timeout_s, max_chunk_bytes=max_chunk)
        for r in range(nprocs)
    ]
    results: list = [None] * nprocs
    errors: list = [None] * nprocs

    def run(r):
        try:
            results[r] = fn(colls[r], r)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors[r] = e

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(nprocs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout_s + 10)
    for e in errors:
        if e is not None:
            raise e
    return results


@pytest.mark.parametrize("nprocs", [2, 3, 5])
def test_ring_allgather_objects(nprocs):
    kv = _MemKV()
    outs = _ring(kv, nprocs,
                 lambda c, r: c.allgather_obj({"rank": r}, "t"))
    for got in outs:
        assert got == [{"rank": r} for r in range(nprocs)]


def test_ring_allgather_arrays_chunked_uneven_payloads():
    """Chunking at a tiny bound plus per-rank payloads of DIFFERENT
    sizes (the unaligned reduce ships per-shard partials, so ranks
    legitimately send different byte counts)."""
    kv = _MemKV()

    def fn(c, r):
        named = {"x": np.arange((r + 1) * 40, dtype=np.float32)}
        return c.allgather_arrays(named, "arr")

    outs = _ring(kv, 3, fn, max_chunk=16)
    for got in outs:
        assert len(got) == 3
        for r in range(3):
            np.testing.assert_array_equal(
                got[r]["x"], np.arange((r + 1) * 40, dtype=np.float32)
            )
    # Single-reader ring keys were retired after the read; only the
    # (multi-reader) failure key namespace may remain.
    assert not kv._d, sorted(kv._d)


def test_ring_broadcast_and_barrier():
    kv = _MemKV()

    def fn(c, r):
        v = c.broadcast_obj({"model": "x"} if r == 0 else None, "bc")
        c.barrier("b")
        return v

    outs = _ring(kv, 3, fn)
    assert all(v == {"model": "x"} for v in outs)


def test_reduce_partials_aligned_matches_canonical_tree():
    """2 aligned ranks exchanging subtree roots reduce to the exact
    bytes a 1-rank reduction of the same shard partials produces."""
    rng = np.random.default_rng(1)
    parts = {s: {"ss": rng.standard_normal((17, 3)).astype(np.float32),
                 "ll": np.float32(rng.standard_normal())}
             for s in range(8)}
    want = tree_combine([parts[s] for s in range(8)])

    plan1 = plan_shards(100, 1, 8)
    coll1 = Collective(client=None, rank=0, nprocs=1, transport="local")
    got1 = reduce_partials(coll1, plan1, parts, "t")
    np.testing.assert_array_equal(got1["ss"], want["ss"])

    plan2 = plan_shards(100, 2, 8)
    kv = _MemKV()

    def fn(c, r):
        mine = {s: parts[s] for s in plan2.owned(r)}
        return reduce_partials(c, plan2, mine, "t")

    for got in _ring(kv, 2, fn):
        np.testing.assert_array_equal(got["ss"], want["ss"])
        np.testing.assert_array_equal(got["ll"], want["ll"])


def test_reduce_partials_unaligned_ships_per_shard():
    """3 ranks over 8 shards (unaligned): per-shard partials cross the
    ring and the canonical shard-order tree still applies — same bytes
    as the 1-rank reduction."""
    rng = np.random.default_rng(2)
    parts = {s: {"ss": rng.standard_normal((9, 2)).astype(np.float32)}
             for s in range(8)}
    want = tree_combine([parts[s] for s in range(8)])
    plan = plan_shards(100, 3, 8)
    assert not plan.aligned
    kv = _MemKV()

    def fn(c, r):
        mine = {s: parts[s] for s in plan.owned(r)}
        return reduce_partials(c, plan, mine, "t")

    for got in _ring(kv, 3, fn):
        np.testing.assert_array_equal(got["ss"], want["ss"])


def test_ring_wait_timeout_is_peer_failure():
    """A rank whose peer never shows terminates with the structured
    PeerFailure (bounded wait), not a hang."""
    kv = _MemKV()
    coll = Collective(client=kv, rank=0, nprocs=2, transport="kvring",
                      timeout_s=0.3)
    with pytest.raises(PeerFailure, match="timed out"):
        coll.allgather_obj(1, "never")


def test_fail_key_relays_structured_peer_failure():
    """A failing rank's posted failure surfaces on a BLOCKED peer
    within one poll slice as "failed on another rank" — the
    coordination-client health barrier of the mid-EM death contract."""
    kv = _MemKV()
    c0 = Collective(client=kv, rank=0, nprocs=2, transport="kvring",
                    timeout_s=30.0)
    c1 = Collective(client=kv, rank=1, nprocs=2, transport="kvring",
                    timeout_s=30.0)
    err: list = []

    def blocked():
        try:
            c0.allgather_obj(1, "x")
        except BaseException as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.1)
    c1.fail("boom in stage lda")
    t.join(30)
    assert err and isinstance(err[0], PeerFailure)
    assert "failed on another rank" in str(err[0])
    assert "boom in stage lda" in str(err[0])
    # ...and PeerFailure is BackendLost, so ml_ops exits rc=3.
    from oni_ml_tpu.telemetry import BackendLost

    assert isinstance(err[0], BackendLost)
    # A rank's own failure post never self-triggers.
    c1.check_peer_failure()


def test_failed_rank_drains_bounded(monkeypatch):
    """A rank that already posted its OWN failure must not wait the
    full collective timeout for barriers its (aborted) peers will
    never finish — its waits cap at the drain window and re-raise its
    own failure."""
    from oni_ml_tpu.parallel import allreduce as ar

    monkeypatch.setattr(ar, "FAIL_DRAIN_S", 0.2)
    kv = _MemKV()
    c = Collective(client=kv, rank=0, nprocs=3, transport="kvring",
                   timeout_s=60.0)
    c.fail("stage lda: boom")
    t0 = time.monotonic()
    with pytest.raises(PeerFailure, match="own failure.*boom"):
        c.allgather_obj(False, "outcome")
    assert time.monotonic() - t0 < 5.0


def test_fail_post_is_idempotent():
    kv = _MemKV()
    c = Collective(client=kv, rank=0, nprocs=2, transport="kvring")
    c.fail("first")
    c.fail("second")  # allow_overwrite — must not raise
    raw = kv.blocking_key_value_get_bytes("oni/ar/fail", 1)
    rank, reason = pickle.loads(raw)
    assert rank == 0 and reason == "second"


def test_transport_selection_and_validation():
    c = Collective(client=None, rank=0, nprocs=1)
    assert c.transport == "local"
    assert c.allgather_arrays({"x": np.ones(3)}, "t") == [
        {"x": pytest.approx(np.ones(3))}
    ]
    assert c.broadcast_obj("v", "t") == "v"
    with pytest.raises(ValueError, match="unknown allreduce transport"):
        Collective(client=_MemKV(), rank=0, nprocs=2, transport="wat")


def test_psum_gather_single_process():
    """The ICI-path gather degenerates cleanly at one process (the
    shape every CPU test and the dryrun can execute; multi-host ICI
    numbers stay projections until a TPU grant)."""
    from oni_ml_tpu.parallel.allreduce import _psum_gather

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = _psum_gather(x, 1)
    assert out.shape == (1, 3, 4)
    np.testing.assert_array_equal(out[0], x)
    # 8-byte dtypes transport bit-exact via the uint32 view (jax would
    # otherwise canonicalize f64 down to f32 with x64 off — the gamma
    # merge must not lose precision on the pod path).
    g = np.random.default_rng(3).standard_normal((5, 2))  # float64
    out64 = _psum_gather(g, 1)
    assert out64.dtype == np.float64
    np.testing.assert_array_equal(out64[0], g)


def test_allreduce_journal_record(monkeypatch):
    """Every data-plane op journals {"kind": "allreduce"} with bytes/
    rounds/wall through the active recorder, and the stats counters
    accumulate (what bench distributed_em and the lda stage record
    read)."""
    from oni_ml_tpu.telemetry.spans import Recorder, use_recorder

    class _Sink:
        def __init__(self):
            self.records = []

        def append(self, rec, sync=False):
            self.records.append(rec)

    sink = _Sink()
    rec = Recorder(journal=sink)
    kv = _MemKV()

    def fn(c, r):
        with use_recorder(rec):
            c.allgather_arrays({"x": np.ones(4, np.float32)}, "em1")
        return dict(c.stats)

    stats = _ring(kv, 2, fn)
    ars = [r for r in sink.records if r.get("kind") == "allreduce"]
    assert len(ars) == 2
    for a in ars:
        assert a["transport"] == "kvring"
        assert a["nprocs"] == 2
        assert a["rounds"] == 1
        assert a["bytes_out"] > 0 and a["bytes_in"] > 0
    for s in stats:
        assert s["ops"] == 1 and s["bytes_out"] > 0


# ---------------------------------------------------------------------------
# bf16-compressed payloads
# ---------------------------------------------------------------------------


def test_bf16_pack_roundtrip_tolerance():
    """Round-to-nearest-even bf16 pack: exact unpack back into f32
    with <= 2^-8 relative error on normal values, specials preserved."""
    from oni_ml_tpu.parallel.allreduce import _bf16_pack, _bf16_unpack

    rng = np.random.default_rng(0)
    x = (rng.standard_normal(4096).astype(np.float32)
         * np.float32(10.0) ** rng.integers(-6, 6, 4096))
    r = _bf16_unpack(_bf16_pack(x))
    np.testing.assert_allclose(r, x, rtol=2 ** -8, atol=0)
    specials = np.array([0.0, -0.0, np.inf, -np.inf], np.float32)
    np.testing.assert_array_equal(
        _bf16_unpack(_bf16_pack(specials)), specials)
    # NaN must survive the wire (a diverged rank's stats fail loudly,
    # never silently zero): every NaN bit pattern, incl. the
    # high-payload ones whose carry add would wrap to +/-0.0.
    nans = np.array([np.nan, -np.nan], np.float32)
    nans = np.concatenate([
        nans,
        np.array([0x7FFFFFFF, 0xFFFFFFFF, 0x7FC00001],
                 np.uint32).view(np.float32),
    ])
    assert np.isnan(_bf16_unpack(_bf16_pack(nans))).all()
    # f64 input packs through f32 (accumulation dtype is f32).
    assert _bf16_unpack(_bf16_pack(np.ones(3, np.float64))).dtype \
        == np.float32


def test_allgather_bf16_halves_bytes_rank_identical():
    """bf16 wire precision on the kvring: float payloads ship half the
    bytes, EVERY rank (sender included) unpacks the same bits, so the
    gathered arrays are rank-identical and within bf16 tolerance of
    the f32 wire; int arrays pass through untouched."""
    rng = np.random.default_rng(3)
    payloads = [
        {"ss": rng.standard_normal((128, 8)).astype(np.float32),
         "n": np.int64(7 + r)}
        for r in range(2)
    ]
    out = {}
    for precision in ("f32", "bf16"):
        kv = _MemKV()

        def fn(c, r, precision=precision):
            g = c.allgather_arrays(payloads[r], "t",
                                   precision=precision)
            return tree_combine(g), dict(c.stats)

        out[precision] = _ring(kv, 2, fn, max_chunk=1 << 20)
    (f32_a, s32), (f32_b, _) = out["f32"]
    (bf_a, s16), (bf_b, _) = out["bf16"]
    np.testing.assert_array_equal(f32_a["ss"], f32_b["ss"])
    np.testing.assert_array_equal(bf_a["ss"], bf_b["ss"])
    assert bf_a["n"] == f32_a["n"] == np.int64(7) + np.int64(8)
    np.testing.assert_allclose(bf_a["ss"], f32_a["ss"],
                               rtol=2 ** -7, atol=2 ** -6)
    assert not np.array_equal(bf_a["ss"], f32_a["ss"])
    assert s16["bytes_out"] < 0.62 * s32["bytes_out"]


def test_reduce_partials_bf16_parity_and_journal_precision():
    """The suff-stats reduce under precision="bf16": rank-identical
    reduced stats within tolerance of the f32 wire, and the
    {"kind": "allreduce"} record carries the APPLIED precision."""
    from oni_ml_tpu.telemetry.spans import Recorder, use_recorder

    class _Sink:
        def __init__(self):
            self.records = []

        def append(self, rec, sync=False):
            self.records.append(rec)

    rng = np.random.default_rng(5)
    parts = {s: {"ss": rng.standard_normal((17, 3)).astype(np.float32)}
             for s in range(8)}
    plan2 = plan_shards(100, 2, 8)

    def run(precision, sink):
        kv = _MemKV()
        rec = Recorder(journal=sink)

        def fn(c, r):
            mine = {s: parts[s] for s in plan2.owned(r)}
            with use_recorder(rec):
                return reduce_partials(c, plan2, mine, "t",
                                       precision=precision)

        return _ring(kv, 2, fn)

    sink32, sink16 = _Sink(), _Sink()
    got32 = run(None, sink32)
    got16 = run("bf16", sink16)
    np.testing.assert_array_equal(got16[0]["ss"], got16[1]["ss"])
    np.testing.assert_allclose(got16[0]["ss"], got32[0]["ss"],
                               rtol=2 ** -6, atol=2 ** -5)
    assert {r["precision"] for r in sink32.records
            if r.get("kind") == "allreduce"} == {"f32"}
    assert {r["precision"] for r in sink16.records
            if r.get("kind") == "allreduce"} == {"bf16"}


def test_collective_payload_precision_env_and_validation(monkeypatch):
    """ONI_ML_TPU_ALLREDUCE_PRECISION sets the collective default;
    junk values fail loudly at construction."""
    monkeypatch.setenv("ONI_ML_TPU_ALLREDUCE_PRECISION", "bf16")
    c = Collective(client=_MemKV(), rank=0, nprocs=1,
                   transport="local")
    assert c.payload_precision == "bf16"
    monkeypatch.delenv("ONI_ML_TPU_ALLREDUCE_PRECISION")
    c = Collective(client=_MemKV(), rank=0, nprocs=1,
                   transport="local")
    assert c.payload_precision == "f32"
    with pytest.raises(ValueError, match="payload_precision"):
        Collective(client=_MemKV(), rank=0, nprocs=1,
                   transport="local", payload_precision="f16")
