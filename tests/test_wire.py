"""Cross-host serving wire (oni_ml_tpu/serving/wire.py + autoscale.py
+ the TCP promotion claims): columnar frame round-trips for every
typed encoding, the columnar<->pickle score parity pins across all
three registered sources, loud rejection of truncated / oversized /
version-drifted frames, the same-host shm ring's wraparound and
concurrent stress contracts, the autoscaler's hysteresis / cooldown /
reaction-clock control law on an injectable clock, and the
concurrent-router failover claim (exactly one winner, both routers'
futures resolve).  All CPU, no markers — the tier-1 cross-host smoke."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from oni_ml_tpu import sources
from oni_ml_tpu.config import ServingConfig
from oni_ml_tpu.parallel.membership import FileKVClient
from oni_ml_tpu.scoring import ScoringModel
from oni_ml_tpu.serving import (
    AutoScaler,
    FleetRouter,
    ReplicaServer,
    ShmRing,
    TenantSpec,
    decode_payload,
    encode_payload,
    score_features,
)
from oni_ml_tpu.serving import wire as wire_mod
from oni_ml_tpu.serving import wire_pickle


# ---------------------------------------------------------------------------
# columnar frame round-trips
# ---------------------------------------------------------------------------


def test_frame_roundtrip_typed_encodings():
    """Every typed encoding survives encode->decode: nd arrays
    (zero-copy, bit-identical), id lists, string tables, split raws,
    cuts tuples, and plain JSON scalars."""
    rng = np.random.default_rng(0)
    msg = {
        "op": "submit_many",
        "tenant": "t0",
        "ids": [7, 8, 9, 10],
        "raws": [["a", "bb", "ccc"], ["dd", ""], ["zzz"]],
        "arr": rng.standard_normal((3, 5)),
        "cuts": (np.arange(4.0), [0.5, 1.5], np.array([9.0])),
        "n": 42,
        "flag": True,
    }
    out = decode_payload(encode_payload(msg))
    assert out["op"] == "submit_many" and out["tenant"] == "t0"
    assert out["n"] == 42 and out["flag"] is True
    assert out["ids"] == msg["ids"]
    assert out["raws"] == [["a", "bb", "ccc"], ["dd", ""], ["zzz"]]
    np.testing.assert_array_equal(out["arr"], msg["arr"])
    assert len(out["cuts"]) == 3
    for got, want in zip(out["cuts"], msg["cuts"]):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want, np.float64))


def test_frame_roundtrip_model_and_scores_bit_identical():
    """A ScoringModel column set and a coalesced score batch (floats
    + interleaved errors) round-trip bit-identically — float64 scores
    never reformat on the wire."""
    rng = np.random.default_rng(1)
    model = ScoringModel.from_results(
        ["10.0.0.1", "10.0.0.2"], rng.dirichlet(np.ones(3), size=2),
        ["w0", "w1", "w2", "w3"],
        rng.dirichlet(np.ones(4), size=3).T, fallback=0.05,
    )
    out = decode_payload(encode_payload({"op": "add", "model": model}))
    m = out["model"]
    np.testing.assert_array_equal(np.asarray(m.theta),
                                  np.asarray(model.theta))
    np.testing.assert_array_equal(np.asarray(m.p), np.asarray(model.p))
    assert m.ip_index == model.ip_index
    assert m.word_index == model.word_index

    batch = [
        {"id": 1, "score": float(np.nextafter(0.1, 1.0)), "version": 3},
        {"id": 2, "error": "boom"},
        {"id": 3, "score": -1.5e-300, "version": 1},
    ]
    got = decode_payload(encode_payload(batch))
    assert got[0] == batch[0]   # == on floats: bit-identical or bust
    assert got[1] == {"id": 2, "error": "boom"}
    assert got[2] == batch[2]


def test_pickle_fallback_gated_by_negotiated_codec():
    """A non-columnar frame decodes only on a link whose negotiation
    settled on the pickle fallback; on a columnar link it is rejected
    outright — the receiver never sniffs its way into the unpickler."""
    msg = {"op": "stats", "x": [1, 2, 3]}
    blob = wire_pickle.encode_payload(msg)
    assert bytes(blob[:4]) != wire_mod.MAGIC
    assert decode_payload(blob, codec="pickle") == msg
    with pytest.raises(ConnectionError, match="did not negotiate"):
        decode_payload(blob)


def test_fallback_unpickler_refuses_code_execution_gadgets():
    """Even a negotiated-fallback link never executes frame bytes:
    the allowlisted unpickler refuses globals outside the wire's
    legitimate vocabulary, so an os.system reduce gadget fails the
    decode instead of running."""
    import os
    import pickle as _pickle

    class Evil:
        def __reduce__(self):
            return (os.system, ("true",))

    blob = _pickle.dumps(Evil())
    with pytest.raises(ConnectionError, match="allowlist"):
        wire_pickle.decode_payload(blob)
    with pytest.raises(_pickle.UnpicklingError, match="allowlist"):
        wire_pickle.decode_opaque(blob)
    # The legitimate vocabulary still round-trips.
    arr = np.arange(4.0)
    np.testing.assert_array_equal(
        wire_pickle.decode_opaque(wire_pickle.encode_opaque(arr)), arr)


# ---------------------------------------------------------------------------
# malformed-frame rejection
# ---------------------------------------------------------------------------


def test_truncated_and_drifted_frames_rejected():
    frame = encode_payload({"op": "x", "arr": np.arange(6.0)})
    # Truncated anywhere: header, descriptors, meta, or column bytes.
    for cut in (2, wire_mod._HDR.size - 1, len(frame) - 3):
        with pytest.raises(ConnectionError):
            decode_payload(frame[:cut])
    # Trailing junk is length drift, not silently ignored padding.
    with pytest.raises(ConnectionError):
        decode_payload(frame + b"\0\0")


def test_version_mismatch_and_unknown_kind_rejected():
    frame = bytearray(encode_payload({"op": "x"}))
    vers = frame[:]
    vers[4] = wire_mod.WIRE_VERSION + 1   # !4sBBHI — byte 4 = version
    with pytest.raises(ConnectionError, match="version"):
        decode_payload(bytes(vers))
    kind = frame[:]
    kind[5] = 250                         # byte 5 = frame kind
    with pytest.raises(ConnectionError, match="kind"):
        decode_payload(bytes(kind))


def test_malformed_columnar_frames_fail_as_connection_error():
    """Hostile descriptors — meta referencing a missing column, a
    garbage dtype string — surface as the wire's uniform
    ConnectionError, never a TypeError/ValueError/KeyError that would
    escape a reader thread's ``except (ConnectionError, OSError)``."""
    missing = wire_mod._frame(wire_mod.KIND_MSG,
                              {"f": {}, "e": {"x": "nd"}}, [])
    with pytest.raises(ConnectionError):
        decode_payload(missing)
    good = bytearray(encode_payload({"op": "x", "arr": np.arange(4.0)}))
    i = good.index(b"<f8")
    good[i:i + 3] = b"zzz"       # np.dtype("zzz") raises TypeError
    with pytest.raises(ConnectionError):
        decode_payload(bytes(good))


def test_oversized_announcement_rejected_before_allocation():
    """A length prefix announcing more than MAX_FRAME_BYTES fails the
    read loudly instead of allocating by attacker."""
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("!I", wire_mod.MAX_FRAME_BYTES + 1))
        with pytest.raises(ConnectionError, match="oversized"):
            wire_mod.recv_frame(b)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# columnar <-> pickle parity pins (dns / flow / proxy)
# ---------------------------------------------------------------------------


def _source_tenant(dsource: str, seed: int):
    """One tenant's day from the source registry: raw benign rows,
    the trained-yesterday model over the day's actual key/word
    populations, and the pinned cuts."""
    src = sources.get(dsource)
    rows = src.synth_benign(32, seed=seed)
    feats = src.featurize(rows)
    cuts = src.cuts_of(feats)
    keys, vocab = set(), set()
    for ks, words in src.event_pairs(feats):
        keys.update(ks)
        vocab.update(words)
    rng = np.random.default_rng(seed)
    k = 4
    model = ScoringModel.from_results(
        sorted(keys), rng.dirichlet(np.ones(k), size=len(keys)),
        sorted(vocab),
        rng.dirichlet(np.ones(len(vocab)), size=k).T, fallback=0.05,
    )
    return rows, model, cuts, feats


def _routed_scores(cfg: ServingConfig, tenants: dict) -> dict:
    replica = ReplicaServer("r0", cfg)
    router = FleetRouter(cfg)
    try:
        router.connect_replica("r0", replica.host, replica.port)
        for name, (rows, model, cuts, _) in tenants.items():
            router.add_tenant(
                TenantSpec(tenant=name, dsource=name), cuts, model)
        router.start(warmup=False)
        futs = {name: router.submit_many(name, rows)
                for name, (rows, _, _, _) in tenants.items()}
        router.flush()
        return {name: np.array([f.result(timeout=30.0)[0]
                                for f in fs])
                for name, fs in futs.items()}
    finally:
        router.close()
        replica.stop()


def test_wire_parity_pin_all_sources_columnar_vs_pickle():
    """THE byte-parity pin (acceptance criteria): for every registered
    source, scores routed over the columnar wire are bit-identical to
    the same census over the negotiated pickle wire AND to the
    in-process oracle."""
    tenants = {name: _source_tenant(name, seed)
               for seed, name in enumerate(("dns", "flow", "proxy"))}
    base = dict(fleet_max_batch=64, fleet_max_wait_ms=5.0,
                device_score_min=None)
    columnar = _routed_scores(
        ServingConfig(wire_format="columnar", **base), tenants)
    fallback = _routed_scores(
        ServingConfig(wire_format="pickle", **base), tenants)
    for name, (_, model, _, feats) in tenants.items():
        oracle = score_features(model, feats, name, device_min=None)
        np.testing.assert_array_equal(columnar[name], oracle)
        np.testing.assert_array_equal(fallback[name], oracle)


# ---------------------------------------------------------------------------
# receive-side pickle gating (the replica's ports)
# ---------------------------------------------------------------------------


def test_unnegotiated_pickle_frame_drops_the_connection():
    """A peer that skips negotiation and throws a pickle frame at a
    columnar replica gets its connection dropped — the frame is never
    unpickled (default wire_accept_pickle=False)."""
    rep = ReplicaServer("r0", ServingConfig())
    try:
        s = socket.create_connection((rep.host, rep.port))
        try:
            payload = wire_pickle.encode_payload(
                {"op": "ping", "id": 1})
            s.sendall(struct.pack("!I", len(payload)) + payload)
            with pytest.raises(ConnectionError):
                wire_mod.recv_frame(s)    # replica hung up, no reply
        finally:
            s.close()
    finally:
        rep.stop()


def test_hello_pickle_only_offer_refused_unless_accepted():
    """The hello negotiation is the only gate into the fallback: a
    pickle-only offer is an error under the default config and only
    negotiates the fallback when wire_accept_pickle is on."""
    rep = ReplicaServer("r0", ServingConfig())
    try:
        s = socket.create_connection((rep.host, rep.port))
        try:
            wire_mod.send_frame(
                s, {"op": "hello", "id": 1, "wire": ["pickle"]})
            rsp = wire_mod.recv_frame(s)
            assert "wire_accept_pickle" in rsp["error"]
        finally:
            s.close()
    finally:
        rep.stop()
    rep = ReplicaServer("r1", ServingConfig(wire_accept_pickle=True))
    try:
        s = socket.create_connection((rep.host, rep.port))
        try:
            wire_mod.send_frame(
                s, {"op": "hello", "id": 1, "wire": ["pickle"]})
            rsp = wire_mod.recv_frame(s)
            assert rsp["wire"] == "pickle" and rsp["ok"]
        finally:
            s.close()
    finally:
        rep.stop()


def test_hello_rings_are_torn_down_with_their_connection():
    """Rings negotiated by a connection's hello die with the
    connection (and a repeat hello replaces, not accumulates) — a
    reconnecting or SIGKILL'd router leaks no shm segments or polling
    threads on the replica."""
    rep = ReplicaServer("r0", ServingConfig())
    try:
        hello = {"op": "hello", "wire": ["columnar"], "shm": True,
                 "host": socket.gethostname()}
        s = socket.create_connection((rep.host, rep.port))
        try:
            wire_mod.send_frame(s, {**hello, "id": 1})
            rsp = wire_mod.recv_frame(s)
            assert rsp["shm"] is not None
            assert len(rep._rings) == 2
            # Second hello on the same connection: replaced, not
            # appended.
            wire_mod.send_frame(s, {**hello, "id": 2})
            rsp2 = wire_mod.recv_frame(s)
            assert rsp2["shm"] is not None
            assert rsp2["shm"]["c2s"] != rsp["shm"]["c2s"]
            assert len(rep._rings) == 2
        finally:
            s.close()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and rep._rings:
            time.sleep(0.01)
        assert rep._rings == []
    finally:
        rep.stop()


# ---------------------------------------------------------------------------
# shm ring
# ---------------------------------------------------------------------------


def test_shm_ring_wraparound_orders_and_survives_reuse():
    """Sequence numbers run far past the two physical slabs: every
    frame arrives intact, in order, with sizes spanning empty to
    nearly slab-filling — slab reuse never overwrites an unread
    frame."""
    ring = ShmRing.create(slab_bytes=4096)
    peer = ShmRing.attach(ring.name, 4096)
    try:
        rng = np.random.default_rng(2)
        sizes = [int(s) for s in rng.integers(0, 4000, size=64)]
        payloads = [bytes(rng.integers(0, 256, size=s, dtype=np.uint8))
                    for s in sizes]
        got = []

        def consume():
            while len(got) < len(payloads):
                p = peer.pop(timeout_s=5.0)
                assert p is not None
                got.append(p)

        t = threading.Thread(target=consume)
        t.start()
        for p in payloads:
            assert ring.push(p, timeout_s=5.0)
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert got == payloads
        with pytest.raises(ValueError, match="exceeds ring slab"):
            ring.push(b"x" * 4097)
    finally:
        peer.close()
        ring.close()


def test_shm_ring_concurrent_stress_columnar_frames():
    """Producer/consumer threads under real columnar frames: 300
    variable score batches cross the ring bit-identically while the
    producer backpressures on the two-slab window."""
    ring = ShmRing.create(slab_bytes=1 << 16)
    peer = ShmRing.attach(ring.name, 1 << 16)
    sent = []
    rng = np.random.default_rng(3)
    for i in range(300):
        n = int(rng.integers(1, 64))
        sent.append([{"id": 1000 * i + j,
                      "score": float(rng.standard_normal()),
                      "version": i} for j in range(n)])
    got = []
    try:
        def consume():
            while len(got) < len(sent):
                p = peer.pop(timeout_s=10.0)
                assert p is not None
                got.append(decode_payload(p))

        t = threading.Thread(target=consume)
        t.start()
        for batch in sent:
            assert ring.push(encode_payload(batch), timeout_s=10.0)
        t.join(timeout=60.0)
        assert not t.is_alive()
        assert got == sent
    finally:
        peer.close()
        ring.close()


def test_shm_ring_stuck_seqlock_times_out_and_closes():
    """A peer SIGKILL'd between its seqlock guard writes leaves the
    guard odd forever: the survivor's read bounds out, marks the ring
    closed, and degrades instead of busy-looping at 100% CPU."""
    ring = ShmRing.create(slab_bytes=1024)
    peer = ShmRing.attach(ring.name, 1024)
    try:
        peer._SEQLOCK_STUCK_S = 0.2
        # Producer dies mid-_locked_write: pseq stays odd.
        ring._write_u64(wire_mod._OFF_PSEQ, 1)
        t0 = time.monotonic()
        assert peer.pop(timeout_s=30.0) is None
        assert time.monotonic() - t0 < 5.0
        assert peer.closed
        # Both ends now see the ring dead and return immediately.
        assert peer.pop(timeout_s=1.0) is None
        assert not ring.push(b"x", timeout_s=0.2)
    finally:
        peer.close()
        ring.close()


def test_shm_ring_close_unblocks_peer():
    ring = ShmRing.create(slab_bytes=1024)
    peer = ShmRing.attach(ring.name, 1024)
    try:
        assert ring.push(b"last", timeout_s=1.0)
        ring.close()
        # Pending frames still drain after close...
        assert peer.pop(timeout_s=1.0) == b"last"
        # ...then the peer sees shutdown, not a hang.
        assert peer.pop(timeout_s=1.0) is None
        assert peer.closed
        assert not peer.push(b"x", timeout_s=0.2)
    finally:
        peer.close()
        ring.close()


# ---------------------------------------------------------------------------
# autoscaler control law (fake router, injectable clock)
# ---------------------------------------------------------------------------


class _FakeRouter:
    """stats()-shaped occupancy the tests steer directly."""

    def __init__(self, replicas, cap=8):
        self.replicas = list(replicas)
        self.cap = cap
        self.occupancy = 0
        self.joined = []
        self.drained = []

    def stats(self):
        return {
            "replicas": list(self.replicas),
            "max_inflight": self.cap,
            "edges": {r: {"inflight": self.occupancy
                          // max(1, len(self.replicas)),
                          "events": 0, "admission_stall_s": 0.0}
                      for r in self.replicas},
        }

    def join_replica(self, rid, host, port):
        self.replicas.append(rid)
        self.joined.append(rid)

    def drain_replica(self, rid):
        self.replicas.remove(rid)
        self.drained.append(rid)


def _scaler(router, **cfg_kw):
    cfg = ServingConfig(
        autoscale_interval_s=0.5, autoscale_halflife_s=1.0,
        autoscale_cooldown_s=5.0, autoscale_high=0.75,
        autoscale_low=0.25, autoscale_max_replicas=4, **cfg_kw)
    counter = {"n": 0}

    def spawn():
        counter["n"] += 1
        rid = f"as{counter['n']}"
        return rid, "127.0.0.1", 0

    return AutoScaler(router, spawn=spawn,
                      stop=lambda rid: None, config=cfg)


def test_autoscaler_hysteresis_ewma_and_reaction_clock():
    """The EWMA delays the decision past the first raw breach (no
    flap on one bursty sample) and reaction_s measures breach ->
    join, not zero."""
    router = _FakeRouter(["r0"], cap=8)
    sc = _scaler(router)
    # Seed the EWMA low: in-band, no action.
    router.occupancy = 4            # util 0.5 of 1x8
    assert sc.tick(now=0.0)["action"] == "hold"
    # Raw breach at t=1: EWMA (0.5 -> 0.625) still under 0.75.
    router.occupancy = 8            # util 1.0
    d = sc.tick(now=1.0)
    assert d["action"] == "hold" and d["util"] == 1.0
    # Breach persists: EWMA crosses, the join fires, and the
    # reaction clock started at the FIRST raw breach (t=1).
    d = sc.tick(now=2.0)
    assert d["action"] == "up" and router.joined == ["as1"]
    assert d["reaction_s"] == pytest.approx(1.0)


def test_autoscaler_cooldown_max_replicas_and_owned_drain():
    router = _FakeRouter(["r0"], cap=8)
    sc = _scaler(router)
    # Saturated on the very first sample: the seed EWMA IS the raw
    # sample, so the join fires without the smoothing delay.
    router.occupancy = 8
    assert sc.tick(now=0.0)["action"] == "up"
    # Cooldown: still saturated, but the controller only observes.
    d = sc.tick(now=1.0)
    assert (d["action"], d["reason"]) == ("hold", "cooldown")
    # After cooldown the grown fleet is saturated again -> up (the
    # EWMA restarted from None after the join, so it reseeds hot).
    router.occupancy = 16
    assert sc.tick(now=6.0)["action"] == "up"
    router.occupancy = 96
    assert sc.tick(now=12.0)["action"] == "up"
    # At max_replicas the controller reports the ceiling, not a spawn.
    d = sc.tick(now=18.0)
    assert d["action"] == "hold" and d["reason"] == "at max_replicas"
    assert router.replicas == ["r0", "as1", "as2", "as3"]
    # Drain: LIFO over OWNED replicas only, down to the floor — r0
    # (operator-connected) is never drained.
    router.occupancy = 0
    for i in range(40):
        sc.tick(now=20.0 + 6.0 * i)
        if not sc._owned:
            break
    assert router.drained == ["as3", "as2", "as1"]
    assert router.replicas == ["r0"]
    d = sc.tick(now=500.0)
    assert d["action"] == "hold"    # nothing owned, floor holds


def test_autoscaler_decisions_are_journaled():
    journal = []

    class _J:
        def append(self, rec):
            journal.append(rec)

    router = _FakeRouter(["r0"], cap=8)
    sc = AutoScaler(router, spawn=lambda: ("a", "h", 0),
                    stop=lambda rid: None,
                    config=ServingConfig(), journal=_J())
    router.occupancy = 0
    sc.tick(now=0.0)
    assert journal and journal[-1]["kind"] == "autoscale"
    assert {"action", "util", "util_ewma", "replicas",
            "occupancy"} <= set(journal[-1])


# ---------------------------------------------------------------------------
# concurrent-router failover claim
# ---------------------------------------------------------------------------


def test_claim_promotion_error_classes():
    """Only a genuine ALREADY_EXISTS loses the promotion election; a
    KV transport failure mid-failover claims by default — duplicate
    backfills are router_version-idempotent on the replica, zero
    backfills silently lose the promoted tenants' data path."""
    from oni_ml_tpu.parallel.membership import MembershipClient

    class _DeadKV:
        def key_value_set(self, *a, **k):
            raise RuntimeError("connection refused")

    class _TakenKV:
        def key_value_set(self, *a, **k):
            raise RuntimeError("ALREADY_EXISTS: oni/fleet/promote/r0")

    assert MembershipClient(_DeadKV()).claim_promotion(
        "r0", "ra") is True
    assert MembershipClient(_TakenKV()).claim_promotion(
        "r0", "ra") is False


def test_concurrent_router_failover_single_claim_both_resolve(tmp_path):
    """Two routers over the same membership both see the replica die:
    exactly ONE wins the first-writer promotion claim (the other's
    failover record carries claimed=false), and BOTH routers' in-flight
    futures resolve bit-identically — no event is lost to losing the
    race."""
    cfg = ServingConfig(fleet_max_batch=32, fleet_max_wait_ms=5.0,
                        device_score_min=None)
    kv_dir = str(tmp_path / "kv")
    tenants = {f"t{i}": _source_tenant("dns", seed=10 + i)
               for i in range(4)}
    replicas = {f"r{i}": ReplicaServer(f"r{i}", cfg,
                                       kv=FileKVClient(kv_dir))
                for i in range(3)}
    journals = {"ra": [], "rb": []}

    class _J:
        def __init__(self, sink):
            self.sink = sink

        def append(self, rec):
            self.sink.append(rec)

    routers = {}
    try:
        for name in ("ra", "rb"):
            r = FleetRouter(cfg, kv=FileKVClient(kv_dir),
                            router_id=name,
                            journal=_J(journals[name]))
            for rid, rep in replicas.items():
                r.connect_replica(rid, rep.host, rep.port)
            for t, (rows, model, cuts, _) in tenants.items():
                r.add_tenant(TenantSpec(tenant=t, dsource="dns"),
                             cuts, model)
            r.start(warmup=False)
            routers[name] = r
        victim = routers["ra"].placement()["t0"].primary
        futs = {name: {t: r.submit_many(t, tenants[t][0])
                       for t in tenants}
                for name, r in routers.items()}
        replicas[victim].kill()
        for r in routers.values():
            r.flush()
        time.sleep(0.1)
        for r in routers.values():
            r.flush()
        for name, r in routers.items():
            for t, fs in futs[name].items():
                got = np.array([f.result(timeout=30.0)[0]
                                for f in fs])
                _, model, _, feats = tenants[t]
                np.testing.assert_array_equal(
                    got,
                    score_features(model, feats, "dns",
                                   device_min=None))
        # Exactly one claim winner across the two routers.
        deadline = time.monotonic() + 15.0
        claims = []
        while time.monotonic() < deadline:
            claims = [rec["claimed"]
                      for recs in journals.values() for rec in recs
                      if rec.get("kind") == "failover"
                      and "event" not in rec]
            if len(claims) >= 2:
                break
            time.sleep(0.05)
        assert len(claims) == 2
        assert sorted(claims) == [False, True]
    finally:
        for r in routers.values():
            r.close()
        for rep in replicas.values():
            rep.stop()
