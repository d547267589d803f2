"""Replicated elastic serving (oni_ml_tpu/serving/placement.py +
replica.py + router.py, parallel/membership.py): consistent-hash
placement properties (determinism across processes, balance, minimal
movement, primary != shadow), the file-KV membership/heartbeat/fail
relay, the framed replica protocol, router score parity against the
single-process oracle, publish fan-out freshness, the kill-a-replica
chaos contract (zero failed futures, bit-identical survivor scores),
rolling drain/join redeploy, the route CLI dry-run, the load_gen
replicated harness + shed-path regression, and bench_diff's
replicated direction keys.  All CPU, no markers — the tier-1
replicated-serving smoke."""

import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from oni_ml_tpu.config import ServingConfig
from oni_ml_tpu.parallel.membership import (
    FileKVClient,
    HeartbeatPublisher,
    MembershipClient,
    kv_list,
)
from oni_ml_tpu.runner.route import route_main
from oni_ml_tpu.runner.serve import _synthetic_day
from oni_ml_tpu.serving import (
    DnsEventFeaturizer,
    FleetRouter,
    ReplicaServer,
    TenantSpec,
    load_by_replica,
    moved_primaries,
    place,
    score_features,
    shadow_for,
)
from oni_ml_tpu.serving.placement import preference, stable_hash
from oni_ml_tpu.serving.replica import recv_frame, send_frame
from oni_ml_tpu.serving.router import ReplicaLink

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))


def _tenants(n):
    return [f"t{i}" for i in range(n)]


def _replicas(n):
    return [f"r{i}" for i in range(n)]


# ---------------------------------------------------------------------------
# placement properties
# ---------------------------------------------------------------------------


def test_placement_deterministic_and_input_order_invariant():
    p1 = place(_tenants(64), ["a", "b", "c"])
    p2 = place(list(reversed(_tenants(64))), ["c", "a", "b"])
    for t in _tenants(64):
        assert p1[t] == p2[t]
    # stable_hash is blake2b, not the per-process-salted builtin.
    assert stable_hash("place", "t0", "a") == stable_hash(
        "place", "t0", "a")


def test_placement_deterministic_across_processes(tmp_path):
    """The satellite pin: a DIFFERENT python process (fresh hash seed)
    computes the identical placement from the same census."""
    here = place(_tenants(32), _replicas(3))
    script = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "from oni_ml_tpu.serving import place\n"
        "p = place([f't{i}' for i in range(32)],\n"
        "          [f'r{i}' for i in range(3)])\n"
        "print(json.dumps({t: [v.primary, v.shadow]\n"
        "                  for t, v in p.items()}))\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="99")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-500:]
    theirs = json.loads(out.stdout.strip().splitlines()[-1])
    assert theirs == {
        t: [v.primary, v.shadow] for t, v in here.items()
    }


def test_placement_balance_and_primary_shadow_invariants():
    for n_t, n_r in ((50, 2), (256, 4), (256, 6), (100, 3)):
        pl = place(_tenants(n_t), _replicas(n_r))
        cap = math.ceil(n_t / n_r)
        loads = load_by_replica(pl)
        assert max(loads.values()) <= cap
        assert set(loads) <= set(_replicas(n_r))
        for t, p in pl.items():
            assert p.shadow is not None
            assert p.shadow != p.primary
    # Single replica: no shadow possible, surfaced as None.
    pl = place(_tenants(8), ["only"])
    assert all(p.primary == "only" and p.shadow is None
               for p in pl.values())


def test_placement_minimal_movement_join_leave():
    """<= ceil(T/N) moved primaries across join/leave in the fleet
    regime (tenants-per-replica >= ~16 — the censuses the replicated
    benches run), and zero movement on a no-op recompute."""
    for n_t in (64, 256):
        tenants = _tenants(n_t)
        for n in (1, 2, 3, 4):
            if n_t / n < 16:
                continue
            old = place(tenants, _replicas(n))
            new = place(tenants, _replicas(n + 1))
            bound = math.ceil(n_t / n)
            joined = moved_primaries(old, new)
            assert len(joined) <= bound, (n_t, n, len(joined), bound)
            # leave == the same transition reversed.
            left = moved_primaries(new, old)
            assert len(left) <= bound
            # no-op recompute moves nothing.
            assert moved_primaries(old, place(tenants,
                                              _replicas(n))) == []


def test_placement_errors_and_shadow_for():
    with pytest.raises(ValueError, match="at least one replica"):
        place(_tenants(3), [])
    with pytest.raises(ValueError, match="duplicate tenant"):
        place(["a", "a"], _replicas(2))
    pref = preference("t3", _replicas(4))
    assert shadow_for("t3", _replicas(4)) == pref[0]
    assert shadow_for("t3", _replicas(4),
                      exclude={pref[0]}) == pref[1]
    assert shadow_for("t3", ["r0"], exclude={"r0"}) is None


# ---------------------------------------------------------------------------
# file KV + membership
# ---------------------------------------------------------------------------


def test_file_kv_set_without_overwrite_has_one_winner(tmp_path):
    """`allow_overwrite=False` is an election (claim_promotion): of many
    writers racing for one key exactly one returns, the others raise
    ALREADY_EXISTS, and the value is the winner's."""
    kv_dir = str(tmp_path / "kv")
    workers, rounds = 16, 40
    won = [[] for _ in range(rounds)]
    errors = []
    barrier = threading.Barrier(workers)

    def race(i):
        kv = FileKVClient(kv_dir)
        for r in range(rounds):
            barrier.wait(timeout=30)
            try:
                kv.key_value_set(f"promote/{r}", str(i))
                won[r].append(i)
            except RuntimeError as e:
                if "ALREADY_EXISTS" not in str(e):
                    errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=race, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert [len(w) for w in won] == [1] * rounds
    kv = FileKVClient(kv_dir)
    assert [kv.blocking_key_value_get(f"promote/{r}", 10)
            for r in range(rounds)] == [str(w[0]) for w in won]
    assert not [n for n in os.listdir(kv_dir) if ".tmp." in n]


def test_file_kv_client(tmp_path):
    kv = FileKVClient(str(tmp_path / "kv"))
    kv.key_value_set("a/b", "one")
    assert kv.blocking_key_value_get("a/b", 10) == "one"
    with pytest.raises(RuntimeError, match="ALREADY_EXISTS"):
        kv.key_value_set("a/b", "two")
    kv.key_value_set("a/b", "two", allow_overwrite=True)
    kv.key_value_set("a/c", "three")
    kv.key_value_set("z", "zed")
    assert kv_list(kv, "a/") == {"a/b": "two", "a/c": "three"}
    kv.key_value_delete("a/b")
    kv.key_value_delete("a/b")          # idempotent
    with pytest.raises(RuntimeError, match="DEADLINE_EXCEEDED"):
        kv.blocking_key_value_get("a/b", 30)
    # A blocked get is satisfied by a concurrent writer.
    got = {}

    def reader():
        got["v"] = kv.blocking_key_value_get("late", 5000)

    th = threading.Thread(target=reader)
    th.start()
    time.sleep(0.05)
    kv.key_value_set("late", "arrived")
    th.join(timeout=10)
    assert got["v"] == "arrived"


def test_membership_roster_heartbeats_fail_relay(tmp_path):
    kv = FileKVClient(str(tmp_path / "kv"))
    m = MembershipClient(kv, "oni/testfleet")
    m.register("r0", {"port": 1})
    m.register("r1", {"port": 2})
    assert set(m.members()) == {"r0", "r1"}
    assert m.members()["r1"]["meta"]["port"] == 2
    hb = HeartbeatPublisher(m, "r0", 0.03)
    try:
        time.sleep(0.12)
        beats = m.heartbeats()
        assert beats["r0"]["seq"] >= 2
        assert "r0" in m.alive(5.0)
        assert "r1" not in m.alive(5.0)     # never beat
    finally:
        hb.stop()
    m.fail("r0", "injected stall")
    assert m.failures()["r0"]["reason"] == "injected stall"
    m.clear_failure("r0")
    assert m.failures() == {}
    m.deregister("r0")
    assert set(m.members()) == {"r1"}


# ---------------------------------------------------------------------------
# frame protocol
# ---------------------------------------------------------------------------


def test_frame_roundtrip_and_oversize_guard():
    import socket as socket_mod

    a, b = socket_mod.socketpair()
    try:
        send_frame(a, {"op": "ping", "payload": list(range(100))})
        assert recv_frame(b)["payload"][-1] == 99
        # EOF mid-frame surfaces as ConnectionError, not a hang.
        a.close()
        with pytest.raises((ConnectionError, OSError)):
            recv_frame(b)
    finally:
        b.close()
    # An absurd announced length fails loudly before allocating.
    c, d = socket_mod.socketpair()
    try:
        import struct

        c.sendall(struct.pack("!I", (1 << 31) - 1))
        with pytest.raises(ConnectionError, match="oversized"):
            recv_frame(d)
    finally:
        c.close()
        d.close()


# ---------------------------------------------------------------------------
# replica + router end-to-end (in-process replicas, real sockets)
# ---------------------------------------------------------------------------


_CFG = ServingConfig(fleet_max_batch=32, fleet_max_wait_ms=5.0,
                     device_score_min=None)


@pytest.fixture()
def fleet3():
    """3 in-process replicas + router + 6 synthetic tenants, started;
    yields (router, replicas, days) and tears everything down."""
    replicas = {f"r{i}": ReplicaServer(f"r{i}", _CFG)
                for i in range(3)}
    router = FleetRouter(_CFG)
    days = {}
    try:
        for rid, rep in replicas.items():
            router.connect_replica(rid, rep.host, rep.port)
        for i in range(6):
            t = f"t{i}"
            days[t] = _synthetic_day(n_events=48, seed=200 + i)
            rows, model, cuts = days[t]
            router.add_tenant(TenantSpec(tenant=t, dsource="dns"),
                              cuts, model)
        router.start(warmup=False)
        yield router, replicas, days
    finally:
        router.close()
        for rep in replicas.values():
            rep.stop()


def _wait_failovers(router, timeout_s=15.0):
    """Failover completion (promotion + journal replay + shadow
    backfill) runs on a reader thread; poll stats() until the
    recovery record lands instead of racing it."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        fos = router.stats()["failovers"]
        if fos:
            return fos
        time.sleep(0.02)
    return router.stats()["failovers"]


def _oracle(days, t, rows):
    _, model, cuts = days[t]
    feats = DnsEventFeaturizer(cuts)(rows)
    return score_features(model, feats, "dns")


def test_router_score_parity_submit_and_submit_many(fleet3):
    """Routed scores — single submits AND chunked submit_many with
    batched responses — are bit-identical to the single-process
    oracle for every tenant."""
    router, replicas, days = fleet3
    futs = {}
    for t, (rows, _, _) in days.items():
        futs[t] = [router.submit(t, r) for r in rows[:20]]
        futs[t] += router.submit_many(t, rows[20:44])
    router.flush()
    for t, fs in futs.items():
        got = np.array([f.result(timeout=30.0)[0] for f in fs])
        np.testing.assert_array_equal(
            got, _oracle(days, t, days[t][0][:44]))
    # Every tenant is placed with a live shadow distinct from primary.
    for t, p in router.placement().items():
        assert p.shadow is not None and p.shadow != p.primary
    # Route edges priced: every replica edge saw its events.
    stats = router.stats()
    assert sum(e["events"] for e in stats["edges"].values()) \
        == sum(len(fs) for fs in futs.values())


def test_router_kill_replica_zero_failed_futures(fleet3):
    """THE chaos pin (acceptance criteria): kill a replica with
    events in flight — zero failed futures (the admission journal
    replays the victims onto promoted shadows), bit-identical scores
    for tenants on surviving replicas, and the promoted primary IS
    the old shadow (warm standby, not a re-placement)."""
    router, replicas, days = fleet3
    placement = router.placement()
    victim = placement["t0"].primary
    old = {t: placement[t] for t in days}
    futs = {t: [router.submit(t, r) for r in days[t][0][:30]]
            for t in days}
    replicas[victim].kill()
    router.flush()
    time.sleep(0.1)
    router.flush()
    for t, fs in futs.items():
        got = np.array([f.result(timeout=30.0)[0] for f in fs])
        np.testing.assert_array_equal(
            got, _oracle(days, t, days[t][0][:30]))
    new = router.placement()
    for t in days:
        if old[t].primary == victim:
            # shadow promotion, in place.
            assert new[t].primary == old[t].shadow
        else:
            # tenants that never touched the dead replica do not move.
            assert new[t].primary == old[t].primary
        assert new[t].primary != victim
        assert new[t].shadow != victim
        assert new[t].shadow != new[t].primary
    fos = _wait_failovers(router)
    assert len(fos) == 1
    assert fos[0]["resend_failures"] == 0
    assert fos[0]["recovery_s"] < 10.0
    # Post-failover traffic stays bit-identical on every tenant.
    futs2 = {t: router.submit_many(t, days[t][0][:12]) for t in days}
    router.flush()
    for t, fs in futs2.items():
        got = np.array([f.result(timeout=30.0)[0] for f in fs])
        np.testing.assert_array_equal(
            got, _oracle(days, t, days[t][0][:12]))


def test_router_publish_fanout_keeps_shadow_fresh(fleet3):
    """publish() fans out to primary AND shadow, so a post-publish
    failover serves the REFRESHED model — the shadow was never
    stale."""
    router, replicas, days = fleet3
    rows, model, cuts = days["t0"]
    rng = np.random.default_rng(11)
    k = model.num_topics
    ips = sorted(model.ip_index, key=model.ip_index.get)
    vocab = sorted(model.word_index, key=model.word_index.get)
    from oni_ml_tpu.scoring import ScoringModel

    model2 = ScoringModel.from_results(
        ips, rng.dirichlet(np.ones(k), size=len(ips)),
        vocab, rng.dirichlet(np.ones(len(vocab)), size=k).T,
        fallback=0.1,
    )
    version = router.publish("t0", model2)
    assert version == 2
    victim = router.placement()["t0"].primary
    replicas[victim].kill()
    time.sleep(0.1)
    futs = router.submit_many("t0", rows[:16])
    router.flush()
    got = np.array([f.result(timeout=30.0)[0] for f in futs])
    feats = DnsEventFeaturizer(cuts)(rows[:16])
    np.testing.assert_array_equal(
        got, score_features(model2, feats, "dns"))


def test_router_drain_join_rolling_redeploy(fleet3):
    """Drain-one-replica-at-a-time: routing flips to warm shadows
    (graceful), the drained replica reports a clean drain, a
    replacement joins with bounded movement, and traffic never
    breaks."""
    router, replicas, days = fleet3
    placement = router.placement()
    target = placement["t0"].primary
    futs = {t: router.submit_many(t, days[t][0][:16]) for t in days}
    res = router.drain_replica(target)
    assert res["drained"] is True
    for t, fs in futs.items():
        got = np.array([f.result(timeout=30.0)[0] for f in fs])
        np.testing.assert_array_equal(
            got, _oracle(days, t, days[t][0][:16]))
    after_drain = router.placement()
    assert all(p.primary != target and p.shadow != target
               for p in after_drain.values())
    assert target not in router.stats()["replicas"]
    # Respawn under a fresh id and join: minimal movement, and the
    # joined replica serves its share bit-identically.
    spare = ReplicaServer("r9", _CFG)
    try:
        joined = router.join_replica("r9", spare.host, spare.port,
                                     warmup=False)
        moved = moved_primaries(
            after_drain, router.placement())
        assert len(moved) == joined["moved"]
        assert joined["moved"] <= math.ceil(len(days) / 2)
        futs2 = {t: router.submit_many(t, days[t][0][:10])
                 for t in days}
        router.flush()
        for t, fs in futs2.items():
            got = np.array([f.result(timeout=30.0)[0] for f in fs])
            np.testing.assert_array_equal(
                got, _oracle(days, t, days[t][0][:10]))
    finally:
        spare.stop()


def test_router_drain_last_replica_refused(fleet3):
    router, replicas, days = fleet3
    live = router.stats()["replicas"]
    router.drain_replica(live[0])
    router.drain_replica(live[1])
    with pytest.raises(RuntimeError, match="last replica"):
        router.drain_replica(live[2])


def test_router_fail_key_triggers_monitor_failover(tmp_path):
    """The PR 11 relay, serving-side: a replica posting its fail key
    is failed over by the router's monitor without waiting for a
    connection EOF or heartbeat timeout."""
    cfg = ServingConfig(fleet_max_batch=32, fleet_max_wait_ms=5.0,
                        device_score_min=None,
                        replica_heartbeat_s=0.05)
    kv = FileKVClient(str(tmp_path / "kv"))
    replicas = {f"r{i}": ReplicaServer(f"r{i}", cfg, kv=kv)
                for i in range(2)}
    router = FleetRouter(cfg, kv=kv)
    try:
        for rid, rep in replicas.items():
            router.connect_replica(rid, rep.host, rep.port)
        rows, model, cuts = _synthetic_day(n_events=32, seed=400)
        for i in range(4):
            router.add_tenant(TenantSpec(tenant=f"t{i}",
                                         dsource="dns"), cuts, model)
        router.start(warmup=False)
        victim = router.placement()["t0"].primary
        MembershipClient(kv).fail(victim, "backend lost")
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if router.stats()["failovers"]:
                break
            time.sleep(0.02)
        fos = router.stats()["failovers"]
        assert fos and fos[0]["replica"] == victim
        assert victim not in router.stats()["replicas"]
        futs = router.submit_many("t0", rows[:8])
        router.flush()
        for f in futs:
            f.result(timeout=30.0)
        # Respawn under the SAME id and rejoin: connect clears the
        # stale fail key, so the monitor must not re-kill the healthy
        # replacement (review regression).
        replicas[victim].stop()
        respawn = ReplicaServer(victim, cfg, kv=kv)
        replicas[victim + "_v2"] = respawn
        router.join_replica(victim, respawn.host, respawn.port,
                            warmup=False)
        time.sleep(cfg.replica_heartbeat_s * 4)
        assert victim in router.stats()["replicas"]
        assert len(router.stats()["failovers"]) == len(fos)
        futs = router.submit_many("t0", rows[:6])
        router.flush()
        for f in futs:
            f.result(timeout=30.0)
    finally:
        router.close()
        for rep in replicas.values():
            rep.stop()


def test_router_admission_window_blocks_and_prices_stall():
    """route_max_inflight bounds outstanding events per edge; a
    saturating burst stalls at the window and the stall is priced
    into the edge stats (the Little's-law bound the scaling bench
    leans on)."""
    cfg = ServingConfig(fleet_max_batch=64, fleet_max_wait_ms=20.0,
                        device_score_min=None, route_max_inflight=8)
    rep = ReplicaServer("r0", cfg)
    router = FleetRouter(cfg)
    try:
        router.connect_replica("r0", rep.host, rep.port)
        rows, model, cuts = _synthetic_day(n_events=64, seed=500)
        router.add_tenant(TenantSpec(tenant="t0", dsource="dns"),
                          cuts, model)
        router.start(warmup=False)
        futs = [router.submit("t0", rows[i % len(rows)])
                for i in range(200)]
        router.flush()
        for f in futs:
            f.result(timeout=30.0)
        edge = router.stats()["edges"]["r0"]
        assert edge["events"] == 200
        assert edge["admission_stall_s"] > 0.0
    finally:
        router.close()
        rep.stop()


def test_replica_protocol_ops_direct(tmp_path):
    """Raw protocol against one replica: ping, idempotent add_tenant
    (router_version decides news), publish version bump, stats,
    drain."""
    rep = ReplicaServer("rx", _CFG)
    events = []
    link = ReplicaLink("rx", rep.host, rep.port, op_timeout_s=30.0,
                       on_score=lambda r, m: events.append(m),
                       on_down=lambda r, m: None)
    try:
        assert link.call({"op": "ping"})["ok"] is True
        rows, model, cuts = _synthetic_day(n_events=32, seed=600)
        req = {
            "op": "add_tenant",
            "spec": {"tenant": "ta", "dsource": "dns"},
            "cuts": cuts, "model": model, "router_version": 1,
        }
        assert link.call(dict(req))["published"] is True
        # Re-push at the same router version: no stack churn.
        rsp = link.call(dict(req))
        assert rsp["published"] is False
        assert rsp["version"] == 1
        rsp = link.call({"op": "publish", "tenant": "ta",
                         "model": model, "router_version": 2})
        assert rsp["version"] == 2
        link.send_submit(101, "ta", rows[0])
        link.call({"op": "flush"})
        deadline = time.monotonic() + 10.0
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)
        assert events and events[0]["id"] == 101
        assert np.isfinite(events[0]["score"])
        stats = link.call({"op": "stats"})
        assert stats["tenants"] == ["ta"]
        assert stats["events_scored"] == 1
        assert link.call({"op": "drain"})["drained"] is True
        with pytest.raises(RuntimeError, match="unknown op"):
            link.call({"op": "nope"})
    finally:
        link.close()
        rep.stop()


def test_route_cli_dry_run_acceptance(capsys):
    """`ml_ops route --dry-run synthetic:4x3`: parity, mid-stream
    kill with zero dropped events, rolling redeploy — rc 0 and an ok
    summary."""
    rc = route_main(["--dry-run", "synthetic:4x3"])
    out = capsys.readouterr().out
    summary = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert summary["route_dry_run"] == "ok"
    assert summary["chaos_dropped"] == 0
    assert summary["failovers"]
    assert summary["redeploy"]["drained"]["drained"] is True


# ---------------------------------------------------------------------------
# load_gen: shed-path regression + replicated harness
# ---------------------------------------------------------------------------


def test_load_gen_shed_path_releases_collectors():
    """Regression (PR 15 satellite): a mid-replay AdmissionRejected in
    paged/reject mode must SHED the event — releasing the tenant's
    collector slot — not abort the run or leak the collector thread
    spinning on a slot no future will ever fill."""
    import load_gen

    before = threading.active_count()
    res = load_gen.run_fleet_slo(
        6, "poisson:1", n_events=600, rate_eps=20000.0, zipf_s=1.2,
        hot_tenants=2, warm_tenants=2, admission="reject",
        max_batch=64, max_wait_ms=20.0, device_score_min=None,
        tenant_queue_max=4,
    )
    agg = res["aggregate"]
    assert agg["shed"] > 0
    assert agg["errors"] == 0
    assert agg["shed"] + agg["resolved"] == res["n_events"]
    # Per-tenant shed accounting rides the payload.
    assert sum(v["shed"] for v in res["tenants"].values()) \
        == agg["shed"]
    # Collector threads joined — nothing left spinning.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline \
            and threading.active_count() > before:
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_load_gen_replicated_slo_harness():
    """The serving_slo_replicated harness at toy scale (thread-mode
    replicas): scaling legs with zero errors and zero in-window
    retraces, chaos leg with zero failed futures for victims AND
    survivors, bit-identical survivor scores, measured failover p999
    and recovery, and the payload keys bench_diff gates."""
    import load_gen

    res = load_gen.run_replicated_slo(
        replica_counts=(1, 2), n_tenants=8, zipf_s=1.1,
        events_per_replica=256, chaos_events=384,
        chaos_rate_eps=2500.0, spawn="thread",
        device_score_min=None, max_wait_ms=10.0, route_window=32,
        day_events=96,
    )
    assert res["replica_counts"] == [1, 2]
    for leg in res["scaling"].values():
        assert leg["errors"] == 0
        assert leg["sustained_eps"] > 0
    assert res["replica_scaling_efficiency"] is not None
    chaos = res["chaos"]
    assert chaos["errors_surviving"] == 0
    assert chaos["errors_victim_tenants"] == 0
    assert chaos["survivor_bit_identical"] is True
    assert chaos["failover_record"]["resend_failures"] == 0
    assert res["time_to_recovery_s"] >= 0
    assert res["failover_p999_ms"] is None \
        or res["failover_p999_ms"] > 0


def test_bench_diff_replicated_directions(tmp_path):
    """Direction gates for serving_slo_replicated: efficiency and
    per-count sustained eps higher-better; failover p999 and
    time-to-recovery lower-better."""
    import bench_diff

    base = {
        "metric": "serving_slo_replicated", "value": 10000,
        "unit": "events/sec",
        "secondary": {"serving_slo_replicated": {
            "value": 10000, "unit": "events/sec",
            "replica_scaling_efficiency": 0.95,
            "failover_p999_ms": 200.0,
            "time_to_recovery_s": 0.2,
            "sustained_eps_by_count": {"1": 2800, "2": 5400,
                                       "4": 10000},
        }},
    }

    def diff(**changes):
        import copy

        new = copy.deepcopy(base)
        new["secondary"]["serving_slo_replicated"].update(changes)
        old_p = tmp_path / "old.json"
        new_p = tmp_path / "new.json"
        old_p.write_text(json.dumps(base))
        new_p.write_text(json.dumps(new))
        return bench_diff.main([str(old_p), str(new_p)])

    assert diff() == 0
    assert diff(replica_scaling_efficiency=0.6) == 1
    assert diff(failover_p999_ms=400.0) == 1
    assert diff(time_to_recovery_s=0.5) == 1
    assert diff(time_to_recovery_s=0.05) == 0          # improvement
    assert diff(sustained_eps_by_count={"1": 2800, "2": 3000,
                                        "4": 10000}) == 1
    # Headline-form capture compares too.
    old_p = tmp_path / "ho.json"
    new_p = tmp_path / "hn.json"
    old_p.write_text(json.dumps(
        base["secondary"]["serving_slo_replicated"]))
    worse = dict(base["secondary"]["serving_slo_replicated"],
                 replica_scaling_efficiency=0.5)
    new_p.write_text(json.dumps(worse))
    assert bench_diff.main([str(old_p), str(new_p)]) == 1


def test_replica_subprocess_spawn_and_shutdown(tmp_path):
    """One REAL `ml_ops replica` subprocess: port-file handshake, KV
    registration + heartbeats, protocol round trip, clean shutdown
    over the wire (rc 0)."""
    from oni_ml_tpu.runner.route import _spawn_replica

    kv_dir = str(tmp_path / "kv")
    proc, host, port = _spawn_replica("rsub", kv_dir, str(tmp_path),
                                      platform="cpu")
    link = None
    try:
        link = ReplicaLink("rsub", host, port, op_timeout_s=60.0,
                           on_score=lambda r, m: None,
                           on_down=lambda r, m: None)
        assert link.call({"op": "ping"})["ok"] is True
        m = MembershipClient(FileKVClient(kv_dir))
        assert "rsub" in m.members()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and "rsub" not in m.alive(5.0):
            time.sleep(0.05)
        assert "rsub" in m.alive(5.0)
        link.call({"op": "shutdown"})
        assert proc.wait(timeout=60) == 0
        # The ready line names the platform the replica actually runs
        # on — the one its spawner named, nothing defaulted.
        with open(tmp_path / "rsub.log") as f:
            ready = [ln for ln in f if ln.startswith("REPLICA_READY rsub")]
        assert ready and ready[0].rstrip().endswith("platform=cpu")
    finally:
        if link is not None:
            link.close()
        if proc.poll() is None:
            proc.kill()


def test_replica_without_its_platform_fails_at_once(tmp_path):
    """A replica told to run on a platform it cannot have — here a TPU
    on a machine without one; on the chip, the TPU its parent holds —
    exits at start-up with the reason, and the spawner raises with it
    instead of waiting out the handshake."""
    from oni_ml_tpu.runner.route import _spawn_replica

    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as err:
        _spawn_replica("rtpu", str(tmp_path / "kv"), str(tmp_path),
                       timeout_s=120.0, platform="tpu")
    assert time.monotonic() - t0 < 60.0
    assert "REPLICA_FAILED rtpu platform=tpu" in str(err.value)


def test_spawning_replicas_needs_a_named_platform():
    """No entry point picks a replica's platform: the library spawner
    takes it as a required argument, and the CLIs refuse to spawn
    without --replica-platform."""
    from oni_ml_tpu.runner import route
    from oni_ml_tpu.runner.route import _spawn_replica

    with pytest.raises(TypeError, match="platform"):
        _spawn_replica("r0", "kv", ".")
    with pytest.raises(SystemExit):
        route.build_replica_parser().parse_args(["--id", "r0"])


def test_trace_view_route_lanes_and_summary():
    """route/membership/failover journal records render as counter
    lanes + instants, and the terminal summary prints the per-replica
    routing table with the failover tally."""
    import io

    import trace_view

    records = [
        {"kind": "route", "edge": "r0", "events": 1024,
         "bytes": 90000, "inflight": 12, "mono_ns": 1_000},
        {"kind": "membership", "event": "join", "replica": "r2",
         "moved": 10, "reshadowed": 4, "mono_ns": 2_000},
        {"kind": "failover", "replica": "r1", "reason": "conn lost",
         "promoted": 3, "reshadowed": 2, "inflight": 5,
         "mono_ns": 3_000},
        {"kind": "failover", "replica": "r1", "event": "recovered",
         "promoted": 3, "resent": 5, "resend_failures": 0,
         "recovery_s": 0.03, "mono_ns": 4_000},
        {"kind": "route", "edge": "r0", "event": "close",
         "events": 2048, "bytes": 180000, "errors": 0, "resends": 5,
         "admission_stall_s": 0.5, "mono_ns": 5_000},
    ]
    trace = trace_view.journal_to_trace(records)
    names = [e["name"] for e in trace["traceEvents"]]
    assert "route r0" in names
    assert any(n.startswith("fleet join") for n in names)
    assert "FAILOVER: r1" in names
    assert "FAILOVER recovered: r1" in names
    rows = trace_view.route_table(records)
    assert rows == [{"edge": "r0", "events": 2048, "bytes": 180000,
                     "resends": 5, "admission_stall_s": 0.5}]
    buf = io.StringIO()
    trace_view.print_summary(records, 0, out=buf)
    out = buf.getvalue()
    assert "replicated routing" in out
    assert "failover r1: 3 promoted, 5 in-flight replayed" in out


def test_router_journals_route_membership_failover(tmp_path, fleet3):
    """A journaled router run emits the three new record kinds with
    the schema's fields (the journal-schema lint pins the vocabulary;
    this pins the live emission path)."""
    from oni_ml_tpu.telemetry.journal import Journal

    router, replicas, days = fleet3
    path = tmp_path / "router_journal.jsonl"
    journal = Journal(str(path))
    router._journal = journal
    victim = router.placement()["t0"].primary
    futs = {t: router.submit_many(t, days[t][0][:8]) for t in days}
    replicas[victim].kill()
    router.flush()
    for fs in futs.values():
        for f in fs:
            f.result(timeout=30.0)
    assert _wait_failovers(router)
    router.close()
    journal.close()
    kinds = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            kinds.setdefault(rec["kind"], []).append(rec)
    assert "failover" in kinds
    assert any(r.get("event") == "recovered" for r in kinds["failover"])
    assert "route" in kinds
    assert any(r.get("event") == "close" for r in kinds["route"])


def test_dynamic_scorer_reapplies_plan_guard(tmp_path):
    """Review regression: a dynamic FleetScorer starts with zero lanes
    (guard unreachable) — add_tenant must re-apply the plan-flush
    degradation guard at the GROWN capacity: a plan max_batch above
    total admission capacity degrades to the default, and takes
    effect once capacity covers it."""
    from oni_ml_tpu import plans
    from oni_ml_tpu.plans import KNOBS, PlanStore, use_store
    from oni_ml_tpu.serving import FleetRegistry, FleetScorer

    st = PlanStore(str(tmp_path / "plans.jsonl"))
    fp = plans.fingerprint(KNOBS["fleet_max_batch"].scope)
    st.record("fleet_max_batch", fp, "*", 100, source="probe")
    rows, model, cuts = _synthetic_day(n_events=24, seed=700)
    with use_store(st):
        fleet = FleetRegistry()
        scorer = FleetScorer(fleet, {},
                             ServingConfig(device_score_min=None),
                             dynamic=True)
        try:
            for i in range(3):
                t = f"t{i}"
                fleet.add_tenant(TenantSpec(tenant=t, dsource="dns",
                                            queue_max=40))
                fleet.publish(t, model, source="test")
                scorer.add_tenant(
                    TenantSpec(tenant=t, dsource="dns", queue_max=40),
                    DnsEventFeaturizer(cuts))
                if (i + 1) * 40 < 100:
                    # Capacity 40/80 cannot reach a 100-event flush.
                    assert scorer.max_batch \
                        == ServingConfig.fleet_max_batch
                    assert scorer.plan["max_batch"]["source"] \
                        == "default"
                else:
                    # Capacity 120 covers the measured plan value.
                    assert scorer.max_batch == 100
                    assert scorer.plan["max_batch"]["source"] == "plan"
        finally:
            scorer.close()


def test_stuck_replica_posts_fail_key_and_stops_beating(tmp_path):
    """Review regression: a STUCK replica (healthy process, broken
    scoring backend) must post the membership fail key and stop
    heartbeating — the router's monitor then promotes its shadows
    instead of trusting a liveness signal decoupled from scoring."""
    kv = FileKVClient(str(tmp_path / "kv"))
    cfg = ServingConfig(fleet_max_batch=32, fleet_max_wait_ms=5.0,
                        device_score_min=None,
                        replica_heartbeat_s=0.03)
    state = {"stuck": False}

    def health():
        if state["stuck"]:
            raise RuntimeError("backend lost (injected)")

    rep = ReplicaServer("rw", cfg, kv=kv, health_check=health)
    try:
        m = MembershipClient(kv)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and "rw" not in m.alive(5.0):
            time.sleep(0.02)
        assert "rw" in m.alive(5.0)
        assert m.failures() == {}
        state["stuck"] = True
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and "rw" not in m.failures():
            time.sleep(0.02)
        fail = m.failures()["rw"]
        assert "health check failed" in fail["reason"]
        # Heartbeats stopped: the silence corroborates the fail key.
        seq = m.heartbeats()["rw"]["seq"]
        time.sleep(0.2)
        assert m.heartbeats()["rw"]["seq"] == seq
    finally:
        rep.stop()
