"""`ml_ops replica` / `ml_ops route` — the replicated-serving CLI.

``ml_ops replica --id r0`` runs ONE serve replica process
(serving/replica.py): the full FleetRegistry/FleetScorer stack behind
the framed socket protocol, heartbeating into the shared file-KV
membership directory.  ``ml_ops route`` runs the router in front
(serving/router.py): it spawns (``--replicas N``) or attaches to
(``--connect``) the replicas, places every manifest tenant on a
primary + shadow via the consistent-hash ring, and then speaks the
fleet serve-stream protocol on stdin/stdout — ``<tenant>\\t<csv line>``
in, flagged events out — exactly like ``ml_ops serve --fleet``, except
the scoring happens N processes away and a dead replica costs a
shadow promotion instead of the fleet.

Zero-downtime redeploy from the CLI: ``--redeploy-after N`` performs a
rolling drain-one-join-one cycle over every replica after N events —
the acceptance path for ROADMAP item 5's "drain-one-replica-at-a-time
behind the router".

``--dry-run synthetic:TxR`` is the self-contained acceptance run
(in-process replicas, synthetic tenant days): packed scoring parity,
a mid-stream replica KILL with zero dropped events, and a rolling
redeploy, reported as one JSON summary with rc 0/1.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque

import numpy as np


def build_replica_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ml_ops replica",
        description="Run one serve replica of the replicated fleet.",
    )
    p.add_argument("--id", required=True, help="replica id (becomes "
                   "the membership/journal key)")
    p.add_argument("--platform", required=True, metavar="PLATFORM",
                   help="jax platform this replica runs on (cpu, tpu): "
                   "named by whoever starts the replica, never "
                   "defaulted — one process holds a chip at a time, so "
                   "a replica started beside a process that holds the "
                   "chip says so and exits instead of waiting for it")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral)")
    p.add_argument("--kv-dir", default="",
                   help="shared file-KV membership directory "
                   "(parallel/membership.FileKVClient); empty = no "
                   "membership/heartbeats")
    p.add_argument("--kv-connect", default="", metavar="HOST:PORT",
                   help="TCP KV server to register membership with "
                   "(parallel/membership.TcpKVClient) — the cross-host "
                   "alternative to --kv-dir")
    p.add_argument("--port-file", default="",
                   help="write 'host port' here once listening (the "
                   "spawn handshake)")
    p.add_argument("--fleet-max-batch", type=int, default=None)
    p.add_argument("--fleet-max-wait-ms", type=float, default=None)
    p.add_argument("--device-score-min", default=None,
                   help="int threshold, 'none' to pin host scoring, "
                   "or unset for the measured auto calibration")
    return p


def _make_kv(kv_dir: str, kv_connect: str):
    """Membership transport off the CLI flags: a TCP KV client
    (cross-host), the shared file-KV directory (same-host), or None
    (no membership)."""
    if kv_connect:
        from ..parallel.membership import TcpKVClient

        host, _, port = kv_connect.partition(":")
        return TcpKVClient(host or "127.0.0.1", int(port))
    if kv_dir:
        from ..parallel.membership import FileKVClient

        return FileKVClient(kv_dir)
    return None


def _parse_device_score_min(v):
    if v is None:
        return 0
    if isinstance(v, str) and v.lower() in ("none", "host"):
        return None
    return int(v)


def replica_main(argv: "list[str] | None" = None) -> int:
    import dataclasses

    from ..config import ServingConfig
    from ..serving import ReplicaServer

    args = build_replica_parser().parse_args(argv)
    # The platform is the caller's, fixed before any backend exists,
    # and claimed NOW: a chip another process holds fails here, at
    # start-up, with the reason in the log the spawner reads.
    import jax

    jax.config.update("jax_platforms", args.platform)
    try:
        platform = jax.devices()[0].platform
    except Exception as e:
        print(f"REPLICA_FAILED {args.id} platform={args.platform}: "
              f"{e!r}"[:600], flush=True)
        return 1
    cfg = ServingConfig(
        device_score_min=_parse_device_score_min(args.device_score_min),
    )
    if args.fleet_max_batch is not None:
        cfg = dataclasses.replace(
            cfg, fleet_max_batch=args.fleet_max_batch)
    if args.fleet_max_wait_ms is not None:
        cfg = dataclasses.replace(
            cfg, fleet_max_wait_ms=args.fleet_max_wait_ms)
    kv = _make_kv(args.kv_dir, args.kv_connect)
    # Persistent compilation cache + compile counters BEFORE the first
    # trace: replicas share the cache, so a respawned replica (rolling
    # redeploy) warm-starts its compiled family from disk — the
    # zero-retrace recovery contract — and the stats op's counter
    # deltas are the proof.
    from ..plans import warmup as plans_warmup

    plans_warmup.setup_compilation_cache()
    plans_warmup._ensure_listener()
    server = ReplicaServer(
        args.id, cfg, host=args.host, port=args.port, kv=kv,
    )
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{server.host} {server.port}\n")
        os.replace(tmp, args.port_file)
    print(f"REPLICA_READY {args.id} {server.host} {server.port} "
          f"platform={platform}", flush=True)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    # Exit on SIGTERM/SIGINT or a shutdown op over the wire.
    while not stop.is_set() and not server.stopped.wait(0.2):
        pass
    server.stop()
    return 0


# ---------------------------------------------------------------------------
# router CLI
# ---------------------------------------------------------------------------


def build_route_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ml_ops route",
        description="Async fleet router over N serve replicas: "
        "consistent-hash tenant placement, shadow-promotion failover, "
        "rolling redeploy.",
    )
    p.add_argument("--fleet", default="",
                   help="fleet manifest (serving/tenants.py) naming "
                   "the tenants and their day_dirs")
    p.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="spawn N replica subprocesses (ml_ops "
                   "replica) on this host")
    p.add_argument("--replica-platform", default=None, metavar="PLATFORM",
                   help="jax platform the spawned replicas run on (cpu, "
                   "tpu) — required whenever this router spawns "
                   "replicas (--replicas, --autoscale); never defaulted")
    p.add_argument("--connect", default="", metavar="ID=HOST:PORT,...",
                   help="attach to already-running replicas instead "
                   "of spawning")
    p.add_argument("--kv-dir", default="",
                   help="membership directory shared with the "
                   "replicas (default: a temp dir when spawning)")
    p.add_argument("--kv-listen", default="", metavar="[HOST][:PORT]",
                   help="run the TCP KV membership server "
                   "(parallel/membership.KVServer) here and point "
                   "spawned replicas at it — the cross-host control "
                   "plane (empty PORT = ephemeral)")
    p.add_argument("--kv-connect", default="", metavar="HOST:PORT",
                   help="join an existing TCP KV membership server "
                   "(another router's --kv-listen)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the Little's-law autoscaler: spawn/drain "
                   "replicas between autoscale_min_replicas and "
                   "autoscale_max_replicas to hold admission-window "
                   "occupancy inside the hysteresis band")
    p.add_argument("--threshold", type=float, default=None,
                   help="suspicion threshold for flagged output "
                   "(default: ServingConfig)")
    p.add_argument("--top-domains", default=None)
    p.add_argument("--redeploy-after", type=int, default=0,
                   metavar="N",
                   help="after N routed events, rolling-redeploy "
                   "every spawned replica (drain one, respawn, join, "
                   "next)")
    p.add_argument("--dry-run", default="", metavar="synthetic[:TxR]",
                   help="self-contained acceptance run: T synthetic "
                   "tenants over R in-process replicas (default 6x3) "
                   "with a mid-stream kill and a rolling redeploy")
    return p


def _spawn_replica(rid: str, kv_flags: "str | list[str]", workdir: str,
                   extra: "list[str] | None" = None,
                   timeout_s: float = 120.0, *, platform: str):
    """One `ml_ops replica` subprocess; returns (proc, host, port)
    after the port-file handshake.  `kv_flags` is either the shared
    file-KV directory (the historical signature) or a ready-made flag
    list (["--kv-connect", "host:port"] for the TCP control plane).
    `platform` is the jax platform the replica runs on: every caller
    names it, and it rides the child's command line (placing one
    replica per chip is ROADMAP A5)."""
    if isinstance(kv_flags, str):
        kv_flags = ["--kv-dir", kv_flags]
    port_file = os.path.join(workdir, f"{rid}.port")
    try:
        os.remove(port_file)
    except FileNotFoundError:
        pass
    cmd = [
        sys.executable, "-m", "oni_ml_tpu.runner.ml_ops", "replica",
        "--id", rid, "--platform", platform, "--port-file", port_file,
    ] + kv_flags + (extra or [])
    env = dict(os.environ)
    # The --platform flag decides; an inherited pin must not fight it.
    env.pop("JAX_PLATFORMS", None)
    # The child must import THIS checkout's package wherever the
    # router was launched from (the repo is run in place, not
    # installed).
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    # The child's stdout must not interleave with the router's (a
    # bench phase's stdout is a JSON contract); the port file is the
    # readiness handshake, so the log file is purely diagnostic.
    log_path = os.path.join(workdir, f"{rid}.log")
    log = open(log_path, "ab")
    try:
        proc = subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
    finally:
        log.close()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            with open(log_path, "rb") as f:
                tail = f.read()[-600:].decode("utf-8", "replace")
            raise RuntimeError(
                f"replica {rid} (platform {platform}) exited "
                f"rc={proc.returncode} before listening: {tail.strip()}"
            )
        try:
            with open(port_file) as f:
                host, port = f.read().split()
            return proc, host, int(port)
        except (FileNotFoundError, ValueError):
            time.sleep(0.05)
    proc.kill()
    raise RuntimeError(f"replica {rid} never wrote {port_file}")


class _FlagCollector:
    """FIFO future resolver for the stream front: resolves routed
    futures in submit order and writes flagged events (score under the
    tenant threshold) to stdout in the fleet framing."""

    def __init__(self, thresholds: dict, out) -> None:
        self._thresholds = thresholds
        self._out = out
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._stopped = False
        self.resolved = 0
        self.errors = 0
        self.flagged = 0
        self._thread = threading.Thread(
            target=self._run, name="oni-route-flags", daemon=True)
        self._thread.start()

    def add(self, tenant: str, line: str, future) -> None:
        with self._cond:
            self._queue.append((tenant, line, future))
            self._cond.notify_all()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stopped:
                    self._cond.wait()
                if not self._queue:
                    return
                tenant, line, fut = self._queue.popleft()
            try:
                score, _ = fut.result(timeout=300.0)
            except Exception:
                with self._cond:
                    self.errors += 1
                continue
            with self._cond:
                self.resolved += 1
                flag = score < self._thresholds.get(tenant, 0.0)
                if flag:
                    self.flagged += 1
            if flag:
                self._out.write(f"{tenant}\t{score:.6e}\t{line}\n")
                self._out.flush()

    def close(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=300.0)


def _rolling_redeploy(router, procs: dict, kv_flags, workdir: str,
                      extra: "list[str]", platform: str) -> "list[dict]":
    """Drain-one-respawn-one over every spawned replica: the fleet
    keeps serving throughout (the router promotes each drained
    replica's tenants to their warm shadows, then the placement pulls
    them back when the replacement joins under the same id slot)."""
    out = []
    for rid in sorted(procs):
        drained = router.drain_replica(rid)
        proc = procs.pop(rid)
        proc.terminate()
        proc.wait(timeout=60.0)
        new_id = f"{rid}v2"
        proc2, host, port = _spawn_replica(
            new_id, kv_flags, workdir, extra, platform=platform)
        procs[new_id] = proc2
        joined = router.join_replica(new_id, host, port)
        out.append({"drained": drained, "joined": joined})
    return out


def route_stream(args) -> int:
    from ..config import ServingConfig
    from ..serving import FleetRouter, ModelRegistry, load_manifest
    from ..serving.router import ReplicaLink  # noqa: F401  (re-export)
    from .serve import _load_featurizer

    if not args.fleet:
        print("route: --fleet MANIFEST is required for stream mode",
              file=sys.stderr)
        return 2
    specs = load_manifest(args.fleet)
    cfg = ServingConfig()
    workdir = tempfile.mkdtemp(prefix="oni_route_")
    kv_server = None
    if args.kv_listen:
        from ..parallel.membership import KVServer, TcpKVClient

        lhost, _, lport = args.kv_listen.partition(":")
        kv_server = KVServer(lhost or "127.0.0.1",
                             int(lport) if lport else 0)
        print(f"KV_LISTEN {kv_server.host} {kv_server.port}",
              file=sys.stderr, flush=True)
        kv = TcpKVClient(kv_server.host, kv_server.port)
        kv_flags = ["--kv-connect",
                    f"{kv_server.host}:{kv_server.port}"]
    elif args.kv_connect:
        from ..parallel.membership import TcpKVClient

        chost, _, cport = args.kv_connect.partition(":")
        kv = TcpKVClient(chost or "127.0.0.1", int(cport))
        kv_flags = ["--kv-connect", args.kv_connect]
    else:
        from ..parallel.membership import FileKVClient

        kv_dir = args.kv_dir or os.path.join(workdir, "kv")
        kv = FileKVClient(kv_dir)
        kv_flags = ["--kv-dir", kv_dir]
    procs: dict = {}
    extra: "list[str]" = []
    router = FleetRouter(cfg, kv=kv)
    scaler = None
    try:
        if (args.replicas or args.autoscale) \
                and not args.replica_platform:
            print("route: spawning replicas needs --replica-platform "
                  "(cpu or tpu)", file=sys.stderr)
            return 2
        if args.replicas:
            for i in range(args.replicas):
                rid = f"r{i}"
                proc, host, port = _spawn_replica(
                    rid, kv_flags, workdir, extra,
                    platform=args.replica_platform)
                procs[rid] = proc
                router.connect_replica(rid, host, port)
        elif args.connect:
            for part in args.connect.split(","):
                rid, _, addr = part.strip().partition("=")
                host, _, port = addr.partition(":")
                router.connect_replica(rid, host, int(port))
        else:
            print("route: need --replicas N or --connect",
                  file=sys.stderr)
            return 2
        thresholds: dict = {}
        sc_threshold = (args.threshold if args.threshold is not None
                        else cfg.threshold)
        from ..config import ScoringConfig as SC

        for spec in specs:
            if not spec.day_dir:
                raise SystemExit(
                    f"tenant {spec.tenant!r} has no day_dir")
            from ..sources import get as get_source

            fallback = get_source(spec.dsource).fallback(SC())
            snap = ModelRegistry().load_day(spec.day_dir, fallback)
            fz = _load_featurizer(spec.day_dir, args.top_domains)
            router.add_tenant(spec, (), snap.model, featurizer=fz)
            thresholds[spec.tenant] = (
                spec.threshold if spec.threshold is not None
                else sc_threshold)
        router.start()
        if args.autoscale:
            from ..serving.autoscale import AutoScaler

            spawn_seq = [len(procs)]

            def _as_spawn():
                rid = f"as{spawn_seq[0]}"
                spawn_seq[0] += 1
                proc, host, port = _spawn_replica(
                    rid, kv_flags, workdir, extra,
                    platform=args.replica_platform)
                procs[rid] = proc
                return rid, host, port

            def _as_stop(rid):
                proc = procs.pop(rid, None)
                if proc is not None:
                    proc.terminate()

            scaler = AutoScaler(router, spawn=_as_spawn,
                                stop=_as_stop, config=cfg)
            scaler.start()
        collector = _FlagCollector(thresholds, sys.stdout)
        routed = skipped = 0
        redeploys: "list[dict]" = []
        for line in sys.stdin:
            line = line.rstrip("\n")
            if not line:
                continue
            tenant, sep, payload = line.partition("\t")
            if not sep:
                skipped += 1
                continue
            try:
                fut = router.submit(tenant, payload.split(","))
            except (KeyError, ValueError, RuntimeError):
                skipped += 1
                continue
            collector.add(tenant, payload, fut)
            routed += 1
            if (args.redeploy_after and procs
                    and routed == args.redeploy_after):
                redeploys = _rolling_redeploy(
                    router, procs, kv_flags, workdir, extra,
                    args.replica_platform)
        router.flush()
        collector.close()
        summary = {
            "route": "ok",
            "routed": routed,
            "skipped": skipped,
            "resolved": collector.resolved,
            "errors": collector.errors,
            "flagged": collector.flagged,
            "redeploys": len(redeploys),
            "stats": router.stats(),
        }
        if scaler is not None:
            summary["autoscale"] = [
                d for d in scaler.decisions if d["action"] != "hold"]
        print(json.dumps(summary), file=sys.stderr, flush=True)
        return 0 if collector.errors == 0 else 1
    finally:
        if scaler is not None:
            scaler.close()
        router.close()
        if kv_server is not None:
            kv_server.close()
        for proc in procs.values():
            proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                proc.kill()
        import shutil

        # The workdir (port files, replica logs, the default kv dir)
        # is ours; a long-running service front must not leave one
        # oni_route_* directory per restart in the tempdir.
        shutil.rmtree(workdir, ignore_errors=True)


def _parse_dry_run(spec: str) -> "tuple[int, int]":
    """``synthetic`` or ``synthetic:TxR`` -> (tenants, replicas)."""
    if not spec.startswith("synthetic"):
        raise SystemExit(
            f"--dry-run wants synthetic[:TxR], got {spec!r}")
    _, _, dims = spec.partition(":")
    if not dims:
        return 6, 3
    t, _, r = dims.partition("x")
    return max(2, int(t)), max(2, int(r))


def dry_run(args) -> int:
    """The acceptance path, runnable anywhere: T synthetic tenants
    placed over R in-process replicas; scores must match the
    single-process oracle bit-for-bit, a mid-stream replica kill must
    drop zero events (shadow promotion + admission-journal replay),
    and a rolling drain+join must keep every surviving future
    resolvable."""
    from ..config import ServingConfig
    from ..serving import (
        DnsEventFeaturizer,
        FleetRouter,
        ReplicaServer,
        TenantSpec,
        score_features,
    )
    from .serve import _synthetic_day

    n_tenants, n_replicas = _parse_dry_run(args.dry_run)
    cfg = ServingConfig(fleet_max_batch=32, fleet_max_wait_ms=5.0,
                        device_score_min=None)
    replicas = {
        f"r{i}": ReplicaServer(f"r{i}", cfg) for i in range(n_replicas)
    }
    router = FleetRouter(cfg)
    days = {}
    try:
        for rid, rep in replicas.items():
            router.connect_replica(rid, rep.host, rep.port)
        for i in range(n_tenants):
            t = f"t{i}"
            days[t] = _synthetic_day(n_events=48, seed=100 + i)
            rows, model, cuts = days[t]
            router.add_tenant(
                TenantSpec(tenant=t, dsource="dns"), cuts, model)
        router.start()
        placement = router.placement()

        def replay(rows_per_tenant: int):
            futs = {
                t: [router.submit(t, r)
                    for r in days[t][0][:rows_per_tenant]]
                for t in days
            }
            router.flush()
            ok, dropped = True, 0
            for t, fs in futs.items():
                rows, model, cuts = days[t]
                feats = DnsEventFeaturizer(cuts)(
                    rows[:rows_per_tenant])
                oracle = score_features(model, feats, "dns")
                try:
                    got = np.array(
                        [f.result(timeout=60.0)[0] for f in fs])
                except Exception:
                    dropped += 1
                    ok = False
                    continue
                if not np.array_equal(got, oracle):
                    ok = False
            return ok, dropped

        parity_ok, dropped0 = replay(24)
        # Chaos: kill the replica that primaries t0 with events in
        # flight; every future must still resolve (shadow promotion +
        # admission-journal replay), and survivors stay bit-identical.
        victim = placement["t0"].primary
        futs = {t: [router.submit(t, r) for r in days[t][0][24:44]]
                for t in days}
        replicas[victim].kill()
        router.flush()
        chaos_dropped = 0
        for t, fs in futs.items():
            for f in fs:
                try:
                    f.result(timeout=60.0)
                except Exception:
                    chaos_dropped += 1
        post_ok, dropped1 = replay(16)
        failovers = router.stats()["failovers"]
        # Rolling redeploy over the survivors: join a fresh replica,
        # then drain one — the fleet serves throughout.
        spare = ReplicaServer("rx", cfg)
        replicas["rx"] = spare
        router.join_replica("rx", spare.host, spare.port)
        drain_target = next(
            r for r in sorted(replicas) if r != victim and r != "rx"
            and replicas[r] is not None
        )
        drained = router.drain_replica(drain_target)
        redeploy_ok, dropped2 = replay(12)
        ok = (
            parity_ok and post_ok and redeploy_ok
            and chaos_dropped == 0
            and dropped0 == dropped1 == dropped2 == 0
            and len(failovers) >= 1
            and drained["drained"]
        )
        summary = {
            "route_dry_run": "ok" if ok else "FAILED",
            "tenants": n_tenants,
            "replicas": n_replicas,
            "parity": parity_ok,
            "killed": victim,
            "chaos_dropped": chaos_dropped,
            "post_failover_parity": post_ok,
            "failovers": failovers,
            "redeploy": {"drained": drained,
                         "parity": redeploy_ok},
        }
        print(json.dumps(summary), flush=True)
        return 0 if ok else 1
    finally:
        router.close()
        for rep in replicas.values():
            rep.stop()


def route_main(argv: "list[str] | None" = None) -> int:
    args = build_route_parser().parse_args(argv)
    if args.dry_run:
        return dry_run(args)
    return route_stream(args)
