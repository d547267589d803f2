"""One serve replica of the replicated elastic fleet.

A *replica* is the unit of blast radius: a full single-process serving
stack (FleetRegistry -> dynamic FleetScorer, the PR 10/12 machinery
unchanged) behind a small framed socket protocol, plus a KV heartbeat
(parallel/membership.py) so the router can tell a stuck replica from a
slow one.  N replicas on one or several hosts each run their own
Python process, their own JAX backend, their own compiled-program
family — a dead backend (heartbeat -> BackendLost) now kills ONE
replica's tenants for the promotion window instead of the whole fleet
(ROADMAP item 5).

Wire protocol (router <-> replica): length-prefixed **columnar**
frames over TCP (serving/wire.py — typed arrays as raw buffers with
dtype/shape descriptors, zero-copy numpy decode; pickle only as the
negotiated fallback one release back).  Every request carries an
``id``; every response echoes it.  A ``hello`` op negotiates the codec
per link and, for same-host peers, upgrades the data path to a
shared-memory ring pair (wire.ShmRing) so local hops never touch the
TCP stack — the socket stays open purely as the liveness/EOF signal.
Control ops (add_tenant / publish / warmup / stats / drain / shutdown
/ ping) answer synchronously from the connection's reader thread.
``submit`` is ASYNC: the reader enqueues the event into the tenant's
admission lane and a per-connection FIFO resolver thread streams
``{"id", "score", "version"}`` responses back as the micro-batch
flushes resolve them — the router's scatter/gather never blocks on a
slow flush, and admission backpressure propagates naturally (a full
lane blocks the reader, the socket buffer fills, the router's send
blocks: the dataplane-channel semantics, across a process boundary).

Warm standby contract: the router places every tenant on a primary AND
a shadow replica; both receive ``add_tenant``/``publish`` fan-outs, so
the shadow holds the same model bytes and — because the compiled
family is keyed by the stacked SHAPE, which `warmup` AOT-compiles
through the shared plans/compilation-cache machinery — promotion needs
zero re-sweeps and zero retraces: the shadow already owns the program
family its new traffic dispatches.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from collections import deque

from ..config import ServingConfig
from . import wire
from .fleet import FleetRegistry, FleetScorer
from .tenants import TenantSpec

# Framing lives in serving/wire.py since the columnar wire landed;
# re-exported here because this module IS the protocol endpoint and
# existing callers/tests import the frame helpers from it.
MAX_FRAME_BYTES = wire.MAX_FRAME_BYTES
send_frame = wire.send_frame
recv_frame = wire.recv_frame
_recv_exact = wire._recv_exact


def featurizer_for(dsource: str, cuts: tuple):
    from ..sources import get as get_source

    return get_source(dsource).event_featurizer(cuts)


class _Resolver:
    """Per-connection FIFO response streamer: submits append (id,
    future); this thread resolves them in submit order and writes the
    response frames.  FIFO matches flush-resolution order closely
    enough that head-of-line waiting costs microseconds, and it keeps
    the response path single-writer per purpose (control responses
    share the socket under the same write lock).  `send_fn` abstracts
    the response transport — a framed socket write for TCP
    connections, a ring push for same-host shm links; the resolver
    just streams batches."""

    # Periodic liveness poll while blocked on an unresolved future, so
    # a shutdown/kill never strands the thread on .result(None).
    _WAIT_SLICE_S = 0.25

    def __init__(self, send_fn) -> None:
        self._send = send_fn
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._stopped = False
        self._thread = threading.Thread(
            target=self._run, name="oni-replica-resolver", daemon=True)
        self._thread.start()

    def enqueue(self, rid: int, future) -> None:
        with self._cond:
            if self._stopped:
                raise RuntimeError("resolver stopped")
            self._queue.append((rid, future))
            self._cond.notify_all()

    def pending(self) -> int:
        with self._cond:
            return len(self._queue)

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=5.0)

    # Batched-response bound: one coalesced frame never carries more
    # than this many scores (bounds frame size and head-of-line delay
    # on the router's demux loop).
    _MAX_BATCH_RSP = 512

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stopped:
                    self._cond.wait()
                if not self._queue and self._stopped:
                    return
                rid, fut = self._queue.popleft()
            rsp = {"id": rid}
            while True:
                try:
                    score, version = fut.result(
                        timeout=self._WAIT_SLICE_S)
                    rsp["score"] = score
                    rsp["version"] = version
                    break
                except TimeoutError:
                    with self._cond:
                        if self._stopped:
                            return
                    continue
                except Exception as e:
                    rsp["error"] = repr(e)[:300]
                    break
            # Coalesce every ALREADY-resolved follower into the same
            # frame: a flush resolves a whole micro-batch at once, so
            # the head's wait usually pays for the batch — per-score
            # pickle+syscall overhead amortizes exactly like the
            # router's submit_many on the way in.
            batch = [rsp]
            with self._cond:
                while (self._queue and len(batch) < self._MAX_BATCH_RSP
                       and self._queue[0][1].done()):
                    nrid, nfut = self._queue.popleft()
                    nrsp = {"id": nrid}
                    try:
                        score, version = nfut.result(timeout=0)
                        nrsp["score"] = score
                        nrsp["version"] = version
                    except Exception as e:
                        nrsp["error"] = repr(e)[:300]
                    batch.append(nrsp)
            try:
                self._send(batch if len(batch) > 1 else rsp)
            except OSError:
                return  # connection gone; reader thread handles it


class ReplicaServer:
    """One replica process's serving stack + protocol endpoint.

    `kv` (optional) is any membership KV client
    (parallel/membership.py): the replica registers itself with its
    host/port and publishes heartbeats every
    ``config.replica_heartbeat_s`` carrying live queue/scored counters,
    so the router's monitor reads load and liveness without extra
    RPCs."""

    def __init__(self, replica_id: str,
                 config: "ServingConfig | None" = None, *,
                 host: str = "127.0.0.1", port: int = 0,
                 kv=None, membership_ns: str = "oni/fleet",
                 metrics=None, journal=None,
                 health_check=None) -> None:
        self.replica_id = replica_id
        self.config = config or ServingConfig()
        # Optional backend-liveness probe (e.g. a bound
        # telemetry/heartbeat.HeartbeatMonitor.check): raising marks
        # this replica STUCK — fail key posted, heartbeats stop.
        self._health_check = health_check
        self._journal = getattr(journal, "journal", journal)
        self.fleet = FleetRegistry(journal=journal)
        self.scorer = FleetScorer(
            self.fleet, {}, self.config, metrics=metrics,
            journal=journal, dynamic=True,
        )
        self._lock = threading.Lock()
        self._closed = False
        self._draining = False
        # Set once the server has stopped (graceful or kill) — what a
        # CLI main blocks on.
        self.stopped = threading.Event()
        self._conns: "list[socket.socket]" = []
        self._resolvers: "list[_Resolver]" = []
        self._rings: "list" = []
        self._cuts: dict = {}
        self._router_versions: dict = {}
        self._srv = socket.create_server((host, port))
        self.host, self.port = self._srv.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"oni-replica-{replica_id}", daemon=True)
        self._accept_thread.start()
        self._membership = None
        self._heartbeat = None
        if kv is not None:
            from ..parallel.membership import (
                HeartbeatPublisher,
                MembershipClient,
            )

            self._membership = MembershipClient(kv, membership_ns)
            self._membership.register(
                replica_id,
                {"host": self.host, "port": self.port,
                 "pid": os.getpid()},
            )
            self._heartbeat = HeartbeatPublisher(
                self._membership, replica_id,
                self.config.replica_heartbeat_s,
                payload_fn=self._hb_payload,
            )

    # -- accept / per-connection loops --------------------------------------

    def _hb_payload(self) -> dict:
        """Heartbeat payload doubling as the stuck-replica detector: a
        heartbeat is only worth sending if the scoring stack behind it
        is actually alive.  A dead scorer worker, or a failing
        `health_check` (e.g. telemetry/heartbeat.HeartbeatMonitor's
        check() raising BackendLost — the dead-backend mode), posts
        the membership FAIL KEY and stops the beat: the router's
        monitor promotes this replica's shadows within one poll
        instead of trusting a liveness signal decoupled from
        scoring."""
        reason = None
        if not self.scorer._worker.is_alive():
            reason = "fleet scorer worker died"
        elif self._health_check is not None:
            try:
                self._health_check()
            except Exception as e:
                reason = f"health check failed: {e!r}"
        if reason is not None:
            if self._membership is not None:
                self._membership.fail(self.replica_id, reason)
            raise RuntimeError(reason)   # stops the publisher loop
        return {
            "events_scored": self.scorer.events_scored,
            "draining": self._draining,
        }

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return      # server socket closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                if self._closed:
                    conn.close()
                    return
                self._conns.append(conn)
            threading.Thread(
                target=self._serve_conn, args=(conn,),
                name=f"oni-replica-{self.replica_id}-conn",
                daemon=True,
            ).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        wlock = threading.Lock()
        # Per-connection wire state.  `accept` is what a NON-columnar
        # frame may decode as: "pickle" only when this side's config
        # forces the fallback or the hello negotiation settled on it —
        # never because a frame merely failed the magic check.
        # `reply` mirrors the codec of the last request, so a
        # negotiated-fallback peer is answered in the codec it can
        # actually read.  `rings` are the shm pair (if any) this
        # connection's hello negotiated — their lifecycle is tied to
        # the connection, torn down in the finally below.
        initial = ("pickle" if self.config.wire_format == "pickle"
                   else "columnar")
        state = {"accept": initial, "reply": initial, "rings": []}

        def reply(obj) -> int:
            return wire.send_frame(conn, obj, wlock,
                                   codec=state["reply"])

        resolver = _Resolver(reply)
        with self._lock:
            self._resolvers.append(resolver)
        try:
            while True:
                try:
                    req, state["reply"] = wire.recv_frame_tagged(
                        conn, codec=state["accept"])
                except (ConnectionError, OSError):
                    return
                op = req.get("op")
                rid = req.get("id")
                if op == "submit":
                    try:
                        fut = self.scorer.submit(
                            req["tenant"], req["raw"])
                        resolver.enqueue(rid, fut)
                    except Exception as e:
                        try:
                            reply({"id": rid, "error": repr(e)[:300]})
                        except OSError:
                            return
                    continue
                if op == "submit_many":
                    tenant = req["tenant"]
                    errors = []
                    for eid, raw in zip(req["ids"], req["raws"]):
                        try:
                            fut = self.scorer.submit(tenant, raw)
                            resolver.enqueue(eid, fut)
                        except Exception as e:
                            errors.append(
                                {"id": eid, "error": repr(e)[:300]})
                    if errors:
                        try:
                            reply(errors)
                        except OSError:
                            return
                    continue
                try:
                    rsp = {"id": rid, **self._handle(op, req, state)}
                except Exception as e:
                    rsp = {"id": rid, "error": repr(e)[:300]}
                try:
                    reply(rsp)
                except OSError:
                    return
                if op == "shutdown":
                    self.stop()
                    return
        finally:
            resolver.stop()
            # Ring lifecycle = connection lifecycle: a SIGKILL'd or
            # reconnecting router EOFs this socket, and the rings its
            # hello negotiated close (and unlink) here instead of
            # accumulating shm segments + polling threads until full
            # replica shutdown.
            self._drop_rings(state["rings"])
            try:
                conn.close()
            except OSError:
                pass

    # -- op handlers ---------------------------------------------------------

    def _handle(self, op: str, req: dict,
                state: "dict | None" = None) -> dict:
        if op == "ping":
            return {"ok": True, "replica": self.replica_id}
        if op == "hello":
            return self._op_hello(req, state)
        if op == "add_tenant":
            return self._op_add_tenant(req)
        if op == "publish":
            snap = self.fleet.publish(
                req["tenant"], req["model"],
                req.get("source", "router"))
            if "router_version" in req:
                with self._lock:
                    self._router_versions[req["tenant"]] = int(
                        req["router_version"])
            return {"ok": True, "version": snap.version}
        if op == "flush":
            self.scorer.flush()
            return {"ok": True}
        if op == "warmup":
            return {"ok": True, "warmup": self._op_warmup()}
        if op == "stats":
            return self._op_stats()
        if op == "drain":
            return self._op_drain(
                float(req.get("timeout_s",
                              self.config.route_op_timeout_s)))
        if op == "shutdown":
            return {"ok": True}
        raise ValueError(f"unknown op {op!r}")

    def _op_hello(self, req: dict,
                  state: "dict | None" = None) -> dict:
        """Wire negotiation: pick the frame codec for this link from
        the peer's offer (our own ``wire_format`` config can force the
        one-release pickle fallback), and for a same-host peer that
        asked, stand up a shared-memory ring pair so data frames skip
        the TCP stack entirely.  The response names the rings; the
        caller attaches and the TCP data socket degrades to a
        liveness/EOF signal + oversize-frame escape.

        Acceptance gate: settling on "pickle" arms the unpickler for
        this connection's future frames, so a peer only gets it when
        this replica actually accepts the fallback
        (``wire_accept_pickle``, or our own ``wire_format`` already
        forces it).  Otherwise a pickle-only offer is an error, not a
        silent downgrade."""
        offered = req.get("wire") or ["pickle"]
        chosen = ("pickle"
                  if (self.config.wire_format == "pickle"
                      or "columnar" not in offered)
                  else "columnar")
        if chosen == "pickle" and not (
                self.config.wire_accept_pickle
                or self.config.wire_format == "pickle"):
            raise ValueError(
                "peer offered only the pickle fallback, which this "
                "replica refuses (wire_accept_pickle=False)")
        if state is not None:
            state["accept"] = chosen
        shm = None
        if (chosen == "columnar" and req.get("shm")
                and self.config.wire_shm
                and req.get("host") == socket.gethostname()):
            try:
                shm = self._make_rings(state)
            except Exception:
                shm = None    # ring setup must never break the link
        return {"ok": True, "wire": chosen, "shm": shm}

    def _make_rings(self, state: "dict | None" = None) -> dict:
        # A repeated hello on the same connection replaces its rings:
        # drop the stale pair first so reconnect-negotiate loops can't
        # accumulate segments behind one socket.
        if state is not None and state["rings"]:
            self._drop_rings(state["rings"])
            state["rings"] = []
        slab = int(self.config.wire_shm_slab_bytes)
        c2s = wire.ShmRing.create(slab)     # router -> replica submits
        s2c = wire.ShmRing.create(slab)     # replica -> router scores
        with self._lock:
            if self._closed:
                c2s.close()
                s2c.close()
                raise RuntimeError("replica closed")
            self._rings += [c2s, s2c]
        if state is not None:
            state["rings"] = [c2s, s2c]
        threading.Thread(
            target=self._serve_ring, args=(c2s, s2c),
            name=f"oni-replica-{self.replica_id}-ring", daemon=True,
        ).start()
        return {"c2s": c2s.name, "s2c": s2c.name, "slab": slab}

    def _drop_rings(self, rings: list) -> None:
        """Close a connection's negotiated rings and forget them:
        close() flips the shared closed flag (the _serve_ring poller
        exits on its next timeslice) and, on the owning side, unlinks
        the segments — reclaimed now, not at process exit."""
        if not rings:
            return
        for r in rings:
            r.close()
        with self._lock:
            self._rings = [r for r in self._rings if r not in rings]

    def _serve_ring(self, c2s: "wire.ShmRing",
                    s2c: "wire.ShmRing") -> None:
        """Data-path twin of _serve_conn over a ring pair: pop submit
        frames, stream score batches back.  Control ops stay on the
        TCP ctrl connection; a ring frame carrying one is answered
        with an error instead of silently absorbed."""

        def reply(obj) -> int:
            payload = wire.encode_payload(obj)
            if not s2c.push(payload,
                            timeout_s=self.config.route_op_timeout_s):
                raise BrokenPipeError("response ring closed")
            return len(payload)

        resolver = _Resolver(reply)
        with self._lock:
            self._resolvers.append(resolver)
        try:
            while True:
                payload = c2s.pop(0.25)
                if payload is None:
                    if c2s.closed or self._closed:
                        return
                    continue
                try:
                    req = wire.decode_payload(payload)
                except ConnectionError:
                    return
                op = req.get("op")
                rid = req.get("id")
                try:
                    if op == "submit":
                        fut = self.scorer.submit(
                            req["tenant"], req["raw"])
                        resolver.enqueue(rid, fut)
                    elif op == "submit_many":
                        tenant = req["tenant"]
                        for eid, raw in zip(req["ids"], req["raws"]):
                            try:
                                fut = self.scorer.submit(tenant, raw)
                                resolver.enqueue(eid, fut)
                            except Exception as e:
                                reply([{"id": eid,
                                        "error": repr(e)[:300]}])
                    else:
                        reply({"id": rid,
                               "error": f"op {op!r} is control-path "
                                        "only; rings carry data frames"})
                except OSError:
                    return
                except Exception as e:
                    try:
                        reply({"id": rid, "error": repr(e)[:300]})
                    except OSError:
                        return
        finally:
            resolver.stop()
            c2s.close()
            s2c.close()

    def _op_add_tenant(self, req: dict) -> dict:
        """Idempotent placement push: first call registers the tenant,
        publishes its model, and opens its admission lane; a repeat
        (failover re-push, shadow backfill after the model already
        landed) republishes only when the router's version moved."""
        spec = TenantSpec(**req["spec"])
        known = spec.tenant in self.fleet.tenants()
        if not known:
            self.fleet.add_tenant(spec)
        # The replica-local registry version counts THIS replica's own
        # publishes; the router's monotonically-growing router_version
        # decides whether this push carries news (a failover re-push of
        # a model the shadow already holds must not churn the stack).
        want = int(req.get("router_version", 1))
        with self._lock:
            self._cuts[spec.tenant] = req["cuts"]
            have = self._router_versions.get(spec.tenant, 0)
            fresh = not known or have < want
        published = False
        if fresh:
            self.fleet.publish(spec.tenant, req["model"],
                               req.get("source", "router"))
            published = True
            # Recorded only AFTER the publish lands: a failed first
            # publish must leave the version unclaimed, so the
            # router's idempotent re-push actually re-publishes
            # instead of skipping forever.
            with self._lock:
                self._router_versions[spec.tenant] = want
        if spec.tenant not in self.scorer._lanes:
            # A prebuilt featurizer (day-dir loaded, with its own
            # top-domains table) wins over cuts-only construction.
            fz = req.get("featurizer") or featurizer_for(
                spec.dsource, req["cuts"])
            self.scorer.add_tenant(spec, fz)
        return {"ok": True, "published": published,
                "version": self.fleet.version(spec.tenant)}

    def _op_warmup(self):
        """AOT-warm the stacked program family for every pack group
        this replica hosts (plans/warmup.warmup_serving — the same
        shapes `ml_ops serve --fleet` warms), so a shadow's first
        post-promotion flush dispatches an already-compiled program."""
        from ..plans import warmup as plans_warmup

        try:
            out = []
            ks = sorted({
                self.fleet.tenant_k(t) for t in self.fleet.tenants()
            })
            from ..sources import get as get_source

            for k in ks:
                stack = self.fleet.stack(k)
                mult = max(
                    get_source(self.fleet.spec(t).dsource).pairs_per_event
                    for t in stack.tenants
                )
                out.append({
                    "k": k, "tenants": len(stack.tenants),
                    **plans_warmup.warmup_serving(
                        stack.model.theta.shape[0],
                        stack.model.p.shape[0], k,
                        self.scorer.max_batch * mult,
                        self.config.device_score_min,
                    ),
                })
            return out
        except Exception as e:   # warmup must never block serving
            return {"error": repr(e)[:200]}

    def _op_stats(self) -> dict:
        from ..plans import warmup as plans_warmup

        return {
            "replica": self.replica_id,
            "pid": os.getpid(),
            "events_scored": self.scorer.events_scored,
            "batches_flushed": self.scorer.batches_flushed,
            "tenants": sorted(self.fleet.tenants()),
            "pending": self._pending_events(),
            "draining": self._draining,
            "compile": plans_warmup.compile_counts(),
        }

    def _pending_events(self) -> int:
        with self.scorer._cond:
            return sum(
                len(l.pending) for l in self.scorer._lanes.values()
            )

    def _op_drain(self, timeout_s: float) -> dict:
        """Rolling-redeploy step: flush and wait until every admitted
        event has resolved AND its response frame is queued out —
        after the reply, the router may stop routing here and tear the
        process down with nothing in flight."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + timeout_s
        self.scorer.flush()
        while time.monotonic() < deadline:
            with self._lock:
                resolvers = list(self._resolvers)
            if (self._pending_events() == 0
                    and all(r.pending() == 0 for r in resolvers)):
                return {"ok": True, "drained": True,
                        "events_scored": self.scorer.events_scored}
            self.scorer.flush()
            time.sleep(0.005)
        return {"ok": False, "drained": False,
                "pending": self._pending_events()}

    # -- lifecycle -----------------------------------------------------------

    def stop(self) -> None:
        """Graceful stop: deregister, stop heartbeats, close the
        scorer (draining queued events), close sockets."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            conns = list(self._conns)
            rings = list(self._rings)
        if self._heartbeat is not None:
            self._heartbeat.stop()
        if self._membership is not None:
            try:
                self._membership.deregister(self.replica_id)
            except Exception:
                pass
        try:
            self._srv.close()
        except OSError:
            pass
        self.scorer.close(timeout=self.config.route_op_timeout_s)
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        for r in rings:
            r.close()
        self.stopped.set()

    def kill(self) -> None:
        """Abrupt death for chaos tests: close every socket NOW, skip
        the drain, leave queued futures unresolved — what SIGKILL does
        to a replica process, minus the process.  In-flight events are
        exactly what the router's admission journal must replay."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            conns = list(self._conns)
            rings = list(self._rings)
        if self._heartbeat is not None:
            self._heartbeat.stop()
        try:
            self._srv.close()
        except OSError:
            pass
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        for r in rings:
            r.close()
        self.stopped.set()
