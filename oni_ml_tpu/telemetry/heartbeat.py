"""Background device-liveness prober: dead backends become a clean
`BackendLost`, not a hang or a null record.

When a device call stops answering, nothing in the process notices
until an outer timeout ends everything.  The monitor probes the backend
on a cadence with a tiny jitted add + host transfer (the smallest
possible full round trip: dispatch, compute, D2H), run on a worker
thread so a hung runtime cannot hang the monitor itself.  The probe is
IN-PROCESS only: the process that runs the pipeline holds the chip, and
a second process asking for it fails while the first is healthy — so
nothing here ever starts one.  `max_misses` consecutive missed probes
declare the backend lost.

On loss the monitor journals a `backend_lost` record (crash-safe —
post-mortems see when liveness ended, even if the process then hung),
fires `on_lost`, and every later `check()` raises `BackendLost`, which
the pipeline runner surfaces as a clean failure at the next stage
boundary instead of entering another device call that would hang.

The monitor cannot interrupt a device call already in flight — Python
cannot interrupt a blocked C extension — so its guarantees are: the
loss is detected and journaled promptly, and no NEW device work is
entered after detection.  Bounding the in-flight call remains the job
of process-level timeouts.
"""

from __future__ import annotations

import threading

from .spans import now_ns


class BackendLost(RuntimeError):
    """The device backend stopped answering liveness probes."""


# One cached jitted probe fn per process (compiled lazily on first use).
_PROBE_FN = None
_PROBE_LOCK = threading.Lock()


def _probe_fn():
    global _PROBE_FN
    with _PROBE_LOCK:
        if _PROBE_FN is None:
            import jax

            _PROBE_FN = jax.jit(lambda x: x + 1)
        return _PROBE_FN


def device_add_probe(timeout_s: float = 30.0) -> "float | None":
    """One liveness round trip: jitted add + scalar D2H on a worker
    thread.  Returns the latency in seconds, or None when the call
    hung past `timeout_s` or raised (the worker thread is daemonic
    and abandoned — a hung device call cannot be cancelled)."""
    result: dict = {}

    def work():
        try:
            import jax.numpy as jnp

            t0 = now_ns()
            out = float(_probe_fn()(jnp.asarray(1.0)))
            if out == 2.0:
                result["latency_s"] = (now_ns() - t0) / 1e9
        except Exception as e:  # backend init/dispatch failure = miss
            result["error"] = repr(e)[:200]

    t = threading.Thread(target=work, name="oni-heartbeat-probe",
                         daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive() or "latency_s" not in result:
        return None
    return result["latency_s"]


class HeartbeatMonitor:
    """Periodic device-liveness probe with journaled outcomes.
    `probe` is injectable for tests."""

    def __init__(self, interval_s: float = 30.0, timeout_s: float = 60.0,
                 max_misses: int = 2, journal=None,
                 probe=device_add_probe, on_lost=None,
                 recorder=None) -> None:
        if interval_s <= 0:
            raise ValueError(f"interval_s must be > 0, got {interval_s}")
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self.max_misses = max(1, int(max_misses))
        self.journal = journal           # RunJournal (or None)
        self.probe = probe
        self.on_lost = on_lost
        # Probe round-trip times route into the shared registry
        # (`heartbeat.probe_latency_s` histogram, `heartbeat.misses`
        # counter) so backend DEGRADATION — rising probe latency — is
        # visible on the metrics plane before BackendLost ever fires.
        # Bound at construction: the probe loop runs on a worker thread,
        # where the current_recorder contextvar would not propagate.
        from .spans import current_recorder

        self.recorder = recorder if recorder is not None \
            else current_recorder()
        self.lost = threading.Event()
        self.lost_reason: "str | None" = None
        self.beats = 0
        self.misses = 0
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "HeartbeatMonitor":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, name="oni-heartbeat", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        # Never join past one probe timeout: a probe thread hung in a
        # dead backend must not make stop() hang the caller.
        if t is not None:
            t.join(self.timeout_s + 1.0)

    def __enter__(self) -> "HeartbeatMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- the contract ----------------------------------------------------
    def check(self) -> None:
        """Raise BackendLost once the backend has been declared dead —
        what stage boundaries call so no new device work is entered."""
        if self.lost.is_set():
            raise BackendLost(
                self.lost_reason or "device backend stopped answering "
                "liveness probes"
            )

    def beat_once(self) -> bool:
        """One probe cycle (also the test entry point): probe, journal,
        declare the loss on sustained misses.  Returns liveness."""
        latency = self.probe(self.timeout_s)
        self.beats += 1
        if latency is not None:
            self.misses = 0
            if self.recorder is not None:
                self.recorder.histogram(
                    "heartbeat.probe_latency_s"
                ).observe(latency)
            if self.journal is not None:
                self.journal.heartbeat(True, latency_s=round(latency, 6))
            return True
        self.misses += 1
        if self.recorder is not None:
            self.recorder.counter("heartbeat.misses").add(1)
        if self.journal is not None:
            self.journal.heartbeat(
                False, misses=self.misses, timeout_s=self.timeout_s
            )
        if self.misses < self.max_misses:
            return False
        self._declare_lost(
            f"{self.misses} consecutive liveness probes missed "
            f"(timeout {self.timeout_s:.0f}s each)"
        )
        return False

    def _declare_lost(self, reason: str) -> None:
        if self.lost.is_set():
            return
        self.lost_reason = reason
        self.lost.set()
        if self.journal is not None:
            self.journal.backend_lost(reason=reason)
        if self.on_lost is not None:
            try:
                self.on_lost(reason)
            except Exception:
                pass  # observer failure must not mask the loss itself

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            if self.lost.is_set():
                return
            self.beat_once()
