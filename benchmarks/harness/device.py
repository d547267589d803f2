"""The device stamp and the table of peaks.

A measurement path that finds no chip fails; it never falls back to the
CPU.  A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by jax's `device_kind`.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,     # bf16 MXU
        "bytes_per_s": 819e9,      # HBM
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


class NoChip(RuntimeError):
    """The machine cannot run this cell: exit non-zero, print no result."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise NoChip(
            f"device_kind {kind!r} is not in the benchmark's table of peaks "
            f"({sorted(PEAKS)}): add it with its source, never a default")
    return PEAKS[kind]


def stamp(chips: int) -> dict:
    """What jax reports, or NoChip unless it is `chips` TPU devices (at
    least) of a kind the table knows.  The cell uses the first `chips`."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"jax found no backend: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"jax.devices()[0].platform is {platform!r}, not 'tpu': "
                     "the benchmark measures the chip and nothing else")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax has {len(devices)}")
    kind = devices[0].device_kind
    peaks_for(kind)
    return {"platform": platform, "kind": kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
